"""Host time of the program's ``batch.dispatch`` spans (the device put of
a bucket's operands, the VMEM plan and the jitted call up to its return,
in ``batch._exec_bucket``) per replica-event swept in the traced
window."""
from bench import hostspans


def read(ctx):
    return hostspans.ns_per_replica_event(ctx, hostspans.DISPATCH)
