"""Host time of the program's ``batch.assemble`` spans (each bucket's
phase padding and operand arrays in ```batch.sweep```) per replica-event
swept in the traced window."""
from bench import hostspans


def read(ctx):
    return hostspans.ns_per_replica_event(ctx, hostspans.ASSEMBLE)
