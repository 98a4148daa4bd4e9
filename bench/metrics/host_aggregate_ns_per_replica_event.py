"""Host time of the program's ``batch.aggregate`` spans (each bucket's
reshapes into ``BatchResult`` objects) per replica-event swept in the
traced window."""
from bench import hostspans


def read(ctx):
    return hostspans.ns_per_replica_event(ctx, hostspans.AGGREGATE)
