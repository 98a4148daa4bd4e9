"""Host time of the program's ``batch.lower`` span (``lower()`` of every
workload and the bucketing, ``repro.workloads.lower``) per replica-event
swept in the traced window."""
from bench import hostspans


def read(ctx):
    return hostspans.ns_per_replica_event(ctx, hostspans.LOWER)
