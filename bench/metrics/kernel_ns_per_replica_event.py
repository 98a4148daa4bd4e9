"""Event-loop kernel device time per replica-event: the summed duration
of the kernel's device events over every device, divided by the replicas
times events swept in the traced window (a denominator no tile or chunk
choice moves)."""
from bench import devtrace


def read(ctx):
    ns = devtrace.op_ns(ctx.summary, ctx.kernels["event_loop"]["pattern"])
    return ns / ctx.replica_events if ns else None
