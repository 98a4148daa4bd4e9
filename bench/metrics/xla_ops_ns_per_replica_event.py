"""Device time of every op that is not the event-loop kernel (the draw
precompute, padding and pair packing of ``kernels/event_loop/ops.py``,
and any transfer op), summed over devices, per replica-event swept in
the traced window."""
from bench import devtrace


def read(ctx):
    if not any(ctx.summary["devices"].values()):
        return None
    ns = devtrace.op_ns(ctx.summary, ctx.kernels["event_loop"]["pattern"],
                        match=False)
    return ns / ctx.replica_events
