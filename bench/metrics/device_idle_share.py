"""Share of the traced window in which no op ran on a device, in %,
averaged over the devices the cell uses: how far the host path (lowering,
operand assembly, transfers, aggregation) holds the chip back."""
from bench import devtrace


def read(ctx):
    win = devtrace.window_ns(ctx.summary)
    ids = [d for d in ctx.device_ids if d in ctx.summary["devices"]]
    if not ids or win <= 0:
        return None
    idle = [1.0 - devtrace.busy_ns(ctx.summary, d) / win for d in ids]
    return 100.0 * sum(idle) / len(idle)
