"""Share of each device's idle time in the traced window that none of
the program's leaf host spans (lower, assemble, dispatch, fetch,
aggregate) covers, in %, averaged over the cell's devices: the idle time
the spans leave unexplained, such as the harness's own work between
calls."""
from bench import devtrace, hostspans


def read(ctx):
    ids = hostspans.devices(ctx)
    win = devtrace.window_ns(ctx.summary)
    if not hostspans.has_spans(ctx.summary) or not ids or win <= 0:
        return None
    spans = hostspans.intervals(ctx.summary, hostspans.LEAVES)
    shares = []
    for d in ids:
        idle = win - devtrace.busy_ns(ctx.summary, d)
        covered = hostspans.idle_ns(ctx.summary, spans, d)
        shares.append(1.0 - covered / idle if idle > 0 else 0.0)
    return 100.0 * sum(shares) / len(shares)
