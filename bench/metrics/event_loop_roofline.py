"""The event-loop kernel's share of its HBM roofline, in %: the least time
the chip's published HBM bandwidth allows for the bytes the kernel must
move (``bench/roofline.py``, from the cell's shapes alone), over the
kernel's device time, both summed over the devices."""
from bench import devtrace


def read(ctx):
    ns = devtrace.op_ns(ctx.summary, ctx.kernels["event_loop"]["pattern"])
    if not ns:
        return None
    least_s = ctx.kernel_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
