"""The device-to-host fetch while the device is idle: the part of the
program's ``batch.fetch`` spans in which a device of the cell runs no op,
averaged over the devices, per replica-event swept in the traced window.
The fetch span holds the wait for the kernel too; the shared clock of
the host spans and the device planes takes that wait out."""
from bench import hostspans


def read(ctx):
    ids = hostspans.devices(ctx)
    if not hostspans.has_spans(ctx.summary) or not ids \
            or not ctx.replica_events:
        return None
    spans = hostspans.intervals(ctx.summary, (hostspans.FETCH,))
    idle = [hostspans.idle_ns(ctx.summary, spans, d) for d in ids]
    return sum(idle) / len(idle) / ctx.replica_events
