"""The program's host spans in a device-trace summary.

``repro.core.batch.sweep`` marks its host stages with profiler spans
(``batch.sweep`` around the call; ``batch.lower``, ``batch.assemble``,
``batch.dispatch``, ``batch.fetch`` and ``batch.aggregate`` inside it).
They reach the summary's ``host`` events (``bench/devtrace.py``) on the
clock of the device planes, so a span can be laid over a device's
timeline. The names are written out here, not imported, so the benchmark
reads a program that lacks the spans: every reading is then None (no
reading), never 0.
"""
from __future__ import annotations

from bench import devtrace

SWEEP = "batch.sweep"
LOWER = "batch.lower"
ASSEMBLE = "batch.assemble"
DISPATCH = "batch.dispatch"
FETCH = "batch.fetch"
AGGREGATE = "batch.aggregate"
#: the stages that partition a call's host time
LEAVES = (LOWER, ASSEMBLE, DISPATCH, FETCH, AGGREGATE)


def has_spans(summary: dict) -> bool:
    """The trace holds the program's spans (a ``batch.sweep``)."""
    return any(name == SWEEP for _, name, _, _ in summary["host"])


def intervals(summary: dict, names) -> list[tuple[int, int]]:
    """The union of the in-window intervals of the host spans named in
    ``names``, merged and in order."""
    lo, hi = summary["window"]
    out: list[list[int]] = []
    for a, b in sorted(
            (max(s, lo), min(s + d, hi))
            for _, name, s, d in summary["host"] if name in names):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def span_ns(summary: dict, name: str) -> int:
    """Summed in-window duration of every host span called ``name``."""
    lo, hi = summary["window"]
    return sum(max(0, min(s + d, hi) - max(s, lo))
               for _, n, s, d in summary["host"] if n == name)


def overlap_ns(a, b) -> int:
    """Length of the intersection of two merged, ordered interval lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_ns(summary: dict, spans, device: str) -> int:
    """The part of ``spans`` (merged intervals) in which ``device`` runs
    no op."""
    return (sum(b - a for a, b in spans)
            - overlap_ns(spans, devtrace.busy_intervals(summary, device)))


def devices(ctx) -> list[str]:
    """The cell's devices that the trace holds."""
    return [d for d in ctx.device_ids if d in ctx.summary["devices"]]


def ns_per_replica_event(ctx, name: str):
    """Host time of span ``name`` per replica-event swept in the window."""
    if not has_spans(ctx.summary) or not ctx.replica_events:
        return None
    return span_ns(ctx.summary, name) / ctx.replica_events
