"""The device trace: read the profiler's file into a small summary, and
reduce the summary to times.

A summary is plain data, so a test can hold a recorded one:

    {"window": [start_ns, end_ns],          # the benchmark's window span
     "devices": {"0": [[op, start_ns, duration_ns], ...], ...},
     "host": [[thread, name, start_ns, duration_ns], ...]}

``devices`` holds the events of each TPU's "XLA Ops" line: one per HLO
operation or kernel the device ran, named by its HLO name and the JAX op
path it was lowered from. ``host`` holds the host threads'
events (the benchmark's own annotations and the runtime's). Every time
here is on the profiler's one clock, in nanoseconds.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: the benchmark's host annotations
WINDOW = "bench.window"
SWEEP = "bench.sweep"


def summarize(log_dir: str | Path) -> dict:
    """Read the one ``*.xplane.pb`` under ``log_dir`` into a summary."""
    from jax.profiler import ProfileData
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one profiler trace under {log_dir}, "
                           f"found {len(files)}")
    pd = ProfileData.from_file(str(files[0]))
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[m.group(1)] = [
                [_op_name(e), int(e.start_ns), int(e.duration_ns)]
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            host += [[line.name, e.name, int(e.start_ns), int(e.duration_ns)]
                     for line in plane.lines for e in line.events]
    wins = [(s, s + d) for _, name, s, d in host if name == WINDOW]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} span in the trace, "
                           f"found {len(wins)}")
    return {"window": list(wins[0]), "devices": devices, "host": host}


def _op_name(event) -> str:
    """An op's HLO name, with the JAX op path it came from (``tf_op``,
    such as ``jit(run_events)/pallas_call``) and a Mosaic kernel's custom
    call target, where the trace has them."""
    stats = dict(event.stats)
    parts = [event.name, str(stats.get("tf_op", ""))]
    if "tpu_custom_call" in str(stats.get("long_name", "")):
        parts.append("tpu_custom_call")
    return " ".join(p for p in parts if p)


def window_ns(summary: dict) -> int:
    lo, hi = summary["window"]
    return hi - lo


def _clipped(events, lo, hi):
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield a, b


def busy_intervals(summary: dict, device: str) -> list[tuple[int, int]]:
    """The union of one device's op intervals inside the window, merged
    and in order."""
    lo, hi = summary["window"]
    out: list[list[int]] = []
    for a, b in sorted(_clipped(summary["devices"][device], lo, hi)):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(summary: dict, device: str) -> int:
    return sum(b - a for a, b in busy_intervals(summary, device))


def op_ns(summary: dict, pattern: str, *, match: bool = True) -> int:
    """Summed in-window duration, over all devices, of the ops whose name
    matches ``pattern`` (``match=False``: of every other op)."""
    rx = re.compile(pattern)
    lo, hi = summary["window"]
    return sum(b - a for ops in summary["devices"].values()
               for a, b in _clipped(
                   [o for o in ops if bool(rx.search(o[0])) == match],
                   lo, hi))


def op_count(summary: dict, pattern: str) -> int:
    rx = re.compile(pattern)
    lo, hi = summary["window"]
    return sum(1 for ops in summary["devices"].values()
               for name, s, d in ops
               if rx.search(name) and s < hi and s + d > lo)


def top_ops(summary: dict, n: int = 10) -> list[list]:
    """The ``n`` op names with the most in-window device time, summed over
    devices, in seconds."""
    lo, hi = summary["window"]
    tot: dict[str, int] = defaultdict(int)
    for ops in summary["devices"].values():
        for name, s, d in ops:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                tot[name] += b - a
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(summary: dict, device: str, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps of ``device`` inside the window, each
    named by the host event that overlaps it most (the benchmark's own
    window and sweep spans only where nothing else does), in seconds."""
    lo, hi = summary["window"]
    busy = busy_intervals(summary, device)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in gaps:
        best, best_ns = "no host event", 0
        for _, name, s, d in summary["host"]:
            ov = min(b, s + d) - max(a, s)
            if name in (WINDOW, SWEEP):
                ov = ov // 2 if ov > 0 else ov   # a span of last resort
            if ov > best_ns:
                best, best_ns = name, ov
        out.append([best, (b - a) / 1e9])
    return out

