"""Bytes the event-loop kernel has to move, from a cell's shapes alone.

The count does not depend on how the kernel is written: per replica it is
the simulation's draw stream (the locality uniform, the remote-node
offset and the within-node lock offset, 4 bytes each per event; the
reader coin adds 4 more for ``alock-rw``), the replica's workload operands
in (one phase: per-thread locality and active mask, the Zipf CDF, the
phase edge, the think time, the two budgets, the 8 cost rows, the node
multipliers, and the read fractions or rack ids where the machine reads
them), and its results out (per-thread completions, the int64 latency
ring, the completion count, the int64 end clock, reacquires and passes).

The event loop is a dependent chain per replica, not a stream: these
bytes bound it from below, far below what it takes. No published peak
exists for the TPU's int32 vector work, so HBM bytes are the roofline.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
I32, I64, F32 = 4, 8, 4


def replica_bytes(point: dict, n_events: int, lat_samples: int) -> int:
    alg = point["alg"]
    N, T = point["n_nodes"], point["n_nodes"] * point["threads_per_node"]
    kpn = point["n_locks"] // N
    rw, hl = alg == "alock-rw", alg == "hlock"
    draws = n_events * (4 if rw else 3) * 4
    operands = (T * F32 + T * I32 + kpn * F32 + I32 + I32 + 2 * I32
                + 8 * I32 + N * F32 + I32
                + (T * F32 if rw else 0) + (N * I32 if hl else 0))
    results = T * I32 + lat_samples * I64 + I32 + I64 + I32 + I32
    return draws + operands + results


def sweep_bytes(points: list[dict], n_seeds: int, n_events: int,
                lat_samples: int) -> int:
    """Bytes of one sweep of a mix: every replica, S seeds per point."""
    return n_seeds * sum(replica_bytes(p, n_events, lat_samples)
                         for p in points)


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {path}; known: "
                       f"{sorted(table['devices'])}")
    return table["devices"][device_kind]
