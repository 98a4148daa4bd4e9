"""The comparison that decides ``correct``: a replica's result fields, as
the timed sweep returned them, against the plain reference, bit for bit.

An exact comparison: the limit on mismatched fields is 0. A field is its
value for one replica (one seed of one workload point), with its dtype.
"""
from __future__ import annotations

import numpy as np

#: BatchResult fields a replica is judged by: every per-seed field (the
#: open-loop four are None on both sides in a closed loop, and must stay so)
FIELDS = ("seeds", "ops", "sim_ns", "throughput_mops", "lat_ns",
          "per_thread_ops", "reacquires", "passes", "arr_ns", "wait_ns",
          "sojourn_ns", "rstat")


def replica_fields(result, s: int) -> dict:
    """Seed ``s`` of one BatchResult, as host copies of each field."""
    out = {}
    for name in FIELDS:
        v = getattr(result, name)
        out[name] = None if v is None else np.array(v[s])
    return out


def mismatches(got: dict, want: dict) -> list[str]:
    """Every field where the program's replica differs from the
    reference's."""
    bad = []
    for name in FIELDS:
        x, y = got.get(name), want.get(name)
        if x is None and y is None:
            continue
        if x is None or y is None:
            bad.append(f"{name}: present on one side only")
            continue
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            bad.append(f"{name}: {x.shape}/{x.dtype} vs {y.shape}/{y.dtype}")
        elif not np.array_equal(x, y):
            n = int(np.sum(x != y))
            bad.append(f"{name}: {n} of {x.size} values differ")
    return bad
