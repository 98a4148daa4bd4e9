"""Time the event-loop kernel's two lock layouts and the Zipf draw on a TPU.

Layouts: for a benchmark cell's bucket, one bucket of ``rows`` replicas
runs ``ops.run_events`` three times inside one profiler trace, once with
the layout ``vmem.lock_layout`` derives and once with the other forced,
and the median kernel op is reported as in ``scripts/tile_curve.py``
(us per event, ns per replica-event). The rule in ``vmem.lock_layout``
rests on these numbers.

Draws: for each within-node rank count ``kpn``, the Zipf offsets of a
(72, 150,000) stream of uniforms by each form of ``ops.zipf_offset``
(the fused compare and the merge) and by the compare against a
per-event CDF row that it replaced; the median wall time of five calls,
each forced to finish. ``ops.ZIPF_COMPARE_RANKS`` rests on these.

Usage, from the repo root on a TPU host:
    python scripts/lock_layout_curve.py [--rehearse]
``--rehearse`` runs the smallest cases at 600 events.
"""
import contextlib
import json
import re
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT), str(ROOT / "scripts")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import tile_curve as tc  # noqa: E402
from repro.kernels.event_loop import ops, vmem  # noqa: E402
from repro.workloads import zipf_cdf  # noqa: E402

REHEARSE = tc.REHEARSE
#: (cell, rows, events, layouts): both layouts where both fit; the dense
#: layout at 100,000 locks only as a short ablation
CASES = [("ycsb-b", 72, 20_000, ("direct", "table")),
         ("ycsb-a-k100k", 72, 20_000, ("table",)),
         ("ycsb-a-k100k", 72, 2_048, ("direct",))]
DRAW_RANKS = (5, 10, 100, 1_000, 2_000, 5_000, 10_000)
DRAW_SHAPE = (72, 150_000)
if REHEARSE:
    CASES = [("ycsb-b", 8, 600, ("direct", "table"))]
    DRAW_RANKS = (5, 100)
    DRAW_SHAPE = (8, 600)


@contextlib.contextmanager
def layout(name):
    """Force ``name`` on every bucket traced inside."""
    derived = vmem.lock_layout

    def forced(K, T):
        return ("direct", K) if name == "direct" else (
            "table", -(-T // 128) * 128)

    vmem.lock_layout = forced
    try:
        yield
    finally:
        vmem.lock_layout = derived


def layout_jobs():
    jobs = []
    for cell, rows, events, names in CASES:
        tc.EVENTS = events
        for name in names:
            with layout(name):
                j = tc.job(f"{cell}-{name}", tc.cell_workloads(cell), rows)
            j["events"] = events
            jobs.append(j)
    return jobs


def gather_compare(cdf, ph, u):
    """The draw that ``ops.zipf_offset`` replaced: each event compares its
    uniform with the CDF row of its phase, gathered per event."""
    kpn = cdf.shape[1]
    return jax.vmap(lambda p, x: jnp.minimum(
        jnp.sum(x >= cdf[p]).astype(jnp.int32), kpn - 1))(ph, u)


def time_draws():
    rng = np.random.default_rng(0)
    B, n = DRAW_SHAPE
    u = jax.device_put(rng.random((B, n), np.float32))
    ph = jax.device_put(np.zeros((B, n), np.int32))
    for kpn in DRAW_RANKS:
        cdf = jax.device_put(np.tile(zipf_cdf(kpn, 0.99), (B, 1, 1)))
        row = {"kpn": kpn, "shape": [B, n]}
        outs = {}
        for name, fn in (("compare", ops.zipf_compare),
                         ("merge", ops.zipf_merge),
                         ("gather_compare", gather_compare)):
            f = jax.jit(jax.vmap(fn))
            try:
                outs[name] = np.asarray(f(cdf, ph, u))
                walls = []
                for _ in range(5):
                    start = time.perf_counter()
                    jax.block_until_ready(f(cdf, ph, u))
                    walls.append(time.perf_counter() - start)
                row[name + "_ms"] = statistics.median(walls) * 1e3
            except Exception as e:      # the gathered rows may not fit
                row[name + "_ms"] = None
                row[name + "_error"] = str(e)[:200]
        row["equal"] = all(bool((o == outs["compare"]).all())
                           for o in outs.values())
        print(json.dumps(row), flush=True)


def main():
    pattern = re.compile(json.loads(
        (ROOT / "bench" / "kernels.json").read_text())["event_loop"]
        ["pattern"])
    with jax.enable_x64(True):
        time_draws()
        jobs = layout_jobs()
        tc.compile_all(jobs)
        for j in jobs:                      # warm
            jax.block_until_ready(j["exe"](*j["args"]))
        for j in jobs:
            tc.EVENTS = j["events"]
            out = tc.measure(j, pattern)
            out.update(events=j["events"], layout=j["plan"]["lock_layout"],
                       lock_slots=j["plan"]["lock_slots"],
                       tiles=j["plan"]["tiles"])
            print(json.dumps(out), flush=True)
    print(json.dumps({"device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main()
