"""Time the event-loop kernel at each replica tile on a TPU.

For each shape (a benchmark cell's bucket) and each tile, one bucket of
exactly ``tile`` rows (one grid tile) runs ``ops.run_events`` for
``EVENTS`` events, three times, inside one profiler trace per (shape,
tile). ``bench/kernels.json``'s event-loop pattern also matches the
ring build's sort, so the three longest matching ops are taken as the
kernel's runs and the next two are listed under ``other_matched``.
Prints one JSON line per (shape, tile): the kernel's device times, us
per event (one tile's chain per event) and ns per replica-event.
``ops.MAX_TILE`` rests on this curve.

Usage, from the repo root on a TPU host:
    python scripts/tile_curve.py [--rehearse]
``--rehearse`` runs two shapes at tile 8 for 600 events.
"""
import json
import re
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import cells, devtrace  # noqa: E402
from repro.core.sim import topology  # noqa: E402
from repro.experiments import scenario_workloads  # noqa: E402
from repro.kernels.event_loop import ops  # noqa: E402
from repro.workloads import WorkloadOperands, lower, pad_phases  # noqa: E402

REHEARSE = "--rehearse" in sys.argv
EVENTS = 600 if REHEARSE else 20_000
REPS = 3
SEED = 3716000101


def cell_workloads(name, alg=None):
    """The workload specs of one sweep of a benchmark cell."""
    cell = cells.load_cell(name)
    return [w for w in cells.workload_specs(cell, SEED, 0)
            if alg is None or w.alg == alg]


def shapes():
    """(name, workloads, tiles) for each cell's bucket shape."""
    out = [
        ("fig5-alock", cell_workloads("fig5-grid", "alock"),
         [8, 16, 24, 32, 40, 56, 72, 96, 128, 192]),
        ("fig5-spinlock", cell_workloads("fig5-grid", "spinlock"),
         [8, 16, 24]),
        ("fig5-mcs", cell_workloads("fig5-grid", "mcs"), [8, 16, 24]),
        ("fig4", cell_workloads("fig4-budget"),
         [8, 16, 24, 32, 40, 56, 72, 128]),
        ("ycsb-b", cell_workloads("ycsb-b"),
         [8, 16, 24, 32, 40, 56, 72, 128, 144]),
        ("open-loop", [w for w in scenario_workloads("open-loop-ramp")
                       if w.alg == "alock"], [8, 16, 24, 48, 96]),
    ]
    if REHEARSE:
        out = [(name, ws, [8]) for name, ws, _ in out[::3]]
    return out


def bucket(workloads, rows):
    """One bucket of ``rows`` replicas of the workloads' shape, with
    distinct seeds: its shape key and stacked operands."""
    lowered = [lower(w, EVENTS) for w in workloads]
    pmax = max(lw.operands.n_phases for lw in lowered)
    reps = [pad_phases(lw.operands, pmax)._replace(
        seed=np.int32(int(lw.operands.seed) + s))
        for s in range(rows) for lw in lowered][:rows]
    wl = WorkloadOperands(*(np.stack([np.asarray(getattr(o, f))
                                      for o in reps])
                            for f in WorkloadOperands._fields))
    return lowered[0].shape_key, wl


def job(name, workloads, tile):
    """The lowered one-tile kernel run of a shape at ``tile`` rows."""
    key, wl = bucket(workloads, tile)
    alg, T, N, K, _, R = key
    tn, ln, _ = topology(alg, N, T // N, K)
    plan = ops.plan_for_run(tile, wl.edges.shape[1], EVENTS, T, N, K, R=R,
                            tile=tile, hl=alg == "hlock",
                            rw=alg == "alock-rw")
    fn = jax.jit(lambda w, a, b: ops.run_events(alg, T, N, K, EVENTS, w, a,
                                                b, tile=tile))
    args = (WorkloadOperands(*(jax.device_put(a) for a in wl)),
            jax.device_put(tn), jax.device_put(ln))
    return dict(name=name, alg=alg, T=T, K=K, R=R, tile=tile,
                plan=plan.as_dict(), low=fn.lower(*args), args=args)


def compile_all(jobs):
    def one(j):
        start = time.time()
        j["exe"] = j["low"].compile()
        j["compile_s"] = time.time() - start

    start = time.time()
    with ThreadPoolExecutor(6) as ex:
        list(ex.map(one, jobs))
    print(json.dumps({"compiled": len(jobs), "wall_s": time.time() - start}),
          flush=True)


def measure(j, pattern):
    """One trace of ``REPS`` runs of a compiled job: its JSON line."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    log_dir = tempfile.mkdtemp()
    walls = []
    with jax.profiler.trace(log_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            for _ in range(REPS):
                start = time.perf_counter()
                jax.block_until_ready(j["exe"](*j["args"]))
                walls.append(time.perf_counter() - start)
    summary = devtrace.summarize(log_dir)
    matched = sorted(((d, n) for ops_ in summary["devices"].values()
                      for n, _, d in ops_ if pattern.search(n)),
                     reverse=True)
    kernel = sorted(d for d, _ in matched[:REPS])
    med = float(np.median(kernel)) if kernel else float("nan")
    out = {k: j[k] for k in ("name", "alg", "T", "K", "R", "tile",
                             "compile_s")}
    out.update(wall_s=walls, kernel_ns=kernel,
               other_matched=[[d, n[:60]] for d, n in
                              matched[REPS:REPS + 2]],
               us_per_event=med / EVENTS / 1e3,
               ns_per_replica_event=med / EVENTS / j["tile"],
               plan_tile=j["plan"]["tile"], shrunk=j["plan"]["shrunk"],
               vmem_bytes=j["plan"]["total_bytes"])
    return out


def main():
    pattern = re.compile(json.loads(
        (ROOT / "bench" / "kernels.json").read_text())["event_loop"]
        ["pattern"])
    with jax.enable_x64(True):
        jobs = [job(name, ws, tile) for name, ws, tiles in shapes()
                for tile in tiles]
        compile_all(jobs)
        for j in jobs:                      # warm
            jax.block_until_ready(j["exe"](*j["args"]))
        for j in jobs:
            # one trace per job: no op can be put down to another job
            print(json.dumps(measure(j, pattern)), flush=True)
    print(json.dumps({"device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main()
