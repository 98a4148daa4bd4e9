"""Run the simulator's main path natively on a TPU and check it bitwise.

Usage (from the repository root, on a machine with a TPU):

    python chip_smoke.py               # one chip: fig5, storm, open loop
    python chip_smoke.py --chips 4     # the sharded sweep over 4 chips

One chip: ``Workload`` specs go through ``repro.core.batch.sweep`` with
``backend="pallas"`` (the event-loop kernel, lowered by Mosaic, never
interpreted) in three phases — the paper's Fig. 5 grid (alock, spinlock
and mcs at locality 0.85/0.95/1.0; 10 nodes x 8 threads; 100 locks), one
multi-phase ``hot-key-storm`` bucket (P=3) and one ``open-loop-ramp``
bucket (R=256 requests) — and every replica is compared bit for bit with
the XLA engine (``backend="xla"``) run on the same chip. ``--chips 4``
runs only the Fig. 5 grid sharded over four chips (``devices=``,
``chunk=``) and compares it bitwise with the same grid unsharded on chip
0.

Each phase prints one JSON line: device kind, compile and run seconds,
replica-events/s (a smoke figure, not a benchmark), the bitwise verdict,
the VMEM plan of every bucket and persistent-compilation-cache hits. Any mismatch or
failure exits non-zero. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
it is never printed off-TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: the paper-scale run length (``benchmarks/common.py``)
EVENTS = 150_000
#: seeds per workload: 72 fig5 replicas on one chip, 288 over four
SEEDS = 8
SHARDED_SEEDS = 32
#: BatchResult fields that must agree bit for bit between two runs
FIELDS = ("seeds", "ops", "sim_ns", "lat_ns", "per_thread_ops", "reacquires",
          "passes", "arr_ns", "wait_ns", "sojourn_ns", "rstat")


class CacheEvents:
    """Counts JAX persistent-compilation-cache hits and misses."""

    def __init__(self):
        self.hits = 0
        self.misses = 0

    def __call__(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def fig5_grid():
    from repro.experiments import fig5_workloads
    return fig5_workloads()


def storm_bucket():
    """The alock bucket of ``hot-key-storm``: the flat control and the
    three-phase storm share one executable (P=3 after padding)."""
    from repro.experiments import scenario_workloads
    return [w for w in scenario_workloads("hot-key-storm") if w.alg == "alock"]


def open_loop_bucket():
    """The alock bucket of ``open-loop-ramp``: six Poisson rates, R=256."""
    from repro.experiments import scenario_workloads
    return [w for w in scenario_workloads("open-loop-ramp")
            if w.alg == "alock"]


def timed_sweep(workloads, n_seeds: int, n_events: int, **kw):
    """Sweep twice: the first call compiles, the second runs warm.

    Returns (results, compile_s, run_s, vmem_plans): the plans are every
    distinct kernel plan of the two sweeps, one per shape bucket.
    ``sweep`` hands back host arrays, so both timings include the device
    work.
    """
    from repro.core import batch
    batch.reset_exec_stats()
    t0 = time.perf_counter()
    batch.sweep(workloads, n_seeds, n_events, **kw)
    t1 = time.perf_counter()
    res = batch.sweep(workloads, n_seeds, n_events, **kw)
    t2 = time.perf_counter()
    return (res, (t1 - t0) - (t2 - t1), t2 - t1,
            batch.exec_stats()["vmem_plans"])


def mismatches(got, want) -> list[str]:
    """Every (workload, seed, field) where two sweeps differ."""
    import numpy as np
    bad = []
    for i, (a, b) in enumerate(zip(got, want, strict=True)):
        for name in FIELDS:
            x, y = getattr(a, name), getattr(b, name)
            if x is None and y is None:
                continue
            if x is None or y is None:
                bad.append(f"workload {i}: {name} present in one run only")
                continue
            x, y = np.asarray(x), np.asarray(y)
            if x.shape != y.shape or x.dtype != y.dtype:
                bad.append(f"workload {i}: {name} {x.shape}/{x.dtype} vs "
                           f"{y.shape}/{y.dtype}")
                continue
            for s in range(x.shape[0]):
                if not np.array_equal(x[s], y[s]):
                    bad.append(f"workload {i} seed {s}: {name} differs")
    return bad


def require_native(plans) -> None:
    """Every bucket's kernel ran through Mosaic: hi/lo i32 clocks under
    a VMEM budget taken from the device table (interpret mode plans
    neither)."""
    if not plans:
        raise RuntimeError("the Pallas kernel did not run natively: "
                           "no vmem plan was recorded")
    for plan in plans:
        if plan["representation"] != "i32pair" or plan["budget"] is None:
            raise RuntimeError(f"the Pallas kernel did not run natively: "
                               f"vmem plan {plan}")


def bitwise_phase(name: str, workloads, n_seeds: int, n_events: int,
                  **sweep_kw) -> dict:
    """Pallas kernel vs the XLA engine over the same replicas."""
    res, compile_s, run_s, plans = timed_sweep(
        workloads, n_seeds, n_events, backend="pallas", **sweep_kw)
    from repro.core import batch
    t0 = time.perf_counter()
    ref = batch.sweep(workloads, n_seeds, n_events, backend="xla")
    oracle_s = time.perf_counter() - t0
    return _report(name, res, ref, compile_s, run_s, plans, n_seeds,
                   n_events, oracle="xla", oracle_s=oracle_s)


def sharded_phase(workloads, n_seeds: int, n_events: int, devices,
                  chunk: int) -> dict:
    """The sweep sharded over ``devices`` vs the same grid unsharded."""
    res, compile_s, run_s, plans = timed_sweep(
        workloads, n_seeds, n_events, backend="pallas", devices=devices,
        chunk=chunk)
    from repro.core import batch
    t0 = time.perf_counter()
    ref = batch.sweep(workloads, n_seeds, n_events, backend="pallas")
    oracle_s = time.perf_counter() - t0
    return _report(f"fig5-sharded-{len(devices)}", res, ref, compile_s,
                   run_s, plans, n_seeds, n_events,
                   oracle="pallas-unsharded", oracle_s=oracle_s,
                   chunk=chunk)


def _report(name, res, ref, compile_s, run_s, plans, n_seeds, n_events,
            **extra) -> dict:
    bad = mismatches(res, ref)
    replicas = len(res) * n_seeds
    return {"phase": name, "replicas": replicas, "seeds": n_seeds,
            "events_per_replica": n_events,
            "compile_s": compile_s, "run_s": run_s,
            "smoke_replica_events_per_s": replicas * n_events / run_s,
            "bitwise_equal": not bad, "mismatches": bad[:10],
            "vmem_plans": plans, **extra}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded sweep and its comparison")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found; JAX platform is {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1

    from benchmarks.common import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = CacheEvents()
    jax.monitoring.register_event_listener(cache)
    print(json.dumps({"device_kind": dev.device_kind, "devices": len(devs),
                      "jax": jax.__version__, "compile_cache": cache_dir}),
          flush=True)

    if args.chips == 1:
        phases = [("fig5", fig5_grid), ("hot-key-storm", storm_bucket),
                  ("open-loop-ramp", open_loop_bucket)]
        run = [lambda n=n, f=f: bitwise_phase(n, f(), SEEDS, EVENTS)
               for n, f in phases]
    else:
        run = [lambda: sharded_phase(fig5_grid(), SHARDED_SEEDS, EVENTS,
                                     devs[:4], chunk=8)]
    ok = True
    for phase in run:
        hits0 = cache.hits
        out = phase()
        require_native(out["vmem_plans"])
        out["device_kind"] = dev.device_kind
        out["compile_cache_hits"] = cache.hits - hits0
        print(json.dumps(out), flush=True)
        ok &= out["bitwise_equal"]
    if not ok:
        print("chip_smoke: bitwise mismatch against the reference",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
