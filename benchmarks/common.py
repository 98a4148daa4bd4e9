"""Shared helpers for the benchmark suite.

Every benchmark prints CSV rows: name,us_per_call,derived
  - us_per_call: mean microseconds of acquire->release latency per
    lock+unlock op (simulated time, think_ns excluded — Fig. 6 semantics),
    or wall time per call for kernel benches
  - derived: the figure-specific statistic; simulator rows report
    mean±ci95 across seeds (ci95 is 0.000 for a single seed)

All simulator figures are built on the declarative Workload/Experiment
API: each ``fig*`` section composes ``repro.workloads.Workload`` specs
(per-thread locality, Zipf skew, phases) into a
``repro.experiments.Experiment`` and runs them as one batched sweep —
bucketed by shape key ``(alg, T, N, K, n_events)``, one compile per
bucket, all workload shape as traced operands. Named scenario programs
(``benchmarks.run --scenario``) come from the registry in
``repro.experiments.registry``.

Execution choices (backend, device sharding, chunking) travel as an
explicit immutable ``repro.experiments.ExecOptions`` value, threaded from
``benchmarks.run`` into every section — there is no process-wide mutable
execution state (the old ``EXEC`` module global is gone).
"""
from __future__ import annotations

import os
from pathlib import Path

import numpy as np

from repro.core.batch import BatchResult, sweep
from repro.core.sim import SimResult, simulate
from repro.experiments import ExecOptions, Experiment
from repro.workloads import Workload

# Paper-scale default; REPRO_BENCH_EVENTS=2000 gives a fast smoke pass with
# identical bucketing/compile behavior (n_events is part of the shape key).
EVENTS = int(os.environ.get("REPRO_BENCH_EVENTS", 150_000))

#: the persistent compilation cache when JAX_COMPILATION_CACHE_DIR is unset
#: (a fixed path: the cache key includes it, so it must never move)
REPO_COMPILE_CACHE = (Path(__file__).resolve().parent.parent
                      / ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry-point script.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself); otherwise the cache lives at :data:`REPO_COMPILE_CACHE`.
    Entry points call this once at start-up; library code and tests never
    do. Returns the directory in use.
    """
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_COMPILE_CACHE))
    return jax.config.jax_compilation_cache_dir


def wl(alg, nodes, tpn, locks, loc, b=(5, 20), seed=0,
       zipf=0.0, phases=()) -> Workload:
    return Workload(alg, nodes, tpn, locks, locality=loc, zipf_s=zipf,
                    b_init=b, seed=seed, phases=phases)


def experiment(name: str, n_seeds: int = 1, events: int = EVENTS,
               options: ExecOptions | None = None) -> Experiment:
    """An Experiment wired to the suite's defaults (env backend honored)."""
    return Experiment(name, n_seeds=n_seeds, n_events=events,
                      options=options or ExecOptions.from_env())


def run(alg, nodes, tpn, locks, loc, b=(5, 20), events=EVENTS, seed=0,
        options: ExecOptions | None = None) -> SimResult:
    """One-off serial run (kept for interactive use; figures use sweep)."""
    options = options or ExecOptions.from_env()
    return simulate(wl(alg, nodes, tpn, locks, loc, b, seed),
                    n_events=events, backend=options.backend)


def sweep_all(cfgs, n_seeds: int = 1, events: int = EVENTS,
              options: ExecOptions | None = None) -> dict:
    """Batched run of deduped ``cfgs``; returns {workload: BatchResult}."""
    options = options or ExecOptions.from_env()
    uniq = list(dict.fromkeys(cfgs))
    return dict(zip(uniq, sweep(uniq, n_seeds=n_seeds, n_events=events,
                                **options.sweep_kwargs())))


def us_per_op(r) -> float:
    """Mean acquire->release latency in us (SimResult or BatchResult)."""
    if isinstance(r, BatchResult):
        return r.mean_lat_us
    lat = np.asarray(r.lat_ns)
    lat = lat[lat >= 0]
    return float(lat.mean()) / 1e3 if len(lat) else float("nan")


def mops(br: BatchResult) -> str:
    return f"{br.mean_mops:.3f}±{br.ci95_mops:.3f}Mops"


def emit(name: str, us: float, derived) -> None:
    print(f"{name},{us:.3f},{derived}", flush=True)
