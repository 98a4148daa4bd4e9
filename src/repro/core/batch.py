"""Batched simulation engine: vmap-over-(workload x seed) on top of sim.py.

The paper's headline figures (Fig. 5/6) are grids of simulator runs. Running
each (workload, seed) point as its own ``simulate()`` call costs one device
dispatch per point and gives a single seed with no error bars. This module
batches instead:

  * ``_run_events_batch`` vmaps the serial event loop over a flattened
    (workload x seed) axis, so one compile + one dispatch yields S
    independent replicas for every workload that shares a shape;
  * ``sweep`` accepts ``repro.workloads.Workload`` specs (legacy
    ``SimConfig`` rides through the bitwise-faithful adapter), lowers each
    to its traced ``WorkloadOperands`` struct, and buckets by the static
    shape key ``(alg, T, N, K, n_events)`` — everything workload-shaped
    (per-thread locality, Zipf CDFs, phase programs, think times, active
    masks, per-phase ALock budgets, per-phase cost-model rows, seeds)
    rides along as *batched traced operands*. Replicas with fewer phases
    than their bucket's max are padded with unreachable phases
    (``pad_phases`` — provably inert, including the cost/budget rows), so
    a sweep mixing scenarios — even ones under different cost profiles or
    budget programs — still compiles exactly once per bucket;
  * ``BatchResult`` keeps the per-seed samples bitwise-identical to
    individual ``simulate()`` calls (tested) and derives mean/ci95/p50/p99
    aggregates from them.

Execution backends and sharding
-------------------------------
``sweep(..., backend=)`` picks the per-replica engine: the XLA ``fori_loop``
(``"xla"``, the correctness oracle) or the Pallas event-loop kernel
(``"pallas"``, ``repro.kernels.event_loop`` — VMEM-resident state, replicas
tiled across the Pallas grid). ``"auto"`` resolves per
``sim.resolve_backend``. Both produce bitwise-identical replicas.

``sweep(..., devices=, chunk=)`` turns on the sharded bucket layout: each
bucket's flattened (workload x seed) axis is measured in dispatch *units*
of ``chunk`` rows per device (``chunk * n_devices`` rows each), and the
unit count is greedily decomposed into power-of-two **superchunks** —
each superchunk is ONE dispatch of ``2**k * chunk * n_devices`` rows
through a cached ``jax.shard_map`` runner (mesh axis ``"data"``). A
bucket of ``u`` units therefore costs ``popcount(u)`` dispatches against
an executable family of at most ``log2(u) + 1`` shapes, instead of ``u``
serialized unit dispatches: on hosts where every dispatch pays a full
serial event loop regardless of its replica-row count (vmap rows are
nearly free), this is what keeps the sharded layout's events/sec at
parity with the unsharded single-dispatch layout. The dispatch loop
never blocks — every superchunk is issued before the first result is
touched, chunk operand buffers are donated, and the host-side
aggregation of finished superchunks overlaps the still-in-flight ones.
Edge padding is bounded below the mesh width (rows are only rounded up
to a device-count multiple); the final superchunk is trimmed to the true
remaining rows rather than padded to a full unit.
``exec_stats()`` exposes the dispatch and shape-key counters and the
kernel's VMEM plans. ``repro.experiments.ExecOptions`` carries (backend,
devices, chunk) as one immutable object through the benchmark suite —
there is no process-wide execution state.

Profiler spans
--------------
Each ``sweep`` call marks its host stages with
``jax.profiler.TraceAnnotation`` spans (the ``SPAN_*`` names below). Under
a profiler session they land in the trace on the clock of the device
planes, so every gap in a device's timeline can be put down to a stage;
with no session a span costs one TraceMe constructor. Every span of one
call carries the same ``sweep`` ordinal in its metadata:

  * ``batch.sweep`` — the whole call (``configs``, ``seeds``, ``events``,
    ``buckets``);
  * ``batch.lower`` — ``lower()`` of every config and the bucketing;
  * ``batch.assemble`` — one bucket's host operand arrays (``bucket``,
    ``rows``, ``phases``);
  * ``batch.dispatch`` — the device put of the operands, the VMEM plan
    and the jitted call up to its return: the enqueue, plus any compile
    (per bucket, or per superchunk when sharded; ``bucket``, ``rows``,
    ``bytes`` put, ``new_shape``, and the bucket's ``lock_lanes`` K,
    ``draw_streams`` 3 or 4 and ``zipf_ranks`` K/N; on the Pallas
    backend also each kernel call's ``tile``, ``tiles`` and
    ``padded_rows``, per shard when sharded);
  * ``batch.fetch`` — the wait for the device and the copy of the outputs
    back to the host (``bucket``, ``bytes`` fetched);
  * ``batch.aggregate`` — one bucket's reshapes into ``BatchResult``
    objects.
"""
from __future__ import annotations

import functools
import itertools
import math
import warnings
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.cost_model import CostModel, N_COST_ROWS
from repro.core.sim import (LAT_SAMPLES, SimConfig, SimResult, _run_events,
                            resolve_backend, topology)
from repro.workloads import (Workload, WorkloadOperands, as_workload, lower,
                             pad_phases)

# -- profiler spans (see the module docstring) --------------------------------
SPAN_SWEEP = "batch.sweep"
SPAN_LOWER = "batch.lower"
SPAN_ASSEMBLE = "batch.assemble"
SPAN_DISPATCH = "batch.dispatch"
SPAN_FETCH = "batch.fetch"
SPAN_AGGREGATE = "batch.aggregate"
_SWEEP_IDS = itertools.count()

# -- execution statistics ----------------------------------------------------
# A "dispatch" is one host->device call of a compiled bucket runner (covering
# every device in its mesh). "compiles" counts new (runner, input shape)
# keys seen by this process: a cache-miss count on the Python side, not a
# count of backend compiles (a key first seen may still load from the
# persistent compile cache, and jit may compile for reasons the key does
# not hold); backend compiles are what jax.monitoring reports. "vmem_plan"
# is the most recent event-loop kernel VMEM plan
# (repro.kernels.event_loop.vmem) and "vmem_plans" every distinct plan
# since the reset, one per bucket.
_STATS = {"dispatches": 0, "compiles": 0}
_COMPILED: set = set()


def exec_stats() -> dict:
    """Snapshot of {dispatches, compiles, vmem_plan, vmem_plans} since the
    last reset. ``compiles`` counts new (runner, shape) keys, a cache-miss
    count of this module, not backend compiles. ``vmem_plan`` is None and
    ``vmem_plans`` empty unless the Pallas backend planned a kernel."""
    from repro.kernels.event_loop import vmem
    st = dict(_STATS)
    plan = vmem.last_plan()
    st["vmem_plan"] = plan.as_dict() if plan is not None else None
    st["vmem_plans"] = [p.as_dict() for p in vmem.plans()]
    return st


def reset_exec_stats() -> None:
    from repro.kernels.event_loop import vmem
    _STATS["dispatches"] = 0
    _STATS["compiles"] = 0
    vmem.clear_plan()


def _note_call(key) -> bool:
    """Count one dispatch; True if its (runner, shape) key is new."""
    _STATS["dispatches"] += 1
    if key in _COMPILED:
        return False
    _COMPILED.add(key)
    _STATS["compiles"] += 1
    return True


def _nbytes(arrays) -> int:
    return sum(int(a.nbytes) for a in arrays)


def shape_key(cfg, n_events: int):
    """The static-argument tuple that determines a compile: two workloads
    (or SimConfigs) with equal keys can share one XLA executable. The
    final entry is the open-loop request-slot count R (0 = closed loop;
    legacy SimConfigs have no arrivals and are always closed)."""
    arr = getattr(cfg, "arrivals", None)
    return (cfg.alg, cfg.n_nodes * cfg.threads_per_node, cfg.n_nodes,
            cfg.n_locks, n_events, 0 if arr is None else arr.n_requests)


@functools.partial(jax.jit,
                   static_argnames=("alg", "T", "N", "K", "n_events"))
def _run_events_batch(alg, T, N, K, n_events, wl, thread_node, lock_node):
    """One shape bucket: every ``wl`` leaf has leading axis B = C * S
    (cost rows and budgets included — per-phase, per-replica operands).
    thread_node/lock_node are functions of the shape key alone and stay
    unbatched (broadcast)."""
    def point(w):
        return _run_events(alg, T, N, K, n_events, w, thread_node,
                           lock_node)

    return jax.vmap(point)(wl)


# -- sharded bucket runners --------------------------------------------------

_RUNNER_CACHE: dict = {}


def _bucket_runner(key, n_phases: int, backend: str, mesh: Mesh):
    """Cached jitted shard_map runner for one (shape key, P, backend, mesh).

    The wrapped function maps the flattened replica axis onto the mesh's
    ``data`` axis; inside each shard the local block runs through the
    selected backend. One runner serves every superchunk size — jit keys
    executables by input shape, so the power-of-two superchunk family
    upstream compiles at most O(log units) shapes per runner, reused
    across superchunks and buckets (``_note_call`` mirrors this by
    including the superchunk row count in the compile-counter key). The
    workload-operand arguments are donated: each dispatch transfers fresh
    host slices, so their device buffers are dead on return and the
    runtime may reuse them for the outputs; the broadcast
    thread_node/lock_node args are shared across dispatches and are NOT
    donated.
    """
    alg, T, N, K, n_events, R = key
    ck = (key, n_phases, backend, tuple(d.id for d in mesh.devices.flat))
    if ck in _RUNNER_CACHE:
        return _RUNNER_CACHE[ck], ck
    n_fields = len(WorkloadOperands._fields)
    n_out = 10 if R else 6      # open loop appends arr/wq/soj/rstat

    def local_block(*args):
        wl = WorkloadOperands(*args[:n_fields])
        tn, ln = args[n_fields:]
        if backend == "pallas":
            from repro.kernels.event_loop.ops import run_events
            return run_events(alg, T, N, K, n_events, wl, tn, ln)
        from repro.kernels.event_loop.ref import run_events_ref
        return run_events_ref(alg, T, N, K, n_events, wl, tn, ln)

    fn = jax.jit(jax.shard_map(
        local_block, mesh=mesh,
        in_specs=(P("data"),) * n_fields + (P(), P()),
        out_specs=(P("data"),) * n_out, check_vma=False),
        donate_argnums=tuple(range(n_fields)))
    _RUNNER_CACHE[ck] = fn
    return fn, ck


def _pad_rows(a: np.ndarray, n: int) -> np.ndarray:
    """Edge-pad the leading axis by n rows (duplicates, sliced off after)."""
    if n == 0:
        return a
    return np.concatenate([a, np.repeat(a[-1:], n, axis=0)], axis=0)


class BatchResult(NamedTuple):
    """Per-seed samples + aggregate statistics for one workload.

    ``config`` is the item as passed to ``sweep`` (a ``Workload`` or a
    legacy ``SimConfig``). Sample arrays are stacked over the seed axis S;
    ``result(i)`` recovers the i-th seed as a plain ``SimResult``
    (bitwise-equal to running ``simulate`` with that seed).
    """
    config: object
    n_events: int
    seeds: np.ndarray             # (S,)
    ops: np.ndarray               # (S,)
    sim_ns: np.ndarray            # (S,)
    throughput_mops: np.ndarray   # (S,)
    lat_ns: np.ndarray            # (S, LAT_SAMPLES), -1 padded
    per_thread_ops: np.ndarray    # (S, T)
    reacquires: np.ndarray        # (S,)
    passes: np.ndarray            # (S,)
    # open-loop (Workload.arrivals) extras — None on closed-loop runs
    arr_ns: np.ndarray | None = None      # (S, R) request arrival times
    wait_ns: np.ndarray | None = None     # (S, R) queue waits, -1 padded
    sojourn_ns: np.ndarray | None = None  # (S, R) sojourns, -1 padded
    rstat: np.ndarray | None = None       # (S, R) repro.traffic codes

    @property
    def n_seeds(self) -> int:
        return len(self.seeds)

    @property
    def open_loop(self) -> bool:
        return self.arr_ns is not None

    def result(self, i: int) -> SimResult:
        extras = {}
        if self.open_loop:
            extras = dict(arr_ns=self.arr_ns[i], wait_ns=self.wait_ns[i],
                          sojourn_ns=self.sojourn_ns[i],
                          rstat=self.rstat[i])
        return SimResult(int(self.ops[i]), int(self.sim_ns[i]),
                         float(self.throughput_mops[i]), self.lat_ns[i],
                         self.per_thread_ops[i], int(self.reacquires[i]),
                         int(self.passes[i]), **extras)

    # -- open-loop serving aggregates --------------------------------------

    def serving(self, i: int) -> dict:
        """One seed's ``repro.traffic.metrics.serving_summary`` dict."""
        if not self.open_loop:
            raise ValueError("serving() needs an open-loop run "
                             "(Workload.arrivals)")
        from repro.traffic.metrics import serving_summary
        return serving_summary(self.arr_ns[i], self.wait_ns[i],
                               self.sojourn_ns[i], self.rstat[i],
                               int(self.sim_ns[i]))

    def serving_mean(self) -> dict:
        """Seed-averaged serving summary (nan-safe over empty seeds)."""
        rows = [self.serving(i) for i in range(self.n_seeds)]
        out = {}
        for k in rows[0]:
            vals = np.asarray([r[k] for r in rows], np.float64)
            finite = vals[np.isfinite(vals)]
            out[k] = float(finite.mean()) if len(finite) else float("nan")
        return out

    # -- throughput aggregates ---------------------------------------------

    @property
    def mean_mops(self) -> float:
        return float(self.throughput_mops.mean())

    @property
    def ci95_mops(self) -> float:
        """Half-width of the normal-approx 95% CI of the mean (0 for S=1)."""
        s = self.throughput_mops
        if len(s) < 2:
            return 0.0
        return float(1.96 * s.std(ddof=1) / np.sqrt(len(s)))

    # -- latency aggregates (valid samples only; -1 is padding) ------------

    def _lat_pool(self) -> np.ndarray:
        flat = self.lat_ns.ravel()
        return flat[flat >= 0]

    @property
    def mean_lat_us(self) -> float:
        pool = self._lat_pool()
        return float(pool.mean()) / 1e3 if len(pool) else float("nan")

    @property
    def p50_lat_ns(self) -> float:
        pool = self._lat_pool()
        return float(np.percentile(pool, 50)) if len(pool) else float("nan")

    @property
    def p99_lat_ns(self) -> float:
        pool = self._lat_pool()
        return float(np.percentile(pool, 99)) if len(pool) else float("nan")

    def lat_pct(self, q: float) -> tuple[float, float]:
        """(mean, ci95) of the q-th latency percentile across seeds."""
        per_seed = []
        for row in self.lat_ns:
            valid = row[row >= 0]
            if len(valid):
                per_seed.append(np.percentile(valid, q))
        if not per_seed:
            return float("nan"), 0.0
        per_seed = np.asarray(per_seed, np.float64)
        mean = float(per_seed.mean())
        if len(per_seed) < 2:
            return mean, 0.0
        return mean, float(1.96 * per_seed.std(ddof=1)
                           / np.sqrt(len(per_seed)))


def _draw_shape(alg: str, N: int, K: int) -> dict:
    """A bucket's lock-state and draw-stream widths, as dispatch-span
    metadata: the lanes of the lock table, the precomputed draw streams
    (the alock-rw reader coin is the fourth) and the Zipf ranks a node's
    inverse-CDF scans."""
    return {"lock_lanes": K, "draw_streams": 4 if alg == "alock-rw" else 3,
            "zipf_ranks": K // N}


def _exec_bucket(key, thread_node, lock_node, wl: WorkloadOperands,
                 backend: str, devices, chunk, sweep_id: int,
                 bucket: int):
    """Run one flattened bucket (B rows) and return the 6 output arrays.

    ``wl`` leaves carry the flattened (workload x seed) axis B — the
    per-phase cost rows and budgets included. Unsharded (devices/chunk
    both None): one dispatch for the whole bucket — the XLA leg is the
    original ``_run_events_batch`` oracle. Sharded: the row axis is
    measured in units of ``chunk`` rows per device, the unit count is
    decomposed into greedy power-of-two superchunks, and each superchunk
    is one non-blocking dispatch over the device mesh (see the module
    docstring); the fetch converts finished superchunks while later
    ones are still in flight and only the final concatenate forces the
    last dispatch. ``sweep_id`` and ``bucket`` label the profiler spans.
    """
    alg, T, N, K, n_events, R = key
    B = wl.seed.shape[0]
    n_phases = wl.edges.shape[1]
    if devices is None and chunk is None:
        with TraceAnnotation(SPAN_DISPATCH, sweep=sweep_id, bucket=bucket,
                             rows=B, bytes=_nbytes(wl),
                             **_draw_shape(alg, N, K)) as span:
            with jax.enable_x64(True):
                wj = WorkloadOperands(*(jnp.asarray(a) for a in wl))
                if backend == "pallas":
                    from repro.kernels.event_loop.ops import (
                        plan_for_run, run_events_jit)
                    # re-record the VMEM plan per dispatch: planning inside
                    # run_events is trace-time only, so a cached executable
                    # would otherwise leave exec_stats()["vmem_plan"] stale
                    plan = plan_for_run(B, n_phases, n_events, T, N, K, R=R,
                                        hl=alg == "hlock",
                                        rw=alg == "alock-rw")
                    span.set_metadata(tile=plan.tile, tiles=plan.tiles,
                                      padded_rows=plan.padded_rows)
                    out = run_events_jit(alg, T, N, K, n_events, wj,
                                         thread_node, lock_node)
                else:
                    out = _run_events_batch(alg, T, N, K, n_events, wj,
                                            thread_node, lock_node)
            span.set_metadata(new_shape=_note_call(
                (key, n_phases, backend, "bucket", B)))
        with TraceAnnotation(SPAN_FETCH, sweep=sweep_id, bucket=bucket,
                             bytes=_nbytes(out)):
            return tuple(np.asarray(o) for o in out)

    devs = list(devices) if devices is not None else jax.devices()
    mesh = Mesh(np.asarray(devs), ("data",))
    D = len(devs)
    rows = int(chunk) if chunk is not None else math.ceil(B / D)
    if rows < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    step = rows * D                       # rows per dispatch unit
    # pad only to the mesh width: shard_map needs each dispatch's row
    # count divisible by D, nothing more — per-replica edge padding is
    # dead serial kernel work on every device, so the final superchunk is
    # trimmed to the true remaining rows instead of a full unit
    Bp = math.ceil(B / D) * D
    n_units = math.ceil(Bp / step)
    # greedy power-of-two decomposition of the unit count: one dispatch
    # per superchunk (popcount(n_units) total), executable family bounded
    # by log2(n_units) + 1 full-unit shapes plus at most one trimmed
    # trailing shape
    sizes, rem = [], n_units
    while rem:
        p = 1 << (rem.bit_length() - 1)
        sizes.append(p)
        rem -= p
    leaves = [_pad_rows(np.asarray(a), Bp - B) for a in wl]
    tn = np.asarray(thread_node)
    ln = np.asarray(lock_node)
    runner, ck = _bucket_runner(key, n_phases, backend, mesh)
    outs = []
    with jax.enable_x64(True), warnings.catch_warnings():
        # donated operand buffers only help when an output can reuse one;
        # most of this engine's outputs are clock-typed rings with no
        # matching input shape, so XLA declines those donations with a
        # per-dispatch warning — benign and suppressed here
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        off = 0
        for sz in sizes:
            nrows = min(sz * step, Bp - off)   # multiple of D by induction
            sl = slice(off, off + nrows)
            args = [a[sl] for a in leaves]
            with TraceAnnotation(SPAN_DISPATCH, sweep=sweep_id,
                                 bucket=bucket, rows=nrows,
                                 bytes=_nbytes(args),
                                 **_draw_shape(alg, N, K)) as span:
                if backend == "pallas":
                    # each shard's kernel sees nrows/D replicas (same
                    # trace-time-only caveat as the unsharded branch above)
                    from repro.kernels.event_loop.ops import plan_for_run
                    plan = plan_for_run(nrows // D, n_phases, n_events, T,
                                        N, K, R=R, hl=alg == "hlock",
                                        rw=alg == "alock-rw")
                    span.set_metadata(tile=plan.tile, tiles=plan.tiles,
                                      padded_rows=plan.padded_rows)
                # async: the call returns device futures — every
                # superchunk is issued before any result is forced below
                outs.append(runner(*args, tn, ln))
                span.set_metadata(new_shape=_note_call((ck, nrows)))
            off += nrows
    # the fetch is the only blocking point: np.asarray forces the
    # superchunks in dispatch order, so converting an early (large) one
    # overlaps the later in-flight dispatches
    with TraceAnnotation(SPAN_FETCH, sweep=sweep_id, bucket=bucket,
                         bytes=sum(_nbytes(o) for o in outs)):
        return tuple(np.concatenate([np.asarray(o[j]) for o in outs])[:B]
                     for j in range(10 if R else 6))


def sweep(configs: Sequence[SimConfig | Workload], n_seeds: int = 1,
          n_events: int = 400_000, cm: CostModel = CostModel(), *,
          backend: str = "auto", devices=None,
          chunk: int | None = None) -> list[BatchResult]:
    """Run every workload with seeds ``w.seed + [0, n_seeds)``; one compile
    per ``shape_key`` bucket (per chunk shape when sharding).

    configs: ``Workload`` specs and/or legacy ``SimConfig`` (adapter).
    backend: "xla" | "pallas" | "auto" — per-replica engine (see module
      docstring); every backend/layout combination returns bitwise-identical
      replicas (tested).
    devices: device list to shard the flattened (workload x seed) axis over
      (mesh axis "data"); None with chunk=None keeps the single-dispatch
      layout.
    chunk: rows per device per dispatch *unit*. Units are coalesced into
      greedy power-of-two superchunks — one dispatch each — so an
      oversized bucket costs popcount(units) dispatches against at most
      log2(units)+1 executable shapes instead of one serialized dispatch
      per unit; chunk=None with devices set derives one even chunk per
      device (a single superchunk).

    Returns BatchResults parallel to ``configs`` (duplicates are simulated
    twice — dedupe upstream if the grid overlaps; ``experiments.Experiment``
    does). ``cm`` is the base cost model every ``cost=None`` workload
    inherits (per-workload/per-phase ``cost`` fields override it row-wise
    without adding compiles).

    >>> from repro.core.batch import sweep
    >>> from repro.workloads import Workload
    >>> rs = sweep([Workload("alock", 2, 2, 8, locality=0.9, seed=1)],
    ...            n_seeds=2, n_events=1500, backend="xla")
    >>> rs[0].ops.shape                  # per-seed samples
    (2,)
    >>> rs[0].mean_mops > 0 and rs[0].p99_lat_ns > 0
    True
    """
    if n_seeds < 1:
        raise ValueError(f"n_seeds must be >= 1, got {n_seeds}")
    backend = resolve_backend(backend)
    configs = list(configs)
    sid = next(_SWEEP_IDS)
    with TraceAnnotation(SPAN_SWEEP, sweep=sid, configs=len(configs),
                         seeds=n_seeds, events=n_events) as span:
        with TraceAnnotation(SPAN_LOWER, sweep=sid):
            lowered = [lower(as_workload(c), n_events, cm) for c in configs]
            buckets: dict[tuple, list[int]] = {}
            for i, lw in enumerate(lowered):
                buckets.setdefault(lw.shape_key, []).append(i)
        span.set_metadata(buckets=len(buckets))
        out: list[BatchResult | None] = [None] * len(configs)
        for b, (key, idxs) in enumerate(buckets.items()):
            with TraceAnnotation(SPAN_ASSEMBLE, sweep=sid, bucket=b,
                                 rows=len(idxs) * n_seeds) as asm:
                thread_node, lock_node, wl, seeds = _assemble(
                    key, [lowered[i].operands for i in idxs], n_seeds, cm)
                asm.set_metadata(phases=wl.edges.shape[1])
            outs = _exec_bucket(key, thread_node, lock_node, wl, backend,
                                devices, chunk, sid, b)
            with TraceAnnotation(SPAN_AGGREGATE, sweep=sid, bucket=b):
                _aggregate(key, outs, idxs, seeds, configs, n_events, out)
    return out


def _assemble(key, operands, n_seeds: int, cm: CostModel):
    """One bucket's operands: the broadcast topology rows and every
    replica's ``WorkloadOperands`` flattened over (workload x seed), with
    the (C, S) seed grid."""
    alg, T, N, K, _, R = key
    kpn = K // N
    thread_node, lock_node, _ = topology(alg, N, T // N, K, cm)
    C, S = len(operands), n_seeds
    # scenarios with fewer phases pad up to the bucket max with
    # unreachable phases, so mixed phase programs share one executable
    # (open-loop arrival rows pad identically; R is part of the key)
    Pmax = max(o.n_phases for o in operands)
    loc = np.empty((C, S, Pmax, T), np.float32)
    zc = np.empty((C, S, Pmax, kpn), np.float32)
    ed = np.empty((C, S, Pmax), np.int32)
    th = np.empty((C, S, Pmax), np.int32)
    ac = np.empty((C, S, Pmax, T), np.int32)
    bi = np.empty((C, S, Pmax, 2), np.int32)
    cr = np.empty((C, S, Pmax, N_COST_ROWS), np.int32)
    nm = np.empty((C, S, Pmax, N), np.float32)
    sd = np.empty((C, S), np.int32)
    ag = np.empty((C, S, Pmax), np.float32)
    ae = np.empty((C, S, Pmax), np.int32)
    aq = np.empty((C, S, Pmax), np.int32)
    at = np.empty((C, S, Pmax, 2), np.float32)
    af = np.empty((C, S, R), np.int32)
    rk = np.empty((C, S, N), np.int32)
    rf = np.empty((C, S, Pmax, T), np.float32)
    for row, op in enumerate(operands):
        o = pad_phases(op, Pmax)
        loc[row], zc[row], ed[row] = o.locality, o.zcdf, o.edges
        th[row], ac[row], bi[row] = o.think_ns, o.active, o.b_init
        cr[row], nm[row] = o.cost_rows, o.node_mult
        ag[row], ae[row], aq[row] = (o.arr_gap_ns, o.arr_edges,
                                     o.arr_qcap)
        at[row], af[row] = o.arr_token, o.arr_fix
        rk[row], rf[row] = o.rack, o.read_frac
        sd[row] = int(o.seed) + np.arange(S, dtype=np.int32)

    def flat(a):
        return a.reshape((C * S,) + a.shape[2:])

    wl = WorkloadOperands(flat(loc), flat(zc), flat(ed), flat(th),
                          flat(ac), flat(bi), flat(sd), flat(cr),
                          flat(nm), flat(ag), flat(ae), flat(aq),
                          flat(at), flat(af), flat(rk), flat(rf))
    return thread_node, lock_node, wl, sd


def _aggregate(key, outs, idxs, sd, configs, n_events: int,
               out: list) -> None:
    """Reshape one bucket's outputs into a ``BatchResult`` per workload,
    written into ``out`` at the workload's index."""
    T, R = key[1], key[5]
    C, S = sd.shape
    done, lat, _lat_n, t_end, nreacq, npass = outs[:6]
    done = done.reshape(C, S, T)
    lat = lat.reshape(C, S, LAT_SAMPLES)
    t_end = t_end.reshape(C, S)
    nreacq = nreacq.reshape(C, S)
    npass = npass.reshape(C, S)
    extras = None
    if R:
        extras = tuple(o.reshape(C, S, R) for o in outs[6:])

    for row, i in enumerate(idxs):
        ops = done[row].sum(axis=1).astype(np.int64)
        sim_ns = np.maximum(t_end[row].astype(np.int64), 1)
        # per-element arithmetic matches simulate()'s scalar formula
        # bitwise: ops / sim_ns * 1e3 in float64 either way
        mops = ops / sim_ns * 1e3
        kw = {}
        if extras is not None:
            kw = dict(arr_ns=extras[0][row], wait_ns=extras[1][row],
                      sojourn_ns=extras[2][row], rstat=extras[3][row])
        out[i] = BatchResult(configs[i], n_events, sd[row], ops,
                             sim_ns, mops, lat[row], done[row],
                             nreacq[row], npass[row], **kw)
