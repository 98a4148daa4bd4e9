"""Public entry points for the Pallas event-loop backend.

``run_events`` mirrors ``sim._run_events``'s batched contract (a
``WorkloadOperands`` struct whose leaves carry a leading replica axis B)
and returns the same tuple (done, lat, lat_n, t_end, nreacq, npass).
Replicas are padded to a tile multiple and tiled across the first grid
axis; events are padded to a chunk multiple and streamed along the second
(sequential) grid axis while the simulation state persists in VMEM
scratch.

Clock representation
  ``representation="auto" | "i64" | "i32pair"`` picks how the kernel holds
  its 64-bit clock state (see ``kernel.py``): plain int64 (the interpret /
  XLA-adjacent fast path, callers hold ``jax.enable_x64(True)``) or
  carry-correct hi/lo int32 pairs (``i32pair.py`` — what Mosaic can
  actually lower on TPU, and the only mode that works with x64 entirely
  off). ``"auto"``
  resolves to ``i32pair`` for native lowering and ``i64`` under interpret;
  the ``REPRO_EVENT_CLOCKS`` environment variable overrides either way.
  Both representations are bitwise-equal to the XLA engine
  (``tests/test_event_loop_native_repr.py``). ``run_events`` packs the
  pair outputs back into int64; ``run_events_pairs`` returns the raw
  (hi, lo) arrays and never touches an int64 — that is the entry point
  the x64-off CI leg exercises.

VMEM budget
  Before tracing, the ``vmem.py`` planner prices every VMEM buffer of one
  grid step and deterministically shrinks the replica tile to fit
  ``vmem_budget`` bytes (default: ``vmem.budget_for`` the device's
  ``device_kind`` for native lowering — an unknown device is an error —
  unconstrained under interpret); the native ``pallas_call`` gets the
  device's ``vmem.limit_for`` as ``vmem_limit_bytes``, whatever the
  budget. ``device_kind`` names the target when compiling for a device
  that is not attached (default: ``jax.devices()[0]``). An impossible budget raises an actionable
  ``ValueError`` instead of a Mosaic OOM; the chosen plan is recorded and
  surfaced through ``repro.core.batch.exec_stats()`` and the benchmark
  reports.

The state-independent half of the workload draw stream is precomputed here
(``precompute_draws``) from the identical ``jax.random.fold_in`` counter
scheme the XLA loop uses — the raw locality uniform, the remote-node
offset and the phase-resolved Zipf offset depend only on (seed, event
index), never on simulation state, so hoisting them preserves bitwise
equality. The *thread-dependent* half (comparing the uniform against
``locality[phase, tid]``) runs in-kernel, because ``tid`` is the argmin of
the ready clocks and only exists at runtime; the kernel receives the
per-phase per-thread locality / active-mask / think operands — and the
per-phase cost rows + ALock budgets — directly. The precompute itself is
one vmapped pass fused into the surrounding jit, not a per-event dispatch.

>>> import jax.numpy as jnp
>>> from repro.workloads import Workload, lower
>>> from repro.kernels.event_loop.ops import precompute_draws
>>> o = lower(Workload("alock", 2, 2, 8, locality=0.9), n_events=64).operands
>>> u1, r2, r3 = precompute_draws(jnp.asarray(o.seed)[None],
...                               jnp.asarray(o.edges)[None],
...                               jnp.asarray(o.zcdf)[None],
...                               n_events=64, N=2, kpn=4)
>>> u1.shape, str(r2.dtype), r3.shape
((1, 64), 'int32', (1, 64))

End-to-end, the kernel is selected with ``backend="pallas"`` (interpret
mode off-TPU) and must agree with the XLA loop bit for bit:

>>> from repro.core.sim import simulate
>>> w = Workload("alock", 2, 2, 8, locality=0.9, seed=1)
>>> rx = simulate(w, n_events=300, backend="xla")
>>> rp = simulate(w, n_events=300, backend="pallas")
>>> (rx.ops, rx.sim_ns) == (rp.ops, rp.sim_ns)
True
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cost_model import N_COST_ROWS
from repro.core.sim import I32, I64, LAT_SAMPLES
from repro.kernels.event_loop import i32pair as p32
from repro.kernels.event_loop import vmem
from repro.kernels.event_loop.kernel import event_loop_kernel
from repro.traffic.stream import (arrival_plan, arrival_times_i64,
                                  arrival_times_pairs)

DEFAULT_TILE = 8
DEFAULT_EV_CHUNK = 4096
#: the (8, 128) vreg tiling Mosaic's block shapes must respect
SUBLANES = 8
LANES = 128
#: index-map constant: a python 0 would trace as int64 under x64, which
#: Mosaic refuses in the grid's index functions
_ZERO = np.int32(0)

_REPRESENTATIONS = ("auto", "i64", "i32pair")


def default_interpret() -> bool:
    """Native Mosaic lowering on TPU; interpreter everywhere else."""
    return jax.default_backend() != "tpu"


def _device_kind() -> str:
    """The kind of the device native lowering targets by default."""
    return jax.devices()[0].device_kind


def resolve_representation(representation: str, interpret: bool) -> str:
    """'auto' -> hi/lo i32 pairs for native TPU lowering (Mosaic has no
    64-bit vectors), plain i64 under interpret. ``REPRO_EVENT_CLOCKS``
    overrides the auto choice either way."""
    if representation not in _REPRESENTATIONS:
        raise ValueError(f"representation must be one of "
                         f"{_REPRESENTATIONS}, got {representation!r}")
    if representation == "auto":
        env = os.environ.get("REPRO_EVENT_CLOCKS", "")
        if env:
            if env not in ("i64", "i32pair"):
                raise ValueError(f"REPRO_EVENT_CLOCKS must be 'i64' or "
                                 f"'i32pair', got {env!r}")
            return env
        return "i64" if interpret else "i32pair"
    return representation


def precompute_draws(seed, edges, zcdf, n_events: int, N: int, kpn: int,
                     rw: bool = False):
    """The state-independent per-event draw stream, replica-batched.

    Returns (B, n_events) arrays (loc_uniform f32, remote_offset i32,
    zipf_offset i32) — exactly the values ``sim._run_events`` draws at
    event i from ``split(fold_in(key, i), 3)``. The Zipf inverse-CDF is
    resolved against the phase active at event i (phases are a pure
    function of the event index), so consuming the stream in-kernel
    reproduces the XLA path bit for bit. ``rw=True`` mirrors the
    alock-rw engine's 4-way split and appends the reader/writer coin
    uniform (f32) as a fourth stream.
    """
    def one(sd, ed, cdf):
        key = jax.random.key(sd)

        def ev(i):
            if rw:
                k1, k2, k3, k4 = jax.random.split(
                    jax.random.fold_in(key, i), 4)
            else:
                k1, k2, k3 = jax.random.split(jax.random.fold_in(key, i), 3)
            u1 = jax.random.uniform(k1, dtype=jnp.float32)
            r2 = jax.random.randint(k2, (), 0, max(N - 1, 1), dtype=I32)
            u3 = jax.random.uniform(k3, dtype=jnp.float32)
            ph = jnp.sum(i >= ed) - 1
            r3 = jnp.minimum(jnp.sum(u3 >= cdf[ph]).astype(I32), kpn - 1)
            if rw:
                u4 = jax.random.uniform(k4, dtype=jnp.float32)
                return u1, r2, r3, u4
            return u1, r2, r3

        return jax.vmap(ev)(jnp.arange(n_events))

    return jax.vmap(one)(seed, edges, zcdf)


def plan_for_run(B, P, n_events, T, N, K, *, R: int = 0,
                 tile: int = DEFAULT_TILE,
                 ev_chunk: int = DEFAULT_EV_CHUNK, interpret=None,
                 representation: str = "auto",
                 lat_samples: int = LAT_SAMPLES,
                 vmem_budget: int | None = None,
                 device_kind: str | None = None,
                 hl: bool = False, rw: bool = False) -> vmem.VmemPlan:
    """Resolve representation/budget, clamp (tile, ev_chunk) exactly like
    ``run_events`` will, and record the resulting VMEM plan.

    This is the *single* clamping+planning code path — ``run_events`` goes
    through it at trace time, and ``batch._exec_bucket`` calls it per
    dispatch so ``exec_stats()["vmem_plan"]`` stays populated even when
    the jitted kernel call is a cache hit (planning is python-level and
    does not re-run inside a cached executable).
    """
    if interpret is None:
        interpret = default_interpret()
    repr32 = resolve_representation(representation, interpret) == "i32pair"
    if vmem_budget is None and not interpret:
        vmem_budget = vmem.budget_for(device_kind or _device_kind())
    tile = max(1, min(tile, B))
    # same grid-dim count, minimal edge padding: B=9, tile=8 pads 7 rows
    # of dead kernel work; tile=5 runs the same two tiles padding 1
    tile = -(-B // -(-B // tile))
    ev_chunk = max(1, min(ev_chunk, max(n_events, 1)))
    align = 1
    if not interpret:
        # Mosaic blocks: the replica (sublane) dim is a multiple of 8 or
        # the whole batch, the event (lane) dim a multiple of 128 or the
        # whole padded run; rows and events are padded to match
        align = SUBLANES
        if tile < B:
            tile = min(-(-tile // SUBLANES) * SUBLANES, B)
        if ev_chunk < n_events:
            ev_chunk = max(LANES, ev_chunk // LANES * LANES)
    # price the VMEM footprint up front: shrink the replica tile to fit
    # the budget (or raise actionably) instead of dying inside Mosaic
    plan = vmem.plan_vmem(tile=tile, ev_chunk=ev_chunk, T=T, N=N, K=K, P=P,
                          lat_samples=lat_samples, repr32=repr32, R=R,
                          hl=hl, rw=rw, budget=vmem_budget, align=align)
    vmem.note_plan(plan)
    return plan


def _pallas_events(alg, T, N, K, n_events, wl, thread_node, lock_node, *,
                   tile, ev_chunk, interpret, repr32, lat_samples,
                   vmem_budget, device_kind):
    """Shared pallas_call builder: returns the raw kernel outputs
    (clock outputs as (hi, lo) pairs when ``repr32``)."""
    B = wl.seed.shape[0]
    P = wl.edges.shape[1]
    R = wl.arr_fix.shape[-1]
    kpn = K // N
    is_hl = alg == "hlock"
    is_rw = alg == "alock-rw"
    streams = list(precompute_draws(wl.seed, wl.edges, wl.zcdf, n_events,
                                    N, kpn, rw=is_rw))

    plan = plan_for_run(B, P, n_events, T, N, K, R=R, tile=tile,
                        ev_chunk=ev_chunk, interpret=interpret,
                        representation="i32pair" if repr32 else "i64",
                        lat_samples=lat_samples, vmem_budget=vmem_budget,
                        device_kind=device_kind, hl=is_hl, rw=is_rw)
    tile, ev_chunk = plan.tile, plan.ev_chunk
    pad_b = -B % tile
    pad_e = -n_events % ev_chunk

    def prep(a):
        a = jnp.asarray(a)
        return jnp.pad(a, ((0, pad_b),) + ((0, 0),) * (a.ndim - 1),
                       mode="edge") if pad_b else a

    streams = [jnp.pad(prep(a), ((0, 0), (0, pad_e))) if pad_e
               else prep(a) for a in streams]
    # per-phase payloads ride flattened to 2D blocks (P*T / P*2 / P*8
    # lanes); the kernel reshapes them back — P is static via the shape
    locp = prep(wl.locality.reshape(B, P * T))
    actp = prep(wl.active.reshape(B, P * T))
    binit = prep(jnp.asarray(wl.b_init).reshape(B, P * 2))
    costp = prep(jnp.asarray(wl.cost_rows, I32).reshape(B, P * N_COST_ROWS))
    nmult = prep(jnp.asarray(wl.node_mult, jnp.float32).reshape(B, P * N))
    edges, think = (prep(a) for a in (wl.edges, wl.think_ns))
    if is_rw:
        readf = prep(jnp.asarray(wl.read_frac,
                                 jnp.float32).reshape(B, P * T))
    if is_hl:
        rackp = prep(jnp.asarray(wl.rack, I32))
    if R:
        # open loop: the arrival plan is state-independent, so it is
        # precomputed here with the *same* shared repro.traffic.stream
        # helpers the XLA loop traces — the arrival times ride in as a
        # clock-typed input and come back out verbatim as output #7
        aplan = jax.vmap(lambda w: arrival_plan(w, n_events))(wl)
        if repr32:
            arr = jax.vmap(arrival_times_pairs)(aplan.gaps)
            arr_in = [prep(arr[0]), prep(arr[1])]
        else:
            arr = jax.vmap(arrival_times_i64)(aplan.gaps)
            arr_in = [prep(arr)]
        tokp = prep(jnp.asarray(aplan.tok, I32))
        tokcp = prep(jnp.asarray(aplan.tokcum, I32))
        qcapp = prep(jnp.asarray(aplan.qcap, I32))
    Bp = B + pad_b
    n_chunks = (n_events + pad_e) // ev_chunk
    grid = (Bp // tile, n_chunks)

    def row(w):
        return pl.BlockSpec((tile, w), lambda i, j: (i, _ZERO))

    def clock_out(w):
        """One (Bp, w) i64 output, or an (hi, lo) pair of i32 outputs."""
        if repr32:
            return ([row(w), row(w)],
                    [jax.ShapeDtypeStruct((Bp, w), I32)] * 2)
        return [row(w)], [jax.ShapeDtypeStruct((Bp, w), I64)]

    def clock_scratch(w):
        if repr32:
            return [pltpu.VMEM((tile, w), I32)] * 2
        return [pltpu.VMEM((tile, w), I64)]

    lat_specs, lat_shapes = clock_out(lat_samples)
    tend_specs, tend_shapes = clock_out(1)
    out_specs = ([row(T)] + lat_specs + [row(1)] + tend_specs
                 + [row(1), row(1)])
    out_shape = ([jax.ShapeDtypeStruct((Bp, T), I32)] + lat_shapes
                 + [jax.ShapeDtypeStruct((Bp, 1), I32)] + tend_shapes
                 + [jax.ShapeDtypeStruct((Bp, 1), I32),
                    jax.ShapeDtypeStruct((Bp, 1), I32)])
    if R:
        wq_specs, wq_shapes = clock_out(R)
        soj_specs, soj_shapes = clock_out(R)
        out_specs += wq_specs + soj_specs + [row(R)]
        out_shape += (wq_shapes + soj_shapes
                      + [jax.ShapeDtypeStruct((Bp, R), I32)])
    scratch_shapes = [
        pltpu.VMEM((tile, K), I32),   # tail0 / lock word
        pltpu.VMEM((tile, K), I32),   # tail1
        pltpu.VMEM((tile, K), I32),   # victim
        pltpu.VMEM((tile, T), I32),   # pc
        pltpu.VMEM((tile, T), I32),   # budget
        pltpu.VMEM((tile, T), I32),   # nxt
        pltpu.VMEM((tile, T), I32),   # prev
        pltpu.VMEM((tile, T), I32),   # target
        pltpu.VMEM((tile, T), I32),   # cohort
        # alock-rw reader counts ride between the semantic and clock
        # scratch (matching the kernel's unpack and vmem.buffer_table)
        *([pltpu.VMEM((tile, K), I32)] if is_rw else []),
        *clock_scratch(T),            # ready
        *clock_scratch(N),            # busy
        *clock_scratch(T),            # op_start
    ]
    in_specs = (
        [pl.BlockSpec((tile, ev_chunk), lambda i, j: (i, j))] * len(streams)
        + [row(P), row(P), row(P * T)]
        + ([row(P * T)] if is_rw else [])          # read_frac rows
        + [row(P * T), row(P * 2), row(P * N_COST_ROWS), row(P * N),
           pl.BlockSpec((1, T), lambda i, j: (_ZERO, _ZERO)),
           pl.BlockSpec((1, K), lambda i, j: (_ZERO, _ZERO))]
        + ([row(N)] if is_hl else []))             # rack row
    operands = [*streams,
                jnp.asarray(edges, I32), jnp.asarray(think, I32),
                jnp.asarray(locp, jnp.float32)]
    if is_rw:
        operands += [jnp.asarray(readf, jnp.float32)]
    operands += [jnp.asarray(actp, I32),
                 jnp.asarray(binit, I32), jnp.asarray(costp, I32),
                 jnp.asarray(nmult, jnp.float32),
                 jnp.asarray(thread_node, I32)[None, :],
                 jnp.asarray(lock_node, I32)[None, :]]
    if is_hl:
        operands += [jnp.asarray(rackp, I32)]
    if R:
        in_specs += [row(R)] * (len(arr_in) + 3)
        operands += [*arr_in, tokp, tokcp, qcapp]
        scratch_shapes += [pltpu.VMEM((tile, T), I32),   # curreq
                           pltpu.VMEM((tile, 1), I32),   # arrptr
                           pltpu.VMEM((tile, 1), I32)]   # qlen

    out = pl.pallas_call(
        functools.partial(event_loop_kernel, alg=alg, T=T, N=N, K=K, P=P,
                          n_events=n_events, ev_chunk=ev_chunk,
                          lat_samples=lat_samples, repr32=repr32, R=R),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        compiler_params=(None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=vmem.limit_for(device_kind or _device_kind()))),
    )(*operands)

    out = [o[:B] for o in out]
    if repr32:
        (done, lat_hi, lat_lo, lat_n, te_hi, te_lo, nreacq, npass,
         *extra) = out
        base = (done, (lat_hi, lat_lo), lat_n[:, 0],
                (te_hi[:, 0], te_lo[:, 0]), nreacq[:, 0], npass[:, 0])
        if R:
            wq_hi, wq_lo, soj_hi, soj_lo, rstat = extra
            return base + (arr, (wq_hi, wq_lo), (soj_hi, soj_lo), rstat)
        return base
    done, lat, lat_n, t_end, nreacq, npass, *extra = out
    base = (done, lat, lat_n[:, 0], t_end[:, 0], nreacq[:, 0],
            npass[:, 0])
    if R:
        wq, soj, rstat = extra
        return base + (arr, wq, soj, rstat)
    return base


def run_events(alg, T, N, K, n_events, wl, thread_node, lock_node, *,
               tile: int = DEFAULT_TILE, ev_chunk: int = DEFAULT_EV_CHUNK,
               interpret=None, representation: str = "auto",
               lat_samples: int = LAT_SAMPLES,
               vmem_budget: int | None = None,
               device_kind: str | None = None):
    """Batched Pallas event loop; must run under ``jax.enable_x64(True)``
    (the int64 output contract — use :func:`run_events_pairs` to stay in
    pure int32 with x64 off).

    ``wl`` is a ``WorkloadOperands`` with a leading replica axis B on
    every leaf: locality (B,P,T) f32, zcdf (B,P,K//N) f32, edges (B,P)
    i32, think_ns (B,P) i32, active (B,P,T) i32, b_init (B,P,2) i32,
    cost_rows (B,P,8) i32, node_mult (B,P,N) f32, seed (B,) i32;
    thread_node (T,)/lock_node (K,) broadcast. Returns (done (B,T) i32,
    lat (B,lat_samples) i64, lat_n (B,) i32, t_end (B,) i64,
    nreacq (B,) i32, npass (B,) i32).

    Open-loop workloads (``wl.arr_fix`` non-empty, R request slots) return
    four extra arrays mirroring ``sim._run_events``: arr (B,R) i64 arrival
    times, wq (B,R) i64 queue waits, soj (B,R) i64 sojourns (-1 when never
    dispatched/completed) and rstat (B,R) i32 ``repro.traffic`` status
    codes.

    B need not divide the replica tile and n_events need not divide the
    event chunk: replicas are edge-padded (duplicates, sliced off) and the
    final chunk masks events past n_events inside the kernel. The tile may
    additionally be shrunk by the VMEM planner (see module docstring);
    ``vmem_budget=None`` means the default budget for native lowering and
    unconstrained under interpret.
    """
    if interpret is None:
        interpret = default_interpret()
    repr32 = resolve_representation(representation, interpret) == "i32pair"
    B = wl.seed.shape[0]
    R = wl.arr_fix.shape[-1]
    if n_events < 1:
        # degenerate run: match the XLA loop's 0-iteration outputs instead
        # of tracing a zero-size grid (which Pallas rejects obscurely)
        base = (jnp.zeros((B, T), I32),
                jnp.full((B, lat_samples), -1, I64), jnp.zeros(B, I32),
                jnp.zeros(B, I64), jnp.zeros(B, I32), jnp.zeros(B, I32))
        if R:
            aplan = jax.vmap(lambda w: arrival_plan(w, n_events))(wl)
            arr = jax.vmap(arrival_times_i64)(aplan.gaps)
            return base + (arr, jnp.full((B, R), -1, I64),
                           jnp.full((B, R), -1, I64),
                           jnp.zeros((B, R), I32))
        return base
    out = _pallas_events(alg, T, N, K, n_events, wl, thread_node,
                         lock_node, tile=tile, ev_chunk=ev_chunk,
                         interpret=interpret, repr32=repr32,
                         lat_samples=lat_samples, vmem_budget=vmem_budget,
                         device_kind=device_kind)
    if repr32:
        done, lat, lat_n, t_end, nreacq, npass = out[:6]
        base = (done, p32.pack(lat), lat_n, p32.pack(t_end), nreacq, npass)
        if R:
            arr, wq, soj, rstat = out[6:]
            return base + (p32.pack(arr), p32.pack(wq), p32.pack(soj),
                           rstat)
        return base
    return out


def run_events_pairs(alg, T, N, K, n_events, wl, thread_node, lock_node, *,
                     tile: int = DEFAULT_TILE,
                     ev_chunk: int = DEFAULT_EV_CHUNK, interpret=None,
                     lat_samples: int = LAT_SAMPLES,
                     vmem_budget: int | None = None,
                     device_kind: str | None = None):
    """The hi/lo representation end to end — no int64 anywhere, so it runs
    with x64 off (the TPU vector constraint the x64-off CI leg emulates).

    Returns (done (B,T) i32, (lat_hi, lat_lo) (B,lat_samples) i32 each,
    lat_n (B,) i32, (t_end_hi, t_end_lo) (B,) i32 each, nreacq (B,) i32,
    npass (B,) i32); combine pairs host-side with ``i32pair.pack_np``.
    Open-loop workloads append (arr, wq, soj) as (hi, lo) pairs of
    (B,R) i32 each plus rstat (B,R) i32.
    """
    if interpret is None:
        interpret = default_interpret()
    B = wl.seed.shape[0]
    R = wl.arr_fix.shape[-1]
    if n_events < 1:
        z1 = jnp.zeros(B, I32)
        base = (jnp.zeros((B, T), I32),
                (jnp.full((B, lat_samples), -1, I32),
                 jnp.full((B, lat_samples), -1, I32)),
                z1, (z1, z1), z1, z1)
        if R:
            aplan = jax.vmap(lambda w: arrival_plan(w, n_events))(wl)
            arr = jax.vmap(arrival_times_pairs)(aplan.gaps)
            m1 = p32.pfull((B, R), -1)
            return base + (arr, m1, m1, jnp.zeros((B, R), I32))
        return base
    return _pallas_events(alg, T, N, K, n_events, wl, thread_node,
                          lock_node, tile=tile, ev_chunk=ev_chunk,
                          interpret=interpret, repr32=True,
                          lat_samples=lat_samples, vmem_budget=vmem_budget,
                          device_kind=device_kind)


_jit_run_events = functools.partial(
    jax.jit, static_argnames=("alg", "T", "N", "K", "n_events", "tile",
                              "ev_chunk", "interpret", "representation",
                              "lat_samples", "vmem_budget"))(run_events)


def run_events_jit(alg, T, N, K, n_events, wl, thread_node, lock_node, *,
                   tile: int = DEFAULT_TILE,
                   ev_chunk: int = DEFAULT_EV_CHUNK, interpret=None,
                   representation: str = "auto",
                   lat_samples: int = LAT_SAMPLES,
                   vmem_budget: int | None = None):
    """Jitted ``run_events`` with the environment-dependent knobs resolved
    *eagerly*, so they participate in the jit cache key — a cached
    executable traced under ``"auto"`` would otherwise silently ignore a
    later ``REPRO_EVENT_CLOCKS`` change (the env read inside a jitted
    function only happens at trace time)."""
    if interpret is None:
        interpret = default_interpret()
    representation = resolve_representation(representation, interpret)
    return _jit_run_events(alg, T, N, K, n_events, wl, thread_node,
                           lock_node, tile=tile, ev_chunk=ev_chunk,
                           interpret=interpret,
                           representation=representation,
                           lat_samples=lat_samples,
                           vmem_budget=vmem_budget)
