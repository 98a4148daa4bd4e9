"""Public entry points for the Pallas event-loop backend.

``run_events`` mirrors ``sim._run_events``'s batched contract (a
``WorkloadOperands`` struct whose leaves carry a leading replica axis B)
and returns the same tuple (done, lat, lat_n, t_end, nreacq, npass).
Replicas are padded to a tile multiple and tiled across the first grid
axis; events are padded to a chunk multiple and streamed along the second
(sequential) grid axis while the simulation state persists in VMEM
scratch. The kernel streams each event's latency out the same way, and
``latency_ring`` builds the ``(B, lat_samples)`` ring from that stream
after the ``pallas_call``, so ``lat_samples`` sizes no VMEM buffer.

Clock representation
  The kernel holds its 64-bit clock state as carry-correct hi/lo int32
  pairs (``i32pair.py``; see ``kernel.py``): Mosaic has no 64-bit
  vectors, and interpret mode runs the same kernel program as the chip.
  It is bitwise-equal to the XLA engine
  (``tests/test_event_loop_native_repr.py``). ``run_events`` packs the
  pair outputs into int64 under x64; ``run_events_pairs`` returns the raw
  (hi, lo) arrays and never touches an int64 — that is the entry point
  the x64-off CI leg exercises.

Replica tile
  Each grid tile runs its rows' events as one dependent chain of
  cross-lane reductions, and the tiles run one after another, so an
  event costs about one chain per tile whatever the rows in it: on a
  v5e one event of a tile took 1.12 us at 8 rows and 1.41 us at 24
  (``MAX_TILE`` holds the curve). ``plan_for_run`` therefore derives the
  tile from the bucket's rows B: the fewest tiles of at most
  ``MAX_TILE`` rows, split evenly so the edge padding is least, each a
  multiple of 8 rows or the whole batch under Mosaic. A bucket of up to
  ``MAX_TILE`` rows is one tile. ``tile=`` overrides the ceiling.

VMEM budget
  Before tracing, the ``vmem.py`` planner prices every VMEM buffer of one
  grid step and deterministically shrinks the tile's ceiling to fit
  ``vmem_budget`` bytes before the rows are split under it (default:
  ``vmem.budget_for`` the device's ``device_kind`` for native lowering —
  an unknown device is an error — unconstrained under interpret); the
  native ``pallas_call`` gets the device's ``vmem.limit_for`` as
  ``vmem_limit_bytes``, whatever the budget. ``device_kind`` names the
  target when compiling for a device that is not attached (default:
  ``jax.devices()[0]``). An impossible budget raises an actionable
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
...                               n_events=64, N=2)
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

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cost_model import N_COST_ROWS
from repro.core.sim import I32, LAT_SAMPLES
from repro.kernels.event_loop import i32pair as p32
from repro.kernels.event_loop import vmem
from repro.kernels.event_loop.kernel import event_loop_kernel
from repro.traffic.stream import arrival_plan, arrival_times_pairs

#: the most replica rows one grid tile holds. One event of one tile took,
#: on a v5e (us, kernel device time over 20,000 events): at T=80, K=100
#: (fig5 alock) 1.12 at 8 rows, 1.20 at 16, 1.41 at 24, 1.83 at 56, 2.26
#: at 72, 3.23 at 128 and 4.43 at 192; at K=1000 (alock-rw) 1.07 at 8,
#: 2.98 at 72 and 5.11 at 144; at T=240 1.13 at 8, 2.02 at 56 and 3.61 at
#: 128; open loop (R=256) 1.59 at 8 and 3.95 at 96. The cost per row
#: falls in every shape as far as measured, so a bucket runs best in the
#: fewest tiles. The ceiling is where the gain flattens: 192 rows gained
#: 8% per row over 128 (23.1 against 25.2 ns per replica-event), and 128
#: rows of the widest measured shape (K=1000, 27.8 MB priced) still fit
#: the v5e budget unhalved
MAX_TILE = 128
DEFAULT_EV_CHUNK = 4096
#: the (8, 128) vreg tiling Mosaic's block shapes must respect
SUBLANES = 8
LANES = 128
#: index-map constant: a python 0 would trace as int64 under x64, which
#: Mosaic refuses in the grid's index functions
_ZERO = np.int32(0)
#: the most ranks a node's Zipf draw compares with every event
#: (``zipf_compare``); above it the draw merges (``zipf_merge``). One
#: pass of 72 x 150,000 uniforms on a v5e took (ms, compare / merge) 0.71
#: / 42.95 at 5 ranks, 1.72 / 42.97 at 100, 30.34 / 42.82 at 1,000,
#: 77.52 / 43.71 at 2,000 and 384.15 / 45.64 at 10,000: the merge costs
#: its two sorts whatever the ranks, the compare grows with them
#: (``scripts/lock_layout_curve.py``)
ZIPF_COMPARE_RANKS = 1024
#: the named scope of the draw precompute and its stream padding: their
#: HLO ops carry it in their ``op_name`` metadata
DRAWS_SCOPE = "event_loop.draws"


def default_interpret() -> bool:
    """Native Mosaic lowering on TPU; interpreter everywhere else."""
    return jax.default_backend() != "tpu"


def _device_kind() -> str:
    """The kind of the device native lowering targets by default."""
    return jax.devices()[0].device_kind


def zipf_offset(cdf, ph, u):
    """Each event's within-node Zipf offset: the count of its phase's
    CDF entries at or below its uniform, clamped to ``kpn - 1``.

    ``cdf`` is one replica's (P, kpn) per-phase CDF, non-decreasing
    along each row; ``ph`` and ``u`` are (n,) phase indices and float32
    uniforms. Equal, bit for bit, to the XLA engine's per-event
    ``min(sum(u >= cdf[ph]), kpn - 1)``, in one of two forms derived from
    ``kpn`` (``ZIPF_COMPARE_RANKS``), neither of which gathers a CDF row
    per event: up to that many ranks, each phase's row is compared with
    every event in one fused reduction; above it, a merge of the events
    into the sorted CDF entries.

    >>> import numpy as np
    >>> cdf = np.float32([[0.25, 0.5, 0.75, 1.0]])
    >>> u = np.float32([0.0, 0.25, 0.3, 0.75, 0.99])
    >>> zipf_offset(cdf, np.zeros(5, np.int32), u).tolist()
    [0, 1, 1, 3, 3]
    >>> zipf_merge(cdf, np.zeros(5, np.int32), u).tolist()
    [0, 1, 1, 3, 3]
    """
    form = zipf_compare if cdf.shape[1] <= ZIPF_COMPARE_RANKS else zipf_merge
    return form(cdf, ph, u)


def zipf_compare(cdf, ph, u):
    """``zipf_offset`` by comparing every event with each phase's whole
    row: P * kpn compares an event, which XLA fuses into the count."""
    P, kpn = cdf.shape
    n = None
    for q in range(P):
        c = jnp.sum(u[:, None] >= cdf[q], axis=1, dtype=I32)
        n = c if n is None else jnp.where(ph == q, c, n)
    return jnp.minimum(n, kpn - 1)


def zipf_merge(cdf, ph, u):
    """``zipf_offset`` by merging the events into the CDF entries: one
    sort of both by (phase, value, tag), where a CDF entry's tag -1 puts
    it before an event of equal value (so ``u >= cdf`` counts it) and an
    event's tag is its index; a running count of the CDF entries gives
    each event its rank, and a second sort by tag puts the ranks back in
    event order. The values are float32 uniforms in [0, 1) and CDF
    entries above 0, so the sort's total order is the compare's."""
    P, kpn = cdf.shape
    n = u.shape[0]
    tag = jnp.concatenate([jnp.full(P * kpn, -1, I32),
                           jnp.arange(n, dtype=I32)])
    keys = (jnp.concatenate([jnp.reshape(cdf, (P * kpn,)), u]), tag)
    if P > 1:
        phase = jnp.concatenate([jnp.repeat(jnp.arange(P, dtype=I32), kpn),
                                 ph])
        keys = (phase,) + keys
    tag = lax.sort(keys, num_keys=len(keys))[-1]
    # the CDF entries at or before each position, earlier phases' too
    below = jnp.cumsum((tag < 0).astype(I32))
    rank = lax.sort((tag, below), num_keys=1)[1][P * kpn:]
    if P > 1:
        rank = rank - ph * kpn
    return jnp.minimum(rank, kpn - 1)


def precompute_draws(seed, edges, zcdf, n_events: int, N: int,
                     rw: bool = False):
    """The state-independent per-event draw stream, replica-batched.

    Returns (B, n_events) arrays (loc_uniform f32, remote_offset i32,
    zipf_offset i32) — exactly the values ``sim._run_events`` draws at
    event i from ``split(fold_in(key, i), 3)``. The Zipf inverse-CDF is
    resolved against the phase active at event i (phases are a pure
    function of the event index) by ``zipf_offset``, so consuming the
    stream in-kernel reproduces the XLA path bit for bit.
    ``rw=True`` mirrors the alock-rw engine's 4-way split and appends the
    reader/writer coin uniform (f32) as a fourth stream. Traced under
    ``DRAWS_SCOPE``.
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
            ph = jnp.sum(i >= ed, dtype=I32) - 1
            if rw:
                u4 = jax.random.uniform(k4, dtype=jnp.float32)
                return u1, r2, u3, ph, u4
            return u1, r2, u3, ph

        u1, r2, u3, ph, *u4 = jax.vmap(ev)(jnp.arange(n_events))
        return (u1, r2, zipf_offset(cdf, ph, u3), *u4)

    with jax.named_scope(DRAWS_SCOPE):
        return jax.vmap(one)(seed, edges, zcdf)


def plan_for_run(B, P, n_events, T, N, K, *, R: int = 0,
                 tile: int | None = None,
                 ev_chunk: int = DEFAULT_EV_CHUNK, interpret=None,
                 lat_samples: int = LAT_SAMPLES,
                 vmem_budget: int | None = None,
                 device_kind: str | None = None,
                 hl: bool = False, rw: bool = False) -> vmem.VmemPlan:
    """Resolve the budget, derive and clamp (tile, ev_chunk)
    exactly like ``run_events`` will, and return the resulting VMEM plan.

    The tile is derived from B (module docstring, "Replica tile"). The
    ceiling is ``MAX_TILE`` rows, or ``tile`` where given, rounded up to
    a multiple of 8 under Mosaic unless it is the whole batch; the VMEM
    planner halves that ceiling until the budget fits. B is then split
    evenly into the fewest tiles under the ceiling, ``ceil(B / tiles)``
    rows each, aligned the same way, so even a shrunk tile pads least.
    The plan records B as ``rows``, with the grid's ``tiles`` and
    ``padded_rows``; ``requested_tile`` is the tile without a budget.

    This is the *single* clamping+planning code path — ``run_events`` goes
    through it at trace time, and ``batch._exec_bucket`` calls it per
    dispatch, and to size its sharded dispatches. Planning records
    nothing: ``run_events`` and each dispatch of ``batch._exec_bucket``
    note the plan they run (``vmem.note_plan``), so
    ``exec_stats()["vmem_plans"]`` stays populated even when the jitted
    kernel call is a cache hit (planning is python-level and does not
    re-run inside a cached executable).

    >>> [plan_for_run(b, 1, 512, 80, 10, 100, interpret=False,
    ...               device_kind="TPU v5 lite").tile
    ...  for b in (8, 9, 24, 72, 130)]
    [8, 9, 24, 72, 72]
    """
    if interpret is None:
        interpret = default_interpret()
    if vmem_budget is None and not interpret:
        vmem_budget = vmem.budget_for(device_kind or _device_kind())
    cap = max(1, min(MAX_TILE if tile is None else tile, B))
    ev_chunk = max(1, min(ev_chunk, max(n_events, 1)))
    align = 1
    if not interpret:
        # Mosaic blocks: the replica (sublane) dim is a multiple of 8 or
        # the whole batch, the event (lane) dim a multiple of 128 or the
        # whole padded run; rows and events are padded to match
        align = SUBLANES
        if cap < B:
            cap = min(-(-cap // SUBLANES) * SUBLANES, B)
        if ev_chunk < n_events:
            ev_chunk = max(LANES, ev_chunk // LANES * LANES)

    def split(cap):
        # the fewest tiles of at most `cap` rows, split evenly: B=9 at
        # cap 8 runs two tiles either way, and a tile of 5 pads 1 row,
        # not 7. Never above an aligned cap, so never above its bytes
        t = -(-B // -(-B // cap))
        return t if t == B else min(-(-t // align) * align, B)

    # price the VMEM footprint up front: the budget sets the ceiling (or
    # raises actionably) instead of the kernel dying inside Mosaic
    shape = dict(ev_chunk=ev_chunk, T=T, N=N, K=K, P=P,
                 lat_samples=lat_samples, R=R, hl=hl, rw=rw,
                 budget=vmem_budget, align=align)
    fit = vmem.plan_vmem(tile=cap, **shape).tile
    return dataclasses.replace(vmem.plan_vmem(tile=split(fit), **shape),
                               requested_tile=split(cap), rows=B)


def latency_ring(stream, lat_n, lat_samples: int):
    """The ``(B, lat_samples)`` latency ring from the kernel's per-event
    stream: the same ring ``sim._run_events`` writes at ``lat_n %
    lat_samples`` on each completion, last write winning.

    ``stream`` is a tuple of ``(B, E)`` arrays, the words of one clock
    per event (the kernel's hi and lo), -1 where the event completed
    nothing; a latency is ``now - op_start >= 0``, so the
    first word's sign marks a completion. A completion's rank is the
    number of completions before it; only the last ``lat_samples`` ranks
    survive a wrap, so those are keyed by their slot ``rank %
    lat_samples`` and every other event by ``lat_samples`` plus its
    index. The keys are distinct, and sorting each row by them puts the
    ring in its first ``lat_samples`` lanes: the kept slots, then events
    that completed nothing (-1) where fewer than ``lat_samples`` did. On
    a v5e the sort takes about a fifth of the time of the equivalent
    ``scatter``.

    >>> import numpy as np
    >>> s = np.array([[5, -1, 7, 9, -1, 4]], np.int32)
    >>> np.asarray(latency_ring((s,), np.array([4]), 3)[0]).tolist()
    [[4, 7, 9]]
    """
    fin = stream[0] >= 0
    n = fin.astype(I32)
    rank = jnp.cumsum(n, axis=1, dtype=I32) - n
    keep = fin & (rank >= (lat_n - lat_samples)[:, None])
    key = jnp.where(keep, rank % lat_samples,
                    lat_samples + jax.lax.broadcasted_iota(I32, fin.shape, 1))
    _, *ring = jax.lax.sort((key, *stream), dimension=1, num_keys=1)
    pad = ((0, 0), (0, max(0, lat_samples - fin.shape[1])))
    return tuple(jnp.pad(w[:, :lat_samples], pad, constant_values=-1)
                 for w in ring)


def run_events_pairs(alg, T, N, K, n_events, wl, thread_node, lock_node, *,
                     tile: int | None = None,
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
    P = wl.edges.shape[1]
    R = wl.arr_fix.shape[-1]
    if n_events < 1:
        # degenerate run: match the XLA loop's 0-iteration outputs instead
        # of tracing a zero-size grid (which Pallas rejects obscurely)
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
    is_hl = alg == "hlock"
    is_rw = alg == "alock-rw"
    streams = list(precompute_draws(wl.seed, wl.edges, wl.zcdf, n_events,
                                    N, rw=is_rw))

    plan = plan_for_run(B, P, n_events, T, N, K, R=R, tile=tile,
                        ev_chunk=ev_chunk, interpret=interpret,
                        lat_samples=lat_samples, vmem_budget=vmem_budget,
                        device_kind=device_kind, hl=is_hl, rw=is_rw)
    vmem.note_plan(plan)
    tile, ev_chunk, pad_b = plan.tile, plan.ev_chunk, plan.padded_rows
    pad_e = -n_events % ev_chunk

    def prep(a):
        a = jnp.asarray(a)
        return jnp.pad(a, ((0, pad_b),) + ((0, 0),) * (a.ndim - 1),
                       mode="edge") if pad_b else a

    with jax.named_scope(DRAWS_SCOPE):
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
        arr = jax.vmap(arrival_times_pairs)(aplan.gaps)
        arr_in = [prep(arr[0]), prep(arr[1])]
        tokp = prep(jnp.asarray(aplan.tok, I32))
        tokcp = prep(jnp.asarray(aplan.tokcum, I32))
        qcapp = prep(jnp.asarray(aplan.qcap, I32))
    layout, L = vmem.lock_layout(K, T)
    table = layout == "table"
    Bp = B + pad_b
    n_chunks = (n_events + pad_e) // ev_chunk
    grid = (Bp // tile, n_chunks)

    def row(w):
        return pl.BlockSpec((tile, w), lambda i, j: (i, _ZERO))

    # a (tile, ev_chunk) block per grid step, streamed along the events
    chunk = pl.BlockSpec((tile, ev_chunk), lambda i, j: (i, j))

    def clock_out(w, spec=None):
        """An (hi, lo) pair of (Bp, w) i32 outputs."""
        spec = spec or row(w)
        return [spec, spec], [jax.ShapeDtypeStruct((Bp, w), I32)] * 2

    def clock_scratch(w):
        return [pltpu.VMEM((tile, w), I32)] * 2

    # each event's latency (-1: no completion), streamed out by chunk
    lat_specs, lat_shapes = clock_out(n_events + pad_e, chunk)
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
        pltpu.VMEM((tile, L), I32),   # tail0 / lock word
        pltpu.VMEM((tile, L), I32),   # tail1
        pltpu.VMEM((tile, L), I32),   # victim
        pltpu.VMEM((tile, T), I32),   # pc
        pltpu.VMEM((tile, T), I32),   # budget
        pltpu.VMEM((tile, T), I32),   # nxt
        pltpu.VMEM((tile, T), I32),   # prev
        pltpu.VMEM((tile, T), I32),   # target lock (direct) / slot (table)
        pltpu.VMEM((tile, T), I32),   # cohort
        # alock-rw reader counts ride between the semantic and clock
        # scratch (matching the kernel's unpack and vmem.buffer_table)
        *([pltpu.VMEM((tile, L), I32)] if is_rw else []),
        *clock_scratch(T),            # ready
        *clock_scratch(N),            # busy
        *clock_scratch(T),            # op_start
    ]
    in_specs = (
        [chunk] * len(streams)
        + [row(P), row(P), row(P * T)]
        + ([row(P * T)] if is_rw else [])          # read_frac rows
        + [row(P * T), row(P * 2), row(P * N_COST_ROWS), row(P * N),
           pl.BlockSpec((1, T), lambda i, j: (_ZERO, _ZERO))]
        + ([] if table else                        # lock_node row
           [pl.BlockSpec((1, K), lambda i, j: (_ZERO, _ZERO))])
        + ([row(N)] if is_hl else []))             # rack row
    operands = [*streams,
                jnp.asarray(edges, I32), jnp.asarray(think, I32),
                jnp.asarray(locp, jnp.float32)]
    if is_rw:
        operands += [jnp.asarray(readf, jnp.float32)]
    operands += [jnp.asarray(actp, I32),
                 jnp.asarray(binit, I32), jnp.asarray(costp, I32),
                 jnp.asarray(nmult, jnp.float32),
                 jnp.asarray(thread_node, I32)[None, :]]
    if not table:
        # the table layout takes a lock's node from the draw that chose it
        operands += [jnp.asarray(lock_node, I32)[None, :]]
    if is_hl:
        operands += [jnp.asarray(rackp, I32)]
    if R:
        in_specs += [row(R)] * 5
        operands += [*arr_in, tokp, tokcp, qcapp]
        scratch_shapes += [pltpu.VMEM((tile, T), I32),   # curreq
                           pltpu.VMEM((tile, 1), I32),   # arrptr
                           pltpu.VMEM((tile, 1), I32)]   # qlen
    if table:
        scratch_shapes += [pltpu.VMEM((tile, L), I32),   # slot lock ids
                           pltpu.VMEM((tile, L), I32),   # slot ref counts
                           pltpu.VMEM((tile, T), I32)]   # thread lock node

    out = pl.pallas_call(
        functools.partial(event_loop_kernel, alg=alg, T=T, N=N, K=K, P=P,
                          n_events=n_events, ev_chunk=ev_chunk, R=R,
                          table=table),
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
    (done, lat_hi, lat_lo, lat_n, te_hi, te_lo, nreacq, npass,
     *extra) = out
    base = (done, latency_ring((lat_hi, lat_lo), lat_n[:, 0], lat_samples),
            lat_n[:, 0], (te_hi[:, 0], te_lo[:, 0]), nreacq[:, 0],
            npass[:, 0])
    if R:
        wq_hi, wq_lo, soj_hi, soj_lo, rstat = extra
        return base + (arr, (wq_hi, wq_lo), (soj_hi, soj_lo), rstat)
    return base


def run_events(alg, T, N, K, n_events, wl, thread_node, lock_node, *,
               tile: int | None = None, ev_chunk: int = DEFAULT_EV_CHUNK,
               interpret=None, lat_samples: int = LAT_SAMPLES,
               vmem_budget: int | None = None,
               device_kind: str | None = None):
    """Batched Pallas event loop; must run under ``jax.enable_x64(True)``
    (the kernel's pair outputs packed to int64 — use
    :func:`run_events_pairs` to stay in pure int32 with x64 off).

    ``wl`` is a ``WorkloadOperands`` with a leading replica axis B on
    every leaf: locality (B,P,T) f32, zcdf (B,P,K//N) f32, edges (B,P)
    i32, think_ns (B,P) i32, active (B,P,T) i32, b_init (B,P,2) i32,
    cost_rows (B,P,8) i32, node_mult (B,P,N) f32, seed (B,) i32,
    rack (B,N) i32 (hlock), read_frac (B,P,T) f32 (alock-rw);
    thread_node (T,)/lock_node (K,) broadcast, lock_node read only in the
    direct lock layout (``vmem.lock_layout``: the active-lock table takes
    a lock's node from its draw). Returns (done (B,T) i32,
    lat (B,lat_samples) i64, lat_n (B,) i32, t_end (B,) i64,
    nreacq (B,) i32, npass (B,) i32).

    Open-loop workloads (``wl.arr_fix`` non-empty, R request slots) return
    four extra arrays mirroring ``sim._run_events``: arr (B,R) i64 arrival
    times, wq (B,R) i64 queue waits, soj (B,R) i64 sojourns (-1 when never
    dispatched/completed) and rstat (B,R) i32 ``repro.traffic`` status
    codes.

    ``tile=None`` derives the replica tile from B (module docstring,
    "Replica tile"). B need not divide the tile and n_events need not
    divide the event chunk: replicas are edge-padded (duplicates, sliced
    off) and the final chunk masks events past n_events inside the
    kernel. The tile may additionally be shrunk by the VMEM planner;
    ``vmem_budget=None`` means the default budget for native lowering and
    unconstrained under interpret.
    """
    out = run_events_pairs(alg, T, N, K, n_events, wl, thread_node,
                           lock_node, tile=tile, ev_chunk=ev_chunk,
                           interpret=interpret, lat_samples=lat_samples,
                           vmem_budget=vmem_budget, device_kind=device_kind)
    done, lat, lat_n, t_end, nreacq, npass = out[:6]
    base = (done, p32.pack(lat), lat_n, p32.pack(t_end), nreacq, npass)
    if len(out) > 6:
        arr, wq, soj, rstat = out[6:]
        return base + (p32.pack(arr), p32.pack(wq), p32.pack(soj), rstat)
    return base


_jit_run_events = functools.partial(
    jax.jit, static_argnames=("alg", "T", "N", "K", "n_events", "tile",
                              "ev_chunk", "interpret", "lat_samples",
                              "vmem_budget"))(run_events)


def run_events_jit(alg, T, N, K, n_events, wl, thread_node, lock_node, *,
                   tile: int | None = None,
                   ev_chunk: int = DEFAULT_EV_CHUNK, interpret=None,
                   lat_samples: int = LAT_SAMPLES,
                   vmem_budget: int | None = None):
    """``run_events`` compiled once per shape and static knob: the entry
    the sweep's unsharded dispatch and ``sim.simulate`` call. ``interpret``
    is resolved from the default backend *before* the jit boundary, so
    the executable is keyed by the mode it was traced in."""
    if interpret is None:
        interpret = default_interpret()
    return _jit_run_events(alg, T, N, K, n_events, wl, thread_node,
                           lock_node, tile=tile, ev_chunk=ev_chunk,
                           interpret=interpret, lat_samples=lat_samples,
                           vmem_budget=vmem_budget)
