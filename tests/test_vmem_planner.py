"""VMEM budget planner (`kernels/event_loop/vmem`): the bytes formula
matches the buffers the kernel actually allocates, oversize tiles
auto-shrink deterministically, impossible budgets raise an actionable
ValueError (never a Mosaic crash), and the chosen plan is reported through
``batch.exec_stats()`` — all with no TPU (interpret mode / pure python).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import batch
from repro.core.cost_model import N_COST_ROWS
from repro.core.sim import LAT_SAMPLES, topology
from repro.kernels.event_loop import vmem
from repro.kernels.event_loop.ops import (plan_for_run, run_events,
                                          run_events_pairs)
from repro.kernels.event_loop.ref import run_events_ref
from repro.workloads import Arrivals, Workload, WorkloadOperands, lower

ARGS = dict(tile=4, ev_chunk=256, T=12, N=3, K=6, P=2,
            lat_samples=LAT_SAMPLES)
#: ``buffer_table`` takes the kernel's shapes; ``lat_samples`` is not one
SHAPE = {k: v for k, v in ARGS.items() if k != "lat_samples"}


def test_buffer_table_matches_kernel_allocations():
    """The documented formula, buffer for buffer: every shape in the plan
    equals the block/scratch shape ``ops.run_events`` builds for the same
    parameters (the interpret-mode allocations the acceptance criterion
    points at)."""
    t = vmem.buffer_table(**SHAPE)
    tile, ev_chunk, T, N, K, P = (ARGS["tile"], ARGS["ev_chunk"],
                                  ARGS["T"], ARGS["N"], ARGS["K"],
                                  ARGS["P"])
    # inputs: the in_specs block shapes
    assert t["in.u1"][0] == (tile, ev_chunk)
    assert t["in.locality"][0] == (tile, P * T)
    assert t["in.cost_rows"][0] == (tile, P * N_COST_ROWS)
    assert t["in.thread_node"][0] == (1, T)
    assert t["in.lock_node"][0] == (1, K)
    # outputs: the per-event latency stream, an (hi, lo) i32 pair,
    # double-buffered like the draws; no (tile, lat_samples) ring is
    # priced
    stream = tile * ev_chunk * 8 * vmem.PIPELINE_FACTOR
    assert t["out.lat_stream.hi"][0] == (tile, ev_chunk)
    assert (t["out.lat_stream.hi"][1] + t["out.lat_stream.lo"][1]
            == stream)
    assert "out.lat_stream" not in t
    assert not any(LAT_SAMPLES in shape for shape, _ in t.values())
    # scratch: semantic i32 + clock buffers
    assert t["scr.tail0"] == ((tile, K), tile * K * 4)
    assert t["scr.pc"] == ((tile, T), tile * T * 4)
    assert t["scr.ready.hi"][1] + t["scr.ready.lo"][1] == tile * T * 8
    assert t["scr.busy.hi"][1] + t["scr.busy.lo"][1] == tile * N * 8
    # the double-buffered event streams carry the pipeline factor
    assert t["in.u1"][1] == tile * ev_chunk * 4 * vmem.PIPELINE_FACTOR
    # and the plan total is exactly the sum of the table
    plan = vmem.plan_vmem(**ARGS)
    assert plan.total_bytes == sum(b for _, b in t.values())


def test_plan_matches_measured_pallas_buffers(monkeypatch):
    """Measure, don't restate: intercept ``pl.pallas_call`` and diff the
    planner's table against the in/out/scratch buffers the kernel
    *actually* allocates in interpret mode — name for name, shape for
    shape, byte for byte."""
    from repro.kernels.event_loop import ops as el_ops
    captured = {}
    real = el_ops.pl.pallas_call

    def spy(kernel, **kw):
        captured.update(kw)
        return real(kernel, **kw)

    monkeypatch.setattr(el_ops.pl, "pallas_call", spy)
    wl, tn, ln, ev = _replicas("alock", ev=300, B=5)
    run_events_pairs("alock", 4, 2, 8, ev, wl, tn, ln, interpret=True,
                     tile=2, ev_chunk=128, lat_samples=512)
    plan = vmem.last_plan()
    t = plan.breakdown          # insertion-ordered: in.* / out.* / scr.*

    def names(prefix):
        return [k for k in t if k.startswith(prefix)]

    assert [t[k][0] for k in names("in.")] == \
        [s.block_shape for s in captured["in_specs"]]
    assert [t[k][0] for k in names("out.")] == \
        [s.block_shape for s in captured["out_specs"]]
    assert [t[k][0] for k in names("scr.")] == \
        [tuple(s.shape) for s in captured["scratch_shapes"]]
    # bytes = prod(shape) x 4 (every buffer is f32 or i32, clocks as hi/lo
    # pairs), x2 for the double-buffered event streams
    for k, (shape, nbytes) in t.items():
        factor = (vmem.PIPELINE_FACTOR
                  if k in ("in.u1", "in.r2", "in.r3", "out.lat_stream.hi",
                           "out.lat_stream.lo") else 1)
        assert nbytes == int(np.prod(shape)) * 4 * factor, k


def test_table_layout_prices_slot_lanes():
    """Above S = T rounded up to 128 lanes the planner prices the lock
    words at S lanes, not K: the YCSB-A bucket (K = 100,000, T = 80,
    alock-rw) is one unshrunk tile of 72 rows on the v5e budget, where K
    lanes shrank it to 16 rows in 5 tiles. No lock_node row is priced;
    the slots' ids and counts and each thread's lock node are."""
    t = vmem.buffer_table(72, 4096, 80, 10, 100_000, 1, rw=True)
    for name in ("scr.tail0", "scr.tail1", "scr.victim", "scr.word",
                 "scr.slot_lock", "scr.slot_count"):
        assert t[name] == ((72, 128), 72 * 128 * 4), name
    assert t["scr.lock_node"] == ((72, 80), 72 * 80 * 4)
    assert "in.lock_node" not in t
    assert not any(100_000 in shape for shape, _ in t.values())
    plan = plan_for_run(72, 1, 150_000, 80, 10, 100_000, rw=True,
                        interpret=False, device_kind="TPU v5 lite")
    assert (plan.tile, plan.tiles, plan.shrunk, plan.lock_layout,
            plan.lock_slots) == (72, 1, False, "table", 128)
    assert plan.as_dict()["lock_layout"] == "table"
    # at or below S a lock keeps a lane of its own
    d = vmem.buffer_table(72, 4096, 80, 10, 100, 1)
    assert d["scr.tail0"][0] == (72, 100)
    assert d["in.lock_node"][0] == (1, 100)
    assert "scr.slot_lock" not in d
    assert vmem.lock_layout(128, 80) == ("direct", 128)
    assert vmem.lock_layout(129, 80) == ("table", 128)
    assert vmem.lock_layout(1000, 240) == ("table", 256)


def test_table_plan_matches_measured_pallas_buffers(monkeypatch):
    """The table layout's buffers, measured as above: 256 locks on 2 x 2
    threads (S = 128), alock-rw."""
    from repro.kernels.event_loop import ops as el_ops
    captured = {}
    real = el_ops.pl.pallas_call

    def spy(kernel, **kw):
        captured.update(kw)
        return real(kernel, **kw)

    monkeypatch.setattr(el_ops.pl, "pallas_call", spy)
    ev = 300
    ws = [lower(Workload("alock-rw", 2, 2, 256, locality=0.9,
                         read_frac=0.5, seed=4 + s), ev) for s in range(3)]
    wl = WorkloadOperands(
        *(jnp.asarray(np.stack([np.asarray(getattr(w.operands, f))
                                for w in ws]))
          for f in WorkloadOperands._fields))
    tn, ln, _ = topology("alock-rw", 2, 2, 256)
    run_events_pairs("alock-rw", 4, 2, 256, ev, wl, tn, ln, interpret=True,
                     tile=2, ev_chunk=128, lat_samples=512)
    t = vmem.last_plan().breakdown
    assert vmem.last_plan().lock_layout == "table"
    for prefix, got in (("in.", [s.block_shape
                                 for s in captured["in_specs"]]),
                        ("out.", [s.block_shape
                                  for s in captured["out_specs"]]),
                        ("scr.", [tuple(s.shape)
                                  for s in captured["scratch_shapes"]])):
        assert [t[k][0] for k in t if k.startswith(prefix)] == got, prefix


def test_open_loop_plan_matches_measured_pallas_buffers(monkeypatch):
    """Same measurement, open loop: an ``R > 0`` run must surface the
    arrival rows, the per-request outputs and the dispatch scratch in the
    planner's table at their exact binding positions (the vmem-consistency
    lint diffs traced kernels against this order)."""
    from repro.kernels.event_loop import ops as el_ops
    captured = {}
    real = el_ops.pl.pallas_call

    def spy(kernel, **kw):
        captured.update(kw)
        return real(kernel, **kw)

    monkeypatch.setattr(el_ops.pl, "pallas_call", spy)
    arr = Arrivals(rate_per_us=2.0, max_requests=24, queue_cap=8,
                   token_rate_per_us=1.0, token_burst=4.0)
    ev = 300
    ws = [lower(Workload("alock", 2, 2, 8, locality=0.9, seed=4 + s,
                         arrivals=arr), ev) for s in range(3)]
    wl = WorkloadOperands(
        *(jnp.asarray(np.stack([np.asarray(getattr(w.operands, f))
                                for w in ws]))
          for f in WorkloadOperands._fields))
    tn, ln, _ = topology("alock", 2, 2, 8)
    run_events_pairs("alock", 4, 2, 8, ev, wl, tn, ln, interpret=True,
                     tile=2, ev_chunk=128, lat_samples=512)
    plan = vmem.last_plan()
    t = plan.breakdown
    for k in ("in.arr.hi", "in.arr.lo", "in.tok", "in.tokcum", "in.qcap",
              "out.wq.hi", "out.wq.lo", "out.soj.hi", "out.soj.lo",
              "out.rstat", "scr.curreq", "scr.arrptr", "scr.qlen"):
        assert k in t, k
    assert t["in.arr.hi"][0] == (2, 24)
    assert t["out.rstat"][0] == (2, 24)

    def names(prefix):
        return [k for k in t if k.startswith(prefix)]

    assert [t[k][0] for k in names("in.")] == \
        [s.block_shape for s in captured["in_specs"]]
    assert [t[k][0] for k in names("out.")] == \
        [s.block_shape for s in captured["out_specs"]]
    assert [t[k][0] for k in names("scr.")] == \
        [tuple(s.shape) for s in captured["scratch_shapes"]]


def test_plan_total_does_not_grow_with_lat_samples():
    """The ring is built outside the kernel: ``lat_samples`` changes no
    VMEM buffer, so the priced total is the same at any ring size."""
    totals = {vmem.plan_vmem(**dict(ARGS, lat_samples=n)).total_bytes
              for n in (64, 1024, LAT_SAMPLES, 4 * LAT_SAMPLES)}
    assert len(totals) == 1


def test_oversize_tile_auto_shrinks_deterministically():
    kw = dict(ARGS, tile=64)
    # ~11 KiB a tile row (the double-buffered draw and latency streams):
    # tile 64 needs ~700 KiB, so 256 KiB forces the 64 -> 32 -> 16 path
    budget = 256 * 2**10
    p1 = vmem.plan_vmem(budget=budget, **kw)
    p2 = vmem.plan_vmem(budget=budget, **kw)
    assert p1 == p2                       # deterministic
    assert p1.shrunk and p1.requested_tile == 64
    assert p1.tile < 64 and p1.total_bytes <= budget
    # halving: the next-larger tile would NOT have fit
    over = vmem.plan_vmem(**dict(kw, tile=p1.tile * 2))
    assert over.total_bytes > budget
    # the dict view benchmarks serialize
    d = p1.as_dict()
    assert d["shrunk"] and d["tile"] == p1.tile and d["budget"] == budget


def test_impossible_budget_raises_actionable_error():
    # tile 1 needs 11,268 B at ev_chunk 256; the hint names ev_chunk, the
    # knob that sizes the streamed blocks
    with pytest.raises(ValueError, match="ev_chunk"):
        vmem.plan_vmem(budget=10_000, **ARGS)
    # bad arguments are real errors too
    with pytest.raises(ValueError, match="tile"):
        vmem.plan_vmem(**dict(ARGS, tile=0))
    with pytest.raises(ValueError, match="budget"):
        vmem.plan_vmem(budget=0, **ARGS)


def _replicas(alg="alock", ev=700, B=1):
    ws = [lower(Workload(alg, 2, 2, 8, locality=0.9, seed=4 + s), ev)
          for s in range(B)]
    wl = WorkloadOperands(
        *(jnp.asarray(np.stack([np.asarray(getattr(w.operands, f))
                                for w in ws]))
          for f in WorkloadOperands._fields))
    tn, ln, _ = topology(alg, 2, 2, 8)
    return wl, tn, ln, ev


def test_budgeted_run_shrinks_tile_and_stays_bitwise():
    """An explicit budget that cannot hold the requested tile must shrink
    it — and the shrunk run stays bitwise-equal to the oracle (auto-shrink
    is never allowed to become a silent wrong answer)."""
    wl, tn, ln, ev = _replicas(B=6)
    lat_samples = 1024
    # ev_chunk=128 costs ~5.5 KiB per tile row (the double-buffered draw
    # and latency streams; lat_samples costs no VMEM): tile 3 needs
    # ~16 KiB, tile 1 ~5.4 KiB, so 12 KiB forces the 6 -> 3 -> 1 halving
    budget = 12 * 1024
    with jax.enable_x64(True):
        ref = [np.asarray(r) for r in
               run_events_ref("alock", 4, 2, 8, ev, wl, tn, ln,
                              lat_samples=lat_samples)]
        out = run_events("alock", 4, 2, 8, ev, wl, tn, ln, interpret=True,
                         tile=8, ev_chunk=128, lat_samples=lat_samples,
                         vmem_budget=budget)
    plan = vmem.last_plan()
    assert plan is not None and plan.shrunk
    assert plan.requested_tile == 6 and plan.tile == 1
    assert plan.total_bytes <= budget
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_plan_surfaces_through_exec_stats():
    batch.reset_exec_stats()
    assert batch.exec_stats()["vmem_plan"] is None
    wl, tn, ln, ev = _replicas("mcs")
    run_events_pairs("mcs", 4, 2, 8, ev, wl, tn, ln, interpret=True,
                     lat_samples=256, ev_chunk=256)
    st = batch.exec_stats()
    assert st["vmem_plan"] is not None
    assert st["vmem_plan"]["representation"] == "i32pair"
    assert st["vmem_plan"]["lat_samples"] == 256
    batch.reset_exec_stats()
    assert batch.exec_stats()["vmem_plan"] is None


def test_impossible_budget_through_run_events():
    """The planner error reaches the caller as ValueError, not a trace-
    or Mosaic-level failure."""
    wl, tn, ln, ev = _replicas()
    with pytest.raises(ValueError, match="budget"):
        run_events_pairs("alock", 4, 2, 8, ev, wl, tn, ln, interpret=True,
                         vmem_budget=1024)


def test_plan_for_run_minimizes_edge_padding():
    """The grid keeps its tile count but sheds dead edge rows: B=9 at a
    requested tile of 8 runs two tiles of 5 (pad 1), not 8+1 (pad 7);
    exact divisors and B <= tile stay untouched; and the VMEM halving
    composes with the minimized tile, not the requested one."""
    from repro.kernels.event_loop.ops import plan_for_run
    shape = dict(T=12, N=3, K=6)
    assert plan_for_run(9, 2, 64, tile=8, interpret=True,
                        **shape).tile == 5
    assert plan_for_run(5, 2, 64, tile=2, interpret=True,
                        **shape).tile == 2
    assert plan_for_run(9, 2, 64, tile=3, interpret=True,
                        **shape).tile == 3
    assert plan_for_run(6, 2, 64, tile=6, interpret=True,
                        **shape).tile == 6
    # budget pressure: 5 does not fit, one halving lands on 2 (which the
    # budget below is sized to fit exactly)
    fit2 = vmem.plan_vmem(tile=2, ev_chunk=64, P=2,
                          lat_samples=LAT_SAMPLES, **shape).total_bytes
    p = plan_for_run(9, 2, 64, tile=8, interpret=True, vmem_budget=fit2,
                     **shape)
    assert (p.requested_tile, p.tile, p.shrunk) == (5, 2, True)


def test_native_plan_respects_mosaic_block_rules():
    """Native lowering: the replica tile is a multiple of 8 or the whole
    batch, the event chunk a multiple of 128 or the whole run, and VMEM
    halving never leaves those rules (interpret mode keeps any tile)."""
    from repro.kernels.event_loop.ops import plan_for_run
    shape = dict(T=12, N=3, K=6)
    big = 1 << 30

    def native(B, n_events, **kw):
        kw.setdefault("vmem_budget", big)
        return plan_for_run(B, 2, n_events, interpret=False, **shape, **kw)

    assert native(9, 64, tile=8).tile == 8            # not 5: pads to 16
    assert native(5, 64, tile=2).tile == 5            # whole batch
    assert native(72, 64, tile=32).tile == 24         # 3 tiles, no padding
    assert native(9, 5000, ev_chunk=4000).ev_chunk == 3968
    assert native(9, 300, ev_chunk=4096).ev_chunk == 300   # whole run
    assert native(9, 300, ev_chunk=100).ev_chunk == 128
    # budget pressure halves 64 -> 32 -> 16 -> 8 and stops at 8
    at8 = vmem.plan_vmem(tile=8, ev_chunk=64, P=2,
                         lat_samples=LAT_SAMPLES, **shape).total_bytes
    p = native(64, 64, tile=64, vmem_budget=at8)
    assert (p.requested_tile, p.tile) == (64, 8)
    with pytest.raises(ValueError, match="tile=8"):
        native(64, 64, tile=64, vmem_budget=at8 - 1)


#: the cells' bucket shapes: (T, N, K, rw) of fig5, fig4 and ycsb-b
CELL_SHAPES = {"fig5": (80, 10, 100, False), "fig4": (240, 20, 100, False),
               "ycsb-b": (80, 10, 1000, True)}


def _v5e_plan(B, shape, **kw):
    """The native plan of a B-row bucket of a cell's shape at the v5e
    budget, over the cells' 150,000 events."""
    from repro.kernels.event_loop.ops import plan_for_run
    T, N, K, rw = CELL_SHAPES[shape]
    return plan_for_run(B, 1, 150_000, T, N, K, rw=rw, interpret=False,
                        device_kind="TPU v5 lite", **kw)


@pytest.mark.parametrize("shape", sorted(CELL_SHAPES))
@pytest.mark.parametrize("B, tile, tiles", [
    (8, 8, 1), (9, 9, 1), (16, 16, 1), (24, 24, 1), (56, 56, 1),
    (72, 72, 1),
    # past MAX_TILE: the fewest tiles, split evenly, rounded up to 8
    (130, 72, 2), (300, 104, 3)])
def test_derived_tile_fits_the_bucket(shape, B, tile, tiles):
    """With no ``tile=``, a bucket of up to ``MAX_TILE`` rows is one
    whole tile (no padding, whatever its rows: 9 is the whole batch);
    past it, the fewest tiles. The rule reads the rows alone, and every
    cell's tile fits the v5e budget unshrunk."""
    from repro.kernels.event_loop.ops import MAX_TILE
    plan = _v5e_plan(B, shape)
    assert (plan.rows, plan.tile, plan.tiles) == (B, tile, tiles)
    assert plan.padded_rows == tiles * tile - B
    assert not plan.shrunk and plan.total_bytes <= plan.budget
    assert (plan.tile == B) == (B <= MAX_TILE)


def test_explicit_tile_overrides_the_derived_one():
    """``tile=`` is the ceiling instead of ``MAX_TILE``: 72 rows at tile 8
    run nine tiles of 8, 24 rows at tile 16 two of 16 (8 padded)."""
    assert _v5e_plan(72, "fig5", tile=8).tile == 8
    assert _v5e_plan(24, "fig5", tile=16).tile == 16
    assert _v5e_plan(24, "fig5").tile == 24


def test_vmem_halving_composes_with_the_derived_tile():
    """A budget that cannot hold the derived tile of 72 halves the
    ceiling to 32 (a multiple of 8), and the 72 rows split evenly under
    it: three tiles of 24, none padded. Interpret mode derives the whole
    batch, unaligned, and never shrinks."""
    from repro.kernels.event_loop.ops import plan_for_run
    at32 = _v5e_plan(32, "ycsb-b").total_bytes
    p = _v5e_plan(72, "ycsb-b", vmem_budget=at32)
    assert (p.requested_tile, p.tile, p.shrunk) == (72, 24, True)
    assert (p.rows, p.tiles, p.padded_rows) == (72, 3, 0)
    assert p.total_bytes <= at32
    assert plan_for_run(200, 1, 64, 12, 3, 6, interpret=True).tile == 100


def test_vmem_budget_is_keyed_by_device_kind():
    """The native budget comes from one table keyed by device_kind: the
    v5e entry, and an error (not a default) for an unknown device."""
    limit = vmem.limit_for("TPU v5 lite")
    assert limit == vmem.VMEM_CAPACITY["TPU v5 lite"] * 3 // 4
    assert vmem.budget_for("TPU v5 lite") == 32 * 2**20
    for fn in (vmem.limit_for, vmem.budget_for):
        with pytest.raises(ValueError, match="cpu"):
            fn("cpu")
