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
from repro.kernels.event_loop.ops import run_events, run_events_pairs
from repro.kernels.event_loop.ref import run_events_ref
from repro.workloads import Arrivals, Workload, WorkloadOperands, lower

ARGS = dict(tile=4, ev_chunk=256, T=12, N=3, K=6, P=2,
            lat_samples=LAT_SAMPLES)


def test_buffer_table_matches_kernel_allocations():
    """The documented formula, buffer for buffer: every shape in the plan
    equals the block/scratch shape ``ops.run_events`` builds for the same
    parameters (the interpret-mode allocations the acceptance criterion
    points at)."""
    for repr32 in (False, True):
        t = vmem.buffer_table(repr32=repr32, **ARGS)
        tile, ev_chunk, T, N, K, P = (ARGS["tile"], ARGS["ev_chunk"],
                                      ARGS["T"], ARGS["N"], ARGS["K"],
                                      ARGS["P"])
        # inputs: the in_specs block shapes
        assert t["in.u1"][0] == (tile, ev_chunk)
        assert t["in.locality"][0] == (tile, P * T)
        assert t["in.cost_rows"][0] == (tile, P * N_COST_ROWS)
        assert t["in.thread_node"][0] == (1, T)
        assert t["in.lock_node"][0] == (1, K)
        # outputs: one i64 ring vs an (hi, lo) i32 pair, same total bytes
        if repr32:
            assert t["out.lat.hi"][0] == (tile, LAT_SAMPLES)
            assert (t["out.lat.hi"][1] + t["out.lat.lo"][1]
                    == tile * LAT_SAMPLES * 8)
            assert "out.lat" not in t
        else:
            assert t["out.lat"] == ((tile, LAT_SAMPLES),
                                    tile * LAT_SAMPLES * 8)
        # scratch: semantic i32 + clock buffers
        assert t["scr.tail0"] == ((tile, K), tile * K * 4)
        assert t["scr.pc"] == ((tile, T), tile * T * 4)
        ready = (t["scr.ready.hi"][1] + t["scr.ready.lo"][1] if repr32
                 else t["scr.ready"][1])
        assert ready == tile * T * 8
        busy = (t["scr.busy.hi"][1] + t["scr.busy.lo"][1] if repr32
                else t["scr.busy"][1])
        assert busy == tile * N * 8
        # the double-buffered event streams carry the pipeline factor
        assert t["in.u1"][1] == tile * ev_chunk * 4 * vmem.PIPELINE_FACTOR
        # and the plan total is exactly the sum of the table
        plan = vmem.plan_vmem(repr32=repr32, **ARGS)
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
    # bytes = prod(shape) x 4 (all buffers are f32/i32 pairs under the
    # native representation), x2 for the double-buffered event streams
    for k, (shape, nbytes) in t.items():
        factor = (vmem.PIPELINE_FACTOR
                  if k in ("in.u1", "in.r2", "in.r3") else 1)
        assert nbytes == int(np.prod(shape)) * 4 * factor, k


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


def test_plan_representations_cost_identical_bytes():
    """hi/lo i32 pairs occupy exactly the bytes of the i64 buffers they
    replace — switching representation must never change the footprint."""
    a = vmem.plan_vmem(repr32=False, **ARGS)
    b = vmem.plan_vmem(repr32=True, **ARGS)
    assert a.total_bytes == b.total_bytes


def test_oversize_tile_auto_shrinks_deterministically():
    kw = dict(ARGS, tile=64)
    budget = 4 * 2**20
    p1 = vmem.plan_vmem(repr32=True, budget=budget, **kw)
    p2 = vmem.plan_vmem(repr32=True, budget=budget, **kw)
    assert p1 == p2                       # deterministic
    assert p1.shrunk and p1.requested_tile == 64
    assert p1.tile < 64 and p1.total_bytes <= budget
    # halving: the next-larger tile would NOT have fit
    over = vmem.plan_vmem(repr32=True, **dict(kw, tile=p1.tile * 2))
    assert over.total_bytes > budget
    # the dict view benchmarks serialize
    d = p1.as_dict()
    assert d["shrunk"] and d["tile"] == p1.tile and d["budget"] == budget


def test_impossible_budget_raises_actionable_error():
    with pytest.raises(ValueError, match="lat_samples"):
        vmem.plan_vmem(repr32=True, budget=10_000, **ARGS)
    # bad arguments are real errors too
    with pytest.raises(ValueError, match="tile"):
        vmem.plan_vmem(repr32=True, **dict(ARGS, tile=0))
    with pytest.raises(ValueError, match="budget"):
        vmem.plan_vmem(repr32=True, budget=0, **ARGS)


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
    # 6 replicas at lat_samples=1024 / ev_chunk=128 cost ~12 KiB per tile
    # row; 24 KiB forces the 6 -> 3 -> 1 halving path
    budget = 24 * 1024
    with jax.enable_x64(True):
        ref = [np.asarray(r) for r in
               run_events_ref("alock", 4, 2, 8, ev, wl, tn, ln,
                              lat_samples=lat_samples)]
        out = run_events("alock", 4, 2, 8, ev, wl, tn, ln, interpret=True,
                         representation="i32pair", tile=8, ev_chunk=128,
                         lat_samples=lat_samples, vmem_budget=budget)
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
    fit2 = vmem.plan_vmem(tile=2, ev_chunk=64, P=2, repr32=True,
                          lat_samples=LAT_SAMPLES, **shape).total_bytes
    p = plan_for_run(9, 2, 64, tile=8, interpret=True,
                     representation="i32pair", vmem_budget=fit2, **shape)
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
    at8 = vmem.plan_vmem(tile=8, ev_chunk=64, P=2, repr32=True,
                         lat_samples=LAT_SAMPLES, **shape).total_bytes
    p = native(64, 64, tile=64, vmem_budget=at8)
    assert (p.requested_tile, p.tile) == (64, 8)
    with pytest.raises(ValueError, match="tile=8"):
        native(64, 64, tile=64, vmem_budget=at8 - 1)


def test_vmem_budget_is_keyed_by_device_kind():
    """The native budget comes from one table keyed by device_kind: the
    v5e entry, and an error (not a default) for an unknown device."""
    limit = vmem.limit_for("TPU v5 lite")
    assert limit == vmem.VMEM_CAPACITY["TPU v5 lite"] * 3 // 4
    assert vmem.budget_for("TPU v5 lite") == 32 * 2**20
    for fn in (vmem.limit_for, vmem.budget_for):
        with pytest.raises(ValueError, match="cpu"):
            fn("cpu")
