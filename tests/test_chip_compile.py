"""The event-loop kernel compiles for a TPU v5e (no chip attached).

Each case lowers the Pallas kernel natively (``interpret=False``) and
compiles it for one device of a described ``v5e:2x2`` topology: Mosaic
refuses here what it would refuse on the chip (unsupported primitives,
shape casts, misaligned blocks, 64-bit values, VMEM over the limit).
Compiling runs nothing, so these tests say nothing about results; the
interpret-mode equivalence tests and ``chip_smoke.py`` do.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library.
"""
import numpy as np
import pytest
import jax

from repro.core.sim import topology
from repro.experiments import fig5_workloads, scenario_workloads
from repro.kernels.event_loop import ops
from repro.workloads import (Workload, WorkloadOperands, lower,
                             pad_phases)

#: long enough for several event chunks and a ragged last one
EVENTS = 20_000


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device cannot be read back from the
    # persistent cache without a chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _bucket(workloads, n_seeds):
    """Lower and stack like ``batch.sweep`` does for one shape bucket."""
    lws = [lower(w, EVENTS) for w in workloads]
    key = lws[0].shape_key
    assert all(lw.shape_key == key for lw in lws)
    pmax = max(lw.operands.n_phases for lw in lws)
    rows = [pad_phases(lw.operands, pmax)._replace(
        seed=np.int32(int(lw.operands.seed) + s))
        for lw in lws for s in range(n_seeds)]
    wl = WorkloadOperands(*(np.stack([np.asarray(getattr(o, f)) for o in rows])
                            for f in WorkloadOperands._fields))
    return key, wl


def _compile(sharding, workloads, n_seeds, *, pairs=False, tile=None):
    key, wl = _bucket(workloads, n_seeds)
    alg, T, N, K, n_events, _ = key
    tn, ln, _ = topology(alg, N, T // N, K)
    kind = next(iter(sharding.device_set)).device_kind

    def spec(a):
        return jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                    sharding=sharding)

    kw = dict(interpret=False, device_kind=kind, tile=tile)
    if pairs:
        # the zero-int64 entry point, x64 off
        def fn(w, a, b):
            return ops.run_events_pairs(alg, T, N, K, n_events, w, a, b,
                                        **kw)
    else:
        # what batch.sweep runs: run_events under x64
        def fn(w, a, b):
            return ops.run_events(alg, T, N, K, n_events, w, a, b, **kw)
    with jax.enable_x64(not pairs):
        compiled = jax.jit(fn).lower(
            WorkloadOperands(*(spec(a) for a in wl)), spec(tn),
            spec(ln)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return ops.plan_for_run(wl.seed.shape[0], wl.edges.shape[1], n_events,
                            T, N, K, R=key[5], tile=tile, interpret=False,
                            device_kind=kind,
                            hl=alg == "hlock", rw=alg == "alock-rw")


@pytest.mark.parametrize("alg", ["alock", "mcs", "spinlock"])
def test_fig5_bucket_compiles(one_chip, alg):
    """The paper-fig5 shape (T=80, N=10, K=100) at 72 replicas: one
    derived tile of all 72 rows, within the budget unshrunk."""
    ws = [w for w in fig5_workloads() if w.alg == alg]
    plan = _compile(one_chip, ws, n_seeds=24)
    assert plan.tile == 72
    assert not plan.shrunk


def test_fig4_bucket_compiles(one_chip):
    """The Fig. 4 budget study (T=240, N=20, K=100): 14 budget/locality
    points x 4 seeds = 56 replicas in one derived tile."""
    points = ([(loc, b) for loc in (0.95, 0.9, 0.85)
               for b in ((5, 5), (5, 10), (5, 20))]
              + [(0.9, b) for b in ((1, 1), (2, 2), (2, 8), (2, 20),
                                    (20, 5))])
    ws = [Workload("alock", 20, 12, 100, locality=loc, b_init=b,
                   seed=1 + 100 * i) for i, (loc, b) in enumerate(points)]
    plan = _compile(one_chip, ws, n_seeds=4)
    assert plan.tile == 56
    assert not plan.shrunk


def test_sharded_24_row_shard_compiles(one_chip):
    """What each chip's kernel runs in the 4-chip fig5 sweep: 24 rows of
    one bucket (its 3 chunk units in one dispatch), one tile of 24."""
    ws = [w for w in fig5_workloads() if w.alg == "alock"]
    plan = _compile(one_chip, ws, n_seeds=8)
    assert plan.tile == 24 and plan.tiles == 1
    assert not plan.shrunk


def test_multi_phase_storm_compiles(one_chip):
    ws = [w for w in scenario_workloads("hot-key-storm") if w.alg == "alock"]
    assert max(lower(w, EVENTS).operands.n_phases for w in ws) == 3
    _compile(one_chip, ws, n_seeds=4)


def test_open_loop_bucket_compiles(one_chip):
    ws = [w for w in scenario_workloads("open-loop-ramp") if w.alg == "alock"]
    assert lower(ws[0], EVENTS).shape_key[5] == 256
    _compile(one_chip, ws, n_seeds=2)


def test_hlock_with_racks_compiles(one_chip):
    ws = [w for w in scenario_workloads("rack-locality") if w.alg == "hlock"]
    assert all(w.topology is not None for w in ws)
    _compile(one_chip, ws, n_seeds=2)


def test_alock_rw_compiles(one_chip):
    ws = [w for w in scenario_workloads("read-heavy") if w.alg == "alock-rw"]
    _compile(one_chip, ws, n_seeds=2)


def test_ycsb_b_bucket_compiles(one_chip):
    """The YCSB-B lock table (``bench/configs/ycsb-b-n10-k1000.json``):
    alock-rw on the fig5 cluster with 1,000 locks (past the 128 lanes of
    T = 80, so the active-lock table), Zipf 0.99 draws and the reader
    coin, 3 localities x 24 seeds = 72 replicas in one bucket."""
    ws = [Workload("alock-rw", 10, 8, 1000, locality=loc, zipf_s=0.99,
                   read_frac=0.95, b_init=(5, 20), seed=1 + 100 * i)
          for i, loc in enumerate((0.85, 0.95, 1.0))]
    plan = _compile(one_chip, ws, n_seeds=24)
    assert plan.tile == 72 and plan.lock_layout == "table"
    assert not plan.shrunk and plan.total_bytes <= plan.budget


def test_ycsb_a_bucket_compiles(one_chip):
    """The YCSB-A lock table (``bench/configs/ycsb-a-n10-k100k.json``):
    100,000 locks in the table's 128 slots, the Zipf draw by merge at
    10,000 ranks a node, read 0.5: one unshrunk tile of 72 rows, where
    the lanes of every lock shrank it to 16 and overflowed VMEM."""
    ws = [Workload("alock-rw", 10, 8, 100_000, locality=loc, zipf_s=0.99,
                   read_frac=0.5, b_init=(5, 20), seed=1 + 100 * i)
          for i, loc in enumerate((0.85, 0.95, 1.0))]
    plan = _compile(one_chip, ws, n_seeds=24)
    assert (plan.tile, plan.tiles, plan.lock_layout,
            plan.lock_slots) == (72, 1, "table", 128)
    assert not plan.shrunk and plan.total_bytes <= plan.budget


def test_ragged_batch_compiles(one_chip):
    """B=9 at a tile of 8: the tile stays a multiple of 8 and the rows
    pad to 16, a grid of two tiles."""
    plan = _compile(one_chip, fig5_workloads()[:1], n_seeds=9, tile=8)
    assert (plan.tile, plan.tiles, plan.padded_rows) == (8, 2, 7)


@pytest.mark.parametrize("B, grid", [(9, (9, 1, 0)), (130, (72, 2, 14))])
def test_derived_grid_compiles(one_chip, B, grid):
    """The derived tile, unshrunk: B=9 is one tile of the whole batch,
    Mosaic's other legal block besides a multiple of 8, so no row pads;
    B=130, past ``ops.MAX_TILE``, is two tiles of 72, 14 rows of them
    edge copies."""
    plan = _compile(one_chip, fig5_workloads()[:1], n_seeds=B)
    assert (plan.tile, plan.tiles, plan.padded_rows) == grid
    assert not plan.shrunk


def test_pairs_path_with_x64_off_compiles(one_chip):
    _compile(one_chip, fig5_workloads()[:1], n_seeds=8, pairs=True)
