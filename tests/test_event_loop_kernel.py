"""Event-loop Pallas kernel vs the XLA oracle (bitwise), sharded/chunked
sweep vs the single-dispatch layout (bitwise), and the workload-draw
satellites (Zipf CDF operand, topology ValueError)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import batch
from repro.core.sim import SimConfig, simulate, topology, zipf_cdf

EV = 1500


def _assert_same(rx, rp):
    assert rx.ops == rp.ops
    assert rx.sim_ns == rp.sim_ns
    assert rx.reacquires == rp.reacquires
    assert rx.passes == rp.passes
    np.testing.assert_array_equal(np.asarray(rx.lat_ns),
                                  np.asarray(rp.lat_ns))
    np.testing.assert_array_equal(np.asarray(rx.per_thread_ops),
                                  np.asarray(rp.per_thread_ops))


@pytest.mark.parametrize("alg", ["alock", "spinlock", "mcs"])
@pytest.mark.parametrize("loc", [0.85, 1.0])
def test_pallas_simulate_bitwise_matches_xla(alg, loc):
    """The tentpole contract: same (config, seed) -> bitwise-identical
    done/lat/t_end through the Pallas kernel (interpret mode on CPU)."""
    cfg = SimConfig(alg, 2, 2, 8, loc, (2, 3), seed=7)
    _assert_same(simulate(cfg, n_events=EV, backend="xla"),
                 simulate(cfg, n_events=EV, backend="pallas"))


def test_pallas_bitwise_with_zipf_and_multi_node():
    cfg = SimConfig("alock", 3, 4, 6, 0.9, (5, 20), seed=3, zipf_s=1.2)
    _assert_same(simulate(cfg, n_events=EV, backend="xla"),
                 simulate(cfg, n_events=EV, backend="pallas"))


def _ragged_operands(B, ev):
    """A B-replica, 2-phase operand set with per-thread locality, a downed
    node, doubled phase-2 costs and fail-slow node multipliers — the
    nastiest shape the kernel's ragged tiling has to survive."""
    from repro.workloads import WorkloadOperands
    alg, N, tpn, K = "alock", 3, 4, 6
    T, P = N * tpn, 2
    tn, ln, costs = topology(alg, N, tpn, K)
    rng = np.random.default_rng(0)
    loc = rng.uniform(0.3, 1.0, (B, P, T)).astype(np.float32)
    zc = np.stack([[zipf_cdf(K // N, s) for s in row]
                   for row in rng.uniform(0.0, 2.0, (B, P))])
    active = np.ones((B, P, T), np.int32)
    active[:, 1, :tpn] = 0          # node 0 down in the second phase
    # per-phase budgets and cost rows: the second phase re-programs the
    # budget and doubles the RNIC service cost per replica
    cst = np.tile(np.int32(costs), (B, P, 1))
    cst[:, 1, 4:6] *= 2
    # fail-slow: node 2 limps at 4x in phase 1, then node 1 at 1.5x in
    # phase 2 — exercises the (P, N) node_mult operand across the phase edge
    nm = np.ones((B, P, N), np.float32)
    nm[:, 0, 2] = 4.0
    nm[:, 1, 1] = 1.5
    wl = WorkloadOperands(
        locality=jnp.asarray(loc), zcdf=jnp.asarray(np.float32(zc)),
        edges=jnp.asarray(np.tile(np.int32([0, 600]), (B, 1))),
        think_ns=jnp.asarray(np.tile(np.int32([500, 250]), (B, 1))),
        active=jnp.asarray(active),
        b_init=jnp.asarray(np.tile(np.int32([[2, 3], [1, 5]]), (B, 1, 1))),
        seed=jnp.arange(B, dtype=jnp.int32) + 11,
        cost_rows=jnp.asarray(cst), node_mult=jnp.asarray(nm),
        # closed-loop placeholders: R == 0 arrival rows
        arr_gap_ns=jnp.zeros((B, P), jnp.float32),
        arr_edges=jnp.zeros((B, P), jnp.int32),
        arr_qcap=jnp.full((B, P), np.iinfo(np.int32).max, jnp.int32),
        arr_token=jnp.zeros((B, P, 2), jnp.float32),
        arr_fix=jnp.zeros((B, 0), jnp.int32),
        rack=jnp.tile(jnp.arange(N, dtype=jnp.int32), (B, 1)),
        read_frac=jnp.zeros((B, P, T), jnp.float32))
    return alg, T, N, K, wl, tn, ln


def test_kernel_ragged_tile_and_chunk_bitwise():
    """Replica count not a tile multiple + events not a chunk multiple must
    pad internally and still match the vmapped XLA reference exactly —
    including per-thread locality, a mid-stream phase switch (crossing a
    chunk boundary) and a downed node."""
    from repro.kernels.event_loop.ops import run_events
    from repro.kernels.event_loop.ref import run_events_ref
    ev = 1100
    alg, T, N, K, wl, tn, ln = _ragged_operands(5, ev)
    with jax.enable_x64(True):
        ref = run_events_ref(alg, T, N, K, ev, wl, tn, ln)
        out = run_events(alg, T, N, K, ev, wl, tn, ln,
                         tile=2, ev_chunk=256, interpret=True)
    for a, b in zip(ref, out):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_native_grid_matches_per_replica_runs():
    """The replica axis folded into the Pallas grid (ragged tile) must be
    bitwise-equal to running every replica alone (B=1, tile=1) — replicas
    are independent lanes of one executable, and the grid fan-out may not
    couple them (the pre-fold layout was a vmap of single-replica runs)."""
    from repro.kernels.event_loop.ops import run_events
    ev, B = 700, 5           # ev crosses the 600 phase edge, ragged chunk
    alg, T, N, K, wl, tn, ln = _ragged_operands(B, ev)
    with jax.enable_x64(True):
        out = run_events(alg, T, N, K, ev, wl, tn, ln,
                         tile=2, ev_chunk=256, interpret=True)
        singles = [run_events(
            alg, T, N, K, ev,
            jax.tree_util.tree_map(lambda a, i=i: a[i:i + 1], wl), tn, ln,
            tile=1, ev_chunk=256, interpret=True) for i in range(B)]
    for j, o in enumerate(out):
        cat = np.concatenate([np.asarray(s[j]) for s in singles])
        np.testing.assert_array_equal(np.asarray(o), cat)


def test_sweep_pallas_backend_matches_xla():
    cfgs = [SimConfig("mcs", 2, 2, 8, 0.9, seed=1),
            SimConfig("alock", 2, 2, 8, 0.95, (2, 3), seed=5)]
    rx = batch.sweep(cfgs, n_seeds=2, n_events=EV, backend="xla")
    rp = batch.sweep(cfgs, n_seeds=2, n_events=EV, backend="pallas")
    for a, b in zip(rx, rp):
        np.testing.assert_array_equal(a.ops, b.ops)
        np.testing.assert_array_equal(a.sim_ns, b.sim_ns)
        np.testing.assert_array_equal(a.lat_ns, b.lat_ns)
        np.testing.assert_array_equal(a.per_thread_ops, b.per_thread_ops)


def test_sweep_chunked_matches_unsharded_and_counts_dispatches():
    """A bucket larger than the chunk spills into power-of-two superchunk
    dispatches of one shared runner; results stay bitwise-equal to the
    one-dispatch layout."""
    cfgs = [SimConfig("alock", 2, 2, 8, l, (2, 3), seed=s, zipf_s=z)
            for l, s, z in ((0.9, 7, 0.0), (0.5, 1, 1.2), (0.95, 3, 0.0))]
    base = batch.sweep(cfgs, n_seeds=2, n_events=EV)      # bucket B = 6
    batch.reset_exec_stats()
    ch = batch.sweep(cfgs, n_seeds=2, n_events=EV, chunk=2)
    st = batch.exec_stats()
    # 3 units of 2 rows coalesce into superchunks [2, 1]: one dispatch
    # of 4 rows + one of 2 rows (popcount(3)), not 3 unit dispatches
    assert st["dispatches"] == 2
    for b, c in zip(base, ch):
        np.testing.assert_array_equal(b.ops, c.ops)
        np.testing.assert_array_equal(b.sim_ns, c.sim_ns)
        np.testing.assert_array_equal(b.lat_ns, c.lat_ns)
        np.testing.assert_array_equal(b.per_thread_ops, c.per_thread_ops)
    # same chunk shapes again: zero new compiles, only dispatches
    batch.reset_exec_stats()
    batch.sweep(cfgs, n_seeds=2, n_events=EV, chunk=2)
    st2 = batch.exec_stats()
    assert st2["dispatches"] == 2 and st2["compiles"] == 0


def test_sweep_three_bucket_ragged_counts_and_bitwise():
    """Dispatch/compile accounting across a 3-bucket ragged sweep under the
    pipelined path: per bucket (6, 4, 2 rows at chunk=2) the superchunk
    decomposition is [4, 2] / [4] / [2] rows — 4 dispatches, one compile
    per distinct (runner, rows) shape, zero compiles on the rerun — and
    results stay bitwise-equal to the unsharded layout."""
    ev = EV - 100     # own shape keys: no executable reuse across tests
    cfgs = ([SimConfig("alock", 2, 2, 8, l, (2, 3), seed=i)
             for i, l in enumerate((0.85, 0.9, 1.0))]
            + [SimConfig("mcs", 2, 2, 8, l, seed=3 + i)
               for i, l in enumerate((0.5, 0.95))]
            + [SimConfig("spinlock", 2, 2, 8, 0.9, seed=5)])
    assert len({batch.shape_key(c, ev) for c in cfgs}) == 3
    base = batch.sweep(cfgs, n_seeds=2, n_events=ev)
    batch.reset_exec_stats()
    ch = batch.sweep(cfgs, n_seeds=2, n_events=ev, chunk=1)
    st = batch.exec_stats()
    # alock bucket: 6 rows -> units [4, 2] -> 2 dispatches; mcs: 4 rows
    # -> [4] -> 1; spinlock: 2 rows -> [2] -> 1
    assert st["dispatches"] == 4
    # each bucket runner compiles once per distinct row count it saw:
    # alock {4, 2}, mcs {4}, spinlock {2} -> 4 executables
    assert st["compiles"] == 4
    for b, c in zip(base, ch):
        np.testing.assert_array_equal(b.lat_ns, c.lat_ns)
        np.testing.assert_array_equal(b.ops, c.ops)
        np.testing.assert_array_equal(b.per_thread_ops, c.per_thread_ops)
    batch.reset_exec_stats()
    batch.sweep(cfgs, n_seeds=2, n_events=ev, chunk=1)
    st2 = batch.exec_stats()
    assert st2["dispatches"] == 4 and st2["compiles"] == 0


def test_sweep_devices_path_matches_unsharded():
    """devices= routes through the shard_map runner (1-device mesh on CPU
    CI) and must not perturb results."""
    cfgs = [SimConfig("spinlock", 2, 2, 8, 0.9, seed=2)]
    base = batch.sweep(cfgs, n_seeds=2, n_events=EV)
    shd = batch.sweep(cfgs, n_seeds=2, n_events=EV, devices=jax.devices())
    np.testing.assert_array_equal(base[0].lat_ns, shd[0].lat_ns)
    np.testing.assert_array_equal(base[0].ops, shd[0].ops)


# ---------------------------------------------------------------------------
# satellites: Zipf workload + topology validation


def test_zipf_cdf_properties():
    u = zipf_cdf(8, 0.0)
    np.testing.assert_allclose(u, np.arange(1, 9) / 8.0, rtol=1e-6)
    z = zipf_cdf(8, 1.5)
    assert z.dtype == np.float32
    assert np.all(np.diff(z) > 0) and z[-1] == pytest.approx(1.0)
    # skew concentrates mass on the first ranks
    assert z[0] > u[0]
    with pytest.raises(ValueError):
        zipf_cdf(0, 1.0)


def test_zipf_skew_changes_contention():
    """zipf_s rides the traced axis: same shape bucket, different dynamics
    (heavier skew -> more contention on the hot lock)."""
    flat = SimConfig("alock", 2, 2, 8, 1.0, seed=0, zipf_s=0.0)
    hot = SimConfig("alock", 2, 2, 8, 1.0, seed=0, zipf_s=4.0)
    r0 = simulate(flat, n_events=6_000)
    r4 = simulate(hot, n_events=6_000)
    assert r0.ops > 0 and r4.ops > 0
    # with s=4 nearly every draw is the node's rank-0 lock; the serialized
    # hot lock completes fewer ops in the same event count
    assert r4.ops < r0.ops
    # and the two ride one executable (same shape key)
    assert batch.shape_key(flat, 6_000) == batch.shape_key(hot, 6_000)


def test_topology_rejects_uneven_lock_partition():
    with pytest.raises(ValueError, match=r"\(n_locks, n_nodes\)=\(7, 2\)"):
        topology("alock", 2, 2, 7)
    with pytest.raises(ValueError):
        simulate(SimConfig("alock", 3, 2, 8, 0.9), n_events=10)
