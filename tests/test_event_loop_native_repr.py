"""Differential harness for the event-loop kernel's hi/lo clocks.

Runs the event-loop kernel, whose clocks are hi/lo i32 pairs (interpret
mode, mostly through ``run_events_pairs`` with **no** ``enable_x64``
anywhere near the kernel — the x64-off CI leg executes this file with
``JAX_ENABLE_X64=0`` to emulate the TPU i32-vector constraint) and
asserts bitwise equality with the XLA engine:

  * an alg x phased x zipf x churn operand matrix covering **all five
    algorithms** (alock, spinlock, mcs, the hierarchical hlock with a
    non-trivial rack topology, and the reader-writer alock-rw with
    non-uniform read mixes) with mid-chunk phase boundaries;
  * **every simulator scenario in the registry** (uniform-grid,
    hot-key-storm, mixed-locality, node-churn, paper-fig5, congested-nic,
    budget-ramp, limping-node, fail-slow-cascade, read-heavy,
    rack-locality, plus the open-loop open-loop-ramp and burst-storm,
    whose buckets carry R request slots and four extra per-request
    outputs) via ``repro.experiments.scenario_workloads``;
  * latency-ring overflow (``latn`` wrapping past ``lat_samples``), and
    both entry points (``run_events`` packed under x64,
    ``run_events_pairs`` packed on the host) at ragged derived tiles and
    through ring wraparound;
  * interpret mode traces the very kernel program native lowering
    traces, so these CPU tests check the chip's own kernel.

The XLA oracle still runs under a local ``jax.enable_x64(True)`` (its
clocks are real int64); pair outputs are packed host-side with
``i32pair.pack_np`` so the comparison itself never needs x64.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.sim import topology, zipf_cdf
from repro.experiments import scenario_names, scenario_workloads
from repro.kernels.event_loop import i32pair as p32
from repro.kernels.event_loop.ops import run_events, run_events_pairs
from repro.kernels.event_loop.ref import run_events_ref
from repro.workloads import (Arrivals, Phase, Workload, WorkloadOperands,
                             lower, pad_phases)

EV = 1100


#: engine output order; the last four rows only exist on open-loop (R > 0)
#: buckets — arr/wq/soj are clock-typed ((hi, lo) pairs on the pairs path)
#: and rstat is plain i32
OUT_NAMES = ("done", "lat", "lat_n", "t_end", "nreacq", "npass",
             "arr", "wq", "soj", "rstat")


def _pk(p):
    """(hi, lo) i32 pair -> np int64."""
    return p32.pack_np(np.asarray(p[0]), np.asarray(p[1]))


def _pack_outputs(out):
    """(done, (lat_hi, lat_lo), lat_n, (te_hi, te_lo), ...) -> np int64.

    Handles both the 6-output closed loop and the 10-output open loop
    (arr/wq/soj pairs packed, rstat passed through).
    """
    done, lat_p, lat_n, te_p, nreacq, npass, *extra = out
    base = (np.asarray(done), _pk(lat_p), np.asarray(lat_n), _pk(te_p),
            np.asarray(nreacq), np.asarray(npass))
    if extra:
        arr_p, wq_p, soj_p, rstat = extra
        base += (_pk(arr_p), _pk(wq_p), _pk(soj_p), np.asarray(rstat))
    return base


def _assert_bitwise(ref, got):
    assert len(ref) == len(got), (len(ref), len(got))
    for name, a, b in zip(OUT_NAMES, ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"mismatch in {name}")


def _run(entry, alg, T, N, K, ev, wl, tn, ln, **kw):
    """One interpret-mode kernel run as np int64 outputs: ``run_events``
    packs them under x64; ``run_events_pairs`` runs with x64 off and its
    pairs are packed on the host."""
    if entry == "run_events":
        with jax.enable_x64(True):
            return [np.asarray(r) for r in run_events(
                alg, T, N, K, ev, wl, tn, ln, interpret=True, **kw)]
    with jax.enable_x64(False):
        return list(_pack_outputs(run_events_pairs(
            alg, T, N, K, ev, wl, tn, ln, interpret=True, **kw)))


ENTRIES = ["run_events", "run_events_pairs"]


def _stack_operands(workloads, n_events, **lower_kw):
    """Lower specs, pad phases to the bucket max, stack a replica axis —
    the same layout ``batch.sweep`` builds for one shape bucket."""
    lowered = [lower(w, n_events, **lower_kw) for w in workloads]
    keys = {lw.shape_key for lw in lowered}
    assert len(keys) == 1, f"one bucket at a time, got {keys}"
    pmax = max(lw.operands.n_phases for lw in lowered)
    ops = [pad_phases(lw.operands, pmax) for lw in lowered]
    leaves = [np.stack([np.asarray(getattr(o, f)) for o in ops])
              for f in WorkloadOperands._fields]
    return lowered[0], WorkloadOperands(*(jnp.asarray(a) for a in leaves))


@pytest.mark.parametrize("alg", ["alock", "spinlock", "mcs", "hlock",
                                 "alock-rw"])
def test_native_repr_bitwise_phased_zipf_churn(alg):
    """The tentpole contract on handcrafted operands, for all five
    algorithms: per-thread locality, per-phase Zipf CDFs + cost rows +
    budgets, a downed node, and phase edges that land mid event-chunk —
    i32-pair kernel (x64 off) vs the int64 XLA loop, bitwise. hlock gets
    a non-trivial two-rack topology and alock-rw a *non-uniform*
    per-phase per-thread read_frac, so the new operands flip across the
    mid-chunk phase boundary too."""
    N, tpn, K = 3, 4, 6
    T, B, P = N * tpn, 5, 2
    tn, ln, costs = topology(alg, N, tpn, K)
    rng = np.random.default_rng(0)
    loc = rng.uniform(0.3, 1.0, (B, P, T)).astype(np.float32)
    zc = np.stack([[zipf_cdf(K // N, s) for s in row]
                   for row in rng.uniform(0.0, 2.0, (B, P))])
    active = np.ones((B, P, T), np.int32)
    active[:, 1, :tpn] = 0          # node 0 down in the second phase
    cst = np.tile(np.int32(costs), (B, P, 1))
    cst[:, 1, 4:6] *= 2
    # fail-slow: node 1 limps at 3x in the first phase only, so the
    # degradation operand flips across the mid-chunk phase edge too
    nm = np.ones((B, P, N), np.float32)
    nm[:, 0, 1] = 3.0
    # hlock: nodes 0+1 share a rack, node 2 is alone (non-trivial tiers);
    # others get the trivial every-node-its-own-rack topology
    rack = (np.int32([0, 0, 1]) if alg == "hlock"
            else np.arange(N, dtype=np.int32))
    # alock-rw: read-light first phase, read-heavy second, jittered per
    # thread; inert zeros for every other algorithm
    if alg == "alock-rw":
        rf = np.concatenate([rng.uniform(0.1, 0.3, (1, T)),
                             rng.uniform(0.8, 1.0, (1, T))]
                            ).astype(np.float32)
        rf = np.tile(rf, (B, 1, 1))
    else:
        rf = np.zeros((B, P, T), np.float32)
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
        rack=jnp.asarray(np.tile(rack, (B, 1))),
        read_frac=jnp.asarray(rf))
    with jax.enable_x64(True):
        ref = [np.asarray(r) for r in
               run_events_ref(alg, T, N, K, EV, wl, tn, ln)]
    # the phase edge at 600 falls mid-chunk (600 % 256 != 0)
    out = run_events_pairs(alg, T, N, K, EV, wl, tn, ln,
                           tile=2, ev_chunk=256, interpret=True)
    _assert_bitwise(ref, _pack_outputs(out))


def test_node_mult_phase_edge_mid_chunk_bitwise():
    """Fail-slow satellite: a phase program whose *only* difference across
    the boundary is ``node_mult`` (node 0 healthy -> 4x limp), with the
    edge landing mid event-chunk (605 % 256 != 0) — i32-pair kernel (x64
    off) vs the int64 XLA loop, bitwise, through the full spec -> lower ->
    pad path."""
    w = Workload("alock", n_nodes=4, threads_per_node=3, n_locks=8,
                 locality=0.8, seed=9,
                 phases=(Phase(frac=0.55),
                         Phase(frac=0.45, node_mult="limp-node0-4x")))
    lw = lower(w, EV)
    alg, T, N, K, _, _ = lw.shape_key
    tn, ln, _ = topology(alg, N, T // N, K)
    wl = WorkloadOperands(*(jnp.asarray(a)[None] for a in lw.operands))
    with jax.enable_x64(True):
        ref = [np.asarray(r) for r in
               run_events_ref(alg, T, N, K, EV, wl, tn, ln)]
    out = run_events_pairs(alg, T, N, K, EV, wl, tn, ln,
                           tile=1, ev_chunk=256, interpret=True)
    _assert_bitwise(ref, _pack_outputs(out))
    # the limp is observable: the degraded half really runs slower than a
    # healthy clone of the same spec (sanity, not bitwise)
    healthy = lower(w.replace(phases=(Phase(frac=0.55), Phase(frac=0.45))),
                    EV)
    wl_h = WorkloadOperands(*(jnp.asarray(a)[None] for a in healthy.operands))
    with jax.enable_x64(True):
        ref_h = [np.asarray(r) for r in
                 run_events_ref(alg, T, N, K, EV, wl_h, tn, ln)]
    assert ref[3][0] > ref_h[3][0]      # t_end grows under the limp


def test_read_frac_phase_edge_mid_chunk_bitwise():
    """Reader-writer satellite: an alock-rw phase program whose read mix
    flips from a scalar read-light phase to a *per-thread* read-heavy
    tuple, with the edge landing mid event-chunk (605 % 256 != 0) —
    i32-pair kernel (x64 off) vs the int64 XLA loop, bitwise, through the
    full spec -> lower -> pad path."""
    T = 12
    heavy = tuple(0.7 + 0.02 * t for t in range(T))   # non-uniform row
    w = Workload("alock-rw", n_nodes=4, threads_per_node=3, n_locks=8,
                 locality=0.8, seed=9,
                 phases=(Phase(frac=0.55, read_frac=0.15),
                         Phase(frac=0.45, read_frac=heavy)))
    lw = lower(w, EV)
    alg, T, N, K, _, _ = lw.shape_key
    tn, ln, _ = topology(alg, N, T // N, K)
    wl = WorkloadOperands(*(jnp.asarray(a)[None] for a in lw.operands))
    # the lowered operand really is non-uniform across the phase edge
    rf = np.asarray(lw.operands.read_frac)
    assert rf.shape == (2, T)
    assert np.all(rf[0] == np.float32(0.15)) and len(set(rf[1])) == T
    with jax.enable_x64(True):
        ref = [np.asarray(r) for r in
               run_events_ref(alg, T, N, K, EV, wl, tn, ln)]
    out = run_events_pairs(alg, T, N, K, EV, wl, tn, ln,
                           tile=1, ev_chunk=256, interpret=True)
    _assert_bitwise(ref, _pack_outputs(out))
    # the mix is observable: a near-read-only clone of the same spec
    # completes ops at a higher simulated rate than a writer-only clone
    # (readers share the CS; sanity, not bitwise)
    rates = {}
    for tag, mix in (("rd", 0.99), ("wr", 0.0)):
        lc = lower(w.replace(phases=(Phase(frac=0.55, read_frac=mix),
                                     Phase(frac=0.45, read_frac=mix))), EV)
        wl_c = WorkloadOperands(*(jnp.asarray(a)[None] for a in lc.operands))
        with jax.enable_x64(True):
            ref_c = [np.asarray(r) for r in
                     run_events_ref(alg, T, N, K, EV, wl_c, tn, ln)]
        rates[tag] = ref_c[0].sum() / float(ref_c[3][0])
    assert rates["rd"] > rates["wr"]


def test_registry_scenarios_bitwise_i32pair():
    """Acceptance gate: every simulator scenario in the registry is
    bitwise-identical through the i32-pair kernel. Workloads are grouped
    into shape buckets (one ref + one kernel compile per bucket) exactly
    like a production sweep; lat_samples is shrunk so the interpret-mode
    ring stays cheap (both engines get the same value)."""
    ev, lat_samples = 400, 512
    sim_scenarios = {}
    for name in scenario_names():
        ws = scenario_workloads(name)
        if ws is None:
            assert name == "coord-stress"   # only the threaded coord plane
            continue
        sim_scenarios[name] = ws
    assert set(sim_scenarios) == {
        "uniform-grid", "hot-key-storm", "mixed-locality", "node-churn",
        "paper-fig5", "congested-nic", "budget-ramp", "limping-node",
        "fail-slow-cascade", "open-loop-ramp", "burst-storm",
        "read-heavy", "rack-locality"}
    assert any(w.arrivals is not None
               for ws in sim_scenarios.values() for w in ws)
    # the registry really sweeps all five algorithms, including the
    # hierarchical lock (non-trivial topology) and the reader-writer
    # variant (non-zero read mixes)
    algs = {w.alg for ws in sim_scenarios.values() for w in ws}
    assert algs == {"alock", "spinlock", "mcs", "hlock", "alock-rw"}
    assert any(w.topology is not None
               for w in sim_scenarios["rack-locality"])
    assert any(w.alg == "alock-rw" and float(np.max(w.read_frac)) > 0
               for w in sim_scenarios["read-heavy"])

    buckets: dict[tuple, list] = {}
    for name, ws in sim_scenarios.items():
        for w in ws:
            buckets.setdefault(lower(w, ev).shape_key, []).append((name, w))

    for key, items in buckets.items():
        alg, T, N, K, _, R = key
        tn, ln, _ = topology(alg, N, T // N, K)
        _, wl = _stack_operands([w for _, w in items], ev)
        with jax.enable_x64(True):
            ref = [np.asarray(r) for r in
                   run_events_ref(alg, T, N, K, ev, wl, tn, ln,
                                  lat_samples=lat_samples)]
        # ev_chunk=192: the scenarios' phase edges (ev * 0.3/0.34/0.4...)
        # all land mid-chunk
        out = run_events_pairs(alg, T, N, K, ev, wl, tn, ln, tile=3,
                               ev_chunk=192, interpret=True,
                               lat_samples=lat_samples)
        got = _pack_outputs(out)
        assert len(ref) == len(got) == (10 if R else 6), key
        for i, (name, w) in enumerate(items):
            for fname, a, b in zip(OUT_NAMES, ref, got):
                np.testing.assert_array_equal(
                    a[i], b[i],
                    err_msg=f"scenario {name} workload {i} ({w.alg}): "
                            f"{fname} diverged")


def test_ring_overflow_identical_across_engines():
    """latn wrapping past lat_samples: ring contents and p50/p99
    aggregates must match on the XLA engine and the i32-pair kernel."""
    alg, N, tpn, K, lat_samples = "alock", 2, 2, 8, 64
    T = N * tpn
    ev = 2500                       # ~400 completions >> 64 slots
    tn, ln, _ = topology(alg, N, tpn, K)
    w = lower(Workload(alg, N, tpn, K, locality=0.9, seed=3), ev)
    wl = WorkloadOperands(*(jnp.asarray(a)[None] for a in w.operands))
    with jax.enable_x64(True):
        ref = [np.asarray(r) for r in
               run_events_ref(alg, T, N, K, ev, wl, tn, ln,
                              lat_samples=lat_samples)]
    pair = _pack_outputs(run_events_pairs(
        alg, T, N, K, ev, wl, tn, ln, interpret=True,
        lat_samples=lat_samples, ev_chunk=512))

    assert ref[2][0] > 2 * lat_samples      # the ring really wrapped
    assert (ref[1] >= 0).all()              # ... and every slot was filled
    _assert_bitwise(ref, pair)
    assert np.percentile(pair[1][0], 50) == np.percentile(ref[1][0], 50)
    assert np.percentile(pair[1][0], 99) == np.percentile(ref[1][0], 99)


def _ring_bucket(alg, ev):
    """Three replicas of one bucket (B=3, so a tile of 2 pads a row) on
    2 nodes x 2 threads with one lock per node. The middle replica's
    critical section costs 2**30 ns: its holders stay inside while the
    other threads spin through every event, so it completes nothing."""
    N, tpn, K = 2, 2, 2
    topo = (0, 0) if alg == "hlock" else None     # one rack: three tiers
    _, wl = _stack_operands([Workload(alg, N, tpn, K, locality=0.8,
                                      seed=21 + s, topology=topo)
                             for s in range(3)], ev)
    cost = np.array(wl.cost_rows)
    cost[1, :, 2] = 2**30                         # the CS cost row
    tn, ln, _ = topology(alg, N, tpn, K)
    return N * tpn, N, K, wl._replace(cost_rows=jnp.asarray(cost)), tn, ln


#: XLA oracle outputs by (alg, lat_samples), shared by both entry
#: points' cases of the wraparound test
_RING_REFS: dict = {}


def _ring_ref(alg, ev, lat_samples):
    if (alg, lat_samples) not in _RING_REFS:
        T, N, K, wl, tn, ln = _ring_bucket(alg, ev)
        with jax.enable_x64(True):
            _RING_REFS[alg, lat_samples] = [
                np.asarray(r) for r in
                run_events_ref(alg, T, N, K, ev, wl, tn, ln,
                               lat_samples=lat_samples)]
    return _RING_REFS[alg, lat_samples]


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("alg", ["alock", "mcs", "spinlock", "hlock"])
def test_streamed_ring_wraparound_bitwise(alg, entry):
    """The ring ``ops.py`` builds from the kernel's per-event latency
    stream equals the XLA engine's ring bitwise, whatever the ring size:
    completions that fill exactly ``lat_samples``, one more and one fewer
    than it, and three or more wraps; with a ragged last event chunk
    (700 % 256 != 0), B=3 against a tile of 2, and a replica that
    completes nothing."""
    ev = 700
    T, N, K, wl, tn, ln = _ring_bucket(alg, ev)
    n = _ring_ref(alg, ev, 8)[2]            # lat_n: any ring size gives it
    assert n[1] == 0 and n[0] >= 40, n      # the idle replica, and wraps
    for lat_samples in (n[0], n[0] - 1, n[0] + 1, n[0] // 3 - 1):
        ref = _ring_ref(alg, ev, int(lat_samples))
        got = _run(entry, alg, T, N, K, ev, wl, tn, ln, tile=2,
                   ev_chunk=256, lat_samples=int(lat_samples))
        _assert_bitwise(ref, got)
        assert (ref[1][1] == -1).all()      # the idle replica's ring


#: XLA oracle outputs by algorithm, shared by both entry points' cases
#: of the derived-tile test
_TILE_REFS: dict = {}


def _ragged_bucket(alg):
    """B=15 replicas of one bucket on 2 nodes x 2 threads, 4 locks:
    distinct seeds and localities; hlock on one rack (three tiers),
    alock-rw with a read mix."""
    topo = (0, 0) if alg == "hlock" else None
    rf = 0.6 if alg == "alock-rw" else 0.0
    _, wl = _stack_operands([Workload(alg, 2, 2, 4, locality=0.6 + s / 40,
                                      read_frac=rf, topology=topo,
                                      seed=31 + s)
                             for s in range(15)], 400)
    tn, ln, _ = topology(alg, 2, 2, 4)
    return wl, tn, ln


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("alg", ["alock", "mcs", "spinlock", "alock-rw",
                                 "hlock"])
def test_derived_tile_bitwise(alg, entry):
    """The derived tile (all 15 ragged rows in one tile) and a tile of 8
    (two tiles, the second padded by an edge copy) give outputs equal to
    each other and to the XLA engine, bit for bit: replica rows are
    independent whatever tile they share."""
    wl, tn, ln = _ragged_bucket(alg)
    if alg not in _TILE_REFS:
        with jax.enable_x64(True):
            _TILE_REFS[alg] = [np.asarray(r) for r in run_events_ref(
                alg, 4, 2, 4, 400, wl, tn, ln)]
    for tile in (None, 8):
        got = _run(entry, alg, 4, 2, 4, 400, wl, tn, ln, tile=tile,
                   ev_chunk=256)
        _assert_bitwise(_TILE_REFS[alg], got)


def test_packed_run_events_i32pair_matches_i64():
    """The public ``run_events`` contract: under x64 it returns the
    kernel's pairs packed to int64, field by field what the x64-off
    ``run_events_pairs`` gives packed on the host with ``pack_np``."""
    alg, N, tpn, K, ev = "mcs", 2, 2, 8, 900
    T = N * tpn
    tn, ln, _ = topology(alg, N, tpn, K)
    w = lower(Workload(alg, N, tpn, K, locality=0.85, seed=5), ev)
    wl = WorkloadOperands(*(jnp.asarray(a)[None] for a in w.operands))
    with jax.enable_x64(True):
        a = [np.asarray(r) for r in run_events(alg, T, N, K, ev, wl, tn, ln,
                                               interpret=True)]
    with jax.enable_x64(False):
        b = _pack_outputs(run_events_pairs(alg, T, N, K, ev, wl, tn, ln,
                                           interpret=True))
    assert len(a) == len(b) == 6
    for name, x, y in zip(OUT_NAMES, a, b):
        assert x.dtype == y.dtype, (name, x.dtype, y.dtype)
        np.testing.assert_array_equal(x, y, err_msg=name)
    assert a[1].dtype == a[3].dtype == np.int64


def _kernel_jaxpr(alg, T, N, K, ev, wl, tn, ln, interpret):
    """The ``pallas_call``'s kernel jaxpr of one ``run_events`` trace."""
    def f(w, a, b):
        return run_events(alg, T, N, K, ev, w, a, b, interpret=interpret,
                          tile=2, ev_chunk=128, vmem_budget=1 << 30,
                          device_kind="TPU v5 lite")
    with jax.enable_x64(True):
        jx = jax.make_jaxpr(f)(wl, tn, ln)
    calls = [e for e in jx.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1, [e.primitive.name for e in jx.jaxpr.eqns]
    return str(calls[0].params["jaxpr"])


@pytest.mark.parametrize("case", ["alock", "mcs", "spinlock", "hlock",
                                  "alock-rw", "open-loop"])
def test_interpreter_runs_the_chip_kernel_program(case):
    """Interpret mode traces the kernel program native lowering traces,
    equation for equation: the CPU tests check the chip's own kernel.
    Two replicas in one tile, a ragged last event chunk (300 % 128),
    and for open loop the arrival rows and per-request outputs."""
    alg = "alock" if case == "open-loop" else case
    arr = (Arrivals(rate_per_us=2.0, max_requests=24, queue_cap=8,
                    token_rate_per_us=1.0, token_burst=4.0)
           if case == "open-loop" else None)
    _, wl = _stack_operands([Workload(
        alg, 2, 2, 4, locality=0.8, seed=41 + s, arrivals=arr,
        read_frac=0.6 if alg == "alock-rw" else 0.0,
        topology=(0, 0) if alg == "hlock" else None) for s in range(2)],
        300)
    assert (wl.arr_fix.shape[-1] > 0) == (case == "open-loop")
    tn, ln, _ = topology(alg, 2, 2, 4)
    args = (alg, 4, 2, 4, 300, wl, tn, ln)
    assert (_kernel_jaxpr(*args, interpret=True)
            == _kernel_jaxpr(*args, interpret=False))
