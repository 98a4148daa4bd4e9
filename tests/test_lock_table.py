"""The event-loop kernel's active-lock table (``kernel.py``, "Lock
layout"): above S = T rounded up to 128 lanes the kernel holds S slots
of lock state instead of K lanes, and every output stays bitwise-equal
to the XLA engine's dense state; at or below S it keeps the direct
layout, whose kernel program is unchanged.

* all five machines at a K above S (hlock on two racks), and with a
  table of 8 slots forced on 48 locks, where threads share slots and
  reuse them for other locks all the time (open loop too);
* the machine invariant the table rests on: a lock that no thread holds
  has idle tails and reader count;
* the benchmark's direct-layout buckets trace the kernel program they
  traced before the table existed, hash for hash.
"""
import hashlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import machine as mc
from repro.core.sim import init_sem, sem_step, topology
from repro.kernels.event_loop import ops, vmem
from repro.kernels.event_loop.ops import run_events, run_events_pairs
from repro.kernels.event_loop.ref import run_events_ref
from repro.workloads import (Arrivals, Workload, WorkloadOperands, lower,
                             pad_phases)

ALGS = ["alock", "spinlock", "mcs", "hlock", "alock-rw"]
EV = 900


def _bucket(alg, N, tpn, K, seeds=3, arrivals=None):
    """One bucket of ``seeds`` replicas with skewed, mostly remote draws,
    so threads collide on hot locks; hlock on two racks, alock-rw at a
    read 0.5."""
    topo = (tuple(n * 2 // N for n in range(N)) if alg == "hlock"
            else None)
    lws = [lower(Workload(alg, N, tpn, K, locality=0.5, zipf_s=1.5,
                          read_frac=0.5 if alg == "alock-rw" else 0.0,
                          topology=topo, arrivals=arrivals, seed=17 + s),
                 EV) for s in range(seeds)]
    pmax = max(lw.operands.n_phases for lw in lws)
    ops = [pad_phases(lw.operands, pmax) for lw in lws]
    wl = WorkloadOperands(*(jnp.asarray(np.stack(
        [np.asarray(getattr(o, f)) for o in ops]))
        for f in WorkloadOperands._fields))
    tn, ln, _ = topology(alg, N, tpn, K)
    return (alg, N * tpn, N, K, EV, wl, tn, ln)


def _pk(p):
    return (np.asarray(p[0]).astype(np.int64) << 32) | (
        np.asarray(p[1]).astype(np.int64) & 0xFFFFFFFF)


def _assert_kernel_equals_xla(args):
    with jax.enable_x64(True):
        ref = [np.asarray(r) for r in run_events_ref(*args)]
    out = run_events_pairs(*args, interpret=True, ev_chunk=256)
    got = [np.asarray(out[0]), _pk(out[1]), np.asarray(out[2]),
           _pk(out[3]), np.asarray(out[4]), np.asarray(out[5])]
    if len(out) > 6:
        got += [_pk(out[6]), _pk(out[7]), _pk(out[8]), np.asarray(out[9])]
    assert len(got) == len(ref)
    for i, (a, b) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(a, b, err_msg=f"output {i}")
    assert int(np.asarray(ref[2]).sum()) > 0      # locks were taken


@pytest.mark.parametrize("alg", ALGS)
def test_table_layout_equals_the_xla_engine(alg):
    """K above S: 4 nodes x 2 threads (S = 128) on 4,400 locks, 1,100 a
    node, which the draw merges (above ``ZIPF_COMPARE_RANKS``)."""
    args = _bucket(alg, 4, 2, 4_400)
    assert vmem.lock_layout(4_400, 8) == ("table", 128)
    assert 1_100 > ops.ZIPF_COMPARE_RANKS
    _assert_kernel_equals_xla(args)
    assert vmem.last_plan().lock_layout == "table"


@pytest.mark.parametrize("case", ALGS + ["open-loop"])
def test_a_forced_small_table(case, monkeypatch):
    """A table of 8 slots forced onto 48 locks and 3 x 2 threads: the
    threads join hot locks' slots, and the 8 slots are freed and reused
    for other locks all through the run; the open-loop bucket also
    freezes them on the steps that do nothing."""
    monkeypatch.setattr(vmem, "lock_layout", lambda K, T: ("table", 8))
    alg = "alock" if case == "open-loop" else case
    arr = (Arrivals(rate_per_us=2.0, max_requests=24, queue_cap=8)
           if case == "open-loop" else None)
    _assert_kernel_equals_xla(_bucket(alg, 3, 2, 48, seeds=2,
                                      arrivals=arr))
    assert vmem.last_plan().lock_slots == 8


@pytest.mark.parametrize("alg", ALGS)
def test_an_unheld_lock_holds_idle_words(alg):
    """The invariant the table's reset rests on, on the XLA engine's
    semantic step under a random schedule of 6 threads on 3 locks: after
    every step, a lock that no thread holds (no thread outside NCS
    targets it) has both tails and its reader count at 0, the words a
    fresh slot holds. Only the victim may differ, and it is written
    before it is read in every holding."""
    T, K, steps = 6, 3, 3000
    tn = [t // 2 for t in range(T)]
    ln = [0, 1, 2]
    rack = jnp.asarray([0, 0, 1], jnp.int32)
    rng = np.random.default_rng(5)
    sched = jnp.asarray(rng.integers(0, T, steps), jnp.int32)
    tgts = jnp.asarray(rng.integers(0, K, steps), jnp.int32)
    reads = jnp.asarray(rng.integers(0, 2, steps), jnp.int32)
    b_init = jnp.asarray([2, 3], jnp.int32)

    def body(sem, x):
        tid, nt, nr = x
        nc = (jnp.asarray(tn)[tid] != nt).astype(jnp.int32)
        sem, _, _ = sem_step(alg, sem, tid, b_init, tn, ln, nt, nc,
                             nr if alg == "alock-rw" else None, rack=rack)
        return sem, (sem.pc, sem.target, sem.tail, sem.word)

    _, (pc, tgt, tail, word) = jax.lax.scan(
        body, init_sem(T, K), (sched, tgts, reads))
    pc, tgt, tail, word = map(np.asarray, (pc, tgt, tail, word))
    held = np.zeros((steps, K), bool)
    for k in range(K):
        held[:, k] = ((pc != mc.NCS) & (tgt == k)).any(axis=1)
    assert held.any() and (~held).any()
    assert (tail[~held] == 0).all()
    assert (word[~held] == 0).all()
    # the locks were contended: some holding had company
    assert (((pc != mc.NCS)[:, :, None]
             & (tgt[:, :, None] == np.arange(K))).sum(axis=1) > 1).any()


#: sha256 of the ``pallas_call`` kernel jaxpr and grid mapping of each
#: benchmark bucket in the direct layout (K <= S), traced natively for a
#: v5e at the cells' rows and 150,000 events, as the program traced them
#: before the active-lock table was added
DIRECT_KERNELS = {
    ("alock", 10, 8, 100, 24):
        "df2f5412a8fc702dcd9b20ce98c98ef0b708f853cf694399e4e9c07292716e4b",
    ("spinlock", 10, 8, 100, 24):
        "6836060c659d4063bc1247ee8fe27ad71c0996c44053b91a05600185c62459d1",
    ("mcs", 10, 8, 100, 24):
        "ef6b91c323f6a2735d575bda5c7e590c49eedae2a1e25ef54e88c94e43bd40c3",
    ("alock", 20, 12, 100, 56):
        "dbe3f4794966ae653d73e016ceedf7f4a2f64a10ccd5a9d04951b4e2383fc541",
}


@pytest.mark.parametrize("bucket", list(DIRECT_KERNELS),
                         ids=lambda b: f"{b[0]}-T{b[1] * b[2]}")
def test_direct_buckets_keep_their_kernel_program(bucket):
    alg, N, tpn, K, rows = bucket
    n_events = 150_000
    lw = lower(Workload(alg, N, tpn, K, locality=0.95, seed=1), n_events)
    wl = WorkloadOperands(*(jax.ShapeDtypeStruct(
        (rows,) + np.shape(a), np.asarray(a).dtype) for a in lw.operands))
    tn, ln, _ = topology(alg, N, tpn, K)
    T = N * tpn
    assert vmem.lock_layout(K, T) == ("direct", K)
    with jax.enable_x64(True):
        jx = jax.make_jaxpr(lambda w, a, b: run_events(
            alg, T, N, K, n_events, w, a, b, interpret=False,
            device_kind="TPU v5 lite"))(wl, tn, ln)
    (eqn,) = [e for e in jx.jaxpr.eqns if e.primitive.name == "pallas_call"]
    text = str(eqn.params["jaxpr"]) + str(eqn.params["grid_mapping"])
    assert hashlib.sha256(text.encode()).hexdigest() == \
        DIRECT_KERNELS[bucket]
