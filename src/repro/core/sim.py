"""Vectorized discrete-event simulator for the distributed lock table.

The lock machines from ``core/machine.py`` re-expressed over JAX arrays and
driven by a next-event loop (`lax.fori_loop` + argmin over per-thread ready
times). Every shared-state mutation is serialized through the single event
queue, so executions are linearizable by construction — the same PC/semantic
transitions as the Python machines (cross-validated in tests via
``run_schedule``).

Time is int64 nanoseconds (``simulate``/``batch.sweep`` locally enable x64 so
the clock arrays really are 64-bit; semantic ``Sem`` state stays int32).
int32 clocks wrap after ~2.1s of simulated time — roughly 2M events at
~1us/event — which silently corrupts the argmin event order, so widening is
correctness, not hygiene. f32 time would likewise lose sub-ulp increments
past ~10ms.

Two execution backends share this module's semantics:

  * ``backend="xla"`` — the original serial ``lax.fori_loop`` over
    ``sem_step`` (argmin + ``lax.switch`` per event). This path is the
    correctness oracle.
  * ``backend="pallas"`` — ``repro.kernels.event_loop``: the same loop as a
    Pallas kernel with all per-replica state (Sem, ready/busy clocks)
    resident in VMEM for the whole run, each event's latency streamed out
    and the latency ring built from it, replicas tiled across the grid,
    branch dispatch re-expressed as masked ``jnp.select`` over PC
    classes. Bitwise-identical outputs to the XLA path (tested); the
    workload draws are precomputed per event from the same counter-based
    ``jax.random.fold_in`` stream so per-seed results match exactly.

``backend="auto"`` picks pallas on TPU and the XLA loop elsewhere; asking
for pallas explicitly on CPU runs the kernel in interpret mode.

Workloads — the declarative front door
--------------------------------------
The engines consume ``repro.workloads.WorkloadOperands``: the lowered form
of a declarative ``repro.workloads.Workload`` spec. *Everything* workload-
shaped is a traced operand — per-phase **per-thread** locality ``(P, T)``,
per-phase Zipf CDFs ``(P, kpn)``, phase boundaries over the event axis
(``edges``), per-phase think times, a per-phase active-thread mask
(node join/leave churn), per-phase **cost rows** (the 8 integer-ns cost
scalars, so a phase can swap the whole RDMA cost table — congested vs
idle NIC) and per-phase **ALock budgets** ``b_init``. At event ``i``
thread ``tid`` first resolves its phase (``sum(i >= edges) - 1``), then
draws a node (own node with probability ``locality[phase, tid]``, else
uniform remote) and a lock within that node by inverse-CDF from
``zcdf[phase]``; the step's cost and any budget it arms come from
``cost_rows[phase]`` / ``b_init[phase]``. Threads whose node is down in
the current phase are never scheduled (masked out of the ready-time
argmin). Per-phase **node multipliers** ``node_mult (P, N)`` inject
fail-slow degradation: every cost is scaled by the multiplier of the
node that *performs* the work — RNIC service and wire time by the card's
node, plain CPU-side ops (local/poll/cs/think) by the calling thread's
node — so one limping node drags exactly the traffic that touches it.

Because only ``(alg, T, N, K, n_events)`` — plus the phase count via
operand *shapes* — is static, a ``batch.sweep`` mixing arbitrary
scenarios (locality mixes, hot-key storms, churn programs, cost-profile
bursts, budget ramps) compiles once per shape bucket.

``simulate`` accepts a ``Workload`` directly, or a legacy flat
``SimConfig`` through the bitwise-faithful ``from_simconfig`` adapter.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import machine as mc
from repro.core.cost_model import CostModel
from repro.workloads import (Workload, WorkloadOperands, as_workload, lower,
                             zipf_cdf)

__all__ = [
    "SimConfig", "SimResult", "Sem", "simulate", "topology", "zipf_cdf",
    "resolve_backend", "init_sem", "sem_step", "run_schedule",
    "Workload", "WorkloadOperands", "LAT_SAMPLES",
]

I32 = jnp.int32
I64 = jnp.int64

# cost opcodes emitted by semantic branches
OP_LOCAL, OP_POLL, OP_CS, OP_THINK, OP_RDMA, OP_LOOP = range(6)


class Sem(NamedTuple):
    """Semantic (cost-free) simulator state."""
    tail: jax.Array     # (K,2) tid+1 per cohort
    victim: jax.Array   # (K,)
    word: jax.Array     # (K,) competitor lock word
    budget: jax.Array   # (T,)
    nxt: jax.Array      # (T,)
    prev: jax.Array     # (T,)
    pc: jax.Array       # (T,)
    target: jax.Array   # (T,) lock index
    cohort: jax.Array   # (T,) 0 local / 1 remote


def init_sem(n_threads: int, n_locks: int, targets=None,
             cohorts=None) -> Sem:
    T, K = n_threads, n_locks
    z = jnp.zeros(T, I32)
    return Sem(
        tail=jnp.zeros((K, 2), I32), victim=jnp.zeros(K, I32),
        word=jnp.zeros(K, I32), budget=jnp.full(T, -1, I32), nxt=z, prev=z,
        pc=jnp.full(T, mc.NCS, I32),
        target=(jnp.zeros(T, I32) if targets is None else
                jnp.asarray(targets, I32)),
        cohort=(jnp.zeros(T, I32) if cohorts is None else
                jnp.asarray(cohorts, I32)),
    )


def _step_fns(alg: str, b_init, thread_node, lock_node, rack=None):
    """Build per-PC branch functions: (sem, tid, new_target, new_cohort,
    new_read) -> (sem', opcode, node). Semantics mirror machine.py exactly.

    ``rack`` is the per-node rack-id vector driving hlock's cost tiers
    (same node / same rack / cross rack); ``None`` is the trivial
    topology — every node its own rack — under which the tiers collapse
    to the flat ALock's local/RDMA split.
    """
    b_init = jnp.asarray(b_init, I32)
    thread_node = jnp.asarray(thread_node, I32)
    lock_node = jnp.asarray(lock_node, I32)
    is_hl = alg == "hlock"
    is_rw = alg == "alock-rw"
    # hlock and alock-rw run the ALock tail/victim/budget machinery
    is_alock = alg in ("alock", "hlock", "alock-rw")
    is_mcs = alg == "mcs"
    is_spin = alg == "spinlock"
    if rack is not None:
        rack = jnp.asarray(rack, I32)

    def _rack_of(node_ids):
        return node_ids if rack is None else rack[node_ids]

    def _tiered(node, tid):
        """hlock cost tier: own node -> shared memory, same rack -> the
        cheap loopback/rack fabric, cross rack -> full RDMA."""
        same_rack = _rack_of(node) == _rack_of(thread_node[tid])
        return jnp.where(node == thread_node[tid], OP_LOCAL,
                         jnp.where(same_rack, OP_LOOP, OP_RDMA))

    def lock_op_cost(s, tid):
        """RDMA unless (alock AND local-cohort). Loopback when the RDMA
        target is the caller's own node (competitors only); hlock charges
        the three-tier node/rack/remote split."""
        k = s.target[tid]
        node = lock_node[k]
        if is_hl:
            code = _tiered(node, tid)
        elif is_alock:
            code = jnp.where(s.cohort[tid] == 0, OP_LOCAL, OP_RDMA)
        else:
            code = jnp.where(node == thread_node[tid], OP_LOOP, OP_RDMA)
        return code.astype(I32), node

    def peer_op_cost(s, tid, peer):
        """Write to another thread's descriptor (lives on its node)."""
        node = thread_node[peer]
        if is_hl:
            code = _tiered(node, tid)
        elif is_alock:
            code = jnp.where(node == thread_node[tid], OP_LOCAL, OP_RDMA)
        else:
            code = jnp.where(node == thread_node[tid], OP_LOOP, OP_RDMA)
        return code.astype(I32), node

    def f_ncs(s, tid, new_t, new_c, new_r):
        if is_rw:
            first = jnp.where(new_r != 0, mc.RD_TRY, mc.SWAP)
        else:
            first = mc.SL_CAS if is_spin else mc.SWAP
        s = s._replace(budget=s.budget.at[tid].set(-1),
                       nxt=s.nxt.at[tid].set(0),
                       target=s.target.at[tid].set(new_t),
                       cohort=s.cohort.at[tid].set(new_c),
                       pc=s.pc.at[tid].set(first))
        return s, jnp.int32(OP_THINK), jnp.int32(0)

    def f_swap(s, tid, *_):
        k = s.target[tid]
        c = jnp.where(jnp.int32(is_alock), s.cohort[tid], 0)
        prev = jnp.where(jnp.int32(is_alock), s.tail[k, c], s.word[k])
        me = tid + 1
        if is_alock:
            s = s._replace(tail=s.tail.at[k, c].set(me))
        else:
            s = s._replace(word=s.word.at[k].set(me))
        s = s._replace(prev=s.prev.at[tid].set(prev))
        empty = prev == 0
        if is_alock:
            nxt_pc = jnp.where(empty, mc.SET_VICTIM, mc.WRITE_NEXT)
            s = s._replace(budget=s.budget.at[tid].set(
                jnp.where(empty, b_init[s.cohort[tid]], s.budget[tid])))
        else:
            nxt_pc = jnp.where(empty, mc.CS, mc.WRITE_NEXT)
        s = s._replace(pc=s.pc.at[tid].set(nxt_pc))
        code, node = lock_op_cost(s, tid)
        return s, code, node

    def f_write_next(s, tid, *_):
        p = s.prev[tid] - 1
        s = s._replace(nxt=s.nxt.at[p].set(tid + 1),
                       pc=s.pc.at[tid].set(mc.SPIN_BUDGET))
        code, node = peer_op_cost(s, tid, p)
        return s, code, node

    # a writer's every CS entry detours through the reader drain (rw only)
    enter_cs = mc.WR_DRAIN if is_rw else mc.CS

    def f_spin_budget(s, tid, *_):
        b = s.budget[tid]
        if is_alock:
            nxt_pc = jnp.where(b == -1, mc.SPIN_BUDGET,
                               jnp.where(b == 0, mc.SET_VICTIM_R, enter_cs))
        else:
            nxt_pc = jnp.where(b == -1, mc.SPIN_BUDGET, mc.CS)
        s = s._replace(pc=s.pc.at[tid].set(nxt_pc))
        code = jnp.where(b == -1, OP_POLL, OP_LOCAL)
        return s, code.astype(I32), jnp.int32(0)

    def f_set_victim(s, tid, *_):
        k = s.target[tid]
        s = s._replace(victim=s.victim.at[k].set(s.cohort[tid]),
                       pc=s.pc.at[tid].set(mc.PET_WAIT))
        code, node = lock_op_cost(s, tid)
        return s, code, node

    def f_set_victim_r(s, tid, *_):
        k = s.target[tid]
        s = s._replace(victim=s.victim.at[k].set(s.cohort[tid]),
                       pc=s.pc.at[tid].set(mc.PET_WAIT_R))
        code, node = lock_op_cost(s, tid)
        return s, code, node

    def _pet(s, tid, reacq):
        k = s.target[tid]
        c = s.cohort[tid]
        can = (s.tail[k, 1 - c] == 0) | (s.victim[k] != c)
        if reacq:
            s = s._replace(budget=s.budget.at[tid].set(
                jnp.where(can, b_init[c], s.budget[tid])))
        stay = mc.PET_WAIT_R if reacq else mc.PET_WAIT
        s = s._replace(pc=s.pc.at[tid].set(jnp.where(can, enter_cs, stay)))
        code, node = lock_op_cost(s, tid)
        return s, code, node

    def f_pet_wait(s, tid, *_):
        return _pet(s, tid, False)

    def f_pet_wait_r(s, tid, *_):
        return _pet(s, tid, True)

    def f_cs(s, tid, *_):
        s = s._replace(pc=s.pc.at[tid].set(
            mc.SL_REL if is_spin else mc.REL_CAS))
        return s, jnp.int32(OP_CS), jnp.int32(0)

    def f_rel_cas(s, tid, *_):
        k = s.target[tid]
        me = tid + 1
        if is_alock:
            c = s.cohort[tid]
            solo = s.tail[k, c] == me
            s = s._replace(tail=s.tail.at[k, c].set(
                jnp.where(solo, 0, s.tail[k, c])))
        else:
            solo = s.word[k] == me
            s = s._replace(word=s.word.at[k].set(
                jnp.where(solo, 0, s.word[k])))
        s = s._replace(pc=s.pc.at[tid].set(
            jnp.where(solo, mc.NCS, mc.SPIN_NEXT)))
        code, node = lock_op_cost(s, tid)
        return s, code, node

    def f_spin_next(s, tid, *_):
        has = s.nxt[tid] != 0
        s = s._replace(pc=s.pc.at[tid].set(
            jnp.where(has, mc.PASS, mc.SPIN_NEXT)))
        return s, jnp.where(has, OP_LOCAL, OP_POLL).astype(I32), jnp.int32(0)

    def f_pass(s, tid, *_):
        succ = s.nxt[tid] - 1
        newb = jnp.where(jnp.int32(is_alock), s.budget[tid] - 1, 1)
        s = s._replace(budget=s.budget.at[succ].set(newb),
                       pc=s.pc.at[tid].set(mc.NCS))
        code, node = peer_op_cost(s, tid, succ)
        return s, code, node

    def f_sl_cas(s, tid, *_):
        k = s.target[tid]
        free = s.word[k] == 0
        s = s._replace(word=s.word.at[k].set(
            jnp.where(free, tid + 1, s.word[k])),
            pc=s.pc.at[tid].set(jnp.where(free, mc.CS, mc.SL_CAS)))
        code, node = lock_op_cost(s, tid)
        return s, code, node

    def f_sl_rel(s, tid, *_):
        k = s.target[tid]
        s = s._replace(word=s.word.at[k].set(0),
                       pc=s.pc.at[tid].set(mc.NCS))
        code, node = lock_op_cost(s, tid)
        return s, code, node

    # --- reader-writer branches (alock-rw only; PCs 14..17) --------------
    def f_rd_try(s, tid, *_):
        # reader entry with writer preference: both cohort tails empty
        # means no writer holds or wants the lock; the shared reader
        # count lives in `word` (unused by the plain ALock)
        k = s.target[tid]
        can = (s.tail[k, 0] == 0) & (s.tail[k, 1] == 0)
        s = s._replace(word=s.word.at[k].add(can.astype(I32)),
                       pc=s.pc.at[tid].set(
                           jnp.where(can, mc.RD_CS, mc.RD_TRY)))
        code, node = lock_op_cost(s, tid)
        return s, code, node

    def f_rd_cs(s, tid, *_):
        s = s._replace(pc=s.pc.at[tid].set(mc.RD_REL))
        return s, jnp.int32(OP_CS), jnp.int32(0)

    def f_rd_rel(s, tid, *_):
        k = s.target[tid]
        s = s._replace(word=s.word.at[k].add(-1),
                       pc=s.pc.at[tid].set(mc.NCS))
        code, node = lock_op_cost(s, tid)
        return s, code, node

    def f_wr_drain(s, tid, *_):
        k = s.target[tid]
        can = s.word[k] == 0
        s = s._replace(pc=s.pc.at[tid].set(
            jnp.where(can, mc.CS, mc.WR_DRAIN)))
        code, node = lock_op_cost(s, tid)
        return s, code, node

    fns = [f_ncs, f_swap, f_write_next, f_spin_budget, f_set_victim,
           f_pet_wait, f_set_victim_r, f_pet_wait_r, f_cs, f_rel_cas,
           f_spin_next, f_pass, f_sl_cas, f_sl_rel]
    if is_rw:
        # the rw PCs are unreachable for every other machine — gating them
        # out python-level keeps the other algorithms' traces identical
        fns += [f_rd_try, f_rd_cs, f_rd_rel, f_wr_drain]
    return fns


def sem_step(alg, sem: Sem, tid, b_init, thread_node, lock_node,
             new_target=None, new_cohort=None, new_read=None, rack=None):
    """One semantic step of thread `tid` — used by the event loop and by the
    schedule-driven cross-validation runner. ``new_read`` routes the
    NCS re-arm to the reader path (alock-rw); ``rack`` is the per-node
    rack-id vector hlock's cost tiers consume."""
    fns = _step_fns(alg, b_init, thread_node, lock_node, rack)
    nt = sem.target[tid] if new_target is None else new_target
    nc = sem.cohort[tid] if new_cohort is None else new_cohort
    nr = jnp.int32(0) if new_read is None else new_read
    return lax.switch(sem.pc[tid], fns, sem, tid, nt, nc, nr)


def run_schedule(alg, cohorts, b_init, schedule, n_locks: int = 1):
    """Drive the jnp machine with an explicit thread schedule (single lock,
    semantics only) and return the trace of (pc, tail, victim, budget)."""
    T = len(cohorts)
    sem = init_sem(T, n_locks, targets=[0] * T, cohorts=cohorts)
    tn = [0 if c == 0 else 1 for c in cohorts]   # arbitrary node split
    ln = [0] * n_locks

    def body(sem, tid):
        sem, _, _ = sem_step(alg, sem, tid, b_init, tn, ln)
        return sem, (sem.pc, sem.tail[0], sem.victim[0], sem.budget)

    sem, trace = lax.scan(body, sem, jnp.asarray(schedule, I32))
    return sem, trace


# ---------------------------------------------------------------------------
# Event-driven simulation with the cost model


class SimConfig(NamedTuple):
    """Legacy flat per-run config.

    .. deprecated::
        Kept as a compatibility front door only — it can express neither
        per-thread locality nor phases. New code should build
        ``repro.workloads.Workload`` specs; ``simulate``/``batch.sweep``
        route SimConfig through the bitwise-faithful
        ``repro.workloads.from_simconfig`` adapter.
    """
    alg: str
    n_nodes: int
    threads_per_node: int
    n_locks: int
    locality: float           # P(target lock is on own node)
    b_init: tuple = (5, 20)   # (local, remote) budgets
    seed: int = 0
    zipf_s: float = 0.0       # Zipf skew of the per-node lock choice


def resolve_backend(backend: str) -> str:
    """'auto' -> pallas where natively supported (TPU), else the XLA loop.

    Explicitly requesting 'pallas' off-TPU runs the kernel in interpret
    mode (slow, but bitwise-faithful — that is what the equivalence tests
    exercise on CPU CI).
    """
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend not in ("xla", "pallas"):
        raise ValueError(
            f"backend must be 'xla', 'pallas' or 'auto', got {backend!r}")
    return backend


class SimResult(NamedTuple):
    ops: int
    sim_ns: int
    throughput_mops: float    # million lock+unlock ops per second
    lat_ns: jax.Array         # latency samples (ns), -1 padded
    per_thread_ops: jax.Array
    reacquires: int = 0       # budget-exhaustion pReacquire events
    passes: int = 0           # MCS lock passes
    # open-loop (Workload.arrivals) extras — None on closed-loop runs
    arr_ns: jax.Array | None = None    # (R,) request arrival times
    wait_ns: jax.Array | None = None   # (R,) queue wait, -1 = never served
    sojourn_ns: jax.Array | None = None  # (R,) total, -1 = never completed
    rstat: jax.Array | None = None     # (R,) repro.traffic status codes


LAT_SAMPLES = 1 << 15


def _scale_cost(c, m):
    """Apply a fail-slow node multiplier to an integer-ns cost.

    Round-to-nearest in float32: cost rows are < 2^24 ns so the f32
    product is exact, which makes ``m == 1.0`` bitwise inert (the
    healthy-cluster path reproduces the pre-fault engine exactly) and
    keeps the scaled delta i32 — the Pallas kernel's clock pairs consume
    it unchanged. The kernel mirrors this formula
    verbatim; any change here must be mirrored there.
    """
    return jnp.round(jnp.asarray(c, jnp.float32) * m).astype(I32)


def _run_events(alg, T, N, K, n_events, wl: WorkloadOperands, thread_node,
                lock_node, lat_samples: int = LAT_SAMPLES):
    """Serial next-event loop for one (workload, seed) point — XLA backend.

    Plain (unjitted) so callers can compose it: ``simulate`` jits it directly
    (``_run_events_jit``), ``batch.sweep`` vmaps it over a flattened
    (config x seed) axis. Must run under ``jax.enable_x64(True)`` so the
    clock arrays below really are int64. ``wl`` is the lowered
    ``WorkloadOperands`` struct (see ``repro.workloads.lower``) — every
    leaf is a traced operand and may vary per replica in the batched path,
    including the per-phase cost rows ``wl.cost_rows (P, 8)`` and the
    per-phase ALock budgets ``wl.b_init (P, 2)``. ``lat_samples`` sizes
    the latency ring (static; default ``LAT_SAMPLES`` — the ring-overflow
    tests shrink it to exercise wraparound cheaply).

    The Pallas backend (``repro.kernels.event_loop``) reproduces this loop
    bitwise; any semantic change here must be mirrored there (the
    equivalence tests will catch a divergence).
    """
    sem = init_sem(T, K)
    ready = jnp.zeros(T, I64)
    busy = jnp.zeros(N, I64)
    op_start = jnp.zeros(T, I64)
    done = jnp.zeros(T, I32)
    lat = jnp.full(lat_samples, -1, I64)
    lat_n = jnp.int32(0)
    key = jax.random.key(wl.seed)
    kpn = K // N
    never = jnp.iinfo(jnp.int64).max   # parked threads lose every argmin

    # static via the operand shape: single-phase workloads (every paper
    # figure, the whole SimConfig adapter path) skip the per-event phase
    # resolve / active-mask / rejoin machinery entirely. Sound because
    # lowering guarantees P == 1 operands are all-active (a masked single
    # phase is lowered as two identical halves).
    multi_phase = wl.edges.shape[0] > 1

    # static alg gates: the hierarchical cohort test and the read-draw
    # dispatch are python-dead for every other machine, so the existing
    # algorithms trace the exact pre-change program
    is_hl = alg == "hlock"
    is_rw = alg == "alock-rw"

    # static via the arr_fix shape: R == 0 is the closed loop and traces
    # the exact pre-traffic program (every `if open_loop` block below is
    # python-level dead code then — bitwise inertness by construction)
    R = wl.arr_fix.shape[-1]
    open_loop = R > 0
    if open_loop:
        # lazy: repro.traffic pulls in the i32pair helpers, and the import
        # is only needed on the open-loop path anyway
        from repro.traffic.metrics import COMPLETED, DROPPED, IN_SERVICE
        from repro.traffic.stream import arrival_plan, arrival_times_i64
        plan = arrival_plan(wl, n_events)
        arr = arrival_times_i64(plan.gaps)          # (R,) i64
        idx_r = jnp.arange(R, dtype=I32)

    def event(i, carry):
        if open_loop:
            (sem, ready, busy, op_start, done, lat, lat_n, nreacq, npass,
             rstat, curreq, arrptr, qlen, wq, soj) = carry
        else:
            sem, ready, busy, op_start, done, lat, lat_n, nreacq, npass \
                = carry
        if multi_phase:
            # piecewise phase over the event axis; with all-active phases
            # every line below reduces bitwise to the flat engine
            ph = jnp.sum(i >= wl.edges) - 1
            act = wl.active[ph]
            # phase boundary: a thread whose node rejoins resumes from the
            # cluster's current clock — not its stale park time — so a
            # down phase really costs it the interval (no deferred-event
            # catch-up). "now" is the next event time of the continuously-
            # active threads (a rejoiner's own parked clock must not drag
            # it backwards).
            was_act = wl.active[jnp.maximum(ph - 1, 0)]
            rejoin = jnp.any(i == wl.edges) & (act != 0) & (was_act == 0)
            cont_min = jnp.min(jnp.where((act != 0) & (was_act != 0),
                                         ready, never))
            now_min = jnp.where(cont_min == never,
                                jnp.min(jnp.where(act != 0, ready, never)),
                                cont_min)
            ready = jnp.where(rejoin, jnp.maximum(ready, now_min), ready)
            actm = act != 0
        else:
            ph = 0
            actm = None
        if open_loop:
            # idle threads (NCS, no request bound) wake at the earliest
            # available arrival instead of re-arming; busy threads keep
            # their own clocks. A drained stream with everyone idle makes
            # every lane read `never` -> the event is a no-op (live=False).
            pend = (sem.pc == mc.NCS) & (curreq < 0)
            avail = (rstat == 0) & (plan.tok == 1)
            next_arr = jnp.min(jnp.where(avail, arr, never))
            elig = jnp.where(pend, jnp.maximum(ready, next_arr), ready)
        else:
            elig = ready
        if actm is not None:
            tid = jnp.argmin(jnp.where(actm, elig, never)).astype(I32)
        else:
            tid = jnp.argmin(elig).astype(I32)
        # phase-indexed cost rows + ALock budgets (constant rows for a
        # single-phase spec — identical arithmetic to the flat engine)
        cst = wl.cost_rows[ph]
        c_local, c_poll, c_cs = cst[0], cst[1], cst[2]
        c_svc_r, c_svc_l, c_wire_r, c_wire_l = (cst[4], cst[5], cst[6],
                                                cst[7])
        b_init = wl.b_init[ph]
        now = elig[tid]            # == ready[tid] on the closed-loop path
        if is_rw:
            # the reader/writer coin rides the same counter stream as the
            # other draws (4-way split; state-independent, so the kernel
            # precomputes it identically)
            k1, k2, k3, k4 = jax.random.split(
                jax.random.fold_in(key, i), 4)
        else:
            k1, k2, k3 = jax.random.split(jax.random.fold_in(key, i), 3)
        # workload draw (used only when this step is the NCS re-arm);
        # dtypes pinned so enabling x64 does not change the draws
        mynode = thread_node[tid]
        go_local = (jax.random.uniform(k1, dtype=jnp.float32)
                    < wl.locality[ph, tid])
        other = (mynode + 1 +
                 jax.random.randint(k2, (), 0, max(N - 1, 1), dtype=I32)) % N
        node = jnp.where(go_local, mynode, other).astype(I32)
        u3 = jax.random.uniform(k3, dtype=jnp.float32)
        # inverse-CDF draw of the within-node lock (uniform when zipf_s=0);
        # clamp guards the cumsum's final float32 ulp falling short of 1.0
        off = jnp.minimum(jnp.sum(u3 >= wl.zcdf[ph]).astype(I32), kpn - 1)
        new_t = node * kpn + off
        if is_hl:
            # hierarchical cohort: LOCAL means same *rack*, not same node.
            # The trivial topology (rack = arange(N)) makes this bitwise
            # the flat test — hlock's regression anchor against alock.
            new_c = (wl.rack[node] != wl.rack[mynode]).astype(I32)
        else:
            new_c = (node != mynode).astype(I32)
        if is_rw:
            u4 = jax.random.uniform(k4, dtype=jnp.float32)
            new_r = (u4 < wl.read_frac[ph, tid]).astype(I32)
        else:
            new_r = None

        if open_loop:
            live = now != never
            pend_tid = pend[tid]
            # -- arrival ingestion: every request with arr <= now either
            # joins the wait queue or drops (token reject / queue full).
            # `rank` orders the token-admitted newcomers so tail drop is
            # exact when a burst overshoots the remaining queue room.
            cnt_now = jnp.where(
                live, jnp.sum((arr <= now).astype(I32), dtype=I32), arrptr)
            newly = (idx_r >= arrptr) & (idx_r < cnt_now)
            rank = plan.tokcum - plan.tokcum[arrptr]
            join = newly & (plan.tok == 1) & (rank < plan.qcap - qlen)
            rstat = jnp.where(newly & ~join, DROPPED, rstat)
            qlen = qlen + jnp.sum(join.astype(I32), dtype=I32)
            arrptr = cnt_now
            # -- dispatch: an idle selected thread takes the FIFO head --
            queued = (rstat == 0) & (idx_r < arrptr)
            head = jnp.min(jnp.where(queued, idx_r,
                                     jnp.iinfo(jnp.int32).max))
            do_disp = live & pend_tid & jnp.any(queued)
            hd = jnp.minimum(head, jnp.int32(R - 1))
            rstat = rstat.at[hd].set(
                jnp.where(do_disp, IN_SERVICE, rstat[hd]))
            curreq = curreq.at[tid].set(
                jnp.where(do_disp, hd, curreq[tid]))
            wq = wq.at[hd].set(jnp.where(do_disp, now - arr[hd], wq[hd]))
            qlen = qlen - do_disp.astype(I32)
            # an idle thread with nothing to take makes no machine step
            step_ok = live & (~pend_tid | do_disp)

        was_ncs_bound = (sem.pc[tid] == mc.REL_CAS) | (sem.pc[tid] == mc.PASS) \
            | (sem.pc[tid] == mc.SL_REL)
        if is_rw:
            # a reader's RD_REL decrement is its release — it completes an
            # acquisition exactly like a writer's REL_CAS/PASS
            was_ncs_bound = was_ncs_bound | (sem.pc[tid] == mc.RD_REL)
        pre_pc = sem.pc[tid]
        sem2, code, tnode = sem_step(alg, sem, tid, b_init, thread_node,
                                     lock_node, new_t, new_c, new_r,
                                     rack=wl.rack)
        finished = was_ncs_bound & (sem2.pc[tid] == mc.NCS)
        reacq = (pre_pc == mc.SPIN_BUDGET) & (sem2.pc[tid] == mc.SET_VICTIM_R)
        passed = pre_pc == mc.PASS
        if open_loop:
            sem2 = jax.tree_util.tree_map(
                lambda a, b: jnp.where(step_ok, a, b), sem2, sem)
            finished = finished & step_ok
            reacq = reacq & step_ok
            passed = passed & step_ok

        # completion accounting — lat_val reads op_start BEFORE this event's
        # re-stamp so it spans exactly acquire-entry -> release
        lat_val = now - op_start[tid]
        lat = lax.cond(
            finished,
            lambda l: l.at[lat_n % lat_samples].set(lat_val),
            lambda l: l, lat)
        lat_n = lat_n + finished.astype(I32)
        done = done.at[tid].add(finished.astype(I32))

        # cost application. node_mult degrades the node doing the work:
        # svc/wire belong to the target card's node, dt_plain to the
        # caller's CPU (mult 1.0 is bitwise inert — see _scale_cost)
        nm = wl.node_mult[ph]
        is_rdma = (code == OP_RDMA) | (code == OP_LOOP)
        if open_loop:
            is_rdma = is_rdma & step_ok
        svc = _scale_cost(jnp.where(code == OP_LOOP, c_svc_l, c_svc_r),
                          nm[tnode])
        wire = _scale_cost(jnp.where(code == OP_LOOP, c_wire_l, c_wire_r),
                           nm[tnode])
        start = jnp.maximum(now, busy[tnode])
        fin = start + svc
        busy = busy.at[tnode].set(jnp.where(is_rdma, fin, busy[tnode]))
        dt_plain = _scale_cost(jnp.select(
            [code == OP_LOCAL, code == OP_POLL, code == OP_CS,
             code == OP_THINK],
            [c_local, c_poll, c_cs, wl.think_ns[ph]], c_local), nm[mynode])
        new_ready = jnp.where(is_rdma, fin + wire, now + dt_plain)
        if open_loop:
            ready = ready.at[tid].set(
                jnp.where(step_ok, new_ready, ready[tid]))
            opst_upd = (pre_pc == mc.NCS) & step_ok
        else:
            ready = ready.at[tid].set(new_ready)
            opst_upd = pre_pc == mc.NCS
        # latency clock starts when the first lock op (SWAP/SL_CAS) can
        # issue, i.e. after the NCS think completes — Fig. 6 measures
        # acquire->release, not think_ns of app work
        op_start = op_start.at[tid].set(
            jnp.where(opst_upd, new_ready, op_start[tid]))
        nreacq = nreacq + reacq.astype(I32)
        npass = npass + passed.astype(I32)
        if open_loop:
            # -- departure: the finishing release frees the thread and
            # stamps the request's sojourn at the step's completion time
            req = curreq[tid]
            comp = finished & (req >= 0)
            rq = jnp.maximum(req, 0)
            soj = soj.at[rq].set(
                jnp.where(comp, new_ready - arr[rq], soj[rq]))
            rstat = rstat.at[rq].set(jnp.where(comp, COMPLETED, rstat[rq]))
            curreq = curreq.at[tid].set(jnp.where(comp, -1, curreq[tid]))
            return (sem2, ready, busy, op_start, done, lat, lat_n, nreacq,
                    npass, rstat, curreq, arrptr, qlen, wq, soj)
        return sem2, ready, busy, op_start, done, lat, lat_n, nreacq, npass

    carry = (sem, ready, busy, op_start, done, lat, lat_n, jnp.int32(0),
             jnp.int32(0))
    if open_loop:
        carry = carry + (jnp.zeros(R, I32), jnp.full(T, -1, I32),
                         jnp.int32(0), jnp.int32(0), jnp.full(R, -1, I64),
                         jnp.full(R, -1, I64))
        (sem, ready, busy, op_start, done, lat, lat_n, nreacq, npass,
         rstat, curreq, arrptr, qlen, wq,
         soj) = lax.fori_loop(0, n_events, event, carry)
        return (done, lat, lat_n, jnp.max(ready), nreacq, npass, arr, wq,
                soj, rstat)
    (sem, ready, busy, op_start, done, lat, lat_n, nreacq,
     npass) = lax.fori_loop(0, n_events, event, carry)
    return done, lat, lat_n, jnp.max(ready), nreacq, npass


_run_events_jit = functools.partial(
    jax.jit, static_argnames=("alg", "T", "N", "K", "n_events",
                              "lat_samples"))(_run_events)


def topology(alg: str, n_nodes: int, threads_per_node: int, n_locks: int,
             cm: CostModel = CostModel()):
    """Static per-shape operands: (thread_node, lock_node, cost scalars).

    thread_node/lock_node are fully determined by (alg, N, tpn, K) and
    stay unbatched broadcast operands of every engine. The cost scalars
    are ``cm.cost_rows(...)`` — the *default* rows; the engines actually
    consume the per-phase ``WorkloadOperands.cost_rows`` the lowering
    emits (which equals this tuple for every default-cost phase, keeping
    the pre-profile arithmetic bitwise-frozen).
    """
    T, N, K = n_nodes * threads_per_node, n_nodes, n_locks
    if N < 1 or K < 1:
        raise ValueError(f"need n_nodes >= 1 and n_locks >= 1, got "
                         f"(n_locks={K}, n_nodes={N})")
    if K % N != 0:
        # a real error, not an assert: benchmark CLIs feed user arguments
        # straight in here, and asserts vanish under `python -O`
        raise ValueError(
            f"locks must partition evenly across nodes: n_locks={K} is not "
            f"a multiple of n_nodes={N} (got (n_locks, n_nodes)=({K}, {N}))")
    thread_node = jnp.asarray([t // threads_per_node for t in range(T)], I32)
    lock_node = jnp.asarray([k // (K // N) for k in range(K)], I32)
    return thread_node, lock_node, cm.cost_rows(alg, N, threads_per_node)


def simulate(cfg: SimConfig | Workload, n_events: int = 400_000,
             cm: CostModel = CostModel(), backend: str = "auto") -> SimResult:
    """Run one workload (a ``Workload`` spec, or a legacy ``SimConfig``
    through the adapter) for ``n_events`` events on the chosen backend."""
    w = as_workload(cfg)
    lw = lower(w, n_events, cm)
    T, N, K = lw.n_threads, w.n_nodes, w.n_locks
    thread_node, lock_node, _ = topology(
        w.alg, N, w.threads_per_node, K, cm)
    backend = resolve_backend(backend)
    with jax.enable_x64(True):
        if backend == "pallas":
            from repro.kernels.event_loop.ops import run_events_jit
            batched = WorkloadOperands(
                *(jnp.asarray(a)[None] for a in lw.operands))
            out = run_events_jit(
                w.alg, T, N, K, n_events, batched, thread_node, lock_node)
            out = tuple(o[0] for o in out)
        else:
            wl = WorkloadOperands(*(jnp.asarray(a) for a in lw.operands))
            out = _run_events_jit(
                w.alg, T, N, K, n_events, wl, thread_node, lock_node)
    done, lat, lat_n, t_end, nreacq, npass = out[:6]
    extras = {}
    if len(out) > 6:        # open-loop run: per-request serving arrays
        extras = dict(arr_ns=out[6], wait_ns=out[7], sojourn_ns=out[8],
                      rstat=out[9])
    ops = int(done.sum())
    sim_ns = max(int(t_end), 1)
    return SimResult(ops, sim_ns, ops / sim_ns * 1e3, lat, done,
                     int(nreacq), int(npass), **extras)
