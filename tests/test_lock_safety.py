"""Trace-level lock-safety campaign over per-event CS intervals.

A schedule replay through the canonical state machines
(``repro.core.machine.MACHINES``) yields, per thread, the closed critical-
section intervals ``(tid, kind, t0, t1)`` in step time, where ``kind`` is
``"write"`` (the exclusive CS) or ``"read"`` (alock-rw's shared reader
section, held from the successful rd_enter until the RD_REL decrement).
``check_cs_intervals`` is the safety oracle over that trace:

  * **write mutual exclusion** — no two write intervals of different
    threads ever overlap (all five algorithms);
  * **reader/writer exclusion** — a read interval may overlap other read
    intervals but never a write interval (alock-rw).

The checker is exercised three ways: seeded adversarial schedules that
always run, hypothesis properties, and a
*seeded mutation* — an alock-rw writer whose reader-count drain check is
disabled — which the checker must catch (a checker that cannot fail would
gate nothing).
"""
import itertools
import random

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import machine as mc

L, R = mc.LOCAL, mc.REMOTE

ALGS = ("alock", "spinlock", "mcs", "hlock", "alock-rw")


# ---------------------------------------------------------------------------
# trace extraction + the safety oracle


def cs_intervals(alg, cohorts, b_init, sched, read_bits=(),
                 step_fn=None):
    """Replay ``sched`` through ``MACHINES[alg]`` and return the CS trace.

    ``sched`` is a sequence of thread ids (one atomic action each);
    ``read_bits`` (alock-rw only) supplies the per-step read/write coin a
    thread consults when it leaves NCS — mirroring the engine's per-
    request draw. Returns ``[(tid, kind, t0, t1), ...]`` with half-open
    step intervals ``[t0, t1)``; ``step_fn`` overrides the machine (the
    mutation tests inject a broken writer through it).
    """
    n = len(cohorts)
    step = step_fn if step_fn is not None else mc.MACHINES[alg]
    stt = mc.initial_state(n)
    open_iv: dict = {}
    out = []
    for t, tid in enumerate(sched):
        if alg == "alock-rw":
            is_read = bool(read_bits[t]) if len(read_bits) else False
            stt, _ = step(stt, tid, cohorts[tid], b_init, is_read=is_read)
        else:
            stt, _ = step(stt, tid, cohorts[tid], b_init)
        for u in range(n):
            kind = ("write" if mc.in_cs(stt, u)
                    else "read" if alg == "alock-rw" and mc.in_read_cs(
                        stt, u)
                    else None)
            cur = open_iv.get(u)
            if cur is not None and cur[0] != kind:
                out.append((u, cur[0], cur[1], t + 1))
                del open_iv[u]
                cur = None
            if kind is not None and cur is None:
                open_iv[u] = (kind, t + 1)
    for u, (kind, t0) in sorted(open_iv.items()):
        out.append((u, kind, t0, len(sched) + 1))
    return out


def check_cs_intervals(intervals):
    """The oracle: every overlapping pair of intervals from *different*
    threads involving a write is a violation. Returns the violating
    pairs (empty = trace is safe)."""
    viol = []
    for a, b in itertools.combinations(intervals, 2):
        (u, ku, a0, a1), (v, kv, b0, b1) = a, b
        if u == v:
            continue
        if a0 < b1 and b0 < a1 and ("write" in (ku, kv)):
            viol.append((a, b))
    return viol


def _coins(seed, n_steps, p_read):
    rng = random.Random(seed)
    return [1 if rng.random() < p_read else 0 for _ in range(n_steps)]


def _sched(seed, n_threads, n_steps):
    rng = random.Random(seed)
    return [rng.randrange(n_threads) for _ in range(n_steps)]


# ---------------------------------------------------------------------------
# the checker on its own terms (unit): overlap logic, read/read tolerance


def test_checker_flags_write_write_overlap():
    bad = [(0, "write", 3, 9), (1, "write", 8, 12)]
    assert check_cs_intervals(bad)
    ok = [(0, "write", 3, 8), (1, "write", 8, 12)]   # half-open: no touch
    assert not check_cs_intervals(ok)


def test_checker_read_rules():
    rr = [(0, "read", 1, 10), (1, "read", 2, 8), (2, "read", 5, 20)]
    assert not check_cs_intervals(rr)               # readers share freely
    rw = rr + [(3, "write", 7, 9)]
    viol = check_cs_intervals(rw)
    assert len(viol) == 3                           # ... but never a writer
    # same thread re-entering is not an overlap
    assert not check_cs_intervals([(0, "write", 1, 5), (0, "write", 4, 9)])


# ---------------------------------------------------------------------------
# seeded adversarial schedules: always run (no hypothesis needed)


@pytest.mark.parametrize("alg", ALGS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_safety_seeded_schedules(alg, seed):
    """All five algorithms stay safe under seeded adversarial schedules,
    mixed cohorts and tight budgets (the regime that maximizes Peterson
    re-acquires and lease handoffs)."""
    cohorts = ((L, L, R, R), (L, R, R, R), (L, L, L, R))[seed]
    b_init = ((1, 1), (2, 3), (1, 4))[seed]
    n_steps = 4000
    sched = _sched(seed * 17 + 5, len(cohorts), n_steps)
    coins = _coins(seed * 31 + 7, n_steps, p_read=(0.3, 0.7, 0.95)[seed])
    iv = cs_intervals(alg, cohorts, b_init, sched, read_bits=coins)
    assert iv, "schedule never reached a critical section"
    assert check_cs_intervals(iv) == []
    if alg == "alock-rw":
        kinds = {k for _, k, _, _ in iv}
        assert kinds == {"read", "write"}, kinds


def test_alock_rw_readers_really_share():
    """The shared section is observable: some read intervals of different
    threads overlap (otherwise the reader path would be indistinguishable
    from a mutex and the exclusion checks above would be vacuous)."""
    cohorts = (L, L, R, R)
    n_steps = 4000
    sched = _sched(11, len(cohorts), n_steps)
    coins = _coins(13, n_steps, p_read=0.9)
    iv = cs_intervals("alock-rw", cohorts, (2, 3), sched, read_bits=coins)
    reads = [i for i in iv if i[1] == "read"]
    shared = [(a, b) for a, b in itertools.combinations(reads, 2)
              if a[0] != b[0] and a[2] < b[3] and b[2] < a[3]]
    assert shared, "no two readers ever overlapped"


# ---------------------------------------------------------------------------
# the seeded mutation: a checker that cannot fail gates nothing


def _mutant_rw_step(stt, tid, cohort, b_init, is_read=False):
    """alock-rw with the writer's reader-count drain check disabled: at
    WR_DRAIN the writer enters the CS without looking at ``word``."""
    if stt.pc[tid] == mc.WR_DRAIN:
        stt = stt._replace(pc=stt.pc[:tid] + (mc.CS,) + stt.pc[tid + 1:])
        return stt, mc.Op("wr_drained", "local", True)
    return mc.alock_rw_step(stt, tid, cohort, b_init, is_read=is_read)


def test_mutation_disabled_drain_is_caught():
    """Disabling the reader-count drain must produce a reader/writer
    overlap the checker reports — on a targeted schedule and under seeded
    random ones."""
    cohorts = (L, R)
    # targeted: T0 enters the read CS, then T1 walks the writer path and
    # (mutant) barges past the drain while the reader still holds
    sched = [0, 0, 1, 1, 1, 1, 1, 1, 1]
    coins = [1, 1, 0, 0, 0, 0, 0, 0, 0]
    iv = cs_intervals("alock-rw", cohorts, (2, 3), sched, read_bits=coins,
                      step_fn=_mutant_rw_step)
    viol = check_cs_intervals(iv)
    assert viol, iv
    kinds = {frozenset((a[1], b[1])) for a, b in viol}
    assert frozenset(("read", "write")) in kinds
    # and the same mutant caught from a plain seeded schedule
    n_steps = 4000
    iv = cs_intervals("alock-rw", (L, L, R, R), (2, 3),
                      _sched(3, 4, n_steps),
                      read_bits=_coins(4, n_steps, 0.6),
                      step_fn=_mutant_rw_step)
    assert check_cs_intervals(iv)
    # the unmutated machine on the identical schedules stays clean
    clean = cs_intervals("alock-rw", cohorts, (2, 3), sched,
                         read_bits=coins)
    assert check_cs_intervals(clean) == []


# ---------------------------------------------------------------------------
# hypothesis properties


@given(st.lists(st.integers(0, 3), min_size=200, max_size=1500),
       st.sampled_from(ALGS),
       st.sampled_from([(L, L, R, R), (L, R, R, R), (L, L, L, R)]),
       st.tuples(st.integers(1, 4), st.integers(1, 6)))
def test_safety_property_all_algorithms(sched, alg, cohorts, b_init):
    """Hypothesis schedules: the CS-interval trace of every algorithm
    passes the oracle (write mutex; reader/writer exclusion)."""
    coins = _coins(sum(sched) + len(sched), len(sched), p_read=0.5)
    iv = cs_intervals(alg, cohorts, b_init, sched, read_bits=coins)
    assert check_cs_intervals(iv) == []


@given(st.integers(0, 2**31 - 1), st.floats(0.05, 0.99))
@settings(max_examples=25)
def test_safety_property_rw_mixes(seed, p_read):
    """alock-rw across the whole read-mix axis: safe at every mix, and
    the trace contains both kinds once both coins have landed."""
    n_steps = 2500
    sched = _sched(seed, 4, n_steps)
    coins = _coins(seed ^ 0x9E3779B9, n_steps, p_read)
    iv = cs_intervals("alock-rw", (L, L, R, R), (2, 3), sched,
                      read_bits=coins)
    assert check_cs_intervals(iv) == []
