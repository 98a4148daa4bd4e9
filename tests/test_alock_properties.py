"""The paper's TLA+ properties, checked exhaustively and adversarially."""
import itertools

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import machine as mc
from repro.core.tla import bounded_overtaking, explore

L, R = mc.LOCAL, mc.REMOTE


@pytest.mark.parametrize("machine", ["alock", "mcs", "spinlock"])
@pytest.mark.parametrize("cohorts", [(L, R), (L, L, R), (L, R, R)])
def test_model_check_small(machine, cohorts):
    r = explore(machine, cohorts, b_init=(2, 3))
    assert r.mutex_ok, r.violations[:2]
    assert r.deadlock_free, r.violations[:2]
    assert r.eventual_entry, r.violations[:2]


def test_model_check_alock_2plus2():
    r = explore("alock", (L, L, R, R), b_init=(2, 2))
    assert r.ok and r.states > 10_000


def test_model_check_alock_budget_variants():
    for b in [(1, 1), (1, 3), (3, 1)]:
        r = explore("alock", (L, L, R), b_init=b)
        assert r.ok, (b, r.violations[:2])


@given(st.lists(st.integers(0, 3), min_size=200, max_size=2000),
       st.sampled_from([(L, L, R, R), (L, R, R, R), (L, L, L, R)]),
       st.tuples(st.integers(1, 4), st.integers(1, 6)))
def test_mutex_random_schedules(sched, cohorts, b_init):
    """Hypothesis adversarial schedules: never two threads in CS."""
    st_ = mc.initial_state(4)
    for tid in sched:
        st_, _ = mc.alock_step(st_, tid, cohorts[tid], b_init)
        assert sum(1 for t in range(4) if st_.pc[t] == mc.CS) <= 1


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15)
def test_alock_bounded_overtaking(seed):
    """Budgets make overtaking bounded (fairness). A waiting thread sees at
    most ~(b_local + b_remote) CS entries before entering."""
    import random
    rng = random.Random(seed)
    cohorts = (L, L, R, R)
    b = (2, 3)
    sched = (rng.randrange(4) for _ in itertools.count())
    worst = bounded_overtaking("alock", cohorts, b, sched, steps=30_000)
    assert worst <= b[0] + b[1] + 4, worst


def test_quiescence_resets_tails():
    """If everyone returns to NCS, both Peterson flags (tails) are clear."""
    import random
    rng = random.Random(3)
    cohorts = (L, R, R)
    st_ = mc.initial_state(3)
    for _ in range(50_000):
        tid = rng.randrange(3)
        st_, _ = mc.alock_step(st_, tid, cohorts[tid], (2, 2))
    # drive everyone to NCS round-robin (each gets unlimited turns)
    for tid in range(3):
        guard = 0
        while st_.pc[tid] != mc.NCS:
            prev = st_
            st_, _ = mc.alock_step(st_, tid, cohorts[tid], (2, 2))
            guard += 1
            if st_ == prev:
                # blocked on another thread: give others one step each
                for o in range(3):
                    if o != tid and st_.pc[o] != mc.NCS:
                        st_, _ = mc.alock_step(st_, o, cohorts[o], (2, 2))
            assert guard < 10_000
    assert st_.tail == (0, 0)
