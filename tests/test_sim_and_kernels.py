"""JAX machine/simulator equivalence + Pallas kernel allclose sweeps."""
import numpy as np
import numpy.random as npr
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import machine as mc
from repro.core.sim import SimConfig, run_schedule, simulate


@pytest.mark.parametrize("alg", ["alock", "mcs", "spinlock"])
def test_jnp_machine_matches_python(alg):
    rng = npr.default_rng(0)
    cohorts = (0, 0, 1, 1)
    sched = rng.integers(0, 4, 2000)
    st_ = mc.initial_state(4)
    pcs = []
    for tid in sched:
        st_, _ = mc.MACHINES[alg](st_, int(tid), cohorts[tid], (2, 3))
        pcs.append(st_.pc)
    _, trace = run_schedule(alg, cohorts, (2, 3), sched)
    assert (np.asarray(pcs) == np.asarray(trace[0])).all()


@given(st.integers(0, 2**31 - 1), st.sampled_from(["alock", "mcs"]))
@settings(max_examples=8)
def test_jnp_machine_matches_python_hypothesis(seed, alg):
    rng = npr.default_rng(seed)
    cohorts = tuple(rng.integers(0, 2, 3).tolist())
    sched = rng.integers(0, 3, 500)
    st_ = mc.initial_state(3)
    for tid in sched:
        st_, _ = mc.MACHINES[alg](st_, int(tid), cohorts[tid], (1, 2))
    sem, _ = run_schedule(alg, cohorts, (1, 2), sched)
    assert tuple(np.asarray(sem.pc)) == st_.pc
    assert tuple(np.asarray(sem.budget)) == st_.budget
    if alg == "alock":
        assert tuple(np.asarray(sem.tail[0])) == st_.tail


def test_event_sim_runs_and_counts():
    r = simulate(SimConfig("alock", 2, 2, 8, 0.9), n_events=60_000)
    assert r.ops > 100
    lats = np.asarray(r.lat_ns)
    lats = lats[lats >= 0]
    assert len(lats) > 50 and (lats > 0).all()


# ---------------------------------------------------------------------------
# Pallas kernels vs oracles (interpret mode on CPU)


@pytest.mark.parametrize("S,hd,dtype", [(128, 64, jnp.float32),
                                        (256, 128, jnp.float32),
                                        (128, 64, jnp.bfloat16)])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 32)])
def test_flash_kernel_sweep(S, hd, dtype, causal, window):
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    key = jax.random.key(0)
    B, H = 2, 2
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, H, S, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 3), (B, H, S, hd), dtype)
    o1 = flash_attention(q, k, v, causal=causal, window=window, bq=64,
                         bk=64, interpret=True)
    o2 = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("S,H,P,N,chunk", [(64, 4, 16, 8, 16),
                                           (128, 2, 32, 16, 32),
                                           (32, 8, 8, 4, 8)])
def test_ssd_kernel_sweep(S, H, P, N, chunk):
    from repro.kernels.ssd_scan.ops import ssd_forward
    from repro.kernels.ssd_scan.ref import ssd_sequential
    key = jax.random.key(1)
    B = 2
    xh = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 2),
                                           (B, S, H)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 3), (H,)) * 0.3)
    b = jax.random.normal(jax.random.fold_in(key, 4), (B, S, N)) * 0.5
    c = jax.random.normal(jax.random.fold_in(key, 5), (B, S, N)) * 0.5
    y0, h0 = ssd_sequential(xh, dt, a, b, c)
    y1, h1 = ssd_forward(xh, dt, a, b, c, chunk=chunk, hb=min(2, H),
                         interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=2e-4,
                               rtol=2e-4)


def _run_tick_vs_machine(rng_seed, Tab, T, steps, tile):
    """Run alock_tick on fresh tables and assert every table's final
    (pc, tail, budget) matches the Python machine oracle."""
    from repro.kernels.alock_tick.kernel import alock_tick
    rng = npr.default_rng(rng_seed)
    cohorts = rng.integers(0, 2, T).astype(np.int32)
    sched = rng.integers(0, T, (Tab, steps)).astype(np.int32)
    b_init = (2, 3)
    z = lambda: jnp.zeros((Tab, T), jnp.int32)
    out = alock_tick(
        jnp.zeros((Tab, 2), jnp.int32), jnp.zeros((Tab, 1), jnp.int32),
        jnp.full((Tab, T), mc.NCS, jnp.int32),
        jnp.full((Tab, T), -1, jnp.int32), z(), z(),
        jnp.asarray(sched), jnp.broadcast_to(jnp.asarray(cohorts), (Tab, T)),
        b_init=b_init, tile=tile, interpret=True)
    assert all(o.shape[0] == Tab for o in out)
    for t in range(Tab):
        st_ = mc.initial_state(T)
        for tid in sched[t]:
            st_, _ = mc.alock_step(st_, int(tid), int(cohorts[tid]), b_init)
        assert tuple(np.asarray(out[2][t])) == st_.pc
        assert tuple(np.asarray(out[0][t])) == st_.tail
        assert tuple(np.asarray(out[3][t])) == st_.budget


def test_alock_tick_kernel_matches_machine():
    _run_tick_vs_machine(rng_seed=5, Tab=8, T=4, steps=300, tile=4)


def test_alock_tick_kernel_pads_nonmultiple_tables():
    """Tab not divisible by tile (e.g. 300 tables, tile 128) must pad the
    batch internally and slice back, not crash."""
    _run_tick_vs_machine(rng_seed=11, Tab=6, T=3, steps=150, tile=4)


def test_flash_bwd_kernels_match_oracle():
    from repro.kernels.flash_attention.ops import mha_vjp
    from repro.kernels.flash_attention.ref import attention_ref
    key = jax.random.key(0)
    B, H, S, hd = 2, 2, 64, 16
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, H, S, hd))
    k = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, hd))
    v = jax.random.normal(jax.random.fold_in(key, 3), (B, H, S, hd))
    for causal, window in ((True, None), (True, 16), (False, None)):
        def f1(q, k, v):
            return mha_vjp(q, k, v, causal=causal, window=window, bq=16,
                           bk=16, interpret=True).sum()

        def f2(q, k, v):
            return attention_ref(q, k, v, causal=causal,
                                 window=window).sum()
        g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5)
