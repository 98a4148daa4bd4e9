"""Property tests for the hi/lo i32 clock helpers (`kernels/event_loop/
i32pair`): every operation round-trips against an int64 reference across
carry boundaries, INT32_MAX±1, and the parked-thread ``never`` sentinel.

Runs with x64 off (the whole point of the representation); int64
references are computed host-side in numpy. Hypothesis drives the
property legs; the deterministic edge-case legs below pin the boundaries.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st
from repro.kernels.event_loop import i32pair as p32

I64_MAX = np.iinfo(np.int64).max
NEVER = I64_MAX

# the boundary values the kernel actually crosses: zero, low-word
# carry edges, hi-word sign edges, INT32_MAX±1 and the never sentinel
EDGES = np.int64([0, 1, -1, 2**31 - 2, 2**31 - 1, 2**31, 2**31 + 1,
                  2**32 - 1, 2**32, 2**32 + 1, -2**31, -2**31 - 1,
                  -2**32, 3 * 10**18, -3 * 10**18, NEVER, -NEVER - 1])


def to_pair(x):
    hi, lo = p32.unpack_np(np.asarray(x, np.int64))
    return (jnp.asarray(hi), jnp.asarray(lo))


def from_pair(p):
    return p32.pack_np(np.asarray(p[0]), np.asarray(p[1]))


def _pairs_grid():
    """Every ordered pair of edge values — 17 x 17 combinations."""
    a = np.repeat(EDGES, len(EDGES))
    b = np.tile(EDGES, len(EDGES))
    return a, b


def test_pack_unpack_round_trip_edges():
    np.testing.assert_array_equal(from_pair(to_pair(EDGES)), EDGES)


def test_never_sentinel_is_i64_max():
    assert p32.pack_np(*p32.NEVER) == NEVER
    pe = to_pair(EDGES)
    is_never = np.asarray(p32.peq(pe, p32.NEVER))
    np.testing.assert_array_equal(is_never, EDGES == NEVER)


def test_add_sub_carry_edges():
    a, b = _pairs_grid()
    with np.errstate(over="ignore"):
        np.testing.assert_array_equal(
            from_pair(p32.padd(to_pair(a), to_pair(b))), a + b)
        np.testing.assert_array_equal(
            from_pair(p32.psub(to_pair(a), to_pair(b))), a - b)


def test_add_i32_both_signs_across_carry():
    base = np.int64([2**32 - 1, 2**32, -1, 0, 2**31 - 1, NEVER - 1])
    for d in (-3, -1, 0, 1, 3, 2**31 - 1, -2**31):
        got = from_pair(p32.padd_i32(to_pair(base), jnp.int32(d)))
        np.testing.assert_array_equal(got, base + d)


def test_compare_edges():
    a, b = _pairs_grid()
    pa, pb = to_pair(a), to_pair(b)
    np.testing.assert_array_equal(np.asarray(p32.plt(pa, pb)), a < b)
    np.testing.assert_array_equal(np.asarray(p32.ple(pa, pb)), a <= b)
    np.testing.assert_array_equal(np.asarray(p32.peq(pa, pb)), a == b)
    np.testing.assert_array_equal(from_pair(p32.pmin2(pa, pb)),
                                  np.minimum(a, b))
    np.testing.assert_array_equal(from_pair(p32.pmax2(pa, pb)),
                                  np.maximum(a, b))


def test_argmin_and_reductions_with_mask_and_ties():
    rng = np.random.default_rng(7)
    m = rng.choice(EDGES, size=(32, 16))
    m[0] = m[0][0]                       # full-row tie -> index 0
    m[1, 3] = m[1, 7] = np.int64(5)      # duplicate min -> first index
    mask = rng.integers(0, 2, m.shape).astype(bool)
    mask[2] = False                      # all-masked row -> index 0
    mask[:, 5] = True
    pm, jmask = to_pair(m), jnp.asarray(mask)
    filled = np.where(mask, m, NEVER)
    np.testing.assert_array_equal(
        np.asarray(p32.argmin_masked(pm, jmask)),
        np.argmin(filled, axis=1))
    np.testing.assert_array_equal(np.asarray(p32.argmin_masked(pm)),
                                  np.argmin(m, axis=1))
    np.testing.assert_array_equal(
        from_pair(p32.reduce_min_masked(pm, jmask)),
        np.min(filled, axis=1))
    np.testing.assert_array_equal(from_pair(p32.reduce_max(pm)),
                                  np.max(m, axis=1))


def test_mod_pow2_round_trip():
    v = np.abs(np.concatenate([EDGES[:-2], np.int64([2**33 + 70])]))
    for m in (1, 64, 1 << 15):
        np.testing.assert_array_equal(
            np.asarray(p32.mod_pow2(to_pair(v), m)), v % m)
    with pytest.raises(ValueError):
        p32.mod_pow2(to_pair(v), 48)


def test_gather_one_hot():
    rng = np.random.default_rng(3)
    m = rng.choice(EDGES, size=(8, 6))
    idx = rng.integers(0, 6, 8)
    oh = jnp.asarray(np.arange(6)[None, :] == idx[:, None])
    np.testing.assert_array_equal(from_pair(p32.pgather(oh, to_pair(m))),
                                  m[np.arange(8), idx])


# -- hypothesis legs --------------------------------------------------------

BOUND = 2**62        # keep a+b inside int64 so the reference never wraps
i64s = st.lists(st.integers(min_value=-BOUND, max_value=BOUND - 1),
                min_size=1, max_size=40)


@settings(max_examples=200, deadline=None)
@given(i64s, i64s)
def test_prop_add_sub_compare(xs, ys):
    n = min(len(xs), len(ys))
    a = np.asarray(xs[:n], np.int64)
    b = np.asarray(ys[:n], np.int64)
    pa, pb = to_pair(a), to_pair(b)
    np.testing.assert_array_equal(from_pair(p32.padd(pa, pb)), a + b)
    np.testing.assert_array_equal(from_pair(p32.psub(pa, pb)), a - b)
    np.testing.assert_array_equal(np.asarray(p32.plt(pa, pb)), a < b)
    np.testing.assert_array_equal(np.asarray(p32.ple(pa, pb)), a <= b)
    np.testing.assert_array_equal(np.asarray(p32.peq(pa, pb)), a == b)


@settings(max_examples=100, deadline=None)
@given(st.lists(st.integers(min_value=-BOUND, max_value=BOUND),
                min_size=2, max_size=24),
       st.integers(min_value=0, max_value=2**24))
def test_prop_argmin_matches_i64(xs, maskbits):
    a = np.asarray(xs, np.int64).reshape(1, -1)
    mask = np.asarray([(maskbits >> i) & 1 for i in range(a.shape[1])],
                      bool).reshape(1, -1)
    if not mask.any():
        mask[0, 0] = True
    pa = to_pair(a)
    np.testing.assert_array_equal(
        np.asarray(p32.argmin_masked(pa, jnp.asarray(mask))),
        np.argmin(np.where(mask, a, NEVER), axis=1))
    np.testing.assert_array_equal(np.asarray(p32.argmin_masked(pa)),
                                  np.argmin(a, axis=1))


@settings(max_examples=100, deadline=None)
@given(st.integers(min_value=0, max_value=2**62),
       st.integers(min_value=0, max_value=20))
def test_prop_mod_pow2(v, log2m):
    m = 1 << log2m
    assert int(np.asarray(p32.mod_pow2(to_pair(np.int64([v])), m))[0]) \
        == v % m
