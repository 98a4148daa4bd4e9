"""The Pallas path's within-node Zipf draw (``ops.zipf_offset``): both
of its forms, the fused compare with each phase's row and the merge of
the events into the sorted CDF, equal the XLA engine's per-event
``min(sum(u >= cdf[ph]), kpn - 1)`` bit for bit, and the draw forms
nothing that grows with events x ranks."""
import numpy as np
import pytest
import jax

from repro.kernels.event_loop import ops
from repro.workloads import zipf_cdf


def _compare(cdf, ph, u):
    kpn = cdf.shape[1]
    return np.minimum((u[:, None] >= cdf[ph]).sum(axis=1), kpn - 1)


FORMS = {"derived": ops.zipf_offset, "compare": ops.zipf_compare,
         "merge": ops.zipf_merge}


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("kpn", [1, 5, 10, 100, 10_000])
def test_each_form_equals_the_compare(kpn, form):
    """Two phases (Zipf 0.99 and uniform); uniforms at random, exactly
    on every CDF entry and one ulp either side of it, 0, and the largest
    float32 below 1, which walks past every entry but the last when the
    row's cumsum ends short of 1 (the clamp)."""
    cdf = np.stack([zipf_cdf(kpn, 0.99), zipf_cdf(kpn, 0.0)])
    rng = np.random.default_rng(kpn)
    flat = cdf.ravel()
    u = np.concatenate([
        rng.random(4096, np.float32), flat,
        np.nextafter(flat, np.float32(0)), np.nextafter(flat, np.float32(2)),
        np.float32([0.0, np.nextafter(np.float32(1), np.float32(0))]),
    ]).astype(np.float32)
    ph = rng.integers(0, 2, u.size).astype(np.int32)
    got = np.asarray(jax.jit(FORMS[form])(cdf, ph, u))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, _compare(cdf, ph, u))
    # the clamp: a row whose last entry falls short of u still draws the
    # last rank, never kpn
    short = np.float32([np.linspace(0.1, 0.5, kpn)])
    top = np.float32([0.75])
    assert int(FORMS[form](short, np.int32([0]), top)[0]) == kpn - 1
    # one phase: no phase key
    np.testing.assert_array_equal(
        np.asarray(FORMS[form](cdf[:1], np.zeros_like(ph), u)),
        _compare(cdf[:1], np.zeros_like(ph), u))


def test_the_form_follows_the_ranks():
    assert ops.ZIPF_COMPARE_RANKS < 10_000
    cdf = np.float32([zipf_cdf(10_000, 0.99)])
    text = str(jax.make_jaxpr(ops.zipf_offset)(
        cdf, np.zeros(8, np.int32), np.zeros(8, np.float32)))
    assert "sort" in text
    text = str(jax.make_jaxpr(ops.zipf_offset)(
        cdf[:, :100], np.zeros(8, np.int32), np.zeros(8, np.float32)))
    assert "sort" not in text


def _largest_value(n_events, kpn):
    """The element count of the largest value the traced draw forms, for
    2 replicas of one phase."""
    jx = jax.make_jaxpr(lambda s, e, c: ops.precompute_draws(
        s, e, c, n_events, 10, rw=True))(
            jax.ShapeDtypeStruct((2,), np.int32),
            jax.ShapeDtypeStruct((2, 1), np.int32),
            jax.ShapeDtypeStruct((2, 1, kpn), np.float32))

    def avals(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                yield v.aval
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from avals(sub)

    return max(int(np.prod(a.shape)) for a in avals(jx.jaxpr)
               if hasattr(a, "shape"))


def test_the_draw_forms_nothing_of_events_by_ranks():
    """At 10,000 ranks a node and 150,000 events the largest value of the
    traced draw is the one it forms at 1 rank (the per-event keys): no
    value grows with the ranks, as the gathered CDF row per event of the
    compare it replaced did. (Up to ``ZIPF_COMPARE_RANKS`` the compare
    traces an events x ranks value, which XLA fuses into its count.)"""
    big = _largest_value(150_000, 10_000)
    assert big == _largest_value(150_000, 1)
    assert big < 150_000 * 10_000 // 100
