"""Fail-slow fault-injection satellites: node_mult spec forms, lowering,
and the monotone-degradation property (raising a node's multiplier never
decreases that node's observed p50 latency)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st
from repro.core.sim import topology
from repro.kernels.event_loop.ref import run_events_ref
from repro.workloads import (NODE_MULT_PROFILES, Phase, Workload,
                             WorkloadOperands, freeze_node_mult, lower,
                             node_mult_pairs, resolve_node_mult)

# ---------------------------------------------------------------- spec


def test_freeze_node_mult_forms():
    assert freeze_node_mult(None) is None
    assert freeze_node_mult("healthy") == "healthy"
    assert freeze_node_mult({2: 4.0, 0: 2.0}) == ((0, 2.0), (2, 4.0))
    assert node_mult_pairs("limp-node0-4x") == ((0, 4.0),)
    assert resolve_node_mult({1: 3.0}, 4) == (1.0, 3.0, 1.0, 1.0)
    assert resolve_node_mult(None, 3) == (1.0, 1.0, 1.0)
    assert "limp-node0-2x" in NODE_MULT_PROFILES


def test_node_mult_validation():
    with pytest.raises(ValueError, match="profile"):
        freeze_node_mult("no-such-profile")
    with pytest.raises(ValueError, match="> 0"):
        freeze_node_mult({0: 0.0})
    with pytest.raises(ValueError, match="> 0"):
        freeze_node_mult({0: float("inf")})
    with pytest.raises(ValueError, match="duplicate"):
        freeze_node_mult([(0, 2.0), (0, 3.0)])
    with pytest.raises(ValueError, match="node ids"):
        Workload("alock", 2, 2, 4, node_mult={5: 2.0})
    with pytest.raises(ValueError, match=r"phases\[1\].node_mult"):
        Workload("alock", 2, 2, 4,
                 phases=(Phase(frac=0.5),
                         Phase(frac=0.5, node_mult={3: 2.0})))
    # frozen specs stay hashable and comparable
    a = Workload("alock", 2, 2, 4, node_mult={0: 2.0})
    b = Workload("alock", 2, 2, 4, node_mult=[(0, 2.0)])
    assert a == b and hash(a) == hash(b)


def test_lowering_emits_per_phase_node_mult_rows():
    w = Workload("alock", 3, 2, 6, node_mult={2: 2.0},
                 phases=(Phase(frac=0.5),
                         Phase(frac=0.5, node_mult="limp-node0-4x")))
    o = lower(w, 1000).operands
    assert o.node_mult.shape == (2, 3)
    assert o.node_mult.dtype == np.float32
    np.testing.assert_array_equal(o.node_mult,
                                  [[1.0, 1.0, 2.0],   # workload base
                                   [4.0, 1.0, 1.0]])  # phase override


# ------------------------------------------------- monotone degradation


def _node_p50(node, mult, seed, ev=800, lat_samples=512):
    """p50 acquire->release latency observed *on* ``node``: every other
    node is parked for the whole run, so the latency pool is exactly the
    degraded node's own traffic."""
    N, tpn, K = 2, 2, 4
    others = tuple(n for n in range(N) if n != node)
    w = Workload("alock", N, tpn, K, locality=1.0, seed=seed,
                 node_mult={node: float(mult)},
                 phases=(Phase(frac=1.0, down_nodes=others),))
    lw = lower(w, ev)
    alg, T, N_, K_, _, _ = lw.shape_key
    tn, ln, _ = topology(alg, N_, tpn, K_)
    wl = WorkloadOperands(*(jnp.asarray(a)[None] for a in lw.operands))
    with jax.enable_x64(True):
        done, lat, lat_n, *_ = run_events_ref(alg, T, N_, K_, ev, wl, tn,
                                              ln, lat_samples=lat_samples)
    n = int(min(int(lat_n[0]), lat_samples))
    assert n > 0
    return float(np.percentile(np.asarray(lat[0][:n]), 50))


def test_monotone_degradation_chain():
    """Deterministic spine of the property (no random search):
    1x -> 2x -> 4x never decreases the node's p50, on either node."""
    for node in (0, 1):
        p50s = [_node_p50(node, m, seed=3) for m in (1.0, 2.0, 4.0)]
        assert p50s == sorted(p50s), (node, p50s)
        assert p50s[-1] > p50s[0]       # 4x really hurts


@settings(max_examples=8, deadline=None, derandomize=True)
@given(node=st.integers(0, 1),
       lo=st.floats(1.0, 4.0), factor=st.floats(1.0, 4.0),
       seed=st.integers(0, 2**16))
def test_monotone_degradation_property(node, lo, factor, seed):
    """Raising any node's fail-slow multiplier never decreases that
    node's observed p50 latency."""
    hi = lo * factor
    assert _node_p50(node, hi, seed) >= _node_p50(node, lo, seed)
