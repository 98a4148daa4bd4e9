"""Known-bad fixture corpus: one minimal offender per rule family.

The analyzer's own regression suite. Each fixture is the *smallest*
program (or injected input) that commits exactly the hazard a rule family
exists to catch; ``run_corpus`` runs every family's rules against its
offender and returns the findings per family. A family whose offender
produces **zero** findings means the rule has gone blind — the
``--selftest`` CLI mode and ``tests/test_analysis.py`` both fail on that.

Offenders:

  * ``mosaic_offender`` — a real ``pallas_call`` trace whose kernel does
    int64 math (M001), a dynamic per-row scatter (M002) and a 1-D iota
    (M003): the exact three hazards PR 5 hand-audited out of the event
    kernel;
  * ``rack_offender`` — the hlock topology hazard: a rack-index operand
    held as int64 flowing into the kernel's same-rack tier compare
    (M001) — the exact widening the engine's i32-pinned ``rack`` operand
    exists to prevent;
  * ``x64_offender`` — a trace that manufactures an int64 on a path
    declared x64-off (X001);
  * ``weak_offender`` — a python scalar fed straight into a trace, leaving
    a weak_type operand aval (R001);
  * ``bucket_offender`` — one sweep bucket holding two different abstract
    signatures (R003: a silent recompile per sweep);
  * ``corrupt_buffer_table`` — a VMEM byte table whose ``scr.victim`` row
    drifted from the kernel's real allocation (V001);
  * ``corrupt_open_buffer_table`` — the open-loop variant: the
    per-request dispatch scratch ``scr.curreq`` (only allocated when the
    bucket carries ``R > 0`` request slots) drifted, diffed against a
    real arrival-stream trace — proves V001 watches the traffic buffers
    too (V001).

>>> fams = run_corpus()
>>> sorted(fams) == ["mosaic-lowerability", "retrace-hazards",
...                  "vmem-consistency", "x64-cleanliness"]
True
>>> all(len(f) > 0 for f in fams.values())
True
>>> len({f.rule for fs in fams.values() for f in fs}) >= 4
True
"""
from __future__ import annotations

import functools

import numpy as np

from repro.analysis.entrypoints import Entrypoint
from repro.analysis.rules import (RULES, _stamp, check_bucket_signatures,
                                  check_vmem_consistency, run_rules)

__all__ = ["run_corpus", "mosaic_offender", "rack_offender",
           "x64_offender", "weak_offender", "bucket_offender",
           "corrupt_buffer_table", "corrupt_open_buffer_table"]


def mosaic_offender() -> Entrypoint:
    """A pallas_call whose kernel does everything Mosaic rejects."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    def bad_kernel(x_ref, o_ref):
        v = x_ref[...]
        idx = v[0, 0]                                 # traced scalar
        ramp = lax.iota(jnp.int32, 8)                 # M003: 1-D iota
        wide = v.astype(jnp.int64) * 2                # M001: 64-bit aval
        scat = v.at[0, idx].set(ramp[0])              # M002: dyn scatter
        o_ref[...] = scat + wide.astype(jnp.int32)

    def call(x):
        return pl.pallas_call(
            bad_kernel,
            out_shape=jax.ShapeDtypeStruct((8, 8), jnp.int32),
            interpret=True)(x)

    with jax.enable_x64(True):
        jx = jax.make_jaxpr(call)(np.zeros((8, 8), np.int32))
    return Entrypoint("corpus:mosaic-offender", "pallas-native", jx,
                      x64_off=False)


def rack_offender() -> Entrypoint:
    """A 64-bit rack index reaching the hlock tier compare.

    The engine pins the topology assignment to i32 at lowering
    (``WorkloadOperands.rack``); this fixture is the counterfactual — an
    un-pinned ``np.asarray(racks)`` widening to int64 under x64 and
    flowing into the kernel's same-rack comparison, which Mosaic cannot
    lower (no 64-bit vector registers → M001)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def bad_kernel(rack_ref, o_ref):
        rack = rack_ref[...].astype(jnp.int64)        # M001: wide rack ids
        same_rack = rack[:, :1] == rack               # the tier compare
        o_ref[...] = same_rack.astype(jnp.int32)

    def call(r):
        return pl.pallas_call(
            bad_kernel,
            out_shape=jax.ShapeDtypeStruct((1, 8), jnp.int32),
            interpret=True)(r)

    with jax.enable_x64(True):
        jx = jax.make_jaxpr(call)(np.zeros((1, 8), np.int32))
    return Entrypoint("corpus:rack-offender", "pallas-native", jx,
                      x64_off=False)


def x64_offender() -> Entrypoint:
    """An int64 manufactured on a path that promised zero 64-bit avals."""
    import jax
    import jax.numpy as jnp

    def leaky(x):
        # the classic leak: an unpinned sum widens under x64
        return jnp.sum(x.astype(jnp.int64))

    with jax.enable_x64(True):
        jx = jax.make_jaxpr(leaky)(np.zeros(4, np.int32))
    return Entrypoint("corpus:x64-offender", "pallas-pairs", jx,
                      x64_off=True)


def weak_offender() -> Entrypoint:
    """A python scalar operand: its aval carries weak_type=True."""
    import jax
    jx = jax.make_jaxpr(lambda x: x * 2)(1.0)     # python float, not array
    return Entrypoint("corpus:weak-offender", "xla-batch", jx,
                      x64_off=False)


def bucket_offender() -> dict:
    """One sweep bucket, two abstract signatures: replica 2's locality
    leaked float64 (e.g. an un-pinned ``np.asarray``), so the jit cache
    sees a second signature and recompiles mid-sweep."""
    from repro.workloads import Workload, lower
    ops = lower(Workload("alock", 2, 2, 8, locality=0.9), 512).operands
    drifted = ops._replace(
        locality=np.asarray(ops.locality, np.float64))
    return {"corpus:('alock', 4, 2, 8, 512, 0)": [ops, drifted]}


def corrupt_buffer_table(**kw) -> dict:
    """``vmem.buffer_table`` with ``scr.victim`` silently drifted — the
    planner now budgets a buffer the kernel does not allocate."""
    from repro.kernels.event_loop import vmem
    table = dict(vmem.buffer_table(**kw))
    (shape, nbytes) = table["scr.victim"]
    table["scr.victim"] = ((shape[0], shape[1] + 1), nbytes)
    return table


def corrupt_open_buffer_table(**kw) -> dict:
    """The open-loop drift: ``scr.curreq`` — the per-thread current-request
    dispatch scratch the traffic engine added — silently grew a column.
    Only meaningful against an ``R > 0`` trace (the closed-loop table has
    no such row)."""
    from repro.kernels.event_loop import vmem
    table = dict(vmem.buffer_table(**kw))
    (shape, nbytes) = table["scr.curreq"]
    table["scr.curreq"] = ((shape[0], shape[1] + 1), nbytes)
    return table


@functools.lru_cache(maxsize=1)
def _pairs_entrypoint():
    """One real (tiny) pairs-path trace for the vmem fixture to corrupt."""
    from repro.analysis.entrypoints import trace_entrypoints
    eps = trace_entrypoints(scenarios=["node-churn"], n_events=256,
                            kinds=["pallas-pairs"])
    return eps[0]


@functools.lru_cache(maxsize=1)
def _open_pairs_entrypoint():
    """One real open-loop (R > 0) pairs-path trace — the arrival rows,
    per-request outputs and dispatch scratch are all bound."""
    from repro.analysis.entrypoints import trace_entrypoints
    eps = trace_entrypoints(scenarios=["burst-storm"], n_events=256,
                            kinds=["pallas-pairs"])
    assert eps and all(ep.meta["dims"]["R"] > 0 for ep in eps)
    return eps[0]


def run_corpus() -> dict:
    """Run each family's rules against its known-bad offender.

    Returns ``{family: [Finding, ...]}`` — every list must be non-empty
    for the analyzer to be considered alive (``--selftest`` gates on it).
    """
    out: dict = {}
    out["mosaic-lowerability"] = run_rules(
        [mosaic_offender(), rack_offender()],
        rules=["M001", "M002", "M003"])
    out["x64-cleanliness"] = run_rules([x64_offender()], rules=["X001"])
    retrace = run_rules([weak_offender()], rules=["R001"])
    retrace += _stamp(RULES["R003"], check_bucket_signatures(
        lowered_by_bucket=bucket_offender()))
    out["retrace-hazards"] = retrace
    out["vmem-consistency"] = _stamp(RULES["V001"], check_vmem_consistency(
        _pairs_entrypoint(), table_fn=corrupt_buffer_table))
    out["vmem-consistency"] += _stamp(
        RULES["V001"], check_vmem_consistency(
            _open_pairs_entrypoint(), table_fn=corrupt_open_buffer_table))
    return out
