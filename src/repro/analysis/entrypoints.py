"""Trace the engine's public entrypoints to closed jaxprs — no execution.

The analyzer's raw material: every workload spec a registered simulator
scenario sweeps (``repro.experiments.scenario_workloads``) is lowered and
bucketed exactly like ``batch.sweep`` buckets it (shape key + phase
padding), and each bucket is traced through the public engine entrypoints
with ``jax.make_jaxpr`` — abstract evaluation only, nothing compiles,
nothing dispatches, no TPU required:

  ============== ==========================================================
  kind           what is traced
  ============== ==========================================================
  xla-batch      ``batch._run_events_batch`` (the vmapped XLA oracle),
                 under x64 — int64 clocks are that path's contract
  pallas-native  ``ops.run_events`` with ``interpret=False``, under x64 —
                 the kernel exactly as Mosaic would receive it (tracing
                 never invokes Mosaic)
  pallas-pairs   ``ops.run_events_pairs`` with x64 **disabled** — the
                 zero-int64 contract the x64-off CI leg runs
  ============== ==========================================================

The Mosaic-lowerability family applies to the Pallas kinds
(``Entrypoint.is_pallas``), and their ``meta`` records the
VMEM plan + static dims the vmem-consistency rule diffs the byte table
against. Tracing runs under an explicit x64 context per row, so the
catalog is identical whether the host process enables x64 or not.

Open-loop buckets ride in through the same three kinds: a scenario whose
workloads carry :class:`~repro.workloads.Arrivals` lowers to a shape key
with ``R > 0`` request slots, so the traced jaxprs include the arrival
ingestion/dispatch lanes and the per-request outputs, and the rules lint
them exactly like the closed loop. ``meta["dims"]["R"]`` /
``meta["open_loop"]`` mark those rows; the vmem-consistency rule prices
the open-loop buffer table through the same ``R``.

>>> eps = trace_entrypoints(scenarios=["node-churn"], n_events=512)
>>> sorted({ep.kind for ep in eps})
['pallas-native', 'pallas-pairs', 'xla-batch']
>>> pairs = [ep for ep in eps if ep.kind == "pallas-pairs"]
>>> pairs[0].x64_off and pairs[0].is_pallas
True
>>> pairs[0].meta["plan"].budget is not None
True
>>> ramp = trace_entrypoints(scenarios=["burst-storm"], n_events=512,
...                          kinds=["xla-batch"])
>>> all(ep.meta["open_loop"] and ep.meta["dims"]["R"] > 0 for ep in ramp)
True
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Iterable

import numpy as np

__all__ = ["Entrypoint", "trace_entrypoints", "collect_buckets"]

#: trace length: shapes only (the event loop traces once regardless), so
#: small keeps the operand avals cheap while every phase program stays
#: strictly increasing
DEFAULT_TRACE_EVENTS = 2048
#: the chip the native rows are traced for: tracing needs no device, so
#: the VMEM budget comes from this kind's table entry, not from the host
LINT_DEVICE_KIND = "TPU v5 lite"


@dataclass(frozen=True)
class Entrypoint:
    """One traced engine entrypoint: a closed jaxpr + rule context."""
    name: str            # e.g. "pallas-pairs:('alock', 16, 4, 16, 2048, 0)"
    kind: str            # xla-batch | pallas-native | pallas-pairs
    jaxpr: Any           # jax.core.ClosedJaxpr
    x64_off: bool        # x64-cleanliness rule applies
    meta: dict = field(default_factory=dict, compare=False)

    @property
    def is_pallas(self) -> bool:
        """A Pallas kernel entrypoint: Mosaic-lowerability rules apply."""
        return self.kind.startswith("pallas-")


def collect_buckets(scenarios: Iterable[str] | None = None,
                    n_events: int = DEFAULT_TRACE_EVENTS) -> dict:
    """Lower + bucket every scenario workload the way ``sweep`` would.

    Returns ``{shape_key: (batched WorkloadOperands, meta)}`` — one entry
    per distinct compile bucket across the selected scenarios (default:
    all registered simulator scenarios), each replica phase-padded to its
    bucket max so the batched leaves stack. ``meta`` records which
    scenarios contributed.
    """
    from repro.experiments import scenario_names, scenario_workloads
    from repro.workloads import WorkloadOperands, lower, pad_phases
    names = list(scenarios) if scenarios is not None else scenario_names()
    per_key: dict = {}
    sources: dict = {}
    for scen in names:
        wls = scenario_workloads(scen)
        if not wls:
            continue
        for w in wls:
            lw = lower(w, n_events)
            per_key.setdefault(lw.shape_key, []).append(lw.operands)
            sources.setdefault(lw.shape_key, set()).add(scen)
    buckets = {}
    for key, ops in per_key.items():
        pmax = max(o.n_phases for o in ops)
        padded = [pad_phases(o, pmax) for o in ops]
        wl = WorkloadOperands(*(np.stack([np.asarray(getattr(o, f))
                                          for o in padded])
                                for f in WorkloadOperands._fields))
        buckets[key] = (wl, {"scenarios": sorted(sources[key]),
                             "n_phases": pmax})
    return buckets


def _trace(fn, *args):
    import jax
    return jax.make_jaxpr(fn)(*args)


def trace_entrypoints(scenarios: Iterable[str] | None = None,
                      n_events: int = DEFAULT_TRACE_EVENTS,
                      kinds: Iterable[str] | None = None
                      ) -> list[Entrypoint]:
    """Build the full traced-entrypoint catalog for the rule engine.

    One entrypoint per (bucket, kind); ``kinds`` filters (default: all
    three). Tracing is abstract evaluation only — no executable is built,
    no kernel runs, and the process-wide x64 flag is saved/restored.
    """
    import jax
    from repro.core.batch import _run_events_batch
    from repro.core.sim import LAT_SAMPLES, topology
    from repro.kernels.event_loop import ops as el_ops
    from repro.workloads import WorkloadOperands

    want = set(kinds) if kinds is not None else {
        "xla-batch", "pallas-native", "pallas-pairs"}
    eps: list[Entrypoint] = []
    for key, (wl, bmeta) in collect_buckets(scenarios, n_events).items():
        alg, T, N, K, ne, R = key
        B, P = wl.seed.shape[0], bmeta["n_phases"]
        thread_node, lock_node, _ = topology(alg, N, T // N, K)
        # hl/rw ride in dims so the vmem rule prices the alg-gated buffers
        # (rack row, read coin/probability rows, reader-count scratch)
        dims = {"T": T, "N": N, "K": K, "P": P, "R": R,
                "hl": alg == "hlock", "rw": alg == "alock-rw"}
        meta = dict(bmeta, shape_key=key, B=B, dims=dims,
                    open_loop=R > 0)

        def j(a):
            return jax.numpy.asarray(a)

        wlj = WorkloadOperands(*(j(a) for a in wl))
        tn, ln = j(thread_node), j(lock_node)

        if "xla-batch" in want:
            with jax.enable_x64(True):
                jx = _trace(functools.partial(
                    _run_events_batch, alg, T, N, K, ne), wlj, tn, ln)
            eps.append(Entrypoint(f"xla-batch:{key}", "xla-batch", jx,
                                  x64_off=False, meta=meta))
        # the native rows re-plan exactly like run_events will (single
        # clamping+planning code path), so the vmem rule diffs the same
        # (tile, ev_chunk) the traced pallas_call actually bound
        if "pallas-native" in want:
            plan = el_ops.plan_for_run(B, P, ne, T, N, K, R=R,
                                       hl=dims["hl"], rw=dims["rw"],
                                       interpret=False,
                                       device_kind=LINT_DEVICE_KIND)
            with jax.enable_x64(True):
                jx = _trace(functools.partial(
                    el_ops.run_events, alg, T, N, K, ne, interpret=False,
                    device_kind=LINT_DEVICE_KIND),
                    wlj, tn, ln)
            eps.append(Entrypoint(f"pallas-native:{key}", "pallas-native",
                                  jx, x64_off=False,
                                  meta=dict(meta, plan=plan)))
        if "pallas-pairs" in want:
            plan = el_ops.plan_for_run(B, P, ne, T, N, K, R=R,
                                       hl=dims["hl"], rw=dims["rw"],
                                       interpret=False,
                                       device_kind=LINT_DEVICE_KIND)
            with jax.enable_x64(False):
                jx = _trace(functools.partial(
                    el_ops.run_events_pairs, alg, T, N, K, ne,
                    interpret=False, device_kind=LINT_DEVICE_KIND),
                    wlj, tn, ln)
            eps.append(Entrypoint(f"pallas-pairs:{key}", "pallas-pairs",
                                  jx, x64_off=True,
                                  meta=dict(meta, plan=plan,
                                            lat_samples=LAT_SAMPLES)))
    return eps
