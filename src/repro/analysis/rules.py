"""Rule registry + the four shipped rule families.

A *rule* inspects traced entrypoints (``repro.analysis.entrypoints``) —
closed jaxprs obtained **without executing** anything — and emits
structured :class:`Finding`\\ s. Rules come in two scopes:

  * ``entrypoint`` — run once per traced entrypoint (most rules);
  * ``global`` — run once per analysis over python-level invariants that
    are not a property of any single jaxpr (jit cache keys, bucket
    signatures across a whole scenario sweep).

Shipped families (rule ids are stable — baselines and CI grep them):

  ============ ======== ====================================================
  family       rules    catches
  ============ ======== ====================================================
  mosaic-      M001     64-bit avals inside a Pallas kernel
  lowerability M002     dynamic scatter/gather inside the kernel
               M003     1-D iota inside the kernel (Mosaic requires >= 2D)
  x64-         X001     any 64-bit aval in the x64-off pairs path
  cleanliness
  retrace-     R001     weak_type leaking into traced entrypoint operands
  hazards      R003     >1 abstract signature per sweep bucket (recompiles)
  vmem-        V001     ``vmem.py`` byte-table drift vs the kernel's actual
  consistency           pallas_call buffers
  ============ ======== ====================================================

Adding a rule (see ``docs/analysis.md``): write a check function returning
a list of findings and decorate it —

>>> from repro.analysis.rules import RULES, rule
>>> @rule("T900", family="demo", severity="error",
...       summary="never fires (docs example)")
... def _demo(ep):
...     return []
>>> RULES["T900"].family
'demo'
>>> _ = RULES.pop("T900")      # keep the registry clean after the demo

``run_rules`` drives every registered rule over a list of entrypoints and
returns the combined findings (empty list == lint-clean).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Callable, Iterable

import numpy as np

from repro.analysis.walk import all_avals, walk_jaxpr

__all__ = ["Finding", "Rule", "RULES", "rule", "run_rules",
           "check_bucket_signatures", "check_vmem_consistency",
           "bucket_signature"]

#: scatter/gather primitive names Mosaic cannot lower against VMEM state
#: (the kernel re-expresses them as masked one-hot selects)
DYNAMIC_MEMORY_PRIMS = frozenset({
    "scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max",
    "gather",
})


@dataclass(frozen=True)
class Finding:
    """One structured lint hit: what fired, where, and how to fix it."""
    rule: str            # stable id, e.g. "M001"
    family: str          # rule family, e.g. "mosaic-lowerability"
    severity: str        # "error" | "warning"
    entrypoint: str      # traced entrypoint name (or "<global>")
    where: str           # eqn provenance: path + file:line
    message: str
    hint: str = ""

    def format(self) -> str:
        loc = f" [{self.where}]" if self.where else ""
        tail = f"\n      hint: {self.hint}" if self.hint else ""
        return (f"{self.rule} ({self.family}, {self.severity}) "
                f"{self.entrypoint}{loc}\n      {self.message}{tail}")


@dataclass(frozen=True)
class Rule:
    id: str
    family: str
    severity: str
    summary: str
    scope: str                       # "entrypoint" | "global"
    check: Callable = field(compare=False)


RULES: dict[str, Rule] = {}


def rule(id: str, *, family: str, severity: str = "error",
         summary: str = "", scope: str = "entrypoint"):
    """Register a check function under a stable rule id.

    ``scope="entrypoint"`` checks are called as ``check(ep)`` per traced
    entrypoint; ``scope="global"`` checks are called once as
    ``check(entrypoints)``. Both return an iterable of findings (the
    decorator stamps ``rule``/``family``/``severity`` onto any finding
    the check left blank, so checks can just describe the defect).
    """
    if scope not in ("entrypoint", "global"):
        raise ValueError(f"scope must be 'entrypoint' or 'global', "
                         f"got {scope!r}")

    def deco(fn):
        if id in RULES:
            raise ValueError(f"rule {id!r} already registered")
        RULES[id] = Rule(id, family, severity, summary, scope, fn)
        return fn
    return deco


def _stamp(r: Rule, findings: Iterable[Finding]) -> list[Finding]:
    out = []
    for f in findings:
        if not f.rule:
            f = replace(f, rule=r.id, family=r.family, severity=r.severity)
        out.append(f)
    return out


def run_rules(entrypoints, rules: Iterable[str] | None = None
              ) -> list[Finding]:
    """Run the selected rules (default: all) over the traced entrypoints.

    Returns every finding, entrypoint-scoped rules first (in entrypoint
    order), then global rules. An empty list means lint-clean.
    """
    eps = list(entrypoints)
    active = [RULES[i] for i in rules] if rules is not None \
        else list(RULES.values())
    findings: list[Finding] = []
    for r in active:
        if r.scope != "entrypoint":
            continue
        for ep in eps:
            findings += _stamp(r, r.check(ep))
    for r in active:
        if r.scope == "global":
            findings += _stamp(r, r.check(eps))
    return findings


def _wide(dtype) -> bool:
    """True for 64-bit *numeric* dtypes (extended dtypes — PRNG keys —
    are opaque and skipped)."""
    import jax
    if dtype is None:
        return False
    try:
        if jax.dtypes.issubdtype(dtype, jax.dtypes.extended):
            return False
        return np.dtype(dtype).itemsize == 8
    except TypeError:
        return False


def _f(ep_name, where, message, hint="") -> Finding:
    return Finding("", "", "", ep_name, where, message, hint)


# ---------------------------------------------------------------------------
# mosaic-lowerability: applies to Pallas kernel entrypoints (Mosaic has no
# 64-bit vector registers, rejects dynamic scatters against VMEM state,
# and requires >= 2D iota)


@rule("M001", family="mosaic-lowerability",
      summary="64-bit aval inside the Pallas kernel")
def _kernel_wide_dtype(ep):
    if not ep.is_pallas:
        return []
    out, seen = [], set()
    for site in walk_jaxpr(ep.jaxpr):
        if not site.in_kernel:
            continue
        for v in list(site.eqn.invars) + list(site.eqn.outvars):
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            key = (site.path, site.eqn.primitive.name, str(dt))
            if _wide(dt) and key not in seen:
                seen.add(key)
                out.append(_f(
                    ep.name, f"{site.path} @ {site.src}",
                    f"{dt} aval on `{site.eqn.primitive.name}` inside the "
                    f"kernel jaxpr — Mosaic has no 64-bit vectors",
                    "hold clocks as hi/lo i32 pairs "
                    "(kernels/event_loop/i32pair.py)"))
    return out


@rule("M002", family="mosaic-lowerability",
      summary="dynamic scatter/gather inside the kernel")
def _kernel_dynamic_scatter(ep):
    if not ep.is_pallas:
        return []
    out = []
    for site in walk_jaxpr(ep.jaxpr):
        if site.in_kernel and site.eqn.primitive.name in DYNAMIC_MEMORY_PRIMS:
            out.append(_f(
                ep.name, f"{site.path} @ {site.src}",
                f"`{site.eqn.primitive.name}` inside the kernel jaxpr — "
                f"Mosaic rejects per-row dynamic scatter/gather against "
                f"VMEM state",
                "re-express as a masked one-hot select over an aligned "
                "window of the indexed axis (see draw() in "
                "kernels/event_loop/kernel.py)"))
    return out


@rule("M003", family="mosaic-lowerability",
      summary="1-D iota inside the kernel")
def _kernel_1d_iota(ep):
    if not ep.is_pallas:
        return []
    out = []
    for site in walk_jaxpr(ep.jaxpr):
        if (site.in_kernel and site.eqn.primitive.name == "iota"
                and len(site.eqn.params.get("shape", (0, 0))) < 2):
            out.append(_f(
                ep.name, f"{site.path} @ {site.src}",
                f"1-D iota (shape {site.eqn.params.get('shape')}) inside "
                f"the kernel jaxpr — Mosaic requires >= 2D iota",
                "use lax.broadcasted_iota with a 2D shape (the kernel's "
                "_iota helper)"))
    return out


# ---------------------------------------------------------------------------
# x64-cleanliness: the pairs path must run with x64 entirely off — a single
# 64-bit aval anywhere in the trace means some dtype was left unpinned


@rule("X001", family="x64-cleanliness",
      summary="64-bit aval in the x64-off pairs path")
def _x64_clean(ep):
    if not ep.x64_off:
        return []
    out, seen = [], set()
    for aval, where in all_avals(ep.jaxpr):
        dt = getattr(aval, "dtype", None)
        if _wide(dt) and where not in seen:
            seen.add(where)
            out.append(_f(
                ep.name, where,
                f"{dt} aval on the x64-off pairs path — run_events_pairs "
                f"must never touch a 64-bit value",
                "pin the dtype at the producing op (jnp.int32/float32) or "
                "route the quantity through i32pair"))
    return out


# ---------------------------------------------------------------------------
# retrace-hazards


@rule("R001", family="retrace-hazards",
      summary="weak_type leaking into traced entrypoint operands")
def _weak_operands(ep):
    out = []
    jaxpr = getattr(ep.jaxpr, "jaxpr", ep.jaxpr)
    consts = getattr(ep.jaxpr, "consts", [])
    for i, v in enumerate(jaxpr.invars):
        if getattr(v.aval, "weak_type", False):
            out.append(_f(
                ep.name, f"operand {i}",
                f"traced operand {i} has a weak_type aval "
                f"({v.aval.dtype}) — python scalars fed straight into the "
                f"trace retrace on every dtype-context change",
                "jnp.asarray(..., dtype) the operand before the jit "
                "boundary"))
    for i, c in enumerate(consts):
        aval = getattr(c, "aval", None)
        if getattr(aval, "weak_type", False):
            out.append(_f(
                ep.name, f"const {i}",
                f"captured constant {i} has a weak_type aval — pin its "
                f"dtype", ""))
    return out


def bucket_signature(operands) -> tuple:
    """The abstract signature of one lowered replica: (field, shape,
    dtype) triples — what the jit cache sees after the static shape key.
    Two replicas in one sweep bucket with different signatures force a
    recompile."""
    return tuple((f, tuple(np.shape(a)), str(np.asarray(a).dtype))
                 for f, a in zip(type(operands)._fields, operands))


def check_bucket_signatures(n_events: int = 2048,
                            scenarios: Iterable[str] | None = None,
                            lowered_by_bucket=None) -> list[Finding]:
    """R003 core: one-compile-per-bucket, checked by signature hashing —
    **no execution, no tracing**. Mirrors ``batch.sweep``'s bucketing
    (shape key + pad_phases to the bucket max) for every registered
    simulator scenario and asserts each bucket collapses to exactly one
    abstract signature. ``lowered_by_bucket`` injects a pre-bucketed
    ``{bucket_name: [WorkloadOperands]}`` mapping instead (the fixture
    corpus uses this)."""
    findings = []
    if lowered_by_bucket is None:
        from repro.experiments import scenario_names, scenario_workloads
        from repro.workloads import lower, pad_phases
        names = list(scenarios) if scenarios is not None \
            else scenario_names()
        lowered_by_bucket = {}
        for scen in names:
            wls = scenario_workloads(scen)
            if not wls:
                continue
            per_key: dict = {}
            for w in wls:
                lw = lower(w, n_events)
                per_key.setdefault(lw.shape_key, []).append(lw.operands)
            for key, ops in per_key.items():
                pmax = max(o.n_phases for o in ops)
                lowered_by_bucket[f"{scen}:{key}"] = [
                    pad_phases(o, pmax) for o in ops]
    for bucket, ops in lowered_by_bucket.items():
        sigs = {bucket_signature(o) for o in ops}
        if len(sigs) > 1:
            findings.append(_f(
                "<global>", bucket,
                f"sweep bucket holds {len(sigs)} distinct abstract "
                f"signatures across {len(ops)} replicas — each extra "
                f"signature is one silent recompile per sweep",
                "pad_phases/dtype-pin the lowered operands so every "
                "replica of a shape bucket shares one signature"))
    return findings


@rule("R003", family="retrace-hazards", scope="global",
      summary="one compile per sweep bucket (abstract-signature hash)")
def _bucket_sigs(_eps):
    return check_bucket_signatures()


# ---------------------------------------------------------------------------
# vmem-consistency


def check_vmem_consistency(ep, table_fn=None) -> list[Finding]:
    """V001 core: the pure-python VMEM byte table (``vmem.buffer_table``)
    must mirror the buffers the traced ``pallas_call`` actually binds —
    name for name, shape for shape, itemsize for itemsize, in order
    (inputs, outputs, scratch). Drift means the planner budgets a kernel
    that no longer exists. ``table_fn`` injects an alternative table (the
    fixture corpus passes a corrupted one)."""
    from repro.kernels.event_loop import vmem
    if table_fn is None:
        table_fn = vmem.buffer_table
    plan = ep.meta.get("plan")
    if plan is None:
        return []
    calls = [s for s in walk_jaxpr(ep.jaxpr)
             if s.eqn.primitive.name == "pallas_call" and not s.in_kernel]
    if not calls:
        return []
    findings = []
    dims = ep.meta["dims"]            # {T, N, K, P, R}
    table = table_fn(tile=plan.tile, ev_chunk=plan.ev_chunk, **dims)
    streamed = vmem.STREAMED_INPUTS + vmem.STREAMED_OUTPUTS
    expected = []
    for name, (shape, nbytes) in table.items():
        factor = vmem.PIPELINE_FACTOR if name in streamed else 1
        itemsize = nbytes // (int(np.prod(shape)) * factor)
        expected.append((name, tuple(shape), itemsize))
    for site in calls:
        kernel = site.eqn.params["jaxpr"]
        refs = [(tuple(v.aval.shape), np.dtype(v.aval.dtype).itemsize)
                for v in kernel.invars]
        if len(refs) != len(expected):
            findings.append(_f(
                ep.name, f"pallas_call @ {site.src}",
                f"planner prices {len(expected)} VMEM buffers but the "
                f"kernel binds {len(refs)} — a buffer was added/removed "
                f"without updating vmem.buffer_table",
                "keep vmem.buffer_table in lockstep with ops.run_events' "
                "in_specs/out_specs/scratch_shapes"))
            continue
        for (name, eshape, esize), (kshape, ksize) in zip(expected, refs):
            if eshape != kshape or esize != ksize:
                findings.append(_f(
                    ep.name, f"pallas_call @ {site.src}",
                    f"VMEM plan drift at `{name}`: planner says shape "
                    f"{eshape} x {esize}B/elt, kernel binds {kshape} x "
                    f"{ksize}B/elt",
                    "update vmem.buffer_table (and its docstring table) "
                    "to match the kernel"))
    return findings


@rule("V001", family="vmem-consistency",
      summary="vmem.py byte table must match the traced kernel buffers")
def _vmem_drift(ep):
    return check_vmem_consistency(ep)
