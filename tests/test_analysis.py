"""Acceptance tests for the static jaxpr lint (``repro.analysis``).

Four gates, mirroring the CI lint leg:

  1. the shipped engine is lint-clean — every rule over a real traced
     entrypoint catalog yields zero findings;
  2. the known-bad corpus keeps every rule family alive (>= 4 distinct
     rule ids across all 4 families);
  3. the CLI contract holds in a real subprocess (``--strict`` exit 0 on
     this repo, ``--selftest`` exit 0, ``--imports`` names dead weight,
     unknown ``--rules`` exit 2);
  4. the pairs-path jaxpr matches its golden primitive-set snapshot
     (regenerate with ``REPRO_UPDATE_GOLDENS=1``).

Tracing is scoped to three scenarios — node-churn (the classic closed
loop) plus read-heavy and rack-locality (the alock-rw / hlock buckets
with their gated read-probability, coin-stream and rack operands) — to
keep runtime modest; the full catalog runs in CI's lint leg via
``--strict``.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import (
    RULES,
    bucket_signature,
    check_bucket_signatures,
    run_rules,
    trace_entrypoints,
    walk_jaxpr,
)

ROOT = Path(__file__).resolve().parent.parent
GOLDEN = ROOT / "tests" / "golden" / "run_events_pairs_primitives.txt"
ENV = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")

# one scenario's worth of traced entrypoints, shared across tests
_EPS = None


def _eps():
    global _EPS
    if _EPS is None:
        _EPS = trace_entrypoints(
            scenarios=["node-churn", "read-heavy", "rack-locality"],
            n_events=512)
    return _EPS


# ---------------------------------------------------------------- gate 1

def test_clean_entrypoints_zero_findings():
    """The shipped engine must be lint-clean: all 7 rules, all 3 kinds
    (xla-batch, pallas-native, pallas-pairs), 0 findings."""
    eps = _eps()
    kinds = {e.kind for e in eps}
    assert kinds == {"xla-batch", "pallas-native", "pallas-pairs"}, kinds
    findings = run_rules(eps)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_pairs_trace_has_no_wide_avals():
    """Belt-and-braces on X001's premise: the x64-off pairs traces really
    contain zero 64-bit avals — across every bucket, so the hlock rack
    operand and the alock-rw read-coin stream are covered too."""
    from repro.analysis import all_avals
    from repro.analysis.rules import _wide
    eps = [e for e in _eps() if e.kind == "pallas-pairs"]
    assert eps
    for ep in eps:
        wide = [(str(a), w) for a, w in all_avals(ep.jaxpr)
                if _wide(getattr(a, "dtype", None))]
        assert wide == [], (ep.name, wide[:10])


# ---------------------------------------------------------------- gate 2

def test_corpus_fires_all_families():
    from repro.analysis.fixtures import run_corpus
    per_family = run_corpus()
    assert sorted(per_family) == ["mosaic-lowerability", "retrace-hazards",
                                  "vmem-consistency", "x64-cleanliness"]
    blind = [fam for fam, fs in per_family.items() if not fs]
    assert not blind, f"rule families gone blind: {blind}"
    fired = {f.rule for fs in per_family.values() for f in fs}
    assert len(fired) >= 4, fired
    assert {RULES[r].family for r in fired} == set(per_family), fired


def test_rack_offender_fires_m001():
    """The topology counterfactual: an int64 rack index inside the tier
    compare must trip the Mosaic-lowerability family (a widened rack
    operand can never reach the shipped kernel unnoticed)."""
    from repro.analysis.fixtures import rack_offender
    fs = run_rules([rack_offender()], rules=["M001"])
    assert fs, "M001 went blind on the 64-bit rack-index fixture"
    assert all(f.rule == "M001" for f in fs), fs


def test_every_finding_is_stamped():
    """Corpus findings carry their rule id, family, severity, entrypoint
    and a non-empty message — the structured contract ``--json`` relies
    on."""
    from repro.analysis.fixtures import run_corpus
    for fs in run_corpus().values():
        for f in fs:
            assert f.rule in RULES, f
            assert f.family == RULES[f.rule].family, f
            assert f.severity in ("error", "warning"), f
            assert f.entrypoint and f.message, f


def test_bucket_signature_drift_is_caught():
    """R003 positive/negative: a dtype-drifted replica in a bucket fires;
    the real sweep buckets stay one-signature-per-bucket."""
    from repro.analysis.fixtures import bucket_offender
    assert check_bucket_signatures(lowered_by_bucket=bucket_offender())
    assert check_bucket_signatures(
        n_events=512, scenarios=["node-churn", "hot-key-storm"]) == []


def test_bucket_signature_is_shape_and_dtype():
    from repro.workloads import Workload, lower
    ops = lower(Workload("alock", 2, 2, 8, locality=0.9), 256).operands
    sig = bucket_signature(ops)
    assert sig and all(len(t) == 3 for t in sig), sig
    import numpy as np
    drifted = ops._replace(locality=np.asarray(ops.locality, np.float64))
    assert bucket_signature(drifted) != sig


# ---------------------------------------------------------------- gate 3

def _cli(*args, timeout=560):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, env=ENV, timeout=timeout)


def test_cli_strict_is_clean_on_this_repo():
    # read-heavy / rack-locality put the alock-rw and hlock buckets (rack
    # operand, read-coin stream) under the same strict gate
    r = _cli("--strict", "--scenarios",
             "node-churn,read-heavy,rack-locality", "--events", "512")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "lint-clean." in r.stdout, r.stdout


def test_cli_selftest_passes():
    r = _cli("--selftest")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "selftest passed." in r.stdout, r.stdout
    assert "BLIND" not in r.stdout, r.stdout


def test_cli_imports_gate_clean_with_quarantine():
    r = _cli("--imports")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "unreachable" in r.stdout
    # the superseded tick kernel is real dead weight from the simulator's
    # roots, parked under an explicit quarantine entry rather than deleted
    assert "repro.kernels.alock_tick.kernel" in r.stdout, r.stdout
    assert "quarantined" in r.stdout, r.stdout
    assert "0 unexpected" in r.stdout, r.stdout
    assert "imports gate: clean." in r.stdout, r.stdout


def test_imports_gate_flags_unexpected_and_stale():
    """The gate is actionable both ways: an unreachable module without a
    quarantine entry fails, and a quarantine entry whose tree became
    reachable (or vanished) fails too."""
    from repro.analysis import imports as imp
    quarantined, unexpected, stale = imp.classify()
    assert quarantined and not unexpected and not stale
    # drop one entry -> its modules become unexpected
    trimmed = {k: v for k, v in imp.QUARANTINED.items()
               if k != "repro.kernels.alock_tick"}
    orig = imp.QUARANTINED
    try:
        imp.QUARANTINED = trimmed
        _, unexpected, _ = imp.classify()
        assert "repro.kernels.alock_tick.kernel" in unexpected
        text, rc = imp.report()
        assert rc == 1 and "UNEXPECTED" in text
        # add a prefix covering nothing -> stale
        imp.QUARANTINED = {**orig, "repro.no_such_pkg": "ghost"}
        _, _, stale = imp.classify()
        assert stale == ["repro.no_such_pkg"]
        text, rc = imp.report()
        assert rc == 1 and "STALE" in text
    finally:
        imp.QUARANTINED = orig


def test_cli_unknown_rule_id_exits_2():
    r = _cli("--rules", "M999")
    assert r.returncode == 2, r.stdout + r.stderr
    assert "unknown rule ids" in r.stderr, r.stderr


# ---------------------------------------------------------------- gate 4

def _pairs_primitives():
    # union over every pairs bucket: alock/spinlock/mcs plus the hlock and
    # alock-rw op classes all contribute to the pinned set
    prims = set()
    for ep in _eps():
        if ep.kind == "pallas-pairs":
            prims |= {s.eqn.primitive.name for s in walk_jaxpr(ep.jaxpr)}
    return sorted(prims)


def test_pairs_golden_primitive_set():
    """The run_events_pairs trace's primitive set is pinned: a *new*
    primitive appearing on the hot path (e.g. ``scan`` returning after
    the i32-counter while_loop fix, or a stray ``convert_element_type``
    widening) fails; primitives a newer jax version stops emitting are
    tolerated (the golden is a ceiling, not an exact pin, so CI's
    latest-jax leg stays green on lowering simplifications).

    Regenerate after an intentional kernel change with
    ``REPRO_UPDATE_GOLDENS=1 PYTHONPATH=src python -m pytest
    tests/test_analysis.py -k golden``.
    """
    got = _pairs_primitives()
    if os.environ.get("REPRO_UPDATE_GOLDENS"):
        GOLDEN.write_text("\n".join(got) + "\n")
        pytest.skip(f"golden regenerated: {GOLDEN}")
    assert GOLDEN.exists(), f"missing golden {GOLDEN}"
    want = GOLDEN.read_text().split()
    added = sorted(set(got) - set(want))
    assert not added, (
        f"new primitives entered the run_events_pairs trace: {added} — "
        f"intentional? regenerate with REPRO_UPDATE_GOLDENS=1")
    # the loop fix is load-bearing: scan must never return to this path
    assert "scan" not in got and "while" in got, got


def test_golden_file_is_sorted_unique():
    names = GOLDEN.read_text().split()
    assert names == sorted(set(names)), "golden file must be sorted/unique"
