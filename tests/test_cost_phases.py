"""Phase-dependent cost model + ALock budget programs (PR 4 tentpole).

Contracts under test:
  1. default-profile bitwise freeze: COST_PROFILES["default"] lowers to
     exactly the rows the pre-profile ``topology()`` computed, and a
     default-cost Workload's operands carry them verbatim;
  2. phase-boundary budget handoff: acquisitions arm with the ``b_init``
     of the phase active at the arming event; budgets granted before a
     boundary keep draining across it (xla + pallas bitwise);
  3. per-phase cost rows change the dynamics (congested burst slows the
     loopback algs) while staying bitwise-equal across backends and
     bucket-mixable without extra compiles;
  4. ``pad_phases`` stays inert now that cost/budget rows are per-phase;
  5. spec validation of the new ``cost`` / ``b_init`` fields;
  6. the ``--check-slo`` exit-code gate (subprocess, smoke events).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import batch
from repro.core.cost_model import (COST_PROFILES, CostModel, CostProfile,
                                   resolve_cost)
from repro.core.sim import simulate, topology
from repro.workloads import Phase, Workload, lower, pad_phases

EV = 1200


def _assert_same(rx, rp):
    assert rx.ops == rp.ops
    assert rx.sim_ns == rp.sim_ns
    assert rx.reacquires == rp.reacquires
    assert rx.passes == rp.passes
    np.testing.assert_array_equal(np.asarray(rx.lat_ns),
                                  np.asarray(rp.lat_ns))
    np.testing.assert_array_equal(np.asarray(rx.per_thread_ops),
                                  np.asarray(rp.per_thread_ops))


# -- 1. default-profile provenance / bitwise freeze -------------------------


@pytest.mark.parametrize("alg", ["alock", "spinlock", "mcs"])
@pytest.mark.parametrize("n,tpn", [(2, 2), (20, 12)])
def test_default_profile_matches_pre_change_cost_rows(alg, n, tpn):
    """COST_PROFILES['default'] must reproduce the pre-profile cost_rows
    bit for bit — the exact int(round(...)) arithmetic topology() used
    before CostProfile existed."""
    cm = CostModel()
    uses_loopback = alg != "alock"
    legacy = tuple(int(round(v)) for v in (
        cm.local_ns, cm.spin_poll_ns, cm.cs_ns, cm.think_ns,
        cm.svc_ns(n, tpn, uses_loopback, False),
        cm.svc_ns(n, tpn, uses_loopback, True),
        cm.remote_wire_ns, cm.loopback_wire_ns))
    assert COST_PROFILES["default"].cost_rows(alg, n, tpn) == legacy
    assert cm.cost_rows(alg, n, tpn) == legacy
    _, _, topo_costs = topology(alg, n, tpn, n * 2)
    assert tuple(topo_costs) == legacy
    # and a default-cost workload lowers to exactly these rows
    o = lower(Workload(alg, n, tpn, n * 2), n_events=100).operands
    np.testing.assert_array_equal(o.cost_rows,
                                  np.int32(legacy)[None, :])


def test_default_profile_is_field_identical_to_costmodel():
    cm, prof = CostModel(), COST_PROFILES["default"]
    import dataclasses
    for f in dataclasses.fields(CostModel):
        assert getattr(prof, f.name) == getattr(cm, f.name), f.name


def test_resolve_cost_forms():
    base = CostModel()
    assert resolve_cost(None, base) is base
    assert resolve_cost("congested-nic", base) \
        is COST_PROFILES["congested-nic"]
    over = resolve_cost((("rnic_svc_ns", 999.0),), base)
    assert over.rnic_svc_ns == 999.0 and over.local_ns == base.local_ns
    with pytest.raises(ValueError, match="unknown cost profile"):
        resolve_cost("warp-drive", base)


# -- 2. phase-boundary budget handoff ---------------------------------------


def test_budget_program_rearms_at_phase_b_init():
    """Tight budgets in phase 0 force reacquire churn; a generous phase 1
    must stop it. The split run's counters sit strictly between the
    constant-tight and constant-generous controls."""
    base = Workload("alock", 2, 4, 8, locality=0.5, seed=3)
    tight = base.replace(b_init=(1, 1))
    loose = base.replace(b_init=(50, 50))
    split = base.replace(b_init=(1, 1), phases=(
        Phase(frac=0.5), Phase(frac=0.5, b_init=(50, 50))))
    ev = 4_000
    r_t = simulate(tight, n_events=ev)
    r_l = simulate(loose, n_events=ev)
    r_s = simulate(split, n_events=ev)
    assert r_t.reacquires > 10                 # the mechanism fires at all
    assert r_l.reacquires < r_t.reacquires // 4
    assert r_l.reacquires <= r_s.reacquires <= r_t.reacquires
    # the generous half really suppressed churn: the split run does far
    # fewer reacquires than a full-length tight run
    assert r_s.reacquires < 0.8 * r_t.reacquires


def test_budget_handoff_bitwise_xla_pallas():
    """The budget program through both engines, including a boundary that
    lands mid event-chunk, is bitwise identical."""
    w = Workload("alock", 2, 4, 8, locality=0.5, seed=7, b_init=(1, 2),
                 phases=(Phase(frac=0.37), Phase(frac=0.33, b_init=(9, 40)),
                         Phase(frac=0.30, b_init=(2, 2))))
    _assert_same(simulate(w, n_events=EV, backend="xla"),
                 simulate(w, n_events=EV, backend="pallas"))


def test_phase_b_init_none_inherits_workload():
    w = Workload("alock", 2, 2, 8, b_init=(3, 7),
                 phases=(Phase(frac=0.5), Phase(frac=0.5, b_init=(8, 9))))
    o = lower(w, n_events=100).operands
    np.testing.assert_array_equal(o.b_init, [[3, 7], [8, 9]])


# -- 3. per-phase cost rows --------------------------------------------------


def test_congested_phase_slows_loopback_alg_and_is_bitwise():
    base = Workload("mcs", 2, 4, 8, locality=1.0, seed=1)
    burst = base.replace(phases=(Phase(frac=0.3),
                                 Phase(frac=0.4, cost="congested-nic"),
                                 Phase(frac=0.3)))
    ev = 4_000
    r0 = simulate(base, n_events=ev)
    r1 = simulate(burst, n_events=ev)
    assert r1.ops < r0.ops            # congestion costs completed ops
    assert r1.sim_ns > r0.sim_ns      # ... and simulated time
    _assert_same(simulate(burst, n_events=EV, backend="xla"),
                 simulate(burst, n_events=EV, backend="pallas"))


def test_workload_level_cost_applies_to_all_phases():
    w = Workload("mcs", 2, 2, 8, cost="congested-nic",
                 phases=(Phase(frac=0.5), Phase(frac=0.5, cost="default")))
    o = lower(w, n_events=100).operands
    cong = COST_PROFILES["congested-nic"].cost_rows("mcs", 2, 2)
    dflt = COST_PROFILES["default"].cost_rows("mcs", 2, 2)
    np.testing.assert_array_equal(o.cost_rows[0], np.int32(cong))
    np.testing.assert_array_equal(o.cost_rows[1], np.int32(dflt))


def test_cost_override_mapping_lowered():
    w = Workload("alock", 2, 2, 8, cost={"rnic_svc_ns": 999.0})
    o = lower(w, n_events=100).operands
    assert o.cost_rows[0, 4] == 999 and o.cost_rows[0, 0] == 100


def test_mixed_cost_profiles_share_one_compile():
    """Workloads under different cost profiles and budget programs still
    bucket into ONE executable (cost rows are traced operands)."""
    cfgs = [
        Workload("alock", 2, 2, 8, locality=0.9, seed=1),
        Workload("alock", 2, 2, 8, locality=0.9, cost="congested-nic"),
        Workload("alock", 2, 2, 8, locality=0.9, cost="idle-nic",
                 b_init=(1, 1)),
        Workload("alock", 2, 2, 8, locality=0.9,
                 phases=(Phase(frac=0.5, b_init=(1, 1)),
                         Phase(frac=0.5, cost="congested-nic"))),
    ]
    batch.reset_exec_stats()
    res = batch.sweep(cfgs, n_seeds=2, n_events=EV, backend="xla")
    st = batch.exec_stats()
    assert st["dispatches"] == 1 and st["compiles"] <= 1
    # the default-cost member is bitwise-equal to its solo run
    solo = simulate(cfgs[0], n_events=EV, backend="xla")
    assert int(res[0].ops[0]) == solo.ops
    np.testing.assert_array_equal(res[0].lat_ns[0], np.asarray(solo.lat_ns))
    # ... and the whole mixed bucket agrees across backends
    rp = batch.sweep(cfgs, n_seeds=2, n_events=EV, backend="pallas")
    for a, b in zip(res, rp):
        np.testing.assert_array_equal(a.ops, b.ops)
        np.testing.assert_array_equal(a.lat_ns, b.lat_ns)


# -- 4. pad_phases inertness over cost/budget rows ---------------------------


def test_pad_phases_inert_for_cost_and_budget_rows():
    """Engine-level inertness: padding a 2-phase cost/budget program to 5
    phases must not change a single bit of the run."""
    w = Workload("alock", 2, 2, 8, locality=0.9, seed=5, b_init=(2, 3),
                 phases=(Phase(frac=0.5, cost="idle-nic", b_init=(1, 4)),
                         Phase(frac=0.5, cost="congested-nic")))
    lw = lower(w, n_events=EV)
    padded = pad_phases(lw.operands, 5)
    assert padded.cost_rows.shape == (5, 8)
    assert padded.b_init.shape == (5, 2)
    import jax
    import jax.numpy as jnp
    from repro.kernels.event_loop.ref import run_events_ref
    from repro.workloads import WorkloadOperands
    tn, ln, _ = topology("alock", 2, 2, 8)
    with jax.enable_x64(True):
        outs = []
        for ops in (lw.operands, padded):
            wl = WorkloadOperands(*(jnp.asarray(a)[None] for a in ops))
            outs.append(run_events_ref("alock", 4, 2, 8, EV, wl, tn, ln))
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- 5. spec validation ------------------------------------------------------


def test_cost_and_b_init_spec_validation():
    with pytest.raises(ValueError, match="unknown cost profile"):
        Workload("alock", 2, 2, 8, cost="warp-drive")
    with pytest.raises(ValueError, match="unknown cost-model field"):
        Workload("alock", 2, 2, 8, cost={"wire_speed": 1.0})
    with pytest.raises(ValueError, match="b_init"):
        Phase(frac=0.5, b_init=(1, 2, 3))
    with pytest.raises(ValueError, match=">= 0"):
        Phase(frac=0.5, b_init=(-1, 2))
    with pytest.raises(ValueError, match="unknown cost profile"):
        Phase(frac=0.5, cost="nope")
    # frozen specs stay hashable with the new fields
    w1 = Workload("alock", 2, 2, 8, cost="congested-nic",
                  phases=(Phase(frac=0.5, b_init=(1, 1)),
                          Phase(frac=0.5)))
    w2 = Workload("alock", 2, 2, 8, cost="congested-nic",
                  phases=(Phase(frac=0.5, b_init=(1, 1)),
                          Phase(frac=0.5)))
    assert w1 == w2 and hash(w1) == hash(w2)
    assert {w1: 1}[w2] == 1
    # dict overrides canonicalize to a hashable sorted tuple
    w3 = Workload("alock", 2, 2, 8, cost={"rnic_svc_ns": 999.0})
    assert w3.cost == (("rnic_svc_ns", 999.0),)
    assert hash(w3) == hash(w3.replace())


def test_profile_instances_ride_specs():
    prof = CostProfile(name="custom", rnic_svc_ns=500.0)
    w = Workload("alock", 2, 2, 8, cost=prof)
    o = lower(w, n_events=50).operands
    assert o.cost_rows[0, 4] == 500


# -- 6. --check-slo exit-code gate (subprocess) ------------------------------


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(*args):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               REPRO_BENCH_EVENTS="800", JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)


def test_check_slo_pass_and_fail_exit_codes(tmp_path):
    out = tmp_path / "rows.json"
    ok = _run_bench("--scenario", "budget-ramp", "--seeds", "1",
                    "--check-slo", "--scenario-out", str(out))
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "slo budget-ramp: PASS" in ok.stdout
    rows = json.loads(out.read_text())
    assert any("p99_lat_ns" in r for r in rows)
    assert any("events_per_sec" in r for r in rows)

    bad = _run_bench("--scenario", "budget-ramp", "--seeds", "1",
                     "--check-slo", "--slo-p99-ns", "1")
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "VIOLATION" in bad.stdout


def test_check_slo_requires_scenario():
    r = _run_bench("--check-slo")
    assert r.returncode == 2          # argparse error
