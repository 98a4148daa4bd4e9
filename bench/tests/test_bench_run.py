"""The harness off the chip, and with its timed path broken underneath.

Off a TPU it exits non-zero and prints no result. With the look for a
chip (and the check that the kernel lowered natively) skipped, a whole
run of a tiny cell drives the program on the CPU and comes out correct;
with a fault planted under ``batch.sweep`` it comes out not correct.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_testkit import ROOT, TINY, add_cell, checkout, tiny_traffic

from bench import run


def _on_cpu(monkeypatch):
    import jax
    monkeypatch.setattr(run, "chip_devices", lambda n: jax.devices()[:1] * n)
    monkeypatch.setattr(run, "require_native", lambda plans: None)


def _last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_off_tpu_the_harness_refuses(capsys):
    rc = run.main(["--workload", "fig4-budget", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    cap = capsys.readouterr()
    assert rc != 0 and cap.out == ""
    assert "no TPU" in cap.err and "'cpu'" in cap.err


def test_off_tpu_the_command_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "fig5-grid", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_a_bare_checkout_has_no_result(tmp_path):
    """Only BENCHMARK.json and bench/: no program to run."""
    root = checkout(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "fig5-grid", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def _zero_state(outs):
    """The kernel hands back the state it started from."""
    done, lat, lat_n, t_end, nreacq, npass = outs[:6]
    return (np.zeros_like(done), np.full_like(lat, -1),
            np.zeros_like(lat_n), np.zeros_like(t_end),
            np.zeros_like(nreacq), np.zeros_like(npass)) + tuple(outs[6:])


def _half_batch(outs):
    """Half of the rows run; the other half repeat them."""
    def fill(a):
        h = (a.shape[0] + 1) // 2
        return np.concatenate([a[:h], a[:a.shape[0] - h]])
    return tuple(fill(a) for a in outs)


def _no_exchange(outs):
    """Only the first of four chips' rows come back; the rest are zeros."""
    def drop(a):
        a = a.copy()
        a[-(-a.shape[0] // 4):] = 0
        return a
    return tuple(drop(a) for a in outs)


def _altered(outs):
    """One latency sample of every replica is off by a nanosecond."""
    lat = outs[1].copy()
    lat[:, 0] += 1
    return outs[:1] + (lat,) + tuple(outs[2:])


FAULTS = {"none": None, "state_unchanged": _zero_state,
          "half_batch": _half_batch, "exchange_left_out": _no_exchange,
          "answer_altered": _altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_run_is_correct_unless_the_timed_path_is_broken(
        fault, tmp_path, monkeypatch, capsys):
    from repro.core import batch
    _on_cpu(monkeypatch)
    if FAULTS[fault] is not None:
        real = batch._exec_bucket
        monkeypatch.setattr(batch, "_exec_bucket", lambda *a, **k:
                            FAULTS[fault](real(*a, **k)))
    root = checkout(tmp_path)
    add_cell(root, "tiny", TINY, tiny_traffic(events=400, seeds=4, check=4))
    rc = run.main(["--workload", "tiny", "--seed", str(2**31 + 7),
                   "--seconds", "0.3", "--trace", "0"], root=root)
    res = _last_line(capsys)
    assert rc == 0
    assert res["correct"] is (fault == "none"), res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"replica_events_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"


def test_a_failing_sweep_is_counted_and_not_correct(tmp_path, monkeypatch,
                                                    capsys):
    from repro.core import batch
    _on_cpu(monkeypatch)
    real, calls = batch.sweep, []

    def flaky(*a, **k):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("planted failure")
        return real(*a, **k)

    monkeypatch.setattr(batch, "sweep", flaky)
    root = checkout(tmp_path)
    add_cell(root, "tiny", TINY, tiny_traffic(events=300, seeds=2, check=2))
    run.main(["--workload", "tiny", "--seed", "11", "--seconds", "0.3",
              "--trace", "0"], root=root)
    res = _last_line(capsys)
    assert res["failed"] == 1 and res["correct"] is False
    assert res["compared"]["failed_sweeps"] == {"value": 1, "limit": 0}
