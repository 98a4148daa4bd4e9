"""The control, at a size a test run holds: the reference computed in
bfloat16 in the program's place comes out not correct on every seed; the
reference in the configuration's float32 comes out correct."""
import pytest

from bench_testkit import add_cell, checkout, tiny_traffic

from bench import control, run

SEEDS = (5, 2**31 + 77, 4_000_000_123)


@pytest.fixture()
def root(tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(run, "chip_devices", lambda n: jax.devices()[:1] * n)
    root = checkout(tmp_path)
    # ten locks a node: CDF steps that bfloat16 cannot hold exactly
    add_cell(root, "ctl", {"n_nodes": 2, "threads_per_node": 3,
                           "n_locks": 20},
             tiny_traffic(events=3000, seeds=2, check=4,
                          algs=("alock", "spinlock")))
    return root


@pytest.mark.parametrize("seed", SEEDS)
def test_the_bfloat16_control_is_not_correct(root, seed):
    res = control.control_run("ctl", seed, "bfloat16", root)
    assert res["correct"] is False
    assert res["compared"]["mismatched_fields"]["value"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_float32_reference_in_the_program_place_is_correct(root, seed):
    res = control.control_run("ctl", seed, "float32", root)
    assert res["correct"] is True
    assert res["compared"]["mismatched_fields"]["value"] == 0
