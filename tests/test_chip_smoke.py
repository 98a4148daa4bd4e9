"""``chip_smoke.py``'s phases on the CPU: tiny sizes, interpret mode.

The script itself refuses to run off-TPU; its phases are plain functions,
so the comparison logic is checked here on the same code path the chip
runs (``batch.sweep``), with the kernel interpreted.
"""
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

EV = 300


def test_refuses_to_run_off_tpu(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert "'cpu'" in out.err
    assert '"ok"' not in out.out


@pytest.mark.parametrize("bucket", ["storm", "open-loop"])
def test_bitwise_phase_agrees_with_xla(bucket):
    ws = {"storm": cs.storm_bucket, "open-loop": cs.open_loop_bucket}[bucket]()
    out = cs.bitwise_phase(bucket, ws[:2], 2, EV)
    assert out["bitwise_equal"] and out["mismatches"] == []
    assert out["replicas"] == 4 and out["oracle"] == "xla"
    # interpreted here, so the native-run check must refuse the plans
    assert out["vmem_plans"]
    with pytest.raises(RuntimeError, match="natively"):
        cs.require_native(out["vmem_plans"])


def test_sharded_phase_on_one_device():
    ws = [w for w in cs.fig5_grid() if w.alg == "mcs"][:2]
    out = cs.sharded_phase(ws, 2, EV, jax.devices()[:1], chunk=1)
    assert out["bitwise_equal"], out["mismatches"]
    assert out["oracle"] == "pallas-unsharded"


def test_mismatches_names_the_differing_replica():
    from repro.core.batch import sweep
    res = sweep(cs.fig5_grid()[:1], 2, EV, backend="xla")
    bad = res[0]._replace(ops=res[0].ops + np.array([0, 1]))
    assert cs.mismatches(res, res) == []
    assert cs.mismatches([bad], res) == ["workload 0 seed 1: ops differs"]


def test_require_native_accepts_a_chip_plan():
    chip = {"representation": "i32pair", "budget": 1 << 25}
    cs.require_native([chip, chip])
    interpreted = {"representation": "i32pair", "budget": None}
    for plans in ([], [chip, interpreted]):
        with pytest.raises(RuntimeError, match="natively"):
            cs.require_native(plans)
