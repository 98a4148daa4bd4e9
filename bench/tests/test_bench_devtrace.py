"""The reduction from a device trace to times, on a hand-made trace whose
answers are known."""
import json
from pathlib import Path

import pytest

from bench_testkit import ROOT  # noqa: F401  (puts the checkout on the path)

from bench import cells, devtrace, run

KERNEL = json.loads(
    (Path(__file__).resolve().parents[1] / "kernels.json").read_text())[
        "event_loop"]["pattern"]

# window [100, 1100); device 0 runs two kernels and three other ops, two
# of them overlapping a kernel, one cut by the window's start; device 1
# runs one kernel cut by the window's end
HAND = {
    "window": [100, 1100],
    "devices": {
        "0": [["prep.1", 50, 100],          # [100, 150) inside
              ["KERNEL_A", 200, 300],       # [200, 500)
              ["fusion.2", 450, 100],       # [450, 550): 50 past the kernel
              ["KERNEL_A", 700, 100],       # [700, 800)
              ["copy.3", 900, 50]],         # [900, 950)
        "1": [["KERNEL_A", 1000, 500]],     # [1000, 1100) inside
    },
    "host": [["main", devtrace.WINDOW, 100, 1000],
             ["main", devtrace.SWEEP, 110, 480],
             ["main", devtrace.SWEEP, 600, 480],
             ["main", "Transfer", 560, 130],
             ["main", "Aggregate", 810, 80]],
}


def test_hand_made_trace():
    k = "^KERNEL_A$"
    assert devtrace.window_ns(HAND) == 1000
    assert devtrace.op_ns(HAND, k) == 300 + 100 + 100
    assert devtrace.op_ns(HAND, k, match=False) == 50 + 100 + 50
    assert devtrace.op_count(HAND, k) == 3
    assert devtrace.busy_intervals(HAND, "0") == [
        (100, 150), (200, 550), (700, 800), (900, 950)]
    assert devtrace.busy_ns(HAND, "0") == 50 + 350 + 100 + 50
    assert devtrace.busy_ns(HAND, "1") == 100
    gaps = devtrace.idle_gaps(HAND, "0")
    assert gaps[0] == ["Transfer", 150e-9]          # [550, 700)
    assert sorted(g[1] for g in gaps) == [50e-9, 100e-9, 150e-9, 150e-9]
    assert [g for g in gaps if g[1] == 100e-9] == [["Aggregate", 100e-9]]
    assert devtrace.top_ops(HAND)[0] == ["KERNEL_A", 500e-9]


def test_layer_readers_on_the_hand_made_trace():
    cell = cells.load_cell("fig5-grid")
    ctx = run.Reading(cell, HAND, {"event_loop": {"pattern": "^KERNEL_A$"}},
                      {"hbm_bytes_per_s": 1e9}, ["0", "1"],
                      replica_events=50, kernel_bytes=250)
    val = {m: run.load_reader(m)(ctx) for m in (
        "kernel_ns_per_replica_event", "xla_ops_ns_per_replica_event",
        "event_loop_roofline", "device_idle_share")}
    assert val["kernel_ns_per_replica_event"] == 500 / 50
    assert val["xla_ops_ns_per_replica_event"] == 200 / 50
    assert val["event_loop_roofline"] == pytest.approx(100 * 250e-9 / 500e-9)
    assert val["device_idle_share"] == pytest.approx(
        100 * ((1 - 550 / 1000) + (1 - 100 / 1000)) / 2)
    nothing = dict(HAND, devices={"0": [["prep.1", 200, 10]]})
    ctx.summary = nothing
    assert run.load_reader("kernel_ns_per_replica_event")(ctx) is None
    assert run.load_reader("event_loop_roofline")(ctx) is None
