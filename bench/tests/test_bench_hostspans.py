"""The readers of the program's host spans, on a hand-made trace whose
answers are known, on a trace without the spans, and on a real trace of
a small sweep on the CPU."""
import json
import tempfile

import pytest

from bench_testkit import checkout

from bench import cells, devtrace, hostspans, run

NEW = ("host_lower_ns_per_replica_event",
       "host_assemble_ns_per_replica_event",
       "host_dispatch_ns_per_replica_event",
       "fetch_idle_ns_per_replica_event",
       "host_aggregate_ns_per_replica_event",
       "idle_unattributed_share")
#: the layer each reader's ``per_layer`` entry names
LAYERS = ("host lowering", "operand assembly", "dispatch",
          "device-to-host fetch", "host aggregation", "device")


def host(name, start, end):
    return ["python", name, start, end - start]


# window [0, 1000). Device 0 runs a kernel over [100, 400) and an op over
# [600, 700): 400 busy, 600 idle. Two buckets of one call:
#   lower [-10, 50) (cut by the window's start), assemble [50, 80),
#   dispatch [80, 100), fetch [300, 500): half inside the kernel, half
#   idle, aggregate [500, 540); assemble [540, 560), dispatch [560, 600),
#   fetch [650, 760): 50 inside the op, 60 idle, aggregate [760, 800).
# The leaves cover [0, 100), [300, 600) and [650, 800); the idle gap
# [800, 1000) lies under ``batch.sweep`` alone, and no leaf covers it.
HAND = {
    "window": [0, 1000],
    "devices": {"0": [["KERNEL_A", 100, 300], ["copy.1", 600, 100]],
                "1": [["KERNEL_A", 0, 1000]]},
    "host": [host(devtrace.WINDOW, 0, 1000),
             host(devtrace.SWEEP, -20, 1000),
             host(hostspans.SWEEP, -15, 990),
             host(hostspans.LOWER, -10, 50),
             host(hostspans.ASSEMBLE, 50, 80),
             host(hostspans.DISPATCH, 80, 100),
             host(hostspans.FETCH, 300, 500),
             host("np.asarray(jax.Array)", 310, 490),
             host(hostspans.AGGREGATE, 500, 540),
             host(hostspans.ASSEMBLE, 540, 560),
             host(hostspans.DISPATCH, 560, 600),
             host(hostspans.FETCH, 650, 760),
             host(hostspans.AGGREGATE, 760, 800)],
}


def reading(summary, device_ids=("0",), replica_events=10):
    return run.Reading(cells.load_cell("fig5-grid"), summary,
                       {"event_loop": {"pattern": "^KERNEL_A$"}}, {},
                       list(device_ids), replica_events, 0)


def read_all(ctx):
    return {m: run.load_reader(m)(ctx) for m in NEW}


def test_interval_helpers():
    assert hostspans.intervals(HAND, hostspans.LEAVES) == [
        (0, 100), (300, 600), (650, 800)]
    assert hostspans.intervals(HAND, (hostspans.FETCH,)) == [
        (300, 500), (650, 760)]
    assert hostspans.span_ns(HAND, hostspans.LOWER) == 50
    assert hostspans.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert hostspans.overlap_ns([(0, 10)], []) == 0
    fetch = hostspans.intervals(HAND, (hostspans.FETCH,))
    assert hostspans.idle_ns(HAND, fetch, "0") == 100 + 60
    assert hostspans.idle_ns(HAND, fetch, "1") == 0


def test_readers_on_the_hand_made_trace():
    val = read_all(reading(HAND))
    assert val["host_lower_ns_per_replica_event"] == 50 / 10
    assert val["host_assemble_ns_per_replica_event"] == (30 + 20) / 10
    assert val["host_dispatch_ns_per_replica_event"] == (20 + 40) / 10
    assert val["host_aggregate_ns_per_replica_event"] == (40 + 40) / 10
    # only the idle halves of the fetches: [400, 500) and [700, 760)
    assert val["fetch_idle_ns_per_replica_event"] == (100 + 60) / 10
    # idle [0, 100) + [400, 600) + [700, 1000) = 600; the leaves cover
    # 100 + 200 + 100 of it, and [800, 1000) is left
    assert val["idle_unattributed_share"] == pytest.approx(100 * 200 / 600)
    # device 1 is never idle: it adds no fetch idle time and a share of 0
    two = read_all(reading(HAND, ("0", "1")))
    assert two["fetch_idle_ns_per_replica_event"] == (160 + 0) / 2 / 10
    assert two["idle_unattributed_share"] == pytest.approx(
        (100 * 200 / 600 + 0) / 2)
    assert two["host_lower_ns_per_replica_event"] == 50 / 10


def test_a_trace_without_the_spans_has_no_reading():
    bare = dict(HAND, host=[h for h in HAND["host"]
                            if not h[1].startswith("batch.")])
    assert read_all(reading(bare)) == dict.fromkeys(NEW)
    # the device metrics still read the same trace
    assert run.load_reader("device_idle_share")(reading(bare)) == 60.0


def test_the_metrics_are_entries_of_the_benchmark(tmp_path):
    """Each reader, entered in a copy of the benchmark as the change that
    adds the entries would, is reported by both grid cells and loads from
    that copy."""
    root = checkout(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["per_layer"] = [m for m in b["per_layer"] if m["name"] not in NEW] + [
        {"name": name, "unit": "%" if name.endswith("_share") else "ns",
         "better": "lower", "source": "program_span", "layer": layer,
         "moves": "replica_events_per_s",
         "workloads": ["fig5-grid", "fig4-budget"]}
        for name, layer in zip(NEW, LAYERS)]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    bench = cells.load_benchmark(root)
    for w in bench["workloads"]:
        _, layer = run.cell_metrics(bench, w["name"])
        assert [m["name"] for m in layer][-len(NEW):] == list(NEW)
    for name in NEW:
        assert (root / "bench" / "metrics" / f"{name}.py").is_file()
        assert run.load_reader(name, root)(reading(HAND)) is not None


def test_the_span_names_are_the_programs():
    from repro.core import batch
    assert (hostspans.SWEEP,) + hostspans.LEAVES == (
        batch.SPAN_SWEEP, batch.SPAN_LOWER, batch.SPAN_ASSEMBLE,
        batch.SPAN_DISPATCH, batch.SPAN_FETCH, batch.SPAN_AGGREGATE)


def test_a_real_trace_of_a_cpu_sweep():
    """A traced sweep on the CPU, read through ``devtrace.summarize``: the
    host readers find every stage, and the stages fit inside the call.
    (The CPU has no TPU plane, so the device readers find nothing.)"""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core import batch
    from repro.workloads import Workload
    ws = [Workload("alock", 2, 2, 4, locality=0.9, seed=3),
          Workload("mcs", 2, 2, 4, locality=0.9, seed=9)]
    batch.sweep(ws, 2, 300, backend="xla")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with TraceAnnotation(devtrace.WINDOW):
                with TraceAnnotation(devtrace.SWEEP):
                    batch.sweep(ws, 2, 300, backend="xla")
        finally:
            jax.profiler.stop_trace()
        summary = devtrace.summarize(d)
    val = read_all(reading(summary, ("0",), replica_events=2 * 2 * 300))
    stages = [v for m, v in val.items() if m.startswith("host_")]
    assert all(v is not None and v > 0 for v in stages), val
    total = hostspans.span_ns(summary, hostspans.SWEEP) / (2 * 2 * 300)
    assert sum(stages) <= total
    assert val["fetch_idle_ns_per_replica_event"] is None
    assert val["idle_unattributed_share"] is None
