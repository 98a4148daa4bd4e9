"""The benchmark's cells are data: each configuration and traffic file
builds the intended Workload specs, and a new cell, configuration or
per-layer metric needs only new files and entries."""
import json

import pytest

from bench_testkit import ROOT, TINY, add_cell, checkout, tiny_traffic

from bench import cells, run

BIG_SEED = 2**31 + 987654321

CELLS = [
    # cell, points, seeds per point, shape buckets, events
    ("fig5-grid", 9, 8, 3, 150_000),
    ("fig4-budget", 14, 4, 1, 150_000),
    ("fig5-interactive", 1, 8, 1, 20_000),
    ("fig5-grid-x4", 9, 32, 3, 150_000),
]
#: mixes whose traffic files wait for a later change to add their cells:
#: name -> (configuration, chips)
LATER = {"fig5-interactive": ("alock-fig5-n10-k100", 1),
         "fig5-grid-x4": ("alock-fig5-n10-k100", 4)}


def with_later_cells(tmp_path):
    """A copy of the benchmark with the waiting mixes added as cells, by
    entries alone, as the change that adds them would."""
    root = checkout(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    for name, (config, chips) in LATER.items():
        b["workloads"].append({"name": name, "config": config,
                               "traffic": name, "chips": chips,
                               "why": "a test"})
        for m in b["per_layer"]:
            m["workloads"].append(name)
    b["end_to_end"].insert(1, {
        "name": "sweep_p95_s", "unit": "s", "better": "lower",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["fig5-interactive"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


@pytest.mark.parametrize("name,points,seeds,buckets,events", CELLS)
def test_cell_builds_its_workloads(name, points, seeds, buckets, events,
                                   tmp_path):
    from repro.core.batch import shape_key
    cell = cells.load_cell(name, with_later_cells(tmp_path))
    specs = cells.workload_specs(cell, BIG_SEED, 1)
    assert (len(specs), cell.n_seeds, cell.n_events) == (points, seeds,
                                                         events)
    assert len({shape_key(w, cell.n_events) for w in specs}) == buckets
    cfg = cell.config
    for w in specs:
        assert (w.n_nodes, w.threads_per_node, w.n_locks) == (
            cfg["n_nodes"], cfg["threads_per_node"], cfg["n_locks"])
        assert w.alg in cfg["algorithms"] and w.arrivals is None
        assert dict(w.cost) == {k: float(v)
                                for k, v in cfg["cost_model"].items()}


def test_fig_grids_are_the_papers(tmp_path):
    later = with_later_cells(tmp_path)
    f5 = cells.load_cell("fig5-grid").points
    assert [(p["alg"], p["locality"]) for p in f5] == [
        (a, l) for a in ("alock", "spinlock", "mcs")
        for l in (0.85, 0.95, 1.0)]
    assert cells.load_cell("fig5-grid-x4", later).points == f5
    f4 = cells.load_cell("fig4-budget").points
    assert {p["alg"] for p in f4} == {"alock"}
    assert [(p["locality"], p["b_init"]) for p in f4] == (
        [(l, (5, rb)) for l in (0.95, 0.90, 0.85) for rb in (5, 10, 20)]
        + [(0.90, b) for b in ((1, 1), (2, 2), (2, 8), (2, 20), (20, 5))])
    x4 = cells.load_cell("fig5-grid-x4", later)
    assert (x4.chips, x4.devices, x4.chunk, x4.replicas) == (4, 4, 8, 288)


def test_sweeps_get_fresh_seeds_that_fit_int32():
    cell = cells.load_cell("fig5-grid")
    seen = set()
    for j in range(4):
        for w in cells.workload_specs(cell, BIG_SEED, j):
            mine = set(range(w.seed, w.seed + cell.n_seeds))
            assert not mine & seen
            seen |= mine
    assert max(seen) < 2**31
    again = [w.seed for w in cells.workload_specs(cell, BIG_SEED, 2)]
    assert again == [w.seed for w in cells.workload_specs(cell, BIG_SEED, 2)]


def test_unknown_cell_and_axis_are_refused(tmp_path):
    with pytest.raises(KeyError):
        cells.load_cell("no-such-cell")
    root = checkout(tmp_path)
    bad = tiny_traffic()
    bad["grid"][0]["phases"] = [[]]
    add_cell(root, "bad-axis", TINY, bad)
    with pytest.raises(ValueError, match="unknown grid axes"):
        cells.load_cell("bad-axis", root)


def test_a_new_cell_config_and_metric_are_files_and_entries(tmp_path):
    """A throwaway cell, configuration and per-layer metric, added to a
    copy of the benchmark by new files and entries only."""
    root = checkout(tmp_path)
    add_cell(root, "tmp-cell", {"n_nodes": 4, "threads_per_node": 3,
                                "n_locks": 8},
             tiny_traffic(events=1000, algs=("alock", "spinlock"),
                          locality=(0.5, 0.7, 0.9)))
    (root / "bench/metrics/sweeps_traced.py").write_text(
        "def read(ctx):\n    return float(ctx.replica_events)\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["per_layer"].append({
        "name": "sweeps_traced", "unit": "replica-events", "better": "higher",
        "source": "program_counter", "layer": "device",
        "moves": "replica_events_per_s", "workloads": ["tmp-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = cells.load_cell("tmp-cell", root)
    assert cell.replicas == 2 * 3 * 3
    assert {p["alg"] for p in cell.points} == {"alock", "spinlock"}
    e2e, layer = run.cell_metrics(cells.load_benchmark(root), "tmp-cell")
    assert [m["name"] for m in e2e] == ["replica_events_per_s", "setup_s"]
    assert "sweeps_traced" in [m["name"] for m in layer]
    read = run.load_reader("sweeps_traced", root)
    assert read(run.Reading(cell, {}, {}, {}, [], 42, 0)) == 42.0
    # the checked-in benchmark never sees the throwaway cell
    assert "tmp-cell" not in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["per_layer"][0]["workloads"]


def test_only_the_interactive_cell_reports_the_tail(tmp_path):
    assert [w["name"] for w in cells.load_benchmark()["workloads"]] == [
        "fig5-grid", "fig4-budget"]
    bench = cells.load_benchmark(with_later_cells(tmp_path))
    assert len(bench["workloads"]) == 4
    for w in bench["workloads"]:
        e2e, layer = run.cell_metrics(bench, w["name"])
        names = [m["name"] for m in e2e]
        assert ("sweep_p95_s" in names) == (w["name"] == "fig5-interactive")
        assert {"replica_events_per_s", "setup_s"} <= set(names)
        assert len(layer) == 4
