"""The YCSB-A lock table (``ycsb-a-n10-k100k``), at a size a test run
holds: the reader-writer ALock at read 0.5 on 4,000 locks, past the
kernel's lanes (T = 12 threads, S = 128 slots), so the Pallas engine runs
its active-lock table; both engines equal the plain reference and the
reference in bfloat16 does not."""
import json

import pytest

from bench_testkit import ROOT, checkout

from bench import cells, compare, reference

NAME = "ycsb-a-small"
SEEDS = 2
EVENTS = 2_000


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    """The deployment's configuration with its cluster cut to 4 nodes x 3
    threads and 4,000 locks (Zipf 0.99, read 0.5 kept), added by files
    and an entry to a copy of the benchmark."""
    root = checkout(tmp_path_factory.mktemp("ycsb-a"))
    cfg = json.loads(
        (ROOT / "bench/configs/ycsb-a-n10-k100k.json").read_text())
    cfg.update(name=NAME, n_nodes=4, threads_per_node=3, n_locks=4_000)
    (root / f"bench/configs/{NAME}.json").write_text(json.dumps(cfg))
    traffic = json.loads(
        (ROOT / "bench/traffic/ycsb-a-k100k.json").read_text())
    traffic.update(seeds_per_point=SEEDS, events_per_replica=EVENTS)
    traffic["grid"][0]["locality"] = [0.9]
    (root / f"bench/traffic/{NAME}.json").write_text(json.dumps(traffic))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": NAME, "source": "a test cluster",
                         "file": f"bench/configs/{NAME}.json",
                         "reduced": [], "why": "a test"})
    b["workloads"].append({"name": NAME, "config": NAME, "traffic": NAME,
                           "chips": 1, "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return cells.load_cell(NAME, root)


def _replicas(cell, precision="float32"):
    """Every replica of sweep 0 by the reference: (point, seed) -> fields."""
    specs = cells.workload_specs(cell, 7, 0)
    return {(c, s): reference.simulate(p, w.seed + s, cell.n_events,
                                       cell.config["cost_model"],
                                       cell.config["lat_samples"],
                                       precision=precision)
            for c, (p, w) in enumerate(zip(cell.points, specs))
            for s in range(cell.n_seeds)}


@pytest.fixture(scope="module")
def want(cell):
    return _replicas(cell)


def test_the_small_cell_is_the_deployment(cell):
    from repro.kernels.event_loop import vmem
    (p,) = cell.points
    assert (p["alg"], p["zipf_s"], p["read_frac"]) == ("alock-rw", 0.99,
                                                       0.5)
    assert (p["n_nodes"], p["threads_per_node"], p["n_locks"]) == (4, 3,
                                                                   4_000)
    assert vmem.lock_layout(4_000, 12) == ("table", 128)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_both_engines_equal_the_reference(cell, want, backend):
    from repro.core import batch
    batch.reset_exec_stats()
    res = batch.sweep(cells.workload_specs(cell, 7, 0), cell.n_seeds,
                      cell.n_events, backend=backend)
    for (c, s), fields in want.items():
        got = compare.replica_fields(res[c], s)
        assert compare.mismatches(got, fields) == [], (backend, c, s)
        assert int(got["ops"]) > 0
    if backend == "pallas":
        (plan,) = batch.exec_stats()["vmem_plans"]
        assert plan["lock_layout"] == "table"


def test_the_bfloat16_reference_does_not_equal_it(cell, want):
    low = _replicas(cell, "bfloat16")
    bad = [m for k in want for m in compare.mismatches(low[k], want[k])]
    assert bad
