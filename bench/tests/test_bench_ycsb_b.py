"""The YCSB-B lock table (``ycsb-b-n10-k1000``), at a size a test run
holds: the reader-writer ALock on a 1,000-lane table with Zipf(0.99)
draws equals the plain reference on both engines, the reference in
bfloat16 does not, the draw precompute carries its named scope, and the
reader of that scope's device time finds it or reads None."""
import json

import numpy as np
import pytest

from bench_testkit import ROOT, checkout

from bench import cells, compare, reference, run

NAME = "ycsb-b-small"
SEEDS = 2
EVENTS = 2_000


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    """The deployment's configuration with its cluster cut to 4 nodes x 3
    threads (1,000 locks, Zipf 0.99, read 0.95 kept), added by files and
    an entry to a copy of the benchmark."""
    root = checkout(tmp_path_factory.mktemp("ycsb"))
    cfg = json.loads(
        (ROOT / "bench/configs/ycsb-b-n10-k1000.json").read_text())
    cfg.update(name=NAME, n_nodes=4, threads_per_node=3)
    (root / f"bench/configs/{NAME}.json").write_text(json.dumps(cfg))
    traffic = json.loads((ROOT / "bench/traffic/ycsb-b.json").read_text())
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
    (p,) = cell.points
    assert (p["alg"], p["n_locks"], p["zipf_s"], p["read_frac"]) == (
        "alock-rw", 1000, 0.99, 0.95)
    assert (p["n_nodes"], p["threads_per_node"]) == (4, 3)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_both_engines_equal_the_reference(cell, want, backend):
    from repro.core import batch
    res = batch.sweep(cells.workload_specs(cell, 7, 0), cell.n_seeds,
                      cell.n_events, backend=backend)
    for (c, s), fields in want.items():
        got = compare.replica_fields(res[c], s)
        assert compare.mismatches(got, fields) == [], (backend, c, s)
        assert int(got["ops"]) > 0


def test_the_bfloat16_reference_does_not_equal_it(cell, want):
    low = _replicas(cell, "bfloat16")
    bad = [m for k in want for m in compare.mismatches(low[k], want[k])]
    assert bad


def test_the_lowered_kernel_names_the_draw_scope(cell):
    import jax
    from repro.core.sim import topology
    from repro.kernels.event_loop import ops
    from repro.workloads import WorkloadOperands, lower
    (w,) = cells.workload_specs(cell, 7, 0)
    lw = lower(w, 600)
    alg, T, N, K, n_events, _ = lw.shape_key
    wl = WorkloadOperands(*(np.asarray(a)[None] for a in lw.operands))
    tn, ln, _ = topology(alg, N, T // N, K)
    with jax.enable_x64(True):
        text = jax.jit(lambda o, a, b: ops.run_events(
            alg, T, N, K, n_events, o, a, b, interpret=True)).lower(
                wl, tn, ln).as_text(debug_info=True)
    assert ops.DRAWS_SCOPE == "event_loop.draws"
    assert ops.DRAWS_SCOPE in text


def _reading(cell, devices):
    return run.Reading(cell, {"window": [100, 1100], "devices": devices,
                              "host": []},
                       {"event_loop": {"pattern": "^KERNEL$"}}, {}, ["0"],
                       replica_events=50, kernel_bytes=0)


def test_the_draws_reader(cell):
    read = run.load_reader("draws_ns_per_replica_event")
    scoped = {"0": [["fusion.1 jit(run_events)/event_loop.draws/vmap", 50,
                     150],                                # [100, 200)
                    ["KERNEL", 200, 600],
                    ["pad.2 jit(run_events)/event_loop.draws/pad", 1000,
                     500],                                # [1000, 1100)
                    ["sort.3 jit(run_events)/sort", 850, 100]]}
    assert read(_reading(cell, scoped)) == (100 + 100) / 50
    # a program without the scope, as the parent's: None, not 0
    plain = {"0": [["fusion.1 jit(run_events)/vmap", 50, 150],
                   ["KERNEL", 200, 600]]}
    assert read(_reading(cell, plain)) is None
