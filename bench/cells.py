"""Cells of the benchmark, read from data alone.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix. The configuration's file holds the
deployment: the cluster, the cost constants, the default budgets, the
latency-ring size and the precision the results are stated in. The
traffic file ``bench/traffic/<traffic>.json`` holds the mix: grids of
workload points (the union of the cartesian products of each entry's
axes), the seeds per point, the events per replica, and how the sweep is
laid over the chips. Adding a cell means adding files and an entry;
nothing here names a cell.

Each sweep ``j`` of a run (``j = 0`` is the warm-up) gets seeds of its
own, derived from ``--seed``: point ``c`` of the mix runs the seeds
``base + (j * C + c) * S + [0, S)``, so no replica is ever run twice in a
process and the same ``--seed`` gives the same replicas.
"""
from __future__ import annotations

import itertools
import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

#: the checkout root: ``BENCHMARK.json`` and ``bench/`` live here
ROOT = Path(__file__).resolve().parent.parent
#: configuration keys that make up the cluster of every point
CLUSTER_KEYS = ("n_nodes", "threads_per_node", "n_locks")
#: point keys a traffic grid may set (the rest comes from the config)
GRID_KEYS = ("alg", "locality", "b_init", "zipf_s", "think", "read_frac",
             "topology")
#: seeds stay below this so ``base + offset`` fits the program's int32
SEED_SPAN = 1 << 30


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict

    @property
    def points(self) -> list[dict]:
        """Every workload point of the mix, in file order."""
        return workload_points(self.config, self.traffic)

    @property
    def n_seeds(self) -> int:
        return int(self.traffic["seeds_per_point"])

    @property
    def n_events(self) -> int:
        return int(self.traffic["events_per_replica"])

    @property
    def replicas(self) -> int:
        return len(self.points) * self.n_seeds

    @property
    def devices(self) -> int | None:
        """Chips the sweep is sharded over (None: one unsharded dispatch
        per bucket)."""
        return self.traffic.get("devices")

    @property
    def chunk(self) -> int | None:
        return self.traffic.get("chunk")

    @property
    def check_replicas(self) -> int:
        return int(self.traffic["check_replicas"])


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root``'s BENCHMARK.json, with its files."""
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in {root / 'BENCHMARK.json'}; "
                       f"cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    cell = Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                traffic)
    check_cell(cell)
    return cell


def check_cell(cell: Cell) -> None:
    t = cell.traffic
    if t.get("loop") != "closed":
        raise ValueError(f"{cell.traffic_name}: only closed-loop traffic "
                         f"is generated, got loop={t.get('loop')!r}")
    if cell.n_seeds < 1 or cell.n_events < 1 or cell.check_replicas < 1:
        raise ValueError(f"{cell.traffic_name}: seeds_per_point, "
                         f"events_per_replica and check_replicas must be "
                         f">= 1")
    if cell.devices is not None and cell.devices > cell.chips:
        raise ValueError(f"{cell.name}: the mix shards over {cell.devices} "
                         f"chips but the cell asks for {cell.chips}")
    algs = set(cell.config.get("algorithms", ()))
    for p in cell.points:
        if p["alg"] not in algs:
            raise ValueError(f"{cell.traffic_name}: {p['alg']!r} is not an "
                             f"algorithm of {cell.config_name}")


def workload_points(config: dict, traffic: dict) -> list[dict]:
    """The union, in order, of each grid entry's cartesian product; every
    point carries the configuration's cluster and defaults."""
    base = {k: config[k] for k in CLUSTER_KEYS}
    base["b_init"] = tuple(config["b_init"])
    base["zipf_s"] = float(config.get("zipf_s", 0.0))
    base["think"] = config.get("think", "default")
    points = []
    for entry in traffic["grid"]:
        bad = set(entry) - set(GRID_KEYS)
        if bad:
            raise ValueError(f"unknown grid axes {sorted(bad)}; the "
                             f"generator knows {GRID_KEYS}")
        axes = list(entry)
        for combo in itertools.product(*(entry[a] for a in axes)):
            p = dict(base)
            for a, v in zip(axes, combo):
                p[a] = tuple(v) if isinstance(v, list) else v
            points.append(p)
    return points


def base_seed(seed: int) -> int:
    """The first replica seed of a run, drawn from ``--seed``."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return int(np.random.default_rng(seed).integers(0, SEED_SPAN))


def point_seed(seed: int, sweep: int, point: int, n_points: int,
               n_seeds: int) -> int:
    """The base seed of ``point`` in sweep ``sweep`` of a run."""
    return base_seed(seed) + (sweep * n_points + point) * n_seeds


def workload_specs(cell: Cell, seed: int, sweep: int) -> list:
    """The program's ``Workload`` specs of one sweep of the cell."""
    from repro.workloads import Workload
    pts = cell.points
    cost = dict(cell.config["cost_model"])
    return [Workload(p["alg"], p["n_nodes"], p["threads_per_node"],
                     p["n_locks"], locality=p.get("locality", 1.0),
                     zipf_s=p["zipf_s"], think=p["think"],
                     b_init=p["b_init"], read_frac=p.get("read_frac", 0.0),
                     topology=p.get("topology"), cost=cost,
                     seed=point_seed(seed, sweep, c, len(pts), cell.n_seeds))
            for c, p in enumerate(pts)]

