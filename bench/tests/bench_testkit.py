"""Helpers for the benchmark's CPU tests: a throwaway checkout holding the
benchmark's files and extra cells of the tests' own."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def checkout(tmp: Path) -> Path:
    """A copy of ``BENCHMARK.json`` and ``bench/`` (without its tests)."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return tmp


def add_cell(root: Path, name: str, cluster: dict, traffic: dict,
             chips: int = 1) -> None:
    """Add a configuration, a traffic file and a cell by files and entries
    alone, as a later change would; every per-layer metric lists it."""
    cfg = json.loads(
        (ROOT / "bench/configs/alock-fig5-n10-k100.json").read_text())
    cfg.update(name=name, algorithms=["alock", "spinlock", "mcs"], **cluster)
    (root / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
    (root / f"bench/traffic/{name}.json").write_text(json.dumps(traffic))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": name, "source": "a test cluster",
                         "file": f"bench/configs/{name}.json", "reduced": [],
                         "why": "a test"})
    b["workloads"].append({"name": name, "config": name, "traffic": name,
                           "chips": chips, "why": "a test"})
    for m in b["per_layer"]:
        m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(b))


def tiny_traffic(events: int = 400, seeds: int = 3, check: int = 4,
                 algs=("alock", "mcs"), locality=(0.9,)) -> dict:
    return {"loop": "closed",
            "grid": [{"alg": list(algs), "locality": list(locality)}],
            "seeds_per_point": seeds, "events_per_replica": events,
            "devices": None, "chunk": None, "check_replicas": check}


TINY = {"n_nodes": 2, "threads_per_node": 2, "n_locks": 4}
