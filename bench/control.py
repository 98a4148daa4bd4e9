"""The control: the plain reference, computed a precision below the one
the configuration states, put in the program's place.

    python3 bench/control.py --workload <cell> --seeds 3 [--precision bfloat16]

For each seed it drives a whole run of the harness (``bench/run.py``) at
the cell's own sizes, with ``batch.sweep`` replaced by the reference in
``--precision``, and a window of one sweep; the harness then compares the
sampled replicas with the reference in the configuration's precision, as
it does the program's. The control must come out not correct on every
seed; each seed's ``mismatched_fields`` is a reading the limit was set
from (the upper one). ``--precision float32`` puts the reference itself
in the program's place, which must come out correct. The replicas are
computed only where the harness reads them.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import io
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for _p in (str(BENCH.parent / "src"), str(BENCH.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cells, compare, reference, run  # noqa: E402

OPEN_LOOP = ("arr_ns", "wait_ns", "sojourn_ns", "rstat")


class ReferenceResult:
    """A BatchResult stand-in for one point: each seed's replica is run by
    the reference the first time one of its fields is read."""

    def __init__(self, point, seed0, n_events, config, precision):
        self.replica = functools.lru_cache(maxsize=None)(
            lambda s: reference.simulate(
                point, seed0 + s, n_events, config["cost_model"],
                config["lat_samples"], precision=precision))

    def __getattr__(self, name):
        if name in OPEN_LOOP:
            return None
        if name in compare.FIELDS:
            return _Seeds(self.replica, name)
        raise AttributeError(name)


class _Seeds:
    def __init__(self, replica, name):
        self.replica, self.name = replica, name

    def __getitem__(self, s):
        return self.replica(s)[self.name]


def reference_sweep(cell: cells.Cell, precision: str):
    """A ``batch.sweep`` that the reference answers, for the cell's specs."""
    def sweep(specs, n_seeds, n_events, *_a, **_k):
        return [ReferenceResult(p, w.seed, n_events, cell.config, precision)
                for p, w in zip(cell.points, specs, strict=True)]
    return sweep


def control_run(name: str, seed: int, precision: str,
                root: Path = run.ROOT) -> dict:
    """One harness run with the reference in ``precision`` in the
    program's place; its result line."""
    from repro.core import batch
    cell = cells.load_cell(name, root)
    saved = batch.sweep, run.require_native
    batch.sweep = reference_sweep(cell, precision)
    run.require_native = lambda plans: None
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            run.main(["--workload", name, "--seed", str(seed),
                      "--seconds", "0", "--trace", "0"], root=root)
    finally:
        batch.sweep, run.require_native = saved
    return json.loads(out.getvalue().strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--precision", default="bfloat16",
                    choices=reference.PRECISIONS)
    args = ap.parse_args(argv)
    readings = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        res = control_run(args.workload, seed, args.precision)
        n = res["compared"]["mismatched_fields"]["value"]
        readings.append(n)
        print(json.dumps({"cell": args.workload, "seed": seed,
                          "precision": args.precision,
                          "correct": res["correct"],
                          "mismatched_fields": n}), flush=True)
    print(json.dumps({"cell": args.workload, "precision": args.precision,
                      "readings": readings, "least": min(readings)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
