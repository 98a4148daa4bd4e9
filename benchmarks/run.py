"""Benchmark entry point — paper sections and registered scenarios.

Usage: PYTHONPATH=src python -m benchmarks.run [section ...] [--seeds N]
           [--backend auto|xla|pallas] [--devices N] [--chunk R] [--zipf S]
           [--scenario NAME ... | --scenario all] [--list-scenarios]
           [--scenario-out FILE] [--check-slo] [--slo-p99-ns NS]
           [--slo-min-eps RATE]
Prints ``name,us_per_call,derived`` CSV rows.

Sections reproduce the paper's figures; ``--scenario NAME`` runs a named
workload program from the registry (``repro.experiments.registry``) — the
same single entry point CI uses. ``--scenario all``
runs every registered scenario; ``--scenario-out FILE`` additionally
writes the scenario rows as JSON with the scenario name recorded per row.
Running more than one scenario appends a cross-algorithm leaderboard:
per scenario, every algorithm that ran is ranked by its best workload
row's throughput (simulated p99 alongside) as
``leaderboard.<scenario>.r<rank>.<alg>`` rows.

--seeds N runs every simulator workload with N independent seeds (batched
in one vmapped dispatch per shape bucket — no extra compiles) and turns
the derived columns into mean±ci95. --backend/--devices/--chunk build the
immutable ``ExecOptions`` value threaded explicitly into every section and
scenario (no process-wide execution state). --zipf skews the within-node
lock choice for sections that support it (fig5). The kernel section
ignores the simulator flags. These rows are CPU wall times and simulated
statistics; the speed of the chip is measured by ``bench/run.py``.

--check-slo evaluates each run scenario's registered SLO
(``repro.experiments.Slo``: simulated p99 latency ceiling + wall-clock
events/sec floor) against its result rows and exits non-zero on any
violation — the CI scenarios leg runs under this gate. --slo-p99-ns /
--slo-min-eps override that bound for every checked scenario (merged
onto the registered Slo — the other bound stays enforced — and implying
--check-slo); that is how the exit-code tests deliberately violate an
SLO.
"""
import argparse
import inspect
import json
import sys
import time

from benchmarks import (common, fig1_loopback, fig4_budget, fig5_throughput,
                        fig6_latency, microbench, serving_curves)
from repro.core import batch
from repro.experiments import (ExecOptions, Slo, check_slo, get_scenario,
                               run_scenario, scenario_names)

SECTIONS = {
    "fig1": fig1_loopback.main,
    "fig4": fig4_budget.main,
    "fig5": fig5_throughput.main,
    "fig6": fig6_latency.main,
    "micro": microbench.main,
    "serving": serving_curves.main,
}


def _emit_scenario(name: str, n_seeds: int, options: ExecOptions) -> list:
    batch.reset_exec_stats()
    t0 = time.time()
    rows = run_scenario(name, n_seeds=n_seeds, n_events=common.EVENTS,
                        options=options)
    wall = time.time() - t0
    for r in rows:
        common.emit(f"scenario.{name}.{r['name']}", r["us_per_call"],
                    r["derived"])
        r["scenario"] = name
    print(f"# scenario {name} done in {wall:.1f}s", flush=True)
    # one simulator replica set per row carrying mean_mops; scenarios that
    # never touch the simulator (coord-stress) report wall time only
    n_sim = sum(1 for r in rows if "mean_mops" in r)
    summary = {"scenario": name, "name": f"{name}.wall",
               "wall_s": round(wall, 3), "simulated_workloads": n_sim,
               "events_per_replica": common.EVENTS, "seeds": n_seeds}
    if n_sim:
        total_events = common.EVENTS * n_seeds * n_sim
        summary["total_events"] = total_events
        summary["events_per_sec"] = round(total_events / max(wall, 1e-9), 1)
    # pallas runs leave the event-loop kernel's VMEM plan behind (tile
    # chosen vs requested, bytes, clock representation) — record it so the
    # JSON artifact shows whether the planner had to shrink the tile
    vp = batch.exec_stats().get("vmem_plan")
    if vp is not None:
        summary["vmem_plan"] = vp
        print(f"# scenario {name} vmem plan: tile {vp['requested_tile']}"
              f"->{vp['tile']}, {vp['total_bytes']:,}B "
              f"({vp['representation']})", flush=True)
    return rows + [summary]


def _leaderboard(all_rows: list) -> list:
    """Cross-algorithm leaderboard over every scenario that just ran.

    Per scenario, each algorithm is represented by its best-throughput
    workload row (rows carry ``alg`` since the registry labels them) and
    ranked by ``mean_mops``; the row's simulated p99 rides along so the
    table reads as throughput *and* tail latency per algorithm. Emitted
    as ``leaderboard.<scenario>.r<rank>.<alg>`` CSV rows and appended to
    the JSON artifact under scenario name ``leaderboard``.
    """
    best: dict = {}
    for r in all_rows:
        alg = r.get("alg")
        if alg is None or "mean_mops" not in r:
            continue
        key = (r["scenario"], alg)
        if key not in best or r["mean_mops"] > best[key]["mean_mops"]:
            best[key] = r
    rows = []
    for scen in sorted({s for s, _ in best}):
        ranked = sorted((kv for kv in best.items() if kv[0][0] == scen),
                        key=lambda kv: -kv[1]["mean_mops"])
        for rank, ((_, alg), r) in enumerate(ranked, 1):
            name = f"leaderboard.{scen}.r{rank}.{alg}"
            derived = (f"{r['mean_mops']:.3f}Mops "
                       f"p99={r['p99_lat_ns']:.0f}ns ({r['name']})")
            common.emit(name, 0.0, derived)
            rows.append({"scenario": "leaderboard", "name": name,
                         "us_per_call": 0.0, "derived": derived,
                         "rank": rank, "alg": alg,
                         "ranked_scenario": scen,
                         "best_row": r["name"],
                         "best_mean_mops": r["mean_mops"],
                         "best_p99_lat_ns": r["p99_lat_ns"]})
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("sections", nargs="*", metavar="section",
                    help=f"sections to run (default: all of "
                         f"{', '.join(SECTIONS)})")
    ap.add_argument("--seeds", type=int, default=1,
                    help="independent seeds per simulator workload")
    ap.add_argument("--backend", choices=("auto", "xla", "pallas"),
                    default=None, help="simulator execution backend")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard sweep buckets over this many JAX devices")
    ap.add_argument("--chunk", type=int, default=None,
                    help="rows per device per dispatch unit (units coalesce "
                         "into one kernel tile per device)")
    ap.add_argument("--zipf", type=float, default=0.0,
                    help="Zipf skew of within-node lock targets (fig5)")
    ap.add_argument("--scenario", action="append", default=[],
                    metavar="NAME",
                    help="run a registered scenario ('all' = every one); "
                         "repeatable; replaces the default section list")
    ap.add_argument("--list-scenarios", action="store_true",
                    help="print registered scenario names and exit")
    ap.add_argument("--scenario-out", default=None, metavar="FILE",
                    help="write scenario rows as JSON (scenario name "
                         "recorded per row)")
    ap.add_argument("--check-slo", action="store_true",
                    help="evaluate each scenario's registered SLO and "
                         "exit non-zero on violation")
    ap.add_argument("--slo-p99-ns", type=float, default=None, metavar="NS",
                    help="override the p99 latency ceiling (ns) for every "
                         "checked scenario")
    ap.add_argument("--slo-min-eps", type=float, default=None,
                    metavar="RATE",
                    help="override the wall-clock events/sec floor for "
                         "every checked scenario")
    args = ap.parse_args()
    common.enable_compile_cache()
    if args.list_scenarios:
        for name in scenario_names():
            print(name)
        return
    if args.seeds < 1:
        ap.error(f"--seeds must be >= 1, got {args.seeds}")
    try:
        options = ExecOptions.from_env(backend=args.backend,
                                       devices=args.devices,
                                       chunk=args.chunk)
    except ValueError as e:
        ap.error(str(e))

    scen = args.scenario
    if "all" in scen:
        scen = scenario_names()
    unknown = [s for s in scen if s not in scenario_names()]
    if unknown:
        ap.error(f"unknown scenario(s) {unknown}; pick from "
                 f"{scenario_names()}")
    unknown = [s for s in args.sections if s not in SECTIONS]
    if unknown:
        ap.error(f"unknown section(s) {unknown}; pick from "
                 f"{list(SECTIONS)}")
    if args.scenario_out and not scen:
        ap.error("--scenario-out requires --scenario")
    slo_override = (args.slo_p99_ns is not None
                    or args.slo_min_eps is not None)
    if slo_override:
        args.check_slo = True        # an override implies the gate
        try:
            # fail fast on bad bounds, before any scenario runs
            Slo(p99_ns=args.slo_p99_ns,
                min_events_per_sec=args.slo_min_eps)
        except ValueError as e:
            ap.error(str(e))
    if args.check_slo and not scen:
        ap.error("--check-slo / --slo-* require --scenario")

    print("name,us_per_call,derived")
    all_rows = []
    for name in scen:
        all_rows += _emit_scenario(name, args.seeds, options)
    if len(scen) > 1:
        all_rows += _leaderboard(all_rows)
    if args.scenario_out and scen:
        with open(args.scenario_out, "w") as f:
            json.dump(all_rows, f, indent=2, sort_keys=True, default=str)
        print(f"# wrote {args.scenario_out}", flush=True)

    if args.check_slo:
        failed = False
        for name in scen:
            slo = get_scenario(name).slo
            if slo_override:
                # merge onto the registered bounds: an overridden field
                # wins, the other keeps its registered value (overriding
                # one bound must not silently disable the other)
                slo = Slo(
                    p99_ns=(args.slo_p99_ns if args.slo_p99_ns is not None
                            else slo.p99_ns if slo else None),
                    min_events_per_sec=(
                        args.slo_min_eps if args.slo_min_eps is not None
                        else slo.min_events_per_sec if slo else None),
                    per_label=slo.per_label if slo else ())
            if slo is None:
                print(f"# slo {name}: none registered, skipped",
                      flush=True)
                continue
            rep = check_slo(slo,
                            [r for r in all_rows if r["scenario"] == name])
            verdict = "PASS" if rep.ok else "FAIL"
            print(f"# slo {name}: {verdict} ({rep.checked} row(s) checked)",
                  flush=True)
            for v in rep.violations:
                print(f"# slo {name}: VIOLATION {v}", flush=True)
            failed = failed or not rep.ok
        if failed:
            sys.exit(1)

    which = args.sections or ([] if scen else list(SECTIONS))
    for name in which:
        fn = SECTIONS[name]
        params = inspect.signature(fn).parameters
        kwargs = {}
        if "n_seeds" in params:
            kwargs["n_seeds"] = args.seeds
        if "options" in params:
            kwargs["options"] = options
        if "zipf" in params and args.zipf:
            kwargs["zipf"] = args.zipf
        t0 = time.time()
        fn(**kwargs)
        print(f"# section {name} done in {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
