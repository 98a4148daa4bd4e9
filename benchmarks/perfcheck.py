"""Perf trajectory check: aggregate simulator events/sec, recorded per PR.

Runs a Fig.5-shaped grid through ``batch.sweep`` once per execution backend
(XLA fori_loop vs the Pallas event-loop kernel) and once through the
chunked/sharded bucket layout, then writes ``BENCH_events_per_sec.json`` so
every PR leaves an events/sec data point behind (CI uploads it as an
artifact).

Measured quantities:
  * events/sec per backend (warm: one untimed sweep first, so compile cost
    is reported separately and the steady-state rate is comparable PR to
    PR);
  * an open-loop serving row: the same topology driven by a fixed-rate
    Poisson arrival stream (``repro.traffic``), recording offered vs
    achieved request rate and the harness events/sec of the open-loop
    code path — so the arrival-ingestion lanes show up in the perf
    trajectory, not only in the scenario JSONs;
  * a cross-algorithm leaderboard leg: all five registered algorithms
    (alock, spinlock, mcs, hlock with a 2-rack topology, alock-rw at a
    0.9 read mix) swept on one shared grid and ranked by simulated
    throughput — each algorithm's mean Mops is its own tracked trajectory
    row, so kernel-path regressions in the hierarchical or reader-writer
    designs trip the ``--baseline`` gate even though the Fig.5 grid never
    dispatches them;
  * dispatch/compile counts from ``batch.exec_stats`` — the chunked layout
    must show one dispatch per chunk per mesh (vs one per bucket) while
    reusing a single compile per shape key, which is the CPU-visible half
    of the scaling story (on TPU the pallas backend's events/sec carries
    it);
  * the event-loop kernel's VMEM plan (``repro.kernels.event_loop.vmem``,
    via ``exec_stats()["vmem_plan"]``) for the pallas backend — replica
    tile chosen vs requested, total VMEM bytes, clock representation — so
    every PR records whether the kernel still fits the budget and whether
    the planner had to shrink the tile;
  * a roofline row (``benchmarks.roofline``): the events/sec-per-byte
    ceiling from the VMEM byte table and a *measured* host copy
    bandwidth, plus the fraction of that roof the fastest backend
    achieved — a tracked trajectory row, so an efficiency regression
    trips the ``--baseline`` gate even if absolute ev/s drifts with the
    runner.

The sharded leg also records ``ratio_vs_unsharded`` (sharded ev/s over
the unsharded XLA leg); ``--min-sharded-ratio`` turns that into a hard
gate — superchunk dispatch coalescing keeps the chunked layout near the
one-dispatch layout on CPU, and CI fails if it slides back.

``--baseline FILE`` compares the fresh report against a previous run's
JSON (CI downloads the last ``BENCH_events_per_sec.json`` artifact and
passes it here): every tracked events/sec figure must stay within
``--regression-tolerance`` (default 10%) of the baseline, or the process
exits non-zero. A missing baseline file, or one measured with a
different grid/event count, is reported and skipped — the first run of a
new trajectory cannot regress against anything.

Smoke mode: REPRO_BENCH_EVENTS=2000 (same knob as the other benchmarks).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from benchmarks import roofline
from benchmarks.common import EVENTS, enable_compile_cache
from repro.core import batch
from repro.experiments import fig5_workloads
from repro.workloads import Arrivals, Workload, racks_of

LOCALITY = (0.85, 0.95, 1.0)

# the open-loop leg: the scenario topology under a fixed-rate Poisson
# stream — a rate below ALock's knee but above the loopback designs', so
# the row shows both regimes (tracked vs shed) in one line each
OPEN_RATE_PER_US = 4.0
OPEN_REQS = 256
OPEN_QCAP = 32
OPEN_ALGS = ("alock", "mcs")

# the leaderboard leg: all five registered algorithms on one shared
# topology (2 racks x 2 nodes for hlock's tiering, a 0.9 read mix for
# alock-rw's shared section) — every algorithm's simulated throughput is
# its own trajectory row, so a perf regression in the hlock or alock-rw
# kernel paths trips the --baseline gate even though paper-fig5 never
# dispatches them
LB_ALGS = ("alock", "spinlock", "mcs", "hlock", "alock-rw")
LB_READ_FRAC = 0.9
LB_LOCALITY = 0.95


def _leaderboard_grid():
    racks = racks_of(4, 2)
    out = []
    for alg in LB_ALGS:
        kw = {}
        if alg == "hlock":
            kw["topology"] = racks
        if alg == "alock-rw":
            kw["read_frac"] = LB_READ_FRAC
        out.append(Workload(alg, n_nodes=4, threads_per_node=4, n_locks=16,
                            locality=LB_LOCALITY, **kw))
    return out


def _open_grid():
    arr = Arrivals(rate_per_us=OPEN_RATE_PER_US, max_requests=OPEN_REQS,
                   queue_cap=OPEN_QCAP)
    return [Workload(alg, n_nodes=4, threads_per_node=4, n_locks=16,
                     locality=0.95, arrivals=arr) for alg in OPEN_ALGS]


def _grid():
    # the registry's paper-fig5 grid: perfcheck and --scenario paper-fig5
    # measure the identical workload program
    return fig5_workloads()


def _timed_sweep(cfgs, n_seeds, events, **kw):
    """(results, wall_s of the warm run, stats) — stats carries the warm
    run's dispatch count plus the cold (first) run's compile count."""
    batch.reset_exec_stats()
    batch.sweep(cfgs, n_seeds=n_seeds, n_events=events, **kw)  # warm/compile
    cold = batch.exec_stats()
    batch.reset_exec_stats()
    t0 = time.perf_counter()
    res = batch.sweep(cfgs, n_seeds=n_seeds, n_events=events, **kw)
    wall = time.perf_counter() - t0
    st = batch.exec_stats()
    st["compiles"] = cold["compiles"]
    return res, wall, st


def _tracked_rates(report: dict) -> dict:
    """name -> events/sec for every figure the regression gate tracks."""
    rates = {}
    for b, row in report.get("backends", {}).items():
        rates[f"backends.{b}"] = row.get("events_per_sec", 0.0)
    if "sharding" in report:
        rates["sharding"] = report["sharding"].get("events_per_sec", 0.0)
    if "roofline" in report:
        # achieved fraction of the memory roof: dimensionless, but the
        # same bigger-is-better ratio gate applies
        rates["roofline"] = report["roofline"].get("achieved_fraction", 0.0)
    if "open_loop" in report:
        rates["open_loop"] = report["open_loop"].get("events_per_sec", 0.0)
    if "leaderboard" in report:
        lb = report["leaderboard"]
        rates["leaderboard"] = lb.get("events_per_sec", 0.0)
        for alg, row in lb.get("algorithms", {}).items():
            # simulated Mops, not harness ev/s — still a per-row trajectory
            # figure the same ratio gate applies to
            rates[f"leaderboard.{alg}"] = row.get("mean_mops", 0.0)
    return rates


def _check_baseline(report: dict, path: str, tolerance: float) -> bool:
    """Diff the fresh report's events/sec against a previous run's JSON."""
    if not os.path.exists(path):
        print(f"# baseline: {path} not found — nothing to regress against",
              flush=True)
        return True
    with open(path) as f:
        base = json.load(f)
    bg, fg = base.get("grid", {}), report["grid"]
    keys = ("events_per_replica", "configs", "seeds")
    if tuple(bg.get(k) for k in keys) != tuple(fg[k] for k in keys):
        print(f"# baseline: {path} measured a different grid "
              f"({ {k: bg.get(k) for k in keys} } vs "
              f"{ {k: fg[k] for k in keys} }) — comparison skipped",
              flush=True)
        return True
    base_rates = _tracked_rates(base)
    ok = True
    for name, fresh in _tracked_rates(report).items():
        ref = base_rates.get(name)
        if not ref or ref <= 0:
            continue        # row absent in the baseline: new, not regressed
        ratio = fresh / ref
        verdict = "ok" if ratio >= 1.0 - tolerance else "REGRESSION"
        ok = ok and verdict == "ok"
        unit = ("Mops" if name.startswith("leaderboard.")
                else "of-roof" if name == "roofline" else "ev/s")
        print(f"# baseline {name}: {fresh:,.1f} vs {ref:,.1f} {unit} "
              f"({ratio:.3f}x) {verdict}", flush=True)
    if not ok:
        print(f"# perfcheck: events/sec regressed more than "
              f"{tolerance:.0%} vs {path}", flush=True)
    return ok


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backends", default="xla,pallas",
                    help="comma list of backends to measure")
    ap.add_argument("--events", type=int, default=EVENTS)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--chunk", type=int, default=None,
                    help="rows/device/dispatch for the sharded leg "
                         "(default: half a bucket, forcing 2 chunks)")
    ap.add_argument("--out", default="BENCH_events_per_sec.json")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help="previous BENCH_events_per_sec.json to diff "
                         "against; exit non-zero on an events/sec "
                         "regression beyond the tolerance")
    ap.add_argument("--regression-tolerance", type=float, default=0.10,
                    metavar="FRAC",
                    help="allowed fractional events/sec drop vs the "
                         "baseline (default 0.10)")
    ap.add_argument("--min-sharded-ratio", type=float, default=0.0,
                    metavar="FRAC",
                    help="fail if sharded ev/s falls below FRAC x the "
                         "unsharded XLA leg (default 0.0 = report only)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.baseline and not 0.0 < args.regression_tolerance < 1.0:
        ap.error(f"--regression-tolerance must be in (0, 1), got "
                 f"{args.regression_tolerance}")

    cfgs = _grid()
    n_buckets = len({batch.shape_key(c, args.events) for c in cfgs})
    total_events = len(cfgs) * args.seeds * args.events
    report = {
        "scenario": "paper-fig5",
        "grid": {"configs": len(cfgs), "seeds": args.seeds,
                 "events_per_replica": args.events,
                 "total_events": total_events, "buckets": n_buckets},
        "backends": {},
    }

    base = None     # (backend, results) of the first measured backend
    base_xla = None  # unsharded XLA results — the oracle both legs diff to
    for backend in [b.strip() for b in args.backends.split(",") if b.strip()]:
        res, wall, st = _timed_sweep(cfgs, args.seeds, args.events,
                                     backend=backend)
        eps = total_events / max(wall, 1e-9)
        report["backends"][backend] = {
            "wall_s": round(wall, 4), "events_per_sec": round(eps, 1),
            "dispatches": st["dispatches"], "compiles": st["compiles"],
            "vmem_plan": st.get("vmem_plan"),
        }
        print(f"perfcheck.{backend},{wall*1e6/len(cfgs):.1f},"
              f"{eps/1e6:.3f}Mevents/s", flush=True)
        if backend == "xla":
            base_xla = res
        if base is None:
            base = (backend, res)
        else:
            same = all(np.array_equal(a.lat_ns, b.lat_ns)
                       and np.array_equal(a.ops, b.ops)
                       for a, b in zip(base[1], res))
            report["backends"][backend]["bitwise_equal_to_" + base[0]] = same
    if base_xla is None:
        # the sharded leg below runs on xla, so its bitwise check needs an
        # unsharded xla oracle even when --backends skipped it (untimed)
        base_xla = batch.sweep(cfgs, n_seeds=args.seeds,
                               n_events=args.events, backend="xla")

    # sharded/chunked layout: one dispatch per chunk (per mesh), one compile
    # per shape key — dispatch-count evidence that oversized buckets spill
    # into fixed-size chunks instead of recompiling
    bucket_rows = max(args.seeds * len(LOCALITY), 1)
    chunk = args.chunk or max(1, -(-bucket_rows // 2))
    res_c, wall_c, st_c = _timed_sweep(cfgs, args.seeds, args.events,
                                       backend="xla", chunk=chunk)
    eq = all(
        np.array_equal(a.lat_ns, b.lat_ns) and np.array_equal(a.ops, b.ops)
        for a, b in zip(base_xla, res_c))
    report["sharding"] = {
        "chunk_rows_per_device": chunk,
        "bucket_rows": bucket_rows,
        "wall_s": round(wall_c, 4),
        "events_per_sec": round(total_events / max(wall_c, 1e-9), 1),
        "dispatches": st_c["dispatches"],
        "compiles": st_c["compiles"],
        "unsharded_dispatches_per_bucket": 1,
        "bitwise_equal_to_unsharded": bool(eq),
    }
    ratio_vs_unsharded = None
    if "xla" in report["backends"]:
        ratio_vs_unsharded = report["sharding"]["events_per_sec"] / max(
            report["backends"]["xla"]["events_per_sec"], 1e-9)
        report["sharding"]["ratio_vs_unsharded"] = round(
            ratio_vs_unsharded, 3)
    print(f"perfcheck.sharded.chunk{chunk},{wall_c*1e6/len(cfgs):.1f},"
          f"dispatches={st_c['dispatches']},compiles={st_c['compiles']},"
          f"bitwise_ok={eq},ratio="
          + (f"{ratio_vs_unsharded:.3f}" if ratio_vs_unsharded is not None
             else "n/a"), flush=True)

    # open-loop leg: the arrival-ingestion code path is a different kernel
    # trace (R > 0 adds the request lanes), so its events/sec is tracked
    # as its own trajectory row, with the simulated serving split alongside
    open_cfgs = _open_grid()
    res_o, wall_o, st_o = _timed_sweep(open_cfgs, args.seeds, args.events,
                                       backend="xla")
    open_events = len(open_cfgs) * args.seeds * args.events
    report["open_loop"] = {
        "rate_per_us": OPEN_RATE_PER_US, "requests": OPEN_REQS,
        "queue_cap": OPEN_QCAP, "wall_s": round(wall_o, 4),
        "events_per_sec": round(open_events / max(wall_o, 1e-9), 1),
        "dispatches": st_o["dispatches"], "compiles": st_o["compiles"],
        "workloads": {},
    }
    for w, br in zip(open_cfgs, res_o):
        sm = br.serving_mean()
        report["open_loop"]["workloads"][w.alg] = {
            "offered_per_us": round(sm["offered_per_us"], 3),
            "goodput_per_us": round(sm["goodput_per_us"], 3),
            "drop_rate": round(sm["drop_rate"], 4),
        }
        print(f"perfcheck.open_loop.{w.alg},"
              f"{wall_o * 1e6 / len(open_cfgs):.1f},"
              f"offered={sm['offered_per_us']:.3f}/us,"
              f"goodput={sm['goodput_per_us']:.3f}/us,"
              f"drop={sm['drop_rate']:.3f}", flush=True)

    # leaderboard leg: one sweep over all five algorithms, ranked by
    # simulated throughput — each algorithm's mean_mops is a tracked
    # trajectory row (the only leg that exercises hlock and alock-rw)
    lb_cfgs = _leaderboard_grid()
    res_l, wall_l, st_l = _timed_sweep(lb_cfgs, args.seeds, args.events,
                                       backend="xla")
    lb_events = len(lb_cfgs) * args.seeds * args.events
    report["leaderboard"] = {
        "locality": LB_LOCALITY, "read_frac": LB_READ_FRAC,
        "wall_s": round(wall_l, 4),
        "events_per_sec": round(lb_events / max(wall_l, 1e-9), 1),
        "dispatches": st_l["dispatches"], "compiles": st_l["compiles"],
        "algorithms": {},
    }
    ranked = sorted(zip(lb_cfgs, res_l), key=lambda p: -p[1].mean_mops)
    for rank, (w, br) in enumerate(ranked, 1):
        report["leaderboard"]["algorithms"][w.alg] = {
            "rank": rank,
            "mean_mops": round(br.mean_mops, 4),
            "p99_lat_ns": round(br.p99_lat_ns, 1),
        }
        print(f"perfcheck.leaderboard.r{rank}.{w.alg},"
              f"{wall_l * 1e6 / len(lb_cfgs):.1f},"
              f"{br.mean_mops:.3f}Mops,p99={br.p99_lat_ns:.0f}ns",
              flush=True)

    # roofline leg: the events/sec-per-byte ceiling for the fig5 kernel
    # shape (byte table x measured copy bandwidth) and the fraction of it
    # the fastest backend achieved — the fraction is its own tracked
    # trajectory row, robust to absolute runner-speed drift
    alg0, T0, N0, K0, _, R0 = batch.shape_key(cfgs[0], args.events)
    vp = (report["backends"].get("pallas") or {}).get("vmem_plan") or {}
    mkw = dict(T=T0, N=N0, K=K0, R=R0, hl=alg0 == "hlock",
               rw=alg0 == "alock-rw")
    if vp:
        mkw.update(tile=vp["tile"], ev_chunk=vp["ev_chunk"],
                   lat_samples=vp["lat_samples"],
                   repr32=vp["representation"] == "i32pair")
    m = roofline.model(**mkw)
    bw = roofline.measure_bandwidth()
    roof = roofline.roof_events_per_sec(bw, m)
    best = max((row["events_per_sec"]
                for row in report["backends"].values()), default=0.0)
    report["roofline"] = {
        "bandwidth_bytes_per_s": round(bw, 1),
        "bytes_per_event": m["bytes_per_event"],
        "roof_events_per_sec": round(roof, 1),
        "best_backend_events_per_sec": best,
        "achieved_fraction": round(best / max(roof, 1e-9), 5),
    }
    print(f"perfcheck.roofline,{m['bytes_per_event']:.1f},"
          f"roof={roof / 1e6:.1f}Mev/s,"
          f"achieved={report['roofline']['achieved_fraction']:.4f}",
          flush=True)

    bk = report["backends"]
    if "xla" in bk and "pallas" in bk:
        report["pallas_over_xla"] = round(
            bk["pallas"]["events_per_sec"] / max(bk["xla"]["events_per_sec"],
                                                 1e-9), 3)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"# wrote {args.out}", flush=True)

    failed = False
    if (args.min_sharded_ratio > 0.0 and ratio_vs_unsharded is not None
            and ratio_vs_unsharded < args.min_sharded_ratio):
        print(f"# perfcheck: sharded/unsharded ratio "
              f"{ratio_vs_unsharded:.3f} below --min-sharded-ratio "
              f"{args.min_sharded_ratio}", flush=True)
        failed = True
    if args.baseline and not _check_baseline(report, args.baseline,
                                             args.regression_tolerance):
        failed = True
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
