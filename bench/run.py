"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix; ``bench/cells.py`` turns their files into
the program's ``Workload`` specs. Set-up is the imports, device start,
the compile (or the persistent compile cache) and one sweep of exactly the
window's shapes. The window then calls ``repro.core.batch.sweep`` with
``backend="pallas"`` (sharded over the chips where the mix says so) back
to back, each call on seeds of its own, until ``--seconds`` have passed.
One ``sweep`` call is one attempted operation.

After the window, a sample of its replicas drawn from ``--seed`` (one
from each stretch of the batch axis, at a sweep drawn from all of them)
is run again by the plain reference (``bench/reference.py``) on the host,
and every result field must agree bit for bit (``bench/compare.py``).

``--trace 0`` reports the cell's end-to-end metrics, taken on the host
clock. ``--trace 1`` traces the window with the JAX profiler and reports
the cell's per-layer metrics, each read by ``bench/metrics/<name>.py``
from the device trace (``bench/devtrace.py``), with the busy and window
seconds and a breakdown of device ops and idle gaps.

Off a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result; it never falls back to the CPU, the interpreter or
the XLA engine. The last line of standard output is the result, a JSON
object; the numbers compared, each with its limit, are the last lines of
standard error and the last key of the result.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cells, compare, devtrace, reference, roofline  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The machine lacks the TPU chips a cell asks for."""


def chip_devices(n: int) -> list:
    """The machine's TPU devices, at least ``n`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX platform is {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chips, found {len(devs)}")
    return devs


def require_native(plans) -> None:
    """Every bucket's kernel went through Mosaic: hi/lo int32 clocks under
    a VMEM budget from the device table (interpret mode plans neither)."""
    if not plans:
        raise RuntimeError("the Pallas kernel did not run natively: no VMEM "
                           "plan was recorded")
    for plan in plans:
        if plan["representation"] != "i32pair" or plan["budget"] is None:
            raise RuntimeError(f"the Pallas kernel did not run natively: "
                               f"VMEM plan {plan}")


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory in the checkout (the path is part of
    the cache key, so it never moves). Every program is cached, however
    quick its compile, so a second run compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_compile_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


class CompileCounter:
    """Counts XLA backend compiles (``jax.monitoring`` duration events)."""

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1


class Sample:
    """``k`` replicas of the window drawn from the seed: stretch ``i`` of
    the batch axis (rows ``[i*B/k, (i+1)*B/k)``) gives one row, drawn once,
    and the sweep it is read from is drawn uniformly over every sweep of
    the window (a reservoir of one per stretch)."""

    def __init__(self, seed: int, k: int, n_rows: int):
        self.rng = random.Random(seed)
        k = min(k, n_rows)
        self.rows = [self.rng.randrange(i * n_rows // k,
                                        (i + 1) * n_rows // k)
                     for i in range(k)]
        self.kept: list = [None] * k
        self.seen = 0

    def offer(self, sweep: int, results, n_seeds: int) -> None:
        self.seen += 1
        for i, row in enumerate(self.rows):
            if self.rng.randrange(self.seen) == 0:
                c, s = divmod(row, n_seeds)
                self.kept[i] = (sweep, c, s,
                                compare.replica_fields(results[c], s))


@dataclass
class Window:
    setup_s: float = 0.0
    span_s: float = 0.0
    call_s: list = field(default_factory=list)
    failed: int = 0
    compiles: int = 0


@dataclass
class Reading:
    """What a per-layer reader (``bench/metrics/<name>.py``) is given."""
    cell: cells.Cell
    summary: dict
    kernels: dict
    peaks: dict
    device_ids: list
    replica_events: int
    kernel_bytes: int


def percentile(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


#: the end-to-end metrics, all on the host clock
E2E = {
    "replica_events_per_s":
        lambda cell, w: (len(w.call_s) - w.failed) * cell.replicas
        * cell.n_events / w.span_s,
    "sweep_p95_s": lambda cell, w: percentile(w.call_s, 95),
    "setup_s": lambda cell, w: w.setup_s,
}


def cell_metrics(bench: dict, name: str) -> tuple[list, list]:
    """The end-to-end and per-layer metric entries that ``name`` reports."""
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moves = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moves)]
    return e2e, layer


def load_reader(name: str, root: Path = ROOT):
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_sample(cell: cells.Cell, seed: int, sample: Sample) -> list[str]:
    """Run the reference on every sampled replica; every mismatch."""
    bad = []
    pts = cell.points
    cfg = cell.config
    for kept in sample.kept:
        if kept is None:
            bad.append("a sampled replica was never produced")
            continue
        sweep, c, s, got = kept
        rs = cells.point_seed(seed, sweep, c, len(pts), cell.n_seeds) + s
        want = reference.simulate(pts[c], rs, cell.n_events,
                                  cfg["cost_model"], cfg["lat_samples"],
                                  precision=cfg["precision"])
        bad += [f"sweep {sweep} point {c} seed {rs}: {m}"
                for m in compare.mismatches(got, want)]
    return bad


def run_window(cell, seed, seconds, sweep_kw, sample, counter,
               annotate) -> Window:
    from repro.core import batch
    w = Window()
    c0 = counter.n
    j = 1
    t_start = time.perf_counter()
    w.setup_s = t_start - _T0
    with annotate(devtrace.WINDOW):
        while True:
            specs = cells.workload_specs(cell, seed, j)
            t0 = time.perf_counter()
            try:
                with annotate(devtrace.SWEEP):
                    res = batch.sweep(specs, cell.n_seeds, cell.n_events,
                                      **sweep_kw)
            except Exception:   # a failed call is counted, not fatal
                traceback.print_exc()
                w.failed += 1
                res = None
            t1 = time.perf_counter()
            w.call_s.append(t1 - t0)
            if res is not None:
                sample.offer(j, res, cell.n_seeds)
            j += 1
            if t1 - t_start >= seconds:
                break
    w.span_s = t1 - t_start
    w.compiles = counter.n - c0
    return w


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = cells.load_benchmark(root)
    cell = cells.load_cell(args.workload, root)
    try:
        devs = chip_devices(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    import jax
    from repro.core import batch
    cache_dir = enable_compile_cache(root)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    dev = devs[0]
    used = devs[:cell.devices] if cell.devices else devs[:1]
    sweep_kw = {"backend": "pallas"}
    if cell.devices:
        sweep_kw.update(devices=used, chunk=cell.chunk)
    print(json.dumps({"cell": cell.name, "device_kind": dev.device_kind,
                      "devices": len(devs), "jax": jax.__version__,
                      "compile_cache": cache_dir}), file=sys.stderr)

    # set-up: the window's exact shapes (n_events and R key the compile)
    batch.reset_exec_stats()
    batch.sweep(cells.workload_specs(cell, args.seed, 0), cell.n_seeds,
                cell.n_events, **sweep_kw)
    require_native(batch.exec_stats()["vmem_plans"])

    sample = Sample(args.seed, cell.check_replicas, cell.replicas)
    annotate = contextlib.nullcontext if not args.trace else \
        jax.profiler.TraceAnnotation
    log_dir = None
    if args.trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        w = run_window(cell, args.seed, args.seconds, sweep_kw, sample,
                       counter, annotate)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    summary = None
    if args.trace:
        summary = devtrace.summarize(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
    stats = [d.memory_stats() or {} for d in used]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    batch_plans = batch.exec_stats()["vmem_plans"]
    print(f"bench: {len(w.call_s)} sweeps in {w.span_s} s, backend "
          f"compiles inside the window: {w.compiles}", file=sys.stderr)

    bad = check_sample(cell, args.seed, sample)
    for m in bad[:20]:
        print(f"bench: mismatch: {m}", file=sys.stderr)

    e2e, layer = cell_metrics(bench, cell.name)
    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {}
    if not args.trace:
        for m in e2e:
            metrics[m["name"]] = {"value": E2E[m["name"]](cell, w),
                                  "unit": m["unit"]}
    else:
        kernels = json.loads((Path(root) / "bench" / "kernels.json")
                             .read_text())
        if not devtrace.op_count(summary, kernels["event_loop"]["pattern"]):
            print("bench: no event-loop kernel event in the device trace",
                  file=sys.stderr)
            return 1
        done = len(w.call_s) - w.failed
        ctx = Reading(
            cell=cell, summary=summary, kernels=kernels,
            peaks=roofline.peaks(dev.device_kind, Path(root) / "bench"
                                 / "peaks.json"),
            device_ids=[str(d.id) for d in used],
            replica_events=done * cell.replicas * cell.n_events,
            kernel_bytes=done * roofline.sweep_bytes(
                cell.points, cell.n_seeds, cell.n_events,
                cell.config["lat_samples"]))
        for m in layer:
            v = load_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        ids = [i for i in ctx.device_ids if i in summary["devices"]]
        win = devtrace.window_ns(summary)
        device["busy_s"] = sum(devtrace.busy_ns(summary, i)
                               for i in ids) / max(len(ids), 1) / 1e9
        device["window_s"] = win / 1e9
        result["breakdown"] = {
            "device_ops": devtrace.top_ops(summary),
            "idle_gaps": devtrace.idle_gaps(summary, ids[0]) if ids else []}
    compared = {"mismatched_fields": {"value": len(bad), "limit": 0},
                "failed_sweeps": {"value": w.failed, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    print(json.dumps({"vmem_plans": batch_plans}), file=sys.stderr)
    for name, c in compared.items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    out = {"correct": correct, "attempted": len(w.call_s),
           "failed": w.failed, "metrics": metrics, "device": device,
           **result, "compared": compared}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
