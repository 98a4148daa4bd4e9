"""``batch.sweep``'s host stages as profiler spans.

A small sweep run under ``jax.profiler.trace`` and read back with
``ProfileData`` yields the six ``batch.*`` spans, nested as the module
docstring says and carrying their metadata, on the unsharded path and on
the sharded path over 4 virtual CPU devices (one ``batch.dispatch`` per
superchunk). Tracing changes no result: a sweep under the profiler
equals the same sweep without it, bit for bit.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np

from repro.core import batch
from repro.core.sim import LAT_SAMPLES
from repro.workloads import Workload

ROOT = Path(__file__).resolve().parents[1]
SPANS = (batch.SPAN_SWEEP, batch.SPAN_LOWER, batch.SPAN_ASSEMBLE,
         batch.SPAN_DISPATCH, batch.SPAN_FETCH, batch.SPAN_AGGREGATE)
LEAVES = SPANS[1:]
FIELDS = ("seeds", "ops", "sim_ns", "throughput_mops", "lat_ns",
          "per_thread_ops", "reacquires", "passes")


def workloads(n_alock: int = 2, mcs: bool = True) -> list[Workload]:
    ws = [Workload("alock", 2, 2, 4, locality=0.9 - 0.1 * i, seed=1 + 40 * i)
          for i in range(n_alock)]
    if mcs:
        ws.append(Workload("mcs", 2, 3, 4, locality=0.8, seed=500))
    return ws


def traced_sweeps(calls, **kw):
    """Run ``batch.sweep(*args, **kw)`` for each ``args`` of ``calls``
    under one profiler session; the results and every ``batch.*`` span as
    ``(name, start_ns, end_ns, metadata)``, in start order."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d, profiler_options=opts):
            res = [batch.sweep(*args, **kw) for args in calls]
        (pb,) = Path(d).rglob("*.xplane.pb")
        pd = ProfileData.from_file(str(pb))
    spans = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
              dict(e.stats))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name in SPANS]
    return res, sorted(spans, key=lambda s: (s[1], -s[2]))


def same_results(a, b) -> bool:
    return all(np.array_equal(getattr(x, f), getattr(y, f))
               and getattr(x, f).dtype == getattr(y, f).dtype
               for x, y in zip(a, b, strict=True) for f in FIELDS)


def inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def check_call(spans, n_configs, n_seeds, n_events, bucket_rows):
    """One call's spans: their nesting, order and metadata. Returns the
    call's ordinal and its dispatch spans."""
    (sw,) = [s for s in spans if s[0] == batch.SPAN_SWEEP]
    sid = sw[3]["sweep"]
    assert sw[3] == {"sweep": sid, "configs": n_configs, "seeds": n_seeds,
                     "events": n_events, "buckets": len(bucket_rows)}
    assert all(s[3]["sweep"] == sid and inside(s, sw) for s in spans)
    leaves = [s for s in spans if s[0] in LEAVES]
    for a, b in zip(leaves, leaves[1:]):
        assert a[2] <= b[1], (a, b)          # the leaves never overlap
    (lw,) = [s for s in spans if s[0] == batch.SPAN_LOWER]
    assert lw[3] == {"sweep": sid} and lw is leaves[0]
    dispatches = []
    for b, rows in enumerate(bucket_rows):
        mine = [s for s in leaves if s[3].get("bucket") == b]
        names = [s[0] for s in mine]
        assert names[0] == batch.SPAN_ASSEMBLE
        assert names[-2:] == [batch.SPAN_FETCH, batch.SPAN_AGGREGATE]
        assert set(names[1:-2]) == {batch.SPAN_DISPATCH}
        asm, fetch, agg = mine[0], mine[-2], mine[-1]
        assert asm[3] == {"sweep": sid, "bucket": b, "rows": rows,
                          "phases": 1}
        assert agg[3] == {"sweep": sid, "bucket": b}
        assert set(fetch[3]) == {"sweep", "bucket", "bytes"}
        # the fetched outputs hold at least every replica's latency ring
        assert fetch[3]["bytes"] >= rows * LAT_SAMPLES * 4
        for d in mine[1:-2]:
            assert set(d[3]) == {"sweep", "bucket", "rows", "bytes",
                                 "new_shape"}
            # every operand leaf has the dispatch's rows as leading axis
            assert d[3]["bytes"] > 0 and d[3]["bytes"] % d[3]["rows"] == 0
            assert d[3]["new_shape"] in (0, 1)
        dispatches.append(mine[1:-2])
    return sid, dispatches


def test_unsharded_sweep_spans_and_metadata():
    # an event count of this test's own, so the first call's shapes are
    # new to the process and the second call's are not
    ws, S, E = workloads(), 2, 313
    res, spans = traced_sweeps([(ws, S, E), (ws, S, E)], backend="pallas")
    assert {s[0] for s in spans} == set(SPANS)
    assert same_results(res[0], res[1])
    calls = [[s for s in spans if s[3]["sweep"] == sid]
             for sid in sorted({s[3]["sweep"] for s in spans})]
    assert len(calls) == 2
    sids, news = [], []
    for call in calls:
        sid, dispatches = check_call(call, 3, S, E, [2 * S, S])
        sids.append(sid)
        assert [len(d) for d in dispatches] == [1, 1]
        assert [d[0][3]["rows"] for d in dispatches] == [2 * S, S]
        news.append([d[0][3]["new_shape"] for d in dispatches])
    assert sids[1] == sids[0] + 1
    assert news == [[1, 1], [0, 0]]


def test_tracing_changes_no_result():
    ws = workloads()
    plain = batch.sweep(ws, 3, 400, backend="pallas")
    (traced,), spans = traced_sweeps([(ws, 3, 400)], backend="pallas")
    assert spans and same_results(plain, traced)
    plain = batch.sweep(ws, 2, 400, backend="xla")
    (traced,), _ = traced_sweeps([(ws, 2, 400)], backend="xla")
    assert same_results(plain, traced)


SHARDED = """
import json, sys
import jax
sys.path.insert(0, {tests!r})
import test_batch_spans as t
from repro.core import batch
ws = t.workloads(3, mcs=False)
plain = batch.sweep(ws, 8, 300, backend="pallas", devices=jax.devices(),
                    chunk=2)
(res,), spans = t.traced_sweeps([(ws, 8, 300)], backend="pallas",
                                devices=jax.devices(), chunk=2)
sid, dispatches = t.check_call(spans, 3, 8, 300, [24])
print(json.dumps({{"devices": len(jax.devices()),
                  "same": t.same_results(plain, res),
                  "dispatch_rows": [d[3]["rows"] for d in dispatches[0]]}}))
"""


def test_sharded_sweep_has_one_dispatch_span_per_superchunk():
    """24 rows over 4 devices at ``chunk`` 2: units of 8 rows, 3 units,
    superchunks of 2 and 1 units, so two dispatch spans."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "-c", SHARDED.format(tests=str(ROOT / "tests"))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"devices": 4, "same": True, "dispatch_rows": [16, 8]}
