"""Lock-primitive microbenchmarks: operation counts + kernel wall time.

Uncontended op counts per Lock()+Unlock() for **every** registered state
machine (measured by stepping ``repro.core.machine``, not assumed).
Table 1's headline — ALock-local issues **0 RDMA ops** — is a *checked*
output: the process exits non-zero if the local path ever issues a
remote op, so a machine regression fails ``benchmarks.run`` instead of
silently changing a printed number. ``hlock`` shares ALock's machine
(the caller derives the cohort from the rack topology) and is checked
to the same local-path claim; ``alock-rw`` is counted on both the
writer path (full ALock protocol + reader drain) and the reader path
(queue bypass).

Kernel wall time: one small bucket through the event-loop Pallas kernel
(interpret mode — the CPU CI stand-in) vs the vmapped XLA oracle via
``batch.sweep``. These are wall-us rows for eyeballing, not gates: the
chip's speed is measured by ``bench/run.py``.
"""
import sys
import time

import jax

from benchmarks.common import EVENTS, emit
from repro.core import machine as mc

#: checked op-count claims: row name -> exact expected remote-op count.
#: ALock/hlock local-cohort acquire+release must be RDMA-free (Table 1).
CHECKED = {"alock.local": 0, "hlock.local": 0,
           "alock-rw.writer.local": 0, "alock-rw.reader.local": 0}


def count_ops(alg, cohort, is_read=False):
    """(remote, local) op counts for one uncontended Lock()+Unlock()."""
    st = mc.initial_state(1)
    step = mc.MACHINES[alg]
    remote = local = guard = 0
    while True:
        if alg == "alock-rw":
            st, op = step(st, 0, cohort, (5, 20), is_read=is_read)
        else:
            st, op = step(st, 0, cohort, (5, 20))
        if op.kind == "remote":
            remote += 1
        elif op.kind == "local":
            local += 1
        guard += 1
        if st.pc[0] == mc.NCS and guard > 1:
            break
        assert guard < 100
    return remote, local


def bench_wall(f, *args, iters=5):
    f(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / iters * 1e6


#: (alg, cohort, is_read, row name) — every machine in mc.MACHINES
OPCOUNT_ROWS = (
    ("alock", 0, False, "alock.local"),
    ("alock", 1, False, "alock.remote"),
    ("hlock", 0, False, "hlock.local"),
    ("hlock", 1, False, "hlock.remote"),
    ("alock-rw", 0, False, "alock-rw.writer.local"),
    ("alock-rw", 1, False, "alock-rw.writer.remote"),
    ("alock-rw", 0, True, "alock-rw.reader.local"),
    ("alock-rw", 1, True, "alock-rw.reader.remote"),
    ("mcs", 1, False, "mcs"),
    ("spinlock", 1, False, "spinlock"),
)


def main() -> None:
    failed = []
    for alg, cohort, is_read, name in OPCOUNT_ROWS:
        r, l = count_ops(alg, cohort, is_read=is_read)
        verdict = ""
        if name in CHECKED:
            ok = r == CHECKED[name]
            verdict = f",checked={'ok' if ok else 'FAIL'}"
            if not ok:
                failed.append(f"{name}: expected {CHECKED[name]} remote "
                              f"ops, measured {r}")
        emit(f"micro.opcount.{name}", 0.0,
             f"remote_ops={r},local_ops={l}{verdict}")

    # event-loop kernel vs the XLA oracle on one small bucket (wall time;
    # interpret mode is the CPU stand-in for the Pallas path)
    from repro.core import batch
    from repro.workloads import Workload
    ev = min(EVENTS, 20_000)
    cfgs = [Workload("alock", 2, 2, 8, locality=0.95)]
    walls = {}
    for backend in ("xla", "pallas"):
        walls[backend] = bench_wall(
            lambda b=backend: batch.sweep(cfgs, n_seeds=1, n_events=ev,
                                          backend=b), iters=2)
        emit(f"micro.kernel.{backend}.ev{ev}", walls[backend],
             f"{ev / walls[backend]:.2f}Mev/s")
    emit("micro.kernel.pallas_over_xla", 0.0,
         f"{walls['xla'] / max(walls['pallas'], 1e-9):.2f}x")

    if failed:
        for msg in failed:
            print(f"# microbench CHECK FAILED: {msg}", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
