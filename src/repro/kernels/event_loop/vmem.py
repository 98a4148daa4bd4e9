"""VMEM budget planner for the event-loop kernel (pure python, no JAX).

The kernel keeps *every* per-replica buffer VMEM-resident for the whole
``n_events`` run, so its footprint per grid step is a closed-form function
of ``(tile, ev_chunk, T, N, K, P)``; the latency ring is
built outside the kernel, so ``lat_samples`` costs no VMEM. On real TPU
an oversized tile dies inside Mosaic as an opaque VMEM-exhaustion error;
this planner computes the byte table up front, **deterministically
shrinks the replica tile** (halving) until the configured budget fits,
and raises an actionable ``ValueError`` when even the smallest legal
tile cannot fit — never a silent wrong answer.

Byte formula (one grid step = one replica tile; int32/float32 = 4 bytes,
clocks = 8 bytes, as an (hi, lo) pair of i32 buffers):

  streamed inputs   u1/r2/r3: ``3 * tile*ev_chunk*4``, **x2** for the
                    pipeline double-buffer along the sequential event axis
  workload rows     edges/think ``tile*P*4`` each; locality/active
                    ``tile*P*T*4`` each; b_init ``tile*P*2*4``; cost_rows
                    ``tile*P*8*4``; node_mult ``tile*P*N*4``; thread_node
                    ``T*4``; lock_node ``K*4`` (direct layout only)
  outputs           done ``tile*T*4``; per-event latency stream
                    ``tile*ev_chunk*8``, **x2** double-buffered like the
                    draws; lat_n/reacq/npass ``tile*4`` each; t_end
                    ``tile*8``
  scratch           tails/victim ``3 * tile*L*4`` (L lock lanes, below);
                    six per-thread i32 descriptors ``tile*T*4``;
                    ready/op_start ``tile*T*8``; busy ``tile*N*8``; the
                    table layout adds the slots' lock ids and reference
                    counts ``2 * tile*S*4`` and each thread's lock node
                    ``tile*T*4``

Lock layout (``lock_layout``): the lock words sit on L lanes. While K
fits S = T rounded up to 128 lanes, L = K and a lock's lane is its id
(``"direct"``). Above that, L = S: at most T locks are non-idle at once,
so the kernel keeps an active-lock table of S slots (``"table"``) and
its VMEM no longer grows with K.

``ops.plan_for_run`` derives the tile from the bucket's rows B: it
first lets this planner halve the ceiling (``ops.MAX_TILE`` rows, or
``tile=``) until the budget fits, then splits B evenly into the fewest
tiles under that ceiling (``ceil(B / ceil(B / ceiling))`` rows each:
smallest edge pad). The byte table prices the tile the kernel really
runs, and the ragged event loop bounds itself at the true remaining
event count per chunk instead of masking dead steps.

``plan_vmem`` is exercised by ``tests/test_vmem_planner.py`` with no TPU:
the breakdown shapes are checked against the buffers ``ops.run_events``
actually allocates in interpret mode. The chosen plan is recorded via
``note_plan`` and surfaced through ``repro.core.batch.exec_stats()`` and
the ``benchmarks.run`` scenario summaries.

>>> p = plan_vmem(tile=8, ev_chunk=512, T=16, N=4, K=16, P=1,
...               lat_samples=1 << 15)
>>> p.tile, p.shrunk, p.total_bytes == sum(
...     b for _, b in p.breakdown.values())
(8, False, True)
>>> tight = plan_vmem(tile=64, ev_chunk=512, T=16, N=4, K=16, P=1,
...                   lat_samples=1 << 15, budget=256 * 2**10)
>>> tight.requested_tile, tight.tile, tight.shrunk
(64, 8, True)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping

from repro.core.cost_model import N_COST_ROWS

#: Per-TensorCore VMEM by ``jax.Device.device_kind``. Source: JAX's Pallas
#: TPU hardware table (``jax/_src/pallas/mosaic/tpu_info.py``, jax 0.9.0:
#: "TPU v5 lite" -> 128 MiB per core). A device missing here is an error
#: on the native path, never a guessed default.
VMEM_CAPACITY = {"TPU v5 lite": 128 * 2**20}
#: VMEM the compiled kernel needs per priced byte: Mosaic also keeps the
#: loop-carried state (a value copy of the scratch and outputs) and the
#: step's temporaries in VMEM. Compiled for a described v5e at the
#: paper-fig5, hot-key-storm and open-loop-ramp shapes (tile 8), the
#: kernel needed between 2.5x and 2.75x the priced bytes while it carried
#: the latency ring. With the ring outside it, compiled at tiles the
#: planner derives (fig5 8, 24, 72 and 128 rows; fig4 56 and 128; K=1000
#: 72 and 128; open loop 48; storm 16), every one fit a limit of 1.5x
#: its priced bytes, and seven of the ten failed at 1.25x
MOSAIC_FACTOR = 3


def limit_for(device_kind: str) -> int:
    """The ``vmem_limit_bytes`` the compiler gets on ``device_kind``: 3/4
    of the core's VMEM, whatever budget the planner was given."""
    try:
        cap = VMEM_CAPACITY[device_kind]
    except KeyError:
        raise ValueError(
            f"no VMEM capacity known for device kind {device_kind!r} "
            f"(known: {sorted(VMEM_CAPACITY)}); add it to "
            f"vmem.VMEM_CAPACITY with its source") from None
    return cap * 3 // 4


def budget_for(device_kind: str) -> int:
    """The planner's default byte budget on ``device_kind``: the priced
    buffers get ``1 / MOSAIC_FACTOR`` of the compiler's limit."""
    return limit_for(device_kind) // MOSAIC_FACTOR

_I32 = 4
_F32 = 4
#: the lanes of one vreg row: the active-lock table rounds T up to these
_LANES = 128

#: the sequential event axis streams u1/r2/r3 in and the per-event
#: latencies out chunk by chunk; Pallas double-buffers streamed blocks so
#: the next chunk moves during compute
PIPELINE_FACTOR = 2
#: the table entries the pipeline factor applies to — `repro.analysis`'s
#: vmem-consistency rule divides it back out when diffing the table
#: against the traced kernel's buffer bindings (in.u4 is the alock-rw
#: reader/writer coin stream and only present when ``rw=True``)
STREAMED_INPUTS = ("in.u1", "in.r2", "in.r3", "in.u4")
#: the streamed outputs: the per-event latency's hi and lo blocks
STREAMED_OUTPUTS = ("out.lat_stream.hi", "out.lat_stream.lo")


def lock_layout(K: int, T: int) -> tuple[str, int]:
    """The kernel's lock-state layout for K locks and T threads: its name
    and its lanes L.

    ``"direct"`` (L = K, a lock's lane is its id) while K fits S, the
    T threads rounded up to whole 128-lane rows; ``"table"`` (L = S
    slots, claimed and released by the threads) above. The table never
    needs more than T slots: a thread targets one lock at a time.

    >>> lock_layout(100, 80), lock_layout(1000, 80), lock_layout(100, 240)
    (('direct', 100), ('table', 128), ('direct', 100))
    """
    S = -(-T // _LANES) * _LANES
    return ("direct", K) if K <= S else ("table", S)


def _entries(name, shape, itemsize, factor=1):
    n = factor
    for d in shape:
        n *= d
    return (name, (shape, n * itemsize))


def _clock_entries(name, shape, factor=1):
    """A clock's hi and lo i32 buffers — the shapes here must match
    ``ops.run_events``'s allocations exactly."""
    return [_entries(f"{name}.hi", shape, _I32, factor),
            _entries(f"{name}.lo", shape, _I32, factor)]


def buffer_table(tile: int, ev_chunk: int, T: int, N: int, K: int, P: int,
                 R: int = 0, hl: bool = False, rw: bool = False) -> dict:
    """name -> (block shape, bytes) for every VMEM buffer of one grid step.

    Mirrors the ``in_specs`` / ``out_specs`` / ``scratch_shapes`` that
    ``ops.run_events`` builds — ``tests/test_vmem_planner.py`` asserts the
    two stay in sync. ``R > 0`` adds the open-loop request buffers (the
    arrival rows, the per-request wait/sojourn/status outputs and the
    dispatch scratch) in their exact binding positions; ``R == 0`` is the
    closed loop and reproduces the pre-traffic table unchanged. ``rw``
    (alock-rw) adds the streamed reader/writer coin, the per-phase read
    probabilities and the reader-count scratch; ``hl`` (hlock) adds the
    per-node rack row — both in their exact binding positions, and both
    inert for every other algorithm. The lock layout (``lock_layout``)
    sets the lock words' lanes, drops ``in.lock_node`` under the table and
    appends the table's scratch last.
    """
    layout, L = lock_layout(K, T)
    direct = layout == "direct"
    rows: list[tuple] = [
        # streamed draw inputs (STREAMED_INPUTS — double-buffered along
        # the event axis)
        _entries("in.u1", (tile, ev_chunk), _F32, PIPELINE_FACTOR),
        _entries("in.r2", (tile, ev_chunk), _I32, PIPELINE_FACTOR),
        _entries("in.r3", (tile, ev_chunk), _I32, PIPELINE_FACTOR),
        *([_entries("in.u4", (tile, ev_chunk), _F32, PIPELINE_FACTOR)]
          if rw else []),
        # per-phase workload rows (same block every chunk)
        _entries("in.edges", (tile, P), _I32),
        _entries("in.think", (tile, P), _I32),
        _entries("in.locality", (tile, P * T), _F32),
        *([_entries("in.read_frac", (tile, P * T), _F32)] if rw else []),
        _entries("in.active", (tile, P * T), _I32),
        _entries("in.b_init", (tile, P * 2), _I32),
        _entries("in.cost_rows", (tile, P * N_COST_ROWS), _I32),
        _entries("in.node_mult", (tile, P * N), _F32),
        _entries("in.thread_node", (1, T), _I32),
        *([_entries("in.lock_node", (1, K), _I32)] if direct else []),
        *([_entries("in.rack", (tile, N), _I32)] if hl else []),
        # open-loop arrival rows (same block every chunk)
        *([*_clock_entries("in.arr", (tile, R)),
           _entries("in.tok", (tile, R), _I32),
           _entries("in.tokcum", (tile, R), _I32),
           _entries("in.qcap", (tile, R), _I32)] if R else []),
        # outputs (flushed when the replica tile changes, but for the
        # latency stream: STREAMED_OUTPUTS, double-buffered like the draws)
        _entries("out.done", (tile, T), _I32),
        *_clock_entries("out.lat_stream", (tile, ev_chunk),
                        PIPELINE_FACTOR),
        _entries("out.lat_n", (tile, 1), _I32),
        *_clock_entries("out.t_end", (tile, 1)),
        _entries("out.reacq", (tile, 1), _I32),
        _entries("out.npass", (tile, 1), _I32),
        # open-loop per-request outputs
        *([*_clock_entries("out.wq", (tile, R)),
           *_clock_entries("out.soj", (tile, R)),
           _entries("out.rstat", (tile, R), _I32)] if R else []),
        # semantic scratch (int32)
        _entries("scr.tail0", (tile, L), _I32),
        _entries("scr.tail1", (tile, L), _I32),
        _entries("scr.victim", (tile, L), _I32),
        _entries("scr.pc", (tile, T), _I32),
        _entries("scr.budget", (tile, T), _I32),
        _entries("scr.nxt", (tile, T), _I32),
        _entries("scr.prev", (tile, T), _I32),
        # the target lock (direct) or the thread's slot (table)
        _entries("scr.target", (tile, T), _I32),
        _entries("scr.cohort", (tile, T), _I32),
        # alock-rw reader counts (between semantic and clock scratch)
        *([_entries("scr.word", (tile, L), _I32)] if rw else []),
        # clock scratch
        *_clock_entries("scr.ready", (tile, T)),
        *_clock_entries("scr.busy", (tile, N)),
        *_clock_entries("scr.op_start", (tile, T)),
        # open-loop dispatch scratch
        *([_entries("scr.curreq", (tile, T), _I32),
           _entries("scr.arrptr", (tile, 1), _I32),
           _entries("scr.qlen", (tile, 1), _I32)] if R else []),
        # the active-lock table: each slot's lock id and reference count,
        # each thread's lock node
        *([_entries("scr.slot_lock", (tile, L), _I32),
           _entries("scr.slot_count", (tile, L), _I32),
           _entries("scr.lock_node", (tile, T), _I32)] if not direct
          else []),
    ]
    return dict(rows)


@dataclass(frozen=True)
class VmemPlan:
    """The planner's verdict for one ``run_events`` call."""
    requested_tile: int
    tile: int
    ev_chunk: int
    lat_samples: int
    budget: int | None                       # bytes; None = unconstrained
    total_bytes: int
    breakdown: Mapping[str, tuple]           # name -> (shape, bytes)
    rows: int | None = None                  # the call's replicas B
    lock_layout: str = "direct"              # ``lock_layout``'s name
    lock_slots: int | None = None            # its lock lanes L

    @property
    def shrunk(self) -> bool:
        return self.tile != self.requested_tile

    @property
    def tiles(self) -> int | None:
        """The grid's replica tiles for ``rows``: one latency chain each
        per event, run one after another."""
        return None if self.rows is None else -(-self.rows // self.tile)

    @property
    def padded_rows(self) -> int | None:
        """The edge copies that fill the last tile past ``rows``; the
        kernel runs them and the caller slices them off."""
        return (None if self.rows is None
                else self.tiles * self.tile - self.rows)

    def as_dict(self) -> dict:
        """Compact form for ``exec_stats()`` / benchmark JSON rows. The
        clock ``representation`` is always ``"i32pair"``; the key stays
        for the readers that check it."""
        return {
            "requested_tile": self.requested_tile, "tile": self.tile,
            "ev_chunk": self.ev_chunk, "lat_samples": self.lat_samples,
            "representation": "i32pair", "budget": self.budget,
            "total_bytes": self.total_bytes, "shrunk": self.shrunk,
            "rows": self.rows, "tiles": self.tiles,
            "padded_rows": self.padded_rows,
            "lock_layout": self.lock_layout, "lock_slots": self.lock_slots,
        }


def plan_vmem(*, tile: int, ev_chunk: int, T: int, N: int, K: int, P: int,
              lat_samples: int, R: int = 0,
              hl: bool = False, rw: bool = False,
              budget: int | None = None, align: int = 1) -> VmemPlan:
    """Compute the byte table; halve ``tile`` until ``budget`` fits.

    Deterministic: the same arguments always yield the same plan. With
    ``budget=None`` the table is computed but never shrunk (interpret
    mode / host runs have no VMEM ceiling). Halving keeps the tile a
    multiple of ``align`` (8 for Mosaic's sublane tiling) and stops at
    ``min(tile, align)``. Raises ``ValueError`` when even that smallest
    tile exceeds the budget, naming the dominant buffers and the knobs
    that actually help.
    """
    if tile < 1 or ev_chunk < 1:
        raise ValueError(f"tile and ev_chunk must be >= 1, got "
                         f"(tile={tile}, ev_chunk={ev_chunk})")
    if budget is not None and budget < 1:
        raise ValueError(f"vmem budget must be >= 1 byte, got {budget}")
    requested = tile
    t = tile
    while True:
        table = buffer_table(t, ev_chunk, T, N, K, P, R, hl, rw)
        total = sum(b for _, b in table.values())
        if budget is None or total <= budget or t <= align:
            break
        t = max(align, t // 2 // align * align)
    if budget is not None and total > budget:
        top = sorted(table.items(), key=lambda kv: -kv[1][1])[:3]
        detail = ", ".join(f"{name}{shape}={b:,}B"
                           for name, (shape, b) in top)
        raise ValueError(
            f"event-loop kernel cannot fit VMEM budget {budget:,}B even at "
            f"tile={t} (needs {total:,}B; largest buffers: {detail}). Lower "
            f"ev_chunk ({ev_chunk}), or raise the budget "
            f"(run_events(vmem_budget=...)).")
    layout, lanes = lock_layout(K, T)
    return VmemPlan(requested_tile=requested, tile=t, ev_chunk=ev_chunk,
                    lat_samples=lat_samples, budget=budget,
                    total_bytes=total, breakdown=table, lock_layout=layout,
                    lock_slots=lanes)


# -- plan registry (read by batch.exec_stats / benchmarks) ------------------
# Every distinct plan since the last clear, most recently noted last: a
# sweep plans one kernel per shape bucket, and each must be checked.

_PLANS: list[VmemPlan] = []


def note_plan(plan: VmemPlan) -> None:
    if plan in _PLANS:
        _PLANS.remove(plan)
    _PLANS.append(plan)


def last_plan() -> VmemPlan | None:
    return _PLANS[-1] if _PLANS else None


def plans() -> tuple[VmemPlan, ...]:
    return tuple(_PLANS)


def clear_plan() -> None:
    _PLANS.clear()
