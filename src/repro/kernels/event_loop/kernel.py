"""Pallas TPU kernel: the discrete-event simulator's next-event loop.

``sim._run_events`` is a serial argmin+switch ``fori_loop`` lowered through
XLA: every event re-dispatches a chain of gather/scatter/select HLOs against
HBM-resident state. This kernel keeps ALL per-replica state — the semantic
``Sem`` machine (tails/victim/word, per-thread descriptors) and the
``ready``/``busy``/``op_start`` clocks — resident in VMEM for the entire
``n_events`` run: one HBM read and one write per replica, replicas
tiled across the first grid axis.

Layout
  grid = (replica_tiles, event_chunks); the second axis is the innermost
  (sequential) one, so VMEM scratch carries the simulation state from chunk
  to chunk while each chunk streams in its (tile, ev_chunk) slice of the
  precomputed workload draws and streams out its (tile, ev_chunk) slice of
  per-event latencies. The other outputs index-map to the same block for
  every chunk and are only flushed to HBM when the tile changes.

Branch dispatch
  ``sim.sem_step``'s ``lax.switch`` over 14 PC branches is re-expressed as
  masked ``jnp.select`` over the PC classes: per event each replica row
  computes every branch's update and keeps the one selected by its
  thread's PC. Scatters at per-row indices (lock k,
  thread tid/pred/succ, node) are one-hot masked writes.

Clock representation
  Every 64-bit quantity — the ``ready``/``busy``/``op_start`` clocks, the
  per-event latencies and the parked-thread ``never`` sentinel — is a
  hi/lo int32 pair with carry-correct add/sub and lexicographic
  compare/argmin (``i32pair.py``), held in an (hi, lo) pair of refs.
  Mosaic has no 64-bit vector registers, so this is the one
  representation that lowers natively; it runs with x64 entirely off,
  and interpret mode traces the same kernel program as the chip. The
  ``tests/test_event_loop_native_repr.py`` differential suite holds it
  bitwise-equal to the int64 XLA engine.

  The kernel holds no latency ring. Every event writes its latency, or
  -1 when it completes nothing, into lane ``e`` of a ``(tile, ev_chunk)``
  output block streamed along the event axis like the draws, through
  the same aligned 128-lane window and one-hot select ``draw()`` reads
  with; ``ops.py`` builds the ``(B, lat_samples)`` ring from that
  stream outside the kernel. The ring-overflow tests hold it identical
  to the XLA engine.

Randomness + workload operands
  The XLA loop draws from ``jax.random.fold_in(key, i)`` per event. The
  raw draws depend only on (seed, i) — never on simulation state — so
  ``ops.py`` precomputes the stream with the *same* jax.random calls and
  feeds the kernel three streams: the locality uniform (f32), the
  remote-node offset and the phase-resolved within-node Zipf offset. The
  thread-dependent half of the locality draw (``u < locality[phase, tid]``)
  runs here, against the per-phase per-thread locality operand, because
  ``tid`` is the runtime argmin of the ready clocks. Phases are resolved
  per event from the ``edges`` operand (phase = sum(i >= edges) - 1);
  the per-phase ``active`` mask parks downed threads by excluding them
  from the ready-time argmin, ``think_ns[phase]`` replaces the static
  think cost, and the event's cost scalars / ALock budgets / fail-slow
  node multipliers are one-hot phase selections from the
  ``cost_rows (P, 8)`` / ``b_init (P, 2)`` / ``node_mult (P, N)``
  operands (single-phase specs keep the flat row-0 fast path). Per-seed
  results are bitwise-equal to the XLA path, which the tier-1
  equivalence tests assert. The semantic state stays int32 everywhere.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from repro.core import machine as mc
from repro.core.cost_model import N_COST_ROWS
from repro.core.sim import (OP_CS, OP_LOCAL, OP_LOOP, OP_POLL, OP_RDMA,
                            OP_THINK)
from repro.kernels.event_loop import i32pair as p32
from repro.traffic.metrics import COMPLETED, DROPPED, IN_SERVICE

I32 = jnp.int32


def _iota(shape, dim):
    """2D index grid — Mosaic rejects 1D iota, so every index vector in
    the kernel is built broadcasted."""
    return lax.broadcasted_iota(I32, shape, dim)


#: python-int constants (machine PCs, opcodes, dims) are weak-typed: under
#: x64 they widen `jnp.where` branches to int64, which Mosaic cannot lower.
#: Every such constant is pinned at its use site (repro.analysis rule M001).
_I = np.int32


def _any(mask):
    """Row-wise ``jnp.any`` over axis 1 as an i32 max: Mosaic lowers a
    boolean reduction through a float max, which is f64 under x64."""
    return jnp.max(mask.astype(I32), axis=1) > _I(0)


def _select(conds, vals, default):
    """``jnp.select`` semantics (first true condition wins) as a reversed
    ``jnp.where`` chain — jnp.select lowers through an argmax whose index
    dtype is int64 under x64, poisoning the Mosaic kernel jaxpr."""
    acc = default
    for c, v in zip(reversed(conds), reversed(vals)):
        acc = jnp.where(c, v, acc)
    return acc


# Every 64-bit clock is an (hi, lo) int32 pair (module docstring); these
# helpers are the pair forms the ``i32pair`` functions do not cover.


def _read(refs, idx=...):
    """A clock from its (hi, lo) pair of refs."""
    return (refs[0][idx], refs[1][idx])


def _write(refs, v, idx=...):
    refs[0][idx] = v[0]
    refs[1][idx] = v[1]


def _col(v):
    return (v[0][:, None], v[1][:, None])


def _full_m1(shape):
    return p32.pfull(shape, -1)


def _is_never(v):
    return p32.peq(v, p32.NEVER)


def event_loop_kernel(*refs, alg: str, T: int, N: int, K: int, P: int,
                      n_events: int, ev_chunk: int, R: int = 0):
    """One (replica_tile, event_chunk) grid step.

    ``refs`` arrive flat from ``pl.pallas_call`` — 12 inputs (plus the
    open-loop arrival rows when ``R > 0``, the read coin/probability rows
    for ``alock-rw`` and the rack row for ``hlock``), then the outputs and
    scratch, every clock buffer as an (hi, lo) pair of refs — and are
    regrouped here from the static ``R`` / alg flags. ``R == 0`` is the
    closed loop and parses/traces exactly the pre-traffic program (every
    ``if R > 0`` block below is python-level dead code then); likewise the
    ``is_hl`` / ``is_rw`` blocks are dead for every other algorithm, so
    alock/mcs/spinlock trace the exact pre-topology program.

    s_t0/s_t1 are the two cohort tails for alock (and its hlock/alock-rw
    variants); for mcs/spinlock s_t0 is the lock word and s_t1/s_vic stay
    zero (those PCs are unreachable). alock-rw adds an s_word scratch
    holding per-lock reader counts.
    """
    is_hl = alg == "hlock"
    is_rw = alg == "alock-rw"
    # hlock and alock-rw run the full ALock tail/victim/budget machinery;
    # their extra refs (read coin + read_frac row, rack row, reader-count
    # scratch) are python-gated so every other algorithm's ref layout —
    # and traced program — is byte-identical to the pre-topology kernel
    (u1_ref, r2_ref, r3_ref) = refs[:3]
    pos = 3
    if is_rw:
        u4_ref = refs[pos]                  # reader/writer coin stream
        pos += 1
    (edges_ref, think_ref, locp_ref) = refs[pos:pos + 3]
    pos += 3
    if is_rw:
        readf_ref = refs[pos]               # per-phase read probabilities
        pos += 1
    (actp_ref, binit_ref, costs_ref, nmult_ref, tn_ref,
     ln_ref) = refs[pos:pos + 6]
    pos += 6
    if is_hl:
        rack_ref = refs[pos]                # per-node rack ids
        pos += 1
    if R > 0:
        arr_refs = refs[pos:pos + 2]
        tok_ref, tokcum_ref, qcap_ref = refs[pos + 2:pos + 5]
        pos += 5
    rest = refs[pos:]
    done_ref, lat_refs, latn_ref = rest[0], rest[1:3], rest[3]
    tend_refs, (reacq_ref, npass_ref) = rest[4:6], rest[6:8]
    pos = 8
    if R > 0:
        wq_refs, soj_refs, rstat_ref = rest[8:10], rest[10:12], rest[12]
        pos = 13
    scr = rest[pos:]
    (s_t0, s_t1, s_vic, s_pc, s_bud, s_nxt, s_prev, s_tgt, s_coh) = scr[:9]
    pos = 9
    if is_rw:
        s_word = scr[pos]                   # per-lock reader counts
        pos += 1
    ready_refs, busy_refs = scr[pos:pos + 2], scr[pos + 2:pos + 4]
    opst_refs = scr[pos + 4:pos + 6]
    if R > 0:
        s_curreq, s_arrptr, s_qlen = scr[pos + 6:pos + 9]

    is_alock = alg in ("alock", "hlock", "alock-rw")
    is_spin = alg == "spinlock"
    j = pl.program_id(1)
    tile = s_pc.shape[0]
    kpn = K // N

    @pl.when(j == 0)
    def _init():
        # fresh replicas == sim.init_sem + zeroed clocks/accounting
        zrefs = (s_t0, s_t1, s_vic, s_nxt, s_prev, s_tgt, s_coh,
                 done_ref, latn_ref, reacq_ref, npass_ref)
        if is_rw:
            zrefs = zrefs + (s_word,)
        if R > 0:
            zrefs = zrefs + (rstat_ref, s_arrptr, s_qlen)
        for ref in zrefs:
            ref[...] = jnp.zeros(ref.shape, ref.dtype)
        s_pc[...] = jnp.full((tile, T), mc.NCS, I32)
        s_bud[...] = jnp.full((tile, T), -1, I32)
        for crefs, shape in ((ready_refs, (tile, T)), (busy_refs, (tile, N)),
                             (opst_refs, (tile, T))):
            _write(crefs, p32.pzeros(shape))
        if R > 0:
            s_curreq[...] = jnp.full((tile, T), -1, I32)
            _write(wq_refs, _full_m1((tile, R)))
            _write(soj_refs, _full_m1((tile, R)))

    # the latency block streams out once per chunk: -1 (no completion)
    # in every lane an event does not overwrite, the ragged tail included
    _write(lat_refs, _full_m1((tile, ev_chunk)))

    edges = edges_ref[...].astype(I32)              # (tile, P)
    think = think_ref[...].astype(I32)              # (tile, P)
    # per-phase payloads arrive flattened (tile, P*…) and stay flat:
    # Mosaic cannot reshape them to (tile, P, …), so phase rows are
    # selected from static lane slices (``phase_row``)
    locp = locp_ref[...]                            # (tile, P*T) f32
    actp = actp_ref[...].astype(I32)                # (tile, P*T)
    binitp = binit_ref[...].astype(I32)             # (tile, P*2)
    cstp = costs_ref[...].astype(I32)               # (tile, P*8)
    nmp = nmult_ref[...]                            # (tile, P*N) f32
    tn = jnp.broadcast_to(tn_ref[...].astype(I32), (tile, T))
    ln = jnp.broadcast_to(ln_ref[...].astype(I32), (tile, K))
    if is_rw:
        readfp = readf_ref[...]                     # (tile, P*T) f32
    if is_hl:
        rk = rack_ref[...].astype(I32)              # (tile, N) rack ids
    if R > 0:
        # open-loop arrival rows: times (clock), token admit mask +
        # exclusive prefix count, per-request queue bound (all (tile, R))
        arr = _read(arr_refs)
        tok = tok_ref[...].astype(I32)
        tokcum = tokcum_ref[...].astype(I32)
        qcap = qcap_ref[...].astype(I32)
        rio = _iota((tile, R), 1)

    tids = _iota((tile, T), 1)
    kio = _iota((tile, K), 1)
    nio = _iota((tile, N), 1)
    pio = _iota((tile, P), 1)
    # event e's draw is read from an aligned `win`-lane window of the
    # streamed (tile, ev_chunk) block, then picked by a one-hot lane
    # select: Mosaic has no dynamic_slice of a loaded vector, and a ref
    # slice at a dynamic lane offset must be provably 128-aligned
    win = 128 if ev_chunk % 128 == 0 else ev_chunk
    wio = _iota((tile, win), 1)

    def gat_t(arr, idx):
        """(tile, T) gathered at per-row thread idx -> (tile,). The sum
        dtype is pinned: under x64 ``jnp.sum(int32)`` would widen to the
        default int and poison every downstream carry dtype."""
        return jnp.sum(jnp.where(tids == idx[:, None], arr,
                                 arr.dtype.type(0)), axis=1,
                       dtype=arr.dtype)

    def gat_k(arr, idx):
        return jnp.sum(jnp.where(kio == idx[:, None], arr,
                                 arr.dtype.type(0)), axis=1,
                       dtype=arr.dtype)

    def window(e):
        """The aligned ``win``-lane window of a streamed block holding
        event ``e``, and the one-hot mask of ``e``'s lane in it."""
        base = pl.multiple_of(lax.div(e, _I(win)) * _I(win), win)
        return (slice(None), pl.ds(base, win)), wio == lax.rem(e, _I(win))

    def draw(ref, e):
        """Event ``e``'s (tile,) column of a streamed draw block."""
        w, lane = window(e)
        blk = ref[w]
        return jnp.sum(jnp.where(lane, blk, blk.dtype.type(0)), axis=1,
                       dtype=blk.dtype)

    def emit(refs, e, m, v):
        """Write clock column ``v`` into event ``e``'s lane of a streamed
        output block, in the rows where ``m`` holds."""
        w, lane = window(e)
        _write(refs, p32.pwhere(lane & m[:, None], _col(v), _read(refs, w)),
               w)

    def phase_row(flat, ph, w):
        """Phase ``ph``'s (tile, w) row of a flat (tile, P*w) block: a
        one-hot select over static lane slices (exactly the one-hot sum
        it replaces — one phase matches, the rest contribute zero)."""
        row = jnp.zeros((tile, w), flat.dtype)
        for q in range(P):
            row = jnp.where((ph == _I(q))[:, None],
                            flat[:, q * w:(q + 1) * w], row)
        return row

    state = (s_t0[...], s_t1[...], s_vic[...], s_pc[...], s_bud[...],
             s_nxt[...], s_prev[...], s_tgt[...], s_coh[...],
             _read(ready_refs), _read(busy_refs), _read(opst_refs),
             done_ref[...], latn_ref[...][:, 0],
             reacq_ref[...][:, 0], npass_ref[...][:, 0])
    if R > 0:
        def gat_r(arr, idx):
            return jnp.sum(jnp.where(rio == idx[:, None], arr,
                                     arr.dtype.type(0)), axis=1,
                           dtype=arr.dtype)

        state = state + (rstat_ref[...], s_curreq[...],
                         s_arrptr[...][:, 0], s_qlen[...][:, 0],
                         _read(wq_refs), _read(soj_refs))
    if is_rw:
        # reader counts ride at the tail of the carry so every existing
        # unpack position stays fixed for the other algorithms
        state = state + (s_word[...],)

    def step(e, st):
        if is_rw:
            st_wrd = st[-1]
            wrd = st_wrd
            st = st[:-1]
        if R > 0:
            (t0, t1, vic, pc, bud, nxt, prv, tgt, coh, ready, busy, opst,
             done, latn, reacq, npass,
             rstat, curreq, arrptr, qlen, wq, soj) = st
            sem_old = (t0, t1, vic, pc, bud, nxt, prv, tgt, coh)
        else:
            (t0, t1, vic, pc, bud, nxt, prv, tgt, coh, ready, busy, opst,
             done, latn, reacq, npass) = st

        # -- phase resolve (pure function of the global event index) -------
        gi = j * ev_chunk + e
        if P > 1:
            ph = jnp.sum((gi >= edges).astype(I32), axis=1,
                         dtype=I32) - 1              # (tile,)
            ohP = pio == ph[:, None]
            act_row = phase_row(actp, ph, T)
            loc_row = phase_row(locp, ph, T)
            think_e = jnp.sum(jnp.where(ohP, think, _I(0)), axis=1,
                              dtype=I32)
            # phase-indexed cost rows + ALock budgets
            binit = phase_row(binitp, ph, 2)             # (tile, 2)
            cst = phase_row(cstp, ph, N_COST_ROWS)       # (tile, 8)
            nm_row = phase_row(nmp, ph, N)               # (tile, N)
            if is_rw:
                rf_row = phase_row(readfp, ph, T)        # (tile, T)

            # phase boundary: rejoining threads resume from the cluster's
            # current clock (mirror of the XLA loop's rejoin bump)
            was_act = phase_row(actp, jnp.maximum(ph - _I(1), _I(0)), T)
            rejoin = (_any(gi == edges)[:, None]
                      & (act_row != 0) & (was_act == 0))
            cont_min = p32.reduce_min_masked(ready, (act_row != 0)
                                             & (was_act != 0))
            now_min = p32.pwhere(_is_never(cont_min),
                                 p32.reduce_min_masked(ready, act_row != 0),
                                 cont_min)
            ready = p32.pwhere(rejoin, p32.pmax2(ready, _col(now_min)),
                               ready)
            actm = act_row != 0
        else:
            # single phase: the flat PR-2 hot path, no phase machinery
            # (lowering guarantees P == 1 operands are all-active)
            loc_row = locp
            think_e = think[:, 0]
            binit = binitp
            cst = cstp
            nm_row = nmp
            if is_rw:
                rf_row = readfp
            actm = None
        if R > 0:
            # idle threads (NCS, no request bound) wake at the earliest
            # available arrival instead of re-arming; busy threads keep
            # their own clocks (mirror of sim._run_events' elig)
            pend = (pc == mc.NCS) & (curreq < _I(0))
            avail = (rstat == _I(0)) & (tok == _I(1))
            next_arr = p32.reduce_min_masked(arr, avail)
            elig = p32.pwhere(pend, p32.pmax2(ready, _col(next_arr)), ready)
        else:
            elig = ready
        tid = p32.argmin_masked(elig, actm)
        ohT = tids == tid[:, None]
        now = p32.pgather(ohT, elig)
        me = tid + 1
        p = gat_t(pc, tid)
        tg = gat_t(tgt, tid)
        ch = gat_t(coh, tid)
        bd = gat_t(bud, tid)
        nx = gat_t(nxt, tid)
        pv = gat_t(prv, tid)
        ohK = kio == tg[:, None]
        mynode = gat_t(tn, tid)

        # -- workload draw (precomputed stream; NCS branch consumes it) ----
        u1e = draw(u1_ref, e)
        r2e = draw(r2_ref, e)
        r3e = draw(r3_ref, e)
        # thread-dependent half of the locality draw: same f32 compare as
        # the XLA loop's uniform(k1) < locality[ph, tid]
        loc_t = jnp.sum(jnp.where(ohT, loc_row, np.float32(0)), axis=1,
                        dtype=jnp.float32)
        ge = u1e < loc_t
        other = (mynode + _I(1) + r2e) % _I(N)
        node_w = jnp.where(ge, mynode, other).astype(I32)
        new_t = node_w * kpn + r3e
        if is_hl:
            # hierarchical cohort: LOCAL means same *rack*, not same node
            # (one-hot rack gathers of the XLA loop's wl.rack[] compares)
            rk_w = jnp.sum(jnp.where(nio == node_w[:, None], rk, _I(0)),
                           axis=1, dtype=I32)
            rk_me = jnp.sum(jnp.where(nio == mynode[:, None], rk, _I(0)),
                            axis=1, dtype=I32)
            new_c = (rk_w != rk_me).astype(I32)
        else:
            new_c = (node_w != mynode).astype(I32)
        if is_rw:
            # reader/writer coin: same f32 compare as the XLA loop's
            # uniform(k4) < read_frac[ph, tid]
            u4e = draw(u4_ref, e)
            rf_t = jnp.sum(jnp.where(ohT, rf_row, np.float32(0)), axis=1,
                           dtype=jnp.float32)
            new_r = u4e < rf_t

        if R > 0:
            live = jnp.logical_not(_is_never(now))
            pend_tid = jnp.sum(jnp.where(ohT, pend.astype(I32), _I(0)),
                               axis=1, dtype=I32) == _I(1)
            # -- arrival ingestion: every request with arr <= now either
            # joins the wait queue or drops (token reject / queue full);
            # `rank` orders token-admitted newcomers for exact tail drop.
            # Integer-exact, so the one-hot forms here agree bitwise with
            # the XLA loop's dynamic gathers/scatters.
            arrived = p32.ple(arr, _col(now))
            cnt_now = jnp.where(
                live, jnp.sum(arrived.astype(I32), axis=1, dtype=I32),
                arrptr)
            newly = ((rio >= arrptr[:, None])
                     & (rio < cnt_now[:, None]))
            rank = tokcum - gat_r(tokcum, arrptr)[:, None]
            join = (newly & (tok == _I(1))
                    & (rank < qcap - qlen[:, None]))
            rstat = jnp.where(newly & ~join, _I(DROPPED), rstat)
            qlen = qlen + jnp.sum(join.astype(I32), axis=1, dtype=I32)
            arrptr = cnt_now
            # -- dispatch: an idle selected thread takes the FIFO head --
            queued = (rstat == _I(0)) & (rio < arrptr[:, None])
            head = jnp.min(jnp.where(queued, rio,
                                     _I(np.iinfo(np.int32).max)), axis=1)
            do_disp = live & pend_tid & _any(queued)
            hd = jnp.minimum(head, _I(R - 1))
            ohR = rio == hd[:, None]
            dm = do_disp[:, None]
            rstat = jnp.where(ohR & dm, _I(IN_SERVICE), rstat)
            curreq = jnp.where(ohT & dm, hd[:, None], curreq)
            wqv = p32.psub(now, p32.pgather(ohR, arr))
            wq = p32.pwhere(ohR & dm, _col(wqv), wq)
            qlen = qlen - do_disp.astype(I32)
            # an idle thread with nothing to take makes no machine step
            step_ok = live & (~pend_tid | do_disp)

        # -- PC class masks (exactly one true per row) ---------------------
        is_ncs = p == mc.NCS
        is_swap = p == mc.SWAP
        is_wn = p == mc.WRITE_NEXT
        is_sb = p == mc.SPIN_BUDGET
        is_sv = p == mc.SET_VICTIM
        is_svr = p == mc.SET_VICTIM_R
        is_pw = p == mc.PET_WAIT
        is_pwr = p == mc.PET_WAIT_R
        is_cs = p == mc.CS
        is_rc = p == mc.REL_CAS
        is_sn = p == mc.SPIN_NEXT
        is_ps = p == mc.PASS
        is_slc = p == mc.SL_CAS
        is_slr = p == mc.SL_REL
        if is_rw:
            is_rdt = p == mc.RD_TRY
            is_rdc = p == mc.RD_CS
            is_rdr = p == mc.RD_REL
            is_wd = p == mc.WR_DRAIN

        Bc = jnp.where(ch == 0, binit[:, 0], binit[:, 1])
        tail_c = jnp.where(ch == 0, gat_k(t0, tg), gat_k(t1, tg))
        tail_o = jnp.where(ch == 0, gat_k(t1, tg), gat_k(t0, tg))
        wv = gat_k(t0, tg)            # lock word at target (mcs/spinlock)
        vk = gat_k(vic, tg)
        pred = pv - 1
        succ = nx - 1
        oh_pred = tids == pred[:, None]
        oh_succ = tids == succ[:, None]
        has_succ = nx != 0
        prev_val = tail_c if is_alock else wv
        empty = prev_val == 0
        solo = (tail_c if is_alock else wv) == me
        free = wv == 0
        can = (tail_o == 0) | (vk != ch)
        newb = (bd - 1) if is_alock else jnp.ones_like(bd)
        if is_rw:
            # reader entry with writer preference: both cohort tails empty
            # (mirror of machine.f_rd_try); drain waits for the reader
            # count at the target to reach zero
            can_rd = (tail_c == 0) & (tail_o == 0)
            wdv = gat_k(wrd, tg)

        # -- lock word / tails / victim ------------------------------------
        if is_alock:
            m0 = (is_swap & (ch == 0))[:, None] & ohK
            m1 = (is_swap & (ch == 1))[:, None] & ohK
            t0 = jnp.where(m0, me[:, None], t0)
            t1 = jnp.where(m1, me[:, None], t1)
            r0 = (is_rc & solo & (ch == 0))[:, None] & ohK
            r1 = (is_rc & solo & (ch == 1))[:, None] & ohK
            t0 = jnp.where(r0, _I(0), t0)
            t1 = jnp.where(r1, _I(0), t1)
            vmask = (is_sv | is_svr)[:, None] & ohK
            vic = jnp.where(vmask, ch[:, None], vic)
        else:
            t0 = jnp.where(is_swap[:, None] & ohK, me[:, None], t0)
            t0 = jnp.where((is_rc & solo)[:, None] & ohK, _I(0), t0)
            t0 = jnp.where((is_slc & free)[:, None] & ohK, me[:, None], t0)
            t0 = jnp.where(is_slr[:, None] & ohK, _I(0), t0)
        if is_rw:
            # reader count at the target: +1 on a successful RD_TRY, -1 on
            # RD_REL (one-hot forms of word.at[k].add)
            wrd = wrd + jnp.where((is_rdt & can_rd)[:, None] & ohK, _I(1),
                                  _I(0))
            wrd = wrd - jnp.where(is_rdr[:, None] & ohK, _I(1), _I(0))

        # -- per-thread descriptors ----------------------------------------
        prv = jnp.where(is_swap[:, None] & ohT, prev_val[:, None], prv)
        nxt = jnp.where(is_ncs[:, None] & ohT, _I(0), nxt)
        nxt = jnp.where(is_wn[:, None] & oh_pred, me[:, None], nxt)
        bud_tid_val = _select([is_ncs, is_swap, is_pwr],
                              [jnp.full_like(bd, -1), Bc, Bc], bd)
        swap_bud = (is_swap & empty) if is_alock else jnp.zeros_like(is_swap)
        bud_tid_m = is_ncs | swap_bud | (is_pwr & can)
        bud = jnp.where(bud_tid_m[:, None] & ohT, bud_tid_val[:, None], bud)
        bud = jnp.where(is_ps[:, None] & oh_succ, newb[:, None], bud)
        tgt = jnp.where(is_ncs[:, None] & ohT, new_t[:, None], tgt)
        coh = jnp.where(is_ncs[:, None] & ohT, new_c[:, None], coh)

        # -- next PC (the lax.switch, as one select over PC classes) -------
        # a writer's every CS entry detours through the reader drain (rw)
        ecs = mc.WR_DRAIN if is_rw else mc.CS
        if is_rw:
            first_val = jnp.where(new_r, _I(mc.RD_TRY), _I(mc.SWAP))
        else:
            first_val = jnp.full_like(p, mc.SL_CAS if is_spin else mc.SWAP)
        if is_alock:
            pc_swap = jnp.where(empty, _I(mc.SET_VICTIM), _I(mc.WRITE_NEXT))
            pc_sb = jnp.where(bd == -1, _I(mc.SPIN_BUDGET),
                              jnp.where(bd == 0, _I(mc.SET_VICTIM_R),
                                        _I(ecs)))
        else:
            pc_swap = jnp.where(empty, _I(mc.CS), _I(mc.WRITE_NEXT))
            pc_sb = jnp.where(bd == -1, _I(mc.SPIN_BUDGET), _I(mc.CS))
        pc_conds = [is_ncs, is_swap, is_wn, is_sb, is_sv, is_svr, is_pw,
                    is_pwr, is_cs, is_rc, is_sn, is_ps, is_slc, is_slr]
        pc_vals = [first_val, pc_swap,
                   jnp.full_like(p, mc.SPIN_BUDGET), pc_sb,
                   jnp.full_like(p, mc.PET_WAIT),
                   jnp.full_like(p, mc.PET_WAIT_R),
                   jnp.where(can, _I(ecs), _I(mc.PET_WAIT)),
                   jnp.where(can, _I(ecs), _I(mc.PET_WAIT_R)),
                   jnp.full_like(p, mc.SL_REL if is_spin else mc.REL_CAS),
                   jnp.where(solo, _I(mc.NCS), _I(mc.SPIN_NEXT)),
                   jnp.where(has_succ, _I(mc.PASS), _I(mc.SPIN_NEXT)),
                   jnp.full_like(p, mc.NCS),
                   jnp.where(free, _I(mc.CS), _I(mc.SL_CAS)),
                   jnp.full_like(p, mc.NCS)]
        if is_rw:
            pc_conds += [is_rdt, is_rdc, is_rdr, is_wd]
            pc_vals += [jnp.where(can_rd, _I(mc.RD_CS), _I(mc.RD_TRY)),
                        jnp.full_like(p, mc.RD_REL),
                        jnp.full_like(p, mc.NCS),
                        jnp.where(wdv == 0, _I(mc.CS), _I(mc.WR_DRAIN))]
        new_pc = _select(pc_conds, pc_vals, p).astype(I32)
        pc = jnp.where(ohT, new_pc[:, None], pc)
        if R > 0:
            # no-op events (drained stream / idle thread with an empty
            # queue) keep the semantic machine frozen — the exact analogue
            # of the XLA loop's step_ok tree_map over sem2
            sm = step_ok[:, None]
            (t0, t1, vic, pc, bud, nxt, prv, tgt, coh) = tuple(
                jnp.where(sm, n, o) for n, o in
                zip((t0, t1, vic, pc, bud, nxt, prv, tgt, coh), sem_old))
            if is_rw:
                wrd = jnp.where(sm, wrd, st_wrd)

        # -- cost opcode + RNIC node (sim._step_fns' cost functions) -------
        lnode = gat_k(ln, tg)
        pred_node = gat_t(tn, pred)
        succ_node = gat_t(tn, succ)
        if is_hl:
            # three-tier cost: own node -> shared memory, same rack -> the
            # cheap loopback/rack fabric, cross rack -> full RDMA (mirror
            # of sim._step_fns._tiered, one-hot rack gathers)
            def tiered(nd):
                rk_n = jnp.sum(jnp.where(nio == nd[:, None], rk, _I(0)),
                               axis=1, dtype=I32)
                return jnp.where(nd == mynode, _I(OP_LOCAL),
                                 jnp.where(rk_n == rk_me, _I(OP_LOOP),
                                           _I(OP_RDMA)))

            lock_code = tiered(lnode)
            wn_code = tiered(pred_node)
            ps_code = tiered(succ_node)
        elif is_alock:
            lock_code = jnp.where(ch == 0, _I(OP_LOCAL), _I(OP_RDMA))
            wn_code = jnp.where(pred_node == mynode, _I(OP_LOCAL),
                                _I(OP_RDMA))
            ps_code = jnp.where(succ_node == mynode, _I(OP_LOCAL),
                                _I(OP_RDMA))
        else:
            lock_code = jnp.where(lnode == mynode, _I(OP_LOOP), _I(OP_RDMA))
            wn_code = jnp.where(pred_node == mynode, _I(OP_LOOP),
                                _I(OP_RDMA))
            ps_code = jnp.where(succ_node == mynode, _I(OP_LOOP),
                                _I(OP_RDMA))
        lock_m = (is_swap | is_sv | is_svr | is_pw | is_pwr | is_rc
                  | is_slc | is_slr)
        cs_m = is_cs
        if is_rw:
            # reader entry/release and the writer drain are lock-word ops;
            # the reader CS is an OP_CS like the writer's
            lock_m = lock_m | is_rdt | is_rdr | is_wd
            cs_m = cs_m | is_rdc
        code = _select(
            [is_ncs, is_wn, is_sb, cs_m, is_sn, is_ps, lock_m],
            [jnp.full_like(p, OP_THINK),
             wn_code,
             jnp.where(bd == -1, _I(OP_POLL), _I(OP_LOCAL)),
             jnp.full_like(p, OP_CS),
             jnp.where(has_succ, _I(OP_LOCAL), _I(OP_POLL)),
             ps_code,
             lock_code], jnp.full_like(p, 0)).astype(I32)
        tnode = _select([is_wn, is_ps, lock_m],
                        [pred_node, succ_node, lnode],
                        jnp.full_like(p, 0)).astype(I32)

        # -- cost application (identical int arithmetic to _run_events) ----
        # node_mult fail-slow scaling mirrors sim._scale_cost bitwise:
        # f32 multiply of ints < 2^24 is exact, round-to-nearest, back to
        # i32 — svc/wire take the target card's multiplier, dt_plain the
        # calling thread's node's
        is_rdma = (code == OP_RDMA) | (code == OP_LOOP)
        if R > 0:
            is_rdma = is_rdma & step_ok
        ohN = nio == tnode[:, None]
        nm_t = jnp.sum(jnp.where(ohN, nm_row, np.float32(0)), axis=1,
                       dtype=jnp.float32)
        ohMy = nio == mynode[:, None]
        nm_my = jnp.sum(jnp.where(ohMy, nm_row, np.float32(0)), axis=1,
                        dtype=jnp.float32)
        svc = jnp.round(jnp.where(code == OP_LOOP, cst[:, 5], cst[:, 4])
                        .astype(jnp.float32) * nm_t).astype(I32)
        wire = jnp.round(jnp.where(code == OP_LOOP, cst[:, 7], cst[:, 6])
                         .astype(jnp.float32) * nm_t).astype(I32)
        busy_t = p32.pgather(ohN, busy)
        start = p32.pmax2(now, busy_t)
        fin = p32.padd_i32(start, svc)
        busy = p32.pwhere(is_rdma[:, None] & ohN, _col(fin), busy)
        dt_plain = jnp.round(_select(
            [code == OP_LOCAL, code == OP_POLL, code == OP_CS,
             code == OP_THINK],
            [cst[:, 0], cst[:, 1], cst[:, 2], think_e], cst[:, 0])
            .astype(jnp.float32) * nm_my).astype(I32)
        new_ready = p32.pwhere(is_rdma, p32.padd_i32(fin, wire),
                               p32.padd_i32(now, dt_plain))
        if R > 0:
            ready = p32.pwhere(ohT & sm, _col(new_ready), ready)
        else:
            ready = p32.pwhere(ohT, _col(new_ready), ready)

        # -- completion accounting (latency stream, counters) --------------
        fin_m = is_rc | is_ps | is_slr
        if is_rw:
            # a reader's RD_REL decrement is its release — it completes an
            # acquisition exactly like a writer's REL_CAS/PASS
            fin_m = fin_m | is_rdr
        finished = fin_m & (new_pc == mc.NCS)
        if R > 0:
            finished = finished & step_ok
        emit(lat_refs, e, finished, p32.psub(now, p32.pgather(ohT, opst)))
        latn = latn + finished.astype(I32)
        done = done + jnp.where(ohT & finished[:, None], _I(1), _I(0))
        if R > 0:
            opst = p32.pwhere((is_ncs & step_ok)[:, None] & ohT,
                              _col(new_ready), opst)
            reacq = reacq + (is_sb & (new_pc == mc.SET_VICTIM_R)
                             & step_ok).astype(I32)
            npass = npass + (is_ps & step_ok).astype(I32)
            # -- departure: the finishing release frees the thread and
            # stamps the request's sojourn at the step's completion time
            req = gat_t(curreq, tid)
            comp = finished & (req >= _I(0))
            rq = jnp.maximum(req, _I(0))
            ohRq = rio == rq[:, None]
            cm = comp[:, None]
            sojv = p32.psub(new_ready, p32.pgather(ohRq, arr))
            soj = p32.pwhere(ohRq & cm, _col(sojv), soj)
            rstat = jnp.where(ohRq & cm, _I(COMPLETED), rstat)
            curreq = jnp.where(ohT & cm, _I(-1), curreq)
            new_st = (t0, t1, vic, pc, bud, nxt, prv, tgt, coh, ready,
                      busy, opst, done, latn, reacq, npass,
                      rstat, curreq, arrptr, qlen, wq, soj)
        else:
            opst = p32.pwhere(is_ncs[:, None] & ohT, _col(new_ready), opst)
            reacq = reacq + (is_sb
                             & (new_pc == mc.SET_VICTIM_R)).astype(I32)
            npass = npass + is_ps.astype(I32)
            new_st = (t0, t1, vic, pc, bud, nxt, prv, tgt, coh, ready,
                      busy, opst, done, latn, reacq, npass)
        if is_rw:
            new_st = new_st + (wrd,)
        return new_st

    # ragged final chunk: bound the loop at the true remaining event count
    # instead of running ev_chunk - (n_events % ev_chunk) masked no-op
    # steps through the whole state tree
    nev_here = jnp.minimum(_I(ev_chunk), _I(n_events) - j * _I(ev_chunk))
    # explicit i32-counter while_loop: under x64, fori_loop's induction
    # variable is int64 — the one 64-bit aval Mosaic would still see in
    # this kernel
    carry = lax.while_loop(
        lambda c: c[0] < nev_here,
        lambda c: (c[0] + _I(1), step(c[0], c[1])),
        (jnp.zeros((), I32), state))
    state = carry[1]
    if is_rw:
        s_word[...] = state[-1]
        state = state[:-1]
    (t0, t1, vic, pc, bud, nxt, prv, tgt, coh, ready, busy, opst,
     done, latn, reacq, npass) = state[:16]

    for ref, val in ((s_t0, t0), (s_t1, t1), (s_vic, vic), (s_pc, pc),
                     (s_bud, bud), (s_nxt, nxt), (s_prev, prv), (s_tgt, tgt),
                     (s_coh, coh)):
        ref[...] = val
    for crefs, val in ((ready_refs, ready), (busy_refs, busy),
                       (opst_refs, opst)):
        _write(crefs, val)
    done_ref[...] = done
    latn_ref[...] = latn[:, None]
    _write(tend_refs, _col(p32.reduce_max(ready)))
    reacq_ref[...] = reacq[:, None]
    npass_ref[...] = npass[:, None]
    if R > 0:
        (rstat, curreq, arrptr, qlen, wq, soj) = state[16:]
        rstat_ref[...] = rstat
        s_curreq[...] = curreq
        s_arrptr[...] = arrptr[:, None]
        s_qlen[...] = qlen[:, None]
        _write(wq_refs, wq)
        _write(soj_refs, soj)
