"""Traced arrival-stream precompute shared by BOTH event-loop engines.

The open-loop extension (see ``docs/serving.md``) adds a request stream
on top of the closed-loop lock machines: requests arrive at traced times,
wait in a FIFO queue, get dispatched to the first idle thread, acquire /
release once and depart. Everything *state-independent* about the stream
is computed here, once, before the event loop runs:

  * **arrival gaps** — per-request inter-arrival times, the sum of a
    deterministic base gap (``arr_fix``, trace replay) and a Poisson
    jitter term drawn from the same counter-based ``fold_in`` stream as
    the event draws (counters offset past ``n_events`` so the two streams
    never collide);
  * **arrival times** — the prefix sum of the gaps, as int64 on the XLA /
    i64 path and as a carry-correct hi/lo i32 pair scan on the x64-off
    path (both are exact integer sums, so they agree bit for bit);
  * **token-bucket admission** — debit-on-arrival with per-request refill
    credit; state-independent (it depends only on arrival times), so it
    folds into a precomputed 0/1 admit mask;
  * **queue-bound rows** — the per-request queue capacity (a request's
    phase is its *index* interval via ``arr_edges``, mirroring the
    event-to-phase mapping).

The bounded-queue *tail drop* itself is service-dependent and stays in
the event loops; both consume the same plan arrays, which is what makes
the two engines (the XLA engine's int64 clocks and the kernel's hi/lo
pairs) bitwise-equal on the arrival path — asserted end-to-end in ``tests/test_traffic.py``.

All helpers are pure ``jnp`` over f32/i32 with pinned dtypes: they trace
identically with and without x64 enabled.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.event_loop import i32pair as p32

I32 = jnp.int32
F32 = jnp.float32

__all__ = [
    "ArrivalPlan", "arrival_gaps", "arrival_plan", "arrival_times_i64",
    "arrival_times_pairs", "per_request", "request_phase_onehot",
    "token_admit",
]


class ArrivalPlan(NamedTuple):
    """State-independent per-request arrays (one replica, no batch axis)."""
    gaps: Any     # (R,) i32  inter-arrival gaps, ns
    tok: Any      # (R,) i32  1 = token-bucket admitted (1s when bucket off)
    tokcum: Any   # (R,) i32  exclusive prefix count of ``tok``
    qcap: Any     # (R,) i32  per-request wait-queue bound


def request_phase_onehot(arr_edges, n_requests: int):
    """(R, P) bool one-hot of each request's phase.

    Request ``k`` belongs to phase ``sum(k >= arr_edges) - 1`` — the exact
    analogue of the engines' per-event phase resolve, so padded phases
    (``arr_edges = INT32_MAX``) are unreachable by construction.
    """
    P = arr_edges.shape[0]
    idx = lax.broadcasted_iota(I32, (n_requests, P), 0)
    ph = jnp.sum((idx >= arr_edges[None, :]).astype(I32), axis=1,
                 dtype=I32) - 1
    return ph[:, None] == lax.broadcasted_iota(I32, (n_requests, P), 1)


def per_request(oh, vals):
    """Broadcast per-phase ``(P,)`` values onto requests via the one-hot
    ``(R, P)`` mask (exactly one True per row, so the sum is a gather)."""
    zero = jnp.zeros((), vals.dtype)
    return jnp.sum(jnp.where(oh, vals[None, :], zero), axis=1,
                   dtype=vals.dtype)


def arrival_gaps(seed, arr_fix, gap_ns_r, n_events: int):
    """Per-request inter-arrival gaps: base trace + Poisson jitter.

    ``gap_k = arr_fix[k] + round(-log(1 - u_k) * gap_ns_r[k])`` with
    ``u_k`` drawn from ``fold_in(key, n_events + 1 + k)`` — the same
    counter-based stream as the event draws, offset so the two never
    share a counter. ``gap_ns_r == 0`` (no Poisson term) contributes
    exactly 0, making trace replay deterministic.
    """
    R = arr_fix.shape[0]
    key = jax.random.key(seed)

    def draw(k):
        return jax.random.uniform(
            jax.random.fold_in(key, n_events + 1 + k), dtype=F32)

    u = jax.vmap(draw)(jnp.arange(R, dtype=I32))
    jit = jnp.round(-jnp.log1p(-u) * gap_ns_r).astype(I32)
    return arr_fix + jit


def arrival_times_i64(gaps):
    """Inclusive prefix sum of the gaps as int64 (requires x64)."""
    return jnp.cumsum(gaps.astype(jnp.int64))


def arrival_times_pairs(gaps):
    """Inclusive prefix sum as a hi/lo i32 pair — exact, x64-free.

    ``lax.associative_scan`` over the carry-correct pair add; integer
    addition is associative, so this agrees with the int64 cumsum bit
    for bit (and emits no ``scan`` primitive, keeping the pairs-trace
    primitive set frozen).
    """
    return lax.associative_scan(p32.padd, p32.from_i32(gaps))


def token_admit(gaps, rate_r, burst_r):
    """Debit-on-arrival token-bucket admission -> (R,) i32 0/1 mask.

    The bucket holds ``credit`` tokens (f32), starts full, refills at
    ``rate_r`` tokens/ns between arrivals and caps at ``burst_r``; a
    request is admitted iff a full token is available at its arrival
    (then debited). Rows with ``rate_r == 0`` switch the policy off
    (admit unconditionally). Admission depends only on arrival times —
    never on service — which is what lets it precompute to a mask.
    """

    def step(credit, x):
        g, r, b = x
        c = jnp.minimum(credit + g.astype(F32) * r, b)
        ok = c >= F32(1.0)
        return jnp.where(ok, c - F32(1.0), c), ok

    _, ok = lax.scan(step, burst_r[0], (gaps, rate_r, burst_r))
    return jnp.where(rate_r > F32(0.0), ok, True).astype(I32)


def arrival_plan(wl, n_events: int) -> ArrivalPlan:
    """Build the full per-request plan from lowered operands (one replica).

    ``wl`` is a ``WorkloadOperands`` with unbatched leaves; batched
    callers vmap this over the replica axis (the plan depends on the
    per-replica ``seed`` and per-phase arrival rows).
    """
    R = wl.arr_fix.shape[-1]
    oh = request_phase_onehot(wl.arr_edges, R)
    gap_ns_r = per_request(oh, wl.arr_gap_ns)
    rate_r = per_request(oh, wl.arr_token[:, 0])
    burst_r = per_request(oh, wl.arr_token[:, 1])
    qcap_r = per_request(oh, wl.arr_qcap)
    gaps = arrival_gaps(wl.seed, wl.arr_fix, gap_ns_r, n_events)
    tok = token_admit(gaps, rate_r, burst_r)
    tokcum = jnp.cumsum(tok, dtype=I32) - tok
    return ArrivalPlan(gaps=gaps, tok=tok, tokcum=tokcum, qcap=qcap_r)
