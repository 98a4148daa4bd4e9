"""XLA oracle for the event-loop kernel: the serial ``sim._run_events``
next-event loop, vmapped over the flattened replica axis.

``repro.core.sim._run_events`` is the single source of truth for the
simulator's semantics; this wrapper gives it the same batched call
signature as ``ops.run_events`` so the kernel tests can diff the two paths
operand-for-operand (bitwise — both consume the identical counter-based
``fold_in`` draw stream). ``batch.sweep``'s sharded XLA leg reuses it as
its per-shard block, so the oracle and the production fallback are one
code path.
"""
from __future__ import annotations

import functools

import jax

from repro.core.sim import LAT_SAMPLES, _run_events


def run_events_ref(alg, T, N, K, n_events, wl, thread_node, lock_node, *,
                   lat_samples: int = LAT_SAMPLES):
    """Batched XLA reference. ``wl`` is a ``WorkloadOperands`` whose leaves
    all carry a leading replica axis B (locality (B,P,T), zcdf (B,P,kpn),
    edges/think_ns (B,P), active (B,P,T), b_init (B,P,2), cost_rows
    (B,P,8), node_mult (B,P,N), seed (B,)); thread_node (T,) and
    lock_node (K,) broadcast.
    Returns (done (B,T), lat (B,lat_samples), lat_n (B,), t_end (B,),
    nreacq (B,), npass (B,)) — must run under ``jax.enable_x64(True)``.
    """
    point = functools.partial(_run_events, alg, T, N, K, n_events)

    def one(w):
        return point(w, thread_node, lock_node, lat_samples=lat_samples)

    return jax.vmap(one)(wl)
