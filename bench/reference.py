"""Plain reference of the lock-table simulator the benchmark checks against.

A straightforward, single-replica, pure-Python event loop over the same
semantics the program's engines implement: ALock (and its rack-aware
``hlock`` and reader-writer ``alock-rw`` forms), the RDMA spinlock and
RDMA MCS, driven by the next-event rule (the thread with the smallest
ready clock steps next, the lowest thread id on a tie) over the cost
model of one-sided RDMA, loopback and shared-memory operations.

It imports nothing of the program and takes nothing the program made:
the workload point (cluster, locality, budgets, skew) and the
configuration's cost constants are plain data, and the per-event draws
are made here from the replica seed with ``jax.random`` on the host CPU:
event ``i`` splits ``fold_in(key(seed), i)`` into three keys (four for
``alock-rw``) and draws the locality uniform, the remote-node offset and
the within-node lock uniform from them. Clocks are Python integers.

``precision`` is the floating-point type the draws are kept in and
compared in, with the probabilities and the CDF they are compared with.
The configurations state ``float32``. ``bfloat16`` is the control, the
step below it (the draws rounded to bfloat16, as a stream stored at half
the width would hold them), which must come out not correct.

Supported: closed-loop, single-phase points with a scalar locality and
read fraction, any Zipf skew and think multiplier, and a per-node rack
list for ``hlock``. Anything else raises ``ValueError``.
"""
from __future__ import annotations

import functools
import heapq

import numpy as np

# program counters of the lock machines (the paper's TLA+ spec, App. A)
(NCS, SWAP, WRITE_NEXT, SPIN_BUDGET, SET_VICTIM, PET_WAIT, SET_VICTIM_R,
 PET_WAIT_R, CS, REL_CAS, SPIN_NEXT, PASS, SL_CAS, SL_REL, RD_TRY, RD_CS,
 RD_REL, WR_DRAIN) = range(18)
# what a step costs: shared memory, local poll, critical section, think,
# one-sided RDMA, RDMA through the own card (loopback)
OP_LOCAL, OP_POLL, OP_CS, OP_THINK, OP_RDMA, OP_LOOP = range(6)

ALGS = ("alock", "spinlock", "mcs", "hlock", "alock-rw")
PRECISIONS = ("float32", "bfloat16")
#: the point keys the reference understands
POINT_KEYS = frozenset({"alg", "n_nodes", "threads_per_node", "n_locks",
                        "locality", "zipf_s", "think", "b_init", "read_frac",
                        "topology"})
THINK_CLASSES = {"none": 0.0, "short": 0.25, "default": 1.0, "long": 4.0}


def cost_rows(cost: dict, alg: str, n_nodes: int, tpn: int) -> tuple:
    """Integer-ns costs (local, poll, cs, think, svc_remote, svc_loopback,
    wire_remote, wire_loopback) of one cluster under the cost constants
    ``cost``: card service inflates past the QP-cache capacity, and
    loopback service inflates with the threads per node past the PCIe
    knee (ALock keeps its local cohort off the card, so it has no
    loopback traffic)."""
    loopback = alg != "alock"
    qps = (n_nodes - 1) * tpn + tpn * max(n_nodes - 1, 0) \
        + 2 * (tpn if loopback else 0)
    thrash = 1.0
    if qps > cost["qp_cache"]:
        thrash = min(1.0 + cost["qp_alpha"] * (qps / cost["qp_cache"] - 1.0),
                     cost["thrash_cap"])
    loop_f = 1.0
    if loopback:
        loop_f = 1.0 + cost["pcie_beta"] * max(0, tpn - cost["pcie_knee"])
    svc_r = cost["rnic_svc_ns"] * thrash
    svc_l = cost["rnic_svc_ns"] * (thrash * loop_f)
    return tuple(int(round(v)) for v in (
        cost["local_ns"], cost["spin_poll_ns"], cost["cs_ns"],
        cost["think_ns"], svc_r, svc_l, cost["remote_wire_ns"],
        cost["loopback_wire_ns"]))


def zipf_cdf(kpn: int, s: float) -> np.ndarray:
    """Inclusive CDF of a Zipf(s) rank over one node's ``kpn`` locks:
    weights normalised in float64, the cumulative sum kept in float32."""
    w = np.arange(1, kpn + 1, dtype=np.float64) ** (-float(s))
    return np.cumsum(w / w.sum()).astype(np.float32)


@functools.lru_cache(maxsize=8)
def _draw_fn(n_events: int, n_nodes: int, rw: bool, dtype: str):
    """A jitted (seed, cdf) -> per-event draws function."""
    import jax
    import jax.numpy as jnp
    fdt = jnp.dtype(dtype)

    def uniform(k):
        return jax.random.uniform(k, dtype=jnp.float32).astype(fdt)

    def draws(seed, cdf):
        root = jax.random.key(seed)

        def ev(i):
            ks = jax.random.split(jax.random.fold_in(root, i), 4 if rw else 3)
            u1 = uniform(ks[0])
            r2 = jax.random.randint(ks[1], (), 0, max(n_nodes - 1, 1),
                                    dtype=jnp.int32)
            off = jnp.minimum(jnp.sum(uniform(ks[2]) >= cdf).astype(jnp.int32),
                              cdf.shape[0] - 1)
            return u1, r2, off, uniform(ks[3]) if rw else u1

        return jax.vmap(ev)(jnp.arange(n_events, dtype=jnp.int32))

    return jax.jit(draws)


def draws(seed: int, n_events: int, n_nodes: int, cdf: np.ndarray,
          rw: bool, precision: str = "float32"):
    """Per-event draws of one replica as Python lists: the locality
    uniform, the remote-node offset, the within-node lock offset and the
    reader coin (``alock-rw`` only; the locality uniform otherwise)."""
    import jax
    import jax.numpy as jnp
    try:    # the host CPU; the draws are integer hashing and exact compares,
        dev = jax.devices("cpu")[0]     # so any device gives the same bits
    except RuntimeError:
        dev = jax.devices()[0]
    fn = _draw_fn(n_events, n_nodes, rw, precision)
    with jax.default_device(dev):
        out = fn(jax.device_put(np.int32(seed), dev),
                 jax.device_put(jnp.asarray(cdf, precision), dev))
    u1, r2, off, u4 = (np.asarray(a) for a in out)
    return (u1.astype(np.float64).tolist(), r2.tolist(), off.tolist(),
            u4.astype(np.float64).tolist())


def _as_precision(x: float, precision: str) -> float:
    import jax.numpy as jnp
    return float(np.asarray(jnp.asarray(np.float32(x), precision),
                            np.float64))


def simulate(point: dict, seed: int, n_events: int, cost: dict,
             lat_samples: int, precision: str = "float32") -> dict:
    """Run one replica and return the BatchResult fields of its seed:
    ``seeds``, ``ops``, ``sim_ns``, ``throughput_mops`` (completions per
    simulated microsecond), ``lat_ns``, ``per_thread_ops``, ``reacquires``
    and ``passes`` (open-loop fields are None)."""
    unknown = set(point) - POINT_KEYS
    if unknown:
        raise ValueError(f"the reference has no model of {sorted(unknown)}")
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    alg = point["alg"]
    if alg not in ALGS:
        raise ValueError(f"unknown algorithm {alg!r}")
    N, tpn, K = point["n_nodes"], point["threads_per_node"], point["n_locks"]
    T, kpn = N * tpn, K // N
    if K % N:
        raise ValueError(f"{K} locks do not divide over {N} nodes")
    for name in ("locality", "read_frac", "zipf_s"):
        if not isinstance(point.get(name, 0.0), (int, float)):
            raise ValueError(f"the reference takes a scalar {name}")
    is_hl, is_rw = alg == "hlock", alg == "alock-rw"
    is_alock = alg in ("alock", "hlock", "alock-rw")
    is_spin = alg == "spinlock"
    rack = list(point.get("topology") or range(N))
    b_init = tuple(point.get("b_init", (5, 20)))
    think = point.get("think", "default")
    think = THINK_CLASSES[think] if isinstance(think, str) else float(think)

    c_local, c_poll, c_cs, c_think, c_svc_r, c_svc_l, c_wire_r, c_wire_l = \
        cost_rows(cost, alg, N, tpn)
    c_think = int(round(think * cost["think_ns"]))
    cdf = zipf_cdf(kpn, point.get("zipf_s", 0.0))
    loc = _as_precision(point.get("locality", 1.0), precision)
    rfrac = _as_precision(point.get("read_frac", 0.0), precision)
    u1, r2, off, u4 = draws(seed, n_events, N, cdf, is_rw, precision)

    tail = [[0, 0] for _ in range(K)]   # per-cohort MCS tails (ALock)
    victim = [0] * K
    word = [0] * K                      # lock word / MCS tail / readers
    budget, nxt, prev = [-1] * T, [0] * T, [0] * T
    pc, target, cohort = [NCS] * T, [0] * T, [0] * T
    ready, op_start, done = [0] * T, [0] * T, [0] * T
    busy = [0] * N
    lat = [-1] * lat_samples
    lat_n = nreacq = npass = 0
    heap = [(0, t) for t in range(T)]
    enter_cs = WR_DRAIN if is_rw else CS

    def tier(node, me_node):
        if node == me_node:
            return OP_LOCAL
        return OP_LOOP if rack[node] == rack[me_node] else OP_RDMA

    def lock_cost(tid):
        node = target[tid] // kpn
        if is_hl:
            return tier(node, tid // tpn), node
        if is_alock:
            return (OP_LOCAL if cohort[tid] == 0 else OP_RDMA), node
        return (OP_LOOP if node == tid // tpn else OP_RDMA), node

    def peer_cost(tid, peer):
        node = peer // tpn
        if is_hl:
            return tier(node, tid // tpn), node
        if node == tid // tpn:
            return (OP_LOCAL if is_alock else OP_LOOP), node
        return OP_RDMA, node

    for i in range(n_events):
        now, tid = heapq.heappop(heap)
        p = pc[tid]
        code, tnode = OP_LOCAL, 0
        if p == NCS:
            mynode = tid // tpn
            node = mynode if u1[i] < loc else (mynode + 1 + r2[i]) % N
            budget[tid], nxt[tid] = -1, 0
            target[tid] = node * kpn + off[i]
            cohort[tid] = int(rack[node] != rack[mynode]) if is_hl \
                else int(node != mynode)
            if is_rw:
                pc[tid] = RD_TRY if u4[i] < rfrac else SWAP
            else:
                pc[tid] = SL_CAS if is_spin else SWAP
            code = OP_THINK
        elif p == SWAP:
            k, me = target[tid], tid + 1
            if is_alock:
                c = cohort[tid]
                pv, tail[k][c] = tail[k][c], me
            else:
                pv, word[k] = word[k], me
            prev[tid] = pv
            if is_alock:
                if pv == 0:
                    budget[tid] = b_init[cohort[tid]]
                pc[tid] = SET_VICTIM if pv == 0 else WRITE_NEXT
            else:
                pc[tid] = CS if pv == 0 else WRITE_NEXT
            code, tnode = lock_cost(tid)
        elif p == WRITE_NEXT:
            q = prev[tid] - 1
            nxt[q] = tid + 1
            pc[tid] = SPIN_BUDGET
            code, tnode = peer_cost(tid, q)
        elif p == SPIN_BUDGET:
            b = budget[tid]
            if b == -1:
                code = OP_POLL
            else:
                pc[tid] = SET_VICTIM_R if (is_alock and b == 0) else \
                    (enter_cs if is_alock else CS)
        elif p in (SET_VICTIM, SET_VICTIM_R):
            victim[target[tid]] = cohort[tid]
            pc[tid] = PET_WAIT if p == SET_VICTIM else PET_WAIT_R
            code, tnode = lock_cost(tid)
        elif p in (PET_WAIT, PET_WAIT_R):
            k, c = target[tid], cohort[tid]
            if tail[k][1 - c] == 0 or victim[k] != c:
                if p == PET_WAIT_R:
                    budget[tid] = b_init[c]
                pc[tid] = enter_cs
            code, tnode = lock_cost(tid)
        elif p == CS:
            pc[tid] = SL_REL if is_spin else REL_CAS
            code = OP_CS
        elif p == REL_CAS:
            k, me = target[tid], tid + 1
            if is_alock:
                c = cohort[tid]
                solo = tail[k][c] == me
                if solo:
                    tail[k][c] = 0
            else:
                solo = word[k] == me
                if solo:
                    word[k] = 0
            pc[tid] = NCS if solo else SPIN_NEXT
            code, tnode = lock_cost(tid)
        elif p == SPIN_NEXT:
            if nxt[tid]:
                pc[tid] = PASS
            else:
                code = OP_POLL
        elif p == PASS:
            succ = nxt[tid] - 1
            budget[succ] = budget[tid] - 1 if is_alock else 1
            pc[tid] = NCS
            code, tnode = peer_cost(tid, succ)
        elif p == SL_CAS:
            k = target[tid]
            if word[k] == 0:
                word[k] = tid + 1
                pc[tid] = CS
            code, tnode = lock_cost(tid)
        elif p == SL_REL:
            word[target[tid]] = 0
            pc[tid] = NCS
            code, tnode = lock_cost(tid)
        elif p == RD_TRY:
            k = target[tid]
            if tail[k][0] == 0 and tail[k][1] == 0:
                word[k] += 1
                pc[tid] = RD_CS
            code, tnode = lock_cost(tid)
        elif p == RD_CS:
            pc[tid] = RD_REL
            code = OP_CS
        elif p == RD_REL:
            word[target[tid]] -= 1
            pc[tid] = NCS
            code, tnode = lock_cost(tid)
        elif p == WR_DRAIN:
            if word[target[tid]] == 0:
                pc[tid] = CS
            code, tnode = lock_cost(tid)
        else:
            raise AssertionError(f"unreachable program counter {p}")

        if pc[tid] == NCS and p in (REL_CAS, PASS, SL_REL, RD_REL):
            lat[lat_n % lat_samples] = now - op_start[tid]
            lat_n += 1
            done[tid] += 1
        if p == SPIN_BUDGET and pc[tid] == SET_VICTIM_R:
            nreacq += 1
        if p == PASS:
            npass += 1

        if code == OP_RDMA or code == OP_LOOP:
            loop = code == OP_LOOP
            start = max(now, busy[tnode])
            fin = start + (c_svc_l if loop else c_svc_r)
            busy[tnode] = fin
            new_ready = fin + (c_wire_l if loop else c_wire_r)
        elif code == OP_POLL:
            new_ready = now + c_poll
        elif code == OP_CS:
            new_ready = now + c_cs
        elif code == OP_THINK:
            new_ready = now + c_think
        else:
            new_ready = now + c_local
        ready[tid] = new_ready
        if p == NCS:
            op_start[tid] = new_ready
        heapq.heappush(heap, (new_ready, tid))

    ops, sim_ns = np.int64(sum(done)), np.int64(max(max(ready), 1))
    return {
        "seeds": np.int32(seed),
        "ops": ops,
        "sim_ns": sim_ns,
        "throughput_mops": ops / sim_ns * 1e3,
        "lat_ns": np.asarray(lat, np.int64),
        "per_thread_ops": np.asarray(done, np.int32),
        "reacquires": np.int32(nreacq),
        "passes": np.int32(npass),
        "arr_ns": None, "wait_ns": None, "sojourn_ns": None, "rstat": None,
    }

