"""The fleet scan's admission scoring: one request scored against stacked
``(K, N)`` ledger rows in one vectorized pass.

:func:`fleet_search` is the gap search, the window cap and the
pending-work slack that decide whether a request fits a node's ledger,
batched over the node axis.  :func:`event_select` merges the scan's two
candidate events and scores the winner against every node at its
wire-delayed arrival; ``batched_feasible`` runs it every step.  Both are
plain jnp: their checks are the host engine, the plain reference of the
benchmark and ``repro.core.jax_queue``'s single-ledger search.
"""
from __future__ import annotations

import jax.numpy as jnp


def event_select(t_a, node_a, d_a, p_a, pay_a, avail_a,
                 t_b, node_b, d_b, p_b, pay_b, avail_b,
                 starts: jnp.ndarray, ends: jnp.ndarray,
                 sizes: jnp.ndarray, n: jnp.ndarray, head,
                 speeds: jnp.ndarray, busy: jnp.ndarray,
                 latency: jnp.ndarray, inv_bw: jnp.ndarray,
                 eps: float = 1e-6):
    """Fused next-event merge + per-hop referral scoring.

    Two candidate events — the next *fresh* arrival (``_a``) from the
    sorted request stream and the head of the deferred *re-arrival*
    buffer (``_b``), each ``(t, node, d, p, payload, avail)`` scalars —
    are merged by ``(time, seq)`` order (fresh wins ties: the host heap
    numbers every fresh arrival before the run, so a mid-run push never
    outranks one at an equal timestamp).  The selected event is then
    scored against all K candidate nodes at its network-delayed arrival
    ``t + latency[node] + payload · inv_bw[node]`` (pass zero tensors
    for a network-free run; the diagonal must be zero so the event's own
    node is scored at its true arrival).

    Returns ``(take_fresh, t, node, feasible (K,), arrive (K,), j (K,),
    cap (K,), load (K,))`` — ``j``/``cap`` are the insertion slot and
    window edge, so the event step applies the admission without a
    second search.
    """
    K = starts.shape[0]
    avail_a = jnp.asarray(avail_a, bool)
    avail_b = jnp.asarray(avail_b, bool)
    take_a = avail_a & ((jnp.asarray(t_a) <= jnp.asarray(t_b)) | ~avail_b)
    t = jnp.where(take_a, t_a, t_b)
    node = jnp.where(take_a, node_a, node_b)
    d = jnp.where(take_a, d_a, d_b)
    p = jnp.where(take_a, p_a, p_b)
    payload = jnp.where(take_a, pay_a, pay_b)
    ps = p / speeds.reshape(K)
    arrive = t + latency[node].reshape(K) + payload * inv_bw[node].reshape(K)
    free = jnp.maximum(arrive, busy.reshape(K))
    feas, j, cap, load = fleet_search(starts, ends, sizes, n, ps, d,
                                      free, head, eps)
    return take_a, t, node, feas, arrive, j, cap, load


def fleet_search(starts: jnp.ndarray, ends: jnp.ndarray,
                 sizes: jnp.ndarray, n: jnp.ndarray, ps: jnp.ndarray,
                 d: jnp.ndarray, cpu_free: jnp.ndarray, head=None,
                 eps: float = 1e-6):
    """Full admission geometry per row: (feasible, j, cap, load).

    Stacked (K, N) ledgers, (K,) ``n``/``ps``/``cpu_free``, scalar
    absolute deadline ``d``.  ``feasible`` includes the capacity check;
    ``j`` is the global insertion slot and ``cap`` the window's right edge
    (the quantities ``jax_queue.push`` computes internally) so a caller can
    apply the insert without a second search pass; ``load`` is each row's
    pending work.

    ``head`` supports the fleet simulator's head-pointer rows: slots
    ``[0, head)`` are retired (starts/ends overwritten with -BIG, sizes 0 —
    which keeps the whole row time-sorted and every count/prefix-sum
    valid), live blocks occupy ``[head, head + n)``.  Default 0 == a plain
    :class:`repro.core.jax_queue.Ledger`.
    """
    BIG = 1e30
    K, N = starts.shape
    n = n.reshape(K, 1).astype(jnp.int32)
    head = jnp.zeros((K, 1), jnp.int32) if head is None \
        else head.reshape(K, 1).astype(jnp.int32)
    tail = head + n
    free = jnp.asarray(cpu_free, starts.dtype).reshape(K, 1)
    p = jnp.asarray(ps, starts.dtype).reshape(K, 1)
    idx = jnp.arange(N)[None, :]
    # retired slots hold -BIG and count into both sums identically, so the
    # straddle comparison and the live-relative positions stay consistent
    cap_idx = jnp.sum((starts < d).astype(jnp.int32), axis=1, keepdims=True)
    e_hi = jnp.sum((ends < d).astype(jnp.int32), axis=1, keepdims=True)
    prev_ends = jnp.concatenate(
        [jnp.full((K, 1), -BIG, ends.dtype), ends[:, :-1]], axis=1)
    has_gap = (starts > prev_ends) & (idx >= head + 1) & (idx < tail)
    gap_ok = has_gap & (idx <= e_hi)
    prev_gap = jnp.max(jnp.where(gap_ok, idx, head), axis=1, keepdims=True)
    no_straddle = e_hi >= cap_idx
    j = jnp.where(no_straddle, e_hi, prev_gap)
    start_j = jnp.take_along_axis(starts, jnp.minimum(j, N - 1), axis=1)
    start_j = jnp.where(j < tail, start_j, BIG)
    cap = jnp.where(no_straddle, d, jnp.minimum(start_j, d))
    start_h = jnp.take_along_axis(starts, jnp.minimum(head, N - 1), axis=1)
    start_h = jnp.where(n > 0, start_h, BIG)
    front = ~no_straddle & (prev_gap == head)
    cap = jnp.where(front, jnp.minimum(start_h, d), cap)
    j = jnp.where(front, head, j)
    pw_j = jnp.sum(jnp.where(idx < j, sizes, 0.0), axis=1, keepdims=True)
    feasible = (cap - (free + pw_j) >= p - eps) & (cap > free) & (tail < N)
    return (feasible[:, 0], j[:, 0], cap[:, 0], jnp.sum(sizes, axis=1))
