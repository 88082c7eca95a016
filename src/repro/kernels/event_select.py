"""Pallas kernel: fused next-event selection + per-hop referral scoring.

The event-time fleet scan (DESIGN.md §7) advances by *events*: at every
step the earliest pending event — the next fresh arrival from the sorted
request stream, or the head of the deferred re-arrival buffer — is
selected, and its node's admission geometry plus the network-priced
feasibility of every forwarding candidate must be known before anything
can be applied.  Unfused that is three passes over the same stacked
``(num_nodes, window)`` ledger tile: the two-way ``(time, seq)`` merge,
the ``link_cost`` wire-delay mask, and the insertion-geometry search.
All three are bandwidth-bound on the ledger block, so this kernel runs
them in one VMEM pass: each grid program

1. resolves the merge (fresh wins ties — the host heap assigns all fresh
   arrivals their sequence numbers before the run, so at equal
   timestamps a fresh arrival always outranks a mid-run push);
2. gathers the selected source node's latency / inverse-bandwidth rows
   (masked one-hot sum — no dynamic addressing) and prices each
   candidate's delayed arrival ``t + lat + payload·inv_bw``;
3. emits, per candidate node: the feasibility bit at that delayed
   arrival, the arrival itself, the insertion slot ``j`` and window edge
   ``cap`` (so the apply step needs no second search), and the pending
   load the routing policies rank by.

The admission geometry (searchsorted-as-masked-count, gap scan, prefix
slack) is identical to the ``fleet_feasibility`` / ``link_cost``
kernels.  Pure-jnp oracle: :func:`repro.kernels.ref.event_select_ref`
(bit-for-bit).  Off-TPU the :mod:`repro.kernels.ops` wrapper runs this
body in interpret mode, lowering to ordinary XLA.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e30


def _event_select_kernel(fsc_ref, isc_ref,
                         starts_ref, ends_ref, sizes_ref, n_ref, head_ref,
                         ps_a_ref, ps_b_ref, busy_ref, lat_ref, invbw_ref,
                         isel_ref, tsel_ref,
                         feas_ref, arr_ref, j_ref, cap_ref, load_ref,
                         *, eps: float):
    # -- the merge, on the scalar unit: earliest of (fresh candidate a,
    # buffer head b); fresh wins ties (host heap seq order — see module
    # docstring).  SMEM rows: fsc = (t, d, payload) of a then of b,
    # isc = (node, avail) of a then of b
    avail_a = isc_ref[0, 1] != 0
    avail_b = isc_ref[0, 3] != 0
    take_a = avail_a & ((fsc_ref[0, 0] <= fsc_ref[0, 3]) | ~avail_b)
    pick = lambda ref, i, w: jnp.where(take_a, ref[0, i], ref[0, i + w])
    t, d, payload = (pick(fsc_ref, i, 3) for i in range(3))
    node = pick(isc_ref, 0, 2)

    starts = starts_ref[...]                     # (bk, N)
    ends = ends_ref[...]
    sizes = sizes_ref[...]
    n = n_ref[...]                               # (bk, 1) int32
    head = head_ref[...]                         # (bk, 1) int32
    ps = jnp.where(take_a, ps_a_ref[...], ps_b_ref[...])     # (bk, 1)
    busy = busy_ref[...]                         # (bk, 1)
    lat = lat_ref[...]                           # (bk, K): lat[src, cand].T
    invbw = invbw_ref[...]                       # (bk, K)
    bk, N = starts.shape
    K = lat.shape[1]
    tail = head + n
    idx = jax.lax.broadcasted_iota(jnp.int32, (bk, N), 1)

    # -- source column gather as a one-hot masked lane sum (the selected
    # node is a traced scalar; exactly one column matches, and 0.0 + v == v
    # exactly)
    srcs = jax.lax.broadcasted_iota(jnp.int32, (bk, K), 1)
    lat_row = jnp.sum(jnp.where(srcs == node, lat, 0.0), axis=1,
                      keepdims=True)
    ibw_row = jnp.sum(jnp.where(srcs == node, invbw, 0.0), axis=1,
                      keepdims=True)
    arrive = t + lat_row + payload * ibw_row
    free = jnp.maximum(arrive, busy)

    # -- admission geometry, identical to fleet_feasibility/link_cost:
    # searchsorted on a sorted ledger == masked count; retired slots hold
    # -BIG/0 and count into both sums identically
    cap_idx = jnp.sum((starts < d).astype(jnp.int32), axis=1, keepdims=True)
    e_hi = jnp.sum((ends < d).astype(jnp.int32), axis=1, keepdims=True)

    prev_ends = jnp.where(idx == 0, -BIG, pltpu.roll(ends, 1, 1))
    has_gap = (starts > prev_ends) & (idx >= head + 1) & (idx < tail)
    gap_ok = has_gap & (idx <= e_hi)
    prev_gap = jnp.max(jnp.where(gap_ok, idx, head), axis=1, keepdims=True)

    no_straddle = e_hi >= cap_idx
    j = jnp.where(no_straddle, e_hi, prev_gap)
    j_clip = jnp.minimum(j, N - 1)
    start_j = jnp.sum(jnp.where(idx == j_clip, starts, 0.0), axis=1,
                      keepdims=True)
    start_j = jnp.where(j < tail, start_j, BIG)
    cap = jnp.where(no_straddle, d, jnp.minimum(start_j, d))
    start_h = jnp.sum(jnp.where(idx == jnp.minimum(head, N - 1), starts, 0.0),
                      axis=1, keepdims=True)
    start_h = jnp.where(n > 0, start_h, BIG)
    front = (~no_straddle) & (prev_gap == head)
    cap = jnp.where(front, jnp.minimum(start_h, d), cap)
    j = jnp.where(front, head, j)

    pw_j = jnp.sum(jnp.where(idx < j, sizes, 0.0), axis=1, keepdims=True)
    feasible = (cap - (free + pw_j) >= ps - eps) & (cap > free) & (tail < N)

    isel_ref[0, 0] = take_a.astype(jnp.int32)
    isel_ref[0, 1] = node
    tsel_ref[0, 0] = t
    feas_ref[...] = feasible.astype(jnp.int32)
    arr_ref[...] = arrive
    j_ref[...] = j
    cap_ref[...] = cap
    load_ref[...] = jnp.sum(sizes, axis=1, keepdims=True)


def event_select_fwd(t_a, node_a, d_a, p_a, pay_a, avail_a,
                     t_b, node_b, d_b, p_b, pay_b, avail_b,
                     starts: jnp.ndarray, ends: jnp.ndarray,
                     sizes: jnp.ndarray, n: jnp.ndarray, head,
                     speeds: jnp.ndarray, busy: jnp.ndarray,
                     latency: jnp.ndarray, inv_bw: jnp.ndarray, *,
                     eps: float = 1e-6, block_nodes: int = 8,
                     interpret: bool = True
                     ) -> Tuple[jnp.ndarray, ...]:
    """Two candidate events + stacked (K, N) ledger windows -> the merge
    verdict plus every per-candidate quantity the event step applies.

    Candidate fields are scalars: ``(t, node, d, p, payload, avail)`` for
    the fresh arrival (``_a``) and the re-arrival buffer head (``_b``);
    ``avail`` gates empty streams.  ``latency``/``inv_bw`` are the full
    (K, K) :class:`repro.netsim.NetParams` tensors (pass zeros for a
    network-free run — the diagonal must be zero, so the selected node
    scores itself at its true arrival ``t``).  ``head`` marks retired
    slots (fleetsim head-pointer rows; default 0 == plain Ledger).

    Layout for Mosaic: the candidate scalars ride two packed SMEM rows and
    the merge verdict comes back through SMEM; every vector operand is
    blocked by whole rows of ``block_nodes`` candidates — ledger windows
    ``(bk, N)``, per-node columns ``(bk, 1)`` and the *transposed* net
    tensors ``(bk, K)`` — so each block's last dim is the array's full
    width.  The per-candidate scaled work ``p / speeds`` is divided here,
    outside the kernel, with the same op as the oracle.

    Returns ``(take_fresh, t, node, feasible (K,), arrive (K,), j (K,),
    cap (K,), load (K,))`` — oracle:
    :func:`repro.kernels.ref.event_select_ref`.
    """
    K, N = starts.shape
    block_nodes = min(block_nodes, K)
    grid = -(-K // block_nodes)
    pad = grid * block_nodes - K

    def pad_rows(x, fill):
        return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                       constant_values=fill) if pad else x

    dtype = starts.dtype
    fsc = jnp.stack([jnp.asarray(x, dtype) for x in
                     (t_a, d_a, pay_a, t_b, d_b, pay_b)]).reshape(1, 6)
    isc = jnp.stack([jnp.asarray(x, jnp.int32) for x in
                     (node_a, avail_a, node_b, avail_b)]).reshape(1, 4)
    col = lambda x, f: pad_rows(jnp.asarray(x, dtype).reshape(K, 1), f)
    speeds = jnp.asarray(speeds, dtype).reshape(K)
    ncol = pad_rows(n.astype(jnp.int32).reshape(K, 1), 0)
    hcol = pad_rows(jnp.zeros((K, 1), jnp.int32) if head is None
                    else head.astype(jnp.int32).reshape(K, 1), 0)
    # (K, K) net tensors transposed so candidates run down the rows: each
    # program reads its candidate block's full source rows
    net_t = lambda x: pad_rows(jnp.asarray(x, dtype).T, 0.0)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    bs_rows = pl.BlockSpec((block_nodes, N), lambda i: (i, 0))
    bs_col = pl.BlockSpec((block_nodes, 1), lambda i: (i, 0))
    bs_net = pl.BlockSpec((block_nodes, K), lambda i: (i, 0))
    KB = grid * block_nodes
    isel, t_sel, feas, arr, j, cap, load = pl.pallas_call(
        functools.partial(_event_select_kernel, eps=eps),
        grid=(grid,),
        in_specs=[smem, smem,
                  bs_rows, bs_rows, bs_rows,     # starts, ends, sizes
                  bs_col, bs_col,                # n, head
                  bs_col, bs_col, bs_col,        # ps_a, ps_b, busy
                  bs_net, bs_net],               # latency.T, inv_bw.T
        out_specs=[smem, smem,
                   bs_col, bs_col, bs_col, bs_col, bs_col],
        out_shape=[
            jax.ShapeDtypeStruct((1, 2), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), dtype),
            jax.ShapeDtypeStruct((KB, 1), jnp.int32),
            jax.ShapeDtypeStruct((KB, 1), dtype),
            jax.ShapeDtypeStruct((KB, 1), jnp.int32),
            jax.ShapeDtypeStruct((KB, 1), dtype),
            jax.ShapeDtypeStruct((KB, 1), dtype),
        ],
        interpret=interpret,
    )(fsc, isc,
      pad_rows(starts, BIG), pad_rows(ends, BIG), pad_rows(sizes, 0.0),
      ncol, hcol, col(jnp.asarray(p_a, dtype) / speeds, 0.0),
      col(jnp.asarray(p_b, dtype) / speeds, 0.0), col(busy, 0.0),
      net_t(latency), net_t(inv_bw))
    return (isel[0, 0] != 0, t_sel[0, 0], isel[0, 1],
            feas[:K, 0] != 0, arr[:K, 0], j[:K, 0], cap[:K, 0], load[:K, 0])
