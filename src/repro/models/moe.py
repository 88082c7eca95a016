"""Mixture-of-Experts layer: top-k routing with capacity-bounded dispatch.

TPU-native formulation (DESIGN.md §Hardware-adaptation): tokens are sorted by
expert id and gathered into a dense (E, C, d) buffer so the expert FFN is one
grouped einsum on the MXU — the buffer's expert axis shards over the
``model`` mesh axis (expert parallelism) and GSPMD turns the gather/scatter
into the canonical MoE all-to-alls.  Tokens over capacity are dropped
(GShard-style); the residual stream carries them unchanged.

:func:`moe_held` is the drop-free DeepSeek-V3 layer for a chip of an
expert-parallel group: sigmoid routing over every expert, and a grouped
SwiGLU (:func:`grouped_swiglu`) of only the experts this chip holds.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import swiglu


def capacity(n_tokens: int, n_experts: int, top_k: int,
             capacity_factor: float, min_capacity: int = 4) -> int:
    c = int(n_tokens * top_k * capacity_factor / n_experts)
    c = max(min_capacity, c)
    return min(c, n_tokens)


def route_topk(router_logits: jnp.ndarray, top_k: int,
               n_real: Optional[int] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(T, E) logits -> (gates (T, K) fp32 normalized, experts (T, K) int32).

    ``n_real``: number of real experts — columns beyond it are padding
    (masked out of routing; see LMConfig.n_experts_pad).
    """
    if n_real is not None and n_real < router_logits.shape[-1]:
        col = jnp.arange(router_logits.shape[-1])
        router_logits = jnp.where(col[None, :] < n_real, router_logits, -1e30)
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, experts.astype(jnp.int32)


def load_balancing_loss(router_logits: jnp.ndarray, experts: jnp.ndarray,
                        n_experts: int) -> jnp.ndarray:
    """Switch-style aux loss: E * <fraction routed> . <mean router prob>."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(experts[:, 0], n_experts, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)
    return n_experts * jnp.sum(me * ce)


def dispatch_indices(experts: jnp.ndarray, n_experts: int, cap: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort-based dispatch plan.

    experts: (T, K) int32.  Returns (expert_id (T*K,), slot (T*K,),
    keep (T*K,) bool) — token-copy i goes to buffer[expert_id[i], slot[i]]
    iff keep[i].
    """
    flat = experts.reshape(-1)                       # (T*K,)
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    # rank of each copy within its expert group
    ranks = jnp.arange(flat.shape[0]) - jnp.searchsorted(
        sorted_e, sorted_e, side="left")
    slot = jnp.zeros_like(flat).at[order].set(ranks)
    keep = slot < cap
    return flat, slot.astype(jnp.int32), keep


def _local_dispatch_ffn(x: jnp.ndarray, router_w: jnp.ndarray,
                        w_gate: jnp.ndarray, w_up: jnp.ndarray,
                        w_down: jnp.ndarray, *, top_k: int,
                        capacity_factor: float, n_experts: int,
                        expert_offset,
                        n_real: Optional[int] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-shard MoE: local tokens, local expert slice (E_loc, d, f).

    ``expert_offset`` is this shard's first expert id (0 when experts are
    replicated and only d_ff is sharded).  Returns the PARTIAL output (sum
    over the expert/ffn axis still required) and the local aux loss.
    """
    T, d = x.shape
    E_loc = w_gate.shape[0]
    n_real = n_real or n_experts
    logits = jnp.einsum("td,de->te", x, router_w,
                        preferred_element_type=jnp.float32)
    gates, experts = route_topk(logits, top_k, n_real=n_real)
    aux = load_balancing_loss(logits[:, :n_real], experts, n_real)
    cap = capacity(T, n_real, top_k, capacity_factor)

    eid, slot, keep = dispatch_indices(experts, n_experts, cap)
    tok = jnp.repeat(jnp.arange(T), top_k)
    mine = keep & (eid >= expert_offset) & (eid < expert_offset + E_loc)
    safe_e = jnp.where(mine, eid - expert_offset, 0)
    safe_s = jnp.where(mine, slot, 0)

    buf = jnp.zeros((E_loc, cap, d), x.dtype)
    contrib = jnp.where(mine[:, None], x[tok], 0).astype(x.dtype)
    buf = buf.at[safe_e, safe_s].add(contrib)

    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = swiglu(g, u)
    y = jnp.einsum("ecf,efd->ecd", h, w_down)

    flat_gates = gates.reshape(-1)
    gathered = y[safe_e, safe_s]
    # combine in the activation dtype (bf16): halves the (T·K, d) combine
    # traffic and its backward all-reduce; the scatter-add accumulates in
    # f32 via the out buffer (§Perf A5)
    weighted = gathered * jnp.where(mine, flat_gates, 0.0
                                    )[:, None].astype(gathered.dtype)
    out = jnp.zeros((T, d), jnp.float32).at[tok].add(
        weighted.astype(jnp.float32))
    return out.astype(x.dtype), aux


def moe_ffn_sharded(x: jnp.ndarray, router_w: jnp.ndarray,
                    w_gate: jnp.ndarray, w_up: jnp.ndarray,
                    w_down: jnp.ndarray, *, top_k: int,
                    capacity_factor: float, mesh, dp_axes, model_axis: str,
                    fsdp_axes, expert_sharded: bool,
                    n_real: Optional[int] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """shard_map MoE (beyond-baseline optimization, EXPERIMENTS.md §Perf).

    The einsum/GSPMD formulation sorts and scatters *globally*, which the
    partitioner lowers to catastrophic all-gathers (the dispatch tensors are
    token-count sized).  Here dispatch is LOCAL to each data shard:

    * tokens are replicated across the model axis, so each (data, model)
      device dispatches its own token shard to the experts it owns and a
      single psum over ``model`` combines expert contributions —
      the only cross-device traffic is one (T_loc, d) all-reduce per layer
      plus the (unavoidable) FSDP weight all-gathers;
    * ``expert_sharded``: experts split over ``model`` (E % mp == 0, kimi);
      otherwise each expert's d_ff is split (granite's 40 experts).
    """
    import functools as _ft
    from jax.sharding import PartitionSpec as P

    E = router_w.shape[-1]
    dp = tuple(dp_axes) if dp_axes else ()
    fa = (fsdp_axes,) if isinstance(fsdp_axes, str) else tuple(fsdp_axes or ())

    if expert_sharded:
        w_specs = P(model_axis, fa if fa else None, None)
        wd_spec = P(model_axis, None, fa if fa else None)
    else:
        w_specs = P(None, fa if fa else None, model_axis)
        wd_spec = P(None, model_axis, fa if fa else None)

    def local_fn(x_loc, rw, wg, wu, wd):
        if fa:
            wg = jax.lax.all_gather(wg, fa, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fa, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fa, axis=2, tiled=True)
        if expert_sharded:
            E_loc = wg.shape[0]
            off = jax.lax.axis_index(model_axis) * E_loc
        else:
            off = 0
        out, aux = _local_dispatch_ffn(
            x_loc, rw, wg, wu, wd, top_k=top_k,
            capacity_factor=capacity_factor, n_experts=E, expert_offset=off,
            n_real=n_real)
        out = jax.lax.psum(out, model_axis)
        return out, aux[None]

    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp if dp else None, None), P(None, None),
                  w_specs, w_specs, wd_spec),
        out_specs=(P(dp if dp else None, None), P(dp if dp else None)),
    )(x, router_w, w_gate, w_up, w_down)
    return out, jnp.mean(aux)


def moe_ffn(x: jnp.ndarray, router_w: jnp.ndarray, w_gate: jnp.ndarray,
            w_up: jnp.ndarray, w_down: jnp.ndarray, *, top_k: int,
            capacity_factor: float,
            n_real: Optional[int] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (T, d); expert weights (E, d, f) / (E, f, d). Returns (out, aux)."""
    T, d = x.shape
    E = router_w.shape[-1]
    n_real = n_real or E
    logits = jnp.einsum("td,de->te", x, router_w,
                        preferred_element_type=jnp.float32)
    gates, experts = route_topk(logits, top_k)
    aux = load_balancing_loss(logits, experts, E)
    cap = capacity(T, E, top_k, capacity_factor)

    eid, slot, keep = dispatch_indices(experts, E, cap)
    tok = jnp.repeat(jnp.arange(T), top_k)
    safe_e = jnp.where(keep, eid, 0)
    safe_s = jnp.where(keep, slot, 0)

    # scatter tokens into the (E, C, d) buffer (dropped copies masked out)
    buf = jnp.zeros((E, cap, d), x.dtype)
    contrib = jnp.where(keep[:, None], x[tok], 0).astype(x.dtype)
    buf = buf.at[safe_e, safe_s].add(contrib)

    # grouped expert FFN (SwiGLU)
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = swiglu(g, u)
    y = jnp.einsum("ecf,efd->ecd", h, w_down)

    # combine back with gates
    flat_gates = gates.reshape(-1)
    gathered = y[safe_e, safe_s]                     # (T*K, d)
    weighted = gathered.astype(jnp.float32) * jnp.where(
        keep, flat_gates, 0.0)[:, None]
    out = jnp.zeros((T, d), jnp.float32).at[tok].add(weighted)
    return out.astype(x.dtype), aux


def route_sigmoid(x: jnp.ndarray, router_w: jnp.ndarray, bias: jnp.ndarray,
                  top_k: int, scale: float
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """DeepSeek-V3 ``noaux_tc`` routing in float32: sigmoid scores, the
    per-expert correction ``bias`` added only to pick the top-k, the picked
    unbiased scores normalized to sum 1 and times ``scale``.

    x (T, d), router_w (d, E), bias (E,) -> (gates (T, K) f32, experts
    (T, K) int32)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * scale
    return gates, experts.astype(jnp.int32)


def _plan(M: int, sizes: jnp.ndarray):
    """The (row tile, group) pairs of :func:`grouped_swiglu`: each tile of
    ``tm`` rows is visited once for each group that owns rows in it."""
    tm = min(256, -(-M // 8) * 8)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    done = jnp.cumsum(n_tiles)                       # tiles up to group g

    def pair(i):
        """Visit ``i`` -> (group, tile, rows of the tile the group owns)."""
        g = jnp.sum(done <= i)
        t = first[g] + i - (done[g] - n_tiles[g])
        r = t * tm + jnp.arange(tm)
        return g, t, (r >= starts[g]) & (r < ends[g])

    return tm, -(-M // tm) * tm, done[-1], pair


def _expert(w: jnp.ndarray, layer, g) -> jnp.ndarray:
    """Expert ``g`` of layer ``layer`` of a stack (L, E, a, b)."""
    return jax.lax.dynamic_slice(
        w, (layer, g, 0, 0), (1, 1) + w.shape[2:]).reshape(w.shape[2:])


def _swiglu_expert(x, w_gate, w_up, w_down):
    return swiglu(x @ w_gate, x @ w_up) @ w_down


@jax.custom_vjp
def grouped_swiglu(rows: jnp.ndarray, sizes: jnp.ndarray,
                   w_gate: jnp.ndarray, w_up: jnp.ndarray,
                   w_down: jnp.ndarray, layer) -> jnp.ndarray:
    """Each row of a group through that group's SwiGLU expert of layer
    ``layer``.

    rows (M, d), sorted by group: group g owns ``sizes[g]`` rows after
    those of the groups before it; rows past ``sum(sizes)`` come out zero.
    The weights are stacks (L, E, d, f) / (L, E, f, d).  A loop visits
    each (row tile, group) pair that holds rows of the group, so empty
    groups and rows past the last group compute nothing, and each visited
    expert's weights are read from the stack once per tile, in place."""
    return _grouped_fwd(rows, sizes, w_gate, w_up, w_down, layer)[0]


def _grouped_fwd(rows, sizes, w_gate, w_up, w_down, layer):
    M, d = rows.shape
    tm, Mp, n_visits, pair = _plan(M, sizes)
    xs = jnp.pad(rows, ((0, Mp - M), (0, 0)))

    def visit(i, out):
        g, t, mine = pair(i)
        x = jax.lax.dynamic_slice_in_dim(xs, t * tm, tm)
        h = _swiglu_expert(x, _expert(w_gate, layer, g),
                           _expert(w_up, layer, g), _expert(w_down, layer, g))
        cur = jax.lax.dynamic_slice_in_dim(out, t * tm, tm)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(mine[:, None], h, cur), t * tm, 0)

    out = jax.lax.fori_loop(0, n_visits, visit, jnp.zeros_like(xs))
    return out[:M], (rows, sizes, w_gate, w_up, w_down, layer)


def _grouped_bwd(res, dy):
    """The same visits, each through the expert's VJP: a row's gradient
    comes from its own group's visit, an expert's from its rows."""
    rows, sizes, w_gate, w_up, w_down, layer = res
    M, d = rows.shape
    tm, Mp, n_visits, pair = _plan(M, sizes)
    xs = jnp.pad(rows, ((0, Mp - M), (0, 0)))
    dys = jnp.pad(dy.astype(rows.dtype), ((0, Mp - M), (0, 0)))

    def visit(i, acc):
        dx, dws = acc
        g, t, mine = pair(i)
        x = jax.lax.dynamic_slice_in_dim(xs, t * tm, tm)
        dyt = jnp.where(mine[:, None],
                        jax.lax.dynamic_slice_in_dim(dys, t * tm, tm), 0)
        ws = [_expert(w, layer, g) for w in (w_gate, w_up, w_down)]
        _, vjp = jax.vjp(_swiglu_expert, x, *ws)
        gx, *gws = vjp(dyt)
        cur = jax.lax.dynamic_slice_in_dim(dx, t * tm, tm)
        dx = jax.lax.dynamic_update_slice_in_dim(
            dx, jnp.where(mine[:, None], gx, cur), t * tm, 0)
        dws = tuple(
            jax.lax.dynamic_update_slice(
                dw, (_expert(dw, layer, g) + gw)[None, None],
                (layer, g, 0, 0))
            for dw, gw in zip(dws, gws))
        return dx, dws

    dx, dws = jax.lax.fori_loop(
        0, n_visits, visit,
        (jnp.zeros_like(xs), tuple(jnp.zeros_like(w)
                                   for w in (w_gate, w_up, w_down))))
    return (dx[:M], None, *dws, None)


grouped_swiglu.defvjp(_grouped_fwd, _grouped_bwd)


def moe_held(x: jnp.ndarray, valid: jnp.ndarray, router_w: jnp.ndarray,
             bias: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
             w_down: jnp.ndarray, *, top_k: int, scale: float, first: int,
             layer=None) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The part of a drop-free sigmoid-routed MoE layer that the experts
    ``first .. first + E_held`` give.

    x (T, d), routed as given (float32 in the DeepSeek-V3 layer) and
    computed in the experts' dtype; ``valid`` (T,) bool, False for
    padding, which routes nowhere; expert weights (E_held, d, f) /
    (E_held, f, d), or with ``layer`` the stacks (L, E_held, d, f) /
    (L, E_held, f, d) of which layer ``layer`` is read.  Every (token,
    held expert) route is computed: the routes are sorted by expert and go
    through :func:`grouped_swiglu`.  Returns ``(out (T, d) float32,
    routed, experts)``, where ``routed`` (2,) int32 counts the (token,
    held expert) routes and the held experts given at least one, and
    ``experts`` (T, K) int32 are the picks among all experts."""
    if layer is None:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    T, d = x.shape
    E_held = w_gate.shape[1]
    with jax.named_scope("kernels.moe_route"):
        gates, experts = route_sigmoid(x, router_w, bias, top_k, scale)
        local = experts - first
        mine = (local >= 0) & (local < E_held) & valid[:, None]    # (T, K)
        group = jnp.where(mine, local, E_held).reshape(-1)         # (T*K,)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=E_held + 1)[:E_held].astype(
            jnp.int32)
        rows = x[order // top_k].astype(w_gate.dtype)              # (T*K, d)
    with jax.named_scope("kernels.moe_experts"):
        y = grouped_swiglu(rows, sizes, w_gate, w_up, w_down,
                           jnp.asarray(layer, jnp.int32))
    with jax.named_scope("kernels.moe_route"):
        y = y[jnp.argsort(order)].reshape(T, top_k, d)             # unsort
        y = jnp.where(mine[..., None], y.astype(jnp.float32), 0.0)
        out = jnp.sum(y * gates[..., None], axis=1)
        routed = jnp.stack([jnp.sum(mine), jnp.sum(sizes > 0)]).astype(
            jnp.int32)
    return out, routed, experts
