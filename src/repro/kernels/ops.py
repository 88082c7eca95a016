"""Jitted public wrappers for the Pallas kernels.

On a TPU backend the kernels compile to Mosaic.  On any other backend they
run in interpret mode — the kernel body evaluated as ordinary XLA, used
for correctness validation — and say so with a ``RuntimeWarning`` when
they are traced.  Model code selects kernels via the config's
``attn_impl='pallas'``.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import event_select as _es
from repro.kernels import flash_attention as _fa
from repro.kernels import fleet_feasibility as _ff
from repro.kernels import link_cost as _lc
from repro.kernels import moe_gemm as _mg
from repro.kernels import rmsnorm as _rn


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    warnings.warn(f"Pallas kernels run in interpret mode on the "
                  f"{backend!r} backend (no TPU)", RuntimeWarning,
                  stacklevel=2)
    return True


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    """q (B,S,H,D); k,v (B,S,KV,D) -> (B,S,H,D). GQA via head mapping."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    D_pad = (-D) % 128
    # fold batch x heads; repeat is logical only (index_map equivalent):
    # we expand KV to H by gathering, which XLA fuses into the kernel feed.
    kh = jnp.repeat(k, G, axis=2)
    vh = jnp.repeat(v, G, axis=2)
    q3 = jnp.moveaxis(q, 2, 1).reshape(B * H, S, D)
    k3 = jnp.moveaxis(kh, 2, 1).reshape(B * H, S, D)
    v3 = jnp.moveaxis(vh, 2, 1).reshape(B * H, S, D)
    if D_pad:
        q3 = _pad_to(q3, 128, 2)
        k3 = _pad_to(k3, 128, 2)
        v3 = _pad_to(v3, 128, 2)
    out = _fa.flash_attention_fwd(q3, k3, v3, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  sm_scale=D ** -0.5,
                                  interpret=_interpret())
    out = out[:, :, :D].reshape(B, H, S, D)
    return jnp.moveaxis(out, 1, 2)


@jax.jit
def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """x (..., d) RMS-normalized and scaled by (1 + scale)."""
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    out = _rn.rmsnorm_fwd(flat, scale, interpret=_interpret())
    return out.reshape(shape)


@jax.jit
def moe_gemm(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """(E, C, d) x (E, d, f) -> (E, C, f) grouped GEMM."""
    return _mg.moe_gemm(x, w, interpret=_interpret())


@jax.jit
def fleet_feasibility(starts: jnp.ndarray, ends: jnp.ndarray,
                      sizes: jnp.ndarray, n: jnp.ndarray, ps: jnp.ndarray,
                      d: jnp.ndarray, cpu_free: jnp.ndarray, head=None):
    """Stacked (K, N) fleet ledger -> ((K,) feasible mask, (K,) load).

    The fleet simulator's cross-node admission scan fused with the
    router's pending-work reduction; see kernels/fleet_feasibility.py.
    ``head`` marks retired slots (fleetsim head-pointer rows; default 0).
    """
    with jax.named_scope("kernels.fleet_feasibility"):
        return _ff.fleet_feasibility_fwd(starts, ends, sizes, n, ps, d,
                                         cpu_free, head,
                                         interpret=_interpret())


@jax.jit
def event_select(t_a, node_a, d_a, p_a, pay_a, avail_a,
                 t_b, node_b, d_b, p_b, pay_b, avail_b,
                 starts: jnp.ndarray, ends: jnp.ndarray, sizes: jnp.ndarray,
                 n: jnp.ndarray, head, speeds: jnp.ndarray,
                 busy: jnp.ndarray, latency: jnp.ndarray,
                 inv_bw: jnp.ndarray):
    """Fused next-event merge + per-hop referral scoring (DESIGN.md §7).

    Candidate scalars ``(t, node, d, p, payload, avail)`` for the fresh
    arrival (``_a``) and the re-arrival buffer head (``_b``); stacked
    (K, N) ledger windows; full (K, K) NetParams tensors (zeros for a
    network-free run).  Returns ``(take_fresh, t, node, feasible (K,),
    arrive (K,), j (K,), cap (K,), load (K,))``; oracle:
    :func:`repro.kernels.ref.event_select_ref`.
    """
    with jax.named_scope("kernels.event_select"):
        return _es.event_select_fwd(t_a, node_a, d_a, p_a, pay_a, avail_a,
                                    t_b, node_b, d_b, p_b, pay_b, avail_b,
                                    starts, ends, sizes, n, head, speeds,
                                    busy, latency, inv_bw,
                                    interpret=_interpret())


@jax.jit
def link_cost(starts: jnp.ndarray, ends: jnp.ndarray, sizes: jnp.ndarray,
              n: jnp.ndarray, ps: jnp.ndarray, d: jnp.ndarray,
              busy: jnp.ndarray, head, t_src: jnp.ndarray,
              lat_row: jnp.ndarray, inv_bw_row: jnp.ndarray,
              payload: jnp.ndarray):
    """Fused referral scoring: transfer delay + ledger feasibility.

    One request at a source node at ``t_src`` against K candidates'
    stacked (K, N) ledgers; ``lat_row``/``inv_bw_row`` are the source's
    rows of the :class:`repro.netsim.NetParams` tensors.  Returns
    ``((K,) feasible, (K,) arrival, (K,) load)``; oracle:
    :func:`repro.kernels.ref.link_cost_ref`.
    """
    with jax.named_scope("kernels.link_cost"):
        return _lc.link_cost_fwd(starts, ends, sizes, n, ps, d, busy, head,
                                 t_src, lat_row, inv_bw_row, payload,
                                 interpret=_interpret())
