"""Jitted public wrappers for the Pallas kernels.

On a TPU backend the kernels compile to Mosaic.  On any other backend they
run in interpret mode — the kernel body evaluated as ordinary XLA, used
for correctness validation — and say so with a ``RuntimeWarning`` when
they are traced.  Model code selects the attention kernel with the
config's ``attn_impl='pallas'``; a ViT encoder's ``chunked`` attention on
a TPU runs it too (``repro.models.vit.attention_impl``).
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention(q, k, v, causal, window):
    return _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   interpret=_interpret())


def _attention_fwd(q, k, v, causal, window):
    return _attention(q, k, v, causal, window), (q, k, v)


def _attention_bwd(causal, window, res, g):
    # the gradient recomputes through the blocked XLA path, which holds
    # one block of scores at a time too
    from repro.models.attention import attention_chunked

    def f(q, k, v):
        out = attention_chunked(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)),
                                causal=causal, window=window)
        return jnp.swapaxes(out, 1, 2)

    return jax.vjp(f, *res)[1](g)


_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None
                    ) -> jnp.ndarray:
    """q (B,H,S,D); k,v (B,KV,S,D) head-major -> (B,H,S,D). GQA via the
    kernel's head mapping; block sizes follow from S."""
    return _attention(q, k, v, causal, window)


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
