"""Pure-jnp oracles for the model layers' Pallas kernels (the allclose
ground truth)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True,
                        window: Optional[int] = None) -> jnp.ndarray:
    """q (B,S,H,D); k,v (B,S,KV,D). fp32 softmax, GQA by head grouping."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok &= qpos >= kpos
    if window is not None:
        ok &= qpos - kpos < window
    s = jnp.where(ok[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def rmsnorm_ref(x: jnp.ndarray, scale: jnp.ndarray,
                eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def moe_gemm_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Grouped per-expert GEMM: (E,C,d) @ (E,d,f) -> (E,C,f)."""
    return jnp.einsum("ecd,edf->ecf", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)
