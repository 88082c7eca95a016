"""Multi-head latent attention (DeepSeek-V2 §2.1, as DeepSeek-V3 and
Kimi-VL use it), without a query latent (``q_lora_rank`` null).

Per token, ``wkv_a`` makes a ``kv_lora_rank`` latent ``c`` (RMS-normed) and
one ``qk_rope_head_dim`` key ``k_pe`` shared by the heads (RoPE'd); ``wkv_b``
expands ``c`` into each head's ``qk_nope_head_dim`` key ``k_nope`` and
``v_head_dim`` value.  A head's key is ``[k_nope; k_pe]`` and its query
``[q_nope; q_pe]`` (one ``wq`` projection), both ``qk_nope + qk_rope`` wide,
scaled by that width's inverse square root.

* :func:`prefill_attention` computes the expanded form over a sequence
  and returns the latents ``[c; k_pe]`` that the cache keeps: 576 values a
  token against 16 x 320 for the expanded keys and values.
* :func:`decode_attention` runs on that latent cache in the absorbed form:
  the key up-projection is folded into the query (``q_nope W_UK``) and the
  value up-projection into the output, so the cache is never expanded.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common

PyTree = Any


def _split_kv_b(lp, cfg):
    """wkv_b (r, H*(dn+dv)) -> W_UK (r, H, dn), W_UV (r, H, dv)."""
    H, dn = cfg.n_heads, cfg.qk_nope_head_dim
    w = lp["wkv_b"].reshape(cfg.kv_lora_rank, H, dn + cfg.v_head_dim)
    return w[..., :dn], w[..., dn:]


def project(x: jnp.ndarray, lp: PyTree, cfg, positions: jnp.ndarray):
    """x (B, S, d) normed -> q_nope (B, S, H, dn), q_pe (B, S, H, dr) and the
    latent (B, S, r + dr): ``[rms_norm(c); rope(k_pe)]``."""
    B, S, _ = x.shape
    H, dn, dr, r = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                    cfg.kv_lora_rank)
    q = jnp.einsum("bsd,dh->bsh", x, lp["wq"]).reshape(B, S, H, dn + dr)
    q_nope = q[..., :dn]
    q_pe = attn.apply_rope(q[..., dn:], positions, cfg.rope_theta)
    kv = jnp.einsum("bsd,dh->bsh", x, lp["wkv_a"])
    c = common.rms_norm(kv[..., :r], lp["kv_norm"], cfg.norm_eps)
    k_pe = attn.apply_rope(kv[..., None, r:], positions, cfg.rope_theta)
    return q_nope, q_pe, jnp.concatenate([c, k_pe[:, :, 0]], axis=-1)


def prefill_attention(x: jnp.ndarray, lp: PyTree, cfg,
                      positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Causal MLA over a sequence in the expanded form.

    x (B, S, d) normed -> (output (B, S, d), latents (B, S, r + dr))."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    q_nope, q_pe, lat = project(x, lp, cfg, positions)
    w_uk, w_uv = _split_kv_b(lp, cfg)
    c, k_pe = lat[..., :r], lat[..., r:]
    k_nope = jnp.einsum("bsr,rhn->bshn", c, w_uk)
    v = jnp.einsum("bsr,rhv->bshv", c, w_uv)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, S, H, k_pe.shape[-1]))],
        axis=-1)
    o = attn.attention_naive(q, k, v, causal=True)
    out = jnp.einsum("bsh,hd->bsd", o.reshape(B, S, -1), lp["wo"])
    return out, lat


def decode_attention(q_nope: jnp.ndarray, q_pe: jnp.ndarray,
                     latents: jnp.ndarray, lengths: jnp.ndarray, lp: PyTree,
                     cfg) -> jnp.ndarray:
    """One query token a row against its latent cache, absorbed form.

    q_nope (B, 1, H, dn), q_pe (B, 1, H, dr); latents (B, S, r + dr) bf16,
    of which the first ``lengths`` (B,) entries of each row are valid ->
    output (B, 1, d)."""
    B = q_nope.shape[0]
    r = cfg.kv_lora_rank
    w_uk, w_uv = _split_kv_b(lp, cfg)
    c, k_pe = latents[..., :r], latents[..., r:]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk,
                       preferred_element_type=jnp.float32).astype(c.dtype)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhe,bse->bhs", q_pe[:, 0], k_pe,
                      preferred_element_type=jnp.float32))
    s = s * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    valid = jnp.arange(latents.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, attn.NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
    o_lat = jnp.einsum("bhs,bsr->bhr", p, c,
                       preferred_element_type=jnp.float32).astype(c.dtype)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv)
    return jnp.einsum("bh,hd->bd", o.reshape(B, -1), lp["wo"])[:, None]
