"""Attention: GQA + RoPE + causal / sliding-window masks.

Three interchangeable implementations (``impl``):

* ``naive``   — materializes the (S, S) score matrix.  Fine for short
  sequences and as the numerical oracle.
* ``chunked`` — FlashAttention-style online softmax expressed in pure XLA:
  an outer ``lax.map`` over query chunks with an inner ``lax.scan`` over KV
  chunks carrying (m, l, acc).  Never materializes more than
  (q_chunk × kv_chunk) scores per program — this is what the full-scale
  dry-runs lower (Pallas lowers only on real TPU backends).
* ``pallas``  — the TPU kernel in ``repro.kernels`` (interpret-mode on CPU).

Shapes: q (B, S, H, D); k, v (B, S, KV, D) with H % KV == 0 (GQA groups).
``window``: None for full causal; an int w attends to keys in (i-w, i].
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float = 10_000.0) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10_000.0) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    freqs = rope_frequencies(x.shape[-1], theta)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions.astype(jnp.float32)[:, :, None] * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]      # (B, S, 1, D/2)
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Mask helpers
# ---------------------------------------------------------------------------
def _mask_bias(q_pos: jnp.ndarray, k_pos: jnp.ndarray, causal: bool,
               window) -> jnp.ndarray:
    """(Q, K) additive bias; ``window`` may be a traced scalar."""
    dist = q_pos[:, None] - k_pos[None, :]
    ok = jnp.ones(dist.shape, bool)
    if causal:
        ok &= dist >= 0
    if window is not None:
        ok &= dist < window
    return jnp.where(ok, 0.0, NEG_INF)


# ---------------------------------------------------------------------------
# Core implementations
# ---------------------------------------------------------------------------
def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray, scale: float) -> jnp.ndarray:
    """q (B,Sq,KV,G,D), k (B,Sk,KV,D) -> scores (B,KV,G,Sq,Sk), fp32."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32) * scale


def attention_naive(q, k, v, *, causal=True, window=None,
                    q_offset: int = 0) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = D ** -0.5
    qg = q.reshape(B, Sq, KV, G, D)
    scores = _gqa_scores(qg, k, scale)
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(k.shape[1])
    scores = scores + _mask_bias(q_pos, k_pos, causal, window)[None, None, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def attention_chunked(q, k, v, *, causal=True, window=None,
                      q_chunk: int = 1024, kv_chunk: int = 1024,
                      q_offset: int = 0) -> jnp.ndarray:
    """Online-softmax blocked attention in pure XLA (never materializes S²)."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = D ** -0.5
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    # pad to multiples
    nq = -(-Sq // q_chunk)
    nk = -(-Sk // kv_chunk)
    q_pad, k_pad = nq * q_chunk - Sq, nk * kv_chunk - Sk
    qg = q.reshape(B, Sq, KV, G, D)
    if q_pad:
        qg = jnp.pad(qg, ((0, 0), (0, q_pad), (0, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0))) if k_pad else k
    vp = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0))) if k_pad else v
    qs = jnp.moveaxis(qg.reshape(B, nq, q_chunk, KV, G, D), 1, 0)   # (nq,B,qc,KV,G,D)
    ks = jnp.moveaxis(kp.reshape(B, nk, kv_chunk, KV, D), 1, 0)
    vs = jnp.moveaxis(vp.reshape(B, nk, kv_chunk, KV, D), 1, 0)

    def per_q_chunk(args):
        qi, q_blk = args                      # q_blk (B, qc, KV, G, D)
        q_pos = qi * q_chunk + jnp.arange(q_chunk) + q_offset

        # jax.checkpoint on the kv step: without it the scan SAVES the
        # (qc × kc) score block of every step as a backward residual —
        # re-materializing the full S² attention matrix that blocking is
        # supposed to avoid.  With it, backward recomputes scores from the
        # (much smaller) q/k/v blocks: the flash-attention bwd trade.
        @jax.checkpoint
        def kv_step(carry, inputs):
            m, l, acc = carry
            ki, k_blk, v_blk = inputs
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = _gqa_scores(q_blk, k_blk, scale)              # (B,KV,G,qc,kc) f32
            s = s + _mask_bias(q_pos, k_pos, causal, window)[None, None, None]
            # mask zero-padded KV tail (ragged Sk; causality does not cover
            # it for non-causal attention)
            s = jnp.where((k_pos < Sk)[None, None, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(v_blk.dtype), v_blk
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1)                        # (B,qc,KV,G,D)

    outs = jax.lax.map(per_q_chunk, (jnp.arange(nq), qs))     # (nq,B,qc,KV,G,D)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * q_chunk, KV, G, D)
    out = out[:, :Sq].reshape(B, Sq, H, D)
    return out.astype(q.dtype)


def attention_decode(q, k_cache, v_cache, cache_len, *, window=None
                     ) -> jnp.ndarray:
    """Single-token decode: q (B, 1, H, D) against (B, S, KV, D) caches.

    ``cache_len`` is the number of valid cache entries (scalar or (B,)).
    Linear in S — no quadratic term — softmax in fp32.
    """
    B, _, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = D ** -0.5
    qg = q.reshape(B, 1, KV, G, D)
    s = _gqa_scores(qg, k_cache, scale)[..., 0, :]            # (B,KV,G,S)
    k_pos = jnp.arange(S)
    valid = k_pos[None, :] < jnp.reshape(cache_len, (-1, 1))  # (B,S) or (1,S)
    if window is not None:
        valid &= k_pos[None, :] >= jnp.reshape(cache_len, (-1, 1)) - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def attention_path(impl: str, seq: int, q_chunk: int) -> str:
    """The implementation :func:`attention` runs: ``"naive"``, ``"chunked"``
    (the XLA loop) or ``"kernel"`` (the Pallas kernel).  A sequence of at
    most one chunk is naive whatever ``impl`` says."""
    if impl not in ("naive", "chunked", "pallas"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if impl == "naive" or seq <= q_chunk:
        return "naive"
    return "kernel" if impl == "pallas" else "chunked"


def attention(q, k, v, *, causal=True, window=None, impl="chunked",
              q_chunk: int = 1024, q_offset: int = 0,
              heads_first: bool = False) -> jnp.ndarray:
    """``heads_first``: q, k, v and the output are head-major (B, H, S, D),
    the layout the kernel reads; the XLA paths swap them to token-major
    and back."""
    seq = q.shape[2] if heads_first else q.shape[1]
    path = attention_path(impl, seq, q_chunk)
    swap = heads_first != (path == "kernel")
    if swap:
        q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    if path == "kernel":
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal, window=window)
    elif path == "naive":
        # single-chunk sequences: the naive path IS the blocked path
        out = attention_naive(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)
    else:
        out = attention_chunked(q, k, v, causal=causal, window=window,
                                q_chunk=q_chunk, kv_chunk=q_chunk,
                                q_offset=q_offset)
    return jnp.swapaxes(out, 1, 2) if swap else out
