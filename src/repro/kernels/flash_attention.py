"""FlashAttention forward kernel for TPU (pl.pallas_call + BlockSpec).

TPU-native adaptation of the IO-aware attention idea [arXiv:2205.14135]:

* q, k, v and the output are head-major (B, H, S, D) in HBM; the grid is
  (batch, heads, Q-blocks, KV-blocks) and the BlockSpec index maps pick
  each head's (block, D) tile, so nothing is transposed or padded in HBM.
  GQA lives in the K/V index map (q-head h reads kv-head h // (H // KV)).
* The KV axis is minor-most: one core revisits the same (b, h, qi) output
  block across KV steps while the online-softmax state (m, l, acc) stays
  in VMEM scratch (the canonical TPU accumulation pattern).  The score
  tile never leaves VMEM; only q, k, v and the output pass through HBM.
* The MXU takes q, k and p in the inputs' dtype with float32
  accumulation; the softmax state is float32.  The ``D ** -0.5`` scale is
  folded into the q tile.  D is the tiles' full last dimension (72 for
  MoonViT), which Mosaic pads inside VMEM.
* Block sizes follow from the sequence length (:func:`block_sizes`).
  Causal and sliding-window masks, and the edge mask of a length the
  block does not divide, are position masks inside the tile; a mask that
  cannot bite is not built.

Validated against ``attention_naive`` and ``ref.flash_attention_ref`` in
interpret mode (CPU) in tests/test_kernels.py.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# the largest tiles, from a sweep on a TPU v5e at MoonViT's shape (B 8,
# H 16, S 2,304, D 72; PERF.md): all keys in one step ran fastest, and
# 576-row q tiles within 1% of 1,152-row ones with half the VMEM (a 5.3 MB
# f32 score tile), which leaves room for masks, f32 inputs and D 256
MAX_BLOCK_Q = 576
MAX_BLOCK_K = 2304


def _block(S: int, cap: int, align: int) -> int:
    """The largest multiple of ``align`` that divides ``S`` and is at most
    ``cap``; ``S`` itself if it fits; else ``cap`` (a ragged edge)."""
    if S <= cap:
        return S
    for b in range(cap - cap % align, 0, -align):
        if S % b == 0:
            return b
    return cap


def block_sizes(Sq: int, Sk: int) -> Tuple[int, int]:
    """(block_q, block_k) for sequence lengths (Sq, Sk): q blocks on the
    sublane tiling (8), KV blocks on the lane tiling (128), since the
    score tile puts keys on lanes."""
    return _block(Sq, MAX_BLOCK_Q, 8), _block(Sk, MAX_BLOCK_K, 128)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, block_q: int, block_k: int, causal: bool,
               window: Optional[int], n_k: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = (q_ref[...].astype(jnp.float32) * scale).astype(q_ref.dtype)
    v = v_ref[...]
    s = jax.lax.dot_general(q, k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)

    ragged = seq_k % block_k != 0
    if causal or window is not None or ragged:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        ok = k_pos < seq_k
        if causal:
            ok &= q_pos >= k_pos
        if window is not None:
            ok &= q_pos - k_pos < window
        s = jnp.where(ok, s, NEG_INF)
    if ragged:
        # rows past the end of the keys hold whatever the edge block read
        rows = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)
        v = jnp.where(rows < seq_k, v, 0)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: Optional[int] = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: bool = True) -> jnp.ndarray:
    """q (B, H, Sq, D); k, v (B, KV, Sk, D) head-major -> (B, H, Sq, D).

    Block sizes default to :func:`block_sizes` of the lengths."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    auto_q, auto_k = block_sizes(Sq, Sk)
    block_q, block_k = block_q or auto_q, block_k or auto_k
    n_q, n_k = pl.cdiv(Sq, block_q), pl.cdiv(Sk, block_k)

    kernel = functools.partial(
        _fa_kernel, scale=D ** -0.5, block_q=block_q, block_k=block_k,
        causal=causal, window=window, n_k=n_k, seq_k=Sk)
    q_spec = pl.BlockSpec((None, None, block_q, D),
                          lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, D),
                           lambda b, h, i, j: (b, h // G, j, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # m
            pltpu.VMEM((block_q, 1), jnp.float32),     # l
            pltpu.VMEM((block_q, D), jnp.float32),     # acc
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
