"""Jitted public wrappers for the model layers' Pallas kernels:
attention, RMSNorm and the grouped expert GEMM.

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

from repro.kernels import flash_attention as _fa
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
