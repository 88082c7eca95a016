"""Plain reference for served ViT/DeiT classifiers, in float32.

The encoder as DeiT publishes it (Touvron et al. 2021, arXiv 2012.12877;
ViT, Dosovitskiy et al. 2021): non-overlapping patches flattened and
projected, class and distillation tokens prepended, learned position
embeddings, pre-LN blocks of multi-head self-attention and a GELU MLP
(exact, erf form), a final LayerNorm (eps 1e-6).  The classifier is the
one the configuration states: a single head over the mean of the class
and distillation tokens (DeiT averages two heads' outputs; the two agree
when the heads share weights).

Every matrix product runs at ``highest`` precision.  ``fp8=True`` rounds
both operands of every product to float8 (e4m3, one scale per tensor)
first: the control one precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _ln(x, p, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


@functools.partial(jax.jit, static_argnames=("arch", "fp8"))
def forward(params, images, *, arch, fp8: bool = False):
    """``images`` (B, H, W, C) float32 -> logits (B, n_classes) float32.

    ``arch`` is a tuple of ``(key, value)`` pairs of the configuration's
    architecture; ``params`` the served weights, cast to float32."""
    a = dict(arch)
    P = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    B, H, W, C = images.shape
    p, d, nh = a["patch"], a["d_model"], a["n_heads"]
    hd = d // nh
    g = H // p
    n_extra = 1 + int(a["distill_token"])
    x = images.astype(jnp.float32).reshape(B, g, p, g, p, C)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(B, g * g, p * p * C)
    w = P["patch_embed"]["w"].reshape(p * p * C, d)
    x = _mm("bnk,kd->bnd", x, w, fp8) + P["patch_embed"]["b"]
    tok = jnp.broadcast_to(P["cls_token"][None], (B, n_extra, d))
    x = jnp.concatenate([tok, x], axis=1) + P["pos_embed"][None]
    S = x.shape[1]
    L = P["layers"]
    for i in range(a["n_layers"]):
        lp = jax.tree.map(lambda t: t[i], L)
        y = _ln(x, lp["ln1"])
        q, k, v = (( _mm("bsd,de->bse", y, lp[f"w{n}"], fp8) + lp[f"b{n}"])
                   .reshape(B, S, nh, hd) for n in "qkv")
        s = _mm("bqhe,bkhe->bhqk", q, k, fp8) / jnp.sqrt(jnp.float32(hd))
        o = _mm("bhqk,bkhe->bqhe", jax.nn.softmax(s, -1), v, fp8)
        x = x + _mm("bsd,de->bse", o.reshape(B, S, d), lp["wo"], fp8) + lp["bo"]
        y = _ln(x, lp["ln2"])
        z = jax.nn.gelu(_mm("bsd,df->bsf", y, lp["w_in"], fp8) + lp["b_in"],
                        approximate=False)
        x = x + _mm("bsf,fd->bsd", z, lp["w_out"], fp8) + lp["b_out"]
    x = _ln(x, P["final_ln"])
    feat = jnp.mean(x[:, :n_extra], axis=1)
    return _mm("bd,dc->bc", feat, P["head"]["w"], fp8) + P["head"]["b"]
