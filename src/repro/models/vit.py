"""Vision Transformer (ViT-L/16, ViT-H/14), DeiT-B (distillation token) and
the MoonViT tower of Kimi-VL.

Patch-embedding is part of the model (vision pool rule).  Pre-LN blocks
(:func:`encoder_block`, one for every variant), learned positional
embeddings, GELU MLP, mean-free CLS-token classifier.  Pos-embeddings are
sized for the config resolution and interpolated to other patch grids,
square or not (cls_384 fine-tune shape; MoonViT's 64 x 64 table on a
frame's grid).  MoonViT adds 2-D RoPE on q and k, has no class token and
no head: :func:`encode` returns every patch token.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ViTConfig
from repro.distributed import sharding as shd
from repro.models import attention as attn
from repro.models import common

PyTree = Any


def _dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


def param_defs(cfg: ViTConfig) -> Dict[str, common.ParamDef]:
    L, d, f = cfg.n_layers, cfg.d_model, cfg.d_ff
    p, c = cfg.patch, cfg.in_channels
    dt = _dtype(cfg)
    defs = {
        "patch_embed/w": common.ParamDef((p, p, c, d), dtype=dt),
        "patch_embed/b": common.ParamDef((d,), "zeros", dtype=dt),
        "pos_embed": common.ParamDef((cfg.n_tokens(), d), scale=0.02, dtype=dt),
        "final_ln/scale": common.ParamDef((d,), "ones", dtype=dt),
        "final_ln/bias": common.ParamDef((d,), "zeros", dtype=dt),
        "layers/ln1/scale": common.ParamDef((L, d), "ones", dtype=dt),
        "layers/ln1/bias": common.ParamDef((L, d), "zeros", dtype=dt),
        "layers/ln2/scale": common.ParamDef((L, d), "ones", dtype=dt),
        "layers/ln2/bias": common.ParamDef((L, d), "zeros", dtype=dt),
        "layers/wq": common.ParamDef((L, d, d), dtype=dt),
        "layers/wk": common.ParamDef((L, d, d), dtype=dt),
        "layers/wv": common.ParamDef((L, d, d), dtype=dt),
        "layers/wo": common.ParamDef((L, d, d), dtype=dt),
        "layers/bq": common.ParamDef((L, d), "zeros", dtype=dt),
        "layers/bk": common.ParamDef((L, d), "zeros", dtype=dt),
        "layers/bv": common.ParamDef((L, d), "zeros", dtype=dt),
        "layers/bo": common.ParamDef((L, d), "zeros", dtype=dt),
        "layers/w_in": common.ParamDef((L, d, f), dtype=dt),
        "layers/b_in": common.ParamDef((L, f), "zeros", dtype=dt),
        "layers/w_out": common.ParamDef((L, f, d), dtype=dt),
        "layers/b_out": common.ParamDef((L, d), "zeros", dtype=dt),
    }
    if cfg.n_extra:
        defs["cls_token"] = common.ParamDef((cfg.n_extra, d), "zeros", dtype=dt)
    if cfg.n_classes:
        defs["head/w"] = common.ParamDef((d, cfg.n_classes), dtype=dt)
        defs["head/b"] = common.ParamDef((cfg.n_classes,), "zeros", dtype=dt)
    return defs


def param_specs(cfg): return common.param_specs(param_defs(cfg))
def init_params(cfg, key): return common.init_params(param_defs(cfg), key)


def param_logical(cfg: ViTConfig) -> Dict[str, Tuple]:
    log = {
        "patch_embed/w": (None, None, None, "tp"),
        "patch_embed/b": ("tp",),
        "cls_token": (None, None),
        "pos_embed": (None, None),
        "final_ln/scale": (None,), "final_ln/bias": (None,),
        "head/w": ("fsdp", "tp"), "head/b": ("tp",),
        "layers/ln1/scale": (None, None), "layers/ln1/bias": (None, None),
        "layers/ln2/scale": (None, None), "layers/ln2/bias": (None, None),
        "layers/wq": (None, "fsdp", "tp"),
        "layers/wk": (None, "fsdp", "tp"),
        "layers/wv": (None, "fsdp", "tp"),
        "layers/wo": (None, "tp", "fsdp"),
        "layers/bq": (None, "tp"), "layers/bk": (None, "tp"),
        "layers/bv": (None, "tp"), "layers/bo": (None, None),
        "layers/w_in": (None, "fsdp", "tp"), "layers/b_in": (None, "tp"),
        "layers/w_out": (None, "tp", "fsdp"), "layers/b_out": (None, None),
    }
    if not cfg.n_extra:
        del log["cls_token"]
    if not cfg.n_classes:
        del log["head/w"], log["head/b"]
    return log


def _interp_pos_embed(pos: jnp.ndarray, n_extra: int,
                      grid_from: Tuple[int, int], grid_to: Tuple[int, int],
                      method: str = "bilinear") -> jnp.ndarray:
    """Pos-embed interpolation from a (rows, cols) patch grid to another."""
    if grid_from == grid_to:
        return pos
    extra, grid = pos[:n_extra], pos[n_extra:]
    d = grid.shape[-1]
    grid = grid.reshape(*grid_from, d)
    grid = jax.image.resize(grid.astype(jnp.float32), (*grid_to, d), method,
                            antialias=False).astype(pos.dtype)
    grid = grid.reshape(grid_to[0] * grid_to[1], d)
    return jnp.concatenate([extra, grid], axis=0) if n_extra else grid


def rope_2d_tables(head_dim: int, rows: int, cols: int,
                   theta: float = 10_000.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoonViT's 2-D RoPE: (cos, sin), each (rows * cols, head_dim // 2).

    Channels (2j, 2j+1) of a head form rotary pair j; even pairs turn with
    the patch's column, odd pairs with its row, pair j at frequency
    ``theta ** (-4 * (j // 2) / head_dim)``."""
    freqs = 1.0 / theta ** (jnp.arange(0, head_dim, 4, dtype=jnp.float32)
                            / head_dim)                         # (hd/4,)
    r, c = jnp.divmod(jnp.arange(rows * cols), cols)
    ang = jnp.stack([c[:, None] * freqs, r[:, None] * freqs], -1)
    ang = ang.reshape(rows * cols, head_dim // 2)
    return jnp.cos(ang), jnp.sin(ang)


def _apply_rope_2d(x: jnp.ndarray, rope, seq_axis: int = 1) -> jnp.ndarray:
    """x (B, N, H, D), or (B, H, N, D) with ``seq_axis`` 2, rotated pairwise
    by the (N, D/2) tables."""
    cos, sin = (jnp.expand_dims(t, (0, 3 - seq_axis)) for t in rope)
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def attention_impl(cfg: ViTConfig, platform: str) -> str:
    """The ``impl`` the encoder's attention runs.  On a TPU a blocked
    (``chunked``) encoder runs the Pallas kernel, which keeps the score
    tiles on chip; only MoonViT's 2,304 patches exceed a chunk.  Training
    through it pays one more forward of the attention, which the kernel's
    gradient recomputes through the XLA loop.  Elsewhere the kernel would
    run in Pallas's interpreter, so the config's own ``impl`` stands."""
    if cfg.attn_impl == "chunked" and platform == "tpu":
        return "pallas"
    return cfg.attn_impl


def encoder_block(h: jnp.ndarray, lp: PyTree, cfg: ViTConfig,
                  rope=None) -> jnp.ndarray:
    """One pre-LN block: h (B, S, d) -> (B, S, d).  ``rope``: the 2-D RoPE
    tables of the patch grid, or None."""
    B, S, d = h.shape
    nh = cfg.n_heads
    hd = d // nh
    impl = attention_impl(cfg, jax.default_backend())
    # the kernel reads head-major (B, H, S, D) tiles; the XLA paths token-major
    heads_first = attn.attention_path(impl, S, cfg.attn_chunk) == "kernel"
    with jax.named_scope("kernels.vit_block"):
        y = common.layer_norm(h, lp["ln1"]["scale"], lp["ln1"]["bias"])

        def heads(w, b):
            if heads_first:
                return (jnp.einsum("bsd,dhk->bhsk", y, w.reshape(d, nh, hd))
                        + b.reshape(nh, 1, hd))
            return (jnp.einsum("bsd,dh->bsh", y, w) + b).reshape(B, S, nh, hd)

        q, k, v = (heads(lp[w], lp[b]) for w, b in
                   (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
        if rope is not None:
            q, k = (_apply_rope_2d(x, rope, 2 if heads_first else 1)
                    for x in (q, k))
        o = attn.attention(q, k, v, causal=False, impl=impl,
                           q_chunk=cfg.attn_chunk, heads_first=heads_first)
        if heads_first:
            o = jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].reshape(nh, hd, d))
        else:
            o = jnp.einsum("bsh,hd->bsd", o.reshape(B, S, d), lp["wo"])
        h = h + o + lp["bo"]
        y2 = common.layer_norm(h, lp["ln2"]["scale"], lp["ln2"]["bias"])
        z = common.gelu(jnp.einsum("bsd,df->bsf", y2, lp["w_in"]) + lp["b_in"])
        h = h + jnp.einsum("bsf,fd->bsd", z, lp["w_out"]) + lp["b_out"]
        return shd.hint(h, "dp", None, None)


def encode(params: PyTree, images: jnp.ndarray, cfg: ViTConfig
           ) -> jnp.ndarray:
    """images (B, H, W, C) -> final-LN tokens (B, n_extra + rows * cols, d),
    the extra tokens first, patches row by row."""
    B, H, W, C = images.shape
    d, n_extra = cfg.d_model, cfg.n_extra

    with jax.named_scope("kernels.patch_embed"):
        x = jax.lax.conv_general_dilated(
            images.astype(_dtype(cfg)), params["patch_embed"]["w"],
            window_strides=(cfg.patch, cfg.patch), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = x + params["patch_embed"]["b"]
        gh, gw = H // cfg.patch, W // cfg.patch
        x = x.reshape(B, gh * gw, d)
        if n_extra:
            tok = jnp.broadcast_to(params["cls_token"][None],
                                   (B, n_extra, d)).astype(x.dtype)
            x = jnp.concatenate([tok, x], axis=1)
        g0 = cfg.img_res // cfg.patch
        pos = _interp_pos_embed(params["pos_embed"], n_extra, (g0, g0),
                                (gh, gw), cfg.pos_interp)
        x = x + pos[None]
        x = shd.hint(x, "dp", None, None)
    rope = rope_2d_tables(d // cfg.n_heads, gh, gw) if cfg.rope_2d else None

    def body(h, lp):
        return encoder_block(h, lp, cfg, rope), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(lambda h, lp: body_fn(h, lp), x, params["layers"])
    return common.layer_norm(x, params["final_ln"]["scale"],
                             params["final_ln"]["bias"])


def forward(params: PyTree, images: jnp.ndarray, cfg: ViTConfig
            ) -> jnp.ndarray:
    """images (B, H, W, C) -> logits (B, n_classes)."""
    x = encode(params, images, cfg)
    # DeiT averages the cls and distill heads at inference; we use the mean
    # of the extra tokens as the classifier input for both variants.
    feat = jnp.mean(x[:, :cfg.n_extra], axis=1)
    logits = jnp.einsum("bd,dc->bc", feat, params["head"]["w"],
                        preferred_element_type=jnp.float32) + \
        params["head"]["b"].astype(jnp.float32)
    return logits


def loss_fn(params, batch, cfg: ViTConfig):
    logits = forward(params, batch["images"], cfg)
    loss = common.softmax_xent(logits, batch["labels"])
    acc = jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))
    return loss, {"loss": loss, "accuracy": acc}


def make_train_step(cfg: ViTConfig, opt_cfg):
    from repro.training.optimizer import adamw_update

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)
        params, opt_state, opt_metrics = adamw_update(params, grads,
                                                      opt_state, opt_cfg)
        return params, opt_state, dict(metrics, **opt_metrics)

    return train_step


def serve_step(params, images, cfg: ViTConfig):
    return forward(params, images, cfg)
