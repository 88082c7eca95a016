"""Cell builder: (architecture config × shape) -> lowered-able step function.

One place defines, for every (arch, shape) cell:
* the step callable (train / prefill / decode / serve),
* abstract argument specs (ShapeDtypeStructs — the dry-run never allocates),
* logical sharding trees for the arguments,
* a real-input factory for smoke tests and the end-to-end drivers.

Used by launch/dryrun.py, launch/train.py, launch/serve.py and the smoke
tests, so the dry-run exercises exactly the code the drivers run.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import get_config, shapes_for
from repro.configs.base import (DiTConfig, LMConfig, ResNetConfig, UNetConfig,
                                ViTConfig, VLMConfig)
from repro.configs.shapes import ShapeSpec
from repro.models import common, dit, kimi_vl, resnet, transformer, unet, vit
from repro.training.optimizer import AdamWConfig, OptState, init_opt_state, \
    opt_state_specs

PyTree = Any


def model_module(cfg):
    return {"lm": transformer, "vit": vit, "resnet": resnet,
            "dit": dit, "unet": unet, "vlm": kimi_vl}[cfg.family]


def _nest_logical(flat: Dict[str, Tuple]) -> PyTree:
    out: Dict[str, Any] = {}
    for path, spec in flat.items():
        common._assign(out, path, tuple(spec))
    return out


def opt_cfg_for(cfg) -> AdamWConfig:
    return AdamWConfig(state_dtype=jnp.dtype(getattr(cfg, "opt_state_dtype",
                                                     "float32")))


@dataclasses.dataclass
class Cell:
    arch: str
    shape: ShapeSpec
    cfg: Any
    step_fn: Callable
    arg_specs: Tuple             # abstract args (ShapeDtypeStructs)
    arg_logical: Tuple           # logical sharding trees aligned with args
    make_args: Callable          # key -> real args (smoke/driver use)
    donate: Tuple[int, ...] = ()

    @property
    def label(self) -> str:
        return f"{self.arch}:{self.shape.name}"


def _batch_tree_logical(tree: PyTree) -> PyTree:
    """Shard the leading dim of every array leaf over dp."""
    def leaf(x):
        nd = len(x.shape)
        return ("dp",) + (None,) * (nd - 1) if nd else ()
    return jax.tree_util.tree_map(leaf, tree)


def _opt_logical(param_logical_tree: PyTree) -> OptState:
    return OptState(step=(), m=param_logical_tree, v=param_logical_tree)


# ---------------------------------------------------------------------------
# Family-specific batch builders
# ---------------------------------------------------------------------------
def _lm_batch_specs(cfg: LMConfig, shape: ShapeSpec):
    B, S = shape.global_batch, shape.seq_len
    return {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}


def _vision_batch_specs(cfg, shape: ShapeSpec):
    B, r = shape.global_batch, shape.img_res
    return {"images": jax.ShapeDtypeStruct((B, r, r, 3), jnp.float32),
            "labels": jax.ShapeDtypeStruct((B,), jnp.int32)}


def _vlm_batch_specs(cfg: VLMConfig, shape: ShapeSpec):
    """Frames at the config's size and prompts of ``seq_len`` tokens (one
    prompt block by default)."""
    B, S = shape.global_batch, shape.seq_len or kimi_vl.PROMPT_BLOCK
    return {"images": jax.ShapeDtypeStruct((B, *cfg.frame_hw, 3), jnp.float32),
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}


def _dit_batch_specs(cfg: DiTConfig, shape: ShapeSpec):
    B = shape.global_batch
    lr = cfg.latent_res(shape.img_res)
    return {"latents": jax.ShapeDtypeStruct((B, lr, lr, cfg.latent_channels),
                                            jnp.float32),
            "labels": jax.ShapeDtypeStruct((B,), jnp.int32),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def _unet_batch_specs(cfg: UNetConfig, shape: ShapeSpec):
    B = shape.global_batch
    lr = shape.img_res // 8 if shape.img_res else cfg.latent_res
    return {"latents": jax.ShapeDtypeStruct((B, lr, lr, cfg.latent_channels),
                                            jnp.float32),
            "ctx": jax.ShapeDtypeStruct((B, cfg.ctx_len, cfg.ctx_dim),
                                        jnp.float32),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def _materialize(specs: PyTree, key: jax.Array) -> PyTree:
    leaves, treedef = jax.tree_util.tree_flatten(specs)
    keys = jax.random.split(key, len(leaves))
    out = []
    for s, k in zip(leaves, keys):
        if jnp.issubdtype(s.dtype, jnp.integer):
            # small id range: valid for every vocab / class-count in the zoo
            out.append(jnp.zeros(s.shape, s.dtype) if not s.shape else
                       jax.random.randint(k, s.shape, 0, 8).astype(s.dtype))
        else:
            out.append(jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype) * 0.1)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------
def build_cell(arch: str, shape_name: str, cfg=None) -> Cell:
    cfg = cfg or get_config(arch)
    shape = shapes_for(cfg)[shape_name]
    mod = model_module(cfg)
    p_specs = mod.param_specs(cfg)
    p_logical = _nest_logical(mod.param_logical(cfg))

    if shape.kind == "train":
        ocfg = opt_cfg_for(cfg)
        step = mod.make_train_step(cfg, ocfg)
        o_specs = opt_state_specs(p_specs, ocfg)
        if cfg.family == "lm":
            b_specs = _lm_batch_specs(cfg, shape)
        elif cfg.family in ("vit", "resnet"):
            b_specs = _vision_batch_specs(cfg, shape)
        elif cfg.family == "vlm":
            b_specs = _vlm_batch_specs(cfg, shape)
        elif cfg.family == "dit":
            b_specs = _dit_batch_specs(cfg, shape)
        else:
            b_specs = _unet_batch_specs(cfg, shape)
        arg_specs = (p_specs, o_specs, b_specs)
        arg_logical = (p_logical, _opt_logical(p_logical),
                       _batch_tree_logical(b_specs))

        def make_args(key):
            params = mod.init_params(cfg, key)
            return (params, init_opt_state(params, ocfg),
                    _materialize(b_specs, jax.random.fold_in(key, 1)))

        return Cell(arch, shape, cfg, step, arg_specs, arg_logical,
                    make_args, donate=(0, 1))

    if cfg.family == "lm":
        if shape.kind == "prefill":
            def step(params, tokens):
                return transformer.prefill(params, tokens, cfg)
            t_spec = jax.ShapeDtypeStruct(
                (shape.global_batch, shape.seq_len), jnp.int32)
            arg_specs = (p_specs, t_spec)
            arg_logical = (p_logical, ("dp", None))

            def make_args(key):
                return (mod.init_params(cfg, key),
                        jax.random.randint(key, t_spec.shape, 0,
                                           cfg.vocab_size).astype(jnp.int32))

            return Cell(arch, shape, cfg, step, arg_specs, arg_logical, make_args)

        # decode
        B, S = shape.global_batch, shape.seq_len
        sliding = cfg.sliding_window is not None and cfg.global_every > 0
        if sliding:
            c_specs = transformer.sliding_cache_specs(cfg, B, S)
            c_logical = transformer.sliding_cache_logical()

            def step(params, cache, tokens):
                return transformer.decode_step_sliding(params, cache, tokens, cfg)

            def make_args(key):
                cache = transformer.init_sliding_cache(cfg, B, S)
                cache["length"] = jnp.asarray(S // 2, jnp.int32)
                return (mod.init_params(cfg, key), cache,
                        jax.random.randint(key, (B,), 0, cfg.vocab_size
                                           ).astype(jnp.int32))
        else:
            c_specs = transformer.cache_specs(cfg, B, S)
            c_logical = transformer.cache_logical()

            def step(params, cache, tokens):
                return transformer.decode_step(params, cache, tokens, cfg)

            def make_args(key):
                cache = transformer.init_cache(cfg, B, S)
                cache["length"] = jnp.asarray(S // 2, jnp.int32)
                return (mod.init_params(cfg, key), cache,
                        jax.random.randint(key, (B,), 0, cfg.vocab_size
                                           ).astype(jnp.int32))

        arg_specs = (p_specs, c_specs, jax.ShapeDtypeStruct((B,), jnp.int32))
        arg_logical = (p_logical, c_logical, ("dp",))
        return Cell(arch, shape, cfg, step, arg_specs, arg_logical,
                    make_args, donate=(1,))

    # vision / diffusion serve
    if cfg.family in ("vit", "resnet"):
        i_spec = jax.ShapeDtypeStruct(
            (shape.global_batch, shape.img_res, shape.img_res, 3), jnp.float32)

        def step(params, images):
            return mod.serve_step(params, images, cfg)

        def make_args(key):
            return (mod.init_params(cfg, key),
                    jax.random.normal(key, i_spec.shape, jnp.float32))

        return Cell(arch, shape, cfg, step, (p_specs, i_spec),
                    (p_logical, ("dp", None, None, None)), make_args)

    if cfg.family == "vlm":
        specs = _vlm_batch_specs(cfg, shape)
        f_spec, t_spec = specs["images"], specs["tokens"]

        def step(params, frames, tokens):
            return kimi_vl.serve_step(params, frames, tokens, cfg)

        def make_args(key):
            return (mod.init_params(cfg, key),
                    jax.random.normal(key, f_spec.shape, jnp.float32),
                    jax.random.randint(key, t_spec.shape, 0,
                                       cfg.lm.vocab_size).astype(jnp.int32))

        return Cell(arch, shape, cfg, step, (p_specs, f_spec, t_spec),
                    (p_logical, ("dp", None, None, None), ("dp", None)),
                    make_args)

    if cfg.family == "dit":
        B = shape.global_batch
        lr = cfg.latent_res(shape.img_res)
        l_spec = jax.ShapeDtypeStruct((B, lr, lr, cfg.latent_channels),
                                      jnp.float32)

        def step(params, latents, t, y):
            return dit.serve_step(params, latents, t, y, cfg)

        def make_args(key):
            return (mod.init_params(cfg, key),
                    jax.random.normal(key, l_spec.shape, jnp.float32),
                    jnp.full((B,), 500, jnp.int32),
                    jnp.zeros((B,), jnp.int32))

        arg_specs = (p_specs, l_spec, jax.ShapeDtypeStruct((B,), jnp.int32),
                     jax.ShapeDtypeStruct((B,), jnp.int32))
        arg_logical = (p_logical, ("dp", None, None, None), ("dp",), ("dp",))
        return Cell(arch, shape, cfg, step, arg_specs, arg_logical, make_args)

    # unet serve
    B = shape.global_batch
    lr = shape.img_res // 8 if shape.img_res else cfg.latent_res
    l_spec = jax.ShapeDtypeStruct((B, lr, lr, cfg.latent_channels), jnp.float32)
    c_spec = jax.ShapeDtypeStruct((B, cfg.ctx_len, cfg.ctx_dim), jnp.float32)

    def step(params, latents, t, ctx):
        return unet.serve_step(params, latents, t, ctx, cfg)

    def make_args(key):
        return (mod.init_params(cfg, key),
                jax.random.normal(key, l_spec.shape, jnp.float32),
                jnp.full((B,), 500, jnp.int32),
                jax.random.normal(jax.random.fold_in(key, 1), c_spec.shape,
                                  jnp.float32))

    arg_specs = (p_specs, l_spec, jax.ShapeDtypeStruct((B,), jnp.int32), c_spec)
    arg_logical = (p_logical, ("dp", "sp", None, None), ("dp",),
                   ("dp", None, None))
    return Cell(arch, shape, cfg, step, arg_specs, arg_logical, make_args)
