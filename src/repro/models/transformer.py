"""Decoder-only transformer LM (dense / MoE, GQA, RoPE, sliding-window).

Covers four assigned architectures: kimi-k2-1t-a32b, granite-moe-3b-a800m,
starcoder2-7b, gemma3-27b.  Layers are *stacked* on a leading ``L`` axis and
executed with ``lax.scan`` (small HLO, remat-friendly, overlap-friendly).

Step functions:
* ``make_train_step``  — forward + chunked-vocab loss + AdamW.
* ``prefill``          — forward returning the filled KV cache + last logits.
* ``decode_step``      — one token against a full KV cache.
* ``decode_step_sliding`` — gemma3 path: ring-buffer window caches for local
  layers, full caches only for the 1-in-6 global layers (the sub-quadratic
  structure that makes ``long_500k`` feasible).

Latent-attention configs (``cfg.mla``, Kimi-VL's DeepSeek-V3 block) keep
their ``first_k_dense`` leading dense layers in a stack of their own
(``dense_layers``) before the MoE stack (``layers``), take input
embeddings in ``prefill`` (a VLM's image tokens), and cache one latent a
token (``models/mla.py``) with a length per row.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LMConfig
from repro.distributed import sharding as shd
from repro.models import attention as attn
from repro.models import common, moe

PyTree = Any
NO_WINDOW = 1 << 30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def _dtype(cfg: LMConfig):
    return jnp.dtype(cfg.param_dtype)


def _stack_defs(stack: str, L: int, cfg: LMConfig, moe_ffn: bool,
                f: int) -> Dict[str, common.ParamDef]:
    """One stack of ``L`` layers: norms, attention, and an MoE or dense FFN
    of width ``f``."""
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = _dtype(cfg)
    defs = {"ln1": common.ParamDef((L, d), "zeros", dtype=dt),
            "ln2": common.ParamDef((L, d), "zeros", dtype=dt)}
    if cfg.mla:
        r, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        defs.update({
            "wq": common.ParamDef((L, d, H * (dn + dr)), dtype=dt),
            "wkv_a": common.ParamDef((L, d, r + dr), dtype=dt),
            "kv_norm": common.ParamDef((L, r), "zeros", dtype=dt),
            "wkv_b": common.ParamDef((L, r, H * (dn + cfg.v_head_dim)), dtype=dt),
            "wo": common.ParamDef((L, H * cfg.v_head_dim, d), dtype=dt),
        })
    else:
        defs.update({
            "wq": common.ParamDef((L, d, H * hd), dtype=dt),
            "wk": common.ParamDef((L, d, KV * hd), dtype=dt),
            "wv": common.ParamDef((L, d, KV * hd), dtype=dt),
            "wo": common.ParamDef((L, H * hd, d), dtype=dt),
        })
    if moe_ffn:
        lo, hi = cfg.experts_here
        E = cfg.n_experts_eff
        Eh = hi - lo if cfg.held_experts else E
        defs.update({
            "router": common.ParamDef((L, d, E), dtype=jnp.float32),
            "we_gate": common.ParamDef((L, Eh, d, f), dtype=dt),
            "we_up": common.ParamDef((L, Eh, d, f), dtype=dt),
            "we_down": common.ParamDef((L, Eh, f, d), dtype=dt),
        })
        if cfg.router == "sigmoid":
            defs["router_bias"] = common.ParamDef((L, E), scale=0.05,
                                                  dtype=jnp.float32)
        if cfg.n_shared_experts:
            fs = f * cfg.n_shared_experts
            defs.update({
                "ws_gate": common.ParamDef((L, d, fs), dtype=dt),
                "ws_up": common.ParamDef((L, d, fs), dtype=dt),
                "ws_down": common.ParamDef((L, fs, d), dtype=dt),
            })
    else:
        defs["w_gate"] = common.ParamDef((L, d, f), dtype=dt)
        if not cfg.mlp_gelu():
            defs["w_up"] = common.ParamDef((L, d, f), dtype=dt)
        defs["w_down"] = common.ParamDef((L, f, d), dtype=dt)
    return {f"{stack}/{k}": v for k, v in defs.items()}


def param_defs(cfg: LMConfig) -> Dict[str, common.ParamDef]:
    L, d, V, k = cfg.n_layers, cfg.d_model, cfg.vocab_size, cfg.first_k_dense
    dt = _dtype(cfg)
    defs = {
        "embed": common.ParamDef((V, d), "embed", dtype=dt),
        "final_norm": common.ParamDef((d,), "zeros", dtype=dt),
        "lm_head": common.ParamDef((d, V), dtype=dt),
    }
    defs.update(_stack_defs("layers", L - k, cfg, cfg.moe, cfg.d_ff))
    if k:
        defs.update(_stack_defs("dense_layers", k, cfg, False, cfg.dense_d_ff))
    return defs


def param_specs(cfg: LMConfig) -> PyTree:
    return common.param_specs(param_defs(cfg))


def init_params(cfg: LMConfig, key: jax.Array) -> PyTree:
    return common.init_params(param_defs(cfg), key)


def param_logical(cfg: LMConfig) -> Dict[str, Tuple]:
    """Logical sharding axes aligned with ``param_defs`` paths."""
    log = {
        "embed": ("tp", "fsdp"),
        "final_norm": (None,),
        "lm_head": ("fsdp", "tp"),
        "layers/ln1": (None, None),
        "layers/ln2": (None, None),
        "layers/wq": (None, "fsdp", "tp"),
        # kv projections shard over tp only when n_kv_heads divides the tp
        # size (the 'tp_kv' rule, installed per mesh) — sub-head sharding
        # makes GSPMD partial-sum every attention score tensor (§Perf A4)
        "layers/wk": (None, "fsdp", "tp_kv"),
        "layers/wv": (None, "fsdp", "tp_kv"),
        "layers/wo": (None, "tp", "fsdp"),
    }
    if cfg.mla:
        del log["layers/wk"], log["layers/wv"]
        log.update({"layers/wkv_a": (None, "fsdp", None),
                    "layers/kv_norm": (None, None),
                    "layers/wkv_b": (None, None, "tp")})
    if cfg.router == "sigmoid":
        log["layers/router_bias"] = (None, None)
    if cfg.moe:
        if cfg.moe_shard_mode() == "expert":
            log.update({
                "layers/router": (None, "fsdp", None),
                "layers/we_gate": (None, "tp", "fsdp", None),
                "layers/we_up": (None, "tp", "fsdp", None),
                "layers/we_down": (None, "tp", None, "fsdp"),
            })
        else:   # shard each expert's hidden dim instead (E not divisible)
            log.update({
                "layers/router": (None, "fsdp", None),
                "layers/we_gate": (None, None, "fsdp", "tp"),
                "layers/we_up": (None, None, "fsdp", "tp"),
                "layers/we_down": (None, None, "tp", "fsdp"),
            })
        if cfg.n_shared_experts:
            log.update({
                "layers/ws_gate": (None, "fsdp", "tp"),
                "layers/ws_up": (None, "fsdp", "tp"),
                "layers/ws_down": (None, "tp", "fsdp"),
            })
    else:
        log["layers/w_gate"] = (None, "fsdp", "tp")
        if not cfg.mlp_gelu():
            log["layers/w_up"] = (None, "fsdp", "tp")
        log["layers/w_down"] = (None, "tp", "fsdp")
    if cfg.first_k_dense:
        for k, v in list(log.items()):
            if k.startswith("layers/") and not k.startswith(
                    ("layers/router", "layers/we_", "layers/ws_")):
                log["dense_" + k] = v
        log.update({"dense_layers/w_gate": (None, "fsdp", "tp"),
                    "dense_layers/w_up": (None, "fsdp", "tp"),
                    "dense_layers/w_down": (None, "tp", "fsdp")})
    return log


def _layer_windows(cfg: LMConfig) -> jnp.ndarray:
    """Per-layer attention window (NO_WINDOW = full causal)."""
    idx = jnp.arange(cfg.n_layers)
    if cfg.sliding_window is None:
        return jnp.full((cfg.n_layers,), NO_WINDOW, jnp.int32)
    if cfg.global_every > 0:
        is_global = (idx + 1) % cfg.global_every == 0
        return jnp.where(is_global, NO_WINDOW, cfg.sliding_window).astype(jnp.int32)
    return jnp.full((cfg.n_layers,), cfg.sliding_window, jnp.int32)


def layer_is_global(cfg: LMConfig) -> jnp.ndarray:
    idx = jnp.arange(cfg.n_layers)
    if cfg.sliding_window is None:
        return jnp.ones((cfg.n_layers,), bool)
    return (idx + 1) % max(1, cfg.global_every) == 0 if cfg.global_every else \
        jnp.zeros((cfg.n_layers,), bool)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------
def embed(params: PyTree, tokens: jnp.ndarray, cfg: LMConfig) -> jnp.ndarray:
    """Token ids -> embeddings, times sqrt(d_model) where the config says."""
    h = jnp.take(params["embed"], tokens, axis=0).astype(_dtype(cfg))
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return h


def _qkv(x, lp, cfg: LMConfig, positions):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,dh->bsh", x, lp["wq"]).reshape(B, S, H, hd)
    k = jnp.einsum("bsd,dh->bsh", x, lp["wk"]).reshape(B, S, KV, hd)
    v = jnp.einsum("bsd,dh->bsh", x, lp["wv"]).reshape(B, S, KV, hd)
    q = attn.apply_rope(q, positions, cfg.rope_theta)
    k = attn.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _ffn(x2, lp, cfg: LMConfig):
    """Returns (out, aux_loss). x2: (B, S, d)."""
    B, S, d = x2.shape
    if not cfg.moe:
        g = jnp.einsum("bsd,df->bsf", x2, lp["w_gate"])
        if cfg.mlp_gelu():
            h = common.gelu(g)
        else:
            u = jnp.einsum("bsd,df->bsf", x2, lp["w_up"])
            h = common.swiglu(g, u)
        out = jnp.einsum("bsf,fd->bsd", h, lp["w_down"])
        return out, jnp.zeros((), jnp.float32)
    flat = x2.reshape(B * S, d)
    mesh = shd.active_mesh()
    if cfg.moe_impl == "shard_map" and mesh is not None:
        rules = shd.get_rules()
        dp = rules.get("dp")
        dp_axes = (dp,) if isinstance(dp, str) else dp
        out, aux = moe.moe_ffn_sharded(
            flat, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"],
            top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
            mesh=mesh, dp_axes=dp_axes, model_axis=rules.get("tp", "model"),
            fsdp_axes=rules.get("fsdp"),
            expert_sharded=cfg.moe_shard_mode() == "expert",
            n_real=cfg.n_experts)
    else:
        out, aux = moe.moe_ffn(flat, lp["router"], lp["we_gate"],
                               lp["we_up"], lp["we_down"], top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor,
                               n_real=cfg.n_experts)
    if cfg.n_shared_experts:
        g = jnp.einsum("td,df->tf", flat, lp["ws_gate"])
        u = jnp.einsum("td,df->tf", flat, lp["ws_up"])
        out = out + jnp.einsum("tf,fd->td", common.swiglu(g, u), lp["ws_down"])
    return out.reshape(B, S, d), aux


def _block(h, lp, window, cfg: LMConfig, positions, q_offset=0,
           kv_override=None):
    """One transformer layer. Returns (h, aux, (k, v))."""
    B, S, d = h.shape
    x = common.rms_norm(h, lp["ln1"])
    q, k, v = _qkv(x, lp, cfg, positions)
    if kv_override is not None:
        k, v = kv_override
    o = attn.attention(q, k, v, causal=True, window=window,
                       impl=cfg.attn_impl, q_chunk=cfg.attn_chunk,
                       q_offset=q_offset)
    h = h + jnp.einsum("bsh,hd->bsd", o.reshape(B, S, -1), lp["wo"])
    h = shd.hint(h, "dp", None, None)
    x2 = common.rms_norm(h, lp["ln2"])
    f, aux = _ffn(x2, lp, cfg)
    h = shd.hint(h + f, "dp", None, None)
    return h, aux, (k, v)


# ---------------------------------------------------------------------------
# Forward / loss / train step
# ---------------------------------------------------------------------------
def hidden_states(params: PyTree, tokens: jnp.ndarray, cfg: LMConfig
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(B, S) tokens -> ((B, S, d) hidden, scalar aux loss)."""
    B, S = tokens.shape
    h = embed(params, tokens, cfg)
    h = shd.hint(h, "dp", None, None)
    positions = jnp.arange(S)
    windows = _layer_windows(cfg)

    def body(carry, xs):
        h, aux = carry
        lp, window = xs
        h, a, _ = _block(h, lp, window, cfg, positions)
        return (h, aux + a), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (h, aux), _ = jax.lax.scan(body_fn, (h, jnp.zeros((), jnp.float32)),
                               (params["layers"], windows))
    h = common.rms_norm(h, params["final_norm"])
    return h, aux


def logits_fn(params: PyTree, tokens: jnp.ndarray, cfg: LMConfig) -> jnp.ndarray:
    h, _ = hidden_states(params, tokens, cfg)
    logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits


def chunked_lm_loss(h: jnp.ndarray, head: jnp.ndarray, labels: jnp.ndarray,
                    chunk: int = 512) -> jnp.ndarray:
    """Mean xent without materializing (B, S, V): scan over S-chunks."""
    B, S, d = h.shape
    chunk = min(chunk, S)
    n = S // chunk
    rem = S - n * chunk
    hs = jnp.moveaxis(h[:, :n * chunk].reshape(B, n, chunk, d), 1, 0)
    ys = jnp.moveaxis(labels[:, :n * chunk].reshape(B, n, chunk), 1, 0)

    def body(tot, xs):
        hc, yc = xs
        logits = jnp.einsum("bcd,dv->bcv", hc, head,
                            preferred_element_type=jnp.float32)
        logits = shd.hint(logits, "dp", None, "tp")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(logz - gold), None

    body_fn = jax.checkpoint(body)
    tot, _ = jax.lax.scan(body_fn, jnp.zeros((), jnp.float32), (hs, ys))
    if rem:
        logits = jnp.einsum("bcd,dv->bcv", h[:, n * chunk:], head,
                            preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, n * chunk:][..., None],
                                   axis=-1)[..., 0]
        tot = tot + jnp.sum(logz - gold)
    return tot / (B * S)


def loss_fn(params: PyTree, batch: Dict[str, jnp.ndarray], cfg: LMConfig
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    h, aux = hidden_states(params, batch["tokens"], cfg)
    loss = chunked_lm_loss(h, params["lm_head"], batch["labels"])
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux}


def make_train_step(cfg: LMConfig, opt_cfg):
    from repro.training.optimizer import adamw_update

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)
        params, opt_state, opt_metrics = adamw_update(params, grads,
                                                      opt_state, opt_cfg)
        metrics = dict(metrics, **opt_metrics)
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# KV-cache serving
# ---------------------------------------------------------------------------
def cache_specs(cfg: LMConfig, batch: int, max_len: int) -> Dict[str, Any]:
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    dt = _dtype(cfg)
    shape = (L, batch, max_len, KV, hd)
    return {
        "k": jax.ShapeDtypeStruct(shape, dt),
        "v": jax.ShapeDtypeStruct(shape, dt),
        "length": jax.ShapeDtypeStruct((), jnp.int32),
    }


def cache_logical() -> Dict[str, Tuple]:
    # 'cache_seq'/'cache_kv' are installed per (mesh, config): KV-head
    # sharding when n_kv_heads divides the model axis (in-place DUS stays
    # local), else sequence sharding (flash-decoding style, at the cost of
    # GSPMD copying the shard at the dynamic update; §Perf B2).
    return {"k": (None, "dp", "cache_seq", "cache_kv", None),
            "v": (None, "dp", "cache_seq", "cache_kv", None),
            "length": ()}


def init_cache(cfg: LMConfig, batch: int, max_len: int) -> Dict[str, Any]:
    s = cache_specs(cfg, batch, max_len)
    return {"k": jnp.zeros(s["k"].shape, s["k"].dtype),
            "v": jnp.zeros(s["v"].shape, s["v"].dtype),
            "length": jnp.zeros((), jnp.int32)}


def prefill(params: PyTree, tokens: Optional[jnp.ndarray], cfg: LMConfig,
            max_len: Optional[int] = None, *,
            embeds: Optional[jnp.ndarray] = None,
            lengths: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Forward pass that also returns the KV cache (padded to max_len).

    ``embeds`` (B, S, d) are input embeddings in place of ``tokens`` (a
    VLM's image tokens and prompt).  A latent-attention config takes
    right-padded rows: ``lengths`` (B,) real tokens each (default S; 0
    for a padding row), and returns the logits at each row's last one."""
    h = embed(params, tokens, cfg) if embeds is None else embeds
    B, S = h.shape[:2]
    max_len = max_len or S
    if cfg.mla:
        lengths = jnp.full((B,), S, jnp.int32) if lengths is None else lengths
        return _mla_prefill(params, h, cfg, max_len, lengths)
    h = shd.hint(h, "dp", None, None)
    positions = jnp.arange(S)
    windows = _layer_windows(cfg)

    def body(h, xs):
        lp, window = xs
        h, _, (k, v) = _block(h, lp, window, cfg, positions)
        return h, (k, v)

    body_fn = jax.checkpoint(body) if cfg.remat else body
    h, (ks, vs) = jax.lax.scan(body_fn, h, (params["layers"], windows))
    h = common.rms_norm(h, params["final_norm"])
    last = jnp.einsum("bd,dv->bv", h[:, -1], params["lm_head"],
                      preferred_element_type=jnp.float32)
    pad = max_len - S
    if pad > 0:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {"k": shd.hint(ks, None, "dp", "cache_seq", "cache_kv", None),
             "v": shd.hint(vs, None, "dp", "cache_seq", "cache_kv", None),
             "length": jnp.asarray(S, jnp.int32)}
    return last, cache


def decode_step(params: PyTree, cache: Dict[str, Any], tokens: jnp.ndarray,
                cfg: LMConfig, valid: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step: tokens (B,) int32 -> (logits (B, V) f32, new cache).

    ``valid`` (B,): False for a padding row, whose token no expert
    computes (latent-attention configs)."""
    B = tokens.shape[0]
    if cfg.mla:
        valid = jnp.ones((B,), bool) if valid is None else valid
        return _mla_decode_step(params, cache, tokens, cfg, valid)
    pos = cache["length"]
    h = embed(params, tokens, cfg)[:, None, :]
    positions = jnp.full((B, 1), pos, jnp.int32)
    windows = _layer_windows(cfg)

    def body(h, xs):
        lp, window, k_l, v_l = xs
        x = common.rms_norm(h, lp["ln1"])
        q, k_new, v_new = _qkv(x, lp, cfg, positions)
        k_l = jax.lax.dynamic_update_slice(k_l, k_new, (0, pos, 0, 0))
        v_l = jax.lax.dynamic_update_slice(v_l, v_new, (0, pos, 0, 0))
        o = attn.attention_decode(q, k_l, v_l, pos + 1, window=window)
        h = h + jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, -1), lp["wo"])
        x2 = common.rms_norm(h, lp["ln2"])
        f, _ = _ffn(x2, lp, cfg)
        return h + f, (k_l, v_l)

    h, (ks, vs) = jax.lax.scan(
        body, h, (params["layers"], windows, cache["k"], cache["v"]))
    h = common.rms_norm(h, params["final_norm"])
    logits = jnp.einsum("bd,dv->bv", h[:, 0], params["lm_head"],
                        preferred_element_type=jnp.float32)
    new_cache = {"k": shd.hint(ks, None, "dp", "cache_seq", "cache_kv", None),
                 "v": shd.hint(vs, None, "dp", "cache_seq", "cache_kv", None),
                 "length": pos + 1}
    return logits, new_cache


# ---------------------------------------------------------------------------
# Sliding-window decode (gemma3): ring-buffer caches for local layers
# ---------------------------------------------------------------------------
def sliding_cache_specs(cfg: LMConfig, batch: int, max_len: int
                        ) -> Dict[str, Any]:
    assert cfg.sliding_window and cfg.global_every
    W = cfg.sliding_window
    KV, hd = cfg.n_kv_heads, cfg.hd
    n_global = cfg.n_layers // cfg.global_every
    n_local = cfg.n_layers - n_global
    dt = _dtype(cfg)
    return {
        "k_global": jax.ShapeDtypeStruct((n_global, batch, max_len, KV, hd), dt),
        "v_global": jax.ShapeDtypeStruct((n_global, batch, max_len, KV, hd), dt),
        "k_local": jax.ShapeDtypeStruct((n_local, batch, W, KV, hd), dt),
        "v_local": jax.ShapeDtypeStruct((n_local, batch, W, KV, hd), dt),
        "length": jax.ShapeDtypeStruct((), jnp.int32),
    }


def sliding_cache_logical() -> Dict[str, Tuple]:
    return {"k_global": (None, "dp", "cache_seq", "cache_kv", None),
            "v_global": (None, "dp", "cache_seq", "cache_kv", None),
            "k_local": (None, "dp", None, "cache_kv", None),
            "v_local": (None, "dp", None, "cache_kv", None),
            "length": ()}


def init_sliding_cache(cfg: LMConfig, batch: int, max_len: int) -> Dict[str, Any]:
    return {k: (jnp.zeros(s.shape, s.dtype) if k != "length"
                else jnp.zeros((), jnp.int32))
            for k, s in sliding_cache_specs(cfg, batch, max_len).items()}


def decode_step_sliding(params: PyTree, cache: Dict[str, Any],
                        tokens: jnp.ndarray, cfg: LMConfig
                        ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """gemma3 long-context decode: local layers touch only their W-token ring
    buffers, so per-step compute/memory is O(n_global·S + n_local·W)."""
    assert cfg.sliding_window and cfg.global_every
    B = tokens.shape[0]
    W = cfg.sliding_window
    g = cfg.global_every
    pos = cache["length"]
    ring = jnp.mod(pos, W)
    h = embed(params, tokens, cfg)[:, None, :]
    positions = jnp.full((B, 1), pos, jnp.int32)

    # split stacked layer params into local / global stacks (static indices)
    import numpy as np
    idx = np.arange(cfg.n_layers)
    glb = (idx + 1) % g == 0
    loc_idx, glb_idx = idx[~glb], idx[glb]
    p_loc = jax.tree_util.tree_map(lambda x: x[loc_idx], params["layers"])
    p_glb = jax.tree_util.tree_map(lambda x: x[glb_idx], params["layers"])

    def local_body(h, xs):
        lp, k_l, v_l = xs
        x = common.rms_norm(h, lp["ln1"])
        q, k_new, v_new = _qkv(x, lp, cfg, positions)
        k_l = jax.lax.dynamic_update_slice(k_l, k_new, (0, ring, 0, 0))
        v_l = jax.lax.dynamic_update_slice(v_l, v_new, (0, ring, 0, 0))
        # ring buffer: all slots < min(pos+1, W) are valid; relative order is
        # irrelevant to softmax.
        n_valid = jnp.minimum(pos + 1, W)
        o = attn.attention_decode(q, k_l, v_l, n_valid, window=None)
        h = h + jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, -1), lp["wo"])
        x2 = common.rms_norm(h, lp["ln2"])
        f, _ = _ffn(x2, lp, cfg)
        return h + f, (k_l, v_l)

    def global_body(h, xs):
        lp, k_l, v_l = xs
        x = common.rms_norm(h, lp["ln1"])
        q, k_new, v_new = _qkv(x, lp, cfg, positions)
        k_l = jax.lax.dynamic_update_slice(k_l, k_new, (0, pos, 0, 0))
        v_l = jax.lax.dynamic_update_slice(v_l, v_new, (0, pos, 0, 0))
        o = attn.attention_decode(q, k_l, v_l, pos + 1, window=None)
        h = h + jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, -1), lp["wo"])
        x2 = common.rms_norm(h, lp["ln2"])
        f, _ = _ffn(x2, lp, cfg)
        return h + f, (k_l, v_l)

    # Layer order: (g-1 locals, 1 global) repeated, then trailing locals.
    # Cache stacks are updated IN PLACE via indexed dynamic-update-slice
    # (donated buffers) — rebuilding them with concatenate copies the whole
    # multi-GB cache every decode step (§Perf B3).
    n_global = len(glb_idx)
    lead = g - 1
    k_loc_all, v_loc_all = cache["k_local"], cache["v_local"]
    k_glb_all, v_glb_all = cache["k_global"], cache["v_global"]

    def run_locals(h, k_all, v_all, lo, hi):
        if hi <= lo:
            return h, k_all, v_all
        sl = slice(lo, hi)
        pl = jax.tree_util.tree_map(lambda x: x[sl], p_loc)
        h, (ks, vs) = jax.lax.scan(local_body, h, (pl, k_all[sl], v_all[sl]))
        k_all = jax.lax.dynamic_update_slice_in_dim(k_all, ks, lo, 0)
        v_all = jax.lax.dynamic_update_slice_in_dim(v_all, vs, lo, 0)
        return h, k_all, v_all

    li = 0
    for gi in range(n_global):
        h, k_loc_all, v_loc_all = run_locals(h, k_loc_all, v_loc_all,
                                             li, li + lead)
        li += lead
        pg = jax.tree_util.tree_map(lambda x: x[gi], p_glb)
        h, (kg, vg) = global_body(
            h, (pg, k_glb_all[gi], v_glb_all[gi]))
        k_glb_all = jax.lax.dynamic_update_slice_in_dim(k_glb_all, kg[None],
                                                        gi, 0)
        v_glb_all = jax.lax.dynamic_update_slice_in_dim(v_glb_all, vg[None],
                                                        gi, 0)
    h, k_loc_all, v_loc_all = run_locals(h, k_loc_all, v_loc_all,
                                         li, len(loc_idx))

    h = common.rms_norm(h, params["final_norm"])
    logits = jnp.einsum("bd,dv->bv", h[:, 0], params["lm_head"],
                        preferred_element_type=jnp.float32)
    new_cache = {
        "k_local": k_loc_all,
        "v_local": v_loc_all,
        "k_global": k_glb_all,
        "v_global": v_glb_all,
        "length": pos + 1,
    }
    return logits, new_cache


# ---------------------------------------------------------------------------
# Latent attention (cfg.mla): dense stack, then MoE stack; latent cache
# ---------------------------------------------------------------------------
_MLA_ATTN = ("ln1", "wq", "wkv_a", "kv_norm", "wkv_b", "wo")


def _at(stack: PyTree, i, names) -> PyTree:
    """Layer ``i`` of the named leaves of a layer stack.  Called inside the
    scope of the op that reads them, so that the slice is charged there."""
    return {k: jax.lax.dynamic_index_in_dim(stack[k], i, 0, keepdims=False)
            for k in names}


def _mla_ffn(h: jnp.ndarray, stack: PyTree, i, cfg: LMConfig,
             valid: jnp.ndarray, dense: bool):
    """The FFN half of layer ``i`` of a stack on the float32 residual h (B,
    S, d): (output, routed counts (2,) int32, the picked experts (B, S, K)
    int16, None for a dense layer)."""
    B, S, d = h.shape
    dt = _dtype(cfg)
    if dense:
        with jax.named_scope("kernels.dense_mlp"):
            lp = _at(stack, i, ("ln2", "w_gate", "w_up", "w_down"))
            x = common.rms_norm(h, lp["ln2"], cfg.norm_eps).astype(dt)
            g = jnp.einsum("bsd,df->bsf", x, lp["w_gate"])
            u = jnp.einsum("bsd,df->bsf", x, lp["w_up"])
            out = jnp.einsum("bsf,fd->bsd", common.swiglu(g, u), lp["w_down"])
        return out, jnp.zeros((2,), jnp.int32), None
    with jax.named_scope("kernels.moe_route"):
        lp = _at(stack, i, ("ln2", "router", "router_bias"))
        flat = common.rms_norm(h, lp["ln2"], cfg.norm_eps).reshape(B * S, d)
    # the router reads the float32 norm, the experts its bfloat16 rounding;
    # the experts' weights are read in place, layer i of the stacks
    out, routed, experts = moe.moe_held(
        flat, valid.reshape(-1), lp["router"], lp["router_bias"],
        stack["we_gate"], stack["we_up"], stack["we_down"], top_k=cfg.top_k,
        scale=cfg.routed_scale, first=cfg.experts_here[0], layer=i)
    with jax.named_scope("kernels.moe_shared"):
        lp = _at(stack, i, ("ws_gate", "ws_up", "ws_down"))
        xb = flat.astype(dt)
        g = jnp.einsum("td,df->tf", xb, lp["ws_gate"])
        u = jnp.einsum("td,df->tf", xb, lp["ws_up"])
        out = out + jnp.einsum("tf,fd->td", common.swiglu(g, u), lp["ws_down"])
    return (out.reshape(B, S, d), routed,
            experts.reshape(B, S, -1).astype(jnp.int16))


def _mla_stacks(params: PyTree, cfg: LMConfig):
    """(stack params, is dense, layers) in layer order."""
    out = [(params["dense_layers"], True)] if cfg.first_k_dense else []
    out.append((params["layers"], False))
    return [(s, dense, jax.tree_util.tree_leaves(s)[0].shape[0])
            for s, dense in out]


def _scan_layers(body, carry, n: int):
    """``lax.scan`` of ``body(carry, i)`` over layers ``0 .. n - 1``; a stack
    of one layer runs once at index 0, which reads its weights in place."""
    if n == 1:
        carry, ys = body(carry, 0)
        return carry, jax.tree.map(lambda y: y[None], ys)
    return jax.lax.scan(body, carry, jnp.arange(n))


def mla_forward(params: PyTree, h: jnp.ndarray, cfg: LMConfig,
                valid: jnp.ndarray):
    """Every layer over input embeddings h (B, S, d), causal, with the
    residual stream in float32 and every matrix multiply in the weights'
    dtype.  ``valid`` (B, S) is False on padding, which no expert computes.
    Returns (final-normed hidden (B, S, d), latents (L, B, S, r + dr),
    routed counts (2,) int32, the MoE layers' picked experts (L_moe, B,
    S, K) int16)."""
    from repro.models import mla
    dt = _dtype(cfg)
    positions = jnp.arange(h.shape[1])
    lats, routed = [], jnp.zeros((2,), jnp.int32)
    h = h.astype(jnp.float32)
    for stack, dense, n in _mla_stacks(params, cfg):
        def body(carry, i, stack=stack, dense=dense):
            h, routed = carry
            with jax.named_scope("kernels.mla_prefill"):
                lp = _at(stack, i, _MLA_ATTN)
                x = common.rms_norm(h, lp["ln1"], cfg.norm_eps).astype(dt)
                o, lat = mla.prefill_attention(x, lp, cfg, positions)
            h = h + o
            f, k, picks = _mla_ffn(h, stack, i, cfg, valid, dense)
            return (h + f, routed + k), (lat, picks)

        (h, routed), (lat, picks) = _scan_layers(body, (h, routed), n)
        lats.append(lat)
    with jax.named_scope("kernels.lm_head"):
        h = common.rms_norm(h, params["final_norm"], cfg.norm_eps).astype(dt)
    return h, jnp.concatenate(lats, axis=0), routed, picks


def _lm_head(params: PyTree, h: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("kernels.lm_head"):
        return jnp.einsum("bd,dv->bv", h, params["lm_head"],
                          preferred_element_type=jnp.float32)


def _mla_prefill(params: PyTree, embeds: jnp.ndarray, cfg: LMConfig,
                 max_len: int, lengths: jnp.ndarray):
    B, S, _ = embeds.shape
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    h, lats, routed, picks = mla_forward(params, embeds, cfg, valid)
    last = h[jnp.arange(B), jnp.maximum(lengths - 1, 0)]
    lats = jnp.pad(lats, ((0, 0), (0, 0), (0, max_len - S), (0, 0)))
    return _lm_head(params, last), {"latent": lats, "length": lengths,
                                    "routed": routed, "picks": picks}


def mla_cache_specs(cfg: LMConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """The latent cache: ``[c; k_pe]`` per layer, row and position, each
    row's valid length, and the routed counts of the pass so far."""
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return {"latent": jax.ShapeDtypeStruct(
                (cfg.n_layers, batch, max_len, width), _dtype(cfg)),
            "length": jax.ShapeDtypeStruct((batch,), jnp.int32),
            "routed": jax.ShapeDtypeStruct((2,), jnp.int32)}


def _mla_decode_step(params: PyTree, cache: Dict[str, Any],
                     tokens: jnp.ndarray, cfg: LMConfig,
                     valid: jnp.ndarray):
    """One token a row: each row's token goes to its own slot
    ``cache["length"]``, where its RoPE position is too.  The new cache
    holds the step's picked experts (L_moe, B, K) int16 under
    ``"picks"``."""
    from repro.models import mla
    B = tokens.shape[0]
    pos = cache["length"]
    rows = jnp.arange(B)
    h = embed(params, tokens, cfg)[:, None, :].astype(jnp.float32)
    latent, routed = cache["latent"], cache["routed"]
    first = 0
    for stack, dense, n in _mla_stacks(params, cfg):
        def body(carry, i, stack=stack, dense=dense, first=first):
            h, latent, routed = carry
            with jax.named_scope("kernels.mla_decode"):
                lp = _at(stack, i, _MLA_ATTN)
                x = common.rms_norm(h, lp["ln1"], cfg.norm_eps).astype(
                    _dtype(cfg))
                q_nope, q_pe, lat = mla.project(x, lp, cfg, pos[:, None])
                latent = latent.at[first + i, rows, pos].set(
                    lat[:, 0].astype(latent.dtype))
                o = mla.decode_attention(q_nope, q_pe, latent[first + i],
                                         pos + 1, lp, cfg)
            h = h + o
            f, k, picks = _mla_ffn(h, stack, i, cfg, valid[:, None], dense)
            return (h + f, latent, routed + k), picks

        (h, latent, routed), picks = _scan_layers(
            body, (h, latent, routed), n)
        first += n
    with jax.named_scope("kernels.lm_head"):
        h = common.rms_norm(h, params["final_norm"], cfg.norm_eps).astype(
            _dtype(cfg))
    return _lm_head(params, h[:, 0]), {"latent": latent, "length": pos + 1,
                                       "routed": routed,
                                       "picks": picks[:, :, 0]}
