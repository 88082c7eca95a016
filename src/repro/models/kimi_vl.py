"""Kimi-VL: MoonViT tower -> projector -> DeepSeek-V3-block LM, answering
questions about camera frames through the serving engine.

A request is a frame and a prompt's token ids.  The tower encodes the
frame's patches (``vit.encode``, the encoder block DeiT shares), the
projector merges each 2 x 2 patches into one LM token, and the LM
prefills [image tokens, prompt] (``transformer.prefill``, latent
attention and the held experts' MoE) and then greedily decodes the
answer through the latent cache in one device loop.

:class:`Runner` is a replica's ``run_batch`` for
:class:`repro.serving.engine.ServingReplica`: it pads a batch to
``max_batch`` rows (padding rows route to no expert) and each prompt to
a multiple of :data:`PROMPT_BLOCK` tokens, so the prefill compiles once
per prompt block count and the decode loop once.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import VLMConfig
from repro.models import common, transformer, vit

PyTree = Any
PROMPT_BLOCK = 32


def param_defs(cfg: VLMConfig) -> Dict[str, common.ParamDef]:
    dv, d = cfg.vision.d_model, cfg.lm.d_model
    m = dv * cfg.merge ** 2
    dt = jnp.dtype(cfg.lm.param_dtype)
    defs = {f"vision/{k}": v for k, v in vit.param_defs(cfg.vision).items()}
    defs.update({f"lm/{k}": v for k, v in transformer.param_defs(cfg.lm).items()})
    defs.update({
        "projector/ln/scale": common.ParamDef((dv,), "ones", dtype=dt),
        "projector/ln/bias": common.ParamDef((dv,), "zeros", dtype=dt),
        "projector/w1": common.ParamDef((m, m), dtype=dt),
        "projector/b1": common.ParamDef((m,), "zeros", dtype=dt),
        "projector/w2": common.ParamDef((m, d), dtype=dt),
        "projector/b2": common.ParamDef((d,), "zeros", dtype=dt),
    })
    return defs


def param_specs(cfg): return common.param_specs(param_defs(cfg))
def init_params(cfg, key): return common.init_params(param_defs(cfg), key)


def param_logical(cfg: VLMConfig) -> Dict[str, Tuple]:
    log = {f"vision/{k}": v for k, v in vit.param_logical(cfg.vision).items()}
    log.update({f"lm/{k}": v
                for k, v in transformer.param_logical(cfg.lm).items()})
    log.update({"projector/ln/scale": (None,), "projector/ln/bias": (None,),
                "projector/w1": ("fsdp", "tp"), "projector/b1": ("tp",),
                "projector/w2": ("tp", "fsdp"), "projector/b2": (None,)})
    return log


def encode_images(params: PyTree, frames: jnp.ndarray, cfg: VLMConfig
                  ) -> jnp.ndarray:
    """frames (B, H, W, 3) -> image tokens (B, image_tokens, d_lm)."""
    x = vit.encode(params["vision"], frames, cfg.vision)
    B, _, dv = x.shape
    m = cfg.merge
    gh, gw = frames.shape[1] // cfg.vision.patch, frames.shape[2] // cfg.vision.patch
    p = params["projector"]
    with jax.named_scope("kernels.projector"):
        x = common.layer_norm(x, p["ln"]["scale"], p["ln"]["bias"])
        x = x.reshape(B, gh // m, m, gw // m, m, dv).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(B, (gh // m) * (gw // m), m * m * dv)
        x = jnp.einsum("bnk,km->bnm", x, p["w1"]) + p["b1"]
        x = jax.nn.gelu(x, approximate=False)
        x = jnp.einsum("bnm,md->bnd", x, p["w2"]) + p["b2"]
    return x.astype(jnp.dtype(cfg.lm.param_dtype))


def prefill(params: PyTree, images: jnp.ndarray, tokens: jnp.ndarray,
            lengths: jnp.ndarray, n_rows, cfg: VLMConfig, max_len: int):
    """[image tokens, prompt] through the LM.  tokens (B, P) right-padded,
    ``lengths`` (B,) prompt lengths; rows from ``n_rows`` on are padding.
    Returns (logits of each row's first answer token (B, V) f32, cache,
    the MoE layers' picked experts at every position (L_moe, B, S, K))."""
    B, n_img = images.shape[:2]
    h = jnp.concatenate(
        [images, transformer.embed(params["lm"], tokens, cfg.lm)], axis=1)
    total = jnp.where(jnp.arange(B) < n_rows, n_img + lengths, 0)
    logits, cache = transformer.prefill(params["lm"], None, cfg.lm, max_len,
                                        embeds=h, lengths=total)
    picks = cache.pop("picks")
    return logits, cache, picks


def decode(params: PyTree, cache: Dict[str, Any], logits: jnp.ndarray,
           n_rows, cfg: VLMConfig):
    """Greedy answers: the first token from the prefill's ``logits``, the
    other ``answer_len - 1`` from decode steps through the cache, in one
    device loop.  Returns (ids (B, A) int32, logits (A, B, V) f32, routed
    counts (2,) int32 of the whole pass, the picked experts of each decode
    step (A - 1, L_moe, B, K))."""
    valid = jnp.arange(logits.shape[0]) < n_rows
    with jax.named_scope("kernels.lm_head"):
        first = jnp.argmax(logits, -1).astype(jnp.int32)

    def step(carry, _):
        cache, tok = carry
        out, cache = transformer.decode_step(params["lm"], cache, tok,
                                             cfg.lm, valid)
        picks = cache.pop("picks")
        with jax.named_scope("kernels.lm_head"):
            nxt = jnp.argmax(out, -1).astype(jnp.int32)
        return (cache, nxt), (nxt, out, picks)

    (cache, _), (ids, outs, picks) = jax.lax.scan(
        step, (cache, first), None, length=cfg.answer_len - 1)
    ids = jnp.concatenate([first[None], ids], axis=0).T
    return (ids, jnp.concatenate([logits[None], outs], axis=0),
            cache["routed"], picks)


def generate(params: PyTree, frames: jnp.ndarray, tokens: jnp.ndarray,
             lengths: jnp.ndarray, n_rows, cfg: VLMConfig, max_len: int):
    """:func:`encode_images`, :func:`prefill` and :func:`decode` in one:
    (ids, logits, routed counts, prefill picks, decode picks)."""
    images = encode_images(params, frames, cfg)
    logits, cache, picks = prefill(params, images, tokens, lengths, n_rows,
                                   cfg, max_len)
    ids, logits, routed, steps = decode(params, cache, logits, n_rows, cfg)
    return ids, logits, routed, picks, steps


def max_len(cfg: VLMConfig, max_prompt: int) -> int:
    """Cache positions a row needs: image tokens, prompt, answer."""
    return cfg.image_tokens + max_prompt + cfg.answer_len


def serve_step(params: PyTree, frames: jnp.ndarray, tokens: jnp.ndarray,
               cfg: VLMConfig):
    """Answer ids (B, answer_len) for full-length prompts (dry-run cell)."""
    B, P = tokens.shape
    return generate(params, frames, tokens, jnp.full((B,), P, jnp.int32), B,
                    cfg, max_len(cfg, P))[0]


def loss_fn(params: PyTree, batch: Dict[str, jnp.ndarray], cfg: VLMConfig):
    """Next-token loss over the prompt positions, given the frame."""
    images = encode_images(params, batch["images"], cfg)
    h = jnp.concatenate([images, transformer.embed(
        params["lm"], batch["tokens"], cfg.lm)], axis=1)
    hidden, _, _, _ = transformer.mla_forward(
        params["lm"], h, cfg.lm, jnp.ones(h.shape[:2], bool))
    loss = transformer.chunked_lm_loss(hidden[:, images.shape[1]:],
                                       params["lm"]["lm_head"], batch["labels"])
    return loss, {"loss": loss}


def make_train_step(cfg: VLMConfig, opt_cfg):
    from repro.training.optimizer import adamw_update

    def train_step(params, opt_state, batch):
        (_, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)
        params, opt_state, opt_metrics = adamw_update(params, grads,
                                                      opt_state, opt_cfg)
        return params, opt_state, dict(metrics, **opt_metrics)

    return train_step


class Request(NamedTuple):
    """A question about a frame: the frame (H, W, 3) float32 and the prompt's
    token ids; ``logits``: also return every answer token's logits."""
    frame: np.ndarray
    prompt: np.ndarray
    logits: bool = False


class Answer(NamedTuple):
    ids: np.ndarray                   # (answer_len,) greedy token ids
    first_logits: np.ndarray          # (V,) float32, first answer token
    logits: Optional[np.ndarray]      # (answer_len, V), where asked for
    # (L_moe, image_tokens + prompt + answer_len - 1, K) int16: the experts
    # each MoE layer picked for each position the request ran through
    routes: Optional[np.ndarray] = None


def _no_span(_name):
    return contextlib.nullcontext()


class Runner:
    """A replica's ``run_batch(cls_name, requests) -> answers``.

    ``max_prompt``: the longest prompt served (a multiple of
    :data:`PROMPT_BLOCK`).  ``span(name)``: a context manager around each
    phase (``prefill``, ``decode``, ``fetch``), for host tracing.
    :attr:`routed` sums, over every batch run, the (token, held expert)
    routes and the held experts given at least one, per layer and step.
    Each answer carries the experts picked for its positions (``routes``),
    for a check that follows the program's routing."""

    def __init__(self, params: PyTree, cfg: VLMConfig, max_batch: int = 8,
                 max_prompt: int = 128,
                 span: Callable[[str], Any] = _no_span):
        if max_prompt % PROMPT_BLOCK:
            raise ValueError(f"max_prompt {max_prompt} is not a multiple of "
                             f"{PROMPT_BLOCK}")
        self.params, self.cfg, self.max_batch = params, cfg, max_batch
        self.max_prompt, self.span = max_prompt, span
        self.max_len = max_len(cfg, max_prompt)
        self._encode = jax.jit(encode_images, static_argnums=2)
        self._prefill = jax.jit(prefill, static_argnums=(5, 6))
        self._decode = jax.jit(decode, static_argnums=4)
        self._row = jax.jit(lambda x, i: x[:, i])
        self._routed = jnp.zeros((2,), jnp.int32)

    @property
    def routed(self) -> np.ndarray:
        return np.asarray(self._routed, np.int64)

    def __call__(self, _cls_name, requests: List[Request]) -> List[Answer]:
        n, mb = len(requests), self.max_batch
        lens = [len(r.prompt) for r in requests]
        if max(lens) > self.max_prompt or min(lens) < 1:
            raise ValueError(f"prompt lengths {min(lens)}-{max(lens)} outside "
                             f"1-{self.max_prompt}")
        P = -(-max(lens) // PROMPT_BLOCK) * PROMPT_BLOCK
        tokens = np.zeros((mb, P), np.int32)
        lengths = np.zeros((mb,), np.int32)
        for i, r in enumerate(requests):
            tokens[i, :lens[i]], lengths[i] = r.prompt, lens[i]
        frames = np.stack([r.frame for r in requests]
                          + [requests[0].frame] * (mb - n))
        with self.span("prefill"):
            images = self._encode(self.params, jax.device_put(frames), self.cfg)
            logits, cache, picks = self._prefill(
                self.params, images, tokens, lengths, n, self.cfg,
                self.max_len)
        with self.span("decode"):
            ids, logits, routed, steps = self._decode(self.params, cache,
                                                      logits, n, self.cfg)
        self._routed = self._routed + routed
        with self.span("fetch"):
            ids, first = np.asarray(ids), np.asarray(logits[0])
            picks, steps = np.asarray(picks), np.asarray(steps)
            n_img = images.shape[1]
            return [Answer(
                ids[i], first[i],
                np.asarray(self._row(logits, i)) if r.logits else None,
                np.concatenate([picks[:, i, :n_img + lens[i]],
                                steps[:, :, i].transpose(1, 0, 2)], axis=1))
                for i, r in enumerate(requests)]
