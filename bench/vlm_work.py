"""Work counts from shapes for the vision-language serving cells: the
operations and bytes a request needs, kept with the benchmark so that no
change to the program can move them.

FLOPs count 2 per multiply-add of the model as published, in the textbook
(unabsorbed) form of latent attention; causal attention over a context of
``s`` keys costs ``2 H (dn + dr) s`` for the scores and ``2 H dv s`` for
the weighted sum.  Routed-expert work is counted from the program's
counter of (token, held expert) routes, since which experts a token picks
depends on the weights.  ``cfg`` is the configuration file's dict.
"""
from __future__ import annotations

from typing import Iterable


def image_tokens(cfg: dict, traffic: dict) -> int:
    p = cfg["vision"]["patch"] * cfg["projector"]["merge"]
    return (traffic["frame_h"] // p) * (traffic["frame_w"] // p)


def tower_block_flops(cfg: dict, traffic: dict) -> int:
    """One frame through MoonViT's encoder blocks (``kernels.vit_block``):
    q/k/v/o projections, scores and weighted sum, the MLP."""
    v = cfg["vision"]
    d, f = v["hidden_size"], v["intermediate_size"]
    n = (traffic["frame_h"] // v["patch"]) * (traffic["frame_w"] // v["patch"])
    return 2 * v["num_hidden_layers"] * (4 * n * d * d + 2 * n * n * d
                                         + 2 * n * d * f)


def frame_encode_flops(cfg: dict, traffic: dict) -> int:
    """Patch embedding, the blocks and the projector, one frame."""
    v, pj = cfg["vision"], cfg["projector"]
    d, p = v["hidden_size"], v["patch"]
    n = (traffic["frame_h"] // p) * (traffic["frame_w"] // p)
    m = d * pj["merge"] ** 2
    proj = image_tokens(cfg, traffic) * (m * m + m * cfg["hidden_size"])
    return tower_block_flops(cfg, traffic) + 2 * (n * p * p * 3 * d + proj)


def mla_weights(cfg: dict) -> int:
    """Parameters of one layer's latent attention."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    return d * H * (dn + dr) + d * (r + dr) + r + r * H * (dn + dv) + H * dv * d


def lm_token_flops(cfg: dict, context: int) -> int:
    """One token through every layer, routed experts and head left out,
    attending to ``context`` keys (itself included)."""
    d, H, L = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_hidden_layers"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    k = cfg["first_k_dense_replace"]
    attn = mla_weights(cfg) - cfg["kv_lora_rank"] + H * (dn + dr + dv) * context
    dense = 3 * d * cfg["intermediate_size"]
    moe = (3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
           + d * cfg["n_routed_experts"])
    return 2 * (L * attn + k * dense + (L - k) * moe)


def expert_route_flops(cfg: dict) -> int:
    """One (token, expert) route: the expert's SwiGLU."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def request_flops(cfg: dict, traffic: dict, prompt_len: int) -> int:
    """A request's work outside the routed experts: its frame, the prefill
    of [image, prompt], the answer's decode steps and the head wherever a
    token is picked (the prompt's last position and each decode step)."""
    n = image_tokens(cfg, traffic) + prompt_len
    A = traffic["answer_tokens"]
    prefill = sum(lm_token_flops(cfg, s + 1) for s in range(n))
    decode = sum(lm_token_flops(cfg, n + t + 1) for t in range(A - 1))
    head = A * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return frame_encode_flops(cfg, traffic) + prefill + decode + head


def served_flops(cfg: dict, traffic: dict, prompt_lens: Iterable[int],
                 routes: int) -> int:
    """Model FLOPs of answered requests with these prompt lengths, whose
    tokens made ``routes`` (token, held expert) routes; no padding."""
    return (sum(request_flops(cfg, traffic, n) for n in prompt_lens)
            + routes * expert_route_flops(cfg))


def mla_decode_bytes(cfg: dict, traffic: dict, prompt_lens: Iterable[int],
                     batches: int) -> int:
    """Compulsory bytes of decode attention (bf16): every layer's latent
    cache up to each request's length at every decode step, plus every
    layer's latent-attention weights once a step of each batch."""
    L, A = cfg["num_hidden_layers"], traffic["answer_tokens"]
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    img = image_tokens(cfg, traffic)
    cache = sum(img + n + t + 1 for n in prompt_lens for t in range(A - 1))
    return 2 * L * (cache * width + batches * (A - 1) * mla_weights(cfg))


def moe_expert_bytes(cfg: dict, routes: int, experts_used: int) -> int:
    """Compulsory bytes of the routed experts (bf16): each held expert's
    weights once per layer and pass in which a token picked it, and each
    route's row in and out."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 2 * (experts_used * 3 * d * f + routes * 2 * d)
