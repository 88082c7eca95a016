"""Architecture / shape configuration dataclasses.

Every assigned architecture gets one module in ``repro/configs`` exporting
``CONFIG`` (the exact published configuration) and ``SMOKE_CONFIG`` (a
reduced same-family configuration for CPU smoke tests).  Shapes are defined
per family in ``repro/configs/shapes.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """Decoder-only transformer LM (dense or MoE)."""
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int                      # per-expert d_ff for MoE
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // n_heads
    # MoE
    moe: bool = False
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    n_shared_experts: int = 0      # DeepSeek/Kimi-style shared expert(s)
    # attention flavor
    rope_theta: float = 10_000.0
    sliding_window: Optional[int] = None   # window size for local layers
    global_every: int = 0          # every Nth layer is global (gemma3: 6)
    # MLP flavor: swiglu (llama-family) | gelu (starcoder2)
    mlp: str = "swiglu"
    # MoE weight sharding: expert (E over tp) | ffn (per-expert d_ff over tp)
    moe_shard: str = "expert"
    # MoE dispatch: global (einsum/GSPMD baseline) | shard_map (local
    # dispatch + psum combine — the §Perf optimization)
    moe_impl: str = "global"
    # pad the expert dimension to this count (0 = off): makes a non-divisible
    # expert count (granite's 40) expert-shardable over the 16-way model axis
    # (dummy experts are masked out of routing; §Perf iteration A3)
    n_experts_pad: int = 0
    # multi-head latent attention (DeepSeek-V2/V3; kv_lora_rank 0 = GQA):
    # keys and values come from a kv_lora_rank latent; q and k carry
    # qk_nope_head_dim channels without and qk_rope_head_dim with RoPE
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # leading dense layers of width dense_d_ff, a stack of their own
    # (DeepSeek's first_k_dense_replace); the MoE stack follows
    first_k_dense: int = 0
    dense_d_ff: int = 0
    # router: softmax | sigmoid (noaux_tc: a per-expert bias picks the
    # top-k, the unbiased scores weigh them, normalized, times routed_scale;
    # no token is dropped)
    router: str = "softmax"
    routed_scale: float = 1.0
    # experts [lo, hi) of n_experts held by this chip (expert parallelism);
    # routing is over all n_experts, the layer computes its own experts' part
    held_experts: Optional[Tuple[int, int]] = None
    embed_scale: bool = True       # embeddings times sqrt(d_model) (Gemma)
    norm_eps: float = 1e-6         # RMSNorm epsilon of the latent-attention path

    @property
    def n_experts_eff(self) -> int:
        return max(self.n_experts, self.n_experts_pad)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def experts_here(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.n_experts)
    # ZeRO: additionally shard weights/opt-state over the pod axis (needed by
    # trillion-parameter configs to fit v5e HBM; see DESIGN.md §5)
    zero_over_pods: bool = False
    # numerics
    param_dtype: str = "bfloat16"
    opt_state_dtype: str = "float32"
    remat: bool = True
    attn_impl: str = "chunked"     # naive | chunked | pallas
    attn_chunk: int = 1024
    family: str = "lm"

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def mlp_gelu(self) -> bool:
        return self.mlp == "gelu"

    def moe_shard_mode(self) -> str:
        return self.moe_shard

    def active_params(self) -> int:
        """Approximate active parameter count (per-token) for MODEL_FLOPS."""
        d, hd = self.d_model, self.hd
        attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
            + (self.n_heads * hd) * d
        nmat = 2 if self.mlp == "gelu" else 3
        if self.moe:
            ffn = nmat * d * self.d_ff * (self.top_k + self.n_shared_experts)
            router = d * self.n_experts
        else:
            ffn = nmat * d * self.d_ff
            router = 0
        per_layer = attn + ffn + router + 2 * d
        return self.n_layers * per_layer + 2 * self.vocab_size * d

    def total_params(self) -> int:
        d, hd = self.d_model, self.hd
        attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
            + (self.n_heads * hd) * d
        nmat = 2 if self.mlp == "gelu" else 3
        if self.moe:
            ffn = nmat * d * self.d_ff * (self.n_experts + self.n_shared_experts)
            router = d * self.n_experts
        else:
            ffn = nmat * d * self.d_ff
            router = 0
        per_layer = attn + ffn + router + 2 * d
        return self.n_layers * per_layer + 2 * self.vocab_size * d


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    """Vision transformer (ViT / DeiT) encoder."""
    name: str
    img_res: int
    patch: int
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    n_classes: int = 1000           # 0: no head, every patch token out
    class_token: bool = True
    distill_token: bool = False     # DeiT
    in_channels: int = 3
    # 2-D RoPE on q and k (MoonViT): half of each head's rotary pairs turn
    # with the patch's column, half with its row
    rope_2d: bool = False
    pos_interp: str = "bilinear"    # resizing the learned position table
    param_dtype: str = "bfloat16"
    remat: bool = True
    attn_impl: str = "chunked"
    attn_chunk: int = 512
    family: str = "vit"

    @property
    def n_extra(self) -> int:
        return int(self.class_token) + int(self.distill_token)

    def n_tokens(self, img_res: Optional[int] = None) -> int:
        r = img_res or self.img_res
        return (r // self.patch) ** 2 + self.n_extra

    def total_params(self) -> int:
        d = self.d_model
        per_layer = 4 * d * d + 2 * d * self.d_ff + 4 * d
        patch_embed = self.in_channels * self.patch ** 2 * d
        return self.n_layers * per_layer + patch_embed + d * self.n_classes


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    name: str
    img_res: int
    depths: Tuple[int, ...] = (3, 4, 6, 3)
    width: int = 64
    n_classes: int = 1000
    in_channels: int = 3
    param_dtype: str = "bfloat16"
    family: str = "resnet"

    def total_params(self) -> int:
        return 25_600_000   # nominal ResNet-50


@dataclasses.dataclass(frozen=True)
class DiTConfig:
    name: str
    img_res: int
    patch: int
    n_layers: int
    d_model: int
    n_heads: int
    latent_factor: int = 8          # VAE downsample (f8)
    latent_channels: int = 4
    n_classes: int = 1000
    param_dtype: str = "bfloat16"
    remat: bool = True
    attn_impl: str = "chunked"
    attn_chunk: int = 512
    family: str = "dit"

    def latent_res(self, img_res: Optional[int] = None) -> int:
        return (img_res or self.img_res) // self.latent_factor

    def n_tokens(self, img_res: Optional[int] = None) -> int:
        return (self.latent_res(img_res) // self.patch) ** 2

    @property
    def d_ff(self) -> int:
        return 4 * self.d_model

    def total_params(self) -> int:
        d = self.d_model
        per_layer = 4 * d * d + 2 * d * self.d_ff + 6 * d * d  # attn+mlp+adaLN
        return self.n_layers * per_layer


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    name: str
    img_res: int
    latent_res: int
    ch: int = 320
    ch_mult: Tuple[int, ...] = (1, 2, 4, 4)
    n_res_blocks: int = 2
    attn_levels: Tuple[int, ...] = (0, 1, 2)   # levels with transformer blocks
    ctx_dim: int = 768                         # text-encoder context (stub)
    ctx_len: int = 77
    n_heads: int = 8
    latent_channels: int = 4
    param_dtype: str = "bfloat16"
    remat: bool = True
    family: str = "unet"

    def total_params(self) -> int:
        return 860_000_000  # nominal SD1.5 UNet


@dataclasses.dataclass(frozen=True)
class VLMConfig:
    """Vision-language model: a ViT tower, a projector that merges
    ``merge`` x ``merge`` patches into one LM token, and a decoder LM that
    answers ``answer_len`` greedy tokens about a ``frame_hw`` frame."""
    name: str
    vision: ViTConfig
    lm: LMConfig
    merge: int = 2
    frame_hw: Tuple[int, int] = (504, 896)
    answer_len: int = 32
    family: str = "vlm"

    @property
    def image_tokens(self) -> int:
        h, w = self.frame_hw
        p = self.vision.patch * self.merge
        return (h // p) * (w // p)


ArchConfig = object  # union marker; families dispatch on .family
