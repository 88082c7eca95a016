"""Kimi-VL-A3B-Instruct [hf:moonshotai/Kimi-VL-A3B-Instruct config.json;
arXiv:2504.07491].

Language model (DeepSeek-V3 block, as Moonlight-16B-A3B): 27L d_model=2048,
16 heads of multi-head latent attention (q one projection to 16 x 192,
kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128), layer 0 a dense SwiGLU
of width 11264, then 26 MoE layers of 64 routed experts (width 1408, top-6,
sigmoid scores with a selection-only bias, normalized, x 2.446) and 2
shared experts; vocab 163840, untied head, rope_theta 800000, RMSNorm eps
1e-5, no sqrt(d) embedding scale.

Tower (MoonViT, the report's section 2 and the published vision_config):
pre-LN ViT, patch 14, d=1152, 27L, 16 heads, MLP 4304 (tanh GELU), a
learned 64 x 64 position table resized bicubically to the patch grid, 2-D
RoPE, no class token, final LayerNorm.  Projector: LayerNorm(1152), 2 x 2
patch merge to 4608, Linear 4608 -> 4608, GELU, Linear -> 2048.

``held_experts``: one chip of a four-chip host that splits the 64 experts
four ways (expert parallelism, attention data-parallel) holds 16.
"""
from repro.configs.base import LMConfig, ViTConfig, VLMConfig

_LM = dict(router="sigmoid", embed_scale=False, moe=True,
           moe_impl="global", remat=False, attn_impl="naive")

CONFIG = VLMConfig(
    name="kimi-vl-a3b",
    vision=ViTConfig(
        name="moonvit", img_res=64 * 14, patch=14, n_layers=27, d_model=1152,
        n_heads=16, d_ff=4304, n_classes=0, class_token=False, rope_2d=True,
        pos_interp="bicubic", remat=False, attn_impl="naive"),
    lm=LMConfig(
        name="kimi-vl-a3b-lm", n_layers=27, d_model=2048, n_heads=16,
        n_kv_heads=16, d_ff=1408, vocab_size=163840, n_experts=64, top_k=6,
        n_shared_experts=2, rope_theta=800_000.0, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        first_k_dense=1, dense_d_ff=11264, routed_scale=2.446,
        held_experts=(0, 16), norm_eps=1e-5, **_LM),
    frame_hw=(504, 896), answer_len=32,
)

SMOKE_CONFIG = VLMConfig(
    name="kimi-vl-smoke",
    vision=ViTConfig(
        name="moonvit-smoke", img_res=8 * 4, patch=4, n_layers=2, d_model=32,
        n_heads=4, d_ff=64, n_classes=0, class_token=False, rope_2d=True,
        pos_interp="bicubic", remat=False, attn_impl="naive"),
    lm=LMConfig(
        name="kimi-vl-smoke-lm", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=16, vocab_size=256, n_experts=8, top_k=3,
        n_shared_experts=2, rope_theta=800_000.0, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        first_k_dense=1, dense_d_ff=96, routed_scale=2.446,
        held_experts=(0, 4), norm_eps=1e-5, **_LM),
    frame_hw=(16, 24), answer_len=4,
)
