"""The served VLM's device ops sit in ``kernels.*`` scopes.

The benchmark's trace reduction (``bench/reduce.py``) charges each device
op to the innermost ``kernels.*`` name in its name stack and reads
``vision_mfu``, ``mla_decode_roofline`` and ``moe_roofline`` from the
scopes' time.  These tests compile the tower, the prefill and the decode
loop at smoke size on the CPU and map every matrix multiply (``dot``,
``convolution``) of the optimized HLO through the same ``op_scope``: each
phase shows its scopes, and no multiply is left ``unscoped``.  DeiT's
forward shares the tower's patch embedding and block and their scopes.
"""
import re

import jax
import numpy as np
import pytest

from bench.reduce import op_scope
from repro.configs import get_smoke_config
from repro.models import kimi_vl, transformer, vit

_MATMUL = re.compile(r"=\s*\S+\s+(dot|convolution)\(.*op_name=\"([^\"]*)\"")


def _matmul_scopes(compiled_text):
    return [op_scope({"tf_op": m.group(2)})
            for m in map(_MATMUL.search, compiled_text.splitlines()) if m]


@pytest.fixture(scope="module")
def phases():
    cfg = get_smoke_config("kimi-vl-a3b")
    p = kimi_vl.param_specs(cfg)
    B, P = 2, kimi_vl.PROMPT_BLOCK
    ml = kimi_vl.max_len(cfg, P)
    S = jax.ShapeDtypeStruct
    img = S((B, cfg.image_tokens, cfg.lm.d_model), np.dtype("bfloat16"))
    cache = transformer.mla_cache_specs(cfg.lm, B, ml)
    out = {
        "tower": jax.jit(kimi_vl.encode_images, static_argnums=2).lower(
            p, S((B, *cfg.frame_hw, 3), np.float32), cfg),
        "prefill": jax.jit(kimi_vl.prefill, static_argnums=(5, 6)).lower(
            p, img, S((B, P), np.int32), S((B,), np.int32), 2, cfg, ml),
        "decode": jax.jit(kimi_vl.decode, static_argnums=4).lower(
            p, cache, S((B, cfg.lm.vocab_size), np.float32), 2, cfg),
    }
    deit = get_smoke_config("deit-b")
    out["deit"] = jax.jit(lambda p, x: vit.forward(p, x, deit)).lower(
        vit.param_specs(deit), S((B, deit.img_res, deit.img_res, 3),
                                 np.float32))
    return {k: _matmul_scopes(v.compile().as_text()) for k, v in out.items()}


@pytest.mark.parametrize("phase,want", [
    ("tower", {"kernels.patch_embed", "kernels.vit_block",
               "kernels.projector"}),
    ("prefill", {"kernels.mla_prefill", "kernels.dense_mlp",
                 "kernels.moe_route", "kernels.moe_experts",
                 "kernels.moe_shared", "kernels.lm_head"}),
    ("decode", {"kernels.mla_decode", "kernels.dense_mlp",
                "kernels.moe_route", "kernels.moe_experts",
                "kernels.moe_shared", "kernels.lm_head"}),
])
def test_every_matmul_of_a_phase_is_scoped(phases, phase, want):
    scopes = phases[phase]
    assert scopes, f"no matrix multiply found in the {phase} HLO"
    assert set(scopes) == want


def test_deit_shares_the_tower_scopes(phases):
    assert set(phases["deit"]) == {"kernels.patch_embed", "kernels.vit_block",
                                   "unscoped"}          # its classifier head
