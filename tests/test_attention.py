"""Property tests: the chunked (online-softmax) attention path is
numerically equivalent to the naive oracle across GQA ratios, windows,
offsets and ragged lengths — this is the path every full-scale dry-run
lowers."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests skip cleanly without it
from hypothesis import given, settings, strategies as st

from repro.models.attention import (apply_rope, attention,
                                    attention_chunked, attention_decode,
                                    attention_naive, attention_path)


def _qkv(B, S, H, KV, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, D)),
            jax.random.normal(ks[1], (B, S, KV, D)),
            jax.random.normal(ks[2], (B, S, KV, D)))


@settings(max_examples=25, deadline=None)
@given(st.sampled_from([33, 64, 100, 200]),       # ragged lengths
       st.sampled_from([(4, 4), (4, 2), (6, 2), (8, 1)]),
       st.booleans(),
       st.sampled_from([None, 16, 64]),
       st.sampled_from([16, 32, 64]))
def test_chunked_matches_naive(S, HKV, causal, window, chunk):
    H, KV = HKV
    q, k, v = _qkv(2, S, H, KV, 32, seed=S * 7 + H)
    want = attention_naive(q, k, v, causal=causal, window=window)
    got = attention_chunked(q, k, v, causal=causal, window=window,
                            q_chunk=chunk, kv_chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_q_offset_matches_suffix_of_full():
    """Chunked attention with q_offset equals the suffix of full attention
    (the prefill-continuation contract)."""
    S, H, KV, D = 96, 4, 2, 32
    q, k, v = _qkv(1, S, H, KV, D, seed=3)
    full = attention_naive(q, k, v, causal=True)
    tail = attention_chunked(q[:, 64:], k, v, causal=True, q_chunk=16,
                             kv_chunk=32, q_offset=64)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(full[:, 64:]),
                               rtol=2e-5, atol=2e-5)


def test_decode_matches_full_attention_last_token():
    S, H, KV, D = 40, 4, 2, 32
    q, k, v = _qkv(2, S, H, KV, D, seed=9)
    full = attention_naive(q, k, v, causal=True)
    # cache padded beyond the valid length
    pad = 8
    kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    got = attention_decode(q[:, -1:], kc, vc, cache_len=S)
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(full[:, -1]),
                               rtol=2e-5, atol=2e-5)


def test_decode_window_matches_windowed_attention():
    S, H, KV, D, W = 64, 4, 2, 32, 16
    q, k, v = _qkv(1, S, H, KV, D, seed=11)
    full = attention_naive(q, k, v, causal=True, window=W)
    got = attention_decode(q[:, -1:], k, v, cache_len=S, window=W)
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(full[:, -1]),
                               rtol=2e-5, atol=2e-5)


def test_rope_relative_property():
    """RoPE inner products depend only on relative positions."""
    D = 32
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, D))

    def score(qpos, kpos):
        qr = apply_rope(q, jnp.array([qpos]))
        kr = apply_rope(k, jnp.array([kpos]))
        return float(jnp.sum(qr * kr))

    assert score(5, 3) == pytest.approx(score(105, 103), rel=1e-4)
    assert score(7, 0) == pytest.approx(score(507, 500), rel=1e-4)


def test_gqa_reduces_to_mha_when_kv_repeated():
    """GQA with repeated KV heads equals MHA with those heads."""
    B, S, KV, G, D = 1, 24, 2, 3, 16
    H = KV * G
    q, k, v = _qkv(B, S, H, KV, D, seed=4)
    gqa = attention_naive(q, k, v, causal=True)
    k_rep = jnp.repeat(k, G, axis=2)
    v_rep = jnp.repeat(v, G, axis=2)
    mha = attention_naive(q, k_rep, v_rep, causal=True)
    np.testing.assert_allclose(np.asarray(gqa), np.asarray(mha),
                               rtol=1e-5, atol=1e-5)


def _tower_tokens(cfg):
    """Patch tokens of one frame through a served VLM's tower."""
    rows, cols = (n // cfg.vision.patch for n in cfg.frame_hw)
    return rows * cols


def _cell_tower(impl="chunked"):
    """MoonViT as the benchmark's Kimi-VL cell builds it: 768-token chunks."""
    from repro.configs import get_config
    return dataclasses.replace(get_config("kimi-vl-a3b").vision,
                               attn_impl=impl, attn_chunk=768)


def test_attention_path_by_length():
    """The length decides: MoonViT's 2,304 patches take the kernel on a TPU,
    DeiT-B's 198 tokens and the smoke tower's 24 the naive path on any
    platform; the UNet's 4,096-token self-attention keeps the XLA loop."""
    from repro.configs import get_config, get_smoke_config
    from repro.models import vit
    moon, smoke, deit = (get_config("kimi-vl-a3b"),
                         get_smoke_config("kimi-vl-a3b"), get_config("deit-b"))
    assert _tower_tokens(moon) == 2304 and _tower_tokens(smoke) == 24
    assert deit.n_tokens() == 198
    for platform in ("tpu", "cpu"):
        for cfg, n, path in ((_cell_tower(), 2304,
                              "kernel" if platform == "tpu" else "chunked"),
                             (smoke.vision, 24, "naive"),
                             (deit, 198, "naive")):
            impl = vit.attention_impl(cfg, platform)
            assert attention_path(impl, n, cfg.attn_chunk) == path
    assert attention_path("chunked", 4096, 1024) == "chunked"


@pytest.mark.parametrize("impl,platform,path", [
    ("chunked", "tpu", "kernel"),     # the tower as the benchmark's cell runs
    ("chunked", "cpu", "chunked"),    # no kernel outside its interpreter
    ("pallas", "cpu", "kernel"),
    ("naive", "tpu", "naive"),
])
def test_attention_path_of_blocked_attention(impl, platform, path):
    """A ViT encoder's path past one chunk; within one chunk it is naive."""
    from repro.models import vit
    chosen = vit.attention_impl(_cell_tower(impl), platform)
    assert attention_path(chosen, 2304, 768) == path
    assert attention_path(chosen, 768, 768) == "naive"


def test_attention_path_refuses_unknown_impl():
    with pytest.raises(ValueError):
        attention_path("flash", 2304, 512)


@pytest.mark.parametrize("impl", ["naive", "chunked", "pallas"])
def test_heads_first_matches_token_major(impl):
    """Head-major q, k, v give the head-major output of the token-major
    call, whichever path the length picks."""
    q, k, v = _qkv(2, 100, 4, 2, 72, seed=5)
    want = attention(q, k, v, causal=False, impl=impl, q_chunk=32)
    got = attention(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)),
                    causal=False, impl=impl, q_chunk=32, heads_first=True)
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(got, 1, 2)),
                               np.asarray(want), rtol=2e-5, atol=2e-5)
