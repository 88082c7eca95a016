"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Per assignment: sweep shapes/dtypes and assert_allclose against ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests skip cleanly without it
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

TOLS = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _allclose(a, b, dtype):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **TOLS[dtype])


# ---------------------------------------------------------------------------
# flash attention: the kernel reads head-major (B, H, S, D); the oracles
# take token-major (B, S, H, D)
# ---------------------------------------------------------------------------
def _heads_first(*xs):
    return [jnp.swapaxes(x, 1, 2) for x in xs]


def _qkv(B, S, H, KV, D, seed, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(kk, (B, S, h, D), jnp.float32).astype(dtype)
            for kk, h in zip(keys, (H, KV, KV))]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,D", [
    (1, 128, 4, 4, 64),        # MHA, single block
    (2, 256, 4, 2, 64),        # GQA 2:1
    (1, 384, 8, 2, 128),       # GQA 4:1
    (2, 100, 4, 1, 80),        # MQA, a length no tile divides
    (2, 48, 4, 4, 72),         # MoonViT's head width, the tile's full last dim
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, S, H, KV, D, dtype, causal):
    q, k, v = _qkv(B, S, H, KV, D, 0, dtype)
    got = ops.flash_attention(*_heads_first(q, k, v), causal=causal)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    _allclose(jnp.swapaxes(got, 1, 2), want, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,block_q,block_k,causal,window", [
    (256, 64, 128, False, None),   # blocks divide S, as 1,152 divides 2,304
    (200, 64, 128, False, None),   # ragged: edge blocks in q and in keys
    (200, 64, 128, True, 48),      # causal and windowed across blocks
])
def test_flash_attention_blocks_match_naive(S, block_q, block_k, causal,
                                            window, dtype):
    """Several q and KV blocks at head width 72 (MoonViT's), against the
    model's naive attention at the same precision."""
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.models.attention import attention_naive
    q, k, v = _qkv(2, S, 4, 2, 72, S, dtype)
    got = flash_attention_fwd(*_heads_first(q, k, v), causal=causal,
                              window=window, block_q=block_q,
                              block_k=block_k, interpret=True)
    want = attention_naive(q, k, v, causal=causal, window=window)
    _allclose(jnp.swapaxes(got, 1, 2), want, dtype)


@pytest.mark.parametrize("S,blocks", [
    (2304, (576, 2304)),    # MoonViT's 36 x 64 patches: all keys at once
    (4096, (512, 2048)),
    (198, (198, 198)),      # one block: the whole sequence
    (2500, (576, 2304)),    # nothing aligned divides it: ragged edges
])
def test_block_sizes_follow_the_length(S, blocks):
    from repro.kernels.flash_attention import block_sizes
    assert block_sizes(S, S) == blocks


@pytest.mark.parametrize("window", [16, 64, 1024])
def test_flash_attention_sliding_window(window):
    q, k, v = _qkv(1, 256, 4, 2, 64, 1)
    got = ops.flash_attention(*_heads_first(q, k, v), causal=True,
                              window=window)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    _allclose(jnp.swapaxes(got, 1, 2), want, jnp.float32)


def test_flash_attention_matches_model_attention():
    """Kernel agrees with the model's chunked XLA attention path."""
    from repro.models.attention import attention
    q, k, v = _qkv(2, 256, 4, 2, 64, 2)
    got = ops.flash_attention(*_heads_first(q, k, v), causal=True)
    want = attention(q, k, v, causal=True, impl="chunked", q_chunk=64)
    _allclose(jnp.swapaxes(got, 1, 2), want, jnp.float32)


def test_flash_attention_gradient_matches_naive():
    """The kernel's gradient (recomputed through the blocked XLA path) is
    the naive attention's."""
    from repro.models.attention import attention_naive
    q, k, v = _qkv(1, 96, 4, 2, 72, 3)
    w = jax.random.normal(jax.random.PRNGKey(4), q.shape)

    def loss_kernel(q, k, v):
        out = ops.flash_attention(*_heads_first(q, k, v), causal=False)
        return jnp.sum(jnp.swapaxes(out, 1, 2) * w)

    def loss_naive(q, k, v):
        return jnp.sum(attention_naive(q, k, v, causal=False) * w)

    got = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                   rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.sampled_from([64, 96, 200, 256]),
       st.sampled_from([(4, 4), (4, 2), (8, 1)]), st.booleans())
def test_flash_attention_property(B, S, HKV, causal):
    H, KV = HKV
    q, k, v = _qkv(B, S, H, KV, 64, S * 7 + B)
    got = ops.flash_attention(*_heads_first(q, k, v), causal=causal)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    _allclose(jnp.swapaxes(got, 1, 2), want, jnp.float32)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,d", [(8, 64), (300, 128), (1024, 512), (7, 7168)])
def test_rmsnorm_matches_ref(N, d, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (N, d), jnp.float32).astype(dtype)
    s = (jax.random.normal(k2, (d,), jnp.float32) * 0.1).astype(dtype)
    got = ops.rmsnorm(x, s)
    want = ref.rmsnorm_ref(x, s)
    _allclose(got, want, dtype)


def test_rmsnorm_matches_model_norm():
    from repro.models.common import rms_norm
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 4, 256))
    s = jax.random.normal(jax.random.PRNGKey(4), (256,)) * 0.1
    _allclose(ops.rmsnorm(x, s), rms_norm(x, s), jnp.float32)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 500), st.sampled_from([32, 128, 384]))
def test_rmsnorm_property(N, d):
    x = jax.random.normal(jax.random.PRNGKey(N * 31 + d), (N, d))
    s = jnp.zeros((d,))
    got = ops.rmsnorm(x, s)
    # unit-RMS property: each output row has RMS ~= 1 (for zero scale offset)
    rms = jnp.sqrt(jnp.mean(jnp.square(got), axis=-1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# moe grouped gemm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,d,f", [
    (4, 64, 128, 256),
    (8, 100, 64, 96),          # ragged C/f (padding path)
    (2, 256, 512, 128),
])
def test_moe_gemm_matches_ref(E, C, d, f, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = (jax.random.normal(k1, (E, C, d), jnp.float32) * 0.1).astype(dtype)
    w = (jax.random.normal(k2, (E, d, f), jnp.float32) * 0.1).astype(dtype)
    got = ops.moe_gemm(x, w)
    want = ref.moe_gemm_ref(x, w)
    _allclose(got, want, dtype)


def test_moe_gemm_is_blockwise_independent():
    """Each expert's output only depends on its own inputs."""
    E, C, d, f = 4, 32, 64, 64
    x = jax.random.normal(jax.random.PRNGKey(1), (E, C, d))
    w = jax.random.normal(jax.random.PRNGKey(2), (E, d, f))
    base = ops.moe_gemm(x, w)
    x2 = x.at[2].set(0.0)
    out = ops.moe_gemm(x2, w)
    np.testing.assert_allclose(np.asarray(out[2]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(base[0]),
                               rtol=1e-6)
