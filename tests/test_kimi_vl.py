"""Kimi-VL at smoke size on the CPU against the plain reference
(``bench/reference/kimi_vl_ref.py``, which imports nothing of the
program), on seeded random weights.

Comparisons in float32 (weights cast) check the mathematics: the program
and the reference then differ only in the order of float32 roundings,
about 1e-6 relative, so 1e-4 is far below any wrong term (a missing
RoPE, scale or expert moves logits by 1e-1 or more).  The bfloat16
serving path is checked end to end through the engine."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import kimi_vl, moe, transformer

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench.reference import kimi_vl_ref as ref  # noqa: E402

# float32 op-order differences are ~1e-6 relative; a wrong term is >= 1e-1
TOL = 1e-4


def _f32_config():
    cfg = get_smoke_config("kimi-vl-a3b")
    return dataclasses.replace(
        cfg, vision=dataclasses.replace(cfg.vision, param_dtype="float32"),
        lm=dataclasses.replace(cfg.lm, param_dtype="float32"))


def _arch(cfg, first=None):
    v, m = cfg.vision, cfg.lm
    return tuple(sorted(dict(
        patch=v.patch, v_d_model=v.d_model, v_layers=v.n_layers,
        v_heads=v.n_heads, pos_grid=v.img_res // v.patch, merge=cfg.merge,
        heads=m.n_heads, kv_lora_rank=m.kv_lora_rank,
        qk_nope_head_dim=m.qk_nope_head_dim,
        qk_rope_head_dim=m.qk_rope_head_dim, v_head_dim=m.v_head_dim,
        theta=m.rope_theta, eps=m.norm_eps, top_k=m.top_k,
        routed_scale=m.routed_scale,
        first_expert=m.experts_here[0] if first is None else first).items()))


def _params(cfg, seed=0):
    p = jax.jit(kimi_vl.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    # non-zero norms, biases and correction bias, so that each is tested
    leaves, tree = jax.tree_util.tree_flatten_with_path(p)
    out = []
    for i, (path, x) in enumerate(leaves):
        name = str(path[-1])
        if any(k in name for k in ("ln", "norm", "scale", "bias", "'b")):
            x = x + 0.1 * jax.random.normal(jax.random.PRNGKey(100 + i),
                                            x.shape, x.dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(tree, out)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)


@pytest.fixture(scope="module")
def f32():
    cfg = _f32_config()
    h, w = cfg.frame_hw
    frames = np.random.default_rng(1).standard_normal((3, h, w, 3)).astype(
        np.float32)
    return cfg, _params(cfg), frames


def test_tower_on_a_non_square_grid_matches_reference(f32):
    cfg, params, frames = f32
    assert frames.shape[1] != frames.shape[2]
    got = kimi_vl.encode_images(params, frames, cfg)
    want = ref.image_tokens(params, frames, arch=_arch(cfg))
    assert got.shape == (3, cfg.image_tokens, cfg.lm.d_model)
    assert _rel(got, want).max() < TOL


def test_tower_2d_rope_turns_rows_and_columns():
    """Moving a patch along its row or its column changes different
    rotary pairs: even pairs follow the column, odd pairs the row."""
    from repro.models import vit
    cos, sin = vit.rope_2d_tables(8, rows=2, cols=3)
    assert cos.shape == (6, 4)
    ang = np.arctan2(np.asarray(sin), np.asarray(cos))
    assert np.allclose(ang[1, 0::2], ang[0, 0::2] + [1, 1e-2])  # next column
    assert np.allclose(ang[1, 1::2], 0) and np.allclose(ang[3, 0::2], 0)
    assert np.allclose(ang[3, 1::2], [1, 1e-2])                # next row


def test_prefill_then_cached_decode_matches_full_forward(f32):
    """Prefill over [image, prompt], then decode through the latent cache
    (absorbed form): the logits at every answer position equal the
    reference's full forward teacher-forced on the program's answer."""
    cfg, params, frames = f32
    rng = np.random.default_rng(2)
    lens = np.array([5, 17, 9], np.int32)
    P = 32
    tokens = np.zeros((3, P), np.int32)
    for i, n in enumerate(lens):
        tokens[i, :n] = rng.integers(0, cfg.lm.vocab_size, n)
    ids, logits, routed, _, _ = kimi_vl.generate(
        params, frames, tokens, lens, 3, cfg, kimi_vl.max_len(cfg, P))
    A = cfg.answer_len
    assert ids.shape == (3, A) and logits.shape == (A, 3, cfg.lm.vocab_size)
    text = np.zeros((3, P + A), np.int32)
    for i, n in enumerate(lens):
        text[i, :n] = tokens[i, :n]
        text[i, n:n + A - 1] = np.asarray(ids[i, :-1])
    read = np.stack([n - 1 + np.arange(A) for n in lens])
    images = ref.image_tokens(params, frames, arch=_arch(cfg))
    (want,), _ = ref.forward(params, images, [(np.arange(3), text, read)],
                             arch=_arch(cfg))
    got = np.moveaxis(np.asarray(logits), 0, 1)
    assert _rel(got, want).max() < TOL
    # greedy: each answer token is the argmax of the logits before it
    assert np.array_equal(np.asarray(ids), got.argmax(-1))
    assert int(routed[0]) > 0 and int(routed[1]) > 0


def test_padding_rows_and_tokens_change_no_answer():
    """A request's answer is the same alone, in a batch with others and
    padding rows, and with its prompt padded to another block count."""
    cfg = get_smoke_config("kimi-vl-a3b")
    params = _params(cfg, seed=3)
    h, w = cfg.frame_hw
    rng = np.random.default_rng(4)
    reqs = [kimi_vl.Request(rng.standard_normal((h, w, 3)).astype(np.float32),
                            rng.integers(0, cfg.lm.vocab_size, n).astype(
                                np.int32)) for n in (7, 40, 12)]
    run = kimi_vl.Runner(params, cfg, max_batch=4, max_prompt=64)
    together = run("q", reqs)
    alone = run("q", reqs[:1])
    assert np.array_equal(together[0].ids, alone[0].ids)
    # the lone request pads to one block, the batch to two: routes of
    # padding count nowhere, so the routed counts differ by the other rows
    np.testing.assert_allclose(together[0].first_logits, alone[0].first_logits,
                               rtol=0, atol=2e-2 * np.abs(
                                   alone[0].first_logits).max())


def _moe_inputs(seed=5):
    cfg = _f32_config().lm
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    T = 24
    x = jax.random.normal(k[0], (T, d))
    w = dict(router=jax.random.normal(k[1], (d, E)) / np.sqrt(d),
             router_bias=0.3 * jax.random.normal(k[2], (E,)),
             we_gate=jax.random.normal(k[3], (E, d, f)) / np.sqrt(d),
             we_up=jax.random.normal(k[4], (E, d, f)) / np.sqrt(d),
             we_down=jax.random.normal(k[5], (E, f, d)) / np.sqrt(f),
             ws_gate=jax.random.normal(k[6], (d, 2 * f)) / np.sqrt(d),
             ws_up=jax.random.normal(k[7], (d, 2 * f)) / np.sqrt(d),
             ws_down=jax.random.normal(k[0], (2 * f, d)) / np.sqrt(2 * f))
    return cfg, x, w


def _shared(x, w):
    g, u = x @ w["ws_gate"], x @ w["ws_up"]
    return (jax.nn.silu(g) * u) @ w["ws_down"]


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_expert_shares_add_up_to_the_uncut_layer(shares):
    """What every chip's held experts give, plus the shared experts counted
    once, equals the reference's whole layer over all experts."""
    cfg, x, w = _moe_inputs()
    E, size = cfg.n_experts, cfg.n_experts // shares
    valid = jnp.ones((x.shape[0],), bool)
    parts, pairs = [], 0
    for first in range(0, E, size):
        sl = slice(first, first + size)
        out, routed, _ = moe.moe_held(
            x, valid, w["router"], w["router_bias"], w["we_gate"][sl],
            w["we_up"][sl], w["we_down"][sl], top_k=cfg.top_k,
            scale=cfg.routed_scale, first=first)
        parts.append(out)
        pairs += int(routed[0])
    assert pairs == x.shape[0] * cfg.top_k       # every route, none dropped
    got = sum(parts) + _shared(x, w)
    a = dict(_arch(_f32_config(), first=0))
    want, _ = ref._ffn(w, x, a, dense=False, fp8=False)
    assert _rel(got, want).max() < TOL


def test_sigmoid_routing_bias_selects_and_does_not_weigh():
    cfg, x, w = _moe_inputs(seed=6)
    gates, experts = moe.route_sigmoid(x, w["router"], w["router_bias"],
                                       cfg.top_k, cfg.routed_scale)
    scores = jax.nn.sigmoid(x @ w["router"])
    # selection by score + bias
    want_sel = np.sort(np.asarray(jax.lax.top_k(
        scores + w["router_bias"], cfg.top_k)[1]), -1)
    assert np.array_equal(np.sort(np.asarray(experts), -1), want_sel)
    # weights: the unbiased scores of the picked experts, normalized, scaled
    picked = np.take_along_axis(np.asarray(scores), np.asarray(experts), -1)
    np.testing.assert_allclose(
        gates, picked / picked.sum(-1, keepdims=True) * cfg.routed_scale,
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), cfg.routed_scale,
                               rtol=1e-6)
    # a bias large enough picks other experts than the scores alone
    alone = jax.lax.top_k(scores, cfg.top_k)[1]
    assert not np.array_equal(np.sort(np.asarray(alone), -1), want_sel)


def test_grouped_experts_gradient_matches_a_plain_loop():
    """The expert loop's own backward pass (it runs to a trip count known
    only on the device) gives the gradients of a plain loop over experts,
    for the rows and for each layer of the weight stacks it reads."""
    cfg = _f32_config().lm
    d, f, E, L = cfg.d_model, cfg.d_ff, 4, 3
    k = jax.random.split(jax.random.PRNGKey(8), 5)
    # more rows than one 256-row tile: a group spans tiles, a tile groups
    sizes = jnp.array([200, 0, 300, 97], jnp.int32)        # one group empty
    M = 600                                                 # 3 rows past all
    rows = jax.random.normal(k[0], (M, d))
    ws = [jax.random.normal(k[1], (L, E, d, f)) / np.sqrt(d),
          jax.random.normal(k[2], (L, E, d, f)) / np.sqrt(d),
          jax.random.normal(k[3], (L, E, f, d)) / np.sqrt(f)]
    probe = jax.random.normal(k[4], (M, d))
    group = np.repeat(np.arange(E + 1), list(np.asarray(sizes)) + [3])

    def plain(rows, wg, wu, wd, layer):
        out = jnp.zeros((M, d))
        for g in range(E):
            y = (jax.nn.silu(rows @ wg[layer, g]) * (rows @ wu[layer, g])
                 ) @ wd[layer, g]
            out = out + jnp.where((group == g)[:, None], y, 0.0)
        return out

    for layer in (0, 2):
        got = jax.grad(lambda r, *w: jnp.sum(probe * moe.grouped_swiglu(
            r, sizes, *w, jnp.int32(layer))), argnums=(0, 1, 2, 3))(rows, *ws)
        want = jax.grad(lambda r, *w: jnp.sum(probe * plain(r, *w, layer)),
                        argnums=(0, 1, 2, 3))(rows, *ws)
        for g, w in zip(got, want):
            scale = np.abs(np.asarray(w)).max()
            np.testing.assert_allclose(g, w, rtol=0, atol=TOL * scale)


def test_no_token_is_dropped_when_all_pick_the_same_experts():
    """Every token routes to the same held experts: all are computed."""
    cfg, x, w = _moe_inputs(seed=7)
    bias = jnp.zeros((cfg.n_experts,)).at[:cfg.top_k].set(100.0)
    valid = jnp.ones((x.shape[0],), bool).at[-3:].set(False)
    out, routed, _ = moe.moe_held(
        x, valid, w["router"], bias, w["we_gate"], w["we_up"], w["we_down"],
        top_k=cfg.top_k, scale=cfg.routed_scale, first=0)
    n = x.shape[0] - 3
    assert routed.tolist() == [n * cfg.top_k, cfg.top_k]
    a = dict(_arch(_f32_config(), first=0))
    want = ref._ffn(dict(w, router_bias=bias), x, a, dense=False,
                    fp8=False)[0] - _shared(x, w)
    assert _rel(out[:n], want[:n]).max() < TOL
    assert np.all(np.asarray(out[n:]) == 0)        # padding routes nowhere


def test_lm_embeddings_are_not_scaled_for_this_config():
    cfg = get_smoke_config("kimi-vl-a3b").lm
    assert not cfg.embed_scale
    p = {"embed": jnp.arange(12.0).reshape(4, 3)}
    np.testing.assert_array_equal(
        transformer.embed(p, jnp.array([2]), dataclasses.replace(
            cfg, param_dtype="float32")), [[6.0, 7.0, 8.0]])
    scaled = transformer.embed(p, jnp.array([2]), dataclasses.replace(
        cfg, param_dtype="float32", embed_scale=True))
    np.testing.assert_allclose(
        scaled, np.sqrt(cfg.d_model) * np.array([[6.0, 7.0, 8.0]]), rtol=1e-6)


def test_dense_first_layers_are_a_stack_of_their_own():
    cfg = get_smoke_config("kimi-vl-a3b").lm
    specs = transformer.param_specs(cfg)
    k = cfg.first_k_dense
    assert specs["dense_layers"]["w_gate"].shape == (k, cfg.d_model,
                                                     cfg.dense_d_ff)
    assert "router" not in specs["dense_layers"]
    lo, hi = cfg.held_experts
    assert specs["layers"]["we_gate"].shape == (cfg.n_layers - k, hi - lo,
                                                cfg.d_model, cfg.d_ff)


def test_serve_launcher_answers_through_the_engine():
    from repro.launch.serve import serve
    rep = serve("kimi-vl-a3b", requests=10, inter_arrival=0.5)
    cfg = get_smoke_config("kimi-vl-a3b")
    assert rep.answered == 10
    assert all(len(r) == cfg.answer_len for r in rep.results)
    assert rep.stats["met"] + rep.stats["missed"] == 10
    assert rep.stats["batches"] < 10                 # some batches of several
