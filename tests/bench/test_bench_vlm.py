"""The vision-language cell (``kimi-vl-camera-vqa``) at test sizes: its work
counts against hand counts at full size, its per-layer readers on
hand-built contexts, sound runs correct, the float8 control and a run
whose answers are altered where produced not correct, and a check whose
compiled shapes repeat from run to run."""
import json
import os

import numpy as np
import pytest

import conftest
from conftest import REPO

from bench import run, vlm_work

SEED = 2**31 + 977
CELL = "kimi-vl-camera-vqa"


def _load(*parts):
    with open(os.path.join(REPO, "bench", *parts)) as f:
        return json.load(f)


def _edit(root, rel, update):
    conftest._edit(root, rel, update)


def _tiny(root, dtype):
    """The cell at test sizes; ``dtype`` the program's weights."""
    conftest.shrink(root)

    def cfg(c):
        c["vision"].update(patch=4, hidden_size=32, num_hidden_layers=2,
                           num_attention_heads=4, intermediate_size=64,
                           pos_grid=8)
        c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                 kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
                 v_head_dim=8, num_hidden_layers=3, intermediate_size=96,
                 moe_intermediate_size=16, n_routed_experts=8,
                 num_experts_per_tok=3, experts_held=4, vocab_size=256,
                 param_dtype=dtype)
    _edit(root, "bench/configs/kimi-vl-a3b.json", cfg)
    _edit(root, "bench/traffic/camera-vqa.json", lambda t: t.update(
        pool_frames=4, frame_h=16, frame_w=24, prompt_min=4, prompt_max=64,
        prompt_median=10, answer_tokens=4, trace_seconds=1.0))
    return root


@pytest.fixture
def tiny_f32(tmp_path):
    # float32 weights: the program then differs from the reference by
    # float32 roundings only, far inside the cell's limits
    return _tiny(str(tmp_path), "float32")


def test_frame_counts_match_hand_count():
    cfg, tr = _load("configs", "kimi-vl-a3b.json"), \
        _load("traffic", "camera-vqa.json")
    # 504 x 896 at patch 14: 36 x 64 = 2,304 patches; 2 x 2 merge: 576 tokens
    assert vlm_work.image_tokens(cfg, tr) == 576
    n, d, f, L = 2304, 1152, 4304, 27
    block = L * (4 * n * d * d + 2 * n * n * d + 2 * n * d * f)
    assert vlm_work.tower_block_flops(cfg, tr) == 2 * block
    proj = 576 * (4608 * 4608 + 4608 * 2048)
    assert vlm_work.frame_encode_flops(cfg, tr) == \
        2 * (block + n * 14 * 14 * 3 * d + proj)
    assert 2.5e12 < vlm_work.frame_encode_flops(cfg, tr) < 2.7e12


def test_latent_attention_and_expert_counts_match_hand_count():
    cfg, tr = _load("configs", "kimi-vl-a3b.json"), \
        _load("traffic", "camera-vqa.json")
    # wq 2048 x 16*192, wkv_a 2048 x 576, kv_norm 512, wkv_b 512 x 16*256,
    # wo 16*128 x 2048
    w = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    assert vlm_work.mla_weights(cfg) == w == 13_763_072
    # one request with a 40-token prompt: 31 decode steps of 27 layers read
    # the latent cache up to 576 + 40 + t + 1 positions, 576 values each
    cache = sum(576 + 40 + t + 1 for t in range(31)) * 576
    assert vlm_work.mla_decode_bytes(cfg, tr, [40], 1) == \
        2 * 27 * (cache + 31 * w)
    assert vlm_work.expert_route_flops(cfg) == 2 * 3 * 2048 * 1408
    assert vlm_work.moe_expert_bytes(cfg, 10, 3) == \
        2 * (3 * 3 * 2048 * 1408 + 10 * 2 * 2048)
    # a token at context s: attention projections and scores, the dense
    # layer once, 26 layers of shared experts and router
    s = 100
    per = 2 * (27 * (w - 512 + 16 * (192 + 128) * s) + 3 * 2048 * 11264
               + 26 * (3 * 2048 * 2816 + 2048 * 64))
    assert vlm_work.lm_token_flops(cfg, s) == per
    one = vlm_work.request_flops(cfg, tr, 40)
    assert vlm_work.served_flops(cfg, tr, [40, 40], 7) == \
        2 * one + 7 * vlm_work.expert_route_flops(cfg)


def _ctx(**units):
    pk = run.peaks("TPU v5 lite")
    return dict(units=units, busy_s=1.0, window_s=2.0, peaks=lambda: pk,
                scope_s={"kernels.mla_decode": 0.5, "kernels.moe_experts": 0.25,
                         "kernels.vit_block": 0.4})


def test_readers_divide_by_their_scopes():
    assert run.reader("mla_decode_roofline")(_ctx(mla_decode_bytes=819e9 / 10)) \
        == pytest.approx(20.0)
    # the larger of FLOPs over 197e12 and bytes over 819e9 bounds it
    moe = run.reader("moe_roofline")
    assert moe(_ctx(moe_flops=197e12 / 8, moe_bytes=1.0)) == pytest.approx(50.0)
    assert moe(_ctx(moe_flops=1.0, moe_bytes=819e9 / 20)) == pytest.approx(20.0)
    assert run.reader("vision_mfu")(_ctx(tower_flops=197e12 / 10)) == \
        pytest.approx(25.0)


@pytest.mark.parametrize("metric", ["mla_decode_roofline", "moe_roofline",
                                    "vision_mfu"])
def test_readers_find_nothing_without_their_scope(metric):
    ctx = _ctx(mla_decode_bytes=1.0, moe_flops=1.0, moe_bytes=1.0,
               tower_flops=1.0)
    ctx["scope_s"] = {"unscoped": 1.0}
    assert run.reader(metric)(ctx) is None
    assert run.reader(metric)(dict(_ctx(), units={})) is None


def test_cell_reports_frames_and_its_readers():
    spec = run.resolve(CELL)
    assert {m["name"] for m in spec["end_to_end"]} == {"frames_per_s",
                                                      "setup_s"}
    assert {m["name"] for m in spec["per_layer"]} == {
        "idle_share.serve", "batch_fill", "serve_mfu", "mla_decode_roofline",
        "moe_roofline", "vision_mfu"}
    assert spec["config"]["n_routed_experts"] == 64
    assert spec["config"]["experts_held"] == 16
    assert spec["config"]["reduced"] == ["experts_held"]


def test_sound_run_is_correct(tiny_f32):
    res = run.run_cell(CELL, SEED, 0.5, False, root=tiny_f32,
                       require_chip=False, workers=1)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"frames_per_s", "setup_s"}
    for c in res["checks"].values():
        assert c["value"] < 1e-4


def test_traced_run_reads_per_layer_metrics(tiny_f32):
    path = os.path.join(tiny_f32, "bench", "peaks.json")
    with open(path) as f:
        table = json.load(f)
    table["devices"]["cpu"] = table["devices"]["TPU v5 lite"]
    with open(path, "w") as f:
        json.dump(table, f)
    res = run.run_cell(CELL, SEED, 0.5, True, root=tiny_f32,
                       require_chip=False, workers=1)
    assert res["correct"], res["checks"]
    # CPU ops carry no name stack: the scope readers find nothing
    assert set(res["metrics"]) == {"idle_share.serve", "batch_fill",
                                   "serve_mfu"}
    assert res["metrics"]["batch_fill"]["value"] > 1


def test_control_fails(tmp_path):
    """The reference with float8 products, put in the program's place."""
    from bench.drivers import vlm_serve
    root = _tiny(str(tmp_path), "bfloat16")
    with open(os.path.join(root, "bench/configs/kimi-vl-a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "bench/traffic/camera-vqa.json")) as f:
        tr = json.load(f)
    cell = vlm_serve.Cell(cfg, tr, SEED, False)
    outs = [cell.dispatch() for _ in range(2)]
    cell.release()
    pre, dec, _ = cell.errors(outs, fp8=True)
    lim = cfg["limits"]
    assert max(pre) > lim["prefill_logit_rel_err"]
    assert max(dec) > lim["decode_logit_rel_err"]


def test_check_shapes_repeat_whatever_the_image_variants(tiny_f32):
    """Image rows padded with rows that route on their own change no logit
    and no routing count, and a check with another number of image variants
    compiles no new reference layer: a later run's check finds its compiled
    layers in the cache."""
    from bench.drivers import vlm_serve
    from bench.reference import kimi_vl_ref as ref
    with open(os.path.join(tiny_f32, "bench/configs/kimi-vl-a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(tiny_f32, "bench/traffic/camera-vqa.json")) as f:
        tr = json.load(f)
    a = vlm_serve.arch(cfg)
    rng = np.random.default_rng(SEED)
    n_img, d = vlm_work.image_tokens(cfg, tr), cfg["hidden_size"]
    L = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    E, K, T = cfg["n_routed_experts"], cfg["num_experts_per_tok"], 32
    cell = vlm_serve.Cell(cfg, tr, SEED, False)
    outs = [cell.dispatch() for _ in range(2)]
    cell.release()

    images = rng.standard_normal((3, n_img, d)).astype(np.float32)
    img_routes = rng.integers(0, E, (3, L, n_img, K)).astype(np.int16)
    tok = rng.integers(0, cfg["vocab_size"], (4, T)).astype(np.int32)
    grp = [(np.arange(4) % 3, tok, np.full((4, 1), T - 1),
            rng.integers(0, E, (4, L, T, K)).astype(np.int16))]
    (want,), st = ref.forward(cell.params, images, grp, arch=a,
                              image_routes=img_routes)
    # a padding row: the first frame again, routed on its own
    pad_routes = -np.ones_like(img_routes[:1])
    (got,), st_pad = ref.forward(
        cell.params, np.concatenate([images, images[:1]]), grp, arch=a,
        image_routes=np.concatenate([img_routes, pad_routes]))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(st_pad["image"][:3], st["image"], rtol=1e-5)
    assert not st_pad["image"][3:].any()
    np.testing.assert_allclose(st_pad["text"][0], st["text"][0], rtol=1e-5)

    def variants(outs):
        return len({(int(f), r.routes[:, :n_img].tobytes()) for o in outs
                    for f, r in zip(o["frames"], o["results"])})

    cell.errors(outs)
    layers = (ref._image_layer, ref._text_layer)
    compiled = [f._cache_size() for f in layers]
    # every other request's image rows routed one expert along
    n = variants(outs)
    for o in outs:
        for r in o["results"][::2]:
            r.routes[:, :n_img] = (r.routes[:, :n_img] + 1) % E
    assert variants(outs) != n
    cell.errors(outs)
    assert [f._cache_size() for f in layers] == compiled


@pytest.fixture
def clean_caches():
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_answer_altered_where_produced(tiny_f32, monkeypatch, clean_caches):
    from repro.models import kimi_vl
    real = kimi_vl.decode

    def decode(*a, **kw):
        ids, logits, routed, picks = real(*a, **kw)
        return ids, logits.at[1:, :, 0].add(5.0), routed, picks

    monkeypatch.setattr(kimi_vl, "decode", decode)
    res = run.run_cell(CELL, SEED, 0.5, False, root=tiny_f32,
                       require_chip=False, workers=1)
    assert not res["correct"]
    assert res["checks"]["decode_logit_rel_err"]["value"] > \
        res["checks"]["decode_logit_rel_err"]["limit"]
    assert res["checks"]["prefill_logit_rel_err"]["value"] < 1e-4


def test_held_experts_left_out_is_not_correct(tiny_f32, monkeypatch,
                                              clean_caches):
    """A layer that computes no routed expert (shared experts only)."""
    from repro.models import moe
    import jax.numpy as jnp
    real = moe.moe_held

    def none(x, *a, **kw):
        out, routed, picks = real(x, *a, **kw)
        return jnp.zeros_like(out), routed, picks

    monkeypatch.setattr(moe, "moe_held", none)
    res = run.run_cell(CELL, SEED, 0.5, False, root=tiny_f32,
                       require_chip=False, workers=1)
    assert not res["correct"]
    assert res["failed"] > 0
    np.testing.assert_array_less(
        [c["limit"] for c in res["checks"].values()],
        [c["value"] for c in res["checks"].values()])


def test_routing_that_ignores_the_bias_is_not_correct(tiny_f32, monkeypatch,
                                                      clean_caches):
    """The program picks by score alone, without the correction bias.  The
    reference follows the program's picks, so the logits may stay close;
    the picks' scores fall below the reference's own top-k."""
    from repro.models import moe
    import jax.numpy as jnp
    real = moe.route_sigmoid

    def unbiased(x, router_w, bias, *a, **kw):
        return real(x, router_w, jnp.zeros_like(bias), *a, **kw)

    monkeypatch.setattr(moe, "route_sigmoid", unbiased)
    res = run.run_cell(CELL, SEED, 0.5, False, root=tiny_f32,
                       require_chip=False, workers=1)
    assert not res["correct"]
    assert res["failed"] > 0
    gap = res["checks"]["route_score_gap"]
    assert gap["value"] > gap["limit"]
