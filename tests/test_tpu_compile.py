"""The chip's compiler on the main path, without a chip.

Each test compiles for one chip of a *described* TPU v5e (2x2) host:
the Mosaic ``event_select`` kernel at fleet widths, the 256-node
fleetsim scan (jnp path and kernel path), the deit-b forward at its
published widths, a MoonViT block through the attention kernel and the
Kimi-VL decode loop.  Nothing runs; a compile that passes here is not a
chip run.  It catches what interpret mode cannot: block shapes the TPU
refuses, scalars in vector memory, programs that do not fit.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the workers of a
parallel test run all import this file.  The persistent compilation
cache is off around these compiles, since an entry written for a
described chip cannot be read back without one.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

KERNEL = "tpu_custom_call"

# the 256-node benchmark fleet (benchmarks/fleetsim_bench.py,
# make_fleet_workload(256, 4)): 128,000 requests, capacity 1024, depth 512
FLEET_K, FLEET_R, FLEET_CAP, FLEET_DEPTH = 256, 128_000, 1024, 512


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("K", [32, 256])
def test_event_select_compiles_for_v5e(one_chip, K):
    from repro.kernels.event_select import event_select_fwd
    N = FLEET_DEPTH
    s = lambda shape=(), dt=jnp.float32: _spec(one_chip, shape, dt)
    cand = (s(), s((), jnp.int32), s(), s(), s(), s((), jnp.bool_))
    args = (*cand, *cand, s((K, N)), s((K, N)), s((K, N)),
            s((K,), jnp.int32), s((K,), jnp.int32), s((K,)), s((K,)),
            s((K, K)), s((K, K)))
    fn = jax.jit(lambda *a: event_select_fwd(*a, interpret=False))
    compiled = fn.lower(*args).compile()
    assert KERNEL in compiled.as_text()


@pytest.mark.parametrize("policy,use_pallas", [
    ("random", False), ("batched_feasible", True)])
def test_fleet_scan_compiles_for_v5e(one_chip, monkeypatch, policy,
                                    use_pallas):
    from repro.fleetsim import (RequestArrays, SimParams, TopologyArrays,
                                simulate_fn)
    from repro.kernels import ops
    # ops picks interpret mode from the default backend, which is the CPU
    # here; the target of this compile is the described chip
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    s = lambda shape=(), dt=jnp.float32: _spec(one_chip, shape, dt)
    K, R = FLEET_K, FLEET_R
    reqs = RequestArrays(s((R,)), s((R,)), s((R,)), s((R,), jnp.int32),
                         s((R,), jnp.int32), s((R,)))
    topo = TopologyArrays(s((K, K), jnp.bool_), s((K, K - 1), jnp.int32),
                          s((K,), jnp.int32), s((K,)))
    params = SimParams(s((), jnp.int32), s(()))
    run = simulate_fn(policy=policy, capacity=FLEET_CAP, depth=FLEET_DEPTH,
                      use_pallas=use_pallas)
    compiled = jax.jit(run).lower(reqs, topo, params,
                                  s((R, 2), jnp.int32)).compile()
    assert (KERNEL in compiled.as_text()) == use_pallas
    # the whole fleet state fits one chip's 16 GB with room to spare
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_deit_b_forward_compiles_for_v5e(one_chip):
    from repro.configs import get_config
    from repro.models import vit
    cfg = get_config("deit-b")
    shapes = jax.eval_shape(lambda: vit.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), shapes)
    imgs = _spec(one_chip, (8, cfg.img_res, cfg.img_res, 3))
    fwd = jax.jit(lambda p, x: jnp.argmax(vit.forward(p, x, cfg), -1))
    compiled = fwd.lower(params, imgs).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 150e6


def test_moonvit_block_attention_is_the_kernel_for_v5e(one_chip,
                                                       monkeypatch):
    """One MoonViT encoder block at the served shape (8 frames of 36 x 64
    patches, d 1,152): attention is the Mosaic kernel, charged to the
    block's scope and no nested one (``vision_mfu`` divides by that
    scope's time), and no f32 (2,304 x 2,304) score matrix per head sits
    in HBM (2.72 GB of temporaries on the naive path)."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import vit
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    # the tower as the benchmark's cell builds it, with the impl a TPU picks
    cfg = dataclasses.replace(get_config("kimi-vl-a3b").vision,
                              attn_impl="chunked", attn_chunk=768)
    cfg = dataclasses.replace(cfg, attn_impl=vit.attention_impl(cfg, "tpu"))
    hd = cfg.d_model // cfg.n_heads
    layer = jax.tree.map(lambda a: _spec(one_chip, a.shape[1:], a.dtype),
                         vit.param_specs(cfg)["layers"])
    rope = tuple(_spec(one_chip, t.shape) for t in
                 jax.eval_shape(lambda: vit.rope_2d_tables(hd, 36, 64)))
    h = _spec(one_chip, (8, 36 * 64, cfg.d_model), jnp.bfloat16)
    compiled = jax.jit(lambda h, lp, r: vit.encoder_block(h, lp, cfg, r)
                       ).lower(h, layer, rope).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if f'custom_call_target="{KERNEL}"' in line]
    assert calls
    for line in calls:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert re.findall(r"kernels\.\w+", op_name) == ["kernels.vit_block"]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.fixture(scope="module")
def kimi_decode(one_chip):
    """The answer's decode loop at published widths, compiled for the chip."""
    from repro.configs import get_config
    from repro.models import kimi_vl, transformer
    cfg = get_config("kimi-vl-a3b")
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                          kimi_vl.param_specs(cfg))
    cache = {k: _spec(one_chip, s.shape, s.dtype) for k, s in
             transformer.mla_cache_specs(cfg.lm, 8, kimi_vl.max_len(
                 cfg, 128)).items()}
    return jax.jit(kimi_vl.decode, static_argnums=4).lower(
        params, cache, _spec(one_chip, (8, cfg.lm.vocab_size)),
        _spec(one_chip, (), jnp.int32), cfg).compile()


def test_kimi_vl_decode_loop_compiles_for_v5e(kimi_decode):
    """The answer's decode loop at published widths: the language model's
    10.3 GB of bf16 weights (the tower's 0.9 GB are not its arguments) and
    the latent cache of 8 rows, with the held experts' matrix multiplies
    under their scope in the chip's program (XLA's own ragged dot drops
    the name stack, and the trace would charge them to no scope)."""
    text = kimi_decode.as_text()
    assert re.search(r"convolution\(.*op_name=\"[^\"]*kernels\.moe_experts",
                     text)
    mem = kimi_decode.memory_analysis()
    assert 10e9 < mem.argument_size_in_bytes < 11e9
    # the loop's temporaries fit the chip's 16 GB beside all the weights
    assert mem.temp_size_in_bytes < 2 * 2 ** 30


def test_kimi_vl_decode_reads_expert_weights_in_place(kimi_decode):
    """A decode step reads each touched expert's matrices from the stacks
    where they lie: no layer's 16 held experts (3 x 92 MB) are copied out
    of their stack, which a scan over the stacks does at every step."""
    text = kimi_decode.as_text()
    assert not re.search(r"= bf16\[(1,)?16,(2048,1408|1408,2048)\]", text)
    # the expert loop slices one expert of one layer inside its multiply
    assert re.search(r"dynamic-slice\(.*dynamic_slice_sizes=\{1,1,2048,1408\}",
                     text)
