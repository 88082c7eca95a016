"""Distribution tests: logical rules, divisibility fallback, a real
small-mesh lower+compile, and shard_map MoE equivalence.

Multi-device tests run in subprocesses because XLA locks the host device
count at first jax init (the main pytest process must stay at 1 device for
the smoke tests)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd


class TestLogicalRules:
    def teardown_method(self):
        shd.clear_rules()

    def test_no_rules_noop(self):
        shd.clear_rules()
        import jax.numpy as jnp
        x = jnp.ones((4, 4))
        assert shd.hint(x, "dp", None) is x

    def test_logical_resolution(self):
        shd.set_rules(dp=("pod", "data"), tp="model")
        assert shd.logical("dp", None, "tp") == P(("pod", "data"), None, "model")
        assert shd.logical(None, "missing") == P(None, None)

    def test_rules_cleared(self):
        shd.set_rules(dp="data")
        shd.clear_rules()
        assert shd.get_rules() == {}
        assert shd.active_mesh() is None


def _run_subprocess(code: str) -> str:
    # CPU-only children: never let one reach for an accelerator
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, f"stderr:\n{res.stderr[-3000:]}"
    return res.stdout


@pytest.mark.slow
class TestSmallMeshCompile:
    def test_dryrun_cell_on_8_devices(self):
        """A reduced LM cell lowers + compiles on a real 2x4 mesh with the
        full sharding-rule machinery (subprocess: needs 8 host devices)."""
        out = _run_subprocess("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax
            from jax.sharding import AxisType, NamedSharding
            from repro.configs import get_smoke_config
            from repro.configs.shapes import ShapeSpec
            from repro.launch import steps as S
            from repro.launch.mesh import install_rules
            from repro.launch.dryrun import _to_shardings

            cfg = get_smoke_config("gemma3-27b")
            S.shapes_for(cfg)["t"] = ShapeSpec("t", "train", seq_len=32,
                                               global_batch=4)
            cell = S.build_cell("gemma3-27b", "t", cfg=cfg)
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
            install_rules(mesh, cfg, 4)
            ins = _to_shardings(mesh, cell.arg_logical, cell.arg_specs)
            with mesh:
                compiled = jax.jit(cell.step_fn, in_shardings=ins
                                   ).lower(*cell.arg_specs).compile()
            cost = compiled.cost_analysis()
            print("OK", float((cost[0] if isinstance(cost, list) else
                               cost).get("flops", 0)) > 0)
        """)
        assert "OK True" in out

    def test_uneven_dim_replicated_not_errored(self):
        """_to_shardings drops axes that do not divide (e.g. a 1000-class
        head over a 16-way axis) instead of failing at jit."""
        out = _run_subprocess("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp
            from jax.sharding import AxisType
            from repro.distributed import sharding as shd
            from repro.launch.dryrun import _to_shardings
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
            shd.set_rules(mesh=mesh, dp="data", tp="model")
            specs = {"w": jax.ShapeDtypeStruct((10, 1001), jnp.float32)}
            logical = {"w": ("dp", "tp")}
            sh = _to_shardings(mesh, logical, specs)
            print("spec", sh["w"].spec)
        """)
        assert "spec PartitionSpec('data', None)" in out

    def test_shard_map_moe_matches_global(self):
        out = _run_subprocess("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import AxisType
            from repro.models import moe
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
            ks = jax.random.split(jax.random.PRNGKey(0), 5)
            T, d, E, f, k = 32, 16, 8, 24, 2
            x = jax.random.normal(ks[0], (T, d))
            rw = jax.random.normal(ks[1], (d, E)) * 0.1
            wg = jax.random.normal(ks[2], (E, d, f)) * 0.1
            wu = jax.random.normal(ks[3], (E, d, f)) * 0.1
            wd = jax.random.normal(ks[4], (E, f, d)) * 0.1
            ref, _ = moe.moe_ffn(x, rw, wg, wu, wd, top_k=k,
                                 capacity_factor=8.0)
            with mesh:
                got, _ = jax.jit(lambda *a: moe.moe_ffn_sharded(
                    *a, top_k=k, capacity_factor=8.0, mesh=mesh,
                    dp_axes=("data",), model_axis="model", fsdp_axes="data",
                    expert_sharded=True))(x, rw, wg, wu, wd)
            err = float(np.abs(np.asarray(got) - np.asarray(ref)).max())
            print("maxdiff", err)
        """)
        assert float(out.split("maxdiff")[1]) < 1e-5


def _hlo_parser_matches_this_xla() -> bool:
    """The text cost model tracks a specific XLA HLO dialect; newer/older
    jaxlibs render fusions differently and the parser sees no flops.
    Runs at collection, so any probe failure means skip — never abort."""
    try:
        import jax
        import jax.numpy as jnp
        from repro.launch.hlo_cost import analyze_hlo
        c = jax.jit(lambda a, b: a @ b).lower(
            jax.ShapeDtypeStruct((8, 8), jnp.float32),
            jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
        return analyze_hlo(c.as_text()).flops > 0
    except Exception:
        return False


@pytest.mark.skipif(not _hlo_parser_matches_this_xla(),
                    reason="installed jaxlib emits an HLO dialect "
                           "hlo_cost.analyze_hlo does not parse")
class TestHloCostModel:
    def test_loop_free_matches_cost_analysis(self):
        out = _run_subprocess("""
            import jax, jax.numpy as jnp
            from repro.launch.hlo_cost import analyze_hlo
            def g(x, w):
                return jnp.tanh(x @ w) @ w.T
            x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
            w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
            c = jax.jit(g).lower(x, w).compile()
            ca = c.cost_analysis()
            ca = ca[0] if isinstance(ca, list) else ca
            mine = analyze_hlo(c.as_text())
            print("flops", mine.flops == ca.get("flops"),
                  "bytes", mine.bytes == ca.get("bytes accessed"))
        """)
        assert "flops True bytes True" in out

    def test_scan_trip_count_multiplied(self):
        out = _run_subprocess("""
            import jax, jax.numpy as jnp
            from repro.launch.hlo_cost import analyze_hlo
            def f(x, w):
                def body(h, wi):
                    return jnp.tanh(h @ wi), None
                return jax.lax.scan(body, x, w)[0]
            x = jax.ShapeDtypeStruct((16, 64), jnp.float32)
            w = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
            c = jax.jit(f).lower(x, w).compile()
            mine = analyze_hlo(c.as_text())
            print("flops", mine.flops, "expected", 7 * 2 * 16 * 64 * 64)
        """)
        _, flops, _, expected = out.split()
        assert float(flops) == float(expected)
