"""``correct`` at test sizes: sound runs pass, the control one precision
below the configuration fails the cell's limits, and a run driven with
the timed path broken underneath comes out not correct."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest  # noqa: F401  (puts the repository on sys.path)
from bench import gen, run
from bench.drivers import fleet, vit_serve

SEED = 2**31 + 977          # more than 32 signed bits, as the driver's are


def _run(root, cell, trace=False):
    return run.run_cell(cell, SEED, 0.5, trace, root=root,
                        require_chip=False, workers=1)


def _config(root, name):
    with open(os.path.join(root, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _failed_checks(res):
    return {k for k, c in res["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("cell", ["fleet256-feasible", "campus3-sweep",
                                  "deit-b-serve", "fleet256-roundrobin"])
def test_sound_run_is_correct(tiny_root, cell):
    res = _run(tiny_root, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) >= {"setup_s"}
    assert list(res)[-1] == "checks"


def test_traced_run_reads_per_layer_metrics(tiny_root):
    path = os.path.join(tiny_root, "bench", "peaks.json")
    with open(path) as f:
        table = json.load(f)
    table["devices"]["cpu"] = table["devices"]["TPU v5 lite"]
    with open(path, "w") as f:
        json.dump(table, f)
    res = _run(tiny_root, "fleet256-feasible", trace=True)
    assert res["correct"], res["checks"]
    # CPU ops carry no name stack, so retire_us finds nothing to read and
    # is left out rather than read as 0
    assert set(res["metrics"]) == {"idle_share.sim", "event_us", "scan_mfu"}
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"]


@pytest.mark.parametrize("config,traffic", [("fleet256-campus", "feasible"),
                                            ("paper-campus3", "sweep20")])
def test_fleet_control_fails(tiny_root, config, traffic):
    """The reference in bfloat16, put in the program's place."""
    cfg = _config(tiny_root, config)
    with open(os.path.join(tiny_root, "bench", "traffic",
                           traffic + ".json")) as f:
        tr = json.load(f)
    for seed in (SEED, SEED + 1, SEED + 2):
        points = gen.fleet_points(cfg, tr, seed)
        for p in points:
            p["policy"] = tr["policy"]
        ref = fleet.reference(cfg, points, "float32", 1)
        low = fleet.reference(cfg, points, "bfloat16", 1)
        n = len(points)
        stack = (lambda k: low[0][k]) if n == 1 else \
            (lambda k: np.stack([x[k] for x in low]))
        zero = np.zeros(n if n > 1 else ())
        outs = [(stack("outcome"), stack("served_by"), stack("completion"),
                 stack("transfer"), zero)]
        r = fleet.readings(outs, ref, n)
        assert any(r[k] > v for k, v in cfg["limits"].items()), r


def test_vit_control_fails(tiny_root):
    """The reference with float8 products, put in the program's place."""
    cfg = _config(tiny_root, "deit-b")
    arch = tuple(sorted(cfg["arch"].items()))
    from bench.reference import vit_ref
    for seed in (SEED, SEED + 1, SEED + 2):
        params = jax.jit(vit_serve.make_params, static_argnums=(0, 1))(
            arch, "bfloat16", vit_serve.seed_key(seed))
        x = np.random.default_rng(seed).standard_normal(
            (8, 32, 32, 3), np.float32)
        ref = np.asarray(vit_ref.forward(params, x, arch=arch))
        low = np.asarray(vit_ref.forward(params, x, arch=arch, fp8=True))
        err = max(np.linalg.norm(low[i] - ref[i]) / np.linalg.norm(ref[i])
                  for i in range(8))
        assert err > cfg["limits"]["logit_rel_err"]


@pytest.fixture
def clean_caches():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_step_that_returns_state_unchanged(tiny_root, monkeypatch,
                                           clean_caches):
    from repro.fleetsim import core
    monkeypatch.setattr(core, "_estep", lambda state, _, **kw: (state, None))
    for cell in ("fleet256-feasible", "campus3-sweep"):
        res = _run(tiny_root, cell)
        assert not res["correct"]
        assert "mismatched_requests" in _failed_checks(res)


def test_half_the_fleet_left_out(tiny_root, monkeypatch, clean_caches):
    """Completions of every second node never retire."""
    from repro.fleetsim import core
    real = core._retire

    def half(state, t, R):
        skip = jnp.arange(state.busy.shape[0]) % 2 == 1
        out = real(state._replace(busy=jnp.where(skip, jnp.inf, state.busy)),
                   t, R)
        return out._replace(busy=jnp.where(skip, state.busy, out.busy))

    monkeypatch.setattr(core, "_retire", half)
    for cell in ("fleet256-roundrobin", "campus3-sweep"):
        res = _run(tiny_root, cell)
        assert not res["correct"]


def test_answer_altered_where_produced(tiny_root, monkeypatch, clean_caches):
    import repro.fleetsim as fs
    from repro.models import vit
    real_sim, real_fwd = fs.simulate, vit.forward

    def sim(*a, **kw):
        m = real_sim(*a, **kw)
        return m._replace(served_by=m.served_by.at[0].add(1))

    def fwd(p, x, cfg):
        return real_fwd(p, x, cfg).at[0, 0].add(1.0)

    monkeypatch.setattr(fs, "simulate", sim)
    monkeypatch.setattr(vit, "forward", fwd)
    res = _run(tiny_root, "fleet256-feasible")
    assert "mismatched_requests" in _failed_checks(res)
    res = _run(tiny_root, "deit-b-serve")
    assert "logit_rel_err" in _failed_checks(res)


def test_half_of_each_batch_left_out(tiny_root, monkeypatch):
    """The serving replica runs half of each popped batch and drops the rest."""
    from repro.serving import engine
    real = engine.ServingReplica._pop_run

    def half(self, start):
        batch = real(self, start)
        return batch[:max(1, len(batch) // 2)] if len(batch) > 1 else batch

    monkeypatch.setattr(engine.ServingReplica, "_pop_run", half)
    res = _run(tiny_root, "deit-b-serve")
    assert not res["correct"]
    assert "logit_rel_err" in _failed_checks(res)
    assert res["failed"] > 0
