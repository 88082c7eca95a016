"""The configurations as run: their entries in ``BENCHMARK.json`` agree with
their files, and, on the plain reference at full size, the 256-node
streams are referred (so route choice and wire time are compared there),
and every configured ledger holds what the reference admits."""
import json
import os

import numpy as np
import pytest

from conftest import REPO

from bench import gen
from bench.reference import fleet_ref

SEED = 2**31 + 4099


def _load(*parts):
    with open(os.path.join(REPO, "bench", *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["feasible", "roundrobin"])
def test_fleet256_streams_are_referred(mix):
    cfg, tr = _load("configs", "fleet256-campus.json"), \
        _load("traffic", mix + ".json")
    point = gen.fleet_points(cfg, tr, SEED)[0]
    point.update(policy=tr["policy"], peak={})
    out = fleet_ref.run_point((cfg, point, cfg["dtype"]))
    referred = out["forwards"] > 0
    assert referred.mean() > 0.02
    assert np.all(out["transfer"][referred] > 0)
    assert point["peak"]["admissions"] < cfg["capacity"]
    assert point["peak"]["depth"] < cfg["depth"]


def test_campus3_ledger_holds_the_tightest_sweep_point():
    cfg, tr = _load("configs", "paper-campus3.json"), \
        _load("traffic", "sweep20.json")
    tr = dict(tr, workload_seeds=1, sla_scales=[min(tr["sla_scales"])])
    point = gen.fleet_points(cfg, tr, SEED)[0]
    point.update(policy=tr["policy"], peak={})
    out = fleet_ref.run_point((cfg, point, cfg["dtype"]))
    assert (out["forwards"] > 0).mean() > 0.5       # the paper's overload
    assert point["peak"]["admissions"] < cfg["capacity"]
    assert point["peak"]["depth"] < cfg["depth"]


def test_benchmark_entries_match_their_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["configs"]:
        with open(os.path.join(REPO, entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == entry["name"]
        assert cfg["reduced"] == entry["reduced"]
        assert all(k in cfg for k in entry["reduced"])
