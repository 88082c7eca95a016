"""Fixtures for the benchmark's own tests: a copy of the benchmark shrunk to
sizes a CPU test can run, with the repository's configurations, mixes,
readers and limits otherwise unchanged."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (os.path.join(REPO, "src"), REPO):
    if p not in sys.path:
        sys.path.insert(0, p)


def _edit(root, rel, update):
    path = os.path.join(root, rel)
    with open(path) as f:
        obj = json.load(f)
    update(obj)
    with open(path, "w") as f:
        json.dump(obj, f)


def shrink(root):
    """Copy ``BENCHMARK.json`` and ``bench/`` to ``root`` at test sizes."""
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    _edit(root, "bench/configs/fleet256-campus.json", lambda c: c.update(
        nodes=6, mix_divisor=8, window_ut=13750.0, capacity=1024, depth=256))
    _edit(root, "bench/configs/paper-campus3.json", lambda c: c.update(
        mix_divisor=20, window_ut=5500.0, capacity=512, depth=256))
    _edit(root, "bench/traffic/sweep20.json", lambda c: c.update(
        workload_seeds=2, sla_scales=[0.5, 2.0]))
    for t in ("feasible", "roundrobin"):
        _edit(root, f"bench/traffic/{t}.json",
              lambda c: c.update(trace_mix_divisor=40))
    _edit(root, "bench/configs/deit-b.json", lambda c: c["arch"].update(
        img_res=32, patch=8, n_layers=2, d_model=64, n_heads=4, d_ff=128,
        n_classes=10))
    _edit(root, "bench/traffic/camera60.json", lambda c: c.update(
        pool_frames=16, frames_per_round=20, trace_seconds=1.0))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return shrink(str(tmp_path))
