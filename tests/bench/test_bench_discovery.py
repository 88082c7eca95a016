"""A configuration, a traffic mix and a per-layer metric dropped into the
benchmark's directories are found by name, with no existing file edited."""
import hashlib
import json
import os

import pytest

import conftest  # noqa: F401  (puts the repository on sys.path)
from bench import run


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tiny_root):
    before = _digests(tiny_root)
    b = os.path.join(tiny_root, "bench")
    with open(os.path.join(b, "configs", "fleet256-campus.json")) as f:
        cfg = json.load(f)
    cfg.update(name="fleet64-campus", nodes=64)
    with open(os.path.join(b, "configs", "fleet64-campus.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "bursty.json"), "w") as f:
        json.dump(dict(policy="round_robin", calls="single", note="new mix"), f)
    with open(os.path.join(b, "metrics", "calls_per_window.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx['units']['calls'])\n")
    # the one existing file a new cell edits: BENCHMARK.json gains entries
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append(dict(name="fleet64-campus", source="test",
                                 file="bench/configs/fleet64-campus.json",
                                 reduced=[], why="test"))
    bench["workloads"].append(dict(name="fleet64-bursty",
                                   config="fleet64-campus", traffic="bursty",
                                   chips=1, why="test"))
    bench["per_layer"].append(dict(
        name="calls_per_window", unit="calls", better="higher",
        source="program_counter", layer="harness", moves="sim_req_per_s",
        workloads=["fleet64-bursty"]))
    bench["end_to_end"][0]["workloads"].append("fleet64-bursty")
    with open(path, "w") as f:
        json.dump(bench, f)

    spec = run.resolve("fleet64-bursty", tiny_root)
    assert spec["config"]["nodes"] == 64
    assert spec["traffic"]["note"] == "new mix"
    assert [m["name"] for m in spec["per_layer"]] == ["calls_per_window"]
    assert {m["name"] for m in spec["end_to_end"]} == {"sim_req_per_s",
                                                       "setup_s"}
    assert run.reader("calls_per_window", tiny_root)(
        dict(units=dict(calls=3))) == 3.0
    after = _digests(tiny_root)
    changed = {k for k in before if before[k] != after[k]}
    assert changed == {"BENCHMARK.json"}


def test_each_cell_reports_its_own_metrics():
    for name, e2e in (("fleet256-feasible", "sim_req_per_s"),
                      ("campus3-sweep", "sim_req_per_s"),
                      ("deit-b-serve", "frames_per_s")):
        spec = run.resolve(name)
        assert {m["name"] for m in spec["end_to_end"]} == {e2e, "setup_s"}
        assert spec["per_layer"] and all(m["moves"] == e2e
                                         for m in spec["per_layer"])
        for m in spec["per_layer"]:
            assert callable(run.reader(m["name"]))


def test_unknown_device_has_no_peaks():
    assert run.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks("TPU v9 imaginary")
