"""The per-layer readers of the scoring and scan-loop scopes, on hand-built
contexts: each reads its scopes' device seconds over the window's events,
and finds nothing to read where its scopes took no time."""
import json
import os

import pytest

import conftest  # noqa: F401  (puts the repository on sys.path)
from bench import run

EVENTS = 20_000
LOOP = {"fleetsim.scan": 1.2, "fleetsim.drain": 0.01}


def _ctx(policy, scopes=None):
    return dict(units=dict(attempted=15_000, events=EVENTS, calls=2),
                busy_s=4.0, window_s=4.2, traffic=dict(policy=policy),
                scope_s={"fleetsim.route": 0.7, "fleetsim.retire": 0.2,
                         **(scopes or {})})


@pytest.mark.parametrize("scopes,want", [
    ({"fleetsim.windows": 0.3, "kernels.event_select": 0.2}, 25.0),
    ({"fleetsim.windows": 0.3}, 15.0),
    ({"kernels.event_select": 0.2}, 10.0),
])
def test_select_us_reads_windows_and_scoring(scopes, want):
    read = run.reader("select_us")
    assert read(_ctx("batched_feasible", scopes)) == pytest.approx(want)


def test_scan_loop_us_reads_the_loop_scope():
    read = run.reader("scan_loop_us")
    assert read(_ctx("round_robin", LOOP)) == pytest.approx(60.0)


@pytest.mark.parametrize("metric", ["select_us", "scan_loop_us"])
def test_absent_scopes_read_nothing(metric):
    assert run.reader(metric)(_ctx("batched_feasible")) is None
    ctx = _ctx("batched_feasible", {"fleetsim.scan": 1.0,
                                    "fleetsim.windows": 1.0})
    ctx["units"]["events"] = 0
    assert run.reader(metric)(ctx) is None


def test_select_us_finds_nothing_under_round_robin():
    # round_robin scores one node's window per event, so no op carries
    # the fleet-wide scopes; the loop and the drain still do
    ctx = _ctx("round_robin", LOOP)
    assert run.reader("select_us")(ctx) is None
    assert run.reader("scan_loop_us")(ctx) is not None


@pytest.mark.parametrize("metric", ["select_us", "scan_loop_us"])
def test_entry_lists_only_cells_that_report_its_rate(metric):
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {m["name"]: m for m in bench["per_layer"]}[metric]
    rate = {m["name"]: m for m in bench["end_to_end"]}[entry["moves"]]
    assert entry["moves"] == "sim_req_per_s"
    assert entry["workloads"]
    assert set(entry["workloads"]) <= set(rate["workloads"])
    for cell in entry["workloads"]:
        assert metric in {m["name"] for m in run.resolve(cell)["per_layer"]}
