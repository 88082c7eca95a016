"""Fleet simulator vs the event-heap Orchestrator, and the Pallas
fleet-feasibility kernel vs its pure-jnp oracle.

Equivalence contract (DESIGN.md §5): per-request outcomes match the host
engine exactly — deterministic policies replay move-for-move, stochastic
policies under forwarding-trace replay.  The deterministic-pytest cases
here pin the contract on fixed workloads (they run without hypothesis);
the property test widens the net over random fleets when hypothesis is
installed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import jax_queue as jq
from repro.fleetsim import (DISCARDED, MET, SimParams, pack_requests,
                            simulate, simulate_fn, topology_arrays)
from repro.fleetsim.validate import run_validation
from repro.kernels import ops, ref
from repro.orchestration import Topology, UniformWorkload, get_workload

# a 3-node workload deep in overload: forwards, forced pushes and late
# completions all exercised (~20x the window's worth of work per node)
HOT = UniformWorkload([{"S1": 30, "S4": 30, "S5": 25, "S6": 25}] * 3,
                      window=1200.0, name="hot")
POLICIES = ("random", "power_of_two", "least_loaded", "round_robin",
            "batched_feasible")


# ---------------------------------------------------------------------------
# host equivalence
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", POLICIES)
def test_matches_orchestrator_hot_fleet(policy):
    for seed in (0, 1):
        rep = run_validation(HOT, seed, policy=policy)
        assert rep.exact, (policy, seed, rep.row())
        for k in ("met_deadline", "processed", "forwards", "discarded"):
            assert rep.host[k] == rep.fleet[k], (policy, seed, k)


def test_matches_orchestrator_discard_variant():
    rep = run_validation(HOT, 0, policy="random", discard_on_exhaust=True)
    assert rep.exact and rep.fleet["discarded"] > 0, rep.row()


def test_matches_orchestrator_heterogeneous_ring():
    topo = Topology.ring(3, speeds=[1.0, 2.0, 0.5])
    rep = run_validation(HOT, 0, policy="round_robin", topology=topo)
    assert rep.exact, rep.row()


def test_batched_feasible_scores_post_retire_state():
    """Regression: the fused event_select scoring must run on the
    POST-retire ledgers.  At t=12 the d_abs=18 block has completed (busy
    chain 5→10) but is not yet retired when the event is selected; scoring
    the stale ledger inflates pending work (21 - (12+5) < 5 → bogus
    infeasible) and forwards a request the host admits on the spot."""
    from repro.core.request import Request, Service
    from repro.orchestration import Workload

    class _Fixed(Workload):
        name = "stale-retire"
        n_nodes = 2

        def generate(self, seed):
            return self._finish([
                Request(service=Service("a", 1, "x", 5.0, 100.0),
                        arrival_time=0.0, origin_node=0),
                Request(service=Service("b", 1, "x", 5.0, 17.0),
                        arrival_time=1.0, origin_node=0),
                Request(service=Service("c", 1, "x", 5.0, 9.0),
                        arrival_time=12.0, origin_node=0),
            ])

    rep = run_validation(_Fixed(), 0, policy="batched_feasible",
                         topology=Topology.full_mesh(2))
    assert rep.exact, rep.row()
    assert rep.host["forwards"] == rep.fleet["forwards"] == 0
    # and the Pallas kernel path agrees with the reference path on it
    reqs, _, _ = pack_requests(_Fixed().generate(0))
    ta = topology_arrays(Topology.full_mesh(2))
    kw = dict(policy="batched_feasible", capacity=16, depth=16)
    a = simulate(reqs, ta, SimParams.make(0), use_pallas=False, **kw)
    b = simulate(reqs, ta, SimParams.make(0), use_pallas=True, **kw)
    assert np.array_equal(np.asarray(a.outcome), np.asarray(b.outcome))
    assert int(a.forwards) == int(b.forwards) == 0


@pytest.mark.parametrize("scenario", ["paper/scenario1"])
def test_matches_orchestrator_paper_scenario(scenario):
    """The acceptance contract on a real paper workload (seed 0): exact
    per-request outcome equality under trace replay (scenarios 2-3 are
    covered by `python -m repro.fleetsim.validate`; one scenario keeps the
    suite's runtime tolerable)."""
    rep = run_validation(scenario, 0, policy="random")
    assert rep.exact, rep.row()
    assert rep.fleet["forwards"] > 0


# ---------------------------------------------------------------------------
# Pallas kernel vs oracle (interpret mode off-TPU — skips nothing, the
# kernel body lowers through the interpreter to plain XLA)
# ---------------------------------------------------------------------------
def _random_fleet(rng, K, N):
    leds = []
    frees = []
    for _ in range(K):
        led = jq.empty_ledger(N)
        free = rng.uniform(0, 50)
        for _ in range(rng.randrange(0, N + 2)):
            led, _ = jq.push(led, jnp.float32(rng.choice([5.0, 20.0, 44.0])),
                             jnp.float32(rng.uniform(10, 9000)),
                             jnp.float32(free))
        leds.append(led)
        frees.append(free)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *leds)
    return stacked, jnp.asarray(frees, jnp.float32)


@pytest.mark.parametrize("K,N", [(1, 8), (5, 16), (12, 32)])
def test_fleet_feasibility_kernel_matches_ref(K, N):
    import random
    rng = random.Random(K * 31 + N)
    stacked, frees = _random_fleet(rng, K, N)
    ps = jnp.asarray([rng.choice([5.0, 20.0, 44.0, 180.0])
                      for _ in range(K)], jnp.float32)
    for d in (30.0, 400.0, 8000.0):
        got_f, got_l = ops.fleet_feasibility(
            stacked.starts, stacked.ends, stacked.sizes, stacked.n, ps,
            jnp.float32(d), frees)
        want_f, want_l = ref.fleet_feasibility_ref(
            stacked.starts, stacked.ends, stacked.sizes, stacked.n, ps,
            jnp.float32(d), frees)
        base = jq.feasible_nodes(stacked, ps, jnp.float32(d), frees) \
            & (stacked.n < N)
        assert np.array_equal(np.asarray(got_f), np.asarray(want_f)), d
        assert np.array_equal(np.asarray(got_f), np.asarray(base)), d
        np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                                   rtol=1e-6)


def test_fleet_feasibility_kernel_head_pointer_rows():
    """Retired-slot prefixes (-BIG/0 + head offset) give the same verdict
    as the equivalent compacted plain ledger."""
    led = jq.empty_ledger(16)
    for (p, d) in ((20.0, 100.0), (44.0, 400.0), (180.0, 9000.0)):
        led, _ = jq.push(led, jnp.float32(p), jnp.float32(d), jnp.float32(0.0))
    # head-pointer view: two retired slots in front
    h = 2
    starts = jnp.concatenate([jnp.full((h,), -jq.BIG), led.starts[:-h]])
    ends = jnp.concatenate([jnp.full((h,), -jq.BIG), led.ends[:-h]])
    sizes = jnp.concatenate([jnp.zeros((h,)), led.sizes[:-h]])
    for ps, d in ((5.0, 60.0), (44.0, 300.0), (180.0, 9000.0), (500.0, 200.0)):
        got, _ = ops.fleet_feasibility(
            starts[None], ends[None], sizes[None], led.n[None],
            jnp.float32(ps)[None], jnp.float32(d), jnp.zeros((1,)),
            jnp.array([h], jnp.int32))
        want = jq.feasible(led, jnp.float32(ps), jnp.float32(d),
                           jnp.float32(0.0))
        assert bool(got[0]) == bool(want), (ps, d)


def test_simulate_use_pallas_matches_ref_path():
    reqs, _, _ = pack_requests(HOT.generate(0))
    ta = topology_arrays(Topology.full_mesh(3))
    kw = dict(policy="batched_feasible", capacity=256, depth=128)
    a = simulate(reqs, ta, SimParams.make(0), use_pallas=False, **kw)
    b = simulate(reqs, ta, SimParams.make(0), use_pallas=True, **kw)
    assert np.array_equal(np.asarray(a.outcome), np.asarray(b.outcome))
    assert int(a.met_deadline) == int(b.met_deadline)


@pytest.mark.parametrize("C,W", [(1024, 512), (256, 128), (128, 128),
                                 (257, 256), (100, 37)])
def test_row_windows_match_per_row_gather(C, W):
    from repro.fleetsim.core import _row_windows
    rng = np.random.default_rng(C + W)
    a = jnp.asarray(rng.standard_normal((6, C)), jnp.float32)
    w0 = rng.integers(0, C - W + 1, 6)
    w0[:2] = 0, C - W                          # both ends of the range
    w0 = jnp.asarray(w0, jnp.int32)
    want = jnp.take_along_axis(a, w0[:, None] + jnp.arange(W)[None], axis=1)
    assert np.array_equal(np.asarray(_row_windows(a, w0, W)),
                          np.asarray(want))


# ---------------------------------------------------------------------------
# jax_queue generalizations backing the fleet state
# ---------------------------------------------------------------------------
def test_admit_forced_tail_append_matches_host():
    from repro.core.block_queue import FastPreferentialQueue
    from repro.core.request import Request, Service
    host = FastPreferentialQueue()
    led = jq.empty_ledger(8)
    meta = (jnp.zeros((8,), jnp.int32),)
    svc = Service("s", 1, "x", 50.0, 60.0)
    for k, forced in enumerate([False, True, True]):
        r = Request(service=svc, arrival_time=0.0, origin_node=0)
        ok_host = host.push(r, 0.0, forced=forced)
        led, ok, wf, meta = jq.admit(led, jnp.float32(50.0), jnp.float32(60.0),
                                     jnp.float32(0.0), jnp.bool_(forced),
                                     meta=meta, meta_vals=(k,))
        assert bool(ok) == ok_host
        if ok_host and k > 0:
            assert bool(wf)                      # landed via the tail append
    n = int(led.n)
    np.testing.assert_allclose(np.asarray(led.ends[:n]),
                               [b.end for b in host.blocks], rtol=1e-6)
    # metadata rode the inserts: slot i holds the i-th admitted request
    assert list(np.asarray(meta[0][:n])) == [0, 1, 2]


def test_push_nodes_stacked():
    leds = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[jq.empty_ledger(8) for _ in range(3)])
    ps = jnp.array([10.0, 10.0, 1000.0], jnp.float32)
    ds = jnp.array([50.0, 50.0, 50.0], jnp.float32)
    out, ok, wf = jq.push_nodes(leds, ps, ds, jnp.zeros((3,), jnp.float32),
                                jnp.array([False, True, True]))
    assert list(np.asarray(ok)) == [True, True, True]
    assert list(np.asarray(wf)) == [False, False, True]   # infeasible+forced
    assert list(np.asarray(out.n)) == [1, 1, 1]


# ---------------------------------------------------------------------------
# vmap sweeps
# ---------------------------------------------------------------------------
def test_vmap_over_seeds_and_sla_scale():
    reqs, _, _ = pack_requests(HOT.generate(0))
    ta = topology_arrays(Topology.full_mesh(3))
    reqs = type(reqs)(*(jnp.asarray(a) for a in reqs))
    ta = type(ta)(*(jnp.asarray(a) for a in ta))
    R = reqs.arrival.shape[0]
    tgt = jnp.full((R, 2), -1, jnp.int32)
    run = simulate_fn(policy="random", capacity=256, depth=128)
    grid = jax.vmap(jax.vmap(run, in_axes=(None, None, SimParams(None, 0), None)),
                    in_axes=(None, None, SimParams(0, None), None))
    params = SimParams(seed=jnp.arange(3, dtype=jnp.int32),
                       sla_scale=jnp.array([0.5, 1.0, 2.0], jnp.float32))
    m = grid(reqs, ta, params, tgt)
    assert m.met_deadline.shape == (3, 3)
    met = np.asarray(m.met_deadline)
    # looser SLAs can only help: met rate monotone in sla_scale per seed
    assert (met[:, 0] <= met[:, 2]).all()
    assert int(m.overflow.max()) == 0
    assert int(m.window_saturation.max()) == 0


def test_single_node_degenerate_and_sla_scale_effect():
    wl = UniformWorkload([{"S6": 30}], window=100.0, name="solo")
    reqs, _, _ = pack_requests(wl.generate(0))
    ta = topology_arrays(Topology.full_mesh(1))
    m = simulate(reqs, ta, SimParams.make(0), policy="random", capacity=64)
    assert int(m.forwards) == 0                    # nowhere to forward
    assert int(m.processed) == 30                  # forced pushes run late
    tight = simulate(reqs, ta, SimParams(jnp.int32(0), jnp.float32(1e-3)),
                     policy="random", capacity=64)
    assert int(tight.met_deadline) < int(m.met_deadline)


def test_undersized_window_is_flagged_not_silent():
    """A depth window smaller than the real queue depth must surface in
    window_saturation (admission verdicts may diverge from the host's
    unbounded queue there) — never pass silently."""
    reqs, _, _ = pack_requests(HOT.generate(0))
    ta = topology_arrays(Topology.full_mesh(3))
    tiny = simulate(reqs, ta, SimParams.make(0), policy="least_loaded",
                    capacity=256, depth=8)
    assert int(tiny.window_saturation) > 0
    sized = simulate(reqs, ta, SimParams.make(0), policy="least_loaded",
                     capacity=256, depth=128)
    assert int(sized.window_saturation) == 0


def test_undersized_event_plane_is_flagged_not_silent():
    """The event-time scan's two sizing knobs — scan length (max_events)
    and the in-flight re-arrival buffer (event_buf) — must surface any
    shortfall in metrics.event_overflow, never pass silently."""
    from repro.fleetsim import NetParams, event_bound
    reqs, _, _ = pack_requests(HOT.generate(0))
    ta = topology_arrays(Topology.full_mesh(3))
    R = reqs.arrival.shape[0]
    kw = dict(policy="least_loaded", capacity=256, depth=128)
    full = simulate(reqs, ta, SimParams.make(0), **kw)
    assert int(full.event_overflow) == 0
    assert event_bound(R, 2) == 3 * R
    # scan shorter than the event stream: leftovers are counted
    short = simulate(reqs, ta, SimParams.make(0), max_events=R // 2, **kw)
    assert int(short.event_overflow) > 0
    assert int(short.scan_steps) == R // 2        # the cap ran out
    # a 1-slot buffer under a priced network (25 UT wire vs ~3.6 UT
    # arrival gaps => many referrals in flight at once): drops counted
    net = NetParams.uniform(3, 25.0)
    tight = simulate(reqs, ta, SimParams.make(0), net=net, event_buf=1, **kw)
    assert int(tight.event_overflow) > 0
    sized = simulate(reqs, ta, SimParams.make(0), net=net, **kw)
    assert int(sized.event_overflow) == 0


# ---------------------------------------------------------------------------
# the event scan ends at its last event (max_events is a cap)
# ---------------------------------------------------------------------------
_SCAN_FIELDS = ("outcome", "served_by", "completion", "transfer_used",
                "forwards_used")
_SWEEP_SLA = (0.5, 1.0, 1.5, 2.0)


def _scan_case(case: str, net: bool, max_events=None):
    """One run of the hot fleet (``case`` a policy, or ``sweep``: four
    vmapped round-robin points, one stream and SLA scale each) with
    telemetry on.  The priced wire is long enough that re-arrivals go on
    for more than a chunk after the last fresh arrival."""
    from repro.fleetsim import NetParams
    from repro.telemetry import TelemetryConfig
    ta = topology_arrays(Topology.full_mesh(3))
    kw = dict(capacity=256, depth=128, max_events=max_events,
              telemetry=TelemetryConfig(16, 12000.0))
    wire = NetParams.uniform(3, 400.0) if net else None
    if case != "sweep":
        reqs, _, _ = pack_requests(HOT.generate(0))
        return simulate(reqs, ta, SimParams.make(0, 0.5), policy=case,
                        net=wire, **kw)
    streams = [pack_requests(HOT.generate(s))[0] for s in range(4)]
    reqs = type(streams[0])(*(jnp.stack([jnp.asarray(r[i]) for r in streams])
                              for i in range(6)))
    R = reqs.arrival.shape[1]
    run = simulate_fn(policy="round_robin", network=net, **kw)
    args = (reqs, ta, SimParams(jnp.zeros(4, jnp.int32),
                                jnp.asarray(_SWEEP_SLA, jnp.float32)),
            jnp.full((R, 2), -1, jnp.int32))
    axes = (0, None, SimParams(0, 0), None)
    if net:
        args, axes = args + (wire,), axes + (None,)
    return jax.vmap(run, in_axes=axes)(*args)


def _chunked(events, E):
    """Steps the scan runs: E % SCAN_CHUNK odd steps, then whole chunks
    until no event is left, never more than E."""
    from repro.fleetsim.core import SCAN_CHUNK as C
    head = E % C
    return np.minimum(E, head + -(-np.maximum(events - head, 0) // C) * C)


@pytest.mark.parametrize("net", [False, True], ids=["no_net", "net"])
@pytest.mark.parametrize("case", ["batched_feasible", "round_robin",
                                  "sweep"])
def test_early_exit_scan_matches_exact_length_scan(case, net):
    """Stopping at the last event changes nothing: against a scan sized
    at exactly the realized events (R + forwards), every per-request
    output and the telemetry frame are bit-identical."""
    m = _scan_case(case, net)
    R = m.outcome.shape[-1]
    fwd = np.asarray(m.forwards)
    assert int(np.max(m.event_overflow)) == 0 and fwd.max() > 0
    exact = _scan_case(case, net, max_events=R + int(fwd.max()))
    assert int(np.max(exact.event_overflow)) == 0
    for f in _SCAN_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(m, f)),
                                      np.asarray(getattr(exact, f)), f)
    for a, b in zip(m.telemetry, exact.telemetry):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", ["batched_feasible", "round_robin",
                                  "sweep"])
def test_scan_steps_count_whole_chunks(case):
    """``scan_steps`` is the realized events (each point's own under
    vmap) rounded up to whole chunks: at least the events, within a chunk
    of them, and never above the cap."""
    from repro.fleetsim.core import SCAN_CHUNK
    m = _scan_case(case, net=True)
    assert int(np.max(m.event_overflow)) == 0
    R = m.outcome.shape[-1]
    E = 3 * R
    events = R + np.asarray(m.forwards)
    steps = np.asarray(m.scan_steps)
    assert steps.shape == events.shape
    assert (events <= steps).all() and (steps <= E).all()
    assert (steps < events + SCAN_CHUNK).all()
    np.testing.assert_array_equal(steps, _chunked(events, E))
    if case == "sweep":     # the points end at different events
        assert len(set(steps.tolist())) > 1
    # capped at the (largest) event count, the cap ends the last chunk
    cap = int(events.max())
    exact = _scan_case(case, True, max_events=cap)
    np.testing.assert_array_equal(np.asarray(exact.scan_steps),
                                  _chunked(events, cap))


def test_event_scan_orders_rearrivals_by_time_not_source():
    """Direct check of the deferred-re-arrival contract: with a uniform
    50 UT wire, a request forwarded at t re-arrives at t+50 — after every
    fresh arrival in (t, t+50) — and its per-request transfer_used
    records exactly the wire time paid."""
    from repro.fleetsim import NetParams
    reqs, _, _ = pack_requests(HOT.generate(0))
    ta = topology_arrays(Topology.full_mesh(3))
    m = simulate(reqs, ta, SimParams.make(0), policy="round_robin",
                 capacity=256, depth=128, net=NetParams.uniform(3, 50.0))
    nfwd = np.asarray(m.forwards_used)
    assert nfwd.sum() > 0
    np.testing.assert_allclose(np.asarray(m.transfer_used), nfwd * 50.0,
                               rtol=1e-6)
    assert float(m.transfer_time) == pytest.approx(float(nfwd.sum() * 50.0),
                                                   rel=1e-6)
    # a forwarded request can never complete before its wire-delayed
    # arrival plus its own work
    completion = np.asarray(m.completion)
    done = completion > 0
    floor = (np.asarray(reqs.arrival) + nfwd * 50.0 + np.asarray(reqs.proc))
    assert (completion[done] >= floor[done] - 1e-2).all()


def test_workload_to_arrays_round_trip():
    reqs, names = get_workload("paper/scenario1").to_arrays(0)
    assert reqs.arrival.shape == (6000,)
    assert names == ("S1", "S2", "S3", "S4", "S5", "S6")
    assert (np.diff(reqs.arrival) >= 0).all()      # arrival-sorted
    assert reqs.origin.max() == 2


# ---------------------------------------------------------------------------
# property-based equivalence (hypothesis optional, as elsewhere)
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
    _HYP = True
except ImportError:                                # pragma: no cover
    _HYP = False

if _HYP:
    # times quantized to halves: epsilon-scale f32-vs-f64 boundary ties are
    # precision artifacts, not dynamics (same convention as test_jax_queue)
    svc_mix = st.lists(
        st.tuples(st.sampled_from([5.0, 20.0, 44.0, 180.0]),
                  st.sampled_from([60.0, 400.0, 4000.0]),
                  st.integers(0, 2000).map(lambda i: i / 2.0)),
        min_size=5, max_size=40)

    @settings(max_examples=25, deadline=None)
    @given(svc_mix, st.integers(1, 4), st.integers(0, 2 ** 20),
           st.sampled_from(["random", "round_robin", "least_loaded",
                            "batched_feasible"]))
    def test_property_random_fleets_match_host(mix, n_nodes, seed, policy):
        import random as pyrandom
        from repro.core.request import Request, Service
        from repro.orchestration import Workload

        class _Fixed(Workload):
            name = "prop"
            n_nodes_ = n_nodes

            def __init__(self):
                self.n_nodes = n_nodes

            def generate(self, s):
                rng = pyrandom.Random(s)
                reqs = [Request(service=Service(f"p{p}d{d}", 1, "x", p, d),
                                arrival_time=t, origin_node=rng.randrange(n_nodes))
                        for (p, d, t) in mix]
                return self._finish(reqs)

        rep = run_validation(_Fixed(), seed, policy=policy)
        assert rep.exact, (policy, seed, rep.row())
