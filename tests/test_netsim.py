"""netsim: wire-cost semantics, zero-cost equivalence with BOTH
orchestration cores, the fleet scan's per-hop scorer vs the single-ledger
search, and the latency-never-helps property.

The equivalence contract (DESIGN.md §6): a zero-cost network
(``LinkModel.zero`` / ``NetParams.zero``) must reproduce the network-free
outputs of the event-heap Orchestrator and of ``fleetsim.simulate``
exactly — same per-request completions, same forwards, same everything.
A priced network then *consumes admission slack*: a referral can cause a
miss, which is the whole point of the subsystem.
"""
import math
import random

import numpy as np
import pytest

from repro.core.block_queue import FastPreferentialQueue
from repro.core.request import SERVICES, Request, Service
from repro.fleetsim import (NetParams, SimParams, pack_requests, simulate,
                            simulate_fn, topology_arrays)
from repro.fleetsim.validate import run_validation
from repro.netsim import (CellSite, LinkModel, RadioModel, RadioWorkload,
                          paper_campus)
from repro.netsim.radio import MIN_DEADLINE
from repro.orchestration import (ROUTER_POLICIES, Orchestrator, Router,
                                 Topology, UniformWorkload, Workload,
                                 get_workload)

jax = pytest.importorskip("jax")
import jax.numpy as jnp                                     # noqa: E402

from repro.core import jax_queue as jq                      # noqa: E402
from repro.fleetsim.scoring import event_select             # noqa: E402

# the fleetsim test suite's overloaded 3-node workload: forwards, forced
# pushes and late completions all exercised
HOT = UniformWorkload([{"S1": 30, "S4": 30, "S5": 25, "S6": 25}] * 3,
                      window=1200.0, name="hot")


_uniform_net = NetParams.uniform


# ---------------------------------------------------------------------------
# LinkModel semantics
# ---------------------------------------------------------------------------
class TestLinkModel:
    def test_transfer_delay_is_latency_plus_serialization(self):
        topo = Topology.full_mesh(3)
        lm = LinkModel.uniform(topo, latency=5.0, bandwidth=2.0)
        # S1: 8 294 400 px * 3 B = 24.8832 MB -> 5 + 24.8832 / 2
        assert lm.transfer_delay(0, 1, SERVICES["S1"]) == pytest.approx(
            5.0 + 24.8832 / 2.0)
        # payload override table wins over the frame model
        lm2 = LinkModel.uniform(topo, 5.0, 2.0, payloads={"S1": 4.0})
        assert lm2.transfer_delay(0, 1, SERVICES["S1"]) == pytest.approx(7.0)

    def test_zero_model_prices_everything_at_zero(self):
        topo = Topology.ring(4)
        lm = LinkModel.zero(topo)
        assert lm.is_zero
        assert lm.transfer_delay(0, 1, SERVICES["S1"]) == 0.0
        assert lm.uplink_delay(SERVICES["S4"]) == 0.0
        np.testing.assert_array_equal(lm.net_params().latency,
                                      np.zeros((4, 4), np.float32))

    def test_non_edge_and_self_hops(self):
        topo = Topology.ring(4)                  # 0-1, 1-2, 2-3, 3-0
        lm = LinkModel.uniform(topo, 10.0, math.inf)
        assert lm.transfer_delay(0, 0, SERVICES["S3"]) == 0.0
        with pytest.raises(ValueError):
            lm.transfer_delay(0, 2, SERVICES["S3"])    # not a ring edge

    def test_preset_backhaul_pricing(self):
        topo = Topology.two_tier(2, n_cloud=1, cloud_speed=4.0)
        lm = LinkModel.preset(topo, "campus", cloud_nodes=[2])
        s3 = SERVICES["S3"]
        edge_cloud = lm.transfer_delay(0, 2, s3)
        assert edge_cloud > lm.uplink_delay(s3)
        # backhaul numbers, not LAN numbers
        assert edge_cloud == pytest.approx(
            30.0 + lm.payload_of(s3) / 0.3125)
        with pytest.raises(ValueError):
            LinkModel.preset(topo, "nope")

    def test_paper_campus_preset(self):
        topo, lm = paper_campus()
        assert topo.n_nodes == 3 and lm.name == "campus"
        assert not lm.is_zero
        net = lm.net_params()
        assert net.latency.shape == (3, 3)
        assert net.latency[0, 1] == pytest.approx(5.0)
        assert net.latency[0, 0] == 0.0

    def test_matrix_validation(self):
        topo = Topology.full_mesh(2)
        with pytest.raises(ValueError):
            LinkModel(topo, latency=[[0.0, 1.0]])          # bad shape
        with pytest.raises(ValueError):
            LinkModel(topo, latency=-1.0)
        with pytest.raises(ValueError):
            LinkModel(topo, bandwidth=0.0)


# ---------------------------------------------------------------------------
# zero-cost equivalence: the event-heap core
# ---------------------------------------------------------------------------
class TestOrchestratorZeroCost:
    @pytest.mark.parametrize("scenario", [1, 2, 3])
    def test_paper_scenarios_identical(self, scenario):
        """Zero-cost network == no network, per-request, on every paper
        scenario (the golden battery keeps holding with netsim wired in)."""
        wl = get_workload(f"paper/scenario{scenario}")
        topo = Topology.full_mesh(wl.n_nodes)
        base_reqs, net_reqs = wl.generate(0), wl.generate(0)
        base = Orchestrator(topo, FastPreferentialQueue,
                            Router(topo, seed=0)).run(base_reqs)
        netr = Orchestrator(topo, FastPreferentialQueue,
                            Router(topo, seed=0),
                            network=LinkModel.zero(topo)).run(net_reqs)
        assert (base.met_deadline, base.forwards, base.discarded,
                base.processed) == \
               (netr.met_deadline, netr.forwards, netr.discarded,
                netr.processed)
        assert netr.mean_response_time == base.mean_response_time
        assert netr.transfer_time == 0.0
        for a, b in zip(base_reqs, net_reqs):   # same generation order
            assert a.completion_time == b.completion_time
            assert a.served_by == b.served_by

    @pytest.mark.parametrize("policy", ROUTER_POLICIES)
    def test_policy_battery_identical(self, policy):
        topo = Topology.full_mesh(3)
        wl = HOT
        a_reqs, b_reqs = wl.generate(1), wl.generate(1)
        a = Orchestrator(topo, FastPreferentialQueue,
                         Router(topo, policy, seed=7)).run(a_reqs)
        b = Orchestrator(topo, FastPreferentialQueue,
                         Router(topo, policy, seed=7),
                         network=LinkModel.zero(topo)).run(b_reqs)
        assert a.met_deadline == b.met_deadline
        assert a.forwards == b.forwards
        for x, y in zip(a_reqs, b_reqs):
            assert x.completion_time == y.completion_time

    def test_network_topology_mismatch_rejected(self):
        with pytest.raises(ValueError):
            Orchestrator(Topology.full_mesh(3), FastPreferentialQueue,
                         network=LinkModel.zero(Topology.full_mesh(4)))

    def test_router_scoring_sees_forward_delay(self):
        """The orchestrator syncs forward_delay into the router alongside
        the network, so batched_feasible scores the true re-arrival —
        also when the caller already gave the router the same network."""
        topo = Topology.full_mesh(2)
        lm = LinkModel.zero(topo)
        router = Router(topo, "batched_feasible", seed=0)
        orch = Orchestrator(topo, FastPreferentialQueue, router,
                            forward_delay=50.0, network=lm)
        assert router.forward_delay == 50.0
        assert router.network is orch.network
        pre = Router(topo, "batched_feasible", seed=0, network=lm)
        Orchestrator(topo, FastPreferentialQueue, pre,
                     forward_delay=7.0, network=lm)
        assert pre.forward_delay == 7.0
        # conflicting link models are a configuration error, not a silent
        # scoring mismatch
        with pytest.raises(ValueError):
            Orchestrator(topo, FastPreferentialQueue,
                         Router(topo, network=LinkModel.zero(topo)),
                         network=lm)

    def test_priced_net_requires_payload(self):
        """simulate(net=...) must refuse payload-less RequestArrays
        instead of silently zeroing the serialization cost."""
        from repro.fleetsim import RequestArrays
        r = RequestArrays(
            arrival=np.array([0.0], np.float32),
            proc=np.array([5.0], np.float32),
            rel_deadline=np.array([50.0], np.float32),
            origin=np.array([0], np.int32),
            service=np.array([0], np.int32))
        ta = topology_arrays(Topology.full_mesh(2))
        with pytest.raises(ValueError, match="payload"):
            simulate(r, ta, SimParams.make(0), net=NetParams.zero(2),
                     capacity=16)
        # without a network the old 5-field form still simulates
        m = simulate(r, ta, SimParams.make(0), capacity=16)
        assert int(m.processed) == 1


# ---------------------------------------------------------------------------
# a referral can cause a miss (the paper's economics, restored)
# ---------------------------------------------------------------------------
def _two_node_contention():
    """Node 0's CPU is pinned by a long job; a tight request arriving next
    must refer to node 1 — free it costs nothing, priced it misses."""
    blocker = Service("blk", 1, "x", proc_time=100.0, deadline=105.0)
    tight = Service("tight", 1, "x", proc_time=10.0, deadline=30.0)
    return [Request(service=blocker, arrival_time=0.0, origin_node=0),
            Request(service=tight, arrival_time=1.0, origin_node=0)]


class TestReferralCost:
    def test_free_referral_meets_priced_referral_misses(self):
        topo = Topology.full_mesh(2)
        free = Orchestrator(topo, FastPreferentialQueue,
                            network=LinkModel.zero(topo))
        r_free = free.run(_two_node_contention())
        assert r_free.met_deadline == 2 and r_free.forwards == 1

        priced = Orchestrator(topo, FastPreferentialQueue,
                              network=LinkModel.uniform(topo, latency=25.0,
                                                        bandwidth=math.inf))
        reqs = _two_node_contention()
        r_priced = priced.run(reqs)
        # wire time ate the slack: arrival at node 1 is t=26, 10 UT of work
        # cannot finish by the absolute deadline 31 -> infeasible there too,
        # referred back, forced, late
        assert r_priced.met_deadline == 1
        assert r_priced.transfer_time > 0.0
        tight = [r for r in reqs if r.service.name == "tight"][0]
        assert tight.completion_time is not None
        assert not tight.met_deadline

    def test_host_and_fleet_agree_on_priced_sparse_chain(self):
        """Arrivals sparser than the wire delays: the simplest priced
        referral chain, cross-validated per request (the dense,
        interleaved variant follows in the next test)."""
        class _Fixed(Workload):
            name = "sparse-chain"
            n_nodes = 2

            def generate(self, seed):
                return self._finish(_two_node_contention())

        topo = Topology.full_mesh(2)
        lm = LinkModel.uniform(topo, latency=25.0, bandwidth=math.inf)
        rep = run_validation(_Fixed(), 0, policy="round_robin",
                             topology=topo, network=lm)
        assert rep.exact, rep.row()
        assert rep.fleet["forwards"] >= 1

    def test_interleaved_arrival_inside_priced_chain_is_exact(self):
        """The case the speculative-chain scan could NOT replay: a priced
        referral is in flight when another arrival lands at its target and
        eats the slack the source-step scoring saw.  The event-time scan
        defers the re-arrival to its true wire-delayed time, so the
        outcome (tight request: late) matches the heap per-request."""
        blocker = Service("blk", 1, "x", proc_time=100.0, deadline=105.0)
        tight = Service("tight", 1, "x", proc_time=10.0, deadline=40.0)
        interloper = Service("mid", 1, "x", proc_time=30.0, deadline=35.0)

        class _Fixed(Workload):
            name = "interleaved-chain"
            n_nodes = 2

            def generate(self, seed):
                return self._finish([
                    Request(service=blocker, arrival_time=0.0, origin_node=0),
                    Request(service=tight, arrival_time=1.0, origin_node=0),
                    # lands at node 1 at t=10 — before the tight request's
                    # wire-delayed re-arrival at t=26 — and occupies the
                    # CPU until t=40, past the tight deadline of t=41-10
                    Request(service=interloper, arrival_time=10.0,
                            origin_node=1),
                ])

        topo = Topology.full_mesh(2)
        lm = LinkModel.uniform(topo, latency=25.0, bandwidth=math.inf)
        rep = run_validation(_Fixed(), 0, policy="round_robin",
                             topology=topo, network=lm)
        assert rep.exact, rep.row()
        # the interleaving really bit: the tight request paid two referrals
        # and still ran late on both engines
        assert rep.fleet["forwards"] == rep.host["forwards"] == 2
        assert rep.fleet["met_deadline"] == rep.host["met_deadline"] == 2

    def test_validation_zero_net_exact_on_hot_fleet(self):
        """run_validation --net zero equivalent: the netsim machinery in
        both engines reproduces the free-network outcomes exactly."""
        topo = Topology.full_mesh(3)
        for policy in ("random", "batched_feasible"):
            rep = run_validation(HOT, 0, policy=policy, topology=topo,
                                 network=LinkModel.zero(topo))
            assert rep.exact, (policy, rep.row())

    @pytest.mark.parametrize("policy", ["random", "power_of_two",
                                        "least_loaded", "round_robin",
                                        "batched_feasible"])
    def test_priced_policy_battery_exact_under_campus(self, policy):
        """The §7 contract on a priced network: per-request (outcome,
        node, transfer_time) equality for every native policy — the
        deterministic ones move-for-move, the stochastic ones under
        forwarding-trace replay."""
        topo = Topology.full_mesh(3)
        rep = run_validation(HOT, 0, policy=policy, topology=topo,
                             network=LinkModel.campus(topo))
        assert rep.exact, (policy, rep.row())
        assert rep.fleet["forwards"] > 0
        assert rep.fleet["transfer_time"] > 0

    @pytest.mark.parametrize("profile", ["metro", "wan"])
    def test_priced_deterministic_policies_exact_on_heavy_profiles(
            self, profile):
        """Metro/WAN pricing (the expensive-referral regimes) for the two
        deterministic policies, which replay without a trace."""
        topo = Topology.full_mesh(3)
        lm = LinkModel.preset(topo, profile)
        for policy in ("round_robin", "batched_feasible"):
            rep = run_validation(HOT, 0, policy=policy, topology=topo,
                                 network=lm)
            assert rep.exact, (profile, policy, rep.row())


# ---------------------------------------------------------------------------
# fleetsim: zero-cost equivalence + the NetParams sweep axis
# ---------------------------------------------------------------------------
class TestFleetsimNet:
    @pytest.mark.parametrize("policy", ["random", "least_loaded",
                                        "round_robin", "batched_feasible"])
    def test_zero_net_outcomes_identical(self, policy):
        reqs, _, _ = pack_requests(HOT.generate(0))
        ta = topology_arrays(Topology.full_mesh(3))
        kw = dict(policy=policy, capacity=256, depth=128)
        a = simulate(reqs, ta, SimParams.make(0), **kw)
        b = simulate(reqs, ta, SimParams.make(0), net=NetParams.zero(3), **kw)
        assert np.array_equal(np.asarray(a.outcome), np.asarray(b.outcome))
        assert np.array_equal(np.asarray(a.completion),
                              np.asarray(b.completion))
        assert np.array_equal(np.asarray(a.served_by),
                              np.asarray(b.served_by))
        assert int(a.forwards) == int(b.forwards)

    def test_latency_ladder_matches_host_rung_for_rung(self):
        """The event-time scan inherits the host's full priced dynamics —
        including the non-monotone deep-overload regime where a delayed
        re-arrival lands after a queue drains and *helps*.  The old
        speculative-chain scan was monotone by construction (wire time
        was purely an admission-slack tax); the contract now is the far
        stronger one: met-count equality with the event heap at every
        rung of the ladder (DESIGN.md §7)."""
        reqs, _, _ = pack_requests(HOT.generate(0))
        topo = Topology.full_mesh(3)
        ta = topology_arrays(topo)
        mets = []
        for lam in (0.0, 2.0, 10.0, 50.0, 200.0, 1000.0):
            m = simulate(reqs, ta, SimParams.make(0), policy="least_loaded",
                         capacity=256, depth=128, net=_uniform_net(3, lam))
            lm = LinkModel.uniform(topo, latency=lam, bandwidth=math.inf)
            host = Orchestrator(topo, FastPreferentialQueue,
                                Router(topo, "least_loaded", seed=0),
                                network=lm).run(HOT.generate(0))
            assert int(m.met_deadline) == host.met_deadline, lam
            mets.append(int(m.met_deadline))
        assert min(mets) < mets[0]             # the tax is real
        assert mets != sorted(mets, reverse=True)   # and not a pure tax:
        # deep latency relieves contention on this overloaded fixture,
        # exactly as the heap reports

    def test_netparams_is_a_vmap_axis(self):
        """latency ladder as ONE device call: vmap over stacked NetParams."""
        reqs, _, _ = pack_requests(HOT.generate(0))
        reqs = type(reqs)(*(jnp.asarray(a) for a in reqs))
        ta = topology_arrays(Topology.full_mesh(3))
        ta = type(ta)(*(jnp.asarray(a) for a in ta))
        R = reqs.arrival.shape[0]
        tgt = jnp.full((R, 2), -1, jnp.int32)
        lams = (0.0, 10.0, 200.0)
        stacked = NetParams(
            latency=jnp.stack([_uniform_net(3, l).latency for l in lams]),
            inv_bw=jnp.stack([_uniform_net(3, l).inv_bw for l in lams]))
        run = simulate_fn(policy="least_loaded", capacity=256, depth=128,
                          network=True)
        sweep = jax.vmap(run, in_axes=(None, None, None, None, 0))
        m = sweep(reqs, ta, SimParams.make(0), tgt, stacked)
        assert m.met_deadline.shape == (3,)
        met = np.asarray(m.met_deadline)
        # each cell == the individually-computed point
        for k, lam in enumerate(lams):
            solo = simulate(reqs, ta, SimParams.make(0),
                            policy="least_loaded", capacity=256, depth=128,
                            net=_uniform_net(3, lam))
            assert met[k] == int(solo.met_deadline)

    def test_serialization_cost_scales_with_payload(self):
        """Pure-bandwidth network: 4K referrals pay more wire time than HD
        ones.  The met-rate drop is no longer a sound proxy — the
        event-time scan inherits the heap's real dynamics, where deferred
        re-arrivals can *help* an overloaded fixture — so the wire cost is
        asserted directly on the per-request ``transfer_used`` metric:
        every referral pays exactly ``payload · inv_bw`` per hop."""
        ta = topology_arrays(Topology.full_mesh(3))
        heavy = UniformWorkload([{"S4": 40}] * 3, window=600.0, name="4k")
        light = UniformWorkload([{"S6": 40 * 9}] * 3, window=600.0,
                                name="hd")      # same total work (180 vs 20)
        net = NetParams(latency=np.zeros((3, 3), np.float32),
                        inv_bw=_uniform_net(3, 0.0, inv_bw=4.0).inv_bw)
        per_fwd = {}
        for wl in (heavy, light):
            reqs, _, _ = pack_requests(wl.generate(0))
            priced = simulate(reqs, ta, SimParams.make(0), capacity=512,
                              policy="least_loaded", net=net)
            nfwd = np.asarray(priced.forwards_used)
            wire = np.asarray(priced.transfer_used)
            assert nfwd.sum() > 0
            # each hop pays exactly its frame's serialization time
            payload = np.asarray(reqs.payload)
            np.testing.assert_allclose(wire, nfwd * payload * 4.0,
                                       rtol=1e-5)
            per_fwd[wl.name] = wire.sum() / nfwd.sum()
            # free vs priced outcomes really differ (the wire is not free)
            free = simulate(reqs, ta, SimParams.make(0), capacity=512,
                            policy="least_loaded")
            assert int(free.met_deadline) != int(priced.met_deadline)
        # a 4K frame is 9x an HD frame on the wire, referral for referral
        np.testing.assert_allclose(per_fwd["4k"] / per_fwd["hd"], 9.0,
                                   rtol=1e-4)


# ---------------------------------------------------------------------------
# the fleet scan's per-hop scorer vs the router's single-ledger search
# (jax_queue.feasible_nodes, which shares none of its code) at each
# candidate's wire-delayed arrival
# ---------------------------------------------------------------------------
def _random_fleet(rng, K, N):
    leds, frees = [], []
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


def _net(rng, K):
    """(K, K) latency / inverse-bandwidth tensors with a zero diagonal."""
    lat = [[0.0 if i == j else rng.uniform(0.0, 120.0) for j in range(K)]
           for i in range(K)]
    ibw = [[0.0 if i == j else rng.choice([0.0, 0.1, 1.0]) for j in range(K)]
           for i in range(K)]
    return (jnp.asarray(np.asarray(lat, np.float32)),
            jnp.asarray(np.asarray(ibw, np.float32)))


def _fresh(t, node, d, p, payload):
    """Scalar candidates of a lone fresh arrival (the buffer is empty)."""
    return (jnp.float32(t), jnp.int32(node), jnp.float32(d), jnp.float32(p),
            jnp.float32(payload), True,
            jnp.float32(0.0), jnp.int32(0), jnp.float32(0.0),
            jnp.float32(0.0), jnp.float32(0.0), False)


def _assert_columns(sel, stacked, busy, speeds, lat, ibw, t, node, d, p,
                    payload):
    """Every column of ``event_select`` is the single-ledger search at
    ``max(t + latency[node] + payload * inv_bw[node], busy)``."""
    N = stacked.starts.shape[-1]
    ps = jnp.float32(p) / speeds
    arrive = jnp.float32(t) + lat[node] + jnp.float32(payload) * ibw[node]
    free = jnp.maximum(arrive, busy)
    want_f = jq.feasible_nodes(stacked, ps, jnp.float32(d), free) \
        & (stacked.n < N)
    _, want_j, want_cap = jax.vmap(
        lambda led, p1, cf: jq._search(led, p1, jnp.float32(d), cf))(
            stacked, ps, free)
    _, _, _, feas, arr, j, cap, load = sel
    assert np.array_equal(np.asarray(feas), np.asarray(want_f))
    assert np.array_equal(np.asarray(arr), np.asarray(arrive))
    assert np.array_equal(np.asarray(j), np.asarray(want_j))
    assert np.array_equal(np.asarray(cap), np.asarray(want_cap))
    np.testing.assert_allclose(np.asarray(load),
                               np.asarray(stacked.sizes).sum(axis=1),
                               rtol=1e-6)


@pytest.mark.parametrize("K,N", [(1, 8), (5, 16), (12, 32)])
def test_event_select_priced_hop_matches_feasible_nodes(K, N):
    rng = random.Random(K * 131 + N)
    stacked, busy = _random_fleet(rng, K, N)
    speeds = jnp.asarray([rng.choice([0.5, 1.0, 2.0]) for _ in range(K)],
                         jnp.float32)
    lat, ibw = _net(rng, K)
    for d, t, payload in ((400.0, 10.0, 24.8832), (8000.0, 120.0, 2.0736),
                          (60.0, 0.0, 0.9216)):
        node, p = rng.randrange(K), rng.choice([5.0, 20.0, 44.0, 180.0])
        sel = event_select(*_fresh(t, node, d, p, payload), stacked.starts,
                           stacked.ends, stacked.sizes, stacked.n, None,
                           speeds, busy, lat, ibw)
        assert bool(sel[0]) and float(sel[1]) == t and int(sel[2]) == node
        _assert_columns(sel, stacked, busy, speeds, lat, ibw, t, node, d, p,
                        payload)


def test_event_select_zero_delay_degenerates_to_feasible_nodes():
    rng = random.Random(42)
    K, N = 6, 16
    stacked, busy = _random_fleet(rng, K, N)
    zeros = jnp.zeros((K, K), jnp.float32)
    ones = jnp.ones((K,), jnp.float32)
    for d, t in ((300.0, 0.0), (4000.0, 55.0)):
        sel = event_select(*_fresh(t, 1, d, 20.0, 24.8), stacked.starts,
                           stacked.ends, stacked.sizes, stacked.n, None,
                           ones, busy, zeros, zeros)
        _assert_columns(sel, stacked, busy, ones, zeros, zeros, t, 1, d,
                        20.0, 24.8)
        np.testing.assert_allclose(np.asarray(sel[4]),
                                   np.full((K,), t, np.float32))


def test_event_select_head_pointer_rows():
    """Retired-slot prefixes give the same verdict as the compacted plain
    ledger, with the arrival delay applied on top."""
    led = jq.empty_ledger(16)
    for (p, d) in ((20.0, 100.0), (44.0, 400.0), (180.0, 9000.0)):
        led, _ = jq.push(led, jnp.float32(p), jnp.float32(d), jnp.float32(0.0))
    h = 2
    starts = jnp.concatenate([jnp.full((h,), -jq.BIG), led.starts[:-h]])
    ends = jnp.concatenate([jnp.full((h,), -jq.BIG), led.ends[:-h]])
    sizes = jnp.concatenate([jnp.zeros((h,)), led.sizes[:-h]])
    for ps, d, lam in ((5.0, 60.0, 0.0), (5.0, 60.0, 30.0),
                       (44.0, 300.0, 10.0), (180.0, 9000.0, 100.0)):
        sel = event_select(*_fresh(0.0, 0, d, ps, 0.0), starts[None],
                           ends[None], sizes[None], led.n[None],
                           jnp.array([h], jnp.int32), jnp.ones((1,)),
                           jnp.zeros((1,)), jnp.full((1, 1), lam),
                           jnp.zeros((1, 1)))
        want = jq.feasible(led, jnp.float32(ps), jnp.float32(d),
                           jnp.float32(lam))
        assert bool(sel[3][0]) == bool(want), (ps, d, lam)


@pytest.mark.parametrize("K,N", [(1, 8), (5, 16), (12, 32)])
def test_event_select_kernel_matches_ref(K, N):
    """The merge follows the heap's ``(time, seq)`` rule written out here
    (fresh wins ties; an empty buffer or an exhausted stream leaves the
    other candidate), and the chosen event's columns are the single-ledger
    search at its wire-delayed arrival."""
    rng = random.Random(K * 17 + N)
    stacked, busy = _random_fleet(rng, K, N)
    speeds = jnp.asarray([rng.choice([0.5, 1.0, 2.0]) for _ in range(K)],
                         jnp.float32)
    lat, ibw = _net(rng, K)
    cases = [
        # (t_a, avail_a, t_b, avail_b): both live, tie (fresh must win),
        # buffer earlier, fresh only, buffer only, neither
        (10.0, True, 40.0, True), (25.0, True, 25.0, True),
        (90.0, True, 12.0, True), (10.0, True, 5.0, False),
        (3.0, False, 55.0, True), (1.0, False, 2.0, False),
    ]
    for t_a, av_a, t_b, av_b in cases:
        a = (t_a, rng.randrange(K), rng.uniform(50, 9000), 20.0,
             rng.choice([0.92, 24.88]))
        b = (t_b, rng.randrange(K), rng.uniform(50, 9000), 44.0,
             rng.choice([0.92, 24.88]))
        cand = lambda c, av: (jnp.float32(c[0]), jnp.int32(c[1]),
                              jnp.float32(c[2]), jnp.float32(c[3]),
                              jnp.float32(c[4]), av)
        sel = event_select(*cand(a, av_a), *cand(b, av_b), stacked.starts,
                           stacked.ends, stacked.sizes, stacked.n, None,
                           speeds, busy, lat, ibw)
        take_fresh = av_a and (t_a <= t_b or not av_b)
        won = a if take_fresh else b
        assert bool(sel[0]) == take_fresh, (t_a, av_a, t_b, av_b)
        assert float(sel[1]) == won[0]
        assert int(sel[2]) == won[1]
        _assert_columns(sel, stacked, busy, speeds, lat, ibw, won[0], won[1],
                        won[2], won[3], won[4])


def test_event_select_zero_net_scores_event_node_at_true_arrival():
    """With zero net tensors the selected node's own column degenerates to
    the plain fleet-feasibility verdict at the event time."""
    rng = random.Random(7)
    K, N = 6, 16
    stacked, busy = _random_fleet(rng, K, N)
    zeros = jnp.zeros((K, K), jnp.float32)
    ones = jnp.ones((K,), jnp.float32)
    for t, d in ((10.0, 300.0), (55.0, 4000.0)):
        sel = event_select(
            jnp.float32(t), jnp.int32(2), jnp.float32(d), jnp.float32(20.0),
            jnp.float32(1.0), True,
            jnp.float32(t + 1), jnp.int32(0), jnp.float32(d),
            jnp.float32(20.0), jnp.float32(1.0), True,
            stacked.starts, stacked.ends, stacked.sizes, stacked.n, None,
            ones, busy, zeros, zeros)
        base_f = jq.feasible_nodes(
            stacked, jnp.full((K,), 20.0, jnp.float32), jnp.float32(d),
            jnp.maximum(jnp.float32(t), busy)) & (stacked.n < N)
        assert np.array_equal(np.asarray(sel[3]), np.asarray(base_f))
        np.testing.assert_allclose(np.asarray(sel[4]),
                                   np.full((K,), t, np.float32))


# ---------------------------------------------------------------------------
# radio: ingress, uplink budget, handover
# ---------------------------------------------------------------------------
class TestRadio:
    def test_zero_radio_reproduces_base_workload(self):
        topo = Topology.full_mesh(3)
        radio = RadioModel.per_node(topo)       # 0-cost cells, identity-ish
        wl = RadioWorkload(HOT, radio)
        base = HOT.generate(0)
        got = wl.generate(0)
        assert len(got) == len(base)
        for a, b in zip(base, got):
            assert b.arrival_time == a.arrival_time
            assert b.origin_node == a.origin_node
            assert b.service is a.service        # untouched, not copied

    def test_uplink_consumes_sla_budget(self):
        cells = [CellSite(0, node=0, uplink_latency=10.0)]
        radio = RadioModel(cells)
        base = UniformWorkload([{"S6": 5}], window=100.0, name="u")
        got = RadioWorkload(base, radio).generate(0)
        orig = base.generate(0)
        for a, b in zip(orig, got):
            assert b.arrival_time == pytest.approx(a.arrival_time + 10.0)
            assert b.service.deadline == pytest.approx(
                a.service.deadline - 10.0)
            # absolute deadline is anchored at capture time
            assert b.deadline == pytest.approx(a.deadline)

    def test_uplink_bandwidth_prices_the_frame(self):
        topo, lm = paper_campus(1)
        cells = [CellSite(0, node=0, uplink_latency=2.0,
                          uplink_bandwidth=0.625)]
        radio = RadioModel(cells)
        base = UniformWorkload([{"S1": 1}], window=1.0, name="b")
        got = RadioWorkload(base, radio, link=lm).generate(0)[0]
        orig = base.generate(0)[0]
        d_up = 2.0 + 24.8832 / 0.625
        assert got.arrival_time == pytest.approx(orig.arrival_time + d_up)

    def test_handover_rehomes_traffic(self):
        cells = [CellSite(0, node=0), CellSite(1, node=1)]
        radio = RadioModel(cells, attachment={7: 0},
                           mobility={7: [(50.0, 1)]})
        assert radio.ingress(7, 0.0) == 0
        assert radio.ingress(7, 49.9) == 0
        assert radio.ingress(7, 50.0) == 1       # handover applied at t
        assert radio.ingress(7, 1e9) == 1
        assert radio.handovers(7) == 1

    def test_random_mobility_is_deterministic(self):
        topo = Topology.full_mesh(3)
        a = RadioModel.per_node(topo).with_random_mobility(
            20, horizon=1000.0, handovers_per_ue=2.0, seed=3)
        b = RadioModel.per_node(topo).with_random_mobility(
            20, horizon=1000.0, handovers_per_ue=2.0, seed=3)
        assert a.mobility == b.mobility
        assert sum(a.handovers(u) for u in range(20)) > 0

    def test_radio_workload_end_to_end(self):
        """Mobility + uplink pricing through the event-heap core."""
        topo = Topology.full_mesh(3)
        lm = LinkModel.campus(topo)
        radio = RadioModel.from_link(lm).with_random_mobility(
            3, horizon=1200.0, handovers_per_ue=1.0, seed=0)
        wl = RadioWorkload(HOT, radio, link=lm)
        res = Orchestrator(topo, FastPreferentialQueue,
                           Router(topo, seed=0), network=lm).run(
            wl.generate(0))
        assert res.processed == res.total_requests
        # the uplink tax is strictly harmful vs the wired-only run
        base = Orchestrator(topo, FastPreferentialQueue,
                            Router(topo, seed=0), network=lm).run(
            HOT.generate(0))
        assert res.met_deadline <= base.met_deadline

    def test_handover_on_exact_arrival_tick(self):
        """A handover scheduled at exactly a request's capture time takes
        effect for that request (bisect_right: the handover at t owns t),
        and the re-homed workload still cross-validates exactly against
        the event heap."""
        cells = [CellSite(0, node=0), CellSite(1, node=1),
                 CellSite(2, node=2)]
        radio = RadioModel(cells, attachment={0: 0},
                           mobility={0: [(50.0, 1)]})

        svc = Service("s", 1, "x", proc_time=10.0, deadline=400.0)

        class _Ticked(Workload):
            name = "tick"
            n_nodes = 3

            def generate(self, seed):
                return self._finish([
                    Request(service=svc, arrival_time=t, origin_node=0)
                    for t in (49.5, 50.0, 50.5)])

        wl = RadioWorkload(_Ticked(), radio)
        got = wl.generate(0)
        assert [r.origin_node for r in got] == [0, 1, 1]
        # the tick itself is not half-open on the other side: one ULP
        # before the handover still enters the old cell
        assert radio.ingress(0, np.nextafter(50.0, 0.0)) == 0
        rep = run_validation(wl, 0, policy="round_robin",
                             topology=Topology.full_mesh(3))
        assert rep.exact, rep.row()

    def test_uplink_exhausting_sla_is_doa_on_both_engines(self):
        """A request whose uplink alone eats the whole SLA budget must be
        dead on arrival — admitted nowhere, forced, late — identically on
        the event heap and the event-time scan."""
        topo = Topology.full_mesh(2)
        cells = [CellSite(0, node=0, uplink_latency=5000.0),
                 CellSite(1, node=1, uplink_latency=5000.0)]
        radio = RadioModel(cells)
        base = UniformWorkload([{"S6": 4}, {"S6": 4}], window=200.0,
                               name="doa")   # S6 deadline 4000 < uplink 5000
        wl = RadioWorkload(base, radio)
        reqs = wl.generate(0)
        # the budget clamps to the positive floor, never goes negative
        assert all(0 < r.service.deadline <= MIN_DEADLINE for r in reqs)
        res = Orchestrator(topo, FastPreferentialQueue,
                           Router(topo, "round_robin", seed=0)).run(reqs)
        assert res.processed == len(reqs)        # forced pushes still run
        assert res.met_deadline == 0             # ... but never in budget
        rep = run_validation(wl, 0, policy="round_robin", topology=topo)
        assert rep.exact, rep.row()
        assert rep.fleet["met_deadline"] == 0
        assert rep.fleet["processed"] == len(reqs)

    def test_validation_errors(self):
        with pytest.raises(ValueError):
            RadioModel([])
        with pytest.raises(ValueError):
            RadioModel([CellSite(0, 0), CellSite(0, 1)])
        with pytest.raises(ValueError):
            RadioModel([CellSite(0, 0)], mobility={0: [(1.0, 9)]})


# ---------------------------------------------------------------------------
# properties: link latency never helps — in its universally-true forms.
#
# The aggregate form ("met(lam) <= met(0)") holds on the fixture ladders
# above but is NOT universal: brute-force sweeps find rare admission
# cascades (~1/3000 random fleets) where pushing one big request off a
# node frees slack for two small ones, and the host engine shows the
# same under deep overload (EXPERIMENTS.md §Netsim).  What IS universal,
# and what these properties pin, is the per-request economics: a zero
# network is free, and a priced referral chain is always *paid for* —
# no request completes as if it had not traveled.
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
    _HYP = True
except ImportError:                                # pragma: no cover
    _HYP = False

if _HYP:
    svc_mix = st.lists(
        st.tuples(st.sampled_from([5.0, 20.0, 44.0]),
                  st.sampled_from([60.0, 400.0, 4000.0]),
                  st.integers(0, 2000).map(lambda i: i / 2.0)),
        min_size=5, max_size=40)

    def _pack(mix, n_nodes):
        rng = random.Random(len(mix) * 7 + n_nodes)
        reqs = [Request(service=Service(f"p{p}d{d}", 1, "x", p, d),
                        arrival_time=t, origin_node=rng.randrange(n_nodes))
                for (p, d, t) in mix]
        reqs.sort(key=lambda r: (r.arrival_time, r.rid))
        packed, _, _ = pack_requests(reqs)
        return packed

    @settings(max_examples=25, deadline=None)
    @given(svc_mix, st.integers(2, 4),
           st.sampled_from(["random", "least_loaded", "round_robin",
                            "batched_feasible"]))
    def test_property_zero_latency_is_free(mix, n_nodes, policy):
        """NetParams.zero == no network, per-request, on random fleets."""
        packed = _pack(mix, n_nodes)
        ta = topology_arrays(Topology.full_mesh(n_nodes))
        kw = dict(policy=policy, capacity=128, depth=64)
        a = simulate(packed, ta, SimParams.make(0), **kw)
        b = simulate(packed, ta, SimParams.make(0),
                     net=NetParams.zero(n_nodes), **kw)
        assert np.array_equal(np.asarray(a.outcome), np.asarray(b.outcome))
        assert np.array_equal(np.asarray(a.completion),
                              np.asarray(b.completion))

    @settings(max_examples=25, deadline=None)
    @given(svc_mix, st.integers(2, 4),
           st.sampled_from([2.0, 10.0, 50.0, 250.0, 1500.0]),
           st.sampled_from(["random", "least_loaded", "round_robin"]))
    def test_property_wire_time_is_conserved(mix, n_nodes, lam, policy):
        """Every processed request really paid its referral wire time:
        completion >= arrival + forwards_used * lam + proc.  Latency can
        therefore only ever push THIS request's completion later for the
        same placement — the sound per-request reading of "adding link
        latency never increases met deadlines"."""
        packed = _pack(mix, n_nodes)
        ta = topology_arrays(Topology.full_mesh(n_nodes))
        m = simulate(packed, ta, SimParams.make(0),
                     net=_uniform_net(n_nodes, lam),
                     policy=policy, capacity=128, depth=64)
        completion = np.asarray(m.completion)
        nfwd = np.asarray(m.forwards_used)
        done = completion > 0
        floor = (np.asarray(packed.arrival) + nfwd * lam
                 + np.asarray(packed.proc))
        assert (completion[done] >= floor[done] - 1e-2).all()
        # and met still means met: the admission test saw the wire cost
        d_abs = np.asarray(packed.arrival) + np.asarray(packed.rel_deadline)
        met = np.asarray(m.outcome) == 1
        assert (completion[met] <= d_abs[met] + 1e-2).all()
