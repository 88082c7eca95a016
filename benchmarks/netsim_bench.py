"""netsim throughput + fidelity: the network axis as a device-resident
sweep, vs the event-heap path paying per-hop Python costs.

Cells:

* ``grid`` — the fleetsim-only dimension this PR opens: a full
  **latency × bandwidth × sla_scale** grid, with the network itself a
  vmap axis (stacked :class:`repro.netsim.NetParams`) nested over the
  ``SimParams`` sla axis — every cell of the cube is computed in ONE
  device call.  Reported as sweep cells/sec and aggregate requests/sec.
* ``host`` — honest CPU ratios: the event-heap ``Orchestrator`` runs the
  same campus-priced workload (it pays a ``transfer_delay`` lookup and a
  later heap event per forward), fleetsim runs it device-resident.
  **Cold and warm are separate rows**: the first device call includes
  JIT compilation and must not pollute the throughput number, so the
  timed row is a warm second call of the same compiled executable and
  the cold (compile + run) time is recorded next to it.  On a CPU
  backend the Python heap is fast — the recorded ratio is honest about
  that, as with BENCH_fleetsim.json; the grid rows are where the device
  wins (the host cannot amortize a 27-cell cube at all).
* ``fidelity`` — met-rate delta between the two engines under the campus
  network, measured by forwarding-trace replay so rng streams are
  factored out.  The event-time scan (DESIGN.md §7) replays the heap's
  priced event interleaving exactly, so the delta is asserted to be
  **zero** — this row regression-guards the exactness, it no longer
  measures an approximation.

Run:  PYTHONPATH=src python benchmarks/netsim_bench.py [--smoke]
      (default writes BENCH_netsim.json next to the repo root)
"""
from __future__ import annotations

import argparse
import json
import os
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.block_queue import FastPreferentialQueue
from repro.fleetsim import (NetParams, RequestArrays, SimParams, simulate,
                            simulate_fn, topology_arrays)
from repro.fleetsim.validate import run_validation
from repro.netsim import LinkModel
from repro.orchestration import Orchestrator, Router, Topology
try:                                     # `python -m benchmarks.run`
    from benchmarks._timing import cold_warm, timed
    from benchmarks.fleetsim_bench import make_fleet_workload
except ImportError:                      # `python benchmarks/netsim_bench.py`
    from _timing import cold_warm, timed
    from fleetsim_bench import make_fleet_workload

JSON_DEFAULT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_netsim.json")


def bench_grid(wl, topology: Topology, lams, inv_bws, slas,
               capacity: int, depth: int) -> Tuple[float, float, dict]:
    """The (latency × bandwidth × sla) cube as ONE device call (warm;
    the cold compile+run time rides along in the info dict)."""
    K = topology.n_nodes
    ta = topology_arrays(topology)
    reqs, _ = wl.to_arrays(0)
    reqs = RequestArrays(*(jnp.asarray(a) for a in reqs))
    ta = type(ta)(*(jnp.asarray(a) for a in ta))
    R = reqs.arrival.shape[0]
    tgt = jnp.full((R, 2), -1, jnp.int32)

    nets = [NetParams.uniform(K, lam, ibw) for lam in lams for ibw in inv_bws]
    stacked = NetParams(
        latency=jnp.stack([n.latency for n in nets]),
        inv_bw=jnp.stack([n.inv_bw for n in nets]))
    params = SimParams(seed=jnp.zeros((len(slas),), jnp.int32),
                       sla_scale=jnp.asarray(slas, jnp.float32))

    # the cube's heaviest cells forward freely, so the event plane keeps
    # the exact worst-case bound (undersizing would surface in
    # event_overflow, asserted 0 below)
    run = simulate_fn(policy="least_loaded", capacity=capacity, depth=depth,
                      network=True)
    # inner axis: sla (SimParams), outer axis: the network itself
    cube = jax.vmap(jax.vmap(run, in_axes=(None, None, 0, None, None)),
                    in_axes=(None, None, None, None, 0))
    cw = cold_warm(lambda: cube(reqs, ta, params, tgt, stacked))
    cold_dt, dt, m = cw.cold_s, cw.warm_s, cw.result
    n_cells = len(nets) * len(slas)
    met = np.asarray(m.met_deadline)            # (nets, slas)
    info = dict(
        cells=n_cells, requests_per_cell=int(R),
        cold_s=round(cold_dt, 3), warm_s=round(dt, 3),
        met_grid=met.reshape(len(lams), len(inv_bws), len(slas)).tolist(),
        # the free-network, sla=1 corner for eyeballing the tax
        met_free=int(met[0, list(slas).index(1.0)])
        if 1.0 in slas and lams[0] == 0.0 and inv_bws[0] == 0.0 else None,
    )
    assert int(np.asarray(m.overflow).max()) == 0
    assert int(np.asarray(m.event_overflow).max()) == 0
    return n_cells / dt, n_cells * R / dt, info


def bench_host_vs_fleet(wl, topology: Topology, link: LinkModel,
                        capacity: int, depth: int, seed: int = 0):
    """Honest CPU comparison under the campus network + exact fidelity.

    Timing rows run both engines natively (least_loaded); the fleet
    number is the warm second call of one compiled executable, with the
    cold compile+run time recorded separately.  The fidelity number
    replays the host's forwarding trace (run_validation), so it compares
    dynamics — admission, timing, priced event ordering — with the rng
    stream factored out; the event-time scan makes it exactly 0.
    """
    requests = wl.generate(seed)
    orch = Orchestrator(topology, FastPreferentialQueue,
                        Router(topology, "least_loaded", seed=seed),
                        network=link)
    host_dt, host = timed(lambda: orch.run(requests))

    ta = topology_arrays(topology)
    reqs, _ = wl.to_arrays(seed, payload_fn=link.payload_of)
    net = link.net_params()
    R = len(requests)
    kw = dict(policy="least_loaded", capacity=capacity, depth=depth,
              net=net)
    # same seed both calls: the comparison must replay the same workload
    # cell, and the second call reuses the compiled executable
    cw = cold_warm(lambda: simulate(reqs, ta, SimParams.make(seed), **kw))
    cold_dt, warm_dt, m = cw.cold_s, cw.warm_s, cw.result
    assert int(m.overflow) == 0 and int(m.event_overflow) == 0

    # exact-fidelity regression guard: trace replay of the same cell
    rep = run_validation(wl, seed, policy="least_loaded",
                         topology=topology, network=link)
    assert rep.exact, \
        f"event-time scan must replay the priced heap exactly: {rep.row()}"
    assert rep.met_diff_pp == 0.0, rep.row()

    return (R / host_dt, R / cold_dt, R / warm_dt,
            dict(host_met_rate=round(host.met_deadline / R, 4),
                 fleet_met_rate=round(float(m.met_rate), 4),
                 fidelity_delta_pp=rep.met_diff_pp,
                 fidelity_outcome_mismatches=rep.outcome_mismatches,
                 fidelity_node_mismatches=rep.node_mismatches,
                 host_transfer_time=round(host.transfer_time, 1),
                 host_forwards=host.forwards, fleet_forwards=int(m.forwards),
                 scan_steps=int(m.scan_steps)))


def run(smoke: bool = False,
        json_path: Optional[str] = None) -> List[Tuple[str, float, str]]:
    rows: List[Tuple[str, float, str]] = []
    record = []
    div = 40 if smoke else 8
    K = 3 if smoke else 16
    cap = 256 if smoke else 1024
    dep = 128 if smoke else 512
    wl = make_fleet_workload(K, div)
    topo = Topology.full_mesh(K)
    link = LinkModel.campus(topo)

    # -- the cube: latency × bandwidth × sla as one device call ------------
    lams = (0.0, 5.0) if smoke else (0.0, 5.0, 30.0)
    inv_bws = (0.0, 0.8) if smoke else (0.0, 0.8, 3.2)   # UT per MB
    slas = (0.5, 1.0) if smoke else (0.5, 1.0, 2.0)
    cells_ps, agg_rps, info = bench_grid(wl, topo, lams, inv_bws, slas,
                                         cap, dep)
    rows.append((f"netsim_{K}n_grid{info['cells']}", 1e6 / agg_rps,
                 f"{cells_ps:.2f} cells/s, {agg_rps:,.0f} req/s aggregate "
                 f"({info['cells']} (lat x bw x sla) cells, one device "
                 f"call; cold {info['cold_s']}s, warm {info['warm_s']}s)"))
    record.append(dict(nodes=K, kind="grid", cells=info["cells"],
                       lams=list(lams), inv_bws=list(inv_bws),
                       slas=list(slas),
                       cells_per_s=round(cells_ps, 3),
                       aggregate_rps=round(agg_rps),
                       cold_s=info["cold_s"], warm_s=info["warm_s"],
                       met_grid=info["met_grid"]))

    # -- honest host-vs-fleet single cell under the campus network ---------
    host_rps, cold_rps, warm_rps, fid = bench_host_vs_fleet(
        wl, topo, link, cap, dep)
    ratio = warm_rps / host_rps
    rows.append((f"netsim_{K}n_campus_cold", 1e6 / cold_rps,
                 f"{cold_rps:,.0f} req/s first call (JIT compile folded in "
                 f"— reported separately, not the throughput row)"))
    rows.append((f"netsim_{K}n_campus_warm", 1e6 / warm_rps,
                 f"{warm_rps:,.0f} req/s fleetsim vs {host_rps:,.0f} "
                 f"python = {ratio:.2f}x; fidelity "
                 f"{fid['fidelity_delta_pp']}pp (exact, asserted)"))
    record.append(dict(nodes=K, kind="host_vs_fleet",
                       python_rps=round(host_rps),
                       fleetsim_cold_rps=round(cold_rps),
                       fleetsim_warm_rps=round(warm_rps),
                       ratio_warm=round(ratio, 3), **fid))

    if json_path:
        payload = dict(
            backend=jax.default_backend(), jax=jax.__version__,
            regime=(f"scenario-1 per-node mix / {div}, {K} nodes full mesh, "
                    f"campus link profile (lat 5 UT, 1.25 MB/UT), "
                    f"least_loaded"),
            rows=record,
            notes=("grid rows: the network is a vmap axis (stacked "
                   "NetParams) — a latency x bandwidth x sla cube in one "
                   "device call, which the Python heap cannot amortize "
                   "at all.  host_vs_fleet: single-cell honest CPU "
                   "ratio; cold (compile + run) and warm (second call) "
                   "are separate rows so JIT warm-up never pollutes the "
                   "throughput number.  fidelity_delta_pp compares "
                   "trace-replayed dynamics under campus pricing and is "
                   "asserted exactly 0: the event-time scan (DESIGN.md "
                   "§7) replays the priced heap event for event — this "
                   "row guards the contract, it no longer measures an "
                   "approximation."),
        )
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid, CI-friendly runtime")
    ap.add_argument("--json", default=None,
                    help=f"write the JSON baseline (default {JSON_DEFAULT} "
                         f"unless --smoke)")
    args = ap.parse_args()
    json_path = args.json or (None if args.smoke else JSON_DEFAULT)
    for name, us, derived in run(args.smoke, json_path):
        print(f"{name},{us:.2f},{derived}", flush=True)


if __name__ == "__main__":
    main()
