"""Fleet-simulator throughput vs the Python event-heap orchestrator.

Both engines replay the same strategy on the same workload (scenario-1
per-node service mix replicated over the fleet, arrival window scaled to
keep the paper's ~2x overload per node), so requests/sec is apples to
apples per (fleet size, policy) cell.  Cells:

* ``random`` / ``least_loaded`` — the host engine's fast path (CPython
  heapq + C-speed list ops); fleetsim pays the device's fixed per-step op
  cost, so on a CPU backend it trails these (see BENCH_fleetsim.json for
  the recorded ratios and EXPERIMENTS.md §Fleetsim for the analysis);
* ``batched_feasible`` — the cross-node admission-scoring policy (the
  fleet-feasibility kernel's workload): the host router must round-trip to
  the device per forwarding decision, fleetsim keeps everything resident —
  this is where the >= 10x target at 32+ nodes is measured;
* ``sweep`` — the fleetsim-only dimension: a vmapped (seeds) batch as ONE
  device call, reported as sweep cells/sec and aggregate requests/sec.

Run:  PYTHONPATH=src python benchmarks/fleetsim_bench.py [--smoke] [--full]
      (--full adds the very slow python batched_feasible @ 256 cell;
       default writes BENCH_fleetsim.json next to the repo root)
"""
from __future__ import annotations

import argparse
import json
import os
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

try:                                     # `python -m benchmarks.run`
    from benchmarks._timing import cold_warm, timed
except ImportError:                      # `python benchmarks/fleetsim_bench.py`
    from _timing import cold_warm, timed

from repro.core.block_queue import FastPreferentialQueue
from repro.core.scenarios import SCENARIOS
from repro.fleetsim import (RequestArrays, SimParams, simulate, simulate_fn,
                            topology_arrays)
from repro.orchestration import (Orchestrator, Router, Topology,
                                 UniformWorkload)

JSON_DEFAULT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_fleetsim.json")


def make_fleet_workload(n_nodes: int, div: int = 4) -> UniformWorkload:
    """Scenario-1 node mixes tiled over the fleet; window scaled by ``div``
    so every node sees the paper's overload intensity with 1/div volume."""
    counts = [{s: max(1, c // div) for s, c in SCENARIOS[1][i % 3].items()}
              for i in range(n_nodes)]
    return UniformWorkload(counts, window=110_000.0 / div,
                           name=f"fleet{n_nodes}_div{div}")


def bench_python(wl: UniformWorkload, topology: Topology, policy: str,
                 seed: int = 0) -> Tuple[float, dict]:
    requests = wl.generate(seed)
    orch = Orchestrator(topology, FastPreferentialQueue,
                        Router(topology, policy, seed=seed))
    dt, res = timed(lambda: orch.run(requests))
    return len(requests) / dt, dict(met_rate=res.met_rate,
                                    forwards=res.forwards)


def bench_fleetsim(wl: UniformWorkload, topology: Topology, policy: str,
                   capacity: int, depth: int,
                   use_pallas: bool = False) -> Tuple[float, dict]:
    """Steady-state requests/sec (warm call: same trace cache, new seed).

    ``event_overflow`` is asserted 0, so an undersized event buffer cannot
    silently clip the run."""
    ta = topology_arrays(topology)
    reqs, _ = wl.to_arrays(0)
    R = reqs.arrival.shape[0]
    kw = dict(policy=policy, capacity=capacity, depth=depth,
              use_pallas=use_pallas)
    # cold call on seed 0 (compile + run), warm measurement on seed 1 —
    # same compiled executable, fresh forwarding stream
    cw = cold_warm(lambda: simulate(reqs, ta, SimParams.make(0), **kw),
                   lambda: simulate(reqs, ta, SimParams.make(1), **kw))
    m = cw.result
    assert int(m.overflow) == 0 and int(m.window_saturation) == 0, \
        f"capacity {capacity}/depth {depth} saturated"
    assert int(m.event_overflow) == 0, "event plane saturated"
    return R / cw.warm_s, dict(met_rate=float(m.met_rate),
                               forwards=int(m.forwards),
                               cold_rps=round(R / cw.cold_s))


def bench_sweep(wl: UniformWorkload, topology: Topology, n_seeds: int,
                capacity: int, depth: int) -> Tuple[float, float, int]:
    """One vmapped device call over ``n_seeds`` forwarding streams.

    Returns (sweep cells/sec, aggregate requests/sec, total requests).
    """
    ta = topology_arrays(topology)
    reqs, _ = wl.to_arrays(0)
    reqs = RequestArrays(*(jnp.asarray(a) for a in reqs))
    ta = type(ta)(*(jnp.asarray(a) for a in ta))
    R = reqs.arrival.shape[0]
    tgt = jnp.full((R, 2), -1, jnp.int32)
    run = simulate_fn(policy="random", capacity=capacity, depth=depth)
    sweep = jax.vmap(run, in_axes=(None, None, SimParams(0, 0), None))

    def params(lo):
        return SimParams(jnp.arange(lo, lo + n_seeds, dtype=jnp.int32),
                         jnp.full((n_seeds,), 1.0, jnp.float32))

    # cold on seeds [0, n), warm on fresh seeds [n, 2n) — same executable
    cw = cold_warm(lambda: sweep(reqs, ta, params(0), tgt),
                   lambda: sweep(reqs, ta, params(n_seeds), tgt))
    m, dt = cw.result, cw.warm_s
    # the sweep keeps the exact worst-case event bound (per-seed forward
    # counts differ; undersizing would surface here, never silently)
    assert int(jnp.max(m.event_overflow)) == 0
    return n_seeds / dt, n_seeds * R / dt, n_seeds * R


def run(smoke: bool = False, full: bool = False,
        json_path: Optional[str] = None) -> List[Tuple[str, float, str]]:
    rows: List[Tuple[str, float, str]] = []
    record = []
    div = 40 if smoke else 4
    sizes = (3, 32) if smoke else (3, 32, 256)
    # per-cell (python?, fleetsim?) — python batched_feasible is O(device
    # round-trip per forward): minutes at 32 nodes, ~hours at 256
    policies = {
        3: ["random", "least_loaded"],
        32: ["random", "least_loaded", "batched_feasible"],
        256: ["random", "least_loaded", "batched_feasible"],
    }
    for K in sizes:
        wl = make_fleet_workload(K, div)
        topo = Topology.full_mesh(K)
        cap = 256 if smoke else (4096 if K == 3 else 1024)
        dep = 128 if smoke else (1024 if K == 3 else 512)
        for policy in policies[K]:
            skip_py = policy == "batched_feasible" and (
                smoke or (K >= 256 and not full))
            py_rps = None
            if not skip_py:
                py_rps, _ = bench_python(wl, topo, policy)
            # exercise the Pallas kernel (interpret off-TPU) in the smoke
            # cell so CI covers it; the measured cells use the jnp reference
            use_pallas = smoke and policy == "batched_feasible"
            fs_rps, fs_info = bench_fleetsim(wl, topo, policy, cap, dep,
                                             use_pallas=use_pallas)
            ratio = (fs_rps / py_rps) if py_rps else float("nan")
            tag = f"{fs_rps:,.0f} req/s fleetsim"
            if py_rps:
                tag += f" vs {py_rps:,.0f} python = {ratio:.2f}x"
            rows.append((f"fleetsim_{K}n_{policy}", 1e6 / fs_rps, tag))
            record.append(dict(nodes=K, policy=policy,
                               python_rps=py_rps and round(py_rps),
                               fleetsim_rps=round(fs_rps),
                               fleetsim_cold_rps=fs_info["cold_rps"],
                               ratio=py_rps and round(ratio, 3),
                               met_rate=round(fs_info["met_rate"], 4),
                               forwards=fs_info["forwards"]))
        # one vmapped sweep cell per fleet size
        n_seeds = 2 if smoke else 8
        cells_ps, agg_rps, n_req = bench_sweep(wl, topo, n_seeds, cap, dep)
        rows.append((f"fleetsim_{K}n_sweep{n_seeds}", 1e6 / agg_rps,
                     f"{cells_ps:.2f} cells/s, {agg_rps:,.0f} req/s "
                     f"aggregate ({n_req} req, one device call)"))
        record.append(dict(nodes=K, policy=f"sweep[{n_seeds} seeds]",
                           fleetsim_rps=round(agg_rps),
                           cells_per_s=round(cells_ps, 3)))
    if json_path:
        payload = dict(
            backend=jax.default_backend(), jax=jax.__version__,
            regime=(f"scenario-1 per-node mix / {div}, window "
                    f"{110_000.0 / div:.0f}, full mesh, ~{2000 // div} "
                    f"req/node, seeds 0-1"),
            rows=record,
            notes=("random/least_loaded: host engine is CPython heapq + "
                   "C-speed list ops and wins on a CPU backend (fixed "
                   "per-step op-dispatch cost dominates fleetsim there); "
                   "batched_feasible: cross-node admission scoring — the "
                   "host router round-trips to the device per forward, "
                   "fleetsim stays resident (the >= 10x cell at 32+ "
                   "nodes).  Sweep rows are one vmapped device call."),
        )
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fleets, CI-friendly runtime, Pallas "
                         "interpret path exercised")
    ap.add_argument("--full", action="store_true",
                    help="include python batched_feasible @ 256 nodes "
                         "(very slow)")
    ap.add_argument("--json", default=None,
                    help=f"write the JSON baseline (default "
                         f"{JSON_DEFAULT} unless --smoke)")
    args = ap.parse_args()
    json_path = args.json or (None if args.smoke else JSON_DEFAULT)
    for name, us, derived in run(args.smoke, args.full, json_path):
        print(f"{name},{us:.2f},{derived}", flush=True)


if __name__ == "__main__":
    main()
