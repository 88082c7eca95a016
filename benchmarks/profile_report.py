"""Per-phase wall-time attribution of the fleetsim event-time scan.

The scan's step is a fixed pipeline — event pop, referral scoring (link
cost), admission feasibility, the insert cascade, and the terminal
scatters (``fleetsim.*`` named scopes in ``fleetsim/core.py``).  This
report measures where a warm step actually spends its time:

* each phase runs standalone as a jitted ``lax.scan`` of M iterations
  over representative shapes (the same (K, W) ledger windows and (B,)
  event buffer a real step touches), with a scalar carry threading a
  data dependency through every iteration so XLA cannot dead-code or
  batch the work — the per-iteration time is the phase's amortized cost;
* one real warm :func:`repro.fleetsim.simulate` call (cold/warm split
  via ``benchmarks._timing``) gives the true end-to-end step time; the
  gap between it and the phase sum is reported as ``residual`` — glue
  ops, scan overhead, and fusion effects the standalone cells cannot
  see.  Attribution is a profile, not an identity: phases measured alone
  lose cross-phase fusion, so the residual can be negative.

Output: ``BENCH_profile.json`` (per-phase us/step + fraction of the
measured step) and the usual ``name,us_per_call,derived`` CSV rows.
``--trace`` additionally captures a ``jax.profiler`` trace of the warm
run (viewable at ui.perfetto.dev, like the host engine's
``TraceRecorder`` output — see EXPERIMENTS.md §Telemetry).

Run:  PYTHONPATH=src python benchmarks/profile_report.py [--smoke]
      [--trace DIR] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import os
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

try:                                     # `python -m benchmarks.run`
    from benchmarks._timing import cold_warm
    from benchmarks.fleetsim_bench import make_fleet_workload
except ImportError:                      # `python benchmarks/profile_report.py`
    from _timing import cold_warm
    from fleetsim_bench import make_fleet_workload

from repro.core import jax_queue as jq
from repro.fleetsim import SimParams, simulate, topology_arrays
from repro.kernels import ref as kref
from repro.orchestration import Topology

JSON_DEFAULT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_profile.json")

#: tiny coupling constant: folds each phase's outputs back into the scan
#: carry so no iteration is dead code, while perturbing inputs by an
#: amount that never changes control flow
_EPS = 1e-20


def _per_iter(fn, iters: int) -> float:
    """Amortized seconds per call of ``fn(x: f32 scalar) -> f32 scalar``,
    measured as a warm jitted ``lax.scan`` of ``iters`` iterations."""
    @jax.jit
    def run(x0):
        def body(x, _):
            return fn(x), None
        x, _ = jax.lax.scan(body, x0, None, length=iters)
        return x
    cw = cold_warm(lambda: run(jnp.float32(0.0)))
    return cw.warm_s / iters


def _tsum(*arrays) -> jnp.ndarray:
    return sum(jnp.sum(a.astype(jnp.float32)) for a in arrays)


def phase_cells(K: int, W: int, B: int, R: int):
    """The five measured phases over representative step shapes.

    Returns ``[(name, fn)]`` where each ``fn`` maps the f32 carry to a
    new carry through one execution of that phase's ops.
    """
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    f = jnp.float32
    starts = jnp.sort(jax.random.uniform(ks[0], (K, W), jnp.float32,
                                         0.0, 1e4), axis=1)
    ends = starts + jax.random.uniform(ks[1], (K, W), jnp.float32, 1.0, 50.0)
    sizes = ends - starts
    nq = jnp.full((K,), W // 2, jnp.int32)
    busy = jax.random.uniform(ks[2], (K,), jnp.float32, 0.0, 1e3)
    head = jnp.zeros((K,), jnp.int32)
    lat = jax.random.uniform(ks[3], (K, K), jnp.float32, 0.0, 10.0)
    ibw = jax.random.uniform(ks[4], (K, K), jnp.float32, 0.0, 1.0)
    ev_time = jnp.sort(jax.random.uniform(ks[5], (B,), jnp.float32,
                                          0.0, 1e4))
    ev_rid = jnp.arange(B, dtype=jnp.int32) % R
    ev_meta = jnp.arange(B, dtype=jnp.int32) % (K * 4)
    ev_n = jnp.int32(B // 2)
    sw, ew, zw = starts[0], ends[0], sizes[0]
    srw = jnp.arange(W, dtype=jnp.int32)

    def event_pop(x):
        # the two-way merge + buffer pop of fleetsim.event_pop
        t_a, t_b = f(5e3) + x, ev_time[0]
        take_fresh = t_a <= t_b
        t = jnp.where(take_fresh, t_a, t_b)
        et, (er, em), n = jq.event_pop(ev_time + x, (ev_rid, ev_meta),
                                       ev_n, ~take_fresh)
        return x + _EPS * (_tsum(et, er, em) + t + n)

    def link_cost(x):
        feas, arr, load = kref.link_cost_ref(
            starts + x, ends, sizes, nq, ends[:, 0] / f(2.0), f(8e3),
            busy, head, f(4e3), lat[0], ibw[0], f(1.5))
        return x + _EPS * _tsum(feas, arr, load)

    def feasibility(x):
        ok, j, cap, load = kref.fleet_search_ref(
            starts + x, ends, sizes, nq, ends[:, 0] / f(2.0), f(8e3),
            jnp.maximum(busy, f(4e3)), head)
        return x + _EPS * _tsum(ok, j, cap, load)

    def admission(x):
        ns, ne, nz, admitted, (nsr,) = jq.insert_at(
            sw + x, ew, zw, jnp.int32(0), nq[0], jnp.bool_(True),
            jnp.bool_(False), jnp.int32(W // 2), ew[W // 2], f(7.0),
            f(4e3), meta=(srw,), meta_vals=(jnp.int32(3),))
        return x + _EPS * (_tsum(ns, ne, nz, nsr) + admitted)

    completion = jnp.zeros((R,), jnp.float32)
    reqinfo = jnp.zeros((R,), jnp.int32)

    def scatter(x):
        # the terminal-record writes of fleetsim.scatter: the windowed
        # dynamic_update_slice plus the two (R,) mode="drop" scatters
        cur = jnp.int32(0)
        st = jax.lax.dynamic_update_slice(starts, (sw + x)[None, :],
                                          (cur, jnp.int32(0)))
        nqs = nq.at[cur].add(1)
        c = completion.at[jnp.int32(R // 2)].set(f(5e3) + x, mode="drop")
        ri = reqinfo.at[jnp.int32(R // 2)].set(jnp.int32(7), mode="drop")
        return x + _EPS * _tsum(st, nqs, c, ri)

    return [("event_pop", event_pop), ("link_cost", link_cost),
            ("feasibility", feasibility), ("admission", admission),
            ("scatter", scatter)]


def measure_total(K: int, div: int, capacity: int, depth: int,
                  trace_dir: Optional[str] = None):
    """Warm end-to-end step time of a real run (batched_feasible — the
    kernel-bearing policy the phases model)."""
    wl = make_fleet_workload(K, div)
    topo = Topology.full_mesh(K)
    ta = topology_arrays(topo)
    reqs, _ = wl.to_arrays(0)
    R = reqs.arrival.shape[0]
    probe = simulate(reqs, ta, SimParams.make(0), policy="batched_feasible",
                     capacity=capacity, depth=depth)
    max_events = min(3 * R, R + 4 * int(probe.forwards) + 256)
    kw = dict(policy="batched_feasible", capacity=capacity, depth=depth,
              max_events=max_events)
    cw = cold_warm(lambda: simulate(reqs, ta, SimParams.make(0), **kw),
                   lambda: simulate(reqs, ta, SimParams.make(1), **kw))
    assert int(cw.result.event_overflow) == 0
    if trace_dir is not None:
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready(
                simulate(reqs, ta, SimParams.make(1), **kw))
        print(f"# jax.profiler trace written under {trace_dir} "
              f"(load in ui.perfetto.dev)")
    return cw, max_events, R


def run(smoke: bool = False, json_path: Optional[str] = None,
        trace_dir: Optional[str] = None) -> List[Tuple[str, float, str]]:
    K = 8 if smoke else 32
    W = 64 if smoke else 512
    B = 256 if smoke else 1024
    div = 40 if smoke else 8
    capacity = 256 if smoke else 1024
    iters = 200 if smoke else 1000

    cw, steps, R = measure_total(K, div, capacity, W, trace_dir)
    step_us = cw.warm_s / steps * 1e6

    phases = []
    for name, fn in phase_cells(K, W, B, R):
        us = _per_iter(fn, iters) * 1e6
        phases.append((name, us))
    phase_sum = sum(us for _, us in phases)
    residual = step_us - phase_sum

    rows: List[Tuple[str, float, str]] = []
    rows.append((f"profile_{K}n_step_total", step_us,
                 f"{steps} steps, warm {cw.warm_s:.3f}s "
                 f"(cold {cw.cold_s:.3f}s), {R} req"))
    for name, us in phases:
        rows.append((f"profile_{K}n_{name}", us,
                     f"{100 * us / step_us:.1f}% of the measured step"))
    rows.append((f"profile_{K}n_residual", residual,
                 f"{100 * residual / step_us:.1f}% — scan glue + fusion "
                 f"effects standalone cells cannot see"))

    if json_path:
        payload = dict(
            backend=jax.default_backend(), jax=jax.__version__,
            regime=(f"{K} nodes full mesh, batched_feasible, depth {W}, "
                    f"event buffer {B}, scenario-1 mix / {div}"),
            step_us=round(step_us, 3),
            steps=steps,
            cold_s=round(cw.cold_s, 3), warm_s=round(cw.warm_s, 3),
            phases={name: dict(us_per_step=round(us, 3),
                               fraction=round(us / step_us, 4))
                    for name, us in phases},
            residual_us=round(residual, 3),
            residual_fraction=round(residual / step_us, 4),
            notes=("Phases are standalone jitted lax.scan microbenchmarks "
                   "over representative step shapes, amortized per "
                   "iteration; step_us is a real warm batched_feasible "
                   "run divided by its scan length.  The residual is the "
                   "un-attributed remainder (scan glue, fusion) — "
                   "attribution is a profile, not an identity."),
        )
        with open(json_path, "w") as fjs:
            json.dump(payload, fjs, indent=1)
            fjs.write("\n")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes, CI-friendly runtime")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="also capture a jax.profiler trace of the warm "
                         "run under DIR (Perfetto-viewable)")
    ap.add_argument("--json", default=None,
                    help=f"write the JSON report (default {JSON_DEFAULT} "
                         f"unless --smoke)")
    args = ap.parse_args()
    json_path = args.json or (None if args.smoke else JSON_DEFAULT)
    for name, us, derived in run(args.smoke, json_path, args.trace):
        print(f"{name},{us:.2f},{derived}", flush=True)


if __name__ == "__main__":
    main()
