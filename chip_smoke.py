#!/usr/bin/env python3
"""Bring-up check: the main paths, once each, on one TPU chip.

    python3 chip_smoke.py                # one chip: phases (a), (b), (c)
    python3 chip_smoke.py --four-chips   # four chips: the replica phase only

(a) fleetsim at a real fleet size: the 256-node benchmark fleet
    (128,000 requests, capacity 1024, depth 512) under ``random``,
    ``least_loaded`` and ``batched_feasible``, one 8-seed vmapped sweep,
    and ``batched_feasible`` again through the compiled Pallas
    ``event_select`` kernel, which must agree request for request.
(b) exactness on the chip: the ``fleetsim/validate.py`` contract on the
    three paper scenarios under campus pricing with telemetry.
(c) serving: deit-b from the registry at its published widths through
    ``repro.launch.serve.serve`` on three replicas.

``--four-chips`` serves deit-b on four replicas, each on its own device,
and compares every answer and the engine's counters with the same
requests served with all four replicas on device 0.

Every phase prints its wall time and the backend compile time inside it;
these are smoke timings, not benchmark numbers.  The script runs in one
process, falls back to nothing (no accelerator: exit 1), and prints as
its last line ``{"ok": true, "device": {...}}`` only when every check
passed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

SCENARIOS = ("paper/scenario1", "paper/scenario2", "paper/scenario3")


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


class CompileClock:
    """Backend compile seconds, summed from JAX's monitoring events (pass
    to ``jax.monitoring.register_event_duration_secs_listener``)."""

    def __init__(self):
        self.total = 0.0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += secs


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    """Logs a phase's wall seconds and the compile seconds inside it."""
    t0, c0 = time.perf_counter(), clock.total
    yield
    log(f"phase {name}: wall {time.perf_counter() - t0:.3f}s, compile "
        f"{clock.total - c0:.3f}s (smoke timing)")


def _counters_zero(m, what: str) -> None:
    import numpy as np
    for fld in ("overflow", "window_saturation", "event_overflow"):
        v = int(np.max(np.asarray(getattr(m, fld))))
        check(v == 0, f"{what}: {fld} = {v}, must be 0")


def fleet_phase(n_nodes: int = 256, div: int = 4, capacity: int = 1024,
                depth: int = 512, sweep_seeds: int = 8,
                kernel_marker: str = "tpu_custom_call") -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.fleetsim_bench import make_fleet_workload
    from repro.fleetsim import (RequestArrays, SimParams, TopologyArrays,
                                simulate, simulate_fn, topology_arrays)
    from repro.orchestration import Topology

    wl = make_fleet_workload(n_nodes, div)
    reqs, _ = wl.to_arrays(0)
    reqs = RequestArrays(*(None if a is None else jnp.asarray(a)
                           for a in reqs))
    ta = TopologyArrays(*(jnp.asarray(a) for a in
                          topology_arrays(Topology.full_mesh(n_nodes))))
    R = int(reqs.arrival.shape[0])
    tgt = jnp.full((R, 2), -1, jnp.int32)
    kw = dict(capacity=capacity, depth=depth)
    log(f"fleet: {n_nodes} nodes, {R} requests, capacity {capacity}, "
        f"depth {depth}")

    runs = {}
    for policy in ("random", "least_loaded", "batched_feasible"):
        t0 = time.perf_counter()
        m = jax.block_until_ready(
            simulate(reqs, ta, SimParams.make(0), policy=policy, **kw))
        _counters_zero(m, policy)
        runs[policy] = m
        log(f"fleet {policy}: met {int(m.met_deadline)}/{R}, forwards "
            f"{int(m.forwards)}, wall {time.perf_counter() - t0:.3f}s "
            f"incl. compile")

    # the kernel path: compile it ahead, prove the executable holds the
    # Mosaic kernel, and run that very executable
    run = simulate_fn(policy="batched_feasible", use_pallas=True, **kw)
    args = (reqs, ta, SimParams.make(0), tgt)
    t0 = time.perf_counter()
    compiled = jax.jit(run).lower(*args).compile()
    log(f"fleet batched_feasible[pallas]: compiled in "
        f"{time.perf_counter() - t0:.3f}s")
    check(kernel_marker in compiled.as_text(),
          f"the use_pallas executable holds no {kernel_marker}")
    t0 = time.perf_counter()
    mp = jax.block_until_ready(compiled(*args))
    _counters_zero(mp, "batched_feasible[pallas]")
    ref = runs["batched_feasible"]
    same = {fld: bool(np.array_equal(np.asarray(getattr(mp, fld)),
                                     np.asarray(getattr(ref, fld))))
            for fld in ("outcome", "served_by", "completion",
                        "transfer_used")}
    log(f"fleet batched_feasible[pallas]: wall "
        f"{time.perf_counter() - t0:.3f}s; kernel == jnp reference per "
        f"request: {same}")
    check(all(same.values()), f"pallas vs jnp per-request outputs: {same}")

    sweep = jax.vmap(simulate_fn(policy="random", **kw),
                     in_axes=(None, None, SimParams(0, 0), None))
    params = SimParams(jnp.arange(sweep_seeds, dtype=jnp.int32),
                       jnp.ones((sweep_seeds,), jnp.float32))
    t0 = time.perf_counter()
    ms = jax.block_until_ready(sweep(reqs, ta, params, tgt))
    _counters_zero(ms, f"sweep[{sweep_seeds} seeds]")
    log(f"fleet sweep[{sweep_seeds} seeds]: met "
        f"{np.asarray(ms.met_deadline).tolist()}, wall "
        f"{time.perf_counter() - t0:.3f}s incl. compile")


def validate_phase(seeds: int = 1, buckets: int = 32) -> None:
    from repro.fleetsim.validate import contract_violations, run_validation
    from repro.netsim import LinkModel
    from repro.orchestration import Topology, get_workload

    reports = []
    for sc in SCENARIOS:
        topo = Topology.full_mesh(get_workload(sc).n_nodes)
        net = LinkModel.preset(topo, "campus")
        for seed in range(seeds):
            rep = run_validation(sc, seed, network=net, telemetry=buckets)
            reports.append(rep)
            log(f"validate {rep.row()}")
    log(f"validate: {sum(r.exact for r in reports)}/{len(reports)} cells "
        f"exact (net=campus, telemetry={buckets})")
    bad = contract_violations(reports)
    check(not bad, "validate contract violated: "
          + "; ".join(r.row() for r in bad))


def serve_phase(full: bool = True, replicas: int = 3,
                requests: int = 36) -> None:
    from repro.launch.serve import serve
    rep = serve("deit-b", full=full, replicas=replicas, requests=requests)
    log(f"serve deit-b (full={full}): {rep.answered}/{requests} answered, "
        f"{rep.met_pct:.1f}% met, stats {rep.stats}, devices "
        f"{sorted(set(rep.devices))}")
    check(rep.answered == requests,
          f"{requests - rep.answered} requests got no result")


def four_chip_phase(full: bool = True, n: int = 4,
                    requests: int = 48) -> None:
    import jax
    from repro.launch.serve import serve
    devs = jax.devices()
    check(len(devs) >= n, f"--four-chips needs {n} devices, found "
          f"{len(devs)}")
    kw = dict(full=full, replicas=n, requests=requests)
    spread = serve("deit-b", devices=devs[:n], **kw)
    one = serve("deit-b", devices=[devs[0]], **kw)
    log(f"serve x{n} on {spread.devices}: {spread.answered}/{requests} "
        f"answered, stats {spread.stats}")
    log(f"serve x{n} on {one.devices[0]} only: {one.answered}/{requests} "
        f"answered, stats {one.stats}")
    same = sum(a == b for a, b in zip(spread.results, one.results))
    log(f"serve x{n}: {same}/{requests} per-request results identical, "
        f"stats identical: {spread.stats == one.stats}")
    check(len(set(spread.devices)) == n, "replicas did not get their own "
          "devices")
    check(spread.answered == requests, "a request got no result")
    check(same == requests and spread.stats == one.stats,
          "one-device and per-device serving disagree")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica, 4-device serving phase "
                         "and its one-device comparison")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r}")
    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}, compile cache {cache}")

    t0 = time.perf_counter()
    if args.four_chips:
        with phase("four_chip_serving", clock):
            four_chip_phase()
    else:
        with phase("a_fleetsim", clock):
            fleet_phase()
        with phase("b_validate", clock):
            validate_phase()
        with phase("c_serving", clock):
            serve_phase()
    log(f"total: wall {time.perf_counter() - t0:.3f}s, compile "
        f"{clock.total:.3f}s (smoke timing)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
