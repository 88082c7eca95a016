"""Fleet cells: ``repro.fleetsim`` over the seed's request streams.

A ``single`` mix calls ``repro.fleetsim.simulate`` on one stream; a
``sweep`` mix calls ``jax.vmap(repro.fleetsim.simulate_fn(...))`` once
over every (stream, SLA scale) point.  Both run the program's default
scan bound (``R * (max_forwards + 1)`` steps) and event buffer.  Each
call's per-request results are kept on the device and compared with the
plain reference once the window has closed.
"""
from __future__ import annotations

import importlib
import multiprocessing
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen

FIELDS = ("outcome", "served_by", "completion", "transfer_used")


def topology(cfg: dict):
    """Full-mesh ``TopologyArrays`` and ``NetParams`` of the configuration,
    as numpy arrays."""
    from repro.fleetsim import TopologyArrays
    from repro.netsim import NetParams
    K = cfg["nodes"]
    adj = ~np.eye(K, dtype=bool)
    nbrs = np.array([[j for j in range(K) if j != i] or [i] for i in range(K)],
                    np.int32)
    topo = TopologyArrays(adj=adj, neighbors=nbrs,
                          degree=adj.sum(1).astype(np.int32),
                          speeds=np.full(K, cfg["speed"], np.float32))
    lat, ibw = gen.link_matrices(cfg)
    return topo, NetParams(latency=lat, inv_bw=ibw)


def _request_arrays(streams: List[dict]):
    """Stack streams into one ``RequestArrays`` (leading point axis when
    there is more than one)."""
    from repro.fleetsim import RequestArrays
    pick = (lambda k: streams[0][k]) if len(streams) == 1 else \
        (lambda k: np.stack([s[k] for s in streams]))
    origin = pick("origin")
    return RequestArrays(arrival=pick("arrival"), proc=pick("proc"),
                         rel_deadline=pick("rel_deadline"), origin=origin,
                         service=np.zeros_like(origin), payload=pick("payload"))


def reference(cfg: dict, points: List[dict], dtype_name: str,
              workers: int) -> List[Dict[str, np.ndarray]]:
    """The plain reference over every point, in ``workers`` spawned
    processes (which import no JAX) when there is more than one point."""
    ref = importlib.import_module(f"bench.reference.{cfg['reference']}")
    jobs = [(cfg, p, dtype_name) for p in points]
    if len(jobs) == 1 or workers <= 1:
        return [ref.run_point(j) for j in jobs]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(workers, len(jobs))) as pool:
        out = pool.map(ref.run_point, jobs, chunksize=1)
    pool.join()
    return out


def compare(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> dict:
    """One point's readings: requests whose outcome or serving node
    differs, and the widest completion and wire-time gaps (UT)."""
    bad = (got["outcome"] != ref["outcome"]) | \
        (got["served_by"] != ref["served_by"])
    return dict(
        mismatched_requests=int(bad.sum()),
        completion_gap_ut=float(np.max(np.abs(
            got["completion"].astype(np.float64) - ref["completion"]),
            initial=0.0)),
        transfer_gap_ut=float(np.max(np.abs(
            got["transfer_used"].astype(np.float64) - ref["transfer"]),
            initial=0.0)))


class Cell:
    """Set-up builds the inputs and the compiled call and warms it; the
    harness then calls :meth:`call` back to back."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, trace: bool):
        from repro.fleetsim import SimParams, simulate, simulate_fn
        self.cfg, self.traffic = cfg, traffic
        if trace and "trace_mix_divisor" in traffic:
            # a traced call keeps every op event of every scan step, so the
            # traced run simulates a shorter stream of the same fleet
            cfg = dict(cfg, mix_divisor=traffic["trace_mix_divisor"],
                       window_ut=cfg["window_ut"] * cfg["mix_divisor"]
                       / traffic["trace_mix_divisor"])
            self.cfg = cfg
        self.points = gen.fleet_points(cfg, traffic, seed)
        for p in self.points:
            p["policy"] = traffic["policy"]
        topo, net = topology(cfg)
        reqs = _request_arrays([p["stream"] for p in self.points])
        self.requests_per_call = int(reqs.arrival.size)
        put = lambda tree: jax.tree.map(jnp.asarray, tree)
        reqs, topo, net = put(reqs), put(topo), put(net)
        kw = dict(policy=traffic["policy"], max_forwards=cfg["max_forwards"],
                  discard_on_exhaust=cfg["discard_on_exhaust"],
                  capacity=cfg["capacity"], depth=cfg["depth"])
        if len(self.points) == 1:
            params = SimParams.make(0, self.points[0]["sla_scale"])
            self._run = lambda: simulate(reqs, topo, params, net=net, **kw)
        else:
            R = reqs.arrival.shape[1]
            sweep = jax.jit(jax.vmap(
                simulate_fn(network=True, **kw),
                in_axes=(0, None, SimParams(0, 0), None, None)))
            params = SimParams(
                seed=jnp.zeros(len(self.points), jnp.int32),
                sla_scale=jnp.asarray([p["sla_scale"] for p in self.points],
                                      jnp.float32))
            tgt = jnp.full((R, max(cfg["max_forwards"], 1)), -1, jnp.int32)
            self._run = lambda: sweep(reqs, topo, params, tgt, net)
        self.topo, self.net = topo, net
        self.ahead = int(traffic.get("dispatch_ahead", 0))
        self.wait(self.dispatch())       # compile (or load) and warm up

    def dispatch(self):
        """Send one call to the chip; returns its outputs unawaited."""
        m = self._run()
        return tuple(getattr(m, f) for f in FIELDS) + (m.forwards,)

    @staticmethod
    def wait(out):
        return jax.block_until_ready(out)

    def units(self, outs) -> Dict[str, float]:
        """What the window's calls simulated."""
        fwd = sum(int(np.sum(np.asarray(o[-1]))) for o in outs)
        return dict(attempted=self.requests_per_call * len(outs),
                    events=self.requests_per_call * len(outs) + fwd,
                    calls=len(outs))

    def end_to_end(self, outs, elapsed: float) -> Dict[str, float]:
        return dict(sim_req_per_s=self.requests_per_call * len(outs) / elapsed)

    def compulsory_bytes(self) -> int:
        """Bytes any correct implementation moves once per call: the five
        per-request inputs and five per-request outputs (4 bytes each),
        the topology and the (K, K) network tensors."""
        from bench import work
        return work.scan_bytes(self.requests_per_call, self.topo, self.net)

    def release(self):
        self._run = None
        self.topo = self.net = None

    def check(self, outs, workers: int = 1) -> Dict[str, float]:
        """Every request of every call and point against the reference."""
        ref = reference(self.cfg, self.points, self.cfg["dtype"], workers)
        return readings(outs, ref, len(self.points))

    @staticmethod
    def failed(r: Dict[str, float]) -> int:
        return int(r["mismatched_requests"])


def readings(outs, ref: List[Dict[str, np.ndarray]], n_points: int) -> dict:
    """Compare each call's outputs (host arrays, point axis first when
    ``n_points > 1``) with the per-point reference.

    A request the program lost to an undersized ledger or event buffer
    ends as ``OVERFLOW`` or ``PENDING``, outcomes the reference never
    gives, so it counts among ``mismatched_requests``."""
    total = dict(mismatched_requests=0, completion_gap_ut=0.0,
                 transfer_gap_ut=0.0)
    for o in outs:
        o = [np.asarray(a) for a in o]
        for i in range(n_points):
            pick = (lambda a: a) if n_points == 1 else (lambda a: a[i])
            got = {f: pick(a) for f, a in zip(FIELDS, o[:4])}
            r = compare(got, ref[i])
            total["mismatched_requests"] += r["mismatched_requests"]
            for k in ("completion_gap_ut", "transfer_gap_ut"):
                total[k] = max(total[k], r[k])
    return total
