"""The one traffic generator: every cell's inputs, made from ``--seed``.

It reads a configuration (the deployment: nodes, services, link prices,
per-node request mixes) and a traffic mix (the calls the window makes)
and nothing else, so a new mix is a new data file.  The fleet streams
follow the paper's arrival process (Boing et al. 2022, arXiv 2212.03802,
Tables I-II): fixed per-(node, service) counts, arrival times i.i.d.
uniform over the window, sorted by arrival.  The counts are the same for
every seed, so a seed changes when requests arrive, not how many.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

SERVICE_ORDER = ("S1", "S2", "S3", "S4", "S5", "S6")


def node_counts(cfg: dict) -> List[Dict[str, int]]:
    """Per-node request counts: Table II mixes tiled over the nodes (node
    ``i`` takes mix ``i mod len(mixes)``), each count divided by the
    configuration's ``mix_divisor`` and kept at least 1."""
    mixes, div = cfg["node_mixes"], cfg["mix_divisor"]
    return [{s: max(1, int(c) // div) for s, c in mixes[i % len(mixes)].items()}
            for i in range(cfg["nodes"])]


def fleet_stream(cfg: dict, rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """One request stream in arrival order: ``arrival``, ``proc``,
    ``rel_deadline``, ``payload`` (float32) and ``origin`` (int32).

    Equal arrival times keep generation order (node, then service)."""
    services = cfg["services"]
    origin, svc = [], []
    for node, counts in enumerate(node_counts(cfg)):
        for name in SERVICE_ORDER:
            n = counts.get(name, 0)
            origin += [node] * n
            svc += [name] * n
    arrival = rng.uniform(0.0, cfg["window_ut"], len(origin)).astype(np.float32)
    order = np.argsort(arrival, kind="stable")
    bpp = float(cfg["bytes_per_pixel"])
    col = lambda f: np.array([f(services[s]) for s in svc], np.float32)[order]
    return dict(
        arrival=arrival[order],
        proc=col(lambda s: s["proc_ut"]),
        rel_deadline=col(lambda s: s["deadline_ut"]),
        payload=col(lambda s: s["pixels"] * bpp / 1e6),
        origin=np.asarray(origin, np.int32)[order])


def link_matrices(cfg: dict):
    """Full-mesh ``(K, K)`` hop latency (UT) and inverse bandwidth (UT per
    MB) of the configuration's link profile, float32, zero diagonal."""
    K, link = cfg["nodes"], cfg["link"]
    off = ~np.eye(K, dtype=bool)
    lat = np.where(off, np.float32(link["latency_ut"]), np.float32(0))
    ibw = np.where(off, np.float32(1.0 / link["bandwidth_mb_per_ut"]),
                   np.float32(0))
    return lat.astype(np.float32), ibw.astype(np.float32)


def fleet_points(cfg: dict, traffic: dict, seed: int) -> List[dict]:
    """The points one call simulates: ``{"stream", "sla_scale"}`` each.

    A ``single`` mix is one stream at the mix's ``sla_scale`` (default 1).
    A ``sweep`` mix is ``workload_seeds`` streams times ``sla_scales``
    (the paper's replicates over an SLA grid), every stream drawn from
    its own child of ``seed``."""
    if traffic["calls"] == "single":
        return [dict(stream=fleet_stream(cfg, np.random.default_rng(seed)),
                     sla_scale=float(traffic.get("sla_scale", 1.0)))]
    streams = [fleet_stream(cfg, np.random.default_rng([seed, w]))
               for w in range(traffic["workload_seeds"])]
    return [dict(stream=s, sla_scale=float(k))
            for s in streams for k in traffic["sla_scales"]]


def frame_gaps(traffic: dict, rng: np.random.Generator) -> np.ndarray:
    """Inter-arrival gaps of one serving round on the engine clock.

    The gaps are the quantiles of an exponential distribution with the
    mix's mean (a Poisson stream), in an order drawn from ``rng``: every
    round and every seed offers the same set of gaps."""
    n, mean = traffic["frames_per_round"], traffic["inter_arrival_ut"]
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(-mean * np.log1p(-q))
