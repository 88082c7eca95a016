"""Tensor views of the orchestration plane's host objects.

The fleet simulator consumes the same :class:`~repro.orchestration.workload.
Workload` / :class:`~repro.orchestration.topology.Topology` objects as the
event-heap :class:`~repro.orchestration.orchestrator.Orchestrator`, but as
flat arrays the device can scan:

* :class:`RequestArrays` — one row per request, sorted by arrival time
  exactly like the orchestrator's initial event heap.  ``rid`` is the dense
  index 0..R-1 in that order (the host object's global ``rid`` counter is
  process-dependent; the dense index is what per-request outcome arrays key
  on, with ``pack_requests`` returning the dense->host mapping for
  cross-validation).
* :class:`TopologyArrays` — adjacency matrix, padded neighbor lists and
  per-node speeds; everything a traced router policy needs.

Both are NamedTuples of plain arrays, so they stack with ``tree_map`` for
``vmap`` sweeps (e.g. one leading seed axis over per-seed request tensors).

Event tensors (DESIGN.md §7): the event-time scan advances over *events*
— fresh arrivals streamed from the sorted :class:`RequestArrays` through
a cursor, plus deferred re-arrivals in a compact sorted ``(event_buf,)``
buffer of ``(time, rid, node, hops)`` columns.  Every request arrives at
most ``max_forwards + 1`` times, so :func:`event_bound` — the static
``R * (max_forwards + 1)`` worst case — caps the scan length; the scan
itself stops at its last event, and surfaces any shortfall of a tighter
``max_events`` in ``metrics.event_overflow``.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.core.request import Request
from repro.orchestration.topology import Topology


class RequestArrays(NamedTuple):
    """One request per row, arrival-sorted (the scan order)."""
    arrival: np.ndarray        # (R,) f32 arrival time
    proc: np.ndarray           # (R,) f32 unscaled worst-case processing time
    rel_deadline: np.ndarray   # (R,) f32 relative SLA deadline
    origin: np.ndarray         # (R,) i32 origin node id
    service: np.ndarray        # (R,) i32 index into the service name table
    payload: np.ndarray = None  # (R,) f32 frame size in MB (netsim wire
    #                             cost: delay = latency + payload * inv_bw;
    #                             ignored — and may be None — without a
    #                             NetParams)


class TopologyArrays(NamedTuple):
    """Traced view of a Topology: adjacency + padded neighbor lists."""
    adj: np.ndarray            # (K, K) bool, no self loops
    neighbors: np.ndarray      # (K, maxdeg) i32, row i padded with i
    degree: np.ndarray         # (K,) i32
    speeds: np.ndarray         # (K,) f32


def event_bound(n_requests: int, max_forwards: int) -> int:
    """The static worst-case event count of a fleet run: every request is
    processed once per arrival, and a request re-arrives at most
    ``max_forwards`` times — ``R * (max_forwards + 1)`` scan steps cover
    any forwarding realization (the default ``max_events``, a cap)."""
    return n_requests * (max_forwards + 1)


def pack_requests(requests: Sequence[Request], dtype=np.float32,
                  payload_fn=None
                  ) -> Tuple[RequestArrays, Tuple[str, ...], List[int]]:
    """Request objects -> (arrays, service name table, host rid per row).

    Rows keep the caller's order, which every Workload already emits sorted
    by ``(arrival_time, rid)`` — the same total order the orchestrator's
    event heap uses for simultaneous arrivals.  ``payload_fn(service)``
    sets the per-request wire payload in MB (default: the netsim frame
    model, ``pixels × bytes_per_pixel``); it only matters when a
    :class:`repro.netsim.NetParams` is passed to ``simulate``.
    """
    if payload_fn is None:
        from repro.netsim.link import default_payload as payload_fn
    names = sorted({r.service.name for r in requests})
    name_id = {s: i for i, s in enumerate(names)}
    arrays = RequestArrays(
        arrival=np.array([r.arrival_time for r in requests], dtype),
        proc=np.array([r.service.proc_time for r in requests], dtype),
        rel_deadline=np.array([r.service.deadline for r in requests], dtype),
        origin=np.array([r.origin_node for r in requests], np.int32),
        service=np.array([name_id[r.service.name] for r in requests],
                         np.int32),
        payload=np.array([payload_fn(r.service) for r in requests], dtype),
    )
    return arrays, tuple(names), [r.rid for r in requests]


def topology_arrays(topology: Topology, dtype=np.float32) -> TopologyArrays:
    """Topology -> TopologyArrays (neighbor rows padded with the own id, so
    out-of-degree gathers stay in range and are masked by ``degree``)."""
    K = topology.n_nodes
    adj = np.zeros((K, K), bool)
    maxdeg = max((topology.degree(i) for i in range(K)), default=0)
    neighbors = np.tile(np.arange(K, dtype=np.int32)[:, None],
                        (1, max(maxdeg, 1)))
    degree = np.zeros((K,), np.int32)
    for i in range(K):
        nbrs = topology.neighbors(i)
        degree[i] = len(nbrs)
        for j, v in enumerate(nbrs):
            adj[i, v] = True
            neighbors[i, j] = v
    return TopologyArrays(adj=adj, neighbors=neighbors, degree=degree,
                          speeds=np.asarray(topology.speeds, dtype))


def scenario_arrays(workload, seed: int, dtype=np.float32
                    ) -> Tuple[RequestArrays, Tuple[str, ...]]:
    """``workload.generate(seed)`` packed for the device (drops the host-rid
    mapping, which only cross-validation needs)."""
    arrays, names, _ = pack_requests(workload.generate(seed), dtype)
    return arrays, names
