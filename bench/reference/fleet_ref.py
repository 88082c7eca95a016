"""Plain reference for the fleet cells: the paper's strategy as an event heap.

Deadline-aware admission into a preferential time-block queue, plus
sequential forwarding over a priced network (Boing et al. 2022, arXiv
2212.03802, Sec. III; Algorithms 1-5), written from the description and
independent of the code under test: plain Python lists, ``bisect`` and
``heapq``, one event at a time.

Every time value is a scalar of the dtype ``T`` that the configuration
states (``numpy.float32``), so each addition rounds where the
configuration's arithmetic rounds and a result can be compared request
for request.  Passing ``ml_dtypes.bfloat16`` computes the same semantics
one precision lower: that is the control, which has to come out as not
correct.

Semantics, per request ``r`` arriving at node ``k`` at time ``t``:

* a node is a single work-conserving server; its queue is a ledger of
  non-overlapping blocks ``[start, end]`` kept as late as the deadlines
  allow.  Admission inserts at the rightmost non-empty window (capped by
  the deadline and by the next block's start) when the compacted prefix
  leaves room for ``proc`` before the cap, shifting earlier blocks left
  only as far as needed;
* a request that does not fit is forwarded (at most ``max_forwards``
  times) to the target the routing policy picks, and re-arrives there
  after ``latency + payload * inv_bw``, its absolute deadline unmoved;
* an exhausted request is appended at the tail (it runs late) or, with
  ``discard_on_exhaust``, dropped;
* events at one time are taken in the order they were scheduled: every
  fresh arrival first (in request order), then completions and
  re-arrivals in the order they were pushed.
"""
from __future__ import annotations

import bisect
import heapq
from typing import Dict

import numpy as np

PENDING, MET, LATE, DISCARDED = 0, 1, 2, 3
_ARRIVAL, _COMPLETE = 0, 1


class _Queue:
    """One node's block ledger (head first) and its pending work."""

    __slots__ = ("starts", "ends", "sizes", "rids", "work", "zero")

    def __init__(self, zero):
        self.starts, self.ends, self.sizes, self.rids = [], [], [], []
        self.work = self.zero = zero

    def search(self, p, d, free, p_eps):
        """``(slot, cap)`` of the admission window, or None if ``p`` does not
        fit before ``d`` with the CPU free at ``free``."""
        starts, ends = self.starts, self.ends
        cap_idx = bisect.bisect_left(starts, d)      # first start >= d
        e_hi = bisect.bisect_left(ends, d)           # blocks ending before d
        if e_hi >= cap_idx:
            j, cap = e_hi, d
        else:                                        # a block straddles d
            j = 0
            for i in range(e_hi, 0, -1):
                if starts[i] > ends[i - 1]:          # rightmost real gap
                    j = i
                    break
            cap = min(starts[j], d)
        pw = sum(self.sizes[:j], self.zero)          # compacted prefix
        if cap > free and cap - (free + pw) >= p_eps:
            return j, cap
        return None

    def insert(self, j, cap, p, rid):
        """Right-align the new block at ``cap``; shift earlier blocks left
        just far enough that none overlaps its right neighbour."""
        new_start = cap - p
        req_end = new_start
        for i in range(j - 1, -1, -1):
            if self.ends[i] <= req_end:
                break
            self.ends[i] = req_end
            self.starts[i] = req_end - self.sizes[i]
            req_end = self.starts[i]
        self.starts.insert(j, new_start)
        self.ends.insert(j, cap)
        self.sizes.insert(j, p)
        self.rids.insert(j, rid)
        self.work = self.work + p

    def append(self, p, rid, free):
        """Forced push: plain tail append, the gaps left as they are."""
        right = (self.ends[-1] if self.ends else free) + p
        self.starts.append(right - p)
        self.ends.append(right)
        self.sizes.append(p)
        self.rids.append(rid)
        self.work = self.work + p

    def pop(self):
        self.starts.pop(0)
        self.ends.pop(0)
        size = self.sizes.pop(0)
        self.work = self.work - size
        return self.rids.pop(0), size


def simulate(arrival, proc, rel_deadline, origin, payload, *, n_nodes: int,
             latency, inv_bw, speeds=None, policy: str, max_forwards: int,
             discard_on_exhaust: bool = False, sla_scale: float = 1.0,
             dtype=np.float32, peak: dict = None) -> Dict[str, np.ndarray]:
    """Run the strategy over one request stream (arrays in arrival order).

    ``latency``/``inv_bw`` are ``(K, K)`` hop prices (full mesh: every
    other node is a neighbour).  ``policy`` is ``round_robin`` (a pointer
    over node ids that skips the forwarding node and advances past each
    pick) or ``batched_feasible`` (the least-loaded neighbour that can
    still admit the request at its wire-delayed arrival, lowest id on
    ties; the least-loaded neighbour when none can).

    Returns per-request ``outcome``, ``served_by`` (-1 if not served),
    ``completion`` (0 if not served), ``transfer`` (wire time paid) and
    ``forwards`` (hops taken), each as a numpy array.  A ``peak`` dict,
    if given, receives the most admissions into one node's queue
    (``admissions``) and the deepest queue an admission saw (``depth``):
    the ledger sizes a fixed-capacity implementation needs.
    """
    T = dtype
    R, K = len(arrival), n_nodes
    zero = T(0)
    arr = [T(a) for a in arrival]
    p_r = [T(p) for p in proc]
    spd = [T(1.0 if speeds is None else speeds[k]) for k in range(K)]
    dl = [a + T(r) * T(sla_scale) for a, r in zip(arr, rel_deadline)]
    pay = [T(x) for x in payload]
    lat = [[T(x) for x in row] for row in np.asarray(latency)]
    ibw = [[T(x) for x in row] for row in np.asarray(inv_bw)]
    eps = T(1e-6)
    queues = [_Queue(zero) for _ in range(K)]
    busy = [zero] * K
    active = [False] * K
    hops = [0] * R
    served = np.full(R, -1, np.int64)
    completion = [zero] * R
    transfer = [zero] * R
    outcome = np.full(R, PENDING, np.int64)
    rr = 0
    admitted = [0] * K
    depth_hw = [0] * K

    heap = [(arr[i], i, _ARRIVAL, i, int(origin[i])) for i in range(R)]
    heapq.heapify(heap)
    seq = R

    def dispatch(k, now):
        nonlocal seq
        if active[k] or now < busy[k] or not queues[k].rids:
            return
        rid, size = queues[k].pop()
        active[k] = True
        busy[k] = now + size
        heapq.heappush(heap, (busy[k], seq, _COMPLETE, rid, k))
        seq += 1

    def route(src, rid, now):
        nonlocal rr
        if policy == "round_robin":
            while True:
                cand = rr % K
                rr += 1
                if cand != src:
                    return cand
        if policy != "batched_feasible":
            raise ValueError(f"reference has no policy {policy!r}")
        ranked = sorted((queues[i].work, i) for i in range(K) if i != src)
        for _, i in ranked:
            arrive = (now + lat[src][i]) + pay[rid] * ibw[src][i]
            ps = p_r[rid] / spd[i]
            if queues[i].search(ps, dl[rid], max(arrive, busy[i]),
                                ps - eps) is not None:
                return i
        return ranked[0][1]

    while heap:
        now, _, kind, rid, k = heapq.heappop(heap)
        if kind == _COMPLETE:
            active[k] = False
            completion[rid] = now
            served[rid] = k
            dispatch(k, now)
            continue
        exhausted = hops[rid] >= max_forwards or K == 1
        ps = p_r[rid] / spd[k]
        free = max(now, busy[k])
        slot = queues[k].search(ps, dl[rid], free, ps - eps)
        if slot is not None or (exhausted and not discard_on_exhaust):
            if slot is not None:
                queues[k].insert(*slot, ps, rid)
            else:
                queues[k].append(ps, rid, free)
            admitted[k] += 1
            depth_hw[k] = max(depth_hw[k], len(queues[k].rids))
            dispatch(k, now)
        elif exhausted:
            outcome[rid] = DISCARDED
        else:
            hops[rid] += 1
            nxt = route(k, rid, now)
            delay = lat[k][nxt] + pay[rid] * ibw[k][nxt]
            transfer[rid] = transfer[rid] + delay
            heapq.heappush(heap, (now + delay, seq, _ARRIVAL, rid, nxt))
            seq += 1

    if peak is not None:
        peak["admissions"] = max(admitted)
        peak["depth"] = max(depth_hw)
    done = served >= 0
    met = np.array([c <= d for c, d in zip(completion, dl)])
    outcome = np.where(done, np.where(met, MET, LATE), outcome)
    return dict(outcome=outcome, served_by=served,
                completion=np.array(completion, np.float64),
                transfer=np.array(transfer, np.float64),
                forwards=np.array(hops, np.int64))


def run_point(job) -> Dict[str, np.ndarray]:
    """One sweep point ``(config, point, dtype name)`` as a pool job: the
    configuration's full-mesh link prices, the point's stream, policy and
    SLA scale."""
    cfg, point, dtype_name = job
    if dtype_name == "bfloat16":
        import ml_dtypes
        dtype = ml_dtypes.bfloat16
    else:
        dtype = np.dtype(dtype_name).type
    from bench import gen
    lat, ibw = gen.link_matrices(cfg)
    s = point["stream"]
    return simulate(
        s["arrival"], s["proc"], s["rel_deadline"], s["origin"], s["payload"],
        n_nodes=cfg["nodes"], latency=lat, inv_bw=ibw,
        speeds=[cfg["speed"]] * cfg["nodes"], policy=point["policy"],
        max_forwards=cfg["max_forwards"],
        discard_on_exhaust=cfg["discard_on_exhaust"],
        sla_scale=point["sla_scale"], dtype=dtype, peak=point.get("peak"))
