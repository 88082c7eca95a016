"""Device-resident fleet simulator: the whole deadline-aware admission +
sequential-forwarding strategy compiled end-to-end in JAX.

The event-heap :class:`~repro.orchestration.orchestrator.Orchestrator`
walks a Python heap; this module replays the *same* strategy as one
``lax.scan`` over **events**, with the entire fleet held as stacked
``(num_nodes, capacity)`` ledger arrays (the
:class:`~repro.core.jax_queue.Ledger` geometry plus per-slot absolute
deadlines and request ids) next to per-node ``head``/``busy_until``/
``load`` vectors.  One scan step = one *event* — the earliest of

* the next **fresh arrival**, streamed straight from the arrival-sorted
  request tensor through a cursor (fresh arrivals fill the host heap
  before the run, so they carry the lowest sequence numbers and win
  every timestamp tie), and
* the head of the **deferred re-arrival buffer** — a sorted, compact
  device event queue (:func:`repro.core.jax_queue.event_push` /
  ``event_pop``) holding every in-flight referral at its true wire-
  delayed arrival time, stable-inserted so equal timestamps keep push
  order, exactly the heap's ``(time, seq)`` key

— processed as a **single hop**:

1. **fast-forward** — a masked ``while_loop`` retires every completion
   due strictly before the event time (the CPU model is work-conserving,
   so the pop chain between two events is deterministic).  Rows are
   *head-pointer* ledgers: a pop clears one slot (start/end to -BIG,
   size to 0 — which keeps the whole row time-sorted and every count /
   prefix-sum valid) and bumps ``head``, so retiring costs O(nodes)
   scatters instead of shifting the (num_nodes, capacity) block;
2. **test + route** — the event's node is scored by one feasibility +
   geometry pass over its live window; an infeasible, non-exhausted
   request picks its forwarding target *now*, at true event time (loads,
   rng, the trace row, and — for ``batched_feasible`` — the per-hop
   feasibility mask of :func:`repro.fleetsim.scoring.event_select` all
   reflect every earlier event), and emits a re-arrival event at
   ``t + transfer_delay`` instead of resolving the chain speculatively
   at the source step;
3. **apply** — feasible insert at the pre-computed (slot, window) pair,
   forced tail-append, or discard as ``where``-selects; an idle CPU
   short-circuits the insert (the host engine pushes then immediately
   pops — the net effect is starting the request at ``t``).

Because nothing escapes the device, :func:`simulate` jits whole and
``vmap``s over seeds and policy parameters (``SimParams``): a full paper
table — scenarios x policies x seeds — is one device call.  Equivalence
with the event heap is exact for deterministic policies and exact under
forwarding-trace replay for the stochastic ones — **under any link
pricing**, not just the zero network: deferred re-arrivals replay the
heap's interleaving of arrivals, completions and referrals event for
event (contract in DESIGN.md §7; cross-validated in fleetsim/validate.py
and tests/test_fleetsim.py / tests/test_netsim.py).

The network is a further sweep axis (``net``: :class:`repro.netsim.
NetParams` — (K, K) latency / inverse-bandwidth tensors): a referral's
wire time delays its re-arrival while the absolute deadline stays put,
consuming admission slack exactly as in the heap's netsim integration
(DESIGN.md §6).  ``net=None`` runs the same event machinery with every
hop priced 0.0, so ``NetParams.zero`` reproduces its outcomes
bit-for-bit (equivalence-guarded in tests/test_netsim.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import jax_queue as jq
from repro.fleetsim.arrays import (RequestArrays, TopologyArrays,
                                   event_bound)
from repro.fleetsim.scoring import event_select, fleet_search
from repro.netsim.link import NetParams
from repro.telemetry.timeline import (TelemetryConfig, TelemetryFrame,
                                      bucket_of, bucket_width,
                                      interval_histogram, telemetry_init)

POLICIES = ("random", "power_of_two", "least_loaded", "round_robin",
            "batched_feasible", "trace")

# outcome codes (per request)
PENDING, MET, LATE, DISCARDED, OVERFLOW = 0, 1, 2, 3, 4

_MET_EPS = 1e-9          # same slack as Request.met_deadline

# steps per chunk of the event-time scan: it stops at the first chunk
# boundary after its last event, so at most SCAN_CHUNK - 1 dead steps run
SCAN_CHUNK = 128


class SimParams(NamedTuple):
    """Traced sweep axes: everything here can carry a vmap dimension."""
    seed: jnp.ndarray                  # i32 — forwarding rng stream
    sla_scale: jnp.ndarray             # f32 — multiplies relative deadlines

    @classmethod
    def make(cls, seed: int = 0, sla_scale: float = 1.0) -> "SimParams":
        return cls(seed=jnp.asarray(seed, jnp.int32),
                   sla_scale=jnp.asarray(sla_scale, jnp.float32))


# per-request terminal record, packed into ONE i32 scatter per event (the
# scan's hot loop is fusion-break bound on CPU — every scatter counts):
# bits [0,8) forwards used, bit 8 discarded, bit 9 overflow, bits [10,..)
# serving node + 1 (0 == not admitted).
_INFO_DISC, _INFO_OVF, _INFO_SERVED = 1 << 8, 1 << 9, 10


class EventState(NamedTuple):
    """The scan carry: fleet ledgers + the event plane + outcome arrays."""
    # stacked head-pointer ledgers: (K, N) block geometry + per-slot request
    # identity; live blocks of node k occupy columns [head[k], head[k]+nq[k])
    starts: jnp.ndarray
    ends: jnp.ndarray
    sizes: jnp.ndarray
    slot_rid: jnp.ndarray              # dense request index per block (i32)
    head: jnp.ndarray                  # (K,) i32 retired-slot count
    nq: jnp.ndarray                    # (K,) i32 live block count
    busy: jnp.ndarray                  # (K,) time the CPU frees
    load: jnp.ndarray                  # (K,) pending ledger work (= host
    #                                     queue.pending_work(), active excl.)
    rr: jnp.ndarray                    # () i32 round-robin pointer
    # the event plane: fresh arrivals stream through `cursor`; deferred
    # re-arrivals live in the sorted compact (B,) buffer (host-heap order)
    cursor: jnp.ndarray                # () i32 next fresh arrival index
    ev_time: jnp.ndarray               # (B,) event times, +BIG past ev_n
    ev_rid: jnp.ndarray                # (B,) i32 request dense index
    ev_meta: jnp.ndarray               # (B,) i32 node << hop_bits | hops
    ev_n: jnp.ndarray                  # () i32 buffered event count
    ev_dropped: jnp.ndarray            # () i32 pushes lost to a full buffer
    sat_events: jnp.ndarray            # () i32 events that consulted a full
    #                                    live window (undersized depth guard)
    # per-request outcome carries (events touch arbitrary requests, so none
    # of these can ride the scan's stacked outputs)
    completion: jnp.ndarray            # (R,) pop-time / idle-start scatter
    reqinfo: jnp.ndarray               # (R,) i32 packed terminal record
    transfer: jnp.ndarray              # (R,) wire time paid on referrals
    # the carried half of the telemetry plane (DESIGN.md §8).  None when
    # telemetry is disabled: a None leaf is an empty pytree, so the scan
    # carry, the jaxpr and the compiled step are bit-identical to a
    # build without these fields — the disabled path costs nothing
    # (guarded in tests/test_telemetry.py)
    tel_counts: Optional[jnp.ndarray] = None   # (K, NB, N_KINDS) i32
    tel_occ: Optional[jnp.ndarray] = None      # (NB,) i32 ev_n high water


class FleetMetrics(NamedTuple):
    """Headline aggregates + the per-request arrays they reduce."""
    total: jnp.ndarray
    processed: jnp.ndarray
    met_deadline: jnp.ndarray
    forwards: jnp.ndarray
    discarded: jnp.ndarray
    overflow: jnp.ndarray            # forced pushes dropped: no free slot
    window_saturation: jnp.ndarray   # events that consulted a full live
    #                                  window — admission may diverge from
    #                                  the host's unbounded queue; keep 0
    mean_response_time: jnp.ndarray
    end_time: jnp.ndarray
    outcome: jnp.ndarray
    completion: jnp.ndarray
    served_by: jnp.ndarray
    forwards_used: jnp.ndarray
    transfer_time: jnp.ndarray       # total wire time spent on referrals
    transfer_used: jnp.ndarray       # (R,) per-request wire time
    event_overflow: jnp.ndarray      # events dropped (full buffer) or left
    #                                  unprocessed at max_events; keep 0
    scan_steps: jnp.ndarray          # i32 steps the event scan ran: the
    #                                  events rounded up to whole chunks
    #                                  (SCAN_CHUNK), never above max_events
    telemetry: Optional[TelemetryFrame] = None   # the time-binned cube;
    #                                  None unless simulate(telemetry=...)

    @property
    def met_rate(self):
        return self.met_deadline / jnp.maximum(1, self.total)


# ---------------------------------------------------------------------------
# fast-forward: retire completions due strictly before t (work-conserving
# pop chain), recording outcomes by slot rid.  Also the drain loop (t=inf).
# ---------------------------------------------------------------------------
def _retire(state: EventState, t, R: int) -> EventState:
    K, N = state.starts.shape
    rows = jnp.arange(K)

    def cond(s):
        return jnp.any((s.busy < t) & (s.nq > 0))

    def body(s):
        mask = (s.busy < t) & (s.nq > 0)
        h = jnp.minimum(s.head, N - 1)
        head_size = s.sizes[rows, h]
        new_busy = jnp.where(mask, s.busy + head_size, s.busy)
        rid = jnp.where(mask, s.slot_rid[rows, h], R)   # R => dropped

        def clear(a, v):
            return a.at[rows, h].set(jnp.where(mask, v, a[rows, h]))

        return s._replace(
            # -BIG keeps the retired prefix below every live value, so the
            # row stays globally sorted and counts/prefix sums stay valid
            starts=clear(s.starts, -jq.BIG),
            ends=clear(s.ends, -jq.BIG),
            sizes=clear(s.sizes, 0.0),
            head=s.head + mask,
            nq=s.nq - mask,
            busy=new_busy,
            load=s.load - jnp.where(mask, head_size, 0.0),
            completion=s.completion.at[rid].set(new_busy, mode="drop"),
        )

    return jax.lax.while_loop(cond, body, state)


def _row_windows(a, w0, W: int):
    """``a[k, w0[k]:w0[k] + W]`` for every row ``k`` (``0 <= w0 <=
    a.shape[1] - W``) as a barrel shift: one static slice-and-select per
    bit of ``w0``.  Pure data movement, so bit-identical to the per-row
    gather it replaces — which the TPU runs element by element, and
    which cost milliseconds per scan step at 256 nodes."""
    nb = (a.shape[1] - W).bit_length()
    a = jnp.pad(a, ((0, 0), (0, (1 << nb) - 1 - (a.shape[1] - W))))
    for b in range(nb):
        s = 1 << b
        a = jnp.where((w0 >> b & 1)[:, None] == 1, a[:, s:], a[:, :-s])
    return a


# ---------------------------------------------------------------------------
# routing policies: pure selects over (load, adjacency, rng, trace row).
# Consulted at true event time — every earlier arrival, completion and
# referral has already mutated the state the policy reads, exactly like the
# host Router called from the heap's forward event.
# ---------------------------------------------------------------------------
def _route_next(policy: str, topo: TopologyArrays, load, cur, key, hop,
                tgt_row, feas_all, rr):
    """Forwarding target of ``cur`` at hop ``hop`` (traced); returns
    ``(next_node, advanced_rr)``.  Callers commit ``advanced_rr`` only when
    the forward really happens (host Router semantics: the round-robin
    pointer moves per ``choose()`` call)."""
    deg = topo.degree[cur]
    K = topo.adj.shape[0]
    if policy == "trace":
        M = tgt_row.shape[0]
        return jnp.maximum(tgt_row[jnp.minimum(hop, M - 1)], 0), rr
    if policy == "round_robin":
        # stable-id pointer: probe rr, rr+1, ... (mod K), skip non-neighbors;
        # the pointer advances past the chosen probe (host Router semantics)
        offs = jnp.arange(K)
        cands = (rr + offs) % K
        off = jnp.argmax(topo.adj[cur][cands])
        return cands[off], (rr + off + 1) % K
    if policy == "least_loaded":
        # deterministic variant: ties break to the lowest node id (the host
        # router flips a coin; documented in DESIGN.md §5)
        return jnp.argmin(jnp.where(topo.adj[cur], load, jnp.inf)), rr
    if policy == "batched_feasible":
        # least-loaded neighbor that can still admit (cross-node mask from
        # the fused event_select scoring); least-loaded fallback when nobody
        # can — identical tie-breaking to the host router (lowest id)
        ok = topo.adj[cur] & feas_all
        best_ok = jnp.argmin(jnp.where(ok, load, jnp.inf))
        best_any = jnp.argmin(jnp.where(topo.adj[cur], load, jnp.inf))
        return jnp.where(jnp.any(ok), best_ok, best_any), rr
    kh = jax.random.fold_in(key, hop)
    if policy == "random":
        u = jax.random.uniform(kh)
        pick = jnp.minimum((u * deg).astype(jnp.int32),
                           jnp.maximum(deg - 1, 0))
        return topo.neighbors[cur, pick], rr
    if policy == "power_of_two":
        k1, k2 = jax.random.split(kh)
        i1 = jnp.minimum((jax.random.uniform(k1) * deg).astype(jnp.int32),
                         jnp.maximum(deg - 1, 0))
        i2 = jnp.minimum(
            (jax.random.uniform(k2) * (deg - 1)).astype(jnp.int32),
            jnp.maximum(deg - 2, 0))
        i2 = jnp.where(i2 >= i1, i2 + 1, i2)        # sample w/o replacement
        a = topo.neighbors[cur, i1]
        b = topo.neighbors[cur, jnp.minimum(i2, jnp.maximum(deg - 1, 0))]
        two = jnp.where(load[a] <= load[b], a, b)
        return jnp.where(deg <= 1, topo.neighbors[cur, 0], two), rr
    raise ValueError(f"unknown fleetsim policy {policy!r}; "
                     f"options: {sorted(POLICIES)}")


# ---------------------------------------------------------------------------
# the scan step: one event end-to-end (select, retire, test, route, apply)
# ---------------------------------------------------------------------------
def _estep(state: EventState, _, *, topo: TopologyArrays, key, policy: str,
           max_forwards: int, discard_on_exhaust: bool, capacity: int,
           depth: int, R: int, use_network: bool,
           net: Optional[NetParams], fresh_cols, rear_cols, targets,
           zero_net, hop_bits: int, tel_buckets: Optional[int] = None,
           tel_width=None) -> Tuple[EventState, None]:
    K = topo.speeds.shape[0]
    W = depth
    dt = state.busy.dtype

    # -- the two candidate events: next fresh arrival vs re-arrival head.
    # Per-request constants ride pre-packed row matrices so each candidate
    # costs ONE gather (the scan is fusion-break bound on CPU)
    with jax.named_scope("fleetsim.event_pop"):
        avail_a = state.cursor < R
        ci = jnp.minimum(state.cursor, R - 1)
        rid_b = state.ev_rid[0]
        meta_b = state.ev_meta[0]
        node_b = meta_b >> hop_bits
        hops_b = meta_b & ((1 << hop_bits) - 1)
        fa = fresh_cols[ci]                  # (arrival, origin, d, p, pay)
        fb = rear_cols[rid_b]                # (d, p, pay)
        origin_a = fa[1].astype(jnp.int32)
        cand_a = (fa[0], origin_a, fa[2], fa[3], fa[4], avail_a)
        cand_b = (state.ev_time[0], node_b, fb[0], fb[1], fb[2],
                  state.ev_n > 0)

        # plain-jnp merge: fresh wins timestamp ties (the host heap numbers
        # every fresh arrival before the run — lower seq than any mid-run
        # push), the buffer orders re-arrivals by stable (time, seq) insert
        take_fresh = avail_a & ((cand_a[0] <= cand_b[0]) | ~cand_b[5])
        t = jnp.where(take_fresh, cand_a[0], cand_b[0])
        cur = jnp.where(take_fresh, cand_a[1], cand_b[1])

        live = avail_a | cand_b[5]
        rid = jnp.where(take_fresh, ci, rid_b)
        hops = jnp.where(take_fresh, 0, hops_b)
        d = jnp.where(take_fresh, cand_a[2], cand_b[2])
        p = jnp.where(take_fresh, cand_a[3], cand_b[3])
        pay = jnp.where(take_fresh, cand_a[4], cand_b[4])

        # -- consume the event: bump the cursor or pop the buffer head ----
        ev_time, (ev_rid, ev_meta), ev_n = jq.event_pop(
            state.ev_time, (state.ev_rid, state.ev_meta),
            state.ev_n, live & ~take_fresh)
        state = state._replace(
            cursor=state.cursor + take_fresh.astype(jnp.int32),
            ev_time=ev_time, ev_rid=ev_rid, ev_meta=ev_meta, ev_n=ev_n)

    # -- retire completions due strictly before the event (on a dead step
    # t is +BIG, which simply starts the final drain early — harmless).
    # Everything below — the fused scoring included — must see the
    # POST-retire ledgers: the host pops every completion due before `t`
    # ahead of the admission test, and a stale not-yet-retired block would
    # inflate the pending-work sum and flip verdicts.
    with jax.named_scope("fleetsim.retire"):
        state = _retire(state, t, R)
    with jax.named_scope("fleetsim.decide"):
        ps = p / topo.speeds                                # (K,) scaled
        cpu_free_c = jnp.maximum(t, state.busy[cur])

    feas_all = j_all = cap_all = None
    if policy == "batched_feasible":
        # every node scored at the event's wire-delayed arrival, in one
        # pass over the whole fleet's live windows.  event_select re-derives
        # the merge from the same candidate scalars (bit-identical to the
        # merge above; the selected node picks which latency /
        # inverse-bandwidth row the scoring reads).  The scope names are
        # what `select_us` reads: the windows under "fleetsim.windows", the
        # scoring under "kernels.event_select".
        with jax.named_scope("fleetsim.windows"):
            w0_all = jnp.clip(state.head, 0, capacity - W)
            win_all = lambda a: _row_windows(a, w0_all, W)
            hrel_all = state.head - w0_all
            wins = (win_all(state.starts), win_all(state.ends),
                    win_all(state.sizes))
        lat, ibw = (net.latency, net.inv_bw) if use_network \
            else (zero_net, zero_net)
        with jax.named_scope("kernels.event_select"):
            sel = event_select(*cand_a, *cand_b, *wins, state.nq, hrel_all,
                               topo.speeds, state.busy, lat, ibw)
        take_fresh, t, cur, feas_all, _, j_all, cap_all, _ = sel

    # -- admission test at the event's node -------------------------------
    with jax.named_scope("fleetsim.feasibility"):
        w0c = jnp.clip(state.head[cur], 0, capacity - W)
        hrel_c = state.head[cur] - w0c

        def win_row(buf):
            return jax.lax.dynamic_slice(buf, (cur, w0c), (1, W))[0]

        starts_w, ends_w, sizes_w = (win_row(state.starts),
                                     win_row(state.ends),
                                     win_row(state.sizes))
        if policy == "batched_feasible":
            # the fused pass already scored every node — including `cur`
            # itself at its true arrival (zero net diagonal); gather its
            # verdict
            ok = feas_all[cur]
            j, cap = j_all[cur], cap_all[cur]
        else:
            okv, jv, capv, _ = fleet_search(
                starts_w[None], ends_w[None], sizes_w[None],
                state.nq[cur][None], ps[cur][None], d, cpu_free_c[None],
                hrel_c[None])
            ok, j, cap = okv[0], jv[0], capv[0]

    # -- decide: admit / forward / force / discard ------------------------
    with jax.named_scope("fleetsim.decide"):
        exhausted = (hops >= max_forwards) | (topo.degree[cur] == 0)
        feas_evt = live & ok
        forced_req = live & ~ok & exhausted & (not discard_on_exhaust)
        disc_evt = live & ~ok & exhausted & discard_on_exhaust
        fwd = live & ~ok & ~exhausted

    # -- forward: pick the target NOW (true event time) and defer the
    # re-arrival to t + transfer_delay via a stable sorted insert ---------
    with jax.named_scope("fleetsim.route"):
        kreq = jax.random.fold_in(key, rid) \
            if policy in ("random", "power_of_two") else None
        tgt_row = targets[rid] if policy == "trace" else None
        nxt, rr_adv = _route_next(policy, topo, state.load, cur, kreq, hops,
                                  tgt_row, feas_all, state.rr)
        if use_network:
            # the hop's wire cost — latency plus frame serialization
            # (DESIGN.md §6) — as two scalar gathers
            delay = net.latency[cur, nxt] + pay * net.inv_bw[cur, nxt]
        else:
            delay = jnp.zeros((), dt)
        ev_time, (ev_rid, ev_meta), ev_n, dropped = jq.event_push(
            state.ev_time, (state.ev_rid, state.ev_meta),
            state.ev_n, t + delay, (rid, (nxt << hop_bits) | (hops + 1)),
            fwd)
        state = state._replace(
            ev_time=ev_time, ev_rid=ev_rid, ev_meta=ev_meta,
            ev_n=ev_n,
            ev_dropped=state.ev_dropped + dropped.astype(jnp.int32))
        if policy == "round_robin":
            state = state._replace(rr=jnp.where(fwd, rr_adv, state.rr))

    # -- apply at cur, within its window (jax_queue.insert_at — the shared
    # closed-form cascade — with the pre-computed search results) ---------
    with jax.named_scope("fleetsim.admission"):
        room = hrel_c + state.nq[cur] < W
        forced_ok = forced_req & room
        ovf_evt = forced_req & ~room
        # a consulted node whose live window is exhausted can diverge from
        # the host's unbounded queue even on the feasible path (its
        # admission test reports "no room" where the host might admit) —
        # surface it
        sat_evt = live & (hrel_c + state.nq[cur] >= W)
        idle = state.busy[cur] < t
        sr_w = win_row(state.slot_rid)
        n_starts, n_ends, n_sizes, admitted, (n_sr,) = jq.insert_at(
            starts_w, ends_w, sizes_w, hrel_c, state.nq[cur], feas_evt,
            forced_ok, j, cap, ps[cur], cpu_free_c, meta=(sr_w,),
            meta_vals=(rid,))

    # idle CPU: the host engine pushes then immediately pops — net effect is
    # the request starts at its (wire-delayed) arrival and never enters
    # the ledger
    with jax.named_scope("fleetsim.decide"):
        start_now = admitted & idle
        queue_it = admitted & ~idle
        c_now = t + ps[cur]

    def put(buf, new, old):
        return jax.lax.dynamic_update_slice(
            buf, jnp.where(queue_it, new, old)[None, :], (cur, w0c))

    # the packed terminal record: one (R,) scatter instead of four
    with jax.named_scope("fleetsim.scatter"):
        terminal = admitted | disc_evt | ovf_evt
        info = (hops
                + jnp.where(disc_evt, _INFO_DISC, 0)
                + jnp.where(ovf_evt, _INFO_OVF, 0)
                + jnp.where(admitted, (cur + 1) << _INFO_SERVED, 0))
        rid_if = lambda flag: jnp.where(flag, rid, R)       # R => dropped
        state = state._replace(
            starts=put(state.starts, n_starts, starts_w),
            ends=put(state.ends, n_ends, ends_w),
            sizes=put(state.sizes, n_sizes, sizes_w),
            slot_rid=put(state.slot_rid, n_sr, sr_w),
            nq=state.nq.at[cur].add(queue_it.astype(jnp.int32)),
            load=state.load.at[cur].add(jnp.where(queue_it, ps[cur], 0.0)),
            busy=state.busy.at[cur].set(
                jnp.where(start_now, c_now, state.busy[cur])),
            sat_events=state.sat_events + sat_evt.astype(jnp.int32),
            completion=state.completion.at[rid_if(start_now)].set(
                c_now, mode="drop"),
            reqinfo=state.reqinfo.at[rid_if(terminal)].set(info,
                                                           mode="drop"),
        )
        if use_network:
            state = state._replace(
                transfer=state.transfer.at[rid_if(fwd)].add(delay,
                                                            mode="drop"))

    if tel_buckets is not None:
        # the carried half of the telemetry cube (DESIGN.md §8): one
        # 5-vector scatter-add of the event kinds at (node, bucket) and
        # one high-water max of the re-arrival buffer's live count.  On a
        # dead step every flag is False and ev_n is 0, so both updates
        # are no-ops (the +BIG event time clips into the last bucket on
        # the float side — no int overflow)
        with jax.named_scope("fleetsim.telemetry"):
            b = bucket_of(t, tel_width, tel_buckets)
            kinds = jnp.stack([
                (live & take_fresh).astype(jnp.int32),
                (live & ~take_fresh).astype(jnp.int32),
                fwd.astype(jnp.int32),
                (disc_evt | ovf_evt).astype(jnp.int32),
                admitted.astype(jnp.int32)])
            state = state._replace(
                tel_counts=state.tel_counts.at[cur, b].add(kinds),
                tel_occ=state.tel_occ.at[b].max(state.ev_n))
    return state, None


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("policy", "max_forwards", "discard_on_exhaust",
                              "capacity", "depth", "use_network",
                              "max_events", "event_buf", "tel_buckets",
                              "tel_horizon"))
def _simulate(reqs: RequestArrays, topo: TopologyArrays, params: SimParams,
              targets: jnp.ndarray, net: Optional[NetParams] = None, *,
              policy: str, max_forwards: int, discard_on_exhaust: bool,
              capacity: int, depth: int, use_network: bool = False,
              max_events: Optional[int] = None,
              event_buf: Optional[int] = None,
              tel_buckets: Optional[int] = None,
              tel_horizon: Optional[float] = None) -> FleetMetrics:
    R = reqs.arrival.shape[0]
    K = topo.speeds.shape[0]
    N = capacity
    dt = reqs.arrival.dtype
    E = event_bound(R, max_forwards) if max_events is None else max_events
    B = min(R, 1024) if event_buf is None else event_buf
    if max_forwards >= (1 << 8):     # packed reqinfo holds hops in 8 bits
        raise ValueError("max_forwards must be < 256 (packed terminal "
                         f"record), got {max_forwards}")
    hop_bits = max(max_forwards + 1, 2).bit_length()
    with jax.named_scope("fleetsim.pack"):
        state = EventState(
            starts=jnp.full((K, N), jq.BIG, dt),
            ends=jnp.full((K, N), jq.BIG, dt),
            sizes=jnp.zeros((K, N), dt),
            slot_rid=jnp.zeros((K, N), jnp.int32),
            head=jnp.zeros((K,), jnp.int32),
            nq=jnp.zeros((K,), jnp.int32),
            busy=jnp.zeros((K,), dt),
            load=jnp.zeros((K,), dt),
            rr=jnp.zeros((), jnp.int32),
            cursor=jnp.zeros((), jnp.int32),
            ev_time=jnp.full((B,), jq.BIG, dt),
            ev_rid=jnp.zeros((B,), jnp.int32),
            ev_meta=jnp.zeros((B,), jnp.int32),
            ev_n=jnp.zeros((), jnp.int32),
            ev_dropped=jnp.zeros((), jnp.int32),
            sat_events=jnp.zeros((), jnp.int32),
            completion=jnp.zeros((R,), dt),
            reqinfo=jnp.zeros((R,), jnp.int32),
            transfer=jnp.zeros((R,), dt),
        )
        tel_width = None
        if tel_buckets is not None:
            if tel_horizon is None:
                raise ValueError("telemetry needs a horizon "
                                 "(TelemetryConfig carries both; got "
                                 "tel_buckets without tel_horizon)")
            # the shared bucket contract (DESIGN.md §8): width computed
            # ONCE in f32 on the host so both engines bin with
            # bit-identical arithmetic
            tel_width = jnp.asarray(bucket_width(tel_horizon, tel_buckets),
                                    dt)
            tel_counts0, tel_occ0 = telemetry_init(K, tel_buckets)
            state = state._replace(tel_counts=tel_counts0, tel_occ=tel_occ0)
        key = jax.random.PRNGKey(params.seed)
        d_abs = reqs.arrival + reqs.rel_deadline * params.sla_scale
        payload = (reqs.payload if reqs.payload is not None
                   else jnp.zeros_like(reqs.arrival))
        # per-request constants packed into row matrices: one gather per
        # candidate per step instead of five (origin rides as f32 — exact
        # for any node id below 2^24)
        fresh_cols = jnp.stack([reqs.arrival, reqs.origin.astype(dt),
                                d_abs, reqs.proc, payload], axis=1)
        rear_cols = jnp.stack([d_abs, reqs.proc, payload], axis=1)
        step = functools.partial(
            _estep, topo=topo, key=key, policy=policy,
            max_forwards=max_forwards, discard_on_exhaust=discard_on_exhaust,
            capacity=capacity, depth=depth, R=R,
            use_network=use_network, net=net, fresh_cols=fresh_cols,
            rear_cols=rear_cols, targets=targets,
            zero_net=jnp.zeros((K, K), dt), hop_bits=hop_bits,
            tel_buckets=tel_buckets, tel_width=tel_width)
    with jax.named_scope("fleetsim.scan"):
        # E is a cap: the scan runs its E % SCAN_CHUNK odd steps, then
        # whole chunks while an event is left, so it ends within a chunk
        # of its last event.  Steps past it are dead (every flag False,
        # the retire at +BIG is the drain's own pop chain) and the loop
        # test runs once a chunk, which keeps a vmapped sweep's per-point
        # carry selects off the step
        def steps(s, n):
            return jax.lax.scan(step, s, None, length=n)[0]

        if E % SCAN_CHUNK:
            state = steps(state, E % SCAN_CHUNK)

        def events_left(c):
            n, s = c
            return (n < E) & ((s.cursor < R) | (s.ev_n > 0))

        def chunk(c):
            return c[0] + SCAN_CHUNK, steps(c[1], SCAN_CHUNK)

        scan_steps, state = jax.lax.while_loop(
            events_left, chunk, (jnp.int32(E % SCAN_CHUNK), state))
    with jax.named_scope("fleetsim.unpack"):
        unprocessed = (R - state.cursor) + state.ev_n
    with jax.named_scope("fleetsim.drain"):
        state = _retire(state, jnp.asarray(jnp.inf, dt), R)

    # unpack the per-request terminal records
    with jax.named_scope("fleetsim.unpack"):
        info = state.reqinfo
        nfwd = info & ((1 << 8) - 1)
        disc = (info & _INFO_DISC) != 0
        ovf = (info & _INFO_OVF) != 0
        served_by = (info >> _INFO_SERVED) - 1
        completion = state.completion
        has_c = completion > 0
        met = has_c & (completion <= d_abs + _MET_EPS)
        outcome = jnp.where(
            disc, DISCARDED,
            jnp.where(ovf, OVERFLOW,
                      jnp.where(met, MET, jnp.where(has_c, LATE, PENDING))))
        n_proc = jnp.sum(has_c)
        resp = jnp.sum(jnp.where(has_c, completion - reqs.arrival, 0.0))
        last_arrival = jnp.max(reqs.arrival, initial=0.0)
        end_time = jnp.maximum(jnp.max(completion, initial=0.0),
                               last_arrival)
    telemetry = None
    if tel_buckets is not None:
        # the derived half: queue depth and CPU busy time need no scan
        # carry at all — every served request's ledger interval
        # [admit, start) and service interval [start, completion) is
        # reconstructible from the terminal arrays, so the integrals are
        # two post-scan scatter-adds over the request axis
        with jax.named_scope("fleetsim.telemetry"):
            served = served_by >= 0
            ps_served = reqs.proc / topo.speeds[jnp.clip(served_by, 0,
                                                         K - 1)]
            admit_t = reqs.arrival + state.transfer
            start_t = completion - ps_served
            depth = interval_histogram(admit_t, start_t, served_by, served,
                                       K, tel_width, tel_buckets)
            busy = interval_histogram(start_t, completion, served_by,
                                      served, K, tel_width, tel_buckets)
            telemetry = TelemetryFrame(
                counts=state.tel_counts,
                queue_depth=depth / tel_width,
                busy_time=busy,
                occupancy_hwm=state.tel_occ,
                bucket_width=tel_width)
    with jax.named_scope("fleetsim.unpack"):
        return FleetMetrics(
            total=jnp.int32(R),
            processed=n_proc.astype(jnp.int32),
            met_deadline=jnp.sum(met).astype(jnp.int32),
            forwards=jnp.sum(nfwd).astype(jnp.int32),
            discarded=jnp.sum(disc).astype(jnp.int32),
            overflow=jnp.sum(ovf).astype(jnp.int32),
            window_saturation=state.sat_events,
            mean_response_time=resp / jnp.maximum(1, n_proc),
            end_time=end_time,
            outcome=outcome,
            completion=completion,
            served_by=served_by,
            forwards_used=nfwd,
            transfer_time=jnp.sum(state.transfer),
            transfer_used=state.transfer,
            event_overflow=(state.ev_dropped + unprocessed).astype(jnp.int32),
            scan_steps=scan_steps,
            telemetry=telemetry,
        )


def simulate(reqs: RequestArrays, topo: TopologyArrays,
             params: Optional[SimParams] = None, *, policy: str = "random",
             max_forwards: int = 2, discard_on_exhaust: bool = False,
             capacity: int = 256, depth: Optional[int] = None,
             targets: Optional[jnp.ndarray] = None,
             net: Optional[NetParams] = None,
             max_events: Optional[int] = None,
             event_buf: Optional[int] = None,
             telemetry: Optional[TelemetryConfig] = None) -> FleetMetrics:
    """Run the full fleet simulation as one device call.

    ``reqs``/``topo`` come from :mod:`repro.fleetsim.arrays` (or
    ``Workload.to_arrays()``); ``params`` carries the traced sweep axes.
    For (seeds x thresholds) sweeps, vmap :func:`simulate_fn` — every array
    argument takes a leading batch dimension, nothing leaves the device
    between sweep points.  ``capacity`` is the per-node slot-buffer width;
    each block occupies one slot for the whole run (head-pointer rows), so
    size it at the node's total admission count, not its peak depth.
    ``depth`` (default ``capacity``) is the live-window width the per-step
    math runs over — size it at peak queue depth + slack; smaller depth =
    faster steps.  ``max_events`` caps the scan length (default
    ``R * (max_forwards + 1)``, the exact worst case — every request
    forwarded to exhaustion); the scan stops within ``SCAN_CHUNK`` steps
    of its last event whatever the cap, and ``metrics.scan_steps`` says
    how many it ran.  ``event_buf`` sizes the in-flight re-arrival
    buffer (default ``min(R, 1024)``).  Undersizing any of the four is
    never silent: a forced push that finds no free slot is reported in
    ``metrics.overflow``, a request that merely *consulted* a node with
    an exhausted window counts into ``metrics.window_saturation``, and a
    re-arrival that could not be buffered or processed counts into
    ``metrics.event_overflow`` — size so all three stay 0.  ``targets``
    replays recorded forwarding choices (policy="trace", shape
    (R, max_forwards)).

    ``net`` (a :class:`repro.netsim.NetParams`) prices every referral
    hop: the wire time ``latency[u, v] + payload · inv_bw[u, v]`` defers
    the request's re-arrival event to ``t + transfer_delay`` while its
    absolute deadline stays put, consuming admission slack (DESIGN.md
    §6).  Outcomes are exact against the event-heap Orchestrator under
    any pricing — the event-time scan replays the heap's interleaving of
    arrivals, completions and referrals event for event (DESIGN.md §7).
    ``net=None`` prices every hop 0.0 through the same machinery, and
    ``NetParams.zero`` reproduces its outcomes bit-for-bit
    (equivalence-guarded).

    ``telemetry`` (a :class:`repro.telemetry.TelemetryConfig`) turns on
    the device-side time series: ``metrics.telemetry`` becomes a
    :class:`~repro.telemetry.TelemetryFrame` binning the run into
    ``n_buckets`` buckets over ``[0, horizon)`` — per-node event-kind
    counters, time-averaged queue depth, CPU busy time, and the
    re-arrival buffer's occupancy high-water mark (DESIGN.md §8).  The
    frame costs two extra scan carries; with ``telemetry=None`` (the
    default) those carries are ``None`` pytree leaves that compile out
    entirely — the hot path is bit-identical to a build without
    telemetry.  Both config fields are static: each (n_buckets, horizon)
    pair compiles once.
    """
    if policy not in POLICIES:
        raise ValueError(f"unknown fleetsim policy {policy!r}; "
                         f"options: {sorted(POLICIES)}")
    params = params if params is not None else SimParams.make()
    reqs = RequestArrays(
        *(jnp.asarray(a) for a in reqs[:5]),
        payload=None if reqs.payload is None else jnp.asarray(reqs.payload))
    topo = TopologyArrays(*(jnp.asarray(a) for a in topo))
    if targets is None:
        targets = jnp.full((reqs.arrival.shape[0], max(max_forwards, 1)),
                           -1, jnp.int32)
    depth = capacity if depth is None else min(depth, capacity)
    use_network = net is not None
    if use_network:
        if reqs.payload is None:
            # never silently drop the serialization half of the wire cost
            raise ValueError(
                "net= requires RequestArrays.payload (use pack_requests / "
                "Workload.to_arrays, or pass payload=zeros explicitly for "
                "a latency-only network)")
        net = NetParams(*(jnp.asarray(a, jnp.float32) for a in net))
    tel_buckets = tel_horizon = None
    if telemetry is not None:
        tel_buckets = int(telemetry.n_buckets)
        tel_horizon = float(telemetry.horizon)
        if tel_buckets < 1 or not tel_horizon > 0:
            raise ValueError(f"telemetry needs n_buckets >= 1 and a "
                             f"positive horizon, got {telemetry}")
    return _simulate(reqs, topo, params, jnp.asarray(targets, jnp.int32),
                     net, policy=policy, max_forwards=max_forwards,
                     discard_on_exhaust=discard_on_exhaust,
                     capacity=capacity, depth=depth,
                     use_network=use_network, max_events=max_events,
                     event_buf=event_buf, tel_buckets=tel_buckets,
                     tel_horizon=tel_horizon)


def simulate_fn(*, policy: str = "random", max_forwards: int = 2,
                discard_on_exhaust: bool = False, capacity: int = 256,
                depth: Optional[int] = None, network: bool = False,
                max_events: Optional[int] = None,
                event_buf: Optional[int] = None,
                telemetry: Optional[TelemetryConfig] = None):
    """The jitted simulator with statics bound — the thing to ``jax.vmap``.

    Signature of the returned function:
    ``(reqs: RequestArrays, topo: TopologyArrays, params: SimParams,
    targets: (R, max_forwards) i32) -> FleetMetrics``; map any subset of
    arguments, e.g.::

        run = fleetsim.simulate_fn(policy="least_loaded")
        sweep = jax.vmap(run, in_axes=(None, None, SimParams(0, None), None))
        metrics = sweep(reqs, topo, SimParams.make(jnp.arange(32), 1.0), tgt)

    With ``network=True`` the returned function takes a fifth argument —
    a :class:`repro.netsim.NetParams` — making the network itself a sweep
    axis::

        run = fleetsim.simulate_fn(policy="least_loaded", network=True)
        grid = jax.vmap(run, in_axes=(None, None, None, None, 0))
        metrics = grid(reqs, topo, params, tgt, stacked_net_params)

    ``max_events``/``event_buf`` cap the event-time scan and size its
    re-arrival buffer (see :func:`simulate`; defaults: the exact
    worst-case scan bound, and a ``min(R, 1024)``-slot buffer).  Under
    vmap the scan runs until the point with the most events is done, and
    ``metrics.scan_steps`` holds each point's own count.  A sweep's
    sizing must cover its heaviest cell — undersizing surfaces in
    ``metrics.event_overflow``, never silently, so check it across the
    whole sweep.

    ``telemetry=TelemetryConfig(nb, horizon)`` threads the device time
    series through every mapped cell: under vmap the returned
    ``metrics.telemetry`` is a *stacked* frame — counts of shape
    ``(sweep, K, nb, N_KINDS)`` and so on — one telemetry cube per sweep
    point from a single device call (see :func:`simulate`).
    """
    tel_buckets = tel_horizon = None
    if telemetry is not None:
        tel_buckets = int(telemetry.n_buckets)
        tel_horizon = float(telemetry.horizon)
    return functools.partial(
        _simulate, policy=policy, max_forwards=max_forwards,
        discard_on_exhaust=discard_on_exhaust, capacity=capacity,
        depth=capacity if depth is None else min(depth, capacity),
        use_network=network, max_events=max_events,
        event_buf=event_buf, tel_buckets=tel_buckets,
        tel_horizon=tel_horizon)
