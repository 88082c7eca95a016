"""From a profiler trace to device busy time, per-scope time and idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
operations are the events of the ``XLA Ops`` lines of ``/device:*``
planes; where the trace has no device plane (the CPU backend), they are
the events that carry an ``hlo_op`` statistic.  Busy time is the union of
their intervals inside the harness's ``bench.window`` span, averaged over
devices; idle gaps are the stretches of that window in which no device
operation ran, each named by the innermost ``bench.*`` host span around
its middle.

An operation's scope is the innermost ``fleetsim.*`` or ``kernels.*``
name in its statistics: the TPU runtime records each op's name stack
(``tf_op``) in the op's event metadata, which ``ProfileData`` does not
show, so :func:`op_metadata` reads it from the protobuf itself.
Operations in no such scope, and every operation of a CPU trace, whose
ops carry no name stack, are the row ``unscoped``.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import warnings
from typing import Dict, List, Tuple

SCOPE_RE = re.compile(r"(?:fleetsim|kernels)\.[A-Za-z_]+")


def op_scope(stats: Dict[str, object]) -> str:
    """The innermost scope named in an op's string statistics."""
    scope = "unscoped"
    for v in stats.values():
        if isinstance(v, str):
            found = SCOPE_RE.findall(v)
            scope = found[-1] if found else scope
    return scope


def _stats(ev) -> Dict[str, object]:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        try:
            return dict(ev.stats)
        except (TypeError, ValueError):
            return {}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def self_times(ops: List[Tuple[float, float, str]]) -> collections.Counter:
    """Each scope's self time on one line of device ops: an op's duration
    less the part of it that ops nested inside it cover (a loop or call
    op and the ops of its body are both on the line)."""
    out: collections.Counter = collections.Counter()
    stack: List[List] = []            # open ops: [end, scope, self time]
    for s, e, sc in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            _, psc, own = stack.pop()
            out[psc] += own
        if stack:                     # nested: not the parent's own time
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, sc, e - s])
    for _, psc, own in stack:
        out[psc] += own
    return out


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Dict[int, List]:
    """A protobuf message's fields by number, each a list of values: an
    int for a varint, a ``memoryview`` for a length-delimited field."""
    out: Dict[int, List] = {}
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        out.setdefault(key >> 3, []).append(v)
    return out


def _text(v) -> str:
    return bytes(v).decode("utf-8", errors="replace")


def op_metadata(path: str) -> Dict[str, Dict[str, Dict[str, str]]]:
    """Each device plane's ops by name, with the string statistics of
    their event metadata, read from the ``XSpace`` protobuf (fields of
    ``tsl/profiler/protobuf/xplane.proto``; lines are skipped whole)."""
    with open(path, "rb") as f:
        space = _fields(memoryview(f.read()))
    out: Dict[str, Dict[str, Dict[str, str]]] = {}
    for plane in map(_fields, space.get(1, [])):        # XSpace.planes
        name = _text(plane.get(2, [b""])[0])
        if not name.startswith("/device:"):
            continue
        stat_name = {}                                    # XPlane.stat_metadata
        for entry in map(_fields, plane.get(5, [])):
            sm = _fields(entry.get(2, [b""])[0])
            stat_name[sm.get(1, [0])[0]] = _text(sm.get(2, [b""])[0])
        ops = out.setdefault(name, {})
        for entry in map(_fields, plane.get(4, [])):     # XPlane.event_metadata
            md = _fields(entry.get(2, [b""])[0])
            stats = ops.setdefault(_text(md.get(2, [b""])[0]), {})
            for st in map(_fields, md.get(5, [])):       # XStat
                if 5 in st:                               # str_value
                    value = _text(st[5][0])
                elif 7 in st:                             # ref_value
                    value = stat_name.get(st[7][0], "")
                else:
                    continue
                stats[stat_name.get(st.get(1, [0])[0], "")] = value
    return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_trace(path: str, window_span: str = "bench.window",
                 top: int = 10) -> dict:
    """Reduce one trace file.

    Returns ``busy_s`` (union of device-op time in the window, averaged
    over devices), ``window_s``, ``scope_s`` (device seconds per scope),
    ``n_ops`` and ``breakdown`` (the ``top`` scopes by device time and
    the ``top`` host spans by idle time inside the window)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    meta = op_metadata(path)
    dev_ops: Dict[str, List[Tuple[float, float, str]]] = {}
    host_spans: List[Tuple[float, float, str]] = []
    cpu_ops: List[Tuple[float, float, str]] = []
    scope_of: Dict[Tuple[str, str], str] = {}   # once per plane and op name
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                s, d = float(ev.start_ns), float(ev.duration_ns)
                if is_dev:
                    if line.name == "XLA Ops":
                        key = (plane.name, ev.name)
                        scope = scope_of.get(key)
                        if scope is None:
                            scope = scope_of[key] = op_scope(dict(
                                meta.get(plane.name, {}).get(ev.name, {}),
                                **_stats(ev)))
                        dev_ops.setdefault(plane.name, []).append(
                            (s, s + d, scope))
                    continue
                if ev.name.startswith("bench."):
                    host_spans.append((s, s + d, ev.name))
                    continue
                st = _stats(ev)
                if isinstance(st.get("hlo_op"), str):
                    cpu_ops.append((s, s + d, op_scope(st)))
    if not dev_ops and cpu_ops:
        dev_ops = {"cpu": cpu_ops}
    win = [sp for sp in host_spans if sp[2] == window_span]
    if win:
        w0, w1 = min(w[0] for w in win), max(w[1] for w in win)
    else:
        every = [op for ops in dev_ops.values() for op in ops]
        w0 = min((o[0] for o in every), default=0.0)
        w1 = max((o[1] for o in every), default=0.0)
    window_ns = max(w1 - w0, 0.0)

    busy_ns, scope_ns, gaps = [], collections.Counter(), []
    n_ops = 0
    for ops in dev_ops.values():
        clipped = [(max(s, w0), min(e, w1), sc) for s, e, sc in ops
                   if e > w0 and s < w1]
        n_ops += len(clipped)
        scope_ns.update(self_times(clipped))
        merged = _union([(s, e) for s, e, _ in clipped])
        busy_ns.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(len(dev_ops), 1)

    # name each gap by the shortest host span around its middle
    gaps.sort()
    mids = [(s + e) / 2 for s, e in gaps]
    owner = [(float("inf"), "no bench span")] * len(gaps)
    for s, e, name in host_spans:
        if name == window_span:
            continue
        for i in range(bisect.bisect_left(mids, s),
                       bisect.bisect_right(mids, e)):
            if e - s < owner[i][0]:
                owner[i] = (e - s, name)
    idle = collections.Counter()
    for (s, e), (_, name) in zip(gaps, owner):
        idle[name] += (e - s) / n_dev
    return dict(
        busy_s=sum(busy_ns) / n_dev / 1e9,
        window_s=window_ns / 1e9,
        scope_s={k: v / n_dev / 1e9 for k, v in scope_ns.items()},
        n_ops=n_ops,
        breakdown=dict(
            device_ops=[[k, v / n_dev / 1e9]
                        for k, v in scope_ns.most_common(top)],
            idle_gaps=[[k, v / 1e9] for k, v in idle.most_common(top)]))
