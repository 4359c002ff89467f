"""Reduction of a JAX profiler trace to device busy time, idle share, top
device operations and idle gaps by what the host was doing.

A trace is read into two lists of ``(name, start_ns, end_ns)`` events on
one clock: the device operations of each chip (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) and the host spans the harness wrote with
``jax.profiler.TraceAnnotation``. Everything after ``load`` works on those
lists, so it is tested on synthetic ones.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
WINDOW = "window"              # the host span that marks the measured window
OUTER = ("window", "serve_once")   # too coarse to name a gap alone
TOP = 10


@dataclass
class Trace:
    ops: dict[str, list[tuple[str, float, float]]] = field(default_factory=dict)
    host: list[tuple[str, float, float]] = field(default_factory=list)


def load(directory: str, host_names, device_plane: str = DEVICE_PLANE,
         op_line: str = OP_LINE) -> Trace:
    """Read the newest ``.xplane.pb`` under ``directory``. ``host_names``:
    the annotation names to keep from the host planes. Device operations
    are the events of lines whose name starts with ``op_line`` on planes
    whose name starts with ``device_plane`` (one chip per plane)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    keep = set(host_names)
    tr = Trace()
    seen = []
    for plane in pd.planes:
        seen.append((plane.name, [ln.name for ln in plane.lines]))
        for line in plane.lines:
            if plane.name.startswith(device_plane) and line.name.startswith(op_line):
                tr.ops.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                tr.host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events if e.name in keep)
    if not tr.ops:
        raise ValueError(f"no '{op_line}' line on a '{device_plane}*' plane; "
                         f"planes and lines: {seen}")
    return tr


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def leaves(ops):
    """The events that hold no other event: a ``while`` or ``call`` op
    spans the ops of its body on the same line, which would count twice."""
    ev = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for k, (n, s, e) in enumerate(ev):
        nxt = ev[k + 1] if k + 1 < len(ev) else None
        if nxt is None or nxt[1] >= e:
            out.append((n, s, e))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged, lo: float, hi: float) -> float:
    """Length of ``merged`` (disjoint intervals) inside ``[lo, hi]``."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that ``merged`` does not cover."""
    out, cur = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def spans(tr: Trace, name: str) -> list[tuple[float, float]]:
    return [(s, e) for n, s, e in tr.host if n == name]


def window(tr: Trace) -> tuple[float, float]:
    w = spans(tr, WINDOW)
    if len(w) != 1:
        raise ValueError(f"{len(w)} '{WINDOW}' spans in the trace, expected 1")
    return w[0]


class _Index:
    """Host spans merged per name, for the overlap of a name with a gap."""

    def __init__(self, tr: Trace) -> None:
        names = {n for n, _, _ in tr.host if n != WINDOW}
        self.by_name = {}
        for n in names:
            merged = union((s, e) for m, s, e in tr.host if m == n)
            self.by_name[n] = ([s for s, _ in merged], merged)

    def overlap(self, name: str, lo: float, hi: float) -> float:
        starts, merged = self.by_name[name]
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        total = 0.0
        while i < len(merged) and merged[i][0] < hi:
            total += max(0.0, min(merged[i][1], hi) - max(merged[i][0], lo))
            i += 1
        return total

    def label(self, lo: float, hi: float) -> str:
        """The host span that covers most of ``[lo, hi]``; a coarse span
        only where no finer one overlaps."""
        best, best_key = "unattributed", (0, 0.0)
        for n in self.by_name:
            ov = self.overlap(n, lo, hi)
            key = (int(n not in OUTER), ov)
            if ov > 0 and key > best_key:
                best, best_key = n, key
        return best


def reduce(tr: Trace, lo: float | None = None, hi: float | None = None) -> dict:
    """Busy seconds (averaged over chips), window seconds, idle share,
    the top device operations by total time and idle seconds by host
    span, over ``[lo, hi]`` (default: the ``window`` span)."""
    if lo is None:
        lo, hi = window(tr)
    busy, per_op, idle = [], defaultdict(float), defaultdict(float)
    index = _Index(tr)
    for ops in tr.ops.values():
        merged = union((s, e) for _, s, e in ops)
        busy.append(overlap(merged, lo, hi))
        for n, s, e in leaves(ops):
            per_op[op_name(n)] += max(0.0, min(e, hi) - max(s, lo)) * 1e-9 / len(tr.ops)
        for g0, g1 in gaps(merged, lo, hi):
            idle[index.label(g0, g1)] += (g1 - g0) * 1e-9 / len(tr.ops)
    busy_s = sum(busy) / len(busy) * 1e-9
    window_s = (hi - lo) * 1e-9
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "device_ops": top(per_op), "idle_gaps": top(idle)}


def idle_within(tr: Trace, name: str, lo: float, hi: float,
                containing: str | None = None) -> float | None:
    """Idle share of the device inside the union of host spans ``name``
    that lie in ``[lo, hi]`` (and hold a span ``containing``): time outside
    them (waiting for arrivals) is left out."""
    inner = spans(tr, containing) if containing else None
    inside = union((max(s, lo), min(e, hi)) for s, e in spans(tr, name)
                   if e > lo and s < hi
                   and (inner is None or any(s <= a and b <= e for a, b in inner)))
    total = sum(e - s for s, e in inside)
    if total <= 0:
        return None
    busy = []
    for ops in tr.ops.values():
        merged = union((s, e) for _, s, e in ops)
        busy.append(sum(overlap(merged, s, e) for s, e in inside))
    return 1.0 - sum(busy) / len(busy) / total
