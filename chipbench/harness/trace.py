"""Reduction of a JAX profiler trace to device time.

A trace is read with ``jax.profiler.ProfileData`` into plain event tuples
(name, start_ns, end_ns), all on one clock:

* ``ops``     per chip, the device's operations (plane ``/device:TPU:n``,
  line ``XLA Ops``), named by their HLO instruction (``fusion.3``,
  ``ragged_expert_matmul.2``: a Pallas kernel's instruction carries the
  kernel's name);
* ``modules`` per chip, the executions of whole compiled programs (line
  ``XLA Modules``), named after the jitted function (``jit__decode_fn``);
* ``host``    the harness's own spans (``TraceAnnotation`` names in
  ``SPAN_NAMES``) from the host threads.

Everything below is arithmetic on those tuples, so the CPU tests check it
on a recorded trace and on hand-built events.  The profiler's device and
host timelines agree to about a millisecond (the recorded test trace puts
device programs 1.0–1.3 ms before the host span that launched them), so a
share of a window of seconds is exact to a few parts in ten thousand, and
an idle gap's label to about a millisecond at each end.
"""
from __future__ import annotations

import glob
import os
from bisect import bisect_right
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def find_xplane(root: str) -> str:
    paths = sorted(glob.glob(os.path.join(root, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return paths[-1]


def _chip(plane_name: str) -> Optional[int]:
    head = "/device:TPU:"
    if not plane_name.startswith(head):
        return None
    tail = plane_name[len(head):]
    return int(tail) if tail.isdigit() else None


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def module_name(name: str) -> str:
    """``jit__decode_fn(1234)`` -> ``jit__decode_fn``."""
    return name.split("(", 1)[0]


def is_kernel(op: str, kernel: str) -> bool:
    """Whether instruction ``op`` is a call of Pallas kernel ``kernel``."""
    head, _, tail = op.partition(".")
    return head == kernel and (tail == "" or tail.isdigit())


def family(op: str) -> str:
    """An instruction's name without its number: ``fusion.3`` -> ``fusion``."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def load(path: str, span_names: Iterable[str]) -> Trace:
    from jax.profiler import ProfileData
    names = set(span_names)
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        chip = _chip(plane.name)
        for line in plane.lines:
            if chip is not None and line.name in (OPS_LINE, MODULES_LINE):
                ops = line.name == OPS_LINE
                evs = (tr.ops if ops else tr.modules).setdefault(chip, [])
                for e in line.events:
                    s = int(e.start_ns)
                    name = op_name(e.name) if ops else module_name(e.name)
                    evs.append((name, s, s + int(e.duration_ns)))
            elif chip is None and plane.name.startswith("/host"):
                for e in line.events:
                    if e.name in names:
                        s = int(e.start_ns)
                        tr.host.append((e.name, s, s + int(e.duration_ns)))
    for d in (tr.ops, tr.modules):
        for evs in d.values():
            evs.sort(key=lambda e: e[1])
    tr.host.sort(key=lambda e: e[1])
    return tr


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Tuple[int, int]], lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(ops: Sequence[Event], lo: int, hi: int) -> int:
    """Time in [lo, hi) in which some operation ran on the chip."""
    return sum(e - s for s, e in clip(union((s, e) for _, s, e in ops),
                                      lo, hi))


def gaps(ops: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The idle intervals of [lo, hi): no operation ran."""
    out, t = [], lo
    for s, e in clip(union((s, e) for _, s, e in ops), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def within(events: Sequence[Event], parents: Sequence[Event]
           ) -> List[List[Event]]:
    """For each parent interval, the events that start inside it (events
    and parents sorted by start)."""
    starts = [e[1] for e in events]
    return [list(events[bisect_right(starts, ps - 1):
                        bisect_right(starts, pe - 1)])
            for _, ps, pe in parents]


CONTAINERS = ("while", "conditional", "call")   # their bodies' ops are listed


def top_ops(ops: Sequence[Event], modules: Sequence[Event], lo: int,
            hi: int, n: int = 10):
    """The n kinds of operation with the most device time in [lo, hi),
    each named ``<program>:<instruction without its number>``.  Control
    flow (a scan's ``while``) spans the operations of its body, which the
    trace also lists, so it is left out rather than counted twice."""
    tot: Dict[str, int] = {}
    for (mod, _, _), evs in zip(modules, within(ops, modules)):
        for name, s, e in evs:
            if family(name) in CONTAINERS:
                continue
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = f"{mod}:{family(name)}"
                tot[key] = tot.get(key, 0) + e - s
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def timeline(host: Sequence[Event], outside: str
             ) -> List[Tuple[int, int, str]]:
    """Host spans (properly nested, as context managers on one thread
    make them) flattened to (start, end, innermost label) segments that
    tile the time they cover; uncovered time is ``outside``."""
    pts = []
    for name, s, e in host:
        pts.append((s, 1, -e, name))
        pts.append((e, 0, 0, name))
    pts.sort()
    out: List[Tuple[int, int, str]] = []
    stack: List[str] = []
    t = None
    for p, kind, _neg_e, name in pts:
        if t is not None and p > t:
            out.append((t, p, stack[-1] if stack else outside))
        t = p
        if kind == 1:
            stack.append(name)
        elif name in stack:
            stack.reverse()
            stack.remove(name)
            stack.reverse()
    return out


def idle_by_host(ops: Sequence[Event], host: Sequence[Event], lo: int,
                 hi: int, n: int = 10, outside: str = "no_span"):
    """Idle device time in [lo, hi) by what the host was doing: each idle
    nanosecond goes to the innermost harness span that covers it, or to
    ``outside``.  Sorted by time, the n largest."""
    tot: Dict[str, int] = {}
    segs = timeline(host, outside)
    j = 0
    for g0, g1 in gaps(ops, lo, hi):
        t = g0
        while j < len(segs) and segs[j][1] <= t:
            j += 1
        i = j
        while t < g1:
            if i < len(segs) and segs[i][0] <= t:
                b = min(g1, segs[i][1])
                label = segs[i][2]
                i += 1
            else:
                b = min(g1, segs[i][0]) if i < len(segs) else g1
                label = outside
            tot[label] = tot.get(label, 0) + b - t
            t = b
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]
