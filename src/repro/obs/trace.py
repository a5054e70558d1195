"""Span tracer exporting Chrome trace-event JSON (Perfetto-loadable).

Design points:

* **Near-zero cost when disabled.** The engine holds ``NULL_TRACER`` by
  default; its ``span`` returns one shared null context and every other
  call site guards with ``if tracer.enabled`` (one attribute load), so
  the disabled path does no formatting, no clock reads, no allocation.
* **One clock with the profiler.** A live :meth:`Tracer.span` is also a
  ``jax.profiler.TraceAnnotation`` (its args become the event's stats):
  free while no profiler session runs, and inside a session it lands in
  the ``.xplane.pb`` host plane on the device trace's clock, so an idle
  gap of the chip can be put down to the span the host was in.
* **Flight recorder.** Events live in a bounded ``deque(maxlen=ring)``
  — the newest ``ring`` events double as the crash ring buffer. The
  engine calls :meth:`Tracer.flight_dump` from its exception path so a
  stuck or crashing run leaves a postmortem trace on disk.
* **One anchor.** Tracer time is ``perf_counter`` seconds since the
  first reading the tracer saw, for its whole life: a tracer shared by
  several engine runs or federated rounds writes them one after
  another.  Spans known only afterwards (the per-request lifecycle,
  whose spans overlap across requests) go through :meth:`complete` with
  explicit tracer-time timestamps.
* **Garbage collection.** An enabled tracer records each collection of
  Python's garbage collector as a ``gc`` span (pid 1, tid 1) until
  :meth:`Tracer.close`.  A collection can start at any allocation, so
  read ``events`` after ``close()`` or through ``list(tracer.events)``:
  a loop that allocates while it walks the live ring can see it grow.

Track convention (pid/tid): pid 1 = the engine loop (tid 0) and the
garbage collector (tid 1), pid 2 = one thread per request rid, pid 3 =
federated rounds. Metadata events (``ph: "M"``) name the tracks; they
are kept out of the ring so names survive arbitrarily long runs.
"""
from __future__ import annotations

import gc
import json
import time
import weakref
from collections import deque
from functools import partial
from typing import Dict, List, Optional, Tuple

PID_ENGINE = 1
PID_REQUESTS = 2
PID_FEDERATED = 3
TID_GC = 1


class _Span:
    """A live span: a profiler annotation, and a ring event at exit."""

    __slots__ = ("tr", "name", "pid", "tid", "cat", "args", "ann", "t0")

    def __init__(self, tr: "Tracer", name: str, pid: int, tid: int,
                 cat: str, args: Optional[dict]) -> None:
        self.tr, self.name, self.pid, self.tid = tr, name, pid, tid
        self.cat, self.args = cat, dict(args) if args else {}

    # Each clock reading sits next to the annotation's own (it starts on
    # construction and stops in __exit__) with no allocation between, so
    # a garbage collection cannot fall inside one duration and not the
    # other.
    def __enter__(self) -> "_Span":
        self.ann = self.tr._annotation(self.name, **self.args)
        self.t0 = self.tr.now()
        return self

    def set(self, **args) -> None:
        """Args known only once the span's work is done."""
        self.args.update(args)
        self.ann.set_metadata(**args)

    def __exit__(self, *exc) -> None:
        self.ann.__exit__(*exc)
        t1 = self.tr.now()
        self.tr.complete(self.name, self.t0, t1, pid=self.pid, tid=self.tid,
                         cat=self.cat, args=self.args)


class _NullSpan:
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def set(self, **args) -> None:
        pass

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


def _on_gc(ref, phase: str, info: dict) -> None:
    """``gc.callbacks`` hook; holds its tracer weakly."""
    tr = ref()
    if tr is None:
        return
    if phase == "start":
        tr._gc_span = _Span(tr, "gc", PID_ENGINE, TID_GC, "gc",
                            {"generation": info["generation"]}).__enter__()
    elif tr._gc_span is not None:
        tr._gc_span.set(collected=info["collected"])
        tr._gc_span.__exit__(None, None, None)
        tr._gc_span = None


class Tracer:
    enabled = True

    def __init__(self, ring: int = 65536,
                 flight_path: Optional[str] = None) -> None:
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self.events: deque = deque(maxlen=int(ring))
        self.dropped = 0          # events pushed out of the ring
        self.flight_path = flight_path
        self._meta: Dict[Tuple[int, Optional[int]], dict] = {}
        self._t0: Optional[float] = None   # perf_counter at tracer time 0
        self._gc_span: Optional[_Span] = None
        hook = partial(_on_gc, weakref.ref(self))
        gc.callbacks.append(hook)
        self._unhook = weakref.finalize(self, gc.callbacks.remove, hook)
        self.thread_name(PID_ENGINE, TID_GC, "python gc")

    def close(self) -> None:
        """Stop recording garbage collections (idempotent; also done when
        the tracer itself is collected)."""
        self._unhook()

    # -- clock ------------------------------------------------------------
    def at(self, perf_s: float) -> float:
        """Tracer time of a ``perf_counter`` reading; the first reading
        the tracer sees is its 0."""
        if self._t0 is None:
            self._t0 = perf_s
        return perf_s - self._t0

    def now(self) -> float:
        return self.at(time.perf_counter())

    # -- event emission (timestamps in tracer seconds) --------------------
    def _push(self, ev: dict) -> None:
        if len(self.events) == self.events.maxlen:
            self.dropped += 1
        self.events.append(ev)

    def complete(self, name: str, start_s: float, end_s: float, *,
                 pid: int = PID_ENGINE, tid: int = 0, cat: str = "",
                 args: Optional[dict] = None) -> None:
        """A ``ph: "X"`` complete event spanning [start_s, end_s]."""
        ev = {"name": name, "ph": "X", "ts": start_s * 1e6,
              "dur": max(0.0, (end_s - start_s) * 1e6),
              "pid": pid, "tid": tid}
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = args
        self._push(ev)

    def instant(self, name: str, t_s: float, *, pid: int = PID_ENGINE,
                tid: int = 0, cat: str = "",
                args: Optional[dict] = None) -> None:
        ev = {"name": name, "ph": "i", "ts": t_s * 1e6, "s": "t",
              "pid": pid, "tid": tid}
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = args
        self._push(ev)

    def counter(self, name: str, t_s: float, values: Dict[str, float], *,
                pid: int = PID_ENGINE, tid: int = 0) -> None:
        """A ``ph: "C"`` counter sample — renders as a track in Perfetto
        (queue depth, free blocks, active slots over time)."""
        self._push({"name": name, "ph": "C", "ts": t_s * 1e6,
                    "pid": pid, "tid": tid,
                    "args": {k: float(v) for k, v in values.items()}})

    def span(self, name: str, *, pid: int = PID_ENGINE, tid: int = 0,
             cat: str = "", args: Optional[dict] = None) -> _Span:
        """A live span, opened and closed as a context manager: a
        ``jax.profiler.TraceAnnotation`` while open, a ``ph: "X"`` event
        in the ring once closed.  ``set(**args)`` on the entered span adds
        args known only at its end."""
        return _Span(self, name, pid, tid, cat, args)

    # -- track naming -----------------------------------------------------
    def process_name(self, pid: int, name: str) -> None:
        self._meta[(pid, None)] = {
            "name": "process_name", "ph": "M", "ts": 0, "pid": pid,
            "tid": 0, "args": {"name": name}}

    def thread_name(self, pid: int, tid: int, name: str) -> None:
        self._meta[(pid, tid)] = {
            "name": "thread_name", "ph": "M", "ts": 0, "pid": pid,
            "tid": tid, "args": {"name": name}}

    # -- export -----------------------------------------------------------
    def to_dict(self) -> dict:
        events = list(self._meta.values()) + sorted(
            self.events, key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def dump(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, allow_nan=False)
        return path

    def flight_dump(self) -> Optional[str]:
        """Write the ring buffer to ``flight_path`` (postmortem). Returns
        the path written, or None when no flight path is configured."""
        if not self.flight_path:
            return None
        return self.dump(self.flight_path)


class _NullTracer:
    """Disabled tracer: every method is a no-op and ``span`` returns one
    shared null context. Hot paths additionally guard on ``enabled`` so
    arguments are never even built."""

    enabled = False
    events: deque = deque(maxlen=0)
    dropped = 0
    flight_path = None

    def now(self) -> float:
        return 0.0

    def complete(self, *a, **kw) -> None:
        pass

    def instant(self, *a, **kw) -> None:
        pass

    def counter(self, *a, **kw) -> None:
        pass

    def span(self, *a, **kw) -> _NullSpan:
        return _NULL_SPAN

    def close(self) -> None:
        pass

    def process_name(self, *a, **kw) -> None:
        pass

    def thread_name(self, *a, **kw) -> None:
        pass

    def to_dict(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def dump(self, path: str) -> str:
        raise RuntimeError("cannot dump the null tracer")

    def flight_dump(self) -> Optional[str]:
        return None


NULL_TRACER = _NullTracer()

_REQUIRED = {"name", "ph", "ts", "pid", "tid"}


def validate_chrome_trace(trace: dict) -> List[str]:
    """Structural check of a Chrome trace-event dict: required fields on
    every event, numeric non-negative ts/dur, and — per (pid, tid) track
    — ``X`` spans that nest properly (no partial overlap). Returns a
    list of problems; empty means valid."""
    errors: List[str] = []
    if "traceEvents" not in trace or not isinstance(trace["traceEvents"],
                                                   list):
        return ["missing traceEvents list"]
    spans: Dict[Tuple[int, int], List[Tuple[float, float, str]]] = {}
    for i, ev in enumerate(trace["traceEvents"]):
        missing = _REQUIRED - set(ev)
        if missing:
            errors.append(f"event {i}: missing {sorted(missing)}")
            continue
        if ev["ph"] == "M":
            continue
        ts = ev["ts"]
        if not (isinstance(ts, (int, float)) and ts >= 0):
            errors.append(f"event {i} ({ev['name']}): bad ts {ts!r}")
            continue
        if ev["ph"] == "X":
            dur = ev.get("dur")
            if not (isinstance(dur, (int, float)) and dur >= 0):
                errors.append(f"event {i} ({ev['name']}): bad dur {dur!r}")
                continue
            spans.setdefault((ev["pid"], ev["tid"]), []).append(
                (ts, ts + dur, ev["name"]))
    eps = 1e-3  # µs slop for float rounding
    for track, ss in spans.items():
        ss.sort(key=lambda s: (s[0], -s[1]))
        stack: List[Tuple[float, float, str]] = []
        for s in ss:
            while stack and stack[-1][1] <= s[0] + eps:
                stack.pop()
            if stack and s[1] > stack[-1][1] + eps:
                errors.append(
                    f"track {track}: span {s[2]!r} [{s[0]:.1f},{s[1]:.1f}] "
                    f"partially overlaps {stack[-1][2]!r} "
                    f"[{stack[-1][0]:.1f},{stack[-1][1]:.1f}]")
            stack.append(s)
    return errors
