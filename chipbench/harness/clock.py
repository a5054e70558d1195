"""Compile accounting and host spans.

``CompileClock`` follows chip_smoke.py's: JAX's own monitoring events give
the seconds spent in XLA's backend compiler (persistent-cache reads
included) and the number of programs compiled or loaded, so the harness
can count compiles inside the measured window — there should be none.
"""
from __future__ import annotations

import contextlib
import os
import time


def process_start_s() -> float:
    """Seconds between this process's start and now, from /proc (the
    whole set-up, interpreter start-up included)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.process_time()


class CompileClock:
    def __init__(self, jax):
        self.seconds, self.programs, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class Spans:
    """The harness's own host spans around its calls into each layer.
    With ``traced`` they also go into the profiler's trace
    (``jax.profiler.TraceAnnotation``), on the device trace's clock."""

    def __init__(self, jax, traced: bool):
        self._ann = jax.profiler.TraceAnnotation if traced else None

    def __call__(self, name: str):
        if self._ann is None:
            return contextlib.nullcontext()
        return self._ann(name)
