#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Run from the root of a checkout.  Everything about the cell is data:
``BENCHMARK.json`` names the cell's configuration and traffic mix, found
as ``chipbench/configs/<config>.json`` and ``chipbench/traffic/<traffic>.json``;
``chipbench/cells/<workload>.json`` holds the cell's correctness limits;
every metric is read by ``chipbench/metrics/<metric>.py``.  The traffic
file names the runner (``chipbench/harness/<runner>.py``) that builds the
program, warms it, runs the window and checks the results.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the JAX profiler and they are the
cell's per-layer metrics, with ``device.busy_s``/``window_s`` and a
``breakdown``.  The last line of standard output is the JSON result; the
last lines of standard error list each number compared with its limit.
Exits 1, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

from harness import chip, clock, model_cfg, trace as trace_mod  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
OPS = {"<=": lambda v, lim: v <= lim, ">=": lambda v, lim: v >= lim,
       "==": lambda v, lim: v == lim}


def load_bench(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_metrics(bench: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    e2e_here = {m["name"] for m in bench["end_to_end"]
                if workload in m.get("workloads", [workload])}
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e_here:
            out.append(m)
    return out


def reader(name: str):
    """The reader of metric ``name``: ``chipbench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a runner and the metric readers share about one run."""

    def __init__(self, args, entry, config, traffic, cell, jax, devs,
                 peaks):
        import jax.numpy as jnp
        self.args, self.entry = args, entry
        self.jax, self.jnp, self.devs = jax, jnp, devs
        self.seed, self.seconds = args.seed, args.seconds
        self.traced = bool(args.trace)
        self.control = bool(getattr(args, "control", False))
        self.config, self.traffic, self.cell = config, traffic, cell
        self.sizes = model_cfg.sizes(config)
        self.n_params = model_cfg.count_params(self.sizes)
        self.peaks = peaks
        self.clock = clock.CompileClock(jax)
        self.spans = clock.Spans(jax, self.traced)
        self.result = {"correct": None, "attempted": 0, "failed": 0}
        self.checks: dict = {}
        self.data: dict = {}
        self.device: dict = {}
        self.trace = None
        self.trace_dir = None

    @classmethod
    def from_files(cls, args, bench, entry, jax, devs):
        conf = next(c for c in bench["configs"]
                    if c["name"] == entry["config"])
        config = model_cfg.load(ROOT / conf["file"])
        traffic = json.loads(
            (BENCH / "traffic" / f"{entry['traffic']}.json").read_text())
        cell = json.loads(
            (BENCH / "cells" / f"{entry['name']}.json").read_text())
        return cls(args, entry, config, traffic, cell, jax, devs,
                   chip.peaks(devs[0].device_kind))

    # ---- the measured window
    def window(self, call):
        """Whole calls of ``call`` until ``--seconds`` have passed; the
        window is their span.  Returns the calls' results."""
        jax = self.jax
        if self.traced:
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # no per-call Python events
            opts.host_tracer_level = 1         # the harness's spans only
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        out = []
        c0 = self.clock.programs
        self.setup_s = clock.process_start_s()
        t0 = time.perf_counter()
        with self.spans("window"):
            while True:
                with self.spans("call"):
                    out.append(call())
                if time.perf_counter() - t0 >= self.seconds:
                    break
        self.window_s = time.perf_counter() - t0
        self.window_compiles = self.clock.programs - c0
        if self.traced:
            jax.profiler.stop_trace()
        chip.log(f"window: {len(out)} calls in {self.window_s} s, "
                 f"{self.window_compiles} programs compiled or loaded "
                 f"inside it; set-up {self.setup_s} s")
        return out

    def device_record(self):
        self.device = chip.device_record(self.devs)

    def check(self, name, value, limit, op):
        self.checks[name] = compare(value, limit, op)

    @staticmethod
    def judge(numbers: dict) -> dict:
        """``{name: (value, limit, op)}`` compared as ``check`` compares:
        the checks and whether all passed, under ``correct``."""
        checks = {n: compare(*v) for n, v in numbers.items()}
        return {"correct": all(c["ok"] for c in checks.values()),
                "checks": checks}


def compare(value, limit, op) -> dict:
    return {"value": value, "limit": limit, "op": op,
            "ok": bool(OPS[op](value, limit))}


def reduce_trace(ctx, spans):
    path = trace_mod.find_xplane(ctx.trace_dir)
    tr = trace_mod.load(path, spans)
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    win = [e for e in tr.host if e[0] == "window"]
    if not win:
        raise RuntimeError("the trace holds no window span")
    lo, hi = win[0][1], win[0][2]
    chips = sorted(tr.ops)
    if not chips:
        raise RuntimeError("the trace holds no device operations")
    busy = [trace_mod.busy_ns(tr.ops[c], lo, hi) for c in chips]
    ctx.trace, ctx.trace_lo, ctx.trace_hi = tr, lo, hi
    ctx.device["busy_s"] = sum(busy) / len(busy) / 1e9
    ctx.device["window_s"] = (hi - lo) / 1e9
    c0 = chips[0]
    ctx.breakdown = {
        "device_ops": [[n, t / 1e9] for n, t in
                       trace_mod.top_ops(tr.ops[c0], tr.modules[c0], lo,
                                         hi)],
        "idle_gaps": [[n, t / 1e9] for n, t in trace_mod.idle_by_host(
            tr.ops[c0], tr.host, lo, hi, outside="harness")],
    }


def import_jax():
    """JAX, with its persistent compile cache at the checkout's fixed
    ``.jax_cache`` (the program's own default there too), small programs
    included."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_bench()
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        chip.log(f"unknown workload {args.workload!r}")
        return 2

    jax = import_jax()
    try:
        devs = chip.require_chips(jax, int(entry["chips"]))
    except chip.NoChip as e:
        chip.log(f"chipbench: {e} - nothing run")
        return 1
    chip.log(f"device: {devs[0].platform} {devs[0].device_kind} "
             f"x{len(devs)}; workload {args.workload}, seed {args.seed}; "
             f"{clock.process_start_s():.1f} s since process start")

    ctx = Context.from_files(args, bench, entry, jax, devs)
    result = run_cell(ctx, bench)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(ctx, bench) -> dict:
    """Drive the cell, read its metrics and build the result line."""
    workload = ctx.entry["name"]

    runner = importlib.import_module(f"harness.{ctx.traffic['runner']}")
    runner.run(ctx)

    if ctx.traced:
        reduce_trace(ctx, runner.SPANS)
    kind = "per_layer" if ctx.traced else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, workload, kind):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    chip.log(f"compiles inside the window: {ctx.window_compiles}")
    ok = all(c["ok"] for c in ctx.checks.values())
    for name, c in ctx.checks.items():
        chip.log(f"check {name}: {c['value']} {c['op']} {c['limit']} "
                 f"{'ok' if c['ok'] else 'FAILED'}")
    out = {"correct": bool(ok), "attempted": ctx.result["attempted"],
           "failed": ctx.result["failed"], "metrics": metrics,
           "device": ctx.device}
    if ctx.traced:
        out["breakdown"] = ctx.breakdown
    out["checks"] = {n: {"value": c["value"], "limit": c["limit"]}
                     for n, c in ctx.checks.items()}
    return out


if __name__ == "__main__":
    sys.exit(main())
