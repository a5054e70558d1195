"""Benchmark orchestrator — one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run table1 fig3
  PYTHONPATH=src python -m benchmarks.run --smoke --out bench-smoke.json

``--smoke`` runs the fast hardware-facing subset (kernel micro-bench +
end-to-end backend bench) — the CI job.  ``--out PATH`` writes every
emitted row as JSON (the artifact CI uploads).

Output: CSV blocks (``name,...`` headers) + `#` summary lines asserting the
paper's directional claims.  Roofline numbers live in EXPERIMENTS.md
(§Roofline) — they come from the dry-run, not from CPU wall clock.
"""
from __future__ import annotations

import argparse
import inspect
import json
import time

from repro.launch.compile_cache import use_compile_cache

from . import (backend_bench, common, federated_scale_bench, fig2_activation,
               fig3_temperature, kernel_bench, round_engine_bench,
               serving_bench, table1_flops, table2_budgets, table3_scale,
               table4_sampling, table5_rescaler, telemetry_bench)

ALL = {
    "table1": table1_flops.run,
    "table2": table2_budgets.run,
    "table3": table3_scale.run,
    "table4": table4_sampling.run,
    "table5": table5_rescaler.run,
    "fig2": fig2_activation.run,
    "fig3": fig3_temperature.run,
    "kernels": kernel_bench.run,
    "backend": backend_bench.run,
    "round_engine": round_engine_bench.run,
    "federated_scale": federated_scale_bench.run,
    "serving": serving_bench.run,
    "telemetry": telemetry_bench.run,
}

# CPU-fast subset for CI (`--smoke`): no pretraining; federated_scale
# self-limits to its 64-client row under smoke
SMOKE = ["kernels", "backend", "serving", "telemetry", "federated_scale"]


def main(argv=None) -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(prog="benchmarks.run")
    ap.add_argument("picks", nargs="*", help=f"subset of {list(ALL)}")
    ap.add_argument("--smoke", action="store_true",
                    help="run the fast CI subset")
    ap.add_argument("--out", metavar="PATH",
                    help="write emitted rows as JSON to PATH")
    ns = ap.parse_args(argv)

    picks = ns.picks or (SMOKE if ns.smoke else list(ALL))
    t0 = time.time()
    for name in picks:
        if name not in ALL:
            raise SystemExit(f"unknown benchmark {name!r}; "
                             f"choose from {list(ALL)}")
        t = time.time()
        fn = ALL[name]
        # benchmarks that can scale themselves down take smoke=True
        kw = ({"smoke": True} if ns.smoke
              and "smoke" in inspect.signature(fn).parameters else {})
        fn(**kw)
        print(f"# [{name}] done in {time.time() - t:.1f}s", flush=True)
    wall = time.time() - t0
    print(f"\n# all benchmarks done in {wall:.1f}s")
    if ns.out:
        payload = {"benchmarks": picks, "wall_s": round(wall, 2),
                   "results": common.RESULTS}
        if common.TELEMETRY:
            payload["telemetry"] = common.TELEMETRY
        with open(ns.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(common.RESULTS)} rows to {ns.out}")


if __name__ == "__main__":
    main()
