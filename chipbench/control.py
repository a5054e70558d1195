#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 chipbench/control.py --workload NAME --seeds 11,12,13 [--seconds 1]

For each seed, in this one process: the cell's own run (set-up, a window
of whole calls at the cell's load, the reference check) and, over the same
requests and served tokens, the control (the reference computed in float8,
one precision below the configuration's bfloat16, in the program's place)
and a sampler fault (draws from the float32 reference with the nucleus
left out).  The control and the fault are judged by the cell's limits as
the program is.  Prints one JSON line per seed with each one's numbers and
``correct``, then a summary per number: the program's largest reading
(the lower end for a limit) and the control's and the fault's smallest
(the upper end).
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as R  # noqa: E402
from harness import chip  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    bench = R.load_bench()
    entry = next(w for w in bench["workloads"] if w["name"] == args.workload)
    jax = R.import_jax()
    try:
        devs = chip.require_chips(jax, int(entry["chips"]))
    except chip.NoChip as e:
        chip.log(f"control: {e} - nothing run")
        return 1
    traffic = json.loads(
        (R.BENCH / "traffic" / f"{entry['traffic']}.json").read_text())
    runner = importlib.import_module(f"harness.{traffic['runner']}")
    rows = []
    for seed in [int(x) for x in args.seeds.split(",")]:
        a = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                               control=True)
        ctx = R.Context.from_files(a, bench, entry, jax, devs)
        runner.run(ctx)
        row = {"seed": seed, "numbers": ctx.data["numbers"],
               "correct": {"program": all(c["ok"]
                                          for c in ctx.checks.values()),
                           **{v: j["correct"] for v, j in
                              ctx.data["judged"].items()}}}
        rows.append(row)
        print(json.dumps(row), flush=True)
        # each seed builds its own engine: drop its loaded programs too
        del ctx
        gc.collect()
        jax.clear_caches()
    summary = {"workload": args.workload, "seeds": len(rows)}
    for name in rows[0]["numbers"]["program"]:
        summary[name] = {"program_max": max(
            r["numbers"]["program"][name] for r in rows)}
        for v in rows[0]["numbers"]:
            if v != "program" and name in rows[0]["numbers"][v]:
                summary[name][v + "_min"] = min(
                    r["numbers"][v][name] for r in rows)
    summary["correct"] = {v: [r["correct"][v] for r in rows]
                          for v in rows[0]["correct"]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
