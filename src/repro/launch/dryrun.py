import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run driver (deliverable e).

Lowers + compiles every (architecture × input-shape) step for the production
mesh — 16×16 single-pod and 2×16×16 multi-pod — against ShapeDtypeStruct
stand-ins (no allocation), then records memory_analysis(), cost_analysis()
and the collective schedule for the roofline table.

THE FIRST TWO LINES of this module set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any other
import: jax locks the device count at first initialisation.  No other
module sets this — smoke tests and benches see the real single device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b \
      --shape train_4k --multi-pod --out experiments/dryrun
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax

from ..configs.base import INPUT_SHAPES
from ..configs.registry import ASSIGNED_ARCHS, get_config, long_context_variant
from . import analytic
from . import roofline as roofline_lib
from . import steps as steps_lib
from .mesh import make_production_mesh


def variant_for(arch: str, shape_name: str) -> str:
    """long_500k needs a sub-quadratic attention path: native for SSM/hybrid
    (mamba state is O(1)); the sliding-window variant for attention archs."""
    if shape_name == "long_500k":
        return long_context_variant(arch)
    return "full"


def run_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
             overrides: Optional[Dict[str, Any]] = None,
             keep_hlo: bool = False,
             mesh_shape: Optional[tuple] = None) -> Dict[str, Any]:
    """Lower + compile one (arch × shape × mesh) combination; return the
    dry-run record (roofline terms, memory, collective schedule).

    ``mesh_shape``: override the (data, model) split of the 256 chips —
    the §Perf beyond-paper knob (the deliverable mesh stays 16×16)."""
    t0 = time.time()
    shape = INPUT_SHAPES[shape_name]
    variant = variant_for(arch, shape_name)
    cfg = get_config(arch, variant)
    if mesh_shape is not None:
        from jax.sharding import AxisType
        mesh = jax.make_mesh(tuple(mesh_shape), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    chips = mesh.devices.size

    with mesh:
        bundle = steps_lib.build_step(cfg, shape, mesh, **(overrides or {}))
        lowered = bundle.fn.lower(*bundle.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    hlo = compiled.as_text()
    model_flops = analytic.model_flops_global(cfg, shape, bundle.meta)
    dev_flops = analytic.device_flops(cfg, shape, chips, bundle.meta)
    dev_bytes = analytic.device_bytes(cfg, shape, chips, bundle.meta)

    rl = roofline_lib.extract(
        compiled, hlo, arch=arch, shape=shape_name, mesh_name=mesh_name,
        chips=chips, model_flops=model_flops, device_flops=dev_flops,
        device_bytes=dev_bytes, meta=bundle.meta)

    mem = compiled.memory_analysis()
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "variant": variant, "step": bundle.name, "chips": chips,
        "ok": True,
        "t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
        "memory": {
            "argument_gb": mem.argument_size_in_bytes / 2 ** 30,
            "output_gb": mem.output_size_in_bytes / 2 ** 30,
            "temp_gb": mem.temp_size_in_bytes / 2 ** 30,
            "alias_gb": mem.alias_size_in_bytes / 2 ** 30,
            "peak_gb": (mem.argument_size_in_bytes +
                        mem.output_size_in_bytes +
                        mem.temp_size_in_bytes -
                        mem.alias_size_in_bytes) / 2 ** 30,
        },
        "roofline": rl.row(),
        "collectives": {
            "execs_by_kind": rl.meta["collective_execs_by_kind"],
            "bytes_by_kind": rl.meta["collective_bytes_by_kind"],
        },
        "meta": rl.meta,
    }
    if keep_hlo:
        record["hlo_text"] = hlo
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--act-mode", default=None)
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)

    overrides: Dict[str, Any] = {}
    if args.n_micro is not None:
        overrides["n_micro"] = args.n_micro
    if args.act_mode is not None:
        overrides["act_mode"] = args.act_mode

    n_ok = n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape_name}__{'2x16x16' if mp else '16x16'}"
                path = os.path.join(args.out, tag + ".json")
                try:
                    ov = dict(overrides)
                    if INPUT_SHAPES[shape_name].kind != "train":
                        ov.pop("n_micro", None)
                        ov.pop("act_mode", None)
                    rec = run_pair(arch, shape_name, multi_pod=mp,
                                   overrides=ov)
                    n_ok += 1
                    r = rec["roofline"]
                    print(f"OK   {tag}: peak={rec['memory']['peak_gb']:.2f}GB"
                          f" bottleneck={r['bottleneck']}"
                          f" tc={r['t_compute_ms']:.1f}ms"
                          f" tm={r['t_memory_ms']:.1f}ms"
                          f" tx={r['t_collective_ms']:.1f}ms"
                          f" (compile {rec['t_compile_s']}s)", flush=True)
                except Exception as e:  # noqa: BLE001 — record + continue
                    n_fail += 1
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if mp else "16x16",
                           "ok": False, "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()}
                    print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1, default=str)
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
