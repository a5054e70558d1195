#!/usr/bin/env python3
"""Record the small profiler trace the trace-reduction tests read.

    python3 chipbench/tests/data/record_trace.py OUT_DIR

On one TPU: a jitted program ``step`` that calls a Pallas kernel named
``probe_kernel`` twice between two matmuls is run N_STEPS times inside a
harness ``window`` span, one ``call`` span per step, with a host sleep of
SLEEP_S between steps, so the trace has known device busy periods and
known idle gaps.  Writes ``OUT_DIR/small.xplane.pb`` and, beside it,
``structure.json``: each plane's lines with their event counts and first
event names.
"""
import glob
import json
import os
import shutil
import sys
import tempfile
import time

N_STEPS, SLEEP_S = 5, 0.02


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + 1.0

    def probe(x):
        return pl.pallas_call(kern, out_shape=jax.ShapeDtypeStruct(
            x.shape, x.dtype), name="probe_kernel")(x)

    @jax.jit
    def step(x, w):
        y = probe(x @ w)
        return probe(y @ w)

    x = jnp.ones((1024, 1024), jnp.float32)
    w = jnp.eye(1024, dtype=jnp.float32) * 0.5
    step(x, w).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(N_STEPS):
            with jax.profiler.TraceAnnotation("call"):
                step(x, w).block_until_ready()
            time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    struct = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"line": line.name, "events": len(evs),
                          "first": [e.name for e in evs[:12]]})
        struct.append({"plane": plane.name, "lines": lines})
    with open(os.path.join(out_dir, "structure.json"), "w") as f:
        json.dump(struct, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
