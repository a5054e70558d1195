"""Model FLOPs the window's finished requests needed (prefill of each
prompt and every decode step, at each request's k; harness/flops.py),
per second of the window, as a share of the chip's peak bf16 rate."""
from harness import flops


def read(ctx):
    s = ctx.sizes
    total = sum(flops.request_flops(s, c.k, c.prompt_len, c.n_generated)
                for c in ctx.data["completions"])
    return 100.0 * total / ctx.window_s / ctx.peaks["bf16_flops_per_s"] \
        / len(ctx.devs)
