"""95th percentile, exact over every request finished in the window, of
the time per output token after the first:
(finished - first_token) / (tokens - 1)."""
import numpy as np

from harness.chip import log


def read(ctx):
    xs = [(c.finished - c.first_token) / (c.n_generated - 1) * 1e3
          for c in ctx.data["completions"] if c.n_generated > 1]
    log(f"tpot_p95_ms over {len(xs)} requests")
    return float(np.percentile(np.asarray(xs, np.float64), 95))
