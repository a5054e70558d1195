"""99th percentile, exact over the window's finished requests, of the
gaps between consecutive generated tokens: ``Completion.token_t``, the
engine's own time at each draw.  Logs the number of gaps and the five
longest, each with its request, token and engine time.  A program whose
completions carry no ``token_t`` gives nothing to read."""
import numpy as np

from harness.chip import log


def read(ctx):
    comps = ctx.data["completions"]
    if not all(hasattr(c, "token_t") for c in comps):
        log("itl_p99_ms: the program records no per-token times; not read")
        return None
    gaps = [(np.diff(c.token_t) * 1e3, c) for c in comps]
    ms = np.concatenate([g for g, _ in gaps] + [np.zeros(0)])
    if not ms.size:
        log("itl_p99_ms: no request generated two tokens; not read")
        return None
    longest = sorted(((float(g[i]), c.rid, int(i) + 1, float(c.token_t[i]))
                      for g, c in gaps for i in np.argsort(g)[-5:]),
                     reverse=True)[:5]
    log(f"itl_p99_ms over {ms.size} gaps; longest (ms, request, token, "
        f"engine s before it): {longest}")
    return float(np.percentile(ms, 99))
