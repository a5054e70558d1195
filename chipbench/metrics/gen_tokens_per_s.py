"""Output tokens of the requests finished in the window's whole calls,
over the window's seconds (host clock)."""


def read(ctx):
    comps = ctx.data["completions"]
    return sum(c.n_generated for c in comps) / ctx.window_s
