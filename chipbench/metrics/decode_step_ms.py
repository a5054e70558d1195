"""Mean decode step of the window: the engine's own per-step host time
(``ServingReport.decode_step_s``: dispatch to logits on the host),
summed over the window's steps and divided by their count."""


def read(ctx):
    n = ctx.data["decode_steps"]
    return ctx.data["decode_s"] / n * 1e3 if n else None
