"""The whole drain's share of the chip's peak: the least time for every
micro-batch of the traced window over that window's wall time, percent."""


def read(ctx):
    if not ctx.reduced or not ctx.facts.get("micro_batches"):
        return None
    least = ctx.facts["micro_batches"] * ctx.facts["step_least_s"]
    return 100.0 * least / ctx.reduced["window_s"]
