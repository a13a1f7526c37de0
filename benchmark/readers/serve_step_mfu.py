"""The whole server's share of the chip's peak: the least time for every
row scored in the traced window over that window's wall time, percent."""


def read(ctx):
    if not ctx.reduced or not ctx.facts.get("rows_dispatched"):
        return None
    least = ctx.facts["rows_dispatched"] * ctx.facts["rows_least_s"]
    return 100.0 * least / ctx.reduced["window_s"]
