"""The whole fit loop's share of the chip's peak: the least time for every
superstep of the traced window's fits (k-means|| rounds and Lloyd's alike,
as the program counted them) over that window's wall time, percent."""


def read(ctx):
    if not ctx.reduced or not ctx.facts.get("supersteps"):
        return None
    least = ctx.facts["supersteps"] * ctx.facts["step_least_s"]
    return 100.0 * least / ctx.reduced["window_s"]
