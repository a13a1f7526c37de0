"""The whole ALS loop's share of the chip's peak: the least time for
every half-sweep the traced window's fits ran (as the program counted
them) plus the least time for grouping the raw table once a fit, over
that window's wall time, percent."""


def read(ctx):
    sweeps, fits = ctx.facts.get("half_sweeps"), ctx.facts.get("fits")
    if not ctx.reduced or not sweeps or not fits:
        return None
    least = (sweeps * ctx.facts["sweep_least_s"]
             + fits * ctx.facts["group_least_s"])
    return 100.0 * least / ctx.reduced["window_s"]
