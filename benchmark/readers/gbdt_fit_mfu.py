"""The whole boost loop's share of the chip's peak: the least time for
every tree the traced window's fits grew (as the program counted them)
plus the least time for binning the raw table once a fit, over that
window's wall time, percent."""


def read(ctx):
    trees, fits = ctx.facts.get("trees"), ctx.facts.get("fits")
    if not ctx.reduced or not trees or not fits:
        return None
    least = (trees * ctx.facts["tree_least_s"]
             + fits * ctx.facts["bin_least_s"])
    return 100.0 * least / ctx.reduced["window_s"]
