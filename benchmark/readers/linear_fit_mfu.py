"""The whole fit loop's share of the chip's peak: the least time for every
superstep the traced window's fits ran (as the program counted them) plus
the least time for the moments pass once a fit, over that window's wall
time, percent."""


def read(ctx):
    steps, fits = ctx.facts.get("supersteps"), ctx.facts.get("fits")
    if not ctx.reduced or not steps or not fits:
        return None
    least = (steps * ctx.facts["step_least_s"]
             + fits * ctx.facts["moments_least_s"])
    return 100.0 * least / ctx.reduced["window_s"]
