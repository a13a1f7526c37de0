"""Device milliseconds a fit spends binning the raw table: the executions
of the quantile-edge program and of the binning program on the trace's
``XLA Modules`` line, found by the names the configuration gives
(``edge_program``, ``bin_program``), over the traced window's fits."""

from benchmark import trace_reduce


def read(ctx):
    fits = ctx.facts.get("fits")
    if not ctx.reduced or not fits:
        return None
    secs = calls = 0
    for key in ("edge_program", "bin_program"):
        s, c = trace_reduce.module_time(ctx.reduced, ctx.config[key])
        secs, calls = secs + s, calls + c
    return secs / fits * 1e3 if calls else None
