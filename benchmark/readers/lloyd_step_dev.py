"""Device milliseconds of one Lloyd superstep: the Lloyd program's
executions on the trace's ``XLA Modules`` line, found by the name the
configuration gives (``step_program``), over the Lloyd supersteps the
traced window's fits ran (every superstep the program counted less the
k-means|| rounds)."""

from benchmark import trace_reduce


def lloyd_supersteps(ctx):
    fits, steps = ctx.facts.get("fits"), ctx.facts.get("supersteps")
    if not fits or not steps:
        return None
    return steps - fits * int(ctx.config["init_rounds"])


def read(ctx):
    steps = lloyd_supersteps(ctx)
    if not ctx.reduced or not steps or steps <= 0:
        return None
    secs, calls = trace_reduce.module_time(ctx.reduced,
                                           ctx.config["step_program"])
    return secs / steps * 1e3 if calls else None
