"""Device milliseconds a fit spends grouping the raw triples by user and
by item: the executions of the grouping program on the trace's ``XLA
Modules`` line, found by the name the configuration gives
(``group_program``), over the traced window's fits."""

from benchmark import trace_reduce


def read(ctx):
    fits = ctx.facts.get("fits")
    if not ctx.reduced or not fits:
        return None
    secs, calls = trace_reduce.module_time(ctx.reduced,
                                           ctx.config["group_program"])
    return secs / fits * 1e3 if calls else None
