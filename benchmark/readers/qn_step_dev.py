"""Device milliseconds of one quasi-Newton superstep of the batch linear
trainer: the step program's executions on the trace's ``XLA Modules``
line, found by the name the configuration gives (``step_program``; one
execution runs all of a fit's supersteps), over the supersteps the traced
window's fits ran as the program counted them
(``alink_linear_supersteps_total``)."""

from benchmark import trace_reduce


def read(ctx):
    steps = ctx.facts.get("supersteps")
    if not ctx.reduced or not steps:
        return None
    secs, calls = trace_reduce.module_time(ctx.reduced,
                                           ctx.config["step_program"])
    return secs / steps * 1e3 if calls else None
