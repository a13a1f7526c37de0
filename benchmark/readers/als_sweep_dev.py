"""Device milliseconds of one ALS half-sweep: the sweep program's
executions on the trace's ``XLA Modules`` line, found by the name the
configuration gives (``step_program``; one execution runs a fit's
iterations, a superstep two half-sweeps and the RMSE), over the
half-sweeps the traced window's fits ran as the program counted them
(``alink_als_sweeps_total``)."""

from benchmark import trace_reduce


def read(ctx):
    sweeps = ctx.facts.get("half_sweeps")
    if not ctx.reduced or not sweeps:
        return None
    secs, calls = trace_reduce.module_time(ctx.reduced,
                                           ctx.config["step_program"])
    return secs / sweeps * 1e3 if calls else None
