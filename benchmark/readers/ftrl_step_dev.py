"""Device milliseconds of the FTRL step program per micro-batch: the
program's executions on the trace's ``XLA Modules`` line, found by the
name the configuration gives."""

from benchmark import trace_reduce


def read(ctx):
    if not ctx.reduced:
        return None
    secs, calls = trace_reduce.module_time(ctx.reduced,
                                           ctx.config["step_program"])
    return secs / calls * 1e3 if calls else None
