"""Device milliseconds of one boosted tree: the grow program's executions
on the trace's ``XLA Modules`` line, found by the name the configuration
gives (``step_program``; one execution grows a fit's trees, a superstep a
tree), over the trees the traced window's fits grew as the program
counted them (``alink_gbdt_trees_total``)."""

from benchmark import trace_reduce


def read(ctx):
    trees = ctx.facts.get("trees")
    if not ctx.reduced or not trees:
        return None
    secs, calls = trace_reduce.module_time(ctx.reduced,
                                           ctx.config["step_program"])
    return secs / trees * 1e3 if calls else None
