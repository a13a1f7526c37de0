"""The batched solve kernel's share of its roofline: the least time the
chip could take for the systems the traced window's half-sweeps solved (a
side's rows a half-sweep; operations and bytes from
``opcount_als.als_solves``, shapes alone) over the device time of the
``als_solve`` ops on the trace's ``XLA Ops`` line, percent. A program
whose solve is not that kernel gives nothing to read."""

from benchmark import opcount, opcount_als


def read(ctx):
    sweeps = ctx.facts.get("half_sweeps")
    if not ctx.reduced or not sweeps:
        return None
    secs = sum(s for name, s in ctx.reduced["op_s"].items()
               if name.split(".")[0] == "als_solve")
    if secs <= 0:
        return None
    cfg = ctx.config
    rows = (int(cfg["users"]) + int(cfg["items"])) * sweeps // 2
    least = opcount.least_seconds(
        *opcount_als.als_solves(rows, int(cfg["rank"])), ctx.peak)
    return 100.0 * least / secs
