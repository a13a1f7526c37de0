"""The least time the chip could take for one micro-batch (operations and
bytes from ``opcount.ftrl_step``, shapes alone) over the device time of
the step program, in percent. Memory-bound at these shapes."""

from benchmark.readers import ftrl_step_dev


def read(ctx):
    ms = ftrl_step_dev.read(ctx)
    return 100.0 * ctx.facts["step_least_s"] / (ms / 1e3) if ms else None
