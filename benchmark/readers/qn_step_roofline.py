"""The least time the chip could take for one superstep (operations and
bytes from ``opcount_linear.softmax_superstep``, shapes alone: the
one-byte table read twice) over the device time of one superstep,
percent. Memory-bound at the cell's shapes, and the same work whatever
implements the passes."""

from benchmark.readers import qn_step_dev


def read(ctx):
    ms = qn_step_dev.read(ctx)
    return 100.0 * ctx.facts["step_least_s"] / (ms / 1e3) if ms else None
