"""The least time the chip could take for one superstep (operations and
bytes from ``opcount_kmeans.kmeans_superstep``, shapes alone: the table
and the weights read once) over the device time of one Lloyd superstep,
percent. Memory-bound at these shapes."""

from benchmark.readers import lloyd_step_dev


def read(ctx):
    ms = lloyd_step_dev.read(ctx)
    return 100.0 * ctx.facts["step_least_s"] / (ms / 1e3) if ms else None
