"""The least time the chip could take for one tree (operations and bytes
from ``opcount_gbdt.gbdt_tree``, shapes alone: every level reads each
row's bins, two statistics and node once, and the margins are read and
written once) over the device time of one tree, percent. Memory-bound at
these shapes."""

from benchmark.readers import gbdt_tree_dev


def read(ctx):
    ms = gbdt_tree_dev.read(ctx)
    return 100.0 * ctx.facts["tree_least_s"] / (ms / 1e3) if ms else None
