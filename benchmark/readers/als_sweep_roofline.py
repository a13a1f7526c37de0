"""The least time the chip could take for one ALS half-sweep (operations
and bytes from ``opcount_als.als_iteration``, shapes alone, halved: every
rating's symmetric outer product and right-hand side, every row's solve;
each grouped rating and each factor row crossing memory once) over the
device time of one half-sweep, percent. Compute-bound at rank 100."""

from benchmark.readers import als_sweep_dev


def read(ctx):
    ms = als_sweep_dev.read(ctx)
    return 100.0 * ctx.facts["sweep_least_s"] / (ms / 1e3) if ms else None
