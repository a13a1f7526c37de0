"""Operations and bytes a histogram-boosted tree needs, from shapes alone
(as ``opcount.py`` counts the FTRL step): what ANY implementation of
depth-wise histogram boosting has to do, not what the program's XLA ops
happen to do.

A level has to read, of every row, its ``features`` bins (one byte each:
128 bins fit a byte), its two gradient statistics (float32) and its node
(one byte holds 64 nodes; counted as one). A tree is ``depth`` such levels
and one pass over the margins (read, and written with the new leaf's
value: the label is read with the statistics). Operations: per row and
level one addition of each of the three statistics into each feature's
bin, ``3 * features``; the chip's peak is so far above that (197 TFLOP/s
against 819 GB/s) that the floor is the bytes': memory-bound. The one-hot
product the program builds its histograms with does ~2,000 times the
operations and none of them are counted: a share of this floor says how
far a tree is from what the memory allows, whatever builds it.

The binning pass reads every raw value (float32) once and writes its bin
(one byte); finding the quantile edges reads every value once more.
"""

from __future__ import annotations

from typing import Tuple

STATS_BYTES = 8               # gradient and hessian, float32
NODE_BYTES = 1
MARGIN_BYTES = 4 + 4 + 4      # margin read and written, label read
RAW_BYTES = 4                 # float32


def gbdt_tree(rows: int, features: int, depth: int) -> Tuple[int, int]:
    """(operations, bytes) of growing one tree of ``depth`` levels over
    ``rows`` rows of ``features`` binned features."""
    ops = 3 * features * rows * depth
    byt = rows * (depth * (features + STATS_BYTES + NODE_BYTES) + MARGIN_BYTES)
    return ops, byt


def gbdt_binning(rows: int, features: int) -> Tuple[int, int]:
    """(operations, bytes) of binning the raw table once: a pass for the
    quantile edges (every value read and counted), a pass for the bins
    (every value read, a byte written)."""
    ops = 2 * features * rows
    byt = rows * features * (2 * RAW_BYTES + 1)
    return ops, byt
