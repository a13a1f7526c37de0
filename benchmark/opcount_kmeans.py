"""Operations and bytes a KMeans superstep needs, from shapes alone (as
``opcount.py`` counts the FTRL step): what ANY implementation of one pass
over the table has to do, not what the program's XLA ops happen to do.

One superstep, Lloyd's or a k-means|| round, reads every row's ``d``
features and its weight once and finds the nearest of ``k`` centres. Per
row and centre: ``d`` subtractions, ``d`` multiplications and ``d``
additions for the squared distance (the last addition is the comparison
that keeps the least), and ``d`` additions folding the row into its
centre's sum counted against every centre as the one-hot form does:
``4 d`` a (row, centre). The sampling rounds of k-means|| are counted as
the same pass at the same ``k`` (they fold ``2 k`` new candidates, so this
undercounts them, which lowers the share and never raises it).
"""

from __future__ import annotations

from typing import Tuple

FLOPS_PER_ROW_CENTRE_FEATURE = 4
BYTES_PER_VALUE = 4           # float32


def kmeans_superstep(rows: int, dim: int, k: int) -> Tuple[int, int]:
    """(operations, bytes) of one superstep over ``rows`` rows of ``dim``
    features against ``k`` centres: the table and the weights read once."""
    ops = FLOPS_PER_ROW_CENTRE_FEATURE * rows * k * dim
    byt = BYTES_PER_VALUE * rows * (dim + 1)
    return ops, byt
