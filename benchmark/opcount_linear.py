"""Operations and bytes a batch linear fit needs, from shapes alone (as
``opcount.py`` counts the FTRL step): what ANY implementation of
multinomial logistic regression by a quasi-Newton method with a ladder
line search has to do, not what the program's XLA ops happen to do.

One superstep reads the table TWICE, and no fewer: the line search's
direction depends on the sum of the gradient over every row, so the
direction's logits cannot be taken in the pass that makes the gradient.
Pass one is two products (the logits ``X W^T`` and the gradient ``delta^T
X``), pass two one (``X D^T``), each ``rows x (dim + 1) x (classes - 1)``
multiply-adds. The softmax, the ladder's losses and the two-loop recursion
are not counted, nor the labels, nor logits kept between the passes:
leaving them out lowers the share and never raises it. At one byte a
pixel the bytes are the floor on this chip (15.5 ms against 1.7 ms of
operations at the cell's shapes): memory-bound.

The moments pass reads the table once; its operations (a mean and a
centred square a value) are nowhere near its bytes.
"""

from __future__ import annotations

from typing import Tuple

PRODUCTS_PER_SUPERSTEP = 3
TABLE_READS_PER_SUPERSTEP = 2


def softmax_superstep(rows: int, dim: int, classes: int,
                      bytes_per_value: int = 1) -> Tuple[int, int]:
    """(operations, bytes) of one superstep over ``rows`` rows of ``dim``
    features (and the intercept) against ``classes - 1`` coefficient
    rows."""
    ops = PRODUCTS_PER_SUPERSTEP * 2 * rows * (dim + 1) * (classes - 1)
    byt = TABLE_READS_PER_SUPERSTEP * rows * dim * bytes_per_value
    return ops, byt


def moments_pass(rows: int, dim: int, bytes_per_value: int = 1
                 ) -> Tuple[int, int]:
    """(operations, bytes) of the moments: every value read once, added
    into its column's sum and, centred and squared, into its column's
    sum of squares."""
    return 4 * rows * dim, rows * dim * bytes_per_value
