"""Operations and bytes an ALS fit needs, from shapes alone (as
``opcount.py`` counts the FTRL step): what ANY implementation of
alternating least squares has to do, not what the program's XLA ops happen
to do.

A half-sweep over one side: every rating adds its factor row's outer
product into its row's symmetric Gram sum, ``f (f + 1) / 2`` multiply-adds
= ``f (f + 1)`` operations, and its rating times the row into the
right-hand side, ``2 f``; every row of the side solves a symmetric system
of ``f`` (Cholesky ``f^3 / 3``, two triangular solves ``2 f^2``). Bytes:
each grouped rating read once (the other side's id and the value, 8), the
other side's factors read once and this side's written once (``4 f`` a
row). On this chip (197 TFLOP/s against 819 GB/s) the operations are the
floor at rank 100: compute-bound. A float32 program pays several bfloat16
passes a product and its gathers are not in the floor at all, so it reads
a few per cent of it: that is the honest reading.

Grouping: the raw triples (12 bytes) are read and each side's grouped copy
(8 bytes a rating) written, twice over for a two-pass method (a pass to
count, a pass to place): ``2 * (12 + 8)`` bytes a rating a side. No
operations to speak of: memory-bound.
"""

from __future__ import annotations

from typing import Tuple

RAW_BYTES = 12                # user, item (int32) and rating (float32)
GROUPED_BYTES = 8             # the other side's id and the rating


def als_half_sweep(ratings: int, rows: int, other_rows: int, rank: int
                   ) -> Tuple[int, int]:
    """(operations, bytes) of one half-sweep over a side of ``rows`` rows
    holding ``ratings`` ratings, reading ``other_rows`` factor rows."""
    f = int(rank)
    ops = ratings * (f * (f + 1) + 2 * f) + rows * (f ** 3 // 3 + 2 * f * f)
    byt = ratings * GROUPED_BYTES + 4 * f * (rows + other_rows)
    return ops, byt


def als_solves(rows: int, rank: int) -> Tuple[int, int]:
    """(operations, bytes) of ``rows`` symmetric solves of ``rank``: the
    part of a half-sweep the batched solve kernel does. Each system's
    matrix and right-hand side read once, its solution written once: at
    rank 100 that is 40,800 bytes against 353,333 operations, so the
    solves alone are memory-bound on this chip (50 ns against 1.8)."""
    f = int(rank)
    return rows * (f ** 3 // 3 + 2 * f * f), rows * 4 * (f * f + 2 * f)


def als_iteration(ratings: int, users: int, items: int, rank: int
                  ) -> Tuple[int, int]:
    """(operations, bytes) of one iteration: the user half-sweep, then the
    item half-sweep."""
    a = als_half_sweep(ratings, users, items, rank)
    b = als_half_sweep(ratings, items, users, rank)
    return a[0] + b[0], a[1] + b[1]


def als_grouping(ratings: int) -> Tuple[int, int]:
    """(operations, bytes) of grouping the raw triples by user and by
    item, two passes a side."""
    return 2 * ratings, 2 * 2 * ratings * (RAW_BYTES + GROUPED_BYTES)
