"""Operations and bytes that the algorithm needs, from shapes alone.

These count what ANY implementation of the step has to do, not what the
program's XLA ops happen to do: a whole-state pass that an implementation
adds is not work the algorithm needs, so it lowers the roofline share
instead of raising the count. ``least_seconds`` is the roofline's floor:
the larger of operations over peak FLOP/s and bytes over peak HBM bytes/s.
"""

from __future__ import annotations

from typing import Dict, Tuple

# FTRL-proximal per non-zero entry (McMahan et al., KDD 2013, algorithm 1):
#   weight from (z, n): sqrt, +beta, /alpha, +l2, sign, *l1, z-, /, neg, select  = 10
#   margin: multiply, add                                                        =  2
#   gradient: (p - y) * x                                                        =  1
#   sigma: g*g, n+, sqrt, sqrt, -, /alpha                                        =  6
#   z update: sigma*w, g-, z+                                                    =  3
#   n update: n + g*g (g*g counted above)                                        =  1
FTRL_FLOPS_PER_ENTRY = 23
# per row: clip, exp, 1+, reciprocal, p - y, and the bias-free margin's last add
FTRL_FLOPS_PER_ROW = 6
# per entry: read z and n, write z and n (4 x f32), read the index (i32) and
# the value (f32); per row: the label (f32)
FTRL_BYTES_PER_ENTRY = 4 * 4 + 4 + 4
FTRL_BYTES_PER_ROW = 4


def ftrl_step(rows: int, nnz: int) -> Tuple[int, int]:
    """(operations, bytes) of one FTRL micro-batch of ``rows`` rows with
    ``nnz`` non-zero entries each (the intercept counted among them)."""
    ops = rows * (nnz * FTRL_FLOPS_PER_ENTRY + FTRL_FLOPS_PER_ROW)
    byt = rows * (nnz * FTRL_BYTES_PER_ENTRY + FTRL_BYTES_PER_ROW)
    return ops, byt


def linear_score(rows: int, nnz: int) -> Tuple[int, int]:
    """(operations, bytes) of scoring ``rows`` sparse rows of ``nnz``
    entries against one weight vector: a multiply and an add per entry
    and the bias; the index, the value and the gathered weight read per
    entry and one f32 score written per row."""
    ops = rows * (2 * nnz + 1)
    byt = rows * (nnz * (4 + 4 + 4) + 4)
    return ops, byt


def least_seconds(ops: int, byt: int, peak: Dict[str, float]) -> float:
    """The least time one chip could take: the roofline's floor."""
    return max(ops / peak["flops_per_s"], byt / peak["hbm_bytes_per_s"])


def bound_by(ops: int, byt: int, peak: Dict[str, float]) -> str:
    return ("compute" if ops / peak["flops_per_s"]
            >= byt / peak["hbm_bytes_per_s"] else "memory")
