"""Logistic scoring of sparse rows: p = sigmoid(b + sum_j val_j w[idx_j]).

NumPy, float64 by default (the yardstick a float32 server is held to);
the controls pass ``bfloat16`` (``ml_dtypes``, which rounds after every
operation). The caller hands the weights already gathered at the rows'
coordinates, so a 2^30-wide model costs only what the requests touch.
Imports nothing of the program.
"""

from __future__ import annotations

import numpy as np


def score(w_at: np.ndarray, val: np.ndarray, bias: float,
          dtype="float64") -> np.ndarray:
    """P(positive) per row; ``w_at`` and ``val`` are (rows, nnz)."""
    if dtype == "bfloat16":
        import ml_dtypes
        dt = ml_dtypes.bfloat16
    else:
        dt = np.dtype(dtype)
    w = np.asarray(w_at).astype(dt)
    v = np.asarray(val).astype(dt)
    margin = np.asarray(bias).astype(dt)
    terms = w * v
    for j in range(terms.shape[1]):       # strictly left to right
        margin = margin + terms[:, j]
    m = np.clip(margin.astype(np.float64), -500.0, 500.0)
    return 1.0 / (1.0 + np.exp(-m))
