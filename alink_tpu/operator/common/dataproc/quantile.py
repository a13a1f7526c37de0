"""Distributed quantile computation — device-side, all columns at once,
block by block.

Re-design of the reference's parallel sort-based quantiles
(common/dataproc/SortUtils.java:38-47 ``pSort`` + QuantileDiscretizer's
per-column pass). A distributed full sort is the wrong shape for a TPU;
instead one BSP superstep builds a fine-grained histogram for EVERY
column simultaneously, walking the worker's shard of the blocked table
(``common/columnar.py``: ``(row_blocks, F, S, 128)``, which may already
lie on the device) with ``lax.fori_loop``:

  1. per-shard masked min / max and whether every value is a whole
     number, ``pmax`` / ``pmin`` across the mesh;
  2. per-shard fixed-grid histogram (``fine_bins`` cells a column),
     ``psum`` across the mesh. A block's counts are ONE product of two
     one-hot matrices on the MXU (cell = 128 * hi + lo, counts[hi, lo] =
     onehot(hi)^T onehot(lo), neither ever written to memory) where the
     backend is a TPU, and a scatter-add of the block's pairs elsewhere:
     a scatter-add over all 1.5e9 pairs of a 115-million-row table would
     take minutes (88-94 ns an update on a v5e, PERF.md);
  3. the tiny (F, fine_bins) table goes to the host once; quantiles come
     from the cumulative counts.

A column of whole numbers that spans fewer than ``fine_bins`` values (ids,
codes, clock times: most columns of a table of events) gets one cell a
value, and its quantiles are EXACT: the smallest value with at least the
asked share of the column at or below it. Any other column gets a uniform
grid over [min, max] with linear interpolation inside cells, which matches
``np.quantile`` to ~1e-3 of the column's span. Host work is O(F *
fine_bins) regardless of row count.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ....common.columnar import LANES, DenseBlockColumn, as_block_column
from ....common.mlenv import MLEnvironment, MLEnvironmentFactory
from ....engine import IterativeComQueue
from ....engine.communication import manifest_pmax, manifest_pmin

# n*F at or above this: quantile/bin on device (one sharded pass) instead of
# per-column host numpy — shared by tree binning (tree/hist.py) and
# QuantileDiscretizerTrainBatchOp so the cutover is tuned in one place
DEVICE_BINNING_MIN_CELLS = 2_000_000
#: cells a column's fine histogram has
FINE_BINS = 8192


def count_path(fine_bins: int) -> str:
    """Who counts a block's cells: the ``"onehot"`` product on a TPU (the
    grid must split into 128-cell rows), a ``"scatter"`` elsewhere."""
    if jax.default_backend() == "tpu" and fine_bins % LANES == 0:
        return "onehot"
    return "scatter"


def _cell_counts(cell, fine_bins: int, path: str):
    """(F, fine_bins) int32 counts of one block's cells ``(F, S, 128)``;
    a cell of -1 (padding, NaN) is counted nowhere."""
    F = cell.shape[0]
    cell = cell.reshape(F, -1)
    if path == "onehot":
        hi, lo = cell // LANES, cell % LANES
        oh_hi = (hi[:, None, :] == jnp.arange(
            fine_bins // LANES, dtype=jnp.int32)[None, :, None])
        oh_lo = (lo[:, None, :] == jnp.arange(
            LANES, dtype=jnp.int32)[None, :, None])
        # 0/1 in bfloat16 are exact and a block holds under 2^24 rows, so
        # the float32 accumulation is exact too
        cnt = jnp.einsum("fhr,flr->fhl", oh_hi.astype(jnp.bfloat16),
                         oh_lo.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        return cnt.astype(jnp.int32).reshape(F, fine_bins)
    flat = (jnp.arange(F, dtype=jnp.int32)[:, None] * fine_bins
            + jnp.maximum(cell, 0))
    return jnp.zeros((F * fine_bins,), jnp.int32).at[flat.reshape(-1)].add(
        (cell >= 0).astype(jnp.int32).reshape(-1)).reshape(F, fine_bins)


def fine_histogram(col: DenseBlockColumn, env: Optional[MLEnvironment] = None,
                   fine_bins: int = FINE_BINS, program: str = "quantile_hist",
                   path: Optional[str] = None) -> Dict[str, np.ndarray]:
    """The pass over the table: ``{"hist": (F, fine_bins) counts, "mn",
    "mx": (F,), "unit": (F,) bool}``. Column ``f``'s cell ``c`` holds the
    value ``mn[f] + c`` where ``unit[f]`` (whole numbers spanning fewer
    than ``fine_bins``), else the values in ``mn + [c, c + 1) * (mx - mn)
    / fine_bins``. The engine names the program ``jit_<program>``."""
    env_ = env or MLEnvironmentFactory.get_default()
    path = path or count_path(fine_bins)
    F = col.dim
    S = col.block_rows // LANES
    dt = col.blocks.dtype
    K = int(fine_bins)

    def stage(ctx):
        Xs = ctx.get_obj("X")
        n_rows = ctx.get_obj("n_rows")
        nbl = Xs.shape[0]
        block0 = ctx.task_id * nbl
        at0 = (jax.lax.broadcasted_iota(jnp.int32, (S, LANES), 0) * LANES
               + jax.lax.broadcasted_iota(jnp.int32, (S, LANES), 1))

        def block(i):
            xb = jax.lax.dynamic_index_in_dim(Xs, i, 0, keepdims=False)
            here = ((block0 + i) * (S * LANES) + at0 < n_rows)[None]
            return xb, here & ~jnp.isnan(xb)

        def extremes(i, c):
            big, small, whole = c
            xb, valid = block(i)
            return (jnp.maximum(big, jnp.where(valid, xb, -jnp.inf)
                                .max((1, 2))),
                    jnp.minimum(small, jnp.where(valid, xb, jnp.inf)
                                .min((1, 2))),
                    whole & jnp.where(valid, xb == jnp.round(xb), True)
                    .all((1, 2)))

        big, small, whole = jax.lax.fori_loop(
            0, nbl, extremes, (jnp.full((F,), -jnp.inf, dt),
                               jnp.full((F,), jnp.inf, dt),
                               jnp.ones((F,), bool)))
        mx = manifest_pmax(big, ctx.AXIS, name="quantile_max",
                           num_workers=ctx.num_task)
        low = manifest_pmin(jnp.concatenate([small, whole.astype(dt)]),
                            ctx.AXIS, name="quantile_min",
                            num_workers=ctx.num_task)
        mn, whole = low[:F], low[F:] > 0
        span = mx - mn
        unit = whole & (span < K)
        scale = jnp.where(unit, 1.0, jnp.where(
            span > 0, K / jnp.where(span > 0, span, 1), 0)).astype(dt)

        def count(i, hist):
            xb, valid = block(i)
            cell = jnp.clip(jnp.floor((xb - mn[:, None, None])
                                      * scale[:, None, None]), 0, K - 1)
            cell = jnp.where(valid, cell.astype(jnp.int32), -1)
            # int32 across the blocks: a float32 count stops at 2^24
            return hist + _cell_counts(cell, K, path)

        hist = jax.lax.fori_loop(0, nbl, count,
                                 jnp.zeros((F, K), jnp.int32))
        ctx.put_obj("hist", ctx.all_reduce_sum(hist))
        ctx.put_obj("mn", mn)
        ctx.put_obj("mx", mx)
        ctx.put_obj("unit", unit)

    res = (IterativeComQueue(env=env_, max_iter=1)
           .init_with_partitioned_data("X", col.blocks)
           .init_with_broadcast_data("n_rows", np.int32(col.n_rows))
           .add(stage)
           .set_program_key((program, F, K, S, str(dt), path))
           .exec())
    hist, mn, mx, unit = res.get_all(["hist", "mn", "mx", "unit"])
    return {"hist": np.asarray(hist, np.float64),
            "mn": np.asarray(mn, np.float64),
            "mx": np.asarray(mx, np.float64),
            "unit": np.asarray(unit, bool)}


def distributed_quantiles(X, probs: np.ndarray,
                          env: Optional[MLEnvironment] = None,
                          fine_bins: int = FINE_BINS,
                          program: str = "quantile_hist") -> np.ndarray:
    """(F, len(probs)) per-column quantile values of ``X``: host rows
    ``(n, F)`` (packed once into blocks) or a ``DenseBlockColumn``, which
    may be device-resident and is read where it lies.

    NaNs are excluded per column (matching np.quantile on the non-NaN
    subset). Columns that are entirely NaN/empty return NaN (callers drop
    non-finite cut points).
    """
    env_ = env or MLEnvironmentFactory.get_default()
    col = as_block_column(X, env_.num_workers)
    F = col.dim
    probs = np.asarray(probs, np.float64)
    got = fine_histogram(col, env_, fine_bins, program)
    hist, mn, mx, unit = got["hist"], got["mn"], got["mx"], got["unit"]
    span = mx - mn

    cum = np.cumsum(hist, axis=1)                     # (F, K)
    total = cum[:, -1]                                # non-NaN count per col
    out = np.full((F, len(probs)), np.nan)
    ok = (total > 0) & np.isfinite(span)
    targets = np.outer(total, probs)                  # (F, q)
    for_cols = np.where(ok)[0]
    if for_cols.size:
        # cell index where the cumulative count reaches the target
        idx = np.stack([np.searchsorted(cum[f], targets[f], side="left")
                        for f in for_cols])
        idx = np.clip(idx, 0, fine_bins - 1)
        csel = cum[for_cols]
        prev = np.where(idx > 0,
                        np.take_along_axis(csel, np.maximum(idx - 1, 0), 1), 0.0)
        cell = np.take_along_axis(hist[for_cols], idx, 1)
        frac = np.where(cell > 0,
                        (targets[for_cols] - prev) / np.maximum(cell, 1e-300),
                        0.0)
        vals = (mn[for_cols, None]
                + (idx + np.clip(frac, 0.0, 1.0)) / fine_bins
                * span[for_cols, None])
        # one cell a value: the cell's value itself, exact
        vals = np.where(unit[for_cols, None], mn[for_cols, None] + idx, vals)
        out[for_cols] = np.clip(vals, mn[for_cols, None], mx[for_cols, None])
    return out
