"""Boost loop: one cached table, boosted again and again.

Drives ``GbdtTrainBatchOp().set_num_trees(..)...link_from(source)`` — the
entry a user calls — on a source whose table holds its 13 features as ONE
dense block column on the device (``DenseBlockColumn``) and its label as a
per-row block column beside it (``RowBlockColumn``). Both are drawn from
the seed on the device, block by block, and never exist on the host.

Set-up is the table and ``warm_fits`` fits (they compile and touch every
buffer; the FIRST is what ``correct`` compares: same call, same table,
same programs as the window's). The window is fits back to back, fit ``i``
seeded from (``--seed``, ``i``), EVERY fit binning from the raw table: it
starts at a fit boundary and ends at the first fit boundary at or after
``--seconds`` and after at least ``min_fits`` fits. A fit is complete when
its model table is in the caller's hands. Nothing is drawn inside the
window but the fits' own seeds, and every run of the cell does the same
sequence of work.
"""

from __future__ import annotations

# first thing: the public symbol this path stands on. A program without
# the per-row block column cannot run the cell, and says so before any
# table is built.
from alink_tpu.common.columnar import RowBlockColumn

import time
import traceback
from typing import Dict, List

import numpy as np

from .. import airline, opcount, opcount_gbdt
from ..reference import gbdt as ref_gbdt
from .fit_loop import _counter, fit_seed

VECTOR_COL = "features"
LABEL_COL = "label"
GAPS = ("edge_rank_gap", "split_gain_gap", "leaf_gap", "loss_gap",
        "count_gap")


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        self.n = int(cfg["rows"])
        self.features = int(cfg["features"])
        self.block_rows = int(cfg["block_rows"])
        self.warm_fits = int(tr["warm_fits"])
        self.min_fits = int(tr["min_fits"])
        if self.warm_fits < 1:
            raise ValueError("the first warm fit is the one compared")
        self.table = self.labels = None
        self.source = None
        self.first: Dict = {}
        self.fit_s: List[float] = []

    # -- set-up ---------------------------------------------------------
    def _make_source(self):
        from alink_tpu.common.columnar import DenseBlockColumn
        from alink_tpu.common.mtable import MTable
        from alink_tpu.common.types import TableSchema
        from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
        ctx = self.ctx
        with ctx.spans.span("make_table"):
            self.table, self.labels = airline.make_table(
                ctx.seed, self.n, self.block_rows, ctx.config["generator"])
            self.table.block_until_ready()
        self.source = MemSourceBatchOp(MTable(
            {VECTOR_COL: DenseBlockColumn(self.table, self.n),
             LABEL_COL: RowBlockColumn(self.labels, self.n)},
            TableSchema.parse(f"{VECTOR_COL} VECTOR, {LABEL_COL} DOUBLE")))

    def fit(self, i: int):
        """Fit ``i``, through the operator; the op, or ``None`` where the
        fit raised or its model holds a number that is not finite."""
        from alink_tpu.operator.batch.classification.tree_ops import (
            GbdtTrainBatchOp, TreeModelDataConverter)
        cfg = self.ctx.config
        self.ctx.attempted += 1
        try:
            with self.ctx.spans.span("fit"):
                op = (GbdtTrainBatchOp()
                      .set_vector_col(VECTOR_COL).set_label_col(LABEL_COL)
                      .set_num_trees(int(cfg["num_trees"]))
                      .set_max_depth(int(cfg["max_depth"]))
                      .set_max_bins(int(cfg["max_bins"]))
                      .set_learning_rate(float(cfg["learning_rate"]))
                      .set_min_samples_per_leaf(
                          int(cfg["min_samples_per_leaf"]))
                      .set_reg_lambda(float(cfg["reg_lambda"]))
                      .set_subsampling_ratio(float(cfg["subsampling_ratio"]))
                      .set_feature_subsampling_ratio(
                          float(cfg["feature_subsampling_ratio"]))
                      .set_seed(fit_seed(self.ctx.seed, i))
                      .link_from(self.source))
                model = TreeModelDataConverter().load_model(
                    op.get_output_table())
            if not (np.isfinite(model.leaf_values).all()
                    and np.isfinite(model.thresholds).all()):
                raise FloatingPointError("the model holds a non-finite number")
        except Exception as e:                    # a failed fit is counted
            self.ctx.say(f"fit {i} failed: {type(e).__name__}: {e}\n"
                         + traceback.format_exc())
            self.ctx.failed += 1
            return None
        return op

    # -- the run ----------------------------------------------------------
    def run(self) -> None:
        ctx = self.ctx
        self._make_source()
        for i in range(self.warm_fits):
            t = time.perf_counter()
            op = self.fit(i)
            ctx.say(f"warm fit {i}: {time.perf_counter() - t:.2f} s")
            if i == 0:
                if op is None:
                    raise RuntimeError("the first fit failed")
                self.first = dict(op.get_train_info())
                ctx.say(f"histogram path: {self.first['hist']}")
        ctx.attempted = ctx.failed = 0            # the window's own count
        rows0 = _counter("alink_gbdt_rows_total")
        trees0 = _counter("alink_gbdt_trees_total")
        fits0 = _counter("alink_gbdt_fits_total")
        i = self.warm_fits
        t0 = ctx.begin_window()
        last = t0
        while True:
            self.fit(i)
            i += 1
            now = time.perf_counter()
            self.fit_s.append(now - last)
            last = now
            if (now - t0 >= ctx.window_seconds
                    and (ctx.trace or len(self.fit_s) >= self.min_fits)):
                break
        t1 = last
        ctx.end_window()
        fits = i - self.warm_fits
        elapsed = t1 - t0
        cfg = ctx.config
        ctx.e2e["train_rate"] = self.n * (fits - ctx.failed) / elapsed
        ctx.facts.update(
            fits=fits, window_s=elapsed, rows=self.n,
            fit_s_mean=elapsed / fits, fit_s_max=max(self.fit_s),
            fit_s_median=float(np.median(self.fit_s)),
            hist_path=str(self.first["hist"]),
            rows_counted=_counter("alink_gbdt_rows_total") - rows0,
            trees=_counter("alink_gbdt_trees_total") - trees0,
            fits_counted=_counter("alink_gbdt_fits_total") - fits0,
            tree_least_s=opcount.least_seconds(
                *opcount_gbdt.gbdt_tree(self.n, self.features,
                                        int(cfg["max_depth"])), ctx.peak),
            bin_least_s=opcount.least_seconds(
                *opcount_gbdt.gbdt_binning(self.n, self.features), ctx.peak))

    def release(self) -> None:
        """The table and the labels stay: the reference reads them where
        they lie."""
        self.source = None

    # -- correct ----------------------------------------------------------
    def verify(self) -> None:
        """The first fit against the plain reference on the same table,
        teacher-forced: the edges' ranks, and over the fit's own trees the
        gains, the leaves, the loss and the nodes' rows recounted."""
        ctx = self.ctx
        numbers = compare_first_fit(self.table, self.labels, self.n,
                                    self.first, ref_gbdt.learner(ctx.config))
        lim = ctx.config["limits"]
        for name in GAPS:
            ctx.check(name, numbers[name], float(lim[name]))
        ctx.check("rows_gap", abs(
            ctx.facts["rows_counted"] - self.n * ctx.facts["trees"]), 0.0)
        self.table = self.labels = None


def compare_first_fit(table, labels, n_rows: int, info: Dict, params: Dict,
                      dtype: str = "float32") -> Dict[str, float]:
    """The numbers ``correct`` compares, from what one fit went through
    (``info``: ``GbdtTrainBatchOp.get_train_info()``, or the controls'
    stand-in). ``dtype`` is the controls': the recount itself in a lower
    precision."""
    T = int(np.asarray(info["features"]).shape[0])
    want = ref_gbdt.recount(table, labels, n_rows, info["edges"], info,
                            params, hist_trees=sorted({0, T - 1}),
                            dtype=dtype)
    out = ref_gbdt.gaps(info, want, params)
    out["edge_rank_gap"] = ref_gbdt.edge_rank_gap(
        table, n_rows, np.asarray(info["edges"]), int(params["max_bins"]))
    return out
