"""L-BFGS loop: one cached table of one-byte pixels, fitted again and again.

Drives ``SoftmaxTrainBatchOp().set_vector_col(..).set_label_col(..)
...link_from(source)`` — the entry a user calls — on a source whose table
holds its feature vectors as ONE dense block column of bytes on the device
(``DenseBlockColumn``, uint8: the zero-copy branch of the program's
``extract_design``) and its labels as a per-row block column of whole
numbers (``RowBlockColumn``, int32). Both are drawn from the seed on the
device, block by block, and never exist on the host.

Set-up is the table and ``warm_fits`` fits (they compile and touch every
buffer; the FIRST is what ``correct`` compares: same call, same table,
same programs as the window's). The window is fits back to back, one at a
time, fit ``i`` at ``l2 = l2_ladder[i mod len]`` (a sweep over the penalty,
which the step program takes as data: nothing compiles in the window),
EVERY fit taking its moments from the raw table: it starts at a fit
boundary and ends at the first fit boundary at or after ``--seconds`` and
after at least ``min_fits`` fits. A fit is complete when its model table
and its loss curve are in the caller's hands (that fetch waits for the
device). Nothing is drawn inside the window, and every run of the cell
does the same sequence of work.
"""

from __future__ import annotations

# first thing: what this path reads of the program by name. A program
# without the blocked linear fit (its step and moments programs, its
# counters) cannot run the cell, and says so here, in an ImportError,
# before any table is drawn: the older fit would copy the table to the host
# and write it out again in floats.
from alink_tpu.operator.common.linear.base import MOMENTS_PROGRAM
from alink_tpu.operator.common.optim.optimizers import QN_PROGRAM
from alink_tpu.common.columnar import DenseBlockColumn, RowBlockColumn

import time
import traceback
from typing import Dict, List

import numpy as np

from .. import mnist8m, opcount, opcount_linear
from ..reference import softmax as ref_softmax
from .fit_loop import _counter

VECTOR_COL, LABEL_COL = "pixels", "label"


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        self.n = int(cfg["rows"])
        self.d = int(cfg["features"])
        self.k = int(cfg["classes"])
        self.max_iter = int(cfg["max_iter"])
        self.block_rows = int(cfg["block_rows"])
        self.l2_ladder = [float(v) for v in cfg["l2_ladder"]]
        self.warm_fits = int(tr["warm_fits"])
        self.min_fits = int(tr["min_fits"])
        if self.warm_fits < 1:
            raise ValueError("the first warm fit is the one compared")
        for key, name in (("step_program", QN_PROGRAM),
                          ("moments_program", MOMENTS_PROGRAM)):
            if cfg[key] != "jit_" + name:
                raise ValueError(f"the configuration's {key} is not the "
                                 f"program's jit_{name}")
        self.table = None
        self.labels = None
        self.source = None
        self.first: Dict = {}
        self.fit_s: List[float] = []
        self.steps_min = self.max_iter

    # -- set-up ---------------------------------------------------------
    def _make_source(self):
        from alink_tpu.common.mtable import MTable
        from alink_tpu.common.types import TableSchema
        from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
        ctx = self.ctx
        with ctx.spans.span("make_table"):
            self.table, self.labels = mnist8m.make_table(
                ctx.seed, self.n, self.block_rows, ctx.config["generator"])
            self.labels.block_until_ready()
        self.source = MemSourceBatchOp(MTable(
            {VECTOR_COL: DenseBlockColumn(self.table, self.n),
             LABEL_COL: RowBlockColumn(self.labels, self.n)},
            TableSchema.parse(f"{VECTOR_COL} VECTOR, {LABEL_COL} INT")))

    def fit(self, i: int):
        """Fit ``i``, through the operator; its train info, or ``None``
        where the fit raised or its curve holds a number that is not
        finite."""
        from alink_tpu.operator.batch.classification.linear import (
            SoftmaxTrainBatchOp)
        cfg = self.ctx.config
        self.ctx.attempted += 1
        try:
            with self.ctx.spans.span("fit"):
                op = (SoftmaxTrainBatchOp()
                      .set_vector_col(VECTOR_COL).set_label_col(LABEL_COL)
                      .set_optim_method(cfg["optim_method"])
                      .set_max_iter(self.max_iter)
                      .set_epsilon(float(cfg["epsilon"]))
                      .set_learning_rate(float(cfg["learning_rate"]))
                      .set_l1(float(cfg["l1"]))
                      .set_l2(self.l2_ladder[i % len(self.l2_ladder)])
                      .set_with_intercept(bool(cfg["with_intercept"]))
                      .set_standardization(bool(cfg["standardization"]))
                      .link_from(self.source))
                info = op.get_train_info()
                curve = np.asarray(info.col("loss"))
                if op.get_output_table().num_rows < 2 + self.k:
                    raise RuntimeError("the model table is short")
            if not (len(curve) and np.isfinite(curve).all()):
                raise FloatingPointError("the loss curve holds a number "
                                         "that is not finite")
        except Exception as e:                    # a failed fit is counted
            self.ctx.say(f"fit {i} failed: {type(e).__name__}: {e}\n"
                         + traceback.format_exc())
            self.ctx.failed += 1
            return None
        return info

    # -- the run ----------------------------------------------------------
    def run(self) -> None:
        ctx = self.ctx
        self._make_source()
        for i in range(self.warm_fits):
            t = time.perf_counter()
            info = self.fit(i)
            ctx.say(f"warm fit {i}: {time.perf_counter() - t:.2f} s")
            if i == 0:
                if info is None:
                    raise RuntimeError("the first fit failed")
                self.first = dict(info)
                ctx.say(f"paths: {self.first['paths']}; rungs "
                        f"{[int(r) for r in self.first['rung_trace']]}; "
                        f"loss {float(self.first['loss_curve'][0]):.6g} -> "
                        f"{float(self.first['loss_curve'][-1]):.6g}")
            del info
        ctx.attempted = ctx.failed = 0            # the window's own count
        rows0 = _counter("alink_linear_rows_total")
        steps0 = _counter("alink_linear_supersteps_total")
        passes0 = _counter("alink_linear_passes_total")
        fits0 = _counter("alink_linear_fits_total")
        i = self.warm_fits
        t0 = ctx.begin_window()
        last = t0
        while True:
            info = self.fit(i)
            if info is not None:
                self.steps_min = min(self.steps_min, int(info["steps"]))
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
        paths = self.first["paths"]
        ctx.e2e["train_rate"] = self.n * (fits - ctx.failed) / elapsed
        ctx.facts.update(
            fits=fits, window_s=elapsed, rows=self.n,
            fit_s_mean=elapsed / fits, fit_s_max=max(self.fit_s),
            fit_s_median=float(np.median(self.fit_s)),
            supersteps_min=self.steps_min, max_iter=self.max_iter,
            design_path=str(paths["design"]), pass_path=str(paths["pass"]),
            rows_counted=_counter("alink_linear_rows_total") - rows0,
            supersteps=_counter("alink_linear_supersteps_total") - steps0,
            passes=_counter("alink_linear_passes_total") - passes0,
            fits_counted=_counter("alink_linear_fits_total") - fits0,
            step_least_s=opcount.least_seconds(
                *opcount_linear.softmax_superstep(self.n, self.d, self.k),
                ctx.peak),
            moments_least_s=opcount.least_seconds(
                *opcount_linear.moments_pass(self.n, self.d), ctx.peak))

    def release(self) -> None:
        """The table stays: the reference reads it where it lies."""
        self.source = None

    # -- correct ----------------------------------------------------------
    def verify(self) -> None:
        """The first fit against the plain reference on the same table,
        teacher-forced: its first, a middle and its last superstep
        recomputed over every row from the coefficients the program
        started them from, the moments recounted, and every pass's rows
        held to the table's."""
        ctx = self.ctx
        numbers = ref_softmax.gaps(self.first, self.table, self.labels,
                                   self.n, ref_softmax.learner(ctx.config))
        lim = ctx.config["limits"]
        for name in ref_softmax.GAPS:
            ctx.check(name, numbers[name], float(lim[name]))
        ctx.check("rows_gap", abs(
            ctx.facts["rows_counted"] - self.n * ctx.facts["passes"]), 0.0)
        self.table = self.labels = None
        self.first = {}
