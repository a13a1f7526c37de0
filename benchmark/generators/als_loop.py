"""ALS loop: one cached table of ratings, factored again and again.

Drives ``AlsTrainBatchOp().set_rank(..)...link_from(source)`` — the entry
a user calls — on a source whose table holds its three columns (user,
item, rating) as per-row block columns on the device (``RowBlockColumn``:
two of whole numbers, one float32). They are drawn from the seed on the
device, block by block, and never exist on the host.

Set-up is the table and ``warm_fits`` fits (they compile and touch every
buffer; the FIRST is what ``correct`` compares: same call, same table,
same programs as the window's). The window is fits back to back, fit ``i``
seeded from (``--seed``, ``i``), EVERY fit grouping from the raw table: it
starts at a fit boundary and ends at the first fit boundary at or after
``--seconds`` and after at least ``min_fits`` fits. A fit is complete when
its model table is in the caller's hands and its RMSE curve has been
fetched (that fetch waits for the device); the factors stay on the device,
in the model table. Nothing is drawn inside the window but the fits' own
seeds, and every run of the cell does the same sequence of work.
"""

from __future__ import annotations

# first thing: what this path reads of the program by name. A program
# without the blocked ALS fit (its path words, its counters) cannot run the
# cell, and says so here, in an ImportError, before any table is drawn:
# the older fit would walk every rating of the table in Python.
from alink_tpu.operator.common.recommendation.als import (GROUP_PROGRAM,
                                                          SWEEP_PROGRAM)
from alink_tpu.common.columnar import RowBlockColumn

import time
import traceback
from typing import Dict, List

import numpy as np

from .. import opcount, opcount_als, yahoo
from ..reference import als as ref_als
from .fit_loop import _counter, fit_seed

USER_COL, ITEM_COL, RATE_COL = "user", "item", "rating"
GAPS = ("user_solve_gap", "item_solve_gap", "count_gap", "rmse_gap")


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        self.n = int(cfg["ratings"])
        self.users, self.items = int(cfg["users"]), int(cfg["items"])
        self.block_rows = int(cfg["block_rows"])
        self.warm_fits = int(tr["warm_fits"])
        self.min_fits = int(tr["min_fits"])
        if self.warm_fits < 1:
            raise ValueError("the first warm fit is the one compared")
        for key, name in (("step_program", SWEEP_PROGRAM),
                          ("group_program", GROUP_PROGRAM)):
            if cfg[key] != "jit_" + name:
                raise ValueError(f"the configuration's {key} is not the "
                                 f"program's jit_{name}")
        self.table = None
        self.source = None
        self.first: Dict = {}
        self.fit_s: List[float] = []

    # -- set-up ---------------------------------------------------------
    def _make_source(self):
        from alink_tpu.common.mtable import MTable
        from alink_tpu.common.types import TableSchema
        from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
        ctx = self.ctx
        with ctx.spans.span("make_table"):
            self.table = yahoo.make_table(
                ctx.seed, self.n, self.block_rows, self.users, self.items,
                ctx.config["generator"])
            self.table[2].block_until_ready()
        self.source = MemSourceBatchOp(MTable(
            {name: RowBlockColumn(col, self.n) for name, col in
             zip((USER_COL, ITEM_COL, RATE_COL), self.table)},
            TableSchema.parse(f"{USER_COL} INT, {ITEM_COL} INT, "
                              f"{RATE_COL} FLOAT")))

    def fit(self, i: int):
        """Fit ``i``, through the operator; the op, or ``None`` where the
        fit raised or its curve holds a number that is not finite."""
        from alink_tpu.operator.batch.recommendation.als_ops import (
            AlsTrainBatchOp)
        cfg = self.ctx.config
        self.ctx.attempted += 1
        try:
            with self.ctx.spans.span("fit"):
                op = (AlsTrainBatchOp()
                      .set_user_col(USER_COL).set_item_col(ITEM_COL)
                      .set_rate_col(RATE_COL)
                      .set_rank(int(cfg["rank"]))
                      .set_lambda_(float(cfg["lambda"]))
                      .set_num_iter(int(cfg["num_iter"]))
                      .set_seed(fit_seed(self.ctx.seed, i))
                      .link_from(self.source))
                curve = np.asarray(op.get_side_output(0).get_output_table()
                                   .col("train_rmse"))
                if op.get_output_table().num_rows < 5:
                    raise RuntimeError("the model table is short")
            if not (len(curve) and np.isfinite(curve).all()):
                raise FloatingPointError("the RMSE curve holds a number "
                                         "that is not finite")
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
                ctx.say(f"paths: {self.first['paths']}; rmse "
                        f"{self.first['rmse_curve']}")
            del op
        ctx.attempted = ctx.failed = 0            # the window's own count
        seen0 = _counter("alink_als_ratings_total")
        sweeps0 = _counter("alink_als_sweeps_total")
        fits0 = _counter("alink_als_fits_total")
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
        rank = int(cfg["rank"])
        ctx.e2e["train_rate"] = self.n * (fits - ctx.failed) / elapsed
        paths = self.first["paths"]
        ctx.facts.update(
            fits=fits, window_s=elapsed, rows=self.n,
            fit_s_mean=elapsed / fits, fit_s_max=max(self.fit_s),
            fit_s_median=float(np.median(self.fit_s)),
            gram_path=str(paths["gram"]), solve_path=str(paths["solve"]),
            group_path=str(paths["group"]),
            ratings_counted=_counter("alink_als_ratings_total") - seen0,
            half_sweeps=_counter("alink_als_sweeps_total") - sweeps0,
            fits_counted=_counter("alink_als_fits_total") - fits0,
            # a half-sweep's floor: half an iteration's (the two sides
            # fold the same ratings; their rows differ)
            sweep_least_s=opcount.least_seconds(
                *opcount_als.als_iteration(self.n, self.users, self.items,
                                           rank), ctx.peak) / 2,
            group_least_s=opcount.least_seconds(
                *opcount_als.als_grouping(self.n), ctx.peak))

    def release(self) -> None:
        """The table stays: the reference reads it where it lies."""
        self.source = None

    # -- correct ----------------------------------------------------------
    def verify(self) -> None:
        """The first fit against the plain reference on the same table,
        teacher-forced: sampled rows of each half-sweep solved again in
        float64 from the factors the program read, every count and the
        RMSE recounted."""
        ctx = self.ctx
        numbers = ref_als.gaps(self.first, self.table, self.n,
                               ref_als.learner(ctx.config), ctx.seed)
        lim = ctx.config["limits"]
        for name in GAPS:
            ctx.check(name, numbers[name], float(lim[name]))
        ctx.check("rows_gap", abs(
            ctx.facts["ratings_counted"]
            - self.n * ctx.facts["half_sweeps"]), 0.0)
        self.table = None
        self.first = {}
