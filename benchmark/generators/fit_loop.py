"""Fit loop: one cached table, fitted again and again.

Drives ``KMeansTrainBatchOp().set_k(..)...link_from(source)`` — the entry a
user calls — on a source whose table holds its feature vectors as ONE
dense block column on the device (``DenseBlockColumn``: the zero-copy
branch of the program's ``extract_design``). The table is drawn from the
seed on the device, block by block, and never exists on the host.

Set-up is the table and ``warm_fits`` fits (they compile and touch every
buffer; the FIRST is what ``correct`` compares: same call, same table,
same programs as the window's). The window is fits back to back, fit ``i``
seeded from (``--seed``, ``i``): it starts at a fit boundary and ends at
the first fit boundary at or after ``--seconds``. A fit is complete when
its model table is in the caller's hands. Nothing is drawn inside the
window but the fits' own seeds, and every run of the cell does the same
sequence of work.
"""

from __future__ import annotations

# first thing: the public symbol this path stands on. A program without
# the dense block column cannot run the cell, and says so before any
# table is built.
from alink_tpu.common.columnar import DenseBlockColumn

import time
import traceback
from typing import Dict, List

import numpy as np

from .. import blobs, opcount, opcount_kmeans
from ..reference import kmeans as ref_kmeans

VECTOR_COL = "features"


def fit_seed(seed: int, i: int) -> int:
    """The seed of fit ``i`` of a run: from (``--seed``, ``i``), inside
    what ``set_seed`` and ``numpy.random.RandomState`` take."""
    return int(np.random.default_rng([int(seed), 9, int(i)])
               .integers(0, 2 ** 31 - 1))


def _counter(name: str) -> int:
    from alink_tpu.common.metrics import get_registry
    return int(sum(float(r["value"]) for r in get_registry().snapshot()
                   if r["name"] == name and "value" in r))


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        self.n = int(cfg["rows"])
        self.d = int(cfg["dimensions"])
        self.k = int(cfg["k"])
        self.max_iter = int(cfg["max_iter"])
        self.rounds = int(cfg["init_rounds"])
        self.oversample = int(cfg["init_oversample"])
        self.block_rows = int(cfg["block_rows"])
        self.warm_fits = int(tr["warm_fits"])
        if self.warm_fits < 1:
            raise ValueError("the first warm fit is the one compared")
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
            mix = blobs.mixture(ctx.seed, int(ctx.config["num_of_clusters"]),
                                self.d, ctx.config["generator"])
            self.table = blobs.make_table(ctx.seed, self.n, self.d,
                                          self.block_rows, mix)
            self.table.block_until_ready()
        self.source = MemSourceBatchOp(MTable(
            {VECTOR_COL: DenseBlockColumn(self.table, self.n)},
            TableSchema.parse(f"{VECTOR_COL} VECTOR")))

    def fit(self, i: int):
        """Fit ``i``, through the operator; the op, or ``None`` where the
        fit raised or its model holds a number that is not finite."""
        from alink_tpu.operator.batch.clustering.kmeans_ops import (
            KMeansModelDataConverter, KMeansTrainBatchOp)
        cfg = self.ctx.config
        self.ctx.attempted += 1
        try:
            with self.ctx.spans.span("fit"):
                op = (KMeansTrainBatchOp()
                      .set_vector_col(VECTOR_COL).set_k(self.k)
                      .set_max_iter(self.max_iter)
                      .set_epsilon(float(cfg["epsilon"]))
                      .set_distance_type(cfg["distance"])
                      .set_init_mode(cfg["init"])
                      .set_seed(fit_seed(self.ctx.seed, i))
                      .link_from(self.source))
                model = KMeansModelDataConverter().load_model(
                    op.get_output_table())
            if not (np.isfinite(model.centroids).all()
                    and np.isfinite(model.weights).all()):
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
        ctx.attempted = ctx.failed = 0            # the window's own count
        rows0 = _counter("alink_kmeans_rows_total")
        steps0 = _counter("alink_kmeans_supersteps_total")
        fits0 = _counter("alink_kmeans_fits_total")
        i = self.warm_fits
        t0 = ctx.begin_window()
        last = t0
        while True:
            self.fit(i)
            i += 1
            now = time.perf_counter()
            self.fit_s.append(now - last)
            last = now
            if now - t0 >= ctx.window_seconds:
                break
        t1 = last
        ctx.end_window()
        fits = i - self.warm_fits
        elapsed = t1 - t0
        ctx.e2e["train_rate"] = self.n * (fits - ctx.failed) / elapsed
        ctx.facts.update(
            fits=fits, window_s=elapsed, rows=self.n,
            fit_s_mean=elapsed / fits, fit_s_max=max(self.fit_s),
            fit_s_median=float(np.median(self.fit_s)),
            rows_counted=_counter("alink_kmeans_rows_total") - rows0,
            supersteps=_counter("alink_kmeans_supersteps_total") - steps0,
            fits_counted=_counter("alink_kmeans_fits_total") - fits0,
            step_least_s=opcount.least_seconds(
                *opcount_kmeans.kmeans_superstep(self.n, self.d, self.k),
                ctx.peak))

    def release(self) -> None:
        """The table stays: the reference reads it where it lies."""
        self.source = None

    # -- correct ----------------------------------------------------------
    def verify(self) -> None:
        """The first fit against the plain reference on the same table:
        Lloyd replayed from the fit's own initial centroids, the
        k-means|| candidates' weights recounted and their membership of
        the table confirmed."""
        ctx = self.ctx
        numbers = compare_first_fit(self.table, self.n, self.first,
                                    self.rounds, self.oversample)
        lim = ctx.config["limits"]
        for name in ("centroid_gap", "weight_gap", "inertia_gap",
                     "init_weight_gap", "init_member_gap"):
            ctx.check(name, numbers[name], float(lim[name]))
        ctx.check("rows_gap", abs(
            ctx.facts["rows_counted"] - self.n * ctx.facts["supersteps"]), 0.0)
        self.table = None


def folded_candidates(rounds: int, oversample: int) -> int:
    """Candidates whose weights k-means|| counts: the first and every
    round's but the last's."""
    return 1 + (rounds - 1) * oversample


def compare_first_fit(table, n_rows: int, info: Dict, rounds: int,
                      oversample: int, dtype: str = "float32",
                      **fault) -> Dict[str, float]:
    """The numbers ``correct`` compares, from what one fit went through
    (``info``: ``KMeansTrainBatchOp.get_train_info()``). ``dtype`` and
    ``fault`` are the controls': the reference computed in a lower
    precision, or with a fault planted (``reference.kmeans.lloyd``)."""
    spread = ref_kmeans.rms_spread(table, n_rows)
    want = ref_kmeans.lloyd(table, n_rows, None, info["init_centroids"],
                            int(info["steps"]), dtype, **fault)
    out = ref_kmeans.gaps(info, want, float(n_rows), spread)
    m = folded_candidates(rounds, oversample)
    cands = np.asarray(info["init_candidates"])
    recount = ref_kmeans.candidate_weights(table, n_rows, None, cands[:m],
                                           dtype)
    out["init_weight_gap"] = float(np.abs(
        np.asarray(info["init_weights"], np.float64)[:m] - recount).sum()
    ) / n_rows
    out["init_member_gap"] = float(
        ref_kmeans.member_gaps(table, n_rows, cands).max()) / spread
    return out
