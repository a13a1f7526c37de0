"""Closed drain: a stream trainer fed as fast as it takes rows.

Drives ``FtrlTrainStreamOp(warm).link_from(source).micro_batches()`` — the
entry a user calls — from a source of the benchmark's own that hands out
columnar, pre-hashed micro-batches (``SparseVectorColumn``: the zero-copy
branch of the program's ``extract_design``) cycling a pool made from the
seed. Event time is the batch index, so the trainer emits a snapshot every
``snapshot_every`` micro-batches; every snapshot goes to the device
consumer below, which waits until the weights are there.

One iteration of ``micro_batches()`` carries the whole run: the first
``warm_cycles`` snapshot cycles are set-up (they compile and touch every
buffer, and the first snapshot is what ``correct`` compares), the window
starts on the boundary that ends them and ends on the first boundary at or
after ``--seconds``. Nothing is drawn inside the window, and every run of
the cell does the same sequence of work.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

from .. import data, opcount
from ..reference import ftrl as ref_ftrl

#: coordinates outside the first cycle's rows whose weights are compared
#: with the warm start's closed form
UNTOUCHED_SAMPLE = 1 << 16


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        self.hp = {k: float(cfg["ftrl"][k]) for k in ("alpha", "beta", "l1", "l2")}
        self.dim = 1 << int(cfg["dim_log2"])          # intercept included
        self.batch_rows = int(cfg["batch_rows"])
        self.every = int(tr["snapshot_every"])
        self.warm_cycles = int(tr["warm_cycles"])
        self.pool_rows = int(tr["pool_rows"])
        if self.pool_rows % self.batch_rows:
            raise ValueError("pool_rows must be whole micro-batches")
        self.stop = threading.Event()
        self.pull_t: List[float] = []                  # when batch k was handed out
        self.snap_t: List[float] = []                  # when snapshot c was in hand
        self.snap_batch: List[int] = []
        self.current_w = None
        self.first_at = None                           # first snapshot at check_idx
        self.t0 = self.t1 = None
        self.b0 = self.b1 = None
        self.rows_counter0 = self.rows_counter1 = None

    # -- set-up ---------------------------------------------------------
    def _make_inputs(self):
        ctx = self.ctx
        n_feat = self.dim - 1
        with ctx.spans.span("make_pool"):
            self.idx, self.val, self.click = data.make_rows(
                ctx.seed, self.pool_rows, ctx.config["row_shape"], n_feat)
        with ctx.spans.span("make_warm"):
            self.coef = data.host_weights(ctx.seed, self.dim,
                                          float(ctx.config["warm_scale"]), 0)
        self.first_batches = self.every + 1     # event times 0..every
        n_first = self.first_batches * self.batch_rows
        if n_first > self.pool_rows:
            raise ValueError("the pool is shorter than the first snapshot cycle")
        self.check_idx = check_coordinates(ctx.seed, self.idx, n_first,
                                           self.dim)

    def _source(self):
        from alink_tpu.common.mtable import MTable
        from alink_tpu.common.types import TableSchema
        from alink_tpu.common.vector import SparseVectorColumn
        from alink_tpu.operator.base import StreamOperator

        gen = self
        schema = TableSchema.parse("vec VECTOR, click LONG")
        n_feat = self.dim - 1
        per_pool = self.pool_rows // self.batch_rows

        class PoolSource(StreamOperator):
            """Pre-hashed columnar micro-batches, cycling the pool."""

            def __init__(self):
                super().__init__()
                self._schema = schema
                self._stream_fn = self._batches

            def link_from(self, *inputs):
                raise RuntimeError("PoolSource is a source")

            def _batches(self):
                k = 0
                B = gen.batch_rows
                while not gen.stop.is_set():
                    a = (k % per_pool) * B
                    mt = MTable({"vec": SparseVectorColumn(
                        gen.idx[a:a + B], gen.val[a:a + B], n_feat),
                        "click": gen.click[a:a + B]}, schema)
                    gen.pull_t.append(time.perf_counter())
                    yield (float(k), mt)
                    k += 1

        return PoolSource()

    def _trainer(self, source):
        from alink_tpu.common.types import AlinkTypes
        from alink_tpu.operator.common.linear.base import (LinearModelData,
                                                           LinearModelType)
        from alink_tpu.operator.stream.onlinelearning.ftrl import (
            FtrlTrainStreamOp)

        warm = LinearModelData(
            model_name="warm", linear_model_type=LinearModelType.LR,
            has_intercept=True, vector_col="vec", feature_names=None,
            vector_size=self.dim - 1, coef=self.coef,
            label_values=[1, 0], label_type=AlinkTypes.LONG)

        class WarmStarted(FtrlTrainStreamOp):
            """The trainer, handed its warm start as the model data
            itself: the model TABLE carries coefficients as JSON text,
            which 2^29 of them cannot pass through (PERF.md, open
            questions). Nothing else of the op is touched."""

            def _load_initial(self):
                return warm

        op = WarmStarted(None, vector_col="vec", label_col="click",
                         time_interval=float(self.every), **self.hp)
        op.set_device_snapshot_consumer(self._on_snapshot)
        return op.link_from(source)

    # -- the consumer of every snapshot --------------------------------
    def _on_snapshot(self, w_device, info) -> bool:
        ctx = self.ctx
        with ctx.spans.span("snapshot_take"):
            w_device.block_until_ready()
        now = time.perf_counter()
        self.current_w = w_device          # the newest replaces the last
        self.snap_t.append(now)
        self.snap_batch.append(int(info["batch"] or 0))
        c = len(self.snap_t)
        if c == 1:
            with ctx.spans.span("check_gather"):
                self.first_at = self._take(w_device, self.check_idx_dev)
                self.first_at.block_until_ready()
        if self.stop.is_set():
            return True
        if c == self.warm_cycles:
            self.rows_counter0 = _rows_total()
            self.b0 = self.snap_batch[-1]
            self.t0 = ctx.begin_window()
        elif c > self.warm_cycles \
                and time.perf_counter() - self.t0 >= ctx.window_seconds:
            self.t1 = now
            self.b1 = self.snap_batch[-1]
            self.rows_counter1 = _rows_total()
            ctx.end_window()
            self.stop.set()
        return True

    # -- the run ----------------------------------------------------------
    def run(self) -> None:
        import jax
        import jax.numpy as jnp
        ctx = self.ctx
        self._make_inputs()
        self._take = jax.jit(lambda w, i: jnp.take(w, i, axis=0))
        self.check_idx_dev = jax.device_put(self.check_idx)
        with ctx.spans.span("link"):
            op = self._trainer(self._source())
        with ctx.spans.span("drain"):
            for _ in op.micro_batches():
                pass            # every snapshot went to the consumer
        if self.t1 is None:
            raise RuntimeError("the drain ended before its window did")
        rows = (self.b1 - self.b0) * self.batch_rows
        elapsed = self.t1 - self.t0
        ctx.e2e["train_rate"] = rows / elapsed
        ctx.attempted = self.b1 - self.b0
        ctx.failed = 0
        inside = [i for i, t in enumerate(self.snap_t)
                  if self.t0 < t <= self.t1 + 1e-9]
        ctx.facts.update(
            rows=rows, window_s=elapsed, micro_batches=self.b1 - self.b0,
            snapshots=len(inside), batch_rows=self.batch_rows,
            nnz=self.idx.shape[1] + 1,
            snapshot_s=[self.snap_t[i] - self.pull_t[self.snap_batch[i] - 1]
                        for i in inside],
            step_least_s=opcount.least_seconds(
                *opcount.ftrl_step(self.batch_rows, self.idx.shape[1] + 1),
                ctx.peak))
        ctx.facts["rows_counted"] = self.rows_counter1 - self.rows_counter0

    def release(self) -> None:
        self.current_w = None
        self.check_idx_dev = None

    # -- correct ----------------------------------------------------------
    def verify(self) -> None:
        """The first snapshot against the plain reference over the same
        65 micro-batches, sample by sample."""
        ctx = self.ctx
        got = np.asarray(self.first_at, np.float64)
        self.first_at = None
        numbers = compare_first_cycle(
            got, self.check_idx, self.idx, self.val, self.click, self.coef,
            self.first_batches * self.batch_rows, self.hp, "float32")
        lim = ctx.config["limits"]
        for name in ("dw_norm_gap", "w_worst_gap", "untouched_gap"):
            ctx.check(name, numbers[name], float(lim[name]))
        ctx.check("rows_gap",
                  abs(ctx.facts["rows_counted"] - ctx.facts["rows"]), 0.0)


def check_coordinates(seed: int, idx: np.ndarray, n_first: int, dim: int
                      ) -> np.ndarray:
    """Where the first snapshot is looked at: the intercept, every
    coordinate of the first cycle's rows (the trainer keeps the intercept
    at 0 and feature j at j + 1), and a sample of others from the seed."""
    rng = np.random.default_rng([int(seed), 3])
    sample = rng.integers(0, dim, UNTOUCHED_SAMPLE, dtype=np.int64)
    return np.concatenate([
        np.zeros(1, np.int64),
        idx[:n_first].reshape(-1).astype(np.int64) + 1,
        sample]).astype(np.int32)


def _rows_total() -> float:
    from alink_tpu.common.metrics import get_registry
    return sum(float(r["value"]) for r in get_registry().snapshot()
               if r["name"] == "alink_ftrl_rows_total" and "value" in r)


def reference_first_cycle(check_idx: np.ndarray, idx: np.ndarray,
                          val: np.ndarray, click: np.ndarray,
                          coef: np.ndarray, n_first: int, hp: Dict[str, float],
                          dtype: str, keep_rows: Optional[np.ndarray] = None):
    """What the first snapshot should hold at the distinct coordinates of
    ``check_idx``, and the warm start there: ``(w1_ref, w0_ref, touched,
    first)`` as arrays over those coordinates, ``first`` being where each
    first stands in ``check_idx``. ``keep_rows`` (a fault's) masks rows
    out."""
    import jax.numpy as jnp
    rows_state = np.concatenate(
        [np.zeros((n_first, 1), np.int64), idx[:n_first].astype(np.int64) + 1],
        axis=1)
    rows_val = np.concatenate(
        [np.ones((n_first, 1), np.float32), val[:n_first]], axis=1)
    y = click[:n_first].astype(np.float32)
    if keep_rows is not None:
        rows_state, rows_val, y = (rows_state[keep_rows], rows_val[keep_rows],
                                   y[keep_rows])
    uniq, inv = np.unique(rows_state.reshape(-1), return_inverse=True)
    compact = inv.reshape(rows_state.shape).astype(np.int32)
    z0, n0 = ref_ftrl.warm_state(coef[uniq], hp["alpha"], hp["beta"], hp["l2"])
    z1, n1 = ref_ftrl.run(compact, rows_val, y, z0, n0, hp, dtype)
    f32 = jnp.float32
    w1_u = np.asarray(ref_ftrl.weights(z1.astype(f32), n1.astype(f32), **hp),
                      np.float64)
    # the warm start everywhere the check looks, in ``dtype`` like the rest
    # of the snapshot; each coordinate once, however many rows hold it
    c, first = np.unique(check_idx.astype(np.int64), return_index=True)
    zc, nc = ref_ftrl.warm_state(coef[c], hp["alpha"], hp["beta"], hp["l2"])
    dt = jnp.dtype(dtype)
    w0 = np.asarray(ref_ftrl.weights(zc.astype(dt), nc.astype(dt), **hp)
                    .astype(f32), np.float64)
    pos = np.minimum(np.searchsorted(uniq, c), len(uniq) - 1)
    touched = uniq[pos] == c
    w1 = np.where(touched, w1_u[pos], w0)
    return w1, w0, touched, first


def gaps(got: np.ndarray, w1: np.ndarray, w0: np.ndarray,
         touched: np.ndarray) -> Dict[str, float]:
    """The numbers ``correct`` compares, program (or control) against the
    reference, all over distinct coordinates."""
    change_ref = (w1 - w0)[touched]
    change_got = (got - w0)[touched]
    norm_ref = float(np.sqrt(np.mean(change_ref ** 2)))
    norm_got = float(np.sqrt(np.mean(change_got ** 2)))
    scale0 = float(np.sqrt(np.mean(w0 ** 2)))
    out = {
        "dw_norm_gap": abs(norm_got - norm_ref) / norm_ref,
        "w_worst_gap": float(np.max(np.abs(got - w1)[touched])) / norm_ref,
        "untouched_gap": (float(np.max(np.abs(got - w1)[~touched])) / scale0
                          if (~touched).any() else 0.0),
    }
    return out


def compare_first_cycle(got, check_idx, idx, val, click, coef, n_first, hp,
                        dtype) -> Dict[str, float]:
    w1, w0, touched, first = reference_first_cycle(
        check_idx, idx, val, click, coef, n_first, hp, dtype)
    return gaps(np.asarray(got, np.float64)[first], w1, w0, touched)
