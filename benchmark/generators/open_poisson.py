"""Open loop: requests sent on a schedule, whether or not answers come.

Drives ``PredictServer.submit(row)`` — one request is one row, as Alink's
``LocalPredictor.map`` takes them — from one sender thread that walks a
schedule of Poisson arrivals drawn from the seed during set-up, and one
collector thread that waits on the futures in the order sent. A request is
timed from when it was DUE, so a stalled sender or a blocked ``submit``
(the server's own admission: its queue is bounded and a full queue blocks
the submitter) counts against the server, and ``gen_late`` says how late
the sender ran. Requests unanswered when the window closes count as
attempted and unanswered: in the tail they stand as slower than every
answered one; for the rate they do not count.
"""

from __future__ import annotations

import json
import math
import time
from typing import Dict, List

import numpy as np

from .. import data, opcount
from ..reference import logistic as ref_logistic

#: after the window closes, how long an answer still owed is waited for
GRACE_S = 60.0


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it (0.0 on an empty sample). The
    program's ``serving/loadgen.percentile`` rounds half to even on the
    rank and lands one rank high on exact ranks, so it is not copied."""
    s = sorted(values)
    if not s:
        return 0.0
    return s[max(0, min(len(s) - 1, math.ceil(q / 100.0 * len(s)) - 1))]


def schedule(seed: int, rate: float, seconds: float, stream: int) -> np.ndarray:
    """Arrival times in [0, seconds): a Poisson process of ``rate`` a
    second. The same seed gives the same schedule."""
    rng = np.random.default_rng([int(seed), 4, int(stream)])
    n = int(rate * seconds * 1.2) + 64
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < seconds:                     # never, in practice
        more = np.cumsum(rng.exponential(1.0 / rate, n)) + t[-1]
        t = np.concatenate([t, more])
    return t[t < seconds]


def padded(n_features: int) -> int:
    """Length of the served weight vector: the feature axis padded to the
    serving kernel's lane multiple (its geometry, not its weights)."""
    from alink_tpu.serving.sharded import LANE_PAD
    return -(-n_features // LANE_PAD) * LANE_PAD


class Phase:
    """One walk of a schedule against a server, by ONE thread: it sends
    what is due, notes which answers have come (the server resolves them
    in the order sent), and sleeps until the next is due. A second thread
    of the benchmark's would only contend with the server's loop for the
    interpreter, and the sender would run later for it."""

    #: how long the walk sleeps at most while answers are owed
    POLL_S = 1e-4

    def __init__(self, due: np.ndarray, rows: List, n_rows: int, keep):
        self.due = due
        self.rows = rows
        self.n_rows = n_rows
        n = len(due)
        self.sent = np.full(n, np.nan)
        self.seen = np.full(n, np.nan)
        self.futures: List = [None] * n
        # the answers that ``correct`` will judge (``keep``: their indices);
        # every other future is let go as soon as it is noted, as a caller
        # would: a hundred thousand live futures make the interpreter's
        # garbage collections long enough to show in the tail
        self.keep = frozenset(int(k) for k in keep)
        self.answers: Dict[int, object] = {}
        self.failed_at: List[int] = []
        self.n_sent = 0
        self.n_seen = 0
        self.t0 = 0.0

    def _note(self, now: float) -> None:
        j, i, futures, keep = self.n_seen, self.n_sent, self.futures, self.keep
        while j < i:
            fut = futures[j]
            if not fut.done():
                break
            try:
                row = fut.result(timeout=0)
            except Exception as e:      # a typed failure is an answer too
                row = e
                self.failed_at.append(j)
            if j in keep:
                self.answers[j] = row
            futures[j] = None
            self.seen[j] = now
            j += 1
        self.n_seen = j

    def drive(self, server, t0: float, seconds: float) -> None:
        """Send on schedule from ``t0`` until ``seconds`` have passed."""
        self.t0 = t0
        due, rows, n_rows = self.due, self.rows, self.n_rows
        futures, sent, submit = self.futures, self.sent, server.submit
        end = t0 + seconds
        i, n = 0, len(due)
        clock, sleep = time.perf_counter, time.sleep
        while True:
            now = clock()
            if now >= end:
                break
            while i < n and t0 + due[i] <= now:
                futures[i] = submit(rows[i % n_rows])
                now = sent[i] = clock()
                i += 1
                self.n_sent = i
                if now >= end:
                    break
                if not i & 7:             # while catching up, keep noting
                    self._note(now)
            if now >= end:
                break
            self._note(now)
            wait = (t0 + due[i] if i < n else end) - clock()
            if self.n_seen < i:
                wait = min(wait, self.POLL_S)
            if wait > 0:
                sleep(wait)
        # the close: what has come by now came inside the window
        self._note(end)

    def drain(self, grace_s: float) -> None:
        """Wait for the answers still owed, ``grace_s`` at the most."""
        clock = time.perf_counter
        until = clock() + grace_s
        while self.n_seen < self.n_sent and clock() < until:
            time.sleep(0.001)
            self._note(clock())


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        self.dim = 1 << int(cfg["dim_log2"])         # intercept included
        self.rate = float(tr["rate"])
        self.warm_s = float(tr["warm_seconds"])
        self.n_rows = int(tr["request_pool"])
        self.sample = int(tr["check_sample"])
        self.scale = float(cfg["weight_scale"])

    # -- set-up ---------------------------------------------------------
    def _server(self):
        from alink_tpu.common.mtable import MTable
        from alink_tpu.common.params import Params
        from alink_tpu.common.types import AlinkTypes, TableSchema
        from alink_tpu.common.vector import SparseVectorColumn
        from alink_tpu.operator.common.linear.base import (
            LinearModelData, LinearModelDataConverter, LinearModelType)
        from alink_tpu.operator.common.linear.mapper import LinearModelMapper
        from alink_tpu.serving.predictor import CompiledPredictor
        from alink_tpu.serving.server import PredictServer

        ctx = self.ctx
        n_feat = self.dim - 1
        self.dim8 = padded(n_feat)
        with ctx.spans.span("make_requests"):
            self.idx, self.val, _ = data.make_rows(
                ctx.seed, self.n_rows, ctx.config["row_shape"], n_feat)
            col = SparseVectorColumn(self.idx, self.val, n_feat)
            table = MTable({"vec": col}, TableSchema.parse("vec VECTOR"))
            self.rows = [table.row(i) for i in range(self.n_rows)]
        with ctx.spans.span("make_model_a"):
            self.coef_a = data.host_weights(ctx.seed, self.dim, self.scale, 1)
        model = LinearModelData(
            model_name="served", linear_model_type=LinearModelType.LR,
            has_intercept=True, vector_col="vec", feature_names=None,
            vector_size=n_feat, coef=self.coef_a, label_values=[1, 0],
            label_type=AlinkTypes.LONG)
        mapper = LinearModelMapper(
            LinearModelDataConverter(AlinkTypes.LONG).schema, table.schema,
            Params({"vector_col": "vec", "prediction_col": "pred",
                    "prediction_detail_col": "detail"}))
        # the model TABLE carries coefficients as JSON text, which 2^30 of
        # them cannot pass through; the mapper is handed the model data
        # that ``load_model`` would have parsed out of it
        mapper.model = model
        with ctx.spans.span("place_model_a"):
            predictor = CompiledPredictor(mapper, name="bench")
        with ctx.spans.span("warm_buckets"):
            for b in predictor.buckets:
                predictor.predict_table(table.first_n(min(b, self.n_rows)))
        self.predictor = predictor
        return PredictServer(predictor, name="bench")

    # -- the run ----------------------------------------------------------
    def run(self) -> None:
        import jax
        ctx = self.ctx
        server = self._server()
        try:
            # warm-up: a few seconds of the cell's own schedule through the
            # server the window will use, with the one swap in the middle
            half = self.warm_s / 2.0
            pre = Phase(schedule(ctx.seed, self.rate, half, 1), self.rows,
                        self.n_rows, range(self.sample))
            with ctx.spans.span("warm_before_swap"):
                pre.drive(server, time.perf_counter(), half)
                pre.drain(GRACE_S)
            with ctx.spans.span("swap"):
                self.w_b = data.device_weights(ctx.seed, self.dim8,
                                               self.scale, 2)
                self.bias_b = data.host_weights(ctx.seed, 1, self.scale, 3)[0]
                server.predictor.swap_weights(
                    (self.w_b, jax.device_put(
                        np.asarray(self.bias_b, self.w_b.dtype))))
                jax.block_until_ready(self.w_b)
            post = Phase(schedule(ctx.seed, self.rate, half, 2), self.rows,
                         self.n_rows, ())
            with ctx.spans.span("warm_after_swap"):
                post.drive(server, time.perf_counter(), half)
                post.drain(GRACE_S)
            self.pre = pre
            seconds = ctx.window_seconds
            due = schedule(ctx.seed, self.rate, seconds, 0)
            rng = np.random.default_rng([int(ctx.seed), 5])
            win = Phase(due, self.rows, self.n_rows, rng.choice(
                len(due), min(self.sample, len(due)), replace=False))
            stats0 = server.stats()
            win.drive(server, ctx.begin_window(), seconds)
            stats1 = server.stats()
            ctx.end_window()
            win.drain(GRACE_S)
            self.win = win
        finally:
            server.close()
        t_end = win.t0 + seconds
        n = len(win.due)
        answered = ~np.isnan(win.seen)
        failed = np.zeros(n, bool)
        failed[win.failed_at] = True
        in_time = answered & ~failed & (win.seen <= t_end)
        # unanswered at the close: as slow as the window is long
        lat_ms = np.where(in_time, (win.seen - (win.t0 + win.due)) * 1e3,
                          seconds * 1e3)
        late = win.sent[: win.n_sent] - (win.t0 + win.due[: win.n_sent])
        ctx.attempted = n
        ctx.failed = int(failed.sum())
        ctx.e2e["serve_p95"] = float(percentile(lat_ms.tolist(), 95.0))
        ctx.e2e["serve_rate"] = float(in_time.sum()) / seconds
        rows_w = stats1["requests"] - stats0["requests"]
        batches_w = stats1["batches"] - stats0["batches"]
        nnz = self.idx.shape[1]
        top = max(self.predictor.buckets)
        ctx.facts.update(
            window_s=seconds, requests=n, sent=int(win.n_sent),
            answered_in_time=int(in_time.sum()),
            unanswered_at_close=int(n - in_time.sum()),
            rows_dispatched=rows_w, dispatches=batches_w,
            gen_late_ms=float(percentile((late * 1e3).tolist(), 95.0)),
            p95_ms=ctx.e2e["serve_p95"],
            p50_ms=float(percentile(lat_ms.tolist(), 50.0)),
            answered_by_second=" ".join(str(int(c)) for c in np.histogram(
                win.seen[in_time] - win.t0,
                bins=np.arange(0.0, float(int(seconds)) + 1.0))[0]),
            shed=stats1["shed"] - stats0["shed"],
            fallback_batches=stats1["fallback_batches"],
            breaker_opens=stats1["breaker"].get("opens", 0),
            loop_respawns=stats1["loop_respawns"],
            rows_least_s=opcount.least_seconds(
                *opcount.linear_score(1, nnz), ctx.peak),
            top_bucket=top,
            top_bucket_least_s=opcount.least_seconds(
                *opcount.linear_score(top, nnz), ctx.peak))
        limit = float(ctx.traffic["gen_late_limit_ms"])
        if ctx.facts["gen_late_ms"] > limit and ctx.facts["sent"] == n:
            ctx.say(f"SUSPECT: the sender ran late by "
                    f"{ctx.facts['gen_late_ms']:.3f} ms at p95 (limit "
                    f"{limit} ms) though every request was admitted")

    def release(self) -> None:
        self.predictor = None
        self.w_b = None

    # -- correct ----------------------------------------------------------
    def verify(self) -> None:
        """A sample of the window's answers against float64 scoring under
        model B, and the answers served before the swap under model A."""
        ctx = self.ctx
        lim = ctx.config["limits"]
        win, pre = self.win, self.pre
        never = int(win.n_sent - win.n_seen) + int(pre.n_sent - pre.n_seen)
        # model B is made again, by the benchmark's own function, now that
        # the server's copy is gone; only the touched weights come to the host
        w_b = data.device_weights(ctx.seed, self.dim8, self.scale, 2)
        gap_b = self._gap(win, lambda i: np.asarray(w_b[i]),
                          float(self.bias_b))
        del w_b
        coef = self.coef_a
        gap_a = self._gap(pre, lambda i: coef[1:][i], float(coef[0]))
        ctx.check("prob_gap", gap_b, float(lim["prob_gap"]))
        ctx.check("prob_gap_before_swap", gap_a, float(lim["prob_gap"]))
        ctx.check("never_answered", float(never), 0.0)
        ctx.check("off_device_path",
                  float(ctx.facts["fallback_batches"]
                        + ctx.facts["breaker_opens"]
                        + ctx.facts["loop_respawns"]), 0.0)

    def _gap(self, phase: Phase, weights_at, bias: float) -> float:
        """Widest |P(click) served - P(click) reference| over the kept
        answers of ``phase``; a failure among them counts as infinitely
        wrong."""
        pick = np.array(sorted(phase.answers), np.int64)
        if len(pick) == 0:
            return float("inf")
        rows = pick % self.n_rows
        idx, val = self.idx[rows], self.val[rows]
        want = ref_logistic.score(weights_at(idx.reshape(-1)).reshape(idx.shape),
                                  val, bias, "float64")
        got = np.array([np.nan if isinstance(phase.answers[i], Exception)
                        else json.loads(phase.answers[i][-1])["1"]
                        for i in pick])
        gap = np.abs(got - want)
        return float(np.nan_to_num(gap, nan=np.inf).max())
