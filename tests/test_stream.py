"""Streaming layer tests: sources/sinks, transforms, windowed eval, FTRL.

Mirrors the reference's stream tests (stream op + StreamOperator.execute +
collected results; FTRL example DAG FTRLExample.java:18-113).
"""

import json
import threading
import time

import numpy as np
import pytest

from alink_tpu.common import MTable
from alink_tpu.operator.base import StreamOperator
from alink_tpu.operator.batch.source import MemSourceBatchOp
from alink_tpu.operator.batch.classification import (
    LogisticRegressionTrainBatchOp, LogisticRegressionPredictBatchOp)
from alink_tpu.operator.stream import (
    AppendIdStreamOp, CollectSinkStreamOp, EvalBinaryClassStreamOp,
    FtrlPredictStreamOp, FtrlTrainStreamOp, LogisticRegressionPredictStreamOp,
    MemSourceStreamOp, NumSeqSourceStreamOp, SampleStreamOp, SelectStreamOp,
    SplitStreamOp, UnionAllStreamOp, WhereStreamOp, WindowGroupByStreamOp)


def _drain(op):
    sink = CollectSinkStreamOp().link_from(op)
    StreamOperator.execute()
    return sink.get_and_remove_values()


def test_mem_source_micro_batches():
    src = MemSourceStreamOp({"x": np.arange(10.0)}, batch_size=3)
    batches = [mt.num_rows for mt in src.micro_batches()]
    assert batches == [3, 3, 3, 1]
    out = _drain(src)
    np.testing.assert_array_equal(out.col("x"), np.arange(10.0))


def test_stream_sql_chain():
    src = NumSeqSourceStreamOp(1, 20, col_name="n", batch_size=4)
    out = _drain(SelectStreamOp(clause="n, n*2 as dbl")
                 .link_from(WhereStreamOp(clause="n % 2 == 0").link_from(src)))
    np.testing.assert_array_equal(out.col("n"), np.arange(2, 21, 2))
    np.testing.assert_array_equal(out.col("dbl"), np.arange(2, 21, 2) * 2)


def test_stream_union_sample_split_append_id():
    a = MemSourceStreamOp({"x": np.arange(0.0, 10.0)}, batch_size=5)
    b = MemSourceStreamOp({"x": np.arange(100.0, 110.0)}, batch_size=5)
    u = UnionAllStreamOp().link_from(a, b)
    out = _drain(AppendIdStreamOp().link_from(u))
    assert out.num_rows == 20
    np.testing.assert_array_equal(out.col("append_id"), np.arange(20))

    s = SampleStreamOp(ratio=0.5, seed=7).link_from(a)
    sampled = _drain(s)
    assert 0 < sampled.num_rows < 10

    sp = SplitStreamOp(fraction=0.5, seed=3).link_from(a)
    main = _drain(sp)
    rest = _drain(sp.get_side_stream())
    assert main.num_rows + rest.num_rows == 10


def test_window_group_by():
    # 12 batches of 1 row, event time = batch index; windows of 3s
    rows = [("a", float(i)) for i in range(12)]
    src = MemSourceStreamOp(rows, ["k", "v"], batch_size=1, time_per_batch=1.0)
    w = WindowGroupByStreamOp(group_by_clause="k",
                              select_clause="k, sum(v) as s, count(*) as c",
                              window_length=3.0).link_from(src)
    out = _drain(w)
    # windows [0,3) [3,6) [6,9) [9,12)
    assert list(out.col("c")) == [3, 3, 3, 3]
    assert list(out.col("s")) == [3.0, 12.0, 21.0, 30.0]


def test_hopping_window_group_by():
    # HOP(length=4, slide=2) over t=0..7 one row each: windows [-2,2) [0,4)
    # [2,6) [4,8) [6,10) — overlapping rows must appear in BOTH windows
    rows = [("a", float(i)) for i in range(8)]
    src = MemSourceStreamOp(rows, ["k", "v"], batch_size=1, time_per_batch=1.0)
    w = WindowGroupByStreamOp(group_by_clause="k",
                              select_clause="k, sum(v) as s",
                              window_length=4.0,
                              slide_length=2.0).link_from(src)
    sums = list(_drain(w).col("s"))
    assert sums == [1.0, 6.0, 14.0, 22.0, 13.0]  # 0+1, 0+..3, 2+..5, 4+..7, 6+7


def test_diamond_dag_independent_drains():
    # the same op instance drained twice concurrently (diamond) must not
    # share per-drain state
    src = MemSourceStreamOp({"x": np.arange(6.0)}, batch_size=2)
    ap = AppendIdStreamOp().link_from(src)
    u = UnionAllStreamOp().link_from(ap, ap)
    out = _drain(u)
    ids = sorted(out.col("append_id"))
    assert ids == [0, 0, 1, 1, 2, 2, 3, 3, 4, 4, 5, 5]


def test_first_n_stops_upstream():
    pulled = []

    class CountingSource(MemSourceStreamOp):
        def _set_table(self, table):
            super()._set_table(table)
            inner = self._stream_fn

            def counted():
                for t, mt in inner():
                    pulled.append(t)
                    yield (t, mt)
            self._stream_fn = counted
            return self

    from alink_tpu.operator.stream import FirstNStreamOp
    src = CountingSource({"x": np.arange(100.0)}, batch_size=10)
    out = _drain(FirstNStreamOp(n=10).link_from(src))
    assert out.num_rows == 10
    assert len(pulled) <= 2  # does not drain the remaining 8 batches


def _make_lr_fixture(n=400, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 3)
    w = np.array([1.5, -2.0, 0.7])
    y = (X @ w + 0.3 * rng.randn(n) > 0).astype(np.int64)
    return MTable({"f0": X[:, 0], "f1": X[:, 1], "f2": X[:, 2], "label": y})


def test_stream_model_predict_and_eval():
    table = _make_lr_fixture()
    batch_src = MemSourceBatchOp(table)
    model = LogisticRegressionTrainBatchOp(
        feature_cols=["f0", "f1", "f2"], label_col="label",
        max_iter=60).link_from(batch_src)

    stream_src = MemSourceStreamOp(table, batch_size=64)
    pred = LogisticRegressionPredictStreamOp(
        model, prediction_col="pred", prediction_detail_col="detail"
    ).link_from(stream_src)
    out = _drain(pred)
    acc = np.mean(np.asarray(out.col("pred")) == np.asarray(out.col("label")))
    assert acc > 0.9

    # windowed + cumulative eval rows
    pred2 = LogisticRegressionPredictStreamOp(
        model, prediction_col="pred", prediction_detail_col="detail"
    ).link_from(MemSourceStreamOp(table, batch_size=64))
    ev = EvalBinaryClassStreamOp(label_col="label",
                                 prediction_detail_col="detail",
                                 time_interval=2.0).link_from(pred2)
    rows = _drain(ev)
    stats = list(rows.col("Statistics"))
    assert "window" in stats and "all" in stats
    last_all = [json.loads(d) for s, d in zip(stats, rows.col("Data"))
                if s == "all"][-1]
    assert last_all["AUC"] > 0.9


def test_ftrl_train_and_hot_reload_predict():
    table = _make_lr_fixture(n=600, seed=11)
    batch_src = MemSourceBatchOp(table.first_n(100))
    warm = LogisticRegressionTrainBatchOp(
        feature_cols=["f0", "f1", "f2"], label_col="label",
        max_iter=10).link_from(batch_src)

    train_stream = MemSourceStreamOp(table, batch_size=32, time_per_batch=1.0)
    ftrl = FtrlTrainStreamOp(
        warm, label_col="label", feature_cols=["f0", "f1", "f2"],
        alpha=0.5, beta=1.0, l1=0.001, l2=0.001,
        time_interval=5.0).link_from(train_stream)

    data_stream = MemSourceStreamOp(table, batch_size=32, time_per_batch=1.0)
    pred = FtrlPredictStreamOp(
        warm, prediction_col="pred", prediction_detail_col="detail"
    ).link_from(ftrl, data_stream)
    out = _drain(pred)
    assert out.num_rows == 600
    acc = np.mean(np.asarray(out.col("pred")) == np.asarray(out.col("label")))
    assert acc > 0.85

    # the model stream itself is valid LinearModel rows: load last snapshot
    snapshots = list(ftrl.micro_batches())
    assert len(snapshots) >= 2
    final = snapshots[-1]
    scored = LogisticRegressionPredictBatchOp(prediction_col="p").link_from(
        MemSourceBatchOp(final).alias("model_id, model_info, label_value")
        if False else MemSourceBatchOp(final), MemSourceBatchOp(table))
    acc2 = np.mean(np.asarray(scored.get_output_table().col("p"))
                   == np.asarray(table.col("label")))
    assert acc2 > 0.85


def _sparse_lr_fixture(n, dim, nnz, seed):
    """Sparse-literal LR rows: labels from a planted weight over nnz-hot
    features, as "$dim$i:v ..." strings."""
    rng = np.random.RandomState(seed)
    w = rng.randn(dim) * (rng.rand(dim) < 0.1)
    w[:nnz * 2] = rng.randn(nnz * 2)  # guarantee signal on frequent slots
    vecs, ys = [], []
    for _ in range(n):
        idx = np.sort(rng.choice(dim, nnz, replace=False))
        val = rng.randn(nnz)
        margin = float(val @ w[idx])
        y = int(margin + 0.1 * rng.randn() > 0)
        vecs.append("$%d$" % dim + " ".join(
            f"{i}:{v:.6f}" for i, v in zip(idx, val)))
        ys.append(y)
    return MTable({"vec": np.asarray(vecs, object),
                   "label": np.asarray(ys, np.int64)})


def test_ftrl_sparse_matches_dense():
    """The O(nnz) sparse FTRL program must produce the same model as the
    dense program fed the densified rows (VERDICT round-2 item 1)."""
    from alink_tpu.operator.common.linear.base import LinearModelDataConverter
    from alink_tpu.common.vector import VectorUtil

    dim = 24
    table = _sparse_lr_fixture(n=256, dim=dim, nnz=5, seed=3)
    # densify the same rows into dense-vector literals
    dense_rows = []
    for s in table.col("vec"):
        v = VectorUtil.parse(s)
        x = np.zeros(dim)
        x[np.asarray(v.indices, int)] = v.values
        dense_rows.append(" ".join(f"{t:.6f}" for t in x))
    dense_table = MTable({"vec": np.asarray(dense_rows, object),
                          "label": table.col("label")})

    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=3).link_from(
        MemSourceBatchOp(dense_table.first_n(64)))

    def run(tbl):
        ftrl = FtrlTrainStreamOp(
            warm, label_col="label", vector_col="vec", alpha=0.5,
            l1=0.001, l2=0.001, time_interval=1e9).link_from(
            MemSourceStreamOp(tbl, batch_size=64))
        final = list(ftrl.micro_batches())[-1]
        lt = final.schema.types[2]
        return LinearModelDataConverter(lt).load_model(final).coef

    coef_sparse = run(table)
    coef_dense = run(dense_table)
    np.testing.assert_allclose(coef_sparse, coef_dense, rtol=1e-7, atol=1e-9)
    assert np.abs(coef_sparse).max() > 0


def test_ftrl_sparse_criteo_shape_stays_sparse():
    """dim=65536 micro-batches must train without densifying: the padded
    COO block for 256 rows x nnz 8 is ~20 KB; the old dense encode was
    256*65536*8 bytes = 134 MB per batch."""
    import time
    dim = 65536
    table = _sparse_lr_fixture(n=512, dim=dim, nnz=8, seed=5)
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=1).link_from(
        MemSourceBatchOp(table.first_n(32)))
    ftrl = FtrlTrainStreamOp(
        warm, label_col="label", vector_col="vec", alpha=0.5,
        time_interval=1e9).link_from(MemSourceStreamOp(table, batch_size=256))
    t0 = time.perf_counter()
    final = list(ftrl.micro_batches())[-1]
    dt = time.perf_counter() - t0
    assert final.num_rows > 0
    assert dt < 120.0, f"sparse FTRL at dim=65536 took {dt:.0f}s"


def test_ftrl_improves_on_weak_warm_start():
    """FTRL online updates should beat a deliberately under-trained model."""
    table = _make_lr_fixture(n=800, seed=23)
    weak = LogisticRegressionTrainBatchOp(
        feature_cols=["f0", "f1", "f2"], label_col="label",
        max_iter=1).link_from(MemSourceBatchOp(table.first_n(24)))

    ftrl = FtrlTrainStreamOp(
        weak, label_col="label", feature_cols=["f0", "f1", "f2"],
        alpha=1.0, time_interval=1e9).link_from(
        MemSourceStreamOp(table, batch_size=64))
    final_model = list(ftrl.micro_batches())[-1]

    def batch_acc(model_table):
        scored = LogisticRegressionPredictBatchOp(prediction_col="p").link_from(
            MemSourceBatchOp(model_table), MemSourceBatchOp(table))
        return np.mean(np.asarray(scored.get_output_table().col("p"))
                       == np.asarray(table.col("label")))

    assert batch_acc(final_model) >= batch_acc(weak.get_output_table())


def test_stream_eval_single_class_window_full_schema():
    """A window that saw only one label class still emits the full metric
    schema (reference BaseEvalClassStreamOp) — rank metrics nulled, confusion
    metrics real — instead of a {"count", "note"} stub row."""
    table = _make_lr_fixture(n=80, seed=9)
    batch_src = MemSourceBatchOp(table)
    model = LogisticRegressionTrainBatchOp(
        feature_cols=["f0", "f1", "f2"], label_col="label",
        max_iter=40).link_from(batch_src)

    # an all-positive slice: every window is single-class
    mask = np.asarray(table.col("label")) == 1
    pos_only = MTable({c: np.asarray(table.col(c))[mask] for c in
                       ("f0", "f1", "f2", "label")})
    pred = LogisticRegressionPredictStreamOp(
        model, prediction_col="pred", prediction_detail_col="detail"
    ).link_from(MemSourceStreamOp(pos_only, batch_size=16))
    ev = EvalBinaryClassStreamOp(label_col="label",
                                 prediction_detail_col="detail",
                                 time_interval=2.0).link_from(pred)
    rows = _drain(ev)
    assert rows.num_rows
    for d in rows.col("Data"):
        m = json.loads(d)
        assert "note" not in m
        assert m["AUC"] is None and m["KS"] is None and m["PRC"] is None
        assert m["TotalSamples"] > 0
        assert m["TruePositive"] + m["FalseNegative"] == m["TotalSamples"]
        assert 0.0 <= m["Accuracy"] <= 1.0


def _disjoint_sparse_fixture(n, dim, nnz, seed):
    """Rows with pairwise-disjoint feature sets inside every 8-row batch:
    row i in a batch uses its own contiguous feature block."""
    rng = np.random.RandomState(seed)
    w = rng.randn(dim)
    block = dim // 8
    vecs, ys = [], []
    for i in range(n):
        base = (i % 8) * block
        idx = np.sort(rng.choice(block, nnz, replace=False)) + base
        val = rng.randn(nnz)
        y = int(float(val @ w[idx]) > 0)
        vecs.append("$%d$" % dim + " ".join(
            f"{j}:{v:.6f}" for j, v in zip(idx, val)))
        ys.append(y)
    return MTable({"vec": np.asarray(vecs, object),
                   "label": np.asarray(ys, np.int64)})


def _ftrl_final_coef(table, warm, batch_size, mode, **kw):
    from alink_tpu.operator.common.linear.base import LinearModelDataConverter
    ftrl = FtrlTrainStreamOp(
        warm, label_col="label", vector_col="vec", alpha=0.5,
        l1=0.001, l2=0.001, time_interval=1e9,
        update_mode=mode, **kw).link_from(MemSourceStreamOp(table,
                                                            batch_size=batch_size))
    final = list(ftrl.micro_batches())[-1]
    lt = final.schema.types[2]
    return LinearModelDataConverter(lt).load_model(final).coef


def test_ftrl_batch_mode_exact_on_disjoint_batches():
    """update_mode="batch" computes every gradient at pre-batch weights;
    when the rows of a batch touch pairwise-disjoint features no state is
    shared inside the batch, so it must EQUAL the strict per-sample scan."""
    dim = 64
    table = _disjoint_sparse_fixture(n=128, dim=dim, nnz=3, seed=7)
    # no intercept: the intercept slot is shared by every row, which would
    # make every batch colliding by construction
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=3,
        with_intercept=False).link_from(
        MemSourceBatchOp(_sparse_lr_fixture(64, dim, 4, 1)))
    c_sample = _ftrl_final_coef(table, warm, 8, "sample")
    c_batch = _ftrl_final_coef(table, warm, 8, "batch")
    np.testing.assert_allclose(c_batch, c_sample, rtol=1e-9, atol=1e-12)


def test_ftrl_batch_mode_quality_with_collisions():
    """On ordinary (colliding) sparse data the batched trajectory is an
    approximation — it must stay close to the strict one and train a
    usable model."""
    dim = 2048          # realistic CTR regime: dim >> batch * nnz, so
    # intra-batch feature collisions are rare and the batched trajectory
    # tracks the strict one closely
    table = _sparse_lr_fixture(n=1024, dim=dim, nnz=5, seed=11)
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=3).link_from(
        MemSourceBatchOp(table.first_n(64)))
    c_sample = _ftrl_final_coef(table, warm, 128, "sample")
    c_batch = _ftrl_final_coef(table, warm, 128, "batch")
    # same sign structure and magnitude ballpark, not bitwise equality
    denom = np.abs(c_sample).max()
    assert denom > 0
    assert np.abs(c_batch - c_sample).max() / denom < 0.35
    big = np.abs(c_sample) > 0.2 * denom
    assert (np.sign(c_batch[big]) == np.sign(c_sample[big])).all()


def test_ftrl_staleness_one_equals_strict():
    """update_mode="staleness" with staleness=1 degenerates to the strict
    per-sample scan — bit-level trajectory equality on COLLIDING data."""
    table = _sparse_lr_fixture(n=256, dim=256, nnz=4, seed=3)
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=3).link_from(
        MemSourceBatchOp(table.first_n(32)))
    c_strict = _ftrl_final_coef(table, warm, 32, "sample")
    c_s1 = _ftrl_final_coef(table, warm, 32, "staleness", staleness=1)
    np.testing.assert_allclose(c_s1, c_strict, rtol=1e-6, atol=1e-9)


def test_ftrl_staleness_exact_on_disjoint_chunks():
    """When every row in a staleness chunk touches disjoint features, no
    state is shared inside the chunk and the bounded-staleness program
    EQUALS the strict per-sample scan."""
    dim = 64
    table = _disjoint_sparse_fixture(n=128, dim=dim, nnz=3, seed=7)
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=3,
        with_intercept=False).link_from(
        MemSourceBatchOp(_sparse_lr_fixture(64, dim, 4, 1)))
    c_sample = _ftrl_final_coef(table, warm, 8, "sample")
    c_stale = _ftrl_final_coef(table, warm, 8, "staleness", staleness=8)
    np.testing.assert_allclose(c_stale, c_sample, rtol=1e-9, atol=1e-12)


def test_ftrl_staleness_quality_with_collisions():
    """Bounded staleness (the reference's feedback-edge contract) must
    track the strict trajectory closely on ordinary colliding CTR-shape
    data and preserve the sign structure of the learned weights."""
    dim = 2048
    table = _sparse_lr_fixture(n=1024, dim=dim, nnz=5, seed=11)
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=3).link_from(
        MemSourceBatchOp(table.first_n(64)))
    c_sample = _ftrl_final_coef(table, warm, 128, "sample")
    c_stale = _ftrl_final_coef(table, warm, 128, "staleness", staleness=32)
    denom = np.abs(c_sample).max()
    assert denom > 0
    assert np.abs(c_stale - c_sample).max() / denom < 0.35
    big = np.abs(c_sample) > 0.2 * denom
    assert (np.sign(c_stale[big]) == np.sign(c_sample[big])).all()


def test_ftrl_batch_mode_dense_path():
    """update_mode="batch" on dense feature columns trains a usable model
    through the fused dense program."""
    table = _make_lr_fixture(n=600, seed=31)
    weak = LogisticRegressionTrainBatchOp(
        feature_cols=["f0", "f1", "f2"], label_col="label",
        max_iter=1).link_from(MemSourceBatchOp(table.first_n(24)))
    ftrl = FtrlTrainStreamOp(
        weak, label_col="label", feature_cols=["f0", "f1", "f2"],
        alpha=1.0, time_interval=1e9, update_mode="batch").link_from(
        MemSourceStreamOp(table, batch_size=64))
    final_model = list(ftrl.micro_batches())[-1]
    scored = LogisticRegressionPredictBatchOp(prediction_col="p").link_from(
        MemSourceBatchOp(final_model), MemSourceBatchOp(table))
    acc = np.mean(np.asarray(scored.get_output_table().col("p"))
                  == np.asarray(table.col("label")))
    assert acc > 0.85


def _field_aware_fixture(n, F, S, seed, unit_vals=False):
    """Field-aware-hashed sparse rows: exactly one slot per field, field k's
    global indices in [k*S, (k+1)*S) — the layout FeatureHasher
    field_aware=True emits."""
    rng = np.random.RandomState(seed)
    dim = F * S
    w = rng.randn(dim)
    vecs, ys = [], []
    for _ in range(n):
        local = rng.randint(0, S, F)
        idx = local + np.arange(F) * S
        val = np.ones(F) if unit_vals else rng.randn(F)
        y = int(float(val @ w[idx]) > 0)
        vecs.append("$%d$" % dim + " ".join(
            f"{j}:{v:.6f}" for j, v in zip(idx, val)))
        ys.append(y)
    return MTable({"vec": np.asarray(vecs, object),
                   "label": np.asarray(ys, np.int64)})


def test_ftrl_fb_batch_matches_coo_batch(monkeypatch):
    """Field-aware input in update_mode="batch" routes to the one-hot MXU
    program; its model must match the element-addressed COO batch program
    (same math, different kernels — f32 vs f64 tolerance)."""
    import alink_tpu.ops.fieldblock as fb_mod
    import alink_tpu.operator.stream.onlinelearning.ftrl as ftrl_mod

    F, S = 7, 16                      # +1 intercept field -> 8 | 8-dev mesh
    table = _field_aware_fixture(n=512, F=F, S=S, seed=13)
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=3).link_from(
        MemSourceBatchOp(table.first_n(64)))

    engaged = {"fb": 0}
    orig = ftrl_mod._ftrl_fb_batch_step_factory

    def spy(*a, **k):
        engaged["fb"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(ftrl_mod, "_ftrl_fb_batch_step_factory", spy)
    c_fb = _ftrl_final_coef(table, warm, 64, "batch")
    # the lru-cached factory is looked up per batch now (val-less vs
    # val-carrying variant is a per-batch choice) — engagement, not count
    assert engaged["fb"] >= 1, "field-blocked fast path did not engage"

    # same data through the COO batch program (detection disabled)
    monkeypatch.setattr(fb_mod, "detect_fieldblock", lambda *a, **k: None)
    c_coo = _ftrl_final_coef(table, warm, 64, "batch")
    np.testing.assert_allclose(c_fb, c_coo, rtol=5e-4, atol=5e-5)
    assert np.abs(c_fb).max() > 0


def test_ftrl_empty_stream_emits_warm_start():
    """A stream with no rows still emits the warm-start model snapshot
    (state is lazily allocated, but the final emit must not crash)."""
    from alink_tpu.operator.common.linear.base import LinearModelDataConverter
    table = _make_lr_fixture(n=100, seed=2)
    warm = LogisticRegressionTrainBatchOp(
        feature_cols=["f0", "f1", "f2"], label_col="label",
        max_iter=5).link_from(MemSourceBatchOp(table))
    empty = MTable({c: np.asarray([], float) for c in ("f0", "f1", "f2")}
                   | {"label": np.asarray([], np.int64)})
    ftrl = FtrlTrainStreamOp(
        warm, label_col="label", feature_cols=["f0", "f1", "f2"],
        time_interval=1e9).link_from(MemSourceStreamOp(empty, batch_size=8))
    snaps = list(ftrl.micro_batches())
    assert len(snaps) == 1
    lt = snaps[0].schema.types[2]
    coef = LinearModelDataConverter(lt).load_model(snaps[0]).coef
    warm_coef = LinearModelDataConverter(lt).load_model(
        warm.get_output_table()).coef
    np.testing.assert_allclose(coef, warm_coef, rtol=1e-9)


def test_ftrl_fb_demotes_to_generic_midstream():
    """A coincidental field-blocked detection on the first batch must not
    kill the stream when later generic batches arrive: the state demotes
    to the generic layout (an exact translation) and training continues."""
    from alink_tpu.operator.common.linear.base import LinearModelDataConverter
    F, S = 7, 16
    dim = F * S
    fb_part = _field_aware_fixture(n=64, F=F, S=S, seed=3, unit_vals=True)
    generic = _sparse_lr_fixture(n=64, dim=dim, nnz=3, seed=4)
    mixed = MTable(
        {"vec": np.concatenate([np.asarray(fb_part.col("vec"), object),
                                np.asarray(generic.col("vec"), object)]),
         "label": np.concatenate([np.asarray(fb_part.col("label")),
                                  np.asarray(generic.col("label"))])})
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=3).link_from(
        MemSourceBatchOp(generic))
    ftrl = FtrlTrainStreamOp(
        warm, label_col="label", vector_col="vec", alpha=0.5,
        time_interval=1e9, update_mode="batch").link_from(
        MemSourceStreamOp(mixed, batch_size=32))
    final = list(ftrl.micro_batches())[-1]    # must not raise
    lt = final.schema.types[2]
    coef = LinearModelDataConverter(lt).load_model(final).coef
    assert np.isfinite(coef).all() and np.abs(coef).max() > 0


def test_prefetch_preserves_order_and_propagates_errors():
    """The stream prefetcher (VERDICT r2 #4) must be order-transparent:
    a FIFO hand-off, identical sequence, upstream exceptions re-raised
    at the consumption point, bounded queue giving backpressure."""
    import time as _time

    from alink_tpu.operator.stream.prefetch import prefetch

    # order over a non-trivial length with a slow consumer
    out = []
    for v in prefetch(iter(range(500)), depth=3):
        out.append(v)
    assert out == list(range(500))

    # exception propagation
    def boom():
        yield 1
        yield 2
        raise RuntimeError("upstream failed")

    got = []
    try:
        for v in prefetch(boom(), depth=2):
            got.append(v)
        raise AssertionError("should have raised")
    except RuntimeError as e:
        assert "upstream failed" in str(e)
    assert got == [1, 2]

    # backpressure: producer cannot run more than depth ahead
    produced = []

    def tracked():
        for i in range(10):
            produced.append(i)
            yield i

    it = prefetch(tracked(), depth=2)
    next(it)
    _time.sleep(0.05)
    # 1 yielded + ≤depth in queue + ≤1 in-flight put
    assert len(produced) <= 1 + 2 + 1, produced

    # depth=0 disables (pure inline iteration)
    assert list(prefetch(iter([1, 2, 3]), depth=0)) == [1, 2, 3]


def test_ftrl_prefetch_identical_model(monkeypatch):
    """Prefetching overlaps encode with device compute but must not
    change a single bit of the trained model (no batch reordering)."""
    from alink_tpu.operator.common.linear.base import LinearModelDataConverter

    table = _sparse_lr_fixture(n=256, dim=24, nnz=5, seed=3)
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=3).link_from(
        MemSourceBatchOp(table.first_n(64)))

    def run():
        ftrl = FtrlTrainStreamOp(
            warm, label_col="label", vector_col="vec", alpha=0.5,
            l1=0.001, l2=0.001, time_interval=1e9).link_from(
            MemSourceStreamOp(table, batch_size=64))
        final = list(ftrl.micro_batches())[-1]
        lt = final.schema.types[2]
        return LinearModelDataConverter(lt).load_model(final).coef

    monkeypatch.setenv("ALINK_TPU_STREAM_PREFETCH", "0")
    coef_off = run()
    monkeypatch.setenv("ALINK_TPU_STREAM_PREFETCH", "3")
    coef_on = run()
    np.testing.assert_array_equal(coef_off, coef_on)


def test_ftrl_strict_chunked_scan_exact_under_collisions():
    """The K-per-step strict scan must reproduce per-sample FTRL exactly
    even when every sample shares features with its neighbors (the
    correction-matvec path): compare against a plain numpy sequential
    FTRL on a tiny dense-ish problem, including a batch size NOT
    divisible by the chunk size (internal zero-row padding)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from alink_tpu.common.mlenv import MLEnvironmentFactory
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        _ftrl_sparse_step_factory)

    env = MLEnvironmentFactory.get_default()
    mesh = env.mesh
    alpha, beta, l1, l2 = 0.3, 1.0, 1e-3, 1e-3
    dim_pad = 8 * env.num_workers
    rng = np.random.RandomState(0)
    B, w = 59, 4                     # 59 % 4 != 0 -> exercises padding
    idx = rng.randint(0, dim_pad, size=(B, w)).astype(np.int32)
    val = rng.rand(B, w)
    y = (rng.rand(B) < 0.5).astype(np.float64)

    step = _ftrl_sparse_step_factory(mesh, alpha, beta, l1, l2)
    shard = NamedSharding(mesh, P("d"))
    z0 = rng.randn(dim_pad) * 1e-3
    z, n, margins = step(idx, val, y,
                         jax.device_put(z0, shard),
                         jax.device_put(np.zeros(dim_pad), shard))

    zc, nc, ms = _per_sample_reference(idx, val, y, z0, np.zeros(dim_pad),
                                       alpha, beta, l1, l2)

    np.testing.assert_allclose(np.asarray(z), zc, rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(n), nc, rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(margins), ms, rtol=2e-5,
                               atol=1e-7)
    assert len(np.asarray(margins)) == B


def _strict_case(name, dim_pad):
    """(idx, val, y) of one micro-batch for a case of the strict step's
    working table (K = 4 rows a round, width 6)."""
    rng = np.random.RandomState(7)
    B, w = 32, 6
    if name == "all_distinct":            # no coordinate twice in the batch
        idx = rng.permutation(dim_pad)[:B * w].reshape(B, w)
    elif name == "across_rounds":         # repeats, never inside one round
        rnd = rng.permutation(dim_pad)[:4 * w].reshape(4, w)
        idx = np.tile(rnd, (B // 4, 1))
    elif name == "inside_round":          # rows of a round share coordinates
        idx = np.stack([rng.permutation(12)[:w] * (dim_pad // 12)
                        for _ in range(B)])   # no row holds one twice
    elif name == "inside_row":            # one row holds a coordinate twice
        idx = rng.randint(0, dim_pad, size=(B, w))
        idx[:, 3] = idx[:, 1]
        idx[5, :] = idx[5, 0]
    elif name == "all_identical":
        idx = np.full((B, w), dim_pad - 3)
    elif name == "padding":               # short rows: (0, 0.0) fills them
        idx = rng.randint(0, dim_pad, size=(B, w))
    elif name == "ragged":                # 30 rows: no multiple of K
        B = 30
        idx = rng.randint(0, dim_pad // 2, size=(B, w))
    elif name == "spread":                # every shard of the mesh touched
        idx = (np.arange(B * w).reshape(B, w) * 37) % dim_pad
    else:
        raise ValueError(name)
    val = rng.rand(B, w) + 0.1
    if name == "padding":
        keep = rng.rand(B, w) < 0.6
        idx, val = np.where(keep, idx, 0), np.where(keep, val, 0.0)
    y = (rng.rand(B) < 0.5).astype(np.float64)
    return idx.astype(np.int32), val, y


def _per_sample_reference(idx, val, y, z0, n0, alpha, beta, l1, l2):
    """Plain sequential FTRL-proximal, one sample at a time: every slot of
    a row sees the pre-sample value (as the device program's rows do)."""
    zc, nc, ms = z0.copy(), n0.copy(), []
    for ii, vv, yy in zip(idx, val, y):
        zi, ni = zc[ii], nc[ii]
        decay = (beta + np.sqrt(ni)) / alpha + l2
        wi = np.where(np.abs(zi) <= l1, 0.0,
                      -(zi - np.sign(zi) * l1) / decay)
        m = float(np.sum(vv * wi))
        ms.append(m)
        p = 1.0 / (1.0 + np.exp(-np.clip(m, -35, 35)))
        g = (p - yy) * vv
        sigma = (np.sqrt(ni + g * g) - np.sqrt(ni)) / alpha
        np.add.at(zc, ii, g - sigma * wi)
        np.add.at(nc, ii, g * g)
    return zc, nc, np.asarray(ms)


def _plain_scan_step(mesh, alpha, beta, l1, l2):
    """The strict step without its working table: one sample a scan step,
    gathered from the state and scatter-added to it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from alink_tpu.operator.stream.onlinelearning.ftrl import _ftrl_weights

    def shard_fn(idx, val, y, z, n):
        shard = z.shape[0]
        lo = jax.lax.axis_index("d") * shard

        def body(carry, row):
            z, n = carry
            xi, xv, yy = row
            local = (xi >= lo) & (xi < lo + shard)
            li = jnp.clip(xi - lo, 0, shard - 1)
            zk, nk = jnp.where(local, z[li], 0.0), jnp.where(local, n[li], 0.0)
            wj = jnp.where(local,
                           _ftrl_weights(zk, nk, alpha, beta, l1, l2), 0.0)
            margin = jax.lax.psum(jnp.sum(xv * wj), "d")
            p = 1.0 / (1.0 + jnp.exp(-jnp.clip(margin, -35.0, 35.0)))
            g = (p - yy) * xv
            sigma = (jnp.sqrt(nk + g * g) - jnp.sqrt(nk)) / alpha
            z = z.at[li].add(jnp.where(local, g - sigma * wj, 0.0))
            n = n.at[li].add(jnp.where(local, g * g, 0.0))
            return (z, n), margin

        (z, n), margins = jax.lax.scan(body, (z, n), (idx, val, y))
        return z, n, margins

    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(P(), P(), P(), P("d"), P("d")),
        out_specs=(P("d"), P("d"), P())))


@pytest.mark.parametrize("case", [
    "all_distinct", "across_rounds", "inside_round", "inside_row",
    "all_identical", "padding", "ragged", "spread"])
def test_ftrl_strict_working_table_matches_per_sample(case):
    """The strict step folds its rows through a per-micro-batch working
    table (one state gather before the rounds, one write-back of distinct
    coordinates after them): against sequential per-sample FTRL in
    float64, on the 8-device mesh, it agrees to 1e-12 relative whatever
    repeats where, leaves every untouched coordinate bit for bit, and
    twice over the same state (a second micro-batch) still agrees. Where
    no coordinate repeats inside a round, the table changes no bit of
    what the plain one-sample-a-step scan computes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from alink_tpu.common.mlenv import MLEnvironmentFactory
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        _ftrl_sparse_step_factory)

    env = MLEnvironmentFactory.get_default()
    alpha, beta, l1, l2 = 0.3, 1.0, 1e-3, 1e-3
    dim_pad = 64 * env.num_workers
    idx, val, y = _strict_case(case, dim_pad)
    rng = np.random.RandomState(1)
    z0, n0 = rng.randn(dim_pad) * 0.05, rng.rand(dim_pad)
    step = _ftrl_sparse_step_factory(env.mesh, alpha, beta, l1, l2)
    shard = NamedSharding(env.mesh, P("d"))
    z, n = jax.device_put(z0, shard), jax.device_put(n0, shard)
    zc, nc = z0, n0
    for _ in range(2):
        z, n, margins = step(idx, val, y, z, n)
        zc, nc, ms = _per_sample_reference(idx, val, y, zc, nc,
                                           alpha, beta, l1, l2)
        np.testing.assert_allclose(np.asarray(z), zc, rtol=1e-12, atol=1e-15)
        np.testing.assert_allclose(np.asarray(n), nc, rtol=1e-12, atol=1e-15)
        np.testing.assert_allclose(np.asarray(margins), ms, rtol=1e-12,
                                   atol=1e-15)
        assert np.asarray(margins).shape == (len(y),)
    untouched = np.setdiff1d(np.arange(dim_pad), idx.reshape(-1))
    np.testing.assert_array_equal(np.asarray(z)[untouched], z0[untouched])
    np.testing.assert_array_equal(np.asarray(n)[untouched], n0[untouched])
    if case in ("all_distinct", "across_rounds", "spread"):
        plain = _plain_scan_step(env.mesh, alpha, beta, l1, l2)
        zp, np_ = jax.device_put(z0, shard), jax.device_put(n0, shard)
        for _ in range(2):
            zp, np_, mp = plain(idx, val, y, zp, np_)
        for got, ref in ((z, zp), (n, np_), (margins, mp)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# -- the program's own spans (ISSUE 25) ---------------------------------------

def _tiny_ftrl(table, warm, **kw):
    """The final model's coefficients of a four-micro-batch sparse drain."""
    from alink_tpu.operator.common.linear.base import LinearModelDataConverter
    ftrl = FtrlTrainStreamOp(
        warm, label_col="label", vector_col="vec", alpha=0.5,
        l1=0.001, l2=0.001, time_interval=2.0, **kw).link_from(
        MemSourceStreamOp(table, batch_size=64))
    snaps = list(ftrl.micro_batches())
    lt = snaps[-1].schema.types[2]
    return [LinearModelDataConverter(lt).load_model(s).coef for s in snaps]


#: what a drain leaves in the ring of EVERY process (ISSUE 35): the link's
#: four coarse spans, once, and JAX's own ``jit.*`` events where a program
#: was new; nothing a micro-batch, a snapshot or a wait
ALWAYS_ON = ["ftrl.link", "ftrl.state_alloc", "ftrl.state_ship",
             "ftrl.warm_hash"]


def _always_on_events_alone(events, drains=1):
    """True where ``events`` hold the always-on spans of ``drains`` drains
    and no fine event."""
    names = sorted(e["name"] for e in events
                   if not e["name"].startswith("jit."))
    return names == sorted(ALWAYS_ON * drains)


@pytest.fixture(scope="module")
def tiny_ftrl_inputs():
    table = _sparse_lr_fixture(n=256, dim=24, nnz=5, seed=3)
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=3).link_from(
        MemSourceBatchOp(table.first_n(64)))
    return table, warm


def test_ftrl_drain_records_one_span_of_each_kind_per_micro_batch(
        quiet_tracer, monkeypatch, tiny_ftrl_inputs, tmp_path):
    table, warm = tiny_ftrl_inputs
    monkeypatch.setenv("ALINK_TPU_TRACE", "1")
    _tiny_ftrl(table, warm, checkpoint_dir=str(tmp_path / "ck"),
               checkpoint_every_batches=2)
    evs = [e for e in quiet_tracer.events() if e["ph"] == "X"]
    threads = quiet_tracer._meta()["threads"]
    by = {}
    for e in evs:
        by.setdefault(e["name"], []).append(e)
    # every micro-batch: exactly one encode, ship, dispatch and batch, all
    # under one batch number; producer side on a prefetch thread, consumer
    # side on the caller's
    me = threading.get_ident()
    for name in ("ftrl.encode", "ftrl.ship", "ftrl.dispatch", "ftrl.batch"):
        assert sorted(e["args"]["batch"] for e in by[name]) == [1, 2, 3, 4], name
    for name in ("ftrl.encode", "ftrl.ship"):
        for e in by[name]:
            assert threads[str(e["tid"])].startswith("alink-prefetch-"), name
            assert e["tid"] != me and e["args"]["rows"] == 64
    for name in ("ftrl.dispatch", "ftrl.batch"):
        assert {e["tid"] for e in by[name]} == {me}, name
    for b in (1, 2, 3, 4):
        enc, ship, disp = (next(e for e in by[n] if e["args"]["batch"] == b)
                           for n in ("ftrl.encode", "ftrl.ship",
                                     "ftrl.dispatch"))
        # encoded, then shipped, then dispatched: one micro-batch's order
        assert enc["ts"] + enc["dur"] <= ship["ts"]
        assert ship["ts"] + ship["dur"] <= disp["ts"]
    # the upstream's own time: one pull an item, and the one that found
    # the stream's end
    pulls = by["prefetch.pull"]
    assert len(pulls) == 5
    assert all(threads[str(e["tid"])].startswith("alink-prefetch-")
               for e in pulls)
    # snapshots at event times 2 and 3 (the interval's boundaries), and the
    # final one; checkpoints after micro-batches 2 and 4
    snaps = by["ftrl.snapshot"]
    assert [e["args"]["to"] for e in snaps] == ["host"] * len(snaps)
    assert snaps[-1]["args"].get("final") is True and len(snaps) >= 2
    assert [e["args"]["batch"] for e in by["ftrl.checkpoint"]] == [2, 4]
    assert {e["tid"] for e in snaps + by["ftrl.checkpoint"]} == {me}
    # waits are recorded only on the side that waited, and never for free
    for e in by.get("prefetch.get_wait", []) + by.get("prefetch.put_wait", []):
        assert e["dur"] > 0


@pytest.mark.parametrize("how", ["flag", "profiler"])
def test_ftrl_drain_is_bitwise_the_same_traced_and_untraced(
        how, quiet_tracer, monkeypatch, tiny_ftrl_inputs, tmp_path):
    table, warm = tiny_ftrl_inputs
    off = _tiny_ftrl(table, warm)
    # the overhead guard: with both off a drain of four micro-batches
    # records its link's four spans and not one event a micro-batch
    assert _always_on_events_alone(quiet_tracer.events()), \
        "nothing fine is recorded with both off"
    quiet_tracer.clear()
    if how == "flag":
        monkeypatch.setenv("ALINK_TPU_TRACE", "1")
        on = _tiny_ftrl(table, warm)
    else:
        import jax
        with jax.profiler.trace(str(tmp_path)):
            on = _tiny_ftrl(table, warm)
    names = {e["name"] for e in quiet_tracer.events()}
    assert {"ftrl.encode", "ftrl.ship", "ftrl.dispatch", "ftrl.batch",
            "ftrl.snapshot", "prefetch.pull"} <= names
    assert all(bool(e.get("profiled")) == (how == "profiler")
               for e in quiet_tracer.events())
    assert len(on) == len(off)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)


def test_a_warm_drain_leaves_four_events_whatever_its_micro_batches(
        quiet_tracer, tiny_ftrl_inputs):
    """The always-on grade's budget for the stream trainer (ISSUE 35):
    four coarse spans a link, none a micro-batch, and once the step
    programs are compiled not one ``jit.*`` event."""
    table, warm = tiny_ftrl_inputs
    _tiny_ftrl(table, warm)                       # the programs compile here
    quiet_tracer.clear()
    _tiny_ftrl(table, warm)
    evs = quiet_tracer.events()
    assert sorted(e["name"] for e in evs) == ALWAYS_ON and \
        quiet_tracer.dropped == 0
    by = {e["name"]: e for e in evs}
    assert by["ftrl.link"].get("parent") is None
    assert by["ftrl.warm_hash"]["parent"] == by["ftrl.link"]["id"]
    assert by["ftrl.warm_hash"]["args"]["bytes"] > 0
    # the state is made when the first micro-batch fixes its layout, after
    # the link returned: both numpy arrays, then both on the device
    assert by["ftrl.state_alloc"]["ts"] > by["ftrl.link"]["ts"] \
        + by["ftrl.link"]["dur"]
    assert by["ftrl.state_ship"]["args"]["bytes"] == \
        by["ftrl.state_alloc"]["args"]["bytes"] > 0
    # eight micro-batches where there were four: the same four events
    quiet_tracer.clear()
    ftrl = FtrlTrainStreamOp(
        warm, label_col="label", vector_col="vec", alpha=0.5, l1=0.001,
        l2=0.001, time_interval=2.0).link_from(
        MemSourceStreamOp(table, batch_size=32))
    assert len(list(ftrl.micro_batches())) >= 1
    assert _always_on_events_alone(quiet_tracer.events())


def test_device_snapshot_consumer_runs_inside_the_snapshot_span(
        quiet_tracer, monkeypatch, tiny_ftrl_inputs):
    table, warm = tiny_ftrl_inputs
    monkeypatch.setenv("ALINK_TPU_TRACE", "1")
    got = []
    ftrl = FtrlTrainStreamOp(
        warm, label_col="label", vector_col="vec", alpha=0.5, l1=0.001,
        l2=0.001, time_interval=2.0)
    ftrl.set_device_snapshot_consumer(
        lambda w, info: got.append(info["batch"]) or True)
    assert list(ftrl.link_from(
        MemSourceStreamOp(table, batch_size=64)).micro_batches()) == []
    snaps = [e for e in quiet_tracer.events() if e["name"] == "ftrl.snapshot"]
    assert [e["args"]["batch"] for e in snaps] == got
    assert all(e["ph"] == "X" and e["args"]["to"] == "device" for e in snaps)


def test_ftrl_snapshot_span_carries_entries_and_slots_only_when_recorded(
        quiet_tracer, monkeypatch, tiny_ftrl_inputs):
    """While a span is recorded, each ``ftrl.snapshot`` says how many
    entries its boundary's micro-batch folded and how many distinct
    coordinates (``slots``) they were; with nothing recording, nothing is
    fetched or counted."""
    from alink_tpu.operator.stream.onlinelearning import ftrl as F
    table, warm = tiny_ftrl_inputs
    counted = []
    real = F._distinct
    monkeypatch.setattr(
        F, "_distinct",
        lambda idx: counted.append(np.asarray(idx)) or real(idx))
    _tiny_ftrl(table, warm)
    assert counted == [] and _always_on_events_alone(quiet_tracer.events())
    monkeypatch.setenv("ALINK_TPU_TRACE", "1")
    _tiny_ftrl(table, warm)
    snaps = [e for e in quiet_tracer.events() if e["name"] == "ftrl.snapshot"]
    assert len(snaps) >= 2 and len(counted) == len(snaps)
    for e, idx in zip(snaps, counted):
        assert e["args"]["entries"] == idx.size == 64 * idx.shape[1]
        assert e["args"]["slots"] == np.unique(idx).size < idx.size
    # recording that begins INSIDE a boundary's span (the benchmark's hook
    # opens its traced window there) leaves that boundary uncounted
    monkeypatch.delenv("ALINK_TPU_TRACE")
    quiet_tracer.clear()
    del counted[:]
    hooked = []
    ftrl = FtrlTrainStreamOp(
        warm, label_col="label", vector_col="vec", alpha=0.5, l1=0.001,
        l2=0.001, time_interval=2.0)
    ftrl.set_device_snapshot_consumer(
        lambda w, info: hooked.append(monkeypatch.setenv("ALINK_TPU_TRACE",
                                                         "1")) or True)
    list(ftrl.link_from(MemSourceStreamOp(table, batch_size=64))
         .micro_batches())
    snaps = [e for e in quiet_tracer.events() if e["name"] == "ftrl.snapshot"]
    assert len(counted) == len(snaps) == len(hooked) - 1 >= 1


def test_channel_waits_are_spans_only_where_they_block(quiet_tracer,
                                                       monkeypatch):
    from alink_tpu.operator.stream.prefetch import (_Channel, _EMPTY,
                                                    _SENTINEL)
    monkeypatch.setenv("ALINK_TPU_TRACE", "1")
    ch = _Channel(1)
    assert ch.put("a") is True                    # room: no wait
    assert ch.get() == "a"                        # an item: no wait
    assert ch.get(timeout=0) is _EMPTY            # a poll: no wait
    assert quiet_tracer.events() == []
    assert ch.get(timeout=0.02) is _EMPTY         # a real, timed wait
    (ev,) = quiet_tracer.events()
    assert ev["name"] == "prefetch.get_wait" and ev["dur"] >= 15_000
    quiet_tracer.clear()
    # a full channel: the producer blocks until the consumer takes one
    assert ch.put("b") is True
    th = threading.Thread(target=ch.put, args=("c",), name="producer")
    th.start()
    time.sleep(0.02)
    assert ch.get() == "b"
    th.join(timeout=10)
    assert not th.is_alive() and ch.get() == "c"
    (ev,) = quiet_tracer.events()
    assert ev["name"] == "prefetch.put_wait" and ev["dur"] >= 10_000
    assert quiet_tracer._meta()["threads"][str(ev["tid"])] == "producer"
    quiet_tracer.clear()
    ch.close()
    assert ch.get() is _SENTINEL                  # ended: no wait
    assert ch.put("d") is False
    assert quiet_tracer.events() == []


@pytest.mark.parametrize("workers", [1, 3])
def test_prefetch_map_pull_and_starve_spans(workers, quiet_tracer, monkeypatch):
    from alink_tpu.operator.stream.prefetch import prefetch_map
    monkeypatch.setenv("ALINK_TPU_TRACE", "1")

    def slow_source():
        for i in range(4):
            time.sleep(0.01)
            yield i

    assert list(prefetch_map(slow_source(), lambda x: x * 2,
                             workers=workers, depth=2)) == [0, 2, 4, 6]
    evs = quiet_tracer.events()
    threads = quiet_tracer._meta()["threads"]
    pulls = [e for e in evs if e["name"] == "prefetch.pull"]
    assert len(pulls) == 5 and len({e["tid"] for e in pulls}) == 1
    assert threads[str(pulls[0]["tid"])] == (
        "alink-prefetch-0" if workers == 1 else "alink-prefetch-dispatch")
    assert sum(e["dur"] for e in pulls) >= 35_000   # the source's sleeps
    # the consumer outruns a 10 ms source: it starves on this thread
    me = threading.get_ident()
    starved = [e for e in evs
               if e["name"] == "prefetch.get_wait" and e["tid"] == me]
    assert starved and sum(e["dur"] for e in starved) >= 20_000


def test_ftrl_device_programs_carry_their_names():
    """The step keeps the module name the benchmark's configuration reads
    (``jit_shard_fn``), the snapshot program has one of its own, and the
    round's ops are grouped under named scopes."""
    import jax
    from alink_tpu.common.mlenv import MLEnvironmentFactory
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        _ftrl_sparse_step_factory, _ftrl_step_factory)

    env = MLEnvironmentFactory.get_default()
    dim_pad = 8 * env.num_workers
    f64 = np.float64
    z = jax.ShapeDtypeStruct((dim_pad,), f64)
    step = _ftrl_sparse_step_factory(env.mesh, 0.3, 1.0, 1e-3, 1e-3)
    lowered = step.lower(jax.ShapeDtypeStruct((8, 4), np.int32),
                         jax.ShapeDtypeStruct((8, 4), f64),
                         jax.ShapeDtypeStruct((8,), f64), z, z)
    text = lowered.as_text(debug_info=True)
    assert "module @jit_shard_fn" in text
    for scope in ("ftrl_workset", "ftrl_gather", "ftrl_update",
                  "ftrl_scatter"):
        assert scope in text, scope
    plain = lowered.as_text()
    assert "ftrl_gather" not in plain, "scopes are op metadata alone"
    _, weights_fn = _ftrl_step_factory(env.mesh, 0.3, 1.0, 1e-3, 1e-3)
    wtext = weights_fn.lower(z, z).as_text(debug_info=True)
    assert "module @jit_ftrl_weights" in wtext and "ftrl_weights" in wtext


def test_ftrl_strict_rounds_hold_no_scatter():
    """The 1,024 dependent rounds read a table and write one contiguous
    slab of it; every scatter of the step program sits after them, in the
    write-back of distinct coordinates (a loop whose trip count the data
    gives)."""
    import jax
    from alink_tpu.common.mlenv import MLEnvironmentFactory
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        _ftrl_sparse_step_factory)

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from eqns(sub)

    env = MLEnvironmentFactory.get_default()
    f64 = np.float64
    z = jax.ShapeDtypeStruct((8 * env.num_workers,), f64)
    step = _ftrl_sparse_step_factory(env.mesh, 0.3, 1.0, 1e-3, 1e-3)
    args = (jax.ShapeDtypeStruct((8, 4), np.int32),
            jax.ShapeDtypeStruct((8, 4), f64),
            jax.ShapeDtypeStruct((8,), f64), z, z)
    whole = list(eqns(jax.make_jaxpr(step)(*args).jaxpr))
    (scan,) = [e for e in whole if e.primitive.name == "scan"]
    assert scan.params["length"] == 2             # 8 rows, 4 a round
    rounds = {e.primitive.name for e in eqns(scan.params["jaxpr"].jaxpr)}
    assert not any("scatter" in name for name in rounds), rounds
    assert {"gather", "dynamic_update_slice", "dot_general"} <= rounds
    loops = [e for e in whole if e.primitive.name == "while"]
    stores = [e for e in loops if any(
        "scatter" in b.primitive.name
        for b in eqns(e.params["body_jaxpr"].jaxpr))]
    assert len(loops) == 2 and len(stores) == 1
    text = step.lower(*args).as_text()
    assert "module @jit_shard_fn" in text and "stablehlo.scatter" in text
