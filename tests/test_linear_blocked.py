"""The batch linear trainer's one dense path: a blocked table (floats, or
one byte a value) walked twice a superstep, its moments by one blocked
pass, standardization and the intercept folded into the coefficients.
Seeded, small, on the CPU mesh; the plain reference is the benchmark's
(``benchmark/reference/softmax.py``, which imports nothing of the
program)."""

import numpy as np
import pytest

from alink_tpu.common.columnar import (DenseBlockColumn, RowBlockColumn,
                                       sublane_quantum)
from alink_tpu.common.mlenv import MLEnvironment, MLEnvironmentFactory
from alink_tpu.common.mtable import MTable
from alink_tpu.common.types import TableSchema
from alink_tpu.operator.batch.classification.linear import (
    LogisticRegressionTrainBatchOp, SoftmaxPredictBatchOp,
    SoftmaxTrainBatchOp)
from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
from alink_tpu.operator.common.linear import base as B
from alink_tpu.operator.common.optim import objfunc as F
from alink_tpu.operator.common.optim import optimizers as O

SPEC = {"ink_share": 0.19, "ink_cap": 0.9, "bumps": 4,
        "bump_width_range": [2.0, 4.0], "floor": 0.02, "border_side_rows": 4,
        "teacher_random": 0.5, "teacher_margin": 4.0, "label_noise": 1.0,
        "balance_sample_rows": 2048}
PARAMS = {"classes": 10, "history": 10, "learning_rate": 1.0,
          "ladder": O.LINE_LADDER, "l2_ladder": [1e-4]}


@pytest.fixture(autouse=True)
def _keep_the_sessions_env_and_programs():
    from alink_tpu.engine.comqueue import clear_program_cache
    before = MLEnvironmentFactory.get_default()
    clear_program_cache()
    yield
    clear_program_cache()
    MLEnvironmentFactory.set_default(before)


def _pixels(n, seed=3, block_rows=4096):
    from benchmark import mnist8m
    x, y = mnist8m.make_table(seed, n, block_rows, SPEC)
    return np.asarray(x), np.asarray(y)


def _byte_source(x, y, n):
    return MemSourceBatchOp(MTable(
        {"pixels": DenseBlockColumn(x, n), "label": RowBlockColumn(y, n)},
        TableSchema.parse("pixels VECTOR, label INT")))


def _softmax(src, l2=1e-4, max_iter=5, **kw):
    return (SoftmaxTrainBatchOp().set_vector_col("pixels")
            .set_label_col("label").set_max_iter(max_iter).set_l2(l2)
            .set_epsilon(0.0).link_from(src, **kw))


# -- the program against the plain reference --------------------------------

@pytest.mark.parametrize("walk", ["xla", "kernel"])
@pytest.mark.parametrize("n", [8192, 6000], ids=["whole_blocks", "ragged"])
def test_byte_table_fit_agrees_with_the_plain_reference(monkeypatch, n, walk):
    """A few thousand rows x 784 uint8 through ``SoftmaxTrainBatchOp``:
    every checked superstep's loss, gradient, direction, chosen rung and
    update against the reference teacher-forced from the fit's own traces;
    the moments exact; every pass counted every row. On either walk of
    the passes: the block loop, and the Pallas kernels under the
    interpreter (``kernels/linear.py``; the same limits)."""
    from benchmark.reference import softmax as ref
    monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", str(int(walk == "kernel")))
    x, y = _pixels(n)
    info = dict(_softmax(_byte_source(x, y, n)).get_train_info())
    assert info["paths"] == {"design": "blocks:uint8",
                             "moments": "linear_moments",
                             "pass": "blocked:bf16x3", "walk": walk}
    assert info["coef_trace"].shape == (5, 9 * 785) == info["grad_trace"].shape
    assert info["moments_rows"] == n and (info["rows_trace"] == n).all()
    assert np.all(np.diff(info["loss_curve"]) < 0)
    got = ref.gaps(info, x, y, n, PARAMS)
    assert got["moments_gap"] < 1e-12 and got["step_gap"] == 0.0
    assert got["loss_gap"] < 1e-6 and got["grad_gap"] < 2e-5
    assert got["dir_gap"] < 1e-9 and got["coef_gap"] < 1e-9


def test_plain_bfloat16_coefficients_fail_the_same_comparison(monkeypatch):
    """The comparison is tight enough for the next precision down: the
    coefficients' three-part split cut to its first part reads a gradient
    a hundred times further off."""
    from benchmark.reference import softmax as ref
    import jax.numpy as jnp

    split3 = F.split3

    def top_part_only(a):
        hi = split3(a)[:a.shape[0]]
        return jnp.concatenate([hi, jnp.zeros_like(hi), jnp.zeros_like(hi)], 0)

    n = 8192
    x, y = _pixels(n)
    src = _byte_source(x, y, n)
    clean = ref.gaps(dict(_softmax(src).get_train_info()), x, y, n, PARAMS)
    monkeypatch.setattr(F, "split3", top_part_only)
    from alink_tpu.engine.comqueue import clear_program_cache
    clear_program_cache()
    low = ref.gaps(dict(_softmax(src).get_train_info()), x, y, n, PARAMS)
    assert low["grad_gap"] > 100 * clean["grad_gap"]
    assert low["grad_gap"] > 1e-4


# -- the fold against the written-out standardization ----------------------

def _float_rows(n=700, d=6, seed=5):
    r = np.random.RandomState(seed)
    X = r.randn(n, d) * r.uniform(0.5, 30.0, d) + r.uniform(-40, 40, d)
    y = (X @ r.randn(d) / X.std(0).mean() + 0.5 * r.randn(n) > 0)
    return X, np.where(y, 1.0, -1.0)


def test_folded_standardization_matches_the_written_out_one():
    """Standardization and the intercept folded into the coefficients give
    the coefficients of the parent's form, where ``(X - mean) / std`` was
    written out as a new matrix with a column of ones in front."""
    X, y = _float_rows()
    n, d = X.shape
    mean, std = X.mean(0), X.std(0)
    params = O.OptimParams(max_iter=12, epsilon=0.0)
    w = np.ones(n)
    folded, curve_f, _ = O.optimize(
        F.UnaryLossObjFunc(F.LogLossFunc(), d + 1, l2=1e-3, reg_free_head=1),
        {"X": X, "y": y, "w": w, "scale": 1.0 / std, "shift": mean / std},
        params)
    Xs = np.concatenate([np.ones((n, 1)), (X - mean) / std], 1)
    written, curve_w, _ = O.optimize(
        F.UnaryLossObjFunc(F.LogLossFunc(), d + 1, l2=1e-3, reg_free_head=1),
        {"X": Xs, "y": y, "w": w}, params)
    np.testing.assert_allclose(curve_f, curve_w, rtol=1e-9)
    np.testing.assert_allclose(folded, written, rtol=1e-6, atol=1e-8)


def test_moments_by_the_blocked_pass_are_the_tables():
    """One pass, block means joined pairwise: a column of large mean and
    small spread keeps its spread (``E x^2 - mean^2`` would not), weights
    count, and a constant column keeps ``std = 1``."""
    r = np.random.RandomState(2)
    X = np.stack([1e4 + 0.01 * r.randn(5000), r.randn(5000) * 3,
                  np.full(5000, 7.0)], 1)
    env = MLEnvironmentFactory.get_default()
    from alink_tpu.common.columnar import as_block_column, block_weights
    col = as_block_column(X, env.num_workers)
    w = r.randint(0, 3, 5000).astype(np.float64)
    mean, std, rows = B.linear_moments(col, block_weights(col, w), env)
    want_mean = (X * w[:, None]).sum(0) / w.sum()
    want_std = np.sqrt((w[:, None] * (X - want_mean) ** 2).sum(0) / w.sum())
    assert rows == int((w != 0).sum())
    np.testing.assert_allclose(mean, want_mean, rtol=1e-12)
    np.testing.assert_allclose(std[:2], want_std[:2], rtol=1e-9)
    assert std[2] == 1.0


def test_a_constant_column_trains_and_predicts():
    """The generator's border columns are constant: their std stays 1,
    their coefficients stay 0 (no gradient), and the model predicts
    through the unchanged ``SoftmaxPredictBatchOp``."""
    n = 4096
    x, y = _pixels(n)
    op = _softmax(_byte_source(x, y, n), max_iter=8)
    info = dict(op.get_train_info())
    border = np.flatnonzero(x.transpose(0, 2, 3, 1).reshape(-1, 784)[:n]
                            .std(0) == 0)
    assert len(border) == 64 and (info["std"][border] == 1.0).all()
    W = info["coef"].reshape(9, 785)
    assert not W[:, 1 + border].any()
    rows = x.transpose(0, 2, 3, 1).reshape(-1, 784)[:300].astype(np.float64)
    from alink_tpu.common.vector import DenseVector
    vecs = np.empty(len(rows), object)
    vecs[:] = [DenseVector(r) for r in rows]
    data = MemSourceBatchOp(MTable({"pixels": vecs},
                                   TableSchema.parse("pixels VECTOR")))
    pred = (SoftmaxPredictBatchOp().set_vector_col("pixels")
            .set_prediction_col("pred").link_from(op, data)
            .get_output_table().col("pred"))
    labels = y.reshape(-1)[:300]
    assert (np.asarray(pred, int) == labels).mean() > 0.5


# -- one answer on 1, 4 and 8 devices ---------------------------------------

@pytest.mark.parametrize("workers", [1, 4, 8])
def test_every_worker_count_gives_one_answer(workers):
    """The byte table over 1, 4 and 8 virtual devices (8 blocks, so every
    count divides them): the coefficients of the one-device fit to
    float32's rounding of the products, the same rungs, every row
    counted."""
    import jax
    n = 8 * 4096 - 500
    x, y = _pixels(n, seed=9)
    info = {}
    for nw in sorted({1, workers}):
        env = MLEnvironment(parallelism=nw, devices=jax.devices()[:nw])
        MLEnvironmentFactory.set_default(env)
        info[nw] = dict(_softmax(_byte_source(x, y, n), max_iter=4)
                        .get_train_info())
    one, many = info[1], info[workers]
    assert (many["rows_trace"] == n).all() and many["moments_rows"] == n
    assert list(many["rung_trace"]) == list(one["rung_trace"])
    np.testing.assert_allclose(many["mean"], one["mean"], rtol=1e-12)
    np.testing.assert_allclose(many["loss_curve"], one["loss_curve"],
                               rtol=1e-6)
    scale = np.abs(one["coef"]).max()
    assert np.abs(many["coef"] - one["coef"]).max() < 1e-4 * scale


# -- the tuning axes are data -------------------------------------------------

def test_a_second_l2_reuses_the_compiled_programs():
    """``l2``, ``learning_rate`` and ``epsilon`` enter the step program as
    data: a sweep over them compiles nothing after the first fit."""
    from jax import monitoring
    from alink_tpu.engine.comqueue import program_cache_stats
    n = 4096
    x, y = _pixels(n)
    src = _byte_source(x, y, n)
    first = dict(_softmax(src, l2=1e-6).get_train_info())
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_k: compiles.append(event) if event ==
        "/jax/core/compile/backend_compile_duration" else None)
    before = program_cache_stats()
    other = dict((SoftmaxTrainBatchOp().set_vector_col("pixels")
                  .set_label_col("label").set_max_iter(5).set_l2(1e-2)
                  .set_epsilon(1e-9).set_learning_rate(0.5)
                  .link_from(src)).get_train_info())
    after = program_cache_stats()
    assert not compiles
    assert after["misses"] == before["misses"]
    assert after["hits"] == before["hits"] + 2       # moments and steps
    assert other["loss_curve"][-1] > first["loss_curve"][-1]   # l2 counted


# -- the binary losses ride the same walk ----------------------------------

def test_binary_dense_pass_is_the_objectives_plain_form():
    """The logistic objective's walked gradient pass and line pass against
    its plain form, ``sum w loss(x . c, y)`` over written-out standardized
    rows, at a coefficient vector that is not zero."""
    X, y = _float_rows(500, 5, seed=8)
    n, d = X.shape
    r = np.random.RandomState(1)
    w = r.uniform(0.5, 2.0, n)
    mean, std = X.mean(0), X.std(0)
    obj = F.UnaryLossObjFunc(F.LogLossFunc(), d + 1, reg_free_head=1)
    parts, consts = obj.prepare_data(
        {"X": X, "y": y, "w": w, "scale": 1 / std, "shift": mean / std}, 1)
    coef, direction = r.randn(d + 1) * 0.3, r.randn(d + 1) * 0.1
    shard = {**parts, **consts}
    import jax.numpy as jnp
    g, loss, wsum, eta, rows = obj.grad_pass(shard, jnp.asarray(coef))
    steps = jnp.asarray([0.0, 0.5, 1.0])
    line, rows2 = obj.line_pass(shard, jnp.asarray(coef),
                                jnp.asarray(direction), steps, eta)
    Z = np.concatenate([np.ones((n, 1)), (X - mean) / std], 1)

    def plain(c):
        m = Z @ c
        return (w * np.logaddexp(0, -y * m)).sum(), \
            Z.T @ (w * -y / (1 + np.exp(y * m)))
    want_loss, want_grad = plain(coef)
    assert int(rows) == n == int(rows2) and float(wsum) == pytest.approx(w.sum())
    assert float(loss) == pytest.approx(want_loss, rel=1e-9)
    np.testing.assert_allclose(np.asarray(g), want_grad, rtol=1e-7, atol=1e-9)
    np.testing.assert_allclose(
        np.asarray(line), [plain(coef - s * direction)[0]
                           for s in (0.0, 0.5, 1.0)], rtol=1e-9)


def test_binary_lr_on_a_byte_table_through_the_operator():
    """``LogisticRegressionTrainBatchOp`` on the byte table with a block
    column of {0, 1} labels: the same walk, its loss falls, and the
    labels come out positive first."""
    n = 4096
    x, y = _pixels(n)
    src = _byte_source(x, (y < 5).astype(np.int32) * (
        np.arange(y.size).reshape(y.shape) < n), n)
    op = (LogisticRegressionTrainBatchOp().set_vector_col("pixels")
          .set_label_col("label").set_max_iter(6).link_from(src))
    info = dict(op.get_train_info())
    assert info["label_values"] == [1, 0]
    assert info["paths"]["pass"] == "blocked:bf16x3"
    assert np.all(np.diff(info["loss_curve"]) < 0)
    assert (info["rows_trace"] == n).all()


# -- labels ---------------------------------------------------------------------

def test_a_block_column_of_class_ids_is_its_own_index():
    """The block form and the host form of ``index_labels`` give the same
    ``label_values`` order and the same ids; the binary form the same
    positive-first pair and targets."""
    r = np.random.RandomState(4)
    ids = r.randint(0, 7, 3000).astype(np.int32)
    col = RowBlockColumn.from_values(ids)
    labels_b, y_b = B.index_labels(col)
    labels_h, y_h = B.index_labels(ids)
    assert labels_b == labels_h == list(range(7))
    assert (np.asarray(y_b).reshape(-1)[:3000] == y_h).all()
    two = (ids > 3).astype(np.int32) * 5
    lab_b, t_b = B.encode_labels(RowBlockColumn.from_values(two))
    lab_h, t_h = B.encode_labels(two)
    assert lab_b == lab_h == [5, 0]
    assert (np.asarray(t_b).reshape(-1)[:3000] == t_h).all()
    with pytest.raises(ValueError):
        B.encode_labels(col)


# -- the column of bytes --------------------------------------------------------

def test_a_byte_block_column_is_whole_8_bit_tiles():
    assert [sublane_quantum(t) for t in (np.float32, np.float64, np.uint8,
                                         np.int16)] == [8, 8, 32, 16]
    ok = DenseBlockColumn(np.zeros((2, 3, 32, 128), np.uint8), 5000)
    assert ok.value_dtype == np.uint8 and ok.block_rows == 4096
    with pytest.raises(ValueError, match="multiple of 32"):
        DenseBlockColumn(np.zeros((2, 3, 8, 128), np.uint8), 100)
    DenseBlockColumn(np.zeros((2, 3, 8, 128), np.float32), 100)
    rows = np.arange(12, dtype=np.uint8).reshape(4, 3)
    col = DenseBlockColumn.from_rows(rows)
    assert col.blocks.dtype == np.uint8 and col.blocks.shape[2] % 32 == 0
    assert (col.to_rows() == rows).all()


# -- spans and counters ---------------------------------------------------------

def _ring_mark():
    """Where the tracer's ring stands, as the start of its newest event:
    a time, not a place, since a ring that is full (a worker that has run
    other files) no longer grows."""
    from alink_tpu.common.tracing import get_tracer
    events = get_tracer().events()
    return events[-1]["ts"] if events else -1.0


def _events_since(mark):
    from alink_tpu.common.tracing import get_tracer
    return [e for e in get_tracer().events() if e["ts"] > mark]


def test_a_fit_records_its_spans_and_counts_its_rows():
    """Coarse spans under the operator's link (no flag set): ``linear.fit``
    over extract, moments, optimize and model, none a superstep, within
    the budget of 40 always-on events a fit; the counters carry the rows
    each pass counted on the device."""
    from alink_tpu.common.metrics import get_registry
    n = 4096
    x, y = _pixels(n)
    src = _byte_source(x, y, n)
    _softmax(src)                                   # compile outside

    def count(name):
        return sum(float(r["value"]) for r in get_registry().snapshot()
                   if r["name"] == name and "value" in r)
    names = ("alink_linear_rows_total", "alink_linear_supersteps_total",
             "alink_linear_fits_total", "alink_linear_passes_total")
    before = [count(c) for c in names]
    mark = _ring_mark()
    _softmax(src, max_iter=5)
    events = [e for e in _events_since(mark) if e.get("ph") == "X"]
    got = [e["name"] for e in events]
    for want in ("link:SoftmaxTrainBatchOp", "linear.fit", "linear.extract",
                 "linear.moments", "linear.optimize", "linear.model"):
        assert got.count(want) == 1, want
    assert len(events) <= 40
    opt = next(e for e in events if e["name"] == "linear.optimize")
    assert opt["args"]["pass"] == "blocked:bf16x3"
    assert opt["args"]["classes"] == 10 and opt["args"]["dim"] == 785
    mom = next(e for e in events if e["name"] == "linear.moments")
    assert mom["args"] == {"rows": n, "dim": 784, "dtype": "uint8"}
    rows, steps, fits, passes = (count(c) - b for c, b in zip(names, before))
    assert (rows, steps, fits, passes) == (n * 11, 5, 1, 11)
