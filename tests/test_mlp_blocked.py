"""The MLP trainer's blocked path: a ``DenseBlockColumn`` (bytes, or
floats) walked twice a superstep, block by block, the standardization
folded into layer 1, moments by the linear trainers' blocked pass, the
step program ``jit_mlp_qn``. Seeded, small, on the CPU mesh; the plain
reference is the benchmark's (``benchmark/reference/mlp.py``, which imports
nothing of the program)."""

import json

import numpy as np
import pytest

from alink_tpu.common.columnar import (DenseBlockColumn, RowBlockColumn,
                                       as_block_column, block_weights)
from alink_tpu.common.mlenv import MLEnvironment, MLEnvironmentFactory
from alink_tpu.common.mtable import MTable
from alink_tpu.common.types import TableSchema
from alink_tpu.operator.batch.classification.mlpc_ops import (
    MlpModelConverter, MultilayerPerceptronPredictBatchOp,
    MultilayerPerceptronTrainBatchOp)
from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
from alink_tpu.operator.common.ann import mlp as M

SPEC = {"ink_share": 0.19, "ink_cap": 0.9, "bumps": 4,
        "bump_width_range": [2.0, 4.0], "floor": 0.02, "border_side_rows": 4,
        "teacher_random": 0.5, "teacher_margin": 4.0, "label_noise": 1.0,
        "balance_sample_rows": 2048}
LAYERS = [784, 24, 12, 10]


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


def _source(x, y, n):
    return MemSourceBatchOp(MTable(
        {"pixels": DenseBlockColumn(x, n), "label": RowBlockColumn(y, n)},
        TableSchema.parse("pixels VECTOR, label INT")))


def _mlp(src, hidden=(24, 12), max_iter=4, seed=7, l2=1e-4):
    return MultilayerPerceptronTrainBatchOp(
        vector_col="pixels", label_col="label", layers=list(hidden),
        max_iter=max_iter, l2=l2, seed=seed, epsilon=0.0).link_from(src)


def _float_table(n=3000, d=20, k=4, seed=11):
    """Host rows of floats and class ids, and the same rows as a
    ``DenseBlockColumn``."""
    rng = np.random.RandomState(seed)
    X = (rng.randn(n, d) * rng.uniform(0.5, 3.0, d) + rng.randn(d)) \
        .astype(np.float32)
    y = np.argmax(X[:, :k] + 0.5 * rng.randn(n, k), 1).astype(np.int32)
    return X, y, as_block_column(X)


def _shard(obj, col, y, mean, std):
    """The blocked shard a worker walks, as ``optimize`` packs it."""
    parts, consts = obj.prepare_data(
        {"X": col, "y": y, "w": block_weights(col, None, np.float64),
         "scale": 1.0 / std, "shift": mean / std}, 1)
    return dict(parts, **consts)


def _passes(obj, shard, coef, direction, steps):
    import jax
    import jax.numpy as jnp
    g = jax.jit(obj.grad_pass)(shard, jnp.asarray(coef))
    line = jax.jit(obj.line_pass)(shard, jnp.asarray(coef),
                                  jnp.asarray(direction), jnp.asarray(steps))
    return (np.asarray(g[0], np.float64), float(g[1]), int(g[4]),
            np.asarray(line[0], np.float64), int(line[1]))


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a) - b) / np.linalg.norm(b))


# -- the passes against the plain reference and the host path ---------------

@pytest.mark.parametrize("table", ["bytes", "floats"])
def test_passes_agree_with_the_reference_and_the_host_path(table):
    """``grad_pass`` and ``line_pass`` on seeded random weights against the
    plain reference (float32 at ``highest``, standardization written out,
    a full forward a rung) and against the host path (``jax.grad`` over
    the standardized rows, ``calc_grad_shard`` / ``line_losses_shard``, in
    float64 here). Tolerances: the gradient to 2e-5 relative, the
    reference being float32 (its rows' sums, which cancel, leave ~1e-5 of
    the gradient at these weights); the losses to 1e-6 relative, a sum of
    positive terms that nothing cancels."""
    import jax.numpy as jnp
    from benchmark.reference import mlp as ref
    if table == "bytes":
        n = 6000
        x, y = _pixels(n)
        col, layers = DenseBlockColumn(x, n), LAYERS
        X = x.transpose(0, 2, 3, 1).reshape(-1, 784)[:n].astype(np.float64)
        labels = y.reshape(-1)[:n]
    else:
        X, labels, col = _float_table()
        X = X.astype(np.float64)
        n, layers = len(X), [20, 16, 8, 4]
        x = col.blocks
        y = col.pack(labels, col.block_rows, col.blocks.shape[0])
    mean, std = X.mean(0), X.std(0)
    std = np.where(std < 1e-12, 1.0, std)
    obj = M.MlpObjFunc(layers)
    rng = np.random.RandomState(5)
    coef = rng.randn(obj.dim) * 0.5 / np.sqrt(layers[0])
    direction = rng.randn(obj.dim) * 0.05
    steps = np.asarray([0.0, 2.0, 1.0, 0.5, 0.25])
    g, loss, rows, line, line_rows = _passes(
        obj, _shard(obj, col, labels if table == "bytes" else y, mean, std),
        coef, direction, steps)
    assert rows == line_rows == n
    ref_loss, ref_g = ref.grad_pass(x, y, n, layers, coef, mean, std)
    ref_line = ref.line_pass(x, y, n, layers, coef, direction, steps, mean,
                             std)
    assert _rel(g, ref_g) < 2e-5
    assert abs(loss - ref_loss) / ref_loss < 1e-6
    np.testing.assert_allclose(line, ref_line, rtol=1e-6)
    host = {"X": jnp.asarray((X - mean) / std), "w": jnp.ones(n),
            "y": jnp.asarray(labels, jnp.float64)}
    hg, hl, hw = obj.calc_grad_shard(host, jnp.asarray(coef))
    assert float(hw) == n
    assert _rel(g, np.asarray(hg)) < 2e-5
    np.testing.assert_allclose(loss, float(hl), rtol=1e-6)
    np.testing.assert_allclose(line, np.asarray(obj.line_losses_shard(
        host, jnp.asarray(coef), jnp.asarray(direction),
        jnp.asarray(steps))), rtol=1e-6)


def test_the_layer_1_shortcut_is_eleven_full_forwards():
    """The program's ladder, from one product of the stacked ``[W1; D1]``
    a block, against a full forward from scratch a rung, over the 11 rungs
    of the step program's ladder; the reference's own shortcut (what its
    stand-in fit takes) likewise, and a shortcut made from another
    direction's layer 1 does not agree."""
    from benchmark.reference import mlp as ref
    from alink_tpu.operator.common.optim.optimizers import LINE_LADDER
    n = 4096
    x, y = _pixels(n, seed=4)
    mean, std = np.full(784, 40.0), np.full(784, 70.0)
    rng = np.random.RandomState(6)
    obj = M.MlpObjFunc(LAYERS)
    coef = rng.randn(obj.dim) * 0.5 / np.sqrt(784)
    direction = rng.randn(obj.dim) * 0.05
    steps = 0.5 * LINE_LADDER
    assert len(steps) == 11
    full = ref.line_pass(x, y, n, LAYERS, coef, direction, steps, mean, std)
    line = _passes(obj, _shard(obj, DenseBlockColumn(x, n), y, mean, std),
                   coef, direction, steps)[3]
    np.testing.assert_allclose(line, full, rtol=1e-6)
    own = ref.unstack(direction, LAYERS)[0]
    short = ref.line_pass(x, y, n, LAYERS, coef, direction, steps, mean, std,
                          layer1=own)
    np.testing.assert_allclose(short, full, rtol=1e-6)
    other = ref.unstack(rng.randn(obj.dim) * 0.05, LAYERS)[0]
    stale = ref.line_pass(x, y, n, LAYERS, coef, direction, steps, mean,
                          std, layer1=other)
    assert np.abs(stale - full)[1:3].min() > 1e-5 * full[0]   # long steps


# -- the operator ---------------------------------------------------------------

def test_a_byte_table_fit_through_the_operator_agrees_with_the_reference():
    """``MultilayerPerceptronTrainBatchOp`` on a device-resident uint8
    column: its paths, its traces, every pass counting every row, and
    every checked superstep teacher-forced against the plain reference
    within the cell's own limits."""
    from benchmark import run as R
    from benchmark.reference import mlp as ref
    n = 6000
    x, y = _pixels(n)
    op = _mlp(_source(x, y, n))
    info = dict(op.get_train_info())
    assert info["paths"] == {"design": "blocks:uint8",
                             "moments": "linear_moments",
                             "pass": "blocked:bf16x3", "walk": "xla",
                             "rungs": "xla"}
    dim = M.stack_sizes(LAYERS)
    assert info["coef_trace"].shape == (4, dim) == info["dir_trace"].shape
    assert info["moments_rows"] == n and (info["rows_trace"] == n).all()
    assert info["layers"] == LAYERS and info["label_values"] == list(range(10))
    assert np.all(np.diff(info["loss_curve"]) < 0)
    config = R.tiny(R.load_cell("mlp-fit")["config"])
    params = dict(ref.learner(config), layers=LAYERS)
    got = ref.gaps(info, x, y, n, params)
    over = {k: v for k, v in got.items() if v > config["limits"][k]}
    assert not over, got


def test_the_host_path_and_the_blocked_path_fit_the_same_model():
    """The same float rows through host rows (``feature_cols``) and
    through a ``DenseBlockColumn``: the same first weights, the same
    supersteps, to the blocked path's product grade."""
    X, y, col = _float_table(n=2048, d=6, k=3)
    cols = [f"f{j}" for j in range(6)]
    host = MemSourceBatchOp(
        [tuple(float(v) for v in r) + (int(c),) for r, c in zip(X, y)],
        ", ".join(f"{c} DOUBLE" for c in cols) + ", label INT")
    a = MultilayerPerceptronTrainBatchOp(
        feature_cols=cols, label_col="label", layers=[5], max_iter=3,
        seed=2, epsilon=0.0).link_from(host)
    b = MultilayerPerceptronTrainBatchOp(
        vector_col="v", label_col="label", layers=[5], max_iter=3, seed=2,
        epsilon=0.0).link_from(MemSourceBatchOp(MTable(
            {"v": col, "label": RowBlockColumn(
                col.pack(y, col.block_rows, col.blocks.shape[0]), len(y))},
            TableSchema.parse("v VECTOR, label INT"))))
    ia, ib = a.get_train_info(), b.get_train_info()
    assert ia["paths"]["pass"] == "host:grad"
    assert ib["paths"]["pass"] == "blocked:highest"
    np.testing.assert_allclose(ib["loss_curve"], ia["loss_curve"], rtol=1e-5)
    ma = MlpModelConverter().load_model(a.get_output_table())
    mb = MlpModelConverter().load_model(b.get_output_table())
    np.testing.assert_allclose(mb["mean"], ma["mean"], rtol=1e-6)
    assert _rel(mb["coef"], ma["coef"]) < 1e-4


@pytest.mark.parametrize("workers", [1, 4, 8])
def test_every_worker_count_gives_one_answer(workers):
    """The byte table over 1, 4 and 8 virtual devices (8 blocks, so every
    count divides them): the one-device fit's weights to float32's
    rounding of the products, the same rungs, every row counted."""
    import jax
    n = 8 * 4096 - 700
    x, y = _pixels(n, seed=9)
    info = {}
    for nw in sorted({1, workers}):
        env = MLEnvironment(parallelism=nw, devices=jax.devices()[:nw])
        MLEnvironmentFactory.set_default(env)
        info[nw] = dict(_mlp(_source(x, y, n), hidden=(16, 8), max_iter=3)
                        .get_train_info())
    one, many = info[1], info[workers]
    assert (many["rows_trace"] == n).all() and many["moments_rows"] == n
    assert list(many["rung_trace"]) == list(one["rung_trace"])
    np.testing.assert_allclose(many["loss_curve"], one["loss_curve"],
                               rtol=1e-6)
    assert _rel(many["coef"], one["coef"]) < 1e-5


def test_restarts_and_a_second_l2_compile_nothing():
    """The first weights and ``l2`` enter ``jit_mlp_qn`` as data: a fit
    from another seed at another penalty compiles no program."""
    from jax import monitoring
    from alink_tpu.engine.comqueue import program_cache_stats
    n = 4096
    x, y = _pixels(n)
    src = _source(x, y, n)
    first = dict(_mlp(src, seed=1).get_train_info())
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_k: compiles.append(event) if event ==
        "/jax/core/compile/backend_compile_duration" else None)
    before = program_cache_stats()
    other = dict(_mlp(src, seed=2, l2=1e-2).get_train_info())
    after = program_cache_stats()
    assert not compiles
    assert after["misses"] == before["misses"]
    assert after["hits"] == before["hits"] + 2       # moments and steps
    assert not np.allclose(other["coef_trace"][0], first["coef_trace"][0])


def test_a_fit_records_its_spans_and_counts_its_rows():
    """Coarse spans under the operator's link (no flag set): ``mlp.fit``
    over extract, moments, optimize and model, none a superstep; the
    counters carry the rows each pass counted on the device."""
    from alink_tpu.common.metrics import get_registry
    from alink_tpu.common.tracing import get_tracer
    n = 4096
    x, y = _pixels(n)
    src = _source(x, y, n)
    _mlp(src, max_iter=5)                           # compile outside

    def count(name, **labels):
        return sum(float(r["value"]) for r in get_registry().snapshot()
                   if r["name"] == name and "value" in r
                   and all(r.get("labels", {}).get(k) == v
                           for k, v in labels.items()))
    names = ("alink_mlp_rows_total", "alink_mlp_supersteps_total",
             "alink_mlp_fits_total", "alink_mlp_passes_total")
    before = [count(c) for c in names]
    ring = get_tracer().events()
    mark = max((e["ts"] for e in ring), default=0.0)
    _mlp(src, max_iter=5)
    events = [e for e in get_tracer().events()
              if e["ts"] > mark and e.get("ph") == "X"]
    got = [e["name"] for e in events]
    for want in ("link:MultilayerPerceptronTrainBatchOp", "mlp.fit",
                 "mlp.extract", "mlp.moments", "mlp.optimize", "mlp.model"):
        assert got.count(want) == 1, want
    assert len(events) <= 40
    opt = next(e for e in events if e["name"] == "mlp.optimize")
    assert opt["args"] == {"layers": LAYERS, "max_iter": 5,
                           "pass": "blocked:bf16x3", "walk": "xla",
                           "rungs": "xla"}
    rows, steps, fits, passes = (count(c) - b for c, b in zip(names, before))
    assert (rows, steps, fits, passes) == (n * 11, 5, 1, 11)


def test_the_step_program_is_named_for_the_mlp():
    assert M.MLP_QN_PROGRAM == "mlp_qn"
    assert M.MlpObjFunc([4, 3, 2]).qn_program == "mlp_qn"


# -- the model table --------------------------------------------------------------

def test_the_model_table_carries_arrays_and_reads_old_text_tables():
    """The weights, means and deviations travel as array payloads (no text
    written in a fit); a table whose cells are the ``encode_array`` text
    (what older tables hold, and what ``str()`` of a payload is) loads to
    the same model, and both predict the same."""
    from alink_tpu.model.converters import ArrayPayload
    n = 4096
    x, y = _pixels(n)
    src = _source(x, y, n)
    train = _mlp(src, max_iter=2)
    table = train.get_output_table()
    cells = list(table.col("model_info"))
    assert all(isinstance(c, ArrayPayload) for c in cells[1:])
    text = MTable({"model_id": np.asarray(table.col("model_id")),
                   "model_info": np.asarray([str(c) for c in cells], object)},
                  table.schema)
    a, b = (MlpModelConverter().load_model(t) for t in (table, text))
    for key in ("coef", "mean", "std"):
        np.testing.assert_array_equal(a[key], b[key])
    assert json.loads(str(cells[1]))["shape"] == [M.stack_sizes(LAYERS)]
    assert a["layer_sizes"] == LAYERS

    def predict(model_op):
        return list(MultilayerPerceptronPredictBatchOp(prediction_col="p")
                    .link_from(model_op, src).collect_mtable().col("p"))
    from alink_tpu.operator.base import TableSourceBatchOp
    assert predict(train) == predict(TableSourceBatchOp(text))


# -- the controls, at a tiny size ---------------------------------------------

@pytest.fixture(scope="module")
def readings():
    from benchmark import controls_mlp
    from benchmark import run as R
    config = R.tiny(R.load_cell("mlp-fit")["config"])
    return controls_mlp.readings(31, config, supersteps=3), config["limits"]


def _over(got, limits):
    return [k for k, v in got.items()
            if v > (limits[k] if k in limits else 0.0)]


def test_the_clean_stand_in_reads_correct(readings):
    got, limits = readings
    assert _over(got["float32_again"], limits) == []


@pytest.mark.parametrize("control,number", [
    ("bfloat16", "grad_gap"), ("block_left_out", "moments_gap"),
    ("block_left_out", "rows_gap"), ("no_standardization", "grad_gap"),
    ("rung_off_by_one", "step_gap"), ("stale_zd1", "line_gap"),
    ("pair_dropped", "dir_gap"), ("pair_dropped", "coef_gap"),
    ("weights_unchanged", "coef_gap")])
def test_a_control_reads_not_correct_by_its_number(readings, control, number):
    got, limits = readings
    assert number in _over(got[control], limits), got[control]


def test_plain_bfloat16_fails_the_gradient_by_a_decade(readings):
    got, limits = readings
    assert got["bfloat16"]["grad_gap"] > 10 * limits["grad_gap"]


def test_a_stale_zd1_reads_in_the_ladders_losses_on_every_seed():
    """The shortcut's ``zd1`` from the previous superstep's direction: the
    ladder's losses move, and ``line_gap`` reads it on every seed of
    three, also where the rung it picks is the reference's argmin (so
    ``step_gap`` alone would not see it)."""
    from benchmark import controls_mlp
    from benchmark import run as R
    config = R.tiny(R.load_cell("mlp-fit")["config"])
    for seed in (31, 32, 33):
        got = controls_mlp.readings(seed, config, supersteps=3,
                                    only=["stale_zd1"])["stale_zd1"]
        assert "line_gap" in _over(got, config["limits"]), (seed, got)


def test_a_fault_in_one_leaf_is_read_by_its_leaf():
    """The gradient is compared a leaf at a time: the leaf of least norm
    off by twice the limit is under the limit in the norm of the whole
    vector and over it in its own."""
    from benchmark import mnist8m
    from benchmark import run as R
    from benchmark.reference import mlp as ref
    config = R.tiny(R.load_cell("mlp-fit")["config"])
    params = ref.learner(config)
    n = int(config["rows"])
    x, y = mnist8m.make_table(31, n, int(config["block_rows"]),
                              config["generator"])
    info = ref.fit(x, y, n, params, 31, 2)
    clean = info["grad_trace"][0].copy()
    at, pos = {}, 0
    for W, b in ref.unstack(clean, params["layers"]):
        for leaf in (W, b):
            at[(pos, pos + leaf.size)] = np.linalg.norm(leaf)
            pos += leaf.size
    lo, hi = min(at, key=at.get)
    limit = config["limits"]["grad_gap"]
    info["grad_trace"] = info["grad_trace"].copy()
    info["grad_trace"][:, lo:hi] *= 1 + 2 * limit
    assert _rel(info["grad_trace"][0], clean) < limit
    assert ref.gaps(info, x, y, n, params)["grad_gap"] > limit
