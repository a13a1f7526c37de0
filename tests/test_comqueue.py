"""Engine tests — mirror the reference's IterativeComQueueTest
(core/src/test/java/com/alibaba/alink/common/comqueue/IterativeComQueueTest.java):
testPI (Monte-Carlo pi over many supersteps, :39-64) and a full distributed
linear regression trained on the queue (:67-150).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from alink_tpu.common.mlenv import MLEnvironmentFactory
from alink_tpu.engine import (IterativeComQueue, AllReduce, AllGather,
                              BroadcastFromWorker0, ComputeFunction)


def test_pi():
    N = 1000  # supersteps, like the reference's 1000

    def sample(ctx):
        if ctx.is_init_step:
            ctx.put_obj("inside", jnp.zeros(()))
            ctx.put_obj("total", jnp.zeros(()))
        pts = jax.random.uniform(ctx.rng_key(), (128, 2))
        hit = ((pts ** 2).sum(-1) <= 1.0).sum().astype(jnp.float32)
        ctx.put_obj("local", jnp.stack([hit, jnp.asarray(128.0)]))

    def accumulate(ctx):
        s = ctx.get_obj("local")
        ctx.put_obj("inside", ctx.get_obj("inside") + s[0])
        ctx.put_obj("total", ctx.get_obj("total") + s[1])

    result = (IterativeComQueue(max_iter=N, seed=7)
              .add(sample)
              .add(AllReduce("local"))
              .add(accumulate)
              .exec())
    pi = 4.0 * result.get("inside") / result.get("total")
    assert result.step_count == N
    assert abs(pi - np.pi) < 0.01


def test_distributed_linear_regression():
    rng = np.random.RandomState(0)
    n, d = 1000, 5
    X = rng.randn(n, d)
    w_true = np.arange(1.0, d + 1.0)
    y = X @ w_true + 0.01 * rng.randn(n)
    data = np.concatenate([X, y[:, None], np.ones((n, 1))], axis=1)  # weight col guards padding

    def grad_stage(ctx):
        if ctx.is_init_step:
            ctx.put_obj("coef", jnp.zeros(d))
        block = ctx.get_obj("train")
        Xb, yb, wb = block[:, :d], block[:, d], block[:, d + 1]
        r = Xb @ ctx.get_obj("coef") - yb
        g = (Xb * (r * wb)[:, None]).sum(0)
        ctx.put_obj("gradcnt", jnp.concatenate([g, wb.sum()[None]]))

    def update(ctx):
        gc = ctx.get_obj("gradcnt")
        g = gc[:d] / gc[d]
        ctx.put_obj("coef", ctx.get_obj("coef") - 0.5 * g)

    def criterion(ctx):
        gc = ctx.get_obj("gradcnt")
        return jnp.linalg.norm(gc[:d] / gc[d]) < 1e-6

    result = (IterativeComQueue(max_iter=200)
              .init_with_partitioned_data("train", data)
              .add(grad_stage)
              .add(AllReduce("gradcnt"))
              .add(update)
              .set_compare_criterion(criterion)
              .exec())
    coef = result.get("coef")
    assert np.allclose(coef, w_true, atol=0.01)
    assert result.step_count < 200  # criterion fired early


def test_padding_and_totals():
    # 10 rows over 8 workers: padded to 16; weight column marks real rows
    data = np.ones((10, 2))

    def count(ctx):
        if ctx.is_init_step:
            ctx.put_obj("n", jnp.zeros(()))
        ctx.put_obj("cnt", ctx.get_obj("x")[:, 0].sum())
        ctx.put_obj("total", ctx.get_obj("__total_x"))

    result = (IterativeComQueue(max_iter=1)
              .init_with_partitioned_data("x", data)
              .add(count)
              .add(AllReduce("cnt"))
              .exec())
    assert result.get("cnt") == 10.0
    assert result.get("total") == 10


def test_allreduce_ops_and_gather_and_broadcast():
    def stage(ctx):
        tid = ctx.task_id.astype(jnp.float32)
        ctx.put_obj("v", tid + 1.0)
        ctx.put_obj("vmax", tid)
        ctx.put_obj("vmin", tid)
        ctx.put_obj("from0", tid + 42.0)

    result = (IterativeComQueue(max_iter=1)
              .add(stage)
              .add(AllReduce("v"))
              .add(AllReduce("vmax", op="max"))
              .add(AllReduce("vmin", op="min"))
              .add(AllGather("vmax"))
              .add(BroadcastFromWorker0("from0"))
              .exec())
    assert result.get("v") == 36.0  # sum(1..8)
    assert result.get("vmax") == 7.0
    assert result.get("vmin") == 0.0
    assert result.get("from0") == 42.0
    assert result.shards("v").shape == (8,)


def test_broadcast_data_and_close_with():
    out = (IterativeComQueue(max_iter=3)
           .init_with_broadcast_data("bias", np.asarray(5.0))
           .add(lambda ctx: ctx.put_obj("acc",
                (ctx.get_obj("acc") if not ctx.is_init_step else jnp.zeros(()))
                + ctx.get_obj("bias")))
           .close_with(lambda res: float(res.get("acc")))
           .exec())
    assert out == 15.0


def test_engine_mesh_size_generality():
    """BASELINE's scaling claim needs mesh-size generality, not just the
    8-device default: the same ComQueue program (PI + allreduce) must
    compile and run on 16 and 32 virtual devices. Runs in a subprocess
    because XLA's host-device count latches at backend init."""
    import os
    import subprocess
    import sys

    from bootenv import cpu_mesh_env

    code = """
import numpy as np
import jax
from alink_tpu.common.mlenv import MLEnvironment, MLEnvironmentFactory
from alink_tpu.engine import IterativeComQueue

n = len(jax.devices())
assert n == int(__import__("os").environ["WANT"]), (n,)
env = MLEnvironment(parallelism=n)
MLEnvironmentFactory.set_default(env)

def stage(ctx):
    import jax.numpy as jnp
    if ctx.is_init_step:
        ctx.put_obj("inside", jnp.zeros(()))
        ctx.put_obj("total", jnp.zeros(()))
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0), ctx.step_no), ctx.task_id)
    pts = jax.random.uniform(key, (256, 2))
    hit = ((pts ** 2).sum(1) <= 1.0).sum() * 1.0
    ctx.put_obj("inside", ctx.get_obj("inside") + ctx.all_reduce_sum(hit))
    ctx.put_obj("total", ctx.get_obj("total") + 256.0 * n)

res = (IterativeComQueue(env=env, max_iter=40)
       .add(stage).exec())
pi = 4.0 * float(res.get("inside")) / float(res.get("total"))
assert abs(pi - 3.14159) < 0.1, pi
print("pi ok", pi)
"""
    for want in (16, 32):
        env = cpu_mesh_env(want)
        env["WANT"] = str(want)
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))),
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, (want, r.stdout[-2000:], r.stderr[-2000:])
        assert "pi ok" in r.stdout, r.stdout


def test_program_cache_reuse_and_correctness():
    """A cached program re-runs correctly on FRESH data (the cache key
    must never bake data in), hits the cache on identical structure, and
    misses when the program key differs."""
    from alink_tpu.engine.comqueue import (clear_program_cache,
                                           program_cache_stats)

    def make_queue(scale):
        def stage(ctx):
            if ctx.is_init_step:
                ctx.put_obj("acc", jnp.zeros(()))
            x = ctx.get_obj("x")
            ctx.put_obj("acc", ctx.get_obj("acc")
                        + ctx.all_reduce_sum((scale * x).sum()))
        return stage

    clear_program_cache()
    base = program_cache_stats()
    x1 = np.arange(16, dtype=np.float32)
    q1 = (IterativeComQueue(max_iter=3)
          .init_with_partitioned_data("x", x1)
          .add(make_queue(1.0))
          .set_program_key(("cache_test", 1.0)))
    r1 = q1.exec()
    assert float(r1.get("acc")) == pytest.approx(3 * x1.sum())
    s = program_cache_stats()
    assert s["misses"] == base["misses"] + 1

    # same key, different data -> cache hit, result reflects NEW data
    x2 = np.arange(16, dtype=np.float32) * 10
    q2 = (IterativeComQueue(max_iter=3)
          .init_with_partitioned_data("x", x2)
          .add(make_queue(1.0))
          .set_program_key(("cache_test", 1.0)))
    r2 = q2.exec()
    assert float(r2.get("acc")) == pytest.approx(3 * x2.sum())
    s = program_cache_stats()
    assert s["hits"] == base["hits"] + 1

    # different key (different baked constant) -> miss, different program
    q3 = (IterativeComQueue(max_iter=3)
          .init_with_partitioned_data("x", x1)
          .add(make_queue(2.0))
          .set_program_key(("cache_test", 2.0)))
    r3 = q3.exec()
    assert float(r3.get("acc")) == pytest.approx(3 * 2.0 * x1.sum())
    s = program_cache_stats()
    assert s["misses"] == base["misses"] + 2

    # different max_iter with the same key -> engine must not reuse
    q4 = (IterativeComQueue(max_iter=5)
          .init_with_partitioned_data("x", x1)
          .add(make_queue(1.0))
          .set_program_key(("cache_test", 1.0)))
    r4 = q4.exec()
    assert float(r4.get("acc")) == pytest.approx(5 * x1.sum())


def test_program_cache_optimizer_fits():
    """Two same-shape optimizer fits share one compiled program; the
    second fit must return the correct result for ITS data."""
    from alink_tpu.engine.comqueue import program_cache_stats
    from alink_tpu.operator.common.optim.optimizers import (OptimParams,
                                                            optimize)
    from alink_tpu.operator.common.optim.objfunc import (LogLossFunc,
                                                         UnaryLossObjFunc)

    d = 8

    def make_data(seed):
        r = np.random.RandomState(seed)
        X = r.randn(512, d).astype(np.float32)
        y = (X @ r.randn(d) > 0).astype(np.float32) * 2 - 1
        return {"X": X, "y": y, "w": np.ones(512, np.float32)}

    obj = UnaryLossObjFunc(LogLossFunc(), dim=d)
    params = OptimParams(method="LBFGS", max_iter=25)
    before = program_cache_stats()
    c1, _, _ = optimize(obj, make_data(1), params)
    c2, _, _ = optimize(obj, make_data(2), params)
    after = program_cache_stats()
    assert after["hits"] >= before["hits"] + 1
    assert not np.allclose(c1, c2)
    for seed, coef in ((1, c1), (2, c2)):
        data = make_data(seed)
        acc = ((data["X"] @ coef > 0) == (data["y"] > 0)).mean()
        assert acc > 0.9, (seed, acc)


def test_program_cache_structural_guard():
    """An UNDER-SPECIFIED program_key (same key, different baked constant)
    must still miss: the stage bytecode/closure digest rides in the cache
    key (advisor r4). The old behavior silently re-ran the stale program."""
    from alink_tpu.engine.comqueue import (clear_program_cache,
                                           program_cache_stats)

    def make_stage(scale):
        def stage(ctx):
            if ctx.is_init_step:
                ctx.put_obj("acc", jnp.zeros(()))
            ctx.put_obj("acc", ctx.get_obj("acc")
                        + ctx.all_reduce_sum((scale * ctx.get_obj("x")).sum()))
        return stage

    clear_program_cache()
    x = np.arange(8, dtype=np.float32)

    def run(scale):
        return float((IterativeComQueue(max_iter=2)
                      .init_with_partitioned_data("x", x)
                      .add(make_stage(scale))
                      .set_program_key(("underspecified",))  # scale NOT in key
                      .exec()).get("acc"))

    assert run(1.0) == pytest.approx(2 * x.sum())
    before = program_cache_stats()
    # same (bad) key, different closure constant: guard forces a miss and
    # the CORRECT result comes back
    assert run(3.0) == pytest.approx(2 * 3.0 * x.sum())
    after = program_cache_stats()
    assert after["misses"] == before["misses"] + 1
    # identical closure constant still hits
    assert run(3.0) == pytest.approx(2 * 3.0 * x.sum())
    assert program_cache_stats()["hits"] == after["hits"] + 1


def test_freeze_config_mixed_type_dict_keys():
    from alink_tpu.engine.comqueue import freeze_config
    k1 = freeze_config({1: "a", "b": 2.0})
    k2 = freeze_config({"b": 2.0, 1: "a"})
    assert k1 == k2
    hash(k1)  # must be hashable
    assert freeze_config({1: "a"}) != freeze_config({"1": "a"})


def test_result_memoize_and_release():
    def stage(ctx):
        if ctx.is_init_step:
            ctx.put_obj("s", jnp.zeros(()))
            ctx.put_obj("big", jnp.zeros(64))
        ctx.put_obj("s", ctx.get_obj("s") + ctx.all_reduce_sum(
            ctx.get_obj("x").sum()))

    x = np.ones(8, dtype=np.float32)
    res = (IterativeComQueue(max_iter=2)
           .init_with_partitioned_data("x", x).add(stage).exec())
    g1 = res.get("s")
    assert res.get("s") is g1          # repeated get() served from host
    sh = res.shards("big")
    assert res.shards("big") is sh
    res.release()                       # drop device refs
    assert float(res.get("s")) == pytest.approx(2 * 8.0)
    np.testing.assert_array_equal(res.shards("big"), sh)
    with pytest.raises(KeyError):
        res.shards("x")                 # never fetched -> dropped


# -- collectives: one path, what each trainer asks of it --------------------

_COLLECTIVE_OP = r"\b(?:all-reduce|all-gather|reduce-scatter|" \
                 r"collective-permute|all-to-all)(?:-start)?\("


def _superstep_requests(fit):
    """Run ``fit`` and return ``{program label: (asked, compiled)}`` for
    every engine program it built: ``asked`` is the ``(kind, name)`` list
    ONE superstep's trace wrote into the manifest (the loop body's; the
    init pass's for a queue with no loop), ``compiled`` the number of
    collective ops in the compiled module against the number the module's
    traced passes asked for."""
    import re
    import alink_tpu.engine.comqueue as cq
    out = {}
    orig = cq.IterativeComQueue.exec

    def spy(q):
        before = set(cq._PROGRAM_CACHE_MANIFESTS)
        res = orig(q)
        for key in set(cq._PROGRAM_CACHE_MANIFESTS) - before:
            (per,) = cq._PROGRAM_CACHE_MANIFESTS[key].values()
            hlo = q.lowered().compile().as_text()
            step = per["body"] if q.max_iter > 1 else per["init"]
            out[cq._program_label(q._program_key)] = (
                [(kind, name) for kind, name, _ in step],
                (len(re.findall(_COLLECTIVE_OP, hlo)),
                 len(per["init"]) + len(per["body"])))
        return res

    cq.clear_program_cache()
    cq.IterativeComQueue.exec = spy
    try:
        fit()
    finally:
        cq.IterativeComQueue.exec = orig
        cq.clear_program_cache()
    return out


def _optimizer_fit(method, l1=0.0):
    def fit(env, r):
        import alink_tpu.operator.common.optim.optimizers as O
        from alink_tpu.operator.common.optim.objfunc import (
            LogLossFunc, UnaryLossObjFunc)
        X = r.randn(48, 5)
        data = {"X": X, "y": np.where(X[:, 0] > 0, 1.0, -1.0),
                "w": np.ones(48)}
        O.optimize(UnaryLossObjFunc(LogLossFunc(), 5, l1=l1, l2=1e-3), data,
                   O.OptimParams(method=method, max_iter=3, epsilon=0.0), env)
    return fit


def _softmax_fit(env, r):
    """``SoftmaxTrainBatchOp``'s path on a table of bytes: the moments
    program, then the quasi-Newton program over the blocked walk."""
    import alink_tpu.operator.common.optim.optimizers as O
    from alink_tpu.common.columnar import as_block_column, block_weights
    from alink_tpu.operator.common.linear.base import linear_moments
    from alink_tpu.operator.common.optim.objfunc import SoftmaxObjFunc
    X = r.randint(0, 256, (96, 6)).astype(np.uint8)
    col = as_block_column(X, env.num_workers)
    mean, std, _ = linear_moments(col, block_weights(col, None, np.float64),
                                  env)
    O.optimize(SoftmaxObjFunc(3, 7, l2=1e-3, reg_free_cols=1),
               {"X": col, "y": r.randint(0, 3, 96), "w": None,
                "scale": 1 / std, "shift": mean / std},
               O.OptimParams(max_iter=3, epsilon=0.0), env)


def _kmeans_fit(env, r):
    from alink_tpu.operator.common.clustering.kmeans import kmeans_train
    kmeans_train(r.randn(64, 3).astype(np.float32), k=3, max_iter=4,
                 env=env, init="K_MEANS_PARALLEL")


def _als_fit(shard_solve):
    def fit(env, r):
        from alink_tpu.operator.common.recommendation import als as A
        A.als_train(r.randint(0, 24, 300), r.randint(0, 16, 300),
                    (r.rand(300) * 5).astype(np.float32),
                    A.AlsTrainParams(rank=3, num_iter=3, lambda_reg=0.1,
                                     shard_solve=shard_solve), env=env)
    return fit


def _fm_fit(env, r):
    from alink_tpu.operator.common.fm.fm import FmTrainParams, fm_train
    X = r.randn(64, 8).astype(np.float32)
    fm_train({"X": X, "y": np.where(X[:, 0] > 0, 1.0, -1.0).astype(
        np.float32), "w": np.ones(64, np.float32)}, 8,
        FmTrainParams(num_factors=2, num_epochs=2), env=env)


def _word2vec_fit(env, r):
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.common.nlp.word2vec import (Word2VecParams,
                                                        word2vec_train)
    words = [f"w{i}" for i in range(16)]
    rows = [(" ".join(r.choice(words, 8)),) for _ in range(16)]
    word2vec_train(MTable(rows, "doc STRING"), "doc",
                   Word2VecParams(vector_size=4, min_count=1, num_iter=2,
                                  window=2, batch_size=16), env=env)


def _lda_fit(train):
    def fit(env, r):
        from alink_tpu.operator.common.clustering import lda
        getattr(lda, train)(r.randint(0, 12, (8, 6)).astype(np.int32),
                            np.ones((8, 6), np.float64), k=2, V=12,
                            num_iter=2, env=env)
    return fit


def _quantile_fit(env, r):
    from alink_tpu.operator.common.dataproc.quantile import (
        distributed_quantiles)
    distributed_quantiles(r.randn(128, 3), np.array([0.25, 0.5, 0.75]),
                          env=env)


def _gbdt_fit(env, r):
    from alink_tpu.operator.common.tree.trainers import (TreeTrainParams,
                                                         gbdt_train)
    X = r.randn(64, 4).astype(np.float32)
    gbdt_train(X, (X[:, 0] > 0).astype(np.float32),
               TreeTrainParams(num_trees=2, max_depth=3, n_bins=8), False, env)


_QN = [("AllReduce", "glw"), ("AllReduce", "line_losses")]
# a fit is two programs since PR 33. The grouping sums each side's
# per-worker counts (whole numbers). A superstep's half-sweep walks its
# ranked rows in batches; the batch loop is ONE traced body a tier (these
# sizes have one tier), so a half-sweep asks once for the batch's summed
# equations ``als_eq`` (Gram sums, right-hand sides and the residual's
# lanes in one (rows, 128, 128) buffer; it runs once a batch), or with
# shard_solve for their reduce-scatter and the solved rows' all-gather;
# then the ratings folded and the squared error, once an iteration
_ALS_GROUP = [("AllReduce", "als_count_u"), ("AllReduce", "als_count_i")]
_ALS_TAIL = [("AllReduce", "als_seen"), ("AllReduce", "als_rmse")]
_ALS_EQ = [("AllReduce", "als_eq")]
_ALS_EQ_SHARDED = [("ReduceScatter", "als_eq"), ("AllGather", "als_factors")]
_INLINE = ("InlineAllReduce", "<inline>")

# trainer -> (fit, {program label: the (kind, name) list of ONE superstep})
_SUPERSTEP_COLLECTIVES = {
    # the line-search loss needs the direction built from the psummed
    # gradient: dependency-forced, 2 a superstep whatever the compiler does
    "lbfgs": (_optimizer_fit("LBFGS"), {"linear_qn": _QN}),
    "owlqn": (_optimizer_fit("OWLQN", l1=1e-3), {"linear_qn": _QN}),
    # the table of bytes: its moments are gathered (a worker's mean, its
    # squared deviations and its weight, joined pairwise) and its rows
    # summed; a superstep then asks for the same two psums as any other
    # quasi-Newton fit, the rows of each pass riding them
    "softmax_bytes": (_softmax_fit, {
        "linear_moments": [("AllGather", "linear_moments"), _INLINE],
        "linear_qn": _QN}),
    "newton": (_optimizer_fit("Newton"),
               {"newton": [("AllReduce", "H"), ("AllReduce", "glw")]}),
    "kmeans": (_kmeans_fit, {
        "kmeans_init": [("AllGather", "kmpp_keys"),
                        ("AllGather", "kmpp_cands"), _INLINE],
        "kmeans_lloyd": [("AllReduce", "buf")]}),
    # two half-sweeps of normal equations, then the count and the rmse
    "als": (_als_fit(False), {
        "als_group": _ALS_GROUP,
        "als_sweep": _ALS_EQ + _ALS_EQ + _ALS_TAIL}),
    "als_shard_solve": (_als_fit(True), {
        "als_group": _ALS_GROUP,
        "als_sweep": _ALS_EQ_SHARDED + _ALS_EQ_SHARDED + _ALS_TAIL}),
    "fm": (_fm_fit, {"fm": [("AllReduce", "avg"), ("AllReduce", "lw")]}),
    "word2vec": (_word2vec_fit, {"w2v": [("AllReduce", "emb")]}),
    "lda_online": (_lda_fit("online_lda_train"),
                   {"lda_online": [_INLINE] * 5}),
    "lda_gibbs": (_lda_fit("gibbs_lda_train"), {"lda_gibbs": [_INLINE]}),
    "quantile": (_quantile_fit, {"quantile_hist": [
        ("AllReduce", "quantile_max"), ("AllReduce", "quantile_min"),
        _INLINE]}),
    # a histogram a level (max_depth 3) AFTER the level's block loop, the
    # leaves, the loss; since PR 31 the queue is named gbdt_grow and the
    # binning before it is a program of its own that asks for nothing (a
    # table this small takes its edges from np.quantile on the host; a
    # blocked one runs the quantile pass as gbdt_edges, "quantile" below)
    "gbdt": (_gbdt_fit, {"gbdt_bin": [], "gbdt_grow": [
        ("AllReduce", "tree_hist")] * 3 + [
        ("AllReduce", "tree_leaf_hist"), ("AllReduce", "gbdt_loss")]}),
}


@pytest.mark.parametrize("trainer", sorted(_SUPERSTEP_COLLECTIVES))
def test_superstep_collectives_requested(trainer):
    """What each iterative trainer ASKS of the interconnect in one
    superstep on a 4-device mesh: the record a multi-chip cell is planned
    from, and one no compiler upgrade moves. The compiled module may hold
    fewer collectives than asked (XLA's combiner merges independent
    ones), never more."""
    from alink_tpu.common.mlenv import MLEnvironment
    fit, want = _SUPERSTEP_COLLECTIVES[trainer]
    env = MLEnvironment(parallelism=4, devices=jax.devices()[:4])
    got = _superstep_requests(lambda: fit(env, np.random.RandomState(0)))
    assert {k: asked for k, (asked, _) in got.items()} == want
    for label, (asked, (compiled, asked_in_module)) in got.items():
        # a program that asks for no collective holds none
        assert (0 < compiled <= asked_in_module if asked_in_module
                else compiled == 0), (label, compiled)


_RAW_COLLECTIVES = {
    "psum": (lambda x: jax.lax.psum(x, "d"), "AllReduce", {}),
    "pmax": (lambda x: jax.lax.pmax(x, "d"), "AllReduce", {}),
    "pmin": (lambda x: jax.lax.pmin(x, "d"), "AllReduce", {}),
    "all_gather": (lambda x: jax.lax.all_gather(x, "d", axis=0, tiled=True),
                   "AllGather", {"axis": 0, "tiled": True}),
    "psum_scatter": (lambda x: jax.lax.psum_scatter(
        x, "d", scatter_dimension=0, tiled=True),
        "ReduceScatter", {"scatter_dimension": 0, "tiled": True}),
}


@pytest.mark.parametrize("op", sorted(_RAW_COLLECTIVES))
def test_manifest_wrapper_lowers_to_raw_op(op):
    """Each ``manifest_*`` wrapper is the raw ``lax`` op plus exactly one
    ``(kind, name, bytes)`` record: the lowered text is the same."""
    from jax.sharding import Mesh, PartitionSpec as P
    from alink_tpu.common.compat import shard_map
    from alink_tpu.engine import communication as comm
    raw, kind, kwargs = _RAW_COLLECTIVES[op]
    wrapper = getattr(comm, "manifest_" + op)
    mesh = Mesh(np.array(jax.devices()[:4]), ("d",))
    x = np.ones((16, 3), np.float32)

    def lowered(body):
        def f(x):
            return body(x)
        return jax.jit(shard_map(f, mesh=mesh, in_specs=(P("d"),),
                                 out_specs=P("d"), check_vma=False)
                       ).lower(x).as_text()

    manifest = []
    with comm.collecting(manifest):
        wrapped = lowered(lambda x: wrapper(x, "d", name="v", num_workers=4,
                                            **kwargs))
    assert wrapped == lowered(raw)
    assert manifest == [(kind, "v", 4 * 3 * 4 * 4)]  # a (4, 3) f32 shard x 4


def test_prepare_passes_a_resident_byte_table_and_its_int_labels_untouched():
    """A device-resident uint8 input and an int32 label column beside it
    reach the program as they are: no cast, no second copy (``_prepare``
    hands the very arrays on when the blocks divide over the workers)."""
    from alink_tpu.common.mlenv import MLEnvironment
    x = jnp.asarray(np.arange(4 * 3 * 32 * 128, dtype=np.uint8)
                    .reshape(4, 3, 32, 128))
    y = jnp.asarray(np.arange(4 * 32 * 128, dtype=np.int32)
                    .reshape(4, 32, 128))
    env = MLEnvironment(parallelism=4, devices=jax.devices()[:4])
    q = (IterativeComQueue(env=env, max_iter=1)
         .init_with_partitioned_data("x", x)
         .init_with_partitioned_data("y", y))
    parts, totals, _ = q._prepare(4)
    assert parts["x"] is x and parts["y"] is y
    assert totals == {"x": 4, "y": 4}

    def stage(ctx):
        xs, ys = ctx.get_obj("x"), ctx.get_obj("y")
        assert xs.dtype == jnp.uint8 and ys.dtype == jnp.int32
        ctx.put_obj("s", ctx.all_reduce_sum(
            xs.astype(jnp.int32).sum() + ys.sum()))
    res = q.add(stage).exec()
    assert int(res.get("s")) == int(np.asarray(x, np.int64).sum()
                                    + np.asarray(y, np.int64).sum())
