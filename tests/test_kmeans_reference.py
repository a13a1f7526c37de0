"""The blocked KMeans path against the plain reference of the benchmark
(``benchmark/reference/kmeans.py``) at small sizes on the CPU: the dense
block column, the trainer on 1 and on 4 workers, the operator on a
device-resident block and on host columns, the host recluster, and the
spans, counters and program names the benchmark reads."""

import numpy as np
import pytest

from alink_tpu.common.columnar import DenseBlockColumn
from alink_tpu.common.mlenv import MLEnvironment
from alink_tpu.common.mtable import MTable
from alink_tpu.common.types import TableSchema
from alink_tpu.operator.batch.clustering.kmeans_ops import (
    KMeansModelDataConverter, KMeansPredictBatchOp, KMeansTrainBatchOp)
from alink_tpu.operator.batch.source import MemSourceBatchOp
from alink_tpu.operator.common.clustering import kmeans as K
from benchmark.reference import kmeans as ref

N, D, KC, BLOCK = 5000, 20, 10, 1024      # 5 blocks, the last 904 rows


def _table(seed=0, n=N, d=D, dtype=np.float32):
    rng = np.random.RandomState(seed)
    centres = rng.uniform(-4, 4, (5, d))
    spread = rng.uniform(0.6, 1.8, 5)
    cid = rng.randint(5, size=n)
    return (centres[cid] + spread[cid, None] * rng.randn(n, d)).astype(dtype)


def _fit(X, block=BLOCK, **kw):
    info = {}
    col = DenseBlockColumn.from_rows(X, block)
    kw.setdefault("max_iter", 5)
    C, w, steps = K.kmeans_train(col, KC, seed=kw.pop("seed", 3), info=info,
                                 **kw)
    return col, np.asarray(C), np.asarray(w), steps, info


# -- the dense block column ---------------------------------------------------

def test_dense_block_column_round_trip_and_row_surface():
    X = _table(1, n=2500, d=3)
    col = DenseBlockColumn.from_rows(X, 1024)
    assert col.blocks.shape == (3, 3, 8, 128) and len(col) == 2500
    assert col.dim == 3 and col.block_rows == 1024 and not col.on_device
    assert np.array_equal(col.to_rows(), X)
    assert np.array_equal(col[1030].data, X[1030].astype(np.float64))
    assert np.array_equal(col[-1].data, X[-1].astype(np.float64))
    assert np.all(col.blocks[2].reshape(3, -1)[:, 2500 - 2048:] == 0)
    sub = col[np.arange(0, 2500, 7)]
    assert isinstance(sub, DenseBlockColumn)
    assert np.array_equal(sub.to_rows(), X[::7])
    assert np.array_equal(K.take_rows(col, [0, 1024, 2499]), X[[0, 1024, 2499]])
    with pytest.raises(ValueError):
        DenseBlockColumn(np.zeros((1, 3, 4, 128), np.float32), 10)
    with pytest.raises(ValueError):
        DenseBlockColumn(np.zeros((1, 3, 8, 128), np.float32), 1025)


def test_block_rows_are_whole_register_tiles():
    assert DenseBlockColumn.block_rows_for(150) == 1024
    assert DenseBlockColumn.block_rows_for(10 ** 8) == 65536
    assert DenseBlockColumn.block_rows_for(10 ** 8, 100000) == 100352
    assert DenseBlockColumn.block_rows_for(3000, 1 << 20) == 3072


def test_a_table_holds_the_block_as_one_vector_column():
    import jax.numpy as jnp
    X = _table(2, n=300, d=4)
    col = DenseBlockColumn(jnp.asarray(DenseBlockColumn.pack(X, 1024)), 300)
    assert col.on_device
    t = MTable({"features": col, "id": np.arange(300)},
               TableSchema.parse("features VECTOR, id LONG"))
    assert t.num_rows == 300 and t.col("features") is col
    assert np.allclose(t.row(17)[0].data, X[17])
    assert t.take_rows([5, 6]).col("features").to_rows().shape == (2, 4)
    from alink_tpu.operator.common.dataproc.feature_extract import (
        extract_dense_matrix, extract_design)
    design = extract_design(t, None, "features", np.float32)
    assert design["kind"] == "dense" and design["X"] is col
    assert np.array_equal(extract_dense_matrix(t, None, "features", np.float32), X)


# -- the trainer against the reference -----------------------------------------

@pytest.mark.parametrize("weighted", [False, True], ids=["unit", "weighted"])
def test_fit_follows_the_reference(weighted):
    X = _table(5)
    sw = np.random.RandomState(9).uniform(0.2, 3.0, N).astype(np.float32) \
        if weighted else None
    col, C, w, steps, info = _fit(X, sample_weight=sw)
    wb = DenseBlockColumn.pack(sw, BLOCK) if weighted else None
    total = float(sw.sum()) if weighted else float(N)
    assert steps == 5 and info["rows"].tolist() == [N] * 5
    assert info["init_rows"].tolist() == [N] * 5
    want = ref.lloyd(col.blocks, N, wb, info["init_centroids"], steps)
    spread = ref.rms_spread(col.blocks, N, wb)
    got = ref.gaps(info, want, total, spread)
    assert got["centroid_gap"] < 1e-5 and got["inertia_gap"] < 1e-5, got
    assert got["weight_gap"] < 1e-6, got
    assert np.allclose(C, want[-1]["centroids"], atol=1e-4 * spread)
    assert np.allclose(w, want[-1]["weights"], rtol=1e-5)
    assert [s["rows"] for s in want] == [N] * 5
    # k-means||: every candidate is a row, the counted weights recount
    cands, m = info["init_candidates"], 1 + 4 * 20
    assert cands.shape == (101, D)
    assert ref.member_gaps(col.blocks, N, cands).max() == 0.0
    recount = ref.candidate_weights(col.blocks, N, wb, cands[:m])
    assert np.allclose(info["init_weights"][:m], recount, rtol=1e-5, atol=1e-3)
    assert np.all(info["init_weights"][m:] == 0)
    assert abs(recount.sum() - total) < 1e-3 * total


def test_four_workers_give_the_centroids_of_one():
    X = _table(6)
    out = []
    for nw in (1, 4):
        env = MLEnvironment(parallelism=nw)
        info = {}
        C, w, _ = K.kmeans_train(X, KC, max_iter=5, seed=11, env=env,
                                 info=info)
        out.append((np.asarray(C), np.asarray(w), info["init_candidates"]))
    assert np.array_equal(out[0][2], out[1][2]), "same candidates drawn"
    assert np.allclose(out[0][0], out[1][0], rtol=1e-5, atol=1e-5)
    assert np.allclose(out[0][1], out[1][1])


def test_cosine_still_follows_a_plain_replay():
    X = _table(7, n=1500, d=6, dtype=np.float64) + 6.0
    info = {}
    C, w, steps = K.kmeans_train(X, 4, max_iter=4, seed=2, info=info,
                                 distance_type="COSINE")
    c = info["init_centroids"].astype(np.float64)
    Xn = X / np.linalg.norm(X, axis=1, keepdims=True)
    for s in range(steps):
        near = np.argmax(Xn @ (c / np.linalg.norm(c, axis=1, keepdims=True)).T, 1)
        c = np.stack([X[near == j].mean(0) if (near == j).any() else c[j]
                      for j in range(4)])
        assert np.allclose(info["centroids"][s], c, rtol=1e-6, atol=1e-6)
    assert np.allclose(np.asarray(w), np.bincount(near, minlength=4))


def test_assign_clusters_and_the_mapper_share_the_trainers_distance():
    X = _table(8, n=700, d=5, dtype=np.float64)
    C = X[:3]
    ids, dist = K.assign_clusters(X, C)
    plain = ((X[:, None, :] - C[None]) ** 2).sum(-1)
    assert np.array_equal(np.asarray(ids), plain.argmin(1))
    assert np.allclose(np.asarray(dist), plain.min(1))
    ids2, dist2 = K.assign_table(DenseBlockColumn.from_rows(X), C)
    assert np.array_equal(ids2, plain.argmin(1)) and np.allclose(dist2, plain.min(1))


# -- the operator ------------------------------------------------------------------

def test_a_device_resident_block_fits_as_the_same_table_in_host_columns():
    import jax.numpy as jnp
    X = _table(10, n=3000)
    names = [f"f{j}" for j in range(D)]
    host = MemSourceBatchOp(MTable({n_: X[:, j].astype(np.float64)
                                    for j, n_ in enumerate(names)}))
    # the same blocking as host rows get (k-means|| keys its draws by
    # block, so a fit is a function of the table AND its blocking)
    packed = K.as_block_column(X.astype(np.float64), 8)
    col = DenseBlockColumn(jnp.asarray(packed.blocks), 3000)
    dev = MemSourceBatchOp(MTable({"features": col},
                                  TableSchema.parse("features VECTOR")))
    a = KMeansTrainBatchOp(k=KC, max_iter=5, seed=4,
                           feature_cols=names).link_from(host)
    b = KMeansTrainBatchOp(k=KC, max_iter=5, seed=4,
                           vector_col="features").link_from(dev)
    ma = KMeansModelDataConverter().load_model(a.get_output_table())
    mb = KMeansModelDataConverter().load_model(b.get_output_table())
    assert np.array_equal(ma.centroids, mb.centroids)
    assert np.array_equal(ma.weights, mb.weights)
    assert b.get_train_info()["steps"] == 5
    # and predicts alike, row for row
    pa = KMeansPredictBatchOp(prediction_col="c").link_from(a, host)
    pb = KMeansPredictBatchOp(prediction_col="c").link_from(b, dev)
    assert np.array_equal(np.asarray(pa.collect_mtable().col("c")),
                          np.asarray(pb.collect_mtable().col("c")))


def test_spans_counters_and_program_names(monkeypatch, quiet_tracer):
    from alink_tpu.common.metrics import MetricsRegistry, set_registry
    monkeypatch.setenv("ALINK_TPU_TRACE", "1")
    prev = set_registry(MetricsRegistry())
    try:
        from alink_tpu.common.metrics import get_registry
        X = _table(12, n=2000, d=4)
        src = MemSourceBatchOp(MTable(
            {"features": DenseBlockColumn.from_rows(X, 1024)},
            TableSchema.parse("features VECTOR")))
        op = KMeansTrainBatchOp(k=3, max_iter=4, seed=1,
                                vector_col="features").link_from(src)
        reg = get_registry()
        steps = op.get_train_info()["steps"]
        assert reg.value("alink_kmeans_fits_total") == 1
        assert reg.value("alink_kmeans_supersteps_total") == 5 + steps
        assert reg.value("alink_kmeans_rows_total") == 2000 * (5 + steps)
    finally:
        set_registry(prev)
    events = [e for e in quiet_tracer.events() if e.get("ph") == "X"]
    byname = {}
    for e in events:
        byname.setdefault(e["name"], []).append(e)
    for name in ("kmeans.fit", "kmeans.init", "kmeans.recluster",
                 "kmeans.lloyd", "kmeans.model"):
        assert len(byname[name]) == 1, name
    fit = byname["kmeans.fit"][0]
    for name in ("kmeans.init", "kmeans.recluster", "kmeans.lloyd",
                 "kmeans.model"):
        assert byname[name][0]["parent"] == fit["id"], name
    # the engine's three spans, two executions
    assert len(byname["comqueue.exec"]) == 2
    assert len(byname["comqueue.prepare"]) == 2
    assert len(byname["comqueue.fetch"]) >= 2
    assert all("nbytes" in e["args"] for e in byname["comqueue.fetch"])
    labels = {e["args"]["program"] for e in byname["comqueue.exec"]}
    assert labels == {K.INIT_PROGRAM, K.LLOYD_PROGRAM}


def test_programs_carry_stable_names_and_scopes():
    from alink_tpu.engine import IterativeComQueue
    from alink_tpu.engine.comqueue import _name_program
    X = _table(13, n=2000, d=4)
    seen = {}
    real = IterativeComQueue.exec

    def spy(self):
        seen[self._program_key[0]] = self.lowered().compile().as_text()
        return real(self)

    IterativeComQueue.exec = spy
    try:
        K.kmeans_train(X, 3, max_iter=2, seed=0, env=MLEnvironment(parallelism=1))
    finally:
        IterativeComQueue.exec = real
    assert "jit_kmeans_lloyd" in seen[K.LLOYD_PROGRAM]
    assert "jit_kmeans_init" in seen[K.INIT_PROGRAM]
    for scope in ("kmeans_assign", "kmeans_accumulate"):
        assert scope in seen[K.LLOYD_PROGRAM], scope
    for scope in ("kmpp_sample", "kmpp_topk"):
        assert scope in seen[K.INIT_PROGRAM], scope

    def run():
        pass
    assert _name_program(run, None).__name__ == "run"
    assert _name_program(run, ("qn", 3), "_cont").__name__ == "qn_cont"


def test_a_resident_partitioned_input_reaches_the_program_untouched(monkeypatch):
    import jax
    import jax.numpy as jnp
    from alink_tpu.engine import IterativeComQueue
    env = MLEnvironment(parallelism=4)
    x = jax.device_put(np.arange(32, dtype=np.float32).reshape(8, 4))
    real = jax.jit
    got = {}

    def spy_jit(fn, *a, **kw):
        compiled = real(fn, *a, **kw)

        def call(parts, bcast):
            got["same"] = parts["x"] is x
            return compiled(parts, bcast)
        return call

    def stage(ctx):
        ctx.put_obj("s", ctx.all_reduce_sum(ctx.get_obj("x").sum()))

    monkeypatch.setattr(jax, "jit", spy_jit)
    res = (IterativeComQueue(env=env, max_iter=1)
           .init_with_partitioned_data("x", x).add(stage).exec())
    assert got["same"], "no pad, no copy, no host round trip"
    assert float(res.get("s")) == float(np.arange(32).sum())


def test_lowered_takes_an_abstract_resident_input():
    """A program at a size no host holds is lowered from shapes alone (the
    rehearsal that compiles it for a described chip)."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.engine import IterativeComQueue

    def stage(ctx):
        ctx.put_obj("s", ctx.all_reduce_sum(ctx.get_obj("x").sum()))

    x = jax.ShapeDtypeStruct((8, 1 << 20, 128), jnp.float32)
    low = (IterativeComQueue(env=MLEnvironment(parallelism=4), max_iter=2)
           .init_with_partitioned_data("x", x).add(stage)
           .set_program_key(("abstract_sum",)).lowered())
    assert "jit_abstract_sum" in low.as_text()
    assert "2x1048576x128xf32" in low.as_text()      # a worker's shard


# -- the host recluster ------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 5, 2 ** 31 - 7])
def test_reference_recluster_matches_the_programs_on_one_random_stream(seed):
    rng = np.random.RandomState(seed)
    cands = rng.randn(101, 20) * 3
    w = rng.uniform(0, 50, 101)
    w[-20:] = 1.0
    a = K._weighted_kmeans_pp(cands, w, 10, np.random.RandomState(seed))
    b = ref.weighted_kmeans_pp(cands, w, 10, np.random.RandomState(seed))
    assert np.allclose(a, b, rtol=1e-12, atol=1e-12)
