"""KMeans tests — mirrors the reference KMeansExample iris pipeline
(examples/KMeansExample.java:14-32) with a synthetic blob fixture."""

import numpy as np
import pytest

from alink_tpu.operator.base import TableSourceBatchOp
from alink_tpu.operator.batch.source import MemSourceBatchOp
from alink_tpu.operator.batch.clustering.kmeans_ops import (
    KMeansTrainBatchOp, KMeansPredictBatchOp, KMeansModelDataConverter)
from alink_tpu.operator.batch.evaluation import EvalClusterBatchOp
from alink_tpu.pipeline.clustering import KMeans
from alink_tpu.common import MTable, DenseVector


def _blobs(n_per=60, seed=0):
    rng = np.random.RandomState(seed)
    centers = np.asarray([[0.0, 0.0], [6.0, 6.0], [0.0, 7.0]])
    rows, labels = [], []
    for ci, c in enumerate(centers):
        pts = c + 0.4 * rng.randn(n_per, 2)
        rows += [tuple(p) for p in pts]
        labels += [ci] * n_per
    return rows, np.asarray(labels)


def test_kmeans_train_predict():
    rows, true = _blobs()
    src = MemSourceBatchOp([r + (int(t),) for r, t in zip(rows, true)],
                           "x DOUBLE, y DOUBLE, truth LONG")
    train = KMeansTrainBatchOp(k=3, feature_cols=["x", "y"], max_iter=50).link_from(src)
    pred = (KMeansPredictBatchOp(prediction_col="cluster_id",
                                 prediction_distance_col="dist")
            .link_from(train, src))
    out = pred.collect_mtable()
    ids = np.asarray(out.col("cluster_id"))
    # every true blob maps to exactly one cluster
    for t in range(3):
        assert len(set(ids[true == t])) == 1
    assert len(set(ids.tolist())) == 3
    assert np.asarray(out.col("dist")).max() < 3.0
    # converged early
    assert train._steps < 50


def test_kmeans_model_roundtrip():
    rows, _ = _blobs()
    src = MemSourceBatchOp(rows, "x DOUBLE, y DOUBLE")
    train = KMeansTrainBatchOp(k=3, feature_cols=["x", "y"]).link_from(src)
    model = KMeansModelDataConverter().load_model(train.get_output_table())
    assert model.centroids.shape == (3, 2)
    assert model.weights.sum() == pytest.approx(len(rows))
    # saved+reloaded via table round trip
    reloaded = KMeansModelDataConverter().load_model(
        MTable(train.get_output_table().to_rows(), train.get_output_table().schema))
    assert np.allclose(reloaded.centroids, model.centroids)


def test_kmeans_pipeline_and_eval():
    rows, true = _blobs()
    src = MemSourceBatchOp(rows, "x DOUBLE, y DOUBLE")
    km = KMeans(k=3, feature_cols=["x", "y"], prediction_col="cluster_id")
    model = km.fit(src)
    out = model.transform(src)
    vecs = [DenseVector([r[0], r[1]]) for r in rows]
    t2 = out.collect_mtable().add_column("vec", vecs)
    ev = (EvalClusterBatchOp(vector_col="vec", prediction_col="cluster_id")
          .link_from(TableSourceBatchOp(t2)))
    m = ev.collect_metrics()
    assert m.get("K") == 3
    assert m.get("SilhouetteCoefficient") > 0.7
    assert m.get("CalinskiHarabasz") > 100


def test_kmeans_cosine():
    rng = np.random.RandomState(1)
    a = rng.rand(50, 3) + np.asarray([5, 0, 0])
    b = rng.rand(50, 3) + np.asarray([0, 5, 0])
    rows = [tuple(r) for r in np.vstack([a, b])]
    src = MemSourceBatchOp(rows, "a DOUBLE, b DOUBLE, c DOUBLE")
    train = KMeansTrainBatchOp(k=2, feature_cols=["a", "b", "c"],
                               distance_type="COSINE").link_from(src)
    pred = KMeansPredictBatchOp(prediction_col="cid").link_from(train, src)
    ids = np.asarray(pred.collect_mtable().col("cid"))
    assert len(set(ids[:50])) == 1 and len(set(ids[50:])) == 1
    assert ids[0] != ids[50]


def test_kmeans_parallel_init_quality_parity():
    """K-MEANS|| seeding must match host kmeans++ quality (VERDICT item 5):
    final Lloyd cost ratio within 10% on a blob mixture."""
    from alink_tpu.operator.common.clustering.kmeans import kmeans_train

    rng = np.random.RandomState(0)
    k, d = 12, 6
    centers = rng.randn(k, d) * 8
    X = np.concatenate([c + rng.randn(400, d) for c in centers]).astype(np.float32)

    def final_cost(init):
        C, _, _ = kmeans_train(X, k=k, max_iter=30, tol=1e-5, init=init, seed=1)
        d2 = ((X[:, None, :] - C[None, :, :]) ** 2).sum(-1).min(1)
        return float(d2.sum())

    c_par = final_cost("K_MEANS_PARALLEL")
    c_pp = final_cost("K_MEANS_PLUS_PLUS")
    assert c_par <= c_pp * 1.10, (c_par, c_pp)


def test_kmeans_parallel_init_no_host_pass():
    """k=100 on 400k sharded rows: the seeding itself runs as one BSP
    program; only the O(rounds*oversample) candidate set reaches the host."""
    from alink_tpu.operator.common.clustering.kmeans import (
        kmeans_parallel_init)

    rng = np.random.RandomState(1)
    k = 100
    X = rng.randn(400_000, 8).astype(np.float32) * 3
    C = kmeans_parallel_init(X, k, seed=0)
    assert C.shape == (k, 8)
    assert np.isfinite(C).all()
    # seeds cover the data: every centroid is near some data region and
    # centroids are mutually distinct
    pd = ((C[:, None, :] - C[None, :, :]) ** 2).sum(-1)
    np.fill_diagonal(pd, np.inf)
    assert (pd.min(1) > 1e-6).all()


# -- k-means||: the running top-l and its threshold (PR 28) -------------------

def _topl_cases():
    rng = np.random.RandomState(7)
    inf = np.inf
    plain = rng.randn(6, 4, 8).astype(np.float32)
    across = plain.copy()              # one value in four blocks, and on top
    across[[0, 2, 3, 5], [1, 0, 3, 2], [2, 7, 0, 5]] = 9.0
    inside = plain.copy()              # runs of equal keys inside blocks
    inside[1, 0, :] = 5.0
    inside[4, 2:, 3] = 5.0
    inside[2] = -1.0
    few = np.full((5, 4, 8), -inf, np.float32)     # fewer than l finite keys
    few[[0, 3, 3, 4], [0, 1, 2, 3], [1, 5, 5, 7]] = [0.5, -2.0, 0.5, 3.0]
    none = np.full((3, 4, 8), -inf, np.float32)
    small = rng.randn(9, 3).astype(np.float32)     # blocks of 3 keys, l = 8
    small[[1, 6], [0, 2]] = small.max() + 1
    last = np.sort(rng.randn(6 * 32).astype(np.float32)).reshape(6, 4, 8)
    rising = last.copy()               # every block holds winners
    last[:5] = np.minimum(last[:5], last[5].min() - 1)
    tops = plain.copy()                # +inf keys, tied
    tops[[0, 4], [0, 1], [0, 1]] = inf
    return {"plain": (plain, 5), "equal_across_blocks": (across, 5),
            "equal_inside_blocks": (inside, 12), "few_finite": (few, 6),
            "none_finite": (none, 4), "block_under_l": (small, 8),
            "l_over_all_keys": (small[:2], 8),
            "winners_in_last_block": (last, 7), "every_block_wins": (rising, 7),
            "infinite_keys": (tops, 5), "l_is_one": (across, 1)}


@pytest.mark.parametrize("case", sorted(_topl_cases()))
def test_running_topl_is_top_k_of_the_shard(case):
    """``_topl_fold`` carried over a shard's blocks gives what
    ``lax.top_k`` over all its keys flattened gives — values, and the
    (block, position) of every finite one, equal keys to the lower row —
    and ranks a block only when one of its keys beats the threshold."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.operator.common.clustering.kmeans import _topl_fold

    keys, l = _topl_cases()[case]
    nb, per = keys.shape[0], int(np.prod(keys.shape[1:]))
    fold = jax.jit(_topl_fold)
    run = (jnp.full((l,), -jnp.inf, keys.dtype), jnp.zeros((l,), jnp.int32),
           jnp.zeros((l,), jnp.int32))
    ranked, want_ranked = [], []
    for i in range(nb):
        tau = float(run[0][-1])
        run, won = fold(run, jnp.asarray(keys[i]), i)
        ranked.append(bool(won))
        want_ranked.append(bool(keys[i].max() > tau))
    flat = np.concatenate([keys.reshape(-1),
                           np.full(max(l - keys.size, 0), -np.inf, keys.dtype)])
    wv, wi = (np.asarray(a) for a in jax.lax.top_k(jnp.asarray(flat), l))
    vals, blk, pos = (np.asarray(a) for a in run)
    assert vals.dtype == keys.dtype and blk.dtype == pos.dtype == np.int32
    assert np.array_equal(vals, wv)
    held = wv > -np.inf
    assert np.array_equal(blk[held], wi[held] // per)
    assert np.array_equal(pos[held], wi[held] % per)
    assert ((blk >= 0) & (blk < nb) & (pos >= 0) & (pos < per)).all()
    assert ranked == want_ranked
    if case == "winners_in_last_block":
        assert (blk == nb - 1).all() and ranked[-1]
    if case == "every_block_wins":
        assert all(ranked)
    if case == "none_finite":
        assert not any(ranked)


def _selection_cases():
    """Layouts that stress WHICH blocks the draw keys a second time: the
    ``l`` of the largest maxima, equal maxima to the lower block."""
    rng = np.random.RandomState(11)
    inf = np.inf
    plain = rng.randn(9, 4, 8).astype(np.float32)
    # tau = 2.0 is the maximum of blocks 1 (selected) and 6 (not), l = 3
    tied = np.minimum(plain, 1.0)
    tied[[1, 6], [2, 0], [3, 4]] = 2.0
    tied[[3, 7], [1, 1], [0, 0]] = [5.0, 4.0]
    # block 2 holds tau = 2.0 and is NOT selected: blocks 0 and 1 hold it
    # too, earlier, and blocks 5 and 8 beat it (l = 4)
    early = np.minimum(plain, 1.0)
    early[[0, 1, 2], [0, 3, 1], [5, 5, 5]] = 2.0
    early[[5, 8], [2, 2], [1, 1]] = [3.0, 6.0]
    # tau held three times by the selected block, once by a later one
    runs = np.minimum(plain, 1.0)
    runs[4, 1, 2:5] = 2.0
    runs[7, 0, 0] = 2.0
    runs[0, 0, 0] = 3.0
    # a selected block none of whose keys is kept: block 0 alone fills
    # the best 5, block 3's maximum is the second largest
    shadow = np.minimum(plain, 0.0)
    shadow[0, 0, :5] = [9.0, 8.0, 7.0, 6.0, 5.0]
    shadow[3, 2, 2] = 4.0
    few = np.full((9, 4, 8), -inf, np.float32)     # 3 finite keys, l = 6,
    few[[2, 2, 7], [0, 3, 1], [1, 1, 6]] = [1.0, 1.0, -3.0]  # in two blocks
    none = np.full((9, 4, 8), -inf, np.float32)
    last = np.sort(rng.randn(9 * 32).astype(np.float32)).reshape(9, 4, 8)
    last[:8] = np.minimum(last[:8], last[8].min() - 1)
    tops = plain.copy()                # +inf maxima, tied over three blocks
    tops[[1, 4, 6], [0, 1, 2], [0, 1, 2]] = inf
    return {"equal_maxima_selected_and_not": (tied, 3),
            "tau_in_an_earlier_unselected_block": (early, 4),
            "tau_inside_and_after_a_selected_block": (runs, 2),
            "selected_block_keeps_nothing": (shadow, 5),
            "few_finite": (few, 6), "none_finite": (none, 4),
            "fewer_blocks_than_l": (plain[:3], 7), "l_is_one": (tied, 1),
            "l_is_one_tied_maxima": (tops, 1),
            "winners_in_last_block": (last, 7),
            "infinite_maxima_tied": (tops, 2)}


def _draw_cases():
    return {**{"select:" + k: v for k, v in _selection_cases().items()},
            **{"fold:" + k: v for k, v in _topl_cases().items()}}


def _assert_top_k_of_shard(run, keys, l):
    """``run`` (values, block, position) is ``lax.top_k`` over the blocks'
    ``keys`` flattened, equal keys to the lower row; block and position of
    every finite one. Returns the blocks of the finite ones."""
    import jax
    import jax.numpy as jnp

    nb, per = keys.shape[0], int(np.prod(keys.shape[1:]))
    flat = np.concatenate([keys.reshape(-1),
                           np.full(max(l - keys.size, 0), -np.inf, keys.dtype)])
    wv, wi = (np.asarray(a) for a in jax.lax.top_k(jnp.asarray(flat), l))
    vals, blk, pos = (np.asarray(a) for a in run)
    assert vals.dtype == keys.dtype and blk.dtype == pos.dtype == np.int32
    assert np.array_equal(vals, wv)
    held = wv > -np.inf
    assert np.array_equal(blk[held], wi[held] // per)
    assert np.array_equal(pos[held], wi[held] % per)
    assert ((blk >= 0) & (blk < nb) & (pos >= 0) & (pos < per)).all()
    return blk[held]


@pytest.mark.parametrize("case", sorted(_draw_cases()))
def test_selected_blocks_draw_is_top_k_of_the_shard(case):
    """The draw as ``_kmpp_draw`` makes it, from the block maxima alone:
    ``_topl_of_selected`` over the ``min(l, blocks)`` blocks of the largest
    maxima gives what ``lax.top_k`` over all the shard's keys flattened
    gives (values, and the block and position of every finite one, equal
    keys to the lower row), whatever the other blocks hold; and counts the
    selected blocks that hold a finite key."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.operator.common.clustering.kmeans import _topl_of_selected

    keys, l = _draw_cases()[case]
    nb = keys.shape[0]
    maxima = keys.reshape(nb, -1).max(1)
    K = jnp.asarray(keys)
    run, ranked = jax.jit(lambda M: _topl_of_selected(
        lambda b: jax.lax.dynamic_index_in_dim(K, b, 0, keepdims=False),
        M, l))(jnp.asarray(maxima))
    blk = _assert_top_k_of_shard(run, keys, l)
    selected = np.argsort(-maxima, kind="stable")[:min(l, nb)]
    assert int(ranked) == (maxima[selected] > -np.inf).sum() <= l
    assert set(blk) <= set(selected)
    if case == "select:equal_maxima_selected_and_not":
        assert 1 in selected and 6 not in selected and 1 in blk
    if case == "select:tau_in_an_earlier_unselected_block":
        assert 2 not in selected and {5, 8} <= set(selected)
    if case == "select:selected_block_keeps_nothing":
        assert 3 in selected and (blk == 0).all()
    if case == "select:winners_in_last_block":
        assert (blk == nb - 1).all()
    if case == "select:none_finite":
        assert int(ranked) == 0 and blk.size == 0


@pytest.mark.parametrize("nbl, block_bytes, want", [
    (1526, 512 * 128 * 4, 64),         # kmeans-fit's shard: 24 groups
    (382, 512 * 128 * 4, 64),          # a worker of four: 6
    (23, 8 * 128 * 4, 23), (4, 1 << 30, 1), (1, 4096, 1)])
def test_blocks_per_group_follows_the_shard(nbl, block_bytes, want):
    """The maxima pass keys as many blocks at once as leave four arrays of
    the group's size under 64 MB: computed from the shard's shape."""
    from alink_tpu.operator.common.clustering import kmeans as K

    got = K._blocks_per_group(nbl, block_bytes)
    assert got == want and 1 <= got <= nbl
    assert 4 * got * block_bytes <= K._DRAW_TEMP_BYTES or got == 1


@pytest.mark.parametrize("last", [False, True], ids=["round", "last_round"])
@pytest.mark.parametrize("group", [1, 2, 3, 7])
def test_kmpp_draw_is_the_shard_s_top_k_by_any_grouping(monkeypatch, group,
                                                        last):
    """``_kmpp_draw`` on 7 blocks keyed 1, 2, 3 (the last group overlaps
    the one before) or all 7 at once: the proposals are ``lax.top_k`` over
    the shard's keys, each block keyed by its GLOBAL number; the rows seen
    and, in the last round alone, the candidate weights are the host's
    sums (whole-number weights: exact)."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.operator.common.clustering import kmeans as K

    nbl, S, cap, l, block0 = 7, 8, 9, 5, 21
    monkeypatch.setattr(K, "_DRAW_TEMP_BYTES", 4 * group * S * 128 * 4)
    assert K._blocks_per_group(nbl, S * 128 * 4) == group
    rng = np.random.RandomState(group)
    W = rng.choice([1.0, 2.0], (nbl, S, 128)).astype(np.float32)
    W[-1, S // 2:] = 0                              # the ragged tail
    d2 = (rng.rand(nbl, S, 128) * 3).astype(np.float32) * (W != 0)
    d2[2, 0, :9] = 0                                # rows ON a candidate
    near = rng.randint(0, cap, (nbl, S, 128)).astype(np.int32)
    key = jax.random.fold_in(jax.random.PRNGKey(group), 3)
    # a function of its own a case: traced anew, with this case's grouping
    traced = []

    def draw(*a):
        traced.append(K._blocks_per_group(nbl, S * 128 * 4))
        return K._kmpp_draw.__wrapped__(*a, cap=cap, l=l)
    run, ranked, rows, counts = jax.jit(draw)(W, d2, near, key, block0, last)
    assert traced == [group]

    keys = np.stack([np.asarray(jnp.where(
        d2[i] > 0, jnp.log(jnp.maximum(d2[i], 1e-30)) + jax.random.gumbel(
            jax.random.fold_in(key, block0 + i), d2[i].shape, jnp.float32),
        -jnp.inf)) for i in range(nbl)])
    _assert_top_k_of_shard(run, keys, l)
    assert int(ranked) == l and int(rows) == (W != 0).sum()
    want = [W[near == j].sum() for j in range(cap)] if last else np.zeros(cap)
    assert np.array_equal(np.asarray(counts), np.asarray(want, np.float32))


def _kmpp_table(seed, blocks=5, ragged=700, d=5):
    from alink_tpu.common.columnar import DenseBlockColumn
    rng = np.random.RandomState(100 + seed)
    n = (blocks - 1) * 1024 + ragged
    centers = rng.randn(4, d) * 5
    X = (centers[rng.randint(4, size=n)] + rng.randn(n, d)).astype(np.float32)
    return DenseBlockColumn(DenseBlockColumn.pack(X, 1024, blocks), n)


@pytest.mark.parametrize("nw", [1, 2, 4])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kmeans_parallel_init_bitwise_the_per_block_top_k(seed, nw):
    """The candidates, their weights, the rows counted and the k seeds are
    bit for bit what the parent's hierarchy (``top_k`` of every block, then
    of the blocks' winners) gave: ``tests/fixtures/kmpp_parent_pr27.npz``,
    recorded at PR 27's commit on this mesh (5 blocks of 1,024 rows, the
    last one ragged; on 4 workers the last holds padding alone)."""
    import os
    from alink_tpu.common.mlenv import MLEnvironment
    from alink_tpu.operator.common.clustering.kmeans import (
        kmeans_parallel_init)

    want = np.load(os.path.join(os.path.dirname(__file__), "fixtures",
                                "kmpp_parent_pr27.npz"))
    info = {}
    C = kmeans_parallel_init(_kmpp_table(seed), 4, seed=seed,
                             env=MLEnvironment(parallelism=nw), info=info)
    for name, got in (("cands", info["init_candidates"]),
                      ("weights", info["init_weights"]),
                      ("rows", info["init_rows"]), ("cents", C)):
        ref = want[f"{name}_s{seed}_w{nw}"]
        assert got.dtype == ref.dtype and np.array_equal(got, ref), name


def _host_blocks_ranked(col, cands, seed, l, rounds, nw):
    """Blocks a k-means|| run ranks, counted on the host from the same
    keys: per round and worker, the blocks among the worker's ``l``
    largest block maxima that hold a finite key (a block of padding, or
    one whose every row lies on a candidate, holds none)."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.common.columnar import block_weights
    from alink_tpu.operator.common.clustering import kmeans as K

    blocks = np.asarray(col.blocks)
    w = np.asarray(block_weights(col))
    nbl = -(-blocks.shape[0] // nw)
    out = []
    for r in range(1, rounds + 1):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), r)
        C = jnp.asarray(cands[:1 + (r - 1) * l])
        count = 0
        for task in range(nw):
            maxima = np.full(nbl, -np.inf, np.float32)    # padding blocks
            for b in range(task * nbl, min((task + 1) * nbl, blocks.shape[0])):
                d2 = jnp.where(w[b] != 0, jnp.min(
                    K.block_distances(jnp.asarray(blocks[b]), C), 0), 0)
                g = jax.random.gumbel(jax.random.fold_in(key, b), d2.shape,
                                      d2.dtype)
                maxima[b - task * nbl] = float(jnp.max(jnp.where(
                    d2 > 0, jnp.log(jnp.maximum(d2, 1e-30)) + g, -jnp.inf)))
            selected = np.argsort(-maxima, kind="stable")[:min(l, nbl)]
            count += int((maxima[selected] > -np.inf).sum())
        out.append(count)
    return np.asarray(out)


@pytest.mark.parametrize("nw", [1, 2, 4])
@pytest.mark.parametrize("seed", [0, 5])
def test_init_blocks_ranked_is_the_host_count(seed, nw):
    """``info["init_blocks_ranked"]`` (and the registry counter) is exactly
    the number of blocks the draw keyed a second time and folded: a
    worker's blocks among its ``l`` largest maxima that hold a finite key,
    so at most ``l`` a worker a round whatever the table's order."""
    from alink_tpu.common.metrics import (MetricsRegistry, get_registry,
                                          set_registry)
    from alink_tpu.common.mlenv import MLEnvironment
    from alink_tpu.operator.common.clustering.kmeans import (
        kmeans_parallel_init)

    col = _kmpp_table(seed, blocks=23, ragged=300)
    k, rounds = 2, 5
    l = 2 * k
    info = {}
    prev = set_registry(MetricsRegistry())
    try:
        kmeans_parallel_init(col, k, seed=seed, rounds=rounds,
                             env=MLEnvironment(parallelism=nw), info=info)
        counted = get_registry().value("alink_kmeans_init_blocks_ranked_total")
    finally:
        set_registry(prev)
    ranked = info["init_blocks_ranked"]
    assert ranked.shape == (rounds,) and ranked.dtype == np.int32
    want = _host_blocks_ranked(col, info["init_candidates"], seed, l,
                               rounds, nw)
    assert np.array_equal(ranked, want)
    assert counted == ranked.sum()
    # every worker holds more than l blocks with a row: l a worker a round
    assert (ranked == nw * l).all() and ranked.sum() < rounds * 23


def _ulps(a, b):
    """|a - b| in units of b's last place (float32)."""
    return np.abs(a - b).astype(np.float64) / np.spacing(
        np.maximum(np.abs(b), np.float32(1e-30)))


@pytest.mark.parametrize("S, cut", [(8, False), (512, False), (512, True)],
                         ids=["S8", "S512", "S512_cut"])
@pytest.mark.parametrize("later", [False, True], ids=["first", "later"])
@pytest.mark.parametrize("l", [1, 20])
def test_kmpp_fold_kernel_is_the_xla_fold(monkeypatch, l, later, S, cut):
    """The streamed kernel (interpreted here) against the ``xla`` fold,
    block by block with ``block_distances``: a first round (``d2 =
    +inf``) and a later one, a last block that is part padding, and a
    block ``cut`` over its sublanes as one too wide for VMEM is. ``d2``
    within 2 ulp; ``nearest`` equal wherever the two smallest distances
    differ by more than 2 ulp; padding rows read 0 and, once 0, are
    never ``closer``; equal distances go to the lowest candidate."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.kernels import kmeans as kernel
    from alink_tpu.operator.common.clustering import kmeans as K

    fold_path = kernel.fold_path
    if cut:                      # two table blocks of 64 sublanes fit
        monkeypatch.setattr(kernel, "_TABLE_VMEM", 2 * 3 * 64 * 128 * 4)
        assert kernel._sublanes_per_step(3, S) == 64
    else:
        assert kernel._sublanes_per_step(3, S) == S

    assert fold_path(np.float32, S, l, 3) == "xla"  # the rig as it stands
    monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")
    assert fold_path(np.float32, S, l, 3) == "kernel"
    assert fold_path(np.float64, S, l, 3) == "xla"  # a float64 table,
    assert fold_path(np.float32, 12, l, 3) == "xla"  # ragged register tiles,
    assert fold_path(np.float32, S, 200, 50) == "xla"    # too much to unroll

    rng = np.random.RandomState(l + 2 * later + S)
    nbl, d, off = 2, 3, 1 + 20 * later
    X = rng.randn(nbl, d, S, 128).astype(np.float32)
    W = np.ones((nbl, S, 128), np.float32)
    W[-1, S // 2:] = 0                              # the ragged tail
    pad = W == 0
    new = rng.randn(l, d).astype(np.float32)
    if l > 1:
        new[7] = new[2]                             # equal distances
        X[0, :, 0, :5] = new[2][:, None]            # rows ON a candidate
    if later:
        d2 = (rng.rand(nbl, S, 128) * 3).astype(np.float32) * ~pad
        near = rng.randint(0, off, (nbl, S, 128)).astype(np.int32)
    else:
        d2 = np.full((nbl, S, 128), np.inf, np.float32)
        near = np.zeros((nbl, S, 128), np.int32)

    got, want = (
        [np.asarray(a) for a in jax.jit(
            lambda *a, path=path: K._kmpp_fold(*a, path))(
                X, W, d2, near, new, jnp.int32(off))]
        for path in ("kernel", "xla"))
    assert got[0].dtype == np.float32 and got[1].dtype == np.int32
    assert _ulps(got[0], want[0]).max() <= 2
    D = np.sort(np.stack([np.asarray(K.block_distances(jnp.asarray(xb), new))
                          for xb in X], 1), 0)      # (l, nbl, S, 128)
    clear = np.ones(pad.shape, bool) if l == 1 else \
        (D[1] - D[0]) > 2 * np.spacing(D[1])
    clear &= _ulps(D[0], d2) > 2 if later else True
    assert clear.mean() > 0.9
    assert np.array_equal(got[1][clear], want[1][clear])
    assert (got[0][pad] == 0).all()
    if later:
        assert np.array_equal(got[1][pad], near[pad])   # never closer
    if l > 1:
        assert not (got[1] == off + 7).any() and not (want[1] == off + 7).any()
        assert (got[0][0, 0, :5] == 0).all()
        assert (got[1][0, 0, :5] == off + 2).all()


@pytest.mark.parametrize("nw", [1, 4])
def test_kmeans_parallel_init_same_candidates_by_either_fold(monkeypatch, nw):
    """A whole k-means|| by the ``xla`` fold (the rig's own) and by the
    kernel (interpreted) draws the same candidates; the path is read off
    ``info["init_fold"]`` and the counter of blocks folded."""
    from alink_tpu.common.metrics import (MetricsRegistry, get_registry,
                                          set_registry)
    from alink_tpu.common.mlenv import MLEnvironment
    from alink_tpu.operator.common.clustering.kmeans import (
        kmeans_parallel_init)

    col = _kmpp_table(3, blocks=8)
    out = {}
    prev = set_registry(MetricsRegistry())
    try:
        for path in ("xla", "kernel"):
            if path == "kernel":
                monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")
            out[path] = info = {}
            kmeans_parallel_init(col, 4, seed=3,
                                 env=MLEnvironment(parallelism=nw), info=info)
            assert info["init_fold"] == path
            assert get_registry().value(
                "alink_kmeans_init_fold_blocks_total",
                {"path": path}) == 5 * 8
    finally:
        set_registry(prev)
    for name in ("init_candidates", "init_rows", "init_blocks_ranked"):
        assert np.array_equal(out["kernel"][name], out["xla"][name]), name
    assert np.abs(out["kernel"]["init_weights"]
                  - out["xla"]["init_weights"]).sum() <= 2


# -- Lloyd's superstep as one streamed kernel (PR 32) ---------------------------

@pytest.mark.parametrize("S, cut", [(8, False), (512, False), (512, True)],
                         ids=["S8", "S512", "S512_cut"])
@pytest.mark.parametrize("k", [1, 3, 10])
def test_lloyd_kernel_is_the_xla_pass(monkeypatch, k, S, cut):
    """The streamed kernel (interpreted here) against the ``xla``
    ``_lloyd_pass``: a last block that is part padding (its rows hold
    garbage), weights other than 1, two equal centroids, and a block
    ``cut`` over its sublanes as one too wide for VMEM is. Rows whose two
    smallest distances lie within 2 ulp are left out (weight 0), so
    cluster weights and rows seen are EXACT; the last of two equal
    centroids takes nothing; a cluster's sums agree within 2e-6 of its
    weight times the table's range, the inertia within 1e-6."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.kernels import kmeans as kernel
    from alink_tpu.operator.common.clustering import kmeans as K

    d = 3
    if cut:                      # two table blocks of 64 sublanes fit
        monkeypatch.setattr(kernel, "_TABLE_VMEM", 2 * d * 64 * 128 * 4)
        assert kernel._sublanes_per_step(d, S) == 64
    lloyd_path = kernel.lloyd_path
    assert lloyd_path(np.float32, S, k, d, "EUCLIDEAN") == "xla"    # the rig
    monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")
    assert lloyd_path(np.float32, S, k, d, "EUCLIDEAN") == "kernel"
    assert lloyd_path(np.float64, S, k, d, "EUCLIDEAN") == "xla"
    assert lloyd_path(np.float32, 12, k, d, "EUCLIDEAN") == "xla"
    assert lloyd_path(np.float32, S, k, d, "COSINE") == "xla"
    assert lloyd_path(np.float32, S, 64, 16, "EUCLIDEAN") == "xla"  # too wide

    rng = np.random.RandomState(k + S + cut)
    nbl = 2
    X = (rng.randn(nbl, d, S, 128) * 2 + 1).astype(np.float32)
    W = rng.choice([0.5, 1.0, 2.0], (nbl, S, 128)).astype(np.float32)
    W[-1, S // 2:] = 0                              # the ragged tail,
    X[-1, :, S // 2:] = 1e3                         # whatever it holds
    C = (rng.randn(k, d) * 2 + 1).astype(np.float32)
    if k > 1:
        C[k - 1] = C[0]                             # equal distances
    D = np.sort(np.stack([np.asarray(K.block_distances(jnp.asarray(xb),
                                                       C[:max(k - 1, 1)]))
                          for xb in X], 1), 0)      # (k - 1 or 1, nbl, S, 128)
    if k > 2:
        near = (D[1] - D[0]) <= 2 * np.spacing(D[1])
        assert near.mean() < 0.01
        W[near] = 0
    rows = int((W != 0).sum())

    def run(path):
        monkeypatch.setattr(K, "lloyd_path", lambda *a: path)
        return np.asarray(jax.jit(
            lambda *a: K._lloyd_pass(*a, "EUCLIDEAN"))(X, W, C))
    got, want = run("kernel"), run("xla")
    assert got.dtype == want.dtype == np.float32 and got.shape == (k + 2, d + 1)
    assert int(K._join_count(got[k + 1, 0], got[k + 1, 1])) == rows \
        == int(K._join_count(want[k + 1, 0], want[k + 1, 1]))
    assert np.array_equal(got[:k, d], want[:k, d])  # cluster weights, exact
    assert got[:k, d].sum() == W.sum()
    if k > 1:
        assert got[k - 1, d] == 0 and (got[k - 1] == 0).all()
    wide = np.abs(X[:, :, W[0] != 0]).max() + np.abs(C).max()
    assert (np.abs(got[:k, :d] - want[:k, :d])
            <= 2e-6 * wide * want[:k, d:]).all()
    assert abs(got[k, 0] - want[k, 0]) <= 1e-6 * want[k, 0]
    assert (got[k, 1:] == 0).all() and (got[k + 1, 2:] == 0).all()


@pytest.mark.parametrize("nw", [1, 4])
def test_kmeans_train_same_fit_by_either_lloyd_pass(monkeypatch, nw):
    """A whole ``kmeans_train`` by the ``xla`` pass (the rig's own) and by
    the kernel (interpreted): the same steps, the same centroids and
    cluster weights to float32 round-off; the path is read off
    ``info["lloyd_pass"]`` and the counter of blocks walked."""
    from alink_tpu.common.metrics import (MetricsRegistry, get_registry,
                                          set_registry)
    from alink_tpu.common.mlenv import MLEnvironment
    from alink_tpu.operator.common.clustering.kmeans import kmeans_train

    col = _kmpp_table(5, blocks=8)
    out = {}
    prev = set_registry(MetricsRegistry())
    try:
        for path in ("xla", "kernel"):
            if path == "kernel":
                monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")
            info = {}
            C, w, steps = kmeans_train(col, 4, max_iter=6, tol=1e-6, seed=5,
                                       env=MLEnvironment(parallelism=nw),
                                       info=info)
            out[path] = (np.asarray(C), np.asarray(w), steps, info)
            assert info["lloyd_pass"] == path
            assert get_registry().value(
                "alink_kmeans_lloyd_blocks_total",
                {"path": path}) == steps * 8
    finally:
        set_registry(prev)
    (Ck, wk, sk, ik), (Cx, wx, sx, ix) = out["kernel"], out["xla"]
    assert sk == sx and Ck.dtype == Cx.dtype == np.float32
    assert np.array_equal(ik["rows"], ix["rows"])
    assert np.abs(Ck - Cx).max() <= 1e-5 * np.abs(Cx).max()
    assert np.abs(wk - wx).sum() <= 2
    assert np.allclose(ik["inertia"], ix["inertia"], rtol=1e-5)
