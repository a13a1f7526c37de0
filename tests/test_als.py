"""ALS tests — mirrors the reference ALSExample / MovieLens fixture pattern."""

import json

import numpy as np
import pytest

from alink_tpu.operator.batch.source import MemSourceBatchOp
from alink_tpu.operator.batch.recommendation.als_ops import (
    AlsTrainBatchOp, AlsPredictBatchOp, AlsTopKPredictBatchOp,
    AlsModelDataConverter)


def _ratings(n_users=30, n_items=20, rank=3, seed=0, frac=0.6):
    rng = np.random.RandomState(seed)
    U = rng.rand(n_users, rank)
    V = rng.rand(n_items, rank)
    R = U @ V.T
    rows = []
    for u in range(n_users):
        for i in range(n_items):
            if rng.rand() < frac:
                rows.append((u, i, float(R[u, i])))
    return rows, R


def test_als_reconstruction():
    rows, R = _ratings()
    src = MemSourceBatchOp(rows, "user LONG, item LONG, rating DOUBLE")
    train = AlsTrainBatchOp(user_col="user", item_col="item", rate_col="rating",
                            rank=6, num_iter=15, lambda_=0.01).link_from(src)
    curve = np.asarray(train.get_side_output(0).get_output_table().col("train_rmse"))
    assert curve[-1] < 0.05
    assert curve[-1] <= curve[0]
    # predict observed pairs
    pred = (AlsPredictBatchOp(user_col="user", item_col="item",
                              prediction_col="pred").link_from(train, src))
    out = pred.collect_mtable()
    err = np.abs(np.asarray(out.col("pred")) -
                 np.asarray(out.col("rating")))
    assert err.mean() < 0.05


def test_als_topk_and_cold_user():
    rows, R = _ratings()
    src = MemSourceBatchOp(rows, "user LONG, item LONG, rating DOUBLE")
    train = AlsTrainBatchOp(user_col="user", item_col="item", rate_col="rating",
                            rank=6, num_iter=10, lambda_=0.01).link_from(src)
    users = MemSourceBatchOp([(0,), (5,), (9999,)], "user LONG")
    topk = (AlsTopKPredictBatchOp(user_col="user", prediction_col="recs",
                                  top_k=5).link_from(train, users))
    out = topk.collect_mtable()
    rec0 = json.loads(out.col("recs")[0])
    assert len(rec0["object"]) == 5
    # recommended order matches true preference order direction
    best = int(rec0["object"][0])
    assert R[0, best] >= np.median(R[0])
    assert out.col("recs")[2] is None  # unseen user


def test_als_predict_vectorized_matches_loop():
    """The gather+einsum predict path must be output-identical to a naive
    per-row loop over the factor dicts, including NaN for unknown ids."""
    from alink_tpu.operator.batch.recommendation.als_ops import AlsRater
    rows, _ = _ratings()
    src = MemSourceBatchOp(rows, "user LONG, item LONG, rating DOUBLE")
    train = AlsTrainBatchOp(user_col="user", item_col="item", rate_col="rating",
                            rank=4, num_iter=5).link_from(src)
    rng = np.random.RandomState(7)
    req = [(int(rng.randint(0, 35)), int(rng.randint(0, 24)))  # some unknown
           for _ in range(5000)]
    data = MemSourceBatchOp(req, "user LONG, item LONG")
    rater = AlsRater(train.get_output_table())
    out = rater.rate_table(data.get_output_table(), "user", "item", "pred")
    got = np.asarray(out.col("pred"), np.float64)
    m = rater.m
    uD = {int(u): f for u, f in zip(m.user_ids, m.user_factors)}
    iD = {int(i): f for i, f in zip(m.item_ids, m.item_factors)}
    want = np.asarray([float(uD[u] @ iD[i]) if u in uD and i in iD else np.nan
                       for u, i in req])
    assert np.isnan(want).any() and not np.isnan(want).all()
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
    np.testing.assert_allclose(got[~np.isnan(want)], want[~np.isnan(want)],
                               rtol=1e-12)


def test_als_predict_scales():
    """1M-row predict should take seconds, not minutes (VERDICT weak #3)."""
    import time
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch.recommendation.als_ops import AlsRater
    rows, _ = _ratings()
    src = MemSourceBatchOp(rows, "user LONG, item LONG, rating DOUBLE")
    train = AlsTrainBatchOp(user_col="user", item_col="item", rate_col="rating",
                            rank=4, num_iter=2).link_from(src)
    rater = AlsRater(train.get_output_table())
    n = 1_000_000
    rng = np.random.RandomState(1)
    t = MTable({"user": rng.randint(0, 30, n), "item": rng.randint(0, 20, n)})
    t0 = time.perf_counter()
    out = rater.rate_table(t, "user", "item", "pred")
    dt = time.perf_counter() - t0
    assert out.num_rows == n
    assert not np.isnan(np.asarray(out.col("pred"), np.float64)).any()
    assert dt < 10.0, f"1M-row predict took {dt:.1f}s"


def test_als_implicit():
    rows, R = _ratings(frac=0.5)
    # binarize to implicit clicks
    rows = [(u, i, 1.0 if r > np.median(R) else 0.0) for u, i, r in rows]
    src = MemSourceBatchOp(rows, "user LONG, item LONG, rating DOUBLE")
    train = AlsTrainBatchOp(user_col="user", item_col="item", rate_col="rating",
                            rank=5, num_iter=10, implicit_prefs=True,
                            alpha=10.0).link_from(src)
    m = AlsModelDataConverter().load_model(train.get_output_table())
    assert m.user_factors.shape == (30, 5)
    # clicked items should outscore unclicked on average
    clicked, unclicked = [], []
    lookup = {(u, i): r for u, i, r in rows}
    S = m.user_factors @ m.item_factors.T
    for (u, i), r in lookup.items():
        (clicked if r > 0 else unclicked).append(S[u, i])
    assert np.mean(clicked) > np.mean(unclicked)


def test_batched_nnls_kkt_and_scipy_parity():
    """batched_nnls must satisfy the NNLS KKT conditions and agree with
    scipy.optimize.nnls on pure least-squares instances."""
    import jax.numpy as jnp
    from scipy.optimize import nnls as scipy_nnls

    from alink_tpu.operator.common.recommendation.als import batched_nnls
    rng = np.random.RandomState(0)
    r = 6
    Ms = [rng.randn(20, r) for _ in range(20)]
    ys = [rng.randn(20) for _ in range(20)]
    A = np.stack([M.T @ M for M in Ms])
    b = np.stack([M.T @ y for M, y in zip(Ms, ys)])
    x = np.asarray(batched_nnls(jnp.asarray(A), jnp.asarray(b), num_iter=500))
    assert (x >= 0).all()
    # KKT: stationarity on the free set, nonnegative gradient on the active
    # set, complementary slackness
    g = np.einsum("nij,nj->ni", A, x) - b
    active = x <= 1e-6
    assert np.abs(g[~active]).max() < 1e-3
    assert g[active].min() > -1e-3
    assert np.abs(g * x).max() < 1e-3
    for i in range(20):
        gold, _ = scipy_nnls(Ms[i], ys[i])
        np.testing.assert_allclose(x[i], gold, atol=5e-4)


def test_als_nonnegative():
    rows, R = _ratings(frac=0.6)
    src = MemSourceBatchOp(rows, "user LONG, item LONG, rating DOUBLE")
    train = AlsTrainBatchOp(user_col="user", item_col="item",
                            rate_col="rating", rank=5, num_iter=10,
                            nonnegative=True).link_from(src)
    m = AlsModelDataConverter().load_model(train.get_output_table())
    assert (m.user_factors >= 0).all() and (m.item_factors >= 0).all()
    # reconstruction still works under the constraint (ratings positive)
    S = m.user_factors @ m.item_factors.T
    errs = [abs(S[u, i] - r) for u, i, r in rows]
    assert np.mean(errs) < 0.8, np.mean(errs)


def test_als_tol_early_stop():
    """tol > 0 stops the superstep loop when the train-RMSE delta falls
    under it (KMeansIterTermination analogue), and the returned curve
    length is the MEASURED iteration count — VERDICT r2 #5."""
    from alink_tpu.operator.common.recommendation.als import (AlsTrainParams,
                                                              als_train)
    rng = np.random.RandomState(0)
    U, I, r = 40, 30, 3
    uf = rng.rand(U, r).astype(np.float32)
    if_ = rng.rand(I, r).astype(np.float32)
    users, items = np.meshgrid(np.arange(U), np.arange(I), indexing="ij")
    users, items = users.ravel(), items.ravel()
    ratings = (uf[users] * if_[items]).sum(1)      # exact low rank, no noise
    p = AlsTrainParams(rank=r, num_iter=50, lambda_reg=1e-3, tol=1e-4)
    uf_hat, if_hat, curve = als_train(users, items, ratings, p)
    assert 1 < len(curve) < 50, len(curve)         # stopped early, measured
    assert curve[-1] < 0.1                          # and actually converged
    p0 = AlsTrainParams(rank=r, num_iter=7, lambda_reg=1e-3, tol=0.0)
    _, _, curve0 = als_train(users, items, ratings, p0)
    assert len(curve0) == 7                         # tol=0 runs the budget


def test_als_one_sweep_matches_numpy_normal_equations():
    """One ALS sweep must match a numpy reference computing the same
    normal equations densely — pins the grouping, the chunked Gram sums
    and the batched solve EXACTLY (not just reconstruction quality)."""
    from alink_tpu.operator.common.recommendation.als import (AlsTrainParams,
                                                              als_train)
    rng = np.random.RandomState(5)
    U, I, r, nnz = 17, 13, 4, 150
    users = rng.randint(0, U, nnz).astype(np.int32)
    items = rng.randint(0, I, nnz).astype(np.int32)
    ratings = rng.rand(nnz).astype(np.float32) * 4 + 1
    lam = 0.2
    p = AlsTrainParams(rank=r, num_iter=1, lambda_reg=lam, seed=3)
    info = {}
    uf, if_, _ = als_train(users, items, ratings, p,
                           num_users=U, num_items=I, info=info)

    # numpy reference from the same init: the item factors the one user
    # half-sweep read, drawn on the device from the seed (uniform over
    # [0, 1/sqrt(rank)), the law it always was; the user init is never read)
    if0 = np.asarray(info["items_read"], np.float64)[:, :r]
    assert 0 <= if0.min() and if0.max() < 1 / np.sqrt(r) and if0.std() > 0

    def solve_ref(ids, oids, n_rows, ofac):
        out = np.zeros((n_rows, r))
        for row in range(n_rows):
            m = ids == row
            X = ofac[oids[m]]
            cnt = m.sum()
            A = X.T @ X + lam * max(cnt, 1) * np.eye(r)
            b = X.T @ ratings[m].astype(np.float64)
            out[row] = np.linalg.solve(A, b) if cnt else 0.0
        return out

    uf_ref = solve_ref(users, items, U, if0)
    if_ref = solve_ref(items, users, I, uf_ref)
    np.testing.assert_allclose(uf, uf_ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(if_, if_ref, rtol=2e-4, atol=2e-5)


def _coo(seed=5, n=3000, U=100, I=60):
    rng = np.random.RandomState(seed)
    users = rng.randint(0, U, n).astype(np.int32)
    items = rng.randint(0, I, n).astype(np.int32)
    ratings = (rng.rand(n) * 5).astype(np.float32)
    return users, items, ratings, U, I


class TestAlsShardSolve:
    """shard_solve=True: reduce_scatter the normal equations by id range,
    solve locally, all_gather the solved factors (the escape hatch for
    the replicated-buffer HBM cap, docs/parallelism.md)."""

    def test_parity_8dev(self):
        from dataclasses import replace
        from alink_tpu.operator.common.recommendation.als import (
            AlsTrainParams, als_train)
        users, items, ratings, U, I = _coo()
        p = AlsTrainParams(rank=4, num_iter=6, lambda_reg=0.1, seed=2)
        uf0, if0, c0 = als_train(users, items, ratings, p,
                                 num_users=U, num_items=I)
        uf1, if1, c1 = als_train(users, items, ratings,
                                 replace(p, shard_solve=True),
                                 num_users=U, num_items=I)
        # same math, different reduction order (reduce_scatter vs psum)
        np.testing.assert_allclose(uf1, uf0, rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(if1, if0, rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(c1, c0, rtol=1e-3, atol=1e-4)

    def test_parity_nonnegative(self):
        from dataclasses import replace
        from alink_tpu.operator.common.recommendation.als import (
            AlsTrainParams, als_train)
        users, items, ratings, U, I = _coo(seed=9, n=1500, U=40, I=30)
        p = AlsTrainParams(rank=3, num_iter=4, nonnegative=True, seed=1)
        uf0, _, _ = als_train(users, items, ratings, p,
                              num_users=U, num_items=I)
        uf1, _, _ = als_train(users, items, ratings,
                              replace(p, shard_solve=True),
                              num_users=U, num_items=I)
        assert (np.asarray(uf1) >= -1e-6).all()
        np.testing.assert_allclose(uf1, uf0, rtol=5e-3, atol=5e-4)

    def test_hlo_shows_reduce_scatter_and_all_gather(self):
        """The compiled module must contain the reduce-scatter of the
        packed equations and the factor all-gather with the expected
        payload shapes (the HLO-audit obligation from VERDICT r4 #7)."""
        import re
        import sys
        sys.path.insert(0, "tools")
        from scaling_evidence import capture_lowered
        from alink_tpu.operator.common.recommendation.als import (
            AlsTrainParams, als_train)
        users, items, ratings, U, I = _coo(n=1000, U=64, I=48)
        p = AlsTrainParams(rank=4, num_iter=3, shard_solve=True)
        lowered = capture_lowered(
            lambda: als_train(users, items, ratings, p,
                              num_users=U, num_items=I),
            program="als_sweep")       # the grouping program runs first
        hlo = lowered.compile().as_text()
        assert re.search(r"reduce-scatter(?:-start)?\(", hlo), \
            "no reduce-scatter in compiled ALS shard_solve module"
        assert re.search(r"all-gather(?:-start)?\(", hlo), \
            "no all-gather in compiled ALS shard_solve module"
        # factor all-gather payload: (U_pad, rank) per side appears as an
        # all-gather result with last dim == rank (f64 under the test
        # mesh's x64 flag, f32 on hardware)
        ags = re.findall(r"f(?:32|64)\[(\d+),(\d+)\][^\n]*all-gather", hlo)
        assert any(int(r) == p.rank for _, r in ags), ags

    def test_parity_32dev_subprocess(self):
        import os
        import subprocess
        import sys
        from bootenv import cpu_mesh_env
        code = """
import numpy as np
from dataclasses import replace
import jax
from alink_tpu.common.mlenv import MLEnvironment, MLEnvironmentFactory
from alink_tpu.operator.common.recommendation.als import AlsTrainParams, als_train

n = len(jax.devices())
assert n == 32, n
env = MLEnvironment(parallelism=n)
MLEnvironmentFactory.set_default(env)
rng = np.random.RandomState(5)
users = rng.randint(0, 100, 3000).astype(np.int32)
items = rng.randint(0, 60, 3000).astype(np.int32)
ratings = (rng.rand(3000) * 5).astype(np.float32)
p = AlsTrainParams(rank=4, num_iter=5, seed=2)
uf0, if0, _ = als_train(users, items, ratings, p, num_users=100, num_items=60)
uf1, if1, _ = als_train(users, items, ratings, replace(p, shard_solve=True),
                        num_users=100, num_items=60)
np.testing.assert_allclose(uf1, uf0, rtol=2e-3, atol=2e-4)
np.testing.assert_allclose(if1, if0, rtol=2e-3, atol=2e-4)
print("shard_solve 32dev ok")
"""
        env = cpu_mesh_env(32)
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))),
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
        assert "shard_solve 32dev ok" in r.stdout


# -- the program against the benchmark's plain reference ----------------------

def _blocked_table(U, I, n, seed, block_rows=1024):
    """Seeded triples in the blocked layout: user 0 holds a quarter of the
    ratings (they span every block and many chunks), user 1 exactly one,
    the last ten users and five items none; pairs repeat; the last block
    is ragged."""
    from alink_tpu.common.columnar import DenseBlockColumn
    rng = np.random.RandomState(seed)
    users = rng.randint(2, U - 10, n)
    users[rng.rand(n) < 0.25] = 0
    users[users == 1] = 2
    users[n // 2] = 1
    items = rng.randint(0, I - 5, n)
    ratings = np.round(rng.rand(n) * 100)
    assert n % block_rows and (users == 1).sum() == 1
    assert len(set(zip(users, items))) < n          # repeated pairs
    return tuple(DenseBlockColumn.pack(v.astype(dt), block_rows) for v, dt in
                 ((users, np.int32), (items, np.int32),
                  (ratings, np.float32)))


@pytest.mark.parametrize("case,rank,mode,solve_limit", [
    ("explicit", 8, {}, 2e-5),
    # confidence weights up to 51 on a ridge of 0.1 n: a condition number
    # ~100 times the explicit case's
    ("implicit", 8, {"implicit_prefs": True, "alpha": 0.5}, 1e-3),
    # the program's projected gradient stops after 80 steps; the
    # reference's active-set NNLS is exact
    ("nonnegative", 8, {"nonnegative": True}, 5e-2),
    # one small case at the cell's rank, so that the solve at n = 100 and
    # the 128-lane rows with the rating's lane at 100 are guarded
    ("rank_100", 100, {}, 2e-4)])
def test_fit_against_the_plain_reference_teacher_forced(case, rank, mode,
                                                        solve_limit):
    """One path for every table: a host table's fit read by
    ``benchmark/reference/als.py`` exactly as the cell's fit is, a
    half-sweep at a time from the factors the program read."""
    from alink_tpu.common.columnar import RowBlockColumn
    from alink_tpu.operator.common.recommendation.als import (AlsTrainParams,
                                                              als_train)
    from benchmark.reference import als as ref
    U, I, n = (60, 40, 3000) if rank == 8 else (40, 150, 5000)
    table = _blocked_table(U, I, n, seed=len(case))
    lam = 1.4 if not mode.get("implicit_prefs") else 0.1
    p = AlsTrainParams(rank=rank, num_iter=2, lambda_reg=lam, seed=7, **mode)
    info = {}
    uf, if_, curve = als_train(*(RowBlockColumn(b, n) for b in table), p,
                               num_users=U, num_items=I, info=info)
    assert uf.shape == (U, rank) and if_.shape == (I, rank)
    assert info["ratings"] == 4 * n and info["half_sweeps"] == 4
    assert not np.asarray(uf)[-10:].any() and not np.asarray(if_)[-5:].any()
    got = ref.gaps(info, table, n, {
        "rank": rank, "lambda": lam, "sample_rows": 1000,
        "implicit": bool(mode.get("implicit_prefs")),
        "alpha": mode.get("alpha", 40.0),
        "nonnegative": bool(mode.get("nonnegative"))}, seed=1)
    assert got["count_gap"] == 0
    assert got["user_solve_gap"] < solve_limit, got
    assert got["item_solve_gap"] < solve_limit, got
    assert got["rmse_gap"] < 1e-5, got
    assert len(curve) == 2 and curve[1] < curve[0]


def test_the_model_payload_is_an_array_and_an_old_text_payload_still_loads():
    """ROADMAP M1's first half: the ALS model table carries its factors
    and whole-number ids as arrays (no JSON text, nothing a row in Python);
    its cells still turn into the text the old loader read; a table
    written before that (ids in the meta row, factors as JSON) loads."""
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.params import Params
    from alink_tpu.model.converters import (ArrayPayload, decode_array,
                                            encode_array)
    rows, _ = _ratings()
    src = MemSourceBatchOp(rows, "user LONG, item LONG, rating DOUBLE")
    train = AlsTrainBatchOp(user_col="user", item_col="item",
                            rate_col="rating", rank=4,
                            num_iter=2).link_from(src)
    table = train.get_output_table()
    cells = list(table.col("model_info"))
    assert isinstance(cells[0], str)
    assert all(isinstance(c, ArrayPayload) for c in cells[1:5])
    m = AlsModelDataConverter().load_model(table)
    assert m.user_factors is cells[1].array            # no copy, no text
    assert m.user_ids.dtype == np.int64 and list(m.user_ids) == list(range(30))
    # a sink that writes text writes what the loader reads back
    as_text = MTable({"model_id": table.col("model_id"),
                      "model_info": np.asarray([str(c) for c in cells],
                                               object)}, table.schema)
    t = AlsModelDataConverter().load_model(as_text)
    np.testing.assert_array_equal(t.user_factors, m.user_factors)
    np.testing.assert_array_equal(t.item_ids, m.item_ids)
    # the layout before the array payload
    meta = Params({"user_col": "user", "item_col": "item",
                   "rate_col": "rating",
                   "user_ids": [str(u) for u in m.user_ids],
                   "item_ids": [str(i) for i in m.item_ids]})
    old = MTable([(0, meta.to_json()), (1, encode_array(m.user_factors)),
                  (2, encode_array(m.item_factors))],
                 AlsModelDataConverter.SCHEMA)
    o = AlsModelDataConverter().load_model(old)
    np.testing.assert_array_equal(o.item_factors, m.item_factors)
    np.testing.assert_array_equal(decode_array(cells[2]), m.item_factors)
    data = MemSourceBatchOp([(3, 4), (0, 19), (777, 1)], "user LONG, item LONG")
    want = (AlsPredictBatchOp(user_col="user", item_col="item",
                              prediction_col="pred")
            .link_from(train, data).collect_mtable().col("pred"))
    got = (AlsPredictBatchOp(user_col="user", item_col="item",
                             prediction_col="pred")
           .link_from(MemSourceBatchOp(old), data).collect_mtable()
           .col("pred"))
    np.testing.assert_array_equal(np.asarray(want, float),
                                  np.asarray(got, float))
    assert np.isnan(want[2]) and not np.isnan(want[0])


def test_a_blocked_id_column_is_its_own_index():
    """Ids that are whole numbers in a blocked column are not walked in
    Python: the column is the index, ids with no rating get rows of zeros,
    and the predictors read the model."""
    from alink_tpu.common.columnar import RowBlockColumn
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.types import TableSchema
    rows, _ = _ratings()
    u, i, r = (np.asarray(c) for c in zip(*rows))
    host = MemSourceBatchOp(rows, "user LONG, item LONG, rating DOUBLE")
    blocked = MemSourceBatchOp(MTable(
        {"user": RowBlockColumn.from_values((u + 2).astype(np.int32)),
         "item": RowBlockColumn.from_values(i.astype(np.int32)),
         "rating": RowBlockColumn.from_values(r.astype(np.float32))},
        TableSchema.parse("user INT, item INT, rating FLOAT")))
    kw = dict(user_col="user", item_col="item", rate_col="rating", rank=4,
              num_iter=3, seed=5)
    a = AlsModelDataConverter().load_model(
        AlsTrainBatchOp(**kw).link_from(host).get_output_table())
    op = AlsTrainBatchOp(**kw).link_from(blocked)
    b = AlsModelDataConverter().load_model(op.get_output_table())
    assert list(b.user_ids) == list(range(32))         # 0 and 1: no rating
    assert not np.asarray(b.user_factors)[:2].any()
    np.testing.assert_allclose(np.asarray(b.user_factors)[2:],
                               a.user_factors, rtol=1e-5, atol=1e-6)
    info = op.get_train_info()
    assert info["ratings"] == 6 * len(rows) and info["paths"]["group"] == "sort"
    assert np.asarray(info["user_counts"])[:2].tolist() == [0, 0]
