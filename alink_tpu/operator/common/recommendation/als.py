"""ALS matrix factorization — TPU-native.

Re-design of common/recommendation/AlsTrain.java (587 LoC; SURVEY §2.3
"block/graph parallelism"): the reference groups ratings into user/item
blocks, exchanges factor request/response messages over Flink coGroups
(AlsTrain.java:266-335), and solves per-block normal equations with a
Cholesky (NormalEquation, :493) inside a Flink loop of
numIters*numMiniBatches*2 supersteps.

TPU-first shape: each worker holds its rating shard device-resident; the
per-row normal-equation sums are ``lax.psum``'d across the mesh, which
leaves every worker holding the COMPLETE updated factor matrix — so the
reference's request/response gather ("factor all-gather") costs nothing
extra here: the psum of the (A, b) systems is itself the all-gather, and
the factors ride the carry fully replicated. All per-row normal equations
are solved with a batched dense solve — MXU-batched instead of per-block
Java loops.

Accumulating the per-row (A, b) sums is the hot spot: a scatter-add of
nnz x rank^2 outer products serializes on TPU (~120 ms per side at
MovieLens-1M scale). Instead each worker's rating rows are pre-sorted by
the side's id (host-side, once — the ids never change), so every id owns a
CONTIGUOUS run and its sum is a difference of two prefix sums. The prefix
is two-level (f32 cumsums WITHIN 512-row blocks + a cumsum over only the
~nnz/512 block sums) and MEAN-CENTERED: subtracting the per-column mean
before the scan turns the prefix from a linearly-growing sum (whose f32
differencing loses ~nnz*eps of every short run — round 2 paid an
emulated-f64 inter level for this, 33 ms/side) into a zero-drift random
walk of magnitude ~sqrt(nnz), so all-f32 keeps ~1e-6 relative accuracy
(tools/profile_als3.py) and the exact ``mean * run_length`` is added
back per run. Two tiny per-id gathers then replace the million-row
scatter.

Ids ride in their own int32 columns (never cast through the float32
rating block — f32 is exact only to 2^24, so large ids would silently
collide; ADVICE r2). Ratings rows carry weight-0 padding. Implicit
feedback (implicitprefs) follows the reference's confidence weighting
c = 1 + alpha*|r|.

Convergence mirrors KMeansIterTermination (KMeansTrainBatchOp.java:72-83):
``tol`` > 0 stops the superstep loop when the train-RMSE delta falls
below it, and the returned curve length is the MEASURED iteration count.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ....common.mlenv import MLEnvironment, MLEnvironmentFactory
from ....engine import IterativeComQueue
from ....engine.communication import (manifest_all_gather, manifest_psum,
                                      manifest_psum_scatter)
from ....ops.smallsolve import batched_spd_solve


def batched_nnls(A, b, x0=None, num_iter: int = 80):
    """Batched nonnegative least squares: min_x>=0  1/2 x^T A x - b^T x.

    The reference's NNLSSolver (Scala, projected-gradient NNLS used by ALS
    nonnegative mode) becomes accelerated projected gradient (FISTA) with a
    per-row Lipschitz bound L = trace(A) (valid since A is PSD), batched
    over the leading axis and fully traceable — a fixed-trip-count
    ``lax.fori_loop`` instead of the reference's per-block CPU iterations.

    ``A``: (n, r, r) PSD normal matrices, ``b``: (n, r). ``x0`` optional
    warm start (defaults to the clipped unconstrained solution's role —
    zeros if omitted).
    """
    L = jnp.maximum(jnp.trace(A, axis1=-2, axis2=-1), 1e-12)[:, None]
    x = jnp.zeros_like(b) if x0 is None else x0
    state = (x, x, jnp.asarray(1.0, b.dtype))

    def body(_, st):
        x, yv, t = st
        grad = jnp.einsum("nij,nj->ni", A, yv) - b
        x_new = jnp.maximum(yv - grad / L, 0.0)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        y_new = x_new + ((t - 1.0) / t_new) * (x_new - x)
        return (x_new, y_new, t_new)

    x, _, _ = jax.lax.fori_loop(0, num_iter, body, state)
    return x


@dataclass
class AlsTrainParams:
    rank: int = 10
    num_iter: int = 10
    lambda_reg: float = 0.1
    implicit_prefs: bool = False
    alpha: float = 40.0
    nonnegative: bool = False
    seed: int = 0
    tol: float = 0.0          # train-RMSE delta early stop; 0 = run num_iter
    # Shard the post-reduction normal equations + solve by id range
    # (reduce_scatter instead of psum), then all_gather only the solved
    # factors. The (U, tri+rank+1) normal-equation buffers — ~6.6x the
    # factor bytes at rank 10 — stop being replicated per chip, lifting
    # the docs/parallelism.md HBM cap; the factors themselves remain
    # replicated (the next half-sweep gathers arbitrary rows of them).
    shard_solve: bool = False


def _sorted_side(ids: np.ndarray, rw: np.ndarray, col: int):
    """Sort one worker's rating rows by the side's id column and emit the
    per-id run boundaries. ``ids`` (L, 2) int32, ``rw`` (L, 2) float32
    [rating, weight]. Returns (sorted_ids, sorted_rw, (id, start, end))."""
    order = np.argsort(ids[:, col], kind="stable")
    si, sr = ids[order], rw[order]
    uniq, starts, counts = np.unique(si[:, col], return_index=True,
                                     return_counts=True)
    plan = np.stack([uniq, starts, starts + counts], 1).astype(np.int32)
    return si, sr, plan


def als_train(users: np.ndarray, items: np.ndarray, ratings: np.ndarray,
              p: AlsTrainParams, env: Optional[MLEnvironment] = None,
              num_users: Optional[int] = None, num_items: Optional[int] = None
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Returns (user_factors (U, rank), item_factors (I, rank), rmse_curve);
    ``len(rmse_curve)`` is the measured number of iterations run."""
    env = env or MLEnvironmentFactory.get_default()
    users = np.asarray(users, np.int32)
    items = np.asarray(items, np.int32)
    ratings = np.asarray(ratings, np.float32)
    U = int(num_users if num_users is not None else users.max() + 1)
    I = int(num_items if num_items is not None else items.max() + 1)
    rank = p.rank
    rng = np.random.RandomState(p.seed)
    uf0 = (rng.rand(U, rank).astype(np.float32) / np.sqrt(rank))
    if0 = (rng.rand(I, rank).astype(np.float32) / np.sqrt(rank))
    nw = env.num_workers
    nnz = len(ratings)
    L = -(-max(nnz, 1) // nw)
    ids = np.zeros((nw * L, 2), np.int32)          # id-0 padding rows
    rw = np.zeros((nw * L, 2), np.float32)         # weight-0 padding rows
    ids[:nnz, 0] = users
    ids[:nnz, 1] = items
    rw[:nnz, 0] = ratings
    rw[:nnz, 1] = 1.0
    # per-worker side-sorted copies + run boundaries (the ids are static,
    # so this host pass happens once per training, not per iteration)
    idsU, rwU, idsI, rwI, planU, planI = [], [], [], [], [], []
    for wkr in range(nw):
        ci, cr = ids[wkr * L:(wkr + 1) * L], rw[wkr * L:(wkr + 1) * L]
        si, sr, pl = _sorted_side(ci, cr, 0)
        idsU.append(si)
        rwU.append(sr)
        planU.append(pl)
        si, sr, pl = _sorted_side(ci, cr, 1)
        idsI.append(si)
        rwI.append(sr)
        planI.append(pl)
    Nu = max(pl.shape[0] for pl in planU)
    Ni = max(pl.shape[0] for pl in planI)
    # zero-length (id=0, start=end=0) slots pad to a uniform worker shape
    planU = np.stack([np.concatenate(
        [pl, np.zeros((Nu - pl.shape[0], 3), np.int32)]) for pl in planU])
    planI = np.stack([np.concatenate(
        [pl, np.zeros((Ni - pl.shape[0], 3), np.int32)]) for pl in planI])
    lam = p.lambda_reg
    eye = np.eye(rank, dtype=np.float32)
    # A is symmetric: only the lower triangle's r(r+1)/2 products ride the
    # prefix pipeline (rank 10: 55 instead of 100 columns -> ~40% less HBM
    # traffic through the build/cumsum/gather chain, the measured hot
    # spot); the full matrix is rebuilt by a static unpack gather after
    # the psum.
    il, jl = np.tril_indices(rank)
    unpack = np.zeros((rank, rank), np.int32)
    unpack[il, jl] = np.arange(len(il))
    unpack[jl, il] = np.arange(len(il))
    unpack = unpack.reshape(-1)
    n_tri = len(il)

    def solve_side(bids, brw, plan, other_col, other_factors, n_rows):
        """Per-id normal equations from this worker's rows, which are
        pre-sorted by the side's id: contribution sums are prefix-sum
        differences over the contiguous runs (see module docstring), then
        psum across workers (the reference's request/response
        accumulation) and one batched solve. The psum replicates the
        result, so the return value is the FULL factor matrix."""
        ids_ = plan[:, 0]
        starts = plan[:, 1]
        ends = plan[:, 2]
        r = brw[:, 0]
        w = brw[:, 1]
        x = other_factors[bids[:, other_col]]                 # (L, rank)
        if p.implicit_prefs:
            c = 1.0 + p.alpha * jnp.abs(r)
            pref = (r > 0).astype(x.dtype)
            ww = c * w
            bval = c * pref * w
        else:
            ww = w
            bval = r * w
        contrib = jnp.concatenate(
            [ww[:, None] * (x[:, il] * x[:, jl]),             # packed tril
             bval[:, None] * x, w[:, None]], axis=1)          # (L, tri+r+1)
        # Mean-centered two-level all-f32 prefix (see module docstring):
        # in-block f32 cumsums + an f32 cumsum over block sums, both over
        # CENTERED values so the prefix is a zero-drift random walk; the
        # removed mean re-enters exactly as mean * run_length.
        K = contrib.shape[1]
        Lr = contrib.shape[0]
        C = 512
        Lb = -(-Lr // C)
        pad = Lb * C - Lr
        cpad = jnp.concatenate(
            [contrib, jnp.zeros((pad, K), contrib.dtype)], axis=0)
        blk = cpad.reshape(Lb, C, K)
        mean = blk.sum(axis=1).sum(axis=0) / (Lb * C)         # per-column
        intra = jnp.cumsum(blk - mean, axis=1)                # f32, in-block
        inter = jnp.concatenate(
            [jnp.zeros((1, K), contrib.dtype),
             jnp.cumsum(intra[:, -1, :], axis=0)], axis=0)    # exclusive

        def prefix(t):                                        # t: (N,) positions
            bi = t // C
            ri = t % C
            part = jnp.where((ri > 0)[:, None], intra[bi, ri - 1], 0.0)
            return inter[bi] + part

        span = (ends - starts).astype(contrib.dtype)[:, None]
        slot = (prefix(ends) - prefix(starts)) + mean * span
        n_pad = -(-n_rows // nw) * nw if p.shard_solve else n_rows
        A = jnp.zeros((n_pad, n_tri), x.dtype).at[ids_].add(
            slot[:, :n_tri])
        b = jnp.zeros((n_pad, rank), x.dtype).at[ids_].add(
            slot[:, n_tri:n_tri + rank])
        cnt = jnp.zeros((n_pad,), x.dtype).at[ids_].add(slot[:, -1])
        if p.shard_solve:
            # reduce_scatter: worker d receives only its id-range slice of
            # the summed equations (the replicated-buffer escape hatch,
            # docs/parallelism.md); the solve below then runs on U/nw ids
            # per chip and only the solved factors are re-replicated.
            A = manifest_psum_scatter(A, "d", scatter_dimension=0, tiled=True,
                                      name="als_eq_A", num_workers=nw)
            b = manifest_psum_scatter(b, "d", scatter_dimension=0, tiled=True,
                                      name="als_eq_b", num_workers=nw)
            cnt = manifest_psum_scatter(cnt, "d", scatter_dimension=0,
                                        tiled=True, name="als_eq_cnt",
                                        num_workers=nw)
        else:
            A = manifest_psum(A, "d", name="als_eq_A", num_workers=nw)
            b = manifest_psum(b, "d", name="als_eq_b", num_workers=nw)
            cnt = manifest_psum(cnt, "d", name="als_eq_cnt", num_workers=nw)
        A = A[:, unpack].reshape(A.shape[0], rank, rank)      # symmetrize
        A = A + lam * jnp.maximum(cnt, 1.0)[:, None, None] * eye
        # batched unrolled Gauss-Jordan: jnp.linalg.solve's batched LU
        # leaves the MXU idle (21 ms vs ~0 ms here, tools/profile_als3.py)
        sol = batched_spd_solve(A, b)
        if p.nonnegative:
            sol = batched_nnls(A, b, x0=jnp.maximum(sol, 0.0))
        sol = jnp.where(cnt[:, None] > 0, sol, 0.0)
        if p.shard_solve:
            # factor all-gather (the north-star collective): every worker
            # needs the full matrix for the next half-sweep's gathers
            sol = manifest_all_gather(sol, "d", axis=0, tiled=True,
                                      name="als_factors",
                                      num_workers=nw)[:n_rows]
        return sol

    def step(ctx):
        if ctx.is_init_step:
            # factors ride the carry FULLY REPLICATED: solve_side's psum
            # already leaves every worker with the complete matrix, so the
            # reference's per-half-step factor exchange needs no collective
            # at all here (round 2 spent 3 all_gathers per superstep on it)
            ctx.put_obj("uf", ctx.get_obj("uf0"))
            ctx.put_obj("if_", ctx.get_obj("if0"))
            ctx.put_obj("rmse_curve", jnp.zeros((p.num_iter,), jnp.float32))
            ctx.put_obj("prev_rmse", jnp.asarray(jnp.inf, jnp.float32))
            ctx.put_obj("rmse_delta", jnp.asarray(jnp.inf, jnp.float32))
        bidsU = ctx.get_obj("idsU")
        brwU = ctx.get_obj("rwU")
        bidsI = ctx.get_obj("idsI")
        brwI = ctx.get_obj("rwI")
        plU = ctx.get_obj("planU")
        plI = ctx.get_obj("planI")
        # ---- the two half-sweeps, fused in one compiled superstep ----
        uf = solve_side(bidsU, brwU, plU, 1, ctx.get_obj("if_"), U)
        if_ = solve_side(bidsI, brwI, plI, 0, uf, I)
        ctx.put_obj("uf", uf)
        ctx.put_obj("if_", if_)
        # rmse for the curve + stop criterion (user-sorted copy; order is
        # irrelevant for a sum)
        pred = (uf[bidsU[:, 0]] * if_[bidsU[:, 1]]).sum(-1)
        r = brwU[:, 0]
        w = brwU[:, 1]
        se = manifest_psum(jnp.stack([(w * (pred - r) ** 2).sum(), w.sum()]),
                           "d", name="als_rmse", num_workers=nw)
        rmse = jnp.sqrt(se[0] / jnp.maximum(se[1], 1e-12)).astype(jnp.float32)
        ctx.put_obj("rmse_curve", jax.lax.dynamic_update_index_in_dim(
            ctx.get_obj("rmse_curve"), rmse, ctx.step_no - 1, 0))
        ctx.put_obj("rmse_delta", jnp.abs(ctx.get_obj("prev_rmse") - rmse))
        ctx.put_obj("prev_rmse", rmse)

    queue = (IterativeComQueue(env=env, max_iter=p.num_iter, seed=p.seed)
             .init_with_partitioned_data("idsU", np.concatenate(idsU))
             .init_with_partitioned_data("rwU", np.concatenate(rwU))
             .init_with_partitioned_data("idsI", np.concatenate(idsI))
             .init_with_partitioned_data("rwI", np.concatenate(rwI))
             .init_with_partitioned_data("planU", planU.reshape(-1, 3))
             .init_with_partitioned_data("planI", planI.reshape(-1, 3))
             .init_with_broadcast_data("uf0", uf0)
             .init_with_broadcast_data("if0", if0)
             .add(step))
    from ....engine.comqueue import freeze_config
    queue.set_program_key(("als", U, I, freeze_config(p)))
    if p.tol > 0:
        # KMeansIterTermination analogue: stop when the train-RMSE moves
        # less than tol between supersteps (replicated state only). The
        # step_no >= 4 burn-in matters: ALS from random factors often has
        # a near-flat RMSE plateau on iterations 1-2 before the factors
        # orient (measured on MovieLens-1M shape: deltas 5e-4, 8e-3,
        # 3e-2, ... — a bare delta<tol test stops INSIDE the plateau)
        queue.set_compare_criterion(
            lambda ctx: (ctx.get_obj("rmse_delta") < p.tol)
            & (ctx.step_no >= min(4, p.num_iter)))
    res = queue.exec()
    uf = res.get("uf")
    if_ = res.get("if_")
    curve = np.asarray(res.get("rmse_curve"))[:res.step_count]
    return uf, if_, curve
