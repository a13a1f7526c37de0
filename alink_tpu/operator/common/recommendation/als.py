"""ALS matrix factorization — blocked and device-resident.

Re-design of common/recommendation/AlsTrain.java (587 LoC; SURVEY §2.3
"block/graph parallelism"): the reference groups ratings into user/item
blocks, exchanges factor request/response messages over Flink coGroups
(AlsTrain.java:266-335), and solves per-block normal equations with a
Cholesky (NormalEquation, :493) inside a Flink loop.

Here a fit is two programs of the BSP engine, and ONE path for every
table: the three columns (user index, item index, rating) are per-row
block columns ``(row_blocks, S, 128)``, device-resident where the source
is (``RowBlockColumn``), packed from host values otherwise.

``jit_als_group`` (once a fit, from the raw rows, which may come in any
order): every worker sorts its shard of the triples by user and by item
(``lax.sort``: the key and two payloads), finds each side's offsets by a
binary search of the sorted keys, and counts: a side's counts are whole
numbers, summed over the workers exactly. It also ranks a side's rows by
falling count, because the rows' lengths follow a power law and the
half-sweep walks rows of like length together.

``jit_als_sweep`` (a superstep an iteration: the user half-sweep, the item
half-sweep, the train RMSE). A half-sweep walks the side's rows, ranked,
in batches of static shape (``_tiers``: a few thousand heaviest rows in
narrow batches with long chunks, the rest in wide batches with short
ones). A batch loops over chunks of its rows' ratings, as many as its
longest row needs: a chunk of a row is whole aligned slabs of 16 grouped
ratings, fetched as the 128-wide rows of the grouped columns they lie in
and cut out (every gathered index costs the same ~12 ns on this chip
whatever its width, so a slab costs what one rating would); then the other
side's factor rows are gathered, one index a rating (``als_gather``: the
bulk of a half-sweep), and the rows' Gram products added on the MXU
(``als_gram``): the factor rows are padded to 128 lanes, the lane after the
last factor carries the rating on the right-hand operand, so one product
gives ``sum theta theta^T`` and ``sum r theta`` at float32 (precision
``highest``). The batch's equations are summed over the workers
(``als_combine``; nothing on one worker), ridge-weighted by the row's count
(ALS-WR: ``lambda * n_u`` on the diagonal), and solved (``als_solve``,
``ops/smallsolve.py``: 128 systems a step of a Pallas kernel on the chip);
rows with no rating get zeros. The iteration's squared error costs the item
half-sweep no second pass (``als_rmse``; see ``_half_sweep``): the residual
against the factors BEFORE the half-sweep rides a second spare lane, and
the error against the new factors follows from the sums already in hand,
Kahan-added across batches. Every chunk counts the ratings it folded, as
whole numbers.

The factors ride the carry replicated (the summed equations leave every
worker with the batch's solution); ``shard_solve`` reduce-scatters a
batch's equations instead, solves a slice a worker and all-gathers the
solved rows. Implicit feedback (``implicit_prefs``) follows the
reference's confidence weighting c = 1 + alpha*|r| over the observed
ratings; ``nonnegative`` solves each row by projected gradient
(``batched_nnls``) from the clipped unconstrained solution.

Convergence mirrors KMeansIterTermination (KMeansTrainBatchOp.java:72-83):
``tol`` > 0 stops the superstep loop when the train-RMSE delta falls
below it, and the returned curve length is the MEASURED iteration count.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ....common.columnar import LANES, RowBlockColumn, as_row_blocks
from ....common.metrics import get_registry, metrics_enabled
from ....common.mlenv import MLEnvironment, MLEnvironmentFactory
from ....common.tracing import trace_span
from ....engine import IterativeComQueue
from ....engine.communication import (manifest_all_gather, manifest_psum,
                                      manifest_psum_scatter)
from ....ops.smallsolve import solve_augmented, solve_path
from ..blocked import kahan_add

GROUP_PROGRAM = "als_group"
SWEEP_PROGRAM = "als_sweep"
#: rows of the head tier, its batch and its chunk; the tail's batch and
#: chunk (see ``_tiers``)
HEAD_ROWS, HEAD_BATCH, HEAD_CHUNK = 4096, 64, 512
TAIL_BATCH, TAIL_CHUNK = 4096, 64
#: grouped ratings are fetched in aligned runs of this many (a chunk is a
#: whole number of them)
SLAB = 16
HIGHEST = jax.lax.Precision.HIGHEST


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
    # Reduce-scatter a batch's summed equations by row range instead of
    # summing them onto every worker, solve a slice a worker, and
    # all-gather only the solved rows (docs/parallelism.md).
    shard_solve: bool = False


def _tiers(n_rows: int, nw: int):
    """``(first, last, batch, chunk)`` of the ranked rows' tiers, from the
    row count alone. The rows come ranked by falling count, so the rows of
    a batch are of like length and its chunk loop, as long as its longest
    row, wastes little; the few thousand heaviest rows (a power law's head:
    100,000 ratings beside 1,000) go in narrow batches with long chunks,
    the rest in wide ones with short chunks."""
    q = 8 * nw

    def fit(b):
        return -(-b // q) * q

    if n_rows <= 2 * HEAD_ROWS:
        b = fit(min(n_rows, TAIL_BATCH))
        return [(0, -(-n_rows // b) * b, b, TAIL_CHUNK)]
    hb, tb = fit(HEAD_BATCH), fit(TAIL_BATCH)
    head = -(-HEAD_ROWS // hb) * hb
    return [(0, head, hb, HEAD_CHUNK),
            (head, head + -(-(n_rows - head) // tb) * tb, tb, TAIL_CHUNK)]


def _lanes_for(rank: int) -> int:
    """Lanes of a factor row: the factors, then the lane the rating rides
    on and the lane the residual rides on, padded to whole registers."""
    return -(-(rank + 2) // LANES) * LANES


def _run_lengths(off, n_rows: int):
    """A side's counts on this worker, from its offsets."""
    return off[1:n_rows + 1] - off[:n_rows]


def _ridge_weight(n):
    """ALS-WR: a row's ridge is ``lambda`` times its count (once for a row
    with no rating, which is solved to zeros anyway)."""
    return jnp.maximum(n, 1).astype(jnp.float32)


def _owned(at, st, en):
    """Which of the fetched positions ``at (rows, K)`` are the row's own
    ratings ``[st, en)``."""
    return (at >= st[:, None]) & (at < en[:, None])


def _group_stage(n_users: int, n_items: int, nw: int):
    def group(ctx):
        ub, ib, rb = (ctx.get_obj(k).reshape(-1)
                      for k in ("users", "items", "ratings"))
        L = ub.shape[0]
        here = (ctx.task_id * L + jnp.arange(L, dtype=jnp.int32)
                < ctx.get_obj("n"))
        for side, key, other, n_rows in (("u", ub, ib, n_users),
                                         ("i", ib, ub, n_items)):
            # rows past the table sort behind every id
            key = jnp.where(here, key, n_rows).astype(jnp.int32)
            skey, ids, val = jax.lax.sort(
                (key, other.astype(jnp.int32), rb.astype(jnp.float32)),
                num_keys=1)
            off = jnp.searchsorted(
                skey, jnp.arange(n_rows + 1, dtype=jnp.int32)
            ).astype(jnp.int32)
            # one entry more: the row that stands for padding is empty
            off = jnp.concatenate([off, off[-1:]])
            cnt = manifest_psum(_run_lengths(off, n_rows), "d",
                                name=f"als_count_{side}", num_workers=nw)
            order = jnp.argsort(-cnt, stable=True).astype(jnp.int32)
            # whole registers of grouped ratings: a half-sweep gathers them
            # by the row (the same bytes, seen 128 wide)
            ids, val = ids.reshape(-1, LANES), val.reshape(-1, LANES)
            for name, v in (("ids", ids), ("val", val), ("off", off),
                            ("cnt", cnt), ("order", order),
                            ("rank", jnp.argsort(order).astype(jnp.int32))):
                ctx.put_obj(f"{name}_{side}", v)
    return group


def _half_sweep(other, ids, val, off, cnt, order, rank_of, n_rows: int,
                p: AlsTrainParams, nw: int, task_id, old=None):
    """One side's new factors ``(n_rows, lanes)`` from the other side's
    (``other``), this worker's ratings grouped by the side's row (``ids``:
    the other side's row, ``val``, ``off``), the rows' counts over all
    workers and their ranking. Also the ratings this worker folded and,
    with ``old`` (the side's factors before this half-sweep), its squared
    error against the new factors.

    The squared error costs no second pass: a chunk also adds up, on two
    spare lanes of the same product, ``r' = r - theta . x_old`` against
    theta and against itself, and ``sum (r - theta . x)^2 = sum r'^2 - 2 d
    . sum r' theta + d^T (sum theta theta^T) d`` with ``d = x - x_old``:
    what it loses to cancellation shrinks with the step."""
    f32 = jnp.float32
    r, Fp, L = p.rank, other.shape[1], ids.size
    want_sse = old is not None
    tiers = _tiers(n_rows, nw)
    total = tiers[-1][1]
    pad = jnp.full((total - n_rows,), n_rows, jnp.int32)
    order_p = jnp.concatenate([order, pad])
    cnt_p = jnp.concatenate([cnt, jnp.zeros((1,), cnt.dtype)])
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, Fp), 2)
    J = min(-(-(r + 1) // 8) * 8, Fp)       # the solver's columns: whole tiles
    ridge = jnp.eye(r, J, dtype=f32)[:, :, None]
    in_slab = jnp.arange(SLAB, dtype=jnp.int32)
    piece = jnp.arange(LANES // SLAB, dtype=jnp.int32)[:, None]

    def slabs_of(a, q):
        """Slabs ``q (rows, n)`` of the grouped column ``a (L / 128, 128)``:
        a chunk of a row is whole slabs of SLAB ratings, fetched as the
        128-wide rows they lie in (a gather index costs what a factor
        row's does, whatever its width; a 16-wide view of the column would
        be padded eightfold) and cut out."""
        wide = a[q // (LANES // SLAB)].reshape(
            q.shape + (LANES // SLAB, SLAB))
        mine = (piece == (q % (LANES // SLAB))[..., None, None])
        return jnp.where(mine, wide, 0).sum(-2)            # (rows, n, SLAB)

    def solve(A, n):
        """A batch's rows from its summed equations ``A (rows, lanes,
        lanes)``: ``A[:, :r, :r]`` the Gram sums, ``A[:, :r, r]`` the
        right-hand sides; ``n`` the rows' counts."""
        M = jnp.transpose(A[:, :r, :J], (1, 2, 0))
        M = M + ridge * (p.lambda_reg * _ridge_weight(n))
        x = solve_augmented(M).T                               # (rows, r)
        if p.nonnegative:
            x = batched_nnls(jnp.transpose(M[:, :r], (2, 0, 1)), M[:, r].T,
                             x0=jnp.maximum(x, 0.0))
        return jnp.where((n > 0)[:, None], x, 0.0)

    def tier(lo, hi, B, K, carry):
        slabs = jnp.arange(K // SLAB, dtype=jnp.int32)[None]

        def batch(b, carry):
            xs, seen, sse, lost = carry
            at = lo + b * B
            rows = jax.lax.dynamic_slice(order_p, (at,), (B,))
            st, en = off[rows], off[rows + 1]
            first = st // SLAB                  # a row's first slab
            chunks = jnp.max(jnp.where(
                en > st, (en - first * SLAB + K - 1) // K, 0))
            x_old = old[jnp.minimum(rows, n_rows - 1)] if want_sse else None

            def gram(c, acc):
                A, S, seen = acc
                with jax.named_scope("als_gather"):
                    q = first[:, None] + c * (K // SLAB) + slabs    # (B, K/SLAB)
                    at_ = (q[..., None] * SLAB + in_slab).reshape(B, K)
                    # a slab past the array is fetched from its end and
                    # masked by where it WOULD have stood
                    real = _owned(at_, st, en)
                    q = jnp.minimum(q, L // SLAB - 1)
                    rv = slabs_of(val, q).reshape(B, K)
                    G = other[jnp.where(real, slabs_of(ids, q).reshape(B, K),
                                        0)]
                with jax.named_scope("als_gram"):
                    w = real.astype(f32)
                    if p.implicit_prefs:
                        t = (rv > 0).astype(f32)
                        ww = w * (1.0 + p.alpha * jnp.abs(rv))
                    else:
                        t, ww = rv, w
                    if want_sse:
                        res = rv - jnp.einsum("bkf,bf->bk", G, x_old,
                                              precision=HIGHEST)
                        G = jnp.where(lane == r + 1, res[..., None], G)
                    A = A + jnp.einsum(
                        "bkf,bkg->bfg", G * ww[..., None],
                        jnp.where(lane == r, t[..., None], G),
                        precision=HIGHEST, preferred_element_type=f32)
                    if S is not None:           # implicit: the plain sums
                        S = S + jnp.einsum(
                            "bkf,bkg->bfg", G * w[..., None], G,
                            precision=HIGHEST, preferred_element_type=f32)
                return A, S, seen + real.sum(dtype=jnp.int32)

            zeros = jnp.zeros((B, Fp, Fp), f32)
            A, S, seen = jax.lax.fori_loop(
                0, chunks, gram,
                (zeros, zeros if want_sse and p.implicit_prefs else None,
                 seen))
            mine = A if S is None else S        # this worker's plain sums
            n = cnt_p[rows]
            with jax.named_scope("als_combine"):
                if nw > 1 and p.shard_solve:
                    A = manifest_psum_scatter(
                        A, "d", scatter_dimension=0, tiled=True,
                        name="als_eq", num_workers=nw)
                    n = jax.lax.dynamic_slice(n, (task_id * (B // nw),),
                                              (B // nw,))
                elif nw > 1:
                    A = manifest_psum(A, "d", name="als_eq", num_workers=nw)
            with jax.named_scope("als_solve"):
                x = solve(A, n)
            if nw > 1 and p.shard_solve:
                x = manifest_all_gather(x, "d", axis=0, tiled=True,
                                        name="als_factors", num_workers=nw)
            x = jnp.pad(x, ((0, 0), (0, Fp - r)))
            xs = jax.lax.dynamic_update_slice(xs, x, (at, 0))
            if want_sse:
                with jax.named_scope("als_rmse"):
                    d = (x - x_old)[:, :r]
                    e = (mine[:, r + 1, r + 1]
                         - 2 * (d * mine[:, :r, r + 1]).sum(-1)
                         + jnp.einsum("bf,bfg,bg->b", d, mine[:, :r, :r], d,
                                      precision=HIGHEST))
                    sse, lost = kahan_add(sse, lost, e.sum())
            return xs, seen, sse, lost

        return jax.lax.fori_loop(0, (hi - lo) // B, batch, carry)

    zero = jnp.zeros((), f32)
    carry = (jnp.zeros((total, Fp), f32), jnp.zeros((), jnp.int32), zero,
             zero)
    for lo, hi, B, K in tiers:
        carry = tier(lo, hi, B, K, carry)
    xs, seen, sse, _ = carry
    return xs[rank_of], seen, sse


def als_train(users, items, ratings, p: AlsTrainParams,
              env: Optional[MLEnvironment] = None,
              num_users: Optional[int] = None,
              num_items: Optional[int] = None,
              info: Optional[Dict] = None) -> Tuple:
    """Returns (user_factors (U, rank), item_factors (I, rank),
    rmse_curve); ``len(rmse_curve)`` is the measured number of iterations
    run.

    ``users``, ``items`` (row indices ``0 .. U - 1``, ``0 .. I - 1``) and
    ``ratings`` are host ``(n,)`` values or ``RowBlockColumn``s of one
    layout, which may be device-resident and are then read where they
    lie; the factors come back as device arrays in that case, as host
    arrays otherwise. ``info``, when given, receives what the fit went
    through: the factors as the program holds them (``user_factors``,
    ``item_factors``: 128-lane rows on the device, no copy), the item
    factors the last user half-sweep read (``items_read``), each side's
    counts, the curve, the ratings folded (``ratings``, exact) over
    ``half_sweeps`` and the ``paths`` taken."""
    env = env or MLEnvironmentFactory.get_default()
    nw = env.num_workers
    on_device = isinstance(ratings, RowBlockColumn) and ratings.on_device
    n = len(ratings)
    ub = as_row_blocks(users, np.int32, nw)
    ib = as_row_blocks(items, np.int32, nw, like=ub)
    rb = as_row_blocks(ratings, np.float32, nw, like=ub)
    if num_users is None:
        num_users = int(ub.max()) + 1
    if num_items is None:
        num_items = int(ib.max()) + 1
    U, I, r = int(num_users), int(num_items), int(p.rank)
    Fp = _lanes_for(r)
    # the seed is data: the stages close over the settings without it, so
    # one program serves every seed
    seed, p = p.seed, dataclasses.replace(p, seed=0)
    from ....engine.comqueue import freeze_config
    names = [f"{k}_{s}" for s in "ui"
             for k in ("ids", "val", "off", "cnt", "order", "rank")]
    with trace_span("als.group", cat="als", coarse=True,
                    args={"ratings": n, "users": U, "items": I,
                          "path": "sort"}):
        grouped = (IterativeComQueue(env=env, max_iter=1)
                   .init_with_partitioned_data("users", ub)
                   .init_with_partitioned_data("items", ib)
                   .init_with_partitioned_data("ratings", rb)
                   .init_with_broadcast_data("n", np.asarray(n, np.int32))
                   .add(_group_stage(U, I, nw))
                   .set_program_key((GROUP_PROGRAM, U, I, tuple(ub.shape)))
                   .exec())
    T = p.num_iter

    def sweep(ctx):
        g = {k: ctx.get_obj(k)[0] for k in names}
        if ctx.is_init_step:
            key = jax.random.wrap_key_data(ctx.get_obj("key"))
            if0 = jax.random.uniform(key, (I, r), jnp.float32) * jnp.float32(
                1.0 / np.sqrt(r))
            ctx.put_obj("if_", jnp.pad(if0, ((0, 0), (0, Fp - r))))
            ctx.put_obj("rmse_curve", jnp.zeros((T,), jnp.float32))
            ctx.put_obj("seen", jnp.zeros((T, 2), jnp.int32))
            ctx.put_obj("prev_rmse", jnp.asarray(jnp.inf, jnp.float32))
            ctx.put_obj("rmse_delta", jnp.asarray(jnp.inf, jnp.float32))
        if_read = ctx.get_obj("if_")
        uf, seen_u, _ = _half_sweep(
            if_read, g["ids_u"], g["val_u"], g["off_u"], g["cnt_u"],
            g["order_u"], g["rank_u"], U, p, nw, ctx.task_id)
        if_, seen_i, sse = _half_sweep(
            uf, g["ids_i"], g["val_i"], g["off_i"], g["cnt_i"],
            g["order_i"], g["rank_i"], I, p, nw, ctx.task_id, old=if_read)
        ctx.put_obj("uf", uf)
        ctx.put_obj("if_", if_)
        ctx.put_obj("if_read", if_read)
        t = ctx.step_no - 1
        seen = jnp.stack([seen_u, seen_i])
        if nw > 1:
            seen = manifest_psum(seen, "d", name="als_seen", num_workers=nw)
            sse = manifest_psum(sse, "d", name="als_rmse", num_workers=nw)
        ctx.put_obj("seen", jax.lax.dynamic_update_index_in_dim(
            ctx.get_obj("seen"), seen, t, 0))
        rmse = jnp.sqrt(sse / jnp.maximum(seen[1], 1).astype(jnp.float32))
        ctx.put_obj("rmse_curve", jax.lax.dynamic_update_index_in_dim(
            ctx.get_obj("rmse_curve"), rmse, t, 0))
        ctx.put_obj("rmse_delta", jnp.abs(ctx.get_obj("prev_rmse") - rmse))
        ctx.put_obj("prev_rmse", rmse)

    queue = IterativeComQueue(env=env, max_iter=T)
    for k in names:
        queue.init_with_partitioned_data(k, grouped.device(k))
    queue.init_with_broadcast_data("key", np.asarray(jax.random.key_data(
        jax.random.PRNGKey(seed))))
    queue.add(sweep).set_program_key(
        (SWEEP_PROGRAM, U, I, tuple(ub.shape), freeze_config(p)))
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
    paths = {"group": "sort", "gram": "einsum_highest",
             "solve": "nnls" if p.nonnegative
             else solve_path(jnp.float32, r)}
    with trace_span("als.sweep", cat="als", coarse=True,
                    args={"rank": r, "num_iter": int(T),
                          "gram": paths["gram"], "solve": paths["solve"]}):
        res = queue.exec()
        # the curve's fetch is the fit's end: it waits for the device
        curve, seen, steps = res.get_all(["rmse_curve", "seen", "__step"])
        steps = int(steps)
    curve = np.asarray(curve)[:steps]
    folded = int(np.asarray(seen, np.int64)[:steps].sum())
    if metrics_enabled():
        reg = get_registry()
        reg.inc("alink_als_ratings_total", folded)
        reg.inc("alink_als_sweeps_total", 2 * steps)
    uf, if_, if_read = (res.device(k)[0] for k in ("uf", "if_", "if_read"))
    if info is not None:
        info.update(user_factors=uf, item_factors=if_, items_read=if_read,
                    user_counts=grouped.device("cnt_u")[0],
                    item_counts=grouped.device("cnt_i")[0],
                    rmse_curve=curve, ratings=folded, half_sweeps=2 * steps,
                    rank=r, paths=paths)
    uf, if_ = uf[:, :r], if_[:, :r]
    if not on_device:
        uf, if_ = np.asarray(uf), np.asarray(if_)
    return uf, if_, curve
