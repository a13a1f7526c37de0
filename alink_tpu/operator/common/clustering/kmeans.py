"""KMeans internals — TPU-native, blocked.

Re-design of common/clustering/kmeans/ (call stack SURVEY §3.3):
  KMeansPreallocateCentroid  -> init centroids (k-means|| on the device /
                                random rows)
  KMeansAssignCluster        -> one pass over the worker's shard in row
                                blocks: distances, argmin, and the
                                k x (d+1) sum/weight buffer (replaces
                                KMeansUtil.updateSumMatrix's per-point loop,
                                KMeansAssignCluster.java:60-64)
  AllReduce(centroidAllReduce) -> lax.psum
  KMeansUpdateCentroids      -> sums / weights (KMeansUpdateCentroids.java:53-71)
  KMeansIterTermination      -> centroid movement < tol carry bit
Supports EUCLIDEAN and COSINE distances (reference FastDistance pre-norms).

The table is a ``DenseBlockColumn`` (common/columnar.py): feature-major,
lane-packed blocks ``(row_blocks, d, S, 128)`` that may already live on
the device, partitioned over the workers on the leading axis. A superstep
walks its shard block by block (``lax.fori_loop``), so every intermediate
is ``(k, S, 128)`` and peak memory is the table plus O(block); nothing of
shape ``(n, k)`` or ``(n, d + 1)`` exists. Sums over a shard stay exact to
float32 round-off at 100 million rows because (a) a block's rows are
summed CENTRED on the cluster's current centroid (``sum w (x - c_j)``, so
the addends are small and of both signs), and (b) the per-block partial
sums are added with a Kahan compensation across blocks.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ....common.columnar import (LANES, DenseBlockColumn,
                                  as_block_column, block_weights)
from ....common.metrics import get_registry, metrics_enabled
from ....common.mlenv import MLEnvironment, MLEnvironmentFactory
from ....common.tracing import trace_span
from ....engine import AllReduce, IterativeComQueue
from ....engine.communication import manifest_all_gather
from ....kernels.kmeans import (fold_candidates, fold_path, lloyd_path,
                                lloyd_sums)
from ..blocked import (block_at as _block_at, join_count as _join_count,
                       kahan_add as _kahan_add, split_count as _split_count)

#: the engine names each compiled program ``jit_<first word of its key>``
LLOYD_PROGRAM = "kmeans_lloyd"
INIT_PROGRAM = "kmeans_init"


def _rows(X, idx) -> np.ndarray:
    """Host copies of rows ``idx`` of host rows or of a blocked table."""
    return take_rows(X, idx) if isinstance(X, DenseBlockColumn) else X[idx]


def kmeans_plus_plus_init(X, k: int, seed: int,
                          sample_cap: int = 4096) -> np.ndarray:
    """k-means++ seeding on a bounded host sample (reference KMeansInitCentroids
    K-MEANS|| has the same role: good seeds without a full device pass)."""
    rng = np.random.RandomState(seed)
    n = len(X)
    if n > sample_cap:
        X = _rows(X, rng.choice(n, sample_cap, replace=False))
        n = sample_cap
    else:
        X = _rows(X, np.arange(n))
    cents = [X[rng.randint(n)]]
    d2 = ((X - cents[0]) ** 2).sum(1)
    for _ in range(1, k):
        tot = d2.sum()
        if tot <= 0:  # fewer distinct points than k: fall back to uniform
            cents.append(X[rng.randint(n)])
            continue
        cents.append(X[rng.choice(n, p=d2 / tot)])
        d2 = np.minimum(d2, ((X - cents[-1]) ** 2).sum(1))
    return np.stack(cents)


def random_init(X, k: int, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed)
    return _rows(X, rng.choice(len(X), k, replace=len(X) < k))


def _weighted_kmeans_pp(C: np.ndarray, w: np.ndarray, k: int,
                        rng: np.random.RandomState,
                        lloyd_iters: int = 8) -> np.ndarray:
    """Weighted k-means++ seeding + a few weighted Lloyd sweeps on the
    (small) candidate set — the K-MEANS|| recluster step (Bahmani et al.
    algorithm 2 line 7-8; reference KMeansInitCentroids final recluster).
    Runs on the host: the candidate set is O(rounds * oversample), never
    the data."""
    m = C.shape[0]
    w = np.maximum(np.asarray(w, np.float64), 0.0)
    if w.sum() <= 0:
        w = np.ones(m)
    p = w / w.sum()
    cents = [C[rng.choice(m, p=p)]]
    d2 = ((C - cents[0]) ** 2).sum(1)
    for _ in range(1, k):
        q = w * d2
        tot = q.sum()
        if tot <= 0:
            cents.append(C[rng.choice(m, p=p)])
            continue
        cents.append(C[rng.choice(m, p=q / tot)])
        d2 = np.minimum(d2, ((C - cents[-1]) ** 2).sum(1))
    cc = np.stack(cents)
    for _ in range(lloyd_iters):
        dist = ((C[:, None, :] - cc[None, :, :]) ** 2).sum(-1)
        ids = dist.argmin(1)
        for j in range(k):
            sel = ids == j
            if w[sel].sum() > 0:
                cc[j] = (C[sel] * w[sel, None]).sum(0) / w[sel].sum()
    return cc




# -- the blocked table ---------------------------------------------------------

def take_rows(col: DenseBlockColumn, idx) -> np.ndarray:
    """Host copies ``(len(idx), d)`` of a few rows of the table (read
    tile by tile where the table lives on the device: ``_rows_at``)."""
    idx = np.asarray(idx, np.int64).reshape(-1)
    b, r = np.divmod(idx, col.block_rows)
    if col.on_device:
        return np.asarray(_device_rows(col.blocks, b.astype(np.int32),
                                       r.astype(np.int32)))
    return col.blocks[b, :, r // LANES, r % LANES]


def block_distances(xb, C, distance_type: str = "EUCLIDEAN"):
    """Distances of one feature-major block to every centroid: ``xb`` is
    ``(d, *rows)``, ``C`` is ``(k, d)``, the result ``(k, *rows)``. THE
    distance of the trainer, of ``assign_clusters`` and of the model
    mapper: squared differences summed feature by feature (no ``|x|^2 -
    2 x.c + |c|^2`` cancellation), or one minus the cosine."""
    Cb = C.reshape(C.shape + (1,) * (xb.ndim - 1))
    if distance_type == "COSINE":
        xn = jnp.maximum(jnp.sqrt((xb * xb).sum(0)), 1e-12)
        Cn = Cb / jnp.maximum(
            jnp.sqrt((Cb * Cb).sum(1, keepdims=True)), 1e-12)
        return 1.0 - (xb[None] * Cn).sum(1) / xn[None]
    diff = xb[None] - Cb
    return (diff * diff).sum(1)


def assign_clusters(X, C, distance_type: str = "EUCLIDEAN"):
    """Nearest centroid ids + distances for rows ``X`` of shape (n, d)."""
    D = block_distances(jnp.asarray(X).T, jnp.asarray(C), distance_type)
    return jnp.argmin(D, axis=0), jnp.min(D, axis=0)


@functools.partial(jax.jit, static_argnames="distance_type")
def _assign_blocks(blocks, C, distance_type):
    def one(xb):
        D = block_distances(xb, C, distance_type)
        return jnp.argmin(D, 0), jnp.min(D, 0)
    return jax.lax.map(one, blocks)


def assign_table(col: DenseBlockColumn, C, distance_type: str = "EUCLIDEAN"
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Host ``(ids, distances)`` of every row of a blocked table, block by
    block with the trainer's distance."""
    ids, dist = _assign_blocks(col.blocks, jnp.asarray(C, col.blocks.dtype),
                               distance_type)
    n = col.n_rows
    return (np.asarray(ids).reshape(-1)[:n], np.asarray(dist).reshape(-1)[:n])


def _rows_at(Xs, blk, pos):
    """Rows ``pos`` of blocks ``blk`` of a shard, ``(len, d)``. Each row
    is read as the aligned ``(d, 8, 128)`` register tile that holds it,
    masked down to its one sublane and lane: the table keeps its layout.
    (An advanced-indexing gather, or a one-element ``dynamic_slice``,
    makes XLA lay the WHOLE table out anew: a second copy of the shard.)"""
    d = Xs.shape[1]
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1)

    def one(at):
        b, r = at
        s = r // LANES
        zero = jnp.zeros_like(b)
        tile = jax.lax.dynamic_slice(
            Xs, (b, zero, s // 8 * 8, zero), (1, d, 8, LANES))[0]
        here = (sub == s % 8) & (lane == r % LANES)
        return jnp.where(here[None], tile, 0).sum((1, 2))
    return jax.lax.map(one, (blk, pos))


_device_rows = jax.jit(_rows_at)


# -- k-means|| -----------------------------------------------------------------

def _topl_fold(run, keys, i):
    """Fold block ``i``'s ``keys`` into a shard's running best ``l``:
    ``run`` is ``(values (l,), block (l,), position (l,))``, largest
    first, equal values in row order, ``-inf`` where nothing is held yet.
    A key enters only when STRICTLY greater than the smallest value held
    (later blocks hold later rows) and goes behind the entries it
    equals, so after the last block ``run`` is what ``lax.top_k`` over
    all the shard's keys gives, ties to the lower row. A block none of
    whose keys beats that threshold costs one max over its keys and no
    trip of the loop. Returns ``(run, whether the block was ranked)``."""
    l = run[0].shape[0]
    i = jnp.asarray(i, jnp.int32)
    slot = jnp.arange(l, dtype=jnp.int32)
    at = jnp.arange(keys.size, dtype=jnp.int32).reshape(keys.shape)

    def beats(c):
        return c[3] > c[0][-1]

    def enter(c):
        vals, blk, pos, top, left = c
        q = jnp.min(jnp.where(left == top, at, keys.size))   # its first row
        ahead = (vals >= top).sum(dtype=jnp.int32)

        def put(a, x):
            return jnp.where(slot < ahead, a,
                             jnp.where(slot == ahead, x, jnp.roll(a, 1)))
        left = jnp.where(at == q, -jnp.inf, left)
        return (put(vals, top), put(blk, i), put(pos, q),
                jnp.max(left), left)

    start = run + (jnp.max(keys), keys)
    vals, blk, pos, _, _ = jax.lax.while_loop(beats, enter, start)
    return (vals, blk, pos), beats(start)


def _kmpp_fold(Xs, Ws, d2, nearest, new, off, path: str):
    """Fold a round's candidates ``new`` ``(l or 1, d)``, numbered from
    ``off``, into a shard's per-row ``(d2, nearest)`` state: a row whose
    distance to the nearest of them is under its ``d2`` takes that
    distance and that candidate; a row of weight 0 reads distance 0. The
    first round is the same fold on ``d2 = +inf``, ``nearest = 0``.
    ``path`` (``kernels.kmeans.fold_path``) names who does it: the one
    streamed ``"kernel"``, or ``"xla"``, ``block_distances`` block by
    block."""
    if path == "kernel":
        return fold_candidates(Xs, Ws, d2, nearest, new, off)

    def body(i, c):
        d2, nearest = c
        Dn = block_distances(_block_at(Xs, i), new)      # (l or 1, S, 128)
        dn = jnp.where(_block_at(Ws, i) != 0, jnp.min(Dn, 0), 0)
        j = off + jnp.argmin(Dn, 0).astype(jnp.int32)
        d2b, nb_ = _block_at(d2, i), _block_at(nearest, i)
        closer = dn < d2b
        upd = jax.lax.dynamic_update_index_in_dim
        return (upd(d2, jnp.where(closer, dn, d2b), i, 0),
                upd(nearest, jnp.where(closer, j, nb_), i, 0))

    with jax.named_scope("kmpp_fold"):
        return jax.lax.fori_loop(0, Xs.shape[0], body, (d2, nearest))


#: what the draw's maxima pass may hold at once, should the compiler write a
#: group's noise out before it reduces it (four arrays a group: bits, noise,
#: keys, and one to spare). A larger group buys nothing on the device once a
#: launch is a hundredth of its work, and XLA:TPU compiles the fused pass
#: slower the larger it is (0.9 s at 1 block of 65,536 rows, 1.9 at 64, 3.3
#: at 512, 5.3 at 1,526: a described v5e, PR 36)
_DRAW_TEMP_BYTES = 64 << 20


def _blocks_per_group(nbl: int, block_bytes: int) -> int:
    """Blocks the maxima pass keys at once: the whole shard where four
    arrays of its size stay under ``_DRAW_TEMP_BYTES``, else the most
    that do."""
    return max(1, min(nbl, _DRAW_TEMP_BYTES // (4 * block_bytes)))


def _topl_of_selected(keys_at, maxima, l: int):
    """A shard's best ``l`` keys from its block ``maxima`` ``(nbl,)`` and
    ``keys_at(b)``, block ``b``'s keys: the ``min(l, nbl)`` blocks of the
    largest maxima (``lax.top_k``: equal maxima to the lower block), folded
    in rising block order through ``_topl_fold``. That is ``lax.top_k`` over
    all the shard's keys, ties to the lower row: the selected blocks hold
    at least ``l`` keys >= tau, the smallest selected maximum; a key of any
    other block is <= its block's maximum <= tau, so it could enter only by
    EQUALLING tau, and of equal maxima the earlier block is the one
    selected, as of equal keys the earlier row is the one kept. Returns
    ``(run, selected blocks that hold a finite key)``."""
    m = min(l, maxima.shape[0])
    top, sel = jax.lax.top_k(maxima, m)
    sel = jnp.sort(sel).astype(jnp.int32)
    none = jnp.zeros((l,), jnp.int32)

    def body(j, run):
        b = sel[j]
        return _topl_fold(run, keys_at(b), b)[0]

    run = jax.lax.fori_loop(
        0, m, body, (jnp.full((l,), -jnp.inf, maxima.dtype), none, none))
    return run, (top > -jnp.inf).sum(dtype=jnp.int32)


# jitted: the init pass and the loop body of the program call it on the same
# shapes, so it is traced once (0.3 s of a process's first fit; the program
# still lowers it once a call site)
@functools.partial(jax.jit, static_argnames=("cap", "l"))
def _kmpp_draw(Ws, d2, nearest, key, block0, last, cap: int, l: int):
    """One k-means|| round's draw over a worker's shard, from the folded
    state: the shard's ``l`` proposals by Gumbel-top-l over p ∝ d2. ONE
    pass keys every block (noise keyed by ``block0 +`` its number: one
    ``fold_in`` for the whole shard, since lowering a threefry costs a
    process's first fit a tenth of a second a call site) and keeps only
    the blocks' maxima; the ``min(l, blocks)`` blocks of the largest
    maxima are keyed again and folded into a running best ``l``
    (``_topl_of_selected`` has why no other block can hold a winner). Also
    the rows seen and, in the ``last`` round alone, the row weights summed
    under each of the ``cap`` candidates, block by block. Returns
    ``(proposals (keys (l,), block (l,), position (l,)), blocks ranked,
    rows seen, candidate weights (cap,))``; ``blocks ranked`` are the
    selected blocks that held a finite key, at most ``l``."""
    dt = d2.dtype
    nbl = Ws.shape[0]

    # the blocks' noise keys, by GLOBAL block number
    noise = jax.vmap(lambda i: jax.random.fold_in(key, block0 + i))(
        jnp.arange(nbl, dtype=jnp.int32))

    def keys_of(d2b, k):
        # this round's draw: Gumbel-top-l over p_i ∝ d2_i
        g = jax.random.gumbel(k, d2b.shape, dt)
        return jnp.where(d2b > 0,
                         jnp.log(jnp.maximum(d2b, 1e-30)) + g, -jnp.inf)

    group = _blocks_per_group(
        nbl, int(np.prod(d2.shape[1:])) * dt.itemsize)

    def group_maxima(j, maxima):
        # the last group starts early enough to be whole: the blocks it
        # shares with the one before read the same maxima again
        lo = jnp.minimum(j * group, nbl - group)
        mx = jax.vmap(lambda d2b, k: jnp.max(keys_of(d2b, k)))(
            jax.lax.dynamic_slice_in_dim(d2, lo, group, 0),
            jax.lax.dynamic_slice_in_dim(noise, lo, group, 0))
        return jax.lax.dynamic_update_slice_in_dim(maxima, mx, lo, 0)

    with jax.named_scope("kmpp_sample"):
        maxima = jax.lax.fori_loop(0, -(-nbl // group), group_maxima,
                                   jnp.full((nbl,), -jnp.inf, dt))
        rows = (Ws != 0).sum(dtype=jnp.int32)
    with jax.named_scope("kmpp_topk"):
        run, ranked = _topl_of_selected(
            lambda b: keys_of(_block_at(d2, b), _block_at(noise, b)),
            maxima, l)

    # candidate weights under the current nearest, the last round
    ids = jnp.arange(cap, dtype=jnp.int32)[:, None, None]

    def count(i, c):
        cnt = jnp.where(_block_at(nearest, i)[None] == ids,
                        _block_at(Ws, i)[None], 0).sum((1, 2))
        return _kahan_add(*c, cnt)

    zero = jnp.zeros((cap,), dt)
    with jax.named_scope("kmpp_weights"):
        counts = jax.lax.cond(
            last,
            lambda: jax.lax.fori_loop(0, nbl, count, (zero, zero))[0],
            lambda: zero)
    return run, ranked, rows, counts


def kmeans_parallel_init(X, k: int, seed: int = 0,
                         rounds: int = 5, oversample: Optional[int] = None,
                         env: Optional[MLEnvironment] = None,
                         sample_weight=None,
                         info: Optional[Dict] = None) -> np.ndarray:
    """K-MEANS|| distributed seeding (reference
    clustering/kmeans/KMeansInitCentroids.java; Bahmani et al. 2012) as a
    BSP program over the blocked table — no full-data host pass.

    Each superstep samples ``l = oversample`` new candidates with
    probability proportional to the current squared distance to the
    candidate set (the exactly-l Gumbel-top-l variant of the per-point
    Bernoulli draw). A worker keys its whole shard in one pass that keeps
    only the blocks' largest keys, keys the ``l`` blocks of the largest
    maxima again and folds them into its best ``l`` (``_kmpp_draw``: a key
    of any other block is at most its block's maximum, which is at most
    the smallest selected one, and of equal maxima the earlier block is
    selected as of equal keys the lower row is kept, so the result is a
    ``top_k`` over the whole shard); then an ``all_gather`` and a global
    ``top_k``. The per-row d2/nearest state updates block by block against
    only the l new candidates, so the work is O(rounds * n * l * d /
    workers). The Gumbel noise of a block is keyed by (seed, round, GLOBAL
    block index), so any worker count draws the same candidates. Candidate
    weights (summed row weights under the nearest candidate) are counted
    in the last round; the final weighted recluster to k runs on the
    O(rounds*l) candidate set on the host. ``info``, when given, receives
    the candidate set, its weights, the rows each round counted and the
    blocks each round ranked (``init_blocks_ranked``, also the counter
    ``alink_kmeans_init_blocks_ranked_total`` and the span argument
    ``blocks_ranked``): the selected blocks that held a finite key, at
    most ``l`` a worker a round whatever the table's order. (Until PR 36
    it read the blocks whose keys beat a running threshold, near the
    blocks walked on a table ordered by distance: a cost the draw no
    longer has.)
    """
    env_ = env or MLEnvironmentFactory.get_default()
    nw = env_.num_workers
    col = as_block_column(X, nw)
    n, d = col.n_rows, col.dim
    dt = col.blocks.dtype
    S = col.block_rows // LANES
    l = int(oversample) if oversample else max(2 * k, 1)
    cap = 1 + rounds * l
    rng = np.random.RandomState(seed)
    first = take_rows(col, [rng.randint(n)])[0].astype(dt)
    nbl = -(-col.row_blocks // nw)   # blocks a worker holds (static)
    path = fold_path(dt, S, l, d)

    def sample(ctx):
        Xs = ctx.get_obj("X")
        Ws = ctx.get_obj("w")
        step = ctx.step_no
        if ctx.is_init_step:
            cands = jnp.zeros((cap, d), dt).at[0].set(ctx.get_obj("first"))
            d2 = jnp.full(Ws.shape, jnp.inf, dt)
            nearest = jnp.zeros(Ws.shape, jnp.int32)
            rows_seen = ranked_seen = jnp.zeros((rounds,), jnp.int32)
            new, off = cands[:1], 0
        else:
            cands = ctx.get_obj("cands")
            d2 = ctx.get_obj("d2")
            nearest = ctx.get_obj("nearest")
            rows_seen = ctx.get_obj("rows")
            ranked_seen = ctx.get_obj("ranked")
            # fold in the l candidates written by the previous superstep
            off = 1 + (step - 2) * l
            new = jax.lax.dynamic_slice_in_dim(cands, off, l, 0)  # (l, d)
        key = jax.random.fold_in(
            jax.random.wrap_key_data(ctx.get_obj("key")), step)
        d2, nearest = _kmpp_fold(Xs, Ws, d2, nearest, new, off, path)
        (kv, blk, pos), ranked, rows, counts = _kmpp_draw(
            Ws, d2, nearest, key, ctx.task_id * nbl, step == rounds, cap, l)
        pts = _rows_at(Xs, blk, pos)                          # (l, d)
        gk = manifest_all_gather(kv, ctx.AXIS, name="kmpp_keys",
                                 num_workers=ctx.num_task)
        gp = manifest_all_gather(pts, ctx.AXIS, name="kmpp_cands",
                                 num_workers=ctx.num_task)
        gk = gk.reshape(-1)
        gp = gp.reshape(-1, d)
        gv, gi = jax.lax.top_k(gk, l)
        sel = jnp.where(jnp.isfinite(gv)[:, None], gp[gi], cands[0])
        off_w = 1 + (step - 1) * l
        cands = jax.lax.dynamic_update_slice_in_dim(cands, sel, off_w, 0)
        hi, lo = _split_count(rows)
        tot = ctx.all_reduce_sum(jnp.concatenate(
            [counts, jnp.stack([hi, lo, ranked]).astype(dt)]))
        upd = jax.lax.dynamic_update_index_in_dim
        ctx.put_obj("weights", tot[:cap])
        ctx.put_obj("rows", upd(
            rows_seen, _join_count(tot[cap], tot[cap + 1]), step - 1, 0))
        ctx.put_obj("ranked", upd(
            ranked_seen, tot[cap + 2].astype(jnp.int32), step - 1, 0))
        ctx.put_obj("cands", cands)
        ctx.put_obj("d2", d2)
        ctx.put_obj("nearest", nearest)

    with trace_span("kmeans.init", cat="kmeans", coarse=True,
                    args={"rows": n, "rounds": rounds, "fold": path}) as span:
        res = (IterativeComQueue(env=env_, max_iter=rounds)
               .init_with_partitioned_data("X", col.blocks)
               .init_with_partitioned_data(
                   "w", block_weights(col, sample_weight))
               .init_with_broadcast_data("first", first)
               .init_with_broadcast_data(
                   "key", np.asarray(jax.random.key_data(
                       jax.random.PRNGKey(seed))))
               .add(sample)
               .set_program_key((INIT_PROGRAM, cap, d, l, nbl, S, str(dt),
                                 path))
               .exec())
        cands, weights, rows_seen, ranked = (
            np.array(v) for v in res.get_all(
                ["cands", "weights", "rows", "ranked"]))
        span.set(blocks_ranked=int(ranked.sum()))
    _count(rows_seen.sum(dtype=np.int64), rounds, ranked.sum())
    if metrics_enabled():
        get_registry().inc("alink_kmeans_init_fold_blocks_total",
                           rounds * col.row_blocks, {"path": path})
    if info is not None:
        info.update(init_candidates=cands, init_weights=weights.copy(),
                    init_rows=rows_seen, init_blocks_ranked=ranked,
                    init_fold=path)
    # candidates sampled in the final round carry no counted weight yet;
    # give them each weight 1 so the recluster can still use them
    weights[weights == 0] = 1.0
    with trace_span("kmeans.recluster", cat="kmeans", coarse=True,
                    args={"candidates": int(cap)}):
        return _weighted_kmeans_pp(cands, weights, k, rng).astype(dt)


def _count(rows: int, supersteps: int, blocks_ranked: int = 0) -> None:
    if metrics_enabled():
        reg = get_registry()
        reg.inc("alink_kmeans_rows_total", int(rows))
        reg.inc("alink_kmeans_supersteps_total", int(supersteps))
        if blocks_ranked:
            reg.inc("alink_kmeans_init_blocks_ranked_total",
                    int(blocks_ranked))


# -- Lloyd ----------------------------------------------------------------------

def _lloyd_pass(Xs, Ws, C, distance_type: str):
    """One pass over a worker's shard: the ``(k + 2, d + 1)`` buffer the
    AllReduce sums. Rows ``:k`` hold ``sum w (x - c_j)`` and, last, the
    cluster's summed weight; row ``k`` the weighted inertia; row ``k + 1``
    the rows seen, split so a float psum keeps the count exact. Who walks
    the blocks is read from the input (``kernels.kmeans.lloyd_path``): the
    one streamed ``"kernel"``, or ``"xla"``, ``block_distances`` block by
    block."""
    nbl, d = Xs.shape[0], Xs.shape[1]
    k = C.shape[0]
    dt = Xs.dtype
    if lloyd_path(dt, Xs.shape[2], k, d, distance_type) == "kernel":
        acc, rows = lloyd_sums(Xs, Ws, C)
        return _with_rows(acc, rows)
    Cb = C[:, :, None, None]
    ids = jnp.arange(k, dtype=jnp.int32)[:, None, None]

    def body(i, c):
        acc, comp, rows = c
        xb, wb = _block_at(Xs, i), _block_at(Ws, i)
        with jax.named_scope("kmeans_assign"):
            D = block_distances(xb, C, distance_type)         # (k, S, 128)
            near = jnp.argmin(D, 0).astype(jnp.int32)
            dmin = jnp.min(D, 0)
        with jax.named_scope("kmeans_accumulate"):
            wk = jnp.where(near[None] == ids, wb[None], 0)    # (k, S, 128)
            sums = (wk[:, None] * (xb[None] - Cb)).sum((2, 3))    # (k, d)
            blk = jnp.concatenate([sums, wk.sum((1, 2))[:, None]], 1)
            tail = jnp.zeros((1, d + 1), dt).at[0, 0].set((dmin * wb).sum())
            acc, comp = _kahan_add(acc, comp, jnp.concatenate([blk, tail], 0))
        return acc, comp, rows + (wb != 0).sum(dtype=jnp.int32)

    zero = jnp.zeros((k + 1, d + 1), dt)
    acc, _, rows = jax.lax.fori_loop(
        0, nbl, body, (zero, zero, jnp.asarray(0, jnp.int32)))
    return _with_rows(acc, rows)


def _with_rows(acc, rows):
    """``_lloyd_pass``'s buffer from its sums ``(k + 1, d + 1)`` and the
    rows it saw."""
    hi, lo = _split_count(rows)
    tail = jnp.zeros((1, acc.shape[1]), acc.dtype).at[0, 0].set(hi) \
        .at[0, 1].set(lo)
    return jnp.concatenate([acc, tail], 0)


def _lloyd_update(buf, C):
    """From the all-reduced buffer of ``_lloyd_pass`` and the centroids it
    was made against: ``(new centroids, cluster weights, inertia,
    movement, rows seen)``. A cluster no row chose stays where it was."""
    k, d = C.shape
    sums, cnts = buf[:k, :d], buf[:k, d]
    newC = jnp.where(cnts[:, None] > 0,
                     C + sums / jnp.maximum(cnts[:, None], 1e-12), C)
    movement = jnp.sqrt(((newC - C) ** 2).sum(1)).max()
    return newC, cnts, buf[k, 0], movement, \
        _join_count(buf[k + 1, 0], buf[k + 1, 1])


def kmeans_train(X, k: int, max_iter: int = 50, tol: float = 1e-4,
                 distance_type: str = "EUCLIDEAN", init: str = "K_MEANS_PARALLEL",
                 seed: int = 0, env: Optional[MLEnvironment] = None,
                 sample_weight: Optional[np.ndarray] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 1, checkpoint_keep: int = 3,
                 resume_from: Optional[str] = None,
                 health=None, info: Optional[Dict] = None
                 ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Returns (centroids (k,d), cluster_weights (k,), num_steps).

    ``X`` is host rows ``(n, d)`` or a ``DenseBlockColumn`` (which may be
    device-resident and is then used where it lies). ``info``, when
    given, receives what a fit went through: the k-means|| candidates
    and their weights, the initial centroids, and per superstep the
    centroids, cluster weights, inertia (of the assignment that produced
    them) and rows seen.

    ``health=`` attaches a ``common.health.HealthMonitor`` fed the Lloyd
    loop's probe series (``inertia``, ``movement``, ``empty_clusters``)
    after the run and at every checkpoint boundary; probes record only
    while ``ALINK_TPU_HEALTH`` is on.

    ``checkpoint_dir=`` makes the Lloyd loop durable: the superstep carry
    (centroids, movement, step counter) is snapshotted every
    ``checkpoint_every`` supersteps outside the compiled program, and
    ``resume_from=`` re-enters a killed run with bitwise-identical final
    centroids (engine/recovery.py). The k-means|| init queue is NOT
    checkpointed — it is short and re-running it is cheaper than a
    snapshot per sampling round; exact resume still holds because the
    init is deterministic in ``seed``."""
    env_ = env or MLEnvironmentFactory.get_default()
    col = as_block_column(X, env_.num_workers)
    n, d = col.n_rows, col.dim
    dt = col.blocks.dtype
    weights = block_weights(col, sample_weight)
    init_u = init.upper()
    if init_u == "RANDOM":
        init_c = random_init(col, k, seed)
    elif init_u in ("K_MEANS_PARALLEL", "KMEANS_PARALLEL"):
        init_c = kmeans_parallel_init(col, k, seed=seed, env=env_,
                                      sample_weight=weights, info=info)
    else:  # K_MEANS_PLUS_PLUS / legacy host seeding on a bounded sample
        init_c = kmeans_plus_plus_init(col, k, seed)
    init_c = np.asarray(init_c, dt)
    path = lloyd_path(dt, col.block_rows // LANES, k, d, distance_type)

    def assign(ctx):
        if ctx.is_init_step:
            ctx.put_obj("centroids", ctx.get_obj("init_centroids"))
            ctx.put_obj("movement", jnp.asarray(jnp.inf, dt))
            ctx.put_obj("hist_centroids", jnp.zeros((max_iter, k, d), dt))
            ctx.put_obj("hist_weights", jnp.zeros((max_iter, k), dt))
            ctx.put_obj("hist_inertia", jnp.zeros((max_iter,), dt))
            ctx.put_obj("rows", jnp.zeros((max_iter,), jnp.int32))
        ctx.put_obj("buf", _lloyd_pass(
            ctx.get_obj("X"), ctx.get_obj("w"), ctx.get_obj("centroids"),
            distance_type))

    def update(ctx):
        newC, cnts, inertia, movement, rows = _lloyd_update(
            ctx.get_obj("buf"), ctx.get_obj("centroids"))
        at = ctx.step_no - 1
        # pre-update inertia: the objective of the assignment the
        # centroids being replaced produced (standard Lloyd bookkeeping)
        ctx.probe("inertia", inertia)
        ctx.put_obj("movement", movement)
        ctx.probe("movement", movement)
        ctx.probe("empty_clusters", (cnts <= 0).sum())
        ctx.put_obj("centroids", newC)
        ctx.put_obj("cluster_weights", cnts)
        upd = jax.lax.dynamic_update_index_in_dim
        for name, value in (("hist_centroids", newC), ("hist_weights", cnts),
                            ("hist_inertia", inertia), ("rows", rows)):
            ctx.put_obj(name, upd(ctx.get_obj(name), value, at, 0))

    queue = (IterativeComQueue(env=env_, max_iter=max_iter)
             .init_with_partitioned_data("X", col.blocks)
             .init_with_partitioned_data("w", weights)
             .init_with_broadcast_data("init_centroids", init_c)
             .add(assign)
             .add(AllReduce("buf"))
             .add(update)
             .set_compare_criterion(lambda ctx: ctx.get_obj("movement") < tol)
             .set_program_key((LLOYD_PROGRAM, k, d, distance_type, float(tol),
                               str(dt), path)))
    if checkpoint_dir:
        # knob validation (every/keep_last >= 1) lives in CheckpointConfig
        queue.set_checkpoint(checkpoint_dir, every=int(checkpoint_every),
                             keep_last=int(checkpoint_keep),
                             resume_from=resume_from)
    elif resume_from:
        raise ValueError("resume_from requires checkpoint_dir (an explicit "
                         "resume request must not silently retrain)")
    if health is not None:
        from ....common.health import warn_if_disabled
        warn_if_disabled("kmeans_train(health=...)", stacklevel=3)
        queue.set_health(health)
    with trace_span("kmeans.lloyd", cat="kmeans", coarse=True,
                    args={"rows": n, "k": int(k), "max_iter": int(max_iter),
                          "pass": path}):
        result = queue.exec()
        steps, rows_seen, cents, wts, hist_c, hist_w, hist_i = result.get_all(
            ["__step", "rows", "centroids", "cluster_weights",
             "hist_centroids", "hist_weights", "hist_inertia"])
        steps = int(steps)
    _count(rows_seen[:steps].sum(dtype=np.int64), steps)
    if metrics_enabled():
        get_registry().inc("alink_kmeans_lloyd_blocks_total",
                           steps * col.row_blocks, {"path": path})
    if info is not None:
        info.update(init_centroids=init_c, steps=steps, lloyd_pass=path,
                    rows=np.asarray(rows_seen[:steps]),
                    centroids=np.asarray(hist_c[:steps]),
                    weights=np.asarray(hist_w[:steps]),
                    inertia=np.asarray(hist_i[:steps]))
    return cents, wts, steps
