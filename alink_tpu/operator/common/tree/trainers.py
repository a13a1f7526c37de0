"""Distributed tree trainers on the BSP engine.

Re-design of:
  GBDT  — BaseGbdtTrainBatchOp.java:204-224 histogram boosting (one tree per
          superstep; histograms psum'd per level inside the stage)
  RF    — BaseRandomForestTrainBatchOp.java:152-163,264 (reference trains
          whole trees per worker; here trees are built histogram-parallel —
          same model class, bagging via per-tree weight masks + feature
          column subsampling)
  DecisionTree — RF with one tree, no subsampling.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ....common.columnar import (BYTE_BLOCK_QUANTUM, DenseBlockColumn,
                                  as_block_column, block_values,
                                  block_weights)
from ....common.metrics import get_registry, metrics_enabled
from ....common.mlenv import MLEnvironment, MLEnvironmentFactory
from ....common.tracing import trace_span
from ....engine import IterativeComQueue
from ....engine.communication import manifest_psum
from ..dataproc.quantile import (DEVICE_BINNING_MIN_CELLS, FINE_BINS,
                                 count_path)
from ..blocked import block_at as at, kahan_add
from .hist import (bin_blocks, bin_data, block_hist_path, build_tree,
                   build_tree_blocked, fused_hist_mode, gini_gain, gini_leaf,
                   level_columns, lookup, make_bin_edges, make_xgb_gain,
                   make_xgb_leaf, tree_apply_binned, variance_gain,
                   variance_leaf)


def _feature_subsample_mask(key, F: int, ratio: float, dtype):
    """Exactly ``max(1, round(ratio * F))`` features survive, chosen
    uniformly per tree. A Bernoulli-per-feature draw (the former
    implementation) selects ZERO features with probability (1-ratio)^F —
    on a 1-feature dataset at the default RF ratio that is a 30% chance
    per tree of a root-only stump (tier-1 regression: the seed-0 draw
    masked the only feature on every kept ensemble worker). Exact-count
    subsets are also the reference's featureSubsamplingRatio semantics
    (BaseRandomForestTrainBatchOp.java) and sklearn's ``max_features``."""
    kf = max(1, int(round(ratio * F)))
    u = jax.random.uniform(key, (F,))
    thr = jnp.sort(u)[kf - 1]
    return (u <= thr).astype(dtype)


@dataclass
class TreeTrainParams:
    num_trees: int = 100
    max_depth: int = 5
    # at most 256: ``gbdt_train`` holds a cell's bin in one byte (uint8,
    # ``hist.BIN_DTYPE``); the forests' host ``bin_data`` gives int32
    n_bins: int = 64
    learning_rate: float = 0.3         # gbdt shrinkage
    min_samples_leaf: int = 1
    reg_lambda: float = 1.0            # gbdt leaf regularization
    subsample_ratio: float = 1.0       # bagging row fraction
    feature_subsample_ratio: float = 1.0
    seed: int = 0


#: the engine names each compiled program ``jit_<first word of its key>``
GROW_PROGRAM = "gbdt_grow"
BIN_PROGRAM = "gbdt_bin"
EDGE_PROGRAM = "gbdt_edges"


def _bin_table(col: DenseBlockColumn, edges: np.ndarray, env_):
    """The table's bins on the device, uint8 (``hist.bin_blocks``), from
    one engine program over the blocks where they lie. The result stays
    where it is made and as the engine stacks it, ``(workers, blocks a
    worker, F, S, 128)``: the next queue partitions it on the worker axis
    as it is (merging the two leading axes would copy 1.5 GB)."""
    def stage(ctx):
        ctx.put_obj("bins", bin_blocks(ctx.get_obj("X"),
                                       ctx.get_obj("edges")))

    dt = col.blocks.dtype
    res = (IterativeComQueue(env=env_, max_iter=1)
           .init_with_partitioned_data("X", col.blocks)
           .init_with_broadcast_data("edges", np.asarray(edges, dt))
           .add(stage)
           .set_program_key((BIN_PROGRAM, col.dim, edges.shape[1],
                             col.block_rows, str(dt)))
           .exec())
    return res.device("bins")


def gbdt_train(X, y, p: TreeTrainParams,
               is_regression: bool, env: Optional[MLEnvironment] = None,
               sample_weight=None,
               cat_mask: Optional[np.ndarray] = None,
               info: Optional[Dict] = None):
    """Returns (features (T, 2^d-1), split_bins, split_masks
    (T, 2^d-1, n_bins), leaf_values (T, 2^d), edges, base_score,
    loss_curve, importance (F,)).

    ``X`` is host rows ``(n, F)`` or a ``DenseBlockColumn`` (which may be
    device-resident and is then used where it lies); ``y`` and
    ``sample_weight`` are host ``(n,)`` values, ``RowBlockColumn``s or
    arrays laid out as the table's rows are. ONE path: host rows are
    packed into blocks, then everything is blocked. The bins (uint8, 1
    byte a cell), the margins and the rows' nodes are per-row arrays on
    the device; a tree is a superstep of ``jit_gbdt_grow`` that walks the
    shard block by block once a level (``hist.build_tree_blocked``);
    gradients are recomputed from the margin a block and never stored.
    ``loss_curve[t]`` is the loss of the margins tree ``t`` was grown on.

    ``cat_mask``: (F,) bool — categorical columns (integer category codes)
    bin by identity and split on category subsets (hist.build_tree).
    ``info``, when given, receives what the fit went through: the
    histogram ``hist`` path (``"onehot"`` or ``"scatter"``), the node
    histograms a tree's block loops ``built`` and those it ``derived`` as
    ``parent - built`` (``hist_nodes``), the edges, every tree's node
    ``counts`` and the rows counted."""
    import dataclasses
    env_ = env or MLEnvironmentFactory.get_default()
    col = as_block_column(X, env_.num_workers, BYTE_BLOCK_QUANTUM)
    n, F = col.n_rows, col.dim
    path = block_hist_path()
    # the seed is data (the queue's "key"): the stage below closes over
    # the settings without it, so one program serves every seed
    seed, p = p.seed, dataclasses.replace(p, seed=0)
    with trace_span("gbdt.bin", cat="gbdt", coarse=True,
                    args={"rows": n, "bins": int(p.n_bins),
                          "path": count_path(FINE_BINS)}):
        # a blocked table's edges come from the device pass over the
        # blocks; host rows keep make_bin_edges' own cutover (np.quantile
        # while small, the same pass over the packed blocks when large)
        big = n * F >= DEVICE_BINNING_MIN_CELLS and cat_mask is None
        edges = make_bin_edges(
            col if big or isinstance(X, DenseBlockColumn) else X,
            p.n_bins, cat_mask, env=env_, program=EDGE_PROGRAM)
        # cut points the table's dtype holds, so that host and device
        # compare a value with the same number
        edges = np.asarray(edges, col.blocks.dtype).astype(np.float64)
        bins = _bin_table(col, edges, env_)
    yb = _as_f32(block_values(col, y))
    wb = _as_f32(block_weights(col, block_values(col, sample_weight)))
    base = float(_weighted_mean(yb, wb)) if is_regression else 0.0
    d, T = p.max_depth, p.num_trees
    queue = _grow_queue(env_, bins, yb, wb, base, seed, p, is_regression,
                        F, col.block_rows, path, cat_mask)
    with trace_span("gbdt.grow", cat="gbdt", coarse=True,
                    args={"trees": int(T), "depth": int(d), "hist": path,
                          "sibling": "subtract"}):
        res = queue.exec()
        tf, tb, tm, tv, curve, imp, counts = res.get_all(
            ["trees_f", "trees_b", "trees_m", "trees_v", "loss_curve",
             "importance", "counts"])
    rows = int(np.asarray(counts)[:, 0].sum(dtype=np.int64))
    # a tree's node histograms by how the builder came by them
    built, derived = (sum(v) for v in zip(*map(level_columns, range(d))))
    if metrics_enabled():
        reg = get_registry()
        reg.inc("alink_gbdt_rows_total", rows)
        reg.inc("alink_gbdt_trees_total", int(T))
        reg.inc("alink_gbdt_hist_nodes_total", built * T, {"how": "built"})
        reg.inc("alink_gbdt_hist_nodes_total", derived * T,
                {"how": "derived"})
    if info is not None:
        info.update(hist=path, hist_nodes={"built": built,
                                           "derived": derived},
                    edges=edges, counts=np.asarray(counts),
                    rows=rows, block_rows=col.block_rows)
    return (tf, tb, tm, tv, edges, base, np.asarray(curve), imp)


def _grow_queue(env_, bins, yb, wb, base: float, seed: int,
                p: TreeTrainParams, is_regression: bool, F: int,
                block_rows: int, path: str, cat_mask):
    """The engine program ``jit_gbdt_grow`` as a queue ready to run (or to
    lower from shapes): a tree a superstep over ``bins`` ``(workers, blocks
    a worker, F, S, 128)`` uint8 and the per-row ``yb``, ``wb`` ``(blocks,
    S, 128)`` float32, all partitioned on their leading axis."""
    dtype = np.float32
    d = p.max_depth
    T = p.num_trees
    gain_fn = make_xgb_gain(p.reg_lambda)
    leaf_fn = make_xgb_leaf(p.reg_lambda)
    n_internal, n_leaves = (1 << d) - 1, 1 << d
    upd = jax.lax.dynamic_update_index_in_dim

    def grow(ctx):
        bins_l = ctx.get_obj("bins")[0]       # this worker's, see _bin_table
        yl, wl = ctx.get_obj("y"), ctx.get_obj("w")
        nbl = bins_l.shape[0]
        if ctx.is_init_step:
            ctx.put_obj("F", jnp.zeros(yl.shape, dtype) + ctx.get_obj("base"))
            ctx.put_obj("node", jnp.zeros(yl.shape, jnp.int32))
            ctx.put_obj("leaf_v", jnp.zeros((n_leaves,), dtype))
            ctx.put_obj("trees_f", jnp.zeros((T, n_internal), jnp.int32))
            ctx.put_obj("trees_b", jnp.zeros((T, n_internal), jnp.int32))
            ctx.put_obj("trees_v", jnp.zeros((T, n_leaves), dtype))
            ctx.put_obj("trees_m", jnp.zeros((T, n_internal, p.n_bins), bool))
            ctx.put_obj("importance", jnp.zeros((F,), dtype))
            ctx.put_obj("loss_curve", jnp.zeros((T,), dtype))
            ctx.put_obj("counts",
                        jnp.zeros((T, n_internal + n_leaves), jnp.int32))
        node, leaf_v = ctx.get_obj("node"), ctx.get_obj("leaf_v")
        k_bag, k_feat = jax.random.split(jax.random.fold_in(
            jax.random.wrap_key_data(ctx.get_obj("key")), ctx.step_no))

        def fold(i, c):
            """The last tree into block ``i``'s margins, and their loss."""
            Fm, loss, lost, wsum, wlost = c
            fb = at(Fm, i) + p.learning_rate * lookup(leaf_v, at(node, i))
            yi, wi = at(yl, i), at(wl, i)
            if is_regression:
                li = 0.5 * (fb - yi) ** 2 * wi
            else:
                li = wi * (jnp.logaddexp(0.0, fb) - yi * fb)
            # lanes first, then sublanes: short sums whatever the backend
            loss, lost = kahan_add(loss, lost, li.sum(-1).sum())
            wsum, wlost = kahan_add(wsum, wlost, wi.sum(-1).sum())
            return upd(Fm, fb, i, 0), loss, lost, wsum, wlost

        zero = jnp.zeros((), dtype)
        with jax.named_scope("gbdt_grad"):
            Fm, loss, _, wsum, _ = jax.lax.fori_loop(
                0, nbl, fold, (ctx.get_obj("F"), zero, zero, zero, zero))
        block0 = ctx.task_id * nbl

        def stats_at(i):
            """(g, h, weight) of block ``i``, from its margins."""
            with jax.named_scope("gbdt_grad"):
                fb, yi, wi = at(Fm, i), at(yl, i), at(wl, i)
                if p.subsample_ratio < 1.0:          # bagging, per tree
                    wi = wi * jax.random.bernoulli(
                        jax.random.fold_in(k_bag, block0 + i),
                        p.subsample_ratio, wi.shape)
                if is_regression:
                    return jnp.stack([(fb - yi) * wi, wi, wi])
                prob = jax.nn.sigmoid(fb)
                return jnp.stack([(prob - yi) * wi,           # y in {0,1}
                                  jnp.maximum(prob * (1 - prob), 1e-6) * wi,
                                  wi])

        fmask = _feature_subsample_mask(
            k_feat, F, p.feature_subsample_ratio,
            dtype) if p.feature_subsample_ratio < 1.0 else None
        tf, tb, tm, tv, node, _, imp, counts = build_tree_blocked(
            bins_l, node, stats_at, d, p.n_bins, gain_fn, leaf_fn,
            min_samples_leaf=float(p.min_samples_leaf), feature_mask=fmask,
            axis_name="d", num_workers=ctx.num_task, cat_feats=cat_mask,
            cat_order_fn=lambda h_: jnp.where(
                h_[..., 1] > 0, h_[..., 0] / (h_[..., 1] + p.reg_lambda),
                jnp.inf), path=path)
        t = ctx.step_no - 1
        for name, value in (("trees_f", tf), ("trees_b", tb),
                            ("trees_v", tv.astype(dtype)), ("trees_m", tm),
                            ("counts", counts)):
            ctx.put_obj(name, upd(ctx.get_obj(name), value, t, 0))
        ctx.put_obj("importance", ctx.get_obj("importance") + imp)
        ctx.put_obj("F", Fm)
        ctx.put_obj("node", node)
        ctx.put_obj("leaf_v", tv.astype(dtype))
        lw = manifest_psum(jnp.stack([loss, wsum]), "d",
                           name="gbdt_loss", num_workers=ctx.num_task)
        ctx.put_obj("loss_curve", upd(
            ctx.get_obj("loss_curve"), lw[0] / jnp.maximum(lw[1], 1e-12),
            t, 0))

    from ....engine.comqueue import freeze_config
    return (IterativeComQueue(env=env_, max_iter=T)
            .init_with_partitioned_data("bins", bins)
            .init_with_partitioned_data("y", yb)
            .init_with_partitioned_data("w", wb)
            # the seed and the base score are data: one program for every
            # seed and every table of these shapes
            .init_with_broadcast_data("key", np.asarray(jax.random.key_data(
                jax.random.PRNGKey(seed))))
            .init_with_broadcast_data("base", np.asarray(base, dtype))
            .add(grow)
            .set_program_key((GROW_PROGRAM, is_regression, F,
                              block_rows, path,
                              freeze_config(p), freeze_config(cat_mask))))


def _as_f32(a):
    """Per-row blocks as float32, where they lie."""
    if isinstance(a, np.ndarray):
        return np.asarray(a, np.float32)
    return jnp.asarray(a, jnp.float32)


def _weighted_mean(yb, wb) -> float:
    return float((yb * wb).sum()) / max(float(wb.sum()), 1e-12)


def forest_train(X: np.ndarray, y_stats: np.ndarray, p: TreeTrainParams,
                 kind: str, env: Optional[MLEnvironment] = None,
                 cat_mask: Optional[np.ndarray] = None,
                 ensemble: Optional[bool] = None):
    """Random forest / decision tree. ``y_stats``: (n, m) per-sample stats —
    (onehot(y), 1) for classification (kind="gini") or (y, y^2, 1) for
    regression (kind="variance"). Returns (features, split_bins,
    split_masks, leaf_values (T, 2^d, ...), edges, importance (F,)).

    ``ensemble`` selects TRUE ensemble parallelism (reference
    BaseRandomForestTrainBatchOp.java:264 SeriesTrainFunction: every
    worker grows whole independent trees on its own data partition, no
    histogram allreduce): W trees materialize per superstep, so T trees
    cost ceil(T/W) supersteps. False grows one data-parallel tree per
    superstep with psum'd histograms (better per-tree quality, W-fold
    more supersteps). Default: ensemble when T > 1.
    """
    n, F = X.shape
    dtype = np.float32
    edges = make_bin_edges(X, p.n_bins, cat_mask, env=env)
    binned = bin_data(X, edges)
    d = p.max_depth
    T = p.num_trees
    m = y_stats.shape[1]
    gain_fn = gini_gain if kind == "gini" else variance_gain
    leaf_fn = gini_leaf if kind == "gini" else variance_leaf
    leaf_w = (m - 1) if kind == "gini" else 1
    n_internal, n_leaves = (1 << d) - 1, 1 << d
    env_ = env or MLEnvironmentFactory.get_default()
    W = env_.num_workers
    if ensemble is None:
        ensemble = T > 1
    if ensemble and W > 1:
        # ensemble trees see ONLY their worker's partition; contiguous
        # splits of an ordered dataset (e.g. sorted by label) would hand
        # each worker a biased — possibly single-class — slice. Shuffle
        # rows before partitioning, the analogue of the reference's
        # AvgPartition re-distribution (BaseRandomForestTrainBatchOp.java:350)
        perm = np.random.RandomState(p.seed).permutation(n)
        binned = binned[perm]
        y_stats = y_stats[perm]
    T_store = -(-T // W) if ensemble else T   # per-worker tree slots
    axis = None if ensemble else "d"

    def grow(ctx):
        if ctx.is_init_step:
            ctx.put_obj("trees_f", jnp.zeros((T_store, n_internal), jnp.int32))
            ctx.put_obj("trees_b", jnp.zeros((T_store, n_internal), jnp.int32))
            shape = ((T_store, n_leaves, leaf_w) if kind == "gini"
                     else (T_store, n_leaves))
            ctx.put_obj("trees_v", jnp.zeros(shape, dtype))
            ctx.put_obj("trees_m",
                        jnp.zeros((T_store, n_internal, p.n_bins), bool))
            ctx.put_obj("importance", jnp.zeros((F,), dtype))
        binned_l = ctx.get_obj("binned")
        stats = ctx.get_obj("stats")
        key = ctx.rng_key()      # per-worker, per-step: trees differ per worker
        if p.subsample_ratio < 1.0:
            bag = jax.random.bernoulli(key, p.subsample_ratio,
                                       (stats.shape[0],)).astype(dtype)
            stats = stats * bag[:, None]
        fmask = _feature_subsample_mask(
            jax.random.fold_in(key, 1), F, p.feature_subsample_ratio,
            dtype) if p.feature_subsample_ratio < 1.0 else None
        tf, tb, tm, tv, _, _, imp = build_tree(
            binned_l, stats, d, p.n_bins, gain_fn, leaf_fn,
            min_samples_leaf=float(p.min_samples_leaf), feature_mask=fmask,
            axis_name=axis, num_workers=ctx.num_task, cat_feats=cat_mask)
        t = ctx.step_no - 1
        ctx.put_obj("trees_f", jax.lax.dynamic_update_index_in_dim(
            ctx.get_obj("trees_f"), tf, t, 0))
        ctx.put_obj("trees_b", jax.lax.dynamic_update_index_in_dim(
            ctx.get_obj("trees_b"), tb, t, 0))
        ctx.put_obj("trees_v", jax.lax.dynamic_update_index_in_dim(
            ctx.get_obj("trees_v"), tv.astype(dtype), t, 0))
        ctx.put_obj("trees_m", jax.lax.dynamic_update_index_in_dim(
            ctx.get_obj("trees_m"), tm, t, 0))
        if ensemble:
            # surplus trees past T (T not a multiple of W) are trimmed from
            # the returned forest; keep their gains out of the importances
            kept = (t * W + ctx.task_id) < T
            imp = jnp.where(kept, imp, jnp.zeros_like(imp))
        ctx.put_obj("importance", ctx.get_obj("importance") + imp)

    from ....engine.comqueue import freeze_config
    queue = (IterativeComQueue(env=env_, max_iter=T_store, seed=p.seed)
             .init_with_partitioned_data("binned", binned)
             .init_with_partitioned_data("stats", y_stats.astype(dtype))
             .add(grow)
             .set_program_key(("forest", kind, F, m, bool(ensemble), T,
                               fused_hist_mode(),
                               freeze_config(p), freeze_config(cat_mask))))
    res = queue.exec()
    if not ensemble:
        return (res.get("trees_f"), res.get("trees_b"), res.get("trees_m"),
                res.get("trees_v"), edges, res.get("importance"))
    # ensemble: per-worker tree slices -> interleaved (T, ...) global forest
    # (superstep-major: tree s*W + w grew on worker w at superstep s+1)
    def gather(name):
        v = res.shards(name)                       # (W, T_store, ...)
        v = np.swapaxes(v, 0, 1).reshape((W * T_store,) + v.shape[2:])
        return v[:T]
    importance = res.shards("importance").sum(0)   # no psum ran: host-sum
    return (gather("trees_f"), gather("trees_b"), gather("trees_m"),
            gather("trees_v"), edges, importance)
