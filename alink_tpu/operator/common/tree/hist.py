"""Histogram-based tree building — TPU-native core.

Re-design of common/tree/ (36 files, 7,290 LoC) around one device kernel:
level-wise growth of a perfect binary tree over quantile-binned features.

reference mechanism (parallelcart/, SURVEY §2.3):
  ConstructLocalBin      -> per-worker histogram build (scatter-add here)
  AllReduce("gbdtBin")   -> lax.psum inside the stage
  CalBestSplit (sharded) -> full (node,feature,bin) gain tensor + argmax
                            on device (no DistributedInfo range sharding —
                            the MXU/VPU scans all of it at once)
  Split / UpdateTreeData -> node-id descent array update

Trees are dense arrays (perfect binary tree of ``max_depth``): unsplit nodes
store feature = -1 and route everything left, so shapes stay static for XLA.
Generic over a per-sample stat vector (SURVEY §7: "tree structure on host,
bin statistics on device"):
  regression  stats (y, y^2, 1)      variance gain
  classify    stats (onehot(y), 1)   gini gain
  gbdt        stats (g, h, 1)        xgboost-style gain g^2/(h+lambda)
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# host-side quantile binning
# ---------------------------------------------------------------------------

from ....common.columnar import LANES, DenseBlockColumn
from ....engine.communication import manifest_psum
from ..blocked import block_at, join_count, kahan_add, split_count
from ..dataproc.quantile import DEVICE_BINNING_MIN_CELLS as _DEVICE_BINNING_MIN_CELLS


def make_bin_edges(X, n_bins: int,
                   cat_mask: Optional[np.ndarray] = None,
                   device: Optional[bool] = None, env=None,
                   program: str = "quantile_hist") -> np.ndarray:
    """(F, n_bins-1) per-feature quantile cut points (padded with +inf).
    ``X`` is host rows ``(n, F)`` or a ``DenseBlockColumn`` (read where it
    lies, on the device if it is there).

    Categorical features (``cat_mask[f]`` True; values must be integer
    category codes) get identity edges 0.5, 1.5, ... so every category is
    its own bin — no quantile artifacts (reference
    seriestree/CategoricalSplitter.java treats categories as unordered).

    ``device=None`` auto-selects the distributed histogram-quantile pass
    (dataproc/quantile.py, the SortUtils.pSort analogue) for a blocked
    table and once n*F is large enough that per-column host
    ``np.quantile`` would dominate; True/False force it. ``program``
    names that pass's engine program.
    """
    blocked = isinstance(X, DenseBlockColumn)
    n, F = (X.n_rows, X.dim) if blocked else X.shape
    if blocked and cat_mask is not None and np.any(cat_mask):
        raise ValueError("categorical columns need host rows (a blocked "
                         "vector column has no column identity)")
    edges = np.full((F, n_bins - 1), np.inf)
    if device is None:
        device = blocked or n * F >= _DEVICE_BINNING_MIN_CELLS
    if blocked and not device:
        X, blocked = X.to_rows(), False
    cont = ([f for f in range(F) if not cat_mask[f]]
            if cat_mask is not None else list(range(F)))
    probs = np.linspace(0, 1, n_bins + 1)[1:-1]
    if device and cont:
        from ..dataproc.quantile import distributed_quantiles
        qs_all = distributed_quantiles(
            X if blocked else np.ascontiguousarray(X[:, cont]), probs,
            env=env, program=program)
    for pos, f in enumerate(cont):
        if device:
            qs = qs_all[pos]
        else:
            v = X[:, f]
            v = v[~np.isnan(v)]   # match the device path's per-column NaN
            qs = np.quantile(v, probs) if v.size else np.array([])
        uq = np.unique(qs)
        uq = uq[np.isfinite(uq)]
        edges[f, :len(uq)] = uq
    if cat_mask is not None:
        for f in range(F):
            if cat_mask[f]:
                arity = min(int(X[:, f].max()) + 1, n_bins)
                edges[f, :max(arity - 1, 0)] = (
                    np.arange(max(arity - 1, 0)) + 0.5)
    return edges


def bin_data(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """(n, F) int32 bin ids in [0, n_bins): the number of a feature's
    finite edges at or below the value (host rows; the blocked trainers'
    :func:`bin_blocks` gives the same ids as uint8, on the device)."""
    n, F = X.shape
    out = np.empty((n, F), np.int32)
    for f in range(F):
        e = edges[f]
        out[:, f] = np.searchsorted(e[np.isfinite(e)], X[:, f], side="right")
    return out


#: the dtype of the blocked trainers' bins: ``max_bins`` is at most 256
BIN_DTYPE = jnp.uint8


def bin_blocks(Xs, edges):
    """Bin a worker's shard ``(blocks, F, S, 128)`` against ``edges`` ``(F,
    n_bins - 1)`` (``+inf`` where a feature has fewer) into ``uint8`` of the
    same layout, block by block: a value's bin is the number of its
    feature's finite edges at or below it, as :func:`bin_data` counts, and
    a NaN takes the bin past the last finite edge, where ``searchsorted``
    puts it. Traceable."""
    edges = edges.astype(Xs.dtype)
    last = jnp.isfinite(edges).sum(1).astype(jnp.int32)[:, None, None]

    def one(xb):
        with jax.named_scope("gbdt_bin"):
            at = (xb[:, None] >= edges[:, :, None, None]).sum(
                1, dtype=jnp.int32)
            return jnp.where(jnp.isnan(xb), last, at).astype(BIN_DTYPE)
    return jax.lax.map(one, Xs)


# ---------------------------------------------------------------------------
# gain / leaf functions over cumulated stat histograms
# ---------------------------------------------------------------------------

def variance_gain(left, right, total, min_leaf):
    """stats = (sum_y, sum_y2, count): SSE reduction."""
    def sse(s):
        return s[..., 1] - s[..., 0] ** 2 / jnp.maximum(s[..., 2], 1e-12)
    ok = (left[..., 2] >= min_leaf) & (right[..., 2] >= min_leaf)
    g = sse(total) - sse(left) - sse(right)
    return jnp.where(ok, g, -jnp.inf)


def variance_leaf(stats):
    return stats[..., 0] / jnp.maximum(stats[..., 2], 1e-12)


def gini_gain(left, right, total, min_leaf):
    """stats = (c_0..c_{k-1}, count): weighted gini impurity decrease."""
    def imp(s):
        cnt = jnp.maximum(s[..., -1], 1e-12)
        return cnt - (s[..., :-1] ** 2).sum(-1) / cnt
    ok = (left[..., -1] >= min_leaf) & (right[..., -1] >= min_leaf)
    g = imp(total) - imp(left) - imp(right)
    return jnp.where(ok, g, -jnp.inf)


def gini_leaf(stats):
    return stats[..., :-1] / jnp.maximum(stats[..., -1:], 1e-12)


def make_xgb_gain(reg_lambda: float):
    def xgb_gain(left, right, total, min_leaf):
        """stats = (g, h, count)."""
        def score(s):
            return s[..., 0] ** 2 / (s[..., 1] + reg_lambda)
        ok = (left[..., 2] >= min_leaf) & (right[..., 2] >= min_leaf)
        g = 0.5 * (score(left) + score(right) - score(total))
        return jnp.where(ok, g, -jnp.inf)
    return xgb_gain


def make_xgb_leaf(reg_lambda: float):
    def xgb_leaf(stats):
        return -stats[..., 0] / (stats[..., 1] + reg_lambda)
    return xgb_leaf


# ---------------------------------------------------------------------------
# the level-wise builder (traceable; runs inside shard_map stages)
# ---------------------------------------------------------------------------

def level_hist(binned, stats, node_id, n_nodes: int, n_bins: int,
               use_onehot: bool, onehot_dtype=None, pre=None):
    """(n_nodes, F, n_bins, m) per-(node,feature,bin) stat sums for one level.

    ``use_onehot`` selects a one-hot MXU einsum instead of scatter-add —
    XLA serializes random scatter on TPU (~2.5x slower than the einsum at
    64 nodes); on CPU the scatter is the fast path.

    ``pre`` (fused path): the level-invariant ``(ohB, s2)`` operands from
    :func:`_fused_hist_precompute`, hoisted out of the level loop — ONE
    implementation of the compensated-split einsum serves both the
    default and the fused kernels (with ``pre=None`` the primitive
    sequence is exactly the pre-fused one, preserving the byte-identical
    flag-off HLO contract)."""
    import jax.numpy as jnp
    n, F = binned.shape
    m = stats.shape[1]
    dt = stats.dtype
    if use_onehot or pre is not None:
        hdt = (pre[0].dtype if pre is not None
               else (onehot_dtype or jnp.bfloat16))
        ohN = (node_id[:, None] == jnp.arange(n_nodes)[None, :]).astype(hdt)
        ohB, s2 = (pre if pre is not None
                   else _fused_hist_precompute(binned, stats, n_bins,
                                               onehot_dtype))
        # contract (node-one-hot x stats) FIRST: the (i, n_nodes, 2m)
        # intermediate is ~KBs/sample, where the old explicit
        # ohB[..., None] * s2 product materialized an (i, F, bins, 2m)
        # tensor (~0.5 GB at adult scale) every level
        h2 = jnp.einsum("in,iM,ifb->nfbM", ohN, s2, ohB,
                        preferred_element_type=jnp.float32)
        return (h2[..., :m] + h2[..., m:]).astype(dt)
    flat_idx = (node_id[:, None] * F + jnp.arange(F)[None, :]) * n_bins + binned
    hist = jnp.zeros((n_nodes * F * n_bins, m), dt)
    hist = hist.at[flat_idx.reshape(-1)].add(jnp.repeat(stats, F, axis=0))
    return hist.reshape(n_nodes, F, n_bins, m)


# ---------------------------------------------------------------------------
# fused histogram kernels (ALINK_TPU_FUSED_HIST) — ISSUE 6 tentpole (b)
# ---------------------------------------------------------------------------
#
# The default per-level formulation rebuilds the bin one-hot AND the
# compensated hi/lo stat split EVERY level even though both are
# level-invariant within one tree, and on non-TPU backends it falls back
# to a scatter-add that materializes an (n*F, m) jnp.repeat of the stats.
# The fused kernel hoists the level-invariant operands out of the level
# loop and reduces each level to ONE batched contraction
# (gradient+hessian+count together, all nodes x features x bins at once):
#
#   "xla"    — precompute ohB (n, F, B) + s2 (n, 2m) once per tree; per
#              level a single einsum "in,iM,ifb->nfbM" (two MXU dots, no
#              giant intermediate) on every backend.
#   "pallas" — a hand-written accumulation kernel: grid over
#              (feature, row-block), each step one-hots the COMBINED
#              (node, bin) id in VMEM and accumulates a (B_blk, Q)^T @
#              (B_blk, m) dot into the output block — exact f32
#              accumulation, no hi/lo split, no HBM one-hot
#              materialization. Gated on backend availability (TPU, or
#              interpret mode for tests); off-TPU it demotes to "xla"
#              with a one-time warning when lowering fails, on TPU a
#              failed lowering raises (kernels/runtime.refuse_on_tpu).
#
# What the chip showed (PR 31, one v5e): both forms take the row-major
# ``(n, F)`` int32 bins and ``(n, m)`` stats of ``build_tree``, and at the
# airline table's size (115 million rows x 13, 32 nodes) the chip's
# compiler REFUSES both for memory ("xla" asks for 18.85 GB of
# temporaries, "pallas" for a lane-padded 58.9 GB copy of the ``(n, 13)``
# int32 bins); neither has been timed at any size. The forests are what still reaches them; ``gbdt_train``
# grows on ``build_tree_blocked`` below, which the flag does not touch.
# Their deletion is a ``simplicity`` PR's (ROADMAP D1).
#
# The mode is resolved at TRACE time and folded into the engine
# program-cache key by the forest trainer, so toggling recompiles instead
# of serving a stale program. With the flag off, build_tree executes the
# pre-existing statements unchanged — the lowered HLO is byte-identical
# to pre-flag programs (pinned by tests/test_perf_kernels.py) and the
# collective set (one psum per level, after the histogram) is identical
# in every mode.

FUSED_HIST_ENV = "ALINK_TPU_FUSED_HIST"
_PALLAS_WARNED = [False]


def fused_hist_mode() -> str:
    """Resolved fused-histogram mode: "off" (default) | "xla" | "pallas".

    ``ALINK_TPU_FUSED_HIST`` values: 0/off/false -> "off"; "pallas" ->
    the Pallas kernel when the backend can run it (TPU, or any backend
    with ``ALINK_TPU_PALLAS_INTERPRET=1``), else "xla"; anything truthy
    else -> "xla". The raw value parses through the flag registry
    (common/flags.py — which also declares the program-cache-key fold);
    only the backend gating lives here. The RESOLVED mode is what the
    tree trainers fold into their program keys, so the interpret flag
    needs no fold of its own. The availability check is the kernel
    tier's shared one (``kernels/runtime.pallas_available`` — the
    ISSUE 13 dedupe of the contract this kernel pioneered)."""
    from ....common.flags import flag_value
    from ....kernels.runtime import pallas_available
    v = flag_value(FUSED_HIST_ENV)
    if v == "pallas" and not pallas_available():
        return "xla"
    return v


def _fused_hist_precompute(binned, stats, n_bins: int, onehot_dtype=None):
    """The one-hot-path operands of :func:`level_hist` that are
    level-invariant within one tree (the fused kernel builds them once;
    the default kernel calls this per level — ONE implementation).

    Compensated bf16 split of the stats (:func:`split_hi_lo`): hi + lo
    reconstructs f32 to ~2^-16 relative, so the bf16 MXU path does not
    quantize grad/hess per element (~0.4%) and near-tie splits agree
    with the exact CPU scatter. One einsum over the stacked (hi|lo) stats downstream,
    halves summed in f32 after."""
    hdt = onehot_dtype or jnp.bfloat16
    ohB = (binned[..., None] == jnp.arange(n_bins)[None, None, :]).astype(hdt)
    s32 = stats.astype(jnp.float32)
    if hdt == jnp.bfloat16:
        s_hi, s_lo = split_hi_lo(s32)
    else:
        s_hi = s32.astype(hdt)
        s_lo = (s32 - s_hi.astype(jnp.float32)).astype(hdt)
    s2 = jnp.concatenate([s_hi, s_lo], axis=1)               # (n, 2m)
    return ohB, s2


def split_hi_lo(s32):
    """float32 ``s32`` as a bfloat16 pair ``(hi, lo)`` with ``hi + lo``
    equal to it to 2^-16: ``hi`` is the value with its low 16 bits
    CLEARED (a mask on the bits), ``lo`` the rest, rounded. Not ``hi =
    round(s)``: XLA:TPU folds the float32 -> bfloat16 -> float32 round
    trip that form subtracts away, which leaves ``lo = 0`` and a plain
    bfloat16 histogram (found by the ``gbdt-fit`` cell's ``correct`` on
    its first full-size run, and in a one-block micro-run: the rounding
    form reads exactly what ``hi`` alone reads, worst cell 1.6e-2 of a
    gradient sum of ~8; this form 5.4e-5, what a three-pass float32
    product gives; PERF.md PR 31)."""
    bits = jax.lax.bitcast_convert_type(s32, jnp.uint32) \
        & jnp.uint32(0xFFFF0000)
    hi = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return hi.astype(jnp.bfloat16), (s32 - hi).astype(jnp.bfloat16)


def _pallas_level_hist(binned, stats, node_id, n_nodes: int, n_bins: int):
    """Hand-written histogram accumulation kernel (tentpole (b) Pallas
    path): grid (feature, row-block); each step builds the combined
    (node, bin) one-hot for its rows IN VMEM and accumulates one
    ``(Q, blk) @ (blk, m)`` dot into its feature's output block. Exact
    f32 accumulation (no bf16 quantization, no hi/lo split); the only
    HBM traffic is the binned rows, the stats, and the output —
    the one-hot never materializes outside VMEM. Compiles on a v5e at
    the adult shape and agrees with :func:`level_hist` there (PR 21 chip
    run); never timed. It contracts ``(Q, blk) @ (blk, m)`` with m = 3 of
    the MXU's 128 columns at ``HIGHEST`` and reads every row block once a
    FEATURE; at 115 million rows its row-major inputs alone do not fit
    the chip and the compiler refuses it (PR 31 chip run)."""
    from jax.experimental import pallas as pl

    n, F = binned.shape
    m = stats.shape[1]
    Q = n_nodes * n_bins
    blk = min(512, max(8, n))
    npad = -(-n // blk) * blk
    if npad != n:                      # zero-stat rows are inert
        pz = npad - n
        binned = jnp.concatenate([binned, jnp.zeros((pz, F), binned.dtype)])
        node_id = jnp.concatenate([node_id, jnp.zeros((pz,), node_id.dtype)])
        stats = jnp.concatenate(
            [stats, jnp.zeros((pz, m), stats.dtype)])
    s32 = stats.astype(jnp.float32)
    nid2 = node_id[:, None].astype(jnp.int32)               # (n, 1)

    def kernel(b_ref, nid_ref, s_ref, out_ref):
        f = pl.program_id(0)
        r = pl.program_id(1)

        @pl.when(r == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        # the block carries the WHOLE feature axis and this grid step's
        # column is selected in-kernel: a (blk, 1) column block of a
        # (n, F) array is not lane-aligned, and Mosaic refuses it. The
        # price is that each row block is read once per feature.
        rows = b_ref[...].astype(jnp.int32)                 # (blk, F)
        col = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
        b = jnp.sum(jnp.where(col == f, rows, 0), axis=1,
                    keepdims=True)                          # (blk, 1)
        q = nid_ref[...] * n_bins + b                       # combined id
        oh = (q == jax.lax.broadcasted_iota(jnp.int32, (blk, Q), 1)
              ).astype(jnp.float32)                         # (blk, Q)
        # contract the row axis; HIGHEST keeps the f32 stats exact on
        # the MXU (the default precision would round them to bf16)
        acc = jax.lax.dot_general(
            oh, s_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)            # (Q, m)
        out_ref[...] += acc[None]

    from ....kernels.runtime import interpret_mode
    out = pl.pallas_call(
        kernel,
        grid=(F, npad // blk),
        in_specs=[pl.BlockSpec((blk, F), lambda f, r: (r, 0)),
                  pl.BlockSpec((blk, 1), lambda f, r: (r, 0)),
                  pl.BlockSpec((blk, m), lambda f, r: (r, 0))],
        out_specs=pl.BlockSpec((1, Q, m), lambda f, r: (f, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((F, Q, m), jnp.float32),
        interpret=interpret_mode(),
    )(binned, nid2, s32)
    return out.reshape(F, n_nodes, n_bins, m).transpose(
        1, 0, 2, 3).astype(stats.dtype)


_PALLAS_PROBED: dict = {}      # (n_nodes, n_bins, m) -> bool (compiled ok)


def _pallas_probe(n_nodes: int, n_bins: int, m: int) -> bool:
    """EAGERLY compile+run the Pallas kernel at this level's shape class
    (tiny row count, one feature) before tracing it into the engine
    program. ``pl.pallas_call`` only stages the primitive at trace time —
    a Mosaic/interpreter failure would otherwise surface at
    ``queue.exec()``'s compile, OUTSIDE any try/except around the traced
    call — so the probe is what catches compile-time failures (VMEM
    overflow at deep levels, lane-alignment rejections), not just
    trace-time ones. One probe per shape class per process; off-TPU a
    probe failure demotes with the one-time warning, on TPU it raises."""
    key = (n_nodes, n_bins, m)
    ok = _PALLAS_PROBED.get(key)
    if ok is None:
        def probe():
            out = _pallas_level_hist(
                np.zeros((8, 1), np.int32), np.zeros((8, m), np.float32),
                np.zeros((8,), np.int32), n_nodes, n_bins)
            np.asarray(out)              # force the eager compile+run
        from ....kernels.runtime import (demote_once, refuse_on_tpu,
                                         run_eagerly)
        try:
            # run_eagerly (kernels/runtime.py): the dispatch call site
            # sits inside the engine's shard_map/jit trace, where even
            # concrete-input pallas_calls bind into the trace as
            # tracers; a fresh thread is a genuinely eager context, so
            # the probe really compiles+runs the kernel here and now.
            run_eagerly(probe)
            ok = True
        except Exception as e:  # pragma: no cover - backend-specific
            refuse_on_tpu("fused_hist", f"probe at level shape {key}", e)
            ok = False
            demote_once(
                "fused_hist", "probe-failed", gate=_PALLAS_WARNED,
                message=f"ALINK_TPU_FUSED_HIST=pallas failed to compile "
                        f"at level shape (n_nodes={n_nodes}, "
                        f"n_bins={n_bins}, m={m}) "
                        f"({type(e).__name__}: {e}); demoting to the "
                        f"fused XLA formulation")
        _PALLAS_PROBED[key] = ok
    return ok


def _hist_dispatch(hist_mode, pre, binned, stats, node_id, n_nodes, n_bins):
    """Per-level histogram under the resolved mode. Kept OUT of
    :func:`build_tree`'s flag-off path: with the flag off the original
    :func:`level_hist` call is executed verbatim (byte-identical HLO)."""
    if hist_mode == "pallas" and _pallas_probe(n_nodes, n_bins,
                                               stats.shape[1]):
        try:
            return _pallas_level_hist(binned, stats, node_id, n_nodes,
                                      n_bins)
        except Exception as e:  # pragma: no cover - backend-specific
            from ....kernels.runtime import demote_once, refuse_on_tpu
            refuse_on_tpu("fused_hist", "trace", e)
            demote_once(
                "fused_hist", "trace-failed", gate=_PALLAS_WARNED,
                message=f"ALINK_TPU_FUSED_HIST=pallas failed to trace "
                        f"({type(e).__name__}: {e}); demoting to the "
                        f"fused XLA formulation")
    return level_hist(binned, stats, node_id, n_nodes, n_bins,
                      use_onehot=True, pre=pre)


def _default_cat_order(hist):
    """Per-(node,feature,bin) ordering score for categorical subset splits:
    first-stat / count ratio — g/h-style mean response. Exact (Fisher) for
    regression and binary targets; a standard heuristic for multiclass.
    Empty bins sort last so unseen categories route right."""
    cnt = hist[..., -1]
    r = hist[..., 0] / jnp.maximum(cnt, 1e-12)
    return jnp.where(cnt > 0, r, jnp.inf)


def _cat_columns(cat_feats, F: int, cat_order_fn=None):
    """What :func:`best_splits` needs of the categorical columns (static
    column selection), or ``None`` where there is none."""
    if cat_feats is None:
        return None
    cat_np = np.asarray(cat_feats, bool)
    if not cat_np.any():
        return None
    cat_idx = np.flatnonzero(cat_np)
    cat_pos = np.zeros(F, np.int32)        # F-index -> cat-slice index
    cat_pos[cat_idx] = np.arange(len(cat_idx), dtype=np.int32)
    return (cat_idx, jnp.asarray(cat_pos), jnp.asarray(cat_np),
            cat_order_fn or _default_cat_order)


def best_splits(hist, n_bins: int, gain_fn, min_samples_leaf, min_gain,
                feature_mask=None, cat=None):
    """The split search of one level over its (all-reduced) histogram
    ``(n_nodes, F, n_bins, m)``: per node ``(feature or -1, split bin,
    LEFT-membership mask over the bins, gain counted to the feature's
    importance)``. A few KB of work whatever the table's size."""
    n_nodes, F = hist.shape[0], hist.shape[1]
    bins_ar = jnp.arange(n_bins)
    cum = jnp.cumsum(hist, axis=2)
    total = cum[:, :, -1:, :]
    left = cum[:, :, :-1, :]                      # split "bin <= b"
    right = total - left
    gains = gain_fn(left, right, total, min_samples_leaf)  # (nodes,F,B-1)
    if cat is not None:
        cat_idx, cat_pos, cat_arr, cat_order_fn = cat
        # sorted-by-score cumulation over ONLY the categorical columns
        # (static gather — continuous features skip the second pass):
        # cut position c sends the first c+1 bins (in score order) left
        hist_c = hist[:, cat_idx]                          # (nodes,Fc,B,m)
        total_c = total[:, cat_idx]
        order = jnp.argsort(cat_order_fn(hist_c), axis=2)  # (nodes,Fc,B)
        shist = jnp.take_along_axis(hist_c, order[..., None], 2)
        scum = jnp.cumsum(shist, axis=2)
        sleft = scum[:, :, :-1, :]
        sright = total_c - sleft
        sgains = gain_fn(sleft, sright, total_c, min_samples_leaf)
        gains = gains.at[:, cat_idx].set(sgains)
        # rank[bin] = position of bin in score order
        rank_c = jnp.argsort(order, axis=2)                # (nodes,Fc,B)
    if feature_mask is not None:
        gains = jnp.where(feature_mask[None, :, None] > 0, gains, -jnp.inf)
    flat_g = gains.reshape(n_nodes, F * (n_bins - 1))
    best = jnp.argmax(flat_g, axis=1)
    best_gain = jnp.take_along_axis(flat_g, best[:, None], 1)[:, 0]
    best_f = (best // (n_bins - 1)).astype(jnp.int32)
    best_b = (best % (n_bins - 1)).astype(jnp.int32)
    split = best_gain > min_gain
    # LEFT-membership mask per node over bins
    if cat is not None:
        brank = jnp.take_along_axis(
            rank_c, cat_pos[best_f][:, None, None], 1)[:, 0, :]  # (nodes,B)
        is_cat = cat_arr[best_f]
        pos = jnp.where(is_cat[:, None], brank, bins_ar[None, :])
    else:
        pos = jnp.broadcast_to(bins_ar[None, :], (n_nodes, n_bins))
    mask = pos <= best_b[:, None]                          # (nodes, B)
    return (jnp.where(split, best_f, -1), jnp.where(split, best_b, 0),
            mask & split[:, None],
            jnp.where(split, best_gain, jnp.zeros_like(best_gain)))


def build_tree(binned, stats, max_depth: int, n_bins: int,
               gain_fn, leaf_fn, min_samples_leaf: float = 1.0,
               min_gain: float = 1e-9, feature_mask=None, axis_name=None,
               cat_feats=None, cat_order_fn=None, num_workers: int = 1):
    """Grow one tree; returns
    (features, split_bins, split_masks, leaf_values, node_id, leaf_hist,
     importance).

    binned: (n, F) int32; stats: (n, m) — zero rows are inert (padding /
    bagging handled by zeroing stats); feature_mask: (F,) 1/0 per-tree
    column subsample; axis_name: psum histograms across this mesh axis;
    cat_feats: (F,) bool — categorical features split on category
    *subsets* (bins sorted by ``cat_order_fn`` score, then cut like a
    threshold — the classical exact reduction, reference
    seriestree/CategoricalSplitter.java) instead of bin order.

    features/split_bins: (2^max_depth - 1,) level-order;
    split_masks: (2^max_depth - 1, n_bins) bool — per-node LEFT membership
    by bin (continuous nodes encode ``bin <= split_bin``), the single
    descent rule for both feature kinds; leaf_values: (2^max_depth, ...)
    from leaf_fn; node_id: (n,) final leaf; importance: (F,) summed split
    gain per feature (psum'd histograms make it identical on every worker).
    """
    n, F = binned.shape
    m = stats.shape[1]
    dt = stats.dtype
    node_id = jnp.zeros(n, jnp.int32)
    feats_out, bins_out, masks_out = [], [], []
    importance = jnp.zeros((F,), dt)
    cat = _cat_columns(cat_feats, F, cat_order_fn)

    use_onehot = jax.default_backend() == "tpu"
    # ALINK_TPU_FUSED_HIST: resolved at trace time, folded into the
    # trainers' program-cache key. "off" executes the original
    # level_hist call verbatim (lowered HLO byte-identical to pre-flag
    # programs); the psum placement below is shared by every mode, so
    # the collective set never changes.
    hist_mode = fused_hist_mode()
    pre = (_fused_hist_precompute(binned, stats, n_bins)
           if hist_mode != "off" else None)
    for level in range(max_depth):
        n_nodes = 1 << level
        if hist_mode != "off":
            hist = _hist_dispatch(hist_mode, pre, binned, stats, node_id,
                                  n_nodes, n_bins)
        else:
            hist = level_hist(binned, stats, node_id, n_nodes, n_bins,
                              use_onehot)
        if axis_name is not None:
            # asarray materializes immediately: the per-level histogram
            # psums are dependency-ordered (level L's node assignment
            # needs level L-1's split), so there is nothing to fuse with
            hist = jnp.asarray(manifest_psum(hist, axis_name,
                                             name="tree_hist",
                                             num_workers=num_workers))
        feat, sbin, mask, gain = best_splits(
            hist, n_bins, gain_fn, min_samples_leaf, min_gain, feature_mask,
            cat)
        feats_out.append(feat)
        bins_out.append(sbin)
        masks_out.append(mask)
        importance = importance.at[jnp.maximum(feat, 0)].add(gain)
        # descend: right iff split and sample's bin is not in the left set
        nf = feats_out[-1][node_id]
        sample_bin = jnp.take_along_axis(binned, jnp.maximum(nf, 0)[:, None], 1)[:, 0]
        in_left = masks_out[-1][node_id, sample_bin]
        go_right = (nf >= 0) & jnp.logical_not(in_left)
        node_id = node_id * 2 + go_right.astype(jnp.int32)

    n_leaves = 1 << max_depth
    leaf_hist = jnp.zeros((n_leaves, m), dt).at[node_id].add(stats)
    if axis_name is not None:
        leaf_hist = jnp.asarray(manifest_psum(leaf_hist, axis_name,
                                              name="tree_leaf_hist",
                                              num_workers=num_workers))
    features = jnp.concatenate(feats_out)
    split_bins = jnp.concatenate(bins_out)
    split_masks = jnp.concatenate(masks_out, axis=0)
    return (features, split_bins, split_masks, leaf_fn(leaf_hist), node_id,
            leaf_hist, importance)


# ---------------------------------------------------------------------------
# the blocked builder: a table of deployment size, where it lies
# ---------------------------------------------------------------------------
#
# The bins are a uint8 table ``(blocks, F, S, 128)`` laid out as the raw
# ``DenseBlockColumn`` is, node ids and per-row stats ``(blocks, S, 128)``.
# A level walks the worker's shard block by block (``lax.fori_loop``): the
# rows descend by the level before, the block's histogram is ONE product
# on the MXU, ``onehot(bin)^T (F * n_bins, rows)`` against ``(column
# one-hot x stats) (columns * 2m, rows)``, whose bin one-hot XLA fuses
# into the product's operand (it is never written to memory: compiled and
# timed on a v5e, PERF.md PR 31), and the block's result is Kahan-added to
# the shard's. Nothing of shape ``(n, F, n_bins)`` or ``(n, m)`` exists.
# A node's histogram is the sum of its two children's, so from level 1
# down a level builds ONE child of every parent (a row's column is its
# parent, rows of the other child enter as zeros) and takes the sibling as
# ``parent - built`` once, after the shard's sums are joined: the product
# is half as wide (PR 34; what LightGBM and XGBoost's ``hist`` do). The
# stats ride the bfloat16 product as a compensated (hi, lo) pair and are
# summed in float32; a count of whole weights is exact however many rows
# there are, because a block's sum is exact (under 2^24 rows) and the
# Kahan pair ``(sum, lost)`` of whole numbers stays whole: it is read out
# as ``int32(sum) - int32(lost)``, and a sibling's is an int32 difference.
# Off the TPU a block's histogram is the scatter-add :func:`level_hist`
# uses there.

#: a per-node table up to this long is looked up by a chain of selects
#: (no gather); a longer one by ``jnp.take``
_SELECT_CHAIN_MAX = 64


def block_hist_path() -> str:
    """Which histogram a blocked level builds: the ``"onehot"`` product
    on a TPU, the ``"scatter"`` elsewhere. Chosen from the backend, as
    :func:`build_tree`'s ``use_onehot`` is; the fit reports it."""
    return "onehot" if jax.default_backend() == "tpu" else "scatter"


def lookup(table, ids):
    """``table[ids]`` for a small 1-D ``table`` and ids of any shape."""
    n = table.shape[0]
    if n > _SELECT_CHAIN_MAX:
        return jnp.take(table, ids, axis=0)
    out = jnp.zeros(ids.shape, table.dtype)
    for i in range(n):
        out = jnp.where(ids == i, table[i], out)
    return out


def _whole(acc, comp):
    """The exact int32 value of a Kahan pair of whole numbers."""
    return acc.astype(jnp.int32) - comp.astype(jnp.int32)


def block_hist(bins_b, col_b, stats_b, n_cols: int, n_bins: int,
               path: str):
    """(n_cols, F, n_bins, m) float32 stat sums of ONE block: ``bins_b``
    ``(F, S, 128)`` uint8, ``col_b`` ``(S, 128)`` int32 the row's column,
    ``stats_b`` ``(m, S, 128)`` float32 (zero rows are inert). At the
    root the one column is the node. Below it a column is a PARENT and
    holds the one child :func:`build_tree_blocked` builds of it (the
    smaller one; the other child's rows come with their stats zeroed), so
    the product's operand ``W`` is ``(n_nodes / 2 * 2m, R)``: half the
    level's width. The product is dense over the block's rows, so its
    cost does not depend on WHICH child is built; the numbers do."""
    F, m = bins_b.shape[0], stats_b.shape[0]
    b = bins_b.reshape(F, -1).astype(jnp.int32)
    cl = col_b.reshape(-1)
    st = stats_b.reshape(m, -1).astype(jnp.float32)
    if path == "onehot":
        s2 = jnp.concatenate(split_hi_lo(st), 0)                # (2m, R)
        oh_c = cl[None, :] == jnp.arange(n_cols, dtype=jnp.int32)[:, None]
        W = jnp.where(oh_c[:, None, :], s2[None], 0).reshape(
            n_cols * 2 * m, -1)
        oh_b = (b[:, None, :] == jnp.arange(
            n_bins, dtype=jnp.int32)[None, :, None]).astype(jnp.bfloat16)
        h2 = jnp.einsum("fbr,qr->fbq", oh_b, W,
                        preferred_element_type=jnp.float32)
        h2 = h2.reshape(F, n_bins, n_cols, 2, m)
        return (h2[..., 0, :] + h2[..., 1, :]).transpose(2, 0, 1, 3)
    flat = (cl[None, :] * F + jnp.arange(F, dtype=jnp.int32)[:, None]
            ) * n_bins + b
    hist = jnp.zeros((n_cols * F * n_bins, m), jnp.float32)
    hist = hist.at[flat.reshape(-1)].add(jnp.tile(st.T, (F, 1)))
    return hist.reshape(n_cols, F, n_bins, m)


def level_columns(level: int) -> Tuple[int, int]:
    """``(built, derived)``: how many of a level's ``2^level`` node
    histograms the blocked builder's block loop builds and how many it
    takes as ``parent - built``. The root is built; below it one child of
    every parent is."""
    half = (1 << level) // 2
    return (1 << level) - half, half


def smaller_child(hist, feat, mask):
    """Which child of every node of a level the next level builds: 1 the
    right, 0 the left, ``(n_nodes,)`` int32, from the level's joined
    histogram ``(n_nodes, F, n_bins, m)`` and its chosen splits
    (:func:`best_splits`' ``feat`` and LEFT-membership ``mask``): the one
    of the smaller weight (the LAST stat), the right one on a tie. An
    unsplit node sends every row left, so its built child is the empty
    right one and the left is the parent's own histogram.

    The smaller, for the NUMBERS: ``large = parent - small`` carries the
    parent's rounding into a node at least half its size; the other way
    round a small node would inherit the rounding of a large one, and
    its gains and ``min_samples_leaf`` would read it."""
    w = jnp.take_along_axis(hist[..., -1],
                            jnp.maximum(feat, 0)[:, None, None], 1)[:, 0]
    total = w.sum(1)
    left = jnp.where(feat >= 0, (w * mask).sum(1), total)
    return (total - left <= left).astype(jnp.int32)


def side_words(sides):
    """``(n,)`` 0 / 1 sides packed 32 a ``uint32`` word, for
    :func:`on_side`."""
    s = jnp.pad(sides.astype(jnp.uint32), (0, -sides.shape[0] % 32))
    return (s.reshape(-1, 32) << jnp.arange(32, dtype=jnp.uint32)).sum(
        1, dtype=jnp.uint32)


def on_side(words, node_b):
    """Whether each row of ``node_b`` (its node of this level) lies on its
    parent's packed side (:func:`side_words`): one select a word of 32
    parents and a shift, where a :func:`lookup` of the sides would be a
    select a parent and row (0.43 us a parent and block of 65,536 rows on
    a v5e: 6.5 us at 16 parents, PERF.md PR 34)."""
    parent = node_b >> 1
    bit = lookup(words, parent >> 5) >> (parent & 31).astype(jnp.uint32)
    return (bit & 1) == (node_b & 1).astype(jnp.uint32)


def with_siblings(built, parent, right_built):
    """A level's ``(n_nodes, ...)`` sums in node order from the
    ``(n_nodes / 2, ...)`` sums of the child built a parent and the
    parents' own: the sibling is ``parent - built`` (float32 histograms
    and int32 counts alike)."""
    sel = right_built.reshape((-1,) + (1,) * (built.ndim - 1)) > 0
    other = parent - built
    return jnp.stack([jnp.where(sel, other, built),
                      jnp.where(sel, built, other)], 1).reshape(
        (-1,) + built.shape[1:])


def descend_block(bins_b, node_b, feats, sbins, masks, continuous: bool):
    """One level down for the rows of a block: ``node_b`` ``(S, 128)``
    holds each row's node of the level whose splits ``feats``, ``sbins``
    ``(n_nodes,)`` and ``masks`` ``(n_nodes, n_bins)`` are; a row goes
    right iff its node split and its bin of the split feature is not in
    the node's LEFT set. Where every feature is ``continuous`` that set
    is ``bin <= split bin`` and no row reads a table by index: the node's
    feature and split bin come by a chain of selects over the (few)
    nodes, the row's bin of that feature by one over the features."""
    f_row = lookup(feats, node_b)
    sel = jnp.zeros(node_b.shape, jnp.int32)
    for f in range(bins_b.shape[0]):
        sel = jnp.where(f_row == f, bins_b[f].astype(jnp.int32), sel)
    if continuous:
        in_left = sel <= lookup(sbins, node_b)
    else:
        in_left = masks[node_b, sel]
    return node_b * 2 + ((f_row >= 0) & ~in_left).astype(jnp.int32)


def build_tree_blocked(bins, node_id, stats_at, max_depth: int, n_bins: int,
                       gain_fn, leaf_fn, min_samples_leaf: float = 1.0,
                       min_gain: float = 1e-9, feature_mask=None,
                       axis_name=None, cat_feats=None, cat_order_fn=None,
                       num_workers: int = 1, path: Optional[str] = None):
    """:func:`build_tree` over a worker's shard of a blocked table.

    ``bins``: ``(blocks, F, S, 128)`` uint8; ``node_id``: ``(blocks, S,
    128)`` int32, the buffer the rows' nodes are written to (its content
    is not read); ``stats_at(i)``: block ``i``'s per-row stats ``(m, S,
    128)`` float32, the LAST of them the row's weight (rows of zero stats
    are inert: padding, bagging), called once a level so that stats which
    are cheap to recompute need no ``(n, m)`` array. The other arguments
    are :func:`build_tree`'s; ``path`` is :func:`block_hist_path`'s word.

    The root's histogram is built. From level 1 down the block loop
    builds the histogram of ONE child of every parent, the one of the
    smaller weight by the level above's histogram at its chosen split
    (:func:`smaller_child`, which says why the smaller), in ``n_nodes /
    2`` columns: a row's column is its parent and its stats are zeroed
    unless it went to the built side. The other half of the level is
    ``parent - built``, taken once a level after the shard's sums are
    joined (still ONE ``tree_hist`` all-reduce a level, half as long);
    the split search sees ``(n_nodes, F, n_bins, m)`` in node order as if
    every node had been built. ``node_id`` holds the rows' full nodes.

    Returns ``(features, split_bins, split_masks, leaf_values, node_id,
    leaf_hist, importance, counts)``: as :func:`build_tree`, with
    ``node_id`` the rows' leaves in the blocked layout and ``counts``
    ``(2^(max_depth+1) - 1,)`` int32 the summed weight of every node,
    level by level and the leaves last, exact where the weights are whole
    numbers (a float32 sum stops being exact at 2^24; a derived node's
    count is the int32 difference of two exact ones)."""
    nbl, F = bins.shape[0], bins.shape[1]
    path = path or block_hist_path()
    cat = _cat_columns(cat_feats, F, cat_order_fn)
    continuous = cat is None
    m = jax.eval_shape(stats_at, jnp.asarray(0, jnp.int32)).shape[0]
    at = block_at
    put = jax.lax.dynamic_update_index_in_dim

    def reduce_pair(acc, comp, name):
        """The shard's Kahan pair summed over the mesh: float32 stats and
        the exact weight a column (or leaf), which rides the same psum as
        two halves."""
        lead = acc.shape[0]
        w_at = (slice(None), 0, slice(None), m - 1) if acc.ndim == 4 \
            else (slice(None), m - 1)
        cnt = _whole(acc[w_at], comp[w_at]).reshape(lead, -1).sum(1)
        if axis_name is None:
            return acc - comp, cnt
        hi, lo = split_count(cnt)
        buf = jnp.concatenate([acc.reshape(-1), comp.reshape(-1),
                               hi.astype(jnp.float32),
                               lo.astype(jnp.float32)])
        buf = jnp.asarray(manifest_psum(buf, axis_name, name=name,
                                        num_workers=num_workers))
        k = acc.size
        return ((buf[:k] - buf[k:2 * k]).reshape(acc.shape),
                join_count(buf[2 * k:2 * k + lead], buf[2 * k + lead:]))

    feats_out, bins_out, masks_out, counts = [], [], [], []
    importance = jnp.zeros((F,), jnp.float32)
    prev = None      # the level above's splits, which the rows descend by
    above = None     # its joined (histogram, counts, which child is built)
    for level in range(max_depth + 1):
        n_nodes = 1 << level
        leaves = level == max_depth
        n_cols = n_nodes if leaves else level_columns(level)[0]
        halved = n_cols < n_nodes
        if halved:
            hist_above, cnt_above, right_built = above
            sides = side_words(right_built)

        def body(i, c, n_nodes=n_nodes, n_cols=n_cols, prev=prev,
                 sides=sides if halved else None, leaves=leaves):
            node_id, acc, comp = c
            bins_b = at(bins, i)
            if prev is None:
                node_b = jnp.zeros(node_id.shape[1:], jnp.int32)
            else:
                with jax.named_scope("gbdt_descend"):
                    node_b = descend_block(bins_b, at(node_id, i), *prev,
                                           continuous)
            stats_b = stats_at(i)
            if leaves:
                with jax.named_scope("gbdt_leaf"):
                    oh = (node_b[None] == jnp.arange(
                        n_nodes, dtype=jnp.int32)[:, None, None])
                    blk = jnp.einsum(
                        "lsr,msr->lm", oh.astype(jnp.float32),
                        stats_b.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
            else:
                with jax.named_scope("gbdt_hist"):
                    col_b = node_b
                    if sides is not None:
                        col_b = node_b >> 1
                        stats_b = jnp.where(on_side(sides, node_b)[None],
                                            stats_b, 0)
                    blk = block_hist(bins_b, col_b, stats_b, n_cols,
                                     n_bins, path)
            acc, comp = kahan_add(acc, comp, blk)
            return put(node_id, node_b, i, 0), acc, comp

        zero = jnp.zeros((n_cols, m) if leaves
                         else (n_cols, F, n_bins, m), jnp.float32)
        node_id, acc, comp = jax.lax.fori_loop(
            0, nbl, body, (node_id, zero, zero))
        hist, cnt = reduce_pair(acc, comp,
                                "tree_leaf_hist" if leaves else "tree_hist")
        if halved:
            hist = with_siblings(hist, hist_above, right_built)
            cnt = with_siblings(cnt, cnt_above, right_built)
        counts.append(cnt)
        if leaves:
            leaf_hist = hist
            break
        with jax.named_scope("gbdt_split"):
            feat, sbin, mask, gain = best_splits(
                hist, n_bins, gain_fn, min_samples_leaf, min_gain,
                feature_mask, cat)
            above = (hist, cnt, smaller_child(hist, feat, mask))
        feats_out.append(feat)
        bins_out.append(sbin)
        masks_out.append(mask)
        importance = importance.at[jnp.maximum(feat, 0)].add(gain)
        prev = (feat, sbin, mask)
    return (jnp.concatenate(feats_out), jnp.concatenate(bins_out),
            jnp.concatenate(masks_out, axis=0), leaf_fn(leaf_hist), node_id,
            leaf_hist, importance, jnp.concatenate(counts))


def tree_apply_binned(binned, features, split_bins, max_depth: int,
                      split_masks=None):
    """Final leaf index for each row, descending the dense tree (traceable).

    With ``split_masks`` (n_internal, n_bins) the descent uses the uniform
    LEFT-membership rule (required for categorical splits; identical to
    ``bin <= split_bin`` for continuous nodes)."""
    n = binned.shape[0]
    node = jnp.zeros(n, jnp.int32)
    offset = 0
    for level in range(max_depth):
        gi = offset + node
        f = features[gi]
        sample_bin = jnp.take_along_axis(binned, jnp.maximum(f, 0)[:, None], 1)[:, 0]
        if split_masks is not None:
            in_left = split_masks[gi, sample_bin]
            go_right = (f >= 0) & jnp.logical_not(in_left)
        else:
            go_right = (f >= 0) & (sample_bin > split_bins[gi])
        node = node * 2 + go_right.astype(jnp.int32)
        offset += 1 << level
    return node


def bins_to_thresholds(features: np.ndarray, split_bins: np.ndarray,
                       edges: np.ndarray) -> np.ndarray:
    """Real-valued split thresholds for host-side serving: x > thr -> right.

    A value's bin is the number of edges AT OR BELOW it, so a split at
    bin ``b`` sends ``x >= edges[b]`` right; the threshold is the float64
    just under that edge, so that the served rule ``x > thr`` sends a row
    that TIES the edge (whole-number columns do) the way training did."""
    thr = np.zeros(features.shape, np.float64)
    for i, (f, b) in enumerate(zip(features, split_bins)):
        thr[i] = (np.nextafter(edges[int(f), int(b)], -np.inf)
                  if f >= 0 else 0.0)
    return thr


def thresholds_as(thresholds: np.ndarray, dtype) -> np.ndarray:
    """Thresholds for a kernel that serves in ``dtype``: the largest
    ``dtype`` number not above each, so that ``x > thr`` holds for exactly
    the same ``dtype`` values ``x``. Rounding to the NEAREST float32 would
    put the float64 just under an edge (:func:`bins_to_thresholds`) back
    on the edge, and a row that ties it on the other side."""
    thr = np.asarray(thresholds, np.float64)
    out = thr.astype(dtype)
    return np.where(out.astype(np.float64) > thr,
                    np.nextafter(out, np.asarray(-np.inf, dtype)), out)


def tree_apply_values(X: np.ndarray, features: np.ndarray, thresholds: np.ndarray,
                      max_depth: int, cat_mask: Optional[np.ndarray] = None,
                      split_masks: Optional[np.ndarray] = None) -> np.ndarray:
    """Host/numpy descent on raw feature values.

    Categorical nodes (``cat_mask[f]``) route by LEFT-membership of the
    category code in ``split_masks[node]``; out-of-vocabulary codes route
    right (never in the left set)."""
    n = X.shape[0]
    node = np.zeros(n, np.int64)
    offset = 0
    n_bins = split_masks.shape[1] if split_masks is not None else 0
    for level in range(max_depth):
        gi = offset + node
        f = features[gi].astype(np.int64)
        thr = thresholds[gi]
        x = X[np.arange(n), np.maximum(f, 0)]
        go_right = (f >= 0) & (x > thr)
        if cat_mask is not None and split_masks is not None:
            code = np.round(x).astype(np.int64)
            in_left = np.where(
                code >= 0,
                split_masks[gi, np.clip(code, 0, n_bins - 1)], False)
            is_cat = cat_mask[np.maximum(f, 0)] & (f >= 0)
            go_right = np.where(is_cat, (f >= 0) & ~in_left, go_right)
        node = node * 2 + go_right
        offset += 1 << level
    return node
