"""Histogram gradient boosting as it is published (Friedman 2001; Chen and
Guestrin, KDD 2016, section 3.3 "approximate algorithm" with global
quantile proposals), for a table too large to hold twice: quantile edges;
bins by ``searchsorted``; per level the histogram of (g, h, count) by
(node, feature, bin); the gain ``1/2 (G_L^2/(H_L+l) + G_R^2/(H_R+l) -
G^2/(H+l))`` under ``count >= min_samples_per_leaf`` on both sides; leaves
``-G/(H+l)``; margins ``F += eta * leaf``; the logistic loss. Straight
``jax.numpy`` in float32 unless a control asks for less, matmul precision
``highest``, no kernel, one block of rows at a time; what the blocks give
is added up on the host in float64 (the one large result, a level's
histogram, is carried across the blocks on the device as an error-free
(sum, rest) pair and joined on the host in float64). Imports nothing of
the program.

The table is read where it lies and as it is laid out: ``table[b]`` is
block ``b``, feature-major, ``(F, S, 128)``, row ``r`` of the block at
``[:, r // 128, r % 128]``, labels ``(blocks, S, 128)`` alike; rows past
``n_rows`` in the last block are padding.

Departures from the sources, all in the configuration's ``assumed``:
float32; ``reg_lambda`` 1; a value's bin is the number of edges at or
below it and a split sends ``bin <= b`` left; an unsplit node sends every
row left; splits are on ordered bins only (no categorical subsets).

Two uses. :func:`recount` is TEACHER-FORCED: given trees (the program's),
it rebuilds the margins by descending them and recounts, over that node
assignment, the loss, every node's rows, every leaf's sums and the
deepest level's histogram, from which every level's gains follow
(:func:`gaps`). :func:`fit` grows trees itself by the same passes: the
CPU tests' reference fit and, with a ``fault`` planted, the controls'
stand-in for the program.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import numpy as np

FAULTS = ("block_left_out", "stale_margins", "no_descent", "float32_counts")
LEARNER_KEYS = ("num_trees", "max_depth", "max_bins", "learning_rate",
                "min_samples_per_leaf", "reg_lambda")


def learner(config: Dict) -> Dict:
    """The learner's settings, of a configuration's many keys."""
    return {k: config[k] for k in LEARNER_KEYS}


def cuts_of_sorted(v: np.ndarray, n_bins: int) -> np.ndarray:
    """The cut points of one SORTED column without NaNs: for each target
    share ``k / n_bins`` the smallest value with at least that share of
    the column at or below it, duplicates dropped."""
    if not v.size:
        return v[:0]
    q = np.arange(1, n_bins) / n_bins
    at = np.clip(np.ceil(q * v.size).astype(np.int64) - 1, 0, v.size - 1)
    return np.unique(v[at])


def exact_edges(cols: np.ndarray, n_bins: int) -> np.ndarray:
    """(F, n_bins - 1) cut points of host columns ``(F, n)`` by sorting
    (:func:`cuts_of_sorted`), padded with ``+inf``."""
    F = cols.shape[0]
    out = np.full((F, n_bins - 1), np.inf)
    for f in range(F):
        e = cuts_of_sorted(np.sort(cols[f][~np.isnan(cols[f])]), n_bins)
        out[f, :e.size] = e
    return out


@functools.lru_cache(maxsize=None)
def _edge_counts_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def counts(table, b, n_rows, edges):
        x = jax.lax.dynamic_index_in_dim(table, b, 0, keepdims=False)
        F = x.shape[0]
        x = x.reshape(F, -1)
        here = (b * x.shape[1] + jnp.arange(x.shape[1]) < n_rows)[None, None]
        e = edges.astype(x.dtype)[:, :, None]
        return ((here & (x[:, None] < e)).sum(2, dtype=jnp.int32),
                (here & (x[:, None] <= e)).sum(2, dtype=jnp.int32))
    return counts


def edge_rank_gap(table, n_rows: int, edges: np.ndarray, n_bins: int) -> float:
    """How far the cut points are from the quantiles they stand for, as a
    share of the column. An edge ``e`` stands for a target share ``q`` if
    ``q`` lies between the share of the column under ``e`` and the share
    at or under it (a tie in the column makes that an interval). The gap
    is the widest distance, over the edges, to the nearest target, and
    over the targets ``k / n_bins``, to the nearest edge's interval."""
    import jax
    fn = _edge_counts_fn()
    e32 = np.asarray(edges, np.float32)
    got = jax.device_get([fn(table, b, n_rows, e32)
                          for b in range(int(table.shape[0]))])
    lt = np.sum([g[0] for g in got], 0, dtype=np.float64) / n_rows
    le = np.sum([g[1] for g in got], 0, dtype=np.float64) / n_rows
    q = np.arange(1, n_bins) / n_bins
    worst = 0.0
    for f in range(edges.shape[0]):
        real = np.isfinite(edges[f])
        if not real.any():
            continue
        lo, hi = lt[f][real][:, None], le[f][real][:, None]
        dist = np.maximum(np.maximum(lo - q[None], q[None] - hi), 0.0)
        worst = max(worst, float(dist.min(1).max()), float(dist.min(0).max()))
    return worst


@functools.lru_cache(maxsize=None)
def _pass_fn(depth: int, n_bins: int, hist_level: int, dtype: str):
    """One block's pass, computing its stats in ``dtype``; ``hist_level``
    is the level whose histogram it builds, or -1 for none."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    n_leaves = 1 << depth

    def descend(bins, feats, sbins, skip_level):
        """Every row's node at levels 0..depth, by the splits given."""
        F = bins.shape[0]
        node = jnp.zeros(bins.shape[1], jnp.int32)
        nodes, off = [node], 0
        for level in range(depth):
            n = 1 << level
            oh = node[None] == jnp.arange(n)[:, None]
            f_row = (oh * feats[off:off + n, None]).sum(0)
            b_row = (oh * sbins[off:off + n, None]).sum(0)
            mine = ((f_row[None] == jnp.arange(F)[:, None]) * bins).sum(0)
            right = (f_row >= 0) & (mine > b_row) & (level != skip_level)
            node = node * 2 + right
            nodes.append(node)
            off += n
        return nodes

    def two_sum(hi, lo, x):
        """(hi, lo) + x with nothing lost (Knuth): the running sum and
        the rest the float32 sum dropped."""
        s = hi + x
        bb = s - hi
        return s, lo + ((hi - (s - bb)) + (x - bb))

    # the margins are updated where they lie (off the CPU, which cannot
    # donate a buffer and copies instead)
    @functools.partial(jax.jit, donate_argnums=(
        (2,) if jax.default_backend() != "cpu" else ()))
    def one(table, labels, margins, b, n_rows, skip_block, edges, prev,
            eta, cur, skip_level, acc):
        x = jax.lax.dynamic_index_in_dim(table, b, 0, keepdims=False)
        F = x.shape[0]
        x = x.reshape(F, -1).astype(jnp.float32)
        y = jax.lax.dynamic_index_in_dim(
            labels, b, 0, keepdims=False).reshape(-1).astype(jnp.float32)
        m = jax.lax.dynamic_index_in_dim(
            margins, b, 0, keepdims=False).reshape(-1)
        rows = x.shape[1]
        w = ((b * rows + jnp.arange(rows) < n_rows)
             & (b != skip_block)).astype(jnp.float32)
        bins = jax.vmap(lambda e, c: jnp.searchsorted(
            e, c, side="right", method="compare_all"))(
            edges.astype(jnp.float32), x).astype(jnp.int32)
        # the tree before this one, folded into the margins
        before = descend(bins, prev[0], prev[1], -1)[-1]
        m = m + eta * ((before[None] == jnp.arange(n_leaves)[:, None])
                       * prev[2][:, None]).sum(0)
        p = jax.nn.sigmoid(m)
        st = jnp.stack([(p - y) * w, jnp.maximum(p * (1 - p), 1e-6) * w, w])
        loss = (w * (jnp.logaddexp(0.0, m) - y * m)).reshape(
            -1, 128).sum(1).sum()              # short sums: lanes first
        nodes = descend(bins, cur[0], cur[1], skip_level)
        counts = jnp.concatenate([
            ((nodes[level][None] == jnp.arange(1 << level)[:, None])
             & (w != 0)[None]).sum(1, dtype=jnp.int32)
            for level in range(depth + 1)])
        std = st.astype(dt)
        prec = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
        leaves = jnp.einsum(
            "lr,mr->lm",
            (nodes[depth][None] == jnp.arange(n_leaves)[:, None]).astype(dt),
            std, precision=prec, preferred_element_type=jnp.float32)
        if hist_level >= 0:
            n = 1 << hist_level
            oh_b = (bins[:, None, :] == jnp.arange(n_bins)[None, :, None])
            W = ((nodes[hist_level][None] == jnp.arange(n)[:, None])
                 [:, None, :] * std[None]).reshape(n * 3, rows)
            h = jnp.einsum("fbr,qr->fbq", oh_b.astype(dt), W, precision=prec,
                           preferred_element_type=jnp.float32)
            acc = two_sum(acc[0], acc[1], h)
        margins = jax.lax.dynamic_update_index_in_dim(
            margins, m.reshape(margins.shape[1:]), b, 0)
        return margins, loss, w.sum(), counts, leaves, acc
    return one


class _Walk:
    """The block-by-block passes over one table, with the margins they
    carry; shared by :func:`recount` and :func:`fit`."""

    def __init__(self, table, labels, n_rows: int, edges: np.ndarray,
                 params: Dict, dtype: str = "float32",
                 fault: Optional[str] = None):
        import jax.numpy as jnp
        if fault not in (None,) + FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.table, self.labels, self.n = table, labels, int(n_rows)
        self.edges = np.asarray(edges, np.float32)
        self.depth = int(params["max_depth"])
        self.n_bins = int(params["max_bins"])
        self.eta = float(params["learning_rate"])
        self.dtype, self.fault = dtype, fault
        self.nb = int(table.shape[0])
        self.F = int(table.shape[1])
        self.margins = jnp.zeros(labels.shape, jnp.float32)
        self.n_internal = (1 << self.depth) - 1
        self.no_tree = (np.full(self.n_internal, -1, np.int32),
                        np.zeros(self.n_internal, np.int32),
                        np.zeros(1 << self.depth, np.float32))

    def run(self, prev, fold: bool, cur, hist_level: int = -1) -> Dict:
        """One pass: fold tree ``prev`` (features, split bins, leaf
        values) into the margins if ``fold``, then count under the splits
        ``cur`` (features, split bins). Host float64 sums."""
        import jax
        import jax.numpy as jnp
        fn = _pass_fn(self.depth, self.n_bins, hist_level, self.dtype)
        skip_block = self.nb // 2 if self.fault == "block_left_out" else -1
        skip_level = 2 if self.fault == "no_descent" else -1
        shape = ((self.F, self.n_bins, (1 << hist_level) * 3)
                 if hist_level >= 0 else (1,))
        acc = (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
        outs = []
        for b in range(self.nb):
            self.margins, loss, wsum, counts, leaves, acc = fn(
                self.table, self.labels, self.margins, b, self.n, skip_block,
                self.edges, prev, self.eta if fold else 0.0, cur, skip_level,
                acc)
            outs.append((loss, wsum, counts, leaves))
        got = jax.device_get(outs)
        cdt = np.float32 if self.fault == "float32_counts" else np.int64
        out = {"loss": float(np.sum([g[0] for g in got], dtype=np.float64)),
               "weight": float(np.sum([g[1] for g in got], dtype=np.float64)),
               "counts": sum_counts([g[2] for g in got], cdt),
               "leaves": np.sum([g[3] for g in got], 0, dtype=np.float64)}
        if hist_level >= 0:
            hi, lo = jax.device_get(acc)
            h = hi.astype(np.float64) + lo.astype(np.float64)
            out["hist"] = h.reshape(self.F, self.n_bins, 1 << hist_level,
                                    3).transpose(2, 0, 1, 3)
        return out


def sum_counts(per_block: Sequence[np.ndarray], dtype=np.int64) -> np.ndarray:
    """Per-block counts added up one block after the other in ``dtype``:
    ``int64`` is exact; ``float32`` (the control) stops being exact once a
    node holds more than 2^24 rows."""
    total = np.zeros_like(np.asarray(per_block[0]), dtype=dtype)
    for c in per_block:
        total = (total + np.asarray(c).astype(dtype)).astype(dtype)
    return total.astype(np.int64)


def _leaf_values(leaves: np.ndarray, lam: float) -> np.ndarray:
    """``-G / (H + lambda)`` of summed leaf stats ``(..., 3)``."""
    return -leaves[..., 0] / (leaves[..., 1] + lam)


def recount(table, labels, n_rows: int, edges: np.ndarray, trees: Dict,
            params: Dict, hist_trees: Sequence[int] = (),
            dtype: str = "float32") -> Dict[str, np.ndarray]:
    """Teacher-forced: over the node assignment of ``trees`` (``features``
    and ``split_bins`` ``(T, 2^d - 1)`` level by level, ``leaf_values``
    ``(T, 2^d)``, ``base_score``), per tree: ``loss`` of the margins it
    was grown on (mean over the rows), every node's rows ``counts`` ``(T,
    2^(d+1) - 1)``, the leaves' summed stats ``leaves`` ``(T, 2^d, 3)``,
    and for the trees in ``hist_trees`` the histogram of the deepest
    split level ``hist[t]`` ``(2^(d-1), F, n_bins, 3)``."""
    walk = _Walk(table, labels, n_rows, edges, params, dtype)
    walk.margins = walk.margins + np.float32(trees.get("base_score", 0.0))
    T = int(np.asarray(trees["features"]).shape[0])
    out = {"loss": [], "counts": [], "leaves": [], "hist": {}}
    prev = walk.no_tree
    for t in range(T):
        cur = (np.asarray(trees["features"][t], np.int32),
               np.asarray(trees["split_bins"][t], np.int32))
        got = walk.run(prev, True, cur,
                       walk.depth - 1 if t in hist_trees else -1)
        out["loss"].append(got["loss"] / got["weight"])
        out["counts"].append(got["counts"])
        out["leaves"].append(got["leaves"])
        if "hist" in got:
            out["hist"][t] = got["hist"]
        prev = cur + (np.asarray(trees["leaf_values"][t], np.float32),)
    return {k: (np.asarray(v) if k != "hist" else v) for k, v in out.items()}


def level_gains(hist: np.ndarray, lam: float, min_leaf: float):
    """``(gain (nodes, F, n_bins - 1), admissible)`` of every split of one
    level from its histogram ``(nodes, F, n_bins, 3)``, in float64."""
    cum = np.cumsum(hist, axis=2)
    total = cum[:, :, -1:, :]
    left = cum[:, :, :-1, :]
    right = total - left

    def score(s):
        return s[..., 0] ** 2 / (s[..., 1] + lam)
    gain = 0.5 * (score(left) + score(right) - score(total))
    return gain, (left[..., 2] >= min_leaf) & (right[..., 2] >= min_leaf)


def split_gains(deep_hist: np.ndarray, features: np.ndarray,
                split_bins: np.ndarray, params: Dict) -> List[Dict]:
    """Per node of one tree, level by level: the ``best`` admissible gain,
    the gain of the split ``chosen`` (``None`` where the node was left
    unsplit, ``-inf`` where the chosen split is not admissible) and the
    node's own ``score`` ``G^2 / (H + lambda)``. Every level's histogram
    is taken from the deepest level's: a node's is the sum of its
    descendants'."""
    depth = int(params["max_depth"])
    lam, min_leaf = float(params["reg_lambda"]), float(
        params["min_samples_per_leaf"])
    out, off = [], 0
    for level in range(depth):
        n = 1 << level
        h = deep_hist.reshape((n, deep_hist.shape[0] // n)
                              + deep_hist.shape[1:]).sum(1)
        gain, ok = level_gains(h, lam, min_leaf)
        best = np.where(ok, gain, -np.inf).reshape(n, -1).max(1)
        total = h[:, 0].sum(1)                                  # (n, 3)
        for i in range(n):
            f, b = int(features[off + i]), int(split_bins[off + i])
            chosen = None if f < 0 else (
                float(gain[i, f, b]) if ok[i, f, b] else -np.inf)
            out.append({"level": level, "node": i, "best": float(best[i]),
                        "chosen": chosen, "rows": float(total[i, 2]),
                        "score": float(total[i, 0] ** 2
                                       / (total[i, 1] + lam))})
        off += n
    return out


def split_gain_gap(deep_hist: np.ndarray, features: np.ndarray,
                   split_bins: np.ndarray, params: Dict) -> float:
    """The widest ``(best - chosen) / (|best| + score)`` over the nodes of
    one tree that split. A gain is a difference of terms of the size of
    the node's own score, so a rounding of the sums moves it by a share of
    the SCORE: against ``|best|`` alone a weak split of a large node reads
    its rounding hundreds of times over (1.8e-3 on the first full-size
    run, PERF.md). A node left unsplit reads 1 if an admissible split
    gains more than ``min_gain``, and a chosen split that is not
    admissible reads 1."""
    min_gain = float(params.get("min_gain", 1e-9))
    worst = 0.0
    for d in split_gains(deep_hist, features, split_bins, params):
        if d["chosen"] is None:
            gap = 1.0 if d["best"] > min_gain else 0.0
        elif not np.isfinite(d["chosen"]):
            gap = 1.0
        else:
            gap = (d["best"] - d["chosen"]) / (abs(d["best"]) + d["score"])
        worst = max(worst, float(gap))
    return worst


def gaps(info: Dict, want: Dict, params: Dict) -> Dict[str, float]:
    """The numbers ``correct`` compares, from what a fit reports
    (``info``: ``features``, ``split_bins``, ``leaf_values``,
    ``loss_curve``, ``counts``) and its teacher-forced recount
    (``want``)."""
    lam = float(params["reg_lambda"])
    leaves = np.asarray(want["leaves"], np.float64)
    ref_v = _leaf_values(leaves, lam)
    held = leaves[..., 2] > 0
    got_v = np.asarray(info["leaf_values"], np.float64)
    rms = float(np.sqrt(np.mean(ref_v[held] ** 2))) or 1.0
    loss = np.asarray(info["loss_curve"], np.float64)
    return {
        "split_gain_gap": max(
            split_gain_gap(h, np.asarray(info["features"][t]),
                           np.asarray(info["split_bins"][t]), params)
            for t, h in want["hist"].items()),
        "leaf_gap": float(np.abs(got_v - ref_v)[held].max()) / rms,
        "loss_gap": float(np.max(np.abs(loss - want["loss"])
                                 / np.abs(want["loss"]))),
        "count_gap": float(np.abs(
            np.asarray(info["counts"][0], np.int64)
            - np.asarray(want["counts"][0], np.int64)).max())}


def fit(table, labels, n_rows: int, edges: np.ndarray, params: Dict,
        dtype: str = "float32", fault: Optional[str] = None) -> Dict:
    """Grow ``num_trees`` trees, depth-wise, by the passes above: a pass a
    level for its histogram, the best admissible split a node, a pass for
    the leaves. Returns what a fit reports (see :func:`gaps`).
    ``dtype`` and ``fault`` are the controls': the stats in a lower
    precision, one block left out, margins one tree stale, the rows not
    descended at level 2, counts added in float32."""
    walk = _Walk(table, labels, n_rows, edges, params, dtype, fault)
    depth, T = walk.depth, int(params["num_trees"])
    lam = float(params["reg_lambda"])
    min_leaf = float(params["min_samples_per_leaf"])
    min_gain = float(params.get("min_gain", 1e-9))
    out = {k: [] for k in ("features", "split_bins", "leaf_values",
                           "loss_curve", "counts")}
    done: List = []
    for t in range(T):
        feats = np.full(walk.n_internal, -1, np.int32)
        sbins = np.zeros(walk.n_internal, np.int32)
        # the tree before folds into the margins in this tree's first
        # pass; the stale-margins fault folds the one before that
        lag = 2 if fault == "stale_margins" else 1
        prev = done[t - lag] if t >= lag else walk.no_tree
        off = 0
        for level in range(depth):
            got = walk.run(prev, level == 0, (feats, sbins), level)
            if level == 0:
                out["loss_curve"].append(got["loss"] / got["weight"])
            gain, ok = level_gains(got["hist"], lam, min_leaf)
            n = 1 << level
            flat = np.where(ok, gain, -np.inf).reshape(n, -1)
            best = flat.argmax(1)
            for i in range(n):
                if flat[i, best[i]] > min_gain:
                    feats[off + i] = best[i] // (walk.n_bins - 1)
                    sbins[off + i] = best[i] % (walk.n_bins - 1)
            off += n
        got = walk.run(prev, False, (feats, sbins))
        values = np.where(got["leaves"][:, 2] > 0,
                          _leaf_values(got["leaves"], lam), 0.0)
        done.append((feats, sbins, values.astype(np.float32)))
        out["features"].append(feats)
        out["split_bins"].append(sbins)
        out["leaf_values"].append(values)
        out["counts"].append(got["counts"])
    return {k: np.asarray(v) for k, v in out.items()}
