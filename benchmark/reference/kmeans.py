"""KMeans as the textbook writes it, for a table too large to hold twice:
Lloyd's iteration (assign every row to its nearest centre, move every
centre to the weighted mean of its rows), the weights of a k-means||
candidate set (Bahmani et al., VLDB 2012: each candidate weighs the rows
nearest to it) and the weighted k-means++ recluster of that set. Straight
``jax.numpy`` in float32 unless a control asks for less, matmul precision
``highest``, one block of rows at a time; what the blocks give is added up
on the host in float64. Imports nothing of the program.

The table is read where it lies and as it is laid out: ``table[b]`` is
block ``b``, feature-major, ``(d, S, 128)``, row ``r`` of the block at
``[:, r // 128, r % 128]``; rows past ``n_rows`` in the last block are
padding. ``weights`` (``(blocks, S, 128)`` like the rows, or ``None`` for
one a row) weigh the rows. Distances are squared differences summed over
the features.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterable, List

import numpy as np


@functools.lru_cache(maxsize=None)
def _block_fns(dtype: str, weighted: bool):
    """The per-block programs, computing distances in ``dtype``; with
    ``weighted`` they read the rows' weights from ``w``, else ``w`` is a
    placeholder and every row weighs one."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)

    def distances(x, C):
        """(m, rows) squared distances in ``dtype`` (float32, or the
        control's lower precision), returned as float32."""
        diff = x.astype(dt)[None, :, :] - C.astype(dt)[:, :, None]
        return (diff * diff).sum(1).astype(jnp.float32)

    def block(table, w, b, n_rows):
        """Block ``b`` as ``(d, rows)`` float32 and its rows' weights,
        zero on the padding."""
        x = jax.lax.dynamic_index_in_dim(table, b, 0, keepdims=False)
        rows = x.shape[1] * x.shape[2]
        rw = (b * rows + jnp.arange(rows) < n_rows).astype(jnp.float32)
        if weighted:
            rw = rw * jax.lax.dynamic_index_in_dim(
                w, b, 0, keepdims=False).reshape(-1).astype(jnp.float32)
        return x.reshape(x.shape[0], -1).astype(jnp.float32), rw

    @jax.jit
    def lloyd(table, w, b, n_rows, C):
        x, rw = block(table, w, b, n_rows)
        D = distances(x, C)
        near = jnp.argmin(D, 0)
        onehot = (near[None, :] == jnp.arange(C.shape[0])[:, None]) * rw[None]
        sums = (onehot[:, None, :] * x[None, :, :]).sum(2)        # (k, d)
        return sums, onehot.sum(1), (jnp.min(D, 0) * rw).sum(), \
            (rw != 0).sum()

    @jax.jit
    def count(table, w, b, n_rows, C):
        x, rw = block(table, w, b, n_rows)
        near = jnp.argmin(distances(x, C), 0)       # ties: the lowest index
        return ((near[None, :] == jnp.arange(C.shape[0])[:, None])
                * rw[None]).sum(1)

    @jax.jit
    def member(table, w, b, n_rows, C):
        x, rw = block(table, w, b, n_rows)
        return jnp.min(jnp.where((rw != 0)[None], distances(x, C), jnp.inf), 1)

    @jax.jit
    def moments(table, w, b, n_rows):
        x, rw = block(table, w, b, n_rows)
        return (x * rw[None]).sum(1), (x * x * rw[None]).sum(), rw.sum()

    return {"lloyd": lloyd, "count": count, "member": member,
            "moments": moments}


def _each_block(table, skip: Iterable[int] = ()):
    skip = set(int(b) for b in skip)
    return [b for b in range(int(table.shape[0])) if b not in skip]


def _prep(weights):
    """``(w or a placeholder, weighted)``: jit wants an array either way."""
    return (np.zeros((1,), np.float32), False) if weights is None \
        else (weights, True)


def lloyd(table, n_rows: int, weights, init_centroids: np.ndarray,
          steps: int, dtype: str = "float32", skip_blocks: Iterable[int] = (),
          frozen: bool = False) -> List[Dict[str, np.ndarray]]:
    """``steps`` supersteps of Lloyd from ``init_centroids``; after each,
    ``{"centroids" (k, d), "weights" (k,), "inertia", "rows"}``: the new
    centres, the weight and the inertia of the assignment that made them,
    and the rows seen. A centre that no row chose stays where it was.
    ``skip_blocks`` and ``frozen`` plant the controls' faults: blocks left
    out, centres handed back unchanged."""
    import jax
    w, weighted = _prep(weights)
    fn = _block_fns(dtype, weighted)["lloyd"]
    C = np.asarray(init_centroids, np.float32)
    k, d = C.shape
    out = []
    with jax.default_matmul_precision("highest"):
        for _ in range(int(steps)):
            sums = np.zeros((k, d), np.float64)
            cnt = np.zeros(k, np.float64)
            inertia, rows = 0.0, 0
            parts = [fn(table, w, b, n_rows, C)
                     for b in _each_block(table, skip_blocks)]
            for s, c, i, r in jax.device_get(parts):
                sums += s
                cnt += c
                inertia += float(i)
                rows += int(r)
            new = np.where(cnt[:, None] > 0,
                           sums / np.maximum(cnt[:, None], 1e-300), C)
            if not frozen:
                C = new.astype(np.float32)
            out.append({"centroids": C.copy(), "weights": cnt,
                        "inertia": inertia, "rows": rows})
    return out


def candidate_weights(table, n_rows: int, weights, candidates: np.ndarray,
                      dtype: str = "float32") -> np.ndarray:
    """The summed weight of the rows nearest to each candidate (ties to
    the lowest index), over the whole table."""
    import jax
    w, weighted = _prep(weights)
    fn = _block_fns(dtype, weighted)["count"]
    C = np.asarray(candidates, np.float32)
    with jax.default_matmul_precision("highest"):
        parts = jax.device_get([fn(table, w, b, n_rows, C)
                                for b in _each_block(table)])
    return np.sum(np.asarray(parts, np.float64), axis=0)


def member_gaps(table, n_rows: int, candidates: np.ndarray) -> np.ndarray:
    """For each candidate the distance to its nearest table row: 0 where
    the candidate is a row of the table."""
    import jax
    w, weighted = _prep(None)
    fn = _block_fns("float32", weighted)["member"]
    C = np.asarray(candidates, np.float32)
    parts = jax.device_get([fn(table, w, b, n_rows, C)
                            for b in _each_block(table)])
    return np.sqrt(np.min(np.asarray(parts, np.float64), axis=0))


def rms_spread(table, n_rows: int, weights=None) -> float:
    """Root of the weighted mean squared distance of a row from the
    table's mean: the scale centroid gaps are read against."""
    import jax
    w, weighted = _prep(weights)
    fn = _block_fns("float32", weighted)["moments"]
    parts = jax.device_get([fn(table, w, b, n_rows)
                            for b in _each_block(table)])
    s1 = np.sum([np.asarray(p[0], np.float64) for p in parts], axis=0)
    s2 = float(np.sum([float(p[1]) for p in parts]))
    tot = float(np.sum([float(p[2]) for p in parts]))
    mean = s1 / tot
    return float(np.sqrt(max(s2 / tot - float(mean @ mean), 0.0)))


def weighted_kmeans_pp(C: np.ndarray, w: np.ndarray, k: int,
                       rng: np.random.RandomState, sweeps: int = 8
                       ) -> np.ndarray:
    """The recluster of a weighted candidate set to ``k`` centres:
    k-means++ seeding with probability proportional to weight times
    squared distance to the centres chosen so far (Arthur and
    Vassilvitskii, SODA 2007, weighted), then ``sweeps`` of weighted
    Lloyd. Plain float64 numpy on the host; draws from ``rng`` one
    ``choice`` a centre, as the program's does."""
    C = np.asarray(C, np.float64)
    w = np.maximum(np.asarray(w, np.float64), 0.0)
    if w.sum() <= 0:
        w = np.ones(len(C))
    p = w / w.sum()
    chosen = [C[rng.choice(len(C), p=p)]]
    for _ in range(1, k):
        d2 = np.min([((C - c) ** 2).sum(1) for c in chosen], axis=0)
        q = w * d2
        pick = rng.choice(len(C), p=q / q.sum() if q.sum() > 0 else p)
        chosen.append(C[pick])
    cc = np.stack(chosen)
    for _ in range(sweeps):
        near = np.argmin(((C[:, None, :] - cc[None]) ** 2).sum(-1), axis=1)
        for j in range(k):
            mine = near == j
            if w[mine].sum() > 0:
                cc[j] = np.average(C[mine], axis=0, weights=w[mine])
    return cc


def gaps(got: Dict[str, np.ndarray], want: List[Dict[str, np.ndarray]],
         n_rows_weight: float, spread: float) -> Dict[str, float]:
    """The numbers ``correct`` compares, program (``got``: per superstep
    ``centroids``, ``weights``, ``inertia``) against ``want`` (``lloyd``'s
    return): each the widest over the supersteps."""
    out = {"centroid_gap": 0.0, "weight_gap": 0.0, "inertia_gap": 0.0}
    for s, ref in enumerate(want):
        c = np.asarray(got["centroids"][s], np.float64)
        out["centroid_gap"] = max(out["centroid_gap"], float(np.sqrt(
            ((c - ref["centroids"]) ** 2).sum(1)).max()) / spread)
        out["weight_gap"] = max(out["weight_gap"], float(np.abs(
            np.asarray(got["weights"][s], np.float64)
            - ref["weights"]).sum()) / n_rows_weight)
        out["inertia_gap"] = max(out["inertia_gap"], abs(
            float(got["inertia"][s]) - ref["inertia"]) / ref["inertia"])
    return out
