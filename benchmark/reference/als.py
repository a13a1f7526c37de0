"""The plain reference of an ALS fit (alternating least squares with
weighted-lambda regularisation, ALS-WR; Zhou et al. 2008, as cuMF and
Alink's ``AlsTrainBatchOp`` fit it). It imports nothing of the program.

A fit cannot be replayed to the bit (a rounding moves every later
iterate), so it is TEACHER-FORCED: each half-sweep is recomputed from the
factors the program read. For a seeded sample of a side's rows (always
the heaviest, the lightest and some with no rating), the reference finds
their ratings in the RAW table on the host, builds the row's normal
equations in float64

    A = sum theta theta^T + lambda n I,   b = sum r theta,   x = A^-1 b

(``implicit``: theta theta^T weighted by c = 1 + alpha |r|, b by c [r >
0]) and solves them. Every row's count is recounted from the raw ids
(``numpy.bincount``, whole numbers). The train RMSE is recounted over
EVERY rating on the device, plain float32 ``jax.numpy`` (an elementwise
product and a sum a rating, so no matmul precision enters), block sums
joined on the host in float64.

``stand_in`` is the controls' side: a fit's numbers made by this module
alone in a chosen precision or with a fault planted, read by the same
``gaps`` (``benchmark/controls_als.py``).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np

ENDS = 100        # heaviest, lightest and empty rows always sampled
FAULTS = ("block_left_out", "stale_factors", "plain_lambda",
          "float32_counts")


def learner(config: Dict) -> Dict:
    return {"rank": int(config["rank"]), "lambda": float(config["lambda"]),
            "implicit": bool(config.get("implicit_prefs", False)),
            "alpha": float(config.get("alpha", 40.0)),
            "nonnegative": bool(config.get("nonnegative", False)),
            "sample_rows": int(config["check_sample_rows"])}


_FETCHED: Dict = {}


def host_columns(users, items, ratings, n_rows: int
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The three blocked columns as host ``(n_rows,)`` arrays (the last
    table fetched is kept: the controls read one table many times)."""
    key = (id(users), id(items), id(ratings), n_rows)
    if _FETCHED.get("key") != key:
        _FETCHED.clear()
        _FETCHED.update(key=key, table=(users, items, ratings), host=tuple(
            np.asarray(a).reshape(-1)[:n_rows]
            for a in (users, items, ratings)))
    return _FETCHED["host"]


def sample_rows(cnt: np.ndarray, seed: int, size: int, side: int
                ) -> np.ndarray:
    """Rows of a side the solve is compared on: the ``ENDS`` heaviest
    (in a randomly ordered table they hold ratings in every block), the
    ``ENDS`` lightest with a rating, up to ``ENDS`` with none, and
    ``size`` drawn from the seed."""
    rng = np.random.default_rng([int(seed), 31, int(side)])
    order = np.argsort(cnt, kind="stable")
    full = order[cnt[order] > 0]
    picks = rng.choice(len(cnt), min(int(size), len(cnt)), replace=False)
    return np.unique(np.concatenate(
        [full[:ENDS], full[-ENDS:], order[cnt[order] == 0][:ENDS], picks]))


def ratings_of(keys: np.ndarray, rows: np.ndarray, n_ids: int):
    """Where in the raw table the ratings of ``rows`` (sorted ids) stand:
    ``(at, starts, ends)``, row ``j``'s positions ``at[starts[j]:
    ends[j]]``."""
    member = np.zeros(n_ids, bool)
    member[rows] = True
    at = np.flatnonzero(member[keys])
    at = at[np.argsort(keys[at], kind="stable")]
    k = keys[at]
    return at, np.searchsorted(k, rows), np.searchsorted(k, rows, "right")


def _rounded(a: np.ndarray, dtype: str) -> np.ndarray:
    if dtype == "float64":
        return a.astype(np.float64)
    import ml_dtypes
    return a.astype({"float32": np.float32,
                     "bfloat16": ml_dtypes.bfloat16}[dtype]
                    ).astype(np.float64)


def solve_rows(rows, found, other_ids, ratings, other, cnt, params: Dict,
               dtype: str = "float64", weighted: bool = True,
               left_out: Optional[Tuple[int, int]] = None) -> np.ndarray:
    """``x (len(rows), rank)`` of the sampled rows from ``other`` (the
    other side's factors, ``(ids, rank)``). ``dtype``: the precision the
    Gram products' operands are rounded to (sums stay float64);
    ``weighted``: ``lambda n`` on the diagonal, else plain ``lambda``;
    ``left_out``: raw positions ``[first, last)`` skipped (a block)."""
    at, starts, ends = found
    r, lam = params["rank"], params["lambda"]
    out = np.zeros((len(rows), r))
    eye = np.eye(r)
    for j, row in enumerate(rows):
        sel = at[starts[j]:ends[j]]
        if left_out is not None:
            sel = sel[(sel < left_out[0]) | (sel >= left_out[1])]
        if not cnt[row]:
            continue
        X = _rounded(other[other_ids[sel]], dtype)
        rv = _rounded(ratings[sel], dtype)
        if params["implicit"]:
            c = 1.0 + params["alpha"] * np.abs(rv)
            A = (X * c[:, None]).T @ X
            b = X.T @ (c * (rv > 0))
        else:
            A = X.T @ X
            b = X.T @ rv
        A = A + lam * (max(int(cnt[row]), 1) if weighted else 1.0) * eye
        if params["nonnegative"]:
            from scipy.optimize import nnls
            Lc = np.linalg.cholesky(A)
            out[j] = nnls(Lc.T, np.linalg.solve(Lc, b))[0]
        else:
            out[j] = np.linalg.solve(A, b)
    return out


@functools.lru_cache(maxsize=None)
def _block_errors(n_rows: int, nb: int, S: int, L: int):
    """The program that sums a block's squared errors, traced once a
    table's shape."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sums(users, items, ratings, uf, if_):
        def one(b):
            u, i = users[b].reshape(-1), items[b].reshape(-1)
            e = (uf[u] * if_[i]).sum(-1) - ratings[b].reshape(-1)
            here = b * (S * L) + jnp.arange(S * L) < n_rows
            return jnp.where(here, e * e, 0.0).reshape(S, L).sum(-1).sum()
        return jax.lax.map(one, jnp.arange(nb, dtype=jnp.int32))
    return sums


def rmse(users, items, ratings, n_rows: int, uf, if_,
         left_out: Optional[int] = None) -> float:
    """The root mean squared error of ``uf[u] . if_[i]`` against every
    rating of the blocked table ``(row_blocks, S, 128)``, on the device in
    float32, a block's sum at a time, joined in float64. ``left_out``: a
    block skipped (the controls')."""
    import jax.numpy as jnp
    nb, S, L = users.shape
    sums = _block_errors(n_rows, nb, S, L)
    uf, if_ = jnp.asarray(uf, jnp.float32), jnp.asarray(if_, jnp.float32)
    per_block = np.asarray(sums(users, items, ratings, uf, if_), np.float64)
    n = n_rows
    if left_out is not None:
        n -= min(S * L, n_rows - left_out * S * L)
        per_block[left_out] = 0.0
    return float(np.sqrt(per_block.sum() / max(n, 1)))


def _solve_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Widest ``|x - x_ref|`` of a row over the RMS row norm."""
    scale = np.sqrt((want ** 2).sum(1).mean())
    return float(np.sqrt(((got - want) ** 2).sum(1)).max()
                 / max(scale, 1e-300))


def gaps(info: Dict, table, n_rows: int, params: Dict, seed: int
         ) -> Dict[str, float]:
    """The numbers ``correct`` compares, from what a fit went through
    (``info``: ``AlsTrainBatchOp.get_train_info()`` or a stand-in:
    ``user_factors``, ``item_factors``, ``items_read``, ``user_counts``,
    ``item_counts``, ``rmse_curve``) against the raw ``table`` (the three
    blocked columns)."""
    r = params["rank"]
    users, items, ratings = host_columns(*table, n_rows)
    uf = np.asarray(info["user_factors"])[:, :r]
    if_ = np.asarray(info["item_factors"])[:, :r]
    read = np.asarray(info["items_read"])[:, :r]
    out = {}
    count_gap = 0
    for side, keys, others, got, other, name in (
            (0, users, items, uf, read, "user"),
            (1, items, users, if_, uf, "item")):
        cnt = np.bincount(keys, minlength=got.shape[0])
        count_gap += int(np.abs(np.asarray(info[f"{name}_counts"],
                                           np.int64) - cnt).sum())
        rows = sample_rows(cnt, seed, params["sample_rows"], side)
        want = solve_rows(rows, ratings_of(keys, rows, got.shape[0]), others,
                          ratings, other, cnt, params)
        out[f"{name}_solve_gap"] = _solve_gap(got[rows], want)
    out["count_gap"] = float(count_gap)
    # padded lanes, where a program keeps them, are zero: no slice needed
    want = rmse(*table, n_rows, info["user_factors"], info["item_factors"])
    out["rmse_gap"] = abs(float(np.asarray(info["rmse_curve"])[-1]) - want) \
        / max(want, 1e-300)
    return out


def float32_counts(cnt: np.ndarray) -> np.ndarray:
    """Counts as the differences of offsets carried in float32: exact
    while the offsets stay under 2^24, off by up to the spacing of
    float32 there beyond."""
    off = np.concatenate([[0], np.cumsum(cnt)]).astype(np.float32)
    return np.diff(off).astype(np.int64)


def stand_in(table, n_rows: int, n_users: int, n_items: int, params: Dict,
             seed: int, dtype: str = "float64",
             fault: Optional[str] = None) -> Dict:
    """A fit's numbers as ``gaps`` reads them, made by the reference
    alone: the item factors a user half-sweep reads and the user factors
    an item half-sweep reads are drawn from the seed, the sampled rows of
    each side solved from them in ``dtype`` (``"bfloat16"``: the Gram
    products' operands and the factors the RMSE reads rounded to it), with
    ``fault`` planted:

    * ``block_left_out``: the ratings of one block of the raw table
      (the middle one) never folded, in either half-sweep or the RMSE;
    * ``stale_factors``: the item half-sweep reads the user factors as
      they were BEFORE the user half-sweep, and the curve's point is the
      error of the item factors BEFORE the item half-sweep;
    * ``plain_lambda``: ``lambda`` on the diagonal, not ``lambda n``;
    * ``float32_counts``: a side's counts taken as differences of offsets
      carried in float32.
    """
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    r = params["rank"]
    rng = np.random.default_rng([int(seed), 37])
    users, items, ratings = host_columns(*table, n_rows)
    read = (rng.random((n_items, r)) / np.sqrt(r)).astype(np.float32)
    # user factors of the size a first half-sweep leaves them: a common
    # part that predicts the mean rating from the mean item factor, and a
    # spread of one around it. The common part is what makes an item
    # row's equations ill-conditioned (~4,000 at rank 100), and a control
    # has to be read under the conditioning the program's fit meets
    level = float(ratings.mean()) / (r * float(read.mean()))
    before = (level + rng.normal(0.0, 1.0, (n_users, r))).astype(np.float32)
    block = int(table[0].shape[1]) * int(table[0].shape[2])
    mid = int(table[0].shape[0]) // 2
    left_out = (mid * block, (mid + 1) * block) \
        if fault == "block_left_out" else None
    kw = dict(dtype=dtype, weighted=fault != "plain_lambda",
              left_out=left_out)
    cnt_u = np.bincount(users, minlength=n_users)
    cnt_i = np.bincount(items, minlength=n_items)
    rows_u = sample_rows(cnt_u, seed, params["sample_rows"], 0)
    rows_i = sample_rows(cnt_i, seed, params["sample_rows"], 1)
    if left_out:                  # what is not folded is not counted
        gone = slice(*left_out)
        cnt_u = cnt_u - np.bincount(users[gone], minlength=n_users)
        cnt_i = cnt_i - np.bincount(items[gone], minlength=n_items)
    uf = before.copy()
    uf[rows_u] = solve_rows(rows_u, ratings_of(users, rows_u, n_users),
                            items, ratings, read, cnt_u, params, **kw)
    if_ = rng.normal(0.0, 1.0, (n_items, r)).astype(np.float32)
    if_[rows_i] = solve_rows(
        rows_i, ratings_of(items, rows_i, n_items), users, ratings,
        before if fault == "stale_factors" else uf, cnt_i, params, **kw)
    if fault == "float32_counts":
        cnt_u, cnt_i = float32_counts(cnt_u), float32_counts(cnt_i)
    low = dtype if dtype != "float64" else "float32"
    # the curve's point, from what the fault leaves: a stale item
    # half-sweep also leaves the error of the item factors it started from
    curve = [rmse(*table, n_rows, _rounded(uf, low), _rounded(
        read if fault == "stale_factors" else if_,
        low), left_out=mid if left_out else None)]
    return {"user_factors": uf, "item_factors": if_, "items_read": read,
            "user_counts": cnt_u, "item_counts": cnt_i,
            "rmse_curve": np.asarray(curve)}
