"""The plain reference of multinomial logistic regression by L-BFGS over a
blocked table of bytes, for the cell ``softmax-fit``'s ``correct``.

Imports nothing of the program. Plain float32 ``jax.numpy`` at matmul
precision ``highest``; the table ``(row_blocks, d, S, 128)`` is read block
by block (a block cast to float32 and its standardization WRITTEN OUT, ``(x
- mean) / std``: the program folds it into the coefficients instead),
block results are joined on the host in float64; the two-loop recursion is
float64 numpy. The moments are recounted exactly (a block's sums of bytes
and of their squares as whole numbers).

``gaps`` reads one fit TEACHER-FORCED a superstep at a time: from the
coefficients a checked superstep started from (``coef_trace[t]``) it
recomputes loss and gradient over every row, rebuilds the (s, y) history
from the traces' differences and the two-loop direction from it, evaluates
the 11 ladder losses along the fit's direction, and recounts moments and
rows. A near-tie on the ladder that flips on one rounding reads ~0 in
``step_gap`` (the loss given up by the chosen rung), a wrong argmin does
not. ``fit`` is the reference's own L-BFGS, the stand-in for the program
in the controls (``benchmark/controls_softmax.py``): clean, in a lower
precision, or with a fault planted.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

LANES = 128
TINY = 1e-12
GAPS = ("moments_gap", "loss_gap", "grad_gap", "dir_gap", "step_gap",
        "coef_gap")
FAULTS = ("block_left_out", "no_standardization", "stale_logits",
          "rung_off_by_one", "pair_dropped", "float32_counts")


def learner(config: Dict) -> Dict:
    """What the configuration says of the learner, as the reference reads
    it."""
    return {"classes": int(config["classes"]),
            "history": int(config["history"]),
            "max_iter": int(config["max_iter"]),
            "learning_rate": float(config["learning_rate"]),
            "ladder": np.asarray(config["line_search_ladder"], np.float64),
            "l2_ladder": [float(v) for v in config["l2_ladder"]]}


def checked_supersteps(steps: int) -> Tuple[int, ...]:
    """The first, a middle and the last superstep of a fit of ``steps``."""
    return tuple(sorted({0, steps // 2, steps - 1}))


# -- passes over the table, a block at a time --------------------------------

def _rows_live(i, S: int, n_rows: int):
    import jax.numpy as jnp
    at = i * (S * LANES) + jnp.arange(S * LANES, dtype=jnp.int32)
    return at < n_rows


@functools.lru_cache(maxsize=None)
def _moment_block():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sums(xb):
        x = xb.astype(jnp.uint32)
        return x.sum((1, 2)), (x * x).sum((1, 2))
    return sums


def moments(table, n_rows: int, skip_block: Optional[int] = None
            ) -> Tuple[np.ndarray, np.ndarray]:
    """``(mean, std)`` of every column in float64, from whole-number block
    sums (a block of 65,536 bytes sums under 2^32 in both): exact. A
    column with ``std < 1e-12`` keeps ``std = 1``. Rows past ``n_rows``
    are zero in the table and add nothing."""
    d = int(table.shape[1])
    s1, s2 = np.zeros(d, np.uint64), np.zeros(d, np.uint64)
    for i in range(int(table.shape[0])):
        if i == skip_block:
            continue
        a, b = _moment_block()(table[i])
        s1 += np.asarray(a, np.uint64)
        s2 += np.asarray(b, np.uint64)
    n = float(n_rows)
    mean = s1.astype(np.float64) / n
    var = s2.astype(np.float64) / n - mean * mean
    std = np.sqrt(np.maximum(var, 0.0))
    return mean, np.where(std < 1e-12, 1.0, std)


@functools.lru_cache(maxsize=None)
def _pass_block(k: int, S: int, n_rows: int, dtype: str, raw: bool):
    """One block of both passes: from the coefficient rows ``W`` and the
    direction rows ``D`` ``(k - 1, d + 1)`` (intercept first) and the
    ladder's steps, ``(loss sum, gradient sum (k - 1, d + 1), ladder loss
    sums, logits at W)``. ``z0`` given: the ladder starts from those
    logits and not from W's (the stale-logits fault). ``raw``: the
    standardization left out."""
    import jax
    import jax.numpy as jnp
    low = {"float32": None, "bfloat16": jnp.bfloat16}[dtype]

    @jax.jit
    def block(i, xb, yb, W, D, steps, mean, std, z0):
        with jax.default_matmul_precision("highest"):
            d = xb.shape[0]
            x = xb.reshape(d, -1).astype(jnp.float32)
            z = x if raw else (x - mean[:, None]) / std[:, None]
            y = yb.reshape(-1)
            w = _rows_live(i, S, n_rows).astype(jnp.float32)

            def logits(A):
                F, b = A[:, 1:], A[:, 0]
                if low is not None:      # the next precision down: plain
                    return (F.astype(low) @ z.astype(low)
                            ).astype(jnp.float32) + b[:, None]
                return F @ z + b[:, None]

            def loss_of(zz):
                full = jnp.concatenate([zz, jnp.zeros((1, zz.shape[1]))], 0)
                lse = jax.nn.logsumexp(full, axis=0)
                zy = jnp.take_along_axis(full, y[None], 0)[0]
                return (w * (lse - zy)).sum(), full

            zw, zd = logits(W), logits(D)
            loss, full = loss_of(zw)
            p = jax.nn.softmax(full, axis=0)
            delta = (w[None] * (p - jax.nn.one_hot(y, k, axis=0)))[:k - 1]
            if low is not None:
                gf = (delta.astype(low) @ z.T.astype(low)).astype(jnp.float32)
            else:
                gf = delta @ z.T
            grad = jnp.concatenate([delta.sum(1)[:, None], gf], 1)
            start = zw if z0 is None else z0
            line = jnp.stack([loss_of(start - steps[j] * zd)[0]
                              for j in range(steps.shape[0])])
            return loss, grad, line, zw
    return block


def table_pass(table, labels, n_rows: int, W, D, steps, mean, std, k: int,
               dtype: str = "float32", raw: bool = False,
               skip_block: Optional[int] = None, stale=None,
               keep_logits: bool = False):
    """Both passes of a superstep over every row, the blocks joined in
    float64: ``(loss sum, gradient sum, ladder loss sums, kept logits)``,
    unnormalized and without the penalty."""
    import jax.numpy as jnp
    nb, d, S = (int(v) for v in table.shape[:3])
    fn = _pass_block(k, S, int(n_rows), dtype, bool(raw))
    W32, D32 = jnp.asarray(W, jnp.float32), jnp.asarray(D, jnp.float32)
    st = jnp.asarray(steps, jnp.float32)
    mu, sd = jnp.asarray(mean, jnp.float32), jnp.asarray(std, jnp.float32)
    loss, grad, line = 0.0, np.zeros(np.shape(W), np.float64), \
        np.zeros(len(steps), np.float64)
    kept = []
    for i in range(nb):
        if i == skip_block:
            kept.append(None)
            continue
        a, g, l, zw = fn(jnp.asarray(i, jnp.int32), table[i], labels[i], W32,
                         D32, st, mu, sd, None if stale is None else stale[i])
        loss += float(a)
        grad += np.asarray(g, np.float64)
        line += np.asarray(l, np.float64)
        kept.append(zw if keep_logits else None)
    return loss, grad, line, kept


def penalty(W: np.ndarray, l2: float) -> float:
    """The ridge penalty; the intercepts (column 0) are free."""
    return 0.5 * l2 * float((W[:, 1:] ** 2).sum())


def two_loop(g: np.ndarray, S: Sequence[np.ndarray], Y: Sequence[np.ndarray]
             ) -> np.ndarray:
    """The L-BFGS direction from the gradient and the (s, y) pairs, oldest
    first, in float64; a pair with ``s . y <= 1e-12`` is skipped and the
    first scaling is the newest pair's ``s . y / y . y``."""
    q = np.asarray(g, np.float64).copy()
    alphas = []
    for s, y in zip(reversed(S), reversed(Y)):
        sy = float(s @ y)
        ok = sy > TINY
        a = float(s @ q) / sy if ok else 0.0
        q -= a * y
        alphas.append((a, ok, s, y, sy))
    gamma = 1.0
    if S:
        sy, yy = float(S[-1] @ Y[-1]), float(Y[-1] @ Y[-1])
        if sy > TINY and yy > TINY:
            gamma = sy / yy
    r = gamma * q
    for a, ok, s, y, sy in reversed(alphas):
        if ok:
            r += (a - float(y @ r) / sy) * s
    return r


def history_of(coefs: np.ndarray, grads: np.ndarray, t: int, m: int):
    """The (s, y) pairs superstep ``t`` holds, oldest first, from the
    traces' differences."""
    lo = max(0, t - m)
    return ([coefs[j + 1] - coefs[j] for j in range(lo, t)],
            [grads[j + 1] - grads[j] for j in range(lo, t)])


# -- the comparison ------------------------------------------------------------

def _rel(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def gaps(info: Dict, table, labels, n_rows: int, params: Dict
         ) -> Dict[str, float]:
    """The numbers ``correct`` compares, each the widest over the checked
    supersteps of the fit ``info`` records (``get_train_info()`` of the
    program, or ``fit``'s stand-in)."""
    k, m = params["classes"], params["history"]
    l2 = float(info["l2"])
    coefs = np.asarray(info["coef_trace"], np.float64)
    grads = np.asarray(info["grad_trace"], np.float64)
    dirs = np.asarray(info["dir_trace"], np.float64)
    steps = len(coefs)
    after = np.concatenate([coefs[1:], np.asarray(info["coef"], np.float64)
                            [None]], 0)
    mean, std = moments(table, n_rows)
    span = 255.0                                    # a column's range
    out = {"moments_gap": float(max(
        np.abs(np.asarray(info["mean"], np.float64) - mean).max(),
        np.abs(np.asarray(info["std"], np.float64) - std).max()) / span)}
    out.update({name: 0.0 for name in GAPS[1:]})
    for t in checked_supersteps(steps):
        W = coefs[t].reshape(k - 1, -1)
        D = dirs[t].reshape(k - 1, -1)
        ladder = params["learning_rate"] * params["ladder"] \
            * float(info["scale_trace"][t])
        loss, grad, line, _ = table_pass(table, labels, n_rows, W, D, ladder,
                                         mean, std, k)
        loss_ref = loss / n_rows + penalty(W, l2)
        grad_ref = grad / n_rows
        grad_ref[:, 1:] += l2 * W[:, 1:]
        totals = line / n_rows + np.asarray(
            [penalty(W - s * D, l2) for s in ladder])
        d_ref = two_loop(grads[t], *history_of(coefs, grads, t, m))
        rung = int(info["rung_trace"][t])
        here = {
            "loss_gap": abs(float(info["loss_curve"][t]) - loss_ref)
            / abs(loss_ref),
            "grad_gap": _rel(grads[t], grad_ref.reshape(-1)),
            "dir_gap": _rel(dirs[t], d_ref),
            "step_gap": float(totals[rung] - totals.min()) / abs(loss_ref),
            "coef_gap": _rel(after[t],
                             coefs[t] - float(ladder[rung]) * d_ref),
        }
        for name, v in here.items():
            out[name] = max(out[name], float(v))
    return out


# -- the stand-in: the reference's own fit -----------------------------------

def _rounded(a: np.ndarray, dtype: str) -> np.ndarray:
    if dtype == "float64":
        return np.asarray(a, np.float64)
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(a, jnp.float32).astype(
        {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype])
        .astype(jnp.float32), np.float64)


def float32_count(counts: Sequence[int]) -> int:
    """Rows counted by adding the passes' block counts into a float32."""
    total = np.float32(0)
    for c in counts:
        total = np.float32(total + np.float32(c))
    return int(total)


def fit(table, labels, n_rows: int, params: Dict, l2: float, supersteps: int,
        dtype: str = "float32", fault: Optional[str] = None) -> Dict:
    """The reference's own L-BFGS over the table, ``supersteps`` of them,
    recorded as the program records its fit: the stand-in a control reads.
    ``dtype``: the precision the products' coefficient operands are held
    in (``float32``, or ``bfloat16``: the step below); ``fault``: one of
    ``FAULTS``, planted."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    k, m = params["classes"], params["history"]
    nb, d, S = (int(v) for v in table.shape[:3])
    skip = 1 % nb if fault == "block_left_out" else None
    mean, std = moments(table, n_rows, skip)
    seen = n_rows - (min(S * LANES, max(0, n_rows - skip * S * LANES))
                     if skip is not None else 0)
    low = "bfloat16" if dtype == "bfloat16" else "float32"
    coef = np.zeros((k - 1) * (d + 1))
    S_hist, Y_hist = [], []
    scale, prev = 1.0, None
    rec = {name: [] for name in ("coef_trace", "grad_trace", "dir_trace",
                                 "rung_trace", "step_trace", "scale_trace",
                                 "loss_curve")}
    stale = None
    block_counts = []
    for t in range(supersteps):
        W = coef.reshape(k - 1, d + 1)
        kw = dict(dtype=low, raw=fault == "no_standardization",
                  skip_block=skip)
        zero = np.zeros_like(W)
        loss, grad, _, kept = table_pass(
            table, labels, n_rows, W, zero, [0.0], mean, std, k,
            keep_logits=fault == "stale_logits", **kw)
        g = grad / seen
        g[:, 1:] += l2 * W[:, 1:]
        g = g.reshape(-1)
        if prev is not None:
            S_hist.append(coef - prev[0])
            Y_hist.append(g - prev[1])
            S_hist, Y_hist = S_hist[-m:], Y_hist[-m:]
        if fault == "pair_dropped":          # the newest pair never used
            direction = two_loop(g, S_hist[:-1], Y_hist[:-1])
        else:
            direction = two_loop(g, S_hist, Y_hist)
        D = direction.reshape(k - 1, d + 1)
        ladder = params["learning_rate"] * params["ladder"] * scale
        _, _, line, _ = table_pass(table, labels, n_rows, W, D, ladder, mean,
                                   std, k, stale=stale, **kw)
        totals = line / seen + np.asarray(
            [penalty(W - s * D, l2) for s in ladder])
        rung = int(np.argmin(totals))
        if fault == "rung_off_by_one":
            rung = min(rung + 1, len(ladder) - 1)
        for name, v in (("coef_trace", coef.copy()), ("grad_trace", g),
                        ("dir_trace", direction), ("rung_trace", rung),
                        ("step_trace", ladder[rung]), ("scale_trace", scale),
                        ("loss_curve", loss / seen + penalty(W, l2))):
            rec[name].append(v)
        prev = (coef.copy(), g)
        coef = coef - ladder[rung] * direction
        last = len(ladder) - 1
        scale = float(np.clip(scale * (0.25 if rung == 0 else 2.0 if rung == 1
                                       else 0.5 if rung == last else 1.0),
                              1e-10, 1e6))
        if fault == "stale_logits":
            stale = kept
        block_counts += [seen, seen]
    out = {name: np.asarray(v) for name, v in rec.items()}
    counted = (float32_count([seen] + block_counts)
               if fault == "float32_counts" else seen * (1 + 2 * supersteps))
    out.update(coef=coef, mean=_rounded(mean, dtype if dtype != "bfloat16"
                                        else "float32"),
               std=_rounded(std, dtype if dtype != "bfloat16" else "float32"),
               l2=l2, steps=supersteps, rows_counted=counted,
               passes=1 + 2 * supersteps)
    return out
