"""FTRL-proximal, per sample, as McMahan et al. (KDD 2013, algorithm 1)
write it and Alink's ``FtrlTrainStreamOp`` runs it: for each sample in
arrival order, weights from (z, n) at the sample's own coordinates, the
logistic gradient at those weights, then the (z, n) update. Straight
``jax.numpy`` in one ``lax.scan``, float32 unless a control asks for less.

The state is COMPACT: the caller maps the coordinates that the rows touch
to 0..m-1 (``numpy.unique``), so a 2^29-wide model costs only what its
touched coordinates do. Imports nothing of the program.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def weights(z, n, alpha: float, beta: float, l1: float, l2: float):
    """w from (z, n): the closed form of the proximal step."""
    import jax.numpy as jnp
    decay = (beta + jnp.sqrt(n)) / alpha + l2
    w = -(z - jnp.sign(z) * l1) / decay
    return jnp.where(jnp.abs(z) <= l1, jnp.zeros_like(w), w)


def warm_state(coef, alpha: float, beta: float, l2: float):
    """(z, n) that encode a warm-start weight vector: n = 0 and
    z = -w (beta/alpha + l2), so that ``weights`` gives w back (less the
    l1 shrinkage)."""
    import jax.numpy as jnp
    coef = jnp.asarray(coef)
    return -coef * (beta / alpha + l2), jnp.zeros_like(coef)


def run(idx: np.ndarray, val: np.ndarray, y: np.ndarray, z0, n0,
        hp: Dict[str, float], dtype="float32") -> Tuple:
    """Fold the rows ``(idx, val, y)`` into (z, n) one sample at a time.

    ``idx`` (rows, nnz) int32 into the compact state, ``val`` (rows, nnz),
    ``y`` (rows,) in {0, 1}. Entries with ``val == 0`` are no-ops.
    ``dtype`` is the arithmetic and state type (the controls pass
    ``bfloat16``). Returns the final (z, n) in that type."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    alpha, beta = hp["alpha"], hp["beta"]
    l1, l2 = hp["l1"], hp["l2"]

    def body(carry, row):
        z, n = carry
        i, v, yy = row
        zi, ni = z[i], n[i]
        w = weights(zi, ni, alpha, beta, l1, l2).astype(dt)
        margin = jnp.sum(v * w)
        p = 1.0 / (1.0 + jnp.exp(-jnp.clip(margin, -35.0, 35.0)))
        g = ((p - yy) * v).astype(dt)
        sigma = ((jnp.sqrt(ni + g * g) - jnp.sqrt(ni)) / alpha).astype(dt)
        z = z.at[i].add((g - sigma * w).astype(dt))
        n = n.at[i].add((g * g).astype(dt))
        return (z, n), None

    @jax.jit
    def fold(z, n, idx, val, y):
        (z, n), _ = jax.lax.scan(body, (z, n), (idx, val, y))
        return z, n

    return fold(jnp.asarray(z0, dt), jnp.asarray(n0, dt),
                jnp.asarray(idx, jnp.int32), jnp.asarray(val, dt),
                jnp.asarray(y, dt))
