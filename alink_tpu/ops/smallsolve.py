"""Batched small dense solves, TPU-shaped.

The rank-sized SPD normal equations ALS solves (reference:
NormalEquation.java's dense Cholesky, common/linalg/NormalEquation.java)
come a batch at a time: thousands of systems of the same small static
``n``. They are solved by Gaussian elimination without pivoting (valid for
SPD: the running pivot is a Schur complement's diagonal, positive by
definiteness; the reference's Cholesky makes the same assumption) with
the BATCH on the minor axis, so every operation of the elimination is
elementwise over the systems:

* ``"kernel"`` (``kernels/smallsolve.py``; a TPU, or anywhere under
  ``ALINK_TPU_PALLAS_INTERPRET=1``): 128 systems a grid step, their
  augmented matrices ``(n, n + 1, 128)`` resident in VMEM for the whole
  elimination, so a system is read from HBM once. float32 only.
* ``"xla"``: the same elimination as ``n`` passes of one ``fori_loop``
  over the whole batch. Every pass reads and writes the batch, so it is
  for small batches (the tests' sizes, the CPU) and other dtypes.

Accuracy: ~1e-6 relative on ridge-regularised Gram matrices of condition
~1e2 at n = 100 in float32 (``tests/test_smallsolve.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def solve_path(dtype, n: int) -> str:
    """Who eliminates: ``"kernel"`` where the Pallas kernel can run (see
    the module docstring), else ``"xla"``."""
    from ..kernels.smallsolve import kernel_available
    return "kernel" if kernel_available(dtype, n) else "xla"


def batched_spd_solve(A, b):
    """Solve ``A x = b`` for a batch of small SPD systems.

    ``A``: (..., n, n) SPD (e.g. Gram + ridge), ``b``: (..., n), with n a
    static small int. Returns (..., n)."""
    n = A.shape[-1]
    lead = A.shape[:-2]
    M = jnp.concatenate([A, b[..., None]], axis=-1).reshape(-1, n, n + 1)
    x = solve_augmented(jnp.moveaxis(M, 0, -1))           # (n, B)
    return jnp.moveaxis(x, -1, 0).reshape(lead + (n,))


def solve_augmented(M):
    """``M``: (n, J, B) with ``J > n``, system ``s`` is ``M[:, :n, s] x =
    M[:, n, s]`` (batch on the minor axis; columns past ``n`` are carried
    along and ignored). Returns ``x`` (n, B)."""
    n = M.shape[0]
    if solve_path(M.dtype, n) == "kernel":
        from ..kernels.smallsolve import solve_augmented_kernel
        return solve_augmented_kernel(M)
    rows = jnp.arange(n)[:, None]

    def eliminate(k, M):
        # Gauss-Jordan: the pivot row normalised, then taken out of every
        # other row; after n steps column n holds the solution
        rowk = jax.lax.dynamic_index_in_dim(M, k, 0, keepdims=False)
        rowk = rowk / jax.lax.dynamic_index_in_dim(rowk, k, 0)
        colk = jax.lax.dynamic_index_in_dim(M, k, 1, keepdims=False)
        f = jnp.where(rows == k, 0, colk)                  # (n, B)
        M = M - f[:, None, :] * rowk[None]
        return jax.lax.dynamic_update_index_in_dim(M, rowk, k, 0)

    return jax.lax.fori_loop(0, n, eliminate, M)[:, n, :]
