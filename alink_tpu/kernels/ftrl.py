"""Hand-written Pallas kernels for the sparse FTRL hot loop (ISSUE 13).

PR 6's ceiling-anatomy note (docs/performance.md "Reaching the
roofline") established that strict FTRL cannot drop below O(B)
dependent ops — so the remaining win is making each dependent op cheap.
The two ops XLA refuses to make cheap on TPU are exactly the two this
module replaces:

* **state gather/scatter** — XLA serializes random gather/scatter
  (~5M touched elements/s measured, the ftrl.py wall). The kernels here
  keep the (z, n) slot tiles resident in VMEM: :func:`gather_rows` is
  one VMEM-indexed read of the touched slots, :func:`scatter_add_rows`
  grids over contiguous slot blocks and applies every update to its
  block with a sequential select-accumulate — duplicate slots
  accumulate in update order, which makes the kernel BITWISE-identical
  to XLA's in-order scatter-add (``.at[idx].add``), pinned by
  tests/test_kernels.py. Untouched slots pass through by *selection*
  (never ``+ 0.0``, which would flip ``-0.0``), so the whole state
  round-trips bitwise.
* **the chained-correction einsum** — the dense (K, w, 2) correction
  einsum in ``_ftrl_sparse_chained_step_factory`` contracts over all K
  delta rows even though rows ``j >= k`` are structurally zero.
  :func:`chained_corr` grids over exactly the ``k`` live rows (the
  triangle the dense einsum pays double for) and accumulates
  ``M[k, j] @ D[j]`` in full input precision (the
  ``Precision.HIGHEST`` contract of the XLA path, so chained parity
  stays inside the pinned 1e-12 tolerance).

Availability/demotion ride :mod:`alink_tpu.kernels.runtime` (the
``ALINK_TPU_FUSED_HIST`` contract): kernels run on TPU or under
``ALINK_TPU_PALLAS_INTERPRET=1``, demote to the XLA path with a
one-time warning otherwise, and the flag-off factories lower
byte-identically to pre-kernel-tier programs.

``ALINK_TPU_FTRL_KERNEL`` gates the tier; the RESOLVED mode rides the
FTRL step factories' lru keys (a toggle can never serve a stale step
program) and — in chained mode — the checkpoint signature (the
triangular accumulation order differs from the dense einsum's at the
last ulp, so a chained resume refuses across the toggle).
"""

from __future__ import annotations

import numpy as np

from .runtime import demote_once, eager_probe, interpret_mode, \
    pallas_available

__all__ = ["ftrl_kernel_mode", "gather_rows", "scatter_add_rows",
           "chained_corr", "FTRL_KERNEL_ENV"]

FTRL_KERNEL_ENV = "ALINK_TPU_FTRL_KERNEL"

# scatter grid: slot blocks of this many state rows live in VMEM per
# grid step (f64 on the CPU rig: 512 * 2 * 8 B = 8 KB per (z, n) tile)
_SLOT_BLOCK = 512


def ftrl_kernel_mode() -> str:
    """Resolved FTRL kernel mode: ``"off"`` (default) | ``"pallas"``.

    ``ALINK_TPU_FTRL_KERNEL`` values: 0/off/false -> "off"; anything
    truthy -> "pallas" when the backend can run it (TPU, or
    ``ALINK_TPU_PALLAS_INTERPRET=1``), else a RECORDED demotion to
    "off" (one RuntimeWarning per process +
    ``alink_kernel_demotions_total``). The RESOLVED mode is what the
    step factories fold into their lru keys, so the interpret flag
    needs no fold of its own."""
    from ..common.flags import flag_value
    v = flag_value(FTRL_KERNEL_ENV)
    if v == "off":
        return "off"
    if not pallas_available():
        demote_once("ftrl_scatter", "backend-unavailable",
                    "ALINK_TPU_FTRL_KERNEL requested but the backend is "
                    "not TPU and ALINK_TPU_PALLAS_INTERPRET is off")
        return "off"
    return "pallas"


def _pl():
    from jax.experimental import pallas as pl
    return pl


# ---------------------------------------------------------------------------
# gather
# ---------------------------------------------------------------------------

def _gather_call(state, idx2):
    import jax
    import jax.numpy as jnp
    pl = _pl()
    S, C = state.shape
    M = idx2.shape[0]

    def kernel(st_ref, idx_ref, out_ref):
        # the whole state tile is VMEM-resident; the touched slots read
        # out in one vectorized index (no serialized HBM gather)
        out_ref[...] = st_ref[...][idx_ref[...][:, 0]]

    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec((S, C), lambda: (0, 0)),
                  pl.BlockSpec((M, 1), lambda: (0, 0))],
        out_specs=pl.BlockSpec((M, C), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), state.dtype),
        interpret=interpret_mode(),
    )(state, idx2)


def gather_rows(state, idx):
    """``state[idx]`` with the state tile VMEM-resident.

    ``state``: (S,) or (S, C); ``idx``: (M,) int32 in [0, S). Bitwise-
    identical to the XLA gather (plain vectorized indexing of the same
    values)."""
    import jax.numpy as jnp
    squeeze = state.ndim == 1
    st = state[:, None] if squeeze else state
    out = _gather_call(st, idx.astype(jnp.int32)[:, None])
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# duplicate-safe scatter-add
# ---------------------------------------------------------------------------

def _scatter_call(state, idx2, upd):
    import jax
    import jax.numpy as jnp
    pl = _pl()
    S, C = state.shape
    M = idx2.shape[0]
    BS = min(_SLOT_BLOCK, S)
    Sp = -(-S // BS) * BS
    if Sp != S:                     # pad slots are never addressed
        state = jnp.concatenate(
            [state, jnp.zeros((Sp - S, C), state.dtype)])

    def kernel(idx_ref, upd_ref, st_ref, out_ref):
        b = pl.program_id(0)
        ids = (jax.lax.broadcasted_iota(jnp.int32, (BS, 1), 0)[:, 0]
               + b * BS)
        iv = idx_ref[...][:, 0]                       # (M,)
        u = upd_ref[...]                              # (M, C)

        def body(j, acc):
            # SELECT, not add: untouched slots keep their bits (adding
            # 0.0 would canonicalize -0.0), touched slots accumulate
            # fl(acc + u[j]) in update order — XLA's in-order
            # scatter-add semantics, hence the bitwise contract
            m = (iv[j] == ids)[:, None]
            return jnp.where(m, acc + u[j][None, :], acc)

        out_ref[...] = jax.lax.fori_loop(0, M, body, st_ref[...])

    out = pl.pallas_call(
        kernel,
        grid=(Sp // BS,),
        in_specs=[pl.BlockSpec((M, 1), lambda b: (0, 0)),
                  pl.BlockSpec((M, C), lambda b: (0, 0)),
                  pl.BlockSpec((BS, C), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((BS, C), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((Sp, C), state.dtype),
        interpret=interpret_mode(),
    )(idx2, upd, state)
    return out[:S] if Sp != S else out


def scatter_add_rows(state, idx, upd):
    """``state.at[idx].add(upd)`` as a slot-blocked Pallas kernel.

    ``state``: (S,) or (S, C); ``idx``: (M,); ``upd``: (M,) or (M, C).
    Grid over contiguous slot blocks, each (z, n) tile VMEM-resident;
    duplicate indices accumulate in update order (duplicate-safe AND
    bitwise vs the XLA scatter-add, tests/test_kernels.py)."""
    import jax.numpy as jnp
    squeeze = state.ndim == 1
    st = state[:, None] if squeeze else state
    up = upd[:, None] if squeeze else upd
    out = _scatter_call(st, idx.astype(jnp.int32)[:, None], up)
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# chained-correction triangular matvec
# ---------------------------------------------------------------------------

def chained_corr(Mk, D, k: int):
    """``sum_{j<k} Mk[j] @ D[j]`` — the chained-correction matvec with
    the structurally-zero rows ``j >= k`` skipped.

    ``Mk``: (K, w, w) collision tensor row of sample ``k``; ``D``:
    (K, w, 2) stacked delta buffer; ``k`` static (the unrolled chunk
    position). The dense einsum the XLA path pays contracts all K rows;
    this kernel grids over exactly the ``k`` live ones, accumulating in
    full input precision (the ``Precision.HIGHEST`` contract — no MXU
    bf16 rounding of the f32/f64 deltas), so chained parity stays
    inside the pinned 1e-12 tolerance (association-only difference).
    """
    import jax
    import jax.numpy as jnp
    pl = _pl()
    K, w, _ = Mk.shape
    C = D.shape[2]
    if k == 0:
        return jnp.zeros((w, C), D.dtype)

    def kernel(m_ref, d_ref, out_ref):
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        # HIGHEST: on the MXU the default precision rounds the f32
        # deltas to bf16 (measured on a v5e: the kernel then misses the
        # XLA path's Precision.HIGHEST einsum at the 1e-3 level)
        out_ref[...] += jnp.dot(m_ref[...][0], d_ref[...][0],
                                preferred_element_type=out_ref.dtype,
                                precision=jax.lax.Precision.HIGHEST)

    return pl.pallas_call(
        kernel,
        grid=(k,),                     # rows j >= k never enter the grid
        in_specs=[pl.BlockSpec((1, w, w), lambda j: (j, 0, 0)),
                  pl.BlockSpec((1, w, C), lambda j: (j, 0, 0))],
        out_specs=pl.BlockSpec((w, C), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((w, C), D.dtype),
        interpret=interpret_mode(),
    )(Mk[:k], D[:k])


# ---------------------------------------------------------------------------
# eager probes (one per shape class per process)
# ---------------------------------------------------------------------------

def probe_scatter(S: int, C: int, dtype) -> bool:
    """Compile+run a gather+scatter instance at this state shape class
    — the ACTUAL state extent, not a capped stand-in — before the step
    program traces the kernels in; probe failure demotes (one-time
    warning) and the XLA path is chosen at trace time.

    Probing at the real ``S`` matters: ``gather_rows`` stages the
    whole (S, C) state tile in VMEM, so a large sharded model can
    overflow VMEM at exactly the shapes a smaller probe would pass —
    the hist.py precedent (probe per level-shape class). The probe
    state is zeros (one transient (S, C) allocation per shape class
    per process, memoized)."""
    dt = np.dtype(dtype)

    def probe():
        import jax.numpy as jnp
        st = jnp.zeros((S, C), dt)
        ix = jnp.zeros((8,), jnp.int32)
        np.asarray(_scatter_call(st, ix[:, None], jnp.zeros((8, C), dt)))
        np.asarray(_gather_call(st, ix[:, None]))

    return eager_probe("ftrl_scatter", ("zn", S, C, dt.name), probe)


def probe_chained(K: int, w: int, dtype) -> bool:
    dt = np.dtype(dtype)

    def probe():
        import jax.numpy as jnp
        np.asarray(chained_corr(jnp.zeros((K, w, w), dt),
                                jnp.zeros((K, w, 2), dt), max(K - 1, 1)))

    return eager_probe("ftrl_chained", ("corr", K, w, dt.name), probe)


# the chained kernel's availability probe runs at ONE canonical width:
# the chained checkpoint signature must describe the accumulation
# association the drain will ACTUALLY trace, and a per-batch-width
# probe could demote some widths and not others — leaving a snapshot
# whose signature misdescribes its arithmetic. Probing capability once
# per (K, dtype) keeps the link-time signature fold and the trace-time
# kernel selection deterministically identical; a genuinely
# width-specific compile failure (VMEM at extreme widths) then
# surfaces as a LOUD compile error instead of a silent mid-stream
# association change.
_CHAINED_PROBE_W = 8


def chained_kernel_available(K: int, dtype) -> bool:
    """Can the chained triangular kernel run at this (chunk length,
    dtype) on this backend? Memoized; the chained step factory AND the
    FTRL drain's checkpoint-signature fold both resolve through here,
    so they can never disagree."""
    return probe_chained(K, _CHAINED_PROBE_W, dtype)
