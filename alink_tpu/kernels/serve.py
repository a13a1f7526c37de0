"""Fused serving score kernel + the opt-in low-precision score path
(ISSUE 13 tentpole (3)).

``CompiledPredictor``'s linear bucket programs round-trip their
intermediates through HBM: the sparse path materializes the gathered
``val * w[idx]`` term tensor, the dense path the ``X * w`` product,
before the strict left-to-right ``seq_chunk_sum`` scan reduces them.
The fused kernel (``ALINK_TPU_SERVE_FUSED=1``) runs
encode-gather -> dot -> link (bias) in ONE Pallas kernel: the weight
vector and the request block live in VMEM, terms are produced and
consumed in registers/VMEM, and the only HBM traffic is the encoded
request in and the scores out.

**The reduction-order contract.** The kernel accumulates the per-row
dot product with EXACTLY ``seq_chunk_sum``'s arithmetic: terms rounded
first (a separate multiply, never an FMA), then added strictly left to
right from a zero accumulator, bias added last. Same ops, same order,
same rounding — so fused scores are BITWISE-identical to the XLA
programs at every bucket (padding stays a proven no-op) and the
PR 10/11 bucket/mesh-invariance contracts survive untouched
(tests/test_kernels.py pins fused-vs-unfused bitwise per bucket, and
mesh 1/4/8 sharded parity with the flag on).

**Low precision** (``ALINK_TPU_SERVE_DTYPE=f32|bf16|int8``, default
f32 = the full-precision ship dtype):

* ``bf16`` — weights stored bf16, request cast to bf16, per-term
  product rounds in bf16, accumulation in f32 (the classic inference
  recipe);
* ``int8`` — symmetric per-model weight quantization
  ``w_q = clip(round(w / s), -127, 127)`` with ONE stored scale
  ``s = max|w| / 127``; products and accumulation in f32, the scale
  applied once to the accumulated sum.

Both are gated by a parity test that is bitwise for f32 and
label-exact + pinned-tolerance for bf16/int8; the resolved (dtype,
fused) pair rides the ServingKernel SIGNATURE, i.e. the serving
program-cache key — a toggle compiles new programs, never reuses a
stale one. Every demotion (backend unavailable, probe failure,
softmax/sharded unsupported) records through the existing
``record_serve_fallback`` / ``alink_serve_fallback_total`` machinery.
"""

from __future__ import annotations

import numpy as np

from .runtime import eager_probe, interpret_mode, pallas_available

__all__ = ["SERVE_FUSED_ENV", "SERVE_DTYPE_ENV", "serve_dtype",
           "serve_fused_requested", "resolve_serve_kernel",
           "quantize_int8", "lowp_model_arrays", "make_linear_score_fns"]

SERVE_FUSED_ENV = "ALINK_TPU_SERVE_FUSED"
SERVE_DTYPE_ENV = "ALINK_TPU_SERVE_DTYPE"


def serve_dtype() -> str:
    """``ALINK_TPU_SERVE_DTYPE``: the resolved serving score dtype —
    ``f32`` (default: full ship precision) | ``bf16`` | ``int8``."""
    from ..common.flags import flag_value
    return str(flag_value(SERVE_DTYPE_ENV))


def serve_fused_requested() -> bool:
    """``ALINK_TPU_SERVE_FUSED``: request the fused Pallas score kernel
    for linear serving programs (default off)."""
    from ..common.flags import flag_value
    return bool(flag_value(SERVE_FUSED_ENV, False))


def resolve_serve_kernel(mapper_name: str, dim8: int, ship_dt,
                         supported: bool = True):
    """Resolve the (fused, dtype) pair for ONE serving-kernel build.

    ``supported=False`` (softmax): the fused/low-precision tier serves
    the binary/regression family only — a request on an unsupported
    mapper records a fallback and serves the exact f32 XLA path.
    An unavailable backend or (off-TPU) a failed eager probe demotes
    ``fused`` (recorded); on TPU a failed probe raises
    (``runtime.refuse_on_tpu``). The dtype path is pure XLA-or-Pallas
    arithmetic and needs no backend gate."""
    from ..serving.predictor import record_serve_fallback
    dtype = serve_dtype()
    fused = serve_fused_requested()
    if not (fused or dtype != "f32"):
        return False, "f32"
    if not supported:
        record_serve_fallback(mapper_name, "fused-unsupported",
                              "softmax serves the exact f32 XLA path")
        return False, "f32"
    if fused:
        if not pallas_available():
            record_serve_fallback(
                mapper_name, "pallas-unavailable",
                "ALINK_TPU_SERVE_FUSED needs a TPU backend or "
                "ALINK_TPU_PALLAS_INTERPRET=1")
            fused = False
        elif not _probe_fused(dim8, dtype, ship_dt):
            record_serve_fallback(
                mapper_name, "fused-probe-failed",
                f"score kernel failed to compile at dim {dim8}")
            fused = False
    return fused, dtype


# ---------------------------------------------------------------------------
# weight quantization (int8 path)
# ---------------------------------------------------------------------------

def quantize_int8(w: np.ndarray):
    """Symmetric per-model weight quantization: ``(w_q int8, scale)``
    with ``scale = max|w| / 127`` (1.0 for an all-zero model) and
    ``w_q = clip(round(w / scale), -127, 127)``."""
    a = float(np.max(np.abs(w))) if w.size else 0.0
    scale = a / 127.0 if a > 0.0 else 1.0
    q = np.clip(np.rint(np.asarray(w, np.float64) / scale),
                -127, 127).astype(np.int8)
    return q, np.float32(scale)


def lowp_model_arrays(w: np.ndarray, b, dtype: str):
    """The model-array tuple of one low-precision linear kernel:
    ``bf16`` -> (w_bf16, b_f32); ``int8`` -> (w_q, scale, b_f32)."""
    import jax.numpy as jnp
    if dtype == "bf16":
        return (np.ascontiguousarray(np.asarray(w, jnp.bfloat16.dtype)),
                np.asarray(b, np.float32))
    if dtype == "int8":
        q, scale = quantize_int8(np.asarray(w))
        return (np.ascontiguousarray(q), np.asarray([scale], np.float32),
                np.asarray(b, np.float32))
    raise ValueError(f"lowp_model_arrays: dtype {dtype!r} (want bf16/int8)")


def _unpack(mdl, dtype: str):
    """(w_terms, scale_or_None, b) in the dtype's TERM precision."""
    import jax.numpy as jnp
    if dtype == "int8":
        q, scale, b = mdl
        return q.astype(jnp.float32), scale[0], b
    w, b = mdl
    return w, None, b


def _acc_dtype(dtype: str, ship_dt):
    import jax.numpy as jnp
    return ship_dt if dtype == "f32" else jnp.float32


# ---------------------------------------------------------------------------
# XLA score fns (the dtype path when fused is off/demoted)
# ---------------------------------------------------------------------------

def make_xla_score_fns(dtype: str, ship_dt):
    """Low-precision XLA twins of the mapper's inline f32 device fns —
    same ``seq_chunk_sum`` strict order, dtype-adjusted terms. (The
    f32 path never routes here: the mapper keeps its pre-existing
    inline fns so the flag-off HLO stays byte-identical.)"""
    import jax.numpy as jnp
    from ..serving.sharded import seq_chunk_sum
    acc_dt = _acc_dtype(dtype, ship_dt)

    def _terms_dense(X, w):
        if dtype == "bf16":
            return (X.astype(jnp.bfloat16) * w[None, :]).astype(acc_dt)
        return X.astype(acc_dt) * w[None, :]

    def _dense(mdl, X):
        w, scale, b = _unpack(mdl, dtype)
        acc = seq_chunk_sum(_terms_dense(X, w), axis=1)
        if scale is not None:
            acc = acc * scale
        return acc + b.astype(acc_dt)

    def _sparse(mdl, idx, val):
        w, scale, b = _unpack(mdl, dtype)
        g = w[idx]
        if dtype == "bf16":
            terms = (val.astype(jnp.bfloat16) * g).astype(acc_dt)
        else:
            terms = val.astype(acc_dt) * g
        acc = seq_chunk_sum(terms, axis=1)
        if scale is not None:
            acc = acc * scale
        return acc + b.astype(acc_dt)

    return {"dense": _dense, "sparse": _sparse}


# ---------------------------------------------------------------------------
# the fused Pallas score kernels
# ---------------------------------------------------------------------------

def _term_dt(dtype: str):
    """The per-term rounding dtype: bf16 terms MUST round in bf16
    before entering the f32 add chain. The explicit astype matters:
    interpret mode (and any backend that computes the product wide)
    would otherwise carry extra precision and diverge from the XLA
    twin's term-rounded arithmetic."""
    import jax.numpy as jnp
    return jnp.bfloat16 if dtype == "bf16" else None


def _reduce_terms(terms, acc_dt, term_dt):
    """The in-kernel reduction: term rounding (bf16 mode) + the
    CANONICAL ``seq_chunk_sum`` over the feature axis.

    Calling the literal ``serving/sharded.seq_chunk_sum`` inside the
    kernel body matters beyond code reuse: the kernel compiles through
    XLA too (Mosaic on TPU, the interpreter's jit elsewhere), and XLA's
    mul->add FMA contraction is PATTERN-dependent — a fori_loop
    accumulation here measured 1 ulp off the XLA twin's unrolled chain
    on the CPU rig. Identical structure -> identical contraction ->
    bitwise parity (tests/test_kernels.py pins it)."""
    from ..serving.sharded import seq_chunk_sum
    if term_dt is not None:
        terms = terms.astype(term_dt)
    return seq_chunk_sum(terms.astype(acc_dt), axis=1)


def _fused_dense_call(w2, X, acc_dt, term_dt):
    import jax
    from jax.experimental import pallas as pl
    n, dim8 = X.shape

    def kernel(w_ref, x_ref, out_ref):
        # terms materialize IN VMEM; gather -> product -> strict
        # reduction without an HBM round-trip in between
        terms = x_ref[...] * w_ref[...]
        out_ref[...] = _reduce_terms(terms, acc_dt, term_dt)[:, None]

    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec((1, dim8), lambda: (0, 0)),
                  pl.BlockSpec((n, dim8), lambda: (0, 0))],
        out_specs=pl.BlockSpec((n, 1), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), acc_dt),
        interpret=interpret_mode(),
    )(w2, X)[:, 0]


def _fused_sparse_call(w2, idx, val, acc_dt, term_dt):
    import jax
    from jax.experimental import pallas as pl
    n, width = idx.shape
    dim8 = w2.shape[1]

    def kernel(w_ref, idx_ref, val_ref, out_ref):
        w = w_ref[...][0]                       # (dim8,) VMEM-resident
        g = w[idx_ref[...]]                     # the encode-gather, in VMEM
        terms = val_ref[...] * g
        out_ref[...] = _reduce_terms(terms, acc_dt, term_dt)[:, None]

    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec((1, dim8), lambda: (0, 0)),
                  pl.BlockSpec((n, width), lambda: (0, 0)),
                  pl.BlockSpec((n, width), lambda: (0, 0))],
        out_specs=pl.BlockSpec((n, 1), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), acc_dt),
        interpret=interpret_mode(),
    )(w2, idx, val)[:, 0]


def make_fused_score_fns(dtype: str, ship_dt):
    """The fused encode-gather -> dot -> link kernels as drop-in
    ``device_fns`` twins: ``{kind: fn(model_arrays, *encoded)}``.

    f32 outputs are bitwise-identical to the XLA ``seq_chunk_sum``
    programs (same terms, same strict left-to-right adds, bias last);
    bf16/int8 outputs are bitwise-identical to their
    :func:`make_xla_score_fns` twins."""
    import jax.numpy as jnp
    acc_dt = _acc_dtype(dtype, ship_dt)
    term_dt = _term_dt(dtype)

    def _link(acc, scale, b):
        # scale + bias apply OUTSIDE the kernel, in the same jit
        # computation as the XLA twin's: inside the kernel body the
        # backend can FMA-contract ``acc * scale + b`` into a single
        # rounding and break bitwise fused-vs-XLA parity (the PR 11
        # lane_partials lesson, measured again here in interpret mode)
        if scale is not None:
            acc = acc * scale
        return acc + b.astype(acc_dt)

    def _dense(mdl, X):
        w, scale, b = _unpack(mdl, dtype)
        if dtype == "bf16":
            X = X.astype(jnp.bfloat16)
        elif dtype == "int8":
            X = X.astype(jnp.float32)
        return _link(_fused_dense_call(w.reshape(1, -1), X, acc_dt,
                                       term_dt), scale, b)

    def _sparse(mdl, idx, val):
        w, scale, b = _unpack(mdl, dtype)
        if dtype == "bf16":
            val = val.astype(jnp.bfloat16)
        elif dtype == "int8":
            val = val.astype(jnp.float32)
        return _link(_fused_sparse_call(w.reshape(1, -1),
                                        idx.astype(jnp.int32), val,
                                        acc_dt, term_dt), scale, b)

    return {"dense": _dense, "sparse": _sparse}


def make_linear_score_fns(fused: bool, dtype: str, ship_dt):
    """The linear family's score fns under the RESOLVED (fused, dtype)
    pair. The (False, "f32") combination never routes here — the
    mapper keeps its pre-existing inline fns so the flag-off lowered
    HLO stays byte-identical to pre-kernel-tier programs."""
    if fused:
        return make_fused_score_fns(dtype, ship_dt)
    return make_xla_score_fns(dtype, ship_dt)


# sparse probe width: requests pad their nnz width in chunk steps; 64
# is a generous ceiling for hashed CTR rows. A pathological width
# beyond it can still surface a compile error at dispatch — the probe
# gates the realistic envelope, not every conceivable request.
_SPARSE_PROBE_W = 64


def _probe_fused(dim8: int, dtype: str, ship_dt) -> bool:
    """Eagerly compile+run dense+sparse fused-kernel instances at this
    model's feature width AND the largest configured bucket before the
    kernel reaches a serving program trace (runtime.eager_probe: once
    per shape class; failure demotes with the one-time warning AND the
    serve fallback record).

    The bucket matters: the kernel stages the whole (bucket, dim8)
    request block in VMEM, so the top bucket at a wide model is
    exactly where a 2-row probe would pass and the real program would
    overflow. Requests beyond the top bucket chunk AT the top bucket,
    so probing max(serve_buckets()) covers every default program."""
    import numpy as _np

    from ..serving.predictor import serve_buckets
    rows = max(serve_buckets())

    def probe():
        import jax.numpy as jnp
        fns = make_fused_score_fns(dtype, ship_dt)
        mdl_w = _np.linspace(-1, 1, dim8)
        if dtype in ("bf16", "int8"):
            mdl = lowp_model_arrays(mdl_w, 0.0, dtype)
        else:
            mdl = (_np.asarray(mdl_w, ship_dt), _np.asarray(0.0, ship_dt))
        mdl = tuple(jnp.asarray(a) for a in mdl)
        _np.asarray(fns["dense"](mdl, jnp.zeros((rows, dim8), ship_dt)))
        _np.asarray(fns["sparse"](
            mdl, jnp.zeros((rows, _SPARSE_PROBE_W), jnp.int32),
            jnp.zeros((rows, _SPARSE_PROBE_W), ship_dt)))

    dt = _np_dtype_name(ship_dt)
    return eager_probe("serve_fused", ("linear", dim8, rows, dtype, dt),
                       probe)


def _np_dtype_name(ship_dt) -> str:
    try:
        return np.dtype(ship_dt).name
    except TypeError:  # a jnp scalar type
        return str(ship_dt)
