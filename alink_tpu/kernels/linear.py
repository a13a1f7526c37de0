"""The two passes of a quasi-Newton superstep over a blocked table of
BYTES, each as one streamed Pallas kernel (ISSUE 38): the multinomial
objective's gradient pass (:func:`grad_pass`, device op ``qn_grad_pass``)
and its line-search pass (:func:`line_pass`, ``qn_line_pass``).

As XLA compiles the walk of ``optim/objfunc.py`` (``block_at`` +
``block_forward`` / ``block_backward``), a block of the table is copied
out twice a superstep and each of the three MXU products (forward,
backward, direction) casts the block's bytes to bfloat16 and lays them
out for the MXU anew, inside the product. Here the grid walks the
worker's blocks (a block too wide for VMEM cut over its sublane axis);
the table block, labels, weights and the kept logits ride ``BlockSpec``s
on the leading axis, so block ``i + 1`` is fetched under block ``i``'s
arithmetic and nothing is copied. The table is feature-major, ``(d, S,
128)``: a 32-bit word of a byte block holds four ROWS of one feature,
and the MXU wants the features along a register's sublanes. That turn
is taken once a pass, BEFORE the bytes are widened: the block is read
as 32-bit words with a sublane stride (``pl.ds(..., stride=)`` on the
block seen flat: ONE strided load a register of eight features; an
integer index on the sublane axis reads a sublane at a time and was
4 × slower, PERF.md §6, PR 38), a hundred-odd features at a time so
that they stay in registers, and each of a word's four bytes is shifted
out (an unsigned byte by a logical shift and a mask, a signed one by a
shift to the word's top and an arithmetic shift back, which extends its
sign) and widened to bfloat16, giving ``(features, 128)`` of 128 rows.
Pass 1 feeds both its products from that one copy (the logits, then on
the values in registers softmax, loss and residual, then the gradient's
block sum); pass 2 the direction's logits and, from them and the kept
logits, the whole ladder.

The arithmetic is the XLA walk's own: bytes exact in bfloat16, the
coefficients / residuals / direction as THREE bfloat16 parts split by
bit mask (:func:`split3`), float32 accumulation on the MXU, the parts
joined low to high. What differs is the order of a block's float32 sums
(a row's products are summed over the features in the MXU's order; a
block's sums over its rows lane by lane, then over the lanes), so the
two walks agree to float32's grade, not bitwise; the rows are counted
as whole numbers and agree exactly. Which walk a pass takes is read from
its input (:func:`pass_path`), never set.
"""

from __future__ import annotations

import functools

from .runtime import interpret_mode, pallas_available

__all__ = ["split3", "pass_path", "grad_pass", "line_pass"]

_LANES = 128
#: sublanes of one float32 register
_TILE = 8
#: rows of a byte block one 32-bit word holds (a register of bytes is 32
#: sublanes of 128)
_PACK = 4
#: the MXU's columns: the three stacked parts must fit them
_MXU = 128
#: the table's two pipeline buffers may take this much VMEM; a block
#: wider than half of it is cut over its sublane axis. Small on purpose:
#: at 784 features a step of 32 sublanes ran a pass in 182 µs a block,
#: of 64 in 192, of 128 in 221 (PERF.md §6, PR 38)
_TABLE_VMEM = 8 << 20
#: features up to which the kernels run: two pipeline buffers of the
#: narrowest step (32 sublanes) of a wider table would not fit VMEM
_WIDEST = 8192
#: features widened at a time: one K tile of the MXU at most, in whole
#: bfloat16 registers (16 sublanes), few enough to stay in registers
_FEATURES = 128


def split3(a):
    """float ``a`` ``(m, ...)`` as three bfloat16 parts stacked ``(3 m,
    ...)`` whose sum is ``a`` to float32's 24 bits: each part is what is
    left with its low 16 bits CLEARED (a mask on the bits, not a rounding:
    XLA:TPU folds the float32 -> bfloat16 -> float32 round trip a rounding
    form subtracts away; PERF.md, PR 31). One product of the stack against
    an operand that is exact in bfloat16 (a byte) is a float32-grade
    product at a quarter of the MXU's columns."""
    import jax
    import jax.numpy as jnp
    a = a.astype(jnp.float32)
    mask = jnp.uint32(0xFFFF0000)
    bits = jax.lax.bitcast_convert_type

    def top(v):
        return bits(bits(v, jnp.uint32) & mask, jnp.float32)
    hi = top(a)
    mid = top(a - hi)
    lo = (a - hi) - mid
    return jnp.concatenate([hi, mid, lo], 0).astype(jnp.bfloat16)


def _padded(m: int) -> int:
    """Rows a part of ``m`` takes in the stack: whole float32 registers."""
    return -(-m // _TILE) * _TILE


def pass_path(dtype, d: int, S: int, m: int) -> str:
    """``"kernel"`` where :func:`grad_pass` and :func:`line_pass` can
    run — a backend that executes Pallas (a TPU, or the interpreter the
    tier-1 rig turns on), a table of one-byte integers whose blocks are
    whole 8-bit register tiles (``S % 32 == 0``), and ``m`` coefficient
    rows whose three stacked parts, each in whole registers, fit the
    MXU's columns, over 2 to ``_WIDEST`` features (a block too wide for
    VMEM is cut over its sublane axis, down to one register tile of
    bytes; Mosaic refuses a product over ONE feature) — else ``"xla"``.
    ``tests/test_tpu_compile.py`` compiles the corners of this envelope
    for a described v5e."""
    import numpy as np
    dt = np.dtype(dtype)
    ok = pallas_available() and dt.kind in "iu" and dt.itemsize == 1 \
        and S % (_PACK * _TILE) == 0 and 3 * _padded(m) <= _MXU \
        and 2 <= d <= _WIDEST
    if ok:
        # loaded here, before the engine traces the step: Pallas takes
        # over a second to import, once a process (kmeans.fold_path)
        import jax.experimental.pallas.tpu  # noqa: F401
    return "kernel" if ok else "xla"


def _vmem_limit(d: int, Sb: int, mp: int) -> int:
    """VMEM a pass may take: the table's two buffers, the widened word,
    the gradient's sums four times over, and room for the rest."""
    return 2 * d * Sb * _LANES + d * _PACK * _LANES * 2 \
        + 4 * 3 * mp * d * 4 + (16 << 20)


def _sublanes_per_step(d: int, S: int) -> int:
    """Sublanes of a byte block one grid step holds: all ``S`` where two
    table blocks fit ``_TABLE_VMEM``, else the largest divisor of ``S``
    in whole 8-bit register tiles that does."""
    q = _PACK * _TILE
    fit = max(_TABLE_VMEM // (2 * d * _LANES), q)
    return max(s for s in range(q, min(S, fit) + 1, q) if S % s == 0)


def _chunks(d: int):
    """``d`` features cut into runs of at most ``_FEATURES``, as equal as
    whole bfloat16 registers allow: ``(runs, size, last)``, every run
    ``size`` long but the last (784: 7 × 112)."""
    n = -(-d // _FEATURES)
    size = min(-(-d // (2 * _TILE * n)) * 2 * _TILE, d)
    n = -(-d // size)
    return n, size, d - (n - 1) * size


def _stack(A, mp: int):
    """Coefficient rows ``A`` ``(m, d)`` as the MXU's left operand: each
    row padded to ``mp`` with zeros, split in three, and cut into the
    runs of features the kernels widen at a time, ``(runs, 3 mp, size)``
    bfloat16."""
    import jax.numpy as jnp
    m, d = A.shape
    n, size, _ = _chunks(d)
    A = jnp.pad(A.astype(jnp.float32), ((0, mp - m), (0, n * size - d)))
    return split3(A).reshape(3 * mp, n, size).transpose(1, 0, 2)


def _spread(b, mp: int):
    """The intercepts ``b`` ``(m,)`` a row of lanes each, ``(mp, 128)``."""
    import jax.numpy as jnp
    m = b.shape[0]
    return jnp.broadcast_to(
        jnp.pad(b.astype(jnp.float32), (0, mp - m))[:, None], (mp, _LANES))


def _widen_run(flat, q, f, words: int, width: int, signed: bool):
    """Word ``q`` of features ``f … f + width - 1`` of a byte block
    widened to bfloat16, ``(width, 512)``: rows ``4 q … 4 q + 3`` of the
    block side by side, a row 128 lanes. ``flat`` is the block seen as
    32-bit words, ``(d · words, 128)``, ``words`` of them a feature: the
    run is ONE strided load (a register of eight features), then a byte
    is shifted out of each word, unsigned by a logical shift and a mask,
    signed by a shift to the word's top and an arithmetic shift back
    (which extends its sign), and widened. Both byte kernels' walks
    (``_word_product`` here, ``kernels/mlp.py``) read a block through
    it."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    W = flat[pl.ds(q + f * words, width, stride=words), :]
    top = _PACK - 1                     # a word's highest byte
    parts = []
    for j in range(_PACK):
        if signed:
            v = W if j == top else lax.shift_left(
                W, jnp.int32(8 * (top - j)))
            v = lax.shift_right_arithmetic(v, jnp.int32(8 * top))
        else:
            v = W if j == 0 else lax.shift_right_logical(
                W, jnp.int32(8 * j))
            if j < top:
                v = v & 0xFF
        parts.append(v.astype(jnp.float32).astype(jnp.bfloat16))
    return jnp.concatenate(parts, 1)


def _word_product(x_ref, a_ref, q, xs_ref=None):
    """``a . x`` over the four row groups of word ``q`` of a byte block:
    ``x_ref`` ``(1, d, Sb, 128)`` bytes, ``a_ref`` ``(runs, rows, size)``
    bfloat16 (:func:`_stack`); returns ``(rows, 512)`` float32, a row
    group 128 lanes. A run of features is widened by :func:`_widen_run`,
    ``(size, 512)`` bfloat16, kept in ``xs_ref`` ``(d, 512)`` where
    given. The loop over the runs is traced once and unrolled when it is
    lowered (``kernels/kmeans.py``)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    _, d, Sb, L = x_ref.shape
    words = Sb // _PACK
    flat = x_ref.bitcast(jnp.int32).reshape(d * words, L)
    signed = jnp.issubdtype(x_ref.dtype, jnp.signedinteger)
    runs, size, last = _chunks(d)

    def run(c, p, width):
        f = pl.multiple_of(c * size, 2 * _TILE) if runs > 1 else 0
        x = _widen_run(flat, q, f, words, width, signed)
        if xs_ref is not None:
            xs_ref[pl.ds(f, width), :] = x
        return p + jnp.dot(a_ref[c][:, :width], x,
                           preferred_element_type=jnp.float32)

    p = jnp.zeros((a_ref.shape[1], _PACK * L), jnp.float32)
    whole = runs if last == size else runs - 1
    if whole:
        p = lax.fori_loop(0, whole, functools.partial(run, width=size), p,
                          unroll=True)
    return p if whole == runs else run(runs - 1, p, last)


def _lane_rows(ref, q):
    """Rows ``4 q … 4 q + 3`` of a ``(1, S, 128)`` block side by side,
    ``(1, 512)``: a row of lanes a row group, in :func:`_word_product`'s
    order."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    return jnp.concatenate(
        [ref[0, pl.ds(_PACK * q + j, 1), :] for j in range(_PACK)], 1)


def _fold_lanes(a):
    """``(r, 512)`` partial sums to ``(r, 128)``: the four row groups of
    a word added, lane by lane."""
    out = a[:, :_LANES]
    for j in range(1, a.shape[1] // _LANES):
        out = out + a[:, j * _LANES:(j + 1) * _LANES]
    return out


def _lane_sums(t, rows_at: int):
    """A pass's sums ``t`` ``(T, 128)``, a lane's each, over the lanes:
    ``(sums (T,) float32, rows int32)``. Row ``rows_at`` holds the rows
    seen, a whole number a lane that float32 holds exactly (under 2^24 a
    lane: 2^31 rows a shard); over the lanes they are added as int32,
    since their sum may pass 2^24."""
    import jax.numpy as jnp
    return t.sum(1), t[rows_at].astype(jnp.int32).sum(dtype=jnp.int32)


def _kahan_join(acc_ref, comp_ref, part):
    """``kahan_add`` of a grid step's sums into the pass's."""
    y = part - comp_ref[...]
    acc = acc_ref[...]
    t = acc + y
    comp_ref[...] = (t - acc) - y
    acc_ref[...] = t


# jitted: the step program's first superstep and its loop body call a
# pass on the same shapes, so a kernel is traced and lowered once (a
# trace of one costs the chip's host half a second and more)
@functools.lru_cache(maxsize=None)
def _jitted(fn):
    import jax
    return jax.jit(fn, static_argnames="interpret")


def grad_pass(Xs, ys, ws, A, b):
    """Pass 1 of a superstep over a shard as ONE ``pallas_call``: for
    every row the logits ``A . x + b`` (``A`` ``(m, d)``, ``b`` ``(m,)``:
    the folded coefficients of the ``m`` non-pivot classes), the
    multinomial loss and the residual ``w (softmax − onehot)``, and the
    shard's sums of them, a grid step's joined to the pass's with a Kahan
    compensation in block order. ``Xs`` is ``(nbl, d, S, 128)`` of
    one-byte integers, ``ys`` ``(nbl, S, 128)`` int32 class ids, ``ws``
    ``(nbl, S, 128)`` float32. Returns ``(G (m, d), tail (m + 2,), rows
    int32, logits (nbl, m, S, 128))``: ``G`` the residuals against the
    raw table, ``tail`` their sums, then the loss and the weight, ``rows``
    the rows of weight other than 0; float32 but ``rows``."""
    return _jitted(_grad_pass)(Xs, ys, ws, A, b, interpret=interpret_mode())


def _grad_pass(Xs, ys, ws, A, b, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nbl, d, S, L = Xs.shape
    m = A.shape[0]
    mp = _padded(m)
    f32 = jnp.float32
    Sb = _sublanes_per_step(d, S)
    n = _PACK * L
    T = mp + _TILE                       # the tail's rows: sums, then 3
    a = _stack(A, mp)

    def kernel(a_ref, b_ref, x_ref, y_ref, w_ref, z_ref, g_ref, t_ref,
               xs_ref, gpart_ref, gcomp_ref, tcomp_ref):
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _():
            for ref in (g_ref, t_ref, gcomp_ref, tcomp_ref):
                ref[...] = jnp.zeros(ref.shape, f32)

        gpart_ref[...] = jnp.zeros(gpart_ref.shape, f32)
        bias = jnp.concatenate([b_ref[...]] * _PACK, 1)
        cls = lax.broadcasted_iota(jnp.int32, (mp, n), 0)
        real = cls < m

        def word(q, c):
            dsum, loss, wsum, rows = c
            p = _word_product(x_ref, a_ref, q, xs_ref)
            z = (p[2 * mp:] + p[mp:2 * mp]) + p[:mp] + bias
            y, w = _lane_rows(y_ref, q), _lane_rows(w_ref, q)
            for j in range(_PACK):
                z_ref[0, :, _PACK * q + j, :] = z[:m, j * L:(j + 1) * L]
            # the pivot's logit is 0, as a padded row's is
            top = jnp.maximum(jnp.max(z, 0, keepdims=True), 0.0)
            e = jnp.where(real, jnp.exp(z - top), 0.0)
            den = jnp.sum(e, 0, keepdims=True) + jnp.exp(-top)
            hit = (cls == y) & real
            zy = jnp.sum(jnp.where(hit, z, 0.0), 0, keepdims=True)
            delta = w * (e / den - jnp.where(hit, f32(1), f32(0)))
            gpart_ref[...] += lax.dot_general(
                split3(delta), xs_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=f32)
            return (dsum + delta, loss + w * (top + jnp.log(den) - zy),
                    wsum + w, rows + jnp.where(w != 0, f32(1), f32(0)))

        zero = jnp.zeros((1, n), f32)
        sums = lax.fori_loop(
            jnp.int32(0), jnp.int32(Sb // _PACK), word,
            (jnp.zeros((mp, n), f32), zero, zero, zero))
        _kahan_join(g_ref, gcomp_ref,
                    (gpart_ref[2 * mp:] + gpart_ref[mp:2 * mp])
                    + gpart_ref[:mp])
        _kahan_join(t_ref, tcomp_ref, jnp.concatenate(
            [_fold_lanes(s) for s in sums]
            + [jnp.zeros((_TILE - 3, L), f32)], 0))

    rows = pl.BlockSpec((1, Sb, L), lambda i, s: (i, s, 0))
    z, G, t = pl.pallas_call(
        kernel,
        grid=(nbl, S // Sb),
        in_specs=[pl.BlockSpec(a.shape, lambda i, s: (0, 0, 0)),
                  pl.BlockSpec((mp, L), lambda i, s: (0, 0)),
                  pl.BlockSpec((1, d, Sb, L), lambda i, s: (i, 0, s, 0)),
                  rows, rows],
        out_specs=(pl.BlockSpec((1, m, Sb, L), lambda i, s: (i, 0, s, 0)),
                   pl.BlockSpec((mp, d), lambda i, s: (0, 0)),
                   pl.BlockSpec((T, L), lambda i, s: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((nbl, m, S, L), f32),
                   jax.ShapeDtypeStruct((mp, d), f32),
                   jax.ShapeDtypeStruct((T, L), f32)),
        scratch_shapes=[pltpu.VMEM((d, n), jnp.bfloat16),
                        pltpu.VMEM((3 * mp, d), f32),
                        pltpu.VMEM((mp, d), f32), pltpu.VMEM((T, L), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(d, Sb, mp)),
        interpret=interpret,
        name="qn_grad_pass",
    )(a, _spread(b, mp), Xs, ys, ws.astype(f32))
    t, seen = _lane_sums(t, mp + 2)
    tail = jnp.concatenate([t[:m], t[mp:mp + 2]])
    return G[:m], tail, seen, z


def line_pass(Xs, ys, ws, z0, A, b, steps):
    """Pass 2 of a superstep over a shard as ONE ``pallas_call``: the
    direction's logits ``A . x + b`` of every row and, from them and the
    logits ``z0`` ``(nbl, m, S, 128)`` pass 1 kept, the multinomial loss
    at ``z0 − steps[j] · (A . x + b)`` for every rung ``j``, the shard's
    sums joined as :func:`grad_pass` joins its. Returns ``(losses
    (rungs,), rows int32)``."""
    return _jitted(_line_pass)(Xs, ys, ws, z0, A, b, steps,
                               interpret=interpret_mode())


def _line_pass(Xs, ys, ws, z0, A, b, steps, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nbl, d, S, L = Xs.shape
    m = A.shape[0]
    mp = _padded(m)
    f32 = jnp.float32
    rungs = steps.shape[0]
    Sb = _sublanes_per_step(d, S)
    n = _PACK * L
    T = _padded(rungs + 1)               # the ladder's losses, then rows
    a = _stack(A, mp)

    def kernel(s_ref, a_ref, b_ref, x_ref, y_ref, w_ref, z_ref, t_ref,
               tcomp_ref, part_ref):
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _():
            t_ref[...] = jnp.zeros(t_ref.shape, f32)
            tcomp_ref[...] = jnp.zeros(tcomp_ref.shape, f32)

        part_ref[...] = jnp.zeros(part_ref.shape, f32)
        kept = z_ref.reshape(m * Sb, L)
        bias = jnp.concatenate([b_ref[...]] * _PACK, 1)[:m]
        cls = lax.broadcasted_iota(jnp.int32, (m, n), 0)

        def word(q, rows):
            p = _word_product(x_ref, a_ref, q)
            zd = (p[2 * mp:2 * mp + m] + p[mp:mp + m]) + p[:m] + bias
            z0 = jnp.concatenate(
                [kept[pl.ds(_PACK * q + j, m, stride=Sb), :]
                 for j in range(_PACK)], 1)
            y, w = _lane_rows(y_ref, q), _lane_rows(w_ref, q)
            hit = cls == y

            def rung(j, _):
                z = z0 - s_ref[j] * zd
                top = jnp.maximum(jnp.max(z, 0, keepdims=True), 0.0)
                den = jnp.sum(jnp.exp(z - top), 0, keepdims=True) \
                    + jnp.exp(-top)
                zy = jnp.sum(jnp.where(hit, z, 0.0), 0, keepdims=True)
                part_ref[pl.ds(j, 1), :] += w * (top + jnp.log(den) - zy)

            lax.fori_loop(0, rungs, rung, None, unroll=True)
            return rows + jnp.where(w != 0, f32(1), f32(0))

        part_ref[rungs:rungs + 1, :] = lax.fori_loop(
            jnp.int32(0), jnp.int32(Sb // _PACK), word, jnp.zeros((1, n), f32))
        _kahan_join(t_ref, tcomp_ref, _fold_lanes(part_ref[...]))

    rows = pl.BlockSpec((1, Sb, L), lambda i, s: (i, s, 0))
    t = pl.pallas_call(
        kernel,
        grid=(nbl, S // Sb),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(a.shape, lambda i, s: (0, 0, 0)),
                  pl.BlockSpec((mp, L), lambda i, s: (0, 0)),
                  pl.BlockSpec((1, d, Sb, L), lambda i, s: (i, 0, s, 0)),
                  rows, rows,
                  pl.BlockSpec((1, m, Sb, L), lambda i, s: (i, 0, s, 0))],
        out_specs=pl.BlockSpec((T, L), lambda i, s: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, L), f32),
        scratch_shapes=[pltpu.VMEM((T, L), f32), pltpu.VMEM((T, n), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(d, Sb, mp)),
        interpret=interpret,
        name="qn_line_pass",
    )(steps.astype(f32), a, _spread(b, mp), Xs, ys, ws.astype(f32),
      z0.astype(f32))
    t, seen = _lane_sums(t, rungs)
    return t[:rungs], seen
