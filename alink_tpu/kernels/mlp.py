"""Layer 1 of the MLP trainer's blocked passes over a table of BYTES as
Pallas kernels: the coefficients' product against a block
(:func:`forward`, device op ``mlp_forward``) and the per-row values'
product against the block's transpose (:func:`backward`,
``mlp_backward``).

As XLA compiles ``optim/objfunc.py``'s ``block_forward`` /
``block_backward`` at the MLP's widths, the three bfloat16 parts of the
coefficients (``split3``) are stacked on the OUTPUT's rows: a forward
product writes ``(3 m, rows)`` float32 to HBM and a join reads it back
(786 MB a block of 65,536 rows in the line pass, where ``m`` is 1,000), the
backward's split values are written as ``(3 m, rows)`` bfloat16 before
their product, and every product casts the block's bytes to bfloat16
anew. Here the table is passed whole and the block's index rides as a
scalar-prefetch argument, so a ``BlockSpec`` reads block ``i`` where it
lies, one byte register tile (32 sublanes, 4,096 rows) at a time and
nothing is copied out of the table. A grid step takes one of the tile's
eight 32-bit words (512 rows: four rows of one feature a word) and widens
it to bfloat16 once into VMEM, as ``kernels/linear.py`` does (a strided load gives a register of eight features, each byte is
shifted out and widened; a signed byte keeps its sign). The forward's
coefficient parts stay in VMEM for the whole call and meet the widened
rows in three MXU products into one float32 tile, joined low to high as
the XLA walk joins them, the bias added, the tile written to HBM once:
``(m, rows)``. The backward splits its ``(m, rows)`` float32 values in
VMEM and adds their three products against the widened rows into three
``(m, d)`` sums held across the block's grid steps, joined low to high
once at the block's end, as the XLA walk sums each part over the block
before its join.

The arithmetic is the XLA walk's: bytes exact in bfloat16, the other
operand as three bfloat16 parts split by bit mask, float32 accumulation.
What differs is the order of the float32 sums inside a product, so the
two walks agree to float32's grade, not bitwise. Which walk the passes
take is read from the input (:func:`walk_path`), never set.
"""

from __future__ import annotations

import functools

from .linear import _chunks, _widen_run, split3
from .runtime import interpret_mode, pallas_available

__all__ = ["walk_path", "forward", "backward"]

_LANES = 128
#: rows of a byte block one 32-bit word holds
_PACK = 4
#: sublanes of the table a ``BlockSpec`` reads at a time: one register
#: tile of bytes, 4,096 rows
_SUBLANES = 32
#: a grid step's rows: one 32-bit word across the lanes. On a v5e two
#: words a step ran the kernels 1-2.5 % faster and compiled 1.6-2 x
#: slower; four ran the line pass's forward 14 % slower
_WORD = _PACK * _LANES
#: a part's rows are padded to whole bfloat16 registers
_ROWS = 16
#: VMEM the kernels may ask for. A call's need (:func:`_vmem_bytes`)
#: grows with ``m × d``; the cell's 784-500 layer 1 asks for 42 MB
#: (``m`` = 1,000: the line pass's stack)
_VMEM = 64 << 20


def _padded(m: int) -> int:
    return -(-m // _ROWS) * _ROWS


def _vmem_bytes(d: int, m: int) -> int:
    """VMEM the larger of the two kernels takes for ``m`` rows over ``d``
    features: the forward's three parts in two buffers, two table
    buffers, the widened rows, two output buffers and three float32
    product tiles; the backward's three sums, two buffers of values and
    of its output, and its split values; 4 MB for the rest."""
    mp, n = _padded(m), _WORD
    table = 2 * d * _SUBLANES * _LANES + d * n * 2
    fwd = 2 * 3 * mp * d * 2 + 2 * mp * 4 * _LANES + 2 * mp * n * 4 \
        + 3 * mp * n * 4
    bwd = 3 * mp * d * 4 + 2 * mp * n * 4 + 2 * mp * d * 4 + 3 * mp * n * 2 \
        + 3 * mp * d * 4
    return table + max(fwd, bwd) + (4 << 20)


def walk_path(dtype, d: int, S: int, m: int) -> str:
    """``"kernel"`` where :func:`forward` and :func:`backward` can run
    layer 1 of a blocked table ``(nbl, d, S, 128)`` of ``dtype`` against
    ``m`` coefficient rows (the line pass's stacked weights and direction,
    the larger stack) — a backend that executes Pallas (a TPU, or the
    interpreter the tier-1 rig turns on), one-byte integers, blocks of
    whole byte register tiles (``S % 32 == 0``), two features or more
    (Mosaic refuses a product over one) and a need of VMEM within
    ``_VMEM`` — else ``"xla"``: ``block_forward`` / ``block_backward``.
    ``tests/test_tpu_compile.py`` compiles the step program on both walks
    for a described v5e."""
    import numpy as np
    dt = np.dtype(dtype)
    ok = pallas_available() and dt.kind in "iu" and dt.itemsize == 1 \
        and S % _SUBLANES == 0 and d >= 2 and _vmem_bytes(d, m) <= _VMEM
    if ok:
        # loaded here, before the engine traces the step (kernels/linear.py)
        import jax.experimental.pallas.tpu  # noqa: F401
    return "kernel" if ok else "xla"


def _widen(x_ref, xs_ref) -> None:
    """The grid step's word ``q`` of a byte tile ``x_ref`` ``(1, d, 32,
    128)`` widened to bfloat16 into ``xs_ref`` ``(d, 512)``, a run of
    features at a time (``kernels/linear._widen_run``)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    _, d, Sb, L = x_ref.shape
    words = Sb // _PACK
    q = pl.program_id(0) % words
    flat = x_ref.bitcast(jnp.int32).reshape(d * words, L)
    signed = jnp.issubdtype(x_ref.dtype, jnp.signedinteger)
    runs, size, last = _chunks(d)

    def run(c, carry, width):
        f = pl.multiple_of(c * size, _ROWS) if runs > 1 else 0
        xs_ref[pl.ds(f, width), :] = _widen_run(flat, q, f, words, width,
                                               signed)
        return carry

    whole = runs if last == size else runs - 1
    if whole:
        lax.fori_loop(0, whole, functools.partial(run, width=size), 0,
                      unroll=True)
    if whole < runs:
        run(runs - 1, 0, last)


def _join(lo, mid, hi):
    """The three parts' products, joined low to high (``_join3``)."""
    return (lo + mid) + hi


def _table_spec(d: int):
    """The tile of block ``blk[0]`` a grid step reads."""
    from jax.experimental import pallas as pl
    words = _SUBLANES // _PACK
    return pl.BlockSpec((1, d, _SUBLANES, _LANES),
                        lambda s, blk: (blk[0], 0, s // words, 0))


def _params(d: int, m: int, semantics: str):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=(semantics,),
                                vmem_limit_bytes=_vmem_bytes(d, m)
                                + (16 << 20))


# jitted: the step program's first superstep and its loop body call a
# kernel on the same shapes, so it is traced and lowered once
@functools.lru_cache(maxsize=None)
def _jitted(fn, static=("interpret",)):
    import jax
    return jax.jit(fn, static_argnames=static)


def forward(Xs, i, A, b, cuts: int = 1):
    """``A . x + b`` over block ``i`` of the byte table ``Xs`` ``(nbl, d,
    S, 128)``: ``A`` ``(m, d)`` coefficient rows, ``b`` ``(m,)``, the
    product cut into ``cuts`` equal runs of rows (the line pass's ``W1``
    and ``D1``), each written as an output of its own so that nothing
    slices it afterwards. Returns a tuple of ``cuts`` arrays ``(m / cuts,
    S · 128)`` float32, a column a row of the block in the table's row
    order (``block_forward(x, A).reshape(m, -1) + b[:, None]`` to float32's
    grade, cut)."""
    return _jitted(_forward, ("cuts", "interpret"))(
        Xs, i, A, b, cuts=cuts, interpret=interpret_mode())


def _forward(Xs, i, A, b, cuts: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nbl, d, S, L = Xs.shape
    m = A.shape[0] // cuts
    mp, n = _padded(m), _WORD
    f32 = jnp.float32

    def padded(v):
        v = v.astype(f32).reshape((cuts, m) + v.shape[1:])
        return jnp.pad(v, ((0, 0), (0, mp - m)) + ((0, 0),) * (v.ndim - 2)) \
            .reshape((cuts * mp,) + v.shape[2:])
    a = split3(padded(A)).reshape(3, cuts * mp, d)
    bias = padded(b)[:, None]

    def kernel(blk_ref, a_ref, b_ref, x_ref, *refs):
        out_refs, xs_ref = refs[:cuts], refs[cuts]
        _widen(x_ref, xs_ref)
        x = xs_ref[...]

        def product(part):
            return jnp.dot(a_ref[part], x, preferred_element_type=f32)
        z = _join(product(2), product(1), product(0)) + b_ref[...]
        for k, o_ref in enumerate(out_refs):
            o_ref[...] = z[k * mp:(k + 1) * mp]

    out = pl.BlockSpec((mp, n), lambda s, blk: (0, s))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S * L // n,),
            in_specs=[pl.BlockSpec(a.shape, lambda s, blk: (0, 0, 0)),
                      pl.BlockSpec(bias.shape, lambda s, blk: (0, 0)),
                      _table_spec(d)],
            out_specs=(out,) * cuts,
            scratch_shapes=[pltpu.VMEM((d, n), jnp.bfloat16)]),
        out_shape=(jax.ShapeDtypeStruct((m, S * L), f32),) * cuts,
        compiler_params=_params(d, cuts * m, "arbitrary"),
        interpret=interpret,
        name="mlp_forward",
    )(jnp.reshape(i, (1,)).astype(jnp.int32), a, bias, Xs)


def backward(Xs, i, C):
    """``C . x^T`` over block ``i`` of the byte table ``Xs`` ``(nbl, d, S,
    128)``: ``C`` ``(m, S · 128)`` float values, a column a row of the
    block in the table's row order. Returns the ``(m, d)`` float32 sums
    over the block's rows (``block_backward(x, C.reshape(m, S, 128))`` to
    float32's grade)."""
    return _jitted(_backward)(Xs, i, C, interpret=interpret_mode())


def _backward(Xs, i, C, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nbl, d, S, L = Xs.shape
    m = C.shape[0]
    mp, n = _padded(m), _WORD
    f32 = jnp.float32
    steps = S * L // n

    def kernel(blk_ref, c_ref, x_ref, g_ref, xs_ref, acc_ref):
        s = pl.program_id(0)

        @pl.when(s == 0)
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        _widen(x_ref, xs_ref)
        # rows past ``m`` of the values' tile are the edge block's
        # padding: their sums land in rows of ``acc_ref`` nobody reads
        acc_ref[...] += lax.dot_general(
            split3(c_ref[...]), xs_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=f32)

        @pl.when(s == steps - 1)
        def _():
            g_ref[...] = _join(acc_ref[2 * mp:], acc_ref[mp:2 * mp],
                               acc_ref[:mp])

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[pl.BlockSpec((mp, n), lambda s, blk: (0, s)),
                      _table_spec(d)],
            out_specs=pl.BlockSpec((mp, d), lambda s, blk: (0, 0)),
            scratch_shapes=[pltpu.VMEM((d, n), jnp.bfloat16),
                            pltpu.VMEM((3 * mp, d), f32)]),
        out_shape=jax.ShapeDtypeStruct((m, d), f32),
        compiler_params=_params(d, m, "arbitrary"),
        interpret=interpret,
        name="mlp_backward",
    )(jnp.reshape(i, (1,)).astype(jnp.int32), C.astype(f32), Xs)
