"""The two passes of KMeans over the blocked table, each as one streamed
Pallas kernel: the k-means|| candidate fold (ISSUE 30) and Lloyd's
superstep (ISSUE 32, :func:`lloyd_sums`, built like the fold; its own
account is there).

A k-means|| round folds its ``l`` new candidates into the per-row
``(d2, nearest)`` state. As XLA compiles the ``block_distances``
formulation, a block of the table is first copied out, then the whole
``(l, S, 128)`` distance array is written to memory and read back for
``(min, argmin)``: five launches and two round trips for what a row
needs once. :func:`fold_candidates` is that fold as ONE ``pallas_call``
a round: the grid walks the worker's blocks; the table block, the
weights and the state ride ``BlockSpec``s on the leading axis, so block
``i + 1`` is fetched while block ``i`` is computed; each candidate
coordinate arrives spread over a register (400 registers at 20 × 20,
fetched once: a scalar from SMEM costs an operation of its own at
every use); a loop over the block's register tiles of rows adds ``(x_f
- c_jf)^2`` feature by feature into one running distance a candidate,
then takes ``(min, argmin)`` and the update in registers. Every array
inside is a stack of ``(8, 128)`` registers, so one traced operation
covers many of them and the kernel stays short to trace. ``d2`` and
``nearest`` are aliased in and out; nothing of shape ``(l, S, 128)``
exists.

The arithmetic is ``block_distances``' own (float32, the direct form,
features summed in order, ties to the lowest candidate); a distance may
differ from XLA's reduction in its last bits. Which path a fit takes is
read from its input (:func:`fold_path`, :func:`lloyd_path`), never set.
"""

from __future__ import annotations

import functools

from .runtime import interpret_mode, pallas_available

__all__ = ["fold_path", "fold_candidates", "lloyd_path", "lloyd_sums"]

_LANES = 128
#: sublanes of one float32 register
_TILE = 8
#: running distances (a register each) a step of the inner loop keeps:
#: with few candidates it takes several register tiles of rows at once
_ACCUMULATORS = 40
#: registers one vector operation of the inner loop spans: few, so that
#: an operation's operands and results stay in registers; many, so that
#: the kernel is short to trace
_OPERATION = 16
#: the table's two pipeline buffers may take this much VMEM; a block
#: wider than half of it is cut over its sublane axis
_TABLE_VMEM = 12 << 20
#: candidates × features up to which the kernel runs: its arithmetic is
#: unrolled over them, and each is held in VMEM spread over a register
_UNROLLED = 1024
#: registers a step of Lloyd's inner loop keeps from its distances to
#: its sums (a cluster's masked weight, a register a tile of rows)
_LLOYD_MASKS = 24
#: clusters × features up to which Lloyd's kernel runs: its arithmetic is
#: unrolled over them twice (distances, sums), and every sum is a
#: register of partial sums, held in VMEM six times over
_LLOYD_UNROLLED = 512
#: stacks of rows a trip of Lloyd's inner loop takes, one after the
#: other: the second's reads start under the first's arithmetic
_LLOYD_STACKS = 2


def fold_path(dtype, S: int, l: int, d: int) -> str:
    """``"kernel"`` where :func:`fold_candidates` can run — a backend
    that executes Pallas (a TPU, or the interpreter the tier-1 rig turns
    on), a float32 table whose blocks are whole register tiles, and
    ``l`` candidates a round of ``d`` features that the kernel can
    unroll — else ``"xla"``."""
    import numpy as np
    ok = pallas_available() and np.dtype(dtype) == np.float32 \
        and S % _TILE == 0 and l * d <= _UNROLLED
    if ok:
        # loaded here, before the engine traces the round: Pallas takes
        # over a second to import (1.2 s on the chip's host, 1.7 s when
        # first met inside a trace), once a process
        import jax.experimental.pallas.tpu  # noqa: F401
    return "kernel" if ok else "xla"


def _sublanes_per_step(d: int, S: int) -> int:
    """Sublanes of a block one grid step holds: all ``S`` where two
    table blocks fit ``_TABLE_VMEM``, else the largest divisor of ``S``
    in whole register tiles that does."""
    fit = max(_TABLE_VMEM // (2 * d * _LANES * 4), _TILE)
    return max(s for s in range(_TILE, min(S, fit) + 1, _TILE) if S % s == 0)


def fold_candidates(Xs, Ws, d2, nearest, new, off):
    """Fold candidates ``new`` ``(l, d)``, numbered from ``off``, into a
    shard's per-row state: for every row ``dn = min_j sum_f (x_f -
    new[j, f])^2`` (0 where the row's weight is 0), and where ``dn <
    d2`` the row takes ``dn`` and ``off + argmin_j``. ``Xs`` is ``(nbl,
    d, S, 128)`` float32, ``Ws`` / ``d2`` ``(nbl, S, 128)`` float32,
    ``nearest`` ``(nbl, S, 128)`` int32. Returns ``(d2, nearest)``,
    written in place."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nbl, d, S, L = Xs.shape
    l = new.shape[0]
    Sb = _sublanes_per_step(d, S)
    tiles = Sb // _TILE
    # register tiles of rows a step of the inner loop takes at once, and
    # candidates one vector operation covers
    U = max(u for u in range(1, max(_ACCUMULATORS // l, 1) + 1)
            if tiles % u == 0)
    G = max(_OPERATION // U, 1)
    groups = [(g, min(g + G, l)) for g in range(0, l, G)]

    def kernel(off_ref, c_ref, x_ref, w_ref, d2_ref, near_ref,
               d2_out, near_out):
        # few traced operations: the loop over the features is traced once
        # and unrolled when it is lowered, on lax primitives over stacks
        # of registers. Every process traces the kernel in its first fit,
        # and 500 operations cost that fit most of a second
        def step(t, _):
            rows = pl.ds(t * U, U)

            def feature(f, dist):                  # features in order
                xf = x_ref[0, f, rows]             # (U, 8, 128)
                out = []
                for (lo, hi), acc in zip(groups, dist):
                    diff = lax.sub(
                        lax.broadcast_in_dim(xf, acc.shape, (1, 2, 3)),
                        lax.broadcast_in_dim(c_ref[f, lo:hi], acc.shape,
                                             (0, 2, 3)))
                    out.append(lax.add(acc, lax.mul(diff, diff)))
                return out

            dist = lax.fori_loop(
                0, d, feature,
                [jnp.zeros((hi - lo, U, _TILE, L), jnp.float32)
                 for lo, hi in groups], unroll=True)
            dist = lax.concatenate(dist, 0)        # (l, U, 8, 128)
            best = lax.reduce_min(dist, (0,))      # ties to the lowest
            arg = lax.reduce_min(lax.select(
                lax.eq(dist, lax.broadcast_in_dim(best, dist.shape, (1, 2, 3))),
                lax.broadcasted_iota(jnp.int32, dist.shape, 0),
                jnp.full(dist.shape, l, jnp.int32)), (0,))
            dn = jnp.where(w_ref[0, rows] != 0, best, 0.0)
            old = d2_ref[0, rows]
            closer = dn < old
            d2_out[0, rows] = jnp.where(closer, dn, old)
            near_out[0, rows] = jnp.where(closer, off_ref[0] + arg,
                                          near_ref[0, rows])

        lax.fori_loop(jnp.int32(0), jnp.int32(tiles // U), step, None)

    # rows as register tiles, (S / 8, 8, 128): the same bytes in place
    tiled = (nbl, S // _TILE, _TILE, L)
    rows = pl.BlockSpec((1, tiles, _TILE, L), lambda i, s: (i, s, 0, 0))
    state = (jax.ShapeDtypeStruct(tiled, jnp.float32),
             jax.ShapeDtypeStruct(tiled, jnp.int32))
    # each candidate coordinate spread over a register, fetched once
    spread = jnp.broadcast_to(new.astype(jnp.float32).T[:, :, None, None],
                              (d, l, _TILE, L))
    d2, nearest = pl.pallas_call(
        kernel,
        grid=(nbl, S // Sb),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((d, l, _TILE, L), lambda i, s: (0, 0, 0, 0)),
                  pl.BlockSpec((1, d, tiles, _TILE, L),
                               lambda i, s: (i, 0, s, 0, 0)),
                  rows, rows, rows],
        out_specs=(rows, rows),
        out_shape=state,
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=2 * _TABLE_VMEM + 2 * _UNROLLED * _TILE * L * 4),
        interpret=interpret_mode(),
        name="kmpp_fold",
    )(jnp.asarray(off, jnp.int32).reshape(1), spread,
      Xs.reshape((nbl, d) + tiled[1:]), Ws.reshape(tiled),
      d2.reshape(tiled), nearest.reshape(tiled))
    return d2.reshape(Ws.shape), nearest.reshape(Ws.shape)


def lloyd_path(dtype, S: int, k: int, d: int, distance_type: str) -> str:
    """``"kernel"`` where :func:`lloyd_sums` can run — as
    :func:`fold_path`, for the squared Euclidean distance and ``k``
    clusters of ``d`` features that the kernel can unroll and hold —
    else ``"xla"``."""
    import numpy as np
    ok = pallas_available() and np.dtype(dtype) == np.float32 \
        and S % _TILE == 0 and distance_type == "EUCLIDEAN" \
        and k * d <= _LLOYD_UNROLLED
    if ok:
        import jax.experimental.pallas.tpu  # noqa: F401  (see fold_path)
    return "kernel" if ok else "xla"


def lloyd_sums(Xs, Ws, C):
    """One Lloyd pass over a shard as ONE ``pallas_call``: every row
    goes to its nearest centroid of ``C`` ``(k, d)`` (``block_distances``'
    arithmetic, ties to the lowest) and into that cluster's sums. ``Xs``
    is ``(nbl, d, S, 128)`` float32, ``Ws`` ``(nbl, S, 128)`` float32.
    Returns ``(acc (k + 1, d + 1) float32, rows int32)``: ``acc[:k]``
    holds ``sum w (x - c_j)`` and, last, the cluster's summed weight;
    ``acc[k, 0]`` the weighted inertia; ``rows`` the rows of weight other
    than 0.

    The grid walks the blocks in order, block ``i + 1`` fetched under
    block ``i``'s arithmetic. Per stack of register tiles of rows: the
    ``k`` running distances feature by feature, ``(min, argmin)``, the
    masked weight ``w [argmin = j]`` a cluster, and with it the stack's
    share of every sum. Each sum is a register of 1,024 partial sums in
    VMEM. Inside a grid step a row enters centred on ONE point, the mean
    of the centroids (a multiplication and an addition a cluster and
    feature; centred on its own centroid, a subtraction more); at the
    step's end the partial sums move to their own centroids, ``- weight
    (c_j - mean)`` on at most a few hundred rows each, and join the
    pass's with a Kahan compensation a partial sum. XLA adds the 1,024
    up once a pass. Nothing of shape ``(k, S, 128)`` exists."""
    return _lloyd_call()(Xs, Ws, C, interpret=interpret_mode())


# jitted: the init pass and the loop body of the Lloyd program call it on
# the same shapes, so the kernel is traced and lowered once
@functools.lru_cache(maxsize=None)
def _lloyd_call():
    import jax
    return jax.jit(_lloyd_sums, static_argnames="interpret")


def _lloyd_sums(Xs, Ws, C, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nbl, d, S, L = Xs.shape
    k = C.shape[0]
    f32 = jnp.float32
    Sb = _sublanes_per_step(d, S)
    tiles = Sb // _TILE
    # register tiles of rows a stack holds, and clusters one vector
    # operation covers
    U = max(u for u in range(1, max(_LLOYD_MASKS // k, 1) + 1)
            if tiles % u == 0)
    G = max(_OPERATION // U, 1)
    groups = [(g, min(g + G, k)) for g in range(0, k, G)]

    def kernel(c_ref, shift_ref, x_ref, w_ref, out_ref, comp_ref, part_ref):
        # out_ref[f, j] is cluster j's sum over feature f (f = d: its
        # weight); out_ref[0, k] the inertia, out_ref[1, k] the rows seen
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _():
            out_ref[...] = jnp.zeros(out_ref.shape, f32)
            comp_ref[...] = jnp.zeros(comp_ref.shape, f32)

        part_ref[...] = jnp.zeros(part_ref.shape, f32)

        def stack(t):
            rows = pl.ds(t * U, U)

            def over_rows(a):                  # (g, 8, 128) -> (g, U, 8, 128)
                return lax.broadcast_in_dim(
                    a, (a.shape[0], U, _TILE, L), (0, 2, 3))

            def over_clusters(a, g):           # (U, 8, 128) -> (g, U, 8, 128)
                return lax.broadcast_in_dim(a, (g, U, _TILE, L), (1, 2, 3))

            def distance(f, dist):             # features in order
                xf = x_ref[0, f, rows]
                return [lax.add(acc, lax.square(lax.sub(
                    over_clusters(xf, hi - lo), over_rows(c_ref[f, lo:hi]))))
                    for (lo, hi), acc in zip(groups, dist)]

            dist = lax.fori_loop(
                0, d, distance,
                [jnp.zeros((hi - lo, U, _TILE, L), f32)
                 for lo, hi in groups], unroll=True)
            dist = lax.concatenate(dist, 0)    # (k, U, 8, 128)
            best = dist[0]
            arg = jnp.zeros(best.shape, jnp.int32)
            for j in range(1, k):              # ties to the lowest
                closer = lax.lt(dist[j], best)
                best = lax.select(closer, dist[j], best)
                arg = lax.select(closer, jnp.full(arg.shape, j, jnp.int32),
                                 arg)
            w = w_ref[0, rows]
            # a cluster's masked weight: w on its own rows
            own = [lax.select(
                lax.eq(lax.broadcasted_iota(
                    jnp.int32, (hi - lo, U, _TILE, L), 0) + lo,
                    over_clusters(arg, hi - lo)),
                over_clusters(w, hi - lo),
                jnp.zeros((hi - lo, U, _TILE, L), f32))
                for lo, hi in groups]

            def sums(f, _):
                xf = lax.sub(x_ref[0, f, rows], lax.broadcast_in_dim(
                    c_ref[f, k], (U, _TILE, L), (1, 2)))
                for (lo, hi), m in zip(groups, own):
                    part_ref[f, lo:hi] += lax.reduce_sum(
                        lax.mul(m, over_clusters(xf, hi - lo)), (1,))

            lax.fori_loop(0, d, sums, None, unroll=True)
            for (lo, hi), m in zip(groups, own):
                part_ref[d, lo:hi] += lax.reduce_sum(m, (1,))
            part_ref[0, k] += lax.reduce_sum(lax.mul(w, best), (0,))
            part_ref[1, k] += lax.reduce_sum(
                jnp.where(w != 0, f32(1), f32(0)), (0,))

        R = _LLOYD_STACKS if tiles // U % _LLOYD_STACKS == 0 else 1

        def trip(t, _):
            for r in range(R):
                stack(t * R + r)

        lax.fori_loop(jnp.int32(0), jnp.int32(tiles // U // R), trip, None)

        def join(f, _):
            # to the clusters' own centroids, then ``kahan_add``
            y = part_ref[f] - part_ref[d] * shift_ref[f] - comp_ref[f]
            acc = out_ref[f]
            t = acc + y
            comp_ref[f] = (t - acc) - y
            out_ref[f] = t

        lax.fori_loop(jnp.int32(0), jnp.int32(d + 1), join, None)

    # each centroid coordinate spread over a register, fetched once: last
    # the point a step's sums are centred on, and how far from it each
    # centroid lies (nothing where a register holds no sum over a feature)
    C = C.astype(f32)
    mid = C.mean(0, keepdims=True)
    held = (d + 1, k + 1, _TILE, L)
    spread = jnp.broadcast_to(
        jnp.concatenate([C, mid], 0).T[:, :, None, None],
        (d, k + 1, _TILE, L))
    shift = jnp.broadcast_to(
        jnp.pad((C - mid).T, ((0, 1), (0, 1)))[:, :, None, None], held)
    whole = pl.BlockSpec(held, lambda i, s: (0, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(nbl, S // Sb),
        in_specs=[pl.BlockSpec((d, k + 1, _TILE, L),
                               lambda i, s: (0, 0, 0, 0)),
                  whole,
                  pl.BlockSpec((1, d, tiles, _TILE, L),
                               lambda i, s: (i, 0, s, 0, 0)),
                  pl.BlockSpec((1, tiles, _TILE, L),
                               lambda i, s: (i, s, 0, 0))],
        out_specs=whole,
        out_shape=jax.ShapeDtypeStruct(held, f32),
        scratch_shapes=[pltpu.VMEM(held, f32), pltpu.VMEM(held, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * _TABLE_VMEM
            + 8 * (d + 1) * (k + 1) * _TILE * L * 4),
        interpret=interpret,
        name="lloyd_pass",
    )(spread, shift, Xs.reshape(nbl, d, S // _TILE, _TILE, L),
      Ws.reshape(nbl, S // _TILE, _TILE, L))
    acc = out.sum((2, 3)).T                    # (k + 1, d + 1)
    rows = out[1, k].astype(jnp.int32).sum()   # whole numbers: exact
    return acc.at[k, 1].set(0), rows
