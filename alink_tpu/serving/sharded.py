"""Multi-chip serving: mesh-sharded bucket programs (ISSUE 11 tentpole).

PR 10's serving programs were single-device: a feature-sharded FTRL
model had to gather to one chip before it could serve, and QPS was
capped at one chip no matter how wide ``ALINK_TPU_MESH_DEVICES`` made
the session mesh. This module is where the serving tier meets the
sharded execution path (PR 9):

* :func:`serving_mesh` — the 1-D ``('d',)`` serving mesh over the
  session's devices (the same devices the engine's BSP programs span);
* :func:`make_linear_sharded_fns` — the linear score kernel as a
  ``shard_map`` program: the model's feature axis is partitioned
  ``P('d')`` (the ``io/sharding.py`` placement the FTRL trainer already
  uses for its (z, n) state), each shard reduces its own feature slice,
  and ONE :func:`~alink_tpu.engine.communication.manifest_psum` per
  dispatch combines the partial sums — through the manifest wrappers,
  so the collective manifest (and fusion accounting) sees serving
  traffic exactly like training traffic;
* :func:`seq_chunk_sum` / :func:`lane_partials` — the canonical
  fixed-order reductions every serving kernel builds on.

**The mesh-size-invariance contract.** Serving results must not depend
on how many chips the mesh has — a fleet mixing 1-, 4- and 8-chip
replica groups must answer bitwise-identically. Plain "reduce locally,
psum the partials" breaks that: float addition is non-associative, so a
4-way split rounds differently from an 8-way split. The sharded kernels
therefore reduce in a FIXED lane structure independent of the mesh:

1. the (padded) feature axis splits into ``SERVE_LANES`` (= 8)
   contiguous lanes — a constant, NOT the shard count;
2. each lane reduces strictly left-to-right (:func:`seq_chunk_sum`) on
   whichever shard owns it (shard counts must divide ``SERVE_LANES``,
   so every lane lives whole on exactly one shard);
3. the per-lane partials cross shards as ONE psum of a ``(rows,
   SERVE_LANES)`` buffer in which each lane is non-zero on exactly one
   shard — adding zeros is exact, so the psum reconstructs every lane
   partial bitwise no matter the shard count or reduction order
   (a ``+ 0.0`` canonicalization pins the one IEEE edge, ``-0.0``);
4. every shard then reduces the 8 lane partials in the same strict
   left-to-right order.

Steps 1-4 are literally the same arithmetic at mesh size 1, 2, 4 and 8,
which is what `tests/test_serving_sharded.py` pins bitwise.

The sparse kernel uses the same trick one level down: each gathered
``val * w[idx]`` term is owned by exactly one shard (the one holding
that feature), the ``(rows, width)`` term buffer psums exactly, and the
width-axis reduction runs identically everywhere.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

# The serving reduction granule: feature axes pad to multiples of
# SERVE_CHUNK and reduce CHUNK terms per scan step in strict
# left-to-right order (the PR-10 bucket-invariance contract).
SERVE_CHUNK = 8
# Fixed lane count of the mesh-size-invariant blocked reduction: shard
# counts must divide it (1/2/4/8 — the host-platform mesh sizes the
# scaling evidence runs). Feature axes of SHARDED kernels pad to
# multiples of SERVE_LANES * SERVE_CHUNK so every lane is a whole
# number of scan chunks.
SERVE_LANES = 8
LANE_PAD = SERVE_LANES * SERVE_CHUNK


def serve_sharded_enabled() -> bool:
    """``ALINK_TPU_SERVE_SHARDED``: compile serving bucket programs under
    the session mesh's partition rules (feature-sharded model state, one
    psum per dispatch). Default off — single-device programs."""
    from ..common.flags import flag_value
    return bool(flag_value("ALINK_TPU_SERVE_SHARDED", False))


def serve_replicas() -> int:
    """``ALINK_TPU_SERVE_REPLICAS``: serving-loop replica count of
    :class:`~alink_tpu.serving.server.PredictServer` (data-parallel
    dispatch fan-out across the session mesh's chips). 0 = one replica
    per mesh device; default 1 = the historical single loop.

    Every replica loop runs SUPERVISED (ISSUE 14): a crash — anything
    escaping the per-batch failure handling, e.g. an injected
    ``serve.dispatch`` kill or an admission-channel fault — quarantines
    the replica's in-flight batch (typed ``ReplicaCrashed`` through
    each unresolved future, never silence) and respawns the loop, so
    one bad replica degrades capacity instead of stranding requests
    (``alink_serve_loop_respawns_total``)."""
    from ..common.flags import flag_value
    return int(flag_value("ALINK_TPU_SERVE_REPLICAS", 1))


def serving_mesh(devices: Optional[Sequence] = None):
    """The 1-D ``('d',)`` serving mesh.

    Defaults to the session's devices (``MLEnvironmentFactory.
    get_default()``, sized by ``ALINK_TPU_MESH_DEVICES``) flattened to
    one data axis: serving shards the model's FEATURE axis over 'd',
    the placement :func:`~alink_tpu.operator.stream.onlinelearning.ftrl.
    ftrl_state_rules` already uses for the trainer's (z, n) state, so a
    feature-sharded model swaps in place with no re-layout."""
    import numpy as np
    from jax.sharding import Mesh
    if devices is None:
        from ..common.mlenv import MLEnvironmentFactory
        env = MLEnvironmentFactory.get_default()
        devices = list(env.mesh.devices.reshape(-1))
    return Mesh(np.asarray(devices), ("d",))


def mesh_fingerprint(mesh) -> Optional[Tuple]:
    """Hashable mesh identity for the serving program-cache key: device
    ids + axis names. A different mesh (or sharded-vs-unsharded) can
    therefore never reuse a stale compiled serving program — the fold
    the ``ALINK_TPU_SERVE_SHARDED`` registry entry points at."""
    if mesh is None:
        return None
    return (tuple(int(d.id) for d in mesh.devices.reshape(-1)),
            tuple(mesh.axis_names))


# -- canonical fixed-order reductions ---------------------------------------

def _zeros_carry(shape, like):
    """A zero ``lax.scan`` carry typed like ``like``: inside a
    ``shard_map`` the accumulated terms vary over the mesh axes, and the
    scan's carry type (varying axes included) must not change between
    its input and its output."""
    import jax
    import jax.numpy as jnp
    acc0 = jnp.zeros(shape, like.dtype)
    vma = tuple(jax.typeof(like).vma)
    return jax.lax.pcast(acc0, vma, to="varying") if vma else acc0


def seq_chunk_sum(terms, axis: int):
    """Sum ``terms`` over ``axis`` in a FIXED left-to-right order
    (chunked ``lax.scan`` of elementwise adds): unlike ``jnp.sum`` /
    ``@``, the float rounding cannot depend on the other dimensions'
    sizes, which is what makes serving buckets numerical no-ops. Extents
    beyond the unroll threshold must be a multiple of ``SERVE_CHUNK``
    (encoders pad)."""
    import jax
    import jax.numpy as jnp
    t = jnp.moveaxis(terms, axis, 0)
    ext = t.shape[0]
    acc0 = _zeros_carry(t.shape[1:], t)
    if ext <= 16 * SERVE_CHUNK:
        # small extents unroll in-trace: same strict order, none of the
        # scan loop's per-step dispatch overhead (the serial bucket-1
        # program's latency lives here)
        acc = acc0
        for j in range(ext):
            acc = acc + t[j]
        return acc
    m = ext // SERVE_CHUNK
    t = t.reshape((m, SERVE_CHUNK) + t.shape[1:])

    def body(acc, chunk):
        for k in range(SERVE_CHUNK):
            acc = acc + chunk[k]
        return acc, None

    acc, _ = jax.lax.scan(body, acc0, t)
    return acc


def scan_sum(terms, axis: int):
    """Strict left-to-right sum over ``axis`` as a ``lax.scan`` with the
    term buffer as xs — ALWAYS the loop form, never unrolled.

    The while-loop boundary keeps the producer multiply out of the add
    chain (XLA does not fuse across it), so every term rounds before it
    is added and the chain is pure float adds — deterministic under any
    vectorization. This is the reduction the tree/FM serving kernels
    use: it makes their device scores bitwise-reproducible across shape
    buckets AND bitwise-equal to a host numpy loop that adds the same
    rounded products in the same order."""
    import jax
    import jax.numpy as jnp
    t = jnp.moveaxis(terms, axis, 0)

    def body(acc, x):
        return acc + x, None

    acc, _ = jax.lax.scan(body, _zeros_carry(t.shape[1:], t), t)
    return acc


def lane_partials(terms, lanes: int):
    """Per-lane strict left-to-right partial sums: ``terms`` ``(rows,
    ext)`` split into ``lanes`` contiguous blocks, each reduced to one
    partial -> ``(rows, lanes)``.

    The reduction is a ``lax.scan`` whose xs are the MATERIALIZED term
    buffer, on purpose: an inline/unrolled add chain lets the backend
    contract the producer multiply into the adds as FMA, and whether it
    does depends on the operand shapes — measured on CPU, the same lane
    then rounds ONE ULP differently on a 1-device and an 8-device mesh
    (``optimization_barrier`` does not survive to codegen, so it cannot
    fence this). XLA never fuses across a while-loop boundary, so the
    scan body sees already-rounded terms and is a pure float-add chain
    — deterministic under any vectorization, hence bitwise identical at
    every mesh size."""
    import jax
    import jax.numpy as jnp
    rows, ext_total = terms.shape
    ext = ext_total // lanes
    t = terms.reshape(rows, lanes, ext)
    t = jnp.moveaxis(t, 2, 0)                  # (ext, rows, lanes)

    def body(acc, x):
        return acc + x, None

    acc, _ = jax.lax.scan(body, _zeros_carry((rows, lanes), terms), t)
    return acc


def ordered_lane_reduce(lanes_arr):
    """Strict left-to-right reduce of the ``(rows, L)`` lane partials —
    step 4 of the invariance contract, identical at every mesh size."""
    acc = lanes_arr[:, 0]
    for j in range(1, lanes_arr.shape[1]):
        acc = acc + lanes_arr[:, j]
    return acc


# -- the linear family's sharded score programs -----------------------------

def linear_partition_rules():
    """Partition rules (the ``io/sharding.py`` ``match_partition_rules``
    idiom) for the linear serving kernel's model arrays: the weight
    vector shards over the mesh feature axis 'd' — the serving-side twin
    of ``ftrl_state_rules()`` — and everything else (intercept)
    replicates."""
    from jax.sharding import PartitionSpec as P
    return ((r"^w$", P("d")),)


def linear_input_specs(kind: str):
    """PartitionSpecs of the ENCODED request arrays: the dense design
    matrix shards its feature axis alongside the weights; the sparse
    (idx, val) pair replicates (each shard masks to the features it
    owns)."""
    from jax.sharding import PartitionSpec as P
    if kind == "dense":
        return (P(None, "d"),)
    return (P(), P())


def make_linear_device_fns(mesh) -> Dict[str, callable]:
    """The binary/regression linear score kernel as mesh-sharded
    programs: ``{kind: fn(model_arrays, *encoded)}``, drop-in twins of
    the single-device ``device_fns`` the predictor jits per bucket.

    One ``manifest_psum`` per dispatch crosses the feature-axis partial
    sums between shards; results are bitwise-identical at every mesh
    size dividing ``SERVE_LANES`` (module docstring contract).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..common.compat import shard_map
    from ..engine.communication import manifest_psum

    n_shards = int(mesh.devices.size)
    if SERVE_LANES % n_shards:
        raise ValueError(
            f"serving mesh has {n_shards} devices, which does not divide "
            f"SERVE_LANES={SERVE_LANES}; the lane-blocked reduction "
            f"cannot keep results mesh-size-invariant")
    lanes_local = SERVE_LANES // n_shards

    def _dense_local(w_loc, X_loc):
        # this shard's contiguous feature slice: lanes
        # [idx*lanes_local, (idx+1)*lanes_local)
        part = lane_partials(X_loc * w_loc[None, :], lanes_local)
        lanes = jnp.zeros((X_loc.shape[0], SERVE_LANES), part.dtype)
        idx = jax.lax.axis_index("d")
        lanes = jax.lax.dynamic_update_slice(
            lanes, part, (jnp.zeros((), idx.dtype), idx * lanes_local))
        # each lane non-zero on exactly one shard -> the psum is exact
        lanes = manifest_psum(lanes, "d", name="serve_dense_lanes",
                              num_workers=n_shards)
        # canonicalize -0.0 lane partials (x + 0.0) so a lane that
        # psummed against zeros (mesh > 1) and one that did not
        # (mesh 1) agree bitwise even on signed zeros
        return ordered_lane_reduce(lanes + 0.0)

    def _dense(mdl, X):
        w, b = mdl
        score = shard_map(_dense_local, mesh=mesh,
                          in_specs=(P("d"), P(None, "d")),
                          out_specs=P())(w, X)
        return score + b

    def _sparse_local(w_loc, idx, val):
        block = w_loc.shape[0]
        off = jax.lax.axis_index("d") * block
        loc = idx - off
        mine = (loc >= 0) & (loc < block)
        g = jnp.where(mine, val * w_loc[jnp.clip(loc, 0, block - 1)], 0.0)
        # every (row, slot) term is owned by exactly one shard: the term
        # buffer psums exactly, then reduces in the same strict order
        # at every mesh size
        g = manifest_psum(g, "d", name="serve_sparse_terms",
                          num_workers=n_shards)
        return seq_chunk_sum(g + 0.0, axis=1)

    def _sparse(mdl, idx, val):
        w, b = mdl
        score = shard_map(_sparse_local, mesh=mesh,
                          in_specs=(P("d"), P(), P()),
                          out_specs=P())(w, idx, val)
        return score + b

    return {"dense": _dense, "sparse": _sparse}


def make_linear_fleet_fns() -> Dict[str, callable]:
    """The binary/regression linear score kernel as TENANT-LANE-stacked
    programs (ISSUE 17): ``{kind: fn(stacked_model_arrays, lane,
    *encoded)}`` where each model array gained a leading tenant-lane
    axis — ``W (L, dim8)``, ``b (L,)`` — and ``lane`` is the per-row
    int32 tenant->lane index (the tuning ``(points,)`` carry-lane idiom
    applied to serving weights).

    Bitwise contract (the fleet's coalescing proof): per request row,
    ``(X * W[lane])[i] == X[i] * w_tenant`` elementwise,
    :func:`seq_chunk_sum` reduces the feature axis in the SAME strict
    left-to-right order regardless of what the other rows of the batch
    hold, and ``+ b[lane]`` is the same scalar add — so a row served in
    a coalesced cross-tenant batch is bitwise-identical to the same row
    served through its tenant's own single-model bucket program
    (tests/test_fleet.py pins it). Padding lanes (zero weights) are
    gathered only by padding rows, which are sliced off at decode.
    """

    def _dense(mdls, lane, X):
        W, b = mdls                       # (L, dim8), (L,)
        return seq_chunk_sum(X * W[lane], axis=1) + b[lane]

    def _sparse(mdls, lane, idx, val):
        W, b = mdls
        # per-row two-level gather: row i reads its own tenant's weight
        # slots — value-identical to the single-model w[idx] gather
        return seq_chunk_sum(val * W[lane[:, None], idx], axis=1) + b[lane]

    return {"dense": _dense, "sparse": _sparse}
