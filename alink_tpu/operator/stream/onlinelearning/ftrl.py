"""FTRL online learning — feature-sharded model state on the device mesh.

Re-design of stream/onlinelearning/FtrlTrainStreamOp.java (575 LoC) and
FtrlPredictStreamOp.java.

Reference mechanism (SURVEY §2.3 model parallelism):
  - the coefficient vector is split into ``parallelism`` contiguous feature
    ranges (``getSplitInfo``, FtrlTrainStreamOp.java:74-87);
  - each incoming sample is split by feature range (``SplitVector``, :174)
    and routed to the shard owners;
  - each ``CalcTask`` holds only its shard of the (w, z, n) FTRL state
    (:332-390) and produces a partial dot product;
  - ``ReduceTask`` reassembles partial wx keyed by sampleId (:119-135);
  - model snapshots are emitted every timeInterval (:360) and hot-swapped
    into the predictor (FtrlPredictStreamOp.java:62-110).

TPU-native mechanism: the (z, n) state lives **device-resident, sharded
over the mesh feature axis** via ``shard_map``; the sample split is just
the sharding of the batch's column dimension; the partial-wx reassembly is
one ``lax.psum``; the per-sample sequential FTRL update is a ``lax.scan``
over the micro-batch inside one jitted SPMD program. The feedback routing
(Flink's ConnectedIterativeStreams cycle) disappears: scan order *is* the
feedback.
"""

from __future__ import annotations

import functools
import time
import weakref
from typing import List, Optional

import numpy as np

from ....common.checkpoint import load_latest_validated, save_checkpoint
from ....common.faults import maybe_crash
from ....common.metrics import get_registry, metrics_enabled
from ....common.mtable import MTable
from ....common.params import InValidator, ParamInfo, Params, RangeValidator
from ....common.profiling2 import hbm_snapshot
from ....common.tracing import (Span, trace_complete, trace_instant,
                                 trace_span)
from ....common.types import TableSchema
from ....params.shared import (HasFeatureCols, HasLabelCol, HasPredictionCol,
                               HasPredictionDetailCol, HasReservedCols,
                               HasVectorCol)
from ...base import BatchOperator, StreamOperator
from ...common.dataproc.feature_extract import extract_design
from ...common.linear.base import (LinearModelData, LinearModelDataConverter,
                                   LinearModelType)
from ...common.linear.mapper import LinearModelMapper
from ..core import merge_timed


def ftrl_state_rules():
    """Partition rules for the FTRL model state (io/sharding.py
    match_partition_rules): the accumulated (z, n) vectors are sharded
    over the mesh feature axis 'd' — the device analogue of the
    reference splitting the coefficient range across workers
    (getSplitInfo, FtrlTrainStreamOp.java:74-87); anything else (labels,
    batch tensors) replicates."""
    from jax.sharding import PartitionSpec as P
    return ((r"^(z|n)$", P("d")),)


def _corrupt_snapshot_table(snap: MTable) -> MTable:
    """The ``feeder.snapshot`` fault site's ``corrupt`` mode
    (common/faults.py, ISSUE 14): return a copy of the emitted model
    table with the first coefficient payload row mangled into invalid
    JSON, so the consumer's ``load_model`` fails LOUDLY (the serving
    feeder's poisoned-snapshot path) instead of silently serving
    flipped bits. The original table is never touched — the trainer's
    own state is not corrupted, only the emitted snapshot."""
    rows = [list(snap.row(i)) for i in range(snap.num_rows)]
    for r in rows:
        # payload rows carry model_id >= 1 and a JSON string
        if r[0] and isinstance(r[1], str) and r[1]:
            r[1] = "\x00CORRUPT" + r[1][1:]
            break
    return MTable([tuple(r) for r in rows], snap.schema)


def _ftrl_weights(z, n, alpha, beta, l1, l2):
    """w from the accumulated (z, n) state — the FTRL-proximal closed form
    (one copy shared by the dense program, the sparse program, and the
    snapshot path, so they cannot diverge)."""
    import jax.numpy as jnp
    decay = (beta + jnp.sqrt(n)) / alpha + l2
    w = -(z - jnp.sign(z) * l1) / decay
    return jnp.where(jnp.abs(z) <= l1, 0.0, w)


# Every factory is lru-cached on (mesh, hyperparams): a NEW stream op
# instance (each bench drain, each pipeline re-run) must reuse the SAME
# jitted callables — a fresh closure per op would miss jax's in-memory
# jit cache and recompile the step per drain (profiled: 1.7 s of the
# 2.4 s stream drain was XLA compilation). Mesh and FieldBlockMeta are
# hashable; floats compare exactly (same-source configs hit).
#
# ``donate=True`` (the stream op passes ALINK_TPU_DONATE, default on)
# donates the (z, n) state arguments into the compiled step: XLA aliases
# the state's input buffers to its output buffers, so the per-micro-batch
# copy-on-entry of the full model state disappears and the state's HBM
# footprint halves — the compiled analogue of the reference mutating its
# CalcTask-local (w, z, n) shard in place (FtrlTrainStreamOp.java:332-390).
# Contract: the z/n you PASS are dead after the call (reuse raises) —
# the drain loop rebinds them to the outputs, and every host read
# (snapshot/checkpoint/pv) uses the live post-update arrays. The flag
# rides the lru key, so toggling never aliases through a cached program.
def _aot(fn, factory, mesh, role="step", in_specs=None, **hyper):
    """Wrap a factory's jitted program with the persistent executable
    store (ISSUE 20).  Artifacts key on the factory's own lru arguments
    plus the first call's avals — deliberately NOT on the per-model
    ``warm_coef_blake2b``: coefficients are program *arguments* and the
    executable is byte-identical across models of one geometry, so a
    content dim would churn the store once per model for the same
    program.  Inert (returns ``fn`` untouched) unless the store is
    configured."""
    from ....common import aotcache
    dims = ((("factory", factory), ("role", role), ("mesh", mesh))
            + tuple(sorted(hyper.items())))
    return aotcache.aot_jit(fn, subsystem="ftrl", cache="ftrl.step",
                            site=factory, dims=dims, mesh=mesh,
                            in_specs=in_specs)


@functools.lru_cache(maxsize=64)
def _ftrl_step_factory(mesh, alpha, beta, l1, l2, donate=False):
    """Build the jitted per-micro-batch FTRL SPMD program.

    Carry: (z, n) each (dim_pad,) sharded over mesh axis 'd' (the feature
    axis — reference's getSplitInfo ranges). X: (b, dim_pad) with columns
    sharded. Scan over rows keeps the reference's strict per-sample update
    order; psum reassembles the sharded dot product (ReduceTask).
    """
    import jax
    import jax.numpy as jnp
    from ....common.compat import shard_map
    from ....engine.communication import manifest_psum
    from jax.sharding import PartitionSpec as P

    def weights(z, n):
        return _ftrl_weights(z, n, alpha, beta, l1, l2)

    def shard_fn(X, y, z, n):
        def body(carry, xy):
            z, n = carry
            x, yy = xy
            w = weights(z, n)
            margin = manifest_psum(jnp.dot(x, w), "d", name="ftrl_margin",
                                   num_workers=mesh.size)
            p = 1.0 / (1.0 + jnp.exp(-jnp.clip(margin, -35.0, 35.0)))
            g = (p - yy) * x
            sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / alpha
            z = z + g - sigma * w
            n = n + g * g
            return (z, n), margin

        (z, n), margins = jax.lax.scan(body, (z, n), (X, y))
        return z, n, margins

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(None, "d"), P(), P("d"), P("d")),
                   out_specs=(P("d"), P("d"), P()))
    def ftrl_weights(z, n):          # the snapshot program: jit_ftrl_weights
        with jax.named_scope("ftrl_weights"):
            return weights(z, n)

    weights_fn = shard_map(ftrl_weights, mesh=mesh,
                           in_specs=(P("d"), P("d")), out_specs=P("d"))
    # weights_fn never donates: the snapshot path reads w from the LIVE
    # (z, n) and the state must survive for the next micro-batch
    _hp = dict(alpha=alpha, beta=beta, l1=l1, l2=l2, donate=donate)
    return (_aot(jax.jit(fn, donate_argnums=(2, 3) if donate else ()),
                 "_ftrl_step_factory", mesh,
                 in_specs=(P(None, "d"), P(), P("d"), P("d")), **_hp),
            _aot(jax.jit(weights_fn), "_ftrl_step_factory", mesh,
                 role="weights", in_specs=(P("d"), P("d")), **_hp))


def _kernel_check_vma(kernel: str):
    """``shard_map``'s ``check_vma`` for a step program under the
    RESOLVED kernel mode. A ``pl.pallas_call`` traces its kernel body
    outside the manual-axes context — loaded blocks are typed varying,
    values computed from them are not — so the varying-axes check cannot
    type a kernel-tier program (jax 0.9.0); the XLA path keeps the
    default check."""
    return None if kernel == "off" else False


def _state_kernels(kernel: str):
    """The state gather / duplicate-safe scatter-add pair under the
    RESOLVED FTRL kernel mode (``kernels/ftrl.py``, ISSUE 13).

    ``"off"`` returns the verbatim XLA ops — routing through these
    thunks stages the exact pre-kernel-tier primitive sequence, so the
    flag-off lowered HLO stays byte-identical (tests/test_kernels.py).
    ``"pallas"`` returns the VMEM-resident Pallas kernels, with an
    eager shape-class probe at trace time: a probe failure demotes THIS
    shape class to the XLA ops (one-time warning via
    ``kernels/runtime.demote_once``) — bitwise-identical output either
    way, so a demoted program can never poison the lru cache."""
    if kernel == "pallas":
        from ....kernels.ftrl import (gather_rows, probe_scatter,
                                      scatter_add_rows)

        def _gather(st, flat):
            C = st.shape[1] if st.ndim > 1 else 1
            if probe_scatter(st.shape[0], C, st.dtype):
                return gather_rows(st, flat)
            return st[flat]

        def _scatter(st, flat, upd):
            C = st.shape[1] if st.ndim > 1 else 1
            if probe_scatter(st.shape[0], C, st.dtype):
                return scatter_add_rows(st, flat, upd)
            return st.at[flat].add(upd)

        return _gather, _scatter
    return (lambda st, flat: st[flat],
            lambda st, flat, upd: st.at[flat].add(upd))


@functools.lru_cache(maxsize=64)
def _ftrl_sparse_step_factory(mesh, alpha, beta, l1, l2, donate=False,
                              kernel="off"):
    """Sparse twin of :func:`_ftrl_step_factory` — O(nnz) per sample.

    The micro-batch arrives as padded COO ``idx/val`` of shape
    ``(batch, width)`` replicated to every device (a Criteo row is ~40
    entries — replicating it is nothing; densifying it to 65k columns is
    ~0.5 GB per 1k-row batch, the VERDICT round-1 blocker). Each device
    owns one contiguous feature range of the sharded (z, n) state
    (reference getSplitInfo ranges, FtrlTrainStreamOp.java:74-87); a scan
    round masks its rows' entries to the local range, reads their slots,
    computes weights lazily at those slots and psums the partial dot
    product (ReduceTask, :119-135). Padding entries carry ``val == 0`` so
    every padded position is algebraically a no-op (g = 0, sigma = 0).

    The rounds never touch the state itself. A micro-batch touches far
    fewer coordinates than it has entries (22.5 % on the Criteo shape),
    and XLA's scatter into the state costs ~90 ns an update, serially,
    whatever the operand's size (PERF.md section 6, PR 26). So the rounds
    work on a table ``T`` of ``2E`` slots a state array (``E`` = entries,
    row-major, which is sample order): slot ``e < E`` holds the value of
    entry ``e``'s coordinate after ``e``'s round, slot ``E + k`` the
    state's value at the ``k``-th distinct local coordinate before the
    micro-batch. A round gathers its slots' sources from ``T`` and writes
    its own slots with ONE contiguous update; the state is read once
    before the rounds and written once after them, at distinct
    coordinates only (``ftrl_workset`` / ``ftrl_scatter``).
    """
    import jax
    import jax.numpy as jnp
    from ....common.compat import shard_map
    from ....engine.communication import manifest_psum
    from jax.sharding import PartitionSpec as P

    def weights(z, n):
        return _ftrl_weights(z, n, alpha, beta, l1, l2)

    K = 4        # samples per scan round
    BLK = 2048   # distinct coordinates a block of the state gather / write-back
    _sgather, _ = _state_kernels(kernel)
    HI = jax.lax.Precision.HIGHEST

    def shard_fn(idx, val, y, z, n):
        shard = z.shape[0]                    # block-local feature range
        lo = jax.lax.axis_index("d") * shard
        B, w = idx.shape
        Bp = -(-B // K) * K
        if Bp != B:               # zero rows are algebraic no-ops
            idx = jnp.concatenate([idx, jnp.zeros((Bp - B, w), idx.dtype)])
            val = jnp.concatenate([val, jnp.zeros((Bp - B, w), val.dtype)])
            y = jnp.concatenate([y, jnp.zeros((Bp - B,), y.dtype)])
        R, S = Bp // K, K * w                 # rounds, entries a round
        E = R * S                             # entries, row-major = sample order
        blk = min(BLK, E)

        with jax.named_scope("ftrl_workset"):
            flat = idx.reshape(E)
            here = (flat >= lo) & (flat < lo + shard)
            # non-local entries take a sentinel past the shard: it sorts
            # last and is never gathered from the state or written to it
            c = jnp.where(here, flat - lo, shard).astype(jnp.int32)
            pos = jnp.arange(E, dtype=jnp.int32)
            sc, se = jax.lax.sort((c, pos), num_keys=1, is_stable=True)
            se_prev = jnp.roll(se, 1)
            first = (pos == 0) | (sc != jnp.roll(sc, 1))
            last = ((pos == E - 1) | (sc != jnp.roll(sc, -1))) & (sc < shard)
            # an entry reads the slot of the entry before it in (c, e)
            # order when that one lies in an earlier round; a coordinate's
            # first round reads the state's slot; every further entry of
            # the same coordinate in the same round reads what the first
            # of them reads (forward fill, a round has at most S entries)
            have = first | (se // S != se_prev // S)
            src = jnp.where(first, E - 1 + jnp.cumsum(first, dtype=jnp.int32),
                            se_prev)
            for i in range((S - 1).bit_length()):
                src = jnp.where(have, src, jnp.roll(src, 1 << i))
                have = have | jnp.roll(have, 1 << i)
            _, ptr = jax.lax.sort((se, src), num_keys=1)
            # distinct local coordinates, ascending, with the entry that
            # holds each one's final value; the sentinel fills the rest
            uc, ul = jax.lax.sort((jnp.where(last, sc, shard), se),
                                  num_keys=1)
            nblk = (jnp.sum(last) + blk - 1) // blk

            def block(b):
                at = jnp.minimum(b * blk, E - blk)
                return at, jax.lax.dynamic_slice(uc, (at,), (blk,))

            T0 = jnp.zeros((2 * E,), z.dtype)
            vma = tuple(jax.typeof(z).vma)
            if vma:               # a loop's carry keeps its varying axes
                T0 = jax.lax.pcast(T0, vma, to="varying")

            def load(b, T):
                Tz, Tn = T
                at, ci = block(b)
                ci = jnp.minimum(ci, shard - 1)
                return (jax.lax.dynamic_update_slice(
                            Tz, _sgather(z, ci), (E + at,)),
                        jax.lax.dynamic_update_slice(
                            Tn, _sgather(n, ci), (E + at,)))

            T = jax.lax.fori_loop(0, nblk, load, (T0, T0))

        def body(T, xs):
            Tz, Tn = T
            r, xi, xv, yy, pr = xs            # (), (K, w), (K, w), (K,), (S,)
            # the named scopes group the round's device ops by what they
            # do in the profiler's viewer (op metadata only)
            with jax.named_scope("ftrl_gather"):
                local = (xi >= lo) & (xi < lo + shard)
                zs = jnp.where(local, Tz[pr].reshape(K, w), 0.0)
                ns = jnp.where(local, Tn[pr].reshape(K, w), 0.0)
            dzs, dns, margins = [], [], []
            with jax.named_scope("ftrl_update"):
                fi, fl = xi.reshape(S), local.reshape(S)
                # same[a, b]: entries a and b of the round address the
                # same local coordinate
                same = ((fi[:, None] == fi[None, :])
                        & fl[:, None] & fl[None, :]).astype(zs.dtype)
                M = same.reshape(K, w, K, w)
                # K samples a round, EXACT strict semantics: sample k's
                # visible values are the pre-round values corrected by
                # earlier samples' deltas through straight-line (w, w)
                # same-feature matvecs (bit-identical to the per-sample
                # scan on collision-free rounds, f32-round-identical
                # under collisions)
                for k in range(K):
                    zk, nk = zs[k], ns[k]
                    for j in range(k):
                        # HIGHEST: the default matmul precision would
                        # round the f32 deltas to bf16 on the MXU and
                        # break the exact-strict-semantics claim under
                        # collisions (negligible cost at w ~ 40)
                        zk = zk + jnp.matmul(M[k, :, j], dzs[j],
                                             precision=HI)
                        nk = nk + jnp.matmul(M[k, :, j], dns[j],
                                             precision=HI)
                    wj = jnp.where(local[k], weights(zk, nk), 0.0)
                    margin = manifest_psum(jnp.sum(xv[k] * wj), "d",
                                           name="ftrl_margin",
                                           num_workers=mesh.size)
                    p = 1.0 / (1.0 + jnp.exp(-jnp.clip(margin, -35.0, 35.0)))
                    g = (p - yy[k]) * xv[k]
                    sigma = (jnp.sqrt(nk + g * g) - jnp.sqrt(nk)) / alpha
                    dzs.append(jnp.where(local[k], g - sigma * wj, 0.0))
                    dns.append(jnp.where(local[k], g * g, 0.0))
                    margins.append(margin)
                # every entry's slot takes its coordinate's value after
                # the round: all of the round's deltas at that coordinate
                D = jnp.stack([jnp.concatenate(dzs), jnp.concatenate(dns)],
                              axis=-1)
                A = jnp.matmul(same, D, precision=HI)
                Tz = jax.lax.dynamic_update_slice(
                    Tz, zs.reshape(S) + A[:, 0], (r * S,))
                Tn = jax.lax.dynamic_update_slice(
                    Tn, ns.reshape(S) + A[:, 1], (r * S,))
            return (Tz, Tn), jnp.stack(margins)

        (Tz, Tn), margins = jax.lax.scan(
            body, T, (jnp.arange(R, dtype=jnp.int32),
                      idx.reshape(R, K, w), val.reshape(R, K, w),
                      y.reshape(R, K), ptr.reshape(R, S)))

        with jax.named_scope("ftrl_scatter"):
            # a plain set: the unique/sorted hint makes XLA stream the
            # whole state through the chip once a call (PERF.md section 6)
            def store(b, zn):
                z, n = zn
                at, ci = block(b)
                li = jax.lax.dynamic_slice(ul, (at,), (blk,))
                return (z.at[ci].set(Tz[li], mode="drop"),
                        n.at[ci].set(Tn[li], mode="drop"))

            z, n = jax.lax.fori_loop(0, nblk, store, (z, n))
        return z, n, margins.reshape(Bp)[:B]

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(), P(), P(), P("d"), P("d")),
                   out_specs=(P("d"), P("d"), P()),
                   check_vma=_kernel_check_vma(kernel))
    return _aot(jax.jit(fn, donate_argnums=(3, 4) if donate else ()),
                "_ftrl_sparse_step_factory", mesh,
                in_specs=(P(), P(), P(), P("d"), P("d")), alpha=alpha,
                beta=beta, l1=l1, l2=l2, donate=donate, kernel=kernel)


@functools.lru_cache(maxsize=64)
def _ftrl_sparse_chained_step_factory(mesh, alpha, beta, l1, l2, K=16,
                                      donate=False, kernel="off"):
    """Chained-correction strict FTRL — EXACT strict semantics at chunked
    throughput (``update_mode="chained"``).

    The strict per-sample contract is inherently a chain: sample k's
    margin must be computed at weights reflecting samples 0..k-1. The
    K=4 kernel above pays that chain with k-1 PAIRS of same-feature
    matmuls per sample — O(K^2) dependent ops — which is why K=8/16
    measured slower (docs/performance.md "Where the strict scan's
    time goes"). This kernel restructures the correction so the chain
    stays O(K) dependent ops:

      * ONE gather of the K rows' (z, n) slots at the pre-chunk state,
        stacked (K, w, 2);
      * a collision tensor ``M[k, j, a, b] = [sample k's slot a and
        sample j's slot b address the same local state element]`` built
        once per chunk OFF the dependent chain (pure elementwise
        compares, (K, K, w, w));
      * per sample, ONE dense triangular matvec
        ``corr_k = einsum('jab,jbc->ac', M[k], D)`` over the stacked
        delta buffer D (rows j >= k are still zero, so the triangular
        masking is implicit) corrects both z and n in a single
        contraction — sample k sees exactly the earlier samples'
        deltas at shared features;
      * all K deltas land in ONE duplicate-safe scatter-add.

    The scan shortens K-fold while each sample costs ~5 dependent ops
    (matvec, weights, psum, grad, delta-write) instead of the per-sample
    kernel's gather+scatter+chain. Semantics: bit-identical to the
    per-sample scan on collision-free chunks (the matvec adds an exact
    0.0); on colliding chunks the only difference is ASSOCIATION —
    fl(base + fl(d1 + d2)) instead of fl(fl(base + d1) + d2) — i.e.
    f32-round-level (documented tolerance: rtol 1e-4 on trajectories,
    tests/test_perf_kernels.py). ``K`` rides the lru/jit cache key, so
    changing the chunk length can never serve a stale program.
    """
    import jax
    import jax.numpy as jnp
    from ....common.compat import shard_map
    from ....engine.communication import manifest_psum
    from jax.sharding import PartitionSpec as P

    def weights(z, n):
        return _ftrl_weights(z, n, alpha, beta, l1, l2)

    _sgather, _sscatter = _state_kernels(kernel)
    if kernel == "pallas":
        from ....kernels.ftrl import chained_corr, chained_kernel_available

    def shard_fn(idx, val, y, z, n):
        shard = z.shape[0]
        lo = jax.lax.axis_index("d") * shard
        B, w = idx.shape
        Bp = -(-B // K) * K
        if Bp != B:               # zero rows are algebraic no-ops
            idx = jnp.concatenate([idx, jnp.zeros((Bp - B, w), idx.dtype)])
            val = jnp.concatenate([val, jnp.zeros((Bp - B, w), val.dtype)])
            y = jnp.concatenate([y, jnp.zeros((Bp - B,), y.dtype)])
        # resolved at the CANONICAL probe width, never per batch width:
        # the chained checkpoint signature folds on exactly this
        # predicate, and a width-dependent demotion would change the
        # accumulation association mid-stream under one signature
        use_tri = kernel == "pallas" and chained_kernel_available(
            K, val.dtype)

        def body(carry, xvy):
            z, n = carry
            xi, xv, yy = xvy                  # (K, w), (K, w), (K,)
            local = (xi >= lo) & (xi < lo + shard)
            li = jnp.clip(xi - lo, 0, shard - 1)
            flat = li.reshape(-1)
            zs = jnp.where(local, _sgather(z, flat).reshape(K, w), 0.0)
            ns = jnp.where(local, _sgather(n, flat).reshape(K, w), 0.0)
            # collision tensor, built once per chunk in parallel (not on
            # the dependent chain)
            M = ((xi[:, None, :, None] == xi[None, :, None, :])
                 & local[:, None, :, None] & local[None, :, None, :]
                 ).astype(zs.dtype)           # (K, K, w, w)
            D = jnp.zeros((K, w, 2), zs.dtype)
            margins = []
            for k in range(K):
                # HIGHEST: bf16 MXU rounding of the f32 deltas would
                # break the exact-strict-semantics claim under collisions.
                # The triangular Pallas kernel contracts over exactly the
                # k live delta rows (rows j >= k are structurally zero —
                # dead flops the dense einsum pays every sample) in full
                # input precision; association-only difference, inside
                # the pinned chained tolerance
                if use_tri:
                    corr = chained_corr(M[k], D, k)
                else:
                    corr = jnp.einsum("jab,jbc->ac", M[k], D,
                                      precision=jax.lax.Precision.HIGHEST)
                zk = zs[k] + corr[:, 0]
                nk = ns[k] + corr[:, 1]
                wk = jnp.where(local[k], weights(zk, nk), 0.0)
                margin = manifest_psum(jnp.sum(xv[k] * wk), "d",
                                       name="ftrl_margin",
                                       num_workers=mesh.size)
                p = 1.0 / (1.0 + jnp.exp(-jnp.clip(margin, -35.0, 35.0)))
                g = (p - yy[k]) * xv[k]
                sigma = (jnp.sqrt(nk + g * g) - jnp.sqrt(nk)) / alpha
                D = D.at[k].set(jnp.stack(
                    [jnp.where(local[k], g - sigma * wk, 0.0),
                     jnp.where(local[k], g * g, 0.0)], axis=-1))
                margins.append(margin)
            z = _sscatter(z, flat, D[..., 0].reshape(-1))
            n = _sscatter(n, flat, D[..., 1].reshape(-1))
            return (z, n), jnp.stack(margins)

        (z, n), margins = jax.lax.scan(
            body, (z, n), (idx.reshape(Bp // K, K, w),
                           val.reshape(Bp // K, K, w),
                           y.reshape(Bp // K, K)))
        return z, n, margins.reshape(Bp)[:B]

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(), P(), P(), P("d"), P("d")),
                   out_specs=(P("d"), P("d"), P()),
                   check_vma=_kernel_check_vma(kernel))
    return _aot(jax.jit(fn, donate_argnums=(3, 4) if donate else ()),
                "_ftrl_sparse_chained_step_factory", mesh,
                in_specs=(P(), P(), P(), P("d"), P("d")), alpha=alpha,
                beta=beta, l1=l1, l2=l2, K=K, donate=donate,
                kernel=kernel)


@functools.lru_cache(maxsize=64)
def _ftrl_sparse_staleness_step_factory(mesh, alpha, beta, l1, l2, K,
                                        donate=False, kernel="off"):
    """Bounded-staleness sparse FTRL — the reference's ACTUAL feedback-edge
    semantics, made explicit and measured.

    The reference does not provide strict per-sample ordering: its sharded
    CalcTasks compute partial margins from their CURRENT local state and
    apply each sample's update only when the summed margin returns over the
    cyclic Flink feedback edge (FtrlTrainStreamOp.java:120-135), so every
    sample's gradient is computed at weights that are stale by however many
    samples are in flight in the network buffers. This kernel models that
    contract with a bound: a ``lax.scan`` over chunks of ``K`` rows where
    every row's margin/gradient is computed at the weights from before the
    chunk (staleness <= K-1 samples) and the K updates land in one
    duplicate-safe scatter-add. ``K=1`` degenerates to the strict
    per-sample program.

    Against the strict kernel this drops the O(K^2) same-feature
    correction matvecs AND shortens the scan K/4-fold, so K can grow to
    32-64 — the op-issue-latency chain (the strict kernel's measured
    bottleneck) shrinks proportionally. The (z, n) state rides the scan
    carry STACKED as (shard, 2) so each chunk issues ONE gather and ONE
    scatter instead of two of each.
    """
    import jax
    import jax.numpy as jnp
    from ....common.compat import shard_map
    from ....engine.communication import manifest_psum
    from jax.sharding import PartitionSpec as P

    def weights(z, n):
        return _ftrl_weights(z, n, alpha, beta, l1, l2)

    _sgather, _sscatter = _state_kernels(kernel)

    def shard_fn(idx, val, y, z, n):
        shard = z.shape[0]
        lo = jax.lax.axis_index("d") * shard
        B, w = idx.shape
        Bp = -(-B // K) * K
        if Bp != B:               # zero rows are algebraic no-ops
            idx = jnp.concatenate([idx, jnp.zeros((Bp - B, w), idx.dtype)])
            val = jnp.concatenate([val, jnp.zeros((Bp - B, w), val.dtype)])
            y = jnp.concatenate([y, jnp.zeros((Bp - B,), y.dtype)])
        zn = jnp.stack([z, n], axis=-1)               # (shard, 2)

        def body(zn, xvy):
            xi, xv, yy = xvy                          # (K, w), (K, w), (K,)
            local = (xi >= lo) & (xi < lo + shard)
            li = jnp.clip(xi - lo, 0, shard - 1)
            flat = li.reshape(-1)
            s = _sgather(zn, flat).reshape(K, w, 2)
            zj = jnp.where(local, s[..., 0], 0.0)
            nj = jnp.where(local, s[..., 1], 0.0)
            wj = jnp.where(local, weights(zj, nj), 0.0)
            margins = manifest_psum((xv * wj).sum(-1), "d",
                                    name="ftrl_margins",
                                    num_workers=mesh.size)
            p = 1.0 / (1.0 + jnp.exp(-jnp.clip(margins, -35.0, 35.0)))
            g = (p - yy)[:, None] * xv
            sigma = (jnp.sqrt(nj + g * g) - jnp.sqrt(nj)) / alpha
            dz = jnp.where(local, g - sigma * wj, 0.0)
            dn = jnp.where(local, g * g, 0.0)
            zn = _sscatter(zn, flat,
                           jnp.stack([dz.reshape(-1), dn.reshape(-1)],
                                     axis=-1))
            return zn, margins

        zn, margins = jax.lax.scan(
            body, zn, (idx.reshape(Bp // K, K, w),
                       val.reshape(Bp // K, K, w),
                       y.reshape(Bp // K, K)))
        return zn[:, 0], zn[:, 1], margins.reshape(Bp)[:B]

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(), P(), P(), P("d"), P("d")),
                   out_specs=(P("d"), P("d"), P()),
                   check_vma=_kernel_check_vma(kernel))
    return _aot(jax.jit(fn, donate_argnums=(3, 4) if donate else ()),
                "_ftrl_sparse_staleness_step_factory", mesh,
                in_specs=(P(), P(), P(), P("d"), P("d")), alpha=alpha,
                beta=beta, l1=l1, l2=l2, K=K, donate=donate,
                kernel=kernel)


@functools.lru_cache(maxsize=64)
def _ftrl_sparse_batch_step_factory(mesh, alpha, beta, l1, l2,
                                    donate=False):
    """Batched-update twin of :func:`_ftrl_sparse_step_factory`.

    ``update_mode="batch"``: every row's gradient is computed at the
    weights from *before* the micro-batch, and the (z, n) updates land in
    one fused gather/scatter — no sequential scan, so the whole batch is
    one data-parallel SPMD program and throughput is bound by memory
    bandwidth instead of per-sample loop latency (~50x the strict scan on
    v5e at Criteo shape).

    This is a deliberate TPU-first semantics relaxation of the reference's
    strict per-sample order (FtrlTrainStreamOp.java CalcTask): within one
    micro-batch, updates from earlier rows are not visible to later rows.
    When the rows of a batch touch pairwise-disjoint feature sets it is
    EXACTLY the per-sample program (no state is shared inside the batch);
    with hashed CTR features collisions inside a 1k-row batch are rare, so
    the trajectories track closely (pinned by tests). Convergence of
    delayed/minibatched FTRL-proximal is standard online-learning
    practice; the strict mode stays the default for reference parity.
    """
    import jax
    import jax.numpy as jnp
    from ....common.compat import shard_map
    from ....engine.communication import manifest_psum
    from jax.sharding import PartitionSpec as P

    def weights(z, n):
        return _ftrl_weights(z, n, alpha, beta, l1, l2)

    def shard_fn(idx, val, y, z, n):
        shard = z.shape[0]
        lo = jax.lax.axis_index("d") * shard
        local = (idx >= lo) & (idx < lo + shard)       # (B, width)
        li = jnp.clip(idx - lo, 0, shard - 1)
        zj = jnp.where(local, z[li], 0.0)
        nj = jnp.where(local, n[li], 0.0)
        wj = jnp.where(local, weights(zj, nj), 0.0)
        margins = manifest_psum((val * wj).sum(-1), "d",
                                name="ftrl_margins",
                                num_workers=mesh.size)
        p = 1.0 / (1.0 + jnp.exp(-jnp.clip(margins, -35.0, 35.0)))
        g = (p - y)[:, None] * val
        sigma = (jnp.sqrt(nj + g * g) - jnp.sqrt(nj)) / alpha
        dz = jnp.where(local, g - sigma * wj, 0.0)
        dn = jnp.where(local, g * g, 0.0)
        # duplicate feature slots inside the batch accumulate their rows'
        # contributions (padding has val == 0 -> dz = dn = 0)
        z = z.at[li.reshape(-1)].add(dz.reshape(-1))
        n = n.at[li.reshape(-1)].add(dn.reshape(-1))
        return z, n, margins

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(), P(), P(), P("d"), P("d")),
                   out_specs=(P("d"), P("d"), P()))
    return _aot(jax.jit(fn, donate_argnums=(3, 4) if donate else ()),
                "_ftrl_sparse_batch_step_factory", mesh,
                in_specs=(P(), P(), P(), P("d"), P("d")), alpha=alpha,
                beta=beta, l1=l1, l2=l2, donate=donate)


@functools.lru_cache(maxsize=64)
def _ftrl_fb_batch_step_factory(mesh, meta, alpha, beta, l1, l2,
                                with_val: bool = True, donate=False):
    """Field-blocked batched FTRL — the Criteo fast path.

    Both gather/scatter-style modes above are bound by XLA's serialized
    random gather/scatter on TPU (~5M touched elements/s measured on v5e
    — the same wall the round-1 L-BFGS hit). When the input is
    field-aware hashed (exactly one slot per field per row,
    ops/fieldblock.py), every state access becomes a factored one-hot MXU
    matmul instead: per-slot (n, w) reads via :func:`fb_gather`, margin
    margins from the same gathered slots, and the update scatter via
    :func:`fb_rmatvec`.
    Same batched-update semantics as the COO batch factory (gradients at
    pre-batch weights; exact for collision-free batches).

    Sharding: devices own contiguous FIELD groups (meta.num_fields must
    divide by the mesh size — pad with a zero-valued dummy field if not);
    each device runs the kernels on its own field columns and the margin
    psums, the field-sharded analogue of the reference's feature ranges.
    """
    import jax
    import jax.numpy as jnp
    from ....common.compat import shard_map
    from ....engine.communication import manifest_psum
    from jax.sharding import PartitionSpec as P

    from ....ops.fieldblock import FieldBlockMeta, fb_gather, fb_rmatvec

    n_dev = mesh.devices.size
    if meta.num_fields % n_dev:
        raise ValueError(f"num_fields {meta.num_fields} must be a multiple "
                         f"of the mesh size {n_dev} (pad with a dummy field)")
    local_meta = FieldBlockMeta(meta.num_fields // n_dev, meta.field_size)

    def weights(z, n):
        return _ftrl_weights(z, n, alpha, beta, l1, l2)

    def shard_fn(fb_idx, val, y, z, n):
        # fb_idx/val: (B, F) replicated; z/n: local field-group slice.
        # fb_idx may arrive int16 (half the host->device bytes when
        # field_size fits); widen before gathering. When with_val=False
        # (full batch of pure one-hot rows) val is None and the implicit
        # value is 1.0 — no val tensor is shipped to the device.
        F_loc = local_meta.num_fields
        k0 = jax.lax.axis_index("d") * F_loc
        idx_l = jax.lax.dynamic_slice_in_dim(fb_idx, k0, F_loc, 1)
        idx_l = idx_l.astype(jnp.int32)
        val_l = (jnp.ones(idx_l.shape, jnp.float32) if val is None else
                 jax.lax.dynamic_slice_in_dim(val, k0, F_loc, 1))
        w = weights(z, n)
        nj = fb_gather(idx_l, n, local_meta)
        wj = fb_gather(idx_l, w, local_meta)
        # margins from the exact f32 per-slot gather — a separate fb_matvec
        # would redo the same one-hot pass with bf16 operand rounding
        margins = manifest_psum((val_l * wj).sum(-1), "d",
                                name="ftrl_margins",
                                num_workers=mesh.size)
        p = 1.0 / (1.0 + jnp.exp(-jnp.clip(margins, -35.0, 35.0)))
        g = (p - y)[:, None] * val_l                        # (B, F_loc)
        sigma = (jnp.sqrt(nj + g * g) - jnp.sqrt(nj)) / alpha
        ones = jnp.ones_like(y)
        dz = fb_rmatvec(idx_l, ones, local_meta, val=g - sigma * wj,
                        dtype=jnp.float32)
        dn = fb_rmatvec(idx_l, ones, local_meta, val=g * g,
                        dtype=jnp.float32)
        return z + dz.astype(z.dtype), n + dn.astype(n.dtype), margins

    if with_val:
        fn = shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(), P(), P(), P("d"), P("d")),
                       out_specs=(P("d"), P("d"), P()))
        return _aot(jax.jit(fn, donate_argnums=(3, 4) if donate else ()),
                    "_ftrl_fb_batch_step_factory", mesh,
                    in_specs=(P(), P(), P(), P("d"), P("d")), meta=meta,
                    alpha=alpha, beta=beta, l1=l1, l2=l2,
                    with_val=with_val, donate=donate)
    fn = shard_map(lambda fbi, y, z, n: shard_fn(fbi, None, y, z, n),
                   mesh=mesh, in_specs=(P(), P(), P("d"), P("d")),
                   out_specs=(P("d"), P("d"), P()))
    return _aot(jax.jit(fn, donate_argnums=(2, 3) if donate else ()),
                "_ftrl_fb_batch_step_factory", mesh,
                in_specs=(P(), P(), P("d"), P("d")), meta=meta,
                alpha=alpha, beta=beta, l1=l1, l2=l2,
                with_val=with_val, donate=donate)


@functools.lru_cache(maxsize=1)
def _pv_stats_fn():
    """Jitted progressive-validation reducer: margins are computed at
    PRE-update weights in every FTRL mode (per sample in the strict scan,
    per chunk/batch in the others), so scoring them against the labels is
    exactly the progressive validation of the FTRL ad-click papers — an
    honest online estimate of held-out loss with zero extra passes.
    Returns (sum logloss, #correct, #non-finite margins) as device
    scalars; the caller defers the host fetch to snapshot/checkpoint
    boundaries (a fetch per batch would make the host wait for the
    device every batch — see the drain NOTE below).

    Takes the FULL padded batch plus a traced row count and masks inside
    the program: slicing to the per-batch row count on the host would
    recompile the reducer for every distinct batch size, defeating the
    padded-shape scheme every step factory uses."""
    import jax
    import jax.numpy as jnp

    def stats(margins, y, nrows):
        real = jnp.arange(margins.shape[0]) < nrows
        finite = jnp.isfinite(margins)
        m = jnp.clip(margins, -35.0, 35.0)
        ll = jnp.logaddexp(0.0, -m) * y + jnp.logaddexp(0.0, m) * (1.0 - y)
        # propagate non-finiteness the clip would hide: a NaN/Inf margin
        # must surface in the logloss sum, not be laundered by clipping
        ll = jnp.where(finite, ll, jnp.nan)
        # a non-finite margin is never a correct prediction — without the
        # finite mask, NaN > 0 == False would score label-0 rows 'right'
        # on exactly the diverged batches the monitor exists to flag
        correct = (((margins > 0) == (y > 0.5)) & finite & real).sum()
        nonfinite = ((~finite) & real).sum()
        return jnp.where(real, ll, 0.0).sum(), correct, nonfinite

    return jax.jit(stats)


def _distinct(idx) -> int:
    """Distinct coordinates of one shipped sparse micro-batch: what the
    strict step's working table gathers from the state and writes back to
    it, against the entries it folds. Counted on the host (a device
    program's first call would compile inside a traced window), and only
    for a recorded ``ftrl.snapshot`` span."""
    return int(np.unique(np.asarray(idx)).size)


# Trace-time collective manifests, memoized per (step program, arg-shape
# signature). The step programs are jit/lru-cached, so their
# manifest_psum records fire once per COMPILE — without a replay, a
# 10k-batch drain charges its margin AllReduce to the metrics registry
# exactly once. Each program's manifest is captured from an AOT
# ``.lower`` trace (no execution, so no donated-buffer hazard) and the
# drain loop replays it per micro-batch via record_manifest. Weak keys:
# a program evicted from its factory's lru drops its memo row too.
_STEP_MANIFESTS = weakref.WeakKeyDictionary()


def _step_manifest(step, args):
    try:
        per = _STEP_MANIFESTS.setdefault(step, {})
    except TypeError:          # unweakrefable program object: skip the
        return ()              # accounting rather than leak a strong ref
    sig = tuple((getattr(a, "shape", None), str(getattr(a, "dtype", "")))
                for a in args)
    man = per.get(sig)
    if man is None:
        from ....engine.communication import collecting
        cap = []
        try:
            with collecting(cap):
                step.lower(*args)
        except Exception as e:  # accounting must never break training —
            cap = []            # but a muted metric must not be silent:
            import warnings     # the empty manifest is memoized for good
            warnings.warn(
                f"FTRL collective accounting disabled for this step "
                f"program (AOT lower failed: {e!r}); "
                f"alink_collective_calls_total will under-count this "
                f"drain", RuntimeWarning, stacklevel=2)
        man = per[sig] = tuple(cap)
    return man


@functools.lru_cache(maxsize=64)
def _ftrl_dense_batch_step_factory(mesh, alpha, beta, l1, l2,
                                   donate=False):
    """Batched-update twin of the dense program (see the sparse batch
    factory's docstring for semantics)."""
    import jax
    import jax.numpy as jnp
    from ....common.compat import shard_map
    from ....engine.communication import manifest_psum
    from jax.sharding import PartitionSpec as P

    def weights(z, n):
        return _ftrl_weights(z, n, alpha, beta, l1, l2)

    def shard_fn(X, y, z, n):
        w = weights(z, n)
        margins = manifest_psum(X @ w, "d", name="ftrl_margins",
                                num_workers=mesh.size)
        p = 1.0 / (1.0 + jnp.exp(-jnp.clip(margins, -35.0, 35.0)))
        g = (p - y)[:, None] * X                       # (B, shard)
        sigma = (jnp.sqrt(n[None, :] + g * g) - jnp.sqrt(n[None, :])) / alpha
        z = z + (g - sigma * w[None, :]).sum(0)
        n = n + (g * g).sum(0)
        return z, n, margins

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(None, "d"), P(), P("d"), P("d")),
                   out_specs=(P("d"), P("d"), P()))
    return _aot(jax.jit(fn, donate_argnums=(2, 3) if donate else ()),
                "_ftrl_dense_batch_step_factory", mesh,
                in_specs=(P(None, "d"), P(), P("d"), P("d")), alpha=alpha,
                beta=beta, l1=l1, l2=l2, donate=donate)


class FtrlTrainStreamOp(StreamOperator, HasVectorCol, HasFeatureCols, HasLabelCol):
    """Online FTRL trainer; output is the model-snapshot stream.

    Requires a batch-trained initial linear model (warm start), exactly as
    the reference does (FtrlTrainStreamOp.java:56-60).
    """

    ALPHA = ParamInfo("alpha", float, default=0.1)
    BETA = ParamInfo("beta", float, default=1.0)
    L1 = ParamInfo("l1", float, default=0.0)
    L2 = ParamInfo("l2", float, default=0.0)
    TIME_INTERVAL = ParamInfo("time_interval", float, default=1.0)
    VECTOR_SIZE = ParamInfo("vector_size", int, default=0)
    WITH_INTERCEPT = ParamInfo("with_intercept", bool, default=True)
    # "sample" = STRICT per-sample scan (a stronger ordering guarantee than
    # the reference gives); "chained" = the SAME strict semantics through
    # the chained-correction chunk kernel (K-fold shorter scan, exact on
    # collision-free chunks, f32-round-equal under collisions — see
    # _ftrl_sparse_chained_step_factory); "staleness" = bounded-staleness
    # chunked updates (gradients at weights <= staleness-1 samples old —
    # the reference's actual feedback-edge contract,
    # FtrlTrainStreamOp.java:120-135, with the bound made explicit);
    # "batch" = fused per-micro-batch updates (gradients at pre-batch
    # weights) — the TPU-first high-throughput mode, exact for
    # collision-free batches
    UPDATE_MODE = ParamInfo("update_mode", str, default="sample",
                            validator=InValidator(["sample", "chained",
                                                   "staleness", "batch"]))
    STALENESS = ParamInfo("staleness", int, default=32,
                          description="chunk size for update_mode="
                                      "'staleness' (max update delay in "
                                      "samples)",
                          validator=RangeValidator(1, None))
    CHUNK_SIZE = ParamInfo("chunk_size", int, default=16,
                           description="chunk length for update_mode="
                                       "'chained' (strict semantics at "
                                       "any value; larger = shorter scan "
                                       "+ more correction flops)",
                           validator=RangeValidator(1, None))
    # stream durability (common/checkpoint.py): persist the (z, n) FTRL
    # state every N micro-batches with bounded retention; a crash-restarted
    # op with the same checkpoint_dir resumes from the newest valid
    # snapshot and SKIPS the already-committed prefix of the (replayed)
    # input stream — on a deterministic source the recovered model is
    # bit-identical to the uninterrupted run's.
    CHECKPOINT_DIR = ParamInfo("checkpoint_dir", str, default=None)
    CHECKPOINT_EVERY = ParamInfo("checkpoint_every_batches", int, default=0,
                                 description="micro-batches between state "
                                             "snapshots (0 = off)")
    CHECKPOINT_KEEP = ParamInfo("checkpoint_keep", int, default=3,
                                validator=RangeValidator(1, None))
    RESUME = ParamInfo("resume", bool, default=True,
                       description="resume from the newest valid snapshot "
                                   "in checkpoint_dir when one exists")
    # training-health monitoring (common/health.py): a HealthMonitor fed
    # per-micro-batch progressive-validation logloss/accuracy (margins at
    # pre-update weights), non-finite margin counts, and per-snapshot
    # weight drift vs the previous emitted model. Host fetches of the
    # monitoring scalars are deferred to snapshot/checkpoint boundaries
    # so the drain's asynchronous dispatch pipeline stays unbroken.
    HEALTH = ParamInfo("health", object, default=None,
                       description="HealthMonitor for per-batch "
                                   "progressive validation + drift")

    def __init__(self, initial_model: Optional[BatchOperator] = None,
                 params: Optional[Params] = None, **kwargs):
        super().__init__(params, **kwargs)
        self._initial_model = initial_model
        self._device_snapshot_hook = None
        self._batch_hook = None

    def set_batch_hook(self, hook) -> "FtrlTrainStreamOp":
        """Register a host-side micro-batch lifecycle hook (ISSUE 15,
        the online DAG's pacing point): ``hook("pre", b, t)`` fires
        before batch ``b``'s state update runs (1-based, ``t`` = event
        time) and ``hook("post", b, t)`` after the update — and any
        snapshot emission the batch triggered — has committed. The hook
        runs on the drain thread and MAY BLOCK (that is the point: the
        DAG's deterministic pacing holds the trainer at ``pre`` until
        the scoring leg has consumed the model state the batch is about
        to advance). Unset (the default) the drain is byte-for-byte the
        hook-less path; the hook is never read at trace time and shapes
        no compiled program."""
        self._batch_hook = hook
        return self

    def set_device_snapshot_consumer(self, hook) -> "FtrlTrainStreamOp":
        """Register a device-to-device snapshot consumer (ROADMAP item 1
        leftover): at each emission boundary ``hook(w_device, info)`` is
        handed the LIVE device weights derived from the device-resident
        (z, n) state (``weights_fn`` — never donates, so the state
        survives) plus layout info (``dim``, ``fb_S``,
        ``has_intercept``, ``batch``, ``event_time``). When the hook
        returns True the host model-table snapshot — and its
        device->host weight fetch — is SKIPPED for that boundary:
        nothing is yielded and the model stays on the mesh end-to-end
        (the serving tier's ``swap_weights`` path,
        :class:`~alink_tpu.serving.server.DeviceWeightsFeeder`). A
        False/None return falls back to the host snapshot unchanged."""
        self._device_snapshot_hook = hook
        return self

    # ------------------------------------------------------------------
    def _load_initial(self) -> LinearModelData:
        if self._initial_model is None:
            raise ValueError(
                "FTRL requires an initial batch model (reference "
                "FtrlTrainStreamOp.java:56-60 warm start)")
        table = self._initial_model.get_output_table()
        return LinearModelDataConverter.load_table(table)

    def link_from(self, data_op: StreamOperator) -> "FtrlTrainStreamOp":
        # the trainer's set-up on the flight recorder (coarse, once a
        # link): ``ftrl.link`` here with the warm start's ``ftrl.warm_hash``
        # under it; ``ftrl.state_alloc`` / ``ftrl.state_ship`` when the
        # drain's first micro-batch fixes the state's layout
        with trace_span("ftrl.link", cat="stream", coarse=True):
            return self._link(data_op)

    def _link(self, data_op: StreamOperator) -> "FtrlTrainStreamOp":
        env = self.get_ml_env()
        mesh = env.mesh
        n_dev = env.num_workers * env.model_parallelism
        init = self._load_initial()
        self._schema = LinearModelDataConverter(init.label_type).schema

        alpha, beta = float(self.get_alpha()), float(self.get_beta())
        l1, l2 = float(self.get_l1()), float(self.get_l2())
        interval = float(self.get_time_interval())
        vector_col = self.params._m.get("vector_col") or init.vector_col
        feature_cols = self.params._m.get("feature_cols") or init.feature_names
        label_col = self.get_label_col()
        has_icpt = init.has_intercept

        dim = init.coef.shape[0]            # includes intercept slot if any
        dim_pad = -(-dim // n_dev) * n_dev  # feature ranges, one per device
        update_mode = self.params._m.get("update_mode", "sample")
        batch_mode = update_mode == "batch"
        staleness = int(self.params._m.get("staleness", 32))
        chunk_size = int(self.params._m.get("chunk_size", 16))
        ck_dir = self.params._m.get("checkpoint_dir")
        ck_every = int(self.params._m.get("checkpoint_every_batches", 0) or 0)
        ck_keep = int(self.params._m.get("checkpoint_keep", 3))
        ck_resume = bool(self.params._m.get("resume", True))
        from ....common.health import warn_if_disabled
        monitor = self.params._m.get("health")
        mon_on = monitor is not None \
            and warn_if_disabled("FtrlTrainStreamOp(health=...)")
        # snapshot identity: a resume target trained with different
        # hyperparameters, geometry or warm-start model is a different
        # model — refuse it. The coef fingerprint catches a same-dim but
        # DIFFERENT warm model; the input stream itself cannot be
        # fingerprinted at link time (resume assumes a deterministic
        # replayed source — docs/checkpointing.md)
        import hashlib as _hashlib
        with trace_span("ftrl.warm_hash", cat="stream", coarse=True,
                        args={"bytes": int(np.asarray(init.coef).nbytes)}):
            _warm_fp = _hashlib.blake2b(
                np.ascontiguousarray(np.asarray(init.coef)).tobytes(),
                digest_size=12).hexdigest()
        # ONE ExecutionPlan per drain (ROADMAP item 1): hyperparameters,
        # geometry and the key-folding flags — ALINK_TPU_FTRL_KERNEL
        # (the resolved tier mode the step factories fold into their lru
        # keys, so toggling never serves a stale step program; the
        # chained signature folds the availability-PROBED tier, so a
        # probe-demoted drain keeps the flag-off signature and its
        # snapshots stay interchangeable) and ALINK_TPU_DONATE (the
        # (z, n) buffer-aliasing contract rides every lru key) — both
        # latched ONCE at the plan derivation site (common/plan.ftrl_plan, the
        # ENV-KEY-FOLD checked site).  The resume signature derives from
        # the same plan, content-identical to the historical dict
        # (conditional chained-mode keys included), so every
        # pre-existing snapshot keeps its exact signature and stays
        # resumable.
        from ....common import compileledger
        from ....common import plan as planlib
        fplan = planlib.ftrl_plan(
            mesh=mesh, alpha=alpha, beta=beta, l1=l1, l2=l2, dim=dim,
            dim_pad=dim_pad, update_mode=update_mode,
            staleness=staleness, chunk_size=chunk_size,
            has_intercept=bool(has_icpt), warm_fp=_warm_fp)
        ck_signature = planlib.ftrl_checkpoint_signature(fplan)
        kern = fplan.get("ALINK_TPU_FTRL_KERNEL")
        compileledger.subsystem_start("ftrl")
        allow_fb = [True]    # cleared once the state commits to std layout
        sparse_step = [None]                # built lazily (sparse input only)
        don = fplan.get("ALINK_TPU_DONATE")

        def _step_lookup(factory, args, label, **extra):
            # lru lookup through the compile ledger: cache_info() miss
            # deltas classify the call; the factory and its key tuple
            # are untouched (byte-identical lru behavior, ledger on or
            # off)
            return compileledger.lru_call(
                "ftrl.step", factory, args,
                kwargs={k: v for k, v in extra.items()},
                plan=fplan.extend(("factory", label)),
                site="FtrlTrainStreamOp.link_from", subsystem="ftrl")

        _dense, weights_fn = _step_lookup(
            _ftrl_step_factory, (mesh, alpha, beta, l1, l2), "dense",
            donate=don)
        if batch_mode:
            _dense = _step_lookup(
                _ftrl_dense_batch_step_factory,
                (mesh, alpha, beta, l1, l2), "dense_batch", donate=don)
        # staleness mode: dense rows keep the strict per-sample scan (a
        # REFINEMENT of <=K staleness; dense scans are matvec-bound, not
        # gather-bound, so the chunked kernel buys nothing there)
        dense_step = [_dense]

        _prev_w = [None]   # last emitted snapshot's weights (drift base)

        def snapshot(z_host: np.ndarray, n_host: np.ndarray,
                     fb_S: Optional[int] = None,
                     batch: Optional[int] = None) -> MTable:
            import jax
            # ONE batched host fetch per emission boundary: device_get
            # starts every shard's copy async and blocks once (np.asarray
            # on the sharded weights waits for each shard in turn).
            # weights_fn reads the LIVE state and never donates, so
            # (z, n) survive for the next micro-batch.
            w_full = np.asarray(jax.device_get(weights_fn(z_host, n_host)))
            hbm_snapshot("ftrl.snapshot")
            if mon_on and batch is not None:
                # weight drift vs the PREVIOUS emitted snapshot — the
                # 'model silently walked away' detector. Reuses the host
                # weight fetch the snapshot already pays; layout changes
                # (fb -> std demotion) reset the base instead of
                # reporting a phantom jump
                prev = _prev_w[0]
                if prev is not None and prev.shape == w_full.shape:
                    # denominator includes the NEW norm: an l1-regularized
                    # cold start commonly emits an all-zero first snapshot,
                    # and norm/1e-12 would flag a healthy warm-up as
                    # ~1e12 'drift' (growth from zero caps at 1.0)
                    denom = max(float(np.linalg.norm(prev)),
                                float(np.linalg.norm(w_full)), 1e-12)
                    monitor.record("ftrl.weight_drift", int(batch),
                                   float(np.linalg.norm(w_full - prev))
                                   / denom)
                _prev_w[0] = w_full.copy()
            if fb_S is None:
                w = w_full[:dim]
            elif has_icpt:
                # fb layout: [intercept field (S slots, only slot 0 used)]
                # then the original field-major feature space
                w = np.concatenate([w_full[:1], w_full[fb_S:fb_S + dim - 1]])
            else:
                w = w_full[:dim]
            m = LinearModelData(
                model_name="FTRL", linear_model_type=LinearModelType.LR,
                has_intercept=init.has_intercept, vector_col=init.vector_col,
                feature_names=init.feature_names, vector_size=init.vector_size,
                coef=w, label_values=list(init.label_values),
                label_type=init.label_type)
            return LinearModelDataConverter(init.label_type).save_model(m)

        # ship batches in the dtype the device will execute in: with x64
        # off, jax casts f64 inputs to f32 at the boundary anyway, so f64
        # payloads just double the host->device transfer bytes
        import jax as _jax
        ship_dt = np.float64 if _jax.config.jax_enable_x64 else np.float32

        def labels(mt: MTable, b: int, batch_size: int) -> np.ndarray:
            raw = mt.col(label_col)
            pos = str(init.label_values[0])
            y = np.zeros(batch_size, ship_dt)
            r = np.asarray(raw[:b])
            if r.dtype != object and r.dtype.kind != "S":
                # numpy str() formatting matches str(v) per scalar
                # (bytes do NOT: astype("U") decodes b'1' to '1' while
                # str(b'1') is "b'1'" — keep bytes on the exact path)
                y[:b] = (r.astype("U") == pos)
            else:
                y[:b] = [1.0 if str(v) == pos else 0.0 for v in r]
            return y

        def encode(mt: MTable, batch_size: int, width: int):
            """("dense", X, y) or ("sparse", idx, val, y, width).

            Sparse input NEVER densifies (VERDICT round-1: the dense
            (batch, 65536) encode was ~0.5 GB per 1k-row Criteo batch);
            it stays a padded (batch, width) COO block, intercept as an
            explicit (0, 1.0) entry per real row.
            """
            design = extract_design(mt, feature_cols, vector_col,
                                    ship_dt,
                                    vector_size=init.vector_size or None)
            b = mt.num_rows
            if design["kind"] == "dense":
                Xf = design["X"]
                X = np.zeros((batch_size, dim_pad), ship_dt)
                if has_icpt:
                    X[:b, 0] = 1.0
                    X[:b, 1:1 + Xf.shape[1]] = Xf
                else:
                    X[:b, :Xf.shape[1]] = Xf
                return ("dense", X, labels(mt, b, batch_size))
            idx0, val0 = design["idx"], design["val"]
            hi = int(idx0.max()) if idx0.size else -1
            if hi + (1 if has_icpt else 0) >= dim:
                raise IndexError(
                    f"sparse feature index {hi} out of range for the "
                    f"warm-start model (dim {dim}); the dense path fails "
                    f"loudly on the same input")
            if batch_mode and allow_fb[0]:
                # field-aware-hashed rows route to the one-hot MXU program
                # (random gather/scatter is the TPU bottleneck of both
                # element-addressed modes — see _ftrl_fb_batch_step_factory)
                from ....ops.fieldblock import FieldBlockMeta, detect_fieldblock
                fbd = detect_fieldblock(idx0, val0,
                                        dim - (1 if has_icpt else 0))
                if fbd is not None:
                    fb_local, fb_val, meta0 = fbd
                    F_aug = meta0.num_fields + (1 if has_icpt else 0)
                    if F_aug % n_dev == 0:
                        # int16 indices when the field-local range fits:
                        # half the host->device bytes (widened on device)
                        idt = (np.int16 if meta0.field_size
                               <= np.iinfo(np.int16).max else np.int32)
                        fbi = np.zeros((batch_size, F_aug), idt)
                        c0 = 1 if has_icpt else 0
                        fbi[:b, c0:] = fb_local
                        meta = FieldBlockMeta(F_aug, meta0.field_size)
                        if fb_val is None and b == batch_size:
                            # full batch of pure one-hot rows: value is
                            # implicitly 1.0 — ship NO value tensor (the
                            # full-batch condition matters: padding rows
                            # rely on val == 0 to be no-ops)
                            return ("fb", fbi, None,
                                    labels(mt, b, batch_size), meta)
                        fbv = np.zeros((batch_size, F_aug), ship_dt)
                        if has_icpt:
                            fbv[:b, 0] = 1.0   # intercept field, local 0
                        fbv[:b, c0:] = (1.0 if fb_val is None else fb_val)
                        return ("fb", fbi, fbv,
                                labels(mt, b, batch_size), meta)
            if has_icpt:
                idx0 = np.concatenate(
                    [np.zeros((b, 1), idx0.dtype), idx0 + 1], axis=1)
                val0 = np.concatenate(
                    [np.ones((b, 1), val0.dtype), val0], axis=1)
            w0 = idx0.shape[1]
            width = max(width, -(-w0 // 8) * 8)   # grow in steps of 8
            idx = np.zeros((batch_size, width), np.int32)
            val = np.zeros((batch_size, width), ship_dt)
            idx[:b, :w0] = idx0
            val[:b, :w0] = val0
            return ("sparse", idx, val, labels(mt, b, batch_size), width)

        def gen():
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ....io.sharding import state_sharding

            # declarative state placement: (z, n) feature-sharded across
            # the mesh via the partition rules (io/sharding.py) — one
            # choke point instead of per-site NamedSharding literals
            def state_put(z_arr, n_arr):
                with trace_span("ftrl.state_ship", cat="stream", coarse=True,
                                args={"bytes": int(z_arr.nbytes
                                                   + n_arr.nbytes)}):
                    sh = state_sharding(mesh, ftrl_state_rules(),
                                        {"z": z_arr, "n": n_arr})
                    return (jax.device_put(z_arr, sh["z"]),
                            jax.device_put(n_arr, sh["n"]))
            scale = beta / alpha + l2   # z = -w*(beta/alpha + l2) at n=0:
            # the warm start encodes the initial weights into z

            def alloc(layout, fb_S=None):
                if layout == "fb":
                    dim_state = ((dim - 1 if has_icpt else dim) +
                                 (fb_S if has_icpt else 0))
                else:
                    dim_state = dim_pad
                with trace_span("ftrl.state_alloc", cat="stream",
                                coarse=True) as sp:
                    z0 = np.zeros(dim_state)
                    coef = np.asarray(init.coef)
                    if layout == "fb" and has_icpt:
                        z0[0] = -coef[0] * scale
                        z0[fb_S:fb_S + dim - 1] = -coef[1:] * scale
                    else:
                        z0[:dim] = -coef * scale
                    n0 = np.zeros(dim_state)
                    sp.set(bytes=int(z0.nbytes + n0.nbytes))
                return state_put(z0, n0)

            def fb_to_std_state(z_fb, n_fb):
                """Exact fb -> std state translation: the fb layout is
                [intercept field (slot 0 only)] + the original field-major
                feature space, so dropping the intercept field's unused
                slots loses nothing."""
                zh, nh = np.asarray(z_fb), np.asarray(n_fb)
                z0 = np.zeros(dim_pad)
                n0 = np.zeros(dim_pad)
                if has_icpt:
                    z0[0], n0[0] = zh[0], nh[0]
                    z0[1:dim] = zh[fb_S:fb_S + dim - 1]
                    n0[1:dim] = nh[fb_S:fb_S + dim - 1]
                else:
                    z0[:dim] = zh[:dim]
                    n0[:dim] = nh[:dim]
                return state_put(z0, n0)

            rep_shard = NamedSharding(mesh, P())

            def put_replicated(enc):
                """Move the encoded batch onto the device FROM the
                prefetch thread: the host->device transfer overlaps the
                consumer's step dispatches instead of serializing with
                them."""
                if jax.process_count() > 1:
                    return enc     # multihost: let the jit place inputs
                if enc[0] == "fb":
                    _, fbi, fbv, y, meta = enc
                    return ("fb", jax.device_put(fbi, rep_shard),
                            None if fbv is None else
                            jax.device_put(fbv, rep_shard),
                            jax.device_put(y, rep_shard), meta)
                if enc[0] == "dense":
                    _, X, y = enc
                    return ("dense", jax.device_put(X, rep_shard),
                            jax.device_put(y, rep_shard))
                _, idx, val, y, width = enc
                return ("sparse", jax.device_put(idx, rep_shard),
                        jax.device_put(val, rep_shard),
                        jax.device_put(y, rep_shard), width)

            # -- crash-restart resume (common/checkpoint.py) --------------
            # The newest valid snapshot carries the committed (z, n) state
            # plus the count of micro-batches folded into it; the replayed
            # input stream's committed prefix is skipped below (before
            # encode, so recovery pays no wasted hashing/transfer).
            resume_skip = 0
            _restored = None
            if ck_dir and ck_resume:
                _restored = load_latest_validated(ck_dir, ck_signature,
                                                  scope="ftrl",
                                                  what="FTRL program")
                if _restored is not None:
                    resume_skip = int(_restored[1]["batches_done"])

            def raw_batches():
                """Serial upstream leg: arrival order, the resume skip
                and the batch-size latch happen HERE, before the
                (possibly multi-worker) encode pool — they are inherently
                sequential decisions."""
                batch_size = None
                seen = 0
                for t, mt in data_op.timed_batches():
                    if mt.num_rows == 0:
                        continue
                    if batch_size is None:
                        # batch_size is taken from the FIRST batch even
                        # when resuming, so the padded batch geometry —
                        # and with it the recovered trajectory — matches
                        # the uninterrupted run's exactly
                        batch_size = max(1, mt.num_rows)
                    seen += 1
                    if seen <= resume_skip:
                        continue   # committed before the crash
                    yield (t, mt, batch_size, seen)

            # COO pad width, shared across encode workers. Monotone
            # (grows in steps of 8); with ALINK_TPU_STREAM_WORKERS > 1 a
            # worker may read a stale width — the cost is an extra padded
            # shape (a recompile), never a wrong result: padding columns
            # carry val == 0 and are algebraic no-ops in every kernel.
            # The update is locked: an unlocked read-modify-write race
            # could SHRINK the width (late small writer), breaking the
            # monotone invariant and churning recompiles
            import threading
            width_cell = [8]
            width_lock = threading.Lock()

            def encode_task(item):
                """Parse/hash/pad + host->device ship of ONE micro-batch:
                the unit the prefetch pool runs ahead of the device —
                encode+transfer of batch t+1 (or t+k with k workers)
                overlaps the device running batch t (VERDICT r2 #4;
                Flink's pipelined operators,
                FtrlTrainStreamOp.java:120-135)."""
                t, mt, batch_size, seen = item
                # ``seen`` is the consumer's ``b_done + 1`` for this
                # micro-batch: one number on the spans of both threads
                tag = {"batch": seen, "rows": mt.num_rows}
                with trace_span("ftrl.encode", cat="stream", args=tag):
                    enc = encode(mt, max(batch_size, mt.num_rows),
                                 width_cell[0])
                if enc[0] == "sparse":
                    with width_lock:
                        width_cell[0] = max(width_cell[0], enc[4])
                with trace_span("ftrl.ship", cat="stream", args=tag):
                    shipped = put_replicated(enc)
                return (t, mt, shipped, batch_size)

            from ..prefetch import prefetch_map

            # NOTE: dispatch is asynchronous — the host enqueues step
            # t+1 while the device runs step t, and only a host fetch
            # makes it wait. The drain therefore fetches nothing per
            # batch (a per-batch fetch would stall the host on the
            # device every step and drain the queue that hides dispatch
            # and encode time); results are fetched at snapshot and
            # checkpoint boundaries only.
            z = n = None
            layout = None                # "std" | "fb"
            fb_S = None
            fb_meta = None
            next_emit = None
            b_done = 0                   # micro-batches committed to state
            idx_last = None              # the last sparse micro-batch, shipped
            if _restored is not None:
                _payload, _meta = _restored
                layout = _meta["layout"]
                b_done = resume_skip
                # next_emit is NOT restored: it re-derives from the first
                # replayed batch's event time (the None branch below), so
                # a restart may change time_interval freely and never
                # re-emits for the committed prefix
                if layout == "fb":
                    from ....ops.fieldblock import FieldBlockMeta
                    fb_S = int(_meta["fb_S"])
                    fb_meta = FieldBlockMeta(int(_meta["fb_num_fields"]),
                                             int(_meta["fb_field_size"]))
                else:
                    allow_fb[0] = False
                z, n = state_put(_payload["z"], _payload["n"])

            def save_state():
                # ONE batched host fetch of (z, n) per checkpoint
                # boundary (jax.device_get; the former per-array
                # np.asarray paid two blocking transfers) — the fetch
                # waits for the in-flight batches, which is exactly the
                # durability point: everything before the snapshot is
                # committed, everything after replays on restart
                meta = {"signature": ck_signature, "layout": layout,
                        "batches_done": b_done, "next_emit": next_emit}
                if layout == "fb":
                    meta["fb_S"] = int(fb_S)
                    meta["fb_num_fields"] = int(fb_meta.num_fields)
                    meta["fb_field_size"] = int(fb_meta.field_size)
                with trace_span("ftrl.checkpoint", cat="stream",
                                args={"batch": b_done}):
                    zh, nh = jax.device_get([z, n])
                    hbm_snapshot("ftrl.checkpoint")
                    save_checkpoint(
                        ck_dir, b_done,
                        {"z": np.asarray(zh), "n": np.asarray(nh)},
                        meta=meta, scope="ftrl", keep_last=ck_keep)
                if mon_on:
                    # the snapshot fetch just synced the device queue, so
                    # the pending pv scalars are free to read now; a
                    # watchdog abort here leaves the snapshot on disk
                    flush_pv()
            # -- per-micro-batch health monitoring (common/health.py) -----
            # pv stats are DEVICE scalars queued here and fetched in bulk
            # at snapshot/checkpoint boundaries (plus a cap, so an
            # emission-less drain cannot queue unboundedly) — per-batch
            # host fetches would stall the asynchronous dispatch pipeline
            pv_pending: List[tuple] = []

            def flush_pv():
                if not pv_pending:
                    if mon_on:
                        monitor.evaluate()
                    return
                import jax
                # ONE batched fetch of every queued scalar: device_get
                # starts all host copies async and blocks once — per-item
                # np.asarray would wait for hundreds of copies in turn
                fetched = jax.device_get(
                    [(ll, ok, nf) for _, _, ll, ok, nf in pv_pending])
                for (bi, rows, *_), (ll, ok, nf) in zip(pv_pending, fetched):
                    rows = max(int(rows), 1)
                    monitor.record("ftrl.pv_logloss", bi,
                                   float(ll) / rows)
                    monitor.record("ftrl.pv_accuracy", bi,
                                   float(ok) / rows)
                    monitor.record("nonfinite.margin", bi, float(nf))
                pv_pending.clear()
                # may raise HealthAlertError (monitor raise_on=...): the
                # watchdog abort propagates out of the drain, AFTER any
                # checkpoint this boundary published
                monitor.evaluate()

            # telemetry is per-micro-batch — resolved once per drain
            mx = metrics_enabled()
            reg = get_registry() if mx else None
            m_lbl = {"op": "FtrlTrainStreamOp", "mode": update_mode}

            def mark_workset(span):
                """The boundary's micro-batch on its ``ftrl.snapshot``
                span, if that span is a recorded one (a hook inside it
                may start or stop a profiler session, so ask the span):
                ``entries`` folded and ``slots``, the distinct coordinates
                among them, counted on the shipped ``idx`` once the
                snapshot has waited for the device."""
                if idx_last is not None and isinstance(span, Span):
                    span.set(entries=int(idx_last.size),
                             slots=_distinct(idx_last))

            def device_emit(t_ev, batch) -> bool:
                """Device-to-device emission: hand the registered
                consumer (set_device_snapshot_consumer) the LIVE device
                weights — ``weights_fn`` reads (z, n) without donating —
                with ZERO host traffic; the host model-table snapshot
                and its device_get are skipped when the consumer takes
                the hand-off. Reads gen's current (z, n, fb_S) at call
                time (late-bound closure)."""
                hook = self._device_snapshot_hook
                if hook is None or z is None:
                    return False
                with trace_span("ftrl.snapshot", cat="stream",
                                args={"batch": batch, "to": "device"}) as sp:
                    consumed = bool(hook(weights_fn(z, n),
                                         {"fb_S": fb_S, "dim": dim,
                                          "has_intercept": bool(has_icpt),
                                          "batch": batch,
                                          "event_time": t_ev}))
                    mark_workset(sp)
                if consumed:
                    hbm_snapshot("ftrl.snapshot")
                    if mx:
                        reg.inc("alink_ftrl_device_snapshots_total", 1)
                return consumed

            def run_step(step, *args):
                # per-micro-batch collective accounting (the programs
                # are jit-cached; see _step_manifest). The execution is
                # wrapped in a throwaway collector so a compile-time
                # trace doesn't ALSO record directly — the replay is
                # the single source of truth for this call.
                # The span is the time the step call held the consumer
                # thread: host cost, plus the runtime's back-pressure
                # once its in-flight limit is reached (device work is
                # async; it materializes at the snapshot fetch)
                with trace_span("ftrl.dispatch", cat="stream",
                                args={"batch": b_done + 1}):
                    if mx:
                        from ....engine.communication import (
                            collecting, record_manifest)
                        record_manifest(_step_manifest(step, args))
                        with collecting([]):
                            return step(*args)
                    return step(*args)
            # ordered pool: workers=1 (default) is byte-for-byte the old
            # single-prefetch-thread drain; ALINK_TPU_STREAM_WORKERS=N
            # parallelizes the host encode N-wide with order preserved
            pace = self._batch_hook
            for t, mt, enc, batch_size in prefetch_map(raw_batches(),
                                                       encode_task,
                                                       name="ftrl.encode"):
              t0 = time.perf_counter()
              if pace is not None:
                  # pacing gate (online DAG): may block until the
                  # scoring leg has consumed the pre-batch model state
                  pace("pre", b_done + 1, t)
              if next_emit is None:
                  next_emit = (np.floor(t / interval) + 1) * interval
              if (layout == "fb" and (
                      enc[0] != "fb" or
                      enc[4].num_fields != fb_meta.num_fields or
                      enc[4].field_size != fb_meta.field_size)) or (
                      layout == "std" and enc[0] == "fb"):
                  # the first batch's detection was coincidental (or the
                  # row shape changed): demote the state to the generic
                  # layout — an exact translation — and stay there.
                  # (Also covers up-to-`depth` in-flight batches the
                  # prefetch thread encoded as fb before seeing the
                  # demotion flag flip.)
                  if layout == "fb":
                      # (the fb step is looked up from its lru cache per
                      # batch, so nothing to invalidate here)
                      z, n = fb_to_std_state(z, n)
                  layout, fb_S, fb_meta = "std", None, None
                  allow_fb[0] = False
                  enc = encode(mt, max(batch_size, mt.num_rows), 8)
              if enc[0] == "fb":
                  _, fbi, fbv, y, meta = enc
                  if layout is None:
                      layout, fb_S = "fb", meta.field_size
                      fb_meta = meta
                      z, n = alloc(layout, fb_S)
                  # the lru-cached factory is re-looked-up per batch:
                  # full one-hot batches run the val-less program (no
                  # value tensor shipped), partial/weighted ones the
                  # val-carrying twin
                  step = compileledger.lru_call(
                      "ftrl.step", _ftrl_fb_batch_step_factory,
                      (mesh, meta, alpha, beta, l1, l2, fbv is not None),
                      kwargs={"donate": don},
                      plan=fplan.extend(("factory", "fb_batch"),
                                        ("fb_meta", meta),
                                        ("with_val", fbv is not None)),
                      site="FtrlTrainStreamOp.link_from",
                      subsystem="ftrl")
                  if fbv is None:
                      z, n, mg = run_step(step, fbi, y, z, n)
                  else:
                      z, n, mg = run_step(step, fbi, fbv, y, z, n)
              elif enc[0] == "dense":
                  if layout is None:
                      layout = "std"
                      allow_fb[0] = False
                      z, n = alloc(layout)
                  _, X, y = enc
                  z, n, mg = run_step(dense_step[0], X, y, z, n)
              else:
                  if layout is None:
                      layout = "std"
                      allow_fb[0] = False
                      z, n = alloc(layout)
                  _, idx, val, y, width = enc
                  idx_last = idx
                  if sparse_step[0] is None:
                      if batch_mode:
                          sparse_step[0] = _step_lookup(
                              _ftrl_sparse_batch_step_factory,
                              (mesh, alpha, beta, l1, l2),
                              "sparse_batch", donate=don)
                      elif update_mode == "staleness":
                          sparse_step[0] = _step_lookup(
                              _ftrl_sparse_staleness_step_factory,
                              (mesh, alpha, beta, l1, l2, staleness),
                              "sparse_staleness", donate=don, kernel=kern)
                      elif update_mode == "chained":
                          # strict semantics through the chained-
                          # correction chunk kernel; dense rows keep the
                          # per-sample scan (matvec-bound, not
                          # gather-bound — chunking buys nothing there)
                          sparse_step[0] = _step_lookup(
                              _ftrl_sparse_chained_step_factory,
                              (mesh, alpha, beta, l1, l2, chunk_size),
                              "sparse_chained", donate=don, kernel=kern)
                      else:
                          sparse_step[0] = _step_lookup(
                              _ftrl_sparse_step_factory,
                              (mesh, alpha, beta, l1, l2),
                              "sparse", donate=don, kernel=kern)
                  z, n, mg = run_step(sparse_step[0], idx, val, y, z, n)
              if mon_on:
                  # progressive validation on the device scalars; real
                  # rows only (padding rows would score as margin-0
                  # coin flips — the reducer masks them by row count).
                  # Host fetch deferred to flush_pv.
                  b = mt.num_rows
                  ll, ok, nf = _pv_stats_fn()(mg, y, b)
                  pv_pending.append((b_done + 1, b, ll, ok, nf))
                  if len(pv_pending) >= 512:
                      flush_pv()
              # retroactive span (generator body; see stream/core.py on
              # why an open span must not cross a yield), so in-memory
              # only: encode overlap happens in the prefetch thread, so
              # this span reads as the consumer-side cost of one
              # micro-batch after the item arrived (no get_wait in it)
              trace_complete("ftrl.batch", time.perf_counter() - t0,
                             cat="stream",
                             args={"mode": update_mode, "rows": mt.num_rows,
                                   "batch": b_done + 1})
              if mx:
                  reg.inc("alink_ftrl_rows_total", mt.num_rows, m_lbl)
                  reg.inc("alink_stream_batches_total", 1,
                          {"op": "FtrlTrainStreamOp"})
                  reg.inc("alink_stream_rows_total", mt.num_rows,
                          {"op": "FtrlTrainStreamOp"})
              if t + 1e-12 >= next_emit:
                  if device_emit(t, b_done + 1):
                      if mon_on:
                          flush_pv()
                  else:
                      # fault site (ISSUE 14): kill/error fail the
                      # emission BEFORE the snapshot fetch; corrupt
                      # mangles the EMITTED table (the serving feeder's
                      # poisoned-snapshot path) without touching state
                      _poison = maybe_crash("feeder.snapshot")
                      with trace_span("ftrl.snapshot", cat="stream",
                                      args={"batch": b_done + 1,
                                            "to": "host"}) as sp:
                          snap = snapshot(z, n, fb_S, batch=b_done + 1)
                          mark_workset(sp)
                      if _poison:
                          snap = _corrupt_snapshot_table(snap)
                      if mon_on:
                          flush_pv()  # pv + drift evaluated per emission
                      yield (t, snap)
                  if mx:
                      reg.inc("alink_ftrl_snapshots_total", 1)
                  while next_emit <= t + 1e-12:
                      next_emit += interval
              b_done += 1
              if pace is not None:
                  # committed: the state update AND any snapshot
                  # emission (swap) this batch triggered are done
                  pace("post", b_done, t)
              # the injected-preemption point sits BEFORE the periodic
              # save: a crash at batch k genuinely loses the work since
              # the last snapshot, which is what the kill-and-resume
              # parity test re-executes
              maybe_crash("ftrl.batch", b_done)
              if ck_dir and ck_every and b_done % ck_every == 0:
                  save_state()
            if ck_dir and ck_every and z is not None \
                    and b_done > resume_skip and b_done % ck_every != 0:
                # end-of-stream snapshot so a restart of a COMPLETED drain
                # resumes instead of retraining the tail
                save_state()
            if z is None:
                # empty stream: emit the warm-start model, as the eager
                # allocation used to
                layout = "std"
                z, n = alloc(layout)
            if mx:
                reg.inc("alink_ftrl_snapshots_total", 1)
            if device_emit(next_emit if next_emit is not None else interval,
                           b_done if b_done > 0 else None):
                if mon_on:
                    flush_pv()
            else:
                _poison = maybe_crash("feeder.snapshot")
                with trace_span("ftrl.snapshot", cat="stream",
                                args={"batch": b_done, "to": "host",
                                      "final": True}) as sp:
                    snap = snapshot(z, n, fb_S,
                                    batch=b_done if b_done > 0 else None)
                    mark_workset(sp)
                if _poison:
                    snap = _corrupt_snapshot_table(snap)
                if mon_on:
                    flush_pv()
                yield (next_emit if next_emit is not None else interval,
                       snap)

        self._stream_fn = gen
        return self


class FtrlPredictStreamOp(StreamOperator, HasPredictionCol, HasPredictionDetailCol,
                          HasReservedCols, HasVectorCol):
    """Score a data stream with a hot-reloading model stream.

    reference: FtrlPredictStreamOp.java:62-110 — ``CollectModel`` assembles
    complete models from the model stream and swaps the LinearModelMapper
    live. Here the model stream and data stream merge in event-time order;
    each complete model snapshot replaces the mapper for all later data.
    """

    def __init__(self, initial_model: Optional[BatchOperator] = None,
                 params: Optional[Params] = None, **kwargs):
        super().__init__(params, **kwargs)
        self._initial_model = initial_model

    def link_from(self, model_op: StreamOperator,
                  data_op: StreamOperator) -> "FtrlPredictStreamOp":
        self._schema = None  # resolved once the first mapper loads

        def make_mapper(model_table: MTable, data_schema: TableSchema):
            mapper = LinearModelMapper(model_table.schema, data_schema, self.params)
            mapper.load_model(model_table)
            return mapper

        def gen():
            mapper = None
            latest_model = None
            last_model_t = None
            mx = metrics_enabled()
            reg = get_registry() if mx else None
            lbl = {"op": "FtrlPredictStreamOp"}
            for t, which, mt in merge_timed(model_op.timed_batches(),
                                            data_op.timed_batches()):
                if which == 0:     # model stream: hot swap
                    latest_model = mt
                    last_model_t = t
                    mapper = None  # rebuild lazily against the data schema
                    continue
                if mapper is None:
                    model = latest_model
                    if model is None:
                        if self._initial_model is None:
                            continue  # no model yet: drop (reference buffers)
                        model = self._initial_model.get_output_table()
                    else:
                        # an actual hot swap (not the warm-start fallback)
                        if mx:
                            reg.inc("alink_ftrl_model_reloads_total", 1, lbl)
                        trace_instant("ftrl.model_reload", cat="stream",
                                      args={"model_time": last_model_t,
                                            "data_time": t})
                    mapper = make_mapper(model, mt.schema)
                    self._schema = mapper.get_output_schema()
                if mx:
                    if last_model_t is not None:
                        # event-time staleness of the serving model at this
                        # data batch (the hot-reload lag the reference's
                        # CollectModel swap hides)
                        reg.set_gauge("alink_ftrl_model_staleness_seconds",
                                      float(t - last_model_t), lbl)
                    reg.inc("alink_stream_batches_total", 1, lbl)
                    reg.inc("alink_stream_rows_total", mt.num_rows, lbl)
                yield (t, mapper.map_table(mt))

        self._stream_fn = gen
        return self
