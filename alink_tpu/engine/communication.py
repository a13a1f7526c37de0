"""Communicate stages — XLA collectives over the device mesh.

The reference implements MPI-style primitives by hand over Flink shuffles:
  - AllReduce: 3-phase scatter(4096-chunk)/reduce/broadcast over two
    ``partitionCustom`` shuffles (communication/AllReduce.java:85-360).
  - broadcast: ``withBroadcastSet`` replication (BaseComQueue.java:337-369).
Here each primitive is ONE XLA collective over the ICI mesh (SURVEY §2.4):
psum / pmax / pmin / all_gather / ppermute. Chunking, routing and reassembly
belong to the compiler.

Telemetry: every communicate stage reports its invocation and logical
payload bytes through :func:`record_collective` **at trace time** (shapes
and dtypes are known on tracers; no host callback enters the compiled
program). The engine installs :func:`collecting` around superstep tracing
to capture a per-superstep manifest it later multiplies by the executed
superstep count; outside a collector the record lands directly in the
process ``MetricsRegistry`` (standalone use of these stages).
"""

from __future__ import annotations

import contextlib
import threading
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from ..common.metrics import get_registry, metrics_enabled
from .context import ComContext

# (collective_kind, buffer_name, logical_bytes_per_invocation) triples
CollectiveRecord = Tuple[str, str, int]

_collector = threading.local()


@contextlib.contextmanager
def collecting(manifest: List[CollectiveRecord]):
    """Route :func:`record_collective` calls on this thread into
    ``manifest`` (the engine's per-superstep trace capture) instead of the
    registry. Nests: the previous sink is restored on exit."""
    prev = getattr(_collector, "manifest", None)
    _collector.manifest = manifest
    try:
        yield manifest
    finally:
        _collector.manifest = prev


def payload_nbytes(value) -> int:
    """Logical payload bytes of a buffer pytree as seen by ONE worker
    (tracer-safe: reads only aval shape/dtype)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(value):
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", None)
        itemsize = np.dtype(dtype).itemsize if dtype is not None else 8
        n = 1
        for d in shape:
            n *= int(d)
        total += n * itemsize
    return total


def record_collective(kind: str, name: str, per_worker_bytes: int,
                      num_workers: int) -> None:
    """Record one collective invocation. ``logical bytes moved`` is the
    payload summed over workers (every worker contributes/receives its
    copy), not the wire traffic of a particular ring schedule."""
    logical = int(per_worker_bytes) * int(num_workers)
    manifest = getattr(_collector, "manifest", None)
    if manifest is not None:
        manifest.append((kind, name, logical))
        return
    if metrics_enabled():
        reg = get_registry()
        lbl = {"collective": kind}
        reg.inc("alink_collective_calls_total", 1, lbl)
        reg.inc("alink_collective_logical_bytes_total", logical, lbl)


def record_manifest(manifest: Sequence[CollectiveRecord],
                    times: int = 1) -> None:
    """Charge a memoized trace-time manifest to the metrics registry.

    Collectives record at TRACE time, so inside a jit-cached program the
    records fire once per COMPILE, not once per call. The engine fixes
    this for comqueue programs by multiplying the per-superstep manifest
    by the executed superstep count; callers that invoke cached programs
    outside the engine (the FTRL drain loop) capture the program's
    manifest once (:func:`collecting` around an AOT ``.lower``) and
    replay it here per invocation, so ``alink_collective_calls_total``
    counts executed micro-batches rather than compiles."""
    if not manifest or not metrics_enabled():
        return
    reg = get_registry()
    for kind, _name, logical in manifest:
        lbl = {"collective": kind}
        reg.inc("alink_collective_calls_total", times, lbl)
        reg.inc("alink_collective_logical_bytes_total",
                int(logical) * int(times), lbl)


# -- manifest-recording raw-collective wrappers -----------------------------
# The collective manifest only saw traffic routed through the stage
# classes below (and ctx.all_reduce_sum); raw ``lax.psum``/... calls in
# operator code ran real inter-chip traffic the accounting and the scaling
# evidence could not see.
# These wrappers are the sanctioned call form outside this module — the
# alink-lint COLLECTIVE-SITE rule rejects raw ``lax`` collectives
# anywhere else. Each wrapper records at TRACE time (once per traced
# call site — a site inside a scan body records once per trace, and the
# engine multiplies per-superstep manifests by the executed superstep
# count; loops that drive jit-cached programs outside the engine replay
# the captured manifest per invocation via record_manifest) and lowers
# to exactly the raw ``lax`` op: zero HLO change. Independent collectives
# of one superstep are left to XLA's combiner, which merges them (a
# compiled Newton superstep holds one all-reduce for its two psums).

def manifest_psum(x, axis_name, *, name: str = "<psum>",
                  num_workers: int = 1):
    """``lax.psum`` + manifest record (kind AllReduce)."""
    record_collective("AllReduce", name, payload_nbytes(x), num_workers)
    return jax.lax.psum(x, axis_name)


def manifest_pmax(x, axis_name, *, name: str = "<pmax>",
                  num_workers: int = 1):
    """``lax.pmax`` + manifest record (kind AllReduce)."""
    record_collective("AllReduce", name, payload_nbytes(x), num_workers)
    return jax.lax.pmax(x, axis_name)


def manifest_pmin(x, axis_name, *, name: str = "<pmin>",
                  num_workers: int = 1):
    """``lax.pmin`` + manifest record (kind AllReduce)."""
    record_collective("AllReduce", name, payload_nbytes(x), num_workers)
    return jax.lax.pmin(x, axis_name)


def manifest_all_gather(x, axis_name, *, axis: int = 0, tiled: bool = False,
                        name: str = "<all_gather>", num_workers: int = 1):
    """``lax.all_gather`` + manifest record (kind AllGather; bytes are
    the pre-gather shard payload × workers, like the AllGather stage)."""
    record_collective("AllGather", name, payload_nbytes(x), num_workers)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def manifest_psum_scatter(x, axis_name, *, scatter_dimension: int = 0,
                          tiled: bool = False,
                          name: str = "<psum_scatter>",
                          num_workers: int = 1):
    """``lax.psum_scatter`` + manifest record (kind ReduceScatter)."""
    record_collective("ReduceScatter", name, payload_nbytes(x), num_workers)
    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=tiled)


class CommunicateFunction:
    """Marker base (reference comqueue/CommunicateFunction.java)."""

    def calc(self, context: ComContext):  # pragma: no cover - interface
        raise NotImplementedError


class AllReduce(CommunicateFunction):
    """All-reduce named carry buffers across workers.

    reference: communication/AllReduce.java:85-120 (SUM/MAX/MIN ops :125-159).
    ``lax.psum`` rides the ICI; the reference's TRANSFER_BUFFER_SIZE=4096
    chunking machinery has no analogue here.
    """

    OPS = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin}

    def __init__(self, *buffer_names: str, op: str = "sum",
                 mean: bool = False):
        if not buffer_names:
            raise ValueError("AllReduce needs at least one buffer name")
        self.buffer_names = buffer_names
        if op.lower() not in self.OPS:
            raise ValueError(f"unsupported allreduce op {op}; use sum/max/min")
        self.op = op.lower()
        if mean and self.op != "sum":
            raise ValueError("mean=True only makes sense with op='sum'")
        self.mean = mean

    def calc(self, context: ComContext):
        wrap = {"sum": manifest_psum, "max": manifest_pmax,
                "min": manifest_pmin}[self.op]
        for name in self.buffer_names:
            v = context.get_obj(name)
            out = wrap(v, ComContext.AXIS, name=name,
                       num_workers=context.num_task)
            if self.mean:
                out = jax.tree_util.tree_map(
                    lambda x: x / context.num_task, out)
            context.put_obj(name, out)


class AllGather(CommunicateFunction):
    """Gather per-worker arrays into a replicated stacked array.

    The ALS "factor all-gather" primitive (SURVEY §2.3 block parallelism);
    result shape: (num_workers, *shard_shape), stored under
    ``<name><suffix>``.
    """

    def __init__(self, *buffer_names: str, suffix: str = "_gathered", axis: int = 0,
                 tiled: bool = False):
        self.buffer_names = buffer_names
        self.suffix = suffix
        self.axis = axis
        self.tiled = tiled

    def calc(self, context: ComContext):
        for name in self.buffer_names:
            v = context.get_obj(name)
            out = manifest_all_gather(v, ComContext.AXIS, axis=self.axis,
                                      tiled=self.tiled, name=name,
                                      num_workers=context.num_task)
            context.put_obj(name + self.suffix, out)


class BroadcastFromWorker0(CommunicateFunction):
    """Replicate worker 0's value of a buffer to all workers.

    reference: the node-0 criterion rebroadcast pattern (BaseComQueue.java:242-304).
    """

    def __init__(self, *buffer_names: str):
        self.buffer_names = buffer_names

    def calc(self, context: ComContext):
        tid = context.task_id
        for name in self.buffer_names:
            v = context.get_obj(name)
            record_collective("BroadcastFromWorker0", name, payload_nbytes(v),
                              context.num_task)

            def bcast(x):
                x = jnp.where(tid == 0, x, jnp.zeros_like(x))
                return jax.lax.psum(x, ComContext.AXIS)

            context.put_obj(name, jax.tree_util.tree_map(bcast, v))


def distributed_info_start(total, task_id, num_tasks):
    """Start offset of ``task_id``'s slice of ``total`` items.

    reference: DefaultDistributedInfo.startPos (io/directreader/) — first
    ``total % n`` workers get one extra item. Traceable arithmetic.
    """
    total = jnp.asarray(total)
    base = total // num_tasks
    rem = total % num_tasks
    return task_id * base + jnp.minimum(task_id, rem)


def distributed_info_count(total, task_id, num_tasks):
    """Length of ``task_id``'s slice (DefaultDistributedInfo.localRowCnt)."""
    total = jnp.asarray(total)
    base = total // num_tasks
    rem = total % num_tasks
    return base + (task_id < rem).astype(total.dtype)
