"""CompiledPredictor — per-model jitted serving programs, shape-bucketed.

The reference applies a model per row through ``ModelMapperAdapter.map``
(common/mapper/ModelMapperAdapter.java:42-45); the mappers here are
batched but HOST-side numpy. Serving traffic needs the score kernel on
the device without paying one XLA compile per request size, so:

* a :class:`ServingKernel` (built by the mapper, ``Mapper.
  serving_kernel()``) splits model application into ``encode`` (host:
  rows -> padded arrays), ``device_fn`` (pure jittable scoring) and
  ``decode`` (host: device scores -> output table, the mapper's own
  label/detail logic);
* the predictor compiles ``device_fn`` once per **(model signature,
  encoding kind, shape bucket)** — request batches pad with zero rows to
  the smallest covering bucket from ``ALINK_TPU_SERVE_BUCKETS``, so a
  handful of programs cover arbitrary request sizes and every program
  is reused across requests AND across hot-swapped models of the same
  geometry (weights are *arguments*, never baked into the trace);
* padding rows are numerical no-ops: per-row scoring is row-independent,
  so the real rows of a padded batch are bitwise-identical to the same
  rows served unpadded (tests/test_serving.py pins it).

Hot model swap is double-buffered: :meth:`CompiledPredictor.swap_model`
builds the new model version — mapper load, kernel extraction,
``device_put`` of the weights — entirely in the *standby* slot on the
caller's thread, then flips the active-slot reference atomically.  A
dispatch in flight keeps its own reference to the version it started
with, so no request ever sees a torn model and a swap never blocks the
serving loop.

Cache-key discipline: the predictor resolves ONE :class:`~alink_tpu.
serving.plan.ServingPlan` at construction (kernel signature x bucket
set x sharded mode x mesh fingerprint) and every program-cache key
derives from ``plan.program_key(kind, bucket, shapes)`` — everything
that can change a compiled program is IN the plan or the per-dispatch
dimensions (the mesh fingerprint covers sharded-vs-single-device AND
the device set), so the ``ALINK_TPU_SERVE_*`` flags are declared
key-neutral in ``common/flags.py`` and alink-lint's ENV-KEY-FOLD rule
checks this module as a factory root. The fleet registry
(``serving/fleet.py``) groups same-geometry tenants on the same plan's
``geometry_key()``.

Multi-chip serving (ISSUE 11) lives in :mod:`alink_tpu.serving.sharded`:
``sharded=True`` compiles the bucket programs under the session mesh's
partition rules and places model arrays by their kernel-declared rules;
``ensure_replicas`` pins per-replica single-device placements for the
server's replica fan-out.
"""

from __future__ import annotations

import threading
import time
import warnings
from dataclasses import dataclass, replace
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..common import aotcache, compileledger, reqtrace
from ..common.plan import serving_event_plan
from ..common.faults import maybe_crash
from ..common.metrics import get_registry, metrics_enabled
from ..common.mtable import MTable
from ..common.tracing import trace_span
from .plan import ServingPlan
from .sharded import (SERVE_LANES, mesh_fingerprint,
                      serve_sharded_enabled, serving_mesh)

DEFAULT_BUCKETS = (1, 8, 32, 128, 512)

# -- fallback observability (ISSUE 11 satellite) ----------------------------
# The host-mapper fallback used to be SILENT: a mapper without a
# serving kernel (or a predictor that cannot satisfy a sharding
# request) just quietly served off-device and the fleet-scale numbers
# looked mysteriously flat. Every fallback now records a once-per-
# (mapper, reason) RuntimeWarning plus a labelled counter — the shared
# ``common.metrics.record_fallback_once`` machinery (the tuning sweep's
# fallback contract rides the same helper).


def record_serve_fallback(mapper_name: str, reason: str,
                          detail: str = "") -> None:
    """Record one serving-tier fallback: ``alink_serve_fallback_total
    {mapper=, reason=}`` always, plus ONE RuntimeWarning per
    (mapper, reason) pair per process.

    ``reason`` must be a SMALL ENUM of stable strings — it is a metric
    label, and data-dependent text (exception messages carry request
    widths etc.) would mint a new time series per distinct value.
    Request-specific context goes in ``detail``, which reaches only the
    warning text."""
    from ..common.metrics import record_fallback_once
    record_fallback_once(
        "serve", "alink_serve_fallback_total",
        {"mapper": mapper_name, "reason": reason},
        f"serving falls back to the host mapper path for {mapper_name}: "
        f"{reason}{' (' + detail + ')' if detail else ''} (recorded as "
        f"alink_serve_fallback_total{{mapper={mapper_name!r},"
        f"reason={reason!r}}}; this warning fires once per "
        f"mapper+reason)")


def _reset_fallback_warnings() -> None:
    """Test hook: re-arm the once-per-(mapper, reason) warnings."""
    from ..common.metrics import reset_fallback_warnings
    reset_fallback_warnings("serve")


def serve_compiled_enabled() -> bool:
    """``ALINK_TPU_SERVE_COMPILED``: route the stream predict twins
    (ModelMapStreamOp) through the compiled serving path. Default off —
    the flag-off path runs the exact pre-serving host mapper code."""
    from ..common.flags import flag_value
    return flag_value("ALINK_TPU_SERVE_COMPILED", False)


def serve_buckets(default: Sequence[int] = DEFAULT_BUCKETS) -> Tuple[int, ...]:
    """``ALINK_TPU_SERVE_BUCKETS``: the shape-bucket set, sorted unique
    positive ints (comma-separated). The registry parser normalizes;
    this accessor returns the tuple call sites key programs on."""
    from ..common.flags import flag_value
    raw = flag_value("ALINK_TPU_SERVE_BUCKETS", "")
    if not raw:
        return tuple(default)
    return _parse_buckets(raw) or tuple(default)


def serve_window_s() -> float:
    """``ALINK_TPU_SERVE_WINDOW_MS`` (batching latency budget) in
    seconds."""
    from ..common.flags import flag_value
    return float(flag_value("ALINK_TPU_SERVE_WINDOW_MS", 2.0)) / 1e3


def serve_min_fill() -> int:
    """``ALINK_TPU_SERVE_MIN_FILL``: the micro-batcher's fill target —
    batches below it are held up to the window for stragglers. The
    default of 1 keeps pure adaptive dispatch."""
    from ..common.flags import flag_value
    return int(flag_value("ALINK_TPU_SERVE_MIN_FILL", 1))


def serve_queue_depth() -> int:
    """``ALINK_TPU_SERVE_QUEUE``: admission-control bound of the request
    channel (requests beyond it block the submitter — backpressure)."""
    from ..common.flags import flag_value
    return int(flag_value("ALINK_TPU_SERVE_QUEUE", 1024))


def serve_swap_mode() -> str:
    """``ALINK_TPU_SERVE_SWAP``: ``double`` (default — standby slot
    prepared off the serving loop, atomic flip) or ``sync`` (the flip
    additionally blocks until the standby weights are device-resident;
    debugging aid, serving loop still never blocks)."""
    from ..common.flags import flag_value
    return str(flag_value("ALINK_TPU_SERVE_SWAP", "double"))


def _parse_buckets(raw: str) -> Tuple[int, ...]:
    out = []
    for part in str(raw).split(","):
        part = part.strip()
        if not part:
            continue
        out.append(int(part))
    return tuple(sorted({b for b in out if b > 0}))


@dataclass
class ServingKernel:
    """One model's compiled-serving contract (built by the mapper).

    ``signature``     — hashable PROGRAM identity: geometry/dtype/kind of
                        the model, everything that shapes the traced
                        computation EXCEPT the weight values. Two model
                        versions with equal signatures share compiled
                        programs (the hot-swap fast path).
    ``model_arrays``  — the weights, a tuple of host arrays; the
                        predictor ``device_put``s them once per model
                        version and passes them as program arguments.
    ``encode(mt, bucket)`` -> ``(kind, arrays)`` — host encode of a
                        request table, padded with zero rows to
                        ``bucket``; ``kind`` discriminates encodings
                        (dense vs sparse) of the same model.
    ``device_fns[kind](model_arrays, *arrays)`` — pure jittable scoring;
                        outputs are arrays whose leading axis is rows.
    ``decode(outputs, mt)`` — host decode of the REAL-row slice of the
                        program outputs into the mapper's output table
                        (the mapper's own label/detail logic).
    """
    signature: Tuple
    model_arrays: Tuple[np.ndarray, ...]
    encode: Callable[[MTable, int], Tuple[str, Tuple[np.ndarray, ...]]]
    device_fns: Dict[str, Callable]
    decode: Callable[[Tuple[np.ndarray, ...], MTable], MTable]
    # -- multi-chip serving (optional; ISSUE 11) ------------------------
    # ``model_names``       — one name per model array, matched against
    #                         ``partition_rules`` (the io/sharding.py
    #                         match_partition_rules idiom) to place the
    #                         model on the serving mesh;
    # ``partition_rules``   — ((regex, PartitionSpec), ...); unmatched
    #                         names replicate (default P());
    # ``input_specs(kind)`` — PartitionSpecs of the ENCODED request
    #                         arrays under the mesh;
    # ``make_sharded_fns(mesh)`` -> {kind: fn} — mesh-sharded twins of
    #                         ``device_fns`` (shard_map + manifest
    #                         collectives). ``None`` = the kernel cannot
    #                         shard; a sharding request falls back
    #                         (recorded) to single-device programs.
    model_names: Tuple[str, ...] = ()
    partition_rules: Tuple = ()
    input_specs: Optional[Callable[[str], Tuple]] = None
    make_sharded_fns: Optional[Callable] = None
    # -- multi-tenant fleet coalescing (optional; ISSUE 17) -------------
    # ``make_fleet_fns()`` -> {kind: fn(stacked_model_arrays, lane,
    #                          *arrays)} — lane-stacked twins of
    #                         ``device_fns``: each model array gains a
    #                         leading tenant-lane axis and every request
    #                         row gathers its own tenant's weights via
    #                         the int32 ``lane`` vector (the tuning
    #                         ``(points,)`` carry-lane idiom). Per-row
    #                         arithmetic and reduction order must be
    #                         IDENTICAL to ``device_fns`` so cross-
    #                         tenant coalescing is a bitwise no-op.
    #                         ``None`` = the kernel cannot coalesce; the
    #                         fleet serves its tenants through per-
    #                         tenant dispatch (fallback recorded).
    make_fleet_fns: Optional[Callable] = None


def _merge_parts(parts):
    """Concatenate chunk outputs column-wise in ONE pass — a pairwise
    ``concat_rows`` fold re-copies the growing table per part, O(p^2)
    data movement on the routed-stream hot path."""
    first = parts[0]
    cols = {}
    for nm in first.col_names:
        arrs = []
        for p in parts:
            c = p.col(nm)
            if getattr(c, "__mtable_column__", False):
                c = c.materialize()
            arrs.append(c)
        if any(a.dtype == object for a in arrs):
            out = np.empty(sum(a.shape[0] for a in arrs), object)
            off = 0
            for a in arrs:
                out[off:off + a.shape[0]] = a
                off += a.shape[0]
        else:
            out = np.concatenate(arrs)
        cols[nm] = out
    return MTable(cols, first.schema)


class _ModelVersion:
    """One immutable model slot: kernel + device-resident weights.

    ``shardings`` (multi-chip serving) places each model array with its
    matched ``NamedSharding`` — host arrays ``device_put`` STRAIGHT into
    their mesh placement (no replicated staging copy), and arrays that
    are already device-resident with the right sharding pass through
    without a host round trip (the FTRL in-place swap path).
    ``devices`` (replica dispatch) materializes one placement per
    replica device instead."""

    __slots__ = ("version", "kernel", "mapper", "_placements")

    def __init__(self, version: int, kernel: ServingKernel, mapper=None,
                 shardings: Optional[Tuple] = None,
                 devices: Tuple = (None,)):
        import jax
        self.version = version
        self.kernel = kernel
        self.mapper = mapper
        # the weights land on device HERE — on the swapping thread, not
        # the serving loop (the double-buffer contract)
        if shardings is not None:
            self._placements = (tuple(
                jax.device_put(a, s)
                for a, s in zip(kernel.model_arrays, shardings)),)
        else:
            self._placements = tuple(
                tuple(jax.device_put(a) if d is None
                      else jax.device_put(a, d)
                      for a in kernel.model_arrays)
                for d in devices)

    def arrays_for(self, replica: int = 0) -> Tuple:
        return self._placements[replica % len(self._placements)]

    def block_until_ready(self) -> None:
        """Wait for EVERY placement (all replicas / all shards) — the
        sync-swap contract covers each replica's device copy, not just
        slot 0's."""
        import jax
        jax.block_until_ready([a for p in self._placements for a in p])

    @property
    def device_arrays(self) -> Tuple:
        return self._placements[0]


class CompiledPredictor:
    """Shape-bucketed compiled model application with hot swap.

    ``CompiledPredictor(mapper)`` takes a LOADED ModelMapper that
    implements ``serving_kernel()``; :meth:`for_mapper` returns ``None``
    instead of raising for mappers without a kernel (the stream-twin
    routing falls back to the host path).
    """

    def __init__(self, mapper, buckets: Optional[Sequence[int]] = None,
                 name: str = "serve", sharded: Optional[bool] = None,
                 mesh=None, replica_devices: Optional[Sequence] = None):
        kernel = mapper.serving_kernel()
        if kernel is None:
            raise TypeError(
                f"{type(mapper).__name__} does not provide a serving "
                f"kernel; use CompiledPredictor.for_mapper() to fall "
                f"back to the host mapper path")
        self.name = name
        self._buckets = tuple(sorted({int(b) for b in buckets if int(b) > 0})) \
            if buckets else serve_buckets()
        if not self._buckets:
            raise ValueError("empty bucket set")
        # -- multi-chip resolution (ISSUE 11): sharded bucket programs
        # span the serving mesh; replica dispatch pins per-replica
        # single-device placements. Mutually exclusive by construction
        # (a sharded program already uses every chip).
        self._sharded = serve_sharded_enabled() if sharded is None \
            else bool(sharded)
        self._mesh = None
        if self._sharded:
            if kernel.make_sharded_fns is None:
                record_serve_fallback(type(mapper).__name__,
                                      "no-sharded-kernel")
                self._sharded = False
            else:
                m = mesh if mesh is not None else serving_mesh()
                n = int(m.devices.size)
                if SERVE_LANES % n:
                    record_serve_fallback(
                        type(mapper).__name__, "mesh-indivisible",
                        f"{n} devices vs {SERVE_LANES} lanes")
                    self._sharded = False
                else:
                    self._mesh = m
        self._mesh_fp = mesh_fingerprint(self._mesh)
        if self._sharded and replica_devices:
            raise ValueError("sharded serving programs span the mesh; "
                             "replica_devices does not compose with "
                             "sharded=True")
        self._replica_devices: Tuple = tuple(replica_devices) \
            if replica_devices else (None,)
        # ONE resolved plan (ISSUE 17 / ROADMAP item 1): every program
        # key, the fleet's geometry grouping and the swap signature
        # derive from it instead of re-threading buckets/dtype/fused/
        # sharded/mesh by hand at each site
        self.plan = ServingPlan(signature=kernel.signature,
                                buckets=self._buckets,
                                sharded=self._sharded,
                                mesh_fp=self._mesh_fp)
        # compile-ledger identity (ISSUE 19): one ledger cache per
        # predictor; every miss in _program records an event whose diff
        # names the changed dimension (dtype flip, new bucket, swapped
        # geometry)
        self._ledger_cache = f"serve.{self.name}"
        compileledger.register_cache(self._ledger_cache, "serving")
        compileledger.subsystem_start("serving")
        self._sharded_fns: Dict[Tuple, Dict[str, Callable]] = {}
        self._swap_lock = threading.Lock()
        self._cache_lock = threading.Lock()
        self._programs: Dict[Tuple, Tuple[Callable, Tuple]] = {}
        self._hits = 0
        self._hits_reported = 0
        self._misses = 0
        self._versions = 0
        # slot 0 = active. The standby slot is materialized per swap
        # (a fresh _ModelVersion) and flipped in by ONE reference store,
        # so readers racing a swap see either the old or the new version
        # whole — never a mix.
        self._active = self._make_version(kernel, mapper)

    # ------------------------------------------------------------------
    @classmethod
    def for_mapper(cls, mapper, buckets: Optional[Sequence[int]] = None,
                   name: str = "serve", **kw) -> Optional["CompiledPredictor"]:
        """A predictor, or ``None`` when the mapper has no kernel — and
        the fallback is RECORDED (``alink_serve_fallback_total`` + one
        RuntimeWarning per mapper+reason), never silent."""
        try:
            kernel = mapper.serving_kernel()
            reason, detail = "no-serving-kernel", ""
        except RuntimeError as e:
            kernel = None
            reason, detail = "kernel-error", str(e)
        if kernel is None:
            record_serve_fallback(type(mapper).__name__, reason, detail)
            return None
        return cls(mapper, buckets=buckets, name=name, **kw)

    def _ver_sharded(self, kernel: ServingKernel) -> bool:
        """Does THIS kernel run sharded on this predictor? A hot swap
        can hand a sharded predictor a kernel that cannot shard (e.g. a
        softmax model swapped into a binary slot) — that version serves
        through single-device programs (fallback recorded in
        :meth:`_make_version`) instead of crashing every dispatch."""
        return self._sharded and kernel.make_sharded_fns is not None

    def _model_shardings(self, kernel: ServingKernel) -> Optional[Tuple]:
        """NamedShardings of the model arrays under the partition rules
        (None when unsharded): the ``io/sharding.py`` placement path —
        ``match_partition_rules`` over the kernel's named arrays, every
        unmatched name replicated."""
        if not self._ver_sharded(kernel):
            return None
        from jax.sharding import PartitionSpec as P

        from ..io.sharding import state_sharding
        names = kernel.model_names or tuple(
            f"a{i}" for i in range(len(kernel.model_arrays)))
        named = dict(zip(names, kernel.model_arrays))
        sh = state_sharding(self._mesh, kernel.partition_rules, named,
                            default=P())
        return tuple(sh[n] for n in names)

    def _make_version(self, kernel: ServingKernel, mapper) -> _ModelVersion:
        self._versions += 1
        if self._sharded and kernel.make_sharded_fns is None:
            record_serve_fallback(type(mapper).__name__,
                                  "no-sharded-kernel (swapped model "
                                  "serves single-device)")
        return _ModelVersion(self._versions, kernel, mapper,
                             shardings=self._model_shardings(kernel),
                             devices=self._replica_devices)

    # -- replica dispatch (ISSUE 11) ------------------------------------
    def ensure_replicas(self, devices: Sequence) -> None:
        """Materialize per-replica model placements (one device per
        replica) — called by :class:`~alink_tpu.serving.server.
        PredictServer` before it spawns replica loops. Re-places the
        ACTIVE version; later swaps inherit the device list."""
        devices = tuple(devices)
        if not devices or self._sharded:
            return
        with self._swap_lock:
            if devices == self._replica_devices:
                return
            self._replica_devices = devices
            cur = self._active
            self._active = _ModelVersion(cur.version, cur.kernel,
                                         cur.mapper, devices=devices)

    @property
    def replica_devices(self) -> Tuple:
        return self._replica_devices

    # -- model hot swap -------------------------------------------------
    def swap_model(self, model_table: MTable) -> int:
        """Load ``model_table`` into the standby slot and flip it active.

        Runs entirely on the caller's thread (the model-stream tap):
        mapper construction, ``load_model``, kernel extraction and the
        weight ``device_put`` all happen BEFORE the flip, which is one
        atomic reference store. Returns the new version number.
        Serialized across swappers; never blocks the serving loop."""
        with self._swap_lock:
            t0 = time.perf_counter()
            # fault site: an error-mode fault fails the swap BEFORE the
            # standby build — the active version never flips, so the
            # last good model keeps serving (the feeder-supervision
            # contract this site exists to test)
            maybe_crash("serve.swap")
            with trace_span("serve.swap", cat="serve"):
                base = self._active.mapper
                mapper = type(base)(model_table.schema, base.data_schema,
                                    base.params)
                mapper.load_model(model_table)
                standby = self._make_version(mapper.serving_kernel(), mapper)
                if serve_swap_mode() == "sync":
                    standby.block_until_ready()
                self._active = standby     # the atomic flip
            dt = time.perf_counter() - t0
        # stamp the flip onto every request in flight: a tail exemplar
        # overlapping this swap names it (ISSUE 18)
        reqtrace.annotate_inflight("swap", {"predictor": self.name,
                                            "version": standby.version})
        if metrics_enabled():
            reg = get_registry()
            reg.inc("alink_serve_model_swaps_total", 1,
                    {"predictor": self.name})
            reg.observe("alink_serve_swap_seconds", dt,
                        {"predictor": self.name})
        return standby.version

    def swap_weights(self, model_arrays: Sequence) -> int:
        """Same-geometry in-place weight swap: install ``model_arrays``
        (host or device arrays, matching the ACTIVE kernel's shapes)
        as a new model version WITHOUT reloading a model table.

        This is the no-gather-to-host leg of multi-chip serving: a
        feature-sharded producer (the FTRL trainer's (z, n)-derived
        weights) hands arrays that are already in — or go straight
        into — their mesh placement; ``jax.device_put`` with the
        matched ``NamedSharding`` is a no-op for correctly-placed
        device arrays. The mapper's host-side decode state (labels,
        detail schema) is geometry, not weights, so it carries over.
        The flip is the same atomic reference store as
        :meth:`swap_model`."""
        with self._swap_lock:
            t0 = time.perf_counter()
            maybe_crash("serve.swap")   # same site as swap_model: both
                                        # are the feeder's swap boundary
            with trace_span("serve.swap", cat="serve",
                            args={"mode": "weights"}):
                base = self._active
                arrays = tuple(model_arrays)
                if len(arrays) != len(base.kernel.model_arrays):
                    raise ValueError(
                        f"swap_weights got {len(arrays)} arrays; the "
                        f"active kernel has "
                        f"{len(base.kernel.model_arrays)}")
                for a, old in zip(arrays, base.kernel.model_arrays):
                    if tuple(a.shape) != tuple(old.shape) \
                            or np.dtype(a.dtype) != np.dtype(old.dtype):
                        raise ValueError(
                            f"swap_weights geometry mismatch: "
                            f"{tuple(a.shape)}/{np.dtype(a.dtype)} vs "
                            f"{tuple(old.shape)}/{np.dtype(old.dtype)} — "
                            f"a different geometry must go through "
                            f"swap_model (new signature, new programs)")
                kernel = replace(base.kernel, model_arrays=arrays)
                standby = self._make_version(kernel, base.mapper)
                if serve_swap_mode() == "sync":
                    standby.block_until_ready()
                self._active = standby     # the atomic flip
            dt = time.perf_counter() - t0
        reqtrace.annotate_inflight("swap", {"predictor": self.name,
                                            "version": standby.version,
                                            "mode": "weights"})
        if metrics_enabled():
            reg = get_registry()
            reg.inc("alink_serve_model_swaps_total", 1,
                    {"predictor": self.name})
            reg.observe("alink_serve_swap_seconds", dt,
                        {"predictor": self.name})
        return standby.version

    @property
    def model_version(self) -> int:
        return self._active.version

    @property
    def sharded(self) -> bool:
        return self._sharded

    @property
    def mesh(self):
        return self._mesh

    @property
    def buckets(self) -> Tuple[int, ...]:
        return self._buckets

    @property
    def devices(self) -> Tuple:
        """The devices holding the ACTIVE model's placements — where this
        predictor's programs run (one for the default single-device
        programs, the whole mesh when sharded, one per replica)."""
        devs = {d for placement in self._active._placements
                for a in placement for d in a.devices()}
        return tuple(sorted(devs, key=lambda d: d.id))

    # -- program cache --------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (requests larger than the top bucket are
        served in top-bucket chunks)."""
        for b in self._buckets:
            if n <= b:
                return b
        return self._buckets[-1]

    def _sharded_fn(self, kernel: ServingKernel, kind: str) -> Callable:
        """The mesh-sharded device fn for ``kind`` — built once per
        (kernel signature, mesh) via the kernel's ``make_sharded_fns``
        factory and shared by every bucket program and model version of
        that geometry. Callers hold ``_cache_lock``."""
        fkey = (kernel.signature, self._mesh_fp)
        fns = self._sharded_fns.get(fkey)
        if fns is None:
            fns = self._sharded_fns[fkey] = kernel.make_sharded_fns(
                self._mesh)
        return fns[kind]

    def _place_inputs(self, ver: _ModelVersion, kind: str,
                      arrays: Tuple[np.ndarray, ...], replica: int
                      ) -> Tuple:
        """Encoded request arrays -> device: under sharding each input
        lands with its kernel-declared PartitionSpec (the feature axis
        of the dense design matrix shards alongside the weights); under
        replica dispatch each lands on the replica's device; otherwise
        the arrays pass through and jit commits them (the historical
        single-device path)."""
        if self._ver_sharded(ver.kernel) \
                and ver.kernel.input_specs is not None:
            import jax
            from jax.sharding import NamedSharding
            specs = ver.kernel.input_specs(kind)
            return tuple(jax.device_put(a, NamedSharding(self._mesh, s))
                         for a, s in zip(arrays, specs))
        dev = self._replica_devices[replica % len(self._replica_devices)]
        if dev is not None:
            import jax
            return tuple(jax.device_put(a, dev) for a in arrays)
        return arrays

    def _program(self, ver: _ModelVersion, kind: str, bucket: int,
                 arrays: Tuple, call_args: Tuple
                 ) -> Tuple[Callable, Tuple]:
        """The compiled program for (model signature, kind, bucket,
        mesh) — every dimension that shapes the trace is part of the key
        (leading axes are the bucket itself; dtypes are fixed by the
        kernel signature; the mesh fingerprint covers sharded-vs-single-
        device and the device set), so a cache hit can never serve a
        stale program. The hit path is lock-free (GIL-atomic dict read +
        int bump) — it runs per dispatched batch on the serving loop.

        Returns ``(program, manifest)``: sharded programs additionally
        carry their trace-time collective manifest, captured ONCE via an
        AOT ``lower`` inside :func:`~alink_tpu.engine.communication.
        collecting` and replayed per dispatch by the caller — serving
        traffic shows up in the collective manifest/metrics exactly like
        training traffic."""
        sharded = self._ver_sharded(ver.kernel)
        key = self.plan.program_key(
            kind, bucket, tuple(a.shape[1:] for a in arrays),
            signature=ver.kernel.signature, sharded=sharded)
        entry = self._programs.get(key)
        if entry is not None:
            self._hits += 1
            compileledger.record_hit(self._ledger_cache)
            return entry
        import jax
        _led_t0 = time.perf_counter()
        with self._cache_lock:
            entry = self._programs.get(key)
            if entry is None:
                self._misses += 1
                evplan = serving_event_plan(
                    self.plan, signature=ver.kernel.signature,
                    sharded=sharded, kind=kind, bucket=bucket,
                    trailing=tuple(a.shape[1:] for a in arrays))
                # load-before-compile (ISSUE 20): an exported executable
                # for this exact plan digest installs instead of a fresh
                # trace+compile. Sharded programs stay on the compile
                # path — their trace captures the collective manifest.
                if not sharded and aotcache.active():
                    loaded = aotcache.load(
                        evplan, cache=self._ledger_cache,
                        site="CompiledPredictor._program",
                        subsystem="serving")
                    if loaded is not None:
                        entry = (loaded.fn, ())
                        self._programs[key] = entry
                        if metrics_enabled():
                            get_registry().inc(
                                "alink_serve_program_cache_total", 1,
                                {"result": "disk-hit",
                                 "predictor": self.name})
                        return entry
                if sharded:
                    fn = self._sharded_fn(ver.kernel, kind)
                else:
                    fn = ver.kernel.device_fns[kind]
                prog = jax.jit(fn)
                manifest: Tuple = ()
                if sharded:
                    from ..engine.communication import collecting
                    cap: List = []
                    try:
                        with collecting(cap):
                            prog.lower(ver.arrays_for(0), *call_args)
                    except Exception as e:  # accounting must never
                        cap = []            # break serving — but say so
                        warnings.warn(
                            f"serving collective accounting disabled "
                            f"for program {key[:3]} (AOT lower failed: "
                            f"{e!r})", RuntimeWarning)
                    manifest = tuple(cap)
                entry = (prog, manifest)
                self._programs[key] = entry
                compileledger.record_event(
                    self._ledger_cache, evplan,
                    wall_s=time.perf_counter() - _led_t0,
                    site="CompiledPredictor._program",
                    subsystem="serving")
                if not sharded and aotcache.active():
                    aotcache.store(
                        evplan, prog,
                        (ver.arrays_for(0),) + tuple(call_args),
                        cache=self._ledger_cache,
                        site="CompiledPredictor._program", key=key)
                if metrics_enabled():
                    get_registry().inc("alink_serve_program_cache_total",
                                       1, {"result": "miss",
                                           "predictor": self.name})
            else:
                self._hits += 1
                compileledger.record_hit(self._ledger_cache)
        return entry

    def warm_from_disk(self) -> int:
        """Admission warming (ISSUE 20): install every AOT artifact in
        this predictor's cache directory whose program-cache key, when
        re-derived against THIS predictor's plan, still digests to the
        artifact's plan digest — the bucket x dtype grid of a previous
        process loads before the first request instead of compiling on
        it.  Foreign or drifted artifacts are skipped (a fingerprint
        mismatch refuses loudly inside :func:`aotcache.load`); returns
        how many programs were installed."""
        if not aotcache.active():
            return 0
        import ast
        n = 0
        for _path, header in aotcache.scan(self._ledger_cache):
            try:
                key = ast.literal_eval(header.get("key_repr") or "")
            except Exception:
                continue
            if not isinstance(key, tuple) or len(key) != 7:
                continue
            sig, kind, bucket, trailing, buckets, lanes, mesh_fp = key
            if lanes is not None or mesh_fp is not None:
                continue          # fleet-lane / sharded: not this cache
            if tuple(buckets) != self._buckets:
                continue
            evplan = serving_event_plan(
                self.plan, signature=sig, sharded=False, kind=kind,
                bucket=bucket, trailing=tuple(trailing))
            if evplan.digest() != header.get("plan_digest"):
                continue          # geometry drifted: a plain miss
            # install under the key _program would derive TODAY (the
            # artifact's stored repr is advisory, the derivation is
            # authoritative)
            key = self.plan.program_key(kind, bucket, tuple(trailing),
                                        signature=sig, sharded=False)
            with self._cache_lock:
                if key in self._programs:
                    continue
            loaded = aotcache.load(
                evplan, cache=self._ledger_cache,
                site="CompiledPredictor.warm_from_disk",
                subsystem="serving")
            if loaded is None:
                continue
            with self._cache_lock:
                if key not in self._programs:
                    self._programs[key] = (loaded.fn, ())
                    n += 1
        return n

    def cache_stats(self) -> Dict[str, int]:
        self.flush_metrics()
        with self._cache_lock:
            return {"hits": self._hits, "misses": self._misses,
                    "programs": len(self._programs)}

    def flush_metrics(self) -> None:
        """Push the (lock-free) hit counter delta into the registry —
        per-hit registry updates would tax every dispatched batch, so
        hits batch up and flush at stats/accounting boundaries."""
        if not metrics_enabled():
            return
        with self._cache_lock:
            delta = self._hits - self._hits_reported
            self._hits_reported = self._hits
        if delta > 0:
            get_registry().inc("alink_serve_program_cache_total", delta,
                               {"result": "hit", "predictor": self.name})

    # -- prediction -----------------------------------------------------
    def predict_table(self, data: MTable, replica: int = 0) -> MTable:
        """Serve a whole request table through the bucketed programs.

        Output is bitwise-identical for the real rows no matter which
        bucket (or chunk split) served them — padding rows are zero and
        per-row scoring is row-independent. ``replica`` selects the
        replica-dispatch device placement (0 = default)."""
        n = data.num_rows
        if n == 0:
            return self._active.mapper.map_table(data)
        top = self._buckets[-1]
        if n <= top:
            return self._predict_chunk(data, replica)
        parts = [self._predict_chunk(
                     data.take_rows(np.arange(s, min(s + top, n))), replica)
                 for s in range(0, n, top)]
        return _merge_parts(parts)

    def _predict_chunk(self, data: MTable, replica: int = 0) -> MTable:
        # deterministic fault site (common/faults.py): error = a
        # catchable transient dispatch failure (what trips the serving
        # circuit breaker), delay:MS = latency injection, kill = the
        # loop-supervisor/respawn path. BEFORE encode: a shed/failed
        # dispatch must not have paid any device work
        maybe_crash("serve.dispatch")
        ver = self._active           # one consistent model per dispatch
        n = data.num_rows
        bucket = self.bucket_for(n)
        # one bucket program, by phase: every span of the batch carries
        # the same tag (the first request's trace id inside a server
        # batch scope)
        tag = {"rows": n, "bucket": bucket, "model_version": ver.version}
        trace_id = reqtrace.batch_trace_id()
        if trace_id is not None:
            tag["trace_id"] = trace_id
        with trace_span("serve.batch", cat="serve", args=tag):
            result = self._run_bucket(ver, data, bucket, replica, tag)
        if metrics_enabled():
            reg = get_registry()
            lbl = {"predictor": self.name}
            reg.inc("alink_serve_batches_total", 1, lbl)
            reg.observe("alink_serve_batch_occupancy", n / bucket, lbl)
        return result

    def _run_bucket(self, ver: _ModelVersion, data: MTable, bucket: int,
                    replica: int, tag: dict) -> MTable:
        import jax
        with trace_span("serve.encode", cat="serve", args=tag):
            kind, arrays = ver.kernel.encode(data, bucket)
        with trace_span("serve.place", cat="serve", args=tag):
            placed = self._place_inputs(ver, kind, arrays, replica)
        prog, manifest = self._program(ver, kind, bucket, arrays, placed)
        with trace_span("serve.dispatch", cat="serve", args=tag):
            if manifest:
                from ..engine.communication import (collecting,
                                                    record_manifest)
                record_manifest(manifest)
                # the replayed manifest is the ONLY accounting: should
                # the call retrace (jax version didn't warm the call
                # cache from the AOT lower), its trace-time records land
                # in a discarded sink instead of double-charging the
                # registry — the FTRL drain's collecting([]) idiom
                with collecting([]):
                    out = prog(ver.arrays_for(replica), *placed)
            else:
                out = prog(ver.arrays_for(replica), *placed)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        # request-timeline phase boundaries (ISSUE 18): dispatch work
        # (encode + placement + program launch) ends here; the device
        # wait is the host fetch; decode is the tail. No-ops outside a
        # server batch scope — pure host bookkeeping either way.
        reqtrace.batch_mark("dispatch")
        # ONE batched host fetch, then slice the padding rows off
        with trace_span("serve.fetch", cat="serve", args=tag):
            host = jax.device_get(list(out))
        reqtrace.batch_mark("device")
        with trace_span("serve.decode", cat="serve", args=tag):
            sliced = tuple(np.asarray(a)[:data.num_rows] for a in host)
            result = ver.kernel.decode(sliced, data)
        reqtrace.batch_mark("decode")
        return result

    def predict_row(self, row: Tuple) -> Tuple:
        """LocalPredictor-style single-row serving: the 1-row table trip
        through the bucket-1 program (this is the serial-dispatch
        baseline the micro-batcher is measured against)."""
        one = MTable([row], self._active.mapper.data_schema)
        return self.predict_table(one).row(0)

    # -- parity helpers -------------------------------------------------
    def host_reference(self, data: MTable) -> MTable:
        """The active model applied through the HOST mapper path
        (``map_table``) — the parity baseline of the compiled tier."""
        return self._active.mapper.map_table(data)

    @property
    def output_schema(self):
        return self._active.mapper.get_output_schema()

    @property
    def data_schema(self):
        return self._active.mapper.data_schema
