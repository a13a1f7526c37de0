"""IterativeComQueue — the BSP iterative-compute engine, TPU-native.

Re-design of the reference's ComQueue framework
(common/comqueue/BaseComQueue.java:154-308 ``exec``; IterativeComQueue.java:6):

reference mechanism                      ->  TPU-native mechanism
----------------------------------------     ------------------------------------
Flink IterativeDataSet superstep loop        ``lax.while_loop`` body (one jit)
ComputeFunction.calc(ComContext)             pure stage fn over a carry pytree
AllReduce 3-phase shuffle                    ``lax.psum`` over mesh axis 'd'
partition data cached in TM heap             device-resident sharded arrays
  (SessionSharedObjs.java:157-178)             closed over by the jitted step
withBroadcastSet replication                 replicated (unsharded) arrays
stop-criterion on node 0 + rebroadcast       criterion fn -> ``__stop`` carry bit
  (BaseComQueue.java:242-304)                  (computed on replicated state)
CompleteResultFunction on final state        ``close_with`` host callback

The whole superstep loop — all stages plus collectives — compiles to ONE XLA
program via ``shard_map`` over the session mesh; Flink's per-superstep
scheduling overhead has no analogue. Stage chaining (``optimize()``,
BaseComQueue.java:470-495) is subsumed by XLA fusion.

Contract notes:
  * Partitioned arrays are zero-padded along axis 0 to a multiple of the
    worker count. Algorithms must carry an explicit per-sample weight/mask
    column if padding can perturb them (the reference's Tuple3(weight, ...)
    training format already does this).
  * Stage allocations (reference ``stepNo == 1`` idiom) must happen when
    ``context.is_init_step`` is True; the carry structure is frozen after
    the first superstep.
"""

from __future__ import annotations

import time
import warnings
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ..common.health import health_enabled
from ..common.mlenv import MLEnvironment, MLEnvironmentFactory
from ..common.profiling2 import hbm_snapshot, profile_window
from ..common.tracing import trace_instant, trace_span
from .context import ComContext
from .communication import CommunicateFunction

# Compiled-program cache across exec() calls. Every exec() used to build
# a fresh ``run`` closure, so jax.jit could never hit its own cache and
# every fit paid the full trace+compile (~10-18 s for the optimizer
# programs) even when an identical program had just run. The reference
# pays plan-construction per exec too, but its plan build is cheap
# (BaseComQueue.java:154-308); execution cost is per run. Here the
# expensive artifact is the compiled XLA program, so it is cached keyed
# on (caller program_key, mesh, worker count, max_iter, seed,
# criterion-presence, input-name sets). Shape/dtype polymorphism is
# handled by jax.jit itself underneath each entry.
#
# Caller contract for ``program_key``: the key must determine the stage
# list's STRUCTURE and every Python-level constant the stage closures
# bake into the trace (hyperparameters, dims, loss config). Training
# DATA always flows through partitioned/broadcast inputs, never through
# the key — a cached program re-runs correctly on fresh data.
_PROGRAM_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()
_PROGRAM_CACHE_MAX = 32
_PROGRAM_CACHE_STATS = {"hits": 0, "misses": 0}
# jaxpr text per cached key, populated only under ALINK_VERIFY_PROGRAM_CACHE
_PROGRAM_CACHE_JAXPRS: Dict[tuple, str] = {}
# per-superstep collective manifest per cached key (communication.collecting
# capture, recorded at trace time): {"init": [...], "body": [...]} of
# (kind, buffer, logical_bytes) triples. Kept OUTSIDE the metrics guard so
# a program compiled under ALINK_TPU_METRICS=0 still carries its manifest
# when a later metrics-on exec hits the cache.
_PROGRAM_CACHE_MANIFESTS: Dict[tuple, dict] = {}

# Engine phase wall-clock (prepare inputs / execute+compile / collect).
# Spans mirror into the MetricsRegistry as alink_step_timer_seconds via
# StepTimer itself, so one registry dump carries engine timing too.
from ..common.profiling import StepTimer as _StepTimer

_ENGINE_TIMER = _StepTimer()


def engine_timer():
    """The engine-phase StepTimer (host wall-clock per exec phase)."""
    return _ENGINE_TIMER


def program_cache_stats() -> Dict[str, int]:
    """Cumulative hit/miss counters (observability + tests)."""
    return dict(_PROGRAM_CACHE_STATS)


def donation_enabled() -> bool:
    """``ALINK_TPU_DONATE`` (default ON): donate the chunk-loop carry into
    the compiled ``cont`` chunk program (``jax.jit(donate_argnums=...)``).
    XLA then aliases the carry's input buffers to the output buffers —
    the per-chunk copy-on-entry disappears and the carry's HBM working
    set halves for large models (the reference mutates its shared model
    state in place, SessionSharedObjs; donation is the compiled-loop
    analogue). Read live and folded into the program-cache key, so
    toggling it recompiles instead of serving a structurally different
    cached program.

    Only the ``cont`` program has a carry INPUT to donate: the single
    and first-chunk programs construct the carry inside the trace (the
    init pass), so there is nothing to alias — the flag is a no-op for
    them beyond the cache-key fold. Donation contract for callers: a
    buffer passed into a donated argument is dead after the call
    (``RuntimeError: Array has been deleted`` on reuse) — fetch anything
    you still need BEFORE re-entering the program
    (docs/performance.md)."""
    from ..common.metrics import env_flag
    return env_flag("ALINK_TPU_DONATE", default=True)


def clear_program_cache() -> None:
    _PROGRAM_CACHE.clear()
    _PROGRAM_CACHE_JAXPRS.clear()
    _PROGRAM_CACHE_MANIFESTS.clear()


def _program_label(program_key) -> str:
    """Human-readable, bounded-cardinality label for per-program metrics.
    Callers conventionally lead their ``set_program_key`` tuple with a
    short algorithm string (``("qn", ...)``, ``("als", ...)``); fall back
    to a digest when the key has no such prefix."""
    if isinstance(program_key, (tuple, list)) and program_key \
            and isinstance(program_key[0], str):
        return program_key[0]
    import hashlib
    return hashlib.blake2b(repr(program_key).encode(),
                           digest_size=6).hexdigest()


def _name_program(fn: Callable, program_key, role: str = "") -> Callable:
    """Give a program body the name XLA will carry: a queue with a
    ``program_key`` compiles to ``jit_<label><role>`` (``jit_kmeans_lloyd``,
    ``jit_qn_cont``) in place of a ``jit_run`` every queue would share, so
    a device trace tells the programs apart. Uncached queues keep the
    body's own name."""
    if program_key is not None:
        label = "".join(c if c.isalnum() or c == "_" else "_"
                        for c in _program_label(program_key))
        fn.__name__ = fn.__qualname__ = label + role
    return fn


class _AotMeshCall:
    """Dispatch a deserialized engine program (ISSUE 20).  An exported
    multi-device module must be called in a context with the device
    count it was built for, so each positional argument's leaves are
    placed onto the exec mesh first — ``shard`` along the worker axis
    (parts, stacked carries), ``repl`` replicated (broadcast state,
    loop limits).  Single-device meshes skip placement; ``lower``
    delegates so the static-cost probe keeps working."""

    __slots__ = ("_fn", "_mesh", "_specs")

    def __init__(self, fn: Callable, mesh, specs: Sequence[str]):
        self._fn = fn
        self._mesh = mesh
        self._specs = tuple(specs)

    def __call__(self, *args):
        import jax
        mesh = self._mesh
        if mesh is not None and int(np.prod(mesh.devices.shape)) > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as _P
            sh = {"shard": NamedSharding(mesh, _P("d")),
                  "repl": NamedSharding(mesh, _P())}
            args = tuple(
                jax.tree_util.tree_map(
                    lambda x, _s=sh[spec]: jax.device_put(x, _s), a)
                for a, spec in zip(args, self._specs))
        return self._fn(*args)

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)


def freeze_config(v):
    """Hashable token of a config object for ``set_program_key``. Captures
    every Python constant stage closures bake into a trace (loss type,
    dims, regularization, field metadata). Arrays hash by content; objects
    by public attrs, recursively."""
    import dataclasses
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return v
    if isinstance(v, (tuple, list)):
        return tuple(freeze_config(x) for x in v)
    if isinstance(v, dict):
        # sort by (type, repr) so mixed-type keys (int and str) still
        # produce a stable key instead of raising from sorted()
        return tuple(sorted(((k, freeze_config(x)) for k, x in v.items()),
                            key=lambda kv: (type(kv[0]).__name__, repr(kv[0]))))
    if isinstance(v, np.ndarray) or (hasattr(v, "shape") and hasattr(v, "dtype")):
        a = np.asarray(v)
        raw = a.tobytes()
        if len(raw) > 512:
            # digest large arrays: raw bytes in the key would copy MBs per
            # fit and pin them in the LRU
            import hashlib
            raw = hashlib.blake2b(raw, digest_size=16).digest()
        return ("nd", a.shape, str(a.dtype), raw)
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return (type(v).__name__, freeze_config(dataclasses.asdict(v)))
    if hasattr(v, "__dict__"):
        # PUBLIC attrs only: a config object must not hide trace-relevant
        # state in underscore attrs (the set_program_key contract)
        return (type(v).__name__,
                tuple(sorted((k, freeze_config(x)) for k, x in vars(v).items()
                             if not k.startswith("_"))))
    # no safe generic fallback: default repr() embeds the memory address,
    # which would make the key never match (a silent permanent cache miss
    # churning the LRU) — force the caller to pass something freezable
    raise TypeError(f"freeze_config: cannot build a stable key from "
                    f"{type(v).__name__!r}; pass scalars, arrays, "
                    f"dataclasses, or objects with public __dict__ attrs")


def _freeze_closure_value(v, depth):
    """Best-effort hashable token of one closure-cell value for the
    program-cache structural guard. Unlike freeze_config this must be
    TOTAL (never raise) and must NOT fetch device arrays to host — so it
    recurses itself instead of delegating containers to freeze_config."""
    import dataclasses
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return v
    if isinstance(v, np.ndarray):  # host memory: content hash is cheap
        if v.nbytes > 512:
            import hashlib
            # hash the buffer in place — tobytes() would copy the whole
            # array on every exec() including cache hits
            buf = v.data if v.flags.c_contiguous else \
                np.ascontiguousarray(v).data
            raw = hashlib.blake2b(buf, digest_size=16).digest()
        else:
            raw = v.tobytes()
        return ("nd", v.shape, str(v.dtype), raw)
    if isinstance(v, type):  # a CLASS in a cell (e.g. a slotted type whose
        # 'shape' attr is a member_descriptor, not a value). getattr with
        # defaults: pybind11-defined classes (old jaxlib's PmapFunction)
        # can lack __module__/__qualname__, and this function must be TOTAL
        return ("type", getattr(v, "__module__", "?"),
                getattr(v, "__qualname__", getattr(v, "__name__", repr(v))))
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        # jax.Array: data belongs in partitioned/broadcast inputs by
        # contract; hashing its CONTENT would round-trip device memory.
        # Shape/dtype suffices to catch structural drift.
        try:
            return ("devarray", tuple(v.shape), str(v.dtype))
        except TypeError:
            return ("opaque", type(v).__module__, type(v).__qualname__)
    # containers decrement depth too: a cyclic container (cfg['self'] =
    # cfg) must degrade to an opaque token, not overflow the stack
    if isinstance(v, (tuple, list)):
        if depth <= 0:
            return ("opaque", type(v).__name__, len(v))
        return tuple(_freeze_closure_value(x, depth - 1) for x in v)
    if isinstance(v, dict):
        if depth <= 0:
            return ("opaque", "dict", len(v))
        return tuple(sorted(
            ((repr(k), _freeze_closure_value(x, depth - 1))
             for k, x in v.items())))
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        if depth <= 0:
            return ("opaque", type(v).__name__)
        return (type(v).__name__, tuple(
            (f.name, _freeze_closure_value(getattr(v, f.name), depth - 1))
            for f in dataclasses.fields(v)))
    if callable(v) and depth > 0:
        return _callable_digest(v, depth - 1)
    if hasattr(v, "__dict__") and depth > 0:  # depth bounds cyclic graphs
        return (type(v).__name__, tuple(sorted(
            (k, _freeze_closure_value(x, depth - 1))
            for k, x in vars(v).items() if not k.startswith("_"))))
    return ("opaque", getattr(type(v), "__module__", "?"),
            getattr(type(v), "__qualname__", type(v).__name__))


# dedup keys for the devarray-in-closure warning below: one warning per
# (stage, cell) pair — per-exec repeats would be noise, but a SECOND
# offending stage (or a second cell of the same stage) is a distinct
# bug and must not be muted by the first (the historical once-per-
# process flag did exactly that). Runtime twin of the alink-lint
# TRACED-CAPTURE rule, so the two diagnostics agree on name and unit.
_DEVARRAY_CELL_WARNED: set = set()


def _contains_devarray(v, depth=3) -> bool:
    """True when a closure-cell value holds a jax.Array (directly or
    nested in a shallow container). The check is a POSITIVE isinstance
    against jax.Array — duck-typing on shape/dtype would also trip on
    numpy scalars, pandas Series, or ShapeDtypeStructs, and a false
    positive here both misleads the user and burns the once-per-process
    warning before a genuine device-array capture can use it."""
    if v is None or isinstance(v, (bool, int, float, str, bytes, type,
                                   np.ndarray, np.generic)):
        return False
    try:
        import jax
        if isinstance(v, jax.Array):
            return True
    except (ImportError, AttributeError):  # pragma: no cover - old jax
        if isinstance(getattr(v, "shape", None), tuple) \
                and hasattr(v, "dtype") \
                and type(v).__module__.split(".")[0] in ("jax", "jaxlib"):
            return True
    if depth <= 0:
        return False
    if isinstance(v, (tuple, list)):
        return any(_contains_devarray(x, depth - 1) for x in v)
    if isinstance(v, dict):
        return any(_contains_devarray(x, depth - 1) for x in v.values())
    return False


def _warn_devarray_cell(fn_name: str, cell_name: str, key=None) -> None:
    """The structural cache guard tokenizes device arrays by shape/dtype
    ONLY (hashing content would round-trip device memory per exec), so a
    stage closure holding a jax.Array whose CONTENT changes between
    execs would silently re-run the stale cached program — the content
    is baked into the trace as a constant (ADVICE round 5,
    comqueue.py:144). Warn once per (stage, cell): data belongs in
    partitioned/broadcast inputs, not closures. This is the runtime
    twin of the static TRACED-CAPTURE rule (``python -m tools.lint``) —
    same rule name, same per-(stage, cell) unit. ``key`` carries the
    caller's dedup identity (module + qualname): two DISTINCT defs that
    merely share a nested name like ``step`` are two distinct bugs and
    must both warn."""
    key = key or (fn_name, cell_name)
    if key in _DEVARRAY_CELL_WARNED:
        return
    _DEVARRAY_CELL_WARNED.add(key)
    warnings.warn(
        f"TRACED-CAPTURE: comqueue stage {fn_name!r}: closure variable "
        f"{cell_name!r} "
        f"captures a device array (jax.Array). The program cache "
        f"tokenizes device arrays by shape/dtype only, so if its CONTENT "
        f"changes between execs a stale compiled program would be reused "
        f"silently. Route data through init_with_partitioned_data/"
        f"init_with_broadcast_data instead, or set "
        f"ALINK_VERIFY_PROGRAM_CACHE=1 to catch drift by jaxpr "
        f"comparison.", RuntimeWarning, stacklevel=3)


def _callable_digest(fn, depth=4):
    """Structural token of a stage callable: bytecode + constants + frozen
    closure cells (+ bound-object public attrs for methods). Appended to
    the program-cache key so a caller whose ``program_key`` under-specifies
    a baked constant gets a cache MISS instead of a silently stale
    program (advisor r4, comqueue.py:57)."""
    import functools
    if isinstance(fn, functools.partial):
        return ("partial", _callable_digest(fn.func, depth),
                _freeze_closure_value(fn.args, depth),
                _freeze_closure_value(fn.keywords, depth))
    if hasattr(fn, "__wrapped__"):  # functools.wraps / jit-style wrappers
        return ("wrapped", _callable_digest(fn.__wrapped__, depth))
    if hasattr(fn, "__func__"):  # bound method: include the receiver's config
        self_tok = _freeze_closure_value(getattr(fn, "__self__", None), depth)
        return ("bound", _callable_digest(fn.__func__, depth), self_tok)
    code = getattr(fn, "__code__", None)
    if code is None:
        call = getattr(type(fn), "__call__", None)
        inner = getattr(call, "__code__", None)
        if inner is None:
            return ("opaque_callable", type(fn).__module__, type(fn).__qualname__)
        return ("callable_obj", _callable_digest(call.__get__(fn), depth))
    import hashlib
    h = hashlib.blake2b(code.co_code, digest_size=12)
    for c in code.co_consts:
        if isinstance(c, (bool, int, float, str, bytes, type(None))):
            h.update(repr(c).encode())
        elif hasattr(c, "co_code"):  # nested lambda/comprehension bodies
            h.update(c.co_code)
        else:
            h.update(type(c).__name__.encode())
    defaults = ()
    if fn.__defaults__ or getattr(fn, "__kwdefaults__", None):
        # default-arg values bake into the trace exactly like closure
        # cells do (the `def stage(ctx, scale=scale)` idiom); they must
        # ride in the digest or two structurally-different programs
        # would collide
        defaults = (_freeze_closure_value(fn.__defaults__, depth),
                    _freeze_closure_value(fn.__kwdefaults__, depth))
    cells = []
    if fn.__closure__:
        for name, cell in zip(code.co_freevars, fn.__closure__):
            try:
                v = cell.cell_contents
            except ValueError:
                # unbound cell (a closure var referenced before assignment,
                # e.g. a self-referential recursive fn being built): the
                # digest must be TOTAL, so degrade to an opaque token
                cells.append((name, ("opaque", "unbound_cell")))
                continue
            if _contains_devarray(v):
                _warn_devarray_cell(
                    code.co_name, name,
                    key=(getattr(fn, "__module__", ""),
                         getattr(fn, "__qualname__", code.co_name), name))
            cells.append((name, _freeze_closure_value(v, depth)))
    return (code.co_name, h.hexdigest(), tuple(cells), defaults)


# stage object -> digest. Digesting re-hashes every closure cell (data
# arrays included), so repeated exec() on the same queue object paid the
# full walk per cache HIT. Keyed on the stage OBJECT: a stage's closure
# contents are frozen at construction by the set_program_key contract
# (data flows through partitioned/broadcast inputs, never closures), so
# object identity implies digest identity.
_STAGE_DIGEST_MEMO: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _memo_digest(obj, compute):
    from ..common.metrics import env_flag
    if env_flag("ALINK_VERIFY_PROGRAM_CACHE", default=False):
        # debug mode bypasses the memo: a stage whose closure contents
        # mutated after its first exec (violating the identity contract
        # above) re-hashes fresh, so the jaxpr-compare guard downstream
        # sees the drifted key instead of a stale memo hiding it
        return compute()
    try:
        d = _STAGE_DIGEST_MEMO.get(obj)
    except TypeError:       # not weakref-able: compute every time
        return compute()
    if d is None:
        d = compute()
        try:
            _STAGE_DIGEST_MEMO[obj] = d
        except TypeError:
            pass
    return d


def _stages_digest(stages, criterion) -> tuple:
    items = []
    for s in stages:
        items.append(_memo_digest(s, lambda s=s: _callable_digest(
            s.fn if isinstance(s, _FnStage) else s.calc)))
    if criterion is not None:
        items.append(_memo_digest(criterion,
                                  lambda: _callable_digest(criterion)))
    return tuple(items)


def lazy_jit(fn, static_argnums=()):
    """Persistent jit wrapper for a module-level function. Calling
    ``jax.jit(fn)(...)`` inline creates a fresh wrapper — and a fresh
    trace — on every call; this memoizes the wrapper per (fn, statics)."""
    return _lazy_jit_cached(fn, tuple(static_argnums))


def _lazy_jit_cached(fn, static_argnums):
    key = (fn, static_argnums)
    got = _LAZY_JIT.get(key)
    if got is None:
        import jax
        got = _LAZY_JIT[key] = jax.jit(fn, static_argnums=static_argnums)
    return got


_LAZY_JIT: Dict[tuple, Callable] = {}


def _first_shards(tree):
    """Worker 0's slice of every stacked leaf (one program a carry shape)."""
    import jax
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _lookup_programs(cache: str, key: Optional[tuple], plan, build: Callable,
                     manifest: dict, *, site: Optional[str], mesh, mx: bool,
                     aot: Sequence[tuple] = (),
                     fresh_jaxpr: Optional[Callable[[], str]] = None):
    """The program(s) for one plan: from the in-memory LRU, else from
    the AOT store, else built — the one place that decides, and that
    keeps each outcome's books (LRU order, eviction, the compile ledger,
    the hit/miss counter, the metric, the trace instant).

    ``cache`` names the ledger's cache (``engine.program``: the single
    whole-loop program; ``engine.chunked``: the ``first`` + ``cont``
    pair); ``key`` is the LRU key (None: an uncached queue, built and
    nothing else); ``plan`` is what the ledger records; ``build`` returns
    the tuple of jitted programs. ``aot`` holds one ``(artifact plan,
    placement specs)`` a program where the AOT store may be read
    (load-before-compile, ISSUE 20): every artifact loads or none
    installs — half a pair would force a recompile anyway — so the
    ledger's disk-hit is written only on full success. ``fresh_jaxpr``
    is given under ``ALINK_VERIFY_PROGRAM_CACHE`` alone.

    Returns ``(programs, status, manifest)``; ``manifest`` is the dict
    the programs' superstep closures write into — on a hit the one
    stored at miss time, not this exec's."""
    if key is None:
        return build(), "uncached", manifest
    from ..common import aotcache, compileledger
    compileledger.register_cache(cache, "engine", _PROGRAM_CACHE_MAX)
    programs = _PROGRAM_CACHE.get(key)
    if programs is not None:
        status = "hit"
        _PROGRAM_CACHE_STATS["hits"] += 1
        _PROGRAM_CACHE.move_to_end(key)
        compileledger.record_hit(cache)
        manifest = _PROGRAM_CACHE_MANIFESTS.setdefault(key, manifest)
    else:
        loaded = []
        for aot_plan, _specs in aot:
            got = aotcache.load(aot_plan, cache=cache, site=site,
                                subsystem="engine", record=False)
            if got is None:
                break
            loaded.append(got)
        if aot and len(loaded) == len(aot):
            status = "disk-hit"
            _PROGRAM_CACHE_STATS["hits"] += 1
            programs = tuple(_AotMeshCall(got.fn, mesh, specs)
                             for got, (_plan, specs) in zip(loaded, aot))
            # deserialized programs never trace, so the per-superstep
            # collective manifest rides the artifact header instead of
            # the closure
            header = loaded[0].manifest(None)
            if isinstance(header, dict) and header:
                manifest.update(header)
            for got in loaded:
                compileledger.record_disk_hit(cache, plan, wall_s=got.wall_s,
                                              site=site, subsystem="engine")
        else:
            status = "miss"
            _PROGRAM_CACHE_STATS["misses"] += 1
            programs = build()
            # ledger event at insert time; jit is lazy, so the
            # trace+compile wall is only observable around the first
            # dispatch (the plain path's note_wall attaches it)
            compileledger.record_event(cache, plan, site=site,
                                       subsystem="engine")
        _PROGRAM_CACHE[key] = programs
        _PROGRAM_CACHE_MANIFESTS[key] = manifest
        # an evicted program takes its side tables with it
        while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_MAX:
            old_key, _ = _PROGRAM_CACHE.popitem(last=False)
            _PROGRAM_CACHE_JAXPRS.pop(old_key, None)
            _PROGRAM_CACHE_MANIFESTS.pop(old_key, None)
            compileledger.record_eviction(
                "engine.chunked" if old_key and old_key[0] == "__ckpt__"
                else "engine.program")
    if fresh_jaxpr is not None:
        # debug mode: the baseline jaxpr is recorded AT COMPILE TIME, so
        # the very first post-compile drift is caught on the next hit;
        # every hit re-traces and compares — catches any constant the
        # structural guard cannot see
        fresh = fresh_jaxpr()
        if status == "miss":
            _PROGRAM_CACHE_JAXPRS[key] = fresh
        elif fresh != _PROGRAM_CACHE_JAXPRS.setdefault(key, fresh):
            raise RuntimeError(
                "ALINK_VERIFY_PROGRAM_CACHE: cached program for key "
                f"{key[0]!r} no longer matches a fresh trace — a stage "
                "closure baked state the program_key does not cover")
    if mx:
        from ..common.metrics import get_registry
        get_registry().inc("alink_comqueue_program_cache_total", 1,
                           {"result": status})
    trace_instant("comqueue.program_cache", cat="engine",
                  args={"result": status})
    return programs, status, manifest


class _StepBodies:
    """The traced bodies of one exec: the superstep over the queue's
    stages and the three ``shard_map`` programs built on it — the whole
    loop (``mapped``), and the checkpoint-mode pair whose loop bound is
    a TRACED scalar, so one compiled pair serves every chunk while the
    host persists the carry between the calls (engine/recovery.py):
    ``first_chunk`` runs the init pass, ``cont_chunk`` re-enters with a
    (possibly disk-round-tripped) stacked carry.

    ``manifest`` takes every traced pass's collectives (trace-time; see
    communication.collecting), keyed by the traced input signature:
    jax.jit keeps a shape-keyed trace cache underneath each compiled
    entry, so one cached program can hold several traces with different
    payload sizes. A dict, so that the superstep closure — which may be
    retraced later through a CACHED program — always writes into the
    object stored with that program."""

    def __init__(self, queue: "IterativeComQueue", env: MLEnvironment,
                 probes_on: bool, donate: bool):
        self.stages = list(queue._stages)
        self.criterion = queue._criterion
        self.program_key = queue._program_key
        self.max_iter = int(queue.max_iter)
        self.seed = int(queue.seed)
        self.nw = env.num_workers
        self.mesh = env.mesh
        self.probes_on = probes_on
        self.donate = donate
        self.manifest: Dict[tuple, Dict[str, list]] = {}

    @staticmethod
    def _static_sig(static) -> tuple:
        """Trace signature: per-worker shapes/dtypes of every input
        leaf, computed identically on host inputs (given the P('d')
        leading-axis split) and on the tracers inside superstep."""
        import jax
        items = []
        for k in sorted(static):
            for leaf in jax.tree_util.tree_leaves(static[k]):
                items.append((k, tuple(map(int, leaf.shape)),
                              str(leaf.dtype)))
        return tuple(items)

    def superstep(self, carry, static, init_pass):
        import jax.numpy as jnp

        from ..common.profiling import log_superstep, named_stage
        from .communication import collecting
        ctx = ComContext(carry, static, self.nw, init_pass,
                         max_iter=self.max_iter, probes_on=self.probes_on)
        # capture this pass's collectives at TRACE time (shapes are on
        # the tracers; nothing is added to the compiled program).
        # clear() first: a retrace through a cached program must
        # OVERWRITE the stored per-pass manifest, not append to it.
        per = self.manifest.setdefault(self._static_sig(static),
                                       {"init": [], "body": []})
        entries = per["init" if init_pass else "body"]
        entries.clear()
        with collecting(entries):
            for s in self.stages:
                # name each compiled stage (the reference .name()s every
                # dataflow stage for the Flink UI,
                # BaseComQueue.java:172-195)
                with named_stage(getattr(s, "__name__", type(s).__name__)):
                    s.calc(ctx)
            if self.criterion is not None:
                stop = self.criterion(ctx)
                ctx.put_obj("__stop", jnp.asarray(stop, bool).reshape(()))
            else:
                ctx.put_obj("__stop", jnp.asarray(False))
        log_superstep(ctx.step_no, task=ctx.task_id,
                      stop=ctx.get_obj("__stop"))
        return ctx.carry

    def _loop(self, static, limit=None):
        """``(body, cond)`` of the superstep loop; ``limit`` is a chunk's
        traced upper bound."""
        import jax.numpy as jnp
        max_iter = self.max_iter

        def body(c):
            c = dict(c)
            c["__step"] = c["__step"] + 1
            return self.superstep(c, static, init_pass=False)

        if limit is None:
            def cond(c):
                return (c["__step"] < max_iter) & jnp.logical_not(c["__stop"])
        else:
            def cond(c):
                return ((c["__step"] < limit) & (c["__step"] < max_iter)
                        & jnp.logical_not(c["__stop"]))
        return body, cond

    def _from_init(self, static, limit=None):
        """The init pass, then the loop (to ``limit``, in a chunk)."""
        import jax
        import jax.numpy as jnp
        carry = {"__step": jnp.asarray(1, jnp.int32),
                 "__key": jax.random.PRNGKey(self.seed)}
        carry = self.superstep(carry, static, init_pass=True)
        body, cond = self._loop(static, limit)
        final = jax.lax.while_loop(cond, body, carry) \
            if self.max_iter > 1 else carry
        return _stack_worker_axis(final)

    def _shard_map(self, fn, role, in_specs):
        from jax.sharding import PartitionSpec as P

        from ..common.compat import shard_map
        # uniform out_spec: every leaf gains a leading worker axis
        return shard_map(_name_program(fn, self.program_key, role),
                         mesh=self.mesh, in_specs=in_specs,
                         out_specs=P("d"), check_vma=False)

    def mapped(self):
        # ONE construction shared by lowered() and exec(): the HLO audit
        # must inspect exactly the program exec runs
        from jax.sharding import PartitionSpec as P

        def run(parts_shard, bcast_rep):
            return self._from_init({**parts_shard, **bcast_rep})
        return self._shard_map(run, "", (P("d"), P()))

    def first_chunk(self):
        from jax.sharding import PartitionSpec as P

        def run_first(parts_shard, bcast_rep, limit):
            return self._from_init({**parts_shard, **bcast_rep}, limit)
        return self._shard_map(run_first, "_first", (P("d"), P(), P()))

    def cont_chunk(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def run_cont(parts_shard, bcast_rep, carry_stacked, limit):
            static = {**parts_shard, **bcast_rep}
            carry = jax.tree_util.tree_map(
                lambda x: jnp.squeeze(x, 0), dict(carry_stacked))
            body, cond = self._loop(static, limit)
            return _stack_worker_axis(jax.lax.while_loop(cond, body, carry))
        return self._shard_map(run_cont, "_cont",
                               (P("d"), P(), P("d"), P()))

    def jit_chunks(self):
        """``(first, cont)``, jitted. Carry donation (ALINK_TPU_DONATE):
        argnum 2 of ``cont`` is the stacked chunk carry — the ONLY input
        a chunk pass consumes. parts/bcast are never donatable (every
        later chunk re-reads them)."""
        import jax
        return (jax.jit(self.first_chunk()),
                jax.jit(self.cont_chunk(),
                        donate_argnums=(2,) if self.donate else ()))

    def lower(self, parts, bcast, chunked: bool):
        import jax
        import jax.numpy as jnp
        if not chunked:
            return jax.jit(self.mapped()).lower(parts, bcast)
        lim = jnp.asarray(self.max_iter, jnp.int32)
        first, cont = self.jit_chunks()
        # the cont program's carry geometry comes from the first
        # program's abstract output — no execution, no compile
        carry_shape = jax.eval_shape(first, parts, bcast, lim)
        return (first.lower(parts, bcast, lim),
                cont.lower(parts, bcast, carry_shape, lim))


def _stack_worker_axis(carry):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(lambda x: jnp.expand_dims(x, 0), carry)


class ComputeFunction:
    """One per-worker compute stage (reference comqueue/ComputeFunction.java)."""

    def calc(self, context: ComContext):  # pragma: no cover - interface
        raise NotImplementedError


class _FnStage(ComputeFunction):
    def __init__(self, fn: Callable[[ComContext], None], name: str = ""):
        self.fn = fn
        self.__name__ = name or getattr(fn, "__name__", "stage")

    def calc(self, context: ComContext):
        self.fn(context)


def _readonly(arr: np.ndarray) -> np.ndarray:
    """Flip a host array read-only. Fetched results are MEMOIZED and
    shared between shards()/get()/concat() callers — a caller writing into
    one would silently corrupt every later read, so the memo only ever
    hands out non-writeable arrays (mutators get a loud ValueError and
    must copy)."""
    arr.flags.writeable = False
    return arr


def _fetch_tree(tree):
    """ONE batched device->host fetch of every leaf in ``tree`` (the
    shared ``common.compat.device_get_tree`` idiom), with every returned
    leaf flipped read-only (the memo contract above)."""
    import jax
    from ..common.compat import device_get_tree
    # the result fetch is the engine's device->host leg: ONE coarse span
    # on the process tracer carries its wall time and bytes. The fetch
    # itself is one batched device_get; leaves stay read-only — memo
    # contract.
    with trace_span("comqueue.fetch", cat="engine", coarse=True) as sp:
        got = device_get_tree(tree)
        sp.set(nbytes=int(sum(getattr(leaf, "nbytes", 0) for leaf
                              in jax.tree_util.tree_leaves(got))))
    return jax.tree_util.tree_map(_readonly, got)


class ComQueueResult:
    """Final per-worker state, stacked on a leading worker axis.

    Host arrays returned by ``shards()``/``get()`` are read-only views of
    a per-name memo; ``np.array(...)`` them to get a private writable
    copy."""

    def __init__(self, stacked: Dict[str, Any], num_workers: int,
                 totals: Dict[str, int]):
        self._stacked = stacked
        self.num_workers = num_workers
        self.totals = totals
        self._fetched: Dict[tuple, Any] = {}

    def shards(self, name: str):
        """(num_workers, ...) stacked per-worker values (read-only).

        Multi-leaf carry objects fetch in ONE batched ``jax.device_get``
        (see :func:`_fetch_tree`) — one link round trip per call, not
        per leaf."""
        if name not in self._stacked:
            raise KeyError(f"no carry object '{name}'; have {sorted(self._stacked)}")
        got = self._fetched.get(("shards", name))
        if got is None:
            got = self._fetched[("shards", name)] = _fetch_tree(
                self._stacked[name])
        return got

    def device(self, name: str):
        """The per-worker values of a carry stacked ``(num_workers, ...)``
        WHERE THEY LIE: no fetch. For a result the next program reads on
        the device (a binned table): sharded over the workers on its
        leading axis as the engine partitions an input."""
        if name not in self._stacked:
            raise KeyError(f"no carry object '{name}'; have {sorted(self._stacked)}")
        return self._stacked[name]

    def get(self, name: str):
        """Worker 0's copy (read-only) — use for replicated
        (post-allreduce) state. See :meth:`get_all`."""
        return self.get_all([name])[0]

    def get_all(self, names: Sequence[str]) -> List[Any]:
        """Worker 0's copies (read-only) of several carries, in the order
        asked: ONE compiled slice and ONE batched ``jax.device_get`` for
        all that are not on the host yet, so a trainer that reads its
        centroids, weights and history pays the device link once, not once
        a name.

        Slices BEFORE fetching (x[0] on device): fetching the full
        (num_workers, ...) stack and discarding all but shard 0 on host
        would pay num_workers x the bytes over the device link. Fetched
        leaves are memoized per name, so repeated reads pay the link once
        (advisor r4)."""
        import jax
        missing = [n for n in dict.fromkeys(names)
                   if ("get", n) not in self._fetched]
        on_device = {}
        for n in missing:
            # memo first: after release() a get()-only name serves from
            # its memo even though the stacked entry is gone
            if n not in self._stacked:
                raise KeyError(f"no carry object '{n}'; "
                               f"have {sorted(self._stacked)}")
            full = self._fetched.get(("shards", n))
            if full is not None:  # already on host: slice locally
                self._fetched[("get", n)] = _first_shards(full)
            elif all(isinstance(leaf, np.ndarray) for leaf in
                     jax.tree_util.tree_leaves(self._stacked[n])):
                # a released (or multi-host gathered) carry is host memory
                self._fetched[("get", n)] = jax.tree_util.tree_map(
                    lambda x: _readonly(np.asarray(x[0])), self._stacked[n])
            else:
                on_device[n] = self._stacked[n]
        if on_device:
            # the compiled slice is a dispatch of its own (a third of a
            # millisecond on a TPU host): a coarse span, so that neither
            # the exec's nor the trainer's self time hides it and
            # ``comqueue.fetch`` stays the transfer alone
            with trace_span("comqueue.slice", cat="engine", coarse=True):
                first = lazy_jit(_first_shards)(on_device)
            got = _fetch_tree(first)
            for n, v in got.items():
                self._fetched[("get", n)] = v
        return [self._fetched[("get", n)] for n in names]

    def release(self, keep: Sequence[str] = ()) -> "ComQueueResult":
        """Detach to host and drop every device reference so the superstep
        carry (sk/yk ring buffers, per-row margins, ...) stops pinning
        HBM. Carries named in ``keep`` or previously read via ``shards()``
        stay fully readable; carries read only via ``get()`` keep serving
        ``get()`` from the memo (their per-worker stacks are gone); all
        other device state is discarded. Callers that retain results
        across many cached fits should call this once they are done
        reading device state (advisor r4)."""
        for name in keep:
            self.shards(name)
        # names never fetched are dropped; fetched ones now back _stacked
        # as host arrays, so shards()/get() keep working after release
        self._stacked = {k: self._fetched[("shards", k)]
                         for k in self._stacked
                         if ("shards", k) in self._fetched}
        return self

    def concat(self, name: str, total: Optional[int] = None):
        """Concatenate per-worker shards along axis 0 (departitioning).

        Zero-padding added by ``init_with_partitioned_data`` sits at the end
        of the global order, so per-row outputs aligned with a partitioned
        input can be trimmed with ``total`` (defaults to the input total when
        unambiguous).
        """
        v = self.shards(name)
        out = np.concatenate(list(v), axis=0)
        if total is None and len(set(self.totals.values())) == 1:
            total = next(iter(self.totals.values()), None)
        return out if total is None else out[:total]

    @property
    def step_count(self) -> int:
        return int(self.get("__step"))

    def keys(self):
        return [k for k in self._stacked.keys() if not k.startswith("__")]

    # -- health probe channel (common/health.py) -------------------------
    def probe_names(self):
        """Names published via ``ctx.probe`` during the run (sorted)."""
        pre = ComContext.PROBE_PREFIX
        return sorted(k[len(pre):] for k in self._stacked
                      if k.startswith(pre))

    def probe_series(self, name: str, trim: bool = True):
        """One probe's per-superstep series (worker 0's copy — probes
        conventionally record replicated post-allreduce scalars). With
        ``trim`` the NaN prefill past the executed step count is cut, so
        ``series[i]`` is superstep ``i + 1``'s value."""
        s = self.get(ComContext.PROBE_PREFIX + name)
        return s[:self.step_count] if trim else s

    def probes(self, trim: bool = True):
        """Every probe series as ``{name: (steps,) array}`` (read-only).

        All not-yet-memoized series (plus the ``__step`` count the trim
        needs) fetch in ONE batched ``jax.device_get`` — a run with a
        dozen probes pays one link round trip here, not thirteen."""
        import jax
        pre = ComContext.PROBE_PREFIX
        names = self.probe_names()
        missing = [pre + n for n in names
                   if ("get", pre + n) not in self._fetched]
        if trim and ("get", "__step") not in self._fetched \
                and "__step" in self._stacked:
            missing.append("__step")
        if missing:
            sliced = [jax.tree_util.tree_map(lambda x: x[0],
                                             self._stacked[k])
                      for k in missing]
            fetched = jax.device_get(sliced)
            for k, v in zip(missing, fetched):
                self._fetched[("get", k)] = jax.tree_util.tree_map(
                    lambda x: _readonly(np.asarray(x)), v)
        return {n: self.probe_series(n, trim=trim) for n in names}


class IterativeComQueue:
    def __init__(self, env: Optional[MLEnvironment] = None, max_iter: int = 100,
                 seed: int = 0, checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 1, checkpoint_keep: int = 3,
                 resume_from: Optional[str] = None):
        self.env = env
        self.max_iter = max_iter
        self.seed = seed
        self._stages: List[ComputeFunction] = []
        self._partitioned: Dict[str, np.ndarray] = {}
        self._broadcast: Dict[str, Any] = {}
        self._criterion: Optional[Callable[[ComContext], Any]] = None
        self._close: Optional[Callable[[ComQueueResult], Any]] = None
        self._program_key: Optional[tuple] = None
        self._ckpt = None
        self._boundary = None     # (every, hook) — set_boundary
        self._health = None       # HealthMonitor (set_health)
        self._data_token = None   # checkpoint-signature memo (see _run)
        if checkpoint_dir is not None:
            self.set_checkpoint(checkpoint_dir, every=checkpoint_every,
                                keep_last=checkpoint_keep,
                                resume_from=resume_from)
        elif resume_from is not None:
            raise ValueError("resume_from= requires checkpoint_dir= "
                             "(an explicit resume request must not "
                             "silently retrain from scratch)")

    # -- builder API (mirrors BaseComQueue.java:75-148) -------------------
    def init_with_partitioned_data(self, name: str, data) -> "IterativeComQueue":
        self._partitioned[name] = data
        self._data_token = None
        return self

    def init_with_broadcast_data(self, name: str, data) -> "IterativeComQueue":
        self._broadcast[name] = data
        self._data_token = None
        return self

    def add(self, stage) -> "IterativeComQueue":
        if callable(stage) and not isinstance(stage, (ComputeFunction, CommunicateFunction)):
            stage = _FnStage(stage)
        self._stages.append(stage)
        return self

    def set_compare_criterion(self, fn) -> "IterativeComQueue":
        """Stop when fn(context) is truthy; must read replicated state only."""
        self._criterion = fn
        return self

    # reference name (BaseComQueue.setCompareCriterionOfNode0)
    set_compare_criterion_of_node0 = set_compare_criterion

    def set_max_iter(self, n: int) -> "IterativeComQueue":
        self.max_iter = n
        return self

    def close_with(self, fn: Callable[[ComQueueResult], Any]) -> "IterativeComQueue":
        self._close = fn
        return self

    def set_program_key(self, key) -> "IterativeComQueue":
        """Opt into the compiled-program cache (see _PROGRAM_CACHE).

        ``key`` must be hashable and must determine the stage structure
        and every Python constant the stages close over; data must flow
        through partitioned/broadcast inputs only.
        """
        self._program_key = key
        return self

    def set_checkpoint(self, directory: str, every: int = 1,
                       keep_last: int = 3,
                       resume_from: Optional[str] = None
                       ) -> "IterativeComQueue":
        """Persist the superstep carry every ``every`` supersteps (and at
        the final state) under ``directory`` — durable, checksummed,
        atomically published snapshots (common/checkpoint.py), fetched
        to host OUTSIDE the compiled program. ``resume_from=`` restarts
        a killed run from its newest valid snapshot with bitwise-
        identical final results (engine/recovery.py)."""
        from .recovery import CheckpointConfig
        self._ckpt = CheckpointConfig(directory=str(directory),
                                      every=int(every),
                                      keep_last=int(keep_last),
                                      resume_from=resume_from)
        return self

    def set_boundary(self, every: int, hook) -> "IterativeComQueue":
        """Run the superstep loop CHUNKED with a host boundary hook every
        ``every`` supersteps: ``hook(stacked_carry, step) -> carry|None``
        may transform the carry between chunks (return ``None`` to keep
        it). The batched-carry entry point of the tuning sweep
        (``alink_tpu/tuning/``): ASHA rung decisions read the per-point
        probe lanes from the boundary carry and flip the carry-resident
        alive mask — the compiled chunk programs never change (the chunk
        limit is a traced scalar), so pruning can never recompile.

        Composes with :meth:`set_checkpoint`: when both are set the
        boundary cadence wins (the sweep aligns its rung period with the
        snapshot cadence) and the hook runs right after each snapshot
        publishes — and again after a resume, so a resumed run re-derives
        the same deterministic boundary decisions. Without a checkpoint
        directory the same chunked programs run with persistence off."""
        if int(every) < 1:
            raise ValueError(f"set_boundary(every=) must be >= 1, "
                             f"got {every}")
        self._boundary = (int(every), hook)
        return self

    def set_health(self, monitor) -> "IterativeComQueue":
        """Attach a ``common.health.HealthMonitor``: after the run (and,
        for checkpointed runs, at every snapshot boundary — where the
        carry is already host-synced) the engine feeds it every
        ``ctx.probe`` series and calls ``evaluate()``. A monitor with
        ``raise_on={"critical"}`` therefore aborts a poisoned
        checkpointed run at the next boundary instead of burning the
        remaining superstep budget. No-op when ``ALINK_TPU_HEALTH`` is
        off (stages record no probes)."""
        self._health = monitor
        return self

    # -- execution --------------------------------------------------------
    def lowered(self):
        """Lower (but do not run) the whole-superstep SPMD program;
        returns the jax.stages.Lowered for HLO inspection — the scaling
        evidence tool reads the compiled collectives and their payload
        shapes from it (tools/scaling_evidence.py)."""
        return self._run(lower_only=True)

    def lowered_chunked(self):
        """Lower the CHECKPOINT-mode chunk programs; returns
        ``(first, cont)`` jax.stages.Lowered. The durability test asserts
        these carry no host callbacks and exactly the collectives of the
        unchunked program — checkpointing adds zero compiled ops."""
        return self._run(lower_only=True, lower_chunked=True)

    def exec(self):
        # one root span per exec: every phase span (prepare / execute via
        # StepTimer, wait, fetch), chunk span and instant event below
        # nests under it, so a trace file reads as one tree per fit. The
        # phases are coarse (in the ring of every process): exec =
        # prepare + plan (the ExecutionPlan and the program lookup) +
        # execute (dispatch; on a miss the trace and compile are its
        # ``jit.*`` children) + wait + slice + fetch (the step count's
        # read) + account (the metrics tail) + a self time that should
        # read near 0
        with trace_span("comqueue.exec", cat="engine", coarse=True) as sp:
            sp.set(max_iter=int(self.max_iter),
                   program=_program_label(self._program_key)
                   if self._program_key is not None else "uncached")
            return self._run(lower_only=False)

    def _run(self, lower_only: bool = False, lower_chunked: bool = False):
        """One pass over the queue: prepare the inputs, then either lower
        the program(s) (``lowered`` / ``lowered_chunked``) or plan, look
        the program(s) up and execute — chunked with host boundaries
        under ``set_checkpoint`` / ``set_boundary``, else as ONE
        program."""
        from ..common import compileledger
        from ..common import plan as planlib
        from ..common.metrics import metrics_enabled

        env = self.env or MLEnvironmentFactory.get_default()
        # key-folding flag dims, latched ONCE per run at the plan
        # derivation site (common/plan.engine_flags — the ENV-KEY-FOLD
        # checked site).  probes: stacked (max_iter,) carry entries make
        # a toggled flag a structurally different program.  donate: the
        # buffer-aliasing contract differs even though the HLO ops are
        # identical.  Both (plus step_log) ride the program-cache key
        # via the ExecutionPlan below.
        plan_flags = planlib.engine_flags()
        probes_on = plan_flags[1][1]
        donate = plan_flags[2][1]
        parts, totals, bcast = self._prepare(env.num_workers)
        bodies = _StepBodies(self, env, probes_on, donate)
        if lower_only:
            return bodies.lower(parts, bcast, chunked=lower_chunked)
        compileledger.subsystem_start("engine")
        execute = self._exec_chunked \
            if self._ckpt is not None or self._boundary is not None \
            else self._exec_plain
        return execute(bodies, parts, totals, bcast, plan_flags,
                       metrics_enabled())

    def _prepare(self, nw: int):
        """``(parts, totals, bcast)`` on the device: the partitioned
        inputs padded to whole shards, their true row counts, and the
        broadcast inputs with a ``__total_<name>`` scalar an input."""
        import jax
        import jax.numpy as jnp
        parts: Dict[str, Any] = {}
        totals: Dict[str, int] = {}
        # the prepare phase is host padding + the H2D input ship. ONE span:
        # the StepTimer's, which lands on the process tracer as
        # ``comqueue.prepare`` under the exec's root span (and mirrors into
        # the registry). Host-side wall clock only.
        with _ENGINE_TIMER.span("comqueue.prepare", coarse=True):
            for k, arr in self._partitioned.items():
                if isinstance(arr, (jax.Array, jax.ShapeDtypeStruct)):
                    # already device-resident (a cached table, precomputed
                    # one-hot design factors): it passes through untouched
                    # when its leading axis divides over the workers — no
                    # host round trip and no second device copy. Only a
                    # ragged leading axis is padded, on the device; build
                    # a resident input with whole shards (the trainers'
                    # row masks cover rows short of a shard). A
                    # ``ShapeDtypeStruct`` stands for such an input in
                    # ``lowered()``: the program at a size no host holds
                    # (compiled for a described chip, never run)
                    totals[k] = int(arr.shape[0])
                    pad = (-arr.shape[0]) % nw
                    if pad:
                        arr = jnp.concatenate(
                            [arr, jnp.zeros((pad, *arr.shape[1:]), arr.dtype)],
                            axis=0)
                    parts[k] = arr
                    continue
                arr = np.asarray(arr)
                totals[k] = int(arr.shape[0])
                pad = (-arr.shape[0]) % nw
                if pad:
                    arr = np.concatenate(
                        [arr, np.zeros((pad, *arr.shape[1:]), dtype=arr.dtype)],
                        axis=0)
                parts[k] = jnp.asarray(arr)
            bcast = {k: jax.tree_util.tree_map(jnp.asarray, v)
                     for k, v in self._broadcast.items()}
            for k, n in totals.items():
                bcast[f"__total_{k}"] = jnp.asarray(n, jnp.int32)
        return parts, totals, bcast

    def _plan(self, bodies: _StepBodies, plan_flags, parts, bcast):
        """ONE ExecutionPlan per exec (ROADMAP item 1): the program-cache
        key and the recovery signature both derive from it.  The
        structural guard stays (advisor r4): the stage bytecode + frozen
        closure cells ride in the "stages" dim, so a program_key that
        under-specifies a baked constant misses instead of silently
        re-running a stale program.  Returns ``(plan, cache key)``; the
        key is None for a queue without a ``program_key``."""
        from ..common import plan as planlib
        stages_dig = None
        if self._program_key is not None or self._ckpt is not None:
            stages_dig = _stages_digest(bodies.stages, bodies.criterion)
        splan = planlib.engine_plan(
            program_key=self._program_key, stages_digest=stages_dig,
            mesh=bodies.mesh, num_workers=bodies.nw,
            max_iter=bodies.max_iter, seed=bodies.seed,
            has_criterion=bodies.criterion is not None, flags=plan_flags,
            part_names=tuple(sorted(parts)),
            bcast_names=tuple(sorted(bcast)))
        ckey = splan.legacy_key() if self._program_key is not None else None
        return splan, ckey

    def _exec_chunked(self, bodies: _StepBodies, parts, totals, bcast,
                      plan_flags, mx: bool):
        """Durable chunked execution (engine/recovery.py): the ``first``
        + ``cont`` pair, a host boundary every ``every`` supersteps."""
        import jax
        import jax.numpy as jnp

        from ..common import aotcache
        from ..common import plan as planlib
        from . import recovery
        if jax.process_count() > 1:
            raise NotImplementedError(
                "comqueue checkpointing is single-process for now: the "
                "per-boundary carry fetch would need a multihost "
                "allgather + single-writer election")
        ck = self._ckpt
        on_boundary = None
        if self._boundary is not None:
            # boundary-driven chunking (tuning sweep rungs): the hook
            # cadence overrides the snapshot cadence — the sweep
            # aligns both, and a hook without set_checkpoint runs the
            # chunk programs with persistence off (directory=None)
            b_every, on_boundary = self._boundary
            if ck is None:
                ck = recovery.CheckpointConfig(directory=None,
                                               every=b_every)
            elif int(ck.every) != b_every:
                import dataclasses
                ck = dataclasses.replace(ck, every=b_every)
        site = _program_label(self._program_key) \
            if self._program_key is not None else None
        with trace_span("comqueue.plan", cat="engine", coarse=True):
            splan, ckey = self._plan(bodies, plan_flags, parts, bcast)
            ckkey = ("__ckpt__", ckey) if ckey is not None else None
            cplan = splan.extend(("checkpoint_chunked", True))
            aot = ()
            if ckkey is not None and aotcache.active():
                # the pair ships as two artifacts keyed off the same plan
                # with a role dim
                aot = ((cplan.extend(("role", "first")),
                        ("shard", "repl", "repl")),
                       (cplan.extend(("role", "cont")),
                        ("shard", "repl", "shard", "repl")))
            (first, cont), cache_status, manifest = _lookup_programs(
                "engine.chunked", ckkey, cplan, bodies.jit_chunks,
                bodies.manifest, site=site, mesh=bodies.mesh, mx=mx,
                aot=aot)
        lim0 = jnp.asarray(bodies.max_iter, jnp.int32)
        if cache_status == "miss" and aot:
            # export BEFORE recovery.drive: export's trace runs the
            # superstep closures, so the collective manifest is
            # populated by the time the header snapshots it.  Gate the
            # cont store on the first: a half pair on disk would never
            # install
            if aotcache.store(aot[0][0], first, (parts, bcast, lim0),
                              cache="engine.chunked", site=site,
                              manifest=manifest):
                carry_av = jax.eval_shape(first, parts, bcast, lim0)
                aotcache.store(aot[1][0], cont,
                               (parts, bcast, carry_av, lim0),
                               cache="engine.chunked", site=site,
                               manifest=manifest)
        if ck.directory or ck.resume_from:
            part_sig = tuple(
                (k, tuple(map(int, np.shape(parts[k]))),
                 str(getattr(parts[k], "dtype", "?")))
                for k in sorted(parts))
            # fingerprint the ORIGINAL (pre-padding, host-side)
            # inputs: np arrays hash by content, device-resident
            # arrays degrade to shape/dtype tokens (no forced
            # device->host round trip). Memoized per queue instance
            # (invalidated by init_with_*): repeated exec() on the
            # same queue must not re-hash the whole dataset per
            # program-cache hit
            data_token = self._data_token
            if data_token is None:
                data_token = self._data_token = _freeze_closure_value(
                    {"parts": dict(self._partitioned),
                     "bcast": dict(self._broadcast)}, 3)
            # the durable-run signature derives from the SAME plan
            # as the program-cache key (content identical to the
            # historical direct program_signature call — old
            # snapshots stay resumable)
            signature = planlib.engine_checkpoint_signature(
                splan, part_sig=part_sig, data_token=data_token)
            resumed = recovery.resume_state(ck, signature)
        else:
            # boundary-only chunking (set_boundary without a
            # checkpoint dir): nothing persists and nothing resumes,
            # so content-hashing the whole dataset for a signature
            # no snapshot will ever carry is pure waste
            signature, resumed = None, None
        on_snapshot = None
        if self._health is not None and bodies.probes_on:
            # mid-run watchdog: evaluate on the carry the boundary
            # save just fetched — zero extra device->host traffic.
            # evaluate() may raise HealthAlertError (raise_on=...),
            # aborting AFTER the snapshot published, so the run stays
            # resumable/inspectable
            def on_snapshot(host, step, _m=self._health):
                self._ingest_probes(_m, host, step)
        with _ENGINE_TIMER.span("comqueue.execute",
                                labels={"program": cache_status},
                                coarse=True):
            stacked, ck_info = recovery.drive(
                ck, first=first, cont=cont, parts=parts, bcast=bcast,
                max_iter=bodies.max_iter, signature=signature,
                resumed=resumed, on_snapshot=on_snapshot,
                donate=bodies.donate, on_boundary=on_boundary)
        return self._finish(stacked, bodies.nw, totals, manifest, parts,
                            bcast, mx, ck_info,
                            probes_on=bodies.probes_on)

    def _exec_plain(self, bodies: _StepBodies, parts, totals, bcast,
                    plan_flags, mx: bool):
        """The whole superstep loop as ONE program, dispatched once."""
        import jax

        from ..common import aotcache, compileledger
        from ..common.metrics import env_flag
        verify = env_flag("ALINK_VERIFY_PROGRAM_CACHE", default=False)
        site = _program_label(self._program_key) \
            if self._program_key is not None else None
        with trace_span("comqueue.plan", cat="engine", coarse=True):
            splan, ckey = self._plan(bodies, plan_flags, parts, bcast)
            # verify mode is excluded from the AOT store: it compares
            # fresh jaxprs against the trace recorded at compile time, and
            # a deserialized program has no trace to baseline against
            aot = ()
            if (ckey is not None and not verify
                    and jax.process_count() == 1 and aotcache.active()):
                aot = ((splan, ("shard", "repl")),)
            (compiled,), cache_status, manifest = _lookup_programs(
                "engine.program", ckey, splan,
                lambda: (jax.jit(bodies.mapped()),), bodies.manifest,
                site=site, mesh=bodies.mesh, mx=mx, aot=aot,
                fresh_jaxpr=(lambda: str(jax.make_jaxpr(bodies.mapped())(
                    parts, bcast))) if verify else None)
        with _ENGINE_TIMER.span("comqueue.execute",
                                labels={"program": cache_status},
                                coarse=True):
            # measured-profiling window (ALINK_TPU_PROFILE): dispatch =
            # time the compiled call held the host thread (includes
            # trace+compile on a cache miss — the label says which);
            # device = time an explicit block_until_ready waited on the
            # program. The extra sync only exists under the flag and
            # changes timing, never values or compiled HLO.
            with profile_window("comqueue.exec", label=cache_status,
                                capture=True) as pw:
                _pt0 = time.perf_counter()
                stacked = compiled(parts, bcast)
                _disp = time.perf_counter() - _pt0
                pw.dispatch(_disp)
                if cache_status == "miss":
                    # the first dispatch carried trace+compile — attach
                    # its wall to this miss's ledger entry
                    compileledger.note_wall("engine.program", _disp)
                if pw.on:
                    _pt1 = time.perf_counter()
                    jax.block_until_ready(stacked)
                    pw.device(time.perf_counter() - _pt1)
        hbm_snapshot("comqueue.exec")
        if cache_status == "miss" and aot:
            # persist off the hot path, after the first dispatch: the
            # export re-trace refreshes the same manifest dict the miss
            # installed (superstep capture is overwrite-safe)
            aotcache.store(splan, compiled, (parts, bcast),
                           cache="engine.program", site=site,
                           manifest=manifest)
        if jax.process_count() > 1:
            # multi-host session: leaves span non-addressable devices —
            # gather every worker's shard to every host before fetching
            # (the reference's result collection back to the client)
            from jax.experimental import multihost_utils
            stacked = jax.tree_util.tree_map(
                lambda x: np.asarray(
                    multihost_utils.process_allgather(x, tiled=True)),
                stacked)
        return self._finish(stacked, bodies.nw, totals, manifest, parts,
                            bcast, mx, None, probes_on=bodies.probes_on)

    @staticmethod
    def _ingest_probes(monitor, host, step):
        """Feed the probe prefix of a host (stacked) carry to a
        HealthMonitor and evaluate. Worker 0's copy: probes record
        replicated post-allreduce scalars by convention."""
        pre = ComContext.PROBE_PREFIX
        series = {k[len(pre):]: np.asarray(v)[0][:int(step)]
                  for k, v in host.items() if k.startswith(pre)}
        if series:
            monitor.ingest(series)
            monitor.evaluate()

    @staticmethod
    def _account(steps, nw, manifest, parts, bcast, ck_info):
        """The metrics tail of an exec: executions, supersteps and the
        collectives the traced manifest says each superstep ran."""
        import jax

        from ..common.metrics import get_registry
        reg = get_registry()
        # a resumed run only EXECUTED the supersteps past its snapshot
        # (and no init pass); charge collectives/supersteps for those
        if ck_info is None:
            executed, init_runs = steps, 1
        else:
            init_runs = 1 if ck_info["init_ran"] else 0
            executed = ck_info["steps_executed"]
        reg.inc("alink_comqueue_execs_total", 1)
        reg.inc("alink_comqueue_supersteps_total", executed)
        # this exec's trace signature, computed on the HOST inputs
        # exactly as static_sig sees them inside shard_map: parts are
        # split on the leading axis by the worker count, bcast is
        # replicated unchanged
        items = []
        for k in sorted(set(parts) | set(bcast)):
            split = nw if k in parts else 1
            for leaf in jax.tree_util.tree_leaves(
                    parts[k] if k in parts else bcast[k]):
                sh = tuple(map(int, leaf.shape))
                if split > 1 and sh:
                    sh = (sh[0] // split,) + sh[1:]
                items.append((k, sh, str(leaf.dtype)))
        per = manifest.get(tuple(items))
        if per is None and len(manifest) == 1:
            # defensive: a host/trace signature drift should not drop
            # attribution when only one trace exists
            per = next(iter(manifest.values()))
        # the init pass executed at most once (superstep 1; not at all
        # on a resumed run); the while-loop body executed the other
        # supersteps (the body is TRACED even for runs whose criterion
        # stops at step 1, so it must not be charged for supersteps it
        # never ran)
        if per is not None:
            from .communication import record_manifest
            if init_runs > 0:
                record_manifest(per["init"], times=init_runs)
            if executed - init_runs > 0:
                record_manifest(per["body"],
                                times=executed - init_runs)

    def _finish(self, stacked, nw, totals, manifest, parts, bcast, mx,
                ck_info, probes_on=False):
        """Shared result assembly + metrics tail for the single-program
        and checkpoint-chunked execution paths. ``ck_info`` is the
        recovery driver's accounting (None on the single-program path)."""
        import jax

        # single-process: leave leaves ON DEVICE — ComQueueResult fetches
        # per access, so a fit that only reads coef + loss_curve does not
        # pull the whole carry (L-BFGS sk/yk ring buffers, per-row
        # margins, ...) from the device to the host
        result = ComQueueResult(stacked, nw, totals)
        if mx:
            # one scalar fetch; it waits for the (asynchronously
            # dispatched) run, which the caller's first result read would
            # have done anyway. The wait is taken here, outside
            # ``comqueue.fetch``, so that span times the transfer alone,
            # and under a coarse span of its own: how long the host
            # waited for the chip, in every process
            with trace_span("comqueue.wait", cat="engine", coarse=True):
                jax.block_until_ready(stacked["__step"])
            steps = int(result.step_count)
            with trace_span("comqueue.account", cat="engine", coarse=True):
                self._account(steps, nw, manifest, parts, bcast, ck_info)
        if self._health is not None and probes_on:
            # final pass (also re-runs after a chunked run's last
            # boundary ingest — alerts are deduped by the monitor). The
            # probe fetch is a handful of (max_iter,) f32 series
            names = result.probe_names()
            if names:
                self._health.ingest_result(result)
                self._health.evaluate()
        if self._close is not None:
            return self._close(result)
        return result
