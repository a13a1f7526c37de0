"""Persistent AOT executable store — kill the cold start (ISSUE 20).

Every process restart re-pays tracing + XLA compilation for every
bucket program; fleet rollouts and the online DAG's
restart-from-checkpoint eat it on the critical path.  This module
persists compiled programs to disk via ``jax.export`` and installs
them back with **load-before-compile** semantics under every program
cache PR 19 unified (engine supersteps — the sweep compile groups and
DAG stages ride the same cache — the FTRL step-factory family, and
the serving/fleet bucket programs):

* artifact key — the :class:`~alink_tpu.common.plan.ExecutionPlan`
  blake2b digest (canonical, cross-process; PR 19) names the file:
  ``<dir>/<cache>/<digest>.aot``.  A plan that would compile a
  different program lands at a different path, so the common staleness
  case is a plain miss;
* compatibility fingerprint — jax/jaxlib version, backend platform,
  device kind, device count and grid, x64 mode — rides the artifact
  header.  An artifact FOUND at the right digest but built on another
  rig or toolchain is **refused loudly** (one warning naming the first
  mismatched field, an ``alink_aot_refusals_total`` sample) and the
  caller falls through to a fresh compile: a stale executable is never
  deserialized wrong, it is never deserialized at all;
* atomicity — artifacts publish write-tmp-then-rename with per-file
  fsync and a parent-directory fsync, the ``common/checkpoint.py``
  discipline, with bounded retention (``ALINK_TPU_AOT_CACHE_KEEP``
  newest artifacts per cache directory);
* ledger — a disk hit is recorded as a distinct ``disk-hit`` event
  kind (``compileledger.record_disk_hit``) carrying its deserialize
  wall time, so ``/compilez``, ``doctor.py`` and ``fleetz.py`` can
  attribute a warm restart instead of mistaking it for silence;
* guarded fallback — programs ``jax.export`` cannot serialize (or
  deserialize) skip the executable store without breaking anything;
  the session's XLA persistent compilation cache
  (``mlenv.place_compile_cache``) still saves them the XLA-compile
  half of their cold start on the next process.

The whole module is inert unless BOTH ``ALINK_TPU_AOT_CACHE`` (default
on) and ``ALINK_TPU_AOT_CACHE_DIR`` (default unset) are set: with no
cache directory every instrumented site runs its historical code path
byte-for-byte, and with the store active the installed program was
exported from the very jit the site would have compiled — cache-on
serving outputs are bitwise-identical to cache-off (pinned by
``tests/test_aotcache.py``).
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import struct
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

from .flags import flag_value
from .plan import ExecutionPlan

__all__ = [
    "MAGIC", "FORMAT", "aot_enabled", "aot_dir", "aot_keep", "active",
    "fingerprint", "artifact_path", "store", "load", "scan", "prune",
    "aot_jit", "deferred_store", "LoadedProgram", "stats", "reset",
]

MAGIC = b"ALNKAOT1"
FORMAT = 1

_lock = threading.Lock()
_warned: set = set()
_stats = {"loads": 0, "stores": 0, "refusals": 0, "export_skipped": 0}


# ---------------------------------------------------------------------------
# flags (registered in common/flags.py; key-neutral — see justifications)
# ---------------------------------------------------------------------------

def aot_enabled() -> bool:
    """``ALINK_TPU_AOT_CACHE`` (default ON): the store only acts when a
    cache directory is also configured — see :func:`active`."""
    return bool(flag_value("ALINK_TPU_AOT_CACHE", True))


def aot_dir() -> str:
    """``ALINK_TPU_AOT_CACHE_DIR``: the artifact root.  Unset (the
    default) disables the store entirely."""
    return str(flag_value("ALINK_TPU_AOT_CACHE_DIR", "") or "")


def aot_keep() -> int:
    """``ALINK_TPU_AOT_CACHE_KEEP``: newest artifacts retained per
    cache directory after each store (mtime order)."""
    return max(8, int(flag_value("ALINK_TPU_AOT_CACHE_KEEP", 128)))


def active() -> bool:
    """True when the store should load/persist: flag on AND a cache
    directory configured."""
    return bool(aot_dir()) and aot_enabled()


# ---------------------------------------------------------------------------
# compatibility fingerprint
# ---------------------------------------------------------------------------

def fingerprint() -> Dict[str, Any]:
    """The rig/toolchain identity an artifact must match before its
    payload is deserialized: jax + jaxlib versions, backend platform,
    device kind, device count and grid shape, x64 mode.  Per-program
    mesh geometry (axis names, grid, device strings) additionally rides
    the plan digest itself — the fingerprint guards what the digest
    cannot see."""
    import jax
    import jaxlib
    devs = jax.devices()
    return {
        "jax": str(jax.__version__),
        "jaxlib": str(jaxlib.__version__),
        "backend": str(jax.default_backend()),
        "device_kind": str(devs[0].device_kind) if devs else "?",
        "device_count": len(devs),
        "mesh_shape": [len(devs)],
        "x64": bool(jax.config.jax_enable_x64),
    }


def _fingerprint_mismatch(theirs: Dict[str, Any]) -> Optional[str]:
    """The first mismatched fingerprint field (named, old -> new), or
    None when compatible."""
    mine = fingerprint()
    for k in ("jax", "jaxlib", "backend", "device_kind", "device_count",
              "mesh_shape", "x64"):
        if theirs.get(k) != mine.get(k):
            return f"{k}: artifact={theirs.get(k)!r} rig={mine.get(k)!r}"
    return None


# ---------------------------------------------------------------------------
# paths + atomic publish (common/checkpoint.py discipline)
# ---------------------------------------------------------------------------

def _cache_subdir(cache: str) -> str:
    safe = "".join(c if (c.isalnum() or c in "._-") else "_"
                   for c in cache) or "cache"
    return os.path.join(aot_dir(), safe)

def artifact_path(cache: str, digest: str) -> str:
    """``<dir>/<cache>/<plan-digest>.aot``."""
    return os.path.join(_cache_subdir(cache), f"{digest}.aot")


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _publish(path: str, blob: bytes) -> None:
    """Write-tmp-then-rename with fsync: a crashed store leaves a
    ``.tmp-*`` sibling no reader ever opens, never a torn artifact."""
    directory = os.path.dirname(path)
    os.makedirs(directory, exist_ok=True)
    tmp = os.path.join(directory,
                       f".tmp-{os.getpid()}-{os.path.basename(path)}")
    with open(tmp, "wb") as f:
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.rename(tmp, path)
    _fsync_dir(directory)


def prune(cache: str) -> int:
    """Drop the oldest artifacts beyond ``aot_keep()`` in one cache
    directory (mtime order); returns how many were removed.  ``.tmp-*``
    debris older than an hour is swept too."""
    directory = _cache_subdir(cache)
    try:
        names = os.listdir(directory)
    except OSError:
        return 0
    removed = 0
    now = time.time()
    arts = []
    for n in names:
        p = os.path.join(directory, n)
        if n.startswith(".tmp-"):
            try:
                if now - os.path.getmtime(p) > 3600:
                    os.remove(p)
                    removed += 1
            except OSError:
                pass
            continue
        if n.endswith(".aot"):
            try:
                arts.append((os.path.getmtime(p), p))
            except OSError:
                pass
    arts.sort(reverse=True)
    for _, p in arts[aot_keep():]:
        try:
            os.remove(p)
            removed += 1
        except OSError:
            pass
    return removed


# ---------------------------------------------------------------------------
# refusal plumbing (loud, once per path+reason, never raising)
# ---------------------------------------------------------------------------

def _refuse(path: str, cache: str, reason: str) -> None:
    _stats["refusals"] += 1
    key = (path, reason.split(":", 1)[0])
    with _lock:
        first = key not in _warned
        _warned.add(key)
    if first:
        warnings.warn(
            f"aotcache: refusing artifact {path}: {reason} — falling "
            f"through to a fresh compile", RuntimeWarning, stacklevel=3)
    try:
        from .metrics import get_registry, metrics_enabled
        if metrics_enabled():
            get_registry().inc("alink_aot_refusals_total", 1,
                               {"cache": cache,
                                "reason": reason.split(":", 1)[0]})
    except Exception:
        pass


def _warn_once(key: str, msg: str) -> None:
    with _lock:
        if key in _warned:
            return
        _warned.add(key)
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------

def _short(v: Any) -> str:
    s = repr(v)
    return s if len(s) <= 120 else s[:117] + "..."


def store(plan: ExecutionPlan, fn: Callable, example_args: Tuple, *,
          cache: str, site: str = "", key: Optional[Tuple] = None,
          manifest: Any = None) -> bool:
    """Export ``fn`` (a ``jax.jit`` program) against ``example_args``
    and publish it under this plan's digest.  Never raises: a program
    ``jax.export`` cannot serialize skips the store (warn once per
    cache) and the site keeps its freshly compiled program.  Returns
    True iff an artifact was published."""
    if not active():
        return False
    try:
        from jax import export as jax_export
        exported = jax_export.export(fn)(*example_args)
        payload = exported.serialize()
    except Exception as e:
        _stats["export_skipped"] += 1
        _warn_once(f"export:{cache}",
                   f"aotcache: jax.export cannot serialize programs of "
                   f"cache {cache!r} ({e!r}) — the session's XLA "
                   f"persistent compilation cache still covers their "
                   f"recompiles")
        try:
            from .metrics import get_registry, metrics_enabled
            if metrics_enabled():
                get_registry().inc("alink_aot_export_skipped_total", 1,
                                   {"cache": cache})
        except Exception:
            pass
        return False
    try:
        header = {
            "format": FORMAT,
            "plan_digest": plan.digest(),
            "subsystem": plan.subsystem,
            "cache": cache,
            "site": site,
            "created_unix": round(time.time(), 3),
            "fingerprint": fingerprint(),
            "dims": [[n, _short(v)] for n, v in plan.dims],
            "key_repr": None if key is None else repr(key),
            "manifest_repr": None if manifest is None else repr(manifest),
            "payload_blake2b": hashlib.blake2b(
                payload, digest_size=16).hexdigest(),
            "payload_len": len(payload),
        }
        hdr = json.dumps(header, sort_keys=True).encode()
        blob = MAGIC + struct.pack(">I", len(hdr)) + hdr + payload
        path = artifact_path(cache, header["plan_digest"])
        _publish(path, blob)
        prune(cache)
        _stats["stores"] += 1
        try:
            from .metrics import get_registry, metrics_enabled
            if metrics_enabled():
                get_registry().inc("alink_aot_stores_total", 1,
                                   {"cache": cache})
        except Exception:
            pass
        return True
    except Exception as e:
        _warn_once(f"store:{cache}",
                   f"aotcache: failed to publish an artifact for cache "
                   f"{cache!r}: {e!r}")
        return False


# ---------------------------------------------------------------------------
# load
# ---------------------------------------------------------------------------

class LoadedProgram:
    """One deserialized executable: ``fn`` dispatches it (a ``jax.jit``
    around the exported call — no tracing of user code, no XLA
    build-from-scratch), ``header`` is the artifact header,
    ``wall_s`` the deserialize wall the ledger records."""

    __slots__ = ("fn", "header", "wall_s")

    def __init__(self, fn: Callable, header: Dict[str, Any],
                 wall_s: float):
        self.fn = fn
        self.header = header
        self.wall_s = wall_s

    def manifest(self, default: Any = None) -> Any:
        """The collective manifest persisted with the program (engine
        programs record it at trace time; a disk hit never traces, so
        the artifact carries it).  Falls back to ``default`` when absent
        or unparseable — accounting degrades, the program does not."""
        rep = self.header.get("manifest_repr")
        if not rep:
            return default
        try:
            return ast.literal_eval(rep)
        except Exception:
            return default


def _read_header(path: str, blob: bytes) -> Tuple[Dict[str, Any], bytes]:
    """Parse MAGIC + u32 header length + JSON header + payload; raises
    ValueError naming the defect."""
    if len(blob) < len(MAGIC) + 4 or not blob.startswith(MAGIC):
        raise ValueError("bad-magic: not an ALNKAOT1 artifact")
    (hlen,) = struct.unpack(">I", blob[len(MAGIC):len(MAGIC) + 4])
    body = blob[len(MAGIC) + 4:]
    if hlen <= 0 or hlen > len(body):
        raise ValueError("bad-header: truncated header")
    try:
        header = json.loads(body[:hlen].decode())
    except Exception as e:
        raise ValueError(f"bad-header: {e!r}")
    if not isinstance(header, dict) or header.get("format") != FORMAT:
        raise ValueError(
            f"bad-header: format {header.get('format') if isinstance(header, dict) else '?'}"
            f" != {FORMAT}")
    return header, body[hlen:]


def load(plan: ExecutionPlan, *, cache: str, site: str = "",
         subsystem: str = "", record: bool = True
         ) -> Optional[LoadedProgram]:
    """Load-before-compile: the artifact for this plan's digest, fully
    validated (magic, header, plan digest, compatibility fingerprint,
    payload checksum) and deserialized — or None, with every validation
    failure refused LOUDLY while the caller falls through to compile.
    On success the deserialize wall is recorded in the compile ledger
    as a ``disk-hit`` event (unless ``record=False``: warming paths
    that install into an in-memory cache record at install time)."""
    if not active():
        return None
    digest = plan.digest()
    path = artifact_path(cache, digest)
    t0 = time.perf_counter()
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return None                      # plain miss, not a refusal
    try:
        header, payload = _read_header(path, blob)
    except ValueError as e:
        _refuse(path, cache, str(e))
        return None
    if header.get("plan_digest") != digest:
        _refuse(path, cache,
                f"plan-digest-mismatch: artifact "
                f"{header.get('plan_digest')!r} != requested {digest!r}")
        return None
    mism = _fingerprint_mismatch(header.get("fingerprint") or {})
    if mism is not None:
        _refuse(path, cache, f"fingerprint-mismatch: {mism}")
        return None
    if len(payload) != header.get("payload_len") or \
            hashlib.blake2b(payload, digest_size=16).hexdigest() != \
            header.get("payload_blake2b"):
        _refuse(path, cache,
                "payload-corrupt: length/checksum does not match the "
                "header (truncated or bit-rotted artifact)")
        return None
    try:
        import jax
        from jax import export as jax_export
        fn = jax.jit(jax_export.deserialize(payload).call)
    except Exception as e:
        _refuse(path, cache, f"deserialize-failed: {e!r}")
        return None
    wall = time.perf_counter() - t0
    _stats["loads"] += 1
    try:
        from .metrics import get_registry, metrics_enabled
        if metrics_enabled():
            get_registry().inc("alink_aot_loads_total", 1, {"cache": cache})
    except Exception:
        pass
    if record:
        from . import compileledger
        compileledger.record_disk_hit(cache, plan, wall_s=wall,
                                      site=site, subsystem=subsystem)
    return LoadedProgram(fn, header, wall)


def scan(cache: str) -> List[Tuple[str, Dict[str, Any]]]:
    """Headers of every artifact in one cache directory (payloads are
    NOT read) — the warming paths enumerate these, re-derive the plan
    each key would produce TODAY and only install artifacts whose
    digest still matches.  Unreadable entries are skipped silently (a
    foreign file is not a refusal)."""
    directory = _cache_subdir(cache)
    out: List[Tuple[str, Dict[str, Any]]] = []
    if not active():
        return out
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return out
    for n in names:
        if not n.endswith(".aot"):
            continue
        path = os.path.join(directory, n)
        try:
            with open(path, "rb") as f:
                head = f.read(len(MAGIC) + 4)
                if len(head) < len(MAGIC) + 4 or \
                        not head.startswith(MAGIC):
                    continue
                (hlen,) = struct.unpack(">I", head[len(MAGIC):])
                if hlen <= 0 or hlen > 1 << 24:
                    continue
                header = json.loads(f.read(hlen).decode())
        except Exception:
            continue
        if isinstance(header, dict) and header.get("format") == FORMAT:
            out.append((path, header))
    return out


# ---------------------------------------------------------------------------
# lazy wrappers (sites whose example args only exist at first dispatch)
# ---------------------------------------------------------------------------

class _DeferredStore:
    """Wrap a freshly compiled jit: the first dispatch runs the program
    as today, THEN exports it against the very arguments it ran with.
    Transparent otherwise — same args, same outputs, ``lower``
    delegates."""

    __slots__ = ("_fn", "_plan", "_cache", "_site", "_key", "_done",
                 "_lk")

    def __init__(self, fn, plan, cache, site, key):
        self._fn = fn
        self._plan = plan
        self._cache = cache
        self._site = site
        self._key = key
        self._done = False
        self._lk = threading.Lock()

    def __call__(self, *args):
        out = self._fn(*args)
        if not self._done:
            with self._lk:
                if not self._done:
                    self._done = True
                    store(self._plan, self._fn, args, cache=self._cache,
                          site=self._site, key=self._key)
        return out

    def lower(self, *args, **kw):
        return self._fn.lower(*args, **kw)


def deferred_store(plan: ExecutionPlan, fn: Callable, *, cache: str,
                   site: str = "", key: Optional[Tuple] = None) -> Callable:
    """``store`` for sites that cache the program before its first
    dispatch (the fleet geometry groups): returns ``fn`` untouched when
    the store is inactive, else a transparent first-call exporter."""
    if not active():
        return fn
    return _DeferredStore(fn, plan, cache, site, key)


class _LazyAot:
    """Load-before-compile for lru step factories (the FTRL family):
    the factory returns this in place of its jitted step; the FIRST
    call resolves against the disk using the real arguments' avals as
    the final plan dimensions — a disk hit installs the deserialized
    program (recorded as ``disk-hit``), a miss dispatches the original
    jit (which compiles exactly as today) and then exports it.  A
    deserialized program that fails its first dispatch falls back to
    the original jit, once, loudly."""

    __slots__ = ("_orig", "_impl", "_plan", "_cache", "_site",
                 "_subsystem", "_mesh", "_in_specs", "_lk")

    def __init__(self, fn, plan, cache, site, subsystem, mesh=None,
                 in_specs=None):
        self._orig = fn
        self._impl = None
        self._plan = plan
        self._cache = cache
        self._site = site
        self._subsystem = subsystem
        self._mesh = mesh
        self._in_specs = in_specs
        self._lk = threading.Lock()

    def _placed(self, fn):
        """An exported multi-device program must be called in the device
        context it was built for — wrap the deserialized call so each
        positional arg is ``device_put`` onto the mesh under the same
        partition specs the source ``shard_map`` declared.  No-op for
        single-device meshes or sites that did not pass specs."""
        mesh, specs = self._mesh, self._in_specs
        if mesh is None or specs is None:
            return fn
        import numpy as _np
        if int(_np.prod(mesh.devices.shape)) <= 1:
            return fn
        import jax
        from jax.sharding import NamedSharding
        shardings = tuple(NamedSharding(mesh, s) for s in specs)

        def call(*args):
            placed = [jax.tree_util.tree_map(
                          lambda x, _s=s: jax.device_put(x, _s), a)
                      for a, s in zip(args, shardings)]
            placed.extend(args[len(shardings):])
            return fn(*placed)
        return call

    def _aval_dims(self, args) -> Tuple:
        import jax
        leaves = jax.tree_util.tree_leaves(args)
        return tuple((tuple(int(d) for d in getattr(x, "shape", ())),
                      str(getattr(x, "dtype", type(x).__name__)))
                     for x in leaves)

    def _resolve(self, args):
        plan = self._plan.extend(("avals", self._aval_dims(args)))
        loaded = load(plan, cache=self._cache, site=self._site,
                      subsystem=self._subsystem)
        if loaded is not None:
            try:
                fn = self._placed(loaded.fn)
                out = fn(*args)
                self._impl = fn
                return out, True
            except Exception as e:
                _warn_once(
                    f"dispatch:{self._cache}:{plan.digest()}",
                    f"aotcache: deserialized program for cache "
                    f"{self._cache!r} failed its first dispatch "
                    f"({e!r}) — recompiling from source")
        out = self._orig(*args)
        store(plan, self._orig, args, cache=self._cache, site=self._site)
        self._impl = self._orig
        return out, False

    def __call__(self, *args):
        impl = self._impl
        if impl is not None:
            return impl(*args)
        with self._lk:
            if self._impl is not None:
                return self._impl(*args)
            out, _ = self._resolve(args)
            return out

    def lower(self, *args, **kw):
        return self._orig.lower(*args, **kw)


def aot_jit(fn: Callable, *, subsystem: str, cache: str, site: str,
            dims: Tuple[Tuple[str, Any], ...], mesh=None,
            in_specs=None) -> Callable:
    """Wrap a jitted step function with the lazy disk-backed resolver.
    ``dims`` are the factory's own key dimensions (hyperparameters,
    geometry, mesh, donation) — deliberately EXCLUDING per-model content
    fingerprints like the FTRL ``warm_coef_blake2b``: weights are
    program arguments, the compiled program is identical across models
    of one geometry, and keying artifacts on coefficients would churn
    the store once per model for byte-identical executables.  The
    input avals join the plan at first call.  Inactive store: ``fn``
    returned untouched (byte-identical behavior)."""
    if not active():
        return fn
    return _LazyAot(fn, ExecutionPlan(subsystem, tuple(dims)), cache,
                    site, subsystem, mesh=mesh, in_specs=in_specs)


# ---------------------------------------------------------------------------
# introspection / tests
# ---------------------------------------------------------------------------

def stats() -> Dict[str, int]:
    return dict(_stats)


def reset() -> None:
    """Tests only: drop warn-once state and counters (the on-disk store
    is the test's own tmpdir)."""
    with _lock:
        _warned.clear()
    for k in _stats:
        _stats[k] = 0
