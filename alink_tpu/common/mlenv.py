"""Session / environment layer.

Re-design of ``MLEnvironment`` / ``MLEnvironmentFactory``
(common/MLEnvironment.java:38-44,115-138; common/MLEnvironmentFactory.java:42-90).

The reference session holds Flink batch+stream execution environments sized
to the local cores. The TPU-native session instead holds a
``jax.sharding.Mesh``: the data axis ``'d'`` replaces Flink task slots
(BatchOperator partitions map 1:1 to chips — BASELINE.json north star), and
an optional model axis ``'m'`` carries feature-sharded state (FTRL-style
tensor parallelism, SURVEY §2.3).
"""

from __future__ import annotations

import itertools
import os
import threading
from typing import Dict, Optional, Tuple

import numpy as np

from .lazy import LazyObjectsManager
from .metrics import get_registry, metrics_enabled
from .tracing import trace_complete, trace_span


def mesh_device_request() -> int:
    """``ALINK_TPU_MESH_DEVICES`` (default 0 = all of ``jax.devices()``):
    how many devices the default session mesh should span. On CPU rigs
    this is the knob that turns the historical 1-device virtual axis into
    a real ≥4-device host-platform mesh (measured multi-device execution,
    SCALING_r06) — set it before the first jax backend touch so
    :func:`ensure_host_platform_devices` can still widen the platform."""
    from .flags import flag_value
    return int(flag_value("ALINK_TPU_MESH_DEVICES"))


def _jax_backend_initialized() -> bool:
    """Has any jax backend already been instantiated? XLA flags latch at
    backend init, so widening the host platform is only possible before
    this returns True."""
    import sys
    xb = sys.modules.get("jax._src.xla_bridge")
    return xb is not None and bool(xb._backends)


def ensure_host_platform_devices(n: int) -> bool:
    """Arrange for >= ``n`` devices on a CPU rig by forcing the XLA host
    platform device count BEFORE the backend initializes (the bootenv
    mechanism, in-process). Returns True when the flag could be set (or
    enough devices already exist); False when the backend already latched
    with fewer devices — callers then respawn a fresh interpreter with
    ``bootenv.cpu_mesh_env(n)`` (tools/scaling_evidence.py does)."""
    if _jax_backend_initialized():
        import jax
        return len(jax.devices()) >= n
    flags = os.environ.get("XLA_FLAGS", "")
    import re
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is not None:
        # caller already chose a count; respect it — but report honestly
        # whether it satisfies the request (a smaller pinned count means
        # the caller must respawn, exactly like the initialized case)
        return int(m.group(1)) >= n
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={int(n)}").strip()
    return True


#: where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed directory inside the checkout. The path is part of
#: jax's cache key, so a directory that moves (a temp name, a pid, a
#: timestamp) never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> str:
    """Place jax's persistent compilation cache and return the directory
    in force. ``JAX_COMPILATION_CACHE_DIR`` decides it from outside (jax
    reads the variable itself; nothing here overrides it); unset, every
    session — examples, bench, ``chip_smoke.py``, servers — shares
    :data:`COMPILE_CACHE_DIR`. The one place in the repo that sets
    ``jax_compilation_cache_dir``."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if jax.config.jax_compilation_cache_dir != COMPILE_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


#: JAX's own account of what a program costs a process before it runs,
#: as ``jax.monitoring`` reports it, under the names the ring gives it
_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_cache_outcome = threading.local()
_sessions = itertools.count()       # sessions this process has started


def _on_jax_event(event: str, **_kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        _cache_outcome.last = outcome


def _on_jax_duration(event: str, secs: float, **kw) -> None:
    """One coarse retroactive event a trace, a lowering and a backend
    compile (on a persistent-cache hit: the retrieval), parented to the
    span open on the compiling thread. A function traced inside another's
    trace reports too, so ``jit.trace`` events nest by their intervals;
    a warm call fires none of the three."""
    name = _JIT_EVENTS.get(event)
    if name is None:
        return
    args = {"fun_name": str(kw.get("fun_name", "?"))}
    if name == "jit.compile":
        # the cache's verdict precedes the duration on the same thread;
        # neither a hit nor a miss means the cache was not asked
        cache = getattr(_cache_outcome, "last", "off")
        _cache_outcome.last = "off"
        args["cache"] = cache
        if metrics_enabled():
            get_registry().inc("alink_jit_compiles_total", 1,
                               {"cache": cache})
    trace_complete(name, secs, cat="jit", args=args, coarse=True)


def _listen_to_jax() -> None:
    from jax import monitoring
    monitoring.register_event_listener(_on_jax_event)
    monitoring.register_event_duration_secs_listener(_on_jax_duration)


class MLEnvironment:
    """One session: device mesh + lazy-objects manager + RNG seed stream."""

    def __init__(self, parallelism: Optional[int] = None, model_parallelism: int = 1,
                 devices=None):
        # ``session`` counts from 0: the process's first session ends its
        # boot (interpreter, imports, backend, device discovery)
        nth = next(_sessions)
        with trace_span("session.start", cat="session", coarse=True,
                        args={"session": nth}) as sp:
            if nth == 0:
                _listen_to_jax()
            self._start(parallelism, model_parallelism, devices)
            sp.set(devices=len(self._devices),
                   platform=str(self._devices[0].platform))

    def _start(self, parallelism, model_parallelism, devices) -> None:
        import jax

        place_compile_cache()
        if devices is None:
            req = mesh_device_request()
            if req > 0:
                # widen the CPU host platform before the backend latches
                # (no-op on TPU or once a backend exists)
                ensure_host_platform_devices(req)
            devices = jax.devices()
            if req > 0:
                if len(devices) < req:
                    raise ValueError(
                        f"ALINK_TPU_MESH_DEVICES={req} but only "
                        f"{len(devices)} devices are available and the "
                        f"host platform could not be widened (jax backend "
                        f"already initialized, or XLA_FLAGS already pins a "
                        f"smaller device count); set "
                        f"XLA_FLAGS=--xla_force_host_platform_device_count="
                        f"{req} before the first jax use, or respawn via "
                        f"bootenv.cpu_mesh_env({req})")
                devices = devices[:req]
        n = len(devices)
        if parallelism is None:
            parallelism = max(1, n // model_parallelism)
        total = parallelism * model_parallelism
        if total > n:
            raise ValueError(
                f"requested {parallelism}x{model_parallelism} devices but only {n} available")
        self._devices = devices[:total]
        self.parallelism = parallelism
        self.model_parallelism = model_parallelism
        self._mesh = None
        self.lazy_objects_manager = LazyObjectsManager()
        self._seed_counter = 0

    @property
    def mesh(self):
        from jax.sharding import Mesh
        if self._mesh is None:
            arr = np.asarray(self._devices).reshape(self.parallelism, self.model_parallelism)
            self._mesh = Mesh(arr, ("d", "m"))
        return self._mesh

    @property
    def num_workers(self) -> int:
        """Flink parallelism analogue: number of data-axis shards."""
        return self.parallelism

    def next_seed(self) -> int:
        self._seed_counter += 1
        return self._seed_counter

    def data_sharding(self, *extra_axes):
        """NamedSharding that shards dim 0 along 'd' and replicates the rest."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P("d", *extra_axes))

    def replicated_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P())


class MLEnvironmentFactory:
    """id -> MLEnvironment registry (reference MLEnvironmentFactory.java:42-90)."""

    DEFAULT_ML_ENVIRONMENT_ID = 0
    _lock = threading.Lock()
    _map: Dict[int, MLEnvironment] = {}
    _next_id = 1

    @classmethod
    def get(cls, session_id: int) -> MLEnvironment:
        with cls._lock:
            if session_id not in cls._map:
                if session_id == cls.DEFAULT_ML_ENVIRONMENT_ID:
                    cls._map[session_id] = MLEnvironment()
                else:
                    raise KeyError(
                        f"Cannot find MLEnvironment for id {session_id}; "
                        "call get_new_ml_environment_id()/set_default first.")
            return cls._map[session_id]

    @classmethod
    def get_default(cls) -> MLEnvironment:
        return cls.get(cls.DEFAULT_ML_ENVIRONMENT_ID)

    @classmethod
    def set_default(cls, env: MLEnvironment):
        with cls._lock:
            cls._map[cls.DEFAULT_ML_ENVIRONMENT_ID] = env

    @classmethod
    def get_new_ml_environment_id(cls) -> int:
        with cls._lock:
            sid = cls._next_id
            cls._next_id += 1
            cls._map[sid] = MLEnvironment()
            return sid

    @classmethod
    def register(cls, env: MLEnvironment) -> int:
        with cls._lock:
            sid = cls._next_id
            cls._next_id += 1
            cls._map[sid] = env
            return sid

    @classmethod
    def remove(cls, session_id: int) -> Optional[MLEnvironment]:
        with cls._lock:
            if session_id == cls.DEFAULT_ML_ENVIRONMENT_ID:
                return cls._map.get(session_id)
            return cls._map.pop(session_id, None)

    @classmethod
    def reset(cls):
        with cls._lock:
            cls._map.clear()
            cls._next_id = 1


def use_local_env(parallelism: Optional[int] = None, model_parallelism: int = 1) -> MLEnvironment:
    """PyAlink-style entry (reference README.md:49-58 ``useLocalEnv``)."""
    env = MLEnvironment(parallelism=parallelism, model_parallelism=model_parallelism)
    MLEnvironmentFactory.set_default(env)
    return env


def use_remote_env(coordinator_address: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None,
                   parallelism: Optional[int] = None,
                   model_parallelism: int = 1) -> MLEnvironment:
    """Multi-host entry (reference ``useRemoteEnv``: session on a cluster).

    Where the reference connects the Py4J gateway to a remote Flink cluster,
    the TPU build joins a multi-host JAX runtime: every host in the slice
    calls this with the same coordinator address; ``jax.distributed``
    initializes cross-host ICI/DCN collectives and ``jax.devices()`` then
    spans ALL hosts' chips, so the returned session's mesh — and therefore
    every BSP program, psum, and all_gather — runs slice-wide with no other
    code changes. On Cloud TPU the three arguments are auto-detected from
    the environment and may be omitted.

    The data each host feeds the engine should be that host's input shard
    (per-host sharded readers, SURVEY §7 "scaling 8->128 chips").
    """
    import jax

    already = getattr(jax.distributed, "is_initialized", None)
    if not (callable(already) and already()):
        kwargs = {}
        if coordinator_address is not None:
            kwargs["coordinator_address"] = coordinator_address
        if num_processes is not None:
            kwargs["num_processes"] = num_processes
        if process_id is not None:
            kwargs["process_id"] = process_id
        try:
            jax.distributed.initialize(**kwargs)
        except (RuntimeError, ValueError) as e:
            # RuntimeError: backends already up (jax touched before
            # connecting). ValueError: nothing to auto-detect on this host.
            # A genuinely multi-host request must fail loudly — degrading
            # would train num_processes independent wrong models — but a
            # single/unspecified-process session can continue locally.
            if num_processes is not None and num_processes > 1:
                raise RuntimeError(
                    f"use_remote_env: could not join the {num_processes}-"
                    f"process distributed runtime: {e}") from e
            print(f"[alink_tpu] use_remote_env: jax.distributed not joined "
                  f"({e}); continuing with this process's devices only")
    return use_local_env(parallelism=parallelism,
                         model_parallelism=model_parallelism)
