"""Early pytest plugin (loaded via ``addopts = -p bootenv`` in pytest.ini).

Re-execs the test process with a CPU 8-device JAX environment BEFORE pytest
installs fd capture (so child output reaches the terminal) and before any
jax backend is touched: XLA flags latch at backend init, and the tests run
on the CPU even where a chip is present. See tests/conftest.py for the
rationale of the 8-device mesh.
"""

import os
import sys

_MARK = "ALINK_TPU_TEST_ENV"


def cpu_mesh_env(n_devices: int, base_env=None) -> dict:
    """Env vars for a fresh interpreter with an n-device virtual CPU mesh.

    XLA flags latch at backend init, so the mesh size must be in the env
    before jax is first touched. A forced-CPU interpreter is a
    correctness run: it compiles what it runs (the persistent compile
    cache is switched off), so it never depends on what an earlier run
    left on disk — and never pays XLA:CPU's logged machine-feature
    complaint per cached executable it loads (jax 0.9.0). A caller that
    measures a warm restart re-enables it with its own directory.
    """
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("ALINK_TPU_EXTRA_XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n_devices}"
                        ).strip()
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return env


def warm_restart_cache_env(env: dict, cache_dir: str) -> dict:
    """Place the persistent compile cache of a cold/warm child pair FROM
    OUTSIDE (the only way the repo places it): both children share
    ``<cache_dir>/xla``, every program is cached however fast it
    compiled, and the cache is on even on the CPU."""
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache_dir, "xla")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    return env


if os.environ.get(_MARK) != "1" and "pytest" in sys.modules:
    env = cpu_mesh_env(8)
    env[_MARK] = "1"
    env["JAX_ENABLE_X64"] = "1"  # float64 parity on the CPU test mesh
    os.execvpe(sys.executable, [sys.executable, "-m", "pytest"] + sys.argv[1:], env)
