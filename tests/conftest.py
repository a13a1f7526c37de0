"""Test harness: force an 8-device virtual CPU mesh.

Mirrors the reference's "distributed without a cluster" strategy (SURVEY §4):
Alink tests run on a Flink local mini-cluster whose parallel subtasks are
threads in one JVM; we run on 8 virtual CPU devices in one process
(``--xla_force_host_platform_device_count=8``), so collectives, supersteps
and sharding get real multi-worker semantics.

XLA flags are latched at backend init — so the process is re-exec'd with the
CPU-mesh environment by the early plugin ``bootenv.py`` (repo root, loaded
via pytest.ini ``addopts = -p bootenv`` before fd capture starts).
"""

import numpy as np
import pytest


@pytest.fixture(scope="session", autouse=True)
def _default_env():
    import jax
    assert len(jax.devices()) == 8, f"expected 8 CPU devices, got {jax.devices()}"
    from alink_tpu.common.mlenv import MLEnvironmentFactory, use_local_env
    use_local_env(parallelism=8)
    yield
    MLEnvironmentFactory.reset()


@pytest.fixture
def rng():
    return np.random.RandomState(2026)


@pytest.fixture
def quiet_tracer(monkeypatch):
    """A tracer of the test's own with ``ALINK_TPU_TRACE`` unset: only the
    test (the flag, a profiler session) makes the call-site helpers
    record."""
    from alink_tpu.common.tracing import Tracer, set_tracer
    monkeypatch.delenv("ALINK_TPU_TRACE", raising=False)
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        yield tr
    finally:
        set_tracer(prev)
