"""The Pallas kernel tier's availability/demotion contract (ISSUE 13).

Every hand-written kernel in ``alink_tpu/kernels/`` rides the SAME
contract PR 6's fused-histogram accumulator proved out (and whose
check/warn machinery used to live inlined in
``operator/common/tree/hist.py`` — deduped here):

* **availability** — a Pallas kernel runs when the backend can execute
  it: a real TPU, or any backend with ``ALINK_TPU_PALLAS_INTERPRET=1``
  (the CPU tier-1 rig's mode: ``pl.pallas_call(interpret=True)``
  executes the kernel with jnp semantics, so parity tests run without
  hardware);
* **demotion off the chip, refusal on it** — off-TPU, when a requested
  kernel cannot run (backend unavailable, interpreter or trace
  failure), the call site demotes to its XLA formulation with ONE
  RuntimeWarning per (kernel, reason) per process. A demoted run is
  always numerically valid — the XLA path is the reference the kernel
  is parity-pinned against — but it must never be *silently* slower.
  On a TPU backend a requested kernel that Mosaic refuses RAISES
  (:func:`refuse_on_tpu`): a chip run never executes the XLA path
  under the kernel's name;
* **flag-off byte-identity** — with the gating flag off, the call site
  executes its pre-existing statements verbatim: the lowered HLO is
  byte-identical to pre-kernel-tier programs (pinned per flag by the
  tests), so the tier contributes ZERO risk to anyone who does not opt
  in;
* **eager probing** — ``pl.pallas_call`` only *stages* the primitive at
  trace time; a Mosaic failure would otherwise surface at the engine's
  compile, outside any try/except around the traced call.
  :func:`eager_probe` compiles+runs a tiny instance of the kernel in a
  genuinely eager context (a fresh thread — jax trace contexts are
  thread-local) once per shape class, so compile-time failures are
  handled exactly like trace-time ones.
"""

from __future__ import annotations

import warnings as _warnings
from typing import Callable, Dict, Tuple

__all__ = ["pallas_interpret", "pallas_available", "interpret_mode",
           "demote_once", "refuse_on_tpu", "eager_probe",
           "reset_demotions"]


def pallas_interpret() -> bool:
    """``ALINK_TPU_PALLAS_INTERPRET``: run Pallas kernels in interpret
    mode off-TPU (tests/CI). Key-neutral by registry declaration: only
    the RESOLVED kernel mode reaches any cache key."""
    from ..common.flags import flag_value
    return bool(flag_value("ALINK_TPU_PALLAS_INTERPRET", False))


def pallas_available() -> bool:
    """Can this process execute a Pallas kernel right now? True on a
    TPU backend, or anywhere under ``ALINK_TPU_PALLAS_INTERPRET=1``."""
    import jax
    return jax.default_backend() == "tpu" or pallas_interpret()


def interpret_mode() -> bool:
    """The ``interpret=`` argument every kernel passes to
    ``pl.pallas_call``: interpret everywhere except a real TPU."""
    import jax
    return jax.default_backend() != "tpu"


# one warning per (kernel, reason-class) per process — a drain that
# dispatches 10k micro-batches must not emit 10k demotion warnings,
# but the FIRST demotion of each kernel must always be visible
_DEMOTION_WARNED: Dict[Tuple[str, str], bool] = {}


def demote_once(kernel: str, reason: str, detail: str = "",
                message: str = None, gate=None) -> None:
    """Record one kernel demotion: ONE RuntimeWarning per
    ``(kernel, reason)`` pair per process, plus an
    ``alink_kernel_demotions_total{kernel=,reason=}`` counter on every
    call. ``reason`` must be a small stable enum (it is a metric
    label); request-specific text goes in ``detail``.

    ``message`` overrides the default warning text (the fused-hist
    kernel keeps its historical, test-pinned wording); ``gate`` — a
    mutable ``[bool]`` cell — overrides the module-global once-per-
    (kernel, reason) memo for call sites that own their warn state
    (hist.py's ``_PALLAS_WARNED``, which tests monkeypatch to re-arm).
    """
    from ..common.metrics import get_registry, metrics_enabled
    if metrics_enabled():
        get_registry().inc("alink_kernel_demotions_total", 1,
                           {"kernel": kernel, "reason": reason})
    if gate is not None:
        if gate[0]:
            return
        gate[0] = True
    else:
        key = (kernel, reason)
        if _DEMOTION_WARNED.get(key):
            return
        _DEMOTION_WARNED[key] = True
    _warnings.warn(
        message or (
            f"Pallas kernel {kernel!r} demoted to its XLA path: {reason}"
            f"{' (' + detail + ')' if detail else ''} — results are "
            f"unchanged (the XLA path is the parity reference) but the "
            f"kernel-tier speedup is lost; this warning fires once per "
            f"kernel+reason (recorded as alink_kernel_demotions_total"
            f"{{kernel={kernel!r},reason={reason!r}}})"),
        RuntimeWarning, stacklevel=3)


def refuse_on_tpu(kernel: str, detail: str, cause: BaseException) -> None:
    """Raise when a REQUESTED kernel failed on a TPU backend; return
    (so the caller demotes) anywhere else. The demotion contract exists
    for rigs that cannot run Mosaic at all; on the chip a refused
    kernel is a fact the run must report, not an XLA run labelled with
    the kernel's name."""
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"Pallas kernel {kernel!r} was requested and failed on TPU "
            f"({detail}): {type(cause).__name__}: {cause}") from cause


def reset_demotions() -> None:
    """Test hook: re-arm the once-per-(kernel, reason) warnings."""
    _DEMOTION_WARNED.clear()


def run_eagerly(probe: Callable[[], None]) -> None:
    """Execute ``probe`` in a genuinely eager context.

    jax trace contexts are THREAD-LOCAL: kernel call sites usually sit
    inside a jit/shard_map trace, where even concrete-input
    pallas_calls bind as tracers. A fresh thread is outside every
    trace, so the probe really compiles+runs the kernel here and now
    (the hist.py probe trick, deduped)."""
    import concurrent.futures
    with concurrent.futures.ThreadPoolExecutor(1) as ex:
        ex.submit(probe).result()


# (kernel name, shape-class key) -> bool (compiled+ran ok)
_PROBED: Dict[Tuple, bool] = {}


def eager_probe(kernel: str, key: Tuple, probe: Callable[[], None]) -> bool:
    """EAGERLY compile+run ``probe`` (a tiny instance of the kernel at
    this call's shape class) before the kernel is traced into a
    compiled program. One probe per (kernel, shape class) per process;
    off-TPU a probe failure demotes via :func:`demote_once` and is
    memoized so the XLA path is chosen at trace time from then on; on
    TPU it raises (:func:`refuse_on_tpu`).

    ``pl.pallas_call`` only stages the primitive at trace time — a
    Mosaic failure would otherwise surface at the engine's compile,
    outside any try/except around the traced call. The eager probe is
    what puts compile-time failures (VMEM overflow, lane-alignment
    rejections) under the same contract as trace-time ones."""
    memo_key = (kernel,) + tuple(key)
    ok = _PROBED.get(memo_key)
    if ok is None:
        try:
            run_eagerly(probe)
            ok = True
        except Exception as e:  # pragma: no cover - backend-specific
            refuse_on_tpu(kernel, f"probe at shape class {key}", e)
            ok = False
            demote_once(kernel, "probe-failed",
                        f"shape class {key}: {type(e).__name__}: {e}")
        _PROBED[memo_key] = ok
    return ok
