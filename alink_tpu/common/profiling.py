"""Tracing / profiling utilities.

The reference has no purpose-built profiler: it threads slf4j logs with
taskId/stepNo through hot paths (communication/AllReduce.java:208-261,
kmeans/KMeansAssignCluster.java:30-33) and relies on the Flink web UI for
operator-level metrics — every dataflow stage is ``.name()``d so the UI can
attribute time (comqueue/BaseComQueue.java:172-195).

The TPU build's equivalents (SURVEY §5):

  * **stage naming** — every engine stage runs under ``jax.named_scope``,
    so XLA op metadata and profiler traces attribute device time to the
    algorithm stage (CalcGradient / AllReduce / UpdateModel ...), exactly
    what the Flink UI gave the reference;
  * **device traces** — ``trace(log_dir)`` wraps ``jax.profiler`` for
    XProf/TensorBoard-compatible traces of compiled programs;
  * **host step timer** — ``StepTimer`` accumulates named wall-clock spans
    (graph build, compile+execute, host IO) for coarse driver-side
    attribution;
  * **superstep logging** — set ``ALINK_TPU_STEP_LOG=1`` to emit a host
    callback log line per superstep from inside the compiled while-loop
    (the slf4j taskId/stepNo analogue; works under jit);
  * **metrics mirror** — every ``StepTimer.span`` exit also lands in the
    process ``MetricsRegistry`` (common/metrics.py) as one
    ``alink_step_timer_seconds`` histogram observation labelled by span
    name, so a single ``registry.dump()`` captures host spans next to
    engine/collective/stream counters.

Environment flags (parsed by ``common.metrics.env_flag``: unset uses the
default, ``0``/``false``/``off``/``no`` disable, anything else enables):

  * ``ALINK_TPU_STEP_LOG`` — default off. Per-superstep ``jax.debug.print``
    from inside compiled loops. Changes the compiled program, so it also
    participates in the engine's program-cache key.
  * ``ALINK_TPU_METRICS``  — default on. Master switch for every
    ``MetricsRegistry`` producer, including the span mirror here; hot
    paths skip all registry updates when disabled.
  * ``ALINK_TPU_TRACE``    — default off. When enabled, every
    ``StepTimer.span`` additionally opens a span on the process tracer
    (``common/tracing.py``), so StepTimer call sites land in the trace
    timeline with correct parent/child nesting and need no second
    instrumentation of their own.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

from .metrics import env_flag, get_registry, metrics_enabled
from .tracing import trace_span

__all__ = ["StepTimer", "named_stage", "trace", "step_log_enabled",
           "log_superstep"]


def named_stage(name: str):
    """Name a compiled region (the reference's dataflow ``.name()`` idiom).

    Returns a context manager; ops traced inside carry ``name`` in their
    HLO metadata, so profiler traces and compiler dumps attribute device
    time per algorithm stage.
    """
    import jax
    return jax.named_scope(name)


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a device/host profiler trace into ``log_dir``.

    View with XProf / TensorBoard's profile plugin. Wraps
    ``jax.profiler.trace`` so callers don't import jax directly.
    """
    import jax
    with jax.profiler.trace(str(log_dir)):
        yield


def step_log_enabled() -> bool:
    """``ALINK_TPU_STEP_LOG`` flag — unset/``0``/``false``/``off`` all
    disable (the old parser enabled on any non-empty string except "0",
    so ``ALINK_TPU_STEP_LOG=false`` silently turned logging ON)."""
    return env_flag("ALINK_TPU_STEP_LOG", default=False)


def log_superstep(step, **values):
    """Per-superstep log line from inside a compiled loop (jit-safe).

    The reference logs taskId/stepNo via slf4j in every hot stage; here one
    ``jax.debug.print`` per superstep reports the step counter plus any
    scalar carry values handed in. No-op unless ``ALINK_TPU_STEP_LOG=1``.
    """
    if not step_log_enabled():
        return
    import jax
    fmt = "superstep {step}" + "".join(f" {k}={{{k}}}" for k in values)
    jax.debug.print(fmt, step=step, **values)


@dataclass
class _Span:
    count: int = 0
    total_s: float = 0.0


@dataclass
class StepTimer:
    """Host-side named wall-clock accumulator.

    >>> t = StepTimer()
    >>> with t.span("fit"):
    ...     train()
    >>> t.report()
    [("fit", 1, 0.93, 0.93)]

    Spans nest freely; each name accumulates (count, total seconds).
    ``jax`` work is asynchronous — wrap the span around a blocking call
    (``collect()``/``block_until_ready``) for meaningful device timings.

    Thread-safe: streams and the bench enter ``span()`` from prefetch /
    generator threads concurrently with the driver thread; accumulation
    is guarded by one lock per timer. Unless ``mirror=False`` (or
    ``ALINK_TPU_METRICS=0``), every span exit is also observed into the
    process ``MetricsRegistry`` as ``alink_step_timer_seconds`` labelled
    ``{span: name}`` plus any ``labels=`` passed through.
    """
    _spans: Dict[str, _Span] = field(default_factory=dict)
    _order: List[str] = field(default_factory=list)
    mirror: bool = True
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    METRIC = "alink_step_timer_seconds"

    @contextlib.contextmanager
    def span(self, name: str, labels: Optional[Dict[str, str]] = None,
             coarse: bool = False) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            # single source of truth: the same span also lands on the
            # process tracer (nested via contextvars; always where the
            # call site says ``coarse``, else under ALINK_TPU_TRACE), so
            # StepTimer call sites never need double-instrumentation
            with trace_span(name, cat="steptimer", args=labels,
                            coarse=coarse):
                yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                if name not in self._spans:
                    self._spans[name] = _Span()
                    self._order.append(name)
                s = self._spans[name]
                s.count += 1
                s.total_s += dt
            if self.mirror and metrics_enabled():
                merged = {"span": name}
                if labels:
                    merged.update(labels)
                get_registry().observe(self.METRIC, dt, merged)

    def report(self) -> List[Tuple[str, int, float, float]]:
        """[(name, count, total_s, mean_s)] in first-seen order."""
        with self._lock:
            return [(n, s.count, s.total_s, s.total_s / s.count)
                    for n, s in ((n, self._spans[n]) for n in self._order)]

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._order.clear()

    def pretty(self) -> str:
        rows = self.report()
        if not rows:
            return "(no spans recorded)"
        w = max(len(n) for n, *_ in rows)
        lines = [f"{'stage'.ljust(w)}  count   total_s    mean_s"]
        for n, c, tot, mean in rows:
            lines.append(f"{n.ljust(w)}  {c:5d}  {tot:8.3f}  {mean:8.4f}")
        return "\n".join(lines)

    def __str__(self) -> str:
        return self.pretty()
