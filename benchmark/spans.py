"""Host spans of the benchmark's own, on the profiler's clock.

``Spans.span(name)`` times a block on the host clock and, while a trace is
being taken, also writes a ``jax.profiler.TraceAnnotation`` named
``bench:<name>`` into the profiler's trace, so the trace reduction can lay
the spans over the device's idle gaps on one clock. Thread-safe: each
append is one list operation.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Tuple

PREFIX = "bench:"


class Spans:
    def __init__(self) -> None:
        self.records: List[Tuple[str, float, float]] = []   # name, t0, t1
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        if self.tracing:
            import jax
            mark = jax.profiler.TraceAnnotation(PREFIX + name)
        else:
            mark = contextlib.nullcontext()
        with mark:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def totals(self) -> Dict[str, Tuple[int, float]]:
        """name -> (count, summed seconds)."""
        out: Dict[str, Tuple[int, float]] = {}
        for name, t0, t1 in list(self.records):
            c, secs = out.get(name, (0, 0.0))
            out[name] = (c + 1, secs + (t1 - t0))
        return out
