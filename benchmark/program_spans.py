"""The program's own spans of the traced window, for the per-layer readers.

``alink_tpu/common/tracing.py`` records a span whenever a profiler session
is active, and marks every event it records then with ``profiled: true``.
The harness starts its profiler session at window start and stops it at
window end, so those events are exactly the traced window's. They are read
from the tracer's ring after the run (``run.py`` has deleted the trace
itself by the time the readers run); durations are on the host's
``perf_counter``, seconds here. A program that records no such span (the
tracer of an earlier commit stays off under the profiler) gives every
reader nothing to read, and each returns ``None``.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Sequence

Event = Dict[str, Any]


def window_events() -> List[Event]:
    """Complete spans the program recorded while the profiler ran."""
    from alink_tpu.common.tracing import get_tracer
    return [e for e in get_tracer().events()
            if e.get("profiled") and e.get("ph") == "X"]


def named(events: Sequence[Event], name: str) -> List[Event]:
    return [e for e in events if e["name"] == name]


def seconds(events: Sequence[Event], name: str) -> List[float]:
    """Durations of the spans called ``name``."""
    return [e.get("dur", 0.0) / 1e6 for e in named(events, name)]


def lower_quartile(values: Sequence[float]) -> Optional[float]:
    """The first quartile: of a two-humped list whose lower hump holds at
    least a quarter of the values, a value of the lower hump."""
    if not values:
        return None
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=4)[0]


def rows_encoded(events: Sequence[Event], batch_rows: int) -> int:
    """Rows of the micro-batches whose ``ftrl.encode`` is in ``events``
    (the span carries its micro-batch's rows)."""
    return sum(int((e.get("args") or {}).get("rows", batch_rows))
               for e in named(events, "ftrl.encode"))


def producer_seconds(events: Sequence[Event]) -> Optional[float]:
    """Mean host seconds a micro-batch costs the producer side: the
    source's pull, the encode and the ship, over the micro-batches
    encoded."""
    n = len(named(events, "ftrl.encode"))
    if not n:
        return None
    return sum(sum(seconds(events, name)) for name in
               ("prefetch.pull", "ftrl.encode", "ftrl.ship")) / n


def consumer_thread(events: Sequence[Event]) -> Optional[int]:
    """The thread that dispatched the steps."""
    tids = {e["tid"] for e in named(events, "ftrl.dispatch")}
    return tids.pop() if len(tids) == 1 else None
