"""The program's coarse spans, for the readers of set-up and of the host
between two programs.

Since ISSUE 35 ``alink_tpu/common/tracing.py`` records its COARSE spans in
every process, traced or not: ``session.start``, JAX's own ``jit.trace`` /
``jit.lower`` / ``jit.compile`` (retroactive events, children of the span
open on the compiling thread; a function traced inside another's trace
reports too, so these nest by their intervals), the FTRL trainer's
``ftrl.link`` family, an operator's ``*.fit`` with its children, and the
engine's ``comqueue.*`` with ``comqueue.wait``. So the tracer's ring holds
the process's set-up, which the profiler's session (window start to window
end) never sees.

**Where set-up ends.** The window starts at ``ctx.started +
ctx.e2e["setup_s"]`` on the unix clock: the harness takes exactly that
``time.time()`` in ``begin_window``. An event's ``ts`` is microseconds
from the tracer's origin, whose unix time the tracer gives
(``origin_unix``); an event that ENDED at or before the window's start is
set-up's. (The first ``profiled`` event would do only in a traced run,
and lies a profiler start-up later.)

**When there is nothing to read** every function here gives ``None`` and
each reader returns ``None``: the ring dropped events (its oldest are the
ones set-up needs), the tracer has no ``origin_unix`` (an older program),
or the ring does not hold the process's FIRST ``session.start`` (args
``session`` 0), which ends the boot and anchors the account: a process
whose first session began before this ring did (a test process, a tracer
swapped in later) has no set-up to account for.

Times are seconds on the host's ``perf_counter``.
"""

from __future__ import annotations

from typing import (Any, Dict, Iterable, Iterator, List, NamedTuple, Optional,
                    Tuple)

from .trace_reduce import length, union

Event = Dict[str, Any]
Interval = Tuple[float, float]

BOOT = "session.start"
JIT_TRACE = ("jit.trace", "jit.lower")
JIT_COMPILE = ("jit.compile",)
JIT = JIT_TRACE + JIT_COMPILE
FTRL_STATE = ("ftrl.link", "ftrl.warm_hash", "ftrl.state_alloc",
              "ftrl.state_ship")
FIT_SUFFIX = ".fit"
WAIT = "comqueue.wait"


def ring() -> Tuple[List[Event], int, Optional[float]]:
    """The process tracer's complete spans, how many events it dropped,
    and the unix time of ``ts`` 0 (``None``: an older program's tracer)."""
    from alink_tpu.common.tracing import get_tracer
    tracer = get_tracer()
    return ([e for e in tracer.events() if e.get("ph") == "X"],
            tracer.dropped, getattr(tracer, "origin_unix", None))


def interval(ev: Event) -> Interval:
    return (ev["ts"] / 1e6, (ev["ts"] + ev.get("dur", 0.0)) / 1e6)


def clipped(intervals: Iterable[Interval], lo: float,
            hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def less(intervals: Iterable[Interval],
         holes: Iterable[Interval]) -> float:
    """Seconds of ``intervals``' union that ``holes`` do not cover."""
    whole, holes = union(intervals), list(holes)
    inside = []
    for a, b in whole:
        inside += clipped(holes, a, b)
    return length(whole) - length(union(inside))


def ancestors(by_id: Dict[int, Event], ev: Event) -> Iterator[Event]:
    """``ev``'s parent, its parent's parent, ... as far as the ring has
    them."""
    at = by_id.get(ev.get("parent"))
    while at is not None:
        yield at
        at = by_id.get(at.get("parent"))


def fits_and_descendants(events: List[Event]
                         ) -> List[Tuple[Event, List[Event]]]:
    """The ``*.fit`` spans that lie in no other ``*.fit`` span, each with
    the events under it."""
    by_id = {e["id"]: e for e in events if "id" in e}
    under: Dict[int, List[Event]] = {}
    roots = []
    for e in events:
        chain = [e] + list(ancestors(by_id, e))
        fits = [a for a in chain if a["name"].endswith(FIT_SUFFIX)]
        if not fits:
            continue
        if fits[-1] is e:
            roots.append(e)
        else:
            under.setdefault(fits[-1]["id"], []).append(e)
    return [(f, under.get(f["id"], [])) for f in roots]


def self_seconds(events: List[Event], ev: Event) -> float:
    """``ev``'s duration less what its direct children cover."""
    span = interval(ev)
    children = [interval(e) for e in events
                if e.get("parent", -1) == ev.get("id")]
    return less([span], clipped(children, *span))


class Setup(NamedTuple):
    """The ring's events that ended before the window, on one clock."""
    events: List[Event]
    boot_end: float          # seconds from the tracer's origin
    window_at: float         # seconds from the tracer's origin
    origin_unix: float

    def jit_seconds(self, names: Tuple[str, ...],
                    but_not: Tuple[str, ...] = ()) -> float:
        """Seconds between the boot's end and the window's start that
        events called ``names`` cover, counted once where they nest, less
        what events called ``but_not`` cover inside them."""
        def of(which):
            return clipped((interval(e) for e in self.events
                            if e["name"] in which),
                           self.boot_end, self.window_at)
        return less(of(names), of(but_not))


def before_window(ctx) -> Optional[Setup]:
    events, dropped, origin = ring()
    setup_s = getattr(ctx, "e2e", {}).get("setup_s")
    if dropped or origin is None or setup_s is None:
        return None
    window_at = ctx.started + setup_s - origin
    mine = [e for e in events if interval(e)[1] <= window_at]
    boots = [e for e in mine if e["name"] == BOOT
             and (e.get("args") or {}).get("session") == 0]
    if not boots:
        return None
    return Setup(mine, interval(boots[0])[1], window_at, origin)


def in_window() -> Optional[List[Event]]:
    """The spans recorded while the profiler ran (``program_spans``' rule:
    they carry ``profiled``), where the ring dropped nothing."""
    events, dropped, _ = ring()
    if dropped:
        return None
    return [e for e in events if e.get("profiled")]


def fit_less(events: List[Event], names: Tuple[str, ...]) -> List[float]:
    """For each root ``*.fit`` span of ``events``: its seconds less what
    the events under it called ``names`` cover."""
    out = []
    for fit, under in fits_and_descendants(events):
        span = interval(fit)
        out.append(less([span], clipped(
            (interval(e) for e in under if e["name"] in names), *span)))
    return out
