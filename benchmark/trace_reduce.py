"""From a profiler trace (``.xplane.pb``) to busy/idle, op times and gaps.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else. The
device's planes are those named ``/device:TPU:<n>``; on each, the line
``XLA Ops`` holds one event per executed HLO operation and ``XLA Modules``
one per executed program. Host threads sit on planes named ``/host:...``;
the benchmark's own spans are the events there whose name starts with
``bench:`` (``benchmark/spans.py``). All times are nanoseconds on the
profiler's one clock.

* busy: the union of the op intervals inside the window, per device,
  averaged over the devices used; idle share is 1 - busy / window.
* op sums: summed duration by op name (a ``while`` spans its body's ops,
  so sums overlap; the union does not).
* gaps: the window minus the union, each piece charged to the benchmark
  span that was open on the host at that time (the one that began last,
  where several are), to ``_no_span_`` where none was, and to
  ``_under_<n>us_`` wholesale where the gap is shorter than ``min_gap_ns``:
  those are the device's own pauses between ops, not the host's doing.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]           # start_ns, end_ns

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
NO_SPAN = "_no_span_"


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(plane, line_name: str) -> List[Tuple[str, int, int]]:
    out = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            start = int(ev.start_ns)
            out.append((short_name(ev.name), start,
                        start + int(ev.duration_ns)))
    return out


def short_name(name: str) -> str:
    """An op event carries its whole HLO line (``%fusion.75 = f32[...]
    fusion(...)``); the op's own name is what stands before `` = ``."""
    return name.split(" = ", 1)[0].lstrip("%")


def device_planes(profile) -> List:
    """Planes of the chips themselves (not their SparseCore or host
    offload companions, whose names carry a further word)."""
    out = []
    for plane in profile.planes:
        name = plane.name
        if name.startswith(DEVICE_PLANE) and name[len(DEVICE_PLANE):].isdigit():
            out.append(plane)
    return out


def host_spans(profile, prefix: str = SPAN_PREFIX) -> List[Tuple[str, int, int]]:
    out = []
    for plane in profile.planes:
        if not plane.name.startswith(HOST_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    start = int(ev.start_ns)
                    out.append((ev.name, start, start + int(ev.duration_ns)))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint sorted intervals covering the same points."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def complement(disjoint: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, at = [], lo
    for a, b in disjoint:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def charge_gaps(gaps: Sequence[Interval], spans: Sequence[Tuple[str, int, int]],
                min_gap_ns: int = 10_000) -> Dict[str, int]:
    """Nanoseconds of ``gaps`` by the span open at the time."""
    out: Dict[str, int] = {}
    small = f"_under_{min_gap_ns // 1000}us_"
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    longest = max((s[2] - s[1] for s in spans), default=0)
    for a, b in gaps:
        if b - a < min_gap_ns:
            out[small] = out.get(small, 0) + (b - a)
            continue
        # spans that can touch [a, b): began before b, and no earlier
        # than a less the longest span
        lo = bisect.bisect_left(starts, a - longest)
        hi = bisect.bisect_left(starts, b)
        cuts = {a, b}
        near = [s for s in spans[lo:hi] if s[2] > a]
        for _, s0, s1 in near:
            if a < s0 < b:
                cuts.add(s0)
            if a < s1 < b:
                cuts.add(s1)
        edges = sorted(cuts)
        for p, q in zip(edges, edges[1:]):
            mid = (p + q) // 2
            owner, began = NO_SPAN, None
            for name, s0, s1 in near:
                if s0 <= mid < s1 and (began is None or s0 >= began):
                    owner, began = name[len(SPAN_PREFIX):] \
                        if name.startswith(SPAN_PREFIX) else name, s0
            out[owner] = out.get(owner, 0) + (q - p)
    return out


def reduce_profile(profile, window: Optional[Interval] = None,
                   devices: Optional[int] = None, top: int = 10,
                   min_gap_ns: int = 10_000) -> Dict:
    """The whole reduction. ``window`` defaults to the ``bench:window``
    span; ``devices`` to every device plane that ran an op."""
    spans = host_spans(profile)
    if window is None:
        win = [s for s in spans if s[0] == WINDOW_SPAN]
        if not win:
            raise ValueError("the trace holds no bench:window span")
        window = (min(s[1] for s in win), max(s[2] for s in win))
    lo, hi = window
    spans = [s for s in spans if s[0] != WINDOW_SPAN]
    planes = device_planes(profile)
    per_device = []
    op_ns: Dict[str, int] = {}
    op_calls: Dict[str, int] = {}
    module_ns: Dict[str, int] = {}
    module_calls: Dict[str, int] = {}
    gap_ns: Dict[str, int] = {}
    for plane in planes:
        ops = [(n, max(a, lo), min(b, hi)) for n, a, b in _events(plane, OPS_LINE)
               if min(b, hi) > max(a, lo)]
        if not ops:
            continue
        busy = union((a, b) for _, a, b in ops)
        per_device.append(length(busy))
        for n, a, b in ops:
            op_ns[n] = op_ns.get(n, 0) + (b - a)
            op_calls[n] = op_calls.get(n, 0) + 1
        for n, a, b in _events(plane, MODULES_LINE):
            if min(b, hi) > max(a, lo):
                module_ns[n] = module_ns.get(n, 0) + (min(b, hi) - max(a, lo))
                module_calls[n] = module_calls.get(n, 0) + 1
        for k, v in charge_gaps(complement(busy, lo, hi), spans,
                                min_gap_ns).items():
            gap_ns[k] = gap_ns.get(k, 0) + v
    used = len(per_device) if devices is None else devices
    if used == 0:
        raise ValueError("no operation ran on a device inside the window")
    busy_s = sum(per_device) / used / 1e9
    ranked = sorted(op_ns.items(), key=lambda kv: -kv[1])
    gaps = sorted(gap_ns.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "devices": used,
        "op_s": {n: v / used / 1e9 for n, v in op_ns.items()},
        "op_calls": op_calls,
        "module_s": {n: v / used / 1e9 for n, v in module_ns.items()},
        "module_calls": module_calls,
        "gap_s": {n: v / used / 1e9 for n, v in gap_ns.items()},
        "device_ops": [[n, v / used / 1e9] for n, v in ranked[:top]],
        "idle_gaps": [[n, v / used / 1e9] for n, v in gaps[:top]],
    }


def module_time(reduced: Dict, stem: str) -> Tuple[float, int]:
    """(seconds, calls) of the programs whose name starts with ``stem``:
    XLA names a module ``<stem>(<fingerprint>)`` or ``<stem>.<n>``."""
    secs, calls = 0.0, 0
    for name, s in reduced["module_s"].items():
        base = name.split("(")[0]
        if base == stem or base.startswith(stem + "."):
            secs += s
            calls += reduced["module_calls"][name]
    return secs, calls
