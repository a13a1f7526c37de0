"""Render an alink_tpu metrics run report (JSONL) as summary tables.

Usage:
    python tools/run_report.py RUN_REPORT.jsonl [--prom] [--all]
                               [--trace TRACE.jsonl]
                               [--health HEALTH.json]

The input is a ``MetricsRegistry.dump()`` file (one JSON object per line;
written by ``registry.dump(path)``, by ``bench.py --metrics-out``, or by
any caller of ``alink_tpu.get_registry()``). Output sections:

  * Run summary      — execs, supersteps, program-cache hit rate;
  * Collectives      — per-collective invocation counts and logical bytes;
  * Host spans       — StepTimer spans (engine phases + user spans);
  * Stream           — per-op micro-batch throughput and latency;
  * Batch operators  — per-op wall time and rows in/out;
  * Everything else  — any counters/gauges/histograms not covered above
    (``--all`` prints the remainder even when a section claimed them).

``--prom`` prints the Prometheus exposition text instead of tables.
``--trace TRACE.jsonl`` appends the span-tracer summary (tools/trace.py)
for a flight-recorder export from the same run, so one report carries
both the aggregates and the timeline rollup. ``--health HEALTH.json``
appends the training-health summary (tools/health.py) for a
``HealthMonitor.save_report()`` file from the same run — aggregates,
timeline, and model health in one report.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from alink_tpu.common.metrics import MetricsRegistry  # noqa: E402


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:,.1f} {unit}" if unit != "B" else f"{int(n):,} B"
        n /= 1024.0
    return f"{n:,.1f} TiB"


def _table(headers: List[str], rows: List[List[str]],
           align_right: Optional[List[bool]] = None) -> str:
    if not rows:
        return "  (none)"
    ar = align_right or [False] + [True] * (len(headers) - 1)
    widths = [max(len(str(headers[i])), *(len(str(r[i])) for r in rows))
              for i in range(len(headers))]
    def fmt(cells):
        return "  " + "  ".join(
            str(c).rjust(widths[i]) if ar[i] else str(c).ljust(widths[i])
            for i, c in enumerate(cells)).rstrip()
    sep = "  " + "  ".join("-" * w for w in widths)
    return "\n".join([fmt(headers), sep] + [fmt(r) for r in rows])


def render(reg: MetricsRegistry, show_all: bool = False) -> str:
    snap = reg.snapshot()
    by_name: Dict[str, List[dict]] = {}
    for rec in snap:
        by_name.setdefault(rec["name"], []).append(rec)
    claimed = set()
    out: List[str] = []

    def val(name, labels=None):
        return reg.value(name, labels)

    # -- run summary ------------------------------------------------------
    execs = val("alink_comqueue_execs_total")
    steps = val("alink_comqueue_supersteps_total")
    hits = val("alink_comqueue_program_cache_total", {"result": "hit"})
    miss = val("alink_comqueue_program_cache_total", {"result": "miss"})
    claimed |= {"alink_comqueue_execs_total", "alink_comqueue_supersteps_total",
                "alink_comqueue_program_cache_total"}
    out.append("== Run summary ==")
    rows = [["comqueue execs", f"{int(execs):,}"],
            ["supersteps", f"{int(steps):,}"],
            ["program-cache hits", f"{int(hits):,}"],
            ["program-cache misses", f"{int(miss):,}"]]
    if hits + miss:
        rows.append(["cache hit rate", f"{100.0 * hits / (hits + miss):.1f}%"])
    if execs:
        rows.append(["supersteps / exec", f"{steps / execs:,.1f}"])
    out.append(_table(["metric", "value"], rows))

    # -- collectives ------------------------------------------------------
    out.append("\n== Collectives ==")
    crows = []
    calls = {r["labels"].get("collective", "?"): r["value"]
             for r in by_name.get("alink_collective_calls_total", [])}
    byts = {r["labels"].get("collective", "?"): r["value"]
            for r in by_name.get("alink_collective_logical_bytes_total", [])}
    claimed |= {"alink_collective_calls_total",
                "alink_collective_logical_bytes_total"}
    for kind in sorted(set(calls) | set(byts)):
        c = calls.get(kind, 0.0)
        b = byts.get(kind, 0.0)
        crows.append([kind, f"{int(c):,}", _fmt_bytes(b),
                      _fmt_bytes(b / c) if c else "-"])
    out.append(_table(["collective", "calls", "logical bytes", "bytes/call"],
                      crows))

    # -- host spans (StepTimer mirror) ------------------------------------
    out.append("\n== Host spans (StepTimer) ==")
    srows = []
    for rec in by_name.get("alink_step_timer_seconds", []):
        lbl = dict(rec["labels"])
        name = lbl.pop("span", "?")
        extra = ",".join(f"{k}={v}" for k, v in sorted(lbl.items()))
        cnt, total = rec["count"], rec["sum"]
        srows.append([name + (f" [{extra}]" if extra else ""),
                      f"{cnt:,}", f"{total:.3f}",
                      f"{total / cnt:.4f}" if cnt else "-"])
    claimed.add("alink_step_timer_seconds")
    srows.sort(key=lambda r: -float(r[2]))
    out.append(_table(["span", "count", "total_s", "mean_s"], srows))

    # -- stream -----------------------------------------------------------
    out.append("\n== Stream micro-batches ==")
    trows = []
    lat = {}
    for rec in by_name.get("alink_stream_batch_seconds", []):
        lat[rec["labels"].get("op", "?")] = rec
    batches = {r["labels"].get("op", "?"): r["value"]
               for r in by_name.get("alink_stream_batches_total", [])}
    rows_t = {r["labels"].get("op", "?"): r["value"]
              for r in by_name.get("alink_stream_rows_total", [])}
    claimed |= {"alink_stream_batch_seconds", "alink_stream_batches_total",
                "alink_stream_rows_total"}
    for op in sorted(set(lat) | set(batches) | set(rows_t)):
        rec = lat.get(op)
        n = batches.get(op, rec["count"] if rec else 0)
        rw = rows_t.get(op, 0)
        mean = (rec["sum"] / rec["count"]) if rec and rec["count"] else None
        trows.append([op, f"{int(n):,}", f"{int(rw):,}",
                      f"{1e3 * mean:.2f}" if mean is not None else "-",
                      f"{rw / rec['sum']:,.0f}"
                      if rec and rec["sum"] > 0 and rw else "-"])
    out.append(_table(["op", "batches", "rows", "mean ms/batch", "rows/s"],
                      trows))

    ftrl = [(n, by_name[n]) for n in sorted(by_name) if n.startswith("alink_ftrl_")]
    if ftrl:
        out.append("\n== FTRL ==")
        frows = []
        for name, recs in ftrl:
            claimed.add(name)
            for rec in recs:
                lbl = ",".join(f"{k}={v}" for k, v in
                               sorted(rec["labels"].items()))
                if rec["kind"] == "histogram":
                    v = (f"count={rec['count']:,} "
                         f"mean={1e3 * rec['sum'] / rec['count']:.2f}ms"
                         if rec["count"] else "count=0")
                else:
                    v = f"{rec['value']:,.6g}"
                frows.append([name, lbl, v])
        out.append(_table(["metric", "labels", "value"], frows,
                          align_right=[False, False, False]))

    # -- batch operators --------------------------------------------------
    out.append("\n== Batch operators ==")
    brows = []
    op_t = {r["labels"].get("op", "?"): r
            for r in by_name.get("alink_batch_op_seconds", [])}
    op_in = {r["labels"].get("op", "?"): r["value"]
             for r in by_name.get("alink_batch_rows_in_total", [])}
    op_out = {r["labels"].get("op", "?"): r["value"]
              for r in by_name.get("alink_batch_rows_out_total", [])}
    claimed |= {"alink_batch_op_seconds", "alink_batch_rows_in_total",
                "alink_batch_rows_out_total"}
    for op in sorted(set(op_t) | set(op_in) | set(op_out)):
        rec = op_t.get(op)
        cnt = rec["count"] if rec else 0
        total = rec["sum"] if rec else 0.0
        brows.append([op, f"{cnt:,}", f"{total:.3f}",
                      f"{int(op_in.get(op, 0)):,}",
                      f"{int(op_out.get(op, 0)):,}"])
    brows.sort(key=lambda r: -float(r[2]))
    out.append(_table(["op", "links", "total_s", "rows in", "rows out"],
                      brows))

    # -- remainder --------------------------------------------------------
    rest = [n for n in sorted(by_name) if show_all or n not in claimed]
    if rest:
        out.append("\n== Other metrics ==")
        rrows = []
        for name in rest:
            for rec in by_name[name]:
                lbl = ",".join(f"{k}={v}" for k, v in
                               sorted(rec["labels"].items()))
                if rec["kind"] == "histogram":
                    v = (f"count={rec['count']:,} sum={rec['sum']:.4g}"
                         if rec["count"] else "count=0")
                else:
                    v = f"{rec['value']:,.6g}"
                rrows.append([name, rec["kind"], lbl, v])
        out.append(_table(["metric", "kind", "labels", "value"], rrows,
                          align_right=[False, False, False, False]))
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Render an alink_tpu metrics JSONL run report")
    ap.add_argument("report", help="path to a MetricsRegistry.dump() JSONL")
    ap.add_argument("--prom", action="store_true",
                    help="print Prometheus exposition text instead of tables")
    ap.add_argument("--all", action="store_true",
                    help="also list section-claimed metrics under "
                         "'Other metrics'")
    ap.add_argument("--trace", metavar="TRACE",
                    help="append the span-trace summary for a "
                         "Tracer.export_jsonl()/export_chrome() file "
                         "from the same run")
    ap.add_argument("--health", metavar="HEALTH",
                    help="append the training-health summary for a "
                         "HealthMonitor.save_report() JSON from the "
                         "same run")
    args = ap.parse_args(argv)
    if os.path.isdir(args.report):
        # a bench.py --run-dir artifact directory: the metrics dump is
        # the report; sibling trace/health artifacts auto-attach unless
        # explicitly given. The measured profile has its own renderer
        # (tools/doctor.py) — point at it instead of half-rendering.
        d = args.report
        args.report = os.path.join(d, "metrics.jsonl")
        if not os.path.exists(args.report):
            print(f"run_report.py: {d}: no metrics.jsonl inside "
                  f"(not a bench --run-dir directory?)", file=sys.stderr)
            return 1
        for attr, fname in (("trace", "trace.jsonl"),
                            ("health", "health.json")):
            p = os.path.join(d, fname)
            if getattr(args, attr) is None and os.path.exists(p):
                setattr(args, attr, p)
        if not args.prom and os.path.exists(os.path.join(d, "profile.json")):
            print(f"(measured profile present — render it with: "
                  f"python tools/doctor.py --run-dir {d})")
    reg = MetricsRegistry.load(args.report)
    if args.prom:
        sys.stdout.write(reg.render_text())
    else:
        print(render(reg, show_all=args.all))
    if args.trace and not args.prom:
        # never appended in --prom mode: the exposition text on stdout
        # must stay parseable by Prometheus scrapers
        trace_mod = _load_sibling_tool("trace")
        meta, events = trace_mod.load_events(args.trace)
        print()
        print(trace_mod.summarize(meta, events))
    if args.health and not args.prom:
        health_mod = _load_sibling_tool("health")
        from alink_tpu.common.health import HealthMonitor
        print()
        print(health_mod.render(HealthMonitor.load_report(args.health)))
    return 0


def _load_sibling_tool(name: str):
    """Import a sibling tools/*.py module (tools/ is not a package)."""
    import importlib.util
    p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"alink_tpu_tool_{name}", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


if __name__ == "__main__":
    raise SystemExit(main())
