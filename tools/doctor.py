#!/usr/bin/env python
"""Perf doctor — one per-workload verdict from the measured artifacts.

Merges a bench capture (``bench.py --quick``/full, ideally under
``ALINK_TPU_PROFILE=1``), the exported measured profile
(``common/profiling2.py``), and optionally the metrics dump into one
diagnosis per workload:

  * the MEASURED wall-time attribution (host dispatch / H2D-D2H
    transfer / device compute / collective / unattributed host) and the
    measured ``bound:`` classification next to the static projection
    (``bound_static``);
  * measured achieved FLOP/s and bytes/s against the rig roof,
    device-time-normalized (what the kernels sustain while the device
    is actually busy, not diluted by dispatch gaps);
  * a top-3 "what to fix" attribution ranked by wall-share;
  * a live-HBM section: ``alink_hbm_live_bytes`` boundary snapshots plus
    the measured donation verification (does buffer donation actually
    halve resident carry state on this rig — PR 5's claim, measured).

Usage:
    python tools/doctor.py --run-dir DIR            # bench.py --run-dir output
    python tools/doctor.py --bench BENCH_quick.json [--profile PROFILE.json]
                           [--metrics METRICS.jsonl]
    python tools/doctor.py --url http://host:port   # LIVE admin endpoint
    python tools/doctor.py --url FLEETZ_SNAPSHOT_DIR
    ... [--json]

``--url`` (ISSUE 16) points the same metrics verdict at a RUNNING
process — an ``ALINK_TPU_ADMIN_PORT`` admin endpoint's ``/varz`` (the
dump-file record shape served live) — or at a ``tools/fleetz.py
--snapshot`` directory, merging every archived worker's records.

Exit codes: 0 — artifacts parsed and verdicts rendered; 1 — no usable
input. The doctor never gates (that is bench_compare --threshold's job);
it explains.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

# default chip roofs when neither the bench rig section nor the CLI
# provides them (v5e: bf16 MXU peak / HBM stream) — keep in sync with
# bench.PEAK_TFLOPS / PEAK_HBM_GBPS
DEFAULT_PEAK_TFLOPS = 197.0
DEFAULT_PEAK_HBM_GBPS = 819.0

_BAR = "█"
_BUCKET_ORDER = ("dispatch", "device", "transfer", "collective", "host")
_BUCKET_LABELS = {"dispatch": "host dispatch", "device": "device compute",
                  "transfer": "transfer (H2D/D2H)", "collective":
                  "collective", "host": "host/other (unattributed)"}


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:,.1f} {unit}" if unit != "B" else f"{int(n):,} B"
        n /= 1024.0
    return f"{n:,.1f} TiB"


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_bench(path: str) -> Dict[str, Any]:
    """A bench dump in any of its shapes (driver ``{"parsed": ...}``
    wrapper, ``--out``/``--run-dir`` object). Returns the inner doc."""
    doc = load_json(path)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a bench dump")
    return doc


def _metrics_summary(path: str) -> Dict[str, Any]:
    """The handful of registry aggregates the verdict cites (program
    cache, collectives, live-HBM gauges, serving counters) from a
    MetricsRegistry dump."""
    records: List[dict] = []
    with open(path) as f:
        for ln in f:
            ln = ln.strip()
            if not ln:
                continue
            try:
                records.append(json.loads(ln))
            except ValueError:
                continue
    return _summarize_metric_records(records)


def _records_from_url(url: str) -> List[dict]:
    """Metric records from a LIVE admin endpoint's ``/varz`` (JSON
    array, dump-record shape) or a fleetz ``--snapshot`` directory
    (every ``varz.json`` under it, merged — the fleet's union)."""
    if os.path.isdir(url):
        import glob
        paths = sorted(glob.glob(os.path.join(url, "varz.json"))
                       + glob.glob(os.path.join(url, "*", "varz.json")))
        if not paths:
            raise ValueError(f"{url}: no varz.json under it — not a "
                             f"fleetz snapshot directory")
        records: List[dict] = []
        for p in paths:
            doc = load_json(p)
            if not isinstance(doc, list):
                raise ValueError(f"{p}: not a /varz record array")
            records.extend(r for r in doc if isinstance(r, dict))
        return records
    import urllib.request
    if "://" not in url:
        url = f"http://{url}"
    with urllib.request.urlopen(f"{url.rstrip('/')}/varz",
                                timeout=10) as r:
        doc = json.loads(r.read())
    if not isinstance(doc, list):
        raise ValueError(f"{url}/varz: not a record array")
    return [r for r in doc if isinstance(r, dict)]


def _compilez_from_url(url: str) -> List[Tuple[str, Any]]:
    """``/compilez`` documents from a live admin endpoint or a fleetz
    ``--snapshot`` directory — tolerant of workers predating the
    endpoint (404 / missing file simply contributes nothing, the same
    mixed-fleet contract as fleetz's tracez/requestz scrape)."""
    out: List[Tuple[str, Any]] = []
    if os.path.isdir(url):
        import glob
        for p in sorted(glob.glob(os.path.join(url, "compilez.json"))
                        + glob.glob(os.path.join(url, "*",
                                                 "compilez.json"))):
            try:
                out.append((os.path.basename(os.path.dirname(p))
                            or "snapshot", load_json(p)))
            except (OSError, ValueError):
                pass
        return out
    import urllib.request
    if "://" not in url:
        url = f"http://{url}"
    try:
        with urllib.request.urlopen(f"{url.rstrip('/')}/compilez",
                                    timeout=10) as r:
            out.append((url, json.loads(r.read())))
    except Exception:
        pass
    return out


def _hist_p99(rec: Dict[str, Any]
              ) -> Tuple[Optional[float], Optional[dict]]:
    """(p99 upper-bound estimate, that bucket's exemplar) from one
    histogram snapshot record. The exemplar falls back to the nearest
    LOWER bucket that caught one (the reqtrace.p99_exemplar contract)
    so a tail bucket whose slot was never hit still resolves to a real
    request."""
    counts = rec.get("counts") or []
    total = sum(counts)
    if not total:
        return None, None
    bounds = rec.get("buckets") or []
    exemplars = rec.get("exemplars") or []
    target = 0.99 * total
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if cum >= target:
            est = (bounds[i] if i < len(bounds)
                   else (bounds[-1] if bounds else None))
            for j in range(i, -1, -1):
                if j < len(exemplars) and exemplars[j]:
                    return est, exemplars[j]
            return est, None
    return None, None


def _summarize_metric_records(records: List[dict]) -> Dict[str, Any]:
    """The summary over already-parsed registry records — the shared
    core behind dump files (``--metrics``), live ``/varz`` scrapes and
    fleetz snapshot dirs (``--url``)."""
    out: Dict[str, Any] = {"cache": {}, "collectives": {}, "hbm_gauges": {},
                           "serve": {}, "fleet": {}}
    for rec in records:
            name = rec.get("name")
            labels = rec.get("labels") or {}
            if name == "alink_comqueue_program_cache_total":
                out["cache"][labels.get("result", "?")] = rec.get("value", 0)
            elif name == "alink_collective_calls_total":
                out["collectives"][labels.get("collective", "?")] = \
                    rec.get("value", 0)
            elif name == "alink_hbm_live_bytes":
                out["hbm_gauges"][labels.get("scope", "?")] = \
                    rec.get("value", 0)
            elif name == "alink_serve_requests_total":
                out["serve"]["requests"] = out["serve"].get("requests", 0) \
                    + rec.get("value", 0)
            elif name == "alink_serve_model_swaps_total":
                out["serve"]["swaps"] = out["serve"].get("swaps", 0) \
                    + rec.get("value", 0)
            elif name == "alink_serve_swap_seconds":
                out["serve"]["swap_sum_s"] = out["serve"].get(
                    "swap_sum_s", 0.0) + (rec.get("sum") or 0.0)
                out["serve"]["swap_count"] = out["serve"].get(
                    "swap_count", 0) + (rec.get("count") or 0)
            elif name == "alink_serve_p99_seconds":
                out["serve"]["p99_s"] = max(out["serve"].get("p99_s", 0.0),
                                            rec.get("value", 0.0))
            elif name == "alink_serve_shed_total":
                out["serve"]["shed"] = out["serve"].get("shed", 0) \
                    + rec.get("value", 0)
            # the two Layer-6 request histograms (ISSUE 18): admission->
            # dispatch wait vs whole-request latency, each carrying the
            # tail's exemplar trace_id so the p99 names a real request
            elif name == "alink_serve_queue_wait_seconds":
                out["serve"]["queue_wait_count"] = \
                    out["serve"].get("queue_wait_count", 0) \
                    + (rec.get("count") or 0)
                out["serve"]["queue_wait_sum_s"] = \
                    out["serve"].get("queue_wait_sum_s", 0.0) \
                    + (rec.get("sum") or 0.0)
                est, ex = _hist_p99(rec)
                if est is not None and est >= out["serve"].get(
                        "queue_wait_p99_est_s", -1.0):
                    out["serve"]["queue_wait_p99_est_s"] = est
                    if ex:
                        out["serve"]["queue_wait_p99_exemplar"] = ex
            elif name == "alink_serve_request_seconds":
                out["serve"]["request_count"] = \
                    out["serve"].get("request_count", 0) \
                    + (rec.get("count") or 0)
                est, ex = _hist_p99(rec)
                if est is not None and est >= out["serve"].get(
                        "request_p99_est_s", -1.0):
                    out["serve"]["request_p99_est_s"] = est
                    if ex:
                        out["serve"]["request_p99_exemplar"] = ex
            elif name == "alink_serve_breaker_fallback_total":
                out["serve"]["breaker_fallbacks"] = \
                    out["serve"].get("breaker_fallbacks", 0) \
                    + rec.get("value", 0)
            elif name == "alink_serve_feeder_errors_total":
                out["serve"]["feeder_errors"] = \
                    out["serve"].get("feeder_errors", 0) \
                    + rec.get("value", 0)
            elif name == "alink_serve_loop_respawns_total":
                out["serve"]["loop_respawns"] = \
                    out["serve"].get("loop_respawns", 0) \
                    + rec.get("value", 0)
            # the multi-tenant fleet plane (ISSUE 17): tenant census
            # and the eviction/coalescing economics behind it
            elif name == "alink_fleet_tenants":
                out["fleet"]["tenants"] = max(
                    out["fleet"].get("tenants", 0), rec.get("value", 0))
            elif name == "alink_fleet_evictions_total":
                out["fleet"]["evictions"] = out["fleet"].get(
                    "evictions", 0) + rec.get("value", 0)
            elif name == "alink_fleet_readmissions_total":
                out["fleet"]["readmissions"] = out["fleet"].get(
                    "readmissions", 0) + rec.get("value", 0)
            elif name == "alink_fleet_coalesced_batches_total":
                out["fleet"]["coalesced_batches"] = out["fleet"].get(
                    "coalesced_batches", 0) + rec.get("value", 0)
            elif name == "alink_fleet_resident_bytes":
                out["fleet"]["resident_bytes"] = max(
                    out["fleet"].get("resident_bytes", 0),
                    rec.get("value", 0))
    if not out["serve"]:
        del out["serve"]
    if not out["fleet"]:
        del out["fleet"]
    return out


_BUNDLE_FORMAT = "alink_tpu_postmortem_v1"
_PHASE_COLS = ("queue_s", "coalesce_s", "dispatch_s", "device_s",
               "decode_s")


def _load_postmortem(path: str) -> Dict[str, Any]:
    """One post-mortem bundle (common/postmortem.py shape), version-
    checked — the doctor stays stdlib-only, so the format contract is
    re-validated here rather than imported."""
    doc = load_json(path)
    if not isinstance(doc, dict) or doc.get("format") != _BUNDLE_FORMAT:
        raise ValueError(
            f"{path}: not an alink_tpu post-mortem bundle (format="
            f"{doc.get('format') if isinstance(doc, dict) else None!r}, "
            f"want {_BUNDLE_FORMAT})")
    return doc


def _postmortem_section(bundle: Dict[str, Any]) -> Dict[str, Any]:
    """The bundle's own verdict material: the trigger, the frozen
    request timelines, the event history, and the p99-exemplar request
    (the concrete lifetime behind the tail the incident fired on)."""
    reqs = bundle.get("requests") or []
    by_id = {r.get("trace_id"): r for r in reqs if isinstance(r, dict)}
    exemplar_req = None
    for rec in bundle.get("metrics") or []:
        if isinstance(rec, dict) and \
                rec.get("name") == "alink_serve_request_seconds":
            _est, ex = _hist_p99(rec)
            if ex and ex.get("trace_id") in by_id:
                exemplar_req = by_id[ex["trace_id"]]
                break
    ev_kinds: Dict[str, int] = {}
    for ev in bundle.get("events") or []:
        k = str((ev or {}).get("kind", "?"))
        ev_kinds[k] = ev_kinds.get(k, 0) + 1
    return {
        "reason": bundle.get("reason"),
        "detail": bundle.get("detail"),
        "created_unix": bundle.get("created_unix"),
        "pid": bundle.get("pid"),
        "context": bundle.get("context") or {},
        "extra": bundle.get("extra") or {},
        "requests": reqs,
        "inflight": bundle.get("inflight") or [],
        "event_counts": ev_kinds,
        "trace_events": len((bundle.get("trace") or {}).get("events")
                            or []),
        "statusz_armed": (bundle.get("statusz") or {}).get("armed"),
        "p99_exemplar_request": exemplar_req,
    }


def _request_row(r: Dict[str, Any]) -> List[str]:
    ph = r.get("phases") or {}
    cells = [str(r.get("trace_id") or "?"),
             str(r.get("tenant") or "-"),
             str(r.get("outcome") or "?"),
             (f"{r['total_s'] * 1e3:.2f}"
              if r.get("total_s") is not None else "-")]
    for k in _PHASE_COLS:
        v = ph.get(k)
        cells.append(f"{v * 1e3:.2f}" if v is not None else "-")
    ann = r.get("annotations") or []
    cells.append(",".join(a.get("kind", "?") for a in ann) or "-")
    return cells


def _render_postmortem(pm: Dict[str, Any]) -> List[str]:
    out = [f"\n== post-mortem: {pm.get('reason')} =="]
    if pm.get("detail"):
        out.append(f"  {pm['detail']}")
    import datetime
    when = pm.get("created_unix")
    stamp = (datetime.datetime.fromtimestamp(when).isoformat(" ")
             if when else "?")
    out.append(f"  captured {stamp} by pid {pm.get('pid')}; "
               f"{pm.get('trace_events', 0)} trace events, "
               f"adminz {'armed' if pm.get('statusz_armed') else 'off'}")
    for label, d in (("context", pm.get("context")),
                     ("trigger", pm.get("extra"))):
        if d:
            out.append(f"  {label}: " + ", ".join(
                f"{k}={v}" for k, v in sorted(d.items())))
    ev = pm.get("event_counts") or {}
    if ev:
        out.append("  event history: " + ", ".join(
            f"{k} x{n}" for k, n in sorted(ev.items())))
    reqs = pm.get("requests") or []
    inflight = pm.get("inflight") or []
    out.append(f"  {len(reqs)} finished request timeline(s), "
               f"{len(inflight)} in flight at capture")
    show = reqs[:12]
    if show:
        hdr = ["trace_id", "tenant", "outcome", "total"] + \
            [c[:-2] for c in _PHASE_COLS] + ["overlapping"]
        rows = [hdr] + [_request_row(r) for r in show]
        widths = [max(len(row[i]) for row in rows)
                  for i in range(len(hdr))]
        out.append("  request timelines, newest first (ms):")
        for row in rows:
            out.append("    " + "  ".join(
                c.ljust(w) for c, w in zip(row, widths)).rstrip())
        if len(reqs) > len(show):
            out.append(f"    ... and {len(reqs) - len(show)} more in "
                       f"the bundle")
    exr = pm.get("p99_exemplar_request")
    if exr:
        ph = exr.get("phases") or {}
        out.append(f"  p99 exemplar -> {exr.get('trace_id')}: " + ", ".join(
            f"{k[:-2]} {ph[k] * 1e3:.2f} ms" for k in _PHASE_COLS
            if ph.get(k) is not None))
        for a in exr.get("annotations") or []:
            out.append(f"    overlapped by {a.get('kind')} at "
                       f"+{a.get('t_s', 0) * 1e3:.2f} ms "
                       f"{a.get('args') or ''}")
    out.append(f"  verdict: {pm.get('reason')} fired — the bundle "
               f"alone carries the timelines, metrics and flags above; "
               f"tools/trace.py --trace-id ID <bundle> renders any one "
               f"request's lifetime")
    return out


def _workload_entries(bench: Optional[Dict[str, Any]],
                      profile: Optional[Dict[str, Any]]
                      ) -> List[Tuple[str, Dict[str, Any], Dict[str, Any]]]:
    """(name, bench_row, attribution) per workload. Bench rows carry
    the attribution under ``profile`` when the capture ran profiled;
    the profile artifact fills in workloads the bench did not annotate
    (or stands alone when no bench dump is given)."""
    rows: Dict[str, Dict[str, Any]] = {}
    if bench:
        wl = bench.get("workloads")
        if isinstance(wl, dict):
            rows = {k: v for k, v in wl.items() if isinstance(v, dict)}
    prof_wl = (profile or {}).get("workloads") or {}
    names = list(rows) + [n for n in prof_wl if n not in rows]
    out = []
    for name in names:
        if str(name).startswith("serve_"):
            # serving rows get their own verdict section (loadgen-
            # measured QPS/latency); the generic capture-window
            # attribution sees only their host side and would render a
            # misleading all-host bar
            continue
        if str(name) == "tuning_sweep":
            # the sweep row times two interleaved legs (serial grid +
            # swept program) — it gets its own verdict section; a merged
            # capture-window bar would attribute both legs as one
            continue
        row = rows.get(name, {})
        attr = row.get("profile") or prof_wl.get(name)
        if attr:
            out.append((name, row, attr))
    return out


def _fractions(attr: Dict[str, Any]) -> Dict[str, float]:
    fr = attr.get("fractions")
    if isinstance(fr, dict) and fr:
        return {k: float(fr.get(k, 0.0)) for k in _BUCKET_ORDER}
    wall = attr.get("measured_wall_s") or 0.0
    parts = {k: float(attr.get(f"{k}_s", 0.0)) for k in _BUCKET_ORDER}
    total = max(wall, sum(parts.values()), 1e-12)
    return {k: v / total for k, v in parts.items()}


def _achieved(row: Dict[str, Any], attr: Dict[str, Any],
              fr: Dict[str, float],
              peak_tflops: float, peak_hbm_gbps: float
              ) -> Optional[Dict[str, float]]:
    """Device-time-normalized achieved rates: what the kernels sustain
    while the device is busy. Needs the row's per-sample cost model and
    throughput; None otherwise (the harness cannot invent a model) —
    and None when the attribution's device time merges more than one
    program leg (the headline rate describes a single leg, so the
    normalization would be cross-leg)."""
    fps = row.get("flops_per_sample")
    bps = row.get("hbm_bytes_per_sample")
    sps = row.get("samples_per_sec_per_chip")
    dev = fr.get("device", 0.0)
    if len(attr.get("device_scopes") or ()) > 1:
        return None
    if not (fps and sps) or dev <= 0.0:
        return None
    sps_dev = sps / dev
    out = {"flops_per_s": sps_dev * fps,
           "pct_peak_flops": 100.0 * sps_dev * fps / (peak_tflops * 1e12)}
    if bps:
        out["bytes_per_s"] = sps_dev * bps
        out["pct_peak_hbm"] = 100.0 * sps_dev * bps / (peak_hbm_gbps * 1e9)
    return out


def _fixes(name: str, attr: Dict[str, Any], fr: Dict[str, float],
           row: Dict[str, Any], rig: Dict[str, Any],
           ach: Optional[Dict[str, float]]) -> List[str]:
    """Top-3 what-to-fix, ranked by the wall share each one attacks."""
    cands: List[Tuple[float, str]] = []
    gap = rig.get("dispatch_gap_est_s") or row.get("dispatch_gap_est_s")
    disp = fr.get("dispatch", 0.0)
    if disp >= 0.15:
        tail = (f" (rig floor ~{gap * 1e3:.0f} ms/dispatch)"
                if gap else "")
        cands.append((disp, f"{disp:.0%} of measured wall is host "
                            f"dispatch{tail}: batch more supersteps/"
                            f"micro-batches per compiled call (chunked "
                            f"scans, larger checkpoint_every, fused "
                            f"pools)"))
    host = fr.get("host", 0.0)
    if host >= 0.15:
        cands.append((host, f"{host:.0%} is unattributed host work "
                            f"(encode/IO/python): widen "
                            f"ALINK_TPU_STREAM_WORKERS, keep the "
                            f"prefetch channel fed, move parsing off "
                            f"the consumer thread"))
    xfer = fr.get("transfer", 0.0)
    if xfer >= 0.10:
        cands.append((xfer, f"{xfer:.0%} is H2D/D2H transfer: keep "
                            f"state device-resident, batch host "
                            f"fetches (device_get trees), donate "
                            f"buffers (ALINK_TPU_DONATE)"))
    coll = fr.get("collective", 0.0)
    if coll >= 0.10:
        cands.append((coll, f"{coll:.0%} is collective time: fuse "
                            f"per-superstep all-reduces into one psum "
                            f"payload"))
    dev = fr.get("device", 0.0)
    if dev >= 0.15:
        if ach is not None:
            roof = max(ach.get("pct_peak_flops", 0.0),
                       ach.get("pct_peak_hbm", 0.0))
            if roof < 15.0:
                cands.append((dev, f"device-busy {dev:.0%} but only "
                                   f"{roof:.1f}% of the chip roof: fuse "
                                   f"kernels (ALINK_TPU_FUSED_HIST, "
                                   f"Pallas) or grow the shapes"))
            else:
                cands.append((dev * 0.5,
                              f"device compute at {roof:.0f}% of the "
                              f"roof — scale out or reduce work; this "
                              f"workload is near the hardware limit"))
        else:
            legs = attr.get("device_scopes") or ()
            if len(legs) > 1:
                cands.append((dev, f"device-busy {dev:.0%} merged from "
                                   f"{len(legs)} program legs "
                                   f"({', '.join(legs)}): the per-sample "
                                   f"model cannot normalize cross-leg — "
                                   f"profile the legs as separate rows "
                                   f"to split compute from HBM"))
            else:
                cands.append((dev, f"device-busy {dev:.0%} with no "
                                   f"per-sample cost model on the row: "
                                   f"add flops/bytes accounting "
                                   f"(bench.mfu) to split compute from "
                                   f"HBM"))
    cands.sort(key=lambda c: -c[0])
    return [c[1] for c in cands[:3]]


def _serve_verdicts(bench: Optional[Dict[str, Any]],
                    metrics: Optional[Dict[str, Any]]
                    ) -> List[Dict[str, Any]]:
    """Per-``serve_*``-row serving verdicts: the headline numbers plus
    named fixes when batches run under-occupied, the bucket set misses,
    swaps stall, or requests fail — the serving analogue of the
    roofline fix ranking."""
    rows = ((bench or {}).get("workloads") or {})
    serve_met = (metrics or {}).get("serve") or {}
    out: List[Dict[str, Any]] = []
    for name, row in rows.items():
        if not str(name).startswith("serve_") or not isinstance(row, dict):
            continue
        if str(name) == "serve_online_e2e":
            continue    # the whole-loop DAG row gets its own e2e
                        # verdict section (_e2e_verdicts)
        if str(name) == "serve_fleet":
            continue    # the multi-tenant fleet row gets its own
                        # verdict section (_fleet_verdicts)
        if "error" in row:
            out.append({"workload": name, "error": row["error"]})
            continue
        fixes: List[str] = []
        failed = int(row.get("failed_requests") or 0)
        torn = int(row.get("torn_responses") or 0)
        chaos = str(name) == "serve_chaos"
        if failed or (torn and not chaos):
            fixes.append(f"CRITICAL: {failed} failed / {torn} torn "
                         f"responses — the tier dropped or corrupted "
                         f"requests; check swap geometry (model "
                         f"signature changes recompile mid-swap) and "
                         f"server exceptions before trusting any other "
                         f"number")
        if chaos:
            # the chaos row's SLO contract (ISSUE 14): typed rejections
            # during the storm are by design; torn/silent/no-recovery
            # is what breaks the tier
            silent = int(row.get("silent_drops") or 0)
            if torn or silent:
                fixes.append(f"CRITICAL: chaos storm broke the SLO "
                             f"contract — {torn} torn / {silent} SILENT "
                             f"drops (every submitted request must "
                             f"resolve to a result or a typed "
                             f"rejection; serving/resilience.py)")
            if row.get("recovered_compiled") is False:
                fixes.append("CRITICAL: the circuit breaker never "
                             "recovered to the compiled path after the "
                             "storm cleared — the half-open probe "
                             "schedule is broken (serving/resilience.py "
                             "CircuitBreaker / ALINK_TPU_SERVE_BREAKER_*"
                             ") or the compiled path stayed genuinely "
                             "down")
        shed = row.get("shed_requests")
        if shed and not chaos:
            fixes.append(f"load shedding is ACTIVE ({int(shed)} requests "
                         f"shed on deadline/cancel): queue wait exceeds "
                         f"request budgets — add replicas "
                         f"(ALINK_TPU_SERVE_REPLICAS), widen the "
                         f"admission bound (ALINK_TPU_SERVE_QUEUE) only "
                         f"if deadlines allow the extra wait, or relax "
                         f"the submitted deadline_s")
        occ = row.get("batch_occupancy")
        if occ is not None and occ < 0.5:
            fixes.append(f"batches run under-occupied ({occ:.0%} of "
                         f"bucket): requests are not coalescing — hold "
                         f"under-filled batches (ALINK_TPU_SERVE_MIN_FILL "
                         f"+ ALINK_TPU_SERVE_WINDOW_MS) or shrink "
                         f"ALINK_TPU_SERVE_BUCKETS toward the observed "
                         f"batch size (~{row.get('mean_batch_rows')})")
        hit = row.get("bucket_hit_rate")
        if hit is not None and hit < 0.9:
            fixes.append(f"serving programs miss the cache {1 - hit:.0%} "
                         f"of the time: request geometry is churning "
                         f"(new buckets/widths keep compiling) — pin "
                         f"ALINK_TPU_SERVE_BUCKETS / round request "
                         f"widths")
        speed = row.get("speedup_vs_serial")
        if speed is not None and speed < 2.0:
            fixes.append(f"micro-batching barely wins ({speed}x serial): "
                         f"per-row host work dominates — move encode "
                         f"cost out of the request path, grow the "
                         f"model so the device amortization matters, "
                         f"or cut the score path's HBM round-trips with "
                         f"the fused kernel tier (ALINK_TPU_SERVE_FUSED"
                         f"=1: encode-gather->dot->link in one Pallas "
                         f"kernel)")
        # the Pallas kernel tier's serving row (ISSUE 13)
        fv = row.get("fused_vs_xla")
        if fv is not None:
            if row.get("parity") == "MISMATCH":
                fixes.append("CRITICAL: the fused serving score kernel "
                             "is NOT bitwise-identical to the "
                             "seq_chunk_sum XLA path — the kernel "
                             "tier's reduction-order contract is "
                             "broken (kernels/serve.py)")
            elif fv < 1.0:
                note = str(row.get("rig_note") or "")
                if "interpret" in note:
                    fixes.append(f"the fused score kernel loses to the "
                                 f"XLA path on this rig ({fv}x; "
                                 f"{note}): the HBM-round-trip "
                                 f"elimination (ALINK_TPU_SERVE_FUSED) "
                                 f"shows on a physical TPU slice, not "
                                 f"in interpret mode — recapture there")
                else:
                    fixes.append(f"the fused score kernel LOSES to the "
                                 f"XLA path on a native rig ({fv}x) — "
                                 f"a genuine kernel-tier regression, "
                                 f"not an interpret-mode artifact: "
                                 f"profile the kernel's grid/BlockSpec "
                                 f"(kernels/serve.py) before trusting "
                                 f"serve_fused gains")
        # multi-chip serving (ISSUE 11): per-chip QPS across mesh sizes
        # — the fleet-scale verdict is that QPS/chip HOLDS as chips are
        # added (a sharded/replicated tier that decays per chip is just
        # burning silicon)
        per_chip = {}
        for k, val in row.items():
            if str(k).startswith("qps_per_chip_") and str(k).endswith("dev"):
                try:
                    per_chip[int(str(k)[len("qps_per_chip_"):-3])] = val
                except (TypeError, ValueError):
                    pass
        ns = sorted(per_chip)
        scaling = None
        if len(per_chip) >= 2:
            lo, hi = per_chip[ns[0]], per_chip[ns[-1]]
            scaling = round(hi / lo, 3) if lo else 0.0
            if scaling < 0.7:
                note = (" (expected on this rig: " + str(
                            row.get("mesh_note")) + "; recapture on a "
                        "physical slice)") if row.get("mesh_note") else ""
                fixes.append(
                    f"QPS/chip decays to {scaling:.0%} going "
                    f"{ns[0]}->{ns[-1]} devices: the mesh is not "
                    f"earning its chips — check replica fan-out "
                    f"(ALINK_TPU_SERVE_REPLICAS) and whether the "
                    f"sharded psum dominates the dispatch "
                    f"(ALINK_TPU_SERVE_SHARDED off for small "
                    f"models){note}")
        if row.get("parity") == "MISMATCH" and fv is None:
            fixes.append("CRITICAL: sharded bucket programs are NOT "
                         "bitwise-identical across mesh sizes — the "
                         "lane-blocked reduction contract is broken "
                         "(serving/sharded.py)")
        p99_s = (row.get("p99_ms") or row.get("p99_ms_during") or 0) / 1e3
        swap_count = serve_met.get("swap_count") or 0
        if swap_count and row.get("model_swaps"):
            mean_swap = (serve_met.get("swap_sum_s") or 0.0) / swap_count
            if p99_s and mean_swap > 5.0 * p99_s:
                fixes.append(f"model swaps stall ({mean_swap * 1e3:.1f} "
                             f"ms mean vs p99 {p99_s * 1e3:.1f} ms): "
                             f"keep model geometry stable across "
                             f"snapshots so swapped models reuse the "
                             f"compiled programs, and keep device_put "
                             f"on the feeder thread "
                             f"(ALINK_TPU_SERVE_SWAP=double)")
        v = {"workload": name,
             "qps_per_chip": row.get("qps_per_chip")
             or row.get("samples_per_sec_per_chip"),
             "p50_ms": row.get("p50_ms") or row.get("p50_ms_during"),
             "p99_ms": row.get("p99_ms") or row.get("p99_ms_during"),
             "bucket_hit_rate": hit, "batch_occupancy": occ,
             "failed_requests": failed, "fixes": fixes}
        if scaling is not None:
            v["qps_per_chip_by_devices"] = {str(n): per_chip[n]
                                            for n in ns}
            v["per_chip_scaling"] = scaling
        for k in ("speedup_vs_serial", "serial_qps_per_chip", "parity",
                  "model_swaps", "torn_responses", "p99_ms_before",
                  "p99_ms_during", "p99_ms_after", "fused_vs_xla",
                  "dtype_winner", "label_agreement_bf16",
                  "label_agreement_int8", "shed_requests",
                  "breaker_opens", "breaker_reopens", "typed_rejections",
                  "silent_drops", "recovered_compiled",
                  "feeder_retries", "feeder_skipped", "loop_respawns"):
            if row.get(k) is not None:
                v[k] = row[k]
        out.append(v)
    # run-level resilience signals from the metrics dump (ISSUE 14):
    # one summary verdict, not one line per bench row — metrics are
    # process-global. Skipped when a serve_chaos row already explains
    # the storm it deliberately ran.
    has_chaos = any(str(n) == "serve_chaos" for n in rows)
    met_fixes: List[str] = []
    if not has_chaos and serve_met.get("shed"):
        met_fixes.append(
            f"load shedding is ACTIVE ({int(serve_met['shed'])} requests "
            f"shed on deadline/cancel — alink_serve_shed_total): queue "
            f"wait exceeds request budgets; add replicas "
            f"(ALINK_TPU_SERVE_REPLICAS) or relax the submitted "
            f"deadline_s")
    # satellite 1 (ISSUE 18): when the admission->dispatch wait is the
    # p99, the tier is queue-bound — no kernel fix helps until requests
    # stop aging in the channel
    qw99 = serve_met.get("queue_wait_p99_est_s")
    rq99 = serve_met.get("request_p99_est_s")
    if not has_chaos and qw99 and rq99 and qw99 >= 0.5 * rq99:
        line = (f"queue wait DOMINATES p99 (~{qw99 * 1e3:.1f} ms of the "
                f"~{rq99 * 1e3:.1f} ms request p99 — "
                f"alink_serve_queue_wait_seconds): requests age in "
                f"admission before any device work; add replicas "
                f"(ALINK_TPU_SERVE_REPLICAS), shorten the batch window "
                f"(ALINK_TPU_SERVE_WINDOW_MS / ALINK_TPU_SERVE_MIN_FILL) "
                f"or shrink the admission bound (ALINK_TPU_SERVE_QUEUE) "
                f"so excess load sheds instead of aging")
        ex = serve_met.get("queue_wait_p99_exemplar") or {}
        if ex.get("trace_id"):
            line += (f"; exemplar request {ex['trace_id']} "
                     f"(tools/trace.py --trace-id renders its timeline)")
        met_fixes.append(line)
    if not has_chaos and serve_met.get("feeder_errors"):
        met_fixes.append(
            f"model-stream feeders hit "
            f"{int(serve_met['feeder_errors'])} errors "
            f"(alink_serve_feeder_errors_total): the server keeps "
            f"serving the last good model, but it STOPPED UPDATING on "
            f"those boundaries — check the feeder warnings for "
            f"poisoned vs transient kinds")
    if met_fixes:
        out.append({"workload": "serving (metrics)",
                    "fixes": met_fixes})
    return out


def _fleet_verdicts(bench: Optional[Dict[str, Any]],
                    metrics: Optional[Dict[str, Any]]
                    ) -> List[Dict[str, Any]]:
    """The multi-tenant fleet verdict (ISSUE 17): tenant census, the
    p99-vs-single-model headline, and named fixes for the two fleet
    failure economies — eviction THRASH (the HBM budget churns hot
    tenants through the snapshot store) and UNDER-COALESCING (same-
    geometry tenants dispatch one-by-one, paying per-tenant launches
    for shared programs). Reads the ``serve_fleet`` bench row when one
    exists and the live ``alink_fleet_*`` metrics otherwise."""
    rows = ((bench or {}).get("workloads") or {})
    row = rows.get("serve_fleet")
    fleet_met = (metrics or {}).get("fleet") or {}
    if not isinstance(row, dict) and not fleet_met:
        return []
    if isinstance(row, dict) and "error" in row:
        return [{"workload": "serve_fleet", "error": row["error"]}]
    row = row if isinstance(row, dict) else {}
    fixes: List[str] = []
    tenants = row.get("tenants") or fleet_met.get("tenants") or 0
    evictions = row.get("evictions")
    if evictions is None:
        evictions = fleet_met.get("evictions")
    readmissions = row.get("readmissions")
    if readmissions is None:
        readmissions = fleet_met.get("readmissions")
    leaked = int(row.get("leaked_rows") or 0)
    if leaked or row.get("parity") == "MISMATCH":
        fixes.append(f"CRITICAL: {leaked} cross-tenant probe rows "
                     f"leaked another tenant's scores — coalesced "
                     f"lane gather or eviction/re-admission is routing "
                     f"the wrong weights (serving/fleet.py "
                     f"_dispatch_coalesced / arrays_for); nothing else "
                     f"about the fleet matters until this is bitwise")
    failed = int(row.get("failed_requests") or 0)
    if failed:
        fixes.append(f"CRITICAL: {failed} failed requests — check "
                     f"per-tenant breaker states and server exceptions "
                     f"before trusting the latency numbers")
    # eviction thrash: the budget forces hot tenants out and straight
    # back in — each re-admission pays a snapshot load + device_put in
    # the serving path
    if tenants and evictions and evictions > 3 * tenants:
        fixes.append(
            f"eviction THRASH: {int(evictions)} evictions over "
            f"{int(tenants)} tenants ({int(readmissions or 0)} "
            f"re-admissions) — the working set does not fit "
            f"ALINK_TPU_FLEET_HBM_BUDGET; raise the budget, shrink "
            f"the per-tenant model, or shard tenants across more "
            f"fleet processes so the hot set stays resident")
    # under-coalescing: same-geometry tenants are paying per-tenant
    # dispatches for programs they could share
    rate = row.get("coalesce_rate")
    if rate is not None and rate < 0.5 and tenants and tenants > 1:
        fixes.append(
            f"batches under-coalesce ({rate:.0%} of dispatches carry "
            f">1 tenant): cross-tenant stacking is not happening — "
            f"check ALINK_TPU_FLEET_COALESCE=1, that tenants really "
            f"share serving-kernel geometry (ModelRegistry.stats() "
            f"groups), and hold batches long enough to mix tenants "
            f"(ALINK_TPU_SERVE_MIN_FILL + ALINK_TPU_SERVE_WINDOW_MS)")
    ratio = row.get("p99_vs_single")
    if ratio is not None and ratio > 5.0:
        fixes.append(
            f"fleet p99 runs {ratio}x the single-model baseline: "
            f"multi-tenancy is not free on this rig — look at "
            f"re-admission stalls (evictions above), lane-bucket "
            f"recompiles (ALINK_TPU_FLEET_LANES vs observed group "
            f"sizes), and per-tenant breaker fallbacks")
    v: Dict[str, Any] = {"workload": "serve_fleet",
                         "tenants": int(tenants) if tenants else None,
                         "evictions": evictions,
                         "readmissions": readmissions,
                         "fixes": fixes}
    for k in ("qps_per_chip", "p50_ms", "p99_ms", "p99_ms_single",
              "p99_vs_single", "coalesce_rate", "coalesced_batches",
              "uncoalesced_batches", "model_swaps", "shed_requests",
              "failed_requests", "leaked_rows", "parity",
              "resident_bytes", "hbm_budget"):
        if row.get(k) is not None:
            v[k] = row[k]
    if "resident_bytes" not in v and \
            fleet_met.get("resident_bytes") is not None:
        v["resident_bytes"] = fleet_met["resident_bytes"]
    return [v]


#: SLO clause -> the DAG stage that owns it (the e2e verdict's
#: weakest-stage attribution; ISSUE 15)
_E2E_CLAUSE_STAGE = {
    "serve_p99": ("serve", "serving latency"),
    "swap_staleness": ("feed", "model-swap staleness"),
    "final_window_auc": ("train", "eval-window quality"),
}


def _e2e_verdicts(bench: Optional[Dict[str, Any]]
                  ) -> List[Dict[str, Any]]:
    """The ``serve_online_e2e`` row's whole-loop verdict (ISSUE 15):
    the steady-state headline (QPS/p99/windows/AUC/staleness), the
    storm's supervised-restart and breaker-recovery evidence, and the
    WEAKEST-STAGE attribution — the armed SLO clause running closest
    to its bound names the stage to harden next; a breached clause or
    a broken storm invariant names it CRITICALLY."""
    rows = ((bench or {}).get("workloads") or {})
    row = rows.get("serve_online_e2e")
    if not isinstance(row, dict):
        return []
    if "error" in row:
        return [{"workload": "serve_online_e2e", "error": row["error"]}]
    fixes: List[str] = []
    weakest = None
    weakest_detail = None
    silent = int(row.get("silent_drops") or 0)
    if silent:
        fixes.append(f"CRITICAL: {silent} SILENT drops in the DAG's "
                     f"scoring leg — every scoring future must resolve "
                     f"to a result or a typed rejection "
                     f"(online/dag.py _score_rows; "
                     f"serving/resilience.py)")
    if row.get("storm_bitwise_journals") is False:
        weakest, weakest_detail = "train", (
            "the trainer-side storm's eval journals diverged from the "
            "clean run")
        fixes.append("CRITICAL: the supervised trainer restart did NOT "
                     "resume bitwise — a micro-batch was dropped or "
                     "double-applied across the checkpoint replay "
                     "(FTRL replay-prefix skip / online/dag.py pacing)")
    if row.get("recovered_compiled") is False:
        if weakest is None:   # first-wins, like the SLO-clause loop —
            # a bitwise-resume break outranks the breaker verdict
            weakest, weakest_detail = "serve", (
                "the breaker never measurably re-served compiled after "
                "the storm")
        fixes.append("CRITICAL: the serve-side storm cleared but the "
                     "circuit breaker never recovered to the compiled "
                     "path (serving/resilience.py CircuitBreaker / "
                     "ALINK_TPU_SERVE_BREAKER_*)")
    # the SLO clauses: a failed clause names its stage outright; else
    # the clause running closest to its bound is the weakest stage
    pressure: List[tuple] = []
    for v in row.get("slo") or []:
        clause = v.get("slo")
        stage, what = _E2E_CLAUSE_STAGE.get(clause, ("serve", clause))
        obs, bound = v.get("observed"), v.get("bound")
        if not v.get("ok"):
            if weakest is None:
                weakest = stage
                weakest_detail = (f"SLO clause {clause} BREACHED "
                                  f"({obs} vs bound {bound})")
            fixes.append(f"CRITICAL: SLO clause {clause} failed "
                         f"({v.get('detail')}) — the {stage} stage "
                         f"broke its end-to-end bound")
            continue
        if obs is None or not bound:
            continue
        ratio = (bound / obs if clause == "final_window_auc" and obs
                 else obs / bound)
        pressure.append((ratio, stage, clause, what, obs, bound))
    if weakest is None and pressure:
        ratio, stage, clause, what, obs, bound = max(pressure)
        weakest = stage
        weakest_detail = (f"{what} runs closest to its bound "
                          f"({clause}: {ratio:.0%} of budget used)")
    note = row.get("auc_note")
    if note:
        fixes.append(f"the quality anchor did not clear: {note}")
    v = {"workload": "serve_online_e2e",
         "qps": row.get("qps") or row.get("samples_per_sec_per_chip"),
         "p99_ms": row.get("p99_ms"),
         "windows": row.get("windows"),
         "final_window_auc": row.get("final_window_auc"),
         "auc_note": note,
         "model_swaps": row.get("model_swaps"),
         "swap_staleness_max_ms": row.get("swap_staleness_max_ms"),
         "slo_ok": row.get("slo_ok"),
         "slo_breaches": row.get("slo_breaches"),
         "storm_restarts": row.get("storm_restarts"),
         "recovery_s_by_fault": row.get("recovery_s_by_fault"),
         "storm_bitwise_journals": row.get("storm_bitwise_journals"),
         "recovered_compiled": row.get("recovered_compiled"),
         "feeder_skipped": row.get("feeder_skipped"),
         "typed_rejections": row.get("typed_rejections"),
         "silent_drops": silent,
         "weakest_stage": weakest,
         "weakest_detail": weakest_detail,
         "fixes": fixes}
    return [v]


def _sweep_verdicts(bench: Optional[Dict[str, Any]]
                    ) -> List[Dict[str, Any]]:
    """The ``tuning_sweep`` row's verdict: points/s vs the serial
    candidate loop, the rung schedule and pruned fraction, and named
    fixes when the sweep fell back to serial economics, mispicked the
    winner, or broke the bitwise per-point contract."""
    rows = ((bench or {}).get("workloads") or {})
    out: List[Dict[str, Any]] = []
    for name, row in rows.items():
        if str(name) != "tuning_sweep" or not isinstance(row, dict):
            continue
        if "error" in row:
            out.append({"workload": name, "error": row["error"]})
            continue
        fixes: List[str] = []
        if row.get("parity") == "MISMATCH":
            fixes.append(
                "CRITICAL: per-point sweep results are NOT bitwise-"
                "identical to serial fits — the points-lane kernel "
                "drifted from the serial stage code (alink_tpu/tuning/"
                "sweep.py mirrors operator/common/optim/optimizers.py "
                "op-for-op; re-run tests/test_sweep.py)")
        if row.get("winner_match") is False:
            fixes.append(
                "ASHA picked a different winner than the full serial "
                "grid: the rung schedule prunes on a loss ranking that "
                "flips later — lengthen the rung period "
                "(ALINK_TPU_SWEEP_RUNG) or soften the reduction "
                "(ALINK_TPU_SWEEP_ETA)")
        speed = row.get("speedup_vs_serial")
        if speed is not None and speed < 2.0:
            fixes.append(
                f"the sweep barely beats the serial loop ({speed}x): "
                f"it fell back to serial economics — check "
                f"alink_sweep_fallback_total (every decline names its "
                f"reason: unsupported-estimator / trace-shaping-axis / "
                f"unsupported-evaluator), deepen the rung schedule, or "
                f"grow the population so pruning has leverage")
        progs = row.get("compiled_programs")
        pts = row.get("points")
        if progs is not None and pts and progs >= pts:
            fixes.append(
                f"{progs} compiled programs for {pts} points: every "
                f"point became its own compile group — the swept axes "
                f"are trace-shaping; move the grid onto carry-resident "
                f"axes (learning_rate/epsilon/l1/l2/tol/seed)")
        out.append({
            "workload": name,
            "points_per_sec": row.get("samples_per_sec_per_chip"),
            "speedup_vs_serial": speed,
            "sweep_full_speedup": row.get("sweep_full_speedup"),
            "points": pts, "rungs": row.get("rungs"),
            "pruned_fraction": row.get("pruned_fraction"),
            "compiled_programs": progs,
            "winner_match": row.get("winner_match"),
            "parity": row.get("parity"), "fixes": fixes})
    return out


def _compile_verdicts(compilez: Optional[List[Tuple[str, Any]]]
                      ) -> List[Dict[str, Any]]:
    """The Layer-7 compile-plane verdict (ISSUE 19), rendered OFFLINE
    from a ``/compilez`` document (run-dir ``compilez.json``, a fleetz
    snapshot's per-worker scrape, or a post-mortem bundle's frozen
    copy).  Each fix names the concrete dimension behind the cost: a
    recompile storm's dominant changed dimension (a flapping flag, an
    unbucketed geometry) or a cold-start-dominated restart's slowest
    subsystem."""
    out: List[Dict[str, Any]] = []
    for label, cz in compilez or []:
        if not isinstance(cz, dict) or not isinstance(
                cz.get("caches"), dict):
            continue
        caches = cz["caches"]
        events = [e for e in cz.get("events") or []
                  if isinstance(e, dict)]
        fixes: List[str] = []
        compiles = sum(int(c.get("misses") or 0)
                       for c in caches.values())
        hits = sum(int(c.get("hits") or 0) for c in caches.values())
        evictions = sum(int(c.get("evictions") or 0)
                        for c in caches.values())
        disk_hits = sum(int(c.get("disk_hits") or 0)
                        for c in caches.values())
        wall_s = round(sum(e.get("wall_s") or 0.0 for e in events), 3)
        deser_s = round(sum(e.get("wall_s") or 0.0 for e in events
                            if e.get("kind") == "disk-hit"), 3)
        fresh = [e for e in events if e.get("kind") != "disk-hit"]
        if disk_hits and not fresh:
            fixes.append(
                f"warm restart: all {disk_hits} program(s) came from "
                f"the persistent AOT store ({deser_s}s total "
                f"deserialize, zero XLA compiles) — the cold start is "
                f"dead; keep the cache dir on the deploy path")
        for name, c in sorted(caches.items()):
            n_storms = int(c.get("storms") or 0)
            if n_storms or c.get("storm_active"):
                dim = c.get("dominant_dim") or {}
                what = dim.get("dim", "?")
                hint = ("one env flag is flapping across restarts or "
                        "mid-run — pin it in the deployment env"
                        if str(what).startswith("ALINK_") else
                        "inputs are not bucketing — widen the bucket "
                        "ladder or pad to the ladder before dispatch")
                fixes.append(
                    f"RECOMPILE STORM on cache {name} ({n_storms} "
                    f"storm(s){', ACTIVE' if c.get('storm_active') else ''}"
                    f"): dominant changed dimension {what} "
                    f"({dim.get('old')}→{dim.get('new')}, "
                    f"{dim.get('count', '?')} of the recent events) — "
                    f"{hint}")
            total = int(c.get("hits") or 0) + int(c.get("misses") or 0)
            if (not n_storms and total >= 16
                    and (c.get("hit_rate") or 0.0) < 0.5):
                fixes.append(
                    f"cache {name} hit rate "
                    f"{c.get('hit_rate'):.0%} over {total} lookups: "
                    f"steady-state recompile churn without a storm "
                    f"edge — check the event diffs for the cycling "
                    f"dimension")
            cap = c.get("capacity")
            if cap and int(c.get("evictions") or 0) > \
                    max(4, int(c.get("misses") or 0) // 2):
                fixes.append(
                    f"cache {name} evicted {c['evictions']} programs "
                    f"against capacity {cap}: the working set exceeds "
                    f"the cache — raise the capacity or shrink the "
                    f"plan-dimension fan-out")
        cold = cz.get("cold_start") or {}
        ttfp = {k: float(v) for k, v in
                (cold.get("time_to_first_program_s") or {}).items()}
        if ttfp:
            worst = max(ttfp, key=lambda k: ttfp[k])
            if ttfp[worst] >= 5.0:
                fixes.append(
                    f"cold-start-dominated restart: subsystem "
                    f"{worst} paid {ttfp[worst]:.1f}s from first "
                    f"activity to first compiled program — set "
                    f"ALINK_TPU_AOT_CACHE_DIR and pre-export the "
                    f"bucket ladder with tools/warmcache.py so "
                    f"restarts deserialize instead of recompile")
        out.append({
            "label": label, "enabled": cz.get("enabled"),
            "compiles": compiles, "hits": hits,
            "evictions": evictions, "wall_s": wall_s,
            "disk_hits": disk_hits, "deserialize_s": deser_s,
            "caches": {n: {"subsystem": c.get("subsystem"),
                           "size": c.get("size"),
                           "capacity": c.get("capacity"),
                           "hits": c.get("hits"),
                           "misses": c.get("misses"),
                           "disk_hits": c.get("disk_hits"),
                           "hit_rate": c.get("hit_rate"),
                           "storms": c.get("storms")}
                       for n, c in sorted(caches.items())},
            "cold_start_s": {k: round(v, 3)
                             for k, v in sorted(ttfp.items())},
            "storms": sum(int(c.get("storms") or 0)
                          for c in caches.values()),
            "last_diff": (events[-1].get("diff")
                          if events else None),
            "fixes": fixes})
    return out


def diagnose(bench: Optional[Dict[str, Any]],
             profile: Optional[Dict[str, Any]],
             metrics: Optional[Dict[str, Any]],
             peak_tflops: float, peak_hbm_gbps: float,
             compilez: Optional[List[Tuple[str, Any]]] = None
             ) -> Dict[str, Any]:
    """The machine-shaped verdict document (--json emits it)."""
    rig = (bench or {}).get("rig") or {}
    peak_tflops = rig.get("peak_tflops") or peak_tflops
    peak_hbm_gbps = rig.get("peak_hbm_gbps") or peak_hbm_gbps
    verdicts = []
    for name, row, attr in _workload_entries(bench, profile):
        fr = _fractions(attr)
        ach = _achieved(row, attr, fr, peak_tflops, peak_hbm_gbps)
        bound = (attr.get("bound_measured") or row.get("bound")
                 or max(fr, key=lambda k: fr[k]))
        v = {"workload": name, "bound": bound,
             "bound_static": row.get("bound_static"),
             "source": attr.get("source", "timing-harness"),
             "measured_wall_s": attr.get("measured_wall_s"),
             "buckets": {k: attr.get(f"{k}_s") for k in _BUCKET_ORDER
                         if attr.get(f"{k}_s") is not None},
             "fractions": {k: round(fr[k], 4) for k in _BUCKET_ORDER},
             "fixes": _fixes(name, attr, fr, row, rig, ach)}
        if ach:
            v["achieved_device_time"] = {
                k: round(val, 4) for k, val in ach.items()}
        if attr.get("xprof"):
            v["xprof"] = attr["xprof"]
        verdicts.append(v)
    doc: Dict[str, Any] = {
        "format": "alink_tpu_doctor_v1",
        "rig": {"dispatch_gap_est_s": rig.get("dispatch_gap_est_s"),
                "peak_tflops": peak_tflops,
                "peak_hbm_gbps": peak_hbm_gbps,
                "baseline_fp": rig.get("baseline_fp")},
        "workloads": verdicts,
    }
    serving = _serve_verdicts(bench, metrics)
    if serving:
        doc["serving"] = serving
    fleet = _fleet_verdicts(bench, metrics)
    if fleet:
        doc["fleet"] = fleet
    sweeps = _sweep_verdicts(bench)
    if sweeps:
        doc["tuning"] = sweeps
    compiled = _compile_verdicts(compilez)
    if compiled:
        doc["compile"] = compiled
    e2e = _e2e_verdicts(bench)
    if e2e:
        doc["e2e"] = e2e
    if profile:
        doc["hbm"] = profile.get("hbm") or []
        if profile.get("donation"):
            doc["donation"] = profile["donation"]
        if profile.get("capture_error"):
            doc["capture_error"] = profile["capture_error"]
    if metrics:
        doc["metrics"] = metrics
    return doc


def render(doc: Dict[str, Any]) -> str:
    out: List[str] = []
    rig = doc.get("rig") or {}
    out.append("== perf doctor ==")
    gap = rig.get("dispatch_gap_est_s")
    out.append(f"  rig: dispatch floor "
               f"{'%.1f ms/call' % (gap * 1e3) if gap else 'n/a'}, roofs "
               f"{rig.get('peak_tflops')} TFLOP/s peak, "
               f"{rig.get('peak_hbm_gbps')} GB/s HBM")
    if doc.get("postmortem"):
        out.extend(_render_postmortem(doc["postmortem"]))
    for v in doc.get("workloads", []):
        out.append(f"\n== workload: {v['workload']} ==")
        static = v.get("bound_static")
        out.append(f"  bound: {v['bound']} (measured"
                   + (f"; static: {static}" if static else "")
                   + f")   source: {v.get('source')}")
        wall = v.get("measured_wall_s")
        if wall:
            out.append(f"  measured wall {wall:.3f} s")
        rows = []
        for k in _BUCKET_ORDER:
            sec = (v.get("buckets") or {}).get(k)
            frac = (v.get("fractions") or {}).get(k, 0.0)
            if sec is None and frac == 0.0:
                continue
            bar = _BAR * int(round(frac * 20))
            rows.append((_BUCKET_LABELS[k],
                         f"{sec:.3f}" if sec is not None else "-",
                         f"{frac:6.1%}", bar))
        if rows:
            w = max(len(r[0]) for r in rows)
            out.append(f"  {'bucket'.ljust(w)}  seconds   share")
            for lbl, sec, frac, bar in rows:
                out.append(f"  {lbl.ljust(w)}  {sec:>7}  {frac}  {bar}")
        ach = v.get("achieved_device_time")
        if ach:
            line = (f"  achieved (device-time): "
                    f"{ach['flops_per_s'] / 1e12:.4f} TFLOP/s "
                    f"({ach['pct_peak_flops']:.2f}% of roof)")
            if "bytes_per_s" in ach:
                line += (f", {ach['bytes_per_s'] / 1e9:.3f} GB/s "
                         f"({ach['pct_peak_hbm']:.2f}% of HBM roof)")
            out.append(line)
        xp = v.get("xprof")
        if xp:
            out.append(f"  xprof: device busy {xp.get('busy_s')}s over "
                       f"{xp.get('events')} events on "
                       f"{', '.join(xp.get('lanes', []))}")
        for i, fx in enumerate(v.get("fixes") or [], 1):
            out.append(f"  fix {i}: {fx}")
    for v in doc.get("serving", []):
        out.append(f"\n== serving: {v['workload']} ==")
        if v.get("error"):
            out.append(f"  ERROR: {v['error']}")
            continue
        line = f"  {v.get('qps_per_chip'):,.0f} qps/chip" \
            if v.get("qps_per_chip") else "  qps n/a"
        if v.get("serial_qps_per_chip"):
            line += (f" ({v.get('speedup_vs_serial')}x the "
                     f"{v['serial_qps_per_chip']:,.0f} qps serial-"
                     f"dispatch baseline)")
        out.append(line)
        traj = v.get("qps_per_chip_by_devices")
        if traj:
            arrow = " -> ".join(f"{traj[n]:,.0f}" for n in sorted(
                traj, key=int))
            out.append(f"  QPS/chip at "
                       f"{'/'.join(sorted(traj, key=int))} devices: "
                       f"{arrow} ({v.get('per_chip_scaling')}x per-chip "
                       f"scaling)")
        lat = []
        if v.get("p50_ms") is not None:
            lat.append(f"p50 {v['p50_ms']} ms")
        if v.get("p99_ms") is not None:
            lat.append(f"p99 {v['p99_ms']} ms")
        if v.get("p99_ms_before") is not None:
            lat.append(f"p99 before/during/after swaps "
                       f"{v['p99_ms_before']}/{v['p99_ms_during']}/"
                       f"{v['p99_ms_after']} ms")
        if lat:
            out.append("  " + ", ".join(lat))
        bits = []
        if v.get("bucket_hit_rate") is not None:
            bits.append(f"bucket-hit {v['bucket_hit_rate']:.1%}")
        if v.get("batch_occupancy") is not None:
            bits.append(f"occupancy {v['batch_occupancy']:.1%}")
        if v.get("model_swaps") is not None:
            bits.append(f"{v['model_swaps']} model swaps")
        if v.get("torn_responses") is not None:
            bits.append(f"{v['torn_responses']} torn")
        bits.append(f"{v.get('failed_requests', 0)} failed")
        if v.get("parity"):
            bits.append(f"parity {v['parity']}")
        # resilience counters (ISSUE 14; the serve_chaos row and any
        # shedding/degrading server)
        if v.get("shed_requests") is not None:
            bits.append(f"{v['shed_requests']} shed")
        if v.get("breaker_opens") is not None:
            bits.append(f"breaker opened {v['breaker_opens']}x "
                        f"(re-opened {v.get('breaker_reopens', 0)}x)")
        if v.get("typed_rejections") is not None:
            bits.append(f"{v['typed_rejections']} typed rejections / "
                        f"{v.get('silent_drops', 0)} silent")
        if v.get("recovered_compiled") is not None:
            bits.append("recovered to compiled"
                        if v["recovered_compiled"]
                        else "NOT recovered to compiled")
        out.append("  " + ", ".join(bits))
        for i, fx in enumerate(v.get("fixes") or [], 1):
            out.append(f"  fix {i}: {fx}")
        if not v.get("fixes"):
            out.append("  verdict: healthy — batches fill, programs "
                       "cache-hit, no failed/torn requests")
    for v in doc.get("fleet", []):
        out.append(f"\n== multi-tenant fleet: {v['workload']} ==")
        if v.get("error"):
            out.append(f"  ERROR: {v['error']}")
            continue
        line = (f"  {v['qps_per_chip']:,.0f} qps/chip"
                if v.get("qps_per_chip") else "  qps n/a")
        if v.get("tenants") is not None:
            line += f" across {v['tenants']} tenants"
        if v.get("p99_ms") is not None:
            line += f", p99 {v['p99_ms']} ms"
        if v.get("p99_vs_single") is not None:
            line += (f" ({v['p99_vs_single']}x the single-model "
                     f"baseline")
            if v.get("p99_ms_single") is not None:
                line += f" of {v['p99_ms_single']} ms"
            line += ")"
        out.append(line)
        bits = []
        if v.get("coalesce_rate") is not None:
            bits.append(f"coalesce rate {v['coalesce_rate']:.1%}")
        if v.get("coalesced_batches") is not None:
            bits.append(f"{int(v['coalesced_batches'])} coalesced / "
                        f"{int(v.get('uncoalesced_batches') or 0)} "
                        f"solo batches")
        if v.get("evictions") is not None:
            bits.append(f"{int(v['evictions'])} evictions / "
                        f"{int(v.get('readmissions') or 0)} "
                        f"re-admissions")
        if v.get("resident_bytes") is not None:
            bits.append(f"resident {_fmt_bytes(v['resident_bytes'])}")
        if v.get("model_swaps") is not None:
            bits.append(f"{int(v['model_swaps'])} model swaps")
        if v.get("parity"):
            bits.append(f"parity {v['parity']}")
        bits.append(f"{int(v.get('leaked_rows') or 0)} leaked rows")
        if bits:
            out.append("  " + ", ".join(bits))
        for i, fx in enumerate(v.get("fixes") or [], 1):
            out.append(f"  fix {i}: {fx}")
        if not v.get("fixes"):
            out.append("  verdict: healthy — tenants share compiled "
                       "programs, batches coalesce, the HBM budget "
                       "holds without thrash, and no tenant saw "
                       "another tenant's scores")
    for v in doc.get("e2e", []):
        out.append(f"\n== online DAG e2e: {v['workload']} ==")
        if v.get("error"):
            out.append(f"  ERROR: {v['error']}")
            continue
        line = (f"  {v['qps']:,.0f} qps steady-state"
                if v.get("qps") else "  qps n/a")
        if v.get("p99_ms") is not None:
            line += f", p99 {v['p99_ms']} ms"
        if v.get("windows") is not None:
            line += f", {v['windows']} eval windows"
        if v.get("final_window_auc") is not None:
            line += f", final AUC {v['final_window_auc']}"
        out.append(line)
        bits = []
        if v.get("model_swaps") is not None:
            bits.append(f"{v['model_swaps']} model swaps")
        if v.get("swap_staleness_max_ms") is not None:
            bits.append(f"max swap staleness "
                        f"{v['swap_staleness_max_ms']} ms")
        if v.get("slo_ok") is not None:
            bits.append("SLO ok" if v["slo_ok"]
                        else "SLO BREACHED")
        if v.get("slo_breaches") is not None:
            bits.append(f"{v['slo_breaches']} live breaches")
        out.append("  " + ", ".join(bits))
        storm = []
        if v.get("storm_restarts") is not None:
            storm.append(f"{v['storm_restarts']} supervised restarts")
        rec = v.get("recovery_s_by_fault") or {}
        if rec:
            storm.append("recovery " + ", ".join(
                f"{site} {s}s" for site, s in sorted(rec.items())))
        if v.get("storm_bitwise_journals") is not None:
            storm.append("journals bitwise"
                         if v["storm_bitwise_journals"]
                         else "journals DIVERGED")
        if v.get("recovered_compiled") is not None:
            storm.append("breaker recovered to compiled"
                         if v["recovered_compiled"]
                         else "breaker NOT recovered")
        if v.get("feeder_skipped"):
            storm.append(f"{v['feeder_skipped']} poisoned snapshot(s) "
                         f"skipped")
        if storm:
            out.append("  storm: " + ", ".join(storm))
        if v.get("weakest_stage"):
            out.append(f"  weakest stage: {v['weakest_stage']} — "
                       f"{v.get('weakest_detail')}")
        for i, fx in enumerate(v.get("fixes") or [], 1):
            out.append(f"  fix {i}: {fx}")
        if not v.get("fixes"):
            out.append("  verdict: healthy — the whole loop held its "
                       "SLO contract, restarts resumed bitwise, and "
                       "serving recovered compiled after the storm")
    for v in doc.get("tuning", []):
        out.append(f"\n== tuning sweep: {v['workload']} ==")
        if v.get("error"):
            out.append(f"  ERROR: {v['error']}")
            continue
        line = f"  {v.get('points_per_sec')} points/s"
        if v.get("speedup_vs_serial") is not None:
            line += (f" ({v['speedup_vs_serial']}x the serial candidate "
                     f"loop with ASHA; {v.get('sweep_full_speedup')}x "
                     f"full-depth)")
        out.append(line)
        bits = [f"{v.get('points')} points",
                f"{v.get('compiled_programs')} compiled program(s)",
                f"{v.get('rungs')} rungs"]
        if v.get("pruned_fraction") is not None:
            bits.append(f"{v['pruned_fraction']:.0%} pruned")
        bits.append(f"winner {'MATCHES' if v.get('winner_match') else 'DIFFERS from'} serial grid")
        if v.get("parity"):
            bits.append(f"per-point parity {v['parity']}")
        out.append("  " + ", ".join(bits))
        for i, fx in enumerate(v.get("fixes") or [], 1):
            out.append(f"  fix {i}: {fx}")
        if not v.get("fixes"):
            out.append("  verdict: healthy — one program per compile "
                       "group, deterministic pruning, serial-bitwise "
                       "per-point results")
    for v in doc.get("compile", []):
        out.append(f"\n== compile plane: {v['label']} ==")
        total = (v.get("compiles") or 0) + (v.get("hits") or 0)
        rate = (f"{(v.get('hits') or 0) / total:.0%}"
                if total else "n/a")
        out.append(f"  {v.get('compiles')} compiles / "
                   f"{v.get('hits')} hits ({rate} hit rate), "
                   f"{v.get('disk_hits') or 0} disk hit(s) "
                   f"({v.get('deserialize_s') or 0.0}s deserialize), "
                   f"{v.get('evictions')} evictions, "
                   f"{v.get('wall_s')}s compile wall, "
                   f"{v.get('storms')} storm(s)")
        caches = v.get("caches") or {}
        if caches:
            w = max(len(n) for n in caches)
            out.append(f"  {'cache'.ljust(w)}  size/cap   hits  misses"
                       f"  disk-hits  hit-rate  storms")
            for n, c in caches.items():
                hr = c.get("hit_rate")
                out.append(
                    f"  {n.ljust(w)}  "
                    f"{c.get('size')}/{c.get('capacity') or '-':>3}  "
                    f"{c.get('hits'):>6,}  {c.get('misses'):>6,}  "
                    f"{c.get('disk_hits') or 0:>9,}  "
                    f"{hr:>7.1%}  {c.get('storms'):>6}"
                    if hr is not None else
                    f"  {n.ljust(w)}  "
                    f"{c.get('size')}/{c.get('capacity') or '-':>3}  "
                    f"{c.get('hits'):>6,}  {c.get('misses'):>6,}  "
                    f"{c.get('disk_hits') or 0:>9,}  "
                    f"{'-':>7}  {c.get('storms'):>6}")
        cold = v.get("cold_start_s") or {}
        if cold:
            out.append("  cold start (time to first program): "
                       + ", ".join(f"{k} {s}s"
                                   for k, s in cold.items()))
        ld = v.get("last_diff")
        if ld:
            out.append("  last plan diff: " + "; ".join(
                f"{d.get('dim')} {d.get('old')}→{d.get('new')}"
                for d in ld if isinstance(d, dict)))
        for i, fx in enumerate(v.get("fixes") or [], 1):
            out.append(f"  fix {i}: {fx}")
        if not v.get("fixes"):
            out.append("  verdict: healthy — every compile is "
                       "attributed, no storms, no cold-start-dominated "
                       "restart")
    hbm = doc.get("hbm")
    if hbm is not None:
        out.append("\n== HBM (live device buffers) ==")
        if hbm:
            w = max(len(f"{r.get('workload')}/{r['scope']}") for r in hbm)
            out.append(f"  {'scope'.ljust(w)}  snapshots       last        max")
            for r in hbm:
                key = f"{r.get('workload')}/{r['scope']}"
                out.append(f"  {key.ljust(w)}  {r['count']:9,}  "
                           f"{_fmt_bytes(r['last_bytes']):>9}  "
                           f"{_fmt_bytes(r['max_bytes']):>9}")
        else:
            out.append("  (no boundary snapshots recorded)")
        don = doc.get("donation")
        if don:
            verdict = "VERIFIED" if don.get("verified") else "NOT VERIFIED"
            out.append(f"  donation: {verdict} — donated run holds "
                       f"{don.get('ratio')}x the undonated resident "
                       f"state ({_fmt_bytes(don['donated_peak_bytes'])} "
                       f"vs {_fmt_bytes(don['undonated_peak_bytes'])}, "
                       f"state {_fmt_bytes(don['state_bytes'])})")
        else:
            out.append("  donation: not measured (run bench under "
                       "ALINK_TPU_PROFILE=1)")
    met = doc.get("metrics")
    if met:
        out.append("\n== run metrics ==")
        cache = met.get("cache") or {}
        if cache:
            hits = cache.get("hit", 0)
            miss = cache.get("miss", 0)
            rate = f"{100.0 * hits / (hits + miss):.0f}%" \
                if hits + miss else "n/a"
            out.append(f"  program cache: {int(hits)} hits / "
                       f"{int(miss)} misses ({rate} hit rate)")
        col = met.get("collectives") or {}
        if col:
            out.append("  collective calls: " + ", ".join(
                f"{k}={int(n):,}" for k, n in sorted(col.items())))
    if doc.get("capture_error"):
        out.append(f"\nNOTE: xprof capture degraded "
                   f"({doc['capture_error']}); attribution is "
                   f"timing-harness only")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="doctor.py", description=__doc__.splitlines()[0])
    ap.add_argument("--run-dir", metavar="DIR",
                    help="a bench.py --run-dir directory (bench.json / "
                         "profile.json / metrics.jsonl inside)")
    ap.add_argument("--bench", metavar="PATH",
                    help="a BENCH_*.json / bench.json dump")
    ap.add_argument("--profile", metavar="PATH",
                    help="an alink_tpu_profile_v1 JSON "
                         "(ProfileCollector.export)")
    ap.add_argument("--metrics", metavar="PATH",
                    help="a MetricsRegistry.dump() JSONL")
    ap.add_argument("--url", metavar="URL_OR_DIR",
                    help="a LIVE admin endpoint (http://host:port — "
                         "scrapes its /varz) or a tools/fleetz.py "
                         "--snapshot directory; the metrics verdict "
                         "renders against the running process instead "
                         "of a dump file")
    ap.add_argument("--bundle", metavar="PATH",
                    help="a post-mortem bundle "
                         "(common/postmortem.py, ISSUE 18): renders "
                         "the incident verdict + per-request timeline "
                         "table OFFLINE, with the bundle's frozen "
                         "metrics feeding the run-level verdicts")
    ap.add_argument("--peak-tflops", type=float,
                    default=DEFAULT_PEAK_TFLOPS)
    ap.add_argument("--peak-hbm-gbps", type=float,
                    default=DEFAULT_PEAK_HBM_GBPS)
    ap.add_argument("--json", action="store_true",
                    help="emit the verdict document as JSON")
    args = ap.parse_args(argv)
    bench_path, profile_path, metrics_path = \
        args.bench, args.profile, args.metrics
    if args.run_dir:
        d = args.run_dir
        if not os.path.isdir(d):
            print(f"doctor.py: {d}: not a directory", file=sys.stderr)
            return 1
        bench_path = bench_path or _first_existing(d, "bench.json")
        profile_path = profile_path or _first_existing(d, "profile.json")
        metrics_path = metrics_path or _first_existing(d, "metrics.jsonl")
    compilez_path = (_first_existing(args.run_dir, "compilez.json")
                     if args.run_dir else None)
    if not bench_path and not profile_path and not args.url \
            and not args.bundle and not compilez_path:
        print("doctor.py: need --run-dir, --bench, --profile, --url or "
              "--bundle (nothing to diagnose)", file=sys.stderr)
        return 1
    bundle = None
    compilez: List[Tuple[str, Any]] = []
    try:
        bench = load_bench(bench_path) if bench_path else None
        profile = load_json(profile_path) if profile_path else None
        metrics = _metrics_summary(metrics_path) if metrics_path else None
        if compilez_path:
            compilez.append(("run-dir", load_json(compilez_path)))
        if args.url:
            live = _summarize_metric_records(_records_from_url(args.url))
            metrics = live if metrics is None else {**metrics, **live}
            compilez.extend(_compilez_from_url(args.url))
        if args.bundle:
            bundle = _load_postmortem(args.bundle)
            frozen = _summarize_metric_records(
                [r for r in bundle.get("metrics") or []
                 if isinstance(r, dict)])
            metrics = frozen if metrics is None else {**metrics,
                                                      **frozen}
            cz = (bundle.get("extra") or {}).get("compilez")
            if cz:
                compilez.append(("post-mortem bundle", cz))
    except (OSError, ValueError) as e:
        print(f"doctor.py: {e}", file=sys.stderr)
        return 1
    doc = diagnose(bench, profile, metrics,
                   args.peak_tflops, args.peak_hbm_gbps,
                   compilez=compilez)
    if bundle is not None:
        doc["postmortem"] = _postmortem_section(bundle)
    if not doc["workloads"] and not doc.get("hbm") \
            and (bench is not None or profile is not None):
        # (a --url-only scrape has no profiled workloads by design)
        print("doctor.py: no profiled workloads found — was the capture "
              "run with ALINK_TPU_PROFILE=1?", file=sys.stderr)
        # still render what exists (e.g. a bench without profile rows)
    if args.json:
        json.dump(doc, sys.stdout, indent=1)
        sys.stdout.write("\n")
    else:
        print(render(doc))
    return 0


def _first_existing(d: str, name: str) -> Optional[str]:
    p = os.path.join(d, name)
    return p if os.path.exists(p) else None


if __name__ == "__main__":
    raise SystemExit(main())
