#!/usr/bin/env python
"""Render the BENCH_r01 -> rNN trajectory per workload.

One table: workload rows x bench-round columns (samples/sec/chip), an
ASCII sparkline of each workload's trajectory, and regression flags
where a workload dropped more than the threshold between adjacent
PRESENT rounds (a round that skipped the workload doesn't hide a drop
across it). The r01/r02 dumps predate ``workloads_sps_vs`` (r01 has only
the flagship metric; r02 carries a ``workloads`` detail map) — both are
handled.

Usage:
    python tools/bench_history.py                       # BENCH_r*.json in repo root
    python tools/bench_history.py r04.json r05.json r06.json
    python tools/bench_history.py --json [--threshold PCT]
                                  [--baseline-provenance]

``--threshold PCT`` exits 2 when any adjacent-round regression exceeds
PCT percent (the ``bench_compare --threshold`` contract).
``--baseline-provenance`` refuses (exit 3) a history whose adjacent
rounds carry DIFFERENT baseline fingerprints — cross-rig / re-pinned
captures make round-over-round ratios provenance artifacts, exactly the
``bench_compare --baseline-provenance`` rule; rounds without a
fingerprint (pre-r06) warn instead.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SPARK = "▁▂▃▄▅▆▇█"

# the flagship workload the r01 dump (final-line metric only) maps to
_FLAGSHIP = "logreg_criteo"


def load_round(path: str) -> Tuple[Dict[str, float], Optional[str], str]:
    """({workload: sps}, baseline_fp, mode) from any historical BENCH
    dump shape: the driver wrapper (``parsed``), ``workloads_sps_vs``
    (r03+), the r02 ``workloads`` detail map, or the bare r01 final
    line (flagship metric only)."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a bench dump")
    out: Dict[str, float] = {}
    wl = doc.get("workloads_sps_vs")
    if isinstance(wl, dict) and wl:
        for name, row in wl.items():
            sps = row[0] if isinstance(row, (list, tuple)) else row
            out[str(name)] = float(sps)
    elif isinstance(doc.get("workloads"), dict):
        for name, row in doc["workloads"].items():
            if isinstance(row, dict) \
                    and "samples_per_sec_per_chip" in row:
                out[str(name)] = float(row["samples_per_sec_per_chip"])
    elif doc.get("metric") and doc.get("value") is not None:
        out[_FLAGSHIP] = float(doc["value"])
    if not out:
        raise ValueError(f"{path}: no workload rates found "
                         f"(not a bench dump?)")
    fp = doc.get("baseline_fp")
    if fp is None and isinstance(doc.get("rig"), dict):
        fp = doc["rig"].get("baseline_fp")
    return out, (str(fp) if fp is not None else None), \
        str(doc.get("mode", "full"))


def default_rounds(directory: str) -> List[str]:
    """``BENCH_r*.json`` sorted by round number (r01 < r02 < ... <
    r10)."""
    def key(p: str):
        m = re.search(r"BENCH_r(\d+)", os.path.basename(p))
        return (int(m.group(1)) if m else 0, p)
    return sorted(glob.glob(os.path.join(directory, "BENCH_r*.json")),
                  key=key)


def _round_label(path: str) -> str:
    base = os.path.basename(path)
    m = re.search(r"BENCH_(r\d+)", base)
    return m.group(1) if m else base.replace(".json", "")


def sparkline(values: List[Optional[float]]) -> str:
    """Min-max normalized blocks; '·' for rounds the workload missed."""
    present = [v for v in values if v is not None]
    if not present:
        return ""
    lo, hi = min(present), max(present)
    span = hi - lo
    out = []
    for v in values:
        if v is None:
            out.append("·")
        elif span <= 0:
            out.append(_SPARK[-1])
        else:
            out.append(_SPARK[min(len(_SPARK) - 1,
                                  int((v - lo) / span * (len(_SPARK) - 1)
                                      + 0.5))])
    return "".join(out)


def build_history(paths: List[str]) -> Dict[str, Any]:
    """Unreadable rounds (e.g. the r03 dump whose final line arrived
    head-truncated: ``parsed: null``) are SKIPPED with a note, not
    fatal — one broken capture must not erase the whole trajectory."""
    rounds = []
    skipped = []
    series: Dict[str, List[Optional[float]]] = {}
    order: List[str] = []
    for p in paths:
        try:
            wl, fp, mode = load_round(p)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            skipped.append({"path": p, "label": _round_label(p),
                            "error": str(e)})
            continue
        i = len(rounds)
        rounds.append({"path": p, "label": _round_label(p),
                       "baseline_fp": fp, "mode": mode})
        for name, sps in wl.items():
            if name not in series:
                series[name] = [None] * i
                order.append(name)
            series[name].append(sps)
        for name in order:
            if len(series[name]) < i + 1:
                series[name].append(None)
    return {"rounds": rounds, "skipped": skipped,
            "workloads": {n: series[n] for n in order}}


def regressions(hist: Dict[str, Any],
                threshold_pct: float) -> List[Dict[str, Any]]:
    """Drops beyond the threshold between ADJACENT PRESENT rounds per
    workload: each round compares against the workload's last round
    that actually measured it, so a drop across a skipped round (r04 →
    missing → r06) is still flagged instead of silently vanishing."""
    out = []
    labels = [r["label"] for r in hist["rounds"]]
    for name, vals in hist["workloads"].items():
        last_v: Optional[float] = None
        last_i = -1
        for i, v in enumerate(vals):
            if v is None:
                continue
            if last_v is not None and last_v > 0:
                delta = 100.0 * (v - last_v) / last_v
                if delta < -abs(threshold_pct):
                    out.append({"workload": name,
                                "from": labels[last_i], "to": labels[i],
                                "old": last_v, "new": v,
                                "delta_pct": round(delta, 1)})
            last_v, last_i = v, i
    return out


def check_provenance(hist: Dict[str, Any]) -> Tuple[bool, List[str]]:
    """(ok, messages): ok=False means adjacent rounds carry DIFFERENT
    fingerprints — refuse, like ``bench_compare --baseline-provenance``.
    Fingerprint-less rounds produce warnings, never refusal."""
    msgs = []
    rounds = hist["rounds"]
    missing = [r["label"] for r in rounds if r["baseline_fp"] is None]
    if missing:
        msgs.append(f"WARNING: --baseline-provenance: no baseline "
                    f"fingerprint recorded in {', '.join(missing)} "
                    f"(pre-r06 capture?) — provenance not verifiable")
    ok = True
    # compare each fingerprinted round against the LAST KNOWN
    # fingerprint, not just the adjacent round — a fingerprint-less
    # round in between must not launder a rig change past the refusal
    last_fp: Optional[str] = None
    last_label: Optional[str] = None
    for r in rounds:
        fp = r["baseline_fp"]
        if fp is None:
            continue
        if last_fp is not None and fp != last_fp:
            ok = False
            msgs.append(
                f"REFUSING to compare {last_label} -> {r['label']}: "
                f"baseline fingerprints differ ({last_fp} vs {fp}) — "
                f"the captures ran against different rigs or a "
                f"re-pinned baseline, so round-over-round deltas would "
                f"be provenance artifacts, not code changes")
        last_fp, last_label = fp, r["label"]
    return ok, msgs


def _fmt(v: Optional[float]) -> str:
    return f"{v:,.0f}" if v is not None else "-"


def _display_name(name: str) -> str:
    """Serving rows measure requests, not samples: label them so the
    shared rate column stays readable (``serve_* (qps)``); the p99inv
    gate row is a reciprocal latency, called out explicitly."""
    if name.endswith("_p99inv"):
        return f"{name} (1/p99 s)"
    if name == "tuning_sweep":
        # the sweep row's rate is candidate points tuned per second
        # through the ASHA sweep engine (ISSUE 12)
        return f"{name} (points/s)"
    if name == "serve_fused":
        # whole-table scoring through the fused Pallas kernel, not the
        # micro-batcher: rows scored per second (ISSUE 13)
        return f"{name} (rows/s)"
    if name.startswith("serve_") and name.endswith("_sharded"):
        # multi-chip serving rows report per-chip throughput at the
        # widest measured mesh (ISSUE 11)
        return f"{name} (qps/chip)"
    if name == "serve_chaos":
        # throughput DURING the scripted fault storm — degraded by
        # design; the SLO contract rides the row's own fields (ISSUE 14)
        return f"{name} (qps under storm)"
    if name == "serve_fleet":
        # steady-state multi-tenant throughput with cross-tenant batch
        # coalescing; the leak proof / eviction storm evidence rides the
        # row's own fields (ISSUE 17)
        return f"{name} (qps, multi-tenant)"
    if name == "serve_online_e2e":
        # the whole online-learning DAG's steady-state scoring rate;
        # the SLO verdicts / recovery evidence ride the row (ISSUE 15)
        return f"{name} (qps, whole-loop DAG)"
    if name.startswith("serve_"):
        return f"{name} (qps)"
    return name


def render(hist: Dict[str, Any], regs: List[Dict[str, Any]]) -> str:
    labels = [r["label"] for r in hist["rounds"]]
    out = ["bench history (samples/sec/chip)"]
    names = list(hist["workloads"])
    if not names:
        return out[0] + "\n  (no workloads)"
    wn = max(len("workload"), *(len(_display_name(n)) for n in names))
    cols = [max(len(l), *(len(_fmt(hist["workloads"][n][i]))
                          for n in names))
            for i, l in enumerate(labels)]
    sw = max(len("trend"), len(labels))
    head = ("  " + "workload".ljust(wn) + "  "
            + "  ".join(l.rjust(c) for l, c in zip(labels, cols))
            + "  " + "trend".ljust(sw))
    out.append(head)
    out.append("  " + "-" * (len(head) - 2))
    flagged = {(r["workload"], r["to"]) for r in regs}
    for n in names:
        vals = hist["workloads"][n]
        cells = []
        for i, v in enumerate(vals):
            cell = _fmt(v)
            if (n, labels[i]) in flagged:
                cell += "!"
            cells.append(cell.rjust(cols[i]))
        out.append("  " + _display_name(n).ljust(wn) + "  "
                   + "  ".join(cells) + "  " + sparkline(vals))
    if regs:
        out.append("")
        for r in regs:
            out.append(f"  REGRESSION {r['workload']}: {r['from']} -> "
                       f"{r['to']}  {_fmt(r['old'])} -> {_fmt(r['new'])} "
                       f"({r['delta_pct']:+.1f}%)")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_history.py", description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*",
                    help="bench dumps in round order (default: "
                         "BENCH_r*.json in --dir, numerically sorted)")
    ap.add_argument("--dir", default=ROOT,
                    help="directory to glob BENCH_r*.json from "
                         "(default: repo root)")
    ap.add_argument("--threshold", type=float, metavar="PCT",
                    help="exit 2 when any adjacent-round regression "
                         "exceeds PCT percent")
    ap.add_argument("--json", action="store_true",
                    help="emit the history as JSON")
    ap.add_argument("--baseline-provenance", action="store_true",
                    help="refuse (exit 3) mixed-fingerprint round "
                         "sequences, like bench_compare")
    args = ap.parse_args(argv)
    paths = args.files or default_rounds(args.dir)
    if len(paths) < 2:
        print(f"bench_history.py: need at least two bench dumps, "
              f"found {len(paths)}", file=sys.stderr)
        return 1
    hist = build_history(paths)
    for s in hist["skipped"]:
        print(f"bench_history.py: skipping {s['label']}: {s['error']}",
              file=sys.stderr)
    if len(hist["rounds"]) < 2:
        print(f"bench_history.py: need at least two READABLE bench "
              f"dumps, got {len(hist['rounds'])}", file=sys.stderr)
        return 1
    if args.baseline_provenance:
        ok, msgs = check_provenance(hist)
        for m in msgs:
            print(f"bench_history.py: {m}", file=sys.stderr)
        if not ok:
            return 3
    modes = {r["mode"] for r in hist["rounds"]}
    if len(modes) > 1:
        print("WARNING: mixing quick and full captures — deltas "
              "reflect fixture sizes, not code changes", file=sys.stderr)
    regs = regressions(hist, args.threshold) \
        if args.threshold is not None else []
    if args.json:
        json.dump({"rounds": hist["rounds"],
                   "workloads": hist["workloads"],
                   "threshold_pct": args.threshold,
                   "regressions": regs}, sys.stdout, indent=1)
        sys.stdout.write("\n")
    else:
        print(render(hist, regs))
    return 2 if regs else 0


if __name__ == "__main__":
    raise SystemExit(main())
