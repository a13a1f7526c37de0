"""Regenerate README.md's measured-performance table FROM the latest
driver-captured BENCH_r0N.json, so the README can never disagree with the
recorded evidence again (VERDICT r2 #1: README claimed ALS "~5-19M,
~11-50x" while BENCH_r02 recorded 2.4M / 9.2x).

Usage: python tools/gen_readme_table.py [BENCH_file.json]
(defaults to the highest-numbered driver-captured BENCH_r*.json in the
repo root; a locally saved capture like BENCH_local_r3.json must be
passed explicitly).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LABELS = {
    "logreg_criteo": "LogReg L-BFGS, Criteo-shape CTR (FTRLExample warm start)",
    "kmeans_iris": "KMeans, iris x10k (KMeansExample)",
    "softmax_mnist": "Softmax, MNIST shape (mnist.ipynb)",
    "ftrl_criteo": "FTRL stream, strict per-sample order (ftrl_demo)",
    "gbdt_adult": "GBDT, adult shape (adult.ipynb)",
    "als_movielens": "ALS, MovieLens-1M shape (ALSExample)",
}

START = "<!-- BENCH_TABLE_START (generated by tools/gen_readme_table.py — do not edit by hand) -->"
END = "<!-- BENCH_TABLE_END -->"


def _fmt_sps(v: float) -> str:
    if v >= 1e9:
        return f"{v / 1e9:.1f}G"
    if v >= 1e6:
        return f"{v / 1e6:.1f}M"
    if v >= 1e3:
        return f"{v / 1e3:.0f}k"
    return f"{v:.0f}"


def render(workloads: dict, source_name: str) -> str:
    rows = []
    for key, label in LABELS.items():
        w = workloads.get(key)
        if not w or "samples_per_sec_per_chip" not in w:
            rows.append(f"| {label} | (failed) | — | — |")
            continue
        sps = _fmt_sps(w["samples_per_sec_per_chip"])
        vs = f"{w['vs_baseline']:.1f}x"
        peak = (f"{w['pct_chip_peak_flops']:.2f}%"
                if w.get("pct_chip_peak_flops") is not None else "—")
        rows.append(f"| {label} | {sps} | {vs} | {peak} |")
        if key == "ftrl_criteo" and "batch_mode_samples_per_sec_per_chip" in w:
            bsps = _fmt_sps(w["batch_mode_samples_per_sec_per_chip"])
            bvs = f"{w['batch_mode_vs_baseline']:.1f}x"
            bpeak = (f"{w['batch_mode_pct_chip_peak_flops']:.2f}%"
                     if w.get("batch_mode_pct_chip_peak_flops") is not None
                     else "—")
            rows.append("| FTRL stream, `update_mode=\"batch\"` + "
                        f"field-aware hashing | {bsps} | {bvs} | {bpeak} |")
    captured = ("driver-captured" if source_name.startswith("BENCH_r")
                else "locally captured on the same v5e chip")
    head = (f"Numbers below are machine-derived from `{source_name}` "
            f"({captured}); regenerate with "
            f"`python tools/gen_readme_table.py {source_name}`. \"% peak\" = issued "
            "FLOPs vs the v5e chip's 197 TFLOP/s bf16 ceiling — low "
            "percentages on sparse workloads mean gather/HBM-bound, "
            "which is the expected regime for classical ML.\n\n"
            "| Workload (reference entry point) | samples/sec/chip "
            "| vs 1 CPU worker | % chip peak FLOPs |\n|---|---|---|---|")
    return head + "\n" + "\n".join(rows)


def _balanced_object(s: str, open_idx: int):
    """Return s[open_idx:close+1] for the '{' at open_idx, or None if the
    object never closes inside s."""
    depth = 0
    for i in range(open_idx, len(s)):
        c = s[i]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0:
                return s[open_idx:i + 1]
    return None


def workloads_from_tail(tail: str) -> dict:
    """Recover the workloads dict from a driver capture whose 2000-byte
    stdout tail cut the head off bench.py's final combined JSON line
    (BENCH_r03.json: parsed=null). Every complete '"name": {...}' pair
    still present is balanced-parsed out; if the cut landed inside the
    FIRST workload's first key (always samples_per_sec_per_chip — every
    bench row dict starts with it), that fragment is grafted back."""
    lines = tail.strip().splitlines()
    if not lines:
        return {}
    line = lines[-1]
    out = {}
    for m in re.finditer(r'"(\w+)":\s*\{', line):
        if m.group(1) not in LABELS:
            continue
        obj = _balanced_object(line, m.end() - 1)
        if obj is not None:
            try:
                out[m.group(1)] = json.loads(obj)
            except ValueError:
                pass
    missing = [k for k in LABELS if k not in out]
    if len(missing) == 1 and not line.startswith("{"):
        # first unmatched '}' closes the truncated first workload
        depth = 0
        for i, c in enumerate(line):
            if c == "{":
                depth += 1
            elif c == "}":
                if depth == 0:
                    frag = line[:i + 1]
                    key_cut = frag.split(":", 1)[0].strip()
                    full = '"samples_per_sec_per_chip"'
                    if full.endswith(key_cut):
                        try:
                            out[missing[0]] = json.loads(
                                "{" + full + frag[len(key_cut):])
                        except ValueError:
                            pass
                    break
                depth -= 1
    return out


def newest_capture():
    """Highest-numbered driver capture path, or None. Deterministic:
    numeric ordering, not mtime (mtime is checkout-order noise on a
    fresh clone); local captures must be passed explicitly."""
    cands = sorted(glob.glob(os.path.join(ROOT, "BENCH_r*.json")))
    return cands[-1] if cands else None


def load_workloads(path: str) -> dict:
    """The workloads dict from a driver capture, across every recorded
    schema generation (wrapped ``parsed``, r04+ compact final line,
    truncated-tail recovery). Raises SystemExit when nothing usable."""
    with open(path) as f:
        data = json.load(f)
    # driver files wrap the bench's final line under "parsed"; when the
    # combined line overflowed the driver's 2000-byte tail, parsed is
    # null and the workloads must be recovered from the tail text
    parsed = data.get("parsed", data) or {}
    workloads = parsed.get("workloads", {})
    if not workloads and "workloads_sps_vs" in parsed:
        # r04+ compact final line: name -> [sps, vs_baseline, pct_peak]
        for name, (sps, vs, pct) in parsed["workloads_sps_vs"].items():
            if name == "ftrl_criteo_batch":
                workloads.setdefault("ftrl_criteo", {}).update(
                    batch_mode_samples_per_sec_per_chip=sps,
                    batch_mode_vs_baseline=vs,
                    batch_mode_pct_chip_peak_flops=pct)
            else:
                workloads.setdefault(name, {}).update(
                    samples_per_sec_per_chip=sps, vs_baseline=vs,
                    pct_chip_peak_flops=pct)
    if not workloads and "tail" in data:
        workloads = workloads_from_tail(data["tail"])
    if not workloads:
        raise SystemExit(f"{path}: no workloads key")
    return workloads


def main():
    if len(sys.argv) > 1:
        path = sys.argv[1]
    else:
        path = newest_capture()
        if path is None:
            raise SystemExit("no BENCH_r*.json found; pass a file explicitly")
    workloads = load_workloads(path)
    table = render(workloads, os.path.basename(path))
    rp = os.path.join(ROOT, "README.md")
    with open(rp) as f:
        readme = f.read()
    block = f"{START}\n{table}\n{END}"
    if START in readme:
        readme = re.sub(re.escape(START) + r".*?" + re.escape(END), block,
                        readme, flags=re.S)
    else:
        raise SystemExit("README.md lacks the BENCH_TABLE markers")
    with open(rp, "w") as f:
        f.write(readme)
    print(f"README table regenerated from {os.path.basename(path)}")


if __name__ == "__main__":
    main()
