#!/usr/bin/env bash
# perf_gate.sh — the ONE perf-regression command the builder and CI both run
# (ISSUE 6 satellite; workflow: docs/performance.md "Quick bench gate").
#
#   tools/perf_gate.sh            run `bench.py --quick` (chained-FTRL +
#                                 fused-histogram kernels on the measured
#                                 path), diff against the committed gate
#                                 baseline with bench_compare --threshold
#                                 and --baseline-provenance; exit != 0 on
#                                 regression or provenance mismatch.
#                                 First run (no baseline) promotes the
#                                 fresh capture and exits 0.
#   tools/perf_gate.sh --update   re-baseline after an accepted perf change
#                                 (the diff of PERF_GATE_BASE shows it).
#
# env: PERF_GATE_THRESHOLD  regression gate percent (default 30 — quick
#                           fixtures are small, so the bar is loose; the
#                           full-suite captures are the publishable rows)
#      PERF_GATE_BASE       baseline artifact (default BENCH_quick_base.json)
set -euo pipefail
cd "$(dirname "$0")/.."

# static gate first (ISSUE 7): the compiled-program invariant analyzer.
# Cheap (pure AST, no jax), and a staleness/collective/callback violation
# should fail the gate before any benchmark spends minutes measuring a
# program that is structurally wrong. Intentional exceptions live in
# tools/lint_baseline.json with written justifications.
python -m tools.lint --strict

# 4-device sharded-serve smoke (ISSUE 11): fresh 1- and 4-device
# children serve the SAME feature-sharded model through mesh-sharded
# bucket programs — probe digests must match BITWISE across mesh sizes
# and a hot-swap storm must complete with zero torn responses. Exits 5
# (its own code) so a multi-chip-serving regression names itself.
python tools/serve_shard_bench.py --smoke

# tuning-sweep smoke (ISSUE 12): a small grid through BOTH paths —
# every sweep point bitwise vs its serial fit, full+ASHA winner
# identical to the serial grid's, deterministic rungs, ONE compiled
# program per carry-resident group, and the ASHA sweep not slower than
# the serial loop. Exits 6 (its own code) so a sweep regression names
# itself.
python tools/sweep_smoke.py

# Pallas kernel-tier smoke (ISSUE 13): interpret-mode parity of all
# three hand-written kernels in a fresh 4-device f64 child — FTRL
# scatter bitwise vs the XLA step, chained matvec <= the pinned 1e-12,
# fused serve score bitwise vs seq_chunk_sum per bucket + bf16/int8
# label-exact — and the demotion warning fires EXACTLY once when the
# backend is unavailable. Exits 7 (its own code) so a kernel-tier
# regression names itself.
python tools/kernel_smoke.py

# chaos-storm smoke (ISSUE 14): a live PredictServer under a scripted
# ALINK_TPU_FAULT_INJECT storm (transient dispatch errors + injected
# latency + one corrupt FTRL snapshot + a concurrent swap storm) must
# hold the SLO contract — zero torn responses, zero silent drops
# (results + typed rejections == submissions), deadline sheds are
# typed, and the circuit breaker measurably recovers to the COMPILED
# path once the storm clears. Exits 8 (its own code) so a resilience
# regression names itself.
python tools/chaos_smoke.py

# whole-loop online-DAG smoke (ISSUE 15): the supervised ingest->FTRL->
# hot-swap-serving->windowed-eval DAG under a scripted storm across ALL
# fault sites at once — trainer kill + checkpoint fault (supervised
# restart-from-checkpoint, journals BITWISE vs the clean run), dispatch
# error storm + corrupt snapshot (breaker degradation with measured
# compiled recovery, poisoned snapshot skipped once), latency +
# deadline sheds — with the SloContract's typed verdicts matching the
# injected storm. Exits 9 (its own code) so a whole-loop regression
# names itself.
python tools/e2e_smoke.py

# live-operations-plane smoke (ISSUE 16): the admin endpoint armed on
# a PredictServer and the online DAG under a dispatch-error storm —
# /healthz 503 while the real breaker is open and 200 after recovery,
# the fast-window SLO burn alert fires (readyz 503) and clears, and
# every mid-storm /metrics scrape parses with measured latency. Exits
# 10 (its own code) so an observability regression names itself.
python tools/adminz_smoke.py

# multi-tenant fleet smoke (ISSUE 17): a 24-tenant fleet on a budget
# that holds only half of it, under a swap storm multiplexed through
# ONE ModelStreamFeeder — zero cross-tenant leakage proven bitwise
# (per serving bucket shape) through concurrent swaps + LRU eviction/
# re-admission, coalesced batches actually forming, zero failed
# requests. Exits 11 (its own code) so a fleet-isolation regression
# names itself.
python tools/fleet_smoke.py

# post-mortem capture smoke (ISSUE 18): a breaker-tripping dispatch
# storm plus an SLO fast-window burn cascade against an armed
# ALINK_TPU_POSTMORTEM_DIR — exactly ONE bundle lands atomically (the
# second trigger debounced, zero .tmp leftovers), and a fresh
# interpreter renders the verdict + one request's full
# admit->...->decode lifetime from the bundle ALONE (doctor --bundle,
# trace --trace-id). Exits 12 (its own code) so an incident-capture
# regression names itself.
python tools/postmortem_smoke.py

# compile-plane ledger smoke (ISSUE 19): a serve dtype flip under load
# against the compile ledger — warm-up compiles are recorded, steady-
# state traffic records ZERO events (hits never masquerade as
# compiles), the flip recompiles exactly the warmed program set with
# every event's structural diff naming ALINK_TPU_SERVE_DTYPE f32→int8
# and no other cache moving, and a fresh interpreter renders the
# verdict offline from the run-dir compilez.json (doctor --run-dir).
# Exits 13 (its own code) so a compile-attribution regression names
# itself.
python tools/compilez_smoke.py

# cold-start smoke (ISSUE 20): two fresh interpreters share one AOT
# artifact directory — the first compiles and exports the demo serving
# grid, the second restarts against it and must answer its first
# request with ZERO serve-cache compiles (every program a ledger
# disk-hit), a first response faster than the cold baseline, and
# bitwise-identical predictions; doctor renders the warm-restart
# verdict offline from the run-dir compilez.json. Exits 14 (its own
# code) so a persistent-cache regression names itself.
python tools/coldstart_smoke.py

# docs freshness gate (ISSUE 15 satellite, VERDICT #2): the README's
# machine-generated performance/serving tables must match a fresh
# regeneration from the newest driver-captured BENCH dump, and the
# generated flag tables must match the registry — stale docs fail the
# gate instead of silently drifting from the recorded evidence.
python tools/gen_docs.py --check

BASE=${PERF_GATE_BASE:-BENCH_quick_base.json}
NEW=BENCH_quick.json
THRESH=${PERF_GATE_THRESHOLD:-30}

# the gate bench runs PROFILED (ALINK_TPU_PROFILE=1) into a throwaway
# run dir: the measured-profiling path (ISSUE 8) is on the gate's hot
# path, and the doctor smoke below fails the gate if its artifacts ever
# stop parsing. Harness marks cost ~2 perf_counter calls per dispatch;
# xprof capture stays off (ALINK_TPU_PROFILE_XPROF unset), so the gate
# numbers are unchanged within noise — baselines recorded by --update
# use the same command, keeping the comparison symmetric.
RUNDIR=$(mktemp -d -t alink_perf_gate.XXXXXX)
trap 'rm -rf "$RUNDIR"' EXIT

if [ "${1:-}" = "--update" ]; then
    ALINK_TPU_PROFILE=1 python bench.py --quick --out "$BASE" --run-dir "$RUNDIR"
    echo "perf_gate: baseline updated -> $BASE"
    exit 0
fi

ALINK_TPU_PROFILE=1 python bench.py --quick --out "$NEW" --run-dir "$RUNDIR"

# doctor smoke: the measured artifacts must parse and render (exit 0) —
# the profile path cannot rot silently behind its default-off flag
python tools/doctor.py --run-dir "$RUNDIR" > /dev/null
echo "perf_gate: doctor parsed the profiled run artifacts ($RUNDIR)"

# serve smoke (ISSUE 10): the quick suite's serving rows (micro-batcher
# + one hot-swap storm under load) must be present and CLEAN — zero
# failed and zero torn responses across the swaps. Throughput and p99
# regressions gate through bench_compare below (the compact map carries
# serve_logreg qps + serve_logreg_p99inv = 1/p99).
python - "$NEW" <<'PY'
import json, sys
doc = json.load(open(sys.argv[1]))
wl = doc.get("workloads") or {}
bad = []
for name in ("serve_logreg", "serve_ftrl_hot_swap", "serve_logreg_sharded"):
    row = wl.get(name)
    if not isinstance(row, dict) or "error" in row:
        bad.append(f"{name}: missing or errored ({(row or {}).get('error')})")
        continue
    if row.get("failed_requests"):
        bad.append(f"{name}: {row['failed_requests']} failed requests")
    if row.get("torn_responses"):
        bad.append(f"{name}: {row['torn_responses']} TORN responses")
    if name == "serve_ftrl_hot_swap" and (row.get("model_swaps") or 0) < 20:
        bad.append(f"{name}: only {row.get('model_swaps')} model swaps "
                   f"(need >= 20 under load)")
    if name == "serve_logreg" and row.get("parity") != "bitwise":
        bad.append(f"{name}: parity={row.get('parity')!r} (compiled path "
                   f"diverged from the host mapper)")
    if name == "serve_logreg_sharded" and row.get("parity") != "bitwise":
        bad.append(f"{name}: parity={row.get('parity')!r} (sharded bucket "
                   f"programs diverged across mesh sizes)")
# the chaos row's SLO contract (ISSUE 14): typed rejections during the
# storm are BY DESIGN; torn, silent, or a breaker that never recovered
# to the compiled path is what fails the gate
row = wl.get("serve_chaos")
if not isinstance(row, dict) or "error" in row:
    bad.append(f"serve_chaos: missing or errored "
               f"({(row or {}).get('error')})")
else:
    if row.get("torn_responses"):
        bad.append(f"serve_chaos: {row['torn_responses']} TORN responses")
    if row.get("silent_drops"):
        bad.append(f"serve_chaos: {row['silent_drops']} SILENT drops "
                   f"(a future resolved to neither a result nor a typed "
                   f"rejection)")
    if not row.get("recovered_compiled"):
        bad.append("serve_chaos: the breaker did not recover to the "
                   "compiled path after the storm")
    if not row.get("shed_requests"):
        bad.append("serve_chaos: the latency+deadline leg shed nothing")
# the whole-loop online-DAG row (ISSUE 15): the steady-state loop must
# close eval windows above the quality anchor (or carry its
# self-explaining convergence note), hold the SLO verdicts, and the
# recovery phase must have measured every stage's restart
row = wl.get("serve_online_e2e")
if not isinstance(row, dict) or "error" in row:
    bad.append(f"serve_online_e2e: missing or errored "
               f"({(row or {}).get('error')})")
else:
    if row.get("silent_drops"):
        bad.append(f"serve_online_e2e: {row['silent_drops']} SILENT "
                   f"drops in the DAG's scoring leg")
    if row.get("slo_ok") is False:
        bad.append(f"serve_online_e2e: SLO verdicts failed "
                   f"({row.get('slo')})")
    auc = row.get("final_window_auc")
    if (auc is None or auc < 0.75) and not row.get("auc_note"):
        bad.append(f"serve_online_e2e: final-window AUC {auc} below "
                   f"the 0.75 anchor with NO convergence note (the "
                   f"quality anchor must be discriminating or "
                   f"self-explaining)")
    if not row.get("recovered_compiled"):
        bad.append("serve_online_e2e: the recovery phase's breaker "
                   "never measurably re-served compiled")
    if not row.get("recovery_train_restart_s"):
        bad.append("serve_online_e2e: trainer restart recovery was "
                   "not measured")
# the multi-tenant fleet row (ISSUE 17): the leak proof must be bitwise
# over a real fleet (>= 100 tenants in the quick leg), the eviction
# storm must have run through the snapshot store, batches must coalesce,
# and p99 must stay in the same order as the single-model baseline
# (loose CI bound — the doctor verdict carries the tight one)
row = wl.get("serve_fleet")
if not isinstance(row, dict) or "error" in row:
    bad.append(f"serve_fleet: missing or errored "
               f"({(row or {}).get('error')})")
else:
    if (row.get("tenants") or 0) < 100:
        bad.append(f"serve_fleet: only {row.get('tenants')} concurrent "
                   f"tenants (need >= 100)")
    if row.get("leaked_rows"):
        bad.append(f"serve_fleet: {row['leaked_rows']} probe rows "
                   f"LEAKED another tenant's scores")
    if row.get("parity") != "bitwise":
        bad.append(f"serve_fleet: parity={row.get('parity')!r} "
                   f"(coalesced fleet path diverged from the "
                   f"per-tenant references)")
    if row.get("coalesce_rate") is None or row.get("evictions") is None:
        bad.append("serve_fleet: coalesce_rate/evictions missing — the "
                   "row lost its storm evidence")
    ratio = row.get("p99_vs_single")
    if ratio is not None and ratio > 25:
        bad.append(f"serve_fleet: fleet p99 runs {ratio}x the "
                   f"single-model baseline (gate bound 25x)")
if bad:
    print("perf_gate: serve smoke FAILED:", file=sys.stderr)
    for b in bad:
        print(f"  {b}", file=sys.stderr)
    sys.exit(4)
print("perf_gate: serve smoke clean (micro-batcher + hot swap under load)")
PY

if [ ! -f "$BASE" ]; then
    cp "$NEW" "$BASE"
    echo "perf_gate: no baseline found; promoted $NEW -> $BASE (gate passes trivially this run)"
    exit 0
fi

# the baseline must have been captured profiled too (rig.profile=true in
# the dump) — a pre-profiled-gate baseline makes the comparison
# asymmetric (the new run pays the harness's block_until_ready + marks,
# the old one didn't) and bench_compare's provenance fingerprint cannot
# see that; say so loudly instead of failing mysteriously at the gate
if ! grep -q '"profile": true' "$BASE"; then
    echo "perf_gate: WARNING: baseline $BASE was captured WITHOUT" >&2
    echo "  ALINK_TPU_PROFILE=1 (pre-profiled-gate); deltas include" >&2
    echo "  profiling overhead asymmetrically — refresh it with:" >&2
    echo "  tools/perf_gate.sh --update" >&2
fi

python tools/bench_compare.py "$BASE" "$NEW" --threshold "$THRESH" --baseline-provenance
