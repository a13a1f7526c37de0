"""The benchmark's data files, arithmetic and trace reduction (no cell is
run here; ``test_bench_cells.py`` runs them at a tiny size)."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench(parked=False):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if parked:
        with open(os.path.join(BENCH, "parked.json")) as f:
            more = json.load(f)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[key] = bench[key] + more[key]
    return bench


BOTH = pytest.mark.parametrize("parked", [False, True],
                               ids=["shipped", "with_parked"])


def _json_files(sub):
    d = os.path.join(BENCH, sub)
    return sorted(os.path.join(d, n) for n in os.listdir(d) if n.endswith(".json"))


# -- the data files ---------------------------------------------------------

@BOTH
def test_benchmark_json_has_the_contracts_keys_and_names(parked):
    b = _bench(parked)
    assert set(_bench()) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in b[k]]
    for n in names + [w["traffic"] for w in b["workloads"]]:
        assert NAME.match(n), n
    for k in ("configs", "workloads"):
        ns = [x["name"] for x in b[k]]
        assert len(ns) == len(set(ns))
    ms = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(ms) == len(set(ms))
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(pairs) // 4)


@BOTH
def test_every_cell_config_traffic_and_metric_resolves_to_files(parked):
    b = _bench(parked)
    cells = {w["name"] for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for c in configs.values():
        assert c["file"].startswith("benchmark/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        assert os.path.isfile(os.path.join(ROOT, doc["reference"]))
        assert doc["limits"], "a configuration states its limits"
    assert {w["config"] for w in b["workloads"]} == set(configs)
    for w in b["workloads"]:
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            tr = json.load(f)
        assert os.path.isfile(os.path.join(
            BENCH, "generators", tr["generator"] + ".py"))
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        # every cell that reports it reports the end-to-end metric it moves
        movers = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= movers, m["name"]
        assert os.path.isfile(os.path.join(
            BENCH, "readers", m["name"].split(".")[0] + ".py")), m["name"]
    for w in b["workloads"]:
        reported = [m for m in b["end_to_end"]
                    if w["name"] in m.get("workloads", cells)]
        assert len(reported) >= 2, w["name"]
        assert any(w["name"] in m["workloads"] for m in b["per_layer"])


@pytest.mark.parametrize("sub", ["configs", "traffic"])
def test_every_data_file_loads_and_belongs_to_a_cell(sub):
    b = _bench(parked=True)
    used = ({os.path.basename(c["file"]) for c in b["configs"]}
            if sub == "configs"
            else {w["traffic"] + ".json" for w in b["workloads"]})
    files = _json_files(sub)
    assert files
    for path in files:
        with open(path) as f:
            json.load(f)
        assert os.path.basename(path) in used, path


@BOTH
def test_layers_with_rooflines_report_a_whole_step_mfu_beside_them(parked):
    b = _bench(parked)
    for m in b["per_layer"]:
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in b["per_layer"]), m["name"]


def test_peaks_name_their_source_and_an_unknown_device_is_an_error():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    assert "cpu" not in peaks


# -- operations and bytes against hand counts ---------------------------------

def test_ftrl_step_counts_match_a_hand_count():
    from benchmark import opcount
    # one row, one entry: 23 operations and 6 for the row; z, n read and
    # written (16 B), index and value (8 B), label (4 B)
    assert opcount.ftrl_step(1, 1) == (29, 28)
    # the cell's micro-batch: 4,096 rows of 40 entries
    ops, byt = opcount.ftrl_step(4096, 40)
    assert ops == 4096 * (40 * 23 + 6) == 3_792_896
    assert byt == 4096 * (40 * 24 + 4) == 3_948_544
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert opcount.bound_by(ops, byt, peak) == "memory"
    assert opcount.least_seconds(ops, byt, peak) == pytest.approx(4.8212e-6, rel=1e-4)


def test_linear_score_counts_match_a_hand_count():
    from benchmark import opcount
    assert opcount.linear_score(1, 1) == (3, 16)
    ops, byt = opcount.linear_score(512, 39)
    assert ops == 512 * 79 and byt == 512 * (39 * 12 + 4)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert opcount.least_seconds(ops, byt, peak) == byt / 819e9


# -- the open loop's schedule and clock ---------------------------------------

def test_same_seed_same_schedule_and_a_poisson_rate():
    from benchmark.generators import open_poisson as op
    a = op.schedule(2 ** 31 + 11, 5000.0, 2.0, 0)
    b = op.schedule(2 ** 31 + 11, 5000.0, 2.0, 0)
    c = op.schedule(2 ** 31 + 12, 5000.0, 2.0, 0)
    assert np.array_equal(a, b) and not np.array_equal(a[:50], c[:50])
    assert np.all(np.diff(a) > 0) and a[0] >= 0 and a[-1] < 2.0
    assert abs(len(a) - 10000) < 400          # +-4 sigma of a Poisson count
    # every seed offers the same rate: the work does not change with it
    assert abs(len(c) - len(a)) < 600


def test_percentile_is_nearest_rank():
    from benchmark.generators.open_poisson import percentile
    vals = list(range(1, 101))
    assert percentile(vals, 95.0) == 95 and percentile(vals, 50.0) == 50
    assert percentile([7.0], 95.0) == 7.0 and percentile([], 95.0) == 0.0


class _StallingServer:
    """Answers at once, except that ``submit`` number ``stall_at`` blocks
    for ``stall_s``: the requests due meanwhile must be charged the wait."""

    class _Fut:
        def done(self):
            return True

        def result(self, timeout=None):
            return ("row",)

    def __init__(self, stall_at, stall_s):
        self.n, self.stall_at, self.stall_s = 0, stall_at, stall_s

    def submit(self, row):
        import time
        if self.n == self.stall_at:
            time.sleep(self.stall_s)
        self.n += 1
        return self._Fut()


def test_latency_from_due_time_counts_a_stall():
    import time
    from benchmark.generators.open_poisson import Phase
    due = np.arange(100) * 0.002                     # 500 a second, 0.2 s
    ph = Phase(due, ["r"], 1, range(4))
    ph.drive(_StallingServer(stall_at=10, stall_s=0.1), time.perf_counter(), 0.3)
    assert ph.n_sent == 100 and ph.n_seen == 100
    assert ph.answers == {k: ("row",) for k in range(4)} and not ph.failed_at
    assert all(f is None for f in ph.futures), "futures are let go once noted"
    lat = ph.seen - (ph.t0 + ph.due)
    late = ph.sent - (ph.t0 + ph.due)
    # the requests due during the stall waited for it, though each was
    # answered the moment it was sent
    assert lat[11] > 0.08 and lat[30] > 0.03
    assert lat[5] < 0.02 and lat[95] < 0.02
    assert late[11] > 0.08            # and the sender says how late it ran


# -- the trace reduction on the recorded trace --------------------------------

def test_trace_reduction_busy_idle_ops_and_gaps():
    from benchmark import trace_reduce as T
    prof = T.load(os.path.join(FIXTURES, "synthetic.xplane.pb"))
    assert [p.name for p in T.device_planes(prof)] == ["/device:TPU:0"]
    r = T.reduce_profile(prof, min_gap_ns=100)
    # window = the bench:window span, 10 us; ops cover 2 + 4 + 0.004 us (the
    # two fusion.7 lie inside while.2 and add nothing to the union)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(6.004e-6)
    assert r["devices"] == 1
    assert r["op_s"] == pytest.approx({"fusion.1": 2.004e-6, "while.2": 4e-6,
                                       "fusion.7": 2e-6})
    assert r["op_calls"] == {"fusion.1": 2, "while.2": 1, "fusion.7": 2}
    assert r["device_ops"][0][0] == "while.2"
    # gaps: [3,4) us under bench:pull until 3.6; [8,9) under outer from 8.1
    # with take inside it from 8.2 to 8.7; [9.004,11) under outer until 10
    assert r["gap_s"] == pytest.approx({"pull": 0.6e-6, "take": 0.5e-6,
                                        "outer": 1.396e-6,
                                        "_no_span_": 1.5e-6})
    assert sum(r["gap_s"].values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert T.module_time(r, "jit_shard_fn") == (pytest.approx(4e-6), 1)
    assert T.module_time(r, "jit_step") == (pytest.approx(2.004e-6), 2)
    assert T.module_time(r, "jit_absent") == (0.0, 0)


def test_short_gaps_are_the_devices_own_and_not_charged_to_spans():
    from benchmark import trace_reduce as T
    prof = T.load(os.path.join(FIXTURES, "synthetic.xplane.pb"))
    r = T.reduce_profile(prof, min_gap_ns=1500)
    assert r["gap_s"] == pytest.approx({"_under_1us_": 2e-6, "outer": 0.996e-6,
                                        "_no_span_": 1e-6})


def test_a_trace_with_no_device_op_is_refused():
    from benchmark import trace_reduce as T
    prof = T.load(os.path.join(FIXTURES, "synthetic.xplane.pb"))
    with pytest.raises(ValueError, match="no operation ran"):
        T.reduce_profile(prof, window=(20_000, 30_000))


def test_interval_arithmetic():
    from benchmark import trace_reduce as T
    u = T.union([(5, 7), (1, 3), (2, 4), (7, 7), (6, 9)])
    assert u == [(1, 4), (5, 9)] and T.length(u) == 7
    assert T.complement(u, 0, 10) == [(0, 1), (4, 5), (9, 10)]
    assert T.short_name("%fusion.75 = f32[8]{0} fusion(%a), kind=kLoop") == "fusion.75"


# -- readers return nothing where there is nothing to read ---------------------

def test_readers_return_none_without_a_trace_and_never_zero_for_a_share():
    import importlib
    import types
    ctx = types.SimpleNamespace(reduced=None, facts={}, config={
        "step_program": "jit_shard_fn", "score_program": "jit__sparse"})
    for base in ("device_idle", "ftrl_step_dev", "ftrl_step_roofline",
                 "ftrl_step_mfu", "serve_step_mfu", "serve_step_roofline",
                 "snapshot_ms", "peak_hbm", "gen_late", "flood_p95",
                 "compiles_in_window"):
        reader = importlib.import_module("benchmark.readers." + base)
        assert reader.read(ctx) is None, base


# -- importing the benchmark loads no accelerator library ----------------------

def test_importing_every_module_touches_no_jax_and_no_tpu_library():
    mods = ["benchmark.run", "benchmark.controls", "benchmark.trace_reduce",
            "benchmark.spans", "benchmark.opcount", "benchmark.data",
            "benchmark.generators.closed_drain",
            "benchmark.generators.open_poisson",
            "benchmark.reference.ftrl", "benchmark.reference.logistic"]
    mods += ["benchmark.readers." + n[:-3] for n in sorted(os.listdir(
        os.path.join(BENCH, "readers"))) if n.endswith(".py") and n != "__init__.py"]
    code = ("import sys\n" + "".join(f"import {m}\n" for m in mods)
            + "bad = [m for m in sys.modules if m.split('.')[0] in "
              "('jax', 'jaxlib', 'libtpu', 'alink_tpu')]\n"
              "assert not bad, bad\n")
    env = dict(os.environ, PYTHONPATH=ROOT)
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]
