"""The per-layer metrics that read the program's COARSE spans (ISSUE 35):
set-up accounted from inside the program (`setup_boot_s`, `setup_trace_s`,
`setup_compile_s`, `setup_state_s`, `setup_warm_s`) and the host between
two programs (`host_exposed_ms`). Each reader against a ring countable by
hand, the cases in which there is nothing to read, and a traced tiny run of
every cell in a process of its own, as the driver runs one, reporting the
new entries beside the old ones."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
AS = "TPU v5 lite"      # whose peaks a CPU run counts against; never printed

SETUP = ["setup_boot_s", "setup_trace_s", "setup_compile_s", "setup_state_s",
         "setup_warm_s"]
NEW = SETUP + ["host_exposed_ms"]
ORIGIN = 1000.0         # unix time of the hand-made ring's ``ts`` 0
STARTED = 990.0         # the process began 10 s before its tracer
SETUP_S = 25.0          # so the window starts 15 s after the origin


def _ev(name, t0, t1, id=None, parent=None, profiled=False, **args):
    ev = {"ph": "X", "name": name, "cat": "x", "ts": t0 * 1e6,
          "dur": (t1 - t0) * 1e6, "tid": 1}
    if id is not None:
        ev["id"] = id
    if parent is not None:
        ev["parent"] = parent
    if args:
        ev["args"] = args
    if profiled:
        ev["profiled"] = True
    return ev


def _hand_ring():
    """A process's life, seconds from the tracer's origin.

    0–2 the first session (boot: 10 s before the origin + 2). 3–4.5 a
    program of the harness's own (no parent): a trace 3.0–3.4 holding a
    nested one, a lowering 3.4–3.5, a compile 3.5–4.5. 5–7 ``ftrl.link``
    holding ``ftrl.warm_hash`` 5.5–6.5 and a trace 6.6–6.8; 8–9
    ``ftrl.state_alloc``; 9–9.5 ``ftrl.state_ship`` holding a cache hit
    9.1–9.3. 10.5–13.5 a warm fit whose execute span holds a trace
    11.1–11.6 (a nested trace and an eager constant's compile 11.4–11.5
    inside it), a lowering 11.6–11.7, a compile 11.7–12.2, then a wait.
    A compile 14.8–15.2 straddles the window's start at 15. After it, two
    traced fits: 0.5 s with 0.3 s of wait, 0.4 s with 0.1 s."""
    return [
        _ev("session.start", 0.0, 2.0, id=1, session=0, devices=1),
        _ev("jit.trace", 3.1, 3.2, id=2, fun_name="inner"),
        _ev("jit.trace", 3.0, 3.4, id=3, fun_name="make"),
        _ev("jit.lower", 3.4, 3.5, id=4, fun_name="jit(make)"),
        _ev("jit.compile", 3.5, 4.5, id=5, fun_name="jit(make)", cache="miss"),
        _ev("ftrl.link", 5.0, 7.0, id=10),
        _ev("ftrl.warm_hash", 5.5, 6.5, id=11, parent=10, bytes=64),
        _ev("jit.trace", 6.6, 6.8, id=12, parent=10, fun_name="plan"),
        _ev("ftrl.state_alloc", 8.0, 9.0, id=13, bytes=128),
        _ev("ftrl.state_ship", 9.0, 9.5, id=14, bytes=128),
        _ev("jit.compile", 9.1, 9.3, id=15, parent=14, fun_name="jit(put)",
            cache="hit"),
        _ev("link:KMeansTrainBatchOp", 10.0, 14.0, id=20),
        _ev("kmeans.fit", 10.5, 13.5, id=21, parent=20),
        _ev("comqueue.exec", 11.0, 13.0, id=22, parent=21),
        _ev("comqueue.execute", 11.0, 12.5, id=23, parent=22),
        _ev("jit.trace", 11.2, 11.3, id=24, parent=23, fun_name="dist"),
        _ev("jit.compile", 11.4, 11.5, id=25, parent=23, fun_name="jit(iota)",
            cache="off"),
        _ev("jit.trace", 11.1, 11.6, id=26, parent=23, fun_name="lloyd"),
        _ev("jit.lower", 11.6, 11.7, id=27, parent=23, fun_name="jit(lloyd)"),
        _ev("jit.compile", 11.7, 12.2, id=28, parent=23,
            fun_name="jit(lloyd)", cache="miss"),
        _ev("comqueue.wait", 12.5, 12.9, id=29, parent=22),
        _ev("jit.compile", 14.8, 15.2, id=30, fun_name="jit(late)",
            cache="miss"),
        _ev("kmeans.fit", 15.5, 16.0, id=40, profiled=True),
        _ev("comqueue.exec", 15.55, 15.95, id=41, parent=40, profiled=True),
        _ev("comqueue.wait", 15.6, 15.9, id=42, parent=41, profiled=True),
        _ev("kmeans.fit", 16.0, 16.4, id=43, profiled=True),
        _ev("comqueue.exec", 16.1, 16.3, id=44, parent=43, profiled=True),
        _ev("comqueue.wait", 16.15, 16.25, id=45, parent=44, profiled=True),
    ]


def _ctx(setup_s=SETUP_S):
    e2e = {} if setup_s is None else {"setup_s": setup_s}
    return types.SimpleNamespace(started=STARTED, e2e=e2e, facts={})


def _read(monkeypatch, metric, events, dropped=0, origin=ORIGIN, ctx=None):
    from benchmark import setup_spans
    monkeypatch.setattr(setup_spans, "ring",
                        lambda: (list(events), dropped, origin))
    reader = importlib.import_module("benchmark.readers." + metric)
    return reader.read(ctx or _ctx())


WANT = {
    # (1000 + 2) - 990
    "setup_boot_s": 12.0,
    # 3.0-3.5, 6.6-6.8 and 11.1-11.7 less the compile at 11.4-11.5 inside it;
    # the nested traces at 3.1 and 11.2 are counted once
    "setup_trace_s": 0.5 + 0.2 + 0.5,
    # 3.5-4.5, 9.1-9.3, 11.4-11.5, 11.7-12.2; not the one across the start
    "setup_compile_s": 1.0 + 0.2 + 0.1 + 0.5,
    # link 2.0 less its hash 1.0 and its trace 0.2; the hash 1.0; the
    # arrays 1.0; the ship 0.5 less the cache hit 0.2
    "setup_state_s": 0.8 + 1.0 + 1.0 + 0.3,
    # the fit's 3.0 less 11.1-12.2 of jit.* under it
    "setup_warm_s": 3.0 - 1.1,
    # (0.5 - 0.3) and (0.4 - 0.1) seconds, the mean, in ms
    "host_exposed_ms": 250.0,
}


@pytest.mark.parametrize("metric", NEW)
def test_reader_against_a_hand_made_ring(monkeypatch, metric):
    assert _read(monkeypatch, metric, _hand_ring()) == pytest.approx(
        WANT[metric])


def test_the_parts_are_disjoint_and_fit_inside_setup(monkeypatch):
    got = {m: _read(monkeypatch, m, _hand_ring()) for m in SETUP}
    assert sum(got.values()) == pytest.approx(20.0) and 20.0 <= SETUP_S


def test_self_time_takes_nested_children_once():
    from benchmark import setup_spans as S
    evs = _hand_ring()
    by = {e["id"]: e for e in evs}
    # execute 1.5 s: children 11.1-12.2 once, though two lie inside a third
    assert S.self_seconds(evs, by[23]) == pytest.approx(1.5 - 1.1)
    assert S.self_seconds(evs, by[22]) == pytest.approx(2.0 - 1.5 - 0.4)
    assert S.self_seconds(evs, by[10]) == pytest.approx(0.8)
    assert S.self_seconds(evs, by[11]) == pytest.approx(1.0)
    fits = S.fits_and_descendants(evs)
    assert [f["id"] for f, _ in fits] == [21, 40, 43]
    assert sorted(e["id"] for e in fits[0][1]) == list(range(22, 30))
    assert S.less([(0, 4), (2, 6)], [(1, 3), (2.5, 5), (9, 10)]) == \
        pytest.approx(6 - 4)


@pytest.mark.parametrize("metric", NEW)
def test_reader_finds_nothing_on_a_dropped_ring(monkeypatch, metric):
    """The oldest events fall out first, and set-up's are the oldest."""
    assert _read(monkeypatch, metric, _hand_ring(), dropped=1) is None


@pytest.mark.parametrize("metric", NEW)
def test_reader_finds_nothing_without_coarse_spans(monkeypatch, metric):
    """An older program: its tracer gives no origin, its ring holds what
    the profiler's session turned on and no ``session.start``, no
    ``jit.*``, no ``comqueue.wait``. ``None``, never a 0 and never an
    exception."""
    older = [_ev("kmeans.fit", 15.5, 16.0, id=40, profiled=True),
             _ev("comqueue.exec", 15.55, 15.95, id=41, parent=40,
                 profiled=True),
             _ev("ftrl.dispatch", 15.6, 15.7, id=42, profiled=True)]
    assert _read(monkeypatch, metric, older, origin=None) is None
    assert _read(monkeypatch, metric, older) is None
    assert _read(monkeypatch, metric, []) is None


@pytest.mark.parametrize("metric", SETUP)
def test_setup_needs_the_processs_first_session_and_the_windows_start(
        monkeypatch, metric):
    """A ring that begins after the process's boot (a tracer swapped in
    later, a test process) anchors no account; nor does a run that never
    reached its window."""
    later = [dict(e, args=dict(e["args"], session=3))
             if e["name"] == "session.start" else e for e in _hand_ring()]
    assert _read(monkeypatch, metric, later) is None
    assert _read(monkeypatch, metric, _hand_ring(),
                 ctx=_ctx(setup_s=None)) is None


def test_a_part_that_did_not_happen_reads_zero_or_nothing(monkeypatch):
    """A batch cell's ring has no ``ftrl.link`` and the drain's no fit:
    their readers find nothing. A process that compiled nothing still has
    an account, and its trace and compile parts are 0."""
    quiet = [e for e in _hand_ring() if not e["name"].startswith(
        ("jit.", "ftrl.", "kmeans.", "link:", "comqueue."))]
    assert [e["name"] for e in quiet] == ["session.start"]
    assert _read(monkeypatch, "setup_boot_s", quiet) == pytest.approx(12.0)
    assert _read(monkeypatch, "setup_trace_s", quiet) == 0.0
    assert _read(monkeypatch, "setup_compile_s", quiet) == 0.0
    assert _read(monkeypatch, "setup_state_s", quiet) is None
    assert _read(monkeypatch, "setup_warm_s", quiet) is None
    assert _read(monkeypatch, "host_exposed_ms", quiet) is None


def test_the_tracers_own_ring_is_what_the_readers_read(quiet_tracer):
    """``ring()`` against the real tracer: complete spans only, the drop
    count, and the origin's public accessor."""
    from alink_tpu.common.tracing import trace_instant, trace_span
    from benchmark import setup_spans as S
    with trace_span("a.fit", coarse=True):
        trace_instant("mark")            # fine, and no span
    events, dropped, origin = S.ring()
    assert [e["name"] for e in events] == ["a.fit"] and dropped == 0
    assert origin == quiet_tracer.origin_unix
    assert S.before_window(_ctx()) is None      # no first session in it


# -- the new entries in BENCHMARK.json ------------------------------------------

def test_the_six_entries_are_appended_and_resolve():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    tail = b["per_layer"][-6:]
    assert [m["name"] for m in tail] == NEW
    cells = [w["name"] for w in b["workloads"]]
    for m in tail:
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "readers", m["name"] + ".py"))
    by = {m["name"]: m for m in tail}
    assert all(by[n]["moves"] == "setup_s" and by[n]["unit"] == "s"
               for n in SETUP)
    assert by["host_exposed_ms"]["moves"] == "train_rate"
    assert all(by[n]["workloads"] == cells for n in SETUP[:3])
    assert by["setup_state_s"]["workloads"] == ["ftrl-drain"]
    assert by["setup_warm_s"]["workloads"] == by["host_exposed_ms"][
        "workloads"] == cells[1:]
    # set-up's first per-layer metrics: nothing moved ``setup_s`` before
    assert not [m["name"] for m in b["per_layer"][:-6]
                if m["moves"] == "setup_s"]


# -- a traced run of each cell, tiny, in a process of its own -------------------

RUN_ONE = """
import json, sys
from benchmark import run as R
cell, seed = sys.argv[1], int(sys.argv[2])
e2e = {}
real = R.result_of
def keep(ctx, bench, dev):
    e2e.update(ctx.e2e)
    return real(ctx, bench, dev)
R.result_of = keep
out = R.run_cell(cell, seed, 0.4, True, tiny_size=True, require_tpu=False,
                 device_kind_as=%r)
from alink_tpu.common.tracing import get_tracer
names = sorted({e["name"] for e in get_tracer().events()
                if not e.get("profiled")})
print(json.dumps({"out": out, "e2e": e2e, "unprofiled": names,
                  "dropped": get_tracer().dropped}))
""" % AS


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_traced_tiny_run_reports_the_new_entries_beside_the_old(cell):
    """A fresh interpreter, as the driver starts one: the ring then holds
    the process's first session, so set-up has its account. (In the test
    process the same run reports the old entries alone: its first session
    was the test session's.)"""
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("ALINK_TPU_TRACE", None)
    res = subprocess.run(
        [sys.executable, "-c", RUN_ONE, cell, str(2 ** 31 + 35)], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    out, e2e = got["out"], got["e2e"]
    assert out["correct"] is True, out["compared"]
    b = _bench()
    mine = [m["name"] for m in b["per_layer"] if cell in m["workloads"]]
    new = [n for n in mine if n in NEW]
    old = [n for n in mine if n not in NEW]
    assert new == [n for n in NEW if n in new] and len(new) in (4, 5)
    # every new entry of the cell is reported, after the old ones that a
    # run without a device plane can report
    reported = list(out["metrics"])
    assert reported[-len(new):] == new
    assert set(reported[:-len(new)]) <= set(old)
    assert any(n.startswith("compiles_in_window") for n in reported)
    units = {m["name"]: m["unit"] for m in b["per_layer"]}
    assert all(out["metrics"][n]["unit"] == units[n] for n in new)
    value = {n: out["metrics"][n]["value"] for n in new}
    assert all(v > 0 for v in value.values()), value
    # the account is of disjoint parts of set-up
    parts = sum(value[n] for n in new if n in SETUP)
    assert parts <= e2e["setup_s"], (value, e2e)
    assert parts >= 0.5 * e2e["setup_s"], (value, e2e)
    # outside the profiler's session only the coarse grade was recorded
    assert got["dropped"] == 0
    assert "session.start" in got["unprofiled"]
    assert {"jit.trace", "jit.lower", "jit.compile"} <= set(got["unprofiled"])
    assert not [n for n in got["unprofiled"] if n.startswith(
        ("ftrl.encode", "ftrl.ship", "ftrl.dispatch", "ftrl.batch",
         "prefetch.", "serve."))]
    if "host_exposed_ms" in value:
        assert value["host_exposed_ms"] < 1e3 * out["facts"]["fit_s_max"]
        assert "comqueue.wait" in got["unprofiled"]
