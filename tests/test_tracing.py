"""Structured tracing subsystem (common/tracing.py) + instrumented runtime.

Covers the Tracer contract (contextvars nesting, thread lanes, the bounded
flight recorder, instant events, Chrome/JSONL exporters), the
ALINK_TPU_TRACE gate for the fine spans and the coarse ones that record
in every process (ISSUE 35; including StepTimer's single-source-of-truth
emission and JAX's own jit.* events), compat.compiled_cost_analysis's
flat-dict-or-None contract,
and the end-to-end acceptance path: an L-BFGS train with tracing +
checkpointing produces a Chrome trace whose span tree nests
exec -> chunk -> superstep-phase spans with checkpoint instant events,
tools/trace.py summarizes it, the compiled program is byte-identical with
tracing on/off, and the traced run stays within the overhead budget.
"""

import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from alink_tpu.common.metrics import MetricsRegistry, set_registry
from alink_tpu.common.tracing import (Tracer, get_tracer, profiler_active,
                                      recording, set_tracer, trace_complete,
                                      trace_instant, trace_span,
                                      tracing_enabled)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"tool_{name}", os.path.join(ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def fresh_tracer(monkeypatch):
    """Arm tracing and isolate the process tracer per test."""
    monkeypatch.setenv("ALINK_TPU_TRACE", "1")
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        yield tr
    finally:
        set_tracer(prev)


@pytest.fixture
def fresh_registry():
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        yield reg
    finally:
        set_registry(prev)


def _by_id(events):
    return {e["id"]: e for e in events if "id" in e}


def _chain(events, ev):
    """Names along the parent chain of ``ev``, leaf first."""
    byid = _by_id(events)
    names = []
    while ev is not None:
        names.append(ev["name"])
        ev = byid.get(ev.get("parent"))
    return names


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

class TestTracerCore:
    def test_span_nesting_parent_child(self):
        tr = Tracer()
        with tr.span("root") as root:
            with tr.span("mid") as mid:
                with tr.span("leaf"):
                    pass
            with tr.span("mid2"):
                pass
        evs = tr.events()
        got = {e["name"]: e for e in evs}
        assert got["root"].get("parent") is None
        assert got["mid"]["parent"] == got["root"]["id"] == root.id
        assert got["leaf"]["parent"] == got["mid"]["id"] == mid.id
        assert got["mid2"]["parent"] == got["root"]["id"]
        # complete events carry duration; children within parents
        assert got["leaf"]["dur"] <= got["mid"]["dur"] <= got["root"]["dur"]
        assert got["root"]["ts"] <= got["mid"]["ts"] <= got["leaf"]["ts"]

    def test_span_args_and_set(self):
        tr = Tracer()
        with tr.span("s", cat="test", args={"a": 1}) as sp:
            sp.set(b=2)
        (ev,) = tr.events()
        assert ev["args"] == {"a": 1, "b": 2} and ev["cat"] == "test"

    def test_span_recorded_on_exception(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError()
        assert [e["name"] for e in tr.events()] == ["boom"]
        # and the context unwound: a new span is a root again
        with tr.span("after"):
            pass
        assert {e["name"]: e.get("parent") for e in tr.events()}["after"] \
            is None

    def test_instant_parented_to_current_span(self):
        tr = Tracer()
        tr.instant("lonely")
        with tr.span("host") as sp:
            tr.instant("inside", args={"k": "v"})
        evs = {e["name"]: e for e in tr.events()}
        assert evs["lonely"].get("parent") is None
        assert evs["inside"]["parent"] == sp.id
        assert evs["inside"]["ph"] == "i"
        assert "dur" not in evs["inside"]

    def test_complete_retroactive_span(self):
        tr = Tracer()
        with tr.span("parent") as sp:
            tr.complete("late", 0.01, args={"n": 3})
        evs = {e["name"]: e for e in tr.events()}
        assert evs["late"]["parent"] == sp.id
        assert abs(evs["late"]["dur"] - 1e4) < 1e3   # ~10ms in µs
        # it ENDED inside the parent window (its start may precede the
        # parent's — the lookback is the caller's own timing)
        late_end = evs["late"]["ts"] + evs["late"]["dur"]
        parent_end = evs["parent"]["ts"] + evs["parent"]["dur"]
        assert late_end <= parent_end + 1.0

    def test_threads_are_separate_lanes(self):
        tr = Tracer()

        def work(i):
            with tr.span(f"t{i}"):
                with tr.span(f"t{i}.child"):
                    pass

        with tr.span("main"):
            ths = [threading.Thread(target=work, args=(i,)) for i in range(2)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
        evs = {e["name"]: e for e in tr.events()}
        # new threads start with a fresh context: their roots have NO
        # parent (not children of "main"), and their tids differ
        for i in range(2):
            assert evs[f"t{i}"].get("parent") is None
            assert evs[f"t{i}.child"]["parent"] == evs[f"t{i}"]["id"]
            assert evs[f"t{i}"]["tid"] != evs["main"]["tid"]

    def test_flight_recorder_bound_and_drop_count(self):
        tr = Tracer(capacity=8)
        for i in range(30):
            tr.instant(f"e{i}")
        evs = tr.events()
        assert len(evs) == 8
        assert tr.dropped == 22
        # the ring keeps the NEWEST events
        assert [e["name"] for e in evs] == [f"e{i}" for i in range(22, 30)]
        tr.clear()
        assert tr.events() == [] and tr.dropped == 0

    def test_capacity_env_default(self, monkeypatch):
        monkeypatch.setenv("ALINK_TPU_TRACE_BUFFER", "17")
        assert Tracer().capacity == 17
        monkeypatch.setenv("ALINK_TPU_TRACE_BUFFER", "junk")
        assert Tracer().capacity == 65536
        with pytest.raises(ValueError):
            Tracer(capacity=0)

    def test_thread_safe_concurrent_recording(self):
        tr = Tracer()
        n_threads, n_spans = 8, 200

        def work(i):
            for k in range(n_spans):
                with tr.span(f"w{i}"):
                    pass

        ths = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        evs = tr.events()
        assert len(evs) == n_threads * n_spans
        ids = [e["id"] for e in evs]
        assert len(set(ids)) == len(ids)      # ids never collide


# ---------------------------------------------------------------------------
# exporters + tools/trace.py
# ---------------------------------------------------------------------------

class TestExportersAndCli:
    def _record(self, tr):
        with tr.span("exec", cat="engine", args={"max_iter": 3}):
            with tr.span("prepare", cat="engine"):
                pass
            tr.instant("cache", cat="engine", args={"result": "miss"})
            with tr.span("execute", cat="engine"):
                time.sleep(0.002)

    def test_chrome_export_shape(self, tmp_path):
        tr = Tracer()
        self._record(tr)
        p = tr.export_chrome(str(tmp_path / "t.json"))
        doc = json.load(open(p))
        evs = doc["traceEvents"]
        assert {e["ph"] for e in evs} == {"M", "X", "i"}
        names = {e["name"] for e in evs if e["ph"] != "M"}
        assert names == {"exec", "prepare", "cache", "execute"}
        # metadata names the process and threads
        metas = [e for e in evs if e["ph"] == "M"]
        assert any(e["name"] == "process_name" for e in metas)
        assert any(e["name"] == "thread_name" for e in metas)
        # span ids survive the format (args.span_id / parent_id)
        ex = next(e for e in evs if e.get("name") == "execute")
        root = next(e for e in evs if e.get("name") == "exec")
        assert ex["args"]["parent_id"] == root["args"]["span_id"]
        assert doc["otherData"]["format"] == "alink_tpu_trace_v1"

    def test_jsonl_round_trip_through_cli_loader(self, tmp_path):
        tr = Tracer()
        self._record(tr)
        p = tr.export_jsonl(str(tmp_path / "t.jsonl"))
        first = json.loads(open(p).readline())
        assert first["kind"] == "meta"
        assert first["format"] == "alink_tpu_trace_v1"
        trace_tool = _load_tool("trace")
        meta, events = trace_tool.load_events(p)
        assert len(events) == 4
        assert meta["capacity"] == tr.capacity
        # chrome export loads back to the SAME normalized events
        pc = tr.export_chrome(str(tmp_path / "t.json"))
        _, events_c = trace_tool.load_events(pc)
        strip = lambda evs: [{k: e[k] for k in
                              ("ph", "name", "cat", "ts", "tid")}
                             for e in evs]
        assert strip(events_c) == strip(events)

    def test_cli_summary_and_conversion(self, tmp_path, capsys):
        tr = Tracer()
        self._record(tr)
        p = tr.export_jsonl(str(tmp_path / "t.jsonl"))
        out_json = str(tmp_path / "conv.json")
        trace_tool = _load_tool("trace")
        assert trace_tool.main([p, "--chrome", out_json]) == 0
        out = capsys.readouterr().out
        for section in ("Trace summary", "Top spans by self time",
                        "Per-phase rollup", "Instant events",
                        "Critical path"):
            assert section in out
        assert "execute" in out and "cache" in out
        # the conversion is a loadable chrome document
        doc = json.load(open(out_json))
        assert any(e.get("name") == "exec" for e in doc["traceEvents"])
        # and the CLI reads its own conversion
        assert trace_tool.main([out_json]) == 0

    def test_loads_foreign_chrome_shapes(self, tmp_path):
        """Pretty-printed object form and the bare-array form are both
        valid Chrome traces; the loader must take them (and infer
        parents by interval containment when there are no span ids)."""
        trace_tool = _load_tool("trace")
        evs = [{"ph": "X", "name": "outer", "cat": "c", "pid": 1,
                "tid": 7, "ts": 0.0, "dur": 100.0},
               {"ph": "X", "name": "inner", "cat": "c", "pid": 1,
                "tid": 7, "ts": 10.0, "dur": 50.0}]
        pretty = tmp_path / "pretty.json"
        pretty.write_text(json.dumps({"traceEvents": evs}, indent=2))
        _, got = trace_tool.load_events(str(pretty))
        byname = {e["name"]: e for e in got}
        assert byname["inner"]["parent"] == byname["outer"]["id"]
        arr = tmp_path / "array.json"
        arr.write_text(json.dumps(evs))
        _, got2 = trace_tool.load_events(str(arr))
        assert len(got2) == 2
        with pytest.raises(ValueError, match="neither"):
            bad = tmp_path / "bad.json"
            bad.write_text("not json at all")
            trace_tool.load_events(str(bad))

    def test_self_time_subtracts_children(self, tmp_path):
        tr = Tracer()
        with tr.span("outer"):
            with tr.span("inner"):
                time.sleep(0.02)
        trace_tool = _load_tool("trace")
        meta, events = trace_tool.load_events(
            tr.export_jsonl(str(tmp_path / "t.jsonl")))
        selfs = trace_tool.self_times(events)
        byname = {e["name"]: e for e in events}
        outer_self = selfs[byname["outer"]["id"]]
        assert outer_self < byname["outer"]["dur"] - 1.5e4  # inner removed


# ---------------------------------------------------------------------------
# env gate + StepTimer single source of truth
# ---------------------------------------------------------------------------

class TestGate:
    def test_disabled_records_nothing(self, monkeypatch):
        monkeypatch.delenv("ALINK_TPU_TRACE", raising=False)
        assert not tracing_enabled()
        tr = Tracer()
        prev = set_tracer(tr)
        try:
            with trace_span("nope") as sp:
                sp.set(k=1)          # the null span swallows args
            trace_instant("nope2")
        finally:
            set_tracer(prev)
        assert tr.events() == []

    def test_a_coarse_span_records_with_the_flag_unset_a_fine_one_does_not(
            self, quiet_tracer):
        """The two grades, decided at the call site: neither the flag nor
        a profiler session is on, and the coarse span and the coarse
        retroactive event are in the ring, nested as they ran."""
        assert not recording() and not profiler_active()
        with trace_span("kmeans.fit", cat="kmeans", coarse=True) as fit:
            fit.set(rows=5)
            with trace_span("ftrl.encode"):                  # fine
                pass
            trace_complete("ftrl.batch", 0.001)              # fine
            trace_instant("comqueue.program_cache")          # instants: fine
            trace_complete("jit.compile", 0.002, cat="jit", coarse=True,
                           args={"fun_name": "jit(f)", "cache": "miss"})
        evs = {e["name"]: e for e in quiet_tracer.events()}
        assert set(evs) == {"kmeans.fit", "jit.compile"}
        assert evs["jit.compile"]["parent"] == evs["kmeans.fit"]["id"]
        assert evs["kmeans.fit"]["args"] == {"rows": 5}
        assert "profiled" not in evs["kmeans.fit"]
        assert quiet_tracer.origin_unix == pytest.approx(time.time(), abs=60)

    @pytest.mark.parametrize("val,expect", [
        ("0", False), ("off", False), ("false", False),
        ("1", True), ("on", True)])
    def test_flag_parsing(self, monkeypatch, val, expect):
        monkeypatch.setenv("ALINK_TPU_TRACE", val)
        assert tracing_enabled() is expect

    def test_steptimer_emits_into_tracer_when_armed(self, fresh_tracer,
                                                    fresh_registry):
        from alink_tpu.common.profiling import StepTimer
        t = StepTimer()
        with fresh_tracer.span("outer"):
            with t.span("fit", labels={"algo": "kmeans"}):
                pass
        evs = {e["name"]: e for e in fresh_tracer.events()}
        assert evs["fit"]["parent"] == evs["outer"]["id"]
        assert evs["fit"]["args"] == {"algo": "kmeans"}
        assert evs["fit"]["cat"] == "steptimer"
        # the StepTimer itself and the registry mirror still work
        assert t.report()[0][1] == 1
        fam = fresh_registry.histogram(StepTimer.METRIC)
        assert sum(s.count for _, s in fam.series()) == 1

    def test_steptimer_quiet_when_disarmed(self, monkeypatch,
                                           fresh_registry):
        monkeypatch.delenv("ALINK_TPU_TRACE", raising=False)
        from alink_tpu.common.profiling import StepTimer
        tr = Tracer()
        prev = set_tracer(tr)
        try:
            t = StepTimer()
            with t.span("fit"):
                pass
            with t.span("wait", coarse=True):    # the engine's phases
                pass
        finally:
            set_tracer(prev)
        assert [e["name"] for e in tr.events()] == ["wait"]
        assert [r[:2] for r in t.report()] == [("fit", 1), ("wait", 1)]


# ---------------------------------------------------------------------------
# the bridge to the profiler's clock
# ---------------------------------------------------------------------------

def _xplane_host_events(trace_dir, prefix="alink:"):
    """(name, start_ns, end_ns, stats) of the host planes' events whose
    name starts with ``prefix``, from the one .xplane.pb under
    ``trace_dir``."""
    import glob
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert len(found) == 1, found
    out = []
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                dict(ev.stats)))
    return out


class TestProfilerBridge:
    def test_neither_switch_nor_session_is_the_shared_noop(self,
                                                            quiet_tracer):
        assert not recording() and not profiler_active()
        a, b = trace_span("one", args={"k": 1}), trace_span("two")
        assert a is b, "one shared no-op, nothing allocated"
        with a as sp:
            sp.set(k=2)
        trace_instant("three")
        trace_complete("four", 0.001)
        assert quiet_tracer.events() == []

    def test_a_profiler_session_turns_the_spans_on_for_its_length(
            self, quiet_tracer, tmp_path):
        import jax
        with trace_span("before"):
            pass
        with jax.profiler.trace(str(tmp_path)):
            assert profiler_active() and recording()
            assert not tracing_enabled(), "no flag was set"
            with trace_span("outer", cat="stream",
                            args={"batch": 7, "mode": "sample",
                                  "skipped": [1, 2]}) as sp:
                sp.set(late=1)
                with trace_span("inner"):
                    time.sleep(0.002)
                trace_instant("mark")
                trace_complete("retro", 0.001)
        assert not profiler_active()
        with trace_span("after"):
            pass
        evs = {e["name"]: e for e in quiet_tracer.events()}
        assert set(evs) == {"outer", "inner", "mark", "retro"}
        assert all(e["profiled"] is True for e in evs.values())
        assert evs["inner"]["parent"] == evs["outer"]["id"]
        assert evs["outer"]["args"] == {"batch": 7, "mode": "sample",
                                        "skipped": [1, 2], "late": 1}
        # the real spans are in the profiler's own trace, on its clock;
        # the retroactive one and the instant cannot be
        host = {n: (a, b, st) for n, a, b, st
                in _xplane_host_events(tmp_path)}
        assert set(host) == {"alink:outer", "alink:inner"}
        (oa, ob, ost), (ia, ib, _) = host["alink:outer"], host["alink:inner"]
        assert oa <= ia < ib <= ob, "the child lies inside its parent"
        assert ib - ia >= 2_000_000
        # scalar args known at entry ride along as the event's stats
        assert ost == {"batch": 7, "mode": "sample"}

    def test_spans_of_other_threads_land_on_their_own_lines(
            self, quiet_tracer, tmp_path):
        import jax

        def work():
            with trace_span("worker.span"):
                time.sleep(0.001)

        with jax.profiler.trace(str(tmp_path)):
            th = threading.Thread(target=work, name="alink-prefetch-9")
            th.start()
            th.join(timeout=10)
            assert not th.is_alive()
            with trace_span("caller.span"):
                pass
        evs = {e["name"]: e for e in quiet_tracer.events()}
        assert evs["worker.span"]["tid"] != evs["caller.span"]["tid"]
        assert {n for n, *_ in _xplane_host_events(tmp_path)} == {
            "alink:worker.span", "alink:caller.span"}

    def test_the_flag_alone_records_without_the_profiled_mark(
            self, fresh_tracer):
        with trace_span("plain"):
            pass
        (ev,) = fresh_tracer.events()
        assert "profiled" not in ev
        assert "profiled" not in fresh_tracer.to_chrome()[
            "traceEvents"][-1].get("args", {})

    def test_the_session_test_imports_no_jax(self):
        """``tracing.py`` finds JAX in ``sys.modules`` and never imports
        it: a process without JAX cannot be profiling with it."""
        import re
        from alink_tpu.common import tracing
        with open(tracing.__file__) as f:
            src = f.read()
        assert not re.search(r"^\s*(import|from)\s+jax\b", src, re.M)
        assert 'sys.modules.get("jax")' in src


# ---------------------------------------------------------------------------
# compat.compiled_cost_analysis
# ---------------------------------------------------------------------------

class TestCostShim:
    def test_real_lowered_returns_flops_and_bytes(self):
        import jax
        import jax.numpy as jnp
        from alink_tpu.common.compat import compiled_cost_analysis

        low = jax.jit(lambda x: x @ x).lower(jnp.ones((16, 16)))
        cost = compiled_cost_analysis(low)
        assert cost is not None
        assert cost["flops"] > 0
        assert cost["bytes accessed"] > 0
        # compiled stage too
        cost_c = compiled_cost_analysis(low.compile())
        assert cost_c is not None and cost_c["flops"] > 0

    def test_degrades_to_none_never_raises(self):
        from alink_tpu.common.compat import compiled_cost_analysis

        class Raises:
            def cost_analysis(self):
                raise NotImplementedError("no cost analysis here")

        class Empty:
            def cost_analysis(self):
                return []

        class Weird:
            def cost_analysis(self):
                return "not a dict"

        assert compiled_cost_analysis(Raises()) is None
        assert compiled_cost_analysis(Empty()) is None
        assert compiled_cost_analysis(Weird()) is None
        assert compiled_cost_analysis(object()) is None   # no attr at all


# ---------------------------------------------------------------------------
# instrumented engine
# ---------------------------------------------------------------------------

#: an execution's coarse children, in the order they run
PHASES = ["comqueue.prepare", "comqueue.plan", "comqueue.execute",
          "comqueue.wait", "comqueue.slice", "comqueue.fetch",
          "comqueue.account"]


def _make_queue(key, max_iter=4, **ck):
    import jax.numpy as jnp
    from alink_tpu.engine.communication import AllReduce
    from alink_tpu.engine.comqueue import IterativeComQueue

    X = np.arange(64.0).reshape(32, 2)

    def stage(ctx):
        if ctx.is_init_step:
            ctx.put_obj("s", jnp.zeros(()))
        ctx.put_obj("s", ctx.get_obj("X").sum())

    q = (IterativeComQueue(max_iter=max_iter, **ck)
         .init_with_partitioned_data("X", X)
         .add(stage)
         .add(AllReduce("s")))
    if key is not None:
        q.set_program_key(key)
    return q


class TestEngineTracing:
    def test_exec_span_tree(self, fresh_tracer, fresh_registry):
        key = ("test_tracing_e2e", os.urandom(6).hex())
        r = _make_queue(key=key).exec()
        assert r.step_count == 4
        evs = fresh_tracer.events()
        byname = {e["name"]: e for e in evs}
        # exec is the root; prepare/execute (StepTimer spans), the plan and
        # program lookup, the wait for the run, the step count's slice and
        # fetch and the metrics tail nest under it
        assert byname["comqueue.exec"].get("parent") is None
        for child in PHASES:
            assert byname[child]["parent"] == byname["comqueue.exec"]["id"]
        cache = byname["comqueue.program_cache"]
        assert cache["ph"] == "i" and cache["args"]["result"] == "miss"
        # the program was new: its trace, lowering and compile are the
        # execute span's children (the dispatch carried them)
        mine = [e for e in evs if e["name"].startswith("jit.")
                and "test_tracing_e2e" in e["args"]["fun_name"]]
        assert {e["name"] for e in mine} == {"jit.trace", "jit.lower",
                                             "jit.compile"}
        assert all(e["parent"] == byname["comqueue.execute"]["id"]
                   for e in mine)
        # the second trace and lowering that filled the cost gauges under
        # the flag is gone: the program is lowered once
        assert sum(e["name"] == "jit.lower" for e in mine) == 1
        assert not [r for r in fresh_registry.snapshot()
                    if r["name"].startswith("alink_program_")]

    def test_untraced_exec_records_the_coarse_phases_and_nothing_fine(
            self, quiet_tracer, fresh_registry):
        """With neither the flag nor a session, an exec leaves
        ``comqueue.exec`` > prepare, plan, execute, wait, slice, fetch,
        account in the ring (JAX's ``jit.*`` where the program was new) and
        nothing else: no instant, no chunk."""
        key = ("test_tracing_off", os.urandom(6).hex())
        _make_queue(key=key).exec()
        evs = quiet_tracer.events()
        root = [e for e in evs if e["name"] == "comqueue.exec"]
        assert len(root) == 1 and root[0].get("parent") is None
        assert root[0]["args"]["program"] == "test_tracing_off"
        phases = [e["name"] for e in evs if e.get("parent") == root[0]["id"]]
        assert phases == PHASES
        assert {e["name"] for e in evs} <= {
            "comqueue.exec", *phases, "jit.trace", "jit.lower",
            "jit.compile"}
        assert all(e["ph"] == "X" and "profiled" not in e for e in evs)
        # the account closes: the phases cover the exec but for a sliver
        covered = sum(e["dur"] for e in evs
                      if e.get("parent") == root[0]["id"])
        assert 0 <= root[0]["dur"] - covered < 0.25 * root[0]["dur"] + 2e3
        quiet_tracer.clear()
        # a warm exec fires no ``jit.*`` at all: 8 always-on events
        _make_queue(key=key).exec()
        assert sorted(e["name"] for e in quiet_tracer.events()) == sorted(
            ["comqueue.exec"] + phases)

    def test_lowered_hlo_unchanged_by_tracing(self, monkeypatch):
        """Tracing must add NOTHING to compiled programs: the lowered
        text is byte-identical with the switch on and off."""
        key = ("test_tracing_hlo", os.urandom(6).hex())
        monkeypatch.delenv("ALINK_TPU_TRACE", raising=False)
        off = _make_queue(key=key).lowered().as_text()
        monkeypatch.setenv("ALINK_TPU_TRACE", "1")
        on = _make_queue(key=key).lowered().as_text()
        assert on == off
        assert "callback" not in on.lower()
        assert "outfeed" not in on.lower()

    def test_overhead_guard_and_ring_bound(self, monkeypatch,
                                           fresh_registry):
        """Always-on tracing must be cheap: a traced (cache-hit) run
        stays within 2x the untraced wall time, and the flight recorder
        never outgrows its bound."""
        key = ("test_tracing_overhead", os.urandom(6).hex())
        runs = 5
        # warm under tracing so the compile is paid outside the measured
        # window
        monkeypatch.setenv("ALINK_TPU_TRACE", "1")
        tr = Tracer(capacity=16)
        prev = set_tracer(tr)
        try:
            _make_queue(key=key).exec()

            monkeypatch.delenv("ALINK_TPU_TRACE")
            t0 = time.perf_counter()
            for _ in range(runs):
                _make_queue(key=key).exec()
            untraced = time.perf_counter() - t0

            monkeypatch.setenv("ALINK_TPU_TRACE", "1")
            t0 = time.perf_counter()
            for _ in range(runs):
                _make_queue(key=key).exec()
            traced = time.perf_counter() - t0
        finally:
            set_tracer(prev)
        # generous absolute slack so scheduler noise on ~ms-scale hits
        # cannot flake the ratio; the 2x bound is the contract
        assert traced <= 2.0 * untraced + 0.25, \
            f"traced {traced:.3f}s vs untraced {untraced:.3f}s"
        # ring bound respected with room to spare: 6 execs x ~5 events
        # wanted to land in a 16-slot buffer
        assert len(tr.events()) <= 16
        assert tr.dropped > 0


# ---------------------------------------------------------------------------
# the flight recorder with every switch off (ISSUE 35): the session, JAX's
# compile events, a fit of each batch trainer
# ---------------------------------------------------------------------------

FINE = ("ftrl.encode", "ftrl.ship", "ftrl.dispatch", "ftrl.batch",
        "prefetch.", "serve.", "comqueue.chunk", "comqueue.program_cache")


def _fit_kmeans():
    from alink_tpu.operator.batch.clustering.kmeans_ops import (
        KMeansTrainBatchOp)
    from alink_tpu.operator.batch.source import MemSourceBatchOp
    r = np.random.RandomState(5)
    rows = [tuple(p) for p in r.randn(96, 2) + 4.0 * r.randint(0, 3, (96, 1))]
    KMeansTrainBatchOp(k=3, feature_cols=["x", "y"], max_iter=4).link_from(
        MemSourceBatchOp(rows, "x DOUBLE, y DOUBLE"))


def _fit_gbdt():
    from alink_tpu.operator.batch.classification.tree_ops import (
        GbdtTrainBatchOp)
    from alink_tpu.operator.batch.source import MemSourceBatchOp
    r = np.random.RandomState(6)
    X = r.randn(128, 3)
    rows = [(*x, int(x[0] + x[1] > 0)) for x in X]
    GbdtTrainBatchOp(feature_cols=["a", "b", "c"], label_col="label",
                     num_trees=2, max_depth=2).link_from(
        MemSourceBatchOp(rows, "a DOUBLE, b DOUBLE, c DOUBLE, label LONG"))


def _fit_als():
    from alink_tpu.operator.batch.recommendation.als_ops import (
        AlsTrainBatchOp)
    from alink_tpu.operator.batch.source import MemSourceBatchOp
    r = np.random.RandomState(7)
    rows = [(int(u), int(i), float(r.rand())) for u in range(12)
            for i in range(9) if r.rand() < 0.6]
    AlsTrainBatchOp(user_col="user", item_col="item", rate_col="rating",
                    rank=3, num_iter=2, lambda_=0.1).link_from(
        MemSourceBatchOp(rows, "user LONG, item LONG, rating DOUBLE"))


class TestFlightRecorder:
    def test_session_start_is_in_the_ring_and_counts_the_sessions(
            self, quiet_tracer):
        from alink_tpu.common.mlenv import MLEnvironment
        a, b = MLEnvironment(parallelism=2), MLEnvironment()
        evs = quiet_tracer.events()
        assert [e["name"] for e in evs] == ["session.start"] * 2
        first, second = (e["args"] for e in evs)
        # the process's first session (``session`` 0: the end of its boot)
        # was the test session's own, long before this ring
        assert 0 < first["session"] == second["session"] - 1
        assert first["devices"] == 2 and second["devices"] == 8
        assert first["platform"] == second["platform"] == "cpu"
        assert a.num_workers == 2 and b.num_workers == 8

    def test_jit_events_are_children_of_the_open_span_and_carry_cache(
            self, quiet_tracer, fresh_registry):
        import jax
        import jax.numpy as jnp

        def flight_recorder_probe(x):
            return jnp.tanh(x) * 3.0

        f = jax.jit(flight_recorder_probe)
        with trace_span("probe.fit", coarse=True):
            f(jnp.ones(7))
        evs = quiet_tracer.events()
        fit = next(e for e in evs if e["name"] == "probe.fit")
        mine = [e for e in evs if e["name"].startswith("jit.")
                and "flight_recorder_probe" in e["args"]["fun_name"]]
        assert [e["name"] for e in mine] == ["jit.trace", "jit.lower",
                                             "jit.compile"]
        jits = [e for e in evs if e["name"].startswith("jit.")]
        assert all(e["parent"] == fit["id"] and e["cat"] == "jit"
                   and e["tid"] == fit["tid"] for e in jits)
        # retroactive: each lies inside the span that was open, in order
        lo, hi = fit["ts"], fit["ts"] + fit["dur"]
        assert all(lo <= e["ts"] and e["ts"] + e["dur"] <= hi for e in jits)
        assert mine[0]["ts"] < mine[1]["ts"] < mine[2]["ts"]
        # the test process keeps the persistent cache off
        assert mine[2]["args"]["cache"] == "off"
        assert "cache" not in mine[0]["args"]
        assert fresh_registry.value("alink_jit_compiles_total",
                                    {"cache": "off"}) >= 1
        # a warm call fires none of the three
        quiet_tracer.clear()
        with trace_span("probe.fit", coarse=True):
            f(jnp.ones(7))
        assert [e["name"] for e in quiet_tracer.events()] == ["probe.fit"]

    def test_the_caches_verdict_rides_the_next_compile_of_its_thread(
            self, quiet_tracer, fresh_registry):
        """The listener, driven by hand with JAX's own event names: a hit
        or a miss belongs to the compile that follows it on the same
        thread, and to no other."""
        from alink_tpu.common import mlenv
        compile_ = "/jax/core/compile/backend_compile_duration"
        mlenv._on_jax_event("/jax/compilation_cache/cache_hits")
        other = threading.Thread(target=mlenv._on_jax_duration, args=(
            compile_, 0.25), kwargs={"fun_name": "jit(elsewhere)"})
        other.start()
        other.join(timeout=10)
        mlenv._on_jax_event(
            "/jax/compilation_cache/compile_requests_use_cache")
        mlenv._on_jax_duration(compile_, 0.5, fun_name="jit(loaded)")
        mlenv._on_jax_duration(compile_, 0.5, fun_name="jit(uncached)")
        mlenv._on_jax_event("/jax/compilation_cache/cache_misses")
        mlenv._on_jax_duration("/jax/core/compile/jaxpr_trace_duration", 0.1,
                               fun_name="f")
        mlenv._on_jax_duration(compile_, 2.0, fun_name="jit(f)")
        mlenv._on_jax_duration("/jax/some/other_duration", 1.0)
        got = [(e["name"], e["args"].get("fun_name"), e["args"].get("cache"),
                round(e["dur"] / 1e6, 3)) for e in quiet_tracer.events()]
        assert sorted(got) == sorted([
            ("jit.compile", "jit(elsewhere)", "off", 0.25),
            ("jit.compile", "jit(loaded)", "hit", 0.5),
            ("jit.compile", "jit(uncached)", "off", 0.5),
            ("jit.trace", "f", None, 0.1),
            ("jit.compile", "jit(f)", "miss", 2.0)])
        for cache, n in (("hit", 1), ("miss", 1), ("off", 2)):
            assert fresh_registry.value("alink_jit_compiles_total",
                                        {"cache": cache}) == n

    @pytest.mark.parametrize("fit,root,execs", [
        # (a small host table's cut points are numpy's: no third program)
        (_fit_kmeans, "kmeans.fit", 2), (_fit_gbdt, "gbdt.fit", 2),
        (_fit_als, "als.fit", 2)], ids=["kmeans", "gbdt", "als"])
    def test_a_warm_fit_leaves_its_coarse_tree_and_at_most_40_events(
            self, quiet_tracer, fit, root, execs):
        """The budget of the always-on grade: a fit with every switch off
        leaves its ``*.fit`` tree with the engine's phases, ``jit.*`` only
        while its programs are new, nothing fine, at most 40 events."""
        fit()
        cold = quiet_tracer.events()
        assert {"jit.trace", "jit.lower", "jit.compile"} <= {
            e["name"] for e in cold}
        assert all(e["args"]["fun_name"] for e in cold
                   if e["name"].startswith("jit."))
        assert all(e["args"]["cache"] in ("hit", "miss", "off") for e in cold
                   if e["name"] == "jit.compile")
        quiet_tracer.clear()
        fit()
        evs = quiet_tracer.events()
        assert quiet_tracer.dropped == 0
        assert len(evs) <= 40, sorted(e["name"] for e in evs)
        assert not [e["name"] for e in evs if e["name"].startswith("jit.")]
        assert not [e["name"] for e in evs if e["name"].startswith(FINE)]
        assert all(e["ph"] == "X" for e in evs)
        (top,) = [e for e in evs if e["name"] == root]
        assert _chain(evs, top)[1].startswith("link:")
        runs = [e for e in evs if e["name"] == "comqueue.exec"]
        assert len(runs) == execs
        for run in runs:
            assert root in _chain(evs, run)
            assert [e["name"] for e in evs
                    if e.get("parent") == run["id"]] == PHASES
        # every wait is the engine's own, inside an execution
        waits = [e for e in evs if e["name"] == "comqueue.wait"]
        assert len(waits) == execs
        assert all(_chain(evs, w)[1] == "comqueue.exec" for w in waits)


# ---------------------------------------------------------------------------
# acceptance: L-BFGS train -> chrome trace with nested chunk tree
# ---------------------------------------------------------------------------

def _lbfgs(data, **ck):
    from alink_tpu.operator.common.optim.objfunc import (LogLossFunc,
                                                         UnaryLossObjFunc)
    from alink_tpu.operator.common.optim.optimizers import (OptimParams,
                                                            optimize)
    obj = UnaryLossObjFunc(LogLossFunc(), dim=data["X"].shape[1])
    params = OptimParams(method="LBFGS", max_iter=12, epsilon=0.0, **ck)
    return optimize(obj, data, params)


class TestLbfgsTraceAcceptance:
    def test_lbfgs_chrome_trace_nests_and_summarizes(self, fresh_tracer,
                                                     fresh_registry,
                                                     tmp_path, capsys):
        r = np.random.RandomState(3)
        X = r.randn(256, 6).astype(np.float32)
        y = (X @ r.randn(6) > 0).astype(np.float32) * 2 - 1
        data = {"X": X, "y": y, "w": np.ones(256, np.float32)}
        _lbfgs(data, checkpoint_dir=str(tmp_path / "ck"),
               checkpoint_every=4)

        chrome = fresh_tracer.export_chrome(str(tmp_path / "trace.json"))
        trace_tool = _load_tool("trace")
        meta, events = trace_tool.load_events(chrome)

        # the span tree: exec -> ... -> chunk -> superstep-phase
        syncs = [e for e in events if e["name"] == "superstep.sync"]
        assert syncs, "no superstep phase spans in the trace"
        chain = _chain(events, syncs[0])
        assert chain[-1] == "comqueue.exec"
        assert "comqueue.chunk" in chain
        assert chain.index("comqueue.chunk") < chain.index("comqueue.exec")
        chunks = [e for e in events if e["name"] == "comqueue.chunk"]
        assert len(chunks) == 3                       # 12 supersteps / 4
        assert {c["args"]["limit"] for c in chunks} == {4, 8, 12}
        # checkpoint instant events made it into the chrome trace
        saves = [e for e in events if e["name"] == "checkpoint.save"]
        assert len(saves) == 3
        assert all(e["ph"] == "i" for e in saves)
        assert {s["args"]["tag"] for s in saves} == {4, 8, 12}

        # tools/trace.py summarizes the chrome file
        assert trace_tool.main([chrome]) == 0
        out = capsys.readouterr().out
        assert "comqueue.chunk" in out and "checkpoint.save" in out
        assert "Critical path" in out

    def test_fault_injection_marker_lands_in_trace(self, fresh_tracer,
                                                   monkeypatch):
        from alink_tpu.common.faults import FaultInjected, maybe_crash
        monkeypatch.setenv("ALINK_TPU_FAULT_INJECT", "test.site:2")
        with pytest.raises(FaultInjected):
            maybe_crash("test.site", 5)
        evs = [e for e in fresh_tracer.events()
               if e["name"] == "fault.injected"]
        assert len(evs) == 1
        # r14: the instant additionally names the fault MODE (kill /
        # error / delay / corrupt) so a flight recorder distinguishes
        # an injected kill from an injected transient
        assert evs[0]["args"] == {"site": "test.site", "index": 5,
                                  "threshold": 2, "mode": "kill"}
