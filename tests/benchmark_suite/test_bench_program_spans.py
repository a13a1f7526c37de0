"""The per-layer metrics that read the program's own spans (ISSUE 25): each
reader against a list of events countable by hand, the filter that keeps
the traced window's events, a traced tiny ``ftrl-drain`` run that reports
them beside the metrics the benchmark already had, and ``BENCHMARK.json``
still holding, unedited, everything it held before them."""

import hashlib
import importlib
import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
AS = "TPU v5 lite"      # whose peaks a CPU run counts against; never printed

NEW = ["encode_rate.drain", "ship_ms.drain", "dispatch_ms.drain",
       "feed_wait.drain", "host_ceiling.drain"]
OLD = ["compiles_in_window.drain", "ftrl_step_dev", "snapshot_ms",
       "ftrl_step_roofline", "ftrl_step_mfu", "device_idle.drain",
       "peak_hbm.drain"]

CONSUMER, PRODUCER, OTHER = 11, 22, 33


def _span(name, ms, tid, **args):
    ev = {"ph": "X", "name": name, "cat": "stream", "ts": 0.0,
          "dur": ms * 1e3, "tid": tid, "profiled": True}
    if args:
        ev["args"] = args
    return ev


def _hand_events():
    """Eight micro-batches of 100 rows. Producer: pull 0.5 ms, encode 2 ms,
    ship 1 ms each (and the ninth pull that found the end, 0.5 ms).
    Consumer: three dispatches at host speed (0.2, 0.3, 0.4 ms) and five
    that waited for the device (40 ms): two humps; ``ftrl.batch`` 0.1 ms
    more than its dispatch; two starved waits of 3 ms and 5 ms, and one of
    7 ms on another thread that is nobody's consumer."""
    evs = []
    for b in range(1, 9):
        evs += [_span("prefetch.pull", 0.5, PRODUCER),
                _span("ftrl.encode", 2.0, PRODUCER, batch=b, rows=100),
                _span("ftrl.ship", 1.0, PRODUCER, batch=b, rows=100)]
    evs.append(_span("prefetch.pull", 0.5, PRODUCER))
    for b, ms in enumerate([0.2, 0.3, 0.4, 40, 40, 40, 40, 40], start=1):
        evs += [_span("ftrl.dispatch", ms, CONSUMER, batch=b),
                _span("ftrl.batch", ms + 0.1, CONSUMER, batch=b)]
    evs += [_span("prefetch.get_wait", 3.0, CONSUMER),
            _span("prefetch.get_wait", 5.0, CONSUMER),
            _span("prefetch.get_wait", 7.0, OTHER)]
    return evs


def _ctx(window_s=0.4, batch_rows=100):
    return types.SimpleNamespace(
        facts={"window_s": window_s, "batch_rows": batch_rows})


def _read(monkeypatch, metric, events, ctx=None):
    from benchmark import program_spans
    monkeypatch.setattr(program_spans, "window_events", lambda: list(events))
    reader = importlib.import_module(
        "benchmark.readers." + metric.split(".")[0])
    return reader.read(ctx or _ctx())


@pytest.mark.parametrize("metric,want", [
    # 800 rows over 8 x 2 ms of encode
    ("encode_rate.drain", 800 / 0.016),
    ("ship_ms.drain", 1.0),
    # the lower quartile of (0.2, 0.3, 0.4, 40 x 5) lies in the host's hump;
    # the mean, 25.1 ms, would read the device
    ("dispatch_ms.drain", 0.325),
    # 3 + 5 ms on the consumer's thread, of a 400 ms window
    ("feed_wait.drain", 2.0),
    # producer: (9 x 0.5 + 8 x 2 + 8 x 1) / 8 = 3.5625 ms a micro-batch;
    # consumer: lower quartile of ftrl.batch 0.425 ms; the producer is the
    # slower side: 100 rows / 3.5625 ms
    ("host_ceiling.drain", 100 / 0.0035625),
])
def test_reader_against_a_hand_count(monkeypatch, metric, want):
    assert _read(monkeypatch, metric, _hand_events()) == pytest.approx(want)


def test_the_lower_quartile_reads_the_unblocked_hump():
    from benchmark import program_spans as P
    fast, slow = [0.0002, 0.0003, 0.0004], [0.04] * 5
    q1 = P.lower_quartile(fast + slow)
    assert min(fast) <= q1 <= max(fast)
    assert sum(fast + slow) / 8 > 50 * q1
    assert P.lower_quartile([]) is None and P.lower_quartile([0.5]) == 0.5


def test_host_ceiling_takes_the_slower_side(monkeypatch):
    # a consumer that costs 5 ms a micro-batch even unblocked outweighs the
    # producer's 3.5625 ms
    evs = [e for e in _hand_events() if e["name"] != "ftrl.batch"]
    evs += [_span("ftrl.batch", 5.0, CONSUMER, batch=b) for b in range(1, 9)]
    assert _read(monkeypatch, "host_ceiling.drain", evs) == pytest.approx(
        100 / 0.005)


@pytest.mark.parametrize("metric", NEW)
def test_reader_finds_nothing_without_the_programs_spans(monkeypatch, metric):
    """A program that records no span under the profiler (the commit before
    these spans) leaves every reader silent: ``None``, never a 0 and never
    an exception."""
    assert _read(monkeypatch, metric, []) is None
    # the benchmark's clock alone, or spans of other layers, are no reading
    others = [_span("serve.batch", 1.0, CONSUMER), _span("link:X", 2.0, 5)]
    assert _read(monkeypatch, metric, others) is None


@pytest.mark.parametrize("metric", ["encode_rate.drain", "host_ceiling.drain"])
def test_a_rate_is_never_zero(monkeypatch, metric):
    """Spans of no length give no rate, not an infinite or a zero one."""
    evs = [dict(e, dur=0.0) for e in _hand_events()]
    assert _read(monkeypatch, metric, evs) is None


def test_feed_wait_is_zero_where_the_consumer_never_waited(monkeypatch):
    evs = [e for e in _hand_events() if e["name"] != "prefetch.get_wait"]
    assert _read(monkeypatch, "feed_wait.drain", evs) == 0.0


def test_window_events_are_the_profiled_complete_spans(
        quiet_tracer, monkeypatch, tmp_path):
    import jax
    from alink_tpu.common.tracing import (trace_complete, trace_instant,
                                          trace_span)
    from benchmark import program_spans
    monkeypatch.setenv("ALINK_TPU_TRACE", "1")
    with trace_span("warmup.span"):            # before the window: the flag's
        pass
    with jax.profiler.trace(str(tmp_path)):
        with trace_span("ftrl.ship"):
            pass
        trace_complete("ftrl.batch", 0.002)
        trace_instant("a.mark")
    with trace_span("after.span"):
        pass
    assert len(quiet_tracer.events()) == 5
    got = program_spans.window_events()
    assert sorted(e["name"] for e in got) == ["ftrl.batch", "ftrl.ship"]
    assert program_spans.seconds(got, "ftrl.batch") == [pytest.approx(0.002)]


# -- a traced run of the cell, tiny, on the CPU -------------------------------

@pytest.fixture
def _keep_the_sessions_env():
    from alink_tpu.common.mlenv import MLEnvironmentFactory
    before = MLEnvironmentFactory.get_default()
    yield
    MLEnvironmentFactory.set_default(before)


def test_traced_tiny_drain_reports_the_new_metrics_beside_the_old(
        quiet_tracer, monkeypatch, _keep_the_sessions_env):
    """The CPU has no device plane, so the device's side of the reduction
    is stood in for (busy half the window, one step program execution a
    dispatch); the host's side is the real trace: the program's spans are
    in it as ``alink:*`` on the clock of the benchmark's own window span."""
    from benchmark import run as R, trace_reduce as TR
    seen = {}

    def stand_in(profile, window=None, devices=None, **_kw):
        lo, hi = next((a, b) for n, a, b in TR.host_spans(profile)
                      if n == TR.WINDOW_SPAN)
        seen["alink"] = TR.host_spans(profile, prefix="alink:")
        seen["window"] = (lo, hi)
        calls = sum(n == "alink:ftrl.dispatch" for n, _, _ in seen["alink"])
        w = (hi - lo) / 1e9
        step = "jit_shard_fn(1)"
        return {"window_s": w, "busy_s": w / 2, "devices": 1, "op_s": {},
                "op_calls": {}, "module_s": {step: w / 2},
                "module_calls": {step: calls}, "gap_s": {},
                "device_ops": [], "idle_gaps": []}

    monkeypatch.setattr(TR, "reduce_profile", stand_in)
    monkeypatch.setattr(R, "memory_peak", lambda chips: 1 << 20)
    out = R.run_cell("ftrl-drain", 2 ** 31 + 25, 0.6, True,
                     tiny_size=True, require_tpu=False, device_kind_as=AS)
    assert out["correct"] is True, out["compared"]
    assert list(out["metrics"]) == OLD + NEW
    for name, m in out["metrics"].items():
        if name not in ("compiles_in_window.drain", "feed_wait.drain"):
            assert m["value"] > 0, name
    assert 0 <= out["metrics"]["feed_wait.drain"]["value"] <= 100
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        units = {m["name"]: m["unit"] for m in json.load(f)["per_layer"]}
    assert all(out["metrics"][n]["unit"] == units[n] for n in NEW)
    # the spans the readers read are in the profiler's trace too, inside
    # the window span, on its clock
    lo, hi = seen["window"]
    names = {n for n, _, _ in seen["alink"]}
    assert {"alink:ftrl.encode", "alink:ftrl.ship", "alink:ftrl.dispatch",
            "alink:prefetch.pull"} <= names
    inside = [(a, b) for n, a, b in seen["alink"]
              if n == "alink:ftrl.dispatch"]
    assert len(inside) >= out["facts"]["micro_batches"] - 1
    assert all(lo <= a <= b <= hi for a, b in inside)
    json.dumps(out)


# -- the benchmark's own file -------------------------------------------------

def test_benchmark_json_only_gained_per_layer_entries():
    """What ``BENCHMARK.json`` held before the program's spans were read
    (the commit of PR 24, by digest) is all still there, first and in
    order; after it come the five entries that read the spans."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    before = dict(b, configs=b["configs"][:1], workloads=b["workloads"][:1],
                  end_to_end=b["end_to_end"][:2], per_layer=b["per_layer"][:7])
    digest = hashlib.blake2b(json.dumps(before, sort_keys=True).encode(),
                             digest_size=16).hexdigest()
    assert digest == "9db979fde4c5b1760034e511638a402c"
    assert [m["name"] for m in b["per_layer"][:7]] == OLD
    assert [m["name"] for m in b["per_layer"][7:12]] == NEW
    for m in b["per_layer"][7:12]:
        assert m["source"] == "program_span" and m["moves"] == "train_rate"
        assert m["workloads"] == ["ftrl-drain"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "readers", m["name"].split(".")[0] + ".py"))
