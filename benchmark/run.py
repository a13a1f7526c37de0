"""One cell, once: ``python3 -m benchmark.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

Finds everything by the names in ``BENCHMARK.json``: the cell's entry gives
its configuration (the entry's ``file``) and its traffic mix
(``benchmark/traffic/<traffic>.json``, whose ``generator`` names the module
under ``benchmark/generators/`` that reads it); each per-layer metric is
read by ``benchmark/readers/<name up to the first dot>.py``. Nothing here
names a cell, a configuration or a metric.

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also ``breakdown``)
and, last, ``compared``: each number that decided ``correct`` beside its
limit. The same numbers are the last lines of standard error. Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
#: the profiler writes here (inside the checkout, listed in .gitignore)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def process_start() -> float:
    """``time.time()`` of this process's start, from /proc (the
    interpreter's own start-up is part of set-up)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - (up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return _IMPORTED_AT


_IMPORTED_AT = time.time()


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, parked: bool = False) -> Dict[str, Any]:
    """The cell's entry, its configuration and its traffic mix. With
    ``parked`` the entries of ``benchmark/parked.json`` count too: cells
    that ran correct on the chip but spread too widely to be held to a
    bound, kept runnable for the tests and for whoever steadies them."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if parked:
        more = load_json(os.path.join(HERE, "parked.json"))
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[key] = bench[key] + more[key]
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {"bench": bench, "cell": cell,
            "config": load_json(os.path.join(ROOT, cfg_entry["file"])),
            "traffic": load_json(os.path.join(
                HERE, "traffic", cell["traffic"] + ".json"))}


def metrics_of(bench: Dict, cell_name: str, group: str) -> List[Dict]:
    """The metrics of ``group`` that this cell reports."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    mine = {m["name"] for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])}
    if group == "end_to_end":
        return [e2e[n] for n in e2e if n in mine]
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and m["moves"] in mine]


def tiny(doc: Dict) -> Dict:
    """The test-only size: ``doc`` with its ``tiny`` overrides applied."""
    out = {k: v for k, v in doc.items() if k != "tiny"}
    out.update(doc.get("tiny", {}))
    return out


class Ctx:
    """What one run knows. The generator fills ``e2e``, ``facts`` and the
    compared numbers; the readers read ``facts`` and ``reduced``."""

    def __init__(self, cell: Dict, config: Dict, traffic: Dict, seed: int,
                 seconds: float, trace: bool, peak: Dict, started: float):
        from .spans import Spans
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.peak = peak
        self.started = started
        self.spans = Spans()
        self.window_seconds = (min(seconds, float(traffic["trace_seconds"]))
                               if trace else seconds)
        self.e2e: Dict[str, float] = {}
        self.facts: Dict[str, Any] = {}
        self.compared: List[Dict[str, float]] = []
        self.attempted = 0
        self.failed = 0
        self.reduced: Optional[Dict] = None
        self.compiles = 0            # XLA backend compilations so far
        self._compiles_at_start = 0
        self._window_span = None

    def say(self, message: str) -> None:
        print(f"benchmark: {message}", file=sys.stderr, flush=True)

    def check(self, name: str, value: float, limit: float) -> None:
        self.compared.append({"name": name, "value": float(value),
                              "limit": float(limit)})

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(
            c["value"] <= c["limit"] for c in self.compared)

    # -- the window's two ends (the generator calls them) -----------------
    def begin_window(self) -> float:
        import jax
        gc.collect()
        gc.freeze()
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            self.spans.tracing = True
            self._window_span = jax.profiler.TraceAnnotation("bench:window")
            self._window_span.__enter__()
        self._compiles_at_start = self.compiles
        now = time.perf_counter()
        self.e2e["setup_s"] = time.time() - self.started
        return now

    def end_window(self) -> None:
        import jax
        self.facts["compiles_in_window"] = (self.compiles
                                            - self._compiles_at_start)
        if self.trace:
            self._window_span.__exit__(None, None, None)
            self.spans.tracing = False
            jax.profiler.stop_trace()
        gc.unfreeze()


def device_report() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def prepare_jax(ctx_say) -> None:
    """The compile cache inside the checkout (``JAX_COMPILATION_CACHE_DIR``
    where that is set), keeping small programs too: by default JAX persists
    only what took a second to compile, and this path's step and bucket
    programs compile faster than that and would compile in every process."""
    import jax
    from alink_tpu.common.mlenv import place_compile_cache
    where = place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    ctx_say(f"compile cache at {where}")


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             tiny_size: bool = False, require_tpu: bool = True,
             device_kind_as: Optional[str] = None,
             parked: bool = False) -> Dict:
    """Run one cell and return the result object. ``tiny_size``,
    ``require_tpu=False`` and ``device_kind_as`` (whose peaks to count
    against where the device is not a TPU) are for the tests under
    ``tests/benchmark_suite`` alone; ``parked`` also for the command's
    ``--parked 1``, which the driver never passes."""
    started = process_start() if require_tpu else time.time()
    found = load_cell(workload, parked)
    cell = found["cell"]
    config, traffic = found["config"], found["traffic"]
    if tiny_size:
        config, traffic = tiny(config), tiny(traffic)
    import jax
    dev = device_report()
    if require_tpu and (dev["platform"] != "tpu"
                        or dev["count"] < cell["chips"]):
        raise SystemExit(
            f"benchmark: {workload} needs {cell['chips']} TPU chip(s); JAX "
            f"found {dev['count']} x {dev['platform']} ({dev['kind']}). No "
            f"result.")
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    kind = device_kind_as or dev["kind"]
    if kind not in peaks:
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r} in "
                         f"benchmark/peaks.json")
    ctx = Ctx(cell, config, traffic, int(seed), float(seconds), bool(trace),
              peaks[kind], started)
    if require_tpu:
        prepare_jax(ctx.say)
    from jax import monitoring

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            ctx.compiles += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    from alink_tpu.common.mlenv import use_local_env
    use_local_env(parallelism=cell["chips"])
    module = importlib.import_module(
        f"benchmark.generators.{traffic['generator']}")
    gen = module.Generator(ctx)
    gen.run()
    ctx.say("spans " + json.dumps({k: [c, round(v, 3)] for k, (c, v)
                                   in ctx.spans.totals().items()}))
    ctx.facts["memory_peak_bytes"] = memory_peak(cell["chips"])
    gen.release()
    gc.collect()
    if trace:
        from . import trace_reduce
        t0 = time.perf_counter()
        ctx.reduced = trace_reduce.reduce_profile(
            trace_reduce.load(trace_reduce.find_xplane(TRACE_DIR)),
            devices=cell["chips"])
        ctx.say(f"trace reduced in {time.perf_counter() - t0:.1f} s")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    t0 = time.perf_counter()
    gen.verify()
    ctx.say(f"reference compared in {time.perf_counter() - t0:.1f} s")
    return result_of(ctx, found["bench"], dev)


def result_of(ctx: Ctx, bench: Dict, dev: Dict) -> Dict:
    name = ctx.cell["name"]
    metrics: Dict[str, Dict] = {}
    if ctx.trace:
        for m in metrics_of(bench, name, "per_layer"):
            reader = importlib.import_module(
                "benchmark.readers." + m["name"].split(".")[0])
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in metrics_of(bench, name, "end_to_end"):
            metrics[m["name"]] = {"value": float(ctx.e2e[m["name"]]),
                                  "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=int(ctx.facts["memory_peak_bytes"]))
    out: Dict[str, Any] = {
        "correct": ctx.correct, "attempted": int(ctx.attempted),
        "failed": int(ctx.failed), "metrics": metrics, "device": device}
    if ctx.trace:
        device["busy_s"] = ctx.reduced["busy_s"]
        device["window_s"] = ctx.reduced["window_s"]
        out["breakdown"] = {"device_ops": ctx.reduced["device_ops"],
                            "idle_gaps": ctx.reduced["idle_gaps"]}
    out["facts"] = {k: v for k, v in ctx.facts.items()
                    if isinstance(v, (int, float, str))}
    out["compared"] = ctx.compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.run", description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", type=int, choices=(0, 1), default=0,
                    help="test-only sizes (the configuration's and the "
                         "traffic's 'tiny' overrides)")
    ap.add_argument("--parked", type=int, choices=(0, 1), default=0,
                    help="also look for the cell in benchmark/parked.json")
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   tiny_size=bool(args.tiny), parked=bool(args.parked))
    for c in out["compared"]:
        print(f"benchmark: compared {c['name']} = {c['value']:.6g} "
              f"(limit {c['limit']:.6g})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
