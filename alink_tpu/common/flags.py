"""Central declarative registry of every ``ALINK_*`` environment flag.

Six PRs in, every feature (metrics, tracing, health, donation,
checkpointing, fused kernels) folded its own ``ALINK_TPU_*`` flag into
the program-cache key, the FTRL step lru keys, and the checkpoint
signatures *by hand*, and each site re-invented its own env parsing.
That is the "combinatorial staleness trap" of ROADMAP item 5: a new flag
that changes a traced program but misses a key fold silently serves a
stale compiled program.

This module is the single source of truth the rest of the codebase —
and the ``tools/lint`` static analyzer — cross-check against:

  * **one parser per kind** — the ``0/false/off/no`` falsy convention
    (the ``env_flag`` contract from ``common/metrics.py``) now applies
    to every boolean flag, integer/float flags treat a set-but-empty
    value as unset, and mode flags normalize their choices in one place;
  * **declared key interaction** — every flag states either which cache
    keys it folds into (``folds_into``: ``program_cache`` /
    ``checkpoint_signature`` / ``step_lru``) or WHY no fold is needed
    (``key_neutral``, a human-readable justification). Registration
    refuses a flag that declares neither: "I didn't think about
    staleness" is not a valid state.
  * **machine-checkable metadata** — ``tools/lint``'s ENV-KEY-FOLD rule
    walks every env read reachable from a program/step factory and
    fails the build when the flag's declaration does not cover that
    factory's key dimension; ``tools/gen_docs.py`` renders the
    reference tables in ``docs/performance.md`` / ``docs/observability
    .md`` from the same entries, so the docs cannot drift either.

Deliberately **zero package dependencies** (pure stdlib): the registry
is imported by ``common/metrics.py`` (the bottom of the import graph)
and loaded standalone by ``tools/lint`` via ``importlib`` without
pulling in jax.

This registry is the first concrete step toward the ROADMAP-item-5
ExecutionPlan: the flag dimension of the future plan object already
lives here, declaratively; mesh/partition specs and the donation map
join it when item 1 lands.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

__all__ = [
    "PROGRAM_CACHE", "CHECKPOINT_SIGNATURE", "STEP_LRU", "KEY_DIMENSIONS",
    "Flag", "FlagRegistry", "FLAGS", "env_flag", "flag_value", "flag_raw",
    "parse_bool",
]

# -- cache-key dimensions a flag can fold into ------------------------------
# ``program_cache``        — the engine's compiled-program cache key
#                            (engine/comqueue.py ckey) and the tree
#                            trainers' set_program_key tuples;
# ``checkpoint_signature``  — recovery.program_signature / the FTRL
#                            ck_signature dicts a resume must match;
# ``step_lru``              — the functools.lru_cache keys of the FTRL
#                            step factories (ftrl.py).
PROGRAM_CACHE = "program_cache"
CHECKPOINT_SIGNATURE = "checkpoint_signature"
STEP_LRU = "step_lru"
KEY_DIMENSIONS = frozenset({PROGRAM_CACHE, CHECKPOINT_SIGNATURE, STEP_LRU})

_FALSY = frozenset({"", "0", "false", "off", "no"})
_UNSET = object()


def parse_bool(raw: str) -> bool:
    """The one boolean semantics: ``0/false/off/no`` (any case,
    surrounding whitespace ignored) -> False; anything else -> True."""
    return raw.strip().lower() not in _FALSY


def env_flag(name: str, default: bool = False) -> bool:
    """Boolean env flag: unset -> ``default``; otherwise
    :func:`parse_bool`. Works for undeclared names too (tests);
    declared flags should agree with their registered default —
    :meth:`FlagRegistry.value` enforces that path."""
    v = os.environ.get(name)
    if v is None:
        return default
    return parse_bool(v)


def _parse_int(raw: str) -> int:
    return int(raw.strip())


def _parse_float(raw: str) -> float:
    return float(raw.strip())


def _parse_str(raw: str) -> str:
    return raw


_KIND_PARSERS: Dict[str, Callable[[str], Any]] = {
    "bool": parse_bool,
    "int": _parse_int,
    "float": _parse_float,
    "str": _parse_str,
    "mode": _parse_str,     # overridden per flag with a normalizer
}

_KINDS = tuple(_KIND_PARSERS)


@dataclass(frozen=True)
class Flag:
    """One declared environment flag.

    ``folds_into``  — key dimensions the flag's value is folded into;
    ``key_neutral`` — justification why NO fold is needed (the flag can
                      never make a cached compiled program / snapshot
                      stale). Exactly one of the two must be non-empty.
    ``accessor``    — dotted path of the canonical read helper call
                      sites should use (documentation + lint hint).
    ``section``     — which generated doc table the flag belongs to
                      (``performance`` / ``observability`` /
                      ``durability`` / ``debug`` / ``io`` / ``bench``).
    ``tolerant``    — parse failures return the default instead of
                      raising (the ``ALINK_TPU_TRACE_BUFFER`` contract).
    """
    name: str
    kind: str
    default: Any
    description: str
    section: str
    folds_into: frozenset = frozenset()
    key_neutral: str = ""
    accessor: str = ""
    parser: Optional[Callable[[str], Any]] = None
    clamp: Optional[Callable[[Any], Any]] = None
    tolerant: bool = False

    def parse(self, raw: str, default: Any = _UNSET) -> Any:
        if self.kind == "bool":
            return parse_bool(raw)
        fn = self.parser or _KIND_PARSERS[self.kind]
        try:
            v = fn(raw)
        except (TypeError, ValueError):
            if self.tolerant:
                # a call-site default override must win on the fallback
                # path too, or flag_value(name, d) ignores d exactly
                # when the env value is junk
                return self.default if default is _UNSET else default
            raise
        return self.clamp(v) if self.clamp is not None else v

    def read(self, default: Any = _UNSET) -> Any:
        """The flag's current value: live env read, declared default
        when unset (non-bool kinds also treat a set-but-EMPTY value as
        unset — ``ALINK_TPU_STREAM_PREFETCH=`` must not crash int())."""
        dflt = self.default if default is _UNSET else default
        raw = os.environ.get(self.name)
        if raw is None or (raw == "" and self.kind != "bool"):
            return dflt
        if self.kind == "bool":
            return parse_bool(raw)
        return self.parse(raw, dflt)

    @property
    def folds_label(self) -> str:
        """Doc-table cell: the folded key dimensions, or an em-dash."""
        if self.folds_into:
            return ", ".join(sorted(self.folds_into))
        return "—"


class FlagRegistry:
    """Validating container for :class:`Flag` declarations."""

    def __init__(self):
        self._flags: Dict[str, Flag] = {}

    def register(self, name: str, kind: str, default: Any, description: str,
                 section: str, **kw) -> Flag:
        if not name.startswith("ALINK_"):
            raise ValueError(f"flag {name!r} must carry the ALINK_ prefix")
        if name in self._flags:
            raise ValueError(f"flag {name!r} registered twice")
        if kind not in _KINDS:
            raise ValueError(f"flag {name!r}: unknown kind {kind!r}")
        flag = Flag(name=name, kind=kind, default=default,
                    description=description, section=section, **kw)
        if not flag.folds_into.issubset(KEY_DIMENSIONS):
            raise ValueError(
                f"flag {name!r}: folds_into {set(flag.folds_into)} not a "
                f"subset of {set(KEY_DIMENSIONS)}")
        # the core discipline: every flag must either fold into a cache
        # key or explain why it can never stale one — silence is refused
        if bool(flag.folds_into) == bool(flag.key_neutral):
            raise ValueError(
                f"flag {name!r} must declare exactly one of folds_into= "
                f"(which cache keys it rides) or key_neutral= (why no "
                f"fold is needed)")
        self._flags[name] = flag
        return flag

    # -- lookups -----------------------------------------------------------
    def __contains__(self, name: str) -> bool:
        return name in self._flags

    def __iter__(self):
        return iter(self._flags.values())

    def get(self, name: str) -> Optional[Flag]:
        return self._flags.get(name)

    def names(self) -> List[str]:
        return sorted(self._flags)

    def _require(self, name: str) -> Flag:
        flag = self._flags.get(name)
        if flag is None:
            raise KeyError(
                f"env flag {name!r} is not declared in "
                f"alink_tpu/common/flags.py — register it (with its "
                f"folds_into= or key_neutral= declaration) before use")
        return flag

    def value(self, name: str, default: Any = _UNSET) -> Any:
        """The declared flag's parsed live value (``default=`` overrides
        the registered default for call sites that carry their own)."""
        return self._require(name).read(default)

    def raw(self, name: str) -> Optional[str]:
        """The raw env string of a declared flag (``None`` when unset)
        — for flags whose spec grammar lives with its consumer
        (``ALINK_TPU_FAULT_INJECT``)."""
        self._require(name)
        return os.environ.get(name)

    def folding_into(self, dimension: str) -> Tuple[Flag, ...]:
        if dimension not in KEY_DIMENSIONS:
            raise ValueError(f"unknown key dimension {dimension!r}")
        return tuple(f for f in self if dimension in f.folds_into)

    # -- doc generation (tools/gen_docs.py) --------------------------------
    def doc_rows(self, sections: Optional[Iterable[str]] = None
                 ) -> List[Dict[str, str]]:
        """Rows for the generated env-flag reference tables: name,
        default, what it gates, which keys it folds into (or the
        key-neutral justification)."""
        want = None if sections is None else set(sections)
        rows = []
        for f in sorted(self, key=lambda f: f.name):
            if want is not None and f.section not in want:
                continue
            dflt = f.default
            if f.kind == "bool":
                dflt = "on" if dflt else "off"
            elif dflt in (None, ""):
                dflt = "unset"
            rows.append({
                "name": f.name, "default": str(dflt), "kind": f.kind,
                "section": f.section, "description": f.description,
                "folds": f.folds_label,
                "key_note": f.key_neutral or
                            f"folds into: {f.folds_label}",
            })
        return rows


def _fused_hist_parse(raw: str) -> str:
    """Normalize ``ALINK_TPU_FUSED_HIST``: falsy -> "off"; "pallas" ->
    "pallas" (backend gating — TPU or interpret mode — stays with
    ``operator/common/tree/hist.fused_hist_mode``); any other truthy
    value -> "xla"."""
    v = raw.strip().lower()
    if v in _FALSY:
        return "off"
    if v == "pallas":
        return "pallas"
    return "xla"


def _ftrl_kernel_parse(raw: str) -> str:
    """Normalize ``ALINK_TPU_FTRL_KERNEL``: falsy OR "xla" -> "off"
    (the XLA gather/scatter IS the flag-off path, and the sibling
    ``ALINK_TPU_FUSED_HIST`` taught users that "xla" names it); any
    other truthy value -> "pallas". Backend gating stays with
    ``kernels/ftrl.ftrl_kernel_mode``."""
    v = raw.strip().lower()
    return "off" if v in _FALSY or v == "xla" else "pallas"


def _serve_dtype_parse(raw: str) -> str:
    """Normalize ``ALINK_TPU_SERVE_DTYPE``: falsy -> "f32" (the full
    ship precision); bf16/bfloat16 -> "bf16"; int8/i8 -> "int8";
    f32/fp32/float32 -> "f32". Anything else refuses loudly — a typo'd
    precision must not silently serve full-precision scores."""
    v = raw.strip().lower()
    if v in _FALSY or v in ("f32", "fp32", "float32"):
        return "f32"
    if v in ("bf16", "bfloat16"):
        return "bf16"
    if v in ("int8", "i8"):
        return "int8"
    raise ValueError(
        f"ALINK_TPU_SERVE_DTYPE={raw!r}: want f32 | bf16 | int8")


FLAGS = FlagRegistry()

# -- observability ----------------------------------------------------------
FLAGS.register(
    "ALINK_TPU_METRICS", "bool", True,
    "master switch for every MetricsRegistry producer (comqueue, "
    "collectives, batch ops, streams)", "observability",
    key_neutral="host-side registry updates only; compiled HLO is "
                "byte-identical on/off (tests/test_metrics.py)",
    accessor="alink_tpu.common.metrics.metrics_enabled")
FLAGS.register(
    "ALINK_TPU_STEP_LOG", "bool", False,
    "per-superstep jax.debug.print from inside compiled programs",
    "observability",
    folds_into=frozenset({PROGRAM_CACHE}),
    accessor="alink_tpu.common.profiling.step_log_enabled")
FLAGS.register(
    "ALINK_TPU_TRACE", "bool", False,
    "the flight recorder's FINE grade: per-micro-batch / per-request spans "
    "and instant events outside a profiler session (the coarse grade: "
    "session, jit.*, *.fit, comqueue.* records in every process)",
    "observability",
    key_neutral="host-side span recording only; "
                "lowered HLO byte-identical on/off (tests/test_tracing.py)",
    accessor="alink_tpu.common.tracing.tracing_enabled")
FLAGS.register(
    "ALINK_TPU_TRACE_BUFFER", "int", 65536,
    "flight-recorder capacity in events", "observability",
    key_neutral="sizes the host-side ring buffer; never read at trace time",
    clamp=lambda n: max(1, n), tolerant=True,
    accessor="alink_tpu.common.tracing._buffer_capacity")
FLAGS.register(
    "ALINK_TPU_ADMIN_PORT", "int", 0,
    "live operations plane (common/adminz.py): serve /metrics /healthz "
    "/readyz /statusz /tracez /varz from an in-process HTTP endpoint on "
    "this port (0 = off, -1 = ephemeral OS-assigned port — tests and "
    "smokes discover it via adminz.get_admin().port)", "observability",
    key_neutral="binds a host-side stdlib HTTP server that only READS "
                "the live registry/tracer/flag state; never consulted "
                "at trace time — lowered HLO and program-cache keys "
                "byte-identical on/off (tests/test_adminz.py)",
    clamp=lambda n: max(-1, n), tolerant=True,
    accessor="alink_tpu.common.adminz.admin_port")
FLAGS.register(
    "ALINK_TPU_ADMIN_HOST", "str", "127.0.0.1",
    "bind address of the admin endpoint (loopback by default; set "
    "0.0.0.0 only on trusted networks — the plane has no auth)",
    "observability",
    key_neutral="host-side socket bind address for the admin server; "
                "never read inside a traced program",
    accessor="alink_tpu.common.adminz.admin_host")
FLAGS.register(
    "ALINK_TPU_ADMIN_TRACEZ", "int", 512,
    "max flight-recorder events one /tracez response returns (the "
    "ring itself is sized by ALINK_TPU_TRACE_BUFFER; ?n= lowers "
    "per-request)", "observability",
    key_neutral="bounds a host-side HTTP response body; the tracer "
                "ring and traced programs never see it",
    clamp=lambda n: max(1, n), tolerant=True,
    accessor="alink_tpu.common.adminz.admin_tracez_events")
FLAGS.register(
    "ALINK_TPU_PROFILE", "bool", False,
    "measured device profiling: capture windows, timing-harness "
    "attribution, live-HBM accounting (common/profiling2.py)",
    "observability",
    key_neutral="host-side timing marks, live-array walks and xprof "
                "capture only; lowered HLO and program-cache keys are "
                "byte-identical on/off (tests/test_profiling2.py)",
    accessor="alink_tpu.common.profiling2.profile_enabled")
FLAGS.register(
    "ALINK_TPU_PROFILE_DIR", "str", "",
    "artifact directory for captured jax.profiler traces "
    "(bench.py --run-dir points it at the run directory)",
    "observability",
    key_neutral="output path for host-side capture artifacts; never "
                "read inside a traced program",
    accessor="alink_tpu.common.profiling2.profile_dir")
FLAGS.register(
    "ALINK_TPU_PROFILE_XPROF", "bool", False,
    "arm bounded jax.profiler capture windows (one per scope) when "
    "profiling is on and a profile dir is set", "observability",
    key_neutral="host-side profiler start/stop around already-compiled "
                "program executions; compiled programs unchanged",
    accessor="alink_tpu.common.profiling2.xprof_enabled")
FLAGS.register(
    "ALINK_TPU_HEALTH", "bool", True,
    "in-program training-health probe channel (stacked carry series)",
    "observability",
    folds_into=frozenset({PROGRAM_CACHE, CHECKPOINT_SIGNATURE}),
    accessor="alink_tpu.common.health.health_enabled")
FLAGS.register(
    "ALINK_TPU_REQTRACE", "bool", True,
    "request-scoped tracing (common/reqtrace.py): per-request phase "
    "timelines (admit->queue->coalesce->dispatch->device->decode), "
    "tail-latency exemplars, and overlap annotations from concurrent "
    "swap/eviction/lane-rebuild/breaker events", "observability",
    key_neutral="host-side perf_counter marks and ring appends around "
                "already-compiled dispatches; lowered HLO and "
                "program-cache keys byte-identical on/off "
                "(tests/test_reqtrace.py)",
    accessor="alink_tpu.common.reqtrace.reqtrace_enabled")
FLAGS.register(
    "ALINK_TPU_REQTRACE_RING", "int", 1024,
    "finished-request timeline ring capacity (what /requestz and "
    "post-mortem bundles serve)", "observability",
    key_neutral="sizes a host-side deque of finished-request documents; "
                "never read at trace time",
    clamp=lambda n: max(1, n), tolerant=True,
    accessor="alink_tpu.common.reqtrace.ring_capacity")
FLAGS.register(
    "ALINK_TPU_ADMIN_REQUESTZ", "int", 256,
    "max request timelines one /requestz response returns (?n= lowers "
    "per-request; the ring itself is sized by ALINK_TPU_REQTRACE_RING)",
    "observability",
    key_neutral="bounds a host-side HTTP response body; the request "
                "ring and traced programs never see it",
    clamp=lambda n: max(1, n), tolerant=True,
    accessor="alink_tpu.common.adminz.admin_requestz_entries")
FLAGS.register(
    "ALINK_TPU_COMPILE_LEDGER", "bool", True,
    "compile ledger (common/compileledger.py): record every program "
    "compilation with its ExecutionPlan digest, wall time, trigger "
    "site and a named diff against the previous plan at that cache "
    "(/compilez, alink_compile_* metrics, storm detection)",
    "observability",
    key_neutral="the ledger OBSERVES cache keys and must never be one: "
                "pure host-side bookkeeping recorded after each cache "
                "decision — compiled HLO, every cache key and hit/miss "
                "behavior are byte-identical on or off (pinned by "
                "tests/test_plan.py)",
    accessor="alink_tpu.common.compileledger.ledger_enabled")
FLAGS.register(
    "ALINK_TPU_COMPILE_RING", "int", 256,
    "compile-event ring capacity (what /compilez and post-mortem "
    "bundles serve)", "observability",
    key_neutral="sizes the host-side ledger deque; never read at trace "
                "time and never part of any cache key",
    clamp=lambda n: max(16, n), tolerant=True,
    accessor="alink_tpu.common.compileledger.ring_capacity")
FLAGS.register(
    "ALINK_TPU_POSTMORTEM_DIR", "str", "",
    "post-mortem bundle directory (common/postmortem.py): on SLO burn "
    "firing, breaker open, DAG stage abort, or injected kill, one "
    "versioned JSON bundle (trace ring + request timelines + metrics "
    "+ statusz + resolved flags) is published atomically here "
    "(empty = capture off)", "observability",
    key_neutral="output path for a host-side incident artifact; never "
                "read inside a traced program",
    accessor="alink_tpu.common.postmortem.postmortem_dir")
FLAGS.register(
    "ALINK_TPU_POSTMORTEM_KEEP", "int", 8,
    "bounded bundle retention: the newest N bundles survive pruning",
    "observability",
    key_neutral="host-side file retention in the bundle directory only",
    clamp=lambda n: max(1, n), tolerant=True)
FLAGS.register(
    "ALINK_TPU_POSTMORTEM_DEBOUNCE_S", "float", 60.0,
    "process-wide bundle debounce window in seconds: one incident "
    "firing several triggers (breaker open THEN burn alert) lands ONE "
    "bundle; suppressed triggers count in "
    "alink_postmortem_suppressed_total", "observability",
    key_neutral="host-side rate limit on incident-artifact writes; "
                "never trace-shaping",
    clamp=lambda v: max(0.0, v), tolerant=True)

# -- performance ------------------------------------------------------------
FLAGS.register(
    "ALINK_TPU_MESH_DEVICES", "int", 0,
    "device count for the default session mesh (0 = all of jax.devices()); "
    "on CPU rigs, request host-platform virtual devices BEFORE the jax "
    "backend initializes (measured multi-device execution on 1-chip rigs)",
    "performance",
    key_neutral="selects the session MESH, and the mesh object itself "
                "already rides every program-cache and step-lru key (a "
                "different mesh can never serve a stale program)",
    clamp=lambda n: max(0, n),
    accessor="alink_tpu.common.mlenv.mesh_device_request")
FLAGS.register(
    "ALINK_TPU_DONATE", "bool", True,
    "buffer donation of the engine chunk-loop carry and the FTRL (z, n) "
    "state into compiled programs", "performance",
    folds_into=frozenset({PROGRAM_CACHE, STEP_LRU}),
    accessor="alink_tpu.engine.comqueue.donation_enabled")
FLAGS.register(
    "ALINK_TPU_STREAM_PREFETCH", "int", 2,
    "stream prefetch channel depth; 0 disables (inline iteration)",
    "performance",
    key_neutral="host pipelining only; FIFO order is preserved exactly "
                "(tests/test_stream.py)",
    clamp=lambda n: max(0, n),
    accessor="alink_tpu.operator.stream.prefetch.prefetch_depth")
FLAGS.register(
    "ALINK_TPU_STREAM_WORKERS", "int", 1,
    "width of the ordered stream encode pool (prefetch_map)",
    "performance",
    key_neutral="ordered pool with serial upstream; drain results are "
                "byte-identical to workers=1 (tests/test_overlap.py)",
    clamp=lambda n: max(1, n),
    accessor="alink_tpu.operator.stream.prefetch.stream_workers")
FLAGS.register(
    "ALINK_TPU_FB_ONEHOT_BYTES", "float", 6e9,
    "HBM budget for precomputing field-block one-hot design factors "
    "(<= 0 disables)", "performance",
    key_neutral="toggling the precompute changes the partitioned-input "
                "NAME SET, which already rides the program-cache key")
FLAGS.register(
    "ALINK_TPU_FUSED_HIST", "mode", "off",
    "fused tree-histogram kernel: off | xla | pallas", "performance",
    folds_into=frozenset({PROGRAM_CACHE}),
    parser=_fused_hist_parse,
    accessor="alink_tpu.operator.common.tree.hist.fused_hist_mode")
FLAGS.register(
    "ALINK_TPU_PALLAS_INTERPRET", "bool", False,
    "run Pallas kernels in interpret mode off-TPU (tests/CI) — the "
    "availability gate of the whole kernel tier (kernels/runtime.py)",
    "performance",
    key_neutral="only shifts the RESOLVED kernel modes (fused-hist, "
                "FTRL kernel, fused serve), and the resolved modes are "
                "what fold into the program/step/serving cache keys",
    accessor="alink_tpu.kernels.runtime.pallas_interpret")
FLAGS.register(
    "ALINK_TPU_FTRL_KERNEL", "mode", "off",
    "Pallas FTRL kernel tier: off | pallas — VMEM-resident (z, n) "
    "state gather / duplicate-safe scatter-add in the per-sample and "
    "staleness step programs, triangular chained-correction matvec in "
    "the chained step program", "performance",
    folds_into=frozenset({STEP_LRU, CHECKPOINT_SIGNATURE}),
    parser=_ftrl_kernel_parse,
    accessor="alink_tpu.kernels.ftrl.ftrl_kernel_mode")
FLAGS.register(
    "ALINK_TPU_AOT_CACHE", "bool", True,
    "persistent AOT executable store (common/aotcache.py): serve "
    "program-cache misses from exported-on-disk executables before "
    "compiling (load-before-compile), and export fresh compiles for "
    "the next process — active only when ALINK_TPU_AOT_CACHE_DIR is "
    "also set", "performance",
    key_neutral="the store OBSERVES the plan-keyed caches and never "
                "keys one: every artifact is validated against the "
                "exact ExecutionPlan digest the in-memory key derives "
                "from plus a rig/toolchain fingerprint before install, "
                "a mismatch falls through to the same compile as "
                "flag-off, and installed programs are exported from "
                "the identical jit — outputs are bitwise-identical "
                "cache-on vs cache-off (tests/test_aotcache.py)",
    accessor="alink_tpu.common.aotcache.aot_enabled")
FLAGS.register(
    "ALINK_TPU_AOT_CACHE_DIR", "str", "",
    "AOT artifact root (<dir>/<cache>/<plan-digest>.aot); empty (the "
    "default) disables the executable store entirely", "performance",
    key_neutral="a host-side storage path: it decides WHERE validated "
                "artifacts live, never which program a cache key "
                "resolves to — unset, every instrumented site runs "
                "its historical code path byte-for-byte",
    accessor="alink_tpu.common.aotcache.aot_dir")
FLAGS.register(
    "ALINK_TPU_AOT_CACHE_KEEP", "int", 128,
    "bounded AOT retention: the newest N artifacts per cache "
    "directory survive the post-store prune (mtime order)",
    "performance",
    key_neutral="host-side file retention in the artifact directory "
                "only; a pruned artifact is a plain load miss",
    clamp=lambda n: max(8, n), tolerant=True,
    accessor="alink_tpu.common.aotcache.aot_keep")

# -- serving ----------------------------------------------------------------
# The compiled serving tier's program cache keys on (model signature,
# encoding kind, shape bucket, encoded shapes/dtypes) — everything that
# can change a compiled serving program is IN the key, so every serving
# flag below is key-neutral by construction. tools/lint's ENV-KEY-FOLD
# rule checks the serving factory root against these declarations.
FLAGS.register(
    "ALINK_TPU_SERVE_COMPILED", "bool", False,
    "route ModelMapStreamOp (stream predict twins) through the compiled "
    "serving path (CompiledPredictor); off = the exact host mapper path",
    "serving",
    key_neutral="selects HOST scoring implementation only: flag off runs "
                "no compiled program at all, flag on keys every program "
                "on (model signature, bucket, shapes) — a toggle can "
                "never reuse a stale compiled program",
    accessor="alink_tpu.serving.predictor.serve_compiled_enabled")
FLAGS.register(
    "ALINK_TPU_SERVE_BUCKETS", "str", "",
    "serving shape-bucket set, comma-separated batch sizes "
    "(unset = 1,8,32,128,512); requests pad to the smallest covering "
    "bucket", "serving",
    key_neutral="selects WHICH bucket a request pads to; the bucket "
                "itself rides every serving program-cache key, so a "
                "different bucket set compiles new programs but can "
                "never reuse a stale one",
    accessor="alink_tpu.serving.predictor.serve_buckets")
FLAGS.register(
    "ALINK_TPU_SERVE_WINDOW_MS", "float", 2.0,
    "micro-batcher latency budget: max milliseconds the serving loop "
    "holds a batch below ALINK_TPU_SERVE_MIN_FILL rows waiting for "
    "stragglers (inert at the default min-fill of 1 — adaptive "
    "dispatch)", "serving",
    key_neutral="host-side batch-assembly scheduling only; never read "
                "at trace time",
    clamp=lambda v: max(0.0, v),
    accessor="alink_tpu.serving.predictor.serve_window_s")
FLAGS.register(
    "ALINK_TPU_SERVE_MIN_FILL", "int", 1,
    "micro-batcher fill target in rows: batches below it wait up to "
    "ALINK_TPU_SERVE_WINDOW_MS before dispatching (1 = dispatch the "
    "moment the queue drains — latency over occupancy)", "serving",
    key_neutral="host-side batch-assembly scheduling only; never read "
                "at trace time",
    clamp=lambda n: max(1, n),
    accessor="alink_tpu.serving.predictor.serve_min_fill")
FLAGS.register(
    "ALINK_TPU_SERVE_QUEUE", "int", 1024,
    "admission-control bound of the serving request channel (a full "
    "queue blocks submitters — backpressure)", "serving",
    key_neutral="host-side admission control on the request channel; "
                "never read at trace time",
    clamp=lambda n: max(1, n),
    accessor="alink_tpu.serving.predictor.serve_queue_depth")
FLAGS.register(
    "ALINK_TPU_SERVE_SHARDED", "bool", False,
    "compile serving bucket programs under the session mesh's partition "
    "rules: feature-sharded model placement (io/sharding.py), one "
    "manifest psum per dispatch; off = single-device programs", "serving",
    key_neutral="the resolved sharded mode and the mesh's device "
                "identity ride every serving program-cache key "
                "(CompiledPredictor mesh fingerprint), so a toggle or a "
                "mesh change compiles new programs but can never reuse "
                "a stale one",
    accessor="alink_tpu.serving.sharded.serve_sharded_enabled")
FLAGS.register(
    "ALINK_TPU_SERVE_REPLICAS", "int", 1,
    "PredictServer serving-loop replica count (data-parallel dispatch "
    "fan-out across the session mesh's chips); 0 = one replica per "
    "mesh device; sharded predictors always run one loop", "serving",
    key_neutral="host-side dispatch fan-out only: replicas pick WHICH "
                "device executes a batch, and jax keys its per-device "
                "executables on placement — the serving program cache "
                "is device-independent host routing",
    clamp=lambda n: max(0, n),
    accessor="alink_tpu.serving.sharded.serve_replicas")
FLAGS.register(
    "ALINK_TPU_SERVE_FUSED", "bool", False,
    "fused Pallas serving score kernel for linear bucket programs: "
    "encode-gather -> dot -> link in one kernel, no intermediate HBM "
    "round-trip (TPU or ALINK_TPU_PALLAS_INTERPRET=1; demotions "
    "recorded via alink_serve_fallback_total)", "serving",
    key_neutral="the RESOLVED fused mode rides the ServingKernel "
                "signature, which leads every serving program-cache "
                "key — a toggle compiles new programs, never reuses a "
                "stale one (tests/test_kernels.py pins the miss)",
    accessor="alink_tpu.kernels.serve.serve_fused_requested")
FLAGS.register(
    "ALINK_TPU_SERVE_DTYPE", "mode", "f32",
    "serving score precision: f32 (full ship precision) | bf16 "
    "(bf16 terms, f32 accumulation) | int8 (symmetric per-model "
    "weight quantization with a stored scale, f32 accumulation); "
    "parity gate is bitwise for f32, label-exact + pinned-tolerance "
    "for bf16/int8", "serving",
    key_neutral="the resolved dtype rides the ServingKernel signature, "
                "which leads every serving program-cache key — a "
                "toggle compiles new programs, never reuses a stale "
                "one (tests/test_kernels.py pins the miss)",
    parser=_serve_dtype_parse,
    accessor="alink_tpu.kernels.serve.serve_dtype")
# -- serving resilience (ISSUE 14): every knob below is host-side
# runtime POLICY — when to shed, when to degrade, how fast to re-probe
# — and never trace-shaping: no compiled serving program, cache key or
# checkpoint signature reads any of them.
FLAGS.register(
    "ALINK_TPU_SERVE_BREAKER", "bool", True,
    "circuit-broken degradation of the compiled serving dispatch: "
    "consecutive failures open a per-model-version breaker that routes "
    "traffic to the host-mapper fallback and re-probes the compiled "
    "path on a deterministic backoff schedule; 0 = pre-resilience "
    "behavior (a failed batch fails its requests, no fallback routing)",
    "serving",
    key_neutral="breaker state is runtime dispatch ROUTING between two "
                "already-compiled paths (the bucket programs and the "
                "host mapper), never trace-shaping: no program is "
                "compiled, keyed or invalidated by it",
    accessor="alink_tpu.serving.resilience.serve_breaker_enabled")
FLAGS.register(
    "ALINK_TPU_SERVE_BREAKER_THRESHOLD", "int", 3,
    "consecutive compiled-dispatch failures (closed state) that trip "
    "the serving circuit breaker open", "serving",
    key_neutral="host-side failure counting for dispatch routing only; "
                "never read at trace time",
    clamp=lambda n: max(1, n),
    accessor="alink_tpu.serving.resilience.breaker_threshold")
FLAGS.register(
    "ALINK_TPU_SERVE_BREAKER_BACKOFF_MS", "float", 50.0,
    "first open->half-open probe delay of the serving breaker "
    "(deterministic exponential schedule, no jitter)", "serving",
    key_neutral="host-side recovery scheduling only; never read at "
                "trace time",
    clamp=lambda v: max(0.0, v),
    accessor="alink_tpu.serving.resilience.breaker_backoff_s")
FLAGS.register(
    "ALINK_TPU_SERVE_BREAKER_FACTOR", "float", 2.0,
    "serving-breaker backoff multiplier applied per re-open (a failed "
    "half-open probe re-opens with the NEXT step — the no-flap rule)",
    "serving",
    key_neutral="host-side recovery scheduling only; never read at "
                "trace time",
    clamp=lambda v: max(1.0, v),
    accessor="alink_tpu.serving.resilience.breaker_factor")
FLAGS.register(
    "ALINK_TPU_SERVE_BREAKER_MAX_MS", "float", 5000.0,
    "serving-breaker backoff ceiling", "serving",
    key_neutral="host-side recovery scheduling only; never read at "
                "trace time",
    clamp=lambda v: max(0.0, v),
    accessor="alink_tpu.serving.resilience.breaker_max_s")
FLAGS.register(
    "ALINK_TPU_SERVE_FEEDER_RETRIES", "int", 3,
    "bounded retry budget of the supervised model-stream feeders for a "
    "TRANSIENT swap failure (poisoned snapshots skip-and-record "
    "instead; the server keeps serving the last good model either way)",
    "serving",
    key_neutral="host-side feeder retry policy; a retried swap_model "
                "re-runs the same keyed build — never trace-shaping",
    clamp=lambda n: max(0, n),
    accessor="alink_tpu.serving.resilience.feeder_retries")
FLAGS.register(
    "ALINK_TPU_SERVE_FEEDER_BACKOFF_MS", "float", 20.0,
    "first feeder retry delay, doubling per attempt", "serving",
    key_neutral="host-side feeder retry pacing only; never read at "
                "trace time",
    clamp=lambda v: max(0.0, v),
    accessor="alink_tpu.serving.resilience.feeder_backoff_s")
FLAGS.register(
    "ALINK_TPU_SERVE_SWAP", "mode", "double",
    "hot model-swap mode: double (standby slot prepared off the serving "
    "loop, atomic flip) | sync (flip waits for device residency)",
    "serving",
    key_neutral="host-side model-slot management; the model signature "
                "rides every serving program-cache key, so neither mode "
                "can serve a stale program",
    parser=lambda raw: ("sync" if raw.strip().lower() == "sync"
                        else "double"),
    accessor="alink_tpu.serving.predictor.serve_swap_mode")
# -- multi-tenant fleet (serving/fleet.py, ISSUE 17) -------------------------
FLAGS.register(
    "ALINK_TPU_FLEET_HBM_BUDGET", "int", 0,
    "device-bytes budget for resident fleet tenant weights: cold "
    "tenants LRU-evict over it and re-admit from the snapshot store "
    "on their next request (0 = unlimited, no eviction)", "serving",
    key_neutral="host-side residency policy: eviction drops/re-places "
                "weight ARGUMENTS (re-admitted bitwise from the "
                "snapshot store); the compiled programs are keyed on "
                "geometry and never on which tenants are resident",
    clamp=lambda n: max(0, n),
    accessor="alink_tpu.serving.fleet.fleet_hbm_budget")
FLAGS.register(
    "ALINK_TPU_FLEET_LANES", "str", "",
    "tenant-lane bucket set of the coalesced fleet programs, "
    "comma-separated lane widths (unset = 4,16,64): a cross-tenant "
    "dispatch pads its weight stack to the smallest covering lane "
    "bucket", "serving",
    key_neutral="selects WHICH lane width a dispatch pads to; the lane "
                "width itself rides every coalesced program-cache key "
                "(ServingPlan.program_key lanes dimension), so a "
                "different lane set compiles new programs but can "
                "never reuse a stale one",
    accessor="alink_tpu.serving.fleet.fleet_lanes")
FLAGS.register(
    "ALINK_TPU_FLEET_TENANT_QUOTA", "int", 0,
    "max in-flight requests per fleet tenant; exceeding it is a typed "
    "admission rejection (TenantQuotaExceeded, shed reason 'quota') — "
    "one tenant's storm cannot consume another tenant's admission "
    "slots (0 = unlimited)", "serving",
    key_neutral="host-side admission control per tenant; never read "
                "at trace time",
    clamp=lambda n: max(0, n),
    accessor="alink_tpu.serving.fleet.fleet_tenant_quota")
FLAGS.register(
    "ALINK_TPU_FLEET_COALESCE", "bool", True,
    "coalesce fleet batches across same-geometry tenants through the "
    "lane-stacked programs (per-row tenant->lane weight gather); off = "
    "per-tenant dispatch through the group's single-model programs — "
    "bitwise-identical answers either way (tests/test_fleet.py)",
    "serving",
    key_neutral="routing between two program families that answer "
                "bitwise-identically; each family keys its own cache "
                "entries (the lanes dimension of ServingPlan."
                "program_key), so a toggle can never reuse a stale "
                "program",
    accessor="alink_tpu.serving.fleet.fleet_coalesce_enabled")
FLAGS.register(
    "ALINK_TPU_FLEET_SNAPSHOT_DIR", "str", "",
    "root directory of the per-tenant fleet model snapshot store (the "
    "eviction/re-admission backing; empty = a process-lifetime temp "
    "directory)", "serving",
    key_neutral="host-side snapshot storage location; snapshots are "
                "validated against the tenant group's geometry "
                "signature on load, never read at trace time",
    accessor="alink_tpu.serving.fleet.fleet_snapshot_dir")

# -- online-learning DAG (alink_tpu/online/, ISSUE 15) -----------------------
# Every ALINK_TPU_E2E_* flag is host-side DAG runtime policy — stage
# supervision, SLO bounds, request pacing. None reaches a traced
# program: the DAG composes the EXISTING trainer/serving/feeder program
# factories unchanged, and with the flag family at defaults (and no
# OnlineDag constructed) the serving and trainer lowered HLO and
# response bytes are byte-identical to pre-DAG builds
# (tests/test_online.py pins it).
FLAGS.register(
    "ALINK_TPU_E2E_DAG", "bool", False,
    "arm the online DAG's flag-derived defaults: an OnlineDag built "
    "without an explicit SloContract/deadline picks them up from the "
    "ALINK_TPU_E2E_SLO_*/_DEADLINE_MS flags (off = explicit arguments "
    "only; constructing the DAG itself is always explicit API)", "e2e",
    key_neutral="host-side default selection for the DAG runtime; the "
                "DAG only composes existing keyed program factories "
                "and the flag is never read at trace time",
    accessor="alink_tpu.online.slo.e2e_dag_enabled")
FLAGS.register(
    "ALINK_TPU_E2E_SLO_P99_MS", "float", 0.0,
    "end-to-end SLO: serving p99 bound in ms evaluated live per eval "
    "window by the online DAG's SloContract (0 = clause off)", "e2e",
    key_neutral="host-side SLO verdict evaluation over already-"
                "measured latencies; never trace-shaping",
    clamp=lambda v: max(0.0, v),
    accessor="alink_tpu.online.slo.slo_p99_s")
FLAGS.register(
    "ALINK_TPU_E2E_SLO_STALENESS_MS", "float", 0.0,
    "end-to-end SLO: model swap staleness bound in ms (snapshot "
    "emission -> swap installed) for the online DAG (0 = clause off)",
    "e2e",
    key_neutral="host-side SLO verdict evaluation over swap wall "
                "times; never trace-shaping",
    clamp=lambda v: max(0.0, v),
    accessor="alink_tpu.online.slo.slo_staleness_s")
FLAGS.register(
    "ALINK_TPU_E2E_SLO_AUC", "float", 0.0,
    "end-to-end SLO: final-window AUC floor for the online DAG's "
    "windowed stream eval (0 = clause off)", "e2e",
    key_neutral="host-side SLO verdict over eval-window metrics "
                "computed from served responses; never trace-shaping",
    clamp=lambda v: max(0.0, min(1.0, v)),
    accessor="alink_tpu.online.slo.slo_auc_floor")
FLAGS.register(
    "ALINK_TPU_E2E_DEADLINE_MS", "float", 0.0,
    "default request deadline the online DAG stamps on its side "
    "traffic when ALINK_TPU_E2E_DAG=1 and no explicit deadline_s was "
    "passed (0 = no deadline); eval ground-truth traffic retries typed "
    "rejections instead of dropping windows", "e2e",
    key_neutral="request deadline routing (shed-before-dispatch) "
                "between already-compiled paths; the PR 14 deadline "
                "machinery it feeds is itself key-neutral",
    clamp=lambda v: max(0.0, v),
    accessor="alink_tpu.online.slo.e2e_deadline_s")
FLAGS.register(
    "ALINK_TPU_E2E_BURN_FAST_S", "float", 300.0,
    "SLO burn-rate monitor: FAST window length in seconds (the paging "
    "window — mean clause burn over it >= 1.0 marks a CRITICAL burn "
    "and flips /readyz to 503 while active)", "e2e",
    key_neutral="host-side window length for burn-rate evaluation "
                "over already-measured SLO observations; never "
                "trace-shaping",
    clamp=lambda v: max(1.0, v), tolerant=True,
    accessor="alink_tpu.online.slo.burn_fast_s")
FLAGS.register(
    "ALINK_TPU_E2E_BURN_SLOW_S", "float", 3600.0,
    "SLO burn-rate monitor: SLOW window length in seconds (the "
    "sustained-burn window — budget-fraction burn over it >= 1.0 "
    "means the whole window's error budget is spent)", "e2e",
    key_neutral="host-side window length for burn-rate evaluation "
                "over already-measured SLO observations; never "
                "trace-shaping",
    clamp=lambda v: max(1.0, v), tolerant=True,
    accessor="alink_tpu.online.slo.burn_slow_s")
FLAGS.register(
    "ALINK_TPU_E2E_MAX_RESTARTS", "int", 3,
    "per-stage restart budget of the online DAG's supervisors "
    "(trainer restart-from-checkpoint, feeder respawn-with-last-good-"
    "model, ingest resume-at-offset)", "e2e",
    key_neutral="host-side supervision budget; a restarted stage "
                "rebuilds through the same keyed factories (the FTRL "
                "checkpoint signature refuses any mismatch)",
    clamp=lambda n: max(0, n),
    accessor="alink_tpu.online.dag.e2e_max_restarts")
FLAGS.register(
    "ALINK_TPU_E2E_PACING", "mode", "deterministic",
    "online DAG pacing: deterministic (score batch k+1 only after "
    "train-commit k — bitwise-resumable eval windows) | throughput "
    "(free-running scoring; the bench's steady-state mode)", "e2e",
    key_neutral="host-side scheduling of how scoring interleaves with "
                "training; both modes dispatch the same compiled "
                "programs, and the trainer pace hook is host-only",
    parser=lambda raw: ("throughput"
                        if raw.strip().lower() in ("throughput", "free",
                                                   "async")
                        else "deterministic"),
    accessor="alink_tpu.online.dag.e2e_pacing")

# -- tuning (mesh-parallel sweeps, alink_tpu/tuning/) ------------------------
FLAGS.register(
    "ALINK_TPU_SWEEP", "bool", False,
    "route GridSearchCV/GridSearchTVSplit candidate loops through the "
    "mesh-parallel tuning sweep engine when every grid axis is "
    "carry-resident for a supported estimator (fallbacks recorded as "
    "alink_sweep_fallback_total)", "tuning",
    folds_into=frozenset({PROGRAM_CACHE}),
    accessor="alink_tpu.tuning.sweep.sweep_enabled")
FLAGS.register(
    "ALINK_TPU_SWEEP_ETA", "int", 3,
    "ASHA successive-halving reduction factor: each rung keeps the top "
    "ceil(alive/eta) points", "tuning",
    key_neutral="drives HOST boundary pruning of the carry-resident "
                "alive mask only; the compiled sweep program's geometry "
                "and collective set are independent of the rung "
                "schedule (chunk limits are traced scalars)",
    clamp=lambda n: max(2, n),
    accessor="alink_tpu.tuning.sweep.sweep_eta")
FLAGS.register(
    "ALINK_TPU_SWEEP_RUNG", "int", 0,
    "default ASHA rung period in supersteps for sweeps that enable "
    "pruning without an explicit AshaConfig (0 = max_iter // 4, "
    "minimum 1)", "tuning",
    key_neutral="selects the boundary cadence of the chunked sweep "
                "loop; the chunk limit is a traced scalar, so cadence "
                "never changes a compiled program",
    clamp=lambda n: max(0, n),
    accessor="alink_tpu.tuning.sweep.sweep_rung")

# -- durability -------------------------------------------------------------
FLAGS.register(
    "ALINK_TPU_ASYNC_SNAPSHOT", "bool", True,
    "background checkpoint writer (off = strictly synchronous path)",
    "durability",
    key_neutral="on-disk artifacts and kill-and-resume results are "
                "bitwise-identical to the sync path (tests/test_overlap.py)",
    accessor="alink_tpu.engine.recovery.async_snapshot_enabled")
FLAGS.register(
    "ALINK_TPU_FAULT_INJECT", "str", "",
    "deterministic fault injection at durability/serving sites: "
    "site:index[-end][:mode[:param]] entries (;-separated) with modes "
    "kill (default) | error (catchable transient) | delay:MS (latency) "
    "| corrupt (snapshot bit-flip at the producer)", "durability",
    key_neutral="host-side raise/sleep/corrupt at superstep/batch/save/"
                "dispatch boundaries; never enters a traced program",
    accessor="alink_tpu.common.faults.fault_spec")

# -- debug ------------------------------------------------------------------
FLAGS.register(
    "ALINK_VERIFY_PROGRAM_CACHE", "bool", False,
    "program-cache debug guard: re-trace on every hit and compare jaxprs",
    "debug",
    key_neutral="debug-only guard; bypasses the stage-digest memo and "
                "re-traces on hits — strictly more conservative than off")
FLAGS.register(
    "ALINK_NO_NATIVE", "bool", False,
    "disable the ctypes native helper library (pure-Python fallbacks)",
    "debug",
    key_neutral="selects host-side ctypes vs numpy implementations; no "
                "compiled XLA program involved")

# -- io ---------------------------------------------------------------------
FLAGS.register(
    "ALINK_DIRECT_READER_POLICY", "str", "memory",
    "DirectReader bridge policy: memory | db (the generic "
    "ALINK_<PROPERTY> env fallback of DirectReaderPropertiesStore)", "io",
    key_neutral="host-side IO bridge selection; unreachable from any "
                "program/step factory")

# -- bench knobs (read by bench.py, outside the analyzed package) -----------
FLAGS.register(
    "ALINK_TPU_DISKBENCH_ROWS", "int", 1000000,
    "row count for the from-disk ingest benchmark", "bench",
    key_neutral="bench workload sizing; read only by bench.py")
FLAGS.register(
    "ALINK_TPU_DISK_COMMIT", "bool", True,
    "commit parsed disk shards to device during pipelined ingest "
    "(0 restores the host-array path)", "bench",
    key_neutral="changes where parsed shards land (host vs device), not "
                "any compiled program; parity asserted by the bench row")
FLAGS.register(
    "ALINK_TPU_DISK_GROUPS", "int", 4,
    "async device-transfer groups for the from-disk ingest leg", "bench",
    key_neutral="host-side transfer batching only",
    clamp=lambda n: max(1, n))
FLAGS.register(
    "ALINK_TPU_REPIN_BASELINE", "bool", False,
    "re-measure the pinned compiled CPU baseline (BASELINE_compiled.json)",
    "bench",
    key_neutral="bench provenance control; read only by bench.py")
FLAGS.register(
    "ALINK_TPU_GBDT_LARGE_ROWS", "int", 488420,
    "row count for the gbdt_adult_large roofline row", "bench",
    key_neutral="bench workload sizing; read only by bench.py")
FLAGS.register(
    "ALINK_TPU_GBDT_LARGE_HIST", "mode", "xla",
    "fused-hist mode forced for the large GBDT roofline row", "bench",
    key_neutral="bench sets ALINK_TPU_FUSED_HIST from it, and THAT flag "
                "folds into the program-cache key",
    parser=_fused_hist_parse)
FLAGS.register(
    "ALINK_TPU_ALS_LARGE_NNZ", "int", 10000000,
    "ratings count for the als_movielens_large roofline row", "bench",
    key_neutral="bench workload sizing; read only by bench.py")


def flag_value(name: str, default: Any = _UNSET) -> Any:
    """Module-level convenience for :meth:`FlagRegistry.value`."""
    return FLAGS.value(name, default)


def flag_raw(name: str) -> Optional[str]:
    """Module-level convenience for :meth:`FlagRegistry.raw`."""
    return FLAGS.raw(name)
