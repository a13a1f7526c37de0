"""ExecutionPlan — ONE frozen, hashable plan object behind every
compiled-program cache key (ROADMAP item 1; ISSUE 19 tentpole).

PRs 7-17 threaded cache-key dimensions by hand at ~15 sites: the engine
program cache (``engine/comqueue.py`` ckey), the 7 FTRL step-factory
lru keys, the chained-mode checkpoint signatures, the serving/fleet
program caches (``serving/plan.ServingPlan`` was the first slice of
this refactor), the sweep compile groups and the online-DAG stage
identities.  :class:`ExecutionPlan` collapses them into one shape —

    ExecutionPlan(subsystem, dims=((name, value), ...))

an ORDERED tuple of named dimensions.  Three contracts:

* **byte-identity** — every migrated cache derives its legacy key via
  :meth:`ExecutionPlan.legacy_key` (``tuple(value for name, value in
  dims)``), so the key tuples — and therefore hit/miss behavior and
  all lowered HLO — are byte-identical to the hand-threaded ones
  (pinned by ``tests/test_plan.py``, the PR-7 migration discipline);
* **canonical digest** — :meth:`ExecutionPlan.digest` is a blake2b
  over a canonical serialization of the dims: stable across processes
  for plans built from flags + mesh fingerprints + buckets (the
  ROADMAP item-3 AOT-persistent-cache precondition; Python's salted
  ``hash()`` is NOT);
* **named diffs** — :meth:`ExecutionPlan.diff` names exactly the
  dimensions that changed between two plans, so the compile ledger
  (``common/compileledger.py``) can answer "why did this recompile"
  with ``ALINK_TPU_SERVE_DTYPE f32->int8`` instead of "the key tuple
  differed".

Flag RESOLUTION lives here too: :func:`engine_flags`, :func:`ftrl_plan`
and :func:`sweep_plan` are the one place the key-folding flags are
latched into plan dimensions — alink-lint's ENV-KEY-FOLD rule checks
THESE functions (plus the serving-kernel resolution sites) instead of
every consumer of the values (``tools/lint/rules.py
default_config()``).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "ExecutionPlan", "engine_flags", "engine_plan",
    "engine_checkpoint_signature", "ftrl_plan",
    "ftrl_checkpoint_signature", "serving_event_plan", "sweep_plan",
    "legacy_sweep_program_key", "dag_stage_plan",
]


# ---------------------------------------------------------------------------
# canonical serialization (the digest substrate)
# ---------------------------------------------------------------------------

_SERVE_DTYPES = ("f32", "bf16", "int8")


def _canon(v: Any, out: List[bytes]) -> None:
    """Append a canonical, cross-process-stable token stream for ``v``.

    Covers the value vocabulary cache keys are actually built from:
    primitives, tuples/lists, dicts, ndarray-likes (content-digested)
    and jax ``Mesh`` objects (fingerprinted by axis names + shape +
    device strings — ``repr(mesh)`` would bake in object addresses).
    Anything else degrades to its ``repr`` WITHOUT stability claims;
    such dims still diff correctly, they just make the digest
    process-local (the engine's live-Mesh dim is the deliberate case:
    its digest-facing token is the fingerprint)."""
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        out.append(f"{type(v).__name__}:{v!r};".encode())
        return
    if isinstance(v, (tuple, list)):
        out.append(b"(")
        for x in v:
            _canon(x, out)
        out.append(b")")
        return
    if isinstance(v, dict):
        out.append(b"{")
        for k in sorted(v, key=lambda k: (type(k).__name__, repr(k))):
            _canon(k, out)
            _canon(v[k], out)
        out.append(b"}")
        return
    if hasattr(v, "devices") and hasattr(v, "axis_names"):
        # a jax Mesh: fingerprint, never repr (device objects carry
        # process-local identity)
        try:
            import numpy as _np
            devs = tuple(str(d) for d in _np.asarray(v.devices).flat)
            out.append(("mesh:" + repr((tuple(v.axis_names),
                                        tuple(v.devices.shape),
                                        devs)) + ";").encode())
            return
        except Exception:
            pass
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        import numpy as _np
        a = _np.asarray(v)
        out.append(f"nd:{a.shape}:{a.dtype}:".encode())
        out.append(hashlib.blake2b(a.tobytes(), digest_size=16).digest())
        out.append(b";")
        return
    out.append(f"obj:{v!r};".encode())


def _fmt(v: Any) -> str:
    """Bounded human-readable rendering of a dim value for diffs and
    the /compilez ledger (a 4 MB stages digest must not ride a JSON
    response whole)."""
    s = repr(v)
    if len(s) > 120:
        return s[:117] + "..."
    return s


@dataclass(frozen=True)
class ExecutionPlan:
    """One compiled-program identity: an ordered tuple of named,
    already-resolved dimensions.  Frozen + hashable (every value a
    cache key could hold already is); see the module docstring for the
    byte-identity / digest / diff contracts."""

    subsystem: str
    dims: Tuple[Tuple[str, Any], ...]

    def __post_init__(self):
        object.__setattr__(self, "dims",
                           tuple((str(n), v) for n, v in self.dims))

    # -- accessors ------------------------------------------------------
    def get(self, name: str, default: Any = None) -> Any:
        for n, v in self.dims:
            if n == name:
                return v
        return default

    def dim_names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.dims)

    def extend(self, *extra: Tuple[str, Any]) -> "ExecutionPlan":
        """A new plan with ``extra`` dims appended (per-call dimensions
        layered over a per-drain base plan)."""
        return ExecutionPlan(self.subsystem, self.dims + tuple(extra))

    # -- the three contracts --------------------------------------------
    def legacy_key(self) -> Tuple:
        """The hand-threaded key tuple this plan replaces: the dim
        VALUES in declaration order.  Byte-identity of every migrated
        cache key reduces to byte-identity of this tuple."""
        return tuple(v for _, v in self.dims)

    def digest(self) -> str:
        """Canonical blake2b hex digest of (subsystem, dims) — stable
        across processes for plans built from flags, mesh fingerprints
        and buckets (``tests/test_plan.py`` pins it in a fresh
        interpreter)."""
        out: List[bytes] = [f"plan:{self.subsystem};".encode()]
        for n, v in self.dims:
            out.append(f"dim:{n}=".encode())
            _canon(v, out)
        return hashlib.blake2b(b"".join(out), digest_size=16).hexdigest()

    def diff(self, prev: Optional["ExecutionPlan"]
             ) -> List[Dict[str, str]]:
        """The named dimensions on which ``self`` differs from ``prev``
        — the ledger's "why did this recompile" answer.  ``prev=None``
        (a cache's first program) diffs as a single ``cold-start``
        entry."""
        if prev is None:
            return [{"dim": "cold-start", "old": "-", "new": "-"}]
        mine = dict(self.dims)
        theirs = dict(prev.dims)
        out: List[Dict[str, str]] = []
        for n, _ in self.dims:
            if n not in theirs:
                out.append({"dim": n, "old": "<absent>",
                            "new": _fmt(mine[n])})
            elif mine[n] != theirs[n] or type(mine[n]) is not type(theirs[n]):
                out.append({"dim": n, "old": _fmt(theirs[n]),
                            "new": _fmt(mine[n])})
        for n, v in prev.dims:
            if n not in mine:
                out.append({"dim": n, "old": _fmt(v), "new": "<absent>"})
        return out


# ---------------------------------------------------------------------------
# engine (comqueue program cache + recovery signature)
# ---------------------------------------------------------------------------

def engine_flags() -> Tuple[Tuple[str, Any], ...]:
    """The engine's key-folding flag dims, latched ONCE per exec — the
    single derivation site ENV-KEY-FOLD checks for the engine cache.

    Order is load-bearing: these three occupy positions 7-9 of the
    ckey tuple (after ``criterion``); ``engine_plan`` splices them
    verbatim."""
    from ..common.health import health_enabled
    from ..common.profiling import step_log_enabled
    from ..engine.comqueue import donation_enabled
    return (("ALINK_TPU_STEP_LOG", step_log_enabled()),
            ("ALINK_TPU_HEALTH", health_enabled()),
            ("ALINK_TPU_DONATE", donation_enabled()))


def engine_plan(*, program_key: Any, stages_digest: Any, mesh: Any,
                num_workers: int, max_iter: int, seed: int,
                has_criterion: bool,
                flags: Sequence[Tuple[str, Any]],
                part_names: Tuple[str, ...],
                bcast_names: Tuple[str, ...]) -> ExecutionPlan:
    """The engine program-cache plan.  ``legacy_key()`` is the
    12-tuple the cache is keyed on (order pinned by
    ``tests/test_plan.py``):

        (program_key, stages_digest, mesh, nw, max_iter, seed,
         criterion?, step_log, probes, donate,
         sorted(parts), sorted(bcast))
    """
    flags = tuple(flags)
    step_log = flags[0]
    rest = flags[1:]
    return ExecutionPlan("engine", (
        ("program_key", program_key),
        ("stages", stages_digest),
        ("mesh", mesh),
        ("num_workers", int(num_workers)),
        ("max_iter", int(max_iter)),
        ("seed", int(seed)),
        ("criterion", bool(has_criterion)),
        step_log) + rest + (
        ("parts", tuple(part_names)),
        ("bcast", tuple(bcast_names)),
    ))


def engine_checkpoint_signature(plan: ExecutionPlan, *, part_sig: Tuple,
                                data_token: Any) -> Dict[str, Any]:
    """The engine's durable-run signature, derived from the plan dims
    (content identical to the historical direct
    ``recovery.program_signature`` call — old snapshots stay
    resumable)."""
    from ..engine import recovery
    return recovery.program_signature(
        num_workers=plan.get("num_workers"),
        max_iter=plan.get("max_iter"), seed=plan.get("seed"),
        part_sig=part_sig, bcast_names=plan.get("bcast"),
        stages_digest=plan.get("stages"), data_token=data_token,
        probes_on=plan.get("ALINK_TPU_HEALTH"))


# ---------------------------------------------------------------------------
# FTRL (step-factory lru keys + stream checkpoint signature)
# ---------------------------------------------------------------------------

def ftrl_plan(*, mesh: Any, alpha: float, beta: float, l1: float,
              l2: float, dim: int, dim_pad: int, update_mode: str,
              staleness: int, chunk_size: int, has_intercept: bool,
              warm_fp: str) -> ExecutionPlan:
    """The FTRL drain's plan: hyperparameters + geometry + the resolved
    key-folding flags (``ALINK_TPU_FTRL_KERNEL`` mode,
    ``ALINK_TPU_DONATE``), latched ONCE per drain at this single
    ENV-KEY-FOLD-checked site.

    ``kernel_resolved`` is the availability-probed tier the chained
    signature folds ("pallas" only when the triangular kernel can
    actually run at this chunk length/dtype — the probe-demoted drain
    keeps the flag-off signature, same numbers, interchangeable
    snapshots)."""
    from ..engine.comqueue import donation_enabled
    from ..kernels.ftrl import chained_kernel_available, ftrl_kernel_mode

    chained = update_mode == "chained"
    kern = ftrl_kernel_mode()
    resolved = "off"
    if chained and kern == "pallas":
        import jax as _jx
        import numpy as _np
        if chained_kernel_available(
                int(chunk_size),
                _np.float64 if _jx.config.jax_enable_x64
                else _np.float32):
            resolved = "pallas"
    return ExecutionPlan("ftrl", (
        ("mesh", mesh),
        ("alpha", alpha), ("beta", beta), ("l1", l1), ("l2", l2),
        ("dim", int(dim)), ("dim_pad", int(dim_pad)),
        ("update_mode", str(update_mode)),
        ("staleness", int(staleness)
         if update_mode == "staleness" else None),
        ("chunk_size", int(chunk_size) if chained else None),
        ("has_intercept", bool(has_intercept)),
        ("warm_coef_blake2b", str(warm_fp)),
        ("ALINK_TPU_FTRL_KERNEL", kern),
        ("kernel_resolved", resolved),
        ("ALINK_TPU_DONATE", donation_enabled()),
    ))


def ftrl_checkpoint_signature(plan: ExecutionPlan) -> Dict[str, Any]:
    """The FTRL stream's resume signature, derived from the plan —
    content IDENTICAL to the historical hand-built ``ck_signature``
    dict, including the conditional keys (chained-only ``chunk_size`` /
    ``ftrl_kernel``), so every pre-existing snapshot keeps its exact
    signature and stays resumable."""
    sig: Dict[str, Any] = {
        "kind": "ftrl_state",
        "alpha": plan.get("alpha"), "beta": plan.get("beta"),
        "l1": plan.get("l1"), "l2": plan.get("l2"),
        "dim": plan.get("dim"), "dim_pad": plan.get("dim_pad"),
        "update_mode": plan.get("update_mode"),
        "staleness": plan.get("staleness"),
        "has_intercept": plan.get("has_intercept"),
        "warm_coef_blake2b": plan.get("warm_coef_blake2b"),
    }
    if plan.get("update_mode") == "chained":
        sig["chunk_size"] = plan.get("chunk_size")
        if plan.get("kernel_resolved") == "pallas":
            sig["ftrl_kernel"] = "pallas"
    return sig


# ---------------------------------------------------------------------------
# serving / fleet (ledger-facing event plans over ServingPlan)
# ---------------------------------------------------------------------------

def serving_event_plan(serving_plan, *, signature: Optional[Tuple] = None,
                       sharded: Optional[bool] = None, kind: str = "",
                       bucket: int = 0, trailing: Tuple = (),
                       lanes: Optional[int] = None) -> ExecutionPlan:
    """One compiled serving program's identity as named dims.

    ``serving/plan.ServingPlan`` (PR 17) stays the serving tier's key
    object — its ``program_key`` tuples are untouched — this view
    names the dimensions so ledger diffs read ``ALINK_TPU_SERVE_DTYPE
    f32->int8`` / ``bucket 128->512`` instead of "tuple changed".  The
    kernel-signature tail convention (resolved serve dtype at [-2],
    fused mode at [-1] — ``operator/common/linear/mapper.py``) is
    decomposed when present."""
    sig = tuple(serving_plan.signature if signature is None
                else signature)
    sh = serving_plan.sharded if sharded is None else bool(sharded)
    dims: List[Tuple[str, Any]] = []
    if (len(sig) >= 2 and sig[-2] in _SERVE_DTYPES
            and isinstance(sig[-1], bool)):
        dims += [("geometry", sig[:-2]),
                 ("ALINK_TPU_SERVE_DTYPE", sig[-2]),
                 ("ALINK_TPU_SERVE_FUSED", sig[-1])]
    else:
        dims.append(("geometry", sig))
    dims += [("kind", str(kind)), ("bucket", int(bucket)),
             ("trailing", tuple(trailing)),
             ("buckets", tuple(serving_plan.buckets)),
             ("lanes", None if lanes is None else int(lanes)),
             ("sharded", sh),
             ("mesh", serving_plan.mesh_fp if sh else None)]
    return ExecutionPlan("serving", tuple(dims))


# ---------------------------------------------------------------------------
# tuning sweep (compile groups riding the engine cache)
# ---------------------------------------------------------------------------

def sweep_plan(kind: str, key_tail: Tuple) -> ExecutionPlan:
    """The sweep compile group's plan.  ``legacy_sweep_program_key()``
    reproduces the historical ``set_program_key`` tuple exactly:
    ``("sweep", kind, ALINK_TPU_SWEEP) + key_tail``."""
    from .flags import flag_value
    return ExecutionPlan("sweep", (
        ("family", "sweep"),
        ("sweep_kind", str(kind)),
        ("ALINK_TPU_SWEEP", bool(flag_value("ALINK_TPU_SWEEP", False))),
        ("key_tail", tuple(key_tail)),
    ))


def legacy_sweep_program_key(plan: ExecutionPlan) -> Tuple:
    """The byte-identical legacy sweep program key (the ``key_tail``
    dim splices back, unlike ``legacy_key()``'s value-per-dim shape)."""
    return ((plan.get("family"), plan.get("sweep_kind"),
             plan.get("ALINK_TPU_SWEEP")) + tuple(plan.get("key_tail")))


# ---------------------------------------------------------------------------
# online DAG (stage identities for cold-start attribution)
# ---------------------------------------------------------------------------

def dag_stage_plan(stage: str, config: Any) -> ExecutionPlan:
    """One DAG stage's identity: the stage name + a frozen token of the
    configuration its compiled programs depend on (the engine's
    ``freeze_config`` canonicalization).  Registered with the compile
    ledger so a restart's cold-start report names which stage's
    programs were re-paid."""
    from ..engine.comqueue import freeze_config
    return ExecutionPlan("dag", (
        ("stage", str(stage)),
        ("config", freeze_config(config)),
    ))
