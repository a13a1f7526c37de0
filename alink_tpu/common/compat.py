"""Thin wrappers over the installed jax (0.9.0) that every internal
caller shares: one ``shard_map`` entry, the XLA cost model as a flat
dict, lowered text, and the batched host fetch.

Import of jax is deferred to first call — ``alink_tpu.common`` must stay
importable without touching a backend (XLA flags latch at backend init).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

__all__ = ["shard_map", "lowered_text", "compiled_cost_analysis",
           "device_get_tree"]


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_vma: Optional[bool] = None, **kw) -> Callable:
    """``jax.shard_map``; ``check_vma`` unspecified keeps jax's default
    (the varying-axes check on)."""
    import jax
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def compiled_cost_analysis(stage: Any) -> Optional[dict]:
    """XLA's static cost model for a ``jax.stages.Lowered`` or
    ``Compiled`` object as a flat ``{str: float}`` dict — the
    interesting keys are ``"flops"`` and ``"bytes accessed"`` — or
    ``None`` (never an exception) where the backend has none, so
    telemetry callers attach cost data when available and degrade
    silently when not.

    Caveats (documented in docs/observability.md): the model is *static*
    — a ``while``-loop body is costed once, not per trip, so for the
    engine's superstep programs the figures describe one loop pass;
    non-arithmetic ops (data movement, collectives) may be missing or
    backend-approximate.
    """
    fn = getattr(stage, "cost_analysis", None)
    if fn is None:
        return None
    try:
        ca = fn()
    except Exception:
        return None
    if not isinstance(ca, dict):
        return None
    out = {}
    for k, v in ca.items():
        try:
            out[str(k)] = float(v)
        except (TypeError, ValueError):
            continue
    return out or None


def lowered_text(lowered: Any, debug_info: bool = False) -> str:
    """``Lowered.as_text``; ``debug_info=True`` keeps named-scope /
    location metadata in the text."""
    return lowered.as_text(debug_info=debug_info)


def device_get_tree(tree: Any) -> Any:
    """Fetch every leaf of a pytree to host numpy in ONE batched
    ``jax.device_get``: the batched call starts all device->host copies
    asynchronously and blocks once, where per-leaf ``np.asarray``
    blocks on each leaf's copy in turn. Host leaves pass through as
    numpy. The one batched-fetch
    idiom every boundary shares (ComQueueResult reads, snapshot
    persistence) — fix fetch behavior here, not at call sites."""
    import jax
    import numpy as np
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [np.asarray(x) for x in jax.device_get(leaves)])
