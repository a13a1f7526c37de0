"""ComQueue superstep recovery — durable snapshots + resumable runs.

The reference's ``IterativeComQueue`` is fault-tolerant because it compiles
to a Flink iterative dataflow and Flink checkpoints it; a preempted
TaskManager restarts from the last completed checkpoint and the BSP loop
continues. The TPU rebuild compiles the whole superstep loop into ONE XLA
program (engine/comqueue.py), which is the fast path and also the
durability problem: a preempted host loses every superstep since launch.

This module restores the Flink property without giving up the compiled
loop. With ``checkpoint_every=N`` the engine runs the SAME superstep body
through a *chunked* while-loop whose upper bound is a **traced scalar**
(one compiled program serves every chunk), and between chunks — on the
host, outside the compiled program — the stacked carry is fetched and
persisted through ``common/checkpoint.py``. ``resume_from=`` loads the
newest valid snapshot, validates it against the program's signature, and
re-enters the loop mid-run; because the snapshot round-trips bitwise and
the chunk program is deterministic, the resumed run's final state is
bit-identical to the uninterrupted one (tests/test_checkpoint.py proves
this for L-BFGS and KMeans).

What checkpointing costs: one device->host fetch of the carry every N
supersteps plus the file writes — and nothing inside the compiled
program. The lowered chunk programs contain no host callbacks and exactly
the collectives of the unchunked program (asserted by a lowered-HLO test,
the same discipline as the collective-manifest accounting).

Overlap (``ALINK_TPU_ASYNC_SNAPSHOT``, default on): the fetch + file
write above no longer sit on the accelerator's critical path. At a chunk
boundary the driver takes a device-side copy of the carry (one HBM copy;
with donation on, the original is about to be consumed by the next chunk
anyway), dispatches chunk t+1 immediately, and a bounded background
writer (ONE snapshot in flight) fetches and persists snapshot t while
the device runs t+1. The writer commits strictly in order and the driver
barriers on it before returning, so the on-disk snapshot sequence — and
kill-and-resume parity — is bitwise identical to the synchronous path;
``on_snapshot`` (the health watchdog) fires from the writer after each
publish, and its abort surfaces on the main thread at the next boundary,
at most one chunk later, with the triggering snapshot already durable.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from ..common.checkpoint import load_latest_validated, save_checkpoint
from ..common.faults import maybe_crash
from ..common.metrics import env_flag, get_registry, metrics_enabled
from ..common.profiling2 import hbm_snapshot, profile_window
from ..common.tracing import trace_instant, trace_span

__all__ = ["CheckpointConfig", "program_signature", "resume_state", "drive",
           "async_snapshot_enabled"]

SCOPE = "comqueue"
SITE = "comqueue.superstep"


@dataclass(frozen=True)
class CheckpointConfig:
    """Engine checkpoint knobs (``IterativeComQueue.set_checkpoint``).

    ``every``      — persist the carry at every superstep boundary that is
                     a multiple of this (and at the final state);
    ``directory``  — snapshot root (one ``ckpt-<step>`` dir per snapshot);
                     ``None`` runs the chunked loop WITHOUT persistence —
                     the boundary-driven execution mode of
                     ``IterativeComQueue.set_boundary`` (the tuning
                     sweep's ASHA rungs), same compiled chunk programs,
                     zero disk writes;
    ``keep_last``  — bounded retention, pruned after each publish;
    ``resume_from``— directory to resume from (usually == ``directory``);
                     the newest VALID snapshot wins; a signature mismatch
                     fails loudly instead of resuming the wrong program.
    """
    directory: Optional[str]
    every: int = 1
    keep_last: int = 3
    resume_from: Optional[str] = None

    def __post_init__(self):
        if int(self.every) < 1:
            raise ValueError(f"checkpoint_every must be >= 1, "
                             f"got {self.every}")
        if int(self.keep_last) < 1:
            # fail at construction, not mid-training from inside the
            # first snapshot's prune
            raise ValueError(f"checkpoint_keep must be >= 1, "
                             f"got {self.keep_last}")


def program_signature(*, num_workers: int, max_iter: int, seed: int,
                      part_sig: Tuple, bcast_names: Tuple,
                      stages_digest: Any,
                      data_token: Any = None,
                      probes_on: bool = False) -> Dict[str, Any]:
    """JSON identity of the compiled superstep program a snapshot belongs
    to. A resume target must match exactly: same worker count, same input
    geometry, same stage structure — otherwise the carry pytree would be
    fed to a different program and the 'bitwise-identical' contract would
    silently turn into garbage.

    ``data_token`` additionally fingerprints the training DATA (content
    hash for host arrays; shape/dtype only for already-device-resident
    inputs, where a content hash would round-trip device memory): without
    it, a finished run's final snapshot would be silently 'resumed' as
    already-done for a *different* dataset of the same geometry."""
    import hashlib
    stages = hashlib.blake2b(repr(stages_digest).encode(),
                             digest_size=12).hexdigest()
    sig = {"kind": "comqueue_carry", "num_workers": int(num_workers),
           "max_iter": int(max_iter), "seed": int(seed),
           "parts": [list(map(str, item)) for item in part_sig],
           "bcast": [str(n) for n in bcast_names],
           "stages_blake2b": stages}
    if probes_on:
        # health probes add stacked carry entries: a probe-less snapshot
        # must not resume a probed program (and vice versa). Emitted only
        # when on, so pre-health snapshots stay resumable unchanged.
        sig["health_probes"] = True
    if data_token is not None:
        sig["data_blake2b"] = hashlib.blake2b(
            repr(data_token).encode(), digest_size=12).hexdigest()
    return sig


def _next_limit(step: int, every: int, max_iter: int) -> int:
    """Next checkpoint boundary after ``step`` (multiples of ``every``,
    capped at ``max_iter``)."""
    return min(max_iter, (step // every + 1) * every)


def async_snapshot_enabled() -> bool:
    """``ALINK_TPU_ASYNC_SNAPSHOT`` (default on): persist boundary
    snapshots in a bounded background writer instead of blocking the
    chunk loop on the device->host fetch + file write. Off restores the
    strictly synchronous r02 behavior (identical on-disk artifacts)."""
    return env_flag("ALINK_TPU_ASYNC_SNAPSHOT", default=True)


def _device_copy(stacked) -> Dict[str, Any]:
    """Device-side copy of a stacked carry (sharding preserved). Taken at
    a boundary so the donated ``cont`` program is free to CONSUME the
    original while the background writer still holds live buffers to
    fetch. One HBM-to-HBM pass — orders of magnitude cheaper than the
    host fetch it decouples. Host leaves (a resumed numpy carry) copy on
    host."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda x: jnp.copy(x) if isinstance(x, jax.Array) else np.copy(x),
        dict(stacked))


def _to_host(stacked) -> Dict[str, Any]:
    """Fetch every carry leaf to host numpy in ONE batched transfer (the
    persistence payload) — the shared
    :func:`common.compat.device_get_tree` idiom. The ONLY persistence
    fetch: async writer and synchronous path both go through it, so the
    payload bytes cannot diverge between them."""
    from ..common.compat import device_get_tree
    return device_get_tree(dict(stacked))


class _SnapshotWriter:
    """Bounded background snapshot writer — ONE snapshot in flight.

    ``submit()`` hands over a device-side carry (a copy when donation is
    on) and returns once the PREVIOUS snapshot has committed (the bound:
    the driver can run at most one chunk ahead of durability). The worker
    thread fetches the carry to host (one batched ``jax.device_get``),
    persists it through ``save_checkpoint`` (same atomic-publish path as
    the synchronous writer — artifacts are bitwise identical), then fires
    ``on_snapshot``. Commits are strictly in submission order, so
    retention pruning, ``alink_checkpoint_last_tag`` and the health
    watchdog observe the same sequence the synchronous path produces.

    Any exception — an injected ``ckpt.save`` kill, a watchdog
    ``HealthAlertError``, a real IO error — is captured and re-raised ON
    THE MAIN THREAD (original object, type preserved) at the next
    ``submit()``/``check()``/``barrier()``, i.e. before the driver
    dispatches further work past the failed boundary."""

    def __init__(self, config: CheckpointConfig, signature: Dict[str, Any],
                 on_snapshot: Optional[Callable] = None):
        self._config = config
        self._signature = signature
        self._on_snapshot = on_snapshot
        self._q: "queue.Queue" = queue.Queue(maxsize=1)
        self._errs: list = []
        self._writes = 0
        self._th = threading.Thread(target=self._worker, daemon=True,
                                    name="alink-ckpt-writer")
        self._th.start()

    # -- worker thread ---------------------------------------------------
    def _worker(self):
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                carry, step, stopped = item
                with trace_span("snapshot.write", cat="ckpt") as sp:
                    host = _to_host(carry)
                    save_checkpoint(
                        self._config.directory, step, host,
                        meta={"signature": self._signature, "step": step,
                              "stopped": stopped},
                        scope=SCOPE, keep_last=self._config.keep_last)
                    sp.set(step=step, mode="async")
                self._writes += 1
                if metrics_enabled():
                    get_registry().inc("alink_overlap_snapshot_writes_total",
                                       1, {"scope": SCOPE})
                if self._on_snapshot is not None:
                    # the watchdog hook: may raise HealthAlertError — it
                    # lands in _errs and aborts the run at the next
                    # boundary, with THIS snapshot already on disk
                    self._on_snapshot(host, step)
            except BaseException as e:
                self._errs.append(e)
            finally:
                self._q.task_done()

    # -- driver-thread API -----------------------------------------------
    def check(self):
        """Re-raise the first captured writer exception (original object,
        so FaultInjected/HealthAlertError keep their types)."""
        if self._errs:
            raise self._errs[0]

    def submit(self, carry, step: int, stopped: bool):
        t0 = time.perf_counter()
        self._q.join()       # previous snapshot must commit first (bound)
        wait = time.perf_counter() - t0
        self.check()         # a failed previous write aborts HERE, before
        #                      this boundary's state is handed over
        if metrics_enabled():
            get_registry().observe("alink_overlap_submit_wait_seconds",
                                   wait, {"scope": SCOPE})
        trace_instant("snapshot.submit", cat="ckpt",
                      args={"step": step, "waited_s": round(wait, 6)})
        self._q.put((carry, step, stopped))

    def barrier(self):
        """Final durability barrier: every submitted snapshot is on disk
        (or its error raised) before the driver returns."""
        self._q.join()
        self.check()

    def shutdown(self):
        """Stop the worker without raising (the ``finally`` path). Any
        queued snapshot is still committed first — a run aborted by a
        superstep fault keeps the durability of its last boundary, same
        as the synchronous writer."""
        self._q.put(None)
        self._th.join(timeout=60.0)


def resume_state(config: CheckpointConfig,
                 signature: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Load the newest valid snapshot from ``config.resume_from`` and
    check it against ``signature``; returns the host carry (stacked
    layout) or None when there is nothing to resume from."""
    if not config.resume_from:
        return None
    got = load_latest_validated(config.resume_from, signature, scope=SCOPE,
                                what="program")
    return None if got is None else got[0]


def drive(config: CheckpointConfig, *,
          first: Callable, cont: Callable,
          parts: Dict[str, Any], bcast: Dict[str, Any],
          max_iter: int, signature: Dict[str, Any],
          resumed: Optional[Dict[str, Any]] = None,
          on_snapshot: Optional[Callable] = None,
          donate: bool = False,
          on_boundary: Optional[Callable] = None
          ) -> Tuple[Any, Dict[str, Any]]:
    """Run the chunked superstep loop with host-side persistence.

    ``first(parts, bcast, limit)`` runs the init pass + loop to ``limit``;
    ``cont(parts, bcast, carry, limit)`` continues a stacked carry.
    ``resumed`` is a host carry from :func:`resume_state` (skips
    ``first``). ``on_snapshot(host_carry, step)`` — if given — fires
    right after each snapshot publishes, with the host carry the save
    already fetched (the health monitor's mid-run hook; it may raise to
    abort the run, and because the snapshot is already on disk the
    aborted run stays resumable; with the async writer the abort
    surfaces on the main thread at the next boundary, at most one chunk
    later). ``donate=True`` declares that ``cont`` CONSUMES its carry
    argument (``ALINK_TPU_DONATE``), so the async writer is handed a
    device-side copy instead of the live carry. Returns
    ``(stacked_carry, info)`` where ``info`` carries the superstep
    accounting the metrics tail needs (``steps_executed``, ``init_ran``,
    ``resumed_at``).

    ``on_boundary(stacked, step)`` — if given — runs at every chunk
    boundary AFTER the snapshot published (and once right after a
    resume, BEFORE any new chunk dispatches) and may return a
    replacement stacked carry (``None`` = keep). This is the tuning
    sweep's ASHA pruning hook: it flips carry-resident alive lanes
    between chunks without touching program geometry. Because it runs
    after persistence but is re-applied on resume, a resumed run
    re-derives the same (deterministic) boundary decision the
    uninterrupted run made — kill-and-resume parity holds for the whole
    population. The hook may also rewrite ``__stop`` (the whole
    surviving population has converged); the driver re-reads it.

    With ``config.directory`` None nothing is persisted: the chunked
    loop runs purely for its boundaries (``IterativeComQueue.
    set_boundary`` — the sweep's rung cadence without durability).
    """
    import jax.numpy as jnp

    every = int(config.every)
    max_iter = int(max_iter)

    def boundary(stacked):
        # worker 0's copy — __step/__stop are replicated by construction.
        # ONE batched fetch: this sits inside the per-chunk critical path
        # (superstep.sync) — two np.asarray calls would block twice
        import jax
        step, stop = jax.device_get([stacked["__step"], stacked["__stop"]])
        return int(np.asarray(step)[0]), bool(np.asarray(stop)[0])

    def chunk(fn, args, from_step, limit):
        """One compiled-chunk pass: dispatch + the boundary sync that
        flushes it. The span tree (exec -> execute -> chunk ->
        superstep.sync) is what lets a trace answer 'which chunk of
        which exec was slow' — the aggregate metrics cannot."""
        with trace_span("comqueue.chunk", cat="engine") as sp:
            # measured-profiling window (ALINK_TPU_PROFILE): dispatch =
            # time the chunk call held the host thread; device = the
            # boundary sync that flushes it. Host wall clock only — the
            # chunk program is untouched.
            with profile_window("comqueue.chunk", capture=True) as pw:
                _pt0 = time.perf_counter()
                out = fn(*args, jnp.asarray(limit, jnp.int32))
                pw.dispatch(time.perf_counter() - _pt0)
                # the device work materializes at this host fetch — timed
                # as its own phase span so dispatch vs sync split is
                # visible
                with trace_span("superstep.sync", cat="engine"):
                    _pt1 = time.perf_counter()
                    step, stop = boundary(out)
                    pw.device(time.perf_counter() - _pt1)
            sp.set(from_step=from_step, limit=limit, step=step)
        # superstep-chunk boundary: the live-HBM accounting point (the
        # carry, any writer-held snapshot copy, and the inputs are all
        # resident here — the donation savings show up in this gauge)
        hbm_snapshot("comqueue.chunk")
        return out, step, stop

    writer = _SnapshotWriter(config, signature, on_snapshot) \
        if (async_snapshot_enabled() and config.directory) else None

    def persist(stacked, step, stopped):
        if not config.directory:
            return          # boundary-only mode: chunking without disk
        if writer is not None:
            # hand the writer buffers the next chunk cannot invalidate:
            # a device-side copy when the donated cont will consume the
            # carry; the live carry itself otherwise (a non-donated cont
            # only READS it, and a concurrent device_get is safe)
            writer.submit(_device_copy(stacked) if donate else stacked,
                          step, stopped)
            return
        host = _to_host(stacked)
        save_checkpoint(config.directory, step, host,
                        meta={"signature": signature, "step": step,
                              "stopped": stopped},
                        scope=SCOPE, keep_last=config.keep_last)
        if on_snapshot is not None:
            on_snapshot(host, step)

    info: Dict[str, Any] = {"init_ran": resumed is None, "resumed_at": None}
    try:
        if resumed is None:
            stacked, step, stop = chunk(first, (parts, bcast), 1,
                                        _next_limit(1, every, max_iter))
            start_step = 0
        else:
            stacked = resumed
            step, stop = boundary(stacked)
            start_step = step
            info["resumed_at"] = start_step
        last_saved = start_step if resumed is not None else None
        while True:
            # the injected-preemption point: BEFORE the snapshot publish,
            # so a killed run genuinely loses the work since the last
            # checkpoint and the resume has supersteps to re-execute
            maybe_crash(SITE, step)
            if step != last_saved:
                persist(stacked, step, stop or step >= max_iter)
                last_saved = step
            if on_boundary is not None and not stop and step < max_iter:
                # boundary transform (ASHA rung pruning): runs after the
                # snapshot published — the on-disk state is pre-decision,
                # and a resume re-derives the decision deterministically
                new = on_boundary(stacked, step)
                if new is not None:
                    stacked = new
                    step, stop = boundary(stacked)
            if stop or step >= max_iter:
                break
            # an exhausted boundary hook (the ASHA rung maker once the
            # population is down to its floor) has no further decisions:
            # with persistence OFF the rest of the run is ONE chunk —
            # boundaries are host syncs, pure overhead past that point.
            # With a checkpoint directory the snapshot cadence wins.
            if on_boundary is not None and not config.directory \
                    and getattr(on_boundary, "exhausted", False):
                limit = max_iter
            else:
                limit = _next_limit(step, every, max_iter)
            # snapshot t is now fetching/writing in the background; chunk
            # t+1 dispatches immediately — THE overlap this module buys
            stacked, step, stop = chunk(cont, (parts, bcast, stacked), step,
                                        limit)
        if writer is not None:
            # durability barrier: drive returns only once every boundary
            # is on disk (or its failure raised) — callers observe the
            # exact guarantees of the synchronous path
            writer.barrier()
    finally:
        if writer is not None:
            writer.shutdown()
    info["steps_executed"] = step - start_step
    return stacked, info


