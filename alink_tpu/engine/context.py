"""ComContext — the per-worker state handle inside a superstep.

Re-design of the reference ``ComContext`` (common/comqueue/ComContext.java:52-65):
there, ``getObj/putObj`` hit a static per-TaskManager heap map keyed by
(handle, taskId). Here the backing store is an explicit functional **carry
pytree** traced through ``lax.while_loop`` (SURVEY §7 "hard parts": every
putObj key becomes a carry entry), plus a read-only dict of device-resident
partitioned/broadcast data (the ``SessionSharedObjs`` cache analogue,
comqueue/SessionSharedObjs.java:157-178).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


class ComContext:
    AXIS = "d"

    # carry-key prefix of the probe channel (engine + result accessors)
    PROBE_PREFIX = "__probe_"
    # probe series dtype: probes are monitoring scalars, not model state —
    # a fixed narrow dtype keeps the stacked carry small and the series
    # layout independent of the trainer's compute dtype
    PROBE_DTYPE = jnp.float32

    def __init__(self, carry: Dict[str, Any], static: Dict[str, Any],
                 num_workers: int, init_pass: bool,
                 max_iter: int = 0, probes_on: bool = False):
        self._carry = dict(carry)
        self._static = static
        self._num_workers = num_workers
        self._init_pass = init_pass
        self._max_iter = int(max_iter)
        self._probes_on = bool(probes_on) and self._max_iter > 0

    # -- identity --------------------------------------------------------
    @property
    def task_id(self):
        """Worker index along the data mesh axis (Flink getTaskId analogue)."""
        return jax.lax.axis_index(self.AXIS)

    @property
    def num_task(self) -> int:
        return self._num_workers

    @property
    def step_no(self):
        """1-based superstep number (reference ComContext.getStepNo)."""
        return self._carry["__step"]

    @property
    def is_init_step(self) -> bool:
        """True only during the (un-traced-step) first superstep pass.

        Replaces the reference's ``if (context.getStepNo() == 1)`` allocation
        idiom: allocation must happen where the carry structure is being
        built, i.e. the init pass.
        """
        return self._init_pass

    # -- state -----------------------------------------------------------
    def get_obj(self, name: str):
        if name in self._carry:
            return self._carry[name]
        if name in self._static:
            return self._static[name]
        raise KeyError(f"ComContext: no object '{name}' "
                       f"(carry keys: {sorted(self._carry)}, "
                       f"static keys: {sorted(self._static)})")

    def put_obj(self, name: str, value):
        if name in self._static:
            raise ValueError(f"'{name}' is immutable partitioned/broadcast data")
        self._carry[name] = value

    def contains_obj(self, name: str) -> bool:
        return name in self._carry or name in self._static

    def remove_obj(self, name: str):
        self._carry.pop(name, None)

    # -- health probes (common/health.py) --------------------------------
    @property
    def probes_enabled(self) -> bool:
        """Trace-time truth of the ``ALINK_TPU_HEALTH`` switch. A stage
        may branch on it to skip probe-only arithmetic (the engine folds
        the flag into the program-cache key, so the two variants never
        share a compiled program)."""
        return self._probes_on

    def probe(self, name: str, value) -> None:
        """Publish one named per-superstep health scalar from inside the
        compiled program. The series rides the while-loop carry as a
        stacked ``(max_iter,)`` float32 array prefilled with NaN and
        written at index ``step_no - 1`` — zero host callbacks, no new
        collectives, fetched with the rest of the carry (checkpoint
        snapshots include it, so a resumed run's history stitches).

        No-op when ``ALINK_TPU_HEALTH`` is off — the lowered program is
        then byte-identical to one with no probe calls at all. Call it
        unconditionally from stages; never gate it on your own env read
        (the engine's cache key covers this switch, not yours)."""
        if not self._probes_on:
            return
        key = self.PROBE_PREFIX + name
        v = jnp.asarray(value).astype(self.PROBE_DTYPE).reshape(())
        if key not in self._carry:
            if not self._init_pass:
                raise KeyError(
                    f"probe '{name}' first recorded after the init pass — "
                    f"the carry structure is frozen after superstep 1, so "
                    f"every probe must also be recorded (even with a "
                    f"placeholder value) while ctx.is_init_step is True")
            series = jnp.full((self._max_iter,), jnp.nan, self.PROBE_DTYPE)
        else:
            series = self._carry[key]
        self._carry[key] = jax.lax.dynamic_update_index_in_dim(
            series, v, self.step_no - 1, 0)

    def probe_nonfinite(self, name: str, value) -> None:
        """Probe the count of non-finite elements in a value pytree as
        series ``nonfinite.<name>`` — the NonFiniteRule watchdog input.
        Costs one ``isfinite`` + reduce per leaf inside the program."""
        if not self._probes_on:
            return
        leaves = jax.tree_util.tree_leaves(value)
        cnt = sum((jnp.size(x) - jnp.isfinite(x).sum())
                  if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)
                  else jnp.asarray(0, jnp.int32)
                  for x in leaves)
        self.probe("nonfinite." + name, cnt)

    # -- communication ---------------------------------------------------
    def all_reduce_sum(self, value):
        """Inline psum of a value pytree (communication/AllReduce.java:85-120
        for the common in-stage case; the stage-based ``AllReduce`` class
        remains for queue-structured use)."""
        # late import: communication imports this module at load time
        from .communication import payload_nbytes, record_collective
        record_collective("InlineAllReduce", "<inline>",
                          payload_nbytes(value), self._num_workers)
        return jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, self.AXIS), value)

    # -- randomness ------------------------------------------------------
    def rng_key(self):
        """Per-worker, per-step PRNG key (mini-batch SGD sampling etc.)."""
        key = self._carry["__key"]
        return jax.random.fold_in(jax.random.fold_in(key, self.step_no), self.task_id)

    @property
    def carry(self) -> Dict[str, Any]:
        return self._carry
