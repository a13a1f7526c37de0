"""Bounded prefetch for stream drains — host/device pipelining.

The Flink reference runs every stream operator as its own pipelined task:
while FtrlTrainStreamOp's CalcTask crunches batch t, the upstream hash /
parse operators are already producing batch t+1
(FtrlTrainStreamOp.java:120-135). The round-2 runtime was a single lazy
generator chain, so host encode and device compute ran strictly
back-to-back (VERDICT r2 #4).

``prefetch(it, depth)`` runs the upstream iterator in ONE background
thread feeding a bounded channel: the main thread dispatches device steps
for item t while the thread parses/hashes/pads item t+1. FIFO order is
preserved exactly (test_stream.py proves no reordering), the bound gives
backpressure (the thread blocks when the consumer falls behind — Flink's
bounded exchange buffers), and upstream exceptions re-raise at the
consumption point. Per-sample order INSIDE a batch is untouched, so
strict-FTRL semantics are unchanged.

``prefetch_map(it, fn, workers=N)`` is the multi-worker upgrade: ``fn``
(the parse/hash/encode work) runs on an ORDERED pool of ``N`` named
threads (``alink-prefetch-<i>``) while the upstream iterator itself is
still drained serially — results are emitted in exact input order via a
reordering buffer, so callers observe the single-thread contract at
N-fold host parallelism. Exceptions (from ``fn`` or the upstream) are
delivered at the position where the failing item would have been
yielded, never earlier.

Backpressure is stop-aware: producers wait on a condition variable, not
a poll loop, so a consumer that abandons the stream (STOP sentinel
downstream, an exception) wakes every blocked producer immediately.

Env knobs:
  * ``ALINK_TPU_STREAM_PREFETCH`` — depth override; "0" disables
    (inline iteration), unset means depth 2.
  * ``ALINK_TPU_STREAM_WORKERS`` — pool width for :func:`prefetch_map`
    callers that pass ``workers=None``; unset/1 keeps the single-thread
    path.

Observability: the channel exports an ``alink_prefetch_depth`` gauge
(items currently buffered, labelled by consumer) so a stalled producer
(gauge pinned at 0) or a stalled consumer (pinned at the bound) is
visible in ``tools/run_report.py`` output. Three spans (``common/
tracing.py``; recorded under ``ALINK_TPU_TRACE`` or a profiler session)
say who waits for whom: ``prefetch.pull`` bounds the upstream's own time
for one item on the producer's thread; ``prefetch.put_wait`` is the
producer blocked on a full channel (the consumer is behind);
``prefetch.get_wait`` is the consumer blocked on an empty one (the
producer is behind). The two waits open only on the branch that really
blocks, so a put or get that finds room or an item records nothing.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Iterable, Iterator, Optional, TypeVar

from ...common.faults import maybe_crash
from ...common.tracing import trace_span

T = TypeVar("T")
U = TypeVar("U")

_SENTINEL = object()
# timed-get miss marker (serving micro-batcher): distinct from the
# end-of-stream sentinel so "nothing arrived within the latency budget"
# and "the stream is over" stay distinguishable
_EMPTY = object()


def _pulled(it: Iterable[T]) -> Iterator[T]:
    """``it``, each ``next()`` of it inside a ``prefetch.pull`` span: the
    source's own time for one item. The span closes before the item is
    handed on, so it never stays open across the ``yield``."""
    it = iter(it)
    while True:
        with trace_span("prefetch.pull", cat="stream"):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


def prefetch_depth(default: int = 2) -> int:
    """``ALINK_TPU_STREAM_PREFETCH`` via the flag registry
    (common/flags.py): set-but-empty counts as unset, values clamp to
    >= 0 — the historical semantics, one parser."""
    from ...common.flags import flag_value
    return flag_value("ALINK_TPU_STREAM_PREFETCH", default)


def stream_workers(default: int = 1) -> int:
    """``ALINK_TPU_STREAM_WORKERS``: width of the :func:`prefetch_map`
    encode pool (registry-declared; clamps to >= 1). 1 (the default)
    is the exact single-thread behavior."""
    from ...common.flags import flag_value
    return flag_value("ALINK_TPU_STREAM_WORKERS", default)


class _Channel:
    """Bounded FIFO channel with stop-aware blocking.

    ``put`` blocks while the channel is full — but wakes IMMEDIATELY when
    the consumer abandons the stream (``stop()``), instead of the old
    0.1 s ``queue.Full`` poll loop. ``get`` blocks until an item or the
    sentinel arrives. One lock + two conditions; unbounded when
    ``maxsize <= 0``."""

    def __init__(self, maxsize: int, gauge_label: Optional[str] = None):
        self._buf: deque = deque()
        self._maxsize = maxsize
        self._lock = threading.Lock()
        self._not_full = threading.Condition(self._lock)
        self._not_empty = threading.Condition(self._lock)
        self._stopped = False
        self._closed = False
        self._gauge_label = gauge_label

    def _gauge(self, depth: int) -> None:
        if self._gauge_label is None:
            return
        from ...common.metrics import get_registry, metrics_enabled
        if metrics_enabled():
            get_registry().set_gauge("alink_prefetch_depth", depth,
                                     {"consumer": self._gauge_label})

    def _full(self) -> bool:
        """A put has to wait (lock held): bounded, at the bound, and
        nobody has stopped or closed the channel."""
        return (not self._stopped and not self._closed
                and 0 < self._maxsize <= len(self._buf))

    def put(self, item) -> bool:
        """Enqueue; False when the consumer has stopped OR the channel
        is already closed (a producer racing ``close()`` must not
        strand an item no getter will ever see — the serving tier's
        submit-vs-shutdown race)."""
        with self._not_full:
            if self._full():
                with trace_span("prefetch.put_wait", cat="stream"):
                    while self._full():
                        self._not_full.wait()
            if self._stopped or self._closed:
                return False
            self._buf.append(item)
            self._gauge(len(self._buf))
            self._not_empty.notify()
            return True

    def get(self, timeout: Optional[float] = None):
        """Dequeue one item; blocks until an item, stop/close
        (``_SENTINEL``) or — when ``timeout`` is given — the deadline
        (``_EMPTY``). ``timeout=None`` is the historical behavior;
        ``timeout=0`` polls without blocking (the micro-batcher's
        "queue already holds a full batch" fast path)."""
        # deterministic fault site (common/faults.py): every consumer —
        # stream drains AND the serving micro-batcher — pulls through
        # here, so an error-mode fault is a consumer-loop crash (the
        # serving supervisor's respawn path) and delay:MS injects
        # upstream latency. Unarmed cost: one os.environ probe
        maybe_crash("prefetch.get")
        deadline = None if timeout is None \
            else time.monotonic() + max(0.0, timeout)
        with self._not_empty:
            if not self._buf:
                ended = self._await_item(deadline)
                if ended is not None:
                    return ended
            item = self._buf.popleft()
            self._gauge(len(self._buf))
            self._not_full.notify()
            return item

    def _await_item(self, deadline: Optional[float]):
        """Wait (lock held, buffer empty) until the buffer holds an item:
        ``None`` then, else the marker ``get`` returns. The
        ``prefetch.get_wait`` span opens only where the wait is real, so
        a poll (``timeout=0``) and a get on an ended channel record
        nothing."""
        if self._stopped or self._closed:
            return _SENTINEL
        if deadline is not None and deadline <= time.monotonic():
            return _EMPTY
        with trace_span("prefetch.get_wait", cat="stream"):
            while not self._buf:
                if self._stopped or self._closed:
                    return _SENTINEL
                if deadline is None:
                    self._not_empty.wait()
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return _EMPTY
                self._not_empty.wait(remaining)
        return None

    def depth(self) -> int:
        """Items currently buffered (the admission-control reading the
        serving tier exports as ``alink_serve_queue_depth``)."""
        with self._lock:
            return len(self._buf)

    def drain(self, max_items: int) -> list:
        """Pop up to ``max_items`` buffered items under ONE lock
        acquisition (never blocks; [] when empty). The serving
        micro-batcher's bulk path — a per-item ``get`` would pay a
        lock round trip per coalesced request."""
        with self._lock:
            k = min(int(max_items), len(self._buf))
            if k <= 0:
                return []
            items = [self._buf.popleft() for _ in range(k)]
            self._gauge(len(self._buf))
            self._not_full.notify_all()
            return items

    def close(self) -> None:
        """Producer end-of-stream: buffered items still DRAIN to getters;
        once empty, every get() returns the sentinel (non-consuming, so
        any number of pool workers observe it)."""
        with self._lock:
            self._closed = True
            self._not_empty.notify_all()
            self._not_full.notify_all()   # blocked producers must re-check

    def stop(self) -> None:
        """Consumer abandonment: wake every blocked producer AND consumer
        at once (no poll latency), discard buffered items."""
        with self._lock:
            self._stopped = True
            self._buf.clear()
            self._gauge(0)
            self._not_full.notify_all()
            self._not_empty.notify_all()


def _close_upstream(it, err: list) -> None:
    """Close the upstream generator on EVERY producer exit path (normal
    end, upstream error, consumer abandonment) so a failing
    flush-on-close still reaches the consumer instead of dying on the
    daemon thread."""
    try:
        close = getattr(it, "close", None)
        if close is not None:
            close()
    except BaseException as e:
        err.append(e)


def _warn_stuck(threads, timeout: float = 5.0) -> None:
    """Join ``threads`` against ONE shared deadline (not 5 s each — a
    blocked 8-wide pool would otherwise stall an abandoning consumer
    ~45 s). A thread still alive past the deadline is stuck inside the
    upstream iterator / fn itself (e.g. a blocking poll) — it cannot see
    the stop flag until that call returns, so the daemon thread outlives
    us still holding the iterator. Make that diagnosable, not silent."""
    deadline = time.monotonic() + timeout
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.monotonic()))
    stuck = [th.name for th in threads if th.is_alive()]
    if stuck:
        import logging
        logging.getLogger(__name__).warning(
            "prefetch worker(s) %s did not exit within %.0fs of consumer "
            "abandonment; the upstream source appears blocked",
            ", ".join(stuck), timeout)


def prefetch(it: Iterable[T], depth: int = None,
             name: str = None) -> Iterator[T]:
    """Iterate ``it`` in a background thread, ``depth`` items ahead.

    ``name`` labels this channel's ``alink_prefetch_depth`` gauge
    (``consumer=<name>``); pass the consuming op's name so concurrent
    streams do not overwrite each other's depth reading."""
    depth = prefetch_depth() if depth is None else depth
    if depth <= 0:
        yield from it
        return
    ch = _Channel(depth, gauge_label=name or "prefetch")
    err: list = []

    def worker():
        try:
            for item in it:
                if not ch.put((item,)):
                    break
        except BaseException as e:  # propagate to the consumer
            err.append(e)
        finally:
            _close_upstream(it, err)
            ch.put(_SENTINEL)

    th = threading.Thread(target=worker, daemon=True,
                          name="alink-prefetch-0")
    th.start()
    try:
        while True:
            item = ch.get()
            if item is _SENTINEL:
                if err:
                    raise err[0]
                return
            yield item[0]
    finally:
        # consumer abandoned early (STOP sentinel downstream, exception):
        # stop() wakes an in-flight put immediately — no drain loop needed
        ch.stop()
        _warn_stuck([th])


def prefetch_map(it: Iterable[T], fn: Callable[[T], U],
                 workers: int = None, depth: int = None,
                 name: str = None) -> Iterator[U]:
    """Ordered parallel map: ``fn(item)`` for every item of ``it``, on a
    pool of ``workers`` threads, yielding results in EXACT input order.

    The upstream iterator is drained serially by a dispatcher thread
    (generators are inherently sequential); the per-item work in ``fn``
    — parse/hash/encode/device_put for the stream runtime — is what
    parallelizes. A reordering buffer holds at most
    ``workers + depth`` completed results, so memory stays bounded by
    the same backpressure contract as :func:`prefetch`.

    ``workers=None`` reads ``ALINK_TPU_STREAM_WORKERS`` (default 1);
    ``workers=1`` degrades to :func:`prefetch` over a lazy ``map`` —
    byte-for-byte the single-thread behavior. An exception raised by
    ``fn(item_k)`` (or by the upstream while producing item k) re-raises
    at the consumer exactly where item k would have been yielded; items
    ``< k`` are still delivered first."""
    workers = stream_workers() if workers is None else max(1, int(workers))
    depth = prefetch_depth() if depth is None else depth
    if workers <= 1:
        # a real generator, not map(): closing it must deterministically
        # close the UPSTREAM too (map objects have no close(), which
        # would silently defeat _close_upstream's flush-on-close
        # propagation — the contract the single-thread path always had)
        def _mapped():
            try:
                for item in _pulled(it):
                    yield fn(item)
            finally:
                close = getattr(it, "close", None)
                if close is not None:
                    close()
        yield from prefetch(_mapped(), depth=depth, name=name)
        return

    in_ch = _Channel(max(depth, 1),
                     gauge_label=(name or "prefetch_map") + ".in")
    lock = threading.Lock()
    done = threading.Condition(lock)
    results: dict = {}          # seq -> ("ok", value) | ("err", exc)
    state = {"stop": False, "total": None}  # total set once upstream ends

    def dispatcher():
        seq = 0
        try:
            for item in _pulled(it):
                if not in_ch.put((seq, item)):
                    return
                seq += 1
        except BaseException as e:
            # the upstream failed while producing item `seq`: deliver the
            # error at that position, after every earlier item
            with done:
                results[seq] = ("err", e)
                seq += 1
                done.notify_all()
        finally:
            err2: list = []
            _close_upstream(it, err2)
            with done:
                if err2 and seq not in results:
                    results[seq] = ("err", err2[0])
                    seq += 1
                state["total"] = seq
                done.notify_all()
            # close, not stop: queued items must still reach the workers
            in_ch.close()

    bound = workers + max(depth, 1)

    def worker():
        while True:
            with done:
                # admission control, not storage control: a worker only
                # PULLS new work while the reorder buffer has room, but
                # always stores what it finished — gating the store
                # would deadlock when the buffer fills with seqs ahead
                # of the one the consumer is waiting for
                while not state["stop"] and len(results) >= bound:
                    done.wait()
                if state["stop"]:
                    return
            got = in_ch.get()
            if got is _SENTINEL:
                return
            seq, item = got
            try:
                out = ("ok", fn(item))
            except BaseException as e:
                out = ("err", e)
            with done:
                if state["stop"]:
                    return
                results[seq] = out
                done.notify_all()

    threads = [threading.Thread(target=dispatcher, daemon=True,
                                name="alink-prefetch-dispatch")]
    threads += [threading.Thread(target=worker, daemon=True,
                                 name=f"alink-prefetch-{i}")
                for i in range(workers)]
    for th in threads:
        th.start()
    next_seq = 0

    def _ended() -> bool:
        """Upstream is over and ``next_seq`` lies past its last item
        (``done`` held)."""
        return state["total"] is not None and next_seq >= state["total"]

    try:
        while True:
            with done:
                if next_seq not in results and not _ended():
                    # the consumer starves: no worker has item next_seq yet
                    with trace_span("prefetch.get_wait", cat="stream"):
                        while next_seq not in results and not _ended():
                            done.wait()
                if next_seq not in results:
                    return
                kind, val = results.pop(next_seq)
                done.notify_all()     # admission-gated workers wake here
            if kind == "err":
                raise val
            yield val
            next_seq += 1
    finally:
        with done:
            state["stop"] = True
            results.clear()
            done.notify_all()
        in_ch.stop()
        _warn_stuck(threads)
