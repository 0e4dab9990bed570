"""Parallel input pipeline: ordered collation worker pool, epoch-level
collation cache, and per-stage instrumentation.

The training loop's host-side data path is read (corpus + batcher) →
tokenize/hash/collate (the expensive part: target construction + feature
hashing into padded arrays) → transfer (``device_put``). On CPU the device
step is slow enough to hide all of it behind ``prefetch_iter``'s single
producer thread; a real TPU step is orders of magnitude faster, so the
single-threaded producer becomes the ceiling (PERF.md round-2: compiled
cnn_tagger 5.57M w/s vs 122K e2e — a 45× input-pipeline gap).

Three pieces, composable and individually inert when disabled:

* :class:`OrderedPool` — fans a pure ``fn(item)`` out over N worker
  threads while yielding results in exact submission order. The pool runs
  ONLY the collation stage: reading the source iterator stays on one
  feeder thread (corpus/batcher state is single-threaded), and the
  consumer of the pool performs ``device_put`` + any multi-host
  collectives on its own single thread — the ordering constraint
  documented in ``prefetch.py`` is preserved by construction.
* :class:`CollateCache` — steady-state epochs re-tokenize, re-hash and
  re-collate the exact same cached Example objects into the exact same
  bucket shapes. Cache the collated HOST arrays keyed by batch identity
  and ``(B_pad, T_pad)``, under a byte budget with LRU eviction. The
  training loop bypasses the cache automatically when augmentation is
  active (fresh Example copies every epoch would only churn it) and in
  annotating mode (targets depend on per-step predictions).
* :class:`PipelineStats` — thread-safe per-stage timers (read /
  collate / transfer / queue-wait, the stages inside a collate call, and
  the loop's own host time: the keys are in ``spacy_ray_tpu/names.py``)
  + cache counters, surfaced in the training log at every eval row. Its
  ``timer`` is the one span call of the training path: stage seconds, a
  Chrome-trace span when telemetry is on, and a profiler annotation.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from ..names import COLLATE, QUEUE_WAIT, READ, SPAN_PREFIX, TRANSFER
from .resilience import maybe_fail

__all__ = [
    "OrderedPool",
    "CollateCache",
    "PipelineStats",
    "NO_SPAN",
    "ordered_map",
    "cached_collate",
]


# ----------------------------------------------------------------------
# Per-stage instrumentation
# ----------------------------------------------------------------------

# the stages every snapshot carries, run or not; the timers add their own
# keys (names.py) as they first fire
STAGES = (READ, COLLATE, TRANSFER, QUEUE_WAIT)


class _NoSpan:
    """The span of a caller that records nothing (serving, ``evaluate``,
    tests): entering it, leaving it and asking it for a child cost one
    method call each and touch no clock."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None

    def child(self, name: str) -> "_NoSpan":
        return self


NO_SPAN = _NoSpan()


class PipelineStats:
    """Thread-safe accumulator for input-pipeline stage timings.

    ``collate`` seconds accumulate across worker threads, so with N busy
    workers the collate total can exceed wall time — that is the point:
    stage seconds measure WORK, the words/s rate measures the pipeline.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.seconds: Dict[str, float] = {s: 0.0 for s in STAGES}
        self.counts: Dict[str, int] = {s: 0 for s in STAGES}
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_enabled = False
        self.workers = 1
        # optional span emitter (training/telemetry.py TraceBuffer): when
        # attached, every stage timing that carries its start stamp also
        # lands as a Chrome-trace span. One emitter serves the pooled AND
        # the inline path identically — a collate_workers = 0 run traces
        # the same read/collate/transfer stages as a pooled one, just on
        # one track (the satellite fix: single-threaded runs must be
        # comparable in traces).
        self._trace: Optional[Any] = None
        # per thread: how many timers are open on it, and the closed ones
        # that wait for the outermost to close
        self._open = threading.local()

    def attach_trace(self, trace: Any) -> None:
        self._trace = trace

    def add(
        self, stage: str, seconds: float, n: int = 1, t0: Optional[float] = None
    ) -> None:
        self._add_all([(stage, seconds, t0)], n)

    def _add_all(
        self, spans: List[Tuple[str, float, Optional[float]]], n: int = 1
    ) -> None:
        with self._lock:
            for stage, seconds, _ in spans:
                self.seconds[stage] = self.seconds.get(stage, 0.0) + seconds
                self.counts[stage] = self.counts.get(stage, 0) + n
        trace = self._trace
        if trace is not None:
            for stage, seconds, t0 in spans:
                if t0 is not None:
                    trace.add_span(stage, t0, seconds, cat="pipeline")

    class _Timer:
        """One span, three sinks: the stage's seconds and count (always),
        the attached ``TraceBuffer`` (telemetry on), and a
        ``TraceAnnotation`` — a flag check unless a profiler trace is
        running (the benchmark's, ``train --profile``'s, an operator's),
        in which case the span is a host event of that trace, on its clock
        and on the thread that did the work.

        The seconds of a span that closes inside another span of its thread
        are added when the outermost one closes, all under one lock: whoever
        reads ``seconds`` between two instants (the benchmark's window
        edges) finds a part only together with its whole."""

        __slots__ = ("_stats", "_stage", "_t0", "_note")

        def __init__(self, stats: "PipelineStats", stage: str):
            self._stats = stats
            self._stage = stage

        def __enter__(self) -> "PipelineStats._Timer":
            mine = self._stats._open
            if not getattr(mine, "depth", 0):  # the outermost of its thread
                mine.depth, mine.closed = 0, []
            mine.depth += 1
            self._note = TraceAnnotation(SPAN_PREFIX + self._stage)
            self._note.__enter__()
            self._t0 = time.perf_counter()
            return self

        def __exit__(self, *exc: Any) -> None:
            seconds = time.perf_counter() - self._t0
            self._note.__exit__(*exc)
            mine = self._stats._open
            mine.depth -= 1
            mine.closed.append((self._stage, seconds, self._t0))
            if mine.depth == 0:
                self._stats._add_all(mine.closed)

        def child(self, name: str) -> "PipelineStats._Timer":
            """The timer of ``<this stage>/<name>``: a callee handed this
            span times a part of itself without knowing its own key."""
            return PipelineStats._Timer(self._stats, f"{self._stage}/{name}")

    def timer(self, stage: str) -> "PipelineStats._Timer":
        return PipelineStats._Timer(self, stage)

    def hit(self) -> None:
        with self._lock:
            self.cache_hits += 1

    def miss(self) -> None:
        with self._lock:
            self.cache_misses += 1

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "stage_seconds": {
                    s: round(v, 4) for s, v in self.seconds.items()
                },
                "stage_counts": dict(self.counts),
                "cache": {
                    "enabled": self.cache_enabled,
                    "hits": self.cache_hits,
                    "misses": self.cache_misses,
                },
                "workers": self.workers,
            }


# ----------------------------------------------------------------------
# Epoch-level collation cache
# ----------------------------------------------------------------------


def _entry_nbytes(value: Any) -> int:
    """Total nbytes of every array reachable in a collated batch dict."""
    total = 0
    seen: set = set()

    def walk(node: Any) -> None:
        nonlocal total
        if isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif hasattr(node, "_fields") or isinstance(node, (list, tuple)):
            for v in node:  # NamedTuple (TokenBatch) or plain sequence
                walk(v)
        elif hasattr(node, "nbytes"):
            if id(node) not in seen:
                seen.add(id(node))
                total += int(node.nbytes)

    walk(value)
    return total


class CollateCache:
    """Byte-capped LRU cache of collated host batches.

    Keyed by the IDENTITY of the Example objects in the batch plus the
    padded bucket shape — the corpus's default ``cache = true`` re-yields
    the same Example objects every epoch, so identical batches recur with
    identical keys. Each entry pins a strong reference to its Example
    list, which both keeps ``id()`` values stable for the key's lifetime
    and lets hits verify identity (no hash collisions possible). Batches
    that never recur (augmentation, streaming corpora) simply churn
    through LRU eviction — which is why callers BYPASS the cache when
    they know recurrence is impossible.

    Thread-safe: collation workers race on get/put.
    """

    def __init__(self, max_bytes: int):
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Tuple, Tuple[List[Any], Any, int]]" = (
            OrderedDict()
        )
        self._nbytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def _key(self, examples: List[Any], B: int, T: int) -> Tuple:
        return (tuple(id(eg) for eg in examples), int(B), int(T))

    def get(self, examples: List[Any], B: int, T: int) -> Optional[Any]:
        key = self._key(examples, B, T)
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
                return None
            held, value, _ = entry
            # identity re-check: id() keys are only valid while the entry
            # holds its examples alive — verify rather than trust
            if len(held) != len(examples) or any(
                a is not b for a, b in zip(held, examples)
            ):
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return value

    def put(self, examples: List[Any], B: int, T: int, value: Any) -> None:
        nbytes = _entry_nbytes(value)
        if nbytes > self.max_bytes:
            return  # one oversized batch must not flush the whole cache
        key = self._key(examples, B, T)
        with self._lock:
            if key in self._entries:
                return
            self._entries[key] = (list(examples), value, nbytes)
            self._nbytes += nbytes
            while self._nbytes > self.max_bytes and len(self._entries) > 1:
                _, (_, _, evicted_bytes) = self._entries.popitem(last=False)
                self._nbytes -= evicted_bytes
                self.evictions += 1

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def nbytes(self) -> int:
        with self._lock:
            return self._nbytes


def cached_collate(
    cache: Optional[CollateCache],
    examples: List[Any],
    B: int,
    T: int,
    collate: Callable[[List[Any], int, int], Any],
    stats: Optional[PipelineStats] = None,
) -> Any:
    """The one get-else-collate-and-put sequence of the training loop's
    collate stage. ``cache=None`` degrades to a plain
    ``collate`` call; stats (when given) count hits/misses only while a
    cache is active.

    Also the ``collate`` fault-injection site (training/resilience.py):
    living here, an injected collation failure exercises the loop's own
    path, pool-worker → consumer re-raise included."""
    maybe_fail("collate")
    value = cache.get(examples, B, T) if cache is not None else None
    if value is None:
        value = collate(examples, B, T)
        if cache is not None:
            cache.put(examples, B, T, value)
            if stats is not None:
                stats.miss()
    elif stats is not None:
        stats.hit()
    return value


# ----------------------------------------------------------------------
# Ordered worker pool
# ----------------------------------------------------------------------

_DONE = object()


class _RaisedItem:
    __slots__ = ("err",)

    def __init__(self, err: BaseException):
        self.err = err


class OrderedPool:
    """Run ``fn(item)`` over a worker pool, yielding results in exact
    source order.

    A single feeder thread drains the source iterator (corpus/batcher
    state stays single-threaded) and submits work to N workers; the
    consumer pops futures in submission order, so a slow item blocks
    later (already finished) items from being YIELDED but never from
    being COMPUTED — up to ``prefetch`` items run ahead. Exceptions from
    the source or from ``fn`` re-raise at the consumer in order position.

    ``fn`` must be pure host work: the whole point of the pool contract
    is that ``device_put`` and any collectives stay on the consumer's
    single thread (see training/prefetch.py).

    ``close()`` (idempotent; also triggered by ``__del__``) stops the
    feeder, cancels queued work, and drops buffered results.
    """

    def __init__(
        self,
        it: Iterator[Any],
        fn: Callable[[Any], Any],
        workers: int,
        prefetch: Optional[int] = None,
    ):
        from concurrent.futures import ThreadPoolExecutor

        self._fn = fn
        self._it = it
        self._stopped = threading.Event()
        workers = max(int(workers), 1)
        # enough in-flight items to keep every worker busy plus a ready
        # buffer; bounded so a fast feeder can't collate the whole epoch
        self._q: "queue.Queue" = queue.Queue(
            maxsize=int(prefetch) if prefetch else workers * 2
        )
        self._executor = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="collate-pool"
        )
        self._feeder = threading.Thread(
            target=self._feed, daemon=True, name="collate-pool-feeder"
        )
        self._feeder.start()

    def _call(self, item: Any) -> Any:
        if self._stopped.is_set():
            return _DONE  # cancelled after close: skip the work
        return self._fn(item)

    def _put(self, obj: Any) -> bool:
        while not self._stopped.is_set():
            try:
                self._q.put(obj, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _feed(self) -> None:
        try:
            for item in self._it:
                if self._stopped.is_set():
                    return
                future = self._executor.submit(self._call, item)
                if not self._put(future):
                    future.cancel()
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised at consumer
            self._put(_RaisedItem(e))
            return
        self._put(_DONE)

    def __iter__(self) -> "OrderedPool":
        return self

    def __next__(self) -> Any:
        if self._stopped.is_set():
            raise StopIteration
        obj = self._q.get()
        if obj is _DONE:
            self.close()
            raise StopIteration
        if isinstance(obj, _RaisedItem):
            self.close()
            raise obj.err
        try:
            result = obj.result()
        except BaseException:
            self.close()
            raise
        if result is _DONE:  # worker saw the stop flag mid-close
            raise StopIteration
        return result

    def close(self) -> None:
        """Stop feeder + workers and drop buffered results. Join the
        feeder BEFORE draining so a mid-put future can't slip into the
        just-drained queue; then close the source iterator (its finally
        blocks may hold resources — e.g. a nested pool)."""
        if self._stopped.is_set():
            return
        self._stopped.set()
        self._feeder.join(timeout=5.0)
        try:
            while True:
                obj = self._q.get_nowait()
                if hasattr(obj, "cancel"):
                    obj.cancel()
        except queue.Empty:
            pass
        self._executor.shutdown(wait=False, cancel_futures=True)
        if not self._feeder.is_alive():
            close = getattr(self._it, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    pass

    def __del__(self):
        self.close()


def ordered_map(
    it: Iterator[Any],
    fn: Callable[[Any], Any],
    workers: int = 1,
    prefetch: Optional[int] = None,
) -> Iterator[Any]:
    """``map(fn, it)`` with ``workers >= 2`` fanned out over an
    :class:`OrderedPool`; below that, a plain inline generator (zero
    threads, zero overhead) — so callers can wire one code path and let
    the ``collate_workers`` knob decide."""
    if workers >= 2:
        return OrderedPool(it, fn, workers, prefetch)

    def inline() -> Iterator[Any]:
        try:
            for item in it:
                yield fn(item)
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()

    return inline()
