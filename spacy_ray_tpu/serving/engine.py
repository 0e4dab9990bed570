"""Online inference engine: device-resident params, a compiled-program
warmup sweep over the (B, T) padding buckets, and ONE dispatch thread
executing coalesced batches through the ``predict_docs`` path.

Why one thread: under jit every distinct (B, T) is one cached XLA
program; a single dispatcher serializes device access (no interpreter-
level contention on the params or the jit cache) while the
ThreadingHTTPServer handler threads do the embarrassingly parallel host
work (tokenization, JSON). That is the same host/device split the
training loop uses (collation pool feeds one device thread,
training/collate_pool.py) — serving reuses the split rather than
inventing a second concurrency model.

Warmup (:func:`warmup_buckets`) compiles the forward program for every
bucket shape the admission rules can produce, so steady-state serving
never pays a compile on a live request — the same reasoning as the
trainer's shape bucketing (SURVEY.md §7), and the bucket tables are the
trainer's own (``training/batcher.py``).

Telemetry is a nullable :class:`ServingTelemetry` facade over
``training/telemetry.py``'s registry + trace buffer: request-latency
histograms (p50/p95/p99), queue-depth and batch-occupancy gauges,
reject/timeout counters, per-request and per-batch trace spans. When
disabled the engine holds None and makes ZERO telemetry calls — the
contract the training loop enforces, test-enforced here too.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..training.batcher import (
    DEFAULT_LENGTH_BUCKETS,
    bucket_batch_size,
    bucket_length,
)
from ..training.resilience import log_event
from .batcher import (
    DeadlineExceeded,
    DynamicBatcher,
    Draining,
    RequestTooLarge,
    ServeRequest,
    ServingError,
    SwapFailed,
)

__all__ = [
    "ServingTelemetry",
    "InferenceEngine",
    "warmup_buckets",
    "SERVING_DEFAULTS",
]

# One place for the serving knob defaults: the CLI and the tests read
# these, so neither restates numbers that can drift. ``batching``
# defaults to continuous
# admission (the window discipline survives behind the knob for A/Bs and
# for operators who want to trade latency for bigger batches);
# ``max_wait_s`` only applies in window mode. ``precision`` is the
# serving overlay policy (serving/overlay.py — "auto" arms bf16 on
# accelerators only). ``slo_window_s`` is the sliding window the SLO
# percentiles are additionally reported over (recent load, not lifetime).
SERVING_DEFAULTS: Dict[str, Any] = {
    "max_batch_docs": 16,
    "max_wait_s": 0.005,
    "max_queue_docs": 128,
    "timeout_s": 10.0,
    "max_doc_len": 64,
    "batching": "continuous",
    "precision": "auto",
    "slo_window_s": 30.0,
}


def warmup_buckets(
    max_batch_docs: int,
    max_doc_len: int,
    length_buckets: Sequence[int] = DEFAULT_LENGTH_BUCKETS,
) -> List[Tuple[int, int]]:
    """The (B, T) grid admission can produce: batch buckets from the
    trainer's ``bucket_batch_size`` chain up to the padded max batch,
    and EVERY length bucket ``bucket_length`` can emit for a doc of
    1..max_doc_len tokens — table buckets up to the cap plus, beyond the
    table's top, each multiple of the top bucket (that is
    ``bucket_length``'s overflow rule). Completeness is the contract: a
    live request must never meet a shape this sweep did not compile."""
    b_cap = bucket_batch_size(int(max_batch_docs))
    t_cap = bucket_length(int(max_doc_len), length_buckets)
    bs: List[int] = []
    b = 1
    while b <= b_cap:
        bs.append(bucket_batch_size(b))
        b = bucket_batch_size(b) + 1
    top = length_buckets[-1]
    ts = {b for b in length_buckets if b <= t_cap}
    m = 2 * top
    while m <= t_cap:  # overflow region: multiples of the top bucket
        ts.add(m)
        m += top
    ts.add(t_cap)
    return [(b, t) for b in bs for t in sorted(ts)]


class ServingTelemetry:
    """Serving's SLO surface over the shared registry/trace primitives.

    Instruments (resolved once, observed per request/batch):

    * ``request_latency_seconds`` histogram — admission to completion,
      the SLO number; p50/p95/p99 come from the shared nearest-rank
      percentile convention (one implementation, telemetry.py). The
      latency histogram also keeps a ``slo_window_s`` sliding TIME
      window: the ``slo_window`` snapshot block reports p50/p95/p99
      over the last N seconds only, so a control loop (the fleet
      autoscaler) sees a fresh load spike instead of the spike diluted
      across the whole run's samples.
    * ``queue_wait_seconds`` histogram — admission to batch-assembly
      pickup (time-in-queue).
    * ``dispatch_wait_seconds`` histogram — admission to the batch being
      handed to the device (time-to-first-dispatch). The gap between
      this and queue_wait is the coalescing-window tax; continuous
      batching exists to erase it, and this pair is the per-request
      proof.
    * ``batch_occupancy`` histogram + ``last_batch_occupancy`` gauge —
      docs per dispatched device batch; occupancy ≈ 1 under load means
      coalescing is broken (N serial batches of 1).
    * ``queue_depth`` gauge, ``requests``/``docs``/``batches`` counters,
      and one counter per typed reject (``rejected_queue_full``,
      ``rejected_draining``, ``deadline_exceeded``, ``errors``).
    * trace: one span per batch (cat ``serve``) with occupancy/B/T args,
      one span per request (admission → completion) on the caller's
      track, an instant per reject.
    """

    def __init__(
        self,
        *,
        clock: Callable[[], float] = time.perf_counter,
        process_index: int = 0,
        trace_max_events: int = 100_000,
        slo_window_s: float = SERVING_DEFAULTS["slo_window_s"],
        exemplar_capacity: int = 64,
    ) -> None:
        from ..training.telemetry import (
            LATENCY_BUCKETS,
            OCCUPANCY_BUCKETS,
            MetricsRegistry,
            TraceBuffer,
        )

        self.registry = MetricsRegistry(clock=clock)
        self.trace = TraceBuffer(
            clock=clock, pid=int(process_index), max_events=trace_max_events
        )
        # host-resource truth (docs/OBSERVABILITY.md "Host
        # resources"): inside the facade so telemetry-off serving
        # constructs no sampler; rate-limited internally, so the
        # observer loop, /metrics scrapes and router polls share one
        # cached /proc read
        from ..training.hoststats import ProcessSampler

        self.hoststats = ProcessSampler(clock=clock)
        # the SLO histograms carry cumulative Prometheus bucket tables
        # (telemetry.py LATENCY_BUCKETS — shared repo-wide so replica
        # series sum exactly at the router/scraper) on top of the
        # percentile sample ring
        self._latency = self.registry.histogram(
            "request_latency_seconds", 2048, window_s=slo_window_s or None,
            buckets=LATENCY_BUCKETS,
        )
        self._queue_wait = self.registry.histogram(
            "queue_wait_seconds", 2048, buckets=LATENCY_BUCKETS
        )
        self._dispatch_wait = self.registry.histogram(
            "dispatch_wait_seconds", 2048, buckets=LATENCY_BUCKETS
        )
        self._occupancy = self.registry.histogram(
            "batch_occupancy", 1024, buckets=OCCUPANCY_BUCKETS
        )
        self._queue_depth = self.registry.gauge("queue_depth")
        self._last_occ = self.registry.gauge("last_batch_occupancy")
        self._requests = self.registry.counter("requests")
        self._docs = self.registry.counter("docs")
        self._batches = self.registry.counter("batches")
        # padding tax (data plane, docs/SERVING.md): tokens the device
        # actually computed vs tokens the bucket shape forced it to pad
        # to — pad share = pad / (pad + real) is the number length-aware
        # routing exists to reduce, so it must be measured where the
        # shape is chosen (dispatch assembly), not estimated downstream
        self._pad_tokens = self.registry.counter("pad_tokens")
        self._real_tokens = self.registry.counter("real_tokens")
        # conditional responses: requests answered 304 from the
        # ETag/If-None-Match check — inference AND serialization skipped
        self._not_modified = self.registry.counter("not_modified")
        self._rej_full = self.registry.counter("rejected_queue_full")
        self._rej_drain = self.registry.counter("rejected_draining")
        self._rej_quota = self.registry.counter("rejected_quota")
        self._deadline = self.registry.counter("deadline_exceeded")
        self._errors = self.registry.counter("errors")
        # hot-swap instruments (serving/live): how often the resident
        # generation flipped, and what each swap cost — staging (load +
        # overlay + device put, off the dispatch path) and the flip
        # itself (the only part a dispatch boundary can observe) are
        # timed SEPARATELY, because "swaps are cheap" is only honest if
        # the flip — the part that could stall traffic — is the cheap
        # part
        self._swaps = self.registry.counter("swaps")
        self._rollbacks = self.registry.counter("rollbacks")
        self._swap_total = self.registry.histogram("swap_seconds", 256)
        self._swap_stage = self.registry.histogram("swap_stage_seconds", 256)
        self._swap_flip = self.registry.histogram("swap_flip_seconds", 256)
        self._generation = self.registry.gauge("serving_generation")
        # slow-request exemplars (docs/OBSERVABILITY.md): a bounded ring
        # of p99-outlier requests with their per-stage breakdown, keyed
        # by request id — the bridge from "p99 got worse" to "THIS
        # request spent 80ms waiting for dispatch". The threshold is the
        # latency ring's p99, refreshed every _EXEMPLAR_REFRESH
        # completions (sorting 2048 samples per request would be hot-path
        # work for a diagnostic).
        self._exemplars: "deque" = deque(maxlen=int(exemplar_capacity))
        self._exemplar_count = self.registry.counter("slow_exemplars")
        self._exemplar_lock = threading.Lock()
        self._exemplar_seen = 0
        self._exemplar_threshold: Optional[float] = None

    _EXEMPLAR_REFRESH = 64
    _EXEMPLAR_MIN_SAMPLES = 100

    def now(self) -> float:
        return self.trace.now()

    def request_admitted(self, n_docs: int, queue_depth: int) -> None:
        self._requests.inc()
        self._docs.inc(n_docs)
        self._queue_depth.set(queue_depth)

    def request_rejected(
        self, error: ServingError, request_id: Optional[str] = None
    ) -> None:
        if isinstance(error, Draining):
            self._rej_drain.inc()
        elif isinstance(error, DeadlineExceeded):
            self._deadline.inc()
        elif isinstance(error, ServingError) and error.code == "queue_full":
            self._rej_full.inc()
        elif isinstance(error, ServingError) and error.code == "quota_exceeded":
            self._rej_quota.inc()
        else:
            self._errors.inc()
        args = {"error": str(error)}
        if request_id is not None:
            args["request_id"] = request_id
        self.trace.add_instant(f"reject:{error.code}", cat="serve", args=args)

    def request_completed(
        self,
        *,
        latency_s: float,
        queue_wait_s: Optional[float],
        t0: Optional[float],
        error: Optional[ServingError],
        dispatch_wait_s: Optional[float] = None,
        request_id: Optional[str] = None,
    ) -> None:
        if error is not None:
            self.request_rejected(error, request_id)
        else:
            self._latency.observe(latency_s)
            if queue_wait_s is not None:
                self._queue_wait.observe(queue_wait_s)
            if dispatch_wait_s is not None:
                self._dispatch_wait.observe(dispatch_wait_s)
        if t0 is not None:
            args: Dict[str, Any] = {
                "error": error.code if error is not None else None
            }
            if request_id is not None:
                args["request_id"] = request_id
            self.trace.add_span(
                "request",
                t0,
                max(self.now() - t0, 0.0),
                cat="serve",
                args=args,
            )

    def conditional_hit(self) -> None:
        self._not_modified.inc()

    def batch_span(
        self,
        occupancy: int,
        B: int,
        T: int,
        request_ids: Optional[List[str]] = None,
        real_tokens: Optional[int] = None,
    ):
        self._batches.inc()
        self._occupancy.observe(occupancy)
        self._last_occ.set(occupancy)
        if real_tokens is not None:
            # B*T is what the device computes; real is what was asked for
            self._real_tokens.inc(real_tokens)
            self._pad_tokens.inc(max(B * T - real_tokens, 0))
        kwargs: Dict[str, Any] = {"occupancy": occupancy, "B": B, "T": T}
        if request_ids:
            # a batch holds at most max_batch_docs requests — small
            # enough to name them all, making every dispatch span
            # attributable to the requests it served
            kwargs["request_ids"] = request_ids
        return self.trace.span("serve_batch", cat="serve", **kwargs)

    # -- slow-request exemplars ----------------------------------------
    def consider_exemplar(
        self,
        *,
        request_id: str,
        latency_s: float,
        stages: Dict[str, Optional[float]],
        **meta: Any,
    ) -> bool:
        """Record this completed request in the exemplar ring iff it is
        a p99 outlier (latency STRICTLY ABOVE the latency ring's p99,
        once at least ``_EXEMPLAR_MIN_SAMPLES`` completions exist —
        before that there is no tail to be an outlier of). ``stages`` is
        the per-stage breakdown (queue_wait/dispatch_wait/device/
        serialize seconds, None = stage unobserved). Returns True when
        recorded."""
        with self._exemplar_lock:
            self._exemplar_seen += 1
            if (
                self._exemplar_threshold is None
                or self._exemplar_seen % self._EXEMPLAR_REFRESH == 0
            ):
                if self._latency.count >= self._EXEMPLAR_MIN_SAMPLES:
                    self._exemplar_threshold = self._latency.percentile(0.99)
            threshold = self._exemplar_threshold
            # strictly ABOVE p99: in a flat distribution p99 equals every
            # sample, and "everything is an outlier" is no exemplar at all
            if threshold is None or latency_s <= threshold:
                return False
            self._exemplars.append(
                {
                    "request_id": request_id,
                    "latency_s": round(float(latency_s), 6),
                    "t": round(self.now(), 6),
                    "stages": {
                        k: (round(float(v), 6) if v is not None else None)
                        for k, v in stages.items()
                    },
                    **meta,
                }
            )
        self._exemplar_count.inc()
        return True

    def exemplars(self) -> Dict[str, Any]:
        """The /admin/exemplars payload: the ring (newest last) plus the
        threshold that admitted its members."""
        with self._exemplar_lock:
            return {
                "threshold_s": self._exemplar_threshold,
                "count": len(self._exemplars),
                "exemplars": list(self._exemplars),
            }

    def set_queue_depth(self, depth: int) -> None:
        self._queue_depth.set(depth)

    def swap_completed(
        self,
        *,
        stage_s: float,
        flip_s: float,
        t0: Optional[float],
        generation: Optional[int],
        rollback: bool = False,
    ) -> None:
        """One resident-generation flip: counters, the stage/flip/total
        histograms, the generation gauge, and two trace spans (staging
        then flip, back to back on the swapping thread's track)."""
        self._swaps.inc()
        if rollback:
            self._rollbacks.inc()
        self._swap_stage.observe(stage_s)
        self._swap_flip.observe(flip_s)
        self._swap_total.observe(stage_s + flip_s)
        if generation is not None:
            self._generation.set(float(generation))
        if t0 is not None:
            args = {"generation": generation, "rollback": rollback}
            self.trace.add_span(
                "swap_stage", t0, max(stage_s, 0.0), cat="serve", args=args
            )
            self.trace.add_span(
                "swap_flip", t0 + stage_s, max(flip_s, 0.0), cat="serve",
                args=args,
            )

    def snapshot(self) -> Dict[str, Any]:
        """The /metrics payload: registry snapshot + the SLO percentiles.
        ``slo`` keeps the sample-ring convention (last 2048 requests);
        ``slo_window`` re-states the latency percentiles over the last
        ``slo_window_s`` SECONDS only — the block the autoscaler reads,
        because a run-lifetime-ish ring dilutes a fresh spike exactly
        when the control loop needs to react to it (fake-clock
        regression-tested in test_telemetry.py)."""
        snap = self.registry.snapshot()
        snap["slo"] = {
            "request_latency_p50": self._latency.percentile(0.50),
            "request_latency_p95": self._latency.percentile(0.95),
            "request_latency_p99": self._latency.percentile(0.99),
            "batch_occupancy_p50": self._occupancy.percentile(0.50),
            "dispatch_wait_p50": self._dispatch_wait.percentile(0.50),
            "dispatch_wait_p99": self._dispatch_wait.percentile(0.99),
        }
        win = self._latency.window_snapshot()
        if win is not None:
            snap["slo_window"] = {
                "window_s": win["window_s"],
                "samples": win["samples"],
                "request_latency_p50": win["p50"],
                "request_latency_p95": win["p95"],
                "request_latency_p99": win["p99"],
            }
        # host truth rides every snapshot: the server's JSON /metrics,
        # the observer tick (recorder ring + process.* alert rules) and
        # the router's replica polls all read this one key
        snap["process"] = self.hoststats.sample()
        return snap


class InferenceEngine:
    """Owns the pipeline + device params and the dispatch thread.

    ``submit_texts``/``submit_docs`` run on caller (HTTP handler)
    threads: tokenize, admission-check, enqueue, block until the
    dispatch thread completes the request (or a typed error says why
    not). The dispatch thread assembles batches via
    :class:`DynamicBatcher` (continuous slot-based admission by default;
    the window discipline behind ``batching="window"``) and executes ONE
    ``predict_docs`` call per batch with the padded bucket pinned
    explicitly — exactly a warmed shape. The params it dispatches are
    ``serve_params`` — the precision overlay's output (f32 untouched, or
    a bf16 trunk overlay on accelerators; serving/overlay.py) — and
    ``overlay.label`` is the honest precision story every surface
    reports.
    """

    def __init__(
        self,
        nlp,
        *,
        max_batch_docs: int = SERVING_DEFAULTS["max_batch_docs"],
        max_wait_s: float = SERVING_DEFAULTS["max_wait_s"],
        max_queue_docs: int = SERVING_DEFAULTS["max_queue_docs"],
        timeout_s: float = SERVING_DEFAULTS["timeout_s"],
        max_doc_len: int = SERVING_DEFAULTS["max_doc_len"],
        batching: str = SERVING_DEFAULTS["batching"],
        precision: str = SERVING_DEFAULTS["precision"],
        telemetry: Optional[ServingTelemetry] = None,
        clock: Callable[[], float] = time.monotonic,
        class_weights: Optional[Dict[str, float]] = None,
    ) -> None:
        if nlp.params is None:
            raise ValueError(
                "serving needs an initialized/loaded pipeline (params are "
                "None — load a trained model with Pipeline.from_disk)"
            )
        self.nlp = nlp
        self.max_batch_docs = int(max_batch_docs)
        self.max_doc_len = int(max_doc_len)
        self.timeout_s = float(timeout_s)
        self.tel = telemetry
        self.clock = clock
        self.batching = batching
        # class_weights arms weighted fair queuing across SLO classes
        # (multi-tenant serving); None keeps the legacy single FIFO
        self.batcher = DynamicBatcher(
            max_queue_docs=max_queue_docs,
            max_batch_docs=max_batch_docs,
            max_wait_s=max_wait_s,
            mode=batching,
            clock=clock,
            class_weights=class_weights,
        )
        # precision overlay, applied ONCE at construction: every dispatch
        # (warmup sweep included, so warmed programs match live traffic's
        # param dtypes) consumes self.serve_params, never nlp.params
        # directly. overlay.resolved/label are the honest story /healthz
        # carries.
        from .overlay import build_serving_overlay

        self.precision = precision
        self.overlay = build_serving_overlay(nlp, precision)
        self.serve_params = self.overlay.params
        # live hot-swap state (serving/live, docs/SERVING.md "Continuous
        # learning"): the f32 master tree the overlay was built from,
        # the generation stamp it came from (None = the model as loaded
        # from disk), and ONE previous resident kept for instant
        # rollback. _flip_lock makes (serve_params, overlay, generation)
        # one atomic unit: the dispatch thread snapshots all three at a
        # batch boundary, so no batch ever runs mixed weights or carries
        # another generation's stamp.
        self._master_params = nlp.params
        self.serving_generation: Optional[int] = None
        self.swap_count = 0
        self.rollback_count = 0
        self._previous: Optional[Tuple[Optional[int], Any, Any]] = None
        self._swap_lock = threading.Lock()   # serializes swap/rollback
        self._flip_lock = threading.Lock()   # guards the resident unit
        self._thread: Optional[threading.Thread] = None
        self._state_lock = threading.Lock()
        self._idle = threading.Condition(self._state_lock)
        self._active_batches = 0
        self._started = False
        # readiness is distinct from started-ness: the HTTP listener may
        # be up (so a router can probe /healthz and learn the port) while
        # the warmup sweep is still compiling. Until ready, traffic gets
        # a typed 503 NotReady — never a live mid-warmup compile.
        self.ready = False
        self.warmed: List[Tuple[int, int]] = []

    # -- lifecycle ------------------------------------------------------
    def warmup(self) -> List[Tuple[int, int]]:
        """Compile the forward program for every admissible bucket shape
        (synthetic docs, one ``predict_docs`` per (B, T)); returns the
        swept grid. Runs on the calling thread BEFORE dispatch starts,
        so the jit cache is never touched concurrently."""
        from ..pipeline.doc import Doc

        grid = warmup_buckets(
            self.max_batch_docs, self.max_doc_len, self.nlp.length_buckets
        )
        for B, T in grid:
            docs = [Doc(words=["the"] * T) for _ in range(B)]
            self.nlp.predict_docs(
                docs, params=self.serve_params,
                batch_size=B, pad_batch_to=B, pad_len_to=T,
            )
        self.warmed = grid
        return grid

    def start(self, *, warmup: bool = True) -> "InferenceEngine":
        if self._started:
            return self
        if warmup:
            self.warmup()
        self._started = True
        self._thread = threading.Thread(
            target=self._dispatch_loop, name="serve-dispatch", daemon=True
        )
        self._thread.start()
        self.ready = True  # last: readiness implies warmed AND dispatching
        return self

    # -- submission (handler threads) -----------------------------------
    def submit_texts(
        self,
        texts: Sequence[str],
        timeout_s: Optional[float] = None,
        request_id: Optional[str] = None,
        klass: str = "default",
    ) -> ServeRequest:
        docs = [self.nlp.tokenizer(t) for t in texts]
        return self.submit_docs(
            docs, timeout_s=timeout_s, request_id=request_id, klass=klass
        )

    def submit_docs(
        self,
        docs: List[Any],
        timeout_s: Optional[float] = None,
        request_id: Optional[str] = None,
        klass: str = "default",
    ) -> ServeRequest:
        timeout = self.timeout_s if timeout_s is None else float(timeout_s)
        too_long = [i for i, d in enumerate(docs) if len(d) > self.max_doc_len]
        if too_long:
            err: ServingError = RequestTooLarge(
                f"doc(s) {too_long} exceed max_doc_len={self.max_doc_len} "
                "tokens (the warmed shape cap) — split or truncate"
            )
            if self.tel is not None:
                self.tel.request_rejected(err, request_id)
            raise err
        now = self.clock()
        req = ServeRequest(
            docs, deadline=now + timeout, enqueued_at=now,
            request_id=request_id, klass=klass,
        )
        t0 = self.tel.now() if self.tel is not None else None
        try:
            self.batcher.submit(req)
        except ServingError as e:
            if self.tel is not None:
                self.tel.request_rejected(e, req.request_id)
            raise
        if self.tel is not None:
            self.tel.request_admitted(len(docs), self.batcher.queue_depth())
        # +grace so the dispatch thread (which owns deadline accounting)
        # is the one that times the request out, not this wait
        req.wait(timeout + 1.0)
        latency = self.clock() - req.enqueued_at
        req.latency_s = latency
        queue_wait = (
            req.started_at - req.enqueued_at
            if req.started_at is not None
            else None
        )
        dispatch_wait = (
            req.dispatched_at - req.enqueued_at
            if req.dispatched_at is not None
            else None
        )
        if not req.done:
            err = DeadlineExceeded(
                f"request not completed within {timeout:.3f}s"
            )
            if self.tel is not None:
                self.tel.request_completed(
                    latency_s=latency, queue_wait_s=queue_wait, t0=t0,
                    error=err, request_id=req.request_id,
                )
            raise err
        if self.tel is not None:
            self.tel.request_completed(
                latency_s=latency,
                queue_wait_s=queue_wait,
                t0=t0,
                error=req.error,
                dispatch_wait_s=dispatch_wait,
                request_id=req.request_id,
            )
        if req.error is not None:
            raise req.error
        return req  # docs annotated in place; batch_info says how it ran

    # -- dispatch (one thread) ------------------------------------------
    def _dispatch_loop(self) -> None:
        while True:
            batch = self.batcher.next_batch()
            if batch is None:
                return
            if not batch:
                continue
            with self._state_lock:
                self._active_batches += 1
            try:
                self._run_batch(batch)
            finally:
                with self._state_lock:
                    self._active_batches -= 1
                    self._idle.notify_all()

    def _run_batch(self, requests: List[ServeRequest]) -> None:
        docs = [d for r in requests for d in r.docs]
        n = len(docs)
        B = bucket_batch_size(n)
        T = bucket_length(
            max((len(d) for d in docs), default=1), self.nlp.length_buckets
        )
        # the dispatch boundary: snapshot the resident (params,
        # generation) unit ONCE, under the flip lock. A swap that lands
        # after this point is observed by the NEXT batch; this batch
        # runs entirely on one tree and is stamped with that tree's
        # generation — the no-mixed-weights contract (test-enforced).
        with self._flip_lock:
            serve_params = self.serve_params
            generation = self.serving_generation
        dispatched_at = self.clock()  # assembly over, handed to the device
        for r in requests:
            r.dispatched_at = dispatched_at
        request_ids = [r.request_id for r in requests]
        info = {"occupancy": n, "B": B, "T": T, "generation": generation}
        real_tokens = sum(len(d) for d in docs)
        t_dev = self.clock()
        try:
            if self.tel is not None:
                with self.tel.batch_span(
                    n, B, T, request_ids, real_tokens=real_tokens
                ):
                    self.nlp.predict_docs(
                        docs, params=serve_params,
                        batch_size=n, pad_batch_to=B, pad_len_to=T,
                    )
                self.tel.set_queue_depth(self.batcher.queue_depth())
            else:
                self.nlp.predict_docs(
                    docs, params=serve_params,
                    batch_size=n, pad_batch_to=B, pad_len_to=T,
                )
        except Exception as e:  # a poisoned batch must not kill the server
            log_event(
                "serve-batch-failed",
                f"dispatch of {n} docs (B={B}, T={T}) failed: "
                f"{type(e).__name__}: {e}",
                occupancy=n,
                request_ids=request_ids,
            )
            err = ServingError(f"inference failed: {type(e).__name__}: {e}")
            for r in requests:
                r.batch_info = dict(info)
                r.complete(err)
            return
        # the device stage of the per-request breakdown (exemplars):
        # predict wall time for the batch this request rode in — on the
        # request, not batch_info (the response body stays deterministic)
        dev_s = round(self.clock() - t_dev, 6)
        for r in requests:
            r.device_s = dev_s
            r.batch_info = dict(info)
            r.complete()

    # -- live hot-swap (serving/live; docs/SERVING.md) -------------------
    @staticmethod
    def _tree_spec(tree: Any, prefix: str = "") -> Dict[str, Tuple]:
        """(path -> (shape, dtype)) without materializing anything — the
        compatibility fingerprint a candidate tree must match for the
        warmed (B, T) programs (shape- AND dtype-keyed in the jit cache)
        to keep applying after a flip."""
        out: Dict[str, Tuple] = {}
        if isinstance(tree, dict):
            for k in sorted(tree):
                out.update(
                    InferenceEngine._tree_spec(tree[k], f"{prefix}/{k}")
                )
        else:
            out[prefix] = (
                tuple(getattr(tree, "shape", ())),
                str(getattr(tree, "dtype", type(tree).__name__)),
            )
        return out

    def _stage(self, params: Any):
        """Build the candidate's precision overlay (same requested knob,
        fresh resolution — honest label preserved) and force it onto the
        device NOW, so the flip itself transfers nothing. Runs on the
        swapping thread; the dispatch thread keeps serving the current
        resident throughout. Raises :class:`SwapFailed` on any tree
        mismatch — a candidate that would void the warmed-program
        contract (or silently re-shape the model) is refused, and the
        engine keeps serving what it was serving."""
        import jax

        from .overlay import build_params_overlay

        want = self._tree_spec(self._master_params)
        got = self._tree_spec(params)
        if want != got:
            missing = sorted(set(want) - set(got))[:4]
            extra = sorted(set(got) - set(want))[:4]
            changed = sorted(
                k for k in set(want) & set(got) if want[k] != got[k]
            )[:4]
            raise SwapFailed(
                "candidate param tree does not match the resident one "
                f"(missing: {missing}, unexpected: {extra}, reshaped/"
                f"retyped: {changed}) — swap refused, still serving "
                f"generation {self.serving_generation}"
            )
        overlay = build_params_overlay(params, self.precision)
        jax.block_until_ready(jax.device_put(overlay.params))
        return overlay

    def swap_params(
        self, params: Any, generation: int, *, source: str = "api"
    ) -> Dict[str, Any]:
        """Hot-swap the resident param tree to ``params`` (a verified
        checkpoint generation's f32 masters). Staging — overlay build +
        device put — happens off the dispatch path; the flip is an
        O(pointers) exchange at a dispatch boundary (the single dispatch
        thread snapshots the resident unit once per batch, so no
        in-flight batch ever sees mixed weights). The displaced resident
        stays staged for instant :meth:`rollback`. Returns a summary
        dict; raises :class:`SwapFailed` on an incompatible tree."""
        t_wall = self.clock()
        t0 = self.tel.now() if self.tel is not None else None
        with self._swap_lock:
            overlay = self._stage(params)
            stage_s = self.clock() - t_wall
            t_flip = self.clock()
            with self._flip_lock:
                prev = (
                    self.serving_generation, self.overlay,
                    self._master_params,
                )
                self.overlay = overlay
                self.serve_params = overlay.params
                self._master_params = params
                self.serving_generation = int(generation)
                self.swap_count += 1
                self._previous = prev
            flip_s = self.clock() - t_flip
        if self.tel is not None:
            self.tel.swap_completed(
                stage_s=stage_s, flip_s=flip_s, t0=t0,
                generation=int(generation),
            )
        log_event(
            "serve-swap",
            f"hot-swapped serving params to generation {generation} "
            f"(from {prev[0]}; staged {stage_s * 1e3:.1f} ms, flip "
            f"{flip_s * 1e3:.3f} ms, precision {overlay.label}; "
            f"source {source})",
            level=logging.INFO,
            generation=int(generation),
            previous=prev[0],
            stage_s=round(stage_s, 6),
            flip_s=round(flip_s, 6),
            source=source,
        )
        return {
            "generation": int(generation),
            "previous_generation": prev[0],
            "swap_count": self.swap_count,
            "stage_s": stage_s,
            "flip_s": flip_s,
            "precision_label": overlay.label,
        }

    def rollback(self) -> Dict[str, Any]:
        """Instant rollback to the previous RESIDENT generation: its
        overlay never left staging, so this is a pure flip (no load, no
        digest work, no device transfer). The displaced generation
        becomes the new previous — rollback is its own inverse. Raises
        :class:`SwapFailed` when no previous resident exists."""
        t0 = self.tel.now() if self.tel is not None else None
        with self._swap_lock:
            if self._previous is None:
                raise SwapFailed(
                    "no previous resident generation to roll back to "
                    f"(serving generation {self.serving_generation}, "
                    f"{self.swap_count} swap(s) so far)"
                )
            t_flip = self.clock()
            with self._flip_lock:
                displaced = (
                    self.serving_generation, self.overlay,
                    self._master_params,
                )
                gen, overlay, master = self._previous
                self.overlay = overlay
                self.serve_params = overlay.params
                self._master_params = master
                self.serving_generation = gen
                self.swap_count += 1
                self.rollback_count += 1
                self._previous = displaced
            flip_s = self.clock() - t_flip
        if self.tel is not None:
            self.tel.swap_completed(
                stage_s=0.0, flip_s=flip_s, t0=t0, generation=gen,
                rollback=True,
            )
        log_event(
            "serve-rollback",
            f"rolled serving params back to generation {gen} (from "
            f"{displaced[0]}; flip {flip_s * 1e3:.3f} ms)",
            generation=gen,
            displaced=displaced[0],
            flip_s=round(flip_s, 6),
        )
        return {
            "generation": gen,
            "displaced_generation": displaced[0],
            "swap_count": self.swap_count,
            "flip_s": flip_s,
            "precision_label": self.overlay.label,
        }

    # -- drain / stop ----------------------------------------------------
    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful drain: stop admitting, finish every queued and
        in-flight batch, stop the dispatch thread. Returns True when the
        queue fully drained within the timeout (False = gave up; callers
        escalate to :meth:`stop`)."""
        self.batcher.begin_drain()
        deadline = time.monotonic() + float(timeout_s)
        with self._idle:
            while (
                self.batcher.queue_depth() > 0 or self._active_batches > 0
            ):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._idle.wait(timeout=min(remaining, 0.1))
        self.stop()
        return True

    def stop(self) -> None:
        """Hard stop: close the batcher (failing anything still queued)
        and join the dispatch thread."""
        self.ready = False
        self.batcher.close()
        self.batcher.fail_all_queued(Draining("server shut down"))
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        self._started = False
