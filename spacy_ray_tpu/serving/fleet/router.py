"""Fleet router: one HTTP front-end load-balancing ``/v1/parse`` over N
engine replicas.

Balancing policy is least-outstanding-requests: among READY replicas,
pick the one with the fewest requests currently forwarded to it. With
homogeneous replicas this is the classic supermarket rule — it tracks
the real signal (how busy a replica is NOW, including slow batches)
rather than round-robin's assumption that every request costs the same.

Readiness is probed, never assumed: a background prober GETs each
replica's ``/healthz`` — 200 marks it ready, 503 (``warming`` during
the bucket compile sweep, ``draining`` during shutdown) or a connection
error marks it out. A forward that fails at the socket level marks the
replica unready IMMEDIATELY (no waiting for the next probe) and retries
the request on another replica — a replica crash under load costs the
in-flight retry, never a client-visible 5xx. When no replica is ready,
admission fails with a typed 503 ``no_replica`` instantly (shed, don't
queue blind).

The router deliberately does NOT parse request/response JSON on the hot
path — it forwards bytes. The single exception is the optional response
cache (``cache_bytes > 0``): a byte-capped LRU keyed by the hash of the
request's input texts (the ``CollateCache`` identity-key pattern from
the input pipeline, applied at the serving edge — heavy real traffic is
Zipfian), serving repeat bodies without touching a replica.

``/metrics`` on the router is the FLEET view: each ready replica's SLO
snapshot is scraped and merged (``training/telemetry.py:
merge_serving_snapshots``) with the router's own counters — one scrape
for the whole fleet instead of N.
"""

from __future__ import annotations

import http.client
import json
import logging
import threading
import time
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from ...training.resilience import log_event
from ..batcher import (
    Draining,
    REQUEST_ID_HEADER,
    ServingError,
    UnknownModel,
    cache_key_for,
    clean_request_id,
    etag_for,
    if_none_match_hit,
    mint_request_id,
)
from ..multimodel.registry import TENANT_HEADER
from .replica import ReplicaHandle

__all__ = [
    "NoReplicaAvailable",
    "ResponseCache",
    "GENERATION_MIXED",
    "RouterTelemetry",
    "Router",
    "RouterHTTPServer",
]

logger = logging.getLogger("spacy_ray_tpu.serving")

MAX_BODY_BYTES = 8 << 20  # same abuse cap as the single-replica server


class NoReplicaAvailable(ServingError):
    """Zero ready replicas (all warming, crashed, or draining): a typed
    503 the instant it is known — queueing the request blind would just
    convert an outage into a timeout storm."""

    http_status = 503
    code = "no_replica"


# sentinel for "the ready replicas straddle generations" (mid-rollout /
# mid-promotion): no single generation can vouch for a cached body, so
# the cache is bypassed entirely until the fleet converges
GENERATION_MIXED = object()


def _length_bucket_hint(texts: List[str]) -> int:
    """Coarse length-bucket index for affinity routing. The router does
    not tokenize; a whitespace word count approximates token count well
    enough to BUCKET — the buckets are powers of two, so a near-boundary
    miss lands one bucket off, which only weakens affinity, never
    correctness. Keyed on the MAX text (the shape the device batch pads
    to), same rule as the engine's dispatch assembly."""
    from ...training.batcher import DEFAULT_LENGTH_BUCKETS

    est = max(len(t.split()) for t in texts)
    for i, bucket in enumerate(DEFAULT_LENGTH_BUCKETS):
        if est <= bucket:
            return i
    return len(DEFAULT_LENGTH_BUCKETS) - 1


class ResponseCache:
    """Byte-capped LRU of successful ``/v1/parse`` response bodies,
    keyed by a digest of the request's input texts AND stamped with the
    checkpoint generation that produced them.

    Unlike the input pipeline's ``CollateCache`` (which keys on object
    identity because the corpus re-yields the same Examples), the edge
    sees texts by VALUE over the wire — so the key is a content hash.
    Responses are deterministic given the loaded params — which is
    exactly why the generation stamp exists: a PR 8 hot-swap promotion
    CHANGES the loaded params, and a hit is only exact *for the
    generation that computed it*. ``get`` therefore takes the
    generation the caller expects (the one every ready replica serves);
    an entry stamped with any other generation is dropped on access and
    counted as a stale invalidation, never served. ``flush`` clears the
    whole cache (the promotion hook — versioned keys make staleness
    impossible, the flush just reclaims the dead generation's bytes).
    The cached ``batch`` shape info still reflects the batch the
    ORIGINAL request ran in. Entries are only stored for status-200
    bodies.

    Thread-safe; hit/miss/eviction/stale/flush counters feed
    ``/metrics``.
    """

    def __init__(self, max_bytes: int) -> None:
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[bytes, Tuple[Any, bytes]]" = OrderedDict()
        self._nbytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.stale_invalidations = 0
        self.flushes = 0
        # conditional responses answered body-less (304): the client
        # already held the exact body this cache (or a replica) would
        # have sent — hit-adjacent, but zero bytes moved
        self.not_modified = 0
        # per-model hit/miss ledger (multi-model serving): the model
        # name is a key dimension, so two models' identical texts never
        # collide, and the hit-rate story is attributable per model
        self.by_model: Dict[str, Dict[str, int]] = {}

    # the digest lives in batcher.cache_key_for so the replica's ETag
    # and the router's cache key can never disagree about identity —
    # the ETag is that key plus the generation (docs/SERVING.md)
    key_for = staticmethod(cache_key_for)

    def _tally(self, model: Optional[str], field: str) -> None:
        """Caller holds ``_lock``."""
        if model is None:
            return
        ledger = self.by_model.setdefault(
            model, {"hits": 0, "misses": 0, "stale_invalidations": 0}
        )
        # not_modified joins a ledger lazily (first 304 for that model)
        # so the legacy ledger shape is unchanged for models that never
        # see a conditional request
        ledger[field] = ledger.get(field, 0) + 1

    def get(
        self, key: bytes, generation: Any = None,
        model: Optional[str] = None,
    ) -> Optional[bytes]:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
                self._tally(model, "misses")
                return None
            stored_gen, body = entry
            if stored_gen != generation:
                # a promotion happened since this body was cached: it
                # holds the OLD generation's annotations — drop it, so
                # the miss path re-parses on the new weights
                del self._entries[key]
                self._nbytes -= len(body)
                self.stale_invalidations += 1
                self.misses += 1
                self._tally(model, "stale_invalidations")
                self._tally(model, "misses")
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            self._tally(model, "hits")
            return body

    def put(self, key: bytes, body: bytes, generation: Any = None) -> None:
        if len(body) > self.max_bytes:
            return  # one oversized response must not flush the cache
        with self._lock:
            if key in self._entries:
                old_gen, old_body = self._entries[key]
                if old_gen == generation:
                    return
                # same texts, newer generation: replace the stale entry
                self._nbytes -= len(old_body)
                del self._entries[key]
            self._entries[key] = (generation, body)
            self._nbytes += len(body)
            while self._nbytes > self.max_bytes and len(self._entries) > 1:
                _, (_, evicted) = self._entries.popitem(last=False)
                self._nbytes -= len(evicted)
                self.evictions += 1

    def count_not_modified(self, model: Optional[str] = None) -> None:
        with self._lock:
            self.not_modified += 1
            self._tally(model, "not_modified")

    def flush(self) -> int:
        """Drop every entry; returns how many. Called on promotion —
        the old generation's bodies can never hit again (their stamp no
        longer matches), so their bytes are reclaimed eagerly."""
        with self._lock:
            n = len(self._entries)
            self._entries.clear()
            self._nbytes = 0
            if n:
                self.flushes += 1
        return n

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = {
                "cache_hits": self.hits,
                "cache_misses": self.misses,
                "cache_evictions": self.evictions,
                "cache_stale_invalidations": self.stale_invalidations,
                "cache_flushes": self.flushes,
                "cache_not_modified": self.not_modified,
                "cache_entries": len(self._entries),
                "cache_bytes": self._nbytes,
            }
            if self.by_model:
                out["by_model"] = {
                    m: dict(ledger)
                    for m, ledger in sorted(self.by_model.items())
                }
            return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


class RouterTelemetry:
    """Router-side SLO surface over the shared telemetry primitives:
    fleet latency histogram (admission at the router to response),
    routed/retried/rejected counters, ready-replica gauge, and a trace
    instant per routing anomaly. Nullable like every telemetry facade in
    this repo — when absent, the router makes ZERO telemetry calls."""

    def __init__(
        self,
        *,
        clock: Callable[[], float] = time.perf_counter,
        trace_max_events: int = 100_000,
    ) -> None:
        from ...training.telemetry import (
            LATENCY_BUCKETS,
            MetricsRegistry,
            TraceBuffer,
        )

        self.registry = MetricsRegistry(clock=clock)
        self.trace = TraceBuffer(clock=clock, max_events=trace_max_events)
        # host-resource truth for the router PROCESS itself (replicas
        # report their own via their snapshots); facade-owned so the
        # telemetry-off fleet constructs no sampler
        from ...training.hoststats import ProcessSampler

        self.hoststats = ProcessSampler(clock=clock)
        self._latency = self.registry.histogram(
            "router_latency_seconds", 2048, buckets=LATENCY_BUCKETS
        )
        self._requests = self.registry.counter("requests")
        self._routed = self.registry.counter("routed")
        self._retries = self.registry.counter("retries")
        self._rej_no_replica = self.registry.counter("rejected_no_replica")
        self._rej_draining = self.registry.counter("rejected_draining")
        # multi-model routing: requests naming a model the registry does
        # not know (typed 404 at the edge, never forwarded)
        self._rej_unknown_model = self.registry.counter(
            "rejected_unknown_model"
        )
        self._cache_hits = self.registry.counter("cache_hits")
        self._ready = self.registry.gauge("ready_replicas")
        self._replicas = self.registry.gauge("replicas")
        # satellite of the fleet /metrics contract: a ready replica
        # whose scrape fails is COUNTED, not silently dropped from the
        # aggregate — a fleet view quietly missing its slowest replica
        # is how an SLO breach hides
        self._scrape_failures = self.registry.counter("scrape_failures")
        # generation-split accounting: how many picks went to the canary
        # vs baseline side while a rollout was in flight — the exact
        # ratio the deterministic accumulator promises is auditable here
        self._canary_picks = self.registry.counter("routed_canary")
        self._baseline_picks = self.registry.counter("routed_baseline")
        # length-affinity accounting (data plane): how often the policy
        # placed a request on its bucket's replica vs spilled to
        # least-outstanding because that replica was already loaded —
        # a high spill share means the mixture defeats the affinity map
        self._affinity_picks = self.registry.counter("length_affinity_picks")
        self._affinity_spills = self.registry.counter(
            "length_affinity_spills"
        )

    def now(self) -> float:
        return self.trace.now()

    def request(self) -> None:
        self._requests.inc()

    def routed(
        self,
        latency_s: float,
        *,
        request_id: Optional[str] = None,
        t0: Optional[float] = None,
        replica_id: Optional[int] = None,
    ) -> None:
        self._routed.inc()
        self._latency.observe(latency_s)
        if t0 is not None:
            # the router-side half of the distributed request trace: one
            # ``route`` span per forwarded request, carrying the SAME
            # request id the replica's ``request`` span carries — the
            # collector's merged timeline shows the hop
            args: Dict[str, Any] = {}
            if request_id is not None:
                args["request_id"] = request_id
            if replica_id is not None:
                args["replica"] = replica_id
            self.trace.add_span(
                "route", t0, max(self.now() - t0, 0.0), cat="fleet",
                args=args or None,
            )

    def retry(
        self, replica_id: int, error: str, request_id: Optional[str] = None
    ) -> None:
        self._retries.inc()
        args = {"replica": replica_id, "error": error}
        if request_id is not None:
            args["request_id"] = request_id
        self.trace.add_instant("reroute", cat="fleet", args=args)

    def rejected(
        self, error: ServingError, request_id: Optional[str] = None
    ) -> None:
        if isinstance(error, Draining):
            self._rej_draining.inc()
        elif isinstance(error, UnknownModel):
            self._rej_unknown_model.inc()
        else:
            self._rej_no_replica.inc()
        args = {"error": str(error)}
        if request_id is not None:
            args["request_id"] = request_id
        self.trace.add_instant(
            f"reject:{error.code}", cat="fleet", args=args
        )

    def scrape_failed(self, replica_id: int) -> None:
        self._scrape_failures.inc()

    def cache_hit(self) -> None:
        self._cache_hits.inc()

    def split_pick(self, canary: bool) -> None:
        (self._canary_picks if canary else self._baseline_picks).inc()

    def affinity_pick(self, *, spilled: bool) -> None:
        (self._affinity_spills if spilled else self._affinity_picks).inc()

    def replica_counts(self, ready: int, total: int) -> None:
        self._ready.set(ready)
        self._replicas.set(total)

    def snapshot(self) -> Dict[str, Any]:
        snap = self.registry.snapshot()
        snap["slo"] = {
            "router_latency_p50": self._latency.percentile(0.50),
            "router_latency_p95": self._latency.percentile(0.95),
            "router_latency_p99": self._latency.percentile(0.99),
        }
        return snap


class Router:
    """Balancing + health state over a set of :class:`ReplicaHandle`.

    ``replicas`` is a zero-arg callable returning the current handles —
    the supervisor's live view, so scale-up/down is visible to the
    router without any registration protocol. Tests pass a lambda over
    a static list pointed at stub servers.
    """

    def __init__(
        self,
        replicas: Callable[[], List[ReplicaHandle]],
        *,
        telemetry: Optional[RouterTelemetry] = None,
        cache_bytes: int = 0,
        probe_interval_s: float = 0.5,
        probe_timeout_s: float = 5.0,
        forward_timeout_s: float = 60.0,
        canary_fraction: float = 0.0,
        registry: Optional[Any] = None,
        length_routing: bool = False,
        affinity_slack: int = 2,
    ) -> None:
        self.replicas = replicas
        self.tel = telemetry
        # length-bucket affinity (docs/SERVING.md "Data plane"): off by
        # default — the pad-share win only exists with >1 replica and a
        # skewed length mixture, and the policy costs a texts parse on
        # the otherwise byte-proxy hot path
        self.length_routing = bool(length_routing)
        self.affinity_slack = int(affinity_slack)
        # multi-model serving (``--model-manifest``): a ModelRegistry
        # lets the router resolve WHICH model a request names (path >
        # header > default) and route within the replicas hosting it;
        # None keeps the single-model path bit-identical
        self.registry = registry
        self.cache = ResponseCache(cache_bytes) if cache_bytes > 0 else None
        self.probe_interval_s = float(probe_interval_s)
        self.probe_timeout_s = float(probe_timeout_s)
        self.forward_timeout_s = float(forward_timeout_s)
        # generation traffic splitting (docs/SERVING.md "Continuous
        # learning"): active ONLY while a rollout controller has
        # declared a canary generation (``canary_generation`` set by
        # LiveFleetController at canary start, cleared at
        # promote/rollback/abort) — mere generation heterogeneity is
        # NOT a split trigger, because a crash-restarted replica serving
        # the disk model would otherwise become a one-node "baseline"
        # absorbing 1-fraction of all traffic. While active, this
        # fraction of requests routes to the canary generation's
        # replicas and the rest to everyone else. The split is a
        # deterministic error-diffusion accumulator, not a coin flip —
        # an exact long-run ratio the guard's sample-count math can
        # rely on, and reproducible tests.
        self.canary_fraction = float(canary_fraction)
        self.canary_generation: Optional[int] = None
        self._split_lock = threading.Lock()
        self._split_acc = 0.0
        # diagnosis layer (docs/OBSERVABILITY.md "Alerting & incidents"):
        # the Fleet wires an AlertEngine (served on /admin/alerts and in
        # the /metrics alerts block) and a FlightRecorder here when
        # telemetry is on; both stay None otherwise (zero-calls contract)
        self.alerts: Optional[Any] = None
        self.recorder: Optional[Any] = None
        self._stop = threading.Event()
        self._prober: Optional[threading.Thread] = None
        # per-replica scrape-failure ledger (fleet /metrics): replica_id
        # -> failed scrape count, alongside the telemetry counter — the
        # aggregate names WHO it is missing, not just that it is missing
        self._scrape_lock = threading.Lock()
        self.scrape_failures: Dict[int, int] = {}
        # mixed-generation cache bypasses: requests that skipped the
        # cache because the ready replicas straddled generations (a
        # rollout/promotion window). Counted at the ROUTER (the bypass
        # is a routing decision, not a cache event), surfaced next to
        # the cache's own hit/miss ledger in /metrics and as
        # ``srt_router_cache_mixed_generation_bypasses_total``.
        self._cache_bypass_lock = threading.Lock()
        self.cache_mixed_bypasses = 0
        # drain gate + in-flight accounting for the fleet's own drain
        self.draining = False
        self._inflight_lock = threading.Lock()
        self._inflight = 0
        self._idle = threading.Condition(self._inflight_lock)

    # -- health probing --------------------------------------------------
    def probe_once(self) -> int:
        """Probe every addressed replica's /healthz; update ready flags.
        Returns the number of ready replicas. Called by the prober loop
        and directly by tests (deterministic, no thread needed)."""
        handles = self.replicas()
        n_ready = 0
        for h in handles:
            addr = h.address
            if addr is None or h.stopping or not h.alive:
                self._mark_unready(h, "no address" if addr is None else "down")
                continue
            try:
                status, raw = self._get_aux(
                    h, addr, "/healthz", self.probe_timeout_s
                )
                ok = status == 200
            except OSError:
                ok = False
                raw = b""
            if ok:
                # the healthz body carries the replica's live-serving
                # identity (generation + swap_count) — the canary split
                # and the fleet controller read it from the handle, so
                # it must be as fresh as readiness itself
                try:
                    health = json.loads(raw)
                except ValueError:
                    health = {}
                if isinstance(health, dict):
                    gen = health.get("generation")
                    swaps = health.get("swap_count")
                    resident = health.get("resident_models")
                    default_model = health.get("default_model")
                    with h.lock:
                        h.generation = gen if isinstance(gen, int) else None
                        if isinstance(swaps, int):
                            h.swap_count = swaps
                        # residency advertisement (multi-model replicas
                        # only): the probe loop IS the placement
                        # discovery protocol — no registration RPC
                        h.resident_models = (
                            {
                                str(m): (info if isinstance(info, dict)
                                         else {})
                                for m, info in resident.items()
                            }
                            if isinstance(resident, dict) else {}
                        )
                        h.default_model = (
                            default_model
                            if isinstance(default_model, str) else None
                        )
                        # short health history: a crash postmortem's
                        # "what did the router last know about it"
                        h.health_history.append(
                            {
                                "unix_time": round(time.time(), 3),
                                "health": health,
                            }
                        )
                self._mark_ready(h)
                n_ready += 1
            else:
                self._mark_unready(h, "healthz != 200")
        if self.tel is not None:
            self.tel.replica_counts(n_ready, len(handles))
        return n_ready

    def _mark_ready(self, h: ReplicaHandle) -> None:
        with h.lock:
            was = h.ready
            h.ready = True
        if not was:
            log_event(
                "replica-ready",
                f"replica {h.replica_id} ready at "
                f"{h.host}:{h.port}",
                level=logging.INFO,
                replica=h.replica_id,
            )

    def _mark_unready(self, h: ReplicaHandle, reason: str) -> None:
        with h.lock:
            was = h.ready
            h.ready = False
        h.close_conns()  # pooled conns to a gone replica are all stale
        if was:
            log_event(
                "replica-unready",
                f"replica {h.replica_id} removed from rotation ({reason})",
                replica=h.replica_id,
                reason=reason,
            )

    def _probe_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.probe_once()
            except Exception:  # the prober must survive anything
                logger.exception("health probe pass failed")
            self._stop.wait(self.probe_interval_s)

    def start(self) -> "Router":
        self._prober = threading.Thread(
            target=self._probe_loop, daemon=True, name="fleet-prober"
        )
        self._prober.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._prober is not None:
            self._prober.join(timeout=5.0)
            self._prober = None
        for h in self.replicas():
            h.close_conns()

    # -- response cache generation discipline ---------------------------
    def cache_generation(self, model: Optional[str] = None) -> Any:
        """The generation a cache hit must match: the ONE generation
        every ready replica serves (learned from /healthz; None = the
        disk model is itself a valid generation). When ready replicas
        straddle generations — a canary rollout, a mid-promotion window,
        a crash-restarted straggler — returns :data:`GENERATION_MIXED`
        and the caller bypasses the cache: no single stamp could vouch
        for which replica a forward would hit.

        With ``model`` (multi-model serving), the discipline applies to
        the replicas HOSTING that model: their per-model generation from
        the /healthz resident set. A model resident nowhere yet (first
        request triggers the cold load) also yields the mixed sentinel —
        nothing can vouch for a body before placement is known."""
        if model is not None:
            hosts = [
                h for h in self.ready_handles()
                if model in h.resident_models
            ]
            if not hosts:
                return GENERATION_MIXED
            gens = {
                h.resident_models[model].get("generation") for h in hosts
            }
            if len(gens) == 1:
                return next(iter(gens))
            return GENERATION_MIXED
        gens = {h.generation for h in self.ready_handles()}
        if len(gens) == 1:
            return next(iter(gens))
        return GENERATION_MIXED

    def count_cache_bypass(self) -> None:
        with self._cache_bypass_lock:
            self.cache_mixed_bypasses += 1

    def cache_stats(self) -> Optional[Dict[str, Any]]:
        """The cache's own counters plus the router-side mixed-generation
        bypass count — ONE ledger for every surface (JSON /metrics,
        the Prometheus ``srt_router_cache_*`` series and ``telemetry
        top`` all read this)."""
        if self.cache is None:
            return None
        stats = self.cache.stats()
        with self._cache_bypass_lock:
            stats["cache_mixed_generation_bypasses"] = self.cache_mixed_bypasses
        return stats

    def flush_cache(self, reason: str = "") -> int:
        """Drop the whole response cache (the promotion hook — the live
        controller calls this whenever the fleet's current generation
        changes). No-op without a cache."""
        if self.cache is None:
            return 0
        n = self.cache.flush()
        if n:
            log_event(
                "cache-flush",
                f"response cache flushed ({n} entr(ies))"
                + (f": {reason}" if reason else ""),
                level=logging.INFO,
                entries=n,
                reason=reason,
            )
        return n

    # -- balancing -------------------------------------------------------
    def ready_handles(self) -> List[ReplicaHandle]:
        return [
            h for h in self.replicas()
            if h.ready and not h.stopping and h.address is not None
        ]

    def pick(
        self,
        model: Optional[str] = None,
        length_bucket: Optional[int] = None,
    ) -> ReplicaHandle:
        """Least-outstanding-requests over the ready set; ties broken by
        lowest id (deterministic, and it keeps warm caches warm).

        With ``length_routing`` armed and a ``length_bucket`` hint
        (docs/SERVING.md "Data plane"), a deterministic bucket→replica
        affinity runs WITHIN the final candidate pool — after model
        narrowing and the canary split, never instead of them — so
        similar doc lengths land on the same replica and its device
        batches fill one bucket shape instead of padding to the longest
        straggler. Affinity is advisory: when the affinity replica is
        already ``affinity_slack`` requests above the pool's
        least-loaded, the pick spills to least-outstanding — a skewed
        length mixture must never starve or overload a replica. With
        the flag off, a single-replica pool, or no hint, the pick is
        bit-identical to plain least-outstanding.

        With ``model`` (multi-model serving), least-outstanding runs
        WITHIN the subset of ready replicas whose probe-learned resident
        set includes that model — a request never pays another model's
        cold load when a warm host exists. When NO ready replica hosts
        it yet, the full ready set is the pool: the chosen replica's
        residency manager cold-loads on arrival, and the next probe
        teaches the router the new placement.

        With ``canary_fraction > 0`` and an ACTIVE rollout
        (``canary_generation`` set by the controller), the ready set
        first splits into canary (replicas on that generation) vs
        baseline (everyone else), the accumulator picks the side, and
        least-outstanding runs WITHIN it — load stays balanced inside
        each generation while the cross-generation ratio stays exact.
        Outside a rollout there is never a split, no matter how
        heterogeneous the observed generations are."""
        ready = self.ready_handles()
        if not ready:
            raise NoReplicaAvailable(
                "no replica is ready (all warming, draining, or down)"
            )
        if model is not None:
            hosting = [h for h in ready if model in h.resident_models]
            if hosting:
                ready = hosting
        pool = ready
        target = self.canary_generation
        if self.canary_fraction > 0.0 and target is not None:
            canary = [h for h in ready if h.generation == target]
            baseline = [h for h in ready if h.generation != target]
            if canary and baseline:
                with self._split_lock:
                    self._split_acc += min(self.canary_fraction, 1.0)
                    take_canary = self._split_acc >= 1.0 - 1e-9
                    if take_canary:
                        self._split_acc -= 1.0
                pool = canary if take_canary else baseline
                if self.tel is not None:
                    self.tel.split_pick(take_canary)
        if (
            self.length_routing
            and length_bucket is not None
            and len(pool) > 1
        ):
            ordered = sorted(pool, key=lambda h: h.replica_id)
            target = ordered[length_bucket % len(ordered)]
            floor = min(h.outstanding for h in pool)
            if target.outstanding <= floor + self.affinity_slack:
                if self.tel is not None:
                    self.tel.affinity_pick(spilled=False)
                return target
            if self.tel is not None:
                self.tel.affinity_pick(spilled=True)
        return min(
            pool, key=lambda h: (h.outstanding, h.replica_id)
        )

    # -- forwarding --------------------------------------------------------
    def forward_parse(
        self,
        body: bytes,
        timeout_s: Optional[float] = None,
        request_id: Optional[str] = None,
        *,
        model: Optional[str] = None,
        explicit_model: bool = False,
        tenant: Optional[str] = None,
        length_bucket: Optional[int] = None,
        if_none_match: Optional[str] = None,
    ) -> Tuple[int, bytes, Optional[int], Optional[str]]:
        """Route one ``/v1/parse`` body: pick → forward → on socket
        failure mark the replica unready and retry on another. The retry
        budget is one attempt per distinct ready replica (+1): a body
        that fails everywhere means the fleet is down, not the request.
        Returns ``(status, payload, replica_id, etag)`` — ``etag`` is
        the replica's ``ETag`` response header (None when absent);
        ``request_id`` (when given) is forwarded in the
        ``X-SRT-Request-Id`` header so the replica's spans and response
        carry the router's id. ``length_bucket`` is the affinity hint
        ``pick`` consumes; ``if_none_match`` rides through to the
        replica so ITS conditional check can answer a body-less 304
        even when the router's own cache could not.

        ``model`` (multi-model serving) narrows ``pick`` to the replicas
        hosting it; when the client NAMED the model (``explicit_model``,
        via path or header) the forward goes to the normalized
        ``/v1/models/<name>/parse`` path, while an implicit default stays
        on the legacy ``/v1/parse`` wire shape. ``tenant`` is forwarded
        in ``X-SRT-Tenant`` — quota enforcement lives at the replica's
        admission edge, the router only carries the identity.

        Replica-level HTTP errors (429/504/...) are passed through
        verbatim — they are per-replica admission decisions the client
        must see, not routing failures. The exception is a replica's own
        503 ``draining``/``warming``: that replica is leaving (or has not
        yet joined) rotation — e.g. a scale-down SIGTERM landed between
        ``pick()`` and the forward — so the request retries on another
        replica (safe: ``/v1/parse`` is pure) instead of leaking a 5xx
        to a client other replicas could have served.
        """
        if self.draining:
            raise Draining("fleet is draining; not admitting requests")
        path = (
            f"/v1/models/{model}/parse"
            if model is not None and explicit_model else "/v1/parse"
        )
        extra_headers: Optional[Dict[str, str]] = None
        if tenant or if_none_match:
            extra_headers = {}
            if tenant:
                extra_headers[TENANT_HEADER] = tenant
            if if_none_match:
                extra_headers["If-None-Match"] = if_none_match
        with self._inflight_lock:
            self._inflight += 1
        try:
            attempts = 0
            max_attempts = max(len(self.ready_handles()), 1) + 1
            last_err: Optional[Exception] = None
            while attempts < max_attempts:
                attempts += 1
                # raises NoReplicaAvailable on empty ready set
                h = self.pick(model, length_bucket=length_bucket)
                addr = h.address
                if addr is None:
                    continue
                with h.lock:
                    h.outstanding += 1
                try:
                    status, payload, etag = self._post(
                        h, addr, path, body,
                        timeout_s or self.forward_timeout_s,
                        request_id=request_id,
                        extra_headers=extra_headers,
                    )
                    if status == 503 and self._replica_unavailable(payload):
                        # the replica itself says it can't take traffic
                        # (draining out of a scale-down, or still
                        # warming): out of rotation, retry elsewhere
                        last_err = OSError(
                            f"replica {h.replica_id} answered 503 "
                            "(draining/warming)"
                        )
                        self._mark_unready(h, "replica 503 draining/warming")
                        if self.tel is not None:
                            self.tel.retry(
                                h.replica_id, "Replica503", request_id
                            )
                        continue
                    return status, payload, h.replica_id, etag
                except OSError as e:
                    # crashed or restarting mid-request: out of rotation
                    # NOW; the prober re-adds it when /healthz recovers
                    last_err = e
                    self._mark_unready(h, f"forward failed: {e!r}")
                    if self.tel is not None:
                        self.tel.retry(
                            h.replica_id, type(e).__name__, request_id
                        )
                finally:
                    with h.lock:
                        h.outstanding -= 1
            raise NoReplicaAvailable(
                f"request failed on {attempts} replica attempt(s); "
                f"last error: {last_err!r}"
            )
        finally:
            with self._inflight_lock:
                self._inflight -= 1
                self._idle.notify_all()

    @staticmethod
    def _replica_unavailable(payload: bytes) -> bool:
        """True when a 503 body is the replica's own not-in-rotation
        signal (typed ``draining``/``warming`` from server.py) — the only
        replica statuses the router retries rather than passes through.
        Off the hot path: only 503 bodies are ever parsed."""
        try:
            err = json.loads(payload)
        except ValueError:
            return False
        return (
            isinstance(err, dict)
            and err.get("error") in ("draining", "warming")
        )

    @staticmethod
    def _post(
        h: ReplicaHandle, addr: Tuple[str, int], path: str, body: bytes,
        timeout_s: float, request_id: Optional[str] = None,
        extra_headers: Optional[Dict[str, str]] = None,
    ) -> Tuple[int, bytes]:
        """POST over a pooled keep-alive connection to the replica.

        A fresh TCP dial + replica-side handler-thread spawn per forward
        costs more than a small parse itself, so idle connections are
        pooled per handle. A pooled connection can have gone stale (the
        replica restarted, or closed it while idle) — and when one is,
        usually ALL of them are: a restart severs the whole pool at
        once. A stale failure therefore retries on the NEXT pooled
        connection (draining the severed pool one checkout at a time)
        and finally on a freshly dialed connection before the error
        propagates — safe to resend because ``/v1/parse`` is pure.
        Failures on a fresh dial surface as OSError (the contract
        ``forward_parse``'s replica-level retry loop keys on).

        Returns ``(status, payload, etag)`` — the replica's ``ETag``
        response header rides along so the edge can propagate it to the
        client without parsing the body.
        """
        headers = {"Content-Type": "application/json"}
        if request_id is not None:
            headers[REQUEST_ID_HEADER] = request_id
        if extra_headers:
            headers.update(extra_headers)
        conn = h.checkout_conn()
        while True:
            fresh = conn is None
            if fresh:
                conn = http.client.HTTPConnection(
                    addr[0], addr[1], timeout=timeout_s
                )
            try:
                conn.request("POST", path, body, headers)
                resp = conn.getresponse()
                payload = resp.read()
            except (OSError, http.client.HTTPException) as e:
                conn.close()
                if not fresh:
                    # try the next pooled conn; None → one fresh dial
                    conn = h.checkout_conn()
                    continue
                if not isinstance(e, OSError):
                    raise OSError(f"replica HTTP protocol error: {e!r}")
                raise
            if resp.will_close:
                conn.close()
            else:
                h.checkin_conn(conn)
            return resp.status, payload, resp.getheader("ETag")

    @staticmethod
    def _get_aux(
        h: ReplicaHandle, addr: Tuple[str, int], path: str, timeout_s: float
    ) -> Tuple[int, bytes]:
        """GET over a pooled control-plane connection. Probes and
        scrapes repeat every ``probe_interval_s`` forever — dialing
        fresh each pass adds up to more control-plane TCP churn than
        the data plane's, for sockets to the very same replicas. Same
        stale discipline as ``_post``: a stale pooled socket retries on
        the next pooled one, then one fresh dial; failures surface as
        OSError (what every caller already treats as "unhealthy")."""
        conn = h.checkout_aux_conn()
        while True:
            fresh = conn is None
            if fresh:
                conn = http.client.HTTPConnection(
                    addr[0], addr[1], timeout=timeout_s
                )
            try:
                conn.request("GET", path)
                resp = conn.getresponse()
                payload = resp.read()
            except (OSError, http.client.HTTPException) as e:
                conn.close()
                if not fresh:
                    conn = h.checkout_aux_conn()
                    continue
                if not isinstance(e, OSError):
                    raise OSError(f"replica HTTP protocol error: {e!r}")
                raise
            if resp.will_close:
                conn.close()
            else:
                h.checkin_aux_conn(conn)
            return resp.status, payload

    # -- placement (multi-model) -----------------------------------------
    def placement(self) -> Dict[int, List[str]]:
        """Probe-learned placement: replica_id → resident model names
        (every addressed replica, ready or not — the placement policy
        filters by its own ready list)."""
        return {
            h.replica_id: sorted(h.resident_models)
            for h in self.replicas()
        }

    def load_model(
        self, replica_id: int, model: str, timeout_s: Optional[float] = None
    ) -> Tuple[int, bytes]:
        """Apply one placement decision: POST ``/admin/models/load`` to
        the chosen replica (a fresh connection — admin traffic must not
        touch the hot-path pool). Raises ``NoReplicaAvailable`` when the
        replica has no address."""
        handle = next(
            (h for h in self.replicas() if h.replica_id == replica_id),
            None,
        )
        addr = handle.address if handle is not None else None
        if addr is None:
            raise NoReplicaAvailable(
                f"replica {replica_id} is not addressable"
            )
        body = json.dumps({"model": model}).encode("utf8")
        conn = http.client.HTTPConnection(
            addr[0], addr[1],
            timeout=timeout_s or self.forward_timeout_s,
        )
        try:
            conn.request(
                "POST", "/admin/models/load", body,
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            payload = resp.read()
        finally:
            conn.close()
        if resp.status == 200 and handle is not None:
            # teach the router immediately (the next probe would too,
            # but pick() should see the new host without the probe gap)
            with handle.lock:
                handle.resident_models.setdefault(model, {})
        return resp.status, payload

    # -- fleet metrics ----------------------------------------------------
    def scrape_replica_metrics(self) -> List[Dict[str, Any]]:
        """GET /metrics from every ready replica (best-effort: a replica
        that fails the scrape is skipped, not fatal).

        Scrapes run CONCURRENTLY, one thread per replica: a single hung
        replica bounds the whole pass at max(timeout), not sum — this is
        on the caller's thread for both client ``/metrics`` requests and
        the autoscaler tick, which must keep its cadence exactly when
        replicas are unhealthy and scaling decisions matter most."""
        handles = [h for h in self.ready_handles() if h.address is not None]
        results: List[Optional[Dict[str, Any]]] = [None] * len(handles)

        def scrape(i: int, h: ReplicaHandle) -> None:
            addr = h.address
            if addr is None:
                return
            try:
                status, raw = self._get_aux(
                    h, addr, "/metrics", self.probe_timeout_s
                )
                if status == 200:
                    snap = json.loads(raw)
                    if isinstance(snap, dict):
                        snap["replica_id"] = h.replica_id
                        results[i] = snap
            except (OSError, ValueError):
                pass

        if len(handles) == 1:  # no thread churn for the common small case
            scrape(0, handles[0])
        elif handles:
            threads = [
                threading.Thread(
                    target=scrape, args=(i, h), daemon=True,
                    name=f"scrape-replica-{h.replica_id}",
                )
                for i, h in enumerate(handles)
            ]
            for t in threads:
                t.start()
            deadline = time.monotonic() + self.probe_timeout_s + 1.0
            for t in threads:
                t.join(timeout=max(deadline - time.monotonic(), 0.0))
        # snapshot the results ONCE past the join deadline: a straggler
        # thread landing its payload after this point must not be merged
        # while also being counted as a failure — the ledger and the
        # aggregate have to tell the same story about who was present
        final = list(results)
        # a READY replica that failed its scrape is an observability
        # gap, not a routine miss: count it per replica (and in the
        # scrape_failures counter) so the aggregate says whose numbers
        # it is missing instead of silently shrinking the fleet view
        for h, snap in zip(handles, final):
            if snap is None:
                with self._scrape_lock:
                    self.scrape_failures[h.replica_id] = (
                        self.scrape_failures.get(h.replica_id, 0) + 1
                    )
                if self.tel is not None:
                    self.tel.scrape_failed(h.replica_id)
        return [snap for snap in final if snap is not None]

    def scrape_failure_stats(self) -> Dict[str, int]:
        with self._scrape_lock:
            return {str(k): v for k, v in sorted(self.scrape_failures.items())}

    def scrape_replica_exemplars(self) -> List[Dict[str, Any]]:
        """GET /admin/exemplars from every ready replica (best-effort,
        sequential — this is a diagnostic pull, not the hot path);
        each replica's payload is tagged with its id."""
        out: List[Dict[str, Any]] = []
        for h in self.ready_handles():
            addr = h.address
            if addr is None:
                continue
            try:
                status, raw = self._get_aux(
                    h, addr, "/admin/exemplars", self.probe_timeout_s
                )
                if status == 200:
                    payload = json.loads(raw)
                    if isinstance(payload, dict):
                        payload["replica_id"] = h.replica_id
                        out.append(payload)
            except (OSError, ValueError):
                continue
        return out

    def fleet_metrics(self) -> Dict[str, Any]:
        """The aggregated /metrics payload: per-replica snapshots merged
        into one fleet view + the router's own counters + cache stats +
        the per-replica scrape-failure ledger (a replica missing from
        the merge is NAMED, never silently dropped)."""
        from ...training.telemetry import merge_serving_snapshots

        merged = merge_serving_snapshots(self.scrape_replica_metrics())
        out: Dict[str, Any] = {"fleet": merged}
        out["replicas"] = [h.describe() for h in self.replicas()]
        out["scrape_failures"] = self.scrape_failure_stats()
        if self.registry is not None:
            # the placement view the policy (and `telemetry top`) reads:
            # which replicas host which models, per the last probe pass
            out["placement"] = {
                str(rid): models
                for rid, models in sorted(self.placement().items())
            }
            out["models"] = self.registry.names()
            out["default_model"] = self.registry.default_model
        if self.tel is not None:
            out["router"] = self.tel.snapshot()
            # the router process's own host truth (each replica's rides
            # inside its snapshot under fleet/replica entries)
            out["process"] = self.tel.hoststats.sample()
        if self.alerts is not None:
            out["alerts"] = self.alerts.summary()
        cache_stats = self.cache_stats()
        if cache_stats is not None:
            out["cache"] = cache_stats
        return out

    def prometheus_metrics(self) -> str:
        """The router's ``/metrics?format=prometheus`` body, assembled
        from three honest layers:

        * per-replica serving series labeled ``replica_id`` — counters
          and cumulative ``_bucket`` histograms are exact per replica,
          and a scraper may sum them across replicas exactly (the
          aggregation story Prometheus is built for);
        * fleet-level percentile gauges from the count-weighted
          ``merge_serving_snapshots`` view (``_worst`` alongside) —
          percentiles do NOT sum, so the merge rule is applied here and
          labeled as the fleet view, with the generation-split window
          p99s carrying a ``generation`` label (the canary signal);
        * the router's own counters/gauges under ``srt_router``,
          including ``srt_router_replica_scrape_failures_total`` per
          replica.
        """
        from ...training.prometheus import PromFamilies
        from ...training.telemetry import merge_serving_snapshots

        snaps = self.scrape_replica_metrics()
        merged = merge_serving_snapshots(snaps)
        fam = PromFamilies()
        for snap in snaps:
            labels = {"replica_id": snap.get("replica_id")}
            fam.add_snapshot(snap, prefix="srt_serving", labels=labels)
            gen = snap.get("generation")
            if gen is not None:
                fam.add("srt_serving_generation_id", "gauge", gen, labels)
        win = merged.get("slo_window")
        if isinstance(win, dict):
            for q in ("p50", "p95", "p99"):
                for suffix in ("", "_worst"):
                    fam.add(
                        "srt_fleet_request_latency_window_seconds",
                        "gauge",
                        win.get(f"request_latency_{q}{suffix}"),
                        {
                            "quantile": q.replace("p", "0."),
                            "aggregate": (
                                "worst_replica" if suffix
                                else "count_weighted_mean"
                            ),
                        },
                    )
        by_gen = merged.get("by_generation")
        if isinstance(by_gen, dict):
            for gen_key, sub in sorted(by_gen.items()):
                sub_win = (sub or {}).get("slo_window")
                if isinstance(sub_win, dict):
                    fam.add(
                        "srt_fleet_generation_request_latency_window_seconds",
                        "gauge",
                        sub_win.get("request_latency_p99"),
                        {"generation": gen_key, "quantile": "0.99"},
                    )
        by_model = merged.get("by_model")
        if isinstance(by_model, dict):
            # per-model fleet series (multi-model serving): counters sum
            # exactly across replicas so the model-labeled snapshot walk
            # is honest; window percentiles follow the same merge rule
            # as the fleet-level gauges, labeled per model — the
            # placement policy's breach signal and the per-class SLO
            # story both read these
            for model_name, sub in sorted(by_model.items()):
                if not isinstance(sub, dict):
                    continue
                fam.add_snapshot(
                    sub, prefix="srt_fleet_model",
                    labels={"model": model_name},
                )
                sub_win = sub.get("slo_window")
                if isinstance(sub_win, dict):
                    for q in ("p50", "p95", "p99"):
                        fam.add(
                            "srt_fleet_model_request_latency_window_seconds",
                            "gauge",
                            sub_win.get(f"request_latency_{q}"),
                            {
                                "model": model_name,
                                "quantile": q.replace("p", "0."),
                            },
                        )
        if self.tel is not None:
            tel_snap = self.tel.snapshot()
            if self.cache is not None:
                # the cache's own ledger below is the canonical
                # srt_router_cache_* source; dropping the telemetry twin
                # avoids a duplicate unlabeled series in the same family
                (tel_snap.get("counters") or {}).pop("cache_hits", None)
            fam.add_snapshot(tel_snap, prefix="srt_router")
            from ...training.hoststats import add_process_family

            # the ROUTER's own srt_process_* family, unlabeled; the
            # replicas' families live on their own scrape endpoints
            # (labeling them into this body would double-count RSS in
            # any sum() a scraper writes)
            add_process_family(fam, self.tel.hoststats.sample())
        for rid, n in self.scrape_failure_stats().items():
            fam.add(
                "srt_router_replica_scrape_failures_total", "counter", n,
                {"replica_id": rid},
            )
        if self.alerts is not None:
            self.alerts.add_prometheus(fam)
        cache_stats = self.cache_stats()
        if cache_stats is not None:
            # event tallies are counters (scrapers may rate() them —
            # the Zipfian hit-rate signal); entry/byte occupancy stays a
            # gauge (a level, not an event count)
            for key in (
                "cache_hits", "cache_misses", "cache_evictions",
                "cache_stale_invalidations", "cache_flushes",
                "cache_mixed_generation_bypasses",
                "cache_not_modified",
            ):
                fam.add(
                    f"srt_router_{key}_total", "counter",
                    cache_stats.get(key),
                )
            for key in ("cache_entries", "cache_bytes"):
                fam.add(f"srt_router_{key}", "gauge", cache_stats.get(key))
            # per-model cache ledger under its own family name — mixing
            # model-labeled samples into the unlabeled totals above
            # would double-count any sum() a scraper writes
            for model_name, ledger in sorted(
                (cache_stats.get("by_model") or {}).items()
            ):
                for key in (
                    "hits", "misses", "stale_invalidations",
                    "not_modified",
                ):
                    fam.add(
                        f"srt_router_model_cache_{key}_total", "counter",
                        ledger.get(key), {"model": model_name},
                    )
        fam.add("srt_fleet_replicas", "gauge", merged.get("replicas"))
        return fam.render()

    # -- drain -------------------------------------------------------------
    def begin_drain(self) -> None:
        self.draining = True

    def wait_inflight(self, timeout_s: float) -> bool:
        """Block until every in-flight forwarded request completed (the
        replicas behind them are still up — the fleet drain stops THEM
        only after the router is quiet). False on timeout."""
        deadline = time.monotonic() + float(timeout_s)
        with self._idle:
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._idle.wait(timeout=min(remaining, 0.1))
        return True


class RouterHTTPServer(ThreadingHTTPServer):
    """Handler threads do byte-level proxying only; all JSON work stays
    on the replicas (the router must not become the GIL bottleneck the
    fleet exists to remove)."""

    daemon_threads = True

    def __init__(self, addr: Tuple[str, int], router: Router) -> None:
        super().__init__(addr, _RouterHandler)
        self.router = router


class _RouterHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    # loopback is immune, but over a real link Nagle + delayed ACK can
    # add ~40ms between the header write and the body write
    disable_nagle_algorithm = True
    server: RouterHTTPServer

    def log_message(self, fmt: str, *args: Any) -> None:
        logger.debug("%s " + fmt, self.address_string(), *args)

    def _reply_bytes(
        self,
        status: int,
        body: bytes,
        request_id: Optional[str] = None,
        content_type: str = "application/json",
        etag: Optional[str] = None,
    ) -> None:
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        if request_id is not None:
            self.send_header(REQUEST_ID_HEADER, request_id)
        if etag is not None:
            self.send_header("ETag", etag)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        if body:
            self.wfile.write(body)

    def _reply_not_modified(
        self, etag: Optional[str], request_id: Optional[str] = None
    ) -> None:
        """Body-less 304 from the edge: the client's cached body is
        still exact for the fleet's converged generation."""
        self.send_response(304)
        if etag:
            self.send_header("ETag", etag)
        if request_id is not None:
            self.send_header(REQUEST_ID_HEADER, request_id)
        self.send_header("Content-Length", "0")
        self.end_headers()

    def _reply(
        self,
        status: int,
        payload: Dict[str, Any],
        request_id: Optional[str] = None,
    ) -> None:
        self._reply_bytes(
            status, json.dumps(payload).encode("utf8"), request_id
        )

    def _reply_error(
        self, err: ServingError, request_id: Optional[str] = None
    ) -> None:
        self._reply(
            err.http_status, {"error": err.code, "message": str(err)},
            request_id,
        )

    # -- GET ------------------------------------------------------------
    def do_GET(self) -> None:  # noqa: N802
        router = self.server.router
        parsed = urlparse(self.path)
        self.path = parsed.path
        if self.path == "/healthz":
            replicas = [h.describe() for h in router.replicas()]
            n_ready = sum(1 for r in replicas if r["ready"])
            payload: Dict[str, Any] = {"replicas": replicas}
            if router.tel is not None:
                # clock anchor for the cross-process trace collector —
                # same contract as the replica/trainer /healthz
                payload["anchor"] = router.tel.trace.anchor()
            if router.draining:
                self._reply(503, {"status": "draining", **payload})
            elif n_ready == 0:
                self._reply(
                    503,
                    {"status": "unavailable", "ready": 0, **payload},
                )
            else:
                self._reply(
                    200, {"status": "ok", "ready": n_ready, **payload}
                )
        elif self.path == "/metrics":
            fmt = (parse_qs(parsed.query).get("format") or [""])[0]
            if fmt == "prometheus":
                from ...training.prometheus import EXPOSITION_CONTENT_TYPE

                self._reply_bytes(
                    200,
                    router.prometheus_metrics().encode("utf8"),
                    content_type=EXPOSITION_CONTENT_TYPE,
                )
                return
            from ...training.telemetry import sanitize_json

            self._reply(200, sanitize_json(router.fleet_metrics()))
        elif self.path == "/trace":
            if router.tel is None:
                self._reply(200, {"trace": "disabled"})
                return
            from ...training.telemetry import sanitize_json

            payload = router.tel.trace.payload()
            payload["anchor"] = router.tel.trace.anchor()
            payload["role"] = "router"
            self._reply(200, sanitize_json(payload))
        elif self.path == "/admin/exemplars":
            from ...training.telemetry import sanitize_json

            self._reply(
                200,
                sanitize_json(
                    {"replicas": router.scrape_replica_exemplars()}
                ),
            )
        elif self.path == "/admin/alerts":
            if router.alerts is None:
                self._reply(200, {"alerts": "disabled"})
                return
            from ...training.telemetry import sanitize_json

            self._reply(
                200, sanitize_json({"alerts": router.alerts.states()})
            )
        else:
            self._reply(404, {"error": "not_found", "message": self.path})

    # -- POST -----------------------------------------------------------
    def do_POST(self) -> None:  # noqa: N802
        router = self.server.router
        try:
            length = int(self.headers.get("Content-Length") or 0)
        except ValueError:
            length = -1
        if length < 0 or length > MAX_BODY_BYTES:
            self.close_connection = True
            self._reply(
                400,
                {
                    "error": "bad_request",
                    "message": f"Content-Length must be 0..{MAX_BODY_BYTES}",
                },
            )
            return
        body = self.rfile.read(length)  # consume BEFORE any early reply
        registry = router.registry
        if self.path != "/v1/parse" and not (
            registry is not None and self.path.startswith("/v1/models/")
        ):
            # without a registry, /v1/models/... keeps the legacy 404
            # not_found — the typed unknown_model vocabulary only exists
            # once multi-model serving is configured
            self._reply(404, {"error": "not_found", "message": self.path})
            return
        # the router MINTS the fleet-wide request id (honoring a valid
        # client-supplied one): the same id is forwarded to the replica,
        # stamped on the router's route span, and echoed back in the
        # response header whatever the outcome — the one key that joins
        # client log, router trace, and replica trace
        request_id = clean_request_id(
            self.headers.get(REQUEST_ID_HEADER)
        ) or mint_request_id()
        if router.tel is not None:
            router.tel.request()
        # multi-model resolution at the edge (path > X-SRT-Model header
        # > manifest default): an unknown or malformed model name is a
        # typed 404 BEFORE any forward — no replica pays for it
        model_name: Optional[str] = None
        explicit_model = False
        tenant: Optional[str] = None
        if registry is not None:
            try:
                model_name, explicit_model = registry.resolve_model(
                    self.path, self.headers
                )
            except UnknownModel as e:
                if router.tel is not None:
                    router.tel.rejected(e, request_id)
                self._reply_error(e, request_id)
                return
            tenant = self.headers.get(TENANT_HEADER)
        if router.draining:
            err = Draining("fleet is draining; not admitting requests")
            if router.tel is not None:
                router.tel.rejected(err, request_id)
            self._reply_error(err, request_id)
            return
        # response cache: only when enabled does the router parse JSON —
        # the disabled path stays a pure byte proxy. Generation
        # discipline (ROADMAP 3b): a hit must match the one generation
        # every ready replica serves; while the fleet straddles
        # generations (rollout/promotion in flight) the cache is
        # bypassed entirely — a stale cached annotation must never
        # outlive a promotion
        # texts are parsed ONLY when a policy needs them (the response
        # cache, the length-affinity hint, or a conditional request to
        # validate) — otherwise the router stays a pure byte proxy
        inm = self.headers.get("If-None-Match")
        texts: Optional[List[str]] = None
        if router.cache is not None or router.length_routing:
            texts = self._texts_from(body)
        length_bucket = (
            _length_bucket_hint(texts)
            if router.length_routing and texts is not None else None
        )
        cache_key: Optional[bytes] = None
        cache_gen: Any = GENERATION_MIXED
        if router.cache is not None:
            # with a model resolved, the generation discipline runs per
            # model over the replicas hosting it — each model's entries
            # live under their own (model, generation, texts) key
            cache_gen = router.cache_generation(model_name)
            # parsing happens on BOTH generation verdicts: the bypass
            # counter must only tally requests the cache would actually
            # have served (a texts-free/malformed body skips the cache
            # on the converged path too, so it is not a "bypass"), and
            # the parse cost during a rollout window equals what the
            # converged path already pays per cacheable request
            if texts is not None:
                if cache_gen is GENERATION_MIXED:
                    # the bypass the generation discipline mandates —
                    # and a counted event, so a rollout window's
                    # cache-miss cost is attributable in /metrics
                    # rather than looking like an unexplained hit-rate
                    # dip. Counted ONLY when ready replicas actually
                    # straddle generations: an empty ready set also
                    # yields GENERATION_MIXED, but that request is
                    # about to be rejected no_replica — tallying it as
                    # a "rollout window" would inflate the counter
                    # during startup and outages with bypasses that
                    # never happened. The conditional check is bypassed
                    # on exactly the same verdict: no single generation
                    # can vouch for a client's cached body either, so
                    # If-None-Match is neither answered here nor
                    # forwarded (satellite of the PR 11 discipline).
                    if router.ready_handles():
                        router.count_cache_bypass()
                        inm = None
                else:
                    # converged fleet: the ETag is a pure function of
                    # (texts, model, generation), all known HERE — a
                    # matching If-None-Match is a body-less 304 with no
                    # forward at all, even when the cache never stored
                    # this body (the CLIENT holds it; the tag alone
                    # vouches for its freshness)
                    edge_etag = etag_for(
                        texts, model_name or "", cache_gen
                    )
                    if if_none_match_hit(inm, edge_etag):
                        router.cache.count_not_modified(model_name)
                        self._reply_not_modified(edge_etag, request_id)
                        return
                    cache_key = ResponseCache.key_for(
                        texts, model=model_name or ""
                    )
                    hit = router.cache.get(
                        cache_key, cache_gen, model=model_name
                    )
                    if hit is not None:
                        if router.tel is not None:
                            router.tel.cache_hit()
                        self._reply_bytes(
                            200, hit, request_id, etag=edge_etag
                        )
                        return
        t0 = time.perf_counter()
        span_t0 = router.tel.now() if router.tel is not None else None
        try:
            status, payload, replica_id, fwd_etag = router.forward_parse(
                body, request_id=request_id,
                model=model_name, explicit_model=explicit_model,
                tenant=tenant,
                length_bucket=length_bucket,
                if_none_match=inm,
            )
        except ServingError as e:
            if router.tel is not None:
                router.tel.rejected(e, request_id)
            self._reply_error(e, request_id)
            return
        if router.tel is not None:
            router.tel.routed(
                time.perf_counter() - t0,
                request_id=request_id,
                t0=span_t0,
                replica_id=replica_id,
            )
        if status == 200 and cache_key is not None:
            # stamp the entry with the serving replica's probe-learned
            # generation (a handle lookup, NOT a parse of the response
            # body — responses dwarf requests and the router must stay a
            # byte proxy on the hot path). Probe freshness caveat: a
            # swap landing between the last probe and this forward can
            # stamp a NEWER body with the old generation — the entry
            # then serves the new weights' annotations until the next
            # probe drops it, and the promotion flush clears any such
            # residue; it can never serve STALE (pre-promotion)
            # annotations, which is the contract that matters.
            serving = next(
                (
                    h for h in router.replicas()
                    if h.replica_id == replica_id
                ),
                None,
            )
            if serving is None:
                gen = cache_gen
            elif model_name is not None:
                # per-model stamp: the serving replica's probe-learned
                # generation FOR THIS MODEL (its fleet-level generation
                # may belong to a different resident model's rollout)
                gen = (
                    serving.resident_models.get(model_name) or {}
                ).get("generation")
            else:
                gen = serving.generation
            router.cache.put(cache_key, payload, gen)
        if status == 304:
            # a replica's own conditional check fired (the cache-off or
            # registry-less edge still honors If-None-Match end to end);
            # counted in the cache ledger when one exists — the 304
            # share must be one number however it was answered
            if router.cache is not None:
                router.cache.count_not_modified(model_name)
            self._reply_not_modified(fwd_etag, request_id)
            return
        self._reply_bytes(status, payload, request_id, etag=fwd_etag)

    @staticmethod
    def _texts_from(body: bytes) -> Optional[List[str]]:
        try:
            payload = json.loads(body or b"{}")
        except ValueError:
            return None
        texts = payload.get("texts") if isinstance(payload, dict) else None
        if isinstance(texts, list) and texts and all(
            isinstance(t, str) for t in texts
        ):
            return texts
        return None
