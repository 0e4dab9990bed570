"""Multi-replica serving fleet (spacy_ray_tpu/serving/fleet/): router
balancing/health/retry semantics against stub replicas (fast, no jax on
the hot path), response-cache behaviour, fleet /metrics aggregation,
supervisor crash-restart/scale with stub scripts, autoscaler hysteresis
under a fake clock, the disabled-telemetry zero-calls contract, and the
whole-fleet SIGTERM drain through the real ``serve-fleet`` CLI in a
subprocess (heavy crash-under-load variants are slow-marked).
"""

import json
import http.client
import os
import re
import signal
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from spacy_ray_tpu.serving.fleet import (
    AutoscalerPolicy,
    FleetObservation,
    NoReplicaAvailable,
    ReplicaHandle,
    ReplicaSupervisor,
    ResponseCache,
    Router,
    RouterHTTPServer,
    RouterTelemetry,
    observation_from_snapshots,
)
from spacy_ray_tpu.training.resilience import RetryPolicy, drain_events
from spacy_ray_tpu.training.telemetry import merge_serving_snapshots


# ----------------------------------------------------------------------
# Stub replicas: the `serve` HTTP surface without an engine (or jax)
# ----------------------------------------------------------------------


class _StubServer(ThreadingHTTPServer):
    daemon_threads = True


class _StubHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    # keep-alive + Nagle + delayed ACK stalls ~40ms between the header
    # and body writes (the real servers disable it too)
    disable_nagle_algorithm = True

    def log_message(self, fmt, *args):  # quiet
        pass

    def _reply(self, status, payload, etag=None):
        body = json.dumps(payload).encode("utf8")
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        if etag:
            self.send_header("ETag", etag)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):  # noqa: N802
        stub = self.server.stub
        if self.path == "/healthz":
            if stub.warming:
                self._reply(503, {"status": "warming"})
            else:
                payload = {"status": "ok", "swap_count": stub.swap_count}
                if stub.generation is not None:
                    payload["generation"] = stub.generation
                self._reply(200, payload)
        elif self.path == "/metrics":
            self._reply(200, stub.snapshot)
        else:
            self._reply(404, {"error": "not_found"})

    def do_POST(self):  # noqa: N802
        stub = self.server.stub
        length = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(length)
        with stub.lock:
            stub.parse_calls += 1
        if stub.draining:
            # what server.py answers mid-scale-down: a typed 503 the
            # router must retry elsewhere, not pass to the client
            self._reply(503, {"error": "draining",
                              "message": "draining; not admitting"})
            return
        if stub.etag is not None:
            # mimic the real replica's conditional-response path: a
            # matching If-None-Match validator gets a body-less 304
            inm = self.headers.get("If-None-Match")
            if inm is not None and inm in (stub.etag, "*"):
                self.send_response(304)
                self.send_header("ETag", stub.etag)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
        if stub.latency_s:
            time.sleep(stub.latency_s)
        batch = {"occupancy": 1}
        if stub.generation is not None:
            # the real server stamps every response with the serving
            # generation; the cache's put-time stamp reads it from here
            batch["generation"] = stub.generation
        self._reply(
            200, {"docs": [{"stub": stub.tag, "gen": stub.generation}],
                  "batch": batch},
            etag=stub.etag,
        )


class StubReplica:
    """One fake replica endpoint; behaviour is mutable mid-test
    (``warming`` flips readiness, ``close()`` simulates a crash)."""

    def __init__(self, *, warming=False, latency_s=0.0, snapshot=None,
                 tag="stub", generation=None, etag=None):
        self.warming = warming
        self.draining = False
        self.latency_s = latency_s
        self.generation = generation
        self.etag = etag
        self.swap_count = 0
        self.snapshot = snapshot or {"counters": {}, "gauges": {},
                                     "histograms": {}, "slo": {}}
        self.tag = tag
        self.parse_calls = 0
        self.lock = threading.Lock()
        self.httpd = _StubServer(("127.0.0.1", 0), _StubHandler)
        self.httpd.stub = self
        self.port = self.httpd.server_address[1]
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, kwargs={"poll_interval": 0.05},
            daemon=True,
        )
        self._thread.start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def make_handle(replica_id, stub, *, ready=True):
    h = ReplicaHandle(replica_id)
    h.set_address("127.0.0.1", stub.port)
    h.ready = ready
    return h


def _post(host, port, payload, timeout=30.0, path="/v1/parse"):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        body = json.dumps(payload).encode("utf8")
        conn.request("POST", path, body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def _post_raw(host, port, payload, headers=None, timeout=30.0,
              path="/v1/parse"):
    """Like _post but returns (status, body_bytes, response_headers)."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        body = json.dumps(payload).encode("utf8")
        hdrs = {"Content-Type": "application/json"}
        if headers:
            hdrs.update(headers)
        conn.request("POST", path, body, hdrs)
        resp = conn.getresponse()
        return resp.status, resp.read(), dict(resp.getheaders())
    finally:
        conn.close()


def _get(host, port, path, timeout=10.0):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def serve_router(router):
    """RouterHTTPServer on an ephemeral port; returns (httpd, host, port)."""
    httpd = RouterHTTPServer(("127.0.0.1", 0), router)
    threading.Thread(
        target=httpd.serve_forever, kwargs={"poll_interval": 0.05},
        daemon=True,
    ).start()
    host, port = httpd.server_address[:2]
    return httpd, str(host), int(port)


# ----------------------------------------------------------------------
# Router: balancing, health, retry, typed 503
# ----------------------------------------------------------------------


def test_pick_least_outstanding():
    stubs = [StubReplica(tag=f"s{i}") for i in range(3)]
    try:
        handles = [make_handle(i, s) for i, s in enumerate(stubs)]
        handles[0].outstanding = 2
        handles[1].outstanding = 0
        handles[2].outstanding = 1
        router = Router(lambda: handles)
        assert router.pick() is handles[1]
        handles[1].ready = False  # not ready -> out of rotation
        assert router.pick() is handles[2]
    finally:
        for s in stubs:
            s.close()


def test_no_replica_ready_is_typed_503():
    stub = StubReplica(warming=True)
    try:
        handle = make_handle(0, stub, ready=False)
        router = Router(lambda: [handle])
        with pytest.raises(NoReplicaAvailable):
            router.pick()
        httpd, host, port = serve_router(router)
        try:
            status, payload = _post(host, port, {"texts": ["x"]})
            assert status == 503 and payload["error"] == "no_replica"
            status, health = _get(host, port, "/healthz")
            assert status == 503 and health["status"] == "unavailable"
        finally:
            httpd.shutdown()
            httpd.server_close()
    finally:
        stub.close()


def test_probe_marks_warming_replica_unready_then_readds_it():
    """Automatic removal and re-add: a replica is out of rotation while
    its /healthz says warming (or it is unreachable) and returns the
    moment the probe sees 200 again."""
    stub = StubReplica(warming=True)
    try:
        handle = make_handle(0, stub, ready=False)
        router = Router(lambda: [handle])
        assert router.probe_once() == 0
        assert not handle.ready
        stub.warming = False  # warmup finished
        assert router.probe_once() == 1
        assert handle.ready
        stub.warming = True  # draining/unhealthy again
        assert router.probe_once() == 0
        assert not handle.ready
    finally:
        stub.close()


def test_replica_crash_midload_rerouted_zero_5xx():
    """Acceptance: a replica dying under load costs the in-flight retry,
    never a client-visible 5xx — the router marks it unready on the
    socket error and re-forwards to a surviving replica."""
    dead = StubReplica(tag="dead")
    alive = StubReplica(tag="alive")
    handles = [make_handle(0, dead), make_handle(1, alive)]
    tel = RouterTelemetry()
    router = Router(lambda: handles, telemetry=tel)
    dead.close()  # crash BEFORE the load: every pick of it fails at the socket
    httpd, host, port = serve_router(router)
    try:
        statuses = []
        for _ in range(5):
            status, payload = _post(host, port, {"texts": ["x"]})
            statuses.append(status)
            assert payload["docs"][0]["stub"] == "alive"
        assert statuses == [200] * 5, statuses
        assert not handles[0].ready  # removed from rotation on first failure
        snap = tel.snapshot()
        assert snap["counters"]["retries"] >= 1
        assert snap["counters"]["routed"] == 5
    finally:
        httpd.shutdown()
        httpd.server_close()
        alive.close()


def test_scale_down_503_draining_retried_not_passed_through():
    """A replica SIGTERM'd by a scale-down between pick() and the
    forward answers its own 503 draining — the router must retry on a
    remaining ready replica (the resend is safe, /v1/parse is pure),
    never leak that 5xx to a client other replicas could serve."""
    leaving = StubReplica(tag="leaving")
    leaving.draining = True  # drain flag flips before the router notices
    staying = StubReplica(tag="staying")
    handles = [make_handle(0, leaving), make_handle(1, staying)]
    tel = RouterTelemetry()
    router = Router(lambda: handles, telemetry=tel)
    httpd, host, port = serve_router(router)
    try:
        for _ in range(4):
            status, payload = _post(host, port, {"texts": ["x"]})
            assert status == 200
            assert payload["docs"][0]["stub"] == "staying"
        assert not handles[0].ready  # out of rotation after its first 503
        assert tel.snapshot()["counters"]["retries"] >= 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        leaving.close()
        staying.close()


def test_forward_when_all_replicas_dead_is_typed_not_5xx():
    stub = StubReplica()
    handle = make_handle(0, stub)
    router = Router(lambda: [handle])
    stub.close()
    with pytest.raises(NoReplicaAvailable):
        router.forward_parse(b'{"texts": ["x"]}')


# ----------------------------------------------------------------------
# Response cache at the router edge
# ----------------------------------------------------------------------


def test_response_cache_byte_cap_lru():
    cache = ResponseCache(100)
    k = ResponseCache.key_for
    cache.put(k(["a"]), b"x" * 40)
    cache.put(k(["b"]), b"y" * 40)
    assert cache.get(k(["a"])) == b"x" * 40  # refresh 'a' in LRU order
    cache.put(k(["c"]), b"z" * 40)  # cap 100: evicts LRU ('b')
    assert cache.get(k(["b"])) is None
    assert cache.get(k(["a"])) is not None
    assert cache.get(k(["c"])) is not None
    assert cache.evictions == 1
    # oversized bodies are refused, not cache-flushing
    cache.put(k(["big"]), b"w" * 1000)
    assert cache.get(k(["big"])) is None
    # the key is the text CONTENT, unambiguous across boundaries
    assert k(["ab"]) != k(["a", "b"])


def test_router_cache_serves_repeats_without_touching_replicas():
    stub = StubReplica(tag="origin")
    handle = make_handle(0, stub)
    tel = RouterTelemetry()
    router = Router(lambda: [handle], telemetry=tel,
                    cache_bytes=1 << 20)
    httpd, host, port = serve_router(router)
    try:
        body = {"texts": ["the cat runs", "a dog sleeps"]}
        status1, payload1 = _post(host, port, body)
        status2, payload2 = _post(host, port, body)
        assert (status1, status2) == (200, 200)
        assert payload1 == payload2
        assert stub.parse_calls == 1  # second answer came from the cache
        assert router.cache.stats()["cache_hits"] == 1
        assert tel.snapshot()["counters"]["cache_hits"] == 1
        # different texts -> miss -> forwarded
        status3, _ = _post(host, port, {"texts": ["different text"]})
        assert status3 == 200 and stub.parse_calls == 2
        # hit/miss counters are surfaced on the aggregated /metrics
        status, metrics = _get(host, port, "/metrics")
        assert status == 200
        assert metrics["cache"]["cache_hits"] == 1
        assert metrics["cache"]["cache_misses"] == 2
    finally:
        httpd.shutdown()
        httpd.server_close()
        stub.close()


def test_router_cache_off_by_default():
    stub = StubReplica()
    handle = make_handle(0, stub)
    router = Router(lambda: [handle])
    assert router.cache is None
    assert router.cache_stats() is None
    httpd, host, port = serve_router(router)
    try:
        body = {"texts": ["same text"]}
        _post(host, port, body)
        _post(host, port, body)
        assert stub.parse_calls == 2  # every request forwarded
    finally:
        httpd.shutdown()
        httpd.server_close()
        stub.close()


def test_fleet_config_arms_cache_by_default():
    """ROADMAP 3b's remaining half: the Router primitive stays opt-in
    (cache_bytes=0 — library callers decide), but the FLEET ships with
    the generation-correct cache armed; 0 still turns it off."""
    from spacy_ray_tpu.serving.fleet import FleetConfig

    assert FleetConfig(model_path="m").cache_mb > 0
    assert FleetConfig(model_path="m", cache_mb=0.0).cache_mb == 0.0


def test_router_prometheus_cache_counter_series():
    """The srt_router_cache_* exposition: event tallies as counters
    (rate()-able — the Zipfian hit-rate signal), occupancy as gauges,
    and exactly ONE unlabeled sample per family (the telemetry twin of
    cache_hits must not duplicate the ledger's series)."""
    stub = StubReplica(tag="origin")
    handle = make_handle(0, stub)
    tel = RouterTelemetry()
    router = Router(lambda: [handle], telemetry=tel, cache_bytes=1 << 20)
    httpd, host, port = serve_router(router)
    try:
        body = {"texts": ["the cat runs"]}
        _post(host, port, body)  # miss + store
        _post(host, port, body)  # hit
        text = router.prometheus_metrics()
        assert "# TYPE srt_router_cache_hits_total counter" in text
        assert "srt_router_cache_hits_total 1" in text
        assert "srt_router_cache_misses_total 1" in text
        assert "srt_router_cache_mixed_generation_bypasses_total 0" in text
        assert "# TYPE srt_router_cache_entries gauge" in text
        assert "srt_router_cache_entries 1" in text
        # no duplicate unlabeled sample in the hits family
        assert text.count("srt_router_cache_hits_total 1") == 1
        assert len(
            [ln for ln in text.splitlines()
             if ln.startswith("srt_router_cache_hits_total")]
        ) == 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        stub.close()


def test_response_cache_generation_stamp_and_stale_invalidation():
    """ROADMAP 3b: entries are stamped with the generation that computed
    them; a get expecting any other generation drops the entry (counted)
    instead of serving a stale annotation."""
    cache = ResponseCache(1 << 20)
    k = ResponseCache.key_for
    cache.put(k(["a"]), b"gen1-body", 1)
    assert cache.get(k(["a"]), 1) == b"gen1-body"
    # promotion happened: expecting gen 2 must never yield gen 1's body
    assert cache.get(k(["a"]), 2) is None
    assert cache.stats()["cache_stale_invalidations"] == 1
    assert len(cache) == 0  # dropped on access, bytes reclaimed
    # re-cached under the new generation
    cache.put(k(["a"]), b"gen2-body", 2)
    assert cache.get(k(["a"]), 2) == b"gen2-body"
    # put under a NEWER generation replaces a same-key stale entry
    cache.put(k(["a"]), b"gen3-body", 3)
    assert cache.get(k(["a"]), 3) == b"gen3-body"
    # flush clears everything and counts
    assert cache.flush() == 1
    assert cache.get(k(["a"]), 3) is None
    assert cache.stats()["cache_flushes"] == 1


def test_router_cache_promotion_never_serves_stale_annotation():
    """The regression the satellite demands: fill the cache on gen 1,
    hot-swap the fleet to gen 2 (healthz now reports it), and the SAME
    request body must come back with gen 2's annotations — never the
    cached gen-1 body."""
    stub = StubReplica(tag="origin", generation=1)
    handle = make_handle(0, stub)
    router = Router(lambda: [handle], cache_bytes=1 << 20)
    httpd, host, port = serve_router(router)
    try:
        router.probe_once()  # learn generation 1 from /healthz
        body = {"texts": ["the cat runs"]}
        status, payload = _post(host, port, body)
        assert status == 200 and payload["docs"][0]["gen"] == 1
        status, payload = _post(host, port, body)
        assert status == 200 and payload["docs"][0]["gen"] == 1
        assert stub.parse_calls == 1  # second answer was the cached body

        # promotion: the replica now serves generation 2
        stub.generation = 2
        stub.swap_count = 1
        router.probe_once()  # the router learns it exactly as live fleets do
        status, payload = _post(host, port, body)
        assert status == 200
        assert payload["docs"][0]["gen"] == 2, (
            "promotion served a stale cached annotation"
        )
        assert stub.parse_calls == 2  # forwarded, not cached
        assert router.cache.stats()["cache_stale_invalidations"] == 1
        # and the new generation's body caches normally again
        status, payload = _post(host, port, body)
        assert status == 200 and payload["docs"][0]["gen"] == 2
        assert stub.parse_calls == 2
    finally:
        httpd.shutdown()
        httpd.server_close()
        stub.close()


def test_router_cache_bypassed_while_generations_mixed():
    """Mid-rollout the ready set straddles generations: no single stamp
    can vouch for which replica a forward hits, so the cache is bypassed
    entirely (no hits, no stores) until the fleet converges."""
    from spacy_ray_tpu.serving.fleet.router import GENERATION_MIXED

    s1 = StubReplica(tag="old", generation=1)
    s2 = StubReplica(tag="new", generation=2)
    h1, h2 = make_handle(0, s1), make_handle(1, s2)
    router = Router(lambda: [h1, h2], cache_bytes=1 << 20)
    httpd, host, port = serve_router(router)
    try:
        router.probe_once()
        assert router.cache_generation() is GENERATION_MIXED
        body = {"texts": ["same text"]}
        _post(host, port, body)
        _post(host, port, body)
        assert s1.parse_calls + s2.parse_calls == 2  # nothing cached
        assert len(router.cache) == 0
        # each bypass is a COUNTED routing decision (srt_router_cache_
        # mixed_generation_bypasses_total), not a silent hit-rate dip
        assert router.cache_stats()["cache_mixed_generation_bypasses"] == 2
        # ...but an EMPTY ready set (startup/outage) is not a rollout
        # window: those requests reject no_replica without inflating
        # the counter
        h1.ready = h2.ready = False
        assert router.cache_generation() is GENERATION_MIXED
        status, _ = _post(host, port, body)
        assert status == 503
        assert router.cache_stats()["cache_mixed_generation_bypasses"] == 2
        h1.ready = h2.ready = True
        # ...and a body the cache could never serve (no texts) is not a
        # bypass either — the converged path skips the cache for it too
        status, _ = _post(host, port, {"not_texts": 1})
        assert status == 200
        assert router.cache_stats()["cache_mixed_generation_bypasses"] == 2
        # fleet converges on gen 2: caching resumes
        s1.generation = 2
        router.probe_once()
        assert router.cache_generation() == 2
        _post(host, port, body)
        _post(host, port, body)
        assert len(router.cache) == 1
        assert router.cache.stats()["cache_hits"] == 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        s1.close()
        s2.close()


# ----------------------------------------------------------------------
# Data plane (PR 20): conditional responses, length affinity, conn pools
# ----------------------------------------------------------------------


def test_router_edge_conditional_304_and_promotion_invalidates():
    """Tentpole (c): the edge answers a matching If-None-Match with a
    body-less 304 without forwarding; a generation promotion changes the
    tag, so held validators go stale exactly when the cache does."""
    from spacy_ray_tpu.serving.batcher import etag_for

    texts = ["the cat runs"]
    stub = StubReplica(tag="origin", generation=1,
                       etag=etag_for(texts, "", 1))
    handle = make_handle(0, stub)
    router = Router(lambda: [handle], cache_bytes=1 << 20)
    httpd, host, port = serve_router(router)
    try:
        router.probe_once()  # learn generation 1
        body = {"texts": texts}
        status, raw, headers = _post_raw(host, port, body)
        assert status == 200
        tag1 = headers["ETag"]
        assert tag1 == etag_for(texts, "", 1)

        # conditional revalidation: 304, no body, no forward, counted
        status, raw, headers = _post_raw(
            host, port, body, headers={"If-None-Match": tag1}
        )
        assert status == 304 and raw == b""
        assert headers["ETag"] == tag1
        assert stub.parse_calls == 1
        assert router.cache.stats()["cache_not_modified"] == 1
        # the 304 check runs BEFORE the cache lookup: hit stats clean
        assert router.cache.stats()["cache_hits"] == 0

        # an unconditional repeat is a cache hit and carries the tag
        status, raw, headers = _post_raw(host, port, body)
        assert status == 200 and headers["ETag"] == tag1
        assert stub.parse_calls == 1
        assert router.cache.stats()["cache_hits"] == 1

        # promotion: generation 2 invalidates every held validator
        stub.generation = 2
        stub.etag = etag_for(texts, "", 2)
        router.probe_once()
        status, raw, headers = _post_raw(
            host, port, body, headers={"If-None-Match": tag1}
        )
        assert status == 200, "stale validator must get the full body"
        tag2 = headers["ETag"]
        assert tag2 == etag_for(texts, "", 2) and tag2 != tag1
        assert stub.parse_calls == 2  # forwarded, not answered stale
        # ...and the NEW validator revalidates again
        status, raw, _ = _post_raw(
            host, port, body, headers={"If-None-Match": tag2}
        )
        assert status == 304 and stub.parse_calls == 2
        assert router.cache.stats()["cache_not_modified"] == 2
    finally:
        httpd.shutdown()
        httpd.server_close()
        stub.close()


def test_router_304_suppressed_while_generations_mixed():
    """Mid-rollout no single generation can vouch for a validator, so
    If-None-Match is neither answered at the edge nor forwarded — the
    client gets the full body, exactly like the cache bypass."""
    s1 = StubReplica(tag="old", generation=1, etag='"x"')
    s2 = StubReplica(tag="new", generation=2, etag='"x"')
    h1, h2 = make_handle(0, s1), make_handle(1, s2)
    router = Router(lambda: [h1, h2], cache_bytes=1 << 20)
    httpd, host, port = serve_router(router)
    try:
        router.probe_once()
        # "*" matches ANY tag — if the edge consulted it, or forwarded
        # it to the etag-honoring stub, this would come back 304
        status, raw, _ = _post_raw(
            host, port, {"texts": ["x"]}, headers={"If-None-Match": "*"}
        )
        assert status == 200
        assert json.loads(raw)["docs"]
        assert router.cache.stats().get("cache_not_modified", 0) == 0
        assert router.cache_stats()["cache_mixed_generation_bypasses"] == 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        s1.close()
        s2.close()


def test_router_replica_304_passthrough():
    """A replica-side 304 (cache off at the edge, or edge tag mismatch)
    passes through as a body-less 304 with the replica's ETag."""
    stub = StubReplica(tag="origin", etag='"abc"')
    handle = make_handle(0, stub)
    router = Router(lambda: [handle])  # no cache armed
    httpd, host, port = serve_router(router)
    try:
        status, raw, headers = _post_raw(
            host, port, {"texts": ["x"]}, headers={"If-None-Match": '"abc"'}
        )
        assert status == 304 and raw == b""
        assert headers["ETag"] == '"abc"'
        assert stub.parse_calls == 1  # the replica answered, cheaply
    finally:
        httpd.shutdown()
        httpd.server_close()
        stub.close()


def test_router_replica_304_passthrough_counted_with_cache_armed():
    stub = StubReplica(tag="origin", generation=1, etag='"abc"')
    handle = make_handle(0, stub)
    router = Router(lambda: [handle], cache_bytes=1 << 20)
    httpd, host, port = serve_router(router)
    try:
        router.probe_once()
        # '"abc"' is not the edge tag for these texts, so the edge
        # forwards the validator; the stub replies 304
        status, raw, headers = _post_raw(
            host, port, {"texts": ["x"]}, headers={"If-None-Match": '"abc"'}
        )
        assert status == 304 and raw == b""
        assert router.cache.stats()["cache_not_modified"] == 1
        assert router.cache.stats()["cache_misses"] == 1
        assert len(router.cache) == 0  # a 304 has no body to cache
    finally:
        httpd.shutdown()
        httpd.server_close()
        stub.close()


def _mk_handle(replica_id, port=19000):
    h = ReplicaHandle(replica_id)
    h.set_address("127.0.0.1", port + replica_id)
    h.ready = True
    return h


def test_length_routing_degenerate_cases_match_least_outstanding():
    """Satellite: flag off, no hint, single replica, or a model hosted
    by one replica — the pick is bit-identical to least-outstanding."""
    handles = [_mk_handle(i) for i in range(3)]
    handles[0].outstanding = 2
    handles[1].outstanding = 0
    handles[2].outstanding = 1

    off = Router(lambda: handles, length_routing=False)
    assert off.pick(length_bucket=3) is handles[1]  # flag off: hint inert

    tel = RouterTelemetry()
    on = Router(lambda: handles, length_routing=True, telemetry=tel)
    assert on.pick() is handles[1]  # no hint: plain least-outstanding
    single = [_mk_handle(0, port=19100)]
    on_single = Router(lambda: single, length_routing=True, telemetry=tel)
    assert on_single.pick(length_bucket=5) is single[0]
    # model narrowing to a single host: affinity never reroutes it
    handles[2].resident_models = {"m": {}}
    assert on.pick(model="m", length_bucket=0) is handles[2]
    counters = tel.snapshot()["counters"]
    assert counters["length_affinity_picks"] == 0
    assert counters["length_affinity_spills"] == 0


def test_length_affinity_bucket_mapping_and_spill():
    tel = RouterTelemetry()
    handles = [_mk_handle(i) for i in range(2)]
    router = Router(lambda: handles, length_routing=True, telemetry=tel)
    # equal load: bucket index maps deterministically over sorted ids
    assert router.pick(length_bucket=0) is handles[0]
    assert router.pick(length_bucket=1) is handles[1]
    assert router.pick(length_bucket=2) is handles[0]
    assert router.pick(length_bucket=3) is handles[1]
    assert tel.snapshot()["counters"]["length_affinity_picks"] == 4
    # the affinity target more than affinity_slack above the floor:
    # spill to least-outstanding — affinity is advisory, never a queue
    handles[0].outstanding = 3
    assert router.pick(length_bucket=0) is handles[1]
    counters = tel.snapshot()["counters"]
    assert counters["length_affinity_spills"] == 1


def test_length_affinity_skewed_mixture_no_starvation():
    """A single-bucket (fully skewed) stream must keep spilling to the
    other replica: load imbalance stays bounded by the slack."""
    tel = RouterTelemetry()
    handles = [_mk_handle(i) for i in range(2)]
    router = Router(lambda: handles, length_routing=True, telemetry=tel)
    picked = []
    for _ in range(12):  # every request hints the same bucket
        h = router.pick(length_bucket=1)
        h.outstanding += 1
        picked.append(h.replica_id)
    assert set(picked) == {0, 1}, "skewed mixture starved a replica"
    assert abs(handles[0].outstanding - handles[1].outstanding) <= \
        router.affinity_slack + 1
    counters = tel.snapshot()["counters"]
    assert counters["length_affinity_spills"] >= 1
    assert counters["length_affinity_picks"] >= 1


def _pad_for(lengths, batch=4):
    """Padded-token cost of dispatching `lengths` in arrival order in
    fixed chunks, each padded to its bucketed max — the same bucket
    table the serving engine pads to."""
    from spacy_ray_tpu.training.batcher import DEFAULT_LENGTH_BUCKETS

    pad = 0
    for i in range(0, len(lengths), batch):
        chunk = lengths[i:i + batch]
        t = next(
            (b for b in DEFAULT_LENGTH_BUCKETS if b >= max(chunk)),
            max(chunk),
        )
        pad += len(chunk) * t - sum(chunk)
    return pad


def test_length_affinity_cuts_pad_on_bimodal_mix():
    """Satellite: on a bimodal length mixture, bucket affinity segregates
    short from long docs per replica, and the padded-token cost of the
    resulting dispatch order is strictly below length-blind routing."""
    from spacy_ray_tpu.serving.fleet.router import _length_bucket_hint

    # 64 docs, half 5 words (bucket 16) and half 100 words (bucket 128),
    # interleaved so blind least-outstanding mixes them on both replicas
    pattern = [5, 5, 100, 5, 100, 100, 5, 100] * 8

    def route(use_affinity):
        handles = [_mk_handle(i, port=19200) for i in range(2)]
        router = Router(
            lambda: handles, length_routing=use_affinity,
            telemetry=RouterTelemetry(),
        )
        assigned = {0: [], 1: []}
        for n_words in pattern:
            hint = _length_bucket_hint(["w " * n_words]) \
                if use_affinity else None
            h = router.pick(length_bucket=hint)
            assigned[h.replica_id].append(n_words)
            h.outstanding += 1  # steady accumulation under load
        return assigned

    blind = route(False)
    affine = route(True)
    # no starvation: both replicas carry a fair share either way
    assert min(len(v) for v in affine.values()) >= len(pattern) // 4
    # segregation: each replica's stream is length-homogeneous
    assert all(len(set(v)) == 1 for v in affine.values())
    pad_blind = _pad_for(blind[0]) + _pad_for(blind[1])
    pad_affine = _pad_for(affine[0]) + _pad_for(affine[1])
    assert pad_affine < pad_blind, (
        f"affinity did not cut pad: {pad_affine} >= {pad_blind}"
    )


def test_stale_pooled_conns_drained_then_fresh_dial_no_5xx():
    """Satellite: a replica restart severs every pooled socket at once.
    The forward path must drain the stale pool — retrying each pooled
    conn — and land on a fresh dial, never surfacing a client 5xx."""
    live = StubReplica(tag="live")
    gone = StubReplica(tag="gone")
    gone.close()  # the old incarnation's port: dials now refused
    try:
        h = make_handle(0, live)
        for _ in range(3):  # the severed pool a restart leaves behind
            h.checkin_conn(
                http.client.HTTPConnection("127.0.0.1", gone.port,
                                           timeout=5.0)
            )
        router = Router(lambda: [h])
        httpd, host, port = serve_router(router)
        try:
            for _ in range(4):
                status, payload = _post(host, port, {"texts": ["x"]})
                assert status == 200
                assert payload["docs"][0]["stub"] == "live"
            assert live.parse_calls == 4
            assert h.ready  # the stale drain never marked it unhealthy
        finally:
            httpd.shutdown()
            httpd.server_close()
    finally:
        live.close()


def test_probe_and_scrape_survive_stale_aux_conns():
    """Control-plane pooling has the same stale discipline: a poisoned
    aux pool never fails a probe or a scrape against a live replica."""
    live = StubReplica(
        snapshot={"counters": {"requests": 7}, "gauges": {},
                  "histograms": {}, "slo": {}},
    )
    gone = StubReplica()
    gone.close()
    try:
        h = make_handle(0, live, ready=False)

        def poison():
            for _ in range(2):
                h.checkin_aux_conn(
                    http.client.HTTPConnection("127.0.0.1", gone.port,
                                               timeout=5.0)
                )

        router = Router(lambda: [h])
        poison()
        assert router.probe_once() == 1
        assert h.ready
        poison()
        snaps = router.scrape_replica_metrics()
        assert len(snaps) == 1
        assert snaps[0]["counters"]["requests"] == 7
    finally:
        live.close()


def test_controller_finish_flushes_cache_on_promote(tmp_path):
    """The live controller's promotion hook: a promote (generation
    change fleet-wide) flushes the response cache eagerly."""
    from spacy_ray_tpu.serving.live import LiveFleetController

    stub = StubReplica(generation=7)
    handle = make_handle(0, stub)
    router = Router(lambda: [handle], cache_bytes=1 << 20)
    router.cache.put(ResponseCache.key_for(["x"]), b"old", 6)
    ctl = LiveFleetController(tmp_path, router, canary_fraction=0.25)
    ctl.target = 7
    ctl.canary_ids = [0]
    ctl.phase = "canary"
    assert ctl._promote() == "promote"
    assert len(router.cache) == 0
    assert router.cache.stats()["cache_flushes"] == 1
    stub.close()


# ----------------------------------------------------------------------
# Fleet /metrics aggregation
# ----------------------------------------------------------------------


def _snap(n_requests, p99, queue_depth):
    return {
        "counters": {"requests": n_requests, "docs": 2 * n_requests},
        "gauges": {"queue_depth": queue_depth, "last_batch_occupancy": 4},
        "histograms": {
            "request_latency_seconds": {
                "count": n_requests, "sum": 0.1 * n_requests,
                "min": 0.01, "max": p99, "p50": p99 / 3, "p95": p99 / 2,
                "p99": p99,
            },
            "batch_occupancy": {
                "count": n_requests // 2, "sum": 2.0 * n_requests,
                "min": 1, "max": 8, "p50": 4, "p95": 6, "p99": 8,
            },
        },
        "slo": {"request_latency_p50": p99 / 3, "request_latency_p95": p99 / 2,
                "request_latency_p99": p99, "batch_occupancy_p50": 4},
    }


def test_merge_serving_snapshots_sums_counts_and_weights_percentiles():
    merged = merge_serving_snapshots([_snap(10, 0.3, 4), _snap(30, 0.1, 2)])
    assert merged["replicas"] == 2
    assert merged["counters"]["requests"] == 40
    assert merged["counters"]["docs"] == 80
    # gauges carry sum/max/mean — total queue depth is the sum
    assert merged["gauges"]["queue_depth"]["sum"] == 6
    assert merged["gauges"]["queue_depth"]["max"] == 4
    lat = merged["histograms"]["request_latency_seconds"]
    assert lat["count"] == 40
    assert lat["sum"] == pytest.approx(4.0)
    assert lat["min"] == 0.01 and lat["max"] == 0.3
    # p99: count-weighted mean plus the honest worst-replica bound
    assert lat["p99"] == pytest.approx((0.3 * 10 + 0.1 * 30) / 40)
    assert lat["p99_worst"] == 0.3
    assert merged["slo"]["request_latency_p99"] == pytest.approx(0.15)
    assert merged["slo"]["request_latency_p99_worst"] == 0.3
    # empty input stays well-formed
    empty = merge_serving_snapshots([])
    assert empty["replicas"] == 0 and empty["counters"] == {}


def test_router_metrics_endpoint_aggregates_replicas():
    """One scrape of the router returns the merged fleet view instead of
    requiring N per-replica scrapes."""
    stubs = [
        StubReplica(tag="a", snapshot=_snap(10, 0.3, 4)),
        StubReplica(tag="b", snapshot=_snap(30, 0.1, 2)),
    ]
    handles = [make_handle(i, s) for i, s in enumerate(stubs)]
    tel = RouterTelemetry()
    router = Router(lambda: handles, telemetry=tel)
    httpd, host, port = serve_router(router)
    try:
        status, metrics = _get(host, port, "/metrics")
        assert status == 200
        fleet = metrics["fleet"]
        assert fleet["replicas"] == 2
        assert fleet["counters"]["requests"] == 40
        assert fleet["slo"]["request_latency_p99_worst"] == 0.3
        assert {r["id"] for r in metrics["replicas"]} == {0, 1}
        assert "router" in metrics  # the router's own counters ride along
        # an unreachable replica is skipped, not fatal. close() only
        # stops the stub's LISTENER (its keep-alive handler threads live
        # on), so sever the router's pooled control-plane conns too —
        # that is what a real process death does to every socket
        stubs[0].close()
        handles[0].close_conns()
        handles[0].ready = True  # stale — scrape must tolerate it
        status, metrics = _get(host, port, "/metrics")
        assert status == 200 and metrics["fleet"]["replicas"] == 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        stubs[1].close()


# ----------------------------------------------------------------------
# Autoscaler: deterministic hysteresis under a fake clock
# ----------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


def _policy(clock, **kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("p99_target_s", 0.2)
    kw.setdefault("up_consecutive", 3)
    kw.setdefault("down_consecutive", 5)
    kw.setdefault("cooldown_s", 30.0)
    return AutoscalerPolicy(clock=clock, **kw)


def hot(ready):  # p99 breach
    return FleetObservation(ready=ready, p99_s=0.5, queue_depth=0.0,
                            occupancy=8.0)


def cold(ready):  # comfortably idle
    return FleetObservation(ready=ready, p99_s=0.01, queue_depth=0.0,
                            occupancy=1.0)


def test_autoscaler_scales_up_after_consecutive_breaches_only():
    clock = FakeClock()
    pol = _policy(clock)
    assert pol.observe(hot(1)) is None
    clock.advance(2)
    assert pol.observe(hot(1)) is None
    clock.advance(2)
    assert pol.observe(hot(1)) == 2  # third consecutive breach fires
    assert pol.decisions[-1]["direction"] == "up"


def test_autoscaler_oscillating_metric_never_flaps():
    clock = FakeClock()
    pol = _policy(clock)
    for _ in range(20):  # breach, recover, breach, recover ...
        assert pol.observe(hot(1)) is None
        clock.advance(2)
        assert pol.observe(cold(1)) is None
        clock.advance(2)
    assert pol.decisions == []


def test_autoscaler_cooldown_blocks_back_to_back_decisions():
    clock = FakeClock()
    pol = _policy(clock)
    for _ in range(3):
        decision = pol.observe(hot(1))
        clock.advance(1)
    assert decision == 2
    # still breaching, but inside the cooldown: hold
    for _ in range(10):
        assert pol.observe(hot(2)) is None
        clock.advance(1)
    clock.advance(30)  # cooldown expires; streak must rebuild from zero
    assert pol.observe(hot(2)) is None
    clock.advance(1)
    assert pol.observe(hot(2)) is None
    clock.advance(1)
    assert pol.observe(hot(2)) == 3


def test_autoscaler_scale_down_and_bounds():
    clock = FakeClock()
    pol = _policy(clock)
    # idle fleet of 3: down after 5 consecutive idle ticks
    for i in range(4):
        assert pol.observe(cold(3)) is None
        clock.advance(2)
    assert pol.observe(cold(3)) == 2
    assert pol.decisions[-1]["direction"] == "down"
    # at min_replicas: never below
    clock.advance(60)
    for _ in range(20):
        assert pol.observe(cold(1)) is None
        clock.advance(2)
    # at max_replicas: never above
    clock.advance(60)
    for _ in range(20):
        assert pol.observe(hot(4)) is None
        clock.advance(2)


def test_autoscaler_queue_pressure_triggers_without_p99():
    clock = FakeClock()
    pol = _policy(clock, queue_high=16.0)
    obs = FleetObservation(ready=2, p99_s=None, queue_depth=80.0)
    assert pol.observe(obs) is None
    clock.advance(2)
    assert pol.observe(obs) is None
    clock.advance(2)
    assert pol.observe(obs) == 3  # 40 queued docs/replica > 16


def test_autoscaler_decisions_emit_structured_events():
    drain_events()  # clear whatever other tests queued
    clock = FakeClock()
    pol = _policy(clock)
    for _ in range(3):
        pol.observe(hot(1))
        clock.advance(1)
    events = [e for e in drain_events() if e["event"] == "autoscale-up"]
    assert len(events) == 1
    assert events[0]["from"] == 1 and events[0]["to"] == 2
    assert events[0]["p99_s"] == 0.5


def test_observation_from_snapshots_worst_p99_total_queue():
    obs = observation_from_snapshots(
        [_snap(10, 0.3, 4), _snap(30, 0.1, 2)], ready=2
    )
    assert obs.ready == 2
    assert obs.p99_s == 0.3  # worst replica, not the mean
    assert obs.queue_depth == 6.0
    assert obs.occupancy == 4.0
    # no traffic yet -> no signal -> treated as no pressure
    empty = observation_from_snapshots([], ready=1)
    assert empty.p99_s is None and empty.queue_depth == 0.0


def test_autoscaler_rejects_bad_bounds():
    with pytest.raises(ValueError):
        AutoscalerPolicy(min_replicas=0)
    with pytest.raises(ValueError):
        AutoscalerPolicy(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError):
        AutoscalerPolicy(up_consecutive=0)


# ----------------------------------------------------------------------
# Disabled-telemetry contract: zero telemetry calls fleet-wide
# ----------------------------------------------------------------------


def test_fleet_disabled_telemetry_makes_zero_calls(monkeypatch):
    """The PR 3/4 contract at fleet scope: with telemetry off, neither
    the router path, the metrics merge, nor the autoscaler policy
    constructs ANYTHING from telemetry.py."""
    from spacy_ray_tpu.training import telemetry as telemetry_mod

    def _boom(*a, **k):
        raise AssertionError("telemetry constructed on the disabled path")

    monkeypatch.setattr(telemetry_mod.MetricsRegistry, "__init__", _boom)
    monkeypatch.setattr(telemetry_mod.TraceBuffer, "__init__", _boom)
    # PR 18: the router's host sampler lives inside RouterTelemetry —
    # telemetry off means zero /proc reads on the fleet edge too
    from spacy_ray_tpu.training import hoststats as hoststats_mod

    monkeypatch.setattr(hoststats_mod.ProcessSampler, "__init__", _boom)
    stub = StubReplica(snapshot=_snap(10, 0.3, 4))
    handle = make_handle(0, stub)
    router = Router(lambda: [handle], telemetry=None)
    httpd, host, port = serve_router(router)
    try:
        router.probe_once()
        status, _ = _post(host, port, {"texts": ["x"]})
        assert status == 200
        status, metrics = _get(host, port, "/metrics")
        assert status == 200
        assert "router" not in metrics  # no router-telemetry block
        assert metrics["fleet"]["counters"]["requests"] == 10
        clock = FakeClock()
        pol = _policy(clock)
        for _ in range(3):
            pol.observe(hot(1))
            clock.advance(1)
        assert pol.decisions  # decisions still logged, zero telemetry
    finally:
        httpd.shutdown()
        httpd.server_close()
        stub.close()


# ----------------------------------------------------------------------
# Replica supervisor: banner parsing, crash restart w/ backoff, scaling
# ----------------------------------------------------------------------

# stub replica processes: a banner, then the chosen behaviour — no jax,
# so supervisor semantics are tested in milliseconds
SLEEP_SCRIPT = (
    "import signal, sys, time\n"
    "signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))\n"
    "print('serving on http://127.0.0.1:59000', flush=True)\n"
    "while True:\n"
    "    time.sleep(0.05)\n"
)
CRASH_SCRIPT = (
    "print('serving on http://127.0.0.1:59001', flush=True)\n"
    "raise SystemExit(1)\n"
)


def _wait_until(cond, timeout=15.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def _script_cmd(script):
    return lambda replica_id: [sys.executable, "-c", script]


def _fast_supervisor(script, **kw):
    kw.setdefault("restart_policy",
                  RetryPolicy(max_retries=10, base_delay=0.0, jitter=0.0))
    kw.setdefault("monitor_poll_s", 0.02)
    kw.setdefault("grace_s", 10.0)
    return ReplicaSupervisor(_script_cmd(script), **kw)


def test_supervisor_parses_banner_and_stops_clean():
    sup = _fast_supervisor(SLEEP_SCRIPT)
    [handle] = sup.start(1)
    try:
        assert _wait_until(lambda: handle.address is not None)
        assert handle.address == ("127.0.0.1", 59000)
        assert handle.alive
    finally:
        assert sup.stop_all() is True  # SIGTERM -> the script exits 0


def test_supervisor_restarts_crashes_then_gives_up():
    sup = _fast_supervisor(CRASH_SCRIPT, max_restarts_per_replica=2)
    [handle] = sup.start(1)
    try:
        # 1 initial run + 2 restarts, then the cap: restarts counts crashes
        assert _wait_until(lambda: handle.restarts >= 3)
        time.sleep(0.3)  # give a buggy supervisor time to over-restart
        assert handle.restarts == 3  # capped: left down, not crash-looping
        assert not handle.alive
        # terminal: the gave-up handle leaves the ACTIVE set, so the
        # autoscaler's scale_to sees the honest count and can spawn a
        # replacement instead of silently no-op'ing against a zombie
        assert _wait_until(lambda: sup.replica_count == 0)
        sup.scale_to(1)
        assert sup.replica_count == 1
        [fresh] = sup.handles()
        assert fresh.replica_id != handle.replica_id  # own restart budget
        assert fresh.slot == handle.slot  # ...but the freed slot recycles
    finally:
        sup.stop_all()


def test_supervisor_scale_up_and_down():
    sup = _fast_supervisor(SLEEP_SCRIPT)
    sup.start(1)
    try:
        assert sup.replica_count == 1
        sup.scale_to(3)
        assert sup.replica_count == 3
        assert _wait_until(
            lambda: all(h.address for h in sup.handles())
        )
        sup.scale_to(1)
        # the shrink SIGTERMs the two youngest; handles leave the set as
        # each process exits
        assert _wait_until(lambda: sup.replica_count == 1)
        [survivor] = sup.handles()
        assert survivor.replica_id == 0  # oldest survives
    finally:
        sup.stop_all()


def test_scale_cycle_reuses_freed_slot():
    """Device/core masks and base-port offsets key on the replica's
    SLOT, which recycles: after scale-down/scale-up cycles two live
    replicas must never share a mask while another sits idle (the
    co-scheduling collapse the pinning exists to prevent)."""
    seen = []

    def build(slot):
        seen.append(slot)
        return [sys.executable, "-c", SLEEP_SCRIPT]

    sup = ReplicaSupervisor(build, monitor_poll_s=0.02, grace_s=10.0)
    sup.start(2)  # replicas 0,1 -> slots 0,1
    try:
        assert _wait_until(lambda: all(h.address for h in sup.handles()))
        sup.scale_to(1)  # stops the youngest (id 1, slot 1)
        assert _wait_until(lambda: sup.replica_count == 1)
        sup.scale_to(2)  # new replica id 2 must REUSE freed slot 1
        assert _wait_until(lambda: sup.replica_count == 2)
        assert seen == [0, 1, 1]
        assert sorted(h.slot for h in sup.handles()) == [0, 1]
        assert sorted(h.replica_id for h in sup.handles()) == [0, 2]
    finally:
        sup.stop_all()


def test_supervisor_no_restart_while_draining():
    sup = _fast_supervisor(SLEEP_SCRIPT)
    [handle] = sup.start(1)
    try:
        assert _wait_until(lambda: handle.address is not None)
        sup.begin_drain()
        handle.proc.kill()  # crash during drain
        handle.proc.wait(timeout=10)
        time.sleep(0.3)
        assert handle.restarts == 0  # not restarted: the fleet is exiting
    finally:
        sup.stop_all()


# ----------------------------------------------------------------------
# Whole-fleet SIGTERM drain: the real serve-fleet CLI in a subprocess
# ----------------------------------------------------------------------

SERVE_CFG = """
[nlp]
lang = "en"
pipeline = ["tok2vec","tagger"]

[components]

[components.tok2vec]
factory = "tok2vec"

[components.tok2vec.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = 64
depth = 2
embed_size = 512

[components.tagger]
factory = "tagger"

[components.tagger.model]
@architectures = "spacy.Tagger.v2"

[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 64
"""

FLEET_BANNER_RE = re.compile(r"fleet serving on http://([^:\s]+):(\d+)")


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    from spacy_ray_tpu.config import Config
    from spacy_ray_tpu.pipeline.language import Pipeline
    from spacy_ray_tpu.util import synth_corpus

    nlp = Pipeline.from_config(Config.from_str(SERVE_CFG))
    egs = synth_corpus(64, "tagger", seed=0)
    nlp.initialize(lambda: iter(egs), seed=0)
    out = tmp_path_factory.mktemp("fleet_model") / "model"
    nlp.to_disk(out)
    return out


def _spawn_fleet(model_dir, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.Popen(
        [
            sys.executable, "-m", "spacy_ray_tpu", "serve-fleet",
            str(model_dir),
            "--device", "cpu", "--port", "0", "--replicas", "2",
            "--max-replicas", "2", "--max-batch", "4",
            "--max-doc-len", "16", "--probe-interval-s", "0.2",
            *extra,
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=env,
    )


def _read_fleet_banner(proc, lines, timeout=60.0):
    addr = [None]

    def reader():
        for line in proc.stdout:
            lines.append(line)
            m = FLEET_BANNER_RE.search(line)
            if m and addr[0] is None:
                addr[0] = (m.group(1), int(m.group(2)))

    threading.Thread(target=reader, daemon=True).start()
    deadline = time.monotonic() + timeout
    while addr[0] is None and time.monotonic() < deadline:
        if proc.poll() is not None:
            pytest.fail(f"serve-fleet exited early:\n{''.join(lines)}")
        time.sleep(0.1)
    assert addr[0] is not None, f"no fleet banner:\n{''.join(lines)}"
    return addr[0]


def _wait_fleet_ready(host, port, lines, want_ready=2, timeout=240.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            status, health = _get(host, port, "/healthz", timeout=10.0)
        except OSError:
            time.sleep(0.2)
            continue
        if status == 200 and health["ready"] >= want_ready:
            return health
        if status != 200:
            assert health["status"] in ("unavailable", "warming"), health
        time.sleep(0.3)
    pytest.fail(f"fleet never became ready:\n{''.join(lines)}")


def test_fleet_sigterm_drains_all_replicas_and_exits_zero(model_dir, tmp_path):
    """Acceptance (drain + observability, one real fleet spawn): a
    request with a known ``X-SRT-Request-Id`` through the real fleet
    (router + 2 replica subprocesses) returns the SAME id in the
    response header, and ``collect_fleet_traces`` against the router
    produces ONE merged Perfetto file whose spans for that id appear on
    the router track AND a replica track; the Prometheus endpoints
    answer valid exposition; then SIGTERM — router stops admitting, the
    in-flight request (held in a replica's 600ms coalescing window)
    completes with 200, every replica drains and exits 0, the fleet
    exits 0."""
    proc = _spawn_fleet(model_dir, "--max-wait-ms", "600")
    lines = []
    try:
        host, port = _read_fleet_banner(proc, lines)
        health = _wait_fleet_ready(host, port, lines)
        assert health["ready"] == 2, health
        assert all(r["pid"] for r in health["replicas"])

        # the aggregated metrics endpoint answers through the real stack
        status, metrics = _get(host, port, "/metrics", timeout=30.0)
        assert status == 200 and metrics["fleet"]["replicas"] == 2

        # a request served end-to-end through router -> replica
        status, payload = _post(host, port, {"texts": ["the cat runs"]},
                                timeout=60.0)
        assert status == 200 and payload["docs"][0]["tags"]

        # ---- distributed tracing acceptance ----
        # client-supplied request id: echoed back by the router, and the
        # SAME id must land in the router's and the serving replica's
        # trace buffers
        rid = "acceptance-req-1"
        conn = http.client.HTTPConnection(host, port, timeout=60.0)
        try:
            conn.request(
                "POST", "/v1/parse",
                json.dumps({"texts": ["a dog runs"]}).encode("utf8"),
                {"Content-Type": "application/json",
                 "X-SRT-Request-Id": rid},
            )
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 200
            assert resp.getheader("X-SRT-Request-Id") == rid
        finally:
            conn.close()

        from spacy_ray_tpu.serving.tracecollect import (
            collect_fleet_traces,
            write_merged_trace,
        )

        merged = collect_fleet_traces([f"http://{host}:{port}"])
        # router + 2 replicas on the one merged timeline
        assert len(merged["otherData"]["merged_from"]) == 3, (
            merged["otherData"]
        )
        out = write_merged_trace(merged, tmp_path / "fleet_trace.json")
        reloaded = json.loads(out.read_text(encoding="utf8"))
        pids_with_rid = {
            e["pid"]
            for e in reloaded["traceEvents"]
            if e.get("ph") == "X"
            and (e.get("args") or {}).get("request_id") == rid
        }
        rid_in_batches = {
            e["pid"]
            for e in reloaded["traceEvents"]
            if e.get("ph") == "X"
            and rid in ((e.get("args") or {}).get("request_ids") or [])
        }
        # the request's spans cross a process boundary: the router's
        # `route` span and the replica's `request`/`serve_batch` spans
        # live on DIFFERENT tracks of the one file
        assert len(pids_with_rid | rid_in_batches) >= 2, (
            pids_with_rid, rid_in_batches
        )

        # ---- Prometheus exposition through the real listeners ----
        conn = http.client.HTTPConnection(host, port, timeout=30.0)
        try:
            conn.request("GET", "/metrics?format=prometheus")
            resp = conn.getresponse()
            text = resp.read().decode("utf8")
            assert resp.status == 200
            assert resp.getheader("Content-Type").startswith("text/plain")
        finally:
            conn.close()
        assert re.search(
            r'^srt_serving_requests_total\{replica_id="\d+"\} \d+$',
            text, re.M,
        ), text[:800]
        assert "_bucket{" in text

        # in-flight request: sits in a replica's 600ms coalescing window
        inflight = {}

        def one_request():
            try:
                inflight["result"] = _post(
                    host, port, {"texts": ["a dog sleeps"]}, timeout=90.0
                )
            except Exception as e:  # noqa: BLE001 — recorded for the assert
                inflight["result"] = e

        t = threading.Thread(target=one_request)
        t.start()
        time.sleep(0.25)  # admitted by a replica, not yet dispatched
        proc.send_signal(signal.SIGTERM)

        t.join(timeout=90.0)
        result = inflight.get("result")
        assert isinstance(result, tuple) and result[0] == 200, (
            f"in-flight request not completed through the fleet drain: "
            f"{result!r}\n{''.join(lines)}"
        )

        # new admissions after SIGTERM: typed 503 or (post-exit) refused
        try:
            status, payload = _post(host, port, {"texts": ["another"]},
                                    timeout=10.0)
            assert status == 503, (status, payload)
        except OSError:
            pass  # listener already closed — also a rejection

        rc = proc.wait(timeout=120.0)
        assert rc == 0, f"fleet drain exit {rc}:\n{''.join(lines)}"
        assert any("fleet drained; exiting 0" in l for l in lines), lines
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10.0)


@pytest.mark.slow
def test_fleet_replica_crash_under_real_load_recovers(model_dir):
    """Heavy variant: SIGKILL one real replica while clients hammer the
    router — every client request must come back 200 (the router retry
    absorbs the crash) and the supervisor must restart the replica back
    to ready."""
    proc = _spawn_fleet(model_dir, "--max-wait-ms", "2")
    lines = []
    try:
        host, port = _read_fleet_banner(proc, lines)
        health = _wait_fleet_ready(host, port, lines)
        victim_pid = health["replicas"][0]["pid"]

        stop_at = time.monotonic() + 8.0
        failures = []
        ok = [0]

        def client():
            while time.monotonic() < stop_at:
                try:
                    status, _ = _post(host, port, {"texts": ["the cat"]},
                                      timeout=60.0)
                except OSError as e:
                    failures.append(repr(e))
                    continue
                if status == 200:
                    ok[0] += 1
                elif status >= 500 and status != 503:
                    failures.append(status)

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(1.0)
        os.kill(victim_pid, signal.SIGKILL)  # replica crash under load
        for t in threads:
            t.join(timeout=120.0)
        assert not failures, f"client-visible failures: {failures[:10]}"
        assert ok[0] > 0
        # the supervisor restarts the victim back to ready
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            status, health = _get(host, port, "/healthz", timeout=10.0)
            if status == 200 and health["ready"] == 2:
                break
            time.sleep(0.5)
        assert health["ready"] == 2, health
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60.0)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10.0)


def test_fleet_sigkill_replica_writes_incident_postmortem(
    model_dir, tmp_path
):
    """ISSUE 12 acceptance: SIGKILL a replica mid-load in a REAL
    2-replica fleet with the flight recorder armed. The dead process
    cannot dump anything — the forensics must come from the black box
    it persisted while alive plus what the supervisor/router knew. The
    crash bundle must hold the exit signal, the stderr tail, the
    effective config, the generation, and a NON-EMPTY pre-crash span
    ring, and `telemetry postmortem` must render it."""
    inc_dir = tmp_path / "incidents"
    proc = _spawn_fleet(
        model_dir, "--max-wait-ms", "2",
        "--incidents-dir", str(inc_dir),
        "--observe-interval-s", "0.25",
    )
    lines = []
    try:
        host, port = _read_fleet_banner(proc, lines)
        health = _wait_fleet_ready(host, port, lines)
        victim = health["replicas"][0]
        victim_pid, victim_slot = victim["pid"], victim["slot"]
        blackbox = inc_dir / "blackbox" / f"slot-{victim_slot}.json"

        # load: clients hammer the fleet so the victim's span ring and
        # black box fill with real request/batch spans
        stop_at = [time.monotonic() + 30.0]
        failures = []

        def client():
            while time.monotonic() < stop_at[0]:
                try:
                    status, _ = _post(host, port, {"texts": ["the cat"]},
                                      timeout=60.0)
                except OSError:
                    continue
                if status >= 500 and status != 503:
                    failures.append(status)

        threads = [threading.Thread(target=client) for _ in range(2)]
        for t in threads:
            t.start()
        # the black box must exist and contain post-traffic spans before
        # the kill — that is the artifact the postmortem depends on
        assert _wait_until(
            lambda: blackbox.is_file()
            and (json.loads(blackbox.read_text()).get("trace") or {}).get(
                "traceEvents"
            ),
            timeout=60.0,
        ), "replica black box never persisted a span ring"
        time.sleep(0.6)  # one more persist cycle under load

        os.kill(victim_pid, signal.SIGKILL)

        def bundle_dirs():
            if not inc_dir.is_dir():
                return []
            return [
                d for d in inc_dir.iterdir()
                if d.is_dir() and "crash-replica" in d.name
            ]

        assert _wait_until(lambda: bundle_dirs(), timeout=60.0), (
            "no crash bundle appeared"
        )
        stop_at[0] = 0.0  # stop the load
        for t in threads:
            t.join(timeout=60.0)
        assert not failures, failures[:5]

        bundle = bundle_dirs()[0]
        inc = json.loads((bundle / "incident.json").read_text())
        assert inc["exit_code"] == -9
        assert inc["exit_signal"] == "SIGKILL"
        assert "generation" in inc  # disk model: honestly null
        assert any("serve" in str(a) for a in inc["argv"])
        tail = (bundle / "stderr.txt").read_text()
        assert "serving on http://" in tail  # the replica's last words
        # the pre-crash span ring, recovered from the black box
        flights = list(bundle.glob("flight-*.json"))
        assert flights, "no flight payload in the crash bundle"
        replica_flights = [
            json.loads(f.read_text()) for f in flights
            if "replica" in f.name
        ]
        assert replica_flights
        spans = [
            e
            for fl in replica_flights
            for e in (fl.get("trace") or {}).get("traceEvents") or []
            if e.get("ph") == "X"
        ]
        assert spans, "pre-crash span ring is empty"
        # router health knowledge rode along
        assert (bundle / "health.json").is_file()

        # and the postmortem renders, with the kill signal named
        from spacy_ray_tpu.incidents import render_postmortem

        report = render_postmortem(bundle)
        assert "killed by SIGKILL" in report
        assert "timeline" in report
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=120.0)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10.0)
