"""Online serving subsystem (spacy_ray_tpu/serving/): dynamic batcher
admission/coalescing/deadlines, engine warmup + dispatch correctness
under concurrent load (responses == single-request predict_docs, and
occupancy > 1 proves coalescing), HTTP API surface, SIGTERM graceful
drain in a real subprocess, and the telemetry-disabled zero-calls
contract."""

import json
import http.client
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from spacy_ray_tpu.config import Config
from spacy_ray_tpu.pipeline.language import Pipeline
from spacy_ray_tpu.serving import (
    DeadlineExceeded,
    Draining,
    DynamicBatcher,
    InferenceEngine,
    QueueFull,
    RequestTooLarge,
    Server,
    ServeRequest,
    ServingTelemetry,
    warmup_buckets,
)
from spacy_ray_tpu.serving.batcher import (
    cache_key_for,
    etag_for,
    if_none_match_hit,
)
from spacy_ray_tpu.util import synth_corpus

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

TEXTS = [
    "the cat runs fast today",
    "a dog sleeps near the door",
    "birds sing loudly in the morning",
    "the quick brown fox jumps high",
    "a lazy dog naps all afternoon",
    "rain falls softly on the roof",
    "the child reads an old book",
    "wind moves through the tall trees",
    "a boat drifts down the river",
    "stars shine over the quiet town",
]


def _post(host, port, payload, timeout=30.0):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        body = json.dumps(payload).encode("utf8")
        conn.request(
            "POST", "/v1/parse", body, {"Content-Type": "application/json"}
        )
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
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


# ----------------------------------------------------------------------
# DynamicBatcher: admission, coalescing, deadlines, drain
# ----------------------------------------------------------------------


def _req(n_docs=1, deadline_in=10.0, clock=time.monotonic):
    now = clock()
    return ServeRequest(["d"] * n_docs, deadline=now + deadline_in, enqueued_at=now)


def test_batcher_rejects_when_queue_full():
    b = DynamicBatcher(max_queue_docs=4, max_batch_docs=4, max_wait_s=0.0)
    b.submit(_req(3))
    with pytest.raises(QueueFull):
        b.submit(_req(2))
    assert b.rejected_full == 1
    b.submit(_req(1))  # exactly at the limit is admitted


def test_batcher_rejects_oversized_request():
    b = DynamicBatcher(max_queue_docs=8, max_batch_docs=4, max_wait_s=0.0)
    with pytest.raises(RequestTooLarge):
        b.submit(_req(5))


def test_batcher_drain_rejects_new_but_serves_queued():
    b = DynamicBatcher(max_queue_docs=8, max_batch_docs=4, max_wait_s=0.0)
    queued = _req(2)
    b.submit(queued)
    b.begin_drain()
    with pytest.raises(Draining):
        b.submit(_req(1))
    assert b.rejected_draining == 1
    batch = b.next_batch()
    assert batch == [queued]  # admitted-before-drain still dispatches


def test_batcher_expired_request_completed_not_dispatched():
    b = DynamicBatcher(max_queue_docs=8, max_batch_docs=4, max_wait_s=0.0)
    dead = _req(1, deadline_in=-0.5)  # already past its deadline
    live = _req(1)
    b.submit(dead)
    b.submit(live)
    batch = b.next_batch()
    assert batch == [live]
    assert dead.done and isinstance(dead.error, DeadlineExceeded)
    assert b.expired == 1


def test_batcher_coalesces_within_window():
    b = DynamicBatcher(max_queue_docs=32, max_batch_docs=8, max_wait_s=0.25)
    for _ in range(3):
        b.submit(_req(2))
    t0 = time.monotonic()
    batch = b.next_batch()
    assert sum(len(r.docs) for r in batch) == 6
    # full-batch early exit: 6 < 8 so the window ran — but queued
    # requests were all there at entry, so the first pop got them
    assert time.monotonic() - t0 < 5.0


def test_batcher_full_batch_skips_wait():
    b = DynamicBatcher(max_queue_docs=32, max_batch_docs=4, max_wait_s=30.0)
    b.submit(_req(2))
    b.submit(_req(2))
    t0 = time.monotonic()
    batch = b.next_batch()
    # a full batch must dispatch immediately, not sit out max_wait_s
    assert time.monotonic() - t0 < 5.0
    assert sum(len(r.docs) for r in batch) == 4


def test_batcher_close_unblocks_dispatcher():
    b = DynamicBatcher(max_queue_docs=8, max_batch_docs=4, max_wait_s=0.0)
    got = []
    th = threading.Thread(target=lambda: got.append(b.next_batch()))
    th.start()
    b.close()
    th.join(timeout=5.0)
    assert got == [None]


# ----------------------------------------------------------------------
# Continuous admission: slot-based assembly, no window timer
# ----------------------------------------------------------------------


class _FakeClock:
    """Deterministic clock; also counts reads so a test can assert a
    code path never even consulted time."""

    def __init__(self, t=100.0):
        self.t = t
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return self.t

    def advance(self, dt):
        self.t += dt


def test_continuous_dispatches_partial_batch_immediately():
    """The defining property: a partial batch dispatches the instant the
    dispatch thread asks, never sitting out the window timer (here an
    absurd 30 s — a timer-waiting implementation would hang)."""
    b = DynamicBatcher(
        max_queue_docs=32, max_batch_docs=8, max_wait_s=30.0,
        mode="continuous",
    )
    b.submit(_req(2))
    t0 = time.monotonic()
    batch = b.next_batch()
    assert time.monotonic() - t0 < 5.0
    assert sum(len(r.docs) for r in batch) == 2  # partial, not full


def test_continuous_no_queued_request_waits_for_inflight_drain():
    """Property (the tentpole's contract): while a batch is IN FLIGHT
    (popped, not completed), newly queued requests are admitted into the
    next dispatch's free slots the moment the dispatch thread returns —
    with a fake clock, zero simulated time passes between the in-flight
    handoff and the follow-up's admission into a batch."""
    clock = _FakeClock()
    b = DynamicBatcher(
        max_queue_docs=32, max_batch_docs=4, max_wait_s=30.0,
        mode="continuous", clock=clock,
    )
    b.submit(_req(4, clock=clock))
    inflight = b.next_batch()  # handed to the "device", never completed
    assert sum(len(r.docs) for r in inflight) == 4
    # requests landing while the device runs
    late = [_req(1, clock=clock), _req(2, clock=clock)]
    for r in late:
        b.submit(r)
    batch = b.next_batch()  # dispatch thread frees up
    assert batch == late  # all queued slots filled at once
    assert all(r.started_at == clock.t for r in late)
    # the in-flight batch was NEVER completed — its drain was not a
    # precondition for admitting the follow-ups
    assert not any(r.done for r in inflight)


def test_continuous_typed_rejects_still_fire():
    b = DynamicBatcher(
        max_queue_docs=4, max_batch_docs=4, max_wait_s=0.0,
        mode="continuous",
    )
    with pytest.raises(RequestTooLarge):
        b.submit(_req(5))
    b.submit(_req(3))
    with pytest.raises(QueueFull):
        b.submit(_req(2))
    assert b.rejected_full == 1
    b.begin_drain()
    with pytest.raises(Draining):
        b.submit(_req(1))
    assert b.rejected_draining == 1


def test_continuous_deadlines_honored_before_and_after_admission():
    """An already-expired request never reaches a batch (pre-admission
    check), and a request whose deadline passes while it sits queued
    behind an in-flight batch gets its typed DeadlineExceeded at the
    next slot-fill, not a response nobody reads."""
    clock = _FakeClock()
    b = DynamicBatcher(
        max_queue_docs=32, max_batch_docs=4, max_wait_s=0.0,
        mode="continuous", clock=clock,
    )
    dead = _req(1, deadline_in=-0.5, clock=clock)
    live = _req(1, deadline_in=10.0, clock=clock)
    b.submit(dead)
    b.submit(live)
    assert b.next_batch() == [live]
    assert dead.done and isinstance(dead.error, DeadlineExceeded)
    # queued during an in-flight batch, expires before the slots free up
    expiring = _req(1, deadline_in=1.0, clock=clock)
    survivor = _req(1, deadline_in=60.0, clock=clock)
    b.submit(expiring)
    b.submit(survivor)
    clock.advance(5.0)  # the in-flight batch ran long
    assert b.next_batch() == [survivor]
    assert expiring.done and isinstance(expiring.error, DeadlineExceeded)
    assert b.expired == 2


def test_continuous_drain_completes_requests_mid_assembly():
    """begin_drain with requests queued (mid-assembly for the next
    dispatch): admission closes, but every queued request still
    dispatches — the graceful-drain contract is mode-independent."""
    b = DynamicBatcher(
        max_queue_docs=32, max_batch_docs=2, max_wait_s=0.0,
        mode="continuous",
    )
    queued = [_req(2), _req(2), _req(1)]
    for r in queued:
        b.submit(r)
    b.begin_drain()
    with pytest.raises(Draining):
        b.submit(_req(1))
    served = []
    while True:
        batch = b.next_batch(poll_s=0.01)
        served.extend(batch)
        if len(served) == len(queued):
            break
    assert served == queued  # FIFO, whole requests, none dropped
    b.close()
    assert b.next_batch() is None


def test_batcher_rejects_unknown_mode():
    with pytest.raises(ValueError):
        DynamicBatcher(mode="adaptive")


def test_warmup_bucket_grid_uses_trainer_tables():
    grid = warmup_buckets(8, 32, (16, 32, 64))
    assert grid == [(1, 16), (1, 32), (2, 16), (2, 32), (4, 16), (4, 32),
                    (8, 16), (8, 32)]
    # caps round up through the trainer's own bucket functions
    assert (16, 64) in warmup_buckets(12, 40, (16, 32, 64))


def test_warmup_bucket_grid_is_complete_beyond_table_top():
    """The warmed-shape contract: EVERY length bucket admission can
    produce for a doc of 1..max_doc_len tokens is in the grid —
    including the overflow region beyond the table's top bucket, where
    bucket_length emits multiples of the top. A hole here is a live
    mid-traffic XLA compile."""
    from spacy_ray_tpu.training.batcher import bucket_length

    buckets = (16, 32, 64)
    grid_ts = {t for _, t in warmup_buckets(2, 1500, buckets)}
    admissible = {bucket_length(n, buckets) for n in range(1, 1501)}
    assert admissible <= grid_ts, sorted(admissible - grid_ts)


# ----------------------------------------------------------------------
# Engine + HTTP server
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def serve_nlp():
    nlp = Pipeline.from_config(Config.from_str(SERVE_CFG))
    egs = synth_corpus(64, "tagger", seed=0)
    nlp.initialize(lambda: iter(egs), seed=0)
    return nlp


@pytest.fixture(scope="module")
def served(serve_nlp):
    tel = ServingTelemetry()
    engine = InferenceEngine(
        serve_nlp,
        max_batch_docs=8,
        max_wait_s=0.05,
        max_queue_docs=64,
        timeout_s=30.0,
        max_doc_len=32,
        telemetry=tel,
    )
    engine.start(warmup=True)
    server = Server(engine, "127.0.0.1", 0, telemetry=tel)
    host, port = server.start()
    yield engine, tel, host, port
    server.request_shutdown()
    assert server.wait() == 0


def test_concurrent_load_matches_single_request_and_coalesces(
    served, serve_nlp
):
    """Acceptance: N>=8 concurrent clients through the HTTP API; every
    response equals the single-request predict_docs output, and recorded
    occupancy > 1 proves the requests shared device batches instead of
    running as N serial batches of 1."""
    engine, tel, host, port = served
    n_clients = 10
    barrier = threading.Barrier(n_clients)
    results = [None] * n_clients

    def client(i):
        barrier.wait()  # release all clients at once: coalescing window
        results[i] = _post(host, port, {"texts": [TEXTS[i % len(TEXTS)]]})

    threads = [threading.Thread(target=client, args=(i,)) for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)

    assert all(r is not None and r[0] == 200 for r in results), results
    occupancies = [r[1]["batch"]["occupancy"] for r in results]
    assert max(occupancies) > 1, (
        f"no coalescing happened: occupancies {occupancies}"
    )
    # single-request ground truth, computed after the load so the jit
    # cache is only ever touched by one thread at a time
    for i, (status, payload) in enumerate(results):
        doc = serve_nlp.tokenizer(TEXTS[i % len(TEXTS)])
        serve_nlp.predict_docs([doc])
        [got] = payload["docs"]
        assert got["tokens"] == doc.words
        assert got["tags"] == doc.tags, (
            f"batched response diverged from single-request predict for "
            f"text {i}: {got['tags']} != {doc.tags}"
        )
    # the telemetry surface saw the same story
    occ_hist = tel.registry.histogram("batch_occupancy").snapshot()
    assert occ_hist["max"] > 1
    snap = tel.snapshot()
    assert snap["slo"]["request_latency_p50"] is not None
    assert snap["counters"]["requests"] >= n_clients


def test_healthz_and_metrics_endpoints(served):
    _, _, host, port = served
    status, health = _get(host, port, "/healthz")
    assert status == 200
    assert health["status"] == "ok"
    assert health["pipeline"] == ["tok2vec", "tagger"]
    assert health["warmed_buckets"] == 8  # (1|2|4|8) x (16|32)
    # honest labels: the default admission discipline and the precision
    # the device actually runs (CPU auto resolves the overlay OFF)
    assert health["batching"] == "continuous"
    assert health["precision"] == "f32"
    assert "precision_label" in health
    status, metrics = _get(host, port, "/metrics")
    assert status == 200
    assert {"counters", "gauges", "histograms", "slo"} <= set(metrics)
    assert {"request_latency_p50", "request_latency_p95",
            "request_latency_p99"} <= set(metrics["slo"])
    status, _ = _get(host, port, "/nope")
    assert status == 404


def test_bad_requests_get_400(served):
    _, _, host, port = served
    assert _post(host, port, {"texts": []})[0] == 400
    assert _post(host, port, {"texts": "not a list"})[0] == 400
    assert _post(host, port, {})[0] == 400
    conn = http.client.HTTPConnection(host, port, timeout=10)
    try:
        conn.request("POST", "/v1/parse", b"{not json",
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 400
    finally:
        conn.close()


def test_too_long_doc_rejected_413(served):
    _, _, host, port = served
    status, payload = _post(
        host, port, {"texts": ["word " * 60]}  # 60 tokens > max_doc_len 32
    )
    assert status == 413
    assert payload["error"] == "request_too_large"


# ----------------------------------------------------------------------
# Conditional responses (ETag / If-None-Match) and pad accounting
# ----------------------------------------------------------------------


def _post_raw(host, port, payload, headers=None, timeout=30.0):
    """Like _post but returns (status, body_bytes, response_headers)."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        body = json.dumps(payload).encode("utf8")
        hdrs = {"Content-Type": "application/json"}
        if headers:
            hdrs.update(headers)
        conn.request("POST", "/v1/parse", body, hdrs)
        resp = conn.getresponse()
        return resp.status, resp.read(), dict(resp.getheaders())
    finally:
        conn.close()


def test_etag_helpers_are_model_and_generation_sensitive():
    texts = ["the cat runs fast today"]
    base = etag_for(texts, "", 0)
    assert base.startswith('"') and base.endswith('"')
    # same inputs -> same tag; any axis change -> different tag
    assert etag_for(texts, "", 0) == base
    assert etag_for(texts, "alpha", 0) != base
    assert etag_for(texts, "", 1) != base
    assert etag_for(["other text"], "", 0) != base
    # the text digest is the shared response-cache key
    assert cache_key_for(texts, "alpha") != cache_key_for(texts, "beta")
    # If-None-Match grammar: exact, list, weak validator, wildcard
    assert if_none_match_hit(base, base)
    assert if_none_match_hit(f'"nope", {base}', base)
    assert if_none_match_hit(f"W/{base}", base)
    assert if_none_match_hit("*", base)
    assert not if_none_match_hit(None, base)
    assert not if_none_match_hit('"nope"', base)


def test_replica_etag_and_conditional_304(served):
    """A replica stamps a strong ETag on every 200; a matching
    If-None-Match is answered 304 with no body at admission (before the
    queue), counted as not_modified; a stale tag gets the full 200."""
    engine, tel, host, port = served
    texts = [TEXTS[0]]
    status, body, headers = _post_raw(host, port, {"texts": texts})
    assert status == 200
    etag = headers["ETag"]
    assert etag == etag_for(texts, "", engine.serving_generation)

    before = tel.snapshot()["counters"].get("not_modified", 0)
    status, body, headers = _post_raw(
        host, port, {"texts": texts}, headers={"If-None-Match": etag}
    )
    assert status == 304
    assert body == b""
    assert headers["ETag"] == etag
    assert tel.snapshot()["counters"]["not_modified"] == before + 1

    # a non-matching validator is ignored: full response, no 304 count
    status, body, _ = _post_raw(
        host, port, {"texts": texts}, headers={"If-None-Match": '"stale"'}
    )
    assert status == 200
    assert json.loads(body)["docs"]
    assert tel.snapshot()["counters"]["not_modified"] == before + 1


def test_pad_and_real_token_counters_on_dispatch(served):
    """Every dispatched batch contributes real_tokens (sum of doc lens)
    and pad_tokens (B*T - real) to the serving counters."""
    engine, tel, host, port = served
    before = tel.snapshot()["counters"]
    status, _ = _post(host, port, {"texts": ["a short doc"]})
    assert status == 200
    after = tel.snapshot()["counters"]
    assert after["real_tokens"] > before.get("real_tokens", 0)
    # a 3-token doc in a padded bucket always pads something
    assert after["pad_tokens"] > before.get("pad_tokens", 0)


def test_batch_span_pad_accounting_unit():
    tel = ServingTelemetry()
    with tel.batch_span(2, 4, 32, real_tokens=50):
        pass
    counters = tel.snapshot()["counters"]
    assert counters["real_tokens"] == 50
    assert counters["pad_tokens"] == 4 * 32 - 50
    # real_tokens omitted -> pad counters stay at zero
    tel2 = ServingTelemetry()
    with tel2.batch_span(1, 1, 16):
        pass
    c2 = tel2.snapshot()["counters"]
    assert c2["real_tokens"] == 0
    assert c2["pad_tokens"] == 0


def test_request_deadline_maps_to_504(serve_nlp):
    """A deadline shorter than the coalescing window must come back as a
    typed 504, not hang: the dispatcher completes expired requests
    before spending device time. Window mode pinned explicitly — it is
    the window that guarantees the deadline passes pre-dispatch
    (continuous admission would race the 1 ms deadline)."""
    engine = InferenceEngine(
        serve_nlp,
        max_batch_docs=4,
        max_wait_s=0.3,
        batching="window",
        timeout_s=30.0,
        max_doc_len=32,
    )
    engine.start(warmup=False)  # shapes already compiled by other tests
    server = Server(engine, "127.0.0.1", 0)
    host, port = server.start()
    try:
        status, payload = _post(
            host, port, {"texts": ["the cat"], "timeout_ms": 1}
        )
        assert status == 504
        assert payload["error"] == "deadline_exceeded"
    finally:
        server.request_shutdown()
        assert server.wait() == 0


def test_draining_server_rejects_with_503(serve_nlp):
    engine = InferenceEngine(
        serve_nlp, max_batch_docs=4, max_wait_s=0.0, max_doc_len=32
    )
    engine.start(warmup=False)
    server = Server(engine, "127.0.0.1", 0)
    host, port = server.start()
    server.httpd.draining = True  # gate flips before the drain completes
    status, payload = _post(host, port, {"texts": ["the cat"]})
    assert status == 503
    assert payload["error"] == "draining"
    status, health = _get(host, port, "/healthz")
    assert status == 503 and health["status"] == "draining"
    server.request_shutdown()
    assert server.wait() == 0


def test_healthz_warming_until_warmup_completes(serve_nlp):
    """Readiness gating regression: a replica whose bucket warmup sweep
    has not completed must answer 503 (not 200) on /healthz — and 503
    "warming" on /v1/parse — so a router never sends traffic into a
    mid-warmup compile. Only after the sweep does it report 200 ok."""
    engine = InferenceEngine(
        serve_nlp, max_batch_docs=4, max_wait_s=0.0, max_doc_len=32
    )
    server = Server(engine, "127.0.0.1", 0)
    host, port = server.start()
    try:
        # listener up, engine NOT started: the pre-ready window
        status, health = _get(host, port, "/healthz")
        assert status == 503 and health["status"] == "warming", health
        status, payload = _post(host, port, {"texts": ["the cat runs"]})
        assert status == 503 and payload["error"] == "warming", payload
        # warmup completes (shapes already compiled by the module's other
        # tests, so warmup=False stands in for the finished sweep)
        engine.start(warmup=False)
        status, health = _get(host, port, "/healthz")
        assert status == 200 and health["status"] == "ok"
        status, payload = _post(host, port, {"texts": ["the cat runs"]})
        assert status == 200 and payload["docs"][0]["tags"]
    finally:
        server.request_shutdown()
        assert server.wait() == 0


def test_disabled_telemetry_makes_zero_calls(serve_nlp, monkeypatch):
    """The training loop's contract, enforced for serving too: with no
    ServingTelemetry, the engine/server construct NOTHING from
    telemetry.py — any registry/trace construction raises."""
    from spacy_ray_tpu.training import telemetry as telemetry_mod

    def _boom(*a, **k):
        raise AssertionError("telemetry constructed on the disabled path")

    monkeypatch.setattr(telemetry_mod.MetricsRegistry, "__init__", _boom)
    monkeypatch.setattr(telemetry_mod.TraceBuffer, "__init__", _boom)
    # PR 12's diagnosis layer obeys the same contract: no telemetry =
    # no alert engine, no flight recorder, no observer ticker
    from spacy_ray_tpu import alerting as alerting_mod
    from spacy_ray_tpu import incidents as incidents_mod

    monkeypatch.setattr(alerting_mod.AlertEngine, "__init__", _boom)
    monkeypatch.setattr(incidents_mod.FlightRecorder, "__init__", _boom)
    # PR 18: no facade = no host sampler, no /proc reads, and the
    # /metrics reply carries no srt_process_* family
    from spacy_ray_tpu.training import hoststats as hoststats_mod

    monkeypatch.setattr(hoststats_mod.ProcessSampler, "__init__", _boom)
    engine = InferenceEngine(
        serve_nlp, max_batch_docs=4, max_wait_s=0.01, max_doc_len=32
    )
    engine.start(warmup=False)
    server = Server(engine, "127.0.0.1", 0)
    host, port = server.start()
    try:
        status, payload = _post(host, port, {"texts": [TEXTS[0]]})
        assert status == 200
        assert payload["docs"][0]["tags"]
        status, metrics = _get(host, port, "/metrics")
        # generation/swap_count are engine state, not telemetry — they
        # ride along even with the telemetry surface disabled
        assert status == 200 and metrics == {
            "telemetry": "disabled", "generation": None, "swap_count": 0,
        }
        # the distributed-tracing surfaces make zero telemetry calls on
        # the disabled path too: request IDs are protocol (the header
        # still echoes), but spans/exemplars/trace buffers must not
        # exist — the monkeypatched constructors above prove it by
        # raising on any construction
        import http.client as _hc

        conn = _hc.HTTPConnection(host, port, timeout=30.0)
        try:
            conn.request(
                "POST", "/v1/parse",
                json.dumps({"texts": [TEXTS[0]]}).encode("utf8"),
                {"Content-Type": "application/json",
                 "X-SRT-Request-Id": "client-id-42"},
            )
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 200
            assert resp.getheader("X-SRT-Request-Id") == "client-id-42"
        finally:
            conn.close()
        status, exemplars = _get(host, port, "/admin/exemplars")
        assert status == 200 and exemplars == {"exemplars": "disabled"}
        status, trace = _get(host, port, "/trace")
        assert status == 200 and trace == {"trace": "disabled"}
        conn = _hc.HTTPConnection(host, port, timeout=30.0)
        try:
            conn.request("GET", "/metrics?format=prometheus")
            resp = conn.getresponse()
            body = resp.read().decode("utf8")
            assert resp.status == 200
            assert body == "# srt telemetry disabled\n"
        finally:
            conn.close()
    finally:
        server.request_shutdown()
        assert server.wait() == 0


# ----------------------------------------------------------------------
# Graceful drain: SIGTERM against a real `serve` subprocess
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def model_dir(serve_nlp, tmp_path_factory):
    out = tmp_path_factory.mktemp("serve_model") / "model"
    serve_nlp.to_disk(out)
    return out


def test_sigterm_graceful_drain_subprocess(model_dir):
    """Acceptance: SIGTERM mid-load completes the in-flight request,
    rejects new admissions, and the process exits 0. The in-flight
    request is HELD in the coalescing window (max_wait 600ms — window
    mode pinned: continuous admission would dispatch it before the
    signal) when the signal lands, so the drain provably finishes
    admitted-but-not-dispatched work."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    metrics_dir = model_dir.parent / "serve_metrics"
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "spacy_ray_tpu", "serve", str(model_dir),
            "--device", "cpu", "--port", "0",
            "--max-batch", "4", "--batching", "window",
            "--max-wait-ms", "600",
            "--max-doc-len", "16", "--drain-timeout-s", "30",
            "--metrics-dir", str(metrics_dir),
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=env,
    )
    lines = []
    addr = [None]

    def reader():
        for line in proc.stdout:
            lines.append(line)
            if line.startswith("serving on http://"):
                hostport = line.strip().rsplit("/", 1)[-1]
                host, port = hostport.rsplit(":", 1)
                addr[0] = (host, int(port))

    rt = threading.Thread(target=reader, daemon=True)
    rt.start()
    try:
        deadline = time.monotonic() + 180.0
        while addr[0] is None and time.monotonic() < deadline:
            if proc.poll() is not None:
                pytest.fail(f"serve exited early:\n{''.join(lines)}")
            time.sleep(0.1)
        assert addr[0] is not None, f"no banner:\n{''.join(lines)}"
        host, port = addr[0]

        # listener-first startup: the banner (and the port) appear BEFORE
        # the bucket warmup sweep; /healthz answers 503 "warming" until
        # the sweep completes — poll for readiness exactly like a fleet
        # router would
        ready_deadline = time.monotonic() + 150.0
        while True:
            status, health = _get(host, port, "/healthz", timeout=30.0)
            if status == 200:
                assert health["status"] == "ok"
                break
            assert status == 503 and health["status"] == "warming", health
            assert time.monotonic() < ready_deadline, (
                f"never became ready:\n{''.join(lines)}"
            )
            time.sleep(0.2)

        # in-flight request: sits in the 600ms coalescing window
        inflight = {}

        def one_request():
            try:
                inflight["result"] = _post(
                    host, port, {"texts": ["the cat runs"]}, timeout=60.0
                )
            except Exception as e:  # noqa: BLE001 — recorded for the assert
                inflight["result"] = e

        t = threading.Thread(target=one_request)
        t.start()
        time.sleep(0.2)  # inside the window: admitted, not yet dispatched
        proc.send_signal(signal.SIGTERM)

        t.join(timeout=60.0)
        result = inflight.get("result")
        assert isinstance(result, tuple) and result[0] == 200, (
            f"in-flight request not completed through the drain: {result!r}"
        )
        assert result[1]["docs"][0]["tags"]

        # new admissions after SIGTERM: typed 503 or (post-exit) refused
        try:
            status, payload = _post(
                host, port, {"texts": ["another request"]}, timeout=10.0
            )
            assert status == 503, (status, payload)
        except OSError:
            pass  # listener already closed — also a rejection

        rc = proc.wait(timeout=60.0)
        assert rc == 0, f"drain exit {rc}:\n{''.join(lines)}"
        assert any("drained; exiting 0" in l for l in lines), lines

        # --metrics-dir shutdown artifacts: the serving snapshot lands as
        # a `kind: "serving"` metrics.jsonl row that `telemetry
        # summarize` digests with the training-file contract
        from spacy_ray_tpu.training.telemetry import summarize_metrics

        rows = [
            json.loads(l)
            for l in open(metrics_dir / "metrics.jsonl", encoding="utf8")
        ]
        serving_rows = [r for r in rows if r.get("kind") == "serving"]
        assert serving_rows, rows
        assert serving_rows[-1]["counters"]["requests"] >= 1
        summary = summarize_metrics(metrics_dir / "metrics.jsonl")
        assert "serving: requests" in summary
        assert (metrics_dir / "serving_trace.json").exists()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10.0)
