"""Multi-tenant, multi-model serving (spacy_ray_tpu/serving/multimodel/):
manifest registry + resolution precedence (path > header > default),
token-bucket quotas under an injected clock, weighted fair queuing
shares under saturation, replica model residency (LRU hot set, pinned
default, leader-elected loads), placement-policy hysteresis, per-model
response-cache keys + ledger, model-aware routing at the fleet edge,
the per-model metrics merge, `telemetry top` per-model rows, and the
HTTP surface end-to-end with two real pipelines — where the legacy
single-model /v1/parse contract must stay bit-identical."""

import json
import http.client
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

from spacy_ray_tpu.config import Config
from spacy_ray_tpu.pipeline.language import Pipeline
from spacy_ray_tpu.serving import (
    DynamicBatcher,
    InferenceEngine,
    Server,
    ServeRequest,
    ServingTelemetry,
)
from spacy_ray_tpu.serving.batcher import (
    DeadlineExceeded,
    Draining,
    QueueFull,
    QuotaExceeded,
    ServingError,
    UnknownModel,
)
from spacy_ray_tpu.serving.fleet import (
    ReplicaHandle,
    ResponseCache,
    Router,
    RouterHTTPServer,
    RouterTelemetry,
)
from spacy_ray_tpu.serving.fleet.router import GENERATION_MIXED
from spacy_ray_tpu.serving.multimodel import (
    MODEL_HEADER,
    TENANT_HEADER,
    AdmissionController,
    ClassSpec,
    ModelRegistry,
    ModelSpec,
    PlacementPolicy,
    ResidencyManager,
    TokenBucket,
)
from spacy_ray_tpu.training.telemetry import merge_serving_snapshots


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


MANIFEST = {
    "default_model": "alpha",
    "models": {
        "alpha": {"path": "models/alpha"},
        "beta": {"path": "models/beta"},
    },
    "classes": {
        "gold": {"weight": 4, "p99_target_ms": 500},
        "batch": {"weight": 1, "p99_target_ms": 5000},
    },
    "tenants": {
        "acme": {"class": "gold", "quota_docs_per_s": 10,
                 "quota_burst": 10},
        "bulk": {"class": "batch"},
    },
}


def write_manifest(tmp_path, manifest=None):
    p = tmp_path / "manifest.json"
    p.write_text(json.dumps(manifest or MANIFEST), encoding="utf-8")
    return p


# ----------------------------------------------------------------------
# Registry: manifest parsing + resolution precedence
# ----------------------------------------------------------------------


def test_manifest_parses_and_resolves_relative_paths(tmp_path):
    reg = ModelRegistry.from_manifest(write_manifest(tmp_path))
    assert reg.names() == ["alpha", "beta"]
    assert reg.default_model == "alpha"
    # relative model paths resolve against the manifest's directory
    assert reg.spec("beta").path == str(tmp_path / "models" / "beta")
    assert reg.class_weights() == {"gold": 4.0, "batch": 1.0,
                                   "default": 1.0}
    assert reg.p99_target_ms("gold") == 500.0
    assert reg.p99_target_ms("nope") is None
    desc = reg.describe()
    assert desc["default_model"] == "alpha"
    assert desc["tenants"] == ["acme", "bulk"]


def test_resolution_precedence_path_over_header_over_default(tmp_path):
    reg = ModelRegistry.from_manifest(write_manifest(tmp_path))
    # default: the legacy path with no header
    assert reg.resolve_model("/v1/parse", {}) == ("alpha", False)
    assert reg.resolve_model("/v1/parse", None) == ("alpha", False)
    # header selects on the legacy path
    assert reg.resolve_model(
        "/v1/parse", {MODEL_HEADER: "beta"}
    ) == ("beta", True)
    # path form names the model explicitly
    assert reg.resolve_model(
        "/v1/models/beta/parse", {}
    ) == ("beta", True)
    # path WINS over a contradicting header
    assert reg.resolve_model(
        "/v1/models/alpha/parse", {MODEL_HEADER: "beta"}
    ) == ("alpha", True)


def test_resolution_unknown_and_malformed_are_typed_404(tmp_path):
    reg = ModelRegistry.from_manifest(write_manifest(tmp_path))
    with pytest.raises(UnknownModel):
        reg.resolve_model("/v1/models/nope/parse", {})
    with pytest.raises(UnknownModel):
        reg.resolve_model("/v1/parse", {MODEL_HEADER: "nope"})
    # malformed model path: typed 404, never a silent fallback
    with pytest.raises(UnknownModel):
        reg.resolve_model("/v1/models//parse", {})
    with pytest.raises(UnknownModel):
        reg.resolve_model("/v1/models/beta", {})
    with pytest.raises(UnknownModel):
        reg.resolve_model("/v1/models/beta/parse/extra", {})


def test_manifest_validation_errors(tmp_path):
    with pytest.raises(ValueError):  # no models
        ModelRegistry.from_manifest(write_manifest(tmp_path, {"models": {}}))
    with pytest.raises(ValueError):  # >1 model needs default_model
        ModelRegistry.from_manifest(write_manifest(tmp_path, {
            "models": {"a": {"path": "a"}, "b": {"path": "b"}},
        }))
    with pytest.raises(ValueError):  # weight must be > 0
        ModelRegistry.from_manifest(write_manifest(tmp_path, {
            "models": {"a": {"path": "a"}},
            "classes": {"gold": {"weight": 0}},
        }))
    with pytest.raises(ValueError):  # tenant names unknown class
        ModelRegistry.from_manifest(write_manifest(tmp_path, {
            "models": {"a": {"path": "a"}},
            "tenants": {"t": {"class": "nope"}},
        }))
    with pytest.raises(ValueError):  # hostile model name refused
        ModelRegistry({"a/b": ModelSpec("a/b", "x")}, "a/b")
    # a single model needs no explicit default
    reg = ModelRegistry.from_manifest(write_manifest(tmp_path, {
        "models": {"only": {"path": "m"}},
    }))
    assert reg.default_model == "only"


def test_anonymous_tenant_is_default_class_no_quota(tmp_path):
    reg = ModelRegistry.from_manifest(write_manifest(tmp_path))
    for name in (None, "never-heard-of-you"):
        spec = reg.tenant(name)
        assert spec.klass == "default"
        assert spec.quota_docs_per_s is None
    assert reg.tenant("acme").klass == "gold"


# ----------------------------------------------------------------------
# Token bucket + admission: quota with an injected clock
# ----------------------------------------------------------------------


def test_token_bucket_refill_under_fake_clock():
    clock = FakeClock()
    bucket = TokenBucket(10.0, burst=10.0, clock=clock)
    assert bucket.try_acquire(10)  # spend the full burst at once
    assert not bucket.try_acquire(1)  # empty, no time passed
    clock.advance(0.5)  # refills 5 tokens
    assert bucket.available() == pytest.approx(5.0)
    assert bucket.try_acquire(5)
    assert not bucket.try_acquire(1)
    clock.advance(100.0)  # refill caps at burst, never beyond
    assert bucket.available() == pytest.approx(10.0)
    with pytest.raises(ValueError):
        TokenBucket(0.0)


def test_admission_charges_quota_and_resolves_class(tmp_path):
    clock = FakeClock()
    reg = ModelRegistry.from_manifest(write_manifest(tmp_path))
    adm = AdmissionController(reg, clock=clock)
    # acme: 10 docs/s, burst 10 — the 11th doc in the same instant sheds
    assert adm.admit("acme", n_docs=10) == "gold"
    with pytest.raises(QuotaExceeded):
        adm.admit("acme", n_docs=1)
    assert adm.rejected_quota == 1
    clock.advance(1.0)
    assert adm.admit("acme", n_docs=10) == "gold"
    # unlimited tenant and the anonymous default always admit
    for _ in range(50):
        assert adm.admit("bulk", n_docs=100) == "batch"
        assert adm.admit(None, n_docs=100) == "default"
    stats = adm.stats()
    assert stats["rejected_quota"] == 1.0
    assert "tokens_acme" in stats


def test_typed_reject_vocabulary_is_distinct():
    """429-matrix: a client must be able to tell "the server is
    saturated" (queue_full) from "YOU are over quota" — and the model
    404 is its own code, not a routing fallback."""
    assert QuotaExceeded.http_status == 429
    assert QueueFull.http_status == 429
    assert QuotaExceeded.code == "quota_exceeded"
    assert QueueFull.code == "queue_full"
    assert QuotaExceeded.code != QueueFull.code
    assert UnknownModel.http_status == 404
    assert UnknownModel.code == "unknown_model"
    assert issubclass(QuotaExceeded, ServingError)
    assert issubclass(UnknownModel, ServingError)


# ----------------------------------------------------------------------
# Weighted fair queuing in the batcher
# ----------------------------------------------------------------------


def _mm_req(klass, n_docs=1, deadline_in=60.0):
    now = time.monotonic()
    return ServeRequest(
        [object()] * n_docs, now + deadline_in, now, klass=klass
    )


def _drain_docs(batcher, n_docs):
    """Assemble batches via the dispatch-side pop until ``n_docs`` docs
    are served; returns the total actually popped."""
    served = 0
    while served < n_docs:
        batch = []
        with batcher._lock:
            batcher._pop_ready(batch, time.monotonic())
        if not batch:
            break
        served += sum(len(r.docs) for r in batch)
    return served


def test_wfq_weights_honored_under_saturation():
    """The property the manifest's weights promise: with both classes
    saturated, dispatched-doc shares converge to the weight ratio (4:1),
    and neither class is ever starved outright."""
    b = DynamicBatcher(
        max_queue_docs=1024, max_batch_docs=8, max_wait_s=0.0,
        class_weights={"gold": 4.0, "batch": 1.0},
    )
    for _ in range(320):
        b.submit(_mm_req("gold"))
        b.submit(_mm_req("batch"))
    assert _drain_docs(b, 320) == 320
    gold = b.served_docs_by_class["gold"]
    batch = b.served_docs_by_class["batch"]
    assert batch > 0, "weight-1 class starved outright"
    assert gold / batch == pytest.approx(4.0, rel=0.15), (
        f"dispatched shares {gold}:{batch} do not honor weights 4:1"
    )


def test_wfq_unknown_class_auto_registers_at_weight_one():
    b = DynamicBatcher(
        max_queue_docs=64, max_batch_docs=4, max_wait_s=0.0,
        class_weights={"gold": 4.0},
    )
    b.submit(_mm_req("surprise"))
    assert _drain_docs(b, 1) == 1
    assert b.class_weights["surprise"] == 1.0
    assert b.served_docs_by_class["surprise"] == 1


def test_wfq_idle_class_has_no_penalty():
    """An empty queue forfeits its banked credits (DRR rule): traffic in
    one class alone dispatches at full batch size, no idle-class stall."""
    b = DynamicBatcher(
        max_queue_docs=64, max_batch_docs=4, max_wait_s=0.0,
        class_weights={"gold": 4.0, "batch": 1.0},
    )
    for _ in range(8):
        b.submit(_mm_req("batch"))
    batch = []
    with b._lock:
        b._pop_ready(batch, time.monotonic())
    assert sum(len(r.docs) for r in batch) == 4  # a FULL batch


def test_wfq_expires_per_class_queues():
    b = DynamicBatcher(
        max_queue_docs=64, max_batch_docs=4, max_wait_s=0.0,
        class_weights={"gold": 4.0, "batch": 1.0},
    )
    dead = _mm_req("gold", deadline_in=0.0)
    live = _mm_req("batch")
    b.submit(dead)
    b.submit(live)
    time.sleep(0.002)
    assert _drain_docs(b, 1) == 1
    assert dead.done and isinstance(dead.error, DeadlineExceeded)
    assert live.done is False or live.error is None
    assert b.expired == 1


def test_wfq_fail_all_queued_drains_class_queues():
    b = DynamicBatcher(
        max_queue_docs=64, max_batch_docs=4, max_wait_s=0.0,
        class_weights={"gold": 4.0, "batch": 1.0},
    )
    reqs = [_mm_req("gold"), _mm_req("batch"), _mm_req("gold")]
    for r in reqs:
        b.submit(r)
    assert b.fail_all_queued(Draining("going down")) == 3
    assert b.queue_depth() == 0
    for r in reqs:
        assert r.done and isinstance(r.error, Draining)


def test_legacy_no_weights_is_single_fifo():
    """class_weights=None keeps the legacy single FIFO bit-identical:
    klass is carried but ignored, and no per-class ledger appears."""
    b = DynamicBatcher(
        max_queue_docs=64, max_batch_docs=8, max_wait_s=0.0,
    )
    first = _mm_req("batch")
    second = _mm_req("gold")
    b.submit(first)
    b.submit(second)
    batch = []
    with b._lock:
        b._pop_ready(batch, time.monotonic())
    assert batch == [first, second]  # submit order, classes ignored
    assert b.served_docs_by_class == {}


# ----------------------------------------------------------------------
# Residency: LRU hot set of engines
# ----------------------------------------------------------------------


class FakeEngine:
    def __init__(self, name):
        self.name = name
        self.warmed = [(1, 1)]
        self.serving_generation = 1
        self.swap_count = 0
        self.drained = False
        self.stopped = False

    def drain(self, timeout):
        self.drained = True
        return True

    def stop(self):
        self.stopped = True


def _registry3():
    return ModelRegistry(
        {n: ModelSpec(n, f"/m/{n}") for n in ("a", "b", "c")}, "a"
    )


def test_residency_lru_evicts_oldest_never_pinned():
    clock = FakeClock()
    made = []

    def factory(spec):
        e = FakeEngine(spec.name)
        made.append(e)
        return e

    res = ResidencyManager(
        _registry3(), factory, capacity=2, pinned={"a"}, clock=clock
    )
    default = FakeEngine("a")
    res.adopt("a", default)  # adopt = no load counted
    assert res.loads == 0
    clock.advance(1)
    eng_b = res.engine_for("b")
    clock.advance(1)
    eng_c = res.engine_for("c")  # over capacity: LRU victim is b, not
    assert eng_b.drained and eng_b.stopped  # ... the pinned default
    assert not default.drained and not default.stopped
    assert res.resident() == ["a", "c"]
    assert res.stats() == {
        "resident": ["a", "c"], "capacity": 2,
        "loads": 2, "evictions": 1, "residency_swaps": 3,
    }
    # touching c then re-loading b evicts nothing but... there is no
    # other unpinned candidate except c, and c is LRU after the touch
    clock.advance(1)
    assert res.engine_for("c") is eng_c  # touch: c is now MRU
    clock.advance(1)
    res.engine_for("b")
    assert eng_c.drained and eng_c.stopped
    assert res.resident() == ["a", "b"]
    assert res.evictions == 2


def test_residency_unknown_model_and_load_false():
    res = ResidencyManager(_registry3(), FakeEngine, capacity=2)
    with pytest.raises(UnknownModel):
        res.engine_for("nope")
    with pytest.raises(UnknownModel):
        res.adopt("nope", FakeEngine("nope"))
    # known but not resident + load=False: a typed refusal (the
    # per-model admin path uses this — no implicit cold loads mid-swap)
    with pytest.raises(ServingError):
        res.engine_for("b", load=False)


def test_residency_failed_load_is_refused_then_retryable():
    calls = {"n": 0}

    def factory(spec):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("corrupt pipeline dir")
        return FakeEngine(spec.name)

    res = ResidencyManager(_registry3(), factory, capacity=2)
    with pytest.raises(ServingError):
        res.engine_for("b")
    assert res.resident() == []  # never half-resident
    assert res.engine_for("b").name == "b"  # retry succeeds
    assert res.loads == 1


def test_residency_concurrent_requests_share_one_load():
    gate = threading.Event()
    calls = {"n": 0}

    def factory(spec):
        calls["n"] += 1
        gate.wait(5.0)
        return FakeEngine(spec.name)

    res = ResidencyManager(_registry3(), factory, capacity=2)
    got = []
    threads = [
        threading.Thread(target=lambda: got.append(res.engine_for("b")))
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    time.sleep(0.1)  # let every thread reach the load path
    gate.set()
    for t in threads:
        t.join(timeout=10.0)
    assert calls["n"] == 1, "concurrent requests stampeded the factory"
    assert len(got) == 4 and all(e is got[0] for e in got)


def test_residency_stop_all_drains_everything():
    res = ResidencyManager(_registry3(), FakeEngine, capacity=3)
    engines = [res.engine_for(n) for n in ("a", "b", "c")]
    assert res.stop_all() is True
    assert res.resident() == []
    for e in engines:
        assert e.drained and e.stopped


def test_residency_resident_info_shape():
    res = ResidencyManager(_registry3(), FakeEngine, capacity=2)
    res.engine_for("b")
    info = res.resident_info()
    assert info == {
        "b": {"generation": 1, "swap_count": 0, "warmed": True},
    }


# ----------------------------------------------------------------------
# Placement policy: hysteresis over per-model window p99
# ----------------------------------------------------------------------


def _placement_policy(clock, registry=None):
    return PlacementPolicy(
        registry if registry is not None else _registry3(),
        default_p99_target_ms=500.0,
        breach_consecutive=2,
        cooldown_s=30.0,
        min_window_samples=5,
        clock=clock,
    )


def test_placement_breach_streak_then_cooldown():
    clock = FakeClock()
    pol = _placement_policy(clock)
    hot = {"b": {"p99": 1.0, "samples": 50}}
    placement = {0: ["a", "b"], 1: ["a"]}
    # one breach is noise: no decision until the streak completes
    assert pol.observe(hot, placement, [0, 1]) == []
    clock.advance(1)
    [d] = pol.observe(hot, placement, [0, 1])
    assert d.model == "b" and d.replica_id == 1
    assert "p99" in d.reason
    # cooldown: a continuing breach inside the window moves nothing
    # (the streak keeps accruing — cooldown defers, it does not forgive)
    clock.advance(1)
    assert pol.observe(hot, placement, [0, 1]) == []
    clock.advance(1)
    assert pol.observe(hot, placement, [0, 1]) == []
    clock.advance(31)  # cooldown expires; the standing breach moves now
    [d2] = pol.observe(hot, placement, [0, 1])
    assert d2.replica_id == 1


def test_placement_recovery_and_thin_windows_reset_streak():
    clock = FakeClock()
    pol = _placement_policy(clock)
    placement = {0: ["b"], 1: []}
    assert pol.observe({"b": {"p99": 1.0, "samples": 50}},
                       placement, [0, 1]) == []
    # recovery resets the streak...
    assert pol.observe({"b": {"p99": 0.1, "samples": 50}},
                       placement, [0, 1]) == []
    assert pol.observe({"b": {"p99": 1.0, "samples": 50}},
                       placement, [0, 1]) == []
    # ...and so does a window too thin to trust
    assert pol.observe({"b": {"p99": 1.0, "samples": 2}},
                       placement, [0, 1]) == []
    assert pol.observe({"b": {"p99": 1.0, "samples": 50}},
                       placement, [0, 1]) == []
    [d] = pol.observe({"b": {"p99": 1.0, "samples": 50}},
                      placement, [0, 1])
    assert d.replica_id == 1


def test_placement_targets_fewest_resident_and_saturation_is_no_op():
    clock = FakeClock()
    pol = _placement_policy(clock)
    hot = {"b": {"p99": 1.0, "samples": 50}}
    placement = {0: ["b"], 1: ["a", "c"], 2: []}
    pol.observe(hot, placement, [1, 2])
    [d] = pol.observe(hot, placement, [1, 2])
    assert d.replica_id == 2  # fewest resident models wins
    # every ready replica already hosts it: replica-count scaling is
    # the base autoscaler's job — placement stays silent
    clock.advance(31)
    saturated = {0: ["b"], 1: ["b"], 2: ["b"]}
    pol.observe(hot, saturated, [0, 1, 2])
    assert pol.observe(hot, saturated, [0, 1, 2]) == []


def test_placement_class_target_overrides_default():
    clock = FakeClock(100.0)
    reg = ModelRegistry(
        {"m": ModelSpec("m", "/m")}, "m",
        classes={"gold": ClassSpec("gold", weight=4.0,
                                   p99_target_ms=50.0)},
    )
    pol = _placement_policy(clock, registry=reg)
    # 100ms p99 is UNDER the 500ms default but over gold's 50ms target
    hot = {"m": {"p99": 0.1, "samples": 50}}
    pol.observe(hot, {0: ["m"]}, [0, 1])
    [d] = pol.observe(hot, {0: ["m"]}, [0, 1])
    assert d.model == "m" and d.replica_id == 1


# ----------------------------------------------------------------------
# Response cache: per-model keys + per-model ledger
# ----------------------------------------------------------------------


def test_cache_key_model_scoping_is_collision_free():
    k = ResponseCache.key_for
    # legacy callers (no model) produce byte-identical keys
    assert k(["a", "b"]) == k(["a", "b"], model="")
    assert k(["a"]) != k(["a"], model="m")
    assert k(["a"], model="m1") != k(["a"], model="m2")
    # the model prefix cannot be smuggled via text content
    assert k(["a"], model="b") != k(["ba"])
    assert k(["a"], model="b") != k(["b", "a"])


def test_cache_per_model_ledger_hits_misses_stale():
    cache = ResponseCache(1 << 20)
    k = ResponseCache.key_for
    # model-less traffic keeps the legacy stats shape: no by_model block
    cache.put(k(["x"]), b"body")
    assert cache.get(k(["x"])) == b"body"
    assert "by_model" not in cache.stats()
    ka = k(["t"], model="alpha")
    assert cache.get(ka, 1, model="alpha") is None  # miss
    cache.put(ka, b"alpha-gen1", 1)
    assert cache.get(ka, 1, model="alpha") == b"alpha-gen1"  # hit
    assert cache.get(ka, 2, model="alpha") is None  # stale invalidation
    kb = k(["t"], model="beta")
    cache.put(kb, b"beta-gen1", 1)
    assert cache.get(kb, 1, model="beta") == b"beta-gen1"
    by_model = cache.stats()["by_model"]
    # a stale invalidation is ALSO a miss (the caller re-parses), same
    # double-tally as the fleet-wide ledger
    assert by_model["alpha"] == {
        "hits": 1, "misses": 2, "stale_invalidations": 1,
    }
    assert by_model["beta"]["hits"] == 1
    # the fleet-wide ledger still counts every event
    assert cache.stats()["cache_hits"] == 3


# ----------------------------------------------------------------------
# Router: model-aware pick, probe-learned placement, HTTP edge
# ----------------------------------------------------------------------


def _handle(rid, *, ready=True, outstanding=0, resident=None,
            generation=None, port=9):
    h = ReplicaHandle(rid)
    h.set_address("127.0.0.1", port)
    h.ready = ready
    h.outstanding = outstanding
    h.generation = generation
    if resident is not None:
        h.resident_models = {
            m: {"generation": g} for m, g in resident.items()
        }
    return h


def test_pick_prefers_replicas_hosting_the_model():
    hosting = _handle(0, outstanding=5, resident={"ner": 1})
    idle = _handle(1, outstanding=0, resident={"tagger": 1})
    router = Router(lambda: [hosting, idle])
    # least-outstanding WITHIN the hosting subset, not fleet-wide
    assert router.pick("ner") is hosting
    assert router.pick("tagger") is idle
    # model resident nowhere: fall back to the full ready set (the
    # replica will cold-load it — routable beats unroutable)
    assert router.pick("brand-new") is idle
    assert router.pick(None) is idle  # legacy pick unchanged


def test_cache_generation_per_model():
    h0 = _handle(0, resident={"ner": 3, "tagger": 7})
    h1 = _handle(1, resident={"ner": 3, "tagger": 8})
    router = Router(lambda: [h0, h1])
    assert router.cache_generation("ner") == 3  # converged
    assert router.cache_generation("tagger") is GENERATION_MIXED
    assert router.cache_generation("absent") is GENERATION_MIXED
    assert router.placement() == {0: ["ner", "tagger"],
                                  1: ["ner", "tagger"]}


class _MMStubServer(ThreadingHTTPServer):
    daemon_threads = True


class _MMStubHandler(BaseHTTPRequestHandler):
    """A replica stub that ECHOES the forwarded path and headers, and
    advertises a resident set on /healthz — what the router's probe
    loop and forward path are tested against."""

    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):
        pass

    def _reply(self, status, payload):
        body = json.dumps(payload).encode("utf8")
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):  # noqa: N802
        stub = self.server.stub
        if self.path == "/healthz":
            self._reply(200, {
                "status": "ok",
                "generation": stub.generation,
                "swap_count": 0,
                "resident_models": stub.resident_models,
                "default_model": stub.default_model,
            })
        else:
            self._reply(200, {})

    def do_POST(self):  # noqa: N802
        stub = self.server.stub
        length = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(length)
        stub.seen.append({
            "path": self.path,
            "tenant": self.headers.get(TENANT_HEADER),
        })
        self._reply(200, {"docs": [{"stub": True}],
                          "batch": {"occupancy": 1}})


class MMStub:
    def __init__(self, resident_models, default_model="alpha",
                 generation=1):
        self.resident_models = resident_models
        self.default_model = default_model
        self.generation = generation
        self.seen = []
        self.httpd = _MMStubServer(("127.0.0.1", 0), _MMStubHandler)
        self.httpd.stub = self
        self.port = self.httpd.server_address[1]
        threading.Thread(
            target=self.httpd.serve_forever,
            kwargs={"poll_interval": 0.05}, daemon=True,
        ).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def _serve_router(router):
    httpd = RouterHTTPServer(("127.0.0.1", 0), router)
    threading.Thread(
        target=httpd.serve_forever, kwargs={"poll_interval": 0.05},
        daemon=True,
    ).start()
    host, port = httpd.server_address[:2]
    return httpd, str(host), int(port)


def _post_path(host, port, path, payload, headers=None, timeout=30.0):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        body = json.dumps(payload).encode("utf8")
        hdrs = {"Content-Type": "application/json", **(headers or {})}
        conn.request("POST", path, body, hdrs)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def test_router_edge_routes_models_and_forwards_tenant(tmp_path):
    reg = ModelRegistry.from_manifest(write_manifest(tmp_path))
    stub = MMStub({"alpha": {"generation": 1}, "beta": {"generation": 1}})
    tel = RouterTelemetry()
    handle = _handle(0, port=stub.port)
    router = Router(lambda: [handle], telemetry=tel, registry=reg)
    httpd, host, port = _serve_router(router)
    try:
        router.probe_once()  # learn the resident set from /healthz
        assert handle.resident_models == {
            "alpha": {"generation": 1}, "beta": {"generation": 1},
        }
        # legacy default: forwarded on the legacy path, no model segment
        status, _ = _post_path(host, port, "/v1/parse", {"texts": ["x"]})
        assert status == 200
        assert stub.seen[-1] == {"path": "/v1/parse", "tenant": None}
        # path form: forwarded with the explicit model segment
        status, _ = _post_path(
            host, port, "/v1/models/beta/parse", {"texts": ["x"]},
            headers={TENANT_HEADER: "acme"},
        )
        assert status == 200
        assert stub.seen[-1] == {
            "path": "/v1/models/beta/parse", "tenant": "acme",
        }
        # header form resolves to the same explicit forward
        status, _ = _post_path(
            host, port, "/v1/parse", {"texts": ["x"]},
            headers={MODEL_HEADER: "beta"},
        )
        assert status == 200
        assert stub.seen[-1]["path"] == "/v1/models/beta/parse"
        # unknown model: typed 404 BEFORE any forward
        n_forwards = len(stub.seen)
        status, payload = _post_path(
            host, port, "/v1/models/nope/parse", {"texts": ["x"]},
        )
        assert status == 404 and payload["error"] == "unknown_model"
        assert len(stub.seen) == n_forwards  # no replica paid for it
        snap = tel.snapshot()
        assert snap["counters"]["rejected_unknown_model"] == 1
        # placement + models ride the fleet /metrics payload
        metrics = router.fleet_metrics()
        assert metrics["placement"] == {"0": ["alpha", "beta"]}
        assert metrics["models"] == ["alpha", "beta"]
        assert metrics["default_model"] == "alpha"
    finally:
        httpd.shutdown()
        httpd.server_close()
        stub.close()


def test_router_without_registry_keeps_legacy_404():
    stub = MMStub({})
    handle = _handle(0, port=stub.port)
    router = Router(lambda: [handle])
    httpd, host, port = _serve_router(router)
    try:
        status, payload = _post_path(
            host, port, "/v1/models/x/parse", {"texts": ["x"]},
        )
        assert status == 404 and payload["error"] == "not_found"
    finally:
        httpd.shutdown()
        httpd.server_close()
        stub.close()


# ----------------------------------------------------------------------
# Per-model metrics merge + `telemetry top` rows
# ----------------------------------------------------------------------


def _model_snap(requests, p99=0.01):
    return {
        "counters": {"requests": requests},
        "gauges": {"queue_depth": 1},
        "histograms": {},
        "slo_window": {"request_latency_p99": p99, "samples": requests},
    }


def test_merge_serving_snapshots_by_model():
    snaps = [
        {**_model_snap(10), "models": {
            "alpha": _model_snap(6), "beta": _model_snap(4),
        }},
        {**_model_snap(20), "models": {"alpha": _model_snap(20)}},
    ]
    merged = merge_serving_snapshots(snaps)
    by_model = merged["by_model"]
    assert by_model["alpha"]["counters"]["requests"] == 26
    assert by_model["beta"]["counters"]["requests"] == 4
    assert by_model["alpha"]["model"] == "alpha"
    # snapshots without a models block: no by_model key at all (legacy
    # single-model fleets see an unchanged merge shape)
    assert "by_model" not in merge_serving_snapshots(
        [_model_snap(5), _model_snap(7)]
    )


def test_fleet_placement_tick_appends_ledger(tmp_path):
    """The fleet-level placement half of the scaling loop: a breaching
    model is loaded onto the least-loaded non-hosting replica and the
    move lands in <incidents_dir>/placement.jsonl — the ledger CI
    uploads as a failure artifact."""
    from types import SimpleNamespace

    from spacy_ray_tpu.serving.fleet.fleet import Fleet, FleetConfig

    manifest = write_manifest(tmp_path)
    inc = tmp_path / "incidents"
    fleet = Fleet(FleetConfig(
        model_path=str(tmp_path / "alpha"),
        port=0,
        replicas=0,
        telemetry=False,
        autoscale=True,
        up_consecutive=1,
        model_manifest=str(manifest),
        incidents_dir=str(inc),
    ))
    try:
        fleet.router.ready_handles = lambda: [
            SimpleNamespace(replica_id=0), SimpleNamespace(replica_id=1),
        ]
        fleet.router.placement = lambda: {0: ["alpha", "beta"],
                                          1: ["alpha"]}
        loads = []
        fleet.router.load_model = (
            lambda rid, model, **kw: loads.append((rid, model)) or (200, {})
        )
        snap = {**_model_snap(400), "models": {
            "alpha": _model_snap(200, p99=0.005),
            "beta": _model_snap(200, p99=10.0),  # way past gold 500ms
        }}
        decisions = fleet.placement_tick([snap])
        assert [(d.model, d.replica_id) for d in decisions] == [("beta", 1)]
        assert loads == [(1, "beta")]
        lines = (inc / "placement.jsonl").read_text().strip().splitlines()
        assert len(lines) == 1
        entry = json.loads(lines[0])
        assert entry["model"] == "beta"
        assert entry["replica_id"] == 1
        assert entry["status"] == 200
        assert entry["reason"]
    finally:
        fleet.httpd.server_close()


def _mm_router_payload(requests, quota_rejects=0):
    return {
        "fleet": {
            "replicas": 2,
            "counters": {"requests": requests,
                         "rejected_quota": quota_rejects},
            "gauges": {"queue_depth": {"sum": 1, "max": 1, "mean": 1.0}},
            "histograms": {},
            "slo_window": {"request_latency_p99": 0.040},
            "by_model": {
                "alpha": {
                    "counters": {"requests": requests,
                                 "rejected_quota": quota_rejects},
                    "slo_window": {"request_latency_p99": 0.030},
                },
                "beta": {
                    "counters": {"requests": requests // 2},
                    "slo_window": {"request_latency_p99": 0.080},
                },
            },
        },
        "router": {"counters": {"requests": requests,
                                "rejected_no_replica": 0,
                                "rejected_draining": 0}},
        "replicas": [
            {"id": 0, "ready": True, "generation": 1, "swap_count": 0},
            {"id": 1, "ready": True, "generation": 1, "swap_count": 0},
        ],
        "placement": {"0": ["alpha", "beta"], "1": ["alpha"]},
        "cache": {
            "cache_hits": 8, "cache_misses": 2,
            "cache_stale_invalidations": 0,
            "cache_mixed_generation_bypasses": 0,
            "by_model": {
                "alpha": {"hits": 8, "misses": 2,
                          "stale_invalidations": 0},
            },
        },
        "scrape_failures": {},
    }


def test_top_per_model_rows_and_quota_column():
    from spacy_ray_tpu.top import TopModel, render

    model = TopModel()
    model.update("http://r", _mm_router_payload(100), now=0.0)
    row = model.update(
        "http://r", _mm_router_payload(200, quota_rejects=30), now=10.0,
    )
    assert row["quota_s"] == pytest.approx(3.0)
    by_name = {m["name"]: m for m in row["models"]}
    assert by_name["alpha"]["req_s"] == pytest.approx(10.0)
    assert by_name["alpha"]["p99"] == 0.030
    assert by_name["alpha"]["cache_hit_rate"] == pytest.approx(0.8)
    assert by_name["alpha"]["hosts"] == 2
    assert by_name["alpha"]["quota_s"] == pytest.approx(3.0)
    assert by_name["beta"]["hosts"] == 1
    assert by_name["beta"]["cache_hit_rate"] is None  # no cache traffic
    screen = render([row])
    assert "model alpha" in screen and "model beta" in screen
    assert "429-quota" in screen and "hosts 2" in screen


def test_multimodel_disabled_telemetry_makes_zero_calls(
    tmp_path, monkeypatch
):
    """The zero-calls guard extends to the whole multimodel subsystem:
    registry/admission/residency/placement construct NOTHING from
    telemetry.py (their ledgers are plain ints)."""
    from spacy_ray_tpu.training import telemetry as telemetry_mod

    def _boom(*a, **k):
        raise AssertionError("telemetry constructed on the disabled path")

    monkeypatch.setattr(telemetry_mod.MetricsRegistry, "__init__", _boom)
    monkeypatch.setattr(telemetry_mod.TraceBuffer, "__init__", _boom)
    reg = ModelRegistry.from_manifest(write_manifest(tmp_path))
    adm = AdmissionController(reg, clock=FakeClock())
    assert adm.admit("acme", n_docs=1) == "gold"
    res = ResidencyManager(reg, FakeEngine, capacity=2)
    res.engine_for("beta")
    assert res.stats()["loads"] == 1
    pol = PlacementPolicy(reg, clock=FakeClock())
    pol.observe({"beta": {"p99": 1.0, "samples": 50}}, {0: []}, [0])
    cache = ResponseCache(1 << 20)
    cache.get(ResponseCache.key_for(["x"], model="beta"), 1, model="beta")
    assert cache.stats()["by_model"]["beta"]["misses"] == 1


# ----------------------------------------------------------------------
# HTTP end-to-end: two real pipelines behind one server
# ----------------------------------------------------------------------

MM_CFG = """
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

MM_TEXTS = [
    "the cat runs fast today",
    "a dog sleeps near the door",
    "rain falls softly on the roof",
]


@pytest.fixture(scope="module")
def mm_nlps():
    from spacy_ray_tpu.util import synth_corpus

    nlps = []
    for seed in (0, 1):
        nlp = Pipeline.from_config(Config.from_str(MM_CFG))
        egs = synth_corpus(64, "tagger", seed=seed)
        nlp.initialize(lambda: iter(egs), seed=seed)
        nlps.append(nlp)
    return nlps


@pytest.fixture(scope="module")
def mm_server(mm_nlps, tmp_path_factory):
    root = tmp_path_factory.mktemp("mm_fleet")
    dirs = {}
    for name, nlp in zip(("alpha", "beta"), mm_nlps):
        out = root / name
        nlp.to_disk(out)
        dirs[name] = out
    manifest = root / "manifest.json"
    manifest.write_text(json.dumps({
        "default_model": "alpha",
        "models": {n: {"path": str(d)} for n, d in dirs.items()},
        "classes": {
            "gold": {"weight": 4, "p99_target_ms": 500},
            "batch": {"weight": 1, "p99_target_ms": 5000},
        },
        "tenants": {
            "metered": {"class": "gold", "quota_docs_per_s": 1,
                        "quota_burst": 2},
        },
    }), encoding="utf-8")
    registry = ModelRegistry.from_manifest(str(manifest))
    admission = AdmissionController(registry)
    tel = ServingTelemetry()

    def _build(path, mtel):
        return InferenceEngine(
            Pipeline.from_disk(Path(path)),
            max_batch_docs=4,
            max_wait_s=0.02,
            max_queue_docs=64,
            timeout_s=30.0,
            max_doc_len=16,
            telemetry=mtel,
            class_weights=registry.class_weights(),
        )

    def factory(spec):
        e = _build(spec.path, ServingTelemetry())
        e.warmup()
        e.start(warmup=False)
        return e

    engine = _build(dirs["alpha"], tel)
    residency = ResidencyManager(
        registry, factory, capacity=2, pinned={"alpha"},
    )
    residency.adopt("alpha", engine)
    engine.start(warmup=True)
    server = Server(
        engine, "127.0.0.1", 0, telemetry=tel,
        registry=registry, residency=residency, admission=admission,
    )
    host, port = server.start()
    yield host, port, residency
    server.request_shutdown()
    assert server.wait() == 0


def _mm_post(host, port, path, payload, headers=None, timeout=60.0):
    return _post_path(host, port, path, payload, headers=headers,
                      timeout=timeout)


def _expected_tags(nlp, text):
    doc = nlp.tokenizer(text)
    nlp.predict_docs([doc])
    return doc.words, doc.tags


def test_mm_legacy_default_path_unchanged(mm_server, mm_nlps):
    """The legacy contract: /v1/parse with no model header serves the
    manifest default, byte-for-byte what a single-model server says."""
    host, port, _ = mm_server
    status, payload = _mm_post(
        host, port, "/v1/parse", {"texts": [MM_TEXTS[0]]},
    )
    assert status == 200
    words, tags = _expected_tags(mm_nlps[0], MM_TEXTS[0])
    [doc] = payload["docs"]
    assert doc["tokens"] == words and doc["tags"] == tags
    # the explicit path form of the default model answers identically
    status2, payload2 = _mm_post(
        host, port, "/v1/models/alpha/parse", {"texts": [MM_TEXTS[0]]},
    )
    assert status2 == 200 and payload2["docs"] == payload["docs"]


def test_mm_routes_to_second_model_and_residency_is_warm(
    mm_server, mm_nlps
):
    """First beta request cold-loads it into the hot set; the engine
    arrives WARMED (factory runs the bucket sweep before start), so no
    live request ever meets a post-load compile."""
    host, port, residency = mm_server
    status, payload = _mm_post(
        host, port, "/v1/models/beta/parse", {"texts": [MM_TEXTS[1]]},
    )
    assert status == 200
    words, tags = _expected_tags(mm_nlps[1], MM_TEXTS[1])
    [doc] = payload["docs"]
    assert doc["tokens"] == words and doc["tags"] == tags
    assert "beta" in residency.resident()
    beta = residency.engines()["beta"]
    assert beta.warmed, "beta engine served before its warmup sweep"
    assert beta.ready
    # the header form routes to the same resident engine
    status2, payload2 = _mm_post(
        host, port, "/v1/parse", {"texts": [MM_TEXTS[1]]},
        headers={MODEL_HEADER: "beta"},
    )
    assert status2 == 200 and payload2["docs"] == payload["docs"]
    # path beats a contradicting header
    status3, payload3 = _mm_post(
        host, port, "/v1/models/alpha/parse", {"texts": [MM_TEXTS[1]]},
        headers={MODEL_HEADER: "beta"},
    )
    assert status3 == 200
    a_words, a_tags = _expected_tags(mm_nlps[0], MM_TEXTS[1])
    [a_doc] = payload3["docs"]
    assert a_doc["tokens"] == a_words and a_doc["tags"] == a_tags


def test_mm_unknown_model_is_typed_404(mm_server):
    host, port, _ = mm_server
    for path, headers in (
        ("/v1/models/nope/parse", None),
        ("/v1/parse", {MODEL_HEADER: "nope"}),
        ("/v1/models/beta", None),  # malformed model path
    ):
        status, payload = _mm_post(
            host, port, path, {"texts": ["x"]}, headers=headers,
        )
        assert status == 404 and payload["error"] == "unknown_model", (
            path, headers, payload,
        )


def test_mm_quota_429_is_typed_and_sheds_before_the_queue(mm_server):
    host, port, _ = mm_server
    # burst 2 at 1 doc/s: the first 2-doc request drains the bucket,
    # an immediate second one sheds with the tenant-specific 429
    status, _ = _mm_post(
        host, port, "/v1/parse", {"texts": ["a b", "c d"]},
        headers={TENANT_HEADER: "metered"},
    )
    assert status == 200
    status, payload = _mm_post(
        host, port, "/v1/parse", {"texts": ["a b", "c d"]},
        headers={TENANT_HEADER: "metered"},
    )
    assert status == 429 and payload["error"] == "quota_exceeded"
    # an unmetered client is untouched by the neighbor's empty bucket
    status, _ = _mm_post(host, port, "/v1/parse", {"texts": ["a b"]})
    assert status == 200


def test_mm_healthz_and_metrics_advertise_residency(mm_server, tmp_path):
    host, port, _ = mm_server
    conn = http.client.HTTPConnection(host, port, timeout=30.0)
    try:
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        health = json.loads(resp.read())
        assert resp.status == 200
    finally:
        conn.close()
    assert health["default_model"] == "alpha"
    assert "alpha" in health["resident_models"]
    for info in health["resident_models"].values():
        assert "generation" in info and "warmed" in info
    assert health["residency"]["capacity"] == 2
    conn = http.client.HTTPConnection(host, port, timeout=30.0)
    try:
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        metrics = json.loads(resp.read())
        assert resp.status == 200
    finally:
        conn.close()
    assert "alpha" in metrics["models"]
    assert metrics["residency"]["resident"] == health["residency"]["resident"]
    # per-model snapshots are real serving snapshots (counters present)
    for name, msnap in metrics["models"].items():
        assert "counters" in msnap, name
    conn = http.client.HTTPConnection(host, port, timeout=30.0)
    try:
        conn.request("GET", "/metrics?format=prometheus")
        resp = conn.getresponse()
        text = resp.read().decode("utf-8")
        assert resp.status == 200
    finally:
        conn.close()
    assert 'model="alpha"' in text
    # drop the per-model evidence where CI's failure-artifact glob finds
    # it (.pytest-tmp/**/mm-bench-records.jsonl): one record per resident
    # model, post-mortem material for a red multi-model run
    with open(tmp_path / "mm-bench-records.jsonl", "w") as fh:
        for name, msnap in metrics["models"].items():
            fh.write(json.dumps({
                "model": name,
                "counters": msnap.get("counters"),
                "slo_window": msnap.get("slo_window"),
                "residency": metrics["residency"],
            }) + "\n")
