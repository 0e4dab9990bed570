"""Telemetry subsystem tests (training/telemetry.py): Chrome-trace
validity, registry thread-safety under the collation pool, deterministic
anomaly detectors (fake clock + synthetic series), the zero-overhead
disabled path, and the end-to-end smoke: a telemetry-enabled train run
with an injected NaN whose metrics.jsonl round-trips through
``telemetry summarize``."""

import json
import threading

import pytest

from spacy_ray_tpu.config import Config
from spacy_ray_tpu.training import resilience
from spacy_ray_tpu.training import telemetry as telemetry_mod
from spacy_ray_tpu.training.collate_pool import PipelineStats, ordered_map
from spacy_ray_tpu.training.loop import train, validate_training
from spacy_ray_tpu.training.telemetry import (
    AnomalyDetectors,
    MetricsRegistry,
    Telemetry,
    TraceBuffer,
    summarize_metrics,
)
from spacy_ray_tpu.util import write_synth_jsonl


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def now(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


# ----------------------------------------------------------------------
# Trace buffer: valid Chrome trace-event JSON
# ----------------------------------------------------------------------


def _schema_check_trace(path):
    data = json.loads(path.read_text(encoding="utf8"))
    assert isinstance(data["traceEvents"], list) and data["traceEvents"]
    for ev in data["traceEvents"]:
        assert isinstance(ev, dict)
        assert isinstance(ev["name"], str)
        assert ev["ph"] in ("X", "M", "i")
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
    return data


def test_trace_buffer_writes_valid_chrome_trace(tmp_path):
    clk = FakeClock()
    buf = TraceBuffer(clock=clk.now, pid=0)
    t0 = clk.now()
    clk.advance(0.25)
    buf.add_span("read", t0, 0.25, cat="pipeline")
    with buf.span("eval", step=7):
        clk.advance(0.5)
    buf.add_instant("nan-loss", args={"message": "boom"})
    # spans from a worker thread get their own tid + thread_name metadata
    thread = threading.Thread(
        target=lambda: buf.add_span("collate", clk.now(), 0.1),
        name="collate-pool-0",
    )
    thread.start()
    thread.join()
    out = tmp_path / "trace.json"
    assert buf.flush(out) == 4
    data = _schema_check_trace(out)
    events = data["traceEvents"]
    by_name = {e["name"]: e for e in events}
    assert {"read", "eval", "nan-loss", "collate"} <= set(by_name)
    # microsecond conversion: the read span started at origin, 0.25s long
    assert by_name["read"]["ts"] == 0.0
    assert by_name["read"]["dur"] == pytest.approx(250_000, abs=1)
    assert by_name["eval"]["dur"] == pytest.approx(500_000, abs=1)
    assert by_name["eval"]["args"] == {"step": 7}
    # the worker thread has a distinct tid and a thread_name metadata row
    meta = [e for e in events if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} >= {"collate-pool-0"}
    assert by_name["collate"]["tid"] != by_name["read"]["tid"]


def test_trace_window_gating_drops_unforced_spans():
    clk = FakeClock()
    buf = TraceBuffer(clock=clk.now)
    buf.set_recording(False)
    buf.add_span("step", clk.now(), 0.1)
    assert len(buf) == 0
    buf.add_span("checkpoint_save", clk.now(), 0.1, force=True)
    assert len(buf) == 1


def test_trace_buffer_bounded():
    buf = TraceBuffer(max_events=8)
    for i in range(20):
        buf.add_span(f"s{i}", 0.0, 0.001)
    assert len(buf) == 8
    assert buf.dropped == 12


# ----------------------------------------------------------------------
# Metrics registry: thread-safety under the OrderedPool workers
# ----------------------------------------------------------------------


def test_registry_thread_safe_under_collate_pool():
    reg = MetricsRegistry()
    counter = reg.counter("items")
    hist = reg.histogram("work_seconds", max_samples=4096)
    stats = PipelineStats()

    def work(i: int) -> int:
        counter.inc()
        hist.observe(0.001 * (i % 7))
        stats.add("collate", 0.001)
        return i

    results = list(ordered_map(iter(range(400)), work, workers=4))
    assert results == list(range(400))  # order preserved
    snap = reg.snapshot()
    assert snap["counters"]["items"] == 400
    assert snap["histograms"]["work_seconds"]["count"] == 400
    assert stats.snapshot()["stage_counts"]["collate"] == 400


def test_histogram_percentiles():
    reg = MetricsRegistry()
    hist = reg.histogram("h")
    for v in range(1, 101):  # 1..100
        hist.observe(float(v))
    assert hist.percentile(0.5) == 51.0  # nearest-rank over 100 samples
    assert hist.percentile(0.95) == 96.0
    snap = hist.snapshot()
    assert snap["count"] == 100 and snap["min"] == 1.0 and snap["max"] == 100.0


def test_windowed_histogram_sees_spike_lifetime_ring_dilutes_it():
    """The autoscaler regression (fake clock): a load spike in the last
    few seconds must be VISIBLE in the sliding time window while the
    big sample ring still dilutes it below 1% — reacting to the ring
    means reacting to the lifetime average, i.e. never in time."""
    clk = FakeClock()
    reg = MetricsRegistry(clock=clk.now)
    hist = reg.histogram("lat", 4096, window_s=10.0)
    # 200 s of healthy 5 ms traffic (2000 samples)
    for _ in range(2000):
        hist.observe(0.005)
        clk.advance(0.1)
    # a spike: 15 requests at 2 s latency inside the last 5 seconds
    for _ in range(15):
        hist.observe(2.0)
        clk.advance(0.3)
    # ring (4096 cap holds all 2015): 15/2015 < 1% -> p99 stays healthy
    assert hist.percentile(0.99) == 0.005
    win = hist.window_snapshot()
    assert win["window_s"] == 10.0
    # the 10 s window holds the 4.5 s spike plus ~5.5 s of 5 ms
    # stragglers (≤56): ~70 samples where the spike is >20%, vs <1%
    # of the 2015-sample ring
    assert win["samples"] <= 75
    assert win["p99"] == 2.0, "spike invisible in the sliding window"
    # quiet period: the window EMPTIES instead of freezing the spike
    clk.advance(30.0)
    assert hist.window_snapshot()["samples"] == 0
    assert hist.window_snapshot()["p99"] is None


def test_windowless_histogram_has_no_window_snapshot():
    reg = MetricsRegistry()
    hist = reg.histogram("h2")
    hist.observe(1.0)
    assert hist.window_snapshot() is None


def test_serving_telemetry_snapshot_carries_slo_window():
    """ServingTelemetry surfaces both blocks: `slo` (sample ring) and
    `slo_window` (last-T-seconds) — and a spike shows up in the window
    block while the ring percentile lags."""
    from spacy_ray_tpu.serving.engine import ServingTelemetry

    clk = FakeClock()
    tel = ServingTelemetry(clock=clk.now, slo_window_s=10.0)
    for _ in range(1500):
        tel.request_completed(
            latency_s=0.004, queue_wait_s=0.001, t0=None, error=None
        )
        clk.advance(0.1)
    for _ in range(12):
        tel.request_completed(
            latency_s=1.5, queue_wait_s=1.0, t0=None, error=None,
            dispatch_wait_s=1.2,
        )
        clk.advance(0.2)
    snap = tel.snapshot()
    assert snap["slo"]["request_latency_p99"] == 0.004  # diluted
    win = snap["slo_window"]
    assert win["window_s"] == 10.0
    assert win["request_latency_p99"] == 1.5  # visible
    assert snap["slo"]["dispatch_wait_p99"] == 1.2


def test_merge_serving_snapshots_merges_slo_window():
    from spacy_ray_tpu.training.telemetry import merge_serving_snapshots

    a = {
        "counters": {}, "gauges": {}, "histograms": {},
        "slo": {"request_latency_p99": 0.01},
        "slo_window": {"window_s": 30.0, "samples": 90,
                       "request_latency_p99": 0.01},
    }
    b = {
        "counters": {}, "gauges": {}, "histograms": {},
        "slo": {"request_latency_p99": 0.5},
        "slo_window": {"window_s": 30.0, "samples": 10,
                       "request_latency_p99": 0.5},
    }
    merged = merge_serving_snapshots([a, b])
    win = merged["slo_window"]
    assert win["samples"] == 100
    # count-weighted mean + honest worst-replica bound
    assert abs(win["request_latency_p99"] - 0.059) < 1e-9
    assert win["request_latency_p99_worst"] == 0.5
    # replicas without a window block don't break the merge
    merged2 = merge_serving_snapshots(
        [a, {"counters": {}, "gauges": {}, "histograms": {}, "slo": {}}]
    )
    assert merged2["slo_window"]["samples"] == 90


def test_gauge_and_counter():
    reg = MetricsRegistry()
    reg.gauge("hbm").set(123.0)
    reg.counter("words").inc(5)
    reg.counter("words").inc(7)
    snap = reg.snapshot()
    assert snap["gauges"]["hbm"] == 123.0
    assert snap["counters"]["words"] == 12


# ----------------------------------------------------------------------
# Anomaly detectors: deterministic with fake clock + synthetic series
# ----------------------------------------------------------------------


def _detector(clk, **kw):
    events = []
    det = AnomalyDetectors(
        lambda event, message, **fields: events.append((event, fields)),
        clock=clk.now,
        **kw,
    )
    return det, events


def test_nan_loss_detector_fires():
    clk = FakeClock()
    det, events = _detector(clk)
    det.check_loss(1, 1.0)
    det.check_loss(2, float("nan"))
    det.check_loss(3, float("inf"))
    assert [e for e, _ in events] == ["nan-loss", "nan-loss"]
    assert events[0][1]["step"] == 2
    # the NaN must not poison the rolling history
    det.check_loss(4, 1.0)
    assert len(events) == 2


def test_loss_spike_detector_vs_rolling_median():
    clk = FakeClock()
    det, events = _detector(clk, spike_factor=4.0, spike_min_history=3)
    for step, loss in enumerate([1.0, 1.1, 0.9, 1.0], start=1):
        det.check_loss(step, loss)
    assert events == []  # steady series: no firing
    det.check_loss(5, 1.2)  # 1.2x median: fine
    assert events == []
    det.check_loss(6, 40.0)  # 40x the rolling median
    assert [e for e, _ in events] == ["loss-spike"]
    assert events[0][1]["step"] == 6
    assert events[0][1]["median"] == pytest.approx(1.0)


def test_step_time_regression_detector():
    clk = FakeClock()
    det, events = _detector(clk, step_factor=2.5, step_warmup=5)
    for step in range(1, 6):  # warmup: even a huge value must not fire
        det.check_step_time(step, 10.0 if step == 1 else 0.1)
    assert events == []
    for step in range(6, 10):
        det.check_step_time(step, 0.1)
    assert events == []
    det.check_step_time(10, 0.5)  # 5x the rolling p50 of 0.1
    assert [e for e, _ in events] == ["step-time-regression"]
    assert events[0][1]["p50"] == pytest.approx(0.1)


def test_recompile_after_warmup_detector():
    clk = FakeClock()
    det, events = _detector(clk, recompile_warmup_steps=50)
    det.check_compiles(10, 5)  # baseline
    det.check_compiles(40, 8)  # still warming up: compiles expected
    assert events == []
    det.check_compiles(60, 8)  # steady count: fine
    assert events == []
    det.check_compiles(80, 10)  # +2 compiles after warmup
    assert [e for e, _ in events] == ["recompile-after-warmup"]
    assert events[0][1]["new_compiles"] == 2


# ----------------------------------------------------------------------
# Knob validation
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "key,value",
    [
        ("trace_steps", [1]),
        ("trace_steps", [5, 1]),
        ("trace_steps", [-1, 5]),
        ("trace_steps", "0-50"),
        ("profile_window", [15, 5]),
        ("profile_window", "5-15"),
        ("metrics_dir", 5),
        ("anomaly_detection", "yes"),
        ("metrics_port", "8080"),
        ("metrics_port", -1),
        ("metrics_port", 70000),
    ],
)
def test_mistyped_telemetry_knobs_rejected(key, value):
    with pytest.raises(ValueError, match=f"\\[training\\] {key}"):
        validate_training({key: value})


def test_valid_telemetry_knobs_pass():
    validate_training(
        {
            "metrics_dir": "telemetry",
            "trace_steps": [0, 100],
            "profile_window": [2, 4],
            "anomaly_detection": False,
            "metrics_port": 9100,
        }
    )


# ----------------------------------------------------------------------
# Training-loop integration
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("teldata")
    write_synth_jsonl(d / "train.jsonl", 80, kind="tagger", seed=0)
    write_synth_jsonl(d / "dev.jsonl", 20, kind="tagger", seed=1)
    return d


def _config(tagger_config_text, data_dir, **over):
    cfg = Config.from_str(tagger_config_text)
    return cfg.apply_overrides(
        {
            "paths.train": str(data_dir / "train.jsonl"),
            "paths.dev": str(data_dir / "dev.jsonl"),
            "training.max_steps": 8,
            "training.eval_frequency": 4,
            **over,
        }
    )


def test_disabled_telemetry_constructs_nothing(
    tagger_config_text, data_dir, monkeypatch
):
    """The acceptance guard: with telemetry disabled the hot loop makes
    ZERO registry calls — enforced by making ANY construction of the
    registry or the facade an error."""

    def _boom(*a, **k):
        raise AssertionError("telemetry constructed on the disabled path")

    monkeypatch.setattr(telemetry_mod.Telemetry, "__init__", _boom)
    monkeypatch.setattr(telemetry_mod.MetricsRegistry, "__init__", _boom)
    # the PR 12 diagnosis layer rides inside Telemetry: with telemetry
    # off there must be zero rule evaluations, zero flight-ring writes,
    # zero incident I/O — any construction raises
    from spacy_ray_tpu import alerting as alerting_mod
    from spacy_ray_tpu import incidents as incidents_mod
    from spacy_ray_tpu.training import hoststats as hoststats_mod

    monkeypatch.setattr(alerting_mod.AlertEngine, "__init__", _boom)
    monkeypatch.setattr(incidents_mod.FlightRecorder, "__init__", _boom)
    # PR 18: the host sampler lives inside the facade — disabled
    # telemetry must read /proc exactly never
    monkeypatch.setattr(hoststats_mod.ProcessSampler, "__init__", _boom)
    cfg = _config(tagger_config_text, data_dir, **{"training.max_steps": 2})
    _, result = train(cfg, n_workers=1, stdout_log=False)
    assert result.final_step == 2


def test_telemetry_smoke_train_roundtrip(
    tagger_config_text, data_dir, tmp_path, monkeypatch
):
    """Acceptance criterion end-to-end: a CPU smoke run with telemetry on
    emits (a) a Perfetto-loadable trace with read/collate/transfer/step/
    eval/checkpoint spans, (b) a metrics.jsonl with per-step step-times
    and per-eval HBM/compile gauges, (c) a FaultPlan-driven NaN anomaly
    visible in metrics.jsonl, the jsonl training log, AND `telemetry
    summarize` — which parses the file round-trip."""
    monkeypatch.setenv(resilience.FAULT_PLAN_ENV, "step:3:nan")
    tel_dir = tmp_path / "tel"
    train_log = tmp_path / "train_log.jsonl"
    cfg = _config(
        tagger_config_text,
        data_dir,
        **{
            "training.metrics_dir": str(tel_dir),
            "training.logger": {
                "@loggers": "spacy_ray_tpu.JsonlLogger.v1",
                "path": str(train_log),
            },
        },
    )
    try:
        _, result = train(
            cfg, output_path=tmp_path / "out", n_workers=1, stdout_log=False
        )
    finally:
        resilience.set_fault_plan(None)  # the env plan must not leak
    assert result.final_step == 8

    # (b) metrics.jsonl: per-step step-time rows + per-eval gauge rows —
    # STRICT json even on the NaN row (bare NaN tokens would break every
    # non-Python consumer exactly when the anomaly the file exists to
    # capture occurs)
    def strict_json(s):
        def _reject(c):
            raise AssertionError(f"bare {c} token in jsonl output")
        return json.loads(s, parse_constant=_reject)

    metrics_path = tel_dir / "metrics.jsonl"
    rows = [strict_json(l) for l in open(metrics_path, encoding="utf8")]
    steps = [r for r in rows if r["kind"] == "step"]
    evals = [r for r in rows if r["kind"] == "eval"]
    anomalies = [r for r in rows if r["kind"] == "anomaly"]
    assert len(steps) == 8
    assert all(r["step_seconds"] > 0 for r in steps)
    assert len(evals) == 2
    for ev in evals:
        # gauges present on every backend; HBM is None on CPU (an honest
        # absence) but the KEY must be there for dashboards
        assert "hbm_peak_bytes" in ev and "compile_count" in ev
        assert isinstance(ev["compile_count"], int) and ev["compile_count"] > 0
        assert ev["step_seconds_p50"] > 0
        assert ev["input_pipeline"]["stage_seconds"]["collate"] > 0

    # (c) the injected NaN fired the detector into metrics.jsonl...
    assert any(a["anomaly"] == "nan-loss" for a in anomalies)
    # ...and into the jsonl training log via the log_event channel
    # (strict json there too: the NaN loss rides in the eval row's losses)
    log_rows = [strict_json(l) for l in open(train_log, encoding="utf8")]
    logged_events = [
        e["event"] for r in log_rows for e in r.get("events", [])
    ]
    assert "fault-injected" in logged_events and "nan-loss" in logged_events
    # jsonl rows carry the telemetry snapshot
    assert any(r.get("telemetry") for r in log_rows)

    # (a) Perfetto-loadable trace with every promised span family
    data = _schema_check_trace(tel_dir / "trace.json")
    names = {e["name"] for e in data["traceEvents"]}
    assert {
        "read", "collate", "transfer", "queue_wait", "step", "eval",
        "checkpoint_save",
    } <= names

    # round-trip: `telemetry summarize` parses what the run wrote
    text = summarize_metrics(metrics_path)
    assert "nan-loss" in text
    assert "collate" in text and "step-time p50" in text

    # and through the CLI surface
    from spacy_ray_tpu.cli import main as cli_main

    assert cli_main(["telemetry", "summarize", str(metrics_path)]) == 0


def test_trainer_metrics_port_serves_during_training(
    tagger_config_text, data_dir, tmp_path
):
    """[training] metrics_port wires the trainer's telemetry HTTP
    endpoint through a REAL train(): a poller thread scrapes /metrics
    (JSON + prometheus) and /healthz (clock anchor) while the loop runs;
    the listener is gone after train() returns (stopped in finally)."""
    import http.client
    import socket
    import threading

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    tel_dir = tmp_path / "tel"
    cfg = _config(
        tagger_config_text,
        data_dir,
        **{
            "training.metrics_dir": str(tel_dir),
            "training.metrics_port": port,
        },
    )
    scraped = {}
    stop = threading.Event()

    def poll():
        while not stop.is_set():
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", port, timeout=5.0
                )
                try:
                    conn.request("GET", "/healthz")
                    health = json.loads(conn.getresponse().read())
                finally:
                    conn.close()
                conn = http.client.HTTPConnection(
                    "127.0.0.1", port, timeout=5.0
                )
                try:
                    conn.request("GET", "/metrics?format=prometheus")
                    text = conn.getresponse().read().decode("utf8")
                finally:
                    conn.close()
                if "srt_training_steps_total" in text:
                    scraped["health"] = health
                    scraped["prometheus"] = text
                    return
            except OSError:
                pass
            stop.wait(0.05)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    try:
        _, result = train(cfg, n_workers=1, stdout_log=False)
    finally:
        stop.set()
        poller.join(timeout=10.0)
    assert result.final_step == 8
    assert "prometheus" in scraped, "endpoint never answered mid-train"
    assert scraped["health"]["role"] == "trainer"
    assert {"origin", "clock_now", "unix_now"} <= set(
        scraped["health"]["anchor"]
    )
    assert "# TYPE srt_training_steps_total counter" in scraped["prometheus"]
    # the listener died with the run
    import errno

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=2.0)
    try:
        with pytest.raises(OSError) as exc_info:
            conn.request("GET", "/healthz")
            conn.getresponse()
        assert exc_info.value.errno in (errno.ECONNREFUSED, None)
    finally:
        conn.close()


def test_telemetry_via_pooled_collation(tagger_config_text, data_dir, tmp_path):
    """Spans and stats populate identically when collation fans out over
    pool workers (and the single-threaded run above stays comparable)."""
    tel_dir = tmp_path / "tel"
    cfg = _config(
        tagger_config_text,
        data_dir,
        **{
            "training.metrics_dir": str(tel_dir),
            "training.collate_workers": 2,
            "training.max_steps": 4,
        },
    )
    _, result = train(cfg, n_workers=1, stdout_log=False)
    assert result.final_step == 4
    data = _schema_check_trace(tel_dir / "trace.json")
    names = {e["name"] for e in data["traceEvents"]}
    assert {"read", "collate", "transfer", "step"} <= names


def test_rearm_step_clock_excludes_eval_time(tmp_path):
    """The step after an eval must not absorb the eval+checkpoint
    duration into its measured step time (it would skew p95 and fire a
    spurious step-time regression at every eval boundary)."""
    clk = FakeClock()
    tel = Telemetry(tmp_path / "tel", clock=clk.now, anomaly_detection=False)
    tel.loop_start()
    clk.advance(0.1)
    tel.step_boundary(step=1, epoch=0, n_words=10, steps_run=1)
    clk.advance(5.0)  # a long eval + checkpoint save happens here
    tel.rearm_step_clock()
    clk.advance(0.1)
    tel.step_boundary(step=2, epoch=0, n_words=10, steps_run=2)
    tel.finalize()
    rows = [json.loads(l) for l in open(tmp_path / "tel" / "metrics.jsonl")]
    steps = [r for r in rows if r["kind"] == "step"]
    assert steps[0]["step_seconds"] == pytest.approx(0.1)
    assert steps[1]["step_seconds"] == pytest.approx(0.1)  # not 5.1


def test_summarize_handles_sanitized_nan_scores(tmp_path):
    """A run whose eval score went NaN (stored as the string "nan" by
    sanitize_json) must still summarize — that run IS the headline use
    case for the digest."""
    p = tmp_path / "metrics.jsonl"
    rows = [
        {"kind": "step", "step": 1, "step_seconds": 0.1, "words": 10},
        {"kind": "eval", "step": 1, "score": "nan", "loss_total": "nan",
         "compile_count": 3, "platform": "cpu"},
        {"kind": "eval", "step": 2, "score": 0.5, "loss_total": 1.0,
         "compile_count": 3, "platform": "cpu"},
        {"kind": "anomaly", "anomaly": "nan-loss", "step": 1,
         "message": "non-finite loss"},
    ]
    p.write_text("".join(json.dumps(r) + "\n" for r in rows), encoding="utf8")
    text = summarize_metrics(p)
    assert "last score 0.5000" in text  # the "nan" string is excluded
    assert "nan-loss" in text


def test_summarize_rejects_non_telemetry_file(tmp_path):
    p = tmp_path / "other.jsonl"
    p.write_text('{"foo": 1}\n{"bar": 2}\n', encoding="utf8")
    with pytest.raises(ValueError, match="no telemetry rows"):
        summarize_metrics(p)


def test_cli_telemetry_usage_errors(tmp_path, capsys):
    from spacy_ray_tpu.cli import main as cli_main

    assert cli_main(["telemetry"]) == 1
    assert cli_main(["telemetry", "summarize", str(tmp_path / "nope.jsonl")]) == 1


def test_profile_window_knob(tagger_config_text, data_dir, tmp_path):
    """The profiler window is configurable ([training] profile_window)
    instead of hardcoded 5-15 — a 3-step run can now capture a trace."""
    cfg = _config(
        tagger_config_text,
        data_dir,
        **{"training.max_steps": 3, "training.profile_window": [0, 2]},
    )
    train(cfg, n_workers=1, stdout_log=False, profile_dir=tmp_path / "prof")
    produced = [p for p in (tmp_path / "prof").rglob("*") if p.is_file()]
    assert produced, "profile_window [0, 2] produced no profiler artifacts"


def test_profile_window_inside_k_dispatch_stride(
    tagger_config_text, data_dir, tmp_path
):
    """A profile_window strictly inside one steps_per_dispatch stride must
    still fire: the loop caps k_this so a dispatch lands exactly on the
    window edges (start is only checked at dispatch boundaries)."""
    cfg = _config(
        tagger_config_text,
        data_dir,
        **{
            "training.max_steps": 8,
            "training.steps_per_dispatch": 4,
            "training.profile_window": [5, 7],
        },
    )
    train(cfg, n_workers=1, stdout_log=False, profile_dir=tmp_path / "prof")
    produced = [p for p in (tmp_path / "prof").rglob("*") if p.is_file()]
    assert produced, (
        "profile_window [5, 7] inside a K=4 stride produced no artifacts"
    )


def test_nan_fault_kind_rejected_at_unwired_sites():
    """Only the step site polls consume_poison — a nan rule anywhere else
    would be a silent no-op drill, so the plan rejects it loudly."""
    with pytest.raises(ValueError, match="only wired at the 'step' site"):
        resilience.FaultPlan.parse("collate:1:nan")


def test_nan_fault_kind_consumed_once():
    plan = resilience.FaultPlan.parse("step:2:nan")
    prev = resilience.set_fault_plan(plan)
    try:
        resilience.maybe_fail("step")
        assert not resilience.consume_poison("step")
        resilience.maybe_fail("step")  # call 2: the nan rule triggers
        assert resilience.consume_poison("step")
        assert not resilience.consume_poison("step")  # consumed exactly once
    finally:
        resilience.set_fault_plan(prev)
