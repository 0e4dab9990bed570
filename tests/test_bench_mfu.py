"""Unit tests for bench.py's MFU accounting + session persistence
(VERDICT r3 next #1): peak-FLOPs resolution self-heals a corrupt cache,
the FLOPs probe falls back to analytical 6ND, and completed records are
persisted append-as-you-go."""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

import bench


def test_peak_cache_non_dict_self_heals(tmp_path, monkeypatch):
    cache = tmp_path / "peak.json"
    cache.write_text("[]")  # valid JSON, wrong shape (truncated/hand-edited)
    monkeypatch.setattr(bench, "PEAK_CACHE_FILE", cache)
    peak, kind = bench._peak_flops_per_chip("cpu")
    assert peak > 0
    assert "measured matmul" in kind
    # the re-measured value must have been cached back as a dict
    assert isinstance(json.loads(cache.read_text()), dict)


def test_peak_cache_hit_skips_measurement(tmp_path, monkeypatch):
    cache = tmp_path / "peak.json"
    monkeypatch.setattr(bench, "PEAK_CACHE_FILE", cache)
    monkeypatch.setattr(
        bench, "_measure_matmul_peak", lambda platform: 123.0e9
    )
    peak1, _ = bench._peak_flops_per_chip("cpu")
    assert peak1 == 123.0e9
    # second call must come from the cache, not a re-measure
    monkeypatch.setattr(
        bench, "_measure_matmul_peak",
        lambda platform: (_ for _ in ()).throw(AssertionError("re-measured")),
    )
    peak2, _ = bench._peak_flops_per_chip("cpu")
    assert peak2 == 123.0e9


def test_program_flops_analytical_fallback():
    class BrokenUpdate:
        def lower(self, *args):
            raise RuntimeError("no cost analysis on this backend")

    flops, kind = bench._program_flops(
        BrokenUpdate(), (None, None, None, None, None),
        n_params=1000, n_tokens=50,
    )
    assert kind == "analytical_6ND"
    assert flops == 6.0 * 1000 * 50


def test_append_session_jsonl(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "SESSION_FILE", tmp_path / "session.jsonl")
    rec = {"name": "cnn_tagger", "value": 1.0, "mfu": 0.5}
    bench._append_session(rec, "cpu")
    lines = (tmp_path / "session.jsonl").read_text().splitlines()
    assert len(lines) == 1
    stamped = json.loads(lines[0])
    assert stamped["name"] == "cnn_tagger" and "recorded_at" in stamped

    bench._append_session(rec, "tpu")
    bench._append_session({"name": "trf", "value": 2.0}, "tpu")
    # append-only, whatever the platform: nothing is merged or overwritten
    assert len((tmp_path / "session.jsonl").read_text().splitlines()) == 3
    assert sorted(p.name for p in tmp_path.iterdir()) == ["session.jsonl"]
