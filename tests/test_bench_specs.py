"""bench.py spec-shape and rigor-machinery tests (VERDICT r4 next #2/#6/#7):
dispersion fields, the accelerator-gated hardware-shaped trf spec, per-spec
timeouts, and the headline-summary-last ordering fix."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent.parent))

import bench


def _by_name(platform):
    return {s["name"]: s for s in bench._configs(platform)}


def test_trf_realistic_gated_to_accelerators():
    cpu = _by_name("cpu")
    tpu = _by_name("tpu")
    assert "trf_realistic" not in cpu
    spec = tpu["trf_realistic"]
    # hardware-shaped: batch_by_words-scale tokens per step (>= 8K)
    assert spec["B"] * spec["T"] >= 8192
    # staged compiles ascend strictly in token count up to the full shape
    sizes = [b * t for b, t in spec["stages"]] + [spec["B"] * spec["T"]]
    assert sizes == sorted(sizes) and len(set(sizes)) == len(sizes)
    assert spec["timeout"] >= 3600


def test_trf_family_cpu_steps_at_least_10():
    # r4 weak #1: 3-step CPU timings at toy shapes swung 2.6x between
    # sessions; every config now times >= 10 steps per repetition
    for name, spec in _by_name("cpu").items():
        assert spec["steps"] >= 10, f"{name}: {spec['steps']} timed steps"


def test_all_specs_have_rep_defaults():
    assert bench.N_REPS >= 3


def test_round7_fixed_floor_ab_arms():
    """The round-7 A/B arms exist with honest knob combinations: the
    fused arms ride every suite; the bf16 pairs (the dtype regime where
    the shadow acts) are manual_only evidence arms, shadow implies a
    pinned bf16 trunk, and each A/B pair shares its baseline's shape."""
    cpu = _by_name("cpu")
    assert cpu["trf_fused"]["fused"] and "trf_fused" in _by_name("tpu")
    assert cpu["trf_realistic_cpu_fused"]["fused"]
    assert (cpu["trf_realistic_cpu_fused"]["B"], cpu["trf_realistic_cpu_fused"]["T"]) == (
        cpu["trf_realistic_cpu"]["B"], cpu["trf_realistic_cpu"]["T"]
    )
    for base, arm in (("trf_bf16", "trf_bf16_shadow"),
                      ("trf_bf16_realistic", "trf_bf16_realistic_shadow")):
        b, a = cpu[base], cpu[arm]
        assert b["manual_only"] and a["manual_only"]
        assert b["compute_dtype"] == a["compute_dtype"] == "bfloat16"
        assert not b.get("shadow") and a["shadow"] and a["fused"]
        assert (b["B"], b["T"]) == (a["B"], a["T"])


def test_headline_summary_prefers_flagship(tmp_path, monkeypatch, capsys):
    session = tmp_path / "session.jsonl"
    monkeypatch.setattr(bench, "SESSION_FILE", session)
    recs = [
        {"name": "cnn_tagger", "metric": "m1", "value": 1.0, "platform": "cpu"},
        {"name": "trf", "metric": "m2", "value": 2.0, "platform": "cpu"},
        {"name": "trf_longseq_noflash", "metric": "m3", "value": 3.0,
         "platform": "cpu"},
    ]
    session.write_text("".join(json.dumps(r) + "\n" for r in recs))
    bench._print_headline_summary(0, ["cpu"])
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    # trf outranks cnn_tagger; the last-run config (longseq) never wins
    assert summary["name"] == "headline_summary"
    assert summary["headline_of"] == "trf"
    assert summary["value"] == 2.0
    assert summary["metric"].startswith("HEADLINE")


def test_headline_summary_only_reads_past_mark(tmp_path, monkeypatch, capsys):
    session = tmp_path / "session.jsonl"
    monkeypatch.setattr(bench, "SESSION_FILE", session)
    stale = json.dumps(
        {"name": "trf", "metric": "old", "value": 9.0, "platform": "cpu"}
    ) + "\n"
    session.write_text(stale)
    mark = session.stat().st_size
    with open(session, "a") as f:
        f.write(json.dumps(
            {"name": "cnn_tagger", "metric": "new", "value": 1.0,
             "platform": "cpu"}
        ) + "\n")
    bench._print_headline_summary(mark, ["cpu"])
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # the stale trf record from a previous session must not be the headline
    assert summary["headline_of"] == "cnn_tagger"


def test_headline_summary_ignores_foreign_platform(tmp_path, monkeypatch, capsys):
    """A concurrent campaign's TPU record appended mid-suite must not
    become a CPU run's headline; torn half-written lines are skipped."""
    session = tmp_path / "session.jsonl"
    monkeypatch.setattr(bench, "SESSION_FILE", session)
    session.write_text(
        json.dumps({"name": "trf_realistic", "metric": "m", "value": 99.0,
                    "platform": "tpu"}) + "\n"
        + '{"name": "trf", "metric": "torn'  # no newline: torn write
        + "\n"
        + json.dumps({"name": "cnn_tagger", "metric": "m", "value": 1.0,
                      "platform": "cpu"}) + "\n"
    )
    bench._print_headline_summary(0, ["cpu"])
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["headline_of"] == "cnn_tagger"
    assert summary["platform"] == "cpu"


def test_headline_summary_run_id_filter(tmp_path, monkeypatch, capsys):
    """A same-platform record from a CONCURRENT campaign (different run_id)
    must not be re-labeled as this run's headline."""
    session = tmp_path / "session.jsonl"
    monkeypatch.setattr(bench, "SESSION_FILE", session)
    recs = [
        {"name": "trf", "metric": "m", "value": 9.0, "platform": "tpu",
         "run_id": "other-123"},
        {"name": "cnn_tagger", "metric": "m", "value": 1.0, "platform": "tpu",
         "run_id": "mine-456"},
    ]
    session.write_text("".join(json.dumps(r) + "\n" for r in recs))
    bench._print_headline_summary(0, ["tpu"], run_id="mine-456")
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["headline_of"] == "cnn_tagger"
    assert summary["run_id"] == "mine-456"


def test_parent_runs_each_config_once_and_fails_with_its_children(
    tmp_path, monkeypatch, capsys
):
    """Parent mode on the chip: every config is dispatched exactly once,
    for the TPU (never re-dispatched on the CPU), stamped with one run id —
    and a child that exits non-zero makes the whole run exit non-zero,
    naming it, after the others still ran."""
    monkeypatch.setattr(bench, "SESSION_FILE", tmp_path / "session.jsonl")
    calls = []

    def fake_child(name, cpu=False, env=None, timeout=None):
        calls.append((name, cpu, (env or {}).get("SRT_BENCH_RUN_ID")))
        return 1 if len(calls) == 1 else 0  # the first config fails

    monkeypatch.setattr(bench, "_run_spec_subprocess", fake_child)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    wanted = [
        s["name"] for s in bench._configs("tpu") if not s.get("manual_only")
    ]
    assert [c[0] for c in calls] == wanted  # once each, accel_only included
    assert not any(cpu for (_, cpu, _) in calls)
    assert len({c[2] for c in calls}) == 1 and calls[0][2]
    assert exc.value.code not in (0, None)
    assert wanted[0] in str(exc.value.code)


def test_child_without_cpu_flag_requires_the_tpu(monkeypatch):
    """``bench.py --configs X`` without ``--cpu`` on a machine with no TPU
    exits non-zero and names the platform JAX found; nothing is measured
    on the CPU in the chip's place."""
    ran = []
    monkeypatch.setattr(bench, "run_one", lambda *a, **k: ran.append(a))
    monkeypatch.setattr(sys, "argv", ["bench.py", "--configs", "cnn_tagger"])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert "JAX found no tpu" in str(exc.value.code)
    assert "'cpu'" in str(exc.value.code)
    assert ran == []


def test_config_that_raises_fails_the_run(tmp_path, monkeypatch, capsys):
    """A config that raises is reported, the others still run, and the
    process exits non-zero (it used to catch the failure and exit 0)."""
    monkeypatch.setattr(bench, "SESSION_FILE", tmp_path / "s.jsonl")
    monkeypatch.setattr(bench, "BASELINE_FILE", tmp_path / "none.json")
    monkeypatch.setattr(
        bench, "_configs", lambda platform: [dict(name="a"), dict(name="b")]
    )

    def fake_run_one(spec, platform):
        if spec["name"] == "a":
            raise RuntimeError("boom")
        return {"name": "b", "value": 2.0, "metric": "m"}

    monkeypatch.setattr(bench, "run_one", fake_run_one)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--configs", "a,b", "--cpu"])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert "a" in str(exc.value.code)
    out = capsys.readouterr().out
    assert "a: FAILED RuntimeError: boom" in out
    assert json.loads(out.strip().splitlines()[-1])["name"] == "b"


def test_measure_baseline_keeps_cleaner_entry(tmp_path, monkeypatch, capsys):
    """--measure-baseline must not overwrite a clean denominator with a
    contended (depressed) one — that would inflate every future
    vs_baseline ratio."""
    baseline = tmp_path / "base.json"
    baseline.write_text(json.dumps({
        "cnn_tagger": {"name": "cnn_tagger", "value": 2800.0,
                       "peak_reprobe_ratio": 0.99, "contended": False},
    }))
    monkeypatch.setattr(bench, "BASELINE_FILE", baseline)
    monkeypatch.setattr(bench, "SESSION_FILE", tmp_path / "s.jsonl")
    contended_rec = {"name": "cnn_tagger", "value": 2500.0, "metric": "m",
                     "peak_reprobe_ratio": 0.85, "contended": True}
    clean_rec = {"name": "trf", "value": 9.0, "metric": "m",
                 "peak_reprobe_ratio": 0.98, "contended": False}

    def fake_configs(platform):
        return [dict(name="cnn_tagger"), dict(name="trf")]

    results = {"cnn_tagger": contended_rec, "trf": clean_rec}
    monkeypatch.setattr(bench, "_configs", fake_configs)
    monkeypatch.setattr(
        bench, "run_one", lambda spec, platform: dict(results[spec["name"]])
    )
    monkeypatch.setattr(sys, "argv", ["bench.py", "--measure-baseline"])
    bench.main()
    out = capsys.readouterr().out
    assert "keeping previous baseline" in out
    merged = json.loads(baseline.read_text())
    assert merged["cnn_tagger"]["value"] == 2800.0  # clean entry survived
    assert merged["trf"]["value"] == 9.0  # clean new record written


def test_headline_summary_no_records(tmp_path, monkeypatch, capsys):
    session = tmp_path / "session.jsonl"
    session.write_text("")
    monkeypatch.setattr(bench, "SESSION_FILE", session)
    bench._print_headline_summary(0, ["cpu"])
    assert "no headline-eligible record" in capsys.readouterr().out


def test_child_zero_config_match_exits_nonzero(monkeypatch):
    """An accel_only spec asked for with --cpu matches nothing in
    _configs('cpu'): the child must exit non-zero instead of silently
    losing the flagship record."""
    monkeypatch.setattr(
        sys, "argv", ["bench.py", "--configs", "trf_realistic", "--cpu"]
    )
    try:
        bench.main()
    except SystemExit as e:
        assert e.code == 3
    else:
        raise AssertionError("expected SystemExit(3)")


def test_trf_moe_spec_shape():
    tpu = _by_name("tpu")
    assert "trf_moe" not in _by_name("cpu")
    spec = tpu["trf_moe"]
    assert "n_experts = 8" in spec["cfg"]
    sizes = [b * t for b, t in spec["stages"]] + [spec["B"] * spec["T"]]
    assert sizes == sorted(sizes) and len(set(sizes)) == len(sizes)


@pytest.mark.slow
def test_run_one_scales_reps_to_min_seconds(monkeypatch):
    """A config whose nominal step count finishes in well under
    MIN_REP_SECONDS gets its per-rep step count scaled up (sub-second
    timing windows showed the worst run-to-run drift — PERF.md)."""
    from spacy_ray_tpu.presets import CNN_TAGGER_CFG

    spec = dict(
        name="tiny_probe",
        metric="m",
        cfg=CNN_TAGGER_CFG.format(width=32, depth=1, embed_size=200),
        kinds=["tagger"],
        B=8, T=16, steps=2, warmup=1, n_reps=1,
    )
    rec = bench.run_one(spec, "cpu")
    assert rec is not None
    assert rec["steps_per_rep"] > 2, rec["steps_per_rep"]
    # each rep must have measured at least ~MIN_REP_SECONDS of work
    # (within the one-probe-step estimate's slack)
    assert rec["steps_per_rep"] * rec["value"] > 0
    # every record carries its telemetry block: compile delta (this spec
    # compiled at least the full-shape step), HBM + live-buffer gauges
    tel = rec["telemetry"]
    assert tel["compile_count"] > 0
    assert "hbm_peak_bytes" in tel and "live_buffers" in tel


def test_run_one_e2e_records_stage_seconds(monkeypatch):
    """The e2e variant's record includes per-stage host seconds from the
    training loop's own PipelineStats — the bench trajectory captures
    where batch-preparation time went, not just the rate."""
    from spacy_ray_tpu.presets import CNN_TAGGER_CFG

    spec = dict(
        name="tiny_e2e_probe",
        metric="m",
        cfg=CNN_TAGGER_CFG.format(width=32, depth=1, embed_size=200),
        kinds=["tagger"],
        B=8, T=16, steps=2, warmup=1, n_reps=1, e2e=True,
    )
    monkeypatch.setattr(bench, "MIN_REP_SECONDS", 0.2)  # keep the probe fast
    rec = bench.run_one(spec, "cpu")
    assert rec is not None
    stages = rec["telemetry"]["input_pipeline"]["stage_seconds"]
    assert stages["collate"] > 0 and stages["transfer"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("spec_name", ["trf_realistic", "trf_moe"])
def test_accel_spec_first_stage_compiles_on_cpu(spec_name):
    """The accelerator-gated specs must not be dead code: their pipelines
    build and the smallest compile stage (B=4, T=32) runs one real update
    on the CPU host (VERDICT r4 next #6 'compiles in the dryrun-sized
    stage on CPU')."""
    import jax

    from spacy_ray_tpu.config import Config
    from spacy_ray_tpu.pipeline.language import Pipeline
    from spacy_ray_tpu.parallel.mesh import build_mesh
    from spacy_ray_tpu.parallel.step import (
        make_train_step,
        place_batch,
        place_replicated,
        shard_opt_state,
    )
    from spacy_ray_tpu.registry import registry

    spec = _by_name("tpu")[spec_name]
    sb, st = spec["stages"][0]
    nlp = Pipeline.from_config(Config.from_str(spec["cfg"]))
    examples = bench._corpus(spec["kinds"], max(2 * sb, 16))
    nlp.initialize(lambda: iter(examples), seed=0)
    mesh = build_mesh(n_data=1)
    tx = registry.get("optimizers", "Adam.v1")(learn_rate=0.001)
    params = place_replicated(nlp.params, mesh)
    opt_state = shard_opt_state(tx.init(params), mesh, zero1=False)
    update = make_train_step(nlp.make_loss_fn(), tx, mesh,
                             opt_state_template=opt_state)
    batch = nlp.collate(examples[:sb], pad_batch_to=sb, pad_len_to=st)
    tokens = place_batch(batch["tokens"], mesh)
    targets = place_batch(batch["targets"], mesh)
    params, opt_state, loss, _ = update(
        params, opt_state, tokens, targets, jax.random.PRNGKey(0)
    )
    assert float(jax.block_until_ready(loss)) > 0


def test_headline_summary_prefers_clean_session_record(tmp_path, monkeypatch,
                                                       capsys):
    """A contended flagship record (post-run matmul re-probe < 0.94) must
    not stamp the round artifact when the session holds a clean record of
    the same config (VERDICT r5 next #1)."""
    session = tmp_path / "session.jsonl"
    monkeypatch.setattr(bench, "SESSION_FILE", session)
    clean_old = {"name": "trf", "metric": "m", "value": 9.6, "platform": "cpu",
                 "peak_reprobe_ratio": 0.97, "recorded_at": "2026-08-01"}
    contended_new = {"name": "trf", "metric": "m", "value": 8.1,
                     "platform": "cpu", "peak_reprobe_ratio": 0.82}
    session.write_text(json.dumps(clean_old) + "\n")
    mark = session.stat().st_size
    with open(session, "a") as f:
        f.write(json.dumps(contended_new) + "\n")
    bench._print_headline_summary(mark, ["cpu"])
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["headline_of"] == "trf"
    assert summary["value"] == 9.6  # the clean record, not this run's
    assert summary["contended_run_value"] == 8.1
    assert "contended" in summary["headline_note"]


def test_headline_summary_contended_without_clean_alternative(tmp_path,
                                                              monkeypatch,
                                                              capsys):
    """No clean record exists: the contended one still prints (a flagged
    lower bound beats no headline), unmodified."""
    session = tmp_path / "session.jsonl"
    monkeypatch.setattr(bench, "SESSION_FILE", session)
    rec = {"name": "trf", "metric": "m", "value": 8.1, "platform": "cpu",
           "peak_reprobe_ratio": 0.82}
    session.write_text(json.dumps(rec) + "\n")
    bench._print_headline_summary(0, ["cpu"])
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["value"] == 8.1
    assert "headline_note" not in summary


def test_headline_summary_skips_skip_markers(tmp_path, monkeypatch, capsys):
    """A skipped-spec marker (value null) appended by the rc=4 path must
    never be selected as a headline."""
    session = tmp_path / "session.jsonl"
    monkeypatch.setattr(bench, "SESSION_FILE", session)
    session.write_text(
        json.dumps({"name": "trf_realistic", "metric": "m", "value": None,
                    "platform": "tpu", "skipped": True}) + "\n"
        + json.dumps({"name": "cnn_tagger", "metric": "m", "value": 1.0,
                      "platform": "tpu"}) + "\n"
    )
    bench._print_headline_summary(0, ["tpu"])
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["headline_of"] == "cnn_tagger"


def test_zipf_ranks_deterministic_and_skewed():
    """The Zipfian sampler behind the edge-cache spec: deterministic
    given the seed (committed records are reproducible), full index
    range, and actually Zipf-skewed (rank 1 dominates; the top decile
    of keys draws the majority of requests at s=1.1)."""
    a = bench.zipf_ranks(64, 5000, s=1.1, seed=1)
    b = bench.zipf_ranks(64, 5000, s=1.1, seed=1)
    assert a == b
    assert min(a) >= 0 and max(a) < 64
    counts = [a.count(r) for r in range(64)]
    assert counts[0] == max(counts)  # rank 1 is the hottest key
    top = sum(sorted(counts, reverse=True)[:7])  # top ~10% of 64 keys
    assert top / len(a) > 0.4, "distribution not meaningfully skewed"
    # higher exponent = more skew
    hot = bench.zipf_ranks(64, 5000, s=2.0, seed=1)
    assert hot.count(0) > a.count(0)
