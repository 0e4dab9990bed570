"""End-to-end training tests: the minimum slice (SURVEY.md §7 layer 3) —
config → pipeline → loop → improving scores → checkpoint/resume."""

import numpy as np
import pytest

from spacy_ray_tpu.config import Config
from spacy_ray_tpu.pipeline.language import Pipeline
from spacy_ray_tpu.training.loop import train, weighted_score
from spacy_ray_tpu.util import synth_corpus, write_synth_jsonl


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("data")
    write_synth_jsonl(d / "train.jsonl", 200, kind="tagger", seed=0)
    write_synth_jsonl(d / "dev.jsonl", 40, kind="tagger", seed=1)
    return d


def _config(tagger_config_text, data_dir, **over):
    cfg = Config.from_str(tagger_config_text)
    cfg = cfg.apply_overrides(
        {
            "paths.train": str(data_dir / "train.jsonl"),
            "paths.dev": str(data_dir / "dev.jsonl"),
            **over,
        }
    )
    return cfg


def test_train_tagger_learns(tagger_config_text, data_dir, tmp_path):
    cfg = _config(tagger_config_text, data_dir)
    nlp, result = train(cfg, output_path=tmp_path / "out", n_workers=1, stdout_log=False)
    assert result.final_step == 60
    # synthetic tags are word-recoverable: accuracy should be high
    assert result.best_score > 0.8, f"tagger failed to learn: {result.best_score}"
    assert (tmp_path / "out" / "best-model" / "params.npz").exists()
    assert (tmp_path / "out" / "last-model" / "train_meta.json").exists()


def test_model_roundtrip_and_predict(tagger_config_text, data_dir, tmp_path):
    cfg = _config(tagger_config_text, data_dir, **{"training.max_steps": 20})
    nlp, _ = train(cfg, output_path=tmp_path / "out", n_workers=1, stdout_log=False)
    reloaded = Pipeline.from_disk(tmp_path / "out" / "last-model")
    dev = synth_corpus(20, "tagger", seed=2)
    s1 = nlp.evaluate(dev)
    s2 = reloaded.evaluate(dev)
    assert s1["tag_acc"] == pytest.approx(s2["tag_acc"], abs=1e-6)
    doc = reloaded("the cat runs quickly")
    assert doc.tags is not None and len(doc.tags) == 4


def test_resume_continues_from_checkpoint(tagger_config_text, data_dir, tmp_path):
    cfg = _config(tagger_config_text, data_dir, **{"training.max_steps": 20})
    _, r1 = train(cfg, output_path=tmp_path / "out", n_workers=1, stdout_log=False)
    assert r1.final_step == 20
    cfg2 = _config(tagger_config_text, data_dir, **{"training.max_steps": 40})
    _, r2 = train(cfg2, output_path=tmp_path / "out", n_workers=1, resume=True, stdout_log=False)
    # resumed from step 20, so only 20 more steps were run
    assert r2.final_step == 40


def test_gradient_accumulation_runs(tagger_config_text, data_dir, tmp_path):
    cfg = _config(
        tagger_config_text,
        data_dir,
        **{"training.max_steps": 10, "training.accumulate_gradient": 2},
    )
    _, result = train(cfg, n_workers=1, stdout_log=False)
    assert result.final_step == 10


def test_weighted_score():
    assert weighted_score({"a": 0.5, "b": 1.0}, {"a": 0.6, "b": 0.4}) == pytest.approx(0.7)
    assert weighted_score({"a": 0.5}, {}) == pytest.approx(0.5)
    assert weighted_score({"a": 0.5, "b": 0.9}, {"a": 1.0, "b": None}) == pytest.approx(0.5)


def test_frozen_component_not_updated(tagger_config_text, data_dir):
    cfg = _config(
        tagger_config_text,
        data_dir,
        **{"training.max_steps": 5, "training.frozen_components": ["tok2vec"]},
    )
    from spacy_ray_tpu.training.loop import train as train_fn

    nlp, _ = train_fn(cfg, n_workers=1, stdout_log=False)
    # train again without freezing; compare tok2vec params drift
    import jax

    cfg2 = _config(tagger_config_text, data_dir, **{"training.max_steps": 5})
    nlp2, _ = train_fn(cfg2, n_workers=1, stdout_log=False)

    def leaves(params):
        return jax.tree_util.tree_leaves(params)

    # frozen run: tok2vec params identical to a fresh init with same seed
    fresh = Pipeline.from_config(cfg.interpolate())
    fresh.initialize(lambda: iter(synth_corpus(50, "tagger", 0)), seed=0)
    frozen_leaves = leaves(nlp.params["tok2vec"])
    fresh_leaves = leaves(fresh.params["tok2vec"])
    for a, b in zip(frozen_leaves, fresh_leaves):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_resume_is_exact(tagger_config_text, data_dir, tmp_path):
    """Resume must continue the EXACT run: same shuffle order, same data
    position within the epoch, same rng chain — so straight-through and
    checkpoint+resume end with identical params (pre-fix, resume replayed
    the stream from the epoch-0 start and diverged)."""
    import jax

    over = {
        "training.eval_frequency": 10,
        "corpora.train.shuffle": True,
        "corpora.train.seed": 3,
    }
    cfg_a = _config(tagger_config_text, data_dir, **{"training.max_steps": 40, **over})
    nlp_a, _ = train(cfg_a, output_path=tmp_path / "a", n_workers=1, stdout_log=False)

    cfg_b1 = _config(tagger_config_text, data_dir, **{"training.max_steps": 20, **over})
    _, rb1 = train(cfg_b1, output_path=tmp_path / "b", n_workers=1, stdout_log=False)
    assert rb1.final_step == 20
    cfg_b2 = _config(tagger_config_text, data_dir, **{"training.max_steps": 30, **over})
    _, rb2 = train(
        cfg_b2, output_path=tmp_path / "b", n_workers=1, resume=True, stdout_log=False
    )
    assert rb2.final_step == 30
    # second resume: the mid-epoch position saved DURING a resumed run must
    # be absolute from the epoch start, not relative to the resume point
    cfg_b3 = _config(tagger_config_text, data_dir, **{"training.max_steps": 40, **over})
    nlp_b, rb3 = train(
        cfg_b3, output_path=tmp_path / "b", n_workers=1, resume=True, stdout_log=False
    )
    assert rb3.final_step == 40

    la = jax.tree_util.tree_leaves(nlp_a.params)
    lb = jax.tree_util.tree_leaves(nlp_b.params)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_eval_matches_replicated(tagger_config_text, data_dir):
    """Eval with dev batches sharded over the data axis must score
    identically to plain single-device eval (VERDICT r1 weak #10)."""
    from spacy_ray_tpu.parallel.mesh import build_mesh
    from spacy_ray_tpu.parallel.step import place_replicated

    cfg = _config(tagger_config_text, data_dir, **{"training.max_steps": 20})
    nlp, _ = train(cfg, n_workers=1, stdout_log=False)
    dev = synth_corpus(30, "tagger", seed=5)

    plain = nlp.evaluate(dev)
    mesh = build_mesh(n_data=8)
    sharded = nlp.evaluate(
        dev, place_replicated(nlp.params, mesh), mesh=mesh
    )
    assert plain.keys() == sharded.keys()
    for k in plain:
        assert plain[k] == pytest.approx(sharded[k], abs=1e-6), k


def test_console_logger_elapsed_column_and_progress(tagger_config_text, data_dir, tmp_path):
    """The console table leads with a wall-clock elapsed column (reference
    loggers.py:52) and progress_bar=True draws/clears an in-place bar on
    stderr between rows."""
    import io
    import re

    from spacy_ray_tpu.registry import registry
    from spacy_ray_tpu.training.loop import train
    from spacy_ray_tpu.config import Config

    cfg = Config.from_str(tagger_config_text).apply_overrides(
        {
            "paths.train": str(data_dir / "train.jsonl"),
            "paths.dev": str(data_dir / "dev.jsonl"),
        }
    )
    nlp = __import__("spacy_ray_tpu.pipeline.language", fromlist=["Pipeline"]).Pipeline.from_config(cfg)
    setup = registry.get("loggers", "spacy_ray_tpu.ConsoleLogger.v1")(progress_bar=True)
    out, err = io.StringIO(), io.StringIO()
    log_step, finalize = setup(nlp, out, err)
    header = out.getvalue().splitlines()[0]
    assert header.split()[0] == "T"
    log_step(None)  # non-eval step -> progress bar on stderr
    assert "1/" in err.getvalue() or "+1" in err.getvalue()
    log_step(
        {"epoch": 0, "step": 5, "words": 100, "losses": {}, "other_scores": {},
         "score": 0.5, "wps": 10.0, "eval_seconds": 0.1}
    )
    finalize()
    row = out.getvalue().splitlines()[2]
    assert re.match(r"\s*\d+:\d\d:\d\d\b", row), row


def test_profile_flag_writes_trace(tagger_config_text, data_dir, tmp_path):
    """--profile captures a jax.profiler trace of steps 5-15 (SURVEY §5.1:
    tracing is first-class here, unlike the reference's unwired timers)."""
    from spacy_ray_tpu.config import Config
    from spacy_ray_tpu.training.loop import train

    cfg = Config.from_str(tagger_config_text).apply_overrides(
        {
            "paths.train": str(data_dir / "train.jsonl"),
            "paths.dev": str(data_dir / "dev.jsonl"),
            "training.max_steps": 20,
            "training.eval_frequency": 10,
        }
    )
    train(cfg, n_workers=1, stdout_log=False, profile_dir=tmp_path / "trace")
    produced = list((tmp_path / "trace").rglob("*"))
    assert any(p.is_file() for p in produced), (
        f"no profiler artifacts under {tmp_path/'trace'}: {produced}"
    )


def test_checkpoint_save_is_crash_safe(tmp_path):
    """A crash mid-save must leave the previous complete generation
    loadable: array files are generation-stamped and the meta (written
    last, atomically) names the generation it points at."""
    import numpy as np

    from spacy_ray_tpu.training.checkpoint import TrainCheckpoint

    params = {"c": {"w": np.ones((2, 2), np.float32)}}
    opt = {"m": np.zeros((2, 2), np.float32)}
    import jax

    rng = jax.random.PRNGKey(0)
    TrainCheckpoint.save(
        tmp_path, params=params, opt_state=opt, step=1, epoch=0, rng=rng,
        best_score=0.5, best_step=1,
    )
    # simulate a crash DURING the next save: new stamped params written
    # (corrupt!) but the meta replace never happened
    (tmp_path / "params-2.npz").write_bytes(b"truncated garbage")
    ck = TrainCheckpoint.load(tmp_path)
    assert ck is not None and ck["step"] == 1
    assert np.array_equal(np.asarray(ck["params"]["c"]["w"]), np.ones((2, 2)))

    # a completed second save supersedes; the previous generation is
    # RETAINED (keep=2 default) so a torn newest generation can fall back
    params2 = {"c": {"w": 2 * np.ones((2, 2), np.float32)}}
    TrainCheckpoint.save(
        tmp_path, params=params2, opt_state=opt, step=2, epoch=0, rng=rng,
        best_score=0.6, best_step=2,
    )
    ck = TrainCheckpoint.load(tmp_path)
    assert ck["step"] == 2
    assert np.array_equal(np.asarray(ck["params"]["c"]["w"]), 2 * np.ones((2, 2)))
    assert (tmp_path / "params-1.npz").exists()  # history, not garbage
    # ... and a third save rotates generation 1 out (beyond keep=2)
    TrainCheckpoint.save(
        tmp_path, params=params2, opt_state=opt, step=3, epoch=0, rng=rng,
        best_score=0.6, best_step=2,
    )
    assert not (tmp_path / "params-1.npz").exists()
    assert (tmp_path / "params-2.npz").exists()
