"""`parse` command: bulk parallel inference over a corpus (the reference
README advertises `spacy ray parse` as planned surface, README.md:15).
Covers: training a model, parsing .spacy input sharded over the 8-device
mesh, raw-.txt input through the tokenizer, and jsonl/.spacy outputs."""

import json

import pytest

from spacy_ray_tpu.cli import main as cli_main
from spacy_ray_tpu.config import Config
from spacy_ray_tpu.util import write_synth_jsonl


@pytest.fixture(scope="module")
def trained_model(tagger_config_text, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("parse_model")
    write_synth_jsonl(tmp / "train.jsonl", 120, kind="tagger", seed=0)
    write_synth_jsonl(tmp / "dev.jsonl", 30, kind="tagger", seed=1)
    from spacy_ray_tpu.training.loop import train

    cfg = Config.from_str(tagger_config_text).apply_overrides(
        {
            "paths.train": str(tmp / "train.jsonl"),
            "paths.dev": str(tmp / "dev.jsonl"),
            "training.max_steps": 40,
        }
    )
    train(cfg, output_path=tmp / "out", n_workers=1, stdout_log=False)
    return tmp / "out" / "best-model"


def test_parse_spacy_input_jsonl_output(trained_model, tmp_path):
    write_synth_jsonl(tmp_path / "in.jsonl", 40, kind="tagger", seed=2)
    assert cli_main([
        "convert", str(tmp_path / "in.jsonl"), str(tmp_path / "in.spacy"),
    ]) == 0
    assert cli_main([
        "parse", str(trained_model), str(tmp_path / "in.spacy"),
        str(tmp_path / "out.jsonl"), "--device", "cpu",
    ]) == 0
    rows = [json.loads(l) for l in (tmp_path / "out.jsonl").read_text().splitlines()]
    assert len(rows) == 40
    # predictions, not gold: every doc must carry model-assigned tags
    assert all(r.get("tags") and all(t for t in r["tags"]) for r in rows)


def test_parse_txt_input_docbin_output(trained_model, tmp_path):
    (tmp_path / "raw.txt").write_text("the cat runs .\nthe dog sleeps .\n")
    assert cli_main([
        "parse", str(trained_model), str(tmp_path / "raw.txt"),
        str(tmp_path / "out.spacy"), "--device", "cpu",
    ]) == 0
    from spacy_ray_tpu.training.corpus import _iter_path

    docs = list(_iter_path(tmp_path / "out.spacy"))
    assert len(docs) == 2
    assert [t for t in docs[0].words] == ["the", "cat", "runs", "."]
    assert all(docs[0].tags), docs[0].tags


def test_benchmark_speed_and_accuracy(trained_model, tmp_path, capsys):
    """`benchmark speed` reports median/min/max words/s over reps;
    `benchmark accuracy` is the spaCy-CLI name for evaluate."""
    write_synth_jsonl(tmp_path / "dev.jsonl", 20, kind="tagger", seed=4)
    rc = cli_main([
        "benchmark", "speed", str(trained_model), str(tmp_path / "dev.jsonl"),
        "--device", "cpu", "--n-reps", "2",
    ])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "words/s: median" in out and "min" in out and "max" in out

    rc = cli_main([
        "benchmark", "accuracy", str(trained_model),
        str(tmp_path / "dev.jsonl"), "--device", "cpu",
    ])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "tag_acc" in out

    rc = cli_main(["benchmark", "nope"])
    assert rc == 1
    assert "speed,accuracy" in capsys.readouterr().err


def test_debug_diff_config(tmp_path, tagger_config_text, capsys):
    """debug-diff-config classifies [training] keys: customized vs
    redundant restatements vs implicit defaults."""
    cfg = tmp_path / "cfg.cfg"
    # the fixture already covers all three classes: patience = 0 is
    # customized (default 1600), dropout = 0.1 restates the default, and
    # untouched keys (e.g. logger) are implicit defaults
    text = tagger_config_text
    cfg.write_text(text)
    rc = cli_main(["debug-diff-config", str(cfg)])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "customized" in out
    assert "implicit default" in out
    lines = {l.split()[0]: l for l in out.splitlines() if l.strip()}
    assert "redundant" in lines.get("dropout", "")  # 0.1 IS the default

    # an invalid config still fails loudly before any diffing
    bad = tmp_path / "bad.cfg"
    bad.write_text(text.replace("patience = 0", "patiance = 0"))
    import pytest as _pytest

    with _pytest.raises(ValueError, match="patiance"):
        cli_main(["debug-diff-config", str(bad)])


def test_apply_alias_and_debug_profile(trained_model, tmp_path, capsys):
    """`apply` is spaCy's name for bulk annotation (same command as
    parse); `debug-profile` prints a host-side cProfile table."""
    write_synth_jsonl(tmp_path / "in.jsonl", 12, kind="tagger", seed=5)
    rc = cli_main([
        "apply", str(trained_model), str(tmp_path / "in.jsonl"),
        str(tmp_path / "applied.jsonl"), "--device", "cpu",
    ])
    assert rc == 0
    rows = [json.loads(l)
            for l in (tmp_path / "applied.jsonl").read_text().splitlines()]
    assert len(rows) == 12 and all(r.get("tags") for r in rows)
    capsys.readouterr()

    rc = cli_main([
        "debug-profile", str(trained_model), str(tmp_path / "in.jsonl"),
        "--device", "cpu", "--n-rows", "10",
    ])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "cumtime" in out and "predict_docs" in out


def test_parse_empty_input_fails_loudly(trained_model, tmp_path):
    (tmp_path / "empty.txt").write_text("")
    assert cli_main([
        "parse", str(trained_model), str(tmp_path / "empty.txt"),
        str(tmp_path / "out.jsonl"), "--device", "cpu",
    ]) == 1
    assert not (tmp_path / "out.jsonl").exists()  # no empty artifact


def test_parse_empty_rank_slice_succeeds(trained_model, tmp_path, monkeypatch):
    """world > n_docs: a rank whose round-robin slice is empty must still
    exit 0 and write its (empty) part file — only a genuinely empty CORPUS
    is an error (the pre-streaming behavior, kept across the rewrite)."""
    import jax

    (tmp_path / "three.txt").write_text("a b\nc d\ne f\n")
    monkeypatch.setattr(jax, "process_index", lambda: 3)
    monkeypatch.setattr(jax, "process_count", lambda: 4)
    rc = cli_main([
        "parse", str(trained_model), str(tmp_path / "three.txt"),
        str(tmp_path / "out.jsonl"), "--device", "cpu",
    ])
    assert rc == 0
    part = tmp_path / "out.part3.jsonl"
    assert part.exists() and part.read_text() == ""


def test_parse_failure_leaves_no_truncated_artifact(trained_model, tmp_path,
                                                    monkeypatch):
    """A mid-corpus prediction failure must not leave a well-formed-looking
    truncated output at the final path (the .tmp is cleaned up instead)."""
    from spacy_ray_tpu.pipeline.language import Pipeline

    write_synth_jsonl(tmp_path / "in.jsonl", 40, kind="tagger", seed=3)
    calls = {"n": 0}
    real = Pipeline.predict_docs

    def boom(self, docs, **kw):
        calls["n"] += 1
        if calls["n"] >= 2:
            raise RuntimeError("synthetic mid-corpus failure")
        return real(self, docs, **kw)

    monkeypatch.setattr(Pipeline, "predict_docs", boom)
    with pytest.raises(RuntimeError, match="mid-corpus"):
        cli_main([
            "parse", str(trained_model), str(tmp_path / "in.jsonl"),
            str(tmp_path / "out.jsonl"), "--device", "cpu",
            "--batch-size", "8",
        ])
    assert not (tmp_path / "out.jsonl").exists()
    assert not (tmp_path / "out.jsonl.tmp").exists()


TEXTCAT_CFG = """
[paths]
train = null
dev = null

[nlp]
lang = "en"
pipeline = ["tok2vec","textcat_multilabel"]

[components]
[components.tok2vec]
factory = "tok2vec"
[components.tok2vec.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = 32
depth = 1
embed_size = 256
[components.textcat_multilabel]
factory = "textcat_multilabel"
[components.textcat_multilabel.model]
@architectures = "spacy.TextCatCNN.v2"
[components.textcat_multilabel.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 32

[corpora]
[corpora.train]
@readers = "spacy.JsonlCorpus.v1"
path = ${paths.train}
[corpora.dev]
@readers = "spacy.JsonlCorpus.v1"
path = ${paths.dev}

[training]
seed = 0
max_steps = 60
eval_frequency = 30
patience = 0
"""


def test_find_threshold_sweeps_and_reports_best(tmp_path, capsys, monkeypatch):
    """find-threshold: sweep textcat_multilabel's threshold on dev data,
    report the best value by the component's default positive score key
    (spaCy's find-threshold surface) — and leave the component's threshold
    attribute at its ORIGINAL value afterwards (round-4 advisor: the sweep
    must not park it at the last trial value, t=1.0, where any future
    in-process save would persist it)."""
    write_synth_jsonl(tmp_path / "train.jsonl", 120, kind="textcat", seed=0)
    write_synth_jsonl(tmp_path / "dev.jsonl", 40, kind="textcat", seed=1)
    from spacy_ray_tpu.pipeline.language import Pipeline
    from spacy_ray_tpu.training.loop import train

    cfg = Config.from_str(TEXTCAT_CFG).apply_overrides(
        {
            "paths.train": str(tmp_path / "train.jsonl"),
            "paths.dev": str(tmp_path / "dev.jsonl"),
        }
    )
    train(cfg, output_path=tmp_path / "out", n_workers=1, stdout_log=False)

    captured = {}
    real_from_disk = Pipeline.from_disk.__func__

    def spy(cls, path):
        nlp = real_from_disk(cls, path)
        comp = nlp.components["textcat_multilabel"]
        captured["comp"], captured["before"] = comp, comp.threshold
        return nlp

    monkeypatch.setattr(Pipeline, "from_disk", classmethod(spy))
    rc = cli_main([
        "find-threshold", str(tmp_path / "out" / "best-model"),
        str(tmp_path / "dev.jsonl"), "textcat_multilabel",
        "--device", "cpu", "--n-trials", "5",
    ])
    out = capsys.readouterr().out
    assert rc == 0, out
    # 5 sweep rows + a Best line naming the config key to set
    assert out.count("threshold=") >= 5
    assert "Best: threshold=" in out
    assert "cats_score=" in out
    # the sweep restored the component's original threshold
    assert captured["comp"].threshold == captured["before"]


def test_find_threshold_unknown_pipe_fails(tmp_path, trained_model):
    write_synth_jsonl(tmp_path / "dev.jsonl", 10, kind="tagger", seed=1)
    rc = cli_main([
        "find-threshold", str(trained_model), str(tmp_path / "dev.jsonl"),
        "nope", "--device", "cpu",
    ])
    assert rc == 1


def test_init_config_pipeline_composition_trains(tmp_path):
    """init-config --pipeline composes an arbitrary component list over a
    shared trunk into a config that ACTUALLY TRAINS (score weights come
    from the components' default_score_weights since the section is left
    empty)."""
    cfg_path = tmp_path / "composed.cfg"
    assert cli_main([
        "init-config", str(cfg_path),
        "--pipeline", "tagger,senter,entity_ruler",
    ]) == 0
    write_synth_jsonl(tmp_path / "train.jsonl", 60, kind="tagger", seed=0)
    write_synth_jsonl(tmp_path / "dev.jsonl", 20, kind="tagger", seed=1)
    from spacy_ray_tpu.config import Config
    from spacy_ray_tpu.training.loop import train

    cfg = Config.from_str(cfg_path.read_text()).apply_overrides(
        {
            "paths.train": str(tmp_path / "train.jsonl"),
            "paths.dev": str(tmp_path / "dev.jsonl"),
            "training.max_steps": 20,
            "training.eval_frequency": 10,
        }
    )
    nlp, result = train(cfg, n_workers=1, stdout_log=False)
    assert nlp.pipe_names == ["tok2vec", "tagger", "senter", "entity_ruler"]
    assert result.best_score >= 0  # eval ran with derived score weights


def test_init_config_pipeline_rejects_unknown(tmp_path):
    rc = cli_main([
        "init-config", str(tmp_path / "x.cfg"), "--pipeline", "tagger,entity_linker",
    ])
    assert rc == 1


def test_init_config_preset_still_works(tmp_path):
    assert cli_main([
        "init-config", str(tmp_path / "p.cfg"), "--preset", "sm",
    ]) == 0
    from spacy_ray_tpu.config import Config

    Config.from_str((tmp_path / "p.cfg").read_text())


def test_info_command(trained_model, capsys):
    assert cli_main(["info"]) == 0
    out = capsys.readouterr().out
    assert "spacy-ray-tpu" in out and "jax" in out
    assert cli_main(["info", str(trained_model)]) == 0
    out = capsys.readouterr().out
    assert "components" in out and "tagger" in out
    assert cli_main(["info", "/nonexistent/model"]) == 1


def test_debug_model_prints_shapes(tmp_path, capsys):
    cfg_path = tmp_path / "dm.cfg"
    assert cli_main(["init-config", str(cfg_path), "--pipeline", "tagger,entity_ruler"]) == 0
    write_synth_jsonl(tmp_path / "t.jsonl", 30, kind="tagger", seed=0)
    rc = cli_main([
        "debug-model", str(cfg_path),
        "--paths.train", str(tmp_path / "t.jsonl"),
        "--paths.dev", str(tmp_path / "t.jsonl"),
    ])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "[tok2vec]" in out and "[tagger]" in out
    assert "host-side component" in out  # entity_ruler has no device params
    assert "TOTAL:" in out
    # component filter + unknown component
    assert cli_main([
        "debug-model", str(cfg_path), "tagger",
        "--paths.train", str(tmp_path / "t.jsonl"),
        "--paths.dev", str(tmp_path / "t.jsonl"),
    ]) == 0
    assert cli_main([
        "debug-model", str(cfg_path), "nope",
        "--paths.train", str(tmp_path / "t.jsonl"),
        "--paths.dev", str(tmp_path / "t.jsonl"),
    ]) == 1


def test_fill_config_completes_partial(tmp_path, capsys):
    """fill-config materializes every [training] default into the written
    file, the filled config trains, and bad keys still fail loudly."""
    partial = tmp_path / "partial.cfg"
    partial.write_text("""
[nlp]
lang = "en"
pipeline = ["tok2vec","tagger"]

[components.tok2vec]
factory = "tok2vec"
[components.tok2vec.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = 32
depth = 1
embed_size = 128
[components.tagger]
factory = "tagger"
[components.tagger.model]
@architectures = "spacy.Tagger.v2"
[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 32

[corpora]
[corpora.train]
@readers = "spacy.JsonlCorpus.v1"
path = ${paths.train}
[corpora.dev]
@readers = "spacy.JsonlCorpus.v1"
path = ${paths.dev}

[training]
dropout = 0.25
""")
    filled = tmp_path / "filled.cfg"
    assert cli_main(["fill-config", str(partial), str(filled)]) == 0
    out = capsys.readouterr().out
    assert "added:" in out
    from spacy_ray_tpu.config import Config

    cfg = Config.from_str(filled.read_text())
    t = cfg["training"]
    assert t["dropout"] == 0.25          # user value preserved
    assert t["patience"] == 1600         # default materialized
    assert "optimizer" in t and "batcher" in t and "logger" in t
    # the filled config actually trains
    write_synth_jsonl(tmp_path / "t.jsonl", 40, kind="tagger", seed=0)
    from spacy_ray_tpu.training.loop import train

    cfg2 = cfg.apply_overrides(
        {
            "paths.train": str(tmp_path / "t.jsonl"),
            "paths.dev": str(tmp_path / "t.jsonl"),
            "training.max_steps": 10,
            "training.eval_frequency": 5,
        }
    )
    _, result = train(cfg2, n_workers=1, stdout_log=False)
    assert result.final_step == 10

    # typo'd keys are rejected at fill time, not silently filled around
    bad = tmp_path / "bad.cfg"
    bad.write_text(partial.read_text().replace("dropout = 0.25", "dropot = 0.25"))
    import pytest as _pytest

    with _pytest.raises(ValueError, match="dropot"):
        cli_main(["fill-config", str(bad), str(tmp_path / "x.cfg")])


def test_find_threshold_rejects_non_numeric_attr(trained_model, tmp_path):
    write_synth_jsonl(tmp_path / "dev.jsonl", 10, kind="tagger", seed=1)
    rc = cli_main([
        "find-threshold", str(trained_model), str(tmp_path / "dev.jsonl"),
        "tagger", "--threshold-key", "score", "--device", "cpu",
    ])
    assert rc == 1  # bound method, not a numeric attribute


def test_init_config_pipeline_rejects_duplicates(tmp_path):
    rc = cli_main([
        "init-config", str(tmp_path / "d.cfg"), "--pipeline", "tagger,tagger",
    ])
    assert rc == 1
