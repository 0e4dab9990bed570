"""Config parse/serialize/interpolate/override tests (the surface at
reference train_cli.py:44-46)."""

import pytest

from spacy_ray_tpu.config import Config, ConfigValidationError, parse_cli_overrides
from spacy_ray_tpu.registry import Registry, RegistryError, registry


SAMPLE = """
[paths]
train = "data/train.jsonl"
dev = null

[nlp]
lang = "en"
pipeline = ["tok2vec","tagger"]
batch_size = 1000

[components.tagger.model]
@architectures = "spacy.Tagger.v2"
nO = null

[training]
dropout = 0.1
seed = 42

[training.optimizer]
@optimizers = "Adam.v1"
learn_rate = 0.001
"""


def test_parse_types():
    cfg = Config.from_str(SAMPLE)
    assert cfg["paths"]["train"] == "data/train.jsonl"
    assert cfg["paths"]["dev"] is None
    assert cfg["nlp"]["pipeline"] == ["tok2vec", "tagger"]
    assert cfg["nlp"]["batch_size"] == 1000
    assert cfg["training"]["dropout"] == 0.1
    assert cfg["components"]["tagger"]["model"]["@architectures"] == "spacy.Tagger.v2"


def test_roundtrip():
    cfg = Config.from_str(SAMPLE)
    text = cfg.to_str()
    cfg2 = Config.from_str(text)
    assert cfg == cfg2


def test_interpolation():
    cfg = Config.from_str(
        """
[paths]
train = "corpus/train"

[x]
width = 64

[y]
path = ${paths.train}
w = ${x.width}
msg = "width is ${x.width}!"
"""
    )
    out = cfg.interpolate()
    assert out["y"]["path"] == "corpus/train"
    assert out["y"]["w"] == 64
    assert out["y"]["msg"] == "width is 64!"


def test_interpolation_missing():
    cfg = Config.from_str("[a]\nx = ${nope.nothing}\n")
    with pytest.raises(ConfigValidationError):
        cfg.interpolate()


def test_overrides():
    cfg = Config.from_str(SAMPLE)
    out = cfg.apply_overrides({"training.seed": 7, "paths.train": "other.jsonl"})
    assert out["training"]["seed"] == 7
    assert out["paths"]["train"] == "other.jsonl"
    # original untouched
    assert cfg["training"]["seed"] == 42


def test_parse_cli_overrides():
    ov = parse_cli_overrides(["--training.seed", "7", "--paths.train=x.jsonl", "--nlp.flag", "true"])
    assert ov == {"training.seed": 7, "paths.train": "x.jsonl", "nlp.flag": True}


def test_registry_resolve_nested():
    reg = Registry()

    @reg.misc("inner.v1")
    def inner(value: int):
        return value * 2

    @reg.misc("outer.v1")
    def outer(child, name: str):
        return (name, child)

    block = {"@misc": "outer.v1", "name": "hi", "child": {"@misc": "inner.v1", "value": 4}}
    assert reg.resolve(block) == ("hi", 8)


def test_registry_validation():
    reg = Registry()

    @reg.misc("f.v1")
    def f(a: int, b: int = 2):
        return a + b

    with pytest.raises(RegistryError):
        reg.resolve({"@misc": "f.v1"})  # missing a
    with pytest.raises(RegistryError):
        reg.resolve({"@misc": "f.v1", "a": 1, "zzz": 3})  # unknown kwarg
    assert reg.resolve({"@misc": "f.v1", "a": 1}) == 3


def test_global_registry_has_builtins():
    assert registry.has("architectures", "spacy.HashEmbedCNN.v2")
    assert registry.has("architectures", "spacy.Tagger.v2")
    assert registry.has("optimizers", "Adam.v1")
    assert registry.has("batchers", "spacy.batch_by_words.v1")
    assert registry.has("loggers", "spacy-ray.ConsoleLogger.v1")
    assert registry.has("readers", "spacy.Corpus.v1")


def test_v1_architecture_aliases_resolve():
    """Older spaCy configs name .v1 architectures; they must resolve."""
    from spacy_ray_tpu.registry import registry

    for name, cfg in [
        ("spacy.HashEmbedCNN.v1",
         {"width": 32, "depth": 1, "embed_size": 128}),
        ("spacy.Tagger.v1",
         {"tok2vec": {"@architectures": "spacy.HashEmbedCNN.v1",
                      "width": 32, "depth": 1, "embed_size": 128}}),
        ("spacy.MultiHashEmbed.v1", {"width": 32, "rows": 500}),
        ("spacy.Tok2Vec.v1",
         {"embed": {"@architectures": "spacy.MultiHashEmbed.v1",
                    "width": 32, "rows": 500},
          "encode": {"@architectures": "spacy.MaxoutWindowEncoder.v1",
                     "width": 32, "depth": 1}}),
        ("spacy.TransitionBasedParser.v1",
         {"state_type": "parser", "hidden_width": 32,
          "tok2vec": {"@architectures": "spacy.Tok2VecListener.v1",
                      "width": 32}}),
    ]:
        model = registry.resolve({"@architectures": name, **cfg})
        assert model is not None, name


def test_device_gpu_fails_loudly_without_cuda():
    # reference --gpu-id surface: in a CUDA-less install --device gpu must
    # exit with a clear message, not silently train on CPU (and certainly
    # not crash later with a bare AssertionError)
    import pytest

    from spacy_ray_tpu.cli import _setup_device

    with pytest.raises(SystemExit, match="JAX found no gpu here"):
        _setup_device("gpu")
