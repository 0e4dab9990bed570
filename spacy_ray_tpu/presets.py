"""Preset config strings: canonical pipeline shapes used by
__graft_entry__, tests, and the ``init-config`` CLI command (the role of
``spacy init config`` templates in the reference ecosystem)."""

# standard [paths]/[corpora]/[training] tail shared by init-config presets
_TRAINING_TAIL = """
[paths]
train = null
dev = null

[corpora.train]
@readers = "spacy.Corpus.v1"
path = ${{paths.train}}
shuffle = true

[corpora.dev]
@readers = "spacy.Corpus.v1"
path = ${{paths.dev}}

[training]
seed = 0
dropout = 0.1
accumulate_gradient = {accumulate_gradient}
patience = 1600
max_epochs = 0
max_steps = 20000
eval_frequency = 200
zero1 = {zero1}
update_sharding = "{update_sharding}"

[training.optimizer]
@optimizers = "Adam.v1"
learn_rate = 0.001
beta1 = 0.9
beta2 = 0.999
grad_clip = 1.0
use_averages = false

[training.batcher]
@batchers = "spacy.batch_by_words.v1"
size = 2000
tolerance = 0.2

[training.score_weights]
{score_weights}
"""


def _full(components: str, score_weights: str, accumulate_gradient: int = 1,
          zero1: bool = False, update_sharding: str = "auto") -> str:
    # update_sharding defaults to "auto" (arms "full" on accelerator
    # meshes with >1 data rank, honors a zero1 alias, stays replicated on
    # CPU); the trf preset pins "full" outright — it subsumes its old
    # zero1=true (state sharded in both; full also shards the apply,
    # bit-exactly vs replicated) at every mesh shape, degenerating
    # harmlessly to replicated on one device
    return components + _TRAINING_TAIL.format(
        accumulate_gradient=accumulate_gradient,
        zero1="true" if zero1 else "false",
        update_sharding=update_sharding,
        score_weights=score_weights,
    )

CNN_TAGGER_CFG = """
[nlp]
lang = "en"
pipeline = ["tok2vec","tagger"]

[components.tok2vec]
factory = "tok2vec"

[components.tok2vec.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = {width}
depth = {depth}
embed_size = {embed_size}

[components.tagger]
factory = "tagger"

[components.tagger.model]
@architectures = "spacy.Tagger.v2"

[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = {width}
"""

# ---------------------------------------------------------------------------
# init-config presets (full trainable configs, BASELINE.json config shapes)
# ---------------------------------------------------------------------------

_SM_COMPONENTS = """
[nlp]
lang = "en"
pipeline = ["tok2vec","tagger","parser","ner"]

[components.tok2vec]
factory = "tok2vec"

[components.tok2vec.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = 96
depth = 4
embed_size = 2000

[components.tagger]
factory = "tagger"

[components.tagger.model]
@architectures = "spacy.Tagger.v2"

[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 96

[components.parser]
factory = "parser"

[components.parser.model]
@architectures = "spacy.TransitionBasedParser.v2"
state_type = "parser"
hidden_width = 128
maxout_pieces = 2

[components.parser.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 96

[components.ner]
factory = "ner"

[components.ner.model]
@architectures = "spacy.TransitionBasedParser.v2"
state_type = "ner"
hidden_width = 128
maxout_pieces = 2

[components.ner.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 96
"""

_TRF_COMPONENTS = """
[nlp]
lang = "en"
pipeline = ["transformer","tagger","parser","ner"]

[components.transformer]
factory = "transformer"

[components.transformer.model]
@architectures = "spacy_ray_tpu.TransformerEncoder.v1"
width = 768
depth = 12
n_heads = 12
ffn_mult = 4
dropout = 0.1
max_len = 512
embed_size = 20000
remat = true

[components.tagger]
factory = "tagger"

[components.tagger.model]
@architectures = "spacy.Tagger.v2"

[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 768

[components.parser]
factory = "parser"

[components.parser.model]
@architectures = "spacy.TransitionBasedParser.v2"
state_type = "parser"
hidden_width = 128
maxout_pieces = 2

[components.parser.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 768

[components.ner]
factory = "ner"

[components.ner.model]
@architectures = "spacy.TransitionBasedParser.v2"
state_type = "ner"
hidden_width = 128
maxout_pieces = 2

[components.ner.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 768
"""

_SPANCAT_COMPONENTS = """
[nlp]
lang = "en"
pipeline = ["tok2vec","spancat","textcat_multilabel"]

[components.tok2vec]
factory = "tok2vec"

[components.tok2vec.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = 96
depth = 4
embed_size = 2000

[components.spancat]
factory = "spancat"
spans_key = "sc"
threshold = 0.5

[components.spancat.suggester]
@misc = "spacy.ngram_suggester.v1"
sizes = [1,2,3]

[components.spancat.model]
@architectures = "spacy.SpanCategorizer.v1"
hidden_size = 128

[components.spancat.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 96

[components.textcat_multilabel]
factory = "textcat_multilabel"

[components.textcat_multilabel.model]
@architectures = "spacy.TextCatReduce.v1"

[components.textcat_multilabel.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 96
"""

INIT_PRESETS = {
    "cnn": _full(
        CNN_TAGGER_CFG.format(width=96, depth=4, embed_size=2000),
        "tag_acc = 1.0",
    ),
    "sm": _full(
        _SM_COMPONENTS,
        "tag_acc = 0.33\ndep_las = 0.33\nents_f = 0.34",
    ),
    "trf": _full(
        _TRF_COMPONENTS,
        "tag_acc = 0.33\ndep_las = 0.33\nents_f = 0.34",
        accumulate_gradient=3,
        update_sharding="full",
    ),
    "spancat": _full(
        _SPANCAT_COMPONENTS,
        "spans_sc_f = 0.7\ncats_micro_f = 0.3",
    ),
}

TINY_TRF_TAGGER_CFG = """
[nlp]
lang = "en"
pipeline = ["transformer","tagger"]

[components.transformer]
factory = "transformer"

[components.transformer.model]
@architectures = "spacy_ray_tpu.TransformerEncoder.v1"
width = 32
depth = 2
n_heads = 4
ffn_mult = 2
dropout = 0.1
max_len = 64
embed_size = 256
remat = false

[components.tagger]
factory = "tagger"

[components.tagger.model]
@architectures = "spacy.Tagger.v2"

[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 32
"""


# ---------------------------------------------------------------------------
# init-config --pipeline composition (spacy `init config --pipeline` role)
# ---------------------------------------------------------------------------

_CNN_TRUNK = """
[components.{trunk}]
factory = "tok2vec"

[components.{trunk}.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = {width}
depth = 4
embed_size = 2000
"""

_TRF_TRUNK = """
[components.{trunk}]
factory = "transformer"

[components.{trunk}.model]
@architectures = "spacy_ray_tpu.TransformerEncoder.v1"
width = {width}
depth = 12
n_heads = 12
dropout = 0.1
max_len = 512
embed_size = 20000
"""

_LISTENER = """
[components.{name}.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = {width}
"""

_TAGGER_LIKE = """
[components.{name}]
factory = "{factory}"

[components.{name}.model]
@architectures = "spacy.Tagger.v2"
""" + _LISTENER

_PARSER_LIKE = """
[components.{name}]
factory = "{factory}"

[components.{name}.model]
@architectures = "spacy.TransitionBasedParser.v2"
state_type = "{state_type}"
hidden_width = 128
maxout_pieces = 2
""" + _LISTENER

_SPANCAT_BLOCK = """
[components.{name}]
factory = "spancat"
spans_key = "sc"
threshold = 0.5

[components.{name}.suggester]
@misc = "spacy.ngram_suggester.v1"
sizes = [1,2,3]

[components.{name}.model]
@architectures = "spacy.SpanCategorizer.v1"
hidden_size = 128
""" + _LISTENER

_TEXTCAT_BLOCK = """
[components.{name}]
factory = "{factory}"

[components.{name}.model]
@architectures = "spacy.TextCatReduce.v1"
""" + _LISTENER

_HOST_ONLY_BLOCK = """
[components.{name}]
factory = "{factory}"
"""

# component name -> (template, template kwargs beyond name/width)
COMPOSABLE = {
    "tagger": (_TAGGER_LIKE, {"factory": "tagger"}),
    "morphologizer": (_TAGGER_LIKE, {"factory": "morphologizer"}),
    "senter": (_TAGGER_LIKE, {"factory": "senter"}),
    "trainable_lemmatizer": (_TAGGER_LIKE, {"factory": "trainable_lemmatizer"}),
    "parser": (_PARSER_LIKE, {"factory": "parser", "state_type": "parser"}),
    "ner": (_PARSER_LIKE, {"factory": "ner", "state_type": "ner"}),
    "spancat": (_SPANCAT_BLOCK, {}),
    "textcat": (_TEXTCAT_BLOCK, {"factory": "textcat"}),
    "textcat_multilabel": (_TEXTCAT_BLOCK, {"factory": "textcat_multilabel"}),
    "lemmatizer": (_HOST_ONLY_BLOCK, {"factory": "lemmatizer"}),
    "entity_ruler": (_HOST_ONLY_BLOCK, {"factory": "entity_ruler"}),
    "attribute_ruler": (_HOST_ONLY_BLOCK, {"factory": "attribute_ruler"}),
}

_HOST_ONLY = {"lemmatizer", "entity_ruler", "attribute_ruler"}


def compose_pipeline_config(
    pipeline, trunk: str = "cnn", width: int = 0
) -> str:
    """Generate a full trainable config for an arbitrary component list over
    one shared trunk (spacy's ``init config --pipeline`` role). Score
    weights are left to the components' declared ``default_score_weights``
    (the training loop combines and normalizes them when the section is
    empty)."""
    if trunk not in ("cnn", "trf"):
        raise ValueError(f"trunk must be 'cnn' or 'trf', got {trunk!r}")
    unknown = [c for c in pipeline if c not in COMPOSABLE]
    if unknown:
        raise ValueError(
            f"Can't compose {unknown!r} (supported: {', '.join(sorted(COMPOSABLE))}; "
            "entity_linker needs a knowledge base — start from a full config)"
        )
    if not pipeline:
        raise ValueError("pipeline must name at least one component")
    dupes = sorted({c for c in pipeline if pipeline.count(c) > 1})
    if dupes:
        raise ValueError(
            f"duplicate component name(s) in --pipeline: {', '.join(dupes)} "
            "(each composable component can appear once)"
        )
    width = width or (96 if trunk == "cnn" else 768)
    trunk_name = "tok2vec" if trunk == "cnn" else "transformer"
    needs_trunk = any(c not in _HOST_ONLY for c in pipeline)
    names = ([trunk_name] if needs_trunk else []) + list(pipeline)
    parts = [
        "\n[nlp]\nlang = \"en\"\npipeline = ["
        + ",".join(f'"{n}"' for n in names)
        + "]\n"
    ]
    if needs_trunk:
        tmpl = _CNN_TRUNK if trunk == "cnn" else _TRF_TRUNK
        parts.append(tmpl.format(trunk=trunk_name, width=width))
    for comp in pipeline:
        tmpl, kwargs = COMPOSABLE[comp]
        parts.append(tmpl.format(name=comp, width=width, **kwargs))
    return _full(
        "".join(parts),
        "",  # empty: loop derives weights from component metadata
        accumulate_gradient=3 if trunk == "trf" else 1,
        update_sharding="full" if trunk == "trf" else "auto",
    )
