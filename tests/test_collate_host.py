"""The collate path stays on the host: NER's window features are one layout
(``models/parser.ner_window_features``) answered in NumPy for the training
targets and in ``jax.numpy`` inside the jitted programs, bit for bit the
same, and ``Pipeline.collate(..., host=True)`` neither compiles nor
dispatches a program nor copies from the device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spacy_ray_tpu.config import Config
from spacy_ray_tpu.models.parser import NER_N_FEATURES, ner_window_features
from spacy_ray_tpu.pipeline.components.ner import NERComponent, biluo_action_id
from spacy_ray_tpu.pipeline.language import Pipeline
from spacy_ray_tpu.training import telemetry
from spacy_ray_tpu.util import synth_corpus

from test_spans import SM_CFG


def _by_the_definition(Tlen, lengths):
    """[t-2 .. t+2], -1 outside [0, length): the loop, as the reference."""
    out = np.full((len(lengths), Tlen, NER_N_FEATURES), -1, dtype=np.int32)
    for b, n in enumerate(lengths):
        for t in range(Tlen):
            for f, off in enumerate((-2, -1, 0, 1, 2)):
                if 0 <= t + off < n:
                    out[b, t, f] = t + off
    return out


@pytest.mark.parametrize("Tlen", [1, 2, 5, 256])
def test_host_window_features_equal_the_jitted_ones(Tlen):
    """The NumPy answer (a ``numpy.ndarray`` of lengths in) equals the
    jitted ``jnp`` answer (a tracer in) in values, dtype and shape, for
    lengths 0, 1, 2, Tlen - 1, Tlen and a padded tail of empty rows."""
    lengths = sorted({n for n in (0, 1, 2, Tlen - 1, Tlen) if 0 <= n <= Tlen})
    lengths = np.asarray(lengths + [0, 0, 0], dtype=np.int32)

    host = ner_window_features(Tlen, lengths)
    jitted = jax.jit(ner_window_features, static_argnums=0)(Tlen, jnp.asarray(lengths))

    assert type(host) is np.ndarray
    assert isinstance(jitted, jax.Array)
    assert host.dtype == np.int32 and jitted.dtype == jnp.int32
    assert host.shape == jitted.shape == (len(lengths), Tlen, NER_N_FEATURES)
    np.testing.assert_array_equal(host, np.asarray(jitted))
    np.testing.assert_array_equal(host, _by_the_definition(Tlen, lengths))
    # whatever is no numpy.ndarray is answered by jax, eagerly too (a
    # jax.Array, a list): the input's type alone decides
    for other in (jnp.asarray(lengths), lengths.tolist()):
        eager = ner_window_features(Tlen, other)
        assert isinstance(eager, jax.Array)
        np.testing.assert_array_equal(host, np.asarray(eager))


def test_ner_targets_equal_the_parents():
    """``NERComponent.make_targets`` on a seeded batch: the same three
    arrays as before PR 25, when ``feats`` came from the eager ``jnp``
    function and a copy back (the golden is made that way here)."""
    examples = synth_corpus(11, "ner", seed=5)
    ner = NERComponent("ner", {})
    ner.add_labels_from(examples)
    B, Tlen = 16, 8  # shorter than the longest document: the cut is covered
    assert max(len(eg.reference) for eg in examples) > Tlen

    label_ids = {label: i for i, label in enumerate(ner.labels)}
    actions = np.zeros((B, Tlen), dtype=np.int32)
    mask = np.zeros((B, Tlen), dtype=bool)
    lengths = [0] * B
    for i, eg in enumerate(examples):
        lengths[i] = min(len(eg.reference), Tlen)
        tags = eg.reference.ents_biluo()
        for t in range(lengths[i]):
            actions[i, t] = biluo_action_id(tags[t], label_ids)
            mask[i, t] = True
    feats = np.asarray(ner_window_features(Tlen, jnp.asarray(lengths)))

    got = ner.make_targets(examples, B, Tlen)
    assert sorted(got) == ["actions", "feats", "ner_mask"]
    assert actions.any() and not mask.all()
    for name, want in (("actions", actions), ("feats", feats), ("ner_mask", mask)):
        assert type(got[name]) is np.ndarray, name
        assert got[name].dtype == want.dtype, name
        np.testing.assert_array_equal(got[name], want, err_msg=name)


def test_collate_never_leaves_the_host():
    """A tagger + parser + NER pipeline collates a batch shape it has not
    met without a compile, a dispatch or a copy from the device: the
    compile count stands still, every leaf is a ``numpy.ndarray``, and the
    call passes where every transfer is disallowed. (On the chip an eager
    call from the collate thread queues behind the running step: PR 24
    read 0.44 s a step for 46 us of device work.)"""
    nlp = Pipeline.from_config(Config.from_str(SM_CFG))
    seen = synth_corpus(8, "parser", seed=3) + synth_corpus(8, "ner", seed=4)
    nlp.initialize(lambda: iter(seen), seed=0)
    nlp.collate(seen, host=True)  # labels, vocabulary and caches are warm

    fresh = synth_corpus(21, "parser", seed=11) + synth_corpus(20, "ner", seed=12)
    assert telemetry.install_compile_hook()
    jnp.zeros((3, 7)).block_until_ready()  # the hook counts: a new shape compiles
    before = telemetry.compile_count()
    jnp.zeros((3, 9)).block_until_ready()
    assert telemetry.compile_count() > before

    before = telemetry.compile_count()
    with jax.transfer_guard("disallow"):
        batch = nlp.collate(fresh, host=True, pad_batch_to=48, pad_len_to=37)
    assert telemetry.compile_count() == before

    assert sorted(batch["targets"]) == ["ner", "parser", "tagger"]
    assert batch["targets"]["ner"]["feats"].shape == (48, 37, NER_N_FEATURES)
    leaves = jax.tree_util.tree_leaves((batch["tokens"], batch["targets"]))
    assert len(leaves) >= 8
    for leaf in leaves:
        assert type(leaf) is np.ndarray, type(leaf)
