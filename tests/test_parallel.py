"""Distribution-layer tests on a REAL 8-device CPU mesh.

The sync-protocol coverage the reference never had (SURVEY.md §4: the proxy
versioning/quorum machinery is "entirely untested", which let the
get_quorum dead-code bug survive): here the equivalent exchange — gradient
all-reduce + ZeRO-1 sharded update — runs as compiled SPMD programs on 8
virtual devices and is checked for numerical equivalence against the
single-device step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spacy_ray_tpu.config import Config
from spacy_ray_tpu.parallel.mesh import build_mesh, zero1_spec
from spacy_ray_tpu.parallel.step import (
    make_train_step,
    place_batch,
    place_replicated,
    shard_opt_state,
)
from spacy_ray_tpu.pipeline.language import Pipeline
from spacy_ray_tpu.registry import registry
from spacy_ray_tpu.util import synth_corpus


def _fixed_len_examples(n, length=16, seed=0):
    """Docs padded/cut to exactly `length` tokens for equivalence tests."""
    import random

    from spacy_ray_tpu.pipeline.doc import Doc, Example
    from spacy_ray_tpu.util import _POS_VOCAB

    rng = random.Random(seed)
    out = []
    pos_names = list(_POS_VOCAB)
    for _ in range(n):
        words, tags = [], []
        for _ in range(length):
            p = rng.choice(pos_names)
            words.append(rng.choice(_POS_VOCAB[p]))
            tags.append(p)
        out.append(Example.from_gold(Doc(words=words, tags=tags)))
    return out


@pytest.fixture(scope="module")
def small_nlp():
    cfg = Config.from_str(
        """
[nlp]
lang = "en"
pipeline = ["tok2vec","tagger"]

[components.tok2vec]
factory = "tok2vec"

[components.tok2vec.model]
@architectures = "spacy.HashEmbedCNN.v2"
width = 32
depth = 2
embed_size = 256

[components.tagger]
factory = "tagger"

[components.tagger.model]
@architectures = "spacy.Tagger.v2"

[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 32
"""
    )
    nlp = Pipeline.from_config(cfg)
    nlp.initialize(lambda: iter(_fixed_len_examples(64)), seed=0)
    return nlp


def _run_steps(nlp, n_data, n_steps=2, zero1=False, B=16):
    examples = _fixed_len_examples(B * n_steps, seed=1)
    mesh = build_mesh(n_data=n_data)
    tx = registry.get("optimizers", "Adam.v1")(learn_rate=0.01)
    params = place_replicated(nlp.params, mesh)
    opt_state = shard_opt_state(tx.init(params), mesh, zero1=zero1)
    update = make_train_step(
        nlp.make_loss_fn(), tx, mesh, zero1=zero1,
        opt_state_template=opt_state, donate=False,
    )
    rng = jax.random.PRNGKey(42)
    losses = []
    for s in range(n_steps):
        batch = nlp.collate(
            examples[s * B : (s + 1) * B], pad_batch_to=B, pad_len_to=16
        )
        tokens = place_batch(batch["tokens"], mesh)
        targets = place_batch(batch["targets"], mesh)
        # fixed rng per step (not split) so dropout noise matches across runs
        params, opt_state, loss, metrics = update(
            params, opt_state, tokens, targets, jax.random.fold_in(rng, s)
        )
        losses.append(float(loss))
    return jax.device_get(params), losses


def test_dp8_matches_single_device(small_nlp):
    """Gradient all-reduce over 8 devices == single-device step (the
    correctness property the reference's async quorum only approximates)."""
    p1, l1 = _run_steps(small_nlp, n_data=1)
    p8, l8 = _run_steps(small_nlp, n_data=8)
    np.testing.assert_allclose(l1, l8, rtol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p8)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2, atol=1e-5)


def test_zero1_matches_replicated(small_nlp):
    """ZeRO-1 sharded optimizer state must be a pure layout change."""
    p_repl, l_repl = _run_steps(small_nlp, n_data=8, zero1=False)
    p_z1, l_z1 = _run_steps(small_nlp, n_data=8, zero1=True)
    np.testing.assert_allclose(l_repl, l_z1, rtol=2e-4)
    for a, b in zip(
        jax.tree_util.tree_leaves(p_repl), jax.tree_util.tree_leaves(p_z1)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2, atol=1e-5)


def test_zero1_spec_shards_divisible_leaves(mesh8):
    leaf = jnp.zeros((64, 32))
    spec = tuple(zero1_spec(leaf, mesh8).spec)
    assert "data" in spec and spec[0] == "data"
    odd = jnp.zeros((7, 3))
    assert "data" not in tuple(zero1_spec(odd, mesh8).spec)


def test_zero1_opt_state_is_sharded(small_nlp, mesh8):
    tx = registry.get("optimizers", "Adam.v1")(learn_rate=0.01)
    params = place_replicated(small_nlp.params, mesh8)
    opt_state = shard_opt_state(tx.init(params), mesh8, zero1=True)
    shardings = [
        leaf.sharding
        for leaf in jax.tree_util.tree_leaves(opt_state)
        if hasattr(leaf, "sharding") and hasattr(leaf, "shape") and leaf.ndim >= 1
    ]
    sharded = [
        s for s in shardings if s.spec != jax.sharding.PartitionSpec()
    ]
    # the big moment tensors (embed tables: 256 rows % 8 == 0) must be sharded
    assert len(sharded) > 0


def test_grad_accumulation_equivalence(small_nlp):
    """accum=2 over two equal microbatches == one step over their union."""
    examples = _fixed_len_examples(32, seed=3)
    mesh = build_mesh(n_data=1)
    tx = registry.get("optimizers", "SGD.v1")(learn_rate=0.1, grad_clip=0.0)
    rng = jax.random.PRNGKey(0)

    # run A: one batch of 32
    params = place_replicated(small_nlp.params, mesh)
    opt = tx.init(params)
    upd1 = make_train_step(
        small_nlp.make_loss_fn(), tx, mesh, opt_state_template=opt, donate=False
    )
    batch = small_nlp.collate(examples, pad_batch_to=32, pad_len_to=16)
    pA, _, lossA, _ = upd1(
        params, opt,
        place_batch(batch["tokens"], mesh), place_batch(batch["targets"], mesh),
        rng,
    )

    # run B: two microbatches of 16 under scan accumulation
    params = place_replicated(small_nlp.params, mesh)
    opt = tx.init(params)
    upd2 = make_train_step(
        small_nlp.make_loss_fn(), tx, mesh, accumulate_gradient=2,
        opt_state_template=opt, donate=False,
    )
    c1 = small_nlp.collate(examples[:16], pad_batch_to=16, pad_len_to=16)
    c2 = small_nlp.collate(examples[16:], pad_batch_to=16, pad_len_to=16)
    tokens = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), c1["tokens"], c2["tokens"]
    )
    targets = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), c1["targets"], c2["targets"]
    )
    pB, _, lossB, _ = upd2(
        params, opt,
        place_batch(tokens, mesh, accum=True), place_batch(targets, mesh, accum=True),
        rng,
    )
    # equal-sized, fully-valid microbatches -> identical mean gradient
    for a, b in zip(jax.tree_util.tree_leaves(pA), jax.tree_util.tree_leaves(pB)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6)


def test_train_loop_non_power_of_two_workers(tagger_config_text, tmp_path):
    """B padding must round to a multiple of the data-axis size (n=3)."""
    from spacy_ray_tpu.training.loop import train
    from spacy_ray_tpu.util import write_synth_jsonl

    write_synth_jsonl(tmp_path / "train.jsonl", 60, kind="tagger", seed=0)
    write_synth_jsonl(tmp_path / "dev.jsonl", 12, kind="tagger", seed=1)
    cfg = Config.from_str(tagger_config_text).apply_overrides(
        {
            "paths.train": str(tmp_path / "train.jsonl"),
            "paths.dev": str(tmp_path / "dev.jsonl"),
            "training.max_steps": 4,
            "training.eval_frequency": 2,
        }
    )
    _, result = train(cfg, n_workers=3, stdout_log=False)
    assert result.final_step == 4


def test_train_loop_8_workers_learns(tagger_config_text, tmp_path):
    from spacy_ray_tpu.training.loop import train
    from spacy_ray_tpu.util import write_synth_jsonl

    write_synth_jsonl(tmp_path / "train.jsonl", 300, kind="tagger", seed=0)
    write_synth_jsonl(tmp_path / "dev.jsonl", 60, kind="tagger", seed=1)
    cfg = Config.from_str(tagger_config_text).apply_overrides(
        {
            "paths.train": str(tmp_path / "train.jsonl"),
            "paths.dev": str(tmp_path / "dev.jsonl"),
            "training.max_steps": 40,
            "training.eval_frequency": 20,
            "training.zero1": True,
        }
    )
    _, result = train(cfg, n_workers=8, stdout_log=False)
    assert result.final_step == 40
    assert result.best_score > 0.7