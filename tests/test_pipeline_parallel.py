"""Pipeline parallelism (parallel/pipeline.py): the GPipe SPMD schedule
over the 'pipe' mesh axis must be numerically EQUAL to the dense layer
loop, and a full train step must compile and run on a pipe x data mesh.
Beyond-parity: SURVEY.md §2.2 marks PP "not required"; round 1 shipped
without it (VERDICT parallelism table row PP: no)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # compile-heavy: full tier only

from spacy_ray_tpu.config import Config
from spacy_ray_tpu.parallel import context as pctx
from spacy_ray_tpu.parallel.mesh import build_mesh
from spacy_ray_tpu.parallel.step import (
    make_train_step,
    place_batch,
    place_replicated,
    shard_opt_state,
)
from spacy_ray_tpu.pipeline.language import Pipeline
from spacy_ray_tpu.registry import registry
from spacy_ray_tpu.util import synth_corpus

TRF_CFG = """
[nlp]
lang = "en"
pipeline = ["transformer","tagger"]

[components.transformer]
factory = "transformer"

[components.transformer.model]
@architectures = "spacy_ray_tpu.TransformerEncoder.v1"
width = 32
depth = 4
n_heads = 4
ffn_mult = 2
dropout = 0.0
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


@pytest.fixture(scope="module")
def trf_nlp():
    nlp = Pipeline.from_config(Config.from_str(TRF_CFG))
    egs = synth_corpus(64, "tagger", seed=0)
    nlp.initialize(lambda: iter(egs), seed=0)
    return nlp, egs


def test_pipeline_forward_equals_dense(trf_nlp):
    nlp, egs = trf_nlp
    batch = nlp.collate(egs[:8], with_targets=False, pad_batch_to=8, pad_len_to=16)
    forward = nlp.make_forward_fn()

    dense = jax.jit(forward)(nlp.params, batch["tokens"])
    dense_X = np.asarray(dense["transformer"].X)

    mesh = build_mesh(n_data=2, n_pipe=4)
    params = place_replicated(nlp.params, mesh)
    tokens = place_batch(batch["tokens"], mesh)
    with pctx.use_mesh(mesh):
        piped = jax.jit(forward)(params, tokens)
    piped_X = np.asarray(jax.device_get(piped["transformer"].X))

    np.testing.assert_allclose(piped_X, dense_X, atol=2e-4, rtol=2e-3)
    # the tagger head consumes the pipelined trunk output identically
    np.testing.assert_allclose(
        np.asarray(jax.device_get(piped["tagger"].X)),
        np.asarray(dense["tagger"].X),
        atol=2e-4, rtol=2e-3,
    )


def test_pipeline_train_step_runs_and_learns(trf_nlp):
    nlp, egs = trf_nlp
    mesh = build_mesh(n_data=2, n_pipe=4)
    tx = registry.get("optimizers", "Adam.v1")(learn_rate=0.01)
    # the update donates its param buffers; give it copies so the shared
    # module fixture's params survive for the other tests
    params = place_replicated(
        jax.tree_util.tree_map(jnp.copy, nlp.params), mesh
    )
    opt_state = shard_opt_state(tx.init(params), mesh, zero1=False)
    update = make_train_step(nlp.make_loss_fn(), tx, mesh, opt_state_template=opt_state)

    batch = nlp.collate(egs[:8], pad_batch_to=8, pad_len_to=16)
    tokens = place_batch(batch["tokens"], mesh)
    targets = place_batch(batch["targets"], mesh)
    rng = jax.random.PRNGKey(0)
    losses = []
    for i in range(4):
        rng, sub = jax.random.split(rng)
        params, opt_state, loss, metrics = update(params, opt_state, tokens, targets, sub)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], f"no learning under PP: {losses}"


def test_pipeline_grads_match_dense(trf_nlp):
    nlp, egs = trf_nlp
    batch = nlp.collate(egs[:8], pad_batch_to=8, pad_len_to=16)
    loss_fn = nlp.make_loss_fn()
    rng = jax.random.PRNGKey(1)

    def scalar_loss(params, tokens, targets):
        loss, _ = loss_fn(params, tokens, targets, rng)
        return loss

    dense_grads = jax.jit(jax.grad(scalar_loss))(
        nlp.params, batch["tokens"], batch["targets"]
    )

    mesh = build_mesh(n_data=2, n_pipe=4)
    params = place_replicated(nlp.params, mesh)
    tokens = place_batch(batch["tokens"], mesh)
    targets = place_batch(batch["targets"], mesh)
    with pctx.use_mesh(mesh):
        pp_grads = jax.jit(jax.grad(scalar_loss))(params, tokens, targets)
    pp_grads = jax.device_get(pp_grads)

    dl = jax.tree_util.tree_leaves(dense_grads)
    pl = jax.tree_util.tree_leaves(pp_grads)
    assert len(dl) == len(pl)
    # bf16 matmuls + different reduction orders (scan-over-stacked-layers vs
    # unrolled loop, plus the psum broadcast) reassociate rounding; the
    # forward agrees to 2e-4, backward accumulates roughly one more ulp
    for a, b in zip(dl, pl):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=2e-3, rtol=3e-2
        )


def test_pipe_composes_with_tp(trf_nlp):
    """PP x TP: partial-manual shard_map keeps the model axis automatic,
    so tensor-parallel constraints inside the stages still apply and the
    result equals the dense loop."""
    nlp, egs = trf_nlp
    batch = nlp.collate(egs[:8], with_targets=False, pad_batch_to=8, pad_len_to=16)
    forward = nlp.make_forward_fn()
    dense = jax.jit(forward)(nlp.params, batch["tokens"])

    mesh = build_mesh(n_data=1, n_model=2, n_pipe=4)
    params = place_replicated(nlp.params, mesh)
    tokens = place_batch(batch["tokens"], mesh)
    with pctx.use_mesh(mesh):
        piped = jax.jit(forward)(params, tokens)
    # bf16 matmuls reassociate differently under the TP sharding
    np.testing.assert_allclose(
        np.asarray(jax.device_get(piped["transformer"].X)),
        np.asarray(dense["transformer"].X),
        atol=5e-4, rtol=5e-3,
    )


def test_pipe_composes_with_context(trf_nlp):
    """PP x CP x DP in one mesh: ring attention nests as a partial-manual
    region (manual over `context` only) inside the pipeline's `pipe`
    region, and the result equals the dense loop."""
    nlp, egs = trf_nlp
    batch = nlp.collate(egs[:8], with_targets=False, pad_batch_to=8, pad_len_to=16)
    forward = nlp.make_forward_fn()
    mesh = build_mesh(n_data=2, n_context=2, n_pipe=2)
    params = place_replicated(nlp.params, mesh)
    tokens = place_batch(batch["tokens"], mesh)

    dense = jax.jit(forward)(nlp.params, batch["tokens"])
    with pctx.use_mesh(mesh):
        piped = jax.jit(forward)(params, tokens)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(piped["transformer"].X)),
        np.asarray(dense["transformer"].X),
        atol=5e-4, rtol=5e-3,
    )


@pytest.mark.slow
def test_config_driven_pipeline_training(tmp_path):
    """[training.mesh] n_pipe reaches build_mesh through the training loop."""
    import json

    from spacy_ray_tpu.training.corpus import _doc_to_json
    from spacy_ray_tpu.training.loop import train

    for name, n, seed in (("train", 60, 0), ("dev", 20, 1)):
        with open(tmp_path / f"{name}.jsonl", "w", encoding="utf8") as f:
            for eg in synth_corpus(n, "tagger", seed=seed):
                f.write(json.dumps(_doc_to_json(eg.reference)) + "\n")

    cfg_text = TRF_CFG.replace("depth = 4", "depth = 2") + f"""
[paths]
train = "{tmp_path}/train.jsonl"
dev = "{tmp_path}/dev.jsonl"

[corpora.train]
@readers = "spacy.JsonlCorpus.v1"
path = ${{paths.train}}

[corpora.dev]
@readers = "spacy.JsonlCorpus.v1"
path = ${{paths.dev}}

[training]
seed = 0
max_steps = 3
eval_frequency = 3
patience = 0

[training.mesh]
n_pipe = 2

[training.optimizer]
@optimizers = "Adam.v1"
learn_rate = 0.001

[training.batcher]
@batchers = "spacy.batch_by_words.v1"
size = 300

[training.score_weights]
tag_acc = 1.0
"""
    nlp, result = train(Config.from_str(cfg_text), stdout_log=False)
    assert result.final_step == 3
    assert np.isfinite(result.best_score)
