"""The latent-attention / routed-expert trunk (``models/latent_moe.py``)
against its plain reference (``benchmark/reference/kanana2_a3b.py``, the ONE
copy: this file imports it by path), on the CPU in float32 at tiny widths.
The letters are ISSUE 27's."""

import importlib.util
from dataclasses import replace
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spacy_ray_tpu import names
from spacy_ray_tpu.config import Config
from spacy_ray_tpu.models import latent_moe
from spacy_ray_tpu.models.latent_moe import Shape, init_params, trunk_forward
from spacy_ray_tpu.models.shadow import (
    SHADOW_LEAF_NAMES,
    TRUNK_F32_LEAF_NAMES,
    shadow_coverage,
    walk_layer_leaves,
)

ROOT = Path(__file__).resolve().parent.parent


def _reference():
    path = ROOT / "benchmark" / "reference" / "kanana2_a3b.py"
    spec = importlib.util.spec_from_file_location("reference_kanana2_a3b", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()

TINY = Shape(
    width=64, n_heads=4, qk_nope=16, qk_rope=8, v_head=16, kv_rank=16, dense_ffn=96,
    expert_ffn=32, n_experts=16, experts_held=4, expert_rank=1, top_k=3, n_shared=2,
    route_scale=2.448, first_dense=1, depth=3, vocab_rows=97, rope_theta=1e6)
B, T = 4, 12
LENGTHS = np.array([12, 7, 3, 9])  # a padded batch of unequal lengths


def dims(s: Shape, tie: float = REF.ROUTE_TIE_F32) -> dict:
    return dict(n_heads=s.n_heads, qk_nope=s.qk_nope, qk_rope=s.qk_rope, v_head=s.v_head,
                kv_rank=s.kv_rank, n_experts=s.n_experts, top_k=s.top_k,
                route_scale=s.route_scale, rope_theta=s.rope_theta, route_tie=tie)


def held(s: Shape):
    return (s.held_from, s.held_from + s.experts_held)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, TINY.vocab_rows, (B, T)))
    mask = jnp.asarray(np.arange(T)[None] < LENGTHS[:, None])
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    return ids, mask, positions


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(3), TINY)


def system(p, ids, mask, positions, s=TINY, **kw):
    return jax.jit(lambda p: trunk_forward(p, ids, mask, positions, s, **kw))(p)


def reference(p, ids, mask, positions, choices, s=TINY):
    return np.asarray(REF.forward(p, ids, mask, positions, held(s), choices, dims(s)))


def rel_err(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


# ---- (a) forward, (g) scanned == unrolled, remat on and off -------------------


@pytest.mark.parametrize("scan,remat", [(True, False), (True, True), (False, False)])
def test_a_forward_agrees_with_the_reference(params, batch, scan, remat):
    X, _, choices = system(params, *batch, scan_layers=scan, remat=remat)
    want = reference(params, *batch, np.asarray(choices))
    assert np.isfinite(want).all()
    assert REF.LAST_TIES["used"] == 0  # float32: the tie rule is not needed
    assert rel_err(X, want) <= REF.TOLERANCE_F32
    assert np.all(np.asarray(X)[~np.asarray(batch[1])] == 0)


def test_g_scanned_layers_equal_the_unrolled_loop(params, batch):
    deep = replace(TINY, depth=4)  # three scanned expert layers
    p = init_params(jax.random.PRNGKey(5), deep)
    a = system(p, *batch, s=deep, scan_layers=True)
    b = system(p, *batch, s=deep, scan_layers=False)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), atol=2e-6)
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))


# ---- (b) gradients of every leaf ---------------------------------------------------


@pytest.mark.parametrize("remat", [False, True])
def test_b_gradients_agree_leaf_by_leaf(params, batch, remat):
    ids, mask, positions = batch
    cot = jnp.asarray(np.random.default_rng(1).standard_normal((B, T, TINY.width)),
                      jnp.float32) * mask[..., None]
    choices = np.asarray(system(params, *batch)[2])

    def sys_loss(p):
        return jnp.sum(trunk_forward(p, ids, mask, positions, TINY, remat=remat)[0] * cot)

    def ref_loss(p):
        return jnp.sum(REF.forward(p, ids, mask, positions, held(TINY), choices, dims(TINY)) * cot)

    got, want = jax.jit(jax.grad(sys_loss))(params), jax.grad(ref_loss)(params)
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    sizes = [float(jnp.max(jnp.abs(w))) for _, w in flat_want]
    floor = float(np.median(sizes))
    for (path, w), g, size in zip(flat_want, jax.tree_util.tree_leaves(got), sizes):
        err = float(jnp.max(jnp.abs(g - w))) / max(size, floor)
        assert err <= REF.GRAD_TOLERANCE_F32, (jax.tree_util.keystr(path), err)
    for i in range(TINY.first_dense, TINY.depth):  # selection only: no gradient at all
        assert np.all(np.asarray(got[f"layer_{i}"]["router_b"]) == 0)


# ---- (c) the share adds up -----------------------------------------------------------


def test_c_the_ranks_parts_sum_to_the_whole_layer():
    """The routed parts of all n_experts / held ranks, with the shared
    experts counted once, sum to what the reference gives for the whole
    layer with every expert held."""
    s = TINY
    rng = np.random.default_rng(2)
    full = init_params(jax.random.PRNGKey(7), replace(s, experts_held=s.n_experts, expert_rank=0))
    p_full = full["layer_1"]
    h = jnp.asarray(rng.standard_normal((B * T, s.width)), jnp.float32)
    real = jnp.asarray((np.arange(T)[None] < LENGTHS[:, None]).reshape(-1))
    idx, weights = latent_moe.route(p_full, h, s)
    total = np.zeros((B * T, s.width), np.float32)
    computed = 0
    for rank in range(s.n_experts // s.experts_held):
        rs = replace(s, expert_rank=rank)
        lo, hi = held(rs)
        p_rank = dict(p_full, eg_W=p_full["eg_W"][lo:hi], eu_W=p_full["eu_W"][lo:hi],
                      ed_W=p_full["ed_W"][lo:hi])
        y, counters = latent_moe.routed_experts(p_rank, h, real, idx, weights, rs, jnp.float32)
        total += np.asarray(y)
        computed += int(counters[2])
    total += np.asarray(latent_moe._gated(h, p_full["sg_W"], p_full["su_W"], p_full["sd_W"],
                                          jnp.float32))
    want, _ = REF._expert_layer(
        jax.tree_util.tree_map(jnp.asarray, p_full), h.reshape(B, T, -1), real.reshape(B, T),
        (0, s.n_experts), idx.reshape(B, T, -1), {**REF.PUBLISHED, **dims(s)})
    want = np.asarray(want).reshape(B * T, -1)
    assert computed == int(real.sum()) * s.top_k  # every pair on exactly one rank
    np.testing.assert_allclose(total[np.asarray(real)], want[np.asarray(real)], atol=2e-5)


# ---- (d) no drop, (e) padding -------------------------------------------------------------


def test_d_no_pair_is_dropped_when_every_word_lands_on_the_same_experts(params, batch):
    forced = jax.tree_util.tree_map(lambda a: a, params)
    lo, _ = held(TINY)
    bias = np.zeros((TINY.n_experts,), np.float32)
    bias[lo:lo + TINY.top_k] = 10.0  # every word's whole top-k lands on three held experts
    for i in range(TINY.first_dense, TINY.depth):
        forced[f"layer_{i}"] = dict(forced[f"layer_{i}"], router_b=jnp.asarray(bias))
    X, counters, choices = system(forced, *batch)
    words, layers = int(LENGTHS.sum()), TINY.depth - TINY.first_dense
    assignments, on_held, computed, max_load, calls = (int(c) for c in counters)
    assert assignments == on_held == computed == words * TINY.top_k * layers
    assert max_load == words * layers and calls == layers  # one expert holds every word
    assert set(np.asarray(choices)[:, np.asarray(batch[1])].reshape(-1)) == set(
        range(lo, lo + TINY.top_k))
    want = reference(forced, *batch, np.asarray(choices))
    assert rel_err(X, want) <= REF.TOLERANCE_F32
    summarise = partial(latent_moe.moe_summary, experts_held=TINY.experts_held,
                        n_experts=TINY.n_experts)
    summary = summarise(dict(zip(latent_moe.COUNTER_KEYS, map(int, counters))))
    assert summary["moe"]["dropped"] == 0 and summary["moe_dropped"] == "0"
    assert summary["moe"]["max_expert_load"] == words
    # the counter reads what the product gave back: one of the three experts
    # returning nothing is a third of the pairs dropped, in every layer
    for i in range(TINY.first_dense, TINY.depth):
        forced[f"layer_{i}"] = dict(
            forced[f"layer_{i}"], ed_W=forced[f"layer_{i}"]["ed_W"].at[1].set(0.0))
    _, counters, _ = system(forced, *batch)
    summary = summarise(dict(zip(latent_moe.COUNTER_KEYS, map(int, counters))))
    assert summary["moe"]["dropped"] == words * layers and summary["moe_dropped"] != "0"


def test_e_a_padded_position_reaches_no_expert_and_moves_no_output(params, batch):
    ids, mask, positions = batch
    X, counters, _ = system(params, ids, mask, positions)
    other = jnp.where(mask, ids, (ids + 17) % TINY.vocab_rows)  # new words under the padding
    X2, counters2, _ = system(params, other, mask, positions)
    np.testing.assert_array_equal(np.asarray(X), np.asarray(X2))
    np.testing.assert_array_equal(np.asarray(counters), np.asarray(counters2))
    layers = TINY.depth - TINY.first_dense
    assert int(counters[0]) == int(LENGTHS.sum()) * TINY.top_k * layers
    assert int(counters[1]) == int(counters[2]) <= int(counters[0])


# ---- (f) causal, rotary --------------------------------------------------------------------


def test_f_causal_and_relative_positions(params, batch):
    ids, mask, positions = batch
    X = np.asarray(system(params, ids, mask, positions)[0])
    t = 5
    changed = ids.at[0, t].set((ids[0, t] + 1) % TINY.vocab_rows)
    X2 = np.asarray(system(params, changed, mask, positions)[0])
    np.testing.assert_array_equal(X[0, :t], X2[0, :t])  # nothing before word t moves
    assert np.abs(X[0, t:] - X2[0, t:]).max() > 1e-3  # word t and those after it do
    np.testing.assert_array_equal(X[1:], X2[1:])
    shifted = np.asarray(system(params, ids, mask, positions + 37)[0])
    np.testing.assert_allclose(shifted, X, atol=5e-5)  # rotary: only differences count


# ---- (i) the attention entry point ---------------------------------------------------------------


def test_i_causal_attention_with_split_widths_and_the_old_call_unchanged():
    from spacy_ray_tpu.ops import flash_attention as fa

    rng = np.random.default_rng(4)
    b, t, h = 2, 20, 3
    q = jnp.asarray(rng.standard_normal((b, t, h, 192)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, 192)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, 128)), jnp.float32)
    mask = jnp.asarray(np.arange(t)[None] < np.array([20, 13])[:, None])
    got = np.asarray(fa.attention(q, k, v, mask, causal=True))
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(192.0)
    visible = np.tril(np.ones((t, t), bool))[None, None] & np.asarray(mask)[:, None, None, :]
    scores = np.where(visible, scores, -np.inf)
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", weights, v)
    assert got.shape == (b, t, h, 128)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert "causal=True" in fa.flash_attention_status()
    # trf's call: the same program as before this trunk existed
    same = jnp.asarray(rng.standard_normal((b, t, h, 64)), jnp.float32)
    before = jax.make_jaxpr(lambda q, k, v, m: jax.nn.dot_product_attention(
        q, k, v, mask=m[:, None, None, :]))(same, same, same, mask)
    now = jax.make_jaxpr(fa.attention)(same, same, same, mask)
    assert str(now) == str(before)


# ---- (h) the normal path: train, checkpoint, resume, evaluate, overlay ------------------------------

TINY_CFG = """
[paths]
train = null
dev = null

[nlp]
lang = "en"
pipeline = ["transformer","tagger"]

[components.transformer]
factory = "transformer"

[components.transformer.model]
@architectures = "spacy_ray_tpu.LatentMoETrunk.v1"
width = 64
n_heads = 4
qk_nope = 16
qk_rope = 8
v_head = 16
kv_rank = 16
dense_ffn = 96
expert_ffn = 32
n_experts = 16
experts_held = 4
expert_rank = 0
top_k = 3
n_shared = 2
first_dense = 1
depth = 3
vocab_rows = 97
compute_dtype = "bfloat16"

[components.tagger]
factory = "tagger"

[components.tagger.model]
@architectures = "spacy.Tagger.v2"

[components.tagger.model.tok2vec]
@architectures = "spacy.Tok2VecListener.v1"
width = 64

[corpora.train]
@readers = "spacy.JsonlCorpus.v1"
path = ${paths.train}

[corpora.dev]
@readers = "spacy.JsonlCorpus.v1"
path = ${paths.dev}

[training]
seed = 0
dropout = 0.0
accumulate_gradient = 1
max_steps = 20
eval_frequency = 20
fused_update = "on"
bf16_shadow = "on"

[training.optimizer]
@optimizers = "Adam.v1"
learn_rate = 0.003

[training.batcher]
@batchers = "spacy.batch_by_words.v1"
size = 400
tolerance = 0.2
"""


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from spacy_ray_tpu.training.loop import train
    from spacy_ray_tpu.util import write_synth_jsonl

    work = tmp_path_factory.mktemp("latent_moe")
    write_synth_jsonl(work / "train.jsonl", 200, kind="tagger", seed=0)
    write_synth_jsonl(work / "dev.jsonl", 40, kind="tagger", seed=1)
    cfg = Config.from_str(TINY_CFG).apply_overrides(
        {"paths.train": str(work / "train.jsonl"), "paths.dev": str(work / "dev.jsonl")})
    nlp, result = train(cfg, output_path=work / "out", n_workers=1, stdout_log=False)
    return work, cfg, nlp, result


def test_h_a_train_run_through_the_normal_path(trained):
    work, cfg, nlp, result = trained
    assert result.final_step == 20
    losses = [row["losses"]["tagger"] for row in result.history]
    assert result.resolved["bf16_shadow"] == "on"
    assert result.resolved["fused_update"].startswith("active")
    moe = result.resolved["moe"]
    assert moe["dropped"] == 0 and result.resolved["moe_dropped"] == "0"
    assert result.resolved["moe_dispatch"] == "sorted, ragged_dot, 4 of 16 held"
    assert moe["assignments"] == result.words_seen * 3 * 2  # words x top_k x expert layers
    assert 0 < moe["assignments_held"] < moe["assignments"]
    assert moe["max_expert_load"] >= moe["mean_expert_load"] > 0
    assert losses and np.isfinite(losses).all()
    assert result.best_score > 0.5  # the tagger learns through the trunk


def test_h_every_trunk_leaf_is_in_exactly_one_set(trained):
    _, _, nlp, _ = trained
    seen = []
    walk_layer_leaves(nlp.params["transformer"], lambda name, leaf, path: seen.append(name))
    assert seen
    for name in seen:
        assert (name in SHADOW_LEAF_NAMES) != (name in TRUNK_F32_LEAF_NAMES), name
    eligible, unknown = shadow_coverage(nlp.params)
    assert unknown == [] and eligible == sum(1 for n in seen if n in SHADOW_LEAF_NAMES)


def test_h_the_serving_overlay_covers_bf16_and_refuses_int8(trained, monkeypatch):
    from spacy_ray_tpu.serving import overlay

    _, _, nlp, _ = trained
    monkeypatch.setattr(overlay, "resolve_precision", lambda p: (p, "forced by the test"))
    bf16 = overlay.build_params_overlay(nlp.params, "bf16")
    assert bf16.resolved == "bf16" and bf16.n_overlaid == shadow_coverage(nlp.params)[0]
    layer = bf16.params["transformer"]["layer_1"]
    assert layer["eg_W"].dtype == jnp.bfloat16 and layer["router_W"].dtype == jnp.float32
    assert layer["router_b"].dtype == jnp.float32
    int8 = overlay.build_params_overlay(nlp.params, "int8")
    assert int8.resolved == "f32" and "refused" in int8.label and "ao_W" in int8.label


def test_h_loss_falls_over_twenty_steps_through_the_sharded_step(trained):
    """parallel/step.py itself, shadow and fused update on, losses read."""
    from spacy_ray_tpu.models.shadow import build_param_shadow
    from spacy_ray_tpu.parallel.mesh import build_mesh
    from spacy_ray_tpu.parallel.step import make_train_step, place_batch, place_replicated
    from spacy_ray_tpu.pipeline.language import Pipeline
    from spacy_ray_tpu.registry import registry
    from spacy_ray_tpu.training import optimizers
    from spacy_ray_tpu.util import synth_corpus

    _, cfg, _, _ = trained
    nlp = Pipeline.from_config(cfg)
    examples = synth_corpus(32, "tagger", seed=0)
    nlp.initialize(lambda: iter(examples), seed=0)
    seeded = np.array(nlp.params["transformer"]["layer_1"]["router_b"])  # the step donates
    mesh = build_mesh(n_data=1)
    tx = optimizers.fuse_optimizer(registry.resolve(
        {"@optimizers": "Adam.v1", "learn_rate": 0.003}))
    update = make_train_step(nlp.make_loss_fn(dropout=0.0), tx, mesh, shadow=True)
    params = place_replicated(nlp.params, mesh)
    state, shadow = tx.init(params), build_param_shadow(params)
    batch = nlp.collate(examples, with_targets=True)
    tokens, targets = place_batch(batch["tokens"], mesh), place_batch(batch["targets"], mesh)
    rng, losses = jax.random.PRNGKey(0), []
    for _ in range(20):
        rng, sub = jax.random.split(rng)
        params, state, shadow, loss, metrics = update(params, state, shadow, tokens, targets, sub)
        losses.append(float(loss))
    assert losses[-1] < 0.5 * losses[0], losses
    assert int(metrics[names.MOE_ASSIGNMENTS_HELD]) == int(metrics[names.MOE_COMPUTED]) > 0
    np.testing.assert_array_equal(  # stays at its seeded value
        np.asarray(params["transformer"]["layer_1"]["router_b"]), seeded)


def test_h_checkpoint_resume_and_evaluate(trained):
    from spacy_ray_tpu.pipeline.language import Pipeline
    from spacy_ray_tpu.training.loop import train
    from spacy_ray_tpu.util import synth_corpus

    work, cfg, nlp, _ = trained
    _, resumed = train(cfg.apply_overrides({"training.max_steps": 30}),
                       output_path=work / "out", n_workers=1, resume=True, stdout_log=False)
    assert resumed.final_step == 30 and resumed.resolved["moe"]["dropped"] == 0
    reloaded = Pipeline.from_disk(work / "out" / "last-model")
    dev = synth_corpus(20, "tagger", seed=2)
    assert reloaded.evaluate(dev)["tag_acc"] > 0.5
    doc = reloaded("the cat runs quickly")
    assert doc.tags is not None and len(doc.tags) == 4


def test_the_registered_architecture_refuses_a_share_that_does_not_divide():
    with pytest.raises(ValueError, match="must divide"):
        latent_moe.LatentMoETrunk(n_experts=128, experts_held=24)
    with pytest.raises(ValueError, match="expert_rank"):
        latent_moe.LatentMoETrunk(n_experts=128, experts_held=16, expert_rank=8)
