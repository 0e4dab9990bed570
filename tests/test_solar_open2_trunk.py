"""The ``K`` and ``G`` layers of the pattern trunk (``models/hybrid_ssm.py``
with ``models/delta_attention.py``: Kimi delta attention, gated grouped
attention, gated-silu routed experts, the heads shared) against the plain
reference (``benchmark/reference/solar_open2_250b.py``, the ONE copy: this
file imports it by path), on the CPU in float32 at tiny widths. The letters
are ISSUE 36's satellites."""

import importlib.util
import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spacy_ray_tpu import names
from spacy_ray_tpu.config import load_config
from spacy_ray_tpu.models import delta_attention, hybrid_ssm, latent_moe
from spacy_ray_tpu.models.delta_attention import chunked_delta_rule, l2norm
from spacy_ray_tpu.models.hybrid_ssm import Shape, held_heads, init_params, trunk_forward
from spacy_ray_tpu.models.shadow import (
    SHADOW_LEAF_NAMES,
    TRUNK_F32_LEAF_NAMES,
    shadow_coverage,
    walk_layer_leaves,
)

ROOT = Path(__file__).resolve().parent.parent


def _reference():
    path = ROOT / "benchmark" / "reference" / "solar_open2_250b.py"
    spec = importlib.util.spec_from_file_location("reference_solar_open2_250b", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()

# one published period, the heads and the experts shared: rank 1 of 2 holds 4 of the 8
# query heads on 1 of the 2 key heads and 4 of the 8 linear heads; rank 1 of 4 holds 4 of 16 experts
TINY = Shape(
    pattern="GEKEKEKE", width=48, ssm_heads=0, ssm_head_dim=0, ssm_groups=1, ssm_state=0,
    conv_kernel=4, chunk=4, n_heads=8, n_kv_heads=2, head_dim=16, expert_ffn=24, shared_ffn=24,
    n_experts=16, experts_held=4, expert_rank=1, top_k=3, route_scale=1.0, vocab_rows=97,
    expert_form=latent_moe.GATED_SILU, route_bias=False, kda_heads=8, kda_head_dim=16,
    kda_gate_rank=16, heads_held=4, kda_heads_held=4, head_rank=1)
B, T = 4, 10  # T is no multiple of the chunk (4): the last chunk is half padding
LENGTHS = np.array([10, 7, 3, 9])  # a padded batch of unequal lengths


def dims(s: Shape, tie: float = REF.ROUTE_TIE_F32) -> dict:
    return {**{key: getattr(s, key) for key in REF.PUBLISHED if key != "hidden_size"},
            "route_tie": tie}


def held(s: Shape):
    return (s.held_from, s.held_from + s.experts_held)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, TINY.vocab_rows, (B, T)))
    mask = jnp.asarray(np.arange(T)[None] < LENGTHS[:, None])
    return ids, mask


def rel_err(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))) / np.max(np.abs(np.asarray(want))))


def worst_leaf(got, want):
    """``trunk_check.gradient_errors``'s measure: max |difference| over max
    |reference| of the leaf or of the median leaf."""
    wants = jax.tree_util.tree_leaves_with_path(want)
    gots = jax.tree_util.tree_leaves(got)
    sizes = [float(jnp.max(jnp.abs(w))) for _, w in wants]
    floor = float(np.median(sizes))
    return max((float(jnp.max(jnp.abs(g - w))) / max(size, floor), jax.tree_util.keystr(path))
               for (path, w), g, size in zip(wants, gots, sizes))


# ---- (a) the chunked delta rule against the recurrence, a word at a time ---------------------


def delta_rule_recurrence(q, k, v, g, beta):
    """The statement the chunked form is held to: ``S_t = (I - beta_t k_t
    k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``,
    one position at a time (``lax.scan`` over T), float32. Shapes as
    ``chunked_delta_rule``'s."""

    def step(S, at):
        q_t, k_t, v_t, g_t, b_t = at  # [B, H, K] x 2, [B, H, V], [B, H, K], [B, H]
        S = jnp.exp(g_t)[..., None] * S
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, S)
        S = S + (b_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    Bn, _, H, K = k.shape
    start = jnp.zeros((Bn, H, K, v.shape[-1]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(step, start, tuple(
            jnp.moveaxis(a.astype(jnp.float32), 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def rule_inputs(t, seed=0, decay=1.0, beta_from=0.0, tail=0, b=2, h=3, k=8, v=8):
    """``decay``: the largest |g| a step; ``beta_from``: beta uniform in [beta_from, 2);
    ``tail``: positions at the row's end that are padding (everything nought)."""
    r = np.random.default_rng(seed)
    real = (np.arange(t) < t - tail)[None, :, None, None]
    q = l2norm(jnp.asarray(r.standard_normal((b, t, h, k)), jnp.float32)) * k ** -0.5 * real
    key = l2norm(jnp.asarray(r.standard_normal((b, t, h, k)), jnp.float32)) * real
    val = jnp.asarray(r.standard_normal((b, t, h, v)), jnp.float32) * real
    g = -jnp.asarray(r.uniform(0.001, decay, (b, t, h, k)), jnp.float32) * real
    beta = jnp.asarray(r.uniform(beta_from, 2.0, (b, t, h)), jnp.float32) * real[..., 0]
    return q, key, val, g, beta


CASES = {
    "two chunks, not a multiple": dict(t=7, chunk=4, sub=2),
    "three chunks, not a multiple": dict(t=10, chunk=4, sub=4),
    "three whole chunks": dict(t=12, chunk=4, sub=2),
    "one chunk longer than the row": dict(t=7, chunk=8, sub=4),
    "a padded tail": dict(t=21, chunk=8, sub=4, tail=6),
    "beta near 2": dict(t=37, chunk=16, sub=4, beta_from=1.95),
    # exp(-G_j) would pass float32's largest number inside one chunk: 12 a step x 32 steps
    "strong decay: the overflow case": dict(t=70, chunk=32, sub=16, decay=12.0, beta_from=1.5),
    "blocks of the published size": dict(t=130, chunk=64, sub=16),
}


@pytest.mark.parametrize("case", CASES)
def test_a_the_chunked_delta_rule_is_the_recurrence_forward_and_gradient(case):
    spec = dict(CASES[case])
    chunk, sub = spec.pop("chunk"), spec.pop("sub")
    args = rule_inputs(**spec)

    def chunked(*a):
        return chunked_delta_rule(*a, chunk, jnp.float32, sub)

    want = delta_rule_recurrence(*args)
    got = jax.jit(chunked)(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert rel_err(got, want) < 2e-5

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    wanted = jax.grad(loss(delta_rule_recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    gots = jax.jit(jax.grad(loss(chunked), argnums=(0, 1, 2, 3, 4)))(*args)
    for name, g, w in zip("q k v g beta".split(), gots, wanted):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert rel_err(g, w) < 1e-4, name


def test_a_the_decay_is_never_formed_as_two_exponentials():
    """What the differences are for: the same inputs through ``exp(G_i) *
    exp(-G_j)`` leave float32 (the case above passes because they are not)."""
    _, _, _, g, _ = rule_inputs(t=70, decay=12.0)
    G = jnp.cumsum(g[:, :32], axis=1)
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-G))))


def test_a_a_state_not_carried_over_the_chunk_edge_fails():
    args = rule_inputs(t=12)
    want = delta_rule_recurrence(*args)
    alone = jnp.concatenate([chunked_delta_rule(*(a[:, c:c + 4] for a in args), 4, jnp.float32)
                             for c in range(0, 12, 4)], axis=1)
    assert rel_err(alone[:, :4], want[:, :4]) < 2e-5 and rel_err(alone, want) > 1e-2


def test_a_without_the_solve_it_is_another_layer(monkeypatch):
    args = rule_inputs(t=12, beta_from=1.0)
    want = delta_rule_recurrence(*args)
    monkeypatch.setattr(delta_attention, "_solve", lambda A, rhs: rhs)
    assert rel_err(chunked_delta_rule(*args, 4, jnp.float32), want) > 1e-2


# ---- (b) the shares add up ------------------------------------------------------------------


def _layer(s: Shape, kind: str, seed: int = 3):
    index = s.pattern.index(kind)
    return init_params(jax.random.PRNGKey(seed), s)[f"layer_{index}"]


@pytest.mark.parametrize("kind", ["G", "K", "*"])
def test_b_the_head_ranks_parts_sum_to_the_uncut_mixer(kind, batch):
    """Over all ``head_rank``s the partial outputs (each rank's heads through
    its rows of the output projection) add up to the uncut mixer's."""
    _, mask = batch
    ranks = 2
    uncut = replace(TINY, pattern=kind + "E", heads_held=0, kda_heads_held=0, head_rank=0)
    layer = _layer(uncut, kind)
    h = jnp.asarray(np.random.default_rng(1).standard_normal((B, T, TINY.width)), jnp.float32)
    scope = hybrid_ssm.KINDS[kind]

    def mixer(p, s):
        if kind == "K":
            return hybrid_ssm.kda_mixer(p, h, s, jnp.float32)
        return hybrid_ssm.grouped_attention(p, h, mask, s, jnp.float32, scope)

    whole = mixer(layer, uncut)
    parts = []
    for rank in range(ranks):
        s = replace(uncut, heads_held=uncut.n_heads // ranks,
                    kda_heads_held=uncut.kda_heads // ranks, head_rank=rank)
        mine = held_heads(layer, scope, rank, ranks)
        seeded = _layer(s, kind)  # what a chip of that share is seeded with: the same shapes
        assert ({k: v.shape for k, v in mine.items()} == {k: v.shape for k, v in seeded.items()})
        parts.append(mixer(mine, s))
    assert rel_err(sum(parts), whole) < 1e-5
    assert rel_err(parts[0], whole) > 1e-2  # one rank alone is not the layer


def test_b_the_expert_ranks_parts_and_the_shared_expert_once_sum_to_the_whole_layer(batch):
    """Over all ``expert_rank``s the routed parts add up, the shared expert
    counted once, to the layer that holds every expert (gated_silu form)."""
    _, mask = batch
    whole_s = replace(TINY, pattern="E", experts_held=TINY.n_experts, expert_rank=0)
    layer = _layer(whole_s, "E")
    h = jnp.asarray(np.random.default_rng(2).standard_normal((B, T, TINY.width)), jnp.float32)
    whole, _, _ = hybrid_ssm.expert_mixer(layer, h, mask, whole_s, jnp.float32)
    shared = latent_moe._gated(h.reshape(B * T, -1), layer["sg_W"], layer["su_W"], layer["sd_W"],
                               jnp.float32).reshape(B, T, -1)
    routed = jnp.zeros_like(whole)
    ranks = TINY.n_experts // TINY.experts_held
    for rank in range(ranks):
        s = replace(whole_s, experts_held=TINY.experts_held, expert_rank=rank)
        lo = rank * TINY.experts_held
        mine = dict(layer, **{name: layer[name][lo:lo + TINY.experts_held]
                              for name in latent_moe.EXPERT_LEAVES[latent_moe.GATED_SILU]})
        part, counters, _ = hybrid_ssm.expert_mixer(mine, h, mask, s, jnp.float32)
        assert int(counters[1]) == int(counters[2])  # every held pair came back
        routed = routed + (part - shared)
    assert rel_err(routed + shared, whole) < 1e-5


# ---- (c) the trunk against the reference ------------------------------------------------------


@pytest.mark.parametrize("remat", [False, True])
def test_c_forward_and_gradient_agree_with_the_reference(batch, remat):
    ids, mask = batch
    p = init_params(jax.random.PRNGKey(0), TINY)
    x, moe, ssm, choices = jax.jit(lambda p: trunk_forward(p, ids, mask, TINY, remat=remat))(p)
    assert int(ssm[0]) == 0 and int(moe[4]) == 4  # no M layer; four expert blocks
    want = REF.forward(p, ids, mask, held(TINY), choices, dims(TINY))
    assert REF.LAST_TIES["used"] == 0
    assert rel_err(x, want) < REF.TOLERANCE_F32
    R = jnp.asarray(np.random.default_rng(1).standard_normal(x.shape), jnp.float32) * mask[..., None]
    got = jax.jit(jax.grad(lambda p: jnp.sum(trunk_forward(p, ids, mask, TINY, remat=remat)[0] * R)))(p)
    wanted = jax.grad(lambda p: jnp.sum(REF.forward(p, ids, mask, held(TINY), choices, dims(TINY)) * R))(p)
    err, leaf = worst_leaf(got, wanted)
    assert err < REF.GRAD_TOLERANCE_F32, leaf
    # every kind of leaf the K and G layers have gets a gradient
    for name in ("q_W", "conv_W", "fa_W", "fb_W", "ga_W", "gb_W", "beta_W", "A_log", "dt_bias",
                 "o_norm_g", "ao_W"):
        assert float(jnp.max(jnp.abs(got["layer_2"][name]))) > 0, name
    assert float(jnp.max(jnp.abs(got["layer_0"]["gate_W"]))) > 0
    assert float(jnp.max(jnp.abs(got["layer_1"]["router_b"]))) == 0  # selection only


def test_c_a_padded_position_moves_no_real_one(batch):
    ids, mask = batch
    p = init_params(jax.random.PRNGKey(0), TINY)
    x = trunk_forward(p, ids, mask, TINY)[0]
    other = jnp.where(mask, ids, (ids + 1) % TINY.vocab_rows)  # other words where there is padding
    y = trunk_forward(p, other, mask, TINY)[0]
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert float(jnp.max(jnp.abs(jnp.where(mask[..., None], 0.0, x)))) == 0.0


def test_c_the_reference_is_given_the_same_share_and_a_wrong_one_fails(batch):
    ids, mask = batch
    p = init_params(jax.random.PRNGKey(0), TINY)
    x, _, _, choices = trunk_forward(p, ids, mask, TINY)
    # far off, or refused outright (NaN: downstream routers then choose otherwise)
    wrong = (0, TINY.experts_held)  # rank 0's experts with rank 1's weights
    assert not rel_err(x, REF.forward(p, ids, mask, wrong, choices, dims(TINY))) <= 1e-3
    unscaled = dict(dims(TINY), kda_neg_eigval=False)
    assert not rel_err(x, REF.forward(p, ids, mask, held(TINY), choices, unscaled)) <= 1e-3


def test_the_registered_architecture_refuses_head_shares_that_do_not_divide():
    from spacy_ray_tpu.registry import registry

    make = registry.architectures.get("spacy_ray_tpu.HybridSSMTrunk.v1")
    base = dict(pattern="GEKE", width=32, n_heads=8, n_kv_heads=2, head_dim=8, kda_heads=8,
                kda_head_dim=8, kda_gate_rank=8, expert_ffn=16, shared_ffn=16, n_experts=8,
                experts_held=4, top_k=2, vocab_rows=97, expert_form="gated_silu")
    model = make(**base, heads_held=4, kda_heads_held=4, head_rank=1)
    assert model.meta["shape"].kv_heads_here == 1
    for bad in (dict(heads_held=2, kda_heads_held=2),  # half a group of query heads
                dict(heads_held=4, kda_heads_held=2),  # two and four ranks
                dict(heads_held=4, kda_heads_held=4, head_rank=2),
                dict(heads_held=3, kda_heads_held=4)):
        with pytest.raises(ValueError, match="heads_held"):
            make(**base, **bad)
    with pytest.raises(ValueError, match="kda_heads"):
        make(**dict(base, kda_heads=0))
    with pytest.raises(ValueError, match="expert_form"):
        make(**dict(base, expert_form="gelu"))


# ---- (d) the normal path with the shipped .cfg at rehearsal widths ------------------------------

BENCH_CONFIG = json.loads((ROOT / "benchmark" / "configs" / "solar_open2_250b.json").read_text())


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from spacy_ray_tpu.training.corpus import _doc_to_json
    from spacy_ray_tpu.training.loop import train
    from spacy_ray_tpu.util import synth_corpus

    work = tmp_path_factory.mktemp("solar_open2")
    for name, n, seed in (("train", 160, 0), ("dev", 24, 7)):
        egs = synth_corpus(n // 2, "parser", seed=seed) + synth_corpus(n // 2, "ner", seed=seed + 1)
        with open(work / f"{name}.jsonl", "w", encoding="utf8") as f:
            for eg in egs:
                f.write(json.dumps(_doc_to_json(eg.reference)) + "\n")
    cfg = load_config(ROOT / "configs" / "solar_open2_250b.cfg", {
        **BENCH_CONFIG["rehearse_overrides"],
        "components.transformer.model.chunk": 8,
        "components.transformer.model.compute_dtype": "bfloat16",
        "paths.train": str(work / "train.jsonl"), "paths.dev": str(work / "dev.jsonl"),
        "training.max_steps": 12, "training.eval_frequency": 12, "training.batcher.size": 300,
        "training.dropout": 0.0, "training.fused_update": "on", "training.bf16_shadow": "on",
        "training.optimizer.learn_rate": 0.003,
    }, interpolate=False)
    nlp, result = train(cfg, output_path=work / "out", n_workers=1, stdout_log=False)
    return work, cfg, nlp, result


def test_d_the_shipped_config_trains_through_the_normal_path_and_reports_what_it_ran(trained):
    _, _, _, result = trained
    assert result.final_step == 12
    resolved = result.resolved
    assert resolved["layer_pattern"] == "GEKEKEKE" and resolved["kda_scan"] == "chunked 8, xla"
    assert resolved["head_share"] == "4 of 8 query, 1 of 2 key, 4 of 8 linear heads, rank 0"
    assert resolved["bf16_shadow"] == "on" and resolved["fused_update"].startswith("active")
    kda = resolved["kda"]
    assert kda["layers"] == 3 and kda["chunk"] == 8 and 0 < kda["live_chunks"] <= kda["chunks"]
    assert "ssm" not in resolved and "ssm_scan" not in resolved  # no M layer, no M keys
    moe = resolved["moe"]
    assert moe["dropped"] == 0 and resolved["moe_dropped"] == "0"
    assert resolved["moe_dispatch"].startswith("sorted, ragged_dot, 4 of 16 held")
    assert moe["assignments"] == result.words_seen * 3 * 4  # words x top_k x expert blocks
    losses = [sum(row["losses"].values()) for row in result.history]
    assert losses and np.isfinite(losses).all()


def test_d_every_leaf_of_the_new_layers_is_in_exactly_one_set(trained):
    _, _, nlp, _ = trained
    seen = []
    walk_layer_leaves(nlp.params["transformer"], lambda name, leaf, path: seen.append(name))
    assert {"gate_W", "beta_W", "fa_W", "fb_W", "ga_W", "gb_W", "o_norm_g", "conv_W", "A_log",
            "dt_bias", "eg_W", "sg_W", "router_b", "norm_g"} <= set(seen)
    for name in seen:
        assert (name in SHADOW_LEAF_NAMES) != (name in TRUNK_F32_LEAF_NAMES), name
    eligible, unknown = shadow_coverage(nlp.params)
    assert unknown == [] and eligible == sum(1 for n in seen if n in SHADOW_LEAF_NAMES)
    np.testing.assert_array_equal(  # no selection bias: nought, and it stays nought
        np.asarray(nlp.params["transformer"]["layer_1"]["router_b"]), 0.0)


def test_d_checkpoint_resume_evaluate_and_serve(trained):
    from spacy_ray_tpu.pipeline.language import Pipeline
    from spacy_ray_tpu.serving.engine import InferenceEngine
    from spacy_ray_tpu.training.loop import train
    from spacy_ray_tpu.util import synth_corpus

    work, cfg, _, _ = trained
    _, resumed = train(cfg.apply_overrides({"training.max_steps": 16}),
                       output_path=work / "out", n_workers=1, resume=True, stdout_log=False)
    assert resumed.final_step == 16 and resumed.resolved["moe"]["dropped"] == 0
    reloaded = Pipeline.from_disk(work / "out" / "last-model")
    scores = reloaded.evaluate(synth_corpus(12, "parser", seed=2))
    assert np.isfinite(scores["tag_acc"]) and np.isfinite(scores["dep_uas"])
    alone = reloaded("the cat runs quickly")
    assert alone.tags is not None and len(alone.tags) == 4 and len(alone.heads) == 4
    # serve's forward: the engine's warmed bucket programs answer as the pipeline does
    engine = InferenceEngine(reloaded, max_batch_docs=4, max_wait_s=0.01, max_doc_len=16)
    engine.start(warmup=True)
    try:
        request = engine.submit_texts(["the cat runs quickly", "a dog sleeps"])
        assert request.wait(60) and request.error is None
        assert list(request.docs[0].tags) == list(alone.tags) and len(request.docs[1].tags) == 3
    finally:
        engine.stop()


def test_d_the_counters_leave_the_sharded_step(trained):
    """parallel/step.py itself, shadow and fused update on: the K layers'
    counters are the mask's, and the loss falls."""
    from spacy_ray_tpu.models.shadow import build_param_shadow
    from spacy_ray_tpu.parallel.mesh import build_mesh
    from spacy_ray_tpu.parallel.step import make_train_step, place_batch, place_replicated
    from spacy_ray_tpu.pipeline.language import Pipeline
    from spacy_ray_tpu.registry import registry
    from spacy_ray_tpu.training import optimizers
    from spacy_ray_tpu.util import synth_corpus

    _, cfg, _, _ = trained
    nlp = Pipeline.from_config(cfg)
    examples = synth_corpus(16, "parser", seed=0)
    nlp.initialize(lambda: iter(examples), seed=0)
    mesh = build_mesh(n_data=1)
    tx = optimizers.fuse_optimizer(registry.resolve(
        {"@optimizers": "Adam.v1", "learn_rate": 0.003}))
    update = make_train_step(nlp.make_loss_fn(dropout=0.0), tx, mesh, shadow=True)
    params = place_replicated(nlp.params, mesh)
    state, shadow = tx.init(params), build_param_shadow(params)
    batch = nlp.collate(examples, with_targets=True)
    tokens, targets = place_batch(batch["tokens"], mesh), place_batch(batch["targets"], mesh)
    rng, losses = jax.random.PRNGKey(0), []
    for _ in range(12):
        rng, sub = jax.random.split(rng)
        params, state, shadow, loss, metrics = update(params, state, shadow, tokens, targets, sub)
        losses.append(float(loss))
    assert losses[-1] < 0.7 * losses[0], losses
    assert int(metrics[names.MOE_ASSIGNMENTS_HELD]) == int(metrics[names.MOE_COMPUTED]) > 0
    rows, t = batch["tokens"].mask.shape
    assert int(metrics[names.KDA_CHUNKS]) == rows * -(-t // 8) * 3  # rows x chunks x K layers
    live = int(np.any(np.asarray(batch["tokens"].mask).reshape(rows, -1, 8), axis=-1).sum()) * 3
    assert int(metrics[names.KDA_LIVE_CHUNKS]) == live <= int(metrics[names.KDA_CHUNKS])
    assert names.SSM_CHUNKS not in metrics
