"""The ``L`` and ``D`` layers of the pattern trunk (``models/hybrid_ssm.py``:
latent attention with no positional term, a leading dense FFN) beside the
delta rule at all its heads with ``beta`` in (0, 1), against the plain
reference (``benchmark/reference/kimi_linear_48b_a3b.py``, the ONE copy: this
file imports it by path), on the CPU in float32 at tiny widths."""

import importlib.util
import re
from dataclasses import replace
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spacy_ray_tpu import names
from spacy_ray_tpu.models import hybrid_ssm, latent_moe
from spacy_ray_tpu.models.hybrid_ssm import Shape, init_params, pattern_from_layer_lists, trunk_forward

ROOT = Path(__file__).resolve().parent.parent


def _reference():
    path = ROOT / "benchmark" / "reference" / "kimi_linear_48b_a3b.py"
    spec = importlib.util.spec_from_file_location("reference_kimi_linear_48b_a3b", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
LISTS = {key: list(value) if isinstance(value, tuple) else value for key, value in REF.LAYER_LISTS.items()}

# the first five published layers; rank 1 of 4 holds 4 of 16 experts; every head held
TINY = Shape(
    pattern="KDKEKELEKE", width=48, ssm_heads=0, ssm_head_dim=0, ssm_groups=1, ssm_state=0,
    conv_kernel=4, chunk=4, n_heads=4, n_kv_heads=4, head_dim=16, expert_ffn=24, shared_ffn=24,
    n_experts=16, experts_held=4, expert_rank=1, top_k=3, route_scale=2.446, vocab_rows=97,
    expert_form=latent_moe.GATED_SILU, route_bias=True, kda_heads=4, kda_head_dim=8,
    kda_gate_rank=8, kda_neg_eigval=False, qk_nope=8, qk_rope=4, v_head=8, kv_rank=12,
    dense_ffn=40)
B, T = 4, 10  # T is no multiple of the chunk (4): the last chunk is half padding
LENGTHS = np.array([10, 7, 3, 9])  # a padded batch of unequal lengths


def dims(s: Shape) -> dict:
    return {**{key: getattr(s, key) for key in REF.PUBLISHED if key != "hidden_size"},
            "route_tie": REF.ROUTE_TIE_F32}


def held(s: Shape):
    return (s.held_from, s.held_from + s.experts_held)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, TINY.vocab_rows, (B, T)))
    mask = jnp.asarray(np.arange(T)[None] < LENGTHS[:, None])
    return ids, mask


def normed_input(seed: int, width: int = TINY.width):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((B, T, width)), jnp.float32)


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


# ---- the depth from the published lists -------------------------------------------------------


def test_the_published_lists_make_the_published_pattern_and_its_cut():
    whole = pattern_from_layer_lists(**LISTS, num_hidden_layers=27)
    assert len(whole) == 54 and whole.count("L") == 7 and whole.count("K") == 20
    assert whole.count("D") == 1 and whole.count("E") == 26
    mixers = whole[0::2]  # every published layer is its mixer, then its FFN
    assert [i + 1 for i, c in enumerate(mixers) if c == "L"] == LISTS["full_attn_layers"]
    assert whole[1::2] == "D" + "E" * 26
    assert mixers == "KKKL" * 6 + "KKL"  # the 3:1 period, then the last layer latent
    assert pattern_from_layer_lists(**LISTS, num_hidden_layers=27, keep_layers=5) == "KDKEKELEKE"
    assert whole[:10] == "KDKEKELEKE" == REF.published_kinds(10) and REF.published_kinds(54) == whole
    assert hybrid_ssm.parse_pattern(whole)[:4] == (hybrid_ssm.KDA, hybrid_ssm.DENSE, hybrid_ssm.KDA,
                                                  hybrid_ssm.MOE)


@pytest.mark.parametrize("bad", [
    dict(LISTS, kda_layers=LISTS["kda_layers"] + [4]),  # layer 4 named twice
    dict(LISTS, kda_layers=LISTS["kda_layers"][1:]),  # layer 1 named by neither
    dict(LISTS, full_attn_layers=LISTS["full_attn_layers"] + [28]),  # past the end
    dict(LISTS, keep_layers=28),
], ids=["twice", "neither", "past_the_end", "keep_too_many"])
def test_lists_that_do_not_name_each_layer_once_are_refused(bad):
    with pytest.raises(ValueError, match="kda_layers and full_attn_layers"):
        pattern_from_layer_lists(**bad, num_hidden_layers=27)


def test_the_shipped_configuration_builds_its_pattern_from_the_published_lists():
    from spacy_ray_tpu.config import load_config
    from spacy_ray_tpu.registry import registry

    config = load_config(ROOT / "configs" / "kimi_linear_48b_a3b.cfg", interpolate=False)
    block = config["components"]["transformer"]["model"]
    assert block["pattern"]["@misc"] == "spacy_ray_tpu.pattern_from_layer_lists.v1"
    assert registry.resolve(block["pattern"]) == "KDKEKELEKE"


# ---- latent attention: the switch for no rotary term ------------------------------------------

LATENT = latent_moe.Shape(
    width=48, n_heads=4, qk_nope=8, qk_rope=4, v_head=8, kv_rank=12, dense_ffn=40, expert_ffn=24,
    n_experts=8, experts_held=8, expert_rank=0, top_k=2, n_shared=1, route_scale=1.0,
    first_dense=1, depth=1, vocab_rows=97, rope_theta=1e6)


def _latent_case(batch):
    _, mask = batch
    p = latent_moe.init_params(jax.random.PRNGKey(4), LATENT)["layer_0"]
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    return p, normed_input(5), mask, positions


def test_latent_attention_with_the_rotary_term_lowers_as_it_stood(batch):
    """The rotary path, the default, against ``latent_attention`` as it stood
    before it took the switch: forward and backward lower to the same text."""
    from spacy_ray_tpu.ops.flash_attention import attention

    p, h, mask, positions = _latent_case(batch)

    def as_it_stood(p, h, mask, positions, s, cd):
        B, T, _ = h.shape
        H = s.n_heads
        h16 = h.astype(cd)
        q = (h16 @ p["q_W"].astype(cd)).reshape(B, T, H, s.qk_nope + s.qk_rope)
        kva = h16 @ p["kva_W"].astype(cd)
        c = latent_moe.rms_norm(kva[..., : s.kv_rank], p["rmskv_g"], s.rms_eps)
        kv = (c.astype(cd) @ p["kvb_W"].astype(cd)).reshape(B, T, H, s.qk_nope + s.v_head)
        with jax.named_scope(names.SCOPE_ATTN_ROPE):
            q_pe = latent_moe.rope(q[..., s.qk_nope:], positions, s.rope_theta).astype(cd)
            k_pe = latent_moe.rope(kva[..., None, s.kv_rank:], positions, s.rope_theta).astype(cd)
        q = jnp.concatenate([q[..., : s.qk_nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., : s.qk_nope], jnp.broadcast_to(k_pe, (B, T, H, s.qk_rope))], axis=-1)
        out = attention(q, k, kv[..., s.qk_nope:], mask, causal=True)
        return (out.reshape(B, T, H * s.v_head) @ p["ao_W"].astype(cd)).astype(jnp.float32)

    def lowered(fn):
        loss = lambda p: jnp.sum(fn(p, h, mask, positions, LATENT, jnp.bfloat16))  # noqa: E731
        return jax.jit(jax.grad(loss)).lower(p).as_text()

    now = lowered(latent_moe.latent_attention)
    assert "stablehlo.cosine" in now  # the rotation is in the program compared
    assert now == lowered(as_it_stood)


def test_latent_attention_without_the_rotary_term_differs_by_the_rotation_alone(batch):
    """No rotary term is the rotary path at position nought, bit for bit (a
    rotation by nought is the identity); at the words' own positions the two
    differ."""
    p, h, mask, positions = _latent_case(batch)
    plain = latent_moe.latent_attention(p, h, mask, None, LATENT, jnp.float32, rotary=False)
    at_nought = latent_moe.latent_attention(p, h, mask, jnp.zeros_like(positions), LATENT, jnp.float32)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(at_nought))
    rotated = latent_moe.latent_attention(p, h, mask, positions, LATENT, jnp.float32)
    assert rel_err(rotated, plain) > 1e-3
    no_rope = jax.make_jaxpr(lambda h: latent_moe.latent_attention(
        p, h, mask, None, LATENT, jnp.float32, rotary=False))(h)
    assert "cos" not in str(no_rope) and "sin" not in str(no_rope)


# ---- the delta rule at all heads, beta in (0, 1) ------------------------------------------------


def test_the_delta_rule_mixer_with_beta_under_one_is_the_references():
    s = replace(TINY, pattern="KE")
    layer = init_params(jax.random.PRNGKey(6), s)["layer_0"]
    h = normed_input(7)
    got = jax.jit(partial(hybrid_ssm.kda_mixer, s=s, cd=jnp.float32))(layer, h)

    def reference(neg_eigval):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, h: REF._kda(p, h, dict(dims(s), kda_neg_eigval=neg_eigval)))(layer, h)

    want, doubled = reference(False), reference(True)
    assert rel_err(got, want) < 2e-5
    assert rel_err(got, doubled) > 1e-2  # beta reaching 2 is another layer


# ---- the expert share ties to the model -------------------------------------------------------------


def test_four_ranks_of_two_experts_and_the_shared_expert_once_sum_to_the_uncut_reference_layer(batch):
    """At 4 ranks x 2 held of 8, the program's parts, the shared expert
    counted once, add up to what the uncut REFERENCE layer gives (every
    expert held, the same choices)."""
    _, mask = batch
    whole_s = replace(TINY, pattern="E", n_experts=8, experts_held=8, expert_rank=0, top_k=3)
    layer = init_params(jax.random.PRNGKey(8), whole_s)["layer_0"]
    h = normed_input(9)
    mixer = jax.jit(hybrid_ssm.expert_mixer, static_argnums=(3, 4))
    _, _, idx = mixer(layer, h, mask, whole_s, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, used, _ = jax.jit(lambda p, h, idx: REF._experts(p, h, mask, (0, 8), idx, dims(whole_s)))(
            layer, h, idx)
    assert int(jnp.sum(used)) == 0
    shared = latent_moe._gated(h.reshape(B * T, -1), layer["sg_W"], layer["su_W"], layer["sd_W"],
                               jnp.float32).reshape(B, T, -1)
    parts = []
    for rank in range(4):
        s = replace(whole_s, experts_held=2, expert_rank=rank)
        mine = dict(layer, **{name: layer[name][2 * rank:2 * rank + 2]
                              for name in latent_moe.EXPERT_LEAVES[latent_moe.GATED_SILU]})
        part, counters, _ = mixer(mine, h, mask, s, jnp.float32)
        assert int(counters[1]) == int(counters[2])  # every held pair came back
        parts.append(part - shared)
    real = np.asarray(mask)[..., None]
    assert rel_err((sum(parts) + shared) * real, want * real) < 1e-5
    assert rel_err((parts[0] + shared) * real, want * real) > 1e-2  # one rank alone is not the layer


# ---- the trunk against the reference ------------------------------------------------------------------


def test_forward_and_gradient_agree_with_the_reference_with_padded_rows(batch):
    """As the configuration trains it: each layer under remat."""
    ids, mask = batch
    remat = True
    p = init_params(jax.random.PRNGKey(0), TINY)
    forward = jax.jit(lambda p: trunk_forward(p, ids, mask, TINY, remat=remat))
    x, moe, ssm, choices = forward(p)
    assert int(ssm[0]) == 0 and int(moe[4]) == 4  # no M layer; four expert blocks
    want = REF.forward(p, ids, mask, held(TINY), choices, dims(TINY))
    assert REF.LAST_TIES["used"] == 0
    assert rel_err(x, want) < REF.TOLERANCE_F32
    assert float(jnp.max(jnp.abs(jnp.where(mask[..., None], 0.0, x)))) == 0.0  # padding zeroed
    R = jnp.asarray(np.random.default_rng(1).standard_normal(x.shape), jnp.float32) * mask[..., None]
    got = jax.jit(jax.grad(lambda p: jnp.sum(trunk_forward(p, ids, mask, TINY, remat=remat)[0] * R)))(p)
    wanted = jax.grad(lambda p: jnp.sum(REF.forward(p, ids, mask, held(TINY), choices, dims(TINY)) * R))(p)
    err, leaf = worst_leaf(got, wanted)
    assert err < REF.GRAD_TOLERANCE_F32, leaf
    # every kind of leaf the L and D layers have gets a gradient
    for name in ("q_W", "kva_W", "kvb_W", "ao_W", "rmskv_g", "norm_g"):
        assert float(jnp.max(jnp.abs(got["layer_6"][name]))) > 0, name
    for name in ("g_W", "u_W", "d_W"):
        assert float(jnp.max(jnp.abs(got["layer_1"][name]))) > 0, name
    assert float(jnp.max(jnp.abs(got["layer_3"]["router_b"]))) == 0  # selection only


def test_a_tree_that_is_not_the_published_stack_is_refused_by_the_reference(batch):
    ids, mask = batch
    swapped = replace(TINY, pattern="KDKEKEKELE")  # the latent layer a place late
    p = init_params(jax.random.PRNGKey(0), swapped)
    choices = jax.jit(lambda p: trunk_forward(p, ids, mask, swapped)[3])(p)
    assert np.isnan(np.asarray(REF.forward(p, ids, mask, held(swapped), choices, dims(swapped)))).all()


# ---- names and counters --------------------------------------------------------------------------------


def test_the_latent_and_dense_layers_have_their_scopes_forward_and_backward(batch):
    ids, mask = batch
    p = init_params(jax.random.PRNGKey(0), TINY)
    text = jax.jit(jax.grad(lambda p: jnp.sum(trunk_forward(
        p, ids, mask, TINY, remat=True)[0]))).lower(p).as_text(debug_info=True)
    trunk = re.escape(names.SCOPE_TRUNK)
    for scope in (names.SCOPE_ATTN, names.SCOPE_DENSE_FFN):
        for side in (rf"jvp\({trunk}\)", rf"transpose\(jvp\({trunk}\)\)"):
            assert re.search(rf'loc\("[^"]*{side}/[^"]*\b{re.escape(scope)}[)/"]', text), (scope, side)
    assert names.SCOPE_ATTN_ROPE not in text  # no rotary term anywhere


def test_the_score_counters_are_the_masks(batch):
    _, mask = batch
    scores, live = (int(v) for v in hybrid_ssm.score_counters(mask))  # one head of one layer
    assert scores == B * T * T
    assert live == sum(n * (n + 1) // 2 for n in LENGTHS)  # causal pairs of real words
    block = hybrid_ssm.summary(dict(zip(hybrid_ssm.MLA_COUNTER_KEYS, (scores, live))), TINY)
    assert block["mla"] == {"scores": 4 * scores, "live_scores": 4 * live, "layers": 1}  # 4 heads
    two = hybrid_ssm.summary(dict(zip(hybrid_ssm.MLA_COUNTER_KEYS, (scores, live))),
                             replace(TINY, pattern="KDLELE"))
    assert two["mla"] == {"scores": 8 * scores, "live_scores": 8 * live, "layers": 2}
    # a width and length at which the count over every head would leave int32
    wide = hybrid_ssm.summary(dict(zip(hybrid_ssm.MLA_COUNTER_KEYS, hybrid_ssm.score_counters(
        np.ones((64, 1024), bool)))), replace(TINY, n_heads=32))
    assert wide["mla"]["scores"] == 64 * 1024 * 1024 * 32 > 2 ** 31 - 1  # past int32
    assert "mla" not in hybrid_ssm.summary({}, replace(TINY, pattern="KE"))
    for key in hybrid_ssm.MLA_COUNTER_KEYS:
        assert key.startswith(names.COUNTER_PREFIX)


def test_the_registered_architecture_refuses_what_the_latent_and_dense_layers_lack():
    make = partial(hybrid_ssm.HybridSSMTrunk, pattern="KDLE", width=32, n_heads=4, n_kv_heads=4,
                   kda_heads=4, kda_head_dim=8, kda_gate_rank=8, expert_ffn=16, shared_ffn=16,
                   n_experts=8, experts_held=4, top_k=2, vocab_rows=97, expert_form="gated_silu",
                   qk_nope=8, qk_rope=4, v_head=8, kv_rank=12, dense_ffn=40)
    assert make().meta["shape"].pattern == "KDLE"
    with pytest.raises(ValueError, match="kv_rank"):
        make(kv_rank=0)
    with pytest.raises(ValueError, match="dense_ffn"):
        make(dense_ffn=0)
    with pytest.raises(ValueError, match="holds every one of its heads"):
        make(heads_held=2)
