"""The trunk built from a layer pattern (``models/hybrid_ssm.py``: Mamba-2
mixers, grouped-key attention, relu² routed experts) against its plain
reference (``benchmark/reference/nemotron3_nano_a3b.py``, the ONE copy: this
file imports it by path), on the CPU in float32 at tiny widths. The letters
are ISSUE 34's."""

import importlib.util
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spacy_ray_tpu import names
from spacy_ray_tpu.config import Config
from spacy_ray_tpu.models import hybrid_ssm, latent_moe
from spacy_ray_tpu.models.hybrid_ssm import Shape, init_params, trunk_forward
from spacy_ray_tpu.models.shadow import (
    SHADOW_LEAF_NAMES,
    TRUNK_F32_LEAF_NAMES,
    shadow_coverage,
    walk_layer_leaves,
)

ROOT = Path(__file__).resolve().parent.parent
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _reference():
    path = ROOT / "benchmark" / "reference" / "nemotron3_nano_a3b.py"
    spec = importlib.util.spec_from_file_location("reference_nemotron3_nano_a3b", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()

TINY = Shape(
    pattern="MEMEM*EME", width=48, ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
    conv_kernel=4, chunk=4, n_heads=4, n_kv_heads=2, head_dim=16, expert_ffn=24, shared_ffn=40,
    n_experts=16, experts_held=4, expert_rank=1, top_k=3, route_scale=2.5, vocab_rows=97)
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


def system(p, ids, mask, s=TINY, **kw):
    return jax.jit(lambda p: trunk_forward(p, ids, mask, s, **kw))(p)


def reference(p, ids, mask, choices, s=TINY):
    return np.asarray(REF.forward(p, ids, mask, held(s), choices, dims(s)))


def rel_err(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


def worst_leaf(got, want):
    """``trunk_check.gradient_errors``'s measure: max |difference| over max
    |reference| of the leaf or of the median leaf."""
    flat = jax.tree_util.tree_leaves_with_path(want)
    sizes = [float(jnp.max(jnp.abs(w))) for _, w in flat]
    floor = float(np.median(sizes))
    return max((float(jnp.max(jnp.abs(g - w))) / max(size, floor), jax.tree_util.keystr(path))
               for (path, w), g, size in zip(flat, jax.tree_util.tree_leaves(got), sizes))


# ---- (a) forward and gradient, each kind alone and the stack ------------------------------


@pytest.mark.parametrize("pattern,remat", [
    ("M", False), ("*", False), ("E", False), ("MEMEM*EME", False), ("MEMEM*EME", True)])
def test_a_forward_and_gradient_agree_with_the_reference(batch, pattern, remat):
    ids, mask = batch
    s = replace(TINY, pattern=pattern)
    p = init_params(jax.random.PRNGKey(3), s)
    X, moe, ssm, choices = system(p, ids, mask, s, remat=remat)
    choices = np.asarray(choices)
    want = reference(p, ids, mask, choices, s)
    assert np.isfinite(want).all()
    assert rel_err(X, want) <= REF.TOLERANCE_F32
    assert np.all(np.asarray(X)[~np.asarray(mask)] == 0)
    assert choices.shape == (pattern.count("E"), B, T, s.top_k)
    if "E" in pattern:
        assert REF.LAST_TIES["used"] == 0  # float32: the tie rule is not needed
        assert int(moe[0]) == int(LENGTHS.sum()) * s.top_k * pattern.count("E")
        assert int(moe[1]) == int(moe[2])  # nothing dropped
    # rows of 10, 7, 3, 9 words in chunks of 4: 3 + 2 + 1 + 3 of the 12 blocks are live
    assert [int(c) for c in ssm] == [12 * pattern.count("M"), 9 * pattern.count("M")]
    cot = jnp.asarray(np.random.default_rng(1).standard_normal((B, T, s.width)),
                      jnp.float32) * mask[..., None]
    got = jax.jit(jax.grad(
        lambda p: jnp.sum(trunk_forward(p, ids, mask, s, remat=remat)[0] * cot)))(p)
    wanted = jax.grad(
        lambda p: jnp.sum(REF.forward(p, ids, mask, held(s), choices, dims(s)) * cot))(p)
    err, leaf = worst_leaf(got, wanted)
    assert err <= REF.GRAD_TOLERANCE_F32, (leaf, err)
    for i, kind in enumerate(pattern):
        if kind == "E":  # selection only: no gradient at all
            assert np.all(np.asarray(got[f"layer_{i}"]["router_b"]) == 0)


def test_a_a_padded_position_moves_no_real_one(batch):
    ids, mask = batch
    p = init_params(jax.random.PRNGKey(3), TINY)
    X, moe, ssm, _ = system(p, ids, mask)
    other = jnp.where(mask, ids, (ids + 17) % TINY.vocab_rows)  # new words under the padding
    X2, moe2, ssm2, _ = system(p, other, mask)
    np.testing.assert_array_equal(np.asarray(X), np.asarray(X2))
    np.testing.assert_array_equal(np.asarray(moe), np.asarray(moe2))
    t = 5  # causal, every kind of layer: nothing before word t moves, word t and after do
    X3 = np.asarray(system(p, ids.at[0, t].set((ids[0, t] + 1) % TINY.vocab_rows), mask)[0])
    np.testing.assert_array_equal(np.asarray(X)[0, :t], X3[0, :t])
    assert np.abs(np.asarray(X)[0, t:] - X3[0, t:]).max() > 1e-3
    np.testing.assert_array_equal(np.asarray(X)[1:], X3[1:])


# ---- (b) the chunked scan against the recurrence, across a chunk's edge -------------------


def scan_inputs(t: int, seed: int = 2):
    rng = np.random.default_rng(seed)
    H, P, G, N = TINY.ssm_heads, TINY.ssm_head_dim, TINY.ssm_groups, TINY.ssm_state
    x = jnp.asarray(rng.standard_normal((2, t, H, P)), jnp.float32)
    B_ = jnp.asarray(rng.standard_normal((2, t, G, N)), jnp.float32)
    C_ = jnp.asarray(rng.standard_normal((2, t, G, N)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (2, t, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 4.0, (H,)), jnp.float32)
    return x, B_, C_, dt, A


def recurrence(x, B_, C_, dt, A):
    """The reference's own, row by row and position by position."""
    H, G = x.shape[2], B_.shape[2]
    rows = (x, jnp.repeat(B_, H // G, axis=2), jnp.repeat(C_, H // G, axis=2), dt)
    return np.asarray(jax.lax.map(lambda row: REF._recurrence_row(*row, A), rows))


@pytest.mark.parametrize("t,chunk", [(10, 4), (16, 4), (7, 8), (9, 3)])
def test_b_the_chunked_scan_is_the_recurrence(t, chunk):
    x, B_, C_, dt, A = scan_inputs(t)
    got = np.asarray(hybrid_ssm.chunked_scan(x, B_, C_, dt, A, chunk, jnp.float32))
    want = recurrence(x, B_, C_, dt, A)
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


def test_b_a_state_not_carried_over_the_chunk_edge_fails():
    chunk, t = 4, 10
    x, B_, C_, dt, A = scan_inputs(t)
    want = recurrence(x, B_, C_, dt, A)
    each_alone = np.concatenate([
        np.asarray(hybrid_ssm.chunked_scan(
            *(a[:, c:c + chunk] for a in (x, B_, C_, dt)), A, chunk, jnp.float32))
        for c in range(0, t, chunk)], axis=1)
    # the first chunk has nothing to be carried into it; every later one differs
    np.testing.assert_allclose(each_alone[:, :chunk], want[:, :chunk], atol=2e-5 * np.abs(want).max())
    assert np.abs(each_alone[:, chunk:] - want[:, chunk:]).max() > 0.05 * np.abs(want).max()


def test_b_the_backward_goes_through_the_carried_state():
    """The gradient of the LAST position's output with respect to the FIRST
    position's input is not nought, and is the recurrence's."""
    x, B_, C_, dt, A = scan_inputs(10)

    def last(fn):
        return jax.grad(lambda x: jnp.sum(fn(x)[:, -1]))(x)

    got = last(lambda x: hybrid_ssm.chunked_scan(x, B_, C_, dt, A, 4, jnp.float32))
    H, G = x.shape[2], B_.shape[2]
    Bh, Ch = jnp.repeat(B_, H // G, axis=2), jnp.repeat(C_, H // G, axis=2)
    want = last(lambda x: jax.lax.map(lambda row: REF._recurrence_row(*row, A), (x, Bh, Ch, dt)))
    assert float(jnp.abs(want[:, 0]).max()) > 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5 * float(jnp.abs(want).max()))


# ---- (c) the share adds up ------------------------------------------------------------------


def test_c_sixteen_ranks_parts_and_the_shared_expert_once_sum_to_the_whole_layer():
    s = replace(TINY, pattern="E", n_experts=32, experts_held=2, expert_rank=0)
    assert s.n_experts // s.experts_held == 16
    rng = np.random.default_rng(2)
    whole = replace(s, experts_held=s.n_experts)
    p_full = init_params(jax.random.PRNGKey(7), whole)["layer_0"]
    h = jnp.asarray(rng.standard_normal((B * T, s.width)), jnp.float32)
    real = jnp.asarray((np.arange(T)[None] < LENGTHS[:, None]).reshape(-1))
    idx, weights = latent_moe.route(p_full, h, s)
    total = np.zeros((B * T, s.width), np.float32)
    computed = 0
    for rank in range(16):
        rs = replace(s, expert_rank=rank)
        lo, hi = held(rs)
        p_rank = dict(p_full, eu_W=p_full["eu_W"][lo:hi], ed_W=p_full["ed_W"][lo:hi])
        y, counters = latent_moe.routed_experts(
            p_rank, h, real, idx, weights, rs, jnp.float32, form=latent_moe.RELU2)
        total += np.asarray(y)
        computed += int(counters[2])
    total += np.asarray(hybrid_ssm.relu2_ffn(h, p_full["su_W"], p_full["sd_W"], jnp.float32))
    want, _ = REF._experts(
        jax.tree_util.tree_map(jnp.asarray, p_full), h.reshape(B, T, -1), real.reshape(B, T),
        (0, s.n_experts), idx.reshape(B, T, -1), {**REF.PUBLISHED, **dims(s)})
    want = np.asarray(want).reshape(B * T, -1)
    assert computed == int(real.sum()) * s.top_k  # every pair on exactly one rank
    np.testing.assert_allclose(total[np.asarray(real)], want[np.asarray(real)], atol=2e-5)


# ---- (d) the pattern string -------------------------------------------------------------------


def test_d_the_published_string_parses_and_builds_its_order():
    kinds = hybrid_ssm.parse_pattern(PUBLISHED_PATTERN)
    assert len(kinds) == 52
    assert (kinds.count("mamba"), kinds.count("moe"), kinds.count("attention")) == (23, 23, 6)
    assert PUBLISHED_PATTERN[:9] == TINY.pattern == "MEMEM*EME"
    s = replace(TINY, pattern=PUBLISHED_PATTERN, width=16, ssm_head_dim=2, expert_ffn=8,
                shared_ffn=8, head_dim=4, vocab_rows=11)
    assert s.depth == 52
    p = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), s))
    marks = {"M": "in_W", "*": "q_W", "E": "router_W"}
    for i, char in enumerate(PUBLISHED_PATTERN):  # each layer has its own kind's leaves, in order
        assert [c for c, leaf in marks.items() if leaf in p[f"layer_{i}"]] == [char], i
    assert "layer_52" not in p
    assert p["layer_0"]["in_W"].shape == (16, 2 * 16 + 2 * 32)  # z | x | B | C
    assert p["layer_0"]["dt_W"].shape == (16, 8)  # and dt: the step's columns, a leaf of their own
    assert p["layer_0"]["conv_W"].shape == (4, 16 + 2 * 32)


@pytest.mark.parametrize("bad", ["", "MEX", "me", "M E"])
def test_d_a_pattern_of_other_characters_is_refused_by_name(bad):
    with pytest.raises(ValueError, match="layer pattern"):
        hybrid_ssm.HybridSSMTrunk(pattern=bad)


def test_the_registered_architecture_refuses_shares_that_do_not_divide():
    with pytest.raises(ValueError, match="must divide"):
        hybrid_ssm.HybridSSMTrunk(n_experts=128, experts_held=24)
    with pytest.raises(ValueError, match="expert_rank"):
        hybrid_ssm.HybridSSMTrunk(n_experts=128, experts_held=8, expert_rank=16)
    with pytest.raises(ValueError, match="n_kv_heads"):
        hybrid_ssm.HybridSSMTrunk(n_heads=32, n_kv_heads=3)


# ---- (e) the routed trunk's program is the one it was --------------------------------------------


def test_e_the_routed_trunks_lowered_program_is_unchanged_by_the_experts_form(monkeypatch):
    """``_expert_products`` as it stood before it took a form (the gated
    three-matrix expert, ISSUE 28's) in the new one's place: the routed
    trunk's forward and backward lower to the same text, byte for byte."""
    from functools import partial

    s = latent_moe.Shape(
        width=64, n_heads=4, qk_nope=16, qk_rope=8, v_head=16, kv_rank=16, dense_ffn=96,
        expert_ffn=32, n_experts=16, experts_held=4, expert_rank=1, top_k=3, n_shared=2,
        route_scale=2.448, first_dense=1, depth=3, vocab_rows=97, rope_theta=1e6)
    rng = np.random.default_rng(6)
    ids = jnp.asarray(rng.integers(0, 97, (8, 64)))  # 1,536 pairs: the bounded branch is there
    mask = jnp.asarray(np.arange(64)[None] < rng.integers(30, 65, (8, 1)))
    positions = jnp.broadcast_to(jnp.arange(64)[None], (8, 64))
    p = latent_moe.init_params(jax.random.PRNGKey(3), s)

    def lowered():
        def loss(p):
            return jnp.sum(latent_moe.trunk_forward(
                p, ids, mask, positions, s, compute_dtype=jnp.bfloat16, remat=True)[0])
        return jax.jit(jax.grad(loss)).lower(p).as_text()

    def as_it_stood(rows, live, group_sizes, eg, eu, ed):
        cd = rows.dtype
        grouped = partial(jax.lax.ragged_dot, group_sizes=group_sizes)
        gate = grouped(rows, eg).astype(jnp.float32)
        up = grouped(rows, eu).astype(jnp.float32)
        inner = jnp.where(live, jax.nn.silu(gate) * up, 0).astype(cd)
        return jnp.where(live, grouped(inner, ed), 0)

    now = lowered()
    monkeypatch.setattr(
        latent_moe, "_expert_products",
        lambda form, rows, live, group_sizes, experts: (as_it_stood(
            rows, live, group_sizes, *experts), None))
    assert "case" in now or "cond" in now  # both paths are in the program compared
    assert lowered() == now


def test_e_the_form_picks_the_experts_leaves():
    assert latent_moe.EXPERT_LEAVES == {"gated_silu": ("eg_W", "eu_W", "ed_W"),
                                        "relu2": ("eu_W", "ed_W")}
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)
    eu = jnp.asarray(rng.standard_normal((2, 8, 5)), jnp.float32)
    ed = jnp.asarray(rng.standard_normal((2, 5, 8)), jnp.float32)
    got, noughts = latent_moe._expert_products(
        latent_moe.RELU2, rows, jnp.ones((6, 1), bool), jnp.asarray([4, 2], jnp.int32), (eu, ed))
    want = np.concatenate([np.square(np.maximum(rows[:4] @ eu[0], 0)) @ ed[0],
                           np.square(np.maximum(rows[4:] @ eu[1], 0)) @ ed[1]])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    assert not np.asarray(noughts).any()
    gated = (eu, eu, ed)
    assert latent_moe._expert_products(latent_moe.GATED_SILU, rows, jnp.ones((6, 1), bool),
                                       jnp.asarray([4, 2], jnp.int32), gated)[1] is None


def test_e_an_expert_no_unit_of_which_fires_answers_noughts_and_is_not_a_dropped_pair():
    """relu² can answer a word with a row of noughts (every unit of the expert
    at or under nought): the pair was computed. Met on the chip: 9 of 850,972
    pairs of one run read as dropped before the counter knew (PR 34)."""
    s = replace(TINY, pattern="E")
    rng = np.random.default_rng(5)
    p = init_params(jax.random.PRNGKey(9), s)["layer_0"]
    h = jnp.asarray(np.abs(rng.standard_normal((B * T, s.width))), jnp.float32)  # all positive
    silent = s.held_from + 1
    p = dict(p, eu_W=p["eu_W"].at[1].set(-jnp.abs(p["eu_W"][1])))  # its every unit under nought
    real = jnp.ones((B * T,), bool)
    idx = jnp.full((B * T, s.top_k), silent, jnp.int32).at[:, 1:].set(
        jnp.asarray([s.held_from, s.held_from + 2], jnp.int32))
    weights = jnp.full((B * T, s.top_k), 1.0 / s.top_k, jnp.float32)
    y, counters = latent_moe.routed_experts(p, h, real, idx, weights, s, jnp.float32,
                                            form=latent_moe.RELU2)
    assert int(counters[1]) == int(counters[2]) == B * T * s.top_k  # held == computed
    y2, _ = latent_moe.routed_experts(  # and the silent expert adds nothing, as it should
        p, h, real, idx, weights.at[:, 0].set(0.0), s, jnp.float32, form=latent_moe.RELU2)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    # a row that is not live is still not counted: the same routing with half the words padding
    half = jnp.arange(B * T) < B * T // 2
    _, masked = latent_moe.routed_experts(p, h, half, idx, weights, s, jnp.float32,
                                          form=latent_moe.RELU2)
    assert int(masked[1]) == int(masked[2]) == (B * T // 2) * s.top_k


def test_e_relu2_experts_on_the_quarter_tier_agree_with_the_full_path(monkeypatch):
    """The routed dispatch's smallest buffer through this trunk: 8 x 64 words
    x top 3 = 1,536 pairs, a bound of 1,024 rows and a tier of 256, the held
    experts' selection bias lowered until each layer's live pairs fit the
    tier. Output, counters and every gradient leaf against the one-path
    program (the bound patched to every pair)."""
    s = replace(TINY, pattern="EE")
    rng = np.random.default_rng(6)
    ids = jnp.asarray(rng.integers(0, s.vocab_rows, (8, 64)))
    mask = jnp.asarray(np.arange(64)[None] < rng.integers(30, 65, (8, 1)))
    p = init_params(jax.random.PRNGKey(3), s)
    for name in ("layer_0", "layer_1"):
        p[name] = dict(p[name], router_b=p[name]["router_b"].at[
            s.held_from:s.held_from + s.experts_held].add(-0.05))
    cot = jnp.asarray(rng.standard_normal((8, 64, s.width)), jnp.float32) * mask[..., None]

    def run():
        def loss(p):
            X, moe, _, choices = trunk_forward(p, ids, mask, s, remat=True)
            return jnp.sum(X * cot), (X, moe, choices)
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(p)

    assert latent_moe.buffer_bounds(8 * 64 * 3, s) == (1024, 256)
    (_, (X, moe, choices)), grads = run()
    monkeypatch.setattr(latent_moe, "live_bound", lambda n_pairs, s: n_pairs)
    (_, (X_full, moe_full, _)), grads_full = run()
    lo, hi = held(s)
    chosen = np.asarray(choices)[:, np.asarray(mask)]
    live = [int(((c >= lo) & (c < hi)).sum()) for c in chosen]
    assert all(0 < n <= 256 for n in live), live
    assert [int(c) for c in moe[5:]] == [2, 2 * 256, 2]  # bounded, rows, tier
    assert [int(c) for c in moe_full[5:]] == [0, 2 * 8 * 64 * 3, 0]
    np.testing.assert_array_equal(np.asarray(moe[:5]), np.asarray(moe_full[:5]))
    assert int(moe[1]) == int(moe[2])  # nothing dropped
    for got, want in ((X, X_full), (grads, grads_full)):
        for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want),
                                jax.tree_util.tree_leaves(got)):
            err = float(jnp.max(jnp.abs(g - w))) / max(float(jnp.max(jnp.abs(w))), 1e-30)
            assert err <= 2e-5, (jax.tree_util.keystr(path), err)


# ---- grouped keys through the attention entry point -----------------------------------------------


def test_grouped_keys_through_the_attention_entry_point():
    from spacy_ray_tpu.ops import flash_attention as fa

    rng = np.random.default_rng(4)
    b, t, h, kv, d = 2, 20, 6, 2, 16
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, kv, d)), jnp.float32)
    mask = jnp.asarray(np.arange(t)[None] < np.array([20, 13])[:, None])
    got = np.asarray(fa.attention(q, k, v, mask, causal=True))
    k_all, v_all = np.repeat(k, h // kv, axis=2), np.repeat(v, h // kv, axis=2)  # head j reads j // 3
    scores = np.einsum("bqhd,bkhd->bhqk", q, k_all) / np.sqrt(d)
    visible = np.tril(np.ones((t, t), bool))[None, None] & np.asarray(mask)[:, None, None, :]
    scores = np.where(visible, scores, -np.inf)
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", weights, v_all)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert "6 query heads on 2 key heads" in fa.flash_attention_status()
    # not causal either: grouped keys alone take the XLA path
    np.testing.assert_allclose(
        np.asarray(fa.attention(q, k, v, mask)),
        np.asarray(fa.xla_attention(q, jnp.asarray(k_all), jnp.asarray(v_all), mask)), atol=2e-5)
    with pytest.raises(ValueError, match="evenly"):
        fa.attention(q, k[:, :, :1].repeat(4, axis=2), v[:, :, :1].repeat(4, axis=2), mask)


# ---- (f) the normal path: train, checkpoint, evaluate, serve, overlay ---------------------------------

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
@architectures = "spacy_ray_tpu.HybridSSMTrunk.v1"
pattern = "MEM*E"
width = 64
ssm_heads = 8
ssm_head_dim = 8
ssm_groups = 2
ssm_state = 16
chunk = 8
n_heads = 4
n_kv_heads = 2
head_dim = 16
expert_ffn = 32
shared_ffn = 48
n_experts = 16
experts_held = 4
expert_rank = 0
top_k = 3
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

    work = tmp_path_factory.mktemp("hybrid_ssm")
    write_synth_jsonl(work / "train.jsonl", 200, kind="tagger", seed=0)
    write_synth_jsonl(work / "dev.jsonl", 40, kind="tagger", seed=1)
    cfg = Config.from_str(TINY_CFG).apply_overrides(
        {"paths.train": str(work / "train.jsonl"), "paths.dev": str(work / "dev.jsonl")})
    nlp, result = train(cfg, output_path=work / "out", n_workers=1, stdout_log=False)
    return work, cfg, nlp, result


def test_f_a_train_run_through_the_normal_path_reports_what_it_ran(trained):
    _, _, _, result = trained
    assert result.final_step == 20
    resolved = result.resolved
    assert resolved["layer_pattern"] == "MEM*E" and resolved["ssm_scan"] == "chunked 8, xla"
    assert resolved["bf16_shadow"] == "on" and resolved["fused_update"].startswith("active")
    ssm = resolved["ssm"]
    assert ssm["layers"] == 2 and ssm["chunk"] == 8 and 0 < ssm["live_chunks"] <= ssm["chunks"]
    moe = resolved["moe"]
    assert moe["dropped"] == 0 and resolved["moe_dropped"] == "0"
    assert resolved["moe_dispatch"].startswith("sorted, ragged_dot, 4 of 16 held")
    assert moe["assignments"] == result.words_seen * 3 * 2  # words x top_k x expert layers
    assert 0 < moe["assignments_held"] < moe["assignments"]
    losses = [row["losses"]["tagger"] for row in result.history]
    assert losses and np.isfinite(losses).all()
    assert result.best_score > 0.5  # the tagger learns through the trunk


def test_f_every_trunk_leaf_is_in_exactly_one_set(trained):
    _, _, nlp, _ = trained
    seen = []
    walk_layer_leaves(nlp.params["transformer"], lambda name, leaf, path: seen.append(name))
    assert {"in_W", "dt_W", "conv_W", "A_log", "D", "dt_bias", "gate_norm_g", "q_W", "k_W", "eu_W",
            "su_W", "router_b", "norm_g"} <= set(seen)
    for name in seen:
        assert (name in SHADOW_LEAF_NAMES) != (name in TRUNK_F32_LEAF_NAMES), name
    eligible, unknown = shadow_coverage(nlp.params)
    assert unknown == [] and eligible == sum(1 for n in seen if n in SHADOW_LEAF_NAMES)
    # every matrix a bfloat16 product reads is shadowed: what stays float32 has one
    # dimension, or is the router's, the step's projection or the convolution's taps
    for name in set(seen) - SHADOW_LEAF_NAMES:
        assert name in ("router_W", "conv_W", "dt_W") or name.endswith(("_g", "_b", "_bias", "_log", "D")), name


def test_f_the_serving_overlay_covers_bf16_and_refuses_int8_by_name(trained, monkeypatch):
    from spacy_ray_tpu.serving import overlay

    _, _, nlp, _ = trained
    monkeypatch.setattr(overlay, "resolve_precision", lambda p: (p, "forced by the test"))
    bf16 = overlay.build_params_overlay(nlp.params, "bf16")
    assert bf16.resolved == "bf16" and bf16.n_overlaid == shadow_coverage(nlp.params)[0]
    trunk = bf16.params["transformer"]
    for name in ("in_W", "out_W"):
        assert trunk["layer_0"][name].dtype == jnp.bfloat16
    assert trunk["layer_0"]["conv_W"].dtype == trunk["layer_0"]["A_log"].dtype == jnp.float32
    assert trunk["layer_3"]["k_W"].dtype == jnp.bfloat16
    assert trunk["layer_1"]["eu_W"].dtype == jnp.bfloat16
    assert trunk["layer_1"]["router_W"].dtype == trunk["layer_1"]["router_b"].dtype == jnp.float32
    int8 = overlay.build_params_overlay(nlp.params, "int8")
    assert int8.resolved == "f32" and "refused" in int8.label and "in_W" in int8.label


def test_f_checkpoint_resume_evaluate_and_serve(trained):
    from spacy_ray_tpu.pipeline.language import Pipeline
    from spacy_ray_tpu.serving.engine import InferenceEngine
    from spacy_ray_tpu.training.loop import train
    from spacy_ray_tpu.util import synth_corpus

    work, cfg, _, _ = trained
    _, resumed = train(cfg.apply_overrides({"training.max_steps": 30}),
                       output_path=work / "out", n_workers=1, resume=True, stdout_log=False)
    assert resumed.final_step == 30 and resumed.resolved["moe"]["dropped"] == 0
    reloaded = Pipeline.from_disk(work / "out" / "last-model")
    dev = synth_corpus(20, "tagger", seed=2)
    assert reloaded.evaluate(dev)["tag_acc"] > 0.5
    alone = reloaded("the cat runs quickly")
    assert alone.tags is not None and len(alone.tags) == 4
    # serve's forward: the engine's warmed bucket programs answer as the pipeline does
    engine = InferenceEngine(reloaded, max_batch_docs=4, max_wait_s=0.01, max_doc_len=16)
    engine.start(warmup=True)
    try:
        request = engine.submit_texts(["the cat runs quickly", "a dog sleeps"])
        assert request.wait(60) and request.error is None
        assert list(request.docs[0].tags) == list(alone.tags) and len(request.docs[1].tags) == 3
    finally:
        engine.stop()


def test_f_loss_falls_through_the_sharded_step_and_the_counters_leave_with_it(trained):
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
    rows, t = batch["tokens"].mask.shape
    assert int(metrics[names.SSM_CHUNKS]) == rows * -(-t // 8) * 2  # rows x chunks x M layers
    live = int(np.any(np.asarray(batch["tokens"].mask).reshape(rows, -1, 8), axis=-1).sum()) * 2
    assert int(metrics[names.SSM_LIVE_CHUNKS]) == live <= int(metrics[names.SSM_CHUNKS])
    np.testing.assert_array_equal(  # stays at its seeded value
        np.asarray(params["transformer"]["layer_1"]["router_b"]), seeded)
