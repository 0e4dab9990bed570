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


# a batch wide enough for the routed layer to have two paths: 8 x 64 words x
# top 3 = 1,536 pairs against a bound of 1,024 rows (twice the even share of 4
# of 16 experts, 768, rounded up to 512s)
WIDE_B, WIDE_T = 8, 64
WIDE_LENGTHS = np.array([64, 57, 33, 64, 48, 60, 64, 51])
WIDE_PAIRS = WIDE_B * WIDE_T * TINY.top_k


@pytest.fixture(scope="module")
def wide_batch():
    rng = np.random.default_rng(6)
    ids = jnp.asarray(rng.integers(0, TINY.vocab_rows, (WIDE_B, WIDE_T)))
    mask = jnp.asarray(np.arange(WIDE_T)[None] < WIDE_LENGTHS[:, None])
    positions = jnp.broadcast_to(jnp.arange(WIDE_T)[None], (WIDE_B, WIDE_T))
    return ids, mask, positions


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(3), TINY)


def every_word_on_held_experts(params, s=TINY):
    """The same parameters with a selection bias that lands every word's whole
    top-k on the first ``top_k`` held experts."""
    bias = np.zeros((s.n_experts,), np.float32)
    bias[s.held_from:s.held_from + s.top_k] = 10.0
    forced = dict(params)
    for i in range(s.first_dense, s.depth):
        forced[f"layer_{i}"] = dict(params[f"layer_{i}"], router_b=jnp.asarray(bias))
    return forced


def fewer_words_on_held_experts(params, s=TINY, by: float = 0.02):
    """The same parameters with the held experts' selection bias lowered by
    ``by``: on the wide batch every expert layer's live pairs (165 and 71)
    then fit the quarter tier of 256 rows."""
    lowered = dict(params)
    for i in range(s.first_dense, s.depth):
        layer = params[f"layer_{i}"]
        lowered[f"layer_{i}"] = dict(
            layer, router_b=layer["router_b"].at[s.held_from:s.held_from + s.experts_held].add(-by))
    return lowered


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


@pytest.mark.parametrize("which,bounded", [("batch", 0), ("wide_batch", 0)])
def test_d_no_pair_is_dropped_when_every_word_lands_on_the_same_experts(
        params, request, which, bounded):
    """On the small batch the layer has one path; on the wide one the live
    pairs (1,323) pass the bound (1,024) and the branch taken is the full one."""
    batch = request.getfixturevalue(which)
    forced = every_word_on_held_experts(params)
    lo, _ = held(TINY)
    X, counters, choices = system(forced, *batch)
    words, layers = int(np.asarray(batch[1]).sum()), TINY.depth - TINY.first_dense
    (assignments, on_held, computed, max_load, calls, bounded_calls, buffer_rows,
     tier_calls) = (int(c) for c in counters)
    assert assignments == on_held == computed == words * TINY.top_k * layers
    assert max_load == words * layers and calls == layers  # one expert holds every word
    assert bounded_calls == bounded == tier_calls
    assert buffer_rows == batch[0].size * TINY.top_k * layers  # every pair's row, each layer
    assert set(np.asarray(choices)[:, np.asarray(batch[1])].reshape(-1)) == set(
        range(lo, lo + TINY.top_k))
    want = reference(forced, *batch, np.asarray(choices))
    assert rel_err(X, want) <= REF.TOLERANCE_F32
    summarise = partial(latent_moe.moe_summary, experts_held=TINY.experts_held,
                        n_experts=TINY.n_experts)
    summary = summarise(dict(zip(latent_moe.COUNTER_KEYS, map(int, counters))))
    assert summary["moe"]["dropped"] == 0 and summary["moe_dropped"] == "0"
    assert summary["moe"]["max_expert_load"] == words
    assert summary["moe"]["bounded_calls"] == bounded and summary["moe"]["layer_calls"] == layers
    assert summary["moe"]["tier_calls"] == 0 and summary["moe"]["buffer_rows"] == buffer_rows
    # the counter reads what the product gave back: one of the three experts
    # returning nothing is a third of the pairs dropped, in every layer
    for i in range(TINY.first_dense, TINY.depth):
        forced[f"layer_{i}"] = dict(
            forced[f"layer_{i}"], ed_W=forced[f"layer_{i}"]["ed_W"].at[1].set(0.0))
    _, counters, _ = system(forced, *batch)
    summary = summarise(dict(zip(latent_moe.COUNTER_KEYS, map(int, counters))))
    assert summary["moe"]["dropped"] == words * layers and summary["moe_dropped"] != "0"


@pytest.mark.parametrize("which,bounded", [("batch", 0), ("wide_batch", 1)])
def test_e_a_padded_position_reaches_no_expert_and_moves_no_output(
        params, request, which, bounded):
    """``bounded``: of each layer's calls, those that take the bounded path
    (the wide batch's seeded routing sends about 330 pairs here, under 1,024)."""
    ids, mask, positions = request.getfixturevalue(which)
    X, counters, _ = system(params, ids, mask, positions)
    other = jnp.where(mask, ids, (ids + 17) % TINY.vocab_rows)  # new words under the padding
    X2, counters2, _ = system(params, other, mask, positions)
    np.testing.assert_array_equal(np.asarray(X), np.asarray(X2))
    np.testing.assert_array_equal(np.asarray(counters), np.asarray(counters2))
    layers = TINY.depth - TINY.first_dense
    assert int(counters[0]) == int(np.asarray(mask).sum()) * TINY.top_k * layers
    assert int(counters[1]) == int(counters[2]) <= int(counters[0])
    assert int(counters[5]) == bounded * layers


# ---- (j) the bounded live prefix and its fall-back (ISSUE 28) ---------------------------------


# the wide batch's two bounded buffers, taken before any test patches the bound
WIDE_BOUNDS = latent_moe.buffer_bounds(WIDE_PAIRS, TINY)


def rows_taken(live: int) -> int:
    """The rows of the buffer a call with ``live`` pairs on the wide batch
    takes: the quarter tier, the bound, or every pair."""
    bound, tier = WIDE_BOUNDS
    return tier if live <= tier else bound if live <= bound else WIDE_PAIRS


def live_per_layer(choices, mask):
    """Each expert layer's pairs on a held expert, from the experts chosen."""
    chosen = np.asarray(choices)[:, np.asarray(mask)]
    return [int(((c >= TINY.held_from) & (c < TINY.held_from + TINY.experts_held)).sum())
            for c in chosen]


def full_path_only(monkeypatch):
    """The parent's program: a bound of every pair leaves one path, no branch."""
    monkeypatch.setattr(latent_moe, "live_bound", lambda n_pairs, s: n_pairs)


def assert_leaves_close(got, want, what):
    """Every leaf within float32 rounding of the other path's, measured against
    the leaf's own size (the two paths add the same products in another order)."""
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    for (path, w), g in zip(flat_want, jax.tree_util.tree_leaves(got)):
        size = max(float(jnp.max(jnp.abs(w))), 1e-30)
        err = float(jnp.max(jnp.abs(g - w))) / size
        assert err <= 2e-5, (what, jax.tree_util.keystr(path), err)


def sub_jaxprs(jaxpr):
    """``jaxpr`` and every jaxpr inside its equations' parameters."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list)) else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from sub_jaxprs(inner)


def branches_of_the_conds(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [eqn.params["branches"] for sub in sub_jaxprs(jaxpr) for eqn in sub.eqns
            if eqn.primitive.name == "cond"]


@pytest.mark.parametrize("scan,remat", [(True, True), (True, False), (False, True), (False, False)])
@pytest.mark.parametrize("routing", ["under_the_bound", "over_the_bound", "under_the_tier"])
def test_j_the_bounded_path_agrees_with_the_full_path(
        params, wide_batch, monkeypatch, routing, scan, remat):
    """The same parameters and batch down the program with the bounds and down
    the parent's (one path, every pair moved): outputs, counters and every
    gradient leaf. Over the bound both take the full path, one of them through
    the branch; under the tier every layer takes the quarter buffer, and under
    the bound the seeded routing sends one layer through each bounded buffer."""
    ids, mask, positions = wide_batch
    p = {"under_the_bound": params, "over_the_bound": every_word_on_held_experts(params),
         "under_the_tier": fewer_words_on_held_experts(params)}[routing]
    cot = jnp.asarray(np.random.default_rng(8).standard_normal((WIDE_B, WIDE_T, TINY.width)),
                      jnp.float32) * mask[..., None]

    def run():
        def loss(p):
            X, counters, choices = trunk_forward(
                p, ids, mask, positions, TINY, scan_layers=scan, remat=remat)
            return jnp.sum(X * cot), (X, counters, choices)
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(p)

    bound = latent_moe.live_bound(WIDE_PAIRS, TINY)
    tier = latent_moe.tier_bound(bound)
    (_, (X, counters, choices)), grads = run()
    full_path_only(monkeypatch)
    (_, (X_full, counters_full, _)), grads_full = run()
    layers = TINY.depth - TINY.first_dense
    live = live_per_layer(choices, mask)
    assert sum(live) == int(counters[1])
    bounded_calls, buffer_rows, tier_calls = (int(c) for c in counters[5:])
    if routing == "under_the_tier":
        assert all(0 < n <= tier for n in live) and tier_calls == bounded_calls == layers
    elif routing == "under_the_bound":
        assert min(live) <= tier < max(live) < bound
        assert bounded_calls == layers and tier_calls == 1
    else:
        assert min(live) > bound and bounded_calls == tier_calls == 0
    assert buffer_rows == sum(map(rows_taken, live))
    assert [int(c) for c in counters_full[5:]] == [0, WIDE_PAIRS * layers, 0]
    np.testing.assert_array_equal(np.asarray(counters[:5]), np.asarray(counters_full[:5]))
    assert int(counters[1]) == int(counters[2])  # nothing dropped on either path
    assert_leaves_close(X, X_full, "output")
    assert_leaves_close(grads, grads_full, "gradient")


def one_expert_layer(n_live: int):
    """A routed layer's inputs with exactly ``n_live`` pairs on held experts:
    512 real words x top 3, the first pairs in flat order sent to the four held
    experts in turn and every other pair to absent ones."""
    rng = np.random.default_rng(9)
    n = WIDE_B * WIDE_T
    layer = init_params(jax.random.PRNGKey(11), TINY)["layer_1"]
    h = jnp.asarray(rng.standard_normal((n, TINY.width)), jnp.float32)
    flat = np.arange(n * TINY.top_k)
    idx = np.where(flat < n_live, TINY.held_from + flat % TINY.experts_held, flat % TINY.held_from)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (n, TINY.top_k)), jnp.float32)
    return layer, h, jnp.ones((n,), bool), jnp.asarray(idx.reshape(n, TINY.top_k), jnp.int32), weights


@pytest.mark.parametrize("buffer,past", [
    pytest.param("bound", 0, id="0"), pytest.param("bound", 1, id="1"),
    pytest.param("tier", 0, id="tier-0"), pytest.param("tier", 1, id="tier-1")])
def test_j_at_the_bound_and_one_pair_past_it(monkeypatch, buffer, past):
    """``n_live`` at each bounded buffer's rows and one pair past them: the
    quarter tier (256), the bound (1,024); one pair past the tier is the
    bound's, one past the bound the full path's."""
    bound = latent_moe.live_bound(WIDE_PAIRS, TINY)
    tier = latent_moe.tier_bound(bound)
    assert latent_moe.buffer_bounds(WIDE_PAIRS, TINY) == (bound, tier) == (1024, 256)
    assert bound < WIDE_PAIRS
    n_live = (bound if buffer == "bound" else tier) + past
    layer, h, real, idx, weights = one_expert_layer(n_live)
    cot = jnp.asarray(np.random.default_rng(10).standard_normal(h.shape), jnp.float32)

    def run():
        def loss(layer, h, weights):
            y, counters = latent_moe.routed_experts(layer, h, real, idx, weights, TINY, jnp.float32)
            return jnp.sum(y * cot), (y, counters)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(layer, h, weights)

    (_, (y, counters)), grads = run()
    full_path_only(monkeypatch)
    (_, (y_full, counters_full)), grads_full = run()
    assert int(counters[1]) == int(counters[2]) == n_live
    bounded_calls, buffer_rows, tier_calls = (int(c) for c in counters[5:])
    assert buffer_rows == rows_taken(n_live)
    assert bounded_calls == int(n_live <= bound) and tier_calls == int(n_live <= tier)
    assert [int(c) for c in counters_full[5:]] == [0, WIDE_PAIRS, 0]
    np.testing.assert_array_equal(np.asarray(counters[:5]), np.asarray(counters_full[:5]))
    assert float(jnp.max(jnp.abs(grads_full[2]))) > 0  # the weights' gradient is there to compare
    assert_leaves_close(y, y_full, "output")
    assert_leaves_close(grads, grads_full, "gradient")


def test_j_the_bounded_branch_makes_no_array_of_every_pair(params, wide_batch):
    """Forward and backward: inside the branches taken under the bound no
    array has a row for each of the N x top_k pairs, and inside the quarter
    tier's none has a row for each of the bound's (index vectors have: they
    stay)."""
    ids, mask, positions = wide_batch
    bound = latent_moe.live_bound(WIDE_PAIRS, TINY)

    def loss(p):
        return jnp.sum(trunk_forward(p, ids, mask, positions, TINY, remat=True)[0])

    def a_row_for_each(rows, branch):
        return {v.aval.shape for sub in sub_jaxprs(branch.jaxpr) for eqn in sub.eqns
                for v in eqn.outvars
                if len(v.aval.shape) >= 2 and v.aval.shape[0] == rows}

    conds = branches_of_the_conds(jax.grad(loss), params)
    assert len(conds) >= 2  # the forward's and the backward's
    for full, bounded, tier in conds:
        assert a_row_for_each(WIDE_PAIRS, bounded) == a_row_for_each(WIDE_PAIRS, tier) == set()
        assert a_row_for_each(bound, tier) == set()
        assert a_row_for_each(WIDE_PAIRS, full)  # the same walk does find the full path's
        assert a_row_for_each(bound, bounded)  # and the bound's


def test_j_a_layer_that_holds_every_expert_has_no_branch(params, wide_batch):
    ids, mask, positions = wide_batch
    whole = replace(TINY, experts_held=TINY.n_experts, expert_rank=0)
    p = init_params(jax.random.PRNGKey(3), whole)

    def loss(s):
        return lambda p: jnp.sum(trunk_forward(p, ids, mask, positions, s, remat=True)[0])

    assert branches_of_the_conds(jax.grad(loss(whole)), p) == []
    assert branches_of_the_conds(jax.grad(loss(TINY)), params) != []
    _, counters, _ = system(p, ids, mask, positions, s=whole)
    assert int(counters[5]) == 0 and int(counters[1]) == int(counters[2]) == int(counters[0])
    summary = latent_moe.moe_summary({}, experts_held=16, n_experts=16)
    assert summary["moe_dispatch"] == "sorted, ragged_dot, 16 of 16 held; one path: every expert held"


def test_j_a_live_prefix_cut_one_row_short_reads_as_a_dropped_pair(params, wide_batch, monkeypatch):
    """A fault planted in both bounded buffers: their live rows end one before
    the last pair that landed here. ``moe_dropped`` reads the rows that came
    back (the seeded routing sends one layer through each buffer)."""
    real = latent_moe._live_rows
    bounds = latent_moe.buffer_bounds(WIDE_PAIRS, TINY)
    monkeypatch.setattr(
        latent_moe, "_live_rows",
        lambda n_live, rows: real(n_live - 1 if rows in bounds else n_live, rows))
    _, counters, _ = system(params, *wide_batch)
    layers = TINY.depth - TINY.first_dense
    assert int(counters[5]) == layers and int(counters[7]) == 1  # both bounded paths did run
    summary = latent_moe.moe_summary(
        dict(zip(latent_moe.COUNTER_KEYS, map(int, counters))),
        experts_held=TINY.experts_held, n_experts=TINY.n_experts)
    assert summary["moe"]["dropped"] == layers and summary["moe_dropped"] != "0"


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
    assert result.resolved["moe_dispatch"].startswith("sorted, ragged_dot, 4 of 16 held; live rows")
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
