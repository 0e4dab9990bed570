"""Latent-attention trunk with sigmoid-routed experts: the second
architecture the trunk slot takes (``spacy_ray_tpu.LatentMoETrunk.v1``).

A pre-norm decoder stack as the DeepSeek-V3 family publishes it (RMSNorm,
rotary positions, multi-head latent attention, SwiGLU, ``first_dense``
leading dense layers, then layers of routed experts chosen top-k by sigmoid
scores beside shared experts), used as the pipeline's shared trunk: one
vector a word, the heads listen to it as they do to the encoder of
``models/transformer.py``. The equations are ISSUE 27's and
``benchmark/reference/kanana2_a3b.py`` is their plain form; this module is
the one the program trains.

**The chip's share.** The layer is told which experts it holds: rank
``expert_rank`` of ``n_experts / experts_held`` holds experts
``[rank * held, (rank + 1) * held)``. It routes every word over ALL
``n_experts`` (the router keeps its published width and top-k), computes the
terms of the sum whose expert it holds, and adds the shared experts. What
the absent experts would add is left out; nothing stands in for the other
chips or for their exchange.

**Dispatch without a capacity.** The (word, choice) pairs are sorted by held
expert (pairs on absent experts and on padding sort last), the rows gathered
in that order, and ONE grouped product (``jax.lax.ragged_dot``) runs over the
experts held: group sizes vary from step to step, shapes do not. No pair is
ever dropped.

**The live prefix and its bounds.** Only the pairs that land on a held expert
produce anything, and after the sort they are the first ``n_live`` rows. A
rank that holds ``experts_held`` of ``n_experts`` is sent ``P * held /
n_experts`` of the ``P = N x top_k`` pairs by an even router, so the rows are
moved through a buffer of ``C`` = twice that share (``live_bound``: from the
shapes alone, rounded up to ``BOUND_STEP`` rows), not of ``P``: the ``C`` rows
are gathered straight from the words (``order[:C] // top_k``), the grouped
products run on ``[C, .]``, and each word's sum is taken from the ``C`` output
rows by position, choice by choice (``_rows_in`` / ``_rows_out``: gathers in
both directions, no array of ``P`` rows forward or backward, where a
scatter-add of rows would serialise on the chip). Below ``C`` lies a tier of
``C_q`` rows, a quarter of ``C`` rounded up to ``TIER_STEP`` (``tier_bound``,
from the shapes alone; none where that is not smaller than ``C``): a frozen
router sends a rank far less than its even share, and the rows past
``n_live`` are noughts that the products multiply all the same. The bounds are
NOT capacities and drop nothing: ``n_live`` is known on the device after the
sort, and the layer takes the smallest buffer it fits (``lax.switch``, no host
round trip): ``C_q``, else ``C``, else the full path, whose buffer has the
static size ``P`` that no routing exceeds; there sorting is a permutation, so
dispatch and combine are gathers by it and by its inverse (``_permute``). The
full path stays because the router is free: with its selection bias frozen it
has sent this rank anything from 1% to 31% of a run's pairs by seed, and
single layers over half of a batch's (0-15% of a run's layer calls pass the
bound: PERF.md section 6); nothing holds it to twice its share. Where
``C >= P`` (a layer that holds every expert, or a batch under ``BOUND_STEP``
pairs) there is one path, the full one, and no branch.

Float32 where it decides something: the residual stream, every RMSNorm, the
router (``h W_r`` at precision ``highest``, sigmoid, top-k, weights), the
softmax. The matrix products run in the compute dtype (bfloat16 on a TPU).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .. import names
from ..ops.hashing import hash_embed_ids, hash_string_u64
from ..registry import registry
from ..types import Padded, TokenBatch
from .core import Context, Model, normal_init
from .shadow import _resolve_compute_dtype, register_trunk_leaves
from .tok2vec import attr_index

MATMUL_LEAVES = (
    "q_W", "kva_W", "kvb_W", "ao_W",  # latent attention
    "g_W", "u_W", "d_W",  # dense gated FFN
    "eg_W", "eu_W", "ed_W",  # the experts held, stacked [held, ., .]
    "sg_W", "su_W", "sd_W",  # shared experts, side by side
)
register_trunk_leaves(
    shadow=MATMUL_LEAVES,
    # norm gains, the router and its selection bias feed float32 ops
    f32=("rms1_g", "rms2_g", "rmskv_g", "router_W", "router_b"),
    # none of this trunk's products goes through the int8 kernel: an "int8"
    # label over it would be false, so that overlay refuses the tree
    int8_unsupported=MATMUL_LEAVES,
)

# one row a word: the NORM key reduced into the table by the program's hashing
EMBED_SEED = hash_string_u64("latent-moe-embed-NORM") & 0x7FFFFFFF
# what an expert layer counts, in this order (device counters: names.py)
COUNTER_KEYS = (
    names.MOE_ASSIGNMENTS, names.MOE_ASSIGNMENTS_HELD, names.MOE_COMPUTED,
    names.MOE_MAX_LOAD, names.MOE_LAYER_CALLS, names.MOE_BOUNDED_CALLS,
    names.MOE_BUFFER_ROWS, names.MOE_TIER_CALLS,
)
N_COUNTERS = len(COUNTER_KEYS)


@dataclass(frozen=True)
class Shape:
    """The trunk's static sizes (python ints: they specialise the program)."""

    width: int
    n_heads: int
    qk_nope: int
    qk_rope: int
    v_head: int
    kv_rank: int
    dense_ffn: int
    expert_ffn: int
    n_experts: int
    experts_held: int
    expert_rank: int
    top_k: int
    n_shared: int
    route_scale: float
    first_dense: int
    depth: int
    vocab_rows: int
    rope_theta: float
    rms_eps: float = 1e-6

    @property
    def held_from(self) -> int:
        return self.expert_rank * self.experts_held


def rms_norm(x: jnp.ndarray, g: jnp.ndarray, eps: float) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate adjacent pairs ``(2i, 2i+1)`` of the last axis by
    ``pos * theta^(-2i/d)``. x [B, T, H, d], positions [B, T]; float32."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, :, None, None] * freq  # [B, T, 1, d/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x = x.astype(jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def _gated(h16: jnp.ndarray, wg, wu, wd, cd) -> jnp.ndarray:
    """``(silu(h Wg) * (h Wu)) Wd``: products in the compute dtype, the
    activation in float32."""
    gate = (h16 @ wg.astype(cd)).astype(jnp.float32)
    up = (h16 @ wu.astype(cd)).astype(jnp.float32)
    return ((jax.nn.silu(gate) * up).astype(cd) @ wd.astype(cd)).astype(jnp.float32)


def latent_attention(p, h: jnp.ndarray, mask, positions, s, cd, rotary: bool = True) -> jnp.ndarray:
    """h [B, T, D] float32 (normed) -> [B, T, D] float32. Causal, as
    published; ONE rotary key shared by all heads. ``rotary`` False: no
    positional term (``mla_use_nope``), the ``qk_rope`` channels of the query
    and of the shared key stay in the product unrotated, the scale is that of
    the whole head all the same; ``positions`` is then not read. ``s`` gives
    ``n_heads``, ``qk_nope``, ``qk_rope``, ``v_head``, ``kv_rank``,
    ``rms_eps`` (and ``rope_theta`` where ``rotary``)."""
    from ..ops.flash_attention import attention

    B, T, _ = h.shape
    H = s.n_heads
    h16 = h.astype(cd)
    q = (h16 @ p["q_W"].astype(cd)).reshape(B, T, H, s.qk_nope + s.qk_rope)
    kva = h16 @ p["kva_W"].astype(cd)
    c = rms_norm(kva[..., : s.kv_rank], p["rmskv_g"], s.rms_eps)
    kv = (c.astype(cd) @ p["kvb_W"].astype(cd)).reshape(B, T, H, s.qk_nope + s.v_head)
    if rotary:
        with jax.named_scope(names.SCOPE_ATTN_ROPE):
            q_pe = rope(q[..., s.qk_nope:], positions, s.rope_theta).astype(cd)
            k_pe = rope(kva[..., None, s.kv_rank:], positions, s.rope_theta).astype(cd)
    else:
        q_pe, k_pe = q[..., s.qk_nope:], kva[..., None, s.kv_rank:]
    q = jnp.concatenate([q[..., : s.qk_nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., : s.qk_nope], jnp.broadcast_to(k_pe, (B, T, H, s.qk_rope))], axis=-1)
    out = attention(q, k, kv[..., s.qk_nope:], mask, causal=True)
    return (out.reshape(B, T, H * s.v_head) @ p["ao_W"].astype(cd)).astype(jnp.float32)


@jax.custom_vjp
def _permute(x: jnp.ndarray, perm: jnp.ndarray, inverse: jnp.ndarray) -> jnp.ndarray:
    """``x[perm]`` for a permutation whose inverse is known: the transpose
    of a gather by a permutation is the gather by its inverse, so neither
    direction needs a scatter."""
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], inverse


def _permute_bwd(inverse, g):
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def route(p, h: jnp.ndarray, s) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """h [N, D] float32 -> (chosen experts [N, top_k] int32, their weights
    [N, top_k] float32). Scores are sigmoids; the bias moves the SELECTION
    only (no gradient reaches it); the weights are the chosen scores,
    normalised and scaled."""
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), p["router_W"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["router_b"]), s.top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = s.route_scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), weights


# the bound moves in steps of this many rows (whole row tiles, whatever tile the
# compiler gives the grouped product); a batch whose doubled share is under one
# step has no smaller buffer to gain and takes the full path alone
BOUND_STEP = 512
# the tier under it moves in steps of two 128-row MXU tiles, the row tile the
# TPU compiler gives ``ragged_dot`` up to 768 rows (512 from 1,024)
TIER_STEP = 256


def live_bound(n_pairs: int, s) -> int:
    """Rows of the bounded path's buffer for ``n_pairs`` (word, choice)
    pairs: twice this rank's even share, rounded up to ``BOUND_STEP``. At
    ``n_pairs`` or over it there is no bounded path. Under it lies the
    quarter tier, ``tier_bound`` of it: the two are ``buffer_bounds``."""
    share = -(-2 * n_pairs * s.experts_held // s.n_experts)
    return -(-share // BOUND_STEP) * BOUND_STEP


def tier_bound(bound: int) -> int:
    """Rows of the tier under a bounded buffer of ``bound`` rows: a quarter
    of it, rounded up to ``TIER_STEP``. At ``bound`` or over it there is no
    tier."""
    return -(-bound // (4 * TIER_STEP)) * TIER_STEP


def buffer_bounds(n_pairs: int, s) -> Tuple[int, ...]:
    """The bounded buffers' rows for ``n_pairs`` pairs, largest first:
    ``(C, C_q)``, ``(C,)`` where the quarter is no smaller, ``()`` where
    ``C`` is no smaller than ``n_pairs`` (one path)."""
    bound = live_bound(n_pairs, s)
    if bound >= n_pairs:
        return ()
    tier = tier_bound(bound)
    return (bound, tier) if tier < bound else (bound,)


def _live_rows(n_live: jnp.ndarray, rows: int) -> jnp.ndarray:
    """[rows, 1] bool: the sorted buffer's rows that belong to a held expert.
    Rows past the last group belong to none: the grouped product owes them
    nothing, so they are zeroed going in, between and coming out."""
    return (jnp.arange(rows, dtype=jnp.int32) < n_live)[:, None]


# an expert's FORM: which stacked matrices it has, in the order the paths take
# them, and what stands between the first products and the last. Both trunks
# that route (this one: gated; models/hybrid_ssm.py: relu2) run the one
# dispatch below and differ in this argument alone
GATED_SILU = "gated_silu"  # (silu(h Wg) * (h Wu)) Wd
RELU2 = "relu2"  # relu(h Wu)^2 Wd
EXPERT_LEAVES = {GATED_SILU: ("eg_W", "eu_W", "ed_W"), RELU2: ("eu_W", "ed_W")}


def _expert_products(form, rows, live, group_sizes, experts):
    """rows [R, D] sorted by held expert -> (the experts' outputs [R, D], the
    live rows whose expert rightly answered with noughts [R] bool, or None
    where the form has no such answer). ``experts``: the held experts' stacked
    matrices, ``EXPERT_LEAVES[form]``. A relu's answer IS a row of noughts
    where no unit of the expert fires for the word (9 of 850,972 pairs of a
    run on the chip, PR 34): the first product came back and the activation
    is nought everywhere, which is not a pair that was dropped."""
    cd = rows.dtype
    grouped = partial(jax.lax.ragged_dot, group_sizes=group_sizes)
    if form == GATED_SILU:
        eg, eu, ed = experts
        gate = grouped(rows, eg).astype(jnp.float32)
        up = grouped(rows, eu).astype(jnp.float32)
        inner = jax.nn.silu(gate) * up
        noughts = None
    else:
        eu, ed = experts
        up = grouped(rows, eu).astype(jnp.float32)
        inner = jnp.square(jax.nn.relu(up))
        noughts = (live[:, 0] & jnp.any(up != 0, axis=-1)) & ~jnp.any(inner != 0, axis=-1)
    inner = jnp.where(live, inner, 0).astype(cd)
    return jnp.where(live, grouped(inner, ed), 0), noughts


def _full_path(form, h16, w, experts, order, inverse, group_sizes):
    """Every pair moved: a buffer of ``P = N x top_k`` rows. Returns (the
    words' sums [N, D] float32, which pairs' output came back [N, K] bool)."""
    N, D = h16.shape
    K = w.shape[1]
    with jax.named_scope(names.SCOPE_MOE_DISPATCH):
        live = _live_rows(jnp.sum(group_sizes), N * K)
        rows = _permute(jnp.repeat(h16, K, axis=0), order, inverse)
        rows = jnp.where(live, rows, 0)
    with jax.named_scope(names.SCOPE_MOE_EXPERTS):
        out_rows, noughts = _expert_products(form, rows, live, group_sizes, experts)
    with jax.named_scope(names.SCOPE_MOE_COMBINE):
        pairs = _permute(out_rows, inverse, order).reshape(N, K, D)
        y = jnp.sum(pairs.astype(jnp.float32) * w[..., None], axis=1)
        came_back = jnp.any(pairs != 0, axis=-1)
        return y, came_back if noughts is None else came_back | noughts[inverse].reshape(N, K)


def _sum_choices(rows, pos, w=None) -> jnp.ndarray:
    """``sum_k w[n, k] * rows[pos[n, k]]`` in float32, choice by choice; a
    position past the last row reads a row of noughts. rows [C, D], pos
    [N, K] -> [N, D]: K gathers of N rows, never one of N x K."""
    total = None
    for k in range(pos.shape[1]):
        term = rows.at[pos[:, k]].get(mode="fill", fill_value=0).astype(jnp.float32)
        if w is not None:
            term = term * w[:, k, None]
        total = term if total is None else total + term
    return total


@jax.custom_vjp
def _rows_in(h16, word, pos):
    """``h16[word]``: the bounded buffer's rows, gathered from their words.
    Backward, a word's gradient is the sum of its choices' rows, taken by
    position (``pos``: where each pair sorted to, the row of noughts for a
    pair outside the buffer): a gather, where the transpose of a gather is a
    scatter-add."""
    return h16[word]


def _rows_in_fwd(h16, word, pos):
    return h16[word], pos


def _rows_in_bwd(pos, g):
    return _sum_choices(g, pos).astype(g.dtype), None, None


_rows_in.defvjp(_rows_in_fwd, _rows_in_bwd)


@jax.custom_vjp
def _rows_out(out_rows, w, slot, pos):
    """Each word's weighted sum of its choices' output rows, float32 [N, D].
    ``slot`` [C]: the flat (word, choice) pair of each row; ``pos`` [N, K] as
    in ``_rows_in``."""
    return _sum_choices(out_rows, pos, w)


def _rows_out_fwd(out_rows, w, slot, pos):
    return _sum_choices(out_rows, pos, w), (out_rows, w, slot, pos)


def _rows_out_bwd(res, g):
    out_rows, w, slot, pos = res
    g_rows = g[slot // w.shape[1]]  # [C, D] float32: each row's word's cotangent
    d_rows = (g_rows * w.reshape(-1)[slot][:, None]).astype(out_rows.dtype)
    d_w = jnp.sum(g_rows * out_rows.astype(jnp.float32), axis=-1)  # one dot product a row
    return d_rows, d_w.at[pos].get(mode="fill", fill_value=0), None, None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


def _bounded_path(bound, form, h16, w, experts, order, inverse, group_sizes):
    """``_full_path`` for a routing whose live pairs fit ``bound`` rows."""
    N, K = w.shape
    slot = order[:bound]  # the flat (word, choice) pair of each row of the buffer
    pos = jnp.minimum(inverse, bound).reshape(N, K)
    with jax.named_scope(names.SCOPE_MOE_DISPATCH):
        live = _live_rows(jnp.sum(group_sizes), bound)
        rows = jnp.where(live, _rows_in(h16, slot // K, pos), 0)
    with jax.named_scope(names.SCOPE_MOE_EXPERTS):
        out_rows, noughts = _expert_products(form, rows, live, group_sizes, experts)
    with jax.named_scope(names.SCOPE_MOE_COMBINE):
        y = _rows_out(out_rows, w, slot, pos)
        came_back = jnp.any(out_rows != 0, axis=-1)
        if noughts is not None:
            came_back = came_back | noughts
        return y, came_back.at[pos].get(mode="fill", fill_value=False)


def _paths(bounds, form):
    """The branches by how many of ``bounds`` (largest first) the live pairs
    fit: the full path, then each bounded buffer, the smallest last."""
    return [partial(_full_path, form)] + [partial(_bounded_path, b, form) for b in bounds]


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _by_live_size(bounds, form, index, h16, w, experts, order, inverse, group_sizes):
    """The smallest of ``bounds`` that the live pairs fit, the full path where
    they fit none (``index``: how many of ``bounds`` they fit, known on the
    device). Its own ``custom_vjp`` so that the backward is a branch too and
    each branch recomputes its forward inside: differentiating the ``switch``
    itself would hand every residual of EVERY branch across it, the untaken
    ones' as noughts, and a bounded branch would write the full one's P-row
    arrays."""
    return jax.lax.switch(index, _paths(bounds, form),
                          h16, w, experts, order, inverse, group_sizes)


def _by_live_size_fwd(bounds, form, index, *operands):
    return _by_live_size(bounds, form, index, *operands), (index, operands)


def _by_live_size_bwd(bounds, form, res, cotangent):
    index, (*floats, order, inverse, group_sizes) = res

    def pull(path, g, *floats):
        _, vjp = jax.vjp(lambda *f: path(*f, order, inverse, group_sizes)[0], *floats)
        return vjp(g)

    grads = jax.lax.switch(index, [partial(pull, path) for path in _paths(bounds, form)],
                           cotangent[0], *floats)
    return (None, *grads, None, None, None)


_by_live_size.defvjp(_by_live_size_fwd, _by_live_size_bwd)


def routed_experts(p, h: jnp.ndarray, token_mask, idx, weights, s, cd, form: str = GATED_SILU):
    """The held experts' part of ``sum_k w_k Expert_k(h)``. h [N, D] float32,
    token_mask [N] bool, idx / weights [N, top_k]; ``s`` gives ``top_k``,
    ``n_experts``, ``experts_held`` and ``held_from``; ``form`` says which
    matrices of ``p`` an expert is made of. Returns ([N, D] float32, counters
    int32 [N_COUNTERS])."""
    K, held = s.top_k, s.experts_held
    P = h.shape[0] * K
    with jax.named_scope(names.SCOPE_MOE_DISPATCH):
        local = idx - s.held_from
        valid = (local >= 0) & (local < held) & token_mask[:, None]  # [N, K]
        key = jnp.where(valid, local, held).reshape(P)  # absent or padding: last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.zeros((P,), jnp.int32).at[order].set(
            jnp.arange(P, dtype=jnp.int32), unique_indices=True)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :], axis=0, dtype=jnp.int32)
    operands = (h.astype(cd), jnp.where(valid, weights, 0.0),
                tuple(p[name].astype(cd) for name in EXPERT_LEAVES[form]),
                order, inverse, group_sizes)
    bounds = buffer_bounds(P, s)
    if not bounds:  # no smaller buffer to be had: one path, no branch
        index = jnp.int32(0)
        y, came_back = _full_path(form, *operands)
    else:
        n_live = jnp.sum(group_sizes)
        index = sum((n_live <= b).astype(jnp.int32) for b in bounds)
        y, came_back = _by_live_size(bounds, form, index, *operands)
    counters = jnp.stack([
        jnp.sum(token_mask, dtype=jnp.int32) * K,
        jnp.sum(valid, dtype=jnp.int32),
        # counted from what the product gave back, not from the mask it was
        # given: a pair whose expert's output is all nought was not computed
        jnp.sum(valid & came_back, dtype=jnp.int32),
        jnp.max(group_sizes),
        jnp.int32(1),
        (index > 0).astype(jnp.int32),  # either bounded buffer
        jnp.asarray((P, *bounds), jnp.int32)[index],  # the rows of the buffer taken
        (index == 2).astype(jnp.int32),  # the tier
    ])
    return y, counters


def apply_layer(p, x, mask, positions, *, s: Shape, cd, routed: bool):
    """One block. x [B, T, D] float32. Returns (x, counters int32
    [N_COUNTERS], chosen experts [B*T, top_k] int32): the last two are
    noughts for a dense layer, so that both kinds have one signature."""
    B, T, D = x.shape
    with jax.named_scope(names.SCOPE_ATTN):
        x = x + latent_attention(
            p, rms_norm(x, p["rms1_g"], s.rms_eps), mask, positions, s, cd)
    h = rms_norm(x, p["rms2_g"], s.rms_eps)
    if not routed:
        with jax.named_scope(names.SCOPE_DENSE_FFN):
            x = x + _gated(h.astype(cd), p["g_W"], p["u_W"], p["d_W"], cd)
        return x, jnp.zeros((N_COUNTERS,), jnp.int32), jnp.zeros((B * T, s.top_k), jnp.int32)
    h2 = h.reshape(B * T, D)
    with jax.named_scope(names.SCOPE_MOE_ROUTER):
        idx, weights = route(p, h2, s)
    y, counters = routed_experts(p, h2, mask.reshape(B * T), idx, weights, s, cd)
    with jax.named_scope(names.SCOPE_MOE_SHARED):
        y = y + _gated(h2.astype(cd), p["sg_W"], p["su_W"], p["sd_W"], cd)
    return x + y.reshape(B, T, D), counters, idx


def trunk_forward(
    params, ids, mask, positions, s: Shape, *, compute_dtype=jnp.float32,
    remat: bool = False, scan_layers: bool = True,
):
    """ids / mask / positions [B, T] -> (X [B, T, D] float32 with padded
    positions zeroed, counters int32 [N_COUNTERS] summed over the expert
    layers, chosen experts [expert layers, B, T, top_k] int32). ``remat``
    keeps only each layer's input for the backward pass and recomputes the
    rest (the least memory: the step of the one cell that runs this trunk
    leaves no room for more)."""
    B, T = ids.shape
    with jax.named_scope(names.SCOPE_EMBED):
        x = params["E"][ids].astype(jnp.float32) * mask[..., None].astype(jnp.float32)

    def layer_fn(routed: bool):
        fn = partial(apply_layer, s=s, cd=compute_dtype, routed=routed)
        return jax.checkpoint(fn) if remat else fn

    with jax.named_scope(names.SCOPE_TRUNK):
        dense, routed = layer_fn(False), layer_fn(True)
        for i in range(s.first_dense):
            x, _, _ = dense(params[f"layer_{i}"], x, mask, positions)
        n_routed = s.depth - s.first_dense
        counters = jnp.zeros((N_COUNTERS,), jnp.int32)
        if scan_layers and n_routed > 1:
            # ONE layer body over stacked parameters (storage stays layer_i)
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[params[f"layer_{i}"] for i in range(s.first_dense, s.depth)])

            def body(carry, lp):
                x, counters = carry
                x, c, idx = routed(lp, x, mask, positions)
                return (x, counters + c), idx

            (x, counters), choices = jax.lax.scan(body, (x, counters), stacked)
        else:
            chosen = []
            for i in range(s.first_dense, s.depth):
                x, c, idx = routed(params[f"layer_{i}"], x, mask, positions)
                counters = counters + c
                chosen.append(idx)
            choices = (jnp.stack(chosen) if chosen
                       else jnp.zeros((0, B * T, s.top_k), jnp.int32))
        x = rms_norm(x, params["rms_f_g"], s.rms_eps)
        x = x * mask[..., None].astype(x.dtype)
    return x, counters, choices.reshape(-1, B, T, s.top_k)


def init_params(rng, s: Shape, std: float = 0.02):
    H = s.n_heads
    rngs = jax.random.split(rng, s.depth + 1)
    params: Dict[str, Any] = {
        "E": normal_init(rngs[0], (s.vocab_rows, s.width), std),
        "rms_f_g": jnp.ones((s.width,)),
    }
    for i in range(s.depth):
        r = jax.random.split(rngs[i + 1], 12)
        layer = {
            "rms1_g": jnp.ones((s.width,)),
            "rms2_g": jnp.ones((s.width,)),
            "rmskv_g": jnp.ones((s.kv_rank,)),
            "q_W": normal_init(r[0], (s.width, H * (s.qk_nope + s.qk_rope)), std),
            "kva_W": normal_init(r[1], (s.width, s.kv_rank + s.qk_rope), std),
            "kvb_W": normal_init(r[2], (s.kv_rank, H * (s.qk_nope + s.v_head)), std),
            "ao_W": normal_init(r[3], (H * s.v_head, s.width), std),
        }
        if i < s.first_dense:
            layer.update(
                g_W=normal_init(r[4], (s.width, s.dense_ffn), std),
                u_W=normal_init(r[5], (s.width, s.dense_ffn), std),
                d_W=normal_init(r[6], (s.dense_ffn, s.width), std),
            )
        else:
            shared = s.n_shared * s.expert_ffn
            layer.update(
                router_W=normal_init(r[4], (s.width, s.n_experts), std),
                # selection only; stays at its seeded value (no gradient)
                router_b=normal_init(r[5], (s.n_experts,), std),
                eg_W=normal_init(r[6], (s.experts_held, s.width, s.expert_ffn), std),
                eu_W=normal_init(r[7], (s.experts_held, s.width, s.expert_ffn), std),
                ed_W=normal_init(r[8], (s.experts_held, s.expert_ffn, s.width), std),
                sg_W=normal_init(r[9], (s.width, shared), std),
                su_W=normal_init(r[10], (s.width, shared), std),
                sd_W=normal_init(r[11], (shared, s.width), std),
            )
        params[f"layer_{i}"] = layer
    return params


def word_rows(attr_keys: jnp.ndarray, vocab_rows: int) -> jnp.ndarray:
    """A word's row of the table: its NORM key, hashed, modulo the rows."""
    return hash_embed_ids(attr_keys[..., attr_index("NORM"), :], EMBED_SEED, vocab_rows)[..., 0]


def word_positions(mask: jnp.ndarray) -> jnp.ndarray:
    """A word's index in its document: a row of the batch is one document."""
    B, T = mask.shape
    return jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))


@registry.architectures("spacy_ray_tpu.LatentMoETrunk.v1")
def LatentMoETrunk(
    width: int = 2048,
    n_heads: int = 32,
    qk_nope: int = 128,
    qk_rope: int = 64,
    v_head: int = 128,
    kv_rank: int = 512,
    dense_ffn: int = 6144,
    expert_ffn: int = 768,
    n_experts: int = 128,
    experts_held: int = 16,
    expert_rank: int = 0,
    top_k: int = 6,
    n_shared: int = 2,
    route_scale: float = 2.448,
    first_dense: int = 1,
    depth: int = 5,
    vocab_rows: int = 16032,
    rope_theta: float = 1e6,
    remat: bool = True,
    compute_dtype: str = "auto",
) -> Model:
    """tok2vec-compatible trunk (module docstring). ``experts_held`` of the
    ``n_experts`` live here, those of rank ``expert_rank``; the router keeps
    its width and ``top_k``. ``remat`` keeps only each layer's input for
    the backward pass."""
    if n_experts % experts_held or not 0 <= expert_rank < n_experts // experts_held:
        raise ValueError(
            f"experts_held {experts_held} must divide n_experts {n_experts}, and "
            f"expert_rank {expert_rank} be one of its {n_experts // max(experts_held, 1)} ranks")
    if not 0 <= first_dense <= depth or top_k > n_experts or qk_rope % 2:
        raise ValueError("need 0 <= first_dense <= depth, top_k <= n_experts, an even qk_rope")
    s = Shape(
        width=width, n_heads=n_heads, qk_nope=qk_nope, qk_rope=qk_rope, v_head=v_head,
        kv_rank=kv_rank, dense_ffn=dense_ffn, expert_ffn=expert_ffn, n_experts=n_experts,
        experts_held=experts_held, expert_rank=expert_rank, top_k=top_k, n_shared=n_shared,
        route_scale=float(route_scale), first_dense=first_dense, depth=depth,
        vocab_rows=vocab_rows, rope_theta=float(rope_theta))
    n_routed = depth - first_dense

    def forward(params, batch: TokenBatch):
        return trunk_forward(
            params, word_rows(batch.attr_keys, vocab_rows), batch.mask,
            word_positions(batch.mask), s,
            compute_dtype=_resolve_compute_dtype(compute_dtype),
            remat=remat)

    def apply_fn(params, batch: TokenBatch, ctx: Context) -> Padded:
        X, counters, _ = forward(params, batch)
        if n_routed:
            ctx.add_metrics(dict(zip(COUNTER_KEYS, counters)))
        return Padded(X=X, mask=batch.mask)

    def routing_choices(params, batch: TokenBatch) -> jnp.ndarray:
        """The experts the trunk chose, [expert layers, B, T, top_k]: the same
        arrays the counters are made from (an evaluation forward)."""
        return forward(params, batch)[2]

    return Model(
        "latent_moe_trunk",
        lambda rng: init_params(rng, s),
        apply_fn,
        dims={"nO": width, "depth": depth, "n_heads": n_heads},
        meta={
            "compute_dtype_name": compute_dtype,
            "shape": s,
            "routing_choices": routing_choices,
            **({names.SUMMARISE_COUNTERS: partial(
                moe_summary, experts_held=experts_held, n_experts=n_experts)} if n_routed else {}),
        },
    )


def moe_summary(totals: Dict[str, float], experts_held: int, n_experts: int) -> Dict[str, Any]:
    """What a run's summed counters come to, for ``TrainResult.resolved``:
    the ``moe`` block (``dropped`` is the pairs that landed on a held expert
    and whose output did not come back; the loads are rows a step and layer;
    ``bounded_calls`` the calls on either bounded buffer, ``tier_calls`` those
    on the smaller; ``buffer_rows`` the rows of the buffers the calls took,
    summed) and two flat keys a record's expectations can be held to."""
    calls = max(int(totals.get(names.MOE_LAYER_CALLS, 0)), 1)
    held = int(totals.get(names.MOE_ASSIGNMENTS_HELD, 0))
    moe = {
        "assignments": int(totals.get(names.MOE_ASSIGNMENTS, 0)),
        "assignments_held": held,
        "dropped": held - int(totals.get(names.MOE_COMPUTED, 0)),
        "max_expert_load": totals.get(names.MOE_MAX_LOAD, 0) / calls,
        "mean_expert_load": held / experts_held / calls,
        "layer_calls": calls,
        "bounded_calls": int(totals.get(names.MOE_BOUNDED_CALLS, 0)),
        "tier_calls": int(totals.get(names.MOE_TIER_CALLS, 0)),
        "buffer_rows": int(totals.get(names.MOE_BUFFER_ROWS, 0)),
    }
    bound = ("one path: every expert held" if experts_held == n_experts else
             f"live rows bounded at 2 x {experts_held}/{n_experts} of the pairs "
             f"(steps of {BOUND_STEP}) and at a quarter of that (steps of {TIER_STEP}), "
             f"the smallest they fit, the full path past both")
    return {"moe": moe, "moe_dropped": str(moe["dropped"]),
            "moe_dispatch": f"sorted, ragged_dot, {experts_held} of {n_experts} held; {bound}"}
