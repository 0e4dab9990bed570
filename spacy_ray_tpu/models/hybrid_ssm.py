"""A trunk whose layers follow a published STRING: state-space (Mamba-2)
mixers, delta-rule linear attention, grouped-key attention with or without an
output gate, latent attention, a dense FFN and routed experts in one stack,
the third architecture the trunk slot takes
(``spacy_ray_tpu.HybridSSMTrunk.v1``).

The Nemotron-H family (arXiv:2504.03624; ``model_type`` ``nemotron_h``)
publishes its depth as ``hybrid_override_pattern``, one character a layer:
``M`` a Mamba-2 mixer (arXiv:2405.21060), ``*`` attention, ``E`` routed
experts. Every layer is ONE mixer under one pre-norm, ``x <- x +
mixer(rms_norm(x))``, and one ``rms_norm`` follows the last. The stack here is
built from that string and from nothing else: its length is the depth, and no
two layers need be alike. The equations are ISSUE 34's and
``benchmark/reference/nemotron3_nano_a3b.py`` is their plain form (the scan as
the per-position recurrence); this module is the one the program trains.

**Unrolled, each layer rematerialised.** No two neighbours of the published
pattern are of one kind, so there is no run of identical layers to scan; a
scan over a repeated unit (``EMEMEM*``) would need a body for each unit and
the stretches between them. The layers are a Python loop, each under
``jax.checkpoint`` (only its input is kept for the backward pass), as the
leading layers of ``latent_moe`` are.

**``M``: the scan in chunks.** After the input projection, the causal
depthwise convolution and ``silu``, the layer is the recurrence ``S_t =
exp(dt_t A) S_(t-1) + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` per head. It
is computed in chunks of ``chunk`` positions (the SSD form): within a chunk
``y`` is a masked product of ``C B^T`` with the decays between the two
positions; the chunk's own contribution to the state and the decay across it
are carried from chunk to chunk by a ``lax.scan``, and each position adds what
the state carried INTO its chunk gives it. The step ``dt`` (its 64 columns
of the input projection are a leaf of their own, ``dt_W``, multiplied in
float32), the decays, their running sums and the carried state are float32;
the other products run in the compute dtype. A row
is one document, right-padded, and everything is causal: a padded position
never reaches a real one.

**``*``: grouped keys.** ``n_kv_heads`` key/value heads serve ``n_heads``
query heads, ``n_heads / n_kv_heads`` of them each, causal, no positional
term (the family's attention has none); through ``ops/flash_attention
.attention``.

**``E``: the dispatch of ``latent_moe``.** The router's rule (``route``), the
sort, the bounded buffer and the full path past it, the counters and their
summary are that module's, called with the expert's form (``expert_form``):
``relu2``, an expert ``W_down relu(W_up x)^2``, or ``gated_silu``, ``W_down
(silu(W_gate x) * W_up x)``; the one shared expert has the same form and is
added unweighted. The layer is told which experts it holds, as there.

**``K`` and ``G``: a second family's two mixers** (``model_type``
``solar_open2``, whose linear layers are Kimi Delta Attention,
arXiv:2510.26692; ``benchmark/reference/solar_open2_250b.py`` is their plain
form). That family publishes a layer as a mixer AND an expert block, each
under its own pre-norm; here each is a character, so one published period
``G K K K`` is the string ``GEKEKEKE``. ``K``: projections to ``q``, ``k``,
``v``, each through a causal depthwise convolution and ``silu``, ``q`` and
``k`` normed to length one a head; a decay for every key channel, ``g =
-exp(A_log) softplus(h W_fa W_fb + dt_bias)``, and a step ``beta = 2
sigmoid(h W_beta)`` (the 2 lets a transition's eigenvalue reach -1;
``kda_neg_eigval``); the recurrence ``S_t = (I - beta_t k_t k_t^T)
Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T``, ``o_t = S_t^T q_t`` in chunks
(``models/delta_attention.py``: a triangular solve inside a chunk, the state
carried between chunks); ``RMSNorm`` a head, a sigmoid gate through a second
low-rank pair, the output projection. The low-rank pairs and ``W_beta`` are
float32 leaves multiplied at precision ``highest`` (they feed an exponential
of a running sum, as ``dt_W`` does in ``M``). ``G``: the ``*`` layer with
``sigmoid(h W_gate)`` multiplied into the heads' outputs before the output
projection.

**``L`` and ``D``: a third family's two** (``model_type`` ``kimi_linear``,
Kimi Linear, arXiv:2510.26692; ``benchmark/reference/kimi_linear_48b_a3b.py``
is their plain form). ``L``: the multi-head latent attention of
``latent_moe`` (``latent_attention``: the query projected whole, a latent of
``kv_rank`` channels and one shared key of ``qk_rope`` channels, the latent
normed and expanded to each head's key and value) with NO positional term
(``mla_use_nope``: the shared key's channels stay in the product unrotated).
``D``: a dense gated FFN, ``W_d (silu(h W_g) * h W_u)``, where a family's
leading layers have no experts (``first_k_dense_replace``). That family
publishes its depth as two lists of layer indices and a count of leading
dense layers, not as a string: ``pattern_from_layer_lists`` maps them to one,
a mixer and an FFN a published layer (``KDKEKELE...``), and a configuration
calls it. ``L`` holds every head: its share of the heads is not written.

**The chip's share of the HEADS.** ``*``, ``G`` and ``K`` are told how many
heads they hold (``heads_held`` query heads with their key/value heads,
``kda_heads_held`` linear heads) and of which rank (``head_rank``): the leaves
have the held heads' columns (of ``W_q``, ``W_k``, ``W_v``, the gates, the
convolution, ``dt_bias``, ``A_log``, ``W_beta``) and rows (of ``W_o``), the
layer projects to its own heads and its output projection gives their part of
the sum over heads. What the absent heads would add is left out; nothing
stands in for the other chips or for their exchange (the sum over ranks is a
test's: ``held_heads`` cuts an uncut layer's leaves as a loader would).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .. import names
from ..registry import registry
from ..types import Padded, TokenBatch
from . import latent_moe
from .core import Context, Model, normal_init
from .delta_attention import chunked_delta_rule, l2norm
from .latent_moe import latent_attention, rms_norm, word_rows
from .shadow import _resolve_compute_dtype, register_trunk_leaves

# a layer's kind is the name of its device scope: a trace splits by it
MAMBA, ATTENTION, MOE = names.SCOPE_MAMBA, names.SCOPE_ATTENTION, names.SCOPE_MOE
KDA, GATED_ATTENTION = names.SCOPE_KDA, names.SCOPE_GATED_ATTENTION
LATENT, DENSE = names.SCOPE_ATTN, names.SCOPE_DENSE_FFN
# the characters: nemotron_h's own three, K / G for the two mixers of solar_open2,
# L / D for kimi_linear's latent attention and leading dense FFN
KINDS = {"M": MAMBA, "*": ATTENTION, "E": MOE, "K": KDA, "G": GATED_ATTENTION,
         "L": LATENT, "D": DENSE}

MATMUL_LEAVES = (
    "in_W", "out_W",  # M: the two projections
    "q_W", "k_W", "v_W", "ao_W",  # *, G, K: the held heads' projections (q_W, ao_W: L too)
    "gate_W",  # G: the output gate
    "kva_W", "kvb_W",  # L: the latent and the shared key, the latent to keys and values
    "g_W", "u_W", "d_W",  # D: the dense gated FFN
    "eg_W", "eu_W", "ed_W",  # E: the experts held, stacked [held, ., .] (eg_W: gated_silu only)
    "sg_W", "su_W", "sd_W",  # E: the shared expert
)
register_trunk_leaves(
    shadow=MATMUL_LEAVES,
    # gains, the router and its bias, the step's projection, the M and K
    # layers' elementwise leaves (convolution taps, decay, skip, step bias) and
    # K's low-rank gates and beta (products at precision highest) feed float32
    # operations
    f32=("norm_g", "gate_norm_g", "conv_W", "conv_b", "A_log", "D", "dt_bias", "dt_W",
         "router_W", "router_b",
         "fa_W", "fb_W", "ga_W", "gb_W", "beta_W", "o_norm_g", "rmskv_g"),
    int8_unsupported=MATMUL_LEAVES,
)

COUNTER_KEYS = (names.SSM_CHUNKS, names.SSM_LIVE_CHUNKS)
KDA_COUNTER_KEYS = (names.KDA_CHUNKS, names.KDA_LIVE_CHUNKS)
MLA_COUNTER_KEYS = (names.MLA_SCORES, names.MLA_LIVE_SCORES)


@dataclass(frozen=True)
class Shape:
    """The trunk's static sizes (python values: they specialise the program).
    ``route`` and ``routed_experts`` of ``latent_moe`` read ``top_k``,
    ``route_scale``, ``n_experts``, ``experts_held`` and ``held_from``."""

    pattern: str
    width: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    conv_kernel: int
    chunk: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    expert_ffn: int
    shared_ffn: int
    n_experts: int
    experts_held: int
    expert_rank: int
    top_k: int
    route_scale: float
    vocab_rows: int
    rms_eps: float = 1e-5
    dt_min: float = 1e-3
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    expert_form: str = latent_moe.RELU2
    route_bias: bool = True  # a seeded selection bias (nemotron_h); False: nought
    # K: the delta-rule heads (key and value width ``kda_head_dim``), the
    # width of its two low-rank gates, and whether beta reaches 2
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_gate_rank: int = 0
    kda_neg_eigval: bool = True
    # the chip's share of the heads (nought: all of them): query heads of * and
    # G with their key/value heads, linear heads of K, and of which rank
    heads_held: int = 0
    kda_heads_held: int = 0
    head_rank: int = 0
    # L: a head's key of qk_nope + qk_rope channels (the second part from the
    # one key all heads share), its value of v_head, the latent of kv_rank;
    # D: the dense FFN's width. L reads n_heads and holds every head
    qk_nope: int = 0
    qk_rope: int = 0
    v_head: int = 0
    kv_rank: int = 0
    dense_ffn: int = 0

    @property
    def depth(self) -> int:
        return len(self.pattern)

    @property
    def q_heads_here(self) -> int:
        return self.heads_held or self.n_heads

    @property
    def kv_heads_here(self) -> int:
        return self.n_kv_heads * self.q_heads_here // self.n_heads

    @property
    def kda_heads_here(self) -> int:
        return self.kda_heads_held or self.kda_heads

    @property
    def held_from(self) -> int:
        return self.expert_rank * self.experts_held

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state


def parse_pattern(pattern: str) -> Tuple[str, ...]:
    """The kinds of the layers, in order. Any other character is an error
    naming it: a pattern is read as published or not at all."""
    unknown = sorted(set(pattern) - set(KINDS))
    if not pattern or unknown:
        raise ValueError(
            f"a layer pattern is a string of {sorted(KINDS)} (attention, dense FFN, routed experts, "
            f"gated attention, delta-rule mixer, latent attention, Mamba-2 mixer), one character a "
            f"layer; got {pattern!r}"
            + (f" with {unknown}" if unknown else ""))
    return tuple(KINDS[c] for c in pattern)


@registry.misc("spacy_ray_tpu.pattern_from_layer_lists.v1")
def pattern_from_layer_lists(
    kda_layers, full_attn_layers, first_k_dense_replace: int, num_hidden_layers: int,
    keep_layers: int = 0,
) -> str:
    """The pattern of a depth published as lists (``kimi_linear``'s
    ``linear_attn_config``): layer ``i`` (1-indexed) is a delta-rule mixer
    ``K`` where ``kda_layers`` names it, latent attention ``L`` where
    ``full_attn_layers`` does, then its FFN, dense ``D`` for the first
    ``first_k_dense_replace`` layers and routed ``E`` after them: two
    characters a published layer. ``keep_layers`` (nought: all) keeps the
    first that many layers. Every layer must be named by exactly one list."""
    kda, full = set(kda_layers), set(full_attn_layers)
    every = set(range(1, num_hidden_layers + 1))
    if kda & full or kda | full != every or not 0 <= keep_layers <= num_hidden_layers:
        raise ValueError(
            f"kda_layers and full_attn_layers must name each of layers 1..{num_hidden_layers} once "
            f"(both: {sorted(kda & full)}, neither: {sorted(every - kda - full)}, past the end: "
            f"{sorted((kda | full) - every)}), and keep_layers {keep_layers} be at most that")
    return "".join(("K" if i in kda else "L") + ("D" if i <= first_k_dense_replace else "E")
                   for i in range(1, (keep_layers or num_hidden_layers) + 1))


def _f32_dot(a, w) -> jnp.ndarray:
    """A product that feeds a float32 operation: float32 at precision highest."""
    return jnp.dot(a, w.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)


# ---- M: the Mamba-2 mixer ------------------------------------------------------


def causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise and causal: position t of a channel sees t-K+1 .. t of that
    channel in its own row. x [B, T, C] float32, w [K, C] (tap K-1 is the
    position itself), b [C]."""
    K, T = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = b
    for k in range(K):
        out = out + padded[:, k:k + T] * w[k]
    return out


def chunked_scan(x, B_, C_, dt, A, chunk: int, cd) -> jnp.ndarray:
    """``y_t = C_t . S_t`` of the recurrence ``S_t = exp(dt_t A) S_(t-1) + dt_t
    x_t B_t^T`` (``S`` nought before a row's first position), in chunks.
    x [B, T, H, P], B_ / C_ [B, T, G, N] (a group serves H / G heads),
    dt [B, T, H] float32 (after softplus), A [H] float32 (negative).
    Returns [B, T, H, P] float32."""
    Bn, T, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    Q, R = chunk, H // G
    pad = -T % Q
    if pad:  # positions past the row's end: behind every real one
        x, B_, C_, dt = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                         for a in (x, B_, C_, dt))
    nc = (T + pad) // Q
    f32 = jnp.float32
    x = x.reshape(Bn, nc, Q, H, P)
    B_ = B_.reshape(Bn, nc, Q, G, N).astype(cd)
    C_ = C_.reshape(Bn, nc, Q, G, N).astype(cd)
    dt = dt.reshape(Bn, nc, Q, H).transpose(0, 1, 3, 2)  # [B, nc, H, Q]: time last
    # the log of the decay from the chunk's start to each position, inclusive
    run = jnp.cumsum(dt * A[:, None], axis=-1)
    total = run[..., -1]  # across the whole chunk [B, nc, H]
    # within the chunk: (C_t . B_s) exp(run_t - run_s) dt_s for s <= t. The
    # exponent is masked, not the result: past the diagonal it is positive
    # and may overflow, and nought times infinity is no number
    scores = jnp.einsum("bctgn,bcsgn->bcgts", C_, B_, preferred_element_type=f32)
    between = run[..., :, None] - run[..., None, :]  # [B, nc, H, t, s]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), between, -jnp.inf))
    decay = (decay * dt[..., None, :]).reshape(Bn, nc, G, R, Q, Q)
    mixed = (scores[:, :, :, None] * decay).reshape(Bn, nc, H, Q, Q).astype(cd)
    y = jnp.einsum("bchts,bcshp->bcthp", mixed, x.astype(cd), preferred_element_type=f32)
    # what each chunk alone leaves in the state at its end, a group's heads
    # side by side: [B, nc, G, R x P, N]
    to_end = (jnp.exp(total[..., None] - run) * dt).transpose(0, 1, 3, 2)  # [B, nc, Q, H]
    weighted = (x * to_end[..., None]).astype(cd).reshape(Bn, nc, Q, G, R * P)
    left = jnp.einsum("bcsgm,bcsgn->bcgmn", weighted, B_, preferred_element_type=f32)

    def carry_over(state, chunk_):
        decay_, left_ = chunk_
        return state * decay_[..., None, None] + left_, state  # emits what came IN

    _, came_in = jax.lax.scan(
        carry_over, jnp.zeros((Bn, H, P, N), f32),
        (jnp.moveaxis(jnp.exp(total), 1, 0),
         jnp.moveaxis(left.reshape(Bn, nc, H, P, N), 1, 0)))
    came_in = jnp.moveaxis(came_in, 0, 1).reshape(Bn, nc, G, R * P, N).astype(cd)
    carried = jnp.einsum("bctgn,bcgmn->bctgm", C_, came_in, preferred_element_type=f32)
    y = y + carried.reshape(Bn, nc, Q, H, P) * jnp.exp(run).transpose(0, 1, 3, 2)[..., None]
    return y.reshape(Bn, nc * Q, H, P)[:, :T]


def gated_group_norm(y, z, g, groups: int, eps: float) -> jnp.ndarray:
    """``rms_norm`` over each of ``groups`` equal runs of channels of ``y *
    silu(z)`` (the gate first), one learned gain over all channels. float32."""
    gated = y * jax.nn.silu(z)
    by_group = gated.reshape(gated.shape[:-1] + (groups, -1))
    scale = jax.lax.rsqrt(jnp.mean(by_group * by_group, axis=-1, keepdims=True) + eps)
    return (by_group * scale).reshape(gated.shape) * g


def mamba_mixer(p, h: jnp.ndarray, s: Shape, cd) -> jnp.ndarray:
    """h [B, T, D] float32 (normed) -> [B, T, D] float32."""
    B, T, _ = h.shape
    H, P, G, N = s.ssm_heads, s.ssm_head_dim, s.ssm_groups, s.ssm_state
    inner = s.d_inner
    with jax.named_scope(names.SCOPE_MAMBA):
        proj = (h.astype(cd) @ p["in_W"].astype(cd)).astype(jnp.float32)
        z, xBC = jnp.split(proj, [inner], axis=-1)
        xBC = jax.nn.silu(causal_conv(xBC, p["conv_W"], p["conv_b"]))
        x, B_, C_ = jnp.split(xBC, [inner, inner + G * N], axis=-1)
        x = x.reshape(B, T, H, P)
        # the step's own columns of the input projection, as the router's
        # product: float32 at precision highest. It feeds the exponential of a
        # running sum: inside the bfloat16 product the gradient's worst leaf
        # read 0.86, 0.45 and 0.29 against the reference on three trained
        # trunks on the chip, as its own float32 leaf 0.20, 0.10 and 0.25 on
        # the same three seeds (PERF.md section 6, PR 34)
        dt = jax.nn.softplus(_f32_dot(h, p["dt_W"]) + p["dt_bias"])
    with jax.named_scope(names.SCOPE_MAMBA_SCAN):
        y = chunked_scan(x, B_.reshape(B, T, G, N), C_.reshape(B, T, G, N), dt,
                         -jnp.exp(p["A_log"]), s.chunk, cd)
    with jax.named_scope(names.SCOPE_MAMBA):
        y = (y + x * p["D"][:, None]).reshape(B, T, inner)
        y = gated_group_norm(y, z, p["gate_norm_g"], G, s.rms_eps)
        return (y.astype(cd) @ p["out_W"].astype(cd)).astype(jnp.float32)


def chunk_counters(mask: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """int32 [2]: the (row, chunk) blocks one M layer's scan runs over this
    batch, and those of them that hold at least one real word."""
    B, T = mask.shape
    pad = -T % chunk
    blocks = jnp.pad(mask, ((0, 0), (0, pad))).reshape(B, (T + pad) // chunk, chunk)
    return jnp.stack([jnp.int32(blocks.shape[0] * blocks.shape[1]),
                      jnp.sum(jnp.any(blocks, axis=-1), dtype=jnp.int32)])


# ---- *: grouped-key attention ----------------------------------------------------


def grouped_attention(p, h: jnp.ndarray, mask, s: Shape, cd, kind: str = ATTENTION) -> jnp.ndarray:
    """The heads held here (``q_heads_here`` on ``kv_heads_here``) and their
    part of the output projection's sum. ``kind`` ``GATED_ATTENTION``: the
    heads' outputs times ``sigmoid(h W_gate)``, element by element, before it."""
    from ..ops.flash_attention import attention

    B, T, _ = h.shape
    H, Hkv = s.q_heads_here, s.kv_heads_here
    with jax.named_scope(kind):
        h16 = h.astype(cd)
        q = (h16 @ p["q_W"].astype(cd)).reshape(B, T, H, s.head_dim)
        k = (h16 @ p["k_W"].astype(cd)).reshape(B, T, Hkv, s.head_dim)
        v = (h16 @ p["v_W"].astype(cd)).reshape(B, T, Hkv, s.head_dim)
        out = attention(q, k, v, mask, causal=True).reshape(B, T, H * s.head_dim)
        if kind == GATED_ATTENTION:
            gate = jax.nn.sigmoid((h16 @ p["gate_W"].astype(cd)).astype(jnp.float32))
            out = (out.astype(jnp.float32) * gate).astype(cd)
        return (out @ p["ao_W"].astype(cd)).astype(jnp.float32)


# ---- K: Kimi delta attention -----------------------------------------------------------


def kda_mixer(p, h: jnp.ndarray, s: Shape, cd) -> jnp.ndarray:
    """h [B, T, D] float32 (normed) -> [B, T, D] float32: the held heads'
    part of the output projection's sum."""
    B, T, _ = h.shape
    H, K = s.kda_heads_here, s.kda_head_dim
    with jax.named_scope(KDA):
        h16 = h.astype(cd)
        qkv = jnp.concatenate([(h16 @ p[name].astype(cd)).astype(jnp.float32)
                               for name in ("q_W", "k_W", "v_W")], axis=-1)
        taps = p["conv_W"].reshape(s.conv_kernel, 3 * H * K)  # stored [tap, q | k | v, channel]
        qkv = jax.nn.silu(causal_conv(qkv, taps, 0.0)).reshape(B, T, 3, H, K)
        q = l2norm(qkv[:, :, 0]) * K ** -0.5
        k, v = l2norm(qkv[:, :, 1]), qkv[:, :, 2]
        # the decay's and the step's pre-activations in float32 at precision
        # highest, as the M layer's dt: they feed the exponential of a running sum
        decay = _f32_dot(_f32_dot(h, p["fa_W"]), p["fb_W"]) + p["dt_bias"]
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(decay.reshape(B, T, H, K))
        beta = (2.0 if s.kda_neg_eigval else 1.0) * jax.nn.sigmoid(_f32_dot(h, p["beta_W"]))
        gate = _f32_dot(_f32_dot(h, p["ga_W"]), p["gb_W"]).reshape(B, T, H, K)
    with jax.named_scope(names.SCOPE_KDA_SCAN):
        o = chunked_delta_rule(q, k, v, g, beta, s.chunk, cd)
    with jax.named_scope(KDA):
        o = rms_norm(o, p["o_norm_g"], s.rms_eps) * jax.nn.sigmoid(gate)
        return (o.reshape(B, T, H * K).astype(cd) @ p["ao_W"].astype(cd)).astype(jnp.float32)


# ---- L and D: latent attention without positions, a dense FFN ----------------------------


def latent_mixer(p, h: jnp.ndarray, mask, s: Shape, cd) -> jnp.ndarray:
    """h [B, T, D] float32 (normed) -> [B, T, D] float32: ``latent_moe``'s
    latent attention, every head, no positional term."""
    with jax.named_scope(LATENT):
        return latent_attention(p, h, mask, None, s, cd, rotary=False)


def score_counters(mask: jnp.ndarray) -> jnp.ndarray:
    """int32 [2]: the (query, key) score entries ONE HEAD of one L layer's
    attention computes over this batch (``xla_attention``: every pair of
    positions of every row) and those of them whose query and key are both
    real words, the key at or before the query. Heads and layers multiply in
    ``summary``, in Python's integers: B x T x T alone stays far inside int32
    where the product with them would not (64 x 1024 x 1024 x 32 heads)."""
    B, T = mask.shape
    seen = jnp.cumsum(mask.astype(jnp.int32), axis=-1)  # real keys at or before each position
    return jnp.stack([jnp.int32(B * T * T), jnp.sum(jnp.where(mask, seen, 0), dtype=jnp.int32)])


# ---- E: routed experts --------------------------------------------------------------


def relu2_ffn(h16: jnp.ndarray, w_up, w_down, cd) -> jnp.ndarray:
    """``relu(h W_up)^2 W_down``: the products in the compute dtype, the
    activation in float32."""
    up = (h16 @ w_up.astype(cd)).astype(jnp.float32)
    return (jnp.square(jax.nn.relu(up)).astype(cd) @ w_down.astype(cd)).astype(jnp.float32)


def expert_mixer(p, h: jnp.ndarray, mask, s: Shape, cd):
    """h [B, T, D] float32 (normed) -> ([B, T, D] float32, the routed layer's
    counters, the experts chosen [B*T, top_k])."""
    B, T, D = h.shape
    h2 = h.reshape(B * T, D)
    with jax.named_scope(names.SCOPE_MOE_ROUTER):
        idx, weights = latent_moe.route(p, h2, s)
    y, counters = latent_moe.routed_experts(
        p, h2, mask.reshape(B * T), idx, weights, s, cd, form=s.expert_form)
    with jax.named_scope(names.SCOPE_MOE_SHARED):
        if s.expert_form == latent_moe.GATED_SILU:
            y = y + latent_moe._gated(h2.astype(cd), p["sg_W"], p["su_W"], p["sd_W"], cd)
        else:
            y = y + relu2_ffn(h2.astype(cd), p["su_W"], p["sd_W"], cd)
    return y.reshape(B, T, D), counters, idx


# ---- the stack ---------------------------------------------------------------------------


def apply_layer(p, x, mask, *, kind: str, s: Shape, cd):
    """One layer of ``kind``: ``x + mixer(rms_norm(x))``. Returns (x, what the
    layer counted or None, the experts it chose or None)."""
    with jax.named_scope(kind):
        h = rms_norm(x, p["norm_g"], s.rms_eps)
    counted = chosen = None
    if kind == MAMBA:
        y = mamba_mixer(p, h, s, cd)
    elif kind == KDA:
        y = kda_mixer(p, h, s, cd)
    elif kind in (ATTENTION, GATED_ATTENTION):
        y = grouped_attention(p, h, mask, s, cd, kind)
    elif kind == LATENT:
        y = latent_mixer(p, h, mask, s, cd)
    elif kind == DENSE:
        with jax.named_scope(DENSE):
            y = latent_moe._gated(h.astype(cd), p["g_W"], p["u_W"], p["d_W"], cd)
    else:
        y, counted, chosen = expert_mixer(p, h, mask, s, cd)
    with jax.named_scope(kind):
        return x + y, counted, chosen


def trunk_forward(params, ids, mask, s: Shape, *, compute_dtype=jnp.float32, remat: bool = False):
    """ids / mask [B, T] -> (X [B, T, D] float32 with padded positions
    zeroed, the expert layers' counters int32 [latent_moe.N_COUNTERS] summed,
    the scan's counters int32 [2] summed over the M layers, chosen experts
    [expert layers, B, T, top_k] int32)."""
    B, T = ids.shape
    kinds = parse_pattern(s.pattern)
    with jax.named_scope(names.SCOPE_EMBED):
        x = params["E"][ids].astype(jnp.float32) * mask[..., None].astype(jnp.float32)
    moe_counters = jnp.zeros((latent_moe.N_COUNTERS,), jnp.int32)
    chosen = []
    with jax.named_scope(names.SCOPE_TRUNK):
        for i, kind in enumerate(kinds):
            fn = partial(apply_layer, kind=kind, s=s, cd=compute_dtype)
            x, counted, idx = (jax.checkpoint(fn) if remat else fn)(params[f"layer_{i}"], x, mask)
            if counted is not None:
                moe_counters = moe_counters + counted
                chosen.append(idx)
        x = rms_norm(x, params["rms_f_g"], s.rms_eps)
        x = x * mask[..., None].astype(x.dtype)
    ssm_counters = chunk_counters(mask, s.chunk) * kinds.count(MAMBA)
    choices = (jnp.stack(chosen) if chosen else jnp.zeros((0, B * T, s.top_k), jnp.int32))
    return x, moe_counters, ssm_counters, choices.reshape(-1, B, T, s.top_k)


def init_params(rng, s: Shape, std: float = 0.02):
    """Matrices normal(0, ``std``), gains 1; the M layer's ``A_log``,
    ``dt_bias`` and ``D`` by the Mamba-2 convention: ``A`` uniform in [1, 16],
    the step ``dt`` log-uniform in [``dt_min``, ``dt_max``] and floored at
    ``dt_floor``, ``dt_bias`` its inverse softplus, ``D`` = 1; the
    convolution uniform in +-1/sqrt(K), its bias nought. The K layer's
    ``A_log`` (a head), ``dt_bias`` (a head and channel) and taps by the same
    convention, which its family keeps. Leaves of ``*``, ``G`` and ``K`` have
    the HELD heads' columns and rows."""
    kinds = parse_pattern(s.pattern)
    rngs = jax.random.split(rng, s.depth + 1)
    params: Dict[str, Any] = {
        "E": normal_init(rngs[0], (s.vocab_rows, s.width), std),
        "rms_f_g": jnp.ones((s.width,)),
    }

    def step_bias(key, shape):
        dt = jnp.exp(jax.random.uniform(key, shape) * (math.log(s.dt_max) - math.log(s.dt_min))
                     + math.log(s.dt_min))
        dt = jnp.maximum(dt, s.dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    for i, kind in enumerate(kinds):
        r = jax.random.split(rngs[i + 1], 6)
        more = partial(jax.random.fold_in, rngs[i + 1])  # keys past the sixth, by number
        layer: Dict[str, Any] = {"norm_g": jnp.ones((s.width,))}
        if kind == MAMBA:
            K, C, H = s.conv_kernel, s.conv_channels, s.ssm_heads
            layer.update(
                # the published in_proj (z | xBC | dt) in two leaves, cut before dt
                in_W=normal_init(r[0], (s.width, s.d_inner + C), std),
                dt_W=normal_init(r[5], (s.width, H), std),
                out_W=normal_init(r[1], (s.d_inner, s.width), std),
                conv_W=jax.random.uniform(r[3], (K, C), minval=-1.0, maxval=1.0) / math.sqrt(K),
                conv_b=jnp.zeros((C,)),
                A_log=jnp.log(jax.random.uniform(r[4], (H,), minval=1.0, maxval=16.0)),
                D=jnp.ones((H,)),
                dt_bias=step_bias(r[2], (H,)),
                gate_norm_g=jnp.ones((s.d_inner,)),
            )
        elif kind == KDA:
            inner, rank = s.kda_heads_here * s.kda_head_dim, s.kda_gate_rank
            layer.update(
                q_W=normal_init(r[0], (s.width, inner), std),
                k_W=normal_init(r[1], (s.width, inner), std),
                v_W=normal_init(r[2], (s.width, inner), std),
                ao_W=normal_init(r[3], (inner, s.width), std),
                # [tap, q | k | v, channel], tap K-1 the position itself; no bias
                conv_W=(jax.random.uniform(r[4], (s.conv_kernel, 3, inner), minval=-1.0, maxval=1.0)
                        / math.sqrt(s.conv_kernel)),
                fa_W=normal_init(r[5], (s.width, rank), std),
                fb_W=normal_init(more(6), (rank, inner), std),
                ga_W=normal_init(more(7), (s.width, rank), std),
                gb_W=normal_init(more(8), (rank, inner), std),
                beta_W=normal_init(more(9), (s.width, s.kda_heads_here), std),
                A_log=jnp.log(jax.random.uniform(
                    more(10), (s.kda_heads_here,), minval=1.0, maxval=16.0)),
                dt_bias=step_bias(more(11), (inner,)),
                o_norm_g=jnp.ones((s.kda_head_dim,)),
            )
        elif kind in (ATTENTION, GATED_ATTENTION):
            layer.update(
                q_W=normal_init(r[0], (s.width, s.q_heads_here * s.head_dim), std),
                k_W=normal_init(r[1], (s.width, s.kv_heads_here * s.head_dim), std),
                v_W=normal_init(r[2], (s.width, s.kv_heads_here * s.head_dim), std),
                ao_W=normal_init(r[3], (s.q_heads_here * s.head_dim, s.width), std),
            )
            if kind == GATED_ATTENTION:
                layer["gate_W"] = normal_init(r[4], (s.width, s.q_heads_here * s.head_dim), std)
        elif kind == LATENT:
            H = s.n_heads
            layer.update(
                q_W=normal_init(r[0], (s.width, H * (s.qk_nope + s.qk_rope)), std),
                kva_W=normal_init(r[1], (s.width, s.kv_rank + s.qk_rope), std),
                kvb_W=normal_init(r[2], (s.kv_rank, H * (s.qk_nope + s.v_head)), std),
                ao_W=normal_init(r[3], (H * s.v_head, s.width), std),
                rmskv_g=jnp.ones((s.kv_rank,)),
            )
        elif kind == DENSE:
            layer.update(
                g_W=normal_init(r[0], (s.width, s.dense_ffn), std),
                u_W=normal_init(r[1], (s.width, s.dense_ffn), std),
                d_W=normal_init(r[2], (s.dense_ffn, s.width), std),
            )
        else:
            layer.update(
                router_W=normal_init(r[0], (s.width, s.n_experts), std),
                # selection only; stays at its seeded value (no gradient)
                router_b=(normal_init(r[1], (s.n_experts,), std) if s.route_bias
                          else jnp.zeros((s.n_experts,))),
                eu_W=normal_init(r[2], (s.experts_held, s.width, s.expert_ffn), std),
                ed_W=normal_init(r[3], (s.experts_held, s.expert_ffn, s.width), std),
                su_W=normal_init(r[4], (s.width, s.shared_ffn), std),
                sd_W=normal_init(r[5], (s.shared_ffn, s.width), std),
            )
            if s.expert_form == latent_moe.GATED_SILU:
                layer.update(
                    eg_W=normal_init(more(6), (s.experts_held, s.width, s.expert_ffn), std),
                    sg_W=normal_init(more(7), (s.width, s.shared_ffn), std),
                )
        params[f"layer_{i}"] = layer
    return params


# which of a layer's leaves run over its heads, and along which axis; the
# rest (norm gains, K's low-rank first factors) is whole on every chip
_HEAD_AXES = {
    KDA: {"q_W": 1, "k_W": 1, "v_W": 1, "ao_W": 0, "conv_W": 2, "fb_W": 1, "gb_W": 1,
          "beta_W": 1, "A_log": 0, "dt_bias": 0},
    ATTENTION: {"q_W": 1, "k_W": 1, "v_W": 1, "ao_W": 0},
    GATED_ATTENTION: {"q_W": 1, "k_W": 1, "v_W": 1, "ao_W": 0, "gate_W": 1},
}


def held_heads(layer: Dict[str, Any], kind: str, rank: int, ranks: int) -> Dict[str, Any]:
    """The leaves rank ``rank`` of ``ranks`` holds of an UNCUT ``*``, ``G`` or
    ``K`` layer: its heads' columns and rows, as a loader of a whole checkpoint
    cuts them; everything else whole. Heads are contiguous: rank r holds heads
    ``[r * H / ranks, (r + 1) * H / ranks)`` of each kind."""
    out = dict(layer)
    for name, axis in _HEAD_AXES[kind].items():
        n = layer[name].shape[axis] // ranks
        out[name] = jax.lax.slice_in_dim(layer[name], rank * n, (rank + 1) * n, axis=axis)
    return out


@registry.architectures("spacy_ray_tpu.HybridSSMTrunk.v1")
def HybridSSMTrunk(
    pattern: str = "MEMEM*EME",
    width: int = 2688,
    ssm_heads: int = 64,
    ssm_head_dim: int = 64,
    ssm_groups: int = 8,
    ssm_state: int = 128,
    conv_kernel: int = 4,
    chunk: int = 128,
    n_heads: int = 32,
    n_kv_heads: int = 2,
    head_dim: int = 128,
    expert_ffn: int = 1856,
    shared_ffn: int = 3712,
    n_experts: int = 128,
    experts_held: int = 8,
    expert_rank: int = 0,
    top_k: int = 6,
    route_scale: float = 2.5,
    vocab_rows: int = 16384,
    remat: bool = True,
    compute_dtype: str = "auto",
    expert_form: str = latent_moe.RELU2,
    route_bias: bool = True,
    kda_heads: int = 0,
    kda_head_dim: int = 0,
    kda_gate_rank: int = 0,
    kda_neg_eigval: bool = True,
    heads_held: int = 0,
    kda_heads_held: int = 0,
    head_rank: int = 0,
    qk_nope: int = 0,
    qk_rope: int = 0,
    v_head: int = 0,
    kv_rank: int = 0,
    dense_ffn: int = 0,
) -> Model:
    """tok2vec-compatible trunk (module docstring). ``pattern`` is the layer
    string as published, or a cut of it; ``experts_held`` of the ``n_experts``
    live here, those of rank ``expert_rank``; ``heads_held`` of the
    ``n_heads`` query heads (whole groups of them, with their key/value heads)
    and ``kda_heads_held`` of the ``kda_heads`` linear heads, those of rank
    ``head_rank`` (nought: all heads, the one rank); an ``L`` layer has
    ``n_heads`` heads of ``qk_nope + qk_rope`` / ``v_head`` over a latent of
    ``kv_rank``, all held; a ``D`` layer is ``dense_ffn`` wide. ``remat``
    keeps only each layer's input for the backward pass."""
    kinds = parse_pattern(pattern)
    if n_experts % experts_held or not 0 <= expert_rank < n_experts // experts_held:
        raise ValueError(
            f"experts_held {experts_held} must divide n_experts {n_experts}, and "
            f"expert_rank {expert_rank} be one of its {n_experts // max(experts_held, 1)} ranks")
    if n_heads % n_kv_heads or ssm_heads % ssm_groups or top_k > n_experts:
        raise ValueError("need n_kv_heads to divide n_heads, ssm_groups to divide ssm_heads, "
                         "top_k <= n_experts")
    if expert_form not in latent_moe.EXPERT_LEAVES:
        raise ValueError(f"expert_form is one of {sorted(latent_moe.EXPERT_LEAVES)}, got {expert_form!r}")
    if KDA in kinds and not (kda_heads and kda_head_dim and kda_gate_rank):
        raise ValueError("a pattern with K needs kda_heads, kda_head_dim and kda_gate_rank")
    if LATENT in kinds and not (qk_nope and v_head and kv_rank) or DENSE in kinds and not dense_ffn:
        raise ValueError("a pattern with L needs qk_nope, v_head and kv_rank, one with D dense_ffn")
    if LATENT in kinds and heads_held:
        raise ValueError(f"heads_held {heads_held}: an L layer holds every one of its heads")
    # how many ranks share each kind of head that the pattern has (all heads held:
    # one rank); one head_rank names a chip among all of them, so they have to agree
    head_ranks = set()
    if ATTENTION in kinds or GATED_ATTENTION in kinds:
        head_ranks.add(n_heads // heads_held if heads_held else 1)
    if KDA in kinds:
        head_ranks.add(kda_heads // kda_heads_held if kda_heads_held else 1)
    uneven = (heads_held and (n_heads % heads_held or heads_held % (n_heads // n_kv_heads))
              or kda_heads_held and kda_heads % kda_heads_held)
    if uneven or len(head_ranks) > 1 or not 0 <= head_rank < max(head_ranks, default=1):
        raise ValueError(
            f"heads_held {heads_held} must divide n_heads {n_heads} in whole groups of "
            f"{n_heads // n_kv_heads} query heads a key head, kda_heads_held {kda_heads_held} "
            f"divide kda_heads {kda_heads}, both into the same number of ranks, and head_rank "
            f"{head_rank} be one of them (ranks: {sorted(head_ranks)})")
    s = Shape(
        pattern=pattern, width=width, ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim,
        ssm_groups=ssm_groups, ssm_state=ssm_state, conv_kernel=conv_kernel, chunk=chunk,
        n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim, expert_ffn=expert_ffn,
        shared_ffn=shared_ffn, n_experts=n_experts, experts_held=experts_held,
        expert_rank=expert_rank, top_k=top_k, route_scale=float(route_scale),
        vocab_rows=vocab_rows, expert_form=expert_form, route_bias=route_bias,
        kda_heads=kda_heads, kda_head_dim=kda_head_dim, kda_gate_rank=kda_gate_rank,
        kda_neg_eigval=kda_neg_eigval, heads_held=heads_held, kda_heads_held=kda_heads_held,
        head_rank=head_rank, qk_nope=qk_nope, qk_rope=qk_rope, v_head=v_head, kv_rank=kv_rank,
        dense_ffn=dense_ffn)

    def rows(batch: TokenBatch) -> jnp.ndarray:
        return word_rows(batch.attr_keys, vocab_rows)

    def forward(params, batch: TokenBatch):
        return trunk_forward(
            params, rows(batch), batch.mask, s,
            compute_dtype=_resolve_compute_dtype(compute_dtype), remat=remat)

    def apply_fn(params, batch: TokenBatch, ctx: Context) -> Padded:
        X, moe_counters, ssm_counters, _ = forward(params, batch)
        if MOE in kinds:
            ctx.add_metrics(dict(zip(latent_moe.COUNTER_KEYS, moe_counters)))
        if MAMBA in kinds:
            ctx.add_metrics(dict(zip(COUNTER_KEYS, ssm_counters)))
        if KDA in kinds:
            ctx.add_metrics(dict(zip(
                KDA_COUNTER_KEYS, chunk_counters(batch.mask, s.chunk) * kinds.count(KDA))))
        if LATENT in kinds:
            ctx.add_metrics(dict(zip(MLA_COUNTER_KEYS, score_counters(batch.mask))))
        return Padded(X=X, mask=batch.mask)

    return Model(
        "hybrid_ssm_trunk",
        lambda rng: init_params(rng, s),
        apply_fn,
        dims={"nO": width, "depth": s.depth, "n_heads": n_heads},
        meta={
            "compute_dtype_name": compute_dtype,
            "shape": s,
            # for a reference that starts from the program's hashing and is
            # told the program's routing (an evaluation forward)
            "word_rows": rows,
            "routing_choices": lambda params, batch: forward(params, batch)[3],
            # the same with the forward's output, for one that has to know what a
            # GRADIENT program chose (top-k is a hard choice, and each compiled
            # program rounds its way to it: jax.grad(..., has_aux=True) of this)
            "forward_and_choices": lambda params, batch: forward(params, batch)[::3],
            names.SUMMARISE_COUNTERS: partial(summary, s=s),
        },
    )


def _chunk_block(totals: Dict[str, float], keys, chunk: int, layers: int) -> Dict[str, int]:
    """A chunked recurrence's block of the report, from its two counters."""
    chunks, live = (int(totals.get(key, 0)) for key in keys)
    return {"chunks": chunks, "live_chunks": live, "chunk": chunk, "layers": layers}


def summary(totals: Dict[str, float], s: Shape) -> Dict[str, Any]:
    """What a run's summed counters come to, for ``TrainResult.resolved``:
    the pattern the stack was built from, how each chunked recurrence ran and
    its block (``ssm`` for M, ``kda`` for K), the L layers' score entries
    (``mla``: computed, and live of them), the share of the heads where the
    chip holds one and, where the pattern has expert layers, ``latent_moe``'s
    ``moe`` block and flat keys."""
    kinds = parse_pattern(s.pattern)
    out: Dict[str, Any] = {"layer_pattern": s.pattern}
    if KDA in kinds:
        out["kda_scan"] = f"chunked {s.chunk}, xla"
        out["kda"] = _chunk_block(totals, KDA_COUNTER_KEYS, s.chunk, kinds.count(KDA))
    if LATENT in kinds:
        layers = kinds.count(LATENT)
        scores, live = (int(totals.get(key, 0)) * s.n_heads * layers for key in MLA_COUNTER_KEYS)
        out["mla"] = {"scores": scores, "live_scores": live, "layers": layers}
    if s.heads_held or s.kda_heads_held:
        parts = []
        if ATTENTION in kinds or GATED_ATTENTION in kinds:
            parts += [f"{s.q_heads_here} of {s.n_heads} query", f"{s.kv_heads_here} of {s.n_kv_heads} key"]
        if KDA in kinds:
            parts.append(f"{s.kda_heads_here} of {s.kda_heads} linear")
        out["head_share"] = ", ".join(parts) + f" heads, rank {s.head_rank}"
    if MAMBA in kinds:
        out["ssm_scan"] = f"chunked {s.chunk}, xla"
        out["ssm"] = _chunk_block(totals, COUNTER_KEYS, s.chunk, kinds.count(MAMBA))
    if MOE in kinds:
        out.update(latent_moe.moe_summary(totals, s.experts_held, s.n_experts))
    return out
