"""Plain reference of the ``solar_open2_250b`` trunk: float32 ``jax.numpy``
under ``default_matmul_precision("highest")``, a Python loop over the layers,
the linear-attention layer as the per-word RECURRENCE under ``lax.scan`` (no
chunks, no triangular solve, no running sums of the decay), attention as a
masked softmax with the key/value head repeated for its query heads, the
experts held computed densely (every held expert, every word) under a 0/1
selection mask. No sort, no grouped product, no kernel. It imports nothing
from ``spacy_ray_tpu/models``.

Compilation and memory only, no mathematics: the loop is traced into ONE
compiled program (``_forward``); the held experts are one product over their
stacked axis and not a Python loop of eight (ISSUE 36 words it as a loop; the
sum is the same, and unrolled such a forward was a program of 50 MiB: PERF.md
section 6, PR 27); the recurrence runs row by row (``lax.map``) with each row
under ``jax.checkpoint``, because its backward keeps the state of every
position (8 x 128 x 128 floats: 134 MB a row of 256, 1.1 GB for 8 rows).

Written from the published architecture (upstage/Solar-Open2-250B
``config.json``, ``model_type`` ``solar_open2``; its linear layers are Kimi
Delta Attention, arXiv:2510.26692 section 3, with eigenvalues down to -1,
arXiv:2411.12537) as ISSUE 36 spells it. ``x`` is the float32 residual stream.
A published layer is ``x += Mixer(RMSNorm(x)); x += Experts(RMSNorm(x))``; the
program stores the mixer and the expert block as two entries, ``layer_<2i>``
and ``layer_<2i+1>``, each with its own ``norm_g``, and a kind is read from
its leaves (``beta_W``: K, ``gate_W``: G, ``router_W``: experts).

* ``RMSNorm(x) = x / sqrt(mean(x^2) + 1e-5) * g``; after the last block
  ``RMSNorm_f``, then the padded positions are zeroed. Input: one table ``E``,
  one row a word. No positional term anywhere (``use_rope`` false).
* ``G`` (``gqa_layers``): ``q = h W_q`` -> the held query heads of 128; ``k``,
  ``v`` -> the held key/value heads of 128; query head j reads key/value head
  ``j // 8``; ``a = softmax(q k^T / sqrt(128) + causal + key padding) v``;
  ``y = (a * sigmoid(h W_gate)) W_o``, the gate element by element
  (``assumed.gqa_gate``). No bias, no norm on q or k.
* ``K`` (Kimi delta attention): ``q = l2norm(silu(conv(h W_q))) / sqrt(128)``,
  ``k = l2norm(silu(conv(h W_k)))``, ``v = silu(conv(h W_v))``: the
  convolution depthwise and causal, position t seeing t-3..t of its own
  channel and row, no bias; ``l2norm(x) = x / sqrt(sum(x^2) + 1e-6)`` a head.
  ``beta = 2 sigmoid(h W_beta)`` a head (``kda_allow_neg_eigval``); ``g =
  -exp(A_log) softplus(h W_fa W_fb + dt_bias)`` a head AND channel
  (``kda_use_full_proj`` false: the low-rank pair). Per head, ``S`` 128 x 128
  and nought before the row's first word: ``S_t = (I - beta_t k_t k_t^T)
  Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``. Then ``y =
  (RMSNorm_head(o) * sigmoid(h W_ga W_gb)) W_o``, the norm over each head's 128
  channels with one gain of 128.
* Experts: ``s = sigmoid(h W_r)`` over 320; the top 8 of ``s + b`` (``b`` is
  nought in this configuration); ``w = 1 * s_k / (sum of the chosen s +
  1e-20)``; ``y = sum_k w_k Expert_k(h) + Shared(h)``, an expert ``W_down
  (silu(W_gate h) * W_up h)`` at 1280, the shared one the same form.

**The chip's share.** Experts: only the terms whose expert lies in ``held =
[lo, hi)`` are computed, plus ``Shared(h)``. Heads: the leaves handed in HAVE
only the held heads' columns (of ``W_q``, ``W_k``, ``W_v``, ``W_gate``,
``W_fb``, ``W_gb``, ``W_beta``, the taps, ``A_log``, ``dt_bias``) and rows (of
``W_o``), so every sum over heads below is over the heads held; what the
absent heads and experts would add is left out, and that partial sum goes on
to the next layer. Padded positions reach no expert.

**Top-k is a hard choice**, as in ``reference/kanana2_a3b.py`` and
``reference/nemotron3_nano_a3b.py``: the reference computes its OWN float32
scores and, word by word, takes the system's set of experts only if every
expert in which that set differs from the reference's own top k has a
selection score within ``ROUTE_TIE`` of the reference's k-th best; else the
word is NaN and the comparison fails (a wrong router is caught). The weights
are always the reference's own. The number of (word, layer) choices that used
the rule is printed and kept in ``LAST_TIES``; over ``MAX_TIE_SHARE`` of them
is NaN everywhere.

Departures of the PROGRAM from the published model (no LM head, words for
subwords, ...) are listed under ``assumed`` in
``benchmark/configs/solar_open2_250b.json``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Both limits lie between two chip readings (PERF.md section 6, PR 36; the
# faults and the controls are ``benchmark/tests/test_solar_open2_250b_faults
# .py``, which puts them through ``trunk_check.check`` and, run as a script on
# the chip, reads them at the published widths; every reading below is of a
# trunk TRAINED by the cell's own loop, 60 to 300 steps).
#
# Forward: max over real positions of |system - reference| over the largest
# |reference| (outputs are RMS-normed, O(1)). The chip computes the matrix
# products in bfloat16 (compute_dtype "auto"), the residual stream, norms, the
# gates' low-rank products, decays, running sums, the solve, the carried state,
# router and softmax in float32. The trained trunk read 5.5e-3 to 1.55e-2 in
# twenty-two runs (median 8.2e-3); the reference itself with bfloat16's 8
# significant bits in every product's operands 1.04e-2 and 1.17e-2 on two
# trunks (the stated precision: it passes, as it should, and the program is
# as near the reference as bfloat16 is: eight layers at width 4096 cost 1%
# where nemotron3_nano_a3b's nine at 2688 cost 0.2%); the control, the same
# with float8's 4 bits (the reference made to follow the system's routing:
# with the tie rule in force it is refused outright), 0.175 and 0.184. The
# limit is 3.1 times over the largest sound reading and 3.6 times under the
# control. Every planted fault reads over it: beta not doubled 0.12, an expert
# skipped 0.13, the decay a scalar a head 0.31, the rank-one correction left
# out 0.31, the gate left off G 0.96. On the CPU, in float32: 8e-7 to 1.3e-6.
TOLERANCE = 4.8e-2
TOLERANCE_F32 = 2e-5
# Gradient of sum(mask * X * R): worst leaf by max |difference| over max
# |reference| of that leaf or of the median leaf (trunk_check.gradient_errors).
# On the CPU, in float32, 2e-6 to 3e-6: the tests hold every leaf of the
# program's backward (the solve, the scan over chunks, remat, the dispatch) to
# the recurrence's. On the chip the limit lies between two readings of trained
# trunks at the published widths. Sound: twenty-two readings, 0.017 to 0.036 in
# seventeen of them (the worst leaf a norm gain, a K layer's taps or a
# projection), then 0.044, 0.057, 0.076, 0.078 and 0.129, each of the five on
# an EXPERT leaf (``eu_W`` / ``ed_W``): the router walks away from the 8 held
# experts of 320 as it trains (0.6-1.4% of the assignments where 2.5% is even),
# so a held expert sees 5 to 35 of the comparison's 1,300 words and ONE word
# is a tenth of its gradient; the leaf is judged by its largest entry. The
# reference itself with bfloat16 operands AND cotangents reads 0.021, with
# operands alone 0.018. Failing: the control, the reference with float8's 4
# bits in every product's operands, 0.237 and 0.283 on two trunks, and 0.228
# with the cotangents rounded too (each made to follow the system's routing:
# with the tie rule in force it is refused outright and reads no number); the
# planted faults 0.16 (an expert skipped, which the forward limit catches),
# 0.58, 1.4, 1.7 and 1.7. The limit is 1.5 times over the largest sound reading
# and 1.2 times under the lowest control: the two precisions stand nearer each
# other in this measure here than in nemotron3_nano_a3b, and the sound
# readings' tail is heavy (the five largest: each about one and a half times
# the one before it; by that tail about one fresh reading in fifty passes the
# limit). The control, and every planted fault, fail by the FORWARD limit with
# three times of room; this limit is the backstop for a fault of the backward
# pass alone, which the CPU tests hold to 2e-4. PERF.md section 7 says what is
# known of the tail and what would narrow it (more sequences in the
# comparison, or a measure by a leaf's norm: ``trunk_check.py``'s, a
# ``benchmark`` PR's). All twenty-two readings were taken with the reference
# told the FORWARD program's choices for both comparisons; since the last
# chip call of PR 36 it is told a gradient program's too (``make_inputs``),
# which is what the five large readings are laid to; one run on the chip with
# both sets read 9.4e-3 and 0.046 (a K layer's taps), and the limit stands
# where the readings under the old telling put it.
GRAD_TOLERANCE = 0.19
GRAD_TOLERANCE_F32 = 2e-4
# A selection score within this of the reference's k-th best may fall either
# side of the cut (kanana2_a3b's rule and its value: a sigmoid's score moves
# by at most a quarter of what its logit moves). The top 8 of 320 put more
# choices near the cut than the top 6 of 128 do, and bfloat16 moves this
# trunk's h five times as far: the rule admitted every choice the trained
# program made in twenty-two readings and was used for 2.0% to 3.4% of about
# 5,300 of them after 117 steps or more (4.1% after 60); the farthest any
# admitted expert lay from the cut: 6e-4 to 2.1e-3 in eighteen readings
# (printed by every run), so 4e-3 has twice of room. ``MAX_TIE_SHARE`` is 0.10
# here, twice the other routed references', two and a half times the largest
# share read. In float32 the rule is expected unused. An UNTRAINED router's
# scores lie too close for it: only a trained trunk can be compared.
ROUTE_TIE = 4e-3
ROUTE_TIE_F32 = 1e-6
MAX_TIE_SHARE = 0.10
# None: the system's forward is compared as the program runs it
SYSTEM_MATMUL_PRECISION = None
COMPUTE_DTYPE_ON_TPU = "bfloat16"

# the published sizes the reference computes with at the published width
PUBLISHED = {
    "hidden_size": 4096, "head_dim": 128, "kda_head_dim": 128, "n_experts": 320, "top_k": 8,
    "route_scale": 1.0, "rms_eps": 1e-5, "kda_neg_eigval": True,
}
LAST_TIES = {"used": 0, "choices": 0, "reached": 0.0}


def make_inputs(nlp, master, tokens):
    """What ``forward`` is handed after the trunk's float32 tree: the word's
    row of the table (the program's hashing gives it; the reference starts
    there), the mask, the range of experts held here, the system's own
    choices (the tie rule reads them; two sets, below) and the sizes: the published ones at the
    published width; at any other width (a rehearsal, a test) the sizes the
    trunk was built with, and said so. How many heads are held is read from
    the leaves."""
    trunk = nlp.components[nlp.tok2vec_name].model
    shape = trunk.meta["shape"]
    mask = jnp.asarray(tokens.mask)
    ids = trunk.meta["word_rows"](tokens)
    held = (shape.expert_rank * shape.experts_held, (shape.expert_rank + 1) * shape.experts_held)
    # the system's choices TWICE: as its forward program makes them and as a
    # gradient program does. Each compiled program rounds its own way to the
    # hard cut (read on the chip, PR 36: the two differ in 11 and 13 of about
    # 5,000 (word, layer) choices on two trunks, more the deeper the layer; two
    # forward programs in none), and a held expert that 5 to 35 of the
    # comparison's words reach has a tenth of its gradient from one word: told
    # the forward's choices, the gradient comparison read 0.13 on an expert
    # leaf where it reads 0.02 to 0.04 elsewhere. ``forward`` takes the second
    # set where it is being differentiated
    forward_choices = jax.jit(trunk.meta["routing_choices"])(master, tokens)

    def summed(p, t, r):
        X, chosen = trunk.meta["forward_and_choices"](p, t)
        return jnp.sum(X.astype(jnp.float32) * r), chosen

    cotangent = mask[..., None] * jnp.ones((shape.width,), jnp.float32)  # the choices do not depend on it
    gradient_choices = jax.jit(jax.grad(summed, has_aux=True))(
        jax.tree_util.tree_map(jnp.asarray, master), tokens, cotangent)[1]
    choices = jnp.stack([forward_choices, gradient_choices])
    dims = dict(PUBLISHED)
    if shape.width != PUBLISHED["hidden_size"]:
        dims.update({key: getattr(shape, key) for key in PUBLISHED if key != "hidden_size"})
        print(f"reference solar_open2_250b: width {shape.width} is not the published "
              f"{PUBLISHED['hidden_size']}: computing with the trunk's own sizes", flush=True)
    dims["route_tie"] = ROUTE_TIE_F32 if jax.default_backend() == "cpu" else ROUTE_TIE
    return ids, mask, held, choices, dims


def _rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _gated_silu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _gated_attention(p, h, mask, d):
    B, T, _ = h.shape
    hd = d["head_dim"]
    H, Hkv = p["q_W"].shape[1] // hd, p["k_W"].shape[1] // hd  # the heads held here
    q = (h @ p["q_W"]).reshape(B, T, H, hd)
    # the key/value head repeated for the H / Hkv query heads that read it
    k = jnp.repeat((h @ p["k_W"]).reshape(B, T, Hkv, hd), H // Hkv, axis=2)
    v = jnp.repeat((h @ p["v_W"]).reshape(B, T, Hkv, hd), H // Hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    visible = causal[None, None] & mask[:, None, None, :]
    weights = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(B, T, H * hd)
    return (out * jax.nn.sigmoid(h @ p["gate_W"])) @ p["ao_W"]


@jax.checkpoint
def _delta_rule_row(q, k, v, g, beta):
    """One row, word by word. q, k, g [T, H, K], v [T, H, V], beta [T, H] ->
    o [T, H, V]."""

    def word(S, at):
        q_t, k_t, v_t, g_t, beta_t = at
        S = jnp.exp(g_t)[:, :, None] * S  # every key channel decays by its own alpha
        # the rank-one correction: what the state holds under k_t moves towards v_t
        held = jnp.einsum("hk,hkv->hv", k_t, S)
        S = S - beta_t[:, None, None] * k_t[:, :, None] * held[:, None, :]
        S = S + beta_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    start = jnp.zeros(k.shape[1:] + v.shape[-1:], jnp.float32)
    return jax.lax.scan(word, start, (q, k, v, g, beta))[1]


def _conv_silu(x, taps):
    """Depthwise, causal, no bias: taps [K, channels], the last the position itself."""
    T = x.shape[1]
    out = jnp.zeros_like(x)
    for back in range(taps.shape[0]):  # position t sees t - back, nought before the row's start
        out = out + taps[taps.shape[0] - 1 - back] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :T]
    return jax.nn.silu(out)


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(p, h, d):
    B, T, _ = h.shape
    K = d["kda_head_dim"]
    H = p["q_W"].shape[1] // K  # the linear heads held here
    # the program stores the three convolutions' taps in one leaf [tap, q | k | v, channel]
    taps_q, taps_k, taps_v = (p["conv_W"][:, i] for i in range(3))
    q = _l2norm(_conv_silu(h @ p["q_W"], taps_q).reshape(B, T, H, K)) / np.sqrt(K)
    k = _l2norm(_conv_silu(h @ p["k_W"], taps_k).reshape(B, T, H, K))
    v = _conv_silu(h @ p["v_W"], taps_v).reshape(B, T, H, K)
    beta = (2.0 if d["kda_neg_eigval"] else 1.0) * jax.nn.sigmoid(h @ p["beta_W"])
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        ((h @ p["fa_W"]) @ p["fb_W"] + p["dt_bias"]).reshape(B, T, H, K))
    o = jax.lax.map(lambda row: _delta_rule_row(*row), (q, k, v, g, beta))
    o = _rms_norm(o, p["o_norm_g"], d["rms_eps"])
    gate = jax.nn.sigmoid(((h @ p["ga_W"]) @ p["gb_W"]).reshape(B, T, H, K))
    return (o * gate).reshape(B, T, H * K) @ p["ao_W"]


def _selection(scores, bias, system_idx, real, d):
    """The 0/1 mask [N, E] of the experts each word is sent to: the system's
    set where the tie rule admits it. Returns (mask, admitted [N] bool, used
    [N] bool, the farthest from the cut that any expert lay in which a real
    word's two sets differ)."""
    E, K = d["n_experts"], d["top_k"]
    select = scores + bias
    own = jnp.sum(jax.nn.one_hot(jax.lax.top_k(select, K)[1], E), axis=1) > 0
    kth = jnp.sort(select, axis=-1)[:, E - K]
    theirs = jnp.sum(jax.nn.one_hot(system_idx, E), axis=1)
    distinct = jnp.all((theirs == 0) | (theirs == 1), axis=-1) & (jnp.sum(theirs, -1) == K)
    differ = own != (theirs > 0)
    near = jnp.abs(select - kth[:, None]) <= d["route_tie"]
    admitted = (distinct & jnp.all(~differ | near, axis=-1)) | ~real
    used = jnp.any(differ, axis=-1) & real
    reach = jnp.max(jnp.where(differ & real[:, None], jnp.abs(select - kth[:, None]), 0.0))
    return (theirs > 0) & real[:, None], admitted, used, reach


def _experts(p, h, mask, held, system_idx, d):
    B, T, D = h.shape
    flat, real = h.reshape(B * T, D), mask.reshape(B * T)
    scores = jax.nn.sigmoid(flat @ p["router_W"])
    chosen, admitted, used, reach = _selection(
        scores, p["router_b"], system_idx.reshape(B * T, -1), real, d)
    picked = jnp.where(chosen, scores, 0.0)
    weights = d["route_scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    y = _gated_silu(flat, p["sg_W"], p["su_W"], p["sd_W"])  # the shared expert, every word
    lo, hi = held
    # the experts held here, densely: EVERY held expert computes EVERY word
    # (the weights are stacked [held, ., .], so one product over that axis),
    # and the 0/1 selection, times the weight, decides what is added
    inner = (jax.nn.silu(jnp.einsum("nd,edf->enf", flat, p["eg_W"]))
             * jnp.einsum("nd,edf->enf", flat, p["eu_W"]))
    each = jnp.einsum("enf,efd->end", inner, p["ed_W"])
    y = y + jnp.sum(weights[:, lo:hi].T[:, :, None] * each, axis=0)
    y = jnp.where(admitted[:, None], y, jnp.nan)
    return y.reshape(B, T, D), used, reach


@functools.partial(jax.jit, static_argnames=("held", "sizes"))
def _forward(params, ids, mask, choices, held, sizes):
    """The whole forward as ONE compiled program; still a Python loop over the
    layers. Returns (x, the number of (word, layer) choices that used the tie
    rule, how far from the cut the farthest of them reached)."""
    d = dict(sizes)
    used_total, reached = jnp.int32(0), jnp.float32(0.0)
    with jax.default_matmul_precision("highest"):
        x = params["E"][ids] * mask[..., None]
        depth = sum(1 for k in params if k.startswith("layer_"))
        routed = 0
        for i in range(depth):
            p = params[f"layer_{i}"]
            h = _rms_norm(x, p["norm_g"], d["rms_eps"])
            if "beta_W" in p:
                y = _kda(p, h, d)
            elif "gate_W" in p:
                y = _gated_attention(p, h, mask, d)
            else:
                y, used, reach = _experts(p, h, mask, held, choices[routed], d)
                used_total, reached = used_total + jnp.sum(used), jnp.maximum(reached, reach)
                routed += 1
            x = x + y
        x = _rms_norm(x, params["rms_f_g"], d["rms_eps"]) * mask[..., None]
    return x, used_total, reached


def forward(params, ids, mask, held, choices, dims=None):
    """``params``: the trunk's float32 tree; ``ids`` / ``mask`` [B, T];
    ``held`` (lo, hi); ``choices`` [expert layers, B, T, top_k], the system's,
    or two such sets stacked (``make_inputs``);
    ``dims`` the sizes (``PUBLISHED`` and a ``route_tie``). Returns [B, T, D]
    float32."""
    d = {**PUBLISHED, "route_tie": ROUTE_TIE, **(dims or {})}
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    mask = jnp.asarray(mask)
    choices = jnp.asarray(choices)
    if choices.ndim == 5:  # (the forward program's, a gradient program's): make_inputs says why
        differentiated = any(isinstance(leaf, jax.core.Tracer)
                             for leaf in jax.tree_util.tree_leaves(params))
        choices = choices[1 if differentiated else 0]
    x, used, reached = _forward(params, jnp.asarray(ids), mask, jnp.asarray(choices),
                                tuple(int(e) for e in held), tuple(sorted(d.items())))
    routed = len(choices)
    if routed and not isinstance(used, jax.core.Tracer):
        n_choices = int(jnp.sum(mask)) * routed
        LAST_TIES.update(used=int(used), choices=n_choices)
        reach = ""
        if not isinstance(reached, jax.core.Tracer):  # under jax.grad it is a function of the tree
            LAST_TIES["reached"] = float(reached)
            reach = f"; the farthest of them from the cut {float(reached):.3g}"
        print(f"reference solar_open2_250b: the tie rule (|score - k-th best| <= "
              f"{d['route_tie']}) took the system's set for {int(used)} of {n_choices} "
              f"(word, layer) choices{reach}", flush=True)
        if int(used) > MAX_TIE_SHARE * n_choices:
            x = x * jnp.nan  # a router that disagrees this often is not a rounding
    return x
