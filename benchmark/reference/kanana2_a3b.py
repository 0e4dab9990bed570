"""Plain reference of the ``kanana2_a3b`` trunk: float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, a Python loop over the layers, the
experts held computed densely (every held expert, every word) under a 0/1
selection mask. No sort, no grouped product, no scan, no remat, no kernel
(the loop is traced into one compiled program, ``_forward``: compilation
only). The held experts are ONE product over their stacked axis and not a
Python loop of sixteen: unrolled, the forward alone was a program of 50 MiB
that took two minutes to compile and pushed the train step out of a capped
compile cache (PERF.md section 6, PR 27).

Written from the published architecture (kakaocorp/kanana-2-30b-a3b-instruct-
2601 ``config.json``, ``model_type`` ``deepseek_v3``) as ISSUE 27 spells it,
not from ``spacy_ray_tpu/models``. ``x`` is the float32 residual stream; no
bias anywhere.

* ``RMSNorm(x) = x / sqrt(mean(x^2) + 1e-6) * g``. Block: ``x += Attn(RMSNorm_1
  (x))``; ``x += FFN(RMSNorm_2(x))``; after the last layer ``RMSNorm_f``, then
  the padded positions are zeroed.
* Input: one table ``E``, one row a word.
* Latent attention, ``h = RMSNorm_1(x)``: ``q = h W_q`` -> heads of (nope |
  rope); ``h W_kva`` -> (``c`` | ``k_pe``); ``c = RMSNorm_kv(c)``; ``c W_kvb``
  -> heads of (``k_nope`` | ``v``); rotary on ``q_pe`` and on the ONE ``k_pe``
  all heads share, adjacent pairs ``(2i, 2i+1)`` rotated by ``pos *
  theta^(-2i/d)``; ``softmax(q k^T / sqrt(nope + rope) + causal + key padding)
  v`` -> ``W_o``. Causal as published.
* Dense FFN (the leading layers): ``(silu(h W_g) * (h W_u)) W_d``.
* Expert layer: ``s = sigmoid(h W_r)``; the top ``k`` of ``s + b`` (``b`` moves
  the selection only); ``w = scale * s_k / (sum of the chosen s + 1e-20)``;
  ``y = sum_k w_k Expert_k(h) + Shared(h)``. **The chip's share**: only the
  terms whose expert lies in ``held = [lo, hi)`` are computed, plus
  ``Shared(h)``; what the absent experts would add is left out, and that
  partial sum goes on to the next layer. Padded positions reach no expert.

**Top-k is a hard choice.** With bfloat16 upstream the 6th and 7th of 128
scores are often closer than the rounding, so an independent choice would
differ on some words by far more than any tolerance, on a correct program.
The reference computes its OWN float32 scores and, word by word, takes the
system's set of experts only if every expert in which that set differs from
the reference's own top k has a selection score within ``ROUTE_TIE`` of the
reference's k-th best; else the word is NaN and the comparison fails (a wrong
router is caught). The weights are always the reference's own. The number of
(word, layer) choices that used the rule is printed and kept in ``LAST_TIES``;
over ``MAX_TIE_SHARE`` of them is NaN everywhere.

Departures of the PROGRAM from the published model (no LM head, words for
subwords, the bias ``b`` left at its seeded value, ...) are listed under
``assumed`` in ``benchmark/configs/kanana2_a3b.json``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Both limits lie between two chip readings (PERF.md section 6, PR 27; the
# faults and the control are ``benchmark/tests/test_kanana2_a3b_faults.py``,
# which puts them through ``trunk_check.check`` and, run as a script on the
# chip, reads them at the published widths).
#
# Forward: max over real positions of |system - reference| over the largest
# |reference| (outputs are RMS-normed, O(1)). The chip computes the matrix
# products in bfloat16 (compute_dtype "auto"), the residual stream, norms,
# router and softmax in float32. The trained trunk read 3.5e-4 to 2.4e-3 in
# twenty-two runs and 9.5e-3 in one (a maximum over 2.5 M entries has a heavy
# tail); the control, the reference itself with the operands of every product
# rounded to 4 significant bits (float8's precision at float32's range, the
# reference made to follow the system's routing), reads 2.2e-2. The limit is
# 1.5 times over the one and under the other: the forward separates a
# precision by little. The shared rotary key not rotated reads 0.14, no causal
# mask 0.42; a held expert dropped and the routed scaling left out read under
# it wherever the router sends the held experts little (5.4e-3 and 3.2e-3 at
# 1.2% of the assignments): the gradient catches those. On the CPU, in
# float32, the forward reads about 2e-7.
TOLERANCE = 1.4e-2
TOLERANCE_F32 = 2e-5
# Gradient of sum(mask * X * R): worst leaf by max |difference| over max
# |reference| (trunk_check.gradient_errors). Twenty-three chip readings of the
# trained trunk: 0.035 to 0.169, the worst leaf nearly always ``kva_W`` or
# ``kvb_W`` (the ONE rotary key all heads share: its gradient is a sum over
# heads and queries of bfloat16 products); the reference with bfloat16
# operands reads alike (0.052 against 0.051), so it is rounding. From above:
# the routed scaling left out reads 1 - 1/2.448 = 0.59 on the expert weights
# however little the router sends them, the 4-bit control 0.84, a held expert
# dropped 1.0, the rotary key not rotated 2.5, no causal mask 2.3. The limit
# is 2.4 times over the largest sound reading and 1.5 times under the lowest
# fault. On the CPU, in float32: about 1e-6.
GRAD_TOLERANCE = 0.4
GRAD_TOLERANCE_F32 = 2e-4
# A selection score within this of the reference's k-th best may fall either
# side of the cut. On the chip: bfloat16 products upstream move h by about
# 1e-3 of its size and a sigmoid's score by at most a quarter of what its
# logit moves; 4e-3 admitted every choice the trained program made in
# twenty-three readings and the rule was used for 0 to 6 of about 5,000 of them (PERF.md section
# 6, PR 27). In float32 the rule is expected unused. An UNTRAINED router's
# scores lie so close that the rule would be needed for over 5% of the
# choices: only a trained trunk can be compared.
ROUTE_TIE = 4e-3
ROUTE_TIE_F32 = 1e-6
MAX_TIE_SHARE = 0.05
# None: the system's forward is compared as the program runs it
SYSTEM_MATMUL_PRECISION = None
COMPUTE_DTYPE_ON_TPU = "bfloat16"

# the published sizes the reference computes with at the published width
PUBLISHED = {
    "hidden_size": 2048, "n_heads": 32, "qk_nope": 128, "qk_rope": 64, "v_head": 128,
    "kv_rank": 512, "n_experts": 128, "top_k": 6, "route_scale": 2.448,
    "rope_theta": 1e6, "rms_eps": 1e-6,
}
LAST_TIES = {"used": 0, "choices": 0}


def make_inputs(nlp, master, tokens):
    """What ``forward`` is handed after the trunk's float32 tree: the word's
    row of the table (the program's hashing gives it; the reference starts
    there), the mask, each word's index in its document, the range of experts
    held here, the system's own choices (the tie rule reads them) and the
    sizes: the published ones at the published width; at any other width (a
    rehearsal, a test) the sizes the trunk was built with, and said so."""
    from spacy_ray_tpu.models import latent_moe

    trunk = nlp.components[nlp.tok2vec_name].model
    shape = trunk.meta["shape"]
    mask = jnp.asarray(tokens.mask)
    ids = latent_moe.word_rows(jnp.asarray(tokens.attr_keys), master["E"].shape[0])
    positions = jnp.broadcast_to(jnp.arange(mask.shape[1])[None, :], mask.shape)
    held = (shape.expert_rank * shape.experts_held, (shape.expert_rank + 1) * shape.experts_held)
    choices = jax.jit(trunk.meta["routing_choices"])(master, tokens)
    dims = dict(PUBLISHED)
    if shape.width != PUBLISHED["hidden_size"]:
        dims.update(n_heads=shape.n_heads, qk_nope=shape.qk_nope, qk_rope=shape.qk_rope,
                    v_head=shape.v_head, kv_rank=shape.kv_rank, n_experts=shape.n_experts,
                    top_k=shape.top_k, route_scale=shape.route_scale,
                    rope_theta=shape.rope_theta)
        print(f"reference kanana2_a3b: width {shape.width} is not the published "
              f"{PUBLISHED['hidden_size']}: computing with the trunk's own sizes", flush=True)
    dims["route_tie"] = ROUTE_TIE_F32 if jax.default_backend() == "cpu" else ROUTE_TIE
    return ids, mask, positions, held, choices, dims


def _rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate(x, positions, theta):
    """x [B, T, H, d]: pair (2i, 2i+1) turned by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    angle = positions[:, :, None, None].astype(jnp.float32) * (
        theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d))
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        even * jnp.sin(angle) + odd * jnp.cos(angle)], axis=-1)
    return turned.reshape(x.shape)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _attention(p, h, mask, positions, d):
    B, T, _ = h.shape
    H, nope, rope_d, v_d, rank = d["n_heads"], d["qk_nope"], d["qk_rope"], d["v_head"], d["kv_rank"]
    q = (h @ p["q_W"]).reshape(B, T, H, nope + rope_d)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kva = h @ p["kva_W"]
    c, k_pe = kva[..., :rank], kva[..., rank:]
    kv = (_rms_norm(c, p["rmskv_g"], d["rms_eps"]) @ p["kvb_W"]).reshape(B, T, H, nope + v_d)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rotate(q_pe, positions, d["rope_theta"])
    k_pe = _rotate(k_pe[:, :, None, :], positions, d["rope_theta"])  # one for all heads
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0, :])) / np.sqrt(nope + rope_d)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    visible = causal[None, None] & mask[:, None, None, :]
    weights = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(B, T, H * v_d)
    return out @ p["ao_W"]


def _selection(scores, bias, system_idx, real, d):
    """The 0/1 mask [N, E] of the experts each word is sent to: the system's
    set where the tie rule admits it, NaN-marked words where it does not.
    Returns (mask, admitted [N] bool, used [N] bool)."""
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
    return (theirs > 0) & real[:, None], admitted, used


def _expert_layer(p, h, mask, held, system_idx, d):
    B, T, D = h.shape
    flat, real = h.reshape(B * T, D), mask.reshape(B * T)
    scores = jax.nn.sigmoid(flat @ p["router_W"])
    chosen, admitted, used = _selection(
        scores, p["router_b"], system_idx.reshape(B * T, -1), real, d)
    picked = jnp.where(chosen, scores, 0.0)
    weights = d["route_scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    y = _swiglu(flat, p["sg_W"], p["su_W"], p["sd_W"])  # the shared experts, every word
    lo, hi = held
    # the experts held here, densely: EVERY held expert computes EVERY word
    # (the weights are stacked [held, ., .], so one product over that axis),
    # and the 0/1 selection, times the weight, decides what is added
    gate = jnp.einsum("nd,edf->enf", flat, p["eg_W"])
    up = jnp.einsum("nd,edf->enf", flat, p["eu_W"])
    each = jnp.einsum("enf,efd->end", jax.nn.silu(gate) * up, p["ed_W"])
    y = y + jnp.sum(weights[:, lo:hi].T[:, :, None] * each, axis=0)
    y = jnp.where(admitted[:, None], y, jnp.nan)
    return y.reshape(B, T, D), used


@functools.partial(jax.jit, static_argnames=("held", "sizes"))
def _forward(params, ids, mask, positions, choices, held, sizes):
    """The whole forward as ONE compiled program (a thousand small float32
    operations dispatched one by one cost ten minutes of compilation on the
    chip the first time); still a Python loop over the layers.
    Returns (x, the number of (word, layer) choices that used the tie rule)."""
    d = dict(sizes)
    used_total = jnp.int32(0)
    with jax.default_matmul_precision("highest"):
        x = params["E"][ids] * mask[..., None]
        depth = sum(1 for k in params if k.startswith("layer_"))
        routed = 0
        for i in range(depth):
            p = params[f"layer_{i}"]
            x = x + _attention(p, _rms_norm(x, p["rms1_g"], d["rms_eps"]), mask, positions, d)
            h = _rms_norm(x, p["rms2_g"], d["rms_eps"])
            if "router_W" in p:
                y, used = _expert_layer(p, h, mask, held, choices[routed], d)
                used_total = used_total + jnp.sum(used)
                routed += 1
            else:
                y = _swiglu(h, p["g_W"], p["u_W"], p["d_W"])
            x = x + y
        x = _rms_norm(x, params["rms_f_g"], d["rms_eps"]) * mask[..., None]
    return x, used_total


def forward(params, ids, mask, positions, held, choices, dims=None):
    """``params``: the trunk's float32 tree; ``ids`` / ``mask`` / ``positions``
    [B, T]; ``held`` (lo, hi); ``choices`` [expert layers, B, T, top_k], the
    system's; ``dims`` the sizes (``PUBLISHED`` and a ``route_tie``). Returns
    [B, T, D] float32."""
    d = {**PUBLISHED, "route_tie": ROUTE_TIE, **(dims or {})}
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    mask = jnp.asarray(mask)
    x, used = _forward(params, jnp.asarray(ids), mask, jnp.asarray(positions), jnp.asarray(choices),
                       tuple(int(e) for e in held), tuple(sorted(d.items())))
    routed = len(choices)
    if routed and not isinstance(used, jax.core.Tracer):
        n_choices = int(jnp.sum(mask)) * routed
        LAST_TIES.update(used=int(used), choices=n_choices)
        print(f"reference kanana2_a3b: the tie rule (|score - k-th best| <= {d['route_tie']}) "
              f"took the system's set for {int(used)} of {n_choices} (word, layer) choices",
              flush=True)
        if int(used) > MAX_TIE_SHARE * n_choices:
            x = x * jnp.nan  # a router that disagrees this often is not a rounding
    return x
