"""Plain reference of the ``kimi_linear_48b_a3b`` trunk: float32 ``jax.numpy``
under ``default_matmul_precision("highest")``, a Python loop over the layers,
the delta-rule layer as the per-word RECURRENCE under ``lax.scan`` (no chunks,
no triangular solve), latent attention as a full masked softmax with the
shared key broadcast to every head and NOT rotated, the experts held computed
densely (every held expert, every word) under a 0/1 selection mask. No sort,
no grouped product, no kernel. It imports nothing from
``spacy_ray_tpu/models``.

The experts, the norms, the gated FFN, the convolution and ``l2norm`` are
``reference/solar_open2_250b.py``'s functions (``_experts``, ``_rms_norm``,
``_gated_silu``, ``_conv_silu``, ``_l2norm``), imported from that file by
path and used as they stand; that file says what each computes. The
delta-rule layer is that file's ``_kda`` written again with the same
equations and one difference of layout, for memory alone: its recurrence is
the same word-at-a-time ``lax.scan``, but run in blocks of ``BLOCK`` words,
each block under ``jax.checkpoint`` (and each row, as there), so that the
gradient keeps a row's state at block edges and not at every word (32 heads
of 128 x 128 floats are 2 MB a state: 0.5 GB a row at every word). Every
layer of ``_forward`` is under ``jax.checkpoint`` too: the gradient keeps a
layer's input and recomputes the rest. Both leave every number as it was.
Compiled for a described v5e at the comparison's 8 x 256, the gradient's
temporaries are 0.83 GiB with both, 2.46 GiB with the blocks alone and 3.86
GiB with neither; with neither the trunk check ran out of the chip's memory
(PERF.md section 6). The faults file's control walks into the checkpointed
layers to round their products. This configuration hands the delta rule
``kda_neg_eigval`` false.

Written from the published architecture (moonshotai/Kimi-Linear-48B-A3B-
Instruct ``config.json``, ``model_type`` ``kimi_linear``; Kimi Linear,
arXiv:2510.26692). ``x`` is the float32 residual stream. A published layer
is ``x += Mixer(RMSNorm(x)); x += FFN(RMSNorm(x))``; the program stores the
mixer and the FFN as two entries, ``layer_<2i>`` and ``layer_<2i+1>``, each
with its own ``norm_g``. The kind of each entry is NOT taken from the
program: it is the published one, from ``linear_attn_config``'s two lists
and ``first_k_dense_replace`` (``LAYER_LISTS``), and a tree whose leaves say
otherwise is answered with NaN.

* ``RMSNorm(x) = x / sqrt(mean(x^2) + 1e-5) * g``; after the last layer
  ``RMSNorm_f``, then the padded positions are zeroed. Input: one table ``E``,
  one row a word. No positional term anywhere (``mla_use_nope``).
* ``K`` (``kda_layers``): Kimi delta attention, 32 heads of 128, ``beta =
  sigmoid(h W_beta)`` in (0, 1) (no ``kda_allow_neg_eigval`` in config.json).
* ``L`` (``full_attn_layers``): ``q = h W_q``, 32 heads of 128 + 64 (no
  ``q_lora_rank``); ``[c, k_r] = h W_kva`` (512 + 64); ``c = RMSNorm(c)``;
  ``[k_nope, v] = c W_kvb`` (32 heads of 128 + 128); ``k = [k_nope, k_r]``
  with ``k_r`` the same for every head and not rotated; ``o = softmax(q k^T /
  sqrt(192) + causal + key padding) v``; ``y = o W_o``.
* ``D`` (the first ``first_k_dense_replace`` layers' FFN): ``(silu(h W_g) *
  (h W_u)) W_d`` at 9216.
* ``E`` (every later layer's FFN): ``s = sigmoid(h W_r)`` over 256; the top 8
  of ``s + b`` (``b`` the selection bias, frozen at its seed); ``w = 2.446 *
  s_k / (sum of the chosen s + 1e-20)``; ``y = sum_k w_k Expert_k(h) +
  Shared(h)``, an expert ``W_down (silu(W_gate h) * W_up h)`` at 1024, the
  shared one the same form. ``num_expert_group`` 1: the plain top-k.

**The chip's share.** Only the terms whose expert lies in ``held = [lo,
hi)`` are computed, plus ``Shared(h)``; what the absent experts would add is
left out, and that partial sum goes on to the next layer. Every head is held.
Padded positions reach no expert.

**Top-k is a hard choice**, as in the other routed references: the system's
set is taken word by word only where every expert in which it differs from
the reference's own top k lies within ``ROUTE_TIE`` of the reference's k-th
best score; else the word is NaN. The weights are always the reference's own.
Over ``MAX_TIE_SHARE`` of the (word, layer) choices is NaN everywhere.

Departures of the PROGRAM from the published model (no LM head, words for
subwords, ...) are listed under ``assumed`` in
``benchmark/configs/kimi_linear_48b_a3b.json``.
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# The chip's two limits, each between two readings at the published widths
# (PERF.md section 6): five trunks trained by the cell's own loop (56-88
# steps) read forward 5.3e-3 to 7.2e-3 and gradient 1.30e-2 to 2.43e-2; the
# control, the plain reference with the operands of its products and the
# cotangents that come back into them rounded to float8's 4 significant bits
# (one precision under the bfloat16 the chip computes in), reads NaN as the
# cell runs it (its choices flip past the tie rule) and, with the reference
# made to follow its routing, forward 9.8e-2 and gradient 0.215. Each limit
# is about the geometric middle of the two: 2.7e-2 is 3.7 times over the
# largest sound reading and 3.6 under the control, 7.2e-2 3.0 times over
# and 3.0 under. The same reference with bfloat16's 8 bits reads 6.2e-3 and
# 1.5e-2, inside both. The faults and the control are
# ``benchmark/tests/test_kimi_linear_48b_a3b_faults.py``, which puts them
# through ``trunk_check.check`` and, run as a script on the chip, reads them at
# the published widths after the cell's own training.
#
# Forward: max over real positions of |system - reference| over the largest
# |reference| (outputs are RMS-normed, O(1)). The chip computes the matrix
# products in bfloat16 (compute_dtype "auto"); the residual stream, norms,
# the delta rule's gates, decays, solve and state, the router and the softmax
# in float32. On the CPU, in float32: about 1e-6 at the tests' widths, and the
# reference with bfloat16's 8 significant bits in its products reads over
# ``TOLERANCE_F32`` (the faults file's control).
TOLERANCE = 2.7e-2
TOLERANCE_F32 = 2e-5
# Gradient of sum(mask * X * R): worst leaf by max |difference| over max
# |reference| of that leaf or of the median leaf (trunk_check.gradient_errors).
# On the CPU, in float32, about 3e-6 at the tests' widths.
GRAD_TOLERANCE = 7.2e-2
GRAD_TOLERANCE_F32 = 2e-4
# A selection score within this of the reference's k-th best may fall either
# side of the cut: solar_open2_250b's rule and value (top 8 of 320 there, of
# 256 here). In float32 the rule is expected unused. An UNTRAINED router's
# scores lie too close for it: only a trained trunk can be compared.
ROUTE_TIE = 4e-3
ROUTE_TIE_F32 = 1e-6
MAX_TIE_SHARE = 0.10
# None: the system's forward is compared as the program runs it
SYSTEM_MATMUL_PRECISION = None
COMPUTE_DTYPE_ON_TPU = "bfloat16"

# the published sizes the reference computes with at the published width
PUBLISHED = {
    "hidden_size": 2304, "n_heads": 32, "qk_nope": 128, "qk_rope": 64, "v_head": 128,
    "kv_rank": 512, "kda_head_dim": 128, "n_experts": 256, "top_k": 8, "route_scale": 2.446,
    "rms_eps": 1e-5, "kda_neg_eigval": False,
}
# which published layer (1-indexed) is which: linear_attn_config and first_k_dense_replace
LAYER_LISTS = {
    "kda_layers": (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26),
    "full_attn_layers": (4, 8, 12, 16, 20, 24, 27),
    "first_k_dense_replace": 1,
}
# the leaf that tells each kind of entry in the program's tree
KIND_LEAF = {"K": "beta_W", "L": "kva_W", "D": "g_W", "E": "router_W"}
LAST_TIES = {"used": 0, "choices": 0, "reached": 0.0}


def _sibling(name: str):
    """``benchmark/reference/<name>.py`` as a module of this file's own."""
    path = Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"kimi_linear_reference_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_SOLAR = _sibling("solar_open2_250b")
_rms_norm, _gated_silu, _conv_silu, _l2norm, _experts = (
    _SOLAR._rms_norm, _SOLAR._gated_silu, _SOLAR._conv_silu, _SOLAR._l2norm, _SOLAR._experts)
# words a checkpointed block of the recurrence (the module docstring says why)
BLOCK = 16


def published_kinds(depth: int) -> str:
    """The kind of each of the first ``depth`` entries of the tree: a
    published layer's mixer (``K`` or ``L``), then its FFN (``D`` or ``E``)."""
    out = ""
    for layer in range(1, depth // 2 + 1):
        out += "K" if layer in LAYER_LISTS["kda_layers"] else "L"
        out += "D" if layer <= LAYER_LISTS["first_k_dense_replace"] else "E"
    return out


def make_inputs(nlp, master, tokens, gradient_choices: bool = True):
    """What ``forward`` is handed after the trunk's float32 tree: the word's
    row of the table (the program's hashing gives it), the mask, the range of
    experts held here, the system's choices twice (as its forward program and
    as a gradient program make them: ``reference/solar_open2_250b.py``'s
    ``make_inputs`` says why) and the sizes: the published ones at the
    published width; at any other width (a rehearsal, a test) the sizes the
    trunk was built with, and said so. ``gradient_choices`` False: the
    forward program's set alone, for a comparison of the forward alone (a
    test's; ``trunk_check`` always asks for both)."""
    trunk = nlp.components[nlp.tok2vec_name].model
    shape = trunk.meta["shape"]
    mask = jnp.asarray(tokens.mask)
    ids = trunk.meta["word_rows"](tokens)
    held = (shape.expert_rank * shape.experts_held, (shape.expert_rank + 1) * shape.experts_held)
    choices = jax.jit(trunk.meta["routing_choices"])(master, tokens)

    def summed(p, t, r):
        X, chosen = trunk.meta["forward_and_choices"](p, t)
        return jnp.sum(X.astype(jnp.float32) * r), chosen

    if gradient_choices:
        cotangent = mask[..., None] * jnp.ones((shape.width,), jnp.float32)  # the choices do not depend on it
        choices = jnp.stack([choices, jax.jit(jax.grad(summed, has_aux=True))(
            jax.tree_util.tree_map(jnp.asarray, master), tokens, cotangent)[1]])
    dims = dict(PUBLISHED)
    if shape.width != PUBLISHED["hidden_size"]:
        dims.update({key: getattr(shape, key) for key in PUBLISHED if key != "hidden_size"})
        print(f"reference kimi_linear_48b_a3b: width {shape.width} is not the published "
              f"{PUBLISHED['hidden_size']}: computing with the trunk's own sizes", flush=True)
    dims["route_tie"] = ROUTE_TIE_F32 if jax.default_backend() == "cpu" else ROUTE_TIE
    return ids, mask, held, choices, dims


def _word(S, at):
    """One word of the delta rule, per head: decay, the rank-one correction,
    the write, the read. S [H, K, V]."""
    q_t, k_t, v_t, g_t, beta_t = at
    S = jnp.exp(g_t)[:, :, None] * S  # every key channel decays by its own alpha
    # the rank-one correction: what the state holds under k_t moves towards v_t
    held = jnp.einsum("hk,hkv->hv", k_t, S)
    S = S - beta_t[:, None, None] * k_t[:, :, None] * held[:, None, :]
    S = S + beta_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
    return S, jnp.einsum("hkv,hk->hv", S, q_t)


@jax.checkpoint
def _block(S, block):
    return jax.lax.scan(_word, S, block)


@jax.checkpoint
def _delta_rule_row(q, k, v, g, beta):
    """One row, word by word, in blocks of ``BLOCK`` words. q, k, g [T, H, K],
    v [T, H, V], beta [T, H] -> o [T, H, V]. Words past the row's end are
    noughts (beta nought: nothing written; g nought: no decay) and are cut off."""
    T = q.shape[0]
    pad = -T % BLOCK
    blocks = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        ((T + pad) // BLOCK, BLOCK) + a.shape[1:]) for a in (q, k, v, g, beta))
    start = jnp.zeros(k.shape[1:] + v.shape[-1:], jnp.float32)
    o = jax.lax.scan(_block, start, blocks)[1]
    return o.reshape((T + pad,) + o.shape[2:])[:T]


def _kda(p, h, d):
    """``reference/solar_open2_250b.py``'s ``_kda``, the recurrence in blocks."""
    B, T, _ = h.shape
    K = d["kda_head_dim"]
    H = p["q_W"].shape[1] // K
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


def _latent_attention(p, h, mask, d):
    B, T, _ = h.shape
    H, nope, rope_d, v_d, rank = d["n_heads"], d["qk_nope"], d["qk_rope"], d["v_head"], d["kv_rank"]
    q = (h @ p["q_W"]).reshape(B, T, H, nope + rope_d)
    kva = h @ p["kva_W"]
    c, k_r = kva[..., :rank], kva[..., rank:]
    kv = (_rms_norm(c, p["rmskv_g"], d["rms_eps"]) @ p["kvb_W"]).reshape(B, T, H, nope + v_d)
    # the shared key, the same for every head and unrotated, beside each head's own
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, rope_d))],
                        axis=-1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(nope + rope_d)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    visible = causal[None, None] & mask[:, None, None, :]
    weights = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, kv[..., nope:]).reshape(B, T, H * v_d)
    return out @ p["ao_W"]


def _mixer_or_dense(p, x, mask, *, kind, d):
    h = _rms_norm(x, p["norm_g"], d["rms_eps"])
    if kind == "K":
        return _kda(p, h, d)
    if kind == "L":
        return _latent_attention(p, h, mask, d)
    return _gated_silu(h, p["g_W"], p["u_W"], p["d_W"])


def _expert_layer(p, x, mask, system_idx, *, held, d):
    return _experts(p, _rms_norm(x, p["norm_g"], d["rms_eps"]), mask, held, system_idx, d)


@functools.partial(jax.jit, static_argnames=("held", "sizes", "kinds"))
def _forward(params, ids, mask, choices, held, sizes, kinds):
    """The whole forward as ONE compiled program; still a Python loop over the
    layers. Returns (x, the number of (word, layer) choices that used the tie
    rule, how far from the cut the farthest of them reached)."""
    d = dict(sizes)
    used_total, reached = jnp.int32(0), jnp.float32(0.0)
    with jax.default_matmul_precision("highest"):
        x = params["E"][ids] * mask[..., None]
        routed = 0
        for i, kind in enumerate(kinds):
            p = params[f"layer_{i}"]
            if kind == "E":
                y, used, reach = jax.checkpoint(functools.partial(_expert_layer, held=held, d=d))(
                    p, x, mask, choices[routed])
                used_total, reached = used_total + jnp.sum(used), jnp.maximum(reached, reach)
                routed += 1
            else:
                y = jax.checkpoint(functools.partial(_mixer_or_dense, kind=kind, d=d))(p, x, mask)
            x = x + y
        x = _rms_norm(x, params["rms_f_g"], d["rms_eps"]) * mask[..., None]
    return x, used_total, reached


def forward(params, ids, mask, held, choices, dims=None):
    """``params``: the trunk's float32 tree; ``ids`` / ``mask`` [B, T];
    ``held`` (lo, hi); ``choices`` [expert layers, B, T, top_k], the system's,
    or two such sets stacked (``make_inputs``); ``dims`` the sizes
    (``PUBLISHED`` and a ``route_tie``). Returns [B, T, D] float32."""
    d = {**PUBLISHED, "route_tie": ROUTE_TIE, **(dims or {})}
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    mask = jnp.asarray(mask)
    choices = jnp.asarray(choices)
    if choices.ndim == 5:  # (the forward program's, a gradient program's): make_inputs says why
        differentiated = any(isinstance(leaf, jax.core.Tracer)
                             for leaf in jax.tree_util.tree_leaves(params))
        choices = choices[1 if differentiated else 0]
    kinds = published_kinds(sum(1 for k in params if k.startswith("layer_")))
    stored = [KIND_LEAF[kind] in params[f"layer_{i}"] for i, kind in enumerate(kinds)]
    if not all(stored):
        print(f"reference kimi_linear_48b_a3b: the tree's layers are not the published "
              f"{kinds} (entries {[i for i, ok in enumerate(stored) if not ok]})", flush=True)
        return jnp.full(mask.shape + params["E"].shape[1:], jnp.nan)
    x, used, reached = _forward(params, jnp.asarray(ids), mask, choices,
                                tuple(int(e) for e in held), tuple(sorted(d.items())), kinds)
    routed = len(choices)
    if routed and not isinstance(used, jax.core.Tracer):
        n_choices = int(jnp.sum(mask)) * routed
        LAST_TIES.update(used=int(used), choices=n_choices)
        reach = ""
        if not isinstance(reached, jax.core.Tracer):  # under jax.grad it is a function of the tree
            LAST_TIES["reached"] = float(reached)
            reach = f"; the farthest of them from the cut {float(reached):.3g}"
        print(f"reference kimi_linear_48b_a3b: the tie rule (|score - k-th best| <= "
              f"{d['route_tie']}) took the system's set for {int(used)} of {n_choices} "
              f"(word, layer) choices{reach}", flush=True)
        if int(used) > MAX_TIE_SHARE * n_choices:
            x = x * jnp.nan  # a router that disagrees this often is not a rounding
    return x
