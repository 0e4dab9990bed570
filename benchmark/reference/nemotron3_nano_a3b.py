"""Plain reference of the ``nemotron3_nano_a3b`` trunk: float32 ``jax.numpy``
under ``default_matmul_precision("highest")``, a Python loop over the layers,
the state-space layer as the per-position RECURRENCE under ``lax.scan`` (no
chunks, no masked products), attention as a masked softmax with every key
head repeated for its query heads, the experts held computed densely (every
held expert, every word) under a 0/1 selection mask. No sort, no grouped
product, no kernel. It imports nothing from ``spacy_ray_tpu/models``.

Compilation and memory only, no mathematics: the loop is traced into ONE
compiled program (``_forward``); the held experts are one product over their
stacked axis and not a Python loop of eight (unrolled, such a forward was a
program of 50 MiB: PERF.md section 6, PR 27); the recurrence runs row by row
(``lax.map``) with each row under ``jax.checkpoint``, because its backward
keeps the state of every position (64 x 64 x 128 floats a head-set: 4.3 GB a
layer for 8 rows of 256 at the published widths, 0.5 GB for one row).

Written from the published architecture (nvidia/NVIDIA-Nemotron-3-Nano-30B-
A3B-BF16 ``config.json``, ``model_type`` ``nemotron_h``; the family:
arXiv:2504.03624; the mixer: Mamba-2, arXiv:2405.21060) as ISSUE 34 spells
it. ``x`` is the float32 residual stream. A layer's kind is read from its
leaves (``in_W``: M, ``q_W``: attention, ``router_W``: experts).

* ``RMSNorm(x) = x / sqrt(mean(x^2) + 1e-5) * g``. Every layer: ``x +=
  Mixer(RMSNorm(x))``; after the last ``RMSNorm_f``, then the padded positions
  are zeroed. Input: one table ``E``, one row a word. No positional term.
* ``M`` (Mamba-2): ``z | xBC | dt = h W_in`` (4096 | 6144 | 64; the program
  keeps ``W_in`` in two leaves, ``in_W`` and dt's 64 columns ``dt_W``); ``xBC =
  silu(conv(xBC))``, the convolution depthwise and causal, position t seeing
  t-3..t of its own channel and row, with bias; ``x | B | C = xBC`` (4096 |
  1024 | 1024), ``x`` as 64 heads of 64, ``B``, ``C`` as 8 groups of state
  128, a group serving 8 consecutive heads; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)`` a head. Per head ``S_t = exp(dt_t A) S_(t-1) + dt_t x_t
  B_t^T`` (``S`` 64 x 128, nought before the row's first word), ``y_t = S_t
  C_t + D x_t``. Then ``RMSNorm`` over each of the 8 groups of 512 channels of
  ``y * silu(z)`` (the gate first), one gain of 4096, and ``W_out``.
* ``*`` (attention): ``q = h W_q`` -> 32 heads of 128; ``k``, ``v`` -> 2 heads
  of 128; query head j reads key/value head ``j // 16``; ``softmax(q k^T /
  sqrt(128) + causal + key padding) v`` -> ``W_o``. No bias.
* ``E`` (experts): ``s = sigmoid(h W_r)``; the top 6 of ``s + b`` (``b`` moves
  the selection only); ``w = 2.5 s_k / (sum of the chosen s + 1e-20)``; ``y =
  sum_k w_k Expert_k(h) + Shared(h)``, an expert ``W_down relu(W_up h)^2``, the
  shared one the same form. **The chip's share**: only the terms whose expert
  lies in ``held = [lo, hi)`` are computed, plus ``Shared(h)``; what the
  absent experts would add is left out, and that partial sum goes on to the
  next layer. Padded positions reach no expert.

**Top-k is a hard choice**, as in ``reference/kanana2_a3b.py``: with bfloat16
upstream the 6th and 7th of 128 scores are often closer than the rounding. The
reference computes its OWN float32 scores and, word by word, takes the
system's set of experts only if every expert in which that set differs from
the reference's own top k has a selection score within ``ROUTE_TIE`` of the
reference's k-th best; else the word is NaN and the comparison fails (a wrong
router is caught). The weights are always the reference's own. The number of
(word, layer) choices that used the rule is printed and kept in ``LAST_TIES``;
over ``MAX_TIE_SHARE`` of them is NaN everywhere.

Departures of the PROGRAM from the published model (no LM head, words for
subwords, the bias ``b`` left at its seeded value, ...) are listed under
``assumed`` in ``benchmark/configs/nemotron3_nano_a3b.json``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# The forward limit lies between two chip readings (PERF.md section 6, PR 34;
# the faults and the control are ``benchmark/tests/test_nemotron3_nano_a3b_
# faults.py``, which puts them through ``trunk_check.check`` and, run as a
# script on the chip, reads them at the published widths; every reading below
# is of a trunk TRAINED by the cell's own loop, 60 to 160 steps).
#
# Forward: max over real positions of |system - reference| over the largest
# |reference| (outputs are RMS-normed, O(1)). The chip computes the matrix
# products in bfloat16 (compute_dtype "auto"), the residual stream, norms, the
# step dt, decays, carried state, router and softmax in float32. The trained
# trunk read 1.1e-3 to 4.8e-3 in thirty-two runs (median 1.6e-3); the reference
# itself with bfloat16's 8 significant bits in every product's operands
# 2.6e-3 (the stated precision: it passes, as it should); the control, the
# same with float8's 4 bits (the reference made to follow the system's
# routing: with the tie rule in force it is refused outright), 3.9e-2. The
# limit is 2.9 times over the largest sound reading and 2.8 times under the
# control. Every planted fault reads far over it: query heads on the wrong key
# head 0.14, the carried state zeroed at chunk edges 0.21, a convolution tap
# dropped 0.26, relu for relu² 0.47, dt_bias left out 0.57. On the CPU, in
# float32: 3e-7 to 8e-7.
TOLERANCE = 1.4e-2
TOLERANCE_F32 = 2e-5
# Gradient of sum(mask * X * R): worst leaf by max |difference| over max
# |reference| of that leaf or of the median leaf (trunk_check.gradient_errors).
# On the CPU, in float32, about 2e-6: the tests hold every leaf of the
# program's backward (scan, remat, dispatch) to the recurrence's. On the chip
# the limit lies between two readings of trained trunks at the published widths
# (PERF.md section 6, PR 34). Sound: thirty-two readings of the program as it
# is at the configuration's learn_rate, 0.009 to 0.17 in twenty-eight of them,
# then 0.26, 0.34, 0.52 and 0.89 (nearly always an M layer's ``conv_W`` or
# ``in_W``; the reference itself with bfloat16 operands reads 0.13 to 0.55, so
# the tail is what 8 significant bits do to this gradient and not a fault of
# the chunked form). Failing: the control, the reference with float8's 4 bits (made to
# follow the system's routing: with the tie rule in force it is refused
# outright and reads no number), 4.8; the planted faults 1.6 (query heads on
# the wrong key head, which the forward limit catches at ten times over), 2.2
# (the carried state zeroed at chunk edges), 4.0, 4.1 and 6.0. The limit is the
# geometric middle of the largest sound reading and the control: 2.2 times over
# the one, 2.4 times under the other. The sound readings' tail is heavy (each
# of the four largest is about one and a half times the one before it: by
# that tail about one reading in a hundred passes the limit), so a fresh seed
# may yet read over it: PERF.md section 7 says what is known about the tail and
# asks ``trunk_check.py`` for a measure by a leaf's norm, which a ``benchmark``
# PR can add. With dt's own 64 columns inside the bfloat16 input projection the
# sound readings were 0.29 to 0.86 on three trunks (0.10 to 0.25 on the same
# three after the split: ``dt_W`` is its own float32 leaf for that reason).
GRAD_TOLERANCE = 2.0
GRAD_TOLERANCE_F32 = 2e-4
# A selection score within this of the reference's k-th best may fall either
# side of the cut (kanana2_a3b's rule and its value: bfloat16 products
# upstream move h by about 1e-3 of its size and a sigmoid's score by at most a
# quarter of what its logit moves). It admitted every choice the trained
# program made in thirty-two readings, and was used for 27 to 58 of about
# 5,000 of them (0.7-1.1%; 3 to 8 after the same steps at trf's five times
# larger learning rate, which sharpens a router sooner). In float32 the rule is expected unused. An UNTRAINED
# router's scores lie too close for it: only a trained trunk can be compared.
ROUTE_TIE = 4e-3
ROUTE_TIE_F32 = 1e-6
MAX_TIE_SHARE = 0.05
# None: the system's forward is compared as the program runs it
SYSTEM_MATMUL_PRECISION = None
COMPUTE_DTYPE_ON_TPU = "bfloat16"

# the published sizes the reference computes with at the published width
PUBLISHED = {
    "hidden_size": 2688, "ssm_heads": 64, "ssm_head_dim": 64, "ssm_groups": 8, "ssm_state": 128,
    "n_heads": 32, "n_kv_heads": 2, "head_dim": 128, "n_experts": 128, "top_k": 6,
    "route_scale": 2.5, "rms_eps": 1e-5,
}
LAST_TIES = {"used": 0, "choices": 0}


def make_inputs(nlp, master, tokens):
    """What ``forward`` is handed after the trunk's float32 tree: the word's
    row of the table (the program's hashing gives it; the reference starts
    there), the mask, the range of experts held here, the system's own
    choices (the tie rule reads them) and the sizes: the published ones at the
    published width; at any other width (a rehearsal, a test) the sizes the
    trunk was built with, and said so."""
    trunk = nlp.components[nlp.tok2vec_name].model
    shape = trunk.meta["shape"]
    mask = jnp.asarray(tokens.mask)
    ids = trunk.meta["word_rows"](tokens)
    held = (shape.expert_rank * shape.experts_held, (shape.expert_rank + 1) * shape.experts_held)
    choices = jax.jit(trunk.meta["routing_choices"])(master, tokens)
    dims = dict(PUBLISHED)
    if shape.width != PUBLISHED["hidden_size"]:
        dims.update({key: getattr(shape, key) for key in PUBLISHED if key != "hidden_size"})
        print(f"reference nemotron3_nano_a3b: width {shape.width} is not the published "
              f"{PUBLISHED['hidden_size']}: computing with the trunk's own sizes", flush=True)
    dims["route_tie"] = ROUTE_TIE_F32 if jax.default_backend() == "cpu" else ROUTE_TIE
    return ids, mask, held, choices, dims


def _rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _relu2(h, w_up, w_down):
    return jnp.square(jnp.maximum(h @ w_up, 0.0)) @ w_down


@jax.checkpoint
def _recurrence_row(x, B_, C_, dt, A):
    """One row, position by position. x [T, H, P], B_ / C_ [T, H, N] (each
    head given its group's), dt [T, H], A [H] -> y [T, H, P]."""

    def step(S, at):
        x_t, B_t, C_t, dt_t = at
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t)

    start = jnp.zeros(x.shape[1:] + B_.shape[-1:], jnp.float32)
    return jax.lax.scan(step, start, (x, B_, C_, dt))[1]


def _mamba(p, h, d):
    B, T, _ = h.shape
    H, P, G, N = d["ssm_heads"], d["ssm_head_dim"], d["ssm_groups"], d["ssm_state"]
    inner, state = H * P, G * N
    # the program keeps the published in_proj in two leaves, cut before dt's 64 columns
    proj = h @ jnp.concatenate([p["in_W"], p["dt_W"]], axis=-1)
    z, xBC, dt = proj[..., :inner], proj[..., inner:inner + inner + 2 * state], proj[..., -H:]
    taps = p["conv_W"].shape[0]  # [K, channels]; the last tap is the position itself
    conv = jnp.broadcast_to(p["conv_b"], xBC.shape)
    for back in range(taps):  # position t sees t - back, nought before the row's start
        shifted = jnp.pad(xBC, ((0, 0), (back, 0), (0, 0)))[:, :T]
        conv = conv + p["conv_W"][taps - 1 - back] * shifted
    xBC = jax.nn.silu(conv)
    x = xBC[..., :inner].reshape(B, T, H, P)
    # a group serves H / G consecutive heads
    B_ = jnp.repeat(xBC[..., inner:inner + state].reshape(B, T, G, N), H // G, axis=2)
    C_ = jnp.repeat(xBC[..., inner + state:].reshape(B, T, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = jax.lax.map(lambda row: _recurrence_row(*row, A), (x, B_, C_, dt))
    y = (y + p["D"][:, None] * x).reshape(B, T, inner)
    gated = (y * jax.nn.silu(z)).reshape(B, T, G, inner // G)
    normed = gated / jnp.sqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + d["rms_eps"])
    return (normed.reshape(B, T, inner) * p["gate_norm_g"]) @ p["out_W"]


def _attention(p, h, mask, d):
    B, T, _ = h.shape
    H, Hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    q = (h @ p["q_W"]).reshape(B, T, H, hd)
    # every key/value head repeated for the H / Hkv query heads that read it
    k = jnp.repeat((h @ p["k_W"]).reshape(B, T, Hkv, hd), H // Hkv, axis=2)
    v = jnp.repeat((h @ p["v_W"]).reshape(B, T, Hkv, hd), H // Hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    visible = causal[None, None] & mask[:, None, None, :]
    weights = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(B, T, H * hd) @ p["ao_W"]


def _selection(scores, bias, system_idx, real, d):
    """The 0/1 mask [N, E] of the experts each word is sent to: the system's
    set where the tie rule admits it. Returns (mask, admitted [N] bool, used
    [N] bool)."""
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


def _experts(p, h, mask, held, system_idx, d):
    B, T, D = h.shape
    flat, real = h.reshape(B * T, D), mask.reshape(B * T)
    scores = jax.nn.sigmoid(flat @ p["router_W"])
    chosen, admitted, used = _selection(
        scores, p["router_b"], system_idx.reshape(B * T, -1), real, d)
    picked = jnp.where(chosen, scores, 0.0)
    weights = d["route_scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    y = _relu2(flat, p["su_W"], p["sd_W"])  # the shared expert, every word, unweighted
    lo, hi = held
    # the experts held here, densely: EVERY held expert computes EVERY word
    # (the weights are stacked [held, ., .], so one product over that axis),
    # and the 0/1 selection, times the weight, decides what is added
    up = jnp.einsum("nd,edf->enf", flat, p["eu_W"])
    each = jnp.einsum("enf,efd->end", jnp.square(jnp.maximum(up, 0.0)), p["ed_W"])
    y = y + jnp.sum(weights[:, lo:hi].T[:, :, None] * each, axis=0)
    y = jnp.where(admitted[:, None], y, jnp.nan)
    return y.reshape(B, T, D), used


@functools.partial(jax.jit, static_argnames=("held", "sizes"))
def _forward(params, ids, mask, choices, held, sizes):
    """The whole forward as ONE compiled program; still a Python loop over the
    layers. Returns (x, the number of (word, layer) choices that used the tie
    rule)."""
    d = dict(sizes)
    used_total = jnp.int32(0)
    with jax.default_matmul_precision("highest"):
        x = params["E"][ids] * mask[..., None]
        depth = sum(1 for k in params if k.startswith("layer_"))
        routed = 0
        for i in range(depth):
            p = params[f"layer_{i}"]
            h = _rms_norm(x, p["norm_g"], d["rms_eps"])
            if "in_W" in p:
                y = _mamba(p, h, d)
            elif "q_W" in p:
                y = _attention(p, h, mask, d)
            else:
                y, used = _experts(p, h, mask, held, choices[routed], d)
                used_total = used_total + jnp.sum(used)
                routed += 1
            x = x + y
        x = _rms_norm(x, params["rms_f_g"], d["rms_eps"]) * mask[..., None]
    return x, used_total


def forward(params, ids, mask, held, choices, dims=None):
    """``params``: the trunk's float32 tree; ``ids`` / ``mask`` [B, T];
    ``held`` (lo, hi); ``choices`` [expert layers, B, T, top_k], the system's;
    ``dims`` the sizes (``PUBLISHED`` and a ``route_tie``). Returns [B, T, D]
    float32."""
    d = {**PUBLISHED, "route_tie": ROUTE_TIE, **(dims or {})}
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    mask = jnp.asarray(mask)
    x, used = _forward(params, jnp.asarray(ids), mask, jnp.asarray(choices),
                       tuple(int(e) for e in held), tuple(sorted(d.items())))
    routed = len(choices)
    if routed and not isinstance(used, jax.core.Tracer):
        n_choices = int(jnp.sum(mask)) * routed
        LAST_TIES.update(used=int(used), choices=n_choices)
        print(f"reference nemotron3_nano_a3b: the tie rule (|score - k-th best| <= "
              f"{d['route_tie']}) took the system's set for {int(used)} of {n_choices} "
              "(word, layer) choices", flush=True)
        if int(used) > MAX_TIE_SHARE * n_choices:
            x = x * jnp.nan  # a router that disagrees this often is not a rounding
    return x
