"""Operations of the ``hybrid_ssm`` trunk (``spacy_ray_tpu/models/hybrid_ssm
.py``; configuration ``nemotron3_nano_a3b``) by ``flops.py``'s contract: the
matrix products of the forward pass for ONE real word, a multiply-add two
operations; no padding, no remat, no elementwise work (the convolution, the
decays, the gate and the norms are none), no sort / gather of the dispatch.
The layers are those of ``shapes.pattern``, one character each.

``M``, a Mamba-2 mixer: the two projections (in: width x (2 x inner + 2 x
groups x state + heads), inner = heads x head_dim; out: inner x width) and the
scan's four products as the chunked form runs them: within a chunk, ``C B^T``
(groups x state) and the weighted sum over ``x`` (inner) against HALF the
chunk's positions (causal; half the document where it is shorter than a
chunk), and the state a word's chunk leaves (``x B^T``: inner x state) and the
state carried into it that the word reads (``C . S``: inner x state). ``*``,
attention with grouped keys: q and o (width x heads x head_dim each), k and v
(width x kv_heads x head_dim each), and, causal, scores and weighted sum
against HALF the words of the word's own document at head_dim, every query
head. ``E``, routed experts of the form ``W_down relu(W_up x)^2`` (two
matrices): the router (width x n_experts), the shared expert (2 x width x
shared_ffn) and the routed experts a word reaches AMONG THOSE HELD HERE: top_k
x experts_held / n_experts of them under even routing, each 2 x width x
expert_ffn; never the experts the chip merely holds. The table lookup is no
product. By hand for nemotron3_nano_a3b at 168 words of context (ISSUE 34,
which reckoned the scan against the whole chunk, 3.4 MFLOP, where its words
and this file say half, 2.75): M 80.2 MFLOP, * 48.2, E 48.1, the trunk of
MEMEM*EME 561.3, 57% of it in the M layers.
"""

from typing import Any, Dict


def mamba_flops(s: Dict[str, Any], context_words: float) -> float:
    d, inner = s["width"], s["ssm_heads"] * s["ssm_head_dim"]
    state = s["ssm_groups"] * s["ssm_state"]
    projections = d * (2 * inner + 2 * state + s["ssm_heads"]) + inner * d
    seen = min(s["chunk"], context_words) / 2.0  # positions of its chunk a word mixes with
    within = seen * (state + inner)
    carried = 2 * inner * s["ssm_state"]  # the state out of the chunk, and in
    return 2.0 * (projections + within + carried)


def attention_flops(s: Dict[str, Any], context_words: float) -> float:
    d, h, hd = s["width"], s["n_heads"], s["head_dim"]
    weights = 2 * d * h * hd + 2 * d * s["n_kv_heads"] * hd
    return 2.0 * weights + 2.0 * (context_words / 2.0) * h * 2 * hd


def expert_flops(s: Dict[str, Any]) -> float:
    d = s["width"]
    reached_here = s["top_k"] * s["experts_held"] / s["n_experts"]
    return 2.0 * (d * s["n_experts"] + 2 * d * s["shared_ffn"]
                  + reached_here * 2 * d * s["expert_ffn"])


def trunk_forward_flops_per_word(s: Dict[str, Any], context_words: float) -> float:
    pattern = s["pattern"]
    if set(pattern) - set("ME*") or len(pattern) != s["depth"]:
        raise ValueError(f"shapes.pattern {pattern!r}: {s['depth']} characters of M, E, * expected")
    return (pattern.count("M") * mamba_flops(s, context_words)
            + pattern.count("*") * attention_flops(s, context_words)
            + pattern.count("E") * expert_flops(s))
