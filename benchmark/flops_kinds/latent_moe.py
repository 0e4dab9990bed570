"""Operations of the ``latent_moe`` trunk (``spacy_ray_tpu/models/latent_moe
.py``; configuration ``kanana2_a3b``) by ``flops.py``'s contract: the matrix
products of the forward pass for ONE real word, a multiply-add two operations;
no padding, no remat, no elementwise work, no sort / gather of the dispatch.

A layer's latent attention: the four projections (q: width x heads x (nope +
rope); kv_a: width x (rank + rope); kv_b: rank x heads x (nope + v); o: heads
x v x width) and, causal, scores and weighted sum against HALF the words of
the word's own document (``context_words / 2``) at widths nope + rope and v.
A leading dense layer adds its gated FFN (three products of width x
dense_ffn). An expert layer adds the router (width x n_experts), the shared
experts (three products of width x n_shared x expert_ffn) and the routed
experts a word reaches AMONG THOSE HELD HERE: top_k x experts_held /
n_experts of them under even routing, each three products of width x
expert_ffn; never the experts the chip merely holds. The table lookup is no
product. By hand for kanana2_a3b at 168 words of context (ISSUE 27): attention
54.4 MFLOP, layer 0 129.9, an expert layer 80.9, the trunk 453.5.
"""

from typing import Any, Dict


def attention_flops(s: Dict[str, Any], context_words: float) -> float:
    d, h = s["width"], s["n_heads"]
    qk, v = s["qk_nope"] + s["qk_rope"], s["v_head"]
    weights = (d * h * qk + d * (s["kv_rank"] + s["qk_rope"])
               + s["kv_rank"] * h * (s["qk_nope"] + v) + h * v * d)
    return 2.0 * weights + 2.0 * (context_words / 2.0) * h * (qk + v)


def dense_ffn_flops(s: Dict[str, Any]) -> float:
    return 2.0 * 3 * s["width"] * s["dense_ffn"]


def expert_ffn_flops(s: Dict[str, Any]) -> float:
    d, f = s["width"], s["expert_ffn"]
    reached_here = s["top_k"] * s["experts_held"] / s["n_experts"]
    return 2.0 * (d * s["n_experts"] + 3 * d * s["n_shared"] * f + reached_here * 3 * d * f)


def trunk_forward_flops_per_word(s: Dict[str, Any], context_words: float) -> float:
    dense_layers = s["first_dense"]
    expert_layers = s["depth"] - dense_layers
    return (s["depth"] * attention_flops(s, context_words)
            + dense_layers * dense_ffn_flops(s) + expert_layers * expert_ffn_flops(s))
