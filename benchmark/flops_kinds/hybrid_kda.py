"""Operations of the ``hybrid_ssm`` trunk where its pattern is made of ``G``,
``K`` and ``E`` (``spacy_ray_tpu/models/hybrid_ssm.py`` with ``models/delta_
attention.py``; configuration ``solar_open2_250b``) by ``flops.py``'s
contract: the matrix products of the forward pass for ONE real word, a
multiply-add two operations; no padding, no remat, no elementwise work (the
convolutions, the decays' exponentials, the gates' sigmoids and the norms are
none), no sort / gather of the dispatch. The layers are those of
``shapes.pattern``, one character each (a published layer is two: its mixer
and its expert block).

``G``, gated attention with grouped keys, THE HEADS HELD HERE: q, the gate
and o (width x heads_held x head_dim each), k and v (width x the held
key/value heads x head_dim each) and, causal, scores and weighted sum against
HALF the words of the word's own document at head_dim, every held query head.
``K``, a delta-rule mixer, the linear heads held (inner = kda_heads_held x
kda_head_dim): q, k, v, o (width x inner each), the two low-rank gates (width
x rank + rank x inner each), beta (width x kda_heads_held) and the
recurrence as the chunked form runs it: within a chunk, against HALF the
chunk's positions (half the document where it is shorter), the decayed
products of k with k and of q with k (inner each), the triangular solve's
substitution for both right-hand sides (2 x inner) and the weighted sum over
the corrected values (inner); and the three products with the carried state
(inner x kda_head_dim each): the correction the state gives, what the word
reads of it, what the word leaves in it. ``E``, routed experts of the form
``W_down (silu(W_gate x) * W_up x)`` (three matrices): the router (width x
n_experts), the shared expert (3 x width x shared_ffn) and the routed experts
a word reaches AMONG THOSE HELD HERE: top_k x experts_held / n_experts of them
under even routing, each 3 x width x expert_ffn; never the experts the chip
merely holds. The table lookup is no product. By hand for solar_open2_250b at
168 words of context: G 27.6 MFLOP, K 37.4 (the recurrence 1.1 of it), E 40.4,
the trunk of GEKEKEKE 301.2, 54% of it in the expert blocks and 37% in the K
layers.
"""

from typing import Any, Dict


def gated_attention_flops(s: Dict[str, Any], context_words: float) -> float:
    d, h, hd = s["width"], s["heads_held"], s["head_dim"]
    kv = s["n_kv_heads"] * h // s["n_heads"]  # the key/value heads those query heads read
    weights = 3 * d * h * hd + 2 * d * kv * hd
    return 2.0 * weights + 2.0 * (context_words / 2.0) * h * 2 * hd


def kda_flops(s: Dict[str, Any], context_words: float) -> float:
    d, heads, hd, rank = s["width"], s["kda_heads_held"], s["kda_head_dim"], s["kda_gate_rank"]
    inner = heads * hd
    projections = 4 * d * inner + 2 * (d * rank + rank * inner) + d * heads
    seen = min(s["chunk"], context_words) / 2.0  # positions of its chunk a word mixes with
    within = seen * 5 * inner
    carried = 3 * inner * hd
    return 2.0 * (projections + within + carried)


def expert_flops(s: Dict[str, Any]) -> float:
    d = s["width"]
    reached_here = s["top_k"] * s["experts_held"] / s["n_experts"]
    return 2.0 * (d * s["n_experts"] + 3 * d * s["shared_ffn"]
                  + reached_here * 3 * d * s["expert_ffn"])


def trunk_forward_flops_per_word(s: Dict[str, Any], context_words: float) -> float:
    pattern = s["pattern"]
    if set(pattern) - set("GKE") or len(pattern) != s["depth"]:
        raise ValueError(f"shapes.pattern {pattern!r}: {s['depth']} characters of G, K, E expected")
    return (pattern.count("G") * gated_attention_flops(s, context_words)
            + pattern.count("K") * kda_flops(s, context_words)
            + pattern.count("E") * expert_flops(s))
