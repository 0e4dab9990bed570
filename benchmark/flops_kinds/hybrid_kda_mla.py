"""Operations of the ``hybrid_ssm`` trunk where its pattern is made of ``K``,
``L``, ``D`` and ``E`` (``spacy_ray_tpu/models/hybrid_ssm.py``; configuration
``kimi_linear_48b_a3b``) by ``flops.py``'s contract: the matrix products of
the forward pass for ONE real word, a multiply-add two operations; no
padding, no remat, no elementwise work, no sort / gather of the dispatch. The
layers are those of ``shapes.pattern``, one character each (a published
layer is two: its mixer and its FFN).

``K`` and ``E`` are counted by ``hybrid_kda.py``'s functions, as they stand
(``kda_heads_held`` is every head here); ``L`` by ``latent_moe.py``'s
attention (the four projections, and, causal, scores at 192 and weighted sum
at 128 against HALF the words of the word's own document: the rotation it
lacks is no product either way); ``D`` by ``latent_moe.py``'s dense FFN
(three products of width x dense_ffn). By hand for kimi_linear_48b_a3b at 168
words of context: K 83.4 MFLOP, L 59.9, D 127.4, E 18.9, the trunk of
KDKEKELEKE 596.4: the four K layers 56%, L 10%, D 21%, the four expert blocks
13%.
"""

from typing import Any, Dict

from common import load_module

_KDA = load_module("flops_kinds", "hybrid_kda")
_LATENT = load_module("flops_kinds", "latent_moe")


def trunk_forward_flops_per_word(s: Dict[str, Any], context_words: float) -> float:
    pattern = s["pattern"]
    if set(pattern) - set("KLDE") or len(pattern) != s["depth"]:
        raise ValueError(f"shapes.pattern {pattern!r}: {s['depth']} characters of K, L, D, E expected")
    return (pattern.count("K") * _KDA.kda_flops(s, context_words)
            + pattern.count("L") * _LATENT.attention_flops(s, context_words)
            + pattern.count("D") * _LATENT.dense_ffn_flops(s)
            + pattern.count("E") * _KDA.expert_flops(s))
