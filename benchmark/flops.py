"""Operations the algorithm needs, from the configuration's shapes: the
numerator of ``step_mfu`` (``model_flops_util`` until PR 32). Kept with the
benchmark so that no change to the program can move it. One multiply-add is
two operations.

What counts: the matrix products of the forward pass for one REAL word, and
twice that again for the backward pass. What does not: padding, recomputation
under remat, the one-hot products that stand in for gathers, elementwise work,
the optimizer. A routed layer counts the experts a word reaches among those
held here, never the experts it holds. So the utilization it gives is the
share of the chip's peak spent on work the model needs, and padding and remat
show as a LOW share.

``shapes`` is the ``shapes`` object of ``benchmark/configs/<config>.json``;
``context_words`` is how many words a word attends to: the words of its own
document, so the mean document length weighted by words (sum L^2 / sum L),
which the harness counts from the masks of the window's batches.

The trunks ``transformer`` and ``cnn`` and the heads ``tagger`` and
``transition`` are counted here. Any other ``shapes.trunk`` or head ``kind``
is counted by ``benchmark/flops_kinds/<kind>.py``, found by that name: a file
a later PR adds, with ``trunk_forward_flops_per_word(shapes, context_words)``
and/or ``head_forward_flops_per_word(shapes, head)``, each the forward of one
real word under the rule above. A kind with no such file is a ``BenchError``
naming the file.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

from common import BenchError, load_module

KINDS = "flops_kinds"


def embed_flops(s: Dict[str, Any]) -> float:
    """MultiHashEmbed's mix: concat of the tables' widths -> pieces x width."""
    d = s["width"]
    return 2.0 * (s["embed_tables"] * d) * (s["embed_mix_pieces"] * d)


def counted_elsewhere(kind: str, function: str) -> Callable[..., float]:
    """``function`` of ``benchmark/flops_kinds/<kind>.py``."""
    count = getattr(load_module(KINDS, kind), function, None)
    if count is None:
        raise BenchError(f"benchmark/{KINDS}/{kind}.py defines no {function}")
    return count


def trunk_forward_flops_per_word(s: Dict[str, Any], context_words: float) -> float:
    if s["trunk"] not in ("transformer", "cnn"):
        count = counted_elsewhere(s["trunk"], "trunk_forward_flops_per_word")
        return float(count(s, context_words))
    d = s["width"]
    if s["trunk"] == "transformer":
        ffn = s["ffn_mult"] * d
        # qkv 3d^2, out d^2, two ffn products d*ffn each
        per_layer = 2.0 * (4 * d * d + 2 * d * ffn)
        # scores and weighted sum against the words of the same document
        per_layer += 2.0 * 2 * context_words * d
        return embed_flops(s) + s["depth"] * per_layer
    window = 2 * s["window_size"] + 1
    per_layer = 2.0 * (window * d) * (s["maxout_pieces"] * d)
    return embed_flops(s) + s["depth"] * per_layer


def heads_forward_flops_per_word(s: Dict[str, Any]) -> float:
    total = 0.0
    for head in s["heads"]:
        if head["kind"] == "tagger":
            total += 2.0 * s["width"] * head["n_out"]
        elif head["kind"] == "transition":
            # one maxout over the state's feature tokens, one output layer,
            # once per transition; `states_per_word` transitions per word
            hidden = head["hidden_width"] * head["maxout_pieces"]
            per_state = 2.0 * (head["n_feats"] * s["width"] * hidden
                               + head["hidden_width"] * head["n_out"])
            total += head["states_per_word"] * per_state
        else:
            count = counted_elsewhere(head["kind"], "head_forward_flops_per_word")
            total += float(count(s, head))
    return total


def forward_flops_per_word(config_file: Dict[str, Any], context_words: float) -> float:
    s = config_file["shapes"]
    return trunk_forward_flops_per_word(s, context_words) + heads_forward_flops_per_word(s)


def train_flops_per_word(config_file: Dict[str, Any], context_words: float) -> float:
    """Forward, and a backward that costs two forwards."""
    return 3.0 * forward_flops_per_word(config_file, context_words)
