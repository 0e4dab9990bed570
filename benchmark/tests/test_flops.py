"""``flops.py`` against counts made by hand. Runs on a CPU:
``pytest benchmark/tests``."""

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_trf_forward_by_hand():
    s = config("trf")["shapes"]
    # embed mix: (4 x 768) x (3 x 768) multiply-adds
    embed = 2 * 3072 * 2304
    assert flops.embed_flops(s) == embed == 14_155_776
    # a layer: qkv 768x2304, out 768x768, ffn 768x3072 twice -> 7,077,888 MACs
    layer_macs = 768 * 2304 + 768 * 768 + 2 * 768 * 3072
    assert layer_macs == 7_077_888
    # attention against 168 words of context: scores and weighted sum
    attn = 2 * 2 * 168 * 768
    trunk = embed + 12 * (2 * layer_macs + attn)
    assert flops.trunk_forward_flops_per_word(s, 168) == trunk == 190_218_240
    # heads: tagger 768x13; parser 2 x (12*768*256 + 128*30); ner 5*768*256 + 128*17
    heads = 2 * (768 * 13) + 2 * 2 * (12 * 768 * 256 + 128 * 30) + 2 * (5 * 768 * 256 + 128 * 17)
    assert flops.heads_forward_flops_per_word(s) == heads == 11_442_944
    assert flops.forward_flops_per_word(config("trf"), 168) == trunk + heads
    assert flops.train_flops_per_word(config("trf"), 168) == 3 * (trunk + heads)
    # a longer document costs each of its words more attention, and nothing else
    assert (flops.forward_flops_per_word(config("trf"), 169)
            - flops.forward_flops_per_word(config("trf"), 168)) == 12 * 2 * 2 * 768


def test_sm_forward_by_hand():
    s = config("sm")["shapes"]
    embed = 2 * (4 * 96) * (3 * 96)
    layer = 2 * (3 * 96) * (3 * 96)  # window of 3 x 96 -> 3 pieces x 96
    assert flops.trunk_forward_flops_per_word(s, 0) == embed + 4 * layer == 884_736
    heads = 2 * (96 * 13) + 2 * 2 * (12 * 96 * 256 + 128 * 30) + 2 * (5 * 96 * 256 + 128 * 17)
    assert flops.heads_forward_flops_per_word(s) == heads == 1_447_616
    # no attention: the context does not enter
    assert flops.forward_flops_per_word(config("sm"), 0) == flops.forward_flops_per_word(config("sm"), 168)
    # the heads need more operations per word than the trunk does
    assert heads > embed + 4 * layer


def test_unknown_shapes_are_errors():
    import pytest

    with pytest.raises(ValueError):
        flops.trunk_forward_flops_per_word({"trunk": "lstm", "width": 8, "embed_tables": 1,
                                            "embed_mix_pieces": 1}, 0)
    with pytest.raises(ValueError):
        flops.heads_forward_flops_per_word({"width": 8, "heads": [{"kind": "spancat"}]})
