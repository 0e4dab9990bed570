"""``flops.py`` against counts made by hand. Runs on a CPU:
``pytest benchmark/tests``."""

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import pytest  # noqa: E402

import common  # noqa: E402
import flops  # noqa: E402
from common import load_module  # noqa: E402


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


# (configuration, train_wps_chip, words of context) -> the metric as PR 25's
# flops.py gave it (computed with that file, before kinds moved into files)
FIXED_RECORDS = [
    ("trf", 29855.0, 171.37, 605356247.04, 9.174066373288934),
    ("sm", 26369.0, 171.37, 6997056.0, 0.09365754805279188),
]


@pytest.mark.parametrize("name,wps,context,per_word,expected", FIXED_RECORDS)
def test_step_mfu_of_a_fixed_record_did_not_move(name, wps, context, per_word, expected):
    record = {"kind": "train", "train_wps_chip": wps, "config": config(name),
              "window": {"attention_context_words": context}, "device_kind": "TPU v5 lite"}
    assert flops.train_flops_per_word(config(name), context) == per_word
    assert load_module("layer_metrics", "step_mfu").read(record) == expected


STUB_KIND = '''
def trunk_forward_flops_per_word(shapes, context_words):
    return 2.0 * shapes["state"] * shapes["state"] * shapes["depth"] + context_words


def head_forward_flops_per_word(shapes, head):
    return 2.0 * shapes["state"] * head["n_spans"]
'''


def test_a_new_kind_is_a_file_and_its_absence_an_error(tmp_path, monkeypatch):
    """A trunk or head that ``flops.py`` does not know is counted by
    ``benchmark/flops_kinds/<kind>.py``, added with no edit to ``flops.py``;
    no such file is a ``BenchError`` that names it."""
    shapes = {"trunk": "lstm", "state": 8, "depth": 3,
              "heads": [{"kind": "spancat", "n_spans": 5}, {"kind": "tagger", "n_out": 13}],
              "width": 8}
    monkeypatch.setattr(common, "ROOT", tmp_path)
    monkeypatch.setattr(common, "BENCH", tmp_path / "benchmark")
    with pytest.raises(common.BenchError, match="benchmark/flops_kinds/lstm.py"):
        flops.trunk_forward_flops_per_word(shapes, 0)
    with pytest.raises(common.BenchError, match="benchmark/flops_kinds/spancat.py"):
        flops.heads_forward_flops_per_word(shapes)
    kinds = tmp_path / "benchmark" / "flops_kinds"
    kinds.mkdir(parents=True)
    (kinds / "lstm.py").write_text(STUB_KIND)
    (kinds / "spancat.py").write_text(STUB_KIND)
    assert flops.trunk_forward_flops_per_word(shapes, 7) == 2 * 8 * 8 * 3 + 7
    assert flops.heads_forward_flops_per_word(shapes) == 2 * 8 * 5 + 2 * 8 * 13
    assert flops.train_flops_per_word({"shapes": shapes}, 7) == 3 * (391 + 288)
    # a file that counts trunks only is no count of a head
    (kinds / "spancat.py").write_text("def trunk_forward_flops_per_word(s, c):\n    return 0.0\n")
    with pytest.raises(common.BenchError, match="head_forward_flops_per_word"):
        flops.heads_forward_flops_per_word(shapes)
    # the kinds counted in flops.py never look for a file
    assert flops.trunk_forward_flops_per_word(config("sm")["shapes"], 0) == 884_736
