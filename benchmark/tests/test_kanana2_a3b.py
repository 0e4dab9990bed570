"""The ``kanana2_a3b`` configuration's files: the operation count of
``flops_kinds/latent_moe.py`` against a count made by hand (ISSUE 27), the two
readers of the program's routing counters on a hand-made record, and the
configuration as run against the published one, key by key. No JAX. Runs on a
CPU: ``pytest benchmark/tests``."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402
from common import load_module  # noqa: E402

CONFIG = json.loads((BENCH / "configs" / "kanana2_a3b.json").read_text())
M = 1e6


def test_latent_moe_forward_by_hand():
    s = CONFIG["shapes"]
    kind = load_module(flops.KINDS, "latent_moe")
    # projections: q 2048x6144, kv_a 2048x576, kv_b 512x8192, o 4096x2048 = 26.35 M products
    weights = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert weights == 26_345_472
    # causal: half of 168 words, scores at width 192 and the weighted sum at 128, 32 heads
    attention = 2 * weights + 2 * 84 * 32 * (192 + 128)
    assert kind.attention_flops(s, 168) == attention == 54_411_264
    dense = 2 * 3 * 2048 * 6144
    assert kind.dense_ffn_flops(s) == dense == 75_497_472
    # router 2048x128; shared 3 x 2048 x 1536; routed 3 x 2048 x 768 x 6 x 16/128
    expert = 2 * (2048 * 128 + 3 * 2048 * 1536 + 3 * 2048 * 768 * 6 * 16 // 128)
    assert kind.expert_ffn_flops(s) == expert == 26_476_544
    trunk = 5 * attention + dense + 4 * expert
    assert flops.trunk_forward_flops_per_word(s, 168) == trunk == 453_459_968
    # heads at width 2048: tagger 13; parser 2 x (12*2048*256 + 128*30); ner 5*2048*256 + 128*17
    heads = 2 * 2048 * 13 + 2 * 2 * (12 * 2048 * 256 + 128 * 30) + 2 * (5 * 2048 * 256 + 128 * 17)
    assert flops.heads_forward_flops_per_word(s) == heads == 30_481_664
    assert flops.train_flops_per_word(CONFIG, 168) == 3 * (trunk + heads)
    assert 1.44e9 < flops.train_flops_per_word(CONFIG, 168) < 1.46e9  # ISSUE 27: 1.45 GFLOP
    # a longer document costs each word more attention in every layer, nothing else
    assert (flops.forward_flops_per_word(CONFIG, 170) - flops.forward_flops_per_word(CONFIG, 168)
            == 5 * 2 * 32 * 320)


def test_the_experts_a_word_reaches_here_are_counted_not_the_experts_held():
    kind = load_module(flops.KINDS, "latent_moe")
    s = dict(CONFIG["shapes"])
    whole = dict(s, experts_held=128)  # every expert here: all six choices are computed
    assert kind.expert_ffn_flops(whole) - kind.expert_ffn_flops(s) == pytest.approx(
        2 * 3 * 2048 * 768 * 6 * (1 - 16 / 128))


MOE = {"assignments": 2_000_000, "assignments_held": 300_000, "dropped": 0,
       "max_expert_load": 150.0, "mean_expert_load": 93.75, "layer_calls": 200}


@pytest.mark.parametrize("name,expected", [("moe_held_share", 15.0), ("moe_load_imbalance", 1.6)])
def test_routing_readers_on_a_hand_made_record(name, expected):
    read = load_module("layer_metrics", name).read
    assert read({"runtime": {"moe": MOE}}) == pytest.approx(expected)
    # a program without the counters (the parent commit, a trunk with no routed layer)
    for record in ({}, {"runtime": None}, {"runtime": {"fused_update": "active (pallas)"}},
                   {"runtime": {"moe": {"assignments": 0, "assignments_held": 0}}}):
        assert read(record) is None


def test_every_width_as_run_is_the_published_one():
    published, as_run = CONFIG["published"], CONFIG["as_run"]
    for ours, theirs in CONFIG["as_run_is_published"].items():
        assert as_run[ours] == published[theirs], (ours, theirs)
    assert as_run["qk_nope"] + as_run["qk_rope"] == published["qk_head_dim"]
    # at the top level every published key stands under its own name; the three
    # cuts (depth, experts held, vocabulary rows) are the only numbers that differ
    cut = {"num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 16032}
    for key, value in published.items():
        assert CONFIG[key] == cut.get(key, value), key
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "kanana2_a3b")
    assert sorted(entry["reduced"]) == sorted(cut) and entry["source"] == CONFIG["source"]
    assert (as_run["depth"], as_run["experts_held"], as_run["vocab_rows"]) == (5, 16, 16032)
    # the floors of a cut: four expert layers, eight experts, an eighth of the rows
    assert as_run["depth"] - as_run["first_dense"] >= 4 and as_run["experts_held"] >= 8
    assert as_run["vocab_rows"] * 8 >= published["vocab_size"]
    shapes = CONFIG["shapes"]
    for key in ("width", "n_heads", "qk_nope", "qk_rope", "v_head", "kv_rank", "dense_ffn",
                "expert_ffn", "n_experts", "experts_held", "top_k", "n_shared", "first_dense", "depth"):
        assert shapes[key] == as_run[key], key


def test_the_program_config_states_the_same_sizes():
    text = (BENCH.parent / CONFIG["program_config"]).read_text()
    block = text.split("[components.transformer.model]")[1].split("[components.tagger]")[0]
    stated = {}
    for line in block.strip().splitlines():
        if "=" in line and not line.startswith("@"):
            key, value = (part.strip() for part in line.split("=", 1))
            stated[key] = json.loads(value)
    for key, value in stated.items():
        assert CONFIG["as_run"][key] == value, key
