"""The ``nemotron3_nano_a3b`` configuration's files: the operation count of
``flops_kinds/hybrid_ssm.py`` against a count made by hand (ISSUE 34), the
reader of the scan's counters on hand-made records, the configuration as run
against the published one, key by key, and the new cell's entries in
``BENCHMARK.json``. No JAX. Runs on a CPU: ``pytest benchmark/tests``."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402
from common import load_cell, load_module  # noqa: E402

CONFIG = json.loads((BENCH / "configs" / "nemotron3_nano_a3b.json").read_text())
BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELL = "nemotron3_nano_a3b_train"


def test_hybrid_ssm_forward_by_hand():
    s = CONFIG["shapes"]
    kind = load_module(flops.KINDS, "hybrid_ssm")
    # M: in_proj 2688 x (4096 + 6144 + 64 = 10304), out_proj 4096 x 2688 = 38.71 M products;
    # the scan: half the chunk (64 positions) x (C B^T: 8 groups x 128 + the sum over x: 4096),
    # and the state out of the chunk and in: 2 x 4096 x 128
    projections = 2688 * 10304 + 4096 * 2688
    assert projections == 38_707_200
    scan = 64 * (1024 + 4096) + 2 * 4096 * 128
    assert scan == 1_376_256  # 2.75 MFLOP
    assert kind.mamba_flops(s, 168) == 2 * (projections + scan) == 80_166_912
    # a document shorter than a chunk mixes each word with half of ITS words
    assert kind.mamba_flops(s, 100) == 2 * (projections + 50 * 5120 + 2 * 4096 * 128)
    # *: q and o 2688 x 4096 each, k and v 2688 x 256 each = 23.40 M; causal: half of 168
    # words, scores and weighted sum at width 128, 32 query heads
    weights = 2 * 2688 * 4096 + 2 * 2688 * 256
    assert weights == 23_396_352
    assert kind.attention_flops(s, 168) == 2 * weights + 2 * 84 * 32 * 256 == 48_168_960
    # E: router 2688 x 128; shared 2 x 2688 x 3712; routed 2 x 2688 x 1856 x 6 x 8 / 128
    expert = 2 * (2688 * 128 + 2 * 2688 * 3712 + 2 * 2688 * 1856 * 6 * 8 // 128)
    assert kind.expert_flops(s) == expert == 48_082_944
    trunk = 4 * 80_166_912 + 48_168_960 + 4 * expert
    assert flops.trunk_forward_flops_per_word(s, 168) == trunk == 561_168_384
    assert 0.565 < 4 * 80_166_912 / trunk < 0.575  # ISSUE 34: 57% of it in the M layers
    # heads at width 2688: tagger 13; parser 2 x (12*2688*256 + 128*30); ner 5*2688*256 + 128*17
    heads = 2 * 2688 * 13 + 2 * 2 * (12 * 2688 * 256 + 128 * 30) + 2 * (5 * 2688 * 256 + 128 * 17)
    assert flops.heads_forward_flops_per_word(s) == heads == 40_001_024
    assert flops.train_flops_per_word(CONFIG, 168) == 3 * (trunk + heads)
    assert trunk / (trunk + heads) > 0.9  # the trunk is over 90% of the counted operations
    # a longer document costs each word more attention in the one * layer, nothing else
    assert (flops.forward_flops_per_word(CONFIG, 170) - flops.forward_flops_per_word(CONFIG, 168)
            == 2 * 32 * 256)


def test_the_count_follows_the_pattern_and_counts_the_experts_reached():
    kind = load_module(flops.KINDS, "hybrid_ssm")
    s = dict(CONFIG["shapes"])
    seven = dict(s, pattern="MEMEM*E", depth=7)  # the issue's fallback cut: 3 : 3 : 1
    assert (flops.trunk_forward_flops_per_word(s, 168) - flops.trunk_forward_flops_per_word(seven, 168)
            == kind.mamba_flops(s, 168) + kind.expert_flops(s))
    whole = dict(s, experts_held=128)  # every expert here: all six choices are computed
    assert kind.expert_flops(whole) - kind.expert_flops(s) == pytest.approx(
        2 * 2 * 2688 * 1856 * 6 * (1 - 8 / 128))
    for bad in (dict(s, pattern="MEMEM*EMX"), dict(s, depth=8)):
        with pytest.raises(ValueError, match="pattern"):
            kind.trunk_forward_flops_per_word(bad, 168)


SSM = {"chunks": 6400, "live_chunks": 4800, "chunk": 128, "layers": 4}


def test_ssm_live_chunk_share_on_hand_made_records():
    read = load_module("layer_metrics", "ssm_live_chunk_share").read
    assert read({"runtime": {"ssm": SSM}}) == pytest.approx(75.0)
    assert read({"runtime": {"ssm": dict(SSM, live_chunks=6400)}}) == pytest.approx(100.0)
    assert read({"runtime": {"ssm": dict(SSM, live_chunks=0)}}) == 0.0


@pytest.mark.parametrize("record", [
    {}, {"runtime": None}, {"runtime": {"fused_update": "active (pallas)"}},  # the parent commit
    {"runtime": {"moe": {"assignments": 1}}},  # a routed trunk with no state-space layer
    {"runtime": {"ssm": {"chunks": 0, "live_chunks": 0}}}, {"runtime": {"ssm": {"chunks": 5}}},
], ids=["empty", "no_runtime", "parent_commit", "no_ssm_block", "no_chunks", "no_live_count"])
def test_a_program_without_the_scans_block_leaves_the_metric_out(record):
    assert load_module("layer_metrics", "ssm_live_chunk_share").read(record) is None


def test_every_state_space_width_as_run_is_the_published_one():
    published, as_run = CONFIG["published"], CONFIG["as_run"]
    for ours, theirs in CONFIG["as_run_is_published"].items():
        assert as_run[ours] == published[theirs], (ours, theirs)
    # the mixer's inner width is heads x head size (4096); nemotron_h does not read `expand`
    assert as_run["ssm_heads"] * as_run["ssm_head_dim"] == 4096
    assert published["norm_eps"] == published["layer_norm_epsilon"] == as_run["rms_eps"]
    assert published["mlp_hidden_act"] == "relu2" and published["n_shared_experts"] == 1
    # at the top level every published key stands under its own name; the three
    # cuts (depth, experts held, vocabulary rows) are the only values that differ
    cut = {"num_hidden_layers": 9, "n_routed_experts": 8, "vocab_size": 16384}
    for key, value in published.items():
        assert CONFIG[key] == cut.get(key, value), key
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == "nemotron3_nano_a3b")
    assert sorted(entry["reduced"]) == sorted(cut) and entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/nemotron3_nano_a3b.json"
    # the cut of the depth is the first 9 characters of the published string
    assert as_run["pattern"] == published["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert len(published["hybrid_override_pattern"]) == published["num_hidden_layers"] == 52
    assert (len(as_run["pattern"]), as_run["experts_held"], as_run["vocab_rows"]) == (9, 8, 16384)
    # the floors of a cut: a whole period and four layers, eight experts, an eighth of the rows
    assert as_run["pattern"].count("M") >= 4 and as_run["pattern"].count("E") >= 4
    assert "*" in as_run["pattern"] and as_run["experts_held"] >= 8
    assert as_run["vocab_rows"] * 8 >= published["vocab_size"]
    assert "16 chips" in CONFIG["stands_for"] and "rank 0" in CONFIG["stands_for"]
    shapes = CONFIG["shapes"]
    for key in ("pattern", "width", "ssm_heads", "ssm_head_dim", "ssm_groups", "ssm_state", "chunk",
                "n_heads", "n_kv_heads", "head_dim", "expert_ffn", "shared_ffn", "n_experts",
                "experts_held", "top_k"):
        assert shapes[key] == as_run[key], key
    assert shapes["trunk"] == "hybrid_ssm" and shapes["depth"] == len(as_run["pattern"])


def test_the_state_space_program_config_states_the_same_sizes():
    text = (BENCH.parent / CONFIG["program_config"]).read_text()
    block = text.split("[components.transformer.model]")[1].split("[components.tagger]")[0]
    stated = {}
    for line in block.strip().splitlines():
        if "=" in line and not line.startswith("@"):
            key, value = (part.strip() for part in line.split("=", 1))
            stated[key] = json.loads(value)
    assert len(stated) >= 20
    for key, value in stated.items():
        assert CONFIG["as_run"][key] == value, key
    for head in ("tagger", "parser", "ner"):  # the heads listen at the trunk's width
        listener = text.split(f"[components.{head}.model.tok2vec]")[1].split("[")[0]
        assert "width = 2688" in listener, head


def test_the_new_cell_and_what_it_reports():
    cell = load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("nemotron3_nano_a3b", "ewt10_16x256", 1)
    assert {m["name"] for m in cell["end_to_end"]} == {"train_wps_chip", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    # the scan's own metric, the routed trunk's three and the update's, read here by the
    # readers PR 27, 28 and 31 wrote; the four-chip cell's collectives are not
    assert {"ssm_live_chunk_share", "moe_held_share", "moe_load_imbalance", "moe_bounded_share",
            "update_in_place_share", "step_mfu", "device_idle_share"} <= reported
    assert "collective_share" not in reported
    entry = BENCHMARK["per_layer"][-1]
    assert entry == {"name": "ssm_live_chunk_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "models", "moves": "train_wps_chip",
                     "workloads": [CELL]}
    assert BENCHMARK["workloads"][-1]["name"] == CELL and BENCHMARK["configs"][-1]["name"] == cell["config"]
    expected = cell["config_file"]["expect_runtime"]["1"]
    assert expected["layer_pattern"] == "MEMEM*EME" and expected["moe_dropped"] == "0"
    assert expected["moe_dispatch"] == "sorted, ragged_dot, 8 of 128 held"
    assert expected["flash_attention"][0].endswith("32 query heads on 2 key heads")


def test_the_mix_differs_from_the_routed_cells_in_the_batch_size_alone():
    mine = json.loads((BENCH / "traffic" / "ewt10_16x256.json").read_text())
    theirs = json.loads((BENCH / "traffic" / "ewt10_b3k5.json").read_text())
    assert mine.pop("what") != theirs.pop("what")
    assert mine.pop("overrides") == {"training.batcher.size": 1750}
    assert theirs.pop("overrides") == {"training.batcher.size": 3500}
    assert mine == theirs
