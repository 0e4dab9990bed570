"""The ``solar_open2_250b`` configuration's files: the operation count of
``flops_kinds/hybrid_kda.py`` against a count made by hand, the reader of the
delta-rule layers' counters on hand-made records, the configuration as run
against the published one, key by key, the new cell's entries in
``BENCHMARK.json``, and the cell's rehearsal on the CPU. Runs on a CPU:
``pytest benchmark/tests``."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402
from common import load_cell, load_module  # noqa: E402

CONFIG = json.loads((BENCH / "configs" / "solar_open2_250b.json").read_text())
BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELL = "solar_open2_250b_train"


def test_hybrid_kda_forward_by_hand():
    s = CONFIG["shapes"]
    kind = load_module(flops.KINDS, "hybrid_kda")
    # G, the heads HELD: q, gate, o 4096 x 1024 each, k and v 4096 x 128 each = 13.63 M products;
    # causal: half of 168 words, scores and weighted sum at width 128, 8 query heads
    weights = 3 * 4096 * 1024 + 2 * 4096 * 128
    assert weights == 13_631_488
    assert kind.gated_attention_flops(s, 168) == 2 * weights + 2 * 84 * 8 * 256 == 27_607_040
    # K, 8 linear heads of 128 (inner 1024): q, k, v, o 4096 x 1024 each; two low-rank gates
    # 4096 x 128 + 128 x 1024 each; beta 4096 x 8 = 18.12 M
    projections = 4 * 4096 * 1024 + 2 * (4096 * 128 + 128 * 1024) + 4096 * 8
    assert projections == 18_120_704
    # the recurrence: half the chunk (32 positions) x (k.k + q.k + the solve's two right-hand
    # sides + the weighted sum = 5 x 1024), and three products with the state: 3 x 1024 x 128
    recurrence = 32 * 5 * 1024 + 3 * 1024 * 128
    assert recurrence == 557_056  # 1.1 MFLOP
    assert kind.kda_flops(s, 168) == 2 * (projections + recurrence) == 37_355_520
    # a document shorter than a chunk mixes each word with half of ITS words
    assert kind.kda_flops(s, 40) == 2 * (projections + 20 * 5 * 1024 + 3 * 1024 * 128)
    # E: router 4096 x 320; shared 3 x 4096 x 1280; routed 3 x 4096 x 1280 x 8 x 8 / 320
    expert = 2 * (4096 * 320 + 3 * 4096 * 1280 + 3 * 4096 * 1280 * 8 * 8 // 320)
    assert kind.expert_flops(s) == expert == 40_370_176
    trunk = 27_607_040 + 3 * 37_355_520 + 4 * expert
    assert flops.trunk_forward_flops_per_word(s, 168) == trunk == 301_154_304
    assert 0.53 < 4 * expert / trunk < 0.54 and 0.37 < 3 * 37_355_520 / trunk < 0.38
    # heads at width 4096: tagger 13; parser 2 x (12*4096*256 + 128*30); ner 5*4096*256 + 128*17
    heads = 2 * 4096 * 13 + 2 * 2 * (12 * 4096 * 256 + 128 * 30) + 2 * (5 * 4096 * 256 + 128 * 17)
    assert flops.heads_forward_flops_per_word(s) == heads == 60_943_616
    assert flops.train_flops_per_word(CONFIG, 168) == 3 * (trunk + heads) == 1_086_293_760
    # a longer document costs each word more attention in the one G layer, nothing else
    assert (flops.forward_flops_per_word(CONFIG, 170) - flops.forward_flops_per_word(CONFIG, 168)
            == 2 * 8 * 256)


def test_the_kda_count_follows_the_pattern_the_heads_held_and_the_experts_reached():
    kind = load_module(flops.KINDS, "hybrid_kda")
    s = dict(CONFIG["shapes"])
    longer = dict(s, pattern="GEKEKEKEKE", depth=10)
    assert (flops.trunk_forward_flops_per_word(longer, 168) - flops.trunk_forward_flops_per_word(s, 168)
            == kind.kda_flops(s, 168) + kind.expert_flops(s))
    whole = dict(s, heads_held=64, kda_heads_held=64)  # every head here: eight times the heads' work
    assert kind.gated_attention_flops(whole, 168) == 8 * kind.gated_attention_flops(s, 168)
    assert kind.kda_flops(whole, 168) - kind.kda_flops(s, 168) == pytest.approx(
        2 * 7 * (4 * 4096 * 1024 + 2 * 128 * 1024 + 4096 * 8 + 557_056))
    every = dict(s, experts_held=320)  # every expert here: all eight choices are computed
    assert kind.expert_flops(every) - kind.expert_flops(s) == pytest.approx(
        2 * 3 * 4096 * 1280 * 8 * (1 - 8 / 320))
    for bad in (dict(s, pattern="GEKEKEM*"), dict(s, depth=7)):
        with pytest.raises(ValueError, match="pattern"):
            kind.trunk_forward_flops_per_word(bad, 168)
    # the two kinds of pattern keep to their own files: each refuses the other's characters
    with pytest.raises(ValueError, match="pattern"):
        load_module(flops.KINDS, "hybrid_ssm").trunk_forward_flops_per_word(
            dict(s, ssm_heads=0), 168)


KDA = {"chunks": 9600, "live_chunks": 6000, "chunk": 64, "layers": 3}


def test_kda_live_chunk_share_on_hand_made_records():
    read = load_module("layer_metrics", "kda_live_chunk_share").read
    assert read({"runtime": {"kda": KDA}}) == pytest.approx(62.5)
    assert read({"runtime": {"kda": dict(KDA, live_chunks=9600)}}) == pytest.approx(100.0)
    assert read({"runtime": {"kda": dict(KDA, live_chunks=0)}}) == 0.0


@pytest.mark.parametrize("record", [
    {}, {"runtime": None}, {"runtime": {"fused_update": "active (pallas)"}},  # the parent commit
    {"runtime": {"ssm": {"chunks": 10, "live_chunks": 5}}},  # a trunk of M layers: not this metric
    {"runtime": {"kda": {"chunks": 0, "live_chunks": 0}}}, {"runtime": {"kda": {"chunks": 5}}},
], ids=["empty", "no_runtime", "parent_commit", "ssm_block_only", "no_chunks", "no_live_count"])
def test_a_program_without_the_delta_rules_block_leaves_the_metric_out(record):
    assert load_module("layer_metrics", "kda_live_chunk_share").read(record) is None


def _published(path: str):
    value = CONFIG["published"]
    for part in path.split("."):
        value = value[part]
    return value


def test_every_delta_rule_width_as_run_is_the_published_one():
    published, as_run = CONFIG["published"], CONFIG["as_run"]
    for ours, theirs in CONFIG["as_run_is_published"].items():
        assert as_run[ours] == _published(theirs), (ours, theirs)
    assert {"width", "head_dim", "kda_head_dim", "expert_ffn", "shared_ffn", "top_k", "n_heads",
            "n_kv_heads", "kda_heads", "n_experts"} <= set(CONFIG["as_run_is_published"])
    assert published["n_shared_experts"] == 1 and published["first_k_dense_replace"] == 0
    assert published["use_rope"] is False and published["use_gqa_gate"] is True
    assert published["kda_use_full_proj"] is False and published["kda_allow_neg_eigval"] is True
    # at the top level every published key stands under its own name; the six cuts (depth,
    # experts held, vocabulary rows and the three counts of heads held) are the only values
    # that differ, the linear heads inside their group, whose other keys are as published
    cut = {"num_hidden_layers": 4, "n_routed_experts": 8, "vocab_size": 24576,
           "num_attention_heads": 8, "num_key_value_heads": 1,
           "linear_attn_config": dict(published["linear_attn_config"], num_heads=8)}
    for key, value in published.items():
        assert CONFIG[key] == cut.get(key, value), key
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == "solar_open2_250b")
    assert sorted(entry["reduced"]) == sorted(cut) and entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/solar_open2_250b.json" and len(CONFIG["reduced"]) == 6
    # no key that `reduced` names is a width
    for key in entry["reduced"]:
        assert not key.endswith(("_dim", "_rank")) and "intermediate" not in key, key
        assert key not in ("hidden_size", "num_experts_per_tok"), key
    # the cut of the depth is the first period: gqa_layers says which of four is softmax
    period = published["gqa_interval"] + 1
    assert published["gqa_layers"] == list(range(0, published["num_hidden_layers"], period))
    assert as_run["pattern"] == "GE" + "KE" * published["gqa_interval"] == "GEKEKEKE"
    assert len(as_run["pattern"]) == 2 * CONFIG["num_hidden_layers"]  # a mixer and its experts
    # the floors of a cut: a whole period and four layers, eight experts, an eighth of the rows
    assert as_run["pattern"].count("E") == 4 and as_run["experts_held"] >= 8
    assert as_run["vocab_rows"] * 8 >= published["vocab_size"]
    # the shares: 40 chips a layer, the experts 40 ways, the heads 8 ways, whole key heads
    assert published["n_routed_experts"] // as_run["experts_held"] == 40
    assert as_run["n_heads"] // as_run["heads_held"] == as_run["kda_heads"] // as_run["kda_heads_held"] == 8
    assert as_run["heads_held"] * as_run["n_kv_heads"] // as_run["n_heads"] == CONFIG["num_key_value_heads"] == 1
    assert "40 chips" in CONFIG["stands_for"] and "rank 0" in CONFIG["stands_for"]
    assert "8 ways" in CONFIG["stands_for"] and "40 ways" in CONFIG["stands_for"]
    for key in ("kda_gate_rank", "gqa_gate", "scoring", "no_lm_head", "words_not_subwords",
                "positions", "init", "heads", "optimizer", "compute", "batch", "chunk"):
        assert CONFIG["assumed"].get(key), key
    shapes = CONFIG["shapes"]
    for key in ("pattern", "width", "chunk", "n_heads", "n_kv_heads", "head_dim", "heads_held",
                "kda_heads", "kda_head_dim", "kda_gate_rank", "kda_heads_held", "expert_ffn",
                "shared_ffn", "n_experts", "experts_held", "top_k"):
        assert shapes[key] == as_run[key], key
    assert shapes["trunk"] == "hybrid_kda" and shapes["depth"] == len(as_run["pattern"])
    assert "758,094,932" in CONFIG["parameters"]


def test_the_delta_rule_program_config_states_the_same_sizes():
    text = (BENCH.parent / CONFIG["program_config"]).read_text()
    block = text.split("[components.transformer.model]")[1].split("[components.tagger]")[0]
    stated = {}
    for line in block.strip().splitlines():
        if "=" in line and not line.startswith("@"):
            key, value = (part.strip() for part in line.split("=", 1))
            stated[key] = json.loads(value)
    assert len(stated) >= 24
    for key, value in stated.items():
        assert CONFIG["as_run"][key] == value, key
    for head in ("tagger", "parser", "ner"):  # the heads listen at the trunk's width
        listener = text.split(f"[components.{head}.model.tok2vec]")[1].split("[")[0]
        assert "width = 4096" in listener, head
    assert "learn_rate = 0.0002" in text and "accumulate_gradient = 1" in text


def test_the_delta_rule_cell_and_what_it_reports():
    cell = load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("solar_open2_250b", "ewt10_8x256", 1)
    assert {m["name"] for m in cell["end_to_end"]} == {"train_wps_chip", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"kda_live_chunk_share", "moe_held_share", "moe_load_imbalance", "moe_bounded_share",
            "update_in_place_share", "step_mfu", "device_idle_share"} <= reported
    assert "collective_share" not in reported and "ssm_live_chunk_share" not in reported
    entry = BENCHMARK["per_layer"][-1]
    assert entry == {"name": "kda_live_chunk_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "models", "moves": "train_wps_chip",
                     "workloads": [CELL]}
    assert BENCHMARK["workloads"][-1]["name"] == CELL and BENCHMARK["configs"][-1]["name"] == cell["config"]
    assert len(BENCHMARK["workloads"]) == 6 and len(BENCHMARK["configs"]) == 5
    assert [c["name"] for c in BENCHMARK["workloads"] if c["chips"] == 4] == ["trf_train_dp4"]
    expected = cell["config_file"]["expect_runtime"]["1"]
    assert expected["layer_pattern"] == "GEKEKEKE" and expected["moe_dropped"] == "0"
    assert expected["moe_dispatch"] == "sorted, ragged_dot, 8 of 320 held"
    assert expected["head_share"] == "8 of 64 query, 1 of 8 key, 8 of 64 linear heads, rank 0"
    assert expected["flash_attention"][0].endswith("8 query heads on 1 key heads")
    assert 1 <= len(BENCHMARK["workloads"][-1]["why"]) <= 200 and "\n" not in BENCHMARK["workloads"][-1]["why"]
    assert 1 <= len(BENCHMARK["configs"][-1]["why"]) <= 200


def test_the_8x256_mix_differs_from_16x256_in_the_batch_size_alone():
    mine = json.loads((BENCH / "traffic" / "ewt10_8x256.json").read_text())
    theirs = json.loads((BENCH / "traffic" / "ewt10_16x256.json").read_text())
    assert mine.pop("what") != theirs.pop("what")
    assert mine.pop("overrides") == {"training.batcher.size": 920}
    assert theirs.pop("overrides") == {"training.batcher.size": 1750}
    assert mine == theirs


def test_the_delta_rule_cell_rehearses_on_the_cpu():
    """``run.py --rehearse-cpu`` of the cell, as a child: the control flow of a
    chip run at tiny widths. Correct, no metric, and the readers that would report."""
    done = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL, "--rehearse-cpu", "--trace", "1",
         "--seconds", "4", "--seed", "2147483659"],
        capture_output=True, text=True, timeout=900, cwd=BENCH.parent)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["metrics"] == {}
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert {"kda_live_chunk_share", "moe_held_share", "moe_load_imbalance", "moe_bounded_share",
            "train_step_ms"} <= set(line["would_report"])
    assert line["compared"]["trunk_rel_err"][0] <= line["compared"]["trunk_rel_err"][1]
    assert line["compared"]["trunk_grad_rel_err"][0] <= line["compared"]["trunk_grad_rel_err"][1]
    runtime = next(l for l in done.stdout.splitlines() if l.startswith("runtime "))
    assert "'kda_scan': 'chunked 32, xla'" in runtime and "'moe_dropped': '0'" in runtime
    assert "'head_share': '4 of 8 query, 1 of 2 key, 4 of 8 linear heads, rank 0'" in runtime
