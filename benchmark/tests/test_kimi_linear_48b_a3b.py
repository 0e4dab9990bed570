"""The ``kimi_linear_48b_a3b`` configuration's files: the operation count of
``flops_kinds/hybrid_kda_mla.py`` against a count made by hand, the reader of
the latent-attention layers' counters on hand-made records, the configuration
as run against the published one, key by key, the new cell's entries in
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

CONFIG = json.loads((BENCH / "configs" / "kimi_linear_48b_a3b.json").read_text())
BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELL = "kimi_linear_48b_a3b_train"


def test_hybrid_kda_mla_forward_by_hand():
    s = CONFIG["shapes"]
    # K, all 32 linear heads of 128 (inner 4096): q, k, v, o 2304 x 4096 each; two low-rank gates
    # 2304 x 128 + 128 x 4096 each; beta 2304 x 32 = 39.5 M products
    projections = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    assert projections == 39_460_864
    # the recurrence: half the chunk (32 positions) x 5 x 4096, three products with the state 3 x 4096 x 128
    recurrence = 32 * 5 * 4096 + 3 * 4096 * 128
    k_layer = 2 * (projections + recurrence)
    assert k_layer == 83_378_176
    # L: q 2304 x 32 x 192; kv_a 2304 x (512 + 64); kv_b 512 x 32 x (128 + 128); o 32 x 128 x 2304;
    # causal: half of 168 words, scores at 192 and weighted sum at 128, 32 heads
    weights = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 32 * 128 * 2304
    assert weights == 29_114_368
    l_layer = 2 * weights + 2 * 84 * 32 * (192 + 128)
    assert l_layer == 59_949_056
    d_layer = 2 * 3 * 2304 * 9216  # the dense gated FFN
    assert d_layer == 127_401_984
    # E: router 2304 x 256; shared 3 x 2304 x 1024; routed 3 x 2304 x 1024 x 8 x 8 / 256
    expert = 2 * (2304 * 256 + 3 * 2304 * 1024 + 3 * 2304 * 1024 * 8 * 8 // 256)
    assert expert == 18_874_368
    kind = load_module(flops.KINDS, "hybrid_kda_mla")
    trunk = 4 * k_layer + l_layer + d_layer + 4 * expert
    assert flops.trunk_forward_flops_per_word(s, 168) == trunk == 596_361_216
    assert kind.trunk_forward_flops_per_word(s, 168) == trunk
    # the shares: the four K layers 56%, L 10%, D 21%, the experts 13%
    assert 0.55 < 4 * k_layer / trunk < 0.57 and 0.09 < l_layer / trunk < 0.11
    assert 0.21 < d_layer / trunk < 0.22 and 0.12 < 4 * expert / trunk < 0.13
    # heads at width 2304: tagger 13; parser 2 x (12*2304*256 + 128*30); ner 5*2304*256 + 128*17
    heads = 2 * 2304 * 13 + 2 * 2 * (12 * 2304 * 256 + 128 * 30) + 2 * (5 * 2304 * 256 + 128 * 17)
    assert flops.heads_forward_flops_per_word(s) == heads == 34_289_408
    assert flops.train_flops_per_word(CONFIG, 168) == 3 * (trunk + heads) == 1_891_951_872
    # a longer document costs each word more in the one L layer and nothing else (K's chunk is full)
    assert (flops.forward_flops_per_word(CONFIG, 170) - flops.forward_flops_per_word(CONFIG, 168)
            == 2 * 32 * (192 + 128))


def test_the_kda_mla_count_follows_the_pattern_and_refuses_other_characters():
    kind = load_module(flops.KINDS, "hybrid_kda_mla")
    s = dict(CONFIG["shapes"])
    longer = dict(s, pattern="KDKEKELEKEKE", depth=12)
    assert (flops.trunk_forward_flops_per_word(longer, 168) - flops.trunk_forward_flops_per_word(s, 168)
            == kind._KDA.kda_flops(s, 168) + kind._KDA.expert_flops(s))
    for bad in (dict(s, pattern="KDKEKEGEKE"), dict(s, pattern="KDKEKELEK*"), dict(s, depth=9)):
        with pytest.raises(ValueError, match="pattern"):
            kind.trunk_forward_flops_per_word(bad, 168)
    with pytest.raises(ValueError, match="pattern"):  # the delta-rule file keeps to its own characters
        load_module(flops.KINDS, "hybrid_kda").trunk_forward_flops_per_word(s, 168)


MLA = {"scores": 33_554_432, "live_scores": 4_194_304, "layers": 1}


def test_latent_live_score_share_on_hand_made_records():
    read = load_module("layer_metrics", "latent_live_score_share").read
    assert read({"runtime": {"mla": MLA}}) == pytest.approx(12.5)
    assert read({"runtime": {"mla": dict(MLA, live_scores=MLA["scores"])}}) == pytest.approx(100.0)
    assert read({"runtime": {"mla": dict(MLA, live_scores=0)}}) == 0.0


@pytest.mark.parametrize("record", [
    {}, {"runtime": None}, {"runtime": {"fused_update": "active (pallas)"}},  # the parent commit
    {"runtime": {"kda": {"chunks": 10, "live_chunks": 5}}},  # a trunk with no L layer: not this metric
    {"runtime": {"mla": {"scores": 0, "live_scores": 0}}}, {"runtime": {"mla": {"scores": 5}}},
], ids=["empty", "no_runtime", "parent_commit", "kda_block_only", "no_scores", "no_live_count"])
def test_a_program_without_the_latent_attentions_block_leaves_the_metric_out(record):
    assert load_module("layer_metrics", "latent_live_score_share").read(record) is None


def _published(path: str):
    value = CONFIG["published"]
    for part in path.split("."):
        value = value[part]
    return value


def test_every_width_as_run_is_the_published_one():
    published, as_run = CONFIG["published"], CONFIG["as_run"]
    for ours, theirs in CONFIG["as_run_is_published"].items():
        assert as_run[ours] == _published(theirs), (ours, theirs)
    assert {"width", "qk_nope", "qk_rope", "v_head", "kv_rank", "kda_heads", "kda_head_dim",
            "dense_ffn", "expert_ffn", "shared_ffn", "top_k", "n_heads", "n_experts",
            "route_scale"} <= set(CONFIG["as_run_is_published"])
    assert published["num_shared_experts"] == 1 and published["first_k_dense_replace"] == 1
    assert published["mla_use_nope"] is True and published["q_lora_rank"] is None
    assert published["num_expert_group"] == published["topk_group"] == 1
    assert "kda_allow_neg_eigval" not in published and as_run["kda_neg_eigval"] is False
    # at the top level every published key stands under its own name; the three cuts (depth,
    # experts held, vocabulary rows) are the only values that differ
    cut = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}
    for key, value in published.items():
        assert CONFIG[key] == cut.get(key, value), key
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == "kimi_linear_48b_a3b")
    assert sorted(entry["reduced"]) == sorted(cut) and entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/kimi_linear_48b_a3b.json" and len(CONFIG["reduced"]) == 3
    for key in entry["reduced"]:  # no key that `reduced` names is a width
        assert not key.endswith(("_dim", "_rank")) and "intermediate" not in key, key
        assert key not in ("hidden_size", "num_experts_per_token"), key
    # the cut of the depth: the first five published layers, from the lists themselves
    lists = published["linear_attn_config"]
    assert sorted(lists["kda_layers"] + lists["full_attn_layers"]) == list(range(1, 28))
    kept = "".join(("K" if i in lists["kda_layers"] else "L")
                   + ("D" if i <= published["first_k_dense_replace"] else "E") for i in range(1, 6))
    assert as_run["pattern"] == kept == "KDKEKELEKE"
    # the floors of a cut: a whole period and four layers after the dense one, eight experts, an
    # eighth of the rows
    assert as_run["pattern"][2::2] == "KKLK" and as_run["experts_held"] >= 8
    assert as_run["vocab_rows"] * 8 >= published["vocab_size"]
    # the share: 32 chips a layer by experts; every head held
    assert published["num_experts"] // as_run["experts_held"] == 32
    assert "32 chips" in CONFIG["stands_for"] and "rank 0" in CONFIG["stands_for"]
    for key in ("kda_gate_rank", "kda_neg_eigval", "mla_use_nope", "head_dim", "scoring",
                "selection_bias", "no_lm_head", "words_not_subwords", "positions", "init", "heads",
                "optimizer", "compute", "batch", "chunk"):
        assert CONFIG["assumed"].get(key), key
    shapes = CONFIG["shapes"]
    for key in ("pattern", "width", "chunk", "n_heads", "qk_nope", "qk_rope", "v_head", "kv_rank",
                "kda_heads", "kda_head_dim", "kda_gate_rank", "dense_ffn", "expert_ffn", "shared_ffn",
                "n_experts", "experts_held", "top_k"):
        assert shapes[key] == as_run[key], key
    assert shapes["kda_heads_held"] == as_run["kda_heads"]
    assert shapes["trunk"] == "hybrid_kda_mla" and shapes["depth"] == len(as_run["pattern"])
    assert "565,312,060" in CONFIG["parameters"]


def test_the_program_config_states_the_same_sizes_and_the_published_lists():
    text = (BENCH.parent / CONFIG["program_config"]).read_text()
    block = text.split("[components.transformer.model]")[1].split("[components.transformer.model.pattern]")[0]
    stated = {}
    for line in block.strip().splitlines():
        if "=" in line and not line.startswith("@"):
            key, value = (part.strip() for part in line.split("=", 1))
            stated[key] = json.loads(value)
    assert len(stated) >= 24
    for key, value in stated.items():
        assert CONFIG["as_run"][key] == value, key
    pattern = text.split("[components.transformer.model.pattern]")[1].split("[components.tagger]")[0]
    lists = {}
    for line in pattern.strip().splitlines():
        key, value = (part.strip() for part in line.split("=", 1))
        lists[key] = json.loads(value)
    published = CONFIG["published"]
    assert lists == {"@misc": "spacy_ray_tpu.pattern_from_layer_lists.v1",
                     "kda_layers": published["linear_attn_config"]["kda_layers"],
                     "full_attn_layers": published["linear_attn_config"]["full_attn_layers"],
                     "first_k_dense_replace": published["first_k_dense_replace"],
                     "num_hidden_layers": 27, "keep_layers": CONFIG["num_hidden_layers"]}
    for head in ("tagger", "parser", "ner"):  # the heads listen at the trunk's width
        listener = text.split(f"[components.{head}.model.tok2vec]")[1].split("[")[0]
        assert "width = 2304" in listener, head
    assert "learn_rate = 0.0002" in text and "accumulate_gradient = 1" in text


def test_the_latent_attention_cell_and_what_it_reports():
    cell = load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("kimi_linear_48b_a3b", "ewt10_16x256", 1)
    assert {m["name"] for m in cell["end_to_end"]} == {"train_wps_chip", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"latent_live_score_share", "kda_live_chunk_share", "moe_held_share", "moe_load_imbalance",
            "moe_bounded_share", "moe_buffer_fill_share", "update_in_place_share", "step_mfu",
            "device_idle_share"} <= reported
    assert "collective_share" not in reported and "ssm_live_chunk_share" not in reported
    # appended: held to the places this configuration gave them, not to being last
    assert BENCHMARK["per_layer"][31] == {
        "name": "latent_live_score_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "models", "moves": "train_wps_chip", "workloads": [CELL]}
    assert BENCHMARK["workloads"][6]["name"] == CELL and BENCHMARK["configs"][5]["name"] == cell["config"]
    assert [c["name"] for c in BENCHMARK["workloads"] if c["chips"] == 4] == ["trf_train_dp4"]
    for name in ("kda_live_chunk_share", "moe_held_share", "moe_load_imbalance", "moe_bounded_share",
                 "moe_buffer_fill_share", "update_in_place_share"):
        assert next(m for m in BENCHMARK["per_layer"] if m["name"] == name)["workloads"][-1] == CELL
    assert next(m for m in BENCHMARK["end_to_end"] if m["name"] == "train_wps_chip")["workloads"][-1] == CELL
    expected = cell["config_file"]["expect_runtime"]["1"]
    assert expected["layer_pattern"] == "KDKEKELEKE" and expected["moe_dropped"] == "0"
    assert expected["moe_dispatch"] == "sorted, ragged_dot, 8 of 256 held"
    assert expected["flash_attention"][0] == "xla (causal=True, q/k width 192, v width 128"
    assert "head_share" not in expected  # every head held
    for text in (BENCHMARK["workloads"][6]["why"], BENCHMARK["configs"][5]["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text


def test_the_latent_attention_cell_rehearses_on_the_cpu():
    """``run.py --rehearse-cpu`` of the cell, as a child: the control flow of a
    chip run at tiny widths, untraced (the traced path is every cell's, and
    the rehearsals of the other cells run it). Correct, no metric, and the
    blocks the cell's own readers read, in the ``runtime`` line."""
    done = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL, "--rehearse-cpu", "--trace", "0",
         "--seconds", "4", "--seed", "2147483671"],
        capture_output=True, text=True, timeout=900, cwd=BENCH.parent)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["metrics"] == {}
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert set(line["would_report"]) == {"train_wps_chip", "setup_s"}
    assert line["compared"]["trunk_rel_err"][0] <= line["compared"]["trunk_rel_err"][1]
    assert line["compared"]["trunk_grad_rel_err"][0] <= line["compared"]["trunk_grad_rel_err"][1]
    runtime = next(l for l in done.stdout.splitlines() if l.startswith("runtime "))
    assert "'layer_pattern': 'KDKEKELEKE'" in runtime and "'moe_dropped': '0'" in runtime
    assert "'kda_scan': 'chunked 32, xla'" in runtime and "'mla': {'scores': " in runtime
    assert "'kda': {'chunks': " in runtime and "'buffer_rows': " in runtime
    assert "head_share" not in runtime
