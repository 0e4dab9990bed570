"""The reader of ``moe_buffer_fill_share`` on hand-made records: with the
program's ``buffer_rows`` counter, and without it (the parent commit's
``moe`` block has no ``buffer_rows``; a trunk with no routed layer has no
block). No JAX. Runs on a CPU: ``pytest benchmark/tests``."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from common import load_module  # noqa: E402

ROUTED = ["kanana2_a3b_train", "nemotron3_nano_a3b_train", "solar_open2_250b_train"]
# a run of 200 expert-layer calls with 300,000 live pairs among them
BEFORE = {"assignments": 2_000_000, "assignments_held": 300_000, "dropped": 0,
          "max_expert_load": 150.0, "mean_expert_load": 93.75, "layer_calls": 200,
          "bounded_calls": 200}


@pytest.mark.parametrize("buffer_rows,expected", [
    (200 * 12_288, 12.20703125),  # every call on the bound
    (200 * 3_072, 48.828125),  # every call on the quarter tier
    (300_000, 100.0),  # buffers no larger than the live pairs
], ids=["bound", "tier", "full"])
def test_the_live_share_of_the_buffer_rows_the_calls_took(buffer_rows, expected):
    read = load_module("layer_metrics", "moe_buffer_fill_share").read
    record = {"runtime": {"moe": dict(BEFORE, buffer_rows=buffer_rows, tier_calls=0)}}
    assert read(record) == pytest.approx(expected)


@pytest.mark.parametrize("record", [
    {}, {"runtime": None}, {"runtime": {"fused_update": "active (pallas)"}},
    {"runtime": {"moe": BEFORE}},  # the parent commit: the block, not the counter
    {"runtime": {"moe": dict(BEFORE, buffer_rows=0)}},
], ids=["empty", "no_runtime", "no_moe_block", "parent_commit", "no_rows"])
def test_a_program_without_buffer_rows_leaves_the_fill_share_out(record):
    assert load_module("layer_metrics", "moe_buffer_fill_share").read(record) is None


def test_the_fill_share_is_declared_for_the_routed_cells():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == "moe_buffer_fill_share")
    assert entry == {"name": "moe_buffer_fill_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "models", "moves": "train_wps_chip",
                     "workloads": ROUTED}
    assert [m["name"] for m in bench["per_layer"]].count("moe_buffer_fill_share") == 1
    # appended after the set-up metrics; held to its place, not to being last
    assert bench["per_layer"][30] == entry
    assert {c["name"] for c in bench["workloads"]} >= set(ROUTED)
