"""The reader of ``moe_bounded_share`` on hand-made records: with the
program's counter, and without it (the parent commit's ``moe`` block has no
``bounded_calls``; a trunk with no routed layer has no block). No JAX. Runs on
a CPU: ``pytest benchmark/tests``."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from common import load_module  # noqa: E402

PARENT_MOE = {"assignments": 2_000_000, "assignments_held": 300_000, "dropped": 0,
              "max_expert_load": 150.0, "mean_expert_load": 93.75, "layer_calls": 200}


@pytest.mark.parametrize("bounded_calls,expected", [(200, 100.0), (150, 75.0), (0, 0.0)])
def test_the_share_of_calls_that_took_the_bounded_path(bounded_calls, expected):
    read = load_module("layer_metrics", "moe_bounded_share").read
    record = {"runtime": {"moe": dict(PARENT_MOE, bounded_calls=bounded_calls)}}
    assert read(record) == pytest.approx(expected)


@pytest.mark.parametrize("record", [
    {}, {"runtime": None}, {"runtime": {"fused_update": "active (pallas)"}},
    {"runtime": {"moe": PARENT_MOE}},  # the parent commit: the block, not the counter
    {"runtime": {"moe": {"bounded_calls": 0, "layer_calls": 0}}},
], ids=["empty", "no_runtime", "no_moe_block", "parent_commit", "no_calls"])
def test_a_program_without_the_counter_leaves_the_metric_out(record):
    assert load_module("layer_metrics", "moe_bounded_share").read(record) is None


def test_the_metric_is_declared_for_the_routed_cell_alone():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == "moe_bounded_share")
    assert entry == {"name": "moe_bounded_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "models", "moves": "train_wps_chip",
                     "workloads": ["kanana2_a3b_train"]}
    # where PR 28 appended it; later metrics follow it (``[-1]`` until PR 32, which no PR that
    # appends a metric could keep)
    assert bench["per_layer"][22] == entry
