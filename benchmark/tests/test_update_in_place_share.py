"""The reader of ``update_in_place_share`` on hand-made records: with the
program's tally, and without it (the parent commit's ``runtime`` has no
``fused_update_in_place``; on a mesh the kernel gives way to XLA and the
program leaves the key out). No JAX. Runs on a CPU: ``pytest benchmark/tests``."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from common import load_module  # noqa: E402

RUNTIME = {"fused_update": "active (pallas)", "bf16_shadow": "on"}


@pytest.mark.parametrize("share,expected", [(1.0, 100.0), (0.9995, 99.95), (0.0, 0.0)])
def test_the_share_of_elements_updated_where_they_lay(share, expected):
    read = load_module("layer_metrics", "update_in_place_share").read
    tally = {"share": share, "leaves": 54, "small": 93, "xla": {}}
    assert read({"runtime": dict(RUNTIME, fused_update_in_place=tally)}) == pytest.approx(expected)


@pytest.mark.parametrize("record", [
    {}, {"runtime": None}, {"runtime": RUNTIME},  # the parent commit: no tally
    {"runtime": dict(RUNTIME, fused_update="active (xla; kernel gated off a multi-device mesh)")},
    {"runtime": dict(RUNTIME, fused_update_in_place={"leaves": 3})},
], ids=["empty", "no_runtime", "parent_commit", "mesh", "no_share"])
def test_a_program_without_the_tally_leaves_the_metric_out(record):
    assert load_module("layer_metrics", "update_in_place_share").read(record) is None


def test_the_metric_is_declared_for_the_one_chip_cells():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == "update_in_place_share")
    assert entry == {"name": "update_in_place_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels", "moves": "train_wps_chip",
                     "workloads": ["trf_train", "sm_train", "kanana2_a3b_train"]}
    one_chip = [c["name"] for c in bench["workloads"] if c["chips"] == 1]
    assert entry["workloads"] == one_chip  # on four chips the kernel gives way to XLA
