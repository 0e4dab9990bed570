"""Every cell of ``BENCHMARK.json`` finds its files. A later PR adds a cell,
a configuration, a mix or a metric as data; this holds each such addition to
what ``run.py`` will look for by name, without JAX and without a chip. Runs on
a CPU: ``pytest benchmark/tests``."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import common  # noqa: E402
import flops  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BENCHMARK["workloads"]]
KNOWN_TRUNKS, KNOWN_HEADS = ("transformer", "cnn"), ("tagger", "transition")


@pytest.mark.parametrize("name", CELLS)
def test_cell_finds_its_files(name):
    cell = common.load_cell(name)  # configuration and mix: a missing file raises
    assert (BENCH / "reference" / f"{cell['config']}.py").is_file()
    assert (ROOT / cell["config_file"]["program_config"]).is_file()
    assert str(cell["chips"]) in cell["config_file"]["expect_runtime"]
    traffic = cell["traffic_file"]
    assert (BENCH / f"{traffic['kind']}_cell.py").is_file()
    assert (BENCH / "generators" / f"{traffic['docs']['generator']}.py").is_file()
    assert "setup_s" in {m["name"] for m in cell["end_to_end"]} and len(cell["end_to_end"]) >= 2
    assert cell["per_layer"]
    for metric in cell["per_layer"]:
        assert callable(common.load_module("layer_metrics", metric["name"]).read)


@pytest.mark.parametrize("name", CELLS)
def test_cell_has_an_operation_count(name):
    shapes = common.load_cell(name)["config_file"]["shapes"]
    kinds = [] if shapes["trunk"] in KNOWN_TRUNKS else [shapes["trunk"]]
    kinds += [h["kind"] for h in shapes["heads"] if h["kind"] not in KNOWN_HEADS]
    for kind in kinds:
        assert (BENCH / flops.KINDS / f"{kind}.py").is_file(), kind
    assert flops.train_flops_per_word({"shapes": shapes}, 160.0) > 0


def test_every_reader_has_a_metric_and_every_metric_a_cell():
    metrics = {m["name"] for m in BENCHMARK["per_layer"]}
    readers = {p.stem for p in (BENCH / "layer_metrics").glob("*.py")}
    assert readers == metrics
    for metric in BENCHMARK["per_layer"] + BENCHMARK["end_to_end"]:
        assert set(metric.get("workloads", [])) <= set(CELLS), metric["name"]


def test_at_most_a_quarter_of_the_cells_or_one_ask_for_four_chips():
    four = [c["name"] for c in BENCHMARK["workloads"] if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in BENCHMARK["workloads"])
    assert len(four) <= max(1, len(CELLS) // 4), four


def test_an_unknown_cell_is_an_error_at_once():
    with pytest.raises(common.BenchError, match="no cell 'trf_train_dp8'"):
        common.load_cell("trf_train_dp8")
