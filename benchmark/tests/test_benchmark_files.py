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
import train_cell  # noqa: E402

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


# ``expect_runtime``: for a key one prefix, or a list of prefixes any of which
# the program's ``runtime`` report may start with (since PR 32)
XLA_AS_THE_PARENT_REPORTS = "xla (causal=True, q/k width 192, v width 128: outside the flash kernels)"


@pytest.mark.parametrize("expected,report,passes", [
    ("active (pallas)", "active (pallas)", True),
    ("active (pallas)", "active (pallas, per shard in a shard_map)", False),
    ("active (pallas", "active (pallas, per shard in a shard_map)", True),
    ("active (pallas)", "off (auto-off on cpu; SRT_PALLAS=1 forces it)", False),
    (["xla (causal=True", "active (pallas"], XLA_AS_THE_PARENT_REPORTS, True),
    (["xla (causal=True", "active (pallas"], "active (pallas)", True),
    (["xla (causal=True", "active (pallas"], "active (pallas, causal, q/k 192, v 128)", True),
    (["xla (causal=True", "active (pallas"], "reference", False),
    (["xla (causal=True", "active (pallas"], "disabled (SRT_PALLAS_FLASH=0)", False),
    (["xla (causal=True", "active (pallas"], "xla (causal=False, q/k width 64, v width 64", False),
    (["xla (causal=True", "active (pallas"], "FAILED (the compiler's words)", False),
    (["xla (causal=True", "active (pallas"], None, False),
    ([], "active (pallas)", False),
], ids=["string", "string_longer_report", "string_prefix", "string_off", "list_first", "list_second",
        "list_second_longer", "list_reference", "list_disabled", "list_other_xla", "list_failed_probe",
        "list_absent", "empty_list"])
def test_expect_runtime_takes_a_prefix_or_a_list_of_them(expected, report, passes):
    runtime = {"fused_update": "active (pallas)"}
    if report is not None:
        runtime["flash_attention"] = report
    found = train_cell.runtime_mismatches(runtime, {"flash_attention": expected,
                                                    "fused_update": "active (pallas)"})
    if passes:
        assert found == []
    else:
        assert len(found) == 1 and "flash_attention" in found[0] and repr(report) in found[0]
        allowed = [expected] if isinstance(expected, str) else expected
        assert all(repr(prefix) in found[0] for prefix in allowed)  # the message lists what was allowed


def first_prefixes(expected):
    """A report that is just the first prefix admitted for every key."""
    return {key: (allowed if isinstance(allowed, str) else allowed[0])
            for key, allowed in expected.items()}


@pytest.mark.parametrize("name", [c["name"] for c in BENCHMARK["configs"]])
def test_every_configuration_states_what_its_runtime_reports(name):
    """Each value a prefix or a list of them, and the first prefix of every
    key passes the configuration's own check."""
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == name)
    for chips, expected in common.load_json(ROOT / entry["file"])["expect_runtime"].items():
        assert chips in ("1", "4") and expected
        for key, allowed in expected.items():
            prefixes = [allowed] if isinstance(allowed, str) else allowed
            assert prefixes and all(isinstance(p, str) and p for p in prefixes), (name, key)
        assert train_cell.runtime_mismatches(first_prefixes(expected), expected) == []
        assert len(train_cell.runtime_mismatches({}, expected)) == len(expected)


def test_the_routed_cell_admits_the_xla_path_or_a_kernel_and_nothing_else():
    expected = common.load_cell("kanana2_a3b_train")["config_file"]["expect_runtime"]["1"]
    assert expected["flash_attention"] == ["xla (causal=True, q/k width 192, v width 128",
                                           "active (pallas"]
    sound = first_prefixes(expected)
    for report in (XLA_AS_THE_PARENT_REPORTS, "active (pallas)", "active (pallas, causal)"):
        assert train_cell.runtime_mismatches(dict(sound, flash_attention=report), expected) == []
    for report in ("reference", "disabled (", "off (auto-off on cpu; SRT_PALLAS=1 forces it)",
                   "not probed (no attention ran in this process)", "xla (mesh"):
        assert len(train_cell.runtime_mismatches(dict(sound, flash_attention=report), expected)) == 1
