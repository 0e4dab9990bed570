"""The benchmark's own tests under the tier-1 suite, every ``test_*.py`` of
``benchmark/tests`` (sixteen files, two of them through ``test_benchmark_files_solar_open2.py``): every cell of ``BENCHMARK.json`` finds the files
``run.py`` will look for by name; the ``kanana2_a3b`` configuration's
operation count, readers and sizes hold (no JAX); the comparison that decides
``correct`` in that cell fails on each planted fault and on the control (CPU,
rehearsal widths); and the code behind every number in the ledger
(``trace_reduce.py``, ``flops.py``, the span readers, ``trunk_check.py``) is
held to hand-made inputs. The cases live with the benchmark (the files are
neither moved nor edited) and are imported here by path, fixtures included,
so a later PR that adds a cell, or a test file beside these, is held to it
by the driver's suite too.

One case is restated here and not taken as it stands:
``test_moe_bounded_share.py`` (PR 28) holds its metric to being the LAST
entry of ``per_layer`` ("appended: nothing before it moved"), which the next
PR to append a metric (PR 31, ``update_in_place_share``) cannot keep, and a
PR that is not a ``benchmark`` PR may edit no file of ``benchmark/``. What the
line meant is kept: the entry as PR 28 wrote it, at the place PR 28 gave it.
The next ``benchmark`` PR should say so in that file (PERF.md section 7).

PR 34 (``model_config``) restates two more the same way, for the same
reason. Its cell ``nemotron3_nano_a3b_train`` is a second routed trunk and a
fourth one-chip cell, and ISSUE 34 has its name appended to the ``workloads``
lists of the routed trunk's metrics and of ``update_in_place_share``; the two
cases that pin those lists to the cells of their day
(``test_moe_bounded_share.py``: "the routed cell alone";
``test_update_in_place_share.py``: three one-chip cells, and the list equal to
EVERY one-chip cell, which any new one-chip cell breaks whichever way its PR
decides) may not be edited by a PR that is not a ``benchmark`` PR. What they
meant is kept below: the entries as their PRs wrote them, at their places,
with the new cell's name appended and nothing else moved.

PR 36 (``model_config``) appends a third routed cell and a fifth one-chip
cell, ``solar_open2_250b_train``, to the same lists, and restates one case of
PR 34's for the reason PR 34 restated PR 28's:
``test_nemotron3_nano_a3b.py`` holds its metric, its cell and its
configuration to being the LAST entries of their lists, which the next PR to
append one cannot keep. What the lines meant is kept: the entries as PR 34
wrote them, at the places PR 34 gave them (``test_solar_open2_250b.py`` holds
the new last entries, and will want the same from the PR after it)."""

import importlib.util
import json
from pathlib import Path

from _pytest.fixtures import getfixturemarker

BENCH_TESTS = Path(__file__).resolve().parent.parent / "benchmark" / "tests"


def _cases(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return {
        name: obj
        for name, obj in vars(module).items()
        if name.startswith("test_") or getfixturemarker(obj) is not None
    }


# the newest configuration's two files are imported by a file of their own
# (``test_benchmark_files_solar_open2.py``): one file is one worker's, and this
# one was already the suite's longest (PR 36)
ELSEWHERE = ("test_solar_open2_250b.py", "test_solar_open2_250b_faults.py")

for _path in sorted(BENCH_TESTS.glob("test_*.py")):
    if _path.name in ELSEWHERE:
        continue
    _found = _cases(_path)
    assert not _found.keys() & globals().keys(), (_path.name, sorted(_found.keys() & globals().keys()))
    globals().update(_found)


BENCHMARK = json.loads((BENCH_TESTS.parent.parent / "BENCHMARK.json").read_text())
ROUTED_CELLS = ["kanana2_a3b_train", "nemotron3_nano_a3b_train", "solar_open2_250b_train"]


def test_the_metric_is_declared_for_the_routed_cell_alone():
    """The routed cells, since PR 34 brought a second trunk that routes and PR 36 a third user."""
    per_layer = BENCHMARK["per_layer"]
    assert per_layer[22] == {"name": "moe_bounded_share", "unit": "%", "better": "higher",
                             "source": "program_counter", "layer": "models", "moves": "train_wps_chip",
                             "workloads": ROUTED_CELLS}
    assert [m["name"] for m in per_layer].count("moe_bounded_share") == 1
    for name in ("moe_held_share", "moe_load_imbalance"):  # PR 27's two, read from the same block
        assert next(m for m in per_layer if m["name"] == name)["workloads"] == ROUTED_CELLS


def test_the_metric_is_declared_for_the_one_chip_cells():
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == "update_in_place_share")
    assert entry == {"name": "update_in_place_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels", "moves": "train_wps_chip",
                     "workloads": ["trf_train", "sm_train"] + ROUTED_CELLS}
    one_chip = [c["name"] for c in BENCHMARK["workloads"] if c["chips"] == 1]
    assert entry["workloads"] == one_chip  # on four chips the kernel gives way to XLA


def test_the_new_cell_and_what_it_reports():
    """PR 34's case with its three ``[-1]`` as the places PR 34 gave them."""
    from common import load_cell  # benchmark/ is on the path since the files above were loaded

    cell = load_cell("nemotron3_nano_a3b_train")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("nemotron3_nano_a3b", "ewt10_16x256", 1)
    assert {m["name"] for m in cell["end_to_end"]} == {"train_wps_chip", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"ssm_live_chunk_share", "moe_held_share", "moe_load_imbalance", "moe_bounded_share",
            "update_in_place_share", "step_mfu", "device_idle_share"} <= reported
    assert "collective_share" not in reported and "kda_live_chunk_share" not in reported
    assert BENCHMARK["per_layer"][24] == {
        "name": "ssm_live_chunk_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "models", "moves": "train_wps_chip",
        "workloads": ["nemotron3_nano_a3b_train"]}
    assert BENCHMARK["workloads"][4]["name"] == "nemotron3_nano_a3b_train"
    assert BENCHMARK["configs"][3]["name"] == cell["config"]
    expected = cell["config_file"]["expect_runtime"]["1"]
    assert expected["layer_pattern"] == "MEMEM*EME" and expected["moe_dropped"] == "0"
    assert expected["moe_dispatch"] == "sorted, ragged_dot, 8 of 128 held"
    assert expected["flash_attention"][0].endswith("32 query heads on 2 key heads")
