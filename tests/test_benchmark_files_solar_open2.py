"""The ``solar_open2_250b`` configuration's two files of ``benchmark/tests``
under the tier-1 suite, imported by path as ``test_benchmark_files.py``
imports the others (cases and fixtures; the files are neither moved nor
edited). A file of its own because a file is one worker's: the rehearsal of
the cell and the seven readings of the faults file are minutes, and
``test_benchmark_files.py`` was the suite's longest file before them.

One case is restated here and not taken as it stands, for the reason
``test_benchmark_files.py`` restates the pattern trunk's:
``test_solar_open2_250b.py::test_the_delta_rule_cell_and_what_it_reports``
holds ``kda_live_chunk_share`` to being the LAST entry of ``per_layer``,
which stops holding once the four metrics of set-up (``setup_program_s``,
``setup_pipeline_s``, ``setup_lower_s``, ``setup_compile_s``) are appended
after it, and the files of ``benchmark/`` are edited only by a change to the
benchmark itself. What the line meant is kept: the entry as it was written,
at the place it was given (``per_layer[25]``), everything else as it
stands. Since the configuration ``kimi_linear_48b_a3b`` appended its cell
after this one, to ``kda_live_chunk_share``'s list among others, the case
holds this cell, its configuration and that entry to the places they were
given, the new cell's name appended to the entry's list, and no longer to
being the last or to the number of cells."""

from test_benchmark_files import BENCH_TESTS, BENCHMARK, ELSEWHERE, _cases

CELL = "solar_open2_250b_train"

for _name in ELSEWHERE:
    _found = _cases(BENCH_TESTS / _name)
    assert not _found.keys() & globals().keys(), (_name, sorted(_found.keys() & globals().keys()))
    globals().update(_found)


def test_the_delta_rule_cell_and_what_it_reports():
    """The case with its ``per_layer[-1]`` as the place the entry was given."""
    from common import load_cell  # benchmark/ is on the path since the files above were loaded

    cell = load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("solar_open2_250b", "ewt10_8x256", 1)
    assert {m["name"] for m in cell["end_to_end"]} == {"train_wps_chip", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"kda_live_chunk_share", "moe_held_share", "moe_load_imbalance", "moe_bounded_share",
            "update_in_place_share", "step_mfu", "device_idle_share"} <= reported
    assert "collective_share" not in reported and "ssm_live_chunk_share" not in reported
    entry = BENCHMARK["per_layer"][25]
    assert entry == {"name": "kda_live_chunk_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "models", "moves": "train_wps_chip",
                     "workloads": [CELL, "kimi_linear_48b_a3b_train"]}
    assert BENCHMARK["workloads"][5]["name"] == CELL and BENCHMARK["configs"][4]["name"] == cell["config"]
    assert [c["name"] for c in BENCHMARK["workloads"] if c["chips"] == 4] == ["trf_train_dp4"]
    expected = cell["config_file"]["expect_runtime"]["1"]
    assert expected["layer_pattern"] == "GEKEKEKE" and expected["moe_dropped"] == "0"
    assert expected["moe_dispatch"] == "sorted, ragged_dot, 8 of 320 held"
    assert expected["head_share"] == "8 of 64 query, 1 of 8 key, 8 of 64 linear heads, rank 0"
    assert expected["flash_attention"][0].endswith("8 query heads on 1 key heads")
    assert 1 <= len(BENCHMARK["workloads"][5]["why"]) <= 200 and "\n" not in BENCHMARK["workloads"][5]["why"]
    assert 1 <= len(BENCHMARK["configs"][4]["why"]) <= 200
