"""The benchmark's own file checks under the tier-1 suite: every cell of
``BENCHMARK.json`` finds the files ``run.py`` will look for by name, and the
``kanana2_a3b`` configuration's operation count, readers and sizes hold (no
JAX); and the comparison that decides ``correct`` in that cell fails on each
planted fault and on the control (CPU, rehearsal widths). The cases live with
the benchmark (``benchmark/tests``: the files are neither moved nor edited)
and are imported here by path, so a later PR that adds a cell is held to its
files by the driver's suite too."""

import importlib.util
from pathlib import Path

BENCH_TESTS = Path(__file__).resolve().parent.parent / "benchmark" / "tests"


def _cases(file_name: str):
    spec = importlib.util.spec_from_file_location(f"bench_{file_name}", BENCH_TESTS / f"{file_name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return {name: obj for name, obj in vars(module).items() if name.startswith("test_")}


globals().update(_cases("test_benchmark_files"))
globals().update(_cases("test_kanana2_a3b"))
globals().update(_cases("test_kanana2_a3b_faults"))
globals().update(_cases("test_moe_bounded_share"))
