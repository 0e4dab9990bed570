"""The benchmark's own tests under the tier-1 suite, every ``test_*.py`` of
``benchmark/tests`` (eight files): every cell of ``BENCHMARK.json`` finds the files
``run.py`` will look for by name; the ``kanana2_a3b`` configuration's
operation count, readers and sizes hold (no JAX); the comparison that decides
``correct`` in that cell fails on each planted fault and on the control (CPU,
rehearsal widths); and the code behind every number in the ledger
(``trace_reduce.py``, ``flops.py``, the span readers, ``trunk_check.py``) is
held to hand-made inputs. The cases live with the benchmark (the files are
neither moved nor edited) and are imported here by path, fixtures included,
so a later PR that adds a cell, or a test file beside these, is held to it
by the driver's suite too."""

import importlib.util
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


for _path in sorted(BENCH_TESTS.glob("test_*.py")):
    _found = _cases(_path)
    assert not _found.keys() & globals().keys(), (_path.name, sorted(_found.keys() & globals().keys()))
    globals().update(_found)
