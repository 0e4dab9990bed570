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
by the driver's suite too.

One case is restated here and not taken as it stands:
``test_moe_bounded_share.py`` (PR 28) holds its metric to being the LAST
entry of ``per_layer`` ("appended: nothing before it moved"), which the next
PR to append a metric (PR 31, ``update_in_place_share``) cannot keep, and a
PR that is not a ``benchmark`` PR may edit no file of ``benchmark/``. What the
line meant is kept: the entry as PR 28 wrote it, at the place PR 28 gave it.
The next ``benchmark`` PR should say so in that file (PERF.md section 7)."""

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


for _path in sorted(BENCH_TESTS.glob("test_*.py")):
    _found = _cases(_path)
    assert not _found.keys() & globals().keys(), (_path.name, sorted(_found.keys() & globals().keys()))
    globals().update(_found)


def test_the_metric_is_declared_for_the_routed_cell_alone():
    per_layer = json.loads((BENCH_TESTS.parent.parent / "BENCHMARK.json").read_text())["per_layer"]
    assert per_layer[22] == {"name": "moe_bounded_share", "unit": "%", "better": "higher",
                             "source": "program_counter", "layer": "models", "moves": "train_wps_chip",
                             "workloads": ["kanana2_a3b_train"]}
    assert [m["name"] for m in per_layer].count("moe_bounded_share") == 1
