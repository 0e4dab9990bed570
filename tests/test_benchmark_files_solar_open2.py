"""The ``solar_open2_250b`` configuration's two files of ``benchmark/tests``
under the tier-1 suite, imported by path as ``test_benchmark_files.py``
imports the others (cases and fixtures; the files are neither moved nor
edited). A file of its own because a file is one worker's: the rehearsal of
the cell and the seven readings of the faults file are minutes, and
``test_benchmark_files.py`` was the suite's longest file before them."""

from test_benchmark_files import BENCH_TESTS, ELSEWHERE, _cases

for _name in ELSEWHERE:
    _found = _cases(BENCH_TESTS / _name)
    assert not _found.keys() & globals().keys(), (_name, sorted(_found.keys() & globals().keys()))
    globals().update(_found)
