"""The ``kimi_linear_48b_a3b`` configuration's main file of ``benchmark/tests``
(``test_kimi_linear_48b_a3b.py``: the operation count, the reader, the
configuration against the published one, the cell's entries) under the
tier-1 suite, imported by path as ``test_benchmark_files.py`` imports the
others (the file is neither moved nor edited). A file of its own because a
file is one worker's.

Every case but one: the cell's CPU rehearsal (``run.py --rehearse-cpu`` as a
child process) stays with ``pytest benchmark/tests``. Beside the suite's six
workers it ran 12 minutes and more where it runs one alone, and it pushed
``solar_open2_250b``'s own rehearsal, which ran beside it, past that case's
15-minute limit and the suite past its own; the harness's control flow it
checks is every cell's, and the other cells' rehearsals run it."""

from test_benchmark_files import BENCH_TESTS, KIMI_LINEAR, _cases

IN_ITS_OWN_RUN = "test_the_latent_attention_cell_rehearses_on_the_cpu"

_found = _cases(BENCH_TESTS / KIMI_LINEAR[0])
assert IN_ITS_OWN_RUN in _found
del _found[IN_ITS_OWN_RUN]
assert not _found.keys() & globals().keys(), sorted(_found.keys() & globals().keys())
globals().update(_found)
