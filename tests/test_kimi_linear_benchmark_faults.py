"""The ``kimi_linear_48b_a3b`` configuration's faults file of
``benchmark/tests`` (``test_kimi_linear_48b_a3b_faults.py``: four faults
planted in the program and the control, each put through the harness's own
comparison) under the tier-1 suite, imported by path as
``test_benchmark_files.py`` imports the others. A file of its own because a
file is one worker's.

Two of its cases: the control and the fault of this configuration's own
switch (the rotary term left on in ``L``). The rest stay with ``pytest
benchmark/tests``: the program-as-it-is reading compiles the trunk's and the
reference's gradients (55 s alone, 175 s beside the suite's six workers),
``tests/test_kimi_linear_trunk.py`` compares the program's forward and
gradient with the reference already, and the other three faults, 38 s
beside the workers, fail the same comparison by the same path."""

import pytest

from test_benchmark_files import BENCH_TESTS, KIMI_LINEAR, _cases

IN_ITS_OWN_RUN = "test_the_latent_attention_program_as_it_is_passes"
FAULTS = "test_a_fault_planted_in_the_latent_attention_trunk_and_the_control_fail_the_comparison"
HERE = ["rotary left on in L", "control"]

_found = _cases(BENCH_TESTS / KIMI_LINEAR[1])
del _found[IN_ITS_OWN_RUN]
_each_fault = _found.pop(FAULTS)
assert set(HERE) <= set(_each_fault.pytestmark[0].args[1])
assert not _found.keys() & globals().keys(), sorted(_found.keys() & globals().keys())
globals().update(_found)


@pytest.mark.parametrize("name", HERE)
def test_a_fault_planted_in_the_latent_attention_trunk_and_the_control_fail_the_comparison(name):
    _each_fault(name)
