"""``correct`` has to be able to come out false. The control (the plain
reference in the program's place, one guarantee of the configuration
broken) and each fault a cell can have, planted under the harness's own
run, at sizes a test run can hold. The chip runs of the same at the cells'
own sizes are in PERF.md."""

import pytest

from benchmarks import testing

CELLS = testing.cells()


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_comes_out_not_correct(cell):
    result, said = testing.run_body(cell, control=True)
    assert result["correct"] is False, said[-3000:]
    broken = [k for k, c in result["compared"].items()
              if c["value"] > c["limit"]]
    assert broken, result["compared"]
    want = testing.tiny(cell).get("control_fails")
    assert want is None or want in broken, result["compared"]
    assert "NOT CORRECT" in said


# alter_answer: an answer altered where it is produced; drop_half: half of
# the batch left out. Which faults a cell can have, and the number that has
# to catch each, are in its configuration's tiny file
@pytest.mark.parametrize("cell,fault,caught_by", testing.cases("faults"))
def test_a_fault_under_the_timed_path_comes_out_not_correct(cell, fault,
                                                            caught_by):
    result, said = testing.run_body(cell, fault=fault)
    assert result["correct"] is False, said[-3000:]
    broken = {k for k, c in result["compared"].items()
              if c["value"] > c["limit"]}
    assert caught_by in broken, result["compared"]
