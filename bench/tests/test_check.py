"""The output check at smoke size on the CPU: a whole run with the
bfloat16 control, or with a planted fault under the timed path, comes
out not correct against the cell's own limits."""
import pytest

from bench import run
from bench.controls import VARIANTS
from bench.tests.test_rehearsal import cells


@pytest.mark.parametrize("name", cells())
@pytest.mark.parametrize("variant", ["bf16", "half_batch", "frozen"])
def test_control_and_faults_fail_the_check(name, variant):
    r = run.run_cell(name, 2**31 + 77, 0.0, False, smoke=True,
                     **VARIANTS[variant])
    assert r["correct"] is False, r["compared"]
    assert r["window"]["rounds"] >= 1
