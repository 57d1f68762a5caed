"""Each configuration's compiled program, with the Pallas kernels in
interpret mode on the CPU at logN=8: one request through the timed
path (batched executor, keyswitch plans, kernels) is correct."""
import pytest

from chipbench import checks, run
from chipbench.tests.test_run import small_cell


@pytest.mark.parametrize("workload", ["helr-paper.serial", "logreg-paper.b4"])
def test_program_in_interpret_mode(workload):
    cell = small_cell(workload)
    cell.traffic["batch"] = 1        # one request: interpret mode is slow
    setup = run.Setup(cell, 2**31 + 21)
    assert setup.ctx.engine.interpret
    _, _, kept = run.window(setup, float("inf"), 1, 1)
    got = checks.run_checks(setup, kept, cell.traffic)
    assert got["correct"], got["checks"]
