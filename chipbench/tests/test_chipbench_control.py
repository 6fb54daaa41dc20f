"""Each cell's lower-precision control, in the program's place, fails the
cell's comparison: bfloat16 for the float32 cells, the program's own
unrefined float32 path for the float64-refined cell."""
import pytest

from chipbench import harness

# the compared numbers the control must fail (the others may pass)
FAILS = {"lung2.sweep.rhs1": {"fwd_err"},
         "lung2.solve.refined": {"fwd_err", "resid"},
         "poisson2d.pcg": {"resid", "hist_gap"}}


@pytest.mark.parametrize("cell", sorted(FAILS))
def test_control_is_not_correct(cell, off_chip):
    for seed in (1, 2, 3):
        correct, checks = off_chip.run_control(cell, seed, 2)
        assert correct is False
        failed = {name for name, v, lim in checks if not v <= lim}
        assert FAILS[cell] <= failed, (seed, checks)


@pytest.mark.parametrize("cell", sorted(FAILS))
def test_program_passes_where_its_control_fails(cell, tiny_configs):
    cell_ = harness.load_cell(cell)
    cfg = tiny_configs[cell_.workload["config"]]
    st = cell_.driver.setup(cfg, cell_.workload["traffic"], 1,
                            harness.Phases())
    for i in range(2):
        cell_.driver.call(st, i)
    cell_.driver.release(st)
    checks, failed = cell_.driver.check(st)
    assert failed == 0 and all(v <= lim for _, v, lim in checks), checks
