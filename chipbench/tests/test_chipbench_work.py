"""Work counts come from the problem, not from the program's schedule."""
import pytest

from chipbench import harness, work


def test_hand_counts_on_a_three_row_factor():
    # L = [[2, 0, 0], [1, 3, 0], [0, 1, 4]]: n = 3, nnz = 5
    # bytes: values + column indices 5 * (4 + 4) = 40, row pointers
    # 4 * 4 = 16, diagonal 3 * 4 = 12, b read + x written 2 * 3 * 4 = 24
    assert work.sweep(3, 5) == {"bytes": 92, "flops": 10}
    # two columns: b and x twice, operations twice
    assert work.sweep(3, 5, columns=2) == {"bytes": 116, "flops": 20}
    # A = L + L^T - diag: nnz 7; CSR 7 * 8 + 16, x read + y written 24
    assert work.spmv(3, 7) == {"bytes": 96, "flops": 14}
    # one PCG iteration: SpMV + two sweeps + 14 vectors of 3 float32
    # (168 bytes) and 12 * 3 = 36 vector operations
    assert work.pcg_iteration(3, 7, 5) == {"bytes": 96 + 92 + 92 + 168,
                                           "flops": 14 + 10 + 10 + 36}


def test_least_seconds_names_the_binding_bound():
    peak = {"hbm_bytes_per_s": 100.0, "flops_per_s": 1000.0}
    assert work.least_seconds({"bytes": 92, "flops": 10}, peak) == \
        (pytest.approx(0.92), "bandwidth")
    assert work.least_seconds({"bytes": 1, "flops": 10_000}, peak) == \
        (pytest.approx(10.0), "compute")


def test_peaks_know_the_chip_and_refuse_an_unknown_kind():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    with pytest.raises(KeyError):
        work.peaks("TPU v99")


def test_sweep_work_is_the_same_for_two_schedules(tiny_configs):
    """`no_rewriting` and `avgLevelCost` compile different schedules of
    one matrix; the yardstick does not follow them."""
    states = {}
    for strategy in ("no_rewriting", "avgLevelCost"):
        cfg = dict(tiny_configs["lung2_full"], strategy=strategy)
        cell = harness.load_cell("lung2.sweep.rhs1")
        st = cell.driver.setup(cfg, cell.workload["traffic"], 3,
                               harness.Phases())
        states[strategy] = (cell.driver.work(st),
                            cell.driver.counters(st)["sweep_steps"])
    (w_plain, steps_plain), (w_avg, steps_avg) = (
        states["no_rewriting"], states["avgLevelCost"])
    assert steps_plain != steps_avg
    assert w_plain == w_avg
