"""What a traffic file and a configuration can set without a code change:
the right-hand side's width, and the steps counted for a schedule."""
import json

import numpy as np
import pytest

from chipbench import harness, steps, work

ROOT_WORKLOADS = harness.BENCH_DIR / "workloads"


def _sweep_traffic(columns: int, max_refine: int = 0) -> dict:
    traffic = json.loads(
        (ROOT_WORKLOADS / "lung2.sweep.rhs1.json").read_text())["traffic"]
    return dict(traffic, columns=columns, max_refine=max_refine,
                rhs_count=3)


def _driven(tiny_configs, traffic, control=False, calls=3):
    cell = harness.load_cell("lung2.sweep.rhs1")
    cfg = tiny_configs["lung2_full"]
    st = cell.driver.setup(cfg, traffic, 2**31 + 5, harness.Phases())
    for i in range(calls):
        cell.driver.call(st, i, control=control)
    return cell.driver, st


@pytest.mark.parametrize("columns", [1, 4])
def test_right_hand_side_width_is_a_traffic_key(tiny_configs, columns):
    drv, st = _driven(tiny_configs, _sweep_traffic(columns))
    n, nnz = st.L.shape[0], st.L.nnz
    want = (n,) if columns == 1 else (n, columns)
    assert all(b.shape == want for b in st.rhs)
    assert all(x.shape == want for _, x in st.sample)
    # columns completed over the window, and the work of one call
    rates = drv.end_to_end(st, [0.5, 0.5], 2.0)
    assert rates["sweep_rhs_per_s"] == pytest.approx(columns)
    assert drv.work(st) == work.sweep(n, nnz, columns)
    drv.release(st)
    checks, failed = drv.check(st)
    assert failed == 0 and all(v <= lim for _, v, lim in checks), checks


def test_wide_right_hand_sides_fail_under_the_control(tiny_configs):
    drv, st = _driven(tiny_configs, _sweep_traffic(4), control=True)
    drv.release(st)
    checks, _ = drv.check(st)
    assert not all(v <= lim for _, v, lim in checks), checks


def test_half_of_a_wide_batch_left_out_is_not_correct(tiny_configs,
                                                      monkeypatch):
    """Every column of a wide right-hand side is compared: a solve that
    leaves half of the batch unsolved fails."""
    from repro.solver import TriangularOperator
    solve = TriangularOperator.solve

    def half(self, b, **kw):
        x = np.array(solve(self, b, **kw))
        x[:, x.shape[1] // 2:] = 0.0
        return x

    monkeypatch.setattr(TriangularOperator, "solve", half)
    drv, st = _driven(tiny_configs, _sweep_traffic(4))
    drv.release(st)
    checks, _ = drv.check(st)
    assert not all(v <= lim for _, v, lim in checks), checks


@pytest.mark.parametrize("strategy", ["no_rewriting", "avgLevelCost"])
def test_steps_count_the_preamble_schedule(tiny_configs, strategy):
    from repro.solver import TriangularOperator, schedule_for_preamble
    from repro.sparse.csr import CSR
    from chipbench import matrices
    L = matrices.build(tiny_configs["lung2_full"], 1)
    op = TriangularOperator.from_csr(
        CSR(indptr=L.indptr.astype(np.int64),
            indices=L.indices.astype(np.int64), data=L.data,
            shape=L.shape), tune=strategy, cache=False)
    pre, _, _ = schedule_for_preamble(op.transformed)
    main = op.schedule.num_steps
    if strategy == "no_rewriting":
        assert pre is None and steps.sweep_steps(op) == main
    else:
        assert pre is not None and pre.num_steps > 0
        assert steps.sweep_steps(op) == main + pre.num_steps
