"""Solver engines: schedule packing, scan/unrolled engines, multi-RHS."""
import jax.numpy as jnp
import numpy as np
import pytest
from _optional_deps import given, settings, st

from repro.core import AvgLevelCost, NoRewrite, transform
from repro.solver import (resolve_engine, schedule_for_csr,
                          schedule_for_transformed, solve, solve_csr_seq,
                          to_device)
from repro.solver.levelset import solve_scan, solve_unrolled
from repro.sparse import build_levels, generators


def _solve_and_check(L, chunk, max_deps, engine=None, rtol=2e-5):
    lv = build_levels(L)
    b = np.random.default_rng(0).standard_normal(L.n_rows)
    x_ref = solve_csr_seq(L, b)
    sched = schedule_for_csr(L, lv, chunk=chunk, max_deps=max_deps,
                             dtype=np.float32)
    x = solve(sched, b, engine=engine)
    scale = np.maximum(1.0, np.abs(x_ref).max())
    assert np.abs(x - x_ref).max() / scale < rtol
    return sched


@pytest.mark.parametrize("chunk,max_deps", [(8, 2), (32, 4), (128, 8)])
def test_schedule_shapes_and_solve(chunk, max_deps):
    L = generators.random_lower(300, avg_offdiag=2.0, seed=4, max_back=30)
    sched = _solve_and_check(L, chunk, max_deps)
    assert sched.chunk == chunk and sched.max_deps == max_deps


def test_row_splitting_wide_rows():
    """Rows wider than max_deps split into carry-chained segments."""
    L = generators.banded(60, 12, seed=1)      # rows with 12 deps
    sched = _solve_and_check(L, chunk=16, max_deps=4)
    assert sched.n_carry > 0                   # splitting happened


def test_unrolled_engine_matches():
    L = generators.random_lower(150, avg_offdiag=2.0, seed=6, max_back=12)
    _solve_and_check(L, 32, 4, engine=resolve_engine("unrolled"))


def test_multi_rhs():
    L = generators.random_lower(120, avg_offdiag=2.0, seed=8, max_back=12)
    lv = build_levels(L)
    sched = schedule_for_csr(L, lv, chunk=32, max_deps=4, dtype=np.float32)
    B = np.random.default_rng(1).standard_normal((120, 5))
    ds = to_device(sched)
    X = np.asarray(solve_scan(ds, jnp.asarray(B, jnp.float32)))
    for j in range(5):
        x_ref = solve_csr_seq(L, B[:, j])
        assert np.abs(X[:, j] - x_ref).max() < 2e-4


def test_transformed_schedule_fewer_steps():
    L = generators.lung2_like(scale=0.1)
    lv = build_levels(L)
    s0 = schedule_for_csr(L, lv, chunk=64, max_deps=4)
    ts = transform(L, AvgLevelCost(), validate=False, codegen=False)
    s1 = schedule_for_transformed(ts, chunk=64, max_deps=4)
    assert s1.num_steps < s0.num_steps
    assert s1.num_levels < s0.num_levels
    # end-to-end solve through the transformed schedule
    b = np.random.default_rng(2).standard_normal(L.n_rows)
    c = ts.preamble(b)
    x = solve(s1, c)
    x_ref = solve_csr_seq(L, b)
    scale = np.maximum(1.0, np.abs(x_ref).max())
    assert np.abs(x - x_ref).max() / scale < 2e-4


@given(st.integers(20, 150), st.integers(0, 10**5),
       st.sampled_from([(8, 2), (16, 4), (64, 8)]))
@settings(max_examples=15, deadline=None)
def test_engine_property(n, seed, cm):
    chunk, max_deps = cm
    L = generators.random_lower(n, avg_offdiag=2.0, seed=seed, max_back=10)
    _solve_and_check(L, chunk, max_deps, rtol=5e-4)


def test_schedule_flop_accounting():
    L = generators.random_lower(100, avg_offdiag=2.0, seed=3)
    lv = build_levels(L)
    sched = schedule_for_csr(L, lv, chunk=16, max_deps=4)
    assert sched.flops() <= sched.padded_flops()
    assert sched.memory_bytes() > 0


def test_preamble_as_schedule():
    """The T-factor preamble solved through the SAME level-scheduled engine
    (and the Pallas kernel) matches the host preamble."""
    from repro.core import AvgLevelCost, transform
    from repro.kernels import ops
    from repro.solver import schedule_for_preamble
    L = generators.lung2_like(scale=0.05)
    ts = transform(L, AvgLevelCost(), validate=False, codegen=False)
    b = np.random.default_rng(3).standard_normal(L.n_rows)
    c_ref = ts.preamble(b)
    psched, src, row_pos = schedule_for_preamble(ts, chunk=64, max_deps=8)
    assert psched is not None
    c_ent = solve(psched, b[src].astype(np.float32))
    np.testing.assert_allclose(c_ent[row_pos], c_ref, rtol=2e-4, atol=2e-4)
    # through the pallas kernel too
    c_pal = ops.sptrsv_solve(psched, b[src].astype(np.float32))
    np.testing.assert_allclose(c_pal[row_pos], c_ref, rtol=2e-4, atol=2e-4)

    # full end-to-end: preamble schedule + main schedule
    from repro.solver import schedule_for_transformed, solve_csr_seq
    s1 = schedule_for_transformed(ts, chunk=64, max_deps=8)
    x = solve(s1, c_ent[row_pos])
    x_ref = solve_csr_seq(L, b)
    scale = max(1.0, np.abs(x_ref).max())
    assert np.abs(x - x_ref).max() / scale < 5e-4


# ----------------------------------------------------------------------
# the operator's host preamble plan: B' rows as one SpMV


_PLAN_OPS: dict = {}


def _plan_op(L, side="lower", transpose=False, tune="avgLevelCost"):
    """A transformed operator, built once per (matrix, orientation)."""
    from repro.solver import TriangularOperator
    key = (id(L), side, transpose, tune)
    if key not in _PLAN_OPS:
        M = L if side == "lower" else L.transpose()
        _PLAN_OPS[key] = TriangularOperator.from_csr(
            M, tune=tune, side=side, transpose=transpose, cache=False)
    return _PLAN_OPS[key]


_LUNG = generators.lung2_like(scale=0.05)


def _assert_same_preamble(op, b):
    c_ref = op.transformed.preamble(b)
    c = op._preamble(b)
    assert c.dtype == c_ref.dtype == np.float64
    assert c.shape == c_ref.shape == b.shape
    assert np.abs(c - c_ref).max() <= 1e-13 * np.abs(c_ref).max()


@pytest.mark.parametrize("side,transpose", [("lower", False),
                                            ("upper", False),
                                            ("lower", True)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("k", [None, 3])
def test_preamble_plan_matches_tfactor(side, transpose, dtype, k):
    """The B' plan's c equals the T-factor loop's for vector and block
    right-hand sides, float32 and float64, and the reversed orientations
    (whose right-hand side reaches the preamble as a reversed view)."""
    op = _plan_op(_LUNG, side, transpose)
    assert not op.transformed.identity_preamble
    plan = op._preamble_plan()
    assert plan.realization == "spmv"
    assert plan.entries == plan.B.nnz <= _LUNG.nnz
    # only original rows, and only those the T factor touches
    T, rows = op.transformed.T, plan.pattern.rows
    assert np.all(rows < op.n) and np.all(T.row_nnz()[rows] > 0)
    shape = (op.n,) if k is None else (op.n, k)
    b = np.random.default_rng(5).standard_normal(shape).astype(dtype)
    _assert_same_preamble(op, b[::-1] if op._reversed else b)


def test_preamble_plan_falls_back_when_b_fills_in():
    """A 2-D mesh whose B' has more entries than the factor keeps the
    T-factor loop, and gives the same answer."""
    L = generators.poisson2d_ic0(16, 16)
    op = _plan_op(L)
    plan = op._preamble_plan()
    assert (plan.realization, plan.entries, plan.pattern) == \
        ("tfactor", op.transformed.T.nnz, None)
    for b in (np.random.default_rng(6).standard_normal(op.n),
              np.random.default_rng(7).standard_normal((op.n, 2))):
        _assert_same_preamble(op, b)
    x = op.solve(b)
    assert np.abs(L.matvec(x) - b).max() < 1e-9 * np.abs(b).max()


def test_preamble_plan_bound_is_the_factors_nnz():
    from repro.core.transform import host_preamble
    ts = _plan_op(_LUNG).transformed
    entries = host_preamble(ts, _LUNG.nnz).entries
    assert host_preamble(ts, entries).realization == "spmv"
    assert host_preamble(ts, entries - 1).realization == "tfactor"


def test_preamble_plan_rows_match_materialized_b():
    """The plan's rows are the rewritten rows of the reference
    `materialize_b`, value for value."""
    from repro.core.rewrite import EquationStore
    ts = transform(_LUNG, AvgLevelCost(), validate=False, codegen=False,
                   materialize_b=True)
    n = _LUNG.n_rows
    plan = EquationStore.b_rows_plan(ts.T, ts.src, n, max_entries=_LUNG.nnz)
    rows, B = plan.rows, plan.matrix(ts.T.data)
    assert rows.size and np.array_equal(
        rows, np.flatnonzero(ts.B.row_nnz()[:n] > 1))
    for r, i in enumerate(rows):
        got, want = np.zeros(n), np.zeros(n)
        lo, hi = B.indptr[r], B.indptr[r + 1]
        assert np.all(np.diff(B.indices[lo:hi]) > 0)
        got[B.indices[lo:hi]] = B.data[lo:hi]
        cols, vals = ts.B.row(int(i))
        want[cols] = vals
        np.testing.assert_allclose(got, want, rtol=1e-13, atol=1e-15)


def test_identity_preamble_has_no_plan_and_no_span():
    from repro import obs
    L = generators.random_lower(120, avg_offdiag=2.0, seed=8, max_back=12)
    op = _plan_op(L, tune="no_rewriting")
    assert op.transformed.identity_preamble
    b = np.random.default_rng(9).standard_normal(op.n).astype(np.float32)
    tr = obs.enable()
    try:
        c = op._preamble(b)
    finally:
        obs.disable()
    assert c.dtype == np.float64 and np.array_equal(c, b)
    assert not any(s.name == "engine.preamble" for s in tr.spans())
    assert "preamble_plan" not in op._runtime
