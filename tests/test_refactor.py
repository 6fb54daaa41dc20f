"""Pattern-frozen refactorization fast path (ISSUE 7).

Differential suite: `op.update_values(L2)` / `Preconditioner.refactor(A2)`
must be BITWISE identical to a fresh build on the new values for every
engine and sweep orientation — and must provably skip the structure-derived
staging (level analysis, transformation, tuning, schedule compilation).
Cache-key regression: the pattern/value key split; property-based checks
ride behind the optional-hypothesis guard; chaos cases prove a poisoned or
drifted update is caught by typed guards, never a finite wrong answer.
"""
import sys

import numpy as np
import pytest

from repro.core import faults
from repro.core.resilience import NumericalHealthError, PatternMismatchError
from repro.precond import Preconditioner, ic0, ilu0, refactor
from repro.solver.operator import (TriangularOperator, matrix_fingerprint,
                                   value_fingerprint)
from repro.sparse import generators
from repro.sparse.csr import CSR, from_coo, same_pattern

from _optional_deps import HAS_HYPOTHESIS, given, settings, st


# -- shared fixtures ----------------------------------------------------------


def _lower(n=160, seed=0):
    return generators.random_lower(n, avg_offdiag=2.5, seed=seed,
                                   max_back=25)


def _revalued(L, seed=1, diag_scale=1.6):
    """Same pattern, perturbed values; the diagonal is scaled (not noised)
    so triangular solves stay well-conditioned."""
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(L.n_rows), L.row_nnz())
    d_mask = L.indices == rows
    data = L.data * (1.0 + 0.25 * rng.standard_normal(L.nnz))
    data[d_mask] = L.data[d_mask] * diag_scale
    return L.with_data(data)


def _general_square(n=150, seed=5):
    """General square matrix with a full diagonal (for ilu0)."""
    B = generators.random_lower(n, avg_offdiag=2.0, seed=seed, max_back=20)
    Bt = B.transpose()
    rows = np.concatenate([np.repeat(np.arange(n), B.row_nnz()),
                           np.repeat(np.arange(n), Bt.row_nnz())])
    cols = np.concatenate([B.indices, Bt.indices])
    vals = np.concatenate([B.data, 0.3 * Bt.data])
    return from_coo(rows, cols, vals, (n, n))


def _revalued_diag_dominant(A, seed=2):
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(A.n_rows), A.row_nnz())
    d_mask = A.indices == rows
    data = A.data * (1.0 + 0.1 * rng.standard_normal(A.nnz))
    data[d_mask] = A.data[d_mask] * 2.0
    return A.with_data(data)


def _revalued_spd(A, seed=2):
    """Symmetric value perturbation (keeps ic0's SPD validation happy):
    one deterministic factor per unordered index pair, boosted diagonal."""
    rows = np.repeat(np.arange(A.n_rows), A.row_nnz())
    key = (np.minimum(rows, A.indices) * A.n_cols
           + np.maximum(rows, A.indices))
    data = A.data * (1.0 + 0.1 * np.sin(key * 12.9898 + seed))
    d_mask = A.indices == rows
    data[d_mask] = A.data[d_mask] * 2.0
    return A.with_data(data)


@pytest.fixture(scope="module")
def rhs():
    return np.random.default_rng(42).standard_normal(160)


# -- differential suite: every engine x every sweep ---------------------------

SWEEPS = [("lower", False), ("lower", True), ("upper", False),
          ("upper", True)]
ENGINES = ["scan", "unrolled", "pallas-interpret", "sharded"]


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("side,transpose", SWEEPS)
def test_update_values_matches_fresh_bitwise(engine, side, transpose, rhs):
    L = _lower()
    M = L if side == "lower" else L.transpose()
    M2 = _revalued(M, seed=3)
    kw = dict(side=side, transpose=transpose, engine=engine, cache=False)
    op = TriangularOperator.from_csr(M, "avgLevelCost", **kw)
    op.solve(rhs)                              # prime compiled fns/preamble
    fresh = TriangularOperator.from_csr(M2, "avgLevelCost", **kw)
    assert fresh.strategy == op.strategy
    x_fresh = fresh.solve(rhs)
    assert op.update_values(M2) is op
    x_upd = op.solve(rhs)
    assert np.array_equal(np.asarray(x_upd), np.asarray(x_fresh))
    assert op.stats.value_updates == 1
    assert op.stats.last_update_ms >= 0.0


def test_update_values_autotuned_matches_fresh(rhs):
    """Auto-tuned operators refactor too: the frozen tuner pick is reused
    and the solve matches a fresh build pinned to the same strategy."""
    L = _lower()
    L2 = _revalued(L, seed=9)
    op = TriangularOperator.from_csr(L, cache=False)
    op.update_values(L2)
    # model-ranked tuning scores the PATTERN, so a fresh auto-tune on the
    # revalued matrix lands on the same pick
    fresh = TriangularOperator.from_csr(L2, cache=False)
    assert fresh.strategy == op.strategy
    assert np.array_equal(np.asarray(op.solve(rhs)),
                          np.asarray(fresh.solve(rhs)))


def test_update_values_batched_rhs(rhs):
    L, L2 = _lower(), _revalued(_lower(), seed=4)
    B = np.random.default_rng(0).standard_normal((160, 3))
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    fresh = TriangularOperator.from_csr(L2, "avgLevelCost", cache=False)
    op.update_values(L2)
    assert np.array_equal(np.asarray(op.solve(B)),
                          np.asarray(fresh.solve(B)))


def test_update_values_refined_fp64(rhs):
    """The fp64 iterative-refinement path sees the NEW matrix (residuals
    against L2, not the stale L)."""
    L, L2 = _lower(), _revalued(_lower(), seed=6)
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False,
                                     dtype=np.float64)
    fresh = TriangularOperator.from_csr(L2, "avgLevelCost", cache=False,
                                        dtype=np.float64)
    op.update_values(L2)
    x = np.asarray(op.solve(rhs, max_refine=4, refine_tol=1e-12))
    assert np.array_equal(x, np.asarray(fresh.solve(rhs, max_refine=4,
                                                    refine_tol=1e-12)))
    r = rhs - L2.matvec(x)
    assert np.linalg.norm(r) <= 1e-10 * np.linalg.norm(rhs)


def _no_pattern_pass(monkeypatch):
    from repro.core.rewrite import EquationStore

    def refuse(*a, **k):
        raise AssertionError("a second pattern pass")
    monkeypatch.setattr(EquationStore, "b_rows_plan", staticmethod(refuse))


def test_update_values_rebuilds_preamble_plan(rhs, monkeypatch):
    """The host preamble's B' pattern is value-free and its values are
    not: after an update the plan keeps the pattern (no second pattern
    pass), the first solve works out B' from the NEW transform, never
    carrying the old values, and the answer is a fresh build's on the new
    values, bit for bit."""
    L, L2 = _lower(), _revalued(_lower(), seed=4)
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    op.solve(rhs, max_refine=0)
    old = op.preamble
    assert old.realization == "spmv"
    old_B = old.B
    op.update_values(L2)
    _no_pattern_pass(monkeypatch)
    new = op.preamble
    assert new is not old and new.pattern is old.pattern
    assert new.ts is op.transformed and "B" not in vars(new)
    x = op.solve(rhs, max_refine=0)
    assert "B" in vars(new) and np.array_equal(new.B.indices, old_B.indices)
    assert not np.array_equal(new.B.data, old_B.data)
    c_ref = op.transformed.preamble(rhs)
    assert np.abs(new.host(rhs) - c_ref).max() \
        <= 1e-13 * np.abs(c_ref).max()
    monkeypatch.undo()
    fresh = TriangularOperator.from_csr(L2, "avgLevelCost", cache=False)
    assert np.array_equal(x, fresh.solve(rhs, max_refine=0))
    assert np.array_equal(new.B.data, fresh.preamble.B.data)


def test_update_values_keeps_the_tfactor_decision(monkeypatch):
    """A pattern whose B' passes the factor's nnz keeps the T-factor loop
    through updates without a second pattern pass, and stays exact."""
    L = generators.poisson2d_ic0(16, 16)
    L2 = _revalued(L, seed=5)
    b = np.random.default_rng(6).standard_normal(L.n_rows)
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    op.solve(b, max_refine=0)
    assert op.preamble.realization == "tfactor"
    op.update_values(L2)
    _no_pattern_pass(monkeypatch)
    assert op.preamble.realization == "tfactor"
    x = op.solve(b, max_refine=0)
    monkeypatch.undo()
    fresh = TriangularOperator.from_csr(L2, "avgLevelCost", cache=False)
    assert np.array_equal(x, fresh.solve(b, max_refine=0))


def test_update_values_repeated_steps(rhs):
    """A time-stepping sequence of updates stays exact at every step."""
    L = _lower()
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    for step in range(4):
        L_k = _revalued(L, seed=100 + step)
        op.update_values(L_k)
        fresh = TriangularOperator.from_csr(L_k, "avgLevelCost", cache=False)
        assert np.array_equal(np.asarray(op.solve(rhs)),
                              np.asarray(fresh.solve(rhs)))
    assert op.stats.value_updates == 4


# -- staging must NOT re-run (acceptance: counters/monkeypatch) ---------------


class _Boom(Exception):
    pass


@pytest.fixture()
def forbid_staging(monkeypatch):
    """Arms a tripwire: after calling the returned function, transform /
    portfolio tuning / schedule compilation raise if re-entered —
    update_values and refactor must never call them.  (Armed AFTER the
    initial from_csr builds, which legitimately stage.)"""
    import repro.core.portfolio as portfolio_mod
    transform_mod = sys.modules["repro.core.transform"]
    schedule_mod = sys.modules["repro.solver.schedule"]

    def boom(*a, **k):
        raise _Boom("from_csr-style staging re-entered on the fast path")

    def arm():
        monkeypatch.setattr(transform_mod, "transform", boom)
        monkeypatch.setattr(portfolio_mod.StrategyPortfolio, "tune", boom)
        monkeypatch.setattr(portfolio_mod.StrategyPortfolio, "tune_pair",
                            boom)
        monkeypatch.setattr(schedule_mod, "build_schedule", boom)

    return arm


def test_update_values_skips_staging(forbid_staging, rhs):
    L = _lower()
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    op.solve(rhs)
    forbid_staging()
    op.update_values(_revalued(L, seed=8))
    x = op.solve(rhs)
    assert np.isfinite(np.asarray(x)).all()


def test_update_values_skips_staging_before_first_solve(forbid_staging):
    """Even an operator that never solved (no materialized preamble) must
    not re-enter staging during the update itself."""
    L = _lower()
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    forbid_staging()
    op.update_values(_revalued(L, seed=8))


def test_precond_refactor_skips_staging(forbid_staging):
    A = generators.poisson2d_spd(10, 10)
    P = Preconditioner.ic0(A, "avgLevelCost", cache=False)
    r = np.random.default_rng(1).standard_normal(A.n_rows)
    P.apply(r)
    forbid_staging()
    P.refactor(_revalued_spd(A))
    assert np.isfinite(P.apply(r)).all()


def test_scan_executable_reused_across_update(rhs):
    """The scan engine's staged jit keys on tile shapes, so a value-only
    repack reuses the already-compiled XLA executable (no retrace)."""
    from repro.solver import levelset
    cache_size = getattr(levelset._scan_jit, "_cache_size", None)
    if cache_size is None:
        pytest.skip("jax jit cache-size introspection unavailable")
    L = _lower()
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False,
                                     engine="scan")
    op.solve(rhs)
    before = cache_size()
    op.update_values(_revalued(L, seed=11))
    op.solve(rhs)
    assert cache_size() == before


# -- pattern mismatch ---------------------------------------------------------


def test_update_values_pattern_mismatch_raises():
    L = _lower()
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    other = generators.random_lower(160, avg_offdiag=2.5, seed=99,
                                    max_back=25)
    with pytest.raises(PatternMismatchError) as ei:
        op.update_values(other)
    assert "update_values" in str(ei.value)
    # shape mismatch reported distinctly
    small = generators.random_lower(40, avg_offdiag=2.0, seed=0, max_back=5)
    with pytest.raises(PatternMismatchError, match="shape"):
        op.update_values(small)


def test_pattern_mismatch_is_typed_resilience_error():
    from repro.core.resilience import ResilienceError
    assert issubclass(PatternMismatchError, ResilienceError)
    e = PatternMismatchError("boom", where="here", detail="why")
    assert e.where == "here" and e.detail == "why"
    assert "here" in str(e) and "why" in str(e)


# -- cache-key split ----------------------------------------------------------


def test_pattern_key_shared_value_key_not():
    L = _lower()
    L2 = _revalued(L, seed=5)
    assert matrix_fingerprint(L, include_values=False) == \
        matrix_fingerprint(L2, include_values=False)
    assert matrix_fingerprint(L) != matrix_fingerprint(L2)
    assert value_fingerprint(L) != value_fingerprint(L2)
    assert value_fingerprint(L) == value_fingerprint(L.with_data(L.data))


def test_from_csr_pattern_cache_hit(tmp_path, rhs):
    """Equal pattern + different values: from_csr derives the payload from
    the cached artifact (cache_source 'pattern') and matches an uncached
    fresh build bitwise."""
    TriangularOperator.clear_memory_cache()
    L = _lower()
    L2 = _revalued(L, seed=7)
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache_dir=tmp_path)
    assert op.stats.cache_source == "built"
    op2 = TriangularOperator.from_csr(L2, "avgLevelCost", cache_dir=tmp_path)
    assert op2.stats.cache_source == "pattern"
    fresh = TriangularOperator.from_csr(L2, "avgLevelCost", cache=False)
    assert np.array_equal(np.asarray(op2.solve(rhs)),
                          np.asarray(fresh.solve(rhs)))
    # the derived payload was stored under its own full key: exact re-ask
    # is a memory hit now
    op3 = TriangularOperator.from_csr(L2, "avgLevelCost", cache_dir=tmp_path)
    assert op3.stats.cache_source == "memory"


def test_from_csr_pattern_hit_from_disk_only(tmp_path, rhs):
    """The pattern match also works via the disk glob after the memory
    cache (and its pattern index) is gone."""
    TriangularOperator.clear_memory_cache()
    L = _lower()
    TriangularOperator.from_csr(L, "avgLevelCost", cache_dir=tmp_path)
    TriangularOperator.clear_memory_cache()
    op2 = TriangularOperator.from_csr(_revalued(L, seed=13), "avgLevelCost",
                                      cache_dir=tmp_path)
    assert op2.stats.cache_source == "pattern"


def test_update_values_stores_under_new_value_key(tmp_path):
    TriangularOperator.clear_memory_cache()
    L = _lower()
    L2 = _revalued(L, seed=21)
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache_dir=tmp_path)
    op.update_values(L2)
    assert op.stats.cache_source == "pattern"
    # both value keys now live on disk under the shared pattern prefix
    pkey = TriangularOperator._pattern_cache_key(L, op._config)
    found = sorted(tmp_path.glob(f"op-{pkey}-*.pkl"))
    assert len(found) == 2
    # a second update to the SAME values is a memory hit
    op.update_values(L2.with_data(L2.data.copy()))
    assert op.stats.cache_source == "memory"


def test_stale_version_artifact_quarantined(tmp_path):
    """CACHE_VERSION 2 artifacts (and any stale version) quarantine
    cleanly under version 3 — warned, moved to .bad/, rebuilt."""
    import pickle
    from repro.core.resilience import CacheQuarantineWarning
    TriangularOperator.clear_memory_cache()
    L = _lower()
    TriangularOperator.from_csr(L, "avgLevelCost", cache_dir=tmp_path)
    for p in tmp_path.glob("op-*.pkl"):
        payload = pickle.loads(p.read_bytes())
        payload["version"] = 2
        p.write_bytes(pickle.dumps(payload))
    TriangularOperator.clear_memory_cache()
    with pytest.warns(CacheQuarantineWarning, match="stale version 2"):
        op = TriangularOperator.from_csr(L, "avgLevelCost",
                                         cache_dir=tmp_path)
    assert op.stats.cache_source == "built"
    assert list((tmp_path / ".bad").glob("op-*.pkl"))


def test_pattern_derive_never_uses_stale_artifacts(tmp_path):
    """A stale-version artifact must not serve as a pattern-derive base
    either (the glob loader runs the same version gate)."""
    TriangularOperator.clear_memory_cache()
    L = _lower()
    TriangularOperator.from_csr(L, "avgLevelCost", cache_dir=tmp_path)
    faults.corrupt_cache_entries(tmp_path, mode="stale")
    TriangularOperator.clear_memory_cache()
    with pytest.warns(Warning):     # quarantine warning on the glob path
        op = TriangularOperator.from_csr(_revalued(L, seed=2),
                                         "avgLevelCost", cache_dir=tmp_path)
    assert op.stats.cache_source == "built"


# -- preconditioner refactor --------------------------------------------------


def test_ic0_refactor_matches_fresh_bitwise():
    A = generators.poisson2d_spd(12, 12)
    A2 = _revalued_spd(A)
    fac = ic0(A)
    fac2 = refactor(fac, A2)
    fresh = ic0(A2)
    assert np.array_equal(fac2.L.data, fresh.L.data)
    assert same_pattern(fac2.L, fac.L)
    assert fac2.plan is fac.plan


def test_ilu0_refactor_matches_fresh_bitwise():
    G = _general_square()
    G2 = _revalued_diag_dominant(G)
    fac = ilu0(G)
    fac2 = refactor(fac, G2)
    fresh = ilu0(G2)
    assert np.array_equal(fac2.L.data, fresh.L.data)
    assert np.array_equal(fac2.U.data, fresh.U.data)


def test_refactor_no_plan_raises():
    fac = ic0(generators.poisson2d_spd(6, 6))
    import dataclasses
    stripped = dataclasses.replace(fac, plan=None)
    with pytest.raises(ValueError, match="no pattern plan"):
        refactor(stripped, generators.poisson2d_spd(6, 6))


def test_refactor_pattern_mismatch_raises():
    fac = ic0(generators.poisson2d_spd(10, 10))
    with pytest.raises(PatternMismatchError, match="ic0"):
        refactor(fac, generators.poisson2d_spd(11, 11))
    G = _general_square()
    gfac = ilu0(G)
    with pytest.raises(PatternMismatchError, match="ilu0"):
        refactor(gfac, generators.poisson2d_spd(10, 10))


def test_ic0_refactor_matches_dense_cholesky_oracle():
    """On a no-fill (tridiagonal) pattern IC(0) IS the exact Cholesky
    factor — the refactored values must match the dense oracle too."""
    la = pytest.importorskip("numpy.linalg")
    n = 50
    rng = np.random.default_rng(17)
    main = 4.0 + rng.random(n)
    off = -1.0 + 0.1 * rng.random(n - 1)
    rows = np.concatenate([np.arange(n), np.arange(1, n), np.arange(n - 1)])
    cols = np.concatenate([np.arange(n), np.arange(n - 1), np.arange(1, n)])
    vals = np.concatenate([main, off, off])
    T = from_coo(rows, cols, vals, (n, n))
    fac = ic0(T)
    T2 = from_coo(rows, cols, np.concatenate([main * 1.4, off, off]), (n, n))
    fac2 = refactor(fac, T2)

    def dense_L(f):
        Ld = np.zeros((n, n))
        Ld[np.repeat(np.arange(n), f.L.row_nnz()), f.L.indices] = f.L.data
        return Ld

    dense = np.zeros((n, n))
    dense[rows, cols] = vals
    dense2 = np.zeros((n, n))
    dense2[rows, cols] = np.concatenate([main * 1.4, off, off])
    assert np.allclose(dense_L(fac), la.cholesky(dense), rtol=1e-12,
                       atol=1e-12)
    assert np.allclose(dense_L(fac2), la.cholesky(dense2), rtol=1e-12,
                       atol=1e-12)


@pytest.mark.parametrize("kind", ["ic0", "ilu0"])
def test_precond_refactor_apply_matches_fresh(kind):
    if kind == "ic0":
        A = generators.poisson2d_spd(11, 11)
        A2 = _revalued_spd(A)
    else:
        A = _general_square(121)
        A2 = _revalued_diag_dominant(A)
    build = getattr(Preconditioner, kind)
    P = build(A, "avgLevelCost", cache=False)
    r = np.random.default_rng(5).standard_normal(A.n_rows)
    z_before = P.apply(r)
    assert P.refactor(A2) is P
    P_fresh = build(A2, "avgLevelCost", cache=False)
    assert np.array_equal(P.apply(r), P_fresh.apply(r))
    assert not np.array_equal(P.apply(r), z_before)
    assert P.forward.stats.value_updates == 1
    assert P.backward.stats.value_updates == 1


def test_precond_refactor_device_apply_recomposes():
    """device_apply closures over the old payload are dropped on refactor."""
    import jax.numpy as jnp
    A = generators.poisson2d_spd(9, 9)
    P = Preconditioner.ic0(A, "avgLevelCost", cache=False)
    r = np.random.default_rng(2).standard_normal(A.n_rows)
    np.asarray(P.jax_apply(jnp.asarray(r, dtype=np.float32)))
    P.refactor(_revalued_spd(A))
    z_dev = np.asarray(P.jax_apply(jnp.asarray(r, dtype=np.float32)),
                       dtype=np.float64)
    z_host = Preconditioner.ic0(_revalued_spd(A), "avgLevelCost",
                                cache=False).apply(r)
    assert np.allclose(z_dev, z_host, rtol=1e-5, atol=1e-6)


def test_precond_refactor_pattern_mismatch():
    A = generators.poisson2d_spd(10, 10)
    P = Preconditioner.ic0(A, "avgLevelCost", cache=False)
    with pytest.raises(PatternMismatchError):
        P.refactor(generators.poisson2d_spd(11, 11))


# -- chaos: poisoned / drifted updates are caught, never silently wrong ------


@pytest.mark.chaos
def test_chaos_poisoned_update_caught_by_health_guard(rhs):
    L = _lower()
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    with faults.corrupt_values_payload() as count:
        op.update_values(_revalued(L, seed=30))
    assert count["calls"] >= 1
    with pytest.raises(NumericalHealthError):
        op.solve(rhs, health="on")


@pytest.mark.chaos
def test_chaos_poisoned_update_recovers_under_fallback(rhs):
    L = _lower()
    L2 = _revalued(L, seed=31)
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    with faults.corrupt_values_payload():
        op.update_values(L2)
    from repro.core.resilience import HealthRepairWarning
    with pytest.warns(HealthRepairWarning):
        x = np.asarray(op.solve(rhs, health="fallback"))
    r = rhs - L2.matvec(x)
    assert np.linalg.norm(r) <= 1e-5 * np.linalg.norm(rhs)


@pytest.mark.chaos
def test_chaos_pattern_drift_raises_never_wrong(rhs):
    L = _lower()
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    x_before = np.asarray(op.solve(rhs)).copy()
    drifted = faults.pattern_drift(L)
    assert drifted.nnz == L.nnz and drifted.shape == L.shape
    assert not same_pattern(drifted, L)
    with pytest.raises(PatternMismatchError):
        op.update_values(drifted)
    # the operator is untouched: still solves the ORIGINAL system exactly
    assert np.array_equal(np.asarray(op.solve(rhs)), x_before)


@pytest.mark.chaos
def test_chaos_pattern_drift_on_precond(rhs):
    A = generators.poisson2d_spd(10, 10)
    P = Preconditioner.ic0(A, "avgLevelCost", cache=False)
    with pytest.raises(PatternMismatchError):
        P.refactor(faults.pattern_drift(A))


@pytest.mark.chaos
def test_chaos_nonfinite_update_rejected(rhs):
    L = _lower()
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    bad = L.with_data(np.where(np.arange(L.nnz) == 3, np.inf, L.data))
    with pytest.raises(NumericalHealthError):
        op.update_values(bad)
    with pytest.raises(NumericalHealthError):
        op.update_values(bad, health="strict")
    # health="off" skips the input gate by explicit request
    op.update_values(bad, health="off")


# -- property-based (hypothesis; skipped when not installed) ------------------


if HAS_HYPOTHESIS:
    matrices = st.integers(min_value=12, max_value=64).flatmap(
        lambda n: st.tuples(st.just(n),
                            st.integers(min_value=0, max_value=10 ** 6),
                            st.integers(min_value=0, max_value=10 ** 6)))
else:                                   # placeholder; tests skip anyway
    matrices = None


@given(matrices)
@settings(max_examples=20, deadline=None)
def test_property_refactor_equals_fresh(params):
    """Random pattern + value sequence: update_values either matches the
    fresh build bitwise or (never) silently diverges."""
    n, seed_pat, seed_val = params
    L = generators.random_lower(n, avg_offdiag=2.0, seed=seed_pat,
                                max_back=max(2, n // 8))
    L2 = _revalued(L, seed=seed_val)
    b = np.random.default_rng(seed_val).standard_normal(n)
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    op.update_values(L2)
    fresh = TriangularOperator.from_csr(L2, "avgLevelCost", cache=False)
    assert np.array_equal(np.asarray(op.solve(b)),
                          np.asarray(fresh.solve(b)))


@given(matrices)
@settings(max_examples=20, deadline=None)
def test_property_pattern_fingerprint_invariance(params):
    """Pattern fingerprint is invariant under any value change; the value
    fingerprint is sensitive to every value change."""
    n, seed_pat, seed_val = params
    L = generators.random_lower(n, avg_offdiag=2.0, seed=seed_pat,
                                max_back=max(2, n // 8))
    L2 = _revalued(L, seed=seed_val)
    assert matrix_fingerprint(L, include_values=False) == \
        matrix_fingerprint(L2, include_values=False)
    if not np.array_equal(L.data, L2.data):
        assert value_fingerprint(L) != value_fingerprint(L2)


@given(matrices)
@settings(max_examples=10, deadline=None)
def test_property_drift_always_detected(params):
    """Any single-entry column drift raises PatternMismatchError — never a
    finite wrong answer."""
    n, seed_pat, _ = params
    L = generators.random_lower(n, avg_offdiag=2.5, seed=seed_pat,
                                max_back=max(2, n // 8))
    try:
        drifted = faults.pattern_drift(L)
    except ValueError:
        return                          # no shiftable entry in this draw
    op = TriangularOperator.from_csr(L, "avgLevelCost", cache=False)
    with pytest.raises(PatternMismatchError):
        op.update_values(drifted)
