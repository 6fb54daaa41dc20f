"""The benchmark's own generators and plain references."""
import ml_dtypes
import numpy as np
import pytest
import scipy.sparse as sp

from chipbench import matrices, reference

LUNG2 = matrices.generator("lung2_like")
POISSON = matrices.generator("poisson2d")


def test_lung2_pattern_is_the_programs_analogue():
    from repro.sparse.generators import lung2_like
    ours = LUNG2.pattern(0.02, 7)
    theirs = lung2_like(0.02, seed=7)
    assert np.array_equal(ours.indptr, theirs.indptr)
    assert np.array_equal(ours.indices, theirs.indices)


def test_poisson_is_the_programs_laplacian():
    from repro.sparse.generators import poisson2d_spd
    ours, theirs = POISSON.laplacian(7, 5), poisson2d_spd(7, 5)
    assert np.array_equal(ours.indptr, theirs.indptr)
    assert np.array_equal(ours.indices, theirs.indices)
    assert np.array_equal(ours.data, theirs.data)


def test_values_come_from_the_seed_on_a_fixed_pattern():
    cfg = {"generator": "lung2_like", "scale": 0.02, "pattern_seed": 7,
           "values_seed_offset": 7}
    a, b, c = (matrices.build(cfg, s) for s in (1, 1, 2))
    assert np.array_equal(a.indices, c.indices)
    assert np.array_equal(a.data, b.data) and not np.array_equal(a.data,
                                                                 c.data)
    # diagonally dominant by construction
    d = a.diagonal()
    off = abs(a - sp.diags(d)).sum(axis=1).A1
    assert (d > off).all()


@pytest.fixture(scope="module")
def lung_small():
    cfg = {"generator": "lung2_like", "scale": 0.02, "pattern_seed": 7,
           "values_seed_offset": 7}
    L = matrices.build(cfg, 4)
    b = np.random.default_rng(0).standard_normal(L.shape[0])
    return L, b, reference.LowerSolve(L)(b)


def test_lower_solve_in_lower_precision(lung_small):
    L, b, ref = lung_small
    assert reference.residual_max(L, ref, b) < 1e-13
    err32 = reference.forward_error(reference.LowerSolve(L, np.float32)(b),
                                    ref)
    err16 = reference.forward_error(
        reference.LowerSolve(L, ml_dtypes.bfloat16)(b), ref)
    assert err32 < 1e-5 and err16 > 1e-3


@pytest.mark.parametrize("dtype", [np.float64, ml_dtypes.bfloat16])
def test_lower_solve_of_many_columns_is_each_columns_solve(lung_small,
                                                           dtype):
    L, _, _ = lung_small
    B = np.random.default_rng(2).standard_normal((L.shape[0], 3))
    solve = reference.LowerSolve(L, dtype)
    X = solve(B)
    assert X.shape == B.shape
    for j in range(3):
        assert np.array_equal(X[:, j], solve(B[:, j]))


def test_ic0_matches_a_on_its_pattern():
    A = POISSON.laplacian(6, 5)
    L = reference.ic0(A)
    from repro.precond.factorize import ic0
    from repro.sparse.csr import CSR
    ours = CSR(indptr=A.indptr.astype(np.int64),
               indices=A.indices.astype(np.int64), data=A.data,
               shape=A.shape)
    assert np.allclose(L.data, ic0(ours).L.data, rtol=1e-14, atol=0)
    LLt = (L @ L.T).tocsr()
    pattern = A.copy()
    pattern.data[:] = 1.0
    assert np.allclose(LLt.multiply(pattern).toarray(), A.toarray())


def test_pcg_converges_and_bfloat16_departs():
    A = POISSON.laplacian(16, 16)
    L = reference.ic0(A)
    b = A @ np.random.default_rng(1).standard_normal(A.shape[0])
    x, hist = reference.pcg(A, b, L, tol=1e-5, maxiter=200)
    assert hist[-1] <= 1e-5 * hist[0]
    assert reference.residual_2norm(A, x, b) < 1e-5
    _, h10 = reference.pcg(A, b, L, tol=1e-5, maxiter=200, iterations=10)
    assert len(h10) == 11
    assert reference.history_gap(hist, h10, 10) == 0.0
    _, h16 = reference.pcg(A, b, L, tol=1e-5, maxiter=200,
                           dtype=ml_dtypes.bfloat16, iterations=10)
    assert reference.history_gap(h16, h10, 10) > 1e-3


def test_a_reading_that_is_not_a_number_compares_as_infinite():
    assert reference.forward_error(np.array([np.nan]), np.array([1.0])) \
        == np.inf


def test_a_generator_is_found_by_name_and_missing_only_for_its_file(
        tmp_path, monkeypatch):
    """A configuration names its generator; the one thing a name with no
    file lacks is that file."""
    cfg = {"generator": "tridiagonal_test", "n": 5}
    monkeypatch.setattr(matrices, "GENERATORS_DIR", tmp_path)
    with pytest.raises(FileNotFoundError, match="tridiagonal_test.py"):
        matrices.build(cfg, 1)
    (tmp_path / "tridiagonal_test.py").write_text(
        "import scipy.sparse as sp\n"
        "def build(config, seed):\n"
        "    return sp.eye(config['n'], format='csr') * (seed + 1)\n")
    assert matrices.build(cfg, 1).diagonal().tolist() == [2.0] * 5
