"""jit-native Krylov drivers (ISSUE 4): convergence, batching, jit, history.

The acceptance-criterion test (`test_cg_acceptance_end_to_end`) runs the
full pipeline: ic0-derived, portfolio-tuned preconditioner; absolute
residual <= 1e-8 on a poisson2d_spd system; fewer iterations than
unpreconditioned CG; and the same solve under jax.jit for single and
batched right-hand sides.  Float64 iterations run inside a scoped
`jax.enable_x64()` — possible with no global config flip
because the device-native preconditioner path has no host callbacks.
"""
import numpy as np
import pytest

from repro.iterative import (SolveResult, as_matvec, as_preconditioner,
                             bicgstab, cg, device_matvec, gmres)
from repro.precond import Preconditioner
from repro.sparse import generators
from repro.sparse.csr import CSR


def nonsymmetric(n=120, seed=7):
    rng = np.random.default_rng(seed)
    A = generators.random_spd(n, avg_offdiag=2.5, seed=seed)
    return CSR(indptr=A.indptr, indices=A.indices,
               data=A.data + 0.25 * rng.uniform(-1, 1, A.nnz), shape=A.shape)


# -- adapters -----------------------------------------------------------------

def test_device_matvec_matches_csr():
    import jax.numpy as jnp
    A = nonsymmetric(n=60)
    mv = device_matvec(A)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(A.n_rows)
    np.testing.assert_allclose(np.asarray(mv(jnp.asarray(x, jnp.float32))),
                               A.matvec(x), rtol=1e-5, atol=1e-4)
    X = rng.standard_normal((A.n_rows, 3))
    np.testing.assert_allclose(np.asarray(mv(jnp.asarray(X, jnp.float32))),
                               A.matvec(X), rtol=1e-5, atol=1e-4)


def test_as_matvec_passthrough_and_reject():
    fn = as_matvec(lambda v: v)
    assert fn(3) == 3
    with pytest.raises(TypeError, match="CSR matrix or a callable"):
        as_matvec(42)


def test_as_preconditioner_adapters():
    import jax.numpy as jnp
    ident = as_preconditioner(None)
    assert ident(5) == 5
    fn = as_preconditioner(lambda r: 2 * r)
    assert fn(3) == 6
    with pytest.raises(TypeError, match="ambiguous"):
        as_preconditioner(generators.poisson2d_spd(3, 3))
    with pytest.raises(TypeError, match="preconditioner"):
        as_preconditioner(object())
    # a TriangularOperator resolves to its device pipeline
    from repro.solver import TriangularOperator
    L = generators.poisson2d_ic0(5, 5)
    op = TriangularOperator.from_csr(L, tune="no_rewriting", cache=False)
    apply = as_preconditioner(op)
    b = np.random.default_rng(0).standard_normal(L.n_rows)
    z = np.asarray(apply(jnp.asarray(b, jnp.float32)))
    np.testing.assert_allclose(z, op.solve(b), rtol=1e-4, atol=1e-4)


# -- cg -----------------------------------------------------------------------

def test_cg_matches_direct_float32():
    import jax.numpy as jnp
    A = generators.poisson2d_spd(9, 8)
    xt = np.random.default_rng(0).standard_normal(A.n_rows)
    b = jnp.asarray(A.matvec(xt), jnp.float32)
    res = cg(A, b, tol=1e-6)
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), xt, rtol=1e-3, atol=1e-3)


def test_cg_acceptance_end_to_end():
    """ISSUE 4 acceptance: tuned ic0-PCG to ||r|| <= 1e-8, fewer iterations
    than plain CG, jit-compatible for single and batched RHS."""
    import jax
    import jax.numpy as jnp
    from jax import enable_x64
    A = generators.poisson2d_spd(16, 16)
    rng = np.random.default_rng(0)
    xt = rng.standard_normal(A.n_rows)
    b_host = A.matvec(xt)
    Preconditioner.clear_pair_decisions()
    P = Preconditioner.ic0(A, tune="auto", cache=False)
    assert P.report is not None            # the portfolio actually ran
    with enable_x64():
        b = jnp.asarray(b_host)
        plain = cg(A, b, tol=0.0, atol=1e-8, maxiter=800)
        tuned = cg(A, b, preconditioner=P, tol=0.0, atol=1e-8, maxiter=800)
        assert bool(plain.converged) and bool(tuned.converged)
        assert float(tuned.final_residual()) <= 1e-8
        # the residual recorded in the history is the TRUE one
        r_true = b_host - A.matvec(np.asarray(tuned.x))
        assert np.linalg.norm(r_true) <= 2e-8
        assert int(tuned.iterations) < int(plain.iterations)
        # under jit: single and batched RHS
        jit_cg = jax.jit(lambda bb: cg(A, bb, preconditioner=P, tol=0.0,
                                       atol=1e-8, maxiter=800))
        rj = jit_cg(b)
        assert bool(rj.converged) and float(rj.final_residual()) <= 1e-8
        B = jnp.asarray(rng.standard_normal((A.n_rows, 4)))
        jit_cg_b = jax.jit(lambda bb: cg(A, bb, preconditioner=P, tol=0.0,
                                         atol=1e-8, maxiter=800))
        rb = jit_cg_b(B)
        assert bool(rb.converged.all())
        xr = np.linalg.solve(A.to_dense(), np.asarray(B))
        np.testing.assert_allclose(np.asarray(rb.x), xr, rtol=1e-5,
                                   atol=1e-6)


def test_cg_batched_columns_match_single():
    import jax.numpy as jnp
    from jax import enable_x64
    A = generators.poisson2d_spd(8, 8)
    rng = np.random.default_rng(1)
    B_host = rng.standard_normal((A.n_rows, 3))
    with enable_x64():
        B = jnp.asarray(B_host)
        resb = cg(A, B, tol=1e-10)
        for k in range(3):
            rk = cg(A, B[:, k], tol=1e-10)
            np.testing.assert_allclose(np.asarray(resb.x[:, k]),
                                       np.asarray(rk.x), rtol=1e-7,
                                       atol=1e-8)
        assert resb.iterations.shape == (3,)
        assert resb.converged.shape == (3,)


def test_residual_history_contract():
    import jax.numpy as jnp
    from jax import enable_x64
    A = generators.poisson2d_spd(8, 7)
    b_host = np.random.default_rng(2).standard_normal(A.n_rows)
    with enable_x64():
        b = jnp.asarray(b_host)
        res = cg(A, b, tol=1e-10, maxiter=300)
        h = np.asarray(res.residual_norms)
        it = int(res.iterations)
        assert h.shape == (301,)
        assert h[0] == pytest.approx(np.linalg.norm(b_host), rel=1e-12)
        assert np.isfinite(h[:it + 1]).all()
        assert np.isnan(h[it + 1:]).all()
        assert h[it] < h[0]
        assert float(res.final_residual()) == pytest.approx(h[it])


def test_maxiter_cap_reports_not_converged():
    import jax.numpy as jnp
    A = generators.poisson2d_spd(10, 10)
    b = jnp.asarray(np.ones(A.n_rows), jnp.float32)
    res = cg(A, b, tol=1e-12, maxiter=3)
    assert not bool(res.converged)
    assert int(res.iterations) == 3


def test_cg_x0_warm_start():
    import jax.numpy as jnp
    from jax import enable_x64
    A = generators.poisson2d_spd(8, 8)
    xt = np.random.default_rng(3).standard_normal(A.n_rows)
    with enable_x64():
        b = jnp.asarray(A.matvec(xt))
        cold = cg(A, b, tol=1e-10)
        warm = cg(A, b, x0=jnp.asarray(xt + 1e-6), tol=1e-10)
        assert int(warm.iterations) < int(cold.iterations)


def test_cg_rejects_bad_shape():
    with pytest.raises(ValueError, match=r"\(n,\) or \(n, k\)"):
        cg(generators.poisson2d_spd(3, 3), np.ones((3, 3, 3)))


# -- bicgstab / gmres ---------------------------------------------------------

def test_bicgstab_nonsymmetric_with_ilu0():
    import jax.numpy as jnp
    from jax import enable_x64
    A = nonsymmetric()
    xt = np.random.default_rng(4).standard_normal(A.n_rows)
    P = Preconditioner.ilu0(A, tune="no_rewriting", cache=False)
    with enable_x64():
        b = jnp.asarray(A.matvec(xt))
        plain = bicgstab(A, b, tol=1e-10)
        pre = bicgstab(A, b, preconditioner=P, tol=1e-10)
        assert bool(plain.converged) and bool(pre.converged)
        assert int(pre.iterations) < int(plain.iterations)
        np.testing.assert_allclose(np.asarray(pre.x), xt, rtol=1e-6,
                                   atol=1e-7)


def test_gmres_nonsymmetric_with_ilu0():
    import jax.numpy as jnp
    from jax import enable_x64
    A = nonsymmetric()
    xt = np.random.default_rng(5).standard_normal(A.n_rows)
    P = Preconditioner.ilu0(A, tune="no_rewriting", cache=False)
    with enable_x64():
        b = jnp.asarray(A.matvec(xt))
        plain = gmres(A, b, tol=1e-10)
        pre = gmres(A, b, preconditioner=P, tol=1e-10)
        assert bool(plain.converged) and bool(pre.converged)
        assert int(pre.iterations) < int(plain.iterations)
        np.testing.assert_allclose(np.asarray(pre.x), xt, rtol=1e-6,
                                   atol=1e-7)


def test_gmres_restart_still_converges():
    import jax.numpy as jnp
    from jax import enable_x64
    A = nonsymmetric(n=80, seed=9)
    xt = np.random.default_rng(6).standard_normal(A.n_rows)
    with enable_x64():
        b = jnp.asarray(A.matvec(xt))
        res = gmres(A, b, tol=1e-9, restart=8, maxiter=40)
        assert bool(res.converged)
        np.testing.assert_allclose(np.asarray(res.x), xt, rtol=1e-5,
                                   atol=1e-6)
        # a restart cycle caps the per-cycle iteration count
        assert int(res.iterations) > 8      # needed more than one cycle


def test_gmres_bicgstab_jit_batched():
    import jax
    import jax.numpy as jnp
    from jax import enable_x64
    A = nonsymmetric(n=90, seed=10)
    P = Preconditioner.ilu0(A, tune="no_rewriting", cache=False)
    rng = np.random.default_rng(7)
    with enable_x64():
        B = jnp.asarray(rng.standard_normal((A.n_rows, 3)))
        xr = np.linalg.solve(A.to_dense(), np.asarray(B))
        for drv in (bicgstab, gmres):
            rb = jax.jit(lambda bb: drv(A, bb, preconditioner=P,
                                        tol=1e-9))(B)
            assert bool(rb.converged.all()), drv.__name__
            np.testing.assert_allclose(np.asarray(rb.x), xr, rtol=1e-5,
                                       atol=1e-6)


# -- SolveResult --------------------------------------------------------------

def test_solve_result_is_pytree():
    import jax
    res = SolveResult(x=np.ones(3), converged=np.bool_(True),
                      iterations=np.int32(2),
                      residual_norms=np.ones(4))
    leaves = jax.tree_util.tree_leaves(res)
    assert len(leaves) == 4     # stats=None contributes no leaf
    rebuilt = jax.tree_util.tree_map(lambda x: x, res)
    assert isinstance(rebuilt, SolveResult)


def test_stats_attached_outside_jit_only():
    import jax
    import jax.numpy as jnp
    A = generators.poisson2d_spd(6, 6)
    P = Preconditioner.ic0(A, tune="no_rewriting", cache=False)
    b = jnp.asarray(np.ones(A.n_rows), jnp.float32)
    host = cg(A, b, preconditioner=P, tol=1e-5)
    assert host.stats is not None and host.stats["kind"] == "ic0"
    jitted = jax.jit(lambda bb: cg(A, bb, preconditioner=P,
                                   tol=1e-5))(b)
    assert jitted.stats is None
