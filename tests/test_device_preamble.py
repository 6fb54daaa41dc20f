"""The transform's preamble inside device sweeps (solver/preamble.py).

`device_solve_fn` applies c = B'v as one product with B''s non-identity
rows where the payload's host preamble plan exists, and as the T factor's
own level schedule only where it does not.  Each case runs a transformed
sweep's device pipeline in float32 against the sequential float64
reference and reads which form the program's counters say was built.
"""
import numpy as np
import pytest

from repro.core.transform import host_preamble
from repro.obs import default_registry
from repro.precond import Preconditioner
from repro.solver import TriangularOperator, orient_lower, solve_csr_seq
from repro.solver import distributed as dist
from repro.sparse import generators

COUNTERS = ("sweep.device_preamble_spmv", "sweep.device_preamble_schedule",
            "sweep.device_preamble_entries")


def _totals() -> list:
    reg = default_registry()
    return [0 if reg.get(k) is None else reg.get(k).total()
            for k in COUNTERS]


def _reference(op, b: np.ndarray) -> np.ndarray:
    """The operator's sweep by plain forward substitution in float64."""
    cfg = op._config
    L_eff, reversed_ = orient_lower(op._L, cfg.get("side", "lower"),
                                    bool(cfg.get("transpose", False)))
    cols = b.reshape(b.shape[0], -1)
    if reversed_:
        cols = cols[::-1]
    x = np.stack([solve_csr_seq(L_eff, c) for c in cols.T], axis=1)
    if reversed_:
        x = x[::-1]
    return x.reshape(b.shape)


def _poisson_pair(**kw):
    A = generators.poisson2d_spd(32, 32)
    return A, Preconditioner.ic0(A, tune="avgLevelCost", cache=False, **kw)


def _case(name: str):
    """(operator whose device pipeline is checked, realization expected)."""
    if name == "poisson_forward":
        return _poisson_pair()[1].forward, "spmv"
    if name == "poisson_backward":
        op = _poisson_pair()[1].backward
        assert op._reversed
        return op, "spmv"
    if name == "lung2":
        L = generators.lung2_like(scale=0.02)
        return TriangularOperator.from_csr(L, tune="avgLevelCost",
                                           cache=False), "spmv"
    if name == "refactor":
        A, P = _poisson_pair()
        P.device_apply()                    # built on the old values
        old = P.forward._preamble_plan().B.data.copy()
        A2 = type(A)(indptr=A.indptr, indices=A.indices,
                     data=np.where(A.indices == np.repeat(
                         np.arange(A.n_rows), np.diff(A.indptr)),
                         1.5 * A.data, A.data), shape=A.shape)
        P.refactor(A2)
        op = P.forward
        # the new factor's B' values differ from the old ones
        assert not np.allclose(op._preamble_plan().B.data, old)
        return op, "spmv"
    if name == "sharded":
        op = _poisson_pair(mesh=dist.default_mesh())[1].forward
        assert op.engine == "sharded"
        return op, "spmv"
    assert name == "tfactor"
    op = _poisson_pair()[1].forward
    # a cap of one entry: B''s rows never fit, the plan is None
    op._runtime["preamble_plan"] = host_preamble(op.transformed, 1)
    return op, "tfactor"


@pytest.mark.parametrize("k", [None, 3], ids=["vector", "block"])
@pytest.mark.parametrize("name", ["poisson_forward", "poisson_backward",
                                  "lung2", "refactor", "sharded", "tfactor"])
def test_device_preamble_matches_the_sequential_solve(name, k):
    import jax.numpy as jnp
    op, realization = _case(name)
    assert not op.transformed.identity_preamble
    before = _totals()
    fn = op.device_solve_fn()
    spmv, scheduled, entries = (a - b for a, b in zip(_totals(), before))
    plan = op._preamble_plan()
    assert plan.realization == realization
    if realization == "spmv":
        assert (spmv, scheduled, entries) == (1, 0, plan.entries)
        assert "preamble_host" not in op._runtime
    else:
        assert (spmv, scheduled, entries) == (0, 1, 0)
    shape = (op.n,) if k is None else (op.n, k)
    b = np.random.default_rng(11).standard_normal(shape)
    y = np.asarray(fn(jnp.asarray(b, jnp.float32)))
    assert y.dtype == np.float32 and y.shape == shape
    x = _reference(op, b)
    # float32 sweeps of these well-conditioned factors: ~1e-7 relative
    assert np.abs(y - x).max() / np.abs(x).max() < 1e-5

