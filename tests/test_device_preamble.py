"""The transform's preamble inside device sweeps (solver/preamble.py).

`device_solve_fn` applies c = B'v (the operator's `SweepPreamble`) as one
product with B''s non-identity rows where they fit its plan, and as the T
factor's own level schedule only where they do not.  Each case runs a
transformed sweep's device pipeline in float32 against the sequential
float64 reference and reads which form the program's counters say was
built.
"""
import numpy as np
import pytest

from repro.obs import default_registry
from repro.precond import Preconditioner
from repro.solver import TriangularOperator, orient_lower, solve_csr_seq
from repro.solver import distributed as dist
from repro.solver.preamble import SweepPreamble
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


def _cap(op) -> None:
    """Give `op` a preamble whose B' plan is capped to None (a cap of one
    entry: B''s rows never fit), so its device form is the T-factor
    schedule."""
    cfg = op._config
    op._runtime["sweep_preamble"] = SweepPreamble(
        op.transformed, 1, chunk=cfg["chunk"], max_deps=cfg["max_deps"],
        dtype=np.dtype(cfg["dtype"]))


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
        old = P.forward.preamble.B.data.copy()
        A2 = type(A)(indptr=A.indptr, indices=A.indices,
                     data=np.where(A.indices == np.repeat(
                         np.arange(A.n_rows), np.diff(A.indptr)),
                         1.5 * A.data, A.data), shape=A.shape)
        P.refactor(A2)
        op = P.forward
        # the new factor's B' values differ from the old ones
        assert not np.allclose(op.preamble.B.data, old)
        return op, "spmv"
    if name == "sharded":
        op = _poisson_pair(mesh=dist.default_mesh())[1].forward
        assert op.engine == "sharded"
        return op, "spmv"
    assert name == "tfactor"
    op = _poisson_pair()[1].forward
    _cap(op)
    return op, "tfactor"


@pytest.mark.parametrize("k", [None, 3], ids=["vector", "block"])
@pytest.mark.parametrize("name", ["poisson_forward", "poisson_backward",
                                  "lung2", "refactor", "sharded", "tfactor"])
def test_device_preamble_matches_the_sequential_solve(name, k):
    op, realization = _case(name)
    assert not op.transformed.identity_preamble
    before = _totals()
    fn = op.device_solve_fn()
    spmv, scheduled, entries = (a - b for a, b in zip(_totals(), before))
    pre = op.preamble
    assert pre.realization == realization
    if realization == "spmv":
        assert (spmv, scheduled, entries) == (1, 0, pre.entries)
        assert "schedule" not in vars(pre)
    else:
        assert (spmv, scheduled, entries) == (0, 1, 0)
    _assert_matches_reference(op, fn, k)


def _assert_matches_reference(op, fn, k) -> None:
    import jax.numpy as jnp
    shape = (op.n,) if k is None else (op.n, k)
    b = np.random.default_rng(11).standard_normal(shape)
    y = np.asarray(fn(jnp.asarray(b, jnp.float32)))
    assert y.dtype == np.float32 and y.shape == shape
    x = _reference(op, b)
    # float32 sweeps of these well-conditioned factors: ~1e-7 relative
    assert np.abs(y - x).max() / np.abs(x).max() < 1e-5


# ----------------------------------------------------------------------
# every strategy the pair tuner may time, in every orientation

STRATEGIES = ("no_rewriting", "avgLevelCost", "manual_every_k",
              "constrained_avg", "critical_path")
ORIENTATIONS = {"lower": ("lower", False), "lower_t": ("lower", True),
                "upper": ("upper", False), "upper_t": ("upper", True)}
_LUNG = generators.lung2_like(scale=0.02)


@pytest.fixture(scope="module")
def sweeps():
    """(strategy, orientation, capped) -> (operator, its device sweep, the
    counters' increments while that sweep was built), each built once."""
    built = {}

    def get(strategy, orientation, capped=False):
        key = (strategy, orientation, capped)
        if key not in built:
            side, transpose = ORIENTATIONS[orientation]
            M = _LUNG if side == "lower" else _LUNG.transpose()
            op = TriangularOperator.from_csr(M, tune=strategy, side=side,
                                             transpose=transpose, cache=False)
            if capped:
                _cap(op)
            before = _totals()
            fn = op.device_solve_fn()
            built[key] = (op, fn, [a - b for a, b in zip(_totals(), before)])
        return built[key]
    return get


def _assert_counted(op, counted) -> None:
    """The counters name the form the sweep was built with; a second
    build on the same engine is the cached one and counts nothing."""
    pre = op.preamble
    if op.transformed.identity_preamble:
        assert pre.device(op._engine, np.float32) is None
        expected = [0, 0, 0]
    elif pre.realization == "spmv":
        expected = [1, 0, pre.entries]
    else:
        expected = [0, 1, 0]
    assert counted == expected
    before = _totals()
    op.device_solve_fn()
    assert _totals() == before


@pytest.mark.parametrize("k", [None, 3], ids=["vector", "block"])
@pytest.mark.parametrize("orientation", list(ORIENTATIONS))
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_every_strategy_device_sweep_matches_the_sequential_solve(
        sweeps, strategy, orientation, k):
    op, fn, counted = sweeps(strategy, orientation)
    assert op.transformed.identity_preamble == (strategy == "no_rewriting")
    _assert_counted(op, counted)
    _assert_matches_reference(op, fn, k)


@pytest.mark.parametrize("orientation", ["lower", "lower_t"],
                         ids=["forward", "reversed"])
@pytest.mark.parametrize("strategy", STRATEGIES[1:])
def test_capped_plan_runs_the_tfactor_schedule(sweeps, strategy,
                                               orientation):
    op, fn, counted = sweeps(strategy, orientation, capped=True)
    assert op._reversed == (orientation == "lower_t")
    assert op.transformed.T.nnz > 0
    assert op.preamble.realization == "tfactor"
    assert "schedule" in vars(op.preamble)
    _assert_counted(op, counted)
    _assert_matches_reference(op, fn, None)
