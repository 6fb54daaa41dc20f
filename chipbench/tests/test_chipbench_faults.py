"""A run with its timed path broken underneath comes out not correct: the
platform check is skipped, everything else runs as on the chip."""
import jax
import numpy as np
import pytest

from repro.solver import TriangularOperator


@pytest.fixture
def fresh_traces():
    """Broken engine code must be traced anew, and not stay cached."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _correct(harness, cell):
    return harness.run(cell, 5, 0.2, False)["correct"]


SWEEPS = ["lung2.sweep.rhs1", "lung2.solve.refined"]


@pytest.mark.parametrize("cell", SWEEPS)
def test_answer_altered_where_it_is_produced(cell, off_chip,
                                             monkeypatch):
    solve = TriangularOperator.solve

    def altered(self, b, **kw):
        x = np.array(solve(self, b, **kw))
        x[len(x) // 2] += 1e-3 * np.abs(x).max()
        return x

    monkeypatch.setattr(TriangularOperator, "solve", altered)
    assert _correct(off_chip, cell) is False


@pytest.mark.parametrize("cell", SWEEPS + ["poisson2d.pcg"])
def test_step_that_returns_its_state_unchanged(cell, off_chip,
                                               monkeypatch, fresh_traces):
    from repro.solver import levelset
    monkeypatch.setattr(levelset, "_step_body",
                        lambda x, carry, c_pad, groups: (x, carry))
    assert _correct(off_chip, cell) is False


def test_pcg_answer_altered_where_it_is_produced(off_chip, monkeypatch):
    import repro.iterative
    cg = repro.iterative.cg

    def altered(*args, **kw):
        res = cg(*args, **kw)
        return res._replace(x=res.x.at[0].add(1.0))

    monkeypatch.setattr(repro.iterative, "cg", altered)
    assert _correct(off_chip, "poisson2d.pcg") is False


def test_pcg_with_its_preconditioner_left_out(off_chip, monkeypatch):
    """CG still converges without M, but the residual history departs
    from the preconditioned reference's."""
    from repro.precond import Preconditioner
    monkeypatch.setattr(Preconditioner, "device_apply",
                        lambda self, engine=None: (lambda r: r))
    assert _correct(off_chip, "poisson2d.pcg") is False
