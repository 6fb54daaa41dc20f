"""Preconditioned CG through the full subsystem — the paper's §I motivation
("building block to preconditioners for sparse iterative solvers") end to
end, with zero hand-rolled solver code:

    A = poisson2d_spd(nx, ny)            # the user's SPD system
    P = Preconditioner.ic0(A, tune=...)  # numeric IC(0) + pair-tuned,
                                         #   cached TriangularOperators
    res = iterative.cg(A, b, preconditioner=P)

Both halves of M^-1 = (L L^T)^-1 run as ONE traceable device computation
(compiled T-factor preamble + width-bucketed schedule per sweep, forward L
and backward L^T via transpose=True), and the CG loop itself is a pure JAX
program — jit the whole solve if you like.  Compare the iteration counts
against unpreconditioned CG, and the schedule shapes across strategies.

    PYTHONPATH=src python examples/pcg_ic0.py
"""
import numpy as np
from jax import enable_x64

from repro.iterative import cg
from repro.precond import Preconditioner
from repro.sparse import generators


def main():
    A = generators.poisson2d_spd(24, 24)
    n = A.n_rows
    rng = np.random.default_rng(0)
    x_true = rng.standard_normal(n)
    b = A.matvec(x_true)

    with enable_x64():      # float64 outer iterations (M^-1 runs float32)
        import jax.numpy as jnp
        bj = jnp.asarray(b)

        base = cg(A, bj, tol=1e-8)
        print(f"{'unpreconditioned':>16s}  cg_iters={int(base.iterations):3d} "
              f"resid={float(base.final_residual()):.2e}")

        for tune in ("no_rewriting", "avgLevelCost", "auto"):
            P = Preconditioner.ic0(A, tune=tune)
            res = cg(A, bj, preconditioner=P, tol=1e-8)
            err = float(jnp.abs(res.x - x_true).max())
            sched = P.forward.schedule
            label = P.strategy if tune == "auto" else tune
            print(f"{tune:>16s}  cg_iters={int(res.iterations):3d} "
                  f"resid={float(res.final_residual()):.2e} err={err:.2e} "
                  f"sched_steps={sched.num_steps:3d} pick={label}")
            assert bool(res.converged), tune
            assert int(res.iterations) < int(base.iterations), \
                f"{tune}: preconditioning must cut iterations"

        # batched right-hand sides stream the same schedules once per step
        B = jnp.asarray(rng.standard_normal((n, 8)))
        P = Preconditioner.ic0(A, tune="auto")
        resb = cg(A, B, preconditioner=P, tol=1e-8)
        print(f"{'batched k=8':>16s}  cg_iters={np.asarray(resb.iterations)} "
              f"all_converged={bool(resb.converged.all())}")


if __name__ == "__main__":
    main()
