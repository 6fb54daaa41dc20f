"""Closed-loop IC(0)-preconditioned CG solves through one compiled program.

Set-up builds the configuration's SPD matrix, factors it with the
program's IC(0), builds the preconditioner pair with the configuration's
fixed strategy (no operator cache), makes the traffic's pool of
`rhs_count` right-hand sides b = A x, x standard normal from the fixed
`rhs_pool_seed`, places them on the device, and compiles
`jax.jit(cg(A, b, preconditioner=P, tol, maxiter))` once, as users of
the Krylov layer do.  Each call of the window solves the next right-hand
side of the pool in an order drawn from `--seed`, and copies the answer
back to the host.  The pool is the same for every seed because a
right-hand side sets the work: its iteration count (37 to 43 at 512 x
512) would otherwise move the time per solve from seed to seed.

Correctness: every answer's float64 residual ||b - A x|| / ||b|| against
the benchmark's own A, a count of solves that did not converge (failed
calls), and, for `REFERENCE_SOLVES` answers drawn from the seed, the
widest relative gap between the program's residual-norm history and that
of a plain float64 PCG with a plain IC(0) factor over the first
`HISTORY_ITERATIONS` iterations: that gap covers the preconditioner's
sweeps inside the loop, not only the loop's result.

Control: in the program's place, the plain PCG with every stored value
rounded to bfloat16 (`chipbench.reference.pcg`).
"""
from __future__ import annotations

import numpy as np

from chipbench import matrices, reference, steps, work as work_

# answers compared with a plain PCG's residual history, over its first
# HISTORY_ITERATIONS iterations: enough to apply the preconditioner ten
# times, while the float32 drift from float64 is still ~1e-6
REFERENCE_SOLVES = 1
HISTORY_ITERATIONS = 10


class State:
    pass


def setup(config: dict, traffic: dict, seed: int, phases) -> State:
    import jax
    import jax.numpy as jnp
    from repro.iterative import cg
    from repro.precond import Preconditioner
    from repro.precond.factorize import ic0
    from repro.sparse.csr import CSR
    st = State()
    st.traffic, st.config = traffic, config
    st.rng = np.random.default_rng([seed, 1])
    with phases("generate"):
        st.A = matrices.build(config, seed)
        n = st.A.shape[0]
        pool = traffic["rhs_pool_seed"]
        st.rhs = [(st.A @ np.random.default_rng([pool, k]).standard_normal(
                   n)).astype(np.float32)
                  for k in range(traffic["rhs_count"])]
        st.order = st.rng.permutation(len(st.rhs))
        A = CSR(indptr=st.A.indptr.astype(np.int64),
                indices=st.A.indices.astype(np.int64),
                data=st.A.data.copy(), shape=st.A.shape)
    with phases("factorize"):
        fac = ic0(A)
    with phases("transform_schedule"):
        P = Preconditioner.from_factors(fac, tune=config["strategy"],
                                        system=A, cache=False)
    st.P, st.steps = P, None
    st.nnz_l = fac.L.nnz
    tol, maxiter = config["tol"], config["maxiter"]

    def solve(rhs):
        return cg(A, rhs, preconditioner=P, tol=tol, maxiter=maxiter)

    with phases("stage"):
        st.b_dev = [jax.device_put(jnp.asarray(b)) for b in st.rhs]
        lowered = jax.jit(solve).lower(st.b_dev[0])
    with phases("compile"):
        st.solve = lowered.compile()
    with phases("warmup"):
        for i in range(traffic["warmup_calls"]):
            jax.block_until_ready(st.solve(st.b_dev[int(st.order[i])]))
    st.answers = []
    return st


def call(st: State, i: int, control: bool = False) -> None:
    k = int(st.order[i % len(st.rhs)])
    if control:
        import ml_dtypes
        x, hist = reference.pcg(st.A, st.rhs[k], _ref_factor(st),
                                tol=st.config["tol"],
                                maxiter=st.config["maxiter"],
                                dtype=ml_dtypes.bfloat16)
        converged = hist[-1] <= st.config["tol"] * hist[0]
        st.answers.append((k, x, len(hist) - 1, converged, hist))
        return
    res = st.solve(st.b_dev[k])
    x = np.asarray(res.x)
    its = int(res.iterations)
    hist = np.asarray(res.residual_norms)[:its + 1]
    st.answers.append((k, x, its, bool(res.converged), hist))


def end_to_end(st: State, latencies: list, elapsed: float) -> dict:
    return {"pcg_time_to_tol_s": elapsed / len(latencies)}


def counters(st: State) -> dict:
    if st.steps is None:
        st.steps = (steps.sweep_steps(st.P.forward)
                    + steps.sweep_steps(st.P.backward))
    its = [a[2] for a in st.answers]
    return {"pcg_steps": st.steps, "iterations": its}


def work(st: State) -> dict:
    """One CG iteration's work."""
    return work_.pcg_iteration(st.A.shape[0], st.A.nnz, st.nnz_l)


def release(st: State) -> None:
    st.solve = st.P = None
    st.b_dev = None


def _ref_factor(st: State):
    """The plain IC(0) factor of the benchmark's A, made once."""
    if getattr(st, "ref_L", None) is None:
        st.ref_L = reference.ic0(st.A)
    return st.ref_L


def check(st: State) -> tuple:
    """([(name, value, limit), ...], failed calls)."""
    limits = st.traffic["limits"]
    upto = HISTORY_ITERATIONS
    resid = max((reference.residual_2norm(st.A, x, st.rhs[k])
                 for k, x, _, _, _ in st.answers), default=np.inf)
    failed = sum(1 for a in st.answers if not a[3])
    gap = 0.0
    picks = st.rng.choice(len(st.answers),
                          size=min(REFERENCE_SOLVES, len(st.answers)),
                          replace=False)
    for p in picks:
        k, _, _, _, hist = st.answers[int(p)]
        _, ref_hist = reference.pcg(st.A, st.rhs[k], _ref_factor(st),
                                    tol=st.config["tol"],
                                    maxiter=st.config["maxiter"],
                                    iterations=upto)
        gap = max(gap, reference.history_gap(hist, ref_hist, upto))
    if not len(picks):
        gap = np.inf
    return [("resid", resid, limits["resid"]),
            ("hist_gap", gap, limits["hist_gap"])], failed
