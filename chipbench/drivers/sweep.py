"""Closed-loop `TriangularOperator.solve` calls, one caller.

Set-up builds the configuration's factor with its values for the seed,
builds the operator with the configuration's fixed strategy (no operator
cache: every run pays the same transform), draws `rhs_count`
right-hand sides of `columns` columns each from the seed and warms up
with the window's own call.  Each call of the window is `op.solve(b,
max_refine=..., refine_tol=...)` on the next right-hand side in turn, as
the traffic file states: the host-path sweep that users call.  A
one-column right-hand side is a vector of shape (n,), as users pass it;
wider ones are (n, columns).

Correctness: every answer of a sample drawn from the seed (a reservoir
of `CHECK_SAMPLE` answers) is compared with scipy's float64 solve of the
same system, by forward error, and, where the traffic refines, by the
residual the operator refines to.  Any engine fallback, health event or
host-reference solve during the window counts as a failed call.

Control: the nearest precision below the stated one, in the program's
place: for float32 sweeps the plain sweep with every stored value in
bfloat16; for traffic that refines to a float64 result, the program's
own unrefined float32 path.
"""
from __future__ import annotations

import numpy as np

from chipbench import matrices, reference, steps, work as work_

CHECK_SAMPLE = 64


class State:
    pass


def setup(config: dict, traffic: dict, seed: int, phases) -> State:
    from repro.solver import TriangularOperator
    from repro.sparse.csr import CSR
    st = State()
    st.traffic = traffic
    st.rng = np.random.default_rng([seed, 1])
    with phases("generate"):
        st.L = matrices.build(config, seed)
        n, k = st.L.shape[0], traffic["columns"]
        dtype = np.float32 if traffic["max_refine"] == 0 else np.float64
        st.rhs = [st.rng.standard_normal((n, k)).astype(dtype)
                  for _ in range(traffic["rhs_count"])]
        if k == 1:
            st.rhs = [b[:, 0] for b in st.rhs]
        L = CSR(indptr=st.L.indptr.astype(np.int64),
                indices=st.L.indices.astype(np.int64),
                data=st.L.data.copy(), shape=st.L.shape)
    with phases("transform_schedule"):
        st.op = TriangularOperator.from_csr(L, tune=config["strategy"],
                                            cache=False)
    st.kw = {"max_refine": traffic["max_refine"],
             "refine_tol": traffic["refine_tol"]}
    with phases("first_call"):
        st.op.solve(st.rhs[0], **st.kw)
    with phases("warmup"):
        for i in range(1, traffic["warmup_calls"]):
            st.op.solve(st.rhs[i % len(st.rhs)], **st.kw)
    st.sample = []
    st.calls = 0
    st.control = st.steps = None
    stats = st.op.stats
    st.stats0 = (stats.fallbacks, stats.health_events)
    return st


def _control(st: State, b: np.ndarray) -> np.ndarray:
    if st.kw["max_refine"] > 0:
        return st.op.solve(b, max_refine=0)
    if st.control is None:
        import ml_dtypes
        st.control = reference.LowerSolve(st.L, ml_dtypes.bfloat16)
    return st.control(b)


def call(st: State, i: int, control: bool = False) -> None:
    k = i % len(st.rhs)
    b = st.rhs[k]
    x = _control(st, b) if control else st.op.solve(b, **st.kw)
    # reservoir sample of the answers, drawn from the seed
    if len(st.sample) < CHECK_SAMPLE:
        st.sample.append((k, x))
    else:
        j = int(st.rng.integers(0, st.calls + 1))
        if j < CHECK_SAMPLE:
            st.sample[j] = (k, x)
    st.calls += 1


def end_to_end(st: State, latencies: list, elapsed: float) -> dict:
    """Right-hand-side columns per second and the calls' 95th percentile,
    under the raw traffic's names (`sweep_*`) and the refined traffic's
    (`solve_*`): the same arithmetic, two metrics, so that the refined
    path's host noise does not widen the raw sweep's bound."""
    from chipbench.harness import percentile
    rate = len(latencies) * st.traffic["columns"] / elapsed
    p95 = percentile(latencies, 95) * 1e3
    return {"sweep_rhs_per_s": rate, "sweep_p95_ms": p95,
            "solve_rhs_per_s": rate, "solve_p95_ms": p95}


def counters(st: State) -> dict:
    if st.steps is None:
        st.steps = steps.sweep_steps(st.op)
    return {"sweep_steps": st.steps}


def work(st: State):
    """One call's work, where a call is one device sweep (no refinement)."""
    if st.traffic["max_refine"] != 0:
        return None
    return work_.sweep(st.L.shape[0], st.L.nnz, st.traffic["columns"])


def release(st: State) -> None:
    stats = st.op.stats
    st.failed = (stats.fallbacks - st.stats0[0]) \
        + (stats.health_events - st.stats0[1])
    st.op = None


def check(st: State) -> tuple:
    """([(name, value, limit), ...], failed calls)."""
    limits = st.traffic["limits"]
    refs = {}
    fwd, resid = 0.0, 0.0
    for k, x in st.sample:
        if k not in refs:
            refs[k] = reference.LowerSolve(st.L)(st.rhs[k])
        fwd = max(fwd, reference.forward_error(x, refs[k]))
        if "resid" in limits:
            resid = max(resid, reference.residual_max(st.L, x, st.rhs[k]))
    checks = [("fwd_err", fwd, limits["fwd_err"])]
    if "resid" in limits:
        checks.append(("resid", resid, limits["resid"]))
    return checks, st.failed
