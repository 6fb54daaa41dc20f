"""End-to-end preconditioned-solver benchmark -> BENCH_iterative.json.

The paper's payoff scenario measured for real: repeated L/L^T solves
inside a full PCG loop.  For each benchmark analogue (lung2-like,
torso2-like — SPD systems whose tril pattern equals the paper matrices'
structural analogues via `spd_from_lower`), this driver runs:

  * unpreconditioned CG              (iteration-count baseline),
  * IC(0)-PCG with `no_rewriting`    (level scheduling, no transform),
  * IC(0)-PCG pair-tuned ("auto" + measured re-ranking, CPU cost model),

each as ONE jitted float64 program whose M^-1 is the device-native
operator pair, and records iterations, residuals, factorization/tuning
time, schedule shapes, and warm per-solve wall time (min over reps).

Headline check (mirrors the ISSUE 4 acceptance criterion): tuned-schedule
PCG wall time <= `no_rewriting` PCG wall time on both analogues — the
transformation payoff compounds over the iteration loop, or at worst the
tuner picks `no_rewriting` itself.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from repro.core.portfolio import CostModel
from repro.iterative import cg
from repro.precond import Preconditioner
from repro.sparse import generators


def _solve_ms(fn, b, iters: int) -> float:
    """Warm wall time of one full jitted PCG solve (min over iters)."""
    import jax
    jax.block_until_ready(fn(b))            # compile + warm outside timer
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(b))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def bench_matrix(L, iters: int = 3, tol: float = 1e-8,
                 maxiter: int = 400, chunk: int = 256, max_deps: int = 16,
                 measure_top_k: int = 3, seed: int = 0) -> dict:
    """One analogue: baseline CG + the two PCG variants (module doc)."""
    import jax
    import jax.numpy as jnp
    from jax import enable_x64
    A = generators.spd_from_lower(L, seed=seed)
    rng = np.random.default_rng(seed)
    x_true = rng.standard_normal(A.n_rows)
    b_host = A.matvec(x_true)

    t0 = time.perf_counter()
    plain_p = Preconditioner.ic0(A, tune="no_rewriting", cache=False,
                                 chunk=chunk, max_deps=max_deps)
    plain_build_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    Preconditioner.clear_pair_decisions()
    tuned_p = Preconditioner.ic0(A, tune="auto", cache=False, chunk=chunk,
                                 max_deps=max_deps,
                                 cost_model=CostModel.cpu(),
                                 measure_top_k=measure_top_k)
    tuned_build_ms = (time.perf_counter() - t0) * 1e3

    out = {"n": A.n_rows, "nnz": A.nnz, "tol": tol,
           "nnz_L": plain_p.factors.L.nnz,
           "ic0_shift": plain_p.factors.shift}
    with enable_x64():
        b = jnp.asarray(b_host)
        base = cg(A, b, tol=tol, maxiter=maxiter)
        out["unpreconditioned"] = {
            "iterations": int(base.iterations),
            "converged": bool(base.converged),
            "residual": float(base.final_residual()),
        }
        for name, P, build_ms in (("no_rewriting", plain_p, plain_build_ms),
                                  ("tuned", tuned_p, tuned_build_ms)):
            fn = jax.jit(lambda bb, P=P: cg(A, bb, preconditioner=P,
                                            tol=tol, maxiter=maxiter))
            res = fn(b)
            err = float(np.abs(np.asarray(res.x) - x_true).max())
            out[name] = {
                "pick": P.strategy,
                "iterations": int(res.iterations),
                "converged": bool(res.converged),
                "residual": float(res.final_residual()),
                "max_err": err,
                "build_ms": round(build_ms, 1),
                "steps_fwd": P.forward.schedule.num_steps,
                "steps_bwd": P.backward.schedule.num_steps,
                "solve_ms": round(_solve_ms(fn, b, iters), 2),
            }
    out["pcg_fewer_iters_than_cg"] = bool(
        out["tuned"]["iterations"] < out["unpreconditioned"]["iterations"])
    # 10% timer-noise margin: when the tuner's measured guardrail picks
    # no_rewriting itself the two pipelines are identical and only noise
    # separates them
    out["tuned_not_slower"] = bool(
        out["tuned"]["solve_ms"] <= 1.10 * out["no_rewriting"]["solve_ms"])
    return out


def run(out_path="experiments/BENCH_iterative.json", scales=(0.08, 0.06),
        iters: int = 3, tol: float = 1e-8, maxiter: int = 400,
        measure_top_k: int = 3) -> dict:
    record = {
        "config": {"scales": list(scales), "iters": iters, "tol": tol,
                   "maxiter": maxiter, "measure_top_k": measure_top_k,
                   "chunk": 256, "max_deps": 16,
                   "cost_model": "cpu", "solver": "cg+ic0", "dtype": "f64"},
        "matrices": {},
    }
    for name, L in (
            (f"lung2_like@{scales[0]}", generators.lung2_like(scales[0])),
            (f"torso2_like@{scales[1]}", generators.torso2_like(scales[1]))):
        m = bench_matrix(L, iters=iters, tol=tol, maxiter=maxiter,
                         measure_top_k=measure_top_k)
        record["matrices"][name] = m
        print(f"{name}: n={m['n']} cg {m['unpreconditioned']['iterations']} "
              f"iters -> pcg {m['tuned']['iterations']} iters | "
              f"no_rewriting {m['no_rewriting']['solve_ms']}ms "
              f"(steps {m['no_rewriting']['steps_fwd']}"
              f"+{m['no_rewriting']['steps_bwd']}) vs tuned "
              f"{m['tuned']['solve_ms']}ms "
              f"(steps {m['tuned']['steps_fwd']}+{m['tuned']['steps_bwd']}, "
              f"pick={m['tuned']['pick']}) -> "
              f"not_slower={m['tuned_not_slower']}")
    if out_path:
        p = Path(out_path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(record, indent=2) + "\n")
    return record


if __name__ == "__main__":
    run()
