"""Chip smoke test: drive the solve path once on a TPU at the paper's sizes.

    python chip_smoke.py              # one chip: sweeps, IC(0)-PCG, service
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

Every phase goes through the entry points users call, on matrices
generated from `--seed`, and checks its answers against scipy in float64:

* sweeps  -- `TriangularOperator.from_csr(lung2_like(1.0),
  tune="avgLevelCost")` and `from_csr(torso2_like(1.0),
  tune="no_rewriting")` on the default scan engine: one right-hand side
  and a block of 8, raw (`max_refine=0`, float32) and refined
  (`refine_tol=1e-10`), plus one transpose sweep through
  `sptrsv(L, y, transpose=True)`.
* pcg     -- `Preconditioner.ic0(poisson2d_spd(512, 512),
  tune="avgLevelCost")` inside a jitted float32 `cg`, checked with a
  float64 host residual.  Then a probe of float64 iterations under
  `jax.enable_x64()`, which reports what it found and gates nothing.
* service -- a `SolveService` answering 16 requests over both sweep
  patterns, each answer checked.  Entries stay untuned (`tune_mode="off"`):
  the background portfolio tune is host work outside this smoke test.
* sharded (`--chips 4` only) -- `lung2_like(1.0)` through
  `mesh=default_mesh()` and the one-mesh IC(0)-PCG of docs/distributed.md,
  both compared with a single-chip solve and with scipy; the
  all_gather count is printed against the step count.

No hidden fallback: every `ResilienceWarning` is an error for the whole
run, and after each phase the script asserts that no engine fell back,
that no health event fired, and that every host solve was served by the
expected engine (read from the `operator.solve` trace spans).  No
exception is caught, except by the float64 probe: a failing phase exits
non-zero.

The timings it prints are smoke numbers from one cold run, compiles
included where labelled: they show the path runs, they are not benchmark
numbers.  The last line of standard output is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Without a TPU, or without the program (`src/repro`) beside this file, the
script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import warnings
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# float32 raw sweeps: relative forward error max|x - x_ref| / max|x_ref|.
# A sweep chains ~10^2-10^3 dependent rows; float32 (eps 1.2e-7) stays
# orders below this bound, while bfloat16 or float16 arithmetic (eps
# 7.8e-3 / 9.8e-4) would exceed it on the first row that has a dependency.
RAW_REL_BOUND = 1e-4
# refined sweeps: the operator's float64 refinement target ...
REFINE_TOL = 1e-10
# ... and the forward error that target implies for these diagonally
# dominant factors; a float32 answer (error ~1e-7) fails it
REFINED_REL_BOUND = 1e-8
# IC(0)-PCG in float32: stop at a 1e-5 relative recursive residual, and
# require the float64 host residual within 1e-4 (the float32 recursion
# drifts from the true residual by a few ulps per iteration; a run that
# stopped short, or bfloat16 iterations, would not reach it).
PCG_TOL = 1e-5
PCG_HOST_BOUND = 1e-4
PCG_MAXITER = 1000
# sharded vs single-chip PCG: the runs differ only in summation order (a
# psum over nnz shards against one scatter-add), so their solutions agree
# to float32 rounding carried through the iterations
SHARDED_PCG_REL_DIFF = 1e-3
SERVICE_REQUESTS = 16
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


class Compiles:
    """Backend compiles, counted from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.seconds += duration

    def snapshot(self) -> tuple:
        with self._lock:
            return self.count, self.seconds


class Phase:
    """Times one phase, audits its solves, prints one smoke line.

    Audit: every `operator.solve` span was served by `engine` and not by
    the host reference, no `engine.fallback` or `health.violation` event
    fired, and every operator in `ops` counts no fallback and no health
    event.  Exceptions propagate: a failing phase ends the run."""

    def __init__(self, name: str, tracer, compiles: Compiles,
                 engine: str = "scan"):
        self.name, self.tracer, self.compiles = name, tracer, compiles
        self.engine = engine
        self.ops: list = []
        self.numbers: dict = {}

    def __enter__(self) -> "Phase":
        self.tracer.reset()
        self.c0 = self.compiles.snapshot()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        import jax
        wall = time.perf_counter() - self.t0
        spans = self.tracer.spans()
        solves = [s for s in spans if s.name == "operator.solve"]
        for s in solves:
            check(s.attrs.get("engine_used") == self.engine
                  and not s.attrs.get("reference"),
                  f"{self.name}: a solve was served by "
                  f"{s.attrs.get('engine_used')!r} "
                  f"(reference={s.attrs.get('reference')}), "
                  f"expected {self.engine!r}")
        events = [e[0] for s in spans for e in s.events]
        events += [e[0] for e in self.tracer.orphan_events()]
        bad = [e for e in events
               if e in ("engine.fallback", "health.violation")]
        check(not bad, f"{self.name}: resilience events {bad}")
        for op in self.ops:
            check(op.stats.fallbacks == 0 and op.stats.health_events == 0,
                  f"{self.name}: operator {op!r} counts "
                  f"fallbacks={op.stats.fallbacks} "
                  f"health_events={op.stats.health_events}")
        count, seconds = self.compiles.snapshot()
        stats = jax.devices()[0].memory_stats() or {}
        line = {"phase": self.name, "wall_s": wall,
                "host_solves": len(solves),
                "engine_used": sorted({s.attrs.get("engine_used")
                                       for s in solves}),
                "compiles": count - self.c0[0],
                "compile_s": seconds - self.c0[1],
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                **self.numbers}
        print("smoke (one cold run, not a benchmark):",
              json.dumps(line, default=float), flush=True)
        return False


def rel_err(x, ref) -> float:
    import numpy as np
    x = np.asarray(x, dtype=np.float64)
    return float(np.abs(x - ref).max() / np.abs(ref).max())


def scipy_lower(L):
    import scipy.sparse as sp
    return sp.csr_matrix((L.data.astype("float64"), L.indices, L.indptr),
                         shape=L.shape)


def timed(fn, reps: int = 3):
    """(result, first-call seconds, best steady seconds)."""
    t0 = time.perf_counter()
    out = fn()
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return out, first, best


def sweep_phase(name: str, L, tune: str, rng, tracer, compiles) -> None:
    """One pattern: single and 8-wide RHS, raw and refined, and one
    transpose sweep through sptrsv."""
    import numpy as np
    from scipy.sparse.linalg import spsolve_triangular
    from repro.solver import TriangularOperator, sptrsv

    Ls = scipy_lower(L)
    with Phase(f"sweep {name} {tune}", tracer, compiles) as ph:
        t0 = time.perf_counter()
        op = TriangularOperator.from_csr(L, tune=tune, cache=False)
        ph.numbers["setup_s"] = time.perf_counter() - t0
        ph.numbers["steps"] = op.schedule.num_steps
        ph.ops.append(op)
        check(op.engine == "scan", f"default engine is {op.engine!r}")
        for label, b in (("rhs1", rng.standard_normal(L.n_rows)),
                         ("rhs8", rng.standard_normal((L.n_rows, 8)))):
            ref = spsolve_triangular(Ls, b, lower=True)
            b32 = b.astype(np.float32)
            x, first, best = timed(lambda: op.solve(b32, max_refine=0))
            err = rel_err(x, ref)
            check(x.dtype == np.float32 and err <= RAW_REL_BOUND,
                  f"{name} {label} raw: dtype {x.dtype}, rel err {err:.3e}"
                  f" > {RAW_REL_BOUND}")
            ph.numbers[f"{label}_raw_first_call_s"] = first
            ph.numbers[f"{label}_raw_solve_s"] = best
            ph.numbers[f"{label}_raw_rel_err"] = err
            x, first, best = timed(lambda: op.solve(b, refine_tol=REFINE_TOL))
            err = rel_err(x, ref)
            resid = op.stats.last_residual
            check(resid <= REFINE_TOL and err <= REFINED_REL_BOUND,
                  f"{name} {label} refined: residual {resid:.3e}, rel err "
                  f"{err:.3e} > {REFINED_REL_BOUND}")
            ph.numbers[f"{label}_refined_solve_s"] = best
            ph.numbers[f"{label}_refined_residual"] = resid
            ph.numbers[f"{label}_refined_rel_err"] = err
        y = rng.standard_normal(L.n_rows)
        ref = spsolve_triangular(Ls.T.tocsr(), y, lower=False)
        t0 = time.perf_counter()
        x = sptrsv(L, y, transpose=True, tune=tune, cache=False,
                   refine_tol=REFINE_TOL)
        ph.numbers["transpose_call_s"] = time.perf_counter() - t0
        err = rel_err(x, ref)
        check(err <= REFINED_REL_BOUND,
              f"{name} transpose: rel err {err:.3e} > {REFINED_REL_BOUND}")
        ph.numbers["transpose_rel_err"] = err


def pcg_run(A, P, b64, matvec=None) -> tuple:
    """Jitted float32 PCG on A with preconditioner P; returns the smoke
    numbers and the solution.  The float64 host residual decides."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.iterative import cg

    mv = A if matvec is None else matvec

    def solve(rhs):
        return cg(mv, rhs, preconditioner=P, tol=PCG_TOL,
                  maxiter=PCG_MAXITER)

    b = jnp.asarray(b64, dtype=jnp.float32)
    t0 = time.perf_counter()
    compiled = jax.jit(solve).lower(b).compile()
    out = {"compile_s": time.perf_counter() - t0}
    mem = compiled.memory_analysis()
    if mem is not None:
        out["generated_code_bytes"] = mem.generated_code_size_in_bytes
        out["temp_bytes"] = mem.temp_size_in_bytes
    res, first, best = timed(lambda: jax.block_until_ready(compiled(b)),
                             reps=2)
    x = np.asarray(res.x, dtype=np.float64)
    r = b64 - A.matvec(x)
    host = float(np.linalg.norm(r) / np.linalg.norm(b64))
    iters = int(res.iterations)
    check(bool(res.converged) and host <= PCG_HOST_BOUND,
          f"PCG: converged={bool(res.converged)} after {iters} iterations, "
          f"float64 host residual {host:.3e} > {PCG_HOST_BOUND}")
    out.update(first_call_s=first, solve_s=best, iterations=iters,
               host_residual=host)
    return out, x


def x64_probe(A, P, b64) -> str:
    """Float64 CG iterations under jax.enable_x64 (the preconditioner
    still runs its float32 schedule).  Reports what happened; gates
    nothing, so this is the one place an exception is caught."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.iterative import cg
    try:
        with jax.enable_x64():
            b = jnp.asarray(b64, dtype=jnp.float64)
            t0 = time.perf_counter()
            res = jax.block_until_ready(jax.jit(lambda rhs: cg(
                A, rhs, preconditioner=P, tol=1e-10,
                maxiter=PCG_MAXITER))(b))
            wall = time.perf_counter() - t0
            x = np.asarray(res.x)
        host = float(np.linalg.norm(b64 - A.matvec(x))
                     / np.linalg.norm(b64))
        return (f"ran: x dtype {x.dtype}, converged={bool(res.converged)}, "
                f"{int(res.iterations)} iterations to tol 1e-10, float64 "
                f"host residual {host:.3e}, {wall:.3f} s with compile")
    except Exception as e:  # noqa: BLE001 - the probe reports, never gates
        return f"failed: {type(e).__name__}: {str(e)[:400]}"


def pcg_phase(rng, tracer, compiles) -> None:
    import numpy as np
    from repro.precond import Preconditioner
    from repro.sparse import generators

    A = generators.poisson2d_spd(512, 512)
    with Phase("pcg ic0 poisson2d_spd(512, 512) avgLevelCost", tracer,
               compiles) as ph:
        t0 = time.perf_counter()
        P = Preconditioner.ic0(A, tune="avgLevelCost", cache=False)
        ph.numbers["setup_s"] = time.perf_counter() - t0
        ph.ops += [P.forward, P.backward]
        check(P.forward.engine == P.backward.engine == "scan",
              f"preconditioner engines {P.forward.engine!r}/"
              f"{P.backward.engine!r}")
        ph.numbers["steps"] = [P.forward.schedule.num_steps,
                               P.backward.schedule.num_steps]
        b64 = A.matvec(rng.standard_normal(A.n_rows))
        numbers, _ = pcg_run(A, P, b64)
        ph.numbers.update(numbers)
    print("x64 probe (float64 CG iterations on the chip, not gated):",
          x64_probe(A, P, b64), flush=True)


def service_phase(mats: list, rng, tracer, compiles) -> None:
    import numpy as np
    from scipy.sparse.linalg import spsolve_triangular
    from repro.serving import SolveService

    with Phase("service", tracer, compiles) as ph:
        # a linger long enough that each pattern's 8 requests fill one
        # width-8 batch: admission fingerprints the whole matrix per submit
        svc = SolveService(max_width=8, max_linger_s=0.25, workers=2,
                           tune_mode="off", cache=False)
        work = [(mats[i % len(mats)],
                 rng.standard_normal(mats[i % len(mats)].n_rows))
                for i in range(SERVICE_REQUESTS)]
        try:
            t0 = time.perf_counter()
            reqs = [(L, b, svc.submit(b, L, tenant=f"t{i % 2}"))
                    for i, (L, b) in enumerate(work)]
            worst = 0.0
            for L, b, fut in reqs:
                x = fut.result(timeout=600)
                ref = spsolve_triangular(scipy_lower(L), b, lower=True)
                err = rel_err(x, ref)
                check(err <= RAW_REL_BOUND,
                      f"service answer rel err {err:.3e} > {RAW_REL_BOUND}")
                worst = max(worst, err)
            ph.numbers["all_answers_s"] = time.perf_counter() - t0
        finally:
            svc.close()
        snap = svc.snapshot()
        check(snap["completed"] == SERVICE_REQUESTS and snap["failed"] == 0,
              f"service completed {snap['completed']}/{SERVICE_REQUESTS}, "
              f"failed {snap['failed']}")
        ph.ops += [e.op for _, e in svc.registry.entries()]
        ph.numbers.update(requests=SERVICE_REQUESTS, worst_rel_err=worst,
                          batches=snap["batches"],
                          width_hist=snap["width_hist"])


def sharded_phase(seed: int, rng, tracer, compiles) -> None:
    """lung2_like(1.0) and the one-mesh IC(0)-PCG over every chip, each
    against a single-chip scan solve and against scipy / the float64 host
    residual."""
    import numpy as np
    from scipy.sparse.linalg import spsolve_triangular
    from repro.iterative.operators import device_matvec
    from repro.precond import Preconditioner
    from repro.precond.factorize import ic0
    from repro.solver import TriangularOperator
    from repro.solver.distributed import count_all_gathers, default_mesh
    from repro.sparse import generators

    mesh = default_mesh()
    L = generators.lung2_like(1.0, seed=seed + 7)
    Ls = scipy_lower(L)
    rhs = {"rhs1": rng.standard_normal(L.n_rows),
           "rhs8": rng.standard_normal((L.n_rows, 8))}
    refs = {k: spsolve_triangular(Ls, b, lower=True) for k, b in rhs.items()}
    single = {}
    with Phase("single-chip sweep lung2_like(1.0) avgLevelCost", tracer,
               compiles) as ph:
        op1 = TriangularOperator.from_csr(L, tune="avgLevelCost",
                                          cache=False)
        ph.ops.append(op1)
        for label, b in rhs.items():
            x, first, best = timed(lambda: op1.solve(b.astype(np.float32),
                                                     max_refine=0))
            err = rel_err(x, refs[label])
            check(err <= RAW_REL_BOUND,
                  f"single-chip {label}: rel err {err:.3e}")
            single[label] = np.asarray(x, dtype=np.float64)
            ph.numbers[f"{label}_solve_s"] = best
            ph.numbers[f"{label}_rel_err_vs_scipy"] = err
    with Phase("sharded sweep lung2_like(1.0) avgLevelCost", tracer,
               compiles, engine="sharded") as ph:
        t0 = time.perf_counter()
        op = TriangularOperator.from_csr(L, tune="avgLevelCost", mesh=mesh,
                                         cache=False)
        ph.numbers["setup_s"] = time.perf_counter() - t0
        ph.ops.append(op)
        audit = count_all_gathers(op.schedule, mesh)
        check(audit["families"] == audit["steps"] == op.schedule.num_steps,
              f"all_gather families {audit} != steps "
              f"{op.schedule.num_steps}")
        ph.numbers.update(devices=int(mesh.devices.size),
                          steps=audit["steps"],
                          all_gathers=audit["families"],
                          all_gather_calls=audit["calls"])
        for label, b in rhs.items():
            x, first, best = timed(lambda: op.solve(b.astype(np.float32),
                                                    max_refine=0))
            err = rel_err(x, refs[label])
            diff = rel_err(x, single[label])
            check(err <= RAW_REL_BOUND and diff <= RAW_REL_BOUND,
                  f"sharded {label}: rel err {err:.3e} vs scipy, "
                  f"{diff:.3e} vs the single-chip solve")
            ph.numbers[f"{label}_first_call_s"] = first
            ph.numbers[f"{label}_solve_s"] = best
            ph.numbers[f"{label}_rel_err_vs_scipy"] = err
            ph.numbers[f"{label}_rel_diff_vs_single_chip"] = diff

    A = generators.poisson2d_spd(512, 512)
    t0 = time.perf_counter()
    fac = ic0(A)                    # one factorization serves both runs
    factor_s = time.perf_counter() - t0
    b64 = A.matvec(rng.standard_normal(A.n_rows))
    with Phase("single-chip pcg ic0 poisson2d_spd(512, 512)", tracer,
               compiles) as ph:
        P1 = Preconditioner.from_factors(fac, tune="avgLevelCost", system=A,
                                         cache=False)
        numbers, x1 = pcg_run(A, P1, b64)
        ph.numbers.update(factor_s=factor_s, **numbers)
    with Phase("sharded pcg ic0 poisson2d_spd(512, 512)", tracer, compiles,
               engine="sharded") as ph:
        t0 = time.perf_counter()
        Pm = Preconditioner.from_factors(fac, tune="avgLevelCost", system=A,
                                         mesh=mesh, cache=False)
        ph.numbers["setup_s"] = time.perf_counter() - t0
        ph.ops += [Pm.forward, Pm.backward]
        numbers, xm = pcg_run(A, Pm, b64,
                              matvec=device_matvec(A, mesh=mesh))
        diff = rel_err(xm, x1)
        check(diff <= SHARDED_PCG_REL_DIFF,
              f"sharded PCG differs from the single-chip PCG by {diff:.3e}")
        ph.numbers.update(rel_diff_vs_single_chip=diff, **numbers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase over four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated matrix and vector")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the program is not beside this script "
              f"({SRC / 'repro'} is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"chip_smoke: device {device}, compile cache {cache_dir}",
          flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found; this smoke test runs only on the "
              "chip", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {device['count']}", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    run_phases(args.chips, args.seed)
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def run_phases(chips: int, seed: int) -> None:
    """The phases of one run (module doc); raises on the first failure."""
    import numpy as np
    from repro import obs
    from repro.core.resilience import ResilienceWarning
    from repro.sparse import generators
    warnings.filterwarnings("error", category=ResilienceWarning)
    tracer = obs.enable()
    compiles = Compiles()
    rng = np.random.default_rng(seed)
    if chips == 4:
        sharded_phase(seed, rng, tracer, compiles)
        return
    mats = [generators.lung2_like(1.0, seed=seed + 7),
            generators.torso2_like(1.0, seed=seed + 11)]
    sweep_phase("lung2_like(1.0)", mats[0], "avgLevelCost", rng, tracer,
                compiles)
    sweep_phase("torso2_like(1.0)", mats[1], "no_rewriting", rng, tracer,
                compiles)
    pcg_phase(rng, tracer, compiles)
    service_phase(mats, rng, tracer, compiles)


if __name__ == "__main__":
    sys.exit(main())
