"""Preconditioner facade: factor A, tune the pair, serve M^-1 applications.

One call takes a user's system matrix to a ready preconditioner whose two
triangular sweeps run through the paper's transformed SpTRSV pipeline:

    P = Preconditioner.ic0(A, tune="auto")     # SPD:     M = L L^T
    P = Preconditioner.ilu0(A, tune="auto")    # general: M = L U
    z = P(r)                                   # z = M^-1 r, (n,) or (n, k)

Under the hood:

1. `repro.precond.factorize` produces the numeric zero-fill factor(s),
   with breakdown detection + diagonal shifting (`P.factors` records the
   shift actually applied).
2. The strategy portfolio tunes the PAIR jointly
   (`StrategyPortfolio.tune_pair`): both oriented sweeps are scored per
   candidate strategy and one strategy minimizing the summed pair cost is
   picked — a preconditioner application is always both sweeps, so
   per-side winners that disagree would optimize half the cost.  The pair
   decision is memoized under the SYSTEM matrix's fingerprint (plus the
   tuning configuration), so re-preconditioning the same A skips straight
   to operator construction.
3. Two cached `TriangularOperator`s are built with the winning strategy —
   forward `L`, backward `L^T` (ic0, via transpose=True) or `U` (ilu0,
   via side="upper") — sharing the operator memory/disk cache keyed by the
   factor fingerprints.

`P(r)` dispatches on the input: numpy in, float64 numpy out (host path,
optional iterative refinement); JAX array (or tracer) in, JAX array out
through `device_apply` — the whole M^-1 application as ONE traceable
device computation (compiled preamble + schedule per sweep, no host
callbacks), so the preconditioner drops straight into the jit-native
Krylov drivers of `repro.iterative` (see docs/iterative.md).
"""
from __future__ import annotations

import collections
import dataclasses as _dc
import hashlib
import threading

import numpy as np

from ..solver.operator import (TriangularOperator, compose_sweep_fn,
                               matrix_fingerprint, orient_lower)
from ..sparse.csr import CSR
from . import factorize
from .factorize import FactorResult

__all__ = ["Preconditioner", "IdentityPreconditioner"]


class Preconditioner:
    """Paired triangular operators applying M^-1 = (L L^T)^-1 or (L U)^-1.

    Construct via the classmethods (`ic0`, `ilu0`, or `from_factors` for a
    factor computed elsewhere); the constructor itself just binds the
    pieces.  Attributes:

    factors:  the FactorResult (factor CSRs, shift, attempts).
    forward:  TriangularOperator for the L sweep.
    backward: TriangularOperator for the L^T / U sweep.
    report:   slim PairReport when tune="auto" ran, else None.
    strategy: the strategy label both operators were compiled with.
    """

    # (system fingerprint, kind, config) -> (Strategy, slim PairReport):
    # re-preconditioning the same A re-uses the pair decision without
    # re-running the portfolio (the compiled operators are cached
    # separately, under the FACTOR fingerprints, by TriangularOperator).
    # Bounded LRU for the same reason as TriangularOperator._memory_cache:
    # a long-lived server over many matrices must not accumulate reports
    # forever
    _pair_decisions: collections.OrderedDict = collections.OrderedDict()
    _pair_decisions_max: int = 16
    # memo mutations must be atomic under concurrent preconditioner
    # construction (serving-tier background tuning); the tuning itself
    # runs OUTSIDE the lock — two racing builders may both tune, but the
    # memo never interleaves a move_to_end with an eviction
    _pair_lock = threading.RLock()

    def __init__(self, factors: FactorResult, forward: TriangularOperator,
                 backward: TriangularOperator, report=None):
        self.factors = factors
        self.forward = forward
        self.backward = backward
        self.report = report
        self.strategy = forward.strategy
        self._device_fns: dict = {}

    # -- construction ---------------------------------------------------------
    @classmethod
    def ic0(cls, A: CSR, tune="auto", **kwargs) -> "Preconditioner":
        """Incomplete-Cholesky preconditioner M = L L^T for SPD A.

        Factorization knobs (shift0, max_shift_attempts, breakdown_rtol,
        check_symmetric) ride in `factor_kwargs`; everything else is
        forwarded to TriangularOperator.from_csr — see `from_factors`.
        """
        factor_kwargs = kwargs.pop("factor_kwargs", None) or {}
        fac = factorize.ic0(A, **factor_kwargs)
        return cls.from_factors(fac, tune=tune, system=A, **kwargs)

    @classmethod
    def ilu0(cls, A: CSR, tune="auto", **kwargs) -> "Preconditioner":
        """Incomplete-LU preconditioner M = L U for general square A."""
        factor_kwargs = kwargs.pop("factor_kwargs", None) or {}
        fac = factorize.ilu0(A, **factor_kwargs)
        return cls.from_factors(fac, tune=tune, system=A, **kwargs)

    @classmethod
    def from_factors(cls, fac: FactorResult, tune="auto", *, system=None,
                     chunk: int = 256, max_deps: int = 16, dtype=np.float32,
                     engine=None, mesh=None, mesh_axis: str = "model",
                     cache: bool = True, cache_dir=None,
                     cost_model=None,
                     measure_top_k: int = 0) -> "Preconditioner":
        """Build the operator pair for an existing FactorResult.

        tune:   "auto" — joint pair tuning through the strategy portfolio
                (memoized per system/config when `system` is given); a
                stable strategy name or Strategy instance — both operators
                use it directly.
        system: the original matrix A (fingerprint key for the pair-
                decision memo; optional — without it "auto" still tunes,
                just never memoizes).
        mesh/mesh_axis: a jax Mesh serves BOTH sweeps through the sharded
                engine over `mesh_axis`, so M^-1 applications (host or
                device_apply) run under one mesh with no host round trips
                between the two sweeps (docs/distributed.md).  Mutually
                exclusive with engine=.
        Remaining arguments match TriangularOperator.from_csr.
        """
        if mesh is not None:
            from ..solver.engines import resolve_engine
            engine = resolve_engine(engine, mesh=mesh, mesh_axis=mesh_axis)
        report = None
        if tune == "auto":
            tune, report = cls._pair_decision(
                fac, system, chunk=chunk, max_deps=max_deps, dtype=dtype,
                engine=engine, cost_model=cost_model,
                measure_top_k=measure_top_k)
        op_kw = dict(chunk=chunk, max_deps=max_deps, dtype=dtype,
                     engine=engine, cache=cache, cache_dir=cache_dir)
        if fac.kind == "ic0":
            forward = TriangularOperator.from_csr(fac.L, tune, side="lower",
                                                  transpose=False, **op_kw)
            backward = TriangularOperator.from_csr(fac.L, tune, side="lower",
                                                   transpose=True, **op_kw)
        else:
            forward = TriangularOperator.from_csr(fac.L, tune, side="lower",
                                                  transpose=False, **op_kw)
            backward = TriangularOperator.from_csr(fac.U, tune, side="upper",
                                                   transpose=False, **op_kw)
        return cls(fac, forward, backward, report=report)

    @classmethod
    def _pair_decision(cls, fac: FactorResult, system, *, chunk, max_deps,
                       dtype, engine, cost_model, measure_top_k):
        """Joint pair tuning, memoized under the system fingerprint.

        Model ranking comes from `StrategyPortfolio.tune_pair`; when
        `measure_top_k > 0` the model's top-k candidates PLUS the
        `no_rewriting` baseline are re-timed through the COMPOSED device
        pipeline (flip + device preamble + schedule, both sweeps back to
        back) — i.e. exactly what a Krylov loop will execute, preamble
        realization included.  Measuring the served pipeline (not the
        host preamble) matters: a transform whose preamble is expensive
        on the device can model-rank well yet lose end to end,
        and including the baseline guarantees the pick is never slower
        than `no_rewriting` up to timer noise.
        """
        from ..core.portfolio import (StrategyPortfolio,
                                      default_cost_model_for)
        from ..solver.engines import resolve_engine
        eng = resolve_engine(engine)
        if cost_model is None:
            # same defaulting as TriangularOperator.from_csr: a pair that
            # will serve sharded sweeps is tuned against the cost model
            # that charges the per-step collective
            cost_model = default_cost_model_for(eng)
        key = None
        if system is not None:
            # like TriangularOperator.from_csr's cache cfg: the decision
            # is engine-independent UNLESS measured re-ranking ran — then
            # the pick depends on which engine was timed (cache_token:
            # sharded engines over different meshes time differently)
            cfg = (fac.kind, chunk, max_deps, np.dtype(dtype).name,
                   measure_top_k,
                   (getattr(eng, "cache_token", lambda: eng.name)()
                    if measure_top_k > 0 else None),
                   None if cost_model is None
                   else tuple(sorted(_dc.asdict(cost_model).items())))
            key = matrix_fingerprint(system) + "-" + hashlib.sha256(
                repr(cfg).encode()).hexdigest()[:16]
            with cls._pair_lock:
                hit = cls._pair_decisions.get(key)
                if hit is not None:
                    cls._pair_decisions.move_to_end(key)
                    return hit
        fwd_sys, _ = orient_lower(fac.L, "lower", False)
        if fac.kind == "ic0":
            bwd_sys, bwd_rev = orient_lower(fac.L, "lower", True)
        else:
            bwd_sys, bwd_rev = orient_lower(fac.U, "upper", False)
        tuner = StrategyPortfolio(chunk=chunk, max_deps=max_deps,
                                  dtype=dtype, cost_model=cost_model,
                                  measure_top_k=0, engine=engine)
        pair = tuner.tune_pair(fwd_sys, bwd_sys)
        best_label = pair.best_label
        if measure_top_k > 0:
            best_label = cls._measure_pair(pair, bwd_rev, engine=engine,
                                           chunk=chunk, max_deps=max_deps,
                                           dtype=dtype,
                                           top_k=measure_top_k)
        best = next(c for c in pair.fwd.candidates if c.label == best_label)
        decision = (best.strategy, pair.slim())
        if key is not None:
            with cls._pair_lock:
                cls._pair_decisions[key] = decision
                cls._pair_decisions.move_to_end(key)
                while len(cls._pair_decisions) > cls._pair_decisions_max:
                    cls._pair_decisions.popitem(last=False)
        return decision

    @staticmethod
    def _measure_pair(pair, bwd_reversed: bool, *, engine, chunk, max_deps,
                      dtype, top_k: int, reps: int = 3) -> str:
        """Re-rank candidate labels by measured wall time of one composed
        M^-1 application through the device pipeline; updates
        pair.combined in place and returns the winner.  The no_rewriting
        baseline is always measured (guardrail, see _pair_decision)."""
        import time as _time
        import jax
        import jax.numpy as jnp
        from ..core.transform import host_preamble
        from ..solver.engines import compile_source, resolve_engine
        from ..solver.levelset import to_device
        from ..solver.preamble import device_preamble
        from ..solver.schedule import schedule_for_preamble
        eng = resolve_engine(engine)
        labels = [c["label"] for c in pair.combined[:top_k]]
        if "no_rewriting" not in labels and any(
                c["label"] == "no_rewriting" for c in pair.combined):
            labels.append("no_rewriting")
        by_label_f = {c.label: c for c in pair.fwd.candidates
                      if c.error is None}
        by_label_b = {c.label: c for c in pair.bwd.candidates
                      if c.error is None}

        def side_fn(cand, reversed_, nnz):
            # host-lowering engines take the host schedules directly (the
            # same engines.compile_source branch the serving path's
            # _compiled_fn/_device_preamble takes)
            main_fn = eng.compile(compile_source(
                eng, cand.sched, lambda: to_device(cand.sched)))

            def scheduled():
                psched, src, row_pos = schedule_for_preamble(
                    cand.ts, chunk=chunk, max_deps=max_deps,
                    dtype=np.dtype(dtype))
                return eng.compile(compile_source(
                    eng, psched, lambda: to_device(psched))), src, row_pos

            # the SAME preamble and composition production runs
            # (device_solve_fn): what gets timed is what gets served
            pre = device_preamble(host_preamble(cand.ts, nnz),
                                  cand.sched.dtype, eng, scheduled)
            return compose_sweep_fn(main_fn, cand.sched.dtype, pre,
                                    reversed_)

        n = pair.fwd.matrix["n"]
        r = jnp.asarray(np.random.default_rng(0).standard_normal(n),
                        dtype=np.dtype(dtype))
        measured = {}
        for label in labels:
            f = side_fn(by_label_f[label], False, pair.fwd.matrix["nnz"])
            g = side_fn(by_label_b[label], bwd_reversed,
                        pair.bwd.matrix["nnz"])
            apply_fn = jax.jit(lambda v: g(f(v)))
            jax.block_until_ready(apply_fn(r))      # compile outside timer
            best = float("inf")
            for _ in range(reps):
                t0 = _time.perf_counter()
                jax.block_until_ready(apply_fn(r))
                best = min(best, _time.perf_counter() - t0)
            measured[label] = best * 1e6
        for c in pair.combined:
            if c["label"] in measured:
                # total_us becomes the measured composed-apply time;
                # fwd_us/bwd_us stay as the per-side MODEL estimates
                c.update(measured=True,
                         total_us=round(measured[c["label"]], 1))
        pair.combined.sort(key=lambda c: (not c["measured"], c["total_us"]))
        winner = min(measured, key=measured.get)
        pair.best_label = winner
        return winner

    @classmethod
    def clear_pair_decisions(cls) -> None:
        with cls._pair_lock:
            cls._pair_decisions.clear()

    def refactor(self, new_A: CSR, **factor_kwargs) -> "Preconditioner":
        """Numeric-only re-preconditioning for a new A on the SAME pattern.

        The refactorization fast path for time-stepping / Newton outer
        loops (docs/refactorization.md): re-runs only the ic0/ilu0 value
        sweep over the frozen pattern plan (`factorize.refactor`), then
        re-binds both triangular operators in place through
        `TriangularOperator.update_values` — pair tuning, level analysis,
        transformations, schedules and compiled engine executables are all
        reused.  Mutates this preconditioner and returns self.

        A pattern-changing A raises PatternMismatchError (build a fresh
        Preconditioner instead); `factor_kwargs` forwards shift0 /
        max_shift_attempts / breakdown_rtol to `factorize.refactor`.
        """
        fac = factorize.refactor(self.factors, new_A, **factor_kwargs)
        self.forward.update_values(fac.L)
        self.backward.update_values(fac.L if fac.kind == "ic0" else fac.U)
        self.factors = fac
        # composed device pipelines close over the old payloads' staged
        # schedules — drop them so the next device_apply recomposes
        self._device_fns.clear()
        return self

    # -- application ----------------------------------------------------------
    @property
    def n(self) -> int:
        return self.factors.n

    @property
    def operators(self) -> tuple:
        """(forward, backward) TriangularOperator pair."""
        return self.forward, self.backward

    def apply(self, r: np.ndarray, *, engine=None, max_refine: int = 0,
              refine_tol: float = 1e-10, health=None) -> np.ndarray:
        """z = M^-1 r on host: forward sweep then backward sweep.

        Refinement defaults OFF (max_refine=0): M^-1 is approximate by
        construction, and a fixed slightly-perturbed M only changes the
        Krylov convergence rate, not the attainable outer residual.  The
        sweeps themselves then run fp64-copy-free in the schedule dtype;
        only the returned z is cast up, preserving the facade's
        numpy-in / float64-numpy-out contract (module doc).

        health: solve-path health policy (HealthPolicy, a named level, or
        None for the REPRO_HEALTH_CHECKS environment default), applied to
        BOTH sweeps — a non-finite r raises a typed NumericalHealthError
        before any device work, a poisoned sweep raises / repairs / falls
        back per the policy, and engine failures walk the registry
        fallback chain (see TriangularOperator.solve, docs/robustness.md).
        Note the residual level of "strict" checks each triangular sweep
        against its own factor, not M^-1 against A — that approximation
        gap is by construction.
        """
        z = self.forward.solve(r, engine=engine, max_refine=max_refine,
                               refine_tol=refine_tol, health=health)
        z = self.backward.solve(z, engine=engine, max_refine=max_refine,
                                refine_tol=refine_tol, health=health)
        return np.asarray(z, dtype=np.float64)

    def device_apply(self, engine=None):
        """The full M^-1 application as a pure JAX callable: forward and
        backward device pipelines (reversal + preamble + compiled
        schedule, see TriangularOperator.device_solve_fn)
        composed back to back.  No host callbacks — safe inside
        jit/while_loop hot paths regardless of thread-local dtype config,
        which pure_callback is not (XLA may run callbacks on worker
        threads where a scoped enable_x64() does not apply).

        The callable is a `jax.tree_util.Partial` over both sweeps' tiles:
        pass it to `jax.jit` as an argument and a mesh preconditioner's
        tiles stay lane-sharded arguments; close over it and they become
        constants of the program (docs/distributed.md)."""
        key = ("device_apply", None if engine is None else str(engine))
        fn = self._device_fns.get(key)
        if fn is None:
            from jax.tree_util import Partial
            fn = self._device_fns[key] = Partial(
                _apply_pair, self.forward.device_solve_fn(engine),
                self.backward.device_solve_fn(engine))
        return fn

    def jax_apply(self, r, *, engine=None):
        """z = M^-1 r as a traceable JAX computation (device_apply)."""
        return self.device_apply(engine)(r)

    def __call__(self, r):
        """Dispatch on the input: JAX arrays/tracers route through
        jax_apply (jit-safe), numpy through the host path."""
        try:
            import jax
            is_jax = isinstance(r, jax.Array) or isinstance(
                r, jax.core.Tracer)
        except ModuleNotFoundError:         # pragma: no cover
            is_jax = False
        if is_jax:
            return self.jax_apply(r)
        return self.apply(np.asarray(r))

    def stats(self) -> dict:
        """Merged factorization + per-operator solve stats.

        The forward/backward counters tick on HOST `apply()`/solve calls
        only; applications through the traced `device_apply` pipeline
        (the Krylov hot path) execute inside jitted programs where host
        counters cannot observe them.
        """
        return {
            "kind": self.factors.kind,
            "n": self.n,
            "nnz_L": self.factors.L.nnz,
            "nnz_U": (self.factors.U.nnz if self.factors.U is not None
                      else None),
            "shift": self.factors.shift,
            "factor_attempts": self.factors.attempts,
            "strategy": self.strategy,
            "forward": self.forward.stats.to_dict(),
            "backward": self.backward.stats.to_dict(),
        }

    def __repr__(self) -> str:  # pragma: no cover
        return (f"Preconditioner(kind={self.factors.kind!r}, n={self.n}, "
                f"strategy={self.strategy!r}, shift={self.factors.shift})")


def _apply_pair(forward, backward, r):
    return backward(forward(r))


class IdentityPreconditioner:
    """M = I — the no-preconditioning baseline with the same interface
    (handy for apples-to-apples iteration counts in benchmarks/tests)."""

    def apply(self, r):
        return np.asarray(r)

    def __call__(self, r):
        return r

    def stats(self) -> dict:
        return {"kind": "identity"}
