"""Strategy-portfolio auto-tuner: pick the best transform per matrix.

The paper's conclusion is that no single rewrite wins everywhere — the
results "provide several hints on how to craft a collection of strategies".
This module makes that operational: a `StrategyPortfolio` enumerates
candidate strategies (the four shipped ones plus parameter sweeps), runs the
full transform + schedule compile for each, scores every candidate with an
analytic per-solve cost model, and returns a ranked `PortfolioReport`.

Cost model (per solve, microseconds; all constants calibratable):

    main     = steps * step_overhead_us
             + padded_flops * us_per_padded_flop      (width-bucketed tiles)
             + schedule_bytes * us_per_byte           (HBM streaming)
    preamble = nnz_T * us_per_preamble_nnz            (T-factor any-b charge)
    total    = main + preamble

`steps` and `padded_flops` come from the *compiled* LevelSchedule (so step
compaction and width bucketing are credited), `nnz_T` from TransformMetrics.
The defaults mirror the TPU roofline constants of benchmarks/solver_bench.py;
`CostModel.cpu()` is calibrated for the CPU scan engine, where per-step scan
overhead dominates.  An optional *measured* mode micro-benchmarks the top-k
candidates through the real engine and re-ranks them by wall time.

Strategy selection guidance (which matrix shapes favour which strategy) is
documented in docs/strategies.md; the end-to-end serving facade that consumes
this tuner is repro.solver.operator.TriangularOperator.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from ..obs import trace as _obs
from ..obs.metrics import default_registry as _default_registry
from ..sparse.csr import CSR
from .strategies import (AvgLevelCost, ConstrainedAvgLevelCost,
                         CriticalPathRewrite, ManualEveryK, NoRewrite,
                         Strategy, strategy_label)
from .transform import (TransformMetrics, TransformedSystem, host_preamble,
                        transform)

__all__ = ["CostModel", "PortfolioCandidate", "PortfolioReport",
           "PairReport", "StrategyPortfolio", "default_candidates",
           "default_cost_model_for", "make_strategy", "STRATEGY_REGISTRY"]

# stable strategy name -> zero-arg-constructible class (docs/strategies.md)
STRATEGY_REGISTRY = {
    "no_rewriting": NoRewrite,
    "avgLevelCost": AvgLevelCost,
    "manual_every_k": ManualEveryK,
    "constrained_avg": ConstrainedAvgLevelCost,
    "critical_path": CriticalPathRewrite,
}


def make_strategy(spec) -> Strategy:
    """Resolve a strategy spec: a Strategy instance passes through, a stable
    name string (see STRATEGY_REGISTRY) constructs the default instance."""
    if isinstance(spec, str):
        try:
            return STRATEGY_REGISTRY[spec]()
        except KeyError:
            raise ValueError(
                f"unknown strategy {spec!r}; expected one of "
                f"{sorted(STRATEGY_REGISTRY)} or a Strategy instance") from None
    if not hasattr(spec, "apply"):
        raise TypeError(f"not a Strategy: {spec!r}")
    return spec


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Calibratable constants of the analytic per-solve cost (microseconds).

    Defaults model a TPU chip (HBM ~819 GB/s, VPU ~4 TF/s f32, ~2 us grid
    step); `cpu()` re-weights for the jitted CPU scan engine where the
    per-step dispatch overhead dominates everything else; `sharded()`
    charges every step its all_gather family so the tuner ranks strategies
    by synchronization cost.
    """

    step_overhead_us: float = 2.0
    us_per_padded_flop: float = 1.0 / 4e6       # 4 TF/s  -> 4e6 flop/us
    us_per_byte: float = 1.0 / 819e3            # 819 GB/s -> 819e3 B/us
    us_per_preamble_nnz: float = 5e-3           # T-factor any-b charge
    # sharded serving (repro.solver.distributed): each step ends in exactly
    # one all_gather family, so the collective charge is latency x steps —
    # the paper's "95% fewer barriers" as a first-class tuning objective.
    # 0 (the default) models single-device serving
    collective_latency_us: float = 0.0

    @classmethod
    def cpu(cls) -> "CostModel":
        """Weights calibrated against the measured CPU scan engine
        (BENCH_schedule.json: ~10-16 us/step, flops nearly free)."""
        return cls(step_overhead_us=12.0, us_per_padded_flop=1.0 / 1e5,
                   us_per_byte=1.0 / 4e6, us_per_preamble_nnz=5e-3)

    @classmethod
    def sharded(cls, collective_latency_us: float = 5.0,
                base: "CostModel | None" = None) -> "CostModel":
        """`base` (default TPU weights) plus a per-step collective charge —
        the model for ShardedEngine serving, where every schedule step is
        one cross-device synchronization barrier (~1-10 us on an ICI/NVLink
        mesh, more over DCN; calibrate for the target fabric)."""
        return dataclasses.replace(
            base if base is not None else cls(),
            collective_latency_us=collective_latency_us)

    def calibrate(self, profile) -> "CostModel":
        """Refit the per-step constants from a measured `ScheduleProfile`
        (repro.obs.profile) and return the calibrated model.

        Least-squares of per-step time against per-step padded FLOPs and
        bytes, intercept -> `step_overhead_us`.  Two profiler realities
        are handled explicitly:

        * when the profile carries a collective split (sharded engines),
          the fit runs on COMPUTE time and `collective_latency_us` is set
          to the median per-step collective time — the objective the
          sharded ranking charges per step;
        * width-bucketed schedules often have (near-)constant per-step
          FLOPs/bytes, a degenerate design matrix.  Constant columns are
          excluded from the fit, their charge (at the model's existing
          rate) is subtracted out of the intercept, and the residual
          becomes the overhead — so `predict()` with the calibrated model
          still reproduces the fitted per-step time.
        """
        t_us = np.asarray(profile.step_ms, dtype=float) * 1e3
        if t_us.size == 0:
            return self
        updates: dict = {}
        coll = getattr(profile, "collective_ms", None)
        if coll is not None:
            coll_us = np.asarray(coll, dtype=float) * 1e3
            t_us = np.maximum(t_us - coll_us, 0.0)
            updates["collective_latency_us"] = float(np.median(coll_us))
        feats = [
            ("us_per_padded_flop",
             np.asarray(profile.step_padded_flops, dtype=float)),
            ("us_per_byte", np.asarray(profile.step_bytes, dtype=float)),
        ]
        included, excluded = [], []
        for name, col in feats:
            scale = max(1.0, float(np.abs(col).mean()))
            (included if float(col.std()) > 1e-9 * scale
             else excluded).append((name, col))
        design = np.column_stack(
            [np.ones_like(t_us)] + [col for _, col in included])
        coef, *_ = np.linalg.lstsq(design, t_us, rcond=None)
        coef = np.maximum(coef, 0.0)
        overhead = float(coef[0])
        for (name, _), v in zip(included, coef[1:]):
            updates[name] = float(v)
        for name, col in excluded:
            overhead -= getattr(self, name) * float(col.mean())
        updates["step_overhead_us"] = max(0.0, overhead)
        return dataclasses.replace(self, **updates)

    def predict(self, sched, metrics: TransformMetrics) -> dict:
        """Cost breakdown (us) for one compiled schedule + its transform."""
        steps_us = sched.num_steps * self.step_overhead_us
        flops_us = sched.padded_flops() * self.us_per_padded_flop
        bytes_us = sched.memory_bytes() * self.us_per_byte
        pre_us = metrics.nnz_T * self.us_per_preamble_nnz
        # collective count == step count (the sharded-body invariant that
        # count_all_gathers audits), so the charge scales with num_steps
        coll_us = sched.num_steps * self.collective_latency_us
        return {
            "steps_us": steps_us, "flops_us": flops_us,
            "bytes_us": bytes_us, "preamble_us": pre_us,
            "collectives_us": coll_us,
            "total_us": steps_us + flops_us + bytes_us + pre_us + coll_us,
        }


def default_cost_model_for(engine) -> "CostModel | None":
    """The auto-tune cost model an engine implies when the caller passes
    none: `CostModel.sharded()` for sharded engines (the serving
    configuration and the tuning objective must agree — each step is one
    collective there), else None (the single-device default).  The ONE
    definition both facades (`TriangularOperator.from_csr` and
    `Preconditioner._pair_decision`) consult, so operator-level and
    pair-level auto-tuning always rank with the same objective for the
    same mesh."""
    from ..solver.engines import ShardedEngine
    if isinstance(engine, ShardedEngine):
        return CostModel.sharded()
    return None


@dataclasses.dataclass
class PortfolioCandidate:
    """One scored (strategy, transform, schedule) triple.

    `ts`/`sched`/`strategy` are dropped by `slim()` (persistent caches store
    only the chosen artifact, not every candidate's)."""

    label: str
    predicted_us: float
    breakdown: dict
    steps: int
    num_levels: int
    padded_flops: int
    memory_bytes: int
    nnz_T: int
    metrics: TransformMetrics | None = None
    measured_us: float | None = None
    error: str | None = None
    measure_note: str | None = None     # timeout / outlier / failure detail
    strategy: Strategy | None = None
    ts: TransformedSystem | None = None
    sched: object | None = None

    def slim(self) -> "PortfolioCandidate":
        return dataclasses.replace(self, strategy=None, ts=None, sched=None)


@dataclasses.dataclass
class PortfolioReport:
    """Ranked tuner output: candidates[0] is the pick."""

    matrix: dict
    candidates: list
    cost_model: CostModel
    measured_top_k: int
    tune_ms: float

    @property
    def best(self) -> PortfolioCandidate:
        return self.candidates[0]

    def slim(self) -> "PortfolioReport":
        return dataclasses.replace(
            self, candidates=[c.slim() for c in self.candidates])

    def to_dict(self) -> dict:
        return {
            "matrix": self.matrix,
            "cost_model": dataclasses.asdict(self.cost_model),
            "measured_top_k": self.measured_top_k,
            "tune_ms": round(self.tune_ms, 2),
            "candidates": [{
                "rank": i, "label": c.label,
                "predicted_us": (None if not np.isfinite(c.predicted_us)
                                 else round(c.predicted_us, 1)),
                "measured_us": (None if c.measured_us is None
                                else round(c.measured_us, 1)),
                "steps": c.steps, "levels": c.num_levels,
                "padded_flops": c.padded_flops,
                "memory_bytes": c.memory_bytes, "nnz_T": c.nnz_T,
                "breakdown": {k: round(v, 2) for k, v in c.breakdown.items()},
                "error": c.error,
                "measure_note": c.measure_note,
            } for i, c in enumerate(self.candidates)],
        }

    def table(self) -> str:
        """Human-readable ranked table (what quickstart.py prints)."""
        hdr = (f"{'rank':>4}  {'strategy':<42} {'pred_us':>10} "
               f"{'meas_us':>10} {'steps':>6} {'levels':>6} "
               f"{'padded_flops':>12} {'nnz_T':>8}")
        lines = [hdr, "-" * len(hdr)]
        for i, c in enumerate(self.candidates):
            meas = f"{c.measured_us:10.1f}" if c.measured_us is not None \
                else f"{'-':>10}"
            if c.error is not None:
                lines.append(f"{i:>4}  {c.label:<42} {'FAILED':>10} "
                             f"{'-':>10}  {c.error[:40]}")
                continue
            lines.append(f"{i:>4}  {c.label:<42} {c.predicted_us:10.1f} "
                         f"{meas} {c.steps:>6} {c.num_levels:>6} "
                         f"{c.padded_flops:>12} {c.nnz_T:>8}")
        return "\n".join(lines)


@dataclasses.dataclass
class PairReport:
    """Joint tuning decision for a forward/backward triangular-operator pair.

    A preconditioner application M^-1 r is TWO sweeps back to back (L then
    L^T, or L then U), and the strategy is chosen ONCE for the pair: per
    candidate label, the pair cost is the sum of the per-side costs, and
    `best_label` minimizes that sum.  Ranking mirrors `tune()`'s contract —
    labels measured on BOTH sides rank first by measured sum; the rest
    follow by predicted sum (never interleaved: wall-clock and model cost
    are different scales).

    `fwd`/`bwd` keep the full per-side PortfolioReports, so per-sweep
    diagnostics (steps, padded FLOPs, nnz_T) stay inspectable.
    """

    fwd: PortfolioReport
    bwd: PortfolioReport
    combined: list                  # [{label, fwd_us, bwd_us, total_us,
    #                                  measured}] ranked, [0] is the pick
    best_label: str

    @property
    def tune_ms(self) -> float:
        return self.fwd.tune_ms + self.bwd.tune_ms

    def slim(self) -> "PairReport":
        return dataclasses.replace(self, fwd=self.fwd.slim(),
                                   bwd=self.bwd.slim())

    def to_dict(self) -> dict:
        return {
            "best_label": self.best_label,
            "combined": self.combined,
            "fwd": self.fwd.to_dict(),
            "bwd": self.bwd.to_dict(),
        }

    def table(self) -> str:
        hdr = (f"{'rank':>4}  {'strategy':<42} {'fwd_us':>10} "
               f"{'bwd_us':>10} {'pair_us':>10} {'scored':>9}")
        lines = [hdr, "-" * len(hdr)]
        for i, c in enumerate(self.combined):
            lines.append(f"{i:>4}  {c['label']:<42} {c['fwd_us']:>10.1f} "
                         f"{c['bwd_us']:>10.1f} {c['total_us']:>10.1f} "
                         f"{'measured' if c['measured'] else 'model':>9}")
        return "\n".join(lines)


def default_candidates() -> list:
    """The shipped portfolio: the four strategies plus parameter sweeps over
    ManualEveryK / ConstrainedAvgLevelCost / CriticalPathRewrite."""
    return [
        NoRewrite(),
        AvgLevelCost(),
        ManualEveryK(k=5),
        ManualEveryK(k=10),
        ManualEveryK(k=20),
        ConstrainedAvgLevelCost(),                          # a=8, b=64
        ConstrainedAvgLevelCost(alpha=16, beta=128),
        ConstrainedAvgLevelCost(alpha=4, beta=32),
        CriticalPathRewrite(beta=8),
        CriticalPathRewrite(beta=32),
    ]


class StrategyPortfolio:
    """Enumerate -> transform -> compile -> score -> rank.

    candidates:     Strategy instances to try (default_candidates() if None).
    cost_model:     CostModel constants (TPU defaults; CostModel.cpu() for
                    CPU-engine calibration).
    chunk/max_deps/dtype: schedule-compiler configuration, forwarded to
                    schedule_for_transformed.
    measure_top_k:  if > 0, micro-benchmark the k model-best candidates with
                    the real engine (preamble included) and re-rank those
                    by measured wall time.
    measure_iters:  timing repetitions per measured candidate.
    measure_timeout_s: wall-clock budget per measured candidate — sampling
                    stops at the deadline and whatever was collected
                    decides (a pathologically slow candidate must not hang
                    the whole tuning run).
    measure_outlier_ratio: when the samples of one candidate disagree by
                    more than this factor (a scheduler hiccup or GC pause
                    polluting a rep), the candidate is re-measured once
                    and the extra samples are pooled in; the recorded time
                    is the pooled minimum (the microbenchmark noise
                    floor).  What happened is recorded on the candidate's
                    `measure_note`.
    engine:         engine used by the measured mode — a registered name,
                    an Engine from repro.solver.engines, or None for the
                    default scan engine (resolved through the registry).
    """

    def __init__(self, candidates=None, cost_model: CostModel | None = None,
                 chunk: int = 256, max_deps: int = 16, dtype=np.float32,
                 measure_top_k: int = 0, measure_iters: int = 3,
                 measure_timeout_s: float = 10.0,
                 measure_outlier_ratio: float = 4.0,
                 engine=None):
        self.candidates = (default_candidates() if candidates is None
                           else list(candidates))
        self.cost_model = cost_model if cost_model is not None else CostModel()
        self.chunk, self.max_deps, self.dtype = chunk, max_deps, dtype
        self.measure_top_k = measure_top_k
        self.measure_iters = measure_iters
        self.measure_timeout_s = measure_timeout_s
        self.measure_outlier_ratio = measure_outlier_ratio
        self.engine = engine

    def tune(self, L: CSR) -> PortfolioReport:
        with _obs.span("portfolio.tune", n=L.n_rows,
                       candidates=len(self.candidates),
                       measure_top_k=self.measure_top_k) as sp:
            report = self._tune(L)
            sp.set(best=report.best.label, tune_ms=report.tune_ms)
        reg = _default_registry()
        with reg.lock:
            reg.counter("portfolio_tunes", "portfolio tuning runs").inc()
            failures = reg.counter(
                "portfolio_candidate_failures",
                "candidates whose transform/compile raised")
            notes = reg.counter(
                "portfolio_measure_notes",
                "measured-mode anomalies by kind "
                "(timeout|outliers|measure_failed)")
            for c in report.candidates:
                if c.error is not None:
                    failures.inc()
                if c.measure_note:
                    kind = ("timeout" if c.measure_note.startswith("timeout")
                            else "measure_failed"
                            if c.measure_note.startswith("measure failed")
                            else "outliers")
                    notes.inc(kind=kind)
        return report

    def _tune(self, L: CSR) -> PortfolioReport:
        import time
        from ..solver.schedule import schedule_for_transformed
        t0 = time.perf_counter()
        scored: list[PortfolioCandidate] = []
        failed: list[PortfolioCandidate] = []
        for strat in self.candidates:
            label = strategy_label(strat)
            try:
                ts = transform(L, strat, validate=False, codegen=False)
                sched = schedule_for_transformed(
                    ts, chunk=self.chunk, max_deps=self.max_deps,
                    dtype=self.dtype)
            except Exception as e:  # a candidate blowing up must not kill
                failed.append(PortfolioCandidate(   # the whole tuning run
                    label=label, predicted_us=float("inf"), breakdown={},
                    steps=-1, num_levels=-1, padded_flops=-1,
                    memory_bytes=-1, nnz_T=-1,
                    error=f"{type(e).__name__}: {e}"))
                continue
            bd = self.cost_model.predict(sched, ts.metrics)
            scored.append(PortfolioCandidate(
                label=label, predicted_us=bd["total_us"], breakdown=bd,
                steps=sched.num_steps, num_levels=ts.metrics.num_levels_after,
                padded_flops=sched.padded_flops(),
                memory_bytes=sched.memory_bytes(),
                nnz_T=ts.metrics.nnz_T, metrics=ts.metrics,
                strategy=strat, ts=ts, sched=sched))
        if not scored:
            raise RuntimeError("every portfolio candidate failed: " +
                               "; ".join(c.error or "" for c in failed))
        scored.sort(key=lambda c: c.predicted_us)
        if self.measure_top_k > 0:
            # re-rank WITHIN the model's top-k by measured wall time; wall
            # time (CPU us) and model cost (device us) are different scales,
            # so measured candidates must never be sorted against unmeasured
            # predictions — the top-k stay ahead of the rest by model rank
            top = scored[:self.measure_top_k]
            for c in top:
                try:
                    self._measure(c, L.nnz)
                except Exception as e:
                    # a candidate whose MEASUREMENT fails (engine compile
                    # blew up, device lost mid-benchmark) is still a valid
                    # compiled artifact — park it at the bottom of the
                    # measured group instead of killing the tuning run
                    c.measured_us = float("inf")
                    c.measure_note = (f"measure failed: "
                                      f"{type(e).__name__}: {e}")
            top.sort(key=lambda c: c.measured_us)
            scored = top + scored[self.measure_top_k:]
        lv_before = scored[0].metrics.num_levels_before
        report = PortfolioReport(
            matrix={"n": L.n_rows, "nnz": L.nnz, "levels": lv_before},
            candidates=scored + failed, cost_model=self.cost_model,
            measured_top_k=self.measure_top_k,
            tune_ms=(time.perf_counter() - t0) * 1e3)
        return report

    def tune_pair(self, fwd: CSR, bwd: CSR) -> PairReport:
        """Tune a forward/backward operator pair jointly (see PairReport).

        `fwd` and `bwd` are the two ORIENTED lower-triangular systems of a
        preconditioner's sweeps (repro.solver.operator.orient_lower output
        for the L and L^T/U halves).  Each side runs the normal `tune()`;
        the pick minimizes the summed pair cost over labels that succeeded
        on both sides.
        """
        rf, rb = self.tune(fwd), self.tune(bwd)

        def _by_label(report):
            return {c.label: c for c in report.candidates if c.error is None}

        cf, cb = _by_label(rf), _by_label(rb)
        shared = [lbl for lbl in cf if lbl in cb]
        if not shared:
            raise RuntimeError("no strategy succeeded on both sides of the "
                               "operator pair")
        combined = []
        for lbl in shared:
            f, b = cf[lbl], cb[lbl]
            measured = f.measured_us is not None and b.measured_us is not None
            fwd_us = f.measured_us if measured else f.predicted_us
            bwd_us = b.measured_us if measured else b.predicted_us
            combined.append({"label": lbl, "fwd_us": round(fwd_us, 1),
                             "bwd_us": round(bwd_us, 1),
                             "total_us": round(fwd_us + bwd_us, 1),
                             "measured": measured})
        combined.sort(key=lambda c: (not c["measured"], c["total_us"]))
        return PairReport(fwd=rf, bwd=rb, combined=combined,
                          best_label=combined[0]["label"])

    def _measure(self, cand: PortfolioCandidate, max_entries: int) -> float:
        """End-to-end per-solve wall time (host preamble + compiled engine),
        dispatched through the engine registry; sets `cand.measured_us`
        (and `cand.measure_note` when something noteworthy happened).  The
        preamble is the operator's own host realization
        (`host_preamble`, bounded by `max_entries`, the factor's nnz).

        Hardened against flaky hosts: per-candidate sampling stops at the
        `measure_timeout_s` deadline, and a sample spread wider than
        `measure_outlier_ratio` triggers one re-measurement whose samples
        are pooled in.  The recorded time is the pooled MINIMUM — the
        standard microbenchmark noise floor, robust to one-sided timing
        noise (a rep can only ever be measured too slow, never too fast).
        """
        import time
        import jax.numpy as jnp
        from ..solver.engines import compile_source, resolve_engine
        from ..solver.levelset import to_device
        eng = resolve_engine(self.engine)
        # host-lowering engines (sharded) stage their own padded copy;
        # handing them an unpadded DeviceSchedule would just pin device
        # memory they never read (engines.compile_source)
        fn = eng.compile(compile_source(eng, cand.sched,
                                        lambda: to_device(cand.sched)))
        pre = host_preamble(cand.ts, max_entries)
        b = np.random.default_rng(0).standard_normal(cand.ts.A.n_rows)
        c = jnp.asarray(pre(b), dtype=cand.sched.dtype)  # builds B' once
        jnp.asarray(fn(c)).block_until_ready()         # compile outside timer

        def sample_until(deadline: float) -> list:
            out = []
            for _ in range(self.measure_iters):
                t0 = time.perf_counter()
                cc = jnp.asarray(pre(b), dtype=cand.sched.dtype)
                jnp.asarray(fn(cc)).block_until_ready()
                out.append((time.perf_counter() - t0) * 1e6)
                if time.perf_counter() >= deadline:
                    break
            return out

        deadline = time.perf_counter() + self.measure_timeout_s
        samples = sample_until(deadline)
        note = None
        if len(samples) < self.measure_iters:
            note = (f"timeout: {len(samples)}/{self.measure_iters} reps "
                    f"within {self.measure_timeout_s:g}s")
        elif max(samples) > self.measure_outlier_ratio * min(samples):
            spread = max(samples) / min(samples)
            samples += sample_until(
                time.perf_counter() + self.measure_timeout_s)
            note = (f"outliers (spread {spread:.1f}x > "
                    f"{self.measure_outlier_ratio:g}x): re-measured, "
                    f"{len(samples)} samples pooled")
        cand.measured_us = min(samples)
        cand.measure_note = note
        return cand.measured_us
