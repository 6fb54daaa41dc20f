"""TriangularOperator: cached, auto-tuned, end-to-end SpTRSV facade.

The serving-path entry point (docs/architecture.md):

    op = TriangularOperator.from_csr(L, tune="auto")   # tune + compile once
    x  = op.solve(b)                                   # b: (n,) or (n, k)

`from_csr` runs the strategy-portfolio auto-tuner (repro.core.portfolio),
compiles the winning transform into a width-bucketed LevelSchedule, and
caches the whole artifact — transform, schedule, ranked tuner report —
in memory and persistently on disk (REPRO_CACHE_DIR or
~/.cache/repro-sptrsv).  Repeat construction for the same matrix +
configuration is a cache hit: no transform, no tuning, no schedule compile.

The cache key is split into a PATTERN fingerprint and a VALUE fingerprint
(`op-{pattern}-{config}-{values}.pkl`): the pattern part keys everything
derived from the sparsity structure alone (level analysis, the
transformation's replay plan, the tuner pick, tile layout), the value part
only the numeric payload.  A `from_csr` for a matrix whose pattern+config
matches a cached artifact but whose values differ derives the new payload
through the refactorization fast path (replay_transform +
repack_schedule_values — `stats.cache_source == "pattern"`) instead of
re-tuning.  `op.update_values(new_L)` is the in-place form for
time-stepping loops; a changed pattern raises `PatternMismatchError`
(docs/refactorization.md).

All four triangular sweeps share the one lower-triangular pipeline:
`side="lower"|"upper"` selects the stored triangle, `transpose=True` solves
with its transpose (the backward sweep of an ILU/IC preconditioner).  The
effective system is always reduced to a lower-triangular one — transposing
and/or reversing both axes (sparse.csr.reverse_both) — so every strategy,
the width-bucketed schedule compiler, and every registered engine work for
both sweeps with no kernel changes.  `op.transposed()` returns the adjoint
operator (same matrix, flipped sweep): it is the backward pass of the
forward solve, which is what `repro.solver.api.sptrsv` builds its
`jax.custom_vjp` on.  Orientation bits are part of the cache key.

Engines resolve through the repro.solver.engines registry: `engine=` takes
a registered name, an Engine instance, or None for the default scan engine;
unknown names raise with the registered options.

`solve` accepts a single right-hand side or a batched (n, k) block — the
engines and the Pallas kernel stream the schedule once for all k columns,
so one transformed matrix amortizes over many b's (the serving scenario).
Device math runs in the schedule dtype (float32 by default); full float64
accuracy is recovered by iterative refinement against the ORIGINAL matrix
(r = b - Lx in float64 on host, correct with another device solve), which
converges in 2-3 rounds for the diagonally-dominant systems here and makes
the operator match the sequential reference to ~1e-10 relative.

Per-solve stats (wall time, refinement rounds, residuals) are recorded on
`op.stats`.

Resilience (docs/robustness.md)
===============================
Every host `solve()` runs under a `SolveGuard` (repro.core.resilience):
non-finite right-hand sides raise a typed `NumericalHealthError`; a
non-finite (or, under `health="strict"`, inaccurate) solution is raised,
repaired by sanitize-and-refine, or replaced by the guaranteed host
reference solve per the resolved `HealthPolicy` (`health=` argument, else
the `REPRO_HEALTH_CHECKS` environment default).  When the preferred
engine's compile or solve fails — Pallas unavailable, dtype capability
rejected, mesh devices lost — the solve walks the registry's fallback
chain (`engines.engine_fallbacks`, e.g. pallas -> scan); every downgrade
is recorded in `OperatorStats` and surfaced as an `EngineFallbackWarning`,
and a chain with no survivor raises `EngineFallbackError` naming each
attempt.  `device_solve_fn` is the raw traced pipeline and is NOT
guarded — host-side checks cannot observe jitted applications (the
jit-native Krylov drivers carry their own in-loop breakdown detection).

Disk artifacts are crash- and concurrency-safe: writes go to a uniquely
named temporary sibling and publish via atomic `os.replace`, so a reader
can never observe a torn pickle; entries that still fail to load (corrupt
bytes, stale CACHE_VERSION) are quarantined to a `.bad/` sibling
directory — preserved for diagnosis, never silently deleted — with a
`CacheQuarantineWarning`, and the artifact is rebuilt.
"""
from __future__ import annotations

import collections
import hashlib
import os
import pickle
import threading
import time
import uuid
import warnings
from pathlib import Path

import numpy as np

from ..obs import trace as _obs
from ..obs.metrics import MetricsRegistry
from ..sparse.csr import CSR, reverse_both

__all__ = ["TriangularOperator", "OperatorStats", "matrix_fingerprint",
           "value_fingerprint", "default_cache_dir", "orient_lower",
           "compose_sweep_fn"]

# 3: cache key split into pattern/config/value segments; payloads carry the
# transform replay plan + schedule value plans for pattern-frozen derivation
# (version-2 artifacts quarantine cleanly through the stale-version path)
CACHE_VERSION = 3


def orient_lower(A: CSR, side: str, transpose: bool) -> tuple:
    """Reduce any triangular solve to a lower-triangular one.

    Returns (L_eff, reversed): solve(A, b, side, transpose) ==
    unreverse(solve_lower(L_eff, reverse(b))), where reverse flips axis 0
    iff `reversed`.  The four sweeps:

      (lower, False)  L x  = b   ->  L itself
      (upper, True)   U'x  = b   ->  U' (already lower)
      (lower, True)   L'x  = b   ->  P L' P, rows/cols reversed
      (upper, False)  U x  = b   ->  P U  P, rows/cols reversed

    (P is the reversal permutation; PMP of an upper-triangular M is lower-
    triangular with the identical dependency DAG, so level sets, transform
    strategies, and step compaction all apply unchanged.)
    """
    if side not in ("lower", "upper"):
        raise ValueError(f"side must be 'lower' or 'upper', got {side!r}")
    lower = side == "lower"
    if lower and not transpose:
        return A, False
    if not lower and transpose:
        return A.transpose(), False
    if lower:                       # lower, transpose
        return reverse_both(A.transpose()), True
    return reverse_both(A), True    # upper, no transpose


def _sweep(schedule_dtype, reversed_: bool, main_fn, pre_fn, v):
    import jax
    import jax.numpy as jnp
    out_dtype = v.dtype
    c = jnp.asarray(v, dtype=schedule_dtype)
    if reversed_:
        c = jnp.flip(c, axis=0)
    if pre_fn is not None:
        with jax.named_scope("sptrsv.preamble"):
            c = pre_fn(c)
    with jax.named_scope("sptrsv.main"):
        x = main_fn(c)
    if reversed_:
        x = jnp.flip(x, axis=0)
    return x.astype(out_dtype)


def compose_sweep_fn(main_fn, schedule_dtype, pre_fn, reversed_: bool):
    """Compose one triangular sweep as a pure JAX callable: axis reversal
    (transpose/upper orientations) -> the transform's preamble c = B'c ->
    main schedule -> un-reverse, in the schedule dtype, cast back to the
    input's dtype.

    The ONE definition of the served device pipeline: both
    `TriangularOperator.device_solve_fn` (production applications) and
    `Preconditioner._measure_pair` (measured pair tuning) build on it, so
    the tuner always times exactly the computation it selects for.
    `pre_fn` is `solver.preamble.SweepPreamble.device`'s callable: one
    product with B''s non-identity rows where they fit its plan, else the
    T-factor schedule; None for identity preambles.  The preamble and the
    main schedule run under the name scopes `sptrsv.preamble` and
    `sptrsv.main`, which label their device ops in a profile
    (docs/observability.md).

    The result is a `jax.tree_util.Partial` whose leaves are those of the
    engines' callables (their staged or placed tiles) and the preamble's
    arrays: an enclosing `jax.jit` that closes over it embeds them as
    constants, one that takes it as an argument takes them as arguments
    (docs/distributed.md).
    """
    import functools
    from jax.tree_util import Partial
    return Partial(functools.partial(_sweep, schedule_dtype, reversed_),
                   main_fn, pre_fn)


def default_cache_dir() -> Path:
    """REPRO_CACHE_DIR env override, else ~/.cache/repro-sptrsv."""
    env = os.environ.get("REPRO_CACHE_DIR")
    if env:
        return Path(env)
    return Path(os.path.expanduser("~/.cache")) / "repro-sptrsv"


def matrix_fingerprint(L: CSR, include_values: bool = True) -> str:
    """Stable hash of a CSR matrix: shape + pattern (+ values by default).

    Values are hashed because the compiled schedule bakes coefficients into
    its ELL tiles; pass include_values=False for a pattern-only key (e.g.
    reusing a tuner *decision* across numerically-refreshed factors).
    """
    h = hashlib.sha256()
    h.update(repr((CACHE_VERSION, L.shape)).encode())
    h.update(np.ascontiguousarray(L.indptr).tobytes())
    h.update(np.ascontiguousarray(L.indices).tobytes())
    if include_values:
        h.update(np.ascontiguousarray(L.data).tobytes())
    return h.hexdigest()[:32]


def value_fingerprint(L: CSR) -> str:
    """Stable hash of the numeric payload alone (16 hex chars).

    The value segment of the operator cache key: two matrices with the same
    pattern and different values share their pattern fingerprint but never
    their value fingerprint, so pattern-derived work (schedule layout,
    tuner pick, replay plan) is shared while numeric payloads stay distinct.
    """
    h = hashlib.sha256()
    h.update(repr((CACHE_VERSION, L.shape)).encode())
    h.update(np.ascontiguousarray(L.data).tobytes())
    return h.hexdigest()[:16]


class OperatorStats:
    """Per-operator stats plane: a VIEW over a `repro.obs` metrics
    registry (prefix "repro_operator"), updated by every solve().

    Every field is backed by one instrument — Counter, Gauge, or Text —
    in `self.registry`; reading a field reads the instrument, and
    Prometheus/JSON export reads the SAME instruments, so there is no
    second ledger to drift (docs/observability.md).  Updates are atomic
    per event: each record_* call commits its instruments under the
    registry's one shared lock, so concurrent `solve()` calls from a
    serving tier's worker threads never interleave a half-written record
    (`solves` and `total_solve_ms` always describe the same set of
    solves, which is what `repro.serving.ServiceStats` aggregation
    relies on).  Reads of individual fields are committed values;
    `to_dict()` snapshots the whole record consistently.

    Fallback counter semantics (made explicit after a double-count
    hazard: the old single counter incremented per retry attempt while
    its warning fired once per pair):

    * `fallbacks` counts DOWNGRADED DISPATCHES — every oriented device
      dispatch served by a non-requested engine.  A refined solve
      dispatches its engine 1 + rounds times, so `fallbacks` can
      legitimately exceed `solves` on a broken-engine operator; that is
      attempt accounting, not double counting.
    * `fallback_downgrades` counts UNIQUE (requested -> used) pairs —
      exactly the events that emit an `EngineFallbackWarning` (which
      warns once per pair).
    """

    _COUNTER_FIELDS = (
        ("solves", "host solve() calls completed"),
        ("rhs_columns", "right-hand-side columns solved"),
        ("refine_rounds", "iterative-refinement correction rounds"),
        ("value_updates", "update_values() calls served"),
        ("fallbacks", "downgraded engine dispatches (attempts)"),
        ("fallback_downgrades", "unique requested->used engine downgrades"),
        ("health_events", "health violations detected"),
    )
    _GAUGE_FIELDS = (
        ("total_solve_ms", 0.0, "cumulative solve wall time (ms)"),
        ("last_solve_ms", 0.0, "wall time of the last solve (ms)"),
        ("last_residual", float("nan"),
         "relative residual of the last solve"),
        ("tune_ms", 0.0, "wall time of the tuner run behind the payload"),
        ("last_update_ms", 0.0, "wall time of the last value update (ms)"),
    )
    _TEXT_FIELDS = (
        # "built" | "memory" | "disk" | "pattern" (payload derived from
        # an equal-pattern artifact via the refactorization fast path)
        ("cache_source", "how the payload was obtained"),
        ("last_fallback", "last downgrade as requested->used"),
        ("last_health_event", "last health event as stage:action"),
    )
    # to_dict() key order: the historical field order, with the new
    # fallback_downgrades riding directly after fallbacks
    _FIELDS = ("solves", "rhs_columns", "refine_rounds", "total_solve_ms",
               "last_solve_ms", "last_residual", "cache_source", "tune_ms",
               "value_updates", "last_update_ms", "fallbacks",
               "fallback_downgrades", "last_fallback", "health_events",
               "last_health_event")

    def __init__(self, cache_source: str = "built", tune_ms: float = 0.0,
                 registry: MetricsRegistry | None = None):
        self.registry = registry if registry is not None else \
            MetricsRegistry(prefix="repro_operator")
        r = self.registry
        self._lock = r.lock
        self._inst = {}
        for name, help in self._COUNTER_FIELDS:
            self._inst[name] = r.counter(name, help)
        for name, default, help in self._GAUGE_FIELDS:
            self._inst[name] = r.gauge(name, help, default=default)
        for name, help in self._TEXT_FIELDS:
            self._inst[name] = r.text(name, help)
        self._inst["cache_source"].set(cache_source)
        self._inst["tune_ms"].set(float(tune_ms))

    def to_dict(self) -> dict:
        with self._lock:
            return {name: getattr(self, name) for name in self._FIELDS}

    # -- atomic mutation (one lock acquisition per event) ---------------------
    def record_solve(self, *, ms: float, columns: int, rounds: int,
                     residual: float) -> None:
        with self._lock:
            self._inst["solves"].inc()
            self._inst["rhs_columns"].inc(columns)
            self._inst["refine_rounds"].inc(rounds)
            self._inst["total_solve_ms"].add(ms)
            self._inst["last_solve_ms"].set(ms)
            self._inst["last_residual"].set(residual)

    def record_fallback(self, last: str, *, new_pair: bool = False) -> None:
        """One downgraded dispatch; `new_pair` marks the first sighting
        of this (requested, used) pair (class doc: attempts vs. unique
        downgrades)."""
        with self._lock:
            self._inst["fallbacks"].inc()
            if new_pair:
                self._inst["fallback_downgrades"].inc()
            self._inst["last_fallback"].set(last)

    def record_health_event(self, last: str = "") -> None:
        """Count a health violation; the action suffix is committed by
        record_health_action once the recovery path is known."""
        with self._lock:
            self._inst["health_events"].inc()
            if last:
                self._inst["last_health_event"].set(last)

    def record_health_action(self, last: str) -> None:
        self._inst["last_health_event"].set(last)

    def record_value_update(self, *, ms: float, cache_source: str) -> None:
        with self._lock:
            self._inst["value_updates"].inc()
            self._inst["last_update_ms"].set(ms)
            self._inst["cache_source"].set(cache_source)

    def __repr__(self) -> str:    # pragma: no cover
        return "OperatorStats(" + ", ".join(
            f"{k}={v!r}" for k, v in self.to_dict().items()) + ")"


def _stats_field_property(name: str) -> property:
    """Field access for OperatorStats: reads/writes the backing
    instrument (writes keep the old dataclass-style assignment working;
    a counter write commits the delta so the monotonic series survives)."""

    def _get(self):
        return self._inst[name].value()

    def _set(self, v):
        inst = self._inst[name]
        with self._lock:
            if inst.kind == "counter":
                inst.inc(v - inst.value())
            else:
                inst.set(v)

    return property(_get, _set)


for _name, *_rest in (OperatorStats._COUNTER_FIELDS
                      + OperatorStats._GAUGE_FIELDS
                      + OperatorStats._TEXT_FIELDS):
    setattr(OperatorStats, _name, _stats_field_property(_name))
del _name, _rest


class TriangularOperator:
    """Compiled triangular-solve operator for one matrix (see module doc)."""

    # bounded LRU: payloads hold full transforms + ELL tiles (MB-scale per
    # large matrix), so a long-lived server over many matrices must not
    # accumulate them forever; overflow falls back to the disk cache
    _memory_cache_max: int = 16
    _memory_cache = collections.OrderedDict()
    # pattern segment of the key ("{pattern32}-{config16}") -> latest full
    # key stored: lets from_csr find an equal-pattern payload to derive
    # from without scanning the LRU
    _pattern_index: dict = {}
    # one lock for cache + index: the serving tier's worker and tuner
    # threads hit from_csr/update_values concurrently, and an OrderedDict
    # mid-move_to_end/popitem is not safe to mutate from two threads
    # (the disk side is already safe via atomic os.replace)
    _cache_lock = threading.RLock()

    @classmethod
    def _memory_get(cls, key: str):
        with cls._cache_lock:
            payload = cls._memory_cache.get(key)
            if payload is not None:
                cls._memory_cache.move_to_end(key)
            return payload

    @classmethod
    def _memory_put(cls, key: str, payload: dict) -> None:
        with cls._cache_lock:
            cls._memory_cache[key] = payload
            cls._memory_cache.move_to_end(key)
            cls._pattern_index[key.rsplit("-", 1)[0]] = key
            while len(cls._memory_cache) > cls._memory_cache_max:
                cls._memory_cache.popitem(last=False)

    @classmethod
    def _memory_get_pattern(cls, pattern_key: str):
        """Newest in-memory payload whose pattern+config segment matches
        (one lock acquisition for index lookup + LRU touch)."""
        with cls._cache_lock:
            return cls._memory_get(cls._pattern_index.get(pattern_key, ""))

    def __init__(self, L: CSR, payload: dict, cache_source: str):
        self._L = L                 # the ORIGINAL matrix, as handed in
        self._payload = payload     # update_values derives from + rebinds it
        self._ts = payload["ts"]    # transform of the oriented lower system
        self._sched = payload["sched"]
        self.report = payload.get("report")        # slim PortfolioReport|None
        self.strategy = payload["strategy"]        # winning strategy label
        cfg = payload["config"]
        self._config = cfg
        self.side = cfg.get("side", "lower")
        self.transpose = bool(cfg.get("transpose", False))
        # recorded by orient_lower at build time (single source of truth
        # for which sweeps reverse the axes)
        self._reversed = bool(payload["reversed"])
        # from_csr overrides this with the actually-resolved instance (which
        # may be an unregistered/custom-configured Engine the registry does
        # not know); name-only resolution is just the cached-payload default
        from .engines import get_engine
        self._engine_name = payload.get("engine", "scan")
        try:
            self._engine = get_engine(self._engine_name)
        except ValueError:          # custom engine: injected by from_csr
            self._engine = None
        self._build_kwargs = {}     # filled by from_csr for transposed()
        # staged schedule + compiled fns live on the payload, NOT the
        # operator, so memory-cache hits share them across from_csr calls
        # (the disk writer strips "_"-prefixed keys; jitted fns can't
        # pickle).  Maps engine name -> (engine instance, compiled fn); the
        # instance is kept for an identity check so two differently
        # configured engines sharing a name never swap compiled code.
        self._runtime = payload.setdefault("_runtime", {"compiled": {}})
        self.stats = OperatorStats(cache_source=cache_source,
                                   tune_ms=payload.get("tune_ms", 0.0))

    @property
    def engine(self) -> str:
        """Name of the default engine (back-compat accessor)."""
        return self._engine.name if self._engine is not None \
            else self._engine_name

    # -- construction ---------------------------------------------------------
    @classmethod
    def from_csr(cls, L: CSR, tune="auto", *, side: str = "lower",
                 transpose: bool = False, chunk: int = 256,
                 max_deps: int = 16, dtype=np.float32, engine=None,
                 mesh=None, mesh_axis: str = "model",
                 cache: bool = True, cache_dir=None, portfolio=None,
                 cost_model=None, measure_top_k: int = 0,
                 health=None) -> "TriangularOperator":
        """Build (or load) the operator for triangular L.

        side/transpose: which sweep this operator performs — `side` names
                the stored triangle ("lower" or "upper"), `transpose=True`
                solves with its transpose (L^T / U^T).  The effective
                system is reduced to lower-triangular form (orient_lower),
                so strategies/compiler/engines are shared by all sweeps.
        tune:   "auto" — run the StrategyPortfolio tuner and take its pick;
                a stable strategy name ("avgLevelCost", ...) or a Strategy
                instance — skip tuning and use that strategy as-is.
        engine: default execution engine — a registered name, an Engine
                from repro.solver.engines, or None for the scan engine.
        mesh/mesh_axis: serve sharded sweeps — a jax Mesh routes every
                solve through the ShardedEngine over `mesh_axis` (one
                all_gather family per step; docs/distributed.md).
                Mutually exclusive with `engine=`.  With tune="auto" and
                no explicit cost_model, tuning defaults to
                CostModel.sharded() so the tuner prices the per-step
                collective it will actually pay.  The compiled artifact
                is otherwise mesh-independent: fixed-strategy sharded and
                single-device operators for the same matrix share the
                cache (auto-tuned ones differ through the cost model in
                the key).
        cache:  look up / persist the compiled artifact (memory + disk,
                keyed by matrix fingerprint and configuration, orientation
                bits included).
        cost_model: tuner scoring constants (a portfolio CostModel, e.g.
                CostModel.cpu() when the scan engine serves on CPU); part
                of the cache key.  tune="auto" only.
        portfolio: a fully custom StrategyPortfolio (tune="auto" only);
                cost_model/measure_top_k are forwarded when constructing
                the default one.  A custom portfolio's configuration is not
                part of the cache key, so passing one disables caching for
                that build.
        health: health policy spec (same forms as solve()'s `health=`).
                Under a policy with `verify_schedule` (the "strict" level),
                the static verifier certifies the compiled artifact ONCE
                per built payload — the `ScheduleCertificate` rides the
                cached payload, so cache hits skip re-verification
                (docs/analysis.md).  Not part of the cache key: verifying
                does not change the artifact.
        """
        import dataclasses as _dc
        from ..core.portfolio import StrategyPortfolio, make_strategy
        from ..core.strategies import strategy_label
        from .engines import resolve_engine
        from .schedule import schedule_for_transformed

        if side not in ("lower", "upper"):
            raise ValueError(f"side must be 'lower' or 'upper', got {side!r}")
        eng = resolve_engine(engine, mesh=mesh, mesh_axis=mesh_axis)
        if tune == "auto" and cost_model is None:
            # sharded engines imply the cost model that charges their
            # per-step collective (docs/distributed.md)
            from ..core.portfolio import default_cost_model_for
            cost_model = default_cost_model_for(eng)
        cache = cache and portfolio is None
        tune_key = "auto" if tune == "auto" else \
            strategy_label(make_strategy(tune))
        # the compiled artifact is engine-independent (engine is a
        # solve-time choice), EXCEPT when measured re-ranking ran: then the
        # tuner's pick depends on which engine was timed (cache_token, not
        # name: sharded engines over different meshes time differently)
        cfg = {"tune": tune_key, "side": side, "transpose": bool(transpose),
               "chunk": chunk, "max_deps": max_deps,
               "dtype": np.dtype(dtype).name,
               "engine": (getattr(eng, "cache_token", lambda: eng.name)()
                          if measure_top_k > 0 else None),
               "measure_top_k": measure_top_k,
               "cost_model": (None if cost_model is None
                              else sorted(_dc.asdict(cost_model).items()))}
        build_kwargs = {"tune": tune, "side": side,
                        "transpose": bool(transpose), "chunk": chunk,
                        "max_deps": max_deps, "dtype": dtype, "engine": eng,
                        "cache": cache, "cache_dir": cache_dir,
                        "portfolio": portfolio, "cost_model": cost_model,
                        "measure_top_k": measure_top_k}
        # pattern segment keys the structure-derived artifact (levels,
        # transform plan, tuner pick, tile layout); the value segment pins
        # the numeric payload.  Same pattern + different values is served
        # by the refactorization fast path below.
        pattern_key = cls._pattern_cache_key(L, cfg)
        key = f"{pattern_key}-{value_fingerprint(L)}"
        from ..core.resilience import resolve_health_policy
        policy = resolve_health_policy(health)

        def _finish(payload, source):
            if policy.verify_schedule and "certificate" not in payload:
                # once per built payload: the certificate rides the cached
                # payload (memory + disk), so hits skip re-verification
                from ..analysis.verify import verify_operator_payload
                verify_operator_payload(
                    payload,
                    where=f"TriangularOperator.from_csr(n={L.n_rows})")
            op = cls(L, payload, cache_source=source)
            op._engine = eng        # the resolved instance, not a name
            op._build_kwargs = build_kwargs
            _obs.event("operator.cache", source=source, n=L.n_rows,
                       strategy=payload["strategy"])
            return op

        if cache:
            payload = cls._memory_get(key)
            if payload is not None:
                return _finish(payload, "memory")
            payload = cls._disk_load(key, cache_dir)
            if payload is not None:
                cls._memory_put(key, payload)
                return _finish(payload, "disk")
            # no exact hit: an equal-pattern artifact (any values) can be
            # numerically re-bound without re-tuning or re-compiling
            base = cls._memory_get_pattern(pattern_key)
            if base is None:
                base = cls._disk_load_pattern(pattern_key, cache_dir)
            if base is not None:
                payload = cls._try_derive_payload(base, L)
                if payload is not None:
                    cls._memory_put(key, payload)
                    cls._disk_store(key, payload, cache_dir)
                    return _finish(payload, "pattern")

        L_eff, reversed_ = orient_lower(L, side, bool(transpose))
        t0 = time.perf_counter()
        report = None
        with _obs.span("operator.tune", n=L.n_rows, tune=tune_key):
            if tune == "auto":
                tuner = portfolio if portfolio is not None else \
                    StrategyPortfolio(
                        chunk=chunk, max_deps=max_deps, dtype=dtype,
                        cost_model=cost_model, measure_top_k=measure_top_k,
                        engine=eng)
                report = tuner.tune(L_eff)
                best = report.best
                ts, sched, label = best.ts, best.sched, best.label
                report = report.slim()  # candidates keep stats, drop arrays
            else:
                strat = make_strategy(tune)
                label = strategy_label(strat)
                from ..core.transform import transform
                ts = transform(L_eff, strat, validate=False, codegen=False)
                sched = schedule_for_transformed(ts, chunk=chunk,
                                                 max_deps=max_deps,
                                                 dtype=dtype)
        payload = {"version": CACHE_VERSION, "strategy": label, "ts": ts,
                   "sched": sched, "report": report, "config": cfg,
                   "reversed": reversed_, "engine": eng.name,
                   "tune_ms": (time.perf_counter() - t0) * 1e3}
        if policy.verify_schedule:
            # certify BEFORE the payload is persisted so the certificate
            # rides the disk artifact too — _finish then has nothing to do
            from ..analysis.verify import verify_operator_payload
            verify_operator_payload(
                payload, where=f"TriangularOperator.from_csr(n={L.n_rows})")
        if cache:
            cls._memory_put(key, payload)
            cls._disk_store(key, payload, cache_dir)
        return _finish(payload, "built")

    def transposed(self) -> "TriangularOperator":
        """The adjoint operator: same stored triangle, flipped sweep.

        For a forward `L x = b` operator this is the `L^T y = g` operator —
        exactly the cotangent solve of the forward one, which is what
        `sptrsv`'s custom VJP runs as its backward pass.  Goes through
        from_csr, so it shares the memory/disk cache.
        """
        kw = dict(self._build_kwargs)
        if not kw:      # constructed without from_csr bookkeeping
            kw = {"tune": self.strategy, "side": self.side,
                  "transpose": self.transpose, "engine": self._engine}
        kw["transpose"] = not kw["transpose"]
        tune = kw.pop("tune")
        return TriangularOperator.from_csr(self._L, tune, **kw)

    # -- pattern-frozen refactorization (docs/refactorization.md) -------------
    @classmethod
    def _derive_payload(cls, base: dict, L_new: CSR) -> dict:
        """Re-bind an equal-pattern payload to new numeric values.

        Reuses everything structure-derived from `base` — level analysis,
        the winning strategy's transformation (replayed numerically via its
        commit log), the schedule's tile layout — and re-runs only the
        value packing.  Raises PatternMismatchError if the new values make
        the replayed transformation's pattern drift (exact cancellation
        creating/removing fill), ValueError if `base` predates the plans.
        """
        from ..core.transform import replay_transform
        # module attribute lookup, not a from-import: fault injection
        # (core.faults.corrupt_values_payload) patches the schedule module
        from . import schedule as _schedule
        cfg = base["config"]
        L_eff, reversed_ = orient_lower(L_new, cfg.get("side", "lower"),
                                        bool(cfg.get("transpose", False)))
        ts_new = replay_transform(L_eff, base["ts"],
                                  where="TriangularOperator.update_values")
        sched_new = _schedule.repack_schedule_values(
            base["sched"], ts_new.A.data, ts_new.diag)
        # the preamble keeps its value-free parts and repacks or re-derives
        # the value-bound ones (SweepPreamble.revalued)
        new_runtime: dict = {"compiled": {}}
        pre = base.get("_runtime", {}).get("sweep_preamble")
        if pre is not None:
            new_runtime["sweep_preamble"] = pre.revalued(ts_new)
        return {"version": CACHE_VERSION, "strategy": base["strategy"],
                "ts": ts_new, "sched": sched_new,
                "report": base.get("report"), "config": cfg,
                "reversed": reversed_, "engine": base.get("engine", "scan"),
                "tune_ms": base.get("tune_ms", 0.0),
                "_runtime": new_runtime}

    @classmethod
    def _try_derive_payload(cls, base: dict, L_new: CSR) -> dict | None:
        """_derive_payload for opportunistic from_csr use: a pattern drift
        or a pre-plan payload means "can't fast-path", not an error — the
        caller falls through to a full build."""
        from ..core.resilience import PatternMismatchError
        try:
            return cls._derive_payload(base, L_new)
        except (PatternMismatchError, ValueError):
            return None

    def update_values(self, new_L: CSR, *, health=None) -> "TriangularOperator":
        """Re-bind this operator to new numeric values on the SAME pattern.

        The refactorization fast path for time-stepping / Newton loops
        where the sparsity pattern is fixed and values change every step:
        level analysis, the graph transformation, the tuner's pick and the
        compiled engine executables are all reused — only the numeric
        payload is re-derived (transform replay + schedule value repack).

        Mutates the operator in place and returns self.  A matrix whose
        pattern differs from the frozen one raises PatternMismatchError
        (rebuild with from_csr instead); non-finite values raise
        NumericalHealthError under any health policy that checks inputs
        (`health=` accepts the same specs as solve()).
        """
        from ..core.resilience import (NumericalHealthError,
                                       PatternMismatchError,
                                       resolve_health_policy)
        from ..sparse.csr import same_pattern
        where = f"TriangularOperator.update_values(n={self.n})"
        if not same_pattern(new_L, self._L):
            if new_L.shape != self._L.shape:
                detail = f"shape {new_L.shape} != {self._L.shape}"
            elif new_L.nnz != self._L.nnz:
                detail = f"nnz {new_L.nnz} != {self._L.nnz}"
            elif not np.array_equal(new_L.indptr, self._L.indptr):
                detail = "row pointer drift"
            else:
                detail = "column index drift"
            raise PatternMismatchError(
                "matrix pattern differs from the frozen operator pattern; "
                "rebuild with from_csr", where=where, detail=detail)
        policy = resolve_health_policy(health)
        if policy.check_inputs and not np.all(np.isfinite(new_L.data)):
            raise NumericalHealthError(
                f"new matrix values contain non-finite entries in {where}",
                stage="input", where=where)
        t0 = time.perf_counter()
        with _obs.span("operator.update_values", n=self.n) as usp:
            cache = bool(self._build_kwargs.get("cache", False))
            cache_dir = self._build_kwargs.get("cache_dir")
            pattern_key = self._pattern_cache_key(new_L, self._config)
            key = f"{pattern_key}-{value_fingerprint(new_L)}"
            payload, source = None, "pattern"
            if cache:
                payload = self._memory_get(key)
                if payload is not None:
                    source = "memory"
                else:
                    payload = self._disk_load(key, cache_dir)
                    if payload is not None:
                        source = "disk"
                        self._memory_put(key, payload)
            derived = payload is None
            if derived:
                payload = self._derive_payload(self._payload, new_L)
            if policy.verify_schedule:
                # the structure was certified at build time; the fast path
                # re-audits only what the value re-bind changed (transform
                # replay facts + packed values/dinv) and fails BEFORE the
                # operator mutates or the payload is cached
                from ..analysis.verify import (audit_transformed_system,
                                               verify_schedule_values)
                audit_transformed_system(payload["ts"], where=where)
                verify_schedule_values(payload["sched"], payload["ts"].A,
                                       payload["ts"].diag, where=where)
            if derived and cache:
                self._memory_put(key, payload)
                self._disk_store(key, payload, cache_dir)
            usp.set(source=source)
        self._L = new_L
        self._payload = payload
        self._ts = payload["ts"]
        self._sched = payload["sched"]
        self._reversed = bool(payload["reversed"])
        self._runtime = payload.setdefault("_runtime", {"compiled": {}})
        self.stats.record_value_update(
            ms=(time.perf_counter() - t0) * 1e3, cache_source=source)
        return self

    # -- static verification (docs/analysis.md) -------------------------------
    @property
    def certificate(self):
        """The `ScheduleCertificate` this operator's payload carries, or
        None when it was never verified (build without strict health and
        no explicit verify() call)."""
        return self._payload.get("certificate")

    def verify(self, *, devices: int = 1, collectives: bool = False):
        """Run the full static verifier on the compiled artifact now.

        Audits the transformed system and certifies the schedule
        regardless of health policy; returns the `ScheduleCertificate`
        and stashes it on the payload (so a later strict-mode cache hit
        skips re-verification).  Raises `ScheduleInvariantError` /
        `TransformInvariantError` on violation.
        """
        from ..analysis.verify import verify_operator_payload
        return verify_operator_payload(
            self._payload, devices=devices, collectives=collectives,
            where=f"TriangularOperator.verify(n={self.n})")

    # -- cache plumbing -------------------------------------------------------
    @classmethod
    def _pattern_cache_key(cls, L: CSR, cfg: dict) -> str:
        """Pattern+config segment of the cache key (values excluded)."""
        return (matrix_fingerprint(L, include_values=False) + "-" +
                hashlib.sha256(
                    repr(sorted(cfg.items())).encode()).hexdigest()[:16])

    @staticmethod
    def _cache_path(key: str, cache_dir) -> Path:
        d = Path(cache_dir) if cache_dir is not None else default_cache_dir()
        return d / f"op-{key}.pkl"

    @classmethod
    def _disk_load(cls, key: str, cache_dir) -> dict | None:
        return cls._disk_load_path(cls._cache_path(key, cache_dir))

    @classmethod
    def _disk_load_pattern(cls, pattern_key: str, cache_dir) -> dict | None:
        """Any healthy on-disk payload whose pattern+config segment matches
        (its values don't matter — the caller re-derives them)."""
        d = Path(cache_dir) if cache_dir is not None else default_cache_dir()
        if not d.exists():
            return None
        for path in sorted(d.glob(f"op-{pattern_key}-*.pkl")):
            payload = cls._disk_load_path(path)
            if payload is not None:
                return payload
        return None

    @classmethod
    def _disk_load_path(cls, path: Path) -> dict | None:
        if not path.exists():
            return None
        try:
            with open(path, "rb") as f:
                payload = pickle.load(f)
            if payload.get("version") != CACHE_VERSION:
                cls._quarantine(
                    path, f"stale version {payload.get('version')!r} "
                    f"(expected {CACHE_VERSION})")
                return None
            return payload
        except Exception as e:          # corrupt entry: quarantine + rebuild
            cls._quarantine(path, f"unreadable ({type(e).__name__}: {e})")
            return None

    @staticmethod
    def _quarantine(path: Path, reason: str) -> None:
        """Move a bad cache entry into a `.bad/` sibling directory — kept
        for diagnosis, never silently deleted — and warn; the caller then
        rebuilds the artifact.  A quarantine that itself fails (read-only
        dir, racing quarantiners) is non-fatal: the rebuild proceeds and
        the next atomic store overwrites the bad entry in place."""
        from ..core.resilience import CacheQuarantineWarning
        dest = path.parent / ".bad" / path.name
        try:
            dest.parent.mkdir(parents=True, exist_ok=True)
            os.replace(path, dest)
            placed = f"quarantined to {dest}"
        except OSError:
            placed = "left in place (quarantine move failed)"
        warnings.warn(
            f"disk cache entry {path.name} is {reason}; {placed}, "
            "rebuilding the artifact", CacheQuarantineWarning, stacklevel=4)

    @classmethod
    def _disk_store(cls, key: str, payload: dict, cache_dir) -> None:
        path = cls._cache_path(key, cache_dir)
        # "_"-prefixed keys are process-local runtime state (staged device
        # arrays, compiled fns) — never serialized
        payload = {k: v for k, v in payload.items() if not k.startswith("_")}
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            # unique tmp name per writer: concurrent builders of the same
            # key each publish a complete file via atomic os.replace, so a
            # reader can never observe a torn pickle (last writer wins)
            tmp = path.parent / (
                f"{path.name}.{os.getpid()}-{uuid.uuid4().hex[:8]}.tmp")
            try:
                with open(tmp, "wb") as f:
                    pickle.dump(payload, f)
                os.replace(tmp, path)
            except BaseException:
                tmp.unlink(missing_ok=True)
                raise
        except OSError:
            pass        # read-only cache dir: operator still works, unseeded

    @classmethod
    def clear_memory_cache(cls) -> None:
        with cls._cache_lock:
            cls._memory_cache.clear()
            cls._pattern_index.clear()

    # -- solving --------------------------------------------------------------
    @property
    def n(self) -> int:
        return self._L.n_rows

    @property
    def schedule(self):
        return self._sched

    @property
    def transformed(self):
        return self._ts

    def _staged(self):
        ds = self._runtime.get("dsched")
        if ds is None:
            import jax
            from .levelset import to_device
            # staging may be triggered lazily from INSIDE a jit trace (an
            # operator first used as a traced preconditioner); the staged
            # arrays are cached on the shared payload, so they must be
            # concrete, never tracers
            with jax.ensure_compile_time_eval():
                ds = self._runtime["dsched"] = to_device(self._sched)
        return ds

    def _compiled_fn(self, engine):
        """engine -> compiled schedule fn, cached on the shared payload.

        Host-lowering engines (ShardedEngine: numpy padding + its own
        staging, memoized per schedule identity) get the host schedule
        directly — staging the unpadded arrays would pin a device copy
        the engine never reads (engines.compile_source)."""
        from .engines import compile_source
        cached = self._runtime["compiled"].get(engine.name)
        if cached is not None and cached[0] is engine:
            return cached[1]
        with _obs.span("engine.compile", engine=engine.name, n=self.n,
                       steps=self._sched.num_steps):
            fn = engine.compile(
                compile_source(engine, self._sched, self._staged))
        self._runtime["compiled"][engine.name] = (engine, fn)
        return fn

    def _canon_dtype(self):
        """The schedule dtype as jax will actually realize it, resolved
        once per payload: under default (non-x64) config a float64
        schedule executes in float32, and requesting float64 per solve
        would emit jax's truncation UserWarning on every call."""
        dt = self._runtime.get("canon_dtype")
        if dt is None:
            import jax.numpy as jnp
            dt = self._runtime["canon_dtype"] = \
                jnp.empty(0, dtype=self._sched.dtype).dtype
        return dt

    def _device_solve(self, c: np.ndarray, engine) -> np.ndarray:
        """One schedule execution in the schedule dtype: the copy to the
        device, the run (dispatch and device wait) and the copy back, each
        in its own span."""
        import jax
        import jax.numpy as jnp
        fn = self._compiled_fn(engine)
        with _obs.span("engine.put"):
            c = jnp.asarray(c, dtype=self._canon_dtype())
        with _obs.span("engine.run"):
            out = jax.block_until_ready(fn(c))
        with _obs.span("engine.get"):
            return np.asarray(out)

    @property
    def preamble(self):
        """The sweep's preamble c = B'v in its host and device forms
        (solver.preamble.SweepPreamble, bounded by nnz of the factor), one
        per payload: memory-cache hits share it, and a refactorized
        payload starts from its base's (`_derive_payload`)."""
        pre = self._runtime.get("sweep_preamble")
        if pre is None:
            from .preamble import SweepPreamble
            cfg = self._config
            pre = self._runtime.setdefault("sweep_preamble", SweepPreamble(
                self._ts, self._L.nnz, chunk=cfg.get("chunk", 256),
                max_deps=cfg.get("max_deps", 16),
                dtype=np.dtype(cfg.get("dtype", "float32"))))
        return pre

    def device_solve_fn(self, engine=None):
        """The operator's sweep as a pure JAX callable — jit/while_loop
        composable, no host callbacks.

        Returns fn(v) -> x for v of shape (n,) or (n, k): axis reversal
        (transpose/upper sweeps), the transform's preamble c = B'v, and the
        main schedule all run on device in the schedule dtype; the result
        is cast back to v's dtype.  The preamble (`preamble.device`) is
        one product with B''s non-identity rows, in the schedule dtype,
        where they fit its plan (B' within nnz of the factor); only where
        they do not is it the T factor's own level schedule
        (schedule_for_preamble), compiled through the SAME engine
        (solver/preamble.py).  No float64 iterative refinement — this is
        the raw device pipeline, which is exactly what preconditioner
        applications inside jit-native Krylov loops want (M^-1 is
        approximate by construction; see repro.iterative/docs/iterative.md).
        """
        from .engines import resolve_engine
        eng = self._engine if engine is None else resolve_engine(engine)
        if eng is None:
            raise ValueError(
                "operator has no resolvable default engine "
                f"({self._engine_name!r}); pass engine= explicitly")
        return compose_sweep_fn(
            self._compiled_fn(eng), self._canon_dtype(),
            self.preamble.device(eng, self._canon_dtype()), self._reversed)

    def _oriented_solve(self, v: np.ndarray, engine,
                        out_dtype=None) -> np.ndarray:
        """Device solve of the oriented system for an original-orientation
        right-hand side v: reverse, preamble, schedule, un-reverse.

        out_dtype=None returns the schedule dtype's natural output (the
        no-refinement serving path); the refinement loop passes float64 so
        corrections accumulate at full precision."""
        if self._reversed:
            v = v[::-1]
        x = self._device_solve(self.preamble.host(v), engine)
        if out_dtype is not None:
            x = x.astype(out_dtype)
        return x[::-1] if self._reversed else x

    def _reference_solve(self, b: np.ndarray) -> np.ndarray:
        """Guaranteed host solve of this sweep in float64 — scipy's
        `spsolve_triangular` when available, else the sequential reference
        loop — built directly from the ORIGINAL matrix, so it cannot be
        poisoned by a bad schedule payload or a failing engine.  The health
        policy's "fallback"/"repair" escape hatch (never the serving path:
        it is host-sequential and slow)."""
        entry = self._runtime.get("ref_system")
        if entry is None:
            L_eff, rev = orient_lower(self._L, self.side, self.transpose)
            try:
                import scipy.sparse as sp
                mat = sp.csr_matrix(
                    (np.asarray(L_eff.data, dtype=np.float64),
                     L_eff.indices, L_eff.indptr), shape=L_eff.shape)
                entry = ("scipy", mat, rev)
            except ImportError:  # pragma: no cover - scipy ships in the env
                entry = ("seq", L_eff, rev)
            self._runtime["ref_system"] = entry
        kind, mat, rev = entry
        v = np.asarray(b, dtype=np.float64)
        if rev:
            v = v[::-1]
        if kind == "scipy":
            from scipy.sparse.linalg import spsolve_triangular
            x = spsolve_triangular(mat, v, lower=True)
        else:
            from .reference import solve_csr_seq
            x = solve_csr_seq(mat, v) if v.ndim == 1 else np.stack(
                [solve_csr_seq(mat, v[:, j]) for j in range(v.shape[1])],
                axis=1)
        return np.asarray(x[::-1] if rev else x, dtype=np.float64)

    def _relative_residual(self, b, x) -> float:
        b64 = np.asarray(b, dtype=np.float64)
        r = b64 - self._L.matvec(np.asarray(x, dtype=np.float64),
                                 transpose=self.transpose)
        scale = max(1.0, float(np.abs(b64).max(initial=0.0)))
        return float(np.abs(r).max(initial=0.0)) / scale

    def _fallback_solve(self, v, eng, out_dtype=None):
        """`_oriented_solve` through `eng`, walking the registry's fallback
        chain (engines.engine_fallbacks) when an engine is unavailable or
        its compile/solve raises.  Returns (x, engine_used).

        Failures are memoized on the shared payload, so a known-broken
        engine is not re-tried on every solve of a hot operator; each
        downgrade bumps `stats.fallbacks` and warns once per
        (requested, used) pair; an exhausted chain raises
        EngineFallbackError naming every attempt and its reason.
        """
        from ..core.resilience import EngineFallbackError
        from .engines import engine_fallbacks
        failures = self._runtime.setdefault("engine_failures", {})
        attempts = []
        for cand in (eng, *engine_fallbacks(eng)):
            known = failures.get(cand.name)
            if known is not None:
                attempts.append((cand.name, f"previously failed ({known})"))
                continue
            try:
                if not cand.available():
                    raise RuntimeError("engine reports unavailable")
                with _obs.span("engine.solve", engine=cand.name):
                    x = self._oriented_solve(v, cand, out_dtype=out_dtype)
            except Exception as e:  # compile, lowering, or solve failure
                reason = f"{type(e).__name__}: {e}"
                failures[cand.name] = reason
                attempts.append((cand.name, reason))
                continue
            if attempts:            # served, but not by the requested engine
                self._note_fallback(eng, cand, attempts)
            return x, cand
        raise EngineFallbackError(
            f"TriangularOperator(n={self.n}, engine={eng.name!r})", attempts)

    def _note_fallback(self, requested, used, attempts) -> None:
        # warn once per (requested, used) pair; `fallbacks` counts every
        # downgraded dispatch and `fallback_downgrades` only the first
        # sighting of a pair, matching the warning (OperatorStats doc)
        warned = self._runtime.setdefault("warned_fallbacks", set())
        pair = (requested.name, used.name)
        new_pair = pair not in warned
        self.stats.record_fallback(f"{requested.name}->{used.name}",
                                   new_pair=new_pair)
        _obs.event("engine.fallback", requested=requested.name,
                   used=used.name, new_pair=new_pair)
        if new_pair:
            warned.add(pair)
            from ..core.resilience import EngineFallbackWarning
            detail = "; ".join(f"{n}: {r}" for n, r in attempts)
            warnings.warn(
                f"engine {requested.name!r} failed, solve downgraded to "
                f"{used.name!r} [{detail}]", EngineFallbackWarning,
                stacklevel=4)

    def _health_recover(self, b, x, reason, stage, guard, eng):
        """Apply the policy's on_nonfinite action to an unhealthy solve:
        "repair" sanitizes non-finite entries and iteratively refines
        through the device chain, escalating to the host reference after
        max_repair_rounds; "fallback" goes straight to the reference;
        anything else (or an unrecoverable solve) raises a typed
        NumericalHealthError naming what was attempted."""
        from ..core.resilience import (HealthRepairWarning,
                                       NumericalHealthError, ResilienceError)
        policy, st = guard.policy, self.stats
        st.record_health_event()
        _obs.event("health.violation", stage=stage, reason=reason)
        attempted = []
        if policy.on_nonfinite == "repair":
            attempted.append("repair")
            xr = np.where(np.isfinite(x), x, 0.0).astype(np.float64)
            for _ in range(policy.max_repair_rounds):
                r = b - self._L.matvec(xr, transpose=self.transpose)
                if not np.isfinite(r).all():
                    break
                try:
                    xr = xr + self._fallback_solve(r, eng,
                                                   out_dtype=np.float64)[0]
                except ResilienceError:
                    break       # no usable device engine: escalate
                if not np.isfinite(xr).all():
                    break       # corrections are poisoned too: escalate
                resid = self._relative_residual(b, xr)
                if resid <= policy.residual_tol:
                    st.record_health_action(f"{stage}:repaired")
                    warnings.warn(
                        f"unhealthy solve ({reason}) repaired by iterative "
                        f"refinement in {guard.where}", HealthRepairWarning,
                        stacklevel=3)
                    return xr, resid
        if policy.on_nonfinite in ("repair", "fallback"):
            attempted.append("reference")
            xref = self._reference_solve(b)
            if np.isfinite(xref).all():
                resid = self._relative_residual(b, xref)
                st.record_health_action(f"{stage}:reference")
                warnings.warn(
                    f"unhealthy solve ({reason}) recovered via the host "
                    f"reference solve in {guard.where}", HealthRepairWarning,
                    stacklevel=3)
                return xref, resid
        st.record_health_action(f"{stage}:raised")
        raise NumericalHealthError(reason, stage=stage, where=guard.where,
                                   fallbacks=attempted)

    def solve(self, b: np.ndarray, *, engine=None,
              refine_tol: float = 1e-10, max_refine: int = 6,
              health=None) -> np.ndarray:
        """Solve the operator's sweep (L, L^T, U, or U^T) x = b for b of
        shape (n,) or batched (n, k).

        Runs the preamble + compiled schedule in the schedule dtype, then
        iteratively refines in float64 against the original matrix until
        the relative residual max|b - Ax| / max(1, max|b|) <= refine_tol
        (or max_refine correction rounds); the residual matvec is
        transpose-aware, so L^T/U^T solves refine against the transposed
        operator.  Refined solves return float64, same leading shape as b.

        Set max_refine=0 for the cheapest per-solve path: no residual is
        computed (stats.last_residual stays NaN), b is NOT promoted to a
        float64 host copy, and the result comes back in the schedule
        dtype's natural output (float32 by default) — the raw device
        pipeline, exactly what refinement-free serving wants.

        health: a HealthPolicy, a named level ("off" | "on" | "strict" |
        "repair" | "fallback"), or None for the REPRO_HEALTH_CHECKS
        environment default ("on").  Controls the SolveGuard around this
        solve — a non-finite b raises NumericalHealthError; an unhealthy
        solution is raised, repaired, or replaced by the host reference
        solve; engine failures walk the registry fallback chain (module
        doc; docs/robustness.md).  Health recoveries return float64
        regardless of max_refine.
        """
        from ..core.resilience import (EngineFallbackError,
                                       HealthRepairWarning, SolveGuard,
                                       resolve_health_policy)
        from .engines import resolve_engine
        eng = self._engine if engine is None else resolve_engine(engine)
        if eng is None:     # payload names a custom engine we don't hold
            raise ValueError(
                "operator has no resolvable default engine "
                f"({self._engine_name!r}); pass engine= explicitly")
        policy = resolve_health_policy(health)
        guard = SolveGuard(policy, where=f"TriangularOperator(n={self.n}, "
                                         f"engine={eng.name!r})")
        # refinement-off solves skip the float64 promotion entirely: no
        # fp64 copy of b, no fp64 cast of the device result
        b = np.asarray(b, dtype=np.float64) if max_refine > 0 \
            else np.asarray(b)
        if b.ndim not in (1, 2) or b.shape[0] != self.n:
            raise ValueError(f"b must be ({self.n},) or ({self.n}, k), "
                             f"got {b.shape}")
        guard.require_finite_input(b)
        t0 = time.perf_counter()
        resid = float("nan")
        rounds = 0
        served_by_reference = False
        with _obs.span("operator.solve", n=self.n, engine=eng.name,
                       columns=1 if b.ndim == 1 else b.shape[1]) as sp:
            try:
                x, eng = self._fallback_solve(
                    b, eng, out_dtype=np.float64 if max_refine > 0 else None)
            except EngineFallbackError:
                # no device engine survived the chain; a recovering policy
                # may still serve the solve from the host reference
                if policy.on_nonfinite == "raise":
                    raise
                self.stats.record_health_event("engine:reference")
                warnings.warn(
                    "every engine in the fallback chain failed; solve served "
                    f"by the host reference in {guard.where}",
                    HealthRepairWarning, stacklevel=2)
                x = self._reference_solve(b)
                served_by_reference = True
            if served_by_reference:
                resid = self._relative_residual(b, x)
            elif max_refine > 0:    # refinement off => skip the host matvec
                bscale = max(1.0, float(np.abs(b).max(initial=0.0)))
                with _obs.span("operator.refine", tol=refine_tol) as rsp:
                    while True:
                        with _obs.span("operator.residual"):
                            r = b - self._L.matvec(x,
                                                   transpose=self.transpose)
                            resid = float(np.abs(r).max(initial=0.0)) \
                                / bscale
                        if not np.isfinite(resid):
                            break   # poisoned pipeline: corrections would
                                    # be NaN too — the health action below
                                    # decides
                        if resid <= refine_tol or rounds >= max_refine:
                            break
                        x = x + self._fallback_solve(
                            r, eng, out_dtype=np.float64)[0]
                        rounds += 1
                    rsp.set(rounds=rounds, residual=resid)
            if not served_by_reference:
                reason, stage = guard.output_unhealthy(x), "output"
                if reason is None and policy.residual_check:
                    if not np.isfinite(resid):  # nan: unset (max_refine=0)
                        resid = self._relative_residual(b, x)   # or poisoned
                    reason, stage = guard.residual_unhealthy(resid), \
                        "residual"
                if reason is not None:
                    x, resid = self._health_recover(b, x, reason, stage,
                                                    guard, eng)
            ms = (time.perf_counter() - t0) * 1e3
            sp.set(ms=ms, rounds=rounds, engine_used=eng.name,
                   reference=served_by_reference)
            self.stats.record_solve(
                ms=ms, columns=1 if b.ndim == 1 else b.shape[1],
                rounds=rounds, residual=resid)
        return x

    def __repr__(self) -> str:  # pragma: no cover
        return (f"TriangularOperator(n={self.n}, side={self.side!r}, "
                f"transpose={self.transpose}, strategy={self.strategy!r}, "
                f"steps={self._sched.num_steps}, engine={self.engine!r}, "
                f"cache={self.stats.cache_source})")
