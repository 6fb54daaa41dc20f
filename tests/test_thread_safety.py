"""Concurrency regression suite for the serving-facing shared state.

The serving tier (src/repro/serving/) calls `from_csr`, `solve`, and the
engine/tuner memos from worker threads, so the facade's process-wide
structures — the bounded in-memory operator cache + pattern index, the
OperatorStats record, the sharded lowering memo, the pair-decision memo
— must survive concurrent hammering without corruption.  These tests
shrink the bounds (tiny `_memory_cache_max`) and hammer from a thread
pool; before the locks landed, the OrderedDict eviction loop and the
read-modify-write stats fields lost updates or blew up under exactly
this load.
"""
import collections
import concurrent.futures
import threading

import numpy as np
import pytest

from repro.solver import TriangularOperator
from repro.solver.reference import solve_csr_seq
from repro.sparse import generators


def _matrices(k=6, n=80):
    return [generators.random_lower(n, avg_offdiag=2.5, seed=100 + i)
            for i in range(k)]


@pytest.fixture(autouse=True)
def _fresh_memory_cache():
    TriangularOperator.clear_memory_cache()
    yield
    TriangularOperator.clear_memory_cache()


def test_from_csr_hammer_with_tiny_lru(tmp_path, monkeypatch):
    """12 threads x 6 matrices through a 3-slot memory LRU: constant
    eviction + pattern-index churn, every solve still correct."""
    monkeypatch.setattr(TriangularOperator, "_memory_cache_max", 3)
    mats = _matrices()
    refs = [solve_csr_seq(L, np.ones(L.n_rows)) for L in mats]
    errors = []

    def worker(tid: int) -> None:
        rng = np.random.default_rng(tid)
        for _ in range(8):
            i = int(rng.integers(len(mats)))
            try:
                op = TriangularOperator.from_csr(
                    mats[i], tune="no_rewriting", cache=True,
                    cache_dir=tmp_path)
                x = op.solve(np.ones(mats[i].n_rows), max_refine=2)
                err = float(np.max(np.abs(np.asarray(x) - refs[i])))
                if err > 1e-6:
                    errors.append(f"thread {tid}: matrix {i} err {err:.2e}")
            except Exception as exc:    # noqa: BLE001 - collect everything
                errors.append(f"thread {tid}: {type(exc).__name__}: {exc}")

    with concurrent.futures.ThreadPoolExecutor(max_workers=12) as pool:
        list(pool.map(worker, range(12)))
    assert errors == []
    # the LRU respected its bound through the churn
    assert len(TriangularOperator._memory_cache) <= 3


def test_concurrent_clear_during_from_csr_is_safe(tmp_path):
    """clear_memory_cache racing builders: no KeyError from the pattern
    index pointing at an evicted entry, results stay correct."""
    mats = _matrices(k=3)
    stop = threading.Event()
    errors = []

    def clearer() -> None:
        while not stop.is_set():
            TriangularOperator.clear_memory_cache()

    def builder(tid: int) -> None:
        for i in range(12):
            L = mats[(tid + i) % len(mats)]
            try:
                op = TriangularOperator.from_csr(
                    L, tune="no_rewriting", cache=True, cache_dir=tmp_path)
                op.solve(np.ones(L.n_rows), max_refine=0)
            except Exception as exc:    # noqa: BLE001
                errors.append(f"thread {tid}: {type(exc).__name__}: {exc}")

    t = threading.Thread(target=clearer)
    t.start()
    try:
        with concurrent.futures.ThreadPoolExecutor(max_workers=6) as pool:
            list(pool.map(builder, range(6)))
    finally:
        stop.set()
        t.join()
    assert errors == []


def test_operator_stats_counters_exact_under_thread_pool():
    """T x K concurrent solves on ONE operator: every counter lands
    exactly (atomic per-event commit), nothing is lost to interleaving."""
    L = generators.random_lower(120, avg_offdiag=2.5, seed=0)
    op = TriangularOperator.from_csr(L, tune="no_rewriting", cache=False)
    b = np.ones(L.n_rows)
    op.solve(b, max_refine=0)                   # prime compiled fns
    base = op.stats.to_dict()
    T, K = 8, 10

    def worker(_tid: int) -> None:
        for _ in range(K):
            op.solve(b, max_refine=0)

    with concurrent.futures.ThreadPoolExecutor(max_workers=T) as pool:
        list(pool.map(worker, range(T)))
    snap = op.stats.to_dict()
    assert snap["solves"] - base["solves"] == T * K
    assert snap["rhs_columns"] - base["rhs_columns"] == T * K
    assert snap["total_solve_ms"] > base["total_solve_ms"]
    assert snap["last_solve_ms"] > 0


def test_stats_record_methods_are_atomic_without_solves():
    """The record_* surface itself, hammered directly: per-event atomicity
    means paired fields never drift apart."""
    from repro.solver import OperatorStats
    stats = OperatorStats()
    T, K = 16, 200

    def worker(_tid: int) -> None:
        for _ in range(K):
            stats.record_solve(ms=0.5, columns=2, rounds=1, residual=1e-12)
            stats.record_fallback("scan->scan")
            stats.record_value_update(ms=0.1, cache_source="pattern")

    with concurrent.futures.ThreadPoolExecutor(max_workers=T) as pool:
        list(pool.map(worker, range(T)))
    assert stats.solves == T * K
    assert stats.rhs_columns == 2 * T * K
    assert stats.refine_rounds == T * K
    assert stats.total_solve_ms == pytest.approx(0.5 * T * K)
    assert stats.fallbacks == T * K
    assert stats.value_updates == T * K
    d = stats.to_dict()
    assert "_lock" not in d and d["solves"] == T * K


def test_pair_decision_memo_concurrent_access():
    """The Preconditioner pair-decision LRU under concurrent factorize
    calls: one decision per pattern, no corruption (the memo dedupes
    concurrent builders' results; tuning itself runs unlocked)."""
    from repro.precond import Preconditioner

    A = generators.poisson2d_spd(8, 8)
    Preconditioner.clear_pair_decisions()
    errors = []

    def worker(tid: int) -> None:
        try:
            M = Preconditioner.ic0(A, tune="auto", cache=False)
            y = M.apply(np.ones(A.n_rows))
            if not np.all(np.isfinite(np.asarray(y))):
                errors.append(f"thread {tid}: non-finite apply")
        except Exception as exc:    # noqa: BLE001
            errors.append(f"thread {tid}: {type(exc).__name__}: {exc}")

    with concurrent.futures.ThreadPoolExecutor(max_workers=6) as pool:
        list(pool.map(worker, range(6)))
    assert errors == []
    assert len(Preconditioner._pair_decisions) == 1    # one pattern, one slot


def test_metrics_registry_hammer_exact_totals():
    """PR 9: the MetricsRegistry itself under contention — 16 threads
    hammering the same counter (plain + labeled), gauge, and histogram
    must produce EXACT totals, not approximately-correct ones.  The
    registry is the single backing store for every stats plane, so a
    lost update here silently corrupts serving dashboards."""
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry(prefix="hammer")
    c = reg.counter("ops", "ops")
    g = reg.gauge("level", "level")
    h = reg.histogram("lat_ms", "latency", reservoir=200_000)
    T, K = 16, 500

    barrier = threading.Barrier(T)

    def worker(tid: int) -> None:
        barrier.wait()          # maximize interleaving
        for i in range(K):
            c.inc()
            c.inc(2, route=f"r{tid % 4}")
            g.add(1.0)
            h.observe(float(i % 7))
            with reg.lock:      # multi-instrument atomic commit
                c.inc(route="atomic")
                h.observe(100.0)

    with concurrent.futures.ThreadPoolExecutor(max_workers=T) as pool:
        list(pool.map(worker, range(T)))

    assert c.value() == T * K
    assert c.value(route="atomic") == T * K
    for r in range(4):
        assert c.value(route=f"r{r}") == 2 * K * (T // 4)
    assert c.total() == T * K + T * K + 2 * T * K
    assert g.value() == float(T * K)
    assert h.count() == 2 * T * K
    expected_sum = T * K * 100.0 + T * sum(i % 7 for i in range(K))
    assert h.sum() == pytest.approx(expected_sum)
    assert len(h.samples()) == 2 * T * K
    # snapshot under load is coherent too
    snap = reg.snapshot()
    assert snap["ops"]["series"][""] == T * K


def test_disabled_tracer_overhead_on_cached_solve():
    """With tracing DISABLED (the default), the no-op span machinery on
    the solve path must cost <=5% of a cached lung2 solve, by count: an
    enabled tracer first counts the spans and events a
    cached raw solve and a default refined solve cross (exact: a new span
    on the hot path is a deliberate edit here); that count times the
    measured cost of one no-op span is held against the median time of a
    warm repeat of the same solve."""
    import time

    from repro import obs
    from repro.obs.trace import NULL_SPAN, Tracer

    obs.disable()
    assert not obs.enabled()

    L = generators.lung2_like(scale=0.03)
    op = TriangularOperator.from_csr(L, tune="no_rewriting", cache=False)
    b = np.ones(L.n_rows)
    op.solve(b, max_refine=0)                   # compile/warm
    op.solve(b)

    def crossings(**kw) -> int:
        tracer = obs.enable(Tracer())
        try:
            op.solve(b, **kw)
        finally:
            obs.disable()
        spans = tracer.spans()
        return (len(spans) + sum(len(sp.events) for sp in spans)
                + len(tracer.orphan_events()))

    # raw: operator.solve > engine.solve > engine.put, engine.run,
    # engine.get (no_rewriting: the preamble is the identity, no span)
    raw = crossings(max_refine=0)
    assert raw == 5
    # refined, one round: operator.solve, operator.refine, two residuals,
    # and two sweeps of engine.solve > put, run, get
    rounds0 = op.stats.refine_rounds
    refined = crossings()
    assert op.stats.refine_rounds - rounds0 == 1
    assert refined == 12

    def med(fn, reps=7):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    N = 10_000
    def noop_spans():
        for _ in range(N):
            with obs.span("solver.hot", n=1) as sp:
                sp.set(k=2)
                obs.event("hot.event", i=3)

    per_call_s = med(noop_spans) / N
    assert obs.span("x") is NULL_SPAN           # really the no-op path
    for count, kw in ((raw, {"max_refine": 0}), (refined, {})):
        solve_s = med(lambda: np.asarray(op.solve(b, **kw)))
        overhead = count * per_call_s
        assert overhead <= 0.05 * solve_s, (
            f"no-op tracing would cost {overhead * 1e6:.1f}us "
            f"({count} spans) against a {solve_s * 1e3:.2f}ms cached "
            f"solve {kw} (> 5%)")
