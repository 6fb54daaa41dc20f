"""Unified observability layer (PR 9): tracing core, metrics registry,
stats-plane views, per-step profiler, exporters, and the end-to-end
serving trace.

Organization mirrors src/repro/obs/:

* tracer semantics under injected fake clocks (exact durations, nesting,
  cross-thread retroactive spans, the off-by-default no-op path);
* metrics instruments + registry (labels, kind mismatch, percentile
  parity with the serving reservoirs);
* `OperatorStats` / `ServiceStats` as views over the registry — the
  snapshot surface must be IDENTICAL to an independently-computed
  expected dict (no dual bookkeeping to drift);
* the fallback counter semantics satellite (`fallbacks` = downgraded
  dispatches, `fallback_downgrades` = unique pairs = warnings);
* per-step profiler exactness, `CostModel.calibrate`, the chaos
  `slow_step` localization test;
* exporters and their validators (including failure detection);
* one traced batched serving request on the lung2 analogue, exported to
  a schema-valid Chrome trace with the queue -> batch -> solve -> engine
  chain (the PR's acceptance trace).
"""
import json
import threading
import types

import numpy as np
import pytest

from repro import obs
from repro.obs.export import (chrome_trace, prometheus_text,
                              validate_chrome_trace,
                              validate_prometheus_text, write_chrome_trace)
from repro.obs.metrics import (DEFAULT_MS_BUCKETS, MetricsRegistry,
                               nearest_rank_percentile)
from repro.obs.trace import NULL_SPAN, Tracer
from repro.sparse import generators


@pytest.fixture(autouse=True)
def _no_global_tracer():
    """Every test starts and ends with tracing disabled."""
    obs.disable()
    yield
    obs.disable()


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# ----------------------------------------------------------------------
# tracing core


def test_span_nesting_and_exact_durations():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("outer", n=3) as outer:
        clk.advance(1.0)
        with tr.span("inner") as inner:
            clk.advance(0.25)
            inner.event("mark", k=1)
            clk.advance(0.25)
        clk.advance(0.5)
    assert outer.duration == pytest.approx(2.0)
    assert inner.duration == pytest.approx(0.5)
    assert inner.parent_id == outer.span_id
    assert outer.parent_id is None
    assert outer.attrs["n"] == 3
    name, t, attrs = inner.events[0]
    assert name == "mark" and t == pytest.approx(1.25) and attrs == {"k": 1}
    assert tr.open_spans() == []
    assert [s.name for s in tr.spans()] == ["inner", "outer"]


def test_span_records_error_attr():
    tr = Tracer(clock=FakeClock())
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    (sp,) = tr.spans()
    assert sp.attrs["error"] == "ValueError"


def test_record_span_cross_thread_parenting():
    clk = FakeClock(10.0)
    tr = Tracer(clock=clk)
    with tr.span("batch") as bsp:
        sp = tr.record_span("queue", 9.0, 10.0, parent=bsp, tenant="a")
    assert sp.parent_id == bsp.span_id
    assert sp.duration == pytest.approx(1.0)
    assert sp.attrs == {"tenant": "a"}
    # a non-span, non-id parent (NULL_SPAN from a mid-flight enable) is
    # dropped, not stored as an unresolvable object
    orphan = tr.record_span("queue", 0.0, 1.0, parent=NULL_SPAN)
    assert orphan.parent_id is None


def test_event_outside_span_is_orphan():
    tr = Tracer(clock=FakeClock(5.0))
    tr.event("loose", why="no span open")
    (name, t, attrs, tid) = tr.orphan_events()[0]
    assert name == "loose" and t == 5.0
    assert tid == threading.get_ident()


def test_module_helpers_are_noop_when_disabled():
    assert not obs.enabled()
    sp = obs.span("anything", k=1)
    assert sp is NULL_SPAN
    with sp as s:
        s.set(a=1).event("e")          # all no-ops, nothing raised
    obs.event("loose")
    assert obs.record_span("x", 0.0, 1.0) is NULL_SPAN


def test_enable_disable_roundtrip():
    tr = obs.enable(clock=FakeClock())
    assert obs.enabled() and obs.get_tracer() is tr
    with obs.span("s"):
        pass
    assert [s.name for s in tr.spans()] == ["s"]
    assert obs.disable() is tr
    assert not obs.enabled()


def test_per_thread_stacks_do_not_cross():
    tr = Tracer(clock=FakeClock())
    seen = {}

    def worker():
        with tr.span("child-thread") as sp:
            seen["parent"] = sp.parent_id

    with tr.span("main-thread"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    # the other thread's span must NOT inherit this thread's stack
    assert seen["parent"] is None


# ----------------------------------------------------------------------
# metrics registry


def test_counter_gauge_text_histogram_basics():
    reg = MetricsRegistry(prefix="t")
    c = reg.counter("hits", "hits")
    c.inc()
    c.inc(2, route="a")
    assert c.value() == 1 and c.value(route="a") == 2 and c.total() == 3
    g = reg.gauge("depth", "queue depth")
    g.set(4.0)
    g.add(-1.0)
    assert g.value() == 3.0
    t = reg.text("source", "cache source")
    t.set("disk")
    assert t.value() == "disk"
    h = reg.histogram("lat", "latency", bounds=(1.0, 10.0), reservoir=4)
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count() == 3 and h.sum() == pytest.approx(55.5)
    assert h.buckets() == {1.0: 1, 10.0: 1, float("inf"): 1}
    assert h.samples() == [0.5, 5.0, 50.0]


def test_histogram_reservoir_bounds_memory_not_counts():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "l", bounds=(10.0,), reservoir=2)
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count() == 4                 # counts keep going
    assert h.samples() == [1.0, 2.0]      # reservoir stops admitting


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("n", "help")
    assert reg.counter("n") is c1
    with pytest.raises(TypeError):
        reg.gauge("n")
    assert reg.get("missing") is None


def test_percentile_matches_serving_formula():
    from repro.serving.service import _percentile
    rng = np.random.default_rng(0)
    samples = list(rng.standard_normal(37))
    reg = MetricsRegistry()
    h = reg.histogram("x", "x", reservoir=100)
    for v in samples:
        h.observe(float(v))
    for q in (0, 25, 50, 99, 100):
        assert h.percentile(q) == _percentile(samples, q)
        assert nearest_rank_percentile(samples, q) == _percentile(samples, q)
    assert np.isnan(nearest_rank_percentile([], 50))


def test_shared_lock_is_reentrant_and_registry_wide():
    reg = MetricsRegistry()
    c = reg.counter("a", "a")
    with reg.lock:
        with reg.lock:          # RLock: multi-instrument commits can nest
            c.inc()
    assert c.value() == 1


# ----------------------------------------------------------------------
# stats planes as registry views


def test_operator_stats_snapshot_is_exact_view():
    from repro.solver.operator import OperatorStats
    st = OperatorStats(cache_source="disk", tune_ms=12.5)
    st.record_solve(ms=2.0, columns=4, rounds=1, residual=1e-9)
    st.record_solve(ms=3.0, columns=1, rounds=0, residual=2e-9)
    st.record_fallback("pallas->scan", new_pair=True)
    st.record_fallback("pallas->scan", new_pair=False)
    st.record_health_event("solve:nonfinite")
    st.record_value_update(ms=0.7, cache_source="pattern")
    expected = {
        "solves": 2, "rhs_columns": 5, "refine_rounds": 1,
        "total_solve_ms": 5.0, "last_solve_ms": 3.0, "last_residual": 2e-9,
        "cache_source": "pattern", "tune_ms": 12.5, "value_updates": 1,
        "last_update_ms": 0.7, "fallbacks": 2, "fallback_downgrades": 1,
        "last_fallback": "pallas->scan", "health_events": 1,
        "last_health_event": "solve:nonfinite",
    }
    assert st.to_dict() == expected
    # the view IS the registry: the same numbers come out of snapshot()
    reg_snap = st.registry.snapshot()
    assert reg_snap["solves"]["series"][""] == 2
    assert reg_snap["fallbacks"]["series"][""] == 2
    assert reg_snap["fallback_downgrades"]["series"][""] == 1
    # attribute writes (legacy surface) commit through the instruments
    st.solves = 10
    assert st.registry.get("solves").value() == 10


def test_service_stats_snapshot_is_exact_view():
    from repro.serving.service import ServiceStats, _percentile
    st = ServiceStats()
    st.record_submit("built")
    st.record_submit("registry")
    st.record_submit("registry")
    st.record_reject("tenant-b")
    batch = types.SimpleNamespace(width=2, reason="width")
    st.record_batch(batch, [1.0, 3.0], 7.5)
    st.record_batch(types.SimpleNamespace(width=1, reason="linger"),
                    [2.0], 4.5)
    st.record_batch_error(types.SimpleNamespace(width=1, reason="drain"))
    snap = st.snapshot()
    expected = {
        "submitted": 3, "completed": 3, "rejected": 1, "failed": 1,
        "batches": 3, "batch_errors": 1,
        "width_hist": {1: 2, 2: 1},
        "flush_reasons": {"width": 1, "linger": 1, "drain": 1},
        "cache_sources": {"built": 1, "registry": 2},
        "rejected_by_tenant": {"tenant-b": 1},
        "queue_ms": {"p50": _percentile([1.0, 3.0, 2.0], 50),
                     "p99": _percentile([1.0, 3.0, 2.0], 99)},
        "solve_ms": {"p50": _percentile([7.5, 4.5], 50),
                     "p99": _percentile([7.5, 4.5], 99)},
        "mean_width": 4 / 3,
    }
    assert snap == expected
    # legacy attribute surface still reads through the registry
    assert st.submitted == 3 and st.batches == 3
    assert st.width_hist == {1: 2, 2: 1}
    assert st.queue_ms == [1.0, 3.0, 2.0]
    assert st.mean_width() == pytest.approx(4 / 3)


def test_registry_lifecycle_counters_are_metrics_backed():
    from repro.serving import OperatorRegistry
    reg = OperatorRegistry(tune_mode="off", cache=False)
    L = generators.random_lower(60, avg_offdiag=2.0, seed=3)
    try:
        reg.admit(L)
        reg.admit(L)                        # warm re-admission
    finally:
        reg.close()
    assert reg.admissions == 1
    assert reg.metrics.get("admissions").value() == 1
    assert reg.stats()["admissions"] == 1


def test_fallback_attempts_vs_unique_downgrades():
    """Satellite: `fallbacks` counts every downgraded dispatch (can exceed
    solves under refinement), `fallback_downgrades` counts unique
    (requested -> used) pairs and matches the warn-once behavior."""
    import warnings
    from repro.core import faults
    from repro.core.resilience import EngineFallbackWarning
    from repro.solver import TriangularOperator

    L = generators.random_lower(80, avg_offdiag=2.0, seed=1)
    op = TriangularOperator.from_csr(L, tune="no_rewriting", cache=False)
    b = np.ones(L.n_rows)
    with faults.fail_engine_compile("pallas-interpret"):
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            op.solve(b, engine="pallas-interpret", max_refine=0)
            op.solve(b, engine="pallas-interpret", max_refine=0)
    fb = [w for w in rec if issubclass(w.category, EngineFallbackWarning)]
    assert op.stats.fallbacks == 2            # one per downgraded dispatch
    assert op.stats.fallback_downgrades == 1  # one unique pair...
    assert len(fb) == 1                       # ...and exactly one warning
    assert op.stats.last_fallback == "pallas-interpret->scan"


# ----------------------------------------------------------------------
# per-step profiler + calibration


@pytest.fixture(scope="module")
def small_L():
    return generators.random_lower(150, avg_offdiag=2.5, seed=2,
                                   max_back=30)


def test_profile_schedule_is_exact_and_consistent(small_L):
    from repro.obs.profile import profile_schedule
    from repro.core.strategies import NoRewrite
    from repro.core.transform import transform
    from repro.solver.reference import solve_csr_seq
    from repro.solver.schedule import schedule_for_transformed

    ts = transform(small_L, NoRewrite(), validate=False, codegen=False)
    sched = schedule_for_transformed(ts, chunk=64, max_deps=8)
    b = np.random.default_rng(0).standard_normal(small_L.n_rows)
    prof = profile_schedule(sched, ts.preamble(b), reps=2, warmup=1)
    assert prof.engine == "stepwise"
    assert prof.num_steps == sched.num_steps
    assert len(prof.step_ms) == sched.num_steps
    assert np.all(prof.step_ms >= 0)
    assert prof.total_ms() == pytest.approx(float(prof.step_ms.sum()))
    assert 0 < prof.critical_path_share() <= 1.0
    assert 0 < prof.utilization() <= 1.0
    assert int(prof.step_padded_flops.sum()) == sched.padded_flops()
    assert int(prof.step_real_flops.sum()) == sched.flops()
    hist = prof.step_histogram()
    assert sum(hist["counts"]) == sched.num_steps
    assert hist["bounds"] == list(DEFAULT_MS_BUCKETS)
    d = prof.to_dict()
    json.dumps(d)                      # JSON-serializable end to end
    assert d["slowest_steps"] == prof.slowest_steps()


def test_profiling_engine_solves_exactly(small_L):
    from repro.obs.profile import ProfilingEngine
    from repro.solver import TriangularOperator
    from repro.solver.reference import solve_csr_seq

    eng = ProfilingEngine()
    op = TriangularOperator.from_csr(small_L, tune="no_rewriting",
                                     cache=False, engine=eng)
    b = np.random.default_rng(1).standard_normal(small_L.n_rows)
    x = op.solve(b, max_refine=0)
    ref = solve_csr_seq(small_L, b)
    assert float(np.max(np.abs(np.asarray(x, np.float64) - ref))) < 1e-4
    prof = eng.last_profile
    assert prof is not None and prof.num_steps > 0


def test_profile_operator_routes_orientation(small_L):
    from repro.obs.profile import profile_operator
    from repro.solver import TriangularOperator

    op = TriangularOperator.from_csr(small_L, tune="no_rewriting",
                                     cache=False)
    prof = profile_operator(op, reps=1, warmup=0)
    assert prof.num_steps == op._sched.num_steps


def test_cost_model_calibrate_recovers_synthetic_constants():
    from repro.core.portfolio import CostModel
    from repro.obs.profile import ScheduleProfile

    rng = np.random.default_rng(0)
    flops = rng.integers(1000, 5000, size=12).astype(np.int64)
    bytes_ = np.full(12, 4096.0)               # degenerate column
    true_overhead, true_flop_rate = 3.0, 2e-3
    t_us = true_overhead + true_flop_rate * flops
    prof = ScheduleProfile(
        engine="stepwise", num_steps=12, reps=1, step_ms=t_us / 1e3,
        collective_ms=None, step_padded_flops=flops,
        step_real_flops=flops, step_bytes=bytes_, width_buckets=[])
    base = CostModel(us_per_byte=1e-4)
    cm = base.calibrate(prof)
    assert cm.us_per_padded_flop == pytest.approx(true_flop_rate, rel=1e-6)
    # the constant bytes column is excluded; its charge at the EXISTING
    # rate is folded out of the intercept so predict() reproduces the fit
    recon = (cm.step_overhead_us + flops * cm.us_per_padded_flop
             + bytes_ * cm.us_per_byte)
    assert np.allclose(recon, t_us, rtol=1e-6)


def test_cost_model_calibrate_collective_split():
    from repro.core.portfolio import CostModel
    from repro.obs.profile import ScheduleProfile

    flops = np.array([1000, 2000, 3000, 4000], dtype=np.int64)
    coll_ms = np.array([0.004, 0.005, 0.006, 0.005])
    comp_us = 2.0 + 1e-3 * flops
    prof = ScheduleProfile(
        engine="sharded", num_steps=4, reps=1,
        step_ms=comp_us / 1e3 + coll_ms, collective_ms=coll_ms,
        step_padded_flops=flops, step_real_flops=flops,
        step_bytes=np.full(4, 64.0), width_buckets=[])
    cm = CostModel.sharded().calibrate(prof)
    assert cm.collective_latency_us == pytest.approx(5.0)
    assert cm.us_per_padded_flop == pytest.approx(1e-3, rel=1e-6)


def test_calibrate_empty_profile_is_identity():
    from repro.core.portfolio import CostModel
    prof = types.SimpleNamespace(step_ms=np.array([]), collective_ms=None,
                                 step_padded_flops=np.array([]),
                                 step_bytes=np.array([]))
    cm = CostModel()
    assert cm.calibrate(prof) == cm


@pytest.mark.chaos
def test_slow_step_fault_is_localized_by_profiler(small_L):
    """Satellite: a stall injected into step 3 must show up as step 3's
    histogram bucket / argmax, and the stall must be visible inside the
    profile span's trace."""
    from repro.core import faults
    from repro.obs.profile import profile_schedule
    from repro.core.strategies import NoRewrite
    from repro.core.transform import transform
    from repro.solver.schedule import schedule_for_transformed

    ts = transform(small_L, NoRewrite(), validate=False, codegen=False)
    sched = schedule_for_transformed(ts, chunk=64, max_deps=8)
    assert sched.num_steps > 4
    b = np.random.default_rng(0).standard_normal(small_L.n_rows)
    tr = obs.enable()
    try:
        with faults.slow_step(3, 0.05):
            prof = profile_schedule(sched, ts.preamble(b), reps=1,
                                    warmup=1)
    finally:
        obs.disable()
    assert int(np.argmax(prof.step_ms)) == 3
    assert prof.step_ms[3] >= 45.0             # the injected 50 ms stall
    hist = prof.step_histogram()
    # the stalled step lands in a bucket above 25 ms; every other step is
    # far below it on this tiny system
    stalled_bucket = next(i for i, bnd in enumerate(hist["bounds"])
                          if prof.step_ms[3] <= bnd)
    assert hist["counts"][stalled_bucket] >= 1
    (psp,) = [s for s in tr.spans() if s.name == "profile.schedule"]
    steps_evts = [a for n, _, a in psp.events if n == "profile.step"]
    assert any(e["step"] == 3 for e in steps_evts)
    assert psp.attrs["total_ms"] == pytest.approx(prof.total_ms())


# ----------------------------------------------------------------------
# exporters + validators


def _sample_tracer():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("serving.batch", width=2) as bsp:
        clk.advance(0.001)
        tr.record_span("serving.queue", 0.0, 0.001, parent=bsp)
        with tr.span("operator.solve"):
            clk.advance(0.002)
        bsp.event("mark")
    tr.event("loose.orphan")
    return tr


def test_chrome_trace_schema_and_validation(tmp_path):
    tr = _sample_tracer()
    doc = write_chrome_trace(tmp_path / "t.json", tr)
    assert validate_chrome_trace(doc) == []
    loaded = json.loads((tmp_path / "t.json").read_text())
    assert validate_chrome_trace(loaded) == []
    xs = [e for e in loaded["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"serving.batch", "serving.queue",
                                       "operator.solve"}
    by_name = {e["name"]: e for e in xs}
    bid = by_name["serving.batch"]["args"]["span_id"]
    assert by_name["serving.queue"]["args"]["parent_id"] == bid
    assert by_name["operator.solve"]["args"]["parent_id"] == bid
    # ts are rebased to the earliest span, µs units
    assert by_name["serving.batch"]["ts"] == 0.0
    assert by_name["serving.batch"]["dur"] == pytest.approx(3000.0)
    instants = [e for e in loaded["traceEvents"] if e["ph"] == "i"]
    assert {e["name"] for e in instants} == {"mark", "loose.orphan"}


def test_chrome_validator_flags_problems():
    tr = Tracer(clock=FakeClock())
    sp = tr.span("never.closed")
    sp.__enter__()
    doc = chrome_trace(tr)
    assert any("unclosed" in p for p in validate_chrome_trace(doc))
    bad = {"traceEvents": [
        {"name": "x", "ph": "X", "ts": 0.0, "dur": 1.0,
         "args": {"span_id": 1, "parent_id": 99}}]}
    assert any("does not resolve" in p for p in validate_chrome_trace(bad))
    assert validate_chrome_trace({"nope": 1})


def test_prometheus_text_round_trip():
    reg = MetricsRegistry(prefix="repro_test")
    reg.counter("hits", "total hits").inc(5, route="a")
    reg.gauge("depth", "queue depth").set(2.5)
    reg.text("source", "cache source").set('we"ird\nvalue')
    h = reg.histogram("lat_ms", "latency", bounds=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    page = prometheus_text(reg)
    assert validate_prometheus_text(page) == []
    assert 'repro_test_hits{route="a"} 5' in page
    assert "# TYPE repro_test_lat_ms histogram" in page
    assert 'repro_test_lat_ms_bucket{le="+Inf"} 3' in page
    assert "repro_test_lat_ms_count 3" in page
    # per-entry merge: same prefix twice under one TYPE header, labeled
    reg2 = MetricsRegistry(prefix="repro_test")
    reg2.counter("hits", "total hits").inc(1, route="a")
    merged = prometheus_text((reg, {"entry": "e1"}), (reg2, {"entry": "e2"}))
    assert validate_prometheus_text(merged) == []
    assert merged.count("# TYPE repro_test_hits counter") == 1
    assert 'entry="e2"' in merged


def test_prometheus_validator_flags_problems():
    assert validate_prometheus_text("repro_x 1\n")      # sample before TYPE
    bad = "# TYPE repro_x counter\nrepro_x{bad-label=\"v\"} 1\n"
    assert any("malformed sample" in p
               for p in validate_prometheus_text(bad))
    ok = "# TYPE repro_x counter\nrepro_x NaN\nrepro_x 1.5e-3\n"
    assert validate_prometheus_text(ok) == []


# ----------------------------------------------------------------------
# krylov residual events


def test_krylov_emits_residual_events():
    from repro.iterative import cg
    from repro.precond import Preconditioner

    A = generators.poisson2d_spd(10, 10)
    b = np.ones(A.n_rows)
    M = Preconditioner.ic0(A, tune="no_rewriting", cache=False)
    tr = obs.enable()
    try:
        res = cg(A, b, preconditioner=M, tol=1e-8)
    finally:
        obs.disable()
    assert bool(np.all(res.converged))
    evts = [(n, a) for n, _, a, _ in tr.orphan_events()
            if n == "krylov.residual"]
    assert evts and all(a["driver"] == "cg" for _, a in evts)
    assert evts[0][1]["iteration"] == 0
    assert len(evts) <= 64 + 1
    # the recorded residual trail matches the result history
    hist = np.asarray(res.residual_norms, dtype=float)
    for _, a in evts:
        assert hist[a["iteration"]] == pytest.approx(a["residual"])


# ----------------------------------------------------------------------
# host spans and device name scopes inside a sweep


@pytest.fixture(scope="module")
def lung_op():
    """A transformed lung2 sweep: its T-factor preamble is not the
    identity, so the host preamble span is opened."""
    from repro.solver import TriangularOperator
    L = generators.lung2_like(scale=0.03)
    op = TriangularOperator.from_csr(L, tune="avgLevelCost", cache=False)
    assert not op.transformed.identity_preamble
    return L, op


def _children(spans, parent) -> list:
    """Names of `parent`'s child spans, in the order they were entered."""
    return [s.name for s in sorted(spans, key=lambda s: s.span_id)
            if s.parent_id == parent.span_id]


SWEEP_SPANS = ["engine.preamble", "engine.put", "engine.run", "engine.get"]


def test_raw_solve_splits_engine_solve_into_its_parts(lung_op):
    L, op = lung_op
    b = np.ones(L.n_rows)
    op.solve(b, max_refine=0)                   # compile outside the trace
    tr = obs.enable()
    op.solve(b, max_refine=0)
    obs.disable()
    spans = tr.spans()
    (solve,) = [s for s in spans if s.name == "operator.solve"]
    (eng,) = [s for s in spans if s.name == "engine.solve"]
    assert eng.parent_id == solve.span_id
    assert _children(spans, eng) == SWEEP_SPANS
    by_name = {s.name: s for s in spans}
    rows = int(np.count_nonzero(op.transformed.T.row_nnz()))
    entries = op._preamble_plan().entries
    assert by_name["engine.preamble"].attrs == {
        "rows": rows, "realization": "spmv", "entries": entries}
    assert rows > 0 and entries > 0
    assert not any(s.name == "operator.residual" for s in spans)
    # the parts lie inside the span that holds them
    for name in SWEEP_SPANS:
        sp = by_name[name]
        assert eng.t_start <= sp.t_start <= sp.t_end <= eng.t_end


def test_refined_solve_spans_one_residual_per_evaluation(lung_op):
    L, op = lung_op
    b = np.ones(L.n_rows)
    op.solve(b)
    tr = obs.enable()
    op.solve(b)
    obs.disable()
    spans = tr.spans()
    (refine,) = [s for s in spans if s.name == "operator.refine"]
    rounds = refine.attrs["rounds"]
    assert rounds >= 1
    assert _children(spans, refine) == \
        ["operator.residual", "engine.solve"] * rounds + ["operator.residual"]
    engines = [s for s in spans if s.name == "engine.solve"]
    assert len(engines) == rounds + 1
    for eng in engines:
        assert _children(spans, eng) == SWEEP_SPANS


def test_preamble_span_names_the_tfactor_fallback():
    """A factor whose B' has more entries than the factor itself keeps the
    T-factor loop, and its span says so, with T's entry count."""
    from repro.solver import TriangularOperator
    L = generators.poisson2d_ic0(16, 16)
    op = TriangularOperator.from_csr(L, tune="avgLevelCost", cache=False)
    b = np.ones(L.n_rows)
    op.solve(b, max_refine=0)
    tr = obs.enable()
    op.solve(b, max_refine=0)
    obs.disable()
    (pre,) = [s for s in tr.spans() if s.name == "engine.preamble"]
    T = op.transformed.T
    assert pre.attrs == {"rows": int(np.count_nonzero(T.row_nnz())),
                         "realization": "tfactor", "entries": T.nnz}


def test_identity_preamble_opens_no_preamble_span(small_L):
    from repro.solver import TriangularOperator
    op = TriangularOperator.from_csr(small_L, tune="no_rewriting",
                                     cache=False)
    b = np.ones(small_L.n_rows)
    op.solve(b, max_refine=0)
    tr = obs.enable()
    op.solve(b, max_refine=0)
    obs.disable()
    (eng,) = [s for s in tr.spans() if s.name == "engine.solve"]
    assert _children(tr.spans(), eng) == SWEEP_SPANS[1:]


def _op_names(text: str) -> list:
    """The op_name of every instruction of an HLO text that has one."""
    import re
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("engine", ["scan", "unrolled"])
def test_step_body_carries_its_scope_in_the_compiled_program(small_L,
                                                             engine):
    import jax.numpy as jnp

    from repro.solver import levelset
    from repro.solver import schedule_for_csr
    from repro.sparse import build_levels
    sched = schedule_for_csr(small_L, build_levels(small_L), chunk=32,
                             max_deps=4, dtype=np.float32)
    ds = levelset.to_device(sched)
    fn = {"scan": levelset._scan_jit,
          "unrolled": levelset._unrolled_jit}[engine]
    text = fn.lower(ds.leaves(), ds.n, ds.n_carry,
                    jnp.zeros(ds.n, jnp.float32)).compile().as_text()
    names = _op_names(text)
    assert any("/sptrsv.step/" in n and n.endswith("scatter")
               for n in names)
    assert any("/sptrsv.step/" in n and "gather" in n for n in names)


def test_device_sweep_scopes_preamble_and_main(lung_op):
    import jax
    import jax.numpy as jnp
    L, op = lung_op
    text = jax.jit(op.device_solve_fn()).lower(
        jnp.zeros(L.n_rows, jnp.float32)).compile().as_text()
    names = _op_names(text)
    assert any("/sptrsv.preamble/" in n for n in names)
    assert any("/sptrsv.main/" in n and "/sptrsv.step/" in n
               for n in names)
    # B' fits the plan: the preamble is one product, with no step
    assert op._preamble_plan().realization == "spmv"
    assert not any("/sptrsv.preamble/" in n and "/sptrsv.step/" in n
                   for n in names)


def test_scheduled_device_preamble_is_steps_under_its_scope(lung_op):
    """Where B' does not fit the plan (here a cap of one entry), the
    device preamble is the T factor's schedule: steps under
    `sptrsv.preamble`."""
    import jax
    import jax.numpy as jnp
    from repro.core.transform import host_preamble
    from repro.solver import TriangularOperator
    L, _ = lung_op
    op = TriangularOperator.from_csr(L, tune="avgLevelCost", cache=False)
    op._runtime["preamble_plan"] = host_preamble(op.transformed, 1)
    text = jax.jit(op.device_solve_fn()).lower(
        jnp.zeros(L.n_rows, jnp.float32)).compile().as_text()
    assert any("/sptrsv.preamble/" in n and "/sptrsv.step/" in n
               for n in _op_names(text))


@pytest.mark.parametrize("driver", ["cg", "bicgstab", "gmres"])
def test_krylov_parts_carry_their_scopes(driver):
    import jax
    import jax.numpy as jnp

    from repro import iterative
    from repro.precond import Preconditioner
    A = generators.poisson2d_spd(8, 8)
    P = Preconditioner.ic0(A, tune="avgLevelCost", cache=False)
    solve = getattr(iterative, driver)
    text = jax.jit(lambda b: solve(A, b, preconditioner=P, maxiter=4)) \
        .lower(jnp.ones(A.n_rows, jnp.float32)).compile().as_text()
    names = _op_names(text)
    for scope in ("krylov.matvec", "krylov.precond", "krylov.reduce"):
        assert any(f"/{scope}/" in n for n in names), scope
    # M^-1's sweeps nest inside its scope
    assert any("/krylov.precond/" in n and "/sptrsv.step/" in n
               for n in names)


SHARDED_SCOPES = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import re
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.solver import distributed as dist
    from repro.solver import levelset, schedule_for_csr
    from repro.sparse import build_levels, generators
    L = generators.random_lower(120, avg_offdiag=2.0, seed=3, max_back=20)
    sched = dist._padded_schedule(
        schedule_for_csr(L, build_levels(L), chunk=16, max_deps=4,
                         dtype=np.float32), 2)
    mesh = dist.default_mesh(devices=jax.devices()[:2])
    leaves = levelset.host_leaves(sched)
    groups = jax.device_put(leaves, dist.group_shardings(leaves, mesh))
    text = dist._sharded_solve.lower(
        groups, jnp.zeros(sched.n, jnp.float32), mesh=mesh, axis="model",
        n=sched.n, n_carry=sched.n_carry).compile().as_text()
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and "all-gather" in line.split("=", 2)[1]:
            print("GATHER", m.group(1))
        elif m:
            print("OP", m.group(1))
"""


def test_sharded_step_and_exchange_carry_their_scopes():
    """On two virtual CPU devices: the sharded step body is a
    `sptrsv.step` and its collective a `sptrsv.exchange` inside it."""
    import subprocess
    import sys
    import textwrap
    from pathlib import Path
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(SHARDED_SCOPES)],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": str(Path.home()), "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).parent.parent)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.splitlines()
    gathers = [ln.split(" ", 1)[1] for ln in lines
               if ln.startswith("GATHER ")]
    assert gathers and all("/sptrsv.step/" in g and "/sptrsv.exchange/"
                           in g for g in gathers)
    assert any(ln.startswith("OP ") and "/sptrsv.step/" in ln
               and "sptrsv.exchange" not in ln for ln in lines)


# ----------------------------------------------------------------------
# the end-to-end acceptance trace


def test_traced_serving_request_exports_valid_nested_trace(tmp_path):
    """One batched serving request on the lung2 analogue: the exported
    Chrome trace is schema-valid and carries the nested
    submit/queue -> batch -> solve -> operator -> engine chain plus the
    registry admit/tune spans (the PR's acceptance criterion)."""
    from repro.serving import SolveService
    from repro.solver.reference import solve_csr_seq

    L = generators.lung2_like(scale=0.02)
    rng = np.random.default_rng(0)
    tr = obs.enable()
    try:
        with SolveService(tune_mode="sync", max_width=4,
                          auto_dispatch=False, cache=False) as svc:
            futs = [svc.submit(rng.standard_normal(L.n_rows), L)
                    for _ in range(4)]
            svc.pump()
            xs = [f.result(timeout=60) for f in futs]
            snap = svc.snapshot()
            prom = svc.prometheus_text()
    finally:
        obs.disable()

    assert snap["completed"] == 4 and snap["batches"] >= 1
    assert validate_prometheus_text(prom) == []
    assert "repro_service_completed 4" in prom
    assert "repro_registry_admissions 1" in prom
    assert "repro_operator_solves" in prom      # per-entry stats merged in

    doc = write_chrome_trace(tmp_path / "serve.trace.json", tr)
    assert validate_chrome_trace(doc) == []
    spans = {e["args"]["span_id"]: e for e in doc["traceEvents"]
             if e["ph"] == "X"}
    by_name: dict = {}
    for e in spans.values():
        by_name.setdefault(e["name"], []).append(e)
    for required in ("serving.submit", "registry.admit", "serving.queue",
                     "serving.batch", "serving.solve", "operator.solve",
                     "operator.tune", "engine.compile", "engine.solve"):
        assert required in by_name, f"missing span {required}"
    # the chain: queue and solve under the batch, operator under solve,
    # engine dispatch under the operator
    batch = by_name["serving.batch"][0]
    bid = batch["args"]["span_id"]
    assert all(q["args"]["parent_id"] == bid
               for q in by_name["serving.queue"])
    ssolve = by_name["serving.solve"][0]
    assert ssolve["args"]["parent_id"] == bid
    opsolve = by_name["operator.solve"][0]
    assert opsolve["args"]["parent_id"] == ssolve["args"]["span_id"]
    esolve = by_name["engine.solve"][0]
    assert esolve["args"]["parent_id"] == opsolve["args"]["span_id"]
    # admit nests under the submit that triggered it
    admit = by_name["registry.admit"][0]
    submit_ids = {e["args"]["span_id"] for e in by_name["serving.submit"]}
    assert admit["args"]["parent_id"] in submit_ids
    # solutions are real: spot-check one column against the oracle
    ref = solve_csr_seq(L, np.asarray(
        rng.standard_normal(L.n_rows)))      # just shape sanity for rng
    assert xs[0].shape == (L.n_rows,)
    assert np.all(np.isfinite(xs[0]))
