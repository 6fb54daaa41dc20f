"""Benchmark aggregator — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # full run
    PYTHONPATH=src python -m benchmarks.run --smoke    # reduced-scale CI run

Sections:
  engines         — capability smoke: every available registered engine
                    solves one system through the registry (smoke only)
  table1          — paper Table I (strategy comparison, lung2/torso2)
  level_profiles  — paper Fig. 5/6 (per-level cost profiles)
  solver_bench    — solve wall time (CPU measured + TPU roofline model)
  schedule        — schedule-compiler before/after (BENCH_schedule.json)
  operator        — auto-tuner vs fixed strategies (BENCH_operator.json)
  iterative       — end-to-end IC(0)-PCG, tuned vs no_rewriting
                    (BENCH_iterative.json)
  refactor        — value-update fast path vs full re-tuned rebuild per
                    time step (BENCH_refactor.json)
  distributed     — sharded-engine scaling curve + steps-vs-all_gathers
                    table (BENCH_distributed.json; full mode on the CPU
                    runs in a subprocess with 8 forced host devices, on
                    an accelerator in-process over its devices and is
                    skipped with one; smoke runs in-process on the
                    available devices)
  serving         — solve-service load sweep: micro-batched throughput
                    vs sequential, cold-start latency anatomy, hot-swap
                    guarantee (BENCH_serving.json)

--smoke runs every section at reduced scale (seconds, not minutes) so the
tier-1 suite can import-check and execute the drivers (pytest -m bench).
The full run writes experiments/BENCH_schedule.json (build ms, steps,
padded vs real FLOPs, us_per_solve before/after — the schedule compiler's
perf trajectory) and experiments/BENCH_operator.json (tuner-vs-fixed-
strategy table — the portfolio auto-tuner's guarantee).  Smoke mode
executes every driver but persists nothing unless smoke() is given
explicit out paths — the committed full-scale artifacts must not be
clobbered by reduced-scale runs.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path


@contextlib.contextmanager
def traced_section(name: str, trace_dir):
    """Wrap one bench section in a fresh tracer (--trace DIR): on exit,
    write `<dir>/<name>.trace.json` (Chrome trace-event) and
    `<dir>/<name>.metrics.json` (default metrics-registry snapshot).
    No-op when trace_dir is falsy — the default full/smoke runs pay
    nothing."""
    if not trace_dir:
        yield
        return
    from repro import obs
    d = Path(trace_dir)
    d.mkdir(parents=True, exist_ok=True)
    tracer = obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.export.write_chrome_trace(d / f"{name}.trace.json", tracer)
        (d / f"{name}.metrics.json").write_text(json.dumps(
            obs.default_registry().snapshot(), indent=2, default=str) + "\n")


def write_bench_summary(out_path="experiments/BENCH_summary.json",
                        exp_dir="experiments"):
    """Distill the committed BENCH_*.json artifacts into one
    section -> headline-numbers map.  Purely derived (no measurement):
    regenerating from the same artifacts is byte-identical, which the
    benchmark smoke test asserts.  Returns the summary dict, or None when
    no artifact exists."""
    exp = Path(exp_dir)
    summary: dict = {}

    def load(name):
        p = exp / f"BENCH_{name}.json"
        return json.loads(p.read_text()) if p.exists() else None

    rec = load("schedule")
    if rec:
        summary["schedule"] = {name: {
            "n": m["n"], "steps": [m["before"]["steps"], m["after"]["steps"]],
            "padded_flops_reduction": m["padded_flops_reduction"],
            "build_speedup_vs_legacy": m["build_speedup_vs_legacy"],
            "us_per_solve": [m["before"].get("us_per_solve"),
                             m["after"].get("us_per_solve")],
        } for name, m in rec["matrices"].items()}
    rec = load("operator")
    if rec:
        summary["operator"] = {name: {
            "pick": m["tuner"]["pick"],
            "tuner_us": m["tuner"]["measured_us"],
            "best_fixed_us": m.get("best_fixed_us"),
            "worst_fixed_us": m.get("worst_fixed_us"),
            "tuner_not_slower_than_worst":
                m.get("tuner_not_slower_than_worst"),
        } for name, m in rec["matrices"].items()}
    rec = load("iterative")
    if rec:
        summary["iterative"] = {name: {
            "unpreconditioned_iterations":
                m["unpreconditioned"]["iterations"],
            "pcg_iterations": m["tuned"]["iterations"],
            "tuned_pick": m["tuned"]["pick"],
            "tuned_solve_ms": m["tuned"]["solve_ms"],
            "no_rewriting_solve_ms": m["no_rewriting"]["solve_ms"],
            "tuned_speedup": round(
                m["no_rewriting"]["solve_ms"] / m["tuned"]["solve_ms"], 2),
        } for name, m in rec["matrices"].items()}
    rec = load("refactor")
    if rec:
        summary["refactor"] = {name: {
            "strategy": m["strategy"],
            "update_speedup_vs_rebuild": m["update_speedup_vs_rebuild"],
            "update_not_slower_than_rebuild":
                m["update_not_slower_than_rebuild"],
            "exact_match_fresh": m["exact_match_fresh"],
        } for name, m in rec["matrices"].items()}
    rec = load("distributed")
    if rec:
        summary["distributed"] = {name: {
            "steps": m["steps"], "all_gather_calls": m["all_gather_calls"],
            "transformed_not_slower": m["transformed_not_slower"],
        } for name, m in rec["matrices"].items()}
        summary["distributed"]["transformed_not_slower_any"] = \
            rec["transformed_not_slower_any"]
    rec = load("serving")
    if rec:
        summary["serving"] = {name: {
            "strategy": m["strategy"],
            "saturation_speedup_vs_sequential":
                m["saturation_speedup_vs_sequential"],
            "batched_beats_sequential": m["batched_beats_sequential"],
            "hot_swap_landed": m["hot_swap_landed"],
            "cold_start_le_untuned": m["cold_start"]["cold_start_le_untuned"],
        } for name, m in rec["matrices"].items()}
    if not summary:
        return None
    if out_path:
        p = Path(out_path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(summary, indent=2, sort_keys=True) + "\n")
    return summary


def bench_schedule(out_path="experiments/BENCH_schedule.json",
                   scales=(0.25, 0.15), reps=5, time_solve=True) -> dict:
    """Schedule-compiler before/after on the benchmark analogues."""
    from benchmarks.solver_bench import schedule_metrics
    from repro.sparse import generators
    record = {
        "config": {"chunk": 256, "max_deps": 16, "scales": list(scales)},
        "matrices": {},
    }
    for name, L in (
            (f"lung2_like@{scales[0]}", generators.lung2_like(scales[0])),
            (f"torso2_like@{scales[1]}", generators.torso2_like(scales[1]))):
        record["matrices"][name] = schedule_metrics(
            L, chunk=256, max_deps=16, reps=reps, time_solve=time_solve)
    if out_path:
        p = Path(out_path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(record, indent=2) + "\n")
    return record


def verify_smoke(scales=(0.12, 0.08)) -> dict:
    """Static-verification smoke over both benchmark analogues: transform
    + schedule every registered strategy and certify each artifact
    (`python -m benchmarks.run --verify`; the CI static-analysis job's
    second gate).  Any invariant violation raises a typed
    ScheduleInvariantError/TransformInvariantError and fails the run."""
    from repro.analysis import certificate_dict, verify_level_schedule
    from repro.analysis.verify import audit_transformed_system
    from repro.core.portfolio import STRATEGY_REGISTRY, make_strategy
    from repro.core.transform import transform
    from repro.solver.schedule import schedule_for_transformed
    from repro.sparse import generators
    out: dict = {}
    for name, L in ((f"lung2_like@{scales[0]}",
                     generators.lung2_like(scales[0])),
                    (f"torso2_like@{scales[1]}",
                     generators.torso2_like(scales[1]))):
        out[name] = {}
        for strategy in STRATEGY_REGISTRY:
            ts = transform(L, make_strategy(strategy), validate=False,
                           codegen=False)
            audit_transformed_system(ts, where=f"{name}/{strategy}")
            cert = verify_level_schedule(
                ts_sched := schedule_for_transformed(ts, chunk=256,
                                                     max_deps=16),
                ts.A, ts.diag, where=f"{name}/{strategy}")
            assert cert.steps == ts_sched.num_steps
            out[name][strategy] = certificate_dict(cert)
    return out


def engine_capability_smoke(n: int = 200) -> dict:
    """Solve one small system through every *available* registered engine
    (registry dispatch, pallas-interpret included) and check it against the
    sequential reference — the CI engine-capability gate."""
    import jax.numpy as jnp
    import numpy as np
    from repro.solver import (available_engines, engine_capabilities,
                              resolve_engine, schedule_for_csr,
                              solve_csr_seq, to_device)
    from repro.sparse import build_levels, generators

    L = generators.random_lower(n, avg_offdiag=2.5, seed=0, max_back=25)
    sched = schedule_for_csr(L, build_levels(L), chunk=64, max_deps=8)
    ds = to_device(sched)
    rng = np.random.default_rng(0)
    b = rng.standard_normal(n)
    B = rng.standard_normal((n, 3))
    x_ref = solve_csr_seq(L, b)
    out = {"capabilities": engine_capabilities(), "rel_err": {}}
    for name in available_engines():
        eng = resolve_engine(name)
        fn = eng.compile(ds)
        x = np.asarray(fn(jnp.asarray(b, ds.dtype)))
        err = float(np.abs(x - x_ref).max() / max(1.0, np.abs(x_ref).max()))
        assert err < 2e-4, f"engine {name}: rel err {err:.2e}"
        if eng.supports_batched_rhs:
            X = np.asarray(fn(jnp.asarray(B, ds.dtype)))
            assert X.shape == (n, 3), f"engine {name}: batched shape"
        out["rel_err"][name] = err
        print(f"engine {name:<18} rel_err={err:.2e} "
              f"{eng.capabilities()}")
    return out


def smoke(out_path=None, operator_out=None, iterative_out=None,
          trace_dir=None) -> dict:
    """Reduced-scale pass over every benchmark driver (tier-1 smoke)."""
    import benchmarks.distributed_bench as db
    import benchmarks.iterative_bench as ib
    import benchmarks.level_profiles as lp
    import benchmarks.operator_bench as ob
    import benchmarks.refactor_bench as rb
    import benchmarks.serving_bench as svb
    import benchmarks.solver_bench as sb
    import benchmarks.table1 as t1
    from repro.sparse import generators
    from repro.sparse import io as sio

    with traced_section("engines", trace_dir):
        engines = engine_capability_smoke()
    with traced_section("distributed", trace_dir):
        distributed = db.smoke_record()
    real_load = sio.load_named
    try:
        sio.load_named = lambda name: (
            generators.lung2_like(scale=0.04) if name == "lung2"
            else generators.torso2_like(scale=0.04))
        with traced_section("table1", trace_dir):
            t1.run(csv_out=None)
        with traced_section("level_profiles", trace_dir):
            lp.run(csv_dir=None)
        with traced_section("solver_bench", trace_dir):
            sb.run(csv_out=None, scales=(0.05, 0.05), iters=2)
    finally:
        sio.load_named = real_load
    with traced_section("operator", trace_dir):
        ob.run(out_path=operator_out, scales=(0.04, 0.04), iters=1,
               measure_top_k=0)
    with traced_section("iterative", trace_dir):
        it_rec = ib.run(out_path=iterative_out, scales=(0.02, 0.02),
                        iters=1, maxiter=200, measure_top_k=2)
    with traced_section("refactor", trace_dir):
        refactor = rb.run(out_path=None, scales=(0.04, 0.04), steps=2,
                          iters=1)
    with traced_section("serving", trace_dir):
        serving = svb.run(out_path=None, scales=(0.03, 0.03),
                          widths=(1, 4), rounds=3)
    with traced_section("schedule", trace_dir):
        rec = bench_schedule(None, scales=(0.08, 0.06), reps=2,
                             time_solve=False)
    rec["engines"] = engines
    rec["iterative"] = it_rec
    rec["distributed_smoke"] = distributed
    rec["refactor_smoke"] = refactor
    rec["serving_smoke"] = serving
    if out_path:        # persist WITH the engine section (record == file)
        p = Path(out_path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(rec, indent=2) + "\n")
    return rec


def main() -> None:
    import os
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if os.environ.get("REPRO_STRICT_DEPRECATIONS") == "1":
        # CI gate: DeprecationWarnings issued from repro's own modules are
        # errors (the string-engine shims must not regress into internal
        # use).  Regex module match — PYTHONWARNINGS can't prefix-match.
        import warnings
        warnings.filterwarnings("error", category=DeprecationWarning,
                                module=r"repro\..*")
    trace_dir = None
    if "--trace" in sys.argv:
        i = sys.argv.index("--trace")
        trace_dir = (sys.argv[i + 1]
                     if i + 1 < len(sys.argv)
                     and not sys.argv[i + 1].startswith("--")
                     else "experiments/traces")
    if "--smoke" in sys.argv:
        t0 = time.time()
        rec = smoke(trace_dir=trace_dir)
        print(json.dumps(rec, indent=2))
        print(f"\nsmoke total {time.time() - t0:.1f}s")
        return
    if "--verify" in sys.argv:
        t0 = time.time()
        rec = verify_smoke()
        for name, strategies in rec.items():
            for strategy, cert in strategies.items():
                print(f"{name:20s} {strategy:16s} steps={cert['steps']:>5} "
                      f"critical_path={cert['critical_path']:>5} "
                      f"padded_flops={cert['padded_flops']}")
        print(f"\nall artifacts certified in {time.time() - t0:.1f}s")
        return
    from benchmarks import level_profiles, solver_bench, table1
    t0 = time.time()
    print("== Table I: strategy comparison (paper values inline) ==")
    with traced_section("table1", trace_dir):
        table1.run(csv_out="experiments/table1.csv")
    print("\n== Fig 5/6: level-cost profiles ==")
    with traced_section("level_profiles", trace_dir):
        level_profiles.run(csv_dir="experiments")
    print("\n== Solver wall-time (name,strategy,steps,levels,us,model_us,"
          "speedup,build_ms,padded,real) ==")
    with traced_section("solver_bench", trace_dir):
        solver_bench.run(csv_out="experiments/solver_bench.csv")
    print("\n== Schedule compiler before/after ==")
    with traced_section("schedule", trace_dir):
        rec = bench_schedule()
    for name, m in rec["matrices"].items():
        print(f"{name}: legacy_build={m['legacy_build_ms']}ms -> "
              f"after={m['after']['build_ms']}ms "
              f"({m['build_speedup_vs_legacy']}x), steps "
              f"{m['before']['steps']} -> {m['after']['steps']} "
              f"(levels {m['after']['levels']}), padded_flops "
              f"{m['before']['padded_flops']} -> "
              f"{m['after']['padded_flops']} "
              f"(-{m['padded_flops_reduction']:.0%})")
    print("\n== Operator auto-tuner vs fixed strategies ==")
    from benchmarks import operator_bench
    with traced_section("operator", trace_dir):
        operator_bench.run(out_path="experiments/BENCH_operator.json")
    print("\n== End-to-end IC(0)-PCG: tuned vs no_rewriting ==")
    from benchmarks import iterative_bench
    with traced_section("iterative", trace_dir):
        iterative_bench.run(out_path="experiments/BENCH_iterative.json")
    print("\n== Refactorization fast path: update_values vs full "
          "rebuild per step ==")
    from benchmarks import refactor_bench
    with traced_section("refactor", trace_dir):
        refactor_bench.run(out_path="experiments/BENCH_refactor.json")
    print("\n== Sharded scaling curve + steps-vs-all_gathers ==")
    from benchmarks import distributed_bench
    with traced_section("distributed", trace_dir):
        distributed_bench.run(out_path="experiments/BENCH_distributed.json")
    print("\n== Solve service: micro-batched load sweep + cold-start "
          "anatomy ==")
    from benchmarks import serving_bench
    with traced_section("serving", trace_dir):
        serving_bench.run(out_path="experiments/BENCH_serving.json")
    write_bench_summary()
    print("wrote experiments/BENCH_summary.json")
    print(f"\ntotal {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
