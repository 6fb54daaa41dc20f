"""Distributed SpTRSV: the ShardedEngine (ISSUE 5 tentpole).

In-process tests run on the single real CPU device (a 1-device mesh is a
degenerate but fully exercised shard_map program); the multi-device
matrix — mesh sizes 1/2/4/8, carry-bearing schedules, batched RHS, and an
end-to-end PCG under one mesh — runs in a subprocess with 8 forced host
devices so the main test process keeps its single-device view.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.solver import (ShardedEngine, get_engine, registered_engines,
                          resolve_engine, schedule_for_csr, sharded_engine,
                          solve_csr_seq)
from repro.solver import distributed as dist
from repro.solver.distributed import count_all_gathers, solve_sharded
from repro.sparse import build_levels, generators


def _small(n=120, seed=7, chunk=32, max_deps=4):
    L = generators.random_lower(n, avg_offdiag=2.0, seed=seed, max_back=15)
    sched = schedule_for_csr(L, build_levels(L), chunk=chunk,
                             max_deps=max_deps)
    b = np.random.default_rng(0).standard_normal(n)
    return L, sched, b


# -- registry + capability (in-process, 1-device mesh) ------------------------

def test_sharded_engine_registered_and_resolvable():
    assert "sharded" in registered_engines()
    eng = resolve_engine("sharded")
    assert isinstance(eng, ShardedEngine)
    caps = eng.capabilities()
    assert caps["supports_batched_rhs"] and caps["available"]
    # the mesh-less default instance and sharded_engine() are one object,
    # so lowering memoization is shared across call sites
    assert sharded_engine() is eng


def test_sharded_cache_token_is_mesh_qualified():
    """Measured-mode cache keys record which engine was TIMED; two sharded
    engines over different meshes measure different collective costs and
    must never collide on the bare name."""
    import jax
    devs = jax.devices()
    e1 = ShardedEngine(dist.default_mesh(devices=devs[:1]))
    e_all = ShardedEngine(dist.default_mesh())
    assert e1.cache_token().startswith("sharded[")
    assert e1.cache_token() != "sharded"
    assert get_engine("scan").cache_token() == "scan"
    if len(devs) > 1:       # distinct meshes => distinct tokens
        assert e1.cache_token() != e_all.cache_token()
    e_other_axis = ShardedEngine(
        dist.default_mesh(axis="data", devices=devs[:1]), axis="data")
    assert e_other_axis.cache_token() != e1.cache_token()


def test_sharded_engine_default_mesh_unifies_with_registry():
    """sharded_engine(default_mesh()) and the registered "sharded"
    instance must be ONE object — two instances over the identical mesh
    would split the lowering memo and pad/stage/compile twice."""
    eng = get_engine("sharded")
    assert sharded_engine(dist.default_mesh()) is eng
    assert sharded_engine(None) is eng


def test_mesh_auto_tune_defaults_to_sharded_cost_model(tmp_path):
    """tune="auto" under mesh= must price the per-step collective: the
    serving configuration and the tuning objective have to agree."""
    from repro.solver import TriangularOperator
    L = generators.random_lower(120, avg_offdiag=2.0, seed=4, max_back=12)
    mesh = dist.default_mesh()
    op = TriangularOperator.from_csr(L, tune="auto", chunk=32, max_deps=4,
                                     mesh=mesh, cache_dir=tmp_path)
    assert op.report.cost_model.collective_latency_us > 0
    for c in op.report.candidates:
        if c.error is None:
            assert c.breakdown["collectives_us"] > 0
    # single-device auto-tune keeps the single-device default
    op2 = TriangularOperator.from_csr(L, tune="auto", chunk=32, max_deps=4,
                                      cache_dir=tmp_path)
    assert op2.report.cost_model.collective_latency_us == 0
    # distinct objectives, distinct cache entries — no collision
    assert op2.stats.cache_source == "built"
    # an explicit cost_model is never overridden
    from repro.core import TuningCostModel
    op3 = TriangularOperator.from_csr(L, tune="auto", chunk=32, max_deps=4,
                                      mesh=mesh, cache=False,
                                      cost_model=TuningCostModel.cpu())
    assert op3.report.cost_model.collective_latency_us == 0


def test_sharded_operator_never_stages_unpadded_schedules():
    """Host-lowering engines must not trigger the unpadded DeviceSchedule
    staging — neither for the main schedule nor for a T-factor preamble;
    the sharded lowering pads and stages its own copies.  Here B''s rows
    fit the plan, so the device preamble is one product and no preamble
    schedule is built at all."""
    import jax.numpy as jnp
    from repro.solver import TriangularOperator
    L = generators.lung2_like(scale=0.02)
    op = TriangularOperator.from_csr(L, tune="avgLevelCost", chunk=32,
                                     max_deps=4, mesh=dist.default_mesh(),
                                     cache=False)
    b = np.random.default_rng(5).standard_normal(L.n_rows)
    x = op.solve(b, max_refine=0)
    fn = op.device_solve_fn()
    y = np.asarray(fn(jnp.asarray(b, np.float32)))
    x_ref = solve_csr_seq(L, b)
    scale = max(1.0, np.abs(x_ref).max())
    assert np.abs(x - x_ref).max() / scale < 1e-3
    assert np.abs(y - x_ref).max() / scale < 1e-3
    assert op._runtime.get("dsched") is None
    assert op._runtime.get("preamble") is None
    assert op._preamble_plan().realization == "spmv"
    assert "preamble_host" not in op._runtime


def test_mesh_pair_decision_defaults_to_sharded_cost_model():
    from repro.precond import Preconditioner
    A = generators.poisson2d_spd(10, 10)
    Preconditioner.clear_pair_decisions()
    P = Preconditioner.ic0(A, tune="auto", mesh=dist.default_mesh(),
                           cache=False)
    assert P.forward.engine == "sharded"
    assert P.report.fwd.cost_model.collective_latency_us > 0


def test_sharded_solves_single_and_batched():
    L, sched, b = _small()
    fn = get_engine("sharded").compile(sched)
    x_ref = solve_csr_seq(L, b)
    import jax.numpy as jnp
    x = np.asarray(fn(jnp.asarray(b, np.float32)))
    assert np.abs(x - x_ref).max() < 2e-4
    B = np.random.default_rng(1).standard_normal((L.n_rows, 3))
    X = np.asarray(fn(jnp.asarray(B, np.float32)))
    assert X.shape == (L.n_rows, 3)
    for j in range(3):
        assert np.abs(X[:, j] - solve_csr_seq(L, B[:, j])).max() < 2e-4


def test_sharded_mismatched_rhs_raises():
    """Regression (ISSUE 5 satellite): a wrong-length RHS used to die with
    an opaque concatenate shape error deep inside shard_map; the lowered
    fn must validate the leading dimension eagerly."""
    _, sched, _ = _small()
    fn = get_engine("sharded").compile(sched)
    n = sched.n
    with pytest.raises(ValueError, match=rf"\({n},\) or \({n}, k\)"):
        fn(np.zeros(n + 1, np.float32))
    with pytest.raises(ValueError, match="right-hand side"):
        fn(np.zeros((n - 1, 2), np.float32))
    with pytest.raises(ValueError, match="right-hand side"):
        fn(np.zeros((n, 2, 2), np.float32))


def test_axis_name_mismatch_is_a_clear_error():
    """A mesh whose axis name differs from mesh_axis/axis must raise an
    eager ValueError naming the mesh's axes — not a KeyError from deep
    inside lowering."""
    import jax
    from repro.iterative.operators import device_matvec
    from repro.solver import TriangularOperator
    mesh = dist.default_mesh(axis="data", devices=jax.devices()[:1])
    with pytest.raises(ValueError, match=r"no axis 'model'.*'data'"):
        ShardedEngine(mesh)                 # default axis="model"
    L, sched, b = _small()
    with pytest.raises(ValueError, match="no axis"):
        dist.solve_sharded(sched, b, mesh)
    with pytest.raises(ValueError, match="no axis"):
        TriangularOperator.from_csr(L, tune="no_rewriting", chunk=32,
                                    max_deps=4, mesh=mesh, cache=False)
    with pytest.raises(ValueError, match="no axis"):
        device_matvec(L, mesh=mesh)
    with pytest.raises(ValueError, match="no axis"):
        dist.count_all_gathers(sched, mesh)


def test_sharded_compile_memoizes_lowering(monkeypatch):
    """Repeat compiles of one schedule return the identical callable and
    never re-pad the groups (the seed re-padded and re-staged per call)."""
    _, sched, b = _small()
    calls = {"pad": 0}
    real_pad = dist._pad_group

    def counting_pad(*a, **kw):
        calls["pad"] += 1
        return real_pad(*a, **kw)

    monkeypatch.setattr(dist, "_pad_group", counting_pad)
    eng = ShardedEngine()               # fresh instance: first compile pads
    fn1 = eng.compile(sched)
    pads_after_first = calls["pad"]
    assert pads_after_first > 0
    from repro.solver import to_device
    fn2 = eng.compile(sched)
    fn3 = eng.compile(to_device(sched))     # DeviceSchedule resolves .host
    assert fn1 is fn2 is fn3
    assert calls["pad"] == pads_after_first
    # a different schedule is a different lowering, not a stale hit
    _, other, _ = _small(seed=11)
    assert eng.compile(other) is not fn1
    assert calls["pad"] > pads_after_first


def test_sharded_compile_inside_jit_trace_stays_usable():
    """Regression: a lowering first triggered INSIDE a jit trace (an
    operator first used as a traced preconditioner) must memoize concrete
    staged arrays, not tracer-backed constants — later solves outside the
    trace used to die with UnexpectedTracerError."""
    import jax
    import jax.numpy as jnp
    L, sched, b = _small(seed=13)
    eng = ShardedEngine()

    @jax.jit
    def traced(v):
        return eng.compile(sched)(v)

    ref = solve_csr_seq(L, b)
    y = np.asarray(traced(jnp.asarray(b, np.float32)))
    assert np.abs(y - ref).max() < 2e-4
    # the memoized fn (same object) must stay usable outside the trace
    x = np.asarray(eng.compile(sched)(jnp.asarray(b, np.float32)))
    assert np.abs(x - ref).max() < 2e-4


def test_solve_sharded_reuses_engine_lowering():
    import jax
    L, sched, b = _small()
    mesh = dist.default_mesh(devices=jax.devices()[:1])
    x = solve_sharded(sched, b, mesh)
    assert np.abs(x - solve_csr_seq(L, b)).max() < 2e-4
    eng = sharded_engine(mesh)
    fn = eng.compile(sched)             # memo hit from solve_sharded's call
    assert eng.compile(sched) is fn


# -- collective-count invariant ----------------------------------------------

def test_all_gather_families_equal_steps():
    _, sched, _ = _small()
    g = count_all_gathers(sched)
    assert g["families"] == g["steps"] == sched.num_steps
    assert g["calls"] >= 2 * g["steps"]


def test_all_gather_families_equal_steps_with_carries():
    """Split-row (carry-bearing) schedules ship their carry updates in the
    SAME per-step family — synchronization points must not double."""
    Lb = generators.banded(160, 12, seed=1)
    sb = schedule_for_csr(Lb, build_levels(Lb), chunk=16, max_deps=4)
    assert sb.n_carry > 0               # the premise: carries exist
    g = count_all_gathers(sb)
    assert g["families"] == g["steps"] == sb.num_steps
    # carry steps gather (xi, rids, tots, couts): more calls, same barriers
    assert g["calls"] > 2 * g["steps"]
    bb = np.random.default_rng(1).standard_normal(160)
    import jax
    mesh = dist.default_mesh(devices=jax.devices()[:1])
    xb = solve_sharded(sb, bb, mesh)
    assert np.abs(xb - solve_csr_seq(Lb, bb)).max() < 2e-4


# -- multi-device matrix (subprocess, 8 forced host devices) ------------------

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import AvgLevelCost, transform
    from repro.iterative import cg
    from repro.iterative.operators import device_matvec
    from repro.precond import Preconditioner
    from repro.solver import (get_engine, schedule_for_csr,
                              schedule_for_transformed, sharded_engine,
                              solve_csr_seq)
    from repro.solver.distributed import (count_all_gathers, default_mesh,
                                          solve_sharded)
    from repro.sparse import build_levels, generators

    res = {}
    devs = jax.devices()
    assert len(devs) == 8

    L = generators.random_lower(400, avg_offdiag=2.0, seed=3, max_back=24)
    lv = build_levels(L)
    b = np.random.default_rng(0).standard_normal(400)
    x_ref = solve_csr_seq(L, b)
    sched = schedule_for_csr(L, lv, chunk=32, max_deps=4, dtype=np.float32)

    # mesh-size sweep: 1/2/4/8 shards of the same schedule
    res["mesh_errs"] = {}
    for d in (1, 2, 4, 8):
        mesh = default_mesh(devices=devs[:d])
        x = solve_sharded(sched, b, mesh, axis="model")
        res["mesh_errs"][str(d)] = float(np.abs(x - x_ref).max())

    # transformed system: fewer steps => fewer all_gather families
    mesh8 = default_mesh(devices=devs)
    ts = transform(L, AvgLevelCost(), validate=False, codegen=False)
    s1 = schedule_for_transformed(ts, chunk=32, max_deps=4)
    c = ts.preamble(b).astype(np.float32)
    x1 = solve_sharded(s1, c, mesh8, axis="model")
    res["err_transformed"] = float(np.abs(x1 - x_ref).max())
    res["steps0"], res["steps1"] = sched.num_steps, s1.num_steps
    res["gathers0"] = count_all_gathers(sched, mesh8)["families"]
    res["gathers1"] = count_all_gathers(s1, mesh8)["families"]

    # carry-bearing (split-row) schedule under 8-way sharding
    Lb = generators.banded(160, 12, seed=1)
    sb = schedule_for_csr(Lb, build_levels(Lb), chunk=16, max_deps=4)
    assert sb.n_carry > 0
    bb = np.random.default_rng(1).standard_normal(160)
    xb = solve_sharded(sb, bb, mesh8)
    res["err_carry"] = float(np.abs(xb - solve_csr_seq(Lb, bb)).max())

    # batched (n, k) RHS through the engine: lanes sharded, columns
    # replicated
    eng = sharded_engine(mesh8)
    fn = eng.compile(sched)
    B = np.random.default_rng(2).standard_normal((400, 3))
    X = np.asarray(fn(jnp.asarray(B, np.float32)))
    res["err_batched"] = float(max(
        np.abs(X[:, j] - solve_csr_seq(L, B[:, j])).max()
        for j in range(3)))
    res["memoized"] = fn is eng.compile(sched)

    # end-to-end PCG under ONE mesh: sharded SpMV + sharded M^-1 sweeps,
    # no host round-trips between matvec and preconditioner
    A = generators.poisson2d_spd(12, 12)
    P = Preconditioner.ic0(A, tune="no_rewriting", mesh=mesh8, cache=False)
    assert P.forward.engine == "sharded" and P.backward.engine == "sharded"
    mv = device_matvec(A, mesh=mesh8)
    rhs = np.random.default_rng(3).standard_normal(A.n_rows)
    y = np.asarray(mv(jnp.asarray(rhs, np.float32)))
    res["err_spmv"] = float(np.abs(y - A.matvec(rhs)).max())
    out = cg(mv, jnp.asarray(rhs, np.float32), preconditioner=P,
             tol=1e-5, maxiter=300)
    r = rhs - np.asarray(mv(out.x), dtype=np.float64)
    res["pcg_converged"] = bool(out.converged)
    res["pcg_iters"] = int(out.iterations)
    res["pcg_resid"] = float(np.abs(r).max())
    print(json.dumps(res))
""")


def test_sharded_solver_subprocess():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).parent.parent, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for d, err in res["mesh_errs"].items():
        assert err < 1e-3, f"mesh size {d}"
    assert res["err_transformed"] < 1e-3
    assert res["err_carry"] < 1e-3
    assert res["err_batched"] < 1e-3
    assert res["memoized"]
    # the paper's claim, made literal: fewer steps == fewer barriers
    assert res["steps1"] <= res["steps0"]
    assert res["gathers0"] == res["steps0"]
    assert res["gathers1"] == res["steps1"]
    # one-mesh PCG: sharded matvec is exact-ish, the loop converges
    assert res["err_spmv"] < 1e-3
    assert res["pcg_converged"] and res["pcg_resid"] < 1e-3
    assert 0 < res["pcg_iters"] < 100
