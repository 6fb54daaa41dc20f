"""Engine protocol + registry: resolution, capabilities, error paths, and
the string-kwarg deprecation shim (ISSUE 3 tentpole)."""
import warnings

import numpy as np
import pytest

from repro.solver import (Engine, PallasEngine, available_engines,
                          default_engine, engine_capabilities, get_engine,
                          register_engine, registered_engines,
                          resolve_engine, schedule_for_csr, solve,
                          solve_csr_seq, to_device)
from repro.solver import engines as engines_mod
from repro.sparse import build_levels, generators


def _small_problem(n=120, seed=7):
    L = generators.random_lower(n, avg_offdiag=2.0, seed=seed, max_back=15)
    sched = schedule_for_csr(L, build_levels(L), chunk=32, max_deps=4)
    b = np.random.default_rng(0).standard_normal(n)
    return L, sched, b


def test_registry_contents_and_capabilities():
    names = registered_engines()
    assert {"scan", "unrolled", "pallas", "pallas-interpret",
            "sharded"} <= set(names)
    assert set(available_engines()) <= set(names)
    caps = engine_capabilities()
    for name in names:
        c = caps[name]
        assert c["name"] == name
        assert isinstance(c["supports_batched_rhs"], bool)
        assert isinstance(c["supports_pallas_backend"], bool)
        assert c["dtypes"]
    assert caps["scan"]["supports_batched_rhs"]
    assert caps["pallas"]["supports_pallas_backend"]
    assert not caps["scan"]["supports_pallas_backend"]


def test_resolve_engine_paths():
    assert resolve_engine(None) is default_engine()
    assert resolve_engine("scan").name == "scan"
    eng = get_engine("unrolled")
    assert resolve_engine(eng) is eng              # instance passes through
    with pytest.raises(TypeError, match="engine spec"):
        resolve_engine(42)


def test_unknown_engine_raises_with_registered_list():
    with pytest.raises(ValueError, match="unknown engine 'palas'"):
        get_engine("palas")
    with pytest.raises(ValueError, match="scan"):      # names the options
        resolve_engine("definitely-not-an-engine")


def test_levelset_solve_unknown_engine_raises():
    """Regression (ISSUE 3 satellite): the seed silently sent 'pallas' and
    any typo to the unrolled engine; unknown names must now raise naming
    the registered options."""
    _, sched, b = _small_problem()
    with pytest.raises(ValueError, match="registered engines"):
        solve(sched, b, engine="unroled")       # typo must not fall through
    with pytest.raises(ValueError, match="scan"):
        solve(sched, b, engine="no-such-engine")


def test_levelset_solve_pallas_actually_runs_pallas():
    """'pallas' used to silently mean 'unrolled'; through the registry it
    must produce the (correct) pallas-kernel solve."""
    L, sched, b = _small_problem()
    x = solve(sched, b, engine=get_engine("pallas-interpret"))
    x_ref = solve_csr_seq(L, b)
    assert np.abs(x - x_ref).max() / max(1.0, np.abs(x_ref).max()) < 2e-5


def test_levelset_solve_string_shim_warns_and_works():
    L, sched, b = _small_problem()
    with pytest.warns(DeprecationWarning, match="deprecated"):
        x = solve(sched, b, engine="scan")
    x_ref = solve_csr_seq(L, b)
    assert np.abs(x - x_ref).max() / max(1.0, np.abs(x_ref).max()) < 2e-5


def test_levelset_solve_engine_instance_no_warning():
    _, sched, b = _small_problem()
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        solve(sched, b, engine=get_engine("scan"))
        solve(sched, b)                         # default: no shim, no warning


def test_every_available_engine_solves_batched():
    L, sched, b = _small_problem()
    import jax.numpy as jnp
    ds = to_device(sched)
    B = np.random.default_rng(1).standard_normal((L.n_rows, 3))
    for name in available_engines():
        eng = get_engine(name)
        fn = eng.compile(ds)
        x = np.asarray(fn(jnp.asarray(b, ds.dtype)))
        x_ref = solve_csr_seq(L, b)
        assert np.abs(x - x_ref).max() < 2e-4, name
        if eng.supports_batched_rhs:
            X = np.asarray(fn(jnp.asarray(B, ds.dtype)))
            for j in range(3):
                assert np.abs(X[:, j] - solve_csr_seq(L, B[:, j])).max() \
                    < 2e-4, name


def test_internal_string_shim_use_is_an_error():
    """The CI deprecation gate (pytest.ini filterwarnings): a string-engine
    shim warning ORIGINATING FROM a repro module frame is an error, while
    the same call from user/test code only warns (covered above).  Simulated
    by exec'ing a caller into repro.solver.levelset's own namespace."""
    from repro.solver import levelset
    _, sched, b = _small_problem()
    src = ("def _internal_caller(sched, b):\n"
           "    return solve(sched, b, engine='scan')\n")
    exec(compile(src, levelset.__file__, "exec"), levelset.__dict__)
    try:
        with pytest.raises(DeprecationWarning):
            levelset._internal_caller(sched, b)
    finally:
        del levelset.__dict__["_internal_caller"]


def test_register_engine_collision_and_custom():
    class EchoEngine(Engine):
        name = "echo-test"
        supports_batched_rhs = False

        def compile(self, dsched):
            return lambda c: c

    try:
        register_engine(EchoEngine())
        assert "echo-test" in registered_engines()
        with pytest.raises(ValueError, match="already registered"):
            register_engine(EchoEngine())
        register_engine(EchoEngine(), overwrite=True)   # explicit replace ok
        assert resolve_engine("echo-test").name == "echo-test"
    finally:
        engines_mod._REGISTRY.pop("echo-test", None)


def test_operator_accepts_unregistered_engine_instance():
    """from_csr must honor a custom Engine instance that is NOT in the
    registry (and not silently swap a same-named registered instance in);
    compiled-fn caching is per instance, not per name."""
    from repro.solver import TriangularOperator, solve_csr_seq

    class CountingScan(Engine):
        name = "scan"                   # shadows the registered name

        def __init__(self):
            self.compiles = 0

        def compile(self, dsched):
            self.compiles += 1
            import jax
            from repro.solver.levelset import solve_scan
            return jax.jit(lambda c: solve_scan(dsched, c))

    L = generators.random_lower(60, avg_offdiag=2.0, seed=2, max_back=8)
    mine = CountingScan()
    op = TriangularOperator.from_csr(L, tune="no_rewriting", chunk=16,
                                     max_deps=4, engine=mine, cache=False)
    assert op._engine is mine           # not replaced by the registry's scan
    b = np.random.default_rng(4).standard_normal(60)
    x = op.solve(b)
    assert mine.compiles == 1
    op.solve(b)                         # same instance: compiled fn reused
    assert mine.compiles == 1
    op.solve(b, engine=get_engine("scan"))      # same name, other instance:
    assert mine.compiles == 1                   # must not reuse mine's fn
    x_ref = solve_csr_seq(L, b)
    assert np.abs(x - x_ref).max() / max(1.0, np.abs(x_ref).max()) < 1e-8

    class Unnamed(Engine):
        name = "not-registered-anywhere"

        def compile(self, dsched):
            import jax
            from repro.solver.levelset import solve_scan
            return jax.jit(lambda c: solve_scan(dsched, c))

    op2 = TriangularOperator.from_csr(L, tune="no_rewriting", chunk=16,
                                      max_deps=4, engine=Unnamed(),
                                      cache=False)
    assert op2.engine == "not-registered-anywhere"
    assert np.abs(op2.solve(b) - x_ref).max() < 1e-8


def test_pallas_engine_interpret_pinning():
    eng = PallasEngine(interpret=True, name="tmp-pallas")
    assert eng.interpret is True
    assert get_engine("pallas").interpret is None   # platform-default instance
    assert get_engine("pallas-interpret").interpret is True


def test_default_interpret_follows_the_platform(monkeypatch):
    import jax
    assert engines_mod.default_interpret() is True      # CPU backend here
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")   # no longer read
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert engines_mod.default_interpret() is False


def test_pallas_refusal_off_cpu_is_a_counted_fallback(monkeypatch):
    """Off the CPU the "pallas" engine never runs the interpreter: it
    compiles with Mosaic or is refused, and a refusal is served by scan
    behind an EngineFallbackWarning, counted in op.stats and labelled scan
    in the trace — never a scan solve passed off as a Pallas one.  On
    this CPU, a non-interpret pallas_call is refused like Mosaic's."""
    import jax
    from repro import obs
    from repro.core.resilience import EngineFallbackWarning
    from repro.solver import TriangularOperator
    L, _, b = _small_problem()
    op = TriangularOperator.from_csr(L, tune="no_rewriting", chunk=32,
                                     max_deps=4, cache=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tracer = obs.enable()
    try:
        with pytest.warns(EngineFallbackWarning, match="'pallas'"):
            x = op.solve(b, engine="pallas", max_refine=0)
    finally:
        obs.disable()
    assert np.abs(x - solve_csr_seq(L, b)).max() < 1e-4
    assert op.stats.fallbacks == 1
    assert op.stats.last_fallback == "pallas->scan"
    used = [s.attrs["engine_used"] for s in tracer.spans()
            if s.name == "operator.solve"]
    assert used == ["scan"]


# -- dtype capability enforcement (ISSUE 5 satellite) -------------------------

def _schedule_with_dtype(dtype, n=100, seed=3):
    L = generators.random_lower(n, avg_offdiag=2.0, seed=seed, max_back=12)
    return L, schedule_for_csr(L, build_levels(L), chunk=32, max_deps=4,
                               dtype=dtype)


def test_pallas_rejects_float64_schedule():
    """Regression: PallasEngine declares dtypes=("float32",) but compile()
    used to silently accept (and cast) a float64 schedule — the module's
    own "never a silent fallback" contract.  The error must name the
    engine and the offending dtype."""
    _, s64 = _schedule_with_dtype(np.float64)
    ds = to_device(s64)
    for name in ("pallas", "pallas-interpret"):
        with pytest.raises(ValueError, match=rf"{name}.*float64"):
            get_engine(name).compile(ds)


def test_dtype_capable_engines_still_compile_float64():
    L, s64 = _schedule_with_dtype(np.float64)
    b = np.random.default_rng(0).standard_normal(L.n_rows)
    x_ref = solve_csr_seq(L, b)
    for name in ("scan", "unrolled"):
        eng = get_engine(name)
        assert "float64" in eng.dtypes
        x = np.asarray(eng.compile(to_device(s64))(b))
        assert np.abs(x - x_ref).max() < 1e-4, name


def test_operator_surfaces_pallas_dtype_violation():
    """The capability check fires through the serving facade too: a
    float64 operator asked to solve via the pallas engine never silently
    casts to float32 — the rejection downgrades the solve through the
    engine fallback chain, warned and recorded in op.stats
    (docs/robustness.md; the bare-engine compile raise is covered by
    test_pallas_rejects_float64_schedule)."""
    from repro.core.resilience import EngineFallbackWarning
    from repro.solver import TriangularOperator
    L = generators.random_lower(80, avg_offdiag=2.0, seed=9, max_back=10)
    op = TriangularOperator.from_csr(L, tune="no_rewriting", chunk=16,
                                     max_deps=4, dtype=np.float64,
                                     cache=False)
    b = np.random.default_rng(1).standard_normal(80)
    assert np.isfinite(op.solve(b)).all()       # scan path: float64 is fine
    with pytest.warns(EngineFallbackWarning, match="float64"):
        x = op.solve(b, engine="pallas-interpret")
    assert np.isfinite(x).all()
    assert op.stats.last_fallback == "pallas-interpret->scan"
