"""Execution-engine protocol + registry: the single seam for solve dispatch.

Before this module, engine selection was bare strings ("scan" / "unrolled" /
"pallas") if/else-dispatched independently in solver/levelset.py,
solver/operator.py, and kernels/ops.py — adding a backend meant touching all
three, and a typo silently fell through to the unrolled engine.  Now every
engine is a registered object with capability metadata, and every consumer
(`levelset.solve`, `TriangularOperator`, `sptrsv`, the portfolio's measured
mode, `benchmarks/`) resolves it through one entry point:

    eng = resolve_engine("scan")          # name, Engine instance, or None
    fn  = eng.compile(dsched)             # DeviceSchedule -> jnp callable
    x   = fn(c)                           # c: (n,) or batched (n, R)

Engine contract
===============
* `name`                    — stable registry key (also the cache-key form).
* `supports_batched_rhs`    — accepts (n, R) right-hand sides.
* `supports_pallas_backend` — lowers through the Pallas kernel path.
* `dtypes`                  — schedule dtypes the engine is validated for.
* `available()`             — importable/usable in this process (an engine
  may be registered but unavailable, e.g. a TPU-only backend on CPU).
* `compile(dsched)`         — returns `fn(c) -> x` over jnp arrays in the
  schedule dtype; `fn` may be called repeatedly (serving path) and must not
  restage the schedule.

Unknown names raise `ValueError` listing the registered engines — never a
silent fallback.  String engine names remain accepted at the public entry
points as thin shims that resolve here; `levelset.solve`'s legacy string
kwarg additionally emits a `DeprecationWarning` (CI fails on such warnings
originating from repro's own modules, so internal code must pass Engine
objects).
"""
from __future__ import annotations

import collections
import threading
import warnings

__all__ = ["Engine", "ScanEngine", "UnrolledEngine", "PallasEngine",
           "ShardedEngine", "sharded_engine", "compile_source",
           "register_engine", "resolve_engine", "get_engine",
           "registered_engines", "available_engines", "default_engine",
           "default_interpret", "engine_capabilities", "DEFAULT_ENGINE",
           "engine_fallbacks", "set_fallback_chain", "fallback_chains"]

DEFAULT_ENGINE = "scan"


def compile_source(engine, sched, staged_fn):
    """The schedule form an engine's `compile()` consumes: the host
    LevelSchedule for host-lowering engines (`lowers_from_host` — they
    pad/stage their own copy), else `staged_fn()` (a DeviceSchedule
    supplier, typically a cached staging).  The ONE branch every consumer
    — serving (`TriangularOperator`) and measuring (portfolio /
    preconditioner pair timing) — goes through, so what gets timed is
    always lowered the same way as what gets served."""
    if getattr(engine, "lowers_from_host", False):
        return sched
    return staged_fn()


def default_interpret() -> bool:
    """Pallas interpret mode default, chosen by the platform: the
    interpreter on the CPU backend only.  Elsewhere the kernel compiles
    with Mosaic or its compile raises, and the operator's fallback chain
    reports the downgrade (EngineFallbackWarning, `stats.fallbacks`)."""
    import jax
    return jax.default_backend() == "cpu"


class Engine:
    """Base class / protocol for SpTRSV execution engines (module doc)."""

    name: str = "abstract"
    supports_batched_rhs: bool = True
    supports_pallas_backend: bool = False
    dtypes: tuple = ("float32", "float64")
    # engines whose lowering is a host-side pass (ShardedEngine pads lane
    # capacities in numpy) set this so consumers hand compile() the host
    # LevelSchedule instead of staging an unpadded DeviceSchedule the
    # engine would ignore (a wasted H2D transfer + pinned device copy)
    lowers_from_host: bool = False

    def available(self) -> bool:
        return True

    def compile(self, dsched):
        """DeviceSchedule -> callable fn(c) -> x over jnp arrays."""
        raise NotImplementedError

    def _require_dtype(self, dsched) -> None:
        """Enforce the declared dtype capability (module contract: never a
        silent fallback).  Every concrete compile() calls this first, so a
        schedule whose dtype the engine is not validated for raises — with
        the engine name and the offending dtype — instead of silently
        casting the solve down/up.

        The capability describes what the engine's kernels are validated
        for; it is not a jax-config check.  Executing a float64 schedule
        additionally requires jax x64 mode (JAX_ENABLE_X64=1) — without
        it jax itself truncates device arrays to float32 and says so with
        its own UserWarning."""
        import numpy as np
        got = np.dtype(dsched.dtype).name
        if got not in self.dtypes:
            raise ValueError(
                f"engine {self.name!r} supports dtypes "
                f"{tuple(self.dtypes)} but the schedule dtype is {got!r}; "
                f"recompile the schedule with a supported dtype or "
                f"resolve an engine that declares {got!r}")

    def cache_token(self) -> str:
        """Identity recorded in measured-mode cache keys ("which engine
        was timed").  The registry name by default; engines whose timings
        depend on more than the name must qualify it (ShardedEngine adds
        the mesh, since the same schedule measures differently per mesh).
        """
        return self.name

    def capabilities(self) -> dict:
        return {
            "name": self.name,
            "supports_batched_rhs": self.supports_batched_rhs,
            "supports_pallas_backend": self.supports_pallas_backend,
            "dtypes": list(self.dtypes),        # list: JSON round-trip stable
            "available": self.available(),
        }

    def __repr__(self) -> str:  # pragma: no cover
        return f"{type(self).__name__}(name={self.name!r})"


class ScanEngine(Engine):
    """`lax.scan` over steps — HLO size independent of step count (default)."""

    name = "scan"

    def compile(self, dsched):
        # staged_scan_fn passes the schedule leaves as jit ARGUMENTS, so
        # compiling a value-repacked schedule with unchanged tile shapes
        # (update_values) reuses the cached XLA executable
        from .levelset import staged_scan_fn
        self._require_dtype(dsched)
        return staged_scan_fn(dsched)


class UnrolledEngine(Engine):
    """Trace-time unrolled steps — bigger HLO, more fusion freedom; sensible
    after the transformation shrank the step count."""

    name = "unrolled"

    def compile(self, dsched):
        from .levelset import staged_unrolled_fn
        self._require_dtype(dsched)
        return staged_unrolled_fn(dsched)


class PallasEngine(Engine):
    """Pallas TPU kernel (interpret mode on CPU): one grid step per schedule
    step, x/carry resident in VMEM.  `interpret=None` follows the
    platform (`default_interpret`) at compile time."""

    supports_pallas_backend = True
    dtypes = ("float32",)

    def __init__(self, interpret: bool | None = None, name: str = "pallas"):
        self.name = name
        self.interpret = interpret

    def available(self) -> bool:
        try:
            import jax.experimental.pallas  # noqa: F401
        except Exception:  # pragma: no cover - env dependent
            return False
        return True

    def compile(self, dsched):
        import jax.numpy as jnp
        from ..kernels.sptrsv_level import (sptrsv_groups_pallas,
                                            sptrsv_groups_pallas_multi)
        # the kernel is validated for float32 only: a float64 schedule
        # must raise here, not silently cast (regression: the capability
        # metadata used to be declarative-only)
        self._require_dtype(dsched)
        interpret = (default_interpret() if self.interpret is None
                     else self.interpret)
        from jax.tree_util import Partial
        n, n_carry, dtype = dsched.n, dsched.n_carry, dsched.dtype

        def fn(groups, c):
            c = jnp.asarray(c, dtype=dtype)
            tail = (c.shape[1],) if c.ndim == 2 else ()
            c_pad = jnp.concatenate([c, jnp.zeros((1,) + tail, dtype)],
                                    axis=0)
            kern = sptrsv_groups_pallas_multi if tail else sptrsv_groups_pallas
            return kern(groups, c_pad, n=n, n_carry=n_carry,
                        interpret=interpret)

        # the staged groups are the callable's pytree leaves, as with the
        # scan engine (levelset.staged_scan_fn)
        return Partial(fn, dsched.groups)


class ShardedEngine(Engine):
    """shard_map distributed engine: lanes of each step sharded over one
    mesh axis, x replicated, ONE all_gather family per schedule step — the
    transformation's "fewer barriers" is literally fewer collectives
    (solver/distributed.py, docs/distributed.md).  Batched (n, k) RHS run
    with lanes sharded and RHS columns replicated, meeting the same
    `supports_batched_rhs` contract as the single-device engines.

    `mesh=None` (the registered default instance) lazily meshes every
    local device along `axis` at compile time.  Lowering is memoized per
    (schedule identity, mesh, axis): repeat compiles of the same schedule
    return the identical callable and never re-pad or re-stage the groups
    — the serving path pays the host-side padding exactly once.
    """

    lowers_from_host = True

    def __init__(self, mesh=None, axis: str = "model",
                 name: str = "sharded"):
        if mesh is not None:
            # fail at construction, not with a KeyError deep in lowering
            from .distributed import require_axis
            require_axis(mesh, axis)
        self.name = name
        self.mesh = mesh            # None: all local devices, resolved lazily
        self.axis = axis
        # (id(schedule), mesh, axis) -> (weakref(schedule), compiled fn);
        # the weakref guards against id() reuse after garbage collection.
        # Bounded LRU: each entry pins a padded staged schedule (device
        # memory), and the registered instance lives for the process —
        # eviction only costs a re-lowering on a later compile
        self._lowered: "collections.OrderedDict" = collections.OrderedDict()
        self._lowered_max: int = 32
        # serving-tier workers compile from multiple threads; an OrderedDict
        # mid-move_to_end/popitem must not be mutated concurrently.  Held
        # across the lowering itself so one schedule is lowered once, not
        # racing-ly re-padded by every thread that misses
        self._lowered_lock = threading.RLock()

    def available(self) -> bool:
        try:
            import jax.sharding  # noqa: F401
        except Exception:  # pragma: no cover - env dependent
            return False
        return True

    def resolve_mesh(self):
        """The engine's mesh: the constructor-pinned one, else the cached
        all-local-devices mesh along `axis`."""
        if self.mesh is not None:
            return self.mesh
        from .distributed import default_mesh
        return default_mesh(axis=self.axis)

    def cache_token(self) -> str:
        """Mesh-qualified identity: two sharded engines over different
        meshes must never share a measured-mode cache entry — collective
        costs are a function of the mesh."""
        mesh = self.resolve_mesh()
        devs = ",".join(str(d.id) for d in mesh.devices.flat)
        return f"{self.name}[{self.axis}:{devs}]"

    def compile(self, dsched):
        import weakref
        from .distributed import lower_sharded
        self._require_dtype(dsched)
        # lowering starts from the HOST schedule (padding is a numpy
        # pass); a DeviceSchedule hands it back via .host, and a bare
        # LevelSchedule is accepted directly (solve_sharded's path)
        host = getattr(dsched, "host", dsched)
        mesh = self.resolve_mesh()
        key = (id(host), mesh, self.axis)
        with self._lowered_lock:
            hit = self._lowered.get(key)
            if hit is not None and hit[0]() is host:
                self._lowered.move_to_end(key)
                return hit[1]
            fn = lower_sharded(host, mesh, axis=self.axis)
            for k in [k for k, v in self._lowered.items()
                      if v[0]() is None]:
                del self._lowered[k]                 # drop collected entries
            self._lowered[key] = (weakref.ref(host), fn)
            while len(self._lowered) > self._lowered_max:
                self._lowered.popitem(last=False)
            return fn


# -- fallback chains ----------------------------------------------------------

# engine name -> ordered degradation chain tried when the preferred engine
# is unavailable or its compile/solve raises (repro.core.resilience:
# EngineFallbackWarning on every downgrade, EngineFallbackError when the
# whole chain fails — never a silent substitution).  The scan engine is
# the terminal fallback everywhere: pure lax.scan, no Pallas, no mesh, no
# dtype restrictions — the most conservative compiled path in the repo.
_FALLBACK_CHAINS: dict[str, tuple] = {
    "pallas": ("scan",),
    "pallas-interpret": ("scan",),
    "sharded": ("scan",),
    "unrolled": ("scan",),
}


def fallback_chains() -> dict:
    """Copy of the configured name -> chain map (docs/robustness.md)."""
    return dict(_FALLBACK_CHAINS)


def set_fallback_chain(name: str, chain) -> None:
    """Configure the degradation chain for an engine name.  `chain` is an
    ordered iterable of registered engine names; an empty chain means
    "fail fast, no downgrade"."""
    _FALLBACK_CHAINS[name] = tuple(chain)


def engine_fallbacks(engine) -> tuple:
    """The resolved degradation chain for an engine: registered Engine
    instances, in order, the engine itself excluded.  Names in the chain
    that are not registered are skipped (a chain must never raise during
    resolution — it is consulted on the failure path)."""
    out = []
    for name in _FALLBACK_CHAINS.get(getattr(engine, "name", None), ()):
        eng = _REGISTRY.get(name)
        if eng is not None and eng is not engine and eng not in out:
            out.append(eng)
    return tuple(out)


# -- registry -----------------------------------------------------------------

_REGISTRY: dict[str, Engine] = {}
# register_engine's exists-check + insert must be atomic under concurrent
# registration (serving workers registering custom engines at startup)
_REGISTRY_LOCK = threading.RLock()
# bounded LRU: each retained instance pins its memoized lowerings, and a
# process sweeping many device-subset meshes must not accumulate engines
# (and their closed-over staged schedules) forever
_SHARDED_INSTANCES: collections.OrderedDict = collections.OrderedDict()
_SHARDED_INSTANCES_MAX = 8
# concurrent sharded_engine() resolutions (serving workers under mesh=)
# must not interleave OrderedDict eviction
_SHARDED_INSTANCES_LOCK = threading.RLock()


def sharded_engine(mesh=None, axis: str = "model") -> ShardedEngine:
    """Memoized ShardedEngine per (mesh, axis): `mesh=None` — or an
    explicit mesh that equals the default instance's resolved
    all-local-devices mesh — returns the registered default instance;
    other meshes share one instance each (bounded LRU).  Every call site
    (solve_sharded, TriangularOperator(mesh=...), Preconditioner(mesh=...),
    engine="sharded") therefore lands on ONE instance per distinct mesh,
    so the lowering memo is never split."""
    reg = _REGISTRY.get("sharded")
    default = reg if isinstance(reg, ShardedEngine) else None
    if default is not None and default.axis == axis and (
            mesh is None or (default.mesh is None
                             and mesh == default.resolve_mesh())):
        return default
    key = (mesh, axis)
    with _SHARDED_INSTANCES_LOCK:
        eng = _SHARDED_INSTANCES.get(key)
        if eng is None:
            eng = _SHARDED_INSTANCES[key] = ShardedEngine(mesh, axis=axis)
        _SHARDED_INSTANCES.move_to_end(key)
        while len(_SHARDED_INSTANCES) > _SHARDED_INSTANCES_MAX:
            _SHARDED_INSTANCES.popitem(last=False)
        return eng


def register_engine(engine: Engine, overwrite: bool = False) -> Engine:
    """Register an engine under `engine.name`; returns it for chaining."""
    if not isinstance(engine.name, str) or not engine.name:
        raise TypeError(f"engine must carry a non-empty string name: "
                        f"{engine!r}")
    with _REGISTRY_LOCK:
        if engine.name in _REGISTRY and not overwrite:
            raise ValueError(f"engine {engine.name!r} already registered "
                             f"(pass overwrite=True to replace)")
        _REGISTRY[engine.name] = engine
    return engine


def registered_engines() -> tuple:
    """Sorted names of every registered engine (available or not)."""
    return tuple(sorted(_REGISTRY))


def available_engines() -> tuple:
    """Sorted names of registered engines whose available() is True."""
    return tuple(name for name in registered_engines()
                 if _REGISTRY[name].available())


def engine_capabilities() -> dict:
    """name -> capability dict for every registered engine (CI smoke uses
    this to print the capability matrix)."""
    return {name: _REGISTRY[name].capabilities()
            for name in registered_engines()}


def get_engine(name: str) -> Engine:
    """Look a registered engine up by name; unknown names raise ValueError
    listing the registered options (never a silent fallback)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; registered engines: "
            f"{list(registered_engines())}") from None


def default_engine() -> Engine:
    return _REGISTRY[DEFAULT_ENGINE]


def resolve_engine(spec=None, *, mesh=None, mesh_axis: str = "model") \
        -> Engine:
    """Resolve an engine spec: None -> default, a name string -> registry
    lookup, an Engine (or anything with name + compile) passes through.

    `mesh=` (with `mesh_axis=`) resolves to the shared ShardedEngine for
    that mesh instead — the ONE place the facades' mesh option maps to an
    engine — and is mutually exclusive with an explicit spec."""
    if mesh is not None:
        if spec is not None:
            raise ValueError("pass either mesh= or engine=, not both "
                             "(mesh= implies the sharded engine)")
        return sharded_engine(mesh, mesh_axis)
    if spec is None:
        return default_engine()
    if isinstance(spec, str):
        return get_engine(spec)
    if isinstance(spec, Engine) or (hasattr(spec, "compile")
                                    and hasattr(spec, "name")):
        return spec
    raise TypeError(f"engine spec must be None, a registered name, or an "
                    f"Engine instance, got {type(spec).__name__}")


def resolve_engine_shim(spec, where: str, stacklevel: int = 3) -> Engine:
    """Legacy string-kwarg shim: same resolution as resolve_engine, but a
    bare string additionally emits a DeprecationWarning attributed to the
    caller (so CI can fail on internal use while user code keeps working).
    Resolution happens first: typos raise the ValueError naming the
    registered engines, never the deprecation notice."""
    if isinstance(spec, str):
        eng = get_engine(spec)
        warnings.warn(
            f"passing engine name strings to {where} is deprecated; pass an "
            f"Engine from repro.solver.engines (e.g. resolve_engine({spec!r}))",
            DeprecationWarning, stacklevel=stacklevel)
        return eng
    return resolve_engine(spec)


register_engine(ScanEngine())
register_engine(UnrolledEngine())
register_engine(PallasEngine(interpret=None, name="pallas"))
register_engine(PallasEngine(interpret=True, name="pallas-interpret"))
register_engine(ShardedEngine())
