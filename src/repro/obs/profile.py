"""Per-step schedule profiler: where does a solve's wall time go?

The paper's argument is about barriers and serial regions; this module
measures them.  `profile_schedule` executes a width-bucketed
`LevelSchedule` ONE STEP AT A TIME — the same `_step_body` the scan and
unrolled engines run, jitted once and reused for every step since all
steps share the tile shapes — and records a min-over-reps wall time per
step.  On the sharded path each step runs twice under `shard_map`: once
with the real per-step `all_gather` family and once with an identity
gather shim (same FLOPs, no collective — the numerics of that pass are
garbage and are discarded), so `collective_ms` = full − compute is the
per-step barrier cost the transformation exists to amortize.

The result is a `ScheduleProfile`: per-step times, the collective/compute
split, padded-FLOP utilization per width bucket, step-time histograms,
and critical-path share.  It is the measurement the analytic CostModel's
constants should come from — `CostModel.calibrate(profile)`
(repro.core.portfolio) fits them to one.

`ProfilingEngine` wraps any registered engine with this loop behind the
standard Engine protocol (opt-in: per-step dispatch costs real overhead,
this is a measurement tool, not a serving path), exposing `last_profile`
after each solve.  `profile_operator` profiles a built
`TriangularOperator`'s main schedule with the operator's own preamble
applied, routing mesh/axis from a sharded default engine.

Clocks are injected (`clock=time.perf_counter` by default), matching the
tracing core's discipline.  `core.faults.slow_step` patches this
module's `_STEP_FAULT` seam to inject a stall into one step — the chaos
test asserts the profile localizes it.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from .metrics import DEFAULT_MS_BUCKETS
from . import trace as _trace

__all__ = ["ScheduleProfile", "profile_schedule", "profile_operator",
           "ProfilingEngine"]

# (step_idx, seconds) | None — patched by core.faults.slow_step to stall
# one step of every *timed* pass (warmup runs stay clean)
_STEP_FAULT = None


def _fire_step_fault(s: int) -> None:
    f = _STEP_FAULT
    if f is not None and f[0] == s:
        time.sleep(f[1])


@dataclasses.dataclass
class ScheduleProfile:
    """One profiled execution of a schedule (module doc).

    `step_ms` is min-over-reps per step; `collective_ms` is present only
    for sharded profiles (None otherwise); the flop/byte columns are the
    schedule the run actually executed (lane-padded on the sharded path).
    """

    engine: str
    num_steps: int
    reps: int
    step_ms: np.ndarray
    collective_ms: np.ndarray | None
    step_padded_flops: np.ndarray
    step_real_flops: np.ndarray
    step_bytes: np.ndarray
    width_buckets: list

    @property
    def compute_ms(self):
        """Per-step compute component (collective subtracted, clamped at
        0); None when the profile has no collective split."""
        if self.collective_ms is None:
            return None
        return np.maximum(self.step_ms - self.collective_ms, 0.0)

    def total_ms(self) -> float:
        return float(self.step_ms.sum())

    def critical_path_share(self) -> float:
        """Share of total time the serialized step floor (S x fastest
        step) accounts for: 1.0 = perfectly uniform steps, low values =
        a few straggler steps dominate."""
        tot = float(self.step_ms.sum())
        if not self.num_steps or tot <= 0:
            return float("nan")
        return float(self.num_steps * self.step_ms.min() / tot)

    def utilization(self) -> float:
        """Real / padded FLOPs over the whole schedule."""
        p = sum(b["padded_flops"] for b in self.width_buckets)
        r = sum(b["real_flops"] for b in self.width_buckets)
        return r / p if p else 0.0

    def slowest_steps(self, k: int = 5) -> list:
        order = np.argsort(self.step_ms, kind="stable")[::-1]
        return [int(i) for i in order[:k]]

    def step_histogram(self, bounds=DEFAULT_MS_BUCKETS) -> dict:
        """Step-time histogram over fixed upper-inclusive bounds (ms);
        the final count is the +Inf overflow."""
        counts = [0] * (len(bounds) + 1)
        for v in self.step_ms:
            i = len(bounds)
            for j, b in enumerate(bounds):
                if v <= b:
                    i = j
                    break
            counts[i] += 1
        return {"bounds": list(bounds), "counts": counts}

    def to_dict(self) -> dict:
        return {
            "engine": self.engine, "num_steps": self.num_steps,
            "reps": self.reps,
            "total_ms": self.total_ms(),
            "critical_path_share": self.critical_path_share(),
            "utilization": self.utilization(),
            "step_ms": [float(v) for v in self.step_ms],
            "collective_ms": (None if self.collective_ms is None else
                              [float(v) for v in self.collective_ms]),
            "step_padded_flops": [int(v) for v in self.step_padded_flops],
            "step_real_flops": [int(v) for v in self.step_real_flops],
            "step_bytes": [float(v) for v in self.step_bytes],
            "width_buckets": list(self.width_buckets),
            "step_histogram": self.step_histogram(),
            "slowest_steps": self.slowest_steps(),
        }


def _schedule_columns(sched):
    """(per-step padded flops, per-step real flops, per-step bytes,
    width buckets) for the schedule as executed."""
    S = sched.num_steps
    ppf = 0
    rf = np.zeros(S, dtype=np.int64)
    buckets = []
    for g in sched.groups:
        s_, c_, d_ = g.dep_idx.shape
        padded = 2 * s_ * c_ * d_ + s_ * c_
        real = int(2 * (g.dep_coef != 0).sum() + g.is_final.sum())
        ppf += 2 * c_ * d_ + c_
        rf += (2 * (g.dep_coef != 0).sum(axis=(1, 2))
               + g.is_final.sum(axis=1))
        buckets.append({
            "width": int(g.width), "lanes": int(c_),
            "padded_flops": int(padded), "real_flops": real,
            "utilization": real / padded if padded else 0.0})
    pf = np.full(S, ppf, dtype=np.int64)
    sb = np.full(S, sched.memory_bytes() / max(1, S), dtype=np.float64)
    return pf, rf, sb, buckets


def _profile_and_solve(host, c, *, reps, warmup, clock, mesh, axis):
    """Core loop: returns (ScheduleProfile, x) for host LevelSchedule."""
    import jax
    import jax.numpy as jnp
    from ..solver.levelset import _init_state, _step_body, to_device

    c = jnp.asarray(c, dtype=jnp.empty(0, dtype=host.dtype).dtype)
    if mesh is None:
        exec_sched = host
        ds = to_device(host)
        step_fns = {"full": jax.jit(_step_body)}
        label = "stepwise"
    else:
        from jax.sharding import PartitionSpec as P
        from ..solver.distributed import (_gather, _padded_schedule,
                                          _step_update, require_axis)
        require_axis(mesh, axis)
        exec_sched = _padded_schedule(host, mesh.shape[axis])
        with jax.ensure_compile_time_eval():
            ds = to_device(exec_sched)
        # specs for ONE step's slices: stacked (S, C) leaves arrive as
        # (C,) lane vectors, (S, C, D) as (C, D) tiles — lanes sharded,
        # x/carry/c_pad replicated, exactly as in lower_sharded
        step_specs = tuple(
            tuple(P(axis) if l.ndim == 2 else P(axis, None) for l in g)
            for g in ds.leaves())

        def make_step(gather):
            def body(x, carry, c_pad, sg):
                return _step_update(x, carry, c_pad, sg,
                                    n_carry=ds.n_carry, axis=axis,
                                    gather=gather)
            return jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=(P(), P(), P(), step_specs),
                out_specs=(P(), P()), check_vma=False))

        # the identity-gather pass keeps each device's partial updates
        # local: same per-step FLOPs, no collective, unusable numerics —
        # timed and discarded (module doc)
        step_fns = {"full": make_step(_gather),
                    "compute": make_step(lambda v, ax: v)}
        label = "sharded"

    leaves = ds.leaves()
    S = ds.num_steps
    per_step = [tuple(tuple(l[s] for l in g) for g in leaves)
                for s in range(S)]

    def run(record, step_fn):
        x, carry, c_pad = _init_state(ds.n, ds.n_carry, c)
        for s, sg in enumerate(per_step):
            t0 = clock()
            if record is not None:
                _fire_step_fault(s)     # stall INSIDE the timed window
            x, carry = step_fn(x, carry, c_pad, sg)
            jax.block_until_ready((x, carry))
            if record is not None:
                record[s] = min(record[s], clock() - t0)
        return x[:ds.n]

    with _trace.span("profile.schedule", steps=S, engine=label,
                     reps=reps) as sp:
        timings = {}
        x = c[:ds.n] * 0 if S == 0 else None
        for kind, step_fn in step_fns.items():
            for _ in range(max(0, warmup)):
                run(None, step_fn)
            rec = np.full(S, np.inf)
            for _ in range(max(1, reps)):
                out = run(rec, step_fn)
                if kind == "full":
                    x = out
            timings[kind] = np.where(np.isfinite(rec), rec, 0.0)

        step_ms = timings["full"] * 1e3
        collective_ms = None
        if "compute" in timings:
            collective_ms = np.maximum(
                step_ms - timings["compute"] * 1e3, 0.0)
        pf, rf, sb, buckets = _schedule_columns(exec_sched)
        prof = ScheduleProfile(
            engine=label, num_steps=S, reps=max(1, reps), step_ms=step_ms,
            collective_ms=collective_ms, step_padded_flops=pf,
            step_real_flops=rf, step_bytes=sb, width_buckets=buckets)
        sp.set(total_ms=prof.total_ms(),
               critical_path_share=prof.critical_path_share(),
               utilization=prof.utilization())
        for s in prof.slowest_steps():
            sp.event("profile.step", step=s, ms=float(prof.step_ms[s]))
    return prof, x


def profile_schedule(sched, c, *, reps: int = 2, warmup: int = 1,
                     clock=time.perf_counter, mesh=None,
                     axis: str = "model") -> ScheduleProfile:
    """Profile one schedule execution per step (module doc).

    sched: a LevelSchedule or DeviceSchedule; c: the preamble-applied
    right-hand side, (n,) or (n, k).  Passing `mesh` profiles the sharded
    path and splits collective vs. compute per step.
    """
    from ..solver.levelset import DeviceSchedule
    host = sched.host if isinstance(sched, DeviceSchedule) else sched
    prof, _ = _profile_and_solve(host, c, reps=reps, warmup=warmup,
                                 clock=clock, mesh=mesh, axis=axis)
    return prof


def profile_operator(op, b=None, *, reps: int = 2, warmup: int = 1,
                     clock=time.perf_counter) -> ScheduleProfile:
    """Profile a built TriangularOperator's main schedule, with the
    operator's own orientation + preamble applied to `b` (default: ones),
    so the profiled c is exactly what a served solve would feed the
    schedule.  A sharded default engine routes its mesh/axis through."""
    from ..solver.engines import ShardedEngine
    v = np.ones(op.n, dtype=np.float64) if b is None else np.asarray(b)
    if op._reversed:
        v = v[::-1]
    c = op.preamble.host(v)
    mesh, axis = None, "model"
    if isinstance(op._engine, ShardedEngine):
        mesh, axis = op._engine.resolve_mesh(), op._engine.axis
    return profile_schedule(op._sched, c, reps=reps, warmup=warmup,
                            clock=clock, mesh=mesh, axis=axis)


from ..solver.engines import Engine as _EngineBase  # noqa: E402  (needs
# the classes above at definition time; repro.obs.__init__ loads this
# module lazily, so the solver package never re-enters obs mid-import)


class ProfilingEngine(_EngineBase):
    """Engine-protocol wrapper running the per-step profiling loop.

    Opt-in measurement tool: per-step dispatch is deliberately paid so
    each step can be timed; do not register it as a serving default.
    `compile(sched)` returns a solve fn whose results are exact (the full
    per-step execution IS the solve); after each call `last_profile`
    holds the fresh ScheduleProfile.  Wrapping a ShardedEngine routes
    mesh/axis (and the collective split) through.
    """

    lowers_from_host = True

    def __init__(self, base=None, *, reps: int = 1, warmup: int = 1,
                 clock=time.perf_counter, name: str | None = None):
        self.base = base
        self.reps = int(reps)
        self.warmup = int(warmup)
        self.clock = clock
        self.name = name or f"profiled[{base.name if base else 'stepwise'}]"
        self.last_profile = None
        if base is not None:
            self.supports_batched_rhs = base.supports_batched_rhs
            self.dtypes = base.dtypes

    def available(self) -> bool:
        return self.base.available() if self.base is not None else True

    def cache_token(self) -> str:
        if self.base is not None:
            return f"{self.name}:{self.base.cache_token()}"
        return self.name

    def compile(self, sched):
        from ..solver.engines import ShardedEngine
        from ..solver.levelset import DeviceSchedule
        host = sched.host if isinstance(sched, DeviceSchedule) else sched
        self._require_dtype(host)
        mesh, axis = None, "model"
        if isinstance(self.base, ShardedEngine):
            mesh, axis = self.base.resolve_mesh(), self.base.axis

        def fn(cv):
            prof, x = _profile_and_solve(
                host, cv, reps=self.reps, warmup=self.warmup,
                clock=self.clock, mesh=mesh, axis=axis)
            self.last_profile = prof
            return x

        return fn
