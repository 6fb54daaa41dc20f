"""Distributed SpTRSV via shard_map: lanes of each step sharded over a mesh
axis; x is replicated and re-synchronized with one all_gather family per
step.

The collective count is therefore proportional to the number of steps —
i.e. to the step count the schedule compiler minimizes (compaction) on top
of the level count the paper's transformation minimizes.  On a TPU mesh the
transformation's "95% fewer synchronization barriers" is literally "95%
fewer all_gathers" here.  `count_all_gathers` verifies the invariant by
tracing an unrolled copy of the sharded body with a counting collective:
exactly one all_gather family (synchronization point) per schedule step,
carry gathers riding in the same family.

Width groups are sharded independently over their lane dimension and their
per-step updates are concatenated before the gather, so the number of
collectives per step stays constant no matter how many width classes the
schedule uses.  Every group's lane capacity is padded up to a multiple of
the axis size on the host before sharding.  Right-hand sides may be single
`(n,)` or batched `(n, k)` — lanes are sharded, RHS columns replicated,
and the gather concatenates along the lane axis only.

This module is the lowering backend of the registered `ShardedEngine`
(repro.solver.engines): engine compiles are memoized per (schedule
identity, mesh, axis), so serving paths never re-pad or re-stage groups
for a schedule they already lowered.  See docs/distributed.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .levelset import host_leaves
from .schedule import LevelSchedule, WidthGroup

__all__ = ["solve_sharded", "lower_sharded", "count_all_gathers",
           "default_mesh", "require_axis", "group_shardings"]


@functools.lru_cache(maxsize=8)
def _default_mesh_cached(axis: str) -> Mesh:
    return Mesh(np.array(jax.devices()), (axis,))


def default_mesh(axis: str = "model", devices=None) -> Mesh:
    """One-axis mesh over `devices` (default: every local device).

    The no-argument form is cached per axis name, so repeat calls return
    the identical Mesh object and memoized lowerings keyed on it hit.
    """
    if devices is None:
        return _default_mesh_cached(axis)
    return Mesh(np.asarray(devices), (axis,))


def require_axis(mesh: Mesh, axis: str) -> None:
    """Validate that `axis` names an axis of `mesh` — a mismatch must be
    an eager ValueError naming the mesh's axes, not a KeyError from deep
    inside lowering."""
    if axis not in mesh.shape:
        raise ValueError(
            f"mesh has no axis {axis!r}; its axes are "
            f"{tuple(mesh.axis_names)} — pass mesh_axis=/axis= naming one "
            f"of them")


def _pad_group(g: WidthGroup, mult: int, n: int, n_carry: int) -> WidthGroup:
    """Pad the lane dimension to a multiple of `mult` with inert lanes."""
    S, C = g.row_ids.shape
    C_new = -(-C // mult) * mult
    if C_new == C:
        return g
    pad = C_new - C

    def pad2(a, fill):
        out = np.full((S, C_new), fill, dtype=a.dtype)
        out[:, :C] = a
        return out

    dep_idx = np.zeros((S, C_new, g.dep_idx.shape[2]), dtype=g.dep_idx.dtype)
    dep_idx[:, :C] = g.dep_idx
    dep_coef = np.zeros((S, C_new, g.dep_coef.shape[2]),
                        dtype=g.dep_coef.dtype)
    dep_coef[:, :C] = g.dep_coef
    return WidthGroup(
        width=g.width, n=n,
        row_ids=pad2(g.row_ids, n),
        dep_idx=dep_idx,
        dep_coef=dep_coef,
        dinv=pad2(g.dinv, 0),
        carry_in=None if g.carry_in is None else pad2(g.carry_in, n_carry),
        carry_out=None if g.carry_out is None else
        pad2(g.carry_out, n_carry + 1))


def _padded_schedule(sched: LevelSchedule, nshards: int) -> LevelSchedule:
    """The schedule with every group's lane capacity padded to a multiple
    of `nshards` (host-side numpy, no staging)."""
    return LevelSchedule(
        groups=tuple(_pad_group(g, nshards, sched.n, sched.n_carry)
                     for g in sched.groups),
        n=sched.n, n_carry=sched.n_carry, num_levels=sched.num_levels,
        chunk=sched.chunk, max_deps=sched.max_deps,
        compacted=sched.compacted, build_ms=sched.build_ms)


def _lane_spec(leaf, axis: str) -> P:
    """Lanes sharded over `axis`: (S, C) leaves as P(None, axis), (S, C, D)
    tiles as P(None, axis, None); steps and deps stay whole."""
    return P(None, axis) if leaf.ndim == 2 else P(None, axis, None)


def group_shardings(groups, mesh: Mesh, axis: str = "model") -> tuple:
    """A NamedSharding per leaf of `groups` (per-group leaf tuples of a
    padded schedule), laid out as `_lane_spec` says: what `lower_sharded`
    places the tiles with, and what a compile for a described mesh gives
    its argument shapes."""
    return tuple(tuple(NamedSharding(mesh, _lane_spec(l, axis)) for l in g)
                 for g in groups)


def _gather(v, axis):
    return jax.lax.all_gather(v, axis, tiled=True)


def _step_update(x, carry, c_pad, step_groups, *, n_carry, axis,
                 gather=_gather):
    """One schedule step on one device's lane shard, published to every
    device by one all_gather family (the per-step synchronization point).
    `gather` is injectable so `count_all_gathers` can audit the family
    count; carry machinery is dropped from the collective entirely when no
    group ships carry maps (the common, no-split-row case)."""
    any_carries = any(len(g) == 6 for g in step_groups)
    xis, tots, rids_l, couts_l = [], [], [], []
    for g in step_groups:
        rids, didx, dcoef, dnv = g[:4]
        gathered = x[didx]                     # (C, D) or (C, D, R)
        if gathered.ndim == 3:
            partial = jnp.einsum("cd,cdr->cr", dcoef, gathered)
        else:
            partial = jnp.sum(dcoef * gathered, axis=-1)    # (C,)
        tot = partial + carry[g[4]] if len(g) == 6 else partial
        xi = (c_pad[rids] - tot) * (dnv if tot.ndim == 1 else dnv[:, None])
        xis.append(xi)
        rids_l.append(rids)
        if any_carries:
            tots.append(tot)
            couts_l.append(g[5] if len(g) == 6 else
                           jnp.full(rids.shape, n_carry + 1, jnp.int32))
    # publish this step's results to every device: one concatenated
    # all_gather family per step — the quantity compaction minimizes
    xi_all = gather(jnp.concatenate(xis), axis)
    rid_all = gather(jnp.concatenate(rids_l), axis)
    x = x.at[rid_all].set(xi_all)
    if any_carries:
        tot_all = gather(jnp.concatenate(tots), axis)
        cout_all = gather(jnp.concatenate(couts_l), axis)
        carry = carry.at[cout_all].set(tot_all)
    return x, carry


def _sharded_body(c_pad, groups, *, n, n_carry, axis):
    tail = c_pad.shape[1:]                  # () single RHS, (R,) batched
    x0 = jnp.zeros((n + 1,) + tail, dtype=c_pad.dtype)
    carry0 = jnp.zeros((n_carry + 2,) + tail, dtype=c_pad.dtype)
    # loop carries become device-varying after the per-step all_gather;
    # mark the (identical) initial values as varying to match
    x0 = jax.lax.pcast(x0, (axis,), to="varying")
    carry0 = jax.lax.pcast(carry0, (axis,), to="varying")

    def body(state, step_groups):
        x, carry = _step_update(*state, c_pad, step_groups,
                                n_carry=n_carry, axis=axis)
        return (x, carry), None

    (x, _), _ = jax.lax.scan(body, (x0, carry0), groups)
    return x[:n]


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "n", "n_carry"))
def _sharded_solve(groups, c, *, mesh, axis, n, n_carry):
    """The sharded sweep with the padded tiles as jit ARGUMENTS (placed
    lane-sharded by `group_shardings`), so no device holds the whole
    schedule and value-repacked schedules of one layout share the
    executable, as with the scan engine's `_scan_jit`."""
    specs = tuple(tuple(_lane_spec(l, axis) for l in g) for g in groups)
    body = functools.partial(_sharded_body, n=n, n_carry=n_carry, axis=axis)
    # x ends replicated (every device applies the same gathered updates),
    # but the replication tracker can't prove it: check_vma=False
    shmapped = jax.shard_map(body, mesh=mesh, in_specs=(P(), specs),
                             out_specs=P(), check_vma=False)
    zero = jnp.zeros((1,) + c.shape[1:], c.dtype)
    return shmapped(jnp.concatenate([c, zero], axis=0), groups)


def solve_sharded(sched: LevelSchedule, c: np.ndarray, mesh: Mesh,
                  axis: str = "model") -> np.ndarray:
    """Solve with step lanes sharded over `axis` of `mesh`.

    Routed through the `ShardedEngine` machinery, so repeat calls on the
    same schedule object reuse the memoized lowering instead of re-padding
    and re-staging the groups per call.  `c` may be `(n,)` or batched
    `(n, k)`; a leading dimension that does not match the schedule raises
    ValueError (never an opaque concatenate error).
    """
    from .engines import sharded_engine
    fn = sharded_engine(mesh, axis).compile(sched)
    return np.asarray(fn(jnp.asarray(c, dtype=sched.dtype)))


def lower_sharded(sched: LevelSchedule, mesh: Mesh, axis: str = "model"):
    """Build the jitted sharded solver fn(c) -> x for a fixed schedule.

    The returned fn accepts `(n,)` or batched `(n, k)` right-hand sides
    (lanes sharded over `axis`, RHS columns replicated) and validates the
    leading dimension eagerly.  Prefer `ShardedEngine.compile` (or
    `solve_sharded`), which memoizes this lowering per schedule identity.
    """
    require_axis(mesh, axis)
    padded = _padded_schedule(sched, mesh.shape[axis])
    leaves = host_leaves(padded)
    # lowering may be triggered lazily from INSIDE a jit trace (an
    # operator first used as a traced preconditioner); the placed arrays
    # are memoized on the engine, so they must be concrete, never tracers
    with jax.ensure_compile_time_eval():
        groups = jax.device_put(leaves, group_shardings(leaves, mesh, axis))
    n, n_carry, dtype = padded.n, padded.n_carry, padded.dtype

    def run(c):
        c = jnp.asarray(c, dtype=dtype)
        if c.ndim not in (1, 2) or c.shape[0] != n:
            raise ValueError(
                f"right-hand side must be ({n},) or ({n}, k) to "
                f"match the schedule, got shape {c.shape}")
        return _sharded_solve(groups, c, mesh=mesh, axis=axis, n=n,
                              n_carry=n_carry)

    return run


def count_all_gathers(sched: LevelSchedule, mesh: Mesh | None = None,
                      axis: str = "model") -> dict:
    """Audit the collective count of one sharded solve by abstract
    tracing (no execution, no device staging, any mesh size — default a
    1-device mesh).

    Traces an unrolled copy of the sharded body over the padded HOST
    schedule with a counting collective and returns ``{"steps",
    "families", "calls"}`` where `families` is the number of steps that
    issued at least one all_gather — the number of per-step
    synchronization barriers — and `calls` the raw all_gather
    invocations: 2 per step (values + row ids), uniformly 4 per step on
    schedules with any split-row group (the carry machinery keys off the
    static leaf structure, which is shared by every step, not off
    per-step carry placement).  The module invariant, which
    benchmarks/tests assert, is ``families == steps``.
    """
    if mesh is None:
        mesh = default_mesh(axis=axis, devices=jax.devices()[:1])
    require_axis(mesh, axis)
    padded = _padded_schedule(sched, mesh.shape[axis])
    # numpy leaves: the audit only traces, so nothing lives on the device
    groups = host_leaves(padded)
    per_step: list[int] = []

    def gather(v, ax):
        per_step[-1] += 1
        return jax.lax.all_gather(v, ax, tiled=True)

    def body(c_pad):
        x = jnp.zeros((padded.n + 1,), dtype=c_pad.dtype)
        carry = jnp.zeros((padded.n_carry + 2,), dtype=c_pad.dtype)
        for s in range(padded.num_steps):
            per_step.append(0)
            step_groups = tuple(tuple(l[s] for l in g) for g in groups)
            x, carry = _step_update(x, carry, c_pad, step_groups,
                                    n_carry=padded.n_carry, axis=axis,
                                    gather=gather)
        return x[:padded.n]

    # groups ride in as replicated closure constants: only the collective
    # structure matters here, and it is independent of the lane sharding
    shmapped = jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                             out_specs=P(), check_vma=False)
    jax.eval_shape(shmapped,
                   jax.ShapeDtypeStruct((padded.n + 1,), padded.dtype))
    return {"steps": padded.num_steps,
            "families": sum(1 for k in per_step if k > 0),
            "calls": sum(per_step)}
