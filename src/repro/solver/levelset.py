"""Level-scheduled SpTRSV execution engines in JAX.

Engines (all consume a width-bucketed LevelSchedule, see schedule.py DESIGN):
  * solve_scan      — lax.scan over steps; HLO size O(num width groups),
                      independent of step count.
  * solve_unrolled  — python loop over steps at trace time; exposes each
                      step to XLA (bigger HLO, more fusion freedom).  Only
                      sensible AFTER the transformation shrank the step
                      count — which is precisely the paper's point.
  * multi-RHS via vmap-style batched gathers (b may be (n,) or (n, R)).

Each step applies its width groups sequentially.  That is safe because the
schedule compiler guarantees no lane reads a row (or carry) finalized in the
same step, so intra-step ordering is free.

The preamble c = B'b (transformed systems) is applied outside: either a
materialized-B' SpMV or a second schedule built on the T factor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .schedule import LevelSchedule

__all__ = ["DeviceSchedule", "host_leaves", "to_device", "solve_scan",
           "solve_unrolled", "staged_scan_fn", "staged_unrolled_fn", "solve"]

# leaf order within a group (row_ids doubles as the c gather index —
# padding lanes hit the zero slot).  Carry leaves are present only for
# groups holding partial-row lanes.
GROUP_LEAVES = ("row_ids", "dep_idx", "dep_coef", "dinv")
CARRY_LEAVES = ("carry_in", "carry_out")


def host_leaves(sched: LevelSchedule) -> tuple:
    """The schedule's numpy leaves: a tuple of per-group leaf tuples (4
    leaves for carry-free groups, 6 with the carry slot maps), each with
    leading dim num_steps — the layout every engine consumes."""
    return tuple(
        tuple(getattr(g, name) for name in GROUP_LEAVES) +
        (tuple(getattr(g, name) for name in CARRY_LEAVES)
         if g.carry_in is not None else ())
        for g in sched.groups)


class DeviceSchedule:
    """LevelSchedule staged as jnp arrays (`host_leaves` layout)."""

    def __init__(self, sched: LevelSchedule):
        # the host LevelSchedule rides along: engines whose lowering is a
        # host-side pass (ShardedEngine pads lane capacities in numpy and
        # memoizes per schedule identity) start from it rather than from
        # the staged arrays
        self.host = sched
        self.groups = jax.tree.map(jnp.asarray, host_leaves(sched))
        self.group_widths = sched.group_widths
        self.n = sched.n
        self.n_carry = sched.n_carry
        self.num_steps = sched.num_steps
        self.dtype = sched.dtype

    def leaves(self):
        """Pytree of stacked leaves; every array has leading dim num_steps."""
        return self.groups


def to_device(sched: LevelSchedule) -> DeviceSchedule:
    return DeviceSchedule(sched)


def _group_body(x, carry, c_pad, leaves_g):
    """Apply one width-group tile of one step."""
    row_ids, dep_idx, dep_coef, dinv = leaves_g[:4]
    has_carry = len(leaves_g) == 6
    gathered = x[dep_idx]                      # (C, D) or (C, D, R)
    if gathered.ndim == 3:
        partial = jnp.einsum("cd,cdr->cr", dep_coef, gathered)
        tot = partial + carry[leaves_g[4]] if has_carry else partial
        xi = (c_pad[row_ids] - tot) * dinv[:, None]
    else:
        partial = jnp.sum(dep_coef * gathered, axis=-1)   # (C,)
        tot = partial + carry[leaves_g[4]] if has_carry else partial
        xi = (c_pad[row_ids] - tot) * dinv
    # padding lanes all write the garbage slot (index n / n_carry+1):
    # in-bounds, duplicate-safe with plain scatter-set
    x = x.at[row_ids].set(xi)
    if has_carry:
        carry = carry.at[leaves_g[5]].set(tot)
    return x, carry


def _step_body(x, carry, c_pad, step_groups):
    for leaves_g in step_groups:
        x, carry = _group_body(x, carry, c_pad, leaves_g)
    return x, carry


def _init_state(n: int, n_carry: int, c: jax.Array):
    tail = (c.shape[1],) if c.ndim == 2 else ()
    x0 = jnp.zeros((n + 1,) + tail, dtype=c.dtype)
    carry0 = jnp.zeros((n_carry + 2,) + tail, dtype=c.dtype)
    c_pad = jnp.concatenate([c, jnp.zeros((1,) + tail, c.dtype)], axis=0)
    return x0, carry0, c_pad


# The staged implementations take the schedule leaves as a PYTREE ARGUMENT
# (not a trace-time closure): the module-level jit wrappers below then key
# their executable cache on leaf structure/shapes only, so a value-only
# schedule repack (`schedule.repack_schedule_values` via
# `TriangularOperator.update_values`) reuses the already-compiled XLA
# executable — new coefficients ride in as arguments, nothing retraces.

def _scan_impl(leaves, n: int, n_carry: int, c: jax.Array) -> jax.Array:
    x0, carry0, c_pad = _init_state(n, n_carry, c)

    def body(state, step_groups):
        x, carry = _step_body(*state, c_pad, step_groups)
        return (x, carry), None

    (x, _), _ = jax.lax.scan(body, (x0, carry0), leaves)
    return x[:n]


def _unrolled_impl(leaves, n: int, n_carry: int, c: jax.Array) -> jax.Array:
    x, carry, c_pad = _init_state(n, n_carry, c)
    num_steps = int(leaves[0][0].shape[0]) if leaves else 0
    for s in range(num_steps):
        step_groups = tuple(tuple(l[s] for l in g) for g in leaves)
        x, carry = _step_body(x, carry, c_pad, step_groups)
    return x[:n]


_scan_jit = jax.jit(_scan_impl, static_argnums=(1, 2))
_unrolled_jit = jax.jit(_unrolled_impl, static_argnums=(1, 2))


def solve_scan(dsched: DeviceSchedule, c: jax.Array) -> jax.Array:
    """Solve given preamble vector c (= b for untransformed systems)."""
    return _scan_impl(dsched.leaves(), dsched.n, dsched.n_carry, c)


def solve_unrolled(dsched: DeviceSchedule, c: jax.Array) -> jax.Array:
    """Trace-time unrolled engine (use when step count is small — i.e. after
    the transformation)."""
    return _unrolled_impl(dsched.leaves(), dsched.n, dsched.n_carry, c)


def staged_scan_fn(dsched: DeviceSchedule):
    """Serving callable for the scan engine: jit with the staged leaves as
    arguments, so schedules sharing a tile layout share one executable."""
    leaves, n, n_carry = dsched.leaves(), dsched.n, dsched.n_carry
    return lambda c: _scan_jit(leaves, n, n_carry, c)


def staged_unrolled_fn(dsched: DeviceSchedule):
    """Serving callable for the unrolled engine (see staged_scan_fn)."""
    leaves, n, n_carry = dsched.leaves(), dsched.n, dsched.n_carry
    return lambda c: _unrolled_jit(leaves, n, n_carry, c)


def solve(sched: LevelSchedule, c: np.ndarray, engine=None,
          dsched: DeviceSchedule | None = None) -> np.ndarray:
    """Convenience host-level entry point (compiles per schedule identity).

    engine: an Engine from repro.solver.engines, a registered name, or None
    for the default scan engine.  Unknown names raise ValueError listing the
    registered engines.  Bare strings are a deprecation shim — pass Engine
    objects (or use repro.solver.api.sptrsv) in new code.
    """
    from .engines import resolve_engine_shim
    eng = resolve_engine_shim(engine, where="levelset.solve(engine=...)")
    ds = dsched if dsched is not None else to_device(sched)
    out = eng.compile(ds)(jnp.asarray(c, dtype=ds.dtype))
    return np.asarray(out)
