"""The transform's preamble c = B'v inside a device sweep.

B' = (I+T)^{-1} (core/transform.py).  Where the host plan exists
(`host_preamble` chose "spmv": B''s non-identity rows hold no more
entries than the factor), the device applies those rows as one product:
c = v, then c[rows] = B_sub @ v.  B_sub's rows are grouped by length into
power-of-two widths; each group is one gather v[idx] of shape
(rows, width), a multiply by the zero-padded coefficients and a sum over
the row, and the groups' results enter c in one scatter-set.  No
scatter-add and no schedule: every row of the product is independent.

Where the plan is None (B' fills in past the cap, "tfactor"), the
preamble stays a unit-triangular solve with the T factor over a level
schedule of its own (`schedule_for_preamble`), compiled through the same
engine as the main sweep.

`device_preamble` picks between the two from the plan alone and counts
the pick in `obs.default_registry()`: `sweep.device_preamble_spmv`,
`sweep.device_preamble_schedule` (one per sweep whose device preamble
is built) and `sweep.device_preamble_entries` (B_sub's entries).
"""
from __future__ import annotations

import numpy as np

from ..obs.metrics import default_registry

__all__ = ["device_preamble"]


def _product(rows, buckets, c):
    import jax.numpy as jnp
    parts = []
    for idx, coef in buckets:
        g = c[idx]                              # (r, w) or (r, w, k)
        if c.ndim == 2:
            coef = coef[:, :, None]
        parts.append(jnp.sum(coef * g, axis=1))
    return c.at[rows].set(jnp.concatenate(parts), unique_indices=True)


def _scheduled(sched_fn, src, row_pos, c):
    return sched_fn(c[src])[row_pos]


def _product_buckets(B, rows: np.ndarray, dtype) -> tuple:
    """Host form of the product: (rows in bucket order, ((idx, coef), ...)
    per power-of-two width).  `B` is B_sub as a CSR (one row per entry of
    `rows`); a row shorter than its width is padded with its first column
    and a zero coefficient.  Indices are int32, coefficients `dtype`."""
    lens = np.diff(B.indptr)
    widths = np.left_shift(1, np.ceil(np.log2(np.maximum(lens, 1)))
                           .astype(np.int64))
    out_rows, buckets = [], []
    for w in np.unique(widths).tolist():
        sel = np.flatnonzero(widths == w)
        slot = np.arange(w)
        live = slot[None, :] < lens[sel, None]
        pos = np.where(live, B.indptr[sel, None] + slot[None, :],
                       B.indptr[sel, None])
        idx = B.indices[pos].astype(np.int32)
        coef = np.where(live, B.data[pos], 0.0).astype(dtype)
        out_rows.append(rows[sel])
        buckets.append((idx, coef))
    return np.concatenate(out_rows).astype(np.int32), tuple(buckets)


def device_preamble(plan, dtype, engine, scheduled):
    """c = B'c as a callable for `compose_sweep_fn`, or None for an
    identity preamble.

    `plan` is the sweep's `HostPreamble`.  With a plan ("spmv") the
    result is the product of the module doc over `plan.B` cast to
    `dtype`, its arrays placed once: replicated over the engine's mesh
    where it has one (the sharded engine's x is replicated), so the
    product needs no collective; `scheduled()` is not called.
    Without one it is `scheduled()`'s (compiled T-factor schedule, src,
    row_pos), applied as c[src] -> schedule -> [row_pos].  Either way the
    result is a `jax.tree_util.Partial` whose leaves are its arrays."""
    import jax
    from jax.tree_util import Partial
    if plan.ts.identity_preamble:
        return None
    reg = default_registry()
    if plan.realization != "spmv":
        sched_fn, src, row_pos = scheduled()
        reg.counter("sweep.device_preamble_schedule",
                    "device sweeps whose preamble is the T-factor "
                    "schedule").inc()
        return Partial(_scheduled, sched_fn, src, row_pos)
    leaves = _product_buckets(plan.B, plan.pattern.rows, dtype)
    sharding = None
    resolve_mesh = getattr(engine, "resolve_mesh", None)
    if resolve_mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        sharding = NamedSharding(resolve_mesh(), PartitionSpec())
    # placing may be triggered from inside a jit trace (an operator first
    # used as a traced preconditioner); the arrays are kept, so they must
    # be concrete, never tracers
    with jax.ensure_compile_time_eval():
        rows, buckets = jax.device_put(leaves, sharding)
    with reg.lock:
        reg.counter("sweep.device_preamble_spmv",
                    "device sweeps whose preamble is one B' product").inc()
        reg.counter("sweep.device_preamble_entries",
                    "entries of B' rows in device preamble products"
                    ).inc(plan.entries)
    return Partial(_product, rows, buckets)
