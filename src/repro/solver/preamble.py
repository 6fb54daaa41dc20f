"""The transform's preamble c = B'v of one sweep, on the host and on the
device (`SweepPreamble`).

B' = (I+T)^{-1} (core/transform.py, whose `TransformedSystem.preamble`
is the reference).  One choice decides both forms: while B''s
non-identity rows, and the auxiliary rows held to build them, stay within
`max_entries` (callers pass nnz of the factor), the preamble is one
product with those rows, "spmv"; past the cap (long rewrite distances on
a 2-D or 3-D mesh fill B' in) it is the T factor, "tfactor".  The cap is
a memory bound, not a crossover: the product's plan then holds no more
than a few arrays the size of the factor.

On the host, "spmv" is c = v, then c[rows] = B_sub @ v (a few
nanoseconds per entry against the T-factor loop's few microseconds per
row); "tfactor" is `TransformedSystem.preamble`.

On the device, "spmv" groups B_sub's rows by length into power-of-two
widths; each group is one gather v[idx] of shape (rows, width), a
multiply by the zero-padded coefficients and a sum over the row, and the
groups' results enter c in one scatter-set.  No scatter-add and no
schedule: every row of the product is independent.  "tfactor" is a
unit-triangular solve with the T factor over a level schedule of its own
(`schedule_for_preamble`), compiled through the same engine as the main
sweep.  Each device build counts its form in `obs.default_registry()`:
`sweep.device_preamble_spmv`, `sweep.device_preamble_schedule` (one per
sweep whose device preamble is built) and `sweep.device_preamble_entries`
(B_sub's entries).
"""
from __future__ import annotations

import functools

import numpy as np

from ..obs import trace as _obs
from ..obs.metrics import default_registry
from . import schedule as _schedule

__all__ = ["SweepPreamble"]


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


class SweepPreamble:
    """c = B'v for the transform `ts` of one sweep (module doc).

    Everything is worked out on first use and kept: the B' pattern
    (`pattern`, None for "tfactor" and for the identity preamble), B_sub's
    values (`B`), the T-factor schedule (`schedule`) and one device form
    per engine.  `chunk`, `max_deps` and `dtype` are the T-factor
    schedule's, as the main schedule's."""

    def __init__(self, ts, max_entries: int, *, chunk: int = 256,
                 max_deps: int = 16, dtype=np.float32):
        self.ts, self.max_entries = ts, max_entries
        self.chunk, self.max_deps, self.dtype = chunk, max_deps, dtype
        self._device: dict = {}     # engine name -> (engine, callable)

    @functools.cached_property
    def rows(self) -> int:
        """Rows of the T factor with entries: the rows the preamble
        eliminates (0 = identity preamble)."""
        return int(np.count_nonzero(self.ts.T.row_nnz()))

    @functools.cached_property
    def pattern(self):
        """The `BRowsPlan` of B''s non-identity rows, or None where they
        pass `max_entries` or the preamble is the identity."""
        if self.ts.identity_preamble:
            return None
        from ..core.rewrite import EquationStore
        ts = self.ts
        return EquationStore.b_rows_plan(ts.T, ts.src, ts.A.n_rows,
                                         self.max_entries)

    @property
    def realization(self) -> str:
        return "tfactor" if self.pattern is None else "spmv"

    @property
    def entries(self) -> int:
        """Entries per application: of B_sub, or of T on the T factor."""
        p = self.pattern
        return self.ts.T.nnz if p is None else p.entries

    @functools.cached_property
    def B(self):
        """B_sub, the "spmv" rows of B', from this transform's T.data."""
        return self.pattern.matrix(self.ts.T.data)

    @functools.cached_property
    def schedule(self) -> tuple:
        """(LevelSchedule|None, src, row_pos) of the T factor
        (`schedule_for_preamble`; None = identity preamble)."""
        return self._build_schedule()

    def _build_schedule(self) -> tuple:
        return _schedule.schedule_for_preamble(
            self.ts, chunk=self.chunk, max_deps=self.max_deps,
            dtype=self.dtype)

    @functools.cached_property
    def _staged(self):
        """`schedule`'s schedule staged to the device, for engines that
        compile DeviceSchedules."""
        import jax
        from .levelset import to_device
        psched = self.schedule[0]
        # staging may be triggered from inside a jit trace (an operator
        # first used as a traced preconditioner); the arrays are kept, so
        # they must be concrete, never tracers
        with jax.ensure_compile_time_eval():
            return to_device(psched) if psched is not None else None

    def revalued(self, ts) -> "SweepPreamble":
        """This preamble for `ts`, a transform with this one's T pattern
        and new values (a refactorization).  The B' pattern is value-free
        and is kept where it was worked out: no second pattern pass, and
        B_sub comes from the new T.data on first use.  A T-factor schedule
        already built is repacked from its value plan where that survived
        renumbering, else rebuilt; one never built stays lazy, so the
        update itself never enters build_schedule."""
        new = SweepPreamble(ts, self.max_entries, chunk=self.chunk,
                            max_deps=self.max_deps, dtype=self.dtype)
        seen = vars(self)
        if "pattern" in seen:
            new.pattern = seen["pattern"]
        if "schedule" in seen:
            psched, src, row_pos = seen["schedule"]
            if psched is None:
                new.schedule = seen["schedule"]
            elif psched.value_plan is not None:
                # a module attribute lookup, not a from-import: fault
                # injection (core.faults) patches the schedule module
                new.schedule = (_schedule.repack_schedule_values(
                    psched, ts.T.data, np.ones(ts.T.n_rows)), src, row_pos)
            else:
                new.schedule = new._build_schedule()
        return new

    def _apply(self, v: np.ndarray) -> np.ndarray:
        if self.pattern is None:
            return self.ts.preamble(v)
        c = v.astype(np.result_type(self.ts.T.data, v), copy=True)
        c[self.pattern.rows] = self.B @ c
        return c

    def host(self, v: np.ndarray) -> np.ndarray:
        """c = B'v on the host, in `TransformedSystem.preamble`'s dtype
        (float64 for a float32 v).  The `engine.preamble` span names the
        realization; no span for the identity preamble."""
        if not self.rows:
            return self.ts.preamble(v)
        with _obs.span("engine.preamble", rows=self.rows) as sp:
            sp.set(realization=self.realization, entries=self.entries)
            return self._apply(v)

    def device(self, engine, dtype):
        """c = B'c as a callable for `compose_sweep_fn`, built once per
        engine, or None for the identity preamble.

        "spmv": the product of the module doc over `B` cast to `dtype`, its
        arrays placed once: replicated over the engine's mesh where it has
        one (the sharded engine's x is replicated), so the product needs
        no collective.  "tfactor": the T-factor schedule compiled by
        `engine` (through `engines.compile_source`, as the main schedule),
        applied as c[src] -> schedule -> [row_pos]; only this form builds
        the schedule.  Either way the result is a `jax.tree_util.Partial`
        whose leaves are its arrays."""
        cached = self._device.get(engine.name)
        if cached is not None and cached[0] is engine:
            return cached[1]
        fn = self._build_device(engine, dtype)
        self._device[engine.name] = (engine, fn)
        return fn

    def _build_device(self, engine, dtype):
        import jax
        from jax.tree_util import Partial
        from .engines import compile_source
        if self.ts.identity_preamble:
            return None
        reg = default_registry()
        if self.pattern is None:
            psched, src, row_pos = self.schedule
            sched_fn = engine.compile(compile_source(
                engine, psched, lambda: self._staged))
            reg.counter("sweep.device_preamble_schedule",
                        "device sweeps whose preamble is the T-factor "
                        "schedule").inc()
            return Partial(_scheduled, sched_fn, src, row_pos)
        leaves = _product_buckets(self.B, self.pattern.rows, dtype)
        sharding = None
        resolve_mesh = getattr(engine, "resolve_mesh", None)
        if resolve_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            sharding = NamedSharding(resolve_mesh(), PartitionSpec())
        with jax.ensure_compile_time_eval():    # see _staged
            rows, buckets = jax.device_put(leaves, sharding)
        with reg.lock:
            reg.counter("sweep.device_preamble_spmv",
                        "device sweeps whose preamble is one B' product"
                        ).inc()
            reg.counter("sweep.device_preamble_entries",
                        "entries of B' rows in device preamble products"
                        ).inc(self.entries)
        return Partial(_product, rows, buckets)
