"""Adapters turning repo objects into the callables Krylov drivers consume.

The drivers in `repro.iterative.krylov` accept any `(matvec,
preconditioner)` pair of JAX-traceable callables.  This module produces
those callables from the repo's native objects:

    as_matvec(A)          CSR -> jit-native scatter-add SpMV closure;
                          callables pass through.
    as_preconditioner(M)  None -> identity; Preconditioner -> its fully
                          device-native application (device_apply);
                          TriangularOperator -> its device_solve_fn;
                          objects with only a host .solve -> a
                          pure_callback wrapper; callables pass through.

Everything returned is traceable under jit/vmap and handles single `(n,)`
and batched `(n, k)` operands, matching the engine-registry contract for
batched right-hand sides.
"""
from __future__ import annotations

import numpy as np

from ..sparse.csr import CSR

__all__ = ["device_matvec", "as_matvec", "as_preconditioner",
           "solve_callback"]


def device_matvec(A: CSR, mesh=None, axis: str = "model"):
    """y = A @ x as a jit-native JAX closure (scatter-add SpMV).

    The CSR arrays ride into the trace as constants cast to x's dtype, so
    the same closure serves float32 and float64 (x64-enabled) programs and
    batched (n, k) operands.

    With `mesh`, the nonzeros are sharded over `axis` and each device
    scatter-adds its partial products into a full-length accumulator that
    one psum reduces — ONE collective per matvec, so a Krylov iteration
    under a mesh synchronizes at the matvec and at the preconditioner's
    per-step all_gathers only, with no host round-trips in between
    (docs/distributed.md).  x is replicated, matching the sharded
    triangular sweeps' replicated carry contract.
    """
    import jax.numpy as jnp
    rows_np = np.repeat(np.arange(A.n_rows), A.row_nnz())
    cols_np = np.asarray(A.indices)
    data_np = np.asarray(A.data)
    n_rows = A.n_rows

    if mesh is None:
        def matvec(x):
            data = jnp.asarray(data_np, dtype=x.dtype)
            gathered = x[cols_np]
            prod = (data * gathered if x.ndim == 1
                    else data[:, None] * gathered)
            out = jnp.zeros((n_rows,) + x.shape[1:], dtype=x.dtype)
            return out.at[rows_np].add(prod)

        return matvec

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..solver.distributed import require_axis
    require_axis(mesh, axis)
    nshards = mesh.shape[axis]
    # pad the nnz triplet to a multiple of the axis size with inert
    # entries: row n_rows is a garbage accumulator slot dropped at the end
    nnz_pad = -(-max(rows_np.size, 1) // nshards) * nshards
    pad = nnz_pad - rows_np.size
    rows_sh = np.concatenate([rows_np, np.full(pad, n_rows, rows_np.dtype)])
    cols_sh = np.concatenate([cols_np, np.zeros(pad, cols_np.dtype)])
    data_sh = np.concatenate([data_np, np.zeros(pad, data_np.dtype)])

    def body(rows, cols, data, x):
        gathered = x[cols]
        prod = data * gathered if x.ndim == 1 else data[:, None] * gathered
        out = jnp.zeros((n_rows + 1,) + x.shape[1:], dtype=x.dtype)
        out = out.at[rows].add(prod)
        return jax.lax.psum(out, axis)

    # the triplet rides in as jit arguments, each device holding only its
    # nnz shard (placed once, never staged whole on one device)
    spmv = jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(axis), P(axis), P(axis), P()),
                                 out_specs=P(), check_vma=False))
    nnz_sharding = NamedSharding(mesh, P(axis))
    # the index triplet is dtype-independent; the coefficient array is
    # placed once per RHS dtype — repeat eager matvecs transfer nothing.
    # Placement may run inside a jit trace: never cache tracers
    with jax.ensure_compile_time_eval():
        rows_dev, cols_dev = jax.device_put((rows_sh, cols_sh), nnz_sharding)
    data_by_dtype: dict = {}

    def matvec(x):
        data = data_by_dtype.get(x.dtype)
        if data is None:
            with jax.ensure_compile_time_eval():
                data = jax.device_put(data_sh.astype(x.dtype), nnz_sharding)
            data_by_dtype[x.dtype] = data
        return spmv(rows_dev, cols_dev, data, x)[:n_rows]

    return matvec


def as_matvec(spec, mesh=None, axis: str = "model"):
    """CSR -> device_matvec(spec, mesh, axis); callables pass through."""
    if isinstance(spec, CSR):
        return device_matvec(spec, mesh=mesh, axis=axis)
    if callable(spec):
        return spec
    raise TypeError(f"matvec must be a CSR matrix or a callable, got "
                    f"{type(spec).__name__}")


def solve_callback(solve_fn):
    """Lift a host solve (e.g. TriangularOperator.solve) into a JAX-
    traceable callable via pure_callback: output shape/dtype == input's."""
    import jax

    def apply(r):
        out = jax.ShapeDtypeStruct(r.shape, r.dtype)

        def cb(rr):
            return np.asarray(solve_fn(np.asarray(rr, dtype=np.float64)),
                              dtype=out.dtype)

        return jax.pure_callback(cb, out, r, vmap_method="sequential")

    return apply


def as_preconditioner(spec):
    """Resolve a preconditioner spec to a traceable callable (module doc).

    Order matters: the device-native paths (`.device_apply` on a
    Preconditioner, `.device_solve_fn` on a TriangularOperator) beat
    plain callability, so those objects run as pure device computations
    with no host callback in the Krylov hot loop; a host-only `.solve`
    falls back to a pure_callback wrapper (note: under a scoped
    enable_x64() XLA may execute callbacks on worker threads that do not
    see the scope — prefer the device-native objects inside jit).
    """
    if spec is None:
        return lambda r: r
    if hasattr(spec, "device_apply"):
        return spec.device_apply()
    if hasattr(spec, "device_solve_fn"):
        return spec.device_solve_fn()
    if hasattr(spec, "jax_apply"):
        return spec.jax_apply
    if isinstance(spec, CSR):
        raise TypeError(
            "a raw CSR matrix is ambiguous as a preconditioner (M or "
            "M^-1?); pass repro.precond.Preconditioner.ic0/ilu0(A) or an "
            "explicit callable applying M^-1")
    if callable(spec):
        return spec
    if hasattr(spec, "solve"):
        return solve_callback(spec.solve)
    raise TypeError(f"cannot interpret {type(spec).__name__} as a "
                    f"preconditioner: expected None, a callable, a "
                    f"Preconditioner, or an object with .solve/.jax_apply")
