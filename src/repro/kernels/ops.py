"""Jit'd public wrappers around the Pallas kernels.

On the CPU backend the kernels execute in interpret mode (the kernel body
runs as jnp ops — bit-exact semantics, no TPU lowering); on a TPU they
compile with Mosaic.  The platform decides (`default_interpret`); pass
interpret= to pin the mode for one call.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..solver.schedule import LevelSchedule
from ..solver.levelset import to_device
from ..solver.engines import PallasEngine, default_interpret, get_engine
from .spmv_ell import spmv_ell_pallas
from . import ref

__all__ = ["default_interpret", "sptrsv_solve", "spmv_ell", "ell_pack_csr"]


def sptrsv_solve(sched: LevelSchedule, c: np.ndarray,
                 interpret: bool | None = None,
                 use_ref: bool = False, dsched=None) -> np.ndarray:
    """Solve a LevelSchedule with the Pallas kernel (or the jnp oracle).

    c may be (n,) or batched (n, R) — batched solves run the multi-RHS
    kernel, streaming the schedule once for all right-hand sides.  Pass a
    pre-staged DeviceSchedule as `dsched` to skip restaging on repeated
    solves.  Dispatches through the engine registry: interpret=None uses
    the registered "pallas" engine (interpret mode on CPU only), an
    explicit bool pins interpret mode for this call.
    """
    ds = dsched if dsched is not None else to_device(sched)
    if use_ref:
        dtype = sched.dtype
        cc = jnp.asarray(c, dtype=dtype)
        tail = (cc.shape[1],) if cc.ndim == 2 else ()
        c_pad = jnp.concatenate([cc, jnp.zeros((1,) + tail, dtype)], axis=0)
        out = ref.sptrsv_levels_grouped_ref(ds.groups, c_pad, n=sched.n,
                                            n_carry=sched.n_carry)
    else:
        eng = get_engine("pallas") if interpret is None \
            else PallasEngine(interpret=interpret)
        out = eng.compile(ds)(c)
    return np.asarray(out)


def ell_pack_csr(m, block_rows: int = 512, dtype=np.float32):
    """Pack a CSR matrix into ELL arrays for spmv_ell (vectorized scatter).

    Returns (ell_idx (n_pad, D), ell_coef (n_pad, D), n).  Padding indices
    point at x_pad's final zero slot.
    """
    n = m.n_rows
    deg = m.row_nnz()
    D = max(int(deg.max()), 1)
    n_pad = -(-n // block_rows) * block_rows
    ell_idx = np.full((n_pad, D), m.n_cols, dtype=np.int32)
    ell_coef = np.zeros((n_pad, D), dtype=dtype)
    indptr = np.asarray(m.indptr, dtype=np.int64)
    flat = np.repeat(np.arange(n, dtype=np.int64) * D, deg) + \
        (np.arange(indptr[-1]) - np.repeat(indptr[:-1], deg))
    ell_idx.reshape(-1)[flat] = m.indices
    ell_coef.reshape(-1)[flat] = m.data
    return ell_idx, ell_coef, n


def spmv_ell(m, x: np.ndarray, interpret: bool | None = None,
             use_ref: bool = False, block_rows: int = 512) -> np.ndarray:
    """y = m @ x via the ELL Pallas kernel."""
    interpret = default_interpret() if interpret is None else interpret
    ell_idx, ell_coef, n = ell_pack_csr(m, block_rows=block_rows)
    x_pad = jnp.concatenate([jnp.asarray(x, dtype=ell_coef.dtype),
                             jnp.zeros((1,), ell_coef.dtype)])
    if use_ref:
        y = ref.spmv_ell_ref(jnp.asarray(ell_idx), jnp.asarray(ell_coef), x_pad)
    else:
        y = spmv_ell_pallas(jnp.asarray(ell_idx), jnp.asarray(ell_coef),
                            x_pad, block_rows=block_rows, interpret=interpret)
    return np.asarray(y[:n])
