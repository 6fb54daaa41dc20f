"""Plain references the benchmark compares the program against.

Nothing here imports the program or takes anything it made: every input
is the benchmark's own matrix (`chipbench.matrices`) and right-hand
sides.  The float64 references use scipy.  `dtype` selects the precision
that every stored value (matrix entries, vectors, scalars) is rounded to,
which is how the lower-precision controls are made.  Below float64 the
arithmetic runs in float32 on those rounded values, as mixed-precision
code does: products and sums in float32, each result rounded to `dtype`.
"""
from __future__ import annotations

import math

import numpy as np
import scipy.sparse as sp
from scipy.sparse.linalg import spsolve_triangular

__all__ = ["LowerSolve", "ic0", "pcg", "forward_error", "residual_max",
           "residual_2norm", "history_gap"]


def _levels(L: sp.csr_matrix) -> np.ndarray:
    """Level of each row of a lower-triangular matrix: one more than the
    deepest row it depends on."""
    n = L.shape[0]
    lvl = np.zeros(n, dtype=np.int64)
    indptr, indices = L.indptr, L.indices
    for i in range(n):
        deps = indices[indptr[i]:indptr[i + 1]]
        deps = deps[deps < i]
        if deps.size:
            lvl[i] = lvl[deps].max() + 1
    return lvl


class LowerSolve:
    """x with L x = b for a lower-triangular CSR matrix L.

    float64 is scipy's `spsolve_triangular`; any other dtype is a plain
    level-by-level sweep computed in that dtype, its gather indices built
    once here."""

    def __init__(self, L: sp.csr_matrix, dtype=np.float64):
        self.L = L.tocsr()
        self.dtype = np.dtype(dtype)
        if self.dtype == np.float64:
            return
        L = self.L
        n = L.shape[0]
        rows = np.repeat(np.arange(n), np.diff(L.indptr))
        off = L.indices != rows
        self.diag = np.zeros(n, dtype=self.dtype)
        self.diag[rows[~off]] = L.data[~off].astype(self.dtype)
        lvl = _levels(L)
        order = np.argsort(lvl, kind="stable")
        bounds = np.searchsorted(lvl[order], np.arange(lvl.max() + 2))
        entry_lvl = lvl[rows]
        e_order = np.argsort(entry_lvl[off], kind="stable")
        e_rows = rows[off][e_order]
        e_cols = L.indices[off][e_order]
        e_vals = L.data[off][e_order].astype(self.dtype)
        e_bounds = np.searchsorted(entry_lvl[off][e_order],
                                   np.arange(lvl.max() + 2))
        self.steps = []
        for k in range(len(bounds) - 1):
            r = order[bounds[k]:bounds[k + 1]]
            lo, hi = e_bounds[k], e_bounds[k + 1]
            self.steps.append((r, e_cols[lo:hi], e_vals[lo:hi],
                               np.searchsorted(r, e_rows[lo:hi])))

    def __call__(self, b: np.ndarray) -> np.ndarray:
        """x for b of shape (n,) or (n, columns)."""
        if self.dtype == np.float64:
            return spsolve_triangular(self.L, np.asarray(b, np.float64),
                                      lower=True)
        f32 = np.float32
        b = np.asarray(b).astype(self.dtype).astype(f32)
        col = (slice(None),) + (None,) * (b.ndim - 1)   # broadcast on rows
        x = np.zeros(b.shape, dtype=self.dtype)
        for r, cols, vals, seg in self.steps:
            acc = np.zeros((r.size,) + b.shape[1:], dtype=f32)
            np.add.at(acc, seg, vals.astype(f32)[col] * x[cols].astype(f32))
            x[r] = ((b[r] - acc) / self.diag[r].astype(f32)[col]).astype(
                self.dtype)
        return x


def ic0(A: sp.csr_matrix) -> sp.csr_matrix:
    """Incomplete Cholesky with zero fill: L on the pattern of tril(A)
    with A ~ L L^T, row by row (Saad, Iterative Methods, 2nd ed., 10.3).
    No diagonal shift: a pivot that is not positive raises."""
    low = sp.tril(A, format="csr")
    low.sort_indices()
    n = low.shape[0]
    indptr, indices = low.indptr, low.indices
    data = low.data.astype(np.float64).copy()
    for i in range(n):
        lo, hi = indptr[i], indptr[i + 1]
        cols_i = indices[lo:hi]
        if cols_i[-1] != i:
            raise ValueError(f"row {i} has no diagonal")
        where = {int(c): lo + k for k, c in enumerate(cols_i)}
        for p in range(lo, hi - 1):
            j = int(indices[p])
            s = data[p]
            for q in range(indptr[j], indptr[j + 1] - 1):
                m = int(indices[q])
                if m in where and where[m] < p:
                    s -= data[where[m]] * data[q]
            data[p] = s / data[indptr[j + 1] - 1]
        d = data[hi - 1] - float(np.dot(data[lo:hi - 1], data[lo:hi - 1]))
        if d <= 0.0:
            raise ValueError(f"IC(0) breaks down at row {i}: pivot {d}")
        data[hi - 1] = math.sqrt(d)
    return sp.csr_matrix((data, indices.copy(), indptr.copy()),
                         shape=low.shape)


def _matvec(A: sp.csr_matrix, x: np.ndarray, dtype) -> np.ndarray:
    if np.dtype(dtype) == np.float64:
        return A @ x
    f32 = np.float32
    rows = np.repeat(np.arange(A.shape[0]), np.diff(A.indptr))
    out = np.zeros(A.shape[0], dtype=f32)
    np.add.at(out, rows, A.data.astype(dtype).astype(f32)
              * x[A.indices].astype(f32))
    return out.astype(dtype)


def pcg(A: sp.csr_matrix, b: np.ndarray, L: sp.csr_matrix, *, tol: float,
        maxiter: int, dtype=np.float64, iterations: int | None = None):
    """Preconditioned CG with M = L L^T, from x0 = 0, in `dtype`: stop when
    the recursive residual's 2-norm reaches tol * ||b||, after `maxiter`
    iterations, or after `iterations` if given.  Returns (x, residual
    2-norms per iteration, index 0 the initial one)."""
    fwd = LowerSolve(L, dtype)
    bwd = _UpperSolve(L, dtype)
    acc = np.float64 if np.dtype(dtype) == np.float64 else np.float32

    def dot(u, v):
        return float(np.dot(u.astype(acc), v.astype(acc)))

    b = np.asarray(b).astype(dtype)
    x = np.zeros_like(b)
    r = b.copy()
    z = bwd(fwd(r)).astype(dtype)
    p = z.copy()
    rz = dot(r, z)
    norms = [math.sqrt(dot(r, r))]
    target = tol * norms[0]
    cap = maxiter if iterations is None else min(maxiter, iterations)
    while len(norms) - 1 < cap and (iterations is not None
                                    or norms[-1] > target):
        Ap = _matvec(A, p, dtype)
        alpha = np.asarray(rz / dot(p, Ap)).astype(dtype)
        x = x + alpha * p
        r = r - alpha * Ap
        norms.append(math.sqrt(dot(r, r)))
        z = bwd(fwd(r)).astype(dtype)
        rz_new = dot(r, z)
        beta = np.asarray(rz_new / rz).astype(dtype)
        p = z + beta * p
        rz = rz_new
    return x, np.asarray(norms)


class _UpperSolve:
    """x with L^T x = b, as a lower solve on the reversed system."""

    def __init__(self, L: sp.csr_matrix, dtype):
        n = L.shape[0]
        rev = sp.csr_matrix((np.ones(n), np.arange(n)[::-1].copy(),
                             np.arange(n + 1)), shape=(n, n))
        self.lower = LowerSolve((rev @ L.T @ rev).tocsr(), dtype)

    def __call__(self, b):
        return self.lower(np.asarray(b)[::-1])[::-1]


def _finite(v: float) -> float:
    """A reading that is not a number compares as infinitely wrong."""
    return v if math.isfinite(v) else math.inf


def forward_error(x, ref) -> float:
    """max |x - ref| / max |ref|, in float64."""
    x = np.asarray(x, dtype=np.float64)
    return _finite(float(np.abs(x - ref).max() / np.abs(ref).max()))


def residual_max(L: sp.csr_matrix, x, b) -> float:
    """max |b - L x| / max(1, max |b|), in float64: the refinement target
    of `TriangularOperator.solve`."""
    b = np.asarray(b, dtype=np.float64)
    r = b - L @ np.asarray(x, dtype=np.float64)
    return _finite(float(np.abs(r).max() / max(1.0, float(np.abs(b).max()))))


def residual_2norm(A: sp.csr_matrix, x, b) -> float:
    """||b - A x||_2 / ||b||_2, in float64."""
    b = np.asarray(b, dtype=np.float64)
    r = b - A @ np.asarray(x, dtype=np.float64)
    return _finite(float(np.linalg.norm(r) / np.linalg.norm(b)))


def history_gap(hist, ref_hist, upto: int) -> float:
    """Widest relative gap between two residual-norm histories over their
    first `upto` + 1 entries (fewer where either stopped earlier)."""
    k = min(upto + 1, len(hist), len(ref_hist))
    h = np.asarray(hist[:k], dtype=np.float64)
    r = np.asarray(ref_hist[:k], dtype=np.float64)
    return _finite(float(np.max(np.abs(h - r) / r)))
