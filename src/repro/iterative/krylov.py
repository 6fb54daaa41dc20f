"""jit-native Krylov drivers: cg, bicgstab, restarted gmres.

The consumer side of the preconditioning subsystem — iterative solvers
whose inner kernel is the paper's transformed SpTRSV (via
`repro.precond.Preconditioner`), written entirely in JAX:

    A = generators.poisson2d_spd(64, 64)
    P = Preconditioner.ic0(A, tune="auto")
    res = cg(A, b, preconditioner=P, tol=1e-8)
    res.x, res.iterations, res.residual_norms

Driver contract
===============
* `matvec` is a CSR matrix (compiled to a jit-native scatter-add SpMV) or
  any traceable callable; `preconditioner` is None, a `Preconditioner`,
  a `TriangularOperator`, or a traceable callable applying M^-1 (see
  `repro.iterative.operators` for the adapter rules).
* Right-hand sides are single `(n,)` or batched `(n, k)`; batched columns
  converge independently (per-column masking), matching the engine
  registry's batched-RHS contract so one schedule streams all k columns.
* Every driver is a pure JAX program built on `lax.while_loop` — it
  composes with `jax.jit`, stops early when all columns converge, and
  returns a `SolveResult` pytree.  Run under `jax.enable_x64()` for
  float64 iterations (the repo default elsewhere: float32 device math +
  float64 host refinement).
* Convergence: ||r||_2 <= max(tol * ||b||_2, atol) per column, residuals
  in the driver's working dtype (= b's dtype).  `gmres` iterates on the
  left-preconditioned system, so its tolerance and recorded history are
  PRECONDITIONED residual norms (cg/bicgstab record true residuals).

`SolveResult.residual_norms` carries the per-iteration history in a
fixed-shape `(maxiter+1,) + batch` buffer (NaN beyond each column's last
iteration — `jnp.nanmin` and friends compose); `iterations` counts the
iterations each column actually ran.  `SolveResult.status` classifies each
column's outcome — STATUS_CONVERGED, STATUS_MAXITER, or STATUS_BREAKDOWN
(`status_labels` decodes) — and every driver detects a non-finite iterate
INSIDE its `lax.while_loop`: a poisoned column (NaN/Inf from an unstable
preconditioner, a singular operator, or a bad right-hand side) is frozen
at its last healthy iterate and reported as a breakdown instead of
silently returning a garbage x with converged=False (host-side health
guards cannot see inside jit, so the drivers carry their own detection).  When the preconditioner is a
`Preconditioner` object and the call runs outside jit, `stats` carries
its metadata (factorization kind/shift/strategy + host-path operator
counters; traced in-loop applications are not host-observable) — inside
jit it is None.

docs/iterative.md walks the full factor -> tune -> solve pipeline,
convergence knobs included.
"""
from __future__ import annotations

import math
import typing

import numpy as np

from .operators import as_matvec, as_preconditioner

__all__ = ["SolveResult", "cg", "bicgstab", "gmres",
           "STATUS_MAXITER", "STATUS_CONVERGED", "STATUS_BREAKDOWN",
           "STATUS_LABELS", "status_labels"]

# per-column outcome codes carried in SolveResult.status (int32, jit-safe)
STATUS_MAXITER = 0      # ran out of iterations without converging
STATUS_CONVERGED = 1    # hit the residual target
STATUS_BREAKDOWN = 2    # frozen at the last healthy iterate (non-finite
#                         step, or a bicgstab rho/omega collapse)
STATUS_LABELS = ("maxiter", "converged", "breakdown")


def status_labels(status):
    """Host-side decoder: a SolveResult.status array -> label strings."""
    return np.asarray(STATUS_LABELS, dtype=object)[np.asarray(status)]


class SolveResult(typing.NamedTuple):
    """Outcome of a Krylov solve (a JAX pytree; jit-transparent).

    x:              solution, same shape as b.
    converged:      bool per column (batch shape).
    iterations:     int32 per column — iterations actually run.
    residual_norms: (maxiter+1,) + batch, residual 2-norms per iteration
                    (index 0 = initial residual), NaN-padded past each
                    column's final iteration.
    status:         int32 per column — STATUS_CONVERGED, STATUS_MAXITER,
                    or STATUS_BREAKDOWN (`status_labels` decodes).
                    Breakdown columns are frozen at their last healthy
                    iterate: `x` is finite and usable, just not converged.
                    None when constructed without one (back-compat).
    stats:          preconditioner metadata dict (factorization kind,
                    shift, strategy, per-operator counters) when the call
                    ran outside jit with a Preconditioner object, else
                    None.  NOTE: in-loop M^-1 applications run through
                    the traced device pipeline, which host-side counters
                    cannot observe — the solve/walltime counters only
                    reflect explicit host `P.apply()` calls.
    """

    x: typing.Any
    converged: typing.Any
    iterations: typing.Any
    residual_norms: typing.Any
    status: typing.Any = None
    stats: typing.Any = None

    def final_residual(self):
        """Last recorded residual norm per column (NaN-aware)."""
        import jax.numpy as jnp
        hist = self.residual_norms
        idx = jnp.asarray(self.iterations, dtype=jnp.int32)
        return jnp.take_along_axis(hist, idx[None, ...], axis=0)[0]


def _vdot(u, v):
    import jax
    with jax.named_scope("krylov.reduce"):
        return (u * v).sum(axis=0)


def _norm(v):
    import jax
    import jax.numpy as jnp
    with jax.named_scope("krylov.reduce"):
        return jnp.sqrt((v * v).sum(axis=0))


def _scoped(name: str, fn):
    """`fn` with its ops under the name scope `name` (trace-time only)."""
    import jax

    def call(v):
        with jax.named_scope(name):
            return fn(v)
    return call


def _guard(d):
    """Replace ~zero denominators by 1 (the quotient is masked anyway)."""
    import jax.numpy as jnp
    return jnp.where(d == 0, jnp.ones_like(d), d)


def _prepare(matvec, preconditioner, b, x0, tol, atol):
    """Shared setup: resolve operators, initial x/r, convergence target."""
    import jax.numpy as jnp
    # device ops of the SpMV and of M^-1 carry these name scopes in a
    # profile, as the reductions carry `krylov.reduce`
    A = _scoped("krylov.matvec", as_matvec(matvec))
    M = _scoped("krylov.precond", as_preconditioner(preconditioner))
    b = jnp.asarray(b)
    if b.ndim not in (1, 2):
        raise ValueError(f"b must be (n,) or (n, k), got shape {b.shape}")
    if x0 is None:
        x = jnp.zeros_like(b)
        r = b
    else:
        x = jnp.asarray(x0, dtype=b.dtype)
        r = b - A(x)
    target = jnp.maximum(tol * _norm(b), atol).astype(b.dtype)
    return A, M, b, x, r, target


def _attach_stats(result: SolveResult, preconditioner) -> SolveResult:
    """Host-path convenience: merge Preconditioner operator stats into the
    result.  Inside jit `x` is a tracer and stats stay None (trace-time
    host counters would be stale constants)."""
    import jax
    if isinstance(result.x, jax.core.Tracer):
        return result
    stats_fn = getattr(preconditioner, "stats", None)
    if callable(stats_fn):
        return result._replace(stats=stats_fn())
    return result


# per-driver residual events are capped: a 10k-iteration solve must not
# flood the trace, so the history is thinned to evenly spaced samples
_TRACE_EVENT_CAP = 64


def _trace_iterations(result: SolveResult, driver: str) -> None:
    """Host-path per-iteration `krylov.residual` events from the recorded
    history (first column when batched).  Inside jit x is a tracer and the
    history is unreadable — nothing is emitted, same guard as
    `_attach_stats`."""
    from ..obs import trace as _obs
    if not _obs.enabled():
        return
    import jax
    if isinstance(result.x, jax.core.Tracer):
        return
    hist = np.asarray(result.residual_norms, dtype=float)
    col = hist if hist.ndim == 1 else hist[:, 0]
    last = int(np.max(np.asarray(result.iterations)))
    idx = np.arange(min(last + 1, col.shape[0]))
    if idx.size > _TRACE_EVENT_CAP:
        idx = np.unique(np.linspace(0, idx[-1],
                                    _TRACE_EVENT_CAP).astype(int))
    for i in idx:
        if np.isfinite(col[i]):
            _obs.event("krylov.residual", driver=driver, iteration=int(i),
                       residual=float(col[i]))


def _finish(result: SolveResult, preconditioner, driver: str) -> SolveResult:
    _trace_iterations(result, driver)
    return _attach_stats(result, preconditioner)


def cg(matvec, b, *, preconditioner=None, x0=None, tol: float = 1e-8,
       atol: float = 0.0, maxiter: int | None = None) -> SolveResult:
    """Preconditioned conjugate gradient for SPD systems.

    matvec/preconditioner: see module doc (M^-1 must be SPD — ic0 is).
    maxiter: history length and iteration cap; defaults to n.
    """
    import jax
    import jax.numpy as jnp
    A, M, b, x, r, target = _prepare(matvec, preconditioner, b, x0, tol,
                                     atol)
    n = b.shape[0]
    maxiter = n if maxiter is None else int(maxiter)
    batch = b.shape[1:]
    hist = jnp.full((maxiter + 1,) + batch, jnp.nan, dtype=b.dtype)
    rn0 = _norm(r)
    hist = hist.at[0].set(rn0)
    z = M(r)
    p = z
    rz = _vdot(r, z)
    done0 = rn0 <= target
    brk0 = jnp.zeros(batch, dtype=bool)
    iters0 = jnp.zeros(batch, dtype=jnp.int32)

    def cond(state):
        it, _, _, _, _, _, done, brk, _ = state
        return (it < maxiter) & ~(done | brk).all()

    def body(state):
        it, x, r, p, rz, hist, done, brk, iters = state
        stop = done | brk
        Ap = A(p)
        alpha = jnp.where(stop, 0.0, rz / _guard(_vdot(p, Ap))) \
            .astype(b.dtype)
        x_new = x + alpha * p
        r_new = r - alpha * Ap
        rn = _norm(r_new)
        z = M(r_new)
        rz_new = _vdot(r_new, z)
        # a non-finite residual or curvature means this step poisoned the
        # column (singular A, unstable M, overflow): freeze it at the last
        # healthy iterate and report breakdown, never return garbage
        bad = ~stop & ~(jnp.isfinite(rn) & jnp.isfinite(rz_new))
        ok = ~stop & ~bad
        x = jnp.where(ok, x_new, x)
        r = jnp.where(ok, r_new, r)
        hist = hist.at[it + 1].set(jnp.where(ok, rn, jnp.nan))
        iters = iters + jnp.where(ok, 1, 0).astype(jnp.int32)
        beta = (rz_new / _guard(rz)).astype(b.dtype)
        p = jnp.where(ok, z + beta * p, p)
        rz = jnp.where(ok, rz_new, rz)
        done = done | (ok & (rn <= target))
        brk = brk | bad
        return it + 1, x, r, p, rz, hist, done, brk, iters

    _, x, r, _, _, hist, done, brk, iters = jax.lax.while_loop(
        cond, body, (jnp.int32(0), x, r, p, rz, hist, done0, brk0, iters0))
    status = jnp.where(done, STATUS_CONVERGED,
                       jnp.where(brk, STATUS_BREAKDOWN,
                                 STATUS_MAXITER)).astype(jnp.int32)
    return _finish(
        SolveResult(x=x, converged=done, iterations=iters,
                    residual_norms=hist, status=status), preconditioner,
        "cg")


def bicgstab(matvec, b, *, preconditioner=None, x0=None, tol: float = 1e-8,
             atol: float = 0.0, maxiter: int | None = None) -> SolveResult:
    """Preconditioned BiCGStab for general (nonsymmetric) systems.

    Right-preconditioned van der Vorst form: two matvecs and two M^-1
    applications per iteration; the recorded history is the TRUE residual
    norm.  Breakdown (rho or omega collapsing) freezes the affected
    column with converged=False.
    """
    import jax
    import jax.numpy as jnp
    A, M, b, x, r, target = _prepare(matvec, preconditioner, b, x0, tol,
                                     atol)
    n = b.shape[0]
    maxiter = n if maxiter is None else int(maxiter)
    batch = b.shape[1:]
    hist = jnp.full((maxiter + 1,) + batch, jnp.nan, dtype=b.dtype)
    rn0 = _norm(r)
    hist = hist.at[0].set(rn0)
    rhat = r
    rho = jnp.ones(batch, dtype=b.dtype)
    alpha = jnp.ones(batch, dtype=b.dtype)
    omega = jnp.ones(batch, dtype=b.dtype)
    v = jnp.zeros_like(b)
    p = jnp.zeros_like(b)
    done0 = rn0 <= target
    brk0 = jnp.zeros(batch, dtype=bool)
    iters0 = jnp.zeros(batch, dtype=jnp.int32)
    eps = jnp.asarray(np.finfo(np.dtype(b.dtype)).tiny * 1e3, b.dtype)

    def cond(state):
        it = state[0]
        done, brk = state[-3], state[-2]
        return (it < maxiter) & ~(done | brk).all()

    def body(state):
        (it, x, r, rhat, rho, alpha, omega, v, p, hist, done, brk,
         iters) = state
        stop = done | brk
        rho_new = _vdot(rhat, r)
        broke = jnp.abs(rho_new) < eps
        beta = ((rho_new / _guard(rho)) * (alpha / _guard(omega))) \
            .astype(b.dtype)
        p = jnp.where(stop, p, r + beta * (p - omega * v))
        phat = M(p)
        v_new = A(phat)
        denom = _vdot(rhat, v_new)
        broke = broke | (jnp.abs(denom) < eps)
        alpha_new = jnp.where(stop | broke, 0.0,
                              rho_new / _guard(denom)).astype(b.dtype)
        s = r - alpha_new * v_new
        shat = M(s)
        t = A(shat)
        tt = _vdot(t, t)
        omega_new = jnp.where(stop | broke, 0.0,
                              _vdot(t, s) / _guard(tt)).astype(b.dtype)
        x_cand = x + alpha_new * phat + omega_new * shat
        r_cand = s - omega_new * t
        rn = _norm(r_cand)
        # a non-finite candidate (unstable M, singular A, overflow) is a
        # breakdown like rho/omega collapse: freeze the column at its last
        # healthy iterate, never commit a poisoned x
        broke = broke | ~jnp.isfinite(rn)
        upd = ~(stop | broke)
        x = jnp.where(upd, x_cand, x)
        r = jnp.where(upd, r_cand, r)
        # a breakdown step is NOT a productive iteration: x/r are frozen,
        # so record nothing and leave the count at the last real step
        hist = hist.at[it + 1].set(jnp.where(upd, rn, jnp.nan))
        iters = iters + jnp.where(upd, 1, 0).astype(jnp.int32)
        v = jnp.where(upd, v_new, v)
        rho = jnp.where(upd, rho_new, rho)
        alpha = jnp.where(upd, alpha_new, alpha)
        omega = jnp.where(upd, omega_new, omega)
        done = done | (upd & (rn <= target))
        brk = brk | (~stop & broke)
        return (it + 1, x, r, rhat, rho, alpha, omega, v, p, hist, done,
                brk, iters)

    state = (jnp.int32(0), x, r, rhat, rho, alpha, omega, v, p, hist,
             done0, brk0, iters0)
    state = jax.lax.while_loop(cond, body, state)
    _, x, r, *_rest = state
    hist, done, brk, iters = state[-4], state[-3], state[-2], state[-1]
    status = jnp.where(done, STATUS_CONVERGED,
                       jnp.where(brk, STATUS_BREAKDOWN,
                                 STATUS_MAXITER)).astype(jnp.int32)
    return _finish(
        SolveResult(x=x, converged=done, iterations=iters,
                    residual_norms=hist, status=status), preconditioner,
        "bicgstab")


def gmres(matvec, b, *, preconditioner=None, x0=None, tol: float = 1e-8,
          atol: float = 0.0, restart: int = 30,
          maxiter: int | None = None) -> SolveResult:
    """Restarted GMRES(m) for general systems, left-preconditioned.

    Arnoldi with twice-iterated classical Gram-Schmidt (CGS2 — fully
    vectorized over batched columns) and Givens-rotation least squares;
    `restart` is the Krylov dimension m, `maxiter` the number of restart
    cycles (default: enough cycles to cover n total iterations).

    Iterates on M^-1 A x = M^-1 b: tolerance and recorded history are
    PRECONDITIONED residual norms (|g_{j+1}| estimates inside a cycle, the
    recomputed true value of M^-1(b - Ax) at cycle boundaries).  History
    entries are written at per-column positions, so `iterations` counts
    each column's productive inner iterations and `hist[iterations]` is
    its last recorded estimate even when a column pauses mid-cycle.
    """
    import jax
    import jax.numpy as jnp
    # _prepare's target tracks the UNpreconditioned rhs; gmres replaces it
    # below with the preconditioned one (left-preconditioned iteration)
    A, M, b, x, _r0, _ = _prepare(matvec, preconditioner, b, x0, tol, atol)
    n = b.shape[0]
    m = max(1, min(int(restart), n))
    maxiter = max(1, math.ceil(n / m)) if maxiter is None else int(maxiter)
    batch = b.shape[1:]
    bmask = (slice(None),) + (None,) * len(batch)   # lift (m+1,) over batch
    mb = M(b)
    target = jnp.maximum(tol * _norm(mb), atol).astype(b.dtype)
    hist = jnp.full((maxiter * m + 1,) + batch, jnp.nan, dtype=b.dtype)
    r = M(b - A(x)) if x0 is not None else mb
    rn0 = _norm(r)
    hist = hist.at[0].set(rn0)
    done0 = rn0 <= target
    iters0 = jnp.zeros(batch, dtype=jnp.int32)
    basis_idx = jnp.arange(m + 1)

    # per-COLUMN history positions (iters + 1), not the absolute cycle
    # index: a column whose |g| estimate converges mid-cycle but whose
    # cycle-end recompute disagrees resumes writing right after its last
    # entry, so `iterations` stays the productive count and
    # hist[iterations] is always the last recorded estimate, gap-free
    if batch:
        col_idx = jnp.arange(batch[0])

        def hist_write(h, pos, val):
            return h.at[pos, col_idx].set(val)
    else:
        def hist_write(h, pos, val):
            return h.at[pos].set(val)

    def inner_body(j, carry):
        V, H, cs, sn, g, hist, inner_done, iters, cycle = carry
        w = M(A(V[j]))
        # CGS2: two passes of classical Gram-Schmidt against V[0..j],
        # vectorized over the basis axis with an i<=j mask
        mask = (basis_idx <= j)[bmask]
        h1 = jnp.where(mask, (V * w[None]).sum(axis=1), 0.0)
        w = w - (h1[:, None] * V).sum(axis=0)
        h2 = jnp.where(mask, (V * w[None]).sum(axis=1), 0.0)
        w = w - (h2[:, None] * V).sum(axis=0)
        hcol = (h1 + h2).astype(b.dtype)
        hnext = _norm(w)
        V = V.at[j + 1].set(jnp.where(inner_done, V[j + 1],
                                      w / _guard(hnext)))

        # apply the stored Givens rotations 0..j-1 to the new column
        def rot_body(i, hc):
            hi, hi1 = hc[i], hc[i + 1]
            new_hi = cs[i] * hi + sn[i] * hi1
            new_hi1 = -sn[i] * hi + cs[i] * hi1
            use = i < j
            hc = hc.at[i].set(jnp.where(use, new_hi, hi))
            return hc.at[i + 1].set(jnp.where(use, new_hi1, hi1))

        hcol = jax.lax.fori_loop(0, m, rot_body, hcol)
        # new rotation zeroing the subdiagonal h_{j+1,j}
        hj = hcol[j]
        d = jnp.sqrt(hj ** 2 + hnext ** 2)
        cs_j = jnp.where(d == 0, 1.0, hj / _guard(d)).astype(b.dtype)
        sn_j = jnp.where(d == 0, 0.0, hnext / _guard(d)).astype(b.dtype)
        hcol = hcol.at[j].set(d.astype(b.dtype)).at[j + 1].set(
            jnp.zeros_like(d, dtype=b.dtype))
        H = H.at[:, j].set(jnp.where(inner_done, H[:, j], hcol))
        cs = cs.at[j].set(jnp.where(inner_done, cs[j], cs_j))
        sn = sn.at[j].set(jnp.where(inner_done, sn[j], sn_j))
        g_j, g_next = g[j], -sn_j * g[j]
        g = g.at[j].set(jnp.where(inner_done, g_j, cs_j * g_j))
        g = g.at[j + 1].set(jnp.where(inner_done, g[j + 1], g_next))
        res_est = jnp.abs(g[j + 1])
        pos = jnp.minimum(iters + 1, maxiter * m)
        hist = hist_write(hist, pos, jnp.where(inner_done, jnp.nan,
                                               res_est))
        iters = iters + jnp.where(inner_done, 0, 1).astype(jnp.int32)
        inner_done = inner_done | (res_est <= target) | (hnext == 0)
        return V, H, cs, sn, g, hist, inner_done, iters, cycle

    def outer_cond(state):
        cycle = state[0]
        done, brk = state[-2], state[-1]
        return (cycle < maxiter) & ~(done | brk).all()

    def outer_body(state):
        cycle, x, r, rn, hist, iters, done, brk = state
        iters_in = iters        # rollback point for a poisoned cycle
        beta = rn
        V = jnp.zeros((m + 1, n) + batch, dtype=b.dtype)
        V = V.at[0].set(r / _guard(beta))
        H = jnp.zeros((m + 1, m) + batch, dtype=b.dtype)
        cs = jnp.zeros((m + 1,) + batch, dtype=b.dtype)
        sn = jnp.zeros((m + 1,) + batch, dtype=b.dtype)
        g = jnp.zeros((m + 1,) + batch, dtype=b.dtype).at[0].set(beta)
        carry = (V, H, cs, sn, g, hist, done | brk, iters, cycle)
        V, H, cs, sn, g, hist, _, iters, _ = jax.lax.fori_loop(
            0, m, inner_body, carry)
        # back-substitute H y = g on the m x m triangle; columns the cycle
        # never reached have H[i,i] == 0 and g[i] == 0 -> y_i = 0
        y = jnp.zeros((m,) + batch, dtype=b.dtype)

        def back_body(l, y):
            i = m - 1 - l
            s = (H[i] * y).sum(axis=0)      # y[l] == 0 for l <= i still
            yi = (g[i] - s) / _guard(H[i, i])
            return y.at[i].set(jnp.where(jnp.abs(H[i, i]) > 0, yi, 0.0))

        y = jax.lax.fori_loop(0, m, back_body, y)
        x_new = x + (y[:, None] * V[:m]).sum(axis=0)
        r_new = M(b - A(x_new))
        rn_new = _norm(r_new)
        # a non-finite recomputed residual means the cycle poisoned the
        # column (unstable M, singular A, NaN rhs): roll x and the
        # iteration count back to the cycle start and report breakdown
        active = ~(done | brk)
        bad = active & ~jnp.isfinite(rn_new)
        ok = active & ~bad
        x = jnp.where(ok, x_new, x)
        r = jnp.where(ok, r_new, r)
        rn = jnp.where(ok, rn_new, rn)
        iters = jnp.where(bad, iters_in, iters)
        done = done | (ok & (rn_new <= target))
        brk = brk | bad
        return cycle + 1, x, r, rn, hist, iters, done, brk

    brk0 = jnp.zeros(batch, dtype=bool)
    state = (jnp.int32(0), x, r, rn0, hist, iters0, done0, brk0)
    _, x, r, rn, hist, iters, done, brk = jax.lax.while_loop(
        outer_cond, outer_body, state)
    status = jnp.where(done, STATUS_CONVERGED,
                       jnp.where(brk, STATUS_BREAKDOWN,
                                 STATUS_MAXITER)).astype(jnp.int32)
    return _finish(
        SolveResult(x=x, converged=done, iterations=iters,
                    residual_norms=hist, status=status), preconditioner,
        "gmres")
