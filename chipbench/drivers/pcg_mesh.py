"""Closed-loop IC(0)-PCG solves under one mesh of chips, through one
compiled program whose sharded operands are its arguments.

Set-up goes as in `pcg.py`, on a one-axis mesh over the configuration's
`chips` (axis `mesh_axis`): the SPD matrix, the program's IC(0), the
preconditioner pair with the fixed strategy built with `mesh=` (both
sweeps through the sharded engine: the lanes of every schedule step
sharded over the mesh, x replicated, one all_gather family per step),
the SpMV `device_matvec(A, mesh=mesh)` (A's nonzeros sharded, one psum
per product), and the pool of right-hand sides b = A x, placed
replicated.  The SpMV and M^-1 enter `jax.jit` as arguments
(`jax.tree_util.Partial`s over their placed arrays), so each chip holds
its share of the tiles and of A and the program embeds neither;
`jax.jit(...).lower(...).compile()` is called once, as users of the
Krylov layer do.  The schedule's all_gather families and the tile bytes
one chip holds are read from the program's counters `sharded.exchanges`
and `sharded.tile_bytes_per_device` around the placing.

A second executable takes the same placed arguments with tol = 0 and
maxiter = `trace_iterations`: in a traced run (`repro.obs.enabled()`) the
first `trace_calls` calls run it, so the profile holds a few iterations
of the timed program, not a whole solve, and the iterations counter says
how many.  Its answers are not compared: they are not meant to converge.

Correctness and the control are `pcg.py`'s: every answer's float64
residual against the benchmark's own A, solves that did not converge as
failed calls, and the residual history against a plain float64 IC(0)-PCG
over its first iterations.  The answers of the warm-up's full solves,
made by the same executable, are compared with the window's: stopping
the profiler can outlast a traced run's window, which then completes no
full solve of its own.

A program that keeps these operands as closure constants would embed the
whole schedule in the program on every chip; set-up refuses it at once,
before any of the transform's minutes are spent.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from chipbench import load_module, matrices, work as work_

pcg = load_module(Path(__file__).resolve().parent / "pcg.py")

EXCHANGES = "sharded.exchanges"
TILE_BYTES = "sharded.tile_bytes_per_device"


State = pcg.State


def _counter(name: str) -> float:
    from repro.obs import default_registry
    inst = default_registry().get(name)
    return 0 if inst is None else inst.total()


def _require_arguments_form(mesh, axis: str) -> None:
    """Raise unless the mesh SpMV and the mesh preconditioner are pytrees
    of placed arrays, which `jax.jit` can take as arguments."""
    import jax
    from repro.iterative.operators import device_matvec
    from repro.precond import Preconditioner
    from repro.sparse.csr import CSR
    spd = CSR(indptr=np.array([0, 2, 5, 7]),
              indices=np.array([0, 1, 0, 1, 2, 1, 2]),
              data=np.array([2.0, -1.0, -1.0, 2.0, -1.0, -1.0, 2.0]),
              shape=(3, 3))
    M = Preconditioner.ic0(spd, tune="no_rewriting", mesh=mesh,
                           mesh_axis=axis, cache=False).device_apply()
    for what, fn in (("device_matvec(A, mesh=...)",
                      device_matvec(spd, mesh=mesh, axis=axis)),
                     ("Preconditioner(mesh=...).device_apply()", M)):
        leaves = jax.tree_util.tree_leaves(fn)
        if not leaves or not all(isinstance(x, jax.Array) for x in leaves):
            raise RuntimeError(
                f"{what} is not a pytree of placed arrays: this program "
                "cannot take its sharded operands as jit arguments")


def setup(config: dict, traffic: dict, seed: int, phases) -> State:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.iterative import cg
    from repro.iterative.operators import device_matvec
    from repro.precond import Preconditioner
    from repro.precond.factorize import ic0
    from repro.solver.distributed import default_mesh
    from repro.sparse.csr import CSR
    st = State()
    st.traffic, st.config = traffic, config
    st.chips, axis = config["chips"], config["mesh_axis"]
    st.rng = np.random.default_rng([seed, 1])
    with phases("mesh"):
        mesh = default_mesh(axis=axis, devices=jax.devices()[:st.chips])
        _require_arguments_form(mesh, axis)
    with phases("generate"):
        st.A = matrices.build(config, seed)
        n = st.A.shape[0]
        pool = traffic["rhs_pool_seed"]
        st.rhs = [(st.A @ np.random.default_rng([pool, k]).standard_normal(
                   n)).astype(np.float32)
                  for k in range(traffic["rhs_count"])]
        st.order = st.rng.permutation(len(st.rhs))
        A = CSR(indptr=st.A.indptr.astype(np.int64),
                indices=st.A.indices.astype(np.int64),
                data=st.A.data.copy(), shape=st.A.shape)
    with phases("factorize"):
        fac = ic0(A)
    with phases("transform_schedule"):
        P = Preconditioner.from_factors(fac, tune=config["strategy"],
                                        system=A, cache=False, mesh=mesh,
                                        mesh_axis=axis)
    st.nnz_l = fac.L.nnz
    before = _counter(EXCHANGES), _counter(TILE_BYTES)
    with phases("place"):
        st.M = P.device_apply()
        st.A_op = device_matvec(A, mesh=mesh, axis=axis)
    st.exchanges = _counter(EXCHANGES) - before[0]
    st.tile_bytes = _counter(TILE_BYTES) - before[1]
    tol, maxiter = config["tol"], config["maxiter"]
    capped = traffic["trace_iterations"]

    def solve(A_op, M, rhs):
        return cg(A_op, rhs, preconditioner=M, tol=tol, maxiter=maxiter)

    def solve_capped(A_op, M, rhs):
        return cg(A_op, rhs, preconditioner=M, tol=0.0, maxiter=capped)

    with phases("stage"):
        replicated = NamedSharding(mesh, PartitionSpec())
        st.b_dev = [jax.device_put(jnp.asarray(b), replicated)
                    for b in st.rhs]
        args = (st.A_op, st.M, st.b_dev[0])
        lowered = jax.jit(solve).lower(*args)
        lowered_capped = jax.jit(solve_capped).lower(*args)
    with phases("compile"):
        st.solve = lowered.compile()
    with phases("compile_capped"):
        st.solve_capped = lowered_capped.compile()
    st.answers, st.iterations = [], []
    with phases("warmup"):
        for i in range(traffic["warmup_calls"]):
            k = int(st.order[i % len(st.rhs)])
            _solve(st, k)
            jax.block_until_ready(
                st.solve_capped(st.A_op, st.M, st.b_dev[k]))
    return st


def _solve(st: State, k: int) -> int:
    """One full solve of pool member k, its answer kept for the check;
    returns its iterations."""
    res = st.solve(st.A_op, st.M, st.b_dev[k])
    x = np.asarray(res.x)
    its = int(res.iterations)
    hist = np.asarray(res.residual_norms)[:its + 1]
    st.answers.append((k, x, its, bool(res.converged), hist))
    return its


def call(st: State, i: int, control: bool = False) -> None:
    if control:
        pcg.call(st, i, control=True)
        return
    from repro import obs
    k = int(st.order[i % len(st.rhs)])
    if obs.enabled() and i < st.traffic["trace_calls"]:
        res = st.solve_capped(st.A_op, st.M, st.b_dev[k])
        st.iterations.append(int(res.iterations))
        return
    st.iterations.append(_solve(st, k))


end_to_end = pcg.end_to_end
check = pcg.check


def counters(st: State) -> dict:
    return {"exchanges": st.exchanges,
            "tile_bytes_per_device": st.tile_bytes,
            "iterations": list(st.iterations)}


def work(st: State) -> dict:
    """One CG iteration's work, and the chips it is spread over."""
    return dict(work_.pcg_iteration(st.A.shape[0], st.A.nnz, st.nnz_l),
                chips=st.chips)


def release(st: State) -> None:
    st.solve = st.solve_capped = st.M = st.A_op = None
    st.b_dev = None
