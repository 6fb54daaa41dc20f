"""One-mesh IC(0)-PCG with the sharded operands as jit arguments.

`device_matvec(A, mesh=...)` and a mesh `Preconditioner`'s `device_apply()`
are `jax.tree_util.Partial`s over their placed arrays.  Passed to `jax.jit`
as arguments, the schedule tiles and A's nonzeros stay lane-sharded
arguments; closed over, they become constants of the program.  The cases
run in one subprocess with 4 forced host devices, so this process keeps
its single-device view (as in test_distributed.py).
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import re
    import numpy as np
    import scipy.sparse as sp
    import jax
    import jax.numpy as jnp
    from chipbench import reference, steps
    from repro.iterative import cg
    from repro.iterative.operators import device_matvec
    from repro.obs import default_registry
    from repro.precond import Preconditioner
    from repro.solver.distributed import count_all_gathers, default_mesh
    from repro.sparse import generators

    CONST = re.compile(r'stablehlo\\.constant dense<("0x[0-9A-Fa-f]+"|'
                       r'\\[[^>]*\\])>\\s*:\\s*tensor<([^>]*)>')

    def constants(text):
        # element counts of the non-splat dense constants of a lowering
        out = []
        for m in CONST.finditer(text):
            dims = m.group(2).split("x")[:-1]
            out.append(int(np.prod([int(d) for d in dims])) if dims else 1)
        return out

    def total(name):
        inst = default_registry().get(name)
        return 0 if inst is None else inst.total()

    res = {}
    assert len(jax.devices()) == 4
    mesh = default_mesh()
    A = generators.poisson2d_spd(48, 48)
    n = A.n_rows
    A_sp = sp.csr_matrix((A.data, A.indices, A.indptr), shape=A.shape)
    names = ("sharded.exchanges", "sharded.tile_bytes_per_device",
             "sweep.device_preamble_spmv")
    before = [total(k) for k in names]
    P = Preconditioner.ic0(A, tune="avgLevelCost", mesh=mesh, cache=False)
    M = P.device_apply()
    A_op = device_matvec(A, mesh=mesh)
    res["exchanges"], res["tile_bytes"], res["products"] = [
        total(k) - b for k, b in zip(names, before)]
    res["steps_py"] = (steps.sweep_steps(P.forward)
                       + steps.sweep_steps(P.backward))
    res["main_steps"] = (P.forward.schedule.num_steps
                         + P.backward.schedule.num_steps)
    res["families"] = sum(count_all_gathers(op.schedule, mesh)["families"]
                          for op in (P.forward, P.backward))

    # M is Partial(pair, forward, backward), each Partial(sweep, main, pre)
    tiles = [a for sweep in M.args for a in jax.tree.leaves(sweep.args[0])]
    pre = [a for sweep in M.args for a in jax.tree.leaves(sweep.args[1])]
    res["preamble_replicated"] = bool(pre) and all(
        a.sharding.is_fully_replicated for a in pre)
    res["whole_tile_bytes"] = sum(a.nbytes for a in tiles)
    res["lane_quarters"] = all(
        a.sharding.shard_shape(a.shape)[1] * 4 == a.shape[1]
        and a.shape[1] % 4 == 0 for a in tiles)
    nnz_leaves = jax.tree.leaves(A_op)
    res["nnz_quarters"] = all(
        a.sharding.shard_shape(a.shape)[0] * 4 == a.shape[0]
        for a in nnz_leaves)
    res["smallest_tile"] = min(int(np.prod(a.shape)) for a in tiles)

    rng = np.random.default_rng(7)
    b_np = (A_sp @ rng.standard_normal(n)).astype(np.float32)
    b = jnp.asarray(b_np)
    tol, maxiter = 1e-5, 1000

    def solve_args(A_op, M, rhs):
        return cg(A_op, rhs, preconditioner=M, tol=tol, maxiter=maxiter)

    lowered = jax.jit(solve_args).lower(A_op, M, b)
    compiled = lowered.compile()
    arg_shardings = jax.tree.leaves(compiled.input_shardings[0][1])
    res["compiled_takes_lanes"] = len(arg_shardings) == len(
        jax.tree.leaves(M)) and all(
        s.is_equivalent_to(a.sharding, a.ndim)
        for s, a in zip(arg_shardings, jax.tree.leaves(M))
        if isinstance(a, jax.Array))
    args_consts = constants(lowered.as_text())
    res["args_largest_constant"] = max(args_consts, default=0)

    closure = jax.jit(lambda rhs: cg(A_op, rhs, preconditioner=P, tol=tol,
                                     maxiter=maxiter))
    res["closure_constant_elements"] = sum(
        constants(closure.lower(b).as_text()))
    P1 = Preconditioner.ic0(A, tune="avgLevelCost", cache=False)
    single = jax.jit(lambda rhs: cg(A, rhs, preconditioner=P1, tol=tol,
                                    maxiter=maxiter))
    L_ref = reference.ic0(A_sp)
    _, ref_hist = reference.pcg(A_sp, b_np, L_ref, tol=tol,
                                maxiter=maxiter, iterations=10)
    res["forms"] = {}
    for form, out in (("arguments", compiled(A_op, M, b)),
                      ("closure", closure(b)), ("single", single(b))):
        its = int(out.iterations)
        hist = np.asarray(out.residual_norms)[:its + 1]
        res["forms"][form] = {
            "converged": bool(out.converged), "iterations": its,
            "resid": reference.residual_2norm(A_sp, np.asarray(out.x), b_np),
            "hist_gap": reference.history_gap(hist, ref_hist, 10)}

    # built at float32, applied to float64 under x64: full precision
    A3 = type(A)(indptr=A.indptr, indices=A.indices, data=A.data / 3,
                 shape=A.shape)
    mv3 = device_matvec(A3, mesh=mesh)
    v = np.random.default_rng(5).standard_normal(n)
    want = (A_sp / 3) @ v
    with jax.enable_x64():
        got = np.asarray(mv3(jnp.asarray(v, jnp.float64)))
    res["f64_rel_err"] = float(np.abs(got - want).max() / np.abs(want).max())
    print(json.dumps(res))
""")


@pytest.fixture(scope="module")
def mesh_pcg():
    env = {"PYTHONPATH": f"{ROOT / 'src'}{os.pathsep}{ROOT}",
           "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_arguments_form_embeds_no_tile_and_no_matrix_entry(mesh_pcg):
    # the closure form embeds the schedules and A; the arguments form
    # holds no constant as large as the smallest tile or as n
    assert mesh_pcg["closure_constant_elements"] > \
        mesh_pcg["whole_tile_bytes"] // 4
    assert mesh_pcg["args_largest_constant"] < min(
        mesh_pcg["smallest_tile"], 48 * 48)


def test_each_device_holds_a_quarter_of_every_group_and_of_A(mesh_pcg):
    assert mesh_pcg["lane_quarters"] and mesh_pcg["nnz_quarters"]
    assert mesh_pcg["compiled_takes_lanes"]
    assert mesh_pcg["tile_bytes"] * 4 == mesh_pcg["whole_tile_bytes"]
    # the preamble's B' product is replicated, as x is: no collective
    assert mesh_pcg["preamble_replicated"]


@pytest.mark.parametrize("form", ["arguments", "closure", "single"])
def test_forms_agree_with_the_plain_reference(mesh_pcg, form):
    got = mesh_pcg["forms"][form]
    assert got["converged"]
    assert got["resid"] <= 1e-3 and got["hist_gap"] <= 1e-3, got
    assert got["iterations"] == mesh_pcg["forms"]["single"]["iterations"]


def test_mesh_matvec_keeps_float64_precision(mesh_pcg):
    assert mesh_pcg["f64_rel_err"] < 1e-13


def test_exchanges_counter_is_the_steps_of_both_sweeps(mesh_pcg):
    # both preambles are B' products (no schedule, no exchange), so the
    # exchanges are the main schedules' steps; chipbench/steps.py still
    # counts the preamble schedules the device no longer runs
    assert mesh_pcg["products"] == 2
    assert mesh_pcg["exchanges"] == mesh_pcg["main_steps"] == \
        mesh_pcg["families"] > 0
    assert mesh_pcg["steps_py"] > mesh_pcg["exchanges"]


@pytest.mark.parametrize("engine", ["scan", "unrolled", "pallas"])
def test_single_device_preconditioner_is_a_jit_argument(engine):
    """Every engine's M^-1 is a pytree of arrays: passed to `jax.jit` as an
    argument it gives the closure form's answer, and its lowering takes
    the tiles as arguments instead of embedding them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.precond import Preconditioner
    from repro.sparse import generators
    A = generators.poisson2d_spd(12, 12)
    M = Preconditioner.ic0(A, tune="avgLevelCost",
                           cache=False).device_apply(engine)
    leaves = jax.tree.leaves(M)
    assert leaves and all(isinstance(a, (jax.Array, np.ndarray))
                          for a in leaves)
    r = jnp.asarray(np.random.default_rng(3).standard_normal(A.n_rows),
                    jnp.float32)
    as_argument = jax.jit(lambda M, r: M(r))
    lowered = as_argument.lower(M, r)
    assert len(jax.tree.leaves(lowered.args_info)) == len(leaves) + 1
    np.testing.assert_allclose(np.asarray(as_argument(M, r)),
                               np.asarray(jax.jit(lambda r: M(r))(r)),
                               rtol=1e-6, atol=1e-7)
