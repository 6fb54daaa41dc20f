"""Compile the solve path for a described TPU v5e, with no chip attached.

The TPU compiler is installed here and compiles for a topology that is
described, not attached (`jax.experimental.topologies`), so these tests
catch at no chip time what the chip's compiler would refuse: the scan
engine at the paper's published sizes (lung2_like(1.0), torso2_like(1.0))
for 1 and 8 right-hand sides, the sharded engine on a four-chip mesh with
its tiles lane-sharded as arguments, the jitted IC(0)-PCG step, and the
Pallas SpTRSV kernel, which Mosaic refuses today (strict xfail).

Nothing runs, so nothing here is a result or a time.  The topology is
described inside a module-scoped fixture, never at import: one process
at a time may load the TPU library, and every test worker imports this
file.  Keep these tests in this one file.
"""
import os

import numpy as np
import pytest

import jax
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache: keep the cache off while these tests compile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def schedules():
    """The sweep schedules the chip smoke test solves, at full size."""
    from repro.solver import TriangularOperator
    from repro.sparse import generators
    return {
        "lung2": TriangularOperator.from_csr(
            generators.lung2_like(1.0), tune="avgLevelCost",
            cache=False).schedule,
        "torso2": TriangularOperator.from_csr(
            generators.torso2_like(1.0), tune="no_rewriting",
            cache=False).schedule,
    }


def _shapes(tree, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


def _rhs(n, rhs, sharding):
    shape = (n,) if rhs == 1 else (n, rhs)
    return jax.ShapeDtypeStruct(shape, np.float32, sharding=sharding)


@pytest.mark.parametrize("rhs", [1, 8])
@pytest.mark.parametrize("name", ["lung2", "torso2"])
def test_scan_engine_compiles_for_v5e(schedules, one_chip, name, rhs):
    from repro.solver.levelset import _scan_jit, host_leaves
    sched = schedules[name]
    leaves = host_leaves(sched)
    args = _shapes(leaves, jax.tree.map(lambda _: one_chip, leaves))
    c = _rhs(sched.n, rhs, one_chip)
    compiled = _scan_jit.lower(args, sched.n, sched.n_carry, c).compile()
    mem = compiled.memory_analysis()
    # the tiles are arguments of the executable, not constants baked in
    assert mem.argument_size_in_bytes >= sched.memory_bytes()
    assert compiled.out_info.shape == c.shape


@pytest.mark.parametrize("rhs", [1, 8])
def test_sharded_engine_compiles_on_four_chip_mesh(topo, schedules, rhs):
    from repro.solver.distributed import (_padded_schedule, _sharded_solve,
                                          group_shardings)
    from repro.solver.levelset import host_leaves
    mesh = Mesh(np.array(topo.devices), ("model",))
    padded = _padded_schedule(schedules["lung2"], mesh.shape["model"])
    leaves = host_leaves(padded)
    args = _shapes(leaves, group_shardings(leaves, mesh, "model"))
    c = _rhs(padded.n, rhs, NamedSharding(mesh, P()))
    lowered = _sharded_solve.lower(args, c, mesh=mesh, axis="model",
                                   n=padded.n, n_carry=padded.n_carry)
    # one all_gather family per step: values + row ids in the scan body
    assert lowered.as_text().count("stablehlo.all_gather") == 2
    mem = lowered.compile().memory_analysis()
    # each chip holds a quarter of the lanes (plus the replicated
    # right-hand side), not the whole schedule
    tiles = mem.argument_size_in_bytes - np.prod(c.shape) * 4
    assert tiles < padded.memory_bytes() / 2


def test_pcg_step_compiles_for_v5e(one_chip):
    """The jitted float32 IC(0)-PCG step, as a user writes it.  The
    closed-over schedules become constants of this program."""
    from repro.iterative import cg
    from repro.precond import Preconditioner
    from repro.sparse import generators
    A = generators.poisson2d_spd(64, 64)
    pre = Preconditioner.ic0(A, tune="avgLevelCost", cache=False)
    step = jax.jit(lambda b: cg(A, b, preconditioner=pre, tol=1e-5,
                                maxiter=500))
    compiled = step.lower(_rhs(A.n_rows, 1, one_chip)).compile()
    assert compiled.out_info.x.shape == (A.n_rows,)


@pytest.mark.xfail(strict=True, raises=(ValueError, NotImplementedError),
                   reason="Mosaic refuses the kernel's (1, C) blocks over "
                          "(S, C) tiles and its dynamic VMEM gathers; the "
                          "kernel rewrite (ROADMAP A2) flips this test")
def test_sptrsv_pallas_kernel_compiles_for_v5e(schedules, one_chip):
    from repro.kernels.sptrsv_level import sptrsv_groups_pallas
    from repro.solver.levelset import host_leaves
    sched = schedules["lung2"]
    leaves = host_leaves(sched)
    args = _shapes(leaves, jax.tree.map(lambda _: one_chip, leaves))
    c_pad = _rhs(sched.n + 1, 1, one_chip)
    sptrsv_groups_pallas.lower(args, c_pad, n=sched.n, n_carry=sched.n_carry,
                               interpret=False).compile()
