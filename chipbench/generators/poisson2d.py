"""5-point Laplacian on an `nx` x `ny` grid (configuration keys), the
matrix `repro.sparse.generators.poisson2d_spd` builds.  It has no
random values: the seed reaches a Poisson cell through its right-hand
sides only."""
from __future__ import annotations

import scipy.sparse as sp


def laplacian(nx: int, ny: int) -> sp.csr_matrix:
    """x fastest: diagonal 4, neighbours -1 (symmetric positive
    definite)."""
    ix = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(nx, nx))
    iy = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(ny, ny))
    m = (sp.kron(sp.identity(ny), ix) + sp.kron(iy, sp.identity(nx))).tocsr()
    m.eliminate_zeros()
    m.sort_indices()
    return m


def build(config: dict, seed: int) -> sp.csr_matrix:
    return laplacian(config["nx"], config["ny"])
