"""The benchmark's matrices: a configuration's generator, found by name.

A configuration file names its generator (`"generator": "<name>"`);
`build` loads `generators/<name>.py` beside this file and calls its
`build(config, seed)`.  A new matrix family is a new generator file.
The helpers here are the pieces that generators share, copied from
`repro.sparse.generators` so that the inputs every cell runs on cannot
change with the program.  Everything is a scipy CSR matrix in float64
with sorted column indices.

The pattern of a configuration is fixed by its file; the values of a
generated factor and every right-hand side come from `--seed`, so two
seeds run the same work on different numbers.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np
import scipy.sparse as sp

from chipbench import load_module

__all__ = ["GENERATORS_DIR", "generator", "build", "csr", "spread",
           "from_level_profile", "dominant_values"]

GENERATORS_DIR = Path(__file__).resolve().parent / "generators"


def generator(name: str):
    """The generator module `generators/<name>.py`; a name with no file
    is an error that names the file it looked for."""
    path = GENERATORS_DIR / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no generator file {path} for generator "
                                f"{name!r}")
    return load_module(path)


def build(config: dict, seed: int) -> sp.csr_matrix:
    """The matrix a configuration file names, with its values for `seed`."""
    return generator(config["generator"]).build(config, seed)


def csr(rows, cols, vals, n: int) -> sp.csr_matrix:
    m = sp.coo_matrix((vals, (rows, cols)), shape=(n, n)).tocsr()
    m.sum_duplicates()
    m.sort_indices()
    return m


def spread(total: int, parts: int) -> list:
    """`total` split into `parts` whole numbers differing by at most 1,
    the larger first."""
    base = total // parts
    rem = total - base * parts
    return [base + (1 if i < rem else 0) for i in range(parts)]


def dominant_values(pattern: sp.csr_matrix, rng) -> sp.csr_matrix:
    """Values on a lower-triangular pattern: off-diagonals U(-1, 1), each
    diagonal the row's absolute off-diagonal sum plus U(1, 2)."""
    n = pattern.shape[0]
    rows = np.repeat(np.arange(n), np.diff(pattern.indptr))
    cols = pattern.indices
    vals = rng.uniform(-1.0, 1.0, size=rows.shape[0])
    diag = rows == cols
    abssum = np.zeros(n)
    np.add.at(abssum, rows[~diag], np.abs(vals[~diag]))
    vals[diag] = abssum[rows[diag]] + rng.uniform(1.0, 2.0, int(diag.sum()))
    return sp.csr_matrix((vals, cols.copy(), pattern.indptr.copy()),
                         shape=pattern.shape)


def from_level_profile(level_sizes, indegree, distance,
                       rng) -> sp.csr_matrix:
    """Pattern with an exact rows-per-level profile (the random draws, in
    order, of `repro.sparse.generators.from_level_profile` without
    locality), values all 1."""
    level_sizes = np.asarray(level_sizes, dtype=np.int64)
    n = int(level_sizes.sum())
    starts = np.concatenate([[0], np.cumsum(level_sizes)])

    def pick(tgt):
        lo, hi = starts[tgt], starts[tgt + 1]
        return lo + (rng.random(tgt.shape[0]) * (hi - lo)).astype(np.int64)

    rows_list, cols_list = [], []
    for lvl in range(1, level_sizes.shape[0]):
        m = int(level_sizes[lvl])
        rids = np.arange(starts[lvl], starts[lvl + 1])
        indeg = np.maximum(np.asarray(indegree(rng, lvl, m), np.int64), 1)
        rows_list.append(rids)
        cols_list.append(pick(np.full(m, lvl - 1, dtype=np.int64)))
        extra = indeg - 1
        if int(extra.sum()):
            dist = np.asarray(distance(rng, lvl, int(extra.sum())), np.int64)
            rows_list.append(np.repeat(rids, extra))
            cols_list.append(pick(lvl - np.clip(dist, 1, lvl)))
    rows = np.concatenate(rows_list + [np.arange(n)])
    cols = np.concatenate(cols_list + [np.arange(n)])
    return csr(rows, cols, np.ones(rows.shape[0]), n)
