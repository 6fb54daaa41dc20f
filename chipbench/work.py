"""Operations and bytes that each cell's algorithm needs, from the problem.

The counts come from the matrices and the algorithm alone, never from the
program's schedule, padding or transform, so a change to the program
cannot move its own yardstick.  Values are float32 (4 bytes) and indices
int32 (4 bytes) on the device, as the configurations state.

* A sweep (L x = b, k right-hand-side columns) reads the factor's CSR once
  (values, column indices, row pointers) and its diagonal, reads b and
  writes x for each column, and does 2 nnz(L) operations per column.
* A PCG iteration is one SpMV with A (CSR once, p read, A p written), one
  forward and one backward sweep with the IC(0) factor, and CG's vector
  work: the dot products p.Ap, r.z and ||r|| and the updates of x, r and
  p, which read or write 14 vectors and do 12 operations per row.

`least_seconds` is the roofline: the larger of bytes over the chip's
bandwidth and operations over its peak, from `peaks.json`.
"""
from __future__ import annotations

import json
from pathlib import Path

__all__ = ["sweep", "spmv", "pcg_iteration", "peaks", "least_seconds"]

VALUE_BYTES = 4
INDEX_BYTES = 4
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def _add(*works) -> dict:
    return {"bytes": sum(w["bytes"] for w in works),
            "flops": sum(w["flops"] for w in works)}


def _csr_bytes(n: int, nnz: int) -> int:
    return nnz * (VALUE_BYTES + INDEX_BYTES) + (n + 1) * INDEX_BYTES


def sweep(n: int, nnz: int, columns: int = 1) -> dict:
    """One triangular sweep of an n-row factor with nnz entries (diagonal
    included) over `columns` right-hand sides."""
    return {"bytes": _csr_bytes(n, nnz) + n * VALUE_BYTES
            + columns * 2 * n * VALUE_BYTES,
            "flops": 2 * nnz * columns}


def spmv(n: int, nnz: int) -> dict:
    """y = A x for an n-row A with nnz entries."""
    return {"bytes": _csr_bytes(n, nnz) + 2 * n * VALUE_BYTES,
            "flops": 2 * nnz}


def pcg_iteration(n: int, nnz_a: int, nnz_l: int) -> dict:
    """One iteration of IC(0)-preconditioned CG (module doc)."""
    vectors = {"bytes": 14 * n * VALUE_BYTES, "flops": 12 * n}
    return _add(spmv(n, nnz_a), sweep(n, nnz_l), sweep(n, nnz_l), vectors)


def peaks(device_kind: str) -> dict:
    """The peak table's entry for a device kind; a kind that is not listed
    is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; listed: {sorted(table)}")
    return table[device_kind]


def least_seconds(work: dict, peak: dict) -> tuple:
    """(least seconds for `work` on the chip, which bound binds:
    "bandwidth" or "compute")."""
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    t_flops = work["flops"] / peak["flops_per_s"]
    return (t_bytes, "bandwidth") if t_bytes >= t_flops \
        else (t_flops, "compute")
