"""Task DAG of HPCG's symmetric Gauss-Seidel smoother, one call.

HPCG 3.1's reference smoother ``ComputeSYMGS_ref.cpp`` runs over the
27-point matrix that ``GenerateProblem_ref.cpp`` builds (``bench.gen
.stencil27``: ``n = nx * ny * nz`` rows, one per grid point).  One call is
a forward sweep over rows 0..n-1 and then a backward sweep over rows
n-1..0; each row ``i`` sets ``x[i]`` from ``r[i]`` and the current ``x``
of every column of row ``i``.  Each sweep is a sparse triangular solve.

One task per row and sweep: forward task ``i`` relaxes row ``i`` in the
forward sweep, backward task ``n + i`` in the backward sweep.  A task
depends on the task that last wrote each value it reads:

* forward task ``i`` reads the new ``x_j`` of each in-grid neighbour
  ``j < i`` (edge ``j -> i``); the neighbours ``j > i`` and ``x_i`` itself
  still hold the input ``x0``, which no task writes;
* backward task ``n + i`` reads its own forward value ``x_i`` (edge
  ``i -> n + i``), the forward ``x_j`` of each neighbour ``j < i`` (edge
  ``j -> n + i``) and the backward ``x_j`` of each neighbour ``j > i``
  (edge ``n + j -> n + i``).

Every value read is a data edge.  The edges ``j -> n + i`` (``j < i``) are
implied transitively, through ``j -> i -> n + i``, and are kept all the
same: in BSP that value still has to be present on the processor that
computes ``n + i``.  With ``m`` the matrix's non-zeros, there are
``2 n`` tasks and ``3 (m - n) / 2 + n`` edges: 68,804 at 16 x 16 x 8
(4,096 tasks), 26,446 at 8 x 8 x 13 (1,664 tasks).

A task's work is its row's non-zeros (8 to 27); each task's output is
one double, so ``mu`` is 1.
"""
from __future__ import annotations

import numpy as np

from bench import gen


def build(nx: int, ny: int, nz: int) -> dict:
    """The DAG of one ``ComputeSYMGS_ref`` call on an nx x ny x nz grid,
    edges deduplicated and sorted by (src, dst)."""
    n, row, col = gen.stencil27(nx, ny, nz)
    low = col < row
    up = col > row
    diag = np.arange(n, dtype=np.int64)
    src = np.concatenate([col[low], diag, col[low], n + col[up]])
    dst = np.concatenate([row[low], n + diag, n + row[low], n + row[up]])
    key = np.unique(src * np.int64(2 * n) + dst)
    nnz = np.bincount(row, minlength=n).astype(np.float64)
    return {"n": 2 * n, "src": key // (2 * n), "dst": key % (2 * n),
            "omega": np.concatenate([nnz, nnz]), "mu": np.ones(2 * n)}


relabel = gen.relabel_dag
