"""Instance generators of the benchmark, as flat numpy arrays.

* ``hpcg_row_net``: the row-net hypergraph of the HPCG benchmark's matrix
  (hpcg-benchmark.org, reference ``GenerateProblem_ref.cpp``), the model
  of distributing HPCG's SpMV (``ComputeSPMV``) over processes.  The
  matrix is the 27-point stencil of a 3-D grid of ``nx * ny * nz`` points:
  one row per point, row id ``ix + nx * (iy + ny * iz)``, a non-zero for
  every neighbour in the 3 x 3 x 3 box that lies inside the grid.  One node
  per column, weighted by its non-zeros; one edge per row.
* ``tiled_cholesky_dag``: the task DAG of the right-looking tiled Cholesky
  factorization of a matrix of ``tiles`` x ``tiles`` tiles (LAPACK Working
  Note 191, PLASMA's ``dpotrf``): POTRF, TRSM, SYRK and GEMM tasks, each
  depending on the tasks that last wrote the tiles it reads.  A task's work
  is its flop count in units of b^3 / 3 (POTRF 1, TRSM 3, SYRK 3, GEMM 6,
  b the tile size); each communication carries one tile.

Both are deterministic.  A seed draws a random relabelling of each: node
ids and edge order are permuted, so every relabelling has the same sizes
and degree sequence, and differs from another only as the solver's
tie-breaking does.  A cell's traffic serves a pool of relabellings drawn
from fixed seeds, in an order that the run's ``--seed`` draws
(``bench/harness.py``).  The kind modules hand the program only the built
``Hypergraph`` / ``Dag``.

A configuration whose ``generator`` is neither of these names a file of
its own, ``bench/generators/<name>.py``, found by that name as the metric
readers are (``bench/generators/__init__.py`` says what it defines).

This module, like every generator file, imports nothing of the program
(``repro``): the instance and the plain reference stay independent of the
code under test.
"""
from __future__ import annotations

import importlib.util
import pathlib

import numpy as np

GENERATOR_DIR = pathlib.Path(__file__).resolve().parent / "generators"


def stencil27(nx: int, ny: int, nz: int) -> tuple[int, np.ndarray, np.ndarray]:
    """Rows and columns of HPCG's 27-point matrix, sorted by (row, column)."""
    n = nx * ny * nz
    iz, iy, ix = (a.ravel() for a in np.meshgrid(
        np.arange(nz), np.arange(ny), np.arange(nx), indexing="ij"))
    rows, cols = [], []
    for sz in (-1, 0, 1):
        for sy in (-1, 0, 1):
            for sx in (-1, 0, 1):
                jx, jy, jz = ix + sx, iy + sy, iz + sz
                ok = ((jx >= 0) & (jx < nx) & (jy >= 0) & (jy < ny)
                      & (jz >= 0) & (jz < nz))
                rows.append(np.flatnonzero(ok))
                cols.append((jx + nx * (jy + ny * jz))[ok])
    row = np.concatenate(rows).astype(np.int64)
    col = np.concatenate(cols).astype(np.int64)
    order = np.lexsort((col, row))
    return n, row[order], col[order]


def hpcg_row_net(nx: int, ny: int, nz: int) -> dict:
    """Row-net hypergraph of the 27-point matrix (every row has at least 8
    non-zeros, so no edge is dropped and no node is isolated)."""
    n, row, col = stencil27(nx, ny, nz)
    xpins = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(row, minlength=n), out=xpins[1:])
    return {"n": n, "xpins": xpins, "pins": col,
            "omega": np.bincount(col, minlength=n).astype(np.float64)}


POTRF, TRSM, SYRK, GEMM = 1.0, 3.0, 3.0, 6.0    # flops / (b^3 / 3)


def tiled_cholesky_dag(tiles: int) -> dict:
    """Task DAG of the tiled Cholesky factorization A = L L^T (lower).

    Step k factors the diagonal tile (POTRF), solves the tiles below it
    (TRSM), and updates the trailing matrix: SYRK on each diagonal tile,
    GEMM on each tile below the diagonal.  A task depends on the task that
    last wrote each tile it reads or updates."""
    work, src, dst = [], [], []
    last: dict[tuple[int, int], int] = {}      # tile -> its last writer

    def task(w: float, tile: tuple[int, int], *reads: tuple[int, int]) -> None:
        t = len(work)
        work.append(w)
        for r in (tile,) + reads:
            if r in last:
                src.append(last[r])
                dst.append(t)
        last[tile] = t

    for k in range(tiles):
        task(POTRF, (k, k))
        for i in range(k + 1, tiles):
            task(TRSM, (i, k), (k, k))
        for i in range(k + 1, tiles):
            task(SYRK, (i, i), (i, k))
            for j in range(k + 1, i):
                task(GEMM, (i, j), (i, k), (j, k))
    n = len(work)
    key = np.unique(np.asarray(src, dtype=np.int64) * n
                    + np.asarray(dst, dtype=np.int64))
    return {"n": n, "src": key // n, "dst": key % n,
            "omega": np.asarray(work), "mu": np.ones(n)}


GENERATORS = {"hpcg_row_net": hpcg_row_net,
              "tiled_cholesky_dag": tiled_cholesky_dag}


def relabel_hypergraph(inst: dict, seed: int) -> dict:
    """Permute node ids and edge order; pins stay sorted within an edge."""
    rng = np.random.default_rng(seed)
    n, xpins, pins = inst["n"], inst["xpins"], inst["pins"]
    node_new = rng.permutation(n)                  # old id -> new id
    order = rng.permutation(len(xpins) - 1)        # new edge -> old edge
    lens = np.diff(xpins)[order]
    new_x = np.zeros(len(order) + 1, dtype=np.int64)
    np.cumsum(lens, out=new_x[1:])
    seg = np.repeat(np.arange(len(order)), lens)
    src = xpins[order][seg] + (np.arange(len(seg)) - new_x[:-1][seg])
    new_pins = node_new[pins[src]]
    new_pins = new_pins[np.lexsort((new_pins, seg))]
    omega = np.empty_like(inst["omega"])
    omega[node_new] = inst["omega"]
    return {"n": n, "xpins": new_x, "pins": new_pins, "omega": omega}


def relabel_dag(inst: dict, seed: int) -> dict:
    """Permute node ids; edges sorted by (src, dst) under the new ids."""
    rng = np.random.default_rng(seed)
    n = inst["n"]
    node_new = rng.permutation(n)
    key = np.unique(node_new[inst["src"]] * np.int64(n)
                    + node_new[inst["dst"]])
    omega = np.empty_like(inst["omega"])
    omega[node_new] = inst["omega"]
    mu = np.empty_like(inst["mu"])
    mu[node_new] = inst["mu"]
    return {"n": n, "src": key // n, "dst": key % n, "omega": omega, "mu": mu}


RELABEL = {"hpcg_row_net": relabel_hypergraph,
           "tiled_cholesky_dag": relabel_dag}


def load_generator(name: str):
    """The module ``GENERATOR_DIR/<name>.py``, which defines ``build`` and
    ``relabel``."""
    path = GENERATOR_DIR / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no instance generator {name!r}: it is neither in "
                       f"bench/gen.py's GENERATORS nor a file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_generator_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def instance(spec: dict, seed: int) -> dict:
    """The instance of a configuration's ``instance`` entry (generator name
    and its sizes), relabelled by the run's seed."""
    params = dict(spec)
    kind = params.pop("generator")
    if kind in GENERATORS:
        return RELABEL[kind](GENERATORS[kind](**params), seed)
    mod = load_generator(kind)
    return mod.relabel(mod.build(**params), seed)
