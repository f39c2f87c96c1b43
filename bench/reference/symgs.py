"""Plain replay of HPCG's symmetric Gauss-Seidel smoother.

``sweep`` transcribes HPCG 3.1's ``ComputeSYMGS_ref.cpp`` line for line
over the matrix of ``GenerateProblem_ref.cpp`` (27-point stencil,
diagonal 26, off-diagonal -1, columns of a row in ascending order): a
forward loop over rows 0..n-1, then a backward loop over rows n-1..0,
each row summing in column order.

``dependencies`` runs the same two loops and records, for each value a
task reads, the task that last wrote it: forward task ``i`` relaxes row
``i`` in the forward loop, backward task ``n + i`` in the backward loop.

``replay`` executes a BSP schedule of those tasks superstep by superstep:
each copy of a task computes its row on its processor, from the values
present there, and raises where one is absent.  Every task computes the
same float64 expression from the same inputs in the same order as
``sweep``, so the replayed ``x`` equals the sweep's bit for bit; any
difference is a wrong dependency or a wrong schedule, not rounding.

This module imports nothing of the program under test, nor the
benchmark's generators.
"""
from __future__ import annotations

import struct

import numpy as np

DIAG = 26.0
OFF = -1.0


def matrix(nx: int, ny: int, nz: int) -> list[list[int]]:
    """Column indices of each row, as ``GenerateProblem_ref`` lists them."""
    cols = []
    for iz in range(nz):
        for iy in range(ny):
            for ix in range(nx):
                row = []
                for sz in (-1, 0, 1):
                    for sy in (-1, 0, 1):
                        for sx in (-1, 0, 1):
                            jx, jy, jz = ix + sx, iy + sy, iz + sz
                            if (0 <= jx < nx and 0 <= jy < ny
                                    and 0 <= jz < nz):
                                row.append(jx + nx * (jy + ny * jz))
                cols.append(row)
    return cols


def _relax(i: int, cols: list[int], ri: float, xv) -> float:
    """One row of ``ComputeSYMGS_ref``: ``xv[j]`` the current ``x_j``."""
    total = ri
    for j in cols:
        total -= (DIAG if j == i else OFF) * xv[j]
    total += xv[i] * DIAG        # remove the diagonal's contribution
    return total / DIAG


def sweep(nx: int, ny: int, nz: int, r, x0) -> np.ndarray:
    """``x`` after one call of ``ComputeSYMGS_ref`` from ``x0``."""
    cols = matrix(nx, ny, nz)
    r = [float(v) for v in r]
    x = [float(v) for v in x0]
    n = len(cols)
    for i in range(n):
        x[i] = _relax(i, cols[i], r[i], x)
    for i in range(n - 1, -1, -1):
        x[i] = _relax(i, cols[i], r[i], x)
    return np.asarray(x)


def dependencies(nx: int, ny: int, nz: int) -> set[tuple[int, int]]:
    """``(writer, reader)`` task pairs of the two loops."""
    cols = matrix(nx, ny, nz)
    n = len(cols)
    last = [-1] * n                  # x_j's last writer; -1 is the input x0
    out = set()
    for t, i in [(i, i) for i in range(n)] + [(n + i, i)
                                             for i in range(n - 1, -1, -1)]:
        for j in cols[i]:
            if last[j] >= 0:
                out.add((last[j], t))
        last[i] = t
    return out


def _bits(v: float) -> bytes:
    return struct.pack("<d", v)


def replay(nx: int, ny: int, nz: int, r, x0, P: int, S: int, assign,
           comms, label=None) -> np.ndarray:
    """``x`` computed by the schedule (``assign``, ``comms`` and ``S`` as
    ``bench.reference.schedule`` reads them, ``P`` processors).

    ``label[t]`` is the schedule's id of task ``t`` (the identity if
    ``None``).  A copy of a task on processor p in superstep s reads each
    value from p's memory: computed there in superstep s or earlier, or
    received there before s.  Copies on one processor in one superstep run
    in the sweeps' order.  Raises ``ValueError`` where a value is absent,
    a task never runs, or two copies of a task differ in a bit."""
    cols = matrix(nx, ny, nz)
    n = len(cols)
    r = [float(v) for v in r]
    x0 = [float(v) for v in x0]
    label = np.arange(2 * n) if label is None else np.asarray(label)
    task = np.empty(2 * n, dtype=np.int64)
    task[label] = np.arange(2 * n)               # schedule id -> task
    # sequential order: forward rows ascending, then backward descending
    rank = np.concatenate([np.arange(n), 3 * n - 1 - np.arange(n, 2 * n)])

    def reads(t: int) -> list[tuple[int, int]]:
        """(column, task whose value of x_column t reads, or -1 for x0)."""
        i = t % n
        if t < n:
            return [(j, j if j < i else -1) for j in cols[i]]
        return [(j, j if j <= i else n + j) for j in cols[i]]

    work: dict[tuple[int, int], list[int]] = {}
    for v, copies in enumerate(assign):
        for p, s in copies.items():
            work.setdefault((s, p), []).append(int(task[v]))
    sends: dict[int, list] = {}
    for (v, d), (sp, s) in comms.items():
        sends.setdefault(s, []).append((int(task[v]), sp, d))
    mem = [dict() for _ in range(P)]             # task -> value, per proc
    value: dict[int, float] = {}
    for s in range(S):
        for p in range(P):
            for t in sorted(work.get((s, p), ()), key=lambda t: rank[t]):
                xv = {}
                for j, u in reads(t):
                    if u < 0:
                        xv[j] = x0[j]
                    elif u in mem[p]:
                        xv[j] = mem[p][u]
                    else:
                        raise ValueError(f"task {t} on p{p} s{s}: value of "
                                         f"task {u} absent")
                mem[p][t] = got = _relax(t % n, cols[t % n], r[t % n], xv)
                if t in value and _bits(value[t]) != _bits(got):
                    raise ValueError(f"task {t}: copies differ")
                value.setdefault(t, got)
        arrivals = []
        for t, sp, d in sends.get(s, ()):
            if t not in mem[sp]:
                raise ValueError(f"task {t} sent from p{sp} in s{s}: value "
                                 "absent there")
            arrivals.append((d, t, mem[sp][t]))
        for d, t, v in arrivals:
            mem[d][t] = v
    missing = [t for t in range(2 * n) if t not in value]
    if missing:
        raise ValueError(f"{len(missing)} tasks never computed, "
                         f"first {missing[0]}")
    return np.asarray([value[n + i] for i in range(n)])
