"""The benchmark's plain references, on hand-checked tiny instances and
against the repository's engines on the CPU."""
from __future__ import annotations

import numpy as np
import pytest

from bench import gen
from bench.reference import partition as rp
from bench.reference import schedule as rs


def _csr(edges):
    xpins = np.zeros(len(edges) + 1, dtype=np.int64)
    np.cumsum([len(e) for e in edges], out=xpins[1:])
    return xpins, np.concatenate([np.asarray(e) for e in edges])


@pytest.mark.parametrize("pin_masks,P,lam", [
    ((0b001, 0b001), 3, 1),           # one processor holds both pins
    ((0b001, 0b010), 3, 2),           # two disjoint single copies
    ((0b011, 0b110), 3, 1),           # replicas meet on processor 1
    ((0b001, 0b010, 0b100), 3, 3),    # three disjoint copies
    ((0b011, 0b100, 0b101), 3, 2),    # {p0 or p1, p2, p0 or p2}: p0 + p2
    ((0b1000_0001, 0b0100_0010, 0b0010_0100, 0b0001_1000), 8, 4),
    ((0b1000_0001, 0b0100_0001, 0b1010_0000), 8, 2),  # p0 + p7
])
def test_edge_lambda_hand_checked(pin_masks, P, lam):
    n = len(pin_masks)
    xpins, pins = _csr([list(range(n))])
    assert rp.edge_lambdas(xpins, pins, np.array(pin_masks), P)[0] == lam


def test_objective_balance_and_validity_hand_checked():
    # 4 nodes of weights 1, 2, 3, 4; edges {0,1}, {1,2,3}, {0,3}; P = 2
    xpins, pins = _csr([[0, 1], [1, 2, 3], [0, 3]])
    omega = np.array([1.0, 2.0, 3.0, 4.0])
    masks = np.array([0b01, 0b01, 0b10, 0b11])
    # lambdas 1, 2 ({p0: 1}, {p1: 2}, 3 on both), 1 (node 3 on p0 too)
    assert rp.objective(xpins, pins, np.ones(3), masks, 2) == 1.0
    assert list(rp.loads(omega, masks, 2)) == [7.0, 7.0]
    # capacity (1 + 0.5) / 2 * 10 = 7.5
    assert rp.overload(omega, masks, 2, 0.5) == -0.5
    assert rp.overload(omega, masks, 2, 0.3) == pytest.approx(0.5)
    assert rp.bad_masks(masks, 2) == 0
    assert rp.bad_masks(np.array([0, 1, 4, 3]), 2) == 2
    assert rp.multi_copies(masks) == 1


def test_partition_reference_agrees_with_engine():
    from repro.core.hypergraph import Hypergraph
    from repro.core.partition import (is_balanced, partition_cost,
                                      partition_with_replication)
    inst = gen.relabel_hypergraph(gen.hpcg_row_net(8, 8, 6), seed=3)
    hg = Hypergraph.from_csr(inst["n"], inst["xpins"], inst["pins"],
                             omega=inst["omega"])
    rng = np.random.default_rng(0)
    for P in (3, 8):
        masks = rng.integers(1, 1 << P, size=hg.n)
        assert rp.objective(inst["xpins"], inst["pins"], hg.mu, masks,
                            P) == partition_cost(hg, masks, P)
    base, rep = partition_with_replication(hg, 4, 0.05, multilevel=True,
                                           frontier="numpy")
    for res in (base, rep):
        assert rp.objective(inst["xpins"], inst["pins"], hg.mu, res.masks,
                            4) == res.cost
        assert (rp.overload(inst["omega"], res.masks, 4, 0.05) <= 0) \
            == is_balanced(hg, res.masks, 4, 0.05)


def _chain_schedule(with_comm: bool = True):
    # a -> b; a on p0 in s0, b on p1 in s1, a sent p0 -> p1 in s0
    comms = {(0, 1): (0, 0)} if with_comm else {}
    return [{0: 0}, {1: 1}], comms


def test_schedule_reference_hand_checked():
    assign, comms = _chain_schedule()
    src, dst = np.array([0]), np.array([1])
    assert rs.errors(2, src, dst, 2, 2, assign, comms) == []
    # s0: work 1, h = 1 -> 1 + L + g; s1: work 1
    assert rs.cost(np.ones(2), np.ones(2), 2, 4.0, 20.0, 2, assign,
                   comms) == 1 + 20 + 4 + 1
    assign, comms = _chain_schedule(with_comm=False)
    errs = rs.errors(2, src, dst, 2, 2, assign, comms)
    assert errs == ["parent 0 of 1 missing on p1 at s1"]
    # recomputation instead of a comm: a on both processors
    assert rs.errors(2, src, dst, 2, 2, [{0: 0, 1: 0}, {1: 1}], {}) == []
    # a value received in superstep s is usable only from s + 1
    assert rs.errors(2, src, dst, 2, 2, [{0: 0}, {1: 0}],
                     {(0, 1): (0, 0)})
    assert rs.errors(2, src, dst, 2, 2, [{0: 0}, {}], {}) \
        == ["node 1 never computed"]
    assert rs.errors(2, src, dst, 2, 2, [{0: 0}, {1: 1}],
                     {(0, 1): (1, 0)}) != []          # source never had a
    assert rs.errors(2, src, dst, 2, 2, [{0: 0}, {0: 2}], {}) \
        == ["node 1 on p0 s2 out of range"]


def test_schedule_reference_agrees_with_engine():
    from repro.core.hypergraph import Dag
    from repro.core.schedule import BspInstance, best_replicated_schedule
    inst = gen.relabel_dag(gen.tiled_cholesky_dag(8), seed=5)
    dag = Dag.from_arrays(inst["n"], inst["src"], inst["dst"],
                          omega=inst["omega"], mu=inst["mu"])
    sched = best_replicated_schedule(BspInstance(dag, P=4, g=1, L=2),
                                     multilevel=True)
    assign = [dict(a) for a in sched.assign]
    assert sched.validate() == [] and sched.S > 1 and sched.comms
    assert rs.errors(inst["n"], inst["src"], inst["dst"], 4, sched.S,
                     assign, dict(sched.comms)) == []
    assert rs.cost(inst["omega"], inst["mu"], 4, 1, 2, sched.S, assign,
                   dict(sched.comms)) == sched.current_cost()


@pytest.mark.parametrize("nx,ny,nz", [(1, 1, 1), (2, 3, 4), (3, 3, 3),
                                      (8, 8, 8)])
def test_hpcg_matrix_hand_checked(nx, ny, nz):
    """Row lengths of the 27-point matrix: (3 - boundary sides) per axis,
    every pattern symmetric, the diagonal present, row ids HPCG's."""
    n, row, col = gen.stencil27(nx, ny, nz)
    assert n == nx * ny * nz

    def side(m):            # neighbours in one axis, including itself
        return np.array([1] if m == 1 else [2] + [3] * (m - 2) + [2])
    want = (side(nx)[None, None, :] * side(ny)[None, :, None]
            * side(nz)[:, None, None]).ravel()
    assert np.array_equal(np.bincount(row, minlength=n), want)
    keys = set(zip(row.tolist(), col.tolist()))
    assert all((j, i) in keys for i, j in keys)
    assert all((i, i) in keys for i in range(n))
    corner = {x + nx * (y + ny * z) for x in range(min(2, nx))
              for y in range(min(2, ny)) for z in range(min(2, nz))}
    assert set(col[row == 0].tolist()) == corner
    hg = gen.hpcg_row_net(nx, ny, nz)
    assert np.array_equal(hg["omega"], want.astype(float))
    assert hg["xpins"][-1] == len(row) == want.sum()


@pytest.mark.parametrize("tiles", [1, 2, 3, 6])
def test_tiled_cholesky_hand_checked(tiles):
    """Task counts and work of the tiled Cholesky DAG; for 2 tiles the
    whole DAG: POTRF(0) -> TRSM(1,0) -> SYRK(1,1) -> POTRF(1)."""
    d = gen.tiled_cholesky_dag(tiles)
    T = tiles
    n_gemm = T * (T - 1) * (T - 2) // 6
    assert d["n"] == T + T * (T - 1) + n_gemm
    assert d["omega"].sum() == (T * gen.POTRF + T * (T - 1) // 2
                                * (gen.TRSM + gen.SYRK) + n_gemm * gen.GEMM)
    # every task but the first POTRF has a parent; edges go forward
    assert np.all(d["src"] < d["dst"])
    assert set(range(1, d["n"])) <= set(d["dst"].tolist())
    if T == 2:
        assert list(zip(d["src"], d["dst"])) == [(0, 1), (1, 2), (2, 3)]
        assert list(d["omega"]) == [1.0, 3.0, 3.0, 1.0]
    if T == 3:
        # ids: POTRF0 0, TRSM10 1, TRSM20 2, SYRK11 3, SYRK22 4, GEMM21 5,
        # POTRF1 6, TRSM21 7, SYRK22 8, POTRF2 9
        assert set(zip(d["src"].tolist(), d["dst"].tolist())) == {
            (0, 1), (0, 2), (1, 3), (2, 4), (1, 5), (2, 5), (3, 6), (5, 7),
            (6, 7), (4, 8), (7, 8), (8, 9)}


def test_relabelling_keeps_sizes_and_structure():
    base = gen.hpcg_row_net(8, 8, 8)
    a = gen.relabel_hypergraph(base, 2**33 + 1)
    b = gen.relabel_hypergraph(base, 2**33 + 1)
    c = gen.relabel_hypergraph(base, 12)
    for k in ("xpins", "pins", "omega"):
        assert np.array_equal(a[k], b[k])           # same seed, same input
    assert not np.array_equal(a["pins"], c["pins"])
    assert a["n"] == base["n"] and len(a["pins"]) == len(base["pins"])
    assert sorted(np.diff(a["xpins"])) == sorted(np.diff(base["xpins"]))
    for e in range(len(a["xpins"]) - 1):            # pins stay sorted
        seg = a["pins"][a["xpins"][e]:a["xpins"][e + 1]]
        assert np.all(np.diff(seg) > 0)
    assert sorted(a["omega"]) == sorted(base["omega"])
    dag = gen.tiled_cholesky_dag(8)
    r = gen.relabel_dag(dag, 99)
    assert len(r["src"]) == len(dag["src"])
