"""Multilevel DAG scheduling invariants (core/schedule/multilevel.py).

The V-cycle's contract: coarsening is acyclicity-safe and work-conserving,
schedule projection is bit-exact against a from-scratch build of the
expanded schedule (and always valid), per-level refinement never increases
the cost, the end-to-end driver is never worse than the flat heuristic
wherever both run, and at or below the coarsest size it *is* the flat
heuristic.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.hypergraph import Dag
from repro.core.frontier import price_comm_moves, price_comp_moves
from repro.core.schedule import (BspInstance, MultilevelScheduleOptions,
                                 Schedule, baseline_schedule,
                                 best_replicated_schedule, bspg_schedule,
                                 basic_heuristic, derive_comms, hill_climb,
                                 multilevel_schedule)
from repro.core.schedule import multilevel as ml
from repro.core.schedule.list_sched import comp_rebalance_pass
from repro.core.schedule.replication import replica_prune_pass
from repro.datagen import (large_psdd_dag, large_sptrsv_dag, psdd_dag,
                           sptrsv_dag)


def random_dag(rng, n=None, weighted=True):
    n = n or int(rng.integers(10, 40))
    edges = []
    for v in range(1, n):
        for u in rng.choice(v, size=min(int(rng.integers(1, 4)), v),
                            replace=False):
            edges.append((int(u), v))
    omega = rng.integers(1, 4, size=n).astype(float) if weighted else None
    mu = rng.integers(1, 4, size=n).astype(float) if weighted else None
    return Dag(n=n, edge_list=edges, omega=omega, mu=mu)


def random_schedule(rng, dag, P=None, g=4.0, L=5.0):
    P = P or int(rng.integers(2, 5))
    inst = BspInstance(dag, P=P, g=g, L=L)
    sched = hill_climb(bspg_schedule(inst, seed=int(rng.integers(100))),
                       seed=0)
    return basic_heuristic(sched)  # adds replicas: exercises replica paths


# ------------------------------------------------------------- contraction

@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_property_contraction_invariants(seed):
    """Both clustering rules produce acyclic contractions (validated by
    ``Dag.contract`` itself) that conserve work, respect the cluster cap,
    and carry exactly the boundary mu and the image of the cross edges."""
    rng = np.random.default_rng(seed)
    dag = random_dag(rng)
    cap = float(dag.omega.sum()) / 3
    for kind in ("funnel", "level"):
        if kind == "funnel":
            cmap, nc = ml.funnel_clustering(dag, cap)
        else:
            lvl = np.asarray(ml.dag_levels(dag), dtype=np.int64)
            cmap, nc = ml.same_level_matching(dag, lvl, cap, rng)
        assert nc <= dag.n and np.all((cmap >= 0) & (cmap < nc))
        coarse = dag.contract(cmap, nc)  # raises on a cyclic contraction
        assert abs(coarse.omega.sum() - dag.omega.sum()) < 1e-9
        want_omega = np.zeros(nc)
        np.add.at(want_omega, cmap, dag.omega)
        assert np.allclose(coarse.omega, want_omega)
        # cluster work cap: multi-member clusters stay under the cap
        sizes = np.bincount(cmap, minlength=nc)
        assert np.all(want_omega[sizes >= 2] <= cap + 1e-9)
        # coarse edge set is exactly the image of the cross edges
        want_edges = {(int(cmap[u]), int(cmap[v]))
                      for (u, v) in dag.edge_list if cmap[u] != cmap[v]}
        assert set(coarse.edge_list) == want_edges
        # boundary mu: sum over members with an external child
        want_mu = np.zeros(nc)
        for v in range(dag.n):
            if any(cmap[c] != cmap[v] for c in dag.children[v]):
                want_mu[cmap[v]] += dag.mu[v]
        assert np.allclose(coarse.mu, want_mu)


def test_contract_raises_on_cyclic_cmap():
    """Merging across a reconvergent path must be rejected eagerly."""
    dag = Dag(n=4, edge_list=[(0, 1), (0, 2), (1, 3), (2, 3)])
    with pytest.raises(ValueError):
        dag.contract(np.array([0, 1, 2, 0]), 3)


def test_funnel_clusters_are_unique_parent_trees():
    """Every non-root member of a funnel cluster has in-degree 1 with its
    unique parent inside the same cluster (the acyclicity argument)."""
    rng = np.random.default_rng(7)
    dag = random_dag(rng, n=60)
    cmap, nc = ml.funnel_clustering(dag, float(dag.omega.sum()))
    roots = {}
    for v in range(dag.n):
        roots.setdefault(int(cmap[v]), v)  # first member in id order
    for v in range(dag.n):
        if roots[int(cmap[v])] == v:
            continue
        assert len(dag.parents[v]) == 1
        assert cmap[dag.parents[v][0]] == cmap[v]


# -------------------------------------------------------------- projection

def _cluster_and_schedule(rng, dag, P=None):
    cap = max(2.0, float(dag.omega.sum()) / 6)
    if rng.random() < 0.5:
        cmap, nc = ml.funnel_clustering(dag, cap)
    else:
        lvl = np.asarray(ml.dag_levels(dag), dtype=np.int64)
        cmap, nc = ml.same_level_matching(dag, lvl, cap, rng)
    coarse = dag.contract(cmap, nc)
    csched = random_schedule(rng, coarse, P=P)
    return cmap, csched


@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_property_projection_bit_exact(seed):
    """``Schedule.from_projection`` must equal a from-scratch build of the
    expanded schedule -- same assign/comms, bit-equal rows, step costs and
    total (integer weights) -- and be valid whenever the coarse one is."""
    rng = np.random.default_rng(seed)
    dag = random_dag(rng)
    cmap, csched = _cluster_and_schedule(rng, dag)
    assert csched.validate() == []
    inst = BspInstance(dag, csched.inst.P, csched.inst.g, csched.inst.L)
    proj = Schedule.from_projection(inst, csched, cmap)
    proj.check()
    assert proj.validate() == []
    # from-scratch comparator: same expansion through primitive ops
    naive = Schedule(inst, csched.S)
    cl_items = [sorted(a.items()) for a in csched.assign]
    for v in range(dag.n):
        for p, s in cl_items[cmap[v]]:
            naive.add_comp(v, p, s)
    derive_comms(naive)
    assert naive.assign == proj.assign
    assert naive.comms == proj.comms
    assert naive.work == proj.work
    assert naive.sent == proj.sent
    assert naive.recv == proj.recv
    assert naive._scost == proj._scost
    assert naive.current_cost() == proj.current_cost()
    # top-2 triples: equivalent (same maxima, argmax points at a maximum)
    for kind in ("work", "sent", "recv"):
        rows, tops = proj._rows_top(kind)
        _, ntops = naive._rows_top(kind)
        for s in range(proj.S):
            m1, i1, m2 = tops[s]
            assert (m1, m2) == (ntops[s][0], ntops[s][2])
            assert rows[s][i1] == m1


def test_projection_float_weights_cost_exact():
    """Float weights: rows still bit-equal (same accumulation order), the
    incrementally-maintained naive total agrees to float tolerance."""
    rng = np.random.default_rng(3)
    dag = random_dag(rng, n=35, weighted=False)
    dag.omega = rng.random(dag.n) + 0.5
    dag.mu = rng.random(dag.n) + 0.1
    cmap, csched = _cluster_and_schedule(rng, dag)
    inst = BspInstance(dag, csched.inst.P, csched.inst.g, csched.inst.L)
    proj = Schedule.from_projection(inst, csched, cmap)
    naive = Schedule(inst, csched.S)
    for v in range(dag.n):
        for p, s in sorted(csched.assign[cmap[v]].items()):
            naive.add_comp(v, p, s)
    derive_comms(naive)
    assert naive.work == proj.work and naive.sent == proj.sent
    assert naive.comms == proj.comms
    assert abs(naive.current_cost() - proj.current_cost()) < 1e-9
    assert proj.validate() == []


def test_projection_composed_cmaps_match_stepwise():
    """Skip-level projection through a composed cluster map must equal
    projecting one level at a time."""
    rng = np.random.default_rng(11)
    dag = sptrsv_dag(n=1200, band=24, seed=5)
    opts = MultilevelScheduleOptions(coarsest_n=150, cluster_cap_frac=0.05)
    levels, cmaps = ml.build_levels(dag, 4, opts, rng)
    assert len(levels) >= 3, "instance did not coarsen enough to test"
    coarse_inst = BspInstance(levels[2], 4, 4.0, 20.0)
    csched = hill_climb(bspg_schedule(coarse_inst, seed=0), seed=0)
    i1 = BspInstance(levels[1], 4, 4.0, 20.0)
    i0 = BspInstance(levels[0], 4, 4.0, 20.0)
    step = Schedule.from_projection(i1, csched, cmaps[1])
    step = Schedule.from_projection(i0, step, cmaps[0])
    direct = Schedule.from_projection(i0, csched,
                                      ml._compose_cmaps(cmaps, 0, 2))
    assert step.assign == direct.assign
    assert step.comms == direct.comms
    assert step.current_cost() == direct.current_cost()


# ------------------------------------------------- refinement move pricing

@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_property_comm_move_front_bit_equal(seed):
    """``price_comm_moves`` entries equal scalar ``delta_move_comm``."""
    rng = np.random.default_rng(seed)
    sched = random_schedule(rng, random_dag(rng))
    for (v, dst) in sorted(sched.comms)[:20]:
        ts = np.arange(sched.S)
        deltas = price_comm_moves(sched, v, dst, ts)
        for t in range(sched.S):
            want = sched.delta_move_comm(v, dst, t)
            assert deltas[t] == want, (v, dst, t)


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_property_comp_move_front_bit_equal(seed):
    """``price_comp_moves`` entries equal the scalar two-cell fold."""
    rng = np.random.default_rng(seed)
    sched = random_schedule(rng, random_dag(rng))
    dag = sched.inst.dag
    for v in range(dag.n):
        if len(sched.assign[v]) != 1:
            continue
        (p, s), = sched.assign[v].items()
        ts = np.arange(sched.S)
        deltas = price_comp_moves(sched, v, p, ts)
        om = dag.omega[v]
        for t in range(sched.S):
            if t == s:
                assert deltas[t] == 0.0
                continue
            want = sched._delta_cells([("work", s, p, -om),
                                       ("work", t, p, om)])
            assert deltas[t] == want, (v, t)


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_property_refinement_passes_safe(seed):
    """Compute re-timing and replica pruning keep schedules valid and
    never increase the cost."""
    rng = np.random.default_rng(seed)
    sched = random_schedule(rng, random_dag(rng))
    before = sched.current_cost()
    comp_rebalance_pass(sched, max_passes=2)
    replica_prune_pass(sched, max_passes=2)
    sched.check()
    assert sched.validate() == []
    assert sched.current_cost() <= before + 1e-9


# ----------------------------------------------------------------- V-cycle

def test_refinement_never_increases_cost_per_level():
    dag = sptrsv_dag(n=2500, band=32, seed=3)
    inst = BspInstance(dag, P=4, g=4.0, L=20.0)
    stats = []
    sched = multilevel_schedule(
        inst, seed=0, stats=stats,
        opts=MultilevelScheduleOptions(coarsest_n=400))
    rows = [r for r in stats if "level" in r]
    assert len(rows) >= 2, "no coarsening happened"
    for row in rows:
        assert row["cost_refined"] <= row["cost_projected"] + 1e-9
    assert sched.validate() == []
    assert abs(sched.current_cost() - sched.cost()) < 1e-9


@pytest.mark.parametrize("n,band", [(2000, 32), (3000, 32)])
def test_multilevel_not_worse_than_flat(n, band):
    """Final-cost parity (<=) against the flat path, on the pure V-cycle
    -- instances where the projection+refinement beats flat outright."""
    dag = sptrsv_dag(n=n, band=band, seed=0)
    inst = BspInstance(dag, P=8, g=4.0, L=20.0)
    flat = best_replicated_schedule(inst, seed=0)
    mlv = best_replicated_schedule(inst, seed=0, multilevel=True)
    assert mlv.validate() == []
    assert mlv.current_cost() <= flat.current_cost() + 1e-9


def test_guard_off_not_worse_on_psdd():
    """The pure V-cycle (no flat hedge, splits on) is not worse than flat
    on the psdd family that used to need the hedge."""
    dag = psdd_dag(n_leaves=500, depth=12, seed=1)
    inst = BspInstance(dag, P=8, g=4.0, L=20.0)
    flat = best_replicated_schedule(inst, seed=0)
    stats = []
    mlv = best_replicated_schedule(inst, seed=0, multilevel=True,
                                   stats=stats)
    assert all("level" in r for r in stats)
    assert mlv.validate() == []
    assert mlv.current_cost() <= flat.current_cost() + 1e-9


def test_multilevel_fallthrough_exact_equality():
    """At or below ``coarsest_n`` the driver is literally the flat path."""
    dag = sptrsv_dag(n=900, band=24, seed=0)
    inst = BspInstance(dag, P=4, g=4.0, L=20.0)
    flat = best_replicated_schedule(inst, seed=0)
    mlv = best_replicated_schedule(inst, seed=0, multilevel=True)
    assert mlv.current_cost() == flat.current_cost()
    assert mlv.assign == flat.assign
    assert mlv.comms == flat.comms


def test_multilevel_immediate_stagnation_falls_through():
    """A DAG no clustering rule can shrink (a wide antichain of isolated
    heavy fan-in stars above the fanout cap) must degenerate to flat."""
    n = 900
    hub_in = 40
    edges = []
    for h in range(n // (hub_in + 1)):
        base = h * (hub_in + 1)
        for i in range(hub_in):
            edges.append((base + i, base + hub_in))
    dag = Dag(n=n, edge_list=edges)
    inst = BspInstance(dag, P=4, g=4.0, L=20.0)
    opts = MultilevelScheduleOptions(coarsest_n=64, max_fanout=8,
                                     cluster_cap_frac=1e-9)
    flat = best_replicated_schedule(inst, seed=0)
    mlv = best_replicated_schedule(inst, seed=0, multilevel=True,
                                   ml_opts=opts)
    assert mlv.current_cost() == flat.current_cost()


# ------------------------------------------------------------- datagen knob

def test_large_sptrsv_dag_structure():
    dag = large_sptrsv_dag(20_000, band=32, seed=9)
    assert dag.n == 20_000
    assert dag.topo_order()  # acyclic
    assert all(u < v for (u, v) in dag.edge_list[:100])
    again = large_sptrsv_dag(20_000, band=32, seed=9)
    assert again.edge_list == dag.edge_list
    assert np.array_equal(dag.edge_src, np.asarray(
        [u for u, _ in dag.edge_list]))


def test_large_psdd_dag_structure():
    dag = large_psdd_dag(n_leaves=2000, depth=12, seed=4)
    assert dag.topo_order()
    assert all(u < v for (u, v) in dag.edge_list[:100])
    indeg = np.diff(dag.xpar)
    assert int(indeg[:2000].sum()) == 0          # leaves have no parents
    assert np.all(indeg[2000:] >= 1)             # every unit has inputs
    again = large_psdd_dag(n_leaves=2000, depth=12, seed=4)
    assert again.edge_list == dag.edge_list


def test_dag_from_arrays_matches_loop_constructor():
    rng = np.random.default_rng(2)
    dag = random_dag(rng, n=50)
    src = np.array([u for u, _ in dag.edge_list])
    dst = np.array([v for _, v in dag.edge_list])
    fast = Dag.from_arrays(dag.n, src, dst, omega=dag.omega, mu=dag.mu)
    # from_arrays adjacency is sorted; the loop constructor preserves
    # edge_list insertion order -- same sets, and no consumer is
    # order-sensitive (every engine path sorts or reduces over them)
    assert [sorted(x) for x in fast.parents] == \
        [sorted(x) for x in dag.parents]
    assert [sorted(x) for x in fast.children] == \
        [sorted(x) for x in dag.children]
    assert sorted(fast.edge_list) == sorted(dag.edge_list)
    assert fast.topo_order() is not None
