"""Frontier-pricing layer invariants (core/frontier/).

The whole contract of the layer is *bit-equality with the scalar engine
deltas*: pricing a candidate as part of an arbitrary front must produce
exactly the float the engine's per-node ``delta_masks`` /
``delta_node_move`` would produce, on every backend -- otherwise batched
heuristic passes could drift off the scalar search trajectory.  These
tests pin that, the output-sensitive ``GainCache`` (consistency with
brute-force best gain after arbitrary apply/undo/refresh interleavings),
the SR front's pure pricing against the transactional trial, and the
explicit tie-breaking rule (ties go to the lowest processor id).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.frontier import (GainCache, add_replica_candidates,
                                 move_candidates, node_move_targets,
                                 price_mask_front, price_node_moves,
                                 price_superstep_replication, sr_front)
from repro.core.hypergraph import Dag, Hypergraph
from repro.core.partition import (PartitionState, partition_heuristic,
                                  partition_with_replication,
                                  replicate_local_search)
from repro.core.partition.reference import (partition_heuristic_reference,
                                            replicate_local_search_reference)
from repro.core.schedule import BspInstance, bspg_schedule
from repro.core.schedule.engine import EPS


def random_hypergraph(rng, n=None, m=None):
    n = n or int(rng.integers(5, 30))
    m = m or int(rng.integers(3, 50))
    edges = [tuple(rng.choice(n, size=int(rng.integers(2, min(6, n) + 1)),
                              replace=False)) for _ in range(m)]
    return Hypergraph(n=n, edges=edges, omega=rng.random(n) + 0.5,
                      mu=rng.random(m) + 0.1)


def random_dag(n, seed, fanin=3, p_edge=0.5, n_src=8, weighted=False):
    rng = np.random.default_rng(seed)
    edges = []
    for v in range(n_src, n):
        for u in rng.choice(v, size=min(fanin, v), replace=False):
            if rng.random() < p_edge:
                edges.append((int(u), v))
    omega = rng.uniform(0.5, 4.0, size=n) if weighted else None
    mu = rng.uniform(0.5, 3.0, size=n) if weighted else None
    return Dag(n=n, edge_list=edges, omega=omega, mu=mu)


# ---------------------------------------------------------- partition front

@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_property_front_equals_per_node_delta_masks(seed):
    """A ragged multi-node front must reproduce per-node ``delta_masks``
    bit-for-bit."""
    rng = np.random.default_rng(seed)
    hg = random_hypergraph(rng)
    P = int(rng.integers(2, 5))
    masks = rng.integers(1, 1 << P, size=hg.n)
    state = PartitionState(hg, P, masks=masks)
    vs = np.sort(rng.choice(hg.n, size=int(rng.integers(1, hg.n + 1)),
                            replace=False))
    for builder in (move_candidates, add_replica_candidates):
        cands, xcand = builder(state, vs)
        want = np.concatenate(
            [state.delta_masks(int(v), cands[xcand[i]:xcand[i + 1]])
             for i, v in enumerate(vs)]) if len(cands) else np.zeros(0)
        got = price_mask_front(state, vs, cands, xcand)
        assert np.array_equal(got, want), builder.__name__


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_property_gain_cache_consistent_after_mutations(seed):
    """After arbitrary apply/undo sequences with adjacency invalidation,
    every cache read must equal a fresh engine pricing, and the cached
    best gain must match brute force over the candidate set."""
    rng = np.random.default_rng(seed)
    hg = random_hypergraph(rng)
    P = int(rng.integers(2, 5))
    masks = rng.integers(1, 1 << P, size=hg.n)
    state = PartitionState(hg, P, masks=masks)
    cache = GainCache(state, add_replica_candidates)
    cache.refresh_dirty()
    for _ in range(30):
        op = rng.integers(0, 4)
        v = int(rng.integers(hg.n))
        if op == 0:  # apply a random mask change
            state.apply(v, int(rng.integers(1, 1 << P)))
            state.commit()
            cache.invalidate_move(v)
        elif op == 1 and state.depth == 0:  # apply + undo = no net change
            state.apply(v, int(rng.integers(1, 1 << P)))
            state.undo()
        elif op == 2:
            cache.refresh_dirty()
        else:  # read check
            cands, deltas = cache.get(v)
            fresh = state.delta_masks(v, cands)
            assert np.array_equal(deltas, fresh)
            if len(cands):
                best = int(np.argmin(deltas))
                brute = min(range(len(cands)),
                            key=lambda j: (fresh[j], j))
                assert best == brute
    # full-front check at the end
    cache.refresh_dirty()
    for v in range(hg.n):
        cands, deltas = cache.get(v)
        assert np.array_equal(deltas, state.delta_masks(v, cands))


def test_tie_break_lowest_processor():
    """Ties go to the lowest processor id: candidates are generated in
    ascending-q order and the first minimum wins (np.argmin first hit).
    A fully symmetric instance makes every target equally good."""
    hg = Hypergraph(n=4, edges=[(0, 1), (2, 3)])
    P = 4
    state = PartitionState(hg, P, masks=np.array([1, 1, 2, 2]))
    vs = np.array([0])
    cands, xcand = move_candidates(state, vs)
    # node 0 sits on processor 0: candidates must be q = 1, 2, 3 ascending
    assert cands.tolist() == [2, 4, 8]
    deltas = price_mask_front(state, vs, cands, xcand)
    # moving 0 anywhere except to its partner's processor costs +1; ties
    # between q=2 and q=3 resolve to q=2 via first-hit argmin
    assert deltas[1] == deltas[2]
    assert int(np.argmin(deltas[1:])) == 0
    # end-to-end: the heuristic must stay deterministic across repeat runs
    rng = np.random.default_rng(0)
    hg2 = random_hypergraph(rng, n=40, m=60)
    a = partition_heuristic(hg2, 4, 0.1, seed=3)
    b = partition_heuristic(hg2, 4, 0.1, seed=3)
    assert a.cost == b.cost and np.array_equal(a.masks, b.masks)


@pytest.mark.parametrize("P", [4, 6, 8])
def test_fm_paths_identical(P):
    """The output-sensitive cached path must take the full-recompute
    oracle's decisions (same masks, not just same cost).  P = 8 prices
    through the single-pin-change bound, P <= 6 through the one-shot
    scan."""
    rng = np.random.default_rng(5)
    hg = random_hypergraph(rng, n=80, m=120)
    got = partition_heuristic(hg, P, 0.1, seed=1, frontier="numpy")
    want_masks, want_cost = partition_heuristic_reference(hg, P, 0.1, seed=1)
    assert np.array_equal(got.masks, want_masks)
    assert abs(got.cost - want_cost) < 1e-9


@pytest.mark.parametrize("max_replicas", [2, None])
def test_replicate_paths_identical(max_replicas):
    """The replication search's cached add front must take the
    full-recompute oracle's decisions, replicas included."""
    rng = np.random.default_rng(5)
    hg = random_hypergraph(rng, n=80, m=120)
    base = partition_heuristic(hg, 4, 0.1, seed=1)
    got = replicate_local_search(hg, base.masks.copy(), 4, 0.1,
                                 max_replicas=max_replicas, seed=2,
                                 frontier="numpy")
    want_masks, want_cost = replicate_local_search_reference(
        hg, base.masks.copy(), 4, 0.1, max_replicas=max_replicas, seed=2)
    assert np.array_equal(got.masks, want_masks)
    assert abs(got.cost - want_cost) < 1e-9
    assert (got.masks & (got.masks - 1)).any()  # some node is replicated


def test_unknown_frontier_raises():
    rng = np.random.default_rng(5)
    hg = random_hypergraph(rng, n=40, m=60)
    with pytest.raises(ValueError, match="frontier"):
        partition_with_replication(hg, 4, 0.1, exact_node_limit=0,
                                   frontier="off")


@pytest.mark.parametrize("P", [3, 5, 8])
def test_front_dlam_matches_oracle(P):
    """The device pass's Pallas kernel (interpret mode) on real candidate
    rows of a ``PartitionState`` -- the move front of every node, pins
    left unassigned included -- gives the cost terms of the engine's
    ``_lambda_from_rows``."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.core.partition.engine import _lambda_from_rows
    from repro.kernels import gain
    rng = np.random.default_rng(P)
    hg = random_hypergraph(rng, n=40, m=80)
    masks = rng.integers(0, 1 << P, size=hg.n)
    masks[: hg.n // 2] = 1 << rng.integers(0, P, size=hg.n // 2)
    state = PartitionState(hg, P, masks=masks)
    vs = np.arange(hg.n)
    cands, xcand = move_candidates(state, vs)
    node = np.repeat(vs, np.diff(xcand))
    pairs = [(e, c, int(state.masks[v])) for v, c in zip(node, cands)
             for e in state.inc_edges[state.xinc[v]:state.xinc[v + 1]]]
    er, cr, orow = (np.array(x, dtype=np.int64) for x in zip(*pairs))
    rows = state.uncov[er] + state._contrib[cr] - state._contrib[orow]
    lam_old = state.edge_lambda[er].astype(np.int32)
    lam_new = _lambda_from_rows(rows, state._order, state._order_pc)
    want = (np.maximum(lam_new.astype(np.int32) - 1, 0)
            - np.maximum(lam_old - 1, 0))
    # the device pass's layout: popcount-ordered columns behind subset 0,
    # padded to 128 columns (never a cover) and a pow2 of rows (dropped)
    cols = np.concatenate(([0], state._order))
    pc = np.concatenate(([gain._NO_COVER], state._order_pc))
    R, M = len(rows), len(cols)
    Rp, Mp = max(512, 1 << (R - 1).bit_length()), -(-M // 128) * 128
    rows_p = np.ones((Rp, Mp), dtype=np.int32)
    rows_p[:R, :M] = rows[:, cols]
    pc_p = np.full(Mp, gain._NO_COVER, dtype=np.int32)
    pc_p[:M] = pc
    lam_p = np.zeros(Rp, dtype=np.int32)
    lam_p[:R] = lam_old
    got = np.asarray(gain.front_dlam(jnp.asarray(rows_p), jnp.asarray(pc_p),
                                     jnp.asarray(lam_p), interpret=True))
    assert np.array_equal(got[:R], want)
    assert (want != 0).any()


# ----------------------------------------------------------- schedule front

@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_property_node_move_front_equals_delta(seed):
    """price_node_moves must equal delta_node_move bit-for-bit per target."""
    rng = np.random.default_rng(seed)
    dag = random_dag(int(rng.integers(20, 60)), seed, weighted=bool(seed % 2))
    inst = BspInstance(dag, P=int(rng.integers(2, 6)),
                       g=float(rng.integers(1, 6)), L=float(rng.integers(0, 25)))
    sched = bspg_schedule(inst, seed=seed)
    for v in range(dag.n):
        if len(sched.assign[v]) != 1:
            continue
        (p, _), = sched.assign[v].items()
        deltas = price_node_moves(sched, v)
        assert deltas[p] == 0.0
        for q in range(inst.P):
            if q != p:
                assert deltas[q] == sched.delta_node_move(v, q)


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_property_node_move_targets_mirror_guards(seed):
    """Feasibility vector == reference.try_node_move's guard conditions."""
    rng = np.random.default_rng(seed)
    dag = random_dag(int(rng.integers(20, 60)), seed)
    inst = BspInstance(dag, P=int(rng.integers(2, 6)),
                       g=2.0, L=5.0)
    sched = bspg_schedule(inst, seed=seed)
    for v in range(dag.n):
        if len(sched.assign[v]) != 1:
            continue
        (p, s), = sched.assign[v].items()
        feas = node_move_targets(sched, v)
        uses_p = sched.uses_on(v, p)
        blocked = bool(uses_p and min(uses_p) <= s)
        for q in range(inst.P):
            want = (q != p and not blocked
                    and all(sched.present_at(u, q, s)
                            for u in dag.parents[v]))
            assert feas[q] == want


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_property_sr_pricing_equals_trial(seed):
    """Pure SR pricing == the transactional trial's pre-prune cost delta,
    and the front enumeration == the scalar eligibility filter."""
    rng = np.random.default_rng(seed)
    dag = random_dag(int(rng.integers(30, 80)), seed)
    inst = BspInstance(dag, P=int(rng.integers(2, 6)),
                       g=float(rng.integers(1, 6)), L=float(rng.integers(0, 25)))
    sched = bspg_schedule(inst, seed=seed)
    for s in range(sched.S):
        seen = set()
        for (p1, p2, nodes) in sr_front(sched, s):
            seen.add((p1, p2))
            want_nodes = [v for v in sorted(sched.comp[s][p1])
                          if p2 not in sched.assign[v]
                          and sched.has_use_on(v, p2)]
            assert nodes == want_nodes
            priced = price_superstep_replication(sched, s, p1, p2, nodes)
            if priced is None:
                continue
            # replay the same mutations in a transaction and compare
            before = sched.current_cost()
            node_set = set(nodes)
            sched.begin()
            for v in nodes:
                for u in dag.parents[v]:
                    if sched.present_at(u, p2, s):
                        continue
                    if u in node_set and sched.assign[u].get(p1) == s:
                        continue
                    src = min(sched.assign[u],
                              key=lambda p: (sched.assign[u][p], p))
                    sched.add_comm(u, src, p2, s - 1)
                if (v, p2) in sched.comms and sched.comms[(v, p2)][1] >= s:
                    sched.remove_comm(v, p2)
                sched.add_comp(v, p2, s)
            actual = sched.current_cost() - before
            sched.rollback()
            assert abs(actual - priced) < 1e-9
        # pairs the front skipped must be empty candidates
        for p1 in range(inst.P):
            for p2 in range(inst.P):
                if p1 == p2 or (p1, p2) in seen:
                    continue
                assert not any(p2 not in sched.assign[v]
                               and sched.has_use_on(v, p2)
                               for v in sched.comp[s][p1])


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_property_sm_pricing_equals_trial(seed):
    """Pure SM pricing == the transactional trial's pre-prune cost delta,
    including the infeasibility verdict, for every adjacent pair."""
    from repro.core.frontier import apply_sm_mutations, price_superstep_merge
    rng = np.random.default_rng(seed)
    dag = random_dag(int(rng.integers(30, 80)), seed, weighted=bool(seed % 2))
    inst = BspInstance(dag, P=int(rng.integers(2, 6)),
                       g=float(rng.integers(1, 6)), L=float(rng.integers(0, 25)))
    sched = bspg_schedule(inst, seed=seed)
    for s in range(sched.S - 1):
        priced = price_superstep_merge(sched, s)
        before = sched.current_cost()
        snapshot_cost = sched.cost()
        sched.begin()
        ok = apply_sm_mutations(sched, s)
        actual = sched.current_cost() - before if ok else None
        sched.rollback()
        assert abs(sched.cost() - snapshot_cost) < 1e-9  # exact rollback
        if priced is None:
            assert actual is None
        else:
            assert actual is not None and abs(actual - priced) < 1e-9


def test_sm_winner_pass_never_increases_cost():
    from repro.core.schedule.replication import superstep_merge_pass
    from repro.datagen import sptrsv_dag
    dag = sptrsv_dag(n=400, band=16, seed=0)
    inst = BspInstance(dag, P=4, g=4.0, L=20.0)
    sched = bspg_schedule(inst, seed=0)
    before = sched.current_cost()
    sched, _ = superstep_merge_pass(sched)
    assert sched.current_cost() <= before + EPS
    sched.check()


def _bits(prices):
    return [None if x is None else float(x).hex() for x in prices]


@pytest.fixture
def checked_sm(monkeypatch):
    """The SM pass with its price cache watched: every round asserts that
    each price it hands the winner rule -- replayed or reused -- equals a
    fresh ``price_superstep_merge`` of the current state bit for bit, and
    records the round's front size; each cache built is kept."""
    from repro.core.frontier import MergePriceCache, price_superstep_merge
    seen = {"fronts": [], "caches": []}
    prices, init = MergePriceCache.prices, MergePriceCache.__init__

    def checked(self, sched):
        got = prices(self, sched)
        fresh = [price_superstep_merge(sched, s) for s in range(sched.S - 1)]
        assert _bits(got) == _bits(fresh)
        seen["fronts"].append(len(got))
        return got

    def kept(self):
        init(self)
        seen["caches"].append(self)

    monkeypatch.setattr(MergePriceCache, "prices", checked)
    monkeypatch.setattr(MergePriceCache, "__init__", kept)
    return seen


def _sm_lockstep_instance(which):
    from repro.datagen import sptrsv_dag
    if which == "sptrsv":
        return BspInstance(sptrsv_dag(n=800, band=32, seed=0), P=8, g=1.0,
                           L=5.0), 0
    seed = int(which.split("-")[1])
    dag = random_dag(90 + 10 * seed, seed, weighted=which.startswith("w"))
    return BspInstance(dag, P=4, g=4.0, L=20.0), seed


@pytest.mark.parametrize("which", ["random-0", "random-1", "random-2",
                                   "random-5", "weighted-1", "weighted-4",
                                   "sptrsv"])
def test_sm_winner_pass_engine_matches_oracle(checked_sm, which):
    """The SM winner rule must walk engine and oracle through identical
    trajectories: same S, comms, assignments and cost, with every price
    the cached pass reuses checked bit for bit against a fresh replay.
    The sptrsv instance commits 20+ merges in one pass, so most of its
    prices come from the cache."""
    from repro.core.schedule import reference as ref
    from repro.core.schedule.replication import superstep_merge_pass
    inst, seed = _sm_lockstep_instance(which)
    exact = not which.startswith("weighted")  # float rows sum differently
    eng = bspg_schedule(inst, seed=seed)
    orc = ref.bspg_schedule(inst, seed=seed)
    assert eng.current_cost() == (orc.current_cost() if exact else
                                  pytest.approx(orc.current_cost()))
    eng, imp_e = superstep_merge_pass(eng)
    orc, imp_o = ref.superstep_merge_pass(orc)
    commits = len(checked_sm["fronts"]) - 1
    if which == "sptrsv":
        assert commits >= 20
        assert checked_sm["caches"][0].reused > 0
    assert imp_e == imp_o == (commits > 0)
    assert eng.S == orc.S
    assert eng.comms == orc.comms
    assert [dict(a) for a in eng.assign] == [dict(a) for a in orc.assign]
    if exact:
        assert eng.current_cost() == orc.current_cost()
        eng.check()
    else:   # float rows: check()'s exact top-2 test is the engine's own
        # known float drift (test_property_delta_matches_recompute)
        assert eng.current_cost() == pytest.approx(orc.current_cost())


def test_sm_counters_cover_every_candidate_of_every_round(checked_sm):
    """``sm_priced + sm_reused`` is the sum of S - 1 over the pass's
    rounds, in the cache and in ``SCHEDULE_TOTALS``; a pass that commits
    twice or more reuses prices, and one that commits nothing reuses
    none."""
    from repro.core.schedule.replication import superstep_merge_pass
    from repro.kernels import front_pass
    inst, seed = _sm_lockstep_instance("sptrsv")
    sched = bspg_schedule(inst, seed=seed)
    for passes in range(2):
        t0 = dict(front_pass.SCHEDULE_TOTALS)
        checked_sm["fronts"].clear()
        sched, improved = superstep_merge_pass(sched)
        cache = checked_sm["caches"][-1]
        fronts = checked_sm["fronts"]
        assert cache.priced + cache.reused == sum(fronts)
        assert front_pass.SCHEDULE_TOTALS["sm_priced"] - t0["sm_priced"] \
            == cache.priced
        assert front_pass.SCHEDULE_TOTALS["sm_reused"] - t0["sm_reused"] \
            == cache.reused
        commits = len(fronts) - 1
        assert improved == (commits > 0)
        if passes == 0:
            assert commits >= 2 and cache.reused > 0
        else:   # the first pass reached the fixed point
            assert commits == 0
            assert cache.reused == 0 and cache.priced == sched.S - 1


def test_sm_commit_renumbers_kept_prices():
    """``compact`` returns the map it applied (None when it removed
    nothing); a candidate above the merged pair keeps its cached price
    under its new index, and every kept price is a fresh replay's."""
    from repro.core.frontier import (MergePriceCache, commit_superstep_merge,
                                     price_superstep_merge)
    inst, seed = _sm_lockstep_instance("sptrsv")
    sched = bspg_schedule(inst, seed=seed)
    assert sched.compact() is None
    cache = MergePriceCache()
    moved_up = 0
    while True:
        prices = cache.prices(sched)
        best = None
        for s, priced in enumerate(prices):
            if priced is not None and priced < -EPS:
                if best is None or priced < best[0]:
                    best = (priced, s)
        if best is None:
            break
        s_star, S0 = best[1], sched.S
        before = dict(cache.entries)
        cache.commit(sched, s_star)
        assert sched.S == S0 - 1
        for c, (price, _reads) in cache.entries.items():
            assert _bits([price]) == _bits([price_superstep_merge(sched, c)])
            if c > s_star:      # above the merged pair: one index down
                assert before[c + 1][0] is price
                moved_up += 1
    assert moved_up > 0
    # the map itself, on a merge the pass would not pick
    trial = sched.copy()
    s = next(s for s in range(trial.S - 1)
             if price_superstep_merge(trial, s) is not None)
    S0 = trial.S
    writes, remap = commit_superstep_merge(trial, s)
    assert writes is not None
    assert remap == {t: (t if t <= s else t - 1)
                     for t in range(S0) if t != s + 1}
    assert trial.compact() is None


@pytest.mark.parametrize("writes, remap, kept", [
    # nothing read was written, nothing removed: every entry stays
    (({9}, {99}, {(99, 0)}), None, {0: 0, 2: 2, 4: 4}),
    # a written row, value or source set drops just its readers
    (({3}, {99}, set()), None, {0: 0, 4: 4}),
    (({9}, {10}, set()), None, {2: 2, 4: 4}),
    (({9}, {99}, {(12, 1)}), None, {0: 0, 2: 2}),
    # a removed superstep renumbers the rest and drops its readers:
    # 3 goes, so 2 (read 1..3) goes and 4 moves to 3
    (({9}, {99}, set()), {0: 0, 1: 1, 2: 2, 4: 3, 5: 4}, {0: 0, 3: 4}),
    # a growth of S drops everything
    (None, None, {}),
], ids=["untouched", "row", "value", "source-set", "renumbered", "grown"])
def test_sm_commit_keeps_only_unwritten_prices(monkeypatch, writes, remap,
                                               kept):
    """``MergePriceCache.commit``'s carry rule on hand-made read and write
    sets: an entry goes when the commit wrote a row, value or source set
    it read, or removed a row it read; the rest keep their price under
    the renumbered index (``kept`` maps new index -> old)."""
    from repro.core.frontier import MergePriceCache
    import repro.core.frontier.schedule_front as sf
    monkeypatch.setattr(sf, "commit_superstep_merge",
                        lambda sched, s: (writes, remap))
    cache = MergePriceCache()
    cache.entries = {0: (-1.0, ({0, 1}, {10}, set())),
                     2: (-2.0, ({1, 2, 3}, {11}, set())),
                     4: (None, ({4, 5}, {13}, {(12, 1)}))}
    old = dict(cache.entries)
    cache.commit(None, 0)
    assert {c: e[0] for c, e in cache.entries.items()} == \
        {c: old[o][0] for c, o in kept.items()}
    for c, o in kept.items():
        want = old[o][1][0]
        if remap is not None:
            want = {remap[t] for t in want}
        assert cache.entries[c][1][0] == want


def test_sm_read_and_write_sets():
    """Every replay reads its own pair's rows; a merge transaction's undo
    records name the rows, values and source sets it wrote."""
    from repro.core.frontier.schedule_front import (_merge_writes,
                                                    _priced_merge)
    inst, seed = _sm_lockstep_instance("random-3")
    sched = bspg_schedule(inst, seed=seed)
    for s in range(sched.S - 1):
        rows, values, srcs = _priced_merge(sched, s, None)[1]
        assert {s, s + 1} <= rows <= {s - 1, s, s + 1}
    # merging an empty superstep moves no cell, and still reads its pair
    from repro.core.schedule import Schedule
    bare = Schedule(BspInstance(Dag(n=2, edge_list=[]), P=2), 3)
    bare.add_comp(0, 0, 0)
    bare.add_comp(1, 0, 2)
    assert _priced_merge(bare, 0, None) == (0.0, ({0, 1}, set(), set()))
    (v, dst), (src, t) = sorted(sched.comms.items())[0]
    u, (p, su) = next((u, next(iter(a.items())))
                      for u, a in enumerate(sched.assign) if u != v)
    sched.begin()
    sched.move_comm(v, dst, t + 1)
    sched.remove_comp(u, p)
    writes = _merge_writes(sched.frame_records())
    sched.rollback()
    assert writes == ({t, t + 1, su}, {v, u}, {(v, src)})


def test_node_move_pass_paths_identical():
    """hill_climb's node-move fronts must produce the full-recompute
    oracle's schedules."""
    from repro.core.schedule import hill_climb
    from repro.core.schedule import reference as ref
    for seed in (0, 1, 2):
        dag = random_dag(120, seed)
        inst = BspInstance(dag, P=4, g=4.0, L=20.0)
        eng = hill_climb(bspg_schedule(inst, seed=seed), seed=seed)
        orc = ref.hill_climb(ref.bspg_schedule(inst, seed=seed), seed=seed)
        assert eng.current_cost() == orc.current_cost()
        assert eng.comms == orc.comms
        assert [dict(a) for a in eng.assign] == [dict(a) for a in orc.assign]


def test_sr_winner_improves_and_stays_valid():
    """The winner-rule SR pass must only ever lower the cost and keep the
    schedule valid on a real dataset instance."""
    from repro.core.schedule import advanced_heuristic, hill_climb
    from repro.datagen import sptrsv_dag
    dag = sptrsv_dag(n=400, band=16, seed=0)
    inst = BspInstance(dag, P=4, g=4.0, L=20.0)
    hc = hill_climb(bspg_schedule(inst, seed=0), seed=0)
    adv = advanced_heuristic(hc.copy())
    assert adv.current_cost() <= hc.current_cost() + EPS
    adv.check()
    assert not adv.validate()
