"""Device-resident refinement (kernels/front_pass.py).

The device pass's contract is *bit-identity*: running a whole FM or
replication sweep as one jitted device program -- one host sync per
committed move -- must reproduce the numpy frontier path's final masks,
costs and decision trajectory exactly, never approximately.  These tests
pin that contract on random integer-weight hypergraphs/DAGs and on the
shipped dataset instances, assert the sync-count bound
(``commits <= syncs <= commits + pass_scans``), exercise the Pallas
interpret-mode find path, and check the attach guards (float weights,
unassigned nodes, size floor) fall back to the host path cleanly.
"""
import contextlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.frontier import device_pass, device_windows
from repro.core.frontier.schedule_front import (price_comm_moves,
                                                price_comp_moves,
                                                price_node_moves)
from repro.core.hypergraph import Dag, Hypergraph
from repro.core.partition import PartitionState
from repro.core.partition.cost import capacity
from repro.core.partition.heuristic import (fm_refine, greedy_initial,
                                            replicate_local_search)
import repro.core.frontier as frontier
from repro.core.schedule import BspInstance, bspg_schedule, list_sched
from repro.core.schedule.list_sched import (comp_rebalance_pass, hill_climb,
                                            node_move_pass, rebalance_comms)
from repro.datagen import large_sptrsv_dag, spmv_dataset, tiny_dataset
from repro.kernels import front_pass, gain, ops


# ----------------------------------------------------------------- helpers

def int_hypergraph(rng, n=None, m=None):
    """Random hypergraph with integer weights (the device contract)."""
    n = n or int(rng.integers(8, 40))
    m = m or int(rng.integers(5, 60))
    edges = [tuple(rng.choice(n, size=int(rng.integers(2, min(6, n) + 1)),
                              replace=False)) for _ in range(m)]
    return Hypergraph(n=n, edges=edges,
                      omega=rng.integers(1, 5, size=n).astype(float),
                      mu=rng.integers(1, 6, size=m).astype(float))


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


def deep_dag(n, width, seed):
    """``width`` chains of dependencies plus random earlier parents: a deep
    DAG whose flat schedules hold comm windows of 12 supersteps or more."""
    rng = np.random.default_rng(seed)
    edges = set()
    for v in range(width, n):
        edges.add((v - width, v))
        for u in rng.choice(v, size=2, replace=False):
            if rng.random() < 0.5:
                edges.add((int(u), v))
    return Dag(n=n, edge_list=sorted(edges))


@contextlib.contextmanager
def small_device_floors():
    """Drop the size floors so tiny test instances take the device path."""
    saved = (front_pass.DEVICE_MIN_NODES, front_pass.DEVICE_MIN_WINDOW,
             front_pass.DEVICE_MIN_STEPS)
    front_pass.DEVICE_MIN_NODES = 1
    front_pass.DEVICE_MIN_WINDOW = 1
    front_pass.DEVICE_MIN_STEPS = 1
    try:
        yield
    finally:
        (front_pass.DEVICE_MIN_NODES, front_pass.DEVICE_MIN_WINDOW,
         front_pass.DEVICE_MIN_STEPS) = saved


def relabel(hg, seed):
    """``hg`` with its nodes renamed by a seeded permutation: the same
    shapes (n, E, degrees), other node ids."""
    perm = np.random.default_rng(seed).permutation(hg.n)
    omega = np.empty_like(hg.omega)
    omega[perm] = hg.omega
    return Hypergraph(n=hg.n, edges=[tuple(int(perm[v]) for v in e)
                                     for e in hg.edges],
                      omega=omega, mu=hg.mu)


def clear_program_caches():
    """Empty the process-wide program caches, so that a test counts its
    own misses whatever an earlier test in this process built."""
    for build in front_pass._PROGRAM_CACHES.values():
        build.cache_clear()


def sched_snap(s):
    """Full observable schedule state: cost, comm plan, assignment rows."""
    return (s.current_cost(), sorted(s.comms.items()),
            [sorted(a.items()) for a in s.assign])


def _fm_pair(hg, P, eps, seed):
    m0 = greedy_initial(hg, P, eps, np.random.default_rng(seed + 1000))
    ma, mb = m0.copy(), m0.copy()
    sta = PartitionState(hg, P, masks=ma)
    stb = PartitionState(hg, P, masks=mb)
    fm_refine(hg, ma, P, eps, np.random.default_rng(seed), state=sta,
              frontier="numpy")
    fm_refine(hg, mb, P, eps, np.random.default_rng(seed), state=stb,
              frontier="jax")
    assert stb.device is None          # detached even on the device path
    return m0, (ma, sta), (mb, stb)


# ------------------------------------------------- partition bit-identity

@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_property_fm_device_bit_identical(seed):
    """Whole-pass device FM == numpy frontier FM: masks and cost exact."""
    rng = np.random.default_rng(seed)
    hg = int_hypergraph(rng)
    P = int(rng.integers(2, 6))
    with small_device_floors():
        _, (ma, sta), (mb, stb) = _fm_pair(hg, P, 0.3, seed)
    assert np.array_equal(ma, mb)
    assert sta.cost == stb.cost


@given(st.integers(0, 10_000), st.sampled_from([None, 2]))
@settings(max_examples=8, deadline=None)
def test_property_rep_device_bit_identical(seed, max_replicas):
    """Device replication sweep (add/drop with resume protocol) == numpy,
    including the host edge-guided phase reaching the device via the
    engine apply/undo hook."""
    rng = np.random.default_rng(seed)
    hg = int_hypergraph(rng)
    P = int(rng.integers(2, 6))
    m0 = greedy_initial(hg, P, 0.3, np.random.default_rng(seed + 1000))
    with small_device_floors():
        ra = replicate_local_search(hg, m0.copy(), P, 0.3, seed=seed,
                                    max_replicas=max_replicas,
                                    frontier="numpy")
        rb = replicate_local_search(hg, m0.copy(), P, 0.3, seed=seed,
                                    max_replicas=max_replicas,
                                    frontier="jax")
    assert np.array_equal(ra.masks, rb.masks)
    assert ra.cost == rb.cost


def test_shipped_spmv_instance_bit_identical():
    """The device path reproduces the numpy path on a real row-net SpMV
    hypergraph, not just synthetic randoms."""
    hg = spmv_dataset("rn", count=1)[0]
    with small_device_floors():
        _, (ma, sta), (mb, stb) = _fm_pair(hg, 4, 0.3, seed=7)
        assert np.array_equal(ma, mb) and sta.cost == stb.cost
        ra = replicate_local_search(hg, ma.copy(), 4, 0.3, seed=7,
                                    frontier="numpy")
        rb = replicate_local_search(hg, ma.copy(), 4, 0.3, seed=7,
                                    frontier="jax")
    assert np.array_equal(ra.masks, rb.masks) and ra.cost == rb.cost


def test_device_mirror_tracks_engine_hook():
    """Host-engine apply/undo keep the device uncov/lambda/mask buffers in
    lockstep without a refresh (the PR's engine hook)."""
    rng = np.random.default_rng(11)
    hg = int_hypergraph(rng, n=30, m=50)
    m0 = greedy_initial(hg, 4, 0.3, np.random.default_rng(11))
    st_ = PartitionState(hg, 4, masks=m0.copy())
    cap = capacity(hg, 4, 0.3) + 1e-9
    with small_device_floors():
        dev = device_pass(st_, cap, backend="jax")
    assert dev is not None
    try:
        for v in range(0, 12):
            st_.apply(v, int(st_.masks[v]) | (1 << (v % 4)))
            if v % 3 == 0:
                st_.undo()
            else:
                st_.commit()
        # hook mutations are queued for find-fusion; flush forces them
        # down so the buffers can be inspected without a find
        assert len(dev._pending) > 0
        dev.flush()
        assert dev.apply_dispatches > 0 and not dev._pending
        got_uncov = np.asarray(dev._uncov)[:dev.E]
        assert np.array_equal(got_uncov, st_.uncov[:, dev.colmap])
        assert np.array_equal(np.asarray(dev._masks)[:hg.n], st_.masks)
    finally:
        dev.detach()
    assert st_.device is None


def test_sync_accounting_bound():
    """At most one host sync per committed move plus one terminal dry scan
    per pass: commits <= syncs <= commits + pass_scans."""
    rng = np.random.default_rng(7)
    hg = int_hypergraph(rng, n=40, m=80)
    m0 = greedy_initial(hg, 4, 0.3, np.random.default_rng(77))
    cap = capacity(hg, 4, 0.3) + 1e-9
    with small_device_floors():
        st_ = PartitionState(hg, 4, masks=m0.copy())
        dev = device_pass(st_, cap, backend="jax")
        assert dev is not None
        try:
            dev.run_fm(np.random.default_rng(7), 6)
        finally:
            dev.detach()
        assert dev.syncs > 0 and dev.commits > 0
        assert dev.commits <= dev.syncs <= dev.commits + dev.pass_scans
        # fused dispatch: a pure FM sweep never pays a standalone apply --
        # every committed move rides the next find program
        assert dev.apply_dispatches == 0
        # replication sweeps obey the same bound
        st2 = PartitionState(hg, 4, masks=m0.copy())
        dev2 = device_pass(st2, cap, backend="jax")
        try:
            for p in range(4):
                if not dev2.rep_pass(np.random.default_rng(p).permutation(
                        hg.n), None):
                    break
        finally:
            dev2.detach()
        assert dev2.commits <= dev2.syncs <= dev2.commits + dev2.pass_scans
        assert dev2.apply_dispatches == 0   # pure node sweeps fuse too


def test_pallas_interpret_find_identity():
    """The find program's Pallas pricing path (interpret mode on CPU) is
    decision-identical to the jnp path and the numpy frontier."""
    ops.force("pallas")
    try:
        with small_device_floors():
            for seed in (0, 3):
                rng = np.random.default_rng(seed)
                hg = int_hypergraph(rng)
                _, (ma, sta), (mb, stb) = _fm_pair(hg, 4, 0.3, seed)
                assert np.array_equal(ma, mb) and sta.cost == stb.cost
    finally:
        ops.force(None)
    stats = gain.kernel_cache_stats()
    assert stats["dlam"]["size"] >= 1      # the fused kernel actually ran


def test_attach_guards():
    """Attach declines float weights, unassigned nodes, sub-floor sizes and
    non-jax backends -- the host path must keep working untouched."""
    rng = np.random.default_rng(3)
    hg = int_hypergraph(rng, n=30, m=40)
    m0 = greedy_initial(hg, 4, 0.3, rng)
    cap = capacity(hg, 4, 0.3) + 1e-9

    st_ = PartitionState(hg, 4, masks=m0.copy())
    assert device_pass(st_, cap, backend="numpy") is None
    assert front_pass.attach(st_, cap) is None        # below default floor

    hg_f = Hypergraph(n=hg.n, edges=hg.edges, omega=hg.omega,
                      mu=hg.mu + 0.5)                  # non-integer mu
    st_f = PartitionState(hg_f, 4, masks=m0.copy())
    with small_device_floors():
        assert device_pass(st_f, cap, backend="jax") is None

    m_un = m0.copy()
    m_un[0] = 0                                        # unassigned node
    st_u = PartitionState(hg, 4, masks=m_un)
    with small_device_floors():
        assert device_pass(st_u, cap, backend="jax") is None


@pytest.mark.parametrize("second, same_find, same_apply", [
    ("relabelled", True, True),     # same signature: both programs reused
    ("reshaped", False, False),     # other n and E
    ("pallas", False, True),        # ops.force("pallas"): other find only
])
def test_attach_reuses_cached_programs(monkeypatch, second, same_find,
                                       same_apply):
    """A second attach gets the first one's jitted find and apply objects
    exactly when its program signature matches, and both attaches stay
    decision-identical to the numpy frontier."""
    attached, real_attach = [], front_pass.attach

    def spy(state, cap):
        dev = real_attach(state, cap)
        attached.append(dev)
        return dev

    monkeypatch.setattr(front_pass, "attach", spy)
    clear_program_caches()
    hg_a = int_hypergraph(np.random.default_rng(5), n=30, m=50)
    hg_b = {"relabelled": relabel(hg_a, 1), "pallas": hg_a,
            "reshaped": int_hypergraph(np.random.default_rng(6), n=36,
                                       m=60)}[second]
    with small_device_floors():
        for i, hg in enumerate((hg_a, hg_b)):
            if i and second == "pallas":
                ops.force("pallas")
            try:
                _, (ma, sta), (mb, stb) = _fm_pair(hg, 4, 0.3, seed=5)
            finally:
                ops.force(None)
            assert np.array_equal(ma, mb) and sta.cost == stb.cost
    dev_a, dev_b = attached
    assert (dev_b._find_fm is dev_a._find_fm) == same_find
    assert (dev_b._find_rep is dev_a._find_rep) == same_find
    assert dev_b._find_fm is not dev_b._find_rep
    assert (dev_b._apply_fn is dev_a._apply_fn) == same_apply
    stats = front_pass.program_cache_stats()
    # one miss per distinct signature (fm and rep are two), a hit for
    # every later attach that reused one
    assert stats["find"]["misses"] == (2 if same_find else 4)
    assert stats["find"]["hits"] == (2 if same_find else 0)
    assert stats["apply"]["misses"] == (1 if same_apply else 2)
    assert stats["apply"]["hits"] == (1 if same_apply else 0)


def test_program_cache_stats_bounded():
    """The program caches are bounded and introspectable, in the shape of
    ``gain.kernel_cache_stats``."""
    stats = front_pass.program_cache_stats()
    assert set(stats) == {"find", "apply", "win", "node"}
    for rec in stats.values():
        assert set(rec) == {"hits", "misses", "size", "maxsize"}
        assert rec["maxsize"] == front_pass._PROGRAM_CACHE_SIZE == 64
        assert 0 <= rec["size"] <= rec["maxsize"]


# ------------------------------------------------------- schedule windows

def _price_every_window(sched):
    """Price every comm, compute and node move of ``sched`` over the whole
    superstep range through a fresh ``DeviceScheduleWindows`` and check
    each delta vector bit for bit against the numpy fronts."""
    win = device_windows(sched, "jax")
    assert win is not None
    ts = np.arange(sched.S)
    priced = 0
    for v, dst in sorted(sched.comms):
        assert np.array_equal(win.price_comm_moves(v, dst, ts),
                              price_comm_moves(sched, v, dst, ts))
        priced += 1
    for v, row in enumerate(sched.assign):
        if len(row) != 1:
            continue
        (p, _), = row.items()
        assert np.array_equal(win.price_node_moves(v),
                              price_node_moves(sched, v))
        if (v, p) not in sched.comms:
            assert np.array_equal(win.price_comp_moves(v, p, ts),
                                  price_comp_moves(sched, v, p, ts))
        priced += 1
    assert priced and win.syncs > 0


def test_window_programs_keyed_on_L_and_g():
    """Pricers of instances with equal ``L`` and ``g`` share their window
    and node programs; another ``g`` gets programs of its own, never a
    stale one built for the other parameters."""
    clear_program_caches()
    dag = random_dag(60, 4)
    with small_device_floors():
        s1 = bspg_schedule(BspInstance(dag=dag, P=4, g=2.0, L=4.0), seed=4)
        _price_every_window(s1)
        first = front_pass.program_cache_stats()
        s2 = bspg_schedule(BspInstance(dag=dag, P=4, g=2.0, L=4.0), seed=4)
        assert s2.S == s1.S
        _price_every_window(s2)
        shared = front_pass.program_cache_stats()
        s4 = bspg_schedule(BspInstance(dag=dag, P=4, g=4.0, L=4.0), seed=4)
        _price_every_window(s4)
        other = front_pass.program_cache_stats()
    for kind in ("win", "node"):
        assert first[kind]["misses"] >= 1
        assert shared[kind]["misses"] == first[kind]["misses"]
        assert shared[kind]["hits"] > first[kind]["hits"]
        assert other[kind]["misses"] > shared[kind]["misses"]
    Kp, Wp = front_pass.DEVICE_COMM_BATCH, front_pass._pow2(s1.S)
    assert (front_pass._win_program("comm", Kp, Wp, 4, 2)
            is not front_pass._win_program("comm", Kp, Wp, 4, 4))


def test_schedule_passes_bit_identical():
    """rebalance/comp/node passes and full hill_climb produce the same
    schedule through the device window pricers as through numpy."""
    with small_device_floors():
        for seed in range(4):
            n = int(np.random.default_rng(seed).integers(40, 110))
            P = int(np.random.default_rng(seed + 1).integers(2, 6))
            inst = BspInstance(dag=random_dag(n, seed), P=P, g=2.0, L=4.0)
            sa = bspg_schedule(inst, seed=seed)
            sb = bspg_schedule(inst, seed=seed)
            assert device_windows(sb, "jax") is not None
            hill_climb(sa, seed=seed)
            hill_climb(sb, seed=seed, backend="jax")
            assert sched_snap(sa) == sched_snap(sb)

        inst = BspInstance(dag=random_dag(80, 5), P=4, g=2.0, L=4.0)
        sa = bspg_schedule(inst, seed=5)
        sb = bspg_schedule(inst, seed=5)
        rebalance_comms(sa)
        rebalance_comms(sb, backend="jax")
        assert sched_snap(sa) == sched_snap(sb)
        node_move_pass(sa)
        node_move_pass(sb, backend="jax")
        assert sched_snap(sa) == sched_snap(sb)
        comp_rebalance_pass(sa)
        comp_rebalance_pass(sb, backend="jax")
        assert sched_snap(sa) == sched_snap(sb)


def test_shipped_tiny_dag_bit_identical():
    """Device-window hill_climb reproduces numpy on a shipped tiny DAG."""
    dag = tiny_dataset()[0]
    inst = BspInstance(dag=dag, P=4, g=2.0, L=4.0)
    with small_device_floors():
        sa = bspg_schedule(inst, seed=0)
        sb = bspg_schedule(inst, seed=0)
        hill_climb(sa, seed=0)
        hill_climb(sb, seed=0, backend="jax")
    assert sched_snap(sa) == sched_snap(sb)


def test_price_comm_batch_matches_numpy():
    """Every row of one batched sync equals the numpy front bit for bit:
    windows of mixed lengths, a batch shorter than ``Kp`` and a full one,
    windows that end at the last superstep and windows that hold the
    comm's own superstep (priced 0 there)."""
    sched = bspg_schedule(BspInstance(large_sptrsv_dag(n=600, seed=0), P=4,
                                      g=2, L=4), seed=0)
    S = sched.S
    win = device_windows(sched, "jax")
    items = []
    for k, (v, dst) in enumerate(sorted(sched.comms)):
        _, s = sched.comms[(v, dst)]
        lo, W = [(0, S),                          # whole range
                 (s, 1),                          # the own superstep alone
                 (S - 1, 1),                      # the last superstep alone
                 (max(s - 3, 0), min(7, S - max(s - 3, 0))),
                 (s + 1, S - s - 1)][k % 5]       # after s, to the end
        if W >= 1:
            items.append((v, dst, lo, W))
    K = front_pass.DEVICE_COMM_BATCH
    assert len(items) > K and len({W for *_, W in items}) > 3
    assert any(lo <= sched.comms[(v, dst)][1] < lo + W
               for v, dst, lo, W in items)
    for batch in (items[:5], items[5:5 + K]):
        syncs = win.syncs
        rows = win.price_comm_batch(batch)
        assert win.syncs == syncs + 1
        for (v, dst, lo, W), row in zip(batch, rows, strict=True):
            assert np.array_equal(
                row, price_comm_moves(sched, v, dst, np.arange(lo, lo + W)))
    assert win.windows == 5 + K and win.discarded == 0


def _lookahead_instances():
    """The random DAGs of ``test_schedule_passes_bit_identical`` and the
    shipped tiny DAG, each with its seed."""
    for seed in range(4):
        n = int(np.random.default_rng(seed).integers(40, 110))
        P = int(np.random.default_rng(seed + 1).integers(2, 6))
        yield BspInstance(dag=random_dag(n, seed), P=P, g=2.0, L=4.0), seed
    yield BspInstance(dag=random_dag(80, 5), P=4, g=2.0, L=4.0), 5
    yield BspInstance(dag=tiny_dataset()[0], P=4, g=2.0, L=4.0), 0
    yield BspInstance(dag=deep_dag(160, 4, 1), P=4, g=2.0, L=4.0), 1


@pytest.mark.parametrize("batch", [1, 2, None])
def test_rebalance_comms_lookahead_bit_identical(monkeypatch, batch):
    """``rebalance_comms`` on the device path, whose syncs price the
    visited comm window with the next ones, ends where numpy ends.  The
    random DAGs' flat schedules hold no window of the default 12
    supersteps, so the floor is lowered: every window of 2 or more prices
    on the device."""
    if batch is not None:
        monkeypatch.setattr(front_pass, "DEVICE_COMM_BATCH", batch)
    monkeypatch.setattr(list_sched, "_COMM_FRONT_MIN_WINDOW", 2)
    t0 = dict(front_pass.SCHEDULE_TOTALS)
    for inst, seed in _lookahead_instances():
        sa = bspg_schedule(inst, seed=seed)
        sb = bspg_schedule(inst, seed=seed)
        rebalance_comms(sa)
        rebalance_comms(sb, backend="jax")
        assert sched_snap(sa) == sched_snap(sb)
    d = {k: front_pass.SCHEDULE_TOTALS[k] - t0[k] for k in t0}
    assert d["syncs"] > 0
    if front_pass.DEVICE_COMM_BATCH == 1:
        assert d["windows"] == d["syncs"] and d["discarded"] == 0
    else:
        assert d["windows"] > d["syncs"] and d["discarded"] > 0


@pytest.mark.parametrize("n, width, seed", [(120, 4, 0), (240, 6, 3)])
def test_comm_lookahead_sync_accounting(monkeypatch, n, width, seed):
    """Rows used equal the numpy path's long-window visits; syncs stay
    within ``moves + passes + ceil(windows / K)`` and under the windows."""
    inst = BspInstance(deep_dag(n, width, seed), P=4, g=2.0, L=4.0)
    sa, sb = bspg_schedule(inst, seed=0), bspg_schedule(inst, seed=0)
    visits, moves = [], []
    price = frontier.price_comm_moves
    monkeypatch.setattr(frontier, "price_comm_moves",
                        lambda *a: visits.append(a[1:3]) or price(*a))
    rebalance_comms(sa, max_passes=4)
    move = sb.move_comm
    monkeypatch.setattr(sb, "move_comm",
                        lambda *a: moves.append(a) or move(*a))
    t0 = dict(front_pass.SCHEDULE_TOTALS)
    rebalance_comms(sb, max_passes=4, backend="jax")
    d = {k: front_pass.SCHEDULE_TOTALS[k] - t0[k] for k in t0}
    assert sched_snap(sa) == sched_snap(sb)
    K = front_pass.DEVICE_COMM_BATCH
    assert moves and d["discarded"] > 0
    assert d["windows"] - d["discarded"] == len(visits)
    assert d["syncs"] <= len(moves) + 4 + -(-d["windows"] // K)
    assert d["syncs"] < d["windows"]


def test_schedule_float_weights_fall_back():
    """Float-weight DAGs never attach (the sequential accept rule is not an
    argmin for floats); the jax backend must silently use numpy."""
    dag = random_dag(60, 3, weighted=True)
    inst = BspInstance(dag=dag, P=4, g=2.0, L=4.0)
    sa = bspg_schedule(inst, seed=0)
    assert device_windows(sa, "jax") is None
    sb = bspg_schedule(inst, seed=0)
    with small_device_floors():
        hill_climb(sa, seed=0)
        hill_climb(sb, seed=0, backend="jax")
    assert sched_snap(sa) == sched_snap(sb)


# ------------------------------------------------------- kernel satellites

def test_front_dlam_matches_oracle():
    """The fused Pallas delta kernel == the straight-line numpy pricing."""
    import jax.numpy as jnp
    rng = np.random.default_rng(9)
    R, M = 512, 128
    rows = (rng.integers(0, 4, size=(R, M)) > 0).astype(np.int32)
    pc = np.full(M, gain._NO_COVER, dtype=np.int32)
    pc[1:16] = rng.integers(1, 6, size=15)
    lam_old = rng.integers(0, 5, size=R).astype(np.int32)
    got = np.asarray(front_dlam_interp(jnp.asarray(rows), jnp.asarray(pc),
                                       jnp.asarray(lam_old)))
    lam_new = np.where(rows == 0, pc[None, :], gain._NO_COVER).min(axis=1)
    want = np.maximum(lam_new - 1, 0) - np.maximum(lam_old - 1, 0)
    assert np.array_equal(got, want)


def front_dlam_interp(rows, pc, lam_old):
    return gain.front_dlam(rows, pc, lam_old, interpret=True)


def test_padded_rows_reuses_and_reonese():
    """The jnp fallback's pad buffer is reused across fronts and stale rows
    from a larger previous front are re-onesed (the sentinel)."""
    M = 16
    a = np.full((3, M), 5, dtype=np.int32)
    out_a = gain._padded_rows(a, 8)
    assert out_a.shape == (8, M)
    assert np.all(out_a[:3] == 5) and np.all(out_a[3:] == 1)
    b = np.full((1, M), 7, dtype=np.int32)
    out_b = gain._padded_rows(b, 8)
    assert out_b is not None and out_b.base is out_a.base  # same buffer
    assert np.all(out_b[0] == 7)
    assert np.all(out_b[1:] == 1)                          # stale re-onesed


def test_kernel_cache_stats_bounded():
    """Per-shape jitted-call caches are bounded and introspectable."""
    stats = gain.kernel_cache_stats()
    assert set(stats) == {"pallas", "dlam"}
    for rec in stats.values():
        assert rec["maxsize"] == gain._PALLAS_CACHE_SIZE == 64
        assert 0 <= rec["size"] <= rec["maxsize"]
