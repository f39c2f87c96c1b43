"""Process-parallel shared-memory V-cycles (core/partition/parallel.py).

The parallel layer's contract has three legs, each pinned here:

  * **Bit-identity where promised.**  Sharded heavy-pin scoring must
    reproduce the serial ``pref``/``cmap`` byte for byte at every worker
    count; chunked ``contract`` and chunked ``large_row_net`` must equal
    their one-shot forms; CSR-backed hypergraphs must behave like
    tuple-edge ones (equality, pickling, rebuild).
  * **Cost-not-worse where bit-identity is impossible.**  Sharded
    refinement reconciles through accept-only-improving replay, so the
    final cost never exceeds the starting cost, at any worker count, for
    both FM and replication -- and the reconciled state passes the
    engine's full invariant check.
  * **No leaks, both start methods.**  Shared segments are unlinked even
    when workers crash mid-task; fork and spawn pools both work (lazy CSR
    caches are dropped from pickles, attach caches rebuild per process).

Everything that needs a pool is skipped when POSIX shared memory is
unavailable (e.g. /dev/shm-less sandboxes).
"""
import os
import pathlib
import pickle
import subprocess
import sys

import numpy as np
import pytest

from repro.core.hypergraph import (Hypergraph, _collapse_ids_dict,
                                   _collapse_ids_hash)
from repro.core.partition import PartitionState
from repro.core.partition.cost import is_valid
from repro.core.partition.heuristic import (fm_refine, partition_heuristic,
                                            partition_with_replication,
                                            replicate_local_search)
from repro.core.partition.multilevel import _match_pref, heavy_pin_matching
from repro.core.partition import parallel as par
from repro.core.partition.parallel import (ParallelContext, ShmRegistry,
                                           boundary_nodes, parallel_refine,
                                           plan_shards, shm_available)
from repro.datagen.spmv import large_row_net

needs_shm = pytest.mark.skipif(not shm_available(),
                               reason="POSIX shared memory unavailable")

START_METHODS = ["fork", "spawn"]
TESTS = pathlib.Path(__file__).resolve().parent


def small_hg(n=1200, seed=1):
    return large_row_net(n, seed=seed)


# ------------------------------------------------------------ CSR plumbing

def test_from_csr_equals_tuple_edges():
    hg = small_hg()
    view = Hypergraph.from_csr(hg.n, hg.xpins, hg.pins, omega=hg.omega,
                               mu=hg.mu)
    tup = Hypergraph(n=hg.n, edges=[tuple(e) for e in hg.edges],
                     omega=hg.omega, mu=hg.mu, presorted=True)
    assert view.edges == tup.edges and tup.edges == list(view.edges)
    assert view.num_pins == tup.num_pins
    for a, b in zip(view._build_csr(), tup._build_csr()):
        assert np.array_equal(a, b)


def test_hypergraph_pickle_drops_csr_cache():
    """Fork/spawn safety: pickles never carry the lazy CSR cache (a
    10^7-pin instance would ship every pin twice), and the cache rebuilds
    bit-identically after unpickling -- for both edge representations."""
    for hg in (small_hg(), Hypergraph.from_csr(
            small_hg().n, small_hg().xpins, small_hg().pins)):
        csr0 = hg._build_csr()
        clone = pickle.loads(pickle.dumps(hg))
        assert clone._csr is None           # cache not shipped
        for a, b in zip(csr0, clone._build_csr()):
            assert np.array_equal(a, b)
        assert clone.edges == hg.edges


def test_dag_pickle_drops_lazy_caches():
    """Same fork/spawn-safety contract for Dag: the lazy CSR and topo-order
    caches are dropped from pickles and rebuild bit-identically."""
    from repro.core.hypergraph import Dag
    rng = np.random.default_rng(3)
    src = rng.integers(0, 50, size=200)
    dst = src + 1 + rng.integers(0, 10, size=200)
    keep = dst < 60
    dag = Dag.from_arrays(60, src[keep], dst[keep])
    csr0 = dag._build_csr()
    clone = pickle.loads(pickle.dumps(dag))
    assert clone._csr is None and clone._topo is None
    for a, b in zip(csr0, clone._build_csr()):
        assert np.array_equal(a, b)


def test_contract_chunked_equals_monolithic():
    hg = small_hg()
    rng = np.random.default_rng(0)
    cmap, nc = heavy_pin_matching(hg, 50.0, rng)
    full, emap_full = hg.contract(cmap, nc)
    for chunk in (64, 1000, 10**9):
        part, emap_part = hg.contract(cmap, nc, chunk_pins=chunk)
        assert part.n == full.n and len(part.edges) == len(full.edges)
        assert np.array_equal(part.xpins, full.xpins)
        assert np.array_equal(part.pins, full.pins)
        assert np.array_equal(part.mu, full.mu)
        assert np.array_equal(emap_part, emap_full)


def test_collapse_hash_equals_dict():
    """The dual-hash identical-net collapse assigns the same coarse ids as
    the byte-key dict reference, including duplicate-heavy inputs."""
    rng = np.random.default_rng(5)
    for _ in range(20):
        m = int(rng.integers(3, 40))
        pool = [tuple(sorted(rng.choice(12, size=int(rng.integers(2, 5)),
                                        replace=False)))
                for _ in range(max(2, m // 3))]
        edges = [pool[int(rng.integers(len(pool)))] for _ in range(m)]
        cp = np.concatenate([np.asarray(e, dtype=np.int64) for e in edges])
        lens = np.array([len(e) for e in edges], dtype=np.int64)
        xk = np.zeros(m + 1, dtype=np.int64)
        np.cumsum(lens, out=xk[1:])
        kept = np.arange(m, dtype=np.int64)
        got = _collapse_ids_hash(cp, xk, kept, lens)
        assert got is not None
        assert np.array_equal(got, _collapse_ids_dict(cp, xk, kept))


def test_large_row_net_chunked_and_alloc_bit_identical():
    one = large_row_net(2000, seed=4)
    chunked = large_row_net(2000, seed=4, chunk_rows=137)
    assert np.array_equal(one.xpins, chunked.xpins)
    assert np.array_equal(one.pins, chunked.pins)
    assert np.array_equal(one.omega, chunked.omega)
    with ShmRegistry() as reg:
        shm = large_row_net(2000, seed=4, chunk_rows=500, alloc=reg.alloc)
        assert np.array_equal(one.xpins, shm.xpins)
        assert np.array_equal(one.pins, shm.pins)
        # zero-copy contract: share() recognizes registry-born arrays
        arr, ref = reg.share(shm.pins)
        assert arr is shm.pins and ref.name is not None


# -------------------------------------------------------------- sharding

def test_plan_shards_partitions_node_range():
    hg = small_hg()
    for W in (1, 2, 3, 8, 10_000):
        b = plan_shards(hg, W)
        assert b[0] == 0 and b[-1] == hg.n
        assert np.all(np.diff(b) >= 0)


def test_boundary_nodes_cover_cross_shard_edges():
    hg = small_hg()
    bounds = plan_shards(hg, 4)
    bnd = set(boundary_nodes(hg, bounds).tolist())
    shard_of = np.searchsorted(bounds[1:-1], np.arange(hg.n), side="right")
    for e in range(len(hg.xpins) - 1):
        pins = hg.pins[hg.xpins[e]:hg.xpins[e + 1]]
        if len(set(shard_of[pins].tolist())) > 1:
            assert set(pins.tolist()) <= bnd


def test_match_pref_shards_bit_identical():
    """The sharding contract of the scorer, without any pool: per-range
    results concatenate into exactly the serial pref."""
    hg = small_hg()
    serial = _match_pref(hg, 24)
    for W in (2, 3, 7):
        b = plan_shards(hg, W)
        parts = [_match_pref(hg, 24, int(b[i]), int(b[i + 1]))
                 for i in range(W) if b[i + 1] > b[i]]
        assert np.array_equal(np.concatenate(parts), serial)


@needs_shm
@pytest.mark.parametrize("W", [1, 2, 4])
def test_pooled_matching_cmap_bit_identical(W):
    hg = small_hg()
    with ParallelContext(W, min_nodes=64) as ctx:
        cm_p, nc_p = heavy_pin_matching(hg, 50.0,
                                        np.random.default_rng(7), ctx=ctx)
        assert not ctx.failed
    cm_s, nc_s = heavy_pin_matching(hg, 50.0, np.random.default_rng(7))
    assert nc_p == nc_s
    assert np.array_equal(cm_p, cm_s)


# ------------------------------------------------- restricted refinement

def test_nodes_restriction_confines_moves():
    """fm_refine/replicate_local_search with ``nodes=`` never touch masks
    outside the allowed set (the worker-shard discipline)."""
    hg = small_hg()
    res = partition_heuristic(hg, 4, 0.1, seed=0)
    allowed = np.arange(0, hg.n // 3, dtype=np.int64)
    outside = np.ones(hg.n, dtype=bool)
    outside[allowed] = False

    st = PartitionState(hg, 4, masks=res.masks.copy())
    fm_refine(hg, st.masks, 4, 0.1, np.random.default_rng(1), passes=2,
              state=st, frontier="numpy", nodes=allowed)
    assert np.array_equal(st.masks[outside], res.masks[outside])
    assert st.cost <= res.cost + 1e-9

    st2 = PartitionState(hg, 4, masks=res.masks.copy())
    replicate_local_search(hg, st2.masks, 4, 0.1, max_passes=2, seed=1,
                           frontier="numpy", state=st2, nodes=allowed)
    assert np.array_equal(st2.masks[outside], res.masks[outside])
    assert st2.cost <= res.cost + 1e-9


@needs_shm
@pytest.mark.parametrize("kind", ["fm", "rep"])
@pytest.mark.parametrize("W", [1, 2, 4])
def test_parallel_refine_cost_not_worse(kind, W):
    """Reconciled sharded refinement never worsens cost and leaves a state
    that passes the engine's full invariant check -- W = 1 exercises the
    serial-fallback leg of the same entry point."""
    hg = small_hg()
    res = partition_heuristic(hg, 4, 0.1, seed=0)
    st = PartitionState(hg, 4, masks=res.masks.copy())
    c0 = st.cost
    with ParallelContext(W, min_nodes=64) as ctx:
        stats = parallel_refine(hg, st, 4, 0.1, ctx, kind, 2, seed=3)
        assert not ctx.failed
    assert st.cost <= c0 + 1e-9
    st.check()
    assert is_valid(hg, st.masks, 4, 0.1,
                    max_replicas=1 if kind == "fm" else None)
    if W > 1:
        assert stats["workers"] == W and not stats["serial_fallback"]


def _start_method_run(method):
    """Replication refine + pooled matching through one ``method`` pool;
    asserts the pool ran, matching stayed bit-identical and the state is
    sound, and returns the reconciled masks."""
    hg = small_hg()
    res = partition_heuristic(hg, 4, 0.1, seed=0)
    st = PartitionState(hg, 4, masks=res.masks.copy())
    c0 = st.cost
    with ParallelContext(2, start_method=method, min_nodes=64) as ctx:
        parallel_refine(hg, st, 4, 0.1, ctx, "rep", 2, seed=3)
        # matching through the same pool: still bit-identical
        cm_p, _ = heavy_pin_matching(hg, 50.0, np.random.default_rng(7),
                                     ctx=ctx)
        assert not ctx.failed
    cm_s, _ = heavy_pin_matching(hg, 50.0, np.random.default_rng(7))
    assert np.array_equal(cm_p, cm_s)
    assert st.cost <= c0 + 1e-9
    st.check()
    return st.masks


def _fork_run_in_fresh_process(out: pathlib.Path) -> np.ndarray:
    """``_start_method_run("fork")`` in a new interpreter that never touches
    jax: a process holding a JAX backend refuses to fork its pool, and the
    test process may already hold one."""
    code = (f"import sys; sys.path.insert(0, {str(TESTS)!r}); "
            "import numpy as np, test_parallel as t; "
            f"np.save({str(out)!r}, t._start_method_run('fork'))")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(TESTS.parent / "src"), os.environ.get("PYTHONPATH", "")]))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=300)
    return np.load(out)


@needs_shm
@pytest.mark.parametrize("method", START_METHODS)
def test_both_start_methods(method, tmp_path):
    import multiprocessing as mp
    if method not in mp.get_all_start_methods():
        pytest.skip(f"{method} start method unavailable")
    if method == "fork":
        _fork_run_in_fresh_process(tmp_path / "masks.npy")
    else:
        _start_method_run(method)


@needs_shm
def test_fork_and_spawn_agree(tmp_path):
    """Same worker count, same seeds -> the two start methods commit the
    same reconciled masks (worker results do not depend on how the
    process got its memory image)."""
    import multiprocessing as mp
    if "fork" not in mp.get_all_start_methods():
        pytest.skip("fork unavailable")
    forked = _fork_run_in_fresh_process(tmp_path / "masks.npy")
    assert np.array_equal(forked, _start_method_run("spawn"))


# ----------------------------------------------------- lifecycle / safety

@needs_shm
def test_crash_cleanup_no_leaked_segments():
    """A worker dying mid-task must not leak segments: the registry owns
    them and unlinks on close regardless of worker fate."""
    from multiprocessing import shared_memory
    hg = small_hg()
    ctx = ParallelContext(2, min_nodes=64)
    ctx.export_hg(hg)
    with pytest.raises(Exception):
        ctx.run(par._crash_task, [(None,), (None,)])
    names = list(ctx.reg.created)
    assert names
    ctx.close()
    for nm in names:
        with pytest.raises(FileNotFoundError):
            seg = shared_memory.SharedMemory(name=nm)
            seg.close()


@needs_shm
def test_pool_failure_falls_back_serial():
    """After a broken pool, parallel_refine still refines (serially), warns
    that it did, and the context reports failed."""
    hg = small_hg()
    res = partition_heuristic(hg, 4, 0.1, seed=0)
    st = PartitionState(hg, 4, masks=res.masks.copy())
    c0 = st.cost
    with ParallelContext(2, min_nodes=64) as ctx:
        with pytest.raises(Exception):
            ctx.run(par._crash_task, [(None,)])
        with pytest.warns(par.SerialFallbackWarning):
            stats = parallel_refine(hg, st, 4, 0.1, ctx, "rep", 2, seed=3)
    assert stats["serial_fallback"] and ctx.failed
    assert st.cost <= c0 + 1e-9
    st.check()


@needs_shm
def test_state_usable_after_context_close():
    """adopt_state re-backs live arrays with shared segments; close() must
    hand back private copies so the state survives the context."""
    hg = small_hg()
    res = partition_heuristic(hg, 4, 0.1, seed=0)
    st = PartitionState(hg, 4, masks=res.masks.copy())
    ctx = ParallelContext(2, min_nodes=64)
    parallel_refine(hg, st, 4, 0.1, ctx, "fm", 1, seed=0)
    ctx.close()
    st.check()                       # would touch unmapped memory if stale
    st.apply(0, int(st.masks[0]))
    st.undo()


# ------------------------------------------------------------- end to end

@needs_shm
def test_end_to_end_workers(monkeypatch):
    """The public entry point with workers=2: valid masks, rep <= base,
    and the parallel path actually engaged (floor lowered)."""
    monkeypatch.setattr(par, "PARALLEL_MIN_NODES", 256)
    hg = small_hg(2000, seed=2)
    base, rep = partition_with_replication(hg, 4, 0.1, multilevel=True,
                                           workers=2, seed=0)
    assert is_valid(hg, base.masks, 4, 0.1, max_replicas=1)
    assert is_valid(hg, rep.masks, 4, 0.1)
    assert rep.cost <= base.cost + 1e-9


# ------------------------------------------- sharded scheduling coarsening

def _sched_pair_fixture(n=6000, seed=3):
    from repro.core.schedule.list_sched import dag_levels
    from repro.datagen import large_sptrsv_dag
    dag = large_sptrsv_dag(n, seed=seed)
    level = np.asarray(dag_levels(dag), dtype=np.int64)
    xch = np.zeros(dag.n + 1, dtype=np.int64)
    np.cumsum(np.bincount(dag.edge_src, minlength=dag.n), out=xch[1:])
    return dag, xch, level


def test_sched_pair_parts_shards_bit_identical():
    """The scheduling V-cycle's pair generator, without any pool: shard
    blocks (child blocks then parent blocks, shard order) concatenate into
    exactly the serial arrays."""
    from repro.core.schedule.multilevel import _pair_parts
    dag, xch, level = _sched_pair_fixture()
    mu = np.asarray(dag.mu, dtype=np.float64)
    serial = _pair_parts(xch, dag.edge_dst, dag.xpar, dag.par_arr, mu,
                         level, 16, 0, dag.n)
    for W in (2, 3, 5):
        bounds = np.linspace(0, dag.n, W + 1).astype(np.int64)
        blocks = [_pair_parts(xch, dag.edge_dst, dag.xpar, dag.par_arr, mu,
                              level, 16, int(bounds[i]), int(bounds[i + 1]))
                  for i in range(W)]
        for k in range(6):
            got = np.concatenate([b[k] for b in blocks])
            assert np.array_equal(got, serial[k]), (W, k)


@needs_shm
@pytest.mark.parametrize("W", [2, 4])
def test_pooled_same_level_matching_bit_identical(W):
    """Pool-backed scoring must yield the identical cmap for every worker
    count (the V-cycle bit-identity contract)."""
    from repro.core.schedule.multilevel import same_level_matching
    dag, xch, level = _sched_pair_fixture()
    cap = float(dag.omega.sum())
    cm_s, nc_s = same_level_matching(dag, level, cap,
                                     np.random.default_rng(5))
    with ParallelContext(W, min_nodes=64) as ctx:
        cm_p, nc_p = same_level_matching(dag, level, cap,
                                         np.random.default_rng(5), ctx=ctx)
        assert not ctx.failed
    assert nc_p == nc_s
    assert np.array_equal(cm_p, cm_s)


@needs_shm
def test_multilevel_schedule_workers_bit_identical():
    """End to end: ``multilevel_schedule(workers=2)`` equals the serial
    V-cycle exactly (sharded scoring changes wall-clock, not results)."""
    from repro.core.schedule import (BspInstance, MultilevelScheduleOptions,
                                     multilevel_schedule)
    from repro.datagen import large_sptrsv_dag
    dag = large_sptrsv_dag(5000, seed=1)
    inst = BspInstance(dag, 4, 2.0, 10.0)
    opts = MultilevelScheduleOptions(coarsest_n=512)
    s1 = multilevel_schedule(inst, opts=opts, seed=0)
    s2 = multilevel_schedule(inst, opts=opts, seed=0, workers=2)
    assert s1.current_cost() == s2.current_cost()
    assert s1.assign == s2.assign
    assert s1.comms == s2.comms
