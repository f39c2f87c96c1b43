"""Partitioning experiments (paper §7.2: Fig. 4, Tables 1, 10-12).

Replicates the paper's protocol on the synthetic dataset analogues:
non-replicating optimum (exact B&B on small instances, heuristic beyond)
vs replication (ILP/D and ILP/R semantics: capped / unlimited replicas),
cost-reduction ratio = 1 - geomean(repl/base), zero-cost cases counted
separately -- exactly the paper's metric (§7.1).

``bench_engine`` additionally tracks the incremental-gain engine's
throughput against the preserved seed implementation
(``core.partition.reference``) at instance sizes the seed could not touch;
its output lands in ``BENCH_partition.json`` via ``run.py``.
"""
from __future__ import annotations

import os
import time

import numpy as np

from repro.core.partition import (exact_partition, is_valid, partition_cost,
                                  partition_heuristic,
                                  partition_with_replication,
                                  replicate_local_search)
from repro.core.partition.reference import partition_heuristic_reference
from repro.datagen import large_row_net, moe_dataset, spmv_dataset
from repro.datagen.spmv import row_net_hypergraph, synthetic_sparse_matrix

FULL = os.environ.get("REPRO_BENCH_FULL", "0") == "1"


def _datasets(count: int):
    return {
        "spmv-fg": spmv_dataset("fg", count=count, sizes=(14, 26)),
        "spmv-rn": spmv_dataset("rn", count=count, sizes=(28, 60)),
        # paper parameters: kappa_0 = 1000, DeepSeek-like 256 experts
        "moe-8": moe_dataset("moe8", n_layers=count, kappa0=1000,
                             n_experts=256),
        "moe-2": moe_dataset("moe2", n_layers=count, kappa0=1000,
                             n_experts=256),
    }


def solve_pair(hg, P, eps, mode, exact_limit=18, time_limit=8.0, seed=0):
    """(base_cost, repl_cost, optimal?) for one instance."""
    from repro.core.partition import partition_with_replication
    if hg.n <= exact_limit:
        base = exact_partition(hg, P, eps, mode="none", time_limit=time_limit)
        ub = replicate_local_search(hg, base.masks.copy(), P, eps,
                                    max_replicas=2 if mode == "dup" else None,
                                    seed=seed)
        rep = exact_partition(hg, P, eps, mode=mode, time_limit=time_limit,
                              ub_masks=ub.masks)
        return base.cost, min(rep.cost, ub.cost), base.optimal and rep.optimal
    base, rep = partition_with_replication(hg, P, eps, mode=mode,
                                           exact_node_limit=0, seed=seed)
    return base.cost, rep.cost, False


def mean_reduction(pairs):
    """Paper metric: 1 - geomean(ratio) over instances with base > 0 and
    repl > 0; returns (reduction_pct, zero_count)."""
    ratios, zeros = [], 0
    for b, r in pairs:
        if b <= 0:
            continue
        if r <= 0:
            zeros += 1
            continue
        ratios.append(min(r / b, 1.0))
    red = (1.0 - float(np.exp(np.mean(np.log(ratios))))) * 100 if ratios else 0.0
    return red, zeros


def fig4_reductions(P=2, eps=0.025, count=None):
    """Fig. 4 analogue: per-dataset mean cost reduction."""
    count = count or (5 if FULL else 3)
    out = {}
    for name, ds in _datasets(count).items():
        pairs = []
        t0 = time.perf_counter()
        for hg in ds:
            b, r, _ = solve_pair(hg, P, eps, mode="rep")
            pairs.append((b, r))
        dt = time.perf_counter() - t0
        red, zeros = mean_reduction(pairs)
        out[name] = {"reduction_pct": red, "zeros": zeros,
                     "pairs": [(float(b), float(r)) for b, r in pairs],
                     "seconds": dt,
                     "instances_per_sec": len(ds) / dt if dt > 0 else 0.0}
    return out


def table1_eps_sweep(P=2, count=None):
    """Table 1: reductions grow with eps (P=2)."""
    count = count or (3 if FULL else 2)
    ds = _datasets(count)
    out = {}
    for eps in (0.0125, 0.025, 0.05):
        row = {}
        for name, insts in ds.items():
            pairs = [solve_pair(hg, P, eps, "rep")[:2] for hg in insts]
            red, zeros = mean_reduction(pairs)
            row[name] = {"reduction_pct": red, "zeros": zeros}
        out[f"eps={eps}"] = row
    return out


def table_forms(P=4, eps=0.05, count=None):
    """Tables 10/5-style: ILP/D vs ILP/R comparison."""
    count = count or (4 if FULL else 3)
    wins = {"same": 0, "D": 0, "R": 0}
    reductions = {"dup": [], "rep": []}
    for name, ds in _datasets(count).items():
        for hg in ds:
            b, rd, _ = solve_pair(hg, P, eps, mode="dup")
            _, rr, _ = solve_pair(hg, P, eps, mode="rep")
            if abs(rd - rr) < 1e-9:
                wins["same"] += 1
            elif rd < rr:
                wins["D"] += 1
            else:
                wins["R"] += 1
            reductions["dup"].append((b, rd))
            reductions["rep"].append((b, rr))
    out = {"wins": wins}
    for m in ("dup", "rep"):
        red, zeros = mean_reduction(reductions[m])
        out[m] = {"reduction_pct": red, "zeros": zeros}
    return out


def bench_engine(P=4, eps=0.05, seed=0):
    """Old-vs-new engine throughput at growing instance sizes.

    The seed implementation re-ran exact set cover per candidate move; the
    engine prices moves in O(degree).  The reference is only timed up to
    ``ref_limit`` nodes (beyond that a single run takes minutes -- exactly
    the scaling wall this PR removes); engine-only rows keep growing.
    Returns rows with instances/sec and best cost, plus replication results
    at the largest size.
    """
    sizes = (128, 256, 512, 1024, 2048) if FULL else (128, 256, 512, 1024)
    ref_limit = 512
    rows = []
    for n in sizes:
        nz = synthetic_sparse_matrix(n, n, seed=seed + n)
        hg = row_net_hypergraph(nz, n, name=f"spmv_rn_{n}")
        t0 = time.perf_counter()
        new = partition_heuristic(hg, P, eps, seed=seed)
        t_new = time.perf_counter() - t0
        assert is_valid(hg, new.masks, P, eps)
        row = {
            "n": hg.n, "edges": len(hg.edges), "pins": int(hg.num_pins),
            "P": P, "eps": eps,
            "engine_seconds": t_new,
            "engine_instances_per_sec": 1.0 / t_new,
            "engine_cost": float(new.cost),
        }
        if hg.n <= ref_limit:
            t0 = time.perf_counter()
            _, ref_cost = partition_heuristic_reference(hg, P, eps, seed=seed)
            t_ref = time.perf_counter() - t0
            row.update(ref_seconds=t_ref, ref_cost=float(ref_cost),
                       speedup=t_ref / t_new,
                       cost_not_worse=bool(new.cost <= ref_cost + 1e-9))
        rows.append(row)
    # replication on the largest instance: the end-to-end path at a size
    # the seed search could not finish in reasonable time
    nz = synthetic_sparse_matrix(sizes[-1], sizes[-1], seed=seed)
    hg = row_net_hypergraph(nz, sizes[-1], name="spmv_rn_large")
    t0 = time.perf_counter()
    base, rep = partition_with_replication(hg, P, eps, mode="rep",
                                           exact_node_limit=0, seed=seed)
    t_rep = time.perf_counter() - t0
    large = {"n": hg.n, "base_cost": float(base.cost),
             "rep_cost": float(rep.cost), "seconds": t_rep,
             "reduction_pct": (100.0 * (1 - rep.cost / base.cost)
                               if base.cost > 0 else 0.0)}
    return {"scale": rows, "replication_large": large}


def bench_multilevel(P=8, eps=0.05, seed=0, sizes=None, flat_limit=None):
    """Flat vs multilevel V-cycle at scale (PR 4 tentpole).

    End-to-end ``partition_with_replication`` on streaming row-net
    instances: the V-cycle path (``multilevel=True``) at every size, the
    flat path up to ``flat_limit`` (beyond it a single flat run takes
    minutes -- the scaling wall the V-cycle removes).  Wherever both run,
    the V-cycle's final cost must be at or below the flat cost
    (``cost_not_worse``); rows land in ``BENCH_partition.json`` as
    ``multilevel_scale`` via ``run.py``.
    """
    sizes = sizes or ((4096, 8192, 16384, 32768, 65536) if FULL
                      else (4096, 8192, 16384, 65536))
    flat_limit = flat_limit if flat_limit is not None else \
        (16384 if FULL else 8192)
    rows = []
    for n in sizes:
        hg = large_row_net(n, seed=seed + n)
        t0 = time.perf_counter()
        base, rep = partition_with_replication(hg, P, eps, seed=seed,
                                               multilevel=True)
        t_ml = time.perf_counter() - t0
        assert is_valid(hg, rep.masks, P, eps)
        row = {
            "n": hg.n, "edges": len(hg.edges), "pins": int(hg.num_pins),
            "P": P, "eps": eps,
            "ml_seconds": t_ml,
            "ml_base_cost": float(base.cost),
            "ml_rep_cost": float(rep.cost),
            "ml_reduction_pct": (100.0 * (1 - rep.cost / base.cost)
                                 if base.cost > 0 else 0.0),
        }
        if hg.n <= flat_limit:
            t0 = time.perf_counter()
            fbase, frep = partition_with_replication(
                hg, P, eps, exact_node_limit=0, seed=seed)
            t_flat = time.perf_counter() - t0
            row.update(flat_seconds=t_flat,
                       flat_base_cost=float(fbase.cost),
                       flat_rep_cost=float(frep.cost),
                       speedup=t_flat / t_ml,
                       cost_not_worse=bool(rep.cost <= frep.cost + 1e-9))
        rows.append(row)
    return {"scale": rows}


def bench_device_resident(P=4, eps=0.05, seed=0, sizes=None,
                          pallas_row=True):
    """Device-resident FM pass vs numpy (PR 6).

    Times one ``fm_refine`` call per variant on integer-weight row-net
    instances: the numpy frontier (the host path), the whole-pass
    device-resident program (one host sync per committed move), and -- at
    the smallest size only, interpret mode is slow off the TPU -- the
    Pallas find-pricing path (``seconds_device_pallas``, with
    ``pallas_interpret`` saying how it ran).  All variants are
    decision-identical, so a cost mismatch is a bug; host-sync counters
    come from an instrumented ``run_fm`` on the same instance and land in
    ``BENCH_partition.json`` as ``device_resident`` via ``run.py``.
    """
    from repro.kernels import front_pass, gain

    sizes = sizes or ((8192, 16384, 32768) if FULL else (4096, 8192))
    # generators trim empty rows, so instances land slightly under the
    # nominal size -- pin the attach floor below the smallest instance for
    # the duration of the bench
    floor_saved = front_pass.DEVICE_MIN_NODES
    front_pass.DEVICE_MIN_NODES = min(min(sizes) // 2, floor_saved)
    try:
        rows = _device_resident_rows(sizes, P, eps, seed, pallas_row)
    finally:
        front_pass.DEVICE_MIN_NODES = floor_saved
    return {"scale": rows, "kernel_cache": gain.kernel_cache_stats()}


def _device_resident_rows(sizes, P, eps, seed, pallas_row):
    from repro.core.partition import PartitionState
    from repro.core.partition.cost import capacity
    from repro.core.partition.heuristic import fm_refine, greedy_initial
    from repro.kernels import ops
    from repro.core.frontier import device_pass

    rows = []
    for n in sizes:
        hg = large_row_net(n, seed=seed + n)
        m0 = greedy_initial(hg, P, eps, np.random.default_rng(seed))

        def timed(frontier, warm=False):
            if warm:  # compile the jit shape family before the timed run
                st = PartitionState(hg, P, masks=m0.copy())
                fm_refine(hg, m0.copy(), P, eps, np.random.default_rng(seed),
                          state=st, frontier=frontier)
            st = PartitionState(hg, P, masks=m0.copy())
            t0 = time.perf_counter()
            fm_refine(hg, m0.copy(), P, eps, np.random.default_rng(seed),
                      state=st, frontier=frontier)
            return time.perf_counter() - t0, float(st.cost)

        t_np, c_np = timed("numpy")
        t_dev, c_dev = timed("jax", warm=True)
        assert c_np == c_dev, (n, c_np, c_dev)

        # instrumented run: host syncs per committed move
        st = PartitionState(hg, P, masks=m0.copy())
        dev = device_pass(st, capacity(hg, P, eps) + 1e-9, backend="jax")
        try:
            dev.run_fm(np.random.default_rng(seed), 6)
            counters = {"syncs": dev.syncs, "commits": dev.commits,
                        "pass_scans": dev.pass_scans,
                        "apply_dispatches": dev.apply_dispatches}
        finally:
            dev.detach()
        row = {
            "n": hg.n, "edges": len(hg.edges), "pins": int(hg.num_pins),
            "P": P, "eps": eps, "cost": c_np,
            "seconds_numpy": t_np,
            "seconds_device": t_dev,
            "speedup_vs_numpy": t_np / t_dev,
            **counters,
        }
        if pallas_row and n == sizes[0]:
            # the Pallas find-pricing path: compiled on a TPU (where it is
            # what seconds_device already ran), interpreted elsewhere
            ops.force("pallas")
            try:
                t_pl, c_pl = timed("jax", warm=True)
            finally:
                ops.force(None)
            assert c_pl == c_np, (n, c_pl, c_np)
            row["seconds_device_pallas"] = t_pl
            row["pallas_interpret"] = dev.interpret
        rows.append(row)
    return rows


def bench_parallel(P=8, eps=0.05, seed=0, sizes=None, workers=(1, 2, 4, 8)):
    """Worker-count sweep of the process-parallel V-cycle (PR 7 tentpole).

    End-to-end ``partition_with_replication(..., multilevel=True,
    workers=W)`` on the same streaming row-net instances as
    ``bench_multilevel``, W swept over ``workers``.  Wall-clock speedup is
    reported against the W=1 run *on this box* together with
    ``cpu_count`` -- on a single-core container every W>1 row is pure
    overhead (fork + shared-memory setup + reconciliation replay) and the
    honest speedup is < 1; the sweep still proves the sharded path end to
    end, and ``cost_vs_w1_pct``/``cost_not_worse`` disclose how the
    reconciled cost compares to serial at every size.  Rows land in
    ``BENCH_partition.json`` as ``parallel_scale`` via ``run.py``.
    """
    from repro.core.partition import parallel as par
    if not par.shm_available():
        return {"scale": [], "available": False}
    sizes = sizes or ((16384, 65536) if FULL else (16384,))
    rows = []
    for n in sizes:
        hg = large_row_net(n, seed=seed + n)
        w1 = None
        for W in workers:
            t0 = time.perf_counter()
            base, rep = partition_with_replication(
                hg, P, eps, seed=seed, multilevel=True, workers=W)
            t = time.perf_counter() - t0
            assert is_valid(hg, rep.masks, P, eps)
            row = {
                "n": hg.n, "edges": len(hg.edges), "pins": int(hg.num_pins),
                "P": P, "eps": eps, "workers": W,
                "cpu_count": os.cpu_count(),
                "seconds": t,
                "base_cost": float(base.cost), "rep_cost": float(rep.cost),
            }
            if W == 1:
                w1 = (t, float(rep.cost))
            else:
                row["speedup_vs_w1"] = w1[0] / t
                row["cost_vs_w1_pct"] = (100.0 * (rep.cost - w1[1]) / w1[1]
                                         if w1[1] > 0 else 0.0)
                row["cost_not_worse"] = bool(rep.cost <= w1[1] + 1e-9)
            rows.append(row)
    return {"scale": rows, "available": True}


def parallel_smoke(P=4, eps=0.1, seed=0):
    """CI-sized proof of the parallel layer (``run.py --parallel-smoke``):
    sharded matching must be bit-identical to serial, and the W=2
    end-to-end V-cycle must produce a valid, rep-not-worse partition."""
    from repro.core.partition import parallel as par
    from repro.core.partition.multilevel import heavy_pin_matching
    out = {"available": par.shm_available(), "cpu_count": os.cpu_count()}
    if not out["available"]:
        return out
    hg = large_row_net(2048, seed=seed)
    cm_s, nc_s = heavy_pin_matching(hg, 50.0, np.random.default_rng(seed))
    with par.ParallelContext(2, min_nodes=64) as ctx:
        cm_p, nc_p = heavy_pin_matching(hg, 50.0,
                                        np.random.default_rng(seed), ctx=ctx)
        assert not ctx.failed, "pool failed; smoke must run the real path"
    assert nc_p == nc_s and np.array_equal(cm_p, cm_s)
    saved = par.PARALLEL_MIN_NODES
    par.PARALLEL_MIN_NODES = 256     # engage workers at smoke size
    try:
        t0 = time.perf_counter()
        base, rep = partition_with_replication(hg, P, eps, seed=seed,
                                               multilevel=True, workers=2)
        t = time.perf_counter() - t0
    finally:
        par.PARALLEL_MIN_NODES = saved
    assert is_valid(hg, rep.masks, P, eps)
    assert rep.cost <= base.cost + 1e-9
    out.update(n=hg.n, workers=2, seconds=t, cmap_bit_identical=True,
               base_cost=float(base.cost), rep_cost=float(rep.cost))
    return out


def device_smoke(P=4, eps=0.1, seed=0):
    """Small-n CI smoke (``run.py --device-smoke``): the device-resident
    pass must reproduce the numpy path bit-exactly on every push."""
    from repro.kernels import front_pass
    saved = front_pass.DEVICE_MIN_NODES
    front_pass.DEVICE_MIN_NODES = 1
    try:
        out = bench_device_resident(P=P, eps=eps, seed=seed, sizes=(1024,),
                                    pallas_row=True)
    finally:
        front_pass.DEVICE_MIN_NODES = saved
    for row in out["scale"]:    # cost equality is asserted inside; re-check
        # fused dispatch (PR 7): every committed move's apply rides in the
        # next find program, so a pure FM sweep is one sync per find --
        # one per commit plus at most one pass-ending scan per pass (a
        # pass whose last find commits at the final position ends without
        # another find) -- and dispatches zero standalone apply programs
        assert row["commits"] <= row["syncs"] <= (row["commits"]
                                                  + row["pass_scans"]), row
        assert row["apply_dispatches"] == 0, row
    return out


def multilevel_smoke(P=4, eps=0.1, seed=0):
    """Small-n CI smoke: exercise the whole V-cycle path on every push.

    Asserts validity, base >= rep, and final-cost parity (<=) against the
    flat path at a size where both run in seconds.
    """
    out = bench_multilevel(P=P, eps=eps, seed=seed, sizes=(1024, 2048),
                           flat_limit=2048)
    for row in out["scale"]:
        assert row["ml_rep_cost"] <= row["ml_base_cost"] + 1e-9
        assert row.get("cost_not_worse", True), row
    return out


def run_all():
    t0 = time.time()
    results = {}
    results["fig4_P2"] = fig4_reductions(P=2, eps=0.025)
    results["fig4_P4"] = fig4_reductions(P=4, eps=0.05)
    results["table1"] = table1_eps_sweep()
    results["forms"] = table_forms()
    results["engine"] = bench_engine()
    results["multilevel"] = bench_multilevel()
    results["device"] = bench_device_resident()
    results["parallel"] = bench_parallel()
    results["seconds"] = time.time() - t0
    return results


if __name__ == "__main__":
    import json
    import sys
    if "--multilevel-smoke" in sys.argv:
        print(json.dumps(multilevel_smoke(), indent=1))
    elif "--parallel-smoke" in sys.argv:
        print(json.dumps(parallel_smoke(), indent=1))
    elif "--device-smoke" in sys.argv:
        print(json.dumps(device_smoke(), indent=1))
    else:
        print(json.dumps(run_all(), indent=1))
