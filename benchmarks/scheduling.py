"""Scheduling experiments (paper §7.3: Tables 2, 3, 4/14).

Protocol mirrors the paper: for each DAG, build the strong non-replicating
baseline (BSPg list scheduling + hill climbing, best-of incl. sequential),
then apply the basic and advanced replication heuristics; report mean cost
reduction = 1 - geomean(repl/base).  Dataset sizes are scaled to this
container's single CPU core (paper: 1k-175k nodes on a 128-thread EPYC);
the generators accept any scale.
"""
from __future__ import annotations

import os
import time

import numpy as np

from repro.core.schedule import (AdvancedOptions, BspInstance,
                                 MultilevelScheduleOptions,
                                 advanced_heuristic, baseline_schedule,
                                 basic_heuristic, best_replicated_schedule,
                                 bspg_schedule, hill_climb)
from repro.core.schedule import reference as ref
from repro.datagen import (hdb_dataset, large_psdd_dag, large_sptrsv_dag,
                           psdd_dag, psdd_dataset, spmv_dag, sptrsv_dag,
                           sptrsv_dataset)

FULL = os.environ.get("REPRO_BENCH_FULL", "0") == "1"


def _datasets():
    # scale=2/3 keeps enough work per processor that parallel schedules
    # beat sequential even at g=16 / L=400 (the paper's DAGs are 1k-175k
    # nodes; too-small instances degenerate the comparison)
    if FULL:
        return {"hdb": hdb_dataset(scale=3), "psdd": psdd_dataset(),
                "sptrsv": sptrsv_dataset(scale=2)}
    return {
        "hdb": hdb_dataset(scale=3)[:4],
        "psdd": psdd_dataset()[:3],
        "sptrsv": sptrsv_dataset(scale=2)[:2],
    }


def _geo_reduction(ratios):
    ratios = [min(max(r, 1e-9), 1.0) for r in ratios]
    return (1.0 - float(np.exp(np.mean(np.log(ratios))))) * 100


def reductions_for(dag, P, g, L, opts=None, seed=0):
    """Paper protocol (§6.1): the comparison baseline is the BSPg +
    hill-climbing PARALLEL schedule; replication is introduced into it.
    (Our framework also keeps a sequential candidate -- often better for
    tiny graphs at huge g/L, cf. §C.2.2 -- but the paper's ratios are
    parallel-baseline vs parallel+replication.)"""
    inst = BspInstance(dag, P=P, g=float(g), L=float(L))
    base = hill_climb(bspg_schedule(inst, seed=seed), seed=seed)
    c0 = base.current_cost()
    basic = basic_heuristic(base.copy())
    adv = advanced_heuristic(base.copy(), opts)
    return c0, basic.current_cost(), adv.current_cost()


def table2_p_sweep(ps=None, g=4, L=20):
    ps = ps or ((2, 4, 8, 16) if FULL else (4, 8))
    out = {}
    for name, ds in _datasets().items():
        row = {}
        for P in ps:
            basics, advs = [], []
            for dag in ds:
                c0, cb, ca = reductions_for(dag, P, g, L)
                basics.append(cb / c0)
                advs.append(ca / c0)
            row[f"P={P}"] = {"basic_pct": _geo_reduction(basics),
                             "advanced_pct": _geo_reduction(advs)}
        out[name] = row
    return out


def table3_gl_sweep(P=8):
    combos = ((4, 20), (1, 20), (16, 20), (4, 1), (4, 400)) if FULL \
        else ((4, 20), (16, 20), (4, 400))
    out = {}
    for name, ds in _datasets().items():
        row = {}
        for g, L in combos:
            basics, advs = [], []
            for dag in ds:
                c0, cb, ca = reductions_for(dag, P, g, L)
                basics.append(cb / c0)
                advs.append(ca / c0)
            row[f"g={g},L={L}"] = {"basic_pct": _geo_reduction(basics),
                                   "advanced_pct": _geo_reduction(advs)}
        out[name] = row
    return out


def table4_ablation(P=8, g=4, L=20):
    """Activate single components of the advanced heuristic (B, B+BR,
    B+SM, B+SR) -- paper Table 4."""
    variants = {
        "B": AdvancedOptions(False, False, False),
        "B+BR": AdvancedOptions(True, False, False),
        "B+SM": AdvancedOptions(False, True, False),
        "B+SR": AdvancedOptions(False, False, True),
        "B+BR+SM+SR": AdvancedOptions(True, True, True),
    }
    out = {}
    for name, ds in _datasets().items():
        row = {}
        bases = table4_bases(ds, P, g, L)
        for vname, opts in variants.items():
            ratios = []
            for base in bases:
                c0 = base.current_cost()
                c = advanced_heuristic(base.copy(), opts).current_cost()
                ratios.append(c / c0)
            row[vname] = _geo_reduction(ratios)
        out[name] = row
    return out


def table4_bases(ds, P, g, L):
    return [hill_climb(bspg_schedule(BspInstance(d, P=P, g=float(g),
                                                 L=float(L)), seed=0), seed=0)
            for d in ds]


def table13_size_consistency(P=8, g=4, L=20):
    """Paper Table 13: improvements are consistent across instance sizes."""
    out = {}
    scales = (2, 3, 4) if FULL else (2, 4)
    for scale in scales:
        ds = hdb_dataset(scale=scale)[:3]
        advs = []
        for dag in ds:
            c0, _, ca = reductions_for(dag, P, g, L)
            advs.append(ca / c0)
        out[f"scale={scale}"] = {
            "n_range": [min(d.n for d in ds), max(d.n for d in ds)],
            "advanced_pct": _geo_reduction(advs),
        }
    return out


def engine_scale(P=8, g=4, L=20):
    """Old-vs-new throughput of the scheduling stack at scale.

    Runs the engine-backed pipeline and the preserved seed implementation
    (``reference.py``) on the same instances; final costs must be identical
    (the engine changes mechanics, not decisions), so the only deliverable
    difference is wall-clock.  Always measured at full scale -- DAG sizes
    where the seed's copy-per-trial pricing dominates (the paper's DAGs are
    1k-175k nodes) -- since the whole comparison fits in well under a
    minute; the seed side is the slow one and it runs exactly once per
    instance.
    """
    instances = [
        ("sptrsv_6000", sptrsv_dag(n=6000, band=48, seed=0)),
        ("sptrsv_3000", sptrsv_dag(n=3000, band=32, seed=0)),
        ("psdd_2035", psdd_dag(n_leaves=500, depth=16, seed=0)),
        ("hdb_spmv_2061", spmv_dag(n_rows=400, seed=0)),
    ]
    rows = []
    for name, dag in instances:
        inst = BspInstance(dag, P=P, g=float(g), L=float(L))
        t0 = time.time()
        new_hc = hill_climb(bspg_schedule(inst, seed=0), seed=0)
        t1 = time.time()
        new_adv = advanced_heuristic(new_hc.copy())
        t2 = time.time()
        ref_hc = ref.hill_climb(ref.bspg_schedule(inst, seed=0), seed=0)
        t3 = time.time()
        ref_adv = ref.advanced_heuristic(ref_hc.copy())
        t4 = time.time()
        rows.append({
            "name": name, "n": dag.n, "P": P,
            "engine_baseline_seconds": t1 - t0,
            "engine_advanced_seconds": t2 - t1,
            "seed_baseline_seconds": t3 - t2,
            "seed_advanced_seconds": t4 - t3,
            "speedup_baseline": (t3 - t2) / max(t1 - t0, 1e-9),
            "speedup_advanced": (t4 - t3) / max(t2 - t1, 1e-9),
            "baseline_cost": float(new_hc.current_cost()),
            "advanced_cost": float(new_adv.current_cost()),
            "costs_match": bool(
                float(new_hc.current_cost()) == float(ref_hc.current_cost())
                and float(new_adv.current_cost()) == float(ref_adv.current_cost())),
        })
    return rows


def frontier_scale(P=8, g=4, L=20):
    """Frontier layer old-vs-new on the scheduling stack (PR 3 tentpole).

    Per instance: the hill climber with node moves priced per-target
    (``use_fronts=False``, the pre-frontier loop) vs one batched front per
    node, and the advanced heuristic with the first-improvement SR sweep
    vs the frontier SR pass (whole ``(p1, p2)`` front priced purely, only
    the winner committed through a transaction).  The hill-climb pair is
    decision-identical (costs must match); the SR pair deliberately
    differs in decision rule, so both costs are recorded.
    """
    instances = [
        ("sptrsv_6000", sptrsv_dag(n=6000, band=48, seed=0)),
        ("sptrsv_3000", sptrsv_dag(n=3000, band=32, seed=0)),
        ("psdd_2035", psdd_dag(n_leaves=500, depth=16, seed=0)),
    ]
    rows = []
    for name, dag in instances:
        inst = BspInstance(dag, P=P, g=float(g), L=float(L))
        base = bspg_schedule(inst, seed=0)
        t0 = time.perf_counter()
        hc_on = hill_climb(base.copy(), seed=0)
        t1 = time.perf_counter()
        hc_off = hill_climb(base.copy(), seed=0, use_fronts=False)
        t2 = time.perf_counter()
        adv_on = advanced_heuristic(hc_on.copy())
        t3 = time.perf_counter()
        adv_off = advanced_heuristic(hc_on.copy(),
                                     AdvancedOptions(use_fronts=False))
        t4 = time.perf_counter()
        assert hc_on.current_cost() == hc_off.current_cost()
        rows.append({
            "name": name, "n": dag.n, "P": P,
            "hill_climb_seconds_front": t1 - t0,
            "hill_climb_seconds_off": t2 - t1,
            "hill_climb_speedup": (t2 - t1) / max(t1 - t0, 1e-9),
            "advanced_seconds_front": t3 - t2,
            "advanced_seconds_off": t4 - t3,
            "advanced_speedup": (t4 - t3) / max(t3 - t2, 1e-9),
            "hill_climb_cost": float(hc_on.current_cost()),
            "advanced_cost_front": float(adv_on.current_cost()),
            "advanced_cost_off": float(adv_off.current_cost()),
        })
    return rows


def multilevel_scale(P=8, g=4, L=20, sizes=None, flat_limit=None, seed=0):
    """Flat vs multilevel V-cycle scheduling at scale (PR 5 tentpole).

    End-to-end ``best_replicated_schedule`` on sptrsv/psdd instances: the
    *pure* V-cycle (``flat_guard_n=0``, so ``ml_seconds``/``vcycle_cost``
    measure the V-cycle itself, not a hidden flat run) at every size, the
    flat path up to ``flat_limit`` nodes (beyond it a single flat run
    takes minutes to hours -- the scaling wall the V-cycle removes; the
    paper schedules up to 175k-node DAGs in exactly this coarse-grained
    regime).  ``ml_cost`` is what the default guarded driver returns --
    ``min(vcycle, flat)`` wherever both ran, the V-cycle alone beyond the
    guard -- so ``cost_not_worse`` holds by construction and
    ``vcycle_not_worse`` reports whether the V-cycle won organically.
    Rows land in ``BENCH_schedule.json`` as ``multilevel_scale`` via
    ``run.py``.
    """
    if sizes is None:
        sizes = ([("sptrsv", 3000), ("sptrsv", 6000), ("psdd", 4000),
                  ("sptrsv", 50_000), ("psdd", 50_000),
                  ("sptrsv", 100_000), ("sptrsv", 1_000_000)] if FULL else
                 [("sptrsv", 3000), ("sptrsv", 6000), ("psdd", 4000),
                  ("sptrsv", 50_000), ("psdd", 50_000)])
    flat_limit = flat_limit if flat_limit is not None else 8192
    rows = []
    for kind, n in sizes:
        if kind == "sptrsv":
            dag = (large_sptrsv_dag(n, band=48, seed=seed) if n > 8192
                   else sptrsv_dag(n=n, band=32 if n <= 3000 else 48,
                                   seed=seed))
        else:
            dag = large_psdd_dag(n_leaves=max(250, n // 4), depth=16,
                                 seed=seed)
        inst = BspInstance(dag, P=P, g=float(g), L=float(L))
        t0 = time.perf_counter()
        mlv = best_replicated_schedule(
            inst, seed=seed, multilevel=True,
            ml_opts=MultilevelScheduleOptions(flat_guard_n=0))
        t_ml = time.perf_counter() - t0
        assert mlv.validate() == []
        row = {
            "name": dag.name, "n": dag.n, "edges": dag.num_edges, "P": P,
            "g": g, "L": L,
            "ml_seconds": t_ml,
            "vcycle_cost": float(mlv.current_cost()),
            "ml_cost": float(mlv.current_cost()),
            "ml_supersteps": mlv.S,
            "ml_replicas": sum(len(a) - 1 for a in mlv.assign
                               if len(a) > 1),
        }
        if dag.n <= flat_limit:
            t0 = time.perf_counter()
            flat = best_replicated_schedule(inst, seed=seed)
            t_flat = time.perf_counter() - t0
            # what the old guarded driver (flat hedge on) would return and
            # cost -- guarded_seconds keeps the row honest about what
            # achieves ml_cost at which price, and guard_retired_seconds
            # is the flat hedge's wall-clock the guard-retired default
            # (PR 9) no longer pays
            guarded = float(min(mlv.current_cost(), flat.current_cost()))
            row.update(flat_seconds=t_flat,
                       flat_cost=float(flat.current_cost()),
                       ml_cost=guarded,
                       guarded_seconds=t_ml + t_flat,
                       guard_retired_seconds=t_flat,
                       speedup=t_flat / t_ml,
                       vcycle_not_worse=bool(mlv.current_cost()
                                             <= flat.current_cost() + 1e-9),
                       cost_not_worse=bool(guarded
                                           <= flat.current_cost() + 1e-9))
        rows.append(row)
    return rows


def split_scale(P=8, g=4, L=20, sizes=None, seed=0):
    """Guard retirement at scale (PR 9 tentpole).

    Per size, up to three end-to-end ``best_replicated_schedule`` variants
    on the same instance:

    * ``guarded``    -- the pre-PR 9 default (``flat_guard_n=8192``,
      splits off): the V-cycle plus one full flat hedge run.  Only at
      n <= 8192, where the flat path is tractable.
    * ``guard_free`` -- ``flat_guard_n=0``, splits off: what retiring the
      guard *without* the split front would return (capped at n <= 200k
      to keep the section's wall-clock sane).
    * ``split``      -- the new default: guard retired, split front on in
      every per-level refinement.  Runs at every size, including the
      n = 10^6 sptrsv row (FULL) -- the scale gate the guard used to
      make unreachable.

    Asserted per row wherever the guarded variant ran: the new default's
    cost is <= the old guarded cost (the PR 9 acceptance gate), while
    ``guard_retired_seconds`` -- guarded minus split wall-clock, i.e.
    what retiring the hedge saves end to end -- is disclosed.  Variants
    that did not run at a size are absent from the row, never silently
    extrapolated.

    Known non-parity instance (disclosed, not benched as a guarded row):
    psdd_large n=8165 (``large_psdd_dag(n_leaves=2000, depth=16)``) is a
    V-cycle fixpoint at 3814 where the flat trajectory reaches 3795
    (+0.5%); forced-split kicks plus full flat polish close it only to
    3800.  The gap is in the assignment structure, not the superstep
    structure -- the split front cannot reach it.  The psdd guarded row
    here runs n=4080, where the guard-free default beats the flat hedge
    outright (1903 vs 1926).
    """
    if sizes is None:
        sizes = ([("sptrsv", 2000), ("sptrsv", 6000), ("sptrsv", 8192),
                  ("psdd", 4000), ("sptrsv", 50_000), ("sptrsv", 100_000),
                  ("sptrsv", 1_000_000)] if FULL else
                 [("sptrsv", 2000), ("sptrsv", 6000), ("psdd", 4000)])
    rows = []
    for kind, n in sizes:
        if kind == "sptrsv":
            dag = (large_sptrsv_dag(n, band=48, seed=seed) if n > 8192
                   else sptrsv_dag(n=n, band=32 if n <= 3000 else 48,
                                   seed=seed))
        else:
            dag = large_psdd_dag(n_leaves=max(250, n // 4), depth=16,
                                 seed=seed)
        inst = BspInstance(dag, P=P, g=float(g), L=float(L))
        row = {"name": dag.name, "n": dag.n, "edges": dag.num_edges,
               "P": P, "g": g, "L": L}
        t0 = time.perf_counter()
        split = best_replicated_schedule(inst, seed=seed, multilevel=True)
        row["split_seconds"] = time.perf_counter() - t0
        row["split_cost"] = float(split.current_cost())
        row["split_supersteps"] = split.S
        row["split_replicas"] = sum(len(a) - 1 for a in split.assign
                                    if len(a) > 1)
        assert split.validate() == []
        if dag.n <= 200_000:
            t0 = time.perf_counter()
            gf = best_replicated_schedule(
                inst, seed=seed, multilevel=True,
                ml_opts=MultilevelScheduleOptions(superstep_splits=False))
            row["guard_free_seconds"] = time.perf_counter() - t0
            row["guard_free_cost"] = float(gf.current_cost())
        if dag.n <= 8192:
            t0 = time.perf_counter()
            guarded = best_replicated_schedule(
                inst, seed=seed, multilevel=True,
                ml_opts=MultilevelScheduleOptions(flat_guard_n=8192,
                                                  superstep_splits=False))
            row["guarded_seconds"] = time.perf_counter() - t0
            row["guarded_cost"] = float(guarded.current_cost())
            row["guard_retired_seconds"] = (row["guarded_seconds"]
                                            - row["split_seconds"])
            assert row["split_cost"] <= row["guarded_cost"] + 1e-9, row
            row["split_not_worse_than_guarded"] = True
        rows.append(row)
    return rows


def device_scale(P=8, g=4, L=20):
    """Device-window pricing vs numpy on the hill climber (PR 6).

    Integer-weight sptrsv/psdd instances run ``hill_climb`` with the numpy
    pricers and with the device window pricers (``backend="jax"``); both
    are decision-identical, so costs must match and the only deliverable
    difference is wall-clock.  A per-instance instrumented
    ``DeviceScheduleWindows`` records host syncs and uploaded bytes for
    the ``device_resident`` rows in ``BENCH_schedule.json``.
    """
    from repro.core.frontier import device_windows

    instances = ([("sptrsv_6000", sptrsv_dag(n=6000, band=48, seed=0)),
                  ("psdd_2035", psdd_dag(n_leaves=500, depth=16, seed=0))]
                 if FULL else
                 [("sptrsv_3000", sptrsv_dag(n=3000, band=32, seed=0)),
                  ("psdd_2035", psdd_dag(n_leaves=500, depth=16, seed=0))])
    rows = []
    for name, dag in instances:
        inst = BspInstance(dag, P=P, g=float(g), L=float(L))
        base = bspg_schedule(inst, seed=0)
        t0 = time.perf_counter()
        hc_np = hill_climb(base.copy(), seed=0)
        t1 = time.perf_counter()
        hill_climb(base.copy(), seed=0, backend="jax")  # warm the jit cache
        t2 = time.perf_counter()
        hc_dev = hill_climb(base.copy(), seed=0, backend="jax")
        t3 = time.perf_counter()
        assert hc_np.current_cost() == hc_dev.current_cost(), name
        # instrumented sample: one full node-move pricing sweep
        probe = base.copy()
        win = device_windows(probe, "jax")
        assert win is not None, f"{name}: device windows did not attach"
        for v in range(0, probe.inst.dag.n, 7):
            win.price_node_moves(v)
        syncs, h2d_bytes = win.syncs, win.h2d_bytes
        rows.append({
            "name": name, "n": dag.n, "P": P, "g": g, "L": L,
            "seconds_numpy": t1 - t0,
            "seconds_device": t3 - t2,
            "seconds_device_cold": t2 - t1,
            "speedup_vs_numpy": (t1 - t0) / max(t3 - t2, 1e-9),
            "cost": float(hc_np.current_cost()),
            "probe_syncs": syncs, "probe_h2d_bytes": h2d_bytes,
        })
    return rows


def device_smoke(P=4, g=2, L=4):
    """Small-n CI smoke: device-window hill climbing must match numpy
    bit-exactly on every push (floors dropped so the device path fires)."""
    from repro.kernels import front_pass

    saved = (front_pass.DEVICE_MIN_WINDOW, front_pass.DEVICE_MIN_STEPS)
    front_pass.DEVICE_MIN_WINDOW = front_pass.DEVICE_MIN_STEPS = 1
    try:
        rows = []
        for n in (300, 600):
            dag = sptrsv_dag(n=n, band=16, seed=0)
            inst = BspInstance(dag, P=P, g=float(g), L=float(L))
            base = bspg_schedule(inst, seed=0)
            hc_np = hill_climb(base.copy(), seed=0)
            hc_dev = hill_climb(base.copy(), seed=0, backend="jax")
            assert hc_np.current_cost() == hc_dev.current_cost(), n
            assert hc_np.comms == hc_dev.comms and \
                hc_np.assign == hc_dev.assign, n
            rows.append({"n": dag.n, "cost": float(hc_np.current_cost())})
    finally:
        front_pass.DEVICE_MIN_WINDOW, front_pass.DEVICE_MIN_STEPS = saved
    return {"rows": rows}


def multilevel_smoke(P=8, g=4, L=20):
    """Small-n CI smoke: exercise the whole scheduling V-cycle on every
    push -- coarsen, coarse solve, project, refine, replica-prune -- with
    validity and flat-parity asserts at sizes where both run in seconds.
    """
    opts = MultilevelScheduleOptions(coarsest_n=400, flat_guard_n=0)
    rows = []
    for n in (1500, 2500):
        dag = sptrsv_dag(n=n, band=32, seed=0)
        inst = BspInstance(dag, P=P, g=float(g), L=float(L))
        t0 = time.perf_counter()
        mlv = best_replicated_schedule(inst, seed=0, multilevel=True,
                                       ml_opts=opts)
        t_ml = time.perf_counter() - t0
        assert mlv.validate() == []
        flat = best_replicated_schedule(inst, seed=0)
        assert mlv.current_cost() <= flat.current_cost() + 1e-9, \
            (n, mlv.current_cost(), flat.current_cost())
        rows.append({"n": n, "ml_cost": float(mlv.current_cost()),
                     "flat_cost": float(flat.current_cost()),
                     "ml_seconds": t_ml})
    return {"multilevel_smoke": rows}


def split_smoke(P=8, g=4, L=20):
    """Small-n CI smoke (PR 9): on every push, the guard-retired default
    (splits on) must return a schedule no costlier than the old guarded
    driver's on a replication-hungry psdd instance -- the family the flat
    hedge existed for."""
    rows = []
    for n_leaves, depth in ((500, 12), (800, 12)):
        dag = psdd_dag(n_leaves=n_leaves, depth=depth, seed=1)
        inst = BspInstance(dag, P=P, g=float(g), L=float(L))
        t0 = time.perf_counter()
        mlv = best_replicated_schedule(inst, seed=0, multilevel=True)
        t_new = time.perf_counter() - t0
        assert mlv.validate() == []
        t0 = time.perf_counter()
        guarded = best_replicated_schedule(
            inst, seed=0, multilevel=True,
            ml_opts=MultilevelScheduleOptions(flat_guard_n=8192,
                                              superstep_splits=False))
        t_old = time.perf_counter() - t0
        assert mlv.current_cost() <= guarded.current_cost() + 1e-9, \
            (dag.n, mlv.current_cost(), guarded.current_cost())
        rows.append({"n": dag.n,
                     "split_cost": float(mlv.current_cost()),
                     "guarded_cost": float(guarded.current_cost()),
                     "split_seconds": t_new, "guarded_seconds": t_old,
                     "guard_retired_seconds": t_old - t_new})
    return {"split_smoke": rows}


def run_all():
    t0 = time.time()
    results = {
        "table2": table2_p_sweep(),
        "table3": table3_gl_sweep(),
        "table4": table4_ablation(),
        "table13": table13_size_consistency(),
        "engine": engine_scale(),
        "frontier": frontier_scale(),
        "multilevel": multilevel_scale(),
        "split": split_scale(),
        "device": device_scale(),
    }
    results["seconds"] = time.time() - t0
    return results


if __name__ == "__main__":
    import json
    import sys
    if "--schedule-multilevel-smoke" in sys.argv:
        print(json.dumps(multilevel_smoke(), indent=1))
    elif "--schedule-split-smoke" in sys.argv:
        print(json.dumps(split_smoke(), indent=1))
    else:
        print(json.dumps(run_all(), indent=1))
