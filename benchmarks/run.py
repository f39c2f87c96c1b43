"""Benchmark harness: one function per paper table (deliverable d).

Prints ``name,us_per_call,derived`` CSV -- `derived` is the table's key
quantity (mean cost-reduction %, exact-gap %, roofline fraction ...).
Full-size runs: REPRO_BENCH_FULL=1.  JSON details land in
benchmarks/results/.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

RESULTS = pathlib.Path(__file__).parent / "results"


def _emit(name: str, seconds: float, derived) -> None:
    print(f"{name},{seconds * 1e6:.0f},{derived}", flush=True)


def main() -> None:
    RESULTS.mkdir(parents=True, exist_ok=True)
    from benchmarks import ilp_vs_heuristic, partitioning, scheduling
    from benchmarks import roofline as roof

    print("name,us_per_call,derived", flush=True)

    # ---- partitioning (paper Fig. 4 / Tables 1, 10-12) -------------------
    t0 = time.time()
    part = partitioning.run_all()
    (RESULTS / "partitioning.json").write_text(json.dumps(part, indent=1))
    for key in ("fig4_P2", "fig4_P4"):
        for ds, row in part[key].items():
            _emit(f"partition_{key}_{ds}", part["seconds"],
                  f"reduction={row['reduction_pct']:.1f}%;zeros={row['zeros']}")
    for eps, row in part["table1"].items():
        mean = sum(r["reduction_pct"] for r in row.values()) / len(row)
        _emit(f"partition_table1_{eps}", part["seconds"],
              f"mean_reduction={mean:.1f}%")
    _emit("partition_forms_DvsR", part["seconds"],
          f"wins={part['forms']['wins']}")

    # ---- partition-engine perf trajectory (machine-readable) -------------
    # BENCH_partition.json at the repo root: instances/sec and best cost per
    # dataset, plus old-vs-new engine throughput -- future PRs diff this.
    bench = {
        "engine_scale": part["engine"]["scale"],
        "replication_large": part["engine"]["replication_large"],
        "frontier_scale": part["frontier"]["scale"],
        "frontier_replication": part["frontier"]["replication"],
        "multilevel_scale": part["multilevel"]["scale"],
        "device_resident": part["device"],
        "parallel_scale": part["parallel"]["scale"],
        "datasets": {
            ds: {"instances_per_sec": row["instances_per_sec"],
                 "best_cost": min((r for _, r in row["pairs"]), default=0.0)}
            for ds, row in part["fig4_P4"].items()
        },
    }
    (pathlib.Path(__file__).resolve().parents[1]
     / "BENCH_partition.json").write_text(json.dumps(bench, indent=1))
    for row in part["engine"]["scale"]:
        spd = (f";speedup_vs_seed={row['speedup']:.1f}x"
               if "speedup" in row else "")
        _emit(f"partition_engine_n{row['n']}", row["engine_seconds"],
              f"inst_per_sec={row['engine_instances_per_sec']:.2f};"
              f"cost={row['engine_cost']:.0f}" + spd)
    for row in part["frontier"]["scale"]:
        jx = (f"speedup_jax={row['speedup_jax']:.2f}x;"
              if "speedup_jax" in row else "")
        _emit(f"partition_frontier_n{row['n']}", row["seconds_numpy"],
              f"speedup_numpy={row['speedup_numpy']:.2f}x;" + jx
              + f"cost={row['cost']:.0f}")
    frep = part["frontier"]["replication"]
    _emit(f"partition_frontier_rep_n{frep['n']}", frep["seconds_numpy"],
          f"speedup_numpy={frep['speedup_numpy']:.2f}x;"
          f"rep_cost={frep['rep_cost']:.0f}")
    for row in part["device"].get("scale", []):
        pi = (f";pallas={row['seconds_device_pallas']:.2f}s"
              f";interpret={row['pallas_interpret']}"
              if "seconds_device_pallas" in row else "")
        _emit(f"partition_device_n{row['n']}", row["seconds_device"],
              f"speedup_vs_numpy={row['speedup_vs_numpy']:.2f}x;"
              f"speedup_vs_perfront={row['speedup_vs_perfront']:.2f}x;"
              f"syncs={row['syncs']};commits={row['commits']}" + pi)
    for row in part["parallel"].get("scale", []):
        rel = (f"speedup_vs_w1={row['speedup_vs_w1']:.2f}x;"
               f"cost_vs_w1={row['cost_vs_w1_pct']:+.2f}%;"
               f"not_worse={row['cost_not_worse']};"
               if "speedup_vs_w1" in row else "")
        _emit(f"partition_parallel_n{row['n']}_w{row['workers']}",
              row["seconds"],
              rel + f"cpus={row['cpu_count']};rep_cost={row['rep_cost']:.0f}")
    for row in part["multilevel"]["scale"]:
        flat = (f"flat={row['flat_seconds']:.1f}s;"
                f"speedup={row['speedup']:.1f}x;"
                f"not_worse={row['cost_not_worse']};"
                if "flat_seconds" in row else "")
        _emit(f"partition_multilevel_n{row['n']}", row["ml_seconds"],
              flat + f"rep_cost={row['ml_rep_cost']:.0f};"
              f"reduction={row['ml_reduction_pct']:.1f}%")

    # ---- scheduling (paper Tables 2, 3, 4) -------------------------------
    sched = scheduling.run_all()
    (RESULTS / "scheduling.json").write_text(json.dumps(sched, indent=1))
    for ds, row in sched["table2"].items():
        for p, v in row.items():
            _emit(f"schedule_table2_{ds}_{p}", sched["seconds"],
                  f"basic={v['basic_pct']:.2f}%;advanced={v['advanced_pct']:.2f}%")
    for ds, row in sched["table3"].items():
        for gl, v in row.items():
            _emit(f"schedule_table3_{ds}_{gl}", sched["seconds"],
                  f"advanced={v['advanced_pct']:.2f}%")
    for ds, row in sched["table4"].items():
        _emit(f"schedule_table4_{ds}", sched["seconds"],
              ";".join(f"{k}={v:.2f}%" for k, v in row.items()))
    for sc, v in sched.get("table13", {}).items():
        _emit(f"schedule_table13_{sc}", sched["seconds"],
              f"n={v['n_range']};advanced={v['advanced_pct']:.2f}%")

    # ---- schedule-engine perf trajectory (machine-readable) --------------
    # BENCH_schedule.json at the repo root: old-vs-new heuristic throughput
    # at scale plus the cost-reduction trajectory -- future PRs diff this.
    sched_bench = {
        "engine_scale": sched["engine"],
        "frontier_scale": sched["frontier"],
        "multilevel_scale": sched["multilevel"],
        "split_scale": sched["split"],
        "device_resident": sched["device"],
        "cost_reduction": sched["table2"],
    }
    (pathlib.Path(__file__).resolve().parents[1]
     / "BENCH_schedule.json").write_text(json.dumps(sched_bench, indent=1))
    for row in sched["engine"]:
        _emit(f"schedule_engine_{row['name']}",
              row["engine_advanced_seconds"],
              f"speedup_advanced={row['speedup_advanced']:.1f}x;"
              f"speedup_baseline={row['speedup_baseline']:.1f}x;"
              f"cost={row['advanced_cost']:.0f};"
              f"costs_match={row['costs_match']}")
    for row in sched["frontier"]:
        _emit(f"schedule_frontier_{row['name']}",
              row["advanced_seconds_front"],
              f"hc_speedup={row['hill_climb_speedup']:.2f}x;"
              f"adv_speedup={row['advanced_speedup']:.2f}x;"
              f"adv_cost={row['advanced_cost_front']:.0f}")
    for row in sched["device"]:
        _emit(f"schedule_device_{row['name']}", row["seconds_device"],
              f"speedup_vs_numpy={row['speedup_vs_numpy']:.2f}x;"
              f"cost={row['cost']:.0f};probe_syncs={row['probe_syncs']}")
    for row in sched["multilevel"]:
        flat = (f"flat={row['flat_seconds']:.1f}s;"
                f"speedup={row['speedup']:.1f}x;"
                f"not_worse={row['cost_not_worse']};"
                f"vcycle_not_worse={row['vcycle_not_worse']};"
                if "flat_seconds" in row else "")
        _emit(f"schedule_multilevel_{row['name']}", row["ml_seconds"],
              flat + f"ml_cost={row['ml_cost']:.0f};"
              f"S={row['ml_supersteps']};replicas={row['ml_replicas']}")
    for row in sched["split"]:
        guarded = (f"guarded={row['guarded_seconds']:.1f}s;"
                   f"retired={row['guard_retired_seconds']:.1f}s;"
                   f"not_worse={row['split_not_worse_than_guarded']};"
                   if "guarded_seconds" in row else "")
        _emit(f"schedule_split_{row['name']}", row["split_seconds"],
              guarded + f"split_cost={row['split_cost']:.0f};"
              f"S={row['split_supersteps']}")

    # ---- online serving replay (machine-readable) ------------------------
    # BENCH_serve.json at the repo root: drift + stationary replays of the
    # online replicated-placement subsystem -- future PRs diff this.
    from benchmarks import serving
    serve = serving.run_all()
    (RESULTS / "serving.json").write_text(json.dumps(serve, indent=1))
    (pathlib.Path(__file__).resolve().parents[1]
     / "BENCH_serve.json").write_text(json.dumps(serve, indent=1))
    for scen in ("drift", "stationary"):
        row = serve[scen]
        on = row["policies"]["online_replicated"]
        _emit(f"serve_{scen}", serve["seconds"],
              f"requests={row['n_requests']};"
              f"saving_vs_static={row['online_vs_static_saving_pct']:.1f}%;"
              f"commits={on['commits']};"
              f"migMB={on['migration_bytes'] >> 20};"
              f"replan_p99={on['replan_p99_ms']:.0f}ms")

    # ---- exact vs heuristic (paper §C.2.2) -------------------------------
    ex = ilp_vs_heuristic.run_all()
    (RESULTS / "ilp_vs_heuristic.json").write_text(json.dumps(ex, indent=1))
    for p in ("P=2", "P=4"):
        _emit(f"schedule_exact_{p}", ex["seconds"],
              f"reduction={ex[p]['mean_reduction_pct']:.2f}%;"
              f"heuristic_gap={ex[p]['heuristic_gap_pct']:.2f}%")

    # ---- roofline table (from dry-run artifacts) -------------------------
    t0 = time.time()
    rows = roof.table()
    (RESULTS / "roofline.json").write_text(json.dumps(rows, indent=1))
    if rows:
        worst = min(rows, key=lambda r: r["roofline_fraction"])
        best = max(rows, key=lambda r: r["roofline_fraction"])
        _emit("roofline_cells", time.time() - t0,
              f"n={len(rows)};best={best['cell']}:"
              f"{best['roofline_fraction']*100:.1f}%;"
              f"worst={worst['cell']}:{worst['roofline_fraction']*100:.1f}%")
    else:
        _emit("roofline_cells", time.time() - t0,
              "no dry-run artifacts (run repro.launch.dryrun --all)")


def device_smoke() -> None:
    """``run.py --device-smoke``: CI-sized proof that the device-resident
    pass reproduces the numpy path bit-exactly (partition and schedule)."""
    from benchmarks import partitioning, scheduling
    out = {"partition": partitioning.device_smoke(),
           "schedule": scheduling.device_smoke()}
    print(json.dumps(out, indent=1))


def parallel_smoke() -> None:
    """``run.py --parallel-smoke``: CI-sized proof of the process-parallel
    V-cycle -- sharded matching bit-identity and a valid W=2 end-to-end
    run (skips cleanly where POSIX shared memory is unavailable)."""
    from benchmarks import partitioning
    print(json.dumps({"partition": partitioning.parallel_smoke()}, indent=1))


def schedule_split_smoke() -> None:
    """``run.py --schedule-split-smoke``: CI-sized proof of the guard
    retirement -- the guard-off split-enabled V-cycle must not cost more
    than the old guarded driver on replication-hungry psdd instances."""
    from benchmarks import scheduling
    print(json.dumps({"schedule": scheduling.split_smoke()}, indent=1))


def serve_smoke() -> None:
    """``run.py --serve-smoke``: CI-sized drift replay asserting the online
    placement contracts -- online <= static-replicated comm cost, valid
    plans every epoch, zero migrations on stationary traffic."""
    from benchmarks import serving
    out = serving.smoke()
    for scen in ("drift", "stationary"):
        out[scen].pop("per_epoch", None)  # keep CI logs terse
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--device-smoke" in sys.argv:
        device_smoke()
    elif "--parallel-smoke" in sys.argv:
        parallel_smoke()
    elif "--schedule-split-smoke" in sys.argv:
        schedule_split_smoke()
    elif "--serve-smoke" in sys.argv:
        serve_smoke()
    else:
        main()
