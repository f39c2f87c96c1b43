"""Quickstart: train a (reduced) assigned architecture end-to-end on CPU.

    PYTHONPATH=src python examples/quickstart.py [--arch smollm-135m]
        [--steps 30] [--full-135m]

Uses the real framework path: config registry -> Trainer (fault-tolerant
loop, atomic checkpoints, deterministic data) -> loss curve.  ``--full-135m``
trains the full 135M-parameter SmolLM config (slow on 1 CPU core; the same
command drives a pod via --production-mesh in repro.launch.train).

Multilevel partitioning path (PR 4):

    PYTHONPATH=src python examples/quickstart.py --multilevel [--n 8192]

runs the V-cycle partitioner (coarsen -> coarsest solve -> project ->
refine -> replicate) on a streaming spmv row-net instance and prints the
per-level cost trajectory plus the flat-heuristic comparison.

Multilevel scheduling path (PR 5):

    PYTHONPATH=src python examples/quickstart.py --multilevel-schedule
        [--n 20000] [--no-splits] [--workers W]

runs the acyclic-coarsening scheduling V-cycle (funnel/same-level
clustering -> coarse replicated solve -> schedule projection ->
frontier-priced refinement, superstep-split front included unless
--no-splits) on a streaming sptrsv DAG and prints the per-level cost
trajectory; --workers shards coarsening's scoring pass over a
shared-memory pool (bit-identical result).

Device-resident refinement path (PR 6):

    PYTHONPATH=src python examples/quickstart.py --device --backend jax
        [--n 4096]

runs one FM refinement pass twice -- numpy frontier vs the whole-pass
device-resident program (`kernels/front_pass.py`: persistent jnp state,
fused pricing, one host sync per committed move) -- and prints both
wall-clocks, the sync/commit counters and the bit-identity check.  It
exits non-zero when the device pass cannot attach.

Online serving path (PR 10):

    PYTHONPATH=src python examples/quickstart.py --serve [--drift 0.8]
        [--epochs 16]

replays drifting router traffic through the online replicated-placement
controller (`core/placement/online.py`): per epoch it prints the live
incremental cost of the current placement, the drift statistic, and
whether the controller migrated experts -- next to the static plan the
traffic is drifting away from (--drift 0 shows the stationary case:
zero migrations, online == static).
"""
import argparse
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.configs import get_config, reduce_config
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim import adamw
from repro.runtime.trainer import Trainer, TrainerConfig


def multilevel_demo(n: int, P: int = 8, eps: float = 0.05,
                    workers: int | None = None) -> None:
    """Partition a production-scale spmv row-net with the V-cycle."""
    from repro.core.partition import (is_valid, partition_heuristic,
                                      partition_with_replication_multilevel)
    from repro.datagen import large_row_net

    hg = large_row_net(n, seed=0)
    print(f"multilevel: {hg.name} n={hg.n} edges={len(hg.edges)} "
          f"pins={hg.num_pins} P={P} eps={eps}"
          + (f" workers={workers}" if workers else ""))
    stats: list = []
    t0 = time.perf_counter()
    base, rep = partition_with_replication_multilevel(hg, P, eps, seed=0,
                                                      stats=stats,
                                                      workers=workers)
    dt = time.perf_counter() - t0
    for row in stats:
        print(f"  level {row['level']:2d}  n={row['n']:7d}  "
              f"projected={row['cost_projected']:.0f}  "
              f"refined={row['cost_refined']:.0f}")
    assert is_valid(hg, rep.masks, P, eps)
    red = 100.0 * (1 - rep.cost / base.cost) if base.cost else 0.0
    print(f"V-cycle: base={base.cost:.0f} repl={rep.cost:.0f} "
          f"(-{red:.1f}%) in {dt:.1f}s")
    if n <= 8192:  # flat comparison only where the flat path is tractable
        t0 = time.perf_counter()
        flat = partition_heuristic(hg, P, eps, seed=0)
        print(f"flat baseline: cost={flat.cost:.0f} in "
              f"{time.perf_counter() - t0:.1f}s "
              f"(multilevel {'<=' if base.cost <= flat.cost else '>'} flat)")


def multilevel_schedule_demo(n: int, P: int = 8, g: float = 4.0,
                             L: float = 20.0, splits: bool = True,
                             workers: int | None = None) -> None:
    """Schedule a production-scale sptrsv DAG with the multilevel V-cycle."""
    from repro.core.schedule import (BspInstance,
                                     MultilevelScheduleOptions,
                                     best_replicated_schedule)
    from repro.datagen import large_sptrsv_dag

    dag = large_sptrsv_dag(n, band=48, seed=0)
    print(f"multilevel schedule: {dag.name} n={dag.n} "
          f"edges={dag.num_edges} P={P} g={g} L={L} "
          f"splits={'on' if splits else 'off'}"
          + (f" workers={workers}" if workers else ""))
    stats: list = []
    t0 = time.perf_counter()
    sched = best_replicated_schedule(
        BspInstance(dag, P=P, g=g, L=L), seed=0, multilevel=True,
        stats=stats, workers=workers,
        ml_opts=MultilevelScheduleOptions(superstep_splits=splits))
    dt = time.perf_counter() - t0
    for row in stats:
        if "level" in row:
            print(f"  level {row['level']:2d}  n={row['n']:7d}  "
                  f"S={row['S']:4d}  projected={row['cost_projected']:.0f}  "
                  f"refined={row['cost_refined']:.0f}")
        else:
            print(f"  flat guard: vcycle={row['vcycle_cost']:.0f}  "
                  f"flat={row['flat_cost']:.0f}")
    assert sched.validate() == []
    repl = sum(len(a) - 1 for a in sched.assign if len(a) > 1)
    print(f"V-cycle: cost={sched.current_cost():.0f} S={sched.S} "
          f"replicas={repl} in {dt:.1f}s")


def device_demo(n: int, backend: str = "jax", P: int = 4,
                eps: float = 0.05) -> None:
    """Run FM refinement host-side and device-resident; show bit-identity."""
    import numpy as np

    from repro.core.frontier import device_pass
    from repro.core.partition import PartitionState
    from repro.core.partition.cost import capacity
    from repro.core.partition.heuristic import fm_refine, greedy_initial
    from repro.datagen import large_row_net

    hg = large_row_net(n, seed=0)
    print(f"device demo: {hg.name} n={hg.n} edges={len(hg.edges)} "
          f"P={P} eps={eps} backend={backend}")
    m0 = greedy_initial(hg, P, eps, np.random.default_rng(0))

    st_np = PartitionState(hg, P, masks=m0.copy())
    t0 = time.perf_counter()
    fm_refine(hg, m0.copy(), P, eps, np.random.default_rng(0), state=st_np,
              frontier="numpy")
    t_np = time.perf_counter() - t0
    print(f"numpy frontier:   cost={st_np.cost:.0f} in {t_np:.2f}s")

    st_dev = PartitionState(hg, P, masks=m0.copy())
    dev = device_pass(st_dev, capacity(hg, P, eps) + 1e-9, backend=backend)
    if dev is None:
        raise SystemExit(
            f"device pass did not attach (frontier='{backend}', n={hg.n}; "
            "needs frontier='jax', integer weights and n >= "
            "DEVICE_MIN_NODES)")
    t0 = time.perf_counter()
    try:
        dev.run_fm(np.random.default_rng(0), 6)
    finally:
        dev.detach()
    t_dev = time.perf_counter() - t0
    print(f"device-resident:  cost={st_dev.cost:.0f} in {t_dev:.2f}s "
          f"(syncs={dev.syncs} commits={dev.commits} "
          f"scans={dev.pass_scans})")
    same = bool(np.array_equal(st_np.masks, st_dev.masks)
                and st_np.cost == st_dev.cost)
    print(f"bit-identical: {same} "
          f"(<= 1 host sync per committed move + 1 terminal scan/pass)")
    assert same


def serve_demo(drift: float, epochs: int, n_experts: int = 64, P: int = 8,
               slots: int = 12) -> None:
    """Drift -> detect -> re-place loop of the online placement subsystem."""
    import numpy as np

    from repro.core.placement import OnlineController, plan_masks, replay_cost
    from repro.datagen import drifting_trace
    from repro.models.moe import round_robin_plan

    print(f"online serving: {n_experts} experts on {P} shards x {slots} "
          f"slots, drift={drift} topics/epoch, {epochs} epochs")
    ctrl = OnlineController(n_experts, P, slots, kappa0=400, seed=0)
    rr = plan_masks(round_robin_plan(n_experts, P))
    static = None
    tot = {"static": 0.0, "online": 0.0}
    for epoch, chunk in enumerate(drifting_trace(
            n_experts=n_experts, tokens_per_epoch=3000, n_epochs=epochs,
            drift_rate=drift, seed=7)):
        serving = plan_masks(ctrl.plan) if ctrl.plan is not None else rr
        m_static = static if static is not None else rr
        rep = ctrl.step(chunk)
        if static is None and rep.plan is not None:
            static = plan_masks(rep.plan)
        c_st, c_on = (replay_cost(m_static, chunk, P),
                      replay_cost(serving, chunk, P))
        tot["static"] += c_st
        tot["online"] += c_on
        if rep.plan is None:
            print(f"  epoch {epoch:2d}: warming up accumulator")
        else:
            act = (f"MIGRATED {rep.migration_bytes >> 20} MiB"
                   if rep.committed else "kept placement")
            print(f"  epoch {epoch:2d}: static={c_st:7.0f} online={c_on:7.0f}"
                  f"  containment={rep.containment:.2f}  {act}")
    red = 100.0 * (1.0 - tot["online"] / max(tot["static"], 1e-9))
    print(f"total comm cost: static={tot['static']:.0f} "
          f"online={tot['online']:.0f} (-{red:.1f}%), "
          f"{ctrl.n_commits} migrations, "
          f"{ctrl.total_migration_bytes >> 20} MiB moved")
    assert tot["online"] <= tot["static"]
    if drift == 0.0:
        assert ctrl.n_commits == 0, "stationary traffic must not migrate"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--full-135m", action="store_true")
    ap.add_argument("--multilevel", action="store_true",
                    help="run the multilevel V-cycle partitioning demo")
    ap.add_argument("--multilevel-schedule", action="store_true",
                    help="run the multilevel DAG-scheduling demo")
    ap.add_argument("--device", action="store_true",
                    help="run the device-resident FM refinement demo")
    ap.add_argument("--serve", action="store_true",
                    help="run the online replicated-placement serving demo")
    ap.add_argument("--drift", type=float, default=0.8,
                    help="topic drift rate for --serve (0 = stationary)")
    ap.add_argument("--epochs", type=int, default=16,
                    help="traffic epochs for --serve")
    ap.add_argument("--backend", default="jax",
                    help="frontier backend for --device (default: jax)")
    ap.add_argument("--n", type=int, default=None,
                    help="instance size for --multilevel[-schedule]/--device "
                         "(defaults: 8192 / 20000 / 4096)")
    ap.add_argument("--workers", type=int, default=None,
                    help="shared-memory worker processes for --multilevel / "
                         "--multilevel-schedule (sharded coarsening [+ "
                         "refinement for partitioning]; default serial)")
    ap.add_argument("--no-splits", action="store_true",
                    help="disable the superstep-split refinement front in "
                         "--multilevel-schedule (PR 9 default: on)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.multilevel:
        multilevel_demo(args.n or 8192, workers=args.workers)
        return
    if args.multilevel_schedule:
        multilevel_schedule_demo(args.n or 20_000,
                                 splits=not args.no_splits,
                                 workers=args.workers)
        return
    if args.device:
        device_demo(args.n or 4096, backend=args.backend)
        return
    if args.serve:
        serve_demo(args.drift, args.epochs)
        return

    cfg = get_config(args.arch)
    if not args.full_135m:
        cfg = reduce_config(cfg, layers_per_segment=2)
    mesh = make_host_mesh()
    print(f"quickstart: {cfg.name} ({cfg.param_count()/1e6:.1f}M params), "
          f"{args.steps} steps @ batch={args.batch} seq={args.seq}")
    with tempfile.TemporaryDirectory() as ckpt:
        tr = Trainer(cfg, mesh, DataConfig(args.batch, args.seq),
                     TrainerConfig(steps=args.steps, ckpt_every=10,
                                   ckpt_dir=ckpt, log_every=5),
                     adamw.AdamWConfig(lr=3e-3, warmup_steps=5,
                                       total_steps=args.steps))
        _, hist = tr.run()
    print(f"loss: {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
          f"({len(hist)} steps, ckpt/restore exercised)")
    assert hist[-1]["loss"] < hist[0]["loss"], "loss did not decrease"


if __name__ == "__main__":
    main()
