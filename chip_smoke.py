"""Smoke run of the partitioner and scheduler on one TPU chip.

    python chip_smoke.py

Drives the main path once through the public entry points, at the sizes a
user runs, and checks each result by the repository's own contract:

* partition: ``large_row_net(65536)`` (n = 65534, 452,918 pins), P = 8,
  eps = 0.05, multilevel with ``frontier="jax"`` and again with
  ``frontier="numpy"``.  Base and replicated masks and costs must be
  bit-identical, valid, and every refined level of at least
  ``DEVICE_MIN_NODES`` nodes must have run device-resident passes with the
  Pallas kernel compiled (not interpreted) and at most one host sync per
  committed move plus one per pass scan;
* schedule: ``large_sptrsv_dag(50_000)`` on ``BspInstance(P=8, g=4,
  L=20)``, multilevel, with the jax and the numpy frontier backend: the
  same schedule, and the device window pricers attached and synced;
* workers: the partition again with ``workers=2`` in this same process
  (which now holds the chip, so the pool must spawn, never fork): valid,
  rep <= base, and no serial fallback.

Every number it prints is one smoke run on the named device, not a
benchmark.  The last line is the JSON verdict.  It exits non-zero, with no
verdict, where JAX finds no TPU.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time
import warnings

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.core.frontier import get_backend, set_backend  # noqa: E402
from repro.core.partition import is_valid  # noqa: E402
from repro.core.partition.heuristic import (  # noqa: E402
    partition_with_replication)
from repro.core.partition.parallel import SerialFallbackWarning  # noqa: E402
from repro.core.schedule import (BspInstance,  # noqa: E402
                                 best_replicated_schedule)
from repro.datagen import large_row_net, large_sptrsv_dag  # noqa: E402
from repro.kernels import front_pass  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _replicas(sched) -> int:
    return sum(len(a) - 1 for a in sched.assign if len(a) > 1)


def partition_phase(hg, P: int = 8, eps: float = 0.05, seed: int = 0,
                    interpret: bool = False) -> dict:
    """jax vs numpy frontier on the multilevel replicated partition.

    ``interpret`` is the Pallas mode every device pass must report: False
    on the chip; the CPU rehearsal forces interpret mode."""
    front_pass.PARTITION_TOTALS.clear()
    levels: list = []
    t0 = time.perf_counter()
    base_j, rep_j = partition_with_replication(
        hg, P, eps, seed=seed, multilevel=True, frontier="jax", stats=levels)
    t_jax = time.perf_counter() - t0
    totals = {k: dict(v) for k, v in front_pass.PARTITION_TOTALS.items()}
    t0 = time.perf_counter()
    base_n, rep_n = partition_with_replication(
        hg, P, eps, seed=seed, multilevel=True, frontier="numpy")
    t_np = time.perf_counter() - t0

    assert np.array_equal(base_j.masks, base_n.masks), "base masks differ"
    assert np.array_equal(rep_j.masks, rep_n.masks), "rep masks differ"
    assert base_j.cost == base_n.cost and rep_j.cost == rep_n.cost, \
        (base_j.cost, base_n.cost, rep_j.cost, rep_n.cost)
    assert is_valid(hg, base_j.masks, P, eps, max_replicas=1)
    assert is_valid(hg, rep_j.masks, P, eps)
    assert rep_j.cost <= base_j.cost

    device_levels = sorted({row["n"] for row in levels
                            if row["n"] >= front_pass.DEVICE_MIN_NODES})
    assert device_levels, "no refined level reached DEVICE_MIN_NODES"
    modes = {(use_pallas, interp) for (_, use_pallas, interp) in totals}
    assert modes == {(True, interpret)}, f"device passes ran as {modes}"
    for n in device_levels:
        tot = totals.get((n, True, interpret))
        assert tot and tot["syncs"] > 0, f"level n={n} ran no device pass"
        assert tot["commits"] <= tot["syncs"] <= (tot["commits"]
                                                  + tot["pass_scans"]), tot
    return {"n": hg.n, "pins": int(hg.num_pins), "P": P, "eps": eps,
            "base_cost": float(base_j.cost), "rep_cost": float(rep_j.cost),
            "seconds_jax": t_jax, "seconds_numpy": t_np,
            "device_levels": {n: totals[(n, True, interpret)]
                              for n in device_levels}}


def schedule_phase(dag, P: int = 8, g: int = 4, L: int = 20,
                   seed: int = 0) -> dict:
    """jax vs numpy frontier backend on the multilevel BSP schedule."""
    inst = BspInstance(dag, P=P, g=g, L=L)
    before = dict(front_pass.SCHEDULE_TOTALS)
    saved = get_backend()
    set_backend("jax")
    try:
        t0 = time.perf_counter()
        s_j = best_replicated_schedule(inst, seed=seed, multilevel=True)
        t_jax = time.perf_counter() - t0
    finally:
        set_backend(saved)
    attaches = front_pass.SCHEDULE_TOTALS["attaches"] - before["attaches"]
    syncs = front_pass.SCHEDULE_TOTALS["syncs"] - before["syncs"]
    set_backend("numpy")
    try:
        t0 = time.perf_counter()
        s_n = best_replicated_schedule(inst, seed=seed, multilevel=True)
        t_np = time.perf_counter() - t0
    finally:
        set_backend(saved)

    assert s_j.current_cost() == s_n.current_cost(), \
        (s_j.current_cost(), s_n.current_cost())
    assert s_j.S == s_n.S and _replicas(s_j) == _replicas(s_n)
    assert s_j.assign == s_n.assign and s_j.comms == s_n.comms
    assert s_j.validate() == []
    assert attaches > 0 and syncs > 0, \
        f"device windows attached {attaches}x, synced {syncs}x"
    return {"n": dag.n, "P": P, "g": g, "L": L,
            "cost": float(s_j.current_cost()), "S": s_j.S,
            "replicas": _replicas(s_j), "seconds_jax": t_jax,
            "seconds_numpy": t_np, "window_attaches": attaches,
            "window_syncs": syncs}


def workers_phase(hg, P: int = 8, eps: float = 0.05, seed: int = 0,
                  workers: int = 2) -> dict:
    """The partition on a worker pool; any serial fallback is an error."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", SerialFallbackWarning)
        t0 = time.perf_counter()
        base, rep = partition_with_replication(
            hg, P, eps, seed=seed, multilevel=True, frontier="jax",
            workers=workers)
        t = time.perf_counter() - t0
    assert is_valid(hg, base.masks, P, eps, max_replicas=1)
    assert is_valid(hg, rep.masks, P, eps)
    assert rep.cost <= base.cost, (rep.cost, base.cost)
    return {"n": hg.n, "workers": workers, "base_cost": float(base.cost),
            "rep_cost": float(rep.cost), "seconds": t}


def main() -> int:
    enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; this script runs only on the "
              "chip", file=sys.stderr)
        return 1
    label = f"smoke run on {dev.device_kind}, not a benchmark"
    t_all = time.perf_counter()
    hg = large_row_net(65536, seed=0)
    phases = (
        ("partition", lambda: partition_phase(hg)),
        ("schedule", lambda: schedule_phase(large_sptrsv_dag(n=50_000,
                                                             seed=0))),
        ("workers", lambda: workers_phase(hg)),
    )
    for name, run in phases:
        out = run()
        out["peak_bytes_in_use"] = _peak_bytes()
        print(f"[{label}] {name}: {json.dumps(out, default=str)}", flush=True)
    print(f"[{label}] total seconds: {time.perf_counter() - t_all}",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
