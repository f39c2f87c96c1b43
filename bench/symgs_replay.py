"""Replay the timed path's SYMGS schedules through the plain smoother.

    python3 bench/symgs_replay.py --members 0 1 --seeds 5 6

For each pool member ``i`` of ``symgs-bsp8.device`` it solves the instance
relabelled by ``i`` through the timed path (``bench/kinds/schedule.py``
``Cell(config, i).solve()``) and replays the schedule through
``bench/reference/symgs.py`` on ``r`` and ``x0`` drawn from each seed.  It
prints one JSON line per member: the reference's checks and, per seed,
whether the replayed ``x`` equals the sequential sweep's bit for bit.  The
benchmark's own runs never run it; the harness keeps no answers.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import gen, harness  # noqa: E402
from bench.reference import symgs  # noqa: E402

GENERATOR = "hpcg_symgs_dag"


def label(dims: tuple[int, int, int], member: int) -> np.ndarray:
    """The id that the relabelling of pool member ``member`` gives each
    task: relabel a copy whose weights are the task ids and read them
    back."""
    mod = gen.load_generator(GENERATOR)
    base = mod.build(*dims)
    tagged = dict(base, omega=np.arange(base["n"], dtype=np.float64))
    return np.argsort(mod.relabel(tagged, member)["omega"])


def replays(config: dict, member: int, seeds: list[int]) -> dict:
    from bench.kinds.schedule import _plain
    dims = tuple(config["instance"][k] for k in ("nx", "ny", "nz"))
    cell = harness.load_kind(config["kind"]).Cell(config, member)
    t = time.perf_counter()
    answer = cell.solve()
    out = {"member": member, "dims": dims, "solve_s": time.perf_counter() - t,
           "checks": cell.check(answer), "cost": answer.current_cost()}
    plain = _plain(answer)
    lab = label(dims, member)
    n = dims[0] * dims[1] * dims[2]
    out["bit_exact"] = {}
    for seed in seeds:
        rng = np.random.default_rng(seed)
        r, x0 = rng.standard_normal(n), rng.standard_normal(n)
        got = symgs.replay(*dims, r, x0, cell.P, plain["S"], plain["assign"],
                           plain["comms"], label=lab)
        want = symgs.sweep(*dims, r, x0)
        out["bit_exact"][seed] = got.tobytes() == want.tobytes()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--members", type=int, nargs="+", default=[0, 1])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    jax = harness.setup_jax()
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    config = harness.load_json(harness.BENCH / "configs" / "symgs_bsp8.json")
    ok = True
    for member in args.members:
        out = replays(config, member, args.seeds)
        ok &= all(out["bit_exact"].values()) and not any(
            out["checks"].values())
        print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
