"""Readings of the control, and of sound solves, for setting the limits.

    python3 bench/control.py --workload <name> --seeds 1 2 3 [--sound]

For each seed it builds the cell's instance relabelled by that seed (a
run's pool member ``i`` is seed ``i``) and prints one JSON line: the control's readings (the timed path with one stated
guarantee broken: the configuration's ``control_*`` setting in place of
the stated one, its answer then checked against what the configuration
states) and, with ``--sound``, the readings of one sound solve and its
comparison with the host path.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(workload: str, seed: int, sound: bool = False) -> dict:
    from bench import harness
    spec = harness.load_spec()
    cell = harness.find_cell(spec, workload)
    config = harness.load_json(harness.BENCH / "configs"
                               / f"{cell['config']}.json")
    sut = harness.load_kind(config["kind"]).Cell(config, seed)
    out = {"workload": workload, "seed": seed,
           "control": sut.check(sut.control())}
    if sound:
        answer = sut.solve()
        out["sound"] = sut.check(answer)
        out["sound"]["host_path_mismatch"] = sut.mismatch(answer,
                                                          sut.host_path())
        out["objective"] = sut.objective(answer)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sound", action="store_true")
    args = ap.parse_args(argv)
    from bench import harness
    jax = harness.setup_jax()
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, args.sound)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
