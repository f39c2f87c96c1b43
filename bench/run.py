"""Run one cell of the chip benchmark.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  It exits non-zero, with no result, where JAX
finds no TPU or fewer chips than the cell asks for.  The last line of its
standard output is the JSON result; the numbers it compared, each beside
its limit, are the last lines of its standard error.  ``bench/harness.py``
describes a run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)


if __name__ == "__main__":
    sys.exit(main())
