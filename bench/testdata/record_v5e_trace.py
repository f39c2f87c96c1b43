"""Record ``v5e_find.xplane.pb``, the small TPU trace the reduction's test
reads: three ``solve`` annotations, each around two runs of a jitted
``find`` whose ``while`` loop calls the Pallas ``front_dlam`` kernel at the
device pass's block shape (2,048 rows of 256 subset columns).

    python3 bench/testdata/record_v5e_trace.py <out-dir>

Run it on one TPU chip; it writes the trace to ``<out-dir>/v5e_find.xplane.pb``
and prints what the reduction reads from it.
"""
from __future__ import annotations

import glob
import json
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

R_BLK, MP, STEPS = 2048, 256, 4


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from bench import trace_reduce
    from repro.kernels.gain import front_dlam

    if jax.devices()[0].platform != "tpu":
        print("record_v5e_trace: no TPU", file=sys.stderr)
        return 1

    def find(rows, pc, lam):
        def body(i, acc):
            return acc + front_dlam(rows + i, pc, lam)
        return jax.lax.fori_loop(0, STEPS, body, jnp.zeros_like(lam))

    find = jax.jit(find)
    key = jax.random.key(0)
    rows = jax.random.randint(key, (R_BLK, MP), 0, 3, dtype=jnp.int32)
    pc = jnp.arange(MP, dtype=jnp.int32) % 9
    lam = jnp.ones((R_BLK,), jnp.int32)
    find(rows, pc, lam).block_until_ready()          # compile outside
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("solve"):
            for _ in range(2):
                find(rows, pc, lam).block_until_ready()
    jax.profiler.stop_trace()
    src, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "v5e_find.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    r = trace_reduce.reduce_file(dst)
    print(json.dumps({"bytes": os.path.getsize(dst), "devices": r["devices"],
                      "solves": r["solves"], "window_s": r["window_s"],
                      "busy_s": r["busy_s"], "modules": r["modules"],
                      "custom_calls": {k: len(v) for k, v in
                                       r["custom_calls"].items()},
                      "device_ops": r["device_ops"][:5]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
