import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real train/prefill/decode step with
ShapeDtypeStruct inputs (no allocation), compiles it, and records
``memory_analysis`` / ``cost_analysis`` / HLO-parsed collective bytes into
``benchmarks/results/dryrun/<cell>.json`` for the roofline analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch olmoe-1b-7b \
        --shape train_4k [--multi-pod] [--all] [--placement plan.json]
"""
import argparse
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (SHAPES, cell_is_applicable, get_config, input_specs,
                           list_archs)
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.parallel import sharding as shd
from repro.roofline.hlo import collective_bytes_from_text, summarize_cost
from repro.train import step as step_lib

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def shard_batch_abstract(cfg, mesh, abstract_batch):
    sh = step_lib.batch_specs(cfg, mesh, abstract_batch)
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh[k])
            for k, v in abstract_batch.items()}


def with_shardings(abstract_tree, shardings_tree):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract_tree, shardings_tree)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             plan=None, tag: str = "", overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.with_(**overrides)
    ok, why = cell_is_applicable(cfg, shape_name)
    cell = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}{tag}"
    if not ok:
        return {"cell": cell, "status": "skipped", "reason": why}
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    shd.set_active_mesh(mesh)
    shape = SHAPES[shape_name]
    try:
        with jax.set_mesh(mesh):
            if shape.kind == "train":
                ts = step_lib.build_train_step(cfg, mesh, plan=plan)
                ab = input_specs(cfg, shape_name)
                batch = shard_batch_abstract(cfg, mesh, ab)
                state = with_shardings(ts.abstract_state, ts.state_shardings)
                lowered = ts.step_fn.lower(state, batch)
            elif shape.kind == "prefill":
                sv = step_lib.build_serve_steps(cfg, mesh, shape.global_batch,
                                                shape.seq_len, plan=plan)
                ab = input_specs(cfg, shape_name)
                batch = shard_batch_abstract(cfg, mesh, ab)
                params = with_shardings(sv.abstract_params, sv.param_shardings)
                lowered = sv.prefill_fn.lower(params, batch)
            else:  # decode
                sv = step_lib.build_serve_steps(cfg, mesh, shape.global_batch,
                                                shape.seq_len, plan=plan)
                params = with_shardings(sv.abstract_params, sv.param_shardings)
                caches = with_shardings(sv.abstract_caches, sv.cache_shardings)
                tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
                if cfg.frame_input:
                    tok = jax.ShapeDtypeStruct(
                        (shape.global_batch, 1, cfg.d_model), jnp.dtype(cfg.dtype))
                pos = jax.ShapeDtypeStruct((), jnp.int32)
                lowered = sv.decode_fn.lower(params, tok, caches, pos)
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            coll = collective_bytes_from_text(compiled.as_text())
    finally:
        shd.set_active_mesh(None)
    n_chips = int(np.prod(list(mesh.shape.values())))
    out = {
        "cell": cell,
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "mesh": dict(mesh.shape),
        "chips": n_chips,
        "seconds": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "cost": summarize_cost(cost),
        "collectives": coll,
        "params": get_config(arch).param_count(),
        "active_params": get_config(arch).active_param_count(),
    }
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    RESULTS.mkdir(parents=True, exist_ok=True)
    archs = list_archs() if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cell = f"{arch}__{shape}__{'pod2' if mp else 'pod1'}{args.tag}"
                path = RESULTS / f"{cell}.json"
                if args.skip_existing and path.exists():
                    print(f"[dryrun] {cell}: cached", flush=True)
                    continue
                try:
                    out = run_cell(arch, shape, mp, tag=args.tag)
                except Exception as e:  # noqa: BLE001
                    out = {"cell": cell, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    failures += 1
                path.write_text(json.dumps(out, indent=1))
                status = out["status"]
                extra = (f" flops={out['cost'].get('flops', 0):.3g}"
                         f" coll={out['collectives'].get('total_bytes', 0):.3g}B"
                         f" peak={out['memory']['peak_bytes']}"
                         if status == "ok" else
                         out.get("reason", out.get("error", "")))
                print(f"[dryrun] {cell}: {status} {extra}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
