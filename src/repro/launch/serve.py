"""Batched serving launcher: prefill + decode over a request queue.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
        --requests 8 --prompt-len 32 --gen 16 [--replicated-placement]

Serving is where the paper's replication technique applies (its MoE traces
come from the decode phase): with ``--replicated-placement`` the engine
profiles router co-activation on warmup traffic, plans a replicated expert
placement (hypergraph partitioning with replication), rebuilds the decode
step with the plan and reports the (lambda_e - 1) communication cost next
to the round-robin baseline.

``--online-placement`` runs the *continuous* version instead: router
traffic is fed to ``core.placement.online.OnlineController`` in epochs;
the controller maintains a live incremental ``PartitionState``, detects
traffic drift, and only migrates expert weights when the projected comm
savings beat the migration bytes (see ``benchmarks/serving.py`` for the
large-scale replay).  The final plan serves the generation phase.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs, reduce_config
from repro.core.placement.expert_placement import (evaluate_plan,
                                                   plan_expert_placement)
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model
from repro.parallel import sharding as shd


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--replicated-placement", action="store_true")
    ap.add_argument("--online-placement", action="store_true",
                    help="drift-aware epoch controller instead of a "
                         "one-shot warmup plan")
    ap.add_argument("--epochs", type=int, default=6,
                    help="router-traffic epochs for --online-placement")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg, layers_per_segment=args.layers)
    rng = np.random.default_rng(0)
    B, S, G = args.requests, args.prompt_len, args.gen
    max_len = S + G

    mesh = make_host_mesh()
    shd.set_active_mesh(mesh)
    plan = None
    model = Model(cfg, n_ep_shards=mesh.shape.get("model", 1))
    params = model.init(jax.random.PRNGKey(0))

    prompts = rng.integers(0, cfg.vocab, (B, S)).astype(np.int32)
    batch = {"tokens": jnp.asarray(prompts)}
    if cfg.n_image_tokens:
        batch["image_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_image_tokens, cfg.d_model)), jnp.float32)

    if args.online_placement and cfg.n_experts:
        # --- continuous placement: epoch controller over router traffic ---
        from repro.core.placement.online import OnlineController
        n_sh = max(mesh.shape.get("model", 1), 2)
        slots = cfg.n_experts // n_sh + max(2, cfg.n_experts // (4 * n_sh))
        ctrl = OnlineController(
            cfg.n_experts, n_sh, slots,
            kappa0=min(1000, 8 * B * S), warmup_epochs=2,
            bytes_per_expert=3 * cfg.d_model * cfg.d_ff_expert * 2
            if getattr(cfg, "d_ff_expert", 0) else 1 << 20)
        for epoch in range(args.epochs):
            ep_prompts = rng.integers(0, cfg.vocab, (B, S)).astype(np.int32)
            traces = model.route_trace(params,
                                       {"tokens": jnp.asarray(ep_prompts)})
            chunk = np.sort(np.asarray(traces[0]).reshape(-1, cfg.top_k),
                            axis=1)
            rep = ctrl.step(chunk)
            if rep.plan is None:
                print(f"[serve] epoch {epoch}: warming up accumulator")
            else:
                print(f"[serve] epoch {epoch}: cost {rep.cost_keep:.1f} -> "
                      f"{rep.cost_new:.1f}, containment "
                      f"{rep.containment:.2f}, "
                      + (f"migrated {rep.migration_bytes >> 20} MiB"
                         if rep.committed else "kept placement"))
        if mesh.shape.get("model", 1) >= 2 and ctrl.plan is not None:
            plan = ctrl.plan
            model = Model(cfg, plan=plan)
    elif args.replicated_placement and cfg.n_experts:
        # --- profile router on warmup traffic, plan replicated placement ---
        traces = model.route_trace(params, {"tokens": jnp.asarray(prompts)})
        trace = np.asarray(traces[0]).reshape(-1, cfg.top_k)
        n_sh = mesh.shape.get("model", 1)
        res = plan_expert_placement(np.sort(trace, axis=1), cfg.n_experts,
                                    max(n_sh, 2), kappa0=min(1000, 8 * len(trace)))
        print(f"[serve] placement: lambda-cost {res.lambda_cost_no_repl:.1f} "
              f"-> {res.lambda_cost_repl:.1f} with replication; "
              f"local fraction {res.local_fraction_no_repl:.2f} -> "
              f"{res.local_fraction_repl:.2f}")
        if n_sh >= 2:
            plan = res.plan
            model = Model(cfg, plan=plan)

    with jax.set_mesh(mesh):
        t0 = time.time()
        logits, caches = jax.jit(
            lambda p, b: model.prefill(p, b, max_len))(params, batch)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out_tokens = [np.asarray(tok)]
        decode = jax.jit(
            lambda p, t, c, pos: model.decode_step(p, t, c, pos))
        for i in range(G - 1):
            logits, caches = decode(params, tok, caches, jnp.int32(S + i))
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            out_tokens.append(np.asarray(tok))
        dt = time.time() - t0
    gen = np.concatenate(out_tokens, axis=1)
    print(f"[serve] {B} requests, prompt {S}, generated {G} tokens each "
          f"in {dt:.2f}s ({B*G/dt:.1f} tok/s)")
    print(f"[serve] sample continuation ids: {gen[0][:12].tolist()}")


if __name__ == "__main__":
    main()
