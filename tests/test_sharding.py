"""Distribution tests under a real multi-device (host) mesh.

Runs in a subprocess so XLA_FLAGS can force 8 host devices without
polluting the single-device test session (same pattern as the dry-run).
"""
import json
import os
import pathlib
import subprocess
import sys

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduce_config
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.models.moe import plan_from_masks
from repro.parallel import sharding as shd
from repro.train import step as step_lib
from repro.optim import adamw

out = {}
mesh = make_mesh((2, 4), ("data", "model"))
shd.set_active_mesh(mesh)

# 1) sharded train step compiles AND runs for a dense + a MoE arch
for arch in ("smollm-135m", "olmoe-1b-7b"):
    cfg = reduce_config(get_config(arch)).with_(strategy="tp")
    with jax.set_mesh(mesh):
        ts = step_lib.build_train_step(cfg, mesh,
                                       adamw.AdamWConfig(lr=5e-3, warmup_steps=1, total_steps=8))
        from repro.models.model import Model as M
        model = M(cfg, n_ep_shards=4)
        params = jax.jit(model.init,
                         out_shardings=ts.state_shardings["params"])(
            jax.random.PRNGKey(0))
        opt = jax.jit(lambda p: adamw.init_state(
            adamw.AdamWConfig(lr=5e-3, warmup_steps=1, total_steps=8), p),
            out_shardings=ts.state_shardings["opt"])(params)
        state = {"params": params, "opt": opt}
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)),
                                       jnp.int32)}
        losses = []
        for _ in range(3):
            state, metrics = ts.step_fn(state, batch)
            losses.append(float(metrics["loss"]))
        out[arch] = losses

# 2) replication-aware placement runs and matches dense numerics
cfg = reduce_config(get_config("olmoe-1b-7b")).with_(strategy="tp")
model_ref = Model(cfg)
params = model_ref.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)}
shd.set_active_mesh(None)
loss_ref, _ = model_ref.loss(params, batch)
shd.set_active_mesh(mesh)
masks = np.array([0b1111 if e < 2 else (1 << (e % 4))
                  for e in range(cfg.n_experts)])
plan = plan_from_masks(masks, cfg.n_experts, 4, capacity_factor=8.0)
with jax.set_mesh(mesh):
    model_r = Model(cfg, plan=plan)
    loss_rep, _ = jax.jit(model_r.loss)(params, batch)
out["placement"] = [float(loss_ref), float(loss_rep)]
print("RESULT" + json.dumps(out))
"""


def test_sharded_training_and_placement():
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][-1]
    out = json.loads(line[len("RESULT"):])
    for arch in ("smollm-135m", "olmoe-1b-7b"):
        losses = out[arch]
        assert all(l > 0 and l == l for l in losses), losses
        assert losses[-1] < losses[0], f"{arch}: no learning {losses}"
    ref, rep = out["placement"]
    assert abs(ref - rep) < 0.12, f"placement path diverges: {ref} vs {rep}"
