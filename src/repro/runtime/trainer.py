"""Fault-tolerant training driver.

Production behaviors implemented and exercised by tests:
  * checkpoint/restart: atomic checkpoints every N steps (async IO
    overlapped with compute); on (re)start the latest step is restored,
    including the data-pipeline cursor -> byte-identical resume;
  * failure handling: any exception in a step triggers restore-from-last-
    checkpoint with bounded retries (``max_failures``), mirroring how a
    TPU pod coordinator restarts after a chip/ICI failure.  A hook lets
    tests inject failures deterministically;
  * straggler mitigation: per-step wall-time watchdog; steps slower than
    ``straggler_factor``x the trailing median are logged and counted --
    on a real pod this signal drives hot-spare swap-in, here it feeds
    metrics (and is unit-tested);
  * elastic re-scaling: ``restore`` accepts a different mesh; the
    checkpointer re-places every shard under the new topology.
"""
from __future__ import annotations

import dataclasses
import statistics
import time

import jax
import numpy as np

from ..checkpoint.checkpointer import Checkpointer
from ..data.pipeline import DataConfig, SyntheticTokenStream
from ..models.config import ModelConfig
from ..optim import adamw
from ..parallel import sharding as shd
from ..train import step as step_lib


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 20
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    max_failures: int = 3
    straggler_factor: float = 3.0
    log_every: int = 10


class Trainer:
    def __init__(self, cfg: ModelConfig, mesh, data_cfg: DataConfig,
                 tcfg: TrainerConfig, opt_cfg: adamw.AdamWConfig | None = None,
                 failure_hook=None):
        self.cfg = cfg
        self.mesh = mesh
        self.tcfg = tcfg
        self.data = SyntheticTokenStream(cfg, data_cfg)
        self.ckpt = Checkpointer(tcfg.ckpt_dir, keep=tcfg.keep)
        self.failure_hook = failure_hook or (lambda step: None)
        self.step_times: list[float] = []
        self.stragglers = 0
        shd.set_active_mesh(mesh)
        self.ts = step_lib.build_train_step(cfg, mesh, opt_cfg=opt_cfg)
        self.opt_cfg = opt_cfg or adamw.AdamWConfig()

    # ------------------------------------------------------------- state
    def fresh_state(self, seed: int = 0):
        from ..models.model import Model
        model = Model(self.cfg,
                      n_ep_shards=self.mesh.shape.get("model", 1))
        with jax.set_mesh(self.mesh):
            params = jax.jit(
                model.init,
                out_shardings=self.ts.state_shardings["params"])(
                jax.random.PRNGKey(seed))
            opt = jax.jit(
                lambda p: adamw.init_state(self.opt_cfg, p),
                out_shardings=self.ts.state_shardings["opt"])(params)
        return {"params": params, "opt": opt}

    def try_restore(self, state):
        last = self.ckpt.latest_step()
        if last is None:
            return state, 0
        restored, extra = self.ckpt.restore(
            last, self.ts.abstract_state, self.ts.state_shardings)
        self.data.restore(extra["data"])
        return restored, int(extra["step"])

    # -------------------------------------------------------------- loop
    def run(self, state=None, seed: int = 0):
        state = state if state is not None else self.fresh_state(seed)
        state, start = self.try_restore(state)
        step = start
        failures = 0
        metrics_hist = []
        while step < self.tcfg.steps:
            try:
                batch_np = self.data.next_batch()
                self.failure_hook(step)  # test injection point
                t0 = time.monotonic()
                with jax.set_mesh(self.mesh):
                    batch = jax.device_put(batch_np)
                    state, metrics = self.ts.step_fn(state, batch)
                    loss = float(metrics["loss"])
                dt = time.monotonic() - t0
                self._watch_straggler(dt, step)
                if not np.isfinite(loss):
                    raise FloatingPointError(f"non-finite loss at {step}")
                metrics_hist.append({"step": step, "loss": loss,
                                     "seconds": dt})
                step += 1
                if step % self.tcfg.ckpt_every == 0 or step == self.tcfg.steps:
                    self.ckpt.save_async(
                        step, state,
                        extra={"step": step, "data": self.data.state()})
                if step % self.tcfg.log_every == 0:
                    print(f"[train] step {step} loss {loss:.4f} "
                          f"({dt*1e3:.0f} ms)", flush=True)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:  # noqa: BLE001 -- pod-level restart path
                failures += 1
                print(f"[train] step {step} FAILED ({type(e).__name__}: {e}); "
                      f"restart {failures}/{self.tcfg.max_failures}",
                      flush=True)
                if failures > self.tcfg.max_failures:
                    raise
                self.ckpt.wait()
                state = self.fresh_state(seed)
                state, step = self.try_restore(state)
        self.ckpt.wait()
        return state, metrics_hist

    def _watch_straggler(self, dt: float, step: int) -> None:
        if len(self.step_times) >= 5:
            med = statistics.median(self.step_times[-20:])
            if dt > self.tcfg.straggler_factor * med:
                self.stragglers += 1
                print(f"[train] straggler at step {step}: {dt*1e3:.0f}ms "
                      f"vs median {med*1e3:.0f}ms", flush=True)
        self.step_times.append(dt)
