"""Substrate tests: data pipeline, checkpointing, fault-tolerant trainer,
optimizer, expert-placement integration."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_config, reduce_config
from repro.core.placement.expert_placement import (evaluate_plan,
                                                   plan_expert_placement)
from repro.data.pipeline import DataConfig, SyntheticTokenStream
from repro.datagen import synthetic_trace
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model
from repro.optim import adamw
from repro.parallel import sharding as shd
from repro.runtime.trainer import Trainer, TrainerConfig


@pytest.fixture(autouse=True)
def _reset_active_mesh():
    """A ``Trainer`` installs its mesh process-wide; drop it after each test
    so later tests in the same worker see the no-mesh (dense) model path."""
    yield
    shd.set_active_mesh(None)


def test_data_determinism_and_resume():
    cfg = reduce_config(get_config("smollm-135m"))
    dc = DataConfig(global_batch=4, seq_len=16, seed=3)
    a = SyntheticTokenStream(cfg, dc)
    batches = [a.next_batch() for _ in range(5)]
    b = SyntheticTokenStream(cfg, dc)
    b.restore({"step": 3})
    resumed = b.next_batch()
    np.testing.assert_array_equal(batches[3]["tokens"], resumed["tokens"])
    assert batches[0]["tokens"].max() < cfg.vocab
    # different steps differ
    assert not np.array_equal(batches[0]["tokens"], batches[1]["tokens"])


def test_checkpoint_roundtrip_and_gc(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": [jnp.ones((4,), jnp.bfloat16), jnp.zeros((), jnp.int32)]}
    for step in (1, 2, 3):
        ck.save(step, tree, extra={"step": step, "data": {"step": step}})
    assert ck.latest_step() == 3
    # keep=2 -> step 1 collected
    assert not (pathlib.Path(tmp_path) / "step_00000001").exists()
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    restored, extra = ck.restore(3, abstract)
    assert extra["step"] == 3
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    assert restored["b"][0].dtype == jnp.bfloat16


def test_checkpoint_async_atomic(tmp_path):
    ck = Checkpointer(tmp_path, keep=3)
    tree = {"w": jnp.ones((8, 8))}
    ck.save_async(5, tree, extra={"step": 5, "data": {"step": 5}})
    ck.wait()
    assert ck.latest_step() == 5
    assert not list(pathlib.Path(tmp_path).glob("*.tmp"))


def test_trainer_loss_decreases(tmp_path):
    cfg = reduce_config(get_config("smollm-135m"), layers_per_segment=2)
    mesh = make_host_mesh()
    tcfg = TrainerConfig(steps=12, ckpt_every=6, ckpt_dir=str(tmp_path),
                         log_every=100)
    ocfg = adamw.AdamWConfig(lr=5e-3, warmup_steps=2, total_steps=12)
    tr = Trainer(cfg, mesh, DataConfig(4, 32), tcfg, ocfg)
    _, hist = tr.run()
    assert len(hist) == 12
    assert hist[-1]["loss"] < hist[0]["loss"], \
        f"{hist[0]['loss']} -> {hist[-1]['loss']}"


def test_trainer_restart_after_failure(tmp_path):
    """Inject a failure mid-run; trainer must restore from checkpoint and
    finish, and the metric history must cover all steps after restart."""
    cfg = reduce_config(get_config("smollm-135m"), layers_per_segment=1)
    mesh = make_host_mesh()
    boom = {"armed": True}

    def failure_hook(step):
        if step == 8 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected chip failure")

    tcfg = TrainerConfig(steps=10, ckpt_every=4, ckpt_dir=str(tmp_path),
                         max_failures=2, log_every=100)
    tr = Trainer(cfg, mesh, DataConfig(2, 16), tcfg,
                 adamw.AdamWConfig(lr=1e-3, total_steps=10),
                 failure_hook=failure_hook)
    _, hist = tr.run()
    assert not boom["armed"]          # failure fired
    steps = [h["step"] for h in hist]
    assert steps[-1] == 9             # ran to completion
    assert 8 in steps                 # the failed step was re-executed
    # restart resumed from step 8 (last ckpt), not from scratch
    assert steps.count(8) >= 1 and 0 not in steps[steps.index(8):]


def test_trainer_resume_from_disk(tmp_path):
    """A brand-new Trainer process picks up where the old one stopped."""
    cfg = reduce_config(get_config("smollm-135m"), layers_per_segment=1)
    mesh = make_host_mesh()
    dc = DataConfig(2, 16)
    t1 = Trainer(cfg, mesh, dc,
                 TrainerConfig(steps=6, ckpt_every=3, ckpt_dir=str(tmp_path),
                               log_every=100),
                 adamw.AdamWConfig(total_steps=12))
    t1.run()
    t2 = Trainer(cfg, mesh, dc,
                 TrainerConfig(steps=10, ckpt_every=3, ckpt_dir=str(tmp_path),
                               log_every=100),
                 adamw.AdamWConfig(total_steps=12))
    _, hist = t2.run()
    assert hist[0]["step"] == 6       # resumed, not restarted


def test_straggler_detection():
    cfg = reduce_config(get_config("smollm-135m"), layers_per_segment=1)
    mesh = make_host_mesh()
    tr = Trainer(cfg, mesh, DataConfig(2, 16),
                 TrainerConfig(steps=1, ckpt_dir="/tmp/_unused_ck"),
                 adamw.AdamWConfig())
    tr.step_times = [0.1] * 10
    tr._watch_straggler(0.5, 11)      # 5x median
    assert tr.stragglers == 1
    tr._watch_straggler(0.11, 12)
    assert tr.stragglers == 1


def test_adamw_converges_quadratic():
    ocfg = adamw.AdamWConfig(lr=0.1, warmup_steps=1, total_steps=200,
                             weight_decay=0.0)
    params = {"w": jnp.array([3.0, -2.0])}
    state = adamw.init_state(ocfg, params)
    for _ in range(150):
        g = {"w": 2 * state["master"]["w"]}
        params, state, _ = adamw.apply_updates(ocfg, state, g, params)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_expert_placement_reduces_lambda_cost():
    """End-to-end paper pipeline: trace -> hypergraph -> replication plan;
    the replicated plan must cost no more than the baseline and raise the
    local fraction."""
    trace = synthetic_trace(n_experts=32, n_tokens=5000, top_k=4, seed=0)
    res = plan_expert_placement(trace, 32, 4, eps=0.5, kappa0=400)
    assert res.lambda_cost_repl <= res.lambda_cost_no_repl + 1e-9
    assert res.local_fraction_repl >= res.local_fraction_no_repl
    ev = evaluate_plan(res.plan, trace, kappa0=400)
    assert ev["replicated_experts"] >= 1
    # the plan covers every expert
    local = np.array(res.plan.local_slot)
    assert np.all((local >= 0).sum(axis=0) >= 1)


def test_route_trace_shapes():
    cfg = reduce_config(get_config("olmoe-1b-7b"), layers_per_segment=2)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)),
                                   jnp.int32)}
    traces = model.route_trace(params, batch)
    assert len(traces) == 1
    L, T, k = traces[0].shape
    assert (L, T, k) == (2, 32, cfg.top_k)
    assert int(traces[0].max()) < cfg.n_experts


def test_plan_remat_directions():
    """BSP-replication->remat bridge: big models at long seq must choose
    recompute (replication); tiny models with headroom must not."""
    from repro.core.placement import plan_remat
    big = plan_remat(get_config("yi-34b"), B=256, S=4096, dp=16, tp=16)
    assert big.policy == "full"
    assert big.save_bytes > 8e9 or big.recompute_seconds < big.save_seconds
    small = plan_remat(reduce_config(get_config("smollm-135m")),
                       B=2, S=64, dp=1, tp=1)
    assert small.policy == "none"
    assert small.fits_budget
