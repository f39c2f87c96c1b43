"""CPU rehearsal of ``chip_smoke.py`` and of the rules it relies on.

The smoke's phase functions run here at tiny sizes with the device floors
lowered and the Pallas kernel forced into interpret mode, under the same
bit-identity and sync-count checks the chip run makes.  ``main`` must
refuse a machine without a TPU.  A worker pool created after JAX has run
must not fork, and its workers never initialise a backend.  The compile
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to the fixed
``<repo>/.jax_cache``.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core.partition import parallel as par
from repro.datagen import large_row_net, large_sptrsv_dag
from repro.kernels import front_pass, ops
from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def small_floors(monkeypatch):
    """Floors low enough that every refined level of a tiny instance runs
    on the device and the worker pool engages."""
    monkeypatch.setattr(front_pass, "DEVICE_MIN_NODES", 256)
    monkeypatch.setattr(front_pass, "DEVICE_MIN_WINDOW", 1)
    monkeypatch.setattr(front_pass, "DEVICE_MIN_STEPS", 1)
    monkeypatch.setattr(par, "PARALLEL_MIN_NODES", 256)


def test_partition_phase_rehearsal(smoke, small_floors):
    ops.force("pallas")            # interpret mode: the CPU stand-in
    try:
        out = smoke.partition_phase(large_row_net(1024, seed=0), P=4,
                                    eps=0.1, interpret=True)
    finally:
        ops.force(None)
    assert len(out["device_levels"]) >= 2
    for tot in out["device_levels"].values():
        assert tot["commits"] <= tot["syncs"] <= (tot["commits"]
                                                  + tot["pass_scans"])
    assert out["rep_cost"] <= out["base_cost"]


def test_partition_phase_rejects_wrong_kernel_mode(smoke, small_floors):
    """The phase fails when a device pass ran in another kernel mode than
    the one asked for (here: the jnp reference instead of Pallas)."""
    with pytest.raises(AssertionError, match="device passes ran as"):
        smoke.partition_phase(large_row_net(1024, seed=0), P=4, eps=0.1,
                              interpret=True)


def test_schedule_phase_rehearsal(smoke, small_floors):
    out = smoke.schedule_phase(large_sptrsv_dag(n=2000, seed=0), P=4, g=2,
                               L=4)
    assert out["window_attaches"] > 0 and out["window_syncs"] > 0


def test_workers_phase_rehearsal(smoke, small_floors):
    jnp.zeros(1).block_until_ready()     # the parent holds a backend now
    out = smoke.workers_phase(large_row_net(1024, seed=0), P=4, eps=0.1)
    assert out["rep_cost"] <= out["base_cost"]


def test_main_refuses_cpu(smoke, capsys, monkeypatch, tmp_path):
    # an explicit cache dir keeps main() from re-pointing this process's
    # compile cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax.default_backend() == "cpu"
    assert smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_fails_without_tpu_or_repo(tmp_path):
    """As a command: non-zero and no verdict without a TPU, both in the
    repository and as a lone copy of the script."""
    lone = tmp_path / "lone"
    lone.mkdir()
    shutil.copy(ROOT / "chip_smoke.py", lone)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    for cwd in (ROOT, lone):
        run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                             env=env, capture_output=True, text=True,
                             timeout=300)
        assert run.returncode != 0, run.stdout
        assert '"ok"' not in run.stdout


def _worker_backend_live(_):
    return par.jax_backend_live()


def test_pool_after_jax_does_not_fork():
    """One process per chip: once this process holds a JAX backend, its
    pool spawns, an explicit fork is refused, and workers that ran a real
    refinement still hold no backend."""
    from repro.core.partition import PartitionState, partition_heuristic
    jnp.zeros(1).block_until_ready()
    assert par.jax_backend_live()
    hg = large_row_net(1200, seed=1)
    res = partition_heuristic(hg, 4, 0.1, seed=0)
    st = PartitionState(hg, 4, masks=res.masks.copy())
    with par.ParallelContext(2, min_nodes=64) as ctx:
        par.parallel_refine(hg, st, 4, 0.1, ctx, "fm", 1, seed=0)
        assert not ctx.failed and ctx.start_method == "spawn"
        assert ctx.run(_worker_backend_live, [0, 1]) == [False, False]
    with par.ParallelContext(2, start_method="fork") as ctx:
        with pytest.raises(RuntimeError, match="JAX backend"):
            ctx.run(_worker_backend_live, [0])


def test_compile_cache_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there."""
    cache = tmp_path / "cache"
    code = ("import jax, jax.numpy as jnp; "
            "from repro.launch.compile_cache import enable_compile_cache; "
            "print(enable_compile_cache()); "
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0); jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=str(ROOT / "src"))
    run = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    assert run.stdout.strip() == str(cache)
    assert any(cache.iterdir())


def test_compile_cache_default_dir(monkeypatch):
    """Unset, the cache goes to the fixed, git-ignored <repo>/.jax_cache."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    saved = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored

