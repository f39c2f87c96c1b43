"""Compile the device path's kernels for a described TPU v5e chip.

Nothing here runs on a chip: the TPU compiler is asked for a program for a
chip that is described, not attached, which shows what interpret mode
cannot -- fast-memory (VMEM) overruns, tilings the chip refuses, programs
that do not fit.  The topology is described inside a fixture, so only the
worker that runs this file loads the TPU library, and the file skips where
no topology can be described.  The persistent compile cache is off here: a
program compiled for an absent chip cannot be read back from it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import front_pass, gain

R = 2048   # rows of one front block on the device pass (_R_BLK_MIN)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", saved)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved)


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cols(P: int) -> int:
    return -(-(1 << P) // 128) * 128


@pytest.mark.parametrize("P", [4, 8, 12])
def test_front_dlam_compiles(one_chip, P):
    """P = 12 is the engine's limit: a 4096-wide block once overran VMEM."""
    Mp = _cols(P)
    fn = jax.jit(lambda rows, pc, lam: gain.front_dlam(rows, pc, lam))
    compiled = fn.lower(_spec(one_chip, (R, Mp)), _spec(one_chip, (Mp,)),
                        _spec(one_chip, (R,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", ["fm", "rep"])
def test_find_program_compiles_at_smoke_shape(one_chip, mode):
    """The whole jitted find program at the smoke's partition shapes:
    n = 65534, E = 65493, P = 8, 2048-row blocks, 2048 blocks."""
    n, E, P, n_blocks = 65534, 65493, 8, 2048
    nsub, B_blk = 1 << P, R // P
    fn = front_pass.find_program(mode, n=n, E=E, P=P, R_blk=R,
                                 use_pallas=True, interpret=False)
    s = lambda shape, dtype=jnp.int32: _spec(one_chip, shape, dtype)  # noqa
    scalars = [s(())] * 8
    compiled = fn.lower(
        s((E + 1, nsub)), s((E + 1,)), s((n + 1,)), s((E + 1,)),
        s((nsub, nsub)), s((n + 1, P), jnp.bool_), s((nsub,)), s((nsub,)),
        s((n_blocks, R)), s((n_blocks, R)), s((n_blocks, B_blk)),
        s((n_blocks, B_blk)), s((n_blocks,), jnp.bool_),
        *scalars, s((300,))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel carries its own name into the compiled program, so a
    # trace or an HLO dump tells it from any other Pallas kernel
    assert "front_dlam" in text


@pytest.mark.parametrize("kind, Kp", [("comm", front_pass.DEVICE_COMM_BATCH),
                                      ("comp", 1)])
def test_window_program_compiles_at_symgs_shape(one_chip, kind, Kp):
    """A schedule window program at the SYMGS cell's shapes: 256 padded
    supersteps, P = 8, a batch of ``Kp`` windows of up to 256 supersteps,
    g = 4, L = 20."""
    Sp, P, Wp = 256, 8, 256
    fn = front_pass._win_program(kind, Kp, Wp, 20, 4)
    s = lambda shape: _spec(one_chip, shape)  # noqa
    compiled = fn.lower(s((Sp, P)), s((Sp, P)), s((Sp, 3)), s((Sp, 3)),
                        s((Sp, 3)), s((Sp,)), s((Kp, 4))).compile()
    assert compiled.out_info.shape == (Kp, Wp)
