"""Batched min-cover (gain) kernel: the JAX/Pallas backend of the frontier.

The frontier layer's hot reduction is: given ``uncov`` rows (one per
(candidate, edge) pair, ``2^P`` processor-subset columns), find each row's
minimum-popcount subset with zero uncovered pins -- ``lambda_e`` under the
candidate mask.  ``engine._lambda_from_rows`` does it with an argmax over
popcount-ordered columns; here the same reduction runs as a Pallas TPU
kernel (row-tiled grid, one masked min per tile on the VPU), with a jitted
``jnp`` fallback off-TPU, dispatched by platform exactly like
``kernels/ops.py`` (same ``force``/``_use_pallas`` switch).

Because the subsets with ``uncov == 0`` always include the full processor
set (every assigned pin is covered by *some* processor), the first zero in
popcount order equals the minimum popcount over all zeros -- which is the
masked-min formulation the kernel uses, avoiding a gather.

Lambdas are small integers, so this backend feeds bit-identical values
into the frontier's float64 NumPy cost reduction: backend choice cannot
change a single heuristic decision.
"""
from __future__ import annotations

import functools

import numpy as np

_NO_COVER = 127  # > any popcount for P <= 12; returned only for all-nonzero
                 # rows, which real uncov rows never produce (see docstring)


@functools.lru_cache(maxsize=1)
def _jnp_lambda():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def lam(rows_perm, pc):
        return jnp.min(jnp.where(rows_perm == 0, pc[None, :], _NO_COVER),
                       axis=1).astype(jnp.int32)

    return lam


# Bytes of one (block_r, Mp) int32 input block.  Pallas double-buffers it
# against the TPU's 16 MiB default scoped VMEM: a 512-row block at P = 12
# (Mp = 4096) would need 16 MiB for that alone, while 2 MiB keeps every
# P <= 12 inside the limit with room for the output and lambda blocks.
_BLOCK_BYTES = 2 << 20


def block_rows(Mp: int) -> int:
    """Row-block size for a padded column width ``Mp`` (a multiple of 128).

    A power of two between 8 and 512, so it divides every pow2 row count the
    callers pad to (``R_blk >= 2048`` on the device pass).  Block size
    changes only the kernel's tiling, never a value it returns.
    """
    return max(8, min(512, _BLOCK_BYTES // (Mp * 4)))


# pow2 padding collapses front shapes onto a logarithmic family, but a long
# multilevel run still visits many (Rp, Mp, block_r) triples across levels
# and P values; an unbounded cache would pin every jitted executable for the
# life of the process.  64 entries comfortably covers one run's working set
# (~log2(rows) x few P values) while letting stale shape families fall out.
_PALLAS_CACHE_SIZE = 64


@functools.lru_cache(maxsize=_PALLAS_CACHE_SIZE)
def _pallas_call(Rp: int, Mp: int, block_r: int, interpret: bool):
    """Jitted pallas_call for one padded shape (cached per shape family)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(rows_ref, pc_ref, out_ref):
        lam = jnp.min(jnp.where(rows_ref[:] == 0, pc_ref[:], _NO_COVER),
                      axis=1, keepdims=True)
        out_ref[:] = lam.astype(jnp.int32)

    return jax.jit(pl.pallas_call(
        kernel,
        grid=(Rp // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, Mp), lambda i: (i, 0)),
            pl.BlockSpec((1, Mp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
        interpret=interpret,
        name="min_cover",
    ))


@functools.lru_cache(maxsize=_PALLAS_CACHE_SIZE)
def _pallas_dlam_call(Rp: int, Mp: int, block_r: int, interpret: bool):
    """Fused front kernel: candidate uncov rows + old lambdas -> cost dlam.

    The device-resident pass (``kernels.front_pass``) feeds it the flat
    (pair, edge) expansion of a whole candidate front: each row is one
    (candidate, edge) uncov row in popcount-column order, paired with the
    edge's current lambda.  The kernel fuses the masked-min cover with the
    ``relu(lam_new - 1) - relu(lam_old - 1)`` cost difference on the VPU,
    so the XLA caller only segment-sums integer dlam terms per candidate.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(rows_ref, pc_ref, lam_old_ref, out_ref):
        lam = jnp.min(jnp.where(rows_ref[:] == 0, pc_ref[:], _NO_COVER),
                      axis=1, keepdims=True).astype(jnp.int32)
        out_ref[:] = (jnp.maximum(lam - 1, 0)
                      - jnp.maximum(lam_old_ref[:] - 1, 0))

    return jax.jit(pl.pallas_call(
        kernel,
        grid=(Rp // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, Mp), lambda i: (i, 0)),
            pl.BlockSpec((1, Mp), lambda i: (0, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
        interpret=interpret,
        name="front_dlam",
    ))


def front_dlam(rows_perm, pc, lam_old, *, interpret: bool = False):
    """Per-row integer cost deltas for a candidate front (Pallas path).

    ``rows_perm`` is a (R, M) jnp int32 array of candidate uncov rows in
    popcount-column order (column 0 = subset 0), ``pc`` the (M,) popcounts
    with a ``_NO_COVER`` sentinel at column 0, ``lam_old`` the (R,) current
    edge lambdas.  Returns the (R,) int32 ``relu(lam_new-1)-relu(lam_old-1)``
    terms.  Shapes must be pre-padded by the caller (rows to a power of two
    of at least 512, columns to a multiple of 128): the device-resident pass
    owns the padding, so this traces inside its jitted program.
    """
    R, M = rows_perm.shape
    call = _pallas_dlam_call(R, M, block_rows(M), interpret)
    return call(rows_perm, pc.reshape(1, M),
                lam_old.reshape(R, 1))[:, 0]


def kernel_cache_stats() -> dict:
    """Hit/miss/size counters of the per-shape jitted-call caches.

    Exposed for the benchmarks (``device_resident`` rows record how many
    shape families a run actually compiled) and for the cache-bound tests.
    """
    out = {}
    for name, fn in (("pallas", _pallas_call), ("dlam", _pallas_dlam_call)):
        info = fn.cache_info()
        out[name] = {"hits": info.hits, "misses": info.misses,
                     "size": info.currsize, "maxsize": info.maxsize}
    return out


# One reused pow2 pad buffer per column width for the jnp fallback: the
# previous implementation np.concatenate'd a fresh padded copy per front,
# which at frontier rates (thousands of fronts per refinement pass) spends
# more time in the allocator than in the reduction.  ``_PAD_DIRTY`` tracks
# the high-water row that holds real data, so only rows a previous front
# actually overwrote are re-onesed (the sentinel value) before reuse.
_PAD_BUFS: dict[int, np.ndarray] = {}
_PAD_DIRTY: dict[int, int] = {}


def _padded_rows(rows_perm: np.ndarray, Rp: int) -> np.ndarray:
    R, M = rows_perm.shape
    buf = _PAD_BUFS.get(M)
    if buf is None or buf.shape[0] < Rp:
        buf = np.ones((Rp, M), dtype=np.int32)
        _PAD_BUFS[M] = buf
        _PAD_DIRTY[M] = 0
    dirty = _PAD_DIRTY[M]
    if dirty > R:
        buf[R:dirty] = 1
    buf[:R] = rows_perm
    _PAD_DIRTY[M] = R
    return buf[:Rp]


def _pallas_lambda(rows_perm: np.ndarray, pc: np.ndarray,
                   interpret: bool = False):
    R, M = rows_perm.shape
    Mp = -(-M // 128) * 128
    block_r = block_rows(Mp)
    # pow2 row padding (>= one block): ragged front sizes collapse onto a
    # logarithmic family of shapes, so the cached jitted pallas_call does
    # not recompile per front
    Rp = max(1 << max(R - 1, 1).bit_length(), block_r)
    # pad columns with a non-zero sentinel (never a cover) and rows with
    # all-ones (their lambda is dropped after the call)
    rows_p = np.ones((Rp, Mp), dtype=np.int32)
    rows_p[:R, :M] = rows_perm
    pc_p = np.full((1, Mp), _NO_COVER, dtype=np.int32)
    pc_p[0, :M] = pc
    out = _pallas_call(Rp, Mp, block_r, interpret)(rows_p, pc_p)
    return out[:R, 0]


def min_cover_lambdas(rows: np.ndarray, order: np.ndarray,
                      order_pc: np.ndarray, *,
                      interpret: bool = False) -> np.ndarray:
    """Min-cover size per uncov row (jax path of ``price_mask_front``).

    Drop-in for ``engine._lambda_from_rows``: ``rows`` is (R, 2^P) with
    column 0 the assigned-pin count, ``order``/``order_pc`` the engine's
    popcount-ordered non-empty subsets and their popcounts.  Rows with no
    assigned pin get lambda 0 (handled host-side, so the kernel is a pure
    masked min).  The row count is padded up to the next power of two
    (all-ones sentinel rows, dropped after the call) so jit sees a bounded
    family of shapes instead of recompiling per front size.
    """
    from .ops import _use_pallas

    R = rows.shape[0]
    if R == 0:
        return np.zeros(0, dtype=np.int16)
    rows_perm = np.ascontiguousarray(rows[:, order], dtype=np.int32)
    pc = np.asarray(order_pc, dtype=np.int32)
    if _use_pallas():
        lam = _pallas_lambda(rows_perm, pc, interpret=interpret)
    else:
        Rp = 1 << max(R - 1, 1).bit_length()
        if Rp != R:
            rows_perm = _padded_rows(rows_perm, Rp)
        lam = _jnp_lambda()(rows_perm, pc)[:R]
    lam = np.asarray(lam, dtype=np.int16)
    lam[rows[:, 0] == 0] = 0
    return lam
