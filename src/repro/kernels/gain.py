"""Pallas min-cover kernel of the device-resident partition pass.

The frontier's hot reduction is: given ``uncov`` rows (one per
(candidate, edge) pair, ``2^P`` processor-subset columns), find each row's
minimum-popcount subset with zero uncovered pins -- ``lambda_e`` under the
candidate mask.  ``engine._lambda_from_rows`` does it on the host with an
argmax over popcount-ordered columns; ``front_dlam`` runs the same
reduction as a Pallas TPU kernel (row-tiled grid, one masked min per tile
on the VPU) fused with the cost difference, inside the jitted find
program of ``kernels.front_pass``.

Because the subsets with ``uncov == 0`` always include the full processor
set (every assigned pin is covered by *some* processor), the first zero in
popcount order equals the minimum popcount over all zeros -- which is the
masked-min formulation the kernel uses, avoiding a gather.  Lambdas are
small integers, so the kernel's deltas are bit-identical to the host's.
"""
from __future__ import annotations

import functools

_NO_COVER = 127  # > any popcount for P <= 12; returned only for all-nonzero
                 # rows, which real uncov rows never produce (see docstring)


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
    """Hit/miss/size counters of the per-shape jitted-call cache.

    Read by the cache-bound tests.
    """
    info = _pallas_dlam_call.cache_info()
    return {"dlam": {"hits": info.hits, "misses": info.misses,
                     "size": info.currsize, "maxsize": info.maxsize}}
