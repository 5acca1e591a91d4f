"""Fused feature-scaling moments kernel: one pass → (Σx, max|x|).

Paper §4.2 runs two SQL aggregates (AVG, MAX(ABS)) per feature over the
union of relations containing it.  A memory-bound op like this should touch
HBM exactly once, so the kernel fuses both reductions into a single stream:
the column arrives lane-dense as ``[M/128, 128]``, and each ``[bm, 128]``
block is folded on the VPU into two ``[8, 128]`` partial accumulators held
in VMEM across the 1-D grid (vector stores only — a TPU kernel cannot store
a scalar to VMEM).  The wrapper finishes the 1024 partials.

Padding: the wrapper zero-pads to a block multiple — zeros do not change the
sum, and max(|x|, 0) = max|x| since |·| ≥ 0.  The true element count is
returned by the wrapper (it is static), completing the AVG.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["moments_kernel_call"]

DEFAULT_BM = 512  # rows of 128 lanes per block


def _moments_kernel(x_ref, sum_ref, max_ref):
    m = pl.program_id(0)

    @pl.when(m == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        max_ref[...] = jnp.zeros_like(max_ref)

    x = x_ref[...].astype(jnp.float32)  # [bm, 128]
    tiles = x.reshape(x.shape[0] // 8, 8, 128)  # sublane-tile aligned
    sum_ref[...] += jnp.sum(tiles, axis=0)
    max_ref[...] = jnp.maximum(max_ref[...], jnp.max(jnp.abs(tiles), axis=0))


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def moments_kernel_call(
    x: jnp.ndarray, bm: int = DEFAULT_BM, interpret: bool = True
):
    """Raw pallas_call on a padded lane-dense ``[M, 128]`` column block
    (``M % bm == 0``, ``bm % 8 == 0``).  Returns ``(sum, maxabs)`` partials,
    each f32 ``[8, 128]``.  Use ``ops.moments``."""
    m, lanes = x.shape
    assert lanes == 128 and m % bm == 0 and bm % 8 == 0, (x.shape, bm)
    part = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    return pl.pallas_call(
        _moments_kernel,
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, 128), lambda mm: (mm, 0))],
        out_specs=[
            pl.BlockSpec((8, 128), lambda mm: (0, 0)),
            pl.BlockSpec((8, 128), lambda mm: (0, 0)),
        ],
        out_shape=[part, part],
        interpret=interpret,
    )(x)
