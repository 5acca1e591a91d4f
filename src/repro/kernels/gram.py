"""Blocked Gram / cofactor kernel: out = X^T X  (the paper's hot aggregate).

The non-factorized ("noPre") cofactor computation and the per-relation leaf
cofactors are Gram matrices over tall-skinny design matrices.  On TPU the
natural blocking is:

  * grid (nk_i, nk_j, nm): output tile (i, j) of shape [bk, bk] stays
    resident in VMEM while the kernel streams [bm, bk] input tiles of X
    from HBM, accumulating partial products on the MXU,
  * bk is a multiple of 128 (MXU lane width) and bm a multiple of 8
    (sublane), so ``x_i^T @ x_j`` maps onto full systolic passes,
  * accumulation is always fp32 (``preferred_element_type``), independent of
    the input dtype (bf16 inputs hit the MXU's native mixed-precision path).

VMEM working set per step: 2·bm·bk·dtype + bk·bk·4 bytes — with the default
bm=512, bk=128 and bf16 inputs that is 2·512·128·2 + 128·128·4 ≈ 0.33 MiB,
far under the ~16 MiB VMEM budget, leaving room for double buffering.

The wrapper (`ops.gram`) zero-pads M and K to block multiples — zero rows or
columns contribute nothing to X^T X, so no in-kernel masking is needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["gram_kernel_call"]

DEFAULT_BM = 512
DEFAULT_BK = 128


def _gram_kernel(x_i_ref, x_j_ref, out_ref):
    """One (i, j, m) grid step: out[i, j] += x[m, i]^T @ x[m, j]."""
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x_i = x_i_ref[...]
    x_j = x_j_ref[...]
    out_ref[...] += jax.lax.dot_general(
        x_i,
        x_j,
        dimension_numbers=(((0,), (0,)), ((), ())),  # contract over rows
        preferred_element_type=jnp.float32,
        # f32 operands would otherwise take one bf16 MXU pass
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def gram_kernel_call(
    x: jnp.ndarray,
    bm: int = DEFAULT_BM,
    bk: int = DEFAULT_BK,
    interpret: bool = True,
) -> jnp.ndarray:
    """Raw pallas_call on an already-padded [M, K] matrix (M % bm == 0,
    K % bk == 0).  Returns fp32 [K, K].  Use ``ops.gram`` for arbitrary
    shapes."""
    m, k = x.shape
    assert m % bm == 0 and k % bk == 0, (m, k, bm, bk)
    nm, nk = m // bm, k // bk
    return pl.pallas_call(
        _gram_kernel,
        grid=(nk, nk, nm),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, mm: (mm, i)),
            pl.BlockSpec((bm, bk), lambda i, j, mm: (mm, j)),
        ],
        out_specs=pl.BlockSpec((bk, bk), lambda i, j, mm: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k, k), jnp.float32),
        interpret=interpret,
    )(x, x)
