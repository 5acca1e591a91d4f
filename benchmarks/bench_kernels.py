"""Kernel-layer benchmark: the factorized engine's hot aggregates.

On this CPU container the Pallas kernels run in interpret mode (Python-level
— their timing is meaningless); what CAN be measured honestly here is the
XLA-compiled jnp formulation that the kernels replace, plus arithmetic-
intensity bookkeeping for the §Roofline narrative.  Pallas correctness is
covered by tests/test_kernels.py; TPU wall-time belongs to real hardware.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

from .common import emit, timeit


def run(gram_shapes=((4096, 16), (65536, 16), (65536, 64)),
        seg_shapes=((65536, 16, 64),), attn_shapes=((8, 1024, 64),)) -> list:
    rows = []
    key = jax.random.key(0)
    for m, k in gram_shapes:
        x = jax.random.normal(key, (m, k), jnp.float32)
        gram = jax.jit(ref.gram_ref)
        t = timeit(lambda: jax.block_until_ready(gram(x)), repeats=5)
        flops = 2.0 * m * k * k
        rows.append(
            {
                "op": "gram(X^T X)",
                "shape": f"{m}x{k}",
                "sec": t,
                "gflops_s": flops / t / 1e9,
                "arith_intensity": flops / (4.0 * (m * k + k * k)),
            }
        )
    for m, k, g in seg_shapes:
        x = jax.random.normal(key, (m, k), jnp.float32)
        seg = jax.random.randint(key, (m,), 0, g)
        sg = jax.jit(lambda x, s, g=g: ref.segment_gram_ref(x, s, g))
        t = timeit(lambda: jax.block_until_ready(sg(x, seg)), repeats=5)
        flops = 2.0 * m * k * k
        rows.append(
            {
                "op": "segment_gram",
                "shape": f"{m}x{k}x{g}",
                "sec": t,
                "gflops_s": flops / t / 1e9,
                "arith_intensity": flops / (4.0 * (m * k + g * k * k)),
            }
        )
    for bh, s, d in attn_shapes:
        q = jax.random.normal(key, (bh, s, d), jnp.float32)
        fl = jax.jit(lambda q: ref.flash_ref(q, q, q, causal=True))
        t = timeit(lambda: jax.block_until_ready(fl(q)), repeats=3)
        flops = 4.0 * bh * s * s * d
        rows.append(
            {
                "op": "attention(dense ref)",
                "shape": f"{bh}x{s}x{d}",
                "sec": t,
                "gflops_s": flops / t / 1e9,
                "arith_intensity": flops / (4.0 * 3 * bh * s * d),
            }
        )
    emit("kernel_hotspots", rows)
    return rows


def main(smoke: bool = False) -> None:
    if smoke:
        run(
            gram_shapes=((4096, 16),),
            seg_shapes=((4096, 16, 16),),
            attn_shapes=((2, 256, 64),),
        )
    else:
        run()


if __name__ == "__main__":
    main()
