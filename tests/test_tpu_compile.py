"""Compile the node kernels for a described TPU v5e chip — shapes only,
nothing runs.  Interpret-mode tests cannot see what Mosaic refuses (shape
casts, scalar VMEM stores, unaligned slices, VMEM overflow); these compiles
do, at the widths the engine's fact-table nodes reach, for no chip time.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU compiler library, and every test
worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.segment_view import (
    LANE,
    SUBLANE,
    VMEM_BUDGET_BYTES,
    step_vmem_bytes,
)

M, K, G = 524_288, 8, 1_024  # rows, features below the node, groups


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compile(fn, *args) -> str:
    """Compile ``fn`` for the described chip; the optimized HLO must hold
    the Mosaic kernel (a Pallas call that fell back to anything else would
    not)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _node_args(spec, m=M, k=K):
    i32 = jnp.int32
    return (
        spec((m,)), spec((m,)), spec((m, k)), spec((m, k, k)),
        spec((m,), i32), spec((m,), i32),
    )


@pytest.mark.parametrize("degree", [1, 2])
def test_segment_view_compiles(spec, degree):
    def node(c, x, l, q, seg, order):
        return ops.segment_view(
            c, x, l, q if degree == 2 else None, seg, G, degree=degree,
            impl="pallas", interpret=False, order=order,
        )

    _compile(node, *_node_args(spec))


def test_segment_view_compiles_when_groups_match_rows(spec):
    """A fact-table leaf: almost every row its own group.  The staircase
    schedule keeps the kernel O(N + G), so G = N compiles like any node."""

    def node(c, x, l, q, seg, order):
        return ops.segment_view(
            c, x, l, q, seg, M, degree=2, impl="pallas", interpret=False,
            order=order,
        )

    _compile(node, *_node_args(spec, k=2))


def test_segment_reduce_compiles(spec):
    def reduce(c, x, l, q, seg, order):
        del x
        return ops.segment_blocks(
            c, l, q, seg, G, degree=2, impl="pallas", interpret=False,
            order=order,
        )

    _compile(reduce, *_node_args(spec))


def test_segment_gram_compiles(spec):
    def gram(x, seg):
        return ops.segment_gram(x, seg, G, interpret=False)

    _compile(gram, spec((M, K)), spec((M,), jnp.int32))


@pytest.mark.parametrize("fill", ["two_columns", "largest_resident"])
def test_multi_segment_gram_compiles(spec, fill):
    """Two segment columns at G groups, and the most groups the wrapper
    still keeps resident in VMEM (one lane tile short of the budget)."""
    if fill == "two_columns":
        groups = [G, G // 2]
    else:
        wp, wo = -(-K // SUBLANE) * SUBLANE, K * K
        gp = LANE
        while step_vmem_bytes(wp, wo, LANE, gp + LANE, 2) <= VMEM_BUDGET_BYTES:
            gp += LANE
        groups = [gp // 2, gp - gp // 2]

    def multi(x, segs):
        return ops.multi_segment_gram(x, segs, groups, interpret=False)

    text = _compile(multi, spec((M, K)), spec((M, len(groups)), jnp.int32))
    # the fused resident kernel, not the per-column fallback: one call
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_gram_compiles(spec):
    _compile(lambda x: ops.gram(x, interpret=False), spec((M, K)))


def test_moments_compiles(spec):
    _compile(lambda x: ops.moments(x, interpret=False)[:2], spec((M,)))
