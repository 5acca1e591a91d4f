"""Roofline extraction: HLO collective parsing + term math + model FLOPs."""

import numpy as np

from repro.configs import ARCHS, SHAPES
from repro.launch.mesh import hardware
from repro.launch.roofline import (
    RooflineTerms,
    collective_bytes,
    model_flops,
    total_collective_bytes,
)

HLO_SAMPLE = """
HloModule jit_step
ENTRY %main {
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %x), replica_groups={}
  %ag = bf16[64,64]{1,0} all-gather(bf16[32,64]{1,0} %y), dimensions={0}
  %rs = f32[8]{0} reduce-scatter(f32[64]{0} %z), dimensions={0}
  %cp = s32[4,4]{1,0} collective-permute(s32[4,4]{1,0} %w)
  %a2a = f32[16]{0} all-to-all(f32[16]{0} %v), dimensions={0}
  %ars = (f32[10]{0}, f32[10]{0}) all-reduce-start(f32[10]{0} %u)
  %ard = f32[10]{0} all-reduce-done((f32[10]{0}, f32[10]{0}) %ars)
  %plain = f32[999]{0} add(f32[999]{0} %p, f32[999]{0} %q)
}
"""


def test_collective_bytes_parses_all_kinds():
    got = collective_bytes(HLO_SAMPLE)
    assert got["all-gather"] == 64 * 64 * 2
    assert got["reduce-scatter"] == 8 * 4
    assert got["collective-permute"] == 4 * 4 * 4
    assert got["all-to-all"] == 16 * 4
    # all-reduce: the plain op + the -start tuple (2x 10 floats)
    assert got["all-reduce"] == 128 * 256 * 4 + 2 * 10 * 4
    # the plain add must NOT be counted
    assert sum(got.values()) < 999 * 4 + sum(got.values())


def test_total_collective_weights_allreduce_2x():
    per_kind = {"all-reduce": 100, "all-gather": 100}
    assert total_collective_bytes(per_kind) == 300.0


def test_roofline_terms_math():
    t = RooflineTerms(
        arch="a", shape="s", mesh="m", chips=256,
        hlo_flops=197e12,          # per-shard == 1 second of compute
        hlo_bytes=819e9,           # == 1 second of HBM
        coll_bytes=50e9,           # == 1 second of ICI
        coll_by_kind={},
        model_flops=197e12 * 256,  # exactly the useful amount
    )
    np.testing.assert_allclose(t.t_compute, 1.0)
    np.testing.assert_allclose(t.t_memory, 1.0)
    np.testing.assert_allclose(t.t_collective, 1.0)
    np.testing.assert_allclose(t.useful_ratio, 1.0)
    np.testing.assert_allclose(t.roofline_fraction, 1.0)
    t2 = RooflineTerms(
        arch="a", shape="s", mesh="m", chips=4,
        hlo_flops=4.0, hlo_bytes=8e20, coll_bytes=0.0,
        coll_by_kind={}, model_flops=16.0,
    )
    assert t2.bottleneck == "memory"
    assert t2.roofline_fraction < 1e-6


def test_model_flops_shapes():
    cfg = ARCHS["smollm-135m"]
    train = model_flops(cfg, SHAPES["train_4k"])
    dec = model_flops(cfg, SHAPES["decode_32k"])
    counts = cfg.param_counts()
    assert train == 6.0 * counts["active"] * 256 * 4096
    assert dec == 2.0 * counts["active"] * 128
    # MoE: active params drive the number, not total
    moe = ARCHS["mixtral-8x7b"]
    mc = moe.param_counts()
    assert model_flops(moe, SHAPES["train_4k"]) == \
        6.0 * mc["active"] * 256 * 4096


def test_hw_constants_match_assignment():
    hw = hardware("TPU v5 lite")  # what JAX reports for a TPU v5e
    assert hw.peak_flops_bf16 == 197e12
    assert hw.hbm_bw == 819e9
    assert hw.ici_bw == 50e9


def test_unknown_device_kind_has_no_peaks():
    import pytest

    from repro.launch.roofline import traversal_node_terms

    with pytest.raises(ValueError, match="no published peaks"):
        hardware("cpu")
    t = traversal_node_terms(64, 2, 4, device_kind="cpu")
    assert t.bytes_fused > 0  # shape accounting needs no peaks
    with pytest.raises(ValueError):
        t.achieved_fraction(1.0)


def test_traversal_node_terms_math():
    from repro.launch.roofline import traversal_node_terms

    n, k, g, b = 1000, 4, 32, 4
    t = traversal_node_terms(n, k, g, degree=2, dtype_bytes=b)
    blk = 1 + k + k * k  # c + l + q elements per row
    ext = 1 + (k + 1) + (k + 1) * (k + 1)
    assert t.packed_width == (k + 2) * (k + 2)
    assert t.bytes_in == n * (blk + 1) * b + n * 4
    assert t.bytes_fused == t.bytes_in + g * (k + 2) * (k + 2) * b
    # the unfused path round-trips the extended [N, k+1, k+1] blocks
    assert t.bytes_unfused == t.bytes_in + 2 * n * ext * b + n * b + g * ext * b
    assert t.flops_fused == n * (k + 2) + n * t.packed_width
    # the whole point: fusion wins on bytes, and the node is memory-bound
    assert t.predicted_speedup > 1.5
    assert t.arith_intensity < 2.0  # FLOPs/byte far under machine balance


def test_traversal_node_terms_degree1():
    from repro.launch.roofline import traversal_node_terms

    t = traversal_node_terms(500, 3, 10, degree=1)
    assert t.packed_width == 5
    assert t.predicted_speedup > 1.0
    import pytest as _pytest

    with _pytest.raises(ValueError):
        traversal_node_terms(10, 2, 2, degree=3)


def test_traversal_node_terms_achieved():
    from repro.launch.roofline import traversal_node_terms

    t = traversal_node_terms(65536, 4, 256, device_kind="TPU v5 lite")
    # at exactly the memory-bound time, the achieved fraction is 1.0 and
    # achieved bandwidth equals the HBM figure
    sec = t.t_memory_fused
    np.testing.assert_allclose(t.achieved_fraction(sec), 1.0)
    np.testing.assert_allclose(t.achieved_gbs(sec) * 1e9, t.hw.hbm_bw)
    assert t.achieved_fraction(0.0) == 0.0
    j = t.to_json()
    assert j["predicted_speedup"] == t.predicted_speedup
    assert j["n_rows"] == 65536
