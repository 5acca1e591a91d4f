"""Shared set-up of the benchmark's CPU tests: the harness module, steered
onto the node kernels (Pallas in interpret mode) and device grouping, and
the configurations at tiny sizes."""

import json
import os

import pytest

from benchmarks.chip import run as harness

TINY = {
    "favorita": {"n_dates": 8, "n_stores": 3, "n_items": 10, "fact_rows": 120,
                 "append_rows_per_step": 4},
    "tpch_sf1": {"scale_factor": 0.001},
}
SEED = 2**31 + 12345  # larger than 32 signed bits hold


def bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def plan(workload: str) -> dict:
    return harness.cell_plan(bench(), workload)


def tiny_config(name: str) -> dict:
    cfg = harness.load_json(harness.ROOT, f"benchmarks/chip/configs/{name}.json")
    cfg.update(TINY[name])
    return cfg


@pytest.fixture
def steered(monkeypatch):
    """The harness as on the chip, but on the CPU and at tiny sizes: node
    kernels through Pallas (interpret mode), grouping through the device
    path, configurations cut by ``TINY``."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "default_impl", lambda: "pallas")
    monkeypatch.setattr(ops, "fast_device_grouping", lambda: True)
    orig = harness.load_json

    def load_json(*parts):
        d = orig(*parts)
        if parts[-1].startswith("benchmarks/chip/configs/"):
            d.update(TINY[d["name"]])
        return d

    monkeypatch.setattr(harness, "load_json", load_json)
    return harness
