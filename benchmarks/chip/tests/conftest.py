"""Shared set-up of the benchmark's CPU tests: the harness module, steered
onto the node kernels (Pallas in interpret mode) and device grouping, and
the configurations at the tiny sizes of ``rehearsal/<config>.json``."""

import json
import os

import pytest

from benchmarks.chip import run as harness

SEED = 2**31 + 12345  # larger than 32 signed bits hold


def bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = [c["name"] for c in bench()["workloads"]]


def plan(workload: str) -> dict:
    return harness.cell_plan(bench(), workload)


def rehearsal(name: str) -> dict:
    """The CPU rehearsal's sizes of the configuration ``name``."""
    return harness.load_json("tests", "rehearsal", name + ".json")


def tiny_config(name: str) -> dict:
    cfg = harness.load_json("configs", name + ".json")
    cfg.update(rehearsal(name))
    return cfg


@pytest.fixture
def steered(monkeypatch):
    """The harness as on the chip, but on the CPU and at tiny sizes: node
    kernels through Pallas (interpret mode), grouping through the device
    path, each configuration cut to its rehearsal sizes."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "default_impl", lambda: "pallas")
    monkeypatch.setattr(ops, "fast_device_grouping", lambda: True)
    orig = harness.load_json

    def load_json(*parts):
        d = orig(*parts)
        if parts[-1].startswith("benchmarks/chip/configs/"):
            d.update(rehearsal(d["name"]))
        return d

    monkeypatch.setattr(harness, "load_json", load_json)
    return harness
