"""Each cell end to end on the CPU at a tiny size: the mix's request loop
through the threaded ``FactorizedService``, the comparison with the fp64
reference, and the metric arithmetic; then the control and the faults the
comparison has to catch."""

import contextlib
import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from benchmarks.chip import control
from benchmarks.chip.tests.conftest import SEED, bench, plan

CELLS = [c["name"] for c in bench()["workloads"]]


def run_cell(harness, workload, seconds=1.5, trace=0, seed=SEED):
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
    return harness.run_cell(args, jax.devices(), plan(workload))


@pytest.mark.parametrize("workload", CELLS)
def test_cell_matches_reference(steered, workload):
    out = run_cell(steered, workload)
    assert out["correct"], out["checks"]
    assert out["checks"]["theta_gap"]["value"] < 1e-4
    assert out["checks"]["count_gap"]["value"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]
    assert set(out["metrics"]) == {"setup_s", "train_rows_per_s"}


def test_traced_run_reports_per_layer_metrics_it_can_read(steered):
    out = run_cell(steered, "favorita.fit", trace=1)
    assert out["correct"]
    # a CPU trace has no device plane: device metrics are left out, never 0
    assert out["metrics"] == {}
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_control_is_not_correct(steered):
    """The node kernels' single bf16 pass fails the comparison."""
    (_, _, res), = control.readings("favorita.fit", [], [SEED], 1.0,
                                    devices=jax.devices())
    assert not res["correct"]
    assert res["checks"]["theta_gap"]["value"] > res["checks"]["theta_gap"]["limit"]


def _altered_theta(monkeypatch):
    from repro.serve import factorized

    orig = factorized.rescale_theta

    def altered(theta_conv, factors, mode="exact"):
        theta = orig(theta_conv, factors, mode)
        theta[1] *= 1.01
        return theta

    monkeypatch.setattr(factorized, "rescale_theta", altered)


def _half_the_rows(monkeypatch):
    from repro.core import factorize

    orig = factorize.FactorizedEngine._leaf_view

    def half(self, rel_name, degree):
        view = orig(self, rel_name, degree)
        if rel_name == "SalesF":
            keep = (np.arange(view.num_rows) % 2 == 0).astype(np.float32)
            view = factorize._View(keys=view.keys, c=view.c * keep, l=view.l,
                                   q=view.q, feats=view.feats, degree=view.degree)
        return view

    monkeypatch.setattr(factorize.FactorizedEngine, "_leaf_view", half)


FAULTS = {
    "answer_altered": (_altered_theta, CELLS),
    "half_the_rows": (_half_the_rows, CELLS),
}


@pytest.mark.parametrize(
    "fault,workload",
    [(f, w) for f, (_, cells) in FAULTS.items() for w in cells],
)
def test_fault_is_not_correct(steered, monkeypatch, fault, workload):
    FAULTS[fault][0](monkeypatch)
    out = run_cell(steered, workload)
    assert not out["correct"], out["checks"]


def test_no_chip_exits_without_result(capsys):
    from benchmarks.chip import run as harness

    with pytest.raises(SystemExit) as exc:
        harness.main(["--workload", "favorita.fit", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    assert exc.value.code == harness.NO_CHIP
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_exit_without_result(tmp_path):
    """A checkout of only BENCHMARK.json and the benchmark's own files
    holds no system to run: the command fails and prints no result."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    subprocess.run(
        ["cp", "-r", os.path.join(root, "BENCHMARK.json"), str(tmp_path)], check=True
    )
    os.makedirs(tmp_path / "benchmarks")
    subprocess.run(
        ["cp", "-r", os.path.join(root, "benchmarks", "chip"), str(tmp_path / "benchmarks")],
        check=True,
    )
    cmd = json.load(open(tmp_path / "BENCHMARK.json"))["command"]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable] + cmd[1:] + ["--workload", "favorita.fit", "--seed", "1",
                                      "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
