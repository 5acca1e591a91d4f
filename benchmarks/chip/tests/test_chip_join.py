"""A configuration and a kind of train join the benchmark with new files
only: a copy of the benchmark gains a configuration under a new name (the
Favorita files copied), its rehearsal sizes, a mix that names train
arguments, a cell with its limits, and that cell's name in
``train_rows_per_s``'s ``workloads``.  The harness, pointed at the copy,
runs the cell correct and each fault not correct."""

import filecmp
import json
import os
import shutil
import types

import pytest

from benchmarks.chip import run as harness
from benchmarks.chip.run import load_module
from benchmarks.chip.tests.test_chip_rehearsal import FAULTS, plant, run_cell
from benchmarks.chip.tests.test_chip_yardstick import check_benchmark_files

NEW = "favorita_copy"
CELL = NEW + ".fit_args"
TRAIN_ARGS = {"deadline": 600.0}


def write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)


@pytest.fixture(scope="module")
def joined(tmp_path_factory):
    """The root of a copy of ``BENCHMARK.json`` and the benchmark's files,
    with the new configuration's files added and its entries appended."""
    root = str(tmp_path_factory.mktemp("joined"))
    here = os.path.join(root, "benchmarks", "chip")
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(harness.HERE, here, ignore=shutil.ignore_patterns("__pycache__"))

    def add(src, dst, **changes):
        with open(os.path.join(here, src)) as f:
            obj = json.load(f)
        obj.update(changes)
        assert not os.path.exists(os.path.join(here, dst))
        write_json(os.path.join(here, dst), obj)

    add("configs/favorita.json", f"configs/{NEW}.json", name=NEW)
    shutil.copy(os.path.join(here, "configs", "favorita.py"),
                os.path.join(here, "configs", NEW + ".py"))
    add("tests/rehearsal/favorita.json", f"tests/rehearsal/{NEW}.json")
    add("mixes/fit.json", "mixes/fit_args.json", train_args=TRAIN_ARGS)
    add("limits/favorita.fit.json", f"limits/{CELL}.json")

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    config = dict(b["configs"][0], name=NEW, file=f"benchmarks/chip/configs/{NEW}.json")
    b["configs"].append(config)
    b["workloads"].append({"name": CELL, "config": NEW, "traffic": "fit_args", "chips": 1,
                           "why": "the favorita.fit cell under a new configuration's name"})
    for m in b["end_to_end"]:
        if m["name"] == "train_rows_per_s":
            m["workloads"].append(CELL)
    write_json(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_the_copy_only_adds(joined):
    """Every file of the benchmark is in the copy unchanged, and its
    ``BENCHMARK.json`` only gains entries; the added pieces are complete."""
    for dirpath, dirs, files in os.walk(harness.HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            rel = os.path.relpath(os.path.join(dirpath, name), harness.ROOT)
            assert filecmp.cmp(os.path.join(harness.ROOT, rel), os.path.join(joined, rel),
                               shallow=False), rel
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        tree = json.load(f)
    with open(os.path.join(joined, "BENCHMARK.json")) as f:
        copy = json.load(f)

    def without_cell(entries):
        return [dict(e, workloads=[w for w in e["workloads"] if w != CELL])
                if "workloads" in e else e for e in entries]

    assert set(copy) == set(tree)
    for key, value in tree.items():
        if isinstance(value, list):  # the entries there, then new ones
            assert without_cell(copy[key])[:len(value)] == value, key
        else:
            assert copy[key] == value, key
    check_benchmark_files(joined)


@pytest.mark.parametrize("fault", ["none", *FAULTS])
def test_a_configuration_joins_with_new_files_only(steered, monkeypatch, joined, fault):
    monkeypatch.setattr(steered, "ROOT", joined)
    monkeypatch.setattr(steered, "HERE", os.path.join(joined, "benchmarks", "chip"))
    if fault in FAULTS:
        plant(steered, monkeypatch, fault, CELL)
    out = run_cell(steered, CELL)
    if fault in FAULTS:
        assert not out["correct"], out["checks"]
    else:
        assert out["correct"], out["checks"]
        assert out["attempted"] > 0 and out["failed"] == 0
        assert out["metrics"]["train_rows_per_s"]["value"] > 0


class FakeService:
    """Records the keyword arguments of each train; answers at once."""

    def __init__(self):
        self.kw = []

    def train(self, tenant, vorder, features, label, **kw):
        self.kw.append(kw)
        answer = types.SimpleNamespace(theta=[0.0], features=list(features))
        return types.SimpleNamespace(result=lambda timeout: answer)


@pytest.mark.parametrize("train_args", [None, TRAIN_ARGS, {"backend": "jax", "deadline": 5}])
def test_mix_train_args_reach_the_service(train_args):
    mix = {"clients": 1} if train_args is None else {"clients": 1, "train_args": train_args}
    cfg = {"features": ["x"], "label": "y", "ridge": 0.5}
    svc = FakeService()
    hooks = types.SimpleNamespace(counts={})
    driver = load_module("drivers", "closed_loop.py").Driver(
        svc, mix, cfg, {}, None, None, hooks, None)
    recs = driver.run(steps=2)
    assert [r["ok"] for r in recs] == [True, True]
    assert svc.kw == [dict(ridge=0.5, **(train_args or {}))] * 2


@pytest.mark.parametrize("kind", ["train", "cofactors", "a_later_kind"])
def test_counts_are_recorded_for_any_kind_of_read(kind):
    """The count behind a read is its batch's ungrouped block's, whatever
    the read's kind and wherever that block sits among the others."""
    import numpy as np

    from benchmarks.chip import probe
    from repro.core.factorize import AggregateBlock

    def block(keys, count):
        return AggregateBlock(keys=keys, count=np.asarray(count, dtype=np.float64),
                              lin=None, quad=None, features=[])

    svc = types.SimpleNamespace(_finish=lambda read, blocks: "answer")
    read = types.SimpleNamespace(kind=kind, ticket=object())
    blocks = {"one_store": block({"store_nbr": np.array([5.0])}, [7.0]),
              "by_store": block({"store_nbr": np.arange(3.0)}, [1.0, 2.0, 3.0]),
              "whole": block({}, [42.0])}
    hooks = probe.Hooks().results(svc)
    try:
        assert svc._finish(read, blocks) == "answer"
        assert svc._finish(read, {"by_store": blocks["by_store"]}) == "answer"
    finally:
        hooks.close()
    assert hooks.counts == {id(read.ticket): 42.0}
