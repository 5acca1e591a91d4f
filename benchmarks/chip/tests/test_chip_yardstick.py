"""The yardstick's own pieces: required work, peaks, the Favorita
generator's copy, and ``BENCHMARK.json`` against the files it names."""

import json
import os
import re

import numpy as np
import pytest

from benchmarks.chip import peaks, work
from benchmarks.chip.configs import favorita
from benchmarks.chip.tests.conftest import SEED, tiny_config

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_node_step_by_hand():
    # Favorita's unit_sales node, worked by hand in PERF.md: M = 10,000,000
    # rows of a k = 1 view extended by x at degree 2 into G = 10,000,000
    w = work.node_step(10_000_000, 1, 2, 10_000_000, feature=True)
    # read per row: c, l (1), q (1 distinct), x, id = 5 words
    # write per group: 1 + 2 + 3 = 6 words (k_out = 2)
    assert w["bytes"] == 4 * (10_000_000 * 5 + 10_000_000 * 6)
    # products x*c, x*x*c, x*l (1) = 3; adds: 6 distinct outputs
    assert w["flops"] == 10_000_000 * (3 + 6)
    t, bound = work.roofline_seconds(w, peaks.peaks("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(w["bytes"] / 819e9)
    # no feature, degree 1: read c, l (2), id; write 3 per group
    w = work.node_step(100, 2, 1, 10, feature=False)
    assert w == {"bytes": 4 * (100 * 4 + 10 * 3), "flops": 100 * 3}


def test_favorita_copy_draws_what_the_program_generator_draws():
    from repro.data.synthetic import favorita_like

    cfg = tiny_config("favorita")
    ours = favorita.generate(cfg, SEED)["relations"]
    total = cfg["n_dates"] * cfg["n_stores"] * cfg["n_items"]
    theirs = favorita_like(cfg["n_dates"], cfg["n_stores"], cfg["n_items"],
                           (cfg["fact_rows"] + 0.5) / total, seed=SEED).store
    for name, rel in ours.items():
        other = theirs.get(name)
        for part in ("keys", "values"):
            for col, arr in rel[part].items():
                assert np.array_equal(arr, getattr(other, part)[col]), (name, col)


def test_appends_are_one_day_of_unsold_keys():
    """Each step is the next day's load: an oil price, every store's
    transactions and sales at distinct keys, all at one new date."""
    cfg = tiny_config("favorita")
    data = favorita.generate(cfg, SEED)
    sales = data["relations"]["SalesF"]["keys"]
    seen = set(zip(sales["date"], sales["store_nbr"], sales["item_nbr"]))
    for step in range(5):
        batch = favorita.new_rows(cfg, data)
        assert list(batch) == ["Oil", "Transactions", "SalesF"]
        day = cfg["n_dates"] + step
        for rel in batch.values():
            assert set(rel["keys"]["date"]) == {day}
        assert list(batch["Transactions"]["keys"]["store_nbr"]) == list(range(cfg["n_stores"]))
        rows = batch["SalesF"]["keys"]
        keys = set(zip(rows["date"], rows["store_nbr"], rows["item_nbr"]))
        assert len(keys) == cfg["append_rows_per_step"] and not keys & seen
        seen |= keys


def test_reference_reads_appends_to_every_relation():
    """A step's gram is that of a join rebuilt from the appended tables,
    and fact rows that matched no parent join once their parent comes."""
    from benchmarks.chip.reference import Reference, concat, raw_gram

    cfg = tiny_config("favorita")
    data = favorita.generate(cfg, SEED)
    rels = data["relations"]
    ref = Reference(cfg, rels)
    cols = cfg["features"] + [cfg["label"]]
    batches = [favorita.new_rows(cfg, data) for _ in range(3)]
    # the second day's sales come before its oil price: they join a step late
    late = batches[1].pop("Oil")
    batches[2]["Oil"] = concat(late, batches[2]["Oil"])
    grown = dict(rels)
    for batch in batches:
        ref.append(batch)
        for name, rows in batch.items():
            grown[name] = concat(grown[name], rows)
        gram, count, _ = raw_gram(grown, cfg["fact"], cfg["joins"], cols)
        assert ref.states[-1].count == count
        assert np.allclose(ref.states[-1].gram, gram, rtol=1e-12)
    n = cfg["append_rows_per_step"]
    assert [s.count for s in ref.states] == [
        cfg["fact_rows"], cfg["fact_rows"] + n, cfg["fact_rows"] + n, cfg["fact_rows"] + 3 * n
    ]


def check_benchmark_files(root: str) -> None:
    """``BENCHMARK.json`` at ``root`` against the files it names under
    ``root``'s benchmark directory: each piece a cell needs is there."""
    here = os.path.join(root, "benchmarks", "chip")

    def load(*parts):
        with open(os.path.join(here, *parts)) as f:
            return json.load(f)

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.isfile(os.path.join(root, b["command"][1]))
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    names += [c["name"] for c in b["configs"] + b["workloads"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in b["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and set(c["reduced"]) == set(cfg["reduced"])
        assert os.path.isfile(os.path.join(here, "configs", c["name"] + ".py"))
        # the CPU rehearsal's sizes: numbers laid over the configuration's own
        sizes = load("tests", "rehearsal", c["name"] + ".json")
        assert sizes and set(sizes) <= set(cfg), c["name"]
        assert all(isinstance(v, (int, float)) for v in sizes.values()), c["name"]
    for w in b["workloads"]:
        mix = load("mixes", w["traffic"] + ".json")
        assert os.path.isfile(os.path.join(here, "drivers", mix["driver"] + ".py"))
        assert os.path.isfile(os.path.join(here, "checks", mix["check"] + ".py"))
        assert isinstance(mix.get("train_args", {}), dict), w["traffic"]
        # which numbers a check compares is held by the rehearsal
        for number in load("limits", w["name"] + ".json").values():
            assert isinstance(number["limit"], (int, float)) and number["from"]
        assert any(m["name"] != "setup_s" and w["name"] in m.get("workloads", [w["name"]])
                   for m in b["end_to_end"]), w["name"]
    for m in b["end_to_end"]:
        assert os.path.isfile(os.path.join(here, "endtoend", m["name"] + ".py"))
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(here, "metrics", m["name"] + ".py"))
        assert m["moves"] in e2e


def test_benchmark_json_names_files_that_exist():
    check_benchmark_files(os.path.dirname(os.path.dirname(HERE)))
