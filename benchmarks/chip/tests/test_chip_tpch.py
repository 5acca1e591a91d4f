"""The TPC-H generator against the rules ``configs/tpch_sf1.json`` quotes
from the TPC-H specification (v3, section 4.2.3), at SF 0.01."""

import numpy as np
import pytest

from benchmarks.chip.configs import tpch_sf1
from benchmarks.chip.tests.conftest import SEED, tiny_config


@pytest.fixture(scope="module")
def db():
    cfg = tiny_config("tpch_sf1")
    cfg["scale_factor"] = 0.01
    return cfg, tpch_sf1.generate(cfg, SEED)["relations"]


def test_row_counts(db):
    cfg, r = db
    for name, rows in cfg["rows_per_sf"].items():
        assert len(next(iter(r[name]["keys"].values()))) == rows // 100
    n_line = len(r["Lineitem"]["keys"]["orderkey"])
    n_ord = len(r["Orders"]["keys"]["orderkey"])
    assert 3.8 * n_ord < n_line < 4.2 * n_ord  # 1..7 lines, mean 4


def test_keys(db):
    _, r = db
    ok = r["Orders"]["keys"]["orderkey"].astype(np.int64)
    assert len(np.unique(ok)) == len(ok) == 15000
    # sparse: the first 8 of every 32 keys
    assert ((ok - 1) % 32 < 8).all() and ok.max() == 15000 // 8 * 32 - 24
    counts = np.bincount(np.searchsorted(ok, r["Lineitem"]["keys"]["orderkey"]))
    assert counts.min() >= 1 and counts.max() <= 7
    part = r["Lineitem"]["keys"]["partkey"].astype(np.int64)
    supp = r["Lineitem"]["keys"]["suppkey"].astype(np.int64)
    assert part.min() >= 1 and part.max() <= 2000
    s = 100
    allowed = np.stack([(part + i * (s // 4 + (part - 1) // s)) % s + 1 for i in range(4)])
    assert (allowed == supp).any(axis=0).all()


def test_two_thirds_of_customers_order(db):
    _, r = db
    cust = r["Orders"]["keys"]["custkey"].astype(np.int64)
    assert (cust % 3 != 0).all() and cust.min() >= 1 and cust.max() <= 1500
    assert len(np.unique(cust)) / 1500 == pytest.approx(2 / 3, abs=0.01)


def test_value_ranges(db):
    _, r = db
    li = r["Lineitem"]["values"]
    assert set(np.unique(li["l_quantity"])) == set(range(1, 51))
    assert np.allclose(np.unique(li["l_discount"]), np.arange(11) / 100)
    assert np.allclose(np.unique(li["l_tax"]), np.arange(9) / 100)
    for rel, col in (("Customer", "c_acctbal"), ("Supplier", "s_acctbal")):
        v = r[rel]["values"][col]
        assert v.min() >= -999.99 and v.max() <= 9999.99
    pk = r["Part"]["keys"]["partkey"].astype(np.int64)
    want = (90000 + (pk // 10) % 20001 + 100 * (pk % 1000)) / 100
    assert np.array_equal(r["Part"]["values"]["p_retailprice"], want)


def test_price_identities(db):
    _, r = db
    li, lk = r["Lineitem"]["values"], r["Lineitem"]["keys"]
    price = r["Part"]["values"]["p_retailprice"][lk["partkey"] - 1]
    assert np.array_equal(li["l_extendedprice"], li["l_quantity"] * price)
    charge = li["l_extendedprice"] * (1 + li["l_tax"]) * (1 - li["l_discount"])
    ok = r["Orders"]["keys"]["orderkey"]
    total = np.bincount(np.searchsorted(ok, lk["orderkey"]), weights=charge)
    assert np.allclose(r["Orders"]["values"]["o_totalprice"], total, atol=0.006)


def test_same_seed_same_data():
    cfg = tiny_config("tpch_sf1")
    a = tpch_sf1.generate(cfg, SEED)["relations"]["Lineitem"]["values"]["l_tax"]
    b = tpch_sf1.generate(cfg, SEED)["relations"]["Lineitem"]["values"]["l_tax"]
    assert np.array_equal(a, b)
