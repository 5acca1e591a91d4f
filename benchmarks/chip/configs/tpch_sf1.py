"""TPC-H lineitem snowflake, generated from a seed by dbgen's rules.

Each column follows TPC-H v3 section 4.2.3 as ``tpch_sf1.json`` quotes it
under ``spec``: sparse order keys, 1-7 lines per order, orders only for
customers whose key is not divisible by 3, each line's supplier one of
its part's four, ``l_extendedprice = l_quantity * p_retailprice`` and
``o_totalprice`` the sum of the order's discounted, taxed lines.  Only the
columns the model reads are generated.  The random stream is numpy's, not
dbgen's, so the lineitem count varies with the seed around four lines per
order.
"""

from __future__ import annotations

import numpy as np


def _money(rng, n):
    """Uniform -999.99..9999.99 in cents (c_acctbal, s_acctbal)."""
    return rng.integers(-99999, 1000000, size=n) / 100.0


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE of 4.2.3, in dollars."""
    k = partkey.astype(np.int64)
    return (90000 + (k // 10) % 20001 + 100 * (k % 1000)) / 100.0


def order_keys(n_orders: int) -> np.ndarray:
    """The sparse O_ORDERKEY sequence: the first 8 of every 32 keys."""
    i = np.arange(n_orders, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def generate(cfg: dict, seed: int) -> dict:
    """The relations as ``{name: {"keys": {...}, "values": {...}}}``."""
    sf = cfg["scale_factor"]
    n = {name: int(round(rows * sf)) for name, rows in cfg["rows_per_sf"].items()}
    n_supp, n_part, n_cust, n_ord = (
        n["Supplier"], n["Part"], n["Customer"], n["Orders"]
    )
    rng = np.random.default_rng(seed)

    suppkey = np.arange(1, n_supp + 1, dtype=np.int64)
    s_acctbal = _money(rng, n_supp)
    partkey = np.arange(1, n_part + 1, dtype=np.int64)
    p_retailprice = retail_price(partkey)
    custkey = np.arange(1, n_cust + 1, dtype=np.int64)
    c_acctbal = _money(rng, n_cust)

    orderkey = order_keys(n_ord)
    # customers with orders: keys not divisible by 3, drawn uniformly
    n_active = n_cust - n_cust // 3
    m = rng.integers(0, n_active, size=n_ord)
    o_custkey = 3 * (m // 2) + m % 2 + 1

    lines = rng.integers(1, 8, size=n_ord)
    n_line = int(lines.sum())
    l_orderkey = np.repeat(orderkey, lines)
    l_partkey = rng.integers(1, n_part + 1, size=n_line)
    supplier_no = rng.integers(0, 4, size=n_line)
    l_suppkey = (
        l_partkey + supplier_no * (n_supp // 4 + (l_partkey - 1) // n_supp)
    ) % n_supp + 1
    l_quantity = rng.integers(1, 51, size=n_line).astype(np.float64)
    l_discount = rng.integers(0, 11, size=n_line) / 100.0
    l_tax = rng.integers(0, 9, size=n_line) / 100.0
    l_extendedprice = l_quantity * p_retailprice[l_partkey - 1]
    charge = l_extendedprice * (1.0 + l_tax) * (1.0 - l_discount)
    o_totalprice = np.round(
        np.add.reduceat(charge, np.concatenate([[0], np.cumsum(lines)[:-1]])), 2
    )

    i32 = np.int32
    return {
        "relations": {
            "Lineitem": {
                "keys": {
                    "orderkey": l_orderkey.astype(i32),
                    "partkey": l_partkey.astype(i32),
                    "suppkey": l_suppkey.astype(i32),
                },
                "values": {
                    "l_quantity": l_quantity,
                    "l_discount": l_discount,
                    "l_tax": l_tax,
                    "l_extendedprice": l_extendedprice,
                },
            },
            "Orders": {
                "keys": {"orderkey": orderkey.astype(i32), "custkey": o_custkey.astype(i32)},
                "values": {"o_totalprice": o_totalprice},
            },
            "Customer": {"keys": {"custkey": custkey.astype(i32)}, "values": {"c_acctbal": c_acctbal}},
            "Part": {"keys": {"partkey": partkey.astype(i32)}, "values": {"p_retailprice": p_retailprice}},
            "Supplier": {"keys": {"suppkey": suppkey.astype(i32)}, "values": {"s_acctbal": s_acctbal}},
        },
    }
