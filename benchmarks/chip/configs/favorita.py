"""Favorita star schema, generated from a seed.

The draws are those of ``repro.data.synthetic.favorita_like`` (copied, so
that the yardstick cannot move with the program): Favorita's published key
cardinalities, facts drawn uniformly without replacement from every
(date, store, item), and ``unit_sales`` a linear function of the paper's
features plus unit noise.  ``favorita.json`` holds the sizes, the
variable order, the join edges and the cuts.

Appends are the next day's data, as a day's load brings it: the new
date's oil price, its transactions at every store, then
``append_rows_per_step`` sales at that date, so the dates grow by one a
step.
"""

from __future__ import annotations

import numpy as np


def generate(cfg: dict, seed: int) -> dict:
    """The relations as ``{name: {"keys": {...}, "values": {...}}}`` of
    numpy arrays, plus the state the append generator draws from."""
    n_dates, n_stores, n_items = cfg["n_dates"], cfg["n_stores"], cfg["n_items"]
    rows = cfg["fact_rows"]
    rng = np.random.default_rng(seed)
    dates = np.arange(n_dates, dtype=np.int32)
    stores = np.arange(n_stores, dtype=np.int32)
    items = np.arange(n_items, dtype=np.int32)

    cluster = rng.integers(1, 6, size=n_stores).astype(np.float64)
    perishable = rng.integers(0, 2, size=n_items).astype(np.float64)
    dcoil = np.cumsum(rng.normal(0, 1, size=n_dates)) + 50.0
    t_date = np.repeat(dates, n_stores)
    t_store = np.tile(stores, n_dates)
    t_count = rng.integers(500, 3000, size=n_dates * n_stores).astype(np.float64)

    total = n_dates * n_stores * n_items
    if not 0 < rows <= total:
        raise ValueError(f"fact_rows {rows} outside 1..{total}")
    flat = rng.choice(total, size=rows, replace=False)
    f_date = (flat // (n_stores * n_items)).astype(np.int32)
    rem = flat % (n_stores * n_items)
    f_store = (rem // n_items).astype(np.int32)
    f_item = (rem % n_items).astype(np.int32)
    promo = rng.integers(0, 2, size=rows).astype(np.float64)
    unit_sales = (
        5.0
        + 0.05 * f_date
        + 2.0 * cluster[f_store]
        + 3.0 * perishable[f_item]
        + 4.0 * promo
        + rng.normal(0, 1.0, size=rows)
    )
    return {
        "relations": {
            "SalesF": {
                "keys": {"date": f_date, "store_nbr": f_store, "item_nbr": f_item},
                "values": {"unit_sales": unit_sales, "onpromotion": promo},
            },
            "Transactions": {
                "keys": {"date": t_date, "store_nbr": t_store},
                "values": {"transactions": t_count},
            },
            "Oil": {"keys": {"date": dates}, "values": {"dcoilwtico": dcoil}},
            "Items": {"keys": {"item_nbr": items}, "values": {"perishable": perishable}},
            "Stores": {"keys": {"store_nbr": stores}, "values": {"cluster": cluster}},
        },
        "append": {
            "rng": np.random.default_rng([seed, 1]),
            "next_date": n_dates,
            "dcoilwtico": float(dcoil[-1]),
            "cluster": cluster,
            "perishable": perishable,
        },
    }


def new_rows(cfg: dict, data: dict) -> dict:
    """The next day's appends, ``{relation: rows}`` in the order they are
    sent: one ``Oil`` row (the random walk's next step), one
    ``Transactions`` row per store, and ``append_rows_per_step`` sales at
    distinct (store, item) keys drawn from the seed, their values drawn as
    the base facts are."""
    n_stores, n_items = cfg["n_stores"], cfg["n_items"]
    n = cfg["append_rows_per_step"]
    st = data["append"]
    rng = st["rng"]
    d = st["next_date"]
    st["next_date"] += 1
    st["dcoilwtico"] += float(rng.normal(0, 1))
    stores = np.arange(n_stores, dtype=np.int32)
    codes = rng.choice(n_stores * n_items, size=n, replace=False)
    shop = (codes // n_items).astype(np.int32)
    item = (codes % n_items).astype(np.int32)
    date = np.full(n, d, dtype=np.int32)
    promo = rng.integers(0, 2, size=n).astype(np.float64)
    unit_sales = (
        5.0
        + 0.05 * date
        + 2.0 * st["cluster"][shop]
        + 3.0 * st["perishable"][item]
        + 4.0 * promo
        + rng.normal(0, 1.0, size=n)
    )
    return {
        "Oil": {
            "keys": {"date": np.array([d], dtype=np.int32)},
            "values": {"dcoilwtico": np.array([st["dcoilwtico"]])},
        },
        "Transactions": {
            "keys": {"date": np.full(n_stores, d, dtype=np.int32), "store_nbr": stores},
            "values": {
                "transactions": rng.integers(500, 3000, size=n_stores).astype(np.float64)
            },
        },
        "SalesF": {
            "keys": {"date": date, "store_nbr": shop, "item_nbr": item},
            "values": {"unit_sales": unit_sales, "onpromotion": promo},
        },
    }
