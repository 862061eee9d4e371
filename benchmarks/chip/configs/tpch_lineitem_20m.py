"""Data of the `tpch_lineitem_20m` configuration.

TPC-H lineitem rows with the column domains and correlations of the
specification's sec. 4.2.3 at the configured scale factor: orders of 1..7
lines share an order date; ship date is order date + 1..121 days; receipt
date is ship date + 1..30 days and sets the return flag against the
current date; line status follows the ship date; extended price is
quantity times the retail price of a uniform part key.

The dates and the categorical columns they set come from the
configuration's `layout_seed`, the same in every run, so every seed sees
the same strata and sample families of the same sizes; the run's seed
draws quantity, part key (so price), discount and tax.
"""
from __future__ import annotations

import numpy as np

from blinkbench import datagen


def generate(cfg: dict, seed: int, n_rows: int) -> datagen.Data:
    spec = cfg["spec"]
    layout = np.random.default_rng(
        np.random.SeedSequence([cfg["layout_seed"], n_rows, 0x7C4]))
    lo, hi = spec["lines_per_order"]
    n_orders = n_rows // ((lo + hi) // 2) + 8
    per_order = layout.integers(lo, hi + 1, n_orders)
    while per_order.sum() < n_rows:   # a short draw: add orders until full
        per_order = np.concatenate([per_order, layout.integers(lo, hi + 1,
                                                               n_orders)])
    start = datagen.day_number(spec["start_date"])
    last_order = datagen.day_number(spec["end_date"]) - 151
    order_date = layout.integers(start, last_order + 1, len(per_order),
                                 dtype=np.int32)
    order_date = np.repeat(order_date, per_order)[:n_rows]

    s_lo, s_hi = spec["ship_days"]
    ship = order_date + layout.integers(s_lo, s_hi + 1, n_rows, dtype=np.int32)
    r_lo, r_hi = spec["receipt_days"]
    receipt = ship + layout.integers(r_lo, r_hi + 1, n_rows, dtype=np.int32)
    current = datagen.day_number(spec["current_date"])

    cols = cfg["columns"]
    flag_codes = {v: i for i, v in enumerate(cols["l_returnflag"]["values"])}
    returned = np.where(layout.random(n_rows) < 0.5, flag_codes["R"],
                        flag_codes["A"])
    returnflag = np.where(receipt <= current, returned, flag_codes["N"])
    status_codes = {v: i for i, v in enumerate(cols["l_linestatus"]["values"])}
    linestatus = np.where(ship > current, status_codes["O"],
                          status_codes["F"])
    codes = {
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipmode": layout.integers(0, len(cols["l_shipmode"]["values"]),
                                      n_rows),
    }

    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x7C5]))
    n_parts = spec["parts_per_sf"] * spec["scale_factor"]
    partkey = rng.integers(1, n_parts + 1, n_rows, dtype=np.int64)
    retail = (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100.0
    quantity = rng.integers(1, 51, n_rows).astype(np.float64)
    numeric = {
        "l_quantity": quantity,
        "l_extendedprice": quantity * retail,
        "l_discount": rng.integers(0, 11, n_rows) / 100.0,
        "l_tax": rng.integers(0, 9, n_rows) / 100.0,
        "l_shipdate": ship,
    }
    return datagen.Data.from_parts(cfg, codes, numeric)
