"""Shared pieces of the configurations' data generators.

A generator (`configs/<name>.py`) returns a `Data`: the table's columns as
host arrays (int32 dictionary codes for categorical columns, float32 for
numeric ones) and the dictionaries of label values. The benchmark keeps
this copy for its reference; the program gets a `Table` built from it
through the table's public constructor.
"""
from __future__ import annotations

import dataclasses
import datetime

import numpy as np

EPOCH = datetime.date(1970, 1, 1)


def day_number(iso: str) -> int:
    """Days since 1970-01-01 of an ISO date (how dates are stored)."""
    return (datetime.date.fromisoformat(iso) - EPOCH).days


def labels(col: dict) -> np.ndarray:
    """The dictionary of a categorical column: its `values`, or
    `prefix` + a zero-padded code for each of `cardinality` codes."""
    if "values" in col:
        return np.asarray(col["values"])
    card = int(col["cardinality"])
    width = len(str(card - 1))
    return np.asarray([f"{col['prefix']}{i:0{width}d}" for i in range(card)])


def draw_codes(rng: np.random.Generator, n: int, col: dict) -> np.ndarray:
    """int32 codes of one categorical column by its `dist`: `zipf` (rank
    r has weight r**-s), `weights` (the listed probabilities) or
    `uniform`. Inverse-CDF draws: one uniform and one search per row."""
    card = int(col["cardinality"])
    dist = col["dist"]
    if dist == "uniform":
        return rng.integers(0, card, n, dtype=np.int32)
    if dist == "zipf":
        p = np.arange(1, card + 1, dtype=np.float64) ** -float(col["s"])
    elif dist == "weights":
        p = np.asarray(col["p"], dtype=np.float64)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    u = rng.random(n, dtype=np.float64)
    return np.minimum(np.searchsorted(cdf, u, side="right"),
                      card - 1).astype(np.int32)


@dataclasses.dataclass
class Data:
    """One generated table: schema order, host columns and dictionaries."""
    table: str
    kinds: dict[str, str]                 # column -> "categorical" | "numeric"
    columns: dict[str, np.ndarray]        # int32 codes or float32 values
    dictionaries: dict[str, np.ndarray]   # categorical column -> labels

    @property
    def n_rows(self) -> int:
        return len(next(iter(self.columns.values())))

    def cardinality(self, name: str) -> int:
        return len(self.dictionaries[name])

    def code_of(self, name: str, label) -> int:
        """Code of a label (-1 when the dictionary lacks it)."""
        hit = np.nonzero(self.dictionaries[name] == label)[0]
        return int(hit[0]) if hit.size else -1

    @classmethod
    def from_parts(cls, cfg: dict, codes: dict[str, np.ndarray],
                   numeric: dict[str, np.ndarray]) -> "Data":
        kinds, cols, dicts = {}, {}, {}
        for name, col in cfg["columns"].items():
            kinds[name] = col["kind"]
            if col["kind"] == "categorical":
                cols[name] = codes[name].astype(np.int32, copy=False)
                dicts[name] = labels(col)
            else:
                cols[name] = numeric[name].astype(np.float32, copy=False)
        return cls(cfg["table"], kinds, cols, dicts)
