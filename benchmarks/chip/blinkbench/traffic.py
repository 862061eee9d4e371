"""The one generator behind every traffic mix (`traffic/<name>.json`).

A mix file holds parameters only. Keys:

* `rate_qps`, `pool_seed`: an open loop, Poisson arrivals at the rate;
  the queries and the gaps between arrivals are drawn from `pool_seed`.
* `service`: `ServiceConfig` fields the cell fixes (answer cache, re-
  optimisation, `max_batch`, ...). Set-up warms the batched programs of
  every batch size up to `max_batch`.
* `shapes`: weighted query shapes. `where` is a list of atoms
  `[column, op, value]`; a shape may carry its own `params`,
  `aggregates`, `group_by` and `bounds`, else the mix's lists apply.
* `aggregates`, `group_by`, `bounds`: weighted options.

A value is a literal, `{"row": true}` (the column's value in one random
row of the table, the same row for every atom of the query, so values
come by popularity and jointly), `{"date": "YYYY-MM-DD"}` (a day number),
or `{"param": name, "times": t, "plus": value}`. A param is
`{"int": [lo, hi]}` (uniform, inclusive) or `{"choice": [values]}`.

Shares are exact: of n queries, each option of each list gets its
largest-remainder quota, shuffled. The window's queries and send times
come from the mix's `pool_seed` alone: every run sends the same schedule,
and the run's seed draws the data's measures. The program's run-time
choices follow the constants (which family a query is served from, whether
an error bound falls back to the exact path), and its latencies follow
where the slow queries fall among the arrivals: a seed that drew or
ordered them changed the work and the queueing from run to run.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from blinkbench import datagen

_OPS = {"=", "!=", "<", "<=", ">", ">="}


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One query as sent, with what the harness needs to judge it."""
    text: str
    shape: str
    agg: str                       # COUNT | SUM | AVG | QUANTILE
    value_column: str | None
    atoms: tuple                   # ((column, op, value), ...)
    group_by: str | None
    level: float | None            # QUANTILE level
    bound: str                     # "error" | "time" | "none"
    eps: float | None              # relative error bound (fraction)
    seconds: float | None          # time bound
    confidence: float
    at: tuple | None = dataclasses.field(default=None, compare=False)
    # (row, params) the values came from: `Mix.make(..., at=spec.at)`
    # gives the same query under another aggregate or bound

    @property
    def columns_read(self) -> tuple[str, ...]:
        """Distinct predicate and group columns, in first-use order."""
        cols = [a[0] for a in self.atoms]
        if self.group_by:
            cols.append(self.group_by)
        return tuple(dict.fromkeys(cols))


def quota(n: int, weights) -> list[int]:
    """Largest-remainder split of n items by weight."""
    w = np.asarray(weights, dtype=np.float64)
    share = n * w / w.sum()
    out = np.floor(share).astype(int)
    for i in np.argsort(-(share - out), kind="stable")[: n - out.sum()]:
        out[i] += 1
    return out.tolist()


def assign(rng: np.random.Generator, n: int, options: list[dict]) -> list[int]:
    """Option index per item: exact quotas, in a seeded random order."""
    idx = np.repeat(np.arange(len(options)),
                    quota(n, [o["weight"] for o in options]))
    rng.shuffle(idx)
    return idx.tolist()


def _fmt(v) -> str:
    if isinstance(v, str):
        return "'" + v.replace("'", "\\'") + "'"
    return f"{float(v):.10g}"


class Mix:
    """A traffic mix over one configuration's data."""

    def __init__(self, spec: dict, data: datagen.Data):
        self.spec = spec
        self.data = data
        for shape in spec["shapes"]:
            for col, op, _ in shape.get("where", ()):
                if col not in data.columns or op not in _OPS:
                    raise ValueError(f"shape {shape['name']}: bad atom "
                                     f"{col!r} {op!r}")

    # -- the pieces of one query --------------------------------------------
    def _options(self, shape: dict, key: str) -> list[dict]:
        return shape.get(key, self.spec.get(key))

    def _value(self, v, row: int, params: dict, column: str):
        if not isinstance(v, dict):
            return v
        if v.get("row"):
            x = self.data.columns[column][row]
            if self.data.kinds[column] == "categorical":
                return str(self.data.dictionaries[column][x])
            return float(x)
        if "date" in v:
            return float(datagen.day_number(v["date"]))
        if "param" in v:
            x = float(params[v["param"]]) * float(v.get("times", 1.0))
            plus = v.get("plus", 0.0)
            x += float(self._value(plus, row, params, column))
            return round(x, 9)
        raise ValueError(f"unknown value {v!r}")

    def _params(self, shape: dict, rng: np.random.Generator) -> dict:
        out = {}
        for name, p in shape.get("params", {}).items():
            if "int" in p:
                lo, hi = p["int"]
                out[name] = int(rng.integers(lo, hi + 1))
            else:
                pick = p["choice"][int(rng.integers(len(p["choice"])))]
                out[name] = self._value(pick, 0, out, "")
        return out

    def instance(self, shape: dict, rng: np.random.Generator
                 ) -> tuple[int, dict]:
        """The row (a random row, so values come by popularity and jointly)
        and the parameters one query of `shape` takes its values from."""
        return int(rng.integers(self.data.n_rows)), self._params(shape, rng)

    def make(self, shape: dict, agg: dict, group: dict, bound: dict,
             rng: np.random.Generator | None = None,
             at: tuple[int, dict] | None = None) -> QuerySpec:
        """One query: its values from `at` (see `instance`), else drawn."""
        row, params = at if at is not None else self.instance(shape, rng)
        atoms = tuple((c, op, self._value(v, row, params, c))
                      for c, op, v in shape.get("where", ()))
        col = agg.get("column")
        inner = col if col else "*"
        if agg["agg"] == "QUANTILE":
            inner = f"{col}, {agg['level']}"
        parts = [f"SELECT {agg['agg']}({inner}) FROM {self.data.table}"]
        if atoms:
            parts.append("WHERE " + " AND ".join(
                f"{c} {op} {_fmt(v)}" for c, op, v in atoms))
        gcol = group.get("column")
        if gcol:
            parts.append(f"GROUP BY {gcol}")
        conf = bound.get("confidence_pct", 95) / 100.0
        if "error_pct" in bound:
            parts.append(f"ERROR WITHIN {bound['error_pct']:g}% "
                         f"CONFIDENCE {bound.get('confidence_pct', 95):g}%")
            kind = "error"
        elif "seconds" in bound:
            parts.append(f"WITHIN {bound['seconds']:g} SECONDS")
            kind = "time"
        else:
            kind = "none"
        return QuerySpec(
            " ".join(parts), shape["name"], agg["agg"], col, atoms, gcol,
            agg.get("level"), kind,
            bound["error_pct"] / 100.0 if kind == "error" else None,
            float(bound["seconds"]) if kind == "time" else None, conf,
            (row, params))

    # -- whole streams --------------------------------------------------------
    def queries(self, rng: np.random.Generator, n: int) -> list[QuerySpec]:
        """n queries with every list's shares held exactly."""
        shapes = self.spec["shapes"]
        which = assign(rng, n, shapes)
        out: list[QuerySpec | None] = [None] * n
        for si, shape in enumerate(shapes):
            slots = [i for i, s in enumerate(which) if s == si]
            lists = [self._options(shape, k)
                     for k in ("aggregates", "group_by", "bounds")]
            picks = [assign(rng, len(slots), opts) for opts in lists]
            for j, i in enumerate(slots):
                a, g, b = (opts[p[j]] for opts, p in zip(lists, picks))
                out[i] = self.make(shape, a, g, b, rng)
        return out

    def window(self, seconds: float) -> list[tuple[float, QuerySpec]]:
        """The open loop's fixed work for a window: rate x seconds queries
        and Poisson send times conditioned on that count (sorted uniform
        times), drawn from the mix's `pool_seed`, the same in every run."""
        n = max(1, round(self.spec["rate_qps"] * seconds))
        rng = np.random.default_rng(
            np.random.SeedSequence([self.spec["pool_seed"], n]))
        times = np.sort(rng.uniform(0.0, seconds, n))
        return list(zip(times.tolist(), self.queries(rng, n)))

    def aggregate_classes(self, shape: dict) -> list[dict]:
        """One aggregate of each kind that compiles programs of its own:
        QUANTILE or not, by aggregated column (SUM and AVG of one column
        share their scans)."""
        out = {}
        for a in self._options(shape, "aggregates"):
            out.setdefault((a["agg"] == "QUANTILE", a.get("column")), a)
        return list(out.values())

    def time_bounds(self, shape: dict) -> list[dict]:
        return [b for b in self._options(shape, "bounds") if "seconds" in b]
