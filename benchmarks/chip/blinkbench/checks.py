"""How `correct` is decided: the numbers compared and their limits.

After the window closes, a sample of the answers it finished, drawn from
the seed and always holding the one that read the most rows, is recomputed
by the plain reference (`reference.py`) and compared:

* `rows_gap`: largest difference, over every group of every sampled
  answer, between the rows the program says it selected and the rows the
  reference selects at the answer's (family, K). Counts below 2**24 are
  exact in float32, so this comparison is exact: limit 0.
* `estimate_gap`: largest relative gap of a group's estimate and of its
  error-bar ends (relative to the larger end) from the float64 reference,
  with selected-row counts of 2**24 or more compared here too. QUANTILE
  answers are held to the reference's histogram quantile (reference.py).
* `exact_gap`: the same for answers served from the base table (the exact
  path): largest relative gap of a group's value from the float64 exact
  answer.
* `unanswered`: queries that raised anything other than a refusal at
  admission, or had no answer a minute after the window closed. Limit 0.

The limits and the readings they were set from are in PERF.md.
"""
from __future__ import annotations

import numpy as np

EXACT_COUNTS = float(2 ** 24)

LIMITS = {
    "rows_gap": 0.0,
    "estimate_gap": 3e-4,
    "exact_gap": 3e-4,
    "unanswered": 0.0,
}

SAMPLE = 24          # sampled answers checked per run
MAX_EXACT = 16       # of which at most this many exact-path answers


def rel(a: float, b: float) -> float:
    scale = max(abs(a), abs(b))
    return abs(a - b) / scale if scale else 0.0


def is_exact(answer) -> bool:
    return tuple(answer.sample_phi) == ("<exact>",)


def _estimate_gap(g, r) -> float:
    est = rel(g.estimate, r.estimate)
    scale = max(abs(r.lo), abs(r.hi))
    if scale:
        est = max(est, abs(g.ci_low - r.lo) / scale,
                  abs(g.ci_high - r.hi) / scale)
    return est


def gaps(groups, ref: dict) -> tuple[float, float]:
    """(rows gap, estimate gap) of an answer's groups against reference
    groups. A group one side lacks counts as 0 rows there; a reference
    group with other readings (`alts`) is met by the nearest."""
    mine = {tuple(str(v) for v in g.key): g for g in groups}
    rows = est = 0.0
    for key in set(mine) | set(ref):
        g, r = mine.get(key), ref.get(key)
        n_p = g.n_selected if g is not None else 0.0
        n_r = r.n if r is not None else 0.0
        if max(n_p, n_r) < EXACT_COUNTS:
            rows = max(rows, abs(n_p - n_r))
        else:
            est = max(est, rel(n_p, n_r))
        if g is None or r is None or r.estimate is None:
            continue
        est = max(est, min(_estimate_gap(g, x) for x in (r, *r.alts)))
    return rows, est


def pick(records, rng: np.random.Generator) -> list:
    """The answers to check: a seeded sample of the finished ones, with
    the one that read the most rows, and at most MAX_EXACT exact ones."""
    done = [r for r in records if r.answer is not None]
    sampled = [r for r in done if not is_exact(r.answer)]
    exact = [r for r in done if is_exact(r.answer)]
    out = []
    if sampled:
        out.append(max(sampled, key=lambda r: r.answer.rows_read))
        rest = [r for r in sampled if r is not out[0]]
        take = rng.choice(len(rest), min(SAMPLE - 1, len(rest)),
                          replace=False) if rest else []
        out += [rest[i] for i in sorted(take)]
    if exact:
        take = rng.choice(len(exact), min(MAX_EXACT, len(exact)),
                          replace=False)
        out += [exact[i] for i in sorted(take)]
    return out


def compare(records, reference, rng: np.random.Generator,
            precision: str = "f64") -> dict:
    """Numbers compared, over the sampled answers. With precision other
    than f64 the reference at that precision stands in for the program
    (the control) on the same answers."""
    out = {"rows_gap": 0.0, "estimate_gap": 0.0, "exact_gap": 0.0}
    checked = 0
    for r in pick(records, rng):
        ans, spec = r.answer, r.spec
        exact = is_exact(ans)

        def want(p: str = "f64") -> dict:
            return (reference.exact(spec, p) if exact else reference.sampled(
                spec, tuple(ans.sample_phi), ans.sample_k, p))
        ref = want()
        groups = ans.groups if precision == "f64" else \
            [_AsGroup(k, g) for k, g in want(precision).items()]
        rows, est = gaps(groups, ref)
        out["rows_gap"] = max(out["rows_gap"], rows)
        key = "exact_gap" if exact else "estimate_gap"
        out[key] = max(out[key], est)
        checked += 1
    out["answers_compared"] = float(checked)
    return out


class _AsGroup:
    """A reference group in the shape of a program's answer group."""

    def __init__(self, key, g):
        self.key = key
        self.n_selected = g.n
        self.estimate = g.estimate
        self.ci_low = g.lo
        self.ci_high = g.hi


def verdict(values: dict) -> bool:
    return (values.get("answers_compared", 0) >= 1
            and all(values[k] <= lim for k, lim in LIMITS.items()))
