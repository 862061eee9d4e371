"""The plain reference that decides `correct`, and its lower-precision
control.

It imports nothing of the program and reads nothing the program made. From
the configuration's data (generated by the benchmark) and the sampling
design's definition it recomputes what each answer must say:

* A sample family on columns phi at resolution K holds the rows whose
  entry key u * F(x) < K, where F(x) is the row's stratum size on phi
  (float32, as the design states it) and u the row's priority, drawn as
  `uniform(PRNGKey(seed), n, float32, 1e-7, 1)` (the uniform family, phi
  empty, F = N, salts the seed with 0x5EED). An answer names its (phi, K)
  and the reference re-derives those rows from the base data.
* Over them, in float64: rows selected per group, the Horvitz-Thompson
  COUNT / SUM / AVG and the closed-form variances behind the error bars
  (BlinkDB Table 2; delta method for AVG).
* QUANTILE by the estimator the configuration states: the HT-weighted
  histogram of QUANTILE_BINS bins over a fixed range, interpolated inside
  the bin where the weighted CDF crosses the level; variance
  p(1-p) / (n_eff f^2) with the Kish effective size n_eff = (sum w)^2 /
  sum w^2 and f the histogram density there. The range is the value
  column's [min, max] over the family's rows (`kernel` scans) or over the
  selected rows (the exact path, and the engine's jnp scans). Bin indices
  are computed in float32, as the design states them; masses, the CDF and
  the interpolation in float64. Where the CDF meets the level within
  CDF_TIE of a bin's end, float32 may cross in either bin: both readings
  are kept and the answer is held to the nearer.
* Exact answers come from every base row, in float64.

The control computes the same in a lower precision, as a lower-precision
path would: `bf16` rounds every number the query reads (predicate columns
and constants, the aggregated values, the stratum sizes) and every per-row
statistic to bfloat16 before the float64 sums; `high` keeps a bfloat16
pair (high and low part) of each, which is what three passes give for a
product with an exact one-hot operand.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

_CMP = {"=": np.equal, "!=": np.not_equal, "<": np.less,
        "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal}
UNIFORM_SALT = 0x5EED
QUANTILE_BINS = 256
CDF_TIE = 1e-6


def priorities(n: int, seed: int) -> np.ndarray:
    """The per-row priorities u of the sampling design (float32)."""
    import jax
    import jax.numpy as jnp
    u = jax.random.uniform(jax.random.PRNGKey(seed), (n,), dtype=jnp.float32,
                           minval=1e-7, maxval=1.0)
    return np.asarray(u)


def round_to(x: np.ndarray, precision: str) -> np.ndarray:
    """x (float) as the given matrix-unit precision represents it."""
    if precision == "f64":
        return x
    import ml_dtypes
    bf16 = ml_dtypes.bfloat16
    x32 = np.asarray(x, dtype=np.float32)
    hi = x32.astype(bf16).astype(np.float32)
    if precision == "bf16":
        return hi.astype(np.float64)
    if precision == "high":
        lo = (x32 - hi).astype(bf16).astype(np.float32)
        return (hi.astype(np.float64) + lo.astype(np.float64))
    raise ValueError(f"unknown precision {precision!r}")


@dataclasses.dataclass
class Group:
    n: float
    estimate: float | None
    lo: float | None
    hi: float | None
    alts: tuple = ()     # other readings an answer may be held to (ties)


@dataclasses.dataclass
class _Family:
    idx: np.ndarray     # int64 base rows with entry key < K1, ascending
    unit: np.ndarray    # float32 priority of each
    freq: np.ndarray    # float32 stratum size of each
    k1: float


def _crossings(cdf: np.ndarray, q: float) -> list[int]:
    """Bins where the CDF may cross q: the first bin with cdf >= q, and
    the one float32 rounding could pick instead within CDF_TIE."""
    out = []
    for t in (q - CDF_TIE, q, q + CDF_TIE):
        hit = np.flatnonzero(cdf >= t)
        j = int(hit[0]) if hit.size else len(cdf) - 1
        if j not in out:
            out.append(j)
    return out


def hist_quantile(x32: np.ndarray, w: np.ndarray, codes: np.ndarray,
                  g: int, lo: np.float32, hi: np.float32, q: float
                  ) -> list[list[tuple[float, float]]]:
    """Per group, the (value, density) readings of the histogram quantile
    at level q over [lo, hi] (one, or two where the CDF ties q)."""
    nb = QUANTILE_BINS
    span = np.maximum(np.float32(hi) - np.float32(lo), np.float32(1e-12))
    bins = np.clip((x32 - np.float32(lo)) / span * np.float32(nb),
                   np.float32(0), np.float32(nb - 1)).astype(np.int64)
    hist = np.bincount(codes * nb + bins, w, minlength=g * nb).reshape(g, nb)
    width = float(span) / nb
    out = []
    for h in hist:
        cum = np.cumsum(h)
        mass = cum[-1]
        if mass <= 0:
            out.append([(0.0, 0.0)])
            continue
        cdf = cum / mass
        got = []
        for j in _crossings(cdf, q):
            prev = cdf[j - 1] if j > 0 else 0.0
            bm = cdf[j] - prev
            frac = (q - prev) / bm if bm > 1e-12 else 0.5
            got.append((float(lo) + (j + frac) * width,
                        h[j] / (mass * width)))
        out.append(got)
    return out


class Reference:
    def __init__(self, data, cfg: dict):
        self.data = data
        self.engine = cfg["engine"]
        self.seed = int(cfg["engine"]["seed"])
        self.kernel = bool(cfg["engine"]["use_pallas"])
        self._families: dict[tuple, _Family] = {}
        self._units: dict[bool, np.ndarray] = {}

    def _priorities(self, uniform: bool) -> np.ndarray:
        if uniform not in self._units:
            seed = (self.seed ^ UNIFORM_SALT) if uniform else self.seed
            self._units[uniform] = priorities(self.data.n_rows, seed)
        return self._units[uniform]

    def family(self, phi: tuple[str, ...]) -> _Family:
        fam = self._families.get(phi)
        if fam is not None:
            return fam
        n = self.data.n_rows
        if phi:
            key = np.zeros(n, dtype=np.int64)
            for c in sorted(phi):
                key = key * self.data.cardinality(c) + self.data.columns[c]
            freq = np.bincount(key).astype(np.float32)[key]
            k1 = float(self.engine["k1"])
        else:
            freq = np.full(n, np.float32(n))
            k1 = float(self.engine["uniform_fraction"]) * n
        unit = self._priorities(not phi)
        idx = np.flatnonzero(unit * freq < np.float32(k1))
        fam = _Family(idx, unit[idx], freq[idx], k1)
        self._families[phi] = fam
        return fam

    def _mask(self, spec, rows: np.ndarray | None,
              precision: str = "f64") -> np.ndarray:
        """The predicate over `rows` (None: every base row), compared in
        float32 as the design states (in the control's precision)."""
        m = True
        for col, op, value in spec.atoms:
            x = self.data.columns[col]
            x = (x if rows is None else x[rows]).astype(np.float32)
            if self.data.kinds[col] == "categorical":
                v = np.float32(self.data.code_of(col, value))
            else:
                v = np.float32(value)
            if precision != "f64":
                x, v = round_to(x, precision), round_to(v, precision)
            m = m & _CMP[op](x, v)
        n = self.data.n_rows if rows is None else len(rows)
        return np.broadcast_to(m, (n,))

    def _keys(self, spec, rows: np.ndarray):
        if not spec.group_by:
            return np.zeros(len(rows), dtype=np.int64), [()]
        d = self.data.dictionaries[spec.group_by]
        return (self.data.columns[spec.group_by][rows].astype(np.int64),
                [(str(v),) for v in d])

    def _values(self, spec, rows: np.ndarray, precision: str) -> np.ndarray:
        """The aggregated column over `rows` as float32 (ones for COUNT),
        rounded to the control's precision."""
        x = (self.data.columns[spec.value_column][rows].astype(np.float32)
             if spec.value_column else np.ones(len(rows), np.float32))
        return round_to(x, precision).astype(np.float32) \
            if precision != "f64" else x

    def sampled(self, spec, phi, k: float, precision: str = "f64"
                ) -> dict[tuple, Group]:
        """What an answer on family phi at resolution k must say."""
        fam = self.family(tuple(phi))
        freq = round_to(fam.freq, precision).astype(np.float32) \
            if precision != "f64" else fam.freq
        inside = fam.unit * freq < np.float32(k)
        rows = fam.idx[inside]
        freq = freq[inside].astype(np.float64)
        m = self._mask(spec, rows, precision)
        rows, freq = rows[m], freq[m]
        rate = np.minimum(1.0, k / freq)
        w = 1.0 / rate
        vfac = (1.0 - rate) / (rate * rate)
        x32 = self._values(spec, rows, precision)
        x = x32.astype(np.float64)
        codes, keys = self._keys(spec, rows)
        g = len(keys)

        def total(v):
            return np.bincount(codes, round_to(v, precision), minlength=g)

        n = np.bincount(codes, minlength=g).astype(np.float64)
        wsum, wx, vc = total(w), total(w * x), total(vfac)
        vs, vs2 = total(vfac * x), total(vfac * x * x)
        quant = None
        if spec.agg == "QUANTILE":
            if self.kernel:    # the family's range, over all its rows
                col = self.data.columns[spec.value_column][fam.idx]
            else:              # the selected rows' range
                col = x32
            lo, hi = ((np.min(col), np.max(col)) if len(col)
                      else (np.float32(0), np.float32(0)))
            quant = hist_quantile(x32, round_to(w, precision), codes, g,
                                  lo, hi, spec.level)
        return self._groups(spec, keys, n, wsum, wx, vc, vs, vs2, quant)

    def exact(self, spec, precision: str = "f64") -> dict[tuple, Group]:
        """The exact answer over every base row."""
        rows = np.flatnonzero(self._mask(spec, None, precision))
        x32 = self._values(spec, rows, precision)
        x = x32.astype(np.float64)
        codes, keys = self._keys(spec, rows)
        g = len(keys)
        n = np.bincount(codes, minlength=g).astype(np.float64)
        sx = np.bincount(codes, x, minlength=g)
        zero = np.zeros(g)
        quant = None
        if spec.agg == "QUANTILE":
            lo, hi = ((np.min(x32), np.max(x32)) if len(x32)
                      else (np.float32(0), np.float32(0)))
            quant = [[(v, 0.0) for v, _ in got] for got in hist_quantile(
                x32, np.ones(len(rows)), codes, g, lo, hi, spec.level)]
        return self._groups(spec, keys, n, n, sx, zero, zero, zero, quant)

    @staticmethod
    def _groups(spec, keys, n, wsum, wx, vc, vs, vs2, quant=None
                ) -> dict[tuple, Group]:
        z = NormalDist().inv_cdf(0.5 + spec.confidence / 2.0)
        out = {}
        for i, key in enumerate(keys):
            if n[i] <= 0:
                continue
            if spec.agg == "QUANTILE":
                w2 = vc[i] + wsum[i]      # sum w^2 = sum (w^2 - w) + sum w
                n_eff = max(wsum[i] * wsum[i] / w2 if w2 > 0 else 0.0, 1.0)
                p = spec.level
                got = []
                for est, dens in quant[i]:
                    var = p * (1 - p) / (n_eff * max(dens, 1e-12) ** 2) \
                        if dens else 0.0
                    se = float(np.sqrt(var))
                    got.append(Group(n[i], float(est), est - z * se,
                                     est + z * se))
                out[key] = dataclasses.replace(got[0], alts=tuple(got[1:]))
                continue
            if spec.agg == "COUNT":
                est, var = wsum[i], vc[i]
            elif spec.agg == "SUM":
                est, var = wx[i], vs2[i]
            else:   # AVG
                c = max(wsum[i], 1e-12)
                est = wx[i] / c
                var = max((vs2[i] - 2 * est * vs[i] + est * est * vc[i])
                          / (c * c), 0.0)
            se = float(np.sqrt(max(var, 0.0)))
            out[key] = Group(n[i], float(est), est - z * se, est + z * se)
        return out
