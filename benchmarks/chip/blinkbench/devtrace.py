"""Reduction of a JAX profiler trace to device metrics.

* Busy time: the union of the intervals in which an operation ran on a
  device (the "XLA Ops" line of each TPU plane), averaged over the devices
  the run used; idle share is 1 - busy / window.
* Operation time by name, from which kernel time is read (kernels are
  matched by name by the metric that needs them).
* Idle gaps: the holes in the busy union inside the window, longest first,
  each named after the host stage whose program span covers its midpoint.

The window is bounded by two host annotations the harness writes
(`MARK_START` / `MARK_END`); their times on the harness's monotonic clock
are known, which puts the program's spans on the profiler's clock.
"""
from __future__ import annotations

import dataclasses
import glob
from pathlib import Path

MARK_START = "blinkbench.window_start"
MARK_END = "blinkbench.window_end"
OPS_LINE = "XLA Ops"


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ascending ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def holes(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi] around disjoint busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Reduced:
    window_ns: tuple[float, float]
    mark_ns: float                 # profiler time of MARK_START
    busy_ns: float                 # mean over devices of the busy union
    op_ns: dict[str, float]        # device time per operation name
    gaps: list[tuple[float, float]]   # idle intervals of device 0

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def kernel_ns(self, patterns) -> float:
        return sum(t for name, t in self.op_ns.items()
                   if any(p in name for p in patterns))


def xplane_file(logdir: Path) -> Path:
    found = sorted(glob.glob(str(Path(logdir) / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return Path(found[-1])


def op_name(hlo: str) -> str:
    """An operation's name without its HLO text: the device trace names
    an op by its whole instruction (`%name.2 = f32[..] custom-call(...)`)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _marks(pd) -> dict[str, float]:
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in (MARK_START, MARK_END):
                    out[ev.name] = ev.start_ns
    return out


def reduce(path: Path, n_devices: int = 1) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    marks = _marks(pd)
    if MARK_START not in marks or MARK_END not in marks:
        raise ValueError("the trace lacks the window's start/end marks")
    lo, hi = marks[MARK_START], marks[MARK_END]
    planes = sorted((p for p in pd.planes
                     if p.name.startswith("/device:TPU:")),
                    key=lambda p: p.name)[:n_devices]
    busy_total, op_ns, gaps0 = 0.0, {}, None
    for plane in planes:
        spans = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                name = op_name(ev.name)
                s, e = (clip([(ev.start_ns, ev.start_ns + ev.duration_ns)],
                             lo, hi) or [(0.0, 0.0)])[0]
                if e > s:
                    spans.append((s, e))
                    op_ns[name] = op_ns.get(name, 0.0) + (e - s)
        busy = union(spans)
        busy_total += sum(e - s for s, e in busy)
        if gaps0 is None:
            gaps0 = holes(busy, lo, hi)
    n = max(len(planes), 1)
    return Reduced((lo, hi), lo, busy_total / n, op_ns,
                   gaps0 if gaps0 is not None else [(lo, hi)])


def attribute(gaps, spans, offset_ns: float, top: int = 10
              ) -> list[tuple[str, float]]:
    """Name the longest idle gaps by what the host was doing: the
    innermost program span (stage name, the part before the first dot)
    covering the gap's midpoint, else "waiting for arrivals". `spans` are
    (t0_s, t1_s, name) on the harness's clock; offset_ns maps them onto
    the profiler's (profiler_ns = t_s * 1e9 + offset_ns)."""
    conv = [((t0 * 1e9 + offset_ns), (t1 * 1e9 + offset_ns), name)
            for t0, t1, name in spans]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        covering = [(t1 - t0, name) for t0, t1, name in conv
                    if t0 <= mid <= t1]
        stage = (min(covering)[1].split(".", 1)[0] if covering
                 else "waiting for arrivals")
        out.append((stage, (e - s) * 1e-9))
    return out
