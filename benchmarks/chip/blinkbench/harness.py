"""One run of one benchmark cell: set-up, a measured window, the check.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix in BENCHMARK.json; the
harness finds `configs/<config>.json` (sizes) with `configs/<config>.py`
(its data generator), `traffic/<traffic>.json` (the mix) and, for each
per-layer metric the cell lists, `metrics/<metric>.py` (its reader; a
metric named `<metric>.<variant>`, one quantity entered once for each
end-to-end metric it moves in different cells, has the reader of
`<metric>`). A new configuration, mix or metric is new files and entries;
this file does not change.

In order, a run: refuses to go on without the accelerators the cell asks
for; turns on the persistent compile cache; draws the data from the seed;
builds the engine and its sample families; warms every program the window
can use; serves the traffic through `BlinkQLService` for `--seconds`;
waits for the answers still due (a minute at most); reads the device's
peak memory; frees the program; checks a seeded sample of the answers
against the plain reference; prints the result as the last line of
standard output. `--trace 1` also records the program's spans on every
query and a profiler trace of the window's middle, and reports the
per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import logging
import math
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from blinkbench import checks, datagen, devtrace, peaks, traffic, widths
from blinkbench.reference import Reference

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
WAIT_AFTER_CLOSE_S = 60.0
TRACE_AT = 0.25        # traced part of the window starts at this share
TRACE_MAX_S = 10.0     # and lasts at most this long (half the window)
UNREACHABLE = {"error_pct": 1e-4, "confidence_pct": 95}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    generator: object
    mix_spec: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_path = root / configs[w["config"]]["file"]
    cfg = json.loads(cfg_path.read_text())
    gen = load_module(cfg_path.with_suffix(".py"),
                      f"blinkbench_config_{w['config']}")
    mix_spec = json.loads(
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]
    return Cell(name, int(w["chips"]), cfg, gen, mix_spec,
                mine(bench["end_to_end"]), mine(bench["per_layer"]))


# ----------------------------------------------------------- the program
def make_table(data: datagen.Data):
    """The program's Table, through its public constructor, over the
    benchmark's host arrays."""
    import jax.numpy as jnp
    from repro.core import table as table_lib
    from repro.core.types import ColumnKind, ColumnSchema, TableSchema
    schema = []
    for name, kind in data.kinds.items():
        if kind == "categorical":
            schema.append(ColumnSchema(name, ColumnKind.CATEGORICAL,
                                       data.cardinality(name)))
        else:
            schema.append(ColumnSchema(name, ColumnKind.NUMERIC))
    device = {n: jnp.asarray(a) for n, a in data.columns.items()}
    return table_lib.Table(TableSchema(data.table, tuple(schema)), device,
                           dict(data.dictionaries), data.n_rows,
                           columns_host=dict(data.columns))


def build_engine(cfg: dict, table):
    from repro.core import BlinkDB, EngineConfig, QueryTemplate
    e = cfg["engine"]
    db = BlinkDB(EngineConfig(k1=e["k1"], c=e["c"], m=e["m"],
                              uniform_fraction=e["uniform_fraction"],
                              use_pallas=e["use_pallas"],
                              ci_method=e["ci_method"], seed=e["seed"]))
    db.register_table(cfg["table"], table)
    templates = [QueryTemplate(frozenset(t["columns"]), t["weight"])
                 for t in cfg["templates"]]
    db.build_samples(cfg["table"], templates,
                     storage_budget_fraction=e["storage_budget_fraction"])
    return db


def warm(db, mix: traffic.Mix, pool: list) -> dict:
    """Run the queries of `pool` (what the window will send) through the
    engine's public calls until every program the window can reach is
    compiled (or loaded from the persistent cache) and every latency model
    the ELP needs is fitted. An engine call costs about half a second on
    the host whatever it compiles, so each program is reached once:

    * Each distinct instantiation (shape, GROUP BY, constants), unbounded:
      the sample family the run-time selection picks for it, with its
      probe scans on every family (skipped where the table has one).
    * For each shape x GROUP BY x family found x aggregate with programs
      of its own: a batch of each size up to the service's `max_batch`
      (the batched scans and pilots, padded to a power of two, and the
      slices the answers are cut from).
    * For each shape x GROUP BY x aggregate of a shape with error bounds:
      one query on the smallest family found under an error bound no
      family meets and the traffic never sends (so no decision the window
      replays is made here): the certification at the top resolution, the
      pilot on every larger family, the exact fallback (again on the next
      family found until one gets there).
    * One query under a time bound on each family found: the ELP's
      latency model of that family.
    Returns {kind: [calls, seconds]}."""
    from repro.service.parser import parse_blinkql
    spent: dict[str, list] = {}

    def run(kind: str, specs: list) -> list:
        t = time.monotonic()
        qs = [parse_blinkql(s.text, db).normalized() for s in specs]
        out = db.query_batch(qs)
        got = spent.setdefault(kind, [0, 0.0])
        got[0] += 1
        got[1] += time.monotonic() - t
        return out

    fams = db.families[mix.data.table]
    shapes = {s["name"]: s for s in mix.spec["shapes"]}
    none = {"weight": 1.0}
    found: dict[tuple, dict] = {}    # (shape, group) -> {family: spec}
    seen: set = set()
    for s in pool:
        got = found.setdefault((s.shape, s.group_by), {})
        if len(fams) == 1:
            got.setdefault(next(iter(fams)), s)
        elif (s.shape, s.group_by, s.atoms) not in seen:
            seen.add((s.shape, s.group_by, s.atoms))
            ans = run("select", [mix.make(shapes[s.shape], {"agg": "COUNT"},
                                          {"column": s.group_by}, none,
                                          at=s.at)])[0]
            got.setdefault(tuple(ans.sample_phi), s)
    sizes = range(1, int(mix.spec["service"]["max_batch"]) + 1)
    timed: set = set()
    for (name, gcol), got in found.items():
        shape, group = shapes[name], {"column": gcol}
        errors = any("error_pct" in b for b in mix._options(shape, "bounds"))
        for agg in mix.aggregate_classes(shape):
            def one(bound, s, agg=agg):
                return mix.make(shape, agg, group, bound, at=s.at)
            for s in got.values():
                for size in sizes:
                    run("batch", [one(none, s)] * size)
            # From the smallest family the walk passes every larger one;
            # where its pilots select no row it stops short of the exact
            # path, and the next instantiation walks again.
            for phi in sorted(got, key=lambda phi: fams[phi].n_rows) \
                    if errors else ():
                ans = run("ladder", [one(UNREACHABLE, got[phi])])[0]
                if checks.is_exact(ans):
                    break
        bound = next(iter(mix.time_bounds(shape)), None)
        for phi, s in got.items():
            if bound is not None and phi not in timed:
                timed.add(phi)
                run("time", [mix.make(shape, {"agg": "COUNT"}, group, bound,
                                      at=s.at)])
    return spent


def striped_bytes(db) -> int | None:
    """Device bytes of the engine's striped sample families (a private
    attribute of the engine: None where it is gone)."""
    import jax
    try:
        return sum(x.nbytes for s in db._striped.values()
                   for x in jax.tree.leaves((s.columns, s.unit, s.strat,
                                             s.freq_table, s.valid)))
    except AttributeError:
        return None


# ------------------------------------------------------------- the load
@dataclasses.dataclass(eq=False)
class Record:
    spec: traffic.QuerySpec
    due: float                      # scheduled send
    sent: float = math.nan
    done: float | None = None
    answer: object = None
    error: BaseException | None = None
    refused: bool = False

    def finish(self, fut) -> None:
        self.done = time.monotonic()
        try:
            self.answer = fut.result()
        except Exception as e:   # noqa: BLE001 — judged, not raised
            self.error = e


_COMPILE_LOG = None


def compile_log() -> "CompileLog":
    """The process's one CompileLog."""
    global _COMPILE_LOG
    if _COMPILE_LOG is None:
        _COMPILE_LOG = CompileLog()
    return _COMPILE_LOG


class CompileLog(logging.Handler):
    """Names of the programs JAX compiled, or loaded from its persistent
    cache, since the last `take()`: what the in-process cache did not hold."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.hits: list[str] = []
        self.misses: list[str] = []
        lg = logging.getLogger("jax._src.compiler")
        lg.setLevel(logging.DEBUG)
        lg.propagate = False
        lg.addHandler(self)

    def emit(self, record: logging.LogRecord) -> None:
        msg = str(record.msg)
        if msg.startswith("Persistent compilation cache hit"):
            self.hits.append(str(record.args[0]))
        elif msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.misses.append(str(record.args[0]))

    def take(self) -> tuple[list[str], list[str]]:
        out = (self.hits, self.misses)
        self.hits, self.misses = [], []
        return out


def run_open(svc, schedule, t0: float) -> list[Record]:
    from repro.service import AdmissionError
    records = []
    for at, spec in schedule:
        due = t0 + at
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        rec = Record(spec, due, sent=time.monotonic())
        records.append(rec)
        try:
            fut = svc.submit_async(spec.text)
        except AdmissionError as e:
            rec.error, rec.refused, rec.done = e, True, time.monotonic()
        except Exception as e:   # noqa: BLE001 — judged, not raised
            rec.error, rec.done = e, time.monotonic()
        else:
            fut.add_done_callback(rec.finish)
    return records


def wait_for(records, t_close: float) -> None:
    """Wait for answers still due, at most a minute past the close."""
    deadline = t_close + WAIT_AFTER_CLOSE_S
    while any(r.done is None for r in records) and \
            time.monotonic() < deadline:
        time.sleep(0.05)


# -------------------------------------------------------- the metrics
def latency_s(rec: Record) -> float:
    if rec.answer is None or rec.done is None:
        return math.inf
    return rec.done - rec.due


def bound_met(rec: Record) -> bool:
    """What the user asked for, judged from the answer and the clock."""
    if rec.answer is None or rec.done is None:
        return False
    spec = rec.spec
    if spec.bound == "time":
        return rec.done - rec.due <= spec.seconds
    if spec.bound == "error":
        for g in rec.answer.groups:
            half = 0.5 * (g.ci_high - g.ci_low)
            if half > spec.eps * abs(g.estimate) * (1 + 1e-9):
                return False
    return True


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100])."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def timebound_halfwidth_pct(records: list[Record]) -> float | None:
    """Median over time-bound answers of each answer's median group CI
    half-width over |estimate|, in %; None where there is none."""
    half = []
    for r in records:
        if r.spec.bound == "time" and r.answer is not None:
            mine = [50.0 * (g.ci_high - g.ci_low) / abs(g.estimate)
                    for g in r.answer.groups if g.estimate]
            if mine:
                half.append(float(np.median(mine)))
    return float(np.median(half)) if half else None


def end_to_end(records: list[Record], setup_s: float) -> dict:
    lat_ms = [latency_s(r) * 1e3 for r in records]
    out = {
        "latency_p50_ms": percentile(lat_ms, 50),
        "latency_p90_ms": percentile(lat_ms, 90),
        "bound_met_share": sum(map(bound_met, records)) / len(records),
        "setup_s": setup_s,
    }
    half = timebound_halfwidth_pct(records)
    if half is not None:
        out["timebound_halfwidth_pct"] = half
    return out


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader (`metrics/<name>.py`) may read."""
    records: list[Record]           # every query due in the window
    window: tuple[float, float]     # harness clock
    counters: dict[str, float]      # program counters' change over window
    trace: devtrace.Reduced | None  # profiler trace of the traced part
    traced: tuple[float, float] | None  # traced part, harness clock
    schema: dict                    # configuration's columns
    device_kind: str

    @property
    def answered(self) -> list[Record]:
        return [r for r in self.records if r.answer is not None]

    def in_traced(self) -> list[Record]:
        lo, hi = self.traced
        return [r for r in self.answered if lo <= r.done <= hi]

    def required_bytes(self, rec: Record) -> float:
        a = rec.answer
        if checks.is_exact(a):
            return 0.0
        return widths.required_bytes(self.schema, rec.spec,
                                      tuple(a.sample_phi), a.rows_read)

    def peaks(self) -> dict:
        return peaks.peaks_for(self.device_kind)


def counters(db) -> dict[str, float]:
    width = db.metrics.histogram("service_batch_width").labels()
    return {"rows_read": db.metrics.counter("engine_rows_read_total").value(),
            "batch_width_sum": width.sum, "batch_width_n": float(width.n)}


def program_spans(records: list[Record]) -> list[tuple[float, float, str]]:
    out = []
    for r in records:
        tr = getattr(r.answer, "trace", None)
        if tr is not None:
            out += [(s.t0, s.t1, s.name) for s in tr.spans]
    return out


# ------------------------------------------------------------- a run
def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


@dataclasses.dataclass
class Prepared:
    """Everything set-up made: the data, the engine and the traffic."""
    cfg: dict
    data: datagen.Data
    db: object
    mix: traffic.Mix


def prepare(cell: Cell, seed: int, seconds: float,
            n_rows: int | None = None,
            engine_overrides: dict | None = None) -> Prepared:
    """Data from the seed, the engine and its families, the warm-up on
    the queries a window of `seconds` sends."""
    cfg = {**cell.cfg,
           "engine": {**cell.cfg["engine"], **(engine_overrides or {})}}
    t = time.monotonic()
    data = cell.generator.generate(cfg, seed, n_rows or cfg["rows"])
    log(f"data: {data.n_rows} rows in {time.monotonic() - t:.1f}s")
    t = time.monotonic()
    db = build_engine(cfg, make_table(data))
    fams = db.families[cfg["table"]]
    log(f"families in {time.monotonic() - t:.1f}s: " + ", ".join(
        f"{list(p)}={f.n_rows}" for p, f in fams.items()))
    mix = traffic.Mix(cell.mix_spec, data)
    t = time.monotonic()
    compiles = compile_log()
    compiles.take()
    spent = warm(db, mix, [s for _, s in mix.window(seconds)])
    hits, misses = compiles.take()
    log(f"warm-up in {time.monotonic() - t:.1f}s: " + ", ".join(
        f"{k} {n} calls {s:.1f}s" for k, (n, s) in spent.items())
        + f"; programs from the persistent cache {len(hits)}, compiled "
        f"{len(misses)}; striped family bytes={striped_bytes(db)}")
    return Prepared(cfg, data, db, mix)


@dataclasses.dataclass
class Served:
    records: list[Record]
    window: tuple[float, float]
    counters: dict[str, float]
    traced: tuple[float, float] | None = None
    trace_file: Path | None = None


def serve(prep: Prepared, seconds: float, trace: bool, trace_dir: Path,
          on_start=None) -> Served:
    """One measured window of the mix through a fresh BlinkQLService, then
    the wait for answers still due. With `trace` the service records every
    query's spans and a profiler thread traces the window's middle into
    `trace_dir`. `on_start()` runs just before the window opens."""
    import jax
    from repro.service import BlinkQLService, ServiceConfig
    mix, db = prep.mix, prep.db
    schedule = mix.window(seconds)
    svc = BlinkQLService(db, config=ServiceConfig(
        **mix.spec.get("service", {}), trace=trace, trace_sample_every=1))
    compiles = compile_log()
    before = counters(db)
    out = Served([], (0.0, 0.0), {})

    def profile() -> None:
        lead = TRACE_AT * seconds
        span = min(TRACE_MAX_S, 0.5 * seconds)
        time.sleep(max(0.0, t0 + lead - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # Python calls would slow the host
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation(devtrace.MARK_START):
            a = time.monotonic()
        time.sleep(max(0.0, t0 + lead + span - time.monotonic()))
        with jax.profiler.TraceAnnotation(devtrace.MARK_END):
            b = time.monotonic()
        jax.profiler.stop_trace()
        out.traced = (a, b)

    if on_start is not None:
        on_start()
    # What set-up allocated stays put: the collector's full passes during
    # the window then walk only what the window itself allocates.
    gc.collect()
    gc.freeze()
    compiles.take()
    t0 = time.monotonic()
    profiler = threading.Thread(target=profile, name="profiler") \
        if trace else None
    if profiler:
        profiler.start()
    records = run_open(svc, schedule, t0)
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    wait_for(records, max(time.monotonic(), t0 + seconds))
    if profiler:
        profiler.join()
    hits, misses = compiles.take()
    gc.unfreeze()
    svc.close()
    out.records, out.window = records, (t0, t0 + seconds)
    out.counters = {k: counters(db)[k] - v for k, v in before.items()}
    late = [r.sent - r.due for r in records] or [0.0]
    log(f"window: {len(records)} queries, {out.counters['batch_width_n']:.0f}"
        f" engine calls; compiles in window={len(hits) + len(misses)} "
        f"(from the persistent cache {sorted(set(hits))}, compiled "
        f"{sorted(set(misses))}); generator late mean="
        f"{np.mean(late) * 1e3:.2f}ms max={np.max(late) * 1e3:.2f}ms")
    picked: dict[str, int] = {}
    for r in records:
        if r.spec.bound == "time" and r.answer is not None:
            key = f"{list(r.answer.sample_phi)} K={r.answer.sample_k:g}"
            picked[key] = picked.get(key, 0) + 1
    log(f"time-bound answers by family and K: {picked}; refused at "
        f"admission {sum(r.refused for r in records)}")
    if trace:
        out.trace_file = devtrace.xplane_file(trace_dir)
    return out


def main(argv=None, *, t_start: float | None = None, root: Path = ROOT,
         require_tpu: bool = True, n_rows: int | None = None,
         engine_overrides: dict | None = None,
         keep_trace: Path | None = None, controls: tuple = ()) -> int:
    """Run one cell once; returns the exit code. The keyword arguments
    are for tests and tools: a smaller table, engine settings, keeping the
    profiler trace, and control precisions to read beside the check."""
    t_start = time.monotonic() if t_start is None else t_start
    args = parse_args(argv)
    try:
        cell = load_cell(root, args.workload)
    except (OSError, KeyError, ValueError) as e:
        log(f"cannot load workload {args.workload!r}: {e!r}")
        return 2
    src = root / "src"
    if not (src / "repro").is_dir():
        log(f"no program under {src}")
        return 2
    sys.path.insert(0, str(src))
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        log(f"needs {cell.chips} TPU chip(s); JAX sees {len(devices)} "
            f"{devices[0].platform} device(s)")
        return 3
    from repro.launch import compile_cache
    log(f"cell={cell.name} seed={args.seed} device={devices[0].device_kind} "
        f"x{len(devices)} compile cache={compile_cache.enable()}")
    seed = args.seed
    prep = prepare(cell, seed, args.seconds, n_rows, engine_overrides)
    setup = {}
    trace_dir = Path(tempfile.mkdtemp(prefix="blinkbench-trace-"))
    try:
        got = serve(prep, args.seconds, bool(args.trace), trace_dir,
                    on_start=lambda: setup.setdefault(
                        "s", time.monotonic() - t_start))
        stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
        peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
        log(f"peak_bytes_in_use={peak_bytes}")
        reduced = None
        if got.trace_file is not None:
            if keep_trace is not None:
                keep_trace.mkdir(parents=True, exist_ok=True)
                shutil.copy(got.trace_file,
                            keep_trace / f"{cell.name}-{seed}.xplane.pb")
            reduced = devtrace.reduce(got.trace_file, cell.chips)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    records = got.records
    view = RunView(records, got.window, got.counters, reduced, got.traced,
                   prep.cfg["columns"], devices[0].device_kind)

    e2e = end_to_end(records, setup["s"])
    log("end to end: " + ", ".join(f"{k}={v:.6g}" for k, v in e2e.items()))
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            base = m["name"].split(".")[0]
            reader = load_module(BENCH_DIR / "metrics" / f"{base}.py",
                                 f"blinkbench_metric_{base}")
            value = reader.read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    breakdown = None
    if reduced is not None:
        ops = sorted(reduced.op_ns.items(), key=lambda kv: -kv[1])[:10]
        offset_ns = reduced.mark_ns - got.traced[0] * 1e9
        breakdown = {
            "device_ops": [[n, t * 1e-9] for n, t in ops],
            "idle_gaps": [list(g) for g in devtrace.attribute(
                reduced.gaps, program_spans(records), offset_ns)],
        }
    data, cfg = prep.data, prep.cfg
    del prep, view
    gc.collect()

    t = time.monotonic()
    ref = Reference(data, cfg)
    values = checks.compare(records, ref, np.random.default_rng([seed, 3]))
    values = {k: float(v) for k, v in values.items()}
    values["unanswered"] = float(sum(1 for r in records
                                     if r.answer is None and not r.refused))
    correct = checks.verdict(values)
    log(f"reference check of {values['answers_compared']:.0f} answers in "
        f"{time.monotonic() - t:.1f}s")
    for p in controls:
        low = checks.compare(records, ref, np.random.default_rng([seed, 3]),
                             precision=p)
        low["unanswered"] = values["unanswered"]
        log(f"control {p}: correct={checks.verdict(low)} " + ", ".join(
            f"{k}={v!r}" for k, v in low.items()))
    shown = {k: {"value": values[k], "limit": lim}
             for k, lim in checks.LIMITS.items()}
    result = {
        "correct": correct,
        "attempted": len(records),
        "failed": sum(1 for r in records if r.answer is None),
        "metrics": {k: {"value": _finite(v["value"]), "unit": v["unit"]}
                    for k, v in metrics.items()},
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": int(peak_bytes)},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = breakdown
    result["checks"] = shown
    for k, v in shown.items():
        log(f"check {k}={v['value']!r} limit={v['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


def _finite(v: float) -> float:
    """JSON has no infinity: a latency percentile that falls on a query
    that was never answered is reported as 1e15 ms."""
    return v if math.isfinite(v) else 1e15
