"""Smoke run of the BlinkQL serving path on a TPU chip.

    python chip_smoke.py [--rows N] [--seed S]   # one chip
    python chip_smoke.py --four-chips            # mesh path only, four chips

Generates the Conviva-like sessions table from --seed (default 50M rows) at
the paper's §6.1 settings (K1 = 100,000, c = 2, 50% storage budget, the
templates of benchmarks/common.py), builds sample families with the fused
Pallas scan on, and answers BlinkQL text through BlinkQLService: a burst of
same-template queries in one submit_many (batched kernel, Q > 1), ERROR
WITHIN and WITHIN SECONDS queries, a 200-group GROUP BY City, a QUANTILE and
a two-atom predicate, each cold (compile included) and warm.

It fails, and prints no result, unless:
  * JAX's first device is a TPU (there is no CPU fallback);
  * every scan program the engine compiled holds the Mosaic kernel;
  * one chip: the kernel path's answers agree to a relative 1e-4 with a
    float64 NumPy evaluation over the same sample rows. A second engine on
    the jnp path answers the same queries; its difference from the kernel
    and from float64 is printed, not gated: on a v5e its f32 segment sums
    over 15-25M rows were the less accurate side (1.7e-3 vs the kernel's
    6.8e-6 from float64);
  * four chips: the mesh engine agrees to a relative 1e-4 with a
    one-device engine wherever both ran the same family at the same K;
  * error bars cover the exact NumPy answer over the base table for at
    least 80% of groups, and ERROR WITHIN answers come from samples;
  * no answer is degraded or stale, no service error is raised and no
    workload epoch failed.
The last line of stdout is one JSON object naming the device. Times are
host-clock smoke readings, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import operator
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

K1 = 100_000.0          # §6.1: largest stratification cap
C = 2.0                 # §6.1: resolutions ×2 apart
M = 5                   # resolutions per family (benchmarks/common.py)
BUDGET = 0.5            # §6.1: 50% storage budget
ROWS = 50_000_000       # sessions rows by default
MIN_ROWS = 20_000_000   # smallest table this smoke calls deployment-sized
MAX_REL_DIFF = 1e-4
MIN_COVERAGE = 0.8
BURST = 16              # same-template queries in one submit_many
# Relative f32 rounding allowed when an error bar has zero width (strata the
# sample holds in full, exact base-table fallbacks).
F32_SLACK = 1e-5

_CMP = {"EQ": operator.eq, "NE": operator.ne, "LT": operator.lt,
        "LE": operator.le, "GT": operator.gt, "GE": operator.ge}


def queries(tbl) -> tuple[list[str], list[tuple[str, str]]]:
    """(burst texts, [(name, text)]) for the smoke's query mix."""
    cities = [str(c) for c in tbl.dictionaries["City"][:BURST]]
    burst = [f"SELECT COUNT(*) FROM sessions WHERE City = '{c}' "
             "ERROR WITHIN 10% CONFIDENCE 95%" for c in cities]
    singles = [
        ("error_within", "SELECT AVG(SessionTime) FROM sessions "
         "WHERE OS = 'os1' ERROR WITHIN 2% CONFIDENCE 95%"),
        ("within_seconds", "SELECT SUM(SessionTime) FROM sessions "
         "WHERE Genre = 'genre03' WITHIN 1 SECONDS"),
        ("group_by_city", "SELECT AVG(SessionTime) FROM sessions "
         "GROUP BY City WITHIN 2 SECONDS"),
        ("quantile", "SELECT QUANTILE(SessionTime, 0.5) FROM sessions "
         "WHERE OS = 'os0' ERROR WITHIN 5% CONFIDENCE 95%"),
        ("two_atoms", "SELECT COUNT(*) FROM sessions "
         "WHERE OS = 'os2' AND Genre = 'genre05' "
         "ERROR WITHIN 5% CONFIDENCE 95%"),
    ]
    # Unbounded twins scan the most accurate resolution (K1), so the two
    # engines compared below run them at the same K whatever the clocks say.
    singles += [(f"{name}_k1", text.split(" ERROR WITHIN")[0]
                 .split(" WITHIN ")[0]) for name, text in singles]
    return burst, singles


def build_engine(tbl, templates, *, use_pallas: bool, seed: int, mesh=None):
    from repro.core import BlinkDB, EngineConfig
    db = BlinkDB(EngineConfig(k1=K1, c=C, m=M, uniform_fraction=0.5,
                              use_pallas=use_pallas, ci_method="closed",
                              seed=seed), mesh=mesh)
    db.register_table("sessions", tbl)
    db.build_samples("sessions", templates, storage_budget_fraction=BUDGET)
    return db


def serve(db, burst, singles, label: str, failures: list[str],
          passes=("cold", "warm")) -> dict:
    """Every query once per pass through one BlinkQLService. Returns
    {name: Answer} from the first pass; records ladder-hidden faults."""
    from repro.service import BlinkQLService, ServiceConfig
    answers: dict = {}
    served: list = []
    # No answer cache: the warm pass must execute, not replay.
    with BlinkQLService(db, config=ServiceConfig(use_cache=False)) as svc:
        for rep in passes:
            t0 = time.perf_counter()
            try:
                got = svc.submit_many(burst)
            except Exception as e:   # noqa: BLE001 — reported, then fails
                failures.append(f"{label} burst {rep}: {e!r}")
                got = []
            print(f"[{label}] burst x{len(burst)} {rep} "
                  f"{time.perf_counter() - t0:.3f}s")
            served += got
            if rep == "cold":
                answers.update({f"burst{i}": a for i, a in enumerate(got)})
            for name, text in singles:
                t0 = time.perf_counter()
                try:
                    ans = svc.submit(text)
                except Exception as e:   # noqa: BLE001
                    failures.append(f"{label} {name} {rep}: {e!r}")
                    continue
                print(f"[{label}] {name} {rep} "
                      f"{time.perf_counter() - t0:.3f}s "
                      f"phi={ans.sample_phi} k={ans.sample_k:g} "
                      f"rows={ans.rows_read}/{ans.rows_total} "
                      f"groups={len(ans.groups)} bound_met={ans.bound_met}")
                served.append(ans)
                if rep == "cold":
                    answers[name] = ans
        for a in served:
            if a.degraded or a.staleness_s:
                failures.append(f"{label}: degraded/stale answer for "
                                f"{a.query}")
        for ep in svc.workload_epochs:
            if "error" in ep:
                failures.append(f"{label}: workload epoch failed: "
                                f"{ep['error']}")
    return answers


def rel(a: float, b: float) -> float:
    scale = max(abs(a), abs(b))
    return abs(a - b) / scale if scale else 0.0


def max_rel_diff(a: dict, b: dict, label: str, failures: list[str]) -> float:
    """Largest relative difference of per-group estimates and selected-row
    counts over answers both engines computed on the same family at the
    same K. A QUANTILE estimate is compared by its row count only: the
    kernel bins over the family's value range, the jnp scan over the
    selected rows' range, so their histogram quantiles differ by design."""
    worst, compared, skipped = 0.0, 0, []
    for name, x in a.items():
        y = b.get(name)
        if y is None:
            continue
        if (x.sample_phi, x.sample_k) != (y.sample_phi, y.sample_k):
            skipped.append(name)
            continue
        compared += 1
        gy = {g.key: g for g in y.groups}
        if set(gy) != {g.key for g in x.groups}:
            failures.append(f"{label}: {name} group sets differ")
        quantile = x.query.agg.name == "QUANTILE"
        mine = binned = 0.0
        for g in x.groups:
            ref = gy.get(g.key)
            if ref is None:
                continue
            mine = max(mine, rel(g.n_selected, ref.n_selected))
            d = rel(g.estimate, ref.estimate)
            if quantile:
                binned = max(binned, d)
            else:
                mine = max(mine, d)
        print(f"[{label}] {name}: max relative difference {mine:.3e}"
              + (f" (histogram quantile {binned:.3e}, not gated)"
                 if quantile else ""))
        worst = max(worst, mine)
    print(f"[{label}] compared {compared} answers; different (family, K) "
          f"on {len(skipped)}: {skipped}")
    if compared < sum(1 for n in a if n.endswith("_k1")):
        failures.append(f"{label}: too few answers comparable")
    return worst


def predicate_mask(q, tbl, column, n: int):
    """The query's DNF predicate over `column(name)` arrays, in NumPy."""
    import numpy as np
    if not q.predicate.disjuncts:
        return np.ones(n, dtype=bool)
    mask = np.zeros(n, dtype=bool)
    for conj in q.predicate.disjuncts:
        m = np.ones(n, dtype=bool)
        for atom in conj.atoms:
            value = (tbl.encode_value(atom.column, atom.value)
                     if atom.column in tbl.dictionaries
                     else float(atom.value))
            m &= _CMP[atom.op.name](column(atom.column), value)
        mask |= m
    return mask


def group_keys(q, tbl, column, n: int):
    import numpy as np
    if not q.group_by:
        return np.zeros(n, dtype=np.int64), [()]
    gcol = q.group_by[0]
    return column(gcol), [(tbl.decode_value(gcol, c),)
                          for c in range(tbl.cardinality(gcol))]


def exact_answer(tbl, q) -> dict:
    """{group key: exact value} over every base row — plain NumPy."""
    import numpy as np
    n = tbl.n_rows
    mask = predicate_mask(q, tbl, tbl.host_column, n)
    codes, keys = group_keys(q, tbl, tbl.host_column, n)
    vals = (tbl.host_column(q.value_column).astype(np.float64)
            if q.value_column else np.ones(n))
    codes, vals = codes[mask], vals[mask]
    cnt = np.bincount(codes, minlength=len(keys))
    out = {}
    agg = q.agg.name
    for c, key in enumerate(keys):
        if not cnt[c]:
            continue
        if agg == "COUNT":
            out[key] = float(cnt[c])
        elif agg in ("SUM", "AVG"):
            s = vals[codes == c].sum()
            out[key] = float(s if agg == "SUM" else s / cnt[c])
        else:
            out[key] = float(np.quantile(vals[codes == c], q.quantile))
    return out


def sample_reference(db, ans) -> dict | None:
    """{group key: (selected rows, HT estimate or None)} recomputed in
    float64 NumPy over the family rows the answer scanned: the same f32
    entry-key test (unit·freq < K) both scan paths run, then HT sums in
    float64. None for exact base-table answers."""
    import numpy as np
    if ans.rows_read >= ans.rows_total:
        return None
    q = ans.query
    tbl = db.tables[q.table]
    fam = db.families[q.table][ans.sample_phi]
    strat = fam.row_strata if fam.row_strata is not None else 0
    freq = fam.stratum_freqs.astype(np.float32)[strat] \
        * np.ones(fam.n_rows, np.float32)
    mask = ((fam.unit_host.astype(np.float32) * freq
             < np.float32(ans.sample_k))
            & predicate_mask(q, tbl, fam.host_column, fam.n_rows))
    rates = np.minimum(1.0, ans.sample_k / freq.astype(np.float64))
    w = np.where(mask, 1.0 / rates, 0.0)
    codes, keys = group_keys(q, tbl, fam.host_column, fam.n_rows)
    x = (fam.host_column(q.value_column).astype(np.float64)
         if q.value_column else np.ones(fam.n_rows))
    g = len(keys)
    n = np.bincount(codes, mask.astype(np.float64), minlength=g)
    ws = np.bincount(codes, w, minlength=g)
    wx = np.bincount(codes, w * x, minlength=g)
    est = {"COUNT": ws, "SUM": wx,
           "AVG": wx / np.maximum(ws, 1e-30)}.get(q.agg.name)
    return {key: (n[c], None if est is None else est[c])
            for c, key in enumerate(keys)}


def max_diff_vs_sample(answers: dict, refs: dict, label: str) -> float:
    """Largest relative difference of answers from their float64 sample
    reference (selected rows and, except QUANTILE, the estimate)."""
    worst = 0.0
    for name, ans in answers.items():
        ref = refs.get(name)
        if ref is None:
            continue
        mine = 0.0
        for g in ans.groups:
            n, est = ref.get(g.key, (0.0, 0.0))
            mine = max(mine, rel(g.n_selected, n))
            if est is not None:
                mine = max(mine, rel(g.estimate, est))
        print(f"[{label}] {name}: max relative difference {mine:.3e}")
        worst = max(worst, mine)
    return worst


def coverage(tbl, answers: dict, failures: list[str]) -> float:
    """Share of groups whose error bars cover the exact value."""
    from repro.core import ErrorBound
    covered = total = 0
    for name, ans in answers.items():
        exact = exact_answer(tbl, ans.query)
        hit = 0
        for g in ans.groups:
            want = exact.get(g.key, 0.0)
            slack = F32_SLACK * max(abs(want), 1.0)
            ok = g.ci_low - slack <= want <= g.ci_high + slack
            hit += ok
        covered += hit
        total += len(ans.groups)
        print(f"[coverage] {name}: {hit}/{len(ans.groups)} groups")
        if (isinstance(ans.query.bound, ErrorBound)
                and ans.rows_read >= ans.rows_total):
            failures.append(f"ERROR WITHIN query {name} was not served "
                            "from samples")
    return covered / max(total, 1)


def mosaic_check(db, label: str, failures: list[str], *,
                 skip_quantile: bool = False) -> None:
    """Every compiled scan program must hold the Mosaic kernel — the
    executor falls back to jnp without saying so."""
    caches = [db._programs, db._batched_programs]
    if not skip_quantile:
        caches.append(db._quantile_programs)
    progs = [p for c in caches for p in c.values()]
    plain = sum("tpu_custom_call" not in p.as_text() for p in progs)
    batched_q = max((k[-1] for k in db._batched_programs), default=0)
    print(f"[{label}] scan programs: {len(progs)}, without the Mosaic "
          f"kernel: {plain}, largest batched Q: {batched_q}")
    if not progs or plain:
        failures.append(f"{label}: a scan program runs without the kernel")
    if batched_q < 2:
        failures.append(f"{label}: no batched scan with Q > 1 ran")


def striped_bytes(db) -> int:
    import jax
    return sum(x.nbytes for s in db._striped.values()
               for x in jax.tree.leaves((s.columns, s.unit, s.strat,
                                         s.freq_table, s.valid)))


def compile_seconds(db) -> tuple[float, float, int]:
    """Compile the burst's batched scan program twice more: once with the
    persistent cache off (cold: XLA + Mosaic), once with it on (warm: reads
    the entry the engine's own compile wrote). Returns (cold s, warm s,
    persistent-cache hits)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from repro.core import executor as exec_lib
    hits = []
    jax.monitoring.register_event_listener(
        lambda ev, **kw: hits.append(ev)
        if ev == "/jax/compilation_cache/cache_hits" else None)
    pkey = max(db._batched_programs, key=lambda k: k[-1])
    table, phi, struct, value_col, group_col, n_groups, _, q_pad = pkey
    striped = db._striped[(table, phi)]
    args = (jnp.ones((q_pad,), jnp.float32),
            jnp.zeros((q_pad, len(exec_lib.flat_atoms(struct))),
                      jnp.float32),
            *exec_lib.scan_args(striped))
    times = []
    for use_cache in (False, True):
        jax.config.update("jax_enable_compilation_cache", use_cache)
        compilation_cache.reset_cache()
        fn = exec_lib.make_batched_query_fn(struct, value_col, group_col,
                                            n_groups, use_pallas=True)
        t0 = time.perf_counter()
        fn.lower(*args).compile()
        times.append(time.perf_counter() - t0)
    return times[0], times[1], len(hits)


def one_chip(rows: int, seed: int, failures: list[str]) -> None:
    import jax
    from benchmarks.common import conviva_templates
    from repro.core import table as table_lib
    from repro.data import synth
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    tbl = table_lib.from_columns("sessions",
                                 synth.sessions_table(rows, seed=seed))
    print(f"rows={tbl.n_rows} generated+encoded in "
          f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    db = build_engine(tbl, conviva_templates(), use_pallas=True, seed=seed)
    print(f"families built in {time.perf_counter() - t0:.1f}s:")
    for phi, fam in db.families["sessions"].items():
        print(f"  family {phi}: {fam.n_rows} rows, K={fam.ks}")
    burst, singles = queries(tbl)
    got = serve(db, burst, singles, "kernel", failures)
    print(f"striped family device bytes={striped_bytes(db)}")
    mosaic_check(db, "kernel", failures)
    cold, warm, hits = compile_seconds(db)
    print(f"compile seconds cold={cold:.3f} warm={warm:.3f} "
          f"persistent-cache hits={hits}")

    t0 = time.perf_counter()
    ref_db = build_engine(tbl, conviva_templates(), use_pallas=False,
                          seed=seed)
    print(f"reference (jnp) families built in "
          f"{time.perf_counter() - t0:.1f}s")
    ref = serve(ref_db, burst, singles, "jnp", failures, passes=("cold",))
    diff = max_rel_diff(got, ref, "kernel-vs-jnp", failures)
    print(f"kernel-vs-jnp max relative difference={diff:.3e}")
    refs = {name: sample_reference(db, a) for name, a in got.items()}
    diff = max_diff_vs_sample(got, refs, "kernel-vs-f64")
    print(f"kernel-vs-f64 max relative difference={diff:.3e}")
    if not diff <= MAX_REL_DIFF:
        failures.append(f"kernel-vs-f64 difference {diff:.3e} > "
                        f"{MAX_REL_DIFF}")
    same_k = {name: a for name, a in ref.items() if name in got and
              (a.sample_phi, a.sample_k) == (got[name].sample_phi,
                                             got[name].sample_k)}
    print("jnp-vs-f64 max relative difference="
          f"{max_diff_vs_sample(same_k, refs, 'jnp-vs-f64'):.3e}")
    cov = coverage(tbl, got, failures)
    print(f"coverage of exact answers={cov:.4f}")
    if not cov >= MIN_COVERAGE:
        failures.append(f"coverage {cov:.4f} < {MIN_COVERAGE}")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def four_chips(rows: int, seed: int, failures: list[str]) -> None:
    import jax
    from jax.sharding import Mesh
    from benchmarks.common import conviva_templates
    from repro.core import table as table_lib
    from repro.data import synth
    if len(jax.devices()) < 4:
        failures.append(f"--four-chips needs 4 devices, found "
                        f"{len(jax.devices())}")
        return
    tbl = table_lib.from_columns("sessions",
                                 synth.sessions_table(rows, seed=seed))
    print(f"rows={tbl.n_rows}")
    mesh = Mesh(jax.devices()[:4], ("data",))
    burst, singles = queries(tbl)
    db = build_engine(tbl, conviva_templates(), use_pallas=True, seed=seed,
                      mesh=mesh)
    got = serve(db, burst, singles, "mesh4", failures)
    shardings = {str(s.unit.sharding.spec) for s in db._striped.values()}
    print(f"striped family shardings={shardings} "
          f"device bytes={striped_bytes(db)}")
    print("note: QUANTILE runs the jnp scan on a mesh (its kernel is "
          "one-device only); it is left out of the kernel check")
    mosaic_check(db, "mesh4", failures, skip_quantile=True)
    one = build_engine(tbl, conviva_templates(), use_pallas=True, seed=seed)
    ref = serve(one, burst, singles, "one-device", failures,
                passes=("cold",))
    diff = max_rel_diff(got, ref, "mesh4-vs-one", failures)
    print(f"mesh4-vs-one-device max relative difference={diff:.3e}")
    if not diff <= MAX_REL_DIFF:
        failures.append(f"mesh difference {diff:.3e} > {MAX_REL_DIFF}")
    for i, d in enumerate(jax.devices()[:4]):
        print(f"device {i} peak_bytes_in_use="
              f"{(d.memory_stats() or {}).get('peak_bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh path and its comparison")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py must run from a checkout of the repository",
              file=sys.stderr)
        return 2
    if args.rows < MIN_ROWS:
        print(f"--rows below {MIN_ROWS} is not a deployment-sized run",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    print(f"device={dev.device_kind} count={len(jax.devices())} "
          f"compile cache={compile_cache.enable()}")
    if args.rows < ROWS:
        print(f"rows cut to {args.rows} from {ROWS}")
    failures: list[str] = []
    (four_chips if args.four_chips else one_chip)(args.rows, args.seed,
                                                  failures)
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
