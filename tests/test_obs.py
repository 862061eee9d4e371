"""Observability plane: span tracing, metrics export, statement surface.

Asserts the contracts docs/OBSERVABILITY.md promises:

* span machinery — no-listener fast path, cross-thread nesting under the
  anchor, activation dedup, outermost-only stage timings;
* sampling policy — contract / forced / armed-fault / 1-in-N;
* LADDER COMPLETENESS — a traced query that walked a degradation rung
  (retry, replica reroute, stale serve, shed, exhausted) carries the
  matching spans, and error traces are retained in the tracer ring;
* disabled tracing is bit-identical — `trace=False` answers match traced
  answers field-for-field (tracing is metadata, never compute);
* export — merged snapshot schema, Prometheus rendering, and the
  `SHOW METRICS` / `EXPLAIN` statement surface.
"""
import threading

import pytest

from repro.core import BlinkDB, EngineConfig
from repro.core import table as table_lib
from repro.data import synth
from repro.fault.inject import FaultPlan, FaultSpec, arm
from repro.obs import metrics as obs_metrics
from repro.obs.trace import (QueryTrace, Tracer, activate, active_traces,
                             span)
from repro.service import (BlinkQLError, BlinkQLService, DeadlineShedError,
                           DegradedServiceError, Explain, ServiceConfig,
                           ShowMetrics, parse_blinkql, parse_statement)

N_SHARDS = 4  # EngineConfig default n_logical_shards


@pytest.fixture(scope="module")
def db():
    tbl = table_lib.from_columns("sessions",
                                 synth.sessions_table(20_000, seed=2))
    d = BlinkDB(EngineConfig(k1=400.0, m=3, seed=1))
    d.register_table("sessions", tbl)
    d.add_family("sessions", ("City",))
    d.add_family("sessions", ())
    return d


AVG_TXT = ("SELECT AVG(SessionTime) FROM sessions WHERE City = 'city003' "
           "ERROR WITHIN 10% CONFIDENCE 95%")


def _avg_q(db):
    return parse_blinkql(AVG_TXT, db).normalized()


def _assert_bit_identical(a, b):
    assert a.sample_phi == b.sample_phi
    assert a.sample_k == b.sample_k
    ka = {g.key: g for g in a.groups}
    kb = {g.key: g for g in b.groups}
    assert ka.keys() == kb.keys()
    for key in ka:
        assert ka[key].estimate == kb[key].estimate
        assert ka[key].stderr == kb[key].stderr
        assert ka[key].ci_low == kb[key].ci_low
        assert ka[key].ci_high == kb[key].ci_high


def _root_reaches(tr, s):
    """Walk the parent chain of span `s` to the trace root; return the
    root's index (must be the request span for every service span)."""
    i = s.index
    while tr.spans[i].parent >= 0:
        i = tr.spans[i].parent
    return i


# ===================================================== span machinery (unit)

def test_span_is_noop_singleton_without_active_trace():
    assert active_traces() == ()
    a = span("anything", x=1)
    b = span("else")
    assert a is b                       # the no-listener fast path singleton
    with a as s:
        assert s.set(more=2) is s       # .set chains and records nothing


def test_cross_thread_spans_nest_under_anchor():
    tr = QueryTrace("q", reason="forced")
    root = tr.open_span("request", {})
    tr.set_anchor(root.index)
    seen = {}

    def worker():
        with activate(tr):
            with span("scan", shard=0):
                pass
        seen["ok"] = True

    t = threading.Thread(target=worker, name="obs-worker")
    t.start()
    t.join()
    assert seen["ok"]
    tr.close_span(root)
    tr.finish()
    (scan,) = tr.find("scan")
    assert scan.parent == root.index    # adopted under the anchor
    assert scan.thread == "obs-worker"
    assert _root_reaches(tr, scan) == root.index


def test_activate_dedups_already_active_trace():
    tr = QueryTrace("q")
    with activate(tr):
        with activate(tr, None):        # re-activation + None filtering
            with span("s"):
                pass
        assert active_traces() == (tr,)
    assert active_traces() == ()
    assert len(tr.find("s")) == 1       # recorded once, not twice


def test_timings_count_only_outermost_stage_spans():
    tr = QueryTrace("q")
    outer = tr.open_span("scan", {})
    inner = tr.open_span("scan.shard", {})
    tr.close_span(inner)
    tr.close_span(outer)
    est = tr.open_span("estimate", {})
    tr.close_span(est)
    tr.finish()
    # Overwrite the monotonic stamps with a hand-built timeline: scan spans
    # 2.0s with a nested 1.5s shard attempt, estimate 0.25s, total 3.0s.
    tr.t0, tr.t1 = 100.0, 103.0
    outer.t0, outer.t1 = 100.0, 102.0
    inner.t0, inner.t1 = 100.25, 101.75
    est.t0, est.t1 = 102.0, 102.25
    t = tr.timings()
    assert t["scan"] == pytest.approx(2.0)        # NOT 3.5: inner folds in
    assert t["estimate"] == pytest.approx(0.25)
    assert t["total"] == pytest.approx(3.0)


def test_tracer_sampling_policy():
    tr = Tracer(sample_every=3)
    assert tr.should_sample(forced=True) == "forced"
    assert tr.should_sample(contract=True) == "contract"
    with arm(FaultPlan()):
        assert tr.should_sample() == "fault"
    assert [tr.should_sample() for _ in range(6)] == \
        [None, None, "sampled", None, None, "sampled"]
    tr.enabled = False
    assert tr.should_sample(forced=True) is None   # kill switch beats forced
    tr.enabled = True
    tr.sample_every = 0
    assert tr.should_sample() is None              # unconditional stream off


def test_tracer_ring_respects_capacity():
    tr = Tracer(capacity=4, sample_every=1)
    for i in range(10):
        tr.finish(tr.start(f"q{i}", "sampled"))
    recent = tr.recent()
    assert len(recent) == 4
    assert [t.query_text for t in recent] == ["q6", "q7", "q8", "q9"]


# ===================================================== end-to-end tracing

def test_contract_query_traced_end_to_end(db):
    svc = BlinkQLService(db)
    try:
        ans = svc.submit(AVG_TXT)
        tr = ans.trace
        assert tr is not None and tr.reason == "contract"
        names = set(tr.span_names())
        assert {"request", "parse", "admit", "plan", "scan",
                "estimate"} <= names
        # Every span closed, and every span's parent chain reaches the
        # request root (index 0) — no orphans across threads.
        assert tr.spans[0].name == "request"
        for s in tr.spans:
            assert s.t1 >= s.t0
            assert _root_reaches(tr, s) == 0
        # Answer.timings mirrors the trace's stage breakdown.
        assert ans.timings is not None
        for stage in ("parse", "admit", "plan", "scan", "estimate"):
            assert ans.timings[stage] >= 0.0
        assert ans.timings["total"] >= ans.timings["scan"]
        # The CACHE stores the untraced answer; traces attach per-request.
        cached = svc.cache.get(_avg_q(db))
        assert cached is not None and cached.trace is None
        # A cache hit still gets its own (short) trace.
        hit = svc.submit(AVG_TXT)
        assert hit.trace is not None
        assert hit.trace.span_names() == ["request", "parse", "cache"]
        (c,) = hit.trace.find("cache")
        assert c.attrs.get("hit") is True
        assert hit.timings["total"] >= 0.0
    finally:
        svc.close()


def test_queued_path_spans_cross_threads(db):
    svc = BlinkQLService(db, config=ServiceConfig(use_cache=False,
                                                  solo_bypass=False))
    try:
        ans = svc.submit(AVG_TXT)
        tr = ans.trace
        assert tr is not None
        names = set(tr.span_names())
        assert {"request", "parse", "admit", "plan", "scan"} <= names
        threads = {s.thread for s in tr.spans}
        assert len(threads) >= 2        # session thread + dispatcher thread
        (admit,) = tr.find("admit")
        assert admit.attrs.get("batch", 0) >= 1
        for s in tr.spans:              # dispatcher spans nest under root
            assert _root_reaches(tr, s) == 0
    finally:
        svc.close()


def test_replica_reroute_attempts_recorded(db):
    svc = BlinkQLService(db, config=ServiceConfig(use_cache=False))
    try:
        kill_r0 = FaultPlan([FaultSpec(site="shard.scan", kind="kill",
                                       match=(("shard", 1), ("replica", 0)))],
                            seed=0)
        with arm(kill_r0):
            ans = svc.submit(AVG_TXT)
        assert not ans.degraded
        tr = ans.trace
        assert tr is not None and tr.reason == "contract"
        attempts = tr.find("scan.shard")
        # N_SHARDS first attempts + one re-route = N_SHARDS + 1.
        assert len(attempts) == N_SHARDS + 1
        failed = [s for s in attempts if s.attrs.get("ok") is False]
        assert [(s.attrs["shard"], s.attrs["replica"]) for s in failed] == \
            [(1, 0)]
        assert failed[0].attrs.get("error")
        assert any(s.attrs.get("shard") == 1 and s.attrs.get("replica") == 1
                   and s.attrs.get("ok") is True for s in attempts)
    finally:
        svc.close()


def test_exact_fallback_span_recorded(db):
    """An unreachable ERROR bound walks the planning ladder to the exact
    base-table rung; the trace must show it (scan.exact) alongside the
    plan span."""
    svc = BlinkQLService(db, config=ServiceConfig(use_cache=False))
    try:
        ans = svc.submit("SELECT AVG(SessionTime) FROM sessions "
                         "WHERE City = 'city003' "
                         "ERROR WITHIN 0.0001% CONFIDENCE 95%")
        assert ans.sample_phi == ("<exact>",) and ans.bound_met
        tr = ans.trace
        assert tr is not None and tr.reason == "contract"
        (exact,) = tr.find("scan.exact")
        assert exact.attrs.get("rows_read", 0) > 0
        assert "plan" in tr.span_names()
    finally:
        svc.close()


def test_stale_serve_ladder_spans(db):
    svc = BlinkQLService(db)
    try:
        warm = svc.submit(AVG_TXT)
        svc.cache._on_invalidate("sessions", None)
        with arm(FaultPlan([FaultSpec(site="engine.scan",
                                      kind="kill")], seed=0)):
            stale = svc.submit(AVG_TXT)
        assert stale.degraded and stale.staleness_s > 0.0
        _assert_bit_identical(warm, stale)
        tr = stale.trace
        assert tr is not None
        retries = tr.find("ladder.retry")
        assert retries and all(r.attrs.get("error") for r in retries)
        (served,) = tr.find("ladder.stale_serve")
        assert served.attrs["age_s"] > 0.0
        assert svc.n_stale == 1
    finally:
        svc.close()


def test_exhausted_ladder_trace_retained_in_ring(db):
    svc = BlinkQLService(db, config=ServiceConfig(use_cache=False))
    try:
        with arm(FaultPlan([FaultSpec(site="engine.scan",
                                      kind="kill")], seed=0)):
            with pytest.raises(DegradedServiceError):
                svc.submit(AVG_TXT)
        tr = svc.tracer.recent()[-1]
        assert tr.error == "DegradedServiceError"
        names = set(tr.span_names())
        assert "ladder.retry" in names and "ladder.exhausted" in names
    finally:
        svc.close()


def test_shed_trace_retained_and_counted(db):
    svc = BlinkQLService(db, config=ServiceConfig(use_cache=False,
                                                  solo_bypass=False))
    try:
        svc.submit("SELECT COUNT(SessionTime) FROM sessions "
                   "WITHIN 5 SECONDS")            # prime the EWMA
        svc._exec_ewma = 10.0                     # simulate saturation
        with pytest.raises(DeadlineShedError):
            svc.submit("SELECT COUNT(SessionTime) FROM sessions "
                       "WHERE City = 'city001' WITHIN 0.05 SECONDS")
        assert svc.n_shed == 1 and svc.stats()["shed"] == 1
        tr = svc.tracer.recent()[-1]
        assert tr.error == "DeadlineShedError"    # shed BEFORE any scan span
        assert "scan" not in tr.span_names()
    finally:
        svc.close()


def test_disabled_tracing_is_bit_identical(db):
    on = BlinkQLService(db, config=ServiceConfig(use_cache=False,
                                                 trace_sample_every=1))
    off = BlinkQLService(db, config=ServiceConfig(use_cache=False,
                                                  trace=False))
    try:
        a = on.submit(AVG_TXT)
        b = off.submit(AVG_TXT)
        assert a.trace is not None and a.timings is not None
        assert b.trace is None and b.timings is None
        _assert_bit_identical(a, b)
        assert off.tracer.recent() == []          # nothing retained either
    finally:
        on.close()
        off.close()


# ===================================================== metrics + statements

def test_metrics_snapshot_schema_and_prometheus(db):
    svc = BlinkQLService(db)
    try:
        svc.submit(AVG_TXT)
        snap = svc.metrics_snapshot()
        assert snap["schema_version"] == 1
        assert set(snap) >= {"schema_version", "counters", "gauges",
                             "histograms"}
        assert {"engine_queries_total", "service_queries_total",
                "service_batches_total", "cache_events_total",
                "workload_queries_total"} <= set(snap["counters"])
        assert "service_queue_depth" in snap["gauges"]
        # The dispatcher heartbeat gauge evaluates live and is a small age.
        beat = snap["gauges"]["service_last_beat_age_s"]["values"]
        assert 0.0 <= beat["dispatcher"] < 60.0
        assert {"service_batch_width",
                "engine_scan_seconds"} <= set(snap["histograms"])
        text = svc.render_prometheus()
        assert "# TYPE service_queries_total counter" in text
        assert "service_last_beat_age_s" in text
        assert obs_metrics.to_json(snap)          # stable-schema JSON output
    finally:
        svc.close()


def test_service_counters_isolated_per_service_instance(db):
    """The metric registry outlives services (it is the ENGINE's); the
    per-service stats()/n_* views must subtract the construction-time
    baseline so a fresh service starts at zero."""
    a = BlinkQLService(db, config=ServiceConfig(use_cache=False))
    try:
        a.submit(AVG_TXT)
        assert a.n_queries == 1
    finally:
        a.close()
    b = BlinkQLService(db, config=ServiceConfig(use_cache=False))
    try:
        assert b.n_queries == 0 and b.n_batches == 0
        b.submit(AVG_TXT)
        assert b.n_queries == 1
    finally:
        b.close()


def test_parse_statement_dispatch(db):
    s = parse_statement("SHOW METRICS", db)
    assert isinstance(s, ShowMetrics) and s.fmt == "json"
    s = parse_statement("show metrics format prometheus", db)
    assert isinstance(s, ShowMetrics) and s.fmt == "prometheus"
    e = parse_statement(f"EXPLAIN {AVG_TXT}", db)
    assert isinstance(e, Explain)
    assert e.query.normalized() == _avg_q(db)
    assert e.text == AVG_TXT
    q = parse_statement(AVG_TXT, db)
    assert q.normalized() == _avg_q(db)
    with pytest.raises(BlinkQLError):
        parse_statement("SHOW METRICS FORMAT XML", db)
    with pytest.raises(BlinkQLError):
        parse_statement("SHOW METRICS garbage", db)
    with pytest.raises(BlinkQLError):
        parse_statement("EXPLAIN", db)
    with pytest.raises(BlinkQLError):
        parse_blinkql("SHOW METRICS", db)   # SELECT-only entry stays strict


def test_execute_show_metrics_and_explain(db):
    svc = BlinkQLService(db, config=ServiceConfig(use_cache=False))
    try:
        snap = svc.execute("SHOW METRICS")
        assert isinstance(snap, dict) and snap["schema_version"] == 1
        text = svc.execute("SHOW METRICS FORMAT PROMETHEUS")
        assert isinstance(text, str) and "service_queries_total" in text
        rep = svc.execute(f"EXPLAIN {AVG_TXT}")
        assert rep["answer"].groups
        assert rep["trace"]["reason"] == "forced"
        assert rep["plan"].get("family") == ["City"]
        assert rep["plan"].get("k", 0) > 0
        assert rep["timings"]["total"] > 0.0
        span_names = [s["name"] for s in rep["trace"]["spans"]]
        assert "plan" in span_names and "scan" in span_names
        # Plain SELECT through execute() behaves exactly like submit().
        ans = svc.execute(AVG_TXT)
        assert ans.groups
    finally:
        svc.close()


def test_explain_reports_cached_plan(db):
    svc = BlinkQLService(db)   # cache ON
    try:
        svc.submit(AVG_TXT)
        rep = svc.explain(AVG_TXT)
        assert rep["plan"] == {"cached": True}
        assert rep["answer"].groups
    finally:
        svc.close()


def test_explain_honors_trace_kill_switch(db):
    svc = BlinkQLService(db, config=ServiceConfig(use_cache=False,
                                                  trace=False))
    try:
        rep = svc.explain(AVG_TXT)
        assert rep["trace"] is None and rep["plan"] == {}
        assert rep["answer"].groups
    finally:
        svc.close()


def test_fault_injection_counter_in_merged_snapshot(db):
    """fault_injections_total lives in the process-global registry; the
    merged snapshot must surface it next to the engine's metrics."""
    svc = BlinkQLService(db, config=ServiceConfig(use_cache=False))
    try:
        before = _fault_count(svc.metrics_snapshot())
        plan = FaultPlan([FaultSpec(site="engine.scan", kind="kill",
                                    max_fires=1)], seed=0)
        with arm(plan):
            ans = svc.submit(AVG_TXT)   # retry rung absorbs one kill
        assert ans.groups and plan.n_fires == 1
        snap = svc.metrics_snapshot()
        assert _fault_count(snap) == before + 1
        # And the retry rung shows in the ladder counter.
        ladder = snap["counters"]["service_ladder_total"]["values"]
        assert ladder.get("retry", 0) >= 1
    finally:
        svc.close()


def _fault_count(snap) -> float:
    vals = snap["counters"].get("fault_injections_total", {})
    return sum(vals.get("values", {}).values())


# ================================== spans inside the scan and the queue

UNBOUNDED_TXT = ("SELECT AVG(SessionTime) FROM sessions "
                 "WHERE City = 'city003'")
QUANTILE_TXT = ("SELECT QUANTILE(SessionTime, 0.5) FROM sessions "
                "WHERE City = 'city005'")
# Span names recorded before the scan split; any other span of these
# stages must sit under a span of its own stage, so Answer.timings and the
# benchmark's stage readers see the same intervals as before.
STAGES_READ = ("plan", "scan", "estimate")
PRE_SPLIT = {"plan", "plan.pilot", "plan.certify_top", "plan.escalate",
             "scan", "scan.shard", "scan.exact", "estimate"}
TAXONOMY = PRE_SPLIT | {"request", "parse", "cache", "admit", "admit.queue",
                        "scan.launch", "scan.wait", "fetch", "compile",
                        "ladder.retry", "ladder.stale_serve",
                        "ladder.exhausted"}


@pytest.fixture(scope="module")
def sub_db():
    tbl = table_lib.from_columns("sessions",
                                 synth.sessions_table(20_000, seed=2))
    d = BlinkDB(EngineConfig(k1=400.0, m=3, seed=1, ci_method="subsampling",
                             n_subsamples=8))
    d.register_table("sessions", tbl)
    d.add_family("sessions", ("City",))
    d.add_family("sessions", ())
    return d


def _q(db, text):
    return parse_blinkql(text, db).normalized()


def _traced(run) -> QueryTrace:
    tr = QueryTrace("q", reason="forced")
    with activate(tr):
        run()
    tr.finish()
    return tr


def _ancestors(tr, s):
    p = s.parent
    while p >= 0:
        yield tr.spans[p]
        p = tr.spans[p].parent


def _assert_scans_split(tr):
    """Each program run under a scan span is split into its launch and its
    wait, in that order; attempts a fault plan killed ran no program."""
    runs = [s for s in tr.spans if s.name in ("scan", "scan.exact")]
    assert runs
    for sc in runs:
        kids = tr.children(sc.index)
        if any(k.name == "scan.shard" for k in kids):
            runs += [k for k in kids if k.attrs.get("ok")]
            continue
        assert [k.name for k in kids] == ["scan.launch", "scan.wait"], \
            (sc.name, [k.name for k in kids])
        launch, wait = kids
        assert sc.t0 <= launch.t0 <= launch.t1 <= wait.t0 <= wait.t1 <= sc.t1
    for s in tr.spans:
        if s.name in ("scan.launch", "scan.wait"):
            assert tr.spans[s.parent].name in ("scan", "scan.exact",
                                                "scan.shard")


def _kill_one_replica():
    return arm(FaultPlan([FaultSpec(site="shard.scan", kind="kill",
                                    match=(("shard", 1), ("replica", 0)))],
                         seed=0))


def _run_path(path, db, sub_db):
    q = _q(db, AVG_TXT)
    if path == "single":
        return _traced(lambda: db.query(_q(db, UNBOUNDED_TXT)))
    if path == "batched":
        return _traced(lambda: db.query_batch([q, _q(db, UNBOUNDED_TXT)]))
    if path == "subsampled":
        return _traced(lambda: (
            sub_db.query(_q(sub_db, UNBOUNDED_TXT)),
            sub_db.query_batch([_q(sub_db, UNBOUNDED_TXT)] * 2)))
    if path == "quantile":
        return _traced(lambda: db.query(_q(db, QUANTILE_TXT)))
    if path == "exact":
        return _traced(lambda: db.exact_query(_q(db, QUANTILE_TXT)))
    assert path == "sharded"
    with _kill_one_replica():
        return _traced(lambda: db.query(q))


@pytest.mark.parametrize("path", ["single", "batched", "subsampled",
                                  "quantile", "exact", "sharded"])
def test_every_scan_path_splits_launch_and_wait(path, db, sub_db):
    tr = _run_path(path, db, sub_db)
    _assert_scans_split(tr)
    if path == "quantile":
        assert any(s.attrs.get("quantile") for s in tr.find("scan"))
    if path == "exact":
        assert tr.find("scan.exact")
    if path == "sharded":
        attempts = tr.find("scan.shard")
        assert len(attempts) == N_SHARDS + 1
        failed = [s for s in attempts if s.attrs.get("ok") is False]
        assert len(failed) == 1 and tr.children(failed[0].index) == []
    for s in tr.find("scan") + tr.find("scan.exact"):
        # Tracing's own attributes are set after the timed interval; the
        # second clock and the unread byte count are gone.
        assert "elapsed_s" not in s.attrs and "bytes_per_row" not in s.attrs
        assert s.attrs["rows_read"] > 0


@pytest.mark.parametrize("path", ["single", "batched", "subsampled",
                                  "quantile", "exact", "sharded"])
def test_scan_split_names_the_family_strata(path, db, sub_db):
    """`scan.launch` and `scan.wait` carry the scanned family's stratum
    count (0 on the base table), and `engine_scanned_rows_total{strata}`
    splits every row read into wide and narrow."""
    engine = sub_db if path == "subsampled" else db
    tr = _run_path(path, db, sub_db)
    counts = ({0} if path == "exact" else
              {f.n_distinct for f in engine.families["sessions"].values()})
    split = [s for s in tr.spans if s.name in ("scan.launch", "scan.wait")]
    assert split
    for s in split:
        assert s.attrs["strata"] in counts, (s.name, s.attrs)
    rows = engine.metrics.counter("engine_scanned_rows_total",
                                  labels=("strata",))
    assert rows.value("wide") == 0   # (City) has 200 strata
    assert rows.value("narrow") == \
        engine.metrics.counter("engine_rows_read_total").value() > 0


@pytest.mark.parametrize("path", ["single", "batched", "subsampled",
                                  "quantile", "exact", "sharded"])
def test_scan_launch_carries_the_rows_its_grid_visits(path, db, sub_db):
    """Every `scan.launch` carries `rows_visited`, and
    `engine_rows_visited_total` rises by their sum. The jnp path visits the
    family's whole striped block; the exact program the table's rows."""
    engine = sub_db if path == "subsampled" else db
    visited = engine.metrics.counter("engine_rows_visited_total")
    before = visited.value()
    tr = _run_path(path, db, sub_db)
    launches = tr.find("scan.launch")
    assert launches
    blocks = ({engine.tables["sessions"].n_rows} if path == "exact" else
              {engine._striped_for("sessions", phi).capacity
               for phi in engine.families["sessions"]})
    for s in launches:
        assert s.attrs["rows_visited"] in blocks, s.attrs
    assert visited.value() - before == \
        sum(s.attrs["rows_visited"] for s in launches)


@pytest.fixture(scope="module")
def kernel_db():
    tbl = table_lib.from_columns("sessions",
                                 synth.sessions_table(60_000, seed=2))
    d = BlinkDB(EngineConfig(k1=400.0, m=3, seed=1, use_pallas=True))
    d.register_table("sessions", tbl)
    d.add_family("sessions", ())
    return d


def test_kernel_scans_visit_the_prefix_of_their_k(kernel_db):
    """On the fused kernel a pilot at the family's smallest K visits the
    row blocks of its key prefix, fewer than the family's rows; a scan at
    the top K visits the whole family, rounded up to a row block."""
    from repro.kernels.agg_scan import DEFAULT_BLOCK_ROWS_BATCHED as block
    fam = kernel_db.families["sessions"][()]
    visited = kernel_db.metrics.counter("engine_rows_visited_total")
    before = visited.value()
    pilot = _traced(lambda: kernel_db.query(_q(
        kernel_db, "SELECT AVG(SessionTime) FROM sessions "
        "ERROR WITHIN 10% CONFIDENCE 95%")))
    top = _traced(lambda: kernel_db.query(_q(
        kernel_db, "SELECT AVG(SessionTime) FROM sessions")))
    probes = [s for s in pilot.find("scan") if s.attrs["k"] == min(fam.ks)]
    assert probes
    for probe in probes:
        (launch,) = [s for s in pilot.find("scan.launch")
                     if s.parent == probe.index]
        assert probe.attrs["rows_read"] <= launch.attrs["rows_visited"] \
            < fam.n_rows
    (full,) = top.find("scan.launch")
    assert full.attrs["rows_visited"] == -(-fam.n_rows // block) * block
    assert visited.value() - before == sum(
        s.attrs["rows_visited"]
        for tr in (pilot, top) for s in tr.find("scan.launch"))


def test_new_spans_keep_the_stage_readings(db):
    """The rule for new spans: a span of a stage Answer.timings and the
    benchmark's readers bill (plan, scan, estimate) either existed before
    the scan split or nests under a span of its own stage; every other new
    span takes a stage of its own. So each stage's outermost spans, and the
    union the front-end reading subtracts, are what they were."""
    svc = BlinkQLService(db, config=ServiceConfig(use_cache=False,
                                                  solo_bypass=False,
                                                  trace_sample_every=1))
    texts = [AVG_TXT, UNBOUNDED_TXT, QUANTILE_TXT,
             "SELECT COUNT(*) FROM sessions WHERE City = 'city001' "
             "WITHIN 2 SECONDS",
             "SELECT SUM(SessionTime) FROM sessions WHERE OS = 'os1' "
             "ERROR WITHIN 3% CONFIDENCE 95%",
             "SELECT AVG(SessionTime) FROM sessions WHERE City = 'city004' "
             "ERROR WITHIN 0.0001% CONFIDENCE 95%"]
    try:
        answers = svc.submit_many(texts)
        with _kill_one_replica():
            answers.append(svc.submit(AVG_TXT))
    finally:
        svc.close()
    seen = set()
    for ans in answers:
        tr = ans.trace
        assert tr is not None
        seen |= set(tr.span_names())
        for s in tr.spans:
            assert s.name in TAXONOMY, s.name
            stage = s.name.split(".", 1)[0]
            if stage in STAGES_READ and s.name not in PRE_SPLIT:
                assert any(a.name.split(".", 1)[0] == stage
                           for a in _ancestors(tr, s)), s.name
        billed = [s for s in tr.spans
                  if s.name.split(".", 1)[0] in STAGES_READ]
        assert _union(billed) == _union(
            [s for s in billed if s.name in PRE_SPLIT])
    assert {"scan.launch", "scan.wait", "fetch", "admit.queue"} <= seen


def _union(spans):
    out = []
    for t0, t1 in sorted((s.t0, s.t1) for s in spans):
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return out


def test_admit_queue_nests_inside_admit(db):
    svc = BlinkQLService(db, config=ServiceConfig(use_cache=False,
                                                  solo_bypass=False))
    try:
        tr = svc.submit(AVG_TXT).trace
    finally:
        svc.close()
    (admit,) = tr.find("admit")
    (queued,) = tr.find("admit.queue")
    assert queued.parent == admit.index
    # From submission until the dispatcher popped it; the rest of admit is
    # the batching window's hold.
    assert admit.t0 == queued.t0 <= queued.t1 <= admit.t1


def test_compile_spans_and_counter_once_per_program():
    tbl = table_lib.from_columns("sessions",
                                 synth.sessions_table(8_000, seed=4))
    d = BlinkDB(EngineConfig(k1=200.0, m=2, seed=3))
    d.register_table("sessions", tbl)
    d.add_family("sessions", ("City",))
    compiles = d.metrics.counter("engine_program_compiles_total",
                                 labels=("program",))

    def run():
        d.query(_q(d, UNBOUNDED_TXT))
        d.query(_q(d, QUANTILE_TXT))
        d.query_batch([_q(d, UNBOUNDED_TXT)] * 2)
        d.exact_query(_q(d, UNBOUNDED_TXT))

    first = _traced(run)
    kinds = [s.attrs["program"] for s in first.find("compile")]
    assert set(kinds) == {"query", "quantile", "batched", "exact"}
    for kind in set(kinds):
        assert compiles.value(kind) == kinds.count(kind)
    again = _traced(run)                 # every program is reused
    assert again.find("compile") == []
    for kind in set(kinds):
        assert compiles.value(kind) == kinds.count(kind)
    assert "engine_program_compiles_total" in d.metrics.snapshot()["counters"]


def test_live_spans_mirror_into_the_profiler_trace(db, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    q = _q(db, UNBOUNDED_TXT)
    db.query(q)                          # compile before the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tr = QueryTrace("q", reason="forced")
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with activate(tr):
            db.query(q)
    tr.finish()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    events: dict[str, list[float]] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("blinkql."):
                    events.setdefault(ev.name, []).append(ev.start_ns)
    offsets = []
    for name in ("scan.launch", "scan.wait"):
        spans = sorted(tr.find(name), key=lambda s: s.t0)
        starts = sorted(events.get(f"blinkql.{name}", []))
        assert len(starts) == len(spans) >= 1
        offsets += [e - s.t0 * 1e9 for e, s in zip(starts, spans)]
    # One clock offset maps every span onto its host event.
    assert max(offsets) - min(offsets) < 1e6


def test_span_without_trace_never_touches_the_profiler(monkeypatch):
    from repro.obs import trace as obs_trace

    def no_profiler():
        raise AssertionError("the profiler was reached with no trace")
    monkeypatch.setattr(obs_trace, "_trace_annotation", no_profiler)
    assert active_traces() == ()
    sp = span("scan.wait")
    assert sp is obs_trace._NULL
    with sp:
        pass
