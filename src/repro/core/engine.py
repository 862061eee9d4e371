"""BlinkDB engine facade.

    db = BlinkDB()
    db.register_table("sessions", table)
    db.build_samples("sessions", templates, storage_budget_fraction=0.5)
    ans = db.query(Query(..., bound=ErrorBound(0.1, 0.95)))

Wires together: offline sample creation driven by the §3.2 optimizer, runtime
family selection (§4.1), ELP resolution selection (§4.2), the fused
distributed scan (executor), HT estimation with Table-2 error bars (§4.3),
and background maintenance (§4.5).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import elp as elp_lib
from repro.core import estimators as est_lib
from repro.core import executor as exec_lib
from repro.core import optimizer as opt_lib
from repro.core import sampling as samp_lib
from repro.core import table as table_lib
from repro.core.types import (AggOp, Answer, BoundUnreachableError,
                              ColumnKind, ErrorBound, GroupResult, Query,
                              QueryTemplate, TimeBound)
from repro.core.selection import rewrite_disjuncts, select_family
from repro.fault import inject
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.sharding import placement as place_lib


# Families of more strata than this count as wide in
# engine_scanned_rows_total; the benchmark's wide-strata reader draws the
# same line on the spans' `strata`.
WIDE_STRATA = 4096


@dataclasses.dataclass
class EngineConfig:
    k1: float = 100_000.0        # largest stratification cap (paper §6.1: 1e5)
    c: float = 2.0               # resolution shrink factor
    m: int | None = None         # resolutions per family (None: log_c K1)
    uniform_fraction: float = 0.5
    max_strat_cols: int = 3      # §6.3: optimizer capped at 3 columns
    probe_resolutions: int = 2
    use_pallas: bool = False     # fused Pallas scan vs pure-jnp reference
    reuse_elp: bool = True       # cache ELP decisions per template (§4.4)
    seed: int = 0
    # A-priori ERROR WITHIN contracts (docs/SERVICE.md): the pilot scan
    # either certifies a K on the selected family, escalates to larger
    # families, falls back to an exact base-table scan, or annotates the
    # answer bound_met=False. Disabling the ladder rungs narrows what the
    # engine may do for an unreachable bound — never back to silence.
    escalate_on_unreachable: bool = True
    exact_fallback: bool = True
    # CI machinery: "closed" = Table-2 / HT closed forms (default, bit-
    # identical to the pre-contract engine); "subsampling" = VerdictDB-style
    # variational subsampling (same point estimates via folded moments,
    # stderr from the replicate spread). Fault-sharded scans always use the
    # closed form (per-shard partials can't carry subsample segments).
    ci_method: str = "closed"
    n_subsamples: int = 32
    # Fault-domain sharding (docs/FAULTS.md). Engages ONLY under an armed
    # non-empty FaultPlan: scans split into n_logical_shards disjoint
    # stratum partitions with shard_replicas attempts each, so a lost shard
    # degrades the answer (HT reweight, wider CIs) instead of failing it.
    # Without an armed plan the fused single-pass path runs unchanged —
    # bit-identical answers, zero overhead.
    n_logical_shards: int = 4
    shard_replicas: int = 2
    straggler_deadline_s: float | None = None   # per-attempt deadline
    # Fleet placement (sharding/placement.py): logical shards get HOME
    # processes round-robin over n_processes simulated processes; replica
    # attempt r of shard s executes on process (s + r) % n_processes, so a
    # process-kill fault fails over to replicas homed elsewhere. Families
    # the workload monitor marks HOT (mark_hot_family) run hot_replicas-long
    # chains. Placement is provenance + fault-domain metadata only — the
    # fault-free fused path is untouched (docs/SERVICE.md).
    n_processes: int = 2
    hot_replicas: int = 3


# Largest Q per fused scan invocation. Pallas: the Qp·B VMEM terms scale
# linearly with Q (docs/BATCHING.md budget math targets Qp=64 ≈ 8 MB of
# ~16 MB/core). Ref path: the vmapped scan materializes O(Q·n) intermediates,
# so unbounded Q risks device OOM on big prefixes. Bigger groups run as
# chunked back-to-back scans, each still 64-way amortized.
_MAX_SCAN_BATCH = 64


@dataclasses.dataclass
class AppendReport:
    """What one BlinkDB.append_rows ingested and what it invalidated."""
    delta: table_lib.TableDelta
    # family -> (LIVE stratum freqs before, after) with STABLE stratum ids —
    # aligned arrays, so maintenance can compute drift on the delta directly.
    freqs: dict[tuple[str, ...], tuple[np.ndarray, np.ndarray]]
    restriped: list[tuple[str, ...]]   # families whose block outgrew padding
    epoch: int                         # 1-based append epoch for this table

    @property
    def merged(self) -> list[tuple[str, ...]]:
        """Families merged in place — every family gets a freqs entry."""
        return list(self.freqs)


@dataclasses.dataclass
class MutationReport:
    """What one BlinkDB.delete_rows / update_rows changed and invalidated."""
    mutation: table_lib.TableMutation
    # family -> (LIVE stratum freqs before, after), stable stratum ids
    freqs: dict[tuple[str, ...], tuple[np.ndarray, np.ndarray]] = \
        dataclasses.field(default_factory=dict)
    # family -> dead rows that were in the sample (now striped-block ghosts)
    tombstoned_sampled: dict[tuple[str, ...], int] = \
        dataclasses.field(default_factory=dict)
    restriped: list[tuple[str, ...]] = dataclasses.field(default_factory=list)
    # append epoch consumed by an update's re-insert delta (None: pure delete
    # or nothing matched — no delta units were drawn)
    epoch: int | None = None


@dataclasses.dataclass(frozen=True)
class ElpDecision:
    """One resolved a-priori contract decision, cached per ELP key (§4.4).

    Replaces the old bare-K cache value: an unreachable bound may resolve to
    a DIFFERENT family than the query's §4.1 selection (escalation) or to an
    exact base-table scan, and replaying the cached decision must reproduce
    that, not just a K. `gen` pins the decided family's content generation —
    a rebuilt/merged family retires the decision even when the cache key's
    own family survived."""
    phi: tuple[str, ...]
    k: float
    certified: bool | None        # None: query had no ErrorBound
    exact: bool = False           # exact base-table fallback
    predicted_half_width: float | None = None   # bound units; 0.0 for exact
    gen: int = 0


@dataclasses.dataclass
class _BatchJob:
    """One conjunctive subquery's slot in a batched execution plan."""
    parent: int                   # index of the originating query
    order: int                    # disjunct order within the parent
    q: Query
    table: str
    phi: tuple[str, ...]
    struct: tuple                 # predicate template (pred_structure)
    consts: tuple[float, ...]     # predicate constants, flat_atoms order
    elp_key: tuple
    scan_key: tuple               # (table, phi, struct, value, group, G)
    confidence: float
    k: float | None = None        # resolved resolution cap
    certified: bool | None = None  # a-priori contract provenance
    predicted_half: float | None = None


class BlinkDB:
    def __init__(self, config: EngineConfig | None = None, mesh=None,
                 data_axes: tuple[str, ...] = ("data",),
                 metrics: "obs_metrics.MetricsRegistry | None" = None):
        self.config = config or EngineConfig()
        self.mesh = mesh
        self.data_axes = data_axes
        # Observability plane (docs/OBSERVABILITY.md): engine-scoped
        # registry — everything hanging off this engine (service scheduler,
        # cache, workload monitor, maintainer) registers here, so two
        # engines in one process never bleed counters into each other.
        self.metrics = (metrics if metrics is not None
                        else obs_metrics.MetricsRegistry())
        self._m_queries = self.metrics.counter(
            "engine_queries_total", "Queries executed, by execution path",
            labels=("path",))
        self._m_rows_read = self.metrics.counter(
            "engine_rows_read_total", "Sample/base rows scanned on device")
        self._m_rows_visited = self.metrics.counter(
            "engine_rows_visited_total",
            "Rows the scan programs' grids visited on device (whole kernel "
            "row blocks; the whole block on the jnp path)")
        self._m_scanned_rows = self.metrics.counter(
            "engine_scanned_rows_total",
            "Rows scanned on device, by the family's stratum count: wide "
            f"(more than {WIDE_STRATA} strata) or narrow", labels=("strata",))
        self._m_escalations = self.metrics.counter(
            "engine_k_escalations_total",
            "ErrorBound plans escalated past the selected family (§4.2)")
        self._m_exact_fallbacks = self.metrics.counter(
            "engine_exact_fallbacks_total",
            "ErrorBound plans resolved to exact base-table scans")
        self._m_scan_seconds = self.metrics.histogram(
            "engine_scan_seconds", "Device scan wall time per fused pass")
        self._m_compiles = self.metrics.counter(
            "engine_program_compiles_total",
            "Scan programs AOT-compiled (or loaded from the persistent "
            "cache), by program kind", labels=("program",))
        self._m_shards_lost = self.metrics.counter(
            "engine_shards_lost_total",
            "Logical shards lost (no surviving replica) across scans")
        self._m_shard_reroutes = self.metrics.counter(
            "engine_shard_reroutes_total",
            "Logical shards served by a replica > 0")
        self._m_shard_scans = self.metrics.counter(
            "engine_shard_scans_total",
            "Sharded-path scans by logical shard (per-shard serving load)",
            labels=("shard",))
        self._m_hot_promotions = self.metrics.counter(
            "engine_hot_promotions_total",
            "Families promoted to hot replication by the workload monitor")
        # Shard placement over the simulated process fleet (ISSUE-10):
        # lazily built per (table, family, n_logical), widened on hot marks.
        self.placements = place_lib.PlacementMap(place_lib.PlacementConfig(
            n_processes=self.config.n_processes,
            n_replicas=self.config.shard_replicas,
            hot_replicas=self.config.hot_replicas))
        self.metrics.gauge(
            "engine_hot_families", "Families under hot replication"
        ).labels().set_function(
            lambda: float(len(self.placements.hot_families())))
        self.tables: dict[str, table_lib.Table] = {}
        # table -> {phi: SampleFamily}; striped views cached alongside
        self.families: dict[str, dict[tuple[str, ...], samp_lib.SampleFamily]] = {}
        self._striped: dict[tuple[str, tuple[str, ...]], exec_lib.StripedFamily] = {}
        self._latency: dict[tuple[str, tuple[str, ...]], elp_lib.LatencyModel] = {}
        self._programs: dict = {}     # (table, phi, template) -> compiled fn
        self._batched_programs: dict = {}   # (scan key, Q_padded) -> compiled fn
        self._quantile_programs: dict = {}  # (table, phi, template) -> compiled fn
        # (table, phi, value_col) -> (lo, hi) histogram range for the fused
        # one-pass quantile kernel; invalidated with the family's programs.
        self._quantile_ranges: dict = {}
        self._exact_programs: dict = {}
        # Variational-subsampling CI programs + per-block subsample codes
        # (ci_method="subsampling"); keyed/invalidated like their plain
        # counterparts.
        self._subsampled_programs: dict = {}
        self._batched_subsampled_programs: dict = {}
        self._subsampled_quantile_programs: dict = {}
        self._subsample_codes: dict = {}    # (table, phi) -> i32[S, n_local]
        # (table, phi, struct, agg, value_col, group_by, repr(bound)) ->
        # ElpDecision (§4.4; invalidation matches positionally on the
        # (table, phi) prefix; TimeBound queries are NOT cached here — their
        # reuse unit is the LatencyModel in self._latency, re-projected per
        # effective budget so scheduler headroom can't alias a direct call)
        self._elp_cache: dict = {}
        self._fk_maps: dict = {}      # (fact, dim, fk) -> np fk->row map
        self._append_epochs: dict[str, int] = {}  # table -> appends so far
        self._decay_epochs: dict[str, int] = {}   # table -> decay passes
        # Sample-generation counters (service answer-cache validity,
        # docs/SERVICE.md): one per (table, family), bumped whenever the
        # family's CONTENT changes — merge, tombstone, rebuild, compaction,
        # join-gather refresh — i.e. exactly where the invalidation matrix
        # (docs/MAINTENANCE.md) retires derived state. A per-table FAMILY-SET
        # generation additionally bumps when families are added/dropped, so a
        # cached answer can also detect that §4.1 selection would now pick a
        # different family.
        self._generations: dict[tuple[str, tuple[str, ...]], int] = {}
        self._family_set_gen: dict[str, int] = {}
        # Hooks fired on every generation bump with (table, phi) — the
        # service answer cache subscribes for eager eviction.
        self._invalidation_listeners: list[Callable[[str, tuple[str, ...]], None]] = []
        self.last_solution: opt_lib.Solution | None = None

    # ------------------------------------------------ generations & hooks
    def family_generation(self, table_name: str, phi: tuple[str, ...]) -> int:
        """Monotone content version of one sample family (0 = never built)."""
        return self._generations.get((table_name, phi), 0)

    def family_set_generation(self, table_name: str) -> int:
        """Monotone version of the SET of families on a table — bumps when a
        family is added or dropped (a cached answer's §4.1 selection could
        change even if its own family's rows didn't)."""
        return self._family_set_gen.get(table_name, 0)

    def add_invalidation_listener(
            self, fn: Callable[[str, tuple[str, ...]], None]) -> None:
        """Subscribe to generation bumps. `fn(table, phi)` fires synchronously
        on every family-content change; `fn(table, None)` on family-set
        changes. Listeners must not call back into the engine."""
        self._invalidation_listeners.append(fn)

    def remove_invalidation_listener(
            self, fn: Callable[[str, tuple[str, ...]], None]) -> None:
        """Unsubscribe (no-op if not registered) — a closed service must not
        leave its cache hooked on a long-lived engine."""
        try:
            self._invalidation_listeners.remove(fn)
        except ValueError:
            pass

    def _bump_generation(self, table_name: str,
                         phi: tuple[str, ...] | None) -> None:
        if phi is None:
            self._family_set_gen[table_name] = \
                self._family_set_gen.get(table_name, 0) + 1
        else:
            key = (table_name, phi)
            self._generations[key] = self._generations.get(key, 0) + 1
        for fn in self._invalidation_listeners:
            fn(table_name, phi)

    # ------------------------------------------------------------- offline
    def register_table(self, name: str, tbl: table_lib.Table) -> None:
        if name in self.tables and self.tables[name] is not tbl:
            # Re-registration (e.g. maintenance ingesting new data): every
            # cache derived from the old table's columns is stale.
            self._invalidate_table(name)
        self.tables[name] = tbl
        self.families.setdefault(name, {})

    def _invalidate_table(self, name: str) -> None:
        for cache in (self._striped, self._latency, self._programs,
                      self._batched_programs, self._quantile_programs,
                      self._quantile_ranges, self._exact_programs,
                      self._subsampled_programs,
                      self._batched_subsampled_programs,
                      self._subsampled_quantile_programs,
                      self._subsample_codes,
                      self._elp_cache):
            for k in [k for k in cache if k[0] == name]:
                del cache[k]
        for k in [k for k in self._fk_maps if name in k[:2]]:
            del self._fk_maps[k]
        for phi in self.families.get(name, {}):
            self._bump_generation(name, phi)
        self._bump_generation(name, None)
        self._invalidate_as_dimension(name)

    def _invalidate_as_dimension(self, name: str) -> None:
        """If `name` serves as a dimension, fact tables and their families
        hold gathered "name.col" columns whose codes reference the OLD
        dictionary — strip them so _resolve_joins regathers on next use."""
        prefix = name + "."
        for fact_name, fact in self.tables.items():
            stale_cols = [c for c in fact.columns if c.startswith(prefix)]
            for c in stale_cols:
                del fact.columns[c]
            if stale_cols:
                for k in [k for k in self._exact_programs
                          if k[0] == fact_name]:
                    del self._exact_programs[k]
            for p, fam in self.families.get(fact_name, {}).items():
                fam_stale = [c for c in fam.columns if c.startswith(prefix)]
                for c in fam_stale:
                    del fam.columns[c]
                if fam_stale:
                    self._striped.pop((fact_name, p), None)
                    self._drop_programs(fact_name, p)
                    # The dimension's data changed under this fact family's
                    # gathered join columns — answers computed through them
                    # are stale (service cache rides this bump).
                    self._bump_generation(fact_name, p)

    def candidate_stats(self, table_name: str) -> Callable[[frozenset[str]], tuple[float, float, float]]:
        """stats(phi) -> (Store(φ), |D(φ)|, Δ(φ)) from table statistics."""
        tbl = self.tables[table_name]
        k1 = self.config.k1

        def stats(phi: frozenset[str]):
            codes, _ = table_lib.combined_codes(tbl, sorted(phi))
            nd = int(codes.max()) + 1 if len(codes) else 0
            # Tombstoned rows are storage the sample will never hold —
            # statistics run over the LIVE histogram, and strata whose rows
            # are ALL dead can never match a live row: they must not inflate
            # |D(φ)| or the §3.2.1 tail-length metric Δ(φ).
            if tbl.live is not None:
                codes = codes[tbl.live]
            freqs = table_lib.stratum_frequencies(codes, nd)
            storage = samp_lib.expected_sample_rows(freqs, k1) * (tbl.row_bytes() + 8)
            nd_live = float(((freqs > 0).sum()) if tbl.live is not None
                            else nd)
            delta = float(((freqs > 0) & (freqs < k1)).sum())
            return storage, nd_live, delta
        return stats

    def build_samples(self, table_name: str, templates: Sequence[QueryTemplate],
                      storage_budget_fraction: float = 0.5,
                      change_fraction: float = 1.0,
                      exact: bool = False,
                      seed: int | None = None) -> opt_lib.Solution:
        """Offline sample creation (§2.2.1): solve §3.2, build chosen families
        plus the always-present uniform family. `seed` overrides the config
        seed for this build only — maintenance threads a fresh per-epoch seed
        through here instead of mutating the shared EngineConfig."""
        seed = self.config.seed if seed is None else seed
        tbl = self.tables[table_name]
        stats = self.candidate_stats(table_name)
        cands = opt_lib.enumerate_candidates(templates, stats,
                                             self.config.max_strat_cols)
        deltas, distincts = [], []
        for t in templates:
            _, nd, dl = stats(t.columns)
            deltas.append(dl)
            distincts.append(nd)
        wl = opt_lib.Workload(tuple(templates), tuple(deltas), tuple(distincts))
        # Budget against LIVE bytes: tombstoned rows are storage the samples
        # will never hold (identical to nbytes for append-only tables).
        budget = storage_budget_fraction * tbl.row_bytes() * tbl.n_live
        existing = frozenset(frozenset(p) for p in self.families[table_name] if p)
        solver = opt_lib.solve_exact if exact else opt_lib.solve_greedy
        sol = solver(cands, wl, budget, existing=existing,
                     change_fraction=change_fraction)
        self.last_solution = sol

        wanted = {tuple(sorted(c.phi)) for c in sol.chosen}
        current = {p for p in self.families[table_name] if p}
        for phi in current - wanted:       # discard (Eq. 5 accounting done in solver)
            del self.families[table_name][phi]
            self._striped.pop((table_name, phi), None)
            self._drop_programs(table_name, phi)
            self._bump_generation(table_name, phi)
        for phi in sorted(wanted - current):
            fam = samp_lib.build_family(tbl, phi, self.config.k1, self.config.c,
                                        self.config.m, seed=seed)
            self.families[table_name][phi] = fam
            self._bump_generation(table_name, phi)
        set_changed = bool((current - wanted) or (wanted - current))
        if () not in self.families[table_name]:
            self.families[table_name][()] = samp_lib.build_uniform_family(
                tbl, self.config.uniform_fraction, self.config.c,
                self.config.m, seed=seed)
            self._bump_generation(table_name, ())
            set_changed = True
        if set_changed:
            self._bump_generation(table_name, None)
        return sol

    def add_family(self, table_name: str, phi: Sequence[str],
                   seed: int | None = None) -> None:
        """Manually add (or force-rebuild) a family. `seed` overrides the
        config seed for this build (per-epoch maintenance resamples)."""
        seed = self.config.seed if seed is None else seed
        tbl = self.tables[table_name]
        phi_t = tuple(sorted(phi))
        if phi_t == ():
            fam = samp_lib.build_uniform_family(
                tbl, self.config.uniform_fraction, self.config.c,
                self.config.m, seed=seed)
        else:
            fam = samp_lib.build_family(tbl, phi_t, self.config.k1,
                                        self.config.c, self.config.m,
                                        seed=seed)
        is_new = phi_t not in self.families.setdefault(table_name, {})
        self.families[table_name][phi_t] = fam
        # Replacing a family orphans anything compiled against its columns.
        self._striped.pop((table_name, phi_t), None)
        self._drop_programs(table_name, phi_t)
        self._bump_generation(table_name, phi_t)
        if is_new:
            self._bump_generation(table_name, None)

    def append_rows(self, table_name: str, raw: Mapping[str, np.ndarray],
                    seed: int | None = None) -> AppendReport:
        """Append-only ingestion with delta-based sample maintenance
        (§3.2.3/§4.5): encode the delta against the existing dictionaries,
        merge every materialized family in place (exact HT rates under the
        grown frequencies — see sampling.merge_family), and ship only the
        delta to the device via the incremental restripe.

        Invalidation is FINE-GRAINED (docs/MAINTENANCE.md has the matrix):
        compiled query programs take the striped block as a traced argument,
        so they stay valid unless a family outgrows its padded shape class
        (then only that family's programs drop); group-by programs whose
        dictionary grew recompile under their new cardinality key; exact-path
        programs for this table drop (the table length changed); ELP
        resolutions and latency models are kept — they are statistical
        calibrations that remain sound under an append, not correctness
        state. Nothing owned by OTHER tables is touched unless this table
        serves them as a join dimension.
        """
        tbl = self.tables[table_name]
        epoch = self._append_epochs.get(table_name, 0) + 1
        self._append_epochs[table_name] = epoch
        unit_seed = self.config.seed if seed is None else seed
        self._pre_delta_invalidation(table_name)
        delta = tbl.append(raw)
        self._post_delta_invalidation(table_name, delta)
        freqs, restriped = self._merge_delta_into_families(
            table_name, delta, epoch, unit_seed)
        return AppendReport(delta, freqs, restriped, epoch)

    def _pre_delta_invalidation(self, table_name: str) -> None:
        """Before a delta lands: gathered join attributes can't ride a
        schema-only delta — the table strips its own in Table.append; strip
        the FAMILIES' copies here (lazily regathered on next use). If this
        table serves as a dimension, the delta changes join results for its
        fact tables: refresh fk maps + gathered columns."""
        fams = self.families.get(table_name, {})
        for phi, fam in fams.items():
            gathered = [c for c in fam.columns if "." in c]
            for c in gathered:
                del fam.columns[c]
            if gathered:
                self._striped.pop((table_name, phi), None)
                self._drop_programs(table_name, phi)
        for k in [k for k in self._fk_maps if k[1] == table_name]:
            del self._fk_maps[k]
        self._invalidate_as_dimension(table_name)

    def _post_delta_invalidation(self, table_name: str,
                                 delta: table_lib.TableDelta) -> None:
        """After a delta landed (append or update re-insert):

        fk maps where THIS table is the fact are sized by the fk column's
        dictionary — stale once that dictionary grew (new fk values would
        silently clamp-join to an arbitrary dimension row). Exact-path
        programs are keyed by table length — every entry for this table is
        now unreachable; drop them (only this table's). Group-by programs
        whose dictionary grew recompile under the new cardinality; prune the
        now-unreachable old-cardinality entries."""
        for k in [k for k in self._fk_maps
                  if k[0] == table_name
                  and len(delta.new_dict_values.get(k[2], ()))]:
            del self._fk_maps[k]
        for k in [k for k in self._exact_programs if k[0] == table_name]:
            del self._exact_programs[k]
        # Appended rows may extend a value column's [min, max]; the fused
        # quantile kernel's histogram range must track it (stale ranges only
        # cost edge-bin resolution, but recomputing host min/max is cheap).
        for k in [k for k in self._quantile_ranges if k[0] == table_name]:
            del self._quantile_ranges[k]
        for col, vals in delta.new_dict_values.items():
            if not len(vals):
                continue
            for cache in (self._programs, self._batched_programs,
                          self._quantile_programs,
                          self._subsampled_programs,
                          self._batched_subsampled_programs,
                          self._subsampled_quantile_programs):
                for k in [k for k in cache
                          if k[0] == table_name and k[4] == col]:
                    del cache[k]

    def _merge_delta_into_families(self, table_name: str,
                                   delta: table_lib.TableDelta, epoch: int,
                                   unit_seed: int):
        """Merge a landed delta into every materialized family in place and
        incrementally restripe the device blocks (one delta-unit draw per
        stream, shared by every family on it)."""
        fams = self.families.get(table_name, {})
        strat_units = samp_lib.delta_units(delta.n_rows, unit_seed, epoch)
        unif_units = samp_lib.delta_units(delta.n_rows, unit_seed, epoch,
                                          uniform=True)
        freqs: dict[tuple[str, ...], tuple[np.ndarray, np.ndarray]] = {}
        restriped: list[tuple[str, ...]] = []
        for phi, fam in list(fams.items()):
            old_freqs = fam.live_freqs
            units = unif_units if phi == () else strat_units
            if phi == ():
                # Uniform family keeps K_1 = p·N as N grows — N being the
                # PHYSICAL (inclusion) count, not the live count: K/F must
                # never grow faster than F or rows re-enter the prefix and
                # the merge can't supply them (it never sees unsampled base
                # rows). Keeping K₁/N_phys constant pins every row's rate at
                # exactly p through any delete/append interleaving.
                n_phys = max(int(fam.stratum_freqs[0]), 1)
                frac = fam.ks[0] / n_phys
                merged, block = samp_lib.merge_family(
                    fam, delta.columns, units,
                    new_k1=frac * (n_phys + delta.n_rows),
                    c=self.config.c, start_row=delta.start_row)
            else:
                merged, block = samp_lib.merge_family(
                    fam, delta.columns, units, c=self.config.c,
                    start_row=delta.start_row)
            fams[phi] = merged
            freqs[phi] = (old_freqs, merged.live_freqs)
            self._bump_generation(table_name, phi)
            key = (table_name, phi)
            striped = self._striped.get(key)
            if striped is not None:
                upd = exec_lib.stripe_append(striped, merged, block)
                if upd is None:   # outgrew padding: full compacting restripe
                    self._striped[key] = self._stripe(merged)
                    self._drop_programs(table_name, phi)
                    restriped.append(phi)
                else:
                    self._striped[key] = upd
        return freqs, restriped

    def delete_rows(self, table_name: str, predicate) -> MutationReport:
        """Delete (tombstone) every live row matching `predicate`, keeping
        all sample families and compiled programs serving (docs/MAINTENANCE.md
        mutation protocol): the table marks rows dead in place; each family
        drops its sampled copies host-side and ships ONE bitmask scatter that
        ghosts their striped-block slots; per-stratum LIVE counts decrement
        while inclusion frequencies — and with them every surviving row's
        entry key and exact HT rate — stay put.

        Invalidation: compiled sampled-path programs are all KEPT (the block
        shape class is untouched by a tombstone scatter); exact-path programs
        are also kept — the live mask is a traced argument and the physical
        table length didn't change; ELP/latency calibrations are kept as with
        appends. Only join state is refreshed when this table serves as a
        dimension (fact rows must not keep serving values gathered from rows
        that no longer exist)."""
        tbl = self.tables[table_name]
        mutation = tbl.delete(predicate)
        report = MutationReport(mutation)
        if mutation.n_tombstoned == 0:
            return report
        self._apply_tombstones_to_families(table_name, mutation, report)
        for k in [k for k in self._fk_maps if k[1] == table_name]:
            del self._fk_maps[k]
        self._invalidate_as_dimension(table_name)
        return report

    def update_rows(self, table_name: str, predicate, assignments,
                    seed: int | None = None) -> MutationReport:
        """Update matching live rows: tombstone the old versions and ingest
        the re-encoded new versions as an ordinary append delta (LSM-style),
        so the re-inserts ride the whole incremental merge/restripe pipeline
        — including the append invalidation matrix (new dictionary values,
        exact-program retirement by table length, fk-map refreshes)."""
        tbl = self.tables[table_name]
        unit_seed = self.config.seed if seed is None else seed
        mutation = tbl.update(predicate, assignments)
        report = MutationReport(mutation)
        if mutation.n_tombstoned == 0:
            return report   # nothing matched: invalidate nothing
        # (After the table mutation is fine: the family-side strips are only
        # consumed by the merge below, and the cache drops are order-free.)
        self._pre_delta_invalidation(table_name)
        self._apply_tombstones_to_families(table_name, mutation, report)
        epoch = self._append_epochs.get(table_name, 0) + 1
        self._append_epochs[table_name] = epoch
        report.epoch = epoch
        self._post_delta_invalidation(table_name, mutation.delta)
        freqs, restriped = self._merge_delta_into_families(
            table_name, mutation.delta, epoch, unit_seed)
        report.restriped = restriped
        for phi, (_, after) in freqs.items():
            before = report.freqs.get(phi, (after, after))[0]
            report.freqs[phi] = (before, after)
        return report

    def _apply_tombstones_to_families(self, table_name: str, mutation,
                                      report: MutationReport) -> None:
        fams = self.families.get(table_name, {})
        for phi, fam in list(fams.items()):
            fam2, tblock = samp_lib.apply_tombstones(
                fam, mutation.tombstoned, mutation.tombstoned_columns)
            fams[phi] = fam2
            report.freqs[phi] = (fam.live_freqs, fam2.live_freqs)
            report.tombstoned_sampled[phi] = tblock.n_sampled
            self._bump_generation(table_name, phi)
            key = (table_name, phi)
            striped = self._striped.get(key)
            if striped is not None:
                self._striped[key] = exec_lib.stripe_tombstone(
                    striped, tblock.row_ids, table_rows=fam2.table_rows)

    # ------------------------------------------------- ghost-slot compaction
    def ghost_fractions(self, table_name: str) -> dict[tuple[str, ...], float]:
        """Per-family ghost+tombstone slot fraction of the materialized
        striped blocks (the compaction-policy trigger metric)."""
        return {phi: s.ghost_fraction
                for (t, phi), s in self._striped.items() if t == table_name}

    def compact_family(self, table_name: str, phi: tuple[str, ...]) -> bool:
        """Compacting restripe: rebuild the family's striped block from the
        (ghost-free) host family, reclaiming every self-excluded slot. The
        new block PINS the old per-shard geometry (stripe_family min_local),
        so in the common case the shape class — and every AOT-compiled
        program — survives; if the natural padding for the surviving rows
        outgrew the old geometry anyway, programs are dropped instead of
        served stale. Returns True if a block was compacted."""
        key = (table_name, phi)
        striped = self._striped.get(key)
        if striped is None:
            return False   # nothing materialized: next stripe is compact
        fam = self.families[table_name][phi]
        fresh = self._stripe(fam, min_local=striped.n_local)
        self._striped[key] = fresh
        if fresh.shape_class != striped.shape_class:
            self._drop_programs(table_name, phi)
        self._bump_generation(table_name, phi)
        return True

    # --------------------------------------------- storage reclamation epochs
    def dead_fraction(self, table_name: str) -> float:
        """Fraction of the base table's physical rows that are tombstoned —
        the base-compaction trigger metric (storage the table holds for rows
        no query can ever return)."""
        tbl = self.tables[table_name]
        return 1.0 - tbl.n_live / max(tbl.n_rows, 1)

    def compact_table(self, table_name: str
                      ) -> table_lib.TableCompaction | None:
        """Base-table compaction epoch: physically drop tombstoned rows and
        ship the old→new row-id remap to every layer keyed on physical ids
        (docs/MAINTENANCE.md reclamation protocol).

        Sample CONTENT is untouched — a compaction relabels the positions of
        live rows, it does not change which rows exist or how they were
        keyed — so families only re-key their `row_ids` host mirror and
        striped blocks their `slot_row_ids` mirror: zero device traffic, and
        every AOT-compiled sampled-path program stays valid (the block's
        arrays and shape class never move). Inclusion frequencies keep
        counting the reclaimed rows (monotonicity is what keeps HT rates
        exact); only a decay epoch ever resets them.

        Invalidation: exact-path programs for this table drop (physical
        length changed — the old-length entries are unreachable anyway);
        join state refreshes when this table serves as a dimension (fk maps
        hold the OLD row indices). Every family's generation bumps — cached
        answers stamped `rows_total = n_live` are still numerically right,
        but the conservative bump keeps the cache contract simple: content
        owners changed identity, dependents revalidate.

        Returns the TableCompaction (None when there was nothing to
        reclaim).
        """
        tbl = self.tables[table_name]
        fams = self.families.get(table_name, {})
        # Validate BEFORE the table mutates: a family that cannot be
        # remapped (legacy, no usable row_ids) must fail the epoch with the
        # engine untouched, not leave it half-compacted with stale ids.
        for phi, fam in fams.items():
            if fam.row_ids is None or (fam.row_ids < 0).any():
                raise ValueError(
                    f"family {phi!r} has no (or sentinel) row_ids — built "
                    "before mutation support; rebuild it to enable base "
                    "compaction")
        comp = tbl.compact()
        if comp is None:
            return None
        for phi, fam in list(fams.items()):
            fams[phi] = samp_lib.remap_family_row_ids(fam, comp.remap)
            self._bump_generation(table_name, phi)
            key = (table_name, phi)
            striped = self._striped.get(key)
            if striped is not None:
                self._striped[key] = exec_lib.remap_slot_row_ids(
                    striped, comp.remap)
        for k in [k for k in self._exact_programs if k[0] == table_name]:
            del self._exact_programs[k]
        for k in [k for k in self._fk_maps if k[1] == table_name]:
            del self._fk_maps[k]
        self._invalidate_as_dimension(table_name)
        return comp

    def decay_family(self, table_name: str, phi: tuple[str, ...],
                     strata, seed: int | None = None
                     ) -> samp_lib.DecayBlock | None:
        """Inclusion-frequency decay epoch for one family: reset the named
        strata's inclusion frequencies to their live counts and resample
        them from the base table (sampling.decay_strata) under fresh units
        drawn from the per-table decay stream — deterministic in
        (seed, decay epoch), so the mutation oracle can replay it.

        Invalidation rides the compaction matrix row: the family content
        changed (generation bump + program-cache hygiene via restripe), and
        the striped block is rebuilt with PINNED geometry — decay admits
        rows, so if the restored rows outgrow the old padded shape the shape
        class changes and that family's compiled programs drop instead of
        being served stale. Returns the DecayBlock (None for an empty
        stratum list).
        """
        strata = np.unique(np.asarray(strata, dtype=np.int64))
        if not strata.size:
            return None
        tbl = self.tables[table_name]
        phi = tuple(phi)
        fam = self.families[table_name][phi]
        # Gathered join attributes can't be resampled from the base table —
        # strip them (regathered lazily), as the delta path does.
        gathered = [c for c in fam.columns if "." in c]
        for c in gathered:
            del fam.columns[c]
        epoch = self._decay_epochs.get(table_name, 0) + 1
        self._decay_epochs[table_name] = epoch
        unit_seed = self.config.seed if seed is None else seed
        units = samp_lib.decay_units(tbl.n_rows, unit_seed, epoch)
        new_fam, block = samp_lib.decay_strata(fam, tbl, strata, units)
        block.epoch = epoch
        self.families[table_name][phi] = new_fam
        self._bump_generation(table_name, phi)
        key = (table_name, phi)
        striped = self._striped.get(key)
        if striped is not None:
            fresh = self._stripe(new_fam, min_local=striped.n_local)
            self._striped[key] = fresh
            if fresh.shape_class != striped.shape_class:
                self._drop_programs(table_name, phi)
        elif gathered:
            self._drop_programs(table_name, phi)
        return block

    # ------------------------------------------------------------- runtime
    def _n_shards(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.data_axes]))

    def _striped_for(self, table_name: str, phi: tuple[str, ...]) -> exec_lib.StripedFamily:
        key = (table_name, phi)
        if key not in self._striped:
            fam = self.families[table_name][phi]
            self._striped[key] = self._stripe(fam)
        return self._striped[key]

    def _stripe(self, fam: samp_lib.SampleFamily,
                min_local: int | None = None) -> exec_lib.StripedFamily:
        return exec_lib.stripe_family(fam, self._n_shards(), min_local,
                                      mesh=self.mesh, data_axes=self.data_axes)

    def _encode(self, table_name: str):
        tbl = self.tables[table_name]

        def encode(col: str, value):
            if "." in col:   # joined dimension attribute (§2.1)
                dim_name, dim_col = col.split(".", 1)
                dim = self.tables[dim_name]
                if dim.schema.column(dim_col).kind is ColumnKind.CATEGORICAL:
                    return dim.encode_value(dim_col, value)
                return float(value)
            if tbl.schema.column(col).kind is ColumnKind.CATEGORICAL:
                return tbl.encode_value(col, value)
            return float(value)
        return encode

    # ------------------------------------------------------------ joins
    def _resolve_joins(self, table_name: str, q: Query,
                       phi: tuple[str, ...] | None = None) -> None:
        """Materialize joined dimension attributes referenced by q as extra
        columns ("dim.col") on the fact table AND every affected family
        (§2.1 case ii: dim tables fit in memory; the join is a gather)."""
        from repro.core import joins as join_lib
        if not q.joins:
            return
        wanted = [c for c in (q.where_group_columns |
                              ({q.value_column} if q.value_column else set()))
                  if "." in c]
        if not wanted:
            return
        fact = self.tables[table_name]
        by_dim = {j.dim_table: j for j in q.joins}
        for col in wanted:
            dim_name, dim_col = col.split(".", 1)
            join = by_dim[dim_name]
            dim = self.tables[dim_name]
            mkey = (table_name, dim_name, join.fact_key)
            if mkey not in self._fk_maps:
                self._fk_maps[mkey] = join_lib.build_fk_map(fact, dim, join)
            fk_map = self._fk_maps[mkey]
            # fact table (exact path)
            if col not in fact.columns:
                fact.columns[col] = join_lib.gather_dim_column(
                    fk_map, dim, dim_col, fact.columns[join.fact_key])
            # every family of this table (sampled path)
            for p, fam in self.families[table_name].items():
                if col not in fam.columns:
                    fam.columns[col] = join_lib.gather_dim_column(
                        fk_map, dim, dim_col, fam.columns[join.fact_key])
                    self._striped.pop((table_name, p), None)
                    self._drop_programs(table_name, p)

    def _drop_programs(self, table_name: str, phi: tuple[str, ...]) -> None:
        """Invalidate everything calibrated against a (table, family)'s
        columns (family rebuilt, dropped, or join-widened): compiled
        programs, plus ELP resolutions and the latency model — a K chosen
        for the old sample need not meet the bound on the new one."""
        for cache in (self._programs, self._batched_programs,
                      self._quantile_programs, self._quantile_ranges,
                      self._subsampled_programs,
                      self._batched_subsampled_programs,
                      self._subsampled_quantile_programs,
                      self._subsample_codes,
                      self._elp_cache, self._latency):
            stale = [k for k in cache if k[0] == table_name and k[1] == phi]
            for k in stale:
                del cache[k]

    def _column_card(self, table_name: str, col: str) -> int:
        if "." in col:
            dim_name, dim_col = col.split(".", 1)
            return self.tables[dim_name].cardinality(dim_col)
        return self.tables[table_name].cardinality(col)

    def _decode_col_value(self, table_name: str, col: str, code: int):
        if "." in col:
            dim_name, dim_col = col.split(".", 1)
            return self.tables[dim_name].decode_value(dim_col, code)
        return self.tables[table_name].decode_value(col, code)

    def _fault_sharding_active(self) -> bool:
        """Engagement rule for the sharded scan path: an armed, NON-EMPTY
        FaultPlan and more than one configured logical shard. Kept off
        otherwise so the fused single pass — and its bit-exact float
        summation order — serves every fault-free query (docs/FAULTS.md)."""
        plan = inject.active()
        return (plan is not None and bool(plan)
                and self.config.n_logical_shards > 1)

    # ------------------------------------------- fleet placement (ISSUE-10)
    def _placement_for(self, table_name: str, phi: tuple[str, ...]
                       ) -> "place_lib.FamilyPlacement":
        return self.placements.for_family(table_name, phi,
                                          self.config.n_logical_shards)

    def _set_placement_attrs(self, sp, table_name: str,
                             phi: tuple[str, ...], fam, struct, consts_list,
                             flat: bool = False) -> None:
        """Scan-span shard-placement provenance (docs/OBSERVABILITY.md):
        the family's placement over the process fleet plus the routed shard
        subset when the batch's template pins every φ column by equality
        (placement.route_shard_set — provenance only, the executor always
        scans the full set so clean answers stay bit-identical)."""
        pl = self._placement_for(table_name, phi)
        consts = (list(consts_list) if flat
                  else [exec_lib.flatten_pred_vals(v) for v in consts_list])
        route = place_lib.route_shard_set(
            fam.strata_keys, phi, struct, consts,
            self.config.n_logical_shards)
        sp.set(placement=pl.span_attrs(),
               shard_set=("all" if route is None else list(route)))

    def _count_rows(self, rows: int, strata: int) -> None:
        """Rows one scan read, in total and by its family's width."""
        self._m_rows_read.inc(rows)
        self._m_scanned_rows.labels(
            "wide" if strata > WIDE_STRATA else "narrow").inc(rows)

    def _count_shard_report(
            self, report: "exec_lib.ShardScanReport | None") -> None:
        if report is None:
            return
        self._m_shards_lost.inc(len(report.lost))
        self._m_shard_reroutes.inc(len(report.rerouted))
        for s in range(report.n_shards):
            if s not in report.lost:
                self._m_shard_scans.labels(str(s)).inc()

    def mark_hot_family(self, table_name: str, phi: tuple[str, ...]
                        ) -> bool:
        """Promote one family to hot replication: its shard placement is
        rebuilt with the longer `hot_replicas` chain, widening fail-over
        (replicas are re-executions, so this changes fault-path behavior
        only — never which strata a shard owns, never a clean answer).
        Driven by the service WorkloadMonitor's hot-family signal; True on
        first promotion."""
        phi = tuple(phi)
        if phi not in self.families.get(table_name, {}):
            return False
        newly = self.placements.mark_hot(table_name, phi)
        if newly:
            self._m_hot_promotions.inc()
        return newly

    def storage_stats(self, table_name: str) -> dict:
        """Host-side storage accounting for the fleet maintainer (§3.2
        budget arithmetic, docs/MAINTENANCE.md): live base bytes, dead base
        bytes still held by tombstoned rows, sample bytes, and the ghost
        sample bytes dead slots keep occupying in striped blocks."""
        tbl = self.tables[table_name]
        rb = tbl.row_bytes()
        sample_rb = rb + 8
        sample_rows = sum(f.n_rows
                          for f in self.families.get(table_name, {}).values())
        ghost_rows = sum(s.n_ghosts for (t, _), s in self._striped.items()
                         if t == table_name)
        return {"live_bytes": rb * tbl.n_live,
                "dead_base_bytes": rb * (tbl.n_rows - tbl.n_live),
                "sample_bytes": sample_rb * sample_rows,
                "ghost_sample_bytes": sample_rb * ghost_rows,
                "dead_bytes": rb * (tbl.n_rows - tbl.n_live)
                + sample_rb * ghost_rows}

    def _launch_and_wait(self, fn, *args, strata: int, rows_visited: int):
        """Run a compiled scan program inside the caller's scan span: the
        call (argument handling, allocation, enqueue) as `scan.launch`, the
        wait for every output as `scan.wait`, each with the scanned family's
        stratum count as `strata`; `scan.launch` also carries the rows the
        program's grid visits, counted in engine_rows_visited_total."""
        self._m_rows_visited.inc(rows_visited)
        with obs_trace.span("scan.launch", strata=strata,
                            rows_visited=rows_visited):
            out = fn(*args)
        with obs_trace.span("scan.wait", strata=strata):
            return jax.tree.map(lambda x: x.block_until_ready(), out)

    def _row_grid(self, striped: exec_lib.StripedFamily, struct, k: float,
                  quantile: bool = False) -> tuple[np.ndarray, int]:
        """The kernel's row spans for a scan at cap `k` (the batch's
        largest) and the rows its grid then visits."""
        spans = exec_lib.row_spans(striped, k)
        kernel = exec_lib.on_kernel(struct, self.config.use_pallas,
                                    quantile=quantile, mesh=self.mesh)
        return spans, exec_lib.rows_visited(striped, spans, kernel)

    def _compile(self, program: str, jfn, *args):
        """AOT-compile `jfn` for `args` (no execution) in a `compile` span,
        counted in `engine_program_compiles_total{program}`."""
        with obs_trace.span("compile", program=program):
            fn = jfn.lower(*args).compile()
        self._m_compiles.labels(program).inc()
        return fn

    def _run_at_k(self, table_name: str, q: Query, phi: tuple[str, ...],
                  k: float) -> tuple[est_lib.GroupedMoments, int, float,
                                     "exec_lib.ShardScanReport | None"]:
        """One fused scan at resolution k via a cached compiled program.
        Programs are compiled once per (family × query template) — k and
        predicate constants are traced args (§2.1 template stability).
        Under an armed fault plan the scan runs shard-partitioned
        (executor.run_sharded_scan, same compiled program per shard via the
        traced `valid` mask) and the returned report carries the loss
        provenance; otherwise the report is None."""
        fam = self.families[table_name][phi]
        striped = self._striped_for(table_name, phi)
        bound_pred = exec_lib.bind_predicate(q.predicate, self._encode(table_name))
        struct, vals = exec_lib.pred_structure(bound_pred)
        group_col = q.group_by[0] if q.group_by else None
        n_groups = self._column_card(table_name, group_col) if group_col else 1
        # The striped block is a traced ARGUMENT of the compiled program, so
        # incremental appends that keep the padded shape class reuse it; the
        # shape class in the key retires programs when a block is reallocated.
        key = (table_name, phi, struct, q.value_column, group_col, n_groups,
               striped.shape_class)
        args = exec_lib.scan_args(striped)
        spans, visited = self._row_grid(striped, struct, k)
        fn = self._programs.get(key)
        if fn is None:
            jfn = exec_lib.make_query_fn(
                struct, q.value_column, group_col, n_groups,
                mesh=self.mesh, data_axes=self.data_axes,
                use_pallas=self.config.use_pallas)
            # AOT-compile (no execution) so the cold path runs the query
            # exactly once: the timed call below both warms and answers.
            fn = self._compile("query", jfn, jnp.float32(k), vals, *args,
                               spans)
            self._programs[key] = fn
        inject.site("engine.scan", table=table_name)
        with obs_trace.span("scan", table=table_name, k=float(k)) as sp:
            t0 = time.perf_counter()
            report = None
            if self._fault_sharding_active():
                def call(mask):
                    return self._launch_and_wait(
                        fn, jnp.float32(k), vals, striped.columns,
                        striped.unit, striped.strat, striped.freq_table,
                        mask, spans, strata=fam.n_distinct,
                        rows_visited=visited)
                mom, report = exec_lib.run_sharded_scan(
                    call, striped,
                    n_logical=self.config.n_logical_shards,
                    n_replicas=self.config.shard_replicas,
                    site_ctx={"table": table_name},
                    deadline_s=self.config.straggler_deadline_s,
                    placement=self._placement_for(table_name, phi))
            else:
                mom = self._launch_and_wait(fn, jnp.float32(k), vals, *args,
                                            spans, strata=fam.n_distinct,
                                            rows_visited=visited)
            dt = time.perf_counter() - t0
            rows = fam.prefix_for_k(k)
            sp.set(rows_read=rows)
            if report is not None:
                sp.set(shards=report.n_shards, lost=list(report.lost),
                       rerouted=list(report.rerouted),
                       reweight=report.reweight)
        if obs_trace.tracing_active():
            self._set_placement_attrs(sp, table_name, phi, fam, struct,
                                      [vals])
        self._m_scan_seconds.observe(dt)
        self._count_rows(rows, fam.n_distinct)
        self._count_shard_report(report)
        return mom, rows, dt, report

    def _answer_from_moments(self, q: Query, table_name: str,
                             phi: tuple[str, ...], k: float,
                             mom: est_lib.GroupedMoments, rows_read: int,
                             elapsed: float, confidence: float,
                             faults: "exec_lib.ShardScanReport | None" = None,
                             qpair=None, certified: bool | None = None,
                             predicted_half_width: float | None = None,
                             est: est_lib.Estimate | None = None) -> Answer:
        tbl = self.tables[table_name]
        fam = self.families[table_name][phi]
        degraded = faults is not None and faults.degraded
        with obs_trace.span("estimate", agg=q.agg.name,
                            degraded=bool(degraded)):
            if est is None:
                est = self._estimate_for(q, table_name, phi, k, mom, qpair)
            stderr, lo, hi = est_lib.ci(est, confidence)
        group_col = q.group_by[0] if q.group_by else None
        with obs_trace.span("fetch"):
            vals = np.asarray(est.value)
            errs = np.asarray(stderr)
            los, his = np.asarray(lo), np.asarray(hi)
            ns = np.asarray(est.n)
            wsum = np.asarray(mom.wsum)
            nsel = np.asarray(mom.n)
        groups = []
        realized_half = 0.0   # worst realized CI half-width, bound units
        for g in range(len(vals)):
            if nsel[g] == 0 and wsum[g] == 0:
                continue  # missing subgroup (paper §3.1 "subset error")
            key = ((self._decode_col_value(table_name, group_col, g),)
                   if group_col else ())
            # A degraded answer never claims exactness: the stratum may be
            # fully sampled among SURVIVORS yet still miss lost-shard rows.
            exact = (not degraded and
                     bool(abs(nsel[g] - wsum[g]) < 1e-6 * max(wsum[g], 1.0)))
            if not exact and isinstance(q.bound, ErrorBound):
                half = est_lib.z_value(confidence) * float(errs[g])
                if q.bound.relative:
                    half = (abs(half / vals[g]) if vals[g]
                            else (0.0 if half == 0.0 else float("inf")))
                realized_half = max(realized_half, half)
            groups.append(GroupResult(key, float(vals[g]), float(errs[g]),
                                      float(los[g]), float(his[g]),
                                      float(nsel[g]), exact))
        # Contract verdict: certified a-priori AND realized post-hoc — a
        # degraded scan (HT-reweighted, wider CIs) can demote a certified
        # answer to bound_met=False, never silently keep the claim.
        bound_met = None
        if isinstance(q.bound, ErrorBound):
            bound_met = bool(certified
                             and realized_half <= q.bound.eps + 1e-12)
        return Answer(q, groups, phi, k, rows_read, tbl.n_live, elapsed,
                      confidence,
                      degraded=degraded,
                      shards_lost=len(faults.lost) if faults else 0,
                      shards_total=faults.n_shards if faults else 0,
                      bound_met=bound_met, certified=certified,
                      predicted_half_width=predicted_half_width)

    def _family_range(self, table_name: str, phi: tuple[str, ...],
                      value_col: str | None) -> tuple[float, float]:
        """Host-cached [min, max] of a family's value column — the fixed
        histogram range for the fused one-pass quantile kernel. Invalidated
        with the family's programs and on table appends; a stale range only
        costs edge-bin resolution (out-of-range values clip into the end
        bins), never histogram mass."""
        key = (table_name, phi, value_col)
        rng = self._quantile_ranges.get(key)
        if rng is None:
            fam = self.families[table_name][phi]
            if value_col is None:
                rng = (0.0, 1.0)  # COUNT-style: values are all ones
            else:
                col = np.asarray(fam.host_column(value_col), np.float32)
                rng = ((float(np.min(col)), float(np.max(col)))
                       if col.size else (0.0, 1.0))
            self._quantile_ranges[key] = rng
        return rng

    def _quantile_program(self, q: Query, table_name: str,
                          phi: tuple[str, ...], k: float) -> tuple:
        """The fused QUANTILE program and its arguments: `fn(*args)` is ONE
        streaming pass producing BOTH the grouped moments and the histogram
        quantile (value, density) — no second full-column read. The program
        is AOT-compiled and cached per (family × template × shape class); k,
        the predicate constants, the level, the histogram range, AND the
        striped block are traced args, so every re-instantiation (and every
        ELP probe) reuses one compiled program, including across incremental
        appends. Also returns the rows the program's grid visits."""
        striped = self._striped_for(table_name, phi)
        bound_pred = exec_lib.bind_predicate(q.predicate, self._encode(table_name))
        struct, vals = exec_lib.pred_structure(bound_pred)
        group_col = q.group_by[0] if q.group_by else None
        n_groups = self._column_card(table_name, group_col) if group_col else 1
        key = (table_name, phi, struct, q.value_column, group_col, n_groups,
               striped.shape_class)
        lo, hi = self._family_range(table_name, phi, q.value_column)
        spans, visited = self._row_grid(striped, struct, k, quantile=True)
        args = (jnp.float32(k), vals, jnp.float32(q.quantile),
                jnp.float32(lo), jnp.float32(hi),
                *exec_lib.scan_args(striped), spans)
        fn = self._quantile_programs.get(key)
        if fn is None:
            jfn = exec_lib.make_quantile_fn(struct, q.value_column, group_col,
                                            n_groups, mesh=self.mesh,
                                            data_axes=self.data_axes,
                                            use_pallas=self.config.use_pallas)
            fn = self._compile("quantile", jfn, *args)
            self._quantile_programs[key] = fn
        return fn, args, visited

    def _run_quantile_at_k(self, table_name: str, q: Query,
                           phi: tuple[str, ...], k: float):
        """QUANTILE analogue of _run_at_k: the fused one-pass program yields
        moments AND the histogram quantile from a single scan. Callers keep
        this off the fault-sharded path (per-shard moment partials need the
        plain scan program); timed like _run_at_k."""
        fam = self.families[table_name][phi]
        fn, args, visited = self._quantile_program(q, table_name, phi, k)
        inject.site("engine.scan", table=table_name)
        with obs_trace.span("scan", table=table_name, k=float(k),
                            quantile=True) as sp:
            t0 = time.perf_counter()
            mom, qv, dens = self._launch_and_wait(fn, *args,
                                                  strata=fam.n_distinct,
                                                  rows_visited=visited)
            dt = time.perf_counter() - t0
            rows = fam.prefix_for_k(k)
            sp.set(rows_read=rows)
        self._m_scan_seconds.observe(dt)
        self._count_rows(rows, fam.n_distinct)
        return mom, rows, dt, None, (qv, dens)

    def _scan_for_query(self, table_name: str, q: Query,
                        phi: tuple[str, ...], k: float):
        """Dispatch one scan at k, QUANTILE-aware: on the clean path a
        QUANTILE query runs the fused one-pass program (moments + histogram
        quantile, one full-column read); every other aggregate — and the
        fault-sharded path, which reduces per-shard partials — runs the plain
        scan program. Returns (mom, rows_read, dt, fault_report, qpair)."""
        if q.agg is AggOp.QUANTILE and not self._fault_sharding_active():
            return self._run_quantile_at_k(table_name, q, phi, k)
        return self._run_at_k(table_name, q, phi, k) + (None,)

    def _estimate_for(self, q: Query, table_name: str, phi: tuple[str, ...],
                      k: float, mom: est_lib.GroupedMoments,
                      qpair=None) -> est_lib.Estimate:
        """Estimate from moments; QUANTILE queries additionally need the
        histogram quantile. When the caller's scan already produced it
        (`qpair` from _scan_for_query) no extra pass runs; otherwise — shared
        batched scans and fault-sharded moments — the fused program supplies
        it (its moments are redundant there and discarded)."""
        if q.agg is not AggOp.QUANTILE:
            return est_lib.estimate(q.agg, mom)
        if qpair is None:
            fn, args, _ = self._quantile_program(q, table_name, phi, k)
            _, qv, dens = fn(*args)
            qpair = (qv, dens)
        return est_lib.estimate(AggOp.QUANTILE, mom, quantile_value=qpair[0],
                                quantile_density=qpair[1], q=q.quantile)

    def _quantile_estimate(self, q: Query, table_name: str,
                           phi: tuple[str, ...], k: float,
                           mom: est_lib.GroupedMoments) -> est_lib.Estimate:
        """Histogram-quantile estimate for moments obtained elsewhere (shared
        batched probe scans); delegates to the fused one-pass program."""
        return self._estimate_for(q, table_name, phi, k, mom)

    # ------------------------------------- variational subsampling CIs
    def _subsample_codes_for(self, table_name: str, phi: tuple[str, ...],
                             striped: exec_lib.StripedFamily) -> jax.Array:
        """Per-slot subsample ids for a family's striped block, cached per
        (table, family) and regenerated when the block's shape changes
        (restripe). A traced argument of the subsampled programs, exactly
        like the block itself."""
        key = (table_name, phi)
        sub = self._subsample_codes.get(key)
        if sub is None or sub.shape != striped.unit.shape:
            sub = jnp.asarray(exec_lib.subsample_codes(
                striped.n_shards, striped.unit.shape[1],
                self.config.n_subsamples))
            self._subsample_codes[key] = sub
        return sub

    def _subsampled_answer(self, q: Query, table_name: str,
                           phi: tuple[str, ...], k: float, confidence: float,
                           certified: bool | None = None,
                           predicted_half_width: float | None = None
                           ) -> Answer:
        """Scan at K with per-subsample segments (ci_method="subsampling"):
        point estimates come from the FOLDED moments — identical to the
        plain scan — and the CI from the spread of the B replicate
        estimates, all in one pass (docs/BATCHING.md)."""
        fam = self.families[table_name][phi]
        striped = self._striped_for(table_name, phi)
        bound_pred = exec_lib.bind_predicate(q.predicate,
                                             self._encode(table_name))
        struct, vals = exec_lib.pred_structure(bound_pred)
        group_col = q.group_by[0] if q.group_by else None
        n_groups = self._column_card(table_name, group_col) if group_col else 1
        b = self.config.n_subsamples
        sub = self._subsample_codes_for(table_name, phi, striped)
        key = (table_name, phi, struct, q.value_column, group_col, n_groups,
               striped.shape_class, b)
        args = exec_lib.scan_args(striped)
        inject.site("engine.scan", table=table_name)
        with obs_trace.span("scan", table=table_name, k=float(k),
                            subsampled=True) as sp:
            t0 = time.perf_counter()
            if q.agg is AggOp.QUANTILE:
                fn = self._subsampled_quantile_programs.get(key)
                if fn is None:
                    fn = exec_lib.make_subsampled_quantile_fn(
                        struct, q.value_column, group_col, n_groups, b,
                        mesh=self.mesh, data_axes=self.data_axes)
                    self._subsampled_quantile_programs[key] = fn
                mom_sub, qv, dens, qsub = self._launch_and_wait(
                    fn, jnp.float32(k), vals, jnp.float32(q.quantile), sub,
                    *args, strata=fam.n_distinct,
                    rows_visited=striped.capacity)
                est = est_lib.subsampling_estimate(
                    AggOp.QUANTILE, mom_sub, n_groups, b, quantile_value=qv,
                    quantile_density=dens, quantile_values_sub=qsub,
                    q=q.quantile)
            else:
                fn = self._subsampled_programs.get(key)
                if fn is None:
                    fn = exec_lib.make_subsampled_query_fn(
                        struct, q.value_column, group_col, n_groups, b,
                        mesh=self.mesh, data_axes=self.data_axes)
                    self._subsampled_programs[key] = fn
                mom_sub = self._launch_and_wait(
                    fn, jnp.float32(k), vals, sub, *args,
                    strata=fam.n_distinct, rows_visited=striped.capacity)
                est = est_lib.subsampling_estimate(q.agg, mom_sub, n_groups, b)
            dt = time.perf_counter() - t0
            rows = fam.prefix_for_k(k)
            sp.set(rows_read=rows)
        self._m_scan_seconds.observe(dt)
        self._count_rows(rows, fam.n_distinct)
        mom = est_lib.fold_subsamples(mom_sub, n_groups, b)
        return self._answer_from_moments(
            q, table_name, phi, k, mom, rows, dt, confidence,
            certified=certified, predicted_half_width=predicted_half_width,
            est=est)

    def _scan_and_answer(self, q: Query, table_name: str,
                         phi: tuple[str, ...], k: float, confidence: float,
                         certified: bool | None = None,
                         predicted_half_width: float | None = None
                         ) -> Answer:
        """One scan at K → Answer, routed by CI method. Subsampling CIs run
        only when no fault plan is armed: the sharded path reduces per-shard
        moment partials that cannot carry subsample segments, so it always
        uses the closed forms."""
        if self.config.ci_method == "subsampling" and inject.active() is None:
            return self._subsampled_answer(q, table_name, phi, k, confidence,
                                           certified, predicted_half_width)
        mom, rows_read, dt, rep, qpair = self._scan_for_query(
            table_name, q, phi, k)
        return self._answer_from_moments(
            q, table_name, phi, k, mom, rows_read, dt, confidence,
            faults=rep, qpair=qpair, certified=certified,
            predicted_half_width=predicted_half_width)

    # --------------------------- a-priori ERROR WITHIN contracts (§4.2)
    def _pilot_certify(self, table_name: str, q: Query,
                       phi: tuple[str, ...], confidence: float
                       ) -> tuple[float | None, float | None]:
        """Pilot scan on the family's smallest resolution → (K or None,
        predicted CI half-width in bound units). The pilot variance is
        inflated by the finite-sample chi-square factor
        (est_lib.pilot_inflation) BEFORE the §4.2 projection, so the
        certificate holds a-priori at the bound's confidence — not just in
        expectation, which is all the raw plug-in projection delivers. When
        no K suffices the half-width reported is the projection at the
        family's largest resolution: the best this family could do."""
        fam = self.families[table_name][phi]
        k_probe = min(fam.ks)
        mom, _, _, _, qpair = self._scan_for_query(table_name, q, phi,
                                                   k_probe)
        est = self._estimate_for(q, table_name, phi, k_probe, mom, qpair)
        n_pilot = np.asarray(est.n, dtype=np.float64)
        infl = est_lib.pilot_inflation(n_pilot, confidence)
        n_req = np.asarray(est_lib.required_n_for_error(
            q.agg, est, q.bound.eps, confidence, q.bound.relative))
        k_q = elp_lib.pick_k_for_error(fam, n_pilot, n_req * infl, k_probe)
        k_half = k_q if k_q is not None else fam.ks[0]
        return k_q, self._predicted_half(q, est, infl, k_probe, k_half,
                                         confidence)

    def _certify_at_top(self, table_name: str, q: Query,
                        phi: tuple[str, ...], confidence: float
                        ) -> tuple[float | None, float | None]:
        """Certify at the family's LARGEST resolution from the realized
        (inflated) CI of an actual scan there — the refinement for bounds
        the linear projection declares unreachable only because it cannot
        model full stratum containment. Returns (ks[0], half) on success,
        (None, half) when even the top resolution misses the bound."""
        fam = self.families[table_name][phi]
        k_top = fam.ks[0]
        mom, _, _, _, qpair = self._scan_for_query(table_name, q, phi, k_top)
        est = self._estimate_for(q, table_name, phi, k_top, mom, qpair)
        infl = est_lib.pilot_inflation(np.asarray(est.n, dtype=np.float64),
                                       confidence)
        half = self._predicted_half(q, est, infl, k_top, k_top, confidence)
        if half is not None and half <= q.bound.eps + 1e-12:
            return k_top, half
        return None, half

    def _predicted_half(self, q: Query, est: est_lib.Estimate, infl,
                        k_probe: float, k: float,
                        confidence: float) -> float | None:
        """Pilot-projected CI half-width at resolution k, in the bound's
        units (relative bounds divide by the pilot point estimate), max over
        the groups the pilot saw — None when it saw none. Variance scales
        ∝ k_probe/k (§4.2), held at 1 for k below the probe."""
        vals = np.atleast_1d(np.asarray(est.value, dtype=np.float64))
        var = np.atleast_1d(np.asarray(est.variance, dtype=np.float64))
        n = np.atleast_1d(np.asarray(est.n, dtype=np.float64))
        infl = np.broadcast_to(np.atleast_1d(infl), n.shape)
        seen = n > 0
        if not seen.any():
            return None
        z = est_lib.z_value(confidence)
        scale = min(k_probe / k, 1.0)
        half = z * np.sqrt(np.maximum(var * infl * scale, 0.0))
        if q.bound.relative:
            with np.errstate(divide="ignore", invalid="ignore"):
                half = np.where(np.abs(vals) > 0.0, np.abs(half / vals),
                                np.where(half > 0.0, np.inf, 0.0))
        return float(np.max(half[seen]))

    def _plan_error_bound(self, table_name: str, q: Query,
                          phi: tuple[str, ...], confidence: float,
                          first: tuple[float | None, float | None]
                          | None = None) -> ElpDecision:
        """Resolve an ErrorBound query to a contract decision by walking the
        ladder (docs/SERVICE.md):

          1. certify a K on the §4.1-selected family (pilot + inflation);
          2. escalate: pilot strictly LARGER families, ascending by size;
          3. exact base-table fallback — bound met by construction;
          4. best-effort annotated certified=False, or a typed
             BoundUnreachableError for a strict bound (`... OR FAIL`).

        `first` injects a pre-computed pilot result for the selected family
        (query_batch's shared batched pilot scan)."""
        fams = self.families[table_name]

        def decide(p, k, certified, half, exact=False):
            return ElpDecision(p, k, certified, exact=exact,
                               predicted_half_width=half,
                               gen=self.family_generation(table_name, p))

        if first is None:
            with obs_trace.span("plan.pilot", family=list(phi)):
                k_q, half = self._pilot_certify(table_name, q, phi,
                                                confidence)
        else:
            k_q, half = first
        if k_q is None and half is not None:
            # Containment refinement: the linear Var ∝ 1/n projection cannot
            # see that the family's largest prefix may fully CONTAIN the
            # strata the predicate touches (rate 1 ⇒ zero sampling
            # variance), so it declares unreachable bounds that the top
            # resolution meets outright. One scan at ks[0] certifies from
            # the realized inflated CI before the ladder escalates.
            with obs_trace.span("plan.certify_top", family=list(phi)):
                k_q, half = self._certify_at_top(table_name, q, phi,
                                                 confidence)
        if k_q is not None:
            return decide(phi, k_q, True, half)
        best_phi, best_half = phi, half
        if self.config.escalate_on_unreachable:
            def size(p):
                return max(fams[p].prefix_sizes)
            for p2 in sorted((p for p in fams
                              if p != phi and size(p) > size(phi)),
                             key=size):
                with obs_trace.span("plan.escalate", family=list(p2)):
                    k2, half2 = self._pilot_certify(table_name, q, p2,
                                                    confidence)
                if k2 is not None:
                    self._m_escalations.inc()
                    return decide(p2, k2, True, half2)
                if half2 is not None and (best_half is None
                                          or half2 < best_half):
                    best_phi, best_half = p2, half2
        if best_half is None:
            # Zero signal: NO pilot (selected family or escalation) saw a
            # single selected row. There is nothing to certify from — but
            # also no evidence the bound is busted (an empty selection
            # vacuously meets it), so burning a full exact scan to prove
            # emptiness is not the default. Serve the most accurate sample
            # annotated certified=False; a strict bound still refuses (or
            # takes the exact fallback) because it demands a guarantee.
            if isinstance(q.bound, ErrorBound) and q.bound.strict:
                if self.config.exact_fallback:
                    self._m_exact_fallbacks.inc()
                    return decide(phi, float(fams[phi].ks[0]), True, 0.0,
                                  exact=True)
                raise BoundUnreachableError(
                    f"ERROR WITHIN {q.bound.eps} cannot be certified on "
                    f"table {table_name!r}: no pilot scan selected any "
                    f"row (nothing to project from)", None)
            return decide(phi, fams[phi].ks[0], False, None)
        if self.config.exact_fallback:
            self._m_exact_fallbacks.inc()
            return decide(phi, float(fams[phi].ks[0]), True, 0.0, exact=True)
        if q.bound.strict:
            raise BoundUnreachableError(
                f"ERROR WITHIN {q.bound.eps} AT CONFIDENCE {confidence} is "
                f"unreachable on table {table_name!r}: best predicted CI "
                f"half-width {best_half} (escalation/exact fallback "
                f"disabled or exhausted)", best_half)
        return decide(best_phi, fams[best_phi].ks[0], False, best_half)

    def _execute_decision(self, q: Query, table_name: str,
                          dec: ElpDecision, confidence: float) -> Answer:
        """Run one resolved contract decision to an Answer."""
        if dec.exact:
            ans = self.exact_query(q)
            return dataclasses.replace(ans, bound_met=True, certified=True,
                                       predicted_half_width=0.0)
        if (isinstance(q.bound, ErrorBound) and q.bound.strict
                and dec.certified is False):
            # Replayed best-effort decision under a strict bound (config
            # may have changed since it was cached): still a refusal.
            raise BoundUnreachableError(
                f"ERROR WITHIN {q.bound.eps} unreachable (predicted CI "
                f"half-width {dec.predicted_half_width})",
                dec.predicted_half_width)
        return self._scan_and_answer(
            q, table_name, dec.phi, dec.k, confidence,
            certified=dec.certified,
            predicted_half_width=dec.predicted_half_width)

    def _cached_decision(self, elp_key: tuple,
                         table_name: str) -> ElpDecision | None:
        """§4.4 cache lookup with generation pinning: a decision whose
        family was dropped or whose CONTENT generation moved (escalated
        decisions can point outside the cache key's own family, which the
        positional invalidation in _drop_programs cannot see) is retired
        rather than replayed."""
        dec = self._elp_cache.get(elp_key)
        if dec is None:
            return None
        if dec.exact:
            return dec   # base-table scans don't pin any family
        fams = self.families.get(table_name, {})
        if dec.phi not in fams or \
                dec.gen != self.family_generation(table_name, dec.phi):
            del self._elp_cache[elp_key]
            return None
        return dec

    def _selection_cat_cols(self, table_name: str, q: Query) -> frozenset[str]:
        """Family selection columns (§4.1): joined dim attributes map to their
        fk column — a family stratified on the join key serves them (§2.1.i)."""
        fk_of = {j.dim_table: j.fact_key for j in q.joins}
        sel_cols = set()
        for c in q.where_group_columns:
            if "." in c:
                sel_cols.add(fk_of[c.split(".", 1)[0]])
            else:
                sel_cols.add(c)
        return frozenset(
            c for c in sel_cols
            if self.tables[table_name].schema.column(c).kind is ColumnKind.CATEGORICAL)

    def _select_phi(self, table_name: str, q: Query) -> tuple[str, ...]:
        """§4.1 runtime family selection (superset rule, else probe)."""
        fams = self.families[table_name]
        cat_cols = self._selection_cat_cols(table_name, q)

        def probe(phi: tuple[str, ...]) -> tuple[float, float]:
            fam = fams[phi]
            k_small = min(fam.ks)
            mom, rows_read, _, _ = self._run_at_k(table_name, q, phi, k_small)
            return float(jnp.sum(mom.n)), float(rows_read)

        return select_family(cat_cols, fams, probe).phi

    def query(self, q: Query) -> Answer:
        """Execute with §4.1 family selection + §4.2 ELP resolution choice.

        ErrorBound queries walk the a-priori contract ladder (pilot scan
        with finite-sample inflation, escalation to larger families, exact
        base-table fallback — docs/SERVICE.md), so every ErrorBound answer
        carries bound_met / certified / predicted_half_width provenance and
        a strict bound (`... OR FAIL`) raises BoundUnreachableError instead
        of silently serving a best-effort answer."""
        subqueries = rewrite_disjuncts(q)
        if len(subqueries) > 1:
            answers = [self.query(sq) for sq in subqueries]
            return _union_answers(q, answers)

        self._m_queries.labels("query").inc()
        table_name = q.table
        self._resolve_joins(table_name, q)
        with obs_trace.span("plan", table=table_name) as sp:
            phi = self._select_phi(table_name, q)
            confidence = q.bound.confidence if q.bound else 0.95

            if isinstance(q.bound, TimeBound):
                # TimeBound reuse unit is the LatencyModel (self._latency); K
                # re-projects against each call's effective budget, so a K
                # chosen under scheduler headroom can never alias a direct
                # call's full bound — nothing bound-shaped is cached.
                k_q = self._pick_k_for_time(table_name, q, phi)
                sp.set(bound="time", family=list(phi), k=float(k_q))
                dec = None
            else:
                # §4.4 ELP reuse: one pilot per (family × template × bound);
                # later instantiations replay the full DECISION (family, K,
                # certification, predicted half-width), generation-pinned to
                # the decided family.
                struct, _ = exec_lib.pred_structure(
                    exec_lib.bind_predicate(q.predicate,
                                            self._encode(table_name)))
                elp_key = (table_name, phi, struct, q.agg, q.value_column,
                           q.group_by, repr(q.bound))
                cached = (self._cached_decision(elp_key, table_name)
                          if self.config.reuse_elp else None)
                dec = cached
                if dec is None:
                    if isinstance(q.bound, ErrorBound):
                        dec = self._plan_error_bound(table_name, q, phi,
                                                     confidence)
                    else:   # no bound: most accurate available sample
                        dec = ElpDecision(
                            phi, self.families[table_name][phi].ks[0], None,
                            gen=self.family_generation(table_name, phi))
                    self._elp_cache[elp_key] = dec
                sp.set(family=list(dec.phi), k=float(dec.k),
                       certified=dec.certified, exact=dec.exact,
                       cached=cached is not None)
        if dec is None:
            return self._scan_and_answer(q, table_name, phi, k_q, confidence)
        return self._execute_decision(q, table_name, dec, confidence)

    def _pick_k_for_time(self, table_name: str, q: Query,
                         phi: tuple[str, ...],
                         headroom_s: float = 0.0) -> float:
        """§4.2 latency profile: calibrate t(rows) on the smallest
        resolutions, then pick the largest K inside the bound. Shared by
        query() and query_batch() (timing probes are inherently sequential).

        The fitted LatencyModel is the reuse unit — cached per (table,
        family) and re-projected against each call's effective budget
        (bound minus `headroom_s`, the admission scheduler's batching
        window, docs/SERVICE.md). The old design cached the RESOLVED K
        under a key that ignored headroom, so a batch-path decision made
        under a nonzero window could be replayed for a direct call (or vice
        versa) and silently bust the time bound."""
        fam = self.families[table_name][phi]
        model = self._latency.get((table_name, phi))
        if model is None:
            probes = elp_lib.run_probes(
                fam,
                lambda k: (lambda m, r, t, _rep: (float(jnp.sum(m.n)), t))(
                    *self._run_at_k(table_name, q, phi, k)),
                n_probes=self.config.probe_resolutions)
            model = elp_lib.fit_latency([p.rows_read for p in probes],
                                        [p.elapsed_s for p in probes])
            self._latency[(table_name, phi)] = model
        return elp_lib.pick_k_for_time(fam, model, q.bound.seconds,
                                       headroom_s=headroom_s)

    # ------------------------------------------------- batched shared scans
    def _plan_batch_job(self, parent: int, order: int, q: Query,
                        sel_cache: dict) -> "_BatchJob":
        """Resolve joins + family selection for one conjunctive subquery.
        Selection decisions are amortized across the batch: one probe per
        distinct (table, selection-column-set), shared by every query that
        maps to it (the batched analogue of §4.1)."""
        table_name = q.table
        self._resolve_joins(table_name, q)
        cat_cols = self._selection_cat_cols(table_name, q)
        struct, vals = exec_lib.pred_structure(
            exec_lib.bind_predicate(q.predicate, self._encode(table_name)))
        consts = exec_lib.flatten_pred_vals(vals)
        # Selection is deterministic given (columns, template, constants) —
        # probe-based choices depend on the constants' selectivity, so they
        # amortize only across identical instantiations; superset choices
        # (the template-stable hot case) never probe at all.
        skey = (table_name, cat_cols, struct, consts)
        phi = sel_cache.get(skey)
        if phi is None:
            phi = self._select_phi(table_name, q)
            sel_cache[skey] = phi
        group_col = q.group_by[0] if q.group_by else None
        n_groups = self._column_card(table_name, group_col) if group_col else 1
        return _BatchJob(
            parent=parent, order=order, q=q, table=table_name, phi=phi,
            struct=struct, consts=consts,
            elp_key=(table_name, phi, struct, q.agg, q.value_column,
                     q.group_by, repr(q.bound)),
            scan_key=(table_name, phi, struct, q.value_column, group_col,
                      n_groups),
            confidence=q.bound.confidence if q.bound else 0.95)

    def _run_batched(self, scan_key, ks: Sequence[float],
                     consts_list: Sequence[tuple[float, ...]]
                     ) -> tuple[est_lib.GroupedMoments, float,
                                "exec_lib.ShardScanReport | None"]:
        """One fused multi-query scan over a family prefix. The batch is
        padded to the next power of two so the per-(family × template) AOT
        program cache sees O(log Q) distinct shapes, not one per batch size.
        Under an armed fault plan the scan is shard-partitioned exactly like
        _run_at_k; the report (None when clean) applies to every query in
        the batch — they shared the one scan that lost the shard."""
        table_name, phi, struct, value_col, group_col, n_groups = scan_key
        striped = self._striped_for(table_name, phi)
        fam = self.families[table_name][phi]
        n_q = len(ks)
        if n_q > _MAX_SCAN_BATCH:
            moms, total_dt, reports = [], 0.0, []
            for i in range(0, n_q, _MAX_SCAN_BATCH):
                m, d, rep = self._run_batched(
                    scan_key, ks[i:i + _MAX_SCAN_BATCH],
                    consts_list[i:i + _MAX_SCAN_BATCH])
                moms.append(m)
                reports.append(rep)
                total_dt += d
            return (jax.tree.map(lambda *xs: jnp.concatenate(xs), *moms),
                    total_dt, exec_lib.merge_shard_reports(reports))
        q_pad = 1 << max(0, n_q - 1).bit_length()
        n_atoms = len(exec_lib.flat_atoms(struct))
        ks_arr = np.asarray(list(ks) + [ks[0]] * (q_pad - n_q), np.float32)
        consts = np.asarray(
            [list(c) for c in consts_list] +
            [list(consts_list[0])] * (q_pad - n_q),
            np.float32).reshape(q_pad, n_atoms)
        ks_dev, consts_dev = jnp.asarray(ks_arr), jnp.asarray(consts)
        args = exec_lib.scan_args(striped)
        spans, visited = self._row_grid(striped, struct, max(ks))
        pkey = scan_key + (striped.shape_class, q_pad)
        fn = self._batched_programs.get(pkey)
        if fn is None:
            jfn = exec_lib.make_batched_query_fn(
                struct, value_col, group_col, n_groups,
                mesh=self.mesh, data_axes=self.data_axes,
                use_pallas=self.config.use_pallas)
            fn = self._compile("batched", jfn, ks_dev, consts_dev, *args,
                               spans)
            self._batched_programs[pkey] = fn
        inject.site("engine.scan", table=table_name)
        with obs_trace.span("scan", table=table_name, batch=n_q,
                            k=float(max(ks))) as sp:
            t0 = time.perf_counter()
            report = None
            if self._fault_sharding_active():
                def call(mask):
                    return self._launch_and_wait(
                        fn, ks_dev, consts_dev, striped.columns,
                        striped.unit, striped.strat, striped.freq_table,
                        mask, spans, strata=fam.n_distinct,
                        rows_visited=visited)
                mom, report = exec_lib.run_sharded_scan(
                    call, striped,
                    n_logical=self.config.n_logical_shards,
                    n_replicas=self.config.shard_replicas,
                    site_ctx={"table": table_name},
                    deadline_s=self.config.straggler_deadline_s,
                    placement=self._placement_for(table_name, phi))
            else:
                mom = self._launch_and_wait(fn, ks_dev, consts_dev, *args,
                                            spans, strata=fam.n_distinct,
                                            rows_visited=visited)
            dt = time.perf_counter() - t0
            rows = fam.prefix_for_k(max(ks))
            sp.set(rows_read=rows)
            if report is not None:
                sp.set(shards=report.n_shards, lost=list(report.lost),
                       rerouted=list(report.rerouted))
        if obs_trace.tracing_active():
            self._set_placement_attrs(sp, table_name, phi, fam, struct,
                                      consts_list, flat=True)
        self._m_scan_seconds.observe(dt)
        self._count_rows(rows, fam.n_distinct)
        self._count_shard_report(report)
        with obs_trace.span("fetch"):
            return jax.tree.map(lambda x: x[:n_q], mom), dt, report

    def _run_batched_subsampled(self, scan_key, ks: Sequence[float],
                                consts_list: Sequence[tuple[float, ...]]
                                ) -> tuple[est_lib.GroupedMoments, float,
                                           None]:
        """Batched scan with per-subsample segments (ci_method=
        "subsampling"): the [Q, n_groups·B] analogue of _run_batched, same
        padding/chunking. Never fault-sharded — query_batch routes
        armed-plan scans to the closed-form path, so the report slot is
        always None."""
        table_name, phi, struct, value_col, group_col, n_groups = scan_key
        striped = self._striped_for(table_name, phi)
        n_q = len(ks)
        if n_q > _MAX_SCAN_BATCH:
            moms, total_dt = [], 0.0
            for i in range(0, n_q, _MAX_SCAN_BATCH):
                m, d, _ = self._run_batched_subsampled(
                    scan_key, ks[i:i + _MAX_SCAN_BATCH],
                    consts_list[i:i + _MAX_SCAN_BATCH])
                moms.append(m)
                total_dt += d
            return (jax.tree.map(lambda *xs: jnp.concatenate(xs), *moms),
                    total_dt, None)
        b = self.config.n_subsamples
        q_pad = 1 << max(0, n_q - 1).bit_length()
        n_atoms = len(exec_lib.flat_atoms(struct))
        ks_arr = np.asarray(list(ks) + [ks[0]] * (q_pad - n_q), np.float32)
        consts = np.asarray(
            [list(c) for c in consts_list] +
            [list(consts_list[0])] * (q_pad - n_q),
            np.float32).reshape(q_pad, n_atoms)
        ks_dev, consts_dev = jnp.asarray(ks_arr), jnp.asarray(consts)
        sub = self._subsample_codes_for(table_name, phi, striped)
        args = exec_lib.scan_args(striped)
        pkey = scan_key + (striped.shape_class, q_pad, b)
        fn = self._batched_subsampled_programs.get(pkey)
        if fn is None:
            jfn = exec_lib.make_batched_subsampled_query_fn(
                struct, value_col, group_col, n_groups, b,
                mesh=self.mesh, data_axes=self.data_axes)
            fn = self._compile("subsampled", jfn, ks_dev, consts_dev, sub,
                               *args)
            self._batched_subsampled_programs[pkey] = fn
        inject.site("engine.scan", table=table_name)
        with obs_trace.span("scan", table=table_name, batch=n_q,
                            k=float(max(ks)), subsampled=True) as sp:
            t0 = time.perf_counter()
            fam = self.families[table_name][phi]
            mom = self._launch_and_wait(fn, ks_dev, consts_dev, sub, *args,
                                        strata=fam.n_distinct,
                                        rows_visited=striped.capacity)
            dt = time.perf_counter() - t0
            rows = fam.prefix_for_k(max(ks))
            sp.set(rows_read=rows)
        self._m_scan_seconds.observe(dt)
        self._count_rows(rows, fam.n_distinct)
        with obs_trace.span("fetch"):
            return jax.tree.map(lambda x: x[:n_q], mom), dt, None

    def query_batch(self, queries: Sequence[Query],
                    deadline_headroom_s: float = 0.0) -> list[Answer]:
        """Execute N concurrent queries, sharing one family scan per
        (table, family, template) group.

        The batched analogue of query(): disjunctive queries are rewritten to
        conjunctive subqueries (§4.1.2) which join the batch individually;
        family selection and ELP probes are amortized across the batch (one
        probe scan per group serves every uncached ErrorBound query in it);
        the final pass is ONE fused multi-query scan per group, whose
        per-query moment slices unpack into ordinary Answers. Estimates are
        identical to sequential query() calls — only the HBM traffic and
        dispatch overhead are amortized. See docs/BATCHING.md.

        `deadline_headroom_s` (the admission scheduler's batching window)
        tightens every TimeBound query's scan budget by that amount, so a
        query that waited up to one window for coalescing still meets its
        bound end to end. TimeBound decisions are never cached: the latency
        MODEL is (per table × family), and K re-projects against each
        call's effective budget, so headroom cannot alias between the batch
        path and direct query() calls.

        ErrorBound queries run the same a-priori contract ladder as
        query(): the shared batched probe scan doubles as the pilot, and
        jobs the pilot cannot certify escalate / fall back to exact /
        annotate bound_met=False out of band (a strict bound raises
        BoundUnreachableError — the admission scheduler's per-query
        fallback path isolates it to the offending submitter).
        """
        queries = list(queries)
        if not queries:
            return []
        self._m_queries.labels("batch").inc(len(queries))
        sel_cache: dict = {}
        jobs: list[_BatchJob] = []
        n_subs = [0] * len(queries)
        with obs_trace.span("plan", batch=len(queries), stage="select"):
            for pi, q in enumerate(queries):
                for sq in rewrite_disjuncts(q):
                    jobs.append(self._plan_batch_job(pi, n_subs[pi], sq,
                                                     sel_cache))
                    n_subs[pi] += 1

        # Decisions that cannot join the shared scan — exact fallback, or
        # escalation onto a family the batch didn't plan for — run out of
        # band through the same decision runner query() uses.
        oob: dict[int, ElpDecision] = {}

        def apply_decision(job: _BatchJob, dec: ElpDecision) -> None:
            if dec.exact or dec.phi != job.phi:
                oob[id(job)] = dec
                return
            job.k = dec.k
            job.certified = dec.certified
            job.predicted_half = dec.predicted_half_width

        # ELP resolution (§4.2/§4.4): cached templates replay their
        # decision; uncached ErrorBound queries share one batched pilot scan
        # per group; TimeBound queries need wall-clock probes (inherently
        # sequential, one model fit per family).
        probe_groups: dict[tuple, list[_BatchJob]] = {}
        for job in jobs:
            fam = self.families[job.table][job.phi]
            if isinstance(job.q.bound, TimeBound):
                job.k = self._pick_k_for_time(job.table, job.q, job.phi,
                                              headroom_s=deadline_headroom_s)
                continue
            dec = (self._cached_decision(job.elp_key, job.table)
                   if self.config.reuse_elp else None)
            if dec is not None:
                apply_decision(job, dec)
            elif isinstance(job.q.bound, ErrorBound):
                probe_groups.setdefault(job.scan_key, []).append(job)
            else:   # no bound: most accurate available sample
                dec = ElpDecision(
                    job.phi, fam.ks[0], None,
                    gen=self.family_generation(job.table, job.phi))
                self._elp_cache[job.elp_key] = dec
                apply_decision(job, dec)

        for scan_key, group in probe_groups.items():
            fam = self.families[group[0].table][group[0].phi]
            k_probe = min(fam.ks)
            with obs_trace.span("plan.pilot", batch=len(group)):
                mom, _, _ = self._run_batched(scan_key,
                                              [k_probe] * len(group),
                                              [j.consts for j in group])
            for i, job in enumerate(group):
                # Sequential-contract parity (§4.4): once the first job of an
                # elp_key resolves, later jobs replay its decision — exactly
                # as sequential calls 2..N would hit the cache query 1 wrote.
                dec = (self._cached_decision(job.elp_key, job.table)
                       if self.config.reuse_elp else None)
                if dec is None:
                    # This job's slice of the shared pilot, brought to the
                    # host numbers that size its K.
                    with obs_trace.span("fetch"):
                        mi = est_lib.moments_slice(mom, i)
                        est = (self._quantile_estimate(job.q, job.table,
                                                       job.phi, k_probe, mi)
                               if job.q.agg is AggOp.QUANTILE
                               else est_lib.estimate(job.q.agg, mi))
                        n_pilot = np.asarray(est.n, dtype=np.float64)
                        infl = est_lib.pilot_inflation(n_pilot,
                                                       job.confidence)
                        n_req = np.asarray(est_lib.required_n_for_error(
                            job.q.agg, est, job.q.bound.eps, job.confidence,
                            job.q.bound.relative))
                        k_q = elp_lib.pick_k_for_error(fam, n_pilot,
                                                       n_req * infl, k_probe)
                        k_half = k_q if k_q is not None else fam.ks[0]
                        half = self._predicted_half(job.q, est, infl,
                                                    k_probe, k_half,
                                                    job.confidence)
                    # The shared batched probe IS this job's pilot; only
                    # unreachable bounds walk the rest of the ladder.
                    dec = self._plan_error_bound(job.table, job.q, job.phi,
                                                 job.confidence,
                                                 first=(k_q, half))
                    self._elp_cache[job.elp_key] = dec
                apply_decision(job, dec)

        # Final fused scan: one pass per (table, family, template) group.
        final_groups: dict[tuple, list[_BatchJob]] = {}
        for job in jobs:
            if id(job) in oob:
                continue
            final_groups.setdefault(job.scan_key, []).append(job)
        sub_answers: list[list[tuple[int, Answer]]] = [[] for _ in queries]
        use_sub = (self.config.ci_method == "subsampling"
                   and inject.active() is None)
        b = self.config.n_subsamples
        for scan_key, group in final_groups.items():
            n_groups = scan_key[5]
            # QUANTILE replicates need the per-subsample histogram pass —
            # batched groups containing one keep the closed-form CIs.
            sub_mode = use_sub and all(j.q.agg is not AggOp.QUANTILE
                                       for j in group)
            runner = (self._run_batched_subsampled if sub_mode
                      else self._run_batched)
            mom, dt, rep = runner(scan_key, [j.k for j in group],
                                  [j.consts for j in group])
            per_query_dt = dt / len(group)  # amortized shared-scan time
            for i, job in enumerate(group):
                fam = self.families[job.table][job.phi]
                with obs_trace.span("fetch"):
                    mi = est_lib.moments_slice(mom, i)
                    rows = fam.prefix_for_k(job.k)
                est = None
                if sub_mode:
                    est = est_lib.subsampling_estimate(job.q.agg, mi,
                                                       n_groups, b)
                    mi = est_lib.fold_subsamples(mi, n_groups, b)
                ans = self._answer_from_moments(
                    job.q, job.table, job.phi, job.k, mi, rows,
                    per_query_dt, job.confidence,
                    faults=rep, certified=job.certified,
                    predicted_half_width=job.predicted_half, est=est)
                sub_answers[job.parent].append((job.order, ans))

        for job in jobs:
            dec = oob.get(id(job))
            if dec is not None:
                ans = self._execute_decision(job.q, job.table, dec,
                                             job.confidence)
                sub_answers[job.parent].append((job.order, ans))

        out = []
        for pi, subs in enumerate(sub_answers):
            subs = [a for _, a in sorted(subs, key=lambda t: t[0])]
            out.append(subs[0] if len(subs) == 1
                       else _union_answers(queries[pi], subs))
        return out

    def exact_query(self, q: Query) -> Answer:
        """Ground truth: run the aggregation over the FULL table (rate=1),
        via a cached compiled program (fair timing baseline for E1)."""
        self._m_queries.labels("exact").inc()
        tbl = self.tables[q.table]
        self._resolve_joins(q.table, q)
        bound_pred = exec_lib.bind_predicate(q.predicate, self._encode(q.table))
        struct, vals = exec_lib.pred_structure(bound_pred)
        group_col = q.group_by[0] if q.group_by else None
        n_groups = self._column_card(q.table, group_col) if group_col else 1
        # Plain-dict snapshot: .items() refreshes any lazily-stale appended
        # device columns, and jit pytrees must not see the lazy dict subclass.
        tcols = dict(tbl.columns.items())
        # Columns are traced args and the key carries the table length +
        # column set, so an appended table can never hit a program compiled
        # against its old buffers (append_rows also prunes old entries).
        key = (q.table, struct, q.value_column, group_col, n_groups,
               tbl.n_rows, tuple(sorted(tcols)))
        # The tombstone mask rides as a traced argument, so exact programs
        # survive deletes (same length, same column set — only mask values
        # change); updates retire them via the n_rows key as appends do.
        live = tbl.live_mask_device()
        fn = self._exact_programs.get(key)
        if fn is None:
            n_rows = tbl.n_rows

            def build(pred_vals, cols, live_):
                disj = exec_lib.eval_pred(struct, cols, pred_vals) & live_
                ones_ = jnp.ones(n_rows, jnp.float32)
                values_ = (cols[q.value_column].astype(jnp.float32)
                           if q.value_column else ones_)
                g_ = (cols[group_col].astype(jnp.int32) if group_col
                      else jnp.zeros(n_rows, jnp.int32))
                return est_lib.grouped_moments(values_, ones_, disj, g_,
                                               n_groups)
            fn = self._compile("exact", jax.jit(build), vals, tcols, live)
            self._exact_programs[key] = fn

        with obs_trace.span("scan.exact", table=q.table) as sp:
            t0 = time.perf_counter()
            # The base table is no family: strata 0; the program visits
            # every physical row.
            self._m_rows_visited.inc(tbl.n_rows)
            with obs_trace.span("scan.launch", strata=0,
                                rows_visited=tbl.n_rows):
                mom = fn(vals, tcols, live)
            # The eager QUANTILE pass and the estimate are host-dispatched
            # device work that waits on the program's moments: scan.wait.
            with obs_trace.span("scan.wait", strata=0):
                mom = jax.tree.map(lambda x: x.block_until_ready(), mom)
                if q.agg is AggOp.QUANTILE:
                    # Only the quantile pass needs the raw mask/values/
                    # groups — the compiled program above already evaluated
                    # the predicate for the moment statistics.
                    mask = exec_lib.predicate_mask(tcols, bound_pred) & live
                    values = (tcols[q.value_column].astype(jnp.float32)
                              if q.value_column
                              else jnp.ones(tbl.n_rows, jnp.float32))
                    g = (tcols[group_col].astype(jnp.int32) if group_col
                         else jnp.zeros(tbl.n_rows, jnp.int32))
                    qv, dens = exec_lib.grouped_quantile(
                        values, mask.astype(jnp.float32), g, n_groups,
                        q.quantile)
                    est = est_lib.estimate(
                        AggOp.QUANTILE, mom, quantile_value=qv,
                        quantile_density=dens, q=q.quantile)
                else:
                    est = est_lib.estimate(q.agg, mom)
                est.value.block_until_ready()
            dt = time.perf_counter() - t0
            sp.set(rows_read=tbl.n_rows)
        self._m_scan_seconds.observe(dt)
        self._count_rows(tbl.n_rows, 0)
        with obs_trace.span("fetch"):
            vals = np.asarray(est.value)
            ns = np.asarray(est.n)
        groups = []
        for gidx in range(len(vals)):
            if ns[gidx] == 0:
                continue
            key = ((self._decode_col_value(q.table, group_col, gidx),)
                   if group_col else ())
            groups.append(GroupResult(key, float(vals[gidx]), 0.0,
                                      float(vals[gidx]), float(vals[gidx]),
                                      float(ns[gidx]), True))
        return Answer(q, groups, ("<exact>",), float("inf"), tbl.n_rows,
                      tbl.n_live, dt, 1.0)


def _union_answers(q: Query, answers: list[Answer]) -> Answer:
    """Combine disjunct sub-answers (§4.1.2): sums/counts add; variances add.
    (Disjuncts may overlap in general; BlinkDB's rewrite assumes disjoint or
    inclusion-exclusion handled upstream — we document the disjoint case.)

    Only ADDITIVE aggregates may be unioned this way; rewrite_disjuncts
    rejects AVG/QUANTILE before execution. Sub-answer GroupResults are
    copied before the union mutates ci_low/ci_high — groups that appear in a
    single disjunct must not alias (and silently corrupt) the sub-answer.
    """
    if q.agg not in (AggOp.COUNT, AggOp.SUM):
        raise ValueError(
            f"disjunct union is only defined for additive aggregates "
            f"(COUNT/SUM), not {q.agg}")
    by_key: dict[tuple, GroupResult] = {}
    for a in answers:
        for g in a.groups:
            if g.key in by_key:
                prev = by_key[g.key]
                var = prev.stderr ** 2 + g.stderr ** 2
                merged = GroupResult(
                    g.key, prev.estimate + g.estimate, var ** 0.5, 0.0, 0.0,
                    prev.n_selected + g.n_selected, prev.exact and g.exact)
                by_key[g.key] = merged
            else:
                by_key[g.key] = dataclasses.replace(g)
    z = est_lib.z_value(answers[0].confidence)
    groups = []
    for g in by_key.values():
        g.ci_low = g.estimate - z * g.stderr
        g.ci_high = g.estimate + z * g.stderr
        groups.append(g)
    mets = [a.bound_met for a in answers]
    certs = [a.certified for a in answers]
    preds = [a.predicted_half_width for a in answers
             if a.predicted_half_width is not None]
    return Answer(q, groups, answers[0].sample_phi, answers[0].sample_k,
                  sum(a.rows_read for a in answers), answers[0].rows_total,
                  sum(a.elapsed_s for a in answers), answers[0].confidence,
                  # Degradation provenance survives the union: one degraded
                  # disjunct makes the whole answer degraded (conservative —
                  # the widest loss across sub-answers is reported).
                  degraded=any(a.degraded for a in answers),
                  shards_lost=max(a.shards_lost for a in answers),
                  shards_total=max(a.shards_total for a in answers),
                  staleness_s=max(a.staleness_s for a in answers),
                  # Contract provenance: the union claims the bound only
                  # when EVERY disjunct did; the predicted half-width is
                  # the worst sub-answer's (conservative for a sum).
                  bound_met=(None if all(m is None for m in mets)
                             else all(bool(m) for m in mets)),
                  certified=(None if all(c is None for c in certs)
                             else all(bool(c) for c in certs)),
                  predicted_half_width=max(preds) if preds else None)
