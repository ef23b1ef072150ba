"""Query-lifecycle telemetry: spans, a metrics registry, and QueryReports.

A compiled-query engine lives or dies by visibility into where wall time
goes — parse vs plan vs (the dominant: seconds to minutes per program)
compile vs device execute vs host materialize.  Flare (PAPERS.md) makes the
same argument for Spark native compilation.  Before this module that
visibility was scattered and partly broken: a module-global ``stats`` dict
in physical/compiled.py with unlocked ``+= 1`` read-modify-writes and
ad-hoc counters in server/app.py.  Everything now funnels through here:

**Span tracer.**  ``trace_scope(sql)`` opens a per-query trace (the same
thread-local propagation pattern as ``resilience.QueryRuntime``; worker
threads re-enter via ``scoped``, exactly like ``resilience.scoped``).
``span(name)`` nests timed spans under the current one; ``annotate``
attaches attributes (row/byte counts, cache hit/miss, degradation rung,
retry counts) to the innermost open span.  Spans record wall time, the
owning thread, and exceptions; child append is lock-protected because
stage-graph workers attach concurrently.  Span times are
``time.monotonic_ns()`` readings, and every span is also a
``jax.profiler.TraceAnnotation`` named ``dsql:<span name>`` for its
lifetime (a TraceMe: one atomic load while no profiler session is on), so
any ``jax.profiler`` trace shows the engine's host phases on the device
trace's own clock.  The root is ``dsql:query`` and carries the query's
process-wide sequence number (``seq``), which ties a request's spans, its
report and its events in the trace together.

**Metrics registry.**  ``REGISTRY`` holds process-global thread-safe
counters and bounded histograms.  It absorbs and deprecates the old
``physical.compiled.stats`` dict (kept as a read-through alias) and the
resilience ``_bump`` path — every increment is atomic under one lock.

**Metric-name stability contract.**  The counter keys in
``STABLE_COUNTERS`` and the histogram names in ``STABLE_HISTOGRAMS`` are a
public, append-only interface: dashboards, ``GET /metrics`` scrapers and
the chip benchmark (``chipbench/``) all key on them.  Renaming or repurposing one
is a breaking change; add new names instead, and never reuse a retired
name for a different meaning.  Prometheus names derive mechanically:
counter ``k`` exports as ``dsql_<k>_total``, histogram ``h`` as
``dsql_<h>`` with ``_bucket``/``_sum``/``_count`` series.

**QueryReport.**  Closing a trace builds a ``QueryReport``: phase timings
aggregated from the span tree, process counter deltas, row/byte counts,
and the tree itself.  ``Context.sql`` stashes it on ``context.last_report``
and (thread-locally) for the server's per-query wire stats.  Reports
render as text (``render()``) or export as ``chrome://tracing`` JSON
(``to_chrome_trace()``; ``DSQL_CHROME_TRACE_DIR`` writes one file per
query).  ``DSQL_SLOW_QUERY_MS`` arms an opt-in slow-query log at trace
close.
"""
from __future__ import annotations

import itertools
import json
import logging
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation as _TraceAnnotation

logger = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# stable metric names (see the module docstring's stability contract)
# ---------------------------------------------------------------------------

# compile/execute pipeline counters (the old physical.compiled.stats keys,
# meanings unchanged) + streaming + server counters
STABLE_COUNTERS: Tuple[str, ...] = (
    # compiled pipeline
    "compiles", "hits", "fallbacks", "unsupported", "recompiles",
    "compile_errors", "exiled", "split_hints",
    # stage-graph observability
    "stage_graphs", "stage_compiles", "stage_hits", "cross_query_hits",
    # resilience observability
    "retries", "degradations", "deadline_exceeded",
    "fault_compile", "fault_materialize", "fault_stage_exec",
    "fault_stage_replay", "fault_chunked_read", "fault_host_transfer",
    "fault_cache_populate", "fault_admission", "fault_drain",
    "fault_spill",
    # failure-domain recovery (stage replay + quarantine + watchdog):
    # stage_execs counts stage-execution ATTEMPTS; stage_replays counts
    # checkpointed re-executions of a single failed stage;
    # stage_replay_saved_stages counts the already-materialized stages a
    # replay did NOT have to re-run
    "stage_execs", "stage_replays", "stage_replay_saved_stages",
    "quarantine_skips", "quarantine_probes", "quarantine_marks",
    "watchdog_trips",
    # tiered execution (physical/tiering.py): queries answered on the
    # eager tier while their stage programs compiled in the background,
    # background compiles that landed / errored, and compile-worker
    # halvings under consecutive-compile-failure pressure
    "served_eager_while_compiling", "background_compiles_done",
    "background_compile_errors", "compile_backoffs",
    # persistent cross-process program store (runtime/program_store.py)
    "program_store_hits", "program_store_misses", "program_store_stores",
    "program_store_rejects", "program_store_evictions",
    "program_store_errors",
    # workload manager (runtime/scheduler.py): per-class admission
    # outcomes; for any submission mix, admitted + rejected + timeout
    # always sums to the queries that entered admission
    "sched_admitted_interactive", "sched_admitted_batch",
    "sched_admitted_background",
    "sched_rejected_interactive", "sched_rejected_batch",
    "sched_rejected_background",
    "sched_timeout_interactive", "sched_timeout_batch",
    "sched_timeout_background",
    # result & subplan cache (runtime/result_cache.py)
    "result_cache_hits", "result_cache_misses", "result_cache_stores",
    "result_cache_evictions", "result_cache_spills",
    "result_cache_invalidations", "result_cache_subplan_hits",
    # streaming (out-of-HBM) execution
    "stream_batches", "stream_batch_rows",
    # out-of-core spill store (runtime/spill.py): runs opened
    # (spill_partitions — the EXPLAIN ANALYZE "spilled" signal), chunks
    # written, tier movement (host->disk flushes, disk->host loads,
    # device->host demotions), monotonic bytes written per tier, and
    # typed spill-IO failures
    "spill_partitions", "spill_chunks", "spill_flushes", "spill_loads",
    "spill_demotions", "spill_bytes_host", "spill_bytes_disk",
    "spill_errors",
    # grace-hash morsel driver (physical/morsel.py): joins lowered to
    # the partitioned path, partition pairs actually joined on device,
    # and pairs whose padded capacity blew past the skew threshold
    "morsel_joins", "morsel_pairs", "morsel_skew_warnings",
    # static-domain groupby reductions traced through the compiled
    # (non-interpreted) Pallas kernel (ops/pallas_kernels.py dispatch)
    "pallas_kernel_traces",
    # query lifecycle
    "queries", "query_errors", "slow_queries",
    # server boundary
    "server_queries", "server_query_errors", "server_cancels",
    "server_throttled", "server_drain_rejects",
    # flight recorder (runtime/flight_recorder.py): persisted event-log
    # appends / ring truncations / swallowed recording failures, and the
    # memory-broker estimates served from MEASURED history instead of the
    # scan-bytes×multiplier heuristic (scheduler.estimate_working_set)
    "history_records", "history_truncations", "history_errors",
    "estimate_from_history",
    # SPMD multi-chip backend (parallel/spmd.py): queries/stages served
    # sharded, program compiles vs cross-process store hits, collective
    # traffic (hash-exchange rounds + bytes moved, partial-aggregate
    # trees, broadcast-vs-exchange join dispatch), and the two refusal
    # paths — static gate (unsupported) vs runtime safety flag (fallback)
    "spmd_queries", "spmd_stages", "spmd_compiles", "spmd_store_hits",
    "spmd_exchanges", "spmd_exchange_bytes", "spmd_partial_aggs",
    "spmd_broadcast_joins", "spmd_exchange_joins", "spmd_join_flips",
    "spmd_fallbacks", "spmd_unsupported",
    # collective bytes by kind (parallel/spmd.py via exchange.py static
    # estimators): spmd_exchange_bytes above is the all_to_all channel;
    # these split out the broadcast-join gathers and psum combine trees
    "spmd_all_gather_bytes", "spmd_psum_bytes",
    # device-level profiler (runtime/profiler.py, DSQL_PROFILE=1):
    # memory snapshots taken, XLA cost-analysis captures (compile or
    # program-store load), and scheduler estimates served from the
    # captured cost model (the ladder's fourth rung)
    "profile_samples", "profile_cost_captures", "estimate_from_cost_model",
    # materialized views (runtime/matview.py): serves through the
    # resolve_table hook, O(delta) vs full refreshes (incremental + full
    # reconciles against the staleness events a soak drives), appended
    # batches logged on the delta seam, and the refresh chaos site
    "mv_serves", "mv_refresh_incremental", "mv_refresh_full",
    "mv_deltas_recorded", "fault_mv_refresh",
    # watchtower event bus + SLO monitor (runtime/events.py,
    # DSQL_EVENTS=1): events published to the bounded bus, publishes
    # that failed and were dropped (never the caller's problem), and
    # edge-triggered multi-window SLO burn-rate breaches
    "events_published", "events_dropped", "slo_breaches",
    # parameterized plan identity (plan/parameterize.py, ISSUE 16):
    # plans that had ≥1 literal hoisted, total literals hoisted, and
    # compiled-path program lookups for parameterized plans that hit
    # (in-memory cache or program store) vs compiled fresh;
    # prepared_executes counts EXECUTE statements served from the
    # per-context PREPARE registry; param_plan_subquery_hoisted counts
    # those of the hoisted literals that a scalar subquery's body reads;
    # param_plan_shared_subtrees the references to a subtree the plan
    # held twice that were pointed at its first copy (physical/shared.py)
    "param_plans", "param_literals_hoisted", "param_plan_subquery_hoisted",
    "param_plan_shared_subtrees", "param_plan_hits", "param_plan_misses",
    "prepared_executes",
    # result spooler (server/app.py, ISSUE 17): results larger than
    # DSQL_RESULT_PAGE_ROWS spool into the spill store and stream out
    # through nextUri pages; the reaper GCs abandoned results/futures
    # after DSQL_RESULT_TTL_S; fault_result_spool is the injection site
    # (a fired spool fault degrades to the unpaged response, never loses
    # the result)
    "result_spooled", "result_pages_spooled", "result_pages_served",
    "result_reaped", "fault_result_spool",
    # multi-tenancy (runtime/tenancy.py): admissions claimed under a
    # tenant, token-bucket/concurrency quota rejects, circuit-breaker
    # rejects/opens and half-open probes
    "tenant_queries", "tenant_quota_rejects", "tenant_circuit_rejects",
    "tenant_circuit_opens", "tenant_circuit_probes",
    # burn-driven load shedding (runtime/scheduler.py): background-class
    # admissions refused while a class burns its SLO error budget past
    # DSQL_SLO_BURN on both windows (each shed ALSO counts into
    # sched_rejected_background, so the admission reconciliation
    # invariant admitted + rejected + timeout == submitted still holds)
    "sched_shed_background",
    # fleet plane (runtime/fleet.py, DSQL_FLEET_DIR): heartbeat files
    # written / beat failures swallowed, and merged-ring reads served
    # (system.events fleet mode, /v1/events?fleet=1, /v1/fleet)
    "fleet_heartbeats", "fleet_heartbeat_errors", "fleet_merged_reads",
    # autopilot (runtime/autopilot.py, DSQL_AUTOPILOT=1): advisor ticks,
    # matview actuator actions (auto-create / drop / background refresh /
    # exact-repeat serves), and the re-planning loop's hint lifecycle
    # (recorded on a tripped threshold, applied to an execution, reverted
    # after two measured-slower strikes)
    "autopilot_ticks", "autopilot_mv_creates", "autopilot_mv_drops",
    "autopilot_mv_refreshes", "autopilot_mv_serves",
    "autopilot_hints_recorded", "autopilot_hints_applied",
    "autopilot_hints_reverted",
    # continuous ingestion (runtime/ingest.py, ISSUE 20): WAL-committed
    # batches/rows, micro-batch buffer traffic (buffered appends + flushes
    # that drained them), restart replay, memory-broker backpressure
    # rejects, torn WAL lines skipped on replay, /v1/ingest requests, the
    # fault_ingest injection site, and delta-log compactions that kept a
    # trickle of tiny appends on the incremental path (runtime/matview.py)
    "ingest_batches_committed", "ingest_rows_committed",
    "ingest_batches_buffered", "ingest_flushes",
    "ingest_replayed_batches", "ingest_replayed_rows",
    "ingest_backpressure_rejects", "ingest_wal_torn_lines",
    "server_ingest_requests", "fault_ingest",
    "mv_delta_compactions",
    # hash-table joins of the compiled tier by how their probe ran (PR 30,
    # physical/compiled.py _count_probes): the data let the table be
    # direct-addressed (one 32-bit gather a probe row), or the probe looped
    "join_probes_direct", "join_probes_looped",
    # and the joins of that formulation that built no table: the build
    # side's key column, strictly increasing as loaded, was probed itself
    # (PR 34, _count_probes): a dense column's probe counts as direct too,
    # a searched one's as neither direct nor looped
    "join_probes_ordered",
    # the once-a-program path itemized (PR 38; ``compile_span`` /
    # ``first_run_span``, whole milliseconds as ``load_*_ms`` are, added
    # where a ``compile`` span or its first run closes, foreground or
    # background): JAX's own clocks for trace, lowering and XLA by whether
    # its persistent cache missed (``compile_xla_ms``) or hit
    # (``compile_cache_load_ms``: the read and the deserialization), the
    # ``materialize`` of a program's first run, and ``compile`` + first run
    # of every round whose cause is not ``first`` (the price of the caps'
    # ladder; overlaps the five before it)
    "compile_trace_ms", "compile_lower_ms", "compile_xla_ms",
    "compile_cache_load_ms", "compile_first_run_ms", "compile_recompile_ms",
    # ``recompiles`` by what asked for the round (``caps._NeedsRecompile``'s
    # reason); ``recompiles`` stays their sum
    "recompiles_overflow", "recompiles_tighten", "recompiles_hint",
    # grouped aggregates that took their groups from the runs of a key
    # column in load order and hashed nothing (PR 45, physical/aggregates.py
    # ``run_aggregate``): one a request and node, over the runs that gave
    # an answer
    "groupby_run_aggregates",
)

STABLE_HISTOGRAMS: Tuple[str, ...] = (
    "query_wall_ms", "parse_ms", "plan_ms", "execute_ms", "compile_ms",
    "materialize_ms",
)

# gauges (point-in-time values, may go down): same append-only contract
STABLE_GAUGES: Tuple[str, ...] = (
    "result_cache_bytes", "result_cache_host_bytes",
    # workload manager: live queue depth (incl. server seats), queries
    # currently executing, and device bytes reserved by admitted queries
    "sched_queue_depth", "sched_running", "sched_reserved_bytes",
    # 1 while the process is draining (SIGTERM/SIGINT received, in-flight
    # queries finishing, new admissions refused), else 0
    "server_draining",
    # spill-store tier occupancy (runtime/spill.py), point-in-time
    "spill_device_bytes", "spill_host_bytes", "spill_disk_bytes",
    # device-memory profiler (runtime/profiler.py): summed local-device
    # HBM truth from the latest memory_stats() sample (zeros on backends
    # without memory stats, e.g. CPU)
    "profile_hbm_bytes_in_use", "profile_hbm_peak_bytes",
    "profile_hbm_bytes_limit",
    # SLO monitor (runtime/events.py, DSQL_EVENTS=1): per-priority-class
    # lifetime attainment and multi-window burn rates (breach fraction
    # over the window / error budget; 1.0 = spending the budget exactly
    # at the sustainable pace)
    "slo_attainment_interactive", "slo_attainment_batch",
    "slo_attainment_background",
    "slo_burn_fast_interactive", "slo_burn_fast_batch",
    "slo_burn_fast_background",
    "slo_burn_slow_interactive", "slo_burn_slow_batch",
    "slo_burn_slow_background",
    # result spooler: live spooled pages + bytes awaiting collection
    "result_spool_pages", "result_spool_bytes",
    # 1 while burn-driven background shedding is active, else 0
    "slo_shedding",
    # tenants the registry has seen this process (runtime/tenancy.py)
    "tenants_known",
    # fleet plane (runtime/fleet.py): replicas within heartbeat TTL at
    # the last fleet snapshot, and the fleet-wide sum of every alive
    # replica's program_store_hits — the shared-warmth proof counter
    "fleet_replicas_alive", "fleet_warm_serves",
    # continuous ingestion (runtime/ingest.py): WAL bytes on disk, rows
    # sitting in un-flushed micro-batch buffers, and view staleness —
    # un-applied delta rows across all registered matview base tables +
    # age in seconds of the oldest pending delta (0 when fully fresh)
    "ingest_wal_bytes", "ingest_buffered_rows",
    "mv_pending_rows", "mv_staleness_s",
)

# exponential-ish bucket bounds in milliseconds; histograms are BOUNDED by
# construction (fixed bucket count + running sum/count, O(1) per observe)
_BUCKETS_MS: Tuple[float, ...] = (
    1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000, 10000, 30000,
    120000,
)


class _Histogram:
    __slots__ = ("counts", "total", "count")

    def __init__(self):
        self.counts = [0] * (len(_BUCKETS_MS) + 1)
        self.total = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        i = 0
        for i, b in enumerate(_BUCKETS_MS):
            if value <= b:
                break
        else:
            i = len(_BUCKETS_MS)
        self.counts[i] += 1
        self.total += value
        self.count += 1

    def snapshot(self) -> dict:
        return {"buckets": list(zip(_BUCKETS_MS, self.counts)),
                "overflow": self.counts[-1],
                "sum": self.total, "count": self.count}


class MetricsRegistry:
    """Process-global thread-safe counters + bounded histograms.

    ``inc`` is the atomic replacement for every unlocked
    ``stats["k"] += 1`` read-modify-write the engine used to do; ``set``
    exists only for the deprecated dict-alias write path.  Counter names
    in STABLE_COUNTERS pre-exist at zero so snapshot consumers (bench
    deltas, fault_smoke) never KeyError on a counter that has not fired.
    """

    def __init__(self, seed: Tuple[str, ...] = (),
                 gauge_seed: Tuple[str, ...] = ()):
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {k: 0 for k in seed}
        self._gauges: Dict[str, float] = {k: 0 for k in gauge_seed}
        self._hists: Dict[str, _Histogram] = {}

    # -- counters ----------------------------------------------------------
    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def set(self, name: str, value: int) -> None:
        with self._lock:
            self._counters[name] = int(value)

    def get(self, name: str, default: Optional[int] = None) -> Optional[int]:
        with self._lock:
            return self._counters.get(name, default)

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    # -- gauges ------------------------------------------------------------
    def set_gauge(self, name: str, value: float) -> None:
        """Point-in-time value (cache sizes, pool depths): unlike counters
        a gauge may go DOWN; prometheus renders it without ``_total``."""
        with self._lock:
            self._gauges[name] = value

    def get_gauge(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return self._gauges.get(name, default)

    def gauges(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._gauges)

    # -- histograms --------------------------------------------------------
    def observe(self, name: str, value_ms: float) -> None:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = _Histogram()
            h.observe(float(value_ms))

    def snapshot(self) -> dict:
        with self._lock:
            return {"counters": dict(self._counters),
                    "gauges": dict(self._gauges),
                    "histograms": {k: h.snapshot()
                                   for k, h in self._hists.items()}}

    def reset(self) -> None:
        """Zero everything (tests only; production counters are
        monotonic by contract)."""
        with self._lock:
            for k in self._counters:
                self._counters[k] = 0
            for k in self._gauges:
                self._gauges[k] = 0
            self._hists.clear()

    # -- prometheus --------------------------------------------------------
    def render_prometheus(self,
                          labels: Optional[Dict[str, str]] = None) -> str:
        """Prometheus text exposition (text/plain; version=0.0.4).

        Counter ``k`` -> ``dsql_<k>_total``; histogram ``h`` ->
        ``dsql_<h>`` with le-bucketed ``_bucket`` series + ``_sum`` +
        ``_count``.  Names are sanitized to the prometheus charset.
        ``labels`` (e.g. ``{"replica": "r1"}`` when a fleet dir is
        armed) are stamped on EVERY series; with none the exposition is
        byte-identical to the label-free historical format.
        """
        def clean(name: str) -> str:
            return "".join(c if (c.isalnum() or c == "_") else "_"
                           for c in name)

        base = ""
        if labels:
            base = ",".join(f'{clean(k)}="{v}"'
                            for k, v in sorted(labels.items()))

        def series(m: str, extra: str = "") -> str:
            parts = ",".join(p for p in (base, extra) if p)
            return f"{m}{{{parts}}}" if parts else m

        snap = self.snapshot()
        out: List[str] = []
        for k in sorted(snap["counters"]):
            m = f"dsql_{clean(k)}_total"
            out.append(f"# TYPE {m} counter")
            out.append(f"{series(m)} {snap['counters'][k]}")
        for k in sorted(snap.get("gauges", ())):
            m = f"dsql_{clean(k)}"
            out.append(f"# TYPE {m} gauge")
            out.append(f"{series(m)} {snap['gauges'][k]:g}")
        for k in sorted(snap["histograms"]):
            h = snap["histograms"][k]
            m = f"dsql_{clean(k)}"
            out.append(f"# TYPE {m} histogram")
            acc = 0
            for bound, c in h["buckets"]:
                acc += c
                le = 'le="%g"' % bound
                out.append(f"{series(m + '_bucket', le)} {acc}")
            acc += h["overflow"]
            inf = 'le="+Inf"'
            out.append(f"{series(m + '_bucket', inf)} {acc}")
            out.append(f"{series(m + '_sum')} {h['sum']:.6g}")
            out.append(f"{series(m + '_count')} {h['count']}")
        return "\n".join(out) + "\n"


REGISTRY = MetricsRegistry(seed=STABLE_COUNTERS, gauge_seed=STABLE_GAUGES)


def inc(name: str, n: int = 1) -> None:
    """Atomic counter increment on the global registry (the replacement
    for every former ``stats[name] += 1`` site)."""
    REGISTRY.inc(name, n)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class Span:
    """One timed node of a query's span tree.  ``t0``/``t1`` are
    ``time.monotonic_ns()`` readings (CLOCK_MONOTONIC: the clock a client
    on the same machine reads), so a span lies on a profiler trace, or
    beside a client's record, without an anchor."""

    __slots__ = ("name", "t0", "t1", "attrs", "children", "tid")

    def __init__(self, name: str, attrs: Optional[dict] = None):
        self.name = name
        self.t0 = time.monotonic_ns()
        self.t1: Optional[int] = None
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.children: List["Span"] = []
        self.tid = threading.get_ident()

    @property
    def wall_ms(self) -> float:
        end = self.t1 if self.t1 is not None else time.monotonic_ns()
        return (end - self.t0) / 1e6

    def walk(self):
        yield self
        for c in list(self.children):
            yield from c.walk()

    def to_dict(self) -> dict:
        return {"name": self.name, "t0_ns": self.t0,
                "wall_ms": round(self.wall_ms, 3),
                "attrs": dict(self.attrs),
                "children": [c.to_dict() for c in self.children]}


# process-wide query sequence: the root span's ``seq`` (next() on a count
# is one bytecode under the interpreter lock)
_query_seq = itertools.count(1)


class QueryTrace:
    """One query's span tree + the registry snapshot at open.

    ``lock`` guards child append: stage-graph worker threads attach spans
    to the same parent concurrently."""

    __slots__ = ("query", "root", "lock", "counters0", "report",
                 "started_unix")

    def __init__(self, query: str = "", root: Optional[Span] = None):
        self.query = query
        self.root = root or Span("query", {"seq": next(_query_seq)})
        self.lock = threading.Lock()
        self.counters0 = REGISTRY.counters()
        self.report: Optional["QueryReport"] = None
        self.started_unix = time.time()


class _Tls(threading.local):
    trace: Optional[QueryTrace] = None
    span: Optional[Span] = None
    node_recorder = None
    last_report: Optional["QueryReport"] = None
    last_load: Optional[Span] = None
    xla_cache_hit = False  # ``_on_jax_duration``: a retrieval was recorded


_tls = _Tls()


def current_trace() -> Optional[QueryTrace]:
    return _tls.trace


def current_span() -> Optional[Span]:
    return _tls.span


@contextmanager
def scoped(trace: Optional[QueryTrace], parent: Optional[Span] = None):
    """Install an existing trace in THIS thread (worker-pool re-entry —
    the telemetry analogue of ``resilience.scoped``)."""
    prev_t, prev_s = _tls.trace, _tls.span
    _tls.trace = trace
    _tls.span = parent if parent is not None else (
        trace.root if trace is not None else None)
    try:
        yield
    finally:
        _tls.trace, _tls.span = prev_t, prev_s


class _NoSpan:
    """What ``span()`` hands out outside a trace: enters to None."""

    __slots__ = ()
    record = None  # as ``_CompileSpan``: nothing was logged

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


class _OpenSpan:
    """The context manager of one span (a class, not a generator: a warm
    query opens a dozen of these, and each is a few microseconds)."""

    __slots__ = ("_trace", "_parent", "_name", "_attrs", "_span",
                 "_annotation")

    def __init__(self, trace: QueryTrace, parent: Span, name: str,
                 attrs: dict):
        self._trace, self._parent = trace, parent
        self._name, self._attrs = name, attrs

    def __enter__(self) -> Span:
        s = self._span = Span(self._name, self._attrs)
        with self._trace.lock:
            self._parent.children.append(s)
        _tls.span = s
        # opened and closed on the thread that runs the span: a stage on a
        # ``scoped()`` worker lands on the worker's own line of the trace
        self._annotation = _TraceAnnotation("dsql:" + self._name)
        self._annotation.__enter__()
        return s

    def __exit__(self, exc_type, exc, tb):
        self._annotation.__exit__(exc_type, exc, tb)
        s = self._span
        if exc_type is not None:
            s.attrs["error"] = exc_type.__name__
        s.t1 = time.monotonic_ns()
        _tls.span = self._parent
        return False


def span(name: str, **attrs):
    """Open a child span under the current one; no-op outside a trace.

    An escaping exception stamps ``error=<type name>`` on the span and
    re-raises — the span tree always closes consistently."""
    trace = _tls.trace
    parent = _tls.span
    if trace is None or parent is None:
        return _NO_SPAN
    return _OpenSpan(trace, parent, name, attrs)


def annotate(**attrs) -> None:
    """Attach attributes to the innermost open span (no-op outside)."""
    s = _tls.span
    if s is not None:
        s.attrs.update(attrs)


def annotation(name: str, **args):
    """A bare ``dsql:<name>`` event on the profiler's trace, for work done
    outside any open query trace (the server encodes a page after the
    query's trace has closed); ``args`` (the query's ``seq``, counts) ride
    as the event's arguments."""
    return _TraceAnnotation("dsql:" + name, **args)


# ---------------------------------------------------------------------------
# the once-a-program path: ``compile`` itemized (physical/programs.py
# ``obtain`` opens it, physical/compiled.py ``_execute_single`` its first run)
# ---------------------------------------------------------------------------

#: JAX's own clocks (``jax.monitoring`` duration events) -> the child of an
#: open ``compile`` span each becomes
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile_lower",
    "/jax/core/compile/backend_compile_duration": "compile_xla",
}
#: recorded on a hit of XLA's persistent cache only, inside the
#: ``backend_compile_duration`` that then closes round it
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

#: why a round after a program's first was asked for
#: (``caps._NeedsRecompile.reason``, a ``compile`` span's ``cause``) -> the
#: counter beside ``recompiles``, which stays their sum
RECOMPILE_COUNTERS = {"cap_overflow": "recompiles_overflow",
                      "cap_tighten": "recompiles_tighten",
                      "hint_refuted": "recompiles_hint"}

_compile_log: "deque[dict]" = deque(maxlen=256)
_compile_log_lock = threading.Lock()  # the ring, and the one registration
_listening = False


def _on_jax_duration(event: str, duration_secs: float, **_) -> None:
    """The engine's one ``jax.monitoring`` listener.  JAX calls it on the
    thread that compiles; where that thread's innermost open span is a
    ``compile``, the event becomes a closed child of it (its end is now,
    its start ``duration_secs`` before, on the spans' clock).  Anywhere
    else it returns at once: a warm request compiles nothing."""
    parent = _tls.span
    if parent is None or parent.name != "compile":
        return
    if event == _CACHE_RETRIEVAL:
        _tls.xla_cache_hit = True
        return
    name = _COMPILE_PHASES.get(event)
    trace = _tls.trace
    if name is None or trace is None:
        return
    child = Span(name)
    child.t1 = child.t0
    child.t0 = max(child.t1 - int(duration_secs * 1e9), parent.t0)
    if name == "compile_xla":
        # a hit's duration is the read and the deserialization
        hit, _tls.xla_cache_hit = _tls.xla_cache_hit, False
        cache_on = (jax.config.jax_enable_compilation_cache
                    and jax.config.jax_compilation_cache_dir is not None)
        child.attrs["xla_cache"] = ("hit" if hit else
                                    "miss" if cache_on else "off")
    with trace.lock:
        kids = parent.children
        # a jit traced inside another's trace reports first and lies inside
        # it (every ``jnp`` function of a program's body does): the outer
        # one's duration holds it, so it goes.  Children stand in the order
        # they ended, so what lies inside the new one is a tail of them.
        i = len(kids)
        while i and kids[i - 1].t1 > child.t0:
            i -= 1
        kids[i:] = [k for k in kids[i:] if k.name != name] + [child]


def _listen() -> None:
    global _listening
    with _compile_log_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            _listening = True


class _CompileSpan(_OpenSpan):
    """The ``compile`` span.  Closing it adds its phases to the counters
    and its record to the ring (``compile_log()``), foreground or
    background; ``record`` is that entry, for ``first_run_span``."""

    __slots__ = ("record",)

    def __exit__(self, exc_type, exc, tb):
        super().__exit__(exc_type, exc, tb)
        s = self._span
        ms = dict.fromkeys(("compile_trace_ms", "compile_lower_ms",
                            "compile_xla_ms", "compile_cache_load_ms"), 0.0)
        verdicts = set()
        for c in s.children:
            if c.name == "compile_xla":
                verdicts.add(c.attrs["xla_cache"])
                hit = c.attrs["xla_cache"] == "hit"
                ms["compile_cache_load_ms" if hit
                   else "compile_xla_ms"] += c.wall_ms
            elif c.name + "_ms" in ms:
                ms[c.name + "_ms"] += c.wall_ms
        for counter, spent in ms.items():
            inc(counter, round(spent))
        wall, attrs = s.wall_ms, s.attrs
        if attrs["cause"] != "first":
            inc("compile_recompile_ms", round(wall))
        self.record = {
            "t0_ns": s.t0,
            "program": attrs["program"],
            "cause": attrs["cause"],
            "round": attrs["round"],
            "caps": attrs["caps"],
            # of the program's XLA compiles (its own, and a helper's built
            # while it traced): one that missed tells
            "xla_cache": next((v for v in ("miss", "hit", "off")
                               if v in verdicts), ""),
            "background": attrs["background"],
            "trace_ms": round(ms["compile_trace_ms"], 3),
            "lower_ms": round(ms["compile_lower_ms"], 3),
            "xla_ms": round(ms["compile_xla_ms"]
                            + ms["compile_cache_load_ms"], 3),
            "first_run_ms": None,
            "wall_ms": round(wall, 3),
            "error": attrs.get("error", ""),
        }
        with _compile_log_lock:
            _compile_log.append(self.record)
        return False


def compile_span(program: str, round: int, cause: str, caps: str):
    """``programs.obtain``'s ``compile`` span: which ``program``, in which
    ``round`` of ``_execute_single``'s loop, its ``cause`` (``first``,
    ``split``, or a ``caps._NeedsRecompile``'s reason) and the ``caps``
    that changed for it; ``background`` is read off the trace.  ``span``'s
    rules, and JAX's trace, lowering and XLA compile (or the read of its
    persistent cache) inside it become its children ``compile_trace``,
    ``compile_lower`` and ``compile_xla`` (``xla_cache=hit|miss|off``).
    Its self time is ``build()``, the quarantine check and the first
    dispatch."""
    trace, parent = _tls.trace, _tls.span
    if trace is None or parent is None:
        return _NO_SPAN
    if not _listening:
        _listen()
    return _CompileSpan(trace, parent, "compile", {
        "program": program, "round": round, "cause": cause, "caps": caps,
        "background": trace.root.name == "background_compile"})


class _FirstRunSpan(_OpenSpan):
    __slots__ = ("_record",)

    def __init__(self, trace: QueryTrace, parent: Span, record: dict):
        super().__init__(trace, parent, "materialize", {"first_run": True})
        self._record = record

    def __exit__(self, exc_type, exc, tb):
        super().__exit__(exc_type, exc, tb)
        ms = self._span.wall_ms
        self._record["first_run_ms"] = round(ms, 3)
        inc("compile_first_run_ms", round(ms))
        if self._record["cause"] != "first":
            inc("compile_recompile_ms", round(ms))
        return False


def first_run_span(record: dict):
    """The ``materialize`` span of the round that compiled its program
    (``record``: that ``compile`` span's): ``first_run=true``, and the wait
    for the program's first run on the device goes to the counters and the
    record, whether the run answers or asks for another round.  A record
    was made inside a trace, on this thread: the trace is still open."""
    return _FirstRunSpan(_tls.trace, _tls.span, record)


def compile_log() -> List[dict]:
    """The last 256 closed ``compile`` spans of the process, oldest first,
    from query and background traces alike: ``t0_ns``, ``program``,
    ``cause``, ``round``, ``caps``, ``xla_cache``, ``background``,
    ``trace_ms``, ``lower_ms``, ``xla_ms`` (a hit's: the load),
    ``first_run_ms`` (None until that run's ``materialize`` closed),
    ``wall_ms``, ``error``.  ``system.compiles`` serves it."""
    with _compile_log_lock:
        return [dict(r) for r in _compile_log]


@contextmanager
def load_scope(**attrs):
    """``Context.create_table``'s span tree: a ``load`` span whose children
    ``load_encode``, ``load_stats`` and ``load_transfer`` open with
    ``span()`` like a query's, each a ``dsql:<name>`` event on a profiler's
    trace.  Inside a query (CREATE TABLE AS) it is a child of the query's
    current span; outside it is a tree of its own, no query and no report,
    kept for ``last_load()``."""
    if _tls.trace is not None:
        with span("load", **attrs) as s:
            yield s
        return
    root = Span("load", attrs)
    with scoped(QueryTrace(root=root)), _TraceAnnotation("dsql:load"):
        try:
            yield root
        finally:
            root.t1 = time.monotonic_ns()
            _tls.last_load = root


def last_load() -> Optional[Span]:
    """The ``load`` span of this thread's last ``create_table`` outside a
    query, children and attributes filled in."""
    return _tls.last_load


# ---------------------------------------------------------------------------
# per-node instrumentation (EXPLAIN ANALYZE)
# ---------------------------------------------------------------------------

class NodeRecorder:
    """Per-plan-node (wall, rows, calls) accumulator, keyed by node id.

    Installed thread-locally by ``record_nodes()``; the eager executor
    feeds it from ``RelExecutor.execute``.  Timings are INCLUSIVE of
    children (the executor recurses through the same entry point);
    renderers derive self-time by subtracting child totals."""

    def __init__(self):
        self.records: Dict[int, List[float]] = {}  # id -> [ms, rows, calls]

    def add(self, rel, ms: float, rows: int) -> None:
        rec = self.records.get(id(rel))
        if rec is None:
            self.records[id(rel)] = [ms, rows, 1]
        else:
            rec[0] += ms
            rec[1] += rows
            rec[2] += 1

    def get(self, rel):
        return self.records.get(id(rel))


def active_node_recorder() -> Optional[NodeRecorder]:
    return _tls.node_recorder


@contextmanager
def record_nodes():
    prev = _tls.node_recorder
    rec = NodeRecorder()
    _tls.node_recorder = rec
    try:
        yield rec
    finally:
        _tls.node_recorder = prev


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------

def _fleet_replica() -> Optional[str]:
    """Replica id when the fleet plane (runtime/fleet.py) is armed, else
    None — env checked BEFORE the import (the profiler/recorder gate
    discipline), so the unarmed path costs one dict lookup."""
    if not os.environ.get("DSQL_FLEET_DIR"):
        return None
    try:
        from . import fleet as _fleet
        return _fleet.replica_id()
    except Exception:
        return None


# span names that aggregate into the phase breakdown
_PHASE_SPANS = frozenset((
    "parse", "plan", "execute", "fetch", "compile", "materialize", "stage",
    "stage_graph", "stream_batch", "queued", "retry_backoff", "drain",
    # compile's children, from JAX's own clocks (``_on_jax_duration``)
    "compile_trace", "compile_lower", "compile_xla",
    # the executor's host side, under execute (or a stage)
    "result_cache", "lookup", "bind", "dispatch"))
# span attribute -> kind of collective, for QueryReport.collective_bytes
_COLLECTIVE_ATTRS = (("spmd_exchange_bytes", "all_to_all"),
                     ("spmd_all_gather_bytes", "all_gather"),
                     ("spmd_psum_bytes", "psum"))


class QueryReport:
    """Everything one ``Context.sql`` call did, in one object.

    ``phases``: wall-ms sums per span name (parse/plan/execute/fetch at
    the top level; result_cache/lookup/bind/dispatch/materialize/compile/
    stage nested under execute — so only parse+plan+execute+fetch
    partition the wall).  ``counters``:
    process-global registry deltas between trace open and close (exact
    per-query attribution when queries do not overlap; an upper bound
    under concurrency).  ``root``: the span tree."""

    __slots__ = ("query", "seq", "wall_ms", "phases", "counters", "root",
                 "rows_out", "bytes_out", "started_unix", "cache", "tier",
                 "priority", "operators", "spilled", "skew_ratio",
                 "collective_bytes", "cost_err", "trace_id", "tenant",
                 "replica")

    def __init__(self, trace: QueryTrace):
        root = trace.root
        self.query = trace.query
        self.seq = root.attrs.get("seq")
        self.started_unix = trace.started_unix
        self.wall_ms = root.wall_ms
        self.root = root
        # end-to-end trace ID (runtime/events.py stamps it on the root at
        # trace open when DSQL_EVENTS is armed); None when the
        # watchtower is off — consumers emit it only when present
        tid = root.attrs.get("trace_id")
        self.trace_id = str(tid) if tid else None
        # tenant identity (runtime/tenancy.py stamps it on the root when
        # an explicit tenant was supplied); None otherwise — consumers
        # emit it only when present, like the trace ID
        ten = root.attrs.get("tenant")
        self.tenant = str(ten) if ten else None
        # replica identity (runtime/fleet.py): present only when a fleet
        # dir is armed — env checked before the import so single-process
        # reports stay byte-identical and the fleet module un-imported
        self.replica = _fleet_replica()
        self.rows_out = int(root.attrs.get("rows_out", 0))
        self.bytes_out = int(root.attrs.get("bytes_out", 0))
        # one pass over the tree collects everything below (a warm compiled
        # query has a dozen spans, and this runs inside the caller's wait)
        phases: Dict[str, float] = {}
        # result-cache section: exact per-query attribution from span attrs
        # (the ``result_cache`` spans carry the verdict), plus the current
        # tier sizes from the gauges
        hit = False
        tier: Optional[str] = None
        stored = False
        subplan_hits = 0
        # execution tier (tiered execution, physical/tiering.py):
        # "compiled" / "eager" / "eager-compiling" (served on the eager
        # tier while the stage programs build in the background)
        exec_tier: Optional[str] = None
        # workload-manager class: the admission path stamps it on the
        # queued span; None when the scheduler is disabled
        priority: Optional[str] = None
        # adaptive operator choices (runtime/statistics.py record_choice
        # appends "groupby=dense ..." lines to span attrs) in span order
        operators: List[str] = []
        # out-of-core marker: the grace-hash driver annotates its morsel
        # spans with spilled=True
        spilled = False
        # device-level profile surface (ISSUE 13): worst shard/partition
        # skew (max/mean row ratio — SPMD stages and grace-hash morsel
        # joins both annotate ``skew_ratio``), collective bytes by kind,
        # and the XLA cost-model error vs measured stage bytes; all None
        # when nothing annotated them (profiler off / single device)
        skew: Optional[float] = None
        coll: Dict[str, int] = {}
        cost_bytes = 0.0
        measured = 0
        for s in root.walk():
            if s is not root and s.name in _PHASE_SPANS:
                phases[s.name] = phases.get(s.name, 0.0) + s.wall_ms
            attrs = s.attrs
            if not attrs:
                continue
            rc = attrs.get("result_cache")
            if rc == "hit":
                hit = True
                tier = attrs.get("result_cache_tier", tier)
            elif rc == "store":
                stored = True
            if attrs.get("subplan_cache") == "hit":
                subplan_hits += 1
            t = attrs.get("tier")
            if t is not None and exec_tier is None:
                exec_tier = str(t)
            if s.name == "queued" and priority is None:
                p = attrs.get("priority")
                priority = str(p) if p is not None else None
            ops = attrs.get("operators")
            if ops:
                operators.extend(str(o) for o in ops)
            if attrs.get("spilled"):
                spilled = True
            r = attrs.get("skew_ratio")
            if r is not None:
                skew = max(float(r), skew) if skew is not None else float(r)
            for attr, kind in _COLLECTIVE_ATTRS:
                v = attrs.get(attr)
                if v:
                    coll[kind] = coll.get(kind, 0) + int(v)
            cb = attrs.get("cost_bytes")
            if cb:
                cost_bytes += float(cb)
            sb = attrs.get("stage_bytes")
            if sb:
                measured += int(sb)
        self.phases = phases
        now = REGISTRY.counters()
        self.counters = {k: now[k] - trace.counters0.get(k, 0)
                         for k in now
                         if now[k] != trace.counters0.get(k, 0)}
        self.tier = exec_tier
        self.priority = priority
        self.operators = operators
        # the counter delta catches spills from nested plans that never
        # opened a span under this trace
        self.spilled = (spilled
                        or self.counters.get("spill_partitions", 0) > 0)
        self.skew_ratio = round(skew, 3) if skew is not None else None
        self.collective_bytes = coll or None
        # measured working set mirrors the flight recorder's definition:
        # result bytes plus every materialized stage boundary
        measured += self.bytes_out
        self.cost_err = (round(abs(cost_bytes - measured) / measured, 4)
                         if cost_bytes and measured else None)
        self.cache = {"hit": hit, "tier": tier, "stored": stored,
                      "subplan_hits": subplan_hits,
                      "bytes": int(REGISTRY.get_gauge("result_cache_bytes")),
                      "host_bytes":
                          int(REGISTRY.get_gauge("result_cache_host_bytes"))}

    def span_count(self, name: str) -> int:
        return sum(1 for s in self.root.walk() if s.name == name)

    def to_dict(self) -> dict:
        out = {"query": self.query, "wall_ms": round(self.wall_ms, 3),
               "trace_id": self.trace_id,
               "tenant": self.tenant,
               "phases": {k: round(v, 3) for k, v in self.phases.items()},
               "counters": dict(self.counters),
               "cache": dict(self.cache),
               "tier": self.tier,
               "priority": self.priority,
               "operators": list(self.operators),
               "spilled": self.spilled,
               "skew_ratio": self.skew_ratio,
               "collective_bytes": self.collective_bytes,
               "cost_err": self.cost_err,
               "rows_out": self.rows_out, "bytes_out": self.bytes_out,
               "spans": self.root.to_dict()}
        # fleet-armed only, so the unarmed dict stays key-identical
        if self.replica:
            out["replica"] = self.replica
        return out

    def render(self) -> str:
        """Human-readable report: header + indented span tree."""
        lines = [f"query: {self.query.strip()[:200]}",
                 f"wall: {self.wall_ms:.2f} ms  rows_out: {self.rows_out}"
                 f"  bytes_out: {self.bytes_out}"]
        if self.phases:
            lines.append("phases: " + "  ".join(
                f"{k}={v:.2f}ms" for k, v in sorted(self.phases.items())))
        if self.counters:
            lines.append("counters: " + "  ".join(
                f"{k}=+{v}" for k, v in sorted(self.counters.items())))
        if self.operators:
            lines.append("operators: " + "; ".join(self.operators))
        if self.spilled:
            lines.append("spilled: true")
        if self.skew_ratio is not None:
            lines.append(f"skew_ratio: {self.skew_ratio}")
        if self.collective_bytes:
            lines.append("collective_bytes: " + "  ".join(
                f"{k}={v}" for k, v in sorted(self.collective_bytes.items())))
        if self.cost_err is not None:
            lines.append(f"cost_err: {self.cost_err}")

        def walk(s: Span, depth: int):
            attrs = "".join(f" {k}={v}" for k, v in sorted(s.attrs.items()))
            lines.append(f"{'  ' * depth}{s.name}: {s.wall_ms:.2f} ms"
                         + attrs)
            for c in s.children:
                walk(c, depth + 1)

        walk(self.root, 0)
        return "\n".join(lines)

    def to_chrome_trace(self) -> dict:
        """chrome://tracing ("Trace Event Format") JSON of the span tree:
        complete ("X") events in microseconds on CLOCK_MONOTONIC, each
        with its absolute ``t0_ns``."""
        events = []
        for s in self.root.walk():
            end = s.t1 if s.t1 is not None else time.monotonic_ns()
            events.append({
                "name": s.name, "ph": "X", "pid": os.getpid(),
                "tid": s.tid,
                "ts": s.t0 / 1e3,
                "dur": (end - s.t0) / 1e3,
                "t0_ns": s.t0,
                "args": {k: (v if isinstance(v, (int, float, str, bool))
                             else repr(v))
                         for k, v in s.attrs.items()},
            })
        other = {"query": self.query[:500]}
        if self.trace_id:
            other["trace_id"] = self.trace_id
        if self.replica:
            other["replica"] = self.replica
        return {"traceEvents": events,
                "displayTimeUnit": "ms",
                "otherData": other}


def last_report() -> Optional[QueryReport]:
    """The report of the most recent trace CLOSED on this thread —
    race-free per-query attribution for the server's worker threads."""
    return _tls.last_report


def _env_float(name: str) -> Optional[float]:
    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw else None
    except ValueError:
        return None


_chrome_counter = [0]
_chrome_lock = threading.Lock()


def _export_chrome_trace(report: QueryReport) -> None:
    """Write the span tree as chrome://tracing JSON when
    ``DSQL_CHROME_TRACE_DIR`` is armed; shared by the per-query close and
    the background-compile daemon threads (close_background_trace)."""
    trace_dir = os.environ.get("DSQL_CHROME_TRACE_DIR")
    if not trace_dir:
        return
    try:
        os.makedirs(trace_dir, exist_ok=True)
        with _chrome_lock:
            _chrome_counter[0] += 1
            n = _chrome_counter[0]
        path = os.path.join(
            trace_dir, f"query_{os.getpid()}_{n:05d}.trace.json")
        with open(path, "w") as f:
            json.dump(report.to_chrome_trace(), f)
    except OSError as e:  # telemetry must never fail the query
        logger.debug("chrome trace export failed: %s", e)


def close_background_trace(trace: QueryTrace) -> QueryReport:
    """Close a NON-query trace (background compile daemon threads carry
    their own — physical/tiering._background_compile): builds the report
    and exports the chrome trace WITHOUT counting a query, arming the
    slow-query log, or recording a history envelope."""
    trace.root.t1 = time.monotonic_ns()
    report = QueryReport(trace)
    trace.report = report
    _export_chrome_trace(report)
    return report


def _close_trace(trace: QueryTrace, error: Optional[BaseException]) -> None:
    trace.root.t1 = time.monotonic_ns()
    if error is not None:
        trace.root.attrs["error"] = type(error).__name__
        REGISTRY.inc("query_errors")
    report = QueryReport(trace)
    trace.report = report
    _tls.last_report = report
    REGISTRY.inc("queries")
    REGISTRY.observe("query_wall_ms", report.wall_ms)
    # ``compile`` is the whole span, its ``compile_*`` children included
    for name in ("parse", "plan", "execute", "compile", "materialize"):
        v = report.phases.get(name)
        if v is not None:
            REGISTRY.observe(f"{name}_ms", v)

    slow_ms = _env_float("DSQL_SLOW_QUERY_MS")
    if slow_ms is not None and report.wall_ms >= slow_ms:
        REGISTRY.inc("slow_queries")
        logger.warning(
            "slow query (%.0f ms >= DSQL_SLOW_QUERY_MS=%.0f): %s | tier: %s "
            "| cacheHit: %s | priority: %s | skew: %s | collectives: %s "
            "| costErr: %s | phases: %s | counters: %s%s%s%s",
            report.wall_ms, slow_ms, report.query.strip()[:500],
            report.tier or "eager", bool(report.cache.get("hit")),
            report.priority or "-",
            report.skew_ratio if report.skew_ratio is not None else "-",
            report.collective_bytes or "-",
            report.cost_err if report.cost_err is not None else "-",
            {k: round(v, 1) for k, v in sorted(report.phases.items())},
            dict(sorted(report.counters.items())),
            # trace/tenant/replica correlation suffixes only when they
            # exist, so the line stays byte-identical with the features off
            f" | trace: {report.trace_id}" if report.trace_id else "",
            f" | tenant: {report.tenant}" if report.tenant else "",
            f" | replica: {report.replica}" if report.replica else "")

    _export_chrome_trace(report)

    # flight recorder (runtime/flight_recorder.py): the env gate keeps the
    # disabled hot path at ONE dict lookup — no import, no lock
    if os.environ.get("DSQL_HISTORY_FILE"):
        try:
            from . import flight_recorder as _fr
            _fr.record_query(report, error)
        except Exception:
            REGISTRY.inc("history_errors")
            logger.debug("flight recorder append failed", exc_info=True)

    # device profiler (runtime/profiler.py): same env-gate-before-import
    # discipline — DSQL_PROFILE=0 costs one dict lookup, zero imports
    if os.environ.get("DSQL_PROFILE", "0").strip() not in ("", "0"):
        try:
            from . import profiler as _prof
            _prof.on_query_complete(report)
        except Exception:
            logger.debug("profiler query hook failed", exc_info=True)

    # watchtower (runtime/events.py): SLO fold-in + query.done event —
    # same env-gate-before-import discipline as the two hooks above
    if os.environ.get("DSQL_EVENTS", "0").strip() not in ("", "0"):
        try:
            from . import events as _ev
            _ev.on_query_complete(report, error)
        except Exception:
            logger.debug("event hook failed", exc_info=True)

    # autopilot feedback (runtime/autopilot.py): hinted-run verdicts and
    # threshold-tripped hint recording — same env-gate-before-import
    if os.environ.get("DSQL_AUTOPILOT", "0").strip() not in ("", "0"):
        try:
            from . import autopilot as _ap
            _ap.on_query_complete(report, error)
        except Exception:
            logger.debug("autopilot hook failed", exc_info=True)


@contextmanager
def trace_scope(query: str = ""):
    """Open the per-query trace on this thread; yields the QueryTrace.

    Nested calls (a query issued from inside another query's execution)
    yield None and ride the enclosing trace as ordinary spans — one trace
    and one report per outermost ``Context.sql``."""
    if _tls.trace is not None:
        yield None
        return
    trace = QueryTrace(query)
    _tls.trace = trace
    _tls.span = trace.root
    # live-query registry for system.active / GET /v1/engine — gated on the
    # recorder's env knob so the disabled path allocates nothing
    registered = False
    if os.environ.get("DSQL_HISTORY_FILE"):
        try:
            from . import flight_recorder as _fr
            registered = _fr.begin_query(trace)
        except Exception:
            logger.debug("flight recorder begin failed", exc_info=True)
    # watchtower ingress: stamp the end-to-end trace ID on the root span
    # (server-minted / env-propagated / fresh) and publish query.begin —
    # env gate BEFORE import, zero cost when DSQL_EVENTS is off
    if os.environ.get("DSQL_EVENTS", "0").strip() not in ("", "0"):
        try:
            from . import events as _ev
            _ev.on_trace_open(trace)
        except Exception:
            logger.debug("event trace-open hook failed", exc_info=True)
    err: Optional[BaseException] = None
    try:
        with _TraceAnnotation("dsql:query", seq=trace.root.attrs["seq"]):
            yield trace
    except BaseException as e:
        err = e
        raise
    finally:
        _tls.trace = None
        _tls.span = None
        try:
            _close_trace(trace, err)
        except Exception:  # pragma: no cover - never mask the query result
            logger.exception("telemetry close failed")
        if registered:
            try:
                _fr.end_query(trace)
            except Exception:  # pragma: no cover - registry is advisory
                logger.debug("flight recorder end failed", exc_info=True)


# ---------------------------------------------------------------------------
# deprecated dict alias support (physical.compiled.stats)
# ---------------------------------------------------------------------------

try:
    from collections.abc import MutableMapping as _MutableMapping
except ImportError:  # pragma: no cover
    from collections import MutableMapping as _MutableMapping  # type: ignore


class CounterAlias(_MutableMapping):
    """DEPRECATED dict-shaped read-through view of REGISTRY's counters.

    Exists so the long-standing ``physical.compiled.stats`` surface keeps
    working (tests, fault_smoke, bench all read it, and ``dict(stats)``
    must keep snapshotting every counter).  Writes forward to the registry
    atomically — but note ``alias[k] += 1`` is still a two-step
    read-modify-write at the CALL SITE; new code must use
    ``telemetry.inc`` instead."""

    def __getitem__(self, key: str) -> int:
        v = REGISTRY.get(key)
        if v is None:
            raise KeyError(key)
        return v

    def __setitem__(self, key: str, value: int) -> None:
        REGISTRY.set(key, value)

    def __delitem__(self, key: str) -> None:
        raise TypeError("registry counters cannot be deleted")

    def __iter__(self):
        return iter(REGISTRY.counters())

    def __len__(self) -> int:
        return len(REGISTRY.counters())

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"CounterAlias({REGISTRY.counters()!r})"
