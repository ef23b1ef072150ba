"""Presto-wire-protocol HTTP server.

Re-implements the reference server (/root/reference/dask_sql/server/app.py):
``POST /v1/statement`` submits SQL, ``GET /v1/status/{uuid}`` polls,
``DELETE /v1/cancel/{uuid}`` cancels, ``GET /v1/empty`` returns an empty
result — with async execution via a thread pool + futures registry mirroring
the reference's dask-client future_list (app.py:69-95).  Submission runs
through the workload manager (runtime/scheduler.py): every POST claims an
admission seat (priority from the ``X-DSQL-Priority`` header), a saturated
system answers 429 + ``Retry-After`` immediately, ``queuedTimeMillis`` and
``queuedSplits``/``runningSplits`` report the scheduler's real measurements,
and the pool is sized by ``DSQL_SERVER_WORKERS`` (default: the scheduler's
concurrency limit) instead of a hardcoded width.  ``GET /metrics``
exposes the engine's telemetry registry (runtime/telemetry.py) in
Prometheus text format — the same counters previously only reachable via
``physical.compiled.stats`` — and per-query wire stats carry the query's
phase breakdown from its QueryReport.

**Graceful drain.**  SIGTERM/SIGINT (handlers installed by the blocking
``run_server`` path; tests and embedders use ``server.drain_async()``)
flips the workload manager into draining: new ``POST /v1/statement``
requests answer **503 + Retry-After** (typed
``resilience.ServerDraining``), in-flight queries finish — and their
results stay fetchable — within ``DSQL_DRAIN_TIMEOUT_S``, stragglers get
typed cancellation, then the listener closes and the process can exit.
The ``server_draining`` gauge is 1 for the duration and the drain itself
records a ``drain`` span in a QueryReport.  ``ERROR_WIRE_MATRIX`` below
pins the full taxonomy → (submit-time HTTP status, errorType, errorName)
mapping; tests assert it row by row.

Built on stdlib http.server (FastAPI/uvicorn are not in this image); the wire
format matches the reference's responses.py so presto/trino clients work.
"""
from __future__ import annotations

import json
import logging
import math
import os
import threading
import time
import uuid as uuid_mod
from concurrent.futures import Future, ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional

from ..runtime import (faults as _faults, resilience as _res,
                       scheduler as _sched, telemetry as _tel)

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# taxonomy -> wire mapping (audited; tests/unit/test_error_wire_matrix.py
# asserts every row).  The submit-time status is what POST /v1/statement
# answers when the verdict is known BEFORE a query id exists (admission /
# drain); verdicts raised later ride the Presto convention — HTTP 200 with
# a FAILED payload carrying errorType/errorName/errorCode — exactly like
# the reference server.
# ---------------------------------------------------------------------------

ERROR_WIRE_MATRIX = {
    # class name: (submit-time HTTP status, errorType, errorName)
    "UserError": (200, "USER_ERROR", "GENERIC_USER_ERROR"),
    "QueryCancelled": (200, "USER_ERROR", "USER_CANCELED"),
    "TransientError": (200, "INTERNAL_ERROR", "TRANSIENT_ERROR"),
    "FatalError": (200, "INTERNAL_ERROR", "GENERIC_INTERNAL_ERROR"),
    "FaultInjected": (200, "INTERNAL_ERROR", "FAULT_INJECTED"),
    "FatalFaultInjected": (200, "INTERNAL_ERROR", "FAULT_INJECTED"),
    "DeadlineExceeded": (200, "INSUFFICIENT_RESOURCES",
                         "EXCEEDED_TIME_LIMIT"),
    "AdmissionRejected": (429, "INSUFFICIENT_RESOURCES", "QUERY_QUEUE_FULL"),
    "AdmissionTimeout": (429, "INSUFFICIENT_RESOURCES",
                         "QUERY_QUEUE_TIMEOUT"),
    # tenant quotas / circuit breakers (runtime/tenancy.py) and the
    # burn-driven load shed (runtime/scheduler.py) ride the 429 +
    # Retry-After path of their AdmissionRejected parent
    "TenantQuotaExceeded": (429, "INSUFFICIENT_RESOURCES",
                            "TENANT_QUOTA_EXCEEDED"),
    "TenantCircuitOpen": (429, "INSUFFICIENT_RESOURCES",
                          "TENANT_CIRCUIT_OPEN"),
    "LoadShedRejected": (429, "INSUFFICIENT_RESOURCES", "SLO_LOAD_SHED"),
    # continuous ingestion (runtime/ingest.py): a write whose batch the
    # memory broker cannot absorb rides the 429 + Retry-After path; a
    # batch that does not fit the target table schema is the writer's
    # mistake — 400, never a retry
    "IngestBackpressure": (429, "INSUFFICIENT_RESOURCES",
                           "INGEST_BACKPRESSURE"),
    "SchemaMismatch": (400, "USER_ERROR", "SCHEMA_MISMATCH"),
    "ServerDraining": (503, "INSUFFICIENT_RESOURCES",
                       "SERVER_SHUTTING_DOWN"),
    "SpillError": (200, "INTERNAL_ERROR", "SPILL_ERROR"),
    "SpillCorrupt": (200, "INTERNAL_ERROR", "SPILL_CORRUPT"),
}


def _events_on() -> bool:
    """Watchtower gate (runtime/events.py): checked BEFORE any import so
    DSQL_EVENTS=0 keeps the wire byte-identical — no trace headers, no
    /v1/events route, no module import."""
    return os.environ.get("DSQL_EVENTS", "0").strip() not in ("", "0")


def _tenancy_on() -> bool:
    """Tenancy gate (runtime/tenancy.py): same env-before-import
    discipline — DSQL_TENANCY=0 keeps the module un-imported and the
    wire byte-identical (no tenant section, no tenant claims)."""
    return os.environ.get("DSQL_TENANCY", "1").strip() not in ("", "0")


def _fleet_on() -> bool:
    """Fleet-plane gate (runtime/fleet.py): checked BEFORE any import so
    an unset DSQL_FLEET_DIR keeps the module un-imported, /v1/fleet on
    the generic 404, and every wire byte byte-identical."""
    return bool(os.environ.get("DSQL_FLEET_DIR"))


def _ingest_on() -> bool:
    """Continuous-ingestion gate (runtime/ingest.py): DSQL_INGEST_DIR
    arms, DSQL_INGEST=0 kills — both checked BEFORE any import so the
    unarmed wire (no /v1/ingest route, no engine section) stays
    byte-identical with the module absent."""
    return bool(os.environ.get("DSQL_INGEST_DIR")) and \
        os.environ.get("DSQL_INGEST", "1").strip() not in ("0", "false")


def _page_rows() -> int:
    """Result-paging threshold (``DSQL_RESULT_PAGE_ROWS``): results with
    more rows spool into SpillStore pages of this many rows; 0 restores
    the old single-shot payload bit-for-bit."""
    try:
        return max(int(os.environ.get("DSQL_RESULT_PAGE_ROWS", "")
                       or 10_000), 0)
    except ValueError:
        return 10_000


def _result_ttl_s() -> float:
    """Reaper TTL (``DSQL_RESULT_TTL_S``): finished-but-never-collected
    queries and abandoned result spools are garbage-collected this many
    seconds after their last touch (0 disables reaping — the historical
    leak-forever behavior)."""
    try:
        return max(float(os.environ.get("DSQL_RESULT_TTL_S", "") or 600.0),
                   0.0)
    except ValueError:
        return 600.0


def submit_status(exc: Exception) -> int:
    """HTTP status for a verdict raised at the POST boundary: 503 while
    draining, 429 on saturation, 200 otherwise (the error then travels in
    the Presto payload)."""
    if isinstance(exc, _res.ServerDraining):
        return 503
    if isinstance(exc, _res.AdmissionRejected):
        return 429
    if isinstance(exc, _res.SchemaMismatch):
        return 400
    return 200


# ---------------------------------------------------------------------------
# presto wire responses (reference server/responses.py)
# ---------------------------------------------------------------------------

def _stats(state: str, info: Optional["_QueryInfo"] = None) -> dict:
    """Wire-shape of reference responses.py:11-49, but FILLED: the reference
    hardcodes zeros; here cpu/wall/queued times, processed rows/bytes, the
    compile-vs-cache-hit split and device peak memory come from the actual
    execution (physical/compiled.py stats + timers)."""
    out = {
        "state": state, "queued": state == "QUEUED", "scheduled": True,
        "nodes": 1, "totalSplits": 1, "queuedSplits": int(state == "QUEUED"),
        "runningSplits": int(state == "RUNNING"),
        "completedSplits": int(state == "FINISHED"),
        "cpuTimeMillis": 0, "wallTimeMillis": 0,
        "queuedTimeMillis": 0, "elapsedTimeMillis": 0, "processedRows": 0,
        "processedBytes": 0, "peakMemoryBytes": 0,
    }
    # live saturation from the workload manager's gauges (not the old
    # per-query 0/1 constants): presto clients polling ANY query see the
    # process-wide queue depth and running count
    mgr = _sched.get_manager()
    if mgr.enabled():
        out["queuedSplits"] = mgr.queue_depth()
        out["runningSplits"] = mgr.running_count()
    if info is not None:
        now = time.monotonic()
        started = info.started or now
        finished = info.finished or now
        if info.queued_ms is not None:
            # the scheduler's own timestamps: seat claim at POST ->
            # admission grant (covers pool wait + admission-queue wait)
            out["queuedTimeMillis"] = int(info.queued_ms)
        else:
            out["queuedTimeMillis"] = int(1000 * (started - info.submitted))
        out["wallTimeMillis"] = int(1000 * max(finished - started, 0))
        out["elapsedTimeMillis"] = int(1000 * (finished - info.submitted))
        out["cpuTimeMillis"] = int(1000 * info.cpu_sec)
        out["processedRows"] = info.rows
        out["processedBytes"] = info.bytes
        out["peakMemoryBytes"] = info.peak_memory
        out["compiledPrograms"] = info.compiles
        out["programCacheHits"] = info.cache_hits
        # result-cache verdict from the query's own QueryReport (exact,
        # span-attributed — not a process-global counter diff)
        out["cacheHit"] = bool(info.cache_hit)
        if info.cache_tier:
            out["cacheTier"] = info.cache_tier
        if info.subplan_cache_hits:
            out["subplanCacheHits"] = info.subplan_cache_hits
        # execution tier (tiered execution, physical/tiering.py):
        # "compiled" / "eager" / "eager-compiling", plus the persistent
        # program-store loads this query was served warm from
        if info.tier:
            out["tier"] = info.tier
        if info.program_store_hits:
            out["programStoreHits"] = info.program_store_hits
        # adaptive operator choices this query's dispatch took
        # (runtime/statistics.py record_choice, via the QueryReport)
        if info.operators:
            out["operatorChoices"] = list(info.operators)
        if info.phases:
            # per-query phase breakdown from the query's own QueryReport
            # (race-free: the report is thread-local to the worker that
            # ran the query, not a process-global snapshot)
            out["phaseMillis"] = {k: round(v, 3)
                                  for k, v in info.phases.items()}
        # end-to-end trace ID (watchtower, DSQL_EVENTS=1): the same ID
        # the X-DSQL-Trace header carries, so payload-only clients can
        # still join wire stats to span trees / envelopes / events
        if info.trace_id:
            out["traceId"] = info.trace_id
    return out


class _QueryInfo:
    __slots__ = ("submitted", "started", "finished", "cpu_sec", "rows",
                 "bytes", "peak_memory", "compiles", "cache_hits", "phases",
                 "cache_hit", "cache_tier", "subplan_cache_hits",
                 "queued_ms", "tier", "program_store_hits", "operators",
                 "trace_id", "seq")

    def __init__(self):
        self.submitted = time.monotonic()
        self.started = None
        self.finished = None
        self.cpu_sec = 0.0
        self.rows = 0
        self.bytes = 0
        self.peak_memory = 0
        self.compiles = 0
        self.cache_hits = 0
        self.phases = {}
        self.cache_hit = False
        self.cache_tier = None
        self.subplan_cache_hits = 0
        self.queued_ms = None
        self.tier = None
        self.program_store_hits = 0
        self.operators = []
        self.trace_id = None
        self.seq = None     # the query's telemetry sequence number


def _run_tracked(context, sql: str, info: _QueryInfo,
                 cancel: Optional[threading.Event] = None,
                 seat: Optional[_sched.Seat] = None,
                 trace_id: Optional[str] = None,
                 params: Optional[list] = None,
                 grant=None):
    from ..physical import compiled
    from contextlib import nullcontext

    # the ingress trace ID rides into the worker thread: trace_scope's
    # watchtower hook picks it up and stamps the span tree, so the ID on
    # the POST response and the ID in the trace/envelope/events agree.
    # trace_id is only ever non-None when DSQL_EVENTS is armed.
    if trace_id:
        from ..runtime import events as _ev
        tid_scope = _ev.trace_id_scope(trace_id)
    else:
        tid_scope = nullcontext()

    # the POST-time tenant pre-claim rides in the same way: tenancy's
    # admission (wrapping the plan execution) consumes it exactly once —
    # mirroring the scheduler seat — so the token spent at the server
    # boundary is the only token this query costs.  grant is only ever
    # non-None when DSQL_TENANCY is armed.
    if grant is not None:
        from ..runtime import tenancy as _ten
        g_scope = _ten.grant_scope(grant)
    else:
        g_scope = nullcontext()

    info.started = time.monotonic()
    c0 = dict(compiled.stats)
    # thread_time, not process_time: concurrent pool queries must not
    # inflate each other's cpu accounting
    cpu0 = time.thread_time()
    _sched.clear_thread_queued_ms()
    try:
        # the cancel token joins the query's supervision scope
        # (runtime/resilience.py): DELETE /v1/cancel sets it and the
        # execution layers abandon queued stages / orphan in-flight
        # compiles at their next checkpoint, instead of running to the end
        # behind a fut.cancel() that cannot stop a started future.
        # seat_scope hands the POST-time admission pre-claim to the
        # workload manager, which consumes its timestamp + priority.
        with tid_scope, g_scope, _sched.seat_scope(seat), \
                _res.query_scope(cancel=cancel):
            table = context.sql(sql, params=params)
    finally:
        if grant is not None:
            # a grant the query never consumed (DDL, pre-plan failure)
            # still holds a concurrency slot — give it back (idempotent:
            # a consumed grant was already released with its outcome)
            from ..runtime import tenancy as _ten
            _ten.get_registry().release(grant)
        info.cpu_sec = time.thread_time() - cpu0
        info.finished = time.monotonic()
        info.compiles = compiled.stats["compiles"] - c0["compiles"]
        info.cache_hits = compiled.stats["hits"] - c0["hits"]
        # measured queue time from the scheduler's own timestamps; a DDL
        # statement (no plan execution) leaves the seat unconsumed — give
        # its queue position back
        info.queued_ms = _sched.thread_queued_ms()
        _sched.get_manager().release_seat(seat)
        # the report of the trace that just closed ON THIS THREAD — the
        # per-query phase split concurrent queries cannot clobber
        report = _tel.last_report()
        if report is not None:
            info.phases = dict(report.phases)
            info.seq = report.seq
            cache = getattr(report, "cache", None) or {}
            info.cache_hit = bool(cache.get("hit"))
            info.cache_tier = cache.get("tier")
            info.subplan_cache_hits = int(cache.get("subplan_hits", 0))
            info.tier = getattr(report, "tier", None)
            info.program_store_hits = int(
                (report.counters or {}).get("program_store_hits", 0))
            info.operators = list(getattr(report, "operators", ()) or ())
    if table is not None and getattr(table, "num_columns", 0):
        info.rows = table.num_rows
        info.bytes = sum(int(getattr(c.data, "nbytes", 0))
                         for c in table.columns)
    try:
        import jax
        # sum peaks over EVERY local device: on a real mesh the query's
        # working set is sharded, so device 0 alone understates (or on an
        # idle coordinator, misses entirely) the true footprint
        peak = 0
        for d in jax.local_devices():
            try:
                mem = d.memory_stats() or {}
            except Exception:
                mem = {}
            peak += int(mem.get("peak_bytes_in_use", 0) or 0)
        info.peak_memory = peak
    except Exception as e:  # telemetry only; never fail the query over it
        logger.debug("memory_stats unavailable: %s", e)
    return table


_TYPE_MAP = {
    "BOOLEAN": "boolean", "TINYINT": "tinyint", "SMALLINT": "smallint",
    "INTEGER": "integer", "BIGINT": "bigint", "FLOAT": "real",
    "DOUBLE": "double", "DECIMAL": "decimal", "VARCHAR": "varchar",
    "CHAR": "char", "DATE": "date", "TIMESTAMP": "timestamp",
    "TIME": "time", "INTERVAL_DAY_TIME": "interval day to second",
    "INTERVAL_YEAR_MONTH": "interval year to month", "NULL": "unknown",
}


def _columns_payload(table) -> list:
    cols = []
    for name, col in zip(table.names, table.columns):
        t = _TYPE_MAP.get(col.stype.name, "varchar")
        cols.append({
            "name": name, "type": t,
            "typeSignature": {"rawType": t, "arguments": []},
        })
    return cols


def _data_payload(table) -> list:
    rows = []
    for row in table.to_pylist():
        out = []
        for v in row:
            if hasattr(v, "isoformat"):
                v = v.isoformat(sep=" ") if hasattr(v, "date") else v.isoformat()
            elif hasattr(v, "item"):
                v = v.item()
            out.append(v)
        rows.append(out)
    return rows


# ---------------------------------------------------------------------------
# result spooling (ISSUE 17): large finished results page through the
# SpillStore instead of riding one giant /v1/status payload
# ---------------------------------------------------------------------------

#: one SpillStore run per page — the store frees whole runs only, and
#: per-page runs are what lets "pages free as fetched" actually free
_RESULT_RUN_FMT = "__result__{uid}__p{page}"


class _Spool:
    """One spooled (paged) result.

    Page 0 goes out inline with the final ``/v1/status`` response (so
    the classic poll loop still sees columns+data); pages ``1..n-1``
    live in the SpillStore as JSON-encoded uint8 chunks — byte-exact
    with what ``_data_payload`` would have sent, and flushable to disk
    under the store's ordinary host budget.  ``next_page`` is the lowest
    page not yet freed: fetching page ``p`` frees everything below it
    (clients may retry the page they are on after a network hiccup), and
    the terminal page ``n`` carries no data, no ``nextUri``, and drops
    the spool."""

    __slots__ = ("uid", "columns", "pages", "page_bytes", "next_page",
                 "trace_id", "created", "last_access")

    def __init__(self, uid: str, columns: list, pages: int,
                 page_bytes: Dict[int, int],
                 trace_id: Optional[str] = None):
        self.uid = uid
        self.columns = columns
        self.pages = pages              # data pages (page 0 included)
        self.page_bytes = page_bytes    # stored page -> payload bytes
        self.next_page = 1              # page 0 served inline
        self.trace_id = trace_id
        self.created = time.monotonic()
        self.last_access = self.created

    def live_bytes(self) -> int:
        return sum(v for p, v in self.page_bytes.items()
                   if p >= self.next_page)

    def live_pages(self) -> int:
        return max(self.pages - self.next_page, 0)


def _spool_result(state: "_AppState", uid: str, table,
                  info: Optional[_QueryInfo]):
    """Spool ``table`` into pages; returns ``(spool, page0_rows)`` or
    None when the result is small enough / paging is off / the spool
    path faulted — the caller then serves the classic single-shot
    payload (degraded, never broken)."""
    pr = _page_rows()
    if (pr <= 0 or table is None or not getattr(table, "num_columns", 0)
            or int(table.num_rows) <= pr):
        return None
    import numpy as np
    from ..runtime import spill as _spill
    store = _spill.get_store()
    stored = []
    try:
        _faults.maybe_fail("result_spool")
        data = _data_payload(table)
        n_pages = (len(data) + pr - 1) // pr
        page_bytes: Dict[int, int] = {}
        for p in range(1, n_pages):
            chunk = data[p * pr:(p + 1) * pr]
            body = json.dumps(chunk, separators=(",", ":"),
                              default=str).encode()
            run = _RESULT_RUN_FMT.format(uid=uid, page=p)
            store.put_host(run, ["body"],
                           [(np.frombuffer(body, dtype=np.uint8).copy(),
                             None, "bytes", None)], rows=len(chunk))
            stored.append(run)
            page_bytes[p] = len(body)
    except Exception as e:
        for run in stored:
            store.free_run(run)
        logger.warning("result spool failed for %s (%s); serving the "
                       "unpaged response", uid, e)
        return None
    spool = _Spool(uid, _columns_payload(table), n_pages, page_bytes,
                   trace_id=getattr(info, "trace_id", None))
    with state.lock:
        state.spools[uid] = spool
    _tel.inc("result_spooled")
    _tel.inc("result_pages_spooled", len(stored))
    state.publish_spool_gauges()
    return spool, data[:pr]


# ---------------------------------------------------------------------------
# GET /v1/engine: one live snapshot of the whole engine
# ---------------------------------------------------------------------------

def _spill_section(counters: dict) -> dict:
    """Out-of-core occupancy for /v1/engine: store tiers (live bytes +
    device peak) plus the cumulative partition/flush counters, so an
    operator can tell a query is running out-of-core — and which tier is
    absorbing it — without attaching a profiler."""
    from ..runtime import spill as _spill

    stats = _spill.get_store().stats()
    return {
        "enabled": stats["enabled"],
        "runs": stats["runs"],
        "chunks": stats["chunks"],
        "deviceBytes": stats["device_bytes"],
        "hostBytes": stats["host_bytes"],
        "diskBytes": stats["disk_bytes"],
        "peakDeviceBytes": stats["peak_device_bytes"],
        "partitions": int(counters.get("spill_partitions", 0)),
        "flushes": int(counters.get("spill_flushes", 0)),
        "morselJoins": int(counters.get("morsel_joins", 0)),
    }


def _engine_snapshot(state: "_AppState") -> dict:
    """Everything an operator needs in one poll: in-flight queries with
    per-stage progress (flight recorder's live registry), scheduler queue
    depths, memory-ledger occupancy, cache tiers, quarantine verdicts,
    program-store stats, and the history ring's location."""
    from ..physical import tiering as _tiering
    from ..runtime import flight_recorder as _fr
    from ..runtime import program_store as _pstore
    from ..runtime import quarantine as _quar
    from ..runtime import result_cache as _rc

    mgr = _sched.get_manager()
    counters = _tel.REGISTRY.counters()
    with state.lock:
        server_queries = [
            {"id": uid,
             "state": ("FINISHED" if fut.done() else
                       "QUEUED" if (state.query_info.get(uid) is not None
                                    and state.query_info[uid].started is None)
                       else "RUNNING")}
            for uid, fut in state.future_list.items()]
    pstore = _pstore.get_store()
    qstore = _quar.get_store()
    out = {
        "pid": os.getpid(),
        "active": _fr.active_snapshot(),
        "serverQueries": server_queries,
        "scheduler": {
            "enabled": mgr.enabled(),
            "limit": mgr.limit(),
            "queueDepth": mgr.queue_depth(),
            "running": mgr.running_count(),
            "waiting": mgr.waiting_snapshot(),
            "draining": mgr.draining(),
        },
        "memory": {
            "budgetBytes": mgr.ledger.budget(),
            "reservedBytes": mgr.ledger.reserved_bytes(),
        },
        "cache": _rc.get_cache().stats(),
        "spill": _spill_section(counters),
        "quarantine": {
            "enabled": qstore.enabled(),
            "entries": len(qstore.entries()) if qstore.enabled() else 0,
        },
        "programStore": {
            "enabled": pstore.enabled(),
            "entries": len(pstore.entries()) if pstore.enabled() else 0,
            "bytes": pstore.total_bytes() if pstore.enabled() else 0,
        },
        "backgroundCompiles": {
            "inflight": len(_tiering.inflight_background_compiles()),
            "done": int(counters.get("background_compiles_done", 0)),
            "errors": int(counters.get("background_compile_errors", 0)),
        },
        "history": {
            "enabled": _fr.enabled(),
            "file": _fr.history_path() or "",
            "records": int(counters.get("history_records", 0)),
        },
        "devices": _devices_section(),
        "profile": _profile_section(),
        "slo": _slo_section(),
    }
    # feature-gated sections: absent with the kill switches thrown, so
    # DSQL_RESULT_PAGE_ROWS=0 / DSQL_TENANCY=0 keep /v1/engine pre-PR
    if _page_rows() > 0 or state.spools:
        out["results"] = state.spools_snapshot()
    if _tenancy_on():
        from ..runtime import tenancy as _ten
        out["tenants"] = _ten.get_registry().snapshot()
    if _fleet_on():
        from ..runtime import fleet as _fleet
        out["fleet"] = {"replica": _fleet.replica_id(),
                        "dir": _fleet.fleet_dir() or ""}
    if os.environ.get("DSQL_AUTOPILOT", "0").strip() not in ("", "0"):
        try:
            from ..runtime import autopilot as _ap
            out["autopilot"] = _ap.engine_section()
        except Exception:
            logger.debug("autopilot engine section failed", exc_info=True)
    if _ingest_on():
        try:
            from ..runtime import ingest as _ing
            out["ingest"] = _ing.engine_section(state.context)
        except Exception:
            logger.debug("ingest engine section failed", exc_info=True)
    return out


def _devices_section() -> list:
    """Per-local-device HBM rows (jax read directly — no profiler import,
    so the disabled-profiler zero-import guarantee holds for /v1/engine)."""
    rows = []
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        return rows
    for d in devices:
        try:
            mem = d.memory_stats() or {}
        except Exception:
            mem = {}
        rows.append({
            "id": int(getattr(d, "id", len(rows))),
            "platform": str(getattr(d, "platform", "")),
            "kind": str(getattr(d, "device_kind", "")),
            "bytesInUse": int(mem.get("bytes_in_use", 0) or 0),
            "peakBytesInUse": int(mem.get("peak_bytes_in_use", 0) or 0),
            "bytesLimit": int(mem.get("bytes_limit", 0) or 0),
        })
    return rows


def _profile_section() -> dict:
    """The device profiler's own stats — imported ONLY when armed."""
    if os.environ.get("DSQL_PROFILE", "0").strip() in ("", "0"):
        return {"enabled": False}
    try:
        from ..runtime import profiler as _prof
        return _prof.engine_section()
    except Exception as e:
        logger.debug("profiler section unavailable: %s", e)
        return {"enabled": False}


def _slo_section() -> dict:
    """Per-class SLO burn rates + live anomaly flags (runtime/events.py)
    — imported ONLY when the watchtower is armed, like the profiler."""
    if not _events_on():
        return {"enabled": False}
    try:
        from ..runtime import events as _ev
        return _ev.engine_section()
    except Exception as e:
        logger.debug("slo section unavailable: %s", e)
        return {"enabled": False}


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def _server_workers() -> int:
    """Worker-thread count: ``DSQL_SERVER_WORKERS``, defaulting to the
    workload manager's concurrency limit (the pool no longer needs its own
    magic width — the scheduler owns saturation policy; the pool just has
    to keep every grantable slot busy).  4 when the scheduler is off,
    matching the historical hardcoded pool."""
    raw = os.environ.get("DSQL_SERVER_WORKERS", "")
    try:
        if raw and int(raw) > 0:
            return int(raw)
    except ValueError:
        pass
    mgr = _sched.get_manager()
    return mgr.limit() if mgr.enabled() else 4


class _AppState:
    def __init__(self, context):
        self.context = context
        self.pool = ThreadPoolExecutor(max_workers=_server_workers())
        self.future_list: Dict[str, Future] = {}
        self.query_info: Dict[str, _QueryInfo] = {}
        self.cancel_events: Dict[str, threading.Event] = {}
        self.seats: Dict[str, _sched.Seat] = {}
        self.spools: Dict[str, _Spool] = {}
        self.lock = threading.Lock()
        self.drained = threading.Event()     # set when a drain completed
        # result/registry reaper (ISSUE 17): GCs never-collected results,
        # abandoned spools and their registry entries after
        # DSQL_RESULT_TTL_S — the fix for the historical future_list /
        # query_info / seats leak when a client submits and walks away
        self._reaper = threading.Thread(target=self._reap_loop,
                                        name="dsql-result-reaper",
                                        daemon=True)
        self._reaper.start()

    def forget(self, uid: str) -> tuple:
        """The one true cleanup for a query's registry entries — status
        collection, cancel, and the reaper all come through here (the
        4-line pop block used to be duplicated across the status paths).
        Hands an unconsumed admission seat back (idempotent) and returns
        ``(future, info, cancel_event)`` for callers that still need
        them — all None when the uid was already forgotten."""
        with self.lock:
            fut = self.future_list.pop(uid, None)
            info = self.query_info.pop(uid, None)
            cancel = self.cancel_events.pop(uid, None)
            seat = self.seats.pop(uid, None)
        _sched.get_manager().release_seat(seat)
        return fut, info, cancel

    # -- spool bookkeeping --------------------------------------------------
    def publish_spool_gauges(self) -> None:
        with self.lock:
            pages = sum(s.live_pages() for s in self.spools.values())
            nbytes = sum(s.live_bytes() for s in self.spools.values())
        _tel.REGISTRY.set_gauge("result_spool_pages", pages)
        _tel.REGISTRY.set_gauge("result_spool_bytes", nbytes)

    def advance_spool(self, uid: str, page: int) -> None:
        """The client fetched ``page``: every page below it was received,
        so free their SpillStore runs (pages free as fetched)."""
        with self.lock:
            spool = self.spools.get(uid)
            if spool is None:
                return
            lo = spool.next_page
            spool.next_page = max(spool.next_page, page)
        if lo < page:
            from ..runtime import spill as _spill
            store = _spill.get_store()
            for p in range(max(lo, 1), page):
                store.free_run(_RESULT_RUN_FMT.format(uid=uid, page=p))
        self.publish_spool_gauges()

    def drop_spool(self, uid: str) -> bool:
        """Free a spool and every page it still holds (terminal page,
        cancel, reaper)."""
        with self.lock:
            spool = self.spools.pop(uid, None)
        if spool is None:
            return False
        from ..runtime import spill as _spill
        store = _spill.get_store()
        for p in range(max(spool.next_page, 1), spool.pages):
            store.free_run(_RESULT_RUN_FMT.format(uid=uid, page=p))
        self.publish_spool_gauges()
        return True

    def spools_snapshot(self) -> dict:
        with self.lock:
            return {
                "enabled": _page_rows() > 0,
                "pageRows": _page_rows(),
                "ttlS": _result_ttl_s(),
                "spools": len(self.spools),
                "livePages": sum(s.live_pages()
                                 for s in self.spools.values()),
                "liveBytes": sum(s.live_bytes()
                                 for s in self.spools.values()),
            }

    # -- reaper -------------------------------------------------------------
    def _reap_loop(self) -> None:
        while not self.drained.wait(0.25):
            try:
                self.reap_once()
            except Exception:
                logger.exception("result reaper tick failed")

    def reap_once(self, now: Optional[float] = None) -> int:
        """One reaper tick: forget finished-but-never-collected queries
        and abandoned spools older than ``DSQL_RESULT_TTL_S``.  Returns
        how many entries were reaped (tests drive this directly)."""
        ttl = _result_ttl_s()
        if ttl <= 0:
            return 0
        now = time.monotonic() if now is None else now
        with self.lock:
            dead_spools = [uid for uid, s in self.spools.items()
                           if now - s.last_access > ttl]
            dead_queries = []
            for uid, fut in self.future_list.items():
                if not fut.done():
                    continue
                info = self.query_info.get(uid)
                done_at = getattr(info, "finished", None) or \
                    getattr(info, "submitted", None) or now
                if now - done_at > ttl:
                    dead_queries.append(uid)
        reaped = 0
        for uid in dead_queries:
            fut, _info, _cancel = self.forget(uid)
            if fut is not None:
                # consume the outcome so an abandoned failure does not
                # warn at interpreter shutdown
                try:
                    fut.exception(timeout=0)
                except Exception:
                    pass
                reaped += 1
                logger.info("reaped never-collected query %s", uid)
        for uid in dead_spools:
            if self.drop_spool(uid):
                reaped += 1
                logger.info("reaped abandoned result spool %s", uid)
        if reaped:
            _tel.inc("result_reaped", reaped)
        return reaped


# ---------------------------------------------------------------------------
# graceful drain (SIGTERM/SIGINT)
# ---------------------------------------------------------------------------

def _drain_and_shutdown(server, state: _AppState,
                        reason: str = "drain") -> None:
    """Drain this server, then stop it.

    New admissions are refused the instant the workload manager flips to
    draining (POST answers 503 + Retry-After); in-flight queries finish —
    and their results stay fetchable, the status poll deletes a query's
    entry only once the client collected it — within
    ``DSQL_DRAIN_TIMEOUT_S``.  Stragglers past the budget get TYPED
    cancellation (``QueryCancelled`` at their next checkpoint), never an
    abandoned thread.  The whole procedure runs under a ``drain`` span so
    the shutdown leaves a QueryReport behind, and it is itself a fault
    site (``drain``, runtime/faults.py) — an injected fault there is
    swallowed, because a broken drain step must never wedge process exit.
    """
    mgr = _sched.get_manager()
    timeout = _sched.drain_timeout_s()
    mgr.begin_drain()
    logger.warning("%s: draining server (timeout %.0f s, %d in flight)",
                   reason, timeout, len(state.future_list))
    if _events_on():
        try:
            from ..runtime import events as _ev
            _ev.publish("server.drain", reason=reason,
                        in_flight=len(state.future_list),
                        timeout_s=timeout)
        except Exception:
            pass
    try:
        with _tel.trace_scope(f"<drain:{reason}>"):
            with _tel.span("drain", reason=reason, timeout_s=timeout):
                try:
                    _faults.maybe_fail("drain")
                except Exception as e:
                    logger.warning(
                        "injected drain fault (%s); continuing shutdown", e)
                deadline = time.monotonic() + timeout
                while state.future_list and time.monotonic() < deadline:
                    time.sleep(0.05)
                stragglers = list(state.future_list.keys())
                if stragglers:
                    _tel.annotate(cancelled=len(stragglers))
                    logger.warning(
                        "drain timeout: typed-cancelling %d in-flight "
                        "quer%s", len(stragglers),
                        "y" if len(stragglers) == 1 else "ies")
                    for ev in list(state.cancel_events.values()):
                        ev.set()
                    grace = time.monotonic() + 2.0
                    while (any(not f.done()
                               for f in list(state.future_list.values()))
                           and time.monotonic() < grace):
                        time.sleep(0.05)
    finally:
        if _ingest_on():
            # micro-batched rows acked BUFFERED are not yet in the WAL;
            # a graceful drain commits them (WAL + apply) before the
            # process exits — only a crash may lose buffered (never
            # committed) batches
            try:
                from ..runtime import ingest as _ing
                log = _ing.get_log(state.context)
                if log is not None:
                    log.flush_all()
            except Exception:
                logger.exception("ingest flush during drain failed")
        try:
            server.shutdown()
            server.server_close()
        except Exception:
            logger.exception("server shutdown failed during drain")
        state.pool.shutdown(wait=False, cancel_futures=True)
        # reset the process-global flag: in production the process exits
        # right after; in tests this restores the shared manager
        mgr.end_drain()
        state.drained.set()
        logger.warning("drain complete; server stopped")


def install_drain_handlers(server) -> dict:
    """Install SIGTERM/SIGINT handlers that drain ``server`` gracefully.

    Only possible from the main thread (a ``signal`` module restriction);
    returns the previous handlers so a caller (tests) can restore them, or
    ``{}`` when installation was not possible.  The handler itself only
    SPAWNS the drain thread — signal context must stay non-blocking."""
    import signal

    state = server.app_state

    def handler(signum, frame):
        threading.Thread(
            target=_drain_and_shutdown,
            args=(server, state, signal.Signals(signum).name),
            daemon=True).start()

    prev: dict = {}
    try:
        for sig in (signal.SIGTERM, signal.SIGINT):
            prev[sig] = signal.signal(sig, handler)
    except ValueError:
        logger.debug("not the main thread; drain signal handlers not "
                     "installed (use server.drain_async())")
        return {}
    return prev


def _make_handler(state: _AppState, base_url: str):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            logger.debug("server: " + fmt, *args)

        def _send(self, code: int, payload: Optional[dict],
                  headers: Optional[dict] = None):
            body = json.dumps(payload or {}).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _req_trace(self) -> Optional[str]:
            """Sanitized client-supplied ``X-DSQL-Trace``, or None
            (always None with the watchtower off — no import)."""
            if not _events_on():
                return None
            from ..runtime import events as _ev
            return _ev.sanitize_trace_id(self.headers.get("X-DSQL-Trace"))

        def _trace_headers(self,
                           info: Optional[_QueryInfo] = None,
                           tid: Optional[str] = None) -> Optional[dict]:
            """``X-DSQL-Trace`` response header for EVERY wire path
            (success and the full ERROR_WIRE_MATRIX): the query's minted
            ID when known, else the client's echoed back.  None (no
            header at all) when the watchtower is off."""
            if not _events_on():
                return None
            tid = tid or (getattr(info, "trace_id", None)
                          if info is not None else None) or \
                self._req_trace()
            return {"X-DSQL-Trace": tid} if tid else None

        # GET /metrics | GET /v1/engine | GET /v1/empty | GET /v1/status/{uuid}
        def do_GET(self):
            if self.path.rstrip("/").split("?")[0] == "/metrics":
                # Prometheus text exposition of the engine's telemetry
                # registry: the same counters previously only reachable
                # in-process via physical.compiled.stats.  With a fleet
                # dir armed every series carries a replica label, so a
                # scraper summing across replicas never mixes series
                labels = None
                if _fleet_on():
                    from ..runtime import fleet as _fleet
                    labels = {"replica": _fleet.replica_id()}
                body = _tel.REGISTRY.render_prometheus(labels).encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if self.path.rstrip("/").split("?")[0] == "/v1/engine":
                try:
                    payload = _engine_snapshot(state)
                except Exception:
                    logger.exception("/v1/engine snapshot failed")
                    self._send(500, {"error": "snapshot failed"})
                    return
                self._send(200, payload)
                return
            if (self.path.rstrip("/").split("?")[0] == "/v1/fleet"
                    and _fleet_on()):
                # the aggregated fleet snapshot (runtime/fleet.py):
                # per-replica heartbeat rows + fleet-wide sums + merged
                # SLO + promoted anomalies.  Unset fleet dir falls
                # through to the generic 404 — byte-identical wire.
                try:
                    from ..runtime import fleet as _fleet
                    payload = _fleet.snapshot()
                except Exception:
                    logger.exception("/v1/fleet snapshot failed")
                    self._send(500, {"error": "fleet snapshot failed"})
                    return
                self._send(200, payload)
                return
            if (self.path.rstrip("/").split("?")[0] == "/v1/events"
                    and _events_on()):
                # live event streaming: JSON lines newer than ?cursor=,
                # long-polling up to ?timeout_ms= for the first arrival.
                # With the watchtower off this path falls through to the
                # generic 404 below — byte-identical pre-PR behavior.
                self._serve_events()
                return
            if self.path.rstrip("/") == "/v1/empty":
                self._send(200, {
                    "id": "empty", "infoUri": base_url,
                    "columns": [], "data": [], "stats": _stats("FINISHED"),
                })
                return
            if self.path.startswith("/v1/status/"):
                uid = self.path[len("/v1/status/"):].strip("/")
                fut = state.future_list.get(uid)
                if fut is None:
                    # a spooled result already collected its page 0: a
                    # re-poll answers FINISHED with columns and the
                    # nextUri of the lowest uncollected page (no data —
                    # rows travel on /v1/result only, once each)
                    with state.lock:
                        spool = state.spools.get(uid)
                    if spool is not None:
                        spool.last_access = time.monotonic()
                        self._send(200, {
                            "id": uid, "infoUri": base_url,
                            "nextUri": (f"{base_url}/v1/result/{uid}/"
                                        f"{spool.next_page}"),
                            "columns": spool.columns,
                            "stats": _stats("FINISHED"),
                        }, headers=self._trace_headers(
                            tid=spool.trace_id))
                        return
                    self._send(404, _error_payload("Unknown query id", uid),
                               headers=self._trace_headers())
                    return
                info = state.query_info.get(uid)
                if not fut.done():
                    self._send(200, {
                        "id": uid, "infoUri": base_url,
                        "nextUri": f"{base_url}/v1/status/{uid}",
                        "partialCancelUri": f"{base_url}/v1/cancel/{uid}",
                        "stats": _stats("RUNNING", info),
                    }, headers=self._trace_headers(info))
                    return
                try:
                    table = fut.result()
                except Exception as e:
                    state.forget(uid)
                    _tel.inc("server_query_errors")
                    self._send(200, _error_payload(str(e), uid, exc=e),
                               headers=self._trace_headers(info))
                    return
                # encode: the result table into the wire's rows.  The
                # query's trace closed on the worker's thread, so this is
                # a phase of its own and a ``dsql:encode`` event carrying
                # the query's seq, timed before _stats builds phaseMillis
                columns = data = None
                t0 = time.monotonic_ns()
                with _tel.annotation(
                        "encode", seq=getattr(info, "seq", None) or 0,
                        rows=getattr(info, "rows", 0),
                        bytes=getattr(info, "bytes", 0)):
                    spooled = _spool_result(state, uid, table, info)
                    if spooled is None and table is not None \
                            and table.num_columns:
                        columns = _columns_payload(table)
                        data = _data_payload(table)
                if info is not None:
                    info.phases["encode"] = (info.phases.get("encode", 0.0)
                                             + (time.monotonic_ns() - t0)
                                             / 1e6)
                state.forget(uid)
                if spooled is not None:
                    # page 0 inline + a REAL nextUri: the rest of the
                    # result pages through GET /v1/result/{uid}/{page}
                    spool, page0 = spooled
                    self._send(200, {
                        "id": uid, "infoUri": base_url,
                        "nextUri": f"{base_url}/v1/result/{uid}/1",
                        "columns": spool.columns,
                        "data": page0,
                        "stats": _stats("FINISHED", info),
                    }, headers=self._trace_headers(info))
                    return
                payload = {
                    "id": uid, "infoUri": base_url,
                    "stats": _stats("FINISHED", info),
                }
                if columns is not None:
                    payload["columns"] = columns
                    payload["data"] = data
                self._send(200, payload,
                           headers=self._trace_headers(info))
                return
            if self.path.startswith("/v1/result/"):
                parts = self.path[len("/v1/result/"):].strip("/").split("/")
                page = -1
                if len(parts) == 2:
                    try:
                        page = int(parts[1])
                    except ValueError:
                        page = -1
                if page < 0:
                    self._send(404, {"error": "not found"})
                    return
                self._serve_result_page(parts[0], page)
                return
            self._send(404, {"error": "not found"})

        def _serve_result_page(self, uid: str, page: int):
            """GET /v1/result/{uid}/{page}: one spooled page.  Pages are
            served in order; fetching page p frees every page below it,
            a page below ``next_page`` is 410 Gone (collected and
            freed), and the terminal page (== page count) answers empty
            data with no nextUri and drops the spool."""
            with state.lock:
                spool = state.spools.get(uid)
            if spool is None:
                self._send(404, _error_payload(
                    "Unknown or expired result id", uid),
                    headers=self._trace_headers())
                return
            spool.last_access = time.monotonic()
            hdrs = self._trace_headers(tid=spool.trace_id)
            if page < spool.next_page or page > spool.pages:
                self._send(410, _error_payload(
                    f"result page {page} of {uid} already collected "
                    f"(pages free as fetched; next is "
                    f"{spool.next_page})", uid), headers=hdrs)
                return
            if page == spool.pages:
                # terminal page: no data, no nextUri — the client has
                # everything, free whatever is left
                state.drop_spool(uid)
                _tel.inc("result_pages_served")
                self._send(200, {
                    "id": uid, "infoUri": base_url,
                    "columns": spool.columns, "data": [],
                    "stats": _stats("FINISHED"),
                }, headers=hdrs)
                return
            from ..runtime import spill as _spill
            try:
                _names, cols = _spill.get_store().get_host_cols(
                    _RESULT_RUN_FMT.format(uid=uid, page=page), 0)
                rows = json.loads(cols[0][0].tobytes().decode())
            except Exception as e:
                logger.exception("result page fetch failed: %s/%d",
                                 uid, page)
                self._send(500, _error_payload(
                    f"result page fetch failed: {e}", uid, exc=e),
                    headers=hdrs)
                return
            state.advance_spool(uid, page)
            _tel.inc("result_pages_served")
            self._send(200, {
                "id": uid, "infoUri": base_url,
                "nextUri": f"{base_url}/v1/result/{uid}/{page + 1}",
                "columns": spool.columns, "data": rows,
                "stats": _stats("FINISHED"),
            }, headers=hdrs)

        def _serve_events(self):
            """GET /v1/events?cursor=N&timeout_ms=M&limit=K — newline-
            delimited JSON events with ``seq > cursor``; the next cursor
            travels in ``X-DSQL-Cursor`` (and on each event's ``seq``).
            A draining process answers immediately with whatever is
            buffered instead of holding the long-poll open.

            ``?fleet=1`` (fleet dir armed) switches to the MERGED
            cross-replica stream (runtime/fleet.py): events from every
            replica's ring k-way-merged in timestamp order, cursored by
            the composite ``replica:seq;...`` string instead of one
            integer."""
            from urllib.parse import parse_qs, urlparse
            from ..runtime import events as _ev

            q = parse_qs(urlparse(self.path).query)

            def qint(name: str, default: int) -> int:
                try:
                    return int(q.get(name, [default])[0])
                except (ValueError, TypeError, IndexError):
                    return default

            limit = min(max(qint("limit", 500), 1), 5000)
            timeout_s = min(max(qint("timeout_ms", 0), 0) / 1e3, 30.0)
            if _sched.get_manager().draining():
                timeout_s = 0.0
            fleet_mode = (q.get("fleet", ["0"])[0] not in ("", "0")
                          and _fleet_on())
            if fleet_mode:
                from ..runtime import fleet as _fleet
                raw_cursor = q.get("cursor", [""])[0]
                evs, nxt = _fleet.read_merged_since(
                    raw_cursor, limit=limit, timeout_s=timeout_s)
            else:
                cursor = max(qint("cursor", 0), 0)
                evs, nxt = _ev.read_since(cursor, limit=limit,
                                          timeout_s=timeout_s)
            body = b"".join(
                json.dumps(e, separators=(",", ":"), default=str).encode()
                + b"\n" for e in evs)
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Content-Length", str(len(body)))
            self.send_header("X-DSQL-Cursor", str(nxt))
            self.end_headers()
            self.wfile.write(body)

        # POST /v1/statement | POST /v1/ingest (armed subsystems only)
        def do_POST(self):
            if self.path.rstrip("/") == "/v1/ingest" and _ingest_on():
                self._do_ingest()
                return
            if self.path.rstrip("/") != "/v1/statement":
                self._send(404, {"error": "not found"})
                return
            length = int(self.headers.get("Content-Length", 0))
            sql = self.rfile.read(length).decode()
            _tel.inc("server_queries")
            uid = str(uuid_mod.uuid4())
            # JSON envelope with server-side parameters: a
            # ``Content-Type: application/json`` body of
            # ``{"sql": "...", "params": [...]}`` binds positional ?/$n
            # markers (Context.sql(params=...)); a plain body stays the
            # raw SQL text it always was
            params = None
            ctype = (self.headers.get("Content-Type") or "")
            if ctype.split(";")[0].strip().lower() == "application/json":
                try:
                    payload = json.loads(sql)
                    sql = payload["sql"]
                    params = payload.get("params")
                except (ValueError, TypeError, KeyError):
                    _tel.inc("server_query_errors")
                    self._send(400, _error_payload(
                        'Invalid JSON statement body (expected '
                        '{"sql": "...", "params": [...]})', uid),
                        headers=self._trace_headers())
                    return
                if params is not None and not isinstance(params, list):
                    _tel.inc("server_query_errors")
                    self._send(400, _error_payload(
                        '"params" must be a JSON array', uid),
                        headers=self._trace_headers())
                    return
            mgr = _sched.get_manager()
            # watchtower ingress: honor the client's X-DSQL-Trace or mint
            # one HERE, before any verdict, so success AND every
            # ERROR_WIRE_MATRIX path return the same correlation ID.
            # tid stays None with DSQL_EVENTS off (no header emitted).
            tid = None
            if _events_on():
                from ..runtime import events as _ev
                tid = self._req_trace() or _ev.mint_trace_id()

            def reject(e: _res.AdmissionRejected) -> None:
                hdrs = {"Retry-After":
                        str(max(int(math.ceil(e.retry_after_s)), 1))}
                hdrs.update(self._trace_headers(tid=tid) or {})
                if tid:
                    from ..runtime import events as _ev
                    _ev.publish("server.rejected", trace=tid,
                                error=type(e).__name__,
                                retry_after_s=round(e.retry_after_s, 3))
                self._send(submit_status(e), _error_payload(str(e), uid,
                                                            exc=e),
                           headers=hdrs)

            # drain gate first (independent of the scheduler subsystem
            # being enabled): a draining process refuses new work with 503
            # so the load balancer retries elsewhere, while GET/DELETE keep
            # serving in-flight queries to completion
            if mgr.draining():
                _tel.inc("server_drain_rejects")
                reject(mgr._drain_verdict())
                return
            # tenant pre-claim FIRST (runtime/tenancy.py, X-DSQL-Tenant
            # header): a tenant over its rate/concurrency quota or with
            # an open circuit gets its typed 429 before a scheduler seat
            # or queue position is spent on it.  grant stays None with
            # DSQL_TENANCY=0 (no import — wire byte-identical).
            grant = None
            if _tenancy_on():
                from ..runtime import tenancy as _ten
                try:
                    grant = _ten.get_registry().claim(
                        self.headers.get("X-DSQL-Tenant"))
                except _res.AdmissionRejected as e:
                    _tel.inc("server_throttled")
                    reject(e)
                    return
            # admission pre-claim at POST time: when every slot AND queue
            # position is taken the client gets an immediate 429 with a
            # Retry-After hint, instead of the query disappearing into an
            # unbounded thread-pool backlog
            priority = _sched.normalize_priority(
                self.headers.get("X-DSQL-Priority"))
            try:
                seat = mgr.claim_seat(priority)
            except _res.AdmissionRejected as e:
                if grant is not None:
                    from ..runtime import tenancy as _ten
                    _ten.get_registry().release(grant)
                _tel.inc("server_drain_rejects"
                         if isinstance(e, _res.ServerDraining)
                         else "server_throttled")
                reject(e)
                return
            info = _QueryInfo()
            info.trace_id = tid
            cancel = threading.Event()
            state.query_info[uid] = info
            state.cancel_events[uid] = cancel
            if seat is not None:
                state.seats[uid] = seat
            fut = state.pool.submit(_run_tracked, state.context, sql, info,
                                    cancel, seat, tid, params, grant)
            state.future_list[uid] = fut
            self._send(200, {
                "id": uid, "infoUri": base_url,
                "nextUri": f"{base_url}/v1/status/{uid}",
                "partialCancelUri": f"{base_url}/v1/cancel/{uid}",
                "stats": _stats("QUEUED", info),
            }, headers=self._trace_headers(tid=tid))

        def _do_ingest(self):
            """POST /v1/ingest (runtime/ingest.py; route 404s unarmed):
            one WAL-committed append per request.  Body::

                {"table": "t", "rows": [[...], ...] | {"col": [...]},
                 "schema": "root"?}

            Tenant-tagged (X-DSQL-Tenant) and quota-governed exactly like
            a statement; the writer's typed verdicts ride the audited
            wire — 429 + Retry-After on quota/backpressure, 400 on a
            schema mismatch, 503 draining."""
            _tel.inc("server_ingest_requests")
            uid = str(uuid_mod.uuid4())
            tid = None
            if _events_on():
                from ..runtime import events as _ev
                tid = self._req_trace() or _ev.mint_trace_id()

            def reject(e: _res.AdmissionRejected) -> None:
                hdrs = {"Retry-After":
                        str(max(int(math.ceil(e.retry_after_s)), 1))}
                hdrs.update(self._trace_headers(tid=tid) or {})
                if tid:
                    from ..runtime import events as _ev
                    _ev.publish("server.rejected", trace=tid,
                                error=type(e).__name__,
                                retry_after_s=round(e.retry_after_s, 3))
                self._send(submit_status(e),
                           _error_payload(str(e), uid, exc=e), headers=hdrs)

            mgr = _sched.get_manager()
            if mgr.draining():
                _tel.inc("server_drain_rejects")
                reject(mgr._drain_verdict())
                return
            length = int(self.headers.get("Content-Length", 0))
            try:
                payload = json.loads(self.rfile.read(length).decode())
                table = payload["table"]
                rows = payload["rows"]
                schema_name = payload.get("schema") or None
                if not isinstance(rows, (list, dict)):
                    raise TypeError("rows must be a list or dict")
            except Exception:
                self._send(400, _error_payload(
                    'Invalid ingest body (expected {"table": "...", '
                    '"rows": [[...], ...] | {"col": [...]}, '
                    '"schema": "..."?})', uid),
                    headers=self._trace_headers(tid=tid))
                return
            grant = None
            if _tenancy_on():
                from ..runtime import tenancy as _ten
                try:
                    grant = _ten.get_registry().claim(
                        self.headers.get("X-DSQL-Tenant"))
                except _res.AdmissionRejected as e:
                    _tel.inc("server_throttled")
                    reject(e)
                    return
            outcome = None  # rejects feed neither breaker nor counts
            try:
                if isinstance(rows, list):
                    rows = [tuple(r) if isinstance(r, list) else r
                            for r in rows]
                n = state.context.append_rows(table, rows,
                                              schema_name=schema_name)
                outcome = "ok"
                self._send(200, {
                    "id": uid,
                    "table": table,
                    "state": "COMMITTED" if n else "BUFFERED",
                    "rows": int(n),
                    "epoch": state.context.table_epoch(
                        schema_name or state.context.schema_name,
                        str(table)),
                }, headers=self._trace_headers(tid=tid))
            except _res.AdmissionRejected as e:
                # backpressure/quota mid-commit: honest Retry-After
                _tel.inc("server_throttled")
                reject(e)
            except Exception as e:
                outcome = "error"
                err = _res.classify(e, default=_res.UserError)
                if err is None:  # control-flow: re-raise untouched
                    raise
                self._send(submit_status(err),
                           _error_payload(str(err), uid, exc=err),
                           headers=self._trace_headers(tid=tid))
            finally:
                if grant is not None:
                    from ..runtime import tenancy as _ten
                    _ten.get_registry().release(grant, outcome=outcome)

        # DELETE /v1/cancel/{uuid}
        def do_DELETE(self):
            if self.path.startswith("/v1/cancel/"):
                uid = self.path[len("/v1/cancel/"):].strip("/")
                # forget() pops every registry dict and hands an
                # unconsumed admission pre-claim back (a query cancelled
                # while still in the pool backlog never reaches
                # _run_tracked — its seat must not hold a queue position
                # forever; idempotent: a consumed seat is a no-op)
                fut, info, cancel = state.forget(uid)
                # a cancel can also target a spooled result mid-page:
                # drop the spool and free its remaining pages
                dropped = state.drop_spool(uid)
                if fut is None and not dropped:
                    self._send(404, _error_payload("Unknown query id", uid),
                               headers=self._trace_headers())
                    return
                if fut is None:
                    _tel.inc("server_cancels")
                    self._send(200, None, headers=self._trace_headers())
                    return
                # REAL cancellation, not just fut.cancel() (which is a
                # no-op once the future started): the cancel token makes
                # the running query raise QueryCancelled at its next
                # checkpoint — queued stages are abandoned and in-flight
                # compiles orphaned (physical/stage_exec.py)
                if cancel is not None:
                    cancel.set()
                fut.cancel()
                _tel.inc("server_cancels")
                tid = getattr(info, "trace_id", None)
                if tid and _events_on():
                    from ..runtime import events as _ev
                    _ev.publish("server.cancel", trace=tid, id=uid)
                self._send(200, None, headers=self._trace_headers(tid=tid))
                return
            self._send(404, {"error": "not found"})

    return Handler


def _error_payload(message: str, uid: str, exc: Exception = None) -> dict:
    """reference responses.py:119-139 ErrorResults shape: the reference's
    QueryError fills errorLocation from the parse error's position
    (``error.from_line + 1``/``from_col + 1``); our ParsingException
    carries 1-based (line, col) directly.

    Failures ride the typed taxonomy (runtime/resilience.py) onto the
    wire: ``errorType`` is USER_ERROR / INTERNAL_ERROR /
    INSUFFICIENT_RESOURCES and ``errorCode``/``errorName`` carry the
    classified verdict (EXCEEDED_TIME_LIMIT, EXCEEDED_MEMORY_LIMIT,
    USER_CANCELED, TRANSIENT_ERROR, ...) — not a stringified exception.
    Unrecognized exceptions escaping ``Context.sql`` classify as user
    errors at this boundary, preserving the reference's errorName
    (``str(type(exc))``) for them."""
    line = getattr(exc, "line", None)
    col = getattr(exc, "col", None)
    error_type, error_code = "USER_ERROR", 0
    error_name = str(type(exc)) if exc is not None else "GENERIC_ERROR"
    if exc is not None:
        err = _res.classify(exc, default=_res.UserError)
        if isinstance(err, _res.ResilienceError):
            error_type = err.error_type
            error_code = err.error_code
            if (isinstance(err, (_res.TransientError, _res.FatalError,
                                 _res.DeadlineExceeded, _res.QueryCancelled))
                    or err is exc):
                # engine verdicts use the taxonomy name; wrapped user
                # exceptions keep their own class name (reference shape)
                error_name = err.error_name
    return {
        "id": uid, "infoUri": "", "stats": _stats("FAILED"),
        "error": {
            "message": message, "errorCode": error_code,
            "errorName": error_name,
            "errorType": error_type,
            "errorLocation": {
                "lineNumber": line if isinstance(line, int) else 1,
                "columnNumber": col if isinstance(col, int) else 1,
            },
        },
    }


def run_server(context=None, host: str = "0.0.0.0", port: int = 8080,
               startup: bool = False, log_level=None, blocking: bool = True):
    """Start the SQL server (reference server/app.py:97-183).

    With ``blocking=False`` returns the (started) server object for tests.
    """
    if log_level:
        logging.basicConfig(level=log_level)
    from ..context import Context

    # fleet plane: arm before serving so the heartbeat registers this
    # replica even when an embedder passed a pre-built context (the
    # Context.__init__ hook is idempotent with this one)
    if _fleet_on():
        from ..runtime import fleet as _fleet
        _fleet.ensure_armed()
    context = context or Context()
    # continuous ingestion: arm on the serving context before the first
    # request — opens the WAL, replays committed batches for registered
    # tables, starts the micro-batch flusher (idempotent with the
    # Context.__init__ hook; env checked before the import)
    if _ingest_on():
        from ..runtime import ingest as _ing
        _ing.ensure_armed(context)
    if startup:
        context.sql("SELECT 1 + 1")

    state = _AppState(context)
    # bind first so port=0 (ephemeral) yields correct nextUri links
    server = ThreadingHTTPServer((host, port), _make_handler(state, ""))
    base_url = f"http://{host}:{server.server_port}"
    server.RequestHandlerClass = _make_handler(state, base_url)
    server.app_state = state
    # drain surface for embedders/tests (the signal handlers below call
    # the same procedure): returns immediately; state.drained (also
    # exposed as server.drained_event) is set when the drain completed
    server.drain_async = lambda reason="drain": threading.Thread(
        target=_drain_and_shutdown, args=(server, state, reason),
        daemon=True).start()
    server.drained_event = state.drained
    context.server = server
    if not blocking:
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        return server
    install_drain_handlers(server)
    try:
        logger.info("dask-sql-tpu server listening on %s", base_url)
        server.serve_forever()
    except KeyboardInterrupt:
        server.shutdown()
    return server


def main():  # pragma: no cover - console entry
    import argparse

    parser = argparse.ArgumentParser(description="dask-sql-tpu presto server")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8080)
    parser.add_argument("--startup", action="store_true")
    parser.add_argument("--log-level", default=None)
    args = parser.parse_args()
    run_server(host=args.host, port=args.port, startup=args.startup,
               log_level=args.log_level)


if __name__ == "__main__":  # pragma: no cover
    main()
