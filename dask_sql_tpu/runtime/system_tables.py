"""The read-only ``system`` schema: the engine introspected through its own
SQL.

Six virtual tables, each BUILT FRESH at name-resolution time
(context.resolve_table) from live process state and the flight-recorder
ring — never persisted in the catalog, never cacheable
(result_cache._canon_rel marks ``system`` scans volatile so they can't
occupy result-cache budget or interact with catalog epochs):

- ``system.queries``     persistent query history (the JSONL ring)
- ``system.active``      in-flight queries + scheduler queue + background
                         compiles, with phase/tier/per-stage progress
- ``system.metrics``     the telemetry registry (counters + gauges)
- ``system.cache``       result-cache entries with tier/bytes/hits
- ``system.quarantine``  standing compiler-crash verdicts
- ``system.programs``    persistent program-store index
- ``system.devices``     per-local-device HBM in-use/peak/limit
- ``system.events``      watchtower event bus ring (DSQL_EVENTS armed;
                         all replicas' rings merged when DSQL_FLEET_DIR
                         is armed, each row stamped with its replica)
- ``system.slo``         per-class latency objectives + burn rates
- ``system.replicas``    fleet heartbeat registry (DSQL_FLEET_DIR armed)
- ``system.compiles``    the programs this process obtained by compiling
                         (``telemetry.compile_log()``): cause, XLA-cache
                         verdict, phases, first run

Every table has a FIXED column schema with explicit dtypes so an empty
engine still binds and executes ``SELECT * FROM system.queries`` — object
columns stay object, numeric columns stay float64/int64 at zero rows.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from ..table import Table

TABLE_NAMES = ("queries", "active", "metrics", "cache", "quarantine",
               "programs", "table_stats", "mesh", "spill", "devices",
               "matviews", "view_candidates", "events", "slo", "prepared",
               "tenants", "replicas", "autopilot", "compiles")


def _fleet_on() -> bool:
    """Fleet-plane gate (runtime/fleet.py): env checked BEFORE any
    import, like ``_events``/``_slo`` below — with ``DSQL_FLEET_DIR``
    unset the module stays out of sys.modules and the fleet tables
    yield their fixed empty schemas."""
    import os

    return bool(os.environ.get("DSQL_FLEET_DIR"))


def _col(rows: List[dict], key: str, dtype, default):
    vals = []
    for r in rows:
        v = r.get(key)
        vals.append(default if v is None else v)
    if dtype is object:
        if not vals:
            # an empty object array crashes host_encode_numpy's null scan;
            # an empty unicode array types as VARCHAR just the same
            return np.array([], dtype="U1")
        return np.array([str(v) for v in vals], dtype=object)
    return np.array(vals, dtype=dtype)


def _queries() -> Table:
    from . import flight_recorder as _fr

    if _fleet_on():
        # fleet mode: every replica's envelope ring merged in timestamp
        # order, each row stamped with its replica (runtime/fleet.py)
        from . import fleet as _fleet

        rows = _fleet.merged_query_rows()
    else:
        rows = _fr.read_events(kind="query")
    return Table.from_pydict({
        "replica": _col(rows, "replica", object, ""),
        "unix": _col(rows, "unix", np.float64, 0.0),
        "pid": _col(rows, "pid", np.int64, 0),
        "query": _col(rows, "query", object, ""),
        "outcome": _col(rows, "outcome", object, ""),
        "error": _col(rows, "error", object, ""),
        "wall_ms": _col(rows, "wall_ms", np.float64, 0.0),
        "tier": _col(rows, "tier", object, ""),
        "priority": _col(rows, "priority", object, ""),
        "cache_hit": _col(rows, "cache_hit", np.bool_, False),
        "tenant": _col(rows, "tenant", object, ""),
        "rows_out": _col(rows, "rows_out", np.int64, 0),
        "bytes_out": _col(rows, "bytes_out", np.int64, 0),
        "measured_bytes": _col(rows, "measured_bytes", np.int64, 0),
        "est_bytes": _col(rows, "est_bytes", np.int64, 0),
        "est_source": _col(rows, "est_source", object, ""),
        "queued_ms": _col(rows, "queued_ms", np.float64, 0.0),
        "plan_fp": _col(rows, "plan_fp", object, ""),
        # adaptive operator choices, "; "-joined record_choice lines
        # ("groupby=dense rows=... ndv=..."); older envelopes lack the
        # field and render empty
        "operators": _col([{"operators": "; ".join(r.get("operators")
                                                   or [])}
                           for r in rows], "operators", object, ""),
        # device-profile fields (runtime/profiler.py): worst shard skew,
        # collective bytes by kind, and the cost-model error (-1 = not
        # profiled / no prediction); older envelopes render the defaults
        "skew_ratio": _col(rows, "skew_ratio", np.float64, 0.0),
        "all_to_all_bytes": _col(
            [{"v": (r.get("collective_bytes") or {}).get("all_to_all", 0)}
             for r in rows], "v", np.int64, 0),
        "all_gather_bytes": _col(
            [{"v": (r.get("collective_bytes") or {}).get("all_gather", 0)}
             for r in rows], "v", np.int64, 0),
        "psum_bytes": _col(
            [{"v": (r.get("collective_bytes") or {}).get("psum", 0)}
             for r in rows], "v", np.int64, 0),
        "cost_err": _col(rows, "cost_err", np.float64, -1.0),
    })


def _active() -> Table:
    import os

    from ..physical import tiering as _tiering
    from . import flight_recorder as _fr
    from . import scheduler as _sched

    rows: List[dict] = []
    for a in _fr.active_snapshot():
        rows.append({"state": "running", "query": a["query"],
                     "phase": a["phase"], "tier": a["tier"],
                     "priority": a["priority"],
                     "elapsed_ms": a["elapsedMillis"], "est_bytes": 0,
                     "stages_done": a["stagesDone"],
                     "stages_total": a["stagesTotal"], "pid": a["pid"]})
    for w in _sched.get_manager().waiting_snapshot():
        rows.append({"state": "queued", "query": "", "phase": "queued",
                     "tier": "", "priority": w["priority"],
                     "elapsed_ms": w["waitedMillis"],
                     "est_bytes": w["estBytes"], "stages_done": 0,
                     "stages_total": 0, "pid": os.getpid()})
    for fp in _tiering.inflight_background_compiles():
        rows.append({"state": "bg-compile",
                     "query": f"<background-compile:{fp[:32]}>",
                     "phase": "compile", "tier": "background",
                     "priority": "", "elapsed_ms": 0.0, "est_bytes": 0,
                     "stages_done": 0, "stages_total": 0,
                     "pid": os.getpid()})
    return Table.from_pydict({
        "state": _col(rows, "state", object, ""),
        "query": _col(rows, "query", object, ""),
        "phase": _col(rows, "phase", object, ""),
        "tier": _col(rows, "tier", object, ""),
        "priority": _col(rows, "priority", object, ""),
        "elapsed_ms": _col(rows, "elapsed_ms", np.float64, 0.0),
        "est_bytes": _col(rows, "est_bytes", np.int64, 0),
        "stages_done": _col(rows, "stages_done", np.int64, 0),
        "stages_total": _col(rows, "stages_total", np.int64, 0),
        "pid": _col(rows, "pid", np.int64, 0),
    })


def _metrics() -> Table:
    from . import telemetry as _tel

    snap = _tel.REGISTRY.snapshot()
    rows = [{"name": k, "kind": "counter", "value": float(v)}
            for k, v in sorted(snap["counters"].items())]
    rows += [{"name": k, "kind": "gauge", "value": float(v)}
             for k, v in sorted(snap["gauges"].items())]
    return Table.from_pydict({
        "name": _col(rows, "name", object, ""),
        "kind": _col(rows, "kind", object, ""),
        "value": _col(rows, "value", np.float64, 0.0),
    })


def _cache() -> Table:
    from . import result_cache as _rc

    rows = _rc.get_cache().entries_snapshot()
    return Table.from_pydict({
        "key": _col(rows, "key", object, ""),
        "tier": _col(rows, "tier", object, ""),
        "nbytes": _col(rows, "nbytes", np.int64, 0),
        "hits": _col(rows, "hits", np.int64, 0),
        "tables": _col(rows, "tables", object, ""),
    })


def _quarantine() -> Table:
    from . import quarantine as _quar

    rows = [{"key": k, **(e if isinstance(e, dict) else {})}
            for k, e in sorted(_quar.get_store().entries().items())]
    return Table.from_pydict({
        "key": _col(rows, "key", object, ""),
        "verdict": _col(rows, "verdict", object, ""),
        "reason": _col(rows, "reason", object, ""),
        "strikes": _col(rows, "strikes", np.int64, 0),
        "at": _col(rows, "at", np.float64, 0.0),
        "expires_at": _col(rows, "expires_at", np.float64, 0.0),
    })


def _programs() -> Table:
    from . import program_store as _pstore

    rows = [{"digest": d, **(e if isinstance(e, dict) else {})}
            for d, e in sorted(_pstore.get_store().entries().items())]
    return Table.from_pydict({
        "digest": _col(rows, "digest", object, ""),
        "nbytes": _col(rows, "bytes", np.int64, 0),
        "used_at": _col(rows, "used_at", np.float64, 0.0),
        "stored_at": _col(rows, "stored_at", np.float64, 0.0),
        # XLA cost prediction captured at store time (profiler armed);
        # zeros for entries stored without profiling
        "cost_flops": _col(rows, "cost_flops", np.float64, 0.0),
        "cost_bytes": _col(rows, "cost_bytes", np.float64, 0.0),
    })


def _table_stats(context=None) -> Table:
    """Ingest-time TableStats (runtime/statistics.py) for every resident
    catalog table: one row per column with NDV / min / max / null fraction
    / dense-domain flags — the numbers adaptive operator selection runs
    on.  Needs the resolving context (the catalog lives there); a
    context-less build yields the empty schema."""
    from . import statistics as _stats

    rows = _stats.system_rows(context) if context is not None else []
    return Table.from_pydict({
        "schema": _col(rows, "schema", object, ""),
        "table": _col(rows, "table", object, ""),
        "column": _col(rows, "column", object, ""),
        "rows": _col(rows, "rows", np.int64, 0),
        "ndv": _col(rows, "ndv", np.int64, -1),
        "min": _col(rows, "min", np.float64, float("nan")),
        "max": _col(rows, "max", np.float64, float("nan")),
        "null_frac": _col(rows, "null_frac", np.float64, 0.0),
        "is_int": _col(rows, "is_int", np.bool_, False),
        "dense": _col(rows, "dense", np.bool_, False),
        "domain": _col(rows, "domain", np.int64, -1),
        "increasing": _col(rows, "increasing", np.bool_, False),
        "collected_ms": _col(rows, "collected_ms", np.float64, 0.0),
    })


def _mesh(context=None) -> Table:
    """One row per visible device, with the context's mesh placement and
    whether the SPMD backend would serve queries on it (parallel/spmd.py
    spmd_enabled: a >=2-device mesh attached and DSQL_MESH != 0)."""
    import jax

    mesh = getattr(context, "mesh", None) if context is not None else None
    axis = ""
    mesh_size = 0
    enabled = False
    if mesh is not None:
        axis = "x".join(f"{n}:{s}" for n, s in
                        zip(mesh.axis_names, mesh.devices.shape))
        mesh_size = int(mesh.devices.size)
        mesh_ids = {d.id for d in mesh.devices.flat}
        from ..parallel.spmd import spmd_enabled
        enabled = spmd_enabled(context)
    else:
        mesh_ids = set()
    rows = []
    try:
        devices = jax.devices()
    except Exception:  # pragma: no cover
        devices = []
    for d in devices:
        rows.append({
            "device_id": int(d.id),
            "platform": str(getattr(d, "platform", "")),
            "kind": str(getattr(d, "device_kind", "")),
            "process": int(getattr(d, "process_index", 0)),
            "in_mesh": d.id in mesh_ids,
            "mesh_axes": axis,
            "mesh_size": mesh_size,
            "spmd_enabled": enabled,
        })
    return Table.from_pydict({
        "device_id": _col(rows, "device_id", np.int64, 0),
        "platform": _col(rows, "platform", object, ""),
        "kind": _col(rows, "kind", object, ""),
        "process": _col(rows, "process", np.int64, 0),
        "in_mesh": _col(rows, "in_mesh", np.bool_, False),
        "mesh_axes": _col(rows, "mesh_axes", object, ""),
        "mesh_size": _col(rows, "mesh_size", np.int64, 0),
        "spmd_enabled": _col(rows, "spmd_enabled", np.bool_, False),
    })


def _devices() -> Table:
    """Per-device HBM truth: one row per LOCAL device with live
    ``memory_stats()`` readings (bytes in use / peak / limit — zeros on
    backends without memory stats, e.g. CPU).  Deliberately reads jax
    directly rather than importing runtime.profiler, so querying
    ``system.devices`` keeps the profiler's zero-import guarantee when
    ``DSQL_PROFILE`` is off."""
    import jax

    rows: List[dict] = []
    try:
        devices = jax.local_devices()
    except Exception:  # pragma: no cover
        devices = []
    for d in devices:
        try:
            mem = d.memory_stats() or {}
        except Exception:
            mem = {}
        rows.append({
            "device_id": int(getattr(d, "id", len(rows))),
            "platform": str(getattr(d, "platform", "")),
            "kind": str(getattr(d, "device_kind", "")),
            "bytes_in_use": int(mem.get("bytes_in_use", 0) or 0),
            "peak_bytes_in_use": int(mem.get("peak_bytes_in_use", 0) or 0),
            "bytes_limit": int(mem.get("bytes_limit", 0) or 0),
        })
    return Table.from_pydict({
        "device_id": _col(rows, "device_id", np.int64, 0),
        "platform": _col(rows, "platform", object, ""),
        "kind": _col(rows, "kind", object, ""),
        "bytes_in_use": _col(rows, "bytes_in_use", np.int64, 0),
        "peak_bytes_in_use": _col(rows, "peak_bytes_in_use", np.int64, 0),
        "bytes_limit": _col(rows, "bytes_limit", np.int64, 0),
    })


def _spill() -> Table:
    """One row per live spill run (grace-hash partition / out-of-core join
    output), with its tier placement — a mid-query `SELECT * FROM
    system.spill` from a second connection shows exactly which partitions
    sit on device vs host vs disk.  Usually empty: runs are freed as each
    partition pair completes."""
    from . import spill as _spill_mod

    rows = _spill_mod.get_store().runs_snapshot()
    return Table.from_pydict({
        "run": _col(rows, "run", object, ""),
        "chunks": _col(rows, "chunks", np.int64, 0),
        "rows": _col(rows, "rows", np.int64, 0),
        "nbytes": _col(rows, "nbytes", np.int64, 0),
        "device_chunks": _col(rows, "device_chunks", np.int64, 0),
        "host_chunks": _col(rows, "host_chunks", np.int64, 0),
        "disk_chunks": _col(rows, "disk_chunks", np.int64, 0),
    })


def _prepared(context=None) -> Table:
    """One row per PREPARE-registered statement on the resolving context
    (physical/rel/custom.py): name, parameter count, and the statement
    text EXECUTE will bind."""
    reg = getattr(context, "_prepared", None) or {}
    rows = [{"name": name, "num_params": int(stmt.num_params),
             "statement": stmt.sql}
            for name, stmt in sorted(reg.items())]
    return Table.from_pydict({
        "name": _col(rows, "name", object, ""),
        "num_params": _col(rows, "num_params", np.int64, 0),
        "statement": _col(rows, "statement", object, ""),
    })


def _matviews(context=None) -> Table:
    """One row per registered materialized view (runtime/matview.py):
    maintainability verdict with the full-recompute reason, delta backlog,
    and the serve/refresh counters the acceptance criteria reconcile."""
    from . import matview as _mv

    rows = _mv.matview_rows(context) if context is not None else []
    return Table.from_pydict({
        "schema": _col(rows, "schema", object, ""),
        "name": _col(rows, "name", object, ""),
        "rows": _col(rows, "rows", np.int64, 0),
        "maintainable": _col(rows, "maintainable", object, ""),
        "reason": _col(rows, "reason", object, ""),
        "base_tables": _col(rows, "base_tables", object, ""),
        "pending_deltas": _col(rows, "pending_deltas", np.int64, 0),
        "pending_rows": _col(rows, "pending_rows", np.int64, 0),
        "staleness_s": _col(rows, "staleness_s", np.float64, 0.0),
        "serves": _col(rows, "serves", np.int64, 0),
        "refresh_incremental": _col(rows, "refresh_incremental",
                                    np.int64, 0),
        "refresh_full": _col(rows, "refresh_full", np.int64, 0),
        "last_refresh": _col(rows, "last_refresh", object, ""),
        "fingerprint": _col(rows, "fingerprint", object, ""),
    })


def _view_candidates(context=None) -> Table:
    """Hot repeated plan fingerprints from the flight recorder's EWMA
    history ranked by hits x recompute cost — the operator's shortlist of
    what to CREATE MATERIALIZED VIEW next.  Empty when the recorder
    (DSQL_HISTORY_FILE) is off."""
    from . import matview as _mv

    rows = _mv.view_candidate_rows(context) if context is not None else []
    return Table.from_pydict({
        "fingerprint": _col(rows, "fingerprint", object, ""),
        "hits": _col(rows, "hits", np.int64, 0),
        "ewma_ms": _col(rows, "ewma_ms", np.float64, 0.0),
        "score": _col(rows, "score", np.float64, 0.0),
        "materialized": _col(rows, "materialized", np.bool_, False),
        "example_sql": _col(rows, "example_sql", object, ""),
    })


def _events() -> Table:
    """Watchtower bus ring (runtime/events.py): one row per structured
    event, trace-correlatable with ``system.queries``.  Reads the env gate
    BEFORE importing events — with ``DSQL_EVENTS`` off this yields the
    fixed empty schema and the module stays un-imported."""
    import os

    rows: List[dict] = []
    if _fleet_on():
        # fleet mode: all replicas' event rings merged in timestamp
        # order — one trace id stitches across the replicas it touched
        from . import fleet as _fleet

        rows = _fleet.merged_events_rows()
    elif os.environ.get("DSQL_EVENTS", "0").strip() not in ("", "0"):
        from . import events as _ev

        rows = _ev.events_rows()
    return Table.from_pydict({
        "seq": _col(rows, "seq", np.int64, 0),
        "unix": _col(rows, "unix", np.float64, 0.0),
        "pid": _col(rows, "pid", np.int64, 0),
        "trace": _col(rows, "trace", object, ""),
        "type": _col(rows, "type", object, ""),
        "replica": _col(rows, "replica", object, ""),
        "detail": _col(rows, "detail", object, ""),
    })


def _slo() -> Table:
    """Per-priority-class latency objectives and their multi-window burn
    rates (runtime/events.py SloMonitor).  Same zero-import discipline as
    ``system.events`` — empty fixed schema when the watchtower is off."""
    import os

    rows: List[dict] = []
    if os.environ.get("DSQL_EVENTS", "0").strip() not in ("", "0"):
        from . import events as _ev

        rows = _ev.slo_rows()
    return Table.from_pydict({
        "class": _col(rows, "class", object, ""),
        "objective_ms": _col(rows, "objective_ms", np.float64, 0.0),
        "target": _col(rows, "target", np.float64, 0.0),
        "window_fast_s": _col(rows, "window_fast_s", np.float64, 0.0),
        "window_slow_s": _col(rows, "window_slow_s", np.float64, 0.0),
        "total": _col(rows, "total", np.int64, 0),
        "breaches": _col(rows, "breaches", np.int64, 0),
        "attainment": _col(rows, "attainment", np.float64, 1.0),
        "burn_fast": _col(rows, "burn_fast", np.float64, 0.0),
        "burn_slow": _col(rows, "burn_slow", np.float64, 0.0),
        "breach": _col(rows, "breach", np.bool_, False),
    })


def _tenants() -> Table:
    """Per-tenant admission accounting and circuit state
    (runtime/tenancy.py TenantRegistry).  Same env-gate-before-import
    discipline as ``system.events`` — ``DSQL_TENANCY=0`` yields the fixed
    empty schema and the module stays un-imported."""
    import os

    rows: List[dict] = []
    if os.environ.get("DSQL_TENANCY", "1").strip() not in ("", "0"):
        from . import tenancy as _ten

        rows = _ten.tenant_rows()
    return Table.from_pydict({
        "tenant": _col(rows, "tenant", object, ""),
        "inflight": _col(rows, "inflight", np.int64, 0),
        "tokens": _col(rows, "tokens", np.float64, 0.0),
        "submitted": _col(rows, "submitted", np.int64, 0),
        "admitted": _col(rows, "admitted", np.int64, 0),
        "completed": _col(rows, "completed", np.int64, 0),
        "failed": _col(rows, "failed", np.int64, 0),
        "quota_rejects": _col(rows, "quota_rejects", np.int64, 0),
        "circuit_rejects": _col(rows, "circuit_rejects", np.int64, 0),
        "circuit_opens": _col(rows, "circuit_opens", np.int64, 0),
        "consecutive_failures": _col(rows, "consecutive_failures",
                                     np.int64, 0),
        "circuit": _col(rows, "circuit", object, ""),
    })


def _replicas() -> Table:
    """One row per registered fleet replica (runtime/fleet.py heartbeat
    registry): identity, liveness (``alive`` = beat within TTL),
    scheduler/cache/spill occupancy, and the shared-warmth counters
    (program-store hits/misses/hit-rate per replica).  Same
    env-gate-before-import discipline as ``system.events`` — an unset
    ``DSQL_FLEET_DIR`` yields the fixed empty schema."""
    rows: List[dict] = []
    if _fleet_on():
        from . import fleet as _fleet

        rows = _fleet.replica_rows()
    return Table.from_pydict({
        "replica": _col(rows, "replica", object, ""),
        "pid": _col(rows, "pid", np.int64, 0),
        "host": _col(rows, "host", object, ""),
        "alive": _col(rows, "alive", np.bool_, False),
        "started": _col(rows, "started", np.float64, 0.0),
        "beat": _col(rows, "beat", np.float64, 0.0),
        "age_s": _col(rows, "age_s", np.float64, 0.0),
        "running": _col(rows, "running", np.int64, 0),
        "queue_depth": _col(rows, "queue_depth", np.int64, 0),
        "slots": _col(rows, "slots", np.int64, 0),
        "queries": _col(rows, "queries", np.int64, 0),
        "cache_bytes": _col(rows, "cache_bytes", np.int64, 0),
        "spill_bytes": _col(rows, "spill_bytes", np.int64, 0),
        "reserved_bytes": _col(rows, "reserved_bytes", np.int64, 0),
        "program_entries": _col(rows, "program_entries", np.int64, 0),
        "program_hits": _col(rows, "program_hits", np.int64, 0),
        "program_misses": _col(rows, "program_misses", np.int64, 0),
        "program_hit_rate": _col(rows, "program_hit_rate", np.float64, 0.0),
        "compiles": _col(rows, "compiles", np.int64, 0),
    })


def _autopilot() -> Table:
    """The autopilot's action journal (runtime/autopilot.py): one row per
    matview create/refresh/drop, re-plan hint record/verdict/revert, or
    faulted tick, newest last.  Same env-gate-before-import discipline as
    ``system.events`` — ``DSQL_AUTOPILOT=0`` yields the fixed empty
    schema and the module stays un-imported."""
    import os

    rows: List[dict] = []
    if os.environ.get("DSQL_AUTOPILOT", "0").strip() not in ("", "0"):
        from . import autopilot as _ap

        rows = _ap.journal_rows()
    return Table.from_pydict({
        "unix": _col(rows, "unix", np.float64, 0.0),
        "action": _col(rows, "action", object, ""),
        "trigger": _col(rows, "trigger", object, ""),
        "fingerprint": _col(rows, "fingerprint", object, ""),
        "verdict": _col(rows, "verdict", object, ""),
        "bytes": _col(rows, "bytes", np.int64, 0),
        "detail": _col(rows, "detail", object, ""),
    })


def _compiles() -> Table:
    """The last 256 ``compile`` spans of the process, oldest first
    (``telemetry.compile_log()``; query and background traces alike): why
    was this restart slow, which program was built twice.  ``xla_ms`` is
    the read of XLA's persistent cache where ``xla_cache`` is ``hit``;
    ``first_run_ms`` is -1 until that run's ``materialize`` closed."""
    from . import telemetry as _tel

    rows = _tel.compile_log()
    return Table.from_pydict({
        "t0_ns": _col(rows, "t0_ns", np.int64, 0),
        "program": _col(rows, "program", object, ""),
        "cause": _col(rows, "cause", object, ""),
        "round": _col(rows, "round", np.int64, 0),
        "caps": _col(rows, "caps", object, ""),
        "xla_cache": _col(rows, "xla_cache", object, ""),
        "background": _col(rows, "background", np.bool_, False),
        "trace_ms": _col(rows, "trace_ms", np.float64, 0.0),
        "lower_ms": _col(rows, "lower_ms", np.float64, 0.0),
        "xla_ms": _col(rows, "xla_ms", np.float64, 0.0),
        "first_run_ms": _col(rows, "first_run_ms", np.float64, -1.0),
        "wall_ms": _col(rows, "wall_ms", np.float64, 0.0),
        "error": _col(rows, "error", object, ""),
    })


_BUILDERS: Dict[str, object] = {
    "queries": _queries,
    "active": _active,
    "metrics": _metrics,
    "cache": _cache,
    "quarantine": _quarantine,
    "programs": _programs,
    "table_stats": _table_stats,
    "mesh": _mesh,
    "spill": _spill,
    "devices": _devices,
    "matviews": _matviews,
    "view_candidates": _view_candidates,
    "events": _events,
    "slo": _slo,
    "prepared": _prepared,
    "tenants": _tenants,
    "replicas": _replicas,
    "autopilot": _autopilot,
    "compiles": _compiles,
}

#: builders that need the resolving context (catalog / mesh live there)
_CONTEXT_BUILDERS = (_table_stats, _mesh, _matviews, _view_candidates,
                     _prepared)


def build(name: str, context=None) -> Optional[Table]:
    """A fresh snapshot Table for ``system.<name>``, or None for unknown
    names (the binder then reports the table as undefined)."""
    builder = _BUILDERS.get(name.lower())
    if builder is None:
        return None
    if builder in _CONTEXT_BUILDERS:
        return builder(context)  # type: ignore[operator]
    return builder()  # type: ignore[operator]
