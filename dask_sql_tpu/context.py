"""Context: the single user-facing object — catalog + SQL entry point.

API parity with the reference Context (/root/reference/dask_sql/context.py:36-826):
``create_table``, ``drop_table``, ``create_schema``, ``register_function``,
``register_aggregation``, ``register_model``, ``sql``, ``explain``, ``fqn``,
``ipython_magic``, ``run_server``.  Differences are intentional and TPU-native:
``sql`` returns a device-columnar ``Table`` (the analogue of the lazy dask
frame — data lives on device; ``.to_pandas()`` is the ``.compute()``
equivalent), and the planner is our native parser/binder/optimizer instead of
the JPype/Calcite bridge.
"""
from __future__ import annotations

import itertools
import logging
import os
import threading
from typing import Any, Callable, List, Optional, Tuple, Union

from .datacontainer import FunctionDescription, SchemaContainer, TableEntry
from .io.inputs import (
    ArrowInputPlugin, BaseInputPlugin, DeviceTableInputPlugin, DictInputPlugin,
    HiveInputPlugin, InputUtil, IntakeCatalogInputPlugin, LocationInputPlugin,
    PandasLikeInputPlugin,
)
from .plan.binder import Binder
from .plan.nodes import Field, RelNode
from .plan.optimizer import optimize
from .sql import ast as A
from .sql.parser import parse_sql
from .table import Table
from .types import SqlType, parse_type_name, sql_type_from_numpy
from .utils import ParsingException

logger = logging.getLogger(__name__)


def _tenancy_on() -> bool:
    # tenancy gate (runtime/tenancy.py): env checked BEFORE any import so
    # DSQL_TENANCY=0 keeps the module out of the process entirely
    return os.environ.get("DSQL_TENANCY", "1").strip() not in ("", "0")


def _ingest_on() -> bool:
    # continuous-ingestion gate (runtime/ingest.py): DSQL_INGEST_DIR arms,
    # DSQL_INGEST=0 kills — both checked BEFORE any import so the unarmed
    # write/read paths stay bit-for-bit baseline with the module absent
    return bool(os.environ.get("DSQL_INGEST_DIR")) and \
        os.environ.get("DSQL_INGEST", "1").strip() not in ("0", "false")


class Context:
    """Main entry point: holds schemas/tables/functions/models and runs SQL.

    Example (reference README):

        from dask_sql_tpu import Context
        c = Context()
        c.create_table("t", df)
        result = c.sql("SELECT name, SUM(x) FROM t GROUP BY name")
    """

    DEFAULT_SCHEMA_NAME = "root"

    def __init__(self, logging_level=logging.INFO, mesh=None):
        """``mesh``: a 1-D ``jax.sharding.Mesh`` — tables registered on this
        context are row-sharded over it and queries compile to SPMD programs
        with XLA-inserted collectives (the distributed mode; the reference
        attaches a dask cluster instead, SURVEY §2.3)."""
        self.schema_name = self.DEFAULT_SCHEMA_NAME
        self.schema = {self.DEFAULT_SCHEMA_NAME: SchemaContainer(self.DEFAULT_SCHEMA_NAME)}
        self.server = None
        self.mesh = mesh
        self._has_chunked = False
        # catalog epochs: monotonic per-table versions bumped by every
        # mutating path (create/drop/alter, CTAS, schema ops) — the
        # correctness backbone of the result cache (runtime/result_cache.py):
        # the epoch joins every cache key, so a mutated table can never
        # serve a stale cached result
        self._table_epochs: dict = {}
        self._epoch_counter = itertools.count(1)
        # per-table append serialization: _apply_delta's read-concat-swap
        # must not interleave between two writers on the same table (the
        # later swap would discard the earlier batch's rows); the ingest
        # log holds the same lock across its WAL write so WAL order
        # matches apply order.  RLock: replay calls _apply_delta under it.
        self._append_locks: dict = {}
        self._append_locks_guard = threading.Lock()
        # the lazily-materialized builtin "system" schema sentinel
        # (runtime/system_tables.py): created on first system.* resolution;
        # a user schema literally named "system" shadows it
        self._system_schema: Optional[SchemaContainer] = None
        # PREPARE registry: name -> PrepareStatement (parsed AST + text).
        # EXECUTE binds the stored AST with fresh values; system.prepared
        # lists entries (physical/rel/custom.py, runtime/system_tables.py)
        self._prepared: dict = {}
        # fleet plane (runtime/fleet.py): arm once per process when a
        # shared fleet dir is configured — env checked BEFORE the import
        # so the unarmed path stays zero-import (the recorder/profiler
        # discipline).  Idempotent: the second Context is a no-op.
        if os.environ.get("DSQL_FLEET_DIR"):
            try:
                from .runtime import fleet as _fleet
                _fleet.ensure_armed()
            except Exception:
                logger.debug("fleet arming failed", exc_info=True)
        # continuous ingestion (runtime/ingest.py): same env-before-import
        # discipline — an unset DSQL_INGEST_DIR (or DSQL_INGEST=0) leaves
        # the module un-imported and the write path bit-for-bit baseline.
        # Arming opens the per-table WAL and replays committed batches for
        # tables registered later (create_table calls maybe_replay).
        if _ingest_on():
            try:
                from .runtime import ingest as _ing
                _ing.ensure_armed(self)
            except Exception:
                logger.debug("ingest arming failed", exc_info=True)
        # register default input plugins (reference context.py:113-119 order)
        for plugin in (DeviceTableInputPlugin(), PandasLikeInputPlugin(),
                       DictInputPlugin(), ArrowInputPlugin(), HiveInputPlugin(),
                       IntakeCatalogInputPlugin(), LocationInputPlugin()):
            InputUtil.add_plugin(type(plugin).__name__, plugin, replace=False)
        # statement plugins live in physical/rel/custom.py; import registers them
        from .physical.rel import custom  # noqa: F401

    # ------------------------------------------------------------- epochs
    def table_epoch(self, schema_name: str, table_name: str) -> int:
        """Current catalog epoch of (schema, table); 0 = never mutated
        since this Context was created.  Under an armed ingest subsystem
        a query running inside a snapshot pin (runtime/ingest.py) reads
        the epoch AS OF admission, so result-cache keys stay consistent
        with the pinned table contents."""
        if _ingest_on():
            from .runtime import ingest as _ing
            pinned = _ing.pinned_epoch(schema_name, table_name.lower())
            if pinned is not None:
                return pinned
        return self._table_epochs.get((schema_name, table_name.lower()), 0)

    def catalog_entry(self, schema_name: str, table_name: str):
        """The executor-facing catalog read (physical/rel/executor.py,
        physical/compiled.py): identical to
        ``self.schema[schema_name].tables[table_name]`` except that inside
        a snapshot pin it returns the entry captured at admission — a
        query sees one consistent prefix of every table it scans even
        while the ingest writer keeps appending.  Raises KeyError exactly
        like the direct lookup."""
        if _ingest_on():
            from .runtime import ingest as _ing
            entry = _ing.pinned_entry(schema_name, table_name)
            if entry is not None:
                return entry
        return self.schema[schema_name].tables[table_name]

    def bump_table_epoch(self, schema_name: str, table_name: str,
                         delta: Optional[Table] = None) -> int:
        """Advance the table's epoch (every mutating path calls this) and
        drop any cached results that reference it.

        ``delta``: the appended batch, when the mutation is a pure append
        (``append_rows`` / INSERT INTO).  Recorded on the materialized-view
        registry so dependent maintainable views refresh in O(delta);
        omitted (every other caller) the bump is a hard tombstone — the
        delta log clears and dependents recompute in full."""
        key = (schema_name, table_name.lower())
        epoch = next(self._epoch_counter)
        self._table_epochs[key] = epoch
        from .runtime import result_cache as _rc
        _rc.get_cache().invalidate_table(schema_name, table_name.lower())
        reg = self.__dict__.get("_matview_registry")
        if reg is not None:
            if delta is not None:
                reg.record_delta(key, epoch, delta)
            else:
                reg.record_overwrite(key, epoch)
        return epoch

    # ------------------------------------------------------------- schemas
    def create_schema(self, schema_name: str):
        self.schema[schema_name] = SchemaContainer(schema_name)

    def drop_schema(self, schema_name: str):
        if schema_name == self.DEFAULT_SCHEMA_NAME:
            raise RuntimeError(f"Default schema {schema_name} cannot be deleted")
        reg = self.__dict__.get("_matview_registry")
        if reg is not None:
            reg.discard_schema(schema_name)
        for table_name in list(self.schema[schema_name].tables):
            self.bump_table_epoch(schema_name, table_name)
        del self.schema[schema_name]
        if self.schema_name == schema_name:
            self.schema_name = self.DEFAULT_SCHEMA_NAME

    # -------------------------------------------------------------- tables
    def create_table(self, table_name: str, input_table: Any,
                     format: Optional[str] = None, persist: bool = False,
                     schema_name: Optional[str] = None,
                     statistics: Optional[dict] = None, gpu: bool = False,
                     chunked: bool = False, batch_rows: Optional[int] = None,
                     **kwargs):
        """Register anything the input plugins understand as a SQL table.

        ``persist`` keeps parity with the reference (context.py:121-204); data
        always lives on device here, so it is a no-op flag.

        ``chunked=True``: out-of-HBM mode — the data stays host-resident as
        encoded columnar batches (``batch_rows`` rows each) and queries
        stream it through the device one batch at a time
        (physical/streaming.py), the TPU analogue of the reference's
        partitioned-dataframe ingestion (input_utils/convert.py:38-62).
        Accepts a pandas frame or a parquet path.
        """
        schema_name = schema_name or self.schema_name
        reg = self.__dict__.get("_matview_registry")
        if reg is not None:
            # re-registering a name that was a materialized view is an
            # overwrite: the registry entry goes, the bump below tombstones
            reg.discard_view(schema_name, table_name.lower())
        if chunked:
            # composes with mesh= : the streaming executor row-shards each
            # uploaded batch over the mesh (physical/streaming.py
            # _set_batch_entry), so execution is out-of-core AND
            # distributed at once, like the reference's partitioned model
            from .io.chunked import DEFAULT_BATCH_ROWS, ChunkedSource
            rows = batch_rows or DEFAULT_BATCH_ROWS
            if isinstance(input_table, ChunkedSource):
                source = input_table  # pre-built (e.g. from_parquet caller)
            elif isinstance(input_table, str):
                source = ChunkedSource.from_parquet(input_table,
                                                    batch_rows=rows)
            else:
                import pandas as pd
                if not isinstance(input_table, pd.DataFrame):
                    raise TypeError("chunked=True accepts a pandas frame "
                                    "or a parquet path")
                source = ChunkedSource.from_pandas(input_table,
                                                   batch_rows=rows)
            self._has_chunked = True
            entry = TableEntry(
                table=source.schema_table(), chunked=source,
                statistics=statistics or {"row_count": source.n_rows},
                filepath=input_table if isinstance(input_table, str) else None)
            self.schema[schema_name].tables[table_name.lower()] = entry
            self.bump_table_epoch(schema_name, table_name)
            logger.debug("Registered chunked table %s.%s (%d rows, %d batches)",
                         schema_name, table_name, source.n_rows,
                         source.n_batches)
            return
        from .runtime import telemetry as _tel
        with _tel.load_scope(table=table_name.lower()) as load:
            table, row_valid, stats = self._load(
                load, InputUtil.to_frame_or_table(
                    input_table, file_format=format, table_name=table_name,
                    **kwargs))
        entry = TableEntry(table=table, statistics=statistics,
                           filepath=input_table if isinstance(input_table, str) else None,
                           gpu=gpu, row_valid=row_valid, stats=stats)
        self.schema[schema_name].tables[table_name.lower()] = entry
        self.bump_table_epoch(schema_name, table_name)
        if _ingest_on():
            # restart path: committed WAL batches recorded against this
            # table in a previous process apply as soon as the base is
            # re-registered (crash recovery loses zero committed batches).
            # With nothing pending this is a mid-run (re-)register: the
            # new source supersedes any logged history, so the table's
            # segments truncate — replaying them onto the fresh base on
            # a later restart would double-apply rows (and the WAL stays
            # bounded by re-registration instead of growing forever).
            try:
                from .runtime import ingest as _ing
                log = _ing.get_log(self, create=True)
                if log.has_pending(schema_name, table_name.lower()):
                    log.maybe_replay(schema_name, table_name.lower())
                else:
                    log.truncate(schema_name, table_name.lower())
            except Exception:
                logger.debug("ingest replay failed", exc_info=True)
        logger.debug("Registered table %s.%s (%d rows)", schema_name,
                     table_name, table.num_rows)

    def _load(self, load, source):
        """A frame (or a plugin's device Table) to ``(table, row_valid,
        stats)``, resident.  Three steps, each a span under ``load`` and a
        ``load_*_ms`` counter: ``load_encode`` (frame to host arrays:
        strings to codes, dates, casts), ``load_stats`` (ingest statistics,
        runtime/statistics.py: NDV/min-max/null fraction/dense-int detection
        per column, the base layer of the adaptive-dispatch vertical;
        best-effort: a failed collection leaves ``stats`` None and every
        consumer falls back to the pre-stats behavior) and ``load_transfer``
        (until the arrays are resident).  A frame's statistics are read off
        its host arrays before the upload, so nothing comes back from the
        device; a device Table has no host arrays and its statistics read
        the device, after the mesh has placed it."""
        import jax

        from .runtime import telemetry as _tel
        from .runtime.statistics import collect_table_stats

        spans = {}
        host = None
        if not isinstance(source, Table):
            with _tel.span("load_encode") as spans["encode"]:
                host = Table.host_from_pandas(source)
            with _tel.span("load_stats") as spans["stats"]:
                stats = collect_table_stats(host)
        with _tel.span("load_transfer") as spans["transfer"]:
            table = source if host is None else host.to_device()
            row_valid = None
            if self.mesh is not None:
                from .parallel.mesh import shard_table_with_validity
                table, row_valid = shard_table_with_validity(table, self.mesh)
            jax.block_until_ready([(c.data, c.mask) for c in table.columns])
        if host is None:
            with _tel.span("load_stats") as spans["stats"]:
                stats = collect_table_stats(table, row_valid=row_valid)
        rows = (source if host is None else host).num_rows
        nbytes = sum(a.nbytes for c in table.columns
                     for a in (c.data, c.mask) if a is not None)
        load.attrs.update(rows=rows, bytes=nbytes, string_columns=sum(
            c.stype.is_string for c in table.columns))
        _tel.inc("load_tables")
        _tel.inc("load_rows", rows)
        _tel.inc("load_bytes", nbytes)
        for step in ("encode", "stats", "transfer"):
            _tel.inc(f"load_{step}_ms", int(round(
                spans[step].wall_ms)) if step in spans else 0)
        return table, row_valid, stats

    def drop_table(self, table_name: str, schema_name: Optional[str] = None):
        schema_name = schema_name or self.schema_name
        reg = self.__dict__.get("_matview_registry")
        if reg is not None:
            # DROP TABLE on a materialized view tears down its registry
            # state too (maintained cache entry, delta pins)
            reg.discard_view(schema_name, table_name.lower())
        del self.schema[schema_name].tables[table_name.lower()]
        self.bump_table_epoch(schema_name, table_name)
        if _ingest_on():
            # the table's WAL history dies with it: replaying old deltas
            # into a future table registered under the same name would
            # resurrect dropped rows
            try:
                from .runtime import ingest as _ing
                log = _ing.get_log(self)
                if log is not None:
                    log.truncate(schema_name, table_name.lower())
            except Exception:
                logger.debug("ingest truncate failed", exc_info=True)

    def alter_schema(self, old_schema_name, new_schema_name):
        reg = self.__dict__.get("_matview_registry")
        if reg is not None:
            # renames re-key the catalog under the views' feet: registered
            # views (old or new schema) and views over tables in either are
            # invalidated by the tombstone bumps below; drop the registry
            # entries so stale maintained state cannot survive the rename
            reg.discard_schema(old_schema_name)
            reg.discard_schema(new_schema_name)
        self.schema[new_schema_name] = self.schema.pop(old_schema_name)
        for table_name in list(self.schema[new_schema_name].tables):
            self.bump_table_epoch(old_schema_name, table_name)
            self.bump_table_epoch(new_schema_name, table_name)

    def alter_table(self, old_table_name, new_table_name, schema_name=None):
        schema_name = schema_name or self.schema_name
        reg = self.__dict__.get("_matview_registry")
        if reg is not None:
            reg.discard_view(schema_name, old_table_name.lower())
            reg.discard_view(schema_name, new_table_name.lower())
        s = self.schema[schema_name]
        s.tables[new_table_name.lower()] = s.tables.pop(old_table_name.lower())
        self.bump_table_epoch(schema_name, old_table_name)
        self.bump_table_epoch(schema_name, new_table_name)

    def append_rows(self, table_name: str, rows: Any,
                    schema_name: Optional[str] = None) -> int:
        """Append ``rows`` to a registered resident table — the delta path
        (ISSUE 14): unlike re-``create_table``, the epoch bump carries the
        appended batch, so materialized views over the table refresh in
        O(delta) instead of recomputing (runtime/matview.py).

        ``rows``: a device ``Table``, pandas DataFrame, dict of columns, or
        list of row tuples (matched positionally).  Columns align to the
        target case-insensitively (or positionally when the names do not
        match; a named strict subset NULL-fills the rest), values cast to
        the target column types — anything that does not fit raises a
        typed ``SchemaMismatch``.  Returns the number of rows appended.
        ``INSERT INTO`` lowers to this.

        With the ingest subsystem armed (DSQL_INGEST_DIR, ISSUE 20) the
        batch goes through the write-ahead log first — durable before
        visible, possibly coalesced with neighbors (DSQL_INGEST_BATCH_*),
        priced through the memory broker (IngestBackpressure when the
        budget cannot absorb it).  The return value is then the rows made
        visible NOW (0 = accepted into the micro-batch buffer).
        """
        from .runtime.resilience import UserError

        schema_name = schema_name or self.schema_name
        entry = self.schema[schema_name].tables.get(table_name.lower())
        if entry is None:
            raise UserError(f"Table {table_name} not found in schema "
                            f"{schema_name}; create it before INSERT INTO.")
        if entry.chunked is not None:
            raise UserError(
                f"Table {table_name} is chunked (host-resident batches); "
                "appends are not supported — re-create it from the extended "
                "source instead.")
        if entry.table is None:
            raise UserError(
                f"{table_name} is a view; INSERT INTO targets tables. "
                "Append to its base tables instead.")
        reg = self.__dict__.get("_matview_registry")
        if reg is not None and (schema_name, table_name.lower()) in \
                getattr(reg, "views", {}):
            raise UserError(
                f"{table_name} is a materialized view; INSERT INTO targets "
                "base tables — the view refreshes from their appends.")
        delta = _coerce_delta(entry.table, rows)
        if delta.num_rows == 0:
            return 0
        if _ingest_on():
            from .runtime import ingest as _ing
            log = _ing.get_log(self, create=True)
            return log.commit(schema_name, table_name.lower(), delta)
        return self._apply_delta(schema_name, table_name.lower(), delta)

    def _append_lock(self, schema_name: str, table_name: str):
        """The per-(schema, table) lock every append takes across its whole
        read-concat-swap (and, under an armed ingest log, across the WAL
        write too, so WAL order matches apply order)."""
        key = (schema_name, table_name.lower())
        with self._append_locks_guard:
            lock = self._append_locks.get(key)
            if lock is None:
                lock = self._append_locks[key] = threading.RLock()
            return lock

    def _apply_delta(self, schema_name: str, table_name: str,
                     delta: Table) -> int:
        """Make one coerced batch visible: new catalog entry + delta-carrying
        epoch bump.  The tail of the pre-ingest ``append_rows``; the ingest
        log calls it after the WAL write (and on replay).  Re-fetches the
        entry and re-coerces — under micro-batching the table may have been
        swapped (or its schema altered) since the batch was coerced.

        Serialized per table: concurrent appends (ThreadingHTTPServer runs
        /v1/ingest handlers concurrently) each read the entry, concat, and
        swap under ``_append_lock`` — without it two writers read the same
        entry and the later swap silently discards the earlier batch."""
        with self._append_lock(schema_name, table_name):
            return self._apply_delta_locked(schema_name, table_name, delta)

    def _apply_delta_locked(self, schema_name: str, table_name: str,
                            delta: Table) -> int:
        from .ops.join import concat_tables
        from .runtime.resilience import UserError
        from .runtime.statistics import collect_table_stats

        entry = self.schema[schema_name].tables.get(table_name)
        if entry is None or entry.table is None:
            raise UserError(f"Table {table_name} not found in schema "
                            f"{schema_name}; create it before INSERT INTO.")
        delta = _coerce_delta(entry.table, delta)
        if self.mesh is not None:
            # sharded base: concat on host against the valid prefix, then
            # re-shard — appends are rare relative to scans, so the round
            # trip beats keeping a resharding kernel alive
            import numpy as np
            import pandas as pd
            from .parallel.mesh import shard_table_with_validity
            base_df = entry.table.to_pandas()
            if entry.row_valid is not None:
                base_df = base_df.iloc[
                    :int(np.asarray(entry.row_valid).sum())]
            combined = pd.concat([base_df, delta.to_pandas()],
                                 ignore_index=True)
            new_table = _coerce_delta(entry.table,
                                      Table.from_pandas(combined))
            new_table, row_valid = shard_table_with_validity(new_table,
                                                             self.mesh)
        else:
            new_table = concat_tables([entry.table, delta])
            row_valid = None
        stats = collect_table_stats(new_table, row_valid=row_valid)
        new_entry = TableEntry(
            table=new_table, statistics=entry.statistics,
            filepath=entry.filepath, gpu=entry.gpu, row_valid=row_valid,
            stats=stats)
        reg = self.__dict__.get("_matview_registry")
        if reg is not None:
            # the catalog swap and the delta record must be one atomic
            # step under the registry lock: a refresh that reads the new
            # table before its delta is logged would double-count the
            # appended rows (delta-join slices old prefixes by row count)
            with reg.lock:
                self.schema[schema_name].tables[table_name] = new_entry
                self.bump_table_epoch(schema_name, table_name, delta=delta)
        else:
            self.schema[schema_name].tables[table_name] = new_entry
            self.bump_table_epoch(schema_name, table_name, delta=delta)
        logger.debug("Appended %d rows to %s.%s (now %d)", delta.num_rows,
                     schema_name, table_name, new_table.num_rows)
        return delta.num_rows

    # ------------------------------------------------------------ functions
    def register_function(self, f: Callable, name: str,
                          parameters: List[Tuple[str, Any]] = None,
                          return_type: Any = None, replace: bool = False,
                          schema_name: Optional[str] = None,
                          row_udf: bool = False):
        """Register a scalar UDF (reference context.py:245-310).

        ``parameters``/``return_type`` accept numpy dtypes or SQL type names.
        """
        self._register_callable(f, name, False, parameters, return_type,
                                replace, schema_name, row_udf)

    def register_aggregation(self, f: Callable, name: str,
                             parameters: List[Tuple[str, Any]] = None,
                             return_type: Any = None, replace: bool = False,
                             schema_name: Optional[str] = None):
        """Register a custom aggregation (reference context.py:312-377)."""
        self._register_callable(f, name, True, parameters, return_type,
                                replace, schema_name, False)

    def _register_callable(self, f, name, aggregation, parameters, return_type,
                           replace, schema_name, row_udf):
        schema_name = schema_name or self.schema_name
        params = [(pname, _to_sql_type(t)) for pname, t in (parameters or [])]
        rt = _to_sql_type(return_type) if return_type is not None else SqlType("DOUBLE")
        fd = FunctionDescription(name=name, parameters=params, return_type=rt,
                                 aggregation=aggregation, func=f, row_udf=row_udf)
        schema = self.schema[schema_name]
        lower = name.lower()
        if not replace and lower in schema.functions and \
                schema.functions[lower].func is not f:
            raise ValueError(f"Function {name} is already registered")
        schema.functions[lower] = fd
        schema.function_lists.append(fd)

    # --------------------------------------------------------------- models
    def register_model(self, model_name: str, model: Any,
                       training_columns: List[str],
                       schema_name: Optional[str] = None):
        """Register a fitted model for PREDICT (reference context.py:497-520)."""
        schema_name = schema_name or self.schema_name
        self.schema[schema_name].models[model_name.lower()] = (model, list(training_columns))

    def _get_model(self, parts: List[str]):
        info = self.resolve_model(parts)
        if info is None:
            raise KeyError(f"Model {'.'.join(parts)} not found")
        return info

    # ------------------------------------------------------------ SQL entry
    def sql(self, sql: str, return_futures: bool = True,
            dataframes: Optional[dict] = None, gpu: bool = False,
            config_options: Optional[dict] = None,
            timeout: Optional[float] = None,
            priority: Optional[str] = None,
            params: Optional[list] = None,
            tenant: Optional[str] = None) -> Union[Table, Any]:
        """Parse, plan, optimize and execute a SQL statement.

        Returns a device ``Table`` (``return_futures=True``, the analogue of
        the reference's lazy dask frame) or a pandas DataFrame
        (``return_futures=False``, the ``.compute()`` path).

        ``timeout`` (seconds) opens a per-query deadline enforced at every
        layer checkpoint — compile attempts, stage scheduling, streamed
        batches, eager plan nodes — raising a typed
        ``runtime.resilience.DeadlineExceeded`` instead of running past the
        budget.  Defaults to ``DSQL_QUERY_TIMEOUT_MS`` (unset/0 = none);
        nested calls inherit the sooner enclosing deadline.

        Every call records a ``runtime.telemetry.QueryReport`` (span tree,
        phase timings, counter deltas, row/byte counts) on
        ``self.last_report``; ``DSQL_SLOW_QUERY_MS`` arms a slow-query log
        and ``DSQL_CHROME_TRACE_DIR`` exports each query's span tree as
        chrome://tracing JSON.

        ``priority`` (``"interactive"`` | ``"batch"`` | ``"background"``)
        sets the query's workload-manager class (runtime/scheduler.py):
        under concurrency, slots are granted by deficit-weighted priority
        with anti-starvation aging.  Defaults to ``DSQL_DEFAULT_PRIORITY``
        (or ``interactive``); the server maps its ``X-DSQL-Priority``
        header here.  Time spent queued counts against ``timeout`` and
        shows up as the ``queued`` phase of the QueryReport.

        ``params`` binds positional ``?`` / ``$n`` markers in the statement
        to python values (client-side prepared statements).  Combined with
        parameterized plan identity (plan/parameterize.py) every distinct
        value list reuses one compiled program per query shape.

        ``tenant`` names the tenant this query bills against
        (runtime/tenancy.py; the server maps its ``X-DSQL-Tenant`` header
        here): per-tenant token-bucket rate (``DSQL_TENANT_QPS``) and
        concurrency (``DSQL_TENANT_CONCURRENT``) quotas plus a per-tenant
        circuit breaker (``DSQL_TENANT_BREAKER``) are enforced at
        admission, raising typed ``TenantQuotaExceeded`` /
        ``TenantCircuitOpen`` (429 + Retry-After on the server wire).
        Unset = the ``default`` tenant; all quotas default to unlimited,
        and ``DSQL_TENANCY=0`` disables the subsystem entirely.
        """
        from .runtime import (resilience as _res, scheduler as _sched,
                              telemetry as _tel)

        from contextlib import nullcontext
        ten_scope = nullcontext()
        if tenant is not None and _tenancy_on():
            from .runtime import tenancy as _ten
            ten_scope = _ten.tenant_scope(tenant)

        if dataframes is not None:
            for df_name, df in dataframes.items():
                self.create_table(df_name, df, gpu=gpu)

        trace = None
        try:
            with _res.query_scope(timeout_s=timeout), \
                    _tel.trace_scope(sql) as trace, \
                    _sched.priority_scope(priority), ten_scope:
                with _tel.span("parse"):
                    stmts = parse_sql(sql)
                result = None
                for stmt in stmts:
                    result = self._execute_statement(stmt, sql,
                                                     params=params)
                if result is None:
                    result = Table([], [])
                if trace is not None and isinstance(result, Table):
                    trace.root.attrs["rows_out"] = result.num_rows
                    trace.root.attrs["bytes_out"] = sum(
                        int(getattr(c.data, "nbytes", 0))
                        for c in result.columns)
                if not return_futures and isinstance(result, Table):
                    with _tel.span("fetch"):
                        result = result.to_pandas()
                return result
        finally:
            # the report is built when the trace CLOSES (the with-exit
            # above), so it is published here — on success and failure
            # alike; nested sql() calls (trace is None) ride the outer
            # query's report instead of overwriting it
            if trace is not None and trace.report is not None:
                self.last_report = trace.report

    def _execute_statement(self, stmt: A.Statement, sql: str,
                           params: Optional[list] = None):
        from .physical.rel.custom import StatementDispatcher
        from .runtime import telemetry as _tel

        if isinstance(stmt, A.QueryStatement):
            with _tel.span("plan"):
                plan = self._get_plan(stmt.query, sql, params=params)
            with _tel.span("execute"):
                return self._execute_query_plan(plan)
        handler = StatementDispatcher.get_plugin(type(stmt).__name__)
        with _tel.span("execute", statement=type(stmt).__name__):
            return handler(stmt, self, sql)

    def _execute_query_plan(self, plan):
        # every device-executing plan — server, direct sql(), streaming,
        # CREATE MODEL's training query — passes through the workload
        # manager first: bounded admission, priority pick, working-set
        # reservation.  Disabled (DSQL_MAX_CONCURRENT_QUERIES=0) or nested
        # plans pass straight through (admission yields None).
        # Tenancy admission wraps OUTSIDE the scheduler's: a tenant over
        # quota must be rejected before it consumes a slot or queue
        # position (env-gated before import; a server pre-claim is
        # adopted here instead of re-claimed).
        from contextlib import nullcontext
        from .runtime import scheduler as _sched

        ten_adm = nullcontext()
        if _tenancy_on():
            from .runtime import tenancy as _ten
            ten_adm = _ten.admission()
        # snapshot isolation under the ingest writer (runtime/ingest.py):
        # pin every scanned table's (entry, epoch) at admission — the
        # query then reads one consistent prefix of the delta log however
        # long it runs and wherever its scans execute
        pin = nullcontext()
        if _ingest_on():
            from .runtime import ingest as _ing
            pin = _ing.pin_scope(self, plan)
        with ten_adm, _sched.get_manager().admission(plan, self), pin:
            return self._run_query_plan(plan)

    def _run_query_plan(self, plan):
        from .physical.rel.executor import RelExecutor
        from .runtime import result_cache as _rc, telemetry as _tel

        # out-of-HBM tables route through the streaming executor — the
        # resident paths below must never compute on their binding stubs.
        # (_has_chunked guards the per-query plan walk + import: contexts
        # that never registered a chunked table skip it entirely)
        if self._has_chunked:
            from .physical.streaming import (execute_streaming,
                                             plan_references_chunked)
            if plan_references_chunked(plan, self):
                if (os.environ.get("DSQL_AUTOPILOT", "0").strip()
                        not in ("", "0")):
                    # adaptive re-planning covers the streaming tier too
                    # (the grace-join partition hint lives there), but the
                    # fingerprint rides a SEPARATE attr: chunked sources
                    # have no stable content identity, so they must stay
                    # out of the flight recorder's plan_fp stats and out
                    # of system.view_candidates
                    from .runtime import autopilot as _ap
                    from .runtime import flight_recorder as _fr
                    fp = None
                    try:
                        fp = _fr.plan_fingerprint(plan, self)
                        if fp is not None:
                            _tel.annotate(autopilot_fp=fp)
                    except Exception:
                        logger.debug("plan fingerprint failed",
                                     exc_info=True)
                    _ap.begin_query(fp, self)
                    try:
                        return execute_streaming(plan, self)
                    finally:
                        _ap.end_query()
                return execute_streaming(plan, self)
        # result cache: an identical plan over unmutated tables (same
        # catalog epochs + table uids) replays its materialized result and
        # skips device execution entirely; volatile plans key to None
        cache = _rc.get_cache()
        ckey = None
        if cache.enabled():
            # the probe: key build and lookup (the store below is the same
            # phase; phases sum by span name)
            with _tel.span("result_cache"):
                ckey = _rc.plan_key(plan, self)
                hit = None
                if ckey is None:
                    pass  # volatile plan: nothing to probe or store
                elif getattr(self, "_rc_bypass", False):
                    # EXPLAIN PROFILE measures a real execution: the lookup
                    # is skipped (the store below still refreshes the entry)
                    _tel.annotate(result_cache="bypass")
                else:
                    hit = cache.get(ckey)
                    if hit is None:
                        _tel.inc("result_cache_misses")
                        _tel.annotate(result_cache="miss")
                    else:
                        _tel.inc("result_cache_hits")
                        _tel.annotate(result_cache="hit",
                                      result_cache_tier=hit[1])
            if hit is not None:
                # the hit bypasses execution, so stamp the plan
                # fingerprint HERE: the cache-hit envelope keeps the
                # hot query's rank in system.view_candidates accruing
                # (the candidate-starvation fix)
                if os.environ.get("DSQL_HISTORY_FILE"):
                    try:
                        from .runtime import flight_recorder as _fr
                        fp = _fr.plan_fingerprint(plan, self)
                        if fp is not None:
                            _tel.annotate(plan_fp=fp)
                    except Exception:
                        logger.debug("plan fingerprint failed",
                                     exc_info=True)
                return hit[0]
        autopilot_on = (os.environ.get("DSQL_AUTOPILOT", "0").strip()
                        not in ("", "0"))
        # flight recorder (runtime/flight_recorder.py): stamp the canonical
        # plan fingerprint on the execute span so the completion envelope
        # and the EWMA statistics history key to it.  Env-gated BEFORE the
        # import — with the recorder off this path allocates nothing.
        # (autopilot keys its hints on the same fingerprint)
        fp = None
        if os.environ.get("DSQL_HISTORY_FILE") or autopilot_on:
            try:
                from .runtime import flight_recorder as _fr
                fp = _fr.plan_fingerprint(plan, self)
                if fp is not None:
                    _tel.annotate(plan_fp=fp)
            except Exception:
                logger.debug("plan fingerprint failed", exc_info=True)
        if autopilot_on:
            # autopilot (runtime/autopilot.py): exact repeats of a managed
            # view's defining query answer from the maintained state, and
            # any active re-plan hint for this fingerprint scopes to this
            # execution (env checked before the import, same discipline)
            from .runtime import autopilot as _ap
            served = _ap.try_serve(plan, self)
            if served is not None:
                return served
            _ap.begin_query(fp, self)
        try:
            # SPMD multi-chip backend (parallel/spmd.py): with a device
            # mesh attached, stages execute as explicit shard_map programs
            # over row-sharded tables.  None means the plan is outside the
            # SPMD envelope or a runtime safety flag tripped — the
            # single-device tiers below serve it instead.
            result = None
            span = _tel.current_span()
            if self.mesh is not None:
                from .parallel.spmd import try_execute_spmd
                result = try_execute_spmd(plan, self)
                if result is not None and span is not None:
                    span.attrs.setdefault("tier", "spmd")
            # whole-plan jit (one device dispatch per query); falls back to
            # the eager per-op executor for plan shapes outside its subset
            if result is None:
                from .physical.compiled import try_execute_compiled
                result = try_execute_compiled(plan, self)
            # execution-tier annotation (tiered execution,
            # physical/compiled): "compiled", "eager", or the gate's own
            # "eager-compiling" — the gate's verdict wins, so only fill in
            # when it said nothing
            if result is None:
                if span is not None:
                    span.attrs.setdefault("tier", "eager")
                result = RelExecutor(self).execute(plan)
            elif span is not None:
                span.attrs.setdefault("tier", "compiled")
            # populate only on the success path: a crashed /
            # deadline-exceeded execution raised before this line and
            # never reaches the cache
            if ckey is not None and result is not None:
                with _tel.span("result_cache"):
                    if cache.put(ckey, result):
                        _tel.annotate(result_cache="store")
            return result
        finally:
            if autopilot_on:
                from .runtime import autopilot as _ap
                _ap.end_query()

    def _get_plan(self, query: A.SelectLike, sql: str = "",
                  params: Optional[list] = None) -> RelNode:
        binder = Binder(self, sql, params=params)
        plan = binder.bind(query)
        # context threads through so the stats-driven join-order pass
        # (plan/optimizer.py reorder_joins_stats) can rank join orders by
        # estimated output cardinality
        return optimize(plan, context=self)

    def explain(self, sql: str, dataframes: Optional[dict] = None) -> str:
        """Return the optimized plan as a string (reference context.py:442-468)."""
        if dataframes is not None:
            for df_name, df in dataframes.items():
                self.create_table(df_name, df)
        stmts = parse_sql(sql)
        stmt = stmts[0]
        if isinstance(stmt, A.ExplainStatement):
            query = stmt.query
        elif isinstance(stmt, A.QueryStatement):
            query = stmt.query
        else:
            return f"-- {type(stmt).__name__}"
        return self._get_plan(query, sql).explain()

    def visualize(self, sql: str, filename: str = "mydask.png"):
        """Plan visualization: writes the text plan (no graphviz dependency)."""
        text = self.explain(sql)
        with open(filename.rsplit(".", 1)[0] + ".txt", "w") as f:
            f.write(text)
        return text

    def profile(self, sql: str, trace_dir: str = "/tmp/dsql_trace"):
        """Run a query under the XLA/JAX profiler and return the result.

        The reference delegates profiling to the dask dashboard (SURVEY §5);
        here device-side timing lives in an XLA trace viewable with
        TensorBoard or Perfetto (``trace_dir`` holds the .trace files).
        """
        import jax

        with jax.profiler.trace(trace_dir):
            result = self.sql(sql)
            for col in getattr(result, "columns", []):
                col.data.block_until_ready()
        logger.info("XLA trace written to %s", trace_dir)
        return result

    # ----------------------------------------------------- catalog interface
    def fqn(self, identifier: Union[str, List[str]]) -> Tuple[str, str]:
        """Split a (qualified) name into (schema, name) (reference context.py:608-632)."""
        if isinstance(identifier, str):
            parts = identifier.split(".")
        else:
            parts = list(identifier)
        if len(parts) == 2 and parts[0] in self.schema:
            return parts[0], parts[1].lower()
        return self.schema_name, ".".join(parts).lower()

    def resolve_table(self, parts: List[str]):
        """Binder hook: (schema, table, fields, view_plan) or None."""
        if len(parts) == 2 and parts[0] == "system":
            resolved = self._resolve_system_table(parts[1])
            if resolved is not None:
                return resolved
        candidates = []
        if len(parts) == 1:
            candidates.append((self.schema_name, parts[0]))
        elif len(parts) >= 2:
            candidates.append((parts[0], ".".join(parts[1:])))
            candidates.append((self.schema_name, ".".join(parts)))
        for schema_name, table_name in candidates:
            schema = self.schema.get(schema_name)
            if schema is None:
                continue
            entry = schema.tables.get(table_name.lower())
            if entry is None:
                entry = schema.tables.get(table_name)
            if entry is not None:
                # materialized-view serve hook (runtime/matview.py): a view
                # whose base tables advanced refreshes HERE, before the scan
                # binds — stale maintained state is never served.  getattr
                # keeps the common no-MV path allocation-free.
                reg = self.__dict__.get("_matview_registry")
                if reg is not None:
                    entry = reg.maybe_serve(self, schema_name,
                                            table_name.lower(), entry)
                if entry.table is not None:
                    fields = [Field(n, c.stype) for n, c in
                              zip(entry.table.names, entry.table.columns)]
                    return schema_name, table_name.lower(), fields, None
                return schema_name, table_name.lower(), list(entry.plan.schema), entry.plan
        return None

    def _resolve_system_table(self, table_name: str):
        """Lazily bind ``system.<name>`` to a FRESH snapshot of live engine
        state (runtime/system_tables.py).  The snapshot Table is registered
        into a sentinel SchemaContainer so the executor's ordinary
        schema[..].tables[..] lookup scans the exact rows the binder saw;
        the next resolution rebuilds it.  A user-created schema named
        "system" takes precedence (None falls through to normal lookup);
        catalog epochs are never touched — system scans are marked volatile
        by the result cache instead (result_cache._canon_rel)."""
        existing = self.schema.get("system")
        if existing is not None and existing is not self._system_schema:
            return None  # user schema shadows the builtin
        from .runtime import system_tables as _sys

        name = table_name.lower()
        tbl = _sys.build(name, self)
        if tbl is None:
            return None
        if self._system_schema is None:
            self._system_schema = SchemaContainer("system")
        self.schema["system"] = self._system_schema
        self._system_schema.tables[name] = TableEntry(table=tbl)
        fields = [Field(n, c.stype)
                  for n, c in zip(tbl.names, tbl.columns)]
        return "system", name, fields, None

    def get_function(self, name: str) -> Optional[FunctionDescription]:
        for schema_name in (self.schema_name, self.DEFAULT_SCHEMA_NAME):
            schema = self.schema.get(schema_name)
            if schema is None:
                continue
            fd = schema.functions.get(name.lower())
            if fd is not None:
                return fd
        return None

    def resolve_model(self, parts: List[str]):
        if len(parts) == 2 and parts[0] in self.schema:
            schema_name, model_name = parts[0], parts[1]
        else:
            schema_name, model_name = self.schema_name, ".".join(parts)
        return self.schema[schema_name].models.get(model_name.lower())

    # --------------------------------------------------------- integrations
    def ipython_magic(self, auto_include: bool = False):
        """Register the %%sql magic (reference integrations/ipython.py:62-133)."""
        from .integrations.ipython import ipython_integration
        ipython_integration(self, auto_include=auto_include)

    def run_server(self, **kwargs):
        """Start the Presto-protocol HTTP server on this context
        (reference context.py:585-605)."""
        from .server.app import run_server
        return run_server(context=self, **kwargs)

    def stop_server(self):
        if self.server is not None:
            self.server.shutdown()
            self.server = None


def _coerce_delta(target: Table, rows: Any) -> Table:
    """Shape ``rows`` into a Table matching ``target``'s column names and
    types (append_rows' alignment/cast step).  Anything that does not fit
    the target schema raises a typed ``SchemaMismatch`` (a ``UserError``:
    the server wire maps it to HTTP 400) naming the offending columns —
    never a raw coercion traceback."""
    import pandas as pd

    from .physical.rex.cast import cast_column
    from .runtime.resilience import SchemaMismatch, UserError

    if isinstance(rows, Table):
        df = rows.to_pandas()
    elif isinstance(rows, pd.DataFrame):
        df = rows
    elif isinstance(rows, dict):
        df = pd.DataFrame(rows)
    elif isinstance(rows, (list, tuple)):
        width = {len(r) for r in rows if isinstance(r, (list, tuple))}
        if width - {len(target.names)}:
            raise SchemaMismatch(
                f"appended row tuples have {sorted(width)} values but the "
                f"table has {len(target.names)} columns "
                f"({list(target.names)})")
        df = pd.DataFrame(list(rows), columns=list(target.names))
    else:
        raise UserError(
            "append_rows accepts a Table, pandas DataFrame, dict of "
            f"columns, or list of row tuples; got {type(rows).__name__}")
    lower_map = {str(c).lower(): c for c in df.columns}
    target_lower = {n.lower() for n in target.names}
    if all(n.lower() in lower_map for n in target.names) and \
            len(df.columns) == len(target.names):
        df = df[[lower_map[n.lower()] for n in target.names]]
        df = df.set_axis(list(target.names), axis=1)
    elif len(df.columns) == len(target.names):
        df = df.set_axis(list(target.names), axis=1)  # positional order
    elif 0 < len(df.columns) < len(target.names) and \
            set(lower_map) <= target_lower:
        # named strict subset: the batch supplies some target columns by
        # name — NULL-fill the rest (INSERT INTO t (a, c) semantics)
        df = pd.DataFrame({
            n: (df[lower_map[n.lower()]].reset_index(drop=True)
                if n.lower() in lower_map
                else pd.Series([None] * len(df), dtype=object))
            for n in target.names})
    else:
        extra = sorted(set(lower_map) - target_lower)
        missing = sorted(target_lower - set(lower_map))
        detail = []
        if extra:
            detail.append(f"unknown column(s) {extra}")
        if missing:
            detail.append(f"missing column(s) {missing}")
        raise SchemaMismatch(
            f"appended rows have columns {list(df.columns)} but the table "
            f"has {list(target.names)}: " + "; ".join(detail) +
            " — supply target columns by name (any case, a subset "
            "NULL-fills the rest) or all of them positionally")
    delta = Table.from_pandas(df)
    cols = []
    for col, tgt, name in zip(delta.columns, target.columns, target.names):
        if col.stype.name != tgt.stype.name:
            try:
                col = cast_column(col, tgt.stype)
            except Exception as exc:
                raise SchemaMismatch(
                    f"column {name!r} of the appended rows "
                    f"({col.stype.name}) does not cast to the table's "
                    f"{tgt.stype.name}: {exc}") from exc
        cols.append(col)
    return Table(list(target.names), cols)


def _to_sql_type(t) -> SqlType:
    if isinstance(t, SqlType):
        return t
    if isinstance(t, str):
        return parse_type_name(t)
    if t is int:
        return SqlType("BIGINT")
    if t is float:
        return SqlType("DOUBLE")
    if t is str:
        return SqlType("VARCHAR")
    if t is bool:
        return SqlType("BOOLEAN")
    return sql_type_from_numpy(t)
