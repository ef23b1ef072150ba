"""Continuous ingestion: a crash-tolerant write-ahead delta log per
append-only table, with snapshot-isolated reads (ISSUE 20).

``DSQL_INGEST_DIR`` arms the subsystem — checked BEFORE this module is
imported (the fleet/autopilot discipline: an unset dir keeps the module
un-imported and every byte of the engine identical).  ``DSQL_INGEST=0``
is the bit-for-bit kill switch with the dir still set.

The write path (``Context.append_rows``, which INSERT INTO and
``POST /v1/ingest`` lower to) becomes::

    coerce -> fault site -> backpressure -> [buffer] -> WAL -> apply

* **WAL**: one newline-terminated JSON envelope per committed batch,
  written with a single ``os.write`` on an ``O_APPEND`` fd and fsynced —
  the commit point — so an ack survives OS crash/power loss, not just
  process death (``DSQL_INGEST_FSYNC=0`` trades that down to
  process-crash-only durability for throughput).  A crash mid-write
  leaves a torn tail that fails the CRC/JSON check and is skipped on
  replay: a batch is committed iff its line is whole, so replay recovers
  exactly the committed prefix and nothing half-written ("degraded never
  wrong").  Segments rotate per table at ``DSQL_INGEST_SEGMENT_MB``, and
  a table's segments truncate when it is dropped or re-registered from
  source mid-run — the new base supersedes the log (this is the
  checkpoint story: persist the table to its source, re-register, and
  the history is gone instead of replaying forever).
* **Replay**: arming (``Context.__init__`` / ``run_server``) loads the
  log; batches for tables that already exist apply immediately, the
  rest wait for ``create_table`` to re-register the base and then apply
  (``maybe_replay``) — a fresh process recovers every committed batch.
* **Micro-batch coalescing**: ``DSQL_INGEST_BATCH_ROWS`` > 1 buffers
  appends per table and commits them as one WAL line + one catalog
  swap + one matview delta once the buffer fills or outlives
  ``DSQL_INGEST_BATCH_MS`` (a daemon flusher drains aged buffers).
  The default (1) is fully synchronous.
* **Backpressure**: every commit prices its batch through the
  scheduler's memory broker (``MemoryLedger.reserve``); a writer that
  outruns the budget gets a typed ``IngestBackpressure`` (HTTP 429 +
  Retry-After on the wire) instead of silently growing the device
  working set.
* **Snapshot isolation**: ``pin_scope`` captures the ``TableEntry`` and
  epoch of every scan in a plan at admission; the executors'
  catalog reads (``Context.catalog_entry`` / ``table_epoch``) consult
  the thread's pin stack, so one query sees one consistent prefix of
  the log across all its scans while the writer keeps appending.
"""
from __future__ import annotations

import glob as _glob
import json
import logging
import os
import threading
import time
import zlib
from contextlib import contextmanager

from . import faults as _faults
from . import resilience as _res
from . import telemetry as _tel

logger = logging.getLogger(__name__)

WAL_SUBDIR = "wal"
WAL_VERSION = 1


# ---------------------------------------------------------------------------
# env knobs (read per call: tests flip them with monkeypatch)
# ---------------------------------------------------------------------------

def ingest_dir():
    return os.environ.get("DSQL_INGEST_DIR") or None


def enabled() -> bool:
    """Armed (dir set) AND not killed (DSQL_INGEST=0).  Callers check the
    same condition inline BEFORE importing this module."""
    if not ingest_dir():
        return False
    return os.environ.get("DSQL_INGEST", "1").strip() not in ("0", "false")


def batch_rows() -> int:
    try:
        return max(int(os.environ.get("DSQL_INGEST_BATCH_ROWS", "") or 1), 1)
    except ValueError:
        return 1


def batch_ms() -> float:
    try:
        return max(float(os.environ.get("DSQL_INGEST_BATCH_MS", "") or 25.0),
                   0.0)
    except ValueError:
        return 25.0


def _fsync_on() -> bool:
    return os.environ.get("DSQL_INGEST_FSYNC", "1").strip() \
        not in ("0", "false")


def _segment_bytes() -> int:
    try:
        mb = float(os.environ.get("DSQL_INGEST_SEGMENT_MB", "") or 64.0)
    except ValueError:
        mb = 64.0
    return max(int(mb * 2**20), 1 << 16)


# ---------------------------------------------------------------------------
# batch <-> JSON (WAL line payload)
# ---------------------------------------------------------------------------

def _encode_table(t) -> dict:
    """Columnar JSON for a coerced delta batch.  Types round-trip through
    the dtype hint + Context._coerce_delta's cast on replay."""
    import numpy as np

    df = t.to_pandas()
    cols = []
    for name in df.columns:
        s = df[name]
        if s.dtype.kind == "M":
            vals = [None if v is None or str(v) == "NaT" else int(v.value)
                    for v in s]
            cols.append({"n": str(name), "d": "datetime64[ns]", "v": vals})
        elif s.dtype.kind in ("O", "U", "S"):
            vals = [None if v is None or (isinstance(v, float) and v != v)
                    else str(v) for v in s.tolist()]
            cols.append({"n": str(name), "d": "str", "v": vals})
        else:
            cols.append({"n": str(name), "d": str(s.dtype),
                         "v": s.tolist()})
    return {"rows": int(t.num_rows), "cols": cols}


def _decode_table(data: dict):
    """Inverse of ``_encode_table``; the caller re-coerces against the
    live target schema so dtype drift degrades to a cast, not a crash."""
    import pandas as pd

    out = {}
    for c in data["cols"]:
        vals = c["v"]
        if c["d"] == "datetime64[ns]":
            out[c["n"]] = pd.to_datetime(
                [None if v is None else int(v) for v in vals])
        elif c["d"] == "str":
            out[c["n"]] = pd.Series(vals, dtype=object)
        else:
            try:
                out[c["n"]] = pd.Series(vals, dtype=c["d"])
            except (ValueError, TypeError):
                out[c["n"]] = pd.Series(vals)
    return pd.DataFrame(out)


def _table_nbytes(t) -> int:
    total = 0
    for col in t.columns:
        data = getattr(col, "data", None)
        total += int(getattr(data, "nbytes", 0) or 0)
        mask = getattr(col, "mask", None)
        total += int(getattr(mask, "nbytes", 0) or 0)
    return total or t.num_rows * 8 * max(t.num_columns, 1)


# ---------------------------------------------------------------------------
# the per-context log
# ---------------------------------------------------------------------------

class _Buffer:
    __slots__ = ("tables", "rows", "born", "grants")

    def __init__(self):
        self.tables = []
        self.rows = 0
        self.born = time.monotonic()
        # (ledger, grant) per buffered batch: the memory-broker
        # reservation stays alive while the rows sit here — they occupy
        # real memory until the flush applies them — so trickle writers
        # cannot park unbounded bytes outside the backpressure budget
        self.grants = []

    def release_grants(self) -> None:
        grants, self.grants = self.grants, []
        for ledger, grant in grants:
            try:
                ledger.release(grant)
            except Exception:  # pragma: no cover
                logger.debug("ingest: grant release failed", exc_info=True)


class _Flusher(threading.Thread):
    def __init__(self, log, interval_s: float):
        super().__init__(name="dsql-ingest-flush", daemon=True)
        self.log = log
        self.interval_s = interval_s
        self.stop = threading.Event()

    def run(self):
        while not self.stop.wait(self.interval_s):
            try:
                self.log.flush_aged()
            except Exception:  # pragma: no cover
                logger.debug("ingest flush failed", exc_info=True)


class IngestLog:
    """WAL + buffers + replay state for one Context."""

    def __init__(self, context, root: str):
        self.context = context
        self.wal_dir = os.path.join(root, WAL_SUBDIR)
        os.makedirs(self.wal_dir, exist_ok=True)
        self.lock = threading.RLock()
        self._fds = {}        # (schema, table) -> (fd, path, seq)
        self._buffers = {}    # (schema, table) -> _Buffer
        self._stats = {}      # (schema, table) -> dict (engine_section)
        self._replay = {}     # (schema, table) -> [payload dicts]
        self._wal_bytes = 0
        self._flusher = None
        self._load_replay()
        _ALL_LOGS.append(self)

    # -- WAL segments ------------------------------------------------------
    def _seg_glob(self, key):
        return os.path.join(self.wal_dir, f"{key[0]}.{key[1]}.*.log")

    def _open_segment(self, key, seq: int):
        path = os.path.join(self.wal_dir, f"{key[0]}.{key[1]}.{seq:05d}.log")
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        return fd, path, seq

    def _fd_for(self, key):
        ent = self._fds.get(key)
        if ent is None:
            segs = sorted(_glob.glob(self._seg_glob(key)))
            seq = int(segs[-1].rsplit(".", 2)[-2]) if segs else 1
            ent = self._fds[key] = self._open_segment(key, seq)
        fd, path, seq = ent
        try:
            if os.fstat(fd).st_size >= _segment_bytes():
                os.close(fd)
                ent = self._fds[key] = self._open_segment(key, seq + 1)
        except OSError:  # pragma: no cover
            pass
        return ent[0]

    def _wal_write(self, key, delta) -> None:
        """The commit point: one line, one write syscall, one fsync.  A
        crash that truncates the line leaves an invalid tail replay skips;
        the fsync makes an acked batch survive OS crash/power loss, not
        just process death (DSQL_INGEST_FSYNC=0 drops it for throughput,
        degrading the guarantee to process-crash-only durability)."""
        payload = json.dumps(
            {"s": key[0], "t": key[1], "d": _encode_table(delta)},
            separators=(",", ":"))
        line = (json.dumps(
            {"v": WAL_VERSION, "crc": zlib.crc32(payload.encode()),
             "p": payload}, separators=(",", ":")) + "\n").encode()
        fd = self._fd_for(key)
        os.write(fd, line)
        if _fsync_on():
            try:
                os.fsync(fd)
            except OSError:  # pragma: no cover - e.g. fs without fsync
                logger.debug("ingest: WAL fsync failed", exc_info=True)
        self._wal_bytes += len(line)
        _tel.REGISTRY.set_gauge("ingest_wal_bytes", self._wal_bytes)

    # -- replay ------------------------------------------------------------
    def _load_replay(self) -> None:
        torn = 0
        for seg in sorted(_glob.glob(os.path.join(self.wal_dir, "*.log"))):
            try:
                with open(seg, "rb") as f:
                    raw = f.read()
            except OSError:  # pragma: no cover
                continue
            self._wal_bytes += len(raw)
            for ln in raw.split(b"\n"):
                if not ln.strip():
                    continue
                try:
                    env = json.loads(ln)
                    p = env["p"]
                    if env.get("crc") != zlib.crc32(p.encode()):
                        raise ValueError("wal crc mismatch")
                    rec = json.loads(p)
                except (ValueError, TypeError, KeyError):
                    # torn/garbled line: the writer never acked this batch
                    # (the commit point is the complete line), so skipping
                    # it loses nothing committed
                    torn += 1
                    continue
                self._replay.setdefault((rec["s"], rec["t"]),
                                        []).append(rec["d"])
        if torn:
            _tel.inc("ingest_wal_torn_lines", torn)
            logger.warning("ingest: skipped %d torn WAL line(s) under %s",
                           torn, self.wal_dir)
        _tel.REGISTRY.set_gauge("ingest_wal_bytes", self._wal_bytes)

    def maybe_replay(self, schema_name: str, table_name: str) -> int:
        """Apply pending WAL batches for a freshly-registered table.
        Called on arming (already-registered tables) and from
        ``create_table`` (the restart path registers bases first)."""
        key = (schema_name, table_name)
        with self.lock:
            recs = self._replay.pop(key, None)
        if not recs:
            return 0
        rows = 0
        for d in recs:
            try:
                rows += self.context._apply_delta(
                    schema_name, table_name, _decode_table(d))
            except Exception:
                logger.warning("ingest: WAL replay batch for %s.%s failed",
                               schema_name, table_name, exc_info=True)
        _tel.inc("ingest_replayed_batches", len(recs))
        _tel.inc("ingest_replayed_rows", rows)
        st = self._stats.setdefault(key, _new_stats())
        st["replayed_batches"] += len(recs)
        st["replayed_rows"] += rows
        logger.info("ingest: replayed %d batch(es) / %d row(s) into %s.%s",
                    len(recs), rows, schema_name, table_name)
        return rows

    # -- the write path ----------------------------------------------------
    def commit(self, schema_name: str, table_name: str, delta) -> int:
        """WAL-then-apply (or buffer) one coerced batch.  Returns rows
        applied now (0 = buffered, flushed later by size/age)."""
        # chaos site: fires BEFORE anything durable or visible, so a
        # failed append is cleanly rejected — never half-committed
        _faults.maybe_fail("ingest")
        key = (schema_name, table_name)
        nbytes = _table_nbytes(delta)
        from . import scheduler as _sched
        ledger = _sched.get_manager().ledger
        grant = ledger.reserve(nbytes)
        if grant is None:
            _tel.inc("ingest_backpressure_rejects")
            raise _res.IngestBackpressure(
                f"ingest batch of {delta.num_rows} rows ({nbytes} bytes) "
                "does not fit the device budget; back off and retry "
                "(DSQL_DEVICE_BUDGET_MB prices writers and readers from "
                "the same ledger)", retry_after_s=0.25)
        if batch_rows() > 1:
            handed_off = False
            try:
                with self.lock:
                    buf = self._buffers.setdefault(key, _Buffer())
                    buf.tables.append(delta)
                    buf.rows += delta.num_rows
                    # the buffer owns the reservation from here: buffered
                    # rows occupy memory until the flush applies them, so
                    # the grant releases in _flush, not on ack
                    buf.grants.append((ledger, grant))
                    handed_off = True
                    if buf.rows < batch_rows():
                        _tel.inc("ingest_batches_buffered")
                        st = self._stats.setdefault(key, _new_stats())
                        st["buffered_rows"] = buf.rows
                        _tel.REGISTRY.set_gauge(
                            "ingest_buffered_rows", self._buffered_rows())
                        return 0
            finally:
                if not handed_off:
                    ledger.release(grant)
            return self._flush(key)
        try:
            return self._commit_now(key, delta)
        finally:
            ledger.release(grant)

    def _commit_now(self, key, delta) -> int:
        # the table's append lock spans WAL write AND apply so (a) two
        # concurrent writers cannot interleave read-concat-swap and lose
        # a batch, and (b) WAL order is apply order — replay reproduces
        # exactly the sequence readers observed
        with self.context._append_lock(key[0], key[1]):
            with self.lock:
                self._wal_write(key, delta)
            rows = self.context._apply_delta_locked(key[0], key[1], delta)
        _tel.inc("ingest_batches_committed")
        _tel.inc("ingest_rows_committed", rows)
        st = self._stats.setdefault(key, _new_stats())
        st["batches"] += 1
        st["rows"] += rows
        return rows

    def _flush(self, key) -> int:
        from ..ops.join import concat_tables
        with self.lock:
            buf = self._buffers.pop(key, None)
            if buf is None or not buf.tables:
                if buf is not None:
                    buf.release_grants()
                return 0
            delta = (buf.tables[0] if len(buf.tables) == 1
                     else concat_tables(buf.tables))
            st = self._stats.setdefault(key, _new_stats())
            st["buffered_rows"] = 0
            _tel.REGISTRY.set_gauge("ingest_buffered_rows",
                                    self._buffered_rows())
        try:
            _tel.inc("ingest_flushes")
            return self._commit_now(key, delta)
        finally:
            buf.release_grants()

    def flush_aged(self) -> int:
        """Flusher-thread entry: commit buffers older than the batch
        window so a trickle writer never strands rows."""
        limit_s = batch_ms() / 1000.0
        now = time.monotonic()
        with self.lock:
            aged = [k for k, b in self._buffers.items()
                    if now - b.born >= limit_s]
        rows = 0
        for key in aged:
            rows += self._flush(key)
        return rows

    def flush_all(self) -> int:
        with self.lock:
            keys = list(self._buffers)
        return sum(self._flush(k) for k in keys)

    def _buffered_rows(self) -> int:
        return sum(b.rows for b in self._buffers.values())

    # -- lifecycle ---------------------------------------------------------
    def start_flusher(self) -> None:
        if self._flusher is None and batch_rows() > 1:
            interval = max(batch_ms() / 1000.0, 0.01)
            self._flusher = _Flusher(self, interval)
            self._flusher.start()

    def close(self) -> None:
        if self._flusher is not None:
            self._flusher.stop.set()
            self._flusher = None
        # buffered rows were acked BUFFERED over the wire; a graceful
        # close must commit them before the fds go away or the accepted
        # batch silently vanishes (the drain path calls this too)
        try:
            self.flush_all()
        except Exception:
            logger.warning("ingest: flush on close failed", exc_info=True)
        with self.lock:
            for fd, _path, _seq in self._fds.values():
                try:
                    os.close(fd)
                except OSError:  # pragma: no cover
                    pass
            self._fds.clear()

    def has_pending(self, schema_name: str, table_name: str) -> bool:
        """True when replayable WAL batches await this table's
        registration (the restart path)."""
        with self.lock:
            return (schema_name, table_name) in self._replay

    def truncate(self, schema_name: str, table_name: str) -> None:
        """Drop a table's WAL history: segments, buffers, pending replay.

        Called when the base is dropped or re-registered from source with
        nothing pending — the new (or absent) base supersedes the log, and
        replaying the old deltas on a later restart would double-apply
        rows the source now carries, or resurrect a dropped table's rows.
        Re-registration is also the checkpoint/compaction path: persist
        the table to its source and re-register, and the WAL stops
        growing instead of replaying the full history every restart."""
        key = (schema_name, table_name)
        with self.lock:
            ent = self._fds.pop(key, None)
            if ent is not None:
                try:
                    os.close(ent[0])
                except OSError:  # pragma: no cover
                    pass
            buf = self._buffers.pop(key, None)
            if buf is not None:
                buf.release_grants()
            self._replay.pop(key, None)
            removed = 0
            for seg in _glob.glob(self._seg_glob(key)):
                try:
                    removed += os.path.getsize(seg)
                    os.remove(seg)
                except OSError:  # pragma: no cover
                    pass
            if removed:
                self._wal_bytes = max(self._wal_bytes - removed, 0)
                _tel.REGISTRY.set_gauge("ingest_wal_bytes", self._wal_bytes)
                _tel.inc("ingest_wal_truncations")
                logger.info("ingest: truncated %d WAL byte(s) for %s.%s",
                            removed, schema_name, table_name)

    def tables_snapshot(self) -> dict:
        with self.lock:
            out = {}
            for key, st in sorted(self._stats.items()):
                out[f"{key[0]}.{key[1]}"] = dict(st)
            for key, buf in self._buffers.items():
                out.setdefault(f"{key[0]}.{key[1]}",
                               _new_stats())["buffered_rows"] = buf.rows
            return out


def _new_stats() -> dict:
    return {"batches": 0, "rows": 0, "buffered_rows": 0,
            "replayed_batches": 0, "replayed_rows": 0}


_ALL_LOGS: list = []


# ---------------------------------------------------------------------------
# arming (Context.__init__ / run_server hook; env checked by the caller)
# ---------------------------------------------------------------------------

_ARM_LOCK = threading.Lock()


def get_log(context, create: bool = False):
    log = getattr(context, "_ingest_log", None)
    if log is None and create and enabled():
        with _ARM_LOCK:
            log = getattr(context, "_ingest_log", None)
            if log is None:
                log = IngestLog(context, ingest_dir())
                context._ingest_log = log
    return log


def ensure_armed(context) -> bool:
    """Idempotent per-context arming: open the WAL, replay committed
    batches for tables that already exist, start the flusher."""
    if not enabled():
        return False
    log = get_log(context, create=True)
    for schema_name, sc in list(context.schema.items()):
        for table_name, entry in list(sc.tables.items()):
            if entry.table is not None and entry.chunked is None:
                log.maybe_replay(schema_name, table_name)
    log.start_flusher()
    return True


def _reset_for_tests() -> None:
    while _ALL_LOGS:
        log = _ALL_LOGS.pop()
        try:
            log.close()
            log.context.__dict__.pop("_ingest_log", None)
        except Exception:  # pragma: no cover
            pass


# ---------------------------------------------------------------------------
# snapshot-isolated reads: the per-thread pin stack
# ---------------------------------------------------------------------------

_TLS = threading.local()


def _collect_scans(plan, out) -> None:
    from ..plan.nodes import LogicalTableScan, RexScalarSubquery

    def walk_rex(rex):
        if isinstance(rex, RexScalarSubquery) and rex.plan is not None:
            _collect_scans(rex.plan, out)
            return
        for op in getattr(rex, "operands", []) or []:
            walk_rex(op)

    if isinstance(plan, LogicalTableScan):
        out.append(plan)
        return
    for e in getattr(plan, "exprs", []) or []:
        walk_rex(e)
    cond = getattr(plan, "condition", None)
    if cond is not None:
        walk_rex(cond)
    for i in plan.inputs:
        _collect_scans(i, out)


@contextmanager
def pin_scope(context, plan):
    """Snapshot-isolate one query: capture (TableEntry, epoch) for every
    scan in ``plan`` at admission.  ``Context.catalog_entry`` /
    ``table_epoch`` consult the top of this thread's stack during
    execution, so all scans — and the result-cache key — see the same
    consistent prefix of the log even while the writer keeps appending
    (tables are immutable and appends swap whole entries, so a pinned
    entry stays valid forever)."""
    pins = {}
    try:
        scans = []
        _collect_scans(plan, scans)
        for scan in scans:
            sc = context.schema.get(scan.schema_name)
            entry = (sc.tables.get(scan.table_name)
                     if sc is not None else None)
            if entry is not None and entry.table is not None:
                key = (scan.schema_name, scan.table_name)
                pins[key] = (entry,
                             context.table_epoch(scan.schema_name,
                                                 scan.table_name))
    except Exception:  # pragma: no cover - pinning must never fail a query
        logger.debug("snapshot pin capture failed", exc_info=True)
        pins = {}
    stack = getattr(_TLS, "pins", None)
    if stack is None:
        stack = _TLS.pins = []
    stack.append(pins)
    try:
        yield
    finally:
        stack.pop()


def pinned_entry(schema_name: str, table_name: str):
    stack = getattr(_TLS, "pins", None)
    if not stack:
        return None
    hit = stack[-1].get((schema_name, table_name))
    return None if hit is None else hit[0]


def pinned_epoch(schema_name: str, table_name: str):
    stack = getattr(_TLS, "pins", None)
    if not stack:
        return None
    hit = stack[-1].get((schema_name, table_name))
    return None if hit is None else hit[1]


# ---------------------------------------------------------------------------
# /v1/engine section
# ---------------------------------------------------------------------------

def engine_section(context) -> dict:
    counters = _tel.REGISTRY.counters()
    gauges = _tel.REGISTRY.gauges()
    log = get_log(context)
    out = {
        "armed": log is not None,
        "dir": ingest_dir() or "",
        "batchRows": batch_rows(),
        "batchMs": batch_ms(),
        "batchesCommitted": int(counters.get("ingest_batches_committed", 0)),
        "rowsCommitted": int(counters.get("ingest_rows_committed", 0)),
        "replayedBatches": int(counters.get("ingest_replayed_batches", 0)),
        "backpressureRejects": int(
            counters.get("ingest_backpressure_rejects", 0)),
        "tornWalLines": int(counters.get("ingest_wal_torn_lines", 0)),
        "walBytes": int(gauges.get("ingest_wal_bytes", 0)),
        "bufferedRows": int(gauges.get("ingest_buffered_rows", 0)),
        "mvPendingRows": int(gauges.get("mv_pending_rows", 0)),
        "mvStalenessS": float(gauges.get("mv_staleness_s", 0.0)),
    }
    if log is not None:
        out["tables"] = log.tables_snapshot()
    return out
