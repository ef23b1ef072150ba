"""Running a ``StageGraph``: a plan above the heavy-node budget
(``physical/stages.py``; ``DSQL_STAGE_HEAVY``, legacy ``DSQL_SPLIT_HEAVY``)
runs as a DAG of bounded programs.

XLA:TPU compile time grows superlinearly with the number of fused
join/aggregate pipelines in one program (TPC-H Q2, 9 heavy nodes after
decorrelation, never finished compiling where 2-join programs took tens of
seconds; what was measured for a v5e since: physical/stages.py).  Every
stage is traced and jitted as its own program, its output materialized into
a padded power-of-2 capacity-class temp table (``__split__`` schema), so
the consumer's program key is stable across runs.  Stages keep the ordinary
(plan fingerprint, input layout) program-cache key: structurally shared
pipelines across queries — TPC-H's repeated lineitem/orders
scan→filter→join prefixes — compile once and hit from then on
(``cross_query_hits``).  Independent stages execute concurrently in a small
worker pool (``DSQL_COMPILE_WORKERS``): XLA compilation releases the GIL, so
a cold warmup is overlapped small compiles, not one serial monolith.  The
executor is handed the function that runs one program; nothing here imports
the tracer.
"""
from __future__ import annotations

import hashlib
import logging
import os
import threading as _threading
import time
from typing import Callable, Dict, List, Optional

import jax.numpy as jnp

from ..plan.nodes import (LogicalFilter, LogicalJoin, LogicalProject,
                          LogicalTableScan, RelNode, RexCall,
                          RexScalarSubquery)
from ..runtime import (faults as _faults, resilience as _res,
                       result_cache as _rcache, telemetry as _tel)
from ..table import Column, Table
from .programs import _compile_workers, _events_on, _profile_on
from .stages import (StageGraph, annotate_stats as _annotate_stage_stats,
                     partition as _partition)

logger = logging.getLogger(__name__)

_SPLIT_SCHEMA = "__split__"

_split_lock = _threading.Lock()
_split_refs: Dict[tuple, int] = {}


def _rex_scan_uids(rex, context) -> list:
    if isinstance(rex, RexScalarSubquery):
        return _scan_uids(rex.plan, context)
    if isinstance(rex, RexCall):
        return [u for o in rex.operands for u in _rex_scan_uids(o, context)]
    return []


def _scan_uids(rel: RelNode, context) -> list:
    """uids of every table a subtree scans (scalar-subquery plans included:
    they live in rex trees, not inputs, and their scans must contribute or
    the data-mutation race the stage digest closes reopens)."""
    if isinstance(rel, LogicalTableScan):
        if rel.schema_name in (_SPLIT_SCHEMA, "__spmd__"):
            # a boundary scan's NAME is already a content digest of its
            # producing subtree (scan uids folded in transitively) — and the
            # temp table may not be registered yet at partition time
            return [rel.table_name]
        entry = context.schema.get(rel.schema_name)
        tbl = (entry.tables[rel.table_name].table
               if entry is not None and rel.table_name in entry.tables
               else None)
        return [str(getattr(tbl, "uid", "?"))]
    out = [u for i in rel.inputs for u in _scan_uids(i, context)]
    if isinstance(rel, LogicalProject):
        for e in rel.exprs:
            out.extend(_rex_scan_uids(e, context))
    elif isinstance(rel, LogicalFilter):
        out.extend(_rex_scan_uids(rel.condition, context))
    elif isinstance(rel, LogicalJoin) and rel.condition is not None:
        out.extend(_rex_scan_uids(rel.condition, context))
    return out


def _stage_table_name(node: RelNode, context) -> str:
    """DETERMINISTIC temp-table name from the subtree's shape PLUS the
    scanned tables' uids: the name feeds the CONSUMER program's plan
    fingerprint, so a per-execution counter would recompile the consumer on
    every run (and leak dead cache entries) — but shape alone is not
    enough, since catalog data can mutate (INSERT / re-register) between
    two concurrent executions sharing a context.  With uids folded in,
    identical digests imply identical subplans over identical table
    OBJECTS, so a concurrent overwrite writes equal content and is
    harmless.  Across queries the digest is what makes shared subplans
    collide into ONE boundary name — the consumer-side half of cross-query
    stage reuse (and the key of the subplan result cache).

    The shape text is ``result_cache.canonical_plan``, not ``explain()``:
    the plan renderer elides VALUES row contents and scalar-subquery
    bodies, so two DIFFERENT subplans could share an explain() digest —
    unacceptable for a content address results are replayed from."""
    shape, _, _ = _rcache.canonical_plan(node, context)
    digest = hashlib.blake2s(
        (shape + "|"
         + ",".join(f.stype.name for f in node.schema) + "|"
         + ",".join(_scan_uids(node, context))).encode()
    ).hexdigest()[:16]
    return f"t{digest}"


def _make_boundary_scan(node: RelNode, context) -> LogicalTableScan:
    from ..plan.nodes import Field
    return LogicalTableScan(
        schema_name=_SPLIT_SCHEMA,
        table_name=_stage_table_name(node, context),
        schema=[Field(f"c{i}", f.stype)
                for i, f in enumerate(node.schema)])


def _partition_plan(plan: RelNode, budget: int, context) -> StageGraph:
    graph = _partition(plan, budget,
                       lambda sub: _make_boundary_scan(sub, context))
    _annotate_stage_stats(graph, context)
    return graph


def _capacity_class(rows: int) -> int:
    """The power-of-2 capacity (64 at least) a stage output of ``rows``
    rows is padded to."""
    return 1 << max((max(rows, 1) - 1).bit_length(), 6)


def _pad_capacity(table: Table):
    """(padded table, row_valid): pad to a power-of-2 capacity with row
    validity.  Consumer programs are keyed on input SHAPES and a stage's
    true row count is data-dependent — capacity classes keep the key stable
    across runs, so reloading fresh data through the same stage never
    recompiles the consumer."""
    n = table.num_rows
    cap = _capacity_class(n)
    table = table.with_names([f"c{i}" for i in range(table.num_columns)])
    if cap != n:
        pad = cap - n
        pcols = []
        for c in table.columns:
            data = jnp.concatenate(
                [c.data, jnp.zeros((pad,) + c.data.shape[1:],
                                   dtype=c.data.dtype)])
            mask = (None if c.mask is None else
                    jnp.concatenate([c.mask, jnp.zeros(pad, dtype=bool)]))
            pcols.append(Column(data, c.stype, mask, c.dictionary))
        table = Table(list(table.names), pcols)
    return table, jnp.arange(cap) < n


def _register_stage_table(context, name: str, table: Table) -> None:
    """Publish a stage output under __split__ (refcounted: concurrent
    queries on one context may share a boundary name; the digest guarantees
    equal content, so the overwrite is harmless)."""
    from ..datacontainer import TableEntry
    padded, row_valid = _pad_capacity(table)
    ref_key = (id(context), name)
    with _split_lock:
        if _SPLIT_SCHEMA not in context.schema:
            context.create_schema(_SPLIT_SCHEMA)
        context.schema[_SPLIT_SCHEMA].tables[name] = TableEntry(
            table=padded, row_valid=row_valid)
        _split_refs[ref_key] = _split_refs.get(ref_key, 0) + 1


def _unregister_stage_table(context, name: str) -> None:
    ref_key = (id(context), name)
    with _split_lock:
        refs = _split_refs.get(ref_key, 0) - 1
        if refs > 0:
            _split_refs[ref_key] = refs
            return
        _split_refs.pop(ref_key, None)
        sch = context.schema.get(_SPLIT_SCHEMA)
        if sch is not None:
            sch.tables.pop(name, None)



def _record_stage_stats(st, idx: int, out: Table, query_fp: str,
                        stage_rows: Dict[int, int], wall_ms: float) -> None:
    """One flight-recorder stats record per executed stage (callers gate
    on DSQL_HISTORY_FILE or DSQL_PROFILE — the fully-disabled path never
    reaches here; with only the profiler armed, the span annotations and
    the measured-side ledger fold still happen but nothing is journaled).

    The digest is the stage's boundary-table content digest
    (_stage_table_name) — the canonical stage fingerprint the EWMA history
    keys on; the root stage (no boundary) keys under the query fingerprint.
    Capacity is the padded power-of-2 class _pad_capacity would
    materialize, so measured rows vs capacity shows the padding waste."""
    try:
        from ..runtime import flight_recorder as _fr

        rows_out = int(out.num_rows)
        stage_rows[idx] = rows_out
        rows_in = sum(stage_rows.get(d, 0) for d in st.deps)
        nbytes = 0
        for c in out.columns:
            nbytes += int(getattr(c.data, "nbytes", 0))
            if getattr(c, "mask", None) is not None:
                nbytes += int(getattr(c.mask, "nbytes", 0))
        digest = (st.scan.table_name if st.scan is not None
                  else f"root:{query_fp}")
        capacity = _capacity_class(rows_out)
        # the span carries the measurements too: record_query sums
        # stage_bytes into the query's measured working set at close
        _tel.annotate(stage_digest=digest, stage_rows_in=rows_in,
                      stage_rows_out=rows_out, stage_capacity=capacity,
                      stage_bytes=nbytes, stage_wall_ms=round(wall_ms, 3))
        if _profile_on():
            # measured side of the model-vs-measured ledger: what the
            # stage actually touched, against the compile-time prediction
            from ..runtime import profiler as _prof
            _prof.record_measured(digest, nbytes=nbytes, wall_ms=wall_ms)
        if os.environ.get("DSQL_HISTORY_FILE"):
            _fr.record_stage(digest, rows_in=rows_in, rows_out=rows_out,
                             capacity=capacity, nbytes=nbytes,
                             wall_ms=wall_ms, query_fp=query_fp)
        if _events_on():
            from ..runtime import events as _ev
            _ev.publish("stage.done", digest=digest, index=idx,
                        rows_out=rows_out, bytes=nbytes,
                        wall_ms=round(wall_ms, 3))
    except Exception:  # recording must never fail a stage
        _tel.inc("history_errors")
        logger.debug("stage stat capture failed", exc_info=True)


def _execute_stage_graph(graph: StageGraph, context, query_fp: str,
                         split_limit: Optional[int],
                         run_program: Callable) -> Optional[Table]:
    """Run a stage DAG: dependencies first, independent stages concurrently;
    ``run_program(plan, context, query_fp, split_limit, in_stage=True)``
    runs one stage's program.

    Any stage that cannot run compiled (unsupported shape, runtime-flag
    fallback) fails the whole graph to the eager executor — partial staged
    execution would still pay the materialization round trips without the
    single-dispatch payoff.  Temp tables are unregistered on EVERY path,
    exceptions included.
    """
    with _tel.span("stage_graph", stages=len(graph.stages)):
        return _execute_stage_graph_inner(graph, context, query_fp,
                                          split_limit, run_program)


def _execute_stage_graph_inner(graph: StageGraph, context, query_fp: str,
                               split_limit: Optional[int],
                               run_program: Callable) -> Optional[Table]:
    _tel.inc("stage_graphs")
    stages = graph.stages
    nst = len(stages)
    root_idx = nst - 1
    registered: List[str] = []
    rt = _res.current()
    tel_trace = _tel.current_trace()
    tel_parent = _tel.current_span()
    # measured per-stage output rows (flight recorder only): a stage's
    # dependencies complete before it runs, so dependents read their
    # inputs' real row counts here.  Plain dict ops — GIL-atomic.
    stage_rows: Dict[int, int] = {}

    def run_stage_once(idx: int, attempt: int) -> Optional[Table]:
        _tel.inc("stage_execs")
        if attempt > 0:
            # the replay path is itself an injection site (checked FIRST,
            # so arming both sites sabotages the replay rather than just
            # re-firing the original), so CI can prove a sabotaged replay
            # still degrades cleanly
            _faults.maybe_fail("stage_replay")
        _faults.maybe_fail("stage_exec")
        st = stages[idx]
        # subplan result cache: a non-root stage's boundary name is a
        # content digest of its subtree (scan uids included), so an
        # OVERLAPPING query sharing the subplan replays the
        # materialized stage output and skips its device execution —
        # data reuse on top of the program reuse the stage cache gives
        skey = None
        cache = _rcache.get_cache()
        if st.scan is not None and cache.enabled():
            skey = _rcache.stage_key(st.scan.table_name)
            hit = cache.get(skey)
            if hit is not None:
                _tel.inc("result_cache_subplan_hits")
                _tel.annotate(subplan_cache="hit",
                              result_cache_tier=hit[1])
                return hit[0]
        out = run_program(st.plan, context, query_fp, split_limit,
                          in_stage=True)
        if skey is not None and out is not None:
            cache.put(skey, out)
        return out

    def run_stage(idx: int) -> Optional[Table]:
        # worker threads re-enter the query's supervision scope AND its
        # telemetry trace (thread locals do not cross pools).
        # Checkpointed stage replay: a transient failure re-executes ONLY
        # this stage — its dependencies' outputs are already materialized
        # as registered boundary temps, so the retry rescans them instead
        # of re-running the stages that produced them.  The failure
        # domain is one stage, not the graph (let alone the query).
        with _res.scoped(rt), _tel.scoped(tel_trace, tel_parent), \
                _tel.span("stage", index=idx, heavy=stages[idx].heavy):
            if stages[idx].est_rows is not None:
                _tel.annotate(stage_est_rows=stages[idx].est_rows)
            attempt = 0
            while True:
                _res.check("stage_exec")
                try:
                    t0s = time.perf_counter()
                    out = run_stage_once(idx, attempt)
                    if out is not None:
                        # what tells one stage of a trace from another:
                        # its place, its weight, and what it handed on
                        rows = int(out.num_rows)
                        _tel.annotate(rows_out=rows,
                                      capacity=_capacity_class(rows))
                    if out is not None and (
                            os.environ.get("DSQL_HISTORY_FILE")
                            or _profile_on()):
                        _record_stage_stats(
                            stages[idx], idx, out, query_fp, stage_rows,
                            (time.perf_counter() - t0s) * 1e3)
                    return out
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    err = _res.classify(e)
                    if err is None:
                        raise
                    if not isinstance(err, _res.TransientError):
                        raise err if err is e else err from e
                    attempt += 1
                    if attempt > _res.retry_max():
                        raise err if err is e else err from e
                    saved = len(registered)
                    _tel.inc("retries")
                    _tel.inc("stage_replays")
                    _tel.inc("stage_replay_saved_stages", saved)
                    _tel.annotate(stage_replays=attempt,
                                  stage_replay_saved=saved)
                    logger.warning(
                        "stage %d failed transiently (%s); replaying it "
                        "from %d materialized boundary stage(s) — retry "
                        "%d/%d", idx, str(err)[:200], saved, attempt,
                        _res.retry_max())
                    _res.backoff(attempt, "stage_exec")

    def stage_error(e: Exception) -> Optional[BaseException]:
        """None => degrade the whole graph to eager; else raise this.

        Only TRANSIENT failures degrade: a stage's own compile ladder
        already resolved everything recoverable inside ``run_program``, so
        an exception escaping a stage is either a supervision verdict
        (deadline/cancel), a user error, or a broken invariant — all of
        which must surface typed, not silently re-run eager."""
        err = _res.classify(e)
        if err is None or not isinstance(err, _res.TransientError):
            return err if err is not None else e
        if os.environ.get("DSQL_EAGER_FALLBACK", "1") == "0":
            return err
        _tel.inc("degradations")
        _tel.annotate(degraded_to="eager")
        logger.warning("stage failed (%s); degrading graph to eager",
                       str(err)[:200])
        return None

    try:
        workers = _compile_workers(nst)
        if workers == 1:
            # serial: the list is already topological
            for idx, st in enumerate(stages):
                _res.check("stage_graph")
                try:
                    out = run_stage(idx)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except (_res.DeadlineExceeded, _res.QueryCancelled):
                    raise
                except Exception as e:
                    raised = stage_error(e)
                    if raised is not None:
                        raise raised from (None if raised is e else e)
                    return None
                if out is None:
                    return None
                if idx == root_idx:
                    return out
                _register_stage_table(context, st.scan.table_name, out)
                registered.append(st.scan.table_name)
            return None  # unreachable: the root returns above

        from concurrent.futures import (FIRST_COMPLETED, ThreadPoolExecutor,
                                        wait as _fwait)
        pending = set(range(nst))
        done: set = set()
        futs: Dict[object, int] = {}
        failed = False
        aborted = False
        result: Optional[Table] = None
        pool = ThreadPoolExecutor(workers)
        try:
            while (pending or futs) and not failed:
                # cancellation/deadline must cut the GRAPH, not only the
                # stage bodies: abandon queued stages, orphan in-flight
                # compiles (the finally's shutdown(wait=False) leaves them
                # to finish in the background — their programs still land
                # in the cache for the next query)
                _res.check("stage_graph")
                for i in sorted(pending):
                    if all(d in done for d in stages[i].deps):
                        pending.discard(i)
                        futs[pool.submit(run_stage, i)] = i
                if not futs:
                    break
                # bounded wait so a cancel/deadline arriving mid-compile is
                # observed within ~100 ms instead of after the compile
                finished, _ = _fwait(list(futs), timeout=0.1,
                                     return_when=FIRST_COMPLETED)
                for f in finished:
                    i = futs.pop(f)
                    try:
                        out = f.result()
                    except (KeyboardInterrupt, SystemExit):
                        raise
                    except Exception as e:
                        raised = stage_error(e)
                        if raised is not None:
                            raise raised from (None if raised is e else e)
                        failed = True
                        continue
                    if out is None:
                        failed = True
                        continue
                    if i == root_idx:
                        result = out
                    else:
                        _register_stage_table(
                            context, stages[i].scan.table_name, out)
                        registered.append(stages[i].scan.table_name)
                    done.add(i)
        except BaseException:
            aborted = True
            raise
        finally:
            pool.shutdown(wait=not aborted, cancel_futures=aborted)
        return None if failed else result
    finally:
        for name in registered:
            _unregister_stage_table(context, name)
