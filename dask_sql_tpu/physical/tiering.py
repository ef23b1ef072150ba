"""Tiered execution: a first arrival must not pay the compile wall.

When a plan's programs are not yet available (in memory OR in the
persistent program store), the query is answered IMMEDIATELY on the eager
tier (the RelExecutor machinery EXPLAIN ANALYZE uses) while the programs
compile in background daemon threads bounded by ``DSQL_COMPILE_WORKERS``
(and its failure backoff); the next arrival of the same plan shape runs
compiled.  Flare's tiered native compilation (PAPERS.md).  It honors:
  - the degradation ladder: ``DSQL_EAGER_FALLBACK=0`` forbids the eager
    tier entirely (there is no tier to serve from), so compiles stay
    synchronous;
  - quarantine / exile / runtime verdicts: a plan with a standing verdict is
    "decided" — it runs the normal path (which serves eager with the proper
    counters) and never spawns background work;
  - the workload manager: background compiles bypass admission entirely, so
    they hold no scheduler slot and no memory-broker reservation;
  - what the eager tier costs where it would have to sort: under the TPU
    strategy a plan that joins two big inputs pays its compile on the first
    arrival (``compiled._eager_bridge_sorts``, handed in as ``bridges``),
    minutes sooner than the eager tier's own programs would have compiled.
Disable with ``DSQL_TIERED=0`` (tests pin this off; production default on).

A probe keys a program (``identity.program_key``) and sizes it
(``caps.starting_caps``) as the request's path does: what it calls ready
is what the path then finds.  The background thread is handed the entry
point; nothing here imports the tracer.
"""
from __future__ import annotations

import ctypes
import logging
import os
import threading as _threading
from collections import OrderedDict
from typing import Callable, Optional

from ..plan.nodes import RelNode
from ..runtime import (program_store as _pstore, quarantine as _quar,
                       telemetry as _tel)
from .caps import _bounded_put, split_hint, starting_caps
from .identity import (Unsupported, _maybe_parameterize, _pstore_digest,
                       program_key)
from .programs import _compile_workers, _events_on, decided as _decided
from .stage_exec import _partition_plan
from .stages import heavy_count as _heavy_count, stage_budget

logger = logging.getLogger(__name__)

_tier_lock = _threading.Lock()
_tier_done: "OrderedDict[tuple, bool]" = OrderedDict()  # attempted keys
_tier_inflight: set = set()
_tier_local = _threading.local()          # .bg guards recursion
_bg_sem: Optional[object] = None          # bounds concurrent bg compiles


def _tiering_enabled() -> bool:
    # the eager tier IS the eager fallback; with it forbidden there is
    # nothing to serve the first arrival from
    return (os.environ.get("DSQL_TIERED", "1") != "0"
            and os.environ.get("DSQL_EAGER_FALLBACK", "1") != "0")


def _program_decided(pk, context) -> bool:
    """True when the normal path needs NO fresh XLA compile for this one
    program: an in-memory entry (or _UNSUPPORTED verdict), a runtime-eager
    exile, a standing quarantine verdict, or a persistent-store entry."""
    caps = starting_caps(pk, context, count=False)
    key = (pk.key, tuple(sorted(caps.items())))
    runtime_key = (pk.key, tuple(t.uid for _, t, _ in pk.scans))
    if _decided(key, runtime_key):
        return True
    qstore = _quar.get_store()
    if qstore.enabled() and _quar.program_key(pk.key) in qstore.entries():
        # skip/half-open-probe semantics belong to the normal path
        return True
    return _pstore.get_store().contains(_pstore_digest(pk.key))


def _probe_single(plan: RelNode, context) -> bool:
    """Readiness of ONE program, under the key the request's path gives
    it."""
    try:
        pk = program_key(plan, context)
    except Unsupported:
        return True  # needs no compile; the normal path serves it eager
    return _program_decided(pk, context)


def _programs_ready(plan: RelNode, context, budget: int) -> bool:
    """Would the normal compiled path answer without paying a fresh XLA
    compile?  Whole-plan programs are probed exactly; stage graphs are
    probed at their LEAF stages (deeper stages scan boundary temps that do
    not exist before execution) — with a warm store every stage hits, so
    all-leaves-warm is the right readiness signal."""
    if _heavy_count(plan) <= budget:
        return _probe_single(plan, context)
    graph = _partition_plan(plan, budget, context)
    if len(graph.stages) <= 1:
        return _probe_single(plan, context)
    return all(_probe_single(st.plan, context)
               for st in graph.stages if not st.deps)


def _release_freed_heap() -> None:
    """Hand the allocator's free pages back to the OS.  An XLA compile of a
    stage program peaks at gigabytes of host memory and glibc keeps what
    the compiler frees (2.1 GB still resident after one limb-kernel
    compile, 0.7 GB after the trim — CHANGES.md, PR 23), so a server that
    has compiled a few programs would hold tens of GB it does not use."""
    try:
        ctypes.CDLL(None).malloc_trim(0)
    except (OSError, AttributeError):  # not glibc
        pass


def _background_compile(run: Callable, plan: RelNode, context, base_key,
                        trace_id: Optional[str] = None) -> None:
    """Compile (and once-execute) this plan's stage programs off the query
    path.  Runs in a daemon thread with fresh thread-locals: no deadline,
    no trace, no scheduler slot, no memory-broker reservation — exactly
    the full normal pipeline minus supervision, so learned caps, the
    program cache, quarantine interplay, and the persistent store all
    populate the same way a foreground compile would.  ``run`` is the
    compiled tier's entry point.  ``trace_id`` is the
    scheduling query's watchtower ID, captured at spawn time because a
    daemon thread's fresh thread-locals can't see the caller's trace."""
    _tier_local.bg = True
    trace = None
    try:
        with _bg_sem:
            # a daemon thread has fresh thread-locals: without its own
            # trace these compile spans ran OUTSIDE any QueryTrace and
            # never reached DSQL_CHROME_TRACE_DIR.  A dedicated
            # background_compile trace captures them; close_background_trace
            # exports it without counting a query or arming the slow log.
            trace = _tel.QueryTrace(f"<background-compile:{base_key[0][:48]}>")
            trace.root.name = "background_compile"
            if trace_id:
                trace.root.attrs["trace_id"] = trace_id
            try:
                try:
                    with _tel.scoped(trace, trace.root):
                        run(plan, context)
                finally:
                    # before the compile counts as done: a query that
                    # finds the program ready does not run beside the trim
                    _release_freed_heap()
                _tel.inc("background_compiles_done")
                if _events_on():
                    from ..runtime import events as _ev
                    _ev.publish("compile.background.done", trace=trace_id,
                                plan=base_key[0][:48])
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:
                trace.root.attrs["error"] = type(e).__name__
                _tel.inc("background_compile_errors")
                if _events_on():
                    from ..runtime import events as _ev
                    _ev.publish("compile.background.error", trace=trace_id,
                                plan=base_key[0][:48],
                                error=type(e).__name__)
                logger.warning("background compile failed (%s: %s)",
                               type(e).__name__, str(e)[:200])
    finally:
        if trace is not None:
            try:
                _tel.close_background_trace(trace)
            except Exception:  # pragma: no cover - telemetry is advisory
                logger.debug("background trace close failed", exc_info=True)
        _tier_local.bg = False
        with _tier_lock:
            _tier_inflight.discard(base_key)
            _bounded_put(_tier_done, base_key, True)


def _tier_serve_eager(plan: RelNode, context, base_key, budget: int,
                      split_limit: Optional[int], run: Callable,
                      bridges: Callable) -> bool:
    """The tier decision: True => answer THIS arrival on the eager tier
    (the caller returns None) while the programs build in the background
    (``run``, the compiled tier's entry point, in a thread of its own).
    False for a plan the eager tier would answer later than its own compile
    (``bridges(plan, context, on_tpu)``): that arrival pays the compile."""
    if split_limit is not None or not _tiering_enabled() \
            or getattr(_tier_local, "bg", False) \
            or bridges(plan, context, base_key[2]):
        return False
    global _bg_sem
    with _tier_lock:
        if base_key in _tier_done:
            return False  # background attempt finished; run the verdict
        if base_key in _tier_inflight:
            return True   # still compiling behind the scenes
    if _programs_ready(plan, context, budget):
        return False
    with _tier_lock:
        if base_key in _tier_done or base_key in _tier_inflight:
            return True
        _tier_inflight.add(base_key)
        if _bg_sem is None:
            _bg_sem = _threading.Semaphore(_compile_workers())
    # daemon threads (not a pool): process exit must never block on a
    # wedged XLA build, and the semaphore bounds real concurrency
    tid = None
    if _events_on():
        try:
            from ..runtime import events as _ev
            tid = _ev.current_trace_id()
        except Exception:
            tid = None
    _threading.Thread(target=_background_compile,
                      args=(run, plan, context, base_key, tid),
                      name="dsql-bg-compile", daemon=True).start()
    return True


def inflight_background_compiles() -> list:
    """Plan fingerprints currently compiling in background daemon threads
    (for ``system.active`` / ``/v1/engine``)."""
    with _tier_lock:
        return [k[0] for k in _tier_inflight]


def tier_probe(plan: RelNode, context, bridges: Callable) -> str:
    """Predict (without executing) which tier would answer this plan NOW:
    ``eager`` (not compilable / compile off), ``compiled`` (programs warm),
    ``eager-compiling`` (cold + tiering serves eager while building), or
    ``compiled-cold`` (tiering off, or ``bridges`` says so: the arrival
    pays the compile)."""
    if os.environ.get("DSQL_COMPILE", "1") == "0":
        return "eager"
    # literals hoist into params BEFORE fingerprinting, as at the entry
    plan = _maybe_parameterize(plan, count=False)
    try:
        base_key = program_key(plan, context).key
    except Unsupported:
        return "eager"
    try:
        if _programs_ready(plan, context,
                           stage_budget(split_hint(base_key))):
            return "compiled"
    except Exception:  # pragma: no cover - probe must never fail a query
        logger.debug("tier probe failed", exc_info=True)
        return "eager"
    with _tier_lock:
        inflight = base_key in _tier_inflight
    if inflight or (_tiering_enabled()
                    and not bridges(plan, context, base_key[2])):
        return "eager-compiling"
    return "compiled-cold"
