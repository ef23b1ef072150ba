"""Device-level query profiler: HBM sampling, XLA cost capture, ledger.

Armed by ``DSQL_PROFILE=1`` and costing nothing when disabled: every hot
path checks the env var BEFORE importing this module (the exact
``DSQL_HISTORY_FILE``/flight-recorder discipline — tests assert this
module never lands in ``sys.modules`` for an unprofiled query).  Three
concerns live here:

1. **Per-device memory sampling.**  Every local device's
   ``memory_stats()`` (HBM bytes in use / peak / limit) folds into the
   ``profile_hbm_*`` gauges and a bounded ring of timestamped snapshots.
   A daemon sampler thread ticks every ``DSQL_PROFILE_SAMPLE_MS``
   (default 500); every query completion also samples, so short-lived
   processes still leave snapshots.  CPU devices report no memory stats
   — rows degrade to zeros, never to an error.

2. **XLA cost-model capture.**  ``compiled.cost_analysis()`` (flops,
   bytes accessed, transcendentals) normalizes through
   :func:`cost_summary` at compile time and persists alongside the
   program-store entry (``"cost"`` key, missing-tolerant), so a warm
   process has cost estimates with zero recompilation.  Backends
   without a cost model yield ``None`` and every consumer degrades:
   EXPLAIN PROFILE prints ``n/a``, the scheduler skips its rung, store
   entries simply lack the key.

3. **Model-vs-measured ledger.**  Predicted bytes/flops accumulate per
   (query fingerprint, program digest); measured bytes/ms fold in from
   stage records.  The scheduler's estimate ladder reads
   :func:`plan_cost_bytes` as its fourth rung (history → chunked →
   stats → **cost_model** → heuristic, ``est_source="cost_model"``),
   and the predicted-vs-measured error is journaled on flight-recorder
   envelopes (``cost_err``) exactly like the history/stats rungs'
   errors — the EWMA fold-in goes through
   ``flight_recorder._observe_stat`` under ``cost_bytes``/``cost_flops``
   keys when a history file is armed.
"""
from __future__ import annotations

import logging
import math
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from . import telemetry as _tel

logger = logging.getLogger(__name__)

#: bounded snapshot ring: at the default 500 ms cadence this holds the
#: last minute of device-memory truth without growing
RING_LEN = 120


def enabled() -> bool:
    """True when profiling is armed (``DSQL_PROFILE`` set and not 0)."""
    return os.environ.get("DSQL_PROFILE", "0").strip() not in ("", "0")


def sample_interval_ms() -> float:
    try:
        ms = float(os.environ.get("DSQL_PROFILE_SAMPLE_MS", "500") or 500)
    except ValueError:
        ms = 500.0
    return max(ms, 10.0)


_lock = threading.Lock()
_ring: deque = deque(maxlen=RING_LEN)
_sampler_started = False

# model-vs-measured ledger: query fingerprint -> program digest ->
# predicted {"flops","bytes","transcendentals"}; and per-digest measured
# fold-ins.  Keyed per digest so repeat executions OVERWRITE instead of
# double-counting.
_ledger: Dict[str, Dict[str, Dict[str, float]]] = {}
_measured: Dict[str, Dict[str, float]] = {}


def _fp_key(query_fp: Optional[str]) -> Optional[str]:
    """Normalize compiled.py's ``query_fp`` (the ROOT plan's canonical
    compiled-pipeline text, threaded to every compile/store site) into
    the ledger key.  Writers (record_program_cost) and the reader
    (plan_cost_bytes, which recomputes the text via ``_fp_plan``) MUST
    agree, so both go through here."""
    if not query_fp:
        return None
    from .kvstore import digest_key
    return digest_key(("cost", str(query_fp)))


# ---------------------------------------------------------------------------
# device memory sampling
# ---------------------------------------------------------------------------

def device_memory_rows() -> List[Dict[str, Any]]:
    """One row per local device.  ``memory_stats()`` may be None or
    absent entirely (CPU backends) — such devices report zeros."""
    rows: List[Dict[str, Any]] = []
    try:
        import jax
        devices = jax.local_devices()
    except Exception:  # jax missing/not initialized: no rows, no error
        return rows
    for d in devices:
        try:
            mem = d.memory_stats() or {}
        except Exception:
            mem = {}
        rows.append({
            "id": int(getattr(d, "id", len(rows))),
            "platform": str(getattr(d, "platform", "?")),
            "kind": str(getattr(d, "device_kind", "?")),
            "bytes_in_use": int(mem.get("bytes_in_use", 0) or 0),
            "peak_bytes_in_use": int(mem.get("peak_bytes_in_use", 0) or 0),
            "bytes_limit": int(mem.get("bytes_limit", 0) or 0),
        })
    return rows


def sample() -> List[Dict[str, Any]]:
    """One snapshot: per-device rows into the ring + summed gauges."""
    rows = device_memory_rows()
    _tel.REGISTRY.set_gauge("profile_hbm_bytes_in_use",
                            sum(r["bytes_in_use"] for r in rows))
    _tel.REGISTRY.set_gauge("profile_hbm_peak_bytes",
                            sum(r["peak_bytes_in_use"] for r in rows))
    _tel.REGISTRY.set_gauge("profile_hbm_bytes_limit",
                            sum(r["bytes_limit"] for r in rows))
    _tel.inc("profile_samples")
    with _lock:
        _ring.append({"unix": time.time(), "devices": rows})
    return rows


def snapshots() -> List[Dict[str, Any]]:
    with _lock:
        return list(_ring)


def ensure_sampler() -> None:
    """Start the daemon sampling thread once (no-op when disabled)."""
    global _sampler_started
    if not enabled():
        return
    with _lock:
        if _sampler_started:
            return
        _sampler_started = True
    threading.Thread(target=_sample_loop, name="dsql-profiler",
                     daemon=True).start()


def _sample_loop() -> None:
    while enabled():
        try:
            sample()
        except Exception:  # sampling must never hurt the engine
            logger.debug("profiler sample failed", exc_info=True)
        time.sleep(sample_interval_ms() / 1e3)


# ---------------------------------------------------------------------------
# XLA cost-model capture
# ---------------------------------------------------------------------------

def cost_summary(compiled) -> Optional[Dict[str, float]]:
    """Normalize ``compiled.cost_analysis()`` to a small plain dict
    (``flops`` / ``bytes`` / ``transcendentals``), or None when the
    backend has no cost model (absent method, raise, None, empty or
    non-finite values) — the universal ``n/a`` signal downstream."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    # jax <= 0.4.x returns [dict] (one per computation); newer returns
    # the dict directly
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None

    def num(key: str) -> float:
        try:
            v = float(ca.get(key, 0.0) or 0.0)
        except (TypeError, ValueError):
            return 0.0
        return v if math.isfinite(v) and v > 0 else 0.0

    out = {"flops": num("flops"), "bytes": num("bytes accessed"),
           "transcendentals": num("transcendentals")}
    if not (out["flops"] or out["bytes"]):
        return None
    return out


# ---------------------------------------------------------------------------
# the model-vs-measured ledger
# ---------------------------------------------------------------------------

def record_program_cost(query_fp: Optional[str], digest: str,
                        cost: Optional[Dict[str, float]]) -> None:
    """Register one program's predicted cost under a query fingerprint
    (at compile time or program-store load time).  None cost = no-op."""
    key = _fp_key(query_fp)
    if key is None or not cost:
        return
    with _lock:
        _ledger.setdefault(key, {})[str(digest)] = dict(cost)
    _tel.inc("profile_cost_captures")
    if os.environ.get("DSQL_HISTORY_FILE"):
        # fold into the flight-recorder EWMA so the cost estimate
        # survives the process (the scheduler rung's warm-read path)
        try:
            from . import flight_recorder as _fr
            _fr._observe_stat(key,
                              cost_bytes=float(cost.get("bytes", 0.0)),
                              cost_flops=float(cost.get("flops", 0.0)))
        except Exception:
            logger.debug("cost EWMA fold failed", exc_info=True)


def record_measured(digest: str, nbytes: Optional[int] = None,
                    wall_ms: Optional[float] = None,
                    device_ms: Optional[float] = None) -> None:
    """Fold one stage's measured truth into the ledger's measured side."""
    with _lock:
        ent = _measured.setdefault(str(digest), {})
        if nbytes is not None:
            ent["bytes"] = float(nbytes)
        if wall_ms is not None:
            ent["ms"] = float(wall_ms)
        if device_ms is not None:
            ent["device_ms"] = float(device_ms)


def program_costs(query_fp: Optional[str]) -> Dict[str, Dict[str, float]]:
    """Predicted costs per program digest for one query fingerprint
    (each dict also carries the measured fold-ins when present)."""
    key = _fp_key(query_fp)
    if key is None:
        return {}
    with _lock:
        out = {}
        for digest, cost in _ledger.get(key, {}).items():
            row = dict(cost)
            row.update({f"measured_{k}": v
                        for k, v in _measured.get(digest, {}).items()})
            out[digest] = row
        return out


def plan_cost_bytes(plan, context) -> Optional[int]:
    """The scheduler's ``cost_model`` rung: predicted working-set bytes
    = XLA "bytes accessed" summed over the plan's captured programs.
    The key is recomputed from the plan exactly the way the compiled
    pipeline fingerprints its root (``_fp_plan`` — an uncompilable plan
    never produced a ledger entry, so Unsupported here is just None).
    Falls back to the flight-recorder-persisted cost EWMA when this
    process hasn't compiled (or store-loaded) the plan yet.  None =
    nothing captured, the caller keeps the shape heuristic."""
    try:
        from ..physical.identity import _fp_plan
        key = _fp_key(_fp_plan(plan, context, []))
    except Exception:
        return None
    if key is None:
        return None
    with _lock:
        costs = _ledger.get(key)
        total = (sum(c.get("bytes", 0.0) for c in costs.values())
                 if costs else 0.0)
    if total <= 0 and os.environ.get("DSQL_HISTORY_FILE"):
        try:
            from . import flight_recorder as _fr
            total = float((_fr.get_stats(key) or {}).get("cost_bytes", 0.0)
                          or 0.0)
        except Exception:
            total = 0.0
    return int(total) if total > 0 else None


def cost_error(predicted_bytes: Optional[float],
               measured_bytes: Optional[float]) -> Optional[float]:
    """Relative model error |predicted - measured| / measured, the same
    shape the bench journals for the history/stats rungs."""
    if not predicted_bytes or not measured_bytes or measured_bytes <= 0:
        return None
    return abs(float(predicted_bytes) - float(measured_bytes)) \
        / float(measured_bytes)


def on_query_complete(report) -> None:
    """Per-query hook from telemetry._close_trace (profile-gated there):
    keep the sampler alive and take one completion-time snapshot."""
    ensure_sampler()
    try:
        sample()
    except Exception:
        logger.debug("completion sample failed", exc_info=True)


def engine_section() -> Dict[str, Any]:
    """The ``profile`` section of ``GET /v1/engine``."""
    with _lock:
        plans = len(_ledger)
        programs = sum(len(v) for v in _ledger.values())
        last = _ring[-1] if _ring else None
    return {
        "enabled": True,
        "sampleMs": sample_interval_ms(),
        "samples": int(_tel.REGISTRY.get("profile_samples")),
        "costCaptures": int(_tel.REGISTRY.get("profile_cost_captures")),
        "costPlans": plans,
        "costPrograms": programs,
        "lastSnapshot": last,
    }


def reset() -> None:
    """Test hook: drop ledger + ring (the sampler flag survives)."""
    with _lock:
        _ledger.clear()
        _measured.clear()
        _ring.clear()
