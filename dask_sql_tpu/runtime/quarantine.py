"""Cross-process failure quarantine + compile watchdog.

The failure domain this module contains: compile is both the dominant cost
(per-query compiles up to 615 s on a backend before the v5e bring-up) and
the dominant failure site (10 compile_errors in one benchmark run), and a
compile that crashes or wedges the XLA helper dies WITH the process — the
in-memory exile verdict (physical/programs.py ``_cache[key] = _UNSUPPORTED``)
is gone on restart, so every new process re-pays the doomed compile.
Flare (PAPERS.md) keeps the same discipline for Spark native compilation:
a hung or crashing program build must be remembered, not re-attempted.

Two cooperating parts:

**Quarantine store.**  A small JSON file (``DSQL_QUARANTINE_FILE``;
unset = disabled) of crash/hang verdicts keyed by a digest of the
canonical program key (plan fingerprint + input-layout fingerprint +
backend strategy) folded with the device fingerprint — the same
content-addressing discipline as the learned-caps store
(``DSQL_CAPS_FILE``), so a verdict can only ever match the same program
over the same data layout on the same device class.  A FATAL compile
verdict or a watchdog hang mark persists with an expiry
(``DSQL_QUARANTINE_TTL_S``); while an entry is live, every process
sharing the file serves that plan via the eager fallback *without a
compile attempt*.  After expiry the store goes **half-open**: exactly one
caller is handed a ``"probe"`` verdict (the entry's expiry is pushed out
by ``DSQL_QUARANTINE_PROBE_S`` so concurrent callers — and other
processes — keep skipping while the probe runs); a successful compile
clears the entry, a failed probe re-arms it for a full TTL.  Corrupt or
unreadable store files read as empty — quarantine is an optimization,
never a crash source.

**Compile watchdog.**  ``DSQL_COMPILE_WATCHDOG_S`` arms a monitor thread
over every compile+first-call section.  The cooperative deadline
checkpoints (``resilience.check``) cannot fire while the worker is wedged
*inside* XLA; the watchdog can — when a watched section exceeds the wall
budget it increments ``watchdog_trips`` and marks the program's
fingerprint suspect (verdict ``"hang"``) in the quarantine store, so even
if the process never returns (or is killed by the operator), the next
process refuses the same compile.  A section that eventually finishes
cleanly lifts its own suspect mark — the watchdog records *wedged right
now*, not *slow once*.

Telemetry: ``quarantine_skips`` / ``quarantine_probes`` /
``quarantine_marks`` / ``watchdog_trips`` (all in the stable-name
contract, runtime/telemetry.py).
"""
from __future__ import annotations

import hashlib
import logging
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

from . import kvstore as _kv
from . import telemetry as _tel

logger = logging.getLogger(__name__)

DEFAULT_TTL_S = 3600.0
DEFAULT_PROBE_S = 60.0

VERDICTS = ("fatal", "hang")


def _env_float(name: str, default: float) -> float:
    try:
        raw = os.environ.get(name, "")
        return float(raw) if raw else default
    except ValueError:
        return default


_device_fp_cache: Optional[str] = None


def device_fingerprint() -> str:
    """Stable identity of the device class this process compiles for; a
    verdict earned on one backend must never gate a different one (the
    same plan that wedges the TPU compiler is fine on XLA:CPU)."""
    global _device_fp_cache
    if _device_fp_cache is None:
        try:
            import jax
            d = jax.local_devices()[0]
            _device_fp_cache = (f"{d.platform}:{getattr(d, 'device_kind', '?')}"
                                f":{jax.local_device_count()}")
        except Exception:  # pragma: no cover - jax not initialized
            _device_fp_cache = "unknown"
    return _device_fp_cache


def program_key(base_key) -> str:
    """Content digest of a compiled program's identity: the executor's
    base key (plan fingerprint, input-layout fingerprint, strategy) folded
    with the device fingerprint."""
    h = hashlib.blake2b(repr(base_key).encode(), digest_size=16)
    h.update(b"|" + device_fingerprint().encode())
    return h.hexdigest()


class QuarantineStore:
    """JSON-file store of crash/hang verdicts with expiry + half-open
    probes.  Disk plumbing rides runtime/kvstore.py (shared with the
    learned-caps file and the program store's index): reads are
    mtime-cached and corrupt-tolerant; writes are read-merge-replace with
    an atomic rename, so concurrent writers can lose a race — costing one
    re-mark — but never corrupt."""

    def __init__(self, path: Optional[str] = None):
        self._path_override = path
        self._file = _kv.MtimeCachedJsonFile(self.path)

    # -- config (env-read per call so tests/operators flip without restart)
    def path(self) -> Optional[str]:
        return self._path_override or os.environ.get("DSQL_QUARANTINE_FILE")

    def enabled(self) -> bool:
        return bool(self.path())

    def ttl_s(self) -> float:
        return max(_env_float("DSQL_QUARANTINE_TTL_S", DEFAULT_TTL_S), 0.0)

    def probe_ttl_s(self) -> float:
        return max(_env_float("DSQL_QUARANTINE_PROBE_S", DEFAULT_PROBE_S),
                   0.001)

    # -- disk (runtime/kvstore.py: mtime-cached tolerant reads, atomic
    # tmp+rename writes — a broken quarantine file must degrade to 'no
    # quarantine', never fail a query) ------------------------------------
    def _read(self) -> Dict[str, dict]:
        return self._file.read()

    def _write(self, data: Dict[str, dict]) -> None:
        self._file.write(data)

    # -- verdicts -----------------------------------------------------------
    def check(self, key: str) -> Optional[str]:
        """``"quarantined"`` (skip the compile), ``"probe"`` (half-open:
        THIS caller re-attempts while everyone else keeps skipping), or
        None (no verdict on record)."""
        if not self.enabled():
            return None
        data = self._read()
        entry = data.get(key)
        if entry is None:
            return None
        now = time.time()
        if now < float(entry.get("expires_at", 0)):
            return "quarantined"
        # expired: half-open.  Push the expiry out by the probe window and
        # persist BEFORE returning, so concurrent checkers (and other
        # processes) see a live entry and skip while this probe runs.
        entry["expires_at"] = now + self.probe_ttl_s()
        entry["probing"] = True
        data[key] = entry
        self._write(data)
        return "probe"

    def mark(self, key: str, verdict: str, reason: str = "") -> None:
        """Record (or re-arm after a failed probe) a crash/hang verdict."""
        if not self.enabled():
            return
        data = self._read()
        prev = data.get(key) or {}
        now = time.time()
        data[key] = {
            "verdict": verdict,
            "reason": str(reason)[:200],
            "at": now,
            "expires_at": now + self.ttl_s(),
            "strikes": int(prev.get("strikes", 0)) + 1,
        }
        self._write(data)
        _tel.inc("quarantine_marks")
        logger.warning("quarantined program %s (%s): %s",
                       key[:12], verdict, str(reason)[:120])

    def clear(self, key: str) -> None:
        """Lift a verdict (successful probe, or a watched section that
        finished after its watchdog trip)."""
        if not self.enabled():
            return
        data = self._read()
        if key not in data:
            return
        del data[key]
        self._write(data)
        logger.info("quarantine lifted for program %s", key[:12])

    def entries(self) -> Dict[str, dict]:
        return self._read()


_store = QuarantineStore()


def get_store() -> QuarantineStore:
    """The process-global quarantine store (env-configured, like the
    result cache and the workload manager)."""
    return _store


# ---------------------------------------------------------------------------
# compile watchdog
# ---------------------------------------------------------------------------

class CompileWatchdog:
    """Monitor thread over compile/first-call sections.

    A wedged XLA compile holds the GIL-released worker inside native code
    where no cooperative ``resilience.check`` can run; this thread is the
    host-side supervisor that still observes wall time.  It cannot unwedge
    the worker (Python cannot interrupt native code) — what it CAN do is
    persist the hang verdict so the cost is paid at most once per process
    lineage, which is exactly the cross-process guarantee the quarantine
    store exists for."""

    _POLL_S = 0.1

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[int, list] = {}  # token -> [deadline, key, label, fired]
        self._next_token = 0
        self._thread: Optional[threading.Thread] = None

    def budget_s(self) -> float:
        return max(_env_float("DSQL_COMPILE_WATCHDOG_S", 0.0), 0.0)

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, name="dsql-compile-watchdog", daemon=True)
            self._thread.start()

    def _loop(self) -> None:
        while True:
            time.sleep(self._POLL_S)
            now = time.monotonic()
            fired: list = []
            with self._lock:
                for entry in self._entries.values():
                    if not entry[3] and now >= entry[0]:
                        entry[3] = True
                        fired.append(entry)
            for deadline, key, label, _ in fired:
                _tel.inc("watchdog_trips")
                budget = self.budget_s()
                logger.error(
                    "compile watchdog: %s exceeded the %.1f s wall budget "
                    "(still wedged); marking fingerprint suspect", label
                    or key[:12], budget)
                get_store().mark(
                    key, "hang",
                    reason=f"exceeded DSQL_COMPILE_WATCHDOG_S={budget:g}"
                           f" at {label or 'compile'}")

    @contextmanager
    def watch(self, key: str, label: str = ""):
        """Supervise the enclosed compile/first-call section.  No-op when
        ``DSQL_COMPILE_WATCHDOG_S`` is unset/0.  A section that trips the
        watchdog but then finishes CLEANLY lifts its own suspect mark —
        the persisted verdict means 'wedged', not 'slow'."""
        budget = self.budget_s()
        if budget <= 0:
            yield
            return
        entry = [time.monotonic() + budget, key, label, False]
        with self._lock:
            token = self._next_token
            self._next_token += 1
            self._entries[token] = entry
            self._ensure_thread()
        ok = False
        try:
            yield
            ok = True
        finally:
            with self._lock:
                self._entries.pop(token, None)
            if ok and entry[3]:
                logger.warning(
                    "compile watchdog: %s finished after tripping; lifting "
                    "the suspect mark", label or key[:12])
                get_store().clear(key)


_watchdog = CompileWatchdog()


def get_watchdog() -> CompileWatchdog:
    return _watchdog
