"""Persistent cross-process program store: compiled stage executables on disk.

The wall of a benchmark run before the v5e bring-up was compilation, not
execution: 19-615 s warm/compile per TPC-H query (an earlier backend and
jax 0.4; not measured on the attached chip), re-paid by EVERY fresh process, while per-query execution was
already sub-2 s.  The in-memory program cache
(physical/programs.py ``_cache``) and the learned-caps file soften repeat
cost *within* a process lineage; this module removes the cross-process
bill entirely: a successfully compiled stage program is serialized (the
XLA executable itself, via ``jax.experimental.serialize_executable``) and
persisted under ``DSQL_PROGRAM_STORE``, so a restarted server or a brand
new process serves every previously-seen plan shape with ZERO XLA
recompilation — Flare's "never compile the same native program twice"
discipline (PAPERS.md) carried across process boundaries.

Keying.  An entry is addressed by a digest of the executor's *canonical*
program identity — the plan fingerprint with stage-boundary temp names
rewritten to position-stable placeholders (boundary names embed per-process
table uids, physical/stage_exec.py ``_stage_table_name``; the program itself
is uid-independent: it depends only on plan shape + input layout), the
input-layout fingerprint (shapes/dtypes/dictionary CONTENT), and the
backend strategy — folded with ``quarantine.device_fingerprint()`` and the
jax/jaxlib versions.  A program can therefore only ever be served to the
same plan shape over the same data layout on the same device class and
runtime version; DDL that changes a plan's shape or layout changes the
digest, and result staleness is impossible by construction (programs are
pure functions of their inputs — result freshness is the result cache's
catalog-epoch problem, not this store's).

Safety.  The serialized blob additionally embeds the fingerprint it was
built under and is verified again at load (belt and suspenders against
digest collisions or hand-copied entries); a mismatch rejects the entry
(``program_store_rejects``) and falls back to a normal compile.  Corrupt,
truncated, or undeserializable entries are tolerated the same way
(``program_store_errors``) and evicted.  Writes are atomic (tmp+rename);
the metadata index rides the shared kvstore plumbing (runtime/kvstore.py)
with read-merge-replace semantics, so concurrent processes can lose an
index race but never corrupt it.

Budget.  ``DSQL_PROGRAM_STORE_MB`` (default 512) bounds the payload bytes
on disk with a least-recently-used eviction over the index's ``used_at``
stamps (``program_store_evictions``).

Telemetry: ``program_store_hits`` / ``program_store_misses`` /
``program_store_stores`` / ``program_store_rejects`` /
``program_store_evictions`` / ``program_store_errors`` (stable-name
contract, runtime/telemetry.py).
"""
from __future__ import annotations

import logging
import os
import pickle
import threading
import time
from typing import Dict, Optional

from . import kvstore as _kv
from . import telemetry as _tel

logger = logging.getLogger(__name__)

DEFAULT_BUDGET_MB = 512.0

_FORMAT_VERSION = 2   # 2: entries carry the device ids they were compiled for
_INDEX_NAME = "index.json"


def _env_float(name: str, default: float) -> float:
    try:
        raw = os.environ.get(name, "")
        return float(raw) if raw else default
    except ValueError:
        return default


def runtime_fingerprint() -> Dict[str, str]:
    """Identity of the runtime a serialized executable is only valid for:
    device class + jax/jaxlib versions.  A deserialized XLA executable is
    NOT portable across any of these."""
    from . import quarantine as _quar

    try:
        import jax
        jax_v = getattr(jax, "__version__", "?")
    except Exception:  # pragma: no cover - jax always present in practice
        jax_v = "?"
    try:
        import jaxlib
        jaxlib_v = getattr(jaxlib, "__version__", "?")
    except Exception:  # pragma: no cover
        jaxlib_v = "?"
    try:
        import jax
        # executables are device-LAYOUT specific too: an SPMD program
        # compiled for an 8-device mesh cannot load on a 1-device process
        n_dev = str(jax.device_count())
    except Exception:  # pragma: no cover
        n_dev = "?"
    return {"device": _quar.device_fingerprint(), "devices": n_dev,
            "jax": jax_v, "jaxlib": jaxlib_v, "format": str(_FORMAT_VERSION)}


def serialize_program(compiled) -> Dict[str, object]:
    """The portable part of an AOT-compiled program: the serialized XLA
    executable plus the ids of the devices it was compiled for, in
    assignment order.  ``load_program`` needs both: jax 0.9's
    ``deserialize_and_load`` otherwise loads onto EVERY local device, and a
    one-device program then refuses its one-shard arguments on any
    multi-device host."""
    from jax.experimental import serialize_executable as _se
    payload, _, _ = _se.serialize(compiled)
    devices = compiled._executable._unloaded_executable.device_list
    return {"payload": payload, "devices": [int(d.id) for d in devices]}


def load_program(rec: Dict[str, object], n_args: int, n_outs: int):
    """Inverse of ``serialize_program``: a ``jax.stages.Compiled`` taking
    ``n_args`` flat arrays and returning ``n_outs``, loaded onto the same
    device ids it was compiled for (KeyError when one is not attached
    here — the caller counts that as an unusable entry)."""
    import jax
    import jax.tree_util as _jtu
    from jax.experimental import serialize_executable as _se
    by_id = {d.id: d for d in jax.devices()}
    in_tree = _jtu.tree_structure((tuple(range(n_args)), {}))
    out_tree = _jtu.tree_structure(tuple(range(n_outs)))
    return _se.deserialize_and_load(
        rec["payload"], in_tree, out_tree,
        execution_devices=[by_id[i] for i in rec["devices"]])


class ProgramStore:
    """Directory of serialized compiled programs + a JSON metadata index.

    Layout: ``<dir>/<digest>.prog`` (pickled entry dict) and
    ``<dir>/index.json`` ({digest: {bytes, used_at, stored_at}}).  One
    entry per program digest; re-stores (capacity-escalated recompiles)
    overwrite in place.
    """

    def __init__(self, path: Optional[str] = None):
        self._path_override = path
        self._lock = threading.Lock()
        self._index = _kv.MtimeCachedJsonFile(self._index_path)

    # -- config (env-read per call so tests/operators flip without restart)
    def path(self) -> Optional[str]:
        return self._path_override or os.environ.get("DSQL_PROGRAM_STORE")

    def enabled(self) -> bool:
        return bool(self.path())

    def budget_bytes(self) -> int:
        return int(max(_env_float("DSQL_PROGRAM_STORE_MB",
                                  DEFAULT_BUDGET_MB), 0.0) * (1 << 20))

    def _index_path(self) -> Optional[str]:
        p = self.path()
        return os.path.join(p, _INDEX_NAME) if p else None

    def _entry_path(self, digest: str) -> str:
        return os.path.join(self.path(), f"{digest}.prog")

    def digest(self, store_key) -> str:
        """Content address of a program: canonical program identity folded
        with the runtime fingerprint."""
        return _kv.digest_key((store_key,
                               tuple(sorted(runtime_fingerprint().items()))))

    # -- lookup -------------------------------------------------------------
    def contains(self, digest: str) -> bool:
        """Cheap presence probe (index only; used by the tier decision)."""
        if not self.enabled():
            return False
        return digest in self._index.read()

    def load(self, digest: str) -> Optional[dict]:
        """The stored entry dict, or None (miss / corrupt / fingerprint
        mismatch — all of which fall back to a normal compile)."""
        if not self.enabled():
            return None
        path = self._entry_path(digest)
        try:
            with open(path, "rb") as f:
                entry = pickle.load(f)
        except FileNotFoundError:
            _tel.inc("program_store_misses")
            return None
        except Exception as e:  # corrupt/truncated/unpicklable: evict it
            _tel.inc("program_store_errors")
            logger.warning("program store entry %s unreadable (%s); "
                           "dropping it", digest[:12], type(e).__name__)
            self._drop(digest)
            return None
        if not isinstance(entry, dict) \
                or entry.get("fingerprint") != runtime_fingerprint():
            # a different device class / jax version / format: the
            # executable bytes are not safe to load here
            _tel.inc("program_store_rejects")
            logger.warning("program store entry %s rejected: runtime "
                           "fingerprint mismatch", digest[:12])
            return None
        self._touch(digest)
        return entry

    # -- mutation -----------------------------------------------------------
    def store(self, digest: str, entry: dict) -> bool:
        """Persist ``entry`` (atomic write), update the index, and enforce
        the byte budget.  Best-effort: False on any failure."""
        if not self.enabled():
            return False
        entry = dict(entry)
        entry["fingerprint"] = runtime_fingerprint()
        try:
            os.makedirs(self.path(), exist_ok=True)
            blob = pickle.dumps(entry, protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as e:
            _tel.inc("program_store_errors")
            logger.warning("program store serialize failed: %s", e)
            return False
        path = self._entry_path(digest)
        tmp = f"{path}.tmp{os.getpid()}.{threading.get_ident()}"
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except OSError as e:
            logger.debug("program store %s not writable: %s", path, e)
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        now = time.time()
        rec = {"bytes": len(blob), "used_at": now, "stored_at": now}
        cost = entry.get("cost")
        if isinstance(cost, dict):
            # the profiler's XLA cost prediction rides the index too, so
            # system.programs answers "which stored programs are heavy"
            # without deserializing any payload
            rec["cost_flops"] = float(cost.get("flops", 0.0) or 0.0)
            rec["cost_bytes"] = float(cost.get("bytes", 0.0) or 0.0)
        with self._lock:
            index = self._index.read()
            index[digest] = rec
            index = self._evict_locked(index, keep=digest)
            self._index.write(index)
        _tel.inc("program_store_stores")
        return True

    def _touch(self, digest: str) -> None:
        """LRU recency stamp on a hit (best-effort)."""
        with self._lock:
            index = self._index.read()
            e = index.get(digest)
            if e is not None:
                e["used_at"] = time.time()
                index[digest] = e
                self._index.write(index)

    def _drop(self, digest: str) -> None:
        try:
            os.unlink(self._entry_path(digest))
        except OSError:
            pass
        with self._lock:
            index = self._index.read()
            if digest in index:
                del index[digest]
                self._index.write(index)

    def _evict_locked(self, index: Dict[str, dict], keep: str
                      ) -> Dict[str, dict]:
        """Drop least-recently-used entries until the payload fits the
        byte budget (the newest store is never its own victim)."""
        budget = self.budget_bytes()
        total = sum(int(e.get("bytes", 0)) for e in index.values())
        if total <= budget:
            return index
        order = sorted((d for d in index if d != keep),
                       key=lambda d: float(index[d].get("used_at", 0)))
        for d in order:
            if total <= budget:
                break
            total -= int(index[d].get("bytes", 0))
            del index[d]
            try:
                os.unlink(self._entry_path(d))
            except OSError:
                pass
            _tel.inc("program_store_evictions")
        return index

    # -- introspection ------------------------------------------------------
    def entries(self) -> Dict[str, dict]:
        return self._index.read()

    def total_bytes(self) -> int:
        return sum(int(e.get("bytes", 0)) for e in self._index.read().values())


_store = ProgramStore()


def get_store() -> ProgramStore:
    """The process-global program store (env-configured, like the result
    cache, scheduler, and quarantine store)."""
    return _store
