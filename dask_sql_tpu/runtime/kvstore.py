"""Shared cross-process JSON store plumbing.

Three subsystems persist small keyed JSON documents across process
boundaries with the SAME discipline — content-digest keys, atomic
tmp+rename writes, and corrupt-file tolerance (a broken store file must
degrade to "empty", never fail a query):

- the learned-caps file (``DSQL_CAPS_FILE``, physical/caps.py),
- the quarantine store (``DSQL_QUARANTINE_FILE``, runtime/quarantine.py),
- the program store's metadata index (``DSQL_PROGRAM_STORE``,
  runtime/program_store.py).

Before this module each carried its own copy of the read/replace logic
(drifting in small ways: tmp-name collision scope, mtime caching, value
filtering).  This is the one implementation they all share.

Concurrency model (unchanged from the originals): writes are
read-merge-replace under an atomic ``os.replace``, so concurrent writers
can lose a race — costing one re-learn / re-mark — but can never corrupt
or interleave bytes.  Tmp names are per-(pid, thread) so two threads of
one process cannot collide either.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
from typing import Dict, Optional

logger = logging.getLogger(__name__)


def digest_key(obj, size: int = 16) -> str:
    """Stable content digest of ``repr(obj)`` — the shared keying scheme
    of every cross-process store (caps, quarantine, programs)."""
    return hashlib.blake2b(repr(obj).encode(), digest_size=size).hexdigest()


def read_json_dict(path: str) -> Dict[str, dict]:
    """Load a {key: dict} JSON file, tolerant of a missing, corrupt, or
    truncated file and of non-dict values (both read as absent)."""
    try:
        with open(path) as f:
            loaded = json.load(f)
        if not isinstance(loaded, dict):
            return {}
        return {k: dict(v) for k, v in loaded.items() if isinstance(v, dict)}
    except (OSError, ValueError):
        return {}


def atomic_write_json(path: str, data: dict) -> bool:
    """Write ``data`` as JSON via tmp + atomic rename; False (logged at
    debug) when the path is unwritable — persistence is an optimization,
    never a crash source."""
    tmp = f"{path}.tmp{os.getpid()}.{threading.get_ident()}"
    try:
        with open(tmp, "w") as f:
            json.dump(data, f)
        os.replace(tmp, path)
        return True
    except OSError:
        logger.debug("store file %s not writable", path)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


class MtimeCachedJsonFile:
    """A {key: dict} JSON file with an mtime-validated in-memory cache
    (reads are cheap enough for per-query hot paths) and read-merge-replace
    writes.  ``path`` is re-resolved per call via the callable so env-flipped
    configuration (tests, operators) takes effect without restart."""

    def __init__(self, path_fn):
        self._path_fn = path_fn
        self._lock = threading.Lock()
        self._cached: Dict[str, dict] = {}
        self._cached_mtime: Optional[int] = None

    def path(self) -> Optional[str]:
        return self._path_fn()

    def read(self) -> Dict[str, dict]:
        path = self.path()
        if not path:
            return {}
        try:
            mtime = os.stat(path).st_mtime_ns
        except OSError:
            with self._lock:
                self._cached, self._cached_mtime = {}, None
            return {}
        with self._lock:
            if self._cached_mtime == mtime:
                return dict(self._cached)
        data = read_json_dict(path)
        with self._lock:
            self._cached, self._cached_mtime = data, mtime
        return dict(data)

    def write(self, data: Dict[str, dict]) -> None:
        path = self.path()
        if not path:
            return
        if atomic_write_json(path, data):
            with self._lock:
                self._cached = dict(data)
                try:
                    self._cached_mtime = os.stat(path).st_mtime_ns
                except OSError:
                    self._cached_mtime = None
