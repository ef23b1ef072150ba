"""Loader for the native (C++) planner front-end.

The reference's planner is native too (Java/Calcite compiled to DaskSQL.jar
and loaded in-process, /root/reference/dask_sql/java.py:62-98, setup.py:25-42).
Here the native piece is a C++ recursive-descent parser built into
``libdsqlparser.so`` (sources in ``native/`` at the repo root) and loaded via
ctypes.  The library is a build product, not a committed file: the first
``load()`` in a checkout runs one ``make`` (~17 s with g++); if that fails a
warning says so and the pure-Python parser in ``dask_sql_tpu.sql.parser``
serves, keeping the package importable without a toolchain.
"""
from __future__ import annotations

import ctypes
import fcntl
import json
import logging
import os
import subprocess
from typing import Optional

logger = logging.getLogger(__name__)

_LIB_NAME = "libdsqlparser.so"
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _try_build(path: str) -> bool:
    """One build of the native library at ``path`` (repo checkouts only).
    Processes that start together (test workers, server replicas) take
    turns on a lock and only the first compiles; the library appears under
    its name by rename, so nobody loads a half-written file."""
    native_src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "native")
    if not os.path.isfile(os.path.join(native_src, "Makefile")):
        return False
    lock = os.open(os.path.dirname(path), os.O_RDONLY)
    try:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if os.path.isfile(path):
            return True
        tmp = f"{path}.{os.getpid()}.partial"
        subprocess.run(["make", "-C", native_src, f"OUT={tmp}"],
                       capture_output=True, timeout=120, check=True)
        os.replace(tmp, path)
        return True
    except Exception as exc:  # toolchain missing, build error, timeout
        logger.warning("native parser build failed (%s); the Python "
                       "parser serves", exc)
        return False
    finally:
        os.close(lock)


def load() -> Optional[ctypes.CDLL]:
    """Load (building if necessary) the native parser library, or None."""
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    if os.environ.get("DSQL_NATIVE", "1") == "0":
        return None
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), _LIB_NAME)
    if not os.path.isfile(path) and not _try_build(path):
        return None
    try:
        lib = ctypes.CDLL(path)
        lib.dsql_parse.argtypes = [ctypes.c_char_p]
        lib.dsql_parse.restype = ctypes.c_void_p  # keep pointer for dsql_free
        lib.dsql_free.argtypes = [ctypes.c_void_p]
        lib.dsql_free.restype = None
        if hasattr(lib, "dsql_optimize"):
            lib.dsql_optimize.argtypes = [ctypes.c_char_p, ctypes.c_int]
            lib.dsql_optimize.restype = ctypes.c_void_p
        _lib = lib
    except OSError as exc:
        logger.warning("native parser load failed (%s); the Python "
                       "parser serves", exc)
        _lib = None
    return _lib


def available() -> bool:
    """True when the native parser library is loadable (CI gate)."""
    return load() is not None


def parse_to_json(sql: str) -> Optional[dict]:
    """Parse via the native library; returns the decoded JSON envelope.

    ``{"ok": [...statements]}`` on success, ``{"error": {...}}`` on parse
    error, or None when the native library is unavailable.
    """
    lib = load()
    if lib is None:
        return None
    ptr = lib.dsql_parse(sql.encode("utf-8"))
    if not ptr:
        return None
    try:
        raw = ctypes.string_at(ptr)
    finally:
        lib.dsql_free(ptr)
    return json.loads(raw.decode("utf-8"))


def optimize_to_json(plan_json: str, enable_pruning: bool = True
                     ) -> Optional[dict]:
    """Optimize a serialized plan via the native library.

    ``{"ok": <plan>}`` on success, ``{"error": {...}}`` on a native
    failure, or None when the library (or entry point) is unavailable.
    """
    lib = load()
    if lib is None or not hasattr(lib, "dsql_optimize"):
        return None
    ptr = lib.dsql_optimize(plan_json.encode("utf-8"),
                            1 if enable_pruning else 0)
    if not ptr:
        return None
    try:
        raw = ctypes.string_at(ptr)
    finally:
        lib.dsql_free(ptr)
    return json.loads(raw.decode("utf-8"))
