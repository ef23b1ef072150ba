"""Engine-wide resilience layer: taxonomy, deadlines, retries, degradation.

The paper's north star is a production engine under heavy traffic; there a
single XLA compile crash, device OOM, or hung TPU program must never take
down a query (let alone the server) with an untyped exception.  Flare
(PAPERS.md) keeps a deoptimization path from native code back to its
interpreted engine, and DrJAX observes that long-running JAX programs need
host-side supervision — this module is that discipline for dask_sql_tpu:

**Taxonomy.**  Every failure is classified into exactly one of

  ``UserError``       the query/input is wrong; retrying cannot help
                      (Presto ``USER_ERROR``);
  ``TransientError``  the attempt failed but a retry or a lower rung can
                      succeed — compile crashes, device OOM, transfer
                      drops (Presto ``INTERNAL_ERROR``, or
                      ``INSUFFICIENT_RESOURCES`` for ``kind="oom"``);
  ``FatalError``      an engine invariant broke; retrying is pointless and
                      the failure must surface (Presto ``INTERNAL_ERROR``);

plus supervision verdicts: ``DeadlineExceeded`` (the per-query budget
ran out — Presto ``INSUFFICIENT_RESOURCES``, like Trino's
EXCEEDED_TIME_LIMIT), ``QueryCancelled`` (the client abandoned the
query), and the admission verdicts ``AdmissionRejected`` /
``AdmissionTimeout`` raised by the workload manager
(runtime/scheduler.py) when the system is saturated — time spent in the
admission queue counts against the query's deadline, so a queued query
can expire or be cancelled exactly like a running one.  ``classify`` maps raw exceptions into the taxonomy; call sites
choose the default bucket for unrecognized types (the server boundary
defaults to ``UserError`` to match Presto semantics; internal sites default
to ``FatalError``).

**Deadlines + cancellation.**  ``Context.sql(..., timeout=)`` (seconds) or
``DSQL_QUERY_TIMEOUT_MS`` opens a ``query_scope`` carrying a monotonic
deadline and a cancel event; ``check()`` at layer checkpoints (compile
attempts, capacity-escalation iterations, stage scheduling, streamed
batches, eager plan nodes) raises the typed verdict instead of letting work
run past its budget.  Worker threads (the stage compile pool) re-enter the
scope via ``scoped`` — thread locals do not cross pools on their own.

**Retry/backoff.**  ``retry_transient`` retries TransientErrors with
bounded exponential backoff (``DSQL_RETRY_MAX`` attempts,
``DSQL_RETRY_BASE_MS`` base), always re-checking the deadline before
sleeping — a retry loop must never become the hang it exists to prevent.

**Degradation ladder.**  ``LADDER`` declares the compile-layer policy the
executor follows (physical/compiled.py): whole-plan jit → bounded stages →
eager → typed failure.  Each rung change increments
``compiled.stats["degradations"]``; each in-rung retry increments
``"retries"``; deadline verdicts increment ``"deadline_exceeded"``; fault
injections increment their per-site ``"fault_*"`` counter — so CI can
assert the ladder actually ran (tests/integration/test_resilience.py).
"""
from __future__ import annotations

import logging
import os
import threading
import time
from contextlib import contextmanager
from typing import Callable, Optional, Tuple

logger = logging.getLogger(__name__)

# the declared compile-layer degradation policy, top rung first (the old
# implicit "two-strike" special case in physical/programs.py, made explicit)
LADDER: Tuple[str, ...] = ("whole", "stages", "eager", "fail")


# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------

class ResilienceError(RuntimeError):
    """Base of the typed taxonomy.  ``error_type``/``error_name``/
    ``error_code`` are the Presto wire classification the server emits."""

    error_type = "INTERNAL_ERROR"
    error_name = "GENERIC_INTERNAL_ERROR"
    error_code = 0x10000          # Trino GENERIC_INTERNAL_ERROR range


class UserError(ResilienceError):
    """The query or its inputs are wrong; no retry can help."""

    error_type = "USER_ERROR"
    error_name = "GENERIC_USER_ERROR"
    error_code = 0x0


class TransientError(ResilienceError):
    """A retry — or a lower degradation rung — can succeed.

    ``kind`` labels the failure class: ``"compile"`` (backend compile
    crash), ``"oom"`` (device memory), ``"io"`` (transfer),
    ``"device"`` (other runtime errors), ``"injected"`` (test faults)."""

    error_name = "TRANSIENT_ERROR"

    def __init__(self, message: str = "", kind: str = "device"):
        super().__init__(message)
        self.kind = kind
        if kind == "oom":
            self.error_type = "INSUFFICIENT_RESOURCES"
            self.error_name = "EXCEEDED_MEMORY_LIMIT"
            self.error_code = 0x20000


class FatalError(ResilienceError):
    """An engine invariant broke; surface it, never retry."""

    error_name = "GENERIC_INTERNAL_ERROR"


class DeadlineExceeded(ResilienceError):
    """The per-query time budget ran out (Trino EXCEEDED_TIME_LIMIT)."""

    error_type = "INSUFFICIENT_RESOURCES"
    error_name = "EXCEEDED_TIME_LIMIT"
    error_code = 0x20000


class QueryCancelled(UserError):
    """The client abandoned the query (DELETE /v1/cancel)."""

    error_name = "USER_CANCELED"


class SchemaMismatch(UserError):
    """An append batch (``append_rows`` / ``INSERT INTO ... SELECT`` /
    ``POST /v1/ingest``) does not fit the target table's schema: missing
    or extra columns, wrong arity, or a value that cannot cast to the
    target column type.  A user mistake by construction — the server
    surfaces it as HTTP 400 rather than a raw coercion traceback."""

    error_name = "SCHEMA_MISMATCH"


class AdmissionRejected(ResilienceError):
    """The workload manager (runtime/scheduler.py) refused the query at
    submit time: queue full, or the deadline would expire before a slot
    could plausibly free.  The server surfaces this as HTTP 429 with a
    ``Retry-After`` derived from ``retry_after_s``."""

    error_type = "INSUFFICIENT_RESOURCES"
    error_name = "QUERY_QUEUE_FULL"
    error_code = 0x20000

    def __init__(self, message: str = "", retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = max(float(retry_after_s), 0.0)


class AdmissionTimeout(AdmissionRejected):
    """The query waited in the admission queue past DSQL_QUEUE_TIMEOUT_MS
    without winning a slot (queue time always counts against the query's
    own deadline too — see scheduler.WorkloadManager.acquire)."""

    error_name = "QUERY_QUEUE_TIMEOUT"


class ServerDraining(AdmissionRejected):
    """The process is draining (SIGTERM/SIGINT): in-flight queries run to
    completion within ``DSQL_DRAIN_TIMEOUT_S`` but NEW admissions are
    refused — the server surfaces this as HTTP 503 + ``Retry-After`` so a
    load balancer retries against another instance."""

    error_name = "SERVER_SHUTTING_DOWN"


class TenantQuotaExceeded(AdmissionRejected):
    """The tenant's token-bucket rate (``DSQL_TENANT_QPS``) or concurrency
    quota (``DSQL_TENANT_CONCURRENT``) is exhausted (runtime/tenancy.py).
    Rides the AdmissionRejected wire path: HTTP 429 + ``Retry-After``
    derived from the bucket's refill time."""

    error_name = "TENANT_QUOTA_EXCEEDED"


class TenantCircuitOpen(AdmissionRejected):
    """The tenant's circuit breaker is open (``DSQL_TENANT_BREAKER``
    consecutive fatal/timeout verdicts): admissions are refused
    immediately until a half-open probe succeeds — the tenant's failure
    loop must not keep burning engine slots.  HTTP 429 + ``Retry-After``
    set to the remaining open window."""

    error_name = "TENANT_CIRCUIT_OPEN"


class LoadShedRejected(AdmissionRejected):
    """Burn-driven load shed (runtime/scheduler.py): a priority class is
    burning its SLO error budget past ``DSQL_SLO_BURN`` on BOTH burn
    windows, so background-class admissions are refused before the SLO
    actually breaches.  HTTP 429 + ``Retry-After``; clears on its own
    when the burn recovers."""

    error_name = "SLO_LOAD_SHED"


class IngestBackpressure(AdmissionRejected):
    """The continuous-ingestion write path (runtime/ingest.py) priced an
    append batch through the scheduler's memory broker and the device
    budget cannot absorb it right now: the writer must back off.  Rides
    the AdmissionRejected wire path (HTTP 429 + ``Retry-After``) so a
    well-behaved writer client retries instead of growing the working
    set past what readers were admitted against."""

    error_name = "INGEST_BACKPRESSURE"


# exception type NAMES (not imports: the parser/binder layer must stay
# importable without this module) that are user mistakes by construction
_USER_ERROR_NAMES = frozenset({
    "ParsingException", "ValidationException", "BinderError",
    "StreamingUnsupported",
})

# XlaRuntimeError status substrings that mean the PROGRAM is wrong (no
# retry will change the verdict) vs the ATTEMPT failed (retry/degrade)
_XLA_FATAL_MARKERS = ("INVALID_ARGUMENT", "UNIMPLEMENTED", "FAILED_PRECONDITION")
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "OUT OF MEMORY", "OOM")


def _is_xla_error(exc: BaseException) -> bool:
    t = type(exc)
    return (t.__name__ == "XlaRuntimeError"
            or t.__module__.startswith(("jaxlib", "jax.")))


def classify(exc: BaseException, *, default=FatalError
             ) -> Optional[ResilienceError]:
    """Map a raw exception into the taxonomy.

    Returns a typed error (the original object when already typed, with
    ``__cause__`` set to the original otherwise), or None for control-flow
    exceptions the caller must re-raise untouched.  ``default`` is the
    bucket for unrecognized types: ``UserError`` at the serve boundary
    (anything escaping ``Context.sql`` on user input is the user's query),
    ``FatalError`` inside the engine.
    """
    if isinstance(exc, (KeyboardInterrupt, SystemExit, GeneratorExit)):
        return None
    if isinstance(exc, ResilienceError):
        return exc

    def wrap(cls, *args, **kw) -> ResilienceError:
        err = cls(*args, **kw)
        err.__cause__ = exc
        return err

    msg = f"{type(exc).__name__}: {exc}"
    if isinstance(exc, MemoryError):
        return wrap(TransientError, msg, kind="oom")
    if type(exc).__name__ in _USER_ERROR_NAMES:
        return wrap(UserError, str(exc))
    if _is_xla_error(exc):
        text = str(exc).upper()
        if any(m in text for m in _OOM_MARKERS):
            return wrap(TransientError, msg, kind="oom")
        if any(m in text for m in _XLA_FATAL_MARKERS):
            return wrap(FatalError, msg)
        # INTERNAL / UNAVAILABLE / ABORTED / DEADLINE_EXCEEDED / transfer
        # drops: the attempt failed, the program may be fine
        return wrap(TransientError, msg, kind="compile")
    if isinstance(exc, (ConnectionError, TimeoutError, OSError)):
        return wrap(TransientError, msg, kind="io")
    return wrap(default, msg)


# ---------------------------------------------------------------------------
# per-query runtime: deadline + cancellation
# ---------------------------------------------------------------------------

def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class QueryRuntime:
    """Deadline + cancel token one query's execution threads share.

    ``backoff_s`` accumulates wall time this query spent SLEEPING in
    retry backoff while holding resources — the workload manager subtracts
    it from the slot-hold time feeding its queue-wait EWMA, so a query
    riding a long in-rung retry chain does not inflate the admission
    estimator (and spuriously fast-reject queued work)."""

    __slots__ = ("deadline_at", "cancel", "backoff_s")

    def __init__(self, timeout_s: Optional[float] = None,
                 cancel: Optional[threading.Event] = None):
        self.deadline_at = (None if timeout_s is None
                            else time.monotonic() + max(timeout_s, 0.0))
        self.cancel = cancel
        self.backoff_s = 0.0

    def remaining(self) -> Optional[float]:
        if self.deadline_at is None:
            return None
        return self.deadline_at - time.monotonic()

    def merged(self, timeout_s: Optional[float],
               cancel: Optional[threading.Event]) -> "QueryRuntime":
        """A nested scope can only tighten: the sooner deadline wins and
        either cancel token aborts (outer cancellation must reach work a
        nested sql() call started)."""
        rt = QueryRuntime(timeout_s, cancel or self.cancel)
        if self.deadline_at is not None and (
                rt.deadline_at is None or self.deadline_at < rt.deadline_at):
            rt.deadline_at = self.deadline_at
        if rt.cancel is None:
            rt.cancel = self.cancel
        return rt


_tls = threading.local()


def current() -> Optional[QueryRuntime]:
    return getattr(_tls, "runtime", None)


@contextmanager
def scoped(rt: Optional[QueryRuntime]):
    """Install an existing runtime in THIS thread (worker-pool re-entry)."""
    prev = current()
    _tls.runtime = rt
    try:
        yield rt
    finally:
        _tls.runtime = prev


@contextmanager
def query_scope(timeout_s: Optional[float] = None,
                cancel: Optional[threading.Event] = None):
    """Open (or tighten) the per-query supervision scope.

    ``timeout_s=None`` reads ``DSQL_QUERY_TIMEOUT_MS`` (unset/0 = no
    deadline).  Nested scopes merge: the sooner deadline and any cancel
    token win."""
    if timeout_s is None:
        ms = _env_int("DSQL_QUERY_TIMEOUT_MS", 0)
        timeout_s = ms / 1e3 if ms > 0 else None
    outer = current()
    rt = (QueryRuntime(timeout_s, cancel) if outer is None
          else outer.merged(timeout_s, cancel))
    with scoped(rt):
        yield rt


def _bump(key: str, n: int = 1) -> None:
    # counters live in the telemetry registry (runtime/telemetry.py);
    # ``physical.compiled.stats`` is a deprecated read-through alias of it
    from . import telemetry as _tel
    _tel.inc(key, n)


def check(site: str = "") -> None:
    """Deadline/cancellation checkpoint; raises the typed verdict."""
    rt = current()
    if rt is None:
        return
    if rt.cancel is not None and rt.cancel.is_set():
        raise QueryCancelled(
            f"query cancelled{f' at {site}' if site else ''}")
    rem = rt.remaining()
    if rem is not None and rem <= 0:
        _bump("deadline_exceeded")
        raise DeadlineExceeded(
            f"query deadline exceeded{f' at {site}' if site else ''} "
            f"({-rem * 1e3:.0f} ms past)")


def interruptible_sleep(seconds: float, site: str = "") -> None:
    """Sleep in small slices so cancellation/deadline cut it short."""
    end = time.monotonic() + max(seconds, 0.0)
    while True:
        check(site)
        left = end - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.01))


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------

def retry_max() -> int:
    return max(_env_int("DSQL_RETRY_MAX", 2), 0)


def backoff_s(attempt: int) -> float:
    """Exponential backoff for retry ``attempt`` (1-based), capped at 2 s."""
    base = _env_int("DSQL_RETRY_BASE_MS", 25) / 1e3
    return min(base * (2 ** (attempt - 1)), 2.0)


def backoff(attempt: int, site: str = "") -> None:
    """Sleep before retry ``attempt`` — but never past the deadline: if the
    budget cannot cover the sleep, raise DeadlineExceeded NOW instead of
    burning the remainder on a doomed wait.

    The sleep runs under a ``retry_backoff`` telemetry span and accrues
    into ``QueryRuntime.backoff_s``, so slot-hold accounting (the
    scheduler's queue-wait EWMA) can subtract time spent deliberately
    idle from time spent actually computing."""
    from . import telemetry as _tel
    delay = backoff_s(attempt)
    rt = current()
    if rt is not None:
        rem = rt.remaining()
        if rem is not None and rem <= delay:
            _bump("deadline_exceeded")
            raise DeadlineExceeded(
                f"deadline cannot cover retry backoff at {site or 'site'} "
                f"({delay * 1e3:.0f} ms needed, {max(rem, 0) * 1e3:.0f} ms "
                "left)")
    t0 = time.monotonic()
    try:
        with _tel.span("retry_backoff", site=site, attempt=attempt):
            interruptible_sleep(delay, site)
    finally:
        # the actually-slept wall (an interrupting deadline/cancel cuts it
        # short), accumulated even on the exception path — the time was
        # spent either way
        if rt is not None:
            rt.backoff_s += time.monotonic() - t0


def retry_transient(fn: Callable, *, site: str,
                    passthrough: Tuple[type, ...] = ()):
    """Run ``fn``, retrying TransientErrors with bounded backoff.

    ``passthrough`` exceptions (control flow like _NeedsRecompile) are
    re-raised untouched.  Non-transient failures are re-raised as their
    classified type; retries count into ``compiled.stats["retries"]``.
    """
    attempt = 0
    while True:
        check(site)
        try:
            return fn()
        except passthrough:
            raise
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            err = classify(e)
            if err is None:
                raise
            if not isinstance(err, TransientError):
                raise err if err is e else err from e
            attempt += 1
            if attempt > retry_max():
                raise err if err is e else err from e
            _bump("retries")
            logger.warning("transient failure at %s (%s); retry %d/%d",
                           site, str(err)[:200], attempt, retry_max())
            backoff(attempt, site)
