"""Resilience primitives: taxonomy classification, deadlines/cancellation,
retry/backoff policy, and the fault-injection spec machinery
(runtime/resilience.py + runtime/faults.py)."""
import threading
import time

import pytest

from dask_sql_tpu.physical import compiled
from dask_sql_tpu.runtime import faults, resilience as R


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    faults.reset()
    monkeypatch.setenv("DSQL_RETRY_BASE_MS", "1")
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# taxonomy / classify
# ---------------------------------------------------------------------------

def test_classify_passthrough_typed_and_control_flow():
    err = R.TransientError("x", kind="io")
    assert R.classify(err) is err
    assert R.classify(KeyboardInterrupt()) is None
    assert R.classify(SystemExit()) is None


def test_classify_xla_statuses():
    class XlaRuntimeError(Exception):
        pass

    oom = R.classify(XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert isinstance(oom, R.TransientError) and oom.kind == "oom"
    assert oom.error_type == "INSUFFICIENT_RESOURCES"
    fatal = R.classify(XlaRuntimeError("INVALID_ARGUMENT: bad hlo"))
    assert isinstance(fatal, R.FatalError)
    transient = R.classify(XlaRuntimeError("INTERNAL: socket closed"))
    assert isinstance(transient, R.TransientError)


def test_classify_user_and_defaults():
    class ValidationException(Exception):
        pass

    assert isinstance(R.classify(ValidationException("no such column")),
                      R.UserError)
    assert isinstance(R.classify(MemoryError()), R.TransientError)
    assert isinstance(R.classify(ConnectionError()), R.TransientError)
    assert isinstance(R.classify(TypeError("boom")), R.FatalError)
    assert isinstance(R.classify(TypeError("boom"), default=R.UserError),
                      R.UserError)
    # original rides along for tracebacks
    src = ValueError("source")
    assert R.classify(src).__cause__ is src


def test_taxonomy_wire_attributes():
    assert R.UserError("x").error_type == "USER_ERROR"
    assert R.FatalError("x").error_type == "INTERNAL_ERROR"
    assert R.TransientError("x").error_type == "INTERNAL_ERROR"
    assert R.DeadlineExceeded("x").error_type == "INSUFFICIENT_RESOURCES"
    assert R.DeadlineExceeded("x").error_name == "EXCEEDED_TIME_LIMIT"
    assert isinstance(R.QueryCancelled("x"), R.UserError)
    assert R.QueryCancelled("x").error_name == "USER_CANCELED"
    # the streaming executor's typed refusal is a UserError AND still a
    # RuntimeError for pre-taxonomy callers
    from dask_sql_tpu.physical.streaming import StreamingUnsupported
    assert issubclass(StreamingUnsupported, R.UserError)
    assert issubclass(StreamingUnsupported, RuntimeError)
    from dask_sql_tpu.io.chunked import ChunkedInputError
    assert issubclass(ChunkedInputError, R.UserError)
    assert issubclass(ChunkedInputError, ValueError)


# ---------------------------------------------------------------------------
# deadlines + cancellation
# ---------------------------------------------------------------------------

def test_check_is_noop_outside_scope():
    R.check("anywhere")  # no scope, no deadline: must not raise


def test_deadline_expires():
    with R.query_scope(timeout_s=0.0):
        with pytest.raises(R.DeadlineExceeded):
            R.check("site")


def test_nested_scope_keeps_sooner_deadline():
    with R.query_scope(timeout_s=0.0):
        with R.query_scope(timeout_s=100.0):
            with pytest.raises(R.DeadlineExceeded):
                R.check()


def test_env_default_timeout(monkeypatch):
    monkeypatch.setenv("DSQL_QUERY_TIMEOUT_MS", "1")
    with R.query_scope():
        time.sleep(0.01)
        with pytest.raises(R.DeadlineExceeded):
            R.check()


def test_cancel_token_reaches_nested_scope():
    cancel = threading.Event()
    with R.query_scope(cancel=cancel):
        with R.query_scope(timeout_s=100.0):
            R.check()
            cancel.set()
            with pytest.raises(R.QueryCancelled):
                R.check()


def test_scoped_reenters_runtime_in_worker_thread():
    cancel = threading.Event()
    cancel.set()
    seen = []
    with R.query_scope(cancel=cancel) as rt:
        def worker():
            with R.scoped(rt):
                try:
                    R.check("worker")
                except R.QueryCancelled:
                    seen.append(True)
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert seen == [True]


def test_interruptible_sleep_cut_by_deadline():
    t0 = time.monotonic()
    with R.query_scope(timeout_s=0.05):
        with pytest.raises(R.DeadlineExceeded):
            R.interruptible_sleep(30.0, "test")
    assert time.monotonic() - t0 < 5.0


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------

def test_retry_transient_succeeds_after_blip():
    before = compiled.stats["retries"]
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) == 1:
            raise R.TransientError("blip", kind="io")
        return "ok"

    assert R.retry_transient(flaky, site="t") == "ok"
    assert len(calls) == 2
    assert compiled.stats["retries"] == before + 1


def test_retry_transient_exhausts_typed(monkeypatch):
    monkeypatch.setenv("DSQL_RETRY_MAX", "1")

    def always():
        raise OSError("link down")   # classifies transient

    with pytest.raises(R.TransientError):
        R.retry_transient(always, site="t")


def test_retry_transient_fatal_is_immediate():
    calls = []

    def fatal():
        calls.append(1)
        raise TypeError("trace bug")

    with pytest.raises(R.FatalError):
        R.retry_transient(fatal, site="t")
    assert len(calls) == 1


def test_retry_transient_passthrough():
    class Control(Exception):
        pass

    def ctl():
        raise Control()

    with pytest.raises(Control):
        R.retry_transient(ctl, site="t", passthrough=(Control,))


def test_backoff_respects_deadline():
    with R.query_scope(timeout_s=0.001):
        with pytest.raises(R.DeadlineExceeded):
            # backoff for a late attempt needs more budget than 1 ms
            R.backoff(8, "t")


# ---------------------------------------------------------------------------
# fault injection machinery
# ---------------------------------------------------------------------------

def test_parse_spec_shapes():
    specs = faults.parse_spec("compile:1,stage_exec:3+,materialize:2:sleep=50")
    assert [(s.site, s.nth, s.from_on, s.sleep_ms) for s in specs] == [
        ("compile", 1, False, None), ("stage_exec", 3, True, None),
        ("materialize", 2, False, 50)]
    with pytest.raises(ValueError):
        faults.parse_spec("nosuchsite:1")
    with pytest.raises(ValueError):
        faults.parse_spec("compile")
    with pytest.raises(ValueError):
        faults.parse_spec("compile:1:frob=2")


def test_maybe_fail_nth_semantics():
    before = compiled.stats["fault_compile"]
    with faults.inject("compile:2"):
        faults.maybe_fail("compile")          # 1st: no fire
        faults.maybe_fail("materialize")      # other site: own counter
        with pytest.raises(faults.FaultInjected) as ei:
            faults.maybe_fail("compile")      # 2nd: fires
        assert ei.value.site == "compile"
        assert isinstance(ei.value, R.TransientError)
        faults.maybe_fail("compile")          # 3rd: no fire (nth, not nth+)
    assert compiled.stats["fault_compile"] == before + 1
    faults.maybe_fail("compile")              # disarmed outside the cm


def test_maybe_fail_from_on_semantics():
    with faults.inject("compile:2+"):
        faults.maybe_fail("compile")
        for _ in range(3):
            with pytest.raises(faults.FaultInjected):
                faults.maybe_fail("compile")


def test_env_spec_is_read_per_call(monkeypatch):
    monkeypatch.setenv("DSQL_FAULT_INJECT", "materialize:1")
    faults.reset()
    with pytest.raises(faults.FaultInjected):
        faults.maybe_fail("materialize")
    monkeypatch.delenv("DSQL_FAULT_INJECT")
    faults.maybe_fail("materialize")


# ---------------------------------------------------------------------------
# probabilistic arming + fatal action (the chaos-soak spec forms)
# ---------------------------------------------------------------------------

def test_parse_probabilistic_and_fatal_spec():
    specs = faults.parse_spec(
        "compile:p=0.25:seed=7,stage_replay:1,drain:1,compile:2:fatal")
    assert specs[0].prob == 0.25 and specs[0].rng is not None
    assert specs[0].nth is None
    assert [s.site for s in specs[1:3]] == ["stage_replay", "drain"]
    assert specs[3].fatal
    with pytest.raises(ValueError):
        faults.parse_spec("compile:p=0")          # outside (0, 1]
    with pytest.raises(ValueError):
        faults.parse_spec("compile:p=1.5")


def test_probabilistic_fire_rate_is_seeded_and_deterministic():
    def fires(spec):
        out = []
        with faults.inject(spec):
            for i in range(200):
                try:
                    faults.maybe_fail("compile")
                    out.append(False)
                except faults.FaultInjected:
                    out.append(True)
        return out

    a = fires("compile:p=0.2:seed=11")
    b = fires("compile:p=0.2:seed=11")
    assert a == b, "same seed must reproduce the same fault sequence"
    rate = sum(a) / len(a)
    assert 0.05 < rate < 0.45, f"p=0.2 spec fired at {rate}"
    c = fires("compile:p=0.2:seed=12")
    assert a != c, "different seeds should diverge"


def test_fatal_action_raises_fatal_typed():
    before = compiled.stats["fault_compile"]
    with faults.inject("compile:1:fatal"):
        with pytest.raises(faults.FatalFaultInjected) as ei:
            faults.maybe_fail("compile")
    assert isinstance(ei.value, R.FatalError)
    assert not isinstance(ei.value, R.TransientError)
    assert compiled.stats["fault_compile"] == before + 1


def test_new_sites_registered():
    for site in ("stage_replay", "drain"):
        assert site in faults.SITES


# ---------------------------------------------------------------------------
# retry-backoff accounting (feeds the scheduler's honest hold-time EWMA)
# ---------------------------------------------------------------------------

def test_backoff_accrues_on_runtime(monkeypatch):
    monkeypatch.setenv("DSQL_RETRY_BASE_MS", "30")
    with R.query_scope() as rt:
        assert rt.backoff_s == 0.0
        R.backoff(1, "t")
        assert rt.backoff_s >= 0.025
        R.backoff(1, "t")
        assert rt.backoff_s >= 0.05


def test_backoff_accrual_survives_deadline_cut(monkeypatch):
    monkeypatch.setenv("DSQL_RETRY_BASE_MS", "400")
    with R.query_scope(timeout_s=0.05) as rt:
        with pytest.raises(R.DeadlineExceeded):
            R.backoff(1, "t")      # budget cannot cover: raises pre-sleep
        assert rt.backoff_s == 0.0
