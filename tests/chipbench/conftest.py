"""Fixtures of the chip benchmark's tests (CPU, small scale, no TPU
described at import)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: what ``tests/conftest.py`` pins off for the other suites; the benchmark
#: runs the engine as shipped, so its rehearsals take the pins out again
_PINS = ("DSQL_RESULT_CACHE_MB", "DSQL_MAX_CONCURRENT_QUERIES", "DSQL_TIERED",
         "DSQL_ADAPTIVE")


@pytest.fixture
def engine_as_shipped(monkeypatch):
    for name in _PINS:
        monkeypatch.delenv(name, raising=False)


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small():
    """(frames, context) of TPC-H at SF0.01, loaded as the harness loads."""
    from chipbench.data.tpch_gen import generate as generate_tpch
    from dask_sql_tpu import Context

    frames = generate_tpch(0.01, 2147483653)
    context = Context()
    for name, frame in frames.items():
        context.create_table(name, frame)
    return frames, context
