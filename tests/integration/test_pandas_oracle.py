"""Third differential oracle: all 22 TPC-H queries, engine vs independent
pandas implementations (benchmarks/pandas_tpch.py).

The sqlite oracle (test_tpch.py) already judges the engine; the pandas
implementations are ALSO the benchmark baseline, so this test pins both at
once — a wrong baseline would make bench.py's vs_baseline meaningless, and a
third independently-written executor agreeing on all 22 queries is the
reference's compatibility-suite strategy scaled up
(/root/reference/tests/integration/test_compatibility.py strategy: same
query, independent engines, equal frames).
"""
import numpy as np
import pandas as pd
import pytest

from benchmarks.pandas_tpch import PANDAS_QUERIES, assert_frames_match
from benchmarks.tpch import QUERIES, generate_tpch
from dask_sql_tpu import Context


@pytest.fixture(scope="module")
def tpch():
    data = generate_tpch(0.02, seed=7)
    c = Context()
    for name, frame in data.items():
        c.create_table(name, frame)
    return c, data


@pytest.mark.parametrize("qid", sorted(QUERIES))
def test_engine_matches_pandas(tpch, qid):
    c, data = tpch
    eng = c.sql(QUERIES[qid], return_futures=False)
    ref = PANDAS_QUERIES[qid](data)
    assert_frames_match(eng, ref, f"Q{qid}")
