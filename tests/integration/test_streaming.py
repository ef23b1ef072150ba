"""Out-of-HBM streaming execution (physical/streaming.py + io/chunked.py).

The reference's execution is out-of-core by construction (partitioned dask
dataframes, /root/reference/dask_sql/input_utils/convert.py:38-62); here the
equivalence under test is: a table registered ``chunked=True`` must produce
the same answers as the resident path while holding at most one batch on
device, with one compile for all batches (shared dictionaries + fixed batch
shapes).
"""
import os

import numpy as np
import pandas as pd
import pytest

from benchmarks.tpch import QUERIES, generate_tpch
from dask_sql_tpu import Context
from dask_sql_tpu.physical import compiled
from dask_sql_tpu.physical.streaming import StreamingUnsupported


@pytest.fixture(scope="module")
def tpch_pair():
    data = generate_tpch(0.01, seed=5)
    plain = Context()
    ck = Context()
    for name, frame in data.items():
        plain.create_table(name, frame)
        if name == "lineitem":
            ck.create_table(name, frame, chunked=True, batch_rows=16384)
        else:
            ck.create_table(name, frame)
    return plain, ck, data


def _assert_frames(a, b):
    a = a.reset_index(drop=True)
    b = b.reset_index(drop=True)
    for col in a.columns:
        if pd.api.types.is_float_dtype(a[col]):
            a[col] = a[col].astype(np.float64).round(6)
            b[col] = b[col].astype(np.float64).round(6)
    cols = list(a.columns)
    pd.testing.assert_frame_equal(a.sort_values(cols, ignore_index=True),
                                  b.sort_values(cols, ignore_index=True),
                                  check_dtype=False, rtol=1e-5, atol=1e-6)


# ALL 22 TPC-H queries with lineitem chunked (review item 5: the reference
# runs every query out-of-core).  Queries not touching lineitem (2, 11, 13,
# 16, 22) run the ordinary resident path — the point is that registering the
# big table chunked never changes any answer.  Iterative subtree lowering
# covers the multi-scan shapes: Q17 reads lineitem twice, Q21 three times,
# Q4/Q21/Q22 need the semi/anti key-set strategy, Q18's inner groupby is
# high-cardinality.
@pytest.mark.parametrize("qid", sorted(QUERIES))
def test_tpch_chunked_matches_resident(tpch_pair, qid):
    plain, ck, _ = tpch_pair
    want = plain.sql(QUERIES[qid], return_futures=False)
    got = ck.sql(QUERIES[qid], return_futures=False)
    _assert_frames(want, got)


@pytest.mark.skipif(os.environ.get("DSQL_COMPILE") == "0",
                    reason="asserts compiled-path batch reuse")
def test_batches_share_one_compiled_program(tpch_pair):
    _, ck, data = tpch_pair
    n_batches = (len(data["lineitem"]) + 16383) // 16384
    assert n_batches >= 3  # the test must actually exercise multi-batch
    before = dict(compiled.stats)
    ck.sql(QUERIES[6], return_futures=False)
    d = {k: compiled.stats[k] - before[k] for k in before}
    # one compile for the first batch (plus possibly the tiny merge plan);
    # every further batch must HIT the program cache
    assert d["hits"] >= n_batches - 1, d
    assert d["compiles"] <= 2, d


def test_chunked_parquet_roundtrip(tmp_path):
    df = pd.DataFrame({
        "g": ["x", "y", "z", "x"] * 700,
        "v": np.arange(2800, dtype=np.float64),
        "k": np.arange(2800) % 13,
    })
    path = str(tmp_path / "t.parquet")
    df.to_parquet(path, index=False, row_group_size=512)
    c = Context()
    c.create_table("t", path, chunked=True, batch_rows=1000)
    entry = c.schema["root"].tables["t"]
    assert entry.chunked.n_batches == 3  # 2800 rows / 1000, re-batched
    got = c.sql("SELECT g, SUM(v) AS s, COUNT(*) AS n FROM t GROUP BY g "
                "ORDER BY g", return_futures=False)
    exp = (df.groupby("g").agg(s=("v", "sum"), n=("v", "count"))
             .reset_index())
    np.testing.assert_allclose(got["s"], exp["s"])
    np.testing.assert_array_equal(got["n"], exp["n"])


def test_streaming_distinct_aggregate(tpch_pair):
    # DISTINCT aggregates stream as per-batch dedup (r2 gap, review item 5)
    plain, ck, _ = tpch_pair
    q = ("SELECT l_returnflag, COUNT(DISTINCT l_suppkey) AS n "
         "FROM lineitem GROUP BY l_returnflag")
    _assert_frames(plain.sql(q, return_futures=False),
                   ck.sql(q, return_futures=False))
    q2 = "SELECT COUNT(DISTINCT l_suppkey) AS n FROM lineitem"
    _assert_frames(plain.sql(q2, return_futures=False),
                   ck.sql(q2, return_futures=False))


def test_streaming_rejects_unmergeable_shapes(tpch_pair):
    _, ck, _ = tpch_pair
    with pytest.raises(StreamingUnsupported, match="DISTINCT"):
        # a DISTINCT mixed with a plain SUM cannot share one dedup stream
        ck.sql("SELECT COUNT(DISTINCT l_suppkey) AS n, SUM(l_quantity) AS s "
               "FROM lineitem")
    with pytest.raises(StreamingUnsupported, match="no aggregate or LIMIT"):
        ck.sql("SELECT l_orderkey FROM lineitem WHERE l_quantity > 1")


def test_streaming_null_group_keys():
    df = pd.DataFrame({"g": ["a", None, "a", None, "b"] * 200,
                       "v": np.arange(1000, dtype=np.float64)})
    plain = Context()
    plain.create_table("t", df)
    ck = Context()
    ck.create_table("t", df, chunked=True, batch_rows=128)
    q = "SELECT g, SUM(v) AS s, COUNT(*) AS n FROM t GROUP BY g"
    _assert_frames(plain.sql(q, return_futures=False),
                   ck.sql(q, return_futures=False))


def test_chunked_parquet_categorical_dictionaries(tmp_path):
    """Dictionary-encoded parquet columns whose row-group dictionaries
    differ must be re-encoded against ONE global dictionary — per-batch
    categorical codes mixed with a shared dictionary would silently decode
    to wrong strings (r2 review finding)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    # two row groups with DIFFERENT dictionary orders for the same column
    t1 = pa.table({"g": pa.array(["b", "a", "b"] * 100).dictionary_encode(),
                   "v": pa.array(np.arange(300, dtype=np.float64))})
    t2 = pa.table({"g": pa.array(["c", "b"] * 150).dictionary_encode(),
                   "v": pa.array(np.arange(300, 600, dtype=np.float64))})
    path = str(tmp_path / "cat.parquet")
    with pq.ParquetWriter(path, t1.schema) as w:
        w.write_table(t1)
        w.write_table(t2)
    c = Context()
    c.create_table("t", path, chunked=True, batch_rows=150)
    got = c.sql("SELECT g, COUNT(*) AS n, SUM(v) AS s FROM t GROUP BY g "
                "ORDER BY g", return_futures=False)
    df = pd.DataFrame({"g": ["b", "a", "b"] * 100 + ["c", "b"] * 150,
                       "v": np.arange(600, dtype=np.float64)})
    exp = df.groupby("g").agg(n=("v", "count"), s=("v", "sum")).reset_index()
    np.testing.assert_array_equal(got["g"], exp["g"])
    np.testing.assert_array_equal(got["n"], exp["n"])
    np.testing.assert_allclose(got["s"], exp["s"])


def test_chunked_parquet_binary_column_global_dictionary(tmp_path):
    """Binary arrow columns convert to object values; without a global
    dictionary pass each piece got a LOCAL dictionary and merged batches
    decoded against piece 0's codes (r2 advisor finding — counts came back
    {aa:250, bb:350} instead of {aa:100, bb:350, cc:150})."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    g1 = [b"aa"] * 100 + [b"bb"] * 200
    g2 = [b"bb"] * 150 + [b"cc"] * 150
    t1 = pa.table({"g": pa.array(g1, type=pa.binary()),
                   "v": pa.array(np.arange(300, dtype=np.float64))})
    t2 = pa.table({"g": pa.array(g2, type=pa.binary()),
                   "v": pa.array(np.arange(300, 600, dtype=np.float64))})
    path = str(tmp_path / "bin.parquet")
    with pq.ParquetWriter(path, t1.schema) as w:
        w.write_table(t1)
        w.write_table(t2)
    c = Context()
    c.create_table("t", path, chunked=True, batch_rows=150)
    got = c.sql("SELECT g, COUNT(*) AS n FROM t GROUP BY g ORDER BY g",
                return_futures=False)
    assert got["n"].tolist() == [100, 350, 150]
    # bytes decode to str (not repr) so string literals match
    assert got["g"].tolist() == ["aa", "bb", "cc"]
    one = c.sql("SELECT COUNT(*) AS n FROM t WHERE g = 'aa'",
                return_futures=False)
    assert one["n"].tolist() == [100]


def test_high_cardinality_groupby_merges_on_host(tpch_pair, monkeypatch):
    """A group-by whose partials exceed the device budget merges on HOST
    (pandas over the accumulated partials) — the shape that would
    previously OOM out-of-HBM mode's own merge step (r2 weakness 7)."""
    from dask_sql_tpu.physical import streaming as sm

    plain, ck, _ = tpch_pair
    monkeypatch.setattr(sm, "PARTIAL_BYTES_BUDGET", 1024)
    # group by orderkey: ~ one group per 4 rows — partials ARE the table
    q = ("SELECT l_orderkey, SUM(l_quantity) AS s, COUNT(*) AS n, "
         "MIN(l_discount) AS mi FROM lineitem GROUP BY l_orderkey")
    _assert_frames(plain.sql(q, return_futures=False),
                   ck.sql(q, return_futures=False))


def test_streaming_composes_with_mesh():
    """chunked=True under Context(mesh=): each uploaded batch row-shards
    over the mesh and the per-batch program runs as GSPMD — out-of-core AND
    distributed at once (review item 4)."""
    from dask_sql_tpu.parallel.mesh import default_mesh

    mesh = default_mesh()
    if mesh.devices.size < 2:
        pytest.skip("needs a multi-device mesh")
    data = generate_tpch(0.01, seed=5)
    plain = Context()
    dist = Context(mesh=mesh)
    for name, frame in data.items():
        plain.create_table(name, frame)
        if name == "lineitem":
            dist.create_table(name, frame, chunked=True, batch_rows=16384)
        else:
            dist.create_table(name, frame)
    # 1: heavy groupby; 3: join above the stream + topk; 9: 6-table
    # snowflake (5/6 exercise nothing further and GSPMD compiles are slow)
    for qid in (1, 3, 9):
        want = plain.sql(QUERIES[qid], return_futures=False)
        got = dist.sql(QUERIES[qid], return_futures=False)
        _assert_frames(want, got)


def test_chunked_inside_scalar_subquery(tpch_pair):
    # r2 rejected this shape; the iterative lowering streams the subquery
    # plan first (TPC-H Q15's shape)
    plain, ck, _ = tpch_pair
    q = ("SELECT s_suppkey FROM supplier WHERE s_suppkey > "
         "(SELECT AVG(l_suppkey) FROM lineitem)")
    _assert_frames(plain.sql(q, return_futures=False),
                   ck.sql(q, return_futures=False))


# ---------------------------------------------------------------------------
# out-of-core window functions (review r3 item 5): a window with
# PARTITION BY streams its input per batch, regroups rows into hash
# buckets of the partition keys, and runs the window resident per bucket
# (physical/streaming.py _stream_window_split).  The reference runs
# windows over partitioned input by construction
# (/root/reference/dask_sql/physical/rel/logical/window.py:207-414).
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def window_pair():
    rng = np.random.RandomState(7)
    n = 3000
    df = pd.DataFrame({
        "k": rng.randint(0, 11, n),
        "s": rng.choice(["a", "b", "c", None], n),
        "v": np.round(rng.randn(n), 4),
        "w": rng.randint(-50, 50, n).astype(np.float64),
    })
    plain = Context()
    plain.create_table("t", df)
    ck = Context()
    ck.create_table("t", df, chunked=True, batch_rows=256)
    return plain, ck


WINDOW_QUERIES = {
    "row_number": (
        "SELECT k, v, ROW_NUMBER() OVER (PARTITION BY k ORDER BY v, w) AS rn "
        "FROM t ORDER BY k, rn LIMIT 200"),
    "sum_over": (
        "SELECT k, SUM(v) OVER (PARTITION BY k ORDER BY v, w) AS c "
        "FROM t ORDER BY k, c LIMIT 200"),
    "rows_frame": (
        "SELECT k, SUM(w) OVER (PARTITION BY k ORDER BY v, w "
        "ROWS BETWEEN 2 PRECEDING AND CURRENT ROW) AS f "
        "FROM t ORDER BY k, f LIMIT 200"),
    "null_partition_keys": (
        "SELECT s, COUNT(*) OVER (PARTITION BY s) AS n, "
        "ROW_NUMBER() OVER (PARTITION BY s ORDER BY v, w) AS rn "
        "FROM t ORDER BY s, rn LIMIT 200"),
    "agg_above_window": (
        "SELECT k, MAX(rn) AS m, SUM(rs) AS t FROM (SELECT k, "
        "ROW_NUMBER() OVER (PARTITION BY k ORDER BY v, w) AS rn, "
        "SUM(v) OVER (PARTITION BY k) AS rs FROM t) x GROUP BY k "
        "ORDER BY k"),
}


@pytest.mark.parametrize("name", sorted(WINDOW_QUERIES))
def test_window_chunked_matches_resident(window_pair, name):
    plain, ck = window_pair
    q = WINDOW_QUERIES[name]
    _assert_frames(plain.sql(q, return_futures=False),
                   ck.sql(q, return_futures=False))


def test_window_output_reregisters_as_chunked(window_pair, monkeypatch):
    """A window output larger than the partial budget re-registers as a
    chunked source (sliced back into batch_rows batches) so the aggregate
    above it KEEPS streaming instead of materializing a table-sized temp."""
    from dask_sql_tpu.physical import streaming as sm

    plain, ck = window_pair
    monkeypatch.setattr(sm, "PARTIAL_BYTES_BUDGET", 1024)
    q = WINDOW_QUERIES["agg_above_window"]
    _assert_frames(plain.sql(q, return_futures=False),
                   ck.sql(q, return_futures=False))


def test_window_without_partition_rejected(window_pair):
    _, ck = window_pair
    with pytest.raises(StreamingUnsupported, match="PARTITION BY"):
        ck.sql("SELECT k, SUM(v) OVER (ORDER BY v) AS c FROM t")


def test_window_partition_skew_warns(caplog):
    """One giant partition defeats the per-bucket memory bound; the result
    stays correct but the weakened bound must be LOUD (no silent caps)."""
    import logging

    n = 600
    df = pd.DataFrame({"k": np.zeros(n, dtype=np.int64),
                       "v": np.arange(n, dtype=np.float64)})
    plain = Context()
    plain.create_table("t", df)
    ck = Context()
    ck.create_table("t", df, chunked=True, batch_rows=100)
    q = ("SELECT k, SUM(v) OVER (PARTITION BY k ORDER BY v) AS c "
         "FROM t ORDER BY c LIMIT 50")
    with caplog.at_level(logging.WARNING,
                         logger="dask_sql_tpu.physical.streaming"):
        got = ck.sql(q, return_futures=False)
    _assert_frames(plain.sql(q, return_futures=False), got)
    assert any("partition skew" in r.message for r in caplog.records)


def test_window_streaming_composes_with_mesh():
    from dask_sql_tpu.parallel.mesh import default_mesh

    mesh = default_mesh()
    if mesh.devices.size < 2:
        pytest.skip("needs a multi-device mesh")
    rng = np.random.RandomState(11)
    n = 1200
    df = pd.DataFrame({"k": rng.randint(0, 5, n),
                       "v": np.round(rng.randn(n), 4)})
    plain = Context()
    plain.create_table("t", df)
    dist = Context(mesh=mesh)
    dist.create_table("t", df, chunked=True, batch_rows=256)
    q = ("SELECT k, MAX(rn) AS m FROM (SELECT k, ROW_NUMBER() OVER "
         "(PARTITION BY k ORDER BY v) AS rn FROM t) x GROUP BY k ORDER BY k")
    _assert_frames(plain.sql(q, return_futures=False),
                   dist.sql(q, return_futures=False))
