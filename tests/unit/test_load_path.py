"""The load path of ``Context.create_table`` (PR 31): strings are hashed to
codes and only the dictionary is sorted, statistics are read off the host
arrays, and every table's load is a ``load`` span with three children and
six counters."""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu.runtime import statistics, telemetry
from dask_sql_tpu.table import (Table, _host_encode_strings,
                                host_encode_series, string_uniques)


def encode_by_element(values, mask=None):
    """The encoding as it was before PR 31, a Python step per row: what the
    hashed one has to equal in codes, mask and dictionary."""
    values = np.array(
        [v.decode("utf-8", "surrogateescape")
         if isinstance(v, (bytes, bytearray)) else v
         for v in np.asarray(values, dtype=object)], dtype=object)
    isna = np.array([v is None or (isinstance(v, float) and np.isnan(v))
                     for v in values], dtype=bool)
    safe = np.where(isna, "", values).astype(str)
    dictionary, codes = np.unique(safe, return_inverse=True)
    if isna.any():
        mask = ~isna if mask is None else (np.asarray(mask, bool) & ~isna)
    return codes.astype(np.int32), mask, dictionary.astype(object)


STRING_CASES = {
    "sorted_dictionary": ["pear", "apple", "fig", "apple", "pear"],
    "null": ["b", None, "a", None, "b"],
    "nan": ["b", float("nan"), "a", np.float64("nan")],
    "null_beside_an_empty_string": ["", None, "a"],
    "all_null": [None, None],
    "bytes": [b"x", "x", b"\xff\xfe", "y", bytearray(b"z")],
    "empty_column": [],
    "one_value": ["z"] * 5,
    "all_distinct": [f"k{i:03d}" for i in range(200)][::-1],
    "non_ascii": ["é", "z", "日本", "a", "\U0001f600", ""],
    "not_strings": ["a", 1, 1.0, True, None],
}


def same_encoding(got, want):
    codes, mask, stype, dictionary = got
    w_codes, w_mask, w_dictionary = want
    assert codes.dtype == np.int32 and stype.is_string
    assert codes.tolist() == w_codes.tolist()
    assert (mask is None) == (w_mask is None)
    assert mask is None or mask.tolist() == w_mask.tolist()
    assert dictionary.dtype == object
    assert dictionary.tolist() == w_dictionary.tolist()
    assert all(type(v) is str for v in dictionary)
    assert dictionary.tolist() == sorted(dictionary.tolist())


@pytest.mark.parametrize("masked", [False, True], ids=["bare", "masked"])
@pytest.mark.parametrize("case", STRING_CASES)
def test_hashed_string_encode_equals_the_per_element_one(case, masked):
    values = np.array(STRING_CASES[case], dtype=object)
    mask = (np.arange(len(values)) % 2 == 0) if masked else None
    same_encoding(_host_encode_strings(values, mask),
                  encode_by_element(values, mask))
    assert string_uniques(values).tolist() == \
        encode_by_element(values)[2].tolist()


@pytest.mark.parametrize("dtype", ["str", "string", object, "U", "S"])
@pytest.mark.parametrize("case", ["sorted_dictionary", "null", "one_value",
                                  "all_distinct", "non_ascii",
                                  "empty_column"])
def test_every_string_container_encodes_alike(case, dtype):
    values = STRING_CASES[case]
    if dtype in ("U", "S"):
        if None in values or (dtype == "S" and case == "non_ascii"):
            pytest.skip("a numpy string array holds no NULL, and a bytes "
                        "one no character beyond ASCII")
        got = _host_encode_strings(np.array(values, dtype=dtype), None)
    else:
        got = host_encode_series(pd.Series(values, dtype=dtype))
    same_encoding(got, encode_by_element(values))


def test_a_shared_dictionary_is_kept_and_an_absent_value_raises():
    shared = np.array(["", "a", "b", "c"], dtype=object)
    for values in (["b", "a", "c", "a"], ["b", None, "a"]):
        codes, mask, _, dictionary = _host_encode_strings(
            np.array(values, dtype=object), None, shared)
        assert dictionary is shared and codes.dtype == np.int32
        assert [shared[c] for c in codes] == [v or "" for v in values]
        assert (mask is None) == (None not in values)
    with pytest.raises(ValueError, match=r"absent from the shared "
                                         r"dictionary \(first few: \['q'\]"):
        _host_encode_strings(np.array(["a", "q"], dtype=object), None, shared)
    with pytest.raises(ValueError, match="absent from the shared"):
        # NULL is "" and the dictionary has to hold it
        _host_encode_strings(np.array(["a", None], dtype=object), None,
                             np.array(["a"], dtype=object))


def test_a_categorical_keeps_its_own_path():
    s = pd.Series(["b", "a", None, "b"]).astype(
        pd.CategoricalDtype(["b", "a"]))
    codes, mask, stype, dictionary = host_encode_series(s)
    assert dictionary.tolist() == ["b", "a"] and codes.tolist() == [0, 1, 0, 0]
    assert mask.tolist() == [True, True, False, True] and stype.is_string


def test_a_decimal_column_is_still_found_behind_nulls():
    import decimal

    data, mask, stype, _ = host_encode_series(pd.Series(
        [None, decimal.Decimal("1.25"), decimal.Decimal("2.5")],
        dtype=object))
    assert stype.name == "DECIMAL" and data.tolist() == [0.0, 1.25, 2.5]
    assert mask.tolist() == [False, True, True]


STATS_FRAMES = {
    "int": pd.DataFrame({"k": np.arange(5000) % 97, "wide": np.arange(
        5000, dtype=np.int64) * 1_000_003}),
    "float_with_nan": pd.DataFrame({"f": [1.5, float("nan"), -2.0, 7.25]}),
    "bool": pd.DataFrame({"b": [True, False, True, True]}),
    "date": pd.DataFrame({"d": pd.to_datetime(
        ["1995-03-15", "1992-01-01", None, "1998-08-02"])}),
    "string": pd.DataFrame({"s": ["x", None, "y", "x"]}),
    "masked": pd.DataFrame({"m": pd.array([1, None, 3, 3], dtype="Int64"),
                            "all_null": pd.array([None] * 4,
                                                 dtype="Float64")}),
}


@pytest.mark.parametrize("kind", STATS_FRAMES)
def test_stats_from_host_arrays_equal_the_devices(kind):
    frame = STATS_FRAMES[kind]
    host = Table.host_from_pandas(frame)
    assert all(isinstance(c.data, np.ndarray) for c in host.columns)
    from_host = statistics.collect_table_stats(host)
    from_device = statistics.collect_table_stats(host.to_device())
    assert from_host.rows == from_device.rows == len(frame)
    assert set(from_host.cols) == set(frame.columns)
    for name in frame.columns:
        assert from_host.cols[name].to_row() == pytest.approx(
            from_device.cols[name].to_row(), nan_ok=True)


def test_stats_of_a_padded_table_are_the_unpadded_frames():
    """The mesh pads a table and marks the real rows with ``row_valid``:
    what the host arrays say before the padding is what the device says
    under the mask."""
    import jax.numpy as jnp

    from dask_sql_tpu.table import Column

    frame = pd.DataFrame({"k": [5, 3, 9], "s": ["b", None, "a"]})
    host = Table.host_from_pandas(frame)
    device = host.to_device()
    padded = Table(device.names, [
        Column(jnp.concatenate([c.data, jnp.zeros(2, c.data.dtype)]),
               c.stype, None if c.mask is None else jnp.concatenate(
                   [c.mask, jnp.zeros(2, bool)]), c.dictionary)
        for c in device.columns])
    under_mask = statistics.collect_table_stats(
        padded, row_valid=jnp.arange(5) < 3)
    from_host = statistics.collect_table_stats(host)
    assert under_mask.rows == from_host.rows == 3
    for name in frame.columns:
        assert from_host.cols[name].to_row() == pytest.approx(
            under_mask.cols[name].to_row(), nan_ok=True)


@pytest.mark.parametrize("kind", STATS_FRAMES)
def test_columns_taken_side_by_side_load_as_one_after_another(kind,
                                                              monkeypatch):
    """From ``PARALLEL_LOAD_ROWS`` rows on a load's columns are encoded and
    counted by a pool of threads: the same arrays, dictionaries and
    statistics, in the frame's order."""
    import threading

    from dask_sql_tpu import table as table_module

    frame = STATS_FRAMES[kind]
    frame = frame.assign(again=frame[frame.columns[0]])
    one_by_one = Table.host_from_pandas(frame)
    stats_one_by_one = statistics.collect_table_stats(one_by_one)

    seen = set()
    encode = table_module.host_encode_series

    def noting(series):
        seen.add(threading.current_thread().name)
        return encode(series)

    monkeypatch.setattr(table_module, "PARALLEL_LOAD_ROWS", 1)
    monkeypatch.setattr(table_module, "host_encode_series", noting)
    side_by_side = Table.host_from_pandas(frame)
    assert seen and "MainThread" not in seen
    assert side_by_side.names == one_by_one.names == list(frame.columns)
    for got, want in zip(side_by_side.columns, one_by_one.columns):
        assert got.stype == want.stype and got.data.dtype == want.data.dtype
        assert got.data.tolist() == want.data.tolist()
        assert (got.mask is None) == (want.mask is None)
        assert got.mask is None or got.mask.tolist() == want.mask.tolist()
        assert (got.dictionary is None) == (want.dictionary is None)
        assert got.dictionary is None \
            or got.dictionary.tolist() == want.dictionary.tolist()
    stats = statistics.collect_table_stats(side_by_side)
    assert list(stats.cols) == list(stats_one_by_one.cols)
    for name in stats.cols:
        assert stats.cols[name].to_row() == pytest.approx(
            stats_one_by_one.cols[name].to_row(), nan_ok=True)


def test_a_device_tables_statistics_take_one_column_at_a_time(monkeypatch):
    """Only host arrays are counted side by side: a device table's columns
    each come to the host whole, and one at a time is all the memory that
    should take."""
    from dask_sql_tpu import table as table_module

    calls = []
    real = table_module.map_columns

    def noting(fn, columns, rows):
        calls.append(rows)
        return real(fn, columns, rows)

    monkeypatch.setattr(table_module, "map_columns", noting)
    frame = STATS_FRAMES["int"]
    host = Table.host_from_pandas(frame)
    statistics.collect_table_stats(host)
    statistics.collect_table_stats(host.to_device())
    assert calls == [len(frame), len(frame), 0]


LOAD_COUNTERS = ("load_tables", "load_rows", "load_bytes", "load_encode_ms",
                 "load_stats_ms", "load_transfer_ms")


def load_counters():
    return {k: telemetry.REGISTRY.get(k, 0) for k in LOAD_COUNTERS}


def test_create_table_writes_four_spans_and_six_counters(monkeypatch):
    seen = []
    collect = statistics._collect_column

    def watched(name, col, rows, valid_rows):
        seen.append((name, type(col.data), type(col.mask)))
        return collect(name, col, rows, valid_rows)

    monkeypatch.setattr(statistics, "_collect_column", watched)
    frame = pd.DataFrame({"k": np.arange(1000), "s": ["x", None] * 500,
                          "f": np.linspace(0, 1, 1000)})
    before = load_counters()
    context = Context()
    context.create_table("Loaded", frame)
    # statistics read the host arrays: nothing comes back from the device
    assert [name for name, _, _ in seen] == ["k", "s", "f"]
    assert all(data is np.ndarray and mask in (np.ndarray, type(None))
               for _, data, mask in seen)
    load = telemetry.last_load()
    assert load.name == "load" and load.t1 is not None
    assert [c.name for c in load.children] == [
        "load_encode", "load_stats", "load_transfer"]
    assert all(c.t1 is not None and c.t0 >= load.t0 and c.t1 <= load.t1
               for c in load.children)
    assert load.attrs == {"table": "loaded", "rows": 1000,
                          "bytes": 8000 + 4000 + 1000 + 8000,
                          "string_columns": 1}
    delta = {k: v - before[k] for k, v in load_counters().items()}
    assert delta["load_tables"] == 1 and delta["load_rows"] == 1000
    assert delta["load_bytes"] == load.attrs["bytes"]
    spans = {c.name: c.wall_ms for c in load.children}
    for step in ("encode", "stats", "transfer"):
        assert abs(delta[f"load_{step}_ms"] - spans[f"load_{step}"]) <= 0.5
    entry = context.schema[context.schema_name].tables["loaded"]
    assert entry.stats.rows == 1000 and set(entry.stats.cols) == {"k", "s",
                                                                  "f"}
    assert entry.stats.cols["s"].null_frac == 0.5
    got = context.sql("SELECT s, COUNT(*) AS n, SUM(k) AS t FROM loaded "
                      "GROUP BY s", return_futures=False)
    assert sorted(got["n"].tolist()) == [500, 500]


def test_a_device_table_loads_without_an_encode_span():
    context = Context()
    context.create_table("t", Table.from_pandas(pd.DataFrame({"a": [1, 2]})))
    load = telemetry.last_load()
    assert [c.name for c in load.children] == ["load_transfer", "load_stats"]
    assert load.attrs["rows"] == 2
    stats = context.schema[context.schema_name].tables["t"].stats
    assert stats.cols["a"].max == 2.0


def test_a_load_inside_a_query_rides_the_querys_trace(tmp_path):
    path = tmp_path / "t.csv"
    pd.DataFrame({"a": [1, 2, 3], "s": ["x", "y", "x"]}).to_csv(
        path, index=False)
    context = Context()
    context.create_table("t", pd.DataFrame({"a": [1]}))
    mark = telemetry.last_load()
    context.sql(f"CREATE TABLE u WITH (location = '{path}', format = 'csv')")
    assert telemetry.last_load() is mark
    report = telemetry.last_report()
    assert report.span_count("load") == 1
    assert report.span_count("load_transfer") == 1


def test_from_pandas_is_host_encode_then_upload():
    import jax

    frame = pd.DataFrame({"a": [1, 2], "s": ["x", "y"]})
    table = Table.from_pandas(frame)
    assert all(isinstance(c.data, jax.Array) for c in table.columns)
    back = table.to_pandas()
    assert back["a"].tolist() == [1, 2] and back["s"].tolist() == ["x", "y"]


def test_the_loads_spans_are_events_on_a_profilers_trace(tmp_path):
    """``dsql:load`` and its three children lie on the trace's clock like a
    query's spans: one event each, on the thread that loaded."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        Context().create_table("t", pd.DataFrame({"a": [1, 2], "s": ["x",
                                                                    "y"]}))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    lines = [[e.name for e in line.events if e.name.startswith("dsql:")]
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    assert [names for names in lines if names] == [[
        "dsql:load", "dsql:load_encode", "dsql:load_stats",
        "dsql:load_transfer"]]
