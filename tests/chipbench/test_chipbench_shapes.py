"""Each shape: its substitution, its byte count, and its reference against
the engine on the embedded surface at SF0.01."""
import datetime
import re

import pytest

from chipbench import compare, roofline, run

SHAPES = ["q1", "q6", "q12", "q14"]


def _shape(name):
    return run.load_by_path("shapes", name)


def _all_params(shape):
    return [shape.params_at(i) for i in range(shape.SPACE)]


def _day(text):
    return datetime.date.fromisoformat(text)


@pytest.mark.parametrize("name", SHAPES)
def test_every_parameter_set_is_a_text_of_its_own(name):
    shape = _shape(name)
    texts = {shape.sql(p) for p in _all_params(shape)}
    assert len(texts) == shape.SPACE
    assert shape.NAME == name


def test_q1_substitution_range():
    days = [_day(p["shipdate"]) for p in _all_params(_shape("q1"))]
    delta = [(datetime.date(1998, 12, 1) - d).days for d in days]
    assert min(delta) == 60 and max(delta) == 480  # cl.2.4.1.3: 60..120


def test_q6_substitution_range():
    params = _all_params(_shape("q6"))
    assert {p["quantity"] for p in params} == {24, 25}
    assert {p["discount_low"] for p in params} == {
        f"{d / 100:.2f}" for d in range(1, 9)}  # DISCOUNT 0.02..0.09, -0.01
    for p in params:
        assert round(float(p["discount_high"]) - float(p["discount_low"]),
                     2) == 0.02
        start, end = _day(p["date_from"]), _day(p["date_to"])
        assert datetime.date(1993, 1, 1) <= start <= datetime.date(1997, 1, 1)
        assert (end.year, end.month) == (start.year + 1, start.month)


def test_q12_substitution_range():
    for p in _all_params(_shape("q12")):
        start, end = _day(p["date_from"]), _day(p["date_to"])
        assert datetime.date(1993, 1, 1) <= start <= datetime.date(1997, 1, 1)
        assert 365 <= (end - start).days <= 366


def test_q14_substitution_range():
    for p in _all_params(_shape("q14")):
        start, end = _day(p["date_from"]), _day(p["date_to"])
        assert datetime.date(1993, 1, 1) <= start <= datetime.date(1997, 12, 1)
        assert (end - start).days == 30


@pytest.mark.parametrize("name", SHAPES)
def test_scan_columns_are_the_columns_the_text_names(name):
    shape = _shape(name)
    named = set(re.findall(r"\b[lop]_[a-z]+\b", shape.SQL))
    listed = {c for columns in shape.SCAN_COLUMNS.values() for c in columns}
    assert listed == named
    prefix = {"lineitem": "l_", "orders": "o_", "part": "p_"}
    for table, columns in shape.SCAN_COLUMNS.items():
        assert all(c.startswith(prefix[table]) for c in columns)


@pytest.mark.parametrize("name", SHAPES)
def test_scan_bytes_are_rows_times_itemsize(name, small):
    frames, context = small
    shape = _shape(name)
    catalog = roofline.catalog_columns(context)
    want = 0
    entries = context.schema[context.schema_name].tables
    for table, columns in shape.SCAN_COLUMNS.items():
        held = dict(zip(entries[table].table.names,
                        entries[table].table.columns))
        for column in columns:
            data = held[column].data
            assert data.shape[0] == len(frames[table])
            want += len(frames[table]) * data.dtype.itemsize
    assert roofline.scan_bytes(shape.SCAN_COLUMNS, catalog) == want > 0


@pytest.mark.parametrize("name", SHAPES)
@pytest.mark.parametrize("index", [0, -1])
def test_reference_agrees_with_the_engine_embedded(name, index, small):
    frames, context = small
    shape = _shape(name)
    params = shape.params_at(index % shape.SPACE)
    record = run.Embedded(context).execute(
        {"shape": name, "params": params, "sql": shape.sql(params)}, 60.0)
    assert record["error"] is None, record["error"]
    assert {"parse", "plan", "fetch"} <= set(record["phases"])
    gap, mismatched = compare.compare_frames(
        record["frame"], shape.reference(frames, **params))
    assert mismatched == 0
    assert gap <= compare.LIMITS["max_rel_gap"]
