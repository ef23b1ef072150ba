"""A hash-table join's table has 16 slots a build row unless the build key's
ingest span says that table cannot be direct-addressed and a larger one can:
``statistics.key_span_hints`` gives a join on one integer key the class of
its base column's span (``span<j>l`` / ``span<j>r`` among a request's
capacities), ``hashing._hash_table_size`` takes the span's table where its
fill is small beside the probe it saves, and ``hashing._direct_info`` goes
on saying, from the run's own keys, whether the table is direct-addressed:
a hint that is wrong costs time and no answer.  ``span_tables`` on
``dispatch`` counts the tables a program sized that way."""
import importlib

import numpy as np
import pandas as pd
import pytest

from chipbench.data import tpch_gen
from dask_sql_tpu import Context
from dask_sql_tpu.ops import hashing
from dask_sql_tpu.physical import caps, compiled as cm, programs
from dask_sql_tpu.runtime import statistics as stats, telemetry as tel
from dask_sql_tpu.sql.parser import parse_sql

K = hashing._SPAN_SLOTS_A_ROW
SLOTS_MAX = hashing._TABLE_BYTES_MAX // hashing._SLOT_BYTES


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    """The hints are ingest statistics at work (conftest pins them off for
    every suite not named for them), and every case starts unlearned."""
    monkeypatch.setenv("DSQL_ADAPTIVE", "1")
    monkeypatch.delenv("DSQL_CAPS_FILE", raising=False)
    programs._cache.clear()
    caps._learned_caps.clear()


# ---------------------------------------------------------------------------
# the size
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,want", [
    (0, 16), (1, 16), (1000, 1 << 14), (15_000, 1 << 18),
    (131_072, 1 << 21), (150_000, 1 << 22), (524_288, 1 << 23),
    (1_500_000, 1 << 25), (2_000_000, 1 << 25),
    # 16 slots a row would take 3.2 GB: halved to what costs 1 GiB at most
    (15_000_000, 1 << 26),
    # and never a load factor over a half
    (60_000_000, 1 << 27)])
def test_without_a_span_a_table_has_the_slots_it_had(rows, want):
    assert hashing._hash_table_size(rows) == want
    assert hashing._hash_table_size(rows, 0, 1 << 30) == want
    # a span the table holds already changes nothing, whatever probes
    assert hashing._hash_table_size(rows, want, 1 << 30) == want
    assert hashing._hash_table_size(rows, 1, 0) == want


@pytest.mark.parametrize("case,rows,span,probe_rows,want", [
    # TPC-H Q10 at SF1: compacted orders under compacted lineitem, orderkeys
    # over 6 M: 2^21 slots cannot be direct-addressed, 2^23 can
    ("q10's first join", 131_072, 6_000_000, 2_097_152, 1 << 23),
    ("the hint is the span's class", 131_072, 1 << 23, 2_097_152, 1 << 23),
    # Q3's lineitem join: 16 slots a row hold the span already
    ("the default holds the span", 524_288, 6_000_000, 5_999_954, 1 << 23),
    ("a narrower span shrinks nothing", 150_000, 150_000, 524_288, 1 << 22),
    # SF10's orders under Q12's first round: halved to 2^26 by its bytes,
    # which is the class of its 60 M keys
    ("the halved table holds the span", 15_000_000, 60_000_000, 1 << 24,
     1 << 26),
    ("a tiny join over a wide span", 1024, 1 << 26, 65_536, 1 << 14),
    # the fill against the probe: K slots a probe row and no more
    ("at the probe's edge", 4096, K << 20, 1 << 20, K << 20),
    ("past the probe's edge", 4096, (K << 20) + 1, 1 << 20, 1 << 16),
    # a probe side smaller than the default table: K of its slots
    ("at the table's edge", 65_536, K << 20, 16, K << 20),
    ("past the table's edge", 65_536, (K << 20) + 1, 16, 1 << 20),
    # what a table may cost: 2^26 slots are 805 MB, 2^27 are 1.6 GB
    ("at the bytes limit", 1 << 20, 1 << 26, 1 << 26, 1 << 26),
    ("past the bytes limit", 1 << 20, (1 << 26) + 1, 1 << 26, 1 << 24),
])
def test_the_span_s_table_is_taken_where_it_fits_and_pays(
        case, rows, span, probe_rows, want):
    assert (1 << 26) <= SLOTS_MAX < (1 << 27)
    assert hashing._hash_table_size(rows, span, probe_rows) == want


# ---------------------------------------------------------------------------
# the hints' walk
# ---------------------------------------------------------------------------

N_FACT = 4000


def _class(values):
    domain = int(values.max()) - int(values.min()) + 1
    return 1 << (domain - 1).bit_length()


@pytest.fixture(scope="module")
def star():
    rng = np.random.default_rng(42)
    frames = {
        "f": pd.DataFrame({"ka": rng.integers(0, 9000, N_FACT),
                           "kb": rng.integers(0, 60, N_FACT),
                           "ks": rng.choice(["x", "y", "z"], N_FACT),
                           "v": np.round(rng.random(N_FACT), 6)}),
        "a": pd.DataFrame({"a_key": np.arange(100, 9100, 3),
                           "a_sub": np.arange(3000) % 7,
                           "w": np.round(rng.random(3000), 6)}),
        "b": pd.DataFrame({"b_key": np.arange(64),
                           "b_name": [f"n{i % 3}" for i in range(64)],
                           "ks": rng.choice(["x", "y", "z"], 64)}),
    }
    ctx = Context()
    for name, frame in frames.items():
        ctx.create_table(name, frame)
    return ctx, frames


def _plan(ctx, query):
    stmt = parse_sql(query)[0]
    return ctx._get_plan(getattr(stmt, "query", stmt), query)


def test_one_integer_key_resolves_to_its_base_column_s_span(star):
    """Through a filter and a project on either side, and through the join
    below for the key of the join above."""
    ctx, frames = star
    plan = _plan(ctx, (
        "SELECT f2.k2, a2.w, b.b_name FROM "
        "(SELECT ka AS k2, kb, v FROM f WHERE v > 0.25) AS f2 "
        "JOIN (SELECT w, a_key FROM a WHERE w >= 0.0) AS a2 "
        "ON f2.k2 = a2.a_key JOIN b ON f2.kb = b.b_key"))
    assert stats.key_span_hints(plan, ctx) == {
        "span0l": _class(frames["f"]["ka"]),
        "span0r": _class(frames["a"]["a_key"]),
        "span1l": _class(frames["f"]["kb"]),
        "span1r": _class(frames["b"]["b_key"])}
    assert _class(frames["a"]["a_key"]) == 1 << 14
    # they ride with the capacities a request starts from
    pk = cm._keyed(plan, ctx)
    assert {t: c for t, c in caps.starting_caps(pk, ctx, count=False).items()
            if t.startswith("span")} == stats.key_span_hints(plan, ctx)


@pytest.mark.parametrize("case,query", [
    ("a key of two parts",
     "SELECT f.v, a.w FROM f JOIN a ON f.ka = a.a_key AND f.kb = a.a_sub"),
    ("a computed key",
     "SELECT f.v, a.w FROM f JOIN (SELECT a_key + 1 AS k, w FROM a) AS a "
     "ON f.ka = a.k"),
    ("a string key", "SELECT f.v, b.b_key FROM f JOIN b ON f.ks = b.ks"),
])
def test_a_key_without_one_integer_base_column_gets_no_hint(star, case, query):
    ctx, _ = star
    plan = _plan(ctx, query)
    assert len(stats.join_tags(plan)) == 1
    hints = stats.key_span_hints(plan, ctx)
    if case == "a computed key":
        # the side whose key is a column keeps its own
        assert set(hints) == {"span0l"}
    else:
        assert hints == {}


def test_the_hints_are_silent_when_statistics_are_off(star, monkeypatch):
    ctx, _ = star
    plan = _plan(ctx, "SELECT f.v, a.w FROM f JOIN a ON f.ka = a.a_key")
    assert set(stats.key_span_hints(plan, ctx)) == {"span0l", "span0r"}
    monkeypatch.setenv("DSQL_ADAPTIVE", "0")
    assert stats.key_span_hints(plan, ctx) == {}


def test_the_numbering_is_join_tags_under_a_scalar_subquery(star):
    """A scalar subquery's joins get no tag and no hint, so the walk and the
    tracer cannot disagree on which join is which."""
    ctx, frames = star
    plan = _plan(ctx, (
        "SELECT f.v, a.w, b.b_name FROM f JOIN a ON f.ka = a.a_key "
        "JOIN b ON f.kb = b.b_key "
        "WHERE f.v > (SELECT AVG(a.w) FROM a JOIN b ON a.a_sub = b.b_key)"))
    tags = stats.join_tags(plan)
    assert sorted(tags.values()) == ["ord0", "ord1"]
    hints = stats.key_span_hints(plan, ctx)
    assert {stats.span_tag(t + s) for t in tags.values() for s in "lr"} \
        == set(hints)
    assert sorted(hints.values()) == sorted(
        _class(frames[t][c]) for t, c in
        (("f", "ka"), ("a", "a_key"), ("f", "kb"), ("b", "b_key")))


# ---------------------------------------------------------------------------
# TPC-H Q10's first join, and the two shapes beside it that keep their tables
# ---------------------------------------------------------------------------

#: lineitem has 360 000 rows here and orders 90 000 whose keys span 360 000
SF = 0.06


def _shape(name):
    return importlib.import_module("chipbench.shapes." + name)


@pytest.fixture(scope="module")
def tpch():
    frames = tpch_gen.generate(SF, 42)
    ctx = Context()
    for name, frame in frames.items():
        ctx.create_table(name, frame)
    return ctx, frames


@pytest.fixture
def tpu_strategy(monkeypatch):
    """The compiled tier as a TPU runs it, with the hash-table join at
    these rows too (``tests/chipbench/test_chipbench_joins.py`` forces the
    same)."""
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    monkeypatch.setattr(cm, "SORT_ROWS_MAX", 256)
    monkeypatch.setattr(cm, "LEXSORT_ROWS_MAX", 8)


def _assert_answer(shape, got, frames, params):
    want = shape.reference(frames, **params)
    assert len(got) == len(want) > 0
    for column in want.columns:
        a, b = got[column].to_numpy(), want[column].to_numpy()
        if a.dtype.kind == "f":
            np.testing.assert_allclose(a, b.astype(float), rtol=1e-9)
        else:
            assert (pd.Series(a).astype(str).to_numpy()
                    == pd.Series(b).astype(str).to_numpy()).all(), column


def _spans(ctx):
    return {s.name: s.attrs for s in ctx.last_report.root.walk()
            if s.name in ("dispatch", "materialize")}


COUNTERS = ("join_probes_direct", "join_probes_looped", "fallbacks",
            "recompiles")


def _counters():
    c = tel.REGISTRY.snapshot()["counters"]
    return {name: c.get(name, 0) for name in COUNTERS}


@pytest.mark.parametrize("name,joins,span_tables", [
    ("q10", 3, 1), ("q3", 2, 0), ("q5", 5, 0)])
def test_a_shape_answers_as_its_reference_and_says_how_its_tables_were_sized(
        tpch, tpu_strategy, name, joins, span_tables):
    ctx, frames = tpch
    shape = _shape(name)
    for i in (shape.FIRST, 0, shape.SPACE - 1):
        params = shape.params_at(i)
        before = _counters()
        got = ctx.sql(shape.SQL.format(**params), return_futures=False)
        _assert_answer(shape, got, frames, params)
        spans = _spans(ctx)
        assert spans["materialize"]["hash_table_joins"] == joins
        assert spans["materialize"]["direct_probes"] == joins
        assert _counters()["join_probes_looped"] \
            == before["join_probes_looped"]
        if i != shape.FIRST:    # a first arrival runs inside ``compile``
            assert spans["dispatch"]["span_tables"] == span_tables
    final = [e for e in programs._cache.values()
             if e is not programs._UNSUPPORTED][-1]
    assert final.meta["span_tables"] == span_tables


def test_a_hint_too_small_loops_as_before_and_answers_the_same(
        tpch, tpu_strategy):
    """The span's class halved among the learned capacities (a table loaded
    again with wider keys, a cap learned on another day): the table it
    sizes does not hold the run's keys, ``fits`` says so, and the probe
    loops: the same answer, no fallback, no recompile."""
    ctx, frames = tpch
    shape = _shape("q10")
    ctx.sql(shape.SQL.format(**shape.params_at(shape.FIRST)),
            return_futures=False)
    (base_key, learned), = caps._learned_caps.items()
    keys = frames["orders"]["o_orderkey"]
    assert learned["span1l"] == learned["span1r"] == _class(keys)
    caps._learned_caps[base_key] = {
        **learned, "span1l": _class(keys) // 2, "span1r": _class(keys) // 2}
    programs._cache.clear()
    before = _counters()
    params = shape.params_at(shape.FIRST + 40)
    got = ctx.sql(shape.SQL.format(**params), return_futures=False)
    _assert_answer(shape, got, frames, params)
    attrs = _spans(ctx)["materialize"]
    assert (attrs["hash_table_joins"], attrs["direct_probes"]) == (3, 2)
    after = _counters()
    assert after["join_probes_looped"] == before["join_probes_looped"] + 1
    assert after["fallbacks"] == before["fallbacks"]
    assert after["recompiles"] == before["recompiles"]
    program, = [e for e in programs._cache.values()
                if e is not programs._UNSUPPORTED]
    assert program.meta["span_tables"] == 1     # sized by what it was told


def test_a_tiny_join_over_a_wide_span_keeps_its_table():
    """1 024 build rows under 65 536 probe rows, keys over 2^26: a table of
    that many slots is a fill of half a gigabyte to save a probe of
    milliseconds."""
    rng = np.random.default_rng(5)
    bk = np.arange(1024, dtype=np.int64) * 65_521
    b = pd.DataFrame({"k": bk, "v": np.round(rng.random(1024), 6)})
    p = pd.DataFrame({"k": rng.choice(np.concatenate([bk, bk + 1]), 65_536),
                      "w": np.round(rng.random(65_536), 6)})
    ctx = Context()
    ctx.create_table("p", p)
    ctx.create_table("b", b)
    query = "SELECT p.k, p.w, b.v FROM p JOIN b ON p.k = b.k"
    assert stats.key_span_hints(_plan(ctx, query), ctx)["span0r"] == 1 << 26
    before = _counters()
    got = ctx.sql(query, return_futures=False)
    want = p.merge(b, on="k")
    assert len(got) == len(want) > 1000
    key = ["k", "w"]
    pd.testing.assert_frame_equal(
        got.sort_values(key, ignore_index=True),
        want.sort_values(key, ignore_index=True))
    program, = [e for e in programs._cache.values()
                if e is not programs._UNSUPPORTED]
    assert (program.meta["hash_table_joins"],
            program.meta["span_tables"]) == (1, 0)
    assert _counters()["join_probes_looped"] \
        == before["join_probes_looped"] + 1


def test_a_small_join_whose_span_pays_probes_direct():
    """1 000 build rows over a span of 100 000 under 4 000 probe rows: 16 384
    slots cannot be direct-addressed, 131 072 can, at 8 slots a slot of those."""
    rng = np.random.default_rng(6)
    bk = rng.choice(np.arange(100_000), 1000, replace=False)
    b = pd.DataFrame({"k": bk, "v": np.round(rng.random(1000), 6)})
    p = pd.DataFrame({"k": rng.integers(-5, 100_005, 4000),
                      "w": np.round(rng.random(4000), 6)})
    p.loc[:999, "k"] = bk
    ctx = Context()
    ctx.create_table("p", p)
    ctx.create_table("b", b)
    before = _counters()
    got = ctx.sql("SELECT p.k, p.w, b.v FROM p JOIN b ON p.k = b.k",
                  return_futures=False)
    want = p.merge(b, on="k")
    assert len(got) == len(want) >= 1000
    key = ["k", "w"]
    pd.testing.assert_frame_equal(
        got.sort_values(key, ignore_index=True),
        want.sort_values(key, ignore_index=True))
    program, = [e for e in programs._cache.values()
                if e is not programs._UNSUPPORTED]
    assert (program.meta["hash_table_joins"],
            program.meta["span_tables"]) == (1, 1)
    after = _counters()
    assert after["join_probes_direct"] == before["join_probes_direct"] + 1
    assert after["join_probes_looped"] == before["join_probes_looped"]
