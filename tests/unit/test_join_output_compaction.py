"""A join hands on every probe row, matched or not, and the join above it
pays for all of them: where another join takes a join's output in, the
output is a compaction site (``compiled._compact_eligible``,
``_Tracer._maybe_compact(after_join=True)``).  An unlearned one only counts;
``_check_flags`` sizes the whole chain of sites from one round's counts."""
import importlib

import numpy as np
import pandas as pd
import pytest

from chipbench.data import tpch_gen
from dask_sql_tpu import Context
from dask_sql_tpu.physical import compiled as cm, programs
from dask_sql_tpu.physical.caps import (_NeedsRecompile, _check_flags,
                                        _learned_caps)
from dask_sql_tpu.plan.nodes import LogicalJoin
from dask_sql_tpu.sql.parser import parse_sql

#: lineitem has 360 000 rows here: the sites above it engage (65 536 rows)
SF = 0.06


def _shape(name):
    return importlib.import_module("chipbench.shapes." + name)


def _joins(rel):
    if isinstance(rel, LogicalJoin):
        yield rel
    for i in rel.inputs:
        yield from _joins(i)


@pytest.fixture(scope="module")
def tpch():
    frames = tpch_gen.generate(SF, 28)
    ctx = Context()
    for name, frame in frames.items():
        ctx.create_table(name, frame)
    return ctx, frames


@pytest.fixture
def tpu_strategy(monkeypatch):
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    monkeypatch.delenv("DSQL_CAPS_FILE", raising=False)
    programs._cache.clear()
    _learned_caps.clear()


@pytest.mark.parametrize("name,sites", [
    ("q3", 1), ("q5", 4), ("q10", 2),
    ("q1", 0), ("q6", 0), ("q12", 0), ("q14", 0)])
def test_the_sites_are_the_joins_that_another_join_takes_in(tpch, name,
                                                           sites):
    shape = _shape(name)
    text = shape.SQL.format(**shape.params_at(shape.FIRST))
    plan = tpch[0]._get_plan(parse_sql(text)[0].query)
    marked = cm._compact_eligible(plan)
    joins = list(_joins(plan))
    fed_to_a_join = [j for j in joins if id(j) in marked]
    assert len(fed_to_a_join) == sites
    # every join but the topmost feeds another one in these plans
    assert sites == max(len(joins) - 1, 0)


def _programs():
    return [e for e in programs._cache.values() if e is not programs._UNSUPPORTED]


def _live_sites(entry):
    return {tag: cap for (n, _, tag), cap in zip(entry.meta["agg_sites"],
                                                 entry.meta["ngroup_caps"])
            if tag.startswith("cmp") and cap < n}


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


#: (shape, the join-output sites live in its final program)
SHAPES = [("q3", {"cmpj0"}), ("q5", {"cmpj1", "cmpj2"}), ("q10", {"cmpj1"})]


@pytest.mark.parametrize("name,live", SHAPES)
def test_a_shape_answers_as_its_reference_with_the_sites_live(
        tpch, tpu_strategy, name, live):
    ctx, frames = tpch
    shape = _shape(name)
    for i in (shape.FIRST, 0, shape.SPACE - 1):
        params = shape.params_at(i)
        got = ctx.sql(shape.SQL.format(**params), return_futures=False)
        _assert_answer(shape, got, frames, params)
    final = _programs()[-1]
    sites = _live_sites(final)
    assert {t for t in sites if t.startswith("cmpj")} == live


def test_a_site_on_the_build_side_shrinks_the_table(tpch, tpu_strategy):
    """Q3: the first join's output is the second's BUILD side."""
    ctx, frames = tpch
    shape = _shape("q3")
    params = shape.params_at(shape.FIRST)
    got = ctx.sql(shape.SQL.format(**params), return_futures=False)
    _assert_answer(shape, got, frames, params)
    final = _programs()[-1]
    cap = _live_sites(final)["cmpj0"]
    assert cap * 2 < len(frames["orders"])
    # both date filters keep half their rows and compact nothing: join one
    # takes in customer and orders, join two lineitem and the site's capacity
    assert final.meta["join_rows"] == (
        len(frames["customer"]) + len(frames["orders"])
        + len(frames["lineitem"]) + cap)


def test_a_site_that_overflows_recompiles_to_the_same_answer(tpch,
                                                             tpu_strategy):
    ctx, frames = tpch
    shape = _shape("q5")
    ctx.sql(shape.SQL.format(**shape.params_at(shape.FIRST)),
            return_futures=False)
    (base_key, learned), = _learned_caps.items()
    tight = learned["cmpj2"]
    # a process that had learned on less data: the third join's output
    # does not fit, rows are dropped, and the flags say so
    _learned_caps[base_key] = {**learned, "cmpj2": 1024}
    programs._cache.clear()
    recompiles = cm.stats["recompiles"]
    params = shape.params_at(shape.FIRST + 40)
    got = ctx.sql(shape.SQL.format(**params), return_futures=False)
    _assert_answer(shape, got, frames, params)
    assert cm.stats["recompiles"] == recompiles + 1
    assert 1024 < _learned_caps[base_key]["cmpj2"] <= tight
    # and the sites below it, whose counts were true, stayed as they were
    assert {t: c for t, c in _learned_caps[base_key].items()
            if t != "cmpj2"} == {t: c for t, c in learned.items()
                                 if t != "cmpj2"}


N = 1 << 17


@pytest.fixture(scope="module")
def chain():
    """fact joins a, then b: ``a`` holds one key in 24, ``every_a`` and
    ``b`` all of them."""
    rng = np.random.RandomState(28)
    frames = {
        "fact": pd.DataFrame({"ka": rng.randint(0, 4096, N),
                              "kb": rng.randint(0, 64, N),
                              "v": np.round(rng.rand(N), 3)}),
        "a": pd.DataFrame({"a_key": np.arange(0, 4096, 24)}),
        "every_a": pd.DataFrame({"a_key": np.arange(4096)}),
        "b": pd.DataFrame({"b_key": np.arange(64)}),
    }
    ctx = Context()
    for name, frame in frames.items():
        ctx.create_table(name, frame)
    return ctx, frames


#: no site but the first join's output: a global aggregate has none
CHAIN = ("SELECT SUM(v) AS s, COUNT(*) AS c FROM fact "
         "JOIN {a} ON ka = a_key JOIN b ON kb = b_key")


def _dispatched(ctx, frames, a):
    """The ``dispatch`` span of CHAIN over ``a`` and the rows it joined.  A
    first arrival runs its program inside ``compile``: the second
    dispatches the one that is there."""
    ctx.sql(CHAIN.format(a=a), return_futures=False)
    got = ctx.sql(CHAIN.format(a=a), return_futures=False)
    span, = [s for s in ctx.last_report.root.walk() if s.name == "dispatch"]
    kept = frames["fact"][frames["fact"]["ka"].isin(frames[a]["a_key"])]
    assert got["c"][0] == len(kept)
    np.testing.assert_allclose(got["s"][0], kept["v"].sum(), rtol=1e-9)
    return span, len(kept)


def test_dispatch_says_the_rows_the_joins_take_in_and_the_live_sites(
        chain, tpu_strategy):
    ctx, frames = chain
    span, kept = _dispatched(ctx, frames, "a")
    assert N // 32 < kept < N // 16
    cap = 2 << (kept - 1).bit_length()   # twice the power of two above
    first, final = _programs()
    assert _live_sites(final) == {"cmpj0": cap}
    assert span.attrs["compact_sites"] == 1
    assert span.attrs["compact_cap"] == cap
    # fact + a into the first join, the site's capacity + b into the second
    assert span.attrs["join_rows"] == (N + len(frames["a"])) + (cap + 64)
    assert first.meta["join_rows"] == (N + len(frames["a"])) + (N + 64)


def test_an_unselective_site_costs_no_compile_and_compacts_nothing(
        chain, tpu_strategy):
    ctx, frames = chain
    span, kept = _dispatched(ctx, frames, "every_a")
    assert kept == N
    # the program that counted is the program that stays
    program, = _programs()
    assert [tag for _, _, tag in program.meta["agg_sites"]] == ["cmpj0"]
    assert span.attrs["compact_sites"] == 0
    assert span.attrs["compact_cap"] == 0
    assert span.attrs["join_rows"] == (N + 4096) + (N + 64)


def _learned(sites, counts, caps=None):
    """What ``_check_flags`` asks the next round to be built with, or None
    where the program stays: ``sites`` are (tag, input rows, cap)."""
    from types import SimpleNamespace
    entry = SimpleNamespace(
        caps=dict(caps or {}),
        meta={"agg_sites": [(n, tag.startswith("agg"), tag)
                            for tag, n, _ in sites],
              "ngroup_caps": [cap for _, _, cap in sites]})
    try:
        _check_flags(entry, np.array([0, 0] + list(counts)))
    except _NeedsRecompile as again:
        return again.caps
    return None


M = 1 << 20


@pytest.mark.parametrize("case,sites,counts,want", [
    # Q5's chain in its counting round: every site sized at once, the first
    # join's output (unselective) switched off by a cap of half its rows
    ("a chain learns in one round",
     [("cmp0", 3 * M // 2, M // 2), ("cmpj0", M // 2, M // 2),
      ("cmpj1", 6 * M, 6 * M), ("cmpj2", 6 * M, 6 * M)],
     [227_000, 227_000, 910_000, 36_000],
     {"cmp0": M // 2, "cmpj0": M // 2, "cmpj1": 2 * M, "cmpj2": M // 8}),
    # a site below overflowed and dropped rows: the counts above it are too
    # low, so the live site keeps its cap and the counting one goes on counting
    ("past an overflow nothing shrinks",
     [("cmp0", 3 * M // 2, M // 2), ("cmpj0", 3 * M // 2, 3 * M // 2),
      ("cmp1", 6 * M, 2 * M)],
     [700_000, 90_000, 20_000],
     {"cmp0": M, "cmp1": 2 * M}),
    # an aggregate's overflow comes last in trace order and spoils nothing
    ("a group cap's round tightens the sites below it",
     [("cmp0", 2 * M, M // 2), ("agg0", M // 2, 512)],
     [115_000, 37_000],
     {"cmp0": M // 4, "agg0": 65536}),
    ("slack under 8x alone is no reason to compile",
     [("cmp0", 2 * M, M // 2), ("cmpj0", M // 2, M // 2)],
     [115_000, 115_000], None),
    ("a slack of 8x is", [("cmp0", 6 * M, 2 * M)], [30_000],
     {"cmp0": 65536}),
])
def test_what_a_round_learns(case, sites, counts, want):
    assert _learned(sites, counts) == want
