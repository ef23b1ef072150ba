"""A GROUP BY on one key column that never decreases in load order takes its
groups from the runs of that column (``ops/groupby.py``: ``key_runs``,
``run_aggregate``; ``aggregates.run_aggregate``): no hash table, no scatter
over the rows.  It is taken on an ingest statistic's word
(``statistics.grouped_by_runs``; the hint ``runs`` among a request's
capacities) and the program checks that word: a refuted hint recompiles on
the hashed path, is learned as cleared, and never answers.  The kernel is
held against ``_group_hashed_codes`` + ``segment_aggregate`` on the same
columns, the tracer against pandas."""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from chipbench.data import tpch_gen
from dask_sql_tpu import Context
from dask_sql_tpu.ops import groupby as G
from dask_sql_tpu.ops.hashing import _group_hashed_codes
from dask_sql_tpu.physical import caps, compiled as cm, programs
from dask_sql_tpu.runtime import statistics as st, telemetry as tel
from dask_sql_tpu.sql.parser import parse_sql
from dask_sql_tpu.table import Column
from dask_sql_tpu.types import BIGINT, BOOLEAN, DOUBLE, INTEGER, decimal

MONEY = decimal(12, 2)


# --- the kernel against the hashed path ------------------------------------

def _keys(layout):
    """(key column, capacity) of a layout; every one in non-decreasing
    order, n a power of two in none."""
    rng = np.random.default_rng(len(layout))
    if layout == "every_key_once":
        lens = np.ones(1237, dtype=np.int64)
    elif layout == "one_run_of_all_rows":
        lens = np.array([1501])
    elif layout == "runs_of_1_to_7":
        lens = rng.integers(1, 8, 700)
    elif layout == "a_run_longer_than_4096":
        lens = np.r_[rng.integers(1, 8, 150), 4099 + 518, rng.integers(1, 8, 150)]
    elif layout == "a_cap_of_exactly_the_runs":
        lens = rng.integers(1, 5, 512)
    else:
        raise AssertionError(layout)
    values = np.cumsum(rng.integers(1, 1000, len(lens))) - 2 ** 40
    k = np.repeat(values, lens).astype(np.int64)
    # the tracer's: a power of two over the groups, the rows at the most
    return k, min(1 << (len(lens) - 1).bit_length(), len(k))


LAYOUTS = ["every_key_once", "one_run_of_all_rows", "runs_of_1_to_7",
           "a_run_longer_than_4096", "a_cap_of_exactly_the_runs"]


def _column(kind, n, rng):
    """An argument column of ``n`` rows (None: COUNT(*))."""
    if kind is None:
        return None
    nulls = rng.random(n) < 0.2
    if kind.startswith("int"):
        data, stype = rng.integers(-10 ** 12, 10 ** 12, n), BIGINT
    elif kind.startswith("small_int"):
        data, stype = rng.integers(-99, 100, n).astype(np.int32), INTEGER
    elif kind.startswith("decimal"):
        data, stype = np.round(rng.random(n) * 1e5 - 3e4, 2), MONEY
    elif kind.startswith("double"):
        data, stype = rng.standard_normal(n) * 1e3, DOUBLE
    elif kind.startswith("bool"):
        data, stype = rng.random(n) < 0.5, BOOLEAN
    elif kind.startswith("string"):
        words = np.array(["pear", "apple", "fig", "quince", "lime", "date",
                          "plum"], dtype=object)
        col = Column._encode_strings(words[rng.integers(0, 7, n)],
                                     ~nulls if kind.endswith("nulls") else None)
        # a dictionary in no sorted order: MIN / MAX go by rank, not code
        order = np.array([3, 0, 6, 1, 5, 2, 4])[:len(col.dictionary)]
        inverse = np.argsort(order)
        return Column(jnp.asarray(inverse[np.asarray(col.data)].astype(
            np.int32)), col.stype, col.mask, col.dictionary[order])
    else:
        raise AssertionError(kind)
    mask = jnp.asarray(~nulls) if kind.endswith("nulls") else None
    return Column(jnp.asarray(data), stype, mask)


#: name -> (op, argument column, out type, FILTER?, DISTINCT?)
AGGREGATES = {
    "count_star": ("COUNT", None, BIGINT, False, False),
    "count_star_filtered": ("COUNT", None, BIGINT, True, False),
    "count_column_with_nulls": ("COUNT", "double_nulls", BIGINT, False, False),
    "count_distinct": ("COUNT", "small_int_nulls", BIGINT, False, True),
    "sum_int": ("SUM", "int", BIGINT, False, False),
    "sum_int_with_nulls_filtered": ("SUM", "int_nulls", BIGINT, True, False),
    "sum0_int_with_nulls": ("$SUM0", "int_nulls", BIGINT, False, False),
    "sum_distinct_small_int": ("SUM", "small_int", BIGINT, False, True),
    "sum_decimal": ("SUM", "decimal", MONEY, False, False),
    "sum_decimal_with_nulls_filtered": ("SUM", "decimal_nulls", MONEY, True,
                                        False),
    "avg_decimal_with_nulls": ("AVG", "decimal_nulls", DOUBLE, False, False),
    "sum_double": ("SUM", "double", DOUBLE, False, False),
    "sum_double_with_nulls_filtered": ("SUM", "double_nulls", DOUBLE, True,
                                       False),
    "sum0_double_with_nulls": ("$SUM0", "double_nulls", DOUBLE, False, False),
    "avg_double_with_nulls": ("AVG", "double_nulls", DOUBLE, False, False),
    "avg_int_filtered": ("AVG", "int", DOUBLE, True, False),
    "min_double_with_nulls": ("MIN", "double_nulls", DOUBLE, False, False),
    "max_double_filtered": ("MAX", "double", DOUBLE, True, False),
    "min_int_with_nulls_filtered": ("MIN", "int_nulls", BIGINT, True, False),
    "max_small_int": ("MAX", "small_int", INTEGER, False, False),
    "min_bool_with_nulls": ("MIN", "bool_nulls", BOOLEAN, False, False),
    "max_bool": ("MAX", "bool", BOOLEAN, False, False),
    "min_string_with_nulls": ("MIN", "string_nulls", None, False, False),
    "max_string_filtered": ("MAX", "string", None, True, False),
}


def _first_occurrences(k, col):
    """A DISTINCT aggregate's keep mask as the tracer hands it over: the
    first row of each (key, value), NULL a value of its own."""
    values = np.where(np.asarray(col.valid_mask()), np.asarray(col.data), -999)
    frame = pd.DataFrame({"k": k, "v": values})
    return jnp.asarray(~frame.duplicated().to_numpy())


def _both_ways(layout, name):
    """(num_groups, hashed column, run column), cut to the groups."""
    op, kind, out_type, filtered, distinct = AGGREGATES[name]
    k, cap = _keys(layout)
    n = len(k)
    rng = np.random.default_rng(n + len(name))
    col = _column(kind, n, rng)
    if out_type is None:
        out_type = col.stype
    fmask = jnp.asarray(rng.random(n) < 0.6) if filtered else None
    if distinct:
        keep = _first_occurrences(k, col)
        fmask = keep if fmask is None else (fmask & keep)
    key = Column(jnp.asarray(k), BIGINT)

    @jax.jit
    def both(key_data):
        kc = Column(key_data, BIGINT)
        codes, first, ng, coll = _group_hashed_codes([kc], None, cap)
        hashed = G.segment_aggregate(op, col, codes, cap + 1, out_type,
                                     filter_mask=fmask, n_rows=n)
        runs = G.key_runs(key_data, cap)
        ran = G.run_aggregate(op, col, runs, out_type, fmask)
        return ((ng, kc.take(first).data, hashed.data[:cap],
                 None if hashed.mask is None else hashed.mask[:cap]),
                (runs.num_groups, kc.take(jnp.minimum(runs.starts, n - 1)).data,
                 ran.data, ran.mask, runs.ok))

    (ng, hkeys, hdata, hmask), (rng_, rkeys, rdata, rmask, ok) = both(key.data)
    assert bool(ok)
    ng = int(ng)
    assert int(rng_) == ng == len(np.unique(k))
    # the groups come out in the hashed path's order: first occurrence
    np.testing.assert_array_equal(np.asarray(rkeys)[:ng], np.asarray(hkeys)[:ng])
    assert (hmask is None) == (rmask is None)
    valid = np.ones(ng, bool)
    if hmask is not None:
        np.testing.assert_array_equal(np.asarray(rmask)[:ng],
                                      np.asarray(hmask)[:ng])
        valid = np.asarray(hmask)[:ng]
        # slots past the count are no groups: NULL wherever NULL exists
        assert not np.asarray(rmask)[ng:].any()
    assert rdata.dtype == hdata.dtype
    return np.asarray(hdata)[:ng][valid], np.asarray(rdata)[:ng][valid], col


@pytest.mark.parametrize("name", sorted(AGGREGATES))
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_run_kernel_answers_as_the_hashed_path(layout, name):
    hashed, ran, col = _both_ways(layout, name)
    assert len(hashed) > 0
    op, kind = AGGREGATES[name][:2]
    floating = kind is not None and kind.startswith("double") \
        and op in ("SUM", "$SUM0", "AVG")
    if floating:
        # another order of the same additions: one run's rounding
        np.testing.assert_allclose(ran, hashed, rtol=1e-14, atol=1e-11)
    else:
        # counts, integer and decimal sums, AVG of an exact sum, MIN, MAX:
        # the hashed path's bits
        np.testing.assert_array_equal(ran, hashed)


def test_a_floating_sum_is_formed_inside_its_run():
    """A column whose total is 1e9 times a group's: a difference of a
    column-wide running sum would leave eps x total in every group (1e-7
    relative here); summed inside its run a group is never worse than the
    hashed path's sequential sum, against ``math.fsum``."""
    rng = np.random.default_rng(5)
    lens = rng.integers(1, 8, 2000)
    k = np.repeat(np.arange(len(lens), dtype=np.int64) * 3, lens)
    n = len(k)
    v = rng.random(n) + 0.5
    huge = rng.integers(0, len(lens), 40)          # 40 groups carry the total
    v[np.isin(k, huge * 3)] *= 1e9 * 4 / 40 * len(lens) / 40
    cap = 2048
    col = Column(jnp.asarray(v), DOUBLE)

    @jax.jit
    def both(key_data):
        codes, _, ng, _ = _group_hashed_codes([Column(key_data, BIGINT)],
                                              None, cap)
        runs = G.key_runs(key_data, cap)
        return (ng, G.segment_aggregate("SUM", col, codes, cap + 1,
                                        DOUBLE).data[:cap],
                G.run_aggregate("SUM", col, runs, DOUBLE).data)

    ng, hashed, ran = both(jnp.asarray(k))
    ng = int(ng)
    bounds = np.r_[0, np.cumsum(lens)]
    exact = np.array([math.fsum(v[a:b]) for a, b in zip(bounds, bounds[1:])])
    assert exact.sum() > 1e8 * np.median(exact)
    gap_ran = np.abs(np.asarray(ran)[:ng] - exact) / exact
    gap_hashed = np.abs(np.asarray(hashed)[:ng] - exact) / exact
    assert gap_ran.max() <= 1e-15 * 4
    assert gap_ran.max() <= max(gap_hashed.max(), 2.3e-16)


def test_more_runs_than_the_capacity_are_counted_not_saturated():
    """An overflow reports the runs it counted, whatever the capacity (the
    ladder then lands in the count's class at once), and the check of the
    order does not depend on it."""
    k, _ = _keys("runs_of_1_to_7")
    runs = jax.jit(lambda x: G.key_runs(x, 64))(jnp.asarray(k))
    assert int(runs.num_groups) == 700 and bool(runs.ok)
    assert np.asarray(runs.starts).tolist() == np.flatnonzero(
        np.r_[True, k[1:] != k[:-1]])[:64].tolist()


def test_a_column_out_of_order_fails_the_programs_check():
    k, cap = _keys("runs_of_1_to_7")
    k[300], k[301] = k[-1], k[0]
    assert not bool(jax.jit(lambda x: G.key_runs(x, cap).ok)(jnp.asarray(k)))


def test_no_rows_are_no_groups():
    col = Column(jnp.zeros(0, jnp.float64), DOUBLE)
    runs = G.key_runs(jnp.zeros(0, jnp.int64), 0)
    assert int(runs.num_groups) == 0 and bool(runs.ok)
    for op in ("SUM", "MIN", "COUNT"):
        assert len(G.run_aggregate(op, col, runs, DOUBLE)) == 0


# --- through Context.sql, under the TPU strategy ---------------------------

@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    """The hint is an ingest statistic at work (conftest pins them off for
    every suite not named for them); every case starts unlearned, on the
    TPU's formulations, and every arrival waits for its program."""
    monkeypatch.setenv("DSQL_ADAPTIVE", "1")
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    monkeypatch.setenv("DSQL_TIERED", "0")
    monkeypatch.delenv("DSQL_CAPS_FILE", raising=False)
    programs._cache.clear()
    caps._learned_caps.clear()


@pytest.fixture(scope="module")
def frames():
    return tpch_gen.generate(0.01, 45)


def _context(tables):
    ctx = Context()
    for name, frame in tables.items():
        ctx.create_table(name, frame)
    return ctx


Q18 = importlib.import_module("chipbench.shapes.q18")


def _plan(ctx, text):
    return ctx._get_plan(parse_sql(text)[0].query, text)


def _counter(name):
    return tel.REGISTRY.snapshot()["counters"].get(name, 0)


def _spans(ctx, name):
    return [s for s in ctx.last_report.root.walk() if s.name == name]


def _last_program():
    return [e for e in programs._cache.values()
            if e is not programs._UNSUPPORTED][-1]


def _sites(entry):
    return {tag: cap for (_, _, tag), cap in zip(entry.meta["agg_sites"],
                                                 entry.meta["ngroup_caps"])}


def _q18(ctx, frames, expect_runs):
    """Three Q18s, each as pandas; the dispatch of every arrival after the
    one that compiled says how the inner GROUP BY ran."""
    ran = _counter("groupby_run_aggregates")
    for i in (Q18.FIRST, Q18.FIRST + 3, Q18.FIRST + 11):
        params = Q18.params_at(i)
        got = ctx.sql(Q18.SQL.format(**params), return_futures=False)
        want = Q18.reference(frames, **params)
        assert ctx.last_report.tier == "compiled"
        assert len(got) == len(want) > 0
        for column in want.columns:
            if want[column].dtype.kind == "f":
                np.testing.assert_allclose(got[column].to_numpy(dtype=float),
                                           want[column].to_numpy(),
                                           rtol=1e-12)
            else:
                assert got[column].astype(str).tolist() \
                    == want[column].astype(str).tolist()
        for span in _spans(ctx, "dispatch"):
            assert span.attrs["run_groupbys"] == expect_runs
    assert _spans(ctx, "dispatch")
    assert _counter("groupby_run_aggregates") - ran == 3 * expect_runs


def test_q18s_inner_group_by_takes_its_groups_from_the_runs(frames):
    ctx = _context(frames)
    hints = _counter("recompiles_hint")
    _q18(ctx, frames, expect_runs=1)
    entry = _last_program()
    orders = frames["lineitem"]["l_orderkey"].nunique()
    # the capacity is still the counted runs' class, the word rides in the key
    assert orders <= _sites(entry)["agg0"] < 2 * orders
    assert dict(entry.caps)[st.RUN_GROUPS_TAG] == 1
    assert entry.meta["run_groupbys"] == 1
    assert _counter("recompiles_hint") == hints


def test_the_same_text_over_a_shuffled_lineitem_hashes_and_answers_the_same(
        frames):
    shuffled = dict(frames)
    shuffled["lineitem"] = frames["lineitem"].sample(
        frac=1.0, random_state=3).reset_index(drop=True)
    ctx = _context(shuffled)
    hints = _counter("recompiles_hint")
    _q18(ctx, frames, expect_runs=0)
    entry = _last_program()
    assert st.RUN_GROUPS_TAG not in dict(entry.caps)
    assert entry.meta["run_groupbys"] == 0
    assert _counter("recompiles_hint") == hints


ROLLUP = ("SELECT k, COUNT(*) AS n, SUM(v) AS s, MIN(w) AS lo, "
          "AVG(v) FILTER (WHERE w > {w}) AS a, COUNT(DISTINCT w) AS d "
          "FROM fact GROUP BY k")


def _fact(ordered=True, rows=6000):
    rng = np.random.default_rng(11)
    k = np.repeat(np.arange(rows // 4, dtype=np.int64) * 7 + 3, 4)
    if not ordered:
        k = rng.permutation(k)
    return pd.DataFrame({"k": k, "v": np.round(rng.random(rows) * 100, 3),
                         "w": rng.integers(0, 9, rows)})


def _rollup_reference(fact, w):
    grouped = fact.groupby("k", sort=True)
    return pd.DataFrame({
        "k": grouped["v"].sum().index.to_numpy(),
        "n": grouped.size().to_numpy(),
        "s": grouped["v"].sum().to_numpy(),
        "lo": grouped["w"].min().to_numpy(),
        "a": fact[fact["w"] > w].groupby("k")["v"].mean().reindex(
            grouped["v"].sum().index).to_numpy(),
        "d": grouped["w"].nunique().to_numpy()})


def _assert_rollup(got, fact, w):
    want = _rollup_reference(fact, w)
    got = got.sort_values("k", ignore_index=True)
    assert got["k"].tolist() == want["k"].tolist()
    assert got["n"].tolist() == want["n"].tolist()
    assert got["lo"].tolist() == want["lo"].tolist()
    assert got["d"].tolist() == want["d"].tolist()
    np.testing.assert_allclose(got["s"].to_numpy(), want["s"], rtol=1e-13)
    np.testing.assert_allclose(got["a"].to_numpy(dtype=float), want["a"],
                               rtol=1e-13)


def test_a_refuted_hint_recompiles_once_and_is_learned():
    """Statistics that say "never decreases" of a column that does (a table
    appended to out of order under them): the run's answer is thrown away,
    one ``hint_refuted`` round hashes, and the next request starts there."""
    fact = _fact(ordered=False)
    ctx = Context()
    ctx.create_table("fact", fact)
    stats = ctx.schema["root"].tables["fact"].stats.cols["k"]
    assert stats.runs is None and not stats.increasing
    stats.runs = 1500
    before = (_counter("recompiles_hint"), _counter("recompiles"),
              _counter("groupby_run_aggregates"))
    got = ctx.sql(ROLLUP.format(w=3), return_futures=False)
    _assert_rollup(got, fact, 3)
    causes = [s.attrs["cause"] for s in _spans(ctx, "compile")]
    assert causes == ["first", "hint_refuted"]
    assert _spans(ctx, "compile")[1].attrs["caps"] == "runs:1>0"
    assert (_counter("recompiles_hint"), _counter("recompiles")) \
        == (before[0] + 1, before[1] + 1)
    assert _last_program().meta["run_groupbys"] == 0
    # the cleared hint was learned: the next request compiles nothing and
    # never takes the runs
    got = ctx.sql(ROLLUP.format(w=5), return_futures=False)
    _assert_rollup(got, fact, 5)
    assert not _spans(ctx, "compile")
    span, = _spans(ctx, "dispatch")
    assert span.attrs["run_groupbys"] == 0
    assert dict(_last_program().caps)[st.RUN_GROUPS_TAG] == 0
    assert _counter("recompiles_hint") == before[0] + 1
    assert _counter("groupby_run_aggregates") == before[2]


def test_an_overflow_climbs_to_the_counts_class_and_answers_right(
        monkeypatch):
    """From a capacity under the runs (nothing hinted or counted, the
    default): one ``cap_overflow`` round to the class of the count the run
    reported."""
    monkeypatch.setattr(st, "compiled_cap_hints", lambda plan, context: {})
    monkeypatch.setattr(st, "counted_groups", lambda rel, context: None)
    monkeypatch.setattr(caps, "DEFAULT_GROUP_CAP", 64)
    fact = _fact()
    ctx = Context()
    ctx.create_table("fact", fact)
    got = ctx.sql(ROLLUP.format(w=3), return_futures=False)
    _assert_rollup(got, fact, 3)
    compiles = _spans(ctx, "compile")
    assert [s.attrs["cause"] for s in compiles] == ["first", "cap_overflow"]
    assert compiles[1].attrs["caps"] == "agg0:64>2048"
    entry = _last_program()
    assert _sites(entry)["agg0"] == 2048 and entry.meta["run_groupbys"] == 1


@pytest.mark.parametrize("text,runs", [
    (ROLLUP.format(w=3), 1),
    # an aggregate the run kernel does not cover keeps the node hashed
    ("SELECT k, STDDEV_SAMP(v) AS sd, SUM(v) AS s FROM fact GROUP BY k", 0),
    # under a filter the runs survive and the count does not: hashed
    ("SELECT k, SUM(v) AS s FROM fact WHERE w > 2 GROUP BY k", 0),
    ("SELECT k, w, SUM(v) AS s FROM fact GROUP BY k, w", 0),
])
def test_only_what_the_kernel_covers_takes_the_runs(text, runs):
    fact = _fact()
    ctx = Context()
    ctx.create_table("fact", fact)
    got = ctx.sql(text, return_futures=False)
    entry = _last_program()
    assert entry.meta["run_groupbys"] == runs
    if "STDDEV" in text:
        want = fact.groupby("k")["v"].agg(["std", "sum"])
        got = got.sort_values("k", ignore_index=True)
        np.testing.assert_allclose(got["sd"], want["std"], rtol=1e-9)
        np.testing.assert_allclose(got["s"], want["sum"], rtol=1e-13)
    elif " WHERE w > 2 " in text:
        want = fact[fact["w"] > 2].groupby("k")["v"].sum()
        got = got.sort_values("k", ignore_index=True)
        assert got["k"].tolist() == want.index.tolist()
        np.testing.assert_allclose(got["s"], want.to_numpy(), rtol=1e-13)
    elif runs:
        _assert_rollup(got, fact, 3)
    else:
        assert len(got) == len(fact.groupby(["k", "w"]))


def test_a_table_with_a_row_mask_of_its_own_keeps_the_hashed_path():
    """The tracer's part of the gate: the hint stands, the rows are a
    scan's, but the stream carries a validity mask."""
    from dask_sql_tpu.plan import nodes as N
    fact = _fact()
    ctx = Context()
    ctx.create_table("fact", fact)
    plan = _plan(ctx, "SELECT k, SUM(v) AS s FROM fact GROUP BY k")
    agg = plan
    while not isinstance(agg, N.LogicalAggregate):
        agg = agg.input
    table = ctx.schema["root"].tables["fact"].table
    for valid, load_order, hint, expect in [
            (None, True, 1, 1), (jnp.ones(table.num_rows, bool), True, 1, 0),
            (None, False, 1, 0), (None, True, 0, 0)]:
        tracer = cm._Tracer(ctx, {}, {st.RUN_GROUPS_TAG: hint})
        src = cm._VT(table.limit_to(["k", "v"]), valid, load_order=load_order)
        tracer._ran[id(agg.input)] = src
        out = tracer._LogicalAggregate(agg)
        assert len(tracer.flags.hints) == expect
        assert out.table.num_rows == tracer.flags.site_caps[0]


# --- no scatter of the rows under the inner aggregate ----------------------

def _scatters(jaxpr, rows, found, outer=""):
    """(primitive, scope path) of every scatter of ``jaxpr`` (sub-jaxprs
    too, under their equation's scopes) with ``rows`` updates or indices."""
    for eqn in jaxpr.eqns:
        scope = outer + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name.startswith("scatter") and any(
                v.aval.shape and v.aval.shape[0] == rows
                for v in eqn.invars[1:]):
            found.append((eqn.primitive.name, scope))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scatters(sub, rows, found, scope)
    return found


@pytest.mark.parametrize("ordered", [True, False])
def test_q18s_program_scatters_no_lineitem_row_under_the_inner_aggregate(
        frames, monkeypatch, ordered):
    """The property the gain rests on: with the runs, no scatter under the
    inner aggregate's ``dsql.groupby_sorted`` has as many updates as
    lineitem has rows; hashed, the insert, the first rows and a segment sum
    an aggregate do."""
    tables = dict(frames)
    if not ordered:
        tables["lineitem"] = frames["lineitem"].sample(
            frac=1.0, random_state=3).reset_index(drop=True)
    ctx = _context(tables)
    rows = len(frames["lineitem"])
    built = []
    real = cm._build
    monkeypatch.setattr(cm, "_build", lambda *a, **k: built.append(
        real(*a, **k)) or built[-1])
    ctx.sql(Q18.SQL.format(**Q18.params_at(Q18.FIRST)), return_futures=False)
    entry = built[-1]
    assert entry.meta["run_groupbys"] == int(ordered)
    pk = cm.program_key(cm._maybe_parameterize(
        _plan(ctx, Q18.SQL.format(**Q18.params_at(Q18.FIRST))), count=False),
        ctx)
    flat = cm._flatten_tables(pk.scans) + cm._param_args(pk.params)
    found = _scatters(jax.make_jaxpr(entry.fn)(*flat).jaxpr, rows, [])
    # the inner aggregate stands below the SEMI join; the outer one, above
    # the joins, sees as many rows at this scale and is not the claim
    inner = [name for name, scope in found
             if "groupby_sorted" in scope and "LogicalJoin" in scope]
    assert (inner == []) if ordered else (len(inner) >= 3)
    assert [name for name, scope in found if "groupby_sorted" in scope
            and "LogicalJoin" not in scope]
