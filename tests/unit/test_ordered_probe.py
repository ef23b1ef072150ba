"""A hash-table join whose build side is a base table's rows in load order,
on a key column that strictly increases, builds nothing and probes the
column itself (``joins.ordered``; kernels in ``ops/hashing.py``).
It takes the path on an ingest statistic's word (``ColumnStats.increasing``,
riding as the hint ``ord<j>l`` / ``ord<j>r`` among a request's capacities)
and the program checks that word: a refuted hint recompiles with the table
and is learned as cleared, and never answers.  Every case against pandas."""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu.ops import pallas_kernels
from dask_sql_tpu.physical import caps, compiled as cm, programs
from dask_sql_tpu.runtime import statistics as stats, telemetry as tel

I64 = np.iinfo(np.int64)
N_PROBE = 4000

SQL = {
    "INNER": "SELECT p.k, p.w, b.v FROM p JOIN {b} ON p.k = b.k",
    "LEFT": "SELECT p.k, p.w, b.v FROM p LEFT JOIN {b} ON p.k = b.k",
    "SEMI": ("SELECT p.k, p.w FROM p WHERE EXISTS "
             "(SELECT 1 FROM {b} WHERE b.k = p.k)"),
    "ANTI": ("SELECT p.k, p.w FROM p WHERE NOT EXISTS "
             "(SELECT 1 FROM {b} WHERE b.k = p.k)"),
}
JOIN_TYPES = ["INNER", "LEFT", "SEMI", "ANTI"]


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    """The hints are ingest statistics at work (conftest pins them off for
    every suite not named for them), and every case starts unlearned."""
    monkeypatch.setenv("DSQL_ADAPTIVE", "1")
    programs._cache.clear()
    caps._learned_caps.clear()


@pytest.fixture
def any_rows(monkeypatch):
    """The search is taken whatever the static row counts say (the rule of
    rows has a test of its own)."""
    monkeypatch.setattr(cm, "ORDERED_GATHERS_A_BUILD_ROW", 1 << 30)


def _probe_keys(rng, inside, outside):
    """Half the probe rows draw from the build side's keys, the rest from
    ``outside``; every one of ``outside`` is there at least once."""
    keys = np.concatenate([
        rng.choice(np.asarray(inside, dtype=np.int64), N_PROBE // 2),
        rng.choice(np.asarray(outside, dtype=np.int64),
                   N_PROBE // 2 - len(outside)),
        np.asarray(outside, dtype=np.int64)])
    return rng.permutation(keys)


def _case(name):
    """(build keys, probe keys beside them, build relation, hint level)."""
    b_rel = "b"
    if name in ("dense", "masked_by_a_filter", "empty_by_a_filter",
                "null_probe_keys"):
        bk = np.arange(100, 1100)
        outside = [I64.min, -1, 0, 99, 1100, I64.max]
        level = stats.ORDERED_DENSE
        if name == "masked_by_a_filter":
            b_rel = "(SELECT k, v FROM b WHERE v > 0.5) AS b"
        elif name == "empty_by_a_filter":
            b_rel = "(SELECT k, v FROM b WHERE v > 100.0) AS b"
    elif name == "dense_negative":
        bk = np.arange(-1500, -500)
        outside = [I64.min, -1501, -500, 0, I64.max]
        level = stats.ORDERED_DENSE
    elif name == "one_row":
        bk = np.array([42])
        outside = [41, 43, I64.min, I64.max]
        level = stats.ORDERED_DENSE
    elif name in ("sparse", "sparse_masked_by_a_filter"):
        # below lo, above hi, between two keys
        bk = np.arange(1000) * 4 + 7
        outside = [I64.min, 6, 8, 9, 10, 4002, 4004, I64.max]
        level = stats.ORDERED_NARROW
        if name == "sparse_masked_by_a_filter":
            b_rel = "(SELECT k, v FROM b WHERE v > 0.5) AS b"
    elif name == "sparse_odd_rows":
        # 1237 rows: the search's last steps run past the end
        bk = np.cumsum(np.arange(1, 1238) % 7 + 1)
        outside = [0, bk[-1] + 1, bk[5] + 1, I64.max]
        level = stats.ORDERED_NARROW
    elif name == "near_int64_max":
        bk = I64.max - 3000 + np.arange(1000) * 3
        outside = [I64.min, -1, 0, bk[0] - 1, bk[3] + 1, bk[-1] + 1, I64.max]
        level = stats.ORDERED_NARROW
    elif name == "near_int64_min":
        bk = I64.min + np.arange(1000) * 3
        outside = [I64.min + 1, bk[-1] + 1, 0, I64.max]
        level = stats.ORDERED_NARROW
    elif name == "span_of_2_to_the_31":
        # the widest a 32-bit search holds is a span of 2^31 - 1
        bk = np.concatenate([np.arange(999) * 5, [2 ** 31]])
        outside = [-1, 1, 2 ** 31 - 1, 2 ** 31 + 1, 2 ** 32, I64.max]
        level = stats.ORDERED_WIDE
    elif name == "span_under_2_to_the_31":
        bk = np.concatenate([np.arange(999) * 5, [2 ** 31 - 1]])
        outside = [-1, 1, 2 ** 31 - 2, 2 ** 31, 2 ** 32, I64.max]
        level = stats.ORDERED_NARROW
    elif name == "wide":
        bk = np.arange(1000) * 5_000_000_000 - 2 ** 40
        outside = [I64.min, bk[0] - 1, bk[7] + 1, bk[-1] + 1, I64.max]
        level = stats.ORDERED_WIDE
    elif name == "all_of_int64":
        # hi - lo wraps: the span is 2^64 - 1
        bk = np.array([I64.min, -5, 0, 7, I64.max])
        outside = [I64.min + 1, -6, -1, 1, 8, I64.max - 1]
        level = stats.ORDERED_WIDE
    else:
        raise AssertionError(name)
    rng = np.random.default_rng(len(name))
    bk = np.asarray(bk, dtype=np.int64)
    return bk, _probe_keys(rng, bk, outside), b_rel, level


def _frames(name):
    bk, pk, b_rel, level = _case(name)
    rng = np.random.default_rng(7)
    p = pd.DataFrame({"k": pk, "w": np.round(rng.random(len(pk)), 6)})
    b = pd.DataFrame({"k": bk, "v": np.round(rng.random(len(bk)), 6)})
    if name == "null_probe_keys":
        p["k"] = p["k"].astype("Int64").mask(rng.random(len(p)) < 0.1)
    return p, b, b_rel, level


def _reference(jt, p, b):
    """pandas, with SQL's NULL keys: they match nothing."""
    if jt == "INNER":
        return p[p["k"].notna()].merge(b, on="k")
    if jt == "LEFT":
        return p.merge(b, on="k", how="left")
    matched = p["k"].isin(b["k"]) & p["k"].notna()
    return p[matched if jt == "SEMI" else ~matched]


def _plain(frame):
    """int64 where a column is whole (float64 would round the keys near
    int64's limits), float64 where it holds NULLs or fractions."""
    out = {}
    for name in frame.columns:
        s = frame[name]
        if s.dtype == object and s.dropna().map(np.isreal).all():
            s = s.astype("Float64")
        whole = pd.api.types.is_integer_dtype(s.dtype) and not s.isna().any()
        out[name] = s.astype("int64") if whole else s.astype("float64")
    return pd.DataFrame(out).sort_values(list(frame.columns),
                                         ignore_index=True)


def _assert_same_rows(got, want):
    assert list(got.columns) == list(want.columns)
    pd.testing.assert_frame_equal(_plain(got), _plain(want))


def _attrs(ctx):
    """Of the round that answered."""
    return [s for s in ctx.last_report.root.walk()
            if s.name == "materialize"][-1].attrs


COUNTERS = ("join_probes_ordered", "join_probes_direct",
            "join_probes_looped", "fallbacks", "recompiles")


def _counters():
    c = tel.REGISTRY.snapshot()["counters"]
    return tuple(c.get(name, 0) for name in COUNTERS)


def _context(p, b):
    ctx = Context()
    ctx.create_table("p", p)
    ctx.create_table("b", b)
    return ctx


def _run(ctx, query):
    """The answer, what the counters moved by, and the (program, flags) of
    every round that reached ``_check_ordered``."""
    seen = []
    real = caps._check_ordered

    def spy(entry, flags):
        seen.append((entry, np.array(flags)))
        return real(entry, flags)

    before = _counters()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(caps, "_check_ordered", spy)
        got = ctx.sql(query, return_futures=False)
    moved = dict(zip(COUNTERS, (x - y for x, y in zip(_counters(), before))))
    return got, moved, seen


CASES = ["dense", "dense_negative", "one_row", "masked_by_a_filter",
         "empty_by_a_filter", "null_probe_keys", "sparse",
         "sparse_masked_by_a_filter", "sparse_odd_rows", "near_int64_max",
         "near_int64_min", "span_under_2_to_the_31", "span_of_2_to_the_31",
         "wide", "all_of_int64"]


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("jt", JOIN_TYPES)
def test_a_build_side_in_key_order_is_probed_and_answers_as_pandas(
        any_rows, jt, name):
    p, b, b_rel, level = _frames(name)
    ctx = _context(p, b)
    got, moved, seen = _run(ctx, SQL[jt].format(b=b_rel))
    kept = b
    if "WHERE" in b_rel:
        kept = b[b["v"] > (100.0 if name.startswith("empty") else 0.5)]
    want = _reference(jt, p, kept)
    assert len(want) > 0 or (name, jt) in {("empty_by_a_filter", "INNER"),
                                           ("empty_by_a_filter", "SEMI")}
    _assert_same_rows(got, want)
    # one round, on the statistic's word, and no table
    (entry, flags), = seen
    assert dict(entry.caps)["ord0r"] == level
    assert entry.meta["ordered"] == ["ord0r"]
    assert entry.meta["hash_table_joins"] == 0
    # of the hash-table formulation, without a table; a dense column's
    # probe is direct, a searched one's neither direct nor looped
    dense = int(level == stats.ORDERED_DENSE)
    attrs = _attrs(ctx)
    assert (attrs["hash_table_joins"], attrs["direct_probes"],
            attrs["ordered_probes"]) == (1, dense, 1)
    assert moved == {"join_probes_ordered": 1, "join_probes_direct": dense,
                     "join_probes_looped": 0, "fallbacks": 0,
                     "recompiles": 0}


@pytest.mark.parametrize("nb,ordered", [
    # at the worst 12 + 3 gathers a probe row: 400 x 15 against 8 x 4000
    # build rows are under 8 gathers a build row, a search
    (4000, 1),
    # 400 x 13 against 8 x 600: the table's inserts may be the cheaper
    (600, 0)])
@pytest.mark.parametrize("jt", ["LEFT", "SEMI", "ANTI"])
def test_a_search_is_taken_where_the_row_counts_say_it_pays(jt, nb, ordered):
    """(An INNER join probes with its larger side, so its search pays only
    below a compaction: ``test_a_compacted_build_side_keeps_the_table``'s
    second half.)"""
    rng = np.random.default_rng(nb)
    bk = np.arange(nb, dtype=np.int64) * 3
    pk = np.r_[rng.choice(bk, 200), rng.integers(-5, 3 * nb + 5, 200)]
    p = pd.DataFrame({"k": pk, "w": np.round(rng.random(400), 6)})
    b = pd.DataFrame({"k": bk, "v": np.round(rng.random(nb), 6)})
    ctx = _context(p, b)
    got, moved, seen = _run(ctx, SQL[jt].format(b="b"))
    _assert_same_rows(got, _reference(jt, p, b))
    (entry, _), = seen
    # the hint is the statistic's either way; what it is worth is the rows'
    assert dict(entry.caps)["ord0r"] == stats.ORDERED_NARROW
    assert len(entry.meta["ordered"]) == ordered
    assert entry.meta["hash_table_joins"] == 1 - ordered
    assert _attrs(ctx).get("ordered_probes", 0) == ordered
    assert moved["join_probes_ordered"] == ordered
    assert moved["join_probes_direct"] == 1 - ordered    # a span of 3 nb


def _keeps_the_table(ctx, query, want, joins=1):
    got, moved, seen = _run(ctx, query)
    _assert_same_rows(got, want)
    entry, _ = seen[-1]
    assert entry.meta["ordered"] == []
    assert entry.meta["hash_table_joins"] == joins
    assert "ordered_probes" not in _attrs(ctx)
    assert moved["join_probes_ordered"] == 0 and moved["fallbacks"] == 0
    return entry


@pytest.mark.parametrize("jt", ["INNER", "SEMI"])
def test_a_two_part_key_keeps_the_table(any_rows, jt):
    rng = np.random.default_rng(3)
    b = pd.DataFrame({"a": np.arange(1000), "c": np.arange(1000) % 25,
                      "v": np.round(rng.random(1000), 6)})
    p = pd.DataFrame({"a": rng.integers(-2, 1002, N_PROBE),
                      "c": rng.integers(-2, 27, N_PROBE),
                      "w": np.round(rng.random(N_PROBE), 6)})
    if jt == "INNER":
        query = ("SELECT p.a, p.w, b.v FROM p JOIN b "
                 "ON p.a = b.a AND p.c = b.c")
        want = p.merge(b, on=["a", "c"])[["a", "w", "v"]]
    else:
        query = ("SELECT p.a, p.w FROM p WHERE EXISTS (SELECT 1 FROM b "
                 "WHERE b.a = p.a AND b.c = p.c)")
        want = p.merge(b, on=["a", "c"])[["a", "w"]]
    assert len(want) > 20
    entry = _keeps_the_table(_context(p, b), query, want)
    assert not any(tag.startswith("ord") for tag in dict(entry.caps))


@pytest.mark.parametrize("reason", [
    None, "exist_test", "moved_rows", "masked_key", "string_key",
    "float_key", "two_parts", "a_subquery_s_join", "no_hint",
    "a_refuted_hint"])
def test_the_tracer_takes_the_probe_only_where_all_of_it_holds(reason):
    """``_ordered_hint`` alone, on a dense hint: every condition in turn.
    (A SEMI / ANTI residual ``b.x <> p.y`` never reaches the tracer through
    SQL: the optimizer rewrites it to an aggregate below a plain join.)"""
    import jax.numpy as jnp

    from dask_sql_tpu.table import Column, Table
    from dask_sql_tpu.types import BIGINT, DOUBLE, VARCHAR

    def col(data, stype=BIGINT, mask=None, dictionary=None):
        return Column(jnp.asarray(data), stype, mask, dictionary)

    key = col(np.arange(100))
    if reason == "masked_key":
        key = col(np.arange(100), mask=jnp.ones(100, bool))
    elif reason == "string_key":
        key = col(np.arange(100, dtype=np.int32), VARCHAR,
                  dictionary=np.array([f"s{i:03d}" for i in range(100)],
                                      dtype=object))
    elif reason == "float_key":
        key = col(np.arange(100) * 1.0, DOUBLE)
    build = cm._VT(Table(["k"], [key]), None,
                   load_order=reason != "moved_rows")
    # 70 probe rows x (7 + 3) gathers are under 8 x 100 build rows
    probe = cm._VT(Table(["k"], [col(np.arange(70) % 120)]), None)
    bparts = [(None, key.data)] * (2 if reason == "two_parts" else 1)
    rel = object()
    tracer = cm._Tracer(None, {}, {
        "no_hint": {}, "a_refuted_hint": {"ord3r": 0}}.get(
            reason, {"ord3r": stats.ORDERED_DENSE}))
    if reason != "a_subquery_s_join":
        tracer.join_tags = {id(rel): "ord3"}
    exist_test = ("<>", key, key) if reason == "exist_test" else None
    got = tracer._ordered_hint(rel, True, probe, build, [key], bparts,
                               exist_test)
    assert got == (("ord3r", stats.ORDERED_DENSE) if reason is None
                   else None)
    # the side named is the one that builds
    if reason is None:
        tracer.caps = {"ord3l": stats.ORDERED_NARROW}
        assert tracer._ordered_hint(rel, False, probe, build, [key], bparts,
                                    None) == ("ord3l", stats.ORDERED_NARROW)


def test_a_build_side_that_is_a_join_s_output_keeps_the_table(any_rows):
    """``c`` is in key order, but what the second join builds from is the
    first join's output: no scan's rows in load order."""
    rng = np.random.default_rng(8)
    p = pd.DataFrame({"k": rng.integers(0, 300, N_PROBE),
                      "w": np.round(rng.random(N_PROBE), 6)})
    b = pd.DataFrame({"k": rng.permutation(300), "j": rng.permutation(300)})
    c = pd.DataFrame({"j": np.arange(300),
                      "v": np.round(rng.random(300), 6)})
    ctx = _context(p, b)
    ctx.create_table("c", c)
    query = ("SELECT p.k, p.w, bc.v FROM p JOIN (SELECT b.k, c.v FROM b "
             "JOIN c ON b.j = c.j) AS bc ON p.k = bc.k")
    got, moved, seen = _run(ctx, query)
    _assert_same_rows(got, p.merge(b.merge(c, on="j")[["k", "v"]], on="k"))
    entry, _ = seen[-1]
    # b joins c on c's increasing key; p joins what comes of it by a table
    assert len(entry.meta["ordered"]) == 1
    assert entry.meta["hash_table_joins"] == 1
    assert moved["join_probes_ordered"] == 1 and moved["fallbacks"] == 0


def test_a_compacted_build_side_keeps_the_table(any_rows, monkeypatch):
    """Under the TPU's strategy a selective filter below a join compacts
    its rows to a learned capacity, and a compacted side's rows are no
    scan's rows; the same side unfiltered is probed in place."""
    monkeypatch.delenv("DSQL_STRATEGY", raising=False)
    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)
    monkeypatch.setattr(cm, "SORT_ROWS_MAX", 256)
    rng = np.random.default_rng(9)
    nb = 1 << 17
    b = pd.DataFrame({"k": np.arange(nb) * 2,
                      "v": np.round(rng.random(nb), 6)})
    p = pd.DataFrame({"k": rng.integers(0, 2 * nb, 3 * nb // 2),
                      "w": np.round(rng.random(3 * nb // 2), 6)})
    ctx = _context(p, b)
    query = "SELECT p.k, p.w, b.v FROM p JOIN {b} ON p.k = b.k"
    entry = _keeps_the_table(
        ctx, query.format(b="(SELECT k, v FROM b WHERE v < 0.01) AS b"),
        p.merge(b[b["v"] < 0.01], on="k"))
    sites = [tag for _, _, tag in entry.meta["agg_sites"]]
    assert sites == ["cmp0"] and entry.meta["ngroup_caps"][0] < nb
    got, moved, seen = _run(ctx, query.format(b="b"))
    _assert_same_rows(got, p.merge(b, on="k"))
    assert seen[-1][0].meta["ordered"] == ["ord0r"]
    assert moved["join_probes_ordered"] == 1


def test_no_hint_rides_where_the_statistics_are_off(monkeypatch):
    monkeypatch.setenv("DSQL_ADAPTIVE", "0")
    p, b, _, _ = _frames("dense")
    ctx = _context(p, b)
    entry = _keeps_the_table(ctx, SQL["INNER"].format(b="b"),
                             _reference("INNER", p, b))
    assert dict(entry.caps) == {}


# ---------------------------------------------------------------------------
# the search alone
# ---------------------------------------------------------------------------

def _columns(name, rng):
    """Strictly increasing build keys that try the interpolation's window:
    how far a key's guessed row lies from its own is the column's to say."""
    if name == "even":
        return np.arange(50_000) * 4 + 1
    if name == "dbgen_order_keys":           # 8 of every 32
        i = np.arange(60_000)
        return (i // 8) * 32 + i % 8 + 1
    if name == "random_gaps":
        return np.cumsum(rng.integers(1, 1000, 50_000))
    if name == "exponential":                # the guess is rows off
        return np.unique(np.floor(1.0003 ** np.arange(70_000)).astype(np.int64))
    if name == "a_far_first_key":            # everything guessed at the end
        return np.r_[0, 2 ** 30 + np.arange(5000)]
    if name == "a_far_last_key":
        return np.r_[np.arange(5000) * 3, 2 ** 31 - 1]
    if name == "float32_cannot_tell_them_apart":
        return np.r_[0, 2 ** 31 - 5000 + np.arange(4000)]
    if name == "two_rows":
        return np.array([5, 9])
    if name == "wide_even":
        return np.arange(50_000) * 5_000_000_000 - 2 ** 50
    if name == "wide_clustered":
        return np.r_[I64.min, -2 ** 40 + np.arange(3000), 0,
                     2 ** 62 + np.arange(3000) * 7, I64.max]
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "even", "dbgen_order_keys", "random_gaps", "exponential",
    "a_far_first_key", "a_far_last_key", "float32_cannot_tell_them_apart",
    "two_rows", "wide_even", "wide_clustered"])
def test_the_search_finds_every_key_whatever_the_column_s_shape(name):
    import jax.numpy as jnp

    from dask_sql_tpu.ops import hashing

    rng = np.random.default_rng(len(name))
    k = np.asarray(_columns(name, rng), dtype=np.int64)
    assert (np.diff(k.astype(object)) > 0).all()
    beside = np.r_[k - 1, k + 1][(np.r_[k > I64.min, k < I64.max])]
    raw = np.r_[k, beside, I64.min, I64.max, 0,
                rng.integers(int(k[0]), int(k[-1]), 5000, endpoint=True)]
    raw = rng.permutation(raw.astype(np.int64))
    narrow = int(k[-1]) - int(k[0]) < 2 ** 31
    assert narrow != name.startswith("wide")
    for as_narrow in {narrow, False}:
        lo, hi, ok = hashing._ordered_check(jnp.asarray(k), False, as_narrow)
        pos, found = hashing._ordered_search(jnp.asarray(k), lo, hi,
                                             jnp.asarray(raw), as_narrow)
        assert bool(ok)
        want_found = np.isin(raw, k)
        np.testing.assert_array_equal(np.asarray(found), want_found)
        np.testing.assert_array_equal(
            np.asarray(pos)[want_found],
            np.searchsorted(k, raw[want_found]))


# ---------------------------------------------------------------------------
# a stale hint
# ---------------------------------------------------------------------------

def _replaced(kind, b):
    """A build side of ``b``'s layout that the hint learned for ``b`` no
    longer describes."""
    k = b["k"].to_numpy().copy()
    if kind == "unsorted":
        k[[10, 700]] = k[[700, 10]]
    elif kind == "a_duplicate":
        k[501] = k[500]
    elif kind == "a_descent_at_the_end":
        k[-1] = k[0] - 1
    elif kind == "sparse_under_a_dense_hint":
        k = k * 3
    elif kind == "wide_under_a_narrow_hint":
        k[-1] = k[0] + 2 ** 31
    else:
        raise AssertionError(kind)
    return pd.DataFrame({"k": k, "v": b["v"].to_numpy()})


@pytest.mark.parametrize("kind,start", [
    ("unsorted", "dense"), ("a_duplicate", "dense"),
    ("a_descent_at_the_end", "sparse"), ("unsorted", "sparse"),
    ("sparse_under_a_dense_hint", "dense"),
    ("wide_under_a_narrow_hint", "sparse")])
@pytest.mark.parametrize("jt", JOIN_TYPES)
def test_a_stale_hint_recompiles_with_the_table_and_is_learned_cleared(
        any_rows, jt, kind, start):
    """The hint rides with what was learned for a program's key, which
    holds layout and no data: a table replaced by one of the same layout
    meets the old hint.  The program's check refutes it, the round is
    thrown away whatever else its flags say, and the next builds the
    table."""
    p, b, _, level = _frames(start)
    ctx = _context(p, b)
    query = SQL[jt].format(b="b")
    _, _, seen = _run(ctx, query)
    (entry, _), = seen
    base_key = entry.key[0]
    # as a round that learned a capacity would have left it
    caps._learned_caps_put(base_key, dict(entry.caps))
    assert caps._learned_caps[base_key]["ord0r"] == level

    b2 = _replaced(kind, b)
    ctx.create_table("b", b2)
    fresh_stats = ctx.schema["root"].tables["b"].stats.col("k")
    got, moved, seen = _run(ctx, query)
    _assert_same_rows(got, _reference(jt, p, b2))
    # the first round ran the old program and was refuted; the second is
    # the table's
    (first, first_flags), (second, _) = seen
    assert first is entry
    assert first_flags[2 + len(first.meta["agg_sites"])] == 1
    assert second.meta["ordered"] == [] \
        and second.meta["hash_table_joins"] == 1
    assert dict(second.caps)["ord0r"] == 0
    assert caps._learned_caps[base_key]["ord0r"] == 0
    assert moved["recompiles"] == 1 and moved["join_probes_ordered"] == 0
    # a duplicate build key is the eager tier's where a join carries one
    # candidate a probe row, as ever: after the table said so, not before
    dup = kind == "a_duplicate" and jt in ("INNER", "LEFT")
    assert moved["fallbacks"] == (1 if dup else 0)
    # and the cleared hint outlives statistics that say "increasing" again
    if kind in ("sparse_under_a_dense_hint", "wide_under_a_narrow_hint"):
        assert fresh_stats.increasing
        _, moved, seen = _run(ctx, query)
        assert seen[-1][0] is second and moved["recompiles"] == 0


def test_a_refuted_round_is_not_the_eager_tier_s(any_rows):
    """Above a join whose hint does not hold everything is garbage, the
    eager bit too: an unsorted dimension makes the join above it see
    duplicate build keys.  The refutation is read first."""
    rng = np.random.default_rng(12)
    c = pd.DataFrame({"j": np.arange(300), "g": np.arange(300) % 7})
    b = pd.DataFrame({"k": rng.permutation(300), "j": rng.permutation(300)})
    p = pd.DataFrame({"k": rng.integers(0, 300, N_PROBE),
                      "w": np.round(rng.random(N_PROBE), 6)})
    ctx = _context(p, b)
    ctx.create_table("c", c)
    query = ("SELECT p.k, p.w, bc.g FROM p JOIN (SELECT b.k, c.g FROM b "
             "JOIN c ON b.j = c.j) AS bc ON p.k = bc.k")
    _, _, seen = _run(ctx, query)
    entry, _ = seen[-1]
    tag, = entry.meta["ordered"]
    caps._learned_caps_put(entry.key[0], dict(entry.caps))
    # every key of c is now the same: whatever row the probe lands on, the
    # join above sees one b.k a c row
    c2 = c.assign(j=rng.permutation(300))
    ctx.create_table("c", c2)
    got, moved, seen = _run(ctx, query)
    _assert_same_rows(got, p.merge(b.merge(c2, on="j")[["k", "g"]], on="k"))
    assert moved["recompiles"] == 1 and moved["fallbacks"] == 0
    assert caps._learned_caps[entry.key[0]][tag] == 0


# ---------------------------------------------------------------------------
# the flags and the counts
# ---------------------------------------------------------------------------

def test_the_flags_hold_a_verdict_a_probe_between_the_sites_and_the_tail(
        any_rows):
    """A grouped aggregate over three joins: two probe a dimension in key
    order, one builds a table.  The group count stays at position 2, the
    verdicts follow the sites in trace order, the table's bit is last."""
    rng = np.random.default_rng(11)
    p = pd.DataFrame({"k": rng.integers(0, 1000, N_PROBE),
                      "j": rng.integers(0, 50, N_PROBE) * 7,
                      "h": rng.integers(0, 40, N_PROBE),
                      "w": np.round(rng.random(N_PROBE), 6)})
    b = pd.DataFrame({"k": np.arange(1000), "g": np.arange(1000) % 300})
    c = pd.DataFrame({"j": np.arange(50) * 7, "x": np.arange(50) % 3})
    d = pd.DataFrame({"h": rng.permutation(40), "y": np.arange(40) % 2})
    ctx = _context(p, b)
    ctx.create_table("c", c)
    ctx.create_table("d", d)
    query = ("SELECT b.g, SUM(p.w * c.x * d.y) AS s FROM p "
             "JOIN b ON p.k = b.k JOIN c ON p.j = c.j "
             "JOIN d ON p.h = d.h GROUP BY b.g")
    got, moved, seen = _run(ctx, query)
    m = p.merge(b, on="k").merge(c, on="j").merge(d, on="h")
    want = (m.assign(s=m["w"] * m["x"] * m["y"])
            .groupby("g", as_index=False)["s"].sum())
    _assert_same_rows(got, want)
    entry, flags = seen[-1]
    sites = len(entry.meta["agg_sites"])
    assert sites >= 1 and len(entry.meta["ordered"]) == 2
    assert entry.meta["hash_table_joins"] == 1
    assert len(flags) == 2 + sites + 2 + 1
    assert flags[0] == 0 and flags[2] == 300 <= entry.meta["ngroup_caps"][0]
    assert list(flags[2 + sites:]) == [0, 0, 1]
    hints = {t: v for t, v in dict(entry.caps).items() if t.startswith("ord")}
    assert sorted(entry.meta["ordered"]) == sorted(hints)
    assert sorted(hints.values()) == [stats.ORDERED_NARROW,
                                      stats.ORDERED_DENSE]
    # three joins of the formulation: a table that fits and a dense column
    # are direct, the searched column neither direct nor looped
    attrs = _attrs(ctx)
    assert (attrs["ordered_probes"], attrs["hash_table_joins"],
            attrs["direct_probes"]) == (2, 3, 2)
    # counted once, for the round that answered
    assert (moved["join_probes_ordered"], moved["join_probes_direct"],
            moved["join_probes_looped"]) == (2, 2, 0)
    assert "join_probes_ordered" in tel.STABLE_COUNTERS


def test_a_program_without_an_ordered_probe_keeps_its_flags():
    rng = np.random.default_rng(13)
    p = pd.DataFrame({"k": rng.integers(0, 1000, N_PROBE),
                      "w": np.round(rng.random(N_PROBE), 6)})
    b = pd.DataFrame({"k": rng.permutation(1000),
                      "v": np.round(rng.random(1000), 6)})
    ctx = _context(p, b)
    got, moved, seen = _run(ctx, SQL["INNER"].format(b="b"))
    _assert_same_rows(got, _reference("INNER", p, b))
    (entry, flags), = seen
    assert not [t for t in entry.caps if t.startswith("ord")]
    assert entry.meta["ordered"] == []
    assert len(flags) == 2 + len(entry.meta["agg_sites"]) + 1
    assert "ordered_probes" not in _attrs(ctx)
    assert moved["join_probes_ordered"] == 0


def test_the_hints_name_a_join_s_sides_in_the_plan_s_own_order():
    """``ord<j>``: joins numbered inputs first; ``l`` / ``r``: the side whose
    key is a base table's increasing column, through projects and filters
    and no further."""
    rng = np.random.default_rng(14)
    ctx = _context(
        pd.DataFrame({"k": rng.integers(0, 100, 500),
                      "j": rng.integers(0, 100, 500)}),
        pd.DataFrame({"k": np.arange(100), "v": np.arange(100) * 1.0}))
    ctx.create_table("c", pd.DataFrame({"j": np.arange(100) * 9,
                                        "x": np.arange(100)}))
    from dask_sql_tpu.sql.parser import parse_sql
    query = ("SELECT p.k, b.v, c.x FROM p JOIN "
             "(SELECT k, v FROM b WHERE v >= 0) AS b ON p.k = b.k "
             "JOIN c ON p.j = c.j")
    stmt = parse_sql(query)[0]
    plan = ctx._get_plan(getattr(stmt, "query", stmt), query)
    hints = stats.ordered_probe_hints(plan, ctx)
    assert hints == {"ord0r": stats.ORDERED_DENSE,
                     "ord1r": stats.ORDERED_NARROW}
    assert set(stats.join_tags(plan).values()) == {"ord0", "ord1"}
