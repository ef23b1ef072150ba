"""Round 0 of a direct-addressed hash-table join is one 32-bit gather and a
range test (``hashing._direct_probe``), and whether a join's table was
direct-addressed is the data's to say: one bit a hash-table join at the end
of a program's flags, read in ``_materialize`` into ``hash_table_joins`` /
``direct_probes`` on the ``materialize`` span and the counters
``join_probes_direct`` / ``join_probes_looped``."""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu.physical import caps, compiled as cm, programs
from dask_sql_tpu.runtime import telemetry as tel

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
    """(probe keys, build keys, build relation, joins that fit) of a case.
    The build side's table holds 16 slots a row (``_hash_table_size``)."""
    rng = np.random.default_rng(len(name))
    b_rel = "b"
    if name == "fits":
        # 1000 keys over a span of 3000 in a table of 16 384
        bk = rng.choice(np.arange(100, 3100), 1000, replace=False)
        pk = _probe_keys(rng, np.arange(100, 3100), [100, 3099])
        direct = 1
    elif name == "sparse":
        # a span of 1e9: the insert hashes, the probe loops
        bk = np.arange(1000, dtype=np.int64) * 1_000_003
        pk = _probe_keys(rng, bk, bk[:50] + 1)
        direct = 0
    elif name == "outside_negative":
        bk = np.arange(-1500, -500)
        pk = _probe_keys(rng, bk, [I64.min, I64.min + 1, -1501, -500, -499,
                                   0, 1500, I64.max - 1, I64.max])
        direct = 1
    elif name == "near_int64_max":
        # the subtraction ``key - lo`` wraps for the keys far below
        bk = I64.max - np.arange(1, 1001)
        pk = _probe_keys(rng, bk, [I64.min, -1, 0, bk.min() - 1, I64.max])
        direct = 1
    elif name == "near_int64_min":
        bk = I64.min + np.arange(0, 1000)
        pk = _probe_keys(rng, bk, [I64.max, 1, 0, bk.max() + 1])
        direct = 1
    elif name == "span_the_f64_rounds_away":
        # two rows, 32 slots, a span of 40: float64 reads both keys as
        # 2**62 and the span as 0.  A table taken for direct-addressed
        # would hold the second key clipped into slot 31, where the probe
        # key 2**62 + 31 would meet it
        bk = np.array([2**62, 2**62 + 40])
        pk = _probe_keys(rng, bk, [2**62 + 31, 2**62 + 39, 2**62 + 41,
                                   2**62 - 1])
        direct = 0
    elif name == "empty_by_filter":
        bk = np.arange(100, 1100)
        pk = _probe_keys(rng, bk, [99, 1100])
        b_rel = "(SELECT k, v FROM b WHERE v > 100.0) AS b"
        direct = 0      # no valid key, no span
    elif name == "null_probe_keys":
        bk = np.arange(100, 1100)
        pk = _probe_keys(rng, bk, [99, 1100])
        direct = 1
    else:
        raise AssertionError(name)
    return pk, np.asarray(bk, dtype=np.int64), b_rel, direct


def _frames(name):
    pk, bk, b_rel, direct = _case(name)
    rng = np.random.default_rng(7)
    p = pd.DataFrame({"k": pk, "w": np.round(rng.random(len(pk)), 6)})
    b = pd.DataFrame({"k": bk, "v": np.round(rng.random(len(bk)), 6)})
    if name == "null_probe_keys":
        p["k"] = p["k"].astype("Int64").mask(rng.random(len(p)) < 0.1)
    return p, b, b_rel, direct


def _reference(jt, p, b):
    """pandas, with SQL's NULL keys: they match nothing."""
    b = b[b["k"].notna()]
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
            s = s.astype("Float64")          # whole numbers beside None
        whole = pd.api.types.is_integer_dtype(s.dtype) and not s.isna().any()
        out[name] = (s.astype("int64") if whole
                     else s if s.dtype == object else s.astype("float64"))
    return pd.DataFrame(out).sort_values(list(frame.columns),
                                         ignore_index=True)


def _assert_same_rows(got, want):
    assert list(got.columns) == list(want.columns)
    pd.testing.assert_frame_equal(_plain(got), _plain(want))


def _materialize_attrs(ctx):
    span, = [s for s in ctx.last_report.root.walk()
             if s.name == "materialize"]
    return span.attrs


@pytest.fixture
def fresh():
    programs._cache.clear()
    caps._learned_caps.clear()


def _counters():
    c = tel.REGISTRY.snapshot()["counters"]
    return (c.get("join_probes_direct", 0), c.get("join_probes_looped", 0),
            c.get("fallbacks", 0))


def _run(jt, p, b, b_rel="b"):
    ctx = Context()
    ctx.create_table("p", p)
    ctx.create_table("b", b)
    before = _counters()
    got = ctx.sql(SQL[jt].format(b=b_rel), return_futures=False)
    after = _counters()
    return ctx, got, tuple(x - y for x, y in zip(after, before))


CASES = ["fits", "sparse", "outside_negative", "near_int64_max",
         "near_int64_min", "span_the_f64_rounds_away", "empty_by_filter",
         "null_probe_keys"]


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("jt", ["INNER", "LEFT", "SEMI", "ANTI"])
def test_a_single_integer_key_answers_as_pandas_and_counts_its_probe(
        fresh, jt, name):
    p, b, b_rel, direct = _frames(name)
    ctx, got, (n_direct, n_looped, n_fallbacks) = _run(jt, p, b, b_rel)
    want = _reference(jt, p, b[b["v"] > 100.0] if "WHERE" in b_rel else b)
    assert len(want) > 0 or name == "empty_by_filter"
    _assert_same_rows(got, want)
    attrs = _materialize_attrs(ctx)
    assert attrs["hash_table_joins"] == 1
    assert attrs["direct_probes"] == direct
    assert (n_direct, n_looped, n_fallbacks) == (direct, 1 - direct, 0)


@pytest.mark.parametrize("jt", ["INNER", "LEFT", "SEMI", "ANTI"])
def test_an_all_null_build_side_matches_nothing(fresh, jt):
    p, b, _, _ = _frames("fits")
    b["k"] = pd.array([pd.NA] * len(b), dtype="Int64")
    ctx, got, (n_direct, n_looped, n_fallbacks) = _run(jt, p, b)
    _assert_same_rows(got, _reference(jt, p, b))
    assert _materialize_attrs(ctx)["direct_probes"] == 0
    assert (n_direct, n_looped, n_fallbacks) == (0, 1, 0)


@pytest.mark.parametrize("jt,flagged", [("INNER", True), ("LEFT", True),
                                        ("SEMI", False), ("ANTI", False)])
def test_a_duplicate_build_key_still_flags_where_it_must(fresh, jt, flagged):
    """INNER and LEFT carry one candidate a probe row: a second build row
    of a key raises the fallback flag and the eager tier answers.  SEMI and
    ANTI ask whether any row matches and take the duplicate."""
    p, b, _, _ = _frames("fits")
    b = pd.concat([b, b.iloc[[5]].assign(v=0.5)], ignore_index=True)
    ctx, got, (n_direct, n_looped, n_fallbacks) = _run(jt, p, b)
    _assert_same_rows(got, _reference(jt, p, b))
    assert n_fallbacks == (1 if flagged else 0)
    # a run that fell back answered nothing: its probes are not counted
    assert (n_direct, n_looped) == ((0, 0) if flagged else (1, 0))


@pytest.mark.parametrize("second,direct", [
    # 40 x 25 combined keys in a table of 16 x 1000 slots
    ("int", 1),
    # a string part joins by its unified dictionary codes, integers too
    ("str", 1),
    # 40 x 1e9: the combination holds (injective) and does not fit
    ("wide", 0)])
@pytest.mark.parametrize("jt", ["INNER", "SEMI"])
def test_a_two_part_key_probes_direct_where_its_combination_fits(
        fresh, jt, second, direct):
    rng = np.random.default_rng(3)
    pairs = rng.permutation(40 * 25)[:1000]
    a, c = pairs // 25, pairs % 25
    pa, pc = rng.integers(-2, 42, N_PROBE), rng.integers(-2, 27, N_PROBE)
    if second == "str":
        c, pc = (np.array([f"c{x:02d}" for x in v]) for v in (c, pc))
    elif second == "wide":
        c, pc = c * 40_000_000, pc * 40_000_000
    p = pd.DataFrame({"a": pa, "c": pc, "w": np.round(rng.random(N_PROBE), 6)})
    b = pd.DataFrame({"a": a, "c": c, "v": np.round(rng.random(1000), 6)})
    ctx = Context()
    ctx.create_table("p", p)
    ctx.create_table("b", b)
    if jt == "INNER":
        query = ("SELECT p.a, p.w, b.v FROM p JOIN b "
                 "ON p.a = b.a AND p.c = b.c")
        want = p.merge(b, on=["a", "c"])[["a", "w", "v"]]
    else:
        query = ("SELECT p.a, p.w FROM p WHERE EXISTS (SELECT 1 FROM b "
                 "WHERE b.a = p.a AND b.c = p.c)")
        want = p.merge(b, on=["a", "c"])[["a", "w"]]
    got = ctx.sql(query, return_futures=False)
    assert len(want) > 100
    _assert_same_rows(got, want)
    attrs = _materialize_attrs(ctx)
    assert (attrs["hash_table_joins"], attrs["direct_probes"]) == (1, direct)


def _flags_of(ctx, query, monkeypatch):
    """The answer, and (program, flags) of every round that gave it."""
    seen = []
    real = cm._check_flags

    def spy(entry, flags):
        seen.append((entry, np.array(flags)))
        return real(entry, flags)

    monkeypatch.setattr(cm, "_check_flags", spy)
    return ctx.sql(query, return_futures=False), seen


def test_the_tail_leaves_the_group_counts_where_check_flags_reads_them(
        fresh, monkeypatch):
    """A grouped aggregate over a hash-table join: the group count stays at
    position 2 and the cap is learned from it as before; the join's bit
    comes after it."""
    monkeypatch.setattr(caps, "DEFAULT_GROUP_CAP", 16)
    rng = np.random.default_rng(11)
    p = pd.DataFrame({"k": rng.integers(0, 1000, N_PROBE),
                      "w": np.round(rng.random(N_PROBE), 6)})
    b = pd.DataFrame({"k": np.arange(1000), "g": np.arange(1000) % 300})
    ctx = Context()
    ctx.create_table("p", p)
    ctx.create_table("b", b)
    query = ("SELECT b.g, SUM(p.w) AS s FROM p JOIN b ON p.k = b.k "
             "GROUP BY b.g")
    recompiles = tel.REGISTRY.snapshot()["counters"].get("recompiles", 0)
    got, seen = _flags_of(ctx, query, monkeypatch)
    want = (p.merge(b, on="k").groupby("g", as_index=False)["w"].sum()
            .rename(columns={"w": "s"}))
    _assert_same_rows(got, want)
    assert tel.REGISTRY.snapshot()["counters"]["recompiles"] > recompiles
    entry, flags = seen[-1]
    sites = len(entry.meta["agg_sites"])
    assert sites >= 1 and entry.meta["hash_table_joins"] == 1
    assert len(flags) == 2 + sites + 1
    assert flags[2] == 300 <= entry.meta["ngroup_caps"][0]
    assert flags[-1] == 1
    # the first round's count overflowed the cap of 16 and was read as such
    first_entry, first_flags = seen[0]
    assert first_entry.meta["ngroup_caps"][0] == 16 < first_flags[2]


def test_a_program_without_a_hash_table_join_keeps_its_flags(fresh,
                                                             monkeypatch):
    rng = np.random.default_rng(12)
    p = pd.DataFrame({"k": rng.integers(0, 50, N_PROBE),
                      "w": np.round(rng.random(N_PROBE), 6)})
    ctx = Context()
    ctx.create_table("p", p)
    before = _counters()
    got, seen = _flags_of(
        ctx, "SELECT k, SUM(w) AS s FROM p GROUP BY k", monkeypatch)
    assert len(got) == 50
    entry, flags = seen[-1]
    assert entry.meta["hash_table_joins"] == 0
    assert len(flags) == 2 + len(entry.meta["agg_sites"])
    assert "hash_table_joins" not in _materialize_attrs(ctx)
    assert _counters() == before
