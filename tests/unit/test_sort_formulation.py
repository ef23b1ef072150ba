"""What the TPU strategy traces for a join and an ORDER BY, by the rows
their sorts would see (``compiled.SORT_ROWS_MAX``, ``LEXSORT_ROWS_MAX``):
XLA:TPU compiles a sort of millions of rows in minutes, more for each key
channel, so above the limits the operators hold no such sort; a grouped
aggregate holds none at any size."""
import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu.ops.kernels import _u32_channels, lexsort_by_passes
from dask_sql_tpu.physical import caps, compiled as cm, programs

RNG = np.random.RandomState(27)
N = 3000
FLOATS = RNG.choice([0.0, -0.0, 1.5, -2.25, 1e30, -1e30, 1e-5, np.inf,
                     -np.inf, 3.0000000000000004, 3.0, 2.9999999999999996,
                     123456789.12345679, 123456789.12345678], N)
KEYS = {
    "float_edges": [FLOATS],
    "float_normal": [RNG.randn(N) * 1e6],
    "float32": [RNG.randn(N).astype(np.float32)],
    "int64_edges": [RNG.choice([-2**63, 2**63 - 1, 0, -1, 1, 2**32, -2**32,
                                2**31, 5, 7], N).astype(np.int64)],
    "int32": [RNG.randint(-2**31, 2**31 - 1, N).astype(np.int32)],
    "int8_flag": [RNG.randint(-1, 2, N).astype(np.int8)],
    "bool": [RNG.randint(0, 2, N).astype(bool)],
    "ties_broken_by_second": [RNG.randn(N), RNG.randint(0, 3, N)],
    "q3_order_by": [RNG.randint(0, 50, N).astype(np.int64),
                    RNG.randint(8000, 9000, N).astype(np.int32),
                    -np.round(RNG.rand(N) * 50) * 1000.0,
                    np.zeros(N, np.int8), RNG.randint(0, 2, N).astype(np.int8)],
    "one_row": [np.array([1.0])],
}


@pytest.mark.parametrize("case", sorted(KEYS))
def test_passes_give_the_permutation_lexsort_gives(case):
    keys = KEYS[case]
    got = np.asarray(jax.jit(lexsort_by_passes)(
        [jnp.asarray(k) for k in keys]))
    np.testing.assert_array_equal(got, np.lexsort(keys))


@pytest.mark.parametrize("dtype", [np.float64, np.float32, np.int64,
                                   np.int32, np.int16, np.int8])
def test_channels_order_as_the_key_orders(dtype):
    if np.issubdtype(dtype, np.floating):
        key = (RNG.randn(500) * 10.0 ** RNG.randint(-20, 20, 500)).astype(dtype)
    else:
        info = np.iinfo(dtype)
        key = RNG.randint(info.min, info.max, 500, dtype=dtype)
    channels = [np.asarray(c) for c in _u32_channels(jnp.asarray(key))]
    assert all(c.dtype == np.uint32 for c in channels)
    assert len(channels) == {8: 3 if np.issubdtype(dtype, np.floating) else 2,
                             4: 3 if np.issubdtype(dtype, np.floating) else 1,
                             2: 1, 1: 1}[np.dtype(dtype).itemsize]
    by_channels = np.lexsort(channels[::-1])
    np.testing.assert_array_equal(key[by_channels], np.sort(key))


def test_the_sort_formulation_is_the_tpu_strategys_up_to_the_limit(
        monkeypatch):
    monkeypatch.delenv("DSQL_STRATEGY", raising=False)
    assert cm.SORT_ROWS_MAX == 1 << 18 and cm.LEXSORT_ROWS_MAX == 1 << 10
    assert not cm._sort_formulation(8)            # this host is no TPU
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    assert cm._sort_formulation(8)
    assert cm._sort_formulation(cm.SORT_ROWS_MAX)     # Q14's probe side
    assert not cm._sort_formulation(cm.SORT_ROWS_MAX + 1)
    monkeypatch.setenv("DSQL_STRATEGY", "host")
    assert not cm._sort_formulation(8)


def _eqns_under(jaxpr, scope: str, inside: bool = False):
    """The equations whose name stack, or that of an equation they are
    nested in, holds ``scope``."""
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack)
        if here:
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_under(sub, scope, here)


def _primitives_under(jaxpr, scope: str) -> set:
    return {eqn.primitive.name for eqn in _eqns_under(jaxpr, scope)}


def _sort_keys_under(jaxpr, scope: str) -> set:
    """``num_keys`` of every sort under ``scope``."""
    return {eqn.params["num_keys"] for eqn in _eqns_under(jaxpr, scope)
            if eqn.primitive.name == "sort"}


ROWS = 1 << 12
QUERY = ("SELECT okey, day, SUM(v) AS s FROM items JOIN orders "
         "ON items.okey = orders.okey2 GROUP BY okey, day "
         "ORDER BY s DESC, okey LIMIT 7")


def _traced(monkeypatch, sort_rows_max, lexsort_rows_max):
    """(the jaxprs of the programs QUERY ran as, its answer) under the TPU
    strategy with the limits set."""
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    monkeypatch.delenv("DSQL_CAPS_FILE", raising=False)
    monkeypatch.setattr(cm, "SORT_ROWS_MAX", sort_rows_max)
    monkeypatch.setattr(cm, "LEXSORT_ROWS_MAX", lexsort_rows_max)
    programs._cache.clear()
    caps._learned_caps.clear()
    jaxprs = []
    build = cm._build

    def spy(*args, **kwargs):
        entry = build(*args, **kwargs)
        jitted = entry.fn

        def fn(*flat):
            jaxprs.append(jax.make_jaxpr(jitted)(*flat).jaxpr)
            return jitted(*flat)

        entry.fn = fn
        return entry

    monkeypatch.setattr(cm, "_build", spy)
    rng = np.random.RandomState(3)
    ctx = Context()
    ctx.create_table("items", pd.DataFrame({
        "okey": rng.randint(0, 900, ROWS), "v": np.round(rng.rand(ROWS), 3)}))
    ctx.create_table("orders", pd.DataFrame({
        "okey2": np.arange(900), "day": rng.randint(0, 5, 900)}))
    frame = ctx.sql(QUERY, return_futures=False)
    return jaxprs, frame


def test_above_the_limits_no_operator_holds_a_multi_key_sort(monkeypatch):
    small, want = _traced(monkeypatch, 1 << 18, 1 << 13)
    big, got = _traced(monkeypatch, 64, 8)
    pd.testing.assert_frame_equal(got, want)
    # under the limits: the merge join sorts, the ORDER BY is one
    # multi-key sort; the group-by sorts at no size
    join = [_primitives_under(j, "dsql.LogicalJoin") for j in small]
    assert any("sort" in found for found in join)
    assert all("sort" not in _primitives_under(j, "dsql.groupby_sorted")
               for j in small)
    assert any(max(_sort_keys_under(j, "dsql.LogicalSort"), default=0) >= 3
               for j in small)
    # above them: scatters in the join and the group-by, no sort there, and
    # every sort the program still holds has one key
    final = big[-1]
    assert "sort" not in _primitives_under(final, "dsql.join_build")
    assert "sort" not in _primitives_under(final, "dsql.join_probe")
    grouped = _primitives_under(final, "dsql.groupby_sorted")
    assert "sort" not in grouped
    assert any(p.startswith("scatter") for p in grouped)
    assert _sort_keys_under(final, "dsql.LogicalSort") == {1}
    assert "while" in _primitives_under(final, "dsql.LogicalSort")


@pytest.mark.parametrize("limits", [(1 << 18, 1 << 13), (64, 8)])
def test_the_dynamic_group_by_has_its_scope_at_every_size(monkeypatch,
                                                          limits):
    jaxprs, _ = _traced(monkeypatch, *limits)
    assert any(_primitives_under(j, "dsql.groupby_sorted") for j in jaxprs)


def test_a_stage_span_says_which_stage_it_is(monkeypatch):
    """``index``, ``heavy``, ``rows_out`` and ``capacity`` on every stage
    of a plan cut into stage programs; the dispatch inside a stage carries
    what a whole-plan dispatch carries."""
    monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
    programs._cache.clear()
    caps._learned_caps.clear()
    rng = np.random.RandomState(5)
    ctx = Context()
    ctx.create_table("items", pd.DataFrame({
        "okey": rng.randint(0, 900, ROWS), "v": rng.rand(ROWS)}))
    ctx.create_table("orders", pd.DataFrame({
        "okey2": np.arange(900), "day": rng.randint(0, 5, 900)}))
    for _ in range(2):      # the second run dispatches cached programs
        ctx.sql(QUERY, return_futures=False)
    spans = list(ctx.last_report.root.walk())
    stage = sorted((s for s in spans if s.name == "stage"),
                   key=lambda s: s.attrs["index"])
    assert [s.attrs["index"] for s in stage] == [0, 1]
    assert [s.attrs["heavy"] for s in stage] == [1, 1]
    assert stage[0].attrs["rows_out"] == ROWS      # the join keeps each item
    assert stage[0].attrs["capacity"] == ROWS
    assert stage[1].attrs["rows_out"] == 7
    assert stage[1].attrs["capacity"] == 64
    dispatch = [s for s in spans if s.name == "dispatch"]
    assert len(dispatch) == 2
    for span in dispatch:
        assert span.attrs["program"].startswith("dsql_")
        assert {"compact_sites", "compact_cap"} <= set(span.attrs)


def test_a_grouped_aggregate_over_a_join_compacts_its_input(monkeypatch):
    """Few of a join's probe rows match: the group-by above it runs on the
    set rows, compacted to a learned capacity (a ``cmp`` site of the
    program), and answers as the uncompacted one does."""
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    monkeypatch.delenv("DSQL_CAPS_FILE", raising=False)
    programs._cache.clear()
    caps._learned_caps.clear()
    rng = np.random.RandomState(9)
    n = 1 << 17
    items = pd.DataFrame({"okey": rng.randint(0, 40000, n),
                          "v": np.round(rng.rand(n), 3)})
    orders = pd.DataFrame({"okey2": np.arange(0, 40000, 40),
                           "day": rng.randint(0, 50, 1000)})
    query = ("SELECT okey, day, SUM(v) AS s FROM items JOIN orders "
             "ON items.okey = orders.okey2 GROUP BY okey, day")
    ctx = Context()
    ctx.create_table("items", items)
    ctx.create_table("orders", orders)
    got = ctx.sql(query, return_futures=False)
    ctx.sql(query, return_futures=False)
    span, = [s for s in ctx.last_report.root.walk() if s.name == "dispatch"]
    assert span.attrs["compact_sites"] == 1
    assert 1024 <= span.attrs["compact_cap"] <= n // 8
    want = (items.merge(orders, left_on="okey", right_on="okey2")
            .groupby(["okey", "day"], as_index=False)["v"].sum()
            .rename(columns={"v": "s"}))
    got = got.sort_values(["okey", "day"], ignore_index=True)
    pd.testing.assert_frame_equal(got, want.sort_values(
        ["okey", "day"], ignore_index=True), check_dtype=False)
