"""``ops.kernels.compact_indices``: the row indices of the compaction below
a join, built without a scatter or a sort of all n rows.  They have to be
``jnp.nonzero(mask, size=cap, fill_value=0)``'s, element for element, in
both formulations (the whole-array sort and the slab form, at every slab
width): every answer above a compaction rests on that."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu.ops import kernels
from dask_sql_tpu.ops.kernels import compact_indices

#: slab widths: the one the cells meet, its neighbours (the constant may
#: move with a later chip's readings), and one small enough that the old
#: cases span thousands of slabs.  0: ``compact_indices`` itself, whatever
#: it chooses at the case's n and cap.
WIDTHS = sorted({0, 8, 128, 512, 1024, kernels.COMPACT_SLAB_ROWS})


def _want(mask: np.ndarray, cap: int) -> np.ndarray:
    """np.nonzero's first ``cap`` positions, slots past the count 0."""
    idx = np.nonzero(mask)[0][:cap]
    return np.concatenate([idx, np.zeros(cap - len(idx), idx.dtype)])


def _random(n, density, seed=0):
    return np.random.RandomState(seed).rand(n) < density


def _exactly(n, count, seed=0):
    mask = np.zeros(n, dtype=bool)
    mask[np.random.RandomState(seed).choice(n, count, replace=False)] = True
    return mask


CASES = {
    "empty": (np.zeros(70_000, dtype=bool), 1024),
    "full": (np.ones(70_000, dtype=bool), 1 << 15),
    "full_cap_n": (np.ones(4097, dtype=bool), 4097),
    "count_eq_cap": (_exactly(100_003, 2048), 2048),
    "count_over_cap": (_random(100_003, 0.6), 4096),
    "one_over_cap": (_exactly(100_003, 1025), 1024),
    "n_odd": (_random(99_991, 0.01), 2048),            # a prime
    "n_below_65536": (_random(65_535, 0.02), 2048),
    "n_65536": (_random(65_536, 0.02), 2048),
    "n_above_65536": (_random(65_537, 0.02), 2048),
    "cap_half_n_less_1": (_random(65_538, 0.3), 65_538 // 2 - 1),
    "first_and_last": (np.eye(1, 70_001, 0, dtype=bool)[0]
                       | np.eye(1, 70_001, 70_000, dtype=bool)[0], 1024),
    "only_last": (np.eye(1, 70_001, 70_000, dtype=bool)[0], 1024),
    "dense_runs": (np.repeat(_random(1100, 0.1, seed=3), 64), 1 << 14),
    "tiny": (np.array([False, True, True, False, True]), 4),
    "cap_1": (_random(70_000, 0.5), 1),
}


def _edges(b: int) -> dict:
    """Masks whose set rows sit on the edges of slabs of ``b`` rows."""
    full, none = np.ones(b, dtype=bool), np.zeros(b, dtype=bool)
    half = np.arange(b) % 2 == 0                           # b / 2 set
    return {
        "n_under_multiple": (_random(5 * b - 1, 0.3, seed=1), 2 * b),
        "n_at_multiple": (_random(5 * b, 0.3, seed=2), 2 * b),
        "n_over_multiple": (_random(5 * b + 1, 0.3, seed=3), 2 * b),
        "empty_slab_between_full": (
            np.concatenate([full, none, full, none, none, full]), 3 * b + 7),
        "slab_all_set": (np.concatenate([half, full, half]), 2 * b + 1),
        "all_in_last_padded_slab": (
            np.concatenate([none, none, none, [False, True, True, False,
                                               True]]), b),
        "count_eq_cap_on_slab_edge": (
            np.concatenate([half, full, none, half]), 2 * b),
        "cap_on_slab_edge_more_set": (
            np.concatenate([half, half, full, half]), b),
        "overflow_ends_inside_slab": (
            np.concatenate([half, full, full, half]), b + b // 4 + 3),
        "one_dense_run": (                                 # a cmpj* site
            np.concatenate([none, none[:b // 3], full, full, full[:b // 5],
                            none, none]), 3 * b),
    }


def _compact(mask, cap, rows):
    """``compact_indices`` (rows 0) or its slab form at ``rows`` a slab."""
    if rows == 0:
        fn = functools.partial(compact_indices, cap=cap)
    else:
        fn = functools.partial(kernels._compact_in_slabs, cap=cap, rows=rows,
                               itype=jnp.int32)
    return jax.jit(fn)(jnp.asarray(mask))


def _check(mask, cap, rows):
    idx, count = _compact(mask, cap, rows)
    assert idx.dtype == jnp.int32 and idx.shape == (cap,)
    assert int(count) == int(mask.sum())
    np.testing.assert_array_equal(np.asarray(idx), _want(mask, cap))
    return idx


@pytest.mark.parametrize("rows", [w for w in WIDTHS if w])
@pytest.mark.parametrize("name", list(_edges(8)))
def test_slab_edges(name, rows):
    _check(*_edges(rows)[name], rows)


@pytest.mark.parametrize("rows", [w for w in WIDTHS if w])
@pytest.mark.parametrize("name", list(CASES))
def test_slab_form_indices_are_nonzeros(name, rows):
    _check(*CASES[name], rows)


@pytest.mark.parametrize("n,cap,slabbed", [
    (kernels.COMPACT_SLAB_ROWS_MIN, 1 << 16, True),        # Q12's at SF1
    (kernels.COMPACT_SLAB_ROWS_MIN + 4097, 1 << 18, True),  # Q14's
    (kernels.COMPACT_SLAB_ROWS_MIN - 1, 1 << 16, False),   # a join's output
    (kernels.COMPACT_SLAB_ROWS_MIN + 4097, 1 << 20, False),  # cap n / 4
])
def test_the_choice_is_static_and_both_sides_agree(n, cap, slabbed):
    """``compact_indices`` at sizes on both sides of ``compact_slab_rows``:
    the formulation is the one the static rule names, and the positions are
    ``np.nonzero``'s either way."""
    assert bool(kernels.compact_slab_rows(n, cap)) == slabbed
    mask = _random(n, cap / n / 2, seed=n % 7)
    found = {e.primitive.name: e for e in _eqns_under(
        jax.make_jaxpr(functools.partial(compact_indices, cap=cap))(
            jnp.asarray(mask)).jaxpr, "")}
    assert ("scatter-add" in found) == slabbed
    assert (found["sort"].invars[0].aval.shape == (n,)) != slabbed
    _check(mask, cap, 0)


@pytest.mark.parametrize("name", list(CASES))
def test_indices_are_nonzeros(name):
    mask, cap = CASES[name]
    idx = _check(mask, cap, 0)
    # the parent's own expression, not only numpy's
    np.testing.assert_array_equal(
        np.asarray(idx),
        np.asarray(jnp.nonzero(jnp.asarray(mask), size=cap, fill_value=0)[0]))


def test_a_cap_over_the_rows_is_refused():
    with pytest.raises(ValueError, match="cap 9 over 8 rows"):
        compact_indices(jnp.ones(8, dtype=bool), 9)


# --- the program: nothing under dsql.compact takes n rows one by one ---------

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


def _primitives_under(jaxpr, scope: str):
    return (eqn.primitive.name for eqn in _eqns_under(jaxpr, scope))


def _serial_over(eqn, n: int) -> bool:
    """Whether ``eqn`` is one of the two ops a compaction must not apply to
    all n rows: a scatter of n updates (a TPU serializes them) or a sort
    whose sorted runs are n long (log2(n)^2 / 2 passes over HBM).  A scatter
    of a mark a slab and a sort inside slabs are neither."""
    name = eqn.primitive.name
    if name.startswith("scatter"):
        return math.prod(eqn.invars[2].aval.shape) >= n
    if name == "sort":
        return eqn.invars[0].aval.shape[eqn.params["dimension"]] >= n
    return False


def _offenders(fn, n: int):
    mask = jax.ShapeDtypeStruct((n,), jnp.bool_)
    return [eqn.primitive.name for eqn in _eqns_under(
        jax.make_jaxpr(fn)(mask).jaxpr, "dsql.compact")
        if _serial_over(eqn, n)]


def test_walker_sees_the_scatter_of_nonzero():
    """What the guard below would have said of the parent's index build."""
    def parent(mask):
        with jax.named_scope("dsql.compact"):
            return jnp.nonzero(mask, size=8, fill_value=0)[0]

    found = set(_primitives_under(
        jax.make_jaxpr(parent)(jnp.zeros(64, dtype=bool)).jaxpr,
        "dsql.compact"))
    assert any(p.startswith("scatter") for p in found)


def test_the_guard_refuses_both_earlier_index_builds():
    """``jnp.nonzero``'s ``bincount`` (before PR 26) and the sort of all n
    positions (PR 26 to PR 37) both fail the guard below; the slab form
    passes it with its scatters of a mark a slab and its sort along slabs."""
    n, cap = 1 << 17, 1 << 12

    def scoped(build):
        def fn(mask):
            with jax.named_scope("dsql.compact"):
                return build(mask)
        return fn

    assert _offenders(scoped(
        lambda m: jnp.nonzero(m, size=cap, fill_value=0)[0]), n) \
        == ["scatter-add"]
    assert _offenders(scoped(lambda m: jnp.sort(
        jnp.where(m, jnp.arange(n, dtype=jnp.int32), n))[:cap]), n) \
        == ["sort"]
    slabbed = scoped(lambda m: kernels._compact_in_slabs(
        m, cap, kernels.COMPACT_SLAB_ROWS, jnp.int32))
    assert _offenders(slabbed, n) == []
    found = set(_primitives_under(jax.make_jaxpr(slabbed)(
        jax.ShapeDtypeStruct((n,), jnp.bool_)).jaxpr, "dsql.compact"))
    assert {"sort", "scatter-add", "gather"} <= found


@pytest.mark.parametrize("cap", [1 << 20, 1 << 21])
def test_deployment_size_by_shapes_alone(cap):
    """TPC-H SF10's lineitem (60 002 228 rows, not a multiple of any slab
    width) at Q12's and Q14's caps, traced and never built: the slab form
    is chosen, positions are ``int32[cap]``, and no intermediate is wider
    than the (slabs, rows) int32 view of the mask."""
    n = 60_002_228
    rows = kernels.compact_slab_rows(n, cap)
    assert rows and n % rows
    mask = jax.ShapeDtypeStruct((n,), jnp.bool_)
    fn = functools.partial(compact_indices, cap=cap)
    idx, count = jax.eval_shape(fn, mask)
    assert (idx.shape, idx.dtype) == ((cap,), jnp.int32)
    assert count.shape == ()
    widest = -(-n // rows) * rows * 4
    eqns = list(_eqns_under(jax.make_jaxpr(fn)(mask).jaxpr, ""))
    assert max(math.prod(v.aval.shape) * v.aval.dtype.itemsize
               for eqn in eqns for v in eqn.outvars) == widest
    assert not [eqn for eqn in eqns if _serial_over(eqn, n)]


@pytest.mark.parametrize("slabs", [True, False])
def test_join_over_filter_program_has_no_n_row_scatter_or_sort_under_compact(
        monkeypatch, slabs):
    """A Q12-like program (a filtered fact table below a join below a
    grouped aggregate) traced under the TPU strategy: the compaction is
    there, and under ``dsql.compact`` no scatter takes n updates and, where
    the slab form is chosen (here at the sizes of a test: the rule's
    constants are lowered), no sort runs n rows long either."""
    from dask_sql_tpu import Context
    from dask_sql_tpu.physical import caps, compiled as cm, programs

    programs._cache.clear()      # the other case's programs and caps
    caps._learned_caps.clear()
    if slabs:
        monkeypatch.setattr(kernels, "COMPACT_SLAB_ROWS_MIN", 1 << 16)
        monkeypatch.setattr(kernels, "COMPACT_SLAB_CAP_SHARE", 2)
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    monkeypatch.delenv("DSQL_CAPS_FILE", raising=False)
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
    rng = np.random.RandomState(1)
    n = 1 << 17
    ctx = Context()
    ctx.create_table("items", pd.DataFrame({
        "okey": rng.randint(0, 4000, n), "mode": rng.randint(0, 50, n),
        "v": rng.randn(n)}))
    ctx.create_table("orders", pd.DataFrame({
        "okey": np.arange(4000), "prio": rng.randint(0, 5, 4000)}))
    ctx.sql("SELECT prio, COUNT(*) AS c FROM items JOIN orders "
            "ON items.okey = orders.okey WHERE mode < 2 GROUP BY prio",
            return_futures=False)
    under = [list(_eqns_under(j, "dsql.compact")) for j in jaxprs]
    assert any(eqn.primitive.name == "gather" for eqns in under
               for eqn in eqns), \
        "no program compacted: the guard guards nothing"
    for eqns in under:
        serial = [eqn.primitive.name for eqn in eqns if _serial_over(eqn, n)]
        assert serial == ([] if slabs or not eqns else ["sort"]), serial
    # a mark a slab: the slab form's scatters, and only there
    assert slabs == any(eqn.primitive.name == "scatter-add"
                        for eqns in under for eqn in eqns)
