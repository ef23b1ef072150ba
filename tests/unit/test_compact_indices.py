"""``ops.kernels.compact_indices``: the row indices of the compaction below
a join, built without a scatter.  They have to be ``jnp.nonzero(mask,
size=cap, fill_value=0)``'s, element for element: every answer above a
compaction rests on that."""
import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu.ops.kernels import compact_indices


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


@pytest.mark.parametrize("name", list(CASES))
def test_indices_are_nonzeros(name):
    mask, cap = CASES[name]
    idx, count = jax.jit(compact_indices, static_argnums=1)(
        jnp.asarray(mask), cap)
    assert idx.dtype == jnp.int32 and idx.shape == (cap,)
    assert int(count) == int(mask.sum())
    np.testing.assert_array_equal(np.asarray(idx), _want(mask, cap))
    # the parent's own expression, not only numpy's
    np.testing.assert_array_equal(
        np.asarray(idx),
        np.asarray(jnp.nonzero(jnp.asarray(mask), size=cap, fill_value=0)[0]))


def test_a_cap_over_the_rows_is_refused():
    with pytest.raises(ValueError, match="cap 9 over 8 rows"):
        compact_indices(jnp.ones(8, dtype=bool), 9)


# --- the program: no scatter under dsql.compact -----------------------------

def _primitives_under(jaxpr, scope: str, inside: bool = False):
    """Names of the primitives whose name stack, or that of an equation
    they are nested in, holds ``scope``."""
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack)
        if here:
            yield eqn.primitive.name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives_under(sub, scope, here)


def test_walker_sees_the_scatter_of_nonzero():
    """What the guard below would have said of the parent's index build."""
    def parent(mask):
        with jax.named_scope("dsql.compact"):
            return jnp.nonzero(mask, size=8, fill_value=0)[0]

    found = set(_primitives_under(
        jax.make_jaxpr(parent)(jnp.zeros(64, dtype=bool)).jaxpr,
        "dsql.compact"))
    assert any(p.startswith("scatter") for p in found)


def test_join_over_filter_program_has_no_scatter_under_compact(monkeypatch):
    """A Q12-like program (a filtered fact table below a join below a
    grouped aggregate) traced under the TPU strategy: the compaction is
    there, and nothing in it is a scatter."""
    from dask_sql_tpu import Context
    from dask_sql_tpu.physical import compiled as cm

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
    under = [set(_primitives_under(j, "dsql.compact")) for j in jaxprs]
    assert any("gather" in found for found in under), \
        "no program compacted: the guard guards nothing"
    for found in under:
        assert not [p for p in found if p.startswith("scatter")], found
