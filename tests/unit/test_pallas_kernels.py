"""Pallas segmented-reduction kernel vs the XLA scatter oracle.

Runs in interpreter mode on the CPU test mesh; the same code path compiles
natively on TPU.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from dask_sql_tpu.ops import pallas_kernels as pk


@pytest.mark.parametrize("n,g,a", [(100, 3, 1), (1024, 8, 4), (5000, 60, 2)])
def test_segmented_sums_matches_oracle(n, g, a):
    rng = np.random.RandomState(7)
    vals = jnp.asarray(rng.randn(a, n))
    codes = jnp.asarray(rng.randint(0, g, n))
    mask = jnp.asarray(rng.rand(n) > 0.3)
    got = pk.segmented_sums(vals, codes, mask, g, interpret=True)
    want = pk.reference_segmented_sums(vals, codes, mask, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-10)


def test_all_masked_rows_are_zero():
    vals = jnp.ones((2, 300))
    codes = jnp.zeros(300, dtype=jnp.int32)
    mask = jnp.zeros(300, dtype=bool)
    got = pk.segmented_sums(vals, codes, mask, 4, interpret=True)
    assert np.allclose(np.asarray(got), 0.0)


def test_padding_rows_do_not_leak():
    # n not a multiple of BLOCK: padded tail must not contribute to group 0
    n = pk.BLOCK + 17
    vals = jnp.ones((1, n))
    codes = jnp.zeros(n, dtype=jnp.int32)
    mask = jnp.ones(n, dtype=bool)
    got = pk.segmented_sums(vals, codes, mask, 2, interpret=True)
    assert got[0, 0] == n
    assert got[0, 1] == 0


def test_nan_inf_isolated_to_their_groups():
    """NaN/Inf values must only affect their own group (NaN*0 == NaN would
    otherwise poison every group through the one-hot contraction)."""
    vals = jnp.asarray([[np.nan, 1.0, 2.0, 3.0, np.inf, -np.inf, 5.0, 6.0]])
    codes = jnp.asarray([0, 1, 1, 1, 2, 3, 4, 4])
    mask = jnp.ones(8, dtype=bool)
    got = np.asarray(pk.segmented_sums(vals, codes, mask, 5, interpret=True))
    assert np.isnan(got[0, 0])
    assert got[0, 1] == 6.0
    assert got[0, 2] == np.inf
    assert got[0, 3] == -np.inf
    assert got[0, 4] == 11.0


def test_masked_nan_contributes_nothing():
    vals = jnp.asarray([[np.nan, 1.0, 2.0]])
    codes = jnp.asarray([0, 0, 1])
    mask = jnp.asarray([False, True, True])
    got = np.asarray(pk.segmented_sums(vals, codes, mask, 2, interpret=True))
    assert got[0, 0] == 1.0 and got[0, 1] == 2.0


def test_posneg_inf_same_group_is_nan():
    vals = jnp.asarray([[np.inf, -np.inf, 1.0]])
    codes = jnp.asarray([0, 0, 1])
    mask = jnp.ones(3, dtype=bool)
    got = np.asarray(pk.segmented_sums(vals, codes, mask, 2, interpret=True))
    assert np.isnan(got[0, 0]) and got[0, 1] == 1.0


def test_xla_blocked_matches_oracle():
    rng = np.random.RandomState(11)
    n, g, a = 5000, 60, 3
    vals = jnp.asarray(rng.randn(a, n))
    codes = jnp.asarray(rng.randint(0, g, n))
    mask = jnp.asarray(rng.rand(n) > 0.3)
    got = pk.segmented_sums_xla_blocked(vals, codes, mask, g, block=512)
    want = pk.reference_segmented_sums(vals, codes, mask, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-10)


def test_xla_blocked_nonfinite_safe_wrapper():
    vals = jnp.asarray([[np.nan, 1.0, 2.0, np.inf]])
    codes = jnp.asarray([0, 1, 1, 2])
    mask = jnp.ones(4, dtype=bool)
    got = np.asarray(pk._nonfinite_safe(pk.segmented_sums_xla_blocked)(
        vals, codes, mask, 3))
    assert np.isnan(got[0, 0]) and got[0, 1] == 3.0 and got[0, 2] == np.inf


def _assert_exact_matches_oracle_bitwise(n, g, a):
    rng = np.random.RandomState(11)
    # integer grid up to ~1e9 per value plus a few +-2**50 outliers: total
    # magnitude stays inside the kernel's sum(|v|) < 2**53 contract (the
    # same bound the f64 scan path it replaces had)
    vals = rng.randint(-10**9, 10**9, (a, n)).astype(np.float64)
    vals[:, 0] = 2.0**50
    vals[:, 1] = -(2.0**50)
    vals[:, 2] = 2.0**50
    vals = jnp.asarray(vals)
    codes = jnp.asarray(rng.randint(0, g, n))
    mask = jnp.asarray(rng.rand(n) > 0.3)
    got = np.asarray(pk.segmented_sums_exact(vals, codes, mask, g,
                                             interpret=True))
    # numpy int64 accumulation is the exact oracle
    vn = np.asarray(vals).astype(np.int64)
    cn, mn = np.asarray(codes), np.asarray(mask)
    want = np.zeros((a, g), dtype=np.int64)
    for gg in range(g):
        want[:, gg] = vn[:, mn & (cn == gg)].sum(axis=1)
    assert np.array_equal(got, want.astype(np.float64)), (
        np.abs(got - want).max())


@pytest.mark.parametrize("n,g,a", [(100, 3, 1), (5000, 25, 3), (9000, 8, 2)])
def test_segmented_sums_exact_matches_oracle_bitwise(n, g, a):
    """The limb kernel's claim is EXACTNESS on integer-grid values (scaled
    decimals / counts), including negatives and magnitudes near 2**52."""
    _assert_exact_matches_oracle_bitwise(n, g, a)


@pytest.mark.parametrize("n", [2 * 8192 + 1, 2 * 8192 + 1234, 3 * 8192])
def test_segmented_sums_exact_looped_slabs_bitwise(monkeypatch, n):
    """More rows than one slab: the slab body is traced once and looped,
    and the last slab overlaps the one before it (those rows masked out) —
    no row may be counted twice or dropped."""
    monkeypatch.setattr(pk, "SLAB_EXACT", 2 * pk.BLOCK_EXACT)
    _assert_exact_matches_oracle_bitwise(n, 7, 2)


def test_segmented_sums_exact_nonfinite_masked_rows_ignored():
    vals = jnp.asarray([[1.0, np.nan, 3.0, np.inf, 5.0]])
    codes = jnp.asarray([0, 0, 1, 1, 1])
    mask = jnp.asarray([True, False, True, False, True])
    got = np.asarray(pk.segmented_sums_exact(vals, codes, mask, 2,
                                             interpret=True))
    assert np.array_equal(got, np.asarray([[1.0, 8.0]]))


def test_segmented_sums_exact_nonfinite_poison_confined():
    vals = jnp.asarray([[1.0, np.inf, 2.0, 4.0]])
    codes = jnp.asarray([0, 0, 1, 1])
    mask = jnp.ones(4, dtype=bool)
    got = np.asarray(pk.segmented_sums_exact(vals, codes, mask, 2,
                                             interpret=True))
    assert np.isposinf(got[0, 0]) and got[0, 1] == 6.0


def test_dispatch_mixed_classes_matches_oracle(monkeypatch):
    """Mixed int/float/unit stacks ride one limb kernel call; int rows stay
    bit-exact, float rows land within sub-ulp of the f64 oracle."""
    monkeypatch.setenv("DSQL_PALLAS", "force")
    rng = np.random.RandomState(3)
    n, g = 2048, 6
    vals = jnp.asarray(np.vstack([
        np.round(rng.randint(-10**9, 10**9, n)).astype(np.float64),
        rng.randn(n),
        (rng.rand(n) > 0.5).astype(np.float64),
    ]))
    codes = jnp.asarray(rng.randint(0, g, n))
    mask = jnp.asarray(rng.rand(n) > 0.2)
    got = np.asarray(pk.segmented_sums_dispatch(
        vals, codes, mask, g, row_classes=["int", "float", "unit"]))
    want = np.asarray(pk.reference_segmented_sums(vals, codes, mask, g))
    assert np.array_equal(got[0], want[0])      # int row: bit-exact
    assert np.array_equal(got[2], want[2])      # unit row: bit-exact
    np.testing.assert_allclose(got[1], want[1], rtol=1e-12)


def test_fixedpoint_float_rows_beat_f64_accumulation():
    """Float rows: the fixed-point sum is within one ulp-of-max of the
    TRUE sum (np.float128 oracle) across 12 orders of magnitude — tighter
    than f64 accumulation, which the old scan path could only match."""
    rng = np.random.RandomState(7)
    n, g = 20000, 4
    vals = (rng.randn(2, n) * 10.0 ** rng.randint(-6, 7, (2, n))
            ).astype(np.float64)
    codes = rng.randint(0, g, n)
    mask = rng.rand(n) > 0.1
    got = np.asarray(pk.segmented_sums_fixedpoint(
        jnp.asarray(vals), jnp.asarray(codes), jnp.asarray(mask), g,
        row_classes=["float", "float"], interpret=True))
    for i in range(2):
        for gg in range(g):
            sel = mask & (codes == gg)
            want = vals[i, sel].astype(np.float128).sum()
            # ~1 ulp of the sum (compensated recombination) + the grid
            # truncation bound n * max|v| * 2**-81
            tol = (2.0 * abs(float(want)) * 2.0 ** -52
                   + sel.sum() * np.abs(vals[i, sel]).max(initial=0.0)
                   * 2.0 ** -81)
            assert abs(float(want) - got[i, gg]) <= max(tol, 1e-300), (
                i, gg, float(want), got[i, gg])


def test_fixedpoint_tiny_and_huge_magnitudes():
    """Runtime power-of-two normalization handles extreme row scales."""
    for m in (1e-200, 1.0, 1e200):
        vals = jnp.asarray([[m, 2 * m, -m, 3 * m]])
        codes = jnp.asarray([0, 0, 1, 1])
        mask = jnp.ones(4, bool)
        got = np.asarray(pk.segmented_sums_fixedpoint(
            vals, codes, mask, 2, row_classes=["float"], interpret=True))
        np.testing.assert_allclose(got, [[3 * m, 2 * m]], rtol=1e-12)


def test_fixedpoint_zero_row_and_empty_input():
    got = np.asarray(pk.segmented_sums_fixedpoint(
        jnp.zeros((2, 5)), jnp.zeros(5, jnp.int32), jnp.ones(5, bool), 3,
        row_classes=["float", "int"], interpret=True))
    assert np.array_equal(got, np.zeros((2, 3)))
    got = np.asarray(pk.segmented_sums_fixedpoint(
        jnp.zeros((2, 0)), jnp.zeros(0, jnp.int32), jnp.ones(0, bool), 3,
        row_classes=["float", "int"], interpret=True))
    assert np.array_equal(got, np.zeros((2, 3)))


# ---------------------------------------------------------------------------
# TPU-compilability regression (a review's finding): the f64 fixed-point path
# must not trace frexp/ldexp — they lower to an s64 bitcast-convert the TPU
# X64 rewrite does not implement, which silently exiled every f64
# static-domain aggregate (the Q1 path) to eager.  The CPU-lowered HLO is
# scanned as a proxy: the banned lowering appears on every backend.
# ---------------------------------------------------------------------------

def test_exact_pow2_is_exact_over_full_range():
    n = np.arange(-1000, 1013)
    got = np.asarray(pk._exact_pow2(jnp.asarray(n, dtype=jnp.int32)))
    want = np.ldexp(np.ones(len(n)), n)
    assert (got == want).all()


def test_dispatch_compile_smoke_no_64bit_bitcast():
    """segmented_sums_dispatch's f64 fixed-point route must lower without
    any 64-bit bitcast-convert (frexp/ldexp would introduce one)."""
    import os

    import jax

    rng = np.random.RandomState(3)
    vals = jnp.asarray(np.stack([
        (rng.rand(512) > 0.5).astype(np.float64),        # 'unit': 0/1
        rng.randint(-10**9, 10**9, 512).astype(np.float64),  # 'int'
        rng.randn(512) * 1e5,                            # 'float'
    ]))
    codes = jnp.asarray(rng.randint(0, 5, 512))
    mask = jnp.asarray(rng.rand(512) > 0.2)
    os.environ["DSQL_PALLAS"] = "force"
    try:
        fn = lambda v, c, m: pk.segmented_sums_dispatch(  # noqa: E731
            v, c, m, 5, row_classes=["unit", "int", "float"])
        lowered = jax.jit(fn).lower(vals, codes, mask)
        text = lowered.as_text()
        assert "bitcast_convert" not in text, (
            "64-bit bitcast-convert in the lowered module — the TPU X64 "
            "rewrite cannot compile it")
        # and it actually compiles + matches the oracle on this backend
        got = np.asarray(jax.jit(fn)(vals, codes, mask))
        want = np.asarray(pk.reference_segmented_sums(vals, codes, mask, 5))
        np.testing.assert_allclose(got, want, rtol=1e-9)
    finally:
        del os.environ["DSQL_PALLAS"]


def test_fixedpoint_masked_outlier_does_not_coarsen_grid():
    """A review's finding: absmax must cover mask-CONTRIBUTING values only —
    a filtered-out 1e300 row must not zero the valid sums."""
    vals = jnp.asarray([[1.0, 2.0, 1e300, 3.0]])
    codes = jnp.asarray([0, 0, 1, 1])
    mask = jnp.asarray([True, True, False, True])
    got = np.asarray(pk.segmented_sums_fixedpoint(
        vals, codes, mask, 2, row_classes=["float"], interpret=True))
    np.testing.assert_allclose(got, [[3.0, 3.0]], rtol=1e-12)


# ---------------------------------------------------------------------------
# What reaches the limb kernel through Context.sql (PR 39): a row named by
# several aggregates is one row, a row mask is handed in once and as the
# bool it is, and the program's ``dispatch`` span says what was named and
# what was summed.  The TPU strategy forced on the CPU, kernels interpreted.
# ---------------------------------------------------------------------------

@pytest.fixture
def tpu_strategy(monkeypatch):
    from dask_sql_tpu.physical import caps, compiled, programs

    monkeypatch.delenv("DSQL_STRATEGY", raising=False)
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    # the first arrival waits for its program: no eager answer
    monkeypatch.setattr(compiled, "EAGER_SCAN_ROWS_MAX", 1 << 10)
    programs._cache.clear()
    caps._learned_caps.clear()


def _dispatch_attrs(context, text):
    """The ``dispatch`` span of a text whose program is there already (a
    first arrival runs its program inside ``compile``)."""
    from dask_sql_tpu.runtime import telemetry

    frame = context.sql(text, return_futures=False)
    span, = [s for s in telemetry.last_report().root.walk()
             if s.name == "dispatch"]
    return frame, span.attrs


LIMB_ROWS = ("limb_rows_named", "limb_rows_summed", "limb_indicator_rows")


@pytest.mark.parametrize("name, counts", [
    # 17 named: 5 value rows and the row mask; 3 indicator rows a value row
    ("q1", (17, 6, 15)),
    # SUM(CASE ...) twice over a join's output: integer rows, no indicators
    ("q12", (5, 5, 0))])
def test_the_dispatch_span_says_what_the_limb_kernel_summed(
        name, counts, tpu_strategy):
    import importlib

    from chipbench.data.tpch_gen import generate
    from dask_sql_tpu import Context

    shape = importlib.import_module(f"chipbench.shapes.{name}")
    frames = generate(0.01, 2147483659)
    context = Context()
    for table in ("lineitem", "orders"):
        context.create_table(table, frames[table])
    context.sql(shape.sql(shape.params_at(shape.FIRST)),
                return_futures=False)
    params = shape.params_at(3)
    got, attrs = _dispatch_attrs(context, shape.sql(params))
    assert tuple(attrs[key] for key in LIMB_ROWS) == counts
    want = shape.reference(frames, **params)
    assert list(got.columns) == list(want.columns) and len(got) == len(want)
    for column in want.columns:
        if want[column].dtype.kind == "f":
            np.testing.assert_allclose(got[column], want[column], rtol=1e-12)
        else:
            assert list(got[column]) == list(want[column])


def test_a_nullable_column_and_a_filter_keep_their_own_rows(tpu_strategy):
    """COUNT(x) of a column with NULLs is not COUNT(*): its count row is
    the column's, not the row mask; an aggregate with a FILTER counts its
    own rows; SUM(x) and AVG(x) still share one value row."""
    import pandas as pd

    from dask_sql_tpu import Context

    rng = np.random.RandomState(17)
    n = 3000
    frame = pd.DataFrame({
        "k": rng.choice(["a", "b", "c"], n),
        "x": np.where(rng.rand(n) < 0.25, np.nan, rng.normal(0, 100, n)),
        "y": rng.uniform(0, 10, n),
        "z": rng.randint(-5, 6, n)})
    context = Context()
    context.create_table("t", frame)
    text = ("SELECT k, COUNT(x) AS cx, COUNT(*) AS c, SUM(x) AS sx, "
            "AVG(x) AS ax, SUM(y) FILTER (WHERE z > {z}) AS sy, "
            "SUM(z) AS sz FROM t GROUP BY k ORDER BY k")
    context.sql(text.format(z=1), return_futures=False)
    got, attrs = _dispatch_attrs(context, text.format(z=0))
    # named: occupancy and (value, count) of six aggregates.  Summed: the
    # row mask (occupancy, COUNT(*) twice, SUM(z)'s count), x's own mask
    # (COUNT(x) twice, SUM(x)'s and AVG(x)'s counts), the FILTER's mask,
    # x (SUM and AVG), y under the FILTER, z.  x and y can hold a NaN.
    assert tuple(attrs[key] for key in LIMB_ROWS) == (13, 6, 6)
    by_k = frame.groupby("k")
    want = pd.DataFrame({
        "cx": by_k["x"].count(), "c": by_k.size(), "sx": by_k["x"].sum(),
        "ax": by_k["x"].mean(),
        "sy": frame[frame["z"] > 0].groupby("k")["y"].sum(),
        "sz": by_k["z"].sum()}).reset_index()
    assert list(got["k"]) == list(want["k"])
    assert (got["cx"] < got["c"]).all()
    for column in ("cx", "c", "sz"):
        assert list(got[column]) == list(want[column])
    for column in ("sx", "ax", "sy"):
        np.testing.assert_allclose(got[column], want[column], rtol=1e-12)
