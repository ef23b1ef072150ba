"""The limb kernel fed a slab at a time (PR 31): where the value rows are
too many and too long to stack (TPC-H Q1 at SF10), the kernel's loop builds
each slab's rows itself, and the sums are the stacked path's bit for bit."""
import jax.numpy as jnp
import numpy as np
import pytest

from dask_sql_tpu.ops import pallas_kernels as pk


@pytest.fixture
def small_slabs(monkeypatch):
    """The TPU's kernels, interpreted, with slabs of two blocks."""
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    monkeypatch.setattr(pk, "SLAB_EXACT", 2 * pk.BLOCK_EXACT)


def rows_and_classes(n, seed):
    rng = np.random.RandomState(seed)
    price = np.round(rng.uniform(900.0, 105_000.0, n), 2)
    price[[3, n - 5]] = [np.nan, np.inf]
    price[n // 2] = -np.inf
    signed = rng.normal(0.0, 1e9, n)
    cents = np.round(rng.uniform(-5e6, 5e6, n))
    ones = (rng.rand(n) < 0.7).astype(np.float64)
    rows = [jnp.asarray(r) for r in (ones, price, signed, cents, ones)]
    return rows, ["unit", "float", "float", "int", "unit"]


@pytest.mark.parametrize("n", [2 * 4096 * 3, 2 * 4096 * 2 + 1234])
def test_slabwise_sums_are_the_stacked_sums_bit_for_bit(n, small_slabs):
    rows, classes = rows_and_classes(n, 7)
    rng = np.random.RandomState(8)
    codes = jnp.asarray(rng.randint(0, 6, n).astype(np.int32))
    mask = rng.rand(n) < 0.9
    # an outlier the mask filters out must not coarsen the grid
    mask[10] = False
    rows[2] = rows[2].at[10].set(1e300)
    mask = jnp.asarray(mask)
    stacked = pk.segmented_sums_dispatch(jnp.stack(rows), codes, mask, 6,
                                         row_classes=classes)
    taken = []

    def rows_of(take):
        slab = [take(r) for r in rows]
        taken.append(slab[0].shape)
        return slab

    slabwise = pk.segmented_sums_slabwise(rows_of, rows, codes, mask, 6,
                                          classes)
    assert taken == [(pk.SLAB_EXACT,)]  # one traced slab body, looped
    assert slabwise.shape == stacked.shape == (5, 6)
    assert np.array_equal(np.asarray(slabwise), np.asarray(stacked),
                          equal_nan=True)
    # and both are the plain sums, non-finite values as IEEE has them
    want = np.asarray(pk.reference_segmented_sums(jnp.stack(rows), codes,
                                                  mask, 6))
    got = np.asarray(slabwise)
    assert np.array_equal(np.isnan(got), np.isnan(want))
    finite = np.isfinite(want)
    assert np.array_equal(got[~finite & ~np.isnan(want)],
                          want[~finite & ~np.isnan(want)])
    assert np.allclose(got[finite], want[finite], rtol=1e-13, atol=0)
    assert np.array_equal(got[[0, 3, 4]], want[[0, 3, 4]])  # exact rows


def test_one_slab_or_no_tpu_takes_the_stacked_path(monkeypatch):
    rows, classes = rows_and_classes(5000, 3)
    codes = jnp.zeros(5000, jnp.int32)
    mask = jnp.ones(5000, bool)
    called = []
    monkeypatch.setattr(pk, "segmented_sums_dispatch",
                        lambda *a, **k: called.append(a[0].shape) or "sums")

    def never(take):
        raise AssertionError("no slab is built where the rows stack")

    assert pk.segmented_sums_slabwise(never, rows, codes, mask, 1,
                                      classes) == "sums"
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    assert pk.segmented_sums_slabwise(never, rows, codes, mask, 1,
                                      classes) == "sums"
    assert called == [(5, 5000)] * 2


@pytest.mark.parametrize("rows, n, fits", [
    (17, 5_999_954, True),      # TPC-H Q1 at SF1: 1.6 GB, one matrix
    (17, 1 << 24, False),       # Q1 at SF10 behind its first compaction
    (17, 60_002_228, False),    # Q1 at SF10
    (3, 60_002_228, False),     # three aggregate rows of SF10's lineitem
    (2, 60_002_228, True)])
def test_the_stack_fits_where_sf1_runs_and_not_at_sf10(rows, n, fits):
    assert pk.stack_fits(rows, n) is fits
