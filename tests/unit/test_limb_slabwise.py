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
    # the rows go on as they came: a sequence, nothing stacked
    monkeypatch.setattr(
        pk, "segmented_sums_dispatch", lambda *a, **k: called.append(
            (len(a[0]),) + a[0][0].shape) or "sums")

    def never(take):
        raise AssertionError("no slab is built where the rows stack")

    assert pk.segmented_sums_slabwise(never, rows, codes, mask, 1,
                                      classes) == "sums"
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    assert pk.segmented_sums_slabwise(never, rows, codes, mask, 1,
                                      classes) == "sums"
    assert called == [(5, 5000)] * 2


@pytest.mark.parametrize("named, n, fits", [
    ("q1", 5_999_954, True),      # TPC-H Q1 at SF1: 21 rows, 1.0 GB
    ("q1", 1 << 24, False),       # Q1 at SF10 behind its first compaction
    ("q1", 60_002_228, False),    # Q1 at SF10: 10.1 GB
    ("sum", 60_002_228, False),   # one SUM of a float column: 5 rows
    ("count", 60_002_228, True)])  # COUNT(*) alone: the row mask, once
def test_the_stack_fits_where_sf1_runs_and_not_at_sf10(named, n, fits):
    """Reckoned on what the kernel would stack (the distinct rows and the
    indicator rows, in f64), not on the rows named."""
    keep, price = jnp.ones(4, bool), jnp.ones(4, jnp.float64)
    if named == "q1":
        rows, classes, _ = _named_rows("q1", 64, np.random.RandomState(0))
    elif named == "sum":
        rows, classes = [keep, price, keep], ["unit", "float", "unit"]
    else:
        rows, classes = [keep, keep, keep], ["unit"] * 3
    assert pk.stack_fits(rows, classes, n) is fits


# --- a row named twice is summed once (PR 39) --------------------------------

def _sums(path, rows, classes, codes, mask, g):
    """The kernel's two entries over the same named rows."""
    if path == "stacked":
        return pk.segmented_sums_dispatch(rows, codes, mask, g,
                                          row_classes=classes)
    return pk.segmented_sums_slabwise(lambda take: [take(r) for r in rows],
                                      rows, codes, mask, g, classes)


def _each_row_on_its_own(rows, classes, codes, mask, g):
    """The plain reference: every named row summed alone, as before rows
    were merged.  A float row through the limb path as a matrix of one row
    (nothing to merge there), an integer-valued row through the scatter
    oracle, which is exact on it."""
    out = []
    for row, c in zip(rows, classes):
        alone = jnp.asarray(row).astype(jnp.float64)[None, :]
        if c == "float":
            out.append(pk.segmented_sums_fixedpoint(
                alone, codes, mask, g, row_classes=[c], interpret=True)[0])
        else:
            out.append(pk.reference_segmented_sums(alone, codes, mask, g)[0])
    return np.stack([np.asarray(r) for r in out])


def _named_rows(case, n, rng):
    """(rows, classes, (named, summed, indicator rows)) of a case: the same
    object wherever the same row is named, each row in its own dtype."""
    keep = jnp.asarray(rng.rand(n) < 0.95)
    if case == "q1":
        # TPC-H Q1: occupancy, then (value, count) of SUM(qty), SUM(price),
        # SUM(disc_price), SUM(charge), AVG(qty), AVG(price), AVG(disc),
        # COUNT(*); no NULLs, no FILTER: every count row is the row mask
        qty = np.floor(rng.uniform(1, 51, n))
        price = np.round(rng.uniform(900.0, 105_000.0, n), 2)
        disc = np.round(rng.uniform(0.0, 0.1, n), 2)
        charge = price * (1 - disc) * (1 + np.round(rng.uniform(0, .08, n), 2))
        qty, price, disc_price, charge, disc = (
            jnp.where(keep, jnp.asarray(v), 0.0)
            for v in (qty, price, price * (1 - disc), charge, disc))
        rows = [keep]
        for value in (qty, price, disc_price, charge, qty, price, disc):
            rows += [value, keep]
        rows += [keep, keep]
        classes = ["unit"] + ["float", "unit"] * 7 + ["unit", "unit"]
        return rows, classes, (17, 6, 15)
    some = jnp.asarray(rng.rand(n) < 0.6)
    big = jnp.asarray(rng.randint(-2**40, 2**40, n))            # int64
    small = jnp.asarray(rng.randint(-1000, 1000, n).astype(np.int32))
    cents = jnp.asarray(np.round(rng.uniform(-5e9, 5e9, n)))     # f64
    if case == "int_only":
        rows = [keep, big, keep, cents, some, big, keep, small, some]
        classes = ["unit", "int", "unit", "int", "unit", "int", "unit",
                   "int", "unit"]
        # cents is read from floats and keeps its indicator rows
        return rows, classes, (9, 5, 3)
    signed = jnp.asarray(rng.normal(0.0, 1e9, n))
    tiny = jnp.asarray(rng.normal(0.0, 1e-9, n))
    rows = [keep, signed, some, small, keep, signed, some, cents, keep,
            tiny, some, some]
    classes = ["unit", "float", "unit", "int", "unit", "float", "unit",
               "int", "unit", "float", "unit", "unit"]
    return rows, classes, (12, 6, 9)


@pytest.mark.parametrize("path", ["stacked", "slabwise"])
@pytest.mark.parametrize("case", ["q1", "int_only", "mixed"])
def test_rows_named_twice_are_summed_once_bit_for_bit(case, path,
                                                      small_slabs):
    n = 2 * 4096 * 2 + 1234
    rng = np.random.RandomState(11)
    rows, classes, counts = _named_rows(case, n, rng)
    codes = jnp.asarray(rng.randint(0, 6, n).astype(np.int32))
    mask = rows[0]
    assert tuple(pk.limb_row_counts(rows, classes).values()) == counts
    got = np.asarray(_sums(path, rows, classes, codes, mask, 6))
    want = _each_row_on_its_own(rows, classes, codes, mask, 6)
    assert got.shape == want.shape == (len(rows), 6)
    assert np.array_equal(got.view(np.uint64), want.view(np.uint64))


def _frozen_case():
    """Q1's 17 named rows over 9426 table rows, a NaN, both infinities
    in one group and an infinity alone among them: fixed, so that
    ``PARENT_BITS`` stays what the parent (37c3576) returned for it."""
    n = 2 * 4096 * 2 + 1234
    rng = np.random.RandomState(39)
    rows, classes, _ = _named_rows("q1", n, rng)
    codes = rng.randint(0, 6, n).astype(np.int32)
    kept = np.flatnonzero(np.asarray(rows[0]))
    left_out = np.flatnonzero(~np.asarray(rows[0]))
    # (which of the kept table rows, the value, its group), a named row:
    # l_quantity, then l_extendedprice (first slab, last slab)
    values = {1: [(17, np.nan, 1)],
              3: [(-3, np.inf, 2), (4100, -np.inf, 2), (90, np.inf, 4)]}
    for at, places in values.items():
        row = np.asarray(rows[at]).copy()
        for where, value, group in places:
            row[kept[where]], codes[kept[where]] = value, group
        # and one in a row the mask leaves out, which counts for nothing
        row[left_out[at]] = np.nan
        fresh = jnp.asarray(row)
        rows = [fresh if r is rows[at] else r for r in rows]
    return rows, classes, jnp.asarray(codes), rows[0]


#: the sums of ``_frozen_case``'s distinct rows (the row mask, l_quantity,
#: l_extendedprice, the discounted price, the charge, l_discount; six
#: groups each) as the PARENT's ``segmented_sums_dispatch`` returned them
#: for the 17 rows stacked in f64, each beside its own three indicator
#: rows: f64 bit patterns, written by the parent's module and by nothing
#: of this tree, so a change to ``finite_matrix`` or to
#: ``_segmented_sums_limbs`` cannot move both sides of the comparison
PARENT_BITS = [
    "40a5e80000000000 40a57c0000000000 40a6f20000000000 "
    "40a5080000000000 40a64a0000000000 40a51c0000000000",
    "40f152c000000000 7ff8000000000000 40f23cb000000000 "
    "40f0c16000000000 40f1f53000000000 40f0ced000000000",
    "41a19c645b800000 41a14c97dca8f5c3 7ff8000000000000 "
    "41a132133d800000 7ff0000000000000 41a116a7ef3851ec",
    "41a0ba3bc1410625 41a06a88ca017c1c 41a1c56de1b1eb85 "
    "41a0544b8c800d1b 41a0c780ccf74bc7 41a038c322d5b574",
    "41a167a64f4e34db 41a11562506ff866 41a27e435b41b995 "
    "41a0f95518cf9a8d 41a174a0b51ea833 41a0de527106b5d4",
    "406198f5c28f5c29 4061828f5c28f5c3 406297ae147ae148 "
    "4060aae147ae147b 40616ae147ae147b 40611ccccccccccd",
]
#: where each of the 17 named rows is found among the six
PARENT_ROW_OF = [0, 1, 0, 2, 0, 3, 0, 4, 0, 1, 0, 2, 0, 5, 0, 0, 0]


@pytest.mark.parametrize("path", ["stacked", "slabwise"])
def test_the_sums_are_the_bits_the_parent_returned(path, small_slabs):
    rows, classes, codes, mask = _frozen_case()
    got = np.asarray(_sums(path, rows, classes, codes, mask, 6))
    want = np.array([[int(word, 16) for word in line.split()]
                     for line in PARENT_BITS], dtype=np.uint64)
    assert want.shape == (6, 6)
    assert np.array_equal(got.view(np.uint64), want[PARENT_ROW_OF])
    # the case holds what it says: IEEE's answers, in both places named
    for named in (1, 9):
        assert np.isnan(got[named, 1])
    for named in (3, 11):
        assert np.isnan(got[named, 2]) and got[named, 4] == np.inf
    assert np.isfinite(got[[0, 5, 7, 13]]).all()


def test_the_limb_loop_is_handed_the_distinct_rows_only(small_slabs,
                                                        monkeypatch):
    """Q1's 17 named rows reach the limb arithmetic as one matrix of 21:
    five float rows, the row mask and 15 indicator rows."""
    n = 2 * 4096 * 2 + 1234
    rng = np.random.RandomState(5)
    rows, classes, _ = _named_rows("q1", n, rng)
    codes = jnp.asarray(rng.randint(0, 6, n).astype(np.int32))
    seen = []
    limbs = pk._segmented_sums_limbs

    def spy(vals, c, m, g, row_classes, interpret, **kw):
        handed = kw["slab_of"](lambda whole: whole) if vals is None else vals
        seen.append((handed.shape, str(handed.dtype), list(row_classes)))
        return limbs(vals, c, m, g, row_classes, interpret, **kw)

    monkeypatch.setattr(pk, "_segmented_sums_limbs", spy)
    for path in ("stacked", "slabwise"):
        _sums(path, rows, classes, codes, rows[0], 6)
    # distinct rows in the order they are first named: mask, qty, price, ...
    want = ((21, n), "float64", ["unit"] + ["float"] * 5 + ["unit"] * 15)
    assert seen == [want, want]


NONFINITE = {
    "nan": ([np.nan], np.nan),
    "posinf": ([np.inf], np.inf),
    "neginf": ([-np.inf], -np.inf),
    "both": ([np.inf, -np.inf], np.nan),
}


@pytest.mark.parametrize("path", ["stacked", "slabwise"])
@pytest.mark.parametrize("kind", list(NONFINITE))
def test_a_nonfinite_value_in_a_row_named_twice(kind, path, small_slabs):
    """IEEE's answer in the value's group, in both places the row is
    named; nothing of it in another group, in another row, or from a row
    the mask leaves out."""
    values, answer = NONFINITE[kind]
    n = 2 * 4096 * 2 + 77
    rng = np.random.RandomState(13)
    codes = rng.randint(0, 4, n).astype(np.int32)
    keep = rng.rand(n) < 0.9
    price = np.round(rng.uniform(1.0, 1000.0, n), 2)
    other = rng.normal(0.0, 50.0, n)
    # group 2 gets the non-finite values, in the last slab and the first
    places = [n - 9, 5][:len(values)]
    for at, value in zip(places, values):
        price[at], codes[at], keep[at] = value, 2, True
    # and every kind sits in rows the mask leaves out, in every group
    for at, value in zip((40, 41, 42, n - 40),
                         (np.nan, np.inf, -np.inf, np.nan)):
        price[at], other[at], keep[at] = value, value, False
    keep_d, price_d, other_d = (jnp.asarray(v) for v in (keep, price, other))
    rows = [keep_d, price_d, keep_d, other_d, keep_d, price_d, keep_d]
    classes = ["unit", "float", "unit", "float", "unit", "float", "unit"]
    assert pk.limb_row_counts(rows, classes) == {
        "limb_rows_named": 7, "limb_rows_summed": 3,
        "limb_indicator_rows": 6}
    got = np.asarray(_sums(path, rows, classes, jnp.asarray(codes), keep_d,
                           4))
    assert np.array_equal(got[1].view(np.uint64), got[5].view(np.uint64))
    assert np.array_equal(got[1, 2], answer, equal_nan=True)
    finite = np.where(np.isfinite(price), price, 0.0)
    for g in (0, 1, 3):
        sel = keep & (codes == g)
        assert np.isclose(got[1, g], finite[sel].sum(), rtol=1e-12, atol=0)
    sel = [keep & (codes == g) for g in range(4)]
    assert np.allclose(got[3], [other[s].sum() for s in sel], rtol=1e-9)
    assert np.array_equal(got[0], [s.sum() for s in sel])
    for r in (2, 4, 6):
        assert np.array_equal(got[r], got[0])
