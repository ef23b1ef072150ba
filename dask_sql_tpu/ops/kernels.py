"""Shared device kernel primitives: key factorization, dictionary unification,
civil-date arithmetic.

These are the building blocks the physical operators compose: SQL groupby/
join/sort all reduce to "turn key columns into dense integer codes, then run
integer kernels on device".  The reference delegates the equivalents to
pandas/dask internals (hash-based groupby/merge); here they are explicit
XLA-friendly array programs.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..table import Column
from ..types import SqlType


# ---------------------------------------------------------------------------
# factorization: columns -> dense int codes
# ---------------------------------------------------------------------------

def unify_string_codes(cols: List[Column]) -> List[jax.Array]:
    """Re-code string columns onto their sorted dictionary union.

    The union dictionary is sorted, so code order == lexicographic order:
    equality AND comparisons on the returned codes are string-correct.
    """
    dicts = [c.dictionary.astype(str) for c in cols]
    union = np.unique(np.concatenate(dicts))
    out = []
    for c, d in zip(cols, dicts):
        remap = np.searchsorted(union, d).astype(np.int64)
        out.append(jnp.take(jnp.asarray(remap), jnp.clip(c.data, 0, len(d) - 1)))
    return out


def comparable_data(col: Column) -> jax.Array:
    """Numeric array whose order matches SQL ordering for this column."""
    if col.stype.is_string:
        return col.dict_ranks().data.astype(jnp.int64)
    if col.data.dtype == jnp.bool_:
        return col.data.astype(jnp.int64)
    return col.data


def factorize_columns(cols: List[Column], *, null_as_group: bool = True
                      ) -> Tuple[jax.Array, jax.Array, int]:
    """Multi-column factorize: rows -> dense codes 0..G-1.

    Returns (codes, representative_row_per_group, num_groups).  Rows where any
    key is NULL either form their own groups keyed by the null pattern
    (``null_as_group=True``, SQL GROUP BY semantics — reference
    physical/utils/groupby.py:8-34) or get code -1 (join-key semantics where
    NULL never matches, reference join.py:224-235).
    """
    n = len(cols[0])
    per_col_codes = []
    for c in cols:
        data = comparable_data(c)
        if c.mask is not None:
            # distinct value for nulls: use code 0 for null, shift others by 1
            uniq, inv = jnp.unique(jnp.where(c.mask, data, data.min() if n else 0),
                                   return_inverse=True)
            inv = jnp.where(c.mask, inv + 1, 0)
        else:
            uniq, inv = jnp.unique(data, return_inverse=True)
            inv = inv + 1
        per_col_codes.append(inv.reshape(-1).astype(jnp.int64))

    combined = per_col_codes[0]
    for c in per_col_codes[1:]:
        m = int(c.max()) + 1 if n else 1
        combined = combined * m + c

    uniq_codes, codes = jnp.unique(combined, return_inverse=True)
    codes = codes.reshape(-1)
    num_groups = int(uniq_codes.shape[0])

    if not null_as_group:
        any_null = jnp.zeros(n, dtype=bool)
        for c in cols:
            if c.mask is not None:
                any_null = any_null | ~c.mask
        codes = jnp.where(any_null, -1, codes)

    # representative (first) row per group
    first = jnp.full(num_groups, n, dtype=jnp.int64)
    valid = codes >= 0
    first = first.at[jnp.where(valid, codes, 0)].min(
        jnp.where(valid, jnp.arange(n), n))
    return codes, first, num_groups


def join_key_codes(left: List[Column], right: List[Column],
                   null_equal: bool = False, variant: str = "hash"
                   ) -> Tuple[jax.Array, jax.Array]:
    """Factorize left+right key columns on a shared domain.

    Returns int64 codes for each side; -1 marks rows with NULL keys (never
    match, reference join.py:220-235).  ``null_equal=True`` switches to
    set-operation equality (SQL "IS NOT DISTINCT FROM"): NULL gets its own
    shared code and matches NULL — INTERSECT/EXCEPT require it (a row
    (NULL, 'x') present on both sides IS in the intersection).

    ``variant="dense"`` (stats-driven, runtime/statistics.py): a single
    integer key pair skips the shared-domain unique/sort entirely —
    ``codes = key - min`` is already a valid shared coding (equal keys get
    equal codes, NULL keeps its sentinel).  Falls back to the factorize
    path when not applicable, so the flag can never change results.
    """
    if variant == "dense":
        out = _dense_join_codes(left, right, null_equal)
        if out is not None:
            return out
    nl = len(left[0]) if left else 0
    combined_cols = []
    for lc, rc in zip(left, right):
        if lc.stype.is_string or rc.stype.is_string:
            lcodes, rcodes = unify_string_codes([lc, rc])
            data = jnp.concatenate([lcodes, rcodes])
        else:
            ldata = lc.data
            rdata = rc.data
            dt = jnp.promote_types(ldata.dtype, rdata.dtype)
            data = jnp.concatenate([ldata.astype(dt), rdata.astype(dt)])
        mask = None
        if lc.mask is not None or rc.mask is not None:
            lm = lc.valid_mask()
            rm = rc.valid_mask()
            mask = jnp.concatenate([lm, rm])
        combined_cols.append((data, mask))

    per = []
    for data, mask in combined_cols:
        uniq, inv = jnp.unique(data, return_inverse=True)
        inv = inv.reshape(-1).astype(jnp.int64)
        if mask is not None:
            if null_equal:
                # NULL becomes code 0, one shared bucket; real values shift
                inv = jnp.where(mask, inv + 1, 0)
            else:
                inv = jnp.where(mask, inv, -1)
        per.append(inv)

    combined = per[0]
    bad = per[0] < 0
    for c in per[1:]:
        m = int(c.max()) + 1 if c.shape[0] else 1
        m = max(m, 1)
        combined = combined * m + jnp.maximum(c, 0)
        bad = bad | (c < 0)
    combined = jnp.where(bad, -1, combined)
    return combined[:nl], combined[nl:]


def _dense_join_codes(left: List[Column], right: List[Column],
                      null_equal: bool):
    """Direct shared coding for one integer key pair: ``code = key - lo``
    (``+1`` with NULL as shared code 0 under ``null_equal``).  No unique,
    no sort — two reductions for ``lo`` are the only synced work.  None
    when not applicable (multi-column, strings, floats, empty)."""
    if len(left) != 1 or len(right) != 1:
        return None
    lc, rc = left[0], right[0]
    for c in (lc, rc):
        if c.stype.is_string or not jnp.issubdtype(c.data.dtype,
                                                   jnp.integer):
            return None
    nl, nr = len(lc), len(rc)
    if nl + nr == 0:
        return None
    imax = jnp.iinfo(jnp.int64).max
    imin = jnp.iinfo(jnp.int64).min
    los, his = [], []
    for c in (lc, rc):
        if not len(c):
            continue
        data = c.data.astype(jnp.int64)
        if c.mask is not None:
            los.append(int(jnp.where(c.mask, data, imax).min()))
            his.append(int(jnp.where(c.mask, data, imin).max()))
        else:
            los.append(int(data.min()))
            his.append(int(data.max()))
    los = [v for v in los if v != imax]
    his = [v for v in his if v != imin]
    if not los or not his:
        return None  # all keys NULL on both sides
    lo, hi = min(los), max(his)
    if hi - lo >= 2 ** 62:
        # adversarial int64 spread: key - lo could overflow; the
        # factorize path handles those (rare) layouts
        return None
    shift = 1 if null_equal else 0
    out = []
    for c in (lc, rc):
        codes = c.data.astype(jnp.int64) - lo + shift
        if c.mask is not None:
            codes = jnp.where(c.mask, codes, 0 if null_equal else -1)
        out.append(codes)
    return out[0], out[1]


# ---------------------------------------------------------------------------
# compaction (filter -> gather indices)
# ---------------------------------------------------------------------------

def mask_to_indices(mask: jax.Array) -> jax.Array:
    """Boolean mask -> row indices (host-synced size; eager execution only)."""
    count = int(mask.sum())
    return jnp.nonzero(mask, size=count)[0]


#: ``compact_indices``' two formulations, chosen from the static n and
#: ``cap`` alone (``compact_slab_rows``).  A v5e alone in a program
#: (PERF.md section 6, PR 37; ms):
#:
#:     n           cap         whole-array sort    slab form
#:     60 002 228  1 048 576   180.3               34.1
#:     60 002 228  2 097 152   180.4               51.3
#:      5 999 954     65 536    12.3                3.1
#:      5 999 954    262 144    12.1                4.4
#:      5 999 954  2 097 152    12.2               18.3
#:      1 500 000     65 536     3.3                1.8
#:      1 500 000    524 288     3.3                5.2
#:      2 097 152    262 144     3.5                3.3
#:        524 288    131 072     1.5                2.0
#:
#: The sort is a network of log2(n) (log2(n) + 1) / 2 passes over all n
#: positions (351 at 60 M), the same at every ``cap``.  The slab form is
#: the slabs' sorts (36 passes at 256 rows, none across HBM: 12.2 ms at
#: 60 M), two scatters of n / rows marks (10 ns a mark) and, growing with
#: ``cap``, two running sums and one gather (8 ns a slot together).  So it
#: wins where ``cap`` is a small share of many rows: the two cross near
#: cap 1.1 M at n = 6 M (n / 5) and near 20 M at 60 M (n / 3), and from
#: 2 M rows down the sort is 3.5 ms or less, of which the slab form saves
#: 1.5 at best.  Rows a slab 128 / 256 / 512 / 1024 / 2048 at n = 60 M,
#: cap 1 048 576: 33.7 / 34.1 / 36.0 / 38.7 / 42.4 ms.
COMPACT_SLAB_ROWS = 256
COMPACT_SLAB_ROWS_MIN = 1 << 22
COMPACT_SLAB_CAP_SHARE = 8


def compact_slab_rows(n: int, cap: int) -> int:
    """The rows of a slab where ``compact_indices(mask[n], cap)`` sorts
    inside slabs, 0 where it sorts all n positions: static, the one place
    that chooses (readings beside ``COMPACT_SLAB_ROWS``)."""
    if n >= COMPACT_SLAB_ROWS_MIN and cap * COMPACT_SLAB_CAP_SHARE <= n:
        return COMPACT_SLAB_ROWS
    return 0


def compact_indices(mask: jax.Array, cap: int) -> Tuple[jax.Array, jax.Array]:
    """(positions of the first ``cap`` set rows of ``mask``, number of set
    rows): element for element ``jnp.nonzero(mask, size=cap,
    fill_value=0)[0]`` (ascending, slots past the count 0; with more than
    ``cap`` rows set, the first ``cap``) and ``mask.sum()``, traceable.

    Not ``jnp.nonzero(size=)`` itself: JAX builds that as a ``bincount``
    of the mask's running sum, a scatter-add of all n rows into ``cap``
    bins whatever the selectivity, and a TPU serializes a scatter's
    updates: 495 ms at n = 6.0 M on a v5e, at every cap (PR 26).  Here
    the set rows' positions are sorted to the front: inside slabs where
    ``compact_slab_rows`` says so (``_compact_in_slabs``), else by one
    single-operand sort of all n positions, which is 12.2 ms at n = 6.0 M
    and 180 ms at 60 M (TPC-H SF10's lineitem), at every cap.  Positions
    are int32 while n allows.
    """
    n = mask.shape[0]
    if cap > n:
        raise ValueError(f"compact_indices: cap {cap} over {n} rows")
    itype = jnp.int32 if n < 2 ** 31 else jnp.int64
    rows = compact_slab_rows(n, cap)
    if rows:
        return _compact_in_slabs(mask, cap, rows, itype)
    pos = jnp.sort(jnp.where(mask, jnp.arange(n, dtype=itype), n))[:cap]
    return jnp.where(pos < n, pos, 0), mask.sum()


def _compact_in_slabs(mask: jax.Array, cap: int, rows: int,
                      itype) -> Tuple[jax.Array, jax.Array]:
    """``compact_indices`` by slabs of ``rows`` rows: each slab's set
    lanes are sorted to its front (a sort along the slab, which stays in
    vector memory), the slabs' running counts say where a slab's rows
    start among the output's slots, and a slot finds its slab without a
    search: every slab leaves a mark at the slot its rows end at, and the
    marks' running sum over the slots is the number of slabs that ended
    before.  The only work that grows with ``cap`` is two running sums
    and the one gather out of the sorted lanes."""
    n = mask.shape[0]
    slabs = -(-n // rows)
    m = jnp.pad(mask, (0, slabs * rows - n)).reshape(slabs, rows)
    lane = jnp.arange(rows, dtype=jnp.int32)
    front = jnp.sort(jnp.where(m, lane, rows), axis=-1)
    counts = m.sum(-1, dtype=itype)
    ends = jnp.cumsum(counts)
    end_slot = jnp.minimum(ends, cap)   # slot cap is cut off: overflow

    def over_slots(ended):
        """Per slot, the sum of ``ended`` over the slabs that end at or
        before it (a scatter of one update a slab, not a row)."""
        at_end = jnp.zeros(cap + 1, itype).at[end_slot].add(
            ended, indices_are_sorted=True)
        return jnp.cumsum(at_end[:cap])

    slot = jnp.arange(cap, dtype=itype)
    # past the count every slab has ended: the last one's lanes, cut below
    slab = jnp.minimum(over_slots(jnp.ones_like(counts)), slabs - 1)
    rank = jnp.minimum(slot - over_slots(counts), rows - 1)
    start = slab * rows
    pos = start + front.reshape(-1)[start + rank]
    return jnp.where(slot < ends[-1], pos, 0), ends[-1]


def _u32_channels(key: jax.Array) -> List[jax.Array]:
    """Order-preserving uint32 channels of one sort key, most significant
    first: rows compare under the channels, taken in turn, as they do under
    ``key``.  Integers of up to 32 bits are one channel (biased to
    unsigned), wider ones two.  A float key is three float32 terms, each
    the rounded remainder of the last (together they hold a finite
    float64's 53 bits as long as its terms stay in float32's range, which
    on a TPU, where a float64 is a pair of float32, is every value there
    is); NaN is the caller's to flag and zero beforehand (``canon_f64``)."""
    sign = np.uint32(1 << 31)
    if jnp.issubdtype(key.dtype, jnp.floating):
        rest = key.astype(jnp.float64)
        out = []
        for _ in range(3):
            term = rest.astype(jnp.float32)
            rest = jnp.where(jnp.isfinite(term),
                             rest - term.astype(jnp.float64), 0.0)
            # -0.0 to +0.0 (not by adding 0.0: XLA folds that away)
            bits = jax.lax.bitcast_convert_type(
                jnp.where(term == 0, jnp.float32(0.0), term), jnp.uint32)
            out.append(jnp.where(bits >= sign, ~bits, bits | sign))
        return out
    if key.dtype.itemsize <= 4:
        return [jax.lax.bitcast_convert_type(key.astype(jnp.int32),
                                             jnp.uint32) ^ sign]
    wide = key.astype(jnp.int64)
    high = jax.lax.bitcast_convert_type((wide >> 32).astype(jnp.int32),
                                        jnp.uint32) ^ sign
    return [high, wide.astype(jnp.uint32)]


def lexsort_by_passes(keys: Sequence[jax.Array]) -> jax.Array:
    """The permutation ``jnp.lexsort(keys)`` gives (the LAST key is the
    primary one; stable), from one single-key sort run once per 32-bit
    channel of the keys, least significant first (``_u32_channels``).

    ``jnp.lexsort`` is one sort with every key a key operand, and XLA:TPU's
    compile time for a sort explodes with its key channels: TPC-H Q3's
    ORDER BY (five keys, eight channels) takes 169 s at 16 384 rows for a
    described v5e, a (uint32 key, int32 row id) sort 17 s at 65 536 rows
    and 34 s at six million (PR 27).  The loop holds that one sort, however
    many keys there are; each pass gathers its channel into the order so
    far and sorts it, stably, with the row ids."""
    channels = [c for key in keys for c in reversed(_u32_channels(key))]
    n = channels[0].shape[0]
    stack = jnp.stack(channels)

    def one_pass(i, perm):
        return jax.lax.sort((stack[i][perm], perm), num_keys=1,
                            is_stable=True)[1]

    return jax.lax.fori_loop(0, len(channels), one_pass,
                             jnp.arange(n, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# civil-date arithmetic (Howard Hinnant's algorithms, pure integer ops)
# ---------------------------------------------------------------------------

US_PER_DAY = 86_400_000_000


def civil_from_days(z: jax.Array):
    """days-since-epoch -> (year, month, day), vectorized integer math."""
    z = z.astype(jnp.int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = jnp.floor_divide(doe - doe // 1460 + doe // 36524 - doe // 146096, 365)
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = jnp.floor_divide(5 * doy + 2, 153)
    d = doy - jnp.floor_divide(153 * mp + 2, 5) + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m, d


def days_from_civil(y: jax.Array, m: jax.Array, d: jax.Array) -> jax.Array:
    y = y.astype(jnp.int64) - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = jnp.floor_divide(153 * mp + 2, 5) + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def timestamp_to_days(us: jax.Array) -> jax.Array:
    return jnp.floor_divide(us.astype(jnp.int64), US_PER_DAY)


def timestamp_time_of_day_us(us: jax.Array) -> jax.Array:
    return us.astype(jnp.int64) - timestamp_to_days(us) * US_PER_DAY


def extract_field(field: str, days: jax.Array, tod_us: Optional[jax.Array]):
    """EXTRACT implementation over (days, time-of-day) pair.

    ``tod_us`` is None for DATE columns.  Field names follow Calcite/postgres
    (reference rex op: call.py:474-513).
    """
    y, m, d = civil_from_days(days)
    f = field.upper()
    if f == "YEAR":
        return y
    if f == "MONTH":
        return m
    if f == "DAY" or f == "DAYOFMONTH":
        return d
    if f == "QUARTER":
        return (m - 1) // 3 + 1
    if f == "DECADE":
        return jnp.floor_divide(y, 10)
    if f == "CENTURY":
        return jnp.floor_divide(y + 99, 100)
    if f == "MILLENNIUM":
        return jnp.floor_divide(y + 999, 1000)
    if f in ("DOW", "DAYOFWEEK"):
        # postgres DOW: 0=Sunday..6=Saturday ; epoch day 0 = Thursday(4)
        return jnp.mod(days + 4, 7)
    if f == "ISODOW":
        return jnp.mod(days + 3, 7) + 1
    if f in ("DOY", "DAYOFYEAR"):
        jan1 = days_from_civil(y, jnp.ones_like(m), jnp.ones_like(d))
        return days - jan1 + 1
    if f == "WEEK":
        # ISO week number
        isodow = jnp.mod(days + 3, 7) + 1
        thursday = days - isodow + 4
        ty, _, _ = civil_from_days(thursday)
        jan1 = days_from_civil(ty, jnp.ones_like(m), jnp.ones_like(d))
        return jnp.floor_divide(thursday - jan1, 7) + 1
    if f == "EPOCH":
        base = days.astype(jnp.int64) * 86400
        if tod_us is not None:
            base = base + tod_us // 1_000_000
        return base
    if tod_us is None:
        tod_us = jnp.zeros_like(days, dtype=jnp.int64)
    if f == "HOUR":
        return tod_us // 3_600_000_000
    if f == "MINUTE":
        return (tod_us // 60_000_000) % 60
    if f == "SECOND":
        return (tod_us // 1_000_000) % 60
    if f == "MILLISECOND":
        return (tod_us // 1000) % 60_000
    if f == "MICROSECOND":
        return tod_us % 60_000_000
    raise NotImplementedError(f"EXTRACT field {field}")


def trunc_date(unit: str, days: jax.Array, tod_us: Optional[jax.Array]):
    """FLOOR(ts TO unit): returns (days, tod_us)."""
    u = unit.upper()
    y, m, d = civil_from_days(days)
    one = jnp.ones_like(m)
    zeros = None if tod_us is None else jnp.zeros_like(tod_us)
    if u == "YEAR":
        return days_from_civil(y, one, one), zeros
    if u == "QUARTER":
        qm = ((m - 1) // 3) * 3 + 1
        return days_from_civil(y, qm, one), zeros
    if u == "MONTH":
        return days_from_civil(y, m, one), zeros
    if u == "WEEK":
        isodow = jnp.mod(days + 3, 7) + 1
        return days - (isodow - 1), zeros
    if u == "DAY":
        return days, zeros
    if tod_us is None:
        return days, None
    if u == "HOUR":
        return days, (tod_us // 3_600_000_000) * 3_600_000_000
    if u == "MINUTE":
        return days, (tod_us // 60_000_000) * 60_000_000
    if u == "SECOND":
        return days, (tod_us // 1_000_000) * 1_000_000
    if u == "MILLISECOND":
        return days, (tod_us // 1000) * 1000
    raise NotImplementedError(f"FLOOR unit {unit}")


# ---------------------------------------------------------------------------
# trace-safe total-order keys (shared by the compiled executor and windows):
# no 64-bit bitcasts (the TPU X64 rewrite lacks them); floats stay raw f64
# with NULL/NaN class flags
# ---------------------------------------------------------------------------

_INT64_MIN = jnp.int64(-(2**63))


def float_class(x: jax.Array, null: Optional[jax.Array]) -> jax.Array:
    """0 = NULL (first), 1 = ordinary value, 2 = NaN (last)."""
    cls = jnp.where(jnp.isnan(x), jnp.int8(2), jnp.int8(1))
    if null is not None:
        cls = jnp.where(null, jnp.int8(0), cls)
    return cls


def canon_f64(x: jax.Array) -> jax.Array:
    """Canonical f64 sort/equality key: -0.0 -> +0.0, NaN -> 0 (class flag
    disambiguates). No i64 bitcast — the TPU X64 rewrite can't do it."""
    x = x.astype(jnp.float64) + 0.0
    return jnp.where(jnp.isnan(x), 0.0, x)




def decimal_unscale(s_int: jax.Array, scale: int) -> jax.Array:
    """Correctly-rounded ``s_int / 10**scale`` under jit.

    XLA rewrites division by a constant into multiplication by its (inexact)
    reciprocal, which mis-rounds the final decimal result by one ulp
    (observed on XLA:CPU: 2505363390/100 -> ...3633.900000002). Splitting
    into an exact integer quotient plus a sub-unit remainder keeps any
    reciprocal error far below the result's rounding granularity.
    """
    if scale == 0:
        return s_int.astype(jnp.float64)
    f = 10 ** scale
    q = s_int // f
    r = s_int - q * f
    return q.astype(jnp.float64) + r.astype(jnp.float64) / float(f)


def orderable_int64(x: jax.Array) -> jax.Array:
    """int64 key for non-float comparable data (ints, bools, dict ranks,
    dates) — comparable_data already made the order numeric."""
    return x.astype(jnp.int64)


def key_parts(cols: List[Column]) -> List[Tuple[jax.Array, Optional[jax.Array]]]:
    """(data, optional class flag) per key column for grouping/dedup.

    data is canonical f64 for float columns (no 64-bit bitcast on TPU) or
    int64 with a NULL sentinel otherwise; the int8 class flag orders
    NULL(0) < values(1) < NaN(2) and disambiguates sentinel collisions.
    flag is None for non-nullable integer-like keys — nothing to
    disambiguate, and every flag array is one more lexsort operand over
    the whole stream. Equality of (data, flag) == SQL group equality
    (-0.0 == +0.0, NaNs grouped together, NULLs grouped together).
    """
    out = []
    for c in cols:
        raw = comparable_data(c)
        null = (~c.mask) if c.mask is not None else None
        if jnp.issubdtype(raw.dtype, jnp.floating):
            d = canon_f64(raw)
            flag = float_class(raw, null)
            if null is not None:
                d = jnp.where(null, 0.0, d)
        else:
            d = orderable_int64(raw)
            if null is not None:
                d = jnp.where(null, _INT64_MIN, d)
                flag = jnp.where(null, jnp.int8(0), jnp.int8(1))
            else:
                flag = None
        out.append((d, flag))
    return out




def append_lexsort_operands(arrays: list, parts) -> None:
    """Append key-part lexsort operands (data + optional class flag) in
    least-to-most-significant order for ``jnp.lexsort`` consumers."""
    for d, flag in reversed(parts):
        arrays.append(d)
        if flag is not None:
            arrays.append(flag)


def ieee_reassemble(clean: jax.Array, nan_c: jax.Array, pos_c: jax.Array,
                    neg_c: jax.Array) -> jax.Array:
    """Recombine a sanitized sum with non-finite indicator counts."""
    out = jnp.where(pos_c > 0, jnp.inf, clean)
    out = jnp.where(neg_c > 0, -jnp.inf, out)
    out = jnp.where((pos_c > 0) & (neg_c > 0), jnp.nan, out)
    return jnp.where(nan_c > 0, jnp.nan, out)
