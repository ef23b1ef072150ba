"""Hashing kernels the compiled tier traces: 64-bit row hashes of key
columns, the open-addressing hash table (joins, group codes), and group
codes for statically enumerable key domains.

Pure ``jnp`` over columns and arrays: no plan node, no tracer state.  The
tracer (``physical/compiled.py``) decides which of them an operator takes
and owns the flags they report through; the eager twins live in
``ops/join.py`` and ``ops/groupby.py``.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..table import Column, dict_sort_order
from .kernels import (canon_f64, key_parts as _key_parts, orderable_int64,
                      unify_string_codes)

_U64_MAX = np.uint64(0xFFFFFFFFFFFFFFFF)
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def _f64_hash_part(x: jax.Array) -> jax.Array:
    """Deterministic u64 encoding of f64 for hashing without a 64-bit
    bitcast: double-float (hi, lo) f32 split, each bitcast to i32 (supported
    on TPU). ~48 mantissa bits — lossy encodings only add hash collisions,
    which the join's collision flag catches; equality is verified on raw
    values."""
    x = canon_f64(x)
    hi = x.astype(jnp.float32)
    lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
    hi_b = jax.lax.bitcast_convert_type(hi, jnp.int32).astype(jnp.uint64)
    lo_b = jax.lax.bitcast_convert_type(lo, jnp.int32).astype(jnp.uint64)
    return (hi_b << np.uint64(32)) | (lo_b & np.uint64(0xFFFFFFFF))


def _mix64(z: jax.Array) -> jax.Array:
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _hash_group_parts(parts) -> jax.Array:
    """Mix all group-key parts (data + class flags) into one u64 per row.

    Float parts ride the lossy double-float encoding (_f64_hash_part);
    any loss only ever ADDS collisions, which the caller detects against
    the raw parts and routes to the eager fallback."""
    h = jnp.full(parts[0][0].shape, _GOLDEN, dtype=jnp.uint64)
    for d, flag in parts:
        if jnp.issubdtype(d.dtype, jnp.floating):
            hp = _f64_hash_part(d)
        else:
            hp = d.astype(jnp.uint64)
        h = _mix64(h + hp + _GOLDEN)
        if flag is not None:
            h = _mix64(h + flag.astype(jnp.uint64) + _GOLDEN)
    return h


STATIC_DOMAIN_CAP = 4096


def _try_static_codes(cols: List[Column]):
    """Direct group codes when every key has a statically-enumerable domain
    (dictionary-encoded strings, booleans). Returns (codes[n] int64 in
    [0, domain), domain, key_meta) or None; key_meta carries per-key
    (size, nullable) so slots decode back to key values without touching
    the data. Code order == eager group order (NULL slot first, then
    dictionary rank order)."""
    domain = 1
    parts: List[Tuple[jax.Array, int]] = []
    key_meta: List[Tuple[int, bool]] = []
    for c in cols:
        nullable = c.mask is not None
        if c.stype.is_string:
            size = len(c.dictionary)
            code = c.dict_ranks().data.astype(jnp.int64)
        elif c.data.dtype == jnp.bool_:
            size = 2
            code = c.data.astype(jnp.int64)
        else:
            return None
        if nullable:
            code = jnp.where(c.mask, code + 1, 0)
            size += 1
        size = max(size, 1)
        domain *= size
        if domain > STATIC_DOMAIN_CAP:
            return None
        parts.append((code, size))
        key_meta.append((size, nullable))
    combined = parts[0][0]
    for code, size in parts[1:]:
        combined = combined * size + code
    return combined, domain, key_meta


def _decode_static_keys(cols: List[Column], key_meta, domain: int
                        ) -> List[Column]:
    """Group-key output columns straight from the slot index: slot g encodes
    (rank+null) digits in mixed radix, so the key values are arithmetic on
    ``arange(domain)`` plus a static rank->dictionary-code gather — the row
    data is never touched."""
    g = jnp.arange(domain, dtype=jnp.int64)
    stride = domain
    out: List[Column] = []
    for c, (size, nullable) in zip(cols, key_meta):
        stride //= size
        code = (g // stride) % size
        mask = None
        if nullable:
            mask = code != 0
            code = jnp.maximum(code - 1, 0)
        if c.stype.is_string:
            # code is a sort RANK; order[rank] = dictionary index
            order = dict_sort_order(c.dictionary)
            data = jnp.take(jnp.asarray(order.astype(np.int32)), code)
            out.append(Column(data, c.stype, mask, c.dictionary))
        else:
            out.append(Column(code.astype(jnp.bool_), c.stype, mask))
    return out


def _join_key_parts(lcols: List[Column], rcols: List[Column]):
    """Per-key (hash part u64, raw verify array) on a shared domain.

    Hash parts may be lossy for f64 (double-float encoding); match
    verification always compares the raw arrays, so a lossy hash can only
    add collisions (caught by the collision flag), never wrong matches.
    """
    lparts, rparts = [], []
    for lc, rc in zip(lcols, rcols):
        if lc.stype.is_string or rc.stype.is_string:
            la, ra = unify_string_codes([lc, rc])
            la, ra = la.astype(jnp.int64), ra.astype(jnp.int64)
            lh, rh = la.astype(jnp.uint64), ra.astype(jnp.uint64)
        else:
            dt = jnp.promote_types(lc.data.dtype, rc.data.dtype)
            la = lc.data.astype(dt)
            ra = rc.data.astype(dt)
            if jnp.issubdtype(dt, jnp.floating):
                # verify arrays keep NaN as NaN (NaN joins nothing, matching
                # the eager path); only the hash canonicalizes NaN, and the
                # resulting extra collisions trip the conservative flags
                la = la.astype(jnp.float64) + 0.0
                ra = ra.astype(jnp.float64) + 0.0
                lh, rh = _f64_hash_part(la), _f64_hash_part(ra)
            else:
                la, ra = orderable_int64(la), orderable_int64(ra)
                lh, rh = la.astype(jnp.uint64), ra.astype(jnp.uint64)
        lparts.append((lh, la))
        rparts.append((rh, ra))
    return lparts, rparts


def _hash_parts(parts, key_valid: jax.Array) -> jax.Array:
    h = jnp.full(parts[0][0].shape, _GOLDEN, dtype=jnp.uint64)
    for hp, _ in parts:
        h = _mix64(h + hp + _GOLDEN)
    h = jnp.where(h == _U64_MAX, _U64_MAX - np.uint64(1), h)
    return jnp.where(key_valid, h, _U64_MAX)


def _keys_valid(cols: List[Column], row_valid: Optional[jax.Array]) -> jax.Array:
    v = jnp.ones(len(cols[0]), dtype=bool) if row_valid is None else row_valid
    for c in cols:
        if c.mask is not None:
            v = v & c.mask
    return v


# ---------------------------------------------------------------------------
# vectorized open-addressing hash table: joins and group-bys on every
# backend (on a TPU, a join above ``compiled.SORT_ROWS_MAX`` probe rows).
#
# On XLA:CPU at 600k rows a u64 argsort costs ~354 ms and
# searchsorted(method='sort') ~751 ms where gathers, scatters and segment_sum
# cost ~1-2 ms; for a TPU a sort of millions of rows compiles for minutes.
# So the table is built with whole-array scatter rounds, no sort: each round,
# still-unresolved rows try to claim an EMPTY slot (scatter-min of row ids),
# and every row whose round slot now holds an equal-hash resident adopts that
# resident.  All rows of one key resolve together to one slot whose resident
# is the key's first row.  A lax.while_loop runs only as many rounds as the
# worst key chain needs (~log(keys)/log(1/load)).  u64 hash collisions
# between DISTINCT raw keys are detected by the caller comparing raw key
# parts against the resident's and routed to the runtime eager-fallback flag.
# ---------------------------------------------------------------------------

_HASH_MAX_ROUNDS = 64


#: What a hash table takes a slot (the u64 claim and the int32 row id of
#: ``_hash_table_insert``), and the most a table takes while halving it
#: keeps its load factor at or under a half.  SF1's largest (1.5 M build
#: rows, 2^25 slots, 403 MB) lies under it, so no table there changes; a
#: build side of 15 M rows (TPC-H SF10's orders) asks 2^28 slots at 16x,
#: 3.2 GB beside 7.8 GB of resident columns, and gets 2^26 (805 MB), which
#: still direct-addresses its orderkeys: their span is four times the rows.
_SLOT_BYTES = 12
_TABLE_BYTES_MAX = 1 << 30


#: The most slots a table sized from its key's span may have for each row
#: of the larger of the probe side and the table it replaces: what a fill of
#: the larger table may cost beside the probe it saves.  Priced on a TPU v5e
#: (PERF.md section 6, PR 42; ``joins.hash_table`` alone, medians of seven):
#: a slot costs 0.045 ns (131 072 x 2 097 152 rows: 65.3 ms at 2^24 slots,
#: 67.6 at 2^26), a probe row that loops 46 to 110 ns more than one that is
#: direct-addressed, so the fill pays up to 1 000 slots a row: 1 024 x
#: 65 536 rows over a span of 2^26 take 4.50 ms at 2^14 slots and 4.55 at
#: 2^26, a tie in time for 805 MB of temporaries.  64 is a sixteenth of
#: that: a table is never taken for the time it ties at.
_SPAN_SLOTS_A_ROW = 64


def _hash_table_size(n_keys: int, span: int = 0, probe_rows: int = 0) -> int:
    """How many slots a table of ``n_keys`` build rows gets: a power of two,
    decided from static row counts and a hint that is part of the program's
    key, so a program's table is part of its text.

    16 slots a row, while that costs at most ``_TABLE_BYTES_MAX``; above it
    the largest power of two that does, and never a load factor over a
    half.  The generous table is what lets sparse integer keys be
    direct-addressed (``_direct_info``): TPC-H's order keys span four times
    their rows, a filtered side's more, and where ``key - lo`` fits the
    table the insert is one scatter round and the probe ONE 32-bit gather
    and a range test (``_direct_probe``; on a TPU v5e 39.8 ms at 6 M probe
    rows, 15.0 at 2 M, 10.7-11.1 at 0.5-1.5 M: PERF.md, PR 30), where a
    table the keys do not fit hashes: several scatter-min rounds to insert
    and, a probe round, three gathers and a ``_mix64`` in emulated u64 (TPC-H
    Q10's 2 M lineitem rows under 131 072 filtered orders: four rounds,
    290-380 ms).

    ``span`` (``statistics.key_span_hints``: the power of two at or above
    the width of the key's base column at ingest; 0 for none) says when 16
    slots a row cannot hold the keys whatever rows the join meets.  The
    span's own table is taken where it is larger than the table above,
    costs at most ``_TABLE_BYTES_MAX``, and has at most
    ``_SPAN_SLOTS_A_ROW`` slots for each of ``probe_rows`` or of the slots
    above, whichever is more: the fill and the row-id pass over its slots
    (``_hash_table_insert``, ``_row_id_table``) are paid every request.
    The kernel alone on the chip: TPC-H Q10's 131 072 x 2 097 152 rows over
    6 M keys take 275.2 ms at 2^21 slots and 47.0 at 2^23; 16 384 x 262 144
    rows over 2^25 keys 27.2 ms at 2^18 slots and 10.2 at 2^25.
    """
    n_keys = max(n_keys, 1)
    size = max(16, 1 << int(16 * n_keys - 1).bit_length())
    while size * _SLOT_BYTES > _TABLE_BYTES_MAX and size >= 4 * n_keys:
        size >>= 1
    wide = 1 << max(int(span) - 1, 0).bit_length()
    if (size < wide <= _SPAN_SLOTS_A_ROW * max(probe_rows, size)
            and wide * _SLOT_BYTES <= _TABLE_BYTES_MAX):
        return wide
    return size


def _single_int_part(parts):
    """The raw int64 array when the key is ONE non-nullable integer part
    (TPC-H's hot case: orderkey/partkey/custkey, non-null dictionary
    codes), else None.  Such keys get two shortcuts: ``_mix64`` is a
    BIJECTION on u64, so the hash is collision-free and raw-key
    verification is unnecessary; and the raw values drive the
    direct-address fast path below."""
    if len(parts) != 1 or parts[0][1] is not None:
        return None
    d = parts[0][0]
    if not jnp.issubdtype(d.dtype, jnp.integer):
        return None
    return d.astype(jnp.int64)


class _Direct(NamedTuple):
    """Direct addressing of one side's keys: where ``fits``, round 0's slot
    of a key is ``raw - lo`` and every key of ``[lo, hi]`` owns its slot."""
    raw: jax.Array
    lo: jax.Array
    hi: jax.Array
    fits: jax.Array


def _int_span(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """``hi - lo`` as f64, exact below 2^53 at any magnitude of the keys.
    The f64 difference alone is overflow-safe but rounds each operand (by
    up to 2^10 near int64's limits); the int64 difference is exact but
    wraps from 2^63.  Where the first reads under 2^62 the second cannot
    have wrapped."""
    approx = hi.astype(jnp.float64) - lo.astype(jnp.float64)
    return jnp.where(approx < 2.0 ** 62, (hi - lo).astype(jnp.float64),
                     approx)


def _direct_info(raw: Optional[jax.Array], valid: jax.Array, size: int):
    """``_Direct`` for direct addressing: when the runtime key range fits
    the table, round 0 gives every distinct key its OWN slot
    (``key - lo``), the while loop exits after one iteration, and the
    whole insert degenerates to one scatter + one gather.  ``fits`` is
    exact (``_int_span``): a probe that skips the hash check on its word
    (``_direct_probe``) has nothing else to catch a key clipped into a
    neighbour's slot."""
    if raw is None:
        return None
    i64 = jnp.iinfo(jnp.int64)
    lo = jnp.min(jnp.where(valid, raw, i64.max))
    hi = jnp.max(jnp.where(valid, raw, i64.min))
    fits = (_int_span(lo, hi) < size) & valid.any()
    return _Direct(raw, lo, hi, fits)


def _combined_direct(key: jax.Array, ok: jax.Array, span_prod: jax.Array,
                     size: int) -> _Direct:
    """``_Direct`` of a ``_combined_int_key``: its keys lie in
    ``[0, span_prod)``, so they address the table where that fits it."""
    fits = ok & (span_prod <= jnp.float64(size))
    hi = jnp.where(fits, span_prod, 1.0).astype(jnp.int64) - 1
    return _Direct(key, jnp.int64(0), hi, fits)


def _combined_int_key(part_sides):
    """Mixed-radix combination of 2+ non-float key parts into ONE int64.

    ``part_sides``: per key part, a list of (data, flag_or_None, valid)
    triples — one per SIDE (group-by passes one side; joins pass build and
    probe, so radix ranges come from the union of both).  Per-part runtime
    ranges become radix strides; nullability flags ride as an extra binary
    digit.  Returns (keys: one i64 array per side, ok[traced bool scalar],
    span_prod[traced f64]) — ``ok`` means every stride product stayed
    below 2^62, making the combination INJECTIVE, so ``_mix64(key)`` is a
    collision-free hash and the key qualifies for direct addressing when
    ``span_prod`` also fits the table.  Where ~ok the combined values are
    meaningless and callers must keep the generic hash + raw verification.
    None when any part is floating (ranges don't express float equality
    classes).
    """
    for sides in part_sides:
        for d, _, _ in sides:
            if jnp.issubdtype(d.dtype, jnp.floating):
                return None
    i64 = jnp.iinfo(jnp.int64)
    n_sides = len(part_sides[0])
    keys = [jnp.zeros(part_sides[0][s][0].shape[0], dtype=jnp.int64)
            for s in range(n_sides)]
    span_prod = jnp.float64(1.0)
    ok = jnp.bool_(True)
    for sides in part_sides:
        lo = jnp.int64(i64.max)
        hi = jnp.int64(i64.min)
        any_v = jnp.bool_(False)
        svalids = []
        for d, flag, valid in sides:
            d = d.astype(jnp.int64)
            sv = valid if flag is None else (valid & (flag == 1))
            svalids.append(sv)
            lo = jnp.minimum(lo, jnp.min(jnp.where(sv, d, i64.max)))
            hi = jnp.maximum(hi, jnp.max(jnp.where(sv, d, i64.min)))
            any_v = any_v | sv.any()
        lo = jnp.where(any_v, lo, 0)
        hi = jnp.where(any_v, hi, 0)
        span_prod = span_prod * (_int_span(lo, hi) + 1.0)
        ok = ok & (span_prod < 2.0 ** 62)
        stride = hi - lo + 1
        has_flag = any(flag is not None for _, flag, _ in sides)
        if has_flag:
            span_prod = span_prod * 2.0
            ok = ok & (span_prod < 2.0 ** 62)
        for s, (d, flag, _) in enumerate(sides):
            d = d.astype(jnp.int64)
            # where ~ok these wrap harmlessly (the caller masks); where
            # ok, d - lo is in [0, span) and the product fits int64
            dn = jnp.where(svalids[s], d - lo, 0)
            k = keys[s] * stride + dn
            if has_flag:
                fl = (jnp.ones_like(dn) if flag is None
                      else flag.astype(jnp.int64))
                k = k * 2 + fl
            keys[s] = k
    return keys, ok, span_prod


def _slot_at_round(h: jax.Array, k, size: int, direct) -> jax.Array:
    s = (_mix64(h + (2 * k + 1).astype(jnp.uint64) * _GOLDEN)
         & jnp.uint64(size - 1)).astype(jnp.int32)
    if direct is not None:
        d = jnp.clip(direct.raw - direct.lo, 0, size - 1).astype(jnp.int32)
        s = jnp.where((k == 0) & direct.fits, d, s)
    return s


_TBL_EMPTY = jnp.iinfo(jnp.int64).max
_TBL_ROW_MASK = jnp.int64((1 << 32) - 1)


def _hash_table_insert(h: jax.Array, valid: jax.Array, size: int,
                       direct=None):
    """Resolve every valid row to one table slot per distinct u64 hash.

    Claims are priority-encoded as ``(round+1) << 32 | row`` and written
    with ONE scatter-min per round: earlier rounds always beat later ones
    and the smallest row wins within a round, so occupied slots are
    permanent and the claim is deterministic — with no table-sized
    temporary or merge per round (those dominated the profile at 4M-slot
    tables).

    Returns (slot[i32 per row], resident[i32 per row: the hash group's
    first row, n where unresolved], resolved[bool], table[i64 size-array:
    priority-encoded claim, _TBL_EMPTY where free], rounds used).
    """
    n = h.shape[0]
    n32 = jnp.int32(n)
    rows = jnp.arange(n, dtype=jnp.int64)

    def cond(st):
        k, _, _, _, active = st
        return (k < _HASH_MAX_ROUNDS) & active.any()

    def body(st):
        k, table, slot, resident, active = st
        s_k = _slot_at_round(h, k, size, direct)
        idx = jnp.where(active, s_k, size)
        val = ((k + 1).astype(jnp.int64) << 32) | rows
        table = table.at[idx].min(val, mode="drop")
        tv = table[s_k]
        res = (tv & _TBL_ROW_MASK).astype(jnp.int32)
        ok = (active & (tv != _TBL_EMPTY)
              & (h[jnp.clip(res, 0, n32 - 1)] == h))
        slot = jnp.where(ok, s_k, slot)
        resident = jnp.where(ok, res, resident)
        return k + 1, table, slot, resident, active & ~ok

    st = (jnp.int32(0), jnp.full(size, _TBL_EMPTY), jnp.zeros(n, jnp.int32),
          jnp.full(n, n32), valid)
    k, table, slot, resident, active = jax.lax.while_loop(cond, body, st)
    return slot, resident, valid & ~active, table, k


def _row_id_table(table: jax.Array, n: int) -> jax.Array:
    """What a probe needs of the insert's claim table, in 32 bits: the
    resident's row, ``n`` where the slot is free.  The round in a claim's
    upper half orders the insert's scatter-min and is never read again, and
    a 64-bit array is two 32-bit ones on the chip: a gather out of this
    table is one where a gather out of ``table`` is two."""
    return jnp.where(table == _TBL_EMPTY, jnp.int32(n),
                     (table & _TBL_ROW_MASK).astype(jnp.int32))


def _direct_probe(rowtab: jax.Array, direct: _Direct, n: int) -> jax.Array:
    """Round 0 of a probe whose table is direct-addressed: one 32-bit gather
    and a range test.  Where ``direct.fits`` every build key owns slot
    ``key - lo``, so a claimed slot at ``raw - lo`` holds the probe's own
    key and the resident's hash has nothing to add; a key outside
    ``[lo, hi]`` has no slot (and ``raw - lo`` may have wrapped: it is read
    only inside the range).  Returns the candidate build row per probe row,
    ``n`` where there is none or the table is not direct-addressed."""
    in_range = direct.fits & (direct.raw >= direct.lo) \
        & (direct.raw <= direct.hi)
    d = jnp.where(in_range, direct.raw - direct.lo, 0).astype(jnp.int32)
    return jnp.where(in_range, rowtab[d], jnp.int32(n))


# ---------------------------------------------------------------------------
# the ordered probe: a build side that is a base table's rows in load order,
# on a key column that strictly increases, is its own index.  Nothing is
# inserted: the row of key ``k`` is ``k - lo`` where the keys are dense, and
# found by a search of the column itself where they are not.  The tracer
# takes it (``joins.ordered``) on an ingest statistic's word and the
# program checks that word over the physical column.
# ---------------------------------------------------------------------------

def _ordered_check(k: jax.Array, dense: bool, narrow: bool):
    """(lo, hi, ok) of a build key column the program was told increases
    strictly (and is ``dense``: ``hi - lo + 1`` rows; or ``narrow``: a span
    under 2^31): the ends are the first and the last row, no reduction, and
    ``ok`` is one elementwise pass.  A strictly increasing int64 column of
    ``n`` rows spans ``n - 1`` to ``2^64 - 1``, so the wrapped difference
    ``hi - lo`` reads a value in ``[0, 2^63)`` only where the span is it."""
    lo, hi = k[0], k[-1]
    ok = (k[1:] > k[:-1]).all()
    if dense:
        ok = ok & (hi - lo == k.shape[0] - 1)
    elif narrow:
        ok = ok & (hi - lo >= 0) & (hi - lo < 2 ** 31)
    return lo, hi, ok


def _ordered_dense(lo: jax.Array, hi: jax.Array, raw: jax.Array):
    """(candidate build row, found) per probe key where the build keys are
    every integer of ``[lo, hi]`` in order: no table and no gather.  ``raw -
    lo`` may wrap outside the range and is read only inside it."""
    found = (raw >= lo) & (raw <= hi)
    return jnp.where(found, raw - lo, 0).astype(jnp.int32), found


def _ordered_search(k: jax.Array, lo: jax.Array, hi: jax.Array,
                    raw: jax.Array, narrow: bool):
    """(candidate build row, found) per probe key in the strictly increasing
    ``k``: an interpolation search whose window is the column's own.

    ``guess`` maps a key to a row linearly between the column's ends, in
    float32 and monotone non-decreasing whatever it rounds (a conversion, a
    product with a positive scalar, a floor).  One elementwise pass over the
    build column reads how far the guess of a key lies before and beyond
    that key's row (``under``, ``over``); a probe key between two build keys
    is guessed between their guesses, so the greatest row whose key is at
    most the probe's lies in ``[guess - over - 1, guess + under]``, taken
    one row wider on both sides.  A binary search of that window is
    ``bit_length(width)`` rounds of one gather at the probe's rows, and one
    more for the equality: 3 to 5 where the keys lie evenly (TPC-H's order
    keys), ``ceil(log2(n))`` + 3 at the worst, which the tracer's rule of
    rows counts.  ``narrow``: keys and gathers are ``k - lo`` in 32 bits (a
    64-bit gather costs four to five on the chip)."""
    n = k.shape[0]
    found = (raw >= lo) & (raw <= hi)
    if narrow:
        keys = (k - lo).astype(jnp.int32)
        want = jnp.where(found, raw - lo, 0).astype(jnp.int32)
        fkeys, fwant = keys, want
    else:
        keys, want = k, jnp.where(found, raw, lo)
        # the wrapped difference, read unsigned, is the true one
        fkeys = (keys - lo).astype(jnp.uint64)
        fwant = (want - lo).astype(jnp.uint64)
    fkeys, fwant = fkeys.astype(jnp.float32), fwant.astype(jnp.float32)
    scale = jnp.float32(n - 1) / jnp.maximum(fkeys[-1], 1.0)

    def guess(x):
        return jnp.clip(jnp.floor(x * scale), 0, n - 1).astype(jnp.int32)

    off = guess(fkeys) - jnp.arange(n, dtype=jnp.int32)
    over, under = jnp.max(off), jnp.max(-off)       # off[0] == 0
    start = jnp.maximum(guess(fwant) - over - 2, 0)
    width = over + under + 4
    last = jnp.minimum(start + width - 1, n - 1)
    rounds = 32 - jax.lax.clz(width - 1)

    def body(i, pos):
        nxt = pos + (jnp.int32(1) << (rounds - 1 - i))
        take = (nxt <= last) & (keys[jnp.minimum(nxt, n - 1)] <= want)
        return jnp.where(take, nxt, pos)

    pos = jax.lax.fori_loop(0, rounds, body, start)
    return pos, found & (keys[pos] == want)


def _group_hashed_codes(key_cols: List[Column],
                        row_valid: Optional[jax.Array], cap: int):
    """Row-order dense group codes without any sort (CPU/GPU strategy).

    Returns (codes[i64 per row, trash slot == cap for invalid rows],
    first_rows[cap-sized original-row index per group], num_groups,
    collision).  num_groups comes back as cap+1 when the table could not
    resolve every key (more groups than cap, or pathological congestion),
    which rides the existing ngroups escalation: the caller recompiles
    with a doubled cap and therefore a doubled table.  Group numbering is
    hash-slot order — unordered, as SQL allows.
    """
    n = len(key_cols[0])
    parts = _key_parts(key_cols)
    h = _hash_group_parts(parts)
    valid = jnp.ones(n, bool) if row_valid is None else row_valid
    size = _hash_table_size(cap)
    single = _single_int_part(parts)
    direct = _direct_info(single, valid, size)
    combo_ok = None
    if single is None:
        combo = _combined_int_key([[(d, flag, valid)] for d, flag in parts])
        if combo is not None:
            # multi-part non-float keys: where the runtime radix product
            # fits, the combination is injective — collision-free mix hash
            # plus direct addressing when it also fits the table
            (key,), combo_ok, span_prod = combo
            h = jnp.where(combo_ok, _mix64(key.astype(jnp.uint64)), h)
            direct = _combined_direct(key, combo_ok, span_prod, size)
    slot, resident, resolved, table, _ = _hash_table_insert(h, valid, size,
                                                            direct)

    coll = jnp.zeros((), bool)
    if single is None:
        # true u64 collisions: a resident with equal hash, different raw key
        rc = jnp.clip(resident, 0, n - 1)
        for d, flag in parts:
            coll = coll | (resolved & (d[rc] != d)).any()
            if flag is not None:
                coll = coll | (resolved & (flag[rc] != flag)).any()
        if combo_ok is not None:
            # an injective combined key cannot collide; the raw check only
            # matters where the combination overflowed
            coll = coll & ~combo_ok
    # else: _mix64 over one int part is a bijection — collisions impossible

    # dense codes in first-occurrence order: rank the LEADER rows (a group's
    # resident is its first row) and read every row's code through its
    # resident — all O(n) ops, nothing table-sized
    leader = resolved & (resident == jnp.arange(n, dtype=resident.dtype))
    lrank = jnp.cumsum(leader.astype(jnp.int64)) - 1
    real_groups = jnp.sum(leader.astype(jnp.int64))
    unresolved = (valid & ~resolved).any()
    # congestion (true group count unknowable) reports the impossible value
    # n+1 — _check_flags reads any ng > input rows as "table saturated" and
    # jumps the cap hard; a RESOLVED overflow reports the exact count, so
    # the recompiled cap lands tight
    num_groups = jnp.where(unresolved, jnp.int64(n + 1), real_groups)

    codes_raw = lrank[jnp.clip(resident, 0, n - 1)]
    codes = jnp.where(resolved, jnp.minimum(codes_raw, cap), cap)
    fr_idx = jnp.where(leader & (codes < cap), codes, cap)
    first_rows = (jnp.full(cap, n, dtype=jnp.int64)
                  .at[fr_idx].min(jnp.arange(n, dtype=jnp.int64),
                                  mode="drop"))
    first_rows = jnp.clip(first_rows, 0, max(n - 1, 0))
    return codes, first_rows, num_groups, coll
