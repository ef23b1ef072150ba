"""The formulations of an equi-join, each a function of the two sides, their
key parts and hashes, and the trace's ledger (``traced.ProgramFlags``):
``(jt, probe, build, ...) -> (match over the probe rows, the build columns
fetched to them, or None for SEMI / ANTI)``.

``compiled._Tracer._LogicalJoin`` chooses among them from the static row
counts and the request's hints; nothing here walks a plan or makes that
choice:

- ``merge``: sort the build side's hashes and find each probe hash among
  them; what the TPU strategy takes while its sorts compile inside a set-up
  (``compiled.SORT_ROWS_MAX``);
- ``merge_exists``: the merge join of a SEMI / ANTI join with a residual
  ``build.x OP probe.y``, which needs each key's build rows side by side;
- ``ordered``: the build side's key column is strictly increasing in load
  order, so it is its own index and nothing is built;
- ``hash_table``: an open-addressing table of build row ids, direct-addressed
  where the data lets it be.

The ``dsql.join_build`` / ``dsql.join_probe`` (``dsql.semi_*``) scopes are
what ``join_device_ms`` and ``semi_join_device_ms`` read device time by.
Imports nothing of the compiled tier.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.hashing import (_U64_MAX, _combined_direct, _combined_int_key,
                           _direct_info, _direct_probe, _hash_table_insert,
                           _hash_table_size, _mix64, _ordered_check,
                           _ordered_dense, _ordered_search, _row_id_table,
                           _slot_at_round)
from ..ops.window import segmented_cumsum, segmented_scan
from ..runtime import statistics as _stats
from ..table import Column
from .semijoin import _exist_operands, _exists, _join_scope
from .traced import _VT, ProgramFlags


def _duplicate_policy(flags: ProgramFlags, jt, adj: jax.Array,
                      raw_diffs) -> None:
    """The sort formulations' fallback bit.  ``adj`` marks adjacent
    equal-hash build pairs in build-hash-sorted order; ``raw_diffs`` are the
    matching adjacent raw-key inequality masks.  INNER/LEFT/RIGHT require a
    unique build key (adjacency of any kind covers hash collisions too);
    SEMI/ANTI tolerate duplicates, so only a genuine collision (equal hash,
    different raw key) is fatal."""
    if jt in ("INNER", "LEFT", "RIGHT"):
        flags.fallback(adj.any())
    else:
        coll = jnp.zeros((), dtype=bool)
        for d in raw_diffs:
            coll = coll | (adj & d).any()
        flags.fallback(coll)


def merge(jt, probe: _VT, build: _VT, pparts, bparts, pvalid: jax.Array,
          ph: jax.Array, bh: jax.Array, flags: ProgramFlags):
    """Sorted-probe join: sort ONLY the build side's hashes (one argsort at
    nb rows, whatever the build side's width), locate each probe hash with
    ``searchsorted(method='sort')``, ONE (nb+npr)-row sort, and verify the
    raw keys through row-id gathers.  The default scan method is
    ``ceil(log2(nb))`` rounds of an npr-row gather each, and a gather a
    probe row is what the chip does slowly (7.3-22.6 ns each on a v5e,
    PERF.md section 6, PR 34); the sorts cost 20 ns a build row (TPC-H Q12:
    30.2 ms for 1.5 M orders, PR 27) and compile inside a set-up only up to
    ``compiled.SORT_ROWS_MAX`` rows, which has the table."""
    nb, npr = build.n, probe.n
    if nb == 0:
        # a gather from a 0-row build would fail at trace time; an empty
        # build matches nothing (x NOT IN (empty) handled by the caller's
        # null-aware logic over this all-false match)
        flags.fallback(jnp.zeros((), bool))
        match = jnp.zeros(npr, dtype=bool)
        if jt in ("SEMI", "ANTI"):
            return match, None
        # zero-filled columns, masked by the all-false match downstream
        return match, [
            Column(jnp.zeros(npr, dtype=c0.data.dtype), c0.stype,
                   None if c0.mask is None else jnp.zeros(npr, bool),
                   c0.dictionary)
            for c0 in build.table.columns]
    with jax.named_scope(_join_scope(jt, "build")):
        order = jnp.argsort(bh)
        bh_sorted = bh[order]
        # duplicate build keys / hash collisions appear as adjacent equal
        # hashes in sorted order (same flag policy as every strategy)
        adj = ((bh_sorted[1:] == bh_sorted[:-1])
               & (bh_sorted[1:] != _U64_MAX))
        raws_sorted = [braw[order] for _, braw in bparts]
        _duplicate_policy(flags, jt, adj,
                          [rs[1:] != rs[:-1] for rs in raws_sorted])

    with jax.named_scope(_join_scope(jt, "probe")):
        pos = jnp.searchsorted(bh_sorted, ph, side="left", method="sort")
        in_range = pos < nb
        pos_c = jnp.minimum(pos, nb - 1)
        cand = order[pos_c]
        match = in_range & pvalid & (bh_sorted[pos_c] == ph)
        for (_, praw), (_, braw) in zip(pparts, bparts):
            match = match & (praw == braw[cand])
        if jt in ("SEMI", "ANTI"):
            return match, None
        return match, [c0.take(cand) for c0 in build.table.columns]


def merge_exists(jt, probe: _VT, build: _VT, pparts, bparts,
                 pvalid: jax.Array, ph: jax.Array, bh: jax.Array,
                 exist_test, flags: ProgramFlags):
    """The merge join of a SEMI / ANTI join whose residual is
    ``build.x OP probe.y`` (``semijoin._residual_exist_test``): both sides
    go through ONE stable sort by hash with the operands as payload, so a
    key's build rows stand before its probe rows and segmented scans give
    each probe row the count, least and greatest ``x`` of its key.  Such a
    join fetches no build column: the sort carries the key parts and the
    four operand channels, whatever the build side's width.
    ``hash_table`` decides the same test with per-resident scatters; no
    benchmark cell runs either (ROADMAP D16)."""
    nb, npr = build.n, probe.n
    m = nb + npr
    h_m = jnp.concatenate([bh, ph])
    flag_b = jnp.concatenate([jnp.ones(nb, bool), jnp.zeros(npr, bool)])
    idt = jnp.int32 if m < 2**31 else jnp.int64
    iota_m = jnp.arange(m, dtype=idt)
    raw_ch = [jnp.concatenate([braw, praw])
              for (_, braw), (_, praw) in zip(bparts, pparts)]
    op_t, x_col, y_col = exist_test
    xd, yd = _exist_operands(x_col, y_col)
    res_ch = [
        jnp.concatenate([xd, jnp.zeros(npr, dtype=jnp.int64)]),
        jnp.concatenate([x_col.valid_mask(), jnp.zeros(npr, dtype=bool)]),
        jnp.concatenate([jnp.zeros(nb, dtype=jnp.int64), yd]),
        jnp.concatenate([jnp.zeros(nb, dtype=bool), y_col.valid_mask()]),
    ]

    outs = jax.lax.sort((h_m, flag_b, iota_m, *raw_ch, *res_ch),
                        num_keys=1, is_stable=True)
    hs, fbs, iotas = outs[0], outs[1], outs[2]
    raws = outs[3:3 + len(raw_ch)]
    xs, xvs, ys, yvs = outs[3 + len(raw_ch):]

    # equal-hash build rows are contiguous (stable sort puts build rows
    # before same-hash probe rows), so duplicates/collisions show up as
    # adjacent build pairs — no scan needed for the flags
    adj = fbs[1:] & fbs[:-1] & (hs[1:] == hs[:-1]) & (hs[1:] != _U64_MAX)
    _duplicate_policy(flags, jt, adj, [r[1:] != r[:-1] for r in raws])

    def carry_op(a, b):
        take = b[0]
        return tuple([a[0] | b[0]]
                     + [jnp.where(take, bv, av)
                        for av, bv in zip(a[1:], b[1:])])

    carried = jax.lax.associative_scan(carry_op, (fbs, *raws))
    has_b = carried[0]

    # a probe row matches iff the last build row at-or-before it has the
    # same raw key (equal raw => equal hash, and everything between them
    # in hash order then shares that hash)
    match_s = (~fbs) & has_b
    for cr, r in zip(carried[1:], raws):
        match_s = match_s & (cr == r)

    # per-hash-run build aggregates decide "exists build x OP y": all build
    # rows of a run precede its probe rows (stable sort), so a probe's
    # inclusive segmented scan covers the whole run
    run_start = jnp.concatenate(
        [jnp.ones(1, dtype=bool), hs[1:] != hs[:-1]])
    xv = xvs & fbs
    cnt = segmented_cumsum(xv.astype(jnp.int64), run_start)
    mn = segmented_scan(jnp.where(xv, xs, jnp.iinfo(jnp.int64).max),
                        run_start, jnp.minimum)
    mx = segmented_scan(jnp.where(xv, xs, jnp.iinfo(jnp.int64).min),
                        run_start, jnp.maximum)
    match_s = match_s & (cnt > 0) & _exists(op_t, mn, mx, ys) & yvs

    un = jax.lax.sort((iotas, match_s), num_keys=1)
    return un[1][nb:] & pvalid, None


def ordered(jt, probe: _VT, build: _VT, praw: jax.Array, braw: jax.Array,
            pvalid: jax.Array, tag: str, level: int, flags: ProgramFlags):
    """The ordered probe (kernels in ops/hashing.py): the build side's key
    column is strictly increasing in row order, so the row of a key is
    found in the column itself and nothing is built.  A strictly
    increasing key is unique, so the table's ``dup`` / ``unresolved`` /
    ``raw_mismatch`` flags have nothing to say; what there is to check is
    the hint (``tag``, at ``level``: ``compiled._Tracer._ordered_hint``),
    one elementwise pass under ``dsql.join_build`` into the flags
    (``caps._check_ordered``: a refuted hint recompiles with the table,
    and never answers)."""
    dense = level == _stats.ORDERED_DENSE
    narrow = level == _stats.ORDERED_NARROW
    k = braw.astype(jnp.int64)
    raw = praw.astype(jnp.int64)
    with jax.named_scope(_join_scope(jt, "build")):
        lo, hi, ok = _ordered_check(k, dense, narrow)
    flags.hint(tag, ok)
    flags.ordered_dense += dense
    with jax.named_scope(_join_scope(jt, "probe")):
        if dense:
            cand, found = _ordered_dense(lo, hi, raw)
        else:
            cand, found = _ordered_search(k, lo, hi, raw, narrow)
        match = found & pvalid
        if build.valid is not None:
            match = match & build.valid[cand]
    if jt in ("SEMI", "ANTI"):
        return match, None
    return match, [c.take(cand) for c in build.table.columns]


def hash_table(jt, probe: _VT, build: _VT, pparts, bparts,
               pvalid: jax.Array, ph: jax.Array, bh: jax.Array, exist_test,
               span: int, flags: ProgramFlags):
    """Open-addressing hash join: insert build row ids into a power-of-2
    table (empty-slot claim rounds, see _hash_table_insert), probe with
    one gather chain per round actually used; where the data lets the
    table be direct-addressed, round 0 is one 32-bit gather and the only
    round (``_direct_probe``).  Verification always compares raw key
    parts, so lossy hashes only add collisions — caught by the flags and
    rerun eager.  It holds no sort and compiles in seconds at any size; on
    the chip it pays a serialized scatter a build row (186-211 ns on a
    v5e, PR 27 and PR 34) and a gather a probe row, which is why the merge
    join stays wherever its sorts compile.
    ``exist_test`` (a SEMI / ANTI residual ``build.x OP probe.y``, or
    None): count, least and greatest ``x`` of each key by scatters at its
    resident row.
    ``span``: the class of the build key's ingest span (a ``span*`` hint:
    ``statistics.key_span_hints``), by which ``_hash_table_size`` may give
    one integer key a table that holds it; ``_direct_info`` checks the fit.
    """
    nb, npr = build.n, probe.n
    bvalid = bh != _U64_MAX          # _hash_parts marks invalid keys
    # single integer-raw key (ints, dates, unified string codes): the
    # _mix64 rehash is a BIJECTION, so hash equality IS key equality —
    # no raw verification, no collision flag — and the raw values
    # enable the direct-address round-0 fast path
    bij = (len(bparts) == 1
           and jnp.issubdtype(bparts[0][1].dtype, jnp.integer))
    size = _hash_table_size(nb, span if bij else 0, npr)
    flags.span_tables += size != _hash_table_size(nb)
    direct_b = direct_p = None
    combo_ok = None
    if bij:
        braw1 = bparts[0][1].astype(jnp.int64)
        praw1 = pparts[0][1].astype(jnp.int64)
        bh = _mix64(braw1.astype(jnp.uint64))   # clamp-free, clean
        ph = _mix64(praw1.astype(jnp.uint64))
        direct_b = _direct_info(braw1, bvalid, size)
        direct_p = direct_b._replace(raw=praw1)
    else:
        # multi-part keys: mixed-radix combination over the UNION of
        # both sides' runtime ranges — injective where the radix
        # product fits (combo_ok), giving a collision-free hash and
        # direct addressing when it also fits the table
        combo = _combined_int_key(
            [[(braw, None, bvalid), (praw, None, pvalid)]
             for (_, braw), (_, praw) in zip(bparts, pparts)])
        if combo is not None:
            (bkey, pkey), combo_ok, span_prod = combo
            bh = jnp.where(combo_ok,
                           _mix64(bkey.astype(jnp.uint64)), bh)
            ph = jnp.where(combo_ok,
                           _mix64(pkey.astype(jnp.uint64)), ph)
            direct_b = _combined_direct(bkey, combo_ok, span_prod, size)
            direct_p = direct_b._replace(raw=pkey)
    with jax.named_scope(_join_scope(jt, "build")):
        slot, resident, resolved, table, rounds = _hash_table_insert(
            bh, bvalid, size, direct_b)
        rowtab = _row_id_table(table, nb)

    raw_mismatch = jnp.zeros((), bool)
    if not bij:
        rc0 = jnp.clip(resident, 0, nb - 1)
        for _, braw in bparts:
            raw_mismatch = raw_mismatch | (resolved
                                           & (braw[rc0] != braw)).any()
        if combo_ok is not None:
            # injective combined keys cannot collide; the raw check
            # only matters where the combination overflowed
            raw_mismatch = raw_mismatch & ~combo_ok
    unresolved = (bvalid & ~resolved).any()
    if jt in ("INNER", "LEFT", "RIGHT"):
        # these require a unique build key (same policy as the sort
        # strategies): any second row of a key resolves to a foreign
        # resident
        dup = (resolved
               & (resident != jnp.arange(nb, dtype=resident.dtype))).any()
        flags.fallback(raw_mismatch | dup | unresolved)
    else:
        flags.fallback(raw_mismatch | unresolved)

    # probe: same slot sequence; a key resident at round k implies its
    # rounds 0..k slots are all occupied, so scanning the rounds the
    # insert used and taking the first equal-hash resident is complete
    nb32 = jnp.int32(nb)

    def probe_body(st):
        k, cand = st
        s_k = _slot_at_round(ph, k, size, direct_p)
        r = rowtab[s_k]
        hit = (r != nb32) & (bh[jnp.clip(r, 0, nb32 - 1)] == ph)
        cand = jnp.where((cand == nb32) & hit, r, cand)
        return k + 1, cand

    def probe_cond(st):
        k, _ = st
        return k < rounds

    with jax.named_scope(_join_scope(jt, "probe")):
        # a direct-addressed insert ends after round 0, which is peeled
        # here, so the loop below runs no round at all; any other table
        # discards the peeled candidates and loops from round 0
        if direct_p is None:
            direct = jnp.zeros((), bool)
            cand0 = jnp.full(npr, nb32)
        else:
            direct = direct_p.fits
            cand0 = _direct_probe(rowtab, direct_p, nb)
        _, cand = jax.lax.while_loop(
            probe_cond, probe_body, (direct.astype(jnp.int32), cand0))
    flags.direct(direct)
    found = cand < nb32
    cc = jnp.clip(cand, 0, nb - 1)
    match = found & pvalid
    if not bij:
        raw_eq = jnp.ones(npr, dtype=bool)
        for (_, praw), (_, braw) in zip(pparts, bparts):
            raw_eq = raw_eq & (praw == braw[cc])
        if combo_ok is not None:
            # hash equality is key equality where the combination held
            match = match & (combo_ok | raw_eq)
        else:
            match = match & raw_eq

    if exist_test is not None:
        # per-slot build aggregates decide "exists build x OP y"
        op_t, x_col, y_col = exist_test
        xd, yd = _exist_operands(x_col, y_col)
        # aggregates are indexed by the group's RESIDENT row id (dense
        # in [0, nb)), not by table slot: nb-sized arrays instead of
        # table-sized ones, and the probe's candidate IS the resident
        xv = resolved & x_col.valid_mask()
        idx = jnp.where(xv, resident, nb)
        i64 = jnp.iinfo(jnp.int64)
        cnt = jnp.zeros(nb, jnp.int64).at[idx].add(1, mode="drop")
        mn = (jnp.full(nb, i64.max, jnp.int64)
              .at[idx].min(xd, mode="drop"))
        mx = (jnp.full(nb, i64.min, jnp.int64)
              .at[idx].max(xd, mode="drop"))
        match = (match & (cnt[cc] > 0)
                 & _exists(op_t, mn[cc], mx[cc], yd) & y_col.valid_mask())

    if jt in ("SEMI", "ANTI"):
        return match, None
    return match, [c.take(cc) for c in build.table.columns]
