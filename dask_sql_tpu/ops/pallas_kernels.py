"""Pallas TPU kernels for the engine's hot loops.

The flagship kernel is a fused masked segmented reduction: SQL's
``SELECT agg(x) ... GROUP BY k`` with a small static group domain (Q1 shape).
Instead of XLA scatter-adds (slow on TPU) or a sort-based factorize, each
row block builds its one-hot group matrix in VMEM and contracts it against
the value rows on the MXU:

    out[a, g] += sum_i vals[a, i] * (codes[i] == g & mask[i])

The one-hot never touches HBM — it exists per block in VMEM — so the kernel
is bandwidth-bound on the value stream alone, the MXU does the reduction,
and the grid accumulates partials into the (A, G) output block across steps.

The reference has no analogue (its groupby is a dask tree reduction over
pandas partitions, aggregate.py:325-361); this is the SURVEY §7 "pallas
kernels where XLA ops are awkward" item for groupby.

On non-TPU backends the kernel runs in interpreter mode (tests), keeping one
code path.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


BLOCK = 1024       # rows per grid step (lane-aligned multiple of 128)
GROUP_TILE = 128   # group-axis padding (last-dim tile width)


def _on_tpu() -> bool:
    """A backend that cannot initialise raises here; it is never read as
    "not a TPU"."""
    return jax.default_backend() == "tpu"


def _backend_is_tpu() -> bool:
    """The UNPATCHED hardware truth, gating pallas ``interpret=`` only:
    tests monkeypatch ``_on_tpu`` to force kernel strategies on CPU, but a
    non-interpret ``pallas_call`` on a non-TPU backend is a hard error
    ("Only interpret mode is supported on CPU backend") — the interpret
    decision must never be fooled by a strategy override."""
    return jax.default_backend() == "tpu"


def _strategy_on_tpu() -> bool:
    """Which KERNEL STRATEGY to trace — sort-based merge join / payload-
    through-sort groupby (TPU-shaped: no scatters) vs hash-table join /
    scatter groupby (host-shaped: scatters are ~1 ms where sorts are
    hundreds).  Distinct from ``_on_tpu`` (the hardware truth, which gates
    pallas ``interpret=``): ``DSQL_STRATEGY=tpu|host`` forces a strategy on
    either backend.  BENCH_r04/r05 ran ``host`` on their TPU because the
    merge join's variadic sorts compiled ~8x slower there.  Measured for a
    v5e since (PR 27): a sort's compile time is in its key channels and
    rows, minutes apiece at millions of rows, so under the TPU strategy
    a join still takes the scatter formulation where its sorts would see
    more than ``SORT_ROWS_MAX`` rows
    (``physical/compiled.py::_sort_formulation``, decided per operator from
    the static shapes of the plan), a grouped aggregate takes it at every
    size, and an ORDER BY above ``LEXSORT_ROWS_MAX`` rows sorts a key
    channel a pass."""
    s = os.environ.get("DSQL_STRATEGY", "auto").lower()
    if s == "tpu":
        return True
    if s in ("host", "cpu"):
        return False
    return _on_tpu()


def _seg_matmul_kernel(codes_ref, mask_ref, vals_ref, out_ref):
    """One grid step: accumulate this row block's per-group partial sums."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    codes = codes_ref[:]                      # (1, BLOCK) int32
    mask = mask_ref[:]                        # (1, BLOCK) int32 0/1
    g = out_ref.shape[1]
    # mask arrives as int32 and the masking is arithmetic (multiply), not a
    # bool select: Mosaic supports neither minor-dim insertion nor select_n
    # on 1-bit types
    onehot = (codes.reshape(-1, 1)
              == jax.lax.broadcasted_iota(jnp.int32, (codes.shape[1], g), 1)
              ).astype(out_ref.dtype)
    onehot = onehot * mask.reshape(-1, 1).astype(out_ref.dtype)
    # HIGHEST: at its default precision the MXU rounds f32 operands to
    # bf16 (8 significant bits) — sums came back ~1e-5 off on the v5e
    out_ref[:] += jnp.dot(vals_ref[:].astype(out_ref.dtype), onehot,
                          preferred_element_type=out_ref.dtype,
                          precision=jax.lax.Precision.HIGHEST)


def _seg_matmul_perblock_kernel(codes_ref, mask_ref, vals_ref, out_ref):
    """One grid step: THIS block's per-group partial sums, written to the
    step's own output ROWS (out is (grid*A, g) 2D; step i owns rows
    [i*A, (i+1)*A) — no cross-step accumulation).  Exactness contract: with
    |vals| <= 4095 and BLOCK_EXACT rows, each f32 partial is an integer
    < 2**24 and therefore exact; the caller accumulates the per-block row
    slices in f64."""
    codes = codes_ref[:]                      # (1, BLOCK_EXACT) int32
    mask = mask_ref[:]                        # (1, BLOCK_EXACT) int32 0/1
    g = out_ref.shape[1]
    # mask arrives as int32 and the masking is arithmetic (f32 multiply),
    # not a bool select: Mosaic supports neither minor-dim insertion nor
    # select_n on 1-bit types
    onehot = (codes.reshape(-1, 1)
              == jax.lax.broadcasted_iota(jnp.int32, (codes.shape[1], g), 1)
              ).astype(jnp.float32)
    onehot = onehot * mask.reshape(-1, 1).astype(jnp.float32)
    # HIGHEST: the exactness contract needs every 12-bit limb to reach
    # the accumulator intact, and at its default precision the MXU rounds
    # f32 operands to bf16 (8 significant bits)
    out_ref[:] = jnp.dot(vals_ref[:].astype(jnp.float32), onehot,
                         preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)


# rows per grid step of the limb kernel: BLOCK_EXACT * 4095 < 2**24 keeps
# every per-block limb partial exactly representable in f32
BLOCK_EXACT = 4096
# rows per outer slab: bounds the transient limb expansion (up to 14 limb
# rows per value row at 4 bytes) to ~56*A MB instead of 14x the full column
SLAB_EXACT = 1 << 20
_LIMBS = 7          # 7 x 12-bit limbs: capacity 2**84 per decomposed value
_LIMB_BASE = 4096.0
# limbs needed per row class; 'unit' rows (0/1 indicators, COUNT streams)
# are their own limb 0, 'int' rows are gated < 2**53 (5x12 = 60 bits),
# 'float' rows are runtime-normalized to < 2**83 (see below)
_CLASS_LIMBS = {"unit": 1, "int": 5, "float": _LIMBS}


def _exact_pow2(n: jax.Array) -> jax.Array:
    """``2.0**n`` for integer ``n`` in [-1022, 1023] as an EXACT f64 power
    of two, traced TPU-safely.  Neither standard spelling qualifies:
    ``ldexp``/``frexp`` on f64 lower to an s64 bitcast-convert the TPU X64
    rewrite does not implement (hard compile failure on v5e), and XLA's
    ``exp2`` is exp(n*ln2)-based — off by ulps even at integer arguments,
    which would silently break the fixed-point grid's exactness contract.
    Binary exponentiation instead: every factor (2**(2**i)) and every
    partial product is itself a power of two, so every multiply is exact;
    the negative half divides 1 by the positive power (exact for normal
    powers of two)."""
    n = n.astype(jnp.int32)
    mag = jnp.abs(n)
    out = jnp.ones(jnp.shape(n), jnp.float64)
    base = jnp.float64(2.0)
    for i in range(10):          # covers |n| <= 1023
        out = jnp.where(((mag >> i) & 1) == 1, out * base, out)
        if i < 9:
            base = base * base   # 2**(2**(i+1)), up to 2**512 — finite
    return jnp.where(n >= 0, out, 1.0 / out)


def _segmented_sums_limbs(vals: Optional[jax.Array], codes: jax.Array,
                          mask: jax.Array, num_groups: int,
                          row_classes, interpret: bool, *,
                          slab_of=None, absmax=None) -> jax.Array:
    """Masked segmented sums of f64 rows as fixed-point MXU contractions.

    The f64 scan this replaces (``segmented_sums_xla_blocked``) was the
    single most expensive device op in the TPC-H Q1/Q5 profiles (~0.4-1.2 s
    per query: 64-bit emulation inside a ~1500-step sequential lax.scan,
    with minutes-long compiles to match).  Here every value decomposes into
    sign-split 12-bit limbs on a fixed-point grid, each limb row is a
    per-block one-hot MXU contraction in f32 (integer partials < 2**24:
    exact), per-block partials accumulate in f64 (limb totals < 2**35:
    exact), and limbs recombine with exact power-of-two weights.

    Per-row grid choice by ``row_classes[i]``:
    - ``"unit"``: 0/1 streams (COUNT, occupancy, NaN/Inf indicators) — one
      limb, no negative half.  Bit-exact always.
    - ``"int"``: integer-valued rows (scaled decimals, int columns) on the
      unit grid — 5 limbs cover the caller-guaranteed |v| < 2**53, and the
      result is BIT-EXACT whenever sum(|v|) <= 2**53 (the same contract the
      old scan's f64 adds could only approximate).
    - ``"float"``: arbitrary f64 rows — scaled by the exact power of two
      2**(83-e) (e = exponent of the row's runtime max |v|), floor-truncated
      to the limb grid, summed exactly there, unscaled exactly.  Total
      truncation error is n * 2**(e-83) <= 2**(e-60) at n = 2**23 rows —
      below one ulp of the row maximum, i.e. tighter than ANY f64
      accumulation order, for data of any magnitude.

    ``vals`` is the (rows, n) matrix, whole.  Where that matrix is too
    large to exist (``segmented_sums_slabwise``) it is None: ``slab_of(take)``
    then builds the (rows, SLAB_EXACT) matrix of one slab from ``take``, the
    slab's slice of any full-length array, and ``absmax`` is each row's
    runtime maximum (read for float rows only).
    """
    cls = list(row_classes)
    if vals is None:
        a, n = len(cls), codes.shape[0]
        assert n > SLAB_EXACT, n
    else:
        a, n = vals.shape
    assert len(cls) == a, (len(cls), a)
    if n == 0:
        return jnp.zeros((a, num_groups), jnp.float64)
    g_pad = max(GROUP_TILE, -(-num_groups // GROUP_TILE) * GROUP_TILE)
    cap_bits = 12 * _LIMBS - 1
    # per-row EXACT power-of-two scale: 1 for unit/int rows; ~2**(83-e)
    # for float rows.  NO frexp/ldexp here: on f64 they lower to an s64
    # bitcast-convert the TPU X64 rewrite does not implement (verified on
    # v5e), which killed every f64 static-domain aggregate at compile.
    # Instead e comes from floor(log2(absmax)) — within 1 ulp of the true
    # exponent, so TWO bits of slack in cap_bits bound absmax < 2**e
    # conservatively — and 2**k is built with exp2 of an integer-valued
    # f64, which is an exact power of two.  The slack costs <= 2 bits of
    # limb headroom (error bound ~4x, still far below one ulp of the row
    # maximum).  absmax is taken over MASK-CONTRIBUTING values only: the
    # engine filters by validity mask without compaction, so a huge value
    # in a filtered-out row must not coarsen the grid for the whole row
    # (it would truncate all valid contributions to 0 — silently wrong).
    is_float = np.asarray([c == "float" for c in cls])
    if is_float.any():
        if vals is not None:
            absmax = jnp.max(
                jnp.where(mask.astype(bool)[None, :], jnp.abs(vals), 0.0),
                axis=1)
        e = jnp.floor(jnp.log2(jnp.maximum(absmax, 1e-300))
                      ).astype(jnp.int32) + 2
        k = jnp.where(jnp.asarray(is_float) & (absmax > 0),
                      jnp.clip(cap_bits - e, -940, 1000), 0)
        k = k.astype(jnp.int32)
        scale = _exact_pow2(k)       # multiplying by these is exact
        inv = _exact_pow2(-k)
    else:
        k = jnp.zeros((a,), jnp.int32)
        scale = inv = jnp.ones((a,), jnp.float64)
    # static (row, sign, limb) layout of the limb matrix
    layout = []
    for i, c in enumerate(cls):
        for s in ((1,) if c == "unit" else (1, -1)):
            for lk in range(_CLASS_LIMBS[c]):
                layout.append((i, s, lk))
    ar = len(layout)
    # Mosaic tile rule: the output block's row count must be divisible by 8
    # (f32 (8, 128) tiling) — pad with zero limb rows
    ar_pad = -(-ar // 8) * 8

    def slab_partials(v, c, m):
        """(ar, num_groups) f64 limb totals of one slab: ``v`` (a, ns) f64,
        ``c`` (ns,) int32 codes, ``m`` (ns,) bool."""
        ns = v.shape[1]
        ns_pad = -(-ns // BLOCK_EXACT) * BLOCK_EXACT
        # zero masked-out values BEFORE scaling: the grid is sized for the
        # contributing values only, so a filtered-out outlier could
        # overflow to inf under the scale and poison the f32 limbs as NaN
        v = jnp.where(m[None, :], v, 0.0) * scale[:, None]
        if ns_pad != ns:
            v = jnp.pad(v, ((0, 0), (0, ns_pad - ns)))
            c = jnp.pad(c, (0, ns_pad - ns))
            m = jnp.pad(m, (0, ns_pad - ns))
        # sign-split limb extraction; every step is exact f64 integer
        # arithmetic (power-of-two divides, floors, Sterbenz subtractions)
        halves = {}
        for i, c_i in enumerate(cls):
            halves[(i, 1)] = jnp.floor(jnp.maximum(v[i], 0.0))
            if c_i != "unit":
                halves[(i, -1)] = jnp.floor(jnp.maximum(-v[i], 0.0))
        rows = []
        prev = None
        for (i, s, lk) in layout:
            if lk == 0:
                rem = halves[(i, s)]
            else:
                rem = prev  # floor(rem / 4096) from the previous limb
            q = jnp.floor(rem / _LIMB_BASE)
            rows.append((rem - q * _LIMB_BASE).astype(jnp.float32))
            prev = q
        limb = jnp.stack(rows)                        # (ar, ns_pad) f32
        if ar_pad != ar:
            limb = jnp.concatenate(
                [limb, jnp.zeros((ar_pad - ar, ns_pad), jnp.float32)], axis=0)
        grid = ns_pad // BLOCK_EXACT
        # x64 tracing breaks the Mosaic lowering (i64 index maps fail to
        # legalize); the kernel is pure f32/i32, so trace the compiled call
        # in 32-bit scope (interpret mode keeps the caller's setting)
        import contextlib
        scope = (contextlib.nullcontext() if interpret
                 else jax.enable_x64(False))
        with scope:
            per = pl.pallas_call(
                _seg_matmul_perblock_kernel,
                grid=(grid,),
                in_specs=[
                    pl.BlockSpec((1, BLOCK_EXACT), lambda i: (0, i)),
                    pl.BlockSpec((1, BLOCK_EXACT), lambda i: (0, i)),
                    pl.BlockSpec((ar_pad, BLOCK_EXACT), lambda i: (0, i)),
                ],
                out_specs=pl.BlockSpec((ar_pad, g_pad), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((grid * ar_pad, g_pad),
                                               jnp.float32),
                interpret=interpret,
            )(c.reshape(1, ns_pad), m.astype(jnp.int32).reshape(1, ns_pad),
              limb)
        per = per.reshape(grid, ar_pad, g_pad)[:, :ar]
        return per.astype(jnp.float64).sum(0)[:, :num_groups]

    codes = codes.astype(jnp.int32)
    mask = mask.astype(bool)
    if n <= SLAB_EXACT:
        out = slab_partials(vals, codes, mask)
    else:
        # one traced slab body, looped and not unrolled: the limb
        # arithmetic above is five f64 ops a limb (some 80 a float row,
        # 60 an int row, 9 a unit row), and a copy of it
        # per slab makes program size, compile time and the compiler's
        # host memory grow with the row count (CHANGES.md, PR 23).  The
        # last slab is anchored at n - SLAB_EXACT so every slice is full
        # width; the rows it shares with the slab before it are masked out.
        lane = jnp.arange(SLAB_EXACT, dtype=jnp.int32)

        def one_slab(acc, s0):
            start = jnp.minimum(s0, n - SLAB_EXACT)
            m = (jax.lax.dynamic_slice(mask, (start,), (SLAB_EXACT,))
                 & (start + lane >= s0))
            c = jax.lax.dynamic_slice(codes, (start,), (SLAB_EXACT,))
            if vals is None:
                v = slab_of(lambda x: jax.lax.dynamic_slice(
                    x, (start,), (SLAB_EXACT,)))
            else:
                v = jax.lax.dynamic_slice(vals, (jnp.int32(0), start),
                                          (a, SLAB_EXACT))
            return acc + slab_partials(v, c, m), None

        out, _ = jax.lax.scan(
            one_slab, jnp.zeros((ar, num_groups), jnp.float64),
            jnp.arange(0, n, SLAB_EXACT, dtype=jnp.int32))
    # recombine: T_limb * (+-4096**lk / scale_row); every weight is an exact
    # power of two, so every product is exact, and the 2-14 adds per row run
    # Neumaier-compensated — the recombined value is within ~1 ulp of the
    # exact fixed-point total (for int/unit rows below 2**53 it IS exact:
    # integer terms, integer running sums)
    sums = [jnp.zeros((num_groups,), jnp.float64)] * a
    comp = [jnp.zeros((num_groups,), jnp.float64)] * a
    for r, (i, s, lk) in enumerate(layout):
        # 2**(12*lk - k[i]) replaces ldexp(inv[i], 12*lk) — the combined
        # exponent stays in [-1000, 1012], inside _exact_pow2's range
        term = out[r] * (_exact_pow2(jnp.int32(12 * lk) - k[i]) * s)
        t = sums[i] + term
        comp[i] = comp[i] + jnp.where(
            jnp.abs(sums[i]) >= jnp.abs(term),
            (sums[i] - t) + term, (term - t) + sums[i])
        sums[i] = t
    return jnp.stack([s + c for s, c in zip(sums, comp)])


def _row_plan(rows, row_classes):
    """What the limb kernel sums of the rows it is handed, decided from the
    rows themselves: ``first``, the places of the distinct ones (a row
    handed in twice is the same OBJECT twice: SUM(x) and AVG(x) name one
    value row, every COUNT of a column without NULLs the row mask);
    ``same_as[i]``, which of them row ``i`` is; ``nonfinite``, whether a
    distinct row can hold NaN or an infinity and is summed beside three
    0/1 indicator rows: a float row or a scaled decimal read from floats,
    never a ``unit`` row or a row of an integer dtype."""
    first, same_as, seen = [], [], {}
    for i, (row, c) in enumerate(zip(rows, row_classes)):
        j = seen.setdefault(id(row), len(first))
        if j == len(first):
            first.append(i)
        assert row_classes[first[j]] == c, (i, c, row_classes[first[j]])
        same_as.append(j)
    nonfinite = [row_classes[i] != "unit"
                 and bool(jnp.issubdtype(rows[i].dtype, jnp.floating))
                 for i in first]
    return first, same_as, nonfinite


def limb_row_counts(rows, row_classes) -> dict:
    """The rows the caller named, the distinct rows the limb kernel sums
    for them and the indicator rows it sums beside those: static."""
    first, _, nonfinite = _row_plan(rows, row_classes)
    return {"limb_rows_named": len(rows), "limb_rows_summed": len(first),
            "limb_indicator_rows": 3 * sum(nonfinite)}


def _limb_sums_of_named_rows(rows, codes, mask, num_groups, row_classes,
                             interpret: bool, rows_of=None,
                             counts=None) -> jax.Array:
    """``_segmented_sums_limbs`` of the distinct rows among ``rows``
    (``_row_plan``), non-finite safe, fanned back out to every place a row
    was named: values are sanitized, NaN/+Inf/-Inf indicator rows (class
    'unit': 0/1 by construction) are summed alongside the rows that can
    hold one, and IEEE semantics reassembled.  With ``rows_of`` the rows
    are too long to exist beside each other (``segmented_sums_slabwise``):
    ``rows`` are then read by one max-reduction a float row, and the limb
    kernel's loop builds each slab's from ``rows_of(take)``.  ``counts``,
    a caller's dict, is added what was named and what is summed
    (``limb_row_counts``'s names), for the span of its program."""
    from .kernels import ieee_reassemble
    first, same_as, nonfinite = _row_plan(rows, row_classes)
    d = len(first)
    flagged = [j for j in range(d) if nonfinite[j]]
    if counts is not None:
        for name, count in (("limb_rows_named", len(rows)),
                            ("limb_rows_summed", d),
                            ("limb_indicator_rows", 3 * len(flagged))):
            counts[name] = counts.get(name, 0) + count
    classes = [row_classes[i] for i in first] + ["unit"] * (3 * len(flagged))

    def finite_matrix(named):
        """(d + 3 * len(flagged), n) f64: the distinct rows with NaN and
        +-Inf zeroed, then a 0/1 row of each kind for each row that can
        hold one."""
        out = [named[i] for i in first]
        indicators = []
        for j in flagged:
            kinds = (jnp.isnan(out[j]), jnp.isposinf(out[j]),
                     jnp.isneginf(out[j]))
            indicators.extend(kinds)
            out[j] = jnp.where(kinds[0] | kinds[1] | kinds[2], 0.0, out[j])
        return jnp.stack([row.astype(jnp.float64)
                          for row in out + indicators])

    if rows_of is None:
        sums = _segmented_sums_limbs(finite_matrix(rows), codes, mask,
                                     num_groups, classes, interpret)
    else:
        contributes = mask.astype(bool)

        def top(row):
            row = row.astype(jnp.float64)
            return jnp.max(jnp.where(contributes & jnp.isfinite(row),
                                     jnp.abs(row), 0.0))

        zero = jnp.float64(0.0)
        absmax = jnp.stack(
            [top(rows[i]) if row_classes[i] == "float" else zero
             for i in first] + [zero] * (3 * len(flagged)))
        sums = _segmented_sums_limbs(
            None, codes, mask, num_groups, classes, interpret, absmax=absmax,
            slab_of=lambda take: finite_matrix(rows_of(take)))
    out = [sums[j] for j in range(d)]
    for t, j in enumerate(flagged):
        nan_c, pos_c, neg_c = (sums[d + 3 * t + kind] for kind in range(3))
        out[j] = ieee_reassemble(sums[j], nan_c, pos_c, neg_c)
    return jnp.stack([out[j] for j in same_as])


def segmented_sums_fixedpoint(vals, codes: jax.Array,
                              mask: jax.Array, num_groups: int, *,
                              row_classes=None,
                              interpret: bool | None = None,
                              counts=None) -> jax.Array:
    """Limb-decomposed MXU segmented sums (``_segmented_sums_limbs``) of
    the rows of a matrix, or of a sequence of rows, each in its own dtype,
    among which a row named twice is summed once
    (``_limb_sums_of_named_rows``)."""
    if interpret is None:
        interpret = not _backend_is_tpu()
    rows = list(vals)  # a sequence of rows as it is, a matrix as its rows
    cls = ["float"] * len(rows) if row_classes is None else list(row_classes)
    return _limb_sums_of_named_rows(rows, codes, mask, num_groups, cls,
                                    interpret, counts=counts)


def segmented_sums_exact(vals: jax.Array, codes: jax.Array, mask: jax.Array,
                         num_groups: int, *, interpret: bool | None = None
                         ) -> jax.Array:
    """Exact integer-grid segmented sums: the all-'int' special case of
    segmented_sums_fixedpoint (bit-exact whenever sum(|v|) <= 2**53)."""
    return segmented_sums_fixedpoint(
        vals, codes, mask, num_groups,
        row_classes=["int"] * vals.shape[0], interpret=interpret)


def segmented_sums(vals: jax.Array, codes: jax.Array, mask: jax.Array,
                   num_groups: int, *, interpret: bool | None = None
                   ) -> jax.Array:
    """Masked segmented sums of A value rows over a static group domain.

    vals: (A, n) float; codes: (n,) ints in [0, num_groups); mask: (n,) bool.
    Returns (A, num_groups) sums of vals[:, i] over rows with codes[i]==g and
    mask[i]. Jit/trace-safe; static shapes only.

    Non-finite safety: the one-hot contraction computes vals * 0 for other
    groups, and NaN/Inf * 0 == NaN would poison every group. The kernel
    therefore sums sanitized values and per-group NaN/+Inf/-Inf indicator
    rows, and reconstitutes IEEE semantics afterwards.
    """
    if interpret is None:
        interpret = not _backend_is_tpu()
    return _nonfinite_safe(
        lambda v, c, m, g: _segmented_sums_finite(v, c, m, g, interpret)
    )(vals, codes, mask, num_groups)


def _segmented_sums_finite(vals: jax.Array, codes: jax.Array, mask: jax.Array,
                           num_groups: int, interpret: bool) -> jax.Array:
    a, n = vals.shape
    g_pad = max(GROUP_TILE, -(-num_groups // GROUP_TILE) * GROUP_TILE)
    n_pad = -(-n // BLOCK) * BLOCK
    if n_pad != n:
        vals = jnp.pad(vals, ((0, 0), (0, n_pad - n)))
        codes = jnp.pad(codes, (0, n_pad - n))
        mask = jnp.pad(mask, (0, n_pad - n))  # padded rows masked out
    codes = codes.astype(jnp.int32).reshape(1, n_pad)
    mask = mask.astype(jnp.int32).reshape(1, n_pad)
    out_dtype = vals.dtype if jnp.issubdtype(vals.dtype, jnp.floating) \
        else jnp.float64
    grid = n_pad // BLOCK
    # x64 tracing breaks the Mosaic lowering (i64 index maps fail to
    # legalize); trace the compiled call in 32-bit scope.  Interpret mode
    # (tests, f64 oracle dtypes) keeps the caller's x64 setting — the
    # 32-bit scope would silently canonicalize its f64 output to f32.
    import contextlib
    scope = (contextlib.nullcontext() if interpret
             else jax.enable_x64(False))
    with scope:
        out = pl.pallas_call(
            _seg_matmul_kernel,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((1, BLOCK), lambda i: (0, i)),
                pl.BlockSpec((1, BLOCK), lambda i: (0, i)),
                pl.BlockSpec((a, BLOCK), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((a, g_pad), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((a, g_pad), out_dtype),
            interpret=interpret,
        )(codes, mask, vals)
    return out[:, :num_groups]


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret"))
def segmented_sums_jit(vals, codes, mask, num_groups, interpret=None):
    return segmented_sums(vals, codes, mask, num_groups, interpret=interpret)


def segmented_sums_xla_blocked(vals: jax.Array, codes: jax.Array,
                               mask: jax.Array, num_groups: int,
                               block: int = 4096) -> jax.Array:
    """One-hot contraction via an XLA scan over row blocks.

    Same math as the pallas kernel but in plain XLA: Mosaic has no 64-bit
    support, so this is the f64 path on TPU (X64 emulation is exact). The
    per-block one-hot lives only inside the scan body — peak memory is one
    (block, G) tile, not (n, G). Callers handle non-finite values
    (segmented_sums_dispatch wraps with the sanitize/indicator machinery).
    """
    a, n = vals.shape
    out_dtype = vals.dtype if jnp.issubdtype(vals.dtype, jnp.floating) \
        else jnp.float64
    n_pad = -(-max(n, 1) // block) * block
    if n_pad != n:
        vals = jnp.pad(vals, ((0, 0), (0, n_pad - n)))
        codes = jnp.pad(codes, (0, n_pad - n))
        mask = jnp.pad(mask, (0, n_pad - n))
    nb = n_pad // block
    vb = vals.reshape(a, nb, block).transpose(1, 0, 2).astype(out_dtype)
    cb = codes.astype(jnp.int32).reshape(nb, block)
    mb = mask.reshape(nb, block)

    def step(acc, xs):
        v, c, m = xs
        onehot = (c[:, None]
                  == jax.lax.broadcasted_iota(jnp.int32, (block, num_groups), 1))
        onehot = jnp.where(m[:, None], onehot, False).astype(out_dtype)
        return acc + jnp.dot(v, onehot, preferred_element_type=out_dtype), None

    acc0 = jnp.zeros((a, num_groups), dtype=out_dtype)
    out, _ = jax.lax.scan(step, acc0, (vb, cb, mb))
    return out


def _limb_kernel_engaged() -> bool:
    """Whether the static-domain reduction takes the Pallas kernels: on a
    TPU, or under DSQL_PALLAS=force (interpreted off-TPU: a test hook)."""
    return os.environ.get("DSQL_PALLAS") == "force" or _on_tpu()


def _count_kernel_trace(interpret: bool) -> None:
    """Trace-time proof that a program carries the real kernel, not the
    interpreter or the scatter oracle (chip_smoke.py reads it)."""
    if not interpret:
        from ..runtime import telemetry as _tel
        _tel.inc("pallas_kernel_traces")


def segmented_sums_dispatch(vals, codes: jax.Array,
                            mask: jax.Array, num_groups: int,
                            row_classes=None, counts=None) -> jax.Array:
    """Backend policy for the static-domain groupby reduction.  ``vals``
    is an (A, n) matrix, or the A rows as a sequence (each in its own
    dtype: a mask as the bool it is, an integer column as integers).
    ``counts``: a dict the limb kernel adds its row counts to, where it
    is the backend (``_limb_sums_of_named_rows``).

    - DSQL_PALLAS=force: pallas kernels (interpreted off-TPU) — test hook.
    - TPU + a matrix of 32-bit floats: the accumulate-in-place pallas MXU
      kernel.
    - TPU otherwise: the fixed-point limb kernel (_segmented_sums_limbs) —
      bit-exact on unit/int rows, sub-ulp on float rows, and ~40x cheaper
      than the sequential f64 scan it replaced (the scan was the top device
      op in the TPC-H Q1/Q5 profiles, and its 64-bit-emulated matmul loop
      also dominated query compile time).  A row named twice is summed
      once, and only a row that can hold a NaN or an infinity is summed
      beside indicator rows (``_row_plan``).
    - otherwise (CPU/GPU): XLA scatter segment-sum, which is fine there.
    Non-finite safety is applied once for every backend.
    """
    named = isinstance(vals, (list, tuple))
    if not _limb_kernel_engaged():
        if named:
            vals = jnp.stack([row.astype(jnp.float64) for row in vals])
        return reference_segmented_sums(vals, codes, mask, num_groups)
    interpret = not _backend_is_tpu()
    _count_kernel_trace(interpret)
    if named or os.environ.get("DSQL_PALLAS") == "force" \
            or vals.dtype != jnp.float32:
        return segmented_sums_fixedpoint(
            vals, codes, mask, num_groups, row_classes=row_classes,
            interpret=interpret, counts=counts)
    return segmented_sums(vals, codes, mask, num_groups, interpret=interpret)


def _with_nonfinite_rows(vals: jax.Array) -> jax.Array:
    """(4a, n): the rows with NaN and +-Inf zeroed, then a 0/1 indicator
    row of each kind for each of them."""
    isnan = jnp.isnan(vals)
    ispos = jnp.isposinf(vals)
    isneg = jnp.isneginf(vals)
    clean = jnp.where(isnan | ispos | isneg, 0.0, vals)
    return jnp.concatenate([
        clean, isnan.astype(vals.dtype), ispos.astype(vals.dtype),
        isneg.astype(vals.dtype)])


def _nonfinite_safe(backend):
    """Wrap a sanitized-sum backend with NaN/Inf indicator reassembly."""
    def wrapped(vals, codes, mask, num_groups):
        if not jnp.issubdtype(vals.dtype, jnp.floating):
            return backend(vals, codes, mask, num_groups)
        from .kernels import ieee_reassemble
        a = vals.shape[0]
        sums = backend(_with_nonfinite_rows(vals), codes, mask, num_groups)
        return ieee_reassemble(sums[:a], sums[a:2 * a], sums[2 * a:3 * a],
                               sums[3 * a:])
    return wrapped


#: The most the limb kernel's input may take as ONE matrix: the distinct
#: rows among those a caller names and the indicator rows of those that can
#: be non-finite (``_row_plan``), all n wide, in f64.  TPC-H Q1 names 17
#: rows and stacks 21 (6 distinct, 15 indicator rows): 1.0 GB at SF1 (6 M
#: rows), which stays one matrix (it was 68 rows and 3.3 GB before rows
#: were merged); 10.1 GB at SF10 beside 7.8 GB of resident columns, and
#: 2.8 GB behind SF10's first compaction (16.8 M rows): there the rows are
#: built a slab at a time inside the kernel's loop
#: (``segmented_sums_slabwise``).
STACK_BYTES_MAX = 1 << 31


def stack_fits(rows, row_classes, n: int) -> bool:
    counts = limb_row_counts(rows, row_classes)
    stacked = counts["limb_rows_summed"] + counts["limb_indicator_rows"]
    return 8 * stacked * n <= STACK_BYTES_MAX


def segmented_sums_slabwise(rows_of, full_rows, codes: jax.Array,
                            mask: jax.Array, num_groups: int,
                            row_classes, counts=None) -> jax.Array:
    """``segmented_sums_dispatch`` of rows too many and too long to exist
    beside each other (``stack_fits``): ``rows_of(take)`` builds the rows
    of one slab from ``take``, the slab's slice of any full-length array,
    inside the limb kernel's loop, so nothing as long as the input but the
    input exists.  ``full_rows`` are the same rows at full length, as
    expressions: they say which rows are the same row (``_row_plan``: the
    slab's rows are taken at the distinct places only), and the float
    rows among them are read here by one max-reduction each (their grid)
    and never stored.  The same sums, bit for bit, as the stacked path
    gives: the grid, the limbs and the order of accumulation are its own."""
    n = codes.shape[0]
    if not _limb_kernel_engaged() or n <= SLAB_EXACT:
        return segmented_sums_dispatch(list(full_rows), codes, mask,
                                       num_groups, row_classes=row_classes,
                                       counts=counts)
    interpret = not _backend_is_tpu()
    _count_kernel_trace(interpret)
    return _limb_sums_of_named_rows(list(full_rows), codes, mask, num_groups,
                                    list(row_classes), interpret,
                                    rows_of=rows_of, counts=counts)


def reference_segmented_sums(vals, codes, mask, num_groups):
    """XLA scatter-based oracle for tests (where, not multiply, so masked
    NaN rows contribute nothing)."""
    out_dtype = vals.dtype if jnp.issubdtype(vals.dtype, jnp.floating) \
        else jnp.float64
    return jnp.stack([
        jax.ops.segment_sum(
            jnp.where(mask, vals[i].astype(out_dtype), 0), codes, num_groups)
        for i in range(vals.shape[0])])
