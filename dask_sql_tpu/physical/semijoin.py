"""What a SEMI or ANTI join is beside an INNER one, as functions of the two
sides and the match: the planner's form of ``EXISTS``, ``IN (SELECT ..)``
and their negations (plan/binder.py).  ``compiled._Tracer._LogicalJoin``
finds the matches with the formulation its row counts allow, as for any
join; here is what only these two need:

- the name their device work carries (``_join_scope``): ``dsql.semi_build``
  and ``dsql.semi_probe``, so that a device trace tells a subquery's join
  from one that fetches columns (``dsql.join_build`` / ``dsql.join_probe``);
- the rows an ANTI join keeps, ``NOT IN``'s three-valued logic included
  (``_anti_keep``);
- a residual of the form ``build.x OP probe.y``, decided from each key's
  least and greatest ``x`` (``_residual_exist_test``, ``_exist_operands``,
  ``_exists``).

Imports nothing of the compiled tier.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.kernels import unify_string_codes
from ..plan.nodes import RexCall, RexInputRef
from ..table import Column, Table


def _join_scope(jt: str, part: str) -> str:
    """The ``jax.named_scope`` of a join's ``build`` or ``probe`` work."""
    return f"dsql.{'semi' if jt in ('SEMI', 'ANTI') else 'join'}_{part}"


def _anti_keep(match: jax.Array, pvalid: jax.Array, bvalid: jax.Array,
               build_rows: jax.Array, null_aware: bool) -> jax.Array:
    """The probe rows an ANTI join keeps (before the probe's own validity).
    ``NOT IN`` (``null_aware``): any NULL key on the build side empties the
    result; NULL probe keys qualify only when the build is EMPTY (x NOT IN
    (empty) is TRUE for every x — matches ops/join.py and
    PostgreSQL/SQLite)."""
    keep = ~match
    if null_aware:
        build_has_null = (build_rows & ~bvalid).any()
        keep = keep & ~build_has_null & (pvalid | ~build_rows.any())
    return keep


def _residual_exist_test(rel, residual, probe: Table, build: Table):
    """(op, x build Column, y probe Column) for a residual of the form
    ``build.x OP probe.y`` with OP a comparison; None otherwise.
    ``op`` is normalized so the test reads "exists build x with x OP y".
    Floats are excluded (NaN comparison semantics don't survive the
    min/max reduction)."""
    if len(residual) != 1:
        return None
    r = residual[0]
    if not (isinstance(r, RexCall) and r.op in ("<>", "<", "<=", ">", ">=")
            and len(r.operands) == 2
            and all(isinstance(o, RexInputRef) for o in r.operands)):
        return None
    nl = len(rel.left.schema)  # probe IS the left side for SEMI/ANTI
    a, b = r.operands
    if a.index < nl <= b.index:      # pred = y OP x -> exists x SWAP(OP) y
        y_col = probe.columns[a.index]
        x_col = build.columns[b.index - nl]
        op = {"<": ">", ">": "<", "<=": ">=", ">=": "<=", "<>": "<>"}[r.op]
    elif b.index < nl <= a.index:    # pred = x OP y
        x_col = build.columns[a.index - nl]
        y_col = probe.columns[b.index]
        op = r.op
    else:
        return None
    if x_col.stype.is_string != y_col.stype.is_string:
        return None
    for c in (x_col, y_col):
        if not c.stype.is_string and jnp.issubdtype(c.data.dtype,
                                                    jnp.floating):
            return None
    if not x_col.stype.is_string:
        # the min/max reduction runs in int64: uint64 values >= 2^63
        # would wrap on the cast and invert the ordering, and a MIXED
        # uint64/signed pair promotes to float64 (lossy above 2^53) —
        # only pairs whose promotion stays a signed integer are safe
        dt = jnp.promote_types(x_col.data.dtype, y_col.data.dtype)
        if dt == jnp.uint64 or jnp.issubdtype(dt, jnp.floating):
            return None
    return op, x_col, y_col


def _exist_operands(x_col: Column, y_col: Column):
    """The two sides of a SEMI/ANTI residual ``build.x OP probe.y`` on one
    int64 domain (``_residual_exist_test`` admits nothing else)."""
    if x_col.stype.is_string:
        xd, yd = unify_string_codes([x_col, y_col])
    else:
        dt = jnp.promote_types(x_col.data.dtype, y_col.data.dtype)
        xd, yd = x_col.data.astype(dt), y_col.data.astype(dt)
    return xd.astype(jnp.int64), yd.astype(jnp.int64)


def _exists(op: str, mn, mx, y) -> jax.Array:
    """"Some build x with x OP y", from the least and greatest x of y's key."""
    if op == "<>":
        return (mn != y) | (mx != y)
    if op == "<":
        return mn < y
    if op == "<=":
        return mn <= y
    return mx > y if op == ">" else mx >= y
