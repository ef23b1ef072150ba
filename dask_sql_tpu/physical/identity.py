"""What a compiled program IS: the fingerprint of its plan, the layout of
its inputs, the strategy it was traced for and the mesh it runs on.

Everything that has to name a program reads it here and nowhere else: the
program cache and the learned capacities (``programs``, ``caps``), the tier
probe (``tiering``), the persistent store's digest and the XLA module's
name, the profiler's ledger (``runtime/profiler.py``) and the mesh executor
(``parallel/spmd.py``).  ``program_key`` is the one place the key is built.
Imports nothing of the tracer.
"""
from __future__ import annotations

import hashlib
import re
import weakref
from typing import Dict, List, NamedTuple, Optional

import jax

from ..ops.pallas_kernels import _strategy_on_tpu
from ..plan.nodes import (
    LogicalAggregate, LogicalFilter, LogicalJoin, LogicalProject, LogicalSort,
    LogicalTableScan, LogicalUnion, LogicalValues, LogicalWindow, RelNode,
    RexCall, RexInputRef, RexLiteral, RexNode, RexParam, RexScalarSubquery,
)
from ..runtime import program_store as _pstore, telemetry as _tel


class Unsupported(Exception):
    """Plan (or expression) outside the compilable subset."""


# ops whose kernels are host-bound or non-deterministic: never compile
_DENY_OPS = {"RAND", "RAND_INTEGER"}


def _fp_rex(rex: RexNode, context=None, scans=None, params=None,
            seen=None) -> str:
    if params is None:
        params = []
    if isinstance(rex, RexInputRef):
        return f"@{rex.index}"
    if isinstance(rex, RexParam):
        # hoisted literal (plan/parameterize.py): identity is POSITION and
        # type, never the value — every literal variant of a shape shares
        # this fingerprint, and the value rides as a trailing jit argument.
        # The position is the node's index in THIS serialization walk, so
        # the ``params`` list accumulated alongside the text IS the
        # bound-argument order; any caller that serializes the same
        # (sub)plan recovers the same numbering.
        for i, p in enumerate(params):
            if p is rex:
                return f"P{i}:{rex.stype.name}"
        params.append(rex)
        return f"P{len(params) - 1}:{rex.stype.name}"
    if isinstance(rex, RexLiteral):
        return f"L{rex.stype.name}:{rex.value!r}"
    if isinstance(rex, RexCall):
        if rex.op in _DENY_OPS:
            raise Unsupported(rex.op)
        extra = ""
        info = getattr(rex, "info", None)
        if info is not None:
            extra = f"!{getattr(info, 'name', info)}"
        return (f"C{rex.op}{extra}["
                + ",".join(_fp_rex(o, context, scans, params, seen)
                           for o in rex.operands)
                + f"]:{rex.stype.name}")
    if isinstance(rex, RexScalarSubquery) and context is not None:
        # uncorrelated scalar subquery: the subplan joins the cache key and
        # its scans join the input spec; the tracer inlines it as a
        # broadcast 1-row result
        return ("S[" + _fp_plan(rex.plan, context, scans, params, seen)
                + f"]:{rex.stype.name}")
    raise Unsupported(type(rex).__name__)


def _fp_plan(rel: RelNode, context, scans: list, params=None,
             seen=None) -> str:
    """Serialize the plan for cache keying; collects scan tables (and the
    plan's RexParam nodes, in serialization order, into ``params``).  A node
    the plan holds twice (``shared.unify``) is written once: its second
    reference reads ``^k``, the k-th node the walk finished, so its scans
    are listed, and bound, once."""
    if params is None:
        params = []
    if seen is None:
        seen = {}
    back = seen.get(id(rel))
    if back is None:
        text = _fp_node(rel, context, scans, params, seen)
        seen[id(rel)] = len(seen)
        return text
    return f"^{back}"


def _fp_node(rel: RelNode, context, scans: list, params: list,
             seen: dict) -> str:
    t = type(rel).__name__
    schema = ";".join(f"{f.name}:{f.stype.name}" for f in rel.schema)
    if isinstance(rel, LogicalTableScan):
        # snapshot-pin-aware read (runtime/ingest.py): the compiled program
        # binds the tables captured at admission, not a mid-append swap
        entry = context.catalog_entry(rel.schema_name, rel.table_name)
        if entry.table is None:
            raise Unsupported("view scan")
        if entry.table.num_rows == 0:
            raise Unsupported("empty table")
        scans.append(((rel.schema_name, rel.table_name), entry.table,
                      entry.row_valid))
        rv = "+rv" if entry.row_valid is not None else ""
        return f"Scan({rel.schema_name}.{rel.table_name}{rv})[{schema}]"
    if isinstance(rel, LogicalProject):
        body = ",".join(_fp_rex(e, context, scans, params, seen)
                        for e in rel.exprs)
    elif isinstance(rel, LogicalFilter):
        body = _fp_rex(rel.condition, context, scans, params, seen)
    elif isinstance(rel, LogicalAggregate):
        for agg in rel.aggs:
            if agg.udaf is not None:
                raise Unsupported("udaf agg")
            if agg.distinct and (
                    agg.op not in ("COUNT", "SUM", "$SUM0", "AVG",
                                   "MIN", "MAX")
                    or agg.filter_arg is not None or not agg.args):
                # FILTER + DISTINCT: the first occurrence of a value may be
                # filtered away while a later duplicate passes — the
                # first-occurrence dedup mask would undercount
                raise Unsupported("distinct agg shape")
            if agg.op in ("LISTAGG", "BIT_AND", "BIT_OR", "BIT_XOR"):
                raise Unsupported(agg.op)
        body = (f"g={rel.group_keys}|" + ",".join(
            f"{a.op}{'d' if a.distinct else ''}({a.args})f{a.filter_arg}"
            for a in rel.aggs))
    elif isinstance(rel, LogicalJoin):
        if rel.join_type not in ("INNER", "LEFT", "RIGHT", "SEMI", "ANTI"):
            raise Unsupported(rel.join_type)
        # null-aware anti (NOT IN) compiles too; the flag joins the
        # fingerprint so it can't share a program with a plain anti join
        na = "N" if getattr(rel, "null_aware", False) else ""
        cond = ("T" if rel.condition is None
                else _fp_rex(rel.condition, context, scans, params, seen))
        body = f"{rel.join_type}{na}|{cond}"
    elif isinstance(rel, LogicalSort):
        body = (",".join(f"{c.index}{'a' if c.ascending else 'd'}"
                         f"{'nf' if c.effective_nulls_first else 'nl'}"
                         for c in rel.collation)
                + f"|o={rel.offset}|l={rel.limit}")
    elif isinstance(rel, LogicalWindow):
        from ..ops.window import TRACE_SAFE_OPS
        for call in rel.calls:
            if call.op not in TRACE_SAFE_OPS:
                raise Unsupported(f"window op {call.op}")
        body = ";".join(
            f"{call.op}({call.args})p{call.partition}"
            + "o" + ",".join(f"{c.index}{'a' if c.ascending else 'd'}"
                             f"{'nf' if c.effective_nulls_first else 'nl'}"
                             for c in call.order)
            + f"f{call.frame!r}" for call in rel.calls)
    elif isinstance(rel, LogicalUnion):
        body = f"all={rel.all}"
    elif isinstance(rel, LogicalValues):
        body = repr([[lit.value for lit in row] for row in rel.rows])
    else:
        raise Unsupported(type(rel).__name__)
    kids = ",".join(_fp_plan(i, context, scans, params, seen)
                    for i in rel.inputs)
    return f"{t}({body})[{schema}]<{kids}>"


_dict_fp_memo: Dict[int, tuple] = {}


def _dict_fingerprint(arr) -> str:
    """Content hash of a string dictionary, memoized per array object.

    String dictionaries are embedded in the jitted program as constants, so
    they must join the cache key — but by CONTENT, not object identity:
    reloading the same data (new Table, equal dictionaries) must hit the
    cached program instead of recompiling.
    """
    key = id(arr)
    hit = _dict_fp_memo.get(key)
    if hit is not None and hit[0]() is arr:
        return hit[1]
    h = hashlib.blake2b(digest_size=16)
    h.update(str(len(arr)).encode())
    for s in arr:
        b = str(s).encode()
        # length prefix, not a separator: elements may contain any byte, so
        # a separator could make ["a\0", "b"] and ["a", "\0b"] collide
        h.update(str(len(b)).encode() + b":" + b)
    fp = h.hexdigest()
    _dict_fp_memo[key] = (
        weakref.ref(arr, lambda _r, k=key: _dict_fp_memo.pop(k, None)), fp)
    return fp


def _fp_inputs(scans: list) -> tuple:
    out = []
    for _, tbl, row_valid in scans:
        # keyed on shapes/dtypes + dictionary CONTENT (not table identity):
        # new data with the same layout reuses the compiled program; any
        # dictionary change reshapes the key because the dictionaries are
        # baked into the program as constants
        cols = tuple(
            (c.data.shape, str(c.data.dtype), c.mask is not None,
             None if c.dictionary is None else _dict_fingerprint(c.dictionary))
            for c in tbl.columns)
        out.append((cols, row_valid is not None))
    return tuple(out)


def _mesh_signature(context) -> str:
    """Sharding layout component of program identity: tracing under a
    device mesh lets GSPMD bake in a different partitioning, so a program
    (or persisted executable) compiled with a mesh must never be served to
    a mesh-less context or a different mesh shape — and vice versa."""
    mesh = getattr(context, "mesh", None)
    if mesh is None:
        return ""
    return "x".join(f"{n}:{s}"
                    for n, s in zip(mesh.axis_names, mesh.devices.shape))


def _flatten_tables(scans) -> List[jax.Array]:
    """A program's table arguments, in the order ``_fp_inputs`` keyed them."""
    flat: List[jax.Array] = []
    for _, tbl, row_valid in scans:
        for c in tbl.columns:
            flat.append(c.data)
            if c.mask is not None:
                flat.append(c.mask)
        if row_valid is not None:
            flat.append(row_valid)
    return flat


def _maybe_parameterize(plan: RelNode, count: bool = True):
    """Hoist literals into runtime arguments (plan/parameterize.py) unless
    the DSQL_PARAM_PLANS kill switch is off, after the subtrees the plan
    holds more than once by value were made one node each
    (``shared.unify``: a CTE read twice is then hoisted, keyed and traced
    once; the identity on a plan without a repeat).  Idempotent — re-entries
    from the degradation ladder / background compiles unify and hoist
    nothing and count nothing; probes pass ``count=False`` so a tier
    prediction never inflates the execution counters."""
    from ..plan.parameterize import param_plans_enabled, parameterize_plan
    from .shared import unify
    if not param_plans_enabled():
        return plan
    plan, replaced = unify(plan)
    if replaced and count:
        _tel.inc("param_plan_shared_subtrees", replaced)
    new, hoisted, in_subqueries = parameterize_plan(plan)
    if hoisted and count:
        _tel.inc("param_plans")
        _tel.inc("param_literals_hoisted", hoisted)
        if in_subqueries:
            _tel.inc("param_plan_subquery_hoisted", in_subqueries)
    return new


class ProgramKey(NamedTuple):
    plan: RelNode     # the plan the program is traced from
    key: tuple        # (plan fingerprint, input layout, strategy, mesh)
    scans: list       # the scanned tables, in the key's (and bind) order
    params: list      # the plan's RexParam nodes, in bound-argument order
    host_sort: Optional[LogicalSort]  # the ORDER BY the host applies


def program_key(plan: RelNode, context) -> ProgramKey:
    """The key of the ONE program ``plan`` (a whole small plan, or one
    stage of a graph) runs as, and the plan that program is traced from.
    Raises ``Unsupported`` for a plan outside the compilable subset.

    Off the TPU strategy a terminal ORDER BY / LIMIT is not part of the
    program: ``_materialize`` fetches the result and cuts it to its true
    row count anyway, and sorting those rows on the host costs
    microseconds where the in-program lexsort pays O(padded n) per key
    (~8 ms per key per 100k padded rows on XLA:CPU).  On a TPU the sort
    stays in the program, so that everything before the one fetch fuses.

    The strategy joins the key: tracing picks backend-specific
    formulations (merge or hash-table join), and with content-based input
    fingerprints a program — or an unsupported verdict — traced for one
    backend could otherwise replay on another."""
    on_tpu = bool(_strategy_on_tpu())
    host_sort = None
    if not on_tpu and isinstance(plan, LogicalSort):
        host_sort, plan = plan, plan.input
    scans: list = []
    params: list = []
    plan_fp = _fp_plan(plan, context, scans, params)
    key = (plan_fp, _fp_inputs(scans), on_tpu, _mesh_signature(context))
    return ProgramKey(plan, key, scans, params, host_sort)


# stage-boundary temp names embed per-process table uids (_stage_table_name)
# but the compiled program is uid-independent — it depends only on plan
# shape and input layout.  For the cross-process store key, boundary names
# are rewritten to position-stable placeholders so two processes running
# the same query over the same-layout data address the same entry.
_BOUNDARY_NAME_RE = re.compile(r"__split__\.t[0-9a-f]{16}")


def _canonical_program_key(base_key):
    plan_fp = base_key[0]
    mapping: Dict[str, str] = {}

    def sub(m):
        return mapping.setdefault(m.group(0), f"__split__.#{len(mapping)}")

    return (_BOUNDARY_NAME_RE.sub(sub, plan_fp),) + tuple(base_key[1:])


def _pstore_digest(base_key) -> str:
    return _pstore.get_store().digest(_canonical_program_key(base_key))


def _program_name(plan: RelNode, base_key) -> str:
    """The name a program's XLA module carries (``jit_<name>`` on a
    trace's ``XLA Modules`` line).  XLA's persistent-cache key includes it,
    so it has to come out the same in every process for the same program:
    the root node's type and the canonical digest, never a table uid or an
    ``id()``."""
    return f"dsql_{type(plan).__name__}_{_pstore_digest(base_key)[:8]}"
