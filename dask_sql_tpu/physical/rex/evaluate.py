"""REX evaluator: bound expression tree -> Column/Scalar over a Table.

The reference dispatches expression nodes through a Pluggable registry
(/root/reference/dask_sql/physical/rex/convert.py:37-64) with plugins for
RexInputRef, RexLiteral and RexCall; this is the same shape with native rex
nodes.  New expression kinds register via ``RexExecutor.add_plugin``.
"""
from __future__ import annotations

from typing import Union

import jax.numpy as jnp
import numpy as np

from ...plan.nodes import (
    RexCall, RexInputRef, RexLiteral, RexNode, RexParam, RexScalarSubquery,
    RexUdf,
)
from ...table import Column, Scalar, Table
from ...utils import Pluggable
from .cast import cast_value
from .ops import OPERATION_MAPPING


class RexExecutor(Pluggable):
    """Dispatches on rex node class name — extension point for custom rex."""

    @classmethod
    def convert(cls, rex: RexNode, table: Table, executor) -> Union[Column, Scalar]:
        plugin = cls.get_plugin(type(rex).__name__)
        return plugin(rex, table, executor)


def _eval_input_ref(rex: RexInputRef, table: Table, executor):
    return table.columns[rex.index]


def _eval_literal(rex: RexLiteral, table: Table, executor):
    return Scalar(rex.value, rex.stype)


def _eval_param(rex: RexParam, table: Table, executor):
    """Hoisted literal (plan/parameterize.py).  Inside a compiled trace the
    value is the TRACED scalar the program received as a trailing jit
    argument (``compiled._build`` maps each param node to its arg); every
    other executor — eager, SPMD, stats probes — reads the node's carried
    value exactly like a literal, which is correct because those paths key
    their caches on values."""
    vals = getattr(executor, "param_values", None)
    if vals is not None:
        v = vals.get(id(rex))
        if v is not None:
            return Scalar(v, rex.stype)
    return Scalar(rex.value, rex.stype)


def _eval_call(rex: RexCall, table: Table, executor):
    if rex.op == "CAST":
        v = RexExecutor.convert(rex.operands[0], table, executor)
        return cast_value(v, rex.info, table.num_rows)
    args = [RexExecutor.convert(o, table, executor) for o in rex.operands]
    try:
        fn = OPERATION_MAPPING[rex.op]
    except KeyError:
        raise NotImplementedError(f"Operation {rex.op} not implemented") from None
    ctx = table
    return fn(args, rex.stype, ctx)


def _eval_scalar_subquery(rex: RexScalarSubquery, table: Table, executor):
    if getattr(executor, "is_tracer", False):
        # compiled mode: inline the subplan into the same trace; the result
        # broadcasts to a full-length column (NULL-ness must stay a traced
        # mask — Scalar's host-checked ``value is None`` can't carry it)
        return executor.traced_scalar_subquery(rex, table)
    sub = executor.execute(rex.plan)
    if sub.num_rows == 0:
        return Scalar(None, rex.stype)
    if sub.num_rows > 1:
        raise RuntimeError("Scalar subquery returned more than one row")
    col = sub.columns[0]
    vals = col.to_pylist()
    v = vals[0]
    if v is None or (isinstance(v, float) and np.isnan(v)):
        return Scalar(None, rex.stype)
    if jnp.issubdtype(col.data.dtype, jnp.floating):
        # a floating value stays on the device, broadcast to the outer
        # table's length as the compiled tier's is: read back to the host
        # and sent again as a constant it need not come back the same on a
        # TPU, whose float64 is emulated, and ``x = (SELECT MAX(x) ..)``
        # then finds no row (TPC-H Q15 at SF1, one data set in seventeen:
        # PERF.md section 6, PR 43)
        return Column(jnp.broadcast_to(col.data[0], (table.num_rows,)),
                      rex.stype)
    from ...types import python_value_to_physical
    return Scalar(python_value_to_physical(v, rex.stype), rex.stype)


def _eval_udf(rex: RexUdf, table: Table, executor):
    args = [RexExecutor.convert(o, table, executor) for o in rex.operands]
    n = table.num_rows
    # materialize host arrays; UDFs are arbitrary python (the reference ships
    # them to dask workers; here they run on host over gathered numpy data,
    # with jax-traceable UDFs free to return device arrays)
    host_args = []
    for a in args:
        if isinstance(a, Column):
            host_args.append(a.to_numpy())
        else:
            host_args.append(a.to_python())
    if rex.row_udf:
        import pandas as pd
        df = pd.DataFrame({f"a{i}": v for i, v in enumerate(host_args)})
        out = np.asarray([rex.func(row) for _, row in df.iterrows()])
    else:
        out = rex.func(*host_args)
    out = np.asarray(out)
    if np.isscalar(out) or out.ndim == 0:
        from ...types import python_value_to_physical
        return Scalar(python_value_to_physical(out.item(), rex.stype), rex.stype)
    col = Column.from_numpy(out)
    return cast_value(col, rex.stype, n)


RexExecutor.add_plugin("RexInputRef", _eval_input_ref)
RexExecutor.add_plugin("RexLiteral", _eval_literal)
RexExecutor.add_plugin("RexParam", _eval_param)
RexExecutor.add_plugin("RexCall", _eval_call)
RexExecutor.add_plugin("RexScalarSubquery", _eval_scalar_subquery)
RexExecutor.add_plugin("RexUdf", _eval_udf)


def evaluate_rex(rex: RexNode, table: Table, executor=None) -> Union[Column, Scalar]:
    return RexExecutor.convert(rex, table, executor)


def evaluate_predicate(rex: RexNode, table: Table, executor=None):
    """Evaluate a boolean rex to a row mask (NULL -> False, reference
    filter.py:29 fillna(False))."""
    import jax.numpy as jnp

    v = evaluate_rex(rex, table, executor)
    if isinstance(v, Scalar):
        return bool(v.value) if not v.is_null else False
    data = v.data.astype(bool)
    if v.mask is not None:
        data = data & v.mask
    return data
