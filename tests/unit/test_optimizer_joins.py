"""Join-reorder unit tests: the rewrite must fire only when the ORIGINAL
tree genuinely contains a stranded (cross) step.

The reference's HepPlanner never reorders connected trees either (its
JoinCommuteRule/JoinAssociateRule set is not enabled in dask-sql's default
program); reorder_joins exists to rescue comma-FROM queries whose textual
order strands a leaf, and must leave connected plans — including BUSHY
ones — exactly as written (a review's finding).
"""
from dask_sql_tpu.plan.nodes import (
    Field, LogicalJoin, LogicalTableScan, RexCall, RexInputRef,
)
from dask_sql_tpu.plan.optimizer import reorder_joins
from dask_sql_tpu.types import BIGINT, BOOLEAN


def _scan(table, *cols):
    return LogicalTableScan(schema_name="root", table_name=table,
                            schema=[Field(c, BIGINT) for c in cols])


def _eq(i, j):
    return RexCall(op="=", operands=[RexInputRef(i, BIGINT),
                                     RexInputRef(j, BIGINT)],
                   stype=BOOLEAN)


def test_connected_bushy_tree_not_rewritten():
    """A ⋈ (B ⋈ C on b=c) on a=c is fully connected; linearizing its leaf
    list as a left-deep chain would falsely count B as stranded (b=c needs C
    which 'hasn't joined yet') and rewrite a plan that needs no help."""
    a, b, c = _scan("a", "a1"), _scan("b", "b1"), _scan("c", "c1")
    inner = LogicalJoin(left=b, right=c, join_type="INNER",
                        condition=_eq(0, 1),
                        schema=list(b.schema) + list(c.schema))
    root = LogicalJoin(left=a, right=inner, join_type="INNER",
                       condition=_eq(0, 2),
                       schema=list(a.schema) + list(inner.schema))
    out = reorder_joins(root)
    assert out == root  # structurally untouched: still bushy, same conds


def test_stranded_chain_still_rewritten():
    """(A ⋈ B cross) ⋈ C with conditions a=c and b=c at the top is the
    comma-FROM shape the rewrite exists for: the textual order strands B."""
    a, b, c = _scan("a", "a1"), _scan("b", "b1"), _scan("c", "c1")
    cross = LogicalJoin(left=a, right=b, join_type="CROSS", condition=None,
                        schema=list(a.schema) + list(b.schema))
    cond = RexCall(op="AND", operands=[_eq(0, 2), _eq(1, 2)], stype=BOOLEAN)
    root = LogicalJoin(left=cross, right=c, join_type="INNER", condition=cond,
                       schema=list(cross.schema) + list(c.schema))
    out = reorder_joins(root)
    assert out is not root

    def no_cross(rel):
        if isinstance(rel, LogicalJoin):
            assert rel.join_type != "CROSS" and rel.condition is not None
            for i in rel.inputs:
                no_cross(i)

    # the rewrite's entire purpose: no stranded steps remain
    while not isinstance(out, LogicalJoin):
        out = out.inputs[0]
    no_cross(out)
