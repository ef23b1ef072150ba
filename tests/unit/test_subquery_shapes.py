"""TPC-H's nested-subquery shapes (Q4, Q15, Q18: ``chipbench/shapes``; Q20,
which no cell runs, is ``test_q20_promotion.py``) on the compiled tier under
the TPU strategy: ``EXISTS`` and ``IN (SELECT ..)`` as SEMI joins, a scalar
``MAX`` over a CTE inlined into the program.  Each answers as its pandas
reference, one program serves every parameter set of a capacity class, and
``dispatch`` says what the program holds of subqueries."""
import importlib

import jax
import numpy as np
import pandas as pd
import pytest

from chipbench.data import tpch_gen
from dask_sql_tpu import Context
from dask_sql_tpu.physical import compiled as cm, programs
from dask_sql_tpu.physical.caps import _learned_caps

#: lineitem has 360 000 rows here: joins and compaction sites engage
SF = 0.06

#: shape: (SEMI / ANTI joins, inlined scalar subqueries, references to a
#: subtree the plan held twice that its one trace answered) of its program
SHAPES = {"q4": (1, 0, 0), "q15": (0, 1, 1), "q18": (1, 0, 0)}


def _shape(name):
    return importlib.import_module("chipbench.shapes." + name)


@pytest.fixture(scope="module")
def tpch():
    frames = tpch_gen.generate(SF, 43)
    ctx = Context()
    for name, frame in frames.items():
        ctx.create_table(name, frame)
    return ctx, frames


@pytest.fixture
def tpu_strategy(monkeypatch):
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    monkeypatch.delenv("DSQL_CAPS_FILE", raising=False)
    programs._cache.clear()
    _learned_caps.clear()


def _assert_answer(shape, got, frames, params):
    want = shape.reference(frames, **params)
    assert len(got) == len(want)
    for column in want.columns:
        a, b = got[column].to_numpy(), want[column].to_numpy()
        if a.dtype.kind == "f":
            np.testing.assert_allclose(a, b.astype(float), rtol=1e-9)
        else:
            assert (pd.Series(a).astype(str).to_numpy()
                    == pd.Series(b).astype(str).to_numpy()).all(), column


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_a_shape_answers_as_its_reference_at_both_ends_of_its_space(
        tpch, tpu_strategy, name):
    ctx, frames = tpch
    shape = _shape(name)
    for i in (shape.FIRST, 0, shape.SPACE - 1):
        params = shape.params_at(i)
        got = ctx.sql(shape.SQL.format(**params), return_futures=False)
        assert ctx.last_report.tier == "compiled"
        _assert_answer(shape, got, frames, params)
        # a hundredth of SF1's orders: none passes Q18's largest quantity
        assert len(got) > 0 or (name, i) == ("q18", shape.SPACE - 1)


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_one_program_serves_new_parameters_and_says_what_it_holds(
        tpch, tpu_strategy, name):
    ctx, frames = tpch
    shape = _shape(name)
    ctx.sql(shape.SQL.format(**shape.params_at(shape.FIRST)),
            return_futures=False)
    compiles = cm.stats["compiles"] + cm.stats["recompiles"]
    for i in (shape.FIRST + 1, shape.FIRST + 2):
        params = shape.params_at(i)
        got = ctx.sql(shape.SQL.format(**params), return_futures=False)
        _assert_answer(shape, got, frames, params)
        report = ctx.last_report
        assert report.tier == "compiled"
        span, = [s for s in report.root.walk() if s.name == "dispatch"]
        assert (span.attrs["semi_joins"], span.attrs["scalar_subqueries"],
                span.attrs["shared_subplans"]) == SHAPES[name]
    assert cm.stats["compiles"] + cm.stats["recompiles"] == compiles
    if name == "q15":
        # the CTE the text reads twice is in the program once: one group-by
        # and, lineitem having 360 000 rows here, one compaction below it
        tags = [sorted(tag for _, _, tag in e.meta["agg_sites"])
                for e in programs._cache.values()
                if getattr(e, "name", None) == span.attrs["program"]
                and "agg_sites" in e.meta]
        assert ["agg0", "cmp0"] in tags
        assert all(t.count("agg0") == 1 and "agg1" not in t for t in tags)

def _scopes(jaxpr, found=None, inside=()):
    """{``dsql.`` scope: the primitives of the equations under it}."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        stack = inside + tuple(str(eqn.source_info.name_stack).split("/"))
        for part in stack:
            if part.startswith("dsql."):
                found.setdefault(part, set()).add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scopes(sub, found, stack)
    return found


def _spy_on_build(monkeypatch):
    """The jaxpr of every program the compiled tier runs from here on."""
    jaxprs = []
    build = cm._build

    def spy(*args, **kwargs):
        entry = build(*args, **kwargs)
        jitted = entry.fn

        def fn(*flat):
            jaxprs.append(jax.make_jaxpr(jitted)(*flat).jaxpr)
            return jitted(*flat)

        entry.fn = fn
        return entry

    monkeypatch.setattr(cm, "_build", spy)
    return jaxprs


@pytest.mark.parametrize("strategy", ["tpu", "host"])
def test_a_semi_join_has_scopes_of_its_own_on_the_device(tpch, monkeypatch,
                                                         strategy):
    """Q4's program holds one join, the SEMI join of its ``EXISTS``: its
    work reads ``dsql.semi_*`` in a device trace, in the merge and in the
    hash-table formulation; Q15's INNER join keeps ``dsql.join_*``."""
    monkeypatch.setenv("DSQL_STRATEGY", strategy)
    programs._cache.clear()
    _learned_caps.clear()
    jaxprs = _spy_on_build(monkeypatch)
    ctx, _ = tpch
    found = {}
    for name in ("q4", "q15"):
        del jaxprs[:]
        shape = _shape(name)
        ctx.sql(shape.SQL.format(**shape.params_at(shape.FIRST + 7)),
                return_futures=False)
        found[name] = {s for s in _scopes(jaxprs[-1])
                       if s.split(".")[1].split("_")[0] in ("semi", "join")}
    assert found["q4"] == {"dsql.semi_build", "dsql.semi_probe"}
    assert found["q15"] == {"dsql.join_build", "dsql.join_probe"}

