"""The compiled tier's layout (physical/compiled.py's docstring has the map):
each decision has one module, the imports point one way, a program's key is
built at one site, and a tier probe names the program the request's path
then finds."""
import ast
import os

import numpy as np
import pandas as pd
import pytest

import dask_sql_tpu
from dask_sql_tpu import Context
from dask_sql_tpu.physical import compiled, identity
from dask_sql_tpu.runtime import telemetry as tel
from dask_sql_tpu.sql.parser import parse_sql

PACKAGE = os.path.dirname(os.path.abspath(dask_sql_tpu.__file__))

#: the modules beneath ``physical/compiled.py``, and the three outside the
#: executor that used to reach into it for a program's identity
BENEATH = ["physical/identity.py", "ops/hashing.py", "physical/caps.py",
           "physical/traced.py", "physical/joins.py",
           "physical/aggregates.py",
           "physical/semijoin.py", "physical/shared.py",
           "physical/programs.py", "physical/stage_exec.py",
           "physical/tiering.py", "physical/stages.py",
           "runtime/profiler.py", "runtime/system_tables.py",
           "parallel/spmd.py"]
#: who may import the top of the graph: the surfaces that run a query
ABOVE = {"context.py", "server/app.py", "physical/streaming.py",
         "physical/rel/custom.py"}


def _imports(relative_path):
    """Absolute dotted names of everything a module of the package imports
    (function-level imports too; ``from . import x`` names ``x``)."""
    path = os.path.join(PACKAGE, relative_path)
    with open(path) as f:
        tree = ast.parse(f.read())
    package = ["dask_sql_tpu"] + relative_path.split("/")[:-1]
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else []
            module = ".".join(base + ([node.module] if node.module else []))
            imported.add(module)
            imported |= {f"{module}.{alias.name}" for alias in node.names}
    return imported


def _package_files():
    for root, _, files in os.walk(PACKAGE):
        for name in files:
            if name.endswith(".py"):
                yield os.path.relpath(os.path.join(root, name), PACKAGE)


@pytest.mark.parametrize("module", BENEATH)
def test_nothing_beneath_the_tracer_imports_it(module):
    assert not {name for name in _imports(module)
                if name.startswith("dask_sql_tpu.physical.compiled")}


def test_only_the_query_surfaces_import_the_compiled_tier():
    importers = {path for path in _package_files()
                 if path != "physical/compiled.py"
                 and any(name.startswith("dask_sql_tpu.physical.compiled")
                         for name in _imports(path))}
    assert importers == ABOVE


def test_a_program_key_is_built_at_one_site():
    """The key's last component is the mesh signature: whoever calls
    ``_mesh_signature`` builds a key."""
    callers = set()
    for path in _package_files():
        with open(os.path.join(PACKAGE, path)) as f:
            tree = ast.parse(f.read())
        for fn in ast.walk(tree):
            if isinstance(fn, ast.FunctionDef) and any(
                    isinstance(n, ast.Call)
                    and getattr(n.func, "id", getattr(n.func, "attr", ""))
                    == "_mesh_signature" for n in ast.walk(fn)):
                callers.add((path, fn.name))
    assert callers == {("physical/identity.py", "program_key")}


def test_the_request_path_stays_readable():
    """The tracer and the entry in 1,300 lines (ISSUE 46: the formulations
    it chooses among live beneath it), the request's path in 120 (ISSUE
    29)."""
    with open(os.path.join(PACKAGE, "physical/compiled.py")) as f:
        source = f.read()
    assert len(source.splitlines()) <= 1300
    single, = [n for n in ast.parse(source).body
               if isinstance(n, ast.FunctionDef)
               and n.name == "_execute_single"]
    assert single.end_lineno - single.lineno + 1 <= 120


def test_the_flags_round_trip_through_their_one_home():
    """A hand-built trace (two sites, an ``ord*`` hint, a ``runs`` hint,
    two tables, interleaved as a plan would): ``read`` hands each reader
    what the offsets it used to count by hand gave it."""
    import jax.numpy as jnp

    from dask_sql_tpu.physical.traced import ProgramFlags, read
    from dask_sql_tpu.runtime.statistics import RUN_GROUPS_TAG
    trace = ProgramFlags()
    trace.site("agg0", 6000, False, 2048, jnp.int64(1500))
    trace.hint(RUN_GROUPS_TAG, jnp.array(False))        # refuted
    trace.fallback(jnp.array(False))
    trace.direct(jnp.array(True))
    trace.hint("ord1r", jnp.array(True))                # kept
    trace.ordered_dense += 1
    trace.site("cmp0", 6000, False, 1024, jnp.int64(77))
    trace.direct(jnp.array(False))
    trace.fallback(jnp.array(True))
    trace.join_rows += 6040
    meta = trace.meta()
    assert meta == {
        "ngroup_caps": [2048, 1024],
        "agg_sites": [(6000, False, "agg0"), (6000, False, "cmp0")],
        "join_rows": 6040, "limb_rows": {}, "hash_table_joins": 2,
        "span_tables": 0, "semi_joins": 0, "scalar_subqueries": 0,
        "shared_subplans": 0, "ordered": ["ord1r"], "ordered_dense": 1,
        "run_groupbys": 1}
    flags = np.asarray(trace.pack(jnp.int64(42)))
    # eager, count, the sites' counts, the joins' hints before the GROUP
    # BYs' (a set bit: refuted), a bit a table
    assert flags.dtype == np.int64
    assert flags.tolist() == [1, 42, 1500, 77, 0, 1, 1, 0]
    said = read(meta, flags)
    sites = len(meta["agg_sites"])
    tags = meta["ordered"] + [RUN_GROUPS_TAG] * meta["run_groupbys"]
    assert (said.eager, said.count) == (flags[0], flags[1])
    assert list(said.site_counts) == list(flags[2:][:sites])
    assert said.refuted == list(zip(tags, flags[2 + sites:][:len(tags)]))
    assert said.refuted == [("ord1r", 0), (RUN_GROUPS_TAG, 1)]
    assert list(said.direct) \
        == list(flags[len(flags) - meta["hash_table_joins"]:])
    # a program from before a segment existed has no key for it
    bare = read({"agg_sites": meta["agg_sites"]}, flags[:4])
    assert list(bare.site_counts) == [1500, 77]
    assert bare.refuted == [] and len(bare.direct) == 0


def test_nothing_but_the_ledger_subscripts_a_flags_vector():
    found = set()
    for path in _package_files():
        with open(os.path.join(PACKAGE, path)) as f:
            tree = ast.parse(f.read())
        if any(isinstance(n, ast.Subscript)
               and getattr(n.value, "id", getattr(n.value, "attr", ""))
               == "flags" for n in ast.walk(tree)):
            found.add(path)
    assert found == {"physical/traced.py"}


def test_the_tracer_chooses_a_formulation_and_lowers_none():
    """What ``_Tracer`` has is a method a plan node, the choices and the
    subquery hook: a join's or a grouped aggregate's lowering is a
    function of ``physical/joins.py`` / ``physical/aggregates.py``."""
    methods = {name for name, value in vars(compiled._Tracer).items()
               if callable(value) and not name.startswith("__")}
    nodes = {name for name in methods if name.startswith("_Logical")}
    assert methods - nodes == {"run", "traced_scalar_subquery",
                               "_maybe_compact", "_ordered_hint",
                               "_build_tag"}


def test_the_benchmark_finds_its_names_on_the_compiled_tier():
    """chipbench/ and tests/chipbench/ reach for these seven; the caches
    are the owners' objects, not copies (the benchmark clears them in
    place)."""
    from dask_sql_tpu.physical import caps, programs, stage_exec, tiering
    assert compiled._cache is programs._cache
    assert compiled._learned_caps is caps._learned_caps
    assert compiled._partition_plan is stage_exec._partition_plan
    assert compiled.inflight_background_compiles \
        is tiering.inflight_background_compiles
    assert compiled.SORT_ROWS_MAX > compiled.LEXSORT_ROWS_MAX > 0
    assert compiled.stats["compiles"] >= 0


# --- one key for the probe and for the path --------------------------------

@pytest.fixture()
def context():
    rng = np.random.RandomState(3)
    c = Context()
    c.create_table("fact", pd.DataFrame({
        "k": rng.randint(0, 40, 4000), "g": rng.randint(0, 7, 4000),
        "v": rng.rand(4000)}))
    c.create_table("dim", pd.DataFrame({
        "k": np.arange(40), "w": np.arange(40) * 0.5}))
    return c


#: case: (text, environment, the literal of the second arrival)
PLANS = {
    "whole_plan": ("SELECT k, SUM(v) AS s FROM fact WHERE v > {x} GROUP BY k",
                   {}, 0.5),
    "order_by_off_the_tpu": (
        "SELECT k, v FROM fact WHERE v > {x} ORDER BY v DESC LIMIT 5", {},
        0.5),
    # the same literal again: a boundary table's name is a digest of the
    # values below it, so a stage that scans one is another program at
    # another literal, and a probe looks at the leaf stages only
    "staged": ("SELECT f.g, SUM(f.v * d.w) AS s FROM fact f JOIN dim d "
               "ON f.k = d.k WHERE f.v > {x} GROUP BY f.g",
               {"DSQL_STAGE_HEAVY": "1"}, 0.25),
    # the capacity the program is keyed under starts from a statistics
    # hint, not from what was learned
    "hinted_capacity": (
        "SELECT g, COUNT(*) AS n FROM fact WHERE v > {x} GROUP BY g",
        {"DSQL_ADAPTIVE": "1"}, 0.5),
}


@pytest.mark.skipif(os.environ.get("DSQL_COMPILE") == "0",
                    reason="probes the compiled tier")
@pytest.mark.parametrize("case", sorted(PLANS))
def test_tier_probe_says_compiled_exactly_when_the_run_is_a_hit(
        case, context, monkeypatch):
    text, env, again = PLANS[case]
    for name, value in env.items():
        monkeypatch.setenv(name, value)

    def plan(x):
        sql = text.format(x=x)
        return context._get_plan(parse_sql(sql)[0].query, sql)

    def run(x):
        before = tel.REGISTRY.counters()
        assert compiled.try_execute_compiled(plan(x), context) is not None
        after = tel.REGISTRY.counters()
        return {k: after.get(k, 0) - before.get(k, 0)
                for k in ("compiles", "hits", "stage_graphs",
                          "stats_cap_hints")}

    assert compiled.tier_probe(plan(0.25), context) == "compiled-cold"
    cold = run(0.25)
    assert cold["compiles"] >= 1 and cold["hits"] == 0
    assert (cold["stage_graphs"] > 0) == (case == "staged")
    assert (cold["stats_cap_hints"] > 0) == (case == "hinted_capacity")
    # another literal of the same shape: the same program
    counted = tel.REGISTRY.counters()
    assert compiled.tier_probe(plan(again), context) == "compiled"
    assert tel.REGISTRY.counters() == counted   # a prediction counts nothing
    warm = run(again)
    assert warm["compiles"] == 0 and warm["hits"] >= 1
    if case == "order_by_off_the_tpu":
        pk = identity.program_key(plan(again), context)
        assert pk.host_sort is not None and pk.plan is pk.host_sort.input
