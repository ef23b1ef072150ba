"""The explicit SPMD stage executor (parallel/spmd.py) on the 8-device
CPU mesh: exchange / partial-aggregate primitives against pandas oracles,
end-to-end sharded queries with counters proving the sharded path served
them, pad-row and NULL-key invisibility, and cross-process program-store
round-trips of sharded stage programs.

The module name contains "spmd" so the conftest DSQL_MESH=0 pin does not
apply — these tests exercise the live multi-chip path on purpose.
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest
from jax.sharding import PartitionSpec as P

from dask_sql_tpu import Context
from dask_sql_tpu.parallel import exchange as X
from dask_sql_tpu.parallel import partial_agg as PA
from dask_sql_tpu.parallel.mesh import ROW_AXIS, default_mesh, row_sharding
from dask_sql_tpu.runtime import telemetry as tel

from jax import shard_map


@pytest.fixture(scope="module")
def mesh():
    m = default_mesh()
    if m.devices.size < 2:
        pytest.skip("needs a multi-device mesh")
    return m


def _shard(mesh, x):
    return jax.device_put(jnp.asarray(x), row_sharding(mesh))


def _spmd_deltas(c0):
    now = tel.REGISTRY.counters()
    return {k: v - c0.get(k, 0) for k, v in now.items()
            if k.startswith("spmd_") and v != c0.get(k, 0)}


# ---------------------------------------------------------------------------
# exchange primitives (inside shard_map, where the executor uses them)
# ---------------------------------------------------------------------------

def test_exchange_routes_by_code_and_preserves_rows(mesh):
    n_dev = int(mesh.devices.size)
    n = 16 * n_dev
    rng = np.random.RandomState(0)
    codes = rng.randint(0, 37, n).astype(np.int64)
    # every 5th row dead (code -1): must never resurface as a live row
    codes[::5] = -1
    payload = np.arange(n, dtype=np.float64)

    def body(c, p):
        c2, (p2,) = X.exchange(c, (p,), n_dev)
        return c2, p2

    wrapped = shard_map(body, mesh=mesh, in_specs=P(ROW_AXIS),
                        out_specs=P(ROW_AXIS))
    c2, p2 = wrapped(_shard(mesh, codes), _shard(mesh, payload))
    c2, p2 = np.asarray(c2), np.asarray(p2)

    live = c2 >= 0
    # routing: every live row landed on the device owning code % n_dev
    per_dev = np.split(c2, n_dev)
    for dev, chunk in enumerate(per_dev):
        chunk = chunk[chunk >= 0]
        assert (chunk % n_dev == dev).all()
    # conservation: the live (code, payload) multiset is exactly preserved
    want = sorted(zip(codes[codes >= 0], payload[codes >= 0]))
    got = sorted(zip(c2[live], p2[live]))
    assert got == want


def test_exchange_bytes_counts_payload_and_codes(mesh):
    n_dev = int(mesh.devices.size)
    codes = jnp.zeros(16, dtype=jnp.int64)   # one device's LOCAL shard
    pay = (jnp.zeros(16, dtype=jnp.float64),)
    # send-buffer volume across the whole mesh: each device scatters a
    # (n_dev, local) buffer per array -> size * itemsize * n_dev^2
    assert (X.exchange_bytes(codes, pay, n_dev)
            == 16 * 8 * 2 * n_dev * n_dev)


def test_shard_replicated_round_trip(mesh):
    n_dev = int(mesh.devices.size)
    k = n_dev + 3  # not divisible: forces padding

    def body(_):
        v = jnp.arange(k, dtype=jnp.float64) * 2.0
        out, kp = X.shard_replicated(v, n_dev)
        assert kp % n_dev == 0
        return out

    wrapped = shard_map(body, mesh=mesh, in_specs=P(ROW_AXIS),
                        out_specs=P(ROW_AXIS))
    out = np.asarray(wrapped(_shard(mesh, np.zeros(n_dev))))
    np.testing.assert_allclose(out[:k], np.arange(k) * 2.0)


# ---------------------------------------------------------------------------
# partial-aggregate combine trees
# ---------------------------------------------------------------------------

def test_global_sum_count_match_pandas_with_nulls(mesh):
    n_dev = int(mesh.devices.size)
    n = 8 * n_dev
    rng = np.random.RandomState(1)
    vals = rng.rand(n)
    ok = rng.rand(n) > 0.3  # dead rows: NULLs and pad rows alike

    def body(v, m):
        s, c = PA.global_sum(v, m, True)
        return X.shard_replicated(jnp.stack([s, c.astype(jnp.float64)]),
                                  n_dev)[0]

    wrapped = shard_map(body, mesh=mesh, in_specs=P(ROW_AXIS),
                        out_specs=P(ROW_AXIS))
    out = np.asarray(wrapped(_shard(mesh, vals), _shard(mesh, ok)))
    np.testing.assert_allclose(out[0], vals[ok].sum(), rtol=1e-12)
    assert int(out[1]) == int(ok.sum())


def test_global_minmax_ignores_dead_rows(mesh):
    n_dev = int(mesh.devices.size)
    n = 8 * n_dev
    rng = np.random.RandomState(2)
    vals = rng.randint(-50, 50, n).astype(np.int64)
    ok = np.ones(n, dtype=bool)
    ok[vals == vals.min()] = False  # kill the extremes: they must vanish
    ok[vals == vals.max()] = False

    def body(v, m):
        lo = PA.global_minmax(v, m, is_min=True, sharded=True)
        hi = PA.global_minmax(v, m, is_min=False, sharded=True)
        return X.shard_replicated(jnp.stack([lo, hi]), n_dev)[0]

    wrapped = shard_map(body, mesh=mesh, in_specs=P(ROW_AXIS),
                        out_specs=P(ROW_AXIS))
    out = np.asarray(wrapped(_shard(mesh, vals), _shard(mesh, ok)))
    assert int(out[0]) == int(vals[ok].min())
    assert int(out[1]) == int(vals[ok].max())


# ---------------------------------------------------------------------------
# end-to-end sharded SQL, counters as the proof of path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def spmd_ctx(mesh):
    rng = np.random.RandomState(7)
    n = 8 * int(mesh.devices.size) + 5  # NOT divisible: pad rows exist
    fact = pd.DataFrame({
        "k": rng.randint(0, 20, n).astype(np.int64),
        "grp": rng.randint(0, 4, n).astype(np.int64),
        "v": np.round(rng.rand(n), 6),
    })
    # NULLs in both an aggregate input and a group key
    fact.loc[fact.index[::7], "v"] = np.nan
    gk = fact["grp"].astype("float64")
    gk[fact.index[::11]] = np.nan
    fact["gk"] = gk.astype("Int64")
    dim = pd.DataFrame({"k": np.arange(20, dtype=np.int64),
                        "w": np.round(np.arange(20) * 0.25, 6)})
    ctx = Context(mesh=mesh)
    ctx.create_table("fact", fact)
    ctx.create_table("dim", dim)
    return ctx, fact, dim


def test_global_agg_pad_rows_invisible(spmd_ctx):
    ctx, fact, _ = spmd_ctx
    c0 = tel.REGISTRY.counters()
    got = ctx.sql("SELECT COUNT(*) AS n, SUM(v) AS s, AVG(v) AS a "
                  "FROM fact", return_futures=False)
    d = _spmd_deltas(c0)
    assert d.get("spmd_queries", 0) == 1, d
    assert d.get("spmd_fallbacks", 0) == 0, d
    # COUNT(*) counts real rows only — pad rows from the non-divisible
    # shard layout must be invisible
    assert int(got["n"][0]) == len(fact)
    np.testing.assert_allclose(float(got["s"][0]), fact["v"].sum(),
                               rtol=1e-9)
    np.testing.assert_allclose(float(got["a"][0]),
                               fact["v"].mean(), rtol=1e-9)


def test_groupby_null_keys_match_pandas(spmd_ctx):
    ctx, fact, _ = spmd_ctx
    c0 = tel.REGISTRY.counters()
    got = ctx.sql("SELECT gk, COUNT(*) AS n, SUM(v) AS s FROM fact "
                  "GROUP BY gk ORDER BY gk", return_futures=False)
    d = _spmd_deltas(c0)
    assert d.get("spmd_queries", 0) == 1, d
    assert d.get("spmd_partial_aggs", 0) >= 1, d
    want = (fact.groupby("gk", dropna=False)
            .agg(n=("k", "size"), s=("v", "sum")).reset_index()
            .sort_values("gk", na_position="last").reset_index(drop=True))
    assert len(got) == len(want)
    nulls_got = got["gk"].isna().sum()
    assert nulls_got == want["gk"].isna().sum() == 1
    g = got.sort_values("gk", na_position="last").reset_index(drop=True)
    np.testing.assert_array_equal(g["n"].to_numpy(), want["n"].to_numpy())
    np.testing.assert_allclose(g["s"].to_numpy(dtype=float),
                               want["s"].to_numpy(dtype=float), rtol=1e-9)


def test_join_exchange_matches_pandas(spmd_ctx):
    ctx, fact, dim = spmd_ctx
    c0 = tel.REGISTRY.counters()
    got = ctx.sql("SELECT grp, SUM(v * w) AS rev FROM fact "
                  "JOIN dim ON fact.k = dim.k GROUP BY grp ORDER BY grp",
                  return_futures=False)
    d = _spmd_deltas(c0)
    assert d.get("spmd_queries", 0) == 1, d
    assert (d.get("spmd_broadcast_joins", 0)
            + d.get("spmd_exchange_joins", 0)) >= 1, d
    want = (fact.merge(dim, on="k").assign(rev=lambda x: x.v * x.w)
            .groupby("grp").agg(rev=("rev", "sum")).reset_index())
    np.testing.assert_allclose(got["rev"].to_numpy(dtype=float),
                               want["rev"].to_numpy(dtype=float), rtol=1e-9)


def test_forced_exchange_join(mesh, monkeypatch):
    # a zero broadcast cap forces the hash-partitioned all_to_all variant
    monkeypatch.setenv("DSQL_SPMD_BROADCAST_ROWS", "0")
    rng = np.random.RandomState(9)
    n = 16 * int(mesh.devices.size)
    a = pd.DataFrame({"k": rng.randint(0, 50, n).astype(np.int64),
                      "v": rng.rand(n)})
    b = pd.DataFrame({"k": np.arange(50, dtype=np.int64),
                      "w": np.arange(50) * 1.5})
    ctx = Context(mesh=mesh)
    ctx.create_table("a", a)
    ctx.create_table("b", b)
    c0 = tel.REGISTRY.counters()
    got = ctx.sql("SELECT SUM(v * w) AS s FROM a JOIN b ON a.k = b.k",
                  return_futures=False)
    d = _spmd_deltas(c0)
    assert d.get("spmd_exchange_joins", 0) >= 1, d
    assert d.get("spmd_exchanges", 0) >= 1, d
    assert d.get("spmd_exchange_bytes", 0) > 0, d
    want = (a.merge(b, on="k").eval("v * w")).sum()
    np.testing.assert_allclose(float(got["s"][0]), want, rtol=1e-9)


def test_mesh_kill_switch_restores_baseline(spmd_ctx, monkeypatch):
    ctx, fact, _ = spmd_ctx
    monkeypatch.setenv("DSQL_MESH", "0")
    c0 = tel.REGISTRY.counters()
    got = ctx.sql("SELECT COUNT(*) AS n FROM fact", return_futures=False)
    d = _spmd_deltas(c0)
    assert d.get("spmd_queries", 0) == 0, d
    assert int(got["n"][0]) == len(fact)


def test_system_mesh_table_reports_devices(spmd_ctx):
    ctx, _, _ = spmd_ctx
    got = ctx.sql("SELECT COUNT(*) AS n FROM system.mesh "
                  "WHERE in_mesh AND spmd_enabled", return_futures=False)
    assert int(got["n"][0]) == int(ctx.mesh.devices.size)


# ---------------------------------------------------------------------------
# cross-process program-store round-trip of a sharded stage program
# ---------------------------------------------------------------------------

_STORE_CHILD = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np, pandas as pd
    from dask_sql_tpu import Context
    from dask_sql_tpu.parallel.mesh import default_mesh
    from dask_sql_tpu.runtime import telemetry as tel

    rng = np.random.RandomState(5)   # SAME data in both processes
    df = pd.DataFrame({"g": rng.randint(0, 6, 64).astype(np.int64),
                       "v": np.round(rng.rand(64), 6)})
    ctx = Context(mesh=default_mesh())
    ctx.create_table("t", df)
    out = ctx.sql("SELECT g, SUM(v) AS s FROM t GROUP BY g ORDER BY g",
                  return_futures=False)
    c = tel.REGISTRY.counters()
    json.dump({"s": [round(float(x), 9) for x in out["s"]],
               "spmd_queries": int(c.get("spmd_queries", 0)),
               "spmd_compiles": int(c.get("spmd_compiles", 0)),
               "spmd_store_hits": int(c.get("spmd_store_hits", 0))},
              sys.stdout)
""")


@pytest.mark.slow
def test_sharded_program_store_round_trip(tmp_path):
    import json

    env = dict(__import__("os").environ,
               DSQL_PROGRAM_STORE=str(tmp_path / "programs"),
               DSQL_MESH="1", DSQL_ADAPTIVE="0")
    env.pop("JAX_PLATFORMS", None)
    runs = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", _STORE_CHILD],
                              capture_output=True, text=True, env=env,
                              timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        runs.append(json.loads(proc.stdout))
    first, second = runs
    assert first["spmd_queries"] == second["spmd_queries"] == 1
    assert first["spmd_compiles"] >= 1
    # the second process must serve the sharded stage program from the
    # persistent store without a single XLA compile
    assert second["spmd_compiles"] == 0, second
    assert second["spmd_store_hits"] >= 1, second
    assert first["s"] == second["s"]
