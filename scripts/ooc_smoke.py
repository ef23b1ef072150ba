#!/usr/bin/env python
"""Out-of-core smoke gate: TPC-H-shaped queries over chunked tables that
exceed a deliberately tiny device budget must complete CORRECTLY through
the spill path (runtime/spill.py + physical/morsel.py), with bounded
device occupancy — and DSQL_SPILL_MB=0 must restore pre-spill behavior.

Four checks (run by scripts/ci_local.sh as ``python scripts/ooc_smoke.py``):

  1. Q1/Q6 shapes (scan -> filter -> wide aggregate) over ONE chunked
     table stream per-batch and match the pandas oracle — including a
     short final batch and NULLs in an aggregated column;
  2. a Q3 shape (two CHUNKED tables joined on a key, then GROUP BY) runs
     the grace-hash partitioned join: spill_partitions advances, the
     result matches pandas (NULL join keys dropped per INNER semantics),
     and every spill run is freed afterwards;
  3. the spill store's device tier stays bounded: peak_device_bytes never
     exceeds the configured device cap;
  4. DSQL_SPILL_MB=0 (spilling OFF) keeps single-chunked streaming
     byte-identical and turns the two-chunked join back into the typed
     StreamingUnsupported error the engine raised before the subsystem.

Exit 0 on success.
"""
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# a deliberately small ledger budget: the chunked tables below would not
# fit resident, so completing correctly PROVES the out-of-core path
os.environ.setdefault("DSQL_DEVICE_BUDGET_MB", "64")
os.environ.setdefault("DSQL_SPILL_MB", "64")
os.environ.setdefault("DSQL_SPILL_DEVICE_MB", "8")
os.environ.setdefault("DSQL_SPILL_DIR",
                      tempfile.mkdtemp(prefix="dsql_ooc_smoke_"))

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402

N_LINE = 120_000
N_ORD = 30_000
BATCH_ROWS = 16_384


def _norm(df: pd.DataFrame) -> pd.DataFrame:
    out = df.copy()
    for col in out.columns:
        if out[col].dtype.kind in "iuf":
            out[col] = out[col].astype("float64").round(6)
    return (out.sort_values(list(out.columns), na_position="last")
               .reset_index(drop=True))


def _check(name, got, oracle, failures):
    try:
        pd.testing.assert_frame_equal(_norm(got), _norm(oracle),
                                      check_dtype=False, rtol=1e-6,
                                      atol=1e-9)
        print(f"  {name}: correct ({len(got)} rows)")
    except AssertionError as e:
        failures.append(f"{name} wrong result: {str(e)[:300]}")


def _make_data(seed=0):
    rng = np.random.default_rng(seed)
    # lineitem-shaped: status strings, a NULL-bearing measure, and a row
    # count that leaves a SHORT final batch (120000 % 16384 != 0)
    qty = rng.integers(1, 50, N_LINE).astype("float64")
    qty[rng.random(N_LINE) < 0.02] = np.nan
    line = pd.DataFrame({
        "okey": rng.integers(0, N_ORD, N_LINE),
        "qty": qty,
        "price": np.round(rng.random(N_LINE) * 1000, 2),
        "disc": np.round(rng.random(N_LINE) * 0.1, 2),
        "status": rng.choice(["A", "B", "C"], N_LINE),
    })
    okey = np.arange(N_ORD, dtype="float64")
    okey[rng.random(N_ORD) < 0.01] = np.nan  # NULL join keys
    orders = pd.DataFrame({
        "okey": okey,
        "seg": rng.choice(["AUTO", "HOME", "SHIP"], N_ORD),
        "total": np.round(rng.random(N_ORD) * 5000, 2),
    })
    return line, orders


def main() -> int:
    from dask_sql_tpu import Context
    from dask_sql_tpu.runtime import resilience as res
    from dask_sql_tpu.runtime import spill as spill_mod
    from dask_sql_tpu.runtime import telemetry as tel

    line, orders = _make_data()
    failures = []

    ctx = Context()
    ctx.create_table("line", line, chunked=True, batch_rows=BATCH_ROWS)
    ctx.create_table("orders", orders, chunked=True, batch_rows=BATCH_ROWS)

    q1 = ("SELECT status, SUM(qty) AS sq, SUM(price * (1.0 - disc)) AS sp, "
          "COUNT(*) AS n FROM line GROUP BY status")
    o1 = line.groupby("status", as_index=False).agg(
        sq=("qty", "sum"),
        sp=("price", lambda s: float("nan")),  # recomputed below
        n=("qty", "size"))
    o1["sp"] = line.assign(x=line.price * (1.0 - line.disc)).groupby(
        "status")["x"].sum().reindex(o1.status).to_numpy()
    q6 = ("SELECT SUM(price * disc) AS rev FROM line "
          "WHERE disc > 0.02 AND qty < 25.0")
    f6 = line[(line.disc > 0.02) & (line.qty < 25.0)]
    o6 = pd.DataFrame({"rev": [(f6.price * f6.disc).sum()]})
    q3 = ("SELECT orders.seg AS seg, SUM(line.price) AS rev, COUNT(*) AS n "
          "FROM line JOIN orders ON line.okey = orders.okey "
          "GROUP BY orders.seg")
    j = line.merge(orders, on="okey")  # pandas merge drops NaN keys: INNER
    o3 = j.groupby("seg", as_index=False).agg(rev=("price", "sum"),
                                              n=("price", "size"))

    print("[1] single-chunked streaming (Q1/Q6 shapes)")
    _check("Q1-shape", ctx.sql(q1, return_futures=False), o1, failures)
    _check("Q6-shape", ctx.sql(q6, return_futures=False), o6, failures)

    print("[2] two-chunked grace-hash join (Q3 shape)")
    c0 = tel.REGISTRY.counters()
    _check("Q3-shape", ctx.sql(q3, return_futures=False), o3, failures)
    c1 = tel.REGISTRY.counters()
    parts = c1.get("spill_partitions", 0) - c0.get("spill_partitions", 0)
    joins = c1.get("morsel_joins", 0) - c0.get("morsel_joins", 0)
    if parts <= 0 or joins <= 0:
        failures.append(
            f"grace path did not run: spill_partitions delta {parts}, "
            f"morsel_joins delta {joins}")
    else:
        print(f"  grace join ran: {parts} spill partitions, "
              f"{joins} morsel join(s)")
    stats = spill_mod.get_store().stats()
    if stats["runs"]:
        failures.append(f"spill store leaked {stats['runs']} run(s)")

    print("[3] device occupancy bounded")
    peak = stats["peak_device_bytes"]
    cap = spill_mod.device_cap_bytes()
    if peak > cap:
        failures.append(f"spill device tier exceeded its cap: "
                        f"peak {peak} > cap {cap}")
    else:
        print(f"  peak spill device bytes {peak} <= cap {cap}")

    print("[4] DSQL_SPILL_MB=0 restores pre-spill behavior")
    os.environ["DSQL_SPILL_MB"] = "0"
    spill_mod.reset_store()
    ctx0 = Context()
    ctx0.create_table("line", line, chunked=True, batch_rows=BATCH_ROWS)
    ctx0.create_table("orders", orders, chunked=True, batch_rows=BATCH_ROWS)
    _check("Q1-shape (spill off)", ctx0.sql(q1, return_futures=False), o1,
           failures)
    c2 = tel.REGISTRY.counters()
    try:
        ctx0.sql(q3, return_futures=False)
        failures.append("two-chunked join succeeded with spilling OFF — "
                        "DSQL_SPILL_MB=0 did not restore the baseline")
    except res.ResilienceError as e:
        print(f"  two-chunked join raised typed "
              f"{type(e).__name__} (expected)")
    c3 = tel.REGISTRY.counters()
    if c3.get("spill_partitions", 0) != c2.get("spill_partitions", 0):
        failures.append("spill counters advanced with spilling OFF")

    if failures:
        print("OOC SMOKE FAILED:")
        for f in failures:
            print("  - " + f)
        return 1
    print("ooc smoke OK: chunked Q1/Q6/Q3 shapes correct, grace join "
          "spilled and freed, device occupancy bounded, kill switch clean")
    return 0


if __name__ == "__main__":
    rc = main()
    # skip interpreter teardown: the XLA CPU client occasionally aborts in
    # its destructor after heavy device-buffer churn, long after every check
    # has passed
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
