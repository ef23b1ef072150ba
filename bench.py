"""Driver benchmark: all 22 TPC-H queries through the SQL engine on TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

The line is printed twice — once bare (legacy parsers) and once behind the
``DSQL_BENCH_RESULT `` sentinel prefix on its own line — and written to
``bench_result.json`` in the work dir (override: ``BENCH_RESULTS_FILE``):
interleaved ANSI/log output mangled the bare line in r05 ("parsed": null),
and a sentinel + file artifact survive any amount of log noise.

The workload is the BASELINE.md primary metric: the Q1-Q22 geomean wall-clock
over generated TPC-H data, end-to-end through Context.sql (SQL text to host
pandas frame).  ``vs_baseline`` is the geomean speedup against single-threaded
pandas executing hand-written implementations of the same 22 queries on the
same host (benchmarks/pandas_tpch.py) — the reference's single-partition
execution substrate IS pandas, and BASELINE.md publishes no absolute numbers.

Budget design (round 5 — round 4 set the budget ABOVE the driver's observed
~1800 s kill and was SIGTERMed mid-run: the partial emitted, but 6 queries,
the compiled stats and the quiesced re-measure were lost.  The budget must
fit inside the driver's window, not test it):

- ONE absolute deadline is computed at entry (``BENCH_RUN_TIMEOUT``, default
  1700 s — conservatively inside the driver's observed ~1800 s kill window);
- the pandas baseline runs FIRST (it is cheap and cannot wedge), so engine
  trouble can never erase the comparison;
- engine queries run in ONE child process (the SF1 host->device transfer is
  paid once, not per stage); the child journals every completed query to a
  progress file and retires itself at its own deadline, and the parent restarts a child on
  the remaining queries only while enough budget remains;
- emission is structurally guaranteed: a watchdog thread fires just before
  the deadline, SIGTERM/SIGINT are handled, and an atexit hook is the last
  resort — all funnel into one idempotent emitter that reads the progress
  journal, so being killed mid-run still yields a parsed partial result.

Compile latency is managed by the persistent XLA cache (placed by the
package: JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache) + the
learned-caps file under <repo>/.bench_cache, so a bench run primed by an
earlier run on the same host loads programs instead of compiling them.
``detail`` records the platform each query ran on, per-query times, compile
stats and cold/warm cache evidence, so the result can't silently hide a CPU
fallback or a partial run.
"""
import atexit
import json
import math
import os
import signal
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SF = float(os.environ.get("BENCH_SF", "1.0"))
REPS = int(os.environ.get("BENCH_REPS", "3"))
# SAME rep count for the baseline by default: best-of-3 engine vs a single
# cold pandas sample would systematically inflate vs_baseline
PANDAS_REPS = int(os.environ.get("BENCH_PANDAS_REPS", str(REPS)))
WARMUP_THREADS = int(os.environ.get("BENCH_WARMUP_THREADS", "8"))
PLATFORM_PROBE_TIMEOUT = float(os.environ.get("BENCH_PLATFORM_TIMEOUT", "120"))
# the watchdog + SIGTERM handler guarantee the metric line even when the
# caller kills first — but a SIGTERM partial LOSES the stage_done record
# (compiled stats, device memory) and the quiesced re-measure, so the
# budget must finish INSIDE the driver's observed ~1800 s kill window
TOTAL_BUDGET = float(os.environ.get("BENCH_RUN_TIMEOUT", "1700"))
PANDAS_BUDGET = float(os.environ.get("BENCH_PANDAS_TIMEOUT", "420"))
EMIT_MARGIN = float(os.environ.get("BENCH_EMIT_MARGIN", "25"))
# minimum budget worth starting an engine child with: one table transfer
# plus at least one compile+measure
MIN_CHILD_BUDGET = float(os.environ.get("BENCH_MIN_CHILD_BUDGET", "240"))

# priority order: cheap compiles + headline queries first, so an engine child
# that dies mid-run still leaves the most meaningful recorded subset
PRIORITY = [6, 1, 3, 12, 14, 19, 4, 5, 10, 15, 20, 22,
            2, 11, 13, 16, 17, 18, 7, 8, 9, 21]


def _order(all_qids):
    """PRIORITY first, then any query id not hardcoded above — a query added
    to benchmarks.tpch.QUERIES is never silently dropped."""
    extra = sorted(q for q in all_qids if q not in PRIORITY)
    return [q for q in PRIORITY if q in all_qids] + extra


def _geomean(xs):
    return math.exp(sum(math.log(x) for x in xs) / len(xs))


def _pctile(xs, q):
    """Nearest-rank percentile of a non-empty list."""
    s = sorted(xs)
    return s[min(int(round(q / 100.0 * (len(s) - 1))), len(s) - 1)]


def _probe_platform():
    """Decide the platform once, before anything else runs.  "default"
    when the default JAX backend is a TPU; ``BENCH_PLATFORM=cpu`` is the
    explicit way to rehearse.  A run that finds no TPU ends here: a
    timing of XLA:CPU is not a measurement of this engine.  The probe is
    a child process, so this parent never holds the chip."""
    import subprocess

    forced = os.environ.get("BENCH_PLATFORM")
    if forced:
        return forced
    try:
        probe = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].platform)"],
            timeout=PLATFORM_PROBE_TIMEOUT, capture_output=True, text=True)
        found = (probe.stdout.strip().splitlines() or ["none"])[-1] \
            if probe.returncode == 0 else "none (jax.devices() failed)"
    except subprocess.TimeoutExpired:
        found = f"none (probe timed out after {PLATFORM_PROBE_TIMEOUT}s)"
    if found == "tpu":
        return "default"
    sys.exit(f"bench: no TPU found (default JAX platform: {found}); "
             "set BENCH_PLATFORM=cpu to rehearse on the CPU")


def _cache_data(sf: float, cache_dir: str):
    from benchmarks.tpch import generate_tpch

    t0 = time.perf_counter()
    data = generate_tpch(sf)
    for name, frame in data.items():
        frame.to_feather(os.path.join(cache_dir, f"{name}.feather"))
    return time.perf_counter() - t0, len(data["lineitem"])


def _load_data(cache_dir: str):
    import pandas as pd

    data = {}
    for fn in os.listdir(cache_dir):
        if fn.endswith(".feather"):
            data[fn[:-8]] = pd.read_feather(os.path.join(cache_dir, fn))
    return data


def _stage_main():
    """Child: run BENCH_STAGE_QUERIES against the cached data, appending one
    JSON line per completed query to the progress file, retiring itself
    cleanly at BENCH_CHILD_DEADLINE (unix seconds)."""
    platform = os.environ.get("BENCH_PLATFORM_CHOICE", "default")
    deadline = float(os.environ.get("BENCH_CHILD_DEADLINE", "0")) or None
    import jax

    if platform != "default":
        jax.config.update("jax_platforms", platform)
    from benchmarks.tpch import QUERIES
    from dask_sql_tpu import Context

    qids = [int(x) for x in os.environ["BENCH_STAGE_QUERIES"].split(",")]
    progress_path = os.environ["BENCH_PROGRESS"]
    data = _load_data(os.environ["BENCH_DATA_DIR"])

    # the RESULT cache (runtime/result_cache.py) must not contaminate the
    # cold measurement: a repeated rep would replay the materialized result
    # in ~1 ms and the "best of REPS" would measure the cache, not the
    # engine.  Measurement runs with it off; the warm-repeat pass below
    # re-arms it to record hit-rate + warm latency as a SEPARATE metric.
    cache_mb = os.environ.get("DSQL_RESULT_CACHE_MB")
    os.environ["DSQL_RESULT_CACHE_MB"] = "0"
    # tiered execution must not contaminate the measurement either: a
    # first arrival served on the eager tier would record the eager path,
    # not the compiled engine (DSQL_EAGER_FALLBACK=0 already disables the
    # tier; this pins it for explicit-eager configs too).  The program
    # STORE stays armed: store loads ARE the engine's cold path now.
    os.environ.setdefault("DSQL_TIERED", "0")
    # the workload manager (runtime/scheduler.py, 4 slots by default) must
    # not throttle the 8-thread warmup pool: a compile that takes minutes
    # would blow the admission-queue timeout and lose the
    # query.  Measurement runs with it off; the burst pass below re-arms
    # it to record queue-time percentiles as a SEPARATE metric.
    os.environ["DSQL_MAX_CONCURRENT_QUERIES"] = "0"

    c = Context()
    t0 = time.perf_counter()
    for name, frame in data.items():
        c.create_table(name, frame)
    load_sec = time.perf_counter() - t0
    del data
    real_platform = jax.devices()[0].platform

    def left():
        return float("inf") if deadline is None else deadline - time.time()

    def emit(rec):
        with open(progress_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
            f.flush()

    if os.environ.get("BENCH_WARM_RESTART") == "1":
        # RESTART-WARM mode: this is a FRESH process pointed at the
        # program store the measurement child populated — every query
        # should load its stage executables with zero XLA compiles.  One
        # run per query, journaled, plus the store-hit evidence the
        # parent folds into program_store_hit_rate / warm_start_sec.
        from dask_sql_tpu.physical import compiled as _cmp

        t_w = time.perf_counter()
        for qid in qids:
            if left() < 10:
                break
            try:
                t0r = time.perf_counter()
                c.sql(QUERIES[qid], return_futures=False)
                emit({"restart_q": qid,
                      "sec": round(time.perf_counter() - t0r, 4),
                      "platform": real_platform})
            except Exception as e:
                emit({"restart_fail": qid, "error": repr(e)[:200]})
        snap = dict(_cmp.stats)
        emit({"restart_done": True,
              "warm_start_sec": round(time.perf_counter() - t_w, 2),
              "program_store_hits": snap.get("program_store_hits", 0),
              "program_store_errors": snap.get("program_store_errors", 0),
              "compiles": snap.get("compiles", 0)})
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)

    if os.environ.get("BENCH_SHARD_SCALING") == "1":
        # SHARD-SCALING mode: the scan/agg-shaped queries (Q1/Q6) on the
        # single-device engine vs row-sharded over the full mesh through
        # the explicit SPMD executor (parallel/spmd.py) — the multi-chip
        # speedup evidence for the BENCH_r*.json trajectory.  On a
        # CPU-only host the mesh is the 8-virtual-device dry-run analogue;
        # spmd_served certifies the sharded path (not a silent fallback)
        # produced the numbers.
        from dask_sql_tpu.parallel.mesh import default_mesh
        from dask_sql_tpu.runtime import telemetry as _stel

        mesh = default_mesh()
        n_dev = int(mesh.devices.size)
        if n_dev < 2:
            emit({"shard_scaling_skip": f"only {n_dev} device(s)"})
            os._exit(0)
        dist = Context(mesh=mesh)
        for name, frame in _load_data(os.environ["BENCH_DATA_DIR"]).items():
            dist.create_table(name, frame)
        reps = int(os.environ.get("BENCH_SHARD_REPS", "3"))
        scaling = {}
        for qid in (1, 6):
            if left() < 20:
                break
            try:
                c.sql(QUERIES[qid], return_futures=False)     # warm 1-dev
                dist.sql(QUERIES[qid], return_futures=False)  # warm mesh
                c0 = _stel.REGISTRY.counters()
                single = sharded = float("inf")
                for _ in range(reps):
                    t0r = time.perf_counter()
                    c.sql(QUERIES[qid], return_futures=False)
                    single = min(single, time.perf_counter() - t0r)
                    t0r = time.perf_counter()
                    dist.sql(QUERIES[qid], return_futures=False)
                    sharded = min(sharded, time.perf_counter() - t0r)
                c1 = _stel.REGISTRY.counters()
                served = (c1.get("spmd_queries", 0)
                          - c0.get("spmd_queries", 0))
                scaling[str(qid)] = {
                    "single_sec": round(single, 4),
                    "sharded_sec": round(sharded, 4),
                    "speedup": round(single / max(sharded, 1e-9), 3),
                    "devices": n_dev,
                    "spmd_served": served >= reps,
                }
            except Exception as e:
                emit({"shard_scaling_fail": qid, "error": repr(e)[:200]})
        emit({"shard_scaling": scaling})
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)

    if os.environ.get("BENCH_OOC_CHILD") == "1":
        # OUT-OF-CORE mode (parent opts in with BENCH_OOC=1): lineitem and
        # orders re-registered CHUNKED (8 batches each) so Q1/Q6 stream
        # per-batch and Q3's chunked-x-chunked join runs grace-hash
        # partitioned through the spill store — the evidence that queries
        # over tables exceeding the device budget complete, stay correct
        # against the resident engine, and bound their device footprint.
        import pandas as _opd

        from dask_sql_tpu.runtime import spill as _spill_mod
        from dask_sql_tpu.runtime import telemetry as _otel

        def _frames_match(a, b) -> bool:
            try:
                cols = list(a.columns)
                _opd.testing.assert_frame_equal(
                    a.sort_values(cols).reset_index(drop=True),
                    b.sort_values(cols).reset_index(drop=True),
                    check_dtype=False, rtol=1e-6, atol=1e-6)
                return True
            except Exception:  # noqa: BLE001 - any mismatch is "no"
                return False

        ooc = Context()
        data = _load_data(os.environ["BENCH_DATA_DIR"])
        for name, frame in data.items():
            if name in ("lineitem", "orders"):
                ooc.create_table(name, frame, chunked=True,
                                 batch_rows=max(len(frame) // 8, 1))
            else:
                ooc.create_table(name, frame)
        del data
        store = _spill_mod.get_store()
        results = {}
        for qid in (1, 6, 3):
            if left() < 20:
                break
            try:
                c0x = _otel.REGISTRY.counters()
                t0r = time.perf_counter()
                got = ooc.sql(QUERIES[qid], return_futures=False)
                sec = time.perf_counter() - t0r
                ref = c.sql(QUERIES[qid], return_futures=False)
                c1x = _otel.REGISTRY.counters()

                def dlt(k):
                    return c1x.get(k, 0) - c0x.get(k, 0)

                results[str(qid)] = {
                    "sec": round(sec, 4),
                    "match": _frames_match(got, ref),
                    "spill_partitions": dlt("spill_partitions"),
                    "spill_bytes": dlt("spill_bytes_host")
                    + dlt("spill_bytes_disk"),
                    "stream_batches": dlt("stream_batches"),
                }
            except Exception as e:
                emit({"ooc_fail": qid, "error": repr(e)[:200]})
        cs = _otel.REGISTRY.counters()
        emit({"ooc": {
            "queries": results,
            "ooc_completed": bool(results) and all(
                r["match"] for r in results.values()),
            "spill_bytes": int(cs.get("spill_bytes_host", 0)
                               + cs.get("spill_bytes_disk", 0)),
            "spill_partitions": int(cs.get("spill_partitions", 0)),
            "peak_device_bytes": store.stats()["peak_device_bytes"],
        }})
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)

    if os.environ.get("BENCH_MV_CHILD") == "1":
        # MATERIALIZED-VIEW mode (parent opts in with BENCH_MV=1): a
        # SUM/AVG/COUNT group-by view over lineitem, one warm-up append
        # (pays the one-time partial/merge plan compiles), then a
        # 1k-row append with the maintained refresh timed against a full
        # recompute of the defining query — the O(delta) maintenance
        # evidence for the metrics JSON, plus the refresh hit-rate from
        # the mv_* counters and an exactness check of the served view
        # against the recomputed answer.
        import pandas as _mpd

        from dask_sql_tpu.runtime import telemetry as _mtel

        # maintained state is a result-cache tenant: the cache-off pin
        # above (cold-measurement hygiene) would silently disable the
        # whole subsystem, so this mode re-arms the budget
        os.environ["DSQL_RESULT_CACHE_MB"] = cache_mb if cache_mb else "256"
        MV_SQL = ("SELECT l_returnflag, l_linestatus, "
                  "SUM(l_quantity) AS sum_qty, "
                  "SUM(l_extendedprice) AS sum_price, "
                  "AVG(l_discount) AS avg_disc, COUNT(*) AS n "
                  "FROM lineitem GROUP BY l_returnflag, l_linestatus")

        def _mv_match(a, b) -> bool:
            try:
                cols = list(a.columns)
                _mpd.testing.assert_frame_equal(
                    a.sort_values(cols).reset_index(drop=True),
                    b.sort_values(cols).reset_index(drop=True),
                    check_dtype=False, rtol=1e-6, atol=1e-6)
                return True
            except Exception:  # noqa: BLE001 - any mismatch is "no"
                return False

        mv_rec = {}
        try:
            li = _mpd.read_feather(os.path.join(
                os.environ["BENCH_DATA_DIR"], "lineitem.feather"))
            c0m = _mtel.REGISTRY.counters()
            c.sql(f"CREATE MATERIALIZED VIEW bench_mv AS {MV_SQL}")
            c.sql("SELECT * FROM bench_mv", return_futures=False)
            # warm-up append + refresh: the first refresh compiles the
            # delta partial / state merge shapes once; the steady-state
            # claim is about maintenance work, not compiler latency
            c.append_rows("lineitem", li.sample(n=1000, random_state=7))
            c.sql("REFRESH MATERIALIZED VIEW bench_mv")
            c.sql(MV_SQL, return_futures=False)

            delta = li.sample(n=1000, random_state=11)
            c.append_rows("lineitem", delta)
            t0r = time.perf_counter()
            c.sql("REFRESH MATERIALIZED VIEW bench_mv")
            refresh_sec = time.perf_counter() - t0r
            served = c.sql("SELECT * FROM bench_mv", return_futures=False)
            # the append bumped lineitem's epoch, so this recompute is a
            # result-cache miss and measures the real defining query
            t0r = time.perf_counter()
            recomputed = c.sql(MV_SQL, return_futures=False)
            recompute_sec = time.perf_counter() - t0r
            c1m = _mtel.REGISTRY.counters()

            def dltm(k):
                return int(c1m.get(k, 0) - c0m.get(k, 0))

            inc = dltm("mv_refresh_incremental")
            full = dltm("mv_refresh_full")
            mv_rec = {
                "refresh_sec": round(refresh_sec, 4),
                "recompute_sec": round(recompute_sec, 4),
                "speedup": round(recompute_sec / max(refresh_sec, 1e-9), 2),
                "delta_rows": int(len(delta)),
                "base_rows": int(len(li)),
                "mv_refresh_incremental": inc,
                "mv_refresh_full": full,
                "mv_serves": dltm("mv_serves"),
                "mv_deltas_recorded": dltm("mv_deltas_recorded"),
                # fraction of refreshes maintained in O(delta) rather
                # than recomputed — the number the trajectory watches
                "mv_hit_rate": round(inc / max(inc + full, 1), 3),
                "match": _mv_match(served, recomputed),
            }
        except Exception as e:
            mv_rec = {"error": repr(e)[:300]}
        emit({"mv": mv_rec})
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)

    if os.environ.get("BENCH_INGEST_CHILD") == "1":
        # CONTINUOUS-INGESTION mode (parent opts in with BENCH_INGEST=1):
        # WAL-armed 500-row appends into lineitem interleaved with reads
        # of a maintained aggregate view and a COUNT(DISTINCT) view —
        # journals sustained appends/sec, read p50/p99 beside the writer,
        # the max observed staleness (pending delta age + rows), and the
        # served-vs-recomputed exactness verdict (runtime/ingest.py +
        # runtime/delta.py).
        import tempfile as _itmp

        import pandas as _ipd

        from dask_sql_tpu.runtime import telemetry as _itel

        # maintained view state is a result-cache tenant (see the MV mode
        # above), and the WAL dir arms the ingest write path lazily
        os.environ["DSQL_RESULT_CACHE_MB"] = cache_mb if cache_mb else "256"
        os.environ["DSQL_INGEST_DIR"] = _itmp.mkdtemp(
            prefix="dsql_bench_ingest_")
        ING_SQL = ("SELECT l_returnflag, l_linestatus, "
                   "SUM(l_quantity) AS sum_qty, "
                   "SUM(l_extendedprice) AS sum_price, COUNT(*) AS n "
                   "FROM lineitem GROUP BY l_returnflag, l_linestatus")
        CD_SQL = "SELECT COUNT(DISTINCT l_suppkey) AS nd FROM lineitem"

        def _ing_match(a, b) -> bool:
            try:
                cols = list(a.columns)
                _ipd.testing.assert_frame_equal(
                    a.sort_values(cols).reset_index(drop=True),
                    b.sort_values(cols).reset_index(drop=True),
                    check_dtype=False, rtol=1e-6, atol=1e-6)
                return True
            except Exception:  # noqa: BLE001 - any mismatch is "no"
                return False

        rec_ing = {}
        try:
            li = _ipd.read_feather(os.path.join(
                os.environ["BENCH_DATA_DIR"], "lineitem.feather"))
            c.sql(f"CREATE MATERIALIZED VIEW bench_ing AS {ING_SQL}")
            c.sql(f"CREATE MATERIALIZED VIEW bench_cd AS {CD_SQL}")
            # warm-up: pay the one-time delta-plan compiles before timing
            c.append_rows("lineitem", li.sample(n=500, random_state=5))
            c.sql("SELECT * FROM bench_ing", return_futures=False)
            c.sql("SELECT nd FROM bench_cd", return_futures=False)

            c0i = _itel.REGISTRY.counters()
            rounds = int(os.environ.get("BENCH_INGEST_ROUNDS", "30"))
            batch_n = int(os.environ.get("BENCH_INGEST_BATCH", "500"))
            append_sec = 0.0
            appended = 0
            lat_ms = []
            stale_max = 0.0
            pend_max = 0
            for i in range(rounds):
                if left() < 30:
                    break
                delta = li.sample(n=batch_n, random_state=100 + i)
                t0i = time.perf_counter()
                c.append_rows("lineitem", delta)
                append_sec += time.perf_counter() - t0i
                appended += batch_n
                g = _itel.REGISTRY.gauges()
                stale_max = max(stale_max,
                                float(g.get("mv_staleness_s", 0.0)))
                pend_max = max(pend_max, int(g.get("mv_pending_rows", 0)))
                sql_r = ("SELECT * FROM bench_ing" if i % 2 == 0
                         else "SELECT nd FROM bench_cd")
                t0i = time.perf_counter()
                c.sql(sql_r, return_futures=False)
                lat_ms.append((time.perf_counter() - t0i) * 1e3)
            served = c.sql("SELECT * FROM bench_ing", return_futures=False)
            recomputed = c.sql(ING_SQL, return_futures=False)
            c1i = _itel.REGISTRY.counters()

            def dlti(k):
                return int(c1i.get(k, 0) - c0i.get(k, 0))

            lat_ms.sort()

            def pct(p):
                if not lat_ms:
                    return None
                return round(lat_ms[min(int(len(lat_ms) * p),
                                        len(lat_ms) - 1)], 2)

            rec_ing = {
                "batches": dlti("ingest_batches_committed"),
                "rows_appended": appended,
                "appends_per_sec": round(
                    appended / max(append_sec, 1e-9), 1),
                "read_p50_ms": pct(0.50),
                "read_p99_ms": pct(0.99),
                "staleness_max_s": round(stale_max, 3),
                "pending_rows_max": pend_max,
                "wal_bytes": int(_itel.REGISTRY.gauges().get(
                    "ingest_wal_bytes", 0)),
                "backpressure_rejects": dlti("ingest_backpressure_rejects"),
                "mv_refresh_incremental": dlti("mv_refresh_incremental"),
                "mv_refresh_full": dlti("mv_refresh_full"),
                "match": _ing_match(served, recomputed),
            }
        except Exception as e:
            rec_ing = {"error": repr(e)[:300]}
        emit({"ingest": rec_ing})
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)

    if os.environ.get("BENCH_AUTOPILOT_CHILD") == "1":
        # AUTOPILOT mode (parent opts in with BENCH_AUTOPILOT=1): the
        # unattended-vs-hand-tuned comparison.  A hand-tuned operator
        # pre-creates a matview and queries it by name; the unattended
        # workload just repeats its aggregate and lets the autopilot
        # discover, materialize and maintain it.  Both pay the same
        # append-then-read rounds; the journaled ratio is the price of
        # leaving the tuning to the advisor (~1.0 = converged).
        import pandas as _apd

        from dask_sql_tpu.runtime import telemetry as _atel

        # maintained state is a result-cache tenant (see the MV mode
        # above): re-arm the budget the cold-measurement pin zeroed
        os.environ["DSQL_RESULT_CACHE_MB"] = cache_mb if cache_mb else "256"
        TUNED_SQL = ("SELECT l_returnflag, l_linestatus, "
                     "SUM(l_quantity) AS sum_qty, COUNT(*) AS n "
                     "FROM lineitem GROUP BY l_returnflag, l_linestatus")
        AUTO_SQL = ("SELECT l_linestatus, "
                    "SUM(l_extendedprice) AS sum_price, "
                    "AVG(l_discount) AS avg_disc, COUNT(*) AS n "
                    "FROM lineitem GROUP BY l_linestatus")
        rec_ap = {}
        try:
            li = _apd.read_feather(os.path.join(
                os.environ["BENCH_DATA_DIR"], "lineitem.feather"))
            # untuned reference: one full recompute of the aggregate
            t0a = time.perf_counter()
            c.sql(AUTO_SQL, return_futures=False)
            recompute_sec = time.perf_counter() - t0a

            # hand-tuned: operator-created view, queried by name; the
            # warm-up append pays the one-time delta-plan compiles
            c.sql(f"CREATE MATERIALIZED VIEW bench_ap AS {TUNED_SQL}")
            c.append_rows("lineitem", li.sample(n=1000, random_state=3))
            c.sql("SELECT * FROM bench_ap", return_futures=False)
            tuned = []
            for r in range(3):
                if left() < 30:
                    break
                c.append_rows("lineitem",
                              li.sample(n=1000, random_state=20 + r))
                t0a = time.perf_counter()
                c.sql("SELECT * FROM bench_ap", return_futures=False)
                tuned.append(time.perf_counter() - t0a)

            # unattended: arm the advisor, repeat the aggregate until it
            # is the top candidate (the second run is a cache hit whose
            # count-only envelope still accrues), tick, then pay the
            # same append-then-read rounds served from the auto view
            os.environ["DSQL_HISTORY_FILE"] = os.path.join(
                os.environ["BENCH_DATA_DIR"], "autopilot_history.jsonl")
            os.environ["DSQL_AUTOPILOT"] = "1"
            os.environ["DSQL_AUTOPILOT_INTERVAL_S"] = "0"
            os.environ["DSQL_AUTOPILOT_MIN_HITS"] = "2"
            from dask_sql_tpu.runtime import autopilot as _ap
            c0a = _atel.REGISTRY.counters()
            c.sql(AUTO_SQL, return_futures=False)
            c.sql(AUTO_SQL, return_futures=False)
            _ap.tick(c)
            unattended = []
            served = None
            for r in range(3):
                if left() < 30:
                    break
                c.append_rows("lineitem",
                              li.sample(n=1000, random_state=40 + r))
                t0a = time.perf_counter()
                served = c.sql(AUTO_SQL, return_futures=False)
                unattended.append(time.perf_counter() - t0a)
            # exactness: the served answer vs a from-scratch recompute
            # with the advisor disarmed (epoch already bumped, so this
            # is a genuine cache miss)
            os.environ["DSQL_AUTOPILOT"] = "0"
            recomputed = c.sql(AUTO_SQL, return_futures=False)
            os.environ["DSQL_AUTOPILOT"] = "1"
            cols = list(recomputed.columns)
            try:
                _apd.testing.assert_frame_equal(
                    served.sort_values(cols).reset_index(drop=True),
                    recomputed.sort_values(cols).reset_index(drop=True),
                    check_dtype=False, rtol=1e-6, atol=1e-6)
                match = True
            except Exception:  # noqa: BLE001 - any mismatch is "no"
                match = False
            c1a = _atel.REGISTRY.counters()

            def dlta(k):
                return int(c1a.get(k, 0) - c0a.get(k, 0))

            tg = _geomean(tuned) if tuned else 0.0
            ug = _geomean(unattended) if unattended else 0.0
            rec_ap = {
                "recompute_sec": round(recompute_sec, 4),
                "tuned_geomean_sec": round(tg, 4),
                "unattended_geomean_sec": round(ug, 4),
                "vs_tuned_geomean": (round(ug / tg, 3) if tg > 0
                                     else None),
                "auto_views": _ap.engine_section()["managedViews"],
                "autopilot_mv_creates": dlta("autopilot_mv_creates"),
                "autopilot_mv_serves": dlta("autopilot_mv_serves"),
                "match": match,
            }
        except Exception as e:
            rec_ap = {"error": repr(e)[:300]}
        emit({"autopilot": rec_ap})
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)

    if os.environ.get("BENCH_FLEET_CHILD") == "1":
        # FLEET mode (parent opts in with BENCH_FLEET=1): two server
        # REPLICAS on one shared DSQL_FLEET_DIR + a FRESH shared
        # DSQL_PROGRAM_STORE, driven through a Zipf multi-tenant
        # parameterized burst over the wire.  Journals per-tenant SLO
        # attainment from the merged fleet plane, the fleet-wide
        # plan-cache hit rate, and the cross-replica warm serves —
        # replica B must answer shapes replica A compiled with ZERO
        # compiles of its own.
        import subprocess
        import tempfile as _ftmp
        import urllib.request as _furl

        import numpy as _fnp

        fleet_root = _ftmp.mkdtemp(prefix="bench_fleet_")
        fleet_dir = os.path.join(fleet_root, "fleet")
        store_dir = os.path.join(fleet_root, "programs")
        os.makedirs(store_dir, exist_ok=True)
        server_src = (
            "import os, time\n"
            "import pandas as pd\n"
            "from dask_sql_tpu import Context\n"
            "c = Context()\n"
            "c.create_table('lineitem', pd.read_feather(os.path.join(\n"
            "    os.environ['BENCH_DATA_DIR'], 'lineitem.feather')))\n"
            "srv = c.run_server(host='127.0.0.1', port=0, blocking=False)\n"
            "print(f'PORT {srv.server_port}', flush=True)\n"
            "while True:\n"
            "    time.sleep(0.5)\n"
        )

        def _fleet_spawn(rid):
            # NO XLA compile cache: the pass proves warmth through the
            # program store, and a warm compile cache poisons it —
            # serialize_executable on a cache-served CPU executable emits
            # symbol references instead of embedded code, so the other
            # replica's deserialize dies with "Symbols not found".  CPU
            # only (main() refuses the pass on a TPU: two processes, one
            # chip)
            env = dict(os.environ, DSQL_FLEET_DIR=fleet_dir,
                       DSQL_REPLICA_ID=rid, DSQL_FLEET_BEAT_S="0.2",
                       DSQL_PROGRAM_STORE=store_dir,
                       JAX_PLATFORMS="cpu",
                       JAX_ENABLE_COMPILATION_CACHE="false",
                       DSQL_RESULT_CACHE_MB="0",
                       DSQL_MAX_CONCURRENT_QUERIES="0",
                       DSQL_TIERED="0")
            # per-replica rings must come from the fleet arm, not the
            # bench-wide history file every other pass shares
            for k in ("DSQL_EVENTS", "DSQL_EVENTS_FILE",
                      "DSQL_HISTORY_FILE", "BENCH_STAGE"):
                env.pop(k, None)
            p = subprocess.Popen([sys.executable, "-c", server_src],
                                 env=env, stdout=subprocess.PIPE,
                                 stderr=subprocess.PIPE)
            line = p.stdout.readline().decode().strip()
            if not line.startswith("PORT "):
                p.kill()
                raise RuntimeError(
                    f"fleet replica {rid} died: "
                    f"{p.stderr.read().decode()[-300:]}")
            return p, f"http://127.0.0.1:{line.split()[1]}"

        def _fleet_req(url, body=None, headers=None):
            req = _furl.Request(
                url, data=body.encode() if body is not None else None,
                headers=headers or {})
            with _furl.urlopen(req, timeout=120) as r:
                return json.loads(r.read() or b"null")

        def _fleet_run(base, sql_body, tenant):
            payload = _fleet_req(
                f"{base}/v1/statement", sql_body,
                headers={"Content-Type": "application/json",
                         "X-DSQL-Tenant": tenant,
                         "X-DSQL-Priority": "interactive"})
            while "nextUri" in payload:
                payload = _fleet_req(payload["nextUri"])
            return payload

        def _fleet_metric(base, name):
            with _furl.urlopen(f"{base}/metrics", timeout=60) as r:
                for ln in r.read().decode().splitlines():
                    if not ln.startswith("#") \
                            and ln.split("{")[0].split(" ")[0] == name:
                        return float(ln.rsplit(" ", 1)[1])
            return 0.0

        fleet_rec, procs = {}, []
        try:
            pa, base_a = _fleet_spawn("bench-a")
            procs.append(pa)
            pb, base_b = _fleet_spawn("bench-b")
            procs.append(pb)
            tpl = ("SELECT l_returnflag, SUM(l_extendedprice) AS s, "
                   "COUNT(*) AS n FROM lineitem WHERE l_quantity > ? "
                   "GROUP BY l_returnflag ORDER BY l_returnflag")
            distinct = [float(v) for v in
                        _fnp.linspace(1.0, 45.0, 12).round(2)]
            # replica A pays the one compile for the shape...
            _fleet_run(base_a, json.dumps(
                {"sql": tpl, "params": [distinct[0]]}), "tenant-0")
            rng = _fnp.random.RandomState(31)
            lit_ranks = _fnp.clip(rng.zipf(1.2, size=48), 1,
                                  len(distinct)) - 1
            ten_ranks = _fnp.clip(rng.zipf(1.3, size=48), 1, 8) - 1
            execs = 0
            # ...then the Zipf mix lands on BOTH replicas: hot tenants,
            # a literal long tail, every B-side execution warm-served
            for i, (lr, tr) in enumerate(zip(lit_ranks, ten_ranks)):
                if left() < 30:
                    break
                base = base_b if i % 2 else base_a
                _fleet_run(base, json.dumps(
                    {"sql": tpl, "params": [distinct[int(lr)]]}),
                    f"tenant-{int(tr)}")
                execs += 1
            time.sleep(0.5)                 # let the final beats land
            snap = _fleet_req(f"{base_a}/v1/fleet")
            compiles_b = _fleet_metric(base_b, "dsql_compiles_total")
            hits_b = _fleet_metric(base_b,
                                   "dsql_program_store_hits_total")
            plan_hits = sum(_fleet_metric(b, "dsql_param_plan_hits_total")
                            for b in (base_a, base_b))
            fleet_rec = {
                "replicas": len(snap["replicas"]),
                "alive": snap["totals"]["alive"],
                "burst_executions": execs + 1,
                "tenant_slo_attainment": snap["slo"].get("tenants") or None,
                "plan_cache_hit_rate": round(
                    plan_hits / max(execs + 1, 1), 3),
                "warm_serves": snap["totals"]["warmServes"],
                "replica_b_compiles": compiles_b,
                "replica_b_store_hits": hits_b,
                # the shared-warmth verdict: B executed half the burst
                # without compiling anything
                "cross_replica_warm": bool(compiles_b == 0 and hits_b > 0),
            }
        except Exception as e:
            fleet_rec = {"error": repr(e)[:300]}
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
        emit({"fleet": fleet_rec})
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)

    # warmup = compilation; compiles overlap across threads (tracing holds
    # the GIL but the backend compile releases it), which matters
    # where a single cold compile can take minutes.  Each
    # query's compile wall-time is journaled: with the persistent XLA cache
    # primed this is the warm-load evidence (~sub-second), cold it is the
    # true compile cost.
    compiled_ok = set()
    lock = threading.Lock()

    warm_t0 = time.perf_counter()
    last_warm_done = [0.0]

    # expensive programs (many fused join/agg pipelines) compile through a
    # shared remote helper that gets OOM-killed when several land at once
    # (r4: the 6 join-heavy queries all wedged) — heavy plans take a
    # 2-permit semaphore so at most two of them compile concurrently while
    # light plans keep the full thread-pool width
    heavy_sem = threading.Semaphore(
        int(os.environ.get("BENCH_HEAVY_COMPILES", "2")))

    def _is_heavy(q) -> bool:
        try:
            from dask_sql_tpu.physical.compiled import _heavy_count
            from dask_sql_tpu.sql.parser import parse_sql
            stmt = parse_sql(QUERIES[q])[0]
            return _heavy_count(c._get_plan(stmt.query)) >= 4
        except Exception:
            return False

    compile_started = set()

    def warm_one(q):
        # journal the START too: a query missing from the final artifact can
        # then be classified as in-flight-at-kill vs never-started
        emit({"warm_start": q})
        t0 = time.perf_counter()
        if _is_heavy(q):
            with heavy_sem:
                with lock:
                    compile_started.add(q)
                c.sql(QUERIES[q], return_futures=False)
        else:
            with lock:
                compile_started.add(q)
            c.sql(QUERIES[q], return_futures=False)
        dt = time.perf_counter() - t0
        with lock:
            compiled_ok.add(q)
            last_warm_done[0] = time.perf_counter() - warm_t0
        emit({"warm_q": q, "sec": round(dt, 3)})
        # first_arrival: latency of the very FIRST submission of this query
        # in this bench run (the parent keeps the earliest record across
        # children) — against a cold program store it is the compile wall,
        # against a primed one it is the store-load + execute cost
        emit({"first_arrival": q, "sec": round(dt, 3)})

    def learn_split_hint(q):
        """Persist the engine's "split this plan" hint for a query whose
        whole-plan compile the remote helper silently lost — the NEXT
        child (default config) then compiles it as small programs, while
        queries that never got a compile attempt keep their standard
        whole-plan configuration."""
        try:
            from dask_sql_tpu.ops.pallas_kernels import _strategy_on_tpu
            from dask_sql_tpu.physical import compiled as _cm
            from dask_sql_tpu.sql.parser import parse_sql

            plan = c._get_plan(parse_sql(QUERIES[q])[0].query)
            scans = []
            key = (_cm._fp_plan(plan, c, scans), _cm._fp_inputs(scans),
                   bool(_strategy_on_tpu()))
            _cm._learned_caps_put(key, {**_cm._learned_caps_get(key),
                                        "__split__": 1})
            return True
        except Exception as e:
            emit({"hint_fail": q, "error": repr(e)[:200]})
            return False

    t0 = warm_t0
    futs = {}
    if WARMUP_THREADS > 1 and len(qids) > 1:
        from concurrent.futures import ThreadPoolExecutor
        # do NOT pool.shutdown(wait=True) anywhere: a thread wedged in a
        # compile must not hang the child — the os._exit at the
        # bottom reaps everything
        pool = ThreadPoolExecutor(min(WARMUP_THREADS, len(qids)))
        futs = {q: pool.submit(warm_one, q) for q in qids}
    else:
        for q in qids:
            if left() < 20:
                break
            try:
                warm_one(q)
            except Exception as e:
                emit({"warm_fail": q, "error": repr(e)[:300]})

    from dask_sql_tpu.physical import compiled

    # measure-as-compiled INSURANCE pass: one contended rep per query as
    # soon as its warmup lands, while the remaining compiles keep
    # overlapping in the pool.  These numbers are systematically OVERSTATED
    # (the host is saturated by concurrent compiles) — they exist so a
    # killed run still has every compiled query on record; the quiesced
    # pass below produces the real measurement and _emit_locked keeps the
    # minimum per query.
    measured, failed = set(), set()
    warmup_sec = 0.0
    # a compile request the remote helper silently dropped (OOM-killed
    # server side) never raises AND never lands — without a wedge timeout
    # one such query consumes the whole child budget and starves the
    # retry children (this is exactly how r4 lost its 6 queries)
    wedge_timeout = float(os.environ.get("BENCH_WEDGE_TIMEOUT", "420"))
    last_progress = [time.perf_counter()]
    try:
        while left() > 15:
            for q, f in list(futs.items()):
                if q not in failed and f.done() \
                        and f.exception() is not None:
                    failed.add(q)
                    last_progress[0] = time.perf_counter()
                    emit({"warm_fail": q,
                          "error": repr(f.exception())[:300]})
            # sample the all-done flag BEFORE the ready snapshot: the last
            # warmup can land between the two, and checking in this order
            # guarantees one more loop pass sees it in compiled_ok
            all_done = bool(futs) and all(f.done() for f in futs.values())
            with lock:
                ready = [q for q in qids
                         if q in compiled_ok and q not in measured]
                if last_warm_done[0] + warm_t0 > last_progress[0]:
                    last_progress[0] = last_warm_done[0] + warm_t0
            if not ready:
                if len(measured) + len(failed) >= len(qids) or all_done:
                    break
                if not futs:
                    break
                if time.perf_counter() - last_progress[0] > wedge_timeout:
                    # declare wedged ONLY the stragglers whose compile
                    # actually STARTED (queries queued behind the pool or
                    # the heavy semaphore made no attempt and must not
                    # inherit a failure): mark them, persist the engine's
                    # split hint for each so the next child — running the
                    # standard config — compiles THEM as small programs
                    # and everything else whole, then move on to the
                    # quiesced pass
                    with lock:
                        pending = [q for q, f in futs.items()
                                   if not f.done() and q in compile_started
                                   and q not in compiled_ok]
                    for q in pending:
                        failed.add(q)
                        learn_split_hint(q)
                        emit({"warm_fail": q,
                              "error": f"wedged: no warmup progress in "
                                       f"{wedge_timeout:.0f}s (remote "
                                       f"compile presumed lost; split "
                                       f"hint learned)"})
                    break
                time.sleep(2)
                continue
            for qid in ready:
                if left() < 15:
                    break
                try:
                    t0r = time.perf_counter()
                    # end-to-end: SQL text to host pandas frame (matches
                    # what the pandas baseline measures)
                    c.sql(QUERIES[qid], return_futures=False)
                    sec = time.perf_counter() - t0r
                except Exception as e:
                    # one transient execute failure must not abort the
                    # loop (and with it every remaining query's insurance
                    # record AND the quiesced pass)
                    measured.add(qid)  # quiesced pass retries it
                    emit({"measure_fail": qid, "error": repr(e)[:200]})
                    continue
                measured.add(qid)
                emit({"q": qid, "sec": round(sec, 4),
                      "platform": real_platform})
        # wall time until the LAST warmup landed (measurement overlaps it)
        warmup_sec = last_warm_done[0] or (time.perf_counter() - t0)

        # QUIESCED re-measure: every compile has landed (or failed), the
        # device is idle — these are the numbers that stand.  Per-query
        # wall breakdown (host planning vs device round trip vs host
        # decode) is journaled with the best rep, so every recorded time
        # names its own bottleneck.
        for qid in sorted(measured):
            if left() < 25:
                break
            best, bd = float("inf"), None
            try:
                for _ in range(REPS):
                    t0r = time.perf_counter()
                    c.sql(QUERIES[qid], return_futures=False)
                    sec = time.perf_counter() - t0r
                    if sec < best:
                        best = sec
                        t = getattr(c, "last_timings", None) or {}
                        bd = {k: round(v, 1) for k, v in t.items()}
                    if left() < 20:
                        break
            except Exception as e:
                # a transient failure here must not cost the stage_done record
                # — every number is already journaled
                emit({"requiesce_fail": qid, "error": repr(e)[:200]})
                continue
            # per-query adaptive operator choices (runtime/statistics.py):
            # the report collects record_choice lines from the span tree,
            # so the journal names the variant every published time ran on
            try:
                from dask_sql_tpu.runtime import telemetry as _tl
                rep = _tl.last_report()
                ops = list(getattr(rep, "operators", ()) or ())
            except Exception:
                ops = []
            emit({"q": qid, "sec": round(best, 4),
                  "platform": real_platform, "quiesced": True,
                  "breakdown": bd, "operators": ops})

        # WARM-REPEAT pass: result cache armed, each measured query run
        # twice — run 1 populates, run 2 must be a full-query hit.  The
        # warm latency and hit verdict are journaled per query so cache
        # hit-rate lands in the metrics JSON without ever touching the
        # cold numbers above.
        os.environ["DSQL_RESULT_CACHE_MB"] = cache_mb if cache_mb else "256"
        for qid in sorted(measured):
            if left() < 20:
                break
            try:
                c.sql(QUERIES[qid], return_futures=False)  # populate
                t0r = time.perf_counter()
                c.sql(QUERIES[qid], return_futures=False)
                sec = time.perf_counter() - t0r
                rep = getattr(c, "last_report", None)
                rc = dict(getattr(rep, "cache", None) or {})
                emit({"warm_hit": qid, "sec": round(sec, 4),
                      "hit": bool(rc.get("hit")), "tier": rc.get("tier")})
            except Exception as e:
                emit({"warm_hit_fail": qid, "error": repr(e)[:200]})

        # CONCURRENT-BURST pass: the workload manager armed with 2 slots
        # and a 4-deep queue, 8 mixed-priority threads re-running warm
        # (already-compiled) queries at once.  Journals one record per
        # burst query — admitted (with its measured queue time) or
        # rejected — so admission_reject_rate and queue-time percentiles
        # land in the metrics JSON without touching the cold numbers.
        if measured and left() > 30:
            os.environ["DSQL_RESULT_CACHE_MB"] = "0"
            os.environ["DSQL_MAX_CONCURRENT_QUERIES"] = "2"
            os.environ["DSQL_QUEUE_DEPTH"] = "4"
            os.environ["DSQL_QUEUE_TIMEOUT_MS"] = "120000"
            # the watchtower rides the burst: per-class SLO attainment
            # over the one scheduler-armed, mixed-priority window is the
            # number the BENCH_r06 headline journals
            os.environ["DSQL_EVENTS"] = "1"
            try:
                from dask_sql_tpu.runtime import resilience as _resil
                from dask_sql_tpu.runtime import telemetry as _tl
                burst_qids = (sorted(measured) * 8)[:8]
                block = threading.Barrier(len(burst_qids), timeout=60)
                block_lock = threading.Lock()

                def burst_one(slot, qid):
                    prio = "interactive" if slot % 2 == 0 else "batch"
                    rec = {"burst": qid, "slot": slot, "priority": prio}
                    try:
                        blick = time.perf_counter()
                        block.wait()
                        c.sql(QUERIES[qid], return_futures=False,
                              priority=prio)
                        rep = _tl.last_report()
                        rec["outcome"] = "ok"
                        rec["sec"] = round(time.perf_counter() - blick, 4)
                        rec["queued_ms"] = round(
                            (rep.phases.get("queued") if rep else 0) or 0,
                            3)
                    except _resil.AdmissionRejected as e:
                        rec["outcome"] = "rejected"
                        rec["error"] = repr(e)[:200]
                    except Exception as e:
                        rec["outcome"] = "error"
                        rec["error"] = repr(e)[:200]
                    with block_lock:
                        emit(rec)

                bthreads = [threading.Thread(target=burst_one, args=(s, q))
                            for s, q in enumerate(burst_qids)]
                for t in bthreads:
                    t.start()
                for t in bthreads:
                    t.join(timeout=150)
                from dask_sql_tpu.runtime import events as _ev
                emit({"slo_attainment": {
                    r["class"]: r["attainment"] for r in _ev.slo_rows()
                    if r["total"] > 0}})
            except Exception as e:
                emit({"burst_fail": True, "error": repr(e)[:200]})
            finally:
                os.environ["DSQL_MAX_CONCURRENT_QUERIES"] = "0"
                os.environ["DSQL_EVENTS"] = "0"

        # PARAM-MIX pass (ISSUE 16): a Zipf-distributed client mix of one
        # query SHAPE with many distinct literals — the dominant
        # production pattern parameterized plan identity exists for.
        # Journals compiles vs distinct literals (the sublinearity proof:
        # one shape compiles once however many literals arrive) and the
        # plan-cache hit rate the headline publishes.
        if left() > 20:
            os.environ["DSQL_RESULT_CACHE_MB"] = "0"
            try:
                import numpy as np

                from dask_sql_tpu.runtime import telemetry as _tl
                tpl = ("SELECT l_returnflag, SUM(l_extendedprice) AS s, "
                       "COUNT(*) AS n FROM lineitem WHERE l_quantity > ? "
                       "GROUP BY l_returnflag ORDER BY l_returnflag")
                rng = np.random.RandomState(23)
                distinct = [float(v) for v in
                            np.linspace(1.0, 45.0, 12).round(2)]
                # Zipf rank-frequency over the distinct literals: a few
                # hot values, a long tail — rank r drawn w.p. ∝ 1/r^1.2
                ranks = np.clip(rng.zipf(1.2, size=36), 1,
                                len(distinct)) - 1
                pm0 = _tl.REGISTRY.counters()
                execs = 0
                for r in ranks:
                    c.sql(tpl, params=[distinct[int(r)]],
                          return_futures=False)
                    execs += 1
                pm1 = _tl.REGISTRY.counters()
                emit({"param_mix": {
                    "distinct_literals": len(set(int(r) for r in ranks)),
                    "executions": execs,
                    "compiles": pm1["compiles"] - pm0["compiles"],
                    "param_plans": (pm1["param_plans"]
                                    - pm0["param_plans"]),
                    "param_plan_hits": (pm1["param_plan_hits"]
                                        - pm0["param_plan_hits"]),
                    "param_plan_misses": (pm1["param_plan_misses"]
                                          - pm0["param_plan_misses"]),
                }})
            except Exception as e:
                emit({"param_mix_fail": True, "error": repr(e)[:200]})

        # ESTIMATE-ERROR journal: for every measured query, the byte error
        # of the scan-bytes heuristic vs the flight recorder's measured
        # history against the EWMA'd actual working set — the evidence that
        # the feedback loop shrinks memory-broker reservations.  Envelope-
        # level admission estimates (est_source from the burst pass, the
        # only scheduler-armed window) land alongside.
        if measured and left() > 10:
            try:
                from dask_sql_tpu.runtime import flight_recorder as _fr
                from dask_sql_tpu.runtime import scheduler as _sched
                from dask_sql_tpu.runtime import telemetry as _tl
                from dask_sql_tpu.sql.parser import parse_sql as _ps
                if _fr.enabled():
                    err = {"heuristic": [], "history": []}
                    for qid in sorted(measured):
                        plan = c._get_plan(_ps(QUERIES[qid])[0].query)
                        fp = _fr.plan_fingerprint(plan, c)
                        st = _fr.get_stats(fp) if fp else None
                        actual = float((st or {}).get("bytes") or 0.0)
                        if actual <= 0:
                            continue
                        heur = float(_sched.estimate_plan_bytes(plan, c))
                        err["heuristic"].append(
                            abs(heur - actual) / actual)
                        hist = _fr.plan_history_bytes(plan, c)
                        if hist:
                            err["history"].append(
                                abs(hist - actual) / actual)
                    by_src = {}
                    for ev in _fr.read_events(kind="query"):
                        src = ev.get("est_source")
                        m = ev.get("measured_bytes") or 0
                        if src and m > 0 and ev.get("est_bytes"):
                            by_src.setdefault(src, []).append(
                                abs(ev["est_bytes"] - m) / m)
                    emit({"estimate_error": {
                              k: round(sum(v) / len(v), 4) if v else None
                              for k, v in err.items()},
                          "estimate_error_admitted": {
                              k: round(sum(v) / len(v), 4)
                              for k, v in by_src.items()},
                          "estimate_from_history":
                              _tl.REGISTRY.get("estimate_from_history")})
            except Exception as e:
                emit({"estimate_error_fail": True,
                      "error": repr(e)[:200]})
    finally:
        # stage_done must survive anything the loops above throw: it
        # carries the compile stats and memory evidence for the artifact
        mem = {}
        try:
            # sum across ALL local devices: a mesh run that only reads
            # device[0] under-reports HBM by the device count
            for dev in jax.local_devices():
                stats = dev.memory_stats() or {}
                for k in ("bytes_in_use", "peak_bytes_in_use",
                          "bytes_limit"):
                    if k in stats:
                        mem[k] = mem.get(k, 0) + int(stats[k])
        except Exception:
            pass
        # a backend may expose no allocator stats; account for at
        # least the resident table arrays so device_memory is never
        # silently empty
        try:
            tbl_bytes = 0
            for entry in c.schema[c.schema_name].tables.values():
                tbl = getattr(entry, "table", None)
                for col in getattr(tbl, "columns", []):
                    tbl_bytes += int(col.data.nbytes)
                    if col.mask is not None:
                        tbl_bytes += int(col.mask.nbytes)
            mem.setdefault("table_bytes_resident", tbl_bytes)
        except Exception:
            pass
        # adaptive-dispatch counters (operator_choice_* + the stats
        # cap-hint/scheduler-source evidence) ride the stage_done record
        opc = {}
        try:
            from dask_sql_tpu.runtime import telemetry as _tl
            for k, v in _tl.REGISTRY.counters().items():
                if (k.startswith("operator_choice_")
                        or k in ("stats_cap_hints", "estimate_from_stats",
                                 "stats_tables_collected")):
                    opc[k] = int(v)
        except Exception:
            pass
        emit({"stage_done": True, "load_sec": round(load_sec, 1),
              "warmup_sec": round(warmup_sec, 1), "device_memory": mem,
              "compiled_stats": dict(compiled.stats),
              "operator_counters": opc})
        sys.stdout.flush()
        sys.stderr.flush()
    os._exit(0)  # don't join wedged warmup threads


def main():
    import subprocess

    # before the atexit emitter below exists: a run with no TPU prints
    # one line saying so and no metric line
    platform = _probe_platform()
    if os.environ.get("BENCH_FLEET") == "1" and platform != "cpu":
        sys.exit("bench: BENCH_FLEET=1 starts two server processes that "
                 "each build a Context on the default device; one chip "
                 "serves one process, so the fleet pass runs only with "
                 "BENCH_PLATFORM=cpu")

    t_start = time.monotonic()
    deadline = t_start + TOTAL_BUDGET

    state = {
        "progress": None, "qids": [], "sf": SF, "n_lineitem": 0,
        "gen_sec": 0.0, "platform_choice": platform, "stage_meta": [],
        "emitted": False, "child": None,
    }
    emit_lock = threading.Lock()

    def _kill_child():
        """Emergency exits must not orphan an engine child wedged in a
        compile — it would hold the TPU and poison the next run."""
        proc = state.get("child")
        if proc is not None and proc.poll() is None:
            try:
                proc.kill()
            except OSError:
                pass

    def emit_final(reason=None):
        """Idempotent: compute the metric line from the progress journal and
        print it.  Callable from the watchdog thread, signal handlers,
        atexit, or the happy path — whoever gets there first wins.  The
        lock is held through the PRINT: a second caller (watchdog about to
        os._exit) must block until the line is fully out, or the exit
        could truncate it mid-write."""
        if state.get("emitting_thread") == threading.get_ident():
            # re-entered from a signal handler interrupting our own print:
            # returning lets the interrupted emission complete
            return
        # block TERM/INT for the duration on the main thread: a handler
        # firing between lock acquisition and the marker assignment would
        # re-enter emit_final and deadlock on the non-reentrant lock
        is_main = threading.current_thread() is threading.main_thread()
        old_mask = None
        if is_main:
            try:
                old_mask = signal.pthread_sigmask(
                    signal.SIG_BLOCK, {signal.SIGTERM, signal.SIGINT})
            except (ValueError, OSError):
                pass
        try:
            with emit_lock:
                if state["emitted"]:
                    return
                state["emitting_thread"] = threading.get_ident()
                try:
                    _emit_locked(reason)
                    state["emitted"] = True
                finally:
                    state["emitting_thread"] = None
                    if state.get("die_after_emit"):
                        os._exit(0)
        finally:
            if old_mask is not None:
                signal.pthread_sigmask(signal.SIG_SETMASK, old_mask)

    def _emit_locked(reason):
        times, p_times, platforms = {}, {}, set()
        warm_times, mem, cstats = {}, {}, {}
        started, warm_fails, breakdowns, quiesced = set(), {}, {}, set()
        warm_hits = {}
        bursts = []
        query_ops, op_counters = {}, {}
        first_arrival, restart_times, restart_info = {}, {}, {}
        est_err, est_err_admitted, est_from_hist = {}, {}, None
        slo_att = None
        param_mix = None
        shard_scaling = None
        ooc_evidence = None
        mv_evidence = None
        autopilot_evidence = None
        fleet_evidence = None
        ingest_evidence = None
        load_sec = warmup_sec = 0.0
        try:
            with open(state["progress"]) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if "q" in rec:
                        prev = times.get(rec["q"])
                        if prev is None or rec["sec"] < prev:
                            times[rec["q"]] = rec["sec"]
                            if rec.get("operators"):
                                # variant attribution follows the best rec
                                query_ops[rec["q"]] = rec["operators"]
                        if rec.get("breakdown"):
                            # breakdowns keep their own minimum over the
                            # records that carry one: a faster record
                            # WITHOUT a breakdown must not leave a stale
                            # split attributed to the published time
                            bprev = breakdowns.get(rec["q"])
                            if bprev is None or rec["sec"] < bprev[0]:
                                breakdowns[rec["q"]] = (rec["sec"],
                                                        rec["breakdown"])
                        platforms.add(rec["platform"])
                        if rec.get("quiesced"):
                            quiesced.add(rec["q"])
                    elif "pq" in rec:
                        p_times[rec["pq"]] = rec["sec"]
                    elif "burst" in rec:
                        bursts.append(rec)
                    elif "warm_hit" in rec:
                        warm_hits[rec["warm_hit"]] = {
                            "sec": rec["sec"], "hit": bool(rec.get("hit")),
                            "tier": rec.get("tier")}
                    elif "warm_q" in rec:
                        warm_times[rec["warm_q"]] = rec["sec"]
                    elif "first_arrival" in rec:
                        # keep the EARLIEST record: retries in later
                        # children are not "first" arrivals
                        first_arrival.setdefault(rec["first_arrival"],
                                                 rec["sec"])
                    elif "restart_q" in rec:
                        restart_times[rec["restart_q"]] = rec["sec"]
                    elif rec.get("restart_done"):
                        restart_info = rec
                    elif "shard_scaling" in rec:
                        shard_scaling = rec["shard_scaling"] or None
                    elif "shard_scaling_skip" in rec:
                        shard_scaling = {"skipped":
                                         rec["shard_scaling_skip"]}
                    elif "ooc" in rec:
                        ooc_evidence = rec["ooc"] or None
                    elif "mv" in rec:
                        mv_evidence = rec["mv"] or None
                    elif "autopilot" in rec:
                        autopilot_evidence = rec["autopilot"] or None
                    elif "fleet" in rec:
                        fleet_evidence = rec["fleet"] or None
                    elif "ingest" in rec:
                        ingest_evidence = rec["ingest"] or None
                    elif "slo_attainment" in rec:
                        slo_att = rec["slo_attainment"] or None
                    elif "param_mix" in rec:
                        param_mix = rec["param_mix"] or None
                    elif "estimate_error" in rec:
                        est_err = rec["estimate_error"] or {}
                        est_err_admitted = \
                            rec.get("estimate_error_admitted") or {}
                        est_from_hist = rec.get("estimate_from_history")
                    elif "warm_start" in rec:
                        started.add(rec["warm_start"])
                    elif "warm_fail" in rec:
                        q = rec["warm_fail"]
                        n, _ = warm_fails.get(q, (0, ""))
                        warm_fails[q] = (n + 1, rec.get("error", ""))
                    elif rec.get("stage_done"):
                        load_sec += rec.get("load_sec", 0)
                        warmup_sec += rec.get("warmup_sec", 0)
                        for k, v in (rec.get("device_memory") or {}).items():
                            mem[k] = max(mem.get(k, 0), v)
                        for k, v in (rec.get("compiled_stats") or {}).items():
                            cstats[k] = cstats.get(k, 0) + v
                        for k, v in (rec.get("operator_counters")
                                     or {}).items():
                            op_counters[k] = op_counters.get(k, 0) + v
        except Exception:
            pass
        done = sorted(times)
        qids = state["qids"] or sorted(set(done) | set(p_times))
        missing = [q for q in qids if q not in times]
        # every absent query names its own cause: the artifact must never
        # read as "no problems" while silently short of queries
        missing_detail = {}
        for q in missing:
            n, err = warm_fails.get(q, (0, ""))
            if n:
                missing_detail[str(q)] = {
                    "warm_failures": n, "last_error": err[:300],
                    "status": ("failed-twice (real verdict)" if n >= 2
                               else "failed-once (retryable)")}
            elif q in warm_times:
                missing_detail[str(q)] = {
                    "status": "compiled ok, never measured (out of time)"}
            elif q in started:
                missing_detail[str(q)] = {
                    "status": "warmup in flight when time ran out"}
            else:
                missing_detail[str(q)] = {"status": "never started"}
        # schema-versioned headline: the handful of numbers every consumer
        # (scripts/perf_sentinel.py, the BENCH_r*.json trajectory) compares
        # across runs without spelunking through detail
        fa_vals = list(first_arrival.values())
        headline = {
            "schema": 1,
            "first_arrival_sec": (round(_geomean(fa_vals), 4)
                                  if fa_vals else None),
            "program_store_hit_rate": (
                round(restart_info["program_store_hits"]
                      / max(restart_info["program_store_hits"]
                            + restart_info["compiles"], 1), 3)
                if restart_info else None),
            "vs_pandas_geomean": None,
            "warm_exec_geomean_sec": None,
            "compile_errors": int(cstats.get("compile_errors", 0)),
            # watchtower SLO attainment per priority class over the
            # concurrent-burst pass (the one scheduler-armed window);
            # None when the burst never ran
            "slo_attainment": slo_att,
            # parameterized plan identity (ISSUE 16): fraction of the
            # Zipf param-mix executions served by an already-compiled
            # program of their shape; None when the mix never ran
            "param_plan_hit_rate": (
                round(param_mix["param_plan_hits"]
                      / max(param_mix["executions"], 1), 3)
                if param_mix else None),
            # fleet plane (ISSUE 18, BENCH_FLEET=1): cross-replica warm
            # serves off the shared program store and the fleet-wide
            # plan-cache hit rate over the multi-replica Zipf burst;
            # None when the fleet pass never ran
            "fleet_warm_serves": (fleet_evidence or {}).get("warm_serves"),
            "fleet_plan_cache_hit_rate":
                (fleet_evidence or {}).get("plan_cache_hit_rate"),
            # autopilot (ISSUE 19, BENCH_AUTOPILOT=1): the unattended
            # workload's steady-state geomean over the hand-tuned one
            # (~1.0 = the advisor converged to the operator's setup);
            # None when the pass never ran
            "autopilot_vs_tuned_geomean":
                (autopilot_evidence or {}).get("vs_tuned_geomean"),
        }
        if not done:
            out = {"metric": "tpch_q1_q22_geomean_wall", "value": -1,
                   "unit": "s", "vs_baseline": 0,
                   "headline": headline,
                   "detail": {"error": "no engine queries completed",
                              "reason": reason,
                              "sf": state["sf"],
                              "platform_choice": state["platform_choice"],
                              "pandas_sec": {str(k): round(v, 4)
                                             for k, v in p_times.items()},
                              "stages": state["stage_meta"]}}
        else:
            ok_b = [b for b in bursts if b.get("outcome") == "ok"
                    and b.get("queued_ms") is not None]
            burst_queue = None
            if ok_b:
                q_ms = [b["queued_ms"] for b in ok_b]
                burst_queue = {
                    "p50": round(_pctile(q_ms, 50), 1),
                    "p90": round(_pctile(q_ms, 90), 1),
                    "by_class": {
                        p: round(_pctile([b["queued_ms"] for b in ok_b
                                          if b.get("priority") == p], 50), 1)
                        for p in ("interactive", "batch")
                        if any(b.get("priority") == p for b in ok_b)},
                }
            geo_e = _geomean([times[q] for q in done])
            based = [q for q in done if q in p_times]
            geo_p = _geomean([p_times[q] for q in based]) if based else 0.0
            ratio = (_geomean([p_times[q] / times[q] for q in based])
                     if based else 0.0)
            wins = sum(1 for q in based if times[q] < p_times[q])
            headline["vs_pandas_geomean"] = round(ratio, 3)
            headline["warm_exec_geomean_sec"] = round(geo_e, 4)
            out = {
                "metric": "tpch_q1_q22_geomean_wall",
                "value": round(geo_e, 4),
                "unit": "s (geomean over completed queries, lower is better)",
                "vs_baseline": round(ratio, 3),
                "headline": headline,
                "detail": {
                    "sf": state["sf"],
                    "platform": "/".join(sorted(platforms)),
                    "lineitem_rows": state["n_lineitem"],
                    "queries": len(done),
                    "missing_queries": missing,
                    "missing_detail": missing_detail,
                    "quiesced_queries": sorted(quiesced),
                    "reason": reason,
                    "stage_errors": state["stage_meta"],
                    "engine_wins": wins,
                    "engine_sec": {str(k): round(times[k], 4) for k in done},
                    "query_breakdown_ms": {str(k): breakdowns[k][1]
                                           for k in sorted(breakdowns)},
                    "pandas_sec": {str(k): round(p_times[k], 4)
                                   for k in sorted(p_times)},
                    "pandas_geomean_sec": round(geo_p, 4),
                    # the PR-10 success metric spelled out: geomean of
                    # per-query pandas/engine speedups (same number as
                    # vs_baseline; >1.0 = the engine beats pandas warm)
                    "vs_pandas_geomean": round(ratio, 3),
                    # adaptive-dispatch evidence (runtime/statistics.py):
                    # which variant each published time ran on, and the
                    # operator_choice_* counter totals across the run
                    "query_operators": {str(k): query_ops[k]
                                        for k in sorted(query_ops)},
                    "operator_choice": op_counters or None,
                    "warm_or_compile_sec_per_query":
                        {str(k): warm_times[k] for k in sorted(warm_times)},
                    # tiered-execution / program-store evidence: latency of
                    # each query's very first submission (cold store = the
                    # compile wall; primed store = store-load + execute)...
                    "first_arrival_sec": {str(k): first_arrival[k]
                                          for k in sorted(first_arrival)},
                    # ...and the restart-warm pass: a FRESH process against
                    # the populated DSQL_PROGRAM_STORE (zero-compile proof)
                    "restart_warm_sec": {str(k): restart_times[k]
                                         for k in sorted(restart_times)},
                    "warm_start_sec": restart_info.get("warm_start_sec"),
                    # multi-chip evidence (parallel/spmd.py): Q1/Q6 wall
                    # time single-device vs row-sharded over the mesh,
                    # with spmd_served certifying the sharded path ran
                    "shard_scaling": shard_scaling,
                    # out-of-core evidence (runtime/spill.py +
                    # physical/morsel.py): chunked Q1/Q6/Q3 completed and
                    # matched the resident engine, with spill traffic and
                    # the spill store's peak device occupancy
                    "ooc": ooc_evidence,
                    # incremental-view evidence (runtime/matview.py,
                    # BENCH_MV=1): maintained refresh vs full recompute
                    # of the defining query after a 1k-row append into
                    # lineitem, with the mv refresh hit-rate and the
                    # served-vs-recomputed exactness verdict
                    "mv": mv_evidence,
                    # autopilot evidence (runtime/autopilot.py,
                    # BENCH_AUTOPILOT=1): unattended vs hand-tuned
                    # append-then-read rounds, the advisor's auto-created
                    # views/serves, and the exactness verdict
                    "autopilot": autopilot_evidence,
                    # fleet-plane evidence (runtime/fleet.py,
                    # BENCH_FLEET=1): two replicas on one fleet dir +
                    # program store under a Zipf multi-tenant burst —
                    # per-tenant SLO attainment, replica B's zero-compile
                    # warm serves, and the fleet plan-cache hit rate
                    "fleet": fleet_evidence,
                    # continuous-ingestion evidence (runtime/ingest.py,
                    # BENCH_INGEST=1): WAL-armed appends beside maintained
                    # view reads — appends/sec, read p50/p99, the max
                    # observed staleness, and the exactness verdict
                    "ingest": ingest_evidence,
                    "program_store_hit_rate": (
                        round(restart_info["program_store_hits"]
                              / max(restart_info["program_store_hits"]
                                    + restart_info["compiles"], 1), 3)
                        if restart_info else None),
                    # result-cache evidence from the warm-repeat pass: the
                    # 2nd run of each query with the cache armed (cold
                    # numbers above always run cache-off)
                    "warm_hit_sec": {str(k): warm_hits[k]["sec"]
                                     for k in sorted(warm_hits)},
                    "result_cache_hit_rate": (
                        round(sum(1 for v in warm_hits.values() if v["hit"])
                              / len(warm_hits), 3) if warm_hits else None),
                    # workload-manager evidence from the concurrent-burst
                    # pass (2-slot scheduler, 8 mixed-priority threads):
                    # the fraction the admission controller turned away,
                    # and queue-time percentiles for the admitted rest
                    # sublinearity proof (ISSUE 16): a Zipf client mix of
                    # one query shape with many distinct literals — the
                    # compile count must track SHAPES (1), not literals
                    "compiles_vs_distinct_literals": param_mix,
                    "admission_reject_rate": (
                        round(sum(1 for b in bursts
                                  if b.get("outcome") == "rejected")
                              / len(bursts), 3) if bursts else None),
                    "burst_queue_time_ms": burst_queue,
                    # estimate-feedback evidence (runtime/flight_recorder):
                    # mean |estimated - actual| / actual working-set bytes
                    # per estimate source — "history" shrinking under
                    # "heuristic" is the loop closing — plus admission-time
                    # envelope errors and the estimate_from_history count
                    "estimate_error_by_source": est_err or None,
                    "estimate_error_admitted": est_err_admitted or None,
                    "estimate_from_history": est_from_hist,
                    "gen_sec": round(state["gen_sec"], 1),
                    "load_sec": round(load_sec, 1),
                    "warmup_compile_sec": round(warmup_sec, 1),
                    "compiled_stats": cstats,
                    # stage-program cache effectiveness across the run:
                    # hits / (hits + compiles), the number every perf PR
                    # watches in the BENCH_r*.json trajectory
                    "stage_cache_hit_rate": (
                        round(cstats.get("stage_hits", 0)
                              / (cstats.get("stage_hits", 0)
                                 + cstats.get("stage_compiles", 0)), 3)
                        if (cstats.get("stage_hits", 0)
                            + cstats.get("stage_compiles", 0)) else None),
                    "device_memory": mem,
                    "budget_sec": TOTAL_BUDGET,
                    "elapsed_sec": round(time.monotonic() - t_start, 1),
                },
            }
        line = json.dumps(out)
        # results FILE first: it survives even a truncated stdout.  The
        # write is atomic (tmp + replace) so a kill mid-emit can't leave a
        # half-written artifact.
        results_path = os.environ.get("BENCH_RESULTS_FILE")
        if not results_path and state["progress"]:
            results_path = os.path.join(
                os.path.dirname(state["progress"]), "bench_result.json")
        if not results_path:
            # the metrics object must ALWAYS land in a file: r05's artifact
            # read "parsed": null because the bare stdout line was fished
            # out of a mangled log tail
            results_path = os.path.join(os.getcwd(), "bench_result.json")
        if results_path:
            try:
                tmp = f"{results_path}.tmp{os.getpid()}"
                with open(tmp, "w") as f:
                    f.write(line + "\n")
                os.replace(tmp, results_path)
            except OSError:
                pass
        # leading newline forces the bare line out of any partial log line;
        # the sentinel copy is immune to interleaved ANSI/log output
        sys.stdout.flush()
        print("\n" + line, flush=True)
        print("DSQL_BENCH_RESULT " + line, flush=True)

    def _die(signum, frame):
        _kill_child()
        if state.get("emitting_thread") == threading.get_ident():
            # the signal interrupted our own in-progress emission: mark it
            # and let the print finish (the finally above exits for us)
            state["die_after_emit"] = True
            return
        emit_final(reason=f"signal {signum}")
        os._exit(0)

    signal.signal(signal.SIGTERM, _die)
    signal.signal(signal.SIGINT, _die)
    atexit.register(lambda: emit_final(reason="atexit"))

    workdir = os.environ.get("BENCH_WORKDIR") or tempfile.mkdtemp(
        prefix="bench_tpch_")
    data_dir = os.path.join(workdir, "data")
    os.makedirs(data_dir, exist_ok=True)
    progress = os.path.join(workdir, "progress.jsonl")
    open(progress, "w").close()
    state["progress"] = progress

    # the watchdog is armed BEFORE any expensive step: from here on the
    # metric line prints no matter where time runs out
    watchdog = threading.Timer(
        max(deadline - EMIT_MARGIN - time.monotonic(), 1.0),
        lambda: (emit_final(reason="watchdog"), _kill_child(),
                 os._exit(0)))
    watchdog.daemon = True
    watchdog.start()

    sf = SF

    gen_sec, n_lineitem = _cache_data(sf, data_dir)
    state["gen_sec"] = gen_sec
    state["n_lineitem"] = n_lineitem

    from benchmarks.tpch import QUERIES
    qids = sorted(QUERIES)
    only = os.environ.get("BENCH_QUERIES")
    if only:
        only_set = {int(x) for x in only.split(",")}
        qids = [q for q in qids if q in only_set]
    qids = _order(qids)
    state["qids"] = sorted(qids)

    # ---- pandas baseline FIRST (cheap, cannot wedge): single-threaded
    # host pandas, hand-written per query, oracle-validated against the
    # engine in tests/integration/test_pandas_oracle.py
    from benchmarks.pandas_tpch import PANDAS_QUERIES
    data = _load_data(data_dir)
    p_deadline = min(time.monotonic() + PANDAS_BUDGET,
                     deadline - EMIT_MARGIN - 10)
    with open(progress, "a") as pf:
        for qid in qids:
            if time.monotonic() > p_deadline:
                break
            fn = PANDAS_QUERIES.get(qid)
            if fn is None:
                continue
            best = float("inf")
            try:
                for _ in range(PANDAS_REPS):
                    t0 = time.perf_counter()
                    fn(data)
                    best = min(best, time.perf_counter() - t0)
                    if time.monotonic() > p_deadline:
                        break
            except Exception as e:
                # one broken baseline query must not cost the whole bench
                print(f"bench: pandas baseline q{qid} failed: {e!r}",
                      file=sys.stderr)
                continue
            pf.write(json.dumps({"pq": qid, "sec": round(best, 4)}) + "\n")
            pf.flush()
    del data

    # ---- engine: one child (table transfer is paid once); restart on the
    # remaining queries only while enough budget remains
    # caps / program store / history persist across runs at one fixed
    # place in the checkout; the XLA compile cache is placed by the
    # package (JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache)
    cache_root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              ".bench_cache", platform)
    os.makedirs(cache_root, exist_ok=True)
    env_base = dict(os.environ, BENCH_STAGE="1",
                    BENCH_DATA_DIR=data_dir,
                    BENCH_PROGRESS=progress,
                    BENCH_PLATFORM_CHOICE=platform,
                    BENCH_SF=str(sf))
    # never eager-fallback in the engine child: on a TPU the eager path
    # is thousands of per-op dispatches that wedge the whole
    # run behind one broken program — fail fast, journal warm_fail, move on
    env_base.setdefault("DSQL_EAGER_FALLBACK", "0")
    env_base.setdefault("DSQL_CAPS_FILE",
                        os.path.join(cache_root, "caps.json"))
    # persistent program store (runtime/program_store.py): the measurement
    # child populates it, the restart-warm child below proves a fresh
    # process serves every query with zero XLA compiles, and a bench run
    # primed by an earlier run on this host starts warm outright
    env_base.setdefault("DSQL_PROGRAM_STORE",
                        os.path.join(cache_root, "programs"))
    # flight recorder (runtime/flight_recorder.py): the measurement child
    # leaves per-query envelopes + operator statistics, so the burst pass
    # estimates its admissions from MEASURED history and the child can
    # journal estimate-vs-actual byte error against the scan-bytes guess
    env_base.setdefault("DSQL_HISTORY_FILE",
                        os.path.join(cache_root, "history.jsonl"))

    def journal_state():
        """(measured set, warm-failure counts) from the progress file."""
        got, failed = set(), {}
        with open(progress) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if "q" in rec:
                    got.add(rec["q"])
                elif "warm_fail" in rec:
                    failed[rec["warm_fail"]] = \
                        failed.get(rec["warm_fail"], 0) + 1
        return got, failed

    attempt = 0
    max_attempts = int(os.environ.get("BENCH_MAX_CHILDREN", "3"))
    # per-attempt DSQL_SPLIT_HEAVY schedule ("-" = engine default).  The
    # primary splitting mechanism is the engine's learned per-plan hint
    # (wedged/failed compiles persist "__split__" into the caps file, so
    # retry children split exactly the guilty plans and nothing else);
    # this env schedule is the LAST-RESORT hammer for a final child when
    # hints could not be written.  Measured in BENCH_r05 (an earlier backend):
    # Q3's whole program never returned from the compile helper, split=2
    # SIGSEGVs it, split=1 compiles in ~290 s and runs.
    split_schedule = os.environ.get("BENCH_SPLIT_SCHEDULE", "-,-,1").split(",")
    while attempt < max_attempts:
        got, failed = journal_state()
        # compile failures were often TRANSIENT in BENCH_r05 (the compile
        # helper got OOM-killed under load), and wedge-detected stragglers
        # deserve a smaller-program retry — a strike earned at a higher
        # split threshold must not bar the retry at a lower one, so a
        # query stays retryable while its failure count <= attempt number
        remaining_q = [q for q in qids
                       if q not in got and failed.get(q, 0) <= attempt]
        budget_left = deadline - EMIT_MARGIN - time.monotonic()
        if not remaining_q or budget_left < MIN_CHILD_BUDGET:
            break
        child_deadline_ts = time.time() + budget_left - 10
        env = dict(env_base,
                   BENCH_STAGE_QUERIES=",".join(map(str, remaining_q)),
                   BENCH_CHILD_DEADLINE=str(child_deadline_ts))
        split = (split_schedule[attempt] if attempt < len(split_schedule)
                 else split_schedule[-1])
        if split.strip() not in ("", "-"):
            env["DSQL_SPLIT_HEAVY"] = split.strip()
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        state["child"] = proc  # emergency exits kill it (no orphans)
        try:
            _, err = proc.communicate(timeout=budget_left)
            if proc.returncode != 0:
                sys.stderr.write(err[-2000:])
                state["stage_meta"].append(
                    {"attempt": attempt, "error": f"rc={proc.returncode}"})
            # a clean exit does NOT end the loop: the child may have
            # retired at its deadline or given up on failed warmups — the
            # while condition relaunches on whatever queries remain, and
            # exits when none do
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()  # reap: no zombie + closed pipe FDs
            print(f"bench: engine child {attempt} exceeded its "
                  f"{budget_left:.0f}s budget; collecting partials",
                  file=sys.stderr)
            state["stage_meta"].append({"attempt": attempt,
                                        "error": "timeout"})
        finally:
            state["child"] = None
        attempt += 1

    # RESTART-WARM pass: a FRESH process against the populated program
    # store re-runs the measured queries — the cross-process warm-start
    # evidence (program_store_hit_rate, warm_start_sec, per-query
    # restart_warm_sec) without touching the cold numbers above
    restart_left = deadline - EMIT_MARGIN - time.monotonic()
    got_now = sorted(journal_state()[0])
    if got_now and restart_left > 60:
        env = dict(env_base, BENCH_WARM_RESTART="1",
                   BENCH_STAGE_QUERIES=",".join(map(str, got_now)),
                   BENCH_CHILD_DEADLINE=str(time.time() + restart_left - 10))
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        state["child"] = proc
        try:
            proc.communicate(timeout=restart_left)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()  # reap
            state["stage_meta"].append({"attempt": "restart_warm",
                                        "error": "timeout"})
        finally:
            state["child"] = None

    # SHARD-SCALING pass: Q1/Q6 single-device vs row-sharded over the
    # device mesh through the explicit SPMD executor.  The XLA_FLAGS
    # default gives a CPU-only host its 8-virtual-device mesh; a real
    # multi-chip host keeps its own devices.
    scaling_left = deadline - EMIT_MARGIN - time.monotonic()
    if scaling_left > 60:
        env = dict(env_base, BENCH_SHARD_SCALING="1",
                   BENCH_STAGE_QUERIES="1,6",
                   BENCH_CHILD_DEADLINE=str(time.time() + scaling_left - 10))
        env.setdefault("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        state["child"] = proc
        try:
            proc.communicate(timeout=scaling_left)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()  # reap
            state["stage_meta"].append({"attempt": "shard_scaling",
                                        "error": "timeout"})
        finally:
            state["child"] = None

    # OUT-OF-CORE pass (opt-in: BENCH_OOC=1): chunked Q1/Q6/Q3 through the
    # streaming + grace-hash spill path, checked against the resident
    # engine — journals ooc_completed / spill_bytes / peak_device_bytes
    ooc_left = deadline - EMIT_MARGIN - time.monotonic()
    if os.environ.get("BENCH_OOC") == "1" and ooc_left > 60:
        env = dict(env_base, BENCH_OOC_CHILD="1",
                   BENCH_STAGE_QUERIES="1,6,3",
                   BENCH_CHILD_DEADLINE=str(time.time() + ooc_left - 10))
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        state["child"] = proc
        try:
            proc.communicate(timeout=ooc_left)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()  # reap
            state["stage_meta"].append({"attempt": "ooc",
                                        "error": "timeout"})
        finally:
            state["child"] = None

    # MATERIALIZED-VIEW pass (opt-in: BENCH_MV=1): an aggregate view over
    # lineitem maintained through a 1k-row append — journals refresh_sec
    # vs recompute_sec, the mv refresh hit-rate, and the served-vs-
    # recomputed exactness verdict (runtime/matview.py)
    mv_left = deadline - EMIT_MARGIN - time.monotonic()
    if os.environ.get("BENCH_MV") == "1" and mv_left > 60:
        env = dict(env_base, BENCH_MV_CHILD="1",
                   BENCH_STAGE_QUERIES="1",
                   BENCH_CHILD_DEADLINE=str(time.time() + mv_left - 10))
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        state["child"] = proc
        try:
            proc.communicate(timeout=mv_left)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()  # reap
            state["stage_meta"].append({"attempt": "mv",
                                        "error": "timeout"})
        finally:
            state["child"] = None

    # AUTOPILOT pass (opt-in: BENCH_AUTOPILOT=1): unattended convergence
    # vs a hand-tuned matview under the same append-then-read rounds —
    # journals the unattended-vs-tuned geomean ratio the perf sentinel
    # shows as an informational row (runtime/autopilot.py)
    ap_left = deadline - EMIT_MARGIN - time.monotonic()
    if os.environ.get("BENCH_AUTOPILOT") == "1" and ap_left > 60:
        env = dict(env_base, BENCH_AUTOPILOT_CHILD="1",
                   BENCH_STAGE_QUERIES="1",
                   BENCH_CHILD_DEADLINE=str(time.time() + ap_left - 10))
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        state["child"] = proc
        try:
            proc.communicate(timeout=ap_left)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()  # reap
            state["stage_meta"].append({"attempt": "autopilot",
                                        "error": "timeout"})
        finally:
            state["child"] = None

    # FLEET pass (opt-in: BENCH_FLEET=1): two server replicas on one
    # shared DSQL_FLEET_DIR + fresh shared program store, a Zipf
    # multi-tenant parameterized burst split across them — journals
    # per-tenant SLO attainment off the merged fleet plane, the
    # fleet-wide plan-cache hit rate, and the cross-replica warm-serve
    # verdict (replica B answers A's shapes with zero compiles)
    fleet_left = deadline - EMIT_MARGIN - time.monotonic()
    if os.environ.get("BENCH_FLEET") == "1" and fleet_left > 60:
        env = dict(env_base, BENCH_FLEET_CHILD="1",
                   BENCH_STAGE_QUERIES="1",
                   BENCH_CHILD_DEADLINE=str(time.time() + fleet_left - 10))
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        state["child"] = proc
        try:
            proc.communicate(timeout=fleet_left)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()  # reap
            state["stage_meta"].append({"attempt": "fleet",
                                        "error": "timeout"})
        finally:
            state["child"] = None

    # CONTINUOUS-INGESTION pass (opt-in: BENCH_INGEST=1): WAL-armed
    # appends interleaved with maintained-view reads — journals sustained
    # appends/sec x read p99 x max staleness, plus the exactness verdict
    # of the served view vs a recompute (runtime/ingest.py)
    ing_left = deadline - EMIT_MARGIN - time.monotonic()
    if os.environ.get("BENCH_INGEST") == "1" and ing_left > 60:
        env = dict(env_base, BENCH_INGEST_CHILD="1",
                   BENCH_STAGE_QUERIES="1",
                   BENCH_CHILD_DEADLINE=str(time.time() + ing_left - 10))
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        state["child"] = proc
        try:
            proc.communicate(timeout=ing_left)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()  # reap
            state["stage_meta"].append({"attempt": "ingest",
                                        "error": "timeout"})
        finally:
            state["child"] = None

    watchdog.cancel()
    emit_final(reason="complete")


if __name__ == "__main__":
    if os.environ.get("BENCH_STAGE") == "1":
        _stage_main()
    else:
        main()
