#!/usr/bin/env bash
# Executable form of .github/workflows/test.yml for environments without a
# GitHub runner (this image). Runs the same four jobs in sequence:
#   1. native parser build from source + load check
#   2. full suite, single device
#   3. distributed suites on the 8-device virtual CPU mesh
#   4. bare `pip install .` import smoke test (native fallback path)
set -euo pipefail
cd "$(dirname "$0")/.."

echo "=== [1/4] native build ==="
make -C native clean all
python -c "from dask_sql_tpu.native import available; assert available()"

echo "=== [2/4] full suite (single device, process-isolated groups) ==="
# Grouped into separate pytest processes: a crash in one group fails THAT
# group loudly instead of silently truncating the whole run, and per-process
# memory stays bounded (the one-process 565-test run peaked at ~4.4 GB and
# segfaulted in r2).  set -e aborts on the first failing group.
python -m pytest tests/unit -q
python -m pytest tests/integration \
    --ignore=tests/integration/test_tpch.py \
    --ignore=tests/integration/test_tpch_mesh.py \
    --ignore=tests/integration/test_streaming.py \
    --ignore=tests/integration/test_distributed.py \
    --ignore=tests/integration/test_compiled.py \
    --ignore=tests/integration/test_pandas_oracle.py -q
python -m pytest tests/integration/test_compiled.py \
                 tests/integration/test_streaming.py -q
python -m pytest tests/integration/test_tpch.py \
                 tests/integration/test_pandas_oracle.py -q

echo "=== [2b] fault-injection smoke (resilience ladder) ==="
# the first compile of every query is sabotaged (runtime/faults.py); the
# ladder must retry/degrade to the same oracle-correct answers
DSQL_FAULT_INJECT=compile:1 python scripts/fault_smoke.py

echo "=== [2c] observability smoke (telemetry layer) ==="
# three queries with tracing armed: well-formed QueryReports, annotated
# EXPLAIN ANALYZE, non-empty advancing /metrics, chrome-trace exports
python scripts/obs_smoke.py

echo "=== [2d] result-cache smoke (reuse layer) ==="
# a repeated query must hit (execute >=5x faster), DDL on a referenced
# table must invalidate, and DSQL_RESULT_CACHE_MB=0 must disable cleanly
python scripts/cache_smoke.py

echo "=== [2e] scheduler smoke (workload manager) ==="
# 8 mixed-priority queries through a 2-slot scheduler: none lost,
# interactive p50 queue time < batch p50, admission counters reconcile,
# and DSQL_MAX_CONCURRENT_QUERIES=0 restores pre-subsystem behavior
python scripts/sched_smoke.py

echo "=== [2f] chaos soak (failure-domain recovery) ==="
# 45 s of randomized probabilistic faults (p=0.05, every site) under 4
# concurrent mixed-priority clients: zero wrong results, zero lost/hung
# queries, admission counters reconcile, engine healthy afterwards
python scripts/chaos_soak.py --budget-s 45

echo "=== [2g] warm-start smoke (tiered execution + program store) ==="
# a fresh process pointed at a populated DSQL_PROGRAM_STORE must answer
# previously-seen queries with ZERO XLA compiles; with an empty store and
# a slowed compile, the first arrival must answer on the eager tier
# without blocking, then run compiled on the next arrival
python scripts/warmstart_smoke.py

echo "=== [2h] stats smoke (adaptive operator selection) ==="
# dense direct-index must beat forced hash on a 2M-row dense-key
# aggregate, all forced variants must agree, the stats join reorder must
# attach the fact table last, and DSQL_ADAPTIVE=0 must restore baseline
python scripts/stats_smoke.py

echo "=== [2i] shard smoke (explicit SPMD multi-chip executor) ==="
# Q1/Q3/Q6 sharded over the 8-device mesh must match the single-device
# answers with the spmd_* counters proving the sharded path served them
# (exchange/partial-agg collectives, nonzero exchange bytes on Q3), a
# zero broadcast cap must force the hash-partition exchange join, and
# DSQL_MESH=0 must restore the baseline with no spmd counters moving
python scripts/shard_smoke.py

echo "=== [2j] out-of-core smoke (spill manager + grace-hash joins) ==="
# TPC-H-shaped queries over chunked tables under a tiny device budget:
# Q1/Q6 shapes stream, a Q3 shape grace-hash-partitions through the spill
# store (spill_partitions > 0, runs freed, device occupancy bounded), and
# DSQL_SPILL_MB=0 restores the pre-spill StreamingUnsupported baseline
python scripts/ooc_smoke.py

echo "=== [2k] profile smoke (device-level query profiler) ==="
# EXPLAIN PROFILE over the 8-device mesh must render nonzero per-stage
# XLA cost, per-device HBM rows, sane shard skew and collective bytes by
# kind; the cost-model estimate rung must close; DSQL_PROFILE=0 must
# never even import the profiler
python scripts/profile_smoke.py

echo "=== [2m] matview smoke (incremental view maintenance) ==="
# a 1k-row append into a 1M-row base must refresh the maintained view
# >=5x faster than recomputing the defining query, stay pandas-oracle
# exact across appends and an overwrite, reconcile the mv_* counters,
# and DSQL_MV=0 must restore pre-subsystem behavior
python scripts/mv_smoke.py

echo "=== [2n] events smoke (watchtower: traces, bus, SLO burn) ==="
# one trace ID must round-trip client -> wire -> span tree -> envelope ->
# system.events (a child process included), /v1/events must stream with
# a working cursor, a deliberately slow query must trip the interactive
# burn-rate gauge, and DSQL_EVENTS=0 must never even import the bus
python scripts/events_smoke.py

echo "=== [2o] param smoke (parameterized plan identity) ==="
# 50 literal variants of one query shape must compile at most twice with
# a >90% plan-cache hit rate and pandas-oracle parity; a fresh process
# must serve a never-seen literal of a stored shape with zero compiles;
# DSQL_PARAM_PLANS=0 must restore value-baked program identity
python scripts/param_smoke.py

echo "=== [2p] fleet smoke (result paging + tenant quotas + kill switches) ==="
# a ~1M-row result must page through the spool behind a real nextUri with
# the peak single response under 10% of the whole, a noisy tenant on a
# 2-slot server must be throttled (429 + honest Retry-After) while a quiet
# tenant loses zero queries, a client that disconnects mid-pagination must
# be fully reaped within DSQL_RESULT_TTL_S (no /v1/engine occupancy), and
# DSQL_RESULT_PAGE_ROWS=0 / DSQL_TENANCY=0 must restore the pre-armor wire
python scripts/fleet_smoke.py

echo "=== [2q] fleet obs smoke (replica registry + shared warmth) ==="
# two real server replicas on one shared DSQL_FLEET_DIR + program store:
# replica B must serve replica A's query shape with ZERO compiles,
# /v1/fleet must reconcile with each replica's own /v1/engine + /metrics,
# one trace ID must stitch across both replicas in the merged
# system.events stream, and unsetting DSQL_FLEET_DIR must restore the
# label-free baseline wire exactly (fleet module never imported)
python scripts/fleet_obs_smoke.py

echo "=== [2r] autopilot smoke (closed loop: watchtower -> optimizer) ==="
# a shifting workload must converge unattended: the top view candidate
# auto-materialized within 3 queries and served oracle-exact across an
# append, the cold view dropped with its budget freed, a skewed grace
# join re-planned via a journaled hint that measures faster on the next
# run, everything visible in system.autopilot, and DSQL_AUTOPILOT=0 a
# bit-for-bit silent baseline
python scripts/autopilot_smoke.py

echo "=== [2s] ingest smoke (WAL-backed continuous ingestion) ==="
# sustained appends must keep delta-join and COUNT(DISTINCT) views
# oracle-exact with every refresh incremental (>=5x faster than the
# defining recompute), readers must never see a partial batch or two
# prefixes in one query, kill -9 must lose zero acked batches (WAL
# replay), and DSQL_INGEST=0 / an unset dir must never even import the
# ingest module
python scripts/ingest_smoke.py

echo "=== [3/4] mesh suites (8 virtual devices) + 2-process multihost ==="
python -m pytest tests/integration/test_distributed.py \
                 tests/integration/test_tpch_mesh.py \
                 tests/integration/test_spmd_executor.py \
                 tests/integration/test_multihost.py -q

echo "=== [4/4] bare install smoke ==="
TMPDIR=$(mktemp -d)
# --no-build-isolation/--no-deps: the zero-egress image can fetch neither
# the isolated build env's setuptools nor the install_requires; the venv
# already carries both, and the smoke below resolves deps from the venv
pip install --quiet --no-build-isolation --no-deps \
    --target "$TMPDIR/site" . >/dev/null
(cd /tmp && PYTHONPATH="$TMPDIR/site" python - <<'EOF'
import jax; jax.config.update('jax_platforms', 'cpu')
import pandas as pd
from dask_sql_tpu import Context
c = Context()
c.create_table('t', pd.DataFrame({'a': [1, 2, 3]}))
out = c.sql('SELECT SUM(a) AS s FROM t', return_futures=False)
assert int(out['s'][0]) == 6, out
print('bare install OK')
EOF
)
rm -rf "$TMPDIR"
echo "=== CI green ==="
