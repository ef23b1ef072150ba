#!/usr/bin/env python3
"""The quickest proof that the engine still starts on the attached chip.

One process drives the shipped path once, through the entry points a user
calls, at TPC-H SF1 (the spec's smallest legal scale; every table resident
on the device at the engine's own x64 widths):

    device   jax.devices(), versions, where the XLA compile cache is
    kernel   the group-by reduction kernel is exact on integer rows
    load     generate_tpch(sf, seed) -> Context.create_table x 8
    library  Q1, Q6, Q12 through Context.sql(q).to_pandas(), engine as
             shipped (tiering, scheduler, result cache at their defaults)
    correct  every result against benchmarks/pandas_tpch.py
    server   the same queries and one PREPARE/EXECUTE over real HTTP
             against Context.run_server() in this same process

Each phase prints one JSON line.  Any failed assertion or exception ends
the run with a traceback and a non-zero exit code; nothing catches a phase.
Only when every phase passed ON A TPU is the last line of stdout

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--chips 4`` runs the mesh path instead (Context(mesh=default_mesh(4)))
and nothing else.  ``--allow-cpu`` walks every phase on whatever backend
JAX finds (a rehearsal: ``JAX_PLATFORMS=cpu python chip_smoke.py --sf 0.01
--allow-cpu``); its last line is never the ok line.

One process per chip: nothing here starts a child.  The XLA compile cache
is placed by the package (``JAX_COMPILATION_CACHE_DIR``, else
``<repo>/.jax_cache``).
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import resource
import sys
import time
import urllib.request

#: Q12 stands where the issue named Q3: under the shipped TPU strategy Q3's whole-plan program
#: (two merge joins, a sorted group-by, a top-k sort) needs longer to compile
#: than this whole script may run (CHANGES.md, PR 23); Q12 is the nearest
#: query the shipped engine compiles in time (the same orders-lineitem join,
#: a grouped aggregate, an ORDER BY).
QIDS = (1, 6, 12)
#: the one DATE literal a query varies, and the pandas keyword that takes it
_SPEC_LITERAL = {1: ("1998-09-02", "shipdate"),
                 12: ("1994-01-01", "receipt_from")}
_COUNTER_PREFIXES = ("compile", "recompiles", "fallbacks", "hits",
                     "served_eager", "background_compile", "program_store_",
                     "result_cache_", "param_plan", "pallas_", "spmd_")


#: where the kernel says what this process's control group holds (v2, v1)
_CGROUP_USAGE = ("/sys/fs/cgroup/memory.current",
                 "/sys/fs/cgroup/memory/memory.usage_in_bytes")


def _host_memory() -> dict:
    """Host memory of this process now: resident bytes, their high-water
    mark, and what its control group is charged (None where not exposed).
    On the chip tool's machine the resident figure runs ~9 GB above the
    group's from the moment the TPU runtime starts (CHANGES.md, PR 23)."""
    with open("/proc/self/statm") as f:
        rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    cgroup = None
    for path in _CGROUP_USAGE:
        if os.path.exists(path):
            with open(path) as f:
                cgroup = int(f.read())
            break
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    return {"host_rss_bytes": rss, "host_peak_rss_bytes": peak,
            "host_cgroup_bytes": cgroup}


def _emit(phase: str, t0: float, **fields) -> None:
    """One line per phase; each carries the host memory at that moment
    (XLA compiles on the host, gigabytes at a time)."""
    print(json.dumps({"phase": phase,
                      "seconds": round(time.perf_counter() - t0, 3),
                      **fields, **_host_memory()}), flush=True)


def _variant(qid: int, i: int):
    """(sql, pandas keywords) of query ``qid`` with its ``i``-th literal;
    ``i == 0`` is the spec's own text.  A changed literal reuses the
    parameterized program and misses the result cache, so its execution
    is an execution."""
    import pandas as pd

    from benchmarks.tpch import QUERIES

    sql = QUERIES[qid]
    if i == 0:
        return sql, {}
    if qid == 6:
        assert "l_quantity < 24" in sql
        return (sql.replace("l_quantity < 24", f"l_quantity < {24 + i}"),
                {"quantity": 24 + i})
    spec, keyword = _SPEC_LITERAL[qid]
    assert spec in sql
    day = (pd.Timestamp(spec) - pd.Timedelta(days=i)).strftime("%Y-%m-%d")
    return sql.replace(spec, day), {keyword: day}


class _Meter:
    """Deltas of the engine's counters and of JAX's persistent-cache
    events since the last ``take()``."""

    def __init__(self):
        import jax

        from dask_sql_tpu.runtime import telemetry

        self._tel = telemetry
        self.xla_cache = {"hits": 0, "misses": 0}

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.xla_cache["hits"] += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.xla_cache["misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        self._last = self._read()

    def _read(self) -> dict:
        snap = self._tel.REGISTRY.snapshot()
        out = {k: v for k, v in snap["counters"].items()
               if k.startswith(_COUNTER_PREFIXES)}
        out["xla_cache_hits"] = self.xla_cache["hits"]
        out["xla_cache_misses"] = self.xla_cache["misses"]
        return out

    def take(self) -> dict:
        now = self._read()
        delta = {k: v - self._last.get(k, 0)
                 for k, v in now.items() if v != self._last.get(k, 0)}
        self._last = now
        return delta


def _device_phase(args) -> dict:
    t0 = time.perf_counter()
    import jax
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r});"
                 " this script proves nothing on another backend")
    # a rehearsal may borrow the first devices of a larger virtual mesh
    assert (len(devices) == args.chips if dev.platform == "tpu"
            else len(devices) >= args.chips), (
        f"--chips {args.chips} but JAX reports {len(devices)} devices")
    libtpu = "not installed"
    for dist in ("libtpu", "libtpu-nightly"):
        try:
            libtpu = importlib.metadata.version(dist)
            break
        except importlib.metadata.PackageNotFoundError:
            continue
    import dask_sql_tpu  # places the compile cache

    cache_dir = dask_sql_tpu.compile_cache_dir()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    stats = dev.memory_stats() or {}
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    _emit("device", t0, **info,
          memory_limit_bytes=stats.get("bytes_limit"),
          jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
          compile_cache_dir=cache_dir,
          compile_cache_from_env=bool(
              os.environ.get("JAX_COMPILATION_CACHE_DIR")),
          compile_cache_entries=entries, compile_cache_empty=entries == 0)
    return info


def _kernel_phase() -> None:
    """The static-domain group-by reduction on integer-valued f64 rows must
    be EXACT: on a TPU that is the Pallas limb kernel's contract (every
    12-bit limb through the MXU intact), elsewhere the scatter oracle's."""
    import jax
    import numpy as np

    from dask_sql_tpu.ops import pallas_kernels

    t0 = time.perf_counter()
    rng = np.random.RandomState(0)
    n, groups = 8 * pallas_kernels.BLOCK_EXACT, 5
    vals = rng.randint(-(1 << 40), 1 << 40, size=(2, n))
    codes = rng.randint(0, groups, size=n).astype(np.int32)
    mask = rng.rand(n) < 0.9
    want = np.array([[row[mask & (codes == g)].sum() for g in range(groups)]
                     for row in vals])
    got = jax.jit(lambda v, c, m: pallas_kernels.segmented_sums_dispatch(
        v, c, m, groups, row_classes=["int", "int"]))(
            vals.astype(np.float64), codes, mask)
    got = np.asarray(got)
    assert got.dtype == np.float64 and (got == want).all(), (got, want)
    _emit("kernel", t0, rows=n, groups=groups, exact=True)


def _table_bytes_by_device(context) -> dict:
    """Bytes of catalog columns resident on each device, from the arrays'
    own shards (exact on every backend, unlike allocator statistics)."""
    out: dict = {}
    for entry in context.schema[context.schema_name].tables.values():
        arrays = [entry.row_valid]
        for col in entry.table.columns:
            arrays += [col.data, col.mask]
        for arr in arrays:
            if arr is None:
                continue
            for shard in arr.addressable_shards:
                key = str(shard.device.id)
                out[key] = out.get(key, 0) + shard.data.nbytes
    return out


def _load_phase(args, mesh):
    import jax

    from benchmarks.tpch import generate_tpch
    from dask_sql_tpu import Context, native

    t0 = time.perf_counter()
    data = generate_tpch(args.sf, seed=args.seed)
    generate_s = time.perf_counter() - t0
    context = Context(mesh=mesh) if mesh is not None else Context()
    t1 = time.perf_counter()
    for name, frame in data.items():
        context.create_table(name, frame)
    by_device = _table_bytes_by_device(context)
    for arr in jax.live_arrays():
        arr.block_until_ready()
    load_s = time.perf_counter() - t1
    assert len(data) == 8
    n_devices = args.chips if mesh is not None else 1
    assert len(by_device) == n_devices, by_device
    if n_devices > 1:
        # a table that landed whole on device 0 is a failure
        assert max(by_device.values()) <= 1.05 * min(by_device.values()), (
            f"tables are not spread evenly over the mesh: {by_device}")
    in_use = {str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()[:n_devices]}
    _emit("load", t0, sf=args.sf, seed=args.seed,
          rows={name: len(frame) for name, frame in data.items()},
          generate_seconds=round(generate_s, 3),
          create_table_seconds=round(load_s, 3),
          table_bytes_by_device=by_device, device_bytes_in_use=in_use,
          parser="native" if native.available() else "python")
    return context, data


def _run_library(context, sql: str):
    """One ``Context.sql(...).to_pandas()``: (frame, seconds, tier, hit)."""
    from dask_sql_tpu.runtime import telemetry

    t0 = time.perf_counter()
    frame = context.sql(sql).to_pandas()
    seconds = time.perf_counter() - t0
    report = telemetry.last_report()
    return frame, seconds, report.tier, bool(report.cache.get("hit"))


def _library_phase(args, context, meter, on_tpu: bool, mesh) -> list:
    """One query at a time, as an operator warms a server: the first
    arrival (as shipped the eager tier answers it while its programs
    compile in the background), a wait until those compiles have landed,
    new literals until a compiled program serves, and one more literal,
    timed.  The compiles of different queries never overlap: each XLA
    compile holds gigabytes of host memory while it runs (CHANGES.md,
    PR 23).  Returns [(label, frame, qid, pandas keywords)] for the
    correctness phase."""
    from dask_sql_tpu.physical import compiled

    results = []
    for qid in QIDS:
        t0 = time.perf_counter()
        deadline = t0 + args.query_deadline
        meter.take()
        sql, kw = _variant(qid, 0)
        frame, first_s, tier, _ = _run_library(context, sql)
        results.append((f"q{qid} first arrival ({tier})", frame, qid, kw))
        arrival = meter.take()
        _emit("arrival", t0, query=qid, tier=tier, counters=arrival)
        tiers = [tier]
        compiled_s = background_s = None
        i = 0
        while tier not in ("compiled", "spmd"):
            while compiled.inflight_background_compiles():
                assert time.perf_counter() < deadline, (
                    f"Q{qid} was not served by a compiled program within "
                    f"{args.query_deadline}s (tiers seen: {tiers})")
                time.sleep(0.25)
            if background_s is None:
                # an upper bound (first seen landed) on trace + XLA
                # compile + one execution in the background thread,
                # counted from this query's arrival
                background_s = time.perf_counter() - t0
            i += 1
            assert i <= 4, (f"Q{qid} never left the eager tier "
                            f"(tiers seen: {tiers})")
            sql, kw = _variant(qid, i)
            frame, compiled_s, tier, hit = _run_library(context, sql)
            assert not hit
            results.append((f"q{qid} literal {i} ({tier})", frame, qid, kw))
            tiers.append(tier)
        until_compiled = meter.take()
        if on_tpu and qid == 1 and mesh is None:
            # Q1's static-domain reduction was traced through the compiled
            # Pallas fixed-point kernel, not reference_segmented_sums
            assert (arrival.get("pallas_kernel_traces", 0)
                    + until_compiled.get("pallas_kernel_traces", 0)) >= 1
        sql, kw = _variant(qid, i + 1)
        frame, warm_s, tier, hit = _run_library(context, sql)
        assert tier in ("compiled", "spmd") and not hit, (tier, hit)
        results.append((f"q{qid} warm ({tier})", frame, qid, kw))
        warm = meter.take()
        assert until_compiled.get("compile_errors", 0) == 0, until_compiled
        assert warm.get("compile_errors", 0) == 0, warm
        vehicle = {"spmd": "explicit shard_map stages",
                   "compiled": ("single-device program" if mesh is None
                                else "GSPMD whole-plan program")}[tier]
        _emit("library", t0, query=qid, tiers=tiers + [tier],
              vehicle=vehicle,
              first_arrival_seconds=round(first_s, 3),
              programs_ready_within_seconds=(None if background_s is None
                                          else round(background_s, 3)),
              first_compiled_seconds=(None if compiled_s is None
                                      else round(compiled_s, 3)),
              warm_execution_seconds=round(warm_s, 3),
              warm_was_recompiled=bool(warm.get("compiles")
                                       or warm.get("spmd_compiles")),
              until_compiled=until_compiled, warm=warm)
    return results


def _correctness_phase(data, results, surface: str) -> None:
    from benchmarks.pandas_tpch import PANDAS_QUERIES, assert_frames_match

    t0 = time.perf_counter()
    references: dict = {}
    for label, frame, qid, kw in results:
        key = (qid, tuple(sorted(kw.items())))
        if key not in references:
            references[key] = PANDAS_QUERIES[qid](data, **kw)
        assert len(frame) > 0, label
        assert_frames_match(frame, references[key], label)
    _emit("correctness", t0, surface=surface,
          compared=[label for label, *_ in results],
          reference="benchmarks/pandas_tpch.py",
          tolerance="rtol 1e-5, atol 1e-6 (tests/integration/"
                    "test_pandas_oracle.py)")


def _http_sql(base: str, sql: str):
    """POST /v1/statement and follow nextUri: (frame, seconds)."""
    import pandas as pd

    t0 = time.perf_counter()
    request = urllib.request.Request(f"{base}/v1/statement",
                                     data=sql.encode(), method="POST")
    columns, rows = None, []
    while True:
        with urllib.request.urlopen(request, timeout=60) as response:
            body = json.load(response)
        assert "error" not in body, body["error"]
        if body.get("columns"):
            columns = body["columns"]
        rows += body.get("data") or []
        if not body.get("nextUri"):
            break
        if body["stats"]["state"] in ("QUEUED", "RUNNING"):
            time.sleep(0.02)
        request = urllib.request.Request(body["nextUri"])
    seconds = time.perf_counter() - t0
    if columns is None:
        return None, seconds
    frame = pd.DataFrame(rows, columns=[c["name"] for c in columns])
    for c in columns:
        if c["type"].startswith(("date", "timestamp")):
            frame[c["name"]] = pd.to_datetime(frame[c["name"]])
    return frame, seconds


def _server_phase(context, meter) -> list:
    from benchmarks.tpch import QUERIES

    t0 = time.perf_counter()
    meter.take()
    server = context.run_server(host="127.0.0.1", port=0, blocking=False)
    base = f"http://127.0.0.1:{server.server_port}"
    results, seconds = [], {}
    for qid in QIDS:
        frame, seconds[f"q{qid}"] = _http_sql(base, QUERIES[qid])
        results.append((f"q{qid} over http", frame, qid, {}))
    prepared = QUERIES[6].replace("l_quantity < 24", "l_quantity < ?")
    assert prepared != QUERIES[6]
    _http_sql(base, f"PREPARE smoke_q6 AS {prepared}")
    frame, seconds["execute_q6"] = _http_sql(base, "EXECUTE smoke_q6 (31)")
    results.append(("q6 PREPARE/EXECUTE over http", frame, 6,
                    {"quantity": 31}))
    with urllib.request.urlopen(f"{base}/metrics", timeout=60) as response:
        metrics = response.read().decode()
    samples = {}
    for line in metrics.splitlines():
        if line and not line.startswith("#"):
            name, value = line.rsplit(" ", 1)
            samples[name] = float(value)
    assert samples["dsql_server_queries_total"] >= len(QIDS) + 2, samples
    context.stop_server()
    server.server_close()
    delta = meter.take()
    assert delta.get("compile_errors", 0) == 0, delta
    _emit("server", t0, port=server.server_port,
          request_seconds={k: round(v, 3) for k, v in seconds.items()},
          metrics_samples=len(samples), counters=delta)
    return results


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--sf", type=float, default=1.0,
                        help="TPC-H scale factor (default 1; smaller only "
                             "to rehearse)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: the mesh path and nothing else")
    parser.add_argument("--query-deadline", type=float, default=700.0,
                        help="seconds a query may take to reach a compiled "
                             "program")
    parser.add_argument("--allow-cpu", action="store_true",
                        help="rehearse every phase off the TPU; the last "
                             "line is then not the ok line")
    args = parser.parse_args(argv)

    device = _device_phase(args)
    on_tpu = device["platform"] == "tpu"
    mesh = None
    if args.chips > 1:
        from dask_sql_tpu.parallel.mesh import default_mesh
        mesh = default_mesh(args.chips)
    meter = _Meter()
    if mesh is None:
        _kernel_phase()
    context, data = _load_phase(args, mesh)
    _correctness_phase(
        data, _library_phase(args, context, meter, on_tpu, mesh), "library")
    if mesh is None:
        _correctness_phase(data, _server_phase(context, meter), "server")

    from dask_sql_tpu.physical import compiled
    while compiled.inflight_background_compiles():
        time.sleep(0.25)  # leave no compile thread mid-flight at exit
    if on_tpu:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    else:
        print(json.dumps({"rehearsal_passed": True, "device": device}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
