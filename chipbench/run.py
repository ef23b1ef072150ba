#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json`` on the attached chip.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip for the whole run.  It starts one child, and
only on the served surface: the HTTP clients (``chipbench/client.py``),
which import nothing but the standard library.  The phases:

    device    jax.devices(); the platform has to be a TPU whose kind
              ``chipbench/peaks.json`` lists, and as many chips as the cell asks
    generate  the tables from ``--seed`` (``chipbench/data/<generator>.py``)
    load      ``Context.create_table`` x 8, resident on the device
    ready     every shape of the mix, one at a time and in the mix's order:
              first arrival, then new parameter sets until a compiled
              program has served ``1 + warm_extra`` of them
    window    ``--seconds`` of the mix's traffic, timed from the client's
              side; with ``--trace 1`` a few seconds of it under the profiler
    compare   answers of set-up and a seeded sample of the window's against
              each shape's pandas reference (``chipbench/compare.py``)

Everything a cell is made of is found by the name ``BENCHMARK.json`` gives
it: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``data/<generator>.py``, ``shapes/<shape>.py``, ``metrics/<metric>.py``.  A name with no file is an
error.  The last line of stdout is the result the driver reads.
"""
from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:  # ``python3 chipbench/run.py`` works as well
    sys.path.insert(0, _ROOT)

from chipbench import client, compare, roofline, traffic  # noqa: E402

_COMPILED_TIERS = ("compiled", "spmd")
_COUNTER_PREFIXES = ("compile", "recompiles", "fallbacks", "hits",
                     "served_eager", "background_compile", "program_store_",
                     "result_cache_", "param_plan", "pallas_", "spmd_",
                     "queries", "query_errors")


def say(phase: str, **fields) -> None:
    """One JSON line per phase, before the result line."""
    print(json.dumps({"phase": phase, **fields}), flush=True)


def load_by_path(kind: str, name: str):
    """The module ``chipbench/<kind>/<name>.py``; no file is an error."""
    path = os.path.join(_HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"chipbench: {name!r} has no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str) -> dict:
    """The cell with everything its names resolve to."""
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json (it has {sorted(cells)})")
    cell = cells[workload]
    entry = next((c for c in bench["configs"] if c["name"] == cell["config"]),
                 None)
    if entry is None:
        raise SystemExit(f"chipbench: no config {cell['config']!r}")
    with open(os.path.join(_ROOT, entry["file"])) as f:
        config = json.load(f)
    mix = traffic.load_mix(cell["traffic"])

    def in_cell(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell, "config": config, "mix": mix,
        "shapes": {name: load_by_path("shapes", name)
                   for name in mix["shapes"]},
        "end_to_end": {m["name"]: (m, load_by_path("metrics", m["name"]))
                       for m in bench["end_to_end"] if in_cell(m)},
        "per_layer": {m["name"]: (m, load_by_path("metrics", m["name"]))
                      for m in bench["per_layer"] if in_cell(m)},
    }


class Meter:
    """Deltas of the engine's counters and of JAX's persistent-cache
    events since the last ``take()`` (after ``chip_smoke.py::_Meter``)."""

    def __init__(self):
        import jax

        from dask_sql_tpu.runtime import telemetry

        self._registry = telemetry.REGISTRY
        self._xla = {"xla_cache_hits": 0, "xla_cache_misses": 0}

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self._xla["xla_cache_hits"] += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self._xla["xla_cache_misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        self._first = self._last = self._read()

    def _read(self) -> dict:
        out = {k: v for k, v in self._registry.snapshot()["counters"].items()
               if k.startswith(_COUNTER_PREFIXES)}
        out.update(self._xla)
        return out

    def take(self) -> dict:
        now = self._read()
        delta = self._delta(now, self._last)
        self._last = now
        return delta

    def since_start(self) -> dict:
        return self._delta(self._read(), self._first)

    @staticmethod
    def _delta(now: dict, then: dict) -> dict:
        return {k: v - then.get(k, 0)
                for k, v in now.items() if v != then.get(k, 0)}


def device_phase(args, chips: int) -> dict:
    """Refuses anything but the chips the cell asks for (a rehearsal takes
    what JAX finds, and says so in every line it prints)."""
    t0 = time.perf_counter()
    import jax

    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    if args.allow_cpu:
        if dev.platform == "tpu":
            raise SystemExit("chipbench: --allow-cpu is the rehearsal; it "
                             "does not run on a TPU")
        peaks = None
    else:
        if dev.platform != "tpu":
            raise SystemExit(f"chipbench: JAX found no TPU (platform "
                             f"{dev.platform!r}); nothing measured elsewhere "
                             "is a result")
        if len(devices) != chips:
            raise SystemExit(f"chipbench: the cell asks for {chips} chip(s), "
                             f"JAX reports {len(devices)}")
        peaks = roofline.peaks_for(dev.device_kind)
    import dask_sql_tpu  # places the compile cache

    cache_dir = dask_sql_tpu.compile_cache_dir()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say("device", seconds=round(time.perf_counter() - t0, 3), **info,
        jax=jax.__version__, compile_cache_dir=cache_dir,
        compile_cache_entries=entries)
    return {"info": info, "peaks": peaks, "cache_entries": entries,
            "devices": devices[:chips]}


def load_phase(config: dict, sf: float, seed: int, setup: dict):
    import jax

    from dask_sql_tpu import Context

    t0 = time.perf_counter()
    frames = load_by_path("data", config["generator"]).generate(sf, seed)
    setup["generate_s"] = time.perf_counter() - t0
    context = Context()
    t1 = time.perf_counter()
    for name in config["tables"]:
        context.create_table(name, frames[name])
    for arr in jax.live_arrays():
        arr.block_until_ready()
    setup["create_table_s"] = time.perf_counter() - t1
    catalog = roofline.catalog_columns(context)
    resident = sum(rows * itemsize for columns in catalog.values()
                   for rows, itemsize in columns.values())
    say("load", scale_factor=sf, seed=seed,
        rows={name: len(frames[name]) for name in config["tables"]},
        generate_s=round(setup["generate_s"], 3),
        create_table_s=round(setup["create_table_s"], 3),
        resident_column_bytes=resident)
    return context, frames, catalog


class Embedded:
    """``Context.sql(text, return_futures=False)``: the frame is on the
    host when the clock stops."""

    name = "embedded"

    def __init__(self, context):
        self._context = context

    def execute(self, request: dict, deadline_s: float) -> dict:
        import jax
        import pandas as pd

        from dask_sql_tpu.runtime import telemetry

        frame, error = None, None
        t0 = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(f"shape:{request['shape']}"):
            try:
                frame = self._context.sql(request["sql"],
                                          return_futures=False)
            except Exception as e:  # a failed query is counted, not raised
                error = f"{type(e).__name__}: {e}"[:500]
        t1 = time.monotonic_ns()
        report = telemetry.last_report()
        if error is None and not isinstance(frame, pd.DataFrame):
            error = f"no frame came back ({type(frame).__name__})"
        return {**request, "client": 0, "t0_ns": t0, "t1_ns": t1,
                "latency_ms": (t1 - t0) / 1e6, "late_ms": 0.0,
                "frame": frame, "error": error,
                "engine_wall_ms": report.wall_ms,
                "tier": report.tier, "phases": dict(report.phases),
                "cache_hit": bool(report.cache.get("hit"))}

    def close(self):
        pass


def _served_record(request: dict, answer: dict) -> dict:
    """A client's record of one statement, with the engine's own report of
    it as the wire's ``stats`` object carries it."""
    import pandas as pd

    stats = answer.get("stats") or {}
    frame = None
    if answer["error"] is None and answer["columns"] is not None:
        frame = pd.DataFrame(answer["rows"], columns=answer["columns"])
    error = answer["error"]
    if error is None and frame is None:
        error = "the statement finished without columns"
    return {**request, "frame": frame, "error": error,
            "engine_wall_ms": stats.get("wallTimeMillis"),
            "tier": stats.get("tier"),
            "phases": dict(stats.get("phaseMillis") or {}),
            "cache_hit": bool(stats.get("cacheHit"))}


class Served:
    """The Presto wire protocol against ``Context.run_server()`` in this
    process.  Set-up's statements go out from this thread; the window's
    from the clients' own process."""

    name = "served"

    def __init__(self, context, mix: dict):
        self._context = context
        self._mix = mix
        self._server = context.run_server(host="127.0.0.1", port=0,
                                          blocking=False)
        self.base = f"http://127.0.0.1:{self._server.server_port}"
        self._child = None

    def execute(self, request: dict, deadline_s: float) -> dict:
        t0 = time.monotonic_ns()
        answer = client.http_sql(self.base, request["sql"],
                                 self._mix["poll_interval_ms"] / 1e3,
                                 deadline_s)
        t1 = time.monotonic_ns()
        return {**_served_record(request, answer), "client": 0,
                "t0_ns": t0, "t1_ns": t1, "latency_ms": (t1 - t0) / 1e6,
                "late_ms": 0.0}

    def start_clients(self, requests: list) -> None:
        """The child, with its requests, waiting for the start."""
        self._requests = {r["id"]: r for rs in requests for r in rs}
        job = {"base": self.base, "loop": self._mix["loop"],
               "clients": int(self._mix["clients"]),
               "poll_interval_s": self._mix["poll_interval_ms"] / 1e3,
               "deadline_s": self._mix["deadline_s"],
               "requests": [[{k: r[k] for k in ("id", "shape", "sql", "due_s")
                              if k in r} for r in rs] for rs in requests]}
        self._child = subprocess.Popen(
            [sys.executable, os.path.join(_HERE, "client.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self._child.stdin.write(json.dumps(job) + "\n")
        self._child.stdin.flush()
        if not json.loads(self._child.stdout.readline()).get("ready"):
            raise SystemExit("chipbench: the clients did not come up")

    def go(self, start_ns: int, seconds: float) -> None:
        self._child.stdin.write(
            json.dumps({"start_ns": start_ns, "seconds": seconds}) + "\n")
        self._child.stdin.flush()

    def collect(self) -> list:
        line = self._child.stdout.readline()
        self._child.stdin.close()
        if self._child.wait(timeout=60) != 0 or not line:
            raise SystemExit("chipbench: the clients' process failed")
        self._child = None
        return [{**_served_record(self._requests[r["id"]], r),
                 **{k: r[k] for k in ("client", "t0_ns", "t1_ns",
                                      "latency_ms", "late_ms")}}
                for r in json.loads(line)["records"]]

    def close(self):
        if self._child is not None:
            self._child.kill()
            self._child.wait()
        self._context.stop_server()
        self._server.server_close()


def _wait_for_compiles(give_up: float) -> None:
    """Until no compile is in flight in the background, or ``give_up``."""
    from dask_sql_tpu.physical import compiled

    while (compiled.inflight_background_compiles()
           and time.perf_counter() < give_up):
        time.sleep(0.25)


def ready_phase(surface, loaded: dict, draws, meter: Meter, setup: dict):
    """Warm the mix's shapes one at a time (two XLA compiles of SF1 stage
    programs at once are what stopped PR 23's first submission).  Returns
    the records of every statement sent."""
    mix = loaded["mix"]
    records = []
    t_first = time.perf_counter()
    for name in mix["shapes"]:
        t0 = time.perf_counter()
        give_up = t0 + mix["ready_deadline_s"]
        meter.take()
        tiers, served_compiled = [], 0
        while served_compiled < 1 + int(mix["warm_extra"]):
            if tiers and tiers[-1] not in _COMPILED_TIERS:
                _wait_for_compiles(give_up)
            record = surface.execute(
                draws.fresh(name), max(give_up - time.perf_counter(), 1.0))
            record["phase"] = "ready"
            records.append(record)
            tiers.append(record["tier"] if record["error"] is None
                         else "error")
            if record["tier"] in _COMPILED_TIERS and not record["cache_hit"]:
                served_compiled += 1
            elif time.perf_counter() > give_up or len(tiers) > 3:
                # an arrival off the compiled tier costs up to minutes at
                # SF1: three of them, or the deadline, and the shape failed
                record["error"] = record["error"] or (
                    f"{name} was not served by a compiled program within "
                    f"{mix['ready_deadline_s']} s (tiers {tiers})")
                break
        say("ready", shape=name, seconds=round(time.perf_counter() - t0, 3),
            tiers=tiers, first_arrival_ms=round(records[-len(tiers)]
                                                ["latency_ms"], 1),
            last_ms=round(records[-1]["latency_ms"], 1),
            counters=meter.take())
    _wait_for_compiles(time.perf_counter() + mix["ready_deadline_s"])
    setup["ready_s"] = time.perf_counter() - t_first
    return records


class Tracer:
    """A few seconds of the window under ``jax.profiler``, bracketed by two
    annotations whose CLOCK_MONOTONIC times are kept, so that the clients'
    clock can be laid beside the trace's."""

    def __init__(self, directory: str, after_s: float, seconds: float):
        self.directory = directory
        self._after_ns = int(after_s * 1e9)
        self._length_ns = int(seconds * 1e9)
        self.state = "off" if seconds <= 0 else "waiting"
        self.anchor_ns = None

    def tick(self, since_start_ns: int) -> None:
        import jax

        if self.state == "waiting" and since_start_ns >= self._after_ns:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.directory, profiler_options=options)
            self.anchor_ns = time.monotonic_ns()
            with jax.profiler.TraceAnnotation("chipbench:window_start"):
                pass
            self._started = since_start_ns
            self.state = "on"
        elif (self.state == "on"
              and since_start_ns >= self._started + self._length_ns):
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "on":
            with jax.profiler.TraceAnnotation("chipbench:window_end"):
                pass
            jax.profiler.stop_trace()
            self.state = "done"

    def path(self):
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found and self.state == "done" else None


def window_phase(surface, loaded, draws, args, tracer: Tracer) -> dict:
    """``--seconds`` of the mix.  Requests begun before the window closes
    are finished and counted among the latencies."""
    mix = loaded["mix"]
    requests = traffic.requests_for_window(mix, draws, args.seed)
    if surface.name == "served":
        # drawn before the window opens, in turns: the clients' early
        # requests lie side by side in each shape's permutation (a
        # rehearsal's tables are a hundredth, and its clients that faster)
        per_client = int(args.seconds * mix["max_per_client_per_s"]
                         * (25 if args.allow_cpu else 1)) + 1
        turns = [[next(rs) for rs in requests] for _ in range(per_client)]
        requests = [list(column) for column in zip(*turns)]
        surface.start_clients(requests)
        start_ns = time.monotonic_ns() + 100_000_000
        surface.go(start_ns, args.seconds)
        end_ns = start_ns + int(args.seconds * 1e9)
        while (tracer.state in ("waiting", "on")
               and time.monotonic_ns() < end_ns):
            tracer.tick(time.monotonic_ns() - start_ns)
            time.sleep(0.01)
        tracer.stop()
        records = surface.collect()  # returns when the clients are done
        sent = {r["id"] for r in records}
        if any(rs[-1]["id"] in sent for rs in requests):
            raise SystemExit("chipbench: the clients used up their requests; "
                             "raise max_per_client_per_s in the traffic file")
    else:
        if mix["loop"] != "closed" or int(mix["clients"]) != 1:
            raise SystemExit("chipbench: the embedded surface is one caller "
                             "in a closed loop")
        records = []
        start_ns = time.monotonic_ns()
        end_ns = start_ns + int(args.seconds * 1e9)
        for request in requests[0]:
            now = time.monotonic_ns()
            if now >= end_ns:
                break
            tracer.tick(now - start_ns)
            records.append(surface.execute(request, mix["deadline_s"]))
        tracer.stop()
    for record in records:
        record["phase"] = "window"
        if record["error"] is None \
                and record["latency_ms"] > mix["deadline_s"] * 1e3:
            record["error"] = f"over the deadline of {mix['deadline_s']} s"
    return {"records": records, "start_ns": start_ns, "end_ns": end_ns,
            "seconds": float(args.seconds), "loop": mix["loop"],
            "cycle": sum(int(w) for w in mix["shapes"].values())}


def compare_phase(loaded, frames, ready_records, window, seed: int):
    """Every answer of set-up, and of the window a sample drawn from the
    seed (with each shape's last): against the reference, outside every
    clock."""
    import numpy as np

    t0 = time.perf_counter()
    rng = np.random.RandomState((seed + 15485863) % (1 << 32))
    chosen = list(ready_records)
    for name in loaded["mix"]["shapes"]:
        of_shape = [r for r in window["records"] if r["shape"] == name]
        keep = min(int(loaded["mix"]["compare_per_shape"]), len(of_shape))
        if keep:
            picks = set(rng.choice(len(of_shape) - 1, keep - 1, replace=False)
                        .tolist()) if keep > 1 else set()
            chosen += [of_shape[i] for i in sorted(picks | {len(of_shape) - 1})]
    everything = ready_records + window["records"]
    errors = sum(1 for r in everything if r["error"] is not None)
    gap, mismatched, wrong_answers = 0.0, 0, 0
    references = {}
    for record in chosen:
        if record["error"] is not None:
            continue
        key = (record["shape"], tuple(sorted(record["params"].items())))
        if key not in references:
            references[key] = loaded["shapes"][record["shape"]].reference(
                frames, **record["params"])
        g, m = compare.compare_frames(record["frame"], references[key])
        if m or g > compare.LIMITS["max_rel_gap"]:
            say("mismatch", shape=record["shape"], params=record["params"],
                tier=record["tier"], rel_gap=g, mismatched_cells=m,
                got=record["frame"].to_dict("list"),
                want=references[key].to_dict("list"))
            wrong_answers += 1
        gap, mismatched = max(gap, g), mismatched + m
    correct, lines = compare.verdict(gap, mismatched, errors)
    for record in everything:
        if record["error"] is not None:
            say("error", shape=record["shape"], params=record["params"],
                step=record["phase"], error=record["error"])
    say("compare", seconds=round(time.perf_counter() - t0, 3),
        compared=sum(1 for r in chosen if r["error"] is None),
        of_window=len(chosen) - len(ready_records),
        numbers=[{"name": n, "value": v, "limit": lim} for n, v, lim in lines],
        correct=correct)
    return correct, errors + wrong_answers


def read_metrics(readers: dict, run: dict) -> dict:
    """{name: {"value", "unit"}}; a reader that finds nothing to read
    returns None and its metric is left out."""
    out = {}
    for name, (entry, module) in readers.items():
        value = module.read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": entry["unit"]}
    return out


def main(argv=None) -> int:
    t_process = time.perf_counter()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--allow-cpu", action="store_true",
                        help="rehearse every phase at the config's "
                             "rehearsal_scale_factor on whatever backend JAX "
                             "finds; never on a TPU, never a result")
    args = parser.parse_args(argv)

    loaded = load_cell(args.workload)
    config, mix = loaded["config"], loaded["mix"]
    for key, value in config["environment"].items():
        os.environ[key] = str(value)
    if config["surface"] not in ("embedded", "served"):
        raise SystemExit(f"chipbench: unknown surface {config['surface']!r}")
    say("cell", workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearsal=args.allow_cpu, config=config["name"],
        surface=config["surface"], traffic=loaded["cell"]["traffic"],
        environment=config["environment"])

    device = device_phase(args, int(loaded["cell"]["chips"]))
    setup = {}
    meter = Meter()
    sf = (config["rehearsal_scale_factor"] if args.allow_cpu
          else config["scale_factor"])
    context, frames, catalog = load_phase(config, sf, args.seed, setup)
    draws = traffic.Draws(loaded["shapes"], args.seed)
    surface = (Served(context, mix) if config["surface"] == "served"
               else Embedded(context))
    trace_dir = os.path.join(_ROOT, ".chipbench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = Tracer(trace_dir, mix["trace_after_s"],
                    mix["trace_seconds"] if args.trace else 0)
    try:
        ready_records = ready_phase(surface, loaded, draws, meter, setup)
        setup["counters"] = meter.since_start()
        meter.take()
        setup["setup_s"] = time.perf_counter() - t_process
        window = window_phase(surface, loaded, draws, args, tracer)
        _wait_for_compiles(time.perf_counter() + mix["deadline_s"])
        window["counters"] = meter.take()
    finally:
        surface.close()
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in device["devices"]), default=0)
    texts = [r["sql"] for r in ready_records + window["records"]]
    say("window", executions=len(window["records"]),
        by_shape={name: sum(1 for r in window["records"]
                            if r["shape"] == name) for name in mix["shapes"]},
        late_ms_max=max((r["late_ms"] for r in window["records"]), default=0),
        slowest=[{"shape": r["shape"], "latency_ms": round(r["latency_ms"], 1),
                  "at_s": round((r["t0_ns"] - window["start_ns"]) / 1e9, 2),
                  "phases": {k: round(v, 1) for k, v in r["phases"].items()}}
                 for r in sorted(window["records"],
                                 key=lambda r: -r["latency_ms"])[:3]],
        texts_sent_twice=len(texts) - len(set(texts)), counters=window["counters"],
        setup_s=round(setup["setup_s"], 3), ready_s=round(setup["ready_s"], 3))

    correct, failed = compare_phase(loaded, frames, ready_records, window,
                                    args.seed)
    run = {"surface": config["surface"], "setup": setup, "window": window,
           "ready_records": ready_records, "peaks": device["peaks"],
           "scan_bytes": {name: roofline.scan_bytes(shape.SCAN_COLUMNS, catalog)
                          for name, shape in loaded["shapes"].items()},
           "trace": None}
    device_line = dict(device["info"], memory_peak_bytes=int(peak))
    result = {"correct": bool(correct),
              "attempted": len(ready_records) + len(window["records"]),
              "failed": int(failed)}
    if args.trace:
        from chipbench.reduce import trace as reduce_trace

        path = tracer.path()
        if path is not None and not args.allow_cpu:
            run["trace"] = reduce_trace.reduce(
                path, tracer.anchor_ns,
                [(r["shape"], r["t0_ns"], r["t1_ns"])
                 for r in window["records"]])
            device_line["busy_s"] = run["trace"]["busy_s"]
            device_line["window_s"] = run["trace"]["window_s"]
            result["breakdown"] = run["trace"]["breakdown"]
            for name, nbytes in run["scan_bytes"].items():
                say("roofline", shape=name, scan_bytes=nbytes,
                    least_seconds_by_memory=nbytes
                    / device["peaks"]["hbm_bytes_per_s"],
                    bound="memory: the shapes do a few operations for each "
                          "byte their scans read")
        result["metrics"] = read_metrics(loaded["per_layer"], run)
    else:
        result["metrics"] = read_metrics(loaded["end_to_end"], run)
    result["device"] = device_line
    if args.allow_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
