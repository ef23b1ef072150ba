"""From a profiler trace (``*.xplane.pb``) to the engine's own breakdown.

``trace.py`` beside this file reads what any JAX program leaves in a trace.
This one reads what ``dask_sql_tpu`` writes there itself (PERF.md, "Spans
and scopes"):

    dsql:<span>    host events, one per telemetry span; ``dsql:query`` is a
                   request and carries its ``seq``
    jit_dsql_*     the name of a compiled program, on a chip's
                   ``XLA Modules`` line
    dsql.<scope>   the plan node (or kernel inside one) an op was lowered
                   from, in the op's ``op_name``; the ops XLA hangs on a
                   program's parameters read ``dsql_input[i]`` instead,
                   counted as the scope ``dsql.input``

and reduces them to

    idle by span      every idle stretch of the chip between the first and
                      the last op the traced window recorded goes to the
                      ``dsql:`` span opened last among those open during
                      it (the innermost one, where one thread works), or
                      to "between requests"
    device by scope   an op's self time (its duration less the ops nested
                      in it) goes to the innermost scope of its op_name
    per request       the programs the chip ran inside a ``dsql:query``,
                      the wait before its first op and after its last

An op's ``op_name`` is a stat of the plane's event metadata, which
``jax.profiler.ProfileData`` (JAX 0.9.0) does not hand out; the file is
read here with a reader of the protobuf wire format, standard library
only (the messages: tsl/profiler/protobuf/xplane.proto).

    python3 -m chipbench.reduce.spans <xplane.pb>     prints the tables
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import statistics
import struct
import sys

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_START = "chipbench:window_start"
WINDOW_END = "chipbench:window_end"
SPAN = "dsql:"
REQUEST = "dsql:query"
SHAPE = "shape:"
SCOPE = "dsql."
INPUT = "dsql_input["
INPUT_SCOPE = "dsql.input"
NODE = "dsql.Logical"
JOIN_NODE = "dsql.LogicalJoin"
BETWEEN = "between requests"
NO_SCOPE = "(no scope)"
NO_SHAPE = "(no shape)"

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# --- the wire format --------------------------------------------------------

def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of one message; a length-delimited
    value is its bytes, a varint its unsigned value."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield number, kind, value


def _stat(buf: bytes, stat_names: dict):
    """(name, value) of one XStat."""
    name = value = None
    for number, kind, raw in _fields(buf):
        if number == 1:
            name = stat_names.get(raw)
        elif number == 2:
            value = struct.unpack("<d", raw)[0]
        elif number == 3:
            value = raw
        elif number == 4:
            value = raw - (1 << 64) if raw >= (1 << 63) else raw
        elif number in (5, 6):
            value = raw.decode("utf-8", "replace")
        elif number == 7:
            value = stat_names.get(raw)
    return name, value


def _plane(buf: bytes, want_line, want_event) -> dict:
    """{"name", "lines": {line name: [(event name, start_ns, end_ns,
    stats)]}}: the lines ``want_line`` names and the events ``want_event``
    names; ``stats`` holds the event's own stats and its metadata's."""
    name, lines, stat_names, metadata = "", [], {}, {}
    for number, _, raw in _fields(buf):
        if number == 2:
            name = raw.decode()
        elif number == 3:
            lines.append(raw)
        elif number == 4:
            entry = dict((n, v) for n, _, v in _fields(raw))
            metadata[entry[1]] = entry[2]
        elif number == 5:
            entry = dict((n, v) for n, _, v in _fields(raw))
            stat_names[entry[1]] = dict(
                (n, v) for n, _, v in _fields(entry[2])).get(2, b"").decode()
    wanted = {}
    for key, raw in metadata.items():
        event_name, stats = "", []
        for number, _, value in _fields(raw):
            if number == 2:
                event_name = value.decode("utf-8", "replace")
            elif number == 5:
                stats.append(value)
        if want_event(event_name):
            wanted[key] = (event_name,
                           dict(_stat(s, stat_names) for s in stats))
    out = {}
    for raw in lines:
        line_name, timestamp_ns, events, line_id = "", 0, [], 0
        for number, _, value in _fields(raw):
            if number == 1:
                line_id = value
            elif number == 2:
                line_name = value.decode("utf-8", "replace")
            elif number == 3:
                timestamp_ns = value
            elif number == 4:
                events.append(value)
        if not want_line(line_name):
            continue
        kept = []
        for event in events:
            key = offset_ps = duration_ps = 0
            stats = []
            for number, _, value in _fields(event):
                if number == 1:
                    key = value
                elif number == 2:
                    offset_ps = value
                elif number == 3:
                    duration_ps = value
                elif number == 4:
                    stats.append(value)
            if key not in wanted:
                continue
            event_name, of_metadata = wanted[key]
            start = timestamp_ns + offset_ps / 1000.0
            own = dict(_stat(s, stat_names) for s in stats)
            kept.append((event_name, start, start + duration_ps / 1000.0,
                         {**of_metadata, **own}))
        # a thread's line is named after the thread, and two may share it
        out.setdefault((line_name, line_id), []).extend(kept)
    return {"name": name, "lines": out}


def read_planes(path: str) -> list:
    """The device planes (modules and ops) and the host's plane (the
    engine's and the benchmark's annotations) of the trace at ``path``."""
    with open(path, "rb") as f:
        space = f.read()
    planes = []
    for number, _, raw in _fields(space):
        if number != 1:
            continue
        name = next((v.decode() for n, _, v in _fields(raw) if n == 2), "")
        if name.startswith(DEVICE_PLANE):
            planes.append(_plane(raw, (MODULES_LINE, OPS_LINE).__contains__,
                                 lambda _: True))
        elif name == HOST_PLANE:
            planes.append(_plane(
                raw, lambda _: True,
                lambda n: n.startswith((SPAN, SHAPE, "chipbench:"))))
    return planes


# --- the reduction ----------------------------------------------------------

def scope_path(op_name) -> tuple:
    """The ``dsql.`` scopes of an op_name, outermost first:
    ``jit(dsql_x)/dsql.LogicalJoin/dsql.join_build/sort:`` gives
    ``("dsql.LogicalJoin", "dsql.join_build")``."""
    if not op_name:
        return ()
    if op_name.startswith(INPUT):
        return (INPUT_SCOPE,)
    return tuple(part for part in op_name.rstrip(":").split("/")
                 if part.startswith(SCOPE))


def plan_node(path: tuple):
    """The plan node an op was lowered from: the innermost ``dsql.Logical*``
    of its scope path (a kernel's scope inside a node belongs to the node;
    a node's inputs, lowered inside its scope, do not)."""
    return next((part for part in reversed(path) if part.startswith(NODE)),
                None)


def self_times(ops: list) -> list:
    """[[start_ns, end_ns, self_ns, scope path]] of ``ops`` [(start, end,
    path)], by start: an op's self time is its duration less that of the
    ops nested in it."""
    out, stack = [], []
    for start, end, path in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= min(end, stack[-1][0]) - start
        stack.append((end, len(out)))
        out.append([start, end, end - start, path])
    return out


def _union(intervals: list, lo: float, hi: float) -> list:
    merged = []
    for start, end in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def idle_by_span(gaps: list, spans: list) -> dict:
    """{label: ns}: each stretch of the idle ``gaps`` goes to exactly one
    of ``spans`` [(label, start, end)], the one opened last among those
    open during it, or to ``BETWEEN`` where none is open."""
    edges = []
    for start, end in gaps:
        edges += [(start, 0, None), (end, 1, None)]
    for k, (_, start, end) in enumerate(spans):
        if end > start:
            edges += [(start, 2, k), (end, 3, k)]
    # at one instant: close spans, end the gap, open spans, begin the gap
    order = {3: 0, 1: 1, 2: 2, 0: 3}
    out, open_now, idle, last = {}, set(), False, None
    for when, kind, k in sorted(edges, key=lambda e: (e[0], order[e[1]])):
        if idle and when > last:
            label = BETWEEN
            if open_now:
                label = spans[max(open_now,
                                  key=lambda j: (spans[j][1], j))][0]
            out[label] = out.get(label, 0.0) + when - last
        last = when
        if kind < 2:
            idle = kind == 0
        elif kind == 2:
            open_now.add(k)
        else:
            open_now.discard(k)
    return out


def _median(values):
    return statistics.median(values) if values else None


def _add(table: dict, key, value) -> None:
    table[key] = table.get(key, 0.0) + value


def reduce_planes(planes: list) -> dict:
    """The reduction, on what ``read_planes`` returns.  Without a
    ``dsql:query`` in the trace (a program from before the engine wrote
    any) there are no requests and every per-request number is None."""
    host, modules, ops = [], [], []
    for plane in planes:
        for (line_name, _), events in plane["lines"].items():
            if plane["name"] == HOST_PLANE:
                host += events
            elif line_name == MODULES_LINE:
                modules += [(start, name.split("(")[0])
                            for name, start, _, _ in events]
            elif line_name == OPS_LINE:
                ops += [(start, end, scope_path(stats.get("tf_op")))
                        for _, start, end, stats in events]
    if not ops:
        raise SystemExit("chipbench: the trace holds no device operation")
    marks = {name: start for name, start, _, _ in host}
    # without the benchmark's annotations (any ``jax.profiler`` session
    # of the engine): everything the trace holds
    lo = marks.get(WINDOW_START, min([o[0] for o in ops]
                                     + [h[1] for h in host]))
    hi = marks.get(WINDOW_END, max([o[1] for o in ops]
                                   + [h[2] for h in host]))
    modules.sort()
    module_starts = [m[0] for m in modules]
    own = self_times(ops)
    own_starts = [o[0] for o in own]
    shapes = sorted((start, end, name[len(SHAPE):])
                    for name, start, end, _ in host if name.startswith(SHAPE))

    def shape_at(when: float) -> str:
        """The benchmark's ``shape:`` annotation open at ``when`` (one
        caller at a time: at most one is)."""
        i = bisect.bisect_right(shapes, (when, float("inf"), "")) - 1
        return shapes[i][2] if i >= 0 and when < shapes[i][1] else NO_SHAPE

    requests = []
    for name, start, end, stats in host:
        if name != REQUEST or start < lo or end > hi:
            continue
        mine = own[bisect.bisect_left(own_starts, start):
                   bisect.bisect_left(own_starts, end)]
        by_scope = {}
        for _, _, ns, path in mine:
            _add(by_scope, path[-1] if path else NO_SCOPE, ns)
        requests.append({
            "seq": stats.get("seq"), "shape": shape_at(start),
            "start_ns": start, "end_ns": end,
            "programs": [m for _, m in modules[
                bisect.bisect_left(module_starts, start):
                bisect.bisect_left(module_starts, end)]],
            "idle_pre_ns": mine[0][0] - start if mine else None,
            "idle_post_ns": (end - max(o[1] for o in mine)
                             if mine else None),
            "device_ns_by_scope": by_scope,
            "join_ns": sum(ns for _, _, ns, path in mine
                           if plan_node(path) == JOIN_NODE),
        })
    requests.sort(key=lambda r: r["start_ns"])

    busy = _union([(s, e) for s, e, _ in ops], lo, hi)
    # idle is told only where the device's line says something: an op that
    # was running when the profiler started, or still is when it stops, is
    # not in the trace (under two streams that is up to one 0.5 s program
    # at either end, which would read as idle)
    edges = [t for piece in busy for t in piece][1:-1]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    idle = idle_by_span(gaps, [
        ((shape_at(start), name[len(SPAN):]), start, end)
        for name, start, end, _ in host if name.startswith(SPAN)])
    idle_by_shape, device_by_shape, scoped, total = {}, {}, 0.0, 0.0
    for label, ns in idle.items():
        shape, span = (None, BETWEEN) if label == BETWEEN else label
        _add(idle_by_shape.setdefault(shape, {}), span, ns / 1e9)
    for start, _, ns, path in own:
        if lo <= start < hi:
            _add(device_by_shape.setdefault(shape_at(start), {}),
                 path[-1] if path else NO_SCOPE, ns / 1e9)
            total += ns
            scoped += ns if path else 0.0

    def median_ms(key):
        values = [r[key] for r in requests if r[key]]
        return statistics.median(values) / 1e6 if values else None

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        # first recorded op's start to the last one's end, inside the window
        "covered_s": (busy[-1][1] - busy[0][0]) / 1e9,
        # {shape (None: outside every request): {span: s}}
        "idle_s_by_span": idle_by_shape,
        "device_s_by_scope": device_by_shape,     # {shape: {scope: s}}
        "scoped_share": scoped / total if total else None,
        "modules": sorted({m for _, m in modules}),
        "requests": requests,
        "device_programs_per_query": _median(
            [len(r["programs"]) for r in requests]),
        "idle_pre_dispatch_ms": median_ms("idle_pre_ns"),
        "idle_post_device_ms": median_ms("idle_post_ns"),
        # over the requests that ran a join (Q12's and Q14's): the ops of
        # the join node itself, not of the inputs lowered inside its scope
        "join_device_ms": median_ms("join_ns"),
    }


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int) -> dict:
    return reduce_planes(read_planes(path))


def reduce(path: str) -> dict:
    """The reduction of the trace at ``path`` (kept for the file as it is:
    every trace metric of a run reads the same one)."""
    return _reduce_file(path, os.stat(path).st_mtime_ns)


def of_run(run: dict):
    """The reduction of the trace a ``--trace 1`` run of
    ``chipbench/run.py`` left under ``.chipbench_trace``, where
    ``Tracer.path()`` finds it; None for a run without a trace (a
    rehearsal, ``--trace 0``)."""
    if run.get("trace") is None:
        return None
    found = sorted(glob.glob(os.path.join(
        _ROOT, ".chipbench_trace", "plugins", "profile", "*", "*.xplane.pb")))
    return reduce(found[-1]) if found else None


def metric(run: dict, name: str):
    """One number of the run's reduction, or None."""
    reduced = of_run(run)
    return None if reduced is None else reduced[name]


def phase_median(run: dict, phase: str, rehearsed: bool = False):
    """Median per request of one of the engine's phases, as the records of
    the window carry them (``QueryReport.phases``, the wire's
    ``phaseMillis``); None where no record has the phase.  Unless
    ``rehearsed``, None for a run without a trace as well: the embedded
    rehearsal's result line is held to the metrics it had when its test
    was written, the served one's to every host-side metric of its cell
    (``test_chipbench_rehearsal*.py``, which no later PR may edit)."""
    if not rehearsed and run.get("trace") is None:
        return None
    return _median([r["phases"][phase] for r in run["window"]["records"]
                    if phase in r["phases"]])


# --- the tables -------------------------------------------------------------

def _table(title: str, rows: dict, unit: str, scale: float = 1.0) -> list:
    total = sum(rows.values()) or 1.0
    return [f"  {title}"] + [
        f"    {label:<28} {scale * value:>12.6f} {unit}"
        f" {100 * value / total:6.1f} %"
        for label, value in sorted(rows.items(), key=lambda r: -r[1])]


def render(reduced: dict) -> str:
    """Per shape, the tables PERF.md section 5 is written from."""
    lines = [f"window {reduced['window_s']:.6f} s, first to last device op "
             f"{reduced['covered_s']:.6f} s, device busy "
             f"{reduced['busy_s']:.6f} s, under a dsql scope "
             f"{100 * (reduced['scoped_share'] or 0):.2f} % of device time",
             "modules: " + ", ".join(reduced["modules"])]
    lines += _table("device idle outside every request",
                    reduced["idle_s_by_span"].get(None, {}), "s")
    by_shape = {}
    for request in reduced["requests"]:
        by_shape.setdefault(request["shape"], []).append(request)
    for shape in sorted(set(by_shape) | set(reduced["device_s_by_scope"])):
        requests = by_shape.get(shape, [])

        def ms(values):
            values = [v / 1e6 for v in values if v is not None]
            return statistics.median(values) if values else float("nan")

        lines.append(
            f"shape {shape}: {len(requests)} whole requests in the window")
        if requests:
            wall = ms(r["end_ns"] - r["start_ns"] for r in requests)
            pre = ms(r["idle_pre_ns"] for r in requests)
            post = ms(r["idle_post_ns"] for r in requests)
            lines.append(
                f"  median dsql:query {wall:.3f} ms; first device op "
                f"{pre:.3f} ms after it opens; it closes {post:.3f} ms "
                f"after the last op's end")
            programs = {}
            for r in requests:
                for module in r["programs"]:
                    _add(programs, module, 1.0 / len(requests))
            lines.append("  programs per request: " + ", ".join(
                f"{m} {n:.2f}" for m, n in sorted(programs.items())))
        lines += _table("device idle by innermost dsql: span (window)",
                        reduced["idle_s_by_span"].get(shape, {}), "s")
        lines += _table("device self time by innermost dsql. scope (window)",
                        reduced["device_s_by_scope"].get(shape, {}), "s")
    return "\n".join(lines)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(__doc__.split("\n\n")[-1].strip())
    print(render(reduce(sys.argv[1])))
