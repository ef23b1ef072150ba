"""From a profiler trace (``*.xplane.pb``) to the numbers the metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a trace of
this engine on a v5e holds (looked at by hand, PERF.md "Where the time
goes"): one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops``
has one event per operation the chip ran, with children nested inside a
``while``; and the host's plane, ``/host:CPU``, whose lines are threads and
hold the benchmark's own ``TraceAnnotation`` events.  Every time in a trace
is in nanoseconds on the trace's own clock.

    busy      the union of the ``XLA Ops`` intervals of a chip, cut to the
              traced window, averaged over the chips
    idle      the window less busy; each stretch of it goes to the requests
              that were open during it, or to "between requests"
    per op    self time by the name the trace gives: an event's duration
              less that of the events nested in it
    per shape the busy time inside each request of a shape that lies whole
              inside the window (one client at a time: requests that
              overlap would each be given the other's work)
"""
from __future__ import annotations

import bisect
import re
import statistics

DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
WINDOW_START = "chipbench:window_start"
WINDOW_END = "chipbench:window_end"
BETWEEN = "between requests"


def op_name(event_name: str) -> str:
    """The trace names an op by its whole HLO line; keep what names it:
    ``%fusion.16``, and for a custom call its target."""
    name = event_name.split(" = ", 1)[0].strip()[:80]
    target = re.search(r'custom_call_target="([^"]+)"', event_name)
    return f"{name}[{target.group(1)[:40]}]" if target else name


def device_ops(data) -> dict:
    """{plane name: [(op name, start_ns, end_ns)]} of every chip."""
    out = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [
                    (op_name(e.name), float(e.start_ns),
                     float(e.start_ns) + float(e.duration_ns))
                    for e in line.events]
    return out


def annotations(data, prefixes=("shape:", "chipbench:")) -> list:
    """[(name, start_ns, end_ns)] of the benchmark's own annotations."""
    out = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefixes):
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns) + float(e.duration_ns)))
    return sorted(out, key=lambda a: a[1])


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) pieces of ``intervals`` cut to [lo, hi)."""
    merged = []
    for start, end in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(ops) -> dict:
    """{op name: ns of its own}: duration less the events nested in it."""
    out, stack = {}, []
    for name, start, end in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and start >= stack[-1][1]:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= min(end, stack[-1][1]) - start
        out[name] = out.get(name, 0.0) + (end - start)
        stack.append((name, end))
    return out


def idle_by_label(gaps: list, requests: list) -> dict:
    """{label: ns}: every stretch of the idle ``gaps`` goes to the requests
    open during it (several: their labels joined by ``+``), or to
    ``BETWEEN``.  One sweep over the edges of gaps and requests."""
    events = []
    for start, end in gaps:
        if end > start:
            events += [(start, 0, None), (end, 1, None)]
    for label, start, end in requests:
        events += [(start, 2, label), (end, 3, label)]
    out, open_now, idle, last = {}, {}, False, None
    for when, kind, label in sorted(events, key=lambda e: e[:2]):
        if idle and when > last:
            names = "+".join(sorted(n for n, c in open_now.items() if c))
            out[names or BETWEEN] = out.get(names or BETWEEN, 0.0) + when - last
        last = when
        if kind < 2:
            idle = kind == 0
        else:
            open_now[label] = open_now.get(label, 0) + (1 if kind == 2 else -1)
    return out


def reduce_events(ops_by_device: dict, requests: list, lo: float,
                  hi: float) -> dict:
    """The reduction, on plain lists: ``requests`` are (label, start_ns,
    end_ns) on the trace's clock, the window is [lo, hi)."""
    if not ops_by_device or hi <= lo:
        raise SystemExit("chipbench: the trace holds no device operation; "
                         "every cell has to drive the device")
    chips = len(ops_by_device)
    busy_ns, gaps, op_ns = 0.0, {}, {}
    inside = [0.0] * len(requests)
    for ops in ops_by_device.values():
        pieces = union([(s, e) for _, s, e in ops], lo, hi)
        busy_ns += sum(e - s for s, e in pieces)
        edges = [lo] + [t for piece in pieces for t in piece] + [hi]
        for label, ns in idle_by_label(
                list(zip(edges[0::2], edges[1::2])), requests).items():
            gaps[label] = gaps.get(label, 0.0) + ns
        for name, ns in self_times([o for o in ops
                                    if o[2] > lo and o[1] < hi]).items():
            op_ns[name] = op_ns.get(name, 0.0) + ns
        starts = [start for start, _ in pieces]
        before = [0.0]
        for start, end in pieces:
            before.append(before[-1] + end - start)

        def busy_until(t):
            i = bisect.bisect_right(starts, t)
            if i == 0:
                return 0.0
            return before[i - 1] + min(t, pieces[i - 1][1]) - pieces[i - 1][0]

        for k, (_, s, e) in enumerate(requests):
            inside[k] += busy_until(e) - busy_until(s)
    by_shape = {}
    for (label, s, e), ns in zip(requests, inside):
        if s >= lo and e <= hi:
            by_shape.setdefault(label, []).append(ns / 1e9)
    top = lambda d: [[k, v / chips / 1e9] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": busy_ns / chips / 1e9, "window_s": (hi - lo) / 1e9,
            "idle_share": 1.0 - busy_ns / chips / (hi - lo),
            "breakdown": {"device_ops": top(op_ns), "idle_gaps": top(gaps)},
            # a request's busy time, summed over the chips it ran on
            "busy_s_by_shape": by_shape}


def reduce(path: str, anchor_ns: int, requests: list) -> dict:
    """Reduce the trace at ``path``.  ``requests`` are (label, t0_ns,
    t1_ns) on CLOCK_MONOTONIC, and ``anchor_ns`` is that clock's reading
    just before the ``chipbench:window_start`` annotation was written."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    marks = {name: start for name, start, _ in annotations(data)}
    if WINDOW_START not in marks or WINDOW_END not in marks:
        raise SystemExit("chipbench: the trace lacks the window's annotations")
    shift = marks[WINDOW_START] - anchor_ns
    out = reduce_events(
        device_ops(data),
        [(label, t0 + shift, t1 + shift) for label, t0, t1 in requests],
        marks[WINDOW_START], marks[WINDOW_END])
    out["median_busy_s_by_shape"] = {
        label: statistics.median(values)
        for label, values in out["busy_s_by_shape"].items() if values}
    return out
