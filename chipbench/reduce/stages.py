"""What a staged query leaves in a trace, per request.

``spans.py`` beside this file reduces a trace to requests, scopes and idle
time; this reads two more things off the same requests:

    stages     the ``dsql:stage`` events that start inside a request's
               ``dsql:query``: the stage programs of a plan the executor
               cut into a stage graph (``physical/stages.py``), none for a
               plan that runs as one program
    hand-over  the chip's idle time between a request's first and last
               device op: the request's extent less the wait before its
               first op, the wait after its last, and the ops' own time.
               Between stage programs the host materializes one stage's
               output and binds the next; in a whole-plan program what is
               left is the gaps between the small programs the host
               launches for its literals and the program itself

A program from before the engine wrote ``dsql:query`` has no requests, and
every number is None.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import statistics

from chipbench.reduce import spans

STAGE = "dsql:stage"


@functools.lru_cache(maxsize=1)
def _stage_starts(path: str, mtime_ns: int) -> tuple:
    """Start times (ns) of the trace's ``dsql:stage`` events, sorted."""
    starts = []
    for plane in spans.read_planes(path):
        if plane["name"] != spans.HOST_PLANE:
            continue
        for events in plane["lines"].values():
            starts += [start for name, start, _, _ in events if name == STAGE]
    return tuple(sorted(starts))


def stages_per_request(path: str, requests: list) -> list:
    """For each of ``requests`` (of ``spans.reduce``), the stages begun
    inside it."""
    starts = _stage_starts(path, os.stat(path).st_mtime_ns)
    return [bisect.bisect_left(starts, r["end_ns"])
            - bisect.bisect_left(starts, r["start_ns"]) for r in requests]


def handoff_ns(request: dict):
    """Idle time of the chip between the request's first and last device
    op; None for a request that ran nothing on the device."""
    if request["idle_pre_ns"] is None:
        return None
    busy = sum(request["device_ns_by_scope"].values())
    return max(request["end_ns"] - request["start_ns"]
               - request["idle_pre_ns"] - request["idle_post_ns"] - busy, 0.0)


def _trace_path(run: dict):
    """The file ``spans.of_run`` reduced, or None."""
    if run.get("trace") is None:
        return None
    found = sorted(glob.glob(os.path.join(
        spans._ROOT, ".chipbench_trace", "plugins", "profile", "*",
        "*.xplane.pb")))
    return found[-1] if found else None


def stages_per_query(run: dict):
    reduced, path = spans.of_run(run), _trace_path(run)
    if reduced is None or path is None or not reduced["requests"]:
        return None
    return statistics.median(stages_per_request(path, reduced["requests"]))


def stage_handoff_ms(run: dict):
    reduced = spans.of_run(run)
    if reduced is None:
        return None
    values = [v for v in map(handoff_ns, reduced["requests"])
              if v is not None]
    return statistics.median(values) / 1e6 if values else None
