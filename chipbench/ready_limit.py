"""Ends a run at the first shape that set-up got no program for.

``traffic.py`` defines ``ready_deadline_s``: "a shape that no compiled
program serves by then, from its first submission at set-up, has failed".
``run.py::ready_phase`` writes that into the shape's record and goes on: the
other shapes, the window, the comparison, and a last line that says
``correct: false``.  Where a shape's program is many minutes of compiling
away, every further text costs minutes as well: the parent of PR 27
(504c8ff) needs 42 to 48 minutes to that line in ``tpch_sf1_joins.power``
(PERF.md section 6), and the driver gives a run 1200 s.  A shape whose
``sql()`` calls ``asked()`` ends such a run when the deadline strikes: exit
code 1, a ``refused`` line on stdout, the reason on stderr.

What it reads is the run's own clock, and no name of the engine.
``ready_phase`` asks a shape for its second text as soon as the first is
answered and no compile is in flight any more, and while that is not so it
waits out the deadline.  A shape whose second text has not been asked for
when the deadline is a second away (the harness polls four times a second)
is therefore a shape that no compiled program served in time, whatever
engine is under test.  Only a process started as ``chipbench/run.py`` is
held to it, and only in a mix that warms a shape with more than one text:
tests, ``control.py`` and a notebook call ``sql()`` at their own pace.
"""
from __future__ import annotations

import json
import os
import sys
import threading

from chipbench import traffic

_RUN_PY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "run.py")
_texts = {}
_clocks = {}


def _under_the_harness() -> bool:
    main = getattr(sys.modules.get("__main__"), "__file__", None)
    return main is not None and os.path.abspath(main) == _RUN_PY


def _refuse(shape: str, limit: float) -> None:
    print(json.dumps({"phase": "refused", "shape": shape,
                      "ready_deadline_s": limit}), flush=True)
    print(f"chipbench: {shape} was not served by a compiled program within "
          f"{limit:g} s of its first text; the run ends here",
          file=sys.stderr, flush=True)
    # not SystemExit: this is not the main thread, which may be inside a
    # compile of many minutes, as may a daemon thread of the engine
    os._exit(1)


def asked(shape: str, mix: str) -> None:
    """Called by ``shape``'s ``sql()`` for every text it hands out; ``mix`` is
    the traffic file whose ``ready_deadline_s`` the shape is held to."""
    if not _under_the_harness():
        return
    _texts[shape] = _texts.get(shape, 0) + 1
    if _texts[shape] == 1:
        spec = traffic.load_mix(mix)
        if int(spec["warm_extra"]) >= 1:
            limit = float(spec["ready_deadline_s"])
            _clocks[shape] = threading.Timer(limit - 1.0, _refuse,
                                             (shape, limit))
            _clocks[shape].daemon = True
            _clocks[shape].start()
    elif shape in _clocks:
        _clocks.pop(shape).cancel()
