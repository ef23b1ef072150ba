"""Ends a run whose tables are not on the device in time.

``run.py::load_phase`` calls ``Context.create_table`` for each table and
waits, however long that takes.  At SF10 an engine that loads 21 MB/s (every
cell's ``create_table_s`` up to PR 30: 36 s for 777 MB) needs seven minutes
for the 7.77 GB, and then answers its first texts on a tier that takes
minutes a text: in a run the driver gives 1200 s (a tree's first) or 360 s
and kills beyond, and a killed run refuses a PR.  A generator
that hands its frames over as ``watched(frames, deadline_s)``
(``chipbench/data/tpch_resident.py``) ends such a run: exit code 1, a
``refused`` line on stdout, the reason on stderr, as
``chipbench/ready_limit.py`` does for a shape without a program.

What it reads is the harness's own progress, and no name of the engine or
of JAX.  ``load_phase`` takes each table's frame out of the mapping once to
load it, and once more to count its rows for the ``load`` line, after
``block_until_ready`` on every live array: the first table taken out a
second time is a load that is over.  A timer asks at the deadline whether
that has happened; and because a thread of a process that is deep in a load
is not sure of its turn (the parent of PR 31, whose load runs 414 s, twice
went on past a timer thread that was to read ``jax.live_arrays()`` 180 s
in), the mapping asks again on the harness's own thread when the load is
over.  Only a process started as ``chipbench/run.py`` is held to it: tests,
``control.py`` and a notebook load at their own pace.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

_RUN_PY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "run.py")


def _under_the_harness() -> bool:
    main = getattr(sys.modules.get("__main__"), "__file__", None)
    return main is not None and os.path.abspath(main) == _RUN_PY


def _refuse(loaded_s, limit: float) -> None:
    print(json.dumps({"phase": "refused", "load_deadline_s": limit,
                      "loaded_after_s": loaded_s}), flush=True)
    print("chipbench: the tables were "
          + ("not on the device" if loaded_s is None
             else f"on the device only {loaded_s:.1f} s, not")
          + f" {limit:g} s after the generator returned; the run ends here",
          file=sys.stderr, flush=True)
    # not SystemExit: the timer's is not the main thread, which is inside
    # ``create_table``
    os._exit(1)


class Watched(dict):
    """The generator's frames, and when the harness was through with
    loading them: ``loaded_s``, seconds after these were handed over, set
    when a table is taken out for the second time."""

    def __init__(self, frames: dict, deadline_s: float, refuse=_refuse,
                 clock=time.monotonic):
        super().__init__(frames)
        self.deadline_s = float(deadline_s)
        self.loaded_s = None
        self._refuse, self._clock = refuse, clock
        self._t0 = clock()
        self._taken = set()

    def __getitem__(self, name):
        if self.loaded_s is None and name in self._taken:
            self.loaded_s = self._clock() - self._t0
            if self.loaded_s > self.deadline_s:
                self._refuse(self.loaded_s, self.deadline_s)
        self._taken.add(name)
        return super().__getitem__(name)

    def at_the_deadline(self) -> bool:
        """True if the load is over, else ``refuse``."""
        if self.loaded_s is not None:
            return True
        self._refuse(None, self.deadline_s)
        return False


def watched(frames: dict, deadline_s: float) -> dict:
    """``frames`` as they are outside the harness; under it, the same
    frames in a mapping that is held to ``deadline_s``, its timer started."""
    if not _under_the_harness():
        return frames
    held = Watched(frames, deadline_s)
    clock = threading.Timer(held.deadline_s, held.at_the_deadline)
    clock.daemon = True
    clock.start()
    return held
