"""Ends a run in which set-up has a program for a shape and new parameters
do not get it.

``ready_limit`` ends a run at a shape whose SECOND text has not been asked
for when ``ready_deadline_s`` strikes: no program came of the first.  An
engine whose program's key holds a literal that every text moves passes
that test and fails the next: each text is answered by the eager tier while
a program for ITS literals compiles, a whole-plan compile of a minute or
more at SF1.  ``run.py::ready_phase`` gives the shape up after three such
arrivals and goes on with the others and the window, in which every request
of the shape compiles again: with four dates of set-up that is past the 360
s a run after a tree's first is given (the parent of PR 43 on TPC-H Q15,
whose dates stand inside a scalar subquery's body).  A shape whose
``sql()`` calls ``asked()`` ends such a run at the first of those waits:
exit code 1, a ``refused`` line on stdout, the reason on stderr, as
``ready_limit`` does.

What it reads is the run's own clock, and no name of the engine.
``ready_phase`` asks for a shape's next text AT ONCE when a compiled program
served the last, and waits for the compiles in flight when none did; after
a shape's last text come the next shape's first or the window's first.  A
compiled program's answer is inside the mix's ``deadline_s`` ("a query that
takes longer has failed").  So when ``deadline_s`` after a shape's second or
third text of set-up no text of any shape has been asked for, that text was
not served by a compiled program.  A shape's first text is not held to this
(its program compiles then: ``ready_limit``'s part), nor is a text of the
window (after the window's last comes the comparison, not a text).  The
text that may be set-up's last (the mix's last shape's last) is given
``ready_deadline_s`` more: ``ready_phase`` ends with a wait of up to that
for the compiles still in flight, and a sound run is not ended in it.  Only a
process started as ``chipbench/run.py`` is held to it, as with
``ready_limit``.
"""
from __future__ import annotations

import json
import os
import sys
import threading

from chipbench import ready_limit, traffic

_texts = {}
_armed = []
_mixes = {}     # a mix's file is read once: sql() is on the window's path


def _refuse(shape: str, text: int, limit: float) -> None:
    print(json.dumps({"phase": "refused", "shape": shape, "text": text,
                      "deadline_s": limit}), flush=True)
    print(f"chipbench: {limit:g} s after {shape}'s text {text} of set-up no "
          "other text had been asked for: no compiled program served it; "
          "the run ends here", file=sys.stderr, flush=True)
    # not SystemExit: this is not the main thread (see ready_limit._refuse)
    os._exit(1)


def asked(shape: str, mix: str) -> None:
    """Called by ``shape``'s ``sql()`` for every text it hands out; ``mix`` is
    the traffic file whose ``deadline_s`` and ``warm_extra`` it is held to."""
    if not ready_limit._under_the_harness():
        return
    while _armed:
        _armed.pop().cancel()
    _texts[shape] = _texts.get(shape, 0) + 1
    if mix not in _mixes:
        _mixes[mix] = traffic.load_mix(mix)
    spec = _mixes[mix]
    texts = 1 + int(spec["warm_extra"])
    if 2 <= _texts[shape] <= texts:
        limit = float(spec["deadline_s"])
        if shape == list(spec["shapes"])[-1] and _texts[shape] == texts:
            # it may be set-up's last: ``ready_phase`` then waits for the
            # compiles still in flight, up to ``ready_deadline_s``, before
            # the window's first text is asked for
            limit += float(spec["ready_deadline_s"])
        clock = threading.Timer(limit, _refuse,
                                (shape, _texts[shape], limit))
        clock.daemon = True
        clock.start()
        _armed.append(clock)
