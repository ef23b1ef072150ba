"""The one generator every traffic mix goes through.

A mix is a data file, ``chipbench/traffic/<name>.json``:

    loop             "closed" (a client's next request goes out when its
                     last is answered) or "open" (requests go out on a
                     Poisson schedule at ``rate_per_s``, from a pool of
                     ``clients`` senders)
    clients          how many
    shapes           {shape name: weight}; a cycle holds each shape
                     ``weight`` times, in an order drawn from the seed
    repeat_share     share of requests that re-issue one of
    repeat_texts     fixed texts (0 and 0: every request is a new text)
    poll_interval_ms what a served client waits between polls of a
                     statement that is QUEUED or RUNNING
    max_per_client_per_s  requests handed to each served client for each
                     second of the window (they have to be drawn before it
                     opens); a window that uses them all is an error
    deadline_s       a query that takes longer has failed
    ready_deadline_s a shape that no compiled program serves by then, from
                     its first submission at set-up, has failed
    warm_extra       parameter sets a compiled program serves at set-up
                     after its first
    compare_per_shape  window answers of each shape held against the reference
    trace_after_s, trace_seconds   the sub-window a ``--trace 1`` run profiles

Every request that is not a repeat takes the next parameter set of its
shape from one seeded permutation of the shape's whole space, shared by
all clients and by set-up: no text is sent twice, so no answer is a
result-cache replay unless the mix asks for repeats.  Only a shape's first
text, which set-up sends, is the same in every run (the shape's ``FIRST``,
the spec's validation parameters).  The seed changes the order and the
parameters, never the amount or kind of work.
"""
from __future__ import annotations

import itertools
import json
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    path = os.path.join(_HERE, "traffic", f"{name}.json")
    if not os.path.isfile(path):
        raise SystemExit(f"chipbench: no traffic mix {path}")
    with open(path) as f:
        return json.load(f)


class Draws:
    """Parameter sets of each shape, each handed out once."""

    def __init__(self, shapes: dict, seed: int):
        self._shapes = shapes
        self._order = {}
        self._next = {}
        for i, name in enumerate(sorted(shapes)):
            rng = np.random.RandomState((seed + 7919 * (i + 1)) % (1 << 32))
            rest = rng.permutation(shapes[name].SPACE)
            # a shape's first text is the same in every run: the eager tier
            # answers it, and compiles a program for every size it meets
            first = shapes[name].FIRST
            self._order[name] = np.concatenate(
                [[first], rest[rest != first]])
            self._next[name] = 0

    def fresh(self, name: str) -> dict:
        """{"shape", "params", "sql"} of a text not handed out before, as
        long as the shape's space lasts; then it begins again."""
        order = self._order[name]
        index = int(order[self._next[name] % len(order)])
        self._next[name] += 1
        params = self._shapes[name].params_at(index)
        return {"shape": name, "params": params,
                "sql": self._shapes[name].sql(params)}


def requests_for_window(mix: dict, draws: Draws, seed: int) -> list:
    """One endless iterator of requests for each client (an open loop: one
    for all its senders), in whole cycles.  Requests are drawn as they are
    taken, so the clients' iterators are to be taken from in a fixed
    order."""
    rng = np.random.RandomState((seed + 104729) % (1 << 32))
    cycle = [name for name, weight in mix["shapes"].items()
             for _ in range(int(weight))]
    pool = [draws.fresh(cycle[i % len(cycle)])
            for i in range(int(mix.get("repeat_texts", 0)))]
    share = float(mix.get("repeat_share", 0.0))
    ids = itertools.count()

    def client():
        due = 0.0
        while True:
            for k in rng.permutation(len(cycle)):
                if pool and rng.random_sample() < share:
                    request = dict(pool[rng.randint(len(pool))], repeat=True)
                else:
                    request = dict(draws.fresh(cycle[k]), repeat=False)
                request["id"] = next(ids)
                if mix["loop"] == "open":
                    due += rng.exponential(1.0 / float(mix["rate_per_s"]))
                    request["due_s"] = due
                yield request

    return [client() for _ in range(
        1 if mix["loop"] == "open" else int(mix["clients"]))]
