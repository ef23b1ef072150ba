"""Learned capacities: what the compiled tier has found out about a
program's group counts and compaction sites, and how a run's flags turn into
the next round's capacities.

Static shapes need a capacity for every GROUP BY and every compaction site
(``compiled._Tracer``); a program reports what each site counted through its
flags (``traced.read`` names their parts), ``_check_flags`` asks for a
recompile where a capacity proved too small (or far too large), and what was
learned is kept per program key: in memory, and in ``DSQL_CAPS_FILE`` for
the next process.
"""
from __future__ import annotations

import os
from collections import OrderedDict
from typing import Dict, Optional

from ..runtime import kvstore as _kv, telemetry as _tel
from .traced import read as _read_flags

DEFAULT_GROUP_CAP = 4096
_LEARNED_LIMIT = 1024

# escalated group caps and compaction caps per program key, so steady state
# never repeats an overflow run; bounded like the program cache
_learned_caps: "OrderedDict[tuple, Dict[str, int]]" = OrderedDict()

# Optional write-through persistence for learned group caps
# (``DSQL_CAPS_FILE=/path.json``): a capacity-escalation recompile is cheap
# on XLA:CPU and a compile of minutes for a TPU, so caps learned by one
# process (a warmup run) must carry to the next.  Keys are hashes of the full program base key — plan
# fingerprint, input layout fingerprint, strategy — so a cap never applies
# to a different query, data layout, or backend strategy.
_caps_disk: Optional[Dict[str, Dict[str, int]]] = None


def _caps_disk_read(path: str) -> Dict[str, Dict[str, int]]:
    """Tolerant caps-file read on the shared kvstore plumbing
    (runtime/kvstore.py — the same atomic-write/corrupt-tolerant
    discipline the quarantine store and the program store index use)."""
    return {k: {t: int(c) for t, c in v.items()}
            for k, v in _kv.read_json_dict(path).items()}


def _learned_caps_get(base_key) -> Dict[str, int]:
    caps = _learned_caps.get(base_key)
    if caps is not None:
        return dict(caps)
    path = os.environ.get("DSQL_CAPS_FILE")
    if path:
        global _caps_disk
        if _caps_disk is None:
            _caps_disk = _caps_disk_read(path)
        return dict(_caps_disk.get(_kv.digest_key(base_key), {}))
    return {}


def _learned_caps_put(base_key, caps: Dict[str, int]) -> None:
    _bounded_put(_learned_caps, base_key, dict(caps))
    path = os.environ.get("DSQL_CAPS_FILE")
    if not path:
        return
    global _caps_disk
    # read-merge-replace: concurrent writers (threaded warmup) can lose a
    # race, which only costs one re-learn — never corrupts (kvstore's
    # atomic replace; tmp name is per-thread so two warmup threads can't
    # interleave bytes)
    disk = _caps_disk_read(path)
    disk[_kv.digest_key(base_key)] = {k: int(v) for k, v in caps.items()}
    if _kv.atomic_write_json(path, disk):
        _caps_disk = disk


def _bounded_put(d: OrderedDict, key, value, limit: int = _LEARNED_LIMIT):
    while len(d) >= limit:
        d.popitem(last=False)
    d[key] = value


def starting_caps(pk, context, count: bool = True) -> Dict[str, int]:
    """The capacities a request starts from: what was learned for this
    program, then statistics-derived hints for the sites nothing is known
    about yet (runtime/statistics.py).  Learned caps stay authoritative,
    and a too-small hint just trips the normal overflow escalation —
    never a wrong result.  A probe passes ``count=False``: a prediction
    counts nothing."""
    caps = _learned_caps_get(pk.key)
    # "__split__" is the learned budget hint, not a site's capacity: it
    # must not leak into the program cache key or the tracer's lookups
    caps.pop("__split__", None)
    from ..runtime import statistics as _stats
    for tag, cap in _stats.compiled_cap_hints(pk.plan, context).items():
        if tag not in caps:
            caps[tag] = cap
            if count:
                _tel.inc("stats_cap_hints")
                _tel.annotate(cap_hint=f"{tag}={cap}")
    # which joins may probe a build side's key column in place of a table:
    # a hint one run's check refuted stays learned as 0
    for tag, level in _stats.ordered_probe_hints(pk.plan, context).items():
        caps.setdefault(tag, level)
    # how wide a join key's base column is: what lets the tracer size a
    # table that can be direct-addressed (``hashing._hash_table_size``)
    for tag, span in _stats.key_span_hints(pk.plan, context).items():
        caps.setdefault(tag, span)
    # how many GROUP BYs may take their groups from the runs of a key
    # column in load order: refuted once, it stays learned as 0
    for tag, runs in _stats.run_group_hints(pk.plan, context).items():
        caps.setdefault(tag, runs)
    return caps


def split_hint(base_key) -> Optional[int]:
    """The learned stage budget of a plan whose whole program crashed the
    TPU compiler (helper SIGSEGV / silent loss on a fused sort pipeline):
    ``_degrade_compile`` leaves "__split__" among its learned caps, so
    every later process stages it at once instead of crashing again."""
    hint = _learned_caps_get(base_key).get("__split__")
    return None if hint is None else int(hint)


class _NeedsRecompile(Exception):
    """``caps``: what the next round compiles for.  ``reason``: what asked
    for it, the next ``compile`` span's ``cause`` and the key of
    ``telemetry.RECOMPILE_COUNTERS``: ``cap_overflow`` (a group cap or a
    compaction site dropped rows), ``cap_tighten`` (a compaction site far
    above its count, or one that only counted and goes live),
    ``hint_refuted`` (an ``ord*`` or ``runs`` hint the column did not
    keep)."""

    def __init__(self, caps, reason):
        self.caps = caps
        self.reason = reason


def changed(entry, new_caps: Dict[str, int]) -> str:
    """What a ``_NeedsRecompile`` changed, for the next ``compile`` span:
    only the tags that differ, each as ``agg0:256>4096``, from what the
    program ran with (a site's own default where nothing was learned)."""
    had = {**entry.caps,
           **{tag: cap for (_, _, tag), cap in zip(entry.meta["agg_sites"],
                                                   entry.meta["ngroup_caps"])}}
    return ",".join(f"{tag}:{had.get(tag)}>{cap}"
                    for tag, cap in sorted(new_caps.items())
                    if had.get(tag) != cap)


def _check_ordered(entry, flags) -> None:
    """Raise _NeedsRecompile where a program took a column's order on a
    hint (runtime/statistics.py) the column did not keep: a join that
    probed its build side's key column (``ord*``), a GROUP BY that took its
    groups from the runs of its key (``runs``).  The flags say of each
    such hint whether the program's check of the physical column failed.
    Such a run's answer is worth nothing and neither are its other flags,
    the eager bit among them: the next round clears the hint and builds
    the table, and the cleared hint is learned."""
    refuted = _read_flags(entry.meta, flags).refuted
    if any(bad for _, bad in refuted):
        raise _NeedsRecompile({**entry.caps, **{
            tag: 0 for tag, bad in refuted if bad}},
            "hint_refuted")


def _check_flags(entry, flags) -> None:
    """Raise _NeedsRecompile on group-cap overflow.
    ``entry`` is a ``programs._Compiled`` (its ``meta`` and ``caps``).
    Compaction sites (tag cmp*) additionally SHRINK: a cap far above the
    observed count recompiles once to a tight one (persisted, so future
    processes trace tight directly), and a site that only counted so far
    (``_maybe_compact``, ``after_join``) goes live where it is selective.

    Sites stand in chains, in trace order: one that overflowed dropped
    rows, so every count after it in this run is too low, and a cap shrunk
    to such a count overflows in the next round.  Past the first overflow
    nothing shrinks.  A round that recompiles anyway sets every site whose
    count is true to its tight cap and pins the others where they are: a
    default cap goes by the site's input rows, which the sites below are
    about to change."""
    meta = entry.meta
    new_caps = dict(entry.caps)
    recompile = False
    exact = True
    for (n_rows, hashed, tag), cap, ng in zip(
            meta["agg_sites"], meta["ngroup_caps"],
            _read_flags(meta, flags).site_counts):
        ng = int(ng)
        if ng > cap:
            if hashed and ng > n_rows:
                # ng = n+1 is the hashed path's SATURATED sentinel: the true
                # group count is unknowable from this run.  Jump hard (x16,
                # bounded by the input row count) instead of climbing a
                # doubling ladder — but not straight to n_rows: a tight cap
                # matters more at steady state (group outputs are cap-padded
                # downstream) than one extra recompile does at warmup.
                need = min(1 << (int(n_rows) - 1).bit_length(), cap * 16)
            else:
                need = 1 << (ng - 1).bit_length()
            new_caps[tag] = max(need, cap * 2)
            recompile = True
            exact = False
        elif tag.startswith("cmp"):
            if not exact:
                if cap < n_rows:
                    new_caps[tag] = cap
                continue
            # twice the power of two above the count
            tight = 2 << max((max(ng, 1) - 1).bit_length(), 10)
            new_caps[tag] = min(tight, cap)
            if cap >= n_rows:
                # a counting site: worth a compile where it would compact
                recompile = recompile or tight * 2 < n_rows
            elif tight * 4 <= cap:
                # one recompile to the tight cap: every downstream sort in
                # the steady-state program shrinks by >= 8x
                recompile = True
    if recompile:
        # an overflow and a tighten in one round is an overflow
        raise _NeedsRecompile(new_caps,
                              "cap_tighten" if exact else "cap_overflow")
