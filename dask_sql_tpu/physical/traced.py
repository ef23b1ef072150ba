"""What a trace hands from operator to operator, and what it owes the host.

``_VT`` is the stream between two operators of a traced plan: the tracer
(physical/compiled.py) and the formulations beneath it (physical/joins.py,
physical/aggregates.py) both speak it.

``ProgramFlags`` is the ledger of one trace: what XLA cannot express
statically (a group count over its capacity, a build side that is not
unique, a hint the data did not keep) leaves the program in one int64
vector, the flags, and what is static about it in the program's ``meta``.
The vector's layout has this one home: ``pack`` writes it, ``read`` names
its parts for the host (``caps._check_flags``, ``caps._check_ordered``,
``compiled._materialize``), and nothing else indexes or slices one::

    [eager, count] + a count a site + a bit an ``ord*`` hint refuted
    + a bit a ``runs`` hint refuted + a bit a hash table direct-addressed

each part in trace order.  Imports nothing of the compiled tier.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..runtime.statistics import RUN_GROUPS_TAG
from ..table import Table


class _VT:
    """A padded device table + row-validity mask (None = all rows valid).

    ``weight`` is the PRE-compaction row count (defaults to the physical
    row count): heuristics that pick sides by size — the INNER-join
    probe/build choice — must see the logical stream size, or a compacted
    fact side masquerades as small, becomes the build, and its duplicate
    keys trip the unique-build fallback.

    ``hash_joins`` is set on a stream compacted at a join's output: the
    joins above it keep the hash table though their probe side is small
    now (``compiled._Tracer._LogicalJoin`` has the reason).

    ``load_order`` is set while the rows are still a scan's rows in the
    order they were loaded (a project, a filter that only masks): a join
    may then probe such a build side's key column itself
    (``joins.ordered``).  Whatever moves rows clears it."""

    __slots__ = ("table", "valid", "weight", "hash_joins", "load_order")

    def __init__(self, table: Table, valid: Optional[jax.Array],
                 weight: Optional[int] = None, hash_joins: bool = False,
                 load_order: bool = False):
        self.table = table
        self.valid = valid
        self.weight = weight if weight is not None else table.num_rows
        self.hash_joins = hash_joins
        self.load_order = load_order

    def carry(self, table: Table, valid: Optional[jax.Array]) -> "_VT":
        """This stream after an operator that hands its rows on: what the
        joins above decide by rides along."""
        return _VT(table, valid, self.weight, self.hash_joins,
                   self.load_order)

    @property
    def n(self) -> int:
        return self.table.num_rows

    def vmask(self) -> jax.Array:
        if self.valid is None:
            return jnp.ones(self.n, dtype=bool)
        return self.valid


class ProgramFlags:
    """The ledger of one trace.  Device values go in through the four
    methods below, in trace order; the counters are static and the
    formulations add to them as plain attributes."""

    def __init__(self):
        self._fallback: List[jax.Array] = []     # device bools -> eager rerun
        self._counts: List[jax.Array] = []       # device ints, one a site
        self.site_caps: List[int] = []           # matching static caps
        self.sites: List[Tuple[int, bool, str]] = []  # (rows, hashed, tag)
        # (tag, the program's check of the hint), ``ord*`` and ``runs``
        self.hints: List[Tuple[str, jax.Array]] = []
        self._direct: List[jax.Array] = []       # one device bool a table
        # rows the program's joins take in, probe + build of each
        self.join_rows = 0
        # the hash tables a ``span*`` hint sized
        self.span_tables = 0
        # rows the static-domain aggregates named to the limb kernel, and
        # the distinct and the indicator rows the kernel sums for them:
        # added by the kernel where it is the backend
        self.limb_rows: Dict[str, int] = {}
        # the SEMI / ANTI joins, the inlined scalar subqueries, the
        # references to a subtree that an earlier one had traced
        self.semi_joins = self.scalar_subqueries = self.shared_subplans = 0
        # the ordered probes whose key column is dense (their probe is
        # arithmetic: ``compiled._count_probes`` counts it as direct)
        self.ordered_dense = 0

    # -- trace time ---------------------------------------------------------
    def fallback(self, bit: jax.Array) -> None:
        """``bit`` set: the program's answer is wrong (a collision, a
        duplicate build key) and the eager executor answers."""
        self._fallback.append(bit)

    def site(self, tag: str, rows: int, hashed: bool, cap: int,
             count: jax.Array) -> None:
        """A GROUP BY or a compaction: what it counted, against its static
        capacity and input rows (``caps._check_flags`` has the ladder).
        ``hashed``: the count saturates at ``rows`` + 1, where an exact
        site's (a compaction, a GROUP BY by runs) is true."""
        self._counts.append(count)
        self.site_caps.append(cap)
        self.sites.append((rows, hashed, tag))

    def hint(self, tag: str, ok: jax.Array) -> None:
        """The program's check of a hint it took a column's order on: an
        ordered probe's ``ord<j>l`` / ``ord<j>r``, a GROUP BY by runs'
        ``runs`` (``caps._check_ordered``: a refuted one never answers)."""
        self.hints.append((tag, ok))

    def direct(self, bit: jax.Array) -> None:
        """One a join of the hash-table formulation: whether the data let
        its table be direct-addressed."""
        self._direct.append(bit)

    def pack(self, count: jax.Array) -> jax.Array:
        """The program's first output; ``count`` is its result's rows."""
        fb = jnp.zeros((), dtype=bool)
        for f in self._fallback:
            fb = fb | f
        # the joins' hints, then the GROUP BYs', each in trace order
        hints = sorted(self.hints, key=lambda h: h[0] == RUN_GROUPS_TAG)
        return jnp.stack([fb.astype(jnp.int64), count]
                         + [g.astype(jnp.int64) for g in
                            self._counts + [~ok for _, ok in hints]
                            + self._direct])

    def meta(self) -> dict:
        """The static part, under the keys ``programs``, ``caps.changed``
        and a ``dispatch`` span read."""
        tags = [tag for tag, _ in self.hints]
        return {"ngroup_caps": list(self.site_caps),
                "agg_sites": list(self.sites),
                "join_rows": self.join_rows,
                "limb_rows": dict(self.limb_rows),
                "hash_table_joins": len(self._direct),
                "span_tables": self.span_tables,
                "semi_joins": self.semi_joins,
                "scalar_subqueries": self.scalar_subqueries,
                "shared_subplans": self.shared_subplans,
                "ordered": [t for t in tags if t != RUN_GROUPS_TAG],
                "ordered_dense": self.ordered_dense,
                "run_groupbys": tags.count(RUN_GROUPS_TAG)}


class Flags(NamedTuple):
    """A run's flags by name (``read``)."""
    eager: bool                 # the answer is the eager executor's to give
    count: int                  # the result's rows
    site_counts: Sequence[int]  # beside meta["agg_sites"] / ["ngroup_caps"]
    refuted: List[Tuple[str, bool]]   # (hint's tag, its check failed)
    direct: Sequence[int]       # one a hash table: direct-addressed


def read(meta: dict, flags) -> Flags:
    """The parts of a fetched flags vector, by the program's ``meta``."""
    hints = 2 + len(meta["agg_sites"])
    tags = list(meta.get("ordered") or ()) \
        + [RUN_GROUPS_TAG] * meta.get("run_groupbys", 0)
    tables = hints + len(tags)
    return Flags(bool(flags[0]), int(flags[1]), flags[2:hints],
                 list(zip(tags, flags[hints:tables])),
                 flags[tables:tables + meta.get("hash_table_joins", 0)])
