"""``tpch_gen``'s tables for a configuration whose set-up is most of its
run: ``generate(sf, seed)`` gives the frames ``tpch_gen.generate(sf, seed)``
gives, value for value, dtype for dtype and block for block, in a fifth of
the time (26 s for 140 at SF10), and the
clock of ``chipbench/load_limit.py`` starts as they are handed over (under
``chipbench/run.py`` in a mapping that notes when the harness is through
with loading them).

Why not ``tpch_gen.generate`` itself: at SF10 it takes 140 s of a run that
the driver stops at 360 s, 100 of them in work that makes no value: a
fixed-width UCS4 array for every string column (``choice`` out of a list of
words), which pandas then reads back a string at a time; ``str.zfill`` in
Python for every name; two copies of each column of numbers on the way
into its block, one after another.  Here the two random streams are drawn
in ``tpch_gen``'s order by the same calls (``choice(len(words), n)`` draws
what ``choice(words, n)`` draws, and gives the indices), and then the
columns are built from the draws by a pool of threads: a string column as
an arrow ``take`` out of its words, a name by arrow's ``lpad``, the numbers
written into the blocks the frame is then made of (``_frame``).
``tests/chipbench/test_chipbench_sf10.py`` holds the two generators equal.

``chipbench/run.py`` finds a generator by the name a configuration gives it
and asks it for ``generate(sf, seed)``; this one also exists so that
``tpch_sf10_embedded`` can be held to ``load_deadline_s`` without an edit to
the harness or to ``tpch_gen``.
"""
from __future__ import annotations

import json
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.compute as pc

from chipbench import load_limit
from chipbench.data import tpch_gen
from chipbench.data.tpch_gen import _D

_CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "tpch_sf10_embedded.json")

_TEXT = pa.large_string()  # what pandas keeps a ``str`` column in
_STR = pd.StringDtype(na_value=np.nan)
_WORKERS = 8


def _text(array: pa.Array):
    """The ``str`` column pandas makes of these strings."""
    return _STR.__from_arrow__(array)


def _words(words, picks: np.ndarray):
    """``np.array(words)[picks]`` as a frame holds it."""
    return _text(pa.array(list(words), type=_TEXT).take(pa.array(picks)))


def _tag(prefix, nums: np.ndarray, width: int):
    """``tpch_gen._tag``: f"{prefix}{num:0{width}d}" of numbers from 0 up;
    ``prefix`` is one string or one for each number."""
    if not isinstance(prefix, str):
        prefix = pa.array(prefix, type=_TEXT)
    else:
        prefix = pa.scalar(prefix, _TEXT)
    digits = pc.utf8_lpad(pc.cast(pa.array(nums), _TEXT), width, "0")
    return _text(pc.binary_join_element_wise(prefix, digits,
                                             pa.scalar("", _TEXT)))


def _blank(n: int):
    return _text(pa.Array.from_buffers(
        _TEXT, n, [None, pa.py_buffer(np.zeros(n + 1, dtype=np.int64)),
                   pa.py_buffer(b"")]))


def _days(days: np.ndarray) -> np.ndarray:
    """``pd.to_datetime(days, unit="D")`` as a frame holds it."""
    return days.astype("datetime64[D]").astype("datetime64[s]")


class later:
    """A column still to be made, ``fn(*args)``: by a worker, once both
    streams are drawn."""

    def __init__(self, fn, *args):
        self.fn, self.args = fn, args

    def make(self):
        return self.fn(*self.args)


def _frames(sf: float, seed: int) -> dict:
    """``tpch_gen.generate`` (read the two side by side: the tables, the
    columns and the draws of each stream come in its order).  A column is
    its value, or a call that makes it (``later``)."""
    rng = np.random.RandomState(seed % (1 << 32))
    fixed = np.random.RandomState(tpch_gen.STRUCTURE_STREAM)
    n_part = max(int(200_000 * sf), 50)
    n_supp = max(int(10_000 * sf), 10)
    n_cust = max(int(150_000 * sf), 30)
    n_ord = max(int(1_500_000 * sf), 150)
    nations = tpch_gen._NATIONS
    n_nation = len(nations)

    def picked(words, n, p=None):
        picks = fixed.choice(len(words), n, p=p)
        return later(_words, words, picks)

    def uniform(low, high, n):
        return later(np.round, rng.uniform(low, high, n), 2)

    region = {
        "r_regionkey": np.arange(5), "r_name": tpch_gen._REGIONS,
        "r_comment": ["" for _ in range(5)],
    }
    nation = {
        "n_nationkey": np.arange(n_nation),
        "n_name": [n for n, _ in nations],
        "n_regionkey": [r for _, r in nations],
        "n_comment": ["" for _ in range(n_nation)],
    }
    supplier = {
        "s_suppkey": np.arange(1, n_supp + 1),
        "s_name": later(_tag, "Supplier#", np.arange(1, n_supp + 1), 9),
        "s_address": later(_tag, "addr", np.arange(n_supp), 0),
        "s_nationkey": fixed.randint(0, n_nation, n_supp),
        "s_phone": later(_tag, "", np.arange(n_supp), 10),
        "s_acctbal": uniform(-999.99, 9999.99, n_supp),
        "s_comment": later(_blank, n_supp),
    }
    partkeys = np.arange(1, n_part + 1)
    part = {
        "p_partkey": partkeys,
        "p_name": picked(["ivory blue", "green navy", "red linen",
                          "metallic olive", "antique puff"], n_part),
        "p_mfgr": later(_tag, "Manufacturer#", np.arange(n_part) % 5 + 1, 0),
        "p_brand": later(_tag, "Brand#", (np.arange(n_part) % 5 + 1) * 10
                         + (np.arange(n_part) // 5) % 5 + 1, 0),
        "p_type": picked(tpch_gen._TYPES, n_part),
        "p_size": fixed.randint(1, 51, n_part),
        "p_container": picked(tpch_gen._CONTAINERS, n_part),
        "p_retailprice": np.round(900 + (partkeys % 1000) / 10.0
                                  + 100 * (partkeys % 10), 2),
        "p_comment": later(_blank, n_part),
    }
    n_ps = n_part * 4
    _ps_step = max(n_supp // 4, 1)

    def _psupp(partkey, i):
        return (partkey - 1 + i * _ps_step) % n_supp + 1

    partsupp = {
        "ps_partkey": np.repeat(partkeys, 4),
        "ps_suppkey": later(_psupp, np.repeat(partkeys, 4),
                            np.tile(np.arange(4), n_part)),
        "ps_availqty": rng.randint(1, 10_000, n_ps),
        "ps_supplycost": uniform(1.0, 1000.0, n_ps),
        "ps_comment": later(_blank, n_ps),
    }
    c_nationkey = fixed.randint(0, n_nation, n_cust)
    customer = {
        "c_custkey": np.arange(1, n_cust + 1),
        "c_name": later(_tag, "Customer#", np.arange(1, n_cust + 1), 9),
        "c_address": later(_tag, "addr", np.arange(n_cust), 0),
        "c_nationkey": c_nationkey,
        "c_phone": later(
            lambda: _tag(pc.binary_join_element_wise(
                pc.cast(pa.array(c_nationkey + 10), _TEXT),
                pa.scalar("-", _TEXT), pa.scalar("", _TEXT)),
                np.arange(n_cust), 8)),
        "c_acctbal": uniform(-999.99, 9999.99, n_cust),
        "c_mktsegment": picked(tpch_gen._SEGMENTS, n_cust),
        "c_comment": later(_blank, n_cust),
    }
    o_dates = fixed.randint(_D("1992-01-01"), _D("1998-08-02"), n_ord)
    o_custkey = fixed.randint(1, n_cust + 1, n_ord)
    o_custkey = o_custkey + (o_custkey % 3 == 0)
    o_custkey = np.where(o_custkey > n_cust, 1, o_custkey)
    o_orderkey = np.arange(1, n_ord + 1) * 4
    orders = {
        "o_orderkey": o_orderkey,
        "o_custkey": o_custkey,
        "o_orderstatus": picked(["F", "O", "P"], n_ord, p=[0.49, 0.49, 0.02]),
        "o_totalprice": uniform(800.0, 600_000.0, n_ord),
        "o_orderdate": later(_days, o_dates),
        "o_orderpriority": picked(tpch_gen._PRIORITIES, n_ord),
        "o_clerk": later(_tag, "Clerk#", np.arange(n_ord) % 1000, 9),
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": later(_blank, n_ord),
    }
    lines_per_order = tpch_gen._lines_per_order(n_ord)
    n_li = int(lines_per_order.sum())
    li_odate = np.repeat(o_dates, lines_per_order)
    ship = li_odate + fixed.randint(1, 122, n_li)
    commit = li_odate + fixed.randint(30, 91, n_li)
    receipt = ship + fixed.randint(1, 31, n_li)
    r_or_a = fixed.choice(2, n_li)
    cutoff = _D("1995-06-17")
    li_partkey = fixed.randint(1, n_part + 1, n_li)

    def linenumber():
        first = np.cumsum(lines_per_order) - lines_per_order
        return np.arange(n_li) - np.repeat(first, lines_per_order) + 1

    lineitem = {
        "l_orderkey": later(np.repeat, o_orderkey, lines_per_order),
        "l_partkey": li_partkey,
        "l_suppkey": later(_psupp, li_partkey, fixed.randint(0, 4, n_li)),
        "l_linenumber": later(linenumber),
        "l_quantity": later(fixed.randint(1, 51, n_li).astype, np.float64),
        "l_extendedprice": uniform(900.0, 105_000.0, n_li),
        "l_discount": later(lambda d: np.round(d / 100.0, 2),
                            fixed.randint(0, 11, n_li)),
        "l_tax": later(lambda t: np.round(t / 100.0, 2),
                       rng.randint(0, 9, n_li)),
        "l_returnflag": later(lambda: _words(
            ["R", "A", "N"], np.where(receipt <= cutoff, r_or_a, 2))),
        "l_linestatus": later(lambda: _words(
            ["F", "O"], (ship > cutoff).astype(np.int64))),
        "l_shipdate": later(_days, ship),
        "l_commitdate": later(_days, commit),
        "l_receiptdate": later(_days, receipt),
        "l_shipinstruct": picked(tpch_gen._INSTRUCTS, n_li),
        "l_shipmode": picked(tpch_gen._SHIPMODES, n_li),
        "l_comment": later(_blank, n_li),
    }

    tables = {
        "region": region, "nation": nation, "supplier": supplier,
        "part": part, "partsupp": partsupp, "customer": customer,
        "orders": orders, "lineitem": lineitem,
    }
    # the draws are one thread's work whatever runs beside them; the
    # columns are built once they are over, by all the workers at once
    todo = [(columns, name) for columns in tables.values()
            for name, made in columns.items() if isinstance(made, later)]
    with ThreadPoolExecutor(_WORKERS) as pool:
        made = pool.map(lambda at: at[0][at[1]].make(), todo)
        for (columns, name), column in zip(todo, list(made)):
            columns[name] = column
        return {name: _frame(columns, pool)
                for name, columns in tables.items()}


def _frame(columns: dict, pool) -> pd.DataFrame:
    """``pd.DataFrame(columns)`` as its constructor lays a frame out, one
    two-dimensional block for the numbers of each dtype and a column each
    for the strings, with the blocks filled by the workers.  (The layout is
    not seen in a frame's values; a reference that takes rows out of six
    columns and goes on computing finds them in a block, which a frame of
    separate columns has to copy them into first: 4 to 8 s a Q1 reference
    at SF10.)"""
    if any(isinstance(column, list) for column in columns.values()):
        return pd.DataFrame(columns)  # region and nation, a few rows
    numbers = {}
    for name, column in columns.items():
        if isinstance(column, np.ndarray):
            numbers.setdefault(column.dtype, []).append(name)
    rows = len(next(iter(columns.values())))
    blocks = {dtype: np.empty((len(names), rows), dtype)
              for dtype, names in numbers.items()}

    def fill(dtype, row, name):
        blocks[dtype][row] = columns[name]

    list(pool.map(lambda at: fill(*at), [
        (dtype, row, name) for dtype, names in numbers.items()
        for row, name in enumerate(names)]))
    parts = [pd.DataFrame(blocks[dtype].T, columns=names, copy=False)
             for dtype, names in numbers.items()]
    strings = {name: column for name, column in columns.items()
               if not isinstance(column, np.ndarray)}
    if strings:
        parts.append(pd.DataFrame(strings, copy=False))
    return pd.concat(parts, axis=1)[list(columns)]


def generate(sf: float, seed: int) -> dict:
    frames = _frames(sf, seed)
    with open(_CONFIG) as f:
        return load_limit.watched(frames,
                                  float(json.load(f)["load_deadline_s"]))
