"""TPC-H tables for the chip benchmark: dbgen's schema, keys and cardinalities.

A copy of ``benchmarks/tpch.py::generate_tpch`` (PERF.md lists the original
under Open questions) with the change that a seeded benchmark needs:
**the seed changes the measures and nothing else.**  There, every column is
drawn from the caller's seed -- ``lines_per_order`` too, so lineitem's length
and with it every compiled shape, XLA-cache key and program-store key move
with ``--seed``; and so do the dates, flags and keys that predicates and
joins select on, and with them the size of every intermediate result.  The
engine's eager tier, which answers a shape's first arrival, compiles one
small program for each size it meets: 216 of them for the four shapes of
the ``power`` mix, 1.5 s each on the chip, on every seed it has not seen
(PERF.md, Findings, PR 24).

Here the structure of the database -- row counts, keys, dates, flags,
categories, and the quantities and discounts Q6 filters on -- comes from a
stream fixed by ``STRUCTURE_STREAM`` (``LINES_STREAM`` for the lines of an order), so what a predicate or a join selects
is the same at every seed, as it is in dbgen, which makes one database per
scale factor.  The measures that the queries aggregate -- prices, taxes,
balances, supply costs, available quantities -- come from ``seed``: every
seed gives other sums, and the same work.
"""
from __future__ import annotations

import numpy as np
import pandas as pd

#: the streams that fix the database's structure at every seed
LINES_STREAM = 0x7C4
STRUCTURE_STREAM = 0x7C5

_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
_NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
_SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
_INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
_TYPES = [f"{a} {b} {c}" for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE",
                                   "ECONOMY", "PROMO")
          for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
          for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
_CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
               for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM")]

_D = lambda s: (pd.Timestamp(s) - pd.Timestamp("1970-01-01")).days  # noqa: E731


def _tag(prefix: str, nums: np.ndarray, width: int) -> np.ndarray:
    """Vectorized f"{prefix}{num:0{width}d}" (dbgen-style names); the
    per-element Python loop dominated generation time at SF>=1."""
    return (prefix + pd.Series(nums).astype(str).str.zfill(width)).to_numpy()


def _blank(n: int) -> np.ndarray:
    return np.full(n, "", dtype=object)


def _lines_per_order(n_ord: int) -> np.ndarray:
    return np.random.RandomState(LINES_STREAM).randint(1, 8, n_ord)


def cardinalities(sf: float) -> dict:
    """{table: rows} at scale factor ``sf``: no seed enters."""
    n_ord = max(int(1_500_000 * sf), 150)
    n_part = max(int(200_000 * sf), 50)
    lines = _lines_per_order(n_ord)
    return {"region": 5, "nation": len(_NATIONS),
            "supplier": max(int(10_000 * sf), 10), "part": n_part,
            "partsupp": 4 * n_part, "customer": max(int(150_000 * sf), 30),
            "orders": n_ord, "lineitem": int(lines.sum())}


def generate(sf: float, seed: int) -> dict:
    """{table_name: pandas.DataFrame} for the 8 TPC-H tables.  ``seed`` is
    any whole number up to a little over 2**31; numpy's generator takes
    32 unsigned bits."""
    rng = np.random.RandomState(seed % (1 << 32))
    fixed = np.random.RandomState(STRUCTURE_STREAM)
    n_part = max(int(200_000 * sf), 50)
    n_supp = max(int(10_000 * sf), 10)
    n_cust = max(int(150_000 * sf), 30)
    n_ord = max(int(1_500_000 * sf), 150)
    n_nation = len(_NATIONS)

    region = pd.DataFrame({
        "r_regionkey": np.arange(5), "r_name": _REGIONS,
        "r_comment": ["" for _ in range(5)],
    })
    nation = pd.DataFrame({
        "n_nationkey": np.arange(n_nation),
        "n_name": [n for n, _ in _NATIONS],
        "n_regionkey": [r for _, r in _NATIONS],
        "n_comment": ["" for _ in range(n_nation)],
    })
    supplier = pd.DataFrame({
        "s_suppkey": np.arange(1, n_supp + 1),
        "s_name": _tag("Supplier#", np.arange(1, n_supp + 1), 9),
        "s_address": _tag("addr", np.arange(n_supp), 0),
        "s_nationkey": fixed.randint(0, n_nation, n_supp),
        "s_phone": _tag("", np.arange(n_supp), 10),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_supp), 2),
        "s_comment": _blank(n_supp),
    })
    part = pd.DataFrame({
        "p_partkey": np.arange(1, n_part + 1),
        "p_name": fixed.choice(["ivory blue", "green navy", "red linen",
                              "metallic olive", "antique puff"], n_part),
        "p_mfgr": _tag("Manufacturer#", np.arange(n_part) % 5 + 1, 0),
        # dbgen brands are "Brand#MN" with independent M,N in 1..5 — Q17/Q19
        # filter on Brand#23/12/34, which must actually exist in the data
        "p_brand": _tag("Brand#", (np.arange(n_part) % 5 + 1) * 10
                        + (np.arange(n_part) // 5) % 5 + 1, 0),
        "p_type": fixed.choice(_TYPES, n_part),
        "p_size": fixed.randint(1, 51, n_part),
        "p_container": fixed.choice(_CONTAINERS, n_part),
        "p_retailprice": np.round(900 + (np.arange(1, n_part + 1) % 1000) / 10.0
                                  + 100 * (np.arange(1, n_part + 1) % 10), 2),
        "p_comment": _blank(n_part),
    })
    n_ps = n_part * 4
    # dbgen invariant: (ps_partkey, ps_suppkey) is a primary key — each part
    # gets 4 DISTINCT suppliers via a strided formula, and lineitem picks
    # its supplier from the part's four (so l_partkey/l_suppkey pairs exist
    # in partsupp; Q9's two-key join depends on both properties)
    _ps_step = max(n_supp // 4, 1)

    def _psupp(partkey, i):
        return (partkey - 1 + i * _ps_step) % n_supp + 1

    partsupp = pd.DataFrame({
        "ps_partkey": np.repeat(np.arange(1, n_part + 1), 4),
        "ps_suppkey": _psupp(np.repeat(np.arange(1, n_part + 1), 4),
                             np.tile(np.arange(4), n_part)),
        "ps_availqty": rng.randint(1, 10_000, n_ps),
        "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, n_ps), 2),
        "ps_comment": _blank(n_ps),
    })
    c_nationkey = fixed.randint(0, n_nation, n_cust)
    customer = pd.DataFrame({
        "c_custkey": np.arange(1, n_cust + 1),
        "c_name": _tag("Customer#", np.arange(1, n_cust + 1), 9),
        "c_address": _tag("addr", np.arange(n_cust), 0),
        "c_nationkey": c_nationkey,
        # dbgen phones start with the country code nationkey+10 (10..34):
        # Q22 filters SUBSTRING(c_phone,1,2) IN ('13','31',...) and must
        # actually select customers
        "c_phone": _tag(pd.Series(c_nationkey + 10).astype(str) + "-",
                        np.arange(n_cust), 8),
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_cust), 2),
        "c_mktsegment": fixed.choice(_SEGMENTS, n_cust),
        "c_comment": _blank(n_cust),
    })
    o_dates = fixed.randint(_D("1992-01-01"), _D("1998-08-02"), n_ord)
    # dbgen: customers with custkey % 3 == 0 never place orders — Q22's
    # NOT EXISTS(orders) anti-join needs a real population to select
    o_custkey = fixed.randint(1, n_cust + 1, n_ord)
    o_custkey = o_custkey + (o_custkey % 3 == 0)
    o_custkey = np.where(o_custkey > n_cust, 1, o_custkey)
    orders = pd.DataFrame({
        "o_orderkey": np.arange(1, n_ord + 1) * 4,  # dbgen sparse keys
        "o_custkey": o_custkey,
        "o_orderstatus": fixed.choice(["F", "O", "P"], n_ord, p=[0.49, 0.49, 0.02]),
        "o_totalprice": np.round(rng.uniform(800.0, 600_000.0, n_ord), 2),
        "o_orderdate": pd.to_datetime(o_dates, unit="D"),
        "o_orderpriority": fixed.choice(_PRIORITIES, n_ord),
        "o_clerk": _tag("Clerk#", np.arange(n_ord) % 1000, 9),
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": _blank(n_ord),
    })
    lines_per_order = _lines_per_order(n_ord)
    n_li = int(lines_per_order.sum())
    li_order = np.repeat(orders["o_orderkey"].to_numpy(), lines_per_order)
    li_odate = np.repeat(o_dates, lines_per_order)
    ship_delay = fixed.randint(1, 122, n_li)
    ship = li_odate + ship_delay
    commit = li_odate + fixed.randint(30, 91, n_li)
    receipt = ship + fixed.randint(1, 31, n_li)
    returnflag = np.where(receipt <= _D("1995-06-17"),
                          fixed.choice(["R", "A"], n_li), "N")
    lineitem = pd.DataFrame({
        "l_orderkey": li_order,
        "l_partkey": (li_partkey := fixed.randint(1, n_part + 1, n_li)),
        "l_suppkey": _psupp(li_partkey, fixed.randint(0, 4, n_li)),
        "l_linenumber": np.arange(n_li) - np.repeat(np.cumsum(lines_per_order) - lines_per_order, lines_per_order) + 1,
        "l_quantity": fixed.randint(1, 51, n_li).astype(np.float64),
        "l_extendedprice": np.round(rng.uniform(900.0, 105_000.0, n_li), 2),
        "l_discount": np.round(fixed.randint(0, 11, n_li) / 100.0, 2),
        "l_tax": np.round(rng.randint(0, 9, n_li) / 100.0, 2),
        "l_returnflag": returnflag,
        "l_linestatus": np.where(ship > _D("1995-06-17"), "O", "F"),
        "l_shipdate": pd.to_datetime(ship, unit="D"),
        "l_commitdate": pd.to_datetime(commit, unit="D"),
        "l_receiptdate": pd.to_datetime(receipt, unit="D"),
        "l_shipinstruct": fixed.choice(_INSTRUCTS, n_li),
        "l_shipmode": fixed.choice(_SHIPMODES, n_li),
        "l_comment": _blank(n_li),
    })
    return {
        "region": region, "nation": nation, "supplier": supplier,
        "part": part, "partsupp": partsupp, "customer": customer,
        "orders": orders, "lineitem": lineitem,
    }
