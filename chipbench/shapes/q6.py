"""TPC-H Q6, forecasting revenue change: one scan of lineitem, four range
predicates, one SUM of a product.  No join, no group-by: the shape in which
the device does least and the host path (parse, plan, parameter binding,
dispatch, fetch) does most."""
import datetime

import pandas as pd

NAME = "q6"

SQL = """
    SELECT SUM(l_extendedprice * l_discount) AS revenue
    FROM lineitem
    WHERE l_shipdate >= DATE '{date_from}'
      AND l_shipdate < DATE '{date_to}'
      AND l_discount BETWEEN {discount_low} AND {discount_high}
      AND l_quantity < {quantity}
"""

#: what the scan has to read: every row of these columns, once
SCAN_COLUMNS = {"lineitem": ("l_shipdate", "l_discount", "l_quantity",
                             "l_extendedprice")}

#: TPC-H cl.2.4.6.3: DISCOUNT 0.02..0.09, QUANTITY 24..25, and DATE the
#: first of January of 1993..1997.  That is 80 texts, and the engine's
#: result cache replays a text it has seen; the short cell issues thousands
#: in a window.  So DATE is any day from 1993-01-01 to 1997-01-01 (the
#: year that follows it stays inside the data): 1462 x 8 x 2 texts.
_DAYS = 1462
SPACE = _DAYS * 8 * 2
#: the spec's validation parameters (1994-01-01, DISCOUNT 0.06, QUANTITY 24): every run's first text
FIRST = 6213


def _a_year_on(day: datetime.date) -> datetime.date:
    if (day.month, day.day) == (2, 29):
        day = day.replace(day=28)
    return day.replace(year=day.year + 1)


def params_at(i: int) -> dict:
    """The ``i``-th of the ``SPACE`` distinct parameter sets."""
    day, rest = i % _DAYS, i // _DAYS
    discount, quantity = 2 + rest % 8, 24 + rest // 8
    start = datetime.date(1993, 1, 1) + datetime.timedelta(days=day)
    return {"date_from": start.isoformat(),
            "date_to": _a_year_on(start).isoformat(),
            "discount_low": f"{(discount - 1) / 100:.2f}",
            "discount_high": f"{(discount + 1) / 100:.2f}",
            "quantity": quantity}


def sql(params: dict) -> str:
    return SQL.format(**params)


def reference(frames: dict, date_from, date_to, discount_low, discount_high,
              quantity) -> pd.DataFrame:
    li = frames["lineitem"]
    # l_discount holds whole hundredths; compare as such, as SQL's decimal
    # literals do, and not through the nearest doubles
    hundredths = (li["l_discount"] * 100).round()
    x = li[(li["l_shipdate"] >= pd.Timestamp(date_from))
           & (li["l_shipdate"] < pd.Timestamp(date_to))
           & (hundredths >= round(float(discount_low) * 100))
           & (hundredths <= round(float(discount_high) * 100))
           & (li["l_quantity"] < quantity)]
    revenue = (x["l_extendedprice"] * x["l_discount"]).sum() if len(x) \
        else float("nan")
    return pd.DataFrame({"revenue": [revenue]})
