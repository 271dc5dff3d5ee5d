"""The benchmark's own SSB rows, drawn by dbgen's rules from a seed.

The Star Schema Benchmark's `lineorder`, flattened with its dimensions:
a row draws its part, customer, supplier and order date uniformly, as
SSB's dbgen does, and takes every other column from them.

- part: 200,000 x floor(1 + log2(SF)) parts; a part's brand (and with
  it category and manufacturer) is drawn once a part; its retail price
  is dbgen's `rpb_routine`: 90000 + (partkey / 10) mod 20001 +
  100 x (partkey mod 1000), in cents.
- lo_quantity 1..50 and lo_discount 0..10, uniform.
- lo_extendedprice = lo_quantity x retail price;
  lo_revenue = lo_extendedprice x (100 - lo_discount) / 100;
  lo_supplycost = 6 x retail price / 10 (integer division, as in C).
- customer (30,000 x SF) and supplier (2,000 x SF): a city drawn once a
  key; nation and region follow from the city.
- order date: uniform over 1992-01-01 .. 1998-08-02 (dbgen's
  O_ODATE_MIN .. O_ODATE_MAX), year, month and week follow from it.

SF is rows / 6,000,000. Rows are drawn a SEGMENT at a time, from
`default_rng([seed, segment])`, so that segments can be generated and
built side by side and the parent can regenerate exactly the lanes a
worker built from; the dimension tables come from `default_rng([seed,
10007])`. Numpy only: the parent of a run never touches JAX. dbgen's
own random streams are not reproduced: the rules are, the draws are
the seed's.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATION_REGION = {
    "ALGERIA": "AFRICA", "ETHIOPIA": "AFRICA", "KENYA": "AFRICA",
    "MOROCCO": "AFRICA", "MOZAMBIQUE": "AFRICA",
    "ARGENTINA": "AMERICA", "BRAZIL": "AMERICA", "CANADA": "AMERICA",
    "PERU": "AMERICA", "UNITED STATES": "AMERICA",
    "CHINA": "ASIA", "INDIA": "ASIA", "INDONESIA": "ASIA", "JAPAN": "ASIA",
    "VIETNAM": "ASIA",
    "FRANCE": "EUROPE", "GERMANY": "EUROPE", "ROMANIA": "EUROPE",
    "RUSSIA": "EUROPE", "UNITED KINGDOM": "EUROPE",
    "EGYPT": "MIDDLE EAST", "IRAN": "MIDDLE EAST", "IRAQ": "MIDDLE EAST",
    "JORDAN": "MIDDLE EAST", "SAUDI ARABIA": "MIDDLE EAST",
}
NATIONS = sorted(NATION_REGION)
MONTHS = ["Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep",
          "Oct", "Nov", "Dec"]

#: column -> Pinot data type name, in schema order. The measures are
#: SSB's integers (cents), 4 bytes wide as in the flat-table form
COLUMN_TYPES = {
    "lo_quantity": "INT", "lo_discount": "INT", "lo_revenue": "INT",
    "lo_supplycost": "INT",
    "d_year": "INT", "d_yearmonthnum": "INT", "d_yearmonth": "STRING",
    "d_weeknuminyear": "INT",
    "c_region": "STRING", "c_nation": "STRING", "c_city": "STRING",
    "s_region": "STRING", "s_nation": "STRING", "s_city": "STRING",
    "p_mfgr": "STRING", "p_category": "STRING", "p_brand1": "STRING",
}
METRIC_COLUMNS = ("lo_quantity", "lo_discount", "lo_revenue",
                  "lo_supplycost")
#: columns that reach a segment as a lane of values, not of pool ids
VALUE_COLUMNS = ("lo_revenue", "lo_supplycost")

ORDER_DATE_MIN = np.datetime64("1992-01-01")
ORDER_DATE_MAX = np.datetime64("1998-08-02")


def city_pool() -> np.ndarray:
    """250 cities: nation truncated to 9 characters + a digit, so the
    pool is sorted and city id == nation id * 10 + digit."""
    return np.array([n[:9] + str(d) for n in NATIONS for d in range(10)],
                    dtype=object)


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """dbgen's rpb_routine, in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def scale_factor(rows: int) -> float:
    return rows / 6_000_000


def dimension_sizes(rows: int) -> Dict[str, int]:
    """Rows of part, customer and supplier at the table's scale factor
    (never under SF 1's, which is the least SSB defines)."""
    sf = max(1.0, scale_factor(rows))
    return {"part": 200_000 * int(math.floor(1 + math.log2(sf))),
            "customer": int(30_000 * sf), "supplier": int(2_000 * sf)}


def pools() -> Dict[str, np.ndarray]:
    """Sorted value pools of the columns that are drawn as ids: the
    union of every segment's dictionary."""
    days = np.arange(ORDER_DATE_MIN, ORDER_DATE_MAX + 1)
    months = np.unique(days.astype("datetime64[M]"))
    ym = [(int(str(m)[:4]), int(str(m)[5:7])) for m in months]
    return {
        "lo_quantity": np.arange(1, 51, dtype=np.int64),
        "lo_discount": np.arange(0, 11, dtype=np.int64),
        "d_year": np.arange(1992, 1999, dtype=np.int64),
        "d_yearmonthnum": np.array([y * 100 + m for y, m in ym],
                                   dtype=np.int64),
        "d_yearmonth": np.array(sorted(f"{MONTHS[m - 1]}{y}"
                                       for y, m in ym), dtype=object),
        "d_weeknuminyear": np.arange(1, 54, dtype=np.int64),
        "c_region": np.array(sorted(REGIONS), dtype=object),
        "c_nation": np.array(NATIONS, dtype=object),
        "c_city": city_pool(),
        "s_region": np.array(sorted(REGIONS), dtype=object),
        "s_nation": np.array(NATIONS, dtype=object),
        "s_city": city_pool(),
        "p_mfgr": np.array([f"MFGR#{m}" for m in range(1, 6)], dtype=object),
        "p_category": np.array([f"MFGR#{m}{c}" for m in range(1, 6)
                                for c in range(1, 6)], dtype=object),
        "p_brand1": np.array([f"MFGR#{m}{c}{b:02d}" for m in range(1, 6)
                              for c in range(1, 6) for b in range(1, 41)],
                             dtype=object),
    }


def dimensions(seed: int, rows: int) -> dict:
    """The pools, the dimension tables drawn from the seed (a brand a
    part, a city a customer and a supplier) and the id-domain maps of
    the star schema's functional dependencies."""
    pl = pools()
    sizes = dimension_sizes(rows)
    rng = np.random.default_rng([seed, 10_007])
    regions = list(pl["c_region"])
    days = np.arange(ORDER_DATE_MIN, ORDER_DATE_MAX + 1)
    years = days.astype("datetime64[Y]")
    month_of = (days.astype("datetime64[M]") -
                ORDER_DATE_MIN.astype("datetime64[M]")).astype(np.int64)
    ym_sorted = list(pl["d_yearmonth"])
    return {
        "pools": pl,
        "part_brand": rng.integers(0, 1000, sizes["part"]).astype(np.int16),
        "customer_city": rng.integers(0, 250, sizes["customer"]
                                      ).astype(np.int16),
        "supplier_city": rng.integers(0, 250, sizes["supplier"]
                                      ).astype(np.int16),
        "nation_region": np.array([regions.index(NATION_REGION[n])
                                   for n in pl["c_nation"]], np.int8),
        "day_year": (years - years[0]).astype(np.int64).astype(np.int8),
        "day_month": month_of.astype(np.int8),
        "day_week": ((days - years.astype("datetime64[D]")
                      ).astype(np.int64) // 7).astype(np.int8),
        "month_yearmonth": np.array(
            [ym_sorted.index(f"{MONTHS[int(v) % 100 - 1]}{int(v) // 100}")
             for v in pl["d_yearmonthnum"]], np.int8),
    }


def make_segment(dims: dict, n: int, seed: int, segment: int
                 ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """One segment's lanes -> (pool ids a column, values a value
    column)."""
    rng = np.random.default_rng([seed, segment])
    ids: Dict[str, np.ndarray] = {}
    quantity = rng.integers(1, 51, n, dtype=np.int64)
    discount = rng.integers(0, 11, n, dtype=np.int64)
    ids["lo_quantity"] = (quantity - 1).astype(np.int8)
    ids["lo_discount"] = discount.astype(np.int8)
    partkey = rng.integers(1, len(dims["part_brand"]) + 1, n)
    price = retail_price(partkey)
    values = {
        "lo_revenue": (quantity * price * (100 - discount) // 100
                       ).astype(np.int32),
        "lo_supplycost": (6 * price // 10).astype(np.int32),
    }
    brand = dims["part_brand"][partkey - 1]
    ids["p_brand1"] = brand
    ids["p_category"] = (brand // 40).astype(np.int8)
    ids["p_mfgr"] = (brand // 200).astype(np.int8)
    del partkey, price, quantity, discount
    day = rng.integers(0, len(dims["day_year"]), n)
    month = dims["day_month"][day]
    ids["d_year"] = dims["day_year"][day]
    ids["d_yearmonthnum"] = month
    ids["d_yearmonth"] = dims["month_yearmonth"][month]
    ids["d_weeknuminyear"] = dims["day_week"][day]
    del day
    for side, table in (("c", "customer_city"), ("s", "supplier_city")):
        city = dims[table][rng.integers(0, len(dims[table]), n)]
        nation = (city // 10).astype(np.int8)
        ids[f"{side}_city"] = city
        ids[f"{side}_nation"] = nation
        ids[f"{side}_region"] = dims["nation_region"][nation]
    return ids, values
