"""SSB's LINEORDER fact table, one column a lineorder row, every dimension
attribute a mutex field on it (denormalised, as a bitmap index models a
star schema): the draw of the tall mutex fields of
`configs/ssb-sf100-1chip.json` (c_city, s_city, p_brand1), and the rows
every other field of the configuration reads (`ssb_bits.py`).

A shard's columns are lineorder rows, each drawn as SSB's `dbgen` makes
them (P. O'Neil et al., "Star Schema Benchmark, Revision 3"): a customer,
a supplier and a part key uniform over the scale factor's tables
(30,000, 2,000 and 200,000 x (1 + floor(log2 SF)) rows a unit of SF), an
order date uniform over 1992-01-01 .. 1998-08-02, a quantity uniform over
1..50 and a discount over 0..10. A dimension row's attributes are
functions of its key, drawn once for the seed: a customer's and a
supplier's city uniform over 250, whose nation is city // 10 and region
nation // 5; a part's brand uniform over 1,000, whose category is
brand // 40 and manufacturer category // 5; the part's retail price by
TPC-H's formula from its key, in cents. The measures follow
(`ssb_bits.py`): extendedprice = quantity x price, revenue =
extendedprice x (100 - discount) / 100, supplycost = 6 x price / 10.

Row ids: d_year = year - 1992; d_yearmonthnum = 12 (year - 1992) + month
- 1; d_weeknuminyear = week - 1 (day of the year // 7, weeks 1..53);
lo_discount the discount; the dimensions' ids as above.

A field's spec gives `sf`, the scale factor, and its `rows`. Every field
of a shard derives from the same columns, whose RNG key is [seed, shard,
LINEORDER] and not the field's position, so the fields of one shard agree.
Shipped as positions (`(rows, columns)`, one a column): a dense draw of
p_brand1 would be bool[1000, 2^20] a shard, and a row of a city or a brand
holds a few thousand of a shard's columns, so that the loader's slices of
whole rows stay small.
"""

import numpy as np

SHIP = "positions"

#: The RNG streams of the seed: the fact table's, and each dimension's.
LINEORDER, CUSTOMER, SUPPLIER, PART = 0x4C4F, 0x4355, 0x5355, 0x5041
FIRST_DAY, LAST_DAY = np.datetime64("1992-01-01"), np.datetime64("1998-08-02")
CITIES, BRANDS = 250, 1000


def options(spec: dict) -> dict:
    """The body of the request that creates the field."""
    return {"options": {"type": "mutex"}}


def table_sizes(sf: int) -> tuple[int, int, int]:
    """(customers, suppliers, parts) at scale factor sf."""
    return 30_000 * sf, 2_000 * sf, 200_000 * (1 + int(np.floor(np.log2(sf))))


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """TPC-H's p_retailprice of 1-based part keys, in cents:
    90000 + (key / 10) mod 20001 + 100 x (key mod 1000)."""
    k = partkey.astype(np.int64)
    return 90_000 + (k // 10) % 20_001 + 100 * (k % 1_000)


_KEPT: dict = {}


def lineorder(seed: int, shard: int, width: int, sf: int) -> dict:
    """The shard's lineorder rows as columns: {attribute: int array of
    `width`}, the dimension attributes as row ids. Kept for the last
    shard asked, since a shard's fields are drawn one after another."""
    key = (seed, shard, width, sf)
    if _KEPT.get("key") != key:
        _KEPT.clear()
        _KEPT.update(key=key, cols=_draw(seed, shard, width, sf))
    return _KEPT["cols"]


def _dimension(seed: int, which: int, n: int, values: int) -> np.ndarray:
    """An attribute of every row of a dimension, uniform, by key - 1."""
    return np.random.default_rng([seed, which]).integers(0, values, n).astype(np.int16)


def _draw(seed: int, shard: int, width: int, sf: int) -> dict:
    customers, suppliers, parts = table_sizes(sf)
    rng = np.random.default_rng([seed, shard, LINEORDER])
    cust = rng.integers(0, customers, width)
    supp = rng.integers(0, suppliers, width)
    part = rng.integers(0, parts, width)
    days = int((LAST_DAY - FIRST_DAY).astype(np.int64)) + 1
    date = FIRST_DAY + rng.integers(0, days, width).astype("timedelta64[D]")
    quantity = rng.integers(1, 51, width)
    discount = rng.integers(0, 11, width)
    c_city = _dimension(seed, CUSTOMER, customers, CITIES)[cust]
    s_city = _dimension(seed, SUPPLIER, suppliers, CITIES)[supp]
    brand = _dimension(seed, PART, parts, BRANDS)[part]
    year = date.astype("datetime64[Y]")
    month = date.astype("datetime64[M]").astype(np.int64) % 12
    day_of_year = (date - year).astype(np.int64)
    year = year.astype(np.int64) + 1970 - 1992
    price = retail_price(part + 1)
    extendedprice = quantity * price
    return {
        "d_year": year,
        "d_yearmonthnum": 12 * year + month,
        "d_weeknuminyear": day_of_year // 7,
        "lo_discount": discount,
        "c_city": c_city, "c_nation": c_city // 10, "c_region": c_city // 50,
        "s_city": s_city, "s_nation": s_city // 10, "s_region": s_city // 50,
        "p_brand1": brand, "p_category": brand // 40, "p_mfgr": brand // 200,
        "lo_quantity": quantity,
        "lo_extendedprice": extendedprice,
        "lo_revenue": extendedprice * (100 - discount) // 100,
        "lo_supplycost": 6 * price // 10,
    }


def draw(config: dict, seed: int, shard: int, field: str):
    """(rows, columns) of one shard of a mutex field: the column's row id
    at every column of the shard, in column order."""
    spec = config["fields"][field]
    width = config["shard_width"]
    rows = lineorder(seed, shard, width, spec["sf"])[field]
    if int(rows.max()) >= spec["rows"]:
        raise ValueError(f"{field}: a row id past the field's {spec['rows']} rows")
    return rows.astype(np.int32), np.arange(width, dtype=np.int32)
