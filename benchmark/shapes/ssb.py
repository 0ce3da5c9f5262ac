"""The call shape `ssb`: one stream of the Star Schema Benchmark's thirteen
queries (P. O'Neil et al., "Star Schema Benchmark, Revision 3") as PQL on
a bitmap index of LINEORDER, in the specification's order, sixteen calls
a request:

    Q1.1-Q1.3  GroupBy(Rows(lo_discount), filter=<date, discount window,
               lo_quantity range>, aggregate=Sum(field=lo_extendedprice));
               SSB's revenue is the sum over the groups of discount x sum
    Q2.1-Q2.3  GroupBy(Rows(d_year), Rows(p_brand1), filter=<part, supplier
               region>, aggregate=Sum(field=lo_revenue))
    Q3.1       GroupBy(Rows(c_nation), Rows(s_nation), Rows(d_year), ...)
    Q3.2-Q3.4  GroupBy(Rows(c_city), Rows(s_city), Rows(d_year), ...)
    Q4.1       GroupBy(Rows(d_year), Rows(c_nation), ...)
    Q4.2       GroupBy(Rows(d_year), Rows(s_nation), Rows(p_category), ...)
    Q4.3       GroupBy(Rows(d_year), Rows(s_city), Rows(p_brand1), ...),
               its customer region the supplier nation's

every Q4.x twice, with aggregate=Sum(field=lo_revenue) and with
aggregate=Sum(field=lo_supplycost): the profit is their difference, taken
by the client. A range of years or brands is a Union of rows. The
constants of every query are drawn afresh for each request, over the
domain their column has and with the query's filter factor
(configs/ssb-sf100-1chip.json, `assumed`).

A call is (query, aggregate field, constants). Its answer, as the
server's JSON gives it: the non-empty groups in odometer order (the last
field fastest), each {"group": [{"field", "rowID"}, ...], "count",
"sum"}.

The reference is plain numpy over the draws: per shard, `np.bincount`
counts and sums of the lineorder rows over the dimension combinations the
templates read (one table a family of queries, the lineorder rows as
drawn: the dimensions' rows from their bits or positions, the measures
decoded here from their planes), summed over the shards; a query's answer is a
slice of its table. `compare` is exact; its error is the largest absolute
difference of a count or a sum, a missing group counting whole.
"""

from __future__ import annotations

import numpy as np

CALLS_PER_STREAM = 16
REVENUE, SUPPLYCOST, EXTENDEDPRICE = "lo_revenue", "lo_supplycost", "lo_extendedprice"
#: The stream, in the specification's order: (query, aggregate field).
STREAM = (
    ("q1.1", EXTENDEDPRICE), ("q1.2", EXTENDEDPRICE), ("q1.3", EXTENDEDPRICE),
    ("q2.1", REVENUE), ("q2.2", REVENUE), ("q2.3", REVENUE),
    ("q3.1", REVENUE), ("q3.2", REVENUE), ("q3.3", REVENUE), ("q3.4", REVENUE),
    ("q4.1", REVENUE), ("q4.1", SUPPLYCOST),
    ("q4.2", REVENUE), ("q4.2", SUPPLYCOST),
    ("q4.3", REVENUE), ("q4.3", SUPPLYCOST),
)
GROUPS = {
    "q1": ("lo_discount",),
    "q2": ("d_year", "p_brand1"),
    "q3.1": ("c_nation", "s_nation", "d_year"),
    "q3": ("c_city", "s_city", "d_year"),
    "q4.1": ("d_year", "c_nation"),
    "q4.2": ("d_year", "s_nation", "p_category"),
    "q4.3": ("d_year", "s_city", "p_brand1"),
}


def grouping(query: str) -> tuple:
    return GROUPS.get(query) or GROUPS[query[:2]]


# -- traffic ---------------------------------------------------------------


def _constants(rng) -> dict:
    """One stream's constants, each uniform over its domain (rows as the
    draw numbers them: years 0..6 for 1992..1998, months 0..83)."""
    r = lambda lo, hi: int(rng.integers(lo, hi + 1))  # noqa: E731
    nation = r(0, 24)
    x, z = sorted(rng.choice(10, 2, replace=False).tolist())
    return {
        "q1.1": (r(1, 5), r(1, 8)),
        "q1.2": (r(12, 71), r(1, 8), r(1, 41)),
        "q1.3": (r(1, 5), r(0, 51), r(1, 8), r(1, 41)),
        "q2.1": (r(0, 24), r(0, 4)),
        "q2.2": (r(0, 24), r(0, 32), r(0, 4)),
        "q2.3": (r(0, 999), r(0, 4)),
        "q3.1": (r(0, 4), r(0, 1)),
        "q3.2": (r(0, 24), r(0, 1)),
        "q3.3": (nation, x, z, r(0, 1)),
        "q3.4": (nation, x, z, r(0, 71)),
        "q4.1": (r(0, 4), r(0, 3)),
        "q4.2": (r(0, 4), r(0, 5), r(0, 3)),
        "q4.3": (r(0, 24), r(0, 5), r(0, 24)),
    }


def stream(rng) -> list:
    """The sixteen calls of one stream."""
    consts = _constants(rng)
    return [(query, agg, consts[query]) for query, agg in STREAM]


def draw(group: dict, config: dict, rng, n: int):
    """Yield the group's next n calls: whole streams, their constants
    drawn from the client's RNG."""
    if int(group["calls_per_request"]) != CALLS_PER_STREAM:
        raise ValueError(
            f"a request of shape ssb is one stream of {CALLS_PER_STREAM} calls"
        )
    for _ in range(-(-n // CALLS_PER_STREAM)):
        yield from stream(rng)


def _union(field: str, rows) -> str:
    rows = list(rows)
    if len(rows) == 1:
        return f"Row({field}={rows[0]})"
    return "Union(" + ", ".join(f"Row({field}={r})" for r in rows) + ")"


def _filter(query: str, p: tuple) -> str:
    """The filter of a call, as PQL."""
    if query == "q1.1":
        year, d = p
        parts = [f"Row(d_year={year})", _union("lo_discount", range(d, d + 3)),
                 "Row(lo_quantity < 25)"]
    elif query == "q1.2":
        month, d, q = p
        parts = [f"Row(d_yearmonthnum={month})",
                 _union("lo_discount", range(d, d + 3)),
                 f"Row({q} <= lo_quantity <= {q + 9})"]
    elif query == "q1.3":
        year, week, d, q = p
        parts = [f"Row(d_weeknuminyear={week})", f"Row(d_year={year})",
                 _union("lo_discount", range(d, d + 3)),
                 f"Row({q} <= lo_quantity <= {q + 9})"]
    elif query == "q2.1":
        cat, region = p
        parts = [f"Row(p_category={cat})", f"Row(s_region={region})"]
    elif query == "q2.2":
        cat, b0, region = p
        parts = [_union("p_brand1", range(40 * cat + b0, 40 * cat + b0 + 8)),
                 f"Row(s_region={region})"]
    elif query == "q2.3":
        brand, region = p
        parts = [f"Row(p_brand1={brand})", f"Row(s_region={region})"]
    elif query == "q3.1":
        region, y0 = p
        parts = [f"Row(c_region={region})", f"Row(s_region={region})",
                 _union("d_year", range(y0, y0 + 6))]
    elif query == "q3.2":
        nation, y0 = p
        parts = [f"Row(c_nation={nation})", f"Row(s_nation={nation})",
                 _union("d_year", range(y0, y0 + 6))]
    elif query in ("q3.3", "q3.4"):
        nation, x, z, when = p
        cities = (10 * nation + x, 10 * nation + z)
        parts = [_union("c_city", cities), _union("s_city", cities),
                 _union("d_year", range(when, when + 6)) if query == "q3.3"
                 else f"Row(d_yearmonthnum={when})"]
    elif query == "q4.1":
        region, m0 = p
        parts = [f"Row(c_region={region})", f"Row(s_region={region})",
                 _union("p_mfgr", (m0, m0 + 1))]
    elif query == "q4.2":
        region, y0, m0 = p
        parts = [f"Row(c_region={region})", f"Row(s_region={region})",
                 _union("d_year", (y0, y0 + 1)), _union("p_mfgr", (m0, m0 + 1))]
    elif query == "q4.3":
        nation, y0, cat = p
        parts = [f"Row(c_region={nation // 5})", f"Row(s_nation={nation})",
                 _union("d_year", (y0, y0 + 1)), f"Row(p_category={cat})"]
    else:
        raise ValueError(f"no SSB query {query!r}")
    return "Intersect(" + ", ".join(parts) + ")"


def pql(call) -> str:
    query, agg, p = call
    rows = ", ".join(f"Rows({f})" for f in grouping(query))
    return (f"GroupBy({rows}, filter={_filter(query, p)}, "
            f"aggregate=Sum(field={agg}))")


def render(calls) -> bytes:
    return "".join(pql(c) for c in calls).encode()


def warm(group: dict, config: dict, seed: int, client: int, send, say) -> None:
    """Nothing beyond the group's first request, which is a whole stream:
    it builds the stacks and compiles the programs of every call (a
    group_sum program serves any number of live combinations). A server
    that answers without sums fails `compare`."""


# -- reference -------------------------------------------------------------

#: Per shard, (table: its axes). The last axis holds a count and the
#: sums the family's queries ask.
TABLES = {
    "q1m": ("d_yearmonthnum", "lo_discount", "lo_quantity"),
    "q1w": ("d_year", "d_weeknuminyear", "lo_discount", "lo_quantity"),
    "q2": ("d_year", "p_brand1", "s_region"),
    "q3n": ("c_nation", "s_nation", "d_year"),
    "q3c": ("c_nation", "c_city10", "s_city10", "d_yearmonthnum"),
    "q41": ("d_year", "c_nation", "s_region", "p_mfgr"),
    "q42": ("d_year", "s_nation", "p_category", "c_region"),
    "q43": ("d_year", "s_city", "p_brand1"),
}
SUMS = {"q1m": (EXTENDEDPRICE,), "q1w": (EXTENDEDPRICE,), "q2": (REVENUE,),
        "q3n": (REVENUE,), "q3c": (REVENUE,), "q41": (REVENUE, SUPPLYCOST),
        "q42": (REVENUE, SUPPLYCOST), "q43": (REVENUE, SUPPLYCOST)}
AXIS_SIZE = {"d_year": 7, "d_yearmonthnum": 84, "d_weeknuminyear": 53,
             "lo_discount": 11, "lo_quantity": 50, "p_brand1": 1000,
             "p_category": 25, "p_mfgr": 5, "s_region": 5, "c_region": 5,
             "c_nation": 25, "s_nation": 25, "s_city": 250, "c_city10": 10,
             "s_city10": 10}


def tables_needed(groups: list[dict], config: dict) -> list[str]:
    return list(TABLES)


def _attribute(data, field: str, width: int) -> np.ndarray:
    """A mutex field's row id at every column of the shard, from its
    drawn bits (bool[rows, width]) or positions ((rows, columns))."""
    have = data.field(field)
    if isinstance(have, np.ndarray):
        out = np.where(have.any(axis=0), have.argmax(axis=0), -1)
    else:
        rows, cols = have
        out = np.full(width, -1, dtype=np.int64)
        out[np.asarray(cols)] = rows
    if (out < 0).any():
        raise ValueError(f"{field}: a column in no row")
    return out.astype(np.int64)


def _measure(data, field: str) -> np.ndarray:
    """An int field's value at every column, decoded from its drawn
    planes (row 0 exists, row 1 sign, rows 2.. the bits, lowest first)."""
    planes = data.bits(field)
    vals = np.zeros(planes.shape[1], dtype=np.int64)
    for i in range(planes.shape[0] - 2):
        vals += planes[2 + i].astype(np.int64) << i
    if not planes[0].all():
        raise ValueError(f"{field}: a lineorder row without a value")
    return np.where(planes[1], -vals, vals)


def shard_tables(config: dict, names: list[str], data) -> dict:
    """{name: uint32[axes..., 1 + sums]} of one shard: the lineorder
    rows' count, and their sums, over each combination of the axes."""
    width = config["shard_width"]
    cols: dict = {}

    def col(name):
        if name not in cols:
            if name.startswith("lo_") and name != "lo_discount":
                cols[name] = _measure(data, name)
            elif name.endswith("10"):  # a city within its nation
                cols[name] = col(name[:-2]) % 10
            else:
                cols[name] = _attribute(data, name, width)
        return cols[name]

    out = {}
    for name in names:
        axes = TABLES[name]
        # q3c: the rows whose customer and supplier share a nation; q43:
        # those whose customer is in the supplier's region.
        if name == "q3c":
            keep = col("c_nation") == col("s_nation")
        elif name == "q43":
            keep = col("c_region") == col("s_city") // 50
        else:
            keep = slice(None)
        sizes = [AXIS_SIZE[a] for a in axes]
        key = np.zeros(width, dtype=np.int64)
        for a, n in zip(axes, sizes):
            key = key * n + (col(a) - 1 if a == "lo_quantity" else col(a))
        key = key[keep]
        cells = int(np.prod(sizes))
        parts = [np.bincount(key, minlength=cells)]
        for s in SUMS[name]:
            parts.append(np.rint(np.bincount(
                key, weights=col(s)[keep].astype(np.float64), minlength=cells
            )).astype(np.int64))
        table = np.stack(parts, axis=-1).reshape(*sizes, len(parts))
        if table.min() < 0 or table.max() >= 1 << 32:
            raise ValueError(f"{name}: a shard's count or sum outgrows uint32")
        out[name] = table.astype(np.uint32)
    return out


def over_shards(tables: dict) -> dict:
    """The shards' tables summed, in int64."""
    return {k: v.sum(axis=0, dtype=np.int64) for k, v in tables.items()}


def _groups(fields, block: np.ndarray, rows, agg_at: int) -> list[dict]:
    """The non-empty cells of block[..., (count, sums...)] in odometer
    order; `rows[i]` the row id of each position of axis i."""
    out = []
    for at in zip(*np.nonzero(block[..., 0])):
        out.append({
            "group": [{"field": f, "rowID": int(r[i])}
                      for f, r, i in zip(fields, rows, at)],
            "count": int(block[at][0]),
            "sum": int(block[at][agg_at]),
        })
    return out


def _by_year(months: np.ndarray, first: int, years: int) -> np.ndarray:
    """[..., 84 months, k] summed into [..., years, k] from year `first`."""
    shape = months.shape[:-2] + (7, 12) + months.shape[-1:]
    return months.reshape(shape).sum(axis=-2)[..., first:first + years, :]


def answer(config: dict, totals: dict, call):
    """`totals` are the tables summed over the index's shards."""
    query, agg, p = call
    fields = grouping(query)
    at = 1 + SUMS[TABLE_OF[query]].index(agg)
    t = totals[TABLE_OF[query]]
    if query == "q1.1":
        year, d = p
        block = t[12 * year:12 * year + 12, d:d + 3, :24].sum(axis=(0, 2))
        return _groups(fields, block, [range(d, d + 3)], at)
    if query == "q1.2":
        month, d, q = p
        block = t[month, d:d + 3, q - 1:q + 9].sum(axis=1)
        return _groups(fields, block, [range(d, d + 3)], at)
    if query == "q1.3":
        year, week, d, q = p
        block = t[year, week, d:d + 3, q - 1:q + 9].sum(axis=1)
        return _groups(fields, block, [range(d, d + 3)], at)
    if query in ("q2.1", "q2.2", "q2.3"):
        if query == "q2.1":
            brands = range(40 * p[0], 40 * p[0] + 40)
        elif query == "q2.2":
            brands = range(40 * p[0] + p[1], 40 * p[0] + p[1] + 8)
        else:
            brands = range(p[0], p[0] + 1)
        block = t[:, brands.start:brands.stop, p[-1]]
        return _groups(fields, block, [range(7), brands], at)
    if query == "q3.1":
        region, y0 = p
        nations = range(5 * region, 5 * region + 5)
        block = t[nations.start:nations.stop, nations.start:nations.stop,
                  y0:y0 + 6]
        return _groups(fields, block, [nations, nations, range(y0, y0 + 6)], at)
    if query in ("q3.2", "q3.3", "q3.4"):
        nation = p[0]
        within = list(range(10)) if query == "q3.2" else [p[1], p[2]]
        months = t[nation][np.ix_(within, within)]  # [c, s, 84, k]
        if query == "q3.4":
            year = p[3] // 12
            block = months[:, :, p[3]][:, :, None]
            years = [year]
        else:
            y0 = p[-1]
            block = _by_year(months, y0, 6)
            years = list(range(y0, y0 + 6))
        cities = [10 * nation + c for c in within]
        return _groups(fields, block, [cities, cities, years], at)
    if query == "q4.1":
        region, m0 = p
        nations = range(5 * region, 5 * region + 5)
        block = t[:, nations.start:nations.stop, region, m0:m0 + 2].sum(axis=2)
        return _groups(fields, block, [range(7), nations], at)
    if query == "q4.2":
        region, y0, m0 = p
        nations = range(5 * region, 5 * region + 5)
        cats = range(5 * m0, 5 * m0 + 10)
        block = t[y0:y0 + 2, nations.start:nations.stop, cats.start:cats.stop,
                  region]
        return _groups(fields, block, [range(y0, y0 + 2), nations, cats], at)
    nation, y0, cat = p
    cities = range(10 * nation, 10 * nation + 10)
    brands = range(40 * cat, 40 * cat + 40)
    block = t[y0:y0 + 2, cities.start:cities.stop, brands.start:brands.stop]
    return _groups(fields, block, [range(y0, y0 + 2), cities, brands], at)


TABLE_OF = {"q1.1": "q1m", "q1.2": "q1m", "q1.3": "q1w", "q2.1": "q2",
            "q2.2": "q2", "q2.3": "q2", "q3.1": "q3n", "q3.2": "q3c",
            "q3.3": "q3c", "q3.4": "q3c", "q4.1": "q41", "q4.2": "q42",
            "q4.3": "q43"}


def _keyed(result) -> dict:
    """A result as {(group, "count" | "sum"): number}. Raises where it has
    another form."""
    out = {}
    for item in result:
        key = tuple((g["field"], int(g["rowID"])) for g in item["group"])
        out[key + ("count",)] = int(item["count"])
        out[key + ("sum",)] = int(item["sum"])
    return out


def compare(got, want):
    """(the returned result equals the reference's; the largest absolute
    error of a group's count or sum, a missing group counting whole,
    where the result has the form of an answer)."""
    try:
        have, ref = _keyed(got), _keyed(want)
    except (KeyError, TypeError, ValueError):
        return False, None
    err = max(
        (abs(have.get(k, 0) - ref.get(k, 0)) for k in have.keys() | ref.keys()),
        default=0,
    )
    return got == want, err
