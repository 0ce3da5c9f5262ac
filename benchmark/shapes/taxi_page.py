"""The call shape `taxi_page`: the four benchmark queries of the
"Transportation" example (the billion-taxi-rides data set), asked together
as one page of a dashboard, thirteen calls a request, in this order:

    TopN(cab_type)                                         rides per cab type
    Sum(Row(passenger_count=k), field=total_amount)        k = 0 .. 9: the
                                    average amount per passenger count
    GroupBy(Rows(passenger_count), Rows(pickup_year))
    GroupBy(Rows(pickup_year), Rows(passenger_count), Rows(dist_miles))

The page has no parameter, as in the source: every client asks the same
thirteen calls. A group of this shape has no keys of its own, and its
`calls_per_request` is 13 (a whole page a request).

A call is (kind, k): ("topn", 0), ("sum", k), ("groupby2", 0),
("groupby3", 0). Answers, as the server's JSON gives them: TopN, the
non-empty rows as {"id", "count"} by count descending, ties by id
ascending; Sum, {"value", "count"}; GroupBy, the non-empty groups as
{"group": [{"field", "rowID"}, ...], "count"} in odometer order (the last
field fastest).

The reference is plain numpy over the draws themselves. Per shard: the
column counts of cab_type's rows; (sum, count) of total_amount under each
row of passenger_count, the amounts decoded here from the drawn planes
(row 0 exists, row 1 sign, rows 2.. the magnitude's bits, lowest first; a
column without the exists bit holds no value, whatever its other bits);
the counts of columns in a row of each of two, and of three, fields. A
count of columns in several rows is the sum of the products of the rows'
bits, whatever the fields' types: nothing here assumes a column sits in
one row only.
"""

from __future__ import annotations

import numpy as np

TOPN_FIELD = "cab_type"
SUM_FILTER = "passenger_count"
SUM_FIELD = "total_amount"
GROUPBY2 = ("passenger_count", "pickup_year")
GROUPBY3 = ("pickup_year", "passenger_count", "dist_miles")

#: Columns multiplied at once in `group_counts`: 0/1 products summed in
#: float32 are exact far beyond this many.
CHUNK = 1 << 17


# -- traffic ---------------------------------------------------------------


def page(config: dict) -> list[tuple[str, int]]:
    """The page's calls, in order."""
    filters = config["fields"][SUM_FILTER]["rows"]
    return ([("topn", 0)] + [("sum", k) for k in range(filters)]
            + [("groupby2", 0), ("groupby3", 0)])


def draw(group: dict, config: dict, rng, n: int):
    """Yield the group's next n calls: the page, over and over. Nothing is
    drawn from the client's RNG."""
    calls = page(config)
    if int(group["calls_per_request"]) != len(calls):
        raise ValueError(
            f"a request of shape taxi_page is one page of {len(calls)} calls"
        )
    for k in range(n):
        yield calls[k % len(calls)]


def _rows(fields) -> str:
    return ", ".join(f"Rows({f})" for f in fields)


def pql(call) -> str:
    kind, k = call
    if kind == "topn":
        return f"TopN({TOPN_FIELD})"
    if kind == "sum":
        return f"Sum(Row({SUM_FILTER}={k}), field={SUM_FIELD})"
    return f"GroupBy({_rows(GROUPBY2 if kind == 'groupby2' else GROUPBY3)})"


def render(calls) -> bytes:
    return "".join(pql(c) for c in calls).encode()


def warm(group: dict, config: dict, seed: int, client: int, send, say) -> None:
    """Nothing beyond the group's first request, which is the whole page:
    it builds the five stacks and compiles the programs of every kind. A
    row is an argument of the Sum's one program."""


# -- reference -------------------------------------------------------------

TABLES = ("cab", "amount_by_passengers", "passengers_year",
          "year_passengers_miles")


def tables_needed(groups: list[dict], config: dict) -> list[str]:
    return list(TABLES)


def decode_planes(planes: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(which columns hold a value: bool[columns]; the value of each, 0
    where there is none: int64[columns]) of an int field's
    bool[2 + depth, columns]: exists, sign, magnitude bits lowest first."""
    holds = planes[0]
    mag = np.zeros(holds.size, dtype=np.int64)
    for i in range(planes.shape[0] - 2):
        mag += planes[2 + i].astype(np.int64) << i
    return holds, np.where(planes[1], -mag, mag) * holds


def group_counts(fields_bits: list[np.ndarray]) -> np.ndarray:
    """int64[rows of each field]: the columns that sit in a row of every
    field, for each combination of rows. Rows that are empty in a stretch
    of columns are left out of its products (they count nothing)."""
    *outer, last = fields_bits
    shape = tuple(b.shape[0] for b in fields_bits)
    counts = np.zeros((int(np.prod(shape[:-1])), shape[-1]), dtype=np.int64)
    for at in range(0, last.shape[1], CHUNK):
        left = outer[0][:, at:at + CHUNK]
        for bits in outer[1:]:
            part = bits[:, at:at + CHUNK]
            left = (left[:, None, :] & part[None, :, :]).reshape(-1, part.shape[1])
        right = last[:, at:at + CHUNK]
        i, j = np.flatnonzero(left.any(axis=1)), np.flatnonzero(right.any(axis=1))
        counts[np.ix_(i, j)] += (
            left[i].astype(np.float32) @ right[j].T.astype(np.float32)
        ).astype(np.int64)
    return counts.reshape(shape)


def shard_tables(config: dict, names: list[str], data) -> dict:
    """{name: array} of one shard, from the shard's drawn bits."""
    out = {}
    for name in names:
        if name == "cab":
            out[name] = data.bits(TOPN_FIELD).sum(axis=1, dtype=np.int64)
        elif name == "amount_by_passengers":
            holds, vals = decode_planes(data.bits(SUM_FIELD))
            out[name] = np.array(
                [(int(vals[row].sum()), int((row & holds).sum()))
                 for row in data.bits(SUM_FILTER)],
                dtype=np.int64,
            )
        elif name == "passengers_year":
            out[name] = group_counts([data.bits(f) for f in GROUPBY2])
        elif name == "year_passengers_miles":
            out[name] = group_counts([data.bits(f) for f in GROUPBY3])
        else:
            raise ValueError(f"shape taxi_page has no table {name!r}")
    return out


def _groups(fields, counts: np.ndarray) -> list[dict]:
    return [
        {"group": [{"field": f, "rowID": int(r)} for f, r in zip(fields, at)],
         "count": int(counts[at])}
        for at in zip(*np.nonzero(counts))
    ]


#: The answers made so far from one set of tables (held here, so that no
#: other can take its place in memory unseen): every page asks the same
#: thirteen, and the larger GroupBy's answer is a few thousand groups.
_made = {"totals": None, "answers": {}}


def answer(config: dict, totals: dict, call):
    """`totals` are the tables summed over the index's shards."""
    if _made["totals"] is not totals:
        _made.update(totals=totals, answers={})
    if call not in _made["answers"]:
        _made["answers"][call] = _answer(totals, call)
    return _made["answers"][call]


def _answer(totals: dict, call):
    kind, k = call
    if kind == "topn":
        counts = totals["cab"]
        order = sorted(np.flatnonzero(counts).tolist(),
                       key=lambda r: (-int(counts[r]), r))
        return [{"id": r, "count": int(counts[r])} for r in order]
    if kind == "sum":
        total, count = totals["amount_by_passengers"][k]
        return {"value": int(total), "count": int(count)}
    if kind == "groupby2":
        return _groups(GROUPBY2, totals["passengers_year"])
    return _groups(GROUPBY3, totals["year_passengers_miles"])


def _keyed(result) -> dict:
    """A result as {key: number}: a Sum's two numbers, a TopN's count by
    row, a GroupBy's count by group. Raises where it has another form."""
    if isinstance(result, dict):
        return {k: int(result[k]) for k in ("value", "count")}
    out = {}
    for item in result:
        if "group" in item:
            key = tuple((g["field"], int(g["rowID"])) for g in item["group"])
        else:
            key = int(item["id"])
        out[key] = int(item["count"])
    return out


def compare(got, want):
    """(the returned result equals the reference's; the largest absolute
    error of a count, a sum or a group's count, a missing one counting as
    0, where the result has the form of an answer)."""
    try:
        have, ref = _keyed(got), _keyed(want)
    except (KeyError, TypeError, ValueError):
        return False, None
    err = max(
        (abs(have.get(k, 0) - ref.get(k, 0)) for k in have.keys() | ref.keys()),
        default=0,
    )
    return got == want, err
