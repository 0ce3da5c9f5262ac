"""The call shape `sum`: `Sum(Row(<set field>=<row>), field=<int field>)`,
the sum and the number of an int field's values in the columns of one
row, over the whole index.

A group's keys for this shape:
  filters   set fields; a call draws one uniformly, then one of its rows
            uniformly
  fields    int fields; a call draws one uniformly

A call is (set field, row, int field); its answer is
{"value": sum, "count": n}, exact ({"value": 0, "count": 0} where the row
meets no value).

The reference is plain numpy over the draws themselves: per shard, the
int field's (columns, values) and the set field's bits at those columns
give int64[rows, 2] (sum, count), summed over the index. A column with a
value and no bit of the row counts nothing, whatever its value.
"""

from __future__ import annotations

import numpy as np


# -- traffic ---------------------------------------------------------------


def draw(group: dict, config: dict, rng, n: int):
    """Yield the group's next n calls, their random numbers drawn at once."""
    filters = list(group["filters"])
    ints = list(group["fields"])
    rows = {f: config["fields"][f]["rows"] for f in filters}
    filter_i = rng.integers(0, len(filters), n).tolist()
    int_i = rng.integers(0, len(ints), n).tolist()
    u = rng.random(n).tolist()
    for k in range(n):
        f = filters[filter_i[k]]
        yield (f, int(u[k] * rows[f]), ints[int_i[k]])


def render(calls) -> bytes:
    return "".join(
        f"Sum(Row({f}={row}), field={v})" for f, row, v in calls
    ).encode()


def warm(group: dict, config: dict, seed: int, client: int, send, say) -> None:
    """Nothing beyond the group's first request: the filter's row is an
    argument of the one program a (set field, int field) pair compiles."""


# -- reference -------------------------------------------------------------


def table_key(filter_field: str, int_field: str) -> str:
    return f"{filter_field}|{int_field}"


def tables_needed(groups: list[dict], config: dict) -> list[str]:
    """One table for every (set field, int field) a group can ask."""
    return sorted({
        table_key(f, v) for g in groups for f in g["filters"] for v in g["fields"]
    })


def shard_tables(config: dict, names: list[str], data) -> dict:
    """{name: int64[rows, 2]} of one shard: (sum, count) of the int
    field's values under each row of the set field."""
    out = {}
    for name in names:
        filter_field, int_field = name.split("|")
        cols, vals = data.values(int_field)
        under = data.bits(filter_field)[:, np.asarray(cols, dtype=np.int64)]
        vals = np.asarray(vals, dtype=np.int64)
        out[name] = np.stack(
            [(under * vals[None, :]).sum(axis=1, dtype=np.int64),
             under.sum(axis=1, dtype=np.int64)],
            axis=-1,
        )
    return out


def answer(config: dict, totals: dict, call) -> dict:
    """`totals` are the tables summed over the index's shards."""
    f, row, v = call
    total, count = totals[table_key(f, v)][row]
    return {"value": int(total), "count": int(count)}


def compare(got, want: dict):
    """(the returned result equals the reference's, the larger absolute
    error of value and count where both are numbers)."""
    if not isinstance(got, dict):
        return False, None
    try:
        err = max(abs(int(got[k]) - want[k]) for k in ("value", "count"))
    except (KeyError, TypeError, ValueError):
        return False, None
    return got == want, err
