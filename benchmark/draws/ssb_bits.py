"""The fields of `configs/ssb-sf100-1chip.json` shipped as bits, of the same
lineorder rows `ssb_lineorder.py` draws for the shard, one roaring bitmap
a shard (`import-roaring/{shard}`, view ""):

- a mutex field of few rows (a date attribute, the discount, a region,
  nation, manufacturer or category): bool[rows, shard_width], the row of
  each column set;
- an int field (lo_quantity, lo_extendedprice, lo_revenue,
  lo_supplycost), every column holding a value: the fragment's own
  bit-sliced planes, as `int_planes.py` ships them: row 0 says which
  columns hold a value, row 1 holds the sign, rows 2 .. 2 + depth - 1 the
  value's bits, lowest first (the fields' min, and so their base, is 0).
  A field's depth is its max's bit length; the draw refuses a value
  outside the field's range.

The tall mutex fields (the cities, p_brand1) ship as positions
(`ssb_lineorder.py`); the loader cuts those into slices of whole rows, and
a row of a short field holds more of a shard's columns than a slice may."""

import numpy as np

from harness import plugins

SHIP = "roaring"
EXISTS, SIGN, OFFSET = 0, 1, 2


def options(spec: dict) -> dict:
    """The body of the request that creates the field."""
    if spec["type"] == "int":
        return {"options": {"type": "int", "min": spec["min"], "max": spec["max"]}}
    return {"options": {"type": "mutex"}}


def depth(spec: dict) -> int:
    if spec["min"] != 0:
        raise ValueError("ssb_bits draws int fields whose min, and so base, is 0")
    return max(int(spec["max"]).bit_length(), 1)


def column(config: dict, seed: int, shard: int, field: str) -> np.ndarray:
    """int64[shard_width]: the field's value or row at every column."""
    spec = config["fields"][field]
    return plugins.load("draws", "ssb_lineorder").lineorder(
        seed, shard, config["shard_width"], spec["sf"]
    )[field]


def draw(config: dict, seed: int, shard: int, field: str) -> np.ndarray:
    """bool[rows, shard_width] of a mutex field, bool[2 + depth,
    shard_width] of an int field: one shard."""
    spec = config["fields"][field]
    vals = column(config, seed, shard, field)
    if spec["type"] != "int":
        if int(vals.max()) >= spec["rows"]:
            raise ValueError(f"{field}: a row id past the field's {spec['rows']} rows")
        return np.arange(spec["rows"])[:, None] == vals
    if int(vals.max()) > spec["max"] or int(vals.min()) < spec["min"]:
        raise ValueError(f"{field}: a value outside {spec['min']}..{spec['max']}")
    n = depth(spec)
    planes = np.zeros((OFFSET + n, vals.size), dtype=bool)
    planes[EXISTS] = True
    for i in range(n):
        planes[OFFSET + i] = (vals >> i) & 1
    return planes
