"""An int field in which every column holds a value, shipped as the
fragment's own bit-sliced planes in one roaring bitmap a shard
(`import-roaring/{shard}`, view ""): row 0 says which columns hold a
value, row 1 holds the sign, rows 2 .. 2 + depth - 1 the magnitude's bits,
lowest first, of the value less the field's base (here `min`, 0). The
values are skewed: `value_floor` + floor of an exponential of mean
`value_mean`, cut at the field's `max` (amounts: most small, a few large),
but for a share `outlier_share` of the columns, which hold a value uniform
over the field's whole range (the mistyped amounts every such data set
has). With a hundred of those a shard every plane holds bits in every
shard, so the field's bit depth is its range's in every seed and a Sum
reads the same planes in every run.
The RNG key is [seed, shard, position of the field in the configuration].

`values` are the shard's values before they are sliced into planes; the
reference decodes the planes for itself (shapes/taxi_page.py) and
benchmark/tests holds the two equal."""

import numpy as np

from harness import datagen

SHIP = "roaring"
EXISTS, SIGN, OFFSET = 0, 1, 2


def options(spec: dict) -> dict:
    """The body of the request that creates the field."""
    return {"options": {"type": "int", "min": spec["min"], "max": spec["max"]}}


def depth(spec: dict) -> int:
    """Magnitude planes of the field: the bits of its largest value less
    its base."""
    if spec["min"] != 0:
        raise ValueError("int_planes draws fields whose min, and so base, is 0")
    return max(int(spec["max"]).bit_length(), 1)


def values(config: dict, seed: int, shard: int, field: str) -> np.ndarray:
    """int64[shard_width]: the value of every column of the shard."""
    spec = config["fields"][field]
    rng = np.random.default_rng(
        [seed, shard, datagen.field_position(config, field)]
    )
    width = config["shard_width"]
    vals = spec["value_floor"] + np.floor(
        rng.exponential(spec["value_mean"], width)
    )
    vals = np.minimum(vals, spec["max"]).astype(np.int64)
    odd = rng.random(width) < spec["outlier_share"]
    return np.where(odd, rng.integers(0, spec["max"] + 1, width), vals)


def encode(vals: np.ndarray, n_planes: int) -> np.ndarray:
    """bool[2 + n_planes, columns] of non-negative values, one a column."""
    planes = np.zeros((OFFSET + n_planes, vals.size), dtype=bool)
    planes[EXISTS] = True
    for i in range(n_planes):
        planes[OFFSET + i] = (vals >> i) & 1
    return planes


def draw(config: dict, seed: int, shard: int, field: str) -> np.ndarray:
    """bool[2 + depth, shard_width] of one shard of the field."""
    spec = config["fields"][field]
    return encode(values(config, seed, shard, field), depth(spec))
