"""A set field that holds one row a column, the row a whole-numbered
quantity with a long tail: floor of an exponential of mean `value_mean`,
cut at `rows - 1` (trip distance in whole miles: most short, the last row
takes everything beyond), but for a share `outlier_share` of the columns,
whose row is uniform over all `rows` (the mistyped distances every such
data set has). A few hundred of those over the index put a column into
the highest rows in every seed, so the field's stack on the device is
`rows` tall whatever the seed: the plain tail alone leaves the rows from
56 on empty in about one seed of three at 2.7 x 10^8 columns, and the stack,
which is as tall as the highest row in use, 56 rows and not 64. Shipped as
one roaring bitmap a shard. The RNG key is [seed, shard, position of the
field in the configuration]."""

import numpy as np

from harness import datagen

SHIP = "roaring"


def options(spec: dict) -> dict:
    """The body of the request that creates the field."""
    return {}


def draw(config: dict, seed: int, shard: int, field: str) -> np.ndarray:
    """bool[rows, shard_width] of one shard of the field."""
    spec = config["fields"][field]
    rows = spec["rows"]
    rng = np.random.default_rng(
        [seed, shard, datagen.field_position(config, field)]
    )
    width = config["shard_width"]
    row = np.minimum(np.floor(rng.exponential(spec["value_mean"], width)), rows - 1)
    odd = rng.random(width) < spec["outlier_share"]
    row = np.where(odd, rng.integers(0, rows, width), row).astype(np.int8)
    return np.arange(rows, dtype=np.int8)[:, None] == row
