"""A mutex field clustered by shard, as a field of the ingest date is when
columns are numbered in ingest order: shard s of S holds row
floor(rows * s / S) in `home_share` of its columns and the next row (the
last row stays where it is) in the rest, every column in exactly one row.
Shipped as one roaring bitmap a shard. The RNG key is [seed, shard,
position of the field in the configuration]."""

import numpy as np

from harness import datagen

SHIP = "roaring"


def options(spec: dict) -> dict:
    """The body of the request that creates the field."""
    return {"options": {"type": "mutex"}}


def draw(config: dict, seed: int, shard: int, field: str) -> np.ndarray:
    """bool[rows, shard_width] of one shard of the field."""
    spec = config["fields"][field]
    rows = spec["rows"]
    rng = np.random.default_rng(
        [seed, shard, datagen.field_position(config, field)]
    )
    home = rows * shard // config["shards"]
    later = rng.random(config["shard_width"]) >= spec["home_share"]
    row = np.minimum(home + later, rows - 1).astype(np.int8)
    return np.arange(rows, dtype=np.int8)[:, None] == row
