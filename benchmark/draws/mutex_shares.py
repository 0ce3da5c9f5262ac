"""A mutex field whose rows hold fixed, unequal shares of a shard's
columns: every column is in exactly one row, row r with probability
`shares[r]` (the shares sum to 1; a field whose first row holds most of
the columns, as one cab type or one passenger count does). Shipped as one
roaring bitmap a shard. The RNG key is [seed, shard, position of the field
in the configuration]."""

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
    edges = np.cumsum(np.asarray(spec["shares"], dtype=np.float64))
    row = np.searchsorted(edges, rng.random(config["shard_width"]), side="right")
    row = np.minimum(row, rows - 1).astype(np.int8)
    return np.arange(rows, dtype=np.int8)[:, None] == row
