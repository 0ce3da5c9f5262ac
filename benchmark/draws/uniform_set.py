"""A set field of `rows` rows, each holding `density` of a shard's
columns: per row, n uniform columns with replacement (bench.py's draw,
by way of chip_smoke.py). A field marked `density_split_over_rows`
splits one row's n over its rows. Shipped as one roaring bitmap a shard.

The RNG key is [seed, shard, position of the field in the configuration],
so the same seed gives the same bits in every run, here and in the
reference."""

import numpy as np

from harness import datagen

SHIP = "roaring"


def options(spec: dict) -> dict:
    """The body of the request that creates the field."""
    return {}


def draw(config: dict, seed: int, shard: int, field: str) -> np.ndarray:
    """bool[rows, shard_width] of one shard of the field."""
    spec = config["fields"][field]
    width = config["shard_width"]
    rows = spec["rows"]
    n_bits = int(width * spec["density"])
    if spec.get("density_split_over_rows"):
        n_bits //= rows
    rng = np.random.default_rng(
        [seed, shard, datagen.field_position(config, field)]
    )
    cols = rng.integers(0, width, size=(rows, n_bits), dtype=np.uint32)
    bits = np.zeros((rows, width), dtype=bool)
    bits[np.arange(rows)[:, None], cols] = True
    return bits
