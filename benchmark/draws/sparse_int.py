"""An int field with `values_per_shard` values a shard (less the columns
drawn twice), uniform over `value_range`, at uniform columns
(chip_smoke.py's `v_values`). Shipped as columns and values. The RNG key
is [seed, shard, position of the field in the configuration]."""

import numpy as np

from harness import datagen

SHIP = "values"


def options(spec: dict) -> dict:
    """The body of the request that creates the field."""
    return {"options": {"type": "int", "min": spec["min"], "max": spec["max"]}}


def draw(config: dict, seed: int, shard: int, field: str):
    """(in-shard columns, ascending and distinct; their values)."""
    spec = config["fields"][field]
    width = config["shard_width"]
    rng = np.random.default_rng(
        [seed, shard, datagen.field_position(config, field)]
    )
    cols = np.unique(
        rng.integers(0, width, spec["values_per_shard"], dtype=np.int64)
    )
    lo, hi = spec["value_range"]
    return cols, rng.integers(lo, hi + 1, cols.size)
