"""An int field in which a share `density` of every shard's columns holds
a value, the values skewed: floor of an exponential of mean `value_mean`,
cut at the field's `max` (amounts, distances: most small, a few large).
Shipped as columns and values. The RNG key is [seed, shard, position of
the field in the configuration].

Added by benchmark/tests/overlay.py as a files-only PR would add it: the
rehearsal's proof that a configuration's field can name a draw the
harness has never heard of."""

import numpy as np

from harness import datagen

SHIP = "values"


def options(spec: dict) -> dict:
    """The body of the request that creates the field."""
    return {"options": {"type": "int", "min": spec["min"], "max": spec["max"]}}


def draw(config: dict, seed: int, shard: int, field: str):
    """(in-shard columns, ascending and distinct; their values)."""
    spec = config["fields"][field]
    rng = np.random.default_rng(
        [seed, shard, datagen.field_position(config, field)]
    )
    cols = np.flatnonzero(rng.random(config["shard_width"]) < spec["density"])
    vals = np.floor(rng.exponential(spec["value_mean"], cols.size))
    return cols.astype(np.int64), np.minimum(vals, spec["max"]).astype(np.int64)
