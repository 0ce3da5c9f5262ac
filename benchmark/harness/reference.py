"""The plain reference's bookkeeping. What is counted, and how an answer
follows from it, is each call shape's business (benchmark/shapes/<name>.py:
`tables_needed`, `shard_tables`, `answer`, `compare`); here the per-shard
tables of a cell's shapes are made a shard at a time from the seed's
draws, stacked in shard order, kept on disk by shape, summed over the
index, and handed back to the shape with each call. Nothing here or in a
shape imports the program.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np

from . import datagen, plugins


def needs(mix: dict, config: dict) -> dict[str, list[str]]:
    """{shape: the names of the tables its groups of this mix read}: a
    cell's reference holds these and no others."""
    return {
        name: list(plugins.load("shapes", name).tables_needed(groups, config))
        for name, groups in plugins.groups_by_shape(mix).items()
    }


def shard_tables(config: dict, wanted: dict, data) -> dict:
    """{shape: {name: array}} of the one shard `data` holds."""
    return {
        shape: plugins.load("shapes", shape).shard_tables(config, names, data)
        for shape, names in wanted.items() if names
    }


def stack_tables(per_shard: list[dict]) -> dict:
    """Shards' tables of one shape, in shard order, as one array a name."""
    return {k: np.stack([t[k] for t in per_shard]) for k in per_shard[0]}


def tables_for_shards(config: dict, seed: int, shards, wanted: dict,
                      given=None) -> dict:
    """{shape: {name: array[len(shards), ...]}}, one shard after another.
    `given(shard)` maps fields to data that overrides the seed's draw (the
    tests' hand-worked shards)."""
    per_shard = [
        shard_tables(config, wanted, datagen.ShardData(
            config, seed, shard, given(shard) if given is not None else None
        ))
        for shard in shards
    ]
    return {
        shape: stack_tables([t[shape] for t in per_shard])
        for shape, names in wanted.items() if names
    }


class Reference:
    """The per-shard tables of a mix's shapes summed over the index's
    shards, shape by shape: what a shape's `answer` reads."""

    def __init__(self, config: dict, tables: dict):
        self.config = config
        self.totals = {
            shape: {k: v.sum(axis=0) for k, v in named.items()}
            for shape, named in tables.items()
        }

    def answer(self, shape: str, call):
        return plugins.load("shapes", shape).answer(
            self.config, self.totals.get(shape, {}), call
        )


#: The key under which a file of tables keeps the digest of the source of
#: the shape that made them (no table's name: a shape's names hold no
#: double underscore at their ends).
MADE_BY = "__made_by__"


def tables_path(directory: str, shape: str) -> str:
    return os.path.join(directory, f"reference_{shape}.npz")


def shape_digest(shape: str) -> np.ndarray:
    """sha256 of benchmark/shapes/<shape>.py as uint8[32]."""
    digest = hashlib.sha256(plugins.source("shapes", shape)).digest()
    return np.frombuffer(digest, dtype=np.uint8)


def save_tables(directory: str, shape: str, tables: dict) -> None:
    """Written beside and renamed, so that a reader never sees a part;
    with the digest of the shape's source, so that tables made by another
    `shard_tables` are not taken for this one's."""
    path = tables_path(directory, shape)
    tmp = f"{path}.{os.getpid()}.tmp.npz"
    np.savez(tmp, **tables, **{MADE_BY: shape_digest(shape)})
    os.replace(tmp, path)


def load_tables(directory: str, shape: str) -> dict:
    """The shape's tables kept in `directory`; none where there is no
    file, or where the shape's source is not the one that made them (the
    caller makes them again from the draws)."""
    path = tables_path(directory, shape)
    if not os.path.exists(path):
        return {}
    with np.load(path) as z:
        if MADE_BY not in z.files or not np.array_equal(
            z[MADE_BY], shape_digest(shape)
        ):
            return {}
        return {k: z[k] for k in z.files if k != MADE_BY}
