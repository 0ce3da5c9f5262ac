"""The plain reference: numpy over the bits the seed gives, nothing of
the program (never exec/cpu.py, no roaring, no tables the server made).

Per shard it counts, with AND and popcount alone, the intersection of
every combination of rows of up to three distinct set fields: the
*intersection tables*, made a shard at a time (int64[shards, rows_a,
rows_b, ...]) and summed over the index. The answer to `Count(<verb>(Row(a=i), Row(b=j), ...))` over
distinct fields then follows by inclusion and exclusion, which `answer`
spells out and benchmark/tests/test_reference.py checks against the verbs
applied bit by bit on hand-worked shards.
"""

from __future__ import annotations

import itertools

import numpy as np

from . import datagen

VERBS = ("Intersect", "Union", "Difference", "Xor")
MAX_OPERANDS = 3


def popcount(words: np.ndarray) -> np.ndarray:
    return np.bitwise_count(words).sum(axis=-1, dtype=np.int64)


def table_key(fields) -> str:
    return "|".join(fields)


def combos(fields: list[str]):
    """Every combination of 1..MAX_OPERANDS distinct set fields, in the
    configuration's field order."""
    for n in range(1, min(MAX_OPERANDS, len(fields)) + 1):
        yield from itertools.combinations(fields, n)


def shard_tables(words: dict[str, np.ndarray], fields: list[str]) -> dict:
    """{table_key: int64[rows_a, rows_b, ...]} of one shard. `words` maps
    a field to its uint64[rows, width // 64]."""
    out = {}
    for combo in combos(fields):
        if len(combo) == 1:
            out[table_key(combo)] = popcount(words[combo[0]])
        elif len(combo) == 2:
            a, b = (words[c] for c in combo)
            out[table_key(combo)] = popcount(a[:, None, :] & b[None, :, :])
        else:
            a, b, c = (words[x] for x in combo)
            out[table_key(combo)] = np.stack(
                [popcount(a[:, None, :] & (b & cr[None, :])[None, :, :])
                 for cr in c],
                axis=-1,
            )
    return out


def tables_for_shards(config: dict, seed: int, shards, bits_of=None) -> dict:
    """{table_key: int64[len(shards), rows_a, ...]}: the intersection
    tables of `shards`, one shard after another. `bits_of(shard, field)`
    overrides the seed's draw (the tests' hand-worked shards)."""
    fields = datagen.set_fields(config)
    per_shard = []
    for shard in shards:
        words = {}
        for f in fields:
            bits = (
                bits_of(shard, f) if bits_of is not None
                else datagen.field_bits(config, seed, shard, f)
            )
            words[f] = datagen.pack64(bits)
        per_shard.append(shard_tables(words, fields))
    return stack_tables(per_shard)


def stack_tables(per_shard: list[dict]) -> dict:
    """Shards' tables, in shard order, as one array a key."""
    return {k: np.stack([t[k] for t in per_shard]) for k in per_shard[0]}


class Reference:
    """Answers Count calls over the whole index from the per-shard
    intersection tables."""

    def __init__(self, config: dict, tables: dict):
        self.order = datagen.set_fields(config)
        self.tables = {k: v.sum(axis=0) for k, v in tables.items()}

    def intersection(self, leaves) -> int:
        """|Row(a=i) & Row(b=j) & ...| for leaves [(field, row), ...] of
        distinct fields, in any order."""
        leaves = sorted(leaves, key=lambda fr: self.order.index(fr[0]))
        fields = [f for f, _ in leaves]
        if len(set(fields)) != len(fields):
            raise ValueError(f"operands repeat a field: {fields}")
        return int(self.tables[table_key(fields)][tuple(r for _, r in leaves)])

    def answer(self, verb: str, leaves) -> int:
        """Count(<verb>(leaves...)), by inclusion and exclusion over the
        intersections of the operands' subsets T:
          Intersect  = I(all)
          Union      = sum over nonempty T of (-1)^(|T|+1) I(T)
          Difference = first minus the union of the rest
                     = sum over T of the rest of (-1)^|T| I({first} + T)
          Xor        = sum over nonempty T of (-2)^(|T|-1) I(T)
        """
        leaves = [tuple(x) for x in leaves]
        inter = self.intersection
        if verb == "Intersect" or len(leaves) == 1:
            return inter(leaves)
        n = len(leaves)
        total = 0
        if verb == "Union":
            for k in range(1, n + 1):
                for t in itertools.combinations(leaves, k):
                    total += (-1) ** (k + 1) * inter(t)
        elif verb == "Xor":
            for k in range(1, n + 1):
                for t in itertools.combinations(leaves, k):
                    total += (-2) ** (k - 1) * inter(t)
        elif verb == "Difference":
            first, rest = leaves[0], leaves[1:]
            for k in range(0, n):
                for t in itertools.combinations(rest, k):
                    total += (-1) ** k * inter((first,) + t)
        else:
            raise ValueError(f"no reference for verb {verb!r}")
        return total


def direct_answer(verb: str, rows: list[np.ndarray]) -> int:
    """The verb applied bit by bit to bool rows: what `answer` must equal.
    Used by the tests, at sizes where it is cheap."""
    out = rows[0].copy()
    for r in rows[1:]:
        if verb == "Intersect":
            out &= r
        elif verb == "Union":
            out |= r
        elif verb == "Difference":
            out &= ~r
        elif verb == "Xor":
            out ^= r
        else:
            raise ValueError(verb)
    return int(out.sum())


def save_tables(path: str, tables: dict) -> None:
    np.savez(path, **tables)


def load_tables(path: str) -> dict:
    with np.load(path) as z:
        return {k: z[k] for k in z.files}
