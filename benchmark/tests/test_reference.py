"""The numpy reference against hand-worked shards: the intersection
tables by AND and popcount, and every verb's inclusion-exclusion formula
against the verb applied bit by bit."""

import itertools

import numpy as np
import pytest

from harness import datagen, reference

CONFIG = {
    "name": "hand", "index": "hand", "shards": 2, "shard_width": 128,
    "fields": {
        "f": {"type": "set", "rows": 2, "density": 0.5},
        "g": {"type": "set", "rows": 2, "density": 0.5},
        "h": {"type": "set", "rows": 2, "density": 0.5},
    },
}


def hand_bits(shard: int, field: str) -> np.ndarray:
    """Two shards of 128 columns, written out so that every count below can
    be worked by hand. Shard 0: f0 = columns 0..9, f1 = 5..14, g0 = 0..4 and
    10..14, g1 = the even columns under 20, h0 = 3..12, h1 = 127 alone.
    Shard 1: the same rows shifted up by 64 columns, except h1 = nothing."""
    bits = np.zeros((2, 128), dtype=bool)
    off = 64 * shard
    cols = {
        "f": [range(0, 10), range(5, 15)],
        "g": [list(range(0, 5)) + list(range(10, 15)), range(0, 20, 2)],
        "h": [range(3, 13), [127 - off] if shard == 0 else []],
    }[field]
    for r, cs in enumerate(cols):
        for c in cs:
            bits[r, c + off] = True
    return bits


@pytest.fixture(scope="module")
def ref():
    tables = reference.tables_for_shards(CONFIG, 0, range(2), bits_of=hand_bits)
    return reference.Reference(CONFIG, tables)


def test_hand_worked_intersections(ref):
    # Per shard: |f0| = 10, |f0 & g0| = |{0..4}| = 5, |f0 & g1| = |{0,2,4,6,8}| = 5,
    # |f0 & g0 & h0| = |{3,4}| = 2, |f1 & g0 & h0| = |{10,11,12}| = 3.
    assert ref.intersection([("f", 0)]) == 20
    assert ref.intersection([("f", 0), ("g", 0)]) == 10
    assert ref.intersection([("g", 1), ("f", 0)]) == 10
    assert ref.intersection([("f", 0), ("g", 0), ("h", 0)]) == 4
    assert ref.intersection([("h", 0), ("f", 1), ("g", 0)]) == 6
    # h1 has one bit, in shard 0 only, and meets nothing.
    assert ref.intersection([("h", 1)]) == 1
    assert ref.intersection([("f", 0), ("h", 1)]) == 0


def test_counts_sum_over_the_shards(ref):
    """h1's one bit lies in shard 0, and each of the two shards holds half
    of every other count."""
    tables = reference.tables_for_shards(CONFIG, 0, range(1), bits_of=hand_bits)
    first = reference.Reference(CONFIG, tables)
    assert first.intersection([("h", 1)]) == 1
    assert first.intersection([("f", 0), ("g", 0)]) == 5
    assert first.answer("Union", [("f", 0), ("g", 0), ("h", 0)]) == 15
    assert ref.answer("Union", [("f", 0), ("g", 0), ("h", 0)]) == 30


def test_hand_worked_verbs(ref):
    leaves = [("f", 0), ("g", 0), ("h", 0)]
    # Per shard: f0 = 0..9, g0 = 0..4 + 10..14, h0 = 3..12.
    # Union = 0..14 -> 15. Difference f0 - g0 - h0 = {} (5..9 lie in h0) -> 0.
    # Xor: in exactly one or in all three: {0,1,2} (f,g) no: f&g only -> out;
    #   column by column: 0..2 f,g -> 0; 3,4 f,g,h -> 1; 5..9 f,h -> 0;
    #   10..12 g,h -> 0; 13,14 g -> 1  => 4 a shard.
    assert ref.answer("Intersect", leaves) == 4
    assert ref.answer("Union", leaves) == 30
    assert ref.answer("Difference", leaves) == 0
    assert ref.answer("Xor", leaves) == 8
    # Difference is not symmetric: h0 - f0 - g0 = {} too (10..12 in g0);
    # g0 - f0 - h0 = {13, 14} -> 2 a shard.
    assert ref.answer("Difference", [("g", 0), ("f", 0), ("h", 0)]) == 4


@pytest.mark.parametrize("verb", reference.VERBS)
@pytest.mark.parametrize("n_operands", [2, 3])
def test_formulas_equal_the_verb_bit_by_bit(verb, n_operands):
    """Seeded random shards, every ordering of every operand set."""
    config = dict(CONFIG, shards=3, shard_width=1024)
    seed = 2**31 + 77
    tables = reference.tables_for_shards(config, seed, range(3))
    ref = reference.Reference(config, tables)
    fields = datagen.set_fields(config)
    bits = {
        f: np.concatenate(
            [datagen.field_bits(config, seed, s, f) for s in range(3)], axis=1
        ) for f in fields
    }
    for combo in itertools.permutations(fields, n_operands):
        for rows in itertools.product(range(2), repeat=n_operands):
            leaves = list(zip(combo, rows))
            want = reference.direct_answer(verb, [bits[f][r] for f, r in leaves])
            assert ref.answer(verb, leaves) == want, (verb, leaves)


def test_tables_round_trip(tmp_path):
    tables = reference.tables_for_shards(CONFIG, 0, range(2), bits_of=hand_bits)
    path = str(tmp_path / "t.npz")
    reference.save_tables(path, tables)
    back = reference.load_tables(path)
    assert set(back) == set(tables)
    for k in back:
        assert (back[k] == tables[k]).all()


def test_same_seed_same_bits_and_large_seeds():
    config = dict(CONFIG, shard_width=4096)
    a = datagen.field_bits(config, 2**31 + 12345, 1, "g")
    b = datagen.field_bits(config, 2**31 + 12345, 1, "g")
    c = datagen.field_bits(config, 2**31 + 12346, 1, "g")
    assert (a == b).all() and (a != c).any()
