"""The numpy reference against hand-worked shards. Shape `count`: the
intersection tables by AND and popcount, and every verb's
inclusion-exclusion formula against the verb applied bit by bit. Shape
`sum`: sums and counts of hand-placed values under hand-placed rows."""

import itertools
import os

import numpy as np
import pytest

from harness import datagen, plugins, reference

count = plugins.load("shapes", "count")
sum_shape = plugins.load("shapes", "sum")

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


ALL3 = {"count": count.tables_needed(
    [{"operand_sets": [["f", "g", "h"]]}], CONFIG)}


def given(shard: int) -> dict:
    return {f: hand_bits(shard, f) for f in CONFIG["fields"]}


class Ref:
    """The count shape's answers over hand-worked or seeded shards."""

    def __init__(self, config, seed, shards, given=None):
        self.ref = reference.Reference(config, reference.tables_for_shards(
            config, seed, shards, ALL3, given=given))
        self.config = config

    def intersection(self, leaves):
        return count.intersection(self.config, self.ref.totals["count"], leaves)

    def answer(self, verb, leaves):
        return self.ref.answer("count", (verb, leaves))


@pytest.fixture(scope="module")
def ref():
    return Ref(CONFIG, 0, range(2), given)


def test_a_cell_holds_only_the_tables_its_groups_ask_for():
    """Every subset of an operand set, the fields in the configuration's
    order whatever the operands' order; nothing of a field no group names."""
    assert ALL3["count"] == ["f", "g", "h", "f|g", "f|h", "g|h", "f|g|h"]
    pair = [{"operand_sets": [["g", "f"]]}]
    assert count.tables_needed(pair, CONFIG) == ["f", "g", "f|g"]
    two = [{"operand_sets": [["f", "g"]]}, {"operand_sets": [["h"], ["g", "h"]]}]
    assert count.tables_needed(two, CONFIG) == ["f", "g", "h", "f|g", "g|h"]
    mix = {"groups": [dict(pair[0], shape="count"),
                      {"shape": "sum", "filters": ["f"], "fields": ["v"]}]}
    assert reference.needs(mix, CONFIG) == {"count": ["f", "g", "f|g"],
                                            "sum": ["f|v"]}
    with pytest.raises(ValueError):
        count.tables_needed([{"operand_sets": [["f", "g", "h", "f2"]]}], CONFIG)


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
    first = Ref(CONFIG, 0, range(1), given)
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


@pytest.mark.parametrize("verb", count.VERBS)
@pytest.mark.parametrize("n_operands", [2, 3])
def test_formulas_equal_the_verb_bit_by_bit(verb, n_operands):
    """Seeded random shards, every ordering of every operand set."""
    config = dict(CONFIG, shards=3, shard_width=1024)
    seed = 2**31 + 77
    ref = Ref(config, seed, range(3))
    fields = datagen.set_fields(config)
    bits = {
        f: np.concatenate(
            [datagen.draw(config, seed, s, f) for s in range(3)], axis=1
        ) for f in fields
    }
    for combo in itertools.permutations(fields, n_operands):
        for rows in itertools.product(range(2), repeat=n_operands):
            leaves = list(zip(combo, rows))
            want = count.direct_answer(verb, [bits[f][r] for f, r in leaves])
            assert ref.answer(verb, leaves) == want, (verb, leaves)


def test_tables_round_trip(tmp_path):
    tables = reference.tables_for_shards(CONFIG, 0, range(2), ALL3, given=given)
    assert reference.load_tables(str(tmp_path), "count") == {}
    reference.save_tables(str(tmp_path), "count", tables["count"])
    back = reference.load_tables(str(tmp_path), "count")
    assert set(back) == set(tables["count"])
    for k in back:
        assert (back[k] == tables["count"][k]).all()
    assert os.listdir(tmp_path) == ["reference_count.npz"]


def test_tables_of_another_source_of_the_shape_are_not_taken(tmp_path, monkeypatch):
    """Kept tables carry the digest of the shape's source that made them:
    after an edit of shapes/<shape>.py they are made again, not reused."""
    tables = reference.tables_for_shards(CONFIG, 0, range(2), ALL3, given=given)
    reference.save_tables(str(tmp_path), "count", tables["count"])
    assert reference.load_tables(str(tmp_path), "count")
    source = plugins.source
    monkeypatch.setattr(
        plugins, "source", lambda kind, name: source(kind, name) + b"\n# edited\n"
    )
    assert reference.load_tables(str(tmp_path), "count") == {}
    # A file from before the digest was kept has none: not taken either.
    monkeypatch.setattr(plugins, "source", source)
    np.savez(reference.tables_path(str(tmp_path), "count"), **tables["count"])
    assert reference.load_tables(str(tmp_path), "count") == {}


def test_same_seed_same_bits_and_large_seeds():
    config = dict(CONFIG, shard_width=4096)
    a = datagen.draw(config, 2**31 + 12345, 1, "g")
    b = datagen.draw(config, 2**31 + 12345, 1, "g")
    c = datagen.draw(config, 2**31 + 12346, 1, "g")
    assert (a == b).all() and (a != c).any()


# -- shape `sum` -------------------------------------------------------------

SUM_CONFIG = {
    "name": "hand", "index": "hand", "shards": 2, "shard_width": 128,
    "fields": {
        "f": {"type": "set", "rows": 3, "density": 0.5},
        "v": {"type": "int", "min": -100, "max": 100, "values_per_shard": 6,
              "value_range": [-90, 90]},
    },
}
SUM_GROUP = {"shape": "sum", "filters": ["f"], "fields": ["v"]}


def sum_given(shard: int) -> dict:
    """Shard 0: v holds 7 at column 1, -20 at 2, 5 at 3, 90 at 40, -1 at
    100; f0 = columns 0..3 (7 - 20 + 5 = -8, three values), f1 = columns
    2, 40 and 99 (-20 + 90 = 70, two values; 99 has a bit and no value),
    f2 = column 50 alone (a bit and no value: nothing). Column 100 has a
    value and no bit of any row. Shard 1: v holds -3 at column 0 and 3 at
    column 1; f0 = column 0, f1 = columns 0 and 1, f2 = nothing."""
    bits = np.zeros((3, 128), dtype=bool)
    if shard == 0:
        bits[0, 0:4] = True
        bits[1, [2, 40, 99]] = True
        bits[2, 50] = True
        vals = (np.array([1, 2, 3, 40, 100]), np.array([7, -20, 5, 90, -1]))
    else:
        bits[0, 0] = True
        bits[1, [0, 1]] = True
        vals = (np.array([0, 1]), np.array([-3, 3]))
    return {"f": bits, "v": vals}


@pytest.mark.parametrize("shards, row, want", [
    ([0], 0, {"value": -8, "count": 3}),
    ([0], 1, {"value": 70, "count": 2}),
    ([0], 2, {"value": 0, "count": 0}),      # an empty answer: no value under the row
    ([1], 0, {"value": -3, "count": 1}),
    ([1], 1, {"value": 0, "count": 2}),      # a sum of nought over two values
    ([0, 1], 0, {"value": -11, "count": 4}),
    ([0, 1], 1, {"value": 70, "count": 4}),
    ([0, 1], 2, {"value": 0, "count": 0}),
])
def test_hand_worked_sums(shards, row, want):
    wanted = {"sum": sum_shape.tables_needed([SUM_GROUP], SUM_CONFIG)}
    assert wanted == {"sum": ["f|v"]}
    tables = reference.tables_for_shards(SUM_CONFIG, 0, shards, wanted,
                                         given=sum_given)
    assert tables["sum"]["f|v"].shape == (len(shards), 3, 2)
    ref = reference.Reference(SUM_CONFIG, tables)
    assert ref.answer("sum", ("f", row, "v")) == want


def test_seeded_sums_equal_the_values_added_one_by_one():
    config = dict(SUM_CONFIG, shards=3, shard_width=4096)
    config["fields"] = dict(config["fields"])
    config["fields"]["v"] = dict(config["fields"]["v"], values_per_shard=300)
    seed = 2**31 + 99
    wanted = {"sum": ["f|v"]}
    ref = reference.Reference(config, reference.tables_for_shards(
        config, seed, range(3), wanted))
    for row in range(3):
        total = n = 0
        for s in range(3):
            bits = datagen.draw(config, seed, s, "f")
            cols, vals = datagen.draw(config, seed, s, "v")
            for c, v in zip(cols.tolist(), vals.tolist()):
                if bits[row, c]:
                    total, n = total + v, n + 1
        assert n > 100
        assert ref.answer("sum", ("f", row, "v")) == {"value": total, "count": n}


def test_a_field_that_names_its_draw_is_drawn_by_it(checkout_of, monkeypatch):
    """tests/added/: a configuration whose int field names `dense_int`, a
    draw the harness has never heard of, found by that name in the copy
    that holds the added files; the `sum` reference reads it as it reads
    the default draw."""
    import json

    bench = os.path.join(checkout_of("count-sum-dense"), "benchmark")
    monkeypatch.setattr(plugins, "BENCH_DIR", bench)
    with open(os.path.join(bench, "configs", "dense-1chip.json")) as f:
        config = dict(json.load(f), shards=2)
    assert plugins.draw_name(config, "v") == "dense_int"
    assert plugins.draw_name(config, "f") == "uniform_set"
    spec = config["fields"]["v"]
    assert plugins.draw_of(config, "v").options(spec) == {
        "options": {"type": "int", "min": 0, "max": 500}}
    seed = 2**31 + 7
    cols, vals = datagen.draw(config, seed, 1, "v")
    again = datagen.draw(config, seed, 1, "v")
    assert (cols == again[0]).all() and (vals == again[1]).all()
    # Dense beside sparse_int's 50 a shard, distinct, ascending, skewed.
    assert 0.9 < cols.size / (config["shard_width"] * spec["density"]) < 1.1
    assert (np.diff(cols) > 0).all()
    assert vals.min() >= 0 and vals.max() <= 500
    assert np.median(vals) < vals.mean()
    ref = reference.Reference(config, reference.tables_for_shards(
        config, seed, range(2), {"sum": ["f|v"]}))
    for row in (0, 7):
        total = n = 0
        for s in range(2):
            bits = datagen.draw(config, seed, s, "f")
            cols, vals = datagen.draw(config, seed, s, "v")
            under = bits[row, cols]
            total, n = total + int(vals[under].sum()), n + int(under.sum())
        assert n > 1000
        assert ref.answer("sum", ("f", row, "v")) == {"value": total, "count": n}


@pytest.mark.parametrize("shape, got, want, verdict", [
    ("count", 12, 12, (True, 0)),
    ("count", 15, 12, (False, 3)),
    ("count", None, 12, (False, None)),
    ("count", {"value": 12}, 12, (False, None)),
    ("count", True, 1, (False, None)),
    ("sum", {"value": -8, "count": 3}, {"value": -8, "count": 3}, (True, 0)),
    ("sum", {"value": -9, "count": 3}, {"value": -8, "count": 3}, (False, 1)),
    ("sum", {"value": -8, "count": 7}, {"value": -8, "count": 3}, (False, 4)),
    ("sum", {"value": -8}, {"value": -8, "count": 3}, (False, None)),
    ("sum", 3, {"value": -8, "count": 3}, (False, None)),
    ("sum", {"value": -8, "count": 3, "extra": 1}, {"value": -8, "count": 3}, (False, 0)),
])
def test_compare_says_equal_and_how_far(shape, got, want, verdict):
    assert plugins.load("shapes", shape).compare(got, want) == verdict
