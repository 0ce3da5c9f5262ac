"""The cell chem-tanimoto-1chip's own pieces: the draw's families, the
shape's reference against a sweep bit by bit, the pool and the calls as
functions of the seed, and a planted fault under the timed path. The
cell's rehearsal and its control are test_rehearsal.py's, which runs every
cell of BENCHMARK.json. All at the height benchmark/conftest.py sets for
the tests (the configuration's own is 1.7 M rows)."""

import os
import re

import numpy as np
import pytest

from conftest import BENCH_DIR, config_of, mix_of
from harness import datagen, plugins, reference, traffic
from test_rehearsal import FAULTS, assert_no_result_line, judged_by_shape, rehearse

CELL = "chem-tanimoto-1chip"
CHEM = config_of(CELL)
MIX = mix_of(CELL)
GROUP = MIX["groups"][0]
FIELD = GROUP["field"]
shape = plugins.load("shapes", "tanimoto")
families = plugins.load("draws", "morgan_families")
ROWS = families.height(CHEM["fields"][FIELD])
WIDTH = CHEM["fields"][FIELD]["width_bits"]
SEEDS = (7, 2**31 + 99)


def bits_of(seed: int, shard: int = 0, config: dict = CHEM) -> np.ndarray:
    rows, cols = datagen.ShardData(config, seed, shard).positions(FIELD)
    bits = np.zeros((ROWS, WIDTH), dtype=bool)
    bits[rows, cols] = True
    return bits


def sweep(bits: np.ndarray, m: int, t: int):
    """The answer by AND and popcount over every row, the reference's
    integer test spelled as upstream spells it."""
    inter = (bits & bits[m]).sum(axis=1)
    size = bits.sum(axis=1)
    union = size + size[m] - inter
    ok = (inter > 0) & (inter * 100 // np.maximum(union, 1) >= t)
    return {int(r): int(inter[r]) for r in np.flatnonzero(ok)}


def test_the_rehearsal_height_is_the_environments_and_the_cells_is_its_own():
    spec = CHEM["fields"][FIELD]
    assert spec["rows"] == 1_700_000 and CHEM["shards"] == 1
    assert ROWS == int(os.environ["BENCH_REHEARSAL_ROWS"]) < spec["rows"]
    without = dict(os.environ)
    try:
        del os.environ["BENCH_REHEARSAL_ROWS"]
        assert families.height(spec) == spec["rows"]
    finally:
        os.environ.update(without)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_draw_is_families_over_every_row(seed):
    rows, cols = datagen.ShardData(CHEM, seed, 0).positions(FIELD)
    assert rows.shape == cols.shape and rows.dtype == cols.dtype == np.int32
    assert 0 <= cols.min() and cols.max() < WIDTH
    per_row = np.bincount(rows, minlength=ROWS)
    assert per_row.size == ROWS and per_row.min() >= 1
    assert 50 <= per_row.mean() <= 64          # about 57 bits a molecule
    again = datagen.ShardData(CHEM, seed, 0).positions(FIELD)
    assert np.array_equal(rows, again[0]) and np.array_equal(cols, again[1])
    other = datagen.ShardData(CHEM, seed + 1, 0).positions(FIELD)
    assert other[0].size != rows.size or not np.array_equal(other[1], cols)


@pytest.mark.parametrize("seed", SEEDS)
def test_family_sizes_are_heavy_tailed_and_hold_every_row(seed):
    spec = CHEM["fields"][FIELD]
    sizes = families.family_sizes(
        np.random.default_rng(seed), ROWS, spec["family_alpha"], spec["family_max"]
    )
    assert sizes.sum() == ROWS and sizes.min() >= 1
    assert sizes.max() <= spec["family_max"]
    assert np.median(sizes) <= 2 and sizes.max() >= 200


@pytest.mark.parametrize("seed", SEEDS)
def test_answers_run_from_the_molecule_alone_to_hundreds(seed):
    """The small-size analogue of what the configuration assumes at full
    height (median small, maximum in the thousands): every answer holds
    its own molecule, with the largest count (a twin may share it), most hold little else, some hold hundreds."""
    names = shape.tables_needed(MIX["groups"], CHEM)
    tables = reference.tables_for_shards(CHEM, seed, [0], {"tanimoto": names})
    ref = reference.Reference(CHEM, tables)
    calls = list(shape.draw(GROUP, CHEM, np.random.default_rng([seed, 0, 0]), 600))
    sizes = []
    for call in calls:
        got = ref.answer("tanimoto", call)
        own = {p["id"]: p["count"] for p in got}.get(call[1])
        assert own is not None and own == got[0]["count"]   # itself, or a twin
        assert [(-p["count"], p["id"]) for p in got] == sorted(
            (-p["count"], p["id"]) for p in got
        )
        sizes.append(len(got))
    assert min(sizes) == 1 and np.median(sizes) <= 8 and max(sizes) >= 100
    lowest = [len(ref.answer("tanimoto", (f, m, 70))) for f, m, _ in calls[:200]]
    highest = [len(ref.answer("tanimoto", (f, m, 90))) for f, m, _ in calls[:200]]
    assert all(h <= l for h, l in zip(highest, lowest)) and sum(highest) < sum(lowest)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_equals_a_sweep_of_every_row(seed):
    names = shape.tables_needed(MIX["groups"], CHEM)
    tables = reference.tables_for_shards(CHEM, seed, [0], {"tanimoto": names})
    ref = reference.Reference(CHEM, tables)
    bits = bits_of(seed)
    calls = list(shape.draw(GROUP, CHEM, np.random.default_rng([seed, 3, 0]), 40))
    assert {t for _, _, t in calls} <= set(GROUP["thresholds"])
    for call in calls:
        got = ref.answer("tanimoto", call)
        want = sweep(bits, call[1], call[2])
        assert {p["id"]: p["count"] for p in got} == want
        assert shape.compare(got, got) == (True, 0)


def test_two_shards_are_tested_shard_by_shard_and_added_up():
    config = dict(CHEM, shards=2)
    names = shape.tables_needed(MIX["groups"], config)
    tables = reference.tables_for_shards(config, 11, [0, 1], {"tanimoto": names})
    assert tables["tanimoto"][names[0]].shape[0] == 2
    ref = reference.Reference(config, tables)
    halves = [bits_of(11, s, config) for s in (0, 1)]
    for call in list(shape.draw(GROUP, config, np.random.default_rng([11, 0, 0]), 12)):
        want: dict = {}
        for bits in halves:
            for r, k in sweep(bits, call[1], call[2]).items():
                want[r] = want.get(r, 0) + k
        got = ref.answer("tanimoto", call)
        assert {p["id"]: p["count"] for p in got} == want
        assert [(-p["count"], p["id"]) for p in got] == sorted(
            (-k, r) for r, k in want.items()
        )


def test_the_pool_and_the_calls_are_functions_of_the_seed():
    pool = shape.pool_of(7, ROWS, GROUP["pool"])
    assert pool.size == GROUP["pool"] == len(set(pool.tolist()))
    assert np.array_equal(pool, shape.pool_of(7, ROWS, GROUP["pool"]))
    assert not np.array_equal(pool, shape.pool_of(8, ROWS, GROUP["pool"]))
    stream = traffic.RequestStream(GROUP, CHEM, 7, 5)
    body, calls = stream.next()
    assert len(calls) == 1 and calls[0][1] in set(pool.tolist())
    field, m, t = calls[0]
    assert body == f"TopN({field}, Row({field}={m}), tanimotoThreshold={t})".encode()
    again = traffic.RequestStream(GROUP, CHEM, 7, 5).next()
    assert again[0] == body
    seen = {traffic.RequestStream(GROUP, CHEM, 7, c).next()[0] for c in range(64)}
    assert len(seen) > 32      # the clients ask different things


def test_the_tables_name_says_what_was_asked():
    names = shape.tables_needed(MIX["groups"], CHEM)
    assert names == [f"{FIELD}.hits.{GROUP['pool']}.{min(GROUP['thresholds'])}"]
    assert shape.asked_of(names[0]) == (FIELD, GROUP["pool"], min(GROUP["thresholds"]))
    both = shape.tables_needed(
        [GROUP, dict(GROUP, thresholds=[60, 95])], CHEM
    )
    assert both == [f"{FIELD}.hits.{GROUP['pool']}.60"]


@pytest.mark.parametrize("got, want, verdict", [
    ([{"id": 3, "count": 40}], [{"id": 3, "count": 40}], (True, 0)),
    ([{"id": 3, "count": 41}], [{"id": 3, "count": 40}], (False, 1)),
    ([], [{"id": 3, "count": 40}], (False, 40)),                       # a missing row
    ([{"id": 3, "count": 40}, {"id": 9, "count": 33}],
     [{"id": 3, "count": 40}], (False, 33)),                           # an extra row
    ([{"id": 9, "count": 40}, {"id": 3, "count": 40}],
     [{"id": 3, "count": 40}, {"id": 9, "count": 40}], (False, 0)),    # the order alone
    ([{"key": "x"}], [{"id": 3, "count": 40}], (False, None)),
    (None, [], (False, None)),
])
def test_compare_is_exact_and_counts_a_missing_row_whole(got, want, verdict):
    assert shape.compare(got, want) == verdict


def test_the_mix_is_what_the_cell_states():
    assert [g["clients"] for g in MIX["groups"]] == [64]
    assert GROUP["loop"] == "closed" and GROUP["calls_per_request"] == 1
    assert GROUP["thresholds"] == [70, 75, 80, 85, 90] and GROUP["pool"] == 1024
    assert MIX["generator_processes"] == 8 and MIX["trace_seconds"] == 5.0
    assert CHEM["server"] == {} and CHEM["reduced"] == [] and CHEM["architecture"] is None
    assert sorted(CHEM["guarantees"]) == ["answers", "durability", "read_your_writes"]


def test_altered_tanimoto_is_not_correct(tmp_path):
    """A count altered where the Tanimoto leg returns, every third answer
    that holds a row: those requests come out wrong, by 1, and the run is
    not correct."""
    root = os.path.dirname(BENCH_DIR)
    done, verdict = rehearse(
        root, CELL, tmp_path, "--launcher", os.path.join(FAULTS, "alter_tanimoto.py")
    )
    assert_no_result_line(done)
    assert verdict is not None and verdict["correct"] is False
    by_shape = judged_by_shape(done)
    assert 0 < by_shape["tanimoto"]["wrong"] < by_shape["tanimoto"]["requests"]
    assert verdict["failed"] == by_shape["tanimoto"]["wrong"]
    worst = int(re.search(r"worst_abs_count_error=(\d+)\(limit 0\)", done.stderr).group(1))
    assert worst >= 1
