"""The call shape `taxi_page` and the draws of taxi-1chip: the four tables
on a hand-worked pair of shards, the answers' form, the comparison, and the
identity that the int draw's planes decode to the values the reference
sums. Then the cell itself, rehearsed on the CPU at 5 shards: what the
rehearsals of test_rehearsal.py and test_layer_metrics.py assert of a cell
whose mix counts (values shipped through `import`, a Count altered, three
trace metrics by name) restated for a cell that ships planes and asks a
page: the loader's requests, a Sum altered where `bsi_sum` returns it, and
every per-layer metric that needs no device trace."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, BENCH_DIR, REPO
from harness import datagen, plugins, reference, traffic
from test_layer_metrics import REHEARSE
from test_rehearsal import FAULTS, assert_no_result_line, judged_by_shape, rehearse

CELL = "taxi-page-1chip"

page = plugins.load("shapes", "taxi_page")
int_planes = plugins.load("draws", "int_planes")

HAND = {
    "name": "hand", "index": "hand", "shards": 2, "shard_width": 128,
    "fields": {
        "cab_type": {"type": "mutex", "rows": 3},
        "passenger_count": {"type": "mutex", "rows": 3},
        "pickup_year": {"type": "mutex", "rows": 2},
        "dist_miles": {"type": "set", "rows": 3},
        "total_amount": {"type": "int", "min": 0, "max": 63},
    },
}


def rows_of(width: int, off: int, *rows) -> np.ndarray:
    bits = np.zeros((len(rows), width), dtype=bool)
    for r, cols in enumerate(rows):
        bits[r, [c + off for c in cols]] = True
    return bits


def hand(shard: int) -> dict:
    """Sixteen rides a shard, at columns 0..15 of shard 0 and 64..79 of
    shard 1. Passengers: ride 0 has none, rides 1..11 one, rides 12..15 two.
    Year: rides 0..7 the first, 8..15 the second. Miles: even rides 0, odd
    rides up to 9 one mile, odd rides from 11 two. Amount: ride c pays c + 1
    in shard 0 and twice that in shard 1, but ride 15 holds no amount (its
    magnitude bits are set all the same: without the exists bit they count
    nothing). Cab type: shard 0 has rides 0..9 yellow, 10..12 green, 13..15
    the third; shard 1 is all green."""
    off = 64 * shard
    amounts = np.zeros(128, dtype=np.int64)
    amounts[off:off + 16] = (np.arange(16) + 1) * (1 + shard)
    planes = np.zeros((2 + 6, 128), dtype=bool)
    planes[0, off:off + 15] = True
    for bit in range(6):
        planes[2 + bit] = (amounts >> bit) & 1
    odd = range(1, 16, 2)
    return {
        "cab_type": (rows_of(128, off, range(10), range(10, 13), range(13, 16))
                     if shard == 0 else rows_of(128, off, [], range(16), [])),
        "passenger_count": rows_of(128, off, [0], range(1, 12), range(12, 16)),
        "pickup_year": rows_of(128, off, range(8), range(8, 16)),
        "dist_miles": rows_of(128, off, range(0, 16, 2),
                              [c for c in odd if c <= 9],
                              [c for c in odd if c >= 11]),
        "total_amount": planes,
    }


@pytest.fixture(scope="module")
def ref():
    wanted = {"taxi_page": page.tables_needed([], HAND)}
    return reference.Reference(HAND, reference.tables_for_shards(
        HAND, 0, range(2), wanted, given=hand))


def group(fields, rows, count):
    return {"group": [{"field": f, "rowID": r} for f, r in zip(fields, rows)],
            "count": count}


def test_hand_worked_tables_of_one_shard():
    t = page.shard_tables(HAND, list(page.TABLES),
                          datagen.ShardData(HAND, 0, 0, hand(0)))
    assert t["cab"].tolist() == [10, 3, 3]
    # (sum, count) under no, one, two passengers: 1; 2 + .. + 12; 13 + 14 + 15.
    assert t["amount_by_passengers"].tolist() == [[1, 1], [77, 11], [42, 3]]
    assert t["passengers_year"].tolist() == [[1, 0], [7, 4], [0, 4]]
    assert t["year_passengers_miles"].tolist() == [
        [[1, 0, 0], [3, 4, 0], [0, 0, 0]],
        [[0, 0, 0], [2, 1, 1], [2, 0, 2]],
    ]


def test_hand_worked_answers_over_both_shards(ref):
    # Green leads once shard 1, all green, is counted; ties go by id.
    assert ref.answer("taxi_page", ("topn", 0)) == [
        {"id": 1, "count": 19}, {"id": 0, "count": 10}, {"id": 2, "count": 3}]
    assert [ref.answer("taxi_page", ("sum", k)) for k in range(3)] == [
        {"value": 3, "count": 2}, {"value": 231, "count": 22},
        {"value": 126, "count": 6}]
    two = ("passenger_count", "pickup_year")
    assert ref.answer("taxi_page", ("groupby2", 0)) == [
        group(two, (0, 0), 2), group(two, (1, 0), 14), group(two, (1, 1), 8),
        group(two, (2, 1), 8)]
    three = ("pickup_year", "passenger_count", "dist_miles")
    assert ref.answer("taxi_page", ("groupby3", 0)) == [
        group(three, (0, 0, 0), 2), group(three, (0, 1, 0), 6),
        group(three, (0, 1, 1), 8), group(three, (1, 1, 0), 4),
        group(three, (1, 1, 1), 2), group(three, (1, 1, 2), 2),
        group(three, (1, 2, 0), 4), group(three, (1, 2, 2), 4)]


def test_a_tie_goes_to_the_lower_row():
    totals = {"cab": np.array([5, 9, 9, 0])}
    assert page.answer(HAND, totals, ("topn", 0)) == [
        {"id": 1, "count": 9}, {"id": 2, "count": 9}, {"id": 0, "count": 5}]


def test_group_counts_do_not_need_one_row_a_column():
    """The tables count columns in the AND of the rows, bit by bit."""
    rng = np.random.default_rng(5)
    a, b, c = (rng.random((r, 300)) < 0.4 for r in (3, 4, 5))
    got = page.group_counts([a, b, c])
    for i in range(3):
        for j in range(4):
            for k in range(5):
                assert got[i, j, k] == int((a[i] & b[j] & c[k]).sum())


def test_the_page_is_thirteen_calls_in_the_sources_order():
    with open(os.path.join(BENCH_DIR, "configs", "taxi-1chip.json")) as f:
        config = json.load(f)
    mix = traffic.load_mix(os.path.join(BENCH_DIR, "traffic", "taxi-page-c16.json"))
    (grp,) = mix["groups"]
    assert (grp["clients"], grp["calls_per_request"], grp["loop"]) == (16, 13, "closed")
    bodies = set()
    for client in (0, 7, 15):
        stream = traffic.RequestStream(grp, config, 3210000011, client, 0)
        for _ in range(traffic.CHUNK + 3):  # past one drawn chunk
            body, calls = stream.next()
            assert calls == page.page(config)
            bodies.add(body)
    (body,) = bodies
    want = ("TopN(cab_type)"
            + "".join(f"Sum(Row(passenger_count={k}), field=total_amount)"
                      for k in range(10))
            + "GroupBy(Rows(passenger_count), Rows(pickup_year))"
            + "GroupBy(Rows(pickup_year), Rows(passenger_count), Rows(dist_miles))")
    assert body.decode() == want
    with pytest.raises(ValueError):
        next(page.draw(dict(grp, calls_per_request=5), config, None, 5))


G = ("passenger_count", "pickup_year")


@pytest.mark.parametrize("got,want,verdict", [
    ({"value": 7, "count": 2}, {"value": 7, "count": 2}, (True, 0)),
    ({"value": 0, "count": 0}, {"value": 70, "count": 2}, (False, 70)),
    ([{"id": 0, "count": 9}], [{"id": 0, "count": 9}], (True, 0)),
    ([{"id": 0, "count": 9}, {"id": 1, "count": 4}],
     [{"id": 0, "count": 12}, {"id": 1, "count": 4}], (False, 3)),
    # The same counts in another order are another answer.
    ([{"id": 1, "count": 4}, {"id": 0, "count": 9}],
     [{"id": 0, "count": 9}, {"id": 1, "count": 4}], (False, 0)),
    ([group(G, (1, 0), 5)], [group(G, (1, 0), 5), group(G, (1, 1), 2)], (False, 2)),
    ([group(G, (1, 0), 5)], [group(G, (1, 0), 5)], (True, 0)),
    (17, {"value": 7, "count": 2}, (False, None)),
    (None, [{"id": 0, "count": 9}], (False, None)),
    ({"value": 7}, {"value": 7, "count": 2}, (False, None)),
])
def test_compare_says_equal_and_how_far(got, want, verdict):
    assert page.compare(got, want) == verdict


# -- the draws of taxi-1chip ------------------------------------------------


@pytest.fixture(scope="module")
def taxi():
    with open(os.path.join(BENCH_DIR, "configs", "taxi-1chip.json")) as f:
        return dict(json.load(f), shards=16)


@pytest.mark.parametrize("seed,shard", [(11, 0), (2**31 + 12345, 7)])
def test_the_planes_decode_to_the_values_that_were_sliced(taxi, seed, shard):
    """What is shipped (the planes) and what the reference sums (its own
    decoding of them) are the draw's values, column by column."""
    spec = taxi["fields"]["total_amount"]
    planes = datagen.draw(taxi, seed, shard, "total_amount")
    assert planes.shape == (16, taxi["shard_width"]) and planes.dtype == bool
    vals = int_planes.values(taxi, seed, shard, "total_amount")
    holds, decoded = page.decode_planes(planes)
    assert holds.all() and not planes[1].any()
    assert np.array_equal(decoded, vals)
    assert vals.min() >= spec["min"] and vals.max() <= spec["max"]
    # Skewed, with the outliers that keep all 14 planes in use in a shard.
    assert 13 < vals[vals < 1000].mean() < 16
    assert planes[15].any() and 40 <= (vals >= 1000).sum() <= 180
    assert np.array_equal(planes, datagen.draw(taxi, seed, shard, "total_amount"))


def test_every_ride_sits_in_one_row_of_each_field(taxi):
    for name in ("cab_type", "passenger_count", "pickup_year", "dist_miles"):
        bits = datagen.draw(taxi, 5, 3, name)
        assert bits.shape == (taxi["fields"][name]["rows"], taxi["shard_width"])
        assert (bits.sum(axis=0) == 1).all(), name


def test_the_rows_hold_the_shares_the_configuration_states(taxi):
    width = taxi["shard_width"]
    for name in ("cab_type", "passenger_count"):
        spec = taxi["fields"][name]
        assert abs(sum(spec["shares"]) - 1) < 1e-9 and len(spec["shares"]) == spec["rows"]
        got = datagen.draw(taxi, 5, 3, name).sum(axis=1) / width
        assert np.abs(got - np.array(spec["shares"])).max() < 0.002, name
    miles = datagen.draw(taxi, 5, 3, "dist_miles").sum(axis=1) / width
    assert abs(miles[0] - (1 - np.exp(-1 / 2.9))) < 0.002 and miles[1] < miles[0]
    # The outliers alone reach the highest rows: a ride in a million at the
    # configuration's share, so at a share of one in a thousand one shard
    # holds rides in the rows from 56 on, and at none it holds none there.
    assert taxi["fields"]["dist_miles"]["outlier_share"] == 1e-6
    for share, tall in ((1e-3, True), (0.0, False)):
        spec = dict(taxi["fields"]["dist_miles"], outlier_share=share)
        odd = dict(taxi, fields=dict(taxi["fields"], dist_miles=spec))
        assert datagen.draw(odd, 5, 3, "dist_miles")[56:].any() == tall
    # Shard s of 16 holds year floor(8 s / 16) in nine tenths of its
    # columns and the next in the rest; the last year stays.
    for shard, home in ((0, 0), (3, 1), (15, 7)):
        years = datagen.draw(taxi, 5, shard, "pickup_year").sum(axis=1) / width
        if home == 7:
            assert years[7] == 1.0
        else:
            assert abs(years[home] - 0.9) < 0.002
            assert abs(years[home + 1] - 0.1) < 0.002


# -- the cell, rehearsed on the CPU -------------------------------------------


def test_the_loader_ships_every_field_as_one_bitmap_a_shard(tmp_path):
    done, verdict = rehearse(REPO, CELL, tmp_path)
    assert_no_result_line(done)
    assert verdict is not None and verdict["correct"] is True, done.stderr[-3000:]
    assert verdict["attempted"] > 0 and verdict["failed"] == 0
    shipped = re.search(r"(\d+) import-roaring requests, (\d+) import requests "
                        r"of (\d+) int values", done.stderr)
    assert [int(x) for x in shipped.groups()] == [5 * 5, 0, 0]
    by_shape = judged_by_shape(done)
    assert list(by_shape) == ["taxi_page"]
    assert by_shape["taxi_page"]["requests"] == verdict["attempted"]
    assert "device_fallbacks=0.0(limit 0)" in done.stderr


def test_a_sum_altered_is_not_correct(tmp_path):
    """Every third `bsi_sum` one too large: pages come out wrong, by 1."""
    done, verdict = rehearse(REPO, CELL, tmp_path, "--launcher",
                             os.path.join(FAULTS, "alter_sum.py"))
    assert_no_result_line(done)
    assert verdict is not None and verdict["correct"] is False
    assert 0 < verdict["failed"] <= verdict["attempted"]
    assert "worst_abs_count_error=1(limit 0)" in done.stderr


def test_the_rehearsal_reads_the_cells_metrics(tmp_path):
    """The real server on the CPU's devices, the real readers: every
    per-layer metric of the cell that does not read a device trace has a
    value, and no other has. The numbers are a CPU's and are not results."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    done = subprocess.run(
        [sys.executable, "-c", REHEARSE.format(
            bench=BENCH_DIR, cell=CELL, root=str(tmp_path / "bench_data"))],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    lines = [l for l in done.stdout.splitlines() if l.startswith("METRICS ")]
    assert done.returncode == 0 and lines, done.stderr[-3000:]
    got = json.loads(lines[-1][len("METRICS "):])
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert {m["name"] for m in mine if m["workloads"] == [CELL]} == {
        "bsi_sum_roofline", "sum_call_ms", "topn_call_ms", "groupby_call_ms",
        "launches_per_page"}
    assert set(got) == {m["name"] for m in mine if m["source"] != "device_trace"}
    assert 0 < got["launches_per_page"] <= 10
    assert all(got[k] > 0 for k in ("sum_call_ms", "topn_call_ms",
                                    "groupby_call_ms", "exec_host_ms",
                                    "holder_open_s", "stack_build_s",
                                    "program_compile_s"))
