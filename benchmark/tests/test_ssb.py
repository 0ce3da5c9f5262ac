"""The cell ssb-flights-1chip's own pieces: the draws' hierarchies and
measures, the shape's reference against an evaluation of the stream's
sixteen calls column by column, the refusal of an answer without sums,
and a planted fault under the timed path. The cell's rehearsal and
its control are test_rehearsal.py's, which runs every cell of
BENCHMARK.json."""

import os
import re

import numpy as np
import pytest

from conftest import config_of
from harness import datagen, plugins, reference
from test_rehearsal import FAULTS, assert_no_result_line, judged_by_shape, rehearse

CELL = "ssb-flights-1chip"
SSB = config_of(CELL)
shape = plugins.load("shapes", "ssb")
lineorder = plugins.load("draws", "ssb_lineorder")
SHARDS, SEED = 2, 2**31 + 38


def columns(seed: int = SEED, shards: int = SHARDS) -> dict:
    """Every attribute and measure of the lineorder rows of `shards`
    shards, as the loader ships them: the tall mutex fields' positions,
    the others' bits and the int fields' planes, decoded here."""
    config = dict(SSB, shards=shards)
    out: dict = {}
    for s in range(shards):
        data = datagen.ShardData(config, seed, s)
        for name, spec in SSB["fields"].items():
            if spec["type"] == "int":
                planes = data.bits(name)
                vals = sum(planes[2 + i].astype(np.int64) << i
                           for i in range(planes.shape[0] - 2))
                assert planes[0].all() and not planes[1].any()
            elif spec["draw"] == "ssb_lineorder":
                rows, cols = data.positions(name)
                vals = np.empty(SSB["shard_width"], dtype=np.int64)
                vals[cols] = rows
                assert np.array_equal(np.sort(cols), np.arange(SSB["shard_width"]))
            else:
                bits = data.bits(name)
                assert (bits.sum(axis=0) == 1).all()  # one row a column
                vals = bits.argmax(axis=0)
            out.setdefault(name, []).append(vals)
    return {k: np.concatenate(v) for k, v in out.items()}


@pytest.fixture(scope="module")
def cols():
    return columns()


def test_the_deployment_is_sf100_s_share_at_the_published_widths():
    assert SSB["scale_factor"] == 100 and SSB["reduced"] == ["shards"]
    assert -(-600_037_902 // SSB["shard_width"]) == 573
    assert SSB["shards"] == -(-573 // 16) == 36
    rows = {n: s["rows"] for n, s in SSB["fields"].items() if s["type"] == "mutex"}
    assert rows == {"d_year": 7, "d_yearmonthnum": 84, "d_weeknuminyear": 53,
                    "lo_discount": 11, "c_region": 5, "c_nation": 25,
                    "c_city": 250, "s_region": 5, "s_nation": 25, "s_city": 250,
                    "p_mfgr": 5, "p_category": 25, "p_brand1": 1000}
    assert lineorder.table_sizes(100) == (3_000_000, 200_000, 1_400_000)


def test_the_draws_keep_the_schema_s_hierarchies_and_measures(cols):
    for who in ("c", "s"):
        assert np.array_equal(cols[f"{who}_city"] // 10, cols[f"{who}_nation"])
        assert np.array_equal(cols[f"{who}_nation"] // 5, cols[f"{who}_region"])
        assert len(np.unique(cols[f"{who}_city"])) == 250
    assert np.array_equal(cols["p_brand1"] // 40, cols["p_category"])
    assert np.array_equal(cols["p_category"] // 5, cols["p_mfgr"])
    assert len(np.unique(cols["p_brand1"])) == 1000
    assert np.array_equal(cols["d_yearmonthnum"] // 12, cols["d_year"])
    assert cols["d_weeknuminyear"].max() == 52
    q, d = cols["lo_quantity"], cols["lo_discount"]
    assert q.min() == 1 and q.max() == 50 and d.min() == 0 and d.max() == 10
    price = cols["lo_extendedprice"] // q
    assert np.array_equal(price * q, cols["lo_extendedprice"])
    assert 90_000 <= price.min() and price.max() <= 209_900
    assert np.array_equal(cols["lo_revenue"],
                          cols["lo_extendedprice"] * (100 - d) // 100)
    assert np.array_equal(cols["lo_supplycost"], 6 * price // 10)
    # Each field of a shard was drawn from the same rows, whatever its
    # position in the configuration: a second draw of one agrees.
    again = datagen.ShardData(SSB, SEED, 1).positions("c_city")[0]
    assert np.array_equal(again, cols["c_city"][SSB["shard_width"]:])


def evaluate(cols: dict, call) -> list[dict]:
    """A call's answer column by column: its PQL filter's leaves as masks
    over the lineorder rows, the groups of the rows it keeps."""
    text = shape.pql(call)
    keep = np.ones(cols["d_year"].size, dtype=bool)
    inner = text[text.index("filter=Intersect(") + 17:text.index(", aggregate=") - 1]
    for part in re.findall(r"Union\([^()]*(?:\([^()]*\)[^()]*)*\)|Row\([^()]*\)", inner):
        if part.startswith("Union("):
            mask = np.zeros_like(keep)
            for f, r in re.findall(r"Row\((\w+)=(\d+)\)", part):
                mask |= cols[f] == int(r)
        elif m := re.fullmatch(r"Row\((\d+) <= (\w+) <= (\d+)\)", part):
            lo, f, hi = m.groups()
            mask = (cols[f] >= int(lo)) & (cols[f] <= int(hi))
        elif m := re.fullmatch(r"Row\((\w+) < (\d+)\)", part):
            mask = cols[m.group(1)] < int(m.group(2))
        else:
            f, r = re.fullmatch(r"Row\((\w+)=(\d+)\)", part).groups()
            mask = cols[f] == int(r)
        keep &= mask
    query, agg, _ = call
    fields = shape.grouping(query)
    keys = np.stack([cols[f][keep] for f in fields], axis=1)
    uniq, inverse = np.unique(keys, axis=0, return_inverse=True)
    counts = np.bincount(inverse.ravel(), minlength=len(uniq))
    sums = np.zeros(len(uniq), dtype=np.int64)
    np.add.at(sums, inverse.ravel(), cols[agg][keep])
    return [{"group": [{"field": f, "rowID": int(r)} for f, r in zip(fields, k)],
             "count": int(c), "sum": int(s)}
            for k, c, s in zip(uniq, counts, sums)]


def test_the_reference_equals_the_stream_evaluated_column_by_column(cols):
    config = dict(SSB, shards=SHARDS)
    tables = reference.tables_for_shards(
        config, SEED, range(SHARDS), {"ssb": shape.tables_needed([], config)}
    )
    ref = reference.Reference(config, tables)
    rng = np.random.default_rng(SEED)
    groups = 0
    for _ in range(4):
        calls = shape.stream(rng)
        assert len(calls) == 16
        assert [(q, a) for q, a, _ in calls] == list(shape.STREAM)
        for call in calls:
            want = evaluate(cols, call)
            assert shape.compare(ref.answer("ssb", call), want) == (True, 0), call
            groups += len(want)
    assert groups > 4 * 16 * 10


def test_an_answer_without_sums_is_not_correct():
    """A server without the aggregate answers counts alone: `compare`
    fails it, whatever the counts."""
    summed = [{"group": [{"field": "d_year", "rowID": 0},
                         {"field": "p_brand1", "rowID": 3}], "count": 9, "sum": 1234}]
    plain = [{k: v for k, v in summed[0].items() if k != "sum"}]
    assert shape.compare(summed, summed) == (True, 0)
    assert shape.compare(plain, summed) == (False, None)
    assert shape.compare([], summed) == (False, 1234)


def test_q4_3_asks_the_customer_region_of_its_supplier_nation():
    call = ("q4.3", shape.REVENUE, (17, 3, 9))
    assert shape.pql(call).startswith(
        "GroupBy(Rows(d_year), Rows(s_city), Rows(p_brand1), filter=Intersect("
        "Row(c_region=3), Row(s_nation=17), Union(Row(d_year=3), Row(d_year=4)), "
        "Row(p_category=9)), aggregate=Sum(field=lo_revenue))")


def test_a_sum_altered_where_the_group_sum_returns_is_not_correct(tmp_path):
    done, verdict = rehearse(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        CELL, tmp_path, "--launcher", os.path.join(FAULTS, "alter_group_sum.py"),
    )
    assert_no_result_line(done)
    assert verdict is not None and verdict["correct"] is False
    by_shape = judged_by_shape(done)
    assert 0 < by_shape["ssb"]["wrong"] == verdict["failed"]
    assert "worst_abs_count_error=1(limit 0)" in done.stderr
