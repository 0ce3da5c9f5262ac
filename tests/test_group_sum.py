"""GroupBy(…, aggregate=Sum(field=…)): each group's count and the sum of
an int field over the group's columns that hold a value.

Three ways to answer, one reference. The reference is written here in
numpy over the columns' own draws (a row id a grouped field, a value or
none an int field) and knows nothing of planes or bitmaps: a group's
count is a `np.bincount` of the filtered columns by group, its sum a
`np.bincount` of their values. The answers compared with it are the
device executor's (`pilosa_group_sum` on JAX's CPU devices, on one and
over a mesh of them), the executor's host iterator over the same
backend, and the executor over `exec/cpu.py`'s backend.
"""

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import options_for_int, options_for_mutex
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.cpu import CPUBackend, QueryError
from pilosa_tpu.exec.result import GroupCounts, result_to_json
from pilosa_tpu.exec.tpu import TPUBackend
from pilosa_tpu.ops.blocks import WORD_LINES
from pilosa_tpu.parallel import ShardMesh
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import fastjson
from pilosa_tpu.utils.stats import global_stats

N_COLUMNS = 2400
#: Grouped fields, rows each: `big` is as tall as SSB's p_brand1.
ROWS = {"a": 4, "b": 5, "c": 3, "big": 1000, "f": 4}


def served(path: str) -> float:
    return global_stats.counter_totals("groupby_served_total").get(
        f'groupby_served_total{{path="{path}"}}', 0.0
    )


def launches(kind: str) -> float:
    return global_stats.counter_totals("device_launches_total").get(
        f'device_launches_total{{kind="{kind}"}}', 0.0
    )


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """(holder, per-column draws) over two shards: every grouped field
    holds one row a column; `v` (signed, -1000..100000) and `u` (0..5000)
    hold a value in most columns and none in the rest; `w` is the int
    field the filters compare."""
    rng = np.random.default_rng(20261015)
    cols = np.sort(rng.choice(2 * SHARD_WIDTH, N_COLUMNS, replace=False))
    draws = {name: rng.integers(0, n, cols.size) for name, n in ROWS.items()}
    # The big field is tall, and sparse under any filter.
    draws["big"] = rng.integers(0, 40, cols.size) * 25 + rng.integers(0, 2, cols.size)
    draws["big"][0] = ROWS["big"] - 1
    draws["v"] = np.where(rng.random(cols.size) < 0.3,
                          rng.integers(-1000, 0, cols.size),
                          rng.integers(0, 100001, cols.size))
    draws["v_held"] = rng.random(cols.size) < 0.85
    draws["u"] = rng.integers(0, 5001, cols.size)
    draws["u_held"] = rng.random(cols.size) < 0.9
    draws["w"] = rng.integers(0, 100, cols.size)
    holder = Holder(str(tmp_path_factory.mktemp("group_sum") / "data")).open()
    idx = holder.create_index("i")
    for name in ROWS:
        idx.create_field(name, options_for_mutex())
        idx.field(name).import_bits(
            draws[name].astype(np.uint64), cols.astype(np.uint64)
        )
    for name, lo, hi in (("v", -1000, 100000), ("u", 0, 5000), ("w", 0, 99)):
        idx.create_field(name, options_for_int(lo, hi))
        held = draws.get(name + "_held", np.ones(cols.size, bool))
        idx.field(name).import_value(cols[held].astype(np.uint64),
                                     draws[name][held])
    draws["cols"] = cols
    yield holder, draws
    holder.close()


def reference(draws, fields, keep, agg, limit=None, offset=0):
    """The groups of `fields` over the columns `keep` selects, in
    odometer order (the last field fastest), with count and sum."""
    heights = [ROWS[f] for f in fields]
    key = np.zeros(draws["cols"].size, dtype=np.int64)
    for f, h in zip(fields, heights):
        key = key * h + draws[f]
    size = int(np.prod(heights))
    count = np.bincount(key[keep], minlength=size)
    held = keep & draws[agg + "_held"]
    total = np.bincount(key[held], weights=draws[agg][held].astype(np.float64),
                        minlength=size)
    out = []
    for k in np.flatnonzero(count):
        rows = np.unravel_index(k, heights)
        out.append({
            "group": [{"field": f, "rowID": int(r)} for f, r in zip(fields, rows)],
            "count": int(count[k]),
            "sum": int(round(total[k])),
        })
    out = out[offset:]
    return out if limit is None else out[:limit]


# (PQL, fields, the filter as a function of the draws, aggregate, limit,
# offset)
CASES = {
    "one_field": ("GroupBy(Rows(a), aggregate=Sum(field=v))",
                  ["a"], lambda d: np.ones(d["cols"].size, bool), "v", None, 0),
    "two_fields_union_filter": (
        "GroupBy(Rows(a), Rows(b), filter=Union(Row(f=0), Row(f=1)), "
        "aggregate=Sum(field=v))",
        ["a", "b"], lambda d: d["f"] <= 1, "v", None, 0),
    "three_fields_bsi_less_than": (
        "GroupBy(Rows(a), Rows(b), Rows(c), "
        "filter=Intersect(Row(f=2), Row(w < 50)), aggregate=Sum(field=v))",
        ["a", "b", "c"], lambda d: (d["f"] == 2) & (d["w"] < 50), "v", None, 0),
    "thousand_rows_bsi_between": (
        "GroupBy(Rows(big), Rows(a), filter=Intersect(Union(Row(f=0), "
        "Row(f=3)), Row(10 <= w <= 60)), aggregate=Sum(field=v))",
        ["big", "a"],
        lambda d: np.isin(d["f"], [0, 3]) & (d["w"] >= 10) & (d["w"] <= 60),
        "v", None, 0),
    "unsigned_field": ("GroupBy(Rows(b), Rows(c), aggregate=Sum(field=u))",
                       ["b", "c"], lambda d: np.ones(d["cols"].size, bool),
                       "u", None, 0),
    "limit_offset": ("GroupBy(Rows(a), Rows(b), limit=5, offset=3, "
                     "aggregate=Sum(field=v))",
                     ["a", "b"], lambda d: np.ones(d["cols"].size, bool),
                     "v", 5, 3),
    "empty": ("GroupBy(Rows(a), filter=Row(f=9), aggregate=Sum(field=v))",
              ["a"], lambda d: np.zeros(d["cols"].size, bool), "v", None, 0),
    # Filters that name rows of a grouped field: its live rows are among
    # them, and only they are counted.
    "filter_row_of_a_grouped_field": (
        "GroupBy(Rows(a), Rows(b), filter=Intersect(Row(a=1), Row(w < 50)), "
        "aggregate=Sum(field=v))",
        ["a", "b"], lambda d: (d["a"] == 1) & (d["w"] < 50), "v", None, 0),
    "filter_union_of_a_tall_field": (
        "GroupBy(Rows(big), Rows(a), filter=Intersect(Union(Row(big=25), "
        "Row(big=26), Row(big=51), Row(big=999)), Row(f=0)), "
        "aggregate=Sum(field=v))",
        ["big", "a"],
        lambda d: np.isin(d["big"], [25, 26, 51, 999]) & (d["f"] == 0),
        "v", None, 0),
    "two_conjuncts_name_one_field": (
        "GroupBy(Rows(a), Rows(c), filter=Intersect(Union(Row(a=0), Row(a=1), "
        "Row(a=2)), Union(Row(a=1), Row(a=2), Row(a=3))), "
        "aggregate=Sum(field=u))",
        ["a", "c"], lambda d: np.isin(d["a"], [1, 2]), "u", None, 0),
    "an_id_past_the_stack": (
        "GroupBy(Rows(big), filter=Intersect(Union(Row(big=50), "
        "Row(big=4000)), Row(w >= 20)), aggregate=Sum(field=v))",
        ["big"], lambda d: (d["big"] == 50) & (d["w"] >= 20), "v", None, 0),
    "bare_row_filter": (
        "GroupBy(Rows(big), Rows(b), filter=Row(big=26), aggregate=Sum(field=v))",
        ["big", "b"], lambda d: d["big"] == 26, "v", None, 0),
    "bare_union_of_most_of_a_short_field": (
        "GroupBy(Rows(a), filter=Union(Row(a=0), Row(a=2), Row(a=3)), "
        "aggregate=Sum(field=u))",
        ["a"], lambda d: np.isin(d["a"], [0, 2, 3]), "u", None, 0),
    "three_fields_one_restricted": (
        "GroupBy(Rows(a), Rows(b), Rows(c), filter=Intersect(Union(Row(b=1), "
        "Row(b=3)), Row(w < 60)), aggregate=Sum(field=v))",
        ["a", "b", "c"], lambda d: np.isin(d["b"], [1, 3]) & (d["w"] < 60),
        "v", None, 0),
}

#: How a device sweep of each case prunes its grouped fields: (fields
#: pruned from the filter's rows, fields pruned over the whole stack, of
#: the first those whose rows `pilosa_groupby_rows` counts alone). Three
#: ids of `a` (8 rows with the pad) are a bucket of 4, past a quarter of
#: the stack: the whole-stack count serves them.
PRUNED = {
    "one_field": (0, 1, 0), "two_fields_union_filter": (0, 2, 0),
    "three_fields_bsi_less_than": (0, 3, 0), "thousand_rows_bsi_between": (0, 2, 0),
    "unsigned_field": (0, 2, 0), "limit_offset": (0, 2, 0), "empty": (0, 1, 0),
    "filter_row_of_a_grouped_field": (1, 1, 1),
    "filter_union_of_a_tall_field": (1, 1, 1),
    "two_conjuncts_name_one_field": (1, 1, 1),
    "an_id_past_the_stack": (1, 0, 1),
    "bare_row_filter": (1, 1, 1),
    "bare_union_of_most_of_a_short_field": (1, 0, 0),
    "three_fields_one_restricted": (1, 2, 1),
}


def pruned(path: str) -> float:
    return global_stats.counter_totals("group_prune_fields_total").get(
        f'group_prune_fields_total{{path="{path}"}}', 0.0
    )


def executor_for(holder, path):
    if path == "cpu":
        return Executor(holder, backend=CPUBackend(holder))
    # "mesh": the program under shard_map over the test's virtual
    # devices, its counts psum-ed.
    backend = TPUBackend(holder, mesh=ShardMesh() if path == "mesh" else None)
    if path == "host":
        backend.group_by = lambda *a, **k: None
    return Executor(holder, backend=backend)


@pytest.mark.parametrize("path", ["device", "mesh", "host", "cpu"])
@pytest.mark.parametrize("case", list(CASES))
def test_grouped_sum_equals_the_numpy_reference(data, case, path):
    holder, draws = data
    pql, fields, keep, agg, limit, offset = CASES[case]
    ex = executor_for(holder, path)
    before = {p: served(p) for p in ("device", "host")}
    before_launches = launches("group_sum")
    before_rows = launches("groupby_rows")
    before_pruned = {p: pruned(p) for p in ("filter", "stack")}
    got = ex.execute("i", pql)[0]
    want = reference(draws, fields, keep(draws), agg, limit, offset)
    assert result_to_json(got) == want
    # The JSON the server writes is the same groups, byte for byte.
    assert fastjson.encode_result(got) == fastjson.dumps(want)
    if path in ("device", "mesh"):
        assert isinstance(got, GroupCounts)
        assert served("device") == before["device"] + 1
        assert launches("group_sum") >= before_launches + (1 if want else 0)
        by_filter, by_stack, listed = PRUNED[case]
        assert pruned("filter") == before_pruned["filter"] + by_filter
        assert pruned("stack") == before_pruned["stack"] + by_stack
        # The listed fields' rows, and the int field's sign row.
        assert launches("groupby_rows") == before_rows + listed + 1
    else:
        assert served("host") == before["host"] + 1
    if case != "empty":
        assert want, "a case whose reference is empty tests nothing"


def test_a_cached_answer_is_neither_another_field_s_nor_stale(data):
    holder, draws = data
    ex = executor_for(holder, "device")
    fields = ["a", "b"]
    keep = draws["f"] <= 1
    pql = ("GroupBy(Rows(a), Rows(b), filter=Union(Row(f=0), Row(f=1)), "
           "aggregate=Sum(field={}))")
    assert result_to_json(ex.execute("i", pql.format("v"))[0]) == \
        reference(draws, fields, keep, "v")
    hits = served("cache")
    ex.execute("i", pql.format("v"))
    assert served("cache") == hits + 1
    # Another aggregate field over the same groups and filter: a sweep.
    swept = served("device")
    assert result_to_json(ex.execute("i", pql.format("u"))[0]) == \
        reference(draws, fields, keep, "u")
    assert served("device") == swept + 1
    # A write to the int field: the entry no longer answers.
    at = int(np.flatnonzero(keep & draws["v_held"])[0])
    ex.execute("i", f"Set({int(draws['cols'][at])}, v=77777)")
    draws = dict(draws, v=draws["v"].copy())
    draws["v"][at] = 77777
    swept = served("device")
    assert result_to_json(ex.execute("i", pql.format("v"))[0]) == \
        reference(draws, fields, keep, "v")
    assert served("device") == swept + 1


@pytest.mark.parametrize("pql", [
    "GroupBy(Rows(a), aggregate=Sum(field=b))",
    "GroupBy(Rows(a), aggregate=Min(field=v))",
    "GroupBy(Rows(a), aggregate=Sum(field=v, n=2))",
    "GroupBy(Rows(a), aggregate=Sum(Row(f=1), field=v))",
    "GroupBy(Rows(a), aggregate=v)",
])
def test_an_aggregate_that_is_not_a_sum_of_an_int_field_is_an_error(data, pql):
    holder, _ = data
    for path in ("device", "cpu"):
        with pytest.raises(QueryError):
            executor_for(holder, path).execute("i", pql)


def test_partial_answers_merge_their_sums_and_travel_over_protobuf():
    """A coordinator's merge of two nodes' partials adds sums as it adds
    counts, and a group's sum, negative ones too, crosses the protobuf
    wire as field 3."""
    from pilosa_tpu.exec.result import FieldRow, GroupCount, merge_group_counts
    from pilosa_tpu.server.wire import decode_query_response, encode_query_response

    def group(row, count, total):
        return GroupCount([FieldRow("a", row)], count, total)

    merged = merge_group_counts([group(1, 2, -5), group(2, 1, 7)],
                                [group(1, 3, 4), group(3, 1, 0)], 10)
    assert [(g.group[0].row_id, g.count, g.sum) for g in merged] == [
        (1, 5, -1), (2, 1, 7), (3, 1, 0)]
    columns = GroupCounts(["a", "b"], np.array([[1, 2], [3, 4]]),
                          np.array([5, 6]), sums=np.array([-(1 << 40), 9]))
    back = decode_query_response(encode_query_response([columns]))["results"][0]
    assert back == result_to_json(columns) == [
        {"group": [{"field": "a", "rowID": 1}, {"field": "b", "rowID": 2}],
         "count": 5, "sum": -(1 << 40)},
        {"group": [{"field": "a", "rowID": 3}, {"field": "b", "rowID": 4}],
         "count": 6, "sum": 9},
    ]


# The count sweep alone, in interpret mode: (grouped fields' heights,
# depth of the int field, signed, slots, steps run, how the host fills
# the slots, word lines a shard row). "repeat" gives a step's
# combinations one row id of the first field; "holes" leaves slots
# inactive between active ones. At a full shard row (WORD_LINES) the
# kernel walks its blocks and their sub-tiles as on the chip.
SWEEPS = {
    "one_field": ((5,), 3, False, 32, 2, "live", 8),
    "two_fields": ((3, 40), 2, False, 32, 2, "live", 16),
    "three_fields_signed": ((8, 25, 100), 3, True, 32, 2, "live", WORD_LINES),
    "repeated_row_in_a_step": ((6, 9), 2, False, 16, 1, "repeat", 8),
    "padded_slots": ((4, 7, 3), 2, True, 32, 2, "holes", 8),
    "steps_not_run": ((5, 11), 2, False, 64, 2, "live", WORD_LINES),
}


@pytest.mark.parametrize("case", list(SWEEPS))
def test_the_fused_count_sweep_equals_a_numpy_oracle(case):
    """`group_sum_counts` reads each combination's rows by id, ANDs them
    in the kernel and counts them against every mask of
    `group_sum_masks`: out[t, p] = popcount(AND_i stacks[i][:, combos[t,
    i]] & masks[:, p]), exactly 0 for an inactive slot and for every
    slot of a step past `n_chunks`. The oracle builds the masks from
    the planes in numpy too (filter; exists; each plane under them; a
    signed field's planes under its sign as well: P = 2 + 2 x depth)."""
    import jax
    from pilosa_tpu.ops.kernels import (
        GROUP_SUM_CHUNK, group_sum_counts, group_sum_masks,
    )

    heights, depth, signed, t_slots, n_chunks, fill, lines = SWEEPS[case]
    rng = np.random.default_rng(sum(heights) * 31 + t_slots)
    shards = 2
    words = lambda *shape: rng.integers(  # noqa: E731
        0, 1 << 32, (shards, *shape, lines, 128), dtype=np.uint32
    )
    stacks = [words(h) for h in heights]
    bsi = words(2 + depth)
    filt = words()
    combos = np.stack([rng.integers(0, h, t_slots) for h in heights],
                      axis=1).astype(np.int32)
    active = np.ones(t_slots, np.uint32)
    if fill == "repeat":
        combos[:, 0] = combos[0, 0]
    if fill == "holes":
        active[rng.permutation(t_slots)[: t_slots // 3]] = 0
        active[n_chunks * GROUP_SUM_CHUNK - 1] = 0
    active[n_chunks * GROUP_SUM_CHUNK:] = 0  # the host pads past its steps

    @jax.jit
    def sweep(stacks, bsi, filt, combos, active, n_chunks):
        masks = group_sum_masks(filt, bsi, depth, signed)
        return group_sum_counts(stacks, masks, combos, active, n_chunks,
                                interpret=True)

    # One more step than the host asks for, so the run's last step would
    # have something to count if `n_chunks` were not honoured.
    live = active.copy()
    if t_slots > n_chunks * GROUP_SUM_CHUNK:
        active[n_chunks * GROUP_SUM_CHUNK:] = 1
    got = np.asarray(sweep(stacks, bsi, filt, combos, active, np.int32(n_chunks)))

    held = bsi[:, 0] & filt
    planes = bsi[:, 2:2 + depth]
    masks = [filt[:, None], held[:, None], planes & held[:, None]]
    if signed:
        masks.append(planes & (held & bsi[:, 1])[:, None])
    masks = np.concatenate(masks, axis=1)  # [S, P, L, 128]
    rows = np.bitwise_and.reduce(
        [st[:, combos[:, i]] for i, st in enumerate(stacks)], axis=0
    )  # [S, T, L, 128]
    want = np.bitwise_count(rows[:, :, None] & masks[:, None]).sum(
        axis=(0, 3, 4), dtype=np.int64
    ) * live[:, None]
    assert got.shape == (t_slots, 2 + depth * (2 if signed else 1))
    assert np.array_equal(got, want)
    assert (got[live == 0] == 0).all()
    assert (got[live == 1] > 0).all(), "an active slot that counts nothing tests nothing"


def test_the_device_s_work_is_timed_as_dispatch_and_its_wait(data):
    """A request's grouped sum waits for the device in `device_wait`
    (filed under `device_dispatch`), then reads back (`host_reduce`): the
    filter's assembly is `plan`, and no phase of the host holds queued
    device time."""
    from pilosa_tpu.utils.qprofile import profile_scope

    holder, _ = data
    ex = executor_for(holder, "device")
    pql = CASES["three_fields_bsi_less_than"][0]
    with profile_scope(index="i", query=pql, call="GroupBy") as prof:
        ex.execute("i", pql)
    assert {"plan", "device_dispatch", "host_reduce"} <= set(prof.phases)
    assert not set(prof.phases) & {"dispatch", "device_wait", "readback"}
    assert prof.counters["device_wait_us"] > 0


@pytest.mark.parametrize("path", ["device", "mesh"])
def test_a_mutex_field_with_a_column_in_two_rows_is_counted_whole(tmp_path, path):
    """A mutex field's writes keep a column in one row, but a roaring
    import unions bits as they come: here column 7 lies in rows 1 and 2.
    Under `Row(m=1)` the host iterator counts it in group 2 too, so the
    device may not take row 1 for the only row that can be live: the
    stack's rows are not disjoint, and the field is counted whole. Once
    a write has made them disjoint again, the filter's rows serve."""
    from pilosa_tpu.roaring import Bitmap, serialize

    holder = Holder(str(tmp_path / "data")).open()
    try:
        idx = holder.create_index("i")
        idx.create_field("m", options_for_mutex())
        idx.create_field("v", options_for_int(0, 1000))
        cols = np.arange(40, dtype=np.uint64)
        idx.field("m").import_bits(cols % 4, cols)
        idx.field("v").import_value(cols, (cols * 10).astype(np.int64))
        # Column 7 is in row 3; the import adds it to row 1.
        idx.field("m").import_roaring(
            0, serialize(Bitmap(np.array([1 * SHARD_WIDTH + 7], np.uint64)))
        )
        pql = "GroupBy(Rows(m), filter=Row(m=1), aggregate=Sum(field=v))"
        host = result_to_json(executor_for(holder, "host").execute("i", pql)[0])
        assert host == result_to_json(
            executor_for(holder, "cpu").execute("i", pql)[0]
        )
        assert [g["group"][0]["rowID"] for g in host] == [1, 3]
        ex = executor_for(holder, path)
        before = {p: pruned(p) for p in ("filter", "stack")}
        assert result_to_json(ex.execute("i", pql)[0]) == host
        assert pruned("stack") == before["stack"] + 1
        assert pruned("filter") == before["filter"]
        # Clearing the extra bit gives the field a new stack, counted
        # again: now disjoint, so only row 1 is counted.
        ex.execute("i", "Clear(7, m=1)")
        want = result_to_json(executor_for(holder, "host").execute("i", pql)[0])
        assert [g["group"][0]["rowID"] for g in want] == [1]
        before = {p: pruned(p) for p in ("filter", "stack")}
        assert result_to_json(ex.execute("i", pql)[0]) == want
        assert pruned("filter") == before["filter"] + 1
        assert pruned("stack") == before["stack"]
    finally:
        holder.close()


# -- the Star Schema Benchmark's calls ------------------------------------

#: One stream's constants, chosen so that every call finds columns in
#: the data below: nation 3 of region 0, its cities 30..35, categories 7
#: and 8 (brands 280..359, manufacturer 1).
SSB_CONSTANTS = {
    "q1.1": (2, 1), "q1.2": (30, 2, 10), "q1.3": (3, 10, 4, 20),
    "q2.1": (7, 0), "q2.2": (7, 4, 0), "q2.3": (285, 0),
    "q3.1": (0, 1), "q3.2": (3, 0), "q3.3": (3, 0, 5, 1), "q3.4": (3, 1, 2, 30),
    "q4.1": (0, 1), "q4.2": (0, 2, 1), "q4.3": (3, 2, 8),
}

#: A call's grouped fields pruned from the filter's rows and over the
#: whole stack. A stream prunes 37 fields, 16 of them from the filter.
SSB_PRUNED = {
    "q1.1": (1, 0), "q1.2": (1, 0), "q1.3": (1, 0),
    "q2.1": (0, 2), "q2.2": (1, 1), "q2.3": (1, 1),
    "q3.1": (1, 2), "q3.2": (1, 2), "q3.3": (3, 0), "q3.4": (2, 1),
    "q4.1": (0, 2), "q4.2": (1, 2), "q4.3": (1, 2),
}


@pytest.fixture(scope="module")
def ssb(tmp_path_factory):
    """(holder, the `ssb` call shape) over one shard of lineorder rows at
    the benchmark's widths (benchmark/configs/ssb-sf100-1chip.json), each
    row's dimensions drawn within the hierarchy the queries walk."""
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import plugins

    rng = np.random.default_rng(20261018)
    n = 30000
    cols = np.sort(rng.choice(SHARD_WIDTH, n, replace=False)).astype(np.uint64)
    month = rng.integers(0, 84, n)
    c_city = 30 + rng.integers(0, 6, n)
    s_city = np.where(rng.random(n) < 0.5, c_city, 30 + rng.integers(0, 6, n))
    brand = 40 * rng.integers(7, 9, n) + rng.integers(0, 40, n)
    dims = {
        "d_year": month // 12, "d_yearmonthnum": month,
        "d_weeknuminyear": rng.integers(0, 53, n),
        "lo_discount": rng.integers(0, 11, n),
        "c_city": c_city, "c_nation": c_city // 10, "c_region": c_city // 50,
        "s_city": s_city, "s_nation": s_city // 10, "s_region": s_city // 50,
        "p_brand1": brand, "p_category": brand // 40, "p_mfgr": brand // 200,
    }
    measures = {"lo_quantity": (0, 50), "lo_extendedprice": (0, 16777215),
                "lo_revenue": (0, 16777215), "lo_supplycost": (0, 131071)}
    holder = Holder(str(tmp_path_factory.mktemp("ssb") / "data")).open()
    idx = holder.create_index("ssb")
    for name, rows in dims.items():
        idx.create_field(name, options_for_mutex())
        idx.field(name).import_bits(rows.astype(np.uint64), cols)
    for name, (lo, hi) in measures.items():
        idx.create_field(name, options_for_int(lo, hi))
        idx.field(name).import_value(cols, rng.integers(lo + 1, hi + 1, n))
    yield holder, plugins.load("shapes", "ssb")
    holder.close()


def test_a_stream_of_star_schema_calls_prunes_16_of_its_37_fields(ssb):
    """The sixteen calls of an SSB stream as the benchmark renders them:
    the device's answer is the host iterator's, and each call's grouped
    fields are pruned from the filter's rows where it names them (a
    discount window, a brand or eight, two cities, a range of years)."""
    holder, shape = ssb
    device, host = executor_for(holder, "device"), executor_for(holder, "host")
    totals = np.zeros(2)
    for query, agg in shape.STREAM:
        pql = shape.pql((query, agg, SSB_CONSTANTS[query]))
        before = np.array([pruned("filter"), pruned("stack")])
        got = result_to_json(device.execute("ssb", pql)[0])
        grew = np.array([pruned("filter"), pruned("stack")]) - before
        assert tuple(grew) == SSB_PRUNED[query], query
        totals += grew
        assert got == result_to_json(host.execute("ssb", pql)[0]), query
        assert got, f"{query} finds no group: it tests nothing"
    assert tuple(totals) == (16, 21)


@pytest.mark.parametrize("pql, want", [
    ("Row(a=1)", {1}),
    ("Union(Row(a=1), Row(a=3), Row(a=1))", {1, 3}),
    ("Union(Row(a=1), Row(b=2))", None),
    ("Row(b=1)", None),
    ('Row(a=1, from="2020-01-01T00:00", to="2021-01-01T00:00")', None),
    ("Row(a > 3)", None),
    ("Row(a=true)", None),
    ("Not(Row(a=1))", None),
    ("Intersect(Row(a=1), Row(a=2))", None),
])
def test_a_filter_names_rows_of_a_field_only_as_rows_or_their_union(pql, want):
    """What `_filter_candidates` takes from a conjunct: a plain Row of the
    field, or a Union of nothing else. A time range reads other views, a
    comparison no row, and a Not or a nested set operation is not a
    subset of the rows it names."""
    from pilosa_tpu.exec.tpu import _named_rows
    from pilosa_tpu.pql import parse_string

    assert _named_rows(parse_string(pql).calls[0], "a") == want
