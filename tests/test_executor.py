"""Executor tests — the PQL op coverage mirrors the reference's
executor_test.go (every op, keyed variants, existence, GroupBy)."""

import time

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import (
    options_for_bool,
    options_for_int,
    options_for_mutex,
    options_for_time,
)
from pilosa_tpu.core.index import IndexOptions
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec import executor as executor_module
from pilosa_tpu.exec.batcher import ShardLegBatcher
from pilosa_tpu.exec.cpu import CPUBackend, NotFoundError, QueryError
from pilosa_tpu.exec.executor import ExecOptions
from pilosa_tpu.exec.rescache import ResultCache
from pilosa_tpu.exec.result import result_to_json
from pilosa_tpu.pql import Call
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.deadline import DeadlineExceeded
from pilosa_tpu.utils.stats import global_stats


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data")).open()
    yield h
    h.close()


@pytest.fixture
def ex(holder):
    return Executor(holder)


def setup_basic(ex):
    idx = ex.holder.create_index("i")
    idx.create_field("f")
    idx.create_field("g")
    ex.execute("i", "Set(10, f=1) Set(100, f=1) Set(10, g=2)")
    ex.execute("i", f"Set({SHARD_WIDTH * 2 + 7}, f=1)")  # shard 2
    return ex


class TestBitmapCalls:
    def test_row(self, ex):
        setup_basic(ex)
        (row,) = ex.execute("i", "Row(f=1)")
        assert row.columns().tolist() == [10, 100, SHARD_WIDTH * 2 + 7]

    def test_intersect_union_difference_xor(self, ex):
        setup_basic(ex)
        (r,) = ex.execute("i", "Intersect(Row(f=1), Row(g=2))")
        assert r.columns().tolist() == [10]
        (r,) = ex.execute("i", "Union(Row(f=1), Row(g=2))")
        assert r.columns().tolist() == [10, 100, SHARD_WIDTH * 2 + 7]
        (r,) = ex.execute("i", "Difference(Row(f=1), Row(g=2))")
        assert r.columns().tolist() == [100, SHARD_WIDTH * 2 + 7]
        (r,) = ex.execute("i", "Xor(Row(f=1), Row(g=2))")
        assert r.columns().tolist() == [100, SHARD_WIDTH * 2 + 7]

    def test_count(self, ex):
        setup_basic(ex)
        assert ex.execute("i", "Count(Row(f=1))") == [3]
        assert ex.execute("i", "Count(Intersect(Row(f=1), Row(g=2)))") == [1]

    def test_not_uses_existence(self, ex):
        setup_basic(ex)
        (r,) = ex.execute("i", "Not(Row(f=1))")
        # existence = {10, 100, shard2+7}; Not(f=1) = existence - row = {}
        assert r.columns().tolist() == []
        (r,) = ex.execute("i", "Not(Row(g=2))")
        assert r.columns().tolist() == [100, SHARD_WIDTH * 2 + 7]

    def test_not_without_existence_errors(self, holder):
        idx = holder.create_index("noex", IndexOptions(track_existence=False))
        idx.create_field("f")
        ex = Executor(holder)
        ex.execute("noex", "Set(1, f=1)")
        with pytest.raises(QueryError, match="existence"):
            ex.execute("noex", "Not(Row(f=1))")

    def test_all(self, ex):
        setup_basic(ex)
        (r,) = ex.execute("i", "All()")
        assert r.columns().tolist() == [10, 100, SHARD_WIDTH * 2 + 7]

    def test_shift(self, ex):
        setup_basic(ex)
        (r,) = ex.execute("i", "Shift(Row(g=2), n=1)")
        assert r.columns().tolist() == [11]

    def test_set_returns_changed(self, ex):
        ex.holder.create_index("i").create_field("f")
        assert ex.execute("i", "Set(1, f=1)") == [True]
        assert ex.execute("i", "Set(1, f=1)") == [False]

    def test_clear(self, ex):
        setup_basic(ex)
        assert ex.execute("i", "Clear(10, f=1)") == [True]
        assert ex.execute("i", "Clear(10, f=1)") == [False]
        (r,) = ex.execute("i", "Row(f=1)")
        assert r.columns().tolist() == [100, SHARD_WIDTH * 2 + 7]

    def test_clear_row(self, ex):
        setup_basic(ex)
        assert ex.execute("i", "ClearRow(f=1)") == [True]
        assert ex.execute("i", "Count(Row(f=1))") == [0]
        # g untouched
        assert ex.execute("i", "Count(Row(g=2))") == [1]

    def test_store(self, ex):
        setup_basic(ex)
        assert ex.execute("i", "Store(Row(f=1), stored=9)") == [True]
        (r,) = ex.execute("i", "Row(stored=9)")
        assert r.columns().tolist() == [10, 100, SHARD_WIDTH * 2 + 7]


class TestRowTimeRange:
    def test_range_query(self, holder):
        idx = holder.create_index("t")
        idx.create_field("f", options_for_time("YMDH"))
        ex = Executor(holder)
        ex.execute("t", 'Set(2, f=1, 2018-01-01T00:00)')
        ex.execute("t", 'Set(3, f=1, 2018-03-05T12:00)')
        ex.execute("t", 'Set(4, f=1, 2019-06-01T00:00)')
        (r,) = ex.execute("t", "Range(f=1, 2018-01-01T00:00, 2019-01-01T00:00)")
        assert r.columns().tolist() == [2, 3]
        (r,) = ex.execute("t", "Row(f=1, from=2018-03-01T00:00, to=2019-07-01T00:00)")
        assert r.columns().tolist() == [3, 4]
        # plain Row returns standard view (all)
        (r,) = ex.execute("t", "Row(f=1)")
        assert r.columns().tolist() == [2, 3, 4]


class TestBSI:
    def setup_bsi(self, holder):
        idx = holder.create_index("i")
        idx.create_field("v", options_for_int(-1000, 1000))
        idx.create_field("f")
        ex = Executor(holder)
        for col, val in [(1, 100), (2, -300), (3, 500), (4, 500), (5, 0)]:
            ex.execute("i", f"Set({col}, v={val})")
        return ex

    def test_sum_min_max(self, holder):
        ex = self.setup_bsi(holder)
        (vc,) = ex.execute("i", "Sum(field=v)")
        assert (vc.val, vc.count) == (800, 5)
        (vc,) = ex.execute("i", "Min(field=v)")
        assert (vc.val, vc.count) == (-300, 1)
        (vc,) = ex.execute("i", "Max(field=v)")
        assert (vc.val, vc.count) == (500, 2)

    def test_sum_with_filter(self, holder):
        ex = self.setup_bsi(holder)
        ex.execute("i", "Set(1, f=1) Set(3, f=1)")
        (vc,) = ex.execute("i", "Sum(Row(f=1), field=v)")
        assert (vc.val, vc.count) == (600, 2)

    def test_range_conditions(self, holder):
        ex = self.setup_bsi(holder)
        cases = [
            ("Row(v > 100)", [3, 4]),
            ("Row(v >= 100)", [1, 3, 4]),
            ("Row(v < 0)", [2]),
            ("Row(v <= 0)", [2, 5]),
            ("Row(v == 500)", [3, 4]),
            ("Row(v != 500)", [1, 2, 5]),
            ("Row(v >< [0, 200])", [1, 5]),
            ("Row(-300 <= v <= 100)", [1, 2, 5]),
            ("Row(v != null)", [1, 2, 3, 4, 5]),
        ]
        for q, want in cases:
            (r,) = ex.execute("i", q)
            assert r.columns().tolist() == want, q

    def test_out_of_range_conditions(self, holder):
        ex = self.setup_bsi(holder)
        (r,) = ex.execute("i", "Row(v > 100000)")
        assert r.columns().tolist() == []
        (r,) = ex.execute("i", "Row(v < 100000)")  # encompasses all -> notNull
        assert r.columns().tolist() == [1, 2, 3, 4, 5]


class TestTopN:
    def test_topn_basic(self, holder):
        idx = holder.create_index("i")
        idx.create_field("f")
        ex = Executor(holder)
        # row 1: 4 bits; row 2: 2 bits; row 3: 1 bit, spanning shards
        for col in [0, 1, 2, SHARD_WIDTH + 1]:
            ex.execute("i", f"Set({col}, f=1)")
        for col in [0, SHARD_WIDTH + 2]:
            ex.execute("i", f"Set({col}, f=2)")
        ex.execute("i", "Set(5, f=3)")
        (res,) = ex.execute("i", "TopN(f, n=2)")
        assert [(p.id, p.count) for p in res.pairs] == [(1, 4), (2, 2)]
        (res,) = ex.execute("i", "TopN(f)")
        assert [(p.id, p.count) for p in res.pairs] == [(1, 4), (2, 2), (3, 1)]

    def test_topn_with_src(self, holder):
        idx = holder.create_index("i")
        idx.create_field("f")
        idx.create_field("g")
        ex = Executor(holder)
        for col in [0, 1, 2]:
            ex.execute("i", f"Set({col}, f=1)")
        ex.execute("i", "Set(1, f=2)")
        ex.execute("i", "Set(0, g=9) Set(1, g=9)")
        (res,) = ex.execute("i", "TopN(f, Row(g=9), n=5)")
        assert [(p.id, p.count) for p in res.pairs] == [(1, 2), (2, 1)]


class TestRowsAndGroupBy:
    def setup_rows(self, holder):
        idx = holder.create_index("i")
        idx.create_field("a")
        idx.create_field("b")
        ex = Executor(holder)
        ex.execute("i", "Set(0, a=1) Set(1, a=1) Set(1, a=2) Set(2, a=3)")
        ex.execute("i", "Set(0, b=10) Set(1, b=10) Set(2, b=20)")
        return ex

    def test_rows(self, holder):
        ex = self.setup_rows(holder)
        assert list(ex.execute("i", "Rows(a)")[0]) == [1, 2, 3]
        assert list(ex.execute("i", "Rows(a, limit=2)")[0]) == [1, 2]
        assert list(ex.execute("i", "Rows(a, previous=1)")[0]) == [2, 3]
        assert list(ex.execute("i", "Rows(a, column=1)")[0]) == [1, 2]

    def test_group_by(self, holder):
        ex = self.setup_rows(holder)
        (res,) = ex.execute("i", "GroupBy(Rows(a), Rows(b))")
        got = [([fr.row_id for fr in gc.group], gc.count) for gc in res]
        assert got == [
            ([1, 10], 2),
            ([2, 10], 1),
            ([3, 20], 1),
        ]

    def test_group_by_filter(self, holder):
        ex = self.setup_rows(holder)
        (res,) = ex.execute("i", "GroupBy(Rows(a), filter=Row(b=10))")
        got = [([fr.row_id for fr in gc.group], gc.count) for gc in res]
        assert got == [([1], 2), ([2], 1)]

    def test_group_by_limit(self, holder):
        ex = self.setup_rows(holder)
        (res,) = ex.execute("i", "GroupBy(Rows(a), Rows(b), limit=2)")
        assert len(res) == 2


class TestMinMaxRow:
    def test_min_max_row(self, holder):
        holder.create_index("i").create_field("f")
        ex = Executor(holder)
        ex.execute("i", "Set(0, f=3) Set(1, f=7) Set(2, f=7)")
        (res,) = ex.execute("i", "MinRow(field=f)")
        assert (res.pair.id, res.pair.count) == (3, 1)
        (res,) = ex.execute("i", "MaxRow(field=f)")
        assert (res.pair.id, res.pair.count) == (7, 1)


class TestFieldTypes:
    def test_bool_field(self, holder):
        idx = holder.create_index("i")
        idx.create_field("b", options_for_bool())
        ex = Executor(holder)
        ex.execute("i", "Set(1, b=true) Set(2, b=false) Set(3, b=true)")
        (r,) = ex.execute("i", "Row(b=true)")
        assert r.columns().tolist() == [1, 3]
        (r,) = ex.execute("i", "Row(b=false)")
        assert r.columns().tolist() == [2]
        # flip
        ex.execute("i", "Set(1, b=false)")
        (r,) = ex.execute("i", "Row(b=true)")
        assert r.columns().tolist() == [3]

    def test_mutex_field(self, holder):
        idx = holder.create_index("i")
        idx.create_field("m", options_for_mutex())
        ex = Executor(holder)
        ex.execute("i", "Set(1, m=10) Set(1, m=20)")
        (r,) = ex.execute("i", "Row(m=10)")
        assert r.columns().tolist() == []
        (r,) = ex.execute("i", "Row(m=20)")
        assert r.columns().tolist() == [1]


class TestKeys:
    def test_keyed_index_and_field(self, holder):
        idx = holder.create_index("k", IndexOptions(keys=True))
        from pilosa_tpu.core.field import FieldOptions

        idx.create_field("f", FieldOptions(keys=True))
        ex = Executor(holder)
        ex.execute("k", 'Set("alpha", f="one") Set("beta", f="one")')
        (r,) = ex.execute("k", 'Row(f="one")')
        assert sorted(r.keys) == ["alpha", "beta"]
        (res,) = ex.execute("k", 'TopN(f, n=5)')
        assert [(p.key, p.count) for p in res.pairs] == [("one", 2)]

    def test_unkeyed_rejects_strings(self, holder):
        holder.create_index("u")
        ex = Executor(holder)
        with pytest.raises(QueryError, match="keys"):
            ex.execute("u", 'Set("alpha", f=1)')


class TestAttrs:
    def test_row_attrs(self, holder):
        holder.create_index("i").create_field("f")
        ex = Executor(holder)
        ex.execute("i", "Set(1, f=7)")
        ex.execute("i", 'SetRowAttrs(f, 7, color="blue", weight=3)')
        (r,) = ex.execute("i", "Row(f=7)")
        assert r.attrs == {"color": "blue", "weight": 3}

    def test_column_attrs(self, holder):
        idx = holder.create_index("i")
        ex = Executor(holder)
        ex.execute("i", 'SetColumnAttrs(9, happy=true)')
        assert idx.column_attr_store.attrs(9) == {"happy": True}


class TestOptions:
    def test_shards_option(self, ex):
        setup_basic(ex)
        (r,) = ex.execute("i", "Options(Row(f=1), shards=[0])")
        assert r.columns().tolist() == [10, 100]

    def test_exclude_row_attrs(self, ex):
        setup_basic(ex)
        ex.execute("i", 'SetRowAttrs(f, 1, x=1)')
        (r,) = ex.execute("i", "Options(Row(f=1), excludeRowAttrs=true)")
        assert r.attrs == {}


class TestMultiOps:
    def test_write_then_read_same_query(self, ex):
        ex.holder.create_index("i").create_field("f")
        results = ex.execute("i", "Set(1, f=1) Count(Row(f=1))")
        assert results == [True, 1]


class TestReviewRegressions:
    """Regression tests for review findings (cross-shard TopN recount,
    negative-predicate BSI routing, keyed Rows column, threaded stores,
    Shift identity)."""

    def test_topn_cross_shard_recount(self, holder):
        idx = holder.create_index("i")
        idx.create_field("t")
        ex = Executor(holder)
        # row 10: 10 bits all in shard 0; row 20: 6 + 6 across shards = 12.
        for col in range(10):
            ex.execute("i", f"Set({col}, t=10)")
        for col in range(6):
            ex.execute("i", f"Set({100 + col}, t=20)")
            ex.execute("i", f"Set({SHARD_WIDTH + col}, t=20)")
        (res,) = ex.execute("i", "TopN(t, n=1)")
        assert [(p.id, p.count) for p in res.pairs] == [(20, 12)]

    def test_bsi_negative_predicate_routing(self, holder):
        idx = holder.create_index("i")
        idx.create_field("v", options_for_int(-10, 10))
        ex = Executor(holder)
        for col, val in [(1, -2), (2, -1), (3, 0), (4, 1)]:
            ex.execute("i", f"Set({col}, v={val})")
        cases = [
            ("Row(v < 0)", [1, 2]),
            ("Row(v < -1)", [1]),
            ("Row(v <= -1)", [1, 2]),
            ("Row(v > -1)", [3, 4]),
            ("Row(v >= -1)", [2, 3, 4]),
            ("Row(v > -2)", [2, 3, 4]),
        ]
        for q, want in cases:
            (r,) = ex.execute("i", q)
            assert r.columns().tolist() == want, q

    def test_rows_column_keyed(self, holder):
        from pilosa_tpu.core.field import FieldOptions

        idx = holder.create_index("k", IndexOptions(keys=True))
        idx.create_field("f", FieldOptions(keys=True))
        ex = Executor(holder)
        ex.execute("k", 'Set("alice", f="red") Set("bob", f="blue")')
        (rows,) = ex.execute("k", 'Rows(f, column="alice")')
        assert len(rows) == 1

    def test_attr_store_cross_thread(self, holder):
        import threading

        idx = holder.create_index("i")
        idx.create_field("f")
        idx.fields["f"].row_attr_store.set_attrs(1, {"x": 1})
        seen = {}

        def reader():
            seen["attrs"] = idx.fields["f"].row_attr_store.attrs(1)

        t = threading.Thread(target=reader)
        t.start()
        t.join()
        assert seen["attrs"] == {"x": 1}

    def test_shift_identity_and_negative(self, ex):
        setup_basic(ex)
        (r,) = ex.execute("i", "Shift(Row(g=2))")
        assert r.columns().tolist() == [10]  # n missing -> unchanged
        (r,) = ex.execute("i", "Shift(Row(g=2), n=2)")
        assert r.columns().tolist() == [12]
        with pytest.raises(QueryError, match="negative"):
            ex.execute("i", "Shift(Row(g=2), n=-1)")

    def test_rows_result_keys_translated(self, holder):
        from pilosa_tpu.core.field import FieldOptions

        idx = holder.create_index("k2", IndexOptions(keys=True))
        idx.create_field("f", FieldOptions(keys=True))
        ex = Executor(holder)
        ex.execute("k2", 'Set("a", f="red") Set("b", f="blue")')
        (rows,) = ex.execute("k2", "Rows(f)")
        assert rows.to_json() == {"keys": ["red", "blue"]} or set(
            rows.to_json()["keys"]
        ) == {"red", "blue"}


# -- a request's run of device reads goes to the batcher in one trip
#    (ISSUE 33) -----------------------------------------------------------


class LegBackend(CPUBackend):
    """The host oracle with the device backend's synchronous leg methods
    on top (each answered by a plain executor over the same holder), so
    that the executor takes the serving path: Sum / Min / Max / TopN go
    to a batcher as legs. Every backend call is recorded; a field named
    in `not_lowerable` answers None, one named in `broken` raises."""

    def __init__(self, holder):
        super().__init__(holder)
        self.oracle = Executor(holder)
        self.calls = []
        self.not_lowerable = set()
        self.broken = set()
        self.delay = 0.0  # seconds a BSI leg takes to serve

    def _bsi(self, name, index, field, shards, filt):
        self.calls.append((name, field))
        time.sleep(self.delay)
        if field in self.broken:
            raise RuntimeError(f"device lost under {name}({field})")
        if (name, field) in self.not_lowerable:
            return None
        c = Call(name, {"field": field}, [filt] if filt is not None else [])
        vc = self.oracle._execute_bsi(index, c, shards, ExecOptions())
        return vc.val, vc.count

    def bsi_sum(self, index, field, shards, filt=None):
        return self._bsi("Sum", index, field, shards, filt)

    def bsi_min(self, index, field, shards, filt=None):
        return self._bsi("Min", index, field, shards, filt)

    def bsi_max(self, index, field, shards, filt=None):
        return self._bsi("Max", index, field, shards, filt)

    def topn_field(self, index, field, shards, n, src=None):
        self.calls.append(("TopN", field))
        if ("TopN", field) in self.not_lowerable:
            return None
        c = Call("TopN", {"_field": field, "n": n}, [src] if src is not None else [])
        return self.oracle._execute_topn(index, c, shards, ExecOptions()).pairs


def build_page_index(holder, name="i"):
    idx = holder.create_index(name)
    f = idx.create_field("f")
    g = idx.create_field("g")
    v = idx.create_field("v", options_for_int(-1000, 1000))
    w = idx.create_field("w", options_for_int(0, 1000))
    cols = np.arange(0, 3 * SHARD_WIDTH, SHARD_WIDTH // 8, dtype=np.uint64)
    f.import_bits(cols % 10, cols)
    g.import_bits(cols % 3, cols)
    v.import_value(cols, (cols // 7 % 1800).astype(np.int64) - 900)
    w.import_value(cols[::2], (cols[::2] // 5 % 1000).astype(np.int64))
    return idx


@pytest.fixture
def served(holder):
    """(executor on the serving path with a batcher, its backend, the
    legs of every trip to the batcher)."""
    build_page_index(holder)
    be = LegBackend(holder)
    ex = Executor(holder, backend=be)
    ex.batcher = ShardLegBatcher(be)
    trips = []
    submit = ex.batcher.submit

    def recorded(legs):
        trips.append([(leg.kind, leg.payload[0]) for leg in legs])
        assert ex.batcher._pending == []  # nothing of an earlier trip is left
        submit(legs)

    ex.batcher.submit = recorded
    return ex, be, trips


PAGE = "TopN(f) " + " ".join(f"Sum(Row(f={k}), field=v)" for k in range(10))
PAGE_LEGS = [("topn", "f")] + [("bsi_sum", "v")] * 10

#: body -> the legs of each trip it makes, in order
RUN_BODIES = {
    "page": (PAGE, [PAGE_LEGS]),
    "page_then_groupbys": (
        PAGE + " GroupBy(Rows(g), Rows(f)) GroupBy(Rows(f))", [PAGE_LEGS]),
    "every_member_kind": (
        "Min(field=v) Max(Row(g=1), field=v) TopN(f, n=2) TopN(g, Row(f=1)) Sum(field=w)",
        [[("bsi_min", "v"), ("bsi_max", "v"), ("topn", "f"), ("topn", "g"),
          ("bsi_sum", "w")]]),
    "one_read": ("Sum(field=v)", [[("bsi_sum", "v")]]),
    "write_is_a_barrier": (
        "Sum(field=v) Set(1, v=777) Sum(field=v) Clear(1, f=1) TopN(f)",
        [[("bsi_sum", "v")], [("bsi_sum", "v")], [("topn", "f")]]),
    "groupby_ends_the_run": (
        "Sum(field=v) Sum(field=w) GroupBy(Rows(f)) Sum(field=v)",
        [[("bsi_sum", "v"), ("bsi_sum", "w")], [("bsi_sum", "v")]]),
    "bitmap_call_and_rows_end_it": (
        "Min(field=v) Row(f=1) Max(field=v) Rows(f) MinRow(field=f) Sum(field=v)",
        [[("bsi_min", "v")], [("bsi_max", "v")], [("bsi_sum", "v")]]),
    "topn_with_ids_is_no_member": (
        "Sum(field=v) TopN(f, ids=[1, 2]) Sum(field=w)",
        [[("bsi_sum", "v")], [("bsi_sum", "w")]]),
    "options_ends_it_and_makes_its_own_trip": (
        "Sum(field=v) Options(Sum(field=v), shards=[0]) Sum(field=w)",
        [[("bsi_sum", "v")], [("bsi_sum", "v")], [("bsi_sum", "w")]]),
}


def as_json(results):
    return [result_to_json(r) for r in results]


class TestReadRuns:
    @pytest.mark.parametrize("case", sorted(RUN_BODIES))
    def test_a_run_is_one_trip_answered_in_call_order(self, case, served, tmp_path):
        ex, be, trips = served
        body, want_trips = RUN_BODIES[case]
        # The serial loop over the host path, on a holder of its own (a
        # body may write).
        other = Holder(str(tmp_path / "serial")).open()
        try:
            build_page_index(other)
            want = as_json(Executor(other).execute("i", body))
        finally:
            other.close()
        trips0 = global_stats.counter_totals("batch_trips_total")
        got = as_json(ex.execute("i", body))
        assert got == want
        assert trips == want_trips
        grown = (global_stats.counter_totals("batch_trips_total")["batch_trips_total"]
                 - trips0.get("batch_trips_total", 0.0))
        assert grown == len(want_trips)

    def test_sum_set_sum_reads_the_write_in_the_second_sum_only(self, served):
        ex, be, trips = served
        before, = ex.execute("i", "Sum(field=w)")
        col = 3 * SHARD_WIDTH - 1  # no value of w yet
        first, changed, second = ex.execute(
            "i", f"Sum(field=w) Set({col}, w=999) Sum(field=w)")
        assert changed is True
        assert (first.val, first.count) == (before.val, before.count)
        assert (second.val, second.count) == (before.val + 999, before.count + 1)

    @pytest.mark.parametrize("bad, error, match", [
        ("Sum(field=nope)", NotFoundError, "field not found: nope"),
        ("Sum(Row(f=1), Row(f=2), field=v)", QueryError,
         r"Sum\(\) only accepts a single bitmap input"),
        ("Min(field=3)", ValueError, "could not convert 3 to string"),
        ("TopN(f, n=true)", ValueError, "could not convert True to uint64"),
        ("deadline", DeadlineExceeded, "plan"),
    ])
    def test_a_member_that_fails_in_preparation(
            self, bad, error, match, served, monkeypatch):
        """The serial loop's error, after the reads before it were
        answered, and no leg queued behind it."""
        ex, be, trips = served
        if bad == "deadline":
            bad = "Sum(field=w)"
            plans = []

            def check(phase):
                plans.append(phase)
                if plans.count("plan") == 3 and phase == "plan":
                    raise DeadlineExceeded("deadline exceeded before plan", phase)

            monkeypatch.setattr(executor_module, "check_deadline", check)
        else:
            with pytest.raises(error, match=match):  # the serial loop's
                Executor(ex.holder).execute("i", bad)
        body = f"Sum(field=v) TopN(f) {bad} Sum(field=w) Max(field=v)"
        with pytest.raises(error, match=match):
            ex.execute("i", body)
        assert trips == [[("bsi_sum", "v"), ("topn", "f")]]
        assert be.calls == [("Sum", "v"), ("TopN", "f")]
        assert ex.batcher._pending == []

    @pytest.mark.parametrize("missing", [
        ("Sum", "v"), ("Min", "v"), ("Max", "w"), ("TopN", "f")])
    def test_a_leg_that_is_not_lowerable_falls_to_map_reduce_at_its_place(
            self, missing, served):
        ex, be, trips = served
        body = "Sum(field=w) Min(field=v) TopN(f, n=3) Sum(field=v) Max(field=w) TopN(g)"
        want = as_json(Executor(ex.holder).execute("i", body))
        be.not_lowerable.add(missing)
        assert as_json(ex.execute("i", body)) == want
        assert len(trips) == 1 and len(trips[0]) == 6

    def test_a_legs_error_is_raised_at_its_calls_place(self, served):
        ex, be, trips = served
        be.broken.add("w")
        sink = []
        with pytest.raises(RuntimeError, match=r"device lost under Sum\(w\)"):
            ex.execute("i", "Sum(field=v) Sum(field=w) TopN(f)",
                       opt=ExecOptions(wire_sink=sink))
        assert sink == [None]  # the Sum before it was answered, nothing after
        assert len(trips) == 1
        # ... and the executor serves on.
        assert as_json(ex.execute("i", "Sum(field=v) TopN(f)")) == as_json(
            Executor(ex.holder).execute("i", "Sum(field=v) TopN(f)"))

    def test_a_cache_hit_inside_a_run_queues_nothing_and_keeps_its_token(self, served):
        ex, be, trips = served
        ex.rescache = ResultCache(ex.holder, max_bytes=1 << 20)
        body = "Sum(field=v) TopN(f, n=2) Sum(Row(f=1), field=v)"
        first = as_json(ex.execute("i", body))
        assert [len(t) for t in trips] == [3]
        sink = []
        again = ex.execute("i", "Sum(field=v) Max(field=w) TopN(f, n=2) Min(field=v)",
                           opt=ExecOptions(wire_sink=sink))
        # Two hits stand among two misses: one trip of the two misses.
        assert trips[1:] == [[("bsi_max", "w"), ("bsi_min", "v")]]
        assert [t.hit for t in sink] == [True, False, True, False]
        assert as_json(again)[0] == first[0] and as_json(again)[2] == first[1]
        assert as_json(again) == as_json(Executor(ex.holder).execute(
            "i", "Sum(field=v) Max(field=w) TopN(f, n=2) Min(field=v)"))
        # The misses were committed under their tokens: all four hit now.
        sink2 = []
        ex.execute("i", "Sum(field=v) Max(field=w) TopN(f, n=2) Min(field=v)",
                   opt=ExecOptions(wire_sink=sink2))
        assert [t.hit for t in sink2] == [True] * 4 and len(trips) == 2

    def test_one_call_seconds_observation_a_member(self, served):
        ex, be, trips = served

        def observed():
            return {k.split('"')[1]: n for k, (_, n) in
                    global_stats.timing_totals("query_call_seconds").items()}

        def sum_seconds():
            return global_stats.timing_totals(
                "query_call_seconds")['query_call_seconds{call="Sum"}'][0]

        be.delay = 0.004
        before, seconds0, t0 = observed(), sum_seconds(), time.perf_counter()
        ex.execute("i", PAGE + " GroupBy(Rows(f))")
        wall = time.perf_counter() - t0
        after = observed()
        grown = {k: after[k] - before.get(k, 0) for k in after
                 if after[k] != before.get(k, 0)}
        assert grown == {"TopN": 1, "Sum": 10, "GroupBy": 1}
        # A member's latency is its own leg's, from the run's submission:
        # ten legs served in turn took 1, 2, ... 10 turns, so the ten
        # observations add up to several times the request's wall.
        assert sum_seconds() - seconds0 > 0.004 * 55
        assert sum_seconds() - seconds0 > 2 * wall
