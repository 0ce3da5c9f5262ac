"""A GroupBy's answer as columns (PR 31): `exec/result.py` GroupCounts,
the two numpy enumerators of `exec/tpu.py` that build it, its template
encoding in `utils/fastjson`, the key pass, and read-your-writes through
the columnar path. The enumeration loops this PR replaced are kept here
as the plain reference."""

import itertools
import json

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.rescache import result_nbytes
from pilosa_tpu.exec.result import (
    FieldRow,
    GroupCount,
    GroupCounts,
    RowIDs,
    merge_group_counts,
    result_to_json,
)
from pilosa_tpu.exec.tpu import TPUBackend
from pilosa_tpu.server.api import API
from pilosa_tpu.server.wire import encode_query_result
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import fastjson
from pilosa_tpu.utils.stats import global_stats


# -- the plain reference: the loops as they stood before this PR ----------


def _candidates(starts, child_rows, rs, n):
    cand = []
    for i in range(n):
        if child_rows[i] is not None:
            cand.append([r for r in child_rows[i] if r >= starts[i]])
        else:
            cand.append(list(range(starts[i], rs[i])))
    return cand


def reference_enumerate(names, starts, child_rows, rs, stats_np, n, cap=None):
    cand = _candidates(starts, child_rows, rs, n)
    out = []
    full = cap if cap is not None else float("inf")
    if n == 1:
        for a in cand[0]:
            v = int(stats_np[a]) if a < rs[0] else 0
            if v > 0:
                out.append(GroupCount([FieldRow(names[0], a)], v))
                if len(out) >= full:
                    return out
    elif n == 2:
        for a in cand[0]:
            for b in cand[1]:
                v = int(stats_np[a, b]) if (a < rs[0] and b < rs[1]) else 0
                if v > 0:
                    out.append(GroupCount(
                        [FieldRow(names[0], a), FieldRow(names[1], b)], v
                    ))
                    if len(out) >= full:
                        return out
    else:
        extra_rs = rs[2:]
        for a in cand[0]:
            for b in cand[1]:
                if not (a < rs[0] and b < rs[1]):
                    continue
                for extra in itertools.product(*cand[2:]):
                    if any(e >= extra_rs[t] for t, e in enumerate(extra)):
                        continue
                    k = 0
                    for t, e in enumerate(extra):
                        k = k * extra_rs[t] + e
                    v = int(stats_np[k, a, b])
                    if v > 0:
                        out.append(GroupCount(
                            [FieldRow(names[0], a), FieldRow(names[1], b)]
                            + [FieldRow(names[2 + t], e)
                               for t, e in enumerate(extra)],
                            v,
                        ))
                        if len(out) >= full:
                            return out
    return out


def reference_enumerate_live(names, starts, child_rows, rs, live_rows,
                             stats_live, n, cap=None):
    cand = _candidates(starts, child_rows, rs, n)
    dims = [len(lr) for lr in live_rows]
    lookups = [{int(r): p for p, r in enumerate(lr)} for lr in live_rows]
    out = []
    full = cap if cap is not None else float("inf")
    for a in cand[0]:
        for b in cand[1]:
            if not (a < rs[0] and b < rs[1]):
                continue
            for extra in itertools.product(*cand[2:]):
                if any(e not in lookups[t] for t, e in enumerate(extra)):
                    continue
                k = 0
                for t, e in enumerate(extra):
                    k = k * dims[t] + lookups[t][e]
                v = int(stats_live[k, a, b])
                if v > 0:
                    out.append(GroupCount(
                        [FieldRow(names[0], a), FieldRow(names[1], b)]
                        + [FieldRow(names[2 + t], e)
                           for t, e in enumerate(extra)],
                        v,
                    ))
                    if len(out) >= full:
                        return out
    return out


# -- enumeration: one parametrised test, a case a line ---------------------

RS = {1: [11], 2: [5, 9], 3: [3, 4, 6], 4: [3, 2, 4, 5]}
NAMES = ["a", "b", "c", "d"]


def _tensor(n, seed, fill=0.4):
    rng = np.random.default_rng(seed)
    rs = RS[n]
    shape = tuple(rs) if n <= 2 else (int(np.prod(rs[2:])), rs[0], rs[1])
    t = rng.integers(1, 10 ** 6, size=shape).astype(np.int64)
    t[rng.random(shape) > fill] = 0
    return t


def _variants(n):
    """(label, starts, child_rows, cap, fill) for n fields."""
    rs = RS[n]
    none = [None] * n
    zero = [0] * n
    last = n - 1
    rows_last = list(none)
    # Candidate order is the child's, not sorted; 1 twice would be a
    # caller's fault, a row past the stack's height is not.
    rows_last[last] = RowIDs([rs[last] - 1, 0, 1, rs[last] + 3])
    rows_first = list(none)
    rows_first[0] = RowIDs([1, rs[0] + 7, 2])
    rows_all = [RowIDs([0, min(2, r - 1), r + 1]) for r in rs]
    starts_last = list(zero)
    starts_last[last] = 2
    starts_first = list(zero)
    starts_first[0] = 1
    starts_past = list(zero)
    starts_past[last] = rs[last] + 5
    out = [
        ("plain", zero, none, None, 0.4),
        ("full", zero, none, None, 1.0),
        ("all_zero", zero, none, None, 0.0),
        ("cap1", zero, none, 1, 0.4),
        ("cap5", zero, none, 5, 0.4),
        ("cap_past_end", zero, none, 10 ** 6, 0.4),
        ("previous_last", starts_last, none, None, 0.5),
        ("previous_first", starts_first, none, None, 0.5),
        ("previous_past_height", starts_past, none, None, 0.5),
        ("previous_huge", [2 ** 64] + zero[1:], none, None, 0.5),
        ("child_rows_last", zero, rows_last, None, 0.6),
        ("child_rows_first", zero, rows_first, None, 0.6),
        ("child_rows_all", zero, rows_all, None, 0.8),
        ("child_rows_and_previous", starts_last, rows_last, None, 0.8),
        ("child_rows_and_cap", zero, rows_last, 3, 0.8),
        ("child_rows_past_height_only", zero,
         none[:last] + [RowIDs([rs[last], rs[last] + 1])], None, 0.8),
    ]
    return out


DENSE_CASES = [
    pytest.param(n, *v[1:], id=f"n{n}-{v[0]}")
    for n in (1, 2, 3, 4) for v in _variants(n)
]


def _names_fields(n):
    return [(name, None) for name in NAMES[:n]]


@pytest.mark.parametrize("n,starts,child_rows,cap,fill", DENSE_CASES)
def test_enumerate_equals_the_loops(n, starts, child_rows, cap, fill):
    stats = _tensor(n, seed=n * 31 + len(str(cap)), fill=fill)
    be = TPUBackend.__new__(TPUBackend)
    got = be._group_enumerate(
        _names_fields(n), starts, child_rows, RS[n], stats, n, cap
    )
    want = reference_enumerate(
        NAMES[:n], starts, child_rows, RS[n], stats, n, cap
    )
    assert isinstance(got, GroupCounts)
    assert got.rows.dtype == np.int64 and got.rows.shape == (len(want), n)
    assert got.counts.dtype == np.int64
    assert got == want
    assert fastjson.encode_result(got) == json.dumps(
        [gc.to_json() for gc in want]
    ).encode()


def _live(n, seed, fill):
    """A pruned payload: each extra field keeps some of its rows."""
    rng = np.random.default_rng(seed)
    rs = RS[n]
    live_rows = []
    for r in rs[2:]:
        keep = np.flatnonzero(rng.random(r) < 0.7)
        live_rows.append(tuple(int(x) for x in keep))
    k_live = int(np.prod([len(lr) for lr in live_rows]))
    stats = rng.integers(1, 10 ** 6, size=(k_live, rs[0], rs[1]))
    stats[rng.random(stats.shape) > fill] = 0
    return live_rows, stats.astype(np.int32)


LIVE_CASES = [
    pytest.param(n, *v[1:], id=f"n{n}-{v[0]}")
    for n in (3, 4) for v in _variants(n)
]


@pytest.mark.parametrize("n,starts,child_rows,cap,fill", LIVE_CASES)
def test_enumerate_live_equals_the_loops(n, starts, child_rows, cap, fill):
    live_rows, stats = _live(n, seed=n * 17 + len(str(cap)), fill=fill)
    be = TPUBackend.__new__(TPUBackend)
    got = be._group_enumerate_live(
        _names_fields(n), starts, child_rows, RS[n], live_rows, stats, n, cap
    )
    want = reference_enumerate_live(
        NAMES[:n], starts, child_rows, RS[n], live_rows, stats, n, cap
    )
    assert isinstance(got, GroupCounts)
    assert got == want
    assert fastjson.encode_result(got) == json.dumps(
        [gc.to_json() for gc in want]
    ).encode()


def test_enumerate_live_with_a_field_pruned_to_nothing():
    be = TPUBackend.__new__(TPUBackend)
    got = be._group_enumerate_live(
        _names_fields(3), [0, 0, 0], [None] * 3, RS[3], ((),),
        np.zeros((0, 3, 4), np.int32), 3,
    )
    assert len(got) == 0 and got == [] and got.rows.shape == (0, 3)
    assert fastjson.encode_result(got) == b"[]"


# -- the sequence protocol ---------------------------------------------------


def _sample(keys=None):
    rows = np.array([[1, 10], [1, 12], [2, 10], [3, 99]], dtype=np.int64)
    counts = np.array([5, 7, 1, 12345678901], dtype=np.int64)
    return GroupCounts(["f", "g"], rows, counts, keys)


def _objects(keys=(None, None)):
    r = _sample()
    return [
        GroupCount(
            [
                FieldRow(name, int(r.rows[g, j]),
                         keys[j][g] if keys[j] is not None else "")
                for j, name in enumerate(r.fields)
            ],
            int(r.counts[g]),
        )
        for g in range(len(r))
    ]


class TestSequence:
    def test_len_index_iteration(self):
        r, want = _sample(), _objects()
        assert len(r) == 4 and bool(r)
        assert list(r) == want
        assert [r[i] for i in range(4)] == want
        assert r[-1] == want[-1] and r[-4] == want[0]
        assert isinstance(r[0], GroupCount)
        assert isinstance(r[0].group[0].row_id, int)
        assert isinstance(r[0].count, int)
        for bad in (4, -5):
            with pytest.raises(IndexError):
                r[bad]

    @pytest.mark.parametrize("sl", [
        slice(1, None), slice(None, 2), slice(1, 3), slice(0, 0),
        slice(10, None), slice(None, 10 ** 9), slice(None, None, 2),
        slice(-2, None),
    ], ids=str)
    def test_a_slice_is_a_view_of_the_same_type(self, sl):
        r, want = _sample(), _objects()
        part = r[sl]
        assert isinstance(part, GroupCounts)
        assert part == want[sl] and len(part) == len(want[sl])
        assert part.fields == r.fields
        assert len(part) == 0 or np.shares_memory(part.rows, r.rows)

    def test_a_slice_keeps_its_keys(self):
        keys = [None, ["ten", "twelve", "ten", ""]]
        r = _sample(list(keys))
        assert r[1:3] == _objects(keys)[1:3]
        assert r[1:3].keys == [None, ["twelve", "ten"]]

    def test_equality(self):
        r, want = _sample(), _objects()
        assert r == want and want == r
        assert r == tuple(want)
        assert r == _sample()
        assert r != want[:3] and not (r == want[:3])
        other = _sample()
        other.counts = other.counts + 1
        assert r != other
        assert r != 4 and r != "x"
        assert GroupCounts(["f"], np.zeros((0, 1), np.int64),
                           np.zeros(0, np.int64)) == []
        with pytest.raises(TypeError):
            hash(r)

    def test_the_objects_are_fresh_every_time(self):
        r = _sample()
        first = r[0]
        first.group[0].row_key = "mine"
        first.count = -1
        assert r[0] == _objects()[0]

    def test_merge_takes_it_as_either_side(self):
        r, want = _sample(), _objects()
        assert merge_group_counts(r, r[1:], 10) == merge_group_counts(
            want, want[1:], 10
        )
        assert merge_group_counts([], r, 2) == want[:2]

    def test_the_dict_encoder_takes_it(self):
        r = _sample()
        assert result_to_json(r) == [gc.to_json() for gc in _objects()]

    def test_result_nbytes_charges_the_arrays(self):
        r = _sample()
        assert result_nbytes(r) == r.nbytes
        assert r.nbytes >= r.rows.nbytes + r.counts.nbytes
        keyed = _sample([None, ["ten", "twelve", "ten", ""]])
        assert result_nbytes(keyed) > result_nbytes(r)
        many = GroupCounts(
            ["f", "g"], np.zeros((1000, 2), np.int64), np.ones(1000, np.int64)
        )
        # 24 bytes a group, where a thousand GroupCount objects are
        # charged 192 each.
        assert result_nbytes(many) < 1000 * 30
        assert result_nbytes(list(many)) > 1000 * 150


# -- byte identity of the encoding -------------------------------------------


def _oracle(r) -> bytes:
    return json.dumps([gc.to_json() for gc in r]).encode()


class TestEncoding:
    @pytest.mark.parametrize("digits", range(1, 21))
    def test_ids_and_counts_of_every_width(self, digits):
        # Twenty digits are past int64: a column handed over as uint64
        # encodes all the same.
        dtype = np.uint64 if digits == 20 else np.int64
        top = min(10 ** digits - 1, int(np.iinfo(dtype).max))
        low = 10 ** (digits - 1) if digits > 1 else 0
        vals = np.array([low, top, 0, 7, top // 3 + 1], dtype=dtype)
        r = GroupCounts(
            ["f", "weiß"], np.stack([vals, vals[::-1]], axis=1),
            vals[[1, 0, 3, 4, 2]],
        )
        want = _oracle(r)
        assert str(top).encode() in want and len(str(top)) == digits
        assert fastjson.encode_result(r) == want

    def test_mixed_widths_in_one_column(self):
        vals = np.array([10 ** k for k in range(19)] + [0], dtype=np.int64)
        r = GroupCounts(["f"], vals[:, None], vals[::-1].copy())
        assert fastjson.encode_result(r) == _oracle(r)

    def test_empty_and_one_group(self):
        empty = GroupCounts(["f", "g"], np.zeros((0, 2), np.int64),
                            np.zeros(0, np.int64))
        assert fastjson.encode_result(empty) == b"[]" == _oracle(empty)
        one = GroupCounts(["f"], np.array([[3]]), np.array([9]))
        assert fastjson.encode_result(one) == _oracle(one)
        assert fastjson.encode_result(one) == (
            b'[{"group": [{"field": "f", "rowID": 3}], "count": 9}]'
        )

    def test_a_keyed_field_beside_an_id_field(self):
        keys = [None, ["ten", "clé \"q\" \\ \n", "", "zwölf" * 40], None]
        rows = np.array(
            [[1, 10, 0], [1, 12, 5], [2, 13, 10 ** 12], [3, 99, 7]], np.int64
        )
        r = GroupCounts(["f", "g", "h"], rows,
                        np.array([5, 7, 1, 12345678901]), keys)
        want = _oracle(r)
        assert fastjson.encode_result(r) == want
        # A row with no key keeps its rowID, as the object form does.
        assert b'{"field": "g", "rowID": 13}' in want
        assert b'"rowKey": "ten"' in want

    def test_every_field_keyed(self):
        rows = np.array([[1, 2], [3, 4]], np.int64)
        r = GroupCounts(["f", "g"], rows, np.array([1, 2]),
                        [["a", "b"], ["c", "d"]])
        assert fastjson.encode_result(r) == _oracle(r)

    def test_a_field_name_that_needs_escaping(self):
        r = GroupCounts(['q"uo\\te', "ünï"], np.array([[1, 2]]), np.array([3]))
        assert fastjson.encode_result(r) == _oracle(r)

    def test_a_slice_encodes_as_its_groups(self):
        r = _sample([None, ["ten", "twelve", "ten", ""]])
        for sl in (slice(1, None), slice(None, 2), slice(3, 3)):
            assert fastjson.encode_result(r[sl]) == _oracle(r[sl])

    def test_the_protobuf_body_is_that_of_the_objects(self):
        for r in (_sample(), _sample([None, ["ten", "twelve", "ten", ""]]),
                  _sample()[:0]):
            assert encode_query_result(r) == encode_query_result(list(r))

    def test_the_counter_names_the_path(self):
        def read():
            got = global_stats.counter_totals("group_rows_encoded_total")
            return (
                sum(v for k, v in got.items() if "columnar" in k),
                sum(v for k, v in got.items() if "objects" in k),
            )

        r = _sample()
        c0, o0 = read()
        fastjson.encode_result(r)
        fastjson.encode_result(r[1:])
        assert read() == (c0 + 7, o0)
        list(r)
        r[2]
        encode_query_result(r)
        assert read() == (c0 + 7, o0 + 4 + 1 + 4)


# -- through the executor: the device path on a holder ----------------------


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data")).open()
    yield h
    h.close()


def _fill(idx, name, nrows, rng, shards=2, per_row=400, keys=False):
    f = idx.create_field(name, FieldOptions(keys=keys))
    for row in range(nrows):
        cols = np.unique(
            rng.integers(0, shards * SHARD_WIDTH, per_row, dtype=np.uint64)
        )
        f.import_bits(np.full(cols.size, row, dtype=np.uint64), cols)
    return f


def _pair(holder):
    """(api over the device backend, api over the host iterator)."""
    dev = API(holder, Executor(holder, backend=TPUBackend(holder)))
    host = API(holder, Executor(holder))
    return dev, host


PAGE = [
    "GroupBy(Rows(a))",
    "GroupBy(Rows(a), Rows(b))",
    "GroupBy(Rows(a), Rows(b), Rows(c))",
    "GroupBy(Rows(a), Rows(b), Rows(c), limit=100, offset=37)",
    "GroupBy(Rows(a), Rows(b), Rows(c), filter=Row(a=1))",
    "GroupBy(Rows(a, previous=0), Rows(b), Rows(c, limit=20))",
    "GroupBy(Rows(a), Rows(b), Rows(c, previous=1000))",
]


@pytest.fixture
def thousands(holder, rng):
    """a x b x c = 6 x 10 x 50 with dense-enough rows: a three-field
    GroupBy of a few thousand groups."""
    idx = holder.create_index("i")
    _fill(idx, "a", 6, rng, per_row=60000)
    _fill(idx, "b", 10, rng, per_row=60000)
    _fill(idx, "c", 50, rng, per_row=30000)
    return holder


@pytest.mark.parametrize("q", PAGE)
def test_query_bytes_match_the_dict_path_and_the_host_iterator(thousands, q):
    dev, host = _pair(thousands)
    body = dev.query_bytes("i", q)
    assert body == (json.dumps(dev.query("i", q)) + "\n").encode()
    assert body == host.query_bytes("i", q)
    assert dev.query_proto("i", q) == host.query_proto("i", q)
    (answer,) = dev.query_results("i", q)[0]
    assert isinstance(answer, GroupCounts)
    if q == PAGE[2]:
        assert len(answer) > 2000


def test_a_json_client_builds_no_objects(thousands):
    dev, _ = _pair(thousands)

    def read(path):
        got = global_stats.counter_totals("group_rows_encoded_total")
        return sum(v for k, v in got.items() if path in k)

    c0, o0 = read("columnar"), read("objects")
    body = dev.query_bytes("i", "".join(PAGE[:3]))
    groups = sum(len(r) for r in json.loads(body)["results"])
    assert read("columnar") - c0 == groups > 2000
    assert read("objects") == o0


def _keyed(holder, rng):
    idx = holder.create_index("k")
    _fill(idx, "a", 3, rng)
    idx.create_field("city", FieldOptions(keys=True))
    _fill(idx, "c", 4, rng)
    api = API(holder, Executor(holder, backend=TPUBackend(holder)))
    for col, city in enumerate(["oslo", "rom", "oslo", "köln"]):
        for row in range(3):
            api.query("k", f'Set({col + 10 * row}, city="{city}")')
            api.query("k", f"Set({col + 10 * row}, a={row})")
            api.query("k", f"Set({col + 10 * row}, c=1)")
    return api


@pytest.mark.parametrize("q", [
    "GroupBy(Rows(city))",
    "GroupBy(Rows(a), Rows(city))",
    "GroupBy(Rows(city), Rows(a), Rows(c))",
])
def test_the_key_pass_shares_no_state_between_requests(holder, rng, q):
    api = _keyed(holder, rng)
    host = API(holder, Executor(holder))
    (first,) = api.query_results("k", q)[0]
    assert isinstance(first, GroupCounts)
    j = first.fields.index("city")
    assert set(first.keys[j]) == {"oslo", "rom", "köln"}
    assert all(k is None for i, k in enumerate(first.keys) if i != j)
    before = api.query_bytes("k", q)
    assert before == (json.dumps(api.query("k", q)) + "\n").encode()
    assert before == host.query_bytes("k", q)
    frozen = [list(first.keys[j]), first.rows.copy(), first.counts.copy()]
    # A key is added; the second request sees it, and what the first
    # request was handed does not change under it.
    api.query("k", 'Set(5, city="łódź")Set(5, a=1)Set(5, c=1)')
    (second,) = api.query_results("k", q)[0]
    assert second is not first
    assert "łódź" in second.keys[j] and "łódź" not in first.keys[j]
    assert first.keys[j] == frozen[0]
    assert np.array_equal(first.rows, frozen[1])
    assert np.array_equal(first.counts, frozen[2])
    after = api.query_bytes("k", q)
    assert "łódź" in {
        fr.get("rowKey")
        for g in json.loads(after)["results"][0] for fr in g["group"]
    }
    assert after == host.query_bytes("k", q)
    assert api.query_proto("k", q) == host.query_proto("k", q)


@pytest.mark.parametrize("q,group", [
    ("GroupBy(Rows(a))", [("a", 1)]),
    ("GroupBy(Rows(a), Rows(b))", [("a", 1), ("b", 2)]),
    ("GroupBy(Rows(a), Rows(b), Rows(c))", [("a", 1), ("b", 2), ("c", 3)]),
    ("GroupBy(Rows(a), Rows(b), Rows(c), filter=Row(a=1))",
     [("a", 1), ("b", 2), ("c", 3)]),
])
def test_a_set_between_two_groupbys_shows_in_the_second(holder, rng, q, group):
    """Read-your-writes through the columnar path: the TopN rank vector,
    the pair table, the maintained tensor and the filtered payload."""
    idx = holder.create_index("i")
    _fill(idx, "a", 3, rng)
    _fill(idx, "b", 4, rng)
    _fill(idx, "c", 5, rng)
    dev, host = _pair(holder)

    def count_of(api):
        (answer,) = api.query_results("i", q)[0]
        want = [FieldRow(f, r) for f, r in group]
        return sum(gc.count for gc in answer if gc.group == want)

    col = 2 * SHARD_WIDTH - 77
    assert not idx.field("a").row(1, 1).includes_column(col)
    before = count_of(dev)
    assert before == count_of(host)
    dev.query("i", "".join(f"Set({col}, {f}={r})" for f, r in group))
    assert count_of(dev) == before + 1 == count_of(host)
    assert dev.query_bytes("i", q) == host.query_bytes("i", q)
    dev.query("i", f"Clear({col}, {group[0][0]}={group[0][1]})")
    assert count_of(dev) == before
    assert dev.query_bytes("i", q) == host.query_bytes("i", q)
