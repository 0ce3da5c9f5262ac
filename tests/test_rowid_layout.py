"""A mutex field held as one row id a column (the row-id layout).

Where an index's dense stacks would not fit the block store's budget,
its tallest mutex fields are held as row ids (`_StackedBlocks.get_rowid`,
ops/blocks.py `rowid_shape`): a `Row` of such a field is a compare of the
ids, a grouped Sum prunes it by a histogram of the ids under the filter
and builds its live rows into a small dense stack for the count kernel.
Here, at 2 shards, the budget is forced small through `max-hbm-bytes` so
that the choice is taken, and every call of a Star Schema Benchmark
stream is answered by the row-id layout, by the dense layout and by
`exec/cpu.py`, and checked against a plain numpy evaluation of the call
over the row-id form and over the dense form of the same fields. Beside
them: freshness after Set and Clear, the budget at every upload, pins, a
`?shards=` subset, and the paths that leave a row-id field to the host.
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import options_for_int, options_for_mutex
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.cpu import CPUBackend
from pilosa_tpu.exec.result import result_to_json
from pilosa_tpu.exec.tpu import TPUBackend
from pilosa_tpu.ops.blocks import (
    WORDS_PER_SHARD,
    pack_fragment,
    pack_fragment_rowid,
    rowid_dtype,
    tile_words,
)
from pilosa_tpu.ops.kernels import rowid_hist, rowid_row, rowid_words
from pilosa_tpu.pql import parse_string
from pilosa_tpu.pql.ast import BETWEEN, EQ, GT, GTE, LT, LTE, NEQ, Call, Condition
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.monitor import publish_hbm_gauges
from pilosa_tpu.utils.stats import StatsClient, global_stats

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import plugins  # noqa: E402

SHAPE = plugins.load("shapes", "ssb")
SHARDS = 2
#: Holds every dense stack of the index but p_brand1's (~262 MB of ~500).
BUDGET = 350 << 20
#: One stream's constants, chosen where the draws below concentrate.
CONSTANTS = {
    "q1.1": (2, 1), "q1.2": (30, 2, 10), "q1.3": (3, 10, 4, 20),
    "q2.1": (7, 0), "q2.2": (7, 4, 0), "q2.3": (285, 0),
    "q3.1": (0, 1), "q3.2": (3, 0), "q3.3": (3, 0, 5, 1), "q3.4": (3, 1, 2, 30),
    "q4.1": (0, 1), "q4.2": (0, 2, 1), "q4.3": (3, 2, 8),
}
CALLS = [(q, agg, CONSTANTS[q]) for q, agg in SHAPE.STREAM]
MEASURES = {"lo_quantity": (0, 50), "lo_extendedprice": (0, 16777215),
            "lo_revenue": (0, 16777215), "lo_supplycost": (0, 131071)}


def counter(name, **labels):
    want = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return global_stats.counter_totals(name).get(
        f"{name}{{{want}}}" if want else name, 0.0
    ) if labels else sum(global_stats.counter_totals(name).values())


@pytest.fixture(scope="module")
def ssb(tmp_path_factory):
    """(holder, draws) of lineorder rows over two shards at SSB's widths:
    most rows within one nation and two part categories, where the
    stream's constants fall, the rest over every city and brand (so
    p_brand1 is 1,000 rows tall); each row's dimensions follow the
    hierarchy the queries walk. `draws[f]` is the column's row of a
    mutex field and the value of an int field."""
    rng = np.random.default_rng(20261042)
    hot, wide = 30000, 10000
    n = hot + wide
    cols = np.sort(rng.choice(SHARDS * SHARD_WIDTH, n, replace=False))
    month = rng.integers(0, 84, n)
    c_city = np.concatenate([30 + rng.integers(0, 6, hot), rng.integers(0, 250, wide)])
    s_city = np.where(rng.random(n) < 0.5, c_city,
                      np.concatenate([30 + rng.integers(0, 6, hot),
                                      rng.integers(0, 250, wide)]))
    brand = np.concatenate([40 * rng.integers(7, 9, hot) + rng.integers(0, 40, hot),
                            rng.integers(0, 1000, wide)])
    # Hot and wide rows in every shard.
    mix = rng.permutation(n)
    c_city, s_city, brand = c_city[mix], s_city[mix], brand[mix]
    brand[0] = brand[-1] = 999
    draws = {
        "d_year": month // 12, "d_yearmonthnum": month,
        "d_weeknuminyear": rng.integers(0, 53, n),
        "lo_discount": rng.integers(0, 11, n),
        "c_city": c_city, "c_nation": c_city // 10, "c_region": c_city // 50,
        "s_city": s_city, "s_nation": s_city // 10, "s_region": s_city // 50,
        "p_brand1": brand, "p_category": brand // 40, "p_mfgr": brand // 200,
    }
    for name, (lo, hi) in MEASURES.items():
        draws[name] = rng.integers(lo + 1, hi + 1, n)
    holder = Holder(str(tmp_path_factory.mktemp("rowid") / "data")).open()
    idx = holder.create_index("ssb")
    for name in draws:
        if name not in MEASURES:
            idx.create_field(name, options_for_mutex())
            idx.field(name).import_bits(draws[name].astype(np.uint64),
                                        cols.astype(np.uint64))
    for name, (lo, hi) in MEASURES.items():
        idx.create_field(name, options_for_int(lo, hi))
        idx.field(name).import_value(cols.astype(np.uint64), draws[name])
    draws["cols"] = cols
    yield holder, draws
    holder.close()


def backends(holder):
    """The executors: row ids under the small budget, dense with none
    known (a CPU device reports no limit), and exec/cpu.py's."""
    rowid = TPUBackend(holder, max_bytes=BUDGET)
    return (Executor(holder, backend=rowid), Executor(holder, backend=TPUBackend(holder)),
            Executor(holder, backend=CPUBackend(holder)))


@pytest.fixture(scope="module")
def executors(ssb):
    return backends(ssb[0])


# -- the plain reference -----------------------------------------------------


def forms(draws):
    """The same mutex fields two ways: the row-id form, a row a column,
    and the dense form, bool[rows, columns]."""
    mutex = [f for f in draws if f not in MEASURES and f != "cols"]
    rowid = {f: draws[f] for f in mutex}
    dense = {f: np.arange(draws[f].max() + 1)[:, None] == draws[f][None, :]
             for f in mutex}
    return rowid, dense


def _condition(values, cond: Condition):
    if cond.op == BETWEEN:
        lo, hi = cond.int_slice_value()
        return (values >= lo) & (values <= hi)
    v = cond.value
    return {EQ: values == v, NEQ: values != v, LT: values < v, LTE: values <= v,
            GT: values > v, GTE: values >= v}[cond.op]


def _filter(call: Call, draws, rows_of):
    """The columns a filter tree selects, over one form of the fields."""
    if call.name in ("Union", "Intersect"):
        parts = [_filter(ch, draws, rows_of) for ch in call.children]
        return (np.logical_or if call.name == "Union" else np.logical_and).reduce(parts)
    assert call.name == "Row" and len(call.args) == 1, call
    (name, arg), = call.args.items()
    if isinstance(arg, Condition):
        return _condition(draws[name], arg)
    return rows_of(name, arg)


def plain(pql: str, draws, form: str) -> list:
    """A GroupBy(…, filter=…, aggregate=Sum(field=…)) call evaluated column
    by column in numpy over the row-id form or the dense form: its
    non-empty groups in odometer order (the last field fastest), each
    with count and sum, as the server's JSON gives them."""
    rowid, dense = forms(draws)
    call = parse_string(pql).calls[0]
    if form == "rowid":
        rows_of = lambda f, r: rowid[f] == r  # noqa: E731
    else:
        rows_of = lambda f, r: (dense[f][r] if r < dense[f].shape[0]  # noqa: E731
                                else np.zeros(draws["cols"].size, bool))
    keep = _filter(call.args["filter"], draws, rows_of)
    fields = [ch.args.get("_field") or ch.args.get("field") for ch in call.children]
    values = draws[call.args["aggregate"].args["field"]]
    if form == "rowid":
        heights = [int(rowid[f].max()) + 1 for f in fields]
        key = np.zeros(keep.sum(), np.int64)
        for f, h in zip(fields, heights):
            key = key * h + rowid[f][keep]
        size = int(np.prod(heights))
        count = np.bincount(key, minlength=size)
        total = np.bincount(key, weights=values[keep].astype(np.float64),
                            minlength=size)
        groups = [(np.unravel_index(k, heights), count[k], total[k])
                  for k in np.flatnonzero(count)]
    else:
        # The live rows of each field under the filter, then every
        # combination of them counted as an AND of dense rows.
        live = [np.flatnonzero(dense[f][:, keep].any(axis=1)) for f in fields]
        groups = []
        for combo in np.array(np.meshgrid(*live, indexing="ij")).reshape(len(fields), -1).T:
            sel = keep.copy()
            for f, r in zip(fields, combo):
                sel &= dense[f][r]
            if sel.any():
                groups.append((combo, sel.sum(), values[sel].sum()))
    return [{"group": [{"field": f, "rowID": int(r)} for f, r in zip(fields, rows)],
             "count": int(c), "sum": int(round(t))} for rows, c, t in groups]


# -- a stream ----------------------------------------------------------------


def test_the_budget_holds_p_brand1_as_row_ids_and_nothing_else(ssb, executors):
    holder, _ = ssb
    rowid, dense, _ = executors
    idx = holder.index("ssb")
    shards = tuple(range(SHARDS))
    held = {f for f in idx.fields
            if rowid.backend.blocks.rowid_field("ssb", idx.field(f), shards)}
    assert held == {"p_brand1"}
    # No limit known, or one that holds the dense stacks: every field dense.
    assert not any(dense.backend.blocks.rowid_field("ssb", idx.field(f), shards)
                   for f in idx.fields)
    roomy = TPUBackend(holder, max_bytes=1 << 30)
    assert not any(roomy.blocks.rowid_field("ssb", idx.field(f), shards)
                   for f in idx.fields)


@pytest.mark.parametrize("call", CALLS, ids=[f"{q}-{agg}" for q, agg, _ in CALLS])
def test_each_call_of_a_stream_is_the_same_from_row_ids(ssb, executors, call):
    """The row-id layout, the dense layout and exec/cpu.py give the plain
    reference's answer, which is the same over both forms; no call of
    the row-id backend leaves for the host."""
    _, draws = ssb
    rowid, dense, cpu = executors
    pql = SHAPE.pql(call)
    want = plain(pql, draws, "rowid")
    assert plain(pql, draws, "dense") == want
    before = counter("device_fallback_total")
    got = result_to_json(rowid.execute("ssb", pql)[0])
    assert counter("device_fallback_total") == before
    assert got == want
    assert result_to_json(dense.execute("ssb", pql)[0]) == want
    assert result_to_json(cpu.execute("ssb", pql)[0]) == want
    if call[0] in ("q2.1", "q2.3", "q3.2", "q4.3"):
        assert want, f"{call[0]} finds no group: it tests nothing"


def test_a_stream_reads_the_ids_by_histogram_and_row_build(ssb):
    """Q2.1 and Q4.3 (twice) prune p_brand1 by a histogram of its ids
    under the filter, Q2.2 and Q2.3 take the brands their filter names;
    every one builds its live brands into a dense operand."""
    holder, draws = ssb
    rowid, _, _ = backends(holder)
    hist = counter("device_launches_total", kind="rowid_hist")
    built = counter("rowid_rows_built_total")
    bits = counter("rowid_hist_rows_total")
    for call in CALLS:
        rowid.execute("ssb", SHAPE.pql(call))
    assert counter("device_launches_total", kind="rowid_hist") - hist == 3
    assert counter("rowid_hist_rows_total") - bits == 3 * (16 + 1)
    assert counter("rowid_rows_built_total") > built
    led = {e["field"]: e.get("layout", "dense") for e in rowid.backend.blocks.ledger()}
    assert led["p_brand1"] == "rowid"
    assert set(led.values()) == {"dense", "rowid"}


# -- the layout's pieces ------------------------------------------------------


def test_ids_pack_to_the_dense_layout_s_words(ssb):
    """A shard's ids compared with a row pack to the dense layout's row,
    for every row of the field."""
    holder, _ = ssb
    frag = holder.index("ssb").field("p_brand1").view("standard").fragment(0)
    ids = pack_fragment_rowid(frag, rowid_dtype(frag.max_row_id + 1))
    dense = tile_words(pack_fragment(frag))
    rows = np.array([0, 7, 285, 320, 998, 999], dtype=np.uint32)
    got = np.asarray(rowid_words(ids[None].astype(np.uint32)
                                 == rows[:, None, None, None]))
    assert np.array_equal(got, dense[rows])
    assert dense[rows].any()
    one = np.asarray(rowid_row(ids[None], np.array([7, 999], np.uint32)))[0]
    assert np.array_equal(one, dense[7] | dense[999])


@pytest.mark.parametrize("n_rows, filtered", [(200, True), (1000, False), (3000, True)])
def test_the_histogram_counts_ids_under_the_filter(n_rows, filtered):
    """uint8 and uint16 ids, one launch or several (past 1,024 rows the
    high parts take more than one)."""
    rng = np.random.default_rng(n_rows)
    dt = rowid_dtype(n_rows)
    flat = rng.integers(0, n_rows, (1, SHARD_WIDTH)).astype(dt)
    flat[0, ::5] = np.iinfo(dt).max  # columns no row holds
    ids = flat.reshape(1, -1, 128, 32).transpose(0, 1, 3, 2)
    under = rng.random((1, SHARD_WIDTH)) < 0.4 if filtered else np.ones((1, SHARD_WIDTH), bool)
    filt = np.packbits(under, axis=1, bitorder="little").view(np.uint32).reshape(1, -1, 128)
    bits = max(1, (n_rows - 1).bit_length())
    lo = min(bits, 5)
    highs = 1 << (bits - lo)
    step = min(highs, 32)
    got = np.concatenate([
        np.asarray(rowid_hist(ids, filt if filtered else None, h0, lo, step,
                              interpret=True)).reshape(-1)
        for h0 in range(0, highs, step)
    ])
    held = under[0] & (flat[0] != np.iinfo(dt).max)
    want = np.bincount(flat[0][held].astype(np.int64), minlength=got.size)
    assert np.array_equal(got, want)


def test_a_column_in_two_rows_keeps_the_field_dense(tmp_path):
    """import_roaring can leave a column in two rows of a mutex field:
    the ids cannot hold it, and the field stays dense and exact."""
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    f = idx.create_field("m", options_for_mutex())
    f.import_bits(np.arange(600, dtype=np.uint64), np.arange(600, dtype=np.uint64))
    frag = f.view("standard").fragment(0)
    frag.storage.add(5 * SHARD_WIDTH + 7)  # column 7 in rows 7 and 5
    assert pack_fragment_rowid(frag, np.uint16) is None
    be = TPUBackend(holder, max_bytes=(600 // 8 + 2) * WORDS_PER_SHARD * 4)
    assert be.blocks.rowid_field("i", f, (0,))
    ex = Executor(holder, backend=be)
    assert ex.execute("i", "Count(Row(m=5))")[0] == 2
    assert not be.blocks.rowid_field("i", f, (0,))
    holder.close()


# -- freshness, the budget, pins, subsets ------------------------------------


def test_a_set_or_clear_on_a_row_id_field_is_in_the_next_read(tmp_path):
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    f = idx.create_field("m", options_for_mutex())
    rng = np.random.default_rng(3)
    cols = np.arange(0, 2 * SHARD_WIDTH, 97, dtype=np.uint64)
    f.import_bits(rng.integers(0, 600, cols.size).astype(np.uint64), cols)
    be = TPUBackend(holder, max_bytes=64 * WORDS_PER_SHARD * 4)
    ex = Executor(holder, backend=be)
    host = Executor(holder, backend=CPUBackend(holder))
    assert be.blocks.rowid_field("i", f, (0, 1))

    def both(q):
        got = ex.execute("i", q)[0]
        assert got == host.execute("i", q)[0], q
        return got

    n = both("Count(Row(m=599))")
    ids = be.blocks.get_rowid("i", f, (0, 1))[0]
    ex.execute("i", f"Set({SHARD_WIDTH + 5}, m=599)")   # a column moves rows
    assert both("Count(Row(m=599))") == n + 1
    again = be.blocks.get_rowid("i", f, (0, 1))[0]
    assert again is not ids
    ex.execute("i", f"Clear({SHARD_WIDTH + 5}, m=599)")
    assert both("Count(Row(m=599))") == n
    assert both("Count(Union(Row(m=599), Row(m=3), Row(m=4000)))") > 0
    holder.close()


class FakeDevice:
    """A device whose memory is the block store's entries: `bytes_limit`
    small, `bytes_in_use` what the store holds."""

    def __init__(self, limit, blocks=None):
        self.limit, self.blocks = limit, blocks

    def memory_stats(self):
        held = self.blocks.resident_bytes() if self.blocks is not None else 0
        return {"bytes_limit": self.limit, "bytes_in_use": held}


def gauge(text: str, series: str) -> float:
    for line in text.splitlines():
        name, _, value = line.rpartition(" ")
        if not line.startswith("#") and name.endswith(series):
            return float(value)
    raise AssertionError(f"no {series} on the page")


def test_resident_bytes_never_exceed_the_budget_at_any_upload(ssb, monkeypatch):
    holder, _ = ssb
    be = TPUBackend(holder)
    dev = FakeDevice(BUDGET + be.blocks.RESERVE_FLOOR, be.blocks)
    monkeypatch.setattr(be.blocks, "_first_device", lambda: dev)
    assert be.blocks.budget() == BUDGET
    seen = []
    real = be.blocks._ledger_upload

    def upload(key, arr, tiers):
        real(key, arr, tiers)
        seen.append(be.blocks.resident_bytes())

    monkeypatch.setattr(be.blocks, "_ledger_upload", upload)
    ex = Executor(holder, backend=be)
    for call in CALLS:
        ex.execute("ssb", SHAPE.pql(call))
    assert seen and max(seen) <= BUDGET
    published = StatsClient()
    publish_hbm_gauges(be.blocks, published)
    text = published.prometheus_text()
    assert gauge(text, 'hbm_layout_bytes{layout="rowid"}') > 0
    assert gauge(text, "hbm_budget_bytes") == BUDGET
    assert sum(gauge(text, f'hbm_layout_bytes{{layout="{x}"}}')
               for x in ("dense", "packed", "rowid")) == be.blocks.resident_bytes()


def test_a_pinned_entry_is_never_counted_freed(ssb):
    """An upload that needs the room a pinned entry takes waits for the
    pin to clear, then evicts it; one that only its own pins block is
    refused at once; the pinned entry is counted resident throughout."""
    holder, _ = ssb
    idx = holder.index("ssb")
    be = TPUBackend(holder, max_bytes=BUDGET)
    blocks = be.blocks
    shards = tuple(range(SHARDS))
    city = idx.field("c_city")
    held = threading.Event()
    release = threading.Event()

    def query():
        with blocks.pinned():
            blocks.get("ssb", city, shards)
            held.set()
            release.wait(10)

    t = threading.Thread(target=query)
    t.start()
    held.wait(10)
    blocks.get("ssb", idx.field("d_year"), shards)
    size = blocks._entry_bytes(("ssb", "c_city", "standard"))
    assert size > 0
    answer = []
    waiter = threading.Thread(
        target=lambda: answer.append(blocks.make_room(BUDGET)))
    waiter.start()
    waiter.join(0.3)
    # d_year, unpinned, went; c_city waits for its query.
    assert waiter.is_alive() and blocks.resident_bytes() == size
    release.set()
    t.join(10)
    waiter.join(10)
    assert answer == [True] and blocks.resident_bytes() == 0
    assert ("ssb", "c_city", "standard") not in blocks._entries
    with blocks.pinned():
        blocks.get("ssb", city, shards)
        assert blocks.make_room(BUDGET) is False
        assert ("ssb", "c_city", "standard") in blocks._entries


def test_queries_waiting_on_each_others_pins_are_refused_at_once(ssb):
    """Two queries each hold a stack pinned and each need the room the
    other's stack takes: neither would ever unpin, so both uploads are
    refused at once, not after PIN_WAIT_SECONDS, and nothing is
    counted freed while pinned."""
    holder, _ = ssb
    idx = holder.index("ssb")
    blocks = TPUBackend(holder, max_bytes=BUDGET).blocks
    shards = tuple(range(SHARDS))
    both = threading.Barrier(2)
    answers = {}

    def query(name):
        with blocks.pinned():
            blocks.get("ssb", idx.field(name), shards)
            both.wait(10)
            t0 = time.perf_counter()
            answers[name] = (blocks.make_room(BUDGET), time.perf_counter() - t0,
                             ("ssb", name, "standard") in blocks._entries)

    threads = [threading.Thread(target=query, args=(n,)) for n in ("c_city", "s_city")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(2 * blocks.PIN_WAIT_SECONDS)
    assert sorted(answers) == ["c_city", "s_city"]
    for ok, waited, kept in answers.values():
        assert ok is False and kept
        assert waited < blocks.PIN_WAIT_SECONDS / 3
    assert blocks._holders == blocks._stalled == 0


def test_a_subset_query_over_resident_stacks_is_answered(ssb, executors):
    """`?shards=` a subset: the stack over every shard goes before the
    subset's is built, so the two are never held at once."""
    holder, draws = ssb
    rowid, _, cpu = executors
    pql = SHAPE.pql(CALLS[6])  # q3.1: three dense grouped fields
    rowid.execute("ssb", pql)
    got = result_to_json(rowid.execute("ssb", pql, shards=[0])[0])
    assert got == result_to_json(cpu.execute("ssb", pql, shards=[0])[0])
    fps = [e[0][0] for k, e in rowid.backend.blocks._entries.items() if k[1] == "c_nation"]
    assert fps == [(0,)]
    assert rowid.backend.blocks.resident_bytes() <= BUDGET


def test_count_stays_on_the_device_and_topn_leaves_for_the_host(ssb, executors):
    holder, draws = ssb
    rowid, _, cpu = executors
    before = counter("device_fallback_total", reason="unsupported_topn")
    launches = counter("device_launches_total")
    assert rowid.execute("ssb", "Count(Row(p_brand1=285))")[0] == int(
        (draws["p_brand1"] == 285).sum())
    assert counter("device_launches_total") > launches
    assert counter("device_fallback_total", reason="unsupported_topn") == before
    q = "TopN(p_brand1, n=5)"
    assert result_to_json(rowid.execute("ssb", q)[0]) == result_to_json(
        cpu.execute("ssb", q)[0])
    assert counter("device_fallback_total", reason="unsupported_topn") == before + 1
