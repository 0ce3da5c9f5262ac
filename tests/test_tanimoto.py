"""Tanimoto TopN on the device over a packed stack (ISSUE 36): the
kernel against numpy, the backend against `Fragment.top` with a rank
cache as large as the field, the packed layout against the dense one,
freshness under Set/Clear, the batcher's leg kind and its launches, and
the executor's gate. CPU, small sizes, seeded."""

import threading

import numpy as np
import pytest

import jax.numpy as jnp

from pilosa_tpu.core import Holder
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.exec.batcher import ShardLegBatcher
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.exec.tpu import TPUBackend
from pilosa_tpu.ops import kernels
from pilosa_tpu.ops.blocks import (
    PACKED_BITS,
    PACKED_ROW_PAD,
    PACKED_WORDS,
    WORDS_PER_SHARD,
    pack_fragment,
    pack_fragment_packed,
    pack_rows_packed,
    packed_rows,
)
from pilosa_tpu.roaring import Bitmap
from pilosa_tpu.roaring.codec import serialize
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.stats import global_stats

#: A budget under which a field of a few hundred rows is not admitted
#: dense (64 rows of 128 KiB) and is admitted packed.
BUDGET = 64 * WORDS_PER_SHARD * 4
THRESHOLDS = (50, 70, 90, 100)


def library(rng, rows=600, families=40, wide=False):
    """bool[rows, 4096]: families of near-copies, as a molecule library
    has them, so that thresholds from 50 to 100 cut at different places.
    Row 0 has no neighbour; rows 1 and 2 are identical (and so pass at
    100); the first family, rows 3 to 62, is larger than the rest."""
    bits = rng.random((rows, PACKED_BITS)) < 0.012
    at = 3
    for f in range(families):
        size = int(rng.integers(2, 12)) if f else 60
        core = rng.random(PACKED_BITS) < 0.014
        for r in range(at, min(at + size, rows)):
            bits[r] = (core & (rng.random(PACKED_BITS) < rng.uniform(0.8, 1.0))) | (
                rng.random(PACKED_BITS) < 0.0015
            )
        at += size
    bits[0] = False
    bits[0, rng.choice(PACKED_BITS, 40, replace=False)] = True
    bits[2] = bits[1]
    return bits


def reference(bits, m, t, n=0):
    """Fragment.top's arithmetic over every row, in numpy."""
    inter = (bits & bits[m]).sum(axis=1)
    size = bits.sum(axis=1)
    union = size + size[m] - inter
    ok = (inter > 0) & (inter * 100 // np.maximum(union, 1) >= t)
    rows = np.flatnonzero(ok)
    rows = rows[np.lexsort((rows, -inter[rows]))]
    if n:
        rows = rows[:n]
    return [(int(r), int(inter[r])) for r in rows]


def counter(name, **tags):
    want = [f'{k}="{v}"' for k, v in tags.items()]
    return sum(
        v for k, v in global_stats.counter_totals(name).items()
        if k.split("{")[0] == name and all(w in k for w in want)
    )


@pytest.fixture
def mole(tmp_path, rng):
    """(executor over a device backend with a batcher, host executor,
    the field's bits, the holder)."""
    bits = library(rng)
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("mole")
    fld = idx.create_field("fingerprint")
    rows, cols = np.nonzero(bits)
    fld.import_bits(rows.astype(np.uint64), cols.astype(np.uint64))
    fld.view("standard").fragment(0).cache.max_entries = 10**9
    be = TPUBackend(holder, max_bytes=BUDGET)
    ex = Executor(holder, backend=be)
    ex.batcher = ShardLegBatcher(be)
    yield ex, Executor(holder), bits, holder
    holder.close()


def pql(m, t=None, n=None, extra=""):
    args = "".join(
        f", {k}={v}" for k, v in (("tanimotoThreshold", t), ("n", n)) if v is not None
    )
    return f"TopN(fingerprint, Row(fingerprint={m}){args}{extra})"


def pairs(result):
    return [(p.id, p.count) for p in result.pairs]


# -- the kernel ------------------------------------------------------------


@pytest.mark.parametrize("k", [8, 64, kernels.TANIMOTO_LIST])
@pytest.mark.parametrize("shards", [1, 2])
def test_kernel_equals_numpy_on_every_leg(rng, k, shards):
    import jax

    halves = [library(rng, rows=300, families=20) for _ in range(shards)]
    rows_p = packed_rows(300)
    packed = np.zeros((shards, rows_p, PACKED_WORDS), np.uint32)
    for s, bits in enumerate(halves):
        packed[s, :300] = np.packbits(bits, axis=1, bitorder="little").view(np.uint32)
    counts = np.asarray(kernels.packed_row_counts(jnp.asarray(packed)))
    assert (counts[:, :300] == np.stack([b.sum(axis=1) for b in halves])).all()
    ids = np.array([0, 1, 5, 299, 7, 7], np.int32)
    thr = np.array([70, 100, 0, 50, 90, 90], np.int32)
    act = np.array([1, 1, 1, 1, 1, 0], np.int32)
    out = np.asarray(jax.jit(kernels.tanimoto_topn, static_argnames="k")(
        jnp.asarray(packed), jnp.asarray(counts), ids, thr, act, k=k
    ))
    whole = np.asarray(kernels.tanimoto_counts(
        jnp.asarray(packed), jnp.asarray(counts), ids, thr, act
    ))
    for leg in range(ids.size):
        want = np.zeros(rows_p, np.int64)
        if act[leg]:
            for bits in halves:
                for r, c in reference(bits, ids[leg], thr[leg]):
                    want[r] += c
        hits = np.flatnonzero(want)
        assert (whole[leg] == want).all()
        assert out[leg, 0] == hits.size
        kept = min(hits.size, k)
        assert (out[leg, 1:1 + kept] == hits[:kept]).all()
        assert (out[leg, 1 + k:1 + k + kept] == want[hits[:kept]]).all()
        assert not out[leg, 1 + kept:1 + k].any() and not out[leg, 1 + k + kept:].any()


@pytest.mark.parametrize("n_words, k", [(3, 5), (52, 64), (200, 32), (64, 4096)])
def test_first_set_bits_are_the_first_in_order(rng, n_words, k):
    words = rng.integers(0, 2**32, (4, n_words), dtype=np.uint32)
    words &= rng.integers(0, 2**32, (4, n_words), dtype=np.uint32)
    words[1] = 0
    words[2, 1:] = 0
    at, total = (np.asarray(a) for a in kernels._first_set_bits(jnp.asarray(words), k))
    for row in range(4):
        want = np.flatnonzero(np.unpackbits(words[row].view(np.uint8), bitorder="little"))
        assert total[row] == want.size
        kept = min(k, want.size)
        assert (at[row, :kept] == want[:kept]).all()


# -- the packed layout -------------------------------------------------------


def fragment_of(bits) -> Fragment:
    frag = Fragment(None, "i", "f", "standard", 0)
    frag.open()
    rows, cols = np.nonzero(bits)
    frag.bulk_import(rows.astype(np.uint64), cols.astype(np.uint64))
    return frag


def test_packed_equals_dense_bit_for_bit(rng):
    bits = library(rng, rows=70, families=6)
    frag = fragment_of(bits)
    rows_p = packed_rows(70)
    assert rows_p == PACKED_ROW_PAD
    packed = pack_fragment_packed(frag, rows_p)
    dense = pack_fragment(frag, n_rows=72)
    assert packed.shape == (rows_p, PACKED_WORDS) and packed.dtype == np.uint32
    assert (packed[:72] == dense[:, :PACKED_WORDS]).all()
    assert not dense[:, PACKED_WORDS:].any() and not packed[72:].any()
    assert (np.unpackbits(packed[:70].view(np.uint8), axis=1, bitorder="little")
            == bits).all()
    some = [0, 5, 69, 500]
    assert (pack_rows_packed(frag, some)[:3] == packed[[0, 5, 69]]).all()
    assert not pack_rows_packed(frag, some)[3].any()


@pytest.mark.parametrize("column", [PACKED_BITS, 65536, SHARD_WIDTH - 1])
def test_a_bit_beyond_the_packed_width_is_not_packed(rng, column):
    frag = fragment_of(library(rng, rows=20, families=2))
    assert pack_fragment_packed(frag, PACKED_ROW_PAD) is not None
    frag.set_bit(7, column)
    assert pack_fragment_packed(frag, PACKED_ROW_PAD) is None
    assert pack_rows_packed(frag, [7]) is None
    assert pack_rows_packed(frag, [6]) is not None


def test_a_run_container_inside_the_width_packs(rng):
    frag = fragment_of(np.zeros((4, PACKED_BITS), bool))
    frag.bulk_import(np.full(3000, 2, np.uint64), np.arange(3000, dtype=np.uint64))
    frag.storage.optimize()
    assert frag.storage.container(2 * (SHARD_WIDTH >> 16)).typ == "run"
    packed = pack_fragment_packed(frag, PACKED_ROW_PAD)
    assert np.bitwise_count(packed[2]).sum() == 3000 and not packed[:2].any()


def test_the_choice_is_made_from_what_the_fragment_shows(mole, tmp_path):
    ex, _, bits, holder = mole
    be = ex.backend
    fld = holder.index("mole").field("fingerprint")
    # Narrow: packed, counted, in the ledger, its row counts beside it.
    packed, rows_p, row_counts = be.blocks.get_packed("mole", fld, (0,))
    assert packed.shape == (1, packed_rows(bits.shape[0]), PACKED_WORDS) and rows_p == packed.shape[1]
    assert (np.asarray(row_counts)[0, :bits.shape[0]] == bits.sum(axis=1)).all()
    assert be.blocks.get("mole", fld, (0,))[0] is None          # dense: not admitted
    assert be.blocks.resident_bytes() == packed.size * 4
    entry = be.blocks.ledger()[0]
    assert entry["layout"] == "packed" and entry["bytes"] == packed.size * 4
    assert global_stats.with_tags("index:mole", "field:fingerprint").gauge_value(
        "stack_row_words") == PACKED_WORDS
    assert (np.asarray(packed)[0, :bits.shape[0]]
            == np.packbits(bits, axis=1, bitorder="little").view(np.uint32)).all()
    # A hit hands back the same stack and the same counts.
    again = be.blocks.get_packed("mole", fld, (0,))
    assert again[0] is packed and again[2] is row_counts
    # A short field is narrow too; one that does not exist is nothing.
    idx = holder.index("mole")
    idx.create_field("short").import_bits(
        np.array([1, 2], np.uint64), np.array([3, 4], np.uint64))
    assert be.packed_field("mole", "short", [0])
    assert not be.packed_field("mole", "nosuchfield", [0])
    # A packed stack the budget does not hold: not packed.
    tiny = TPUBackend(holder, max_bytes=PACKED_WORDS * 4 * PACKED_ROW_PAD - 1)
    assert tiny.blocks.get_packed("mole", fld, (0,)) == (None, 0, None)


@pytest.mark.parametrize("budget", [None, BUDGET, 1 << 30])
def test_the_same_search_is_exact_under_any_budget(mole, budget):
    """Whether the dense stack is admitted, or any limit is known at all
    (a CPU device reports none), does not enter the choice: the search is
    answered on the device, over every row, and no fallback is counted."""
    _, _, bits, holder = mole
    be = TPUBackend(holder, max_bytes=budget)
    assert be.blocks.admission_bytes() == budget
    assert be.packed_field("mole", "fingerprint", [0])
    ex = Executor(holder, backend=be)
    ex.batcher = ShardLegBatcher(be)
    before = counter("device_fallback_total")
    launches = counter("device_launches_total", kind="topn_tanimoto")
    for m, t in ((3, 50), (30, 70), (1, 100)):
        assert pairs(ex.execute("mole", pql(m, t))[0]) == reference(bits, m, t)
    assert counter("device_launches_total", kind="topn_tanimoto") == launches + 3
    assert counter("device_fallback_total") == before


def test_a_narrow_field_held_dense_too_is_searched_packed(mole):
    """A budget that admits the dense stack: `Count` and plain `TopN`
    read it, the search reads the packed one beside it, and the ledger
    holds both."""
    _, _, bits, holder = mole
    be = TPUBackend(holder, max_bytes=1 << 30)
    ex = Executor(holder, backend=be)
    before = counter("device_fallback_total")
    assert ex.execute("mole", "Count(Row(fingerprint=3))")[0] == int(bits[3].sum())
    assert pairs(ex.execute("mole", pql(30, 70))[0]) == reference(bits, 30, 70)
    layouts = sorted(e.get("layout", "dense") for e in be.blocks.ledger())
    assert layouts == ["dense", "packed"]
    assert be.blocks.resident_bytes() == sum(e["bytes"] for e in be.blocks.ledger())
    # A plain TopN under a Row is exact from either stack: it keeps the
    # dense one's sweep where there is a dense one.
    launches = counter("device_launches_total", kind="topn_tanimoto")
    assert be.packed_field("mole", "fingerprint", [0])
    assert not be.packed_field("mole", "fingerprint", [0], alone=True)
    assert pairs(ex.execute("mole", pql(9))[0]) == reference(bits, 9, 0)
    assert counter("device_launches_total", kind="topn_tanimoto") == launches
    assert counter("device_fallback_total") == before


def test_over_no_shard_nothing_is_packed_and_the_answer_is_empty(mole):
    """`?shards=`: what the benchmark's control asks of a one-shard index."""
    ex, _, _, holder = mole
    fld = holder.index("mole").field("fingerprint")
    assert ex.backend.blocks.get_packed("mole", fld, ()) == (None, 0, None)
    assert pairs(ex.execute("mole", pql(30, 70), shards=[])[0]) == []


def test_not_packed_is_an_entry_of_the_block_store_and_goes_with_it(mole):
    """The verdict costs a walk of the containers: it is kept under the
    key and the generation, holds no bytes and no ledger line, and a
    write that moves the generation asks again."""
    ex, _, bits, holder = mole
    be = ex.backend
    fld = holder.index("mole").field("fingerprint")
    packed = be.blocks.get_packed("mole", fld, (0,))[0]
    ex.execute("mole", f"Set({PACKED_BITS}, fingerprint=3)")
    walks = []
    real = pack_fragment_packed
    import pilosa_tpu.exec.tpu as tpu_mod
    try:
        tpu_mod.pack_fragment_packed = lambda fr, rp: walks.append(1) or real(fr, rp)
        for _ in range(3):
            assert be.blocks.get_packed("mole", fld, (0,)) == (None, 0, None)
        assert len(walks) == 1
        key = ("mole", "fingerprint", "standard", "packed")
        assert be.blocks._entries[key][1] is None and key not in be.blocks._ledger
        assert be.blocks.resident_bytes() == 0 and be.blocks.ledger() == []
        evictions = be.blocks.evictions
        be.blocks.make_room(be.blocks.max_bytes)
        assert be.blocks.evictions == evictions       # freed nothing: not counted
        ex.execute("mole", f"Clear({PACKED_BITS}, fingerprint=3)")
        again = be.blocks.get_packed("mole", fld, (0,))[0]
        assert again is not None and again is not packed and len(walks) == 2
    finally:
        tpu_mod.pack_fragment_packed = real
    be.blocks.clear()
    assert be.blocks._entries == {}


def test_the_devices_own_limit_is_the_budget_where_none_is_set(mole, monkeypatch):
    """On a chip `memory_stats()` gives `bytes_limit`: a dense stack past
    it is refused (ISSUE 36: 207 GB were asked of 16) and the field is
    held packed, with the server's options at their defaults."""
    ex, _, bits, holder = mole
    be = TPUBackend(holder)
    monkeypatch.setattr(be.blocks, "_device_bytes", BUDGET)
    assert be.blocks.admission_bytes() == BUDGET
    fld = holder.index("mole").field("fingerprint")
    assert be.blocks.get("mole", fld, (0,))[0] is None
    assert be.packed_field("mole", "fingerprint", [0])
    got = Executor(holder, backend=be).execute("mole", pql(5, 70))[0]
    assert pairs(got) == reference(bits, 5, 70)


def test_a_mesh_holds_no_field_packed(mole):
    from pilosa_tpu.parallel import ShardMesh

    _, _, _, holder = mole
    be = TPUBackend(holder, mesh=ShardMesh(), max_bytes=BUDGET)
    fld = holder.index("mole").field("fingerprint")
    assert be.blocks.get_packed("mole", fld, (0,)) == (None, 0, None)


# -- answers ----------------------------------------------------------------


@pytest.mark.parametrize("t", THRESHOLDS)
@pytest.mark.parametrize("m", [0, 1, 3, 7, 150, 590, 599])
def test_device_equals_numpy_and_the_host_with_a_whole_rank_cache(mole, m, t):
    ex, host, bits, _ = mole
    before = counter("device_fallback_total")
    got = pairs(ex.execute("mole", pql(m, t))[0])
    assert got == reference(bits, m, t)
    assert got == pairs(host.execute("mole", pql(m, t))[0])
    assert counter("device_fallback_total") == before


def test_a_source_with_no_neighbour_and_twins(mole):
    ex, _, bits, _ = mole
    assert pairs(ex.execute("mole", pql(0, 50))[0]) == [(0, 40)]
    twins = pairs(ex.execute("mole", pql(1, 100))[0])
    assert twins == [(1, int(bits[1].sum())), (2, int(bits[1].sum()))]
    # A row the field does not hold, and one past the stack: nothing.
    assert pairs(ex.execute("mole", pql(650, 50))[0]) == []
    assert pairs(ex.execute("mole", pql(10**7, 50))[0]) == []


@pytest.mark.parametrize("n", [1, 2, 5, 1000])
@pytest.mark.parametrize("t", [0, 50, 70])
def test_n_is_applied_after_the_threshold(mole, n, t):
    ex, _, bits, _ = mole
    m = 30  # of the large family
    whole = reference(bits, m, t)
    assert len(whole) > 5
    assert pairs(ex.execute("mole", pql(m, t or None, n))[0]) == whole[:n]


def test_plain_topn_under_a_row_is_the_threshold_zero(mole):
    ex, host, bits, _ = mole
    got = pairs(ex.execute("mole", pql(9))[0])
    assert got == reference(bits, 9, 0) == pairs(host.execute("mole", pql(9))[0])


def test_other_calls_on_a_packed_field_keep_answering(mole):
    ex, host, bits, _ = mole
    before = counter("device_fallback_total")
    assert ex.execute("mole", "Count(Row(fingerprint=3))")[0] == int(bits[3].sum())
    assert ex.execute("mole", "Count(Intersect(Row(fingerprint=1), Row(fingerprint=2)))")[0] \
        == int((bits[1] & bits[2]).sum())
    row = ex.execute("mole", "Row(fingerprint=4)")[0]
    assert list(row.columns()) == np.flatnonzero(bits[4]).tolist()
    top = pairs(ex.execute("mole", "TopN(fingerprint, n=4)")[0])
    size = bits.sum(axis=1)
    order = np.lexsort((np.arange(size.size), -size))[:4]
    assert top == [(int(r), int(size[r])) for r in order]
    assert counter("device_fallback_total") == before


@pytest.mark.parametrize("extra", [
    ", ids=[3, 4, 5, 30]", ", threshold=30", ", threshold=30, tanimotoThreshold=60",
    ', attrName="x", attrValues=[1]',
])
def test_the_gate_leaves_the_other_options_to_the_host(mole, extra):
    ex, host, _, _ = mole
    launches = counter("device_launches_total", kind="topn_tanimoto")
    q = pql(30, None, None, extra)
    assert pairs(ex.execute("mole", q)[0]) == pairs(host.execute("mole", q)[0])
    assert counter("device_launches_total", kind="topn_tanimoto") == launches


def test_a_source_of_another_shape_is_not_a_tanimoto_leg(mole):
    ex, host, _, holder = mole
    launches = counter("device_launches_total", kind="topn_tanimoto")
    for q in (
        "TopN(fingerprint, Union(Row(fingerprint=3), Row(fingerprint=4)), tanimotoThreshold=50)",
        "TopN(fingerprint, Row(fingerprint=3), tanimotoThreshold=150)",
    ):
        assert pairs(ex.execute("mole", q)[0]) == pairs(host.execute("mole", q)[0])
    assert counter("device_launches_total", kind="topn_tanimoto") == launches


# -- more hits than the list holds ------------------------------------------


def test_a_leg_with_more_hits_than_the_list_is_finished_exactly(tmp_path, rng):
    """5,000 near-copies of one row: its search passes them all, past the
    program's list of 4,096, and the answer is whole: the count vector is
    read back and the hits are taken on the host."""
    rows = 5200
    bits = rng.random((rows, PACKED_BITS)) < 0.01
    core = rng.random(PACKED_BITS) < 0.015
    bits[:5000] = core & (rng.random((5000, PACKED_BITS)) < 0.97)
    holder = Holder(str(tmp_path)).open()
    fld = holder.create_index("mole").create_field("fingerprint")
    r, c = np.nonzero(bits)
    fld.import_bits(r.astype(np.uint64), c.astype(np.uint64))
    be = TPUBackend(holder, max_bytes=BUDGET)
    ex = Executor(holder, backend=be)
    ex.batcher = ShardLegBatcher(be)
    before = counter("topn_tanimoto_overflow_total")
    hits = counter("topn_tanimoto_hits_total")
    res = ex.execute("mole", pql(17, 70) + pql(5100, 70) + pql(17, 70, 3))
    want = reference(bits, 17, 70)
    assert len(want) > kernels.TANIMOTO_LIST
    assert pairs(res[0]) == want and pairs(res[2]) == want[:3]
    assert pairs(res[1]) == reference(bits, 5100, 70)
    # The two legs of row 17 shared a slot: one overflow, not two.
    assert counter("topn_tanimoto_overflow_total") == before + 1
    assert counter("topn_tanimoto_hits_total") == hits + len(want) + len(pairs(res[1]))
    holder.close()


# -- freshness ---------------------------------------------------------------


def test_a_set_and_a_clear_are_in_the_next_topn(mole):
    ex, _, bits, _ = mole
    m = 35
    ex.execute("mole", pql(m, 70))
    rebuilds = counter("stack_full_rebuilds_total")
    splices = counter("stack_incremental_updates_total")
    free = int(np.flatnonzero(~bits[m])[0])
    held = int(np.flatnonzero(bits[m])[0])
    ex.execute("mole", f"Set({free}, fingerprint={m})")
    bits[m, free] = True
    assert pairs(ex.execute("mole", pql(m, 70))[0]) == reference(bits, m, 70)
    ex.execute("mole", f"Clear({held}, fingerprint={m})Clear({held}, fingerprint={m - 1})")
    bits[m, held] = bits[m - 1, held] = False
    assert pairs(ex.execute("mole", pql(m, 70))[0]) == reference(bits, m, 70)
    assert pairs(ex.execute("mole", pql(m - 1, 50))[0]) == reference(bits, m - 1, 50)
    # Point writes splice rows; the stack is not packed again.
    assert counter("stack_incremental_updates_total") == splices + 2
    assert counter("stack_full_rebuilds_total") == rebuilds
    # A new row past the field's height, inside the padded stack.
    ex.execute("mole", "Set(9, fingerprint=777)Set(11, fingerprint=777)")
    assert pairs(ex.execute("mole", pql(777, 100))[0]) == [(777, 2)]


def test_a_bulk_write_packs_again_and_a_wide_bit_leaves_for_the_host(mole):
    ex, host, bits, holder = mole
    ex.execute("mole", pql(3, 70))
    rebuilds = counter("stack_full_rebuilds_total")
    fld = holder.index("mole").field("fingerprint")
    fld.import_bits(np.full(30, 3, np.uint64), np.arange(100, 130, dtype=np.uint64))
    bits[3, 100:130] = True
    assert pairs(ex.execute("mole", pql(3, 70))[0]) == reference(bits, 3, 70)
    assert counter("stack_full_rebuilds_total") == rebuilds + 1
    # A bit at column 4,096: the field is not narrow any more. The
    # answer is the host's (the source's whole row counts there).
    ex.execute("mole", f"Set({PACKED_BITS}, fingerprint=3)")
    assert not ex.backend.packed_field("mole", "fingerprint", [0])
    assert pairs(ex.execute("mole", pql(3, 70))[0]) == pairs(host.execute("mole", pql(3, 70))[0])
    ex.execute("mole", f"Clear({PACKED_BITS}, fingerprint=3)")
    assert ex.backend.packed_field("mole", "fingerprint", [0])
    assert pairs(ex.execute("mole", pql(3, 70))[0]) == reference(bits, 3, 70)


def test_more_rows_written_than_a_splice_ships_packs_again(mole):
    ex, _, bits, _ = mole
    ex.execute("mole", pql(3, 70))
    rebuilds = counter("stack_full_rebuilds_total")
    n = ex.backend.blocks.PACKED_UPDATE_ROWS + 1
    ex.execute("mole", "".join(f"Set(4000, fingerprint={r})" for r in range(n)))
    bits[:n, 4000] = True
    assert pairs(ex.execute("mole", pql(3, 70))[0]) == reference(bits, 3, 70)
    assert counter("stack_full_rebuilds_total") == rebuilds + 1


# -- the batcher's leg kind -----------------------------------------------------


@pytest.mark.parametrize("legs, bucket", [(1, 1), (2, 2), (3, 4), (5, 8), (8, 8), (9, 16), (16, 16)])
def test_a_drain_of_up_to_sixteen_legs_is_one_launch(mole, legs, bucket):
    ex, _, bits, _ = mole
    be = ex.backend
    be.topn_tanimoto("mole", "fingerprint", [0], 0, 50)    # the stack is built
    launches = counter("device_launches_total", kind="topn_tanimoto")
    asked = [(10 + i, THRESHOLDS[i % 4]) for i in range(legs)]
    resolver = be.topn_tanimoto_async("mole", "fingerprint", [0], asked)
    assert counter("device_launches_total", kind="topn_tanimoto") == launches + 1
    entry = [e for e in be.programs.ledger() if e["kind"] == "topn_tanimoto"]
    assert any(f"({bucket},)" in e["shapes"].replace("[", "(").replace("]", ",)") or str(bucket) in e["shapes"] for e in entry)
    for (m, t), (rows, counts) in zip(asked, resolver()):
        order = np.lexsort((rows, -counts))
        assert list(zip(rows[order].tolist(), counts[order].tolist())) == reference(bits, m, t)


def test_a_drain_of_forty_legs_is_three_launches_and_duplicates_share_a_slot(mole):
    ex, _, bits, _ = mole
    batcher = ex.batcher
    batcher.topn_tanimoto("mole", "fingerprint", [0], 0, 50)
    asked = [(20 + i, THRESHOLDS[i % 4]) for i in range(40)]
    asked += asked[:9] + [asked[0]] * 3           # 52 legs, 40 of them different
    launches = counter("device_launches_total", kind="topn_tanimoto")
    legs_before = counter("batch_legs_total", kind="topn_tanimoto")
    drains = counter("batch_drains_total")
    made = [batcher.topn_tanimoto_leg("mole", "fingerprint", [0], m, t) for m, t in asked]
    batcher.submit(made)
    assert counter("batch_drains_total") == drains + 1
    assert counter("batch_legs_total", kind="topn_tanimoto") == legs_before + 52
    assert counter("device_launches_total", kind="topn_tanimoto") == launches + 3
    for (m, t), leg in zip(asked, made):
        rows, counts = leg.value()
        order = np.lexsort((rows, -counts))
        assert list(zip(rows[order].tolist(), counts[order].tolist())) == reference(bits, m, t)


def test_a_launch_that_fails_retries_only_the_legs_not_yet_answered(mole, monkeypatch):
    """A drain of 40 legs is three launches, delivered launch by launch:
    where the second's read-back raises, the first sixteen keep their
    answers and the other 24 are asked again one by one."""
    ex, _, bits, _ = mole
    batcher, be = ex.batcher, ex.backend
    batcher.topn_tanimoto("mole", "fingerprint", [0], 0, 50)
    asked = [(20 + i, THRESHOLDS[i % 4]) for i in range(40)]
    real_async, real_one = be.topn_tanimoto_async, be.topn_tanimoto
    alone = []

    def failing_async(index, field_name, shards, legs):
        resolver = real_async(index, field_name, shards, legs)
        if len(legs) == 1:
            return resolver

        def resolve(deliver=None):
            seen = []

            def once(which, answers):
                if seen:
                    raise RuntimeError("read-back failed")
                seen.append(which)
                deliver(which, answers)

            return resolver(once)

        return resolve

    monkeypatch.setattr(be, "topn_tanimoto_async", failing_async)
    monkeypatch.setattr(
        be, "topn_tanimoto",
        lambda i, f, s, m, t: alone.append((m, t)) or real_one(i, f, s, m, t),
    )
    errors = counter("batch_dispatch_errors_total", kind="topn_tanimoto")
    made = [batcher.topn_tanimoto_leg("mole", "fingerprint", [0], m, t) for m, t in asked]
    batcher.submit(made)
    assert counter("batch_dispatch_errors_total", kind="topn_tanimoto") == errors + 1
    assert alone == asked[16:]
    for (m, t), leg in zip(asked, made):
        rows, counts = leg.value()
        order = np.lexsort((rows, -counts))
        assert list(zip(rows[order].tolist(), counts[order].tolist())) == reference(bits, m, t)


def test_concurrent_requests_coalesce_and_every_one_is_right(mole):
    ex, _, bits, _ = mole
    ex.execute("mole", pql(0, 50))
    ex.batcher.window = 0.05
    asked = [(30 + i, THRESHOLDS[i % 4]) for i in range(24)]
    got: dict = {}

    def one(m, t):
        got[(m, t)] = pairs(ex.execute("mole", pql(m, t))[0])

    launches = counter("device_launches_total", kind="topn_tanimoto")
    threads = [threading.Thread(target=one, args=a) for a in asked]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    for m, t in asked:
        assert got[(m, t)] == reference(bits, m, t)
    assert counter("device_launches_total", kind="topn_tanimoto") - launches < 24


def test_a_request_body_of_searches_is_one_trip(mole):
    ex, _, bits, _ = mole
    ex.execute("mole", pql(0, 50))
    trips = counter("batch_trips_total")
    launches = counter("device_launches_total", kind="topn_tanimoto")
    res = ex.execute("mole", "".join(pql(40 + i, 70) for i in range(6)))
    assert [pairs(r) for r in res] == [reference(bits, 40 + i, 70) for i in range(6)]
    assert counter("batch_trips_total") == trips + 1
    assert counter("device_launches_total", kind="topn_tanimoto") == launches + 1


def test_a_field_not_held_packed_resolves_its_legs_for_the_host(mole):
    ex, host, _, holder = mole
    ex.execute("mole", f"Set({PACKED_BITS}, fingerprint=3)")   # not narrow any more
    assert ex.batcher.topn_tanimoto("mole", "fingerprint", [0], 5, 70) is None
    assert ex.backend.topn_tanimoto("mole", "fingerprint", [0], 5, 70) is None
    assert pairs(ex.execute("mole", pql(5, 70))[0]) == pairs(host.execute("mole", pql(5, 70))[0])


# -- a tall fragment loaded in slices --------------------------------------------


@pytest.mark.parametrize("cache_size", [50, 10**6])
def test_a_sliced_import_leaves_the_rank_cache_of_one_import(rng, cache_size):
    """import_roaring rebuilds the rank cache of the rows the bitmap
    touches: after three slices of whole rows, in any order, the cache
    holds what one import of the union leaves there."""
    bits = library(rng, rows=400, families=30)
    rows, cols = np.nonzero(bits)
    flat = rows.astype(np.uint64) * np.uint64(SHARD_WIDTH) + cols.astype(np.uint64)

    def loaded(parts):
        frag = Fragment(None, "i", "f", "standard", 0)
        frag.open()
        frag.cache.max_entries = cache_size
        for part in parts:
            frag.import_roaring(serialize(Bitmap.from_sorted_array(part)))
        return frag

    cut = [np.searchsorted(flat, r * SHARD_WIDTH) for r in (0, 130, 270, 400)]
    slices = [flat[a:b] for a, b in zip(cut, cut[1:])]
    whole = loaded([flat])
    size = bits.sum(axis=1)
    for order in ([0, 1, 2], [2, 0, 1]):
        sliced = loaded([slices[i] for i in order])
        assert sliced.cache.entries == whole.cache.entries
        assert sliced.cache.top() == whole.cache.top()
        assert len(sliced.cache.entries) == min(cache_size, int((size > 0).sum()))
    assert all(whole.cache.entries[r] == size[r] for r in whole.cache.entries)


def test_an_import_touches_only_its_rows_counts(rng, monkeypatch):
    frag = fragment_of(library(rng, rows=50, families=4))
    counted = []
    real = Fragment.row_count
    monkeypatch.setattr(Fragment, "row_count",
                        lambda self, r: counted.append(r) or real(self, r))
    part = np.array([7 * SHARD_WIDTH + 1, 7 * SHARD_WIDTH + 9, 9 * SHARD_WIDTH + 2], np.uint64)
    frag.import_roaring(serialize(Bitmap.from_sorted_array(part)))
    assert sorted(set(counted)) == [7, 9]
