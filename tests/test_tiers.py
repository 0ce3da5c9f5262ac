"""exec/tiers.py: the serving-tier table and the one protocol that keeps
every host table exact under writes. Host-only — none of this needs
jax (the slab-tier cases pack fragments through ops.blocks, which is
numpy, though importing it brings jax in)."""

import itertools
import sys
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.view import VIEW_STANDARD
from pilosa_tpu.exec.tiers import (
    GroupNRows,
    PairRows,
    RowCountRows,
    TierEntry,
    TierTable,
    VersionWalks,
    fingerprint,
    refresh_entry,
    shard_delta,
)
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.stats import StatsClient


def test_module_is_host_only():
    import subprocess

    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, pilosa_tpu.exec.tiers; print('jax' in sys.modules)"],
        capture_output=True, text=True, timeout=60,
    )
    assert out.stdout.strip() == "False", out.stderr


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------


class _View:
    """What the gate reads of a view: its generation."""

    def __init__(self, generation=0):
        self.generation = generation


class TestTierTable:
    def test_cap_evicts_oldest(self):
        t = TierTable(3)
        for k in range(5):
            t.store(k, TierEntry(None, k))
        assert len(t) == 3
        assert t.get(0) is None and t.get(1) is None
        assert [t[k].value for k in (2, 3, 4)] == [2, 3, 4]

    def test_hit_touches(self):
        t = TierTable(2)
        t.store("a", TierEntry("fp", 1))
        t.store("b", TierEntry("fp", 2))
        assert t.hit("a", "fp").value == 1  # a is now most recent
        t.store("c", TierEntry("fp", 3))
        assert t.get("b") is None
        assert t.get("a") is not None and t.get("c") is not None

    def test_restore_moves_to_newest(self):
        t = TierTable(2)
        t.store("a", TierEntry("fp", 1))
        t.store("b", TierEntry("fp", 2))
        t.store("a", TierEntry("fp2", 10))
        t.store("c", TierEntry("fp", 3))
        assert t.get("b") is None and t["a"].value == 10

    def test_moved_fingerprint_misses(self):
        hits = []
        t = TierTable(4, on_hit=lambda: hits.append(1))
        t.store("k", TierEntry(("s", (1,)), "v"))
        assert t.hit("k", ("s", (2,))) is None
        assert t.hit("missing", ("s", (1,))) is None
        assert hits == []
        assert t.hit("k", ("s", (1,))).value == "v"
        assert hits == [1]

    def test_clear(self):
        t = TierTable(4)
        t.store("k", TierEntry(None, 1))
        assert t and len(t) == 1
        t.clear()
        assert not t and t.get("k") is None
        with pytest.raises(KeyError):
            t["k"]

    def test_change_hook_fires_on_store_evict_and_clear(self):
        seen = []
        t = TierTable(
            2, on_change=lambda ents: seen.append(sorted(e.value for e in ents))
        )
        t.store("a", TierEntry(None, 1))
        t.store("b", TierEntry(None, 2))
        t.store("c", TierEntry(None, 3))  # evicts a before the hook reads
        t.clear()
        assert seen == [[1], [1, 2], [2, 3], []]

    def test_settle_lands_once(self):
        t = TierTable(2)
        pending = object()
        ent = TierEntry("fp", pending)
        t.store("k", ent)
        t.settle(ent, pending, "host", "table")
        t.settle(ent, pending, "late", "late")
        assert (ent.value, ent.pershard) == ("host", "table")

    def test_serve_hit_and_refresh(self):
        hits = []
        t = TierTable(4, on_hit=lambda: hits.append(1))
        v = _View(7)
        calls = []

        def refresh(stale, fp):
            calls.append((stale, fp))
            ent = TierEntry(fp, len(calls))
            t.store("k", ent)
            return ent

        assert t.serve("k", (0, 1), (v,), refresh).value == 1
        assert calls == [(None, ((0, 1), (7,)))] and hits == []
        assert t.serve("k", (0, 1), (v,), refresh).value == 1
        assert len(calls) == 1 and hits == [1]
        v.generation = 8  # a write: the refresher gets the old entry
        assert t.serve("k", (0, 1), (v,), refresh).value == 2
        assert calls[1][0].value == 1 and calls[1][1] == ((0, 1), (8,))
        # another shard set is another fingerprint
        assert t.serve("k", (0,), (v,), refresh).value == 3
        # a view that does not exist yet reads as generation -1
        assert t.serve("n", (0,), (None,), refresh).fp == ((0,), (-1,))

    def test_sixteen_threads_one_refresher(self):
        t = TierTable(4)
        v = _View(1)
        ran = []
        gate = threading.Event()

        def refresh(stale, fp):
            ran.append(threading.get_ident())
            gate.wait(10)
            time.sleep(0.05)  # the others are parked on the latch by now
            ent = TierEntry(fp, "fresh")
            t.store("k", ent)
            return ent

        out = []
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            ths = [
                threading.Thread(
                    target=lambda: out.append(t.serve("k", (0,), (v,), refresh))
                )
                for _ in range(16)
            ]
            for th in ths:
                th.start()
            gate.set()
            for th in ths:
                th.join(20)
            assert not any(th.is_alive() for th in ths)
        finally:
            sys.setswitchinterval(old)
        assert len(ran) == 1
        assert len(out) == 16 and len({id(e) for e in out}) == 1
        assert out[0].value == "fresh"

    def test_latch_released_when_refresher_raises(self):
        t = TierTable(4)
        v = _View(1)

        def boom(stale, fp):
            raise RuntimeError("sweep failed")

        with pytest.raises(RuntimeError):
            t.serve("k", (0,), (v,), boom)
        # Not parked for 60 s behind a dead latch: the next caller is
        # admitted as the refresher at once.
        done = []

        def ok(stale, fp):
            ent = TierEntry(fp, "ok")
            t.store("k", ent)
            return ent

        th = threading.Thread(
            target=lambda: done.append(t.serve("k", (0,), (v,), ok))
        )
        th.start()
        th.join(5)
        assert not th.is_alive() and done[0].value == "ok"

    def test_waiter_becomes_refresher_after_a_failure(self):
        """A waiter re-checks the gate; if the refresher it waited for
        stored nothing, it refreshes itself."""
        t = TierTable(4)
        v = _View(1)
        entered = threading.Event()
        release = threading.Event()

        def failing(stale, fp):
            entered.set()
            release.wait(10)
            raise RuntimeError("no")

        def ok(stale, fp):
            ent = TierEntry(fp, "second")
            t.store("k", ent)
            return ent

        first = threading.Thread(
            target=lambda: pytest.raises(
                RuntimeError, t.serve, "k", (0,), (v,), failing
            )
        )
        first.start()
        assert entered.wait(5)
        got = []
        second = threading.Thread(
            target=lambda: got.append(t.serve("k", (0,), (v,), ok))
        )
        second.start()
        time.sleep(0.05)
        assert not got  # parked on the first one's latch
        release.set()
        first.join(5)
        second.join(5)
        assert not second.is_alive() and got[0].value == "second"

    def test_serve_returns_what_refresh_returns(self):
        t = TierTable(4)
        assert t.serve("k", (0,), (_View(),), lambda s, f: None) is None
        assert len(t) == 0


# ---------------------------------------------------------------------------
# capture / confirm / revert, over two layouts
# ---------------------------------------------------------------------------

RF = RG = 4
RH = 3
SHARD = 0


class _Model:
    """Per field, row -> set of columns: the plain reference the table
    rows are compared with."""

    def __init__(self, names):
        self.bits = {n: {} for n in names}

    def flip(self, name, row, col, on=True):
        s = self.bits[name].setdefault(row, set())
        (s.add if on else s.discard)(col)

    def cols(self, name, row):
        return self.bits[name].get(row, set())


def _pair_row(m, rs):
    rf, rg = rs
    pair = [len(m.cols("f", a) & m.cols("g", b))
            for a in range(rf) for b in range(rg)]
    cf = [len(m.cols("f", a)) for a in range(rf)]
    cg = [len(m.cols("g", b)) for b in range(rg)]
    return np.array(pair + cf + cg, dtype=np.int32)


def _group3_row(m, rs):
    rf, rg, rh = rs
    out = [len(m.cols("f", a) & m.cols("g", b) & m.cols("h", k))
           for k in range(rh) for a in range(rf) for b in range(rg)]
    return np.array(out, dtype=np.int32)


LAYOUTS = {
    "pair": (("f", "g"), (RF, RG), PairRows, _pair_row),
    "group3": (("f", "g", "h"), (RF, RG, RH), GroupNRows, _group3_row),
}


class _Rig:
    """An index of 2 or 3 set fields with some bits in one shard, its
    model, and the table row of the state at `mark()`."""

    def __init__(self, kind):
        self.names, self.rs, make, self.row_of = LAYOUTS[kind]
        self.layout = make(self.rs)
        self.holder = Holder().open()
        idx = self.holder.create_index("i")
        self.fields = [idx.create_field(n) for n in self.names]
        self.model = _Model(self.names)
        rng = np.random.default_rng(7)
        for name, height in zip(self.names, self.rs):
            for _ in range(60):
                self.write(name, int(rng.integers(0, height)),
                           int(rng.integers(0, 40)))
        self.mark()

    @property
    def views(self):
        return [f.view(VIEW_STANDARD) for f in self.fields]

    def frag(self, name):
        return self.fields[self.names.index(name)].view(
            VIEW_STANDARD).fragment(SHARD)

    def write(self, name, row, col, on=True):
        f = self.fields[self.names.index(name)]
        (f.set_bit if on else f.clear_bit)(row, col)
        if row < self.rs[self.names.index(name)]:
            self.model.flip(name, row, col, on)

    def versions(self):
        return [
            (fr.uid, fr.version) if fr is not None else None
            for fr in map(self.frag, self.names)
        ]

    def mark(self):
        self.old = self.versions()
        self.old_row = self.row_of(self.model, self.rs)

    def delta(self, new=None, layout=None):
        row = self.old_row.copy()
        n = shard_delta(
            row, self.old_row, SHARD, self.views, self.old,
            new or self.versions(), layout or self.layout,
        )
        return n, row


@pytest.fixture(params=sorted(LAYOUTS))
def rig(request):
    return _Rig(request.param)


class TestShardDelta:
    def test_point_writes_applied_exactly(self, rig):
        name = rig.names[-1]
        for peer in rig.names[:-1]:
            rig.write(peer, 0, 105)
        rig.mark()
        rig.write(name, 1, 105)       # a set next to the peers' bits
        rig.write(name, 2, 105)
        rig.write(name, 1, 105, on=False)  # a clear in the stream
        rig.write(name, 0, 1_000)     # a column no peer holds
        n, row = rig.delta()
        assert n == 4
        np.testing.assert_array_equal(row, rig.row_of(rig.model, rig.rs))
        assert not np.array_equal(row, rig.old_row)

    def test_every_field_can_be_the_one_that_changed(self, rig):
        for name in rig.names:
            rig.mark()
            rig.write(name, 2, 107)
            rig.write(name, 0, 108)
            n, row = rig.delta()
            assert n == 2, name
            np.testing.assert_array_equal(row, rig.row_of(rig.model, rig.rs))

    def test_nothing_changed_is_not_a_delta(self, rig):
        n, row = rig.delta()
        assert n is None
        np.testing.assert_array_equal(row, rig.old_row)

    def test_two_fields_changed(self, rig):
        rig.write("f", 1, 109)
        rig.write("g", 1, 109)
        n, row = rig.delta()
        assert n is None
        np.testing.assert_array_equal(row, rig.old_row)

    def test_ring_does_not_cover_the_window(self, rig):
        rig.write("f", 1, 109)
        cols = np.array([111, 112], dtype=np.uint64)
        rig.fields[0].import_bits(np.array([2, 2], dtype=np.uint64), cols)
        n, row = rig.delta()
        assert n is None
        np.testing.assert_array_equal(row, rig.old_row)

    def test_recreated_fragment(self, rig):
        rig.write("f", 1, 109)
        uid, ver = rig.old[0]
        rig.old[0] = (uid + 1_000_000, ver)  # recorded under another uid
        n, _ = rig.delta()
        assert n is None
        rig.old[0] = None  # recorded before the fragment existed
        assert rig.delta()[0] is None

    def test_op_past_the_height_restores(self, rig):
        rig.write("f", 1, 109)          # in range: applied first
        rig.write("f", RF + 2, 109)     # past the table
        n, row = rig.delta()
        assert n is None
        np.testing.assert_array_equal(row, rig.old_row)

    def test_peer_moved_before_the_probes(self, rig):
        rig.write("f", 1, 109)
        new = rig.versions()          # the walk
        rig.write("g", 2, 109)          # lands after the walk
        n, row = rig.delta(new=new)
        assert n is None
        np.testing.assert_array_equal(row, rig.old_row)

    def test_peer_vanished_since_the_walk(self, rig):
        rig.write("f", 1, 109)
        new = rig.versions()
        rig.views[1].delete_fragment(SHARD)
        assert rig.delta(new=new)[0] is None

    def test_peer_written_between_the_confirms_restores(self, rig):
        """The write lands after the pre-confirm and is seen by a probe:
        only the post-confirm can catch it."""
        rig.write("f", 1, 109)
        rig.write("f", 2, 109)
        new = rig.versions()
        inner = rig.layout
        applied = []

        class Racing:
            rs = inner.rs

            def apply(self, row, t, r, members, sign):
                if not applied:
                    rig.write("g", 2, 109)
                applied.append(r)
                inner.apply(row, t, r, members, sign)

        n, row = rig.delta(new=new, layout=Racing())
        assert applied == [1, 2]      # both ops went in before the check
        assert n is None
        np.testing.assert_array_equal(row, rig.old_row)

    def test_absent_peer_contributes_no_members(self, rig):
        """A peer with no fragment at the walk and none now: the op
        changes only what does not involve it."""
        rig.views[1].delete_fragment(SHARD)
        for r in range(RG):
            rig.model.bits["g"].pop(r, None)
        rig.mark()
        rig.write("f", 1, 109)
        n, row = rig.delta()
        assert n == 1
        np.testing.assert_array_equal(row, rig.row_of(rig.model, rig.rs))


def test_row_count_layout_delta():
    """One field, no peers: TopN's rank vector row."""
    h = Holder().open()
    f = h.create_index("i").create_field("f")
    f.set_bit(1, 3)
    f.set_bit(2, 3)
    fr = f.view(VIEW_STANDARD).fragment(0)
    old = [(fr.uid, fr.version)]
    old_row = np.array([0, 1, 1, 0], dtype=np.int64)
    f.set_bit(1, 4)
    f.clear_bit(2, 3)
    row = old_row.copy()
    n = shard_delta(row, old_row, 0, [f.view(VIEW_STANDARD)], old,
                    [(fr.uid, fr.version)], RowCountRows((4,)))
    assert n == 2 and row.tolist() == [0, 2, 0, 0]
    f.set_bit(9, 1)
    row = old_row.copy()
    assert shard_delta(row, old_row, 0, [f.view(VIEW_STANDARD)], old,
                       [(fr.uid, fr.version)], RowCountRows((4,))) is None
    assert row.tolist() == old_row.tolist()


# ---------------------------------------------------------------------------
# the walks and the refresh skeleton
# ---------------------------------------------------------------------------


def _counter(stats, name):
    return sum(v for (n, _), v in stats._counters.items() if n == name)


class _Table:
    """A pair table over `n_shards` shards of f and g, kept by the
    skeleton the way exec/tpu.py keeps it."""

    def __init__(self, n_shards=3):
        self.stats = StatsClient()
        self.walks = VersionWalks(self.stats)
        self.holder = Holder().open()
        idx = self.holder.create_index("i")
        self.f, self.g = idx.create_field("f"), idx.create_field("g")
        self.shards_t = tuple(range(n_shards))
        self.model = [_Model(("f", "g")) for _ in self.shards_t]
        rng = np.random.default_rng(3)
        for s in self.shards_t:
            for fld in (self.f, self.g):
                for _ in range(30):
                    self.write(fld, int(rng.integers(0, RF)),
                               s * SHARD_WIDTH + int(rng.integers(0, 30)))
        self.entry = self.cold()

    def write(self, fld, row, col, on=True):
        (fld.set_bit if on else fld.clear_bit)(row, col)
        if col // SHARD_WIDTH < len(self.model):
            self.model[col // SHARD_WIDTH].flip(
                fld.name, row, col % SHARD_WIDTH, on
            )

    @property
    def views(self):
        return (self.f.view(VIEW_STANDARD), self.g.view(VIEW_STANDARD))

    def want(self):
        return np.stack([_pair_row(m, (RF, RG)) for m in self.model])

    def cold(self):
        """What a sweep would have stored."""
        fp = fingerprint(self.shards_t, self.views)
        vers = tuple(
            self.walks._live_versions(fo, self.shards_t, tier="pair")
            for fo in (self.f, self.g)
        )
        table = self.want()
        return TierEntry(fp, table.sum(axis=0, dtype=np.int64), table,
                         vers, (RF, RG))

    def refresh(self, stale="entry", shards_t=None, max_slab_shards=64):
        stale = self.entry if stale == "entry" else stale
        shards_t = shards_t or self.shards_t
        fp = fingerprint(shards_t, self.views)
        live = self.walks._tier_versions(
            stale, (self.f, self.g), shards_t, "pair"
        )
        return refresh_entry(stale, fp, self.views, live, PairRows,
                             self.stats, max_slab_shards)


class TestRefreshEntry:
    def test_cold_key_and_shard_set_change_need_a_sweep(self):
        t = _Table()
        assert t.refresh(stale=None) is None
        assert t.refresh(shards_t=(0, 1)) is None
        t.entry.pershard = None  # past the retention gate
        assert t.refresh() is None

    def test_nothing_dirty_rekeys_the_cached_totals(self):
        t = _Table()
        t.write(t.f, 1, 7 * SHARD_WIDTH + 3)  # a shard nobody asked about
        ent = t.refresh()
        assert ent.fp == fingerprint(t.shards_t, t.views) != t.entry.fp
        assert ent.value is t.entry.value and ent.pershard is t.entry.pershard
        assert _counter(t.stats, "pair_stats_incremental_updates_total") == 0

    def test_delta_tier_and_its_counters(self):
        t = _Table()
        t.write(t.f, 1, 105)
        t.write(t.f, 1, 105, on=False)
        t.write(t.g, 3, 2 * SHARD_WIDTH + 109)
        ent = t.refresh()
        np.testing.assert_array_equal(ent.pershard, t.want())
        np.testing.assert_array_equal(ent.value, t.want().sum(axis=0))
        assert ent.value.dtype == np.int64
        assert ent.extra == (RF, RG)
        assert ent.vers == tuple(
            t.walks._live_versions(fo, t.shards_t) for fo in (t.f, t.g)
        )
        assert t.entry.pershard is not ent.pershard  # the old one is intact
        assert _counter(t.stats, "pair_stats_incremental_updates_total") == 1
        assert _counter(t.stats, "pair_stats_incremental_shards_total") == 2
        assert _counter(t.stats, "pair_stats_delta_ops_total") == 3

    def test_walk_is_journal_backed_and_counts_the_dirty_set(self):
        t = _Table()
        t.write(t.f, 1, 105)
        t.refresh()
        walks = {
            tags: v for (n, tags), v in t.stats._counters.items()
            if n == "version_walk_shards_total" and "kind:journal" in tags
        }
        assert sum(walks.values()) == 1  # f's one dirty shard; g's none

    def test_too_many_slab_shards_needs_a_sweep(self):
        t = _Table()
        for s in t.shards_t:  # both sides change: no delta anywhere
            t.write(t.f, 1, s * SHARD_WIDTH + 105)
            t.write(t.g, 1, s * SHARD_WIDTH + 105)
        assert t.refresh(max_slab_shards=2) is None

    def test_slab_tier_repacks_and_confirms(self):
        t = _Table()
        t.write(t.f, 1, 105)
        t.write(t.g, 1, 105)  # both sides of shard 0: slab tier
        t.write(t.f, 2, SHARD_WIDTH + 106)  # shard 1: delta tier
        ent = t.refresh()
        np.testing.assert_array_equal(ent.pershard, t.want())
        assert _counter(t.stats, "pair_stats_delta_ops_total") == 1
        assert _counter(t.stats, "pair_stats_incremental_shards_total") == 2

    def test_row_growth_past_the_table_needs_a_sweep(self):
        t = _Table()
        t.f.set_bit(RF + 5, 105)
        t.g.set_bit(1, 105)
        assert t.refresh() is None

    def test_self_pair_takes_the_slab_tier_and_packs_once(self):
        t = _Table()
        views = (t.views[0], t.views[0])
        rows = lambda: np.stack([  # noqa: E731
            np.concatenate([
                [len(m.cols("f", a) & m.cols("f", b))
                 for a in range(RF) for b in range(RF)],
                [len(m.cols("f", a)) for a in range(RF)] * 2,
            ]).astype(np.int32)
            for m in t.model
        ])
        fp = fingerprint(t.shards_t, views)
        vers = t.walks._live_versions(t.f, t.shards_t)
        stale = TierEntry(fp, rows().sum(axis=0), rows(), (vers, vers),
                          (RF, RF))
        t.write(t.f, 1, 105)
        live = t.walks._tier_versions(stale, (t.f, t.f), t.shards_t, "pair")
        assert live[0] is live[1]  # walked once
        ent = refresh_entry(stale, fingerprint(t.shards_t, views), views,
                            live, PairRows, t.stats, 64)
        np.testing.assert_array_equal(ent.pershard, rows())
        assert _counter(t.stats, "pair_stats_delta_ops_total") == 0
        assert ent.vers[0] == ent.vers[1]

    def test_a_refresh_chain_stays_exact(self):
        """Every entry is the next one's baseline: recorded versions must
        describe exactly the recorded content, or a later delta applies
        an op twice."""
        t = _Table()
        rng = np.random.default_rng(11)
        for step in range(12):
            for _ in range(int(rng.integers(1, 4))):
                fld = (t.f, t.g)[int(rng.integers(0, 2))]
                t.write(fld, int(rng.integers(0, RF)),
                        int(rng.integers(0, 3)) * SHARD_WIDTH
                        + int(rng.integers(0, 30)),
                        on=bool(rng.integers(0, 4)))
            t.entry = t.refresh()
            np.testing.assert_array_equal(t.entry.pershard, t.want(), str(step))


def test_group3_product_matches_a_brute_force_model():
    """GroupNRows.apply against every combination, through shard_delta
    on a denser state than the rig's."""
    rig = _Rig("group3")
    for a, b, k in itertools.product(range(RF), range(RG), range(RH)):
        rig.write("f", a, 500)
        rig.write("g", b, 500)
        rig.write("h", k, 500)
    rig.mark()
    rig.write("h", 1, 501)
    rig.write("h", 1, 500, on=False)
    n, row = rig.delta()
    assert n == 2
    np.testing.assert_array_equal(row, rig.row_of(rig.model, rig.rs))
