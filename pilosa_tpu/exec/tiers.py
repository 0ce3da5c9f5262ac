"""Serving tiers: host tables that answer queries with no device work,
and how each stays exact under writes.

The reference's rank cache (cache.go:136) materializes counts once and
serves from them until a write invalidates; here every table-answered
query (a pair Count batch, TopN, a 1-/2-/N-field GroupBy, Sum/Min/Max)
does the same against ONE protocol:

- A table (TierTable) maps a key to a TierEntry whose fingerprint is
  the shard tuple plus the views' O(1) data generations. An unchanged
  generation means no write anywhere under the view, so a hit is one
  lock, one lookup, one compare — no version walk.
- A moved generation makes one thread the refresher (single flight);
  it walks per-shard (uid, version) — O(dirty) through the view's
  journal (VersionWalks) — and re-derives JUST the dirty shards' rows
  of the per-shard table (refresh_entry): point writes replay from the
  fragment's bit-op ring under a capture / confirm / revert protocol
  (shard_delta), anything else re-packs the shard's slabs with the
  version confirmed across the pack (_pack_confirmed).
- Recorded versions describe EXACTLY the content captured; whatever
  could not be confirmed records _VERS_STALE and re-derives next epoch.
  Replay is not idempotent: a recorded version older than the content
  would apply an op twice.

What needs the device stays in exec/tpu.py: the size gates, the sweep
programs, stack fetch, dispatch and readback. This module is host-only
(numpy, core, utils) and imports without jax.
"""

from __future__ import annotations

import itertools
import threading
from typing import Callable, Optional

import numpy as np

from pilosa_tpu.core.view import VIEW_STANDARD
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.qprofile import current_profile

#: ops.blocks.WORDS_PER_SHARD, restated: importing anything under ops
#: pulls in jax (ops/__init__.py configures the compile cache).
_WORDS_PER_SHARD = SHARD_WIDTH // 32

#: Recorded-version sentinel: never equal to any live (uid, version), so
#: the next epoch's diff marks the shard dirty and the delta tier's
#: uid check routes it to a slab re-derive. Stored whenever captured
#: content could not be confirmed against a version (a write raced the
#: capture) — recording an OLDER version than the content would make
#: the non-idempotent delta replay double-apply ops.
_VERS_STALE = ("stale", -1)


def fingerprint(shards_t: tuple, views) -> tuple:
    """The freshness token a hit compares: the queried shards and each
    view's data generation (-1 for a view that does not exist yet)."""
    return (
        shards_t,
        tuple([v.generation if v is not None else -1 for v in views]),
    )


class TierEntry:
    """One key's answer and what it was derived from.

    fp: the fingerprint (see `fingerprint`; a tier may append to it).
    value: what queries are served. The pair tier stores the in-flight
    device array right after a sweep and swaps in the int64 host totals
    on first resolve (TierTable.settle). pershard: the resident
    per-shard table that makes write epochs cheap on one chip or many,
    or None where it was too large to retain. vers: per field, the
    per-shard (uid, version) the table was derived from — the
    fine-grained diff consulted only when a generation moved; freshness
    never requires touching the device stack. extra: the row counts
    (padded stack heights) that fix the table's geometry, or the tier's
    own state (Sum's raw total and count, the plan memo's pinned
    calls)."""

    __slots__ = ("fp", "value", "pershard", "vers", "extra")

    def __init__(self, fp, value, pershard=None, vers=None, extra=None):
        self.fp = fp
        self.value = value
        self.pershard = pershard
        self.vers = vers
        self.extra = extra


class TierTable:
    """A bounded LRU of TierEntry behind its own lock, with one
    single-flight refresh per key.

    Single flight: under write churn, 16 serving threads missing the
    same epoch would each redo the same host update (a 16x thundering
    herd that ran the dirty set away into repeated device sweeps);
    instead one thread refreshes, the rest wait and re-check.

    on_hit runs after a hit, outside the lock (the tier's hit counter,
    spelled at the call site where the metric lint reads it);
    on_change runs under the lock after every store, eviction and
    clear, with the live entries (the agg tier's byte gauge)."""

    def __init__(self, cap: int, on_hit: Optional[Callable] = None,
                 on_change: Optional[Callable] = None):
        self.cap = cap
        self._on_hit = on_hit
        self._on_change = on_change
        self._lock = threading.Lock()
        self._entries: dict = {}
        self._refreshing: dict = {}

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __getitem__(self, key) -> TierEntry:
        with self._lock:
            return self._entries[key]

    def get(self, key) -> Optional[TierEntry]:
        with self._lock:
            return self._entries.get(key)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            if self._on_change is not None:
                self._on_change(self._entries.values())

    def store(self, key, ent: TierEntry) -> None:
        """Insert as most recent, evicting the oldest past the cap."""
        with self._lock:
            self._entries.pop(key, None)
            self._entries[key] = ent
            while len(self._entries) > self.cap:
                self._entries.pop(next(iter(self._entries)))
            if self._on_change is not None:
                self._on_change(self._entries.values())

    def settle(self, ent: TierEntry, pending, value, pershard) -> None:
        """Swap an entry's in-flight value for its host form, once:
        racing resolvers each read back, the first one lands."""
        with self._lock:
            if ent.value is pending:
                ent.value = value
                ent.pershard = pershard

    def _fresh(self, key, fp) -> Optional[TierEntry]:
        """The lookup half of a hit, under self._lock: the entry when
        its fingerprint still holds, touched as most recent."""
        ent = self._entries.get(key)
        if ent is None or ent.fp != fp:
            return None
        self._entries[key] = self._entries.pop(key)  # LRU
        return ent

    def hit(self, key, fp) -> Optional[TierEntry]:
        """The hit gate alone (tiers without single flight)."""
        with self._lock:
            ent = self._fresh(key, fp)
        if ent is not None and self._on_hit is not None:
            self._on_hit()
        return ent

    def _admit(self, key, shards_t, views):
        """Hit gate + single-flight admission in one lock acquisition.
        Returns (entry, fp, fresh): a fresh entry is a hit; otherwise
        the caller holds the key's latch, and entry is whatever the key
        held (the refresher's baseline) or None. The generations are
        read INSIDE the loop so a waiter re-checks against the newest
        epoch, and before any version walk, which keeps recorded
        fingerprints conservatively old (a spurious re-check next time,
        never staleness)."""
        while True:
            fp = fingerprint(shards_t, views)
            with self._lock:
                ent = self._fresh(key, fp)
                if ent is None:
                    stale = self._entries.get(key)
                    latch = self._refreshing.get(key)
                    if latch is None:
                        self._refreshing[key] = threading.Event()
                        return stale, fp, False
            if ent is not None:
                if self._on_hit is not None:
                    self._on_hit()
                return ent, fp, True
            latch.wait(timeout=60)

    def serve(self, key, shards_t: tuple, views, refresh: Callable,
              gate_phase: Optional[str] = None):
        """The fresh entry for `key`: a hit, or what refresh(stale, fp)
        returns on the one thread that runs it while the others wait
        and re-check. refresh runs WITHOUT the lock (walks, slab packs,
        stack fetches and dispatches are the slow part) and stores its
        own result; being the only refresher makes store-time
        re-validation unnecessary. gate_phase files the gate (and a
        waiter's wait) under that phase of the active query profile."""
        if gate_phase is None:
            ent, fp, fresh = self._admit(key, shards_t, views)
        else:
            with current_profile().phase(gate_phase):
                ent, fp, fresh = self._admit(key, shards_t, views)
        if fresh:
            return ent
        try:
            return refresh(ent, fp)
        finally:
            with self._lock:
                latch = self._refreshing.pop(key, None)
            if latch is not None:
                latch.set()


# ---------------------------------------------------------------------------
# version walks
# ---------------------------------------------------------------------------


class VersionWalks:
    """Per-shard (uid, version) reads off the live fragments — the
    write-epoch detail behind a moved generation — and their
    accounting. The device backend inherits these."""

    def __init__(self, stats):
        self.stats = stats

    def _count_version_walk(self, kind: str, tier: str, n_shards: int) -> None:
        """Freshness-walk attribution (ISSUE r6): every per-shard version
        read is counted so the O(S) full walks at 954 shards are visible
        on /metrics (version_walk_total / version_walk_shards_total,
        tagged kind=full|journal and the stats tier that paid for it)
        and in the active query's /debug/queries counters. The journal
        tier's shard count is the dirty set — the O(dirty) claim the
        bench and tests assert instead of assuming."""
        st = self.stats.with_tags(f"kind:{kind}", f"tier:{tier}")
        st.count("version_walk_total")
        st.count("version_walk_shards_total", n_shards)
        prof = current_profile()
        prof.incr(f"version_walk_{kind}")
        prof.incr(f"version_walk_{kind}_shards", n_shards)
        ex = getattr(prof, "explain", None)
        if ex is not None:
            ex._node().setdefault("freshness", []).append(
                {"walk": kind, "tier": tier, "shards": n_shards}
            )

    def _confirm_vers(self, field_obj, shards_t, recorded,
                      view_name=VIEW_STANDARD, tier="other"):
        """Post-capture version confirmation: any shard whose live
        (uid, version) moved past the recorded capture version gets
        _VERS_STALE, so the next epoch slab-rederives it instead of
        delta-replaying ops onto content that may already include them
        (sweeps/stack builds read fragment content after reading
        versions; the window is small but real under churn)."""
        live = self._live_versions(field_obj, shards_t, view_name, tier=tier)
        if live == recorded:
            return recorded
        return tuple(
            r if r == l else _VERS_STALE for r, l in zip(recorded, live)
        )

    def _confirm_vers_journal(self, field_obj, shards_t, recorded,
                              gen_recorded, view_name=VIEW_STANDARD,
                              tier="other"):
        """Journal-backed post-capture confirmation: same staleness
        contract as _confirm_vers, but O(dirty) instead of O(S) locked
        reads (ISSUE 17 satellite — the groupn tier paid 12 full walks
        per bench leg through _confirm_vers). Exactness: writers journal
        the shard before bumping the fragment version inside the same
        critical section, so any write that could make a recorded
        version stale after generation `gen_recorded` is in
        dirty_shards_since(gen_recorded); shards outside the dirty set
        are untouched since capture and their recorded version is live
        by construction. Only dirty shards take the locked read."""
        v = field_obj.view(view_name)
        if v is None:
            self._count_version_walk("journal", tier, 0)
            return tuple(None for _ in shards_t)
        dirty = v.dirty_shards_since(gen_recorded)
        if dirty is None:
            # Journal horizon passed (compaction): fall back to the full
            # locked walk — correctness over the O(dirty) fast path.
            return self._confirm_vers(
                field_obj, shards_t, recorded, view_name, tier=tier
            )
        out = list(recorded)
        n_read = 0
        for i, s in enumerate(shards_t):
            if s not in dirty:
                continue
            fr = v.fragment(s)
            if fr is None:
                live = None
            else:
                n_read += 1
                with fr.lock:
                    live = (fr.uid, fr.version)
            if out[i] != live:
                out[i] = _VERS_STALE
        self._count_version_walk("journal", tier, n_read)
        return tuple(out)

    def _live_versions(self, field_obj, shards_t, view_name=VIEW_STANDARD,
                       tier="other"):
        """Per-shard (uid, version) read straight from the live fragments
        — the write-epoch key the host stats caches compare against.
        Reading the LIVE versions (not the resident stack's) is what lets
        pair/TopN batches resolve entirely on the host under write churn:
        the device stack can stay stale until a query actually needs it
        (every stack consumer re-checks its own fingerprint).

        Each read holds fr.lock: writers mutate storage before bumping
        version inside their critical section, so an unlocked read can
        return a pre-write version for post-write content. Locked reads
        serialize with the writer, which makes _confirm_vers (built on
        this) a true post-capture barrier — a capture that raced a write
        is always seen as moved and recorded _VERS_STALE.

        This is the FULL walk — O(len(shards_t)) locked reads — and is
        counted as such per tier (by locked reads actually taken, so a
        missing view or absent fragments don't inflate the accounting);
        _epoch_versions is the journal-backed O(dirty) alternative for
        epoch updates."""
        v = field_obj.view(view_name)
        if v is None:
            self._count_version_walk("full", tier, 0)
            return tuple(None for _ in shards_t)
        out = []
        n_read = 0
        for s in shards_t:
            fr = v.fragment(s)
            if fr is None:
                out.append(None)
            else:
                n_read += 1
                with fr.lock:
                    out.append((fr.uid, fr.version))
        self._count_version_walk("full", tier, n_read)
        return tuple(out)

    def _epoch_versions(self, f, shards_t, vn, vers_old, gen_recorded,
                        tier="agg"):
        """Per-shard live versions for an epoch update, built from the
        view's mutation journal when it fully explains
        (gen_recorded, now]: only the dirtied shards pay a locked
        fragment read; every other shard carries its RECORDED version
        forward (exact — an unjournaled shard had no _mutated, so its
        (uid, version) is unchanged). Falls back to the full locked walk
        (_live_versions) when the journal can't explain. At 954 shards
        the walk cost ~1.8 ms x3 aggregate kinds per write epoch — the
        minmax churn leg's dominant serving cost. Counted per tier as a
        kind=journal walk whose shard count is the DIRTY set (the
        O(dirty) invariant tests/test_telemetry.py asserts).

        Every serving-path freshness consumer routes through here
        (ISSUE r7 journal-complete): Sum/Min/Max value epochs and,
        through _tier_versions, the pair tier, the TopN rank table and
        the GroupN tensor. _VERS_STALE entries recorded by a racing
        capture self-heal: the write that staled them bumped the
        generation AFTER gen_recorded was read, so the journal names
        that shard dirty and the locked re-read replaces the sentinel."""
        v = f.view(vn)
        if v is None or vers_old is None:
            return self._live_versions(f, shards_t, vn, tier=tier)
        dirty = v.dirty_shards_since(gen_recorded)
        if dirty is None or len(vers_old) != len(shards_t):
            return self._live_versions(f, shards_t, vn, tier=tier)
        out = list(vers_old)
        n_read = 0
        for i, s in enumerate(shards_t):
            if s in dirty:
                fr = v.fragment(s)
                if fr is None:
                    out[i] = None
                else:
                    n_read += 1  # counted like _live_versions: locked reads
                    with fr.lock:  # serialize with a mid-write bump
                        out[i] = (fr.uid, fr.version)
        self._count_version_walk("journal", tier, n_read)
        return tuple(out)

    def _tier_versions(self, stale, fobjs, shards_t, tier: str) -> list:
        """Per field, the standard view's per-shard versions for a table
        refresh. Journal-complete (ISSUE r7): when the key's previous
        entry recorded versions at a known generation, the view journal
        names the dirtied shards and only THOSE pay a locked fragment
        read — O(dirty), not O(all shards). The full walk remains only
        for cold keys (no recorded versions) and journal-eviction
        windows. A field named twice (the self pair) is walked once."""
        ok = (
            stale is not None
            and stale.vers is not None
            and stale.fp[0] == shards_t
        )
        live: list = []
        for t, f in enumerate(fobjs):
            if t and f is fobjs[0]:
                live.append(live[0])
                continue
            live.append(self._epoch_versions(
                f, shards_t, VIEW_STANDARD,
                stale.vers[t] if ok else None,
                stale.fp[1][t] if ok else -1,
                tier=tier,
            ))
        return live


# ---------------------------------------------------------------------------
# slab tier: one shard's table row from host-packed slabs
# ---------------------------------------------------------------------------


def _pack_confirmed(fr, n_rows: int):
    """Pack a fragment slab with its (uid, version) CONFIRMED unchanged
    across the pack — a mid-pack write re-packs, so the returned version
    describes exactly the returned content (the delta tier replays ops
    on top of it and must not double-apply).

    The recheck holds fr.lock: writers mutate storage BEFORE bumping
    version inside their fr.lock critical section (fragment.py set_bit),
    so an unlocked recheck could observe the pre-write version for
    content the pack already saw. Acquiring the lock serializes with
    the writer — a mid-pack write has bumped version by the time the
    locked recheck runs, forcing the retry."""
    # Imported here: ops/__init__.py imports jax, and the slab tier only
    # runs under a device backend, which has.
    from pilosa_tpu.ops.blocks import pack_fragment

    while True:
        with fr.lock:
            v = (fr.uid, fr.version)
        slab = pack_fragment(fr, n_rows=n_rows)
        with fr.lock:
            confirmed = (fr.uid, fr.version) == v
        if confirmed:
            return slab, v


def _host_slab_pair_flat(fslab: np.ndarray, gslab: np.ndarray) -> np.ndarray:
    """One shard's pair-stats row [rf*rg + rf + rg] from host-packed
    slabs — must agree bit-for-bit with ops.kernels.pair_stats_pershard
    on the same slabs (differentially tested in test_tpu.py), because a
    host-updated table row sits next to device-swept rows.

    The broadcast AND is chunked over the word axis so the temporary
    stays ~64 MiB: unchunked it is rf*rg*W*4 bytes — 8 GiB per shard at
    the rf*rg = 2^16 bound the dispatch path allows."""
    rf, w = fslab.shape
    rg = gslab.shape[0]
    chunk = max(1, (64 << 20) // max(1, rf * rg * 4))
    pair = np.zeros((rf, rg), dtype=np.int64)
    for c0 in range(0, w, chunk):
        blk = fslab[:, None, c0 : c0 + chunk] & gslab[None, :, c0 : c0 + chunk]
        pair += np.bitwise_count(blk).sum(axis=-1, dtype=np.int64)
    cf = np.bitwise_count(fslab).sum(axis=-1, dtype=np.int64)
    cg = np.bitwise_count(gslab).sum(axis=-1, dtype=np.int64)
    return np.concatenate([pair.ravel(), cf, cg]).astype(np.int32)


def _host_slab_row_counts(slab: np.ndarray) -> np.ndarray:
    """Per-row popcounts of one packed shard slab (the TopN rank-vector
    contribution of that shard)."""
    return np.bitwise_count(slab).sum(axis=-1, dtype=np.int64)


def _host_slab_groupn(slabs: list, rs: list) -> np.ndarray:
    """One shard's N-field group tensor row, flat int32[K*rf*rg] — must
    agree bit-for-bit with ops.kernels.group_tile_stats_pershard over
    every extra-row combination of the same slabs (differentially
    tested in test_tpu.py) because a host-updated table row sits next
    to device-swept rows. The k axis is the tile odometer's: over the
    extras, LAST field fastest."""
    rf, rg = rs[0], rs[1]
    extra_rs = rs[2:]
    k_total = 1
    for rh in extra_rs:
        k_total *= rh
    fslab, gslab = slabs[0], slabs[1]
    w = fslab.shape[1]
    out = np.empty((k_total, rf, rg), dtype=np.int64)
    chunk = max(1, (64 << 20) // max(1, rf * rg * 4))
    for k in range(k_total):
        m = None
        rem = k
        for t in range(len(extra_rs) - 1, -1, -1):
            row = slabs[2 + t][rem % extra_rs[t]]
            rem //= extra_rs[t]
            m = row if m is None else (m & row)
        fm = fslab & m[None, :]
        pair = np.zeros((rf, rg), dtype=np.int64)
        for c0 in range(0, w, chunk):
            blk = fm[:, None, c0 : c0 + chunk] & gslab[None, :, c0 : c0 + chunk]
            pair += np.bitwise_count(blk).sum(axis=-1, dtype=np.int64)
        out[k] = pair
    return out.reshape(-1).astype(np.int32)


# ---------------------------------------------------------------------------
# per-tier row layouts
# ---------------------------------------------------------------------------
#
# What a tier supplies to the shared protocol: the flat layout of one
# shard's table row over the row counts `rs`, as
#   apply(row, t, r, members, sign)  one bit op on field t's row r, given
#       per field the rows holding a bit at the op's column
#       (members[t] == (r,));
#   slab_row(slabs)    the whole row from the fields' packed slabs;
#   slab_words         words ANDed by one slab_row (0: no budget applies);
#   totals(pershard)   the served value from the table;
#   counted(...)       the tier's counters, spelled out for the metric lint.


class _Rows:
    """A layout over the row counts `rs`; by default a slab row costs
    nothing worth a budget."""

    slab_words = 0

    def __init__(self, rs):
        self.rs = rs


class RowCountRows(_Rows):
    """TopN rank vector: int64[R], one field."""

    def apply(self, row, t, r, members, sign) -> None:
        row[r] += sign

    def slab_row(self, slabs) -> np.ndarray:
        return _host_slab_row_counts(slabs[0])

    def totals(self, pershard) -> np.ndarray:
        return pershard.sum(axis=0).astype(np.uint64)

    def counted(self, stats, n_dirty: int, n_ops: int) -> None:
        stats.count("topn_incremental_updates_total")
        stats.count("topn_incremental_shards_total", n_dirty)


class PairRows(_Rows):
    """Pair statistics: int32[rf*rg | cf | cg]. An op on one side is
    cf/cg ±1 plus one pair cell per row of the UNCHANGED side holding
    the column."""

    def apply(self, row, t, r, members, sign) -> None:
        rf, rg = self.rs
        if t == 0:
            row[rf * rg + r] += sign  # cf[r]
            for b in members[1]:
                row[r * rg + b] += sign
        else:
            row[rf * rg + rf + r] += sign  # cg[r]
            for a in members[0]:
                row[a * rg + r] += sign

    def slab_row(self, slabs) -> np.ndarray:
        return _host_slab_pair_flat(slabs[0], slabs[1])

    def totals(self, pershard) -> np.ndarray:
        return pershard.sum(axis=0, dtype=np.int64)

    def counted(self, stats, n_dirty: int, n_ops: int) -> None:
        stats.count("pair_stats_incremental_updates_total")
        stats.count("pair_stats_incremental_shards_total", n_dirty)
        if n_ops:
            stats.count("pair_stats_delta_ops_total", n_ops)


class GroupNRows(_Rows):
    """N>=3 group tensor: int32[K*rf*rg], k the odometer over fields
    3..N (last fastest). An op touches one cell per combination of the
    other fields' rows holding the column — none when any has none."""

    def __init__(self, rs):
        super().__init__(rs)
        self.k_total = 1
        for rh in rs[2:]:
            self.k_total *= rh
        self.slab_words = self.k_total * rs[0] * rs[1] * _WORDS_PER_SHARD

    def apply(self, row, t, r, members, sign) -> None:
        rs = self.rs
        for combo in itertools.product(*members):
            k = 0
            for u in range(2, len(rs)):
                k = k * rs[u] + combo[u]
            row[(k * rs[0] + combo[0]) * rs[1] + combo[1]] += sign

    def slab_row(self, slabs) -> np.ndarray:
        return _host_slab_groupn(slabs, self.rs)

    def totals(self, pershard) -> np.ndarray:
        return pershard.sum(axis=0, dtype=np.int64).reshape(
            self.k_total, self.rs[0], self.rs[1]
        )

    def counted(self, stats, n_dirty: int, n_ops: int) -> None:
        stats.count("groupn_incremental_updates_total")
        stats.count("groupn_incremental_shards_total", n_dirty)
        if n_ops:
            stats.count("groupn_delta_ops_total", n_ops)


# ---------------------------------------------------------------------------
# delta tier: capture / confirm / revert
# ---------------------------------------------------------------------------


def _moved(fr, recorded) -> bool:
    """Whether the live fragment left its recorded (uid, version). Read
    under fr.lock to serialize with a mid-write bump (see
    _pack_confirmed): a racing writer must be seen."""
    with fr.lock:
        return recorded is None or (fr.uid, fr.version) != recorded


def shard_delta(row, old_row, shard, views, old, new, layout) -> Optional[int]:
    """Apply one dirty shard's epoch to its table row, in place, as the
    exact point writes the fragment's bit-op ring recorded. `old`/`new`
    are this shard's per-field recorded and walk (uid, version). Returns
    the op count applied, or None — with `row` as `old_row` had it —
    when the slab tier must handle the shard: more than one field
    changed in the window (probes against a changing peer must see its
    state at op time), a fragment created/recreated, the ring doesn't
    cover the window, a row past the table's height, or a peer that
    moved under the probes."""
    changed = [t for t in range(len(views)) if old[t] != new[t]]
    if len(changed) != 1:
        return None
    t = changed[0]
    ov, nv = old[t], new[t]
    frag = views[t].fragment(shard) if views[t] is not None else None
    if frag is None or ov is None or nv is None or ov[0] != nv[0]:
        return None  # created/recreated fragment: no delta history
    ops = frag.bit_ops_between(ov[1], nv[1])
    if ops is None:
        return None
    # The probes below read the OTHER fields' live storage, which the
    # entry will record at their WALK versions (new[u]): confirm each
    # live fragment still matches before AND after applying — a write
    # racing the walk or the probes would bake its bit into a cell that
    # the peer's own delta replays again next epoch. On conflict, revert
    # the row and let the slab tier (version-confirmed pack) capture a
    # clean snapshot.
    peers = []
    for u, v in enumerate(views):
        if u == t:
            continue
        fru = v.fragment(shard) if v is not None else None
        if fru is None:
            if new[u] is not None:
                return None  # fragment vanished since the walk
        elif _moved(fru, new[u]):
            return None
        peers.append((u, fru))
    height = layout.rs[t]
    members: list = [()] * len(views)
    for _, r, c, sign in ops:
        if r >= height:
            row[:] = old_row
            return None  # table height exceeded mid-window
        members[t] = (r,)
        for u, fru in peers:
            members[u] = () if fru is None else tuple(
                b for b in range(layout.rs[u])
                if fru.storage.contains(b * SHARD_WIDTH + c)
            )
        layout.apply(row, t, r, members, sign)
    for u, fru in peers:
        if fru is not None and _moved(fru, new[u]):
            row[:] = old_row
            return None
    return len(ops)


# ---------------------------------------------------------------------------
# the refresh skeleton
# ---------------------------------------------------------------------------


def refresh_entry(stale, fp, views, live, layout_of, stats,
                  max_slab_shards: int,
                  max_slab_words: Optional[int] = None):
    """Absorb a write epoch on the host (serving under churn must not be
    device-round-trip bound). When the key's previous entry kept its
    per-shard table and the epoch dirtied few shards, re-derive JUST
    those shards' rows and re-sum the totals — the incremental
    maintenance the reference's rank cache does per write
    (cache.go:136-301), so a Set costs O(1 shard) host work instead of
    a stack sweep + device round trip. Host tables are mesh-agnostic:
    multi-chip serving absorbs churn the same way (a sweep's per-shard
    output is gathered over ICI once, cold).

    `live` is `_tier_versions`' walk. Returns the TierEntry to store
    under `fp` — never touching the device — or None when a real sweep
    is needed: cold key, shard-set change, no retained table, row growth
    past the table height, or too many shards (or words) for the slab
    tier.

    Two tiers per dirty shard, exact either way:
    1. DELTA (shard_delta) — the fragment's bit-op ring explains the
       whole epoch as point writes on ONE field. The scalable tier: the
       slab tier alone ran away under random-shard churn (dirty sets
       grew faster than they drained).
    2. SLAB — re-pack + popcount the whole shard. Bounded by
       max_slab_shards (and max_slab_words where a layout's row is
       costly); beyond that, a device sweep wins.
    Slab packs are version-confirmed (_pack_confirmed), delta shards
    keep the walk versions their op windows end at, and a vanished
    fragment records None."""
    if (
        stale is None
        or stale.pershard is None
        or stale.vers is None
        or stale.fp[0] != fp[0]
    ):
        return None
    shards_t = fp[0]
    n = len(views)
    dirty = [
        i for i in range(len(shards_t))
        if any(stale.vers[t][i] != live[t][i] for t in range(n))
    ]
    if not dirty:
        # Generation moved but no queried shard changed (writes outside
        # the queried set — ingest on another node's shards — or under
        # another view): re-key the CACHED totals so the O(1) generation
        # gate hits again, instead of a stack fetch + dispatch on every
        # query for as long as that ingest runs.
        return TierEntry(fp, stale.value, stale.pershard, tuple(live),
                         stale.extra)
    layout = layout_of(stale.extra)
    rs = layout.rs
    pershard = stale.pershard.copy()
    vers_rec = [list(lv) for lv in live]
    # A field paired with itself: the ordering of an op against a
    # changing self is ambiguous — slab tier.
    self_paired = len({id(v) for v in views}) != n
    slab_dirty: list[int] = []
    n_ops = 0
    for i in dirty:
        applied = None if self_paired else shard_delta(
            pershard[i], stale.pershard[i], shards_t[i], views,
            [vs[i] for vs in stale.vers], [lv[i] for lv in live], layout,
        )
        if applied is None:
            slab_dirty.append(i)
        else:
            n_ops += applied
    if len(slab_dirty) > max_slab_shards:
        return None
    if (
        max_slab_words is not None
        and len(slab_dirty) * layout.slab_words > max_slab_words
    ):
        return None
    for i in slab_dirty:
        slabs = []
        packed: dict = {}  # a self pair packs its one fragment once
        for t, v in enumerate(views):
            fr = v.fragment(shards_t[i]) if v is not None else None
            if fr is None:
                slab = np.zeros((rs[t], _WORDS_PER_SHARD), dtype=np.uint32)
                vers_rec[t][i] = None
            elif id(fr) in packed:
                slab, vers_rec[t][i] = packed[id(fr)]
            else:
                slab, vers_rec[t][i] = packed[id(fr)] = _pack_confirmed(
                    fr, rs[t]
                )
                if fr.max_row_id >= rs[t]:
                    return None  # row grew past the table height: re-sweep
            slabs.append(slab[: rs[t]])
        pershard[i] = layout.slab_row(slabs)
    layout.counted(stats, len(dirty), n_ops)
    return TierEntry(
        fp, layout.totals(pershard), pershard,
        tuple(tuple(v) for v in vers_rec), stale.extra,
    )
