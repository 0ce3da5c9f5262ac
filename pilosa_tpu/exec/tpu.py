"""TPU device backend: PQL bitmap calls on dense HBM blocks.

Execution model (the part that makes this TPU-first rather than a port):

- Per (index, field, view) the backend keeps a STACKED device block
  uint32[n_shards, rows, WORDS/128, 128] cached in HBM, rebuilt only when
  a fragment version changes (the write path stays host-roaring). The
  rows are kept off the two minor axes the TPU tiles, so a program reads
  one row of the stack in place (ops/blocks.py).
- A query's call tree is compiled ONCE per tree-shape into a single
  jitted function: Row leaves become dynamic row-gathers from the stacked
  blocks (row ids are traced scalars, so consecutive queries with
  different rows reuse the compiled program), bitmap verbs are fused
  bitwise ops over [S, W/128, 128] slabs, BSI comparisons are plane scans with
  traced predicate bits, and Count/TopN/Sum reduce on device. One
  dispatch + one small transfer per query: a dispatch and its readback
  are a fixed cost the host pays per launch, whatever the launch sweeps.
- The reference's per-shard mapReduce loop (executor.go:2460) therefore
  disappears into XLA: the shard axis is the leading array dim on a
  single chip, or a jax.sharding.Mesh axis on multiple chips. With a
  mesh, blocks are placed with NamedSharding(P('shards')) so each device
  holds its shards in local HBM, and reductions run under shard_map with
  lax.psum over ICI — the XLA-collective replacement for the reference's
  HTTP scatter-gather (SURVEY.md §2.2, BASELINE.json north star).

TopN is *exact* on this backend: popcount of every row is one fused
kernel, so the reference's approximate rank-cache candidates + 2-pass
recount (executor.go:860) collapses into one exact pass (SURVEY.md §3.4).

BSI aggregates (Sum/Min/Max) and comparisons (==, !=, <, <=, >, >=,
BETWEEN) lower to masked bitwise+popcount plane passes mirroring the
reference's algorithms (fragment.go:1111-1537); predicate magnitudes ride
in as traced uint32 bit-vectors so one compiled program serves any
predicate value of the same (op, sign, bit-depth) shape.

HBM residency: stacks are LRU-tracked against a byte budget
(`max-hbm-bytes`, else the device's own limit less a measured reserve),
room is made before every upload, and a stack a query holds is pinned;
stacks too large to ever fit fall back to row pages or the CPU oracle
(SURVEY.md §7 hard part c), and where an index's dense stacks do not fit
the budget its tallest mutex fields are held as one row id a column.
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
import time
import weakref
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from pilosa_tpu.core.cache import Pair
from pilosa_tpu.core.field import FIELD_TYPE_INT, FIELD_TYPE_MUTEX
from pilosa_tpu.core.fragment import BSI_EXISTS_BIT, BSI_OFFSET_BIT, BSI_SIGN_BIT
from pilosa_tpu.core.row import Row
from pilosa_tpu.core.timequantum import parse_time, views_by_time_range
from pilosa_tpu.core.view import (
    VIEW_STANDARD,
    bsi_view_name,
    generation_watermark,
)
from pilosa_tpu.exec.cpu import CPUBackend, NotFoundError, QueryError
from pilosa_tpu.exec.tiers import (  # noqa: F401 — the _names: tests import them from here
    GroupNRows,
    PairRows,
    RowCountRows,
    TierEntry,
    TierTable,
    VersionWalks,
    _host_slab_groupn,
    _host_slab_pair_flat,
    _pack_confirmed,
    fingerprint,
    refresh_entry,
)
from pilosa_tpu.ops.blocks import (
    PACKED_BITS,
    PACKED_WORDS,
    ROW_PAD,
    WORDS_PER_SHARD,
    _padded_rows,
    flat_words,
    fragment_tier_words,
    pack_fragment,
    pack_fragment_packed,
    pack_row,
    pack_rows,
    pack_fragment_rowid,
    pack_rows_packed,
    packed_rows,
    rowid_dtype,
    rowid_shape,
    stack_shape,
    tile_words,
    unpack_row,
    unpack_slab_columns,
)
from pilosa_tpu.ops.kernels import (
    GROUP_SUM_CHUNK,
    MAX_PAIR_SHARDS,
    group_sum_counts,
    group_sum_masks,
    group_tile_stats,
    group_tile_stats_pershard,
    mask_lane_slab,
    masked_lane_counts,
    pair_stats,
    pair_stats_pershard,
    packed_row_counts,
    ROWID_NO_ROW,
    rowid_hist,
    rowid_row,
    rowid_rows,
    slab_counts,
    splice_shard_slabs,
    tanimoto_counts,
    tanimoto_topn,
)
from pilosa_tpu.ops.runtime import pallas_interpret, require_serving_platform
from pilosa_tpu.parallel.mesh import pad_to_multiple
from pilosa_tpu.ops.sparse import (
    MIN_CHUNKED_WORDS,
    ChunkedStackBuilder,
    _sds,
    warm_chunk_programs,
)
from pilosa_tpu.pql.ast import (
    BETWEEN, Call, Condition, EQ, GT, GTE, LT, LTE, NEQ, canonical_key,
    is_reserved_arg,
)
from pilosa_tpu.roaring import Bitmap
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.locks import InstrumentedRLock
from pilosa_tpu.utils.qprofile import current_profile, device_feed
from pilosa_tpu.utils.qprofile import span as trace_span
from pilosa_tpu.utils.reuse import ReuseDistanceEstimator
from pilosa_tpu.utils.stats import global_stats

_DEVICE_LOWERED = ("Row", "Range", "Union", "Intersect", "Difference", "Xor", "Not", "All", "Shift")

# Per-(shard,row) popcounts are ≤2^20, so an on-device uint32 reduction over
# the shard axis is exact up to 4095 shards (4096·2^20 = 2^32). Beyond that
# the programs return per-shard partials and the host sums in Python ints.
MAX_DEVICE_SUM_SHARDS = 4095

# Pair-stats host cache bound: entries hold refs to two device stacks, so
# the cap (LRU) keeps many-field indexes from pinning evicted HBM arrays.
MAX_PAIR_CACHE_ENTRIES = 16

# BSI min/max assemble values from per-plane decision bits on the host, so
# depth is bounded only by the spec key; sums weight plane counts in exact
# Python ints. Depths beyond this are out of int64 BSI range anyway.
MAX_BSI_DEPTH = 63

# Device-memory cap for one batched bitmap-materialization launch's
# [Q, S, W] output; a row-leg group whose slot bucket would exceed it
# splits into multiple launches (each still amortizing its round trip).
MAX_ROW_BATCH_BYTES = 256 << 20

# Tiled GroupBy (ISSUE 17): slot cap per tile launch. Each slot sweeps
# one live (extra-row…) combination against the full [S, Rf, Rg] face,
# so the per-launch accumulator is T·S·Rf·Rg int32 on the pershard path
# — 64 slots keeps that under the pair budget at the bench shape while
# still amortizing the dispatch round trip across a whole bucket.
MAX_GROUP_TILE_SLOTS = 64

# Host-side cap on one GroupBy result tensor's cells (live_K · Rf · Rg).
# Bounds the _agg_cache charge and the enumeration working set; combos
# past it fall back to the CPU oracle rather than OOMing the host.
MAX_GROUP_RESULT_CELLS = 1 << 24

# GroupBy(…, aggregate=Sum(field=…)): combinations one group_sum launch
# lists (a program serves any number up to it), and the most live
# combinations the device answers before the host iterator does.
GROUP_SUM_SLOTS = 1024
MAX_GROUP_SUM_COMBOS = 1 << 16

# A grouped field whose filter names its rows has them counted alone
# (`pilosa_groupby_rows`) while their slot bucket, at most this many,
# is at most a quarter of the stack's rows; past that the whole-stack
# count reads about as much and serves any list.
MAX_GROUP_ROW_SLOTS = 16
GROUP_ROWS_SHARE = 4


def _from_list(table, total: int):
    """A leg's (row ids, counts) from its line of a tanimoto_topn output:
    a leading word, then the list's rows, then their counts."""
    size = (table.size - 1) // 2
    return table[1 : 1 + total].copy(), table[1 + size : 1 + size + total].copy()


def _slot_bucket(n: int) -> int:
    """Slot-count bucket for a batched launch: the next power of two.
    Batched programs trace the slot axis as a concrete array dim, so an
    exact-occupancy shape would recompile per batch size; bucketing pads
    occupancy into O(log Q) compiled signatures (ISSUE r11 tentpole —
    the ragged-paged-attention fixed-slot trick). Padded slots replay
    slot 0's operands and are lane-masked in-kernel."""
    b = 1
    while b < n:
        b <<= 1
    return b


def _named_rows(call: Call, field: str) -> Optional[set]:
    """The row ids of `field` that a filter names, where the filter is
    `Row(field=<id>)` with no other argument or a Union of such Rows
    alone; None for any other call."""

    def row_id(c):
        if c.name != "Row" or c.children or len(c.args) != 1:
            return None
        r = c.args.get(field)
        return None if isinstance(r, bool) or not isinstance(r, int) else r

    if call.name == "Union" and call.children and not call.args:
        ids = {row_id(c) for c in call.children}
        return None if None in ids else ids
    r = row_id(call)
    return None if r is None else {r}


def _layout_of(key: tuple) -> str:
    """The layout of a block-store key: (index, field, view) and a row
    page (…, "row", id) are dense; (…, "packed") and (…, "rowid")."""
    return key[3] if len(key) > 3 and key[3] != "row" else "dense"


def _pinning(method):
    """A backend method with every stack it fetches pinned until it
    returns (_StackedBlocks.pinned): its launches' outputs are read back
    by then, or, for a method that hands back a resolver, dispatched."""

    @functools.wraps(method)
    def run(self, *args, **kwargs):
        with self.blocks.pinned():
            return method(self, *args, **kwargs)

    return run


class _Unsupported(Exception):
    """Raised by the spec builder when a call can't be device-lowered."""


#: Marks the calling thread as the background windowed-refresh flusher
#: (ISSUE r19 tentpole 2): refresh_stale() sets it around its get()
#: calls so the freshness counters can tell a coalesced window flush
#: from a mid-window read forcing the splice barrier.
_REFRESHER = threading.local()


class _StackedBlocks:
    """Device cache: (index, field, view) -> uint32[S, R, W/128, 128] +
    freshness.

    The stack's two minor axes, the pair the TPU tiles in (8, 128), hold
    the words of ONE row (ops/blocks.py): a row of a shard is whole tiles,
    128 KiB contiguous, which a program's row leaf (_eval_spec) reads
    where it lies. Host code packs slabs flat ([R, W]) and tiles them, a
    view, where it hands them to the device.

    With a mesh, the shard axis is padded to a multiple of the device count
    and placed with NamedSharding(P('shards')) so each device holds its
    shards in local HBM. A byte budget (budget(): `max-hbm-bytes`, or the
    device's own limit less a reserve) LRU-evicts whole entries before an
    upload, never one a query in flight has pinned (pinned()); the HBM
    residency policy, resident_bytes feeding /metrics.

    Three layouts: dense (above); packed, a narrow set field's first
    4,096 columns (get_packed); and row ids, a mutex field as the one row
    that holds each column (get_rowid), chosen where the index's dense
    stacks would not fit the budget (rowid_field).
    """

    #: Incremental-update cutoff: splice at most this fraction of the
    #: shard axis before a full repack wins (splice cost is linear in
    #: dirty shards — pack + ship only them — so it beats the full
    #: rebuild's whole-stack pack + upload until about half the stack
    #: is dirty).
    MAX_INCREMENTAL_FRACTION = 2

    #: Dirty slabs ship in fixed-size chunks so ONE compiled scatter
    #: shape serves every epoch — a per-dirty-count shape would hit an
    #: XLA compile (seconds, on a ~GB operand) in the serving path the
    #: first time each count appeared; larger epochs chain this program.
    UPDATE_CHUNK = 8

    #: Mesh splice round width PER DEVICE: each round ships one slab per
    #: device (sharded placement — a device receives only its own slab)
    #: and one dispatch of the shard_map splice program, so a single
    #: dirty shard costs O(n_devices) slabs of wire, never O(all
    #: shards). Wider chunks would multiply the padding wire by the
    #: device count for no dispatch saving at realistic dirty rates.
    MESH_UPDATE_CHUNK = 1

    #: Rows a point-write epoch may splice into a PACKED stack
    #: (get_packed) before a full re-pack wins; the splice ships this
    #: many rows a dispatch, padded, so one compiled scatter serves.
    PACKED_UPDATE_ROWS = 64

    #: Default decayed-frequency half-life in seconds (config
    #: heat-half-life): a block untouched for one half-life keeps half
    #: its heat — 5 minutes separates the serving hot set from batch
    #: stragglers without forgetting a diurnal lull.
    HEAT_HALF_LIFE = 300.0

    #: The least a device keeps beside the entries where the budget is
    #: its own limit: one chunked stack build's staging (a stack of two
    #: chunks, ChunkedStackBuilder.transient_bytes).
    RESERVE_FLOOR = ChunkedStackBuilder.transient_bytes((MIN_CHUNKED_WORDS,))

    #: Longest an upload waits for other queries' pins to clear before
    #: it is refused (make_room).
    PIN_WAIT_SECONDS = 30.0

    #: (index, shards) layout choices kept; more and the cache restarts.
    MAX_LAYOUT_CHOICES = 64

    def __init__(self, device=None, mesh=None, max_bytes: Optional[int] = None,
                 fallback=None, heat_half_life: Optional[float] = None,
                 packed_extra=None, holder=None):
        self.device = device
        self.mesh = mesh  # ShardMesh or None
        self.max_bytes = max_bytes
        # The holder whose indexes the layout choice reckons (None: every
        # field is held dense or packed).
        self.holder = holder
        self.heat_half_life = heat_half_life or self.HEAT_HALF_LIFE
        # Online miss-ratio-curve input (ISSUE 18): every ledger access
        # (hit or rebuild) is offered to the SHARDS sampler; admission
        # is one hash compare, so the block-fetch path stays at its
        # pre-instrumentation cost when the hash rejects.
        self.reuse = ReuseDistanceEstimator()
        # Mesh-tier degradation counter (ISSUE r13 satellite: mesh gaps
        # must not be silent): called with (reason, shape, err) whenever
        # a mesh-specific fast path bails to the dense/rebuild behavior.
        # TPUBackend wires its _count_device_fallback here.
        self._fallback = fallback if fallback is not None else (
            lambda reason, shape, err: None
        )
        # key -> (fingerprint, device array, rows_p, per-shard versions,
        # what the build keeps beside the array: a packed stack's row
        # counts). The array is None where a packed key's verdict is "not
        # packed" (get_packed): no bytes, no ledger line.
        self._entries: dict[tuple, tuple] = {}
        self.evictions = 0
        # Per-entry HBM ledger (ISSUE r8 tentpole 4): resident bytes
        # split by representation tier (dense / array-container /
        # run-container source), upload epoch, access count, last-access
        # time. Keys mirror _entries; served at /debug/hbm sorted by
        # coldness and rolled up as hbm_resident_bytes{tier} gauges.
        self._ledger: dict[tuple, dict] = {}
        self._upload_epoch = 0
        # One compiled in-place slice writer per stack shape (traced shard
        # index, so any dirty shard reuses the same program).
        self._update_fns: dict = {}
        # Queries are served concurrently (ThreadingHTTPServer); the LRU
        # touch/evict mutate on reads, so all access goes under one lock
        # (ADVICE r2: dict-changed-size races surfaced as 500s).
        self._lock = InstrumentedRLock("hbm_ledger")
        # Per-key build latch: concurrent misses for the same stack must
        # not pack+upload it twice (duplicate HBM residency could blow the
        # byte budget); losers wait for the winner's entry.
        self._building: dict[tuple, threading.Event] = {}
        # Windowed device-refresh coalescing (ISSUE r19 tentpole 2):
        # when > 0, TPUBackend's refresher thread calls refresh_stale()
        # every window so dirty shards accumulated across the window
        # flush as ONE incremental splice round per stack — instead of
        # every read paying the splice inline after every write. Journal
        # generation stamps stay per-write (rescache addressability and
        # read-your-writes unchanged); only the device-tensor
        # consequence is batched. Reads landing mid-window still
        # revalidate inline — the flush-on-demand barrier — so answers
        # stay byte-identical to unwindowed execution.
        self.refresh_window_ms = 0
        # key -> (field_obj, shards, view_name, min_rows): the build
        # arguments the flusher replays through get(). Whole stacks
        # only (row pages are demand-paged by design). GIL-atomic dict
        # writes; pruned against _entries under _lock in refresh_stale.
        self._refresh_args: dict[tuple, tuple] = {}
        # bytes_limit of the device, read once (_device_limit).
        self._device_bytes: Optional[int] = None
        # Called with (a packed stack as it is built or spliced, the
        # stack it replaces or None); what it returns is kept in the
        # stack's entry and get_packed hands it back. TPUBackend counts
        # the rows there.
        self.packed_extra = packed_extra or (lambda arr, stale_arr: None)
        # Pins: key -> how many pin scopes hold it (pinned()); a thread's
        # own scope is the set of keys it pinned. An upload waiting for
        # pins to clear sleeps on the event, set as a scope ends.
        self._pins: dict[tuple, int] = {}
        self._pin_local = threading.local()
        self._unpinned = threading.Event()
        # Open pin scopes that hold a key, and how many of them wait in
        # make_room: where all of them wait, none will unpin.
        self._holders = 0
        self._stalled = 0
        # The most the device was seen to hold beyond the entries, and
        # the largest transient a launch was reckoned to take
        # (note_transient): the measured part of the reserve.
        self._other_bytes = 0
        self._launch_bytes = 0
        # (index, shards) -> (generation watermark, frozenset of the
        # fields held as row ids): the layout choice (rowid_field).
        self._layouts: dict[tuple, tuple] = {}
        # (index, field) -> the view generation at which the row-id
        # build found a column in two rows: held dense until it moves.
        self._rowid_refused: dict[tuple, int] = {}

    # -- the budget ---------------------------------------------------------

    def _device_limit(self) -> int:
        """bytes_limit of one device, read once; 0 where none is known (a
        CPU device reports no limit)."""
        with self._lock:
            if self._device_bytes is None:
                limit = 0
                try:
                    limit = int((self._first_device().memory_stats() or {})
                                .get("bytes_limit", 0))
                except (RuntimeError, AttributeError, NotImplementedError):
                    limit = 0  # no memory_stats on this backend: no limit known
                self._device_bytes = limit
            return self._device_bytes

    def _first_device(self):
        return self.device or (
            self.mesh.devices[0] if self.mesh is not None else jax.devices()[0]
        )

    def reserve_bytes(self) -> int:
        """Bytes a device keeps beside the entries where the budget is its
        own limit: the largest of RESERVE_FLOOR (one chunked build's
        staging), the largest transient a launch of this process was
        reckoned to take (note_transient: a grouped Sum's row operand,
        a histogram's two small stacks), and the most the device was
        measured to hold beyond the entries (`bytes_in_use` less the
        entries' bytes, read before every upload: programs' temporaries
        and outputs, arrays a query still holds)."""
        with self._lock:
            return max(self.RESERVE_FLOOR, self._launch_bytes,
                       self._other_bytes)

    def budget(self) -> Optional[int]:
        """The bytes the entries may take: `max-hbm-bytes` where it is
        set; else every device's bytes_limit less the reserve
        (reserve_bytes); None where no limit is known (a CPU device):
        nothing is then evicted, refused or held as row ids."""
        if self.max_bytes is not None:
            return self.max_bytes
        limit = self._device_limit()
        if not limit:
            return None
        n = self.mesh.n if self.mesh is not None else 1
        return max(0, n * (limit - self.reserve_bytes()))

    def note_transient(self, nbytes: int) -> None:
        """A launch about to take `nbytes` of temporaries beside the
        entries: the reserve keeps the largest such."""
        with self._lock:
            if nbytes > self._launch_bytes:
                self._launch_bytes = int(nbytes)

    def _measure_other(self) -> None:
        """Read what the device holds beyond the entries (caller holds
        _lock); where no figure is known, nothing is read."""
        if self.max_bytes is not None or not self._device_limit():
            return
        try:
            in_use = int((self._first_device().memory_stats() or {})
                         .get("bytes_in_use", 0))
        except (RuntimeError, AttributeError, NotImplementedError):
            return
        n = self.mesh.n if self.mesh is not None else 1
        other = in_use - self.resident_bytes() // n
        if other > self._other_bytes:
            self._other_bytes = other

    # -- pins ---------------------------------------------------------------

    @contextlib.contextmanager
    def pinned(self):
        """Pin every entry this thread fetches inside the block until the
        block ends: make_room and _evict skip a pinned entry, so an entry
        whose array a query holds for its launches is never counted freed
        while the device still holds it. Callers end the block once the
        launches' outputs are read back. A block inside another pins
        into the outer one."""
        if getattr(self._pin_local, "keys", None) is not None:
            yield
            return
        keys: set = set()
        self._pin_local.keys = keys
        try:
            yield
        finally:
            self._pin_local.keys = None
            if keys:
                with self._lock:
                    self._holders -= 1
                    for k in keys:
                        n = self._pins.get(k, 0) - 1
                        if n > 0:
                            self._pins[k] = n
                        else:
                            self._pins.pop(k, None)
                self._unpinned.set()

    def _pin(self, key: tuple) -> None:
        """Pin `key` into this thread's open scope, if any (caller holds
        _lock)."""
        keys = getattr(self._pin_local, "keys", None)
        if keys is not None and key not in keys:
            if not keys:
                self._holders += 1
            keys.add(key)
            self._pins[key] = self._pins.get(key, 0) + 1

    def _pinned_by_others(self, key: tuple) -> bool:
        """Caller holds _lock."""
        mine = getattr(self._pin_local, "keys", None) or ()
        return self._pins.get(key, 0) > (1 if key in mine else 0)

    def _pad_shards(self, n: int) -> int:
        if self.mesh is None or self.mesh.n <= 1:
            return n
        # Shared with ShardMesh.put so both placements agree on the
        # padded shard axis (zero slabs, semantically inert — see
        # parallel/mesh.py for the padding contract).
        return pad_to_multiple(n, self.mesh.n)

    def _put(self, host: np.ndarray):
        """Place host-packed slabs uint32[S, R, W] as a device stack."""
        if self.mesh is not None and self.mesh.n > 1:
            sharding = NamedSharding(self.mesh.mesh, P(self.mesh.axis))
            return jax.device_put(tile_words(host), sharding)
        return jax.device_put(tile_words(host), self.device)

    def get(self, index: str, field_obj, shards: tuple[int, ...],
            view_name: str = VIEW_STANDARD, min_rows: int = 1):
        """Returns (block [S_pad,R,W/128,128], rows_p). Missing fragments
        pack as zeros; padded shards are all-zero (they contribute nothing
        to any count/bitwise result). min_rows forces taller stacks (BSI plane
        count independent of stored max row). A field held as row ids
        (rowid_field) has no dense stack: (None, 0)."""
        if view_name == VIEW_STANDARD and self.rowid_field(
            index, field_obj, shards
        ):
            return None, 0
        v = field_obj.view(view_name)
        # O(1) freshness: the view's generation covers every fragment
        # mutation and create/delete under it (core/view.py), so a hit
        # needs no per-fragment walk — the old (uid, version)-per-shard
        # fingerprint cost ~1 ms per lookup at the 954-shard bench shape.
        fingerprint = (
            tuple(shards),
            v.generation if v is not None else -1,
            min_rows,
        )
        # Keyed by (index, field, view) only: a changed shard set REPLACES
        # the cached stack rather than accumulating per-subset copies in HBM.
        key = (index, field_obj.name, view_name)

        with self._lock:
            self._refresh_args[key] = (field_obj, shards, view_name, min_rows)

        def build(stale):
            t_build = time.perf_counter()
            if stale is not None and self.refresh_window_ms > 0:
                # Freshness attribution under windowing: a stale entry
                # refreshed by the background flusher is a coalesced
                # window flush; one refreshed by a serving read is the
                # mid-window flush-on-demand barrier firing.
                if getattr(_REFRESHER, "active", False):
                    global_stats.count("stack_windowed_refresh_total")
                else:
                    global_stats.count("stack_refresh_forced_total")
            frags = {s: (v.fragment(s) if v is not None else None) for s in shards}
            vers = tuple(
                (fr.uid, fr.version) if fr is not None else None
                for fr in (frags[s] for s in shards)
            )
            n_rows = max(
                [fr.max_row_id + 1 for fr in frags.values() if fr is not None]
                + [min_rows]
            )
            rows_p = _padded_rows(n_rows)
            s_pad = self._pad_shards(len(shards))
            nbytes = s_pad * rows_p * WORDS_PER_SHARD * 4
            if stale is not None and stale[0][0] != tuple(shards):
                # Another shard set (a `?shards=` subset): the stack it
                # replaces goes first, so the two are never held at once.
                self._drop_unpinned(key)
            # A splice writes a new array beside the old one, and ships
            # its dirty slabs a chunk at a time.
            splice_bytes = nbytes + self.UPDATE_CHUNK * rows_p * WORDS_PER_SHARD * 4
            updated = None
            if (
                stale is not None and stale[0][0] == tuple(shards)
                and self.make_room(splice_bytes, keep=key)
            ):
                updated = self._try_incremental(
                    stale, shards, min_rows, frags, vers, rows_p, s_pad
                )
            if updated is not None:
                # tiers=None: the ledger keeps the previous split (the
                # splice touched O(dirty) shards; re-walking EVERY
                # container for attribution would re-add exactly the
                # O(all-shards) host work the incremental path removes —
                # the mix re-trues on the next full rebuild).
                return updated, rows_p, vers, None
            budget = self.budget()
            if budget is not None and nbytes > budget:
                # Stack can never be resident under the budget: the caller
                # falls back to row paging or the CPU oracle instead of
                # blowing HBM. Not cached (None entries are cheap to
                # recompute and must not evict real stacks).
                return None, rows_p, vers, None
            if not self.make_room(
                nbytes + self._build_transient(nbytes, rows_p), keep=key
            ):
                return None, rows_p, vers, None
            if stale is not None:
                # A resident stack is being fully re-packed + re-shipped
                # — the cost the incremental splice exists to avoid. The
                # mesh differential suite and the bench's under-churn
                # point assert this stays flat while splices absorb
                # write epochs.
                global_stats.count("stack_full_rebuilds_total")
            # Ledger tier attribution, full builds only: which source
            # containers back the resident words (independent of the
            # WIRE tier each chunk chose — the ledger answers "what
            # representation mix is this HBM holding", the wire counters
            # answer "what did the upload cost"). O(containers), paid
            # only where the pack itself is already O(everything).
            tiers = [0, 0]
            for fr in frags.values():
                if fr is not None:
                    a, r = fragment_tier_words(fr, rows_p)
                    tiers[0] += a
                    tiers[1] += r
            shape = stack_shape(s_pad, rows_p)
            arr = None
            if self.mesh is None and (nbytes // 4) >= MIN_CHUNKED_WORDS:
                # Streaming packed upload (VERDICT r4 #1): shard slabs
                # compress and ship as they pack, so the wire rides
                # under the host pack instead of after it. Fragments
                # stream container-natively (ISSUE r7): array/run
                # containers ship as 16-bit positions / run spans and
                # expand on device, so word-dense-but-bit-sparse stacks
                # (the f/g bench shape) stop shipping dense AND skip the
                # host-side dense pack; word-sparse stacks (time-quantum
                # views, short fields) still ship the zero-word-mask
                # wire. ops/sparse.py for the tier decision and the
                # fixed-shape program design.
                builder = ChunkedStackBuilder(self.device, shape)
                slab_words = rows_p * WORDS_PER_SHARD
                for s in shards:
                    fr = frags[s]
                    if fr is not None:
                        builder.feed_fragment(fr, rows_p)
                    else:
                        builder.skip(slab_words)
                builder.skip((s_pad - len(shards)) * slab_words)
                arr = builder.finish()
            elif self.mesh is not None and (nbytes // 4) >= MIN_CHUNKED_WORDS:
                # Sharded streaming build (ISSUE r13 tentpole 2): one
                # container-tier ChunkedStackBuilder per mesh device
                # assembles that device's shard sub-stack, and the
                # committed sub-arrays stitch into the sharded global
                # with make_array_from_single_device_arrays — mesh cold
                # builds ship the same u16-position/run-span wire as
                # single-device ones instead of a host-dense slab.
                sub_words = (s_pad // self.mesh.n) * rows_p * WORDS_PER_SHARD
                if sub_words >= MIN_CHUNKED_WORDS:
                    try:
                        arr = self._sharded_stream_build(
                            frags, shards, rows_p, s_pad
                        )
                    except Exception as e:  # noqa: BLE001 — degrade to
                        # the dense host pack below, counted + logged:
                        # a stitch/placement failure must serve slow,
                        # never 500 (same contract as the Mosaic paths).
                        self._fallback("mesh_stream", shape, e)
                else:
                    # Per-device share too small to chunk (padding waste
                    # would exceed the wire saving) while a single-device
                    # stack this size WOULD stream — a residual mesh gap,
                    # visible on /metrics rather than silent.
                    self._fallback(
                        "mesh_stream", shape,
                        "per-device sub-stack below MIN_CHUNKED_WORDS",
                    )
            if arr is None:
                host = np.zeros(
                    (s_pad, rows_p, WORDS_PER_SHARD), dtype=np.uint32
                )
                for i, s in enumerate(shards):
                    fr = frags[s]
                    if fr is not None:
                        host[i] = pack_fragment(fr, n_rows=rows_p)
                arr = self._put(host)
            if nbytes >= (64 << 20):
                # Splice warm-up: compile the epoch-update scatter NOW,
                # while the build already costs seconds — the first write
                # of a serving window must not stall on XLA compile (it
                # wedged a whole churn window before this). On one device
                # it compiles from the shapes alone and runs nothing.
                # Under a mesh the shard_map splice program runs once
                # (valid=0 lanes: content unchanged, result discarded).
                if self.mesh is None:
                    self._update_fn(shape)
                else:
                    self._warm_mesh_splice(arr, rows_p)
            # A full build only (not a hit, not a splice): host pack, the
            # upload's enqueue and the splice warm-up. What the device
            # still has queued when this returns is the first launch's
            # wait.
            global_stats.with_tags(f"field:{field_obj.name}").timing(
                "stack_build_seconds", time.perf_counter() - t_build
            )
            self._count_upload("dense", stale, nbytes)
            if view_name == VIEW_STANDARD:
                # Held dense again (the choice moved): the ids go.
                self._drop_unpinned(
                    (index, field_obj.name, VIEW_STANDARD, "rowid")
                )
            return arr, rows_p, vers, tiers

        return self._cached_build(key, fingerprint, build)[:2]

    def _try_incremental(self, stale, shards, min_rows, frags, vers, rows_p, s_pad):
        """Dirty-shard-granular refresh (VERDICT r3 #1): when a write
        epoch touched only a few shards of an already-resident stack,
        re-pack + upload JUST those shard slabs and splice them in with a
        compiled dynamic_update_slice — ~rows_p x 128 KiB per dirty shard
        instead of re-packing and re-shipping the whole (possibly 1 GB)
        stack. The splice returns a NEW device array, so downstream
        caches keyed by array identity (pair/TopN stats) correctly treat
        the update as a fresh write epoch. Returns the updated device
        array, or None when a full rebuild is needed (first build, shape
        change, too many dirty shards). Under a mesh the splice runs
        inside shard_map with per-device slab placement — only the
        owning device applies its slab, no ICI gather
        (_splice_sharded)."""
        if stale is None:
            return None
        old_fp, old_arr, old_rows_p, old_vers = stale[:4]
        if (
            old_arr is None
            or old_vers is None
            or old_rows_p != rows_p
            or old_fp[0] != tuple(shards)
            or len(old_fp) > 2 and old_fp[2] != min_rows
            or old_arr.shape[0] != s_pad
        ):
            return None
        dirty = [i for i in range(len(shards)) if old_vers[i] != vers[i]]
        if not dirty or len(dirty) > max(
            1, len(shards) // self.MAX_INCREMENTAL_FRACTION
        ):
            return None
        if self.mesh is not None:
            try:
                return self._splice_sharded(
                    old_arr, shards, frags, dirty, rows_p
                )
            except Exception as e:  # noqa: BLE001 — a shard_map splice
                # failure (hardware-only compile/VMEM limits) degrades
                # to the full rebuild, counted + logged so the
                # regression is visible instead of shipping as a
                # silently slow correct answer.
                self._fallback(
                    "mesh_splice", (old_arr.shape, len(dirty)), e
                )
                return None
        # Fixed-chunk scatters, chained: each chunk is one upload + one
        # dispatch of the SAME compiled program (compiled at build time —
        # see _update_fn), so no epoch ever pays an XLA compile in
        # the serving path. A short chunk pads by repeating the first
        # dirty slab (duplicate scatter indices with identical payloads
        # are benign). Dispatches pipeline: the chain is async until the
        # caller's readback.
        fn = self._update_fn(old_arr.shape)
        arr = old_arr
        for c0 in range(0, len(dirty), self.UPDATE_CHUNK):
            chunk = dirty[c0 : c0 + self.UPDATE_CHUNK]
            pad = self.UPDATE_CHUNK - len(chunk)
            idx = np.array(chunk + [chunk[0]] * pad, dtype=np.int32)
            slabs = np.zeros(
                (self.UPDATE_CHUNK, rows_p, WORDS_PER_SHARD), dtype=np.uint32
            )
            for j, i in enumerate(chunk):
                fr = frags[shards[i]]
                if fr is not None:
                    slabs[j] = pack_fragment(fr, n_rows=rows_p)
            if pad:
                slabs[len(chunk) :] = slabs[0]
            arr = fn(
                arr,
                jax.device_put(tile_words(slabs), self.device),
                jax.device_put(idx, self.device),
            )
            global_stats.count("stack_update_bytes_total", slabs.nbytes)
            self._count_upload("dense", True, slabs.nbytes)
        global_stats.count("stack_incremental_updates_total")
        global_stats.count("stack_incremental_shards_total", len(dirty))
        return arr

    def _warm_update_fn(self, shape: tuple):
        """The jitted dirty-shard scatter for a stack shape (UPDATE_CHUNK
        slabs a call); _update_fn compiles it ahead of time."""
        fn = self._update_fns.get(shape)
        if fn is None:
            fn = jax.jit(lambda arr, sl, ix: arr.at[ix].set(sl))
            self._update_fns[shape] = fn
        return fn

    def _update_fn(self, shape: tuple, dtype=np.uint32):
        """The dirty-shard scatter for a stack shape, compiled ahead of
        time from the shapes alone. Called at full-build time too (for
        large stacks) so the one-time XLA compile lands during
        build/preheat, not on the first write of a serving window, and
        no warm-up run writes a copy of the stack."""
        key = (tuple(shape), np.dtype(dtype).str)
        fn = self._update_fns.get(key)
        if fn is None:
            chunk = (self.UPDATE_CHUNK,) + tuple(shape[1:])
            fn = self._warm_update_fn(tuple(shape)).lower(
                _sds(tuple(shape), dtype, self.device),
                _sds(chunk, dtype, self.device),
                _sds((self.UPDATE_CHUNK,), np.int32, self.device),
            ).compile()
            self._update_fns[key] = fn
        return fn

    def _mesh_update_fn(self):
        """The shard_map dirty-shard splice (ops/kernels.py
        splice_shard_slabs, ISSUE r13 tentpole 1): every operand sharded
        P('shards'), so each device receives exactly its own slab/index
        lane and applies it locally — the epoch update never moves
        stack bytes over ICI. One jitted wrapper serves every stack
        shape (jit retraces per shape; _warm_mesh_splice fronts the
        compile for large stacks)."""
        fn = self._update_fns.get("mesh")
        if fn is None:
            mesh = self.mesh
            ax = P(mesh.axis)
            fn = jax.jit(
                shard_map(
                    splice_shard_slabs,
                    mesh=mesh.mesh,
                    in_specs=(ax, ax, ax, ax),
                    out_specs=ax,
                    check_vma=False,
                )
            )
            self._update_fns["mesh"] = fn
        return fn

    def _mesh_splice_args(self, slabs, idx, valid):
        """Place one splice round's host operands with the stack's
        shardings (each device gets only its own lane)."""
        mesh = self.mesh
        sh = NamedSharding(mesh.mesh, P(mesh.axis))
        return (
            jax.device_put(tile_words(slabs), sh),
            jax.device_put(idx, sh),
            jax.device_put(valid, sh),
        )

    def _warm_mesh_splice(self, arr, rows_p) -> None:
        """Compile the mesh splice for this stack shape at build time
        (all-invalid lanes: the program executes, content is unchanged,
        the result is discarded) so the first write epoch of a serving
        window never stalls on XLA."""
        n = self.mesh.n
        shape = (n * self.MESH_UPDATE_CHUNK, rows_p, WORDS_PER_SHARD)
        self._mesh_update_fn()(
            arr,
            *self._mesh_splice_args(
                np.zeros(shape, np.uint32),
                np.zeros(shape[0], np.int32),
                np.zeros(shape[0], np.uint32),
            ),
        )

    def _splice_sharded(self, old_arr, shards, frags, dirty, rows_p):
        """Mesh counterpart of the single-device chunk chain: dirty
        shards group by OWNING DEVICE (contiguous blocks of the padded
        shard axis), and each round ships one slab per device — placed
        sharded, so a device's host->HBM wire carries only its own
        dirty slabs — through one dispatch of the shard_map splice.
        Rounds chain until the deepest per-device dirty list drains; a
        single dirty shard costs one round (n_devices slabs of wire,
        all but one of them zero padding) instead of a whole-stack
        rebuild. Returns a NEW sharded array (identity = write-epoch
        token, same contract as the single-device path)."""
        s_pad = old_arr.shape[0]
        n = self.mesh.n
        s_local = s_pad // n
        by_dev: dict[int, list[int]] = {}
        for i in dirty:
            by_dev.setdefault(i // s_local, []).append(i)
        rounds = max(len(v) for v in by_dev.values())
        fn = self._mesh_update_fn()
        c = self.MESH_UPDATE_CHUNK
        arr = old_arr
        for r0 in range(0, rounds, c):
            slabs = np.zeros((n * c, rows_p, WORDS_PER_SHARD), np.uint32)
            idx = np.zeros(n * c, np.int32)
            valid = np.zeros(n * c, np.uint32)
            for d, items in by_dev.items():
                for j in range(c):
                    if r0 + j >= len(items):
                        break
                    i = items[r0 + j]
                    fr = frags[shards[i]]
                    if fr is not None:
                        slabs[d * c + j] = pack_fragment(fr, n_rows=rows_p)
                    idx[d * c + j] = i - d * s_local
                    valid[d * c + j] = 1
            arr = fn(arr, *self._mesh_splice_args(slabs, idx, valid))
            global_stats.count("stack_update_bytes_total", slabs.nbytes)
            self._count_upload("dense", True, slabs.nbytes)
        global_stats.count("stack_incremental_updates_total")
        global_stats.count("stack_incremental_shards_total", len(dirty))
        return arr

    def _sharded_stream_build(self, frags, shards, rows_p, s_pad):
        """Per-device container-tier sub-stack assembly (ISSUE r13
        tentpole 2): device d's ChunkedStackBuilder receives the shard
        positions [d*s_local, (d+1)*s_local) — missing fragments and
        the zero-slab padding tail are skip()s — and the finished
        committed sub-arrays stitch into the NamedSharding(P('shards'))
        global without any cross-device traffic."""
        mesh = self.mesh
        n = mesh.n
        s_local = s_pad // n
        slab_words = rows_p * WORDS_PER_SHARD
        shape_local = stack_shape(s_local, rows_p)
        builders = [
            ChunkedStackBuilder(dev, shape_local) for dev in mesh.devices
        ]
        for pos in range(s_pad):
            b = builders[pos // s_local]
            fr = frags.get(shards[pos]) if pos < len(shards) else None
            if fr is not None:
                b.feed_fragment(fr, rows_p)
            else:
                b.skip(slab_words)
        parts = [b.finish() for b in builders]
        return jax.make_array_from_single_device_arrays(
            stack_shape(s_pad, rows_p),
            NamedSharding(mesh.mesh, P(mesh.axis)),
            parts,
        )

    def get_packed(self, index: str, field_obj, shards: tuple[int, ...],
                   view_name: str = VIEW_STANDARD):
        """(stack uint32[S, rows_p, PACKED_WORDS], rows_p, what
        `packed_extra` keeps beside it) of a narrow field, or (None, 0,
        None) where the field is not: chosen from what the fragments
        show (ISSUE 36). A field is held packed when every bit of it
        lies in a shard's first PACKED_BITS columns and the packed stack
        is admitted (budget); whether its dense stack is
        resident too, or could be, does not enter, so the same field is
        packed beside any neighbour and under any budget that holds it.
        Packed it takes at most half of what it takes dense, and from
        1,024 rows on a 256th. Rows are on the sublane axis, the words
        in use on the lanes: a program sweeps every row in one pass
        (ops/kernels.py tanimoto_topn).

        Cached, counted in the ledger and evicted like any stack, under
        the key (index, field, view, "packed"), and as fresh: the view's
        generation is the fingerprint, a moved one re-derives the entry
        (a point-write epoch splices the rows its bit ops name, anything
        else re-packs), so a Set is in the next read. The verdict "not
        packed" is an entry too, with no array: the walk that found a
        wide bit is not repeated until a write moves the generation. One
        device only: under a mesh the field is not packed; nor over no
        shard at all (`?shards=`), which the host answers."""
        v = field_obj.view(view_name)
        if v is None or self.mesh is not None or not shards:
            return None, 0, None
        fingerprint = (tuple(shards), v.generation)
        key = (index, field_obj.name, view_name, "packed")

        def build(stale):
            t_build = time.perf_counter()
            frags = [v.fragment(s) for s in shards]
            n_rows = max(
                [fr.max_row_id + 1 for fr in frags if fr is not None] + [1]
            )
            rows_p = packed_rows(n_rows)
            budget = self.budget()
            if (
                budget is not None
                and len(shards) * rows_p * PACKED_WORDS * 4 > budget
            ):
                return None, 0, None, None
            nbytes = len(shards) * rows_p * PACKED_WORDS * 4
            if stale is not None and stale[0][0] != tuple(shards):
                self._drop_unpinned(key)
                stale = None
            stale_arr = stale[1] if stale is not None else None
            updated = None
            if stale_arr is not None and self.make_room(
                nbytes + self.PACKED_UPDATE_ROWS * PACKED_WORDS * 4, keep=key
            ):
                updated = self._packed_splice(stale, shards, frags, rows_p)
            if updated is not None:
                arr, vers = updated
                extra = (
                    stale[4] if arr is stale_arr
                    else self.packed_extra(arr, stale_arr)
                )
                return arr, rows_p, vers, None, extra
            host = np.zeros((len(shards), rows_p, PACKED_WORDS), np.uint32)
            vers = []
            for i, fr in enumerate(frags):
                if fr is None:
                    vers.append(None)
                    continue
                with fr.lock:
                    # Read BEFORE the pack: the content may be newer
                    # than the version says, never older, and the row
                    # splice sets whole rows again (idempotent).
                    vers.append((fr.uid, fr.version))
                slab = pack_fragment_packed(fr, rows_p)
                if slab is None:
                    return None, 0, None, None
                host[i] = slab
            if stale_arr is not None:
                global_stats.count("stack_full_rebuilds_total")
            if not self.make_room(host.nbytes, keep=key):
                return None, 0, None, None
            arr = jax.device_put(host, self.device)
            self._count_upload("packed", stale_arr, host.nbytes)
            # Compile the row splice now, beside a build that already
            # costs seconds, and not under a window's first write.
            self._packed_splice_fn(arr.shape)
            global_stats.with_tags(f"field:{field_obj.name}").timing(
                "stack_build_seconds", time.perf_counter() - t_build
            )
            global_stats.with_tags(
                f"index:{index}", f"field:{field_obj.name}"
            ).gauge("stack_row_words", PACKED_WORDS)
            return (
                arr, rows_p, tuple(vers), None,
                self.packed_extra(arr, stale_arr),
            )

        return self._cached_build(key, fingerprint, build, keep_none=True)

    def _packed_update_fn(self):
        """The row splice of a packed stack: PACKED_UPDATE_ROWS (shard,
        row) places set to as many rows of words. Shard 0's row 0
        repeated pads a short epoch (equal payloads: benign)."""
        fn = self._update_fns.get("packed")
        if fn is None:
            fn = jax.jit(
                lambda arr, si, ri, rows: arr.at[si, ri].set(rows)
            )
            self._update_fns["packed"] = fn
        return fn

    def _packed_splice_fn(self, shape: tuple):
        """_packed_update_fn for one stack shape, compiled ahead of time
        from the shapes alone: no warm-up run writes a copy."""
        key = ("packed", tuple(shape))
        fn = self._update_fns.get(key)
        if fn is None:
            n = self.PACKED_UPDATE_ROWS
            fn = self._packed_update_fn().lower(
                _sds(tuple(shape), np.uint32, self.device),
                _sds((n,), np.int32, self.device),
                _sds((n,), np.int32, self.device),
                _sds((n, PACKED_WORDS), np.uint32, self.device),
            ).compile()
            self._update_fns[key] = fn
        return fn

    def _packed_splice(self, stale, shards, frags, rows_p):
        """(new stack, versions) where the epoch since `stale` is point
        writes to few rows, all narrow: those rows are packed again and
        set in place (a NEW device array: identity is the write epoch, as
        for a dense stack). None where a full re-pack is needed: first
        build, changed shape or shard set, a fragment recreated, a bulk
        write, more rows than a splice ships, a bit beyond the packed
        width (the re-pack then finds the field no longer narrow)."""
        if stale is None:
            return None
        old_fp, old_arr, old_rows_p, old_vers = stale[:4]
        if (
            old_arr is None or old_vers is None or old_rows_p != rows_p
            or old_fp[0] != tuple(shards)
        ):
            return None
        places: list[tuple[int, int]] = []
        vers = []
        for i, fr in enumerate(frags):
            was = old_vers[i]
            if fr is None:
                if was is not None:
                    return None
                vers.append(None)
                continue
            with fr.lock:
                now = (fr.uid, fr.version)
            vers.append(now)
            if now == was:
                continue
            if was is None or was[0] != now[0]:
                return None
            ops = fr.bit_ops_between(was[1], now[1])
            if ops is None:
                return None
            for r in sorted({op[1] for op in ops}):
                if r >= rows_p:
                    return None
                places.append((i, r))
        if not places:
            return old_arr, tuple(vers)
        if len(places) > self.PACKED_UPDATE_ROWS:
            return None
        rows = np.zeros((self.PACKED_UPDATE_ROWS, PACKED_WORDS), np.uint32)
        for i in sorted({p[0] for p in places}):
            mine = [j for j, p in enumerate(places) if p[0] == i]
            # Rows as they are NOW, which may be newer than `vers` says:
            # setting a row again is idempotent, so the next epoch's
            # splice of the same rows costs a dispatch and no error.
            packed = pack_rows_packed(frags[i], [places[j][1] for j in mine])
            if packed is None:
                return None
            rows[mine] = packed
        # A short epoch is padded with its first place again (equal
        # payloads at equal places: benign).
        rows[len(places):] = rows[0]
        places += [places[0]] * (self.PACKED_UPDATE_ROWS - len(places))
        arr = self._packed_splice_fn(old_arr.shape)(
            old_arr,
            np.array([p[0] for p in places], np.int32),
            np.array([p[1] for p in places], np.int32),
            rows,
        )
        global_stats.count("stack_update_bytes_total", rows.nbytes)
        global_stats.count("stack_incremental_updates_total")
        self._count_upload("packed", True, rows.nbytes)
        return arr, tuple(vers)

    # -- the row-id layout ---------------------------------------------------

    def rowid_field(self, index: str, field_obj, shards) -> bool:
        """Whether a field is held as row ids over `shards`: a mutex
        field, on one device, among those the layout choice names for
        the index (_choose_rowid). O(1) while no write has moved the
        process's generation watermark."""
        if (
            self.holder is None or self.mesh is not None or not shards
            or field_obj.options.type != FIELD_TYPE_MUTEX
        ):
            return False
        key = (index, tuple(shards))
        mark = generation_watermark()
        with self._lock:
            got = self._layouts.get(key)
            if got is None or got[0] != mark:
                if len(self._layouts) >= self.MAX_LAYOUT_CHOICES:
                    self._layouts.clear()
                got = (mark, self._choose_rowid(index, key[1]))
                self._layouts[key] = got
        return field_obj.name in got[1]

    def _choose_rowid(self, index: str, shards: tuple) -> frozenset:
        """The mutex fields of an index held as row ids over `shards`:
        none while every view's dense stack would fit the budget
        together; else the tallest mutex fields, tallest first, until
        they do (or none is left). A field qualifies where its ids fit
        uint16 and take fewer bytes than its dense rows, and no build
        found a column in two of its rows at the view's generation."""
        budget = self.budget()
        idx = self.holder.index(index)
        if budget is None or idx is None:
            return frozenset()
        s_pad = self._pad_shards(len(shards))
        total = 0
        tall = []
        for name, f in list(idx.fields.items()):
            for vn, v in list(f.views.items()):
                n_rows = 1
                if vn == bsi_view_name(name) and f.options.type == FIELD_TYPE_INT:
                    n_rows = BSI_OFFSET_BIT + f.options.bit_depth
                for sh in shards:
                    fr = v.fragment(sh)
                    if fr is not None:
                        n_rows = max(n_rows, fr.max_row_id + 1)
                dense = s_pad * _padded_rows(n_rows) * WORDS_PER_SHARD * 4
                total += dense
                dt = rowid_dtype(n_rows)
                if (
                    vn != VIEW_STANDARD or f.options.type != FIELD_TYPE_MUTEX
                    or dt is None
                    or self._rowid_refused.get((index, name)) == v.generation
                ):
                    continue
                ids = s_pad * SHARD_WIDTH * np.dtype(dt).itemsize
                if ids < dense:
                    tall.append((dense, name, ids))
        chosen = []
        for dense, name, ids in sorted(tall, reverse=True):
            if total <= budget:
                break
            chosen.append(name)
            total -= dense - ids
        return frozenset(chosen)

    def get_rowid(self, index: str, field_obj, shards: tuple[int, ...]):
        """(ids uint8|uint16[S, WORD_LINES, 32, WORD_LANES], rows) of a
        mutex field held as row ids (ops/blocks.py rowid_shape), or
        (None, 0) where it cannot be: a column in two rows (the field is
        then held dense until a write moves its generation), more rows
        than uint16 holds, or no room under the budget. Built from the
        fragments one numpy pass a shard; cached, ledgered, pinned and
        evicted like any stack under (index, field, view, "rowid"), and
        as fresh: the view's generation is the fingerprint, and a moved
        one re-packs the dirty shards' ids and splices them in (a new
        array), or re-packs all of them."""
        v = field_obj.view(VIEW_STANDARD)
        if v is None or not shards or self.mesh is not None:
            return None, 0
        fingerprint = (tuple(shards), v.generation)
        key = (index, field_obj.name, VIEW_STANDARD, "rowid")

        def refuse(gen):
            with self._lock:
                self._rowid_refused[(index, field_obj.name)] = gen
                self._layouts.clear()
            return None, 0, None, None

        def build(stale):
            t_build = time.perf_counter()
            gen = v.generation
            frags = [v.fragment(sh) for sh in shards]
            vers = []
            n_rows = 1
            for fr in frags:
                if fr is None:
                    vers.append(None)
                    continue
                with fr.lock:
                    vers.append((fr.uid, fr.version))
                n_rows = max(n_rows, fr.max_row_id + 1)
            vers = tuple(vers)
            dt = rowid_dtype(n_rows)
            if dt is None:
                return refuse(gen)
            shape = rowid_shape(len(shards))
            nbytes = int(np.prod(shape)) * np.dtype(dt).itemsize
            if stale is not None and stale[1] is not None and (
                stale[0][0] != tuple(shards) or stale[1].dtype != dt
            ):
                self._drop_unpinned(key)
                stale = None
            none = np.iinfo(dt).max

            def slab(fr):
                if fr is None:
                    return np.full(shape[1:], none, dt)
                return pack_fragment_rowid(fr, dt)

            old = stale[1] if stale is not None else None
            if old is not None and stale[3] is not None:
                dirty = [i for i, (a, b) in enumerate(zip(stale[3], vers))
                         if a != b]
                chunk = self.UPDATE_CHUNK
                if dirty and len(dirty) <= max(
                    1, len(shards) // self.MAX_INCREMENTAL_FRACTION
                ) and self.make_room(
                    nbytes + chunk * int(np.prod(shape[1:])) * old.dtype.itemsize,
                    keep=key,
                ):
                    fn = self._update_fn(old.shape, old.dtype)
                    arr = old
                    for c0 in range(0, len(dirty), chunk):
                        part = dirty[c0:c0 + chunk]
                        part = part + [part[0]] * (chunk - len(part))
                        slabs = [slab(frags[i]) for i in part]
                        if any(sl is None for sl in slabs):
                            return refuse(gen)
                        slabs = np.stack(slabs)
                        arr = fn(arr, jax.device_put(slabs, self.device),
                                 jax.device_put(np.array(part, np.int32),
                                                self.device))
                        self._count_upload("rowid", True, slabs.nbytes)
                    global_stats.count("stack_incremental_updates_total")
                    global_stats.count("stack_incremental_shards_total",
                                       len(dirty))
                    return arr, n_rows, vers, None
                if not dirty:
                    return old, n_rows, vers, None
            host = np.empty(shape, dt)
            for i, fr in enumerate(frags):
                sl = slab(fr)
                if sl is None:
                    return refuse(gen)
                host[i] = sl
            if old is not None:
                global_stats.count("stack_full_rebuilds_total")
            if not self.make_room(nbytes, keep=key):
                return None, 0, None, None
            arr = jax.device_put(host, self.device)
            self._count_upload("rowid", old, nbytes)
            global_stats.with_tags(f"field:{field_obj.name}").timing(
                "stack_build_seconds", time.perf_counter() - t_build
            )
            # Held as ids now: the dense stack, if any, goes.
            self._drop_unpinned((index, field_obj.name, VIEW_STANDARD))
            return arr, n_rows, vers, None

        arr, rows, _ = self._cached_build(key, fingerprint, build)
        return arr, rows

    def get_row(self, index: str, field_obj, shards: tuple[int, ...],
                view_name: str, row_id: int):
        """[S_pad, 1, W/128, 128] single-row stack — the on-demand page
        for fields whose full stack exceeds the HBM budget (VERDICT r2 #8: row
        paging instead of whole-stack CPU fallback). Cached and
        LRU-evicted like whole stacks; each entry costs S_pad x 128 KiB.
        None where the page cannot be admitted under the budget."""
        v = field_obj.view(view_name)
        fingerprint = (tuple(shards), v.generation if v is not None else -1)
        key = (index, field_obj.name, view_name, "row", row_id)

        def build(stale):
            s_pad = self._pad_shards(len(shards))
            if not self.make_room(s_pad * WORDS_PER_SHARD * 4, keep=key):
                return None, 1, None, None
            host = np.zeros((s_pad, 1, WORDS_PER_SHARD), dtype=np.uint32)
            for i, s in enumerate(shards):
                fr = v.fragment(s) if v is not None else None
                if fr is not None and row_id <= fr.max_row_id:
                    host[i, 0] = pack_row(fr, row_id)
            global_stats.count("hbm_page_uploads_total")
            global_stats.count("hbm_page_bytes_total", host.nbytes)
            self._count_upload("dense", "page", host.nbytes)
            return self._put(host), 1, None, None

        return self._cached_build(key, fingerprint, build)[0]

    def get_with_versions(self, index: str, field_obj, shards: tuple[int, ...],
                          view_name: str = VIEW_STANDARD, min_rows: int = 1):
        """get() plus the per-shard (uid, version) tuple the returned
        stack was packed from — the write-epoch diff key for host-side
        incremental stats maintenance (which shards changed between two
        stack identities)."""
        block, rows_p = self.get(index, field_obj, shards, view_name, min_rows)
        with self._lock:
            ent = self._entries.get((index, field_obj.name, view_name))
            vers = ent[3] if ent is not None and ent[1] is block else None
        return block, rows_p, vers

    def refresh_stale(self) -> int:
        """One windowed flush round (ISSUE r19 tentpole 2): re-run the
        build for every resident stack whose view generation moved since
        upload, through the same get() path — i.e. the PR 12 incremental
        splice — so the dirty shards a window accumulated flush as one
        per-device splice round and reads landing after the window find
        a fresh stack instead of paying the splice inline. Keeping the
        per-window dirty set small is also what keeps the splice on its
        incremental path (stack_full_rebuilds_total stays flat under
        sustained churn). Returns the number of stacks refreshed."""
        with self._lock:
            for k in list(self._refresh_args):
                if k not in self._entries:
                    del self._refresh_args[k]
            work = list(self._refresh_args.items())
        n = 0
        for key, (field_obj, shards, view_name, min_rows) in work:
            try:
                v = field_obj.view(view_name)
            except Exception:  # lint: allow-except-exception(field deleted mid-walk: the entry prunes on the next round; nothing to count)
                continue
            gen = v.generation if v is not None else -1
            with self._lock:
                ent = self._entries.get(key)
                if ent is None or ent[0] == (tuple(shards), gen, min_rows):
                    continue  # evicted, or already fresh
            _REFRESHER.active = True
            try:
                self.get(key[0], field_obj, shards, view_name, min_rows)
                n += 1
            except Exception:  # lint: allow-except-exception(flusher crash barrier: a failed background refresh must never kill the loop; the read path's inline barrier still guarantees freshness)
                pass
            finally:
                _REFRESHER.active = False
        return n

    def _cached_build(self, key: tuple, fingerprint: tuple, build,
                      keep_none: bool = False):
        """Shared hit/latch/build/evict protocol for stack and row-page
        entries. build(stale) receives the stale entry for this key (or
        None) so it can refresh incrementally, and returns
        (device_array_or_None, rows_p, shard_versions, tier_words) and,
        where it keeps something beside the array, a fifth `extra`.
        Returns (array, rows_p, extra). A None array means 'cannot be
        resident' and is returned uncached; with keep_none it is kept as
        the key's entry instead (no bytes, no ledger line), so that a
        verdict that cost a walk is reached once a fingerprint.
        Concurrent misses for one key build once (losers wait on the
        winner's latch, then re-check). Inside a pin scope (pinned())
        the entry handed back is pinned; a build is the span
        `pilosa.stack_build` with its layout."""
        while True:
            hit = None
            nbytes = 0
            with self._lock:
                cached = self._entries.get(key)
                if cached is not None and cached[0] == fingerprint:
                    # LRU touch + heat bump (ISSUE 18: bare arithmetic
                    # on the ledger entry already in hand — the hot hit
                    # path allocates nothing new).
                    self._entries[key] = self._entries.pop(key)
                    led = self._ledger.get(key)
                    if led is not None:
                        self._bump_heat(led)
                        nbytes = led["bytes"]
                    if cached[1] is not None:
                        self._pin(key)
                    hit = (cached[1], cached[2], cached[4])
                else:
                    latch = self._building.get(key)
                    if latch is None:
                        self._building[key] = threading.Event()
                        break
            if hit is not None:
                if hit[0] is None:
                    return hit
                # Reuse-distance sample OUTSIDE the ledger lock: the
                # sampler rejects in one hash compare; admitted samples
                # take the estimator's own lock only.
                self._record_reuse(key, nbytes)
                return hit
            # Another thread is packing this entry: wait, then re-check —
            # its fingerprint usually matches ours (same live fragments).
            latch.wait()
        try:
            with trace_span("pilosa.stack_build", layout=_layout_of(key)):
                arr, rows_p, vers, tiers, *extra = build(cached)
            extra = extra[0] if extra else None
            if arr is None:
                if keep_none:
                    with self._lock:
                        self._entries.pop(key, None)
                        self._ledger.pop(key, None)
                        self._entries[key] = (fingerprint, None, rows_p, vers, None)
                return None, rows_p, None
            with self._lock:
                self._entries.pop(key, None)
                self._entries[key] = (fingerprint, arr, rows_p, vers, extra)
                self._ledger_upload(key, arr, tiers)
                self._pin(key)
                self._evict(keep=key)
            # Misses are references too: without them the reuse stream
            # would be hits-only and every distance would look resident.
            self._record_reuse(key, arr.nbytes)
            return arr, rows_p, extra
        finally:
            with self._lock:
                self._building.pop(key).set()

    def _ledger_upload(self, key: tuple, arr, tiers) -> None:
        """Record a (re)upload in the HBM ledger (caller holds _lock).
        Access stats survive re-uploads of the same key — coldness is a
        property of the serving pattern, not of the write churn that
        forced the refresh. tiers=None with an unchanged byte size keeps
        the previous tier split (incremental splices don't re-attribute;
        the mix re-trues on the next full rebuild); otherwise the bytes
        default to the dense tier."""
        nbytes = int(arr.nbytes)
        self._upload_epoch += 1
        led = self._ledger.get(key)
        if led is None:
            led = {"access_count": 0, "uploads": 0}
            self._ledger[key] = led
        if tiers is None and led.get("bytes") == nbytes and "tier_bytes" in led:
            tier_bytes = led["tier_bytes"]
        else:
            array_b = min(int(tiers[0]) * 4, nbytes) if tiers else 0
            run_b = min(int(tiers[1]) * 4, nbytes - array_b) if tiers else 0
            tier_bytes = {
                "dense": nbytes - array_b - run_b,
                "array": array_b,
                "run": run_b,
            }
        led.update(
            bytes=nbytes,
            tier_bytes=tier_bytes,
            upload_epoch=self._upload_epoch,
        )
        led["uploads"] += 1
        self._bump_heat(led)

    def _bump_heat(self, led: dict) -> None:
        """Decayed-frequency heat bump (caller holds _lock): decay the
        stored heat by 2^(-idle/half_life) — computed LAZILY from the
        last-access stamp, so idle entries cost nothing between
        touches — then add this access. Bare float arithmetic on the
        ledger entry; no allocation, no extra lookup (ISSUE 18's
        near-zero-idle-cost contract for the block-fetch path)."""
        now = time.monotonic()
        heat = led.get("heat", 0.0)
        if heat:
            heat *= 2.0 ** ((led["last_access"] - now) / self.heat_half_life)
        led["heat"] = heat + 1.0
        led["access_count"] += 1
        led["last_access"] = now

    def _record_reuse(self, key: tuple, nbytes: int) -> None:
        if self.reuse.record(key, nbytes):
            global_stats.count("reuse_distance_samples_total")

    def peek(self, index: str, field_name: str,
             view_name: str = VIEW_STANDARD):
        """The resident stack for a key, or None — never builds (preheat
        program warming must not trigger uploads/evictions of its own,
        especially after stopping on a full budget)."""
        with self._lock:
            ent = self._entries.get((index, field_name, view_name))
            return ent[1] if ent is not None else None

    def make_room(self, nbytes: int, keep: Optional[tuple] = None) -> bool:
        """Evict least-recently-used entries until `nbytes` more fit under
        the budget, BEFORE an upload (a whole stack and its build's
        transient, a splice's new array, a page). `keep` and pinned
        entries stay. Where what is left to evict is pinned by other
        queries, wait for their pins to clear (hbm_pin_wait_seconds), at
        most PIN_WAIT_SECONDS, and not at all where every query holding
        pins waits here too (none of them would unpin). False where the
        bytes cannot be admitted: the caller does not upload (no
        over-allocation)."""
        if self.budget() is None:
            return True
        t0 = None
        mine = getattr(self._pin_local, "keys", None)
        stalled = False
        try:
            while True:
                self._unpinned.clear()
                with self._lock:
                    self._measure_other()
                    budget = self.budget()
                    resident = self.resident_bytes()
                    waiting = False
                    for k in list(self._entries):
                        if resident + nbytes <= budget:
                            break
                        if k == keep:
                            continue
                        if self._pins.get(k):
                            waiting = waiting or self._pinned_by_others(k)
                            continue
                        resident -= self._entry_bytes(k)
                        self._drop(k)
                    if resident + nbytes <= budget:
                        return True
                    if waiting and mine and not stalled:
                        stalled = True
                        self._stalled += 1
                    if self._stalled >= self._holders:
                        waiting = False
                if not waiting:
                    return False
                if t0 is None:
                    t0 = time.perf_counter()
                elif time.perf_counter() - t0 > self.PIN_WAIT_SECONDS:
                    return False
                self._unpinned.wait(0.05)
        finally:
            if stalled:
                with self._lock:
                    self._stalled -= 1
            if t0 is not None:
                global_stats.timing("hbm_pin_wait_seconds",
                                    time.perf_counter() - t0)

    def _evict(self, keep: tuple) -> None:
        """After an upload: evict unpinned entries, least recently used
        first, while the entries exceed the budget (make_room has made
        the room before; this catches a budget that shrank since)."""
        budget = self.budget()
        if budget is None:
            return
        resident = self.resident_bytes()
        for k in list(self._entries):
            if resident <= budget:
                break
            if k == keep or self._pins.get(k):
                continue
            resident -= self._entry_bytes(k)
            self._drop(k)

    def _drop_unpinned(self, key: tuple) -> None:
        """Evict `key` now unless a query holds it pinned."""
        with self._lock:
            if key in self._entries and not self._pins.get(key):
                self._drop(key)

    def _entry_bytes(self, key: tuple) -> int:
        """Caller holds _lock."""
        arr = self._entries[key][1]
        return int(arr.nbytes) if arr is not None else 0

    def _build_transient(self, nbytes: int, rows_p: int) -> int:
        """Device bytes a full dense build holds beside the stack it
        makes: the chunked builder's staging where it streams
        (ChunkedStackBuilder.transient_bytes), and under a mesh, where
        the splice program is warmed by a run (stacks of 64 MiB and
        more), the copy that run writes and its round of slabs."""
        out = 0
        words = nbytes // 4
        if words >= MIN_CHUNKED_WORDS:
            out += ChunkedStackBuilder.transient_bytes((words,))
        if self.mesh is not None and nbytes >= (64 << 20):
            out += nbytes + self.mesh.n * rows_p * WORDS_PER_SHARD * 4
        return out

    @staticmethod
    def _count_upload(layout: str, why, nbytes: int) -> None:
        """hbm_upload_bytes_total{layout, why}: `why` is "page", or the
        stale entry a build replaced (None: a miss; else a refresh)."""
        if not isinstance(why, str):
            why = "miss" if why is None else "refresh"
        global_stats.with_tags(f"layout:{layout}", f"why:{why}").count(
            "hbm_upload_bytes_total", int(nbytes)
        )

    def _drop(self, victim: tuple) -> None:
        """Caller holds _lock. An entry with no array (a packed key's
        "not packed") goes in its turn and frees nothing: not counted."""
        if self._entries.pop(victim)[1] is not None:
            self.evictions += 1
        self._ledger.pop(victim, None)

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(
                int(e[1].nbytes)
                for e in self._entries.values() if e[1] is not None
            )

    def layout_bytes(self) -> dict[str, int]:
        """Resident bytes by layout (dense, with row pages; packed;
        rowid): the split behind hbm_layout_bytes{layout}, summing to
        resident_bytes()."""
        out = {"dense": 0, "packed": 0, "rowid": 0}
        with self._lock:
            for key, ent in self._entries.items():
                if ent[1] is not None:
                    out[_layout_of(key)] += int(ent[1].nbytes)
        return out

    def tier_bytes(self) -> dict[str, int]:
        """Resident bytes by representation tier; the dict sums exactly
        to resident_bytes() (each ledger entry's tiers sum to its dense
        device footprint)."""
        out = {"dense": 0, "array": 0, "run": 0}
        with self._lock:
            for key in self._entries:
                led = self._ledger.get(key)
                if led is None:
                    continue
                for t, b in led["tier_bytes"].items():
                    out[t] += b
        return out

    def ledger(self) -> list[dict]:
        """The per-entry HBM ledger, coldest first — i.e. the LRU
        eviction-candidate order (served at /debug/hbm). _entries is the
        LRU (oldest-touched iterates first), so the listing order IS the
        order _evict would take victims."""
        # Idle arithmetic runs on the monotonic clock; ONE wall read maps
        # idle ages onto the operator-facing lastAccess epoch stamps.
        now = time.monotonic()
        wall = time.time()  # lint: allow-monotonic-time(lastAccess is an operator-facing epoch display; idleSeconds math is monotonic)
        out = []
        with self._lock:
            for key, (_, arr, rows_p, *_) in self._entries.items():
                led = self._ledger.get(key)
                if led is None:
                    continue
                ent = {
                    "index": key[0],
                    "field": key[1],
                    "view": key[2],
                    "bytes": led["bytes"],
                    "tierBytes": dict(led["tier_bytes"]),
                    "rows": rows_p,
                    "uploadEpoch": led["upload_epoch"],
                    "uploads": led["uploads"],
                    "accessCount": led["access_count"],
                    "lastAccess": round(wall - (now - led["last_access"]), 3),
                    "idleSeconds": round(now - led["last_access"], 3),
                }
                if len(key) > 3 and key[3] == "row":
                    ent["row"] = key[4]
                elif len(key) > 3:
                    ent["layout"] = key[3]
                out.append(ent)
        return out

    def heat_snapshot(self, entries: int = -1) -> dict:
        """Per-entry decayed-frequency heat (decayed to NOW, hottest
        first) plus the per-tier heat rollup behind the
        hbm_access_heat{tier} gauges — an entry's heat splits over
        tiers by its tier-byte fractions, so the tier series answer
        'is the hot set dense or container-tiered' (the pager's
        readmission-format question) rather than double-counting.
        `entries`: -1 = all, 0 = rollup only (the poll-loop gauge path
        skips building the per-entry dicts), N > 0 = hottest N."""
        now = time.monotonic()
        hl = self.heat_half_life
        tier_heat = {"dense": 0.0, "array": 0.0, "run": 0.0}
        ents: list[dict] = []
        with self._lock:
            for key in self._entries:
                led = self._ledger.get(key)
                if led is None:
                    continue
                heat = led.get("heat", 0.0) * 2.0 ** (
                    (led["last_access"] - now) / hl
                )
                b = led["bytes"] or 1
                for t, tb in led["tier_bytes"].items():
                    tier_heat[t] += heat * (tb / b)
                if entries == 0:
                    continue
                ent = {
                    "index": key[0],
                    "field": key[1],
                    "view": key[2],
                    "bytes": led["bytes"],
                    "heat": round(heat, 4),
                    "accessCount": led["access_count"],
                    "idleSeconds": round(now - led["last_access"], 3),
                }
                if len(key) > 3 and key[3] == "row":
                    ent["row"] = key[4]
                ents.append(ent)
        ents.sort(key=lambda e: e["heat"], reverse=True)
        if entries > 0:
            ents = ents[:entries]
        return {
            "halfLifeSeconds": hl,
            "tierHeat": {t: round(v, 4) for t, v in tier_heat.items()},
            "entries": ents,
        }

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._ledger.clear()


# ---------------------------------------------------------------------------
# trace-time evaluation of a spec tree
# ---------------------------------------------------------------------------


def _where(cond, a, b):
    return jnp.where(cond, a, b)


def _bsi_slabs(block, depth):
    """exists/sign/plane slabs from a stacked BSI view block."""
    exists = block[:, BSI_EXISTS_BIT]
    sign = block[:, BSI_SIGN_BIT]
    planes = [block[:, BSI_OFFSET_BIT + i] for i in range(depth)]
    return exists, sign, planes


def _lt_unsigned(filt, planes, bits, depth, allow_eq):
    """Traced-predicate port of fragment.rangeLTUnsigned (fragment.go:1440)
    with the documented strict-<0 fix (see core/fragment.py:481)."""
    zeros = jnp.zeros_like(filt)
    keep = zeros
    lz = jnp.bool_(True)
    if not allow_eq:
        zero_pred = jnp.bool_(True)
        for i in range(depth):
            zero_pred = zero_pred & (bits[i] == 0)
    for i in range(depth - 1, -1, -1):
        plane = planes[i]
        bit = bits[i] != 0
        skip = lz & ~bit
        if i == 0 and not allow_eq:
            res = _where(skip, filt & ~plane, _where(bit, filt & ~(plane & ~keep), keep))
            return _where(zero_pred, zeros, res)
        new_filt = _where(skip, filt & ~plane, _where(bit, filt, filt & ~(plane & ~keep)))
        if i > 0:
            keep = _where(~skip & bit, keep | (filt & ~plane), keep)
        filt = new_filt
        lz = lz & ~bit
    if not allow_eq:
        return _where(zero_pred, zeros, filt)
    return filt


def _gt_unsigned(filt, planes, bits, depth, allow_eq):
    """Traced-predicate port of fragment.rangeGTUnsigned (fragment.go:1471)."""
    keep = jnp.zeros_like(filt)
    for i in range(depth - 1, -1, -1):
        plane = planes[i]
        bit = bits[i] != 0
        if i == 0 and not allow_eq:
            return _where(bit, keep, filt & ~((filt & ~plane) & ~keep))
        new_filt = _where(bit, filt & ~((filt & ~plane) & ~keep), filt)
        if i > 0:
            keep = _where(bit, keep, keep | (filt & plane))
        filt = new_filt
    return filt


def _between_unsigned(filt, planes, lo_bits, hi_bits, depth):
    """Traced-predicate port of fragment.rangeBetweenUnsigned (:1504)."""
    keep1 = jnp.zeros_like(filt)
    keep2 = jnp.zeros_like(filt)
    for i in range(depth - 1, -1, -1):
        plane = planes[i]
        b1 = lo_bits[i] != 0
        b2 = hi_bits[i] != 0
        new_filt = _where(b1, filt & ~((filt & ~plane) & ~keep1), filt)
        if i > 0:
            keep1 = _where(b1, keep1, keep1 | (filt & plane))
        filt = new_filt
        new_filt = _where(b2, filt, filt & ~(plane & ~keep2))
        if i > 0:
            keep2 = _where(b2, keep2 | (filt & ~plane), keep2)
        filt = new_filt
    return filt


def _eq_slab(exists, sign, planes, bits, depth, neg):
    b = (exists & sign) if neg else (exists & ~sign)
    for i in range(depth - 1, -1, -1):
        bit = bits[i] != 0
        b = _where(bit, b & planes[i], b & ~planes[i])
    return b


def _shift_slab(slab, n: int):
    """Shift all bits up by n within each shard slab (little-endian bit
    order within uint32 words). Bits crossing the shard boundary drop,
    matching segment-local Row.Shift (core/row.py:77). A word shift
    crosses the slab's 128-word lines, so the slab is shifted flat,
    [S, W]: on the device that is one relayout of the slab each way,
    which a Shift, a rare call, may pay."""
    if n == 0:
        return slab
    slab = flat_words(slab)
    s_words, s_bits = divmod(n, 32)
    W = slab.shape[-1]
    pad = [(0, 0)] * (slab.ndim - 1)

    def word_shifted(k):
        if k >= W:
            return jnp.zeros_like(slab)
        return jnp.pad(slab, pad + [(k, 0)])[..., :W]

    lo = word_shifted(s_words)
    if s_bits == 0:
        return tile_words(lo)
    hi = word_shifted(s_words + 1)
    return tile_words(
        (lo << np.uint32(s_bits)) | (hi >> np.uint32(32 - s_bits))
    )


def _eval_spec(spec, blocks_it, scalars_it):
    """Trace-time recursive evaluation of a tree spec over [S, W/128, 128]
    slabs; row ids, masks, and predicate bits are traced scalars/vectors,
    so one compiled program serves any values of the same tree shape. Both
    iterators are consumed in the exact order _build emitted. Batched
    (multi-query) execution scans this same evaluation over the query
    axis (see the count_batch program).

    The row leaf is a plain slice of the resident stack along an axis
    the device does not tile, which XLA fuses into whatever reads the
    slab: no program writes a row-sized temporary
    (tests/test_chip_compile.py test_count_programs_read_rows_in_place).
    """
    tag = spec[0]
    if tag == "@":
        # ("@", order, tree): the tree's leaves read the distinct blocks
        # in `order` (TPUBackend._assemble).
        distinct = list(blocks_it)
        return _eval_spec(spec[2], iter([distinct[i] for i in spec[1]]),
                          scalars_it)
    if tag == "R":
        block = next(blocks_it)  # [S, R, W/128, 128]
        row = next(scalars_it)  # traced scalar
        mask = next(scalars_it)
        with jax.named_scope("row_gather"):
            slab = jnp.take(block, row, axis=1)  # [S, W/128, 128]
            return slab * mask  # mask=0 zeroes rows beyond the packed range
    if tag == "RI":
        # ("RI", field, k): the columns of a row-id stack whose id is one
        # of k traced rows (Row(f=r), or a Union of Rows of one field).
        block = next(blocks_it)  # [S, W/128, 32, 128] row ids
        rows = next(scalars_it)  # uint32[k], ROWID_NO_ROW past the field
        with jax.named_scope("rowid_row"):
            return rowid_row(block, rows)
    if tag == "T":
        # Time-range row: union of per-view row slabs (executor.go:1441).
        n_views = spec[2]
        acc = None
        for _ in range(n_views):
            block = next(blocks_it)
            row = next(scalars_it)
            mask = next(scalars_it)
            with jax.named_scope("row_gather"):
                slab = jnp.take(block, row, axis=1) * mask
                acc = slab if acc is None else acc | slab
        return acc
    if tag == "A":
        block = next(blocks_it)  # existence stack
        return block[:, 0]
    if tag == "N":
        block = next(blocks_it)  # existence stack
        inner = _eval_spec(spec[1], blocks_it, scalars_it)
        return block[:, 0] & ~inner
    if tag == "E":
        block = next(blocks_it)  # consumed for shape only
        return jnp.zeros_like(block[:, 0])
    if tag == "NN":
        block = next(blocks_it)  # BSI view stack
        return block[:, BSI_EXISTS_BIT]
    if tag == "C":
        # BSI comparison: ("C", field, op, neg_pred, allow_eq, depth)
        _, _fname, op, neg, allow_eq, depth = spec
        block = next(blocks_it)
        bits = next(scalars_it)  # uint32[depth]
        exists, sign, planes = _bsi_slabs(block, depth)
        if op == "==":
            return _eq_slab(exists, sign, planes, bits, depth, neg)
        if op == "!=":
            return exists & ~_eq_slab(exists, sign, planes, bits, depth, neg)
        if op == "<":
            if not neg:
                pos = _lt_unsigned(exists & ~sign, planes, bits, depth, allow_eq)
                return (sign & exists) | pos
            return _gt_unsigned(exists & sign, planes, bits, depth, allow_eq)
        # op == ">"
        if not neg:
            return _gt_unsigned(exists & ~sign, planes, bits, depth, allow_eq)
        negs = _lt_unsigned(exists & sign, planes, bits, depth, allow_eq)
        return (exists & ~sign) | negs
    if tag == "CB":
        # BSI between: ("CB", field, cls, depth) — fragment.rangeBetween :1504
        _, _fname, cls, depth = spec
        block = next(blocks_it)
        lo_bits = next(scalars_it)
        hi_bits = next(scalars_it)
        exists, sign, planes = _bsi_slabs(block, depth)
        if cls == "pos":
            return _between_unsigned(exists & ~sign, planes, lo_bits, hi_bits, depth)
        if cls == "neg":
            # negative range: magnitudes swap (|hi| <= mag <= |lo|)
            return _between_unsigned(exists & sign, planes, hi_bits, lo_bits, depth)
        pos = _lt_unsigned(exists & ~sign, planes, hi_bits, depth, True)
        neg = _lt_unsigned(exists & sign, planes, lo_bits, depth, True)
        return pos | neg
    if tag == "S":
        inner = _eval_spec(spec[2], blocks_it, scalars_it)
        return _shift_slab(inner, spec[1])
    # Operands first, in the order _build emitted them, then the verb
    # under a scope of its own (scopes are metadata for whoever opens a
    # trace; one inside another would read "verb/row_gather").
    operands = [_eval_spec(ch, blocks_it, scalars_it) for ch in spec[1]]
    with jax.named_scope("verb"):
        acc = operands[0]
        for v in operands[1:]:
            if tag == "U":
                acc = acc | v
            elif tag == "I":
                acc = acc & v
            elif tag == "D":
                acc = acc & ~v
            elif tag == "X":
                acc = acc ^ v
        return acc


def _named(kind: str, fn):
    """`fn` as jax.jit is to see it: the lowered module, and with it the
    profiler's `XLA Modules` line, is then called `jit_pilosa_<kind>`,
    so a trace reduction can tell a count program from a TopN's."""
    fn.__name__ = fn.__qualname__ = "pilosa_" + kind
    return fn


def _pred_bits(value: int, depth: int) -> np.ndarray:
    return np.array([(value >> i) & 1 for i in range(depth)], dtype=np.uint32)


def _shape_sig(tree) -> tuple:
    """Hashable nested (dtype, shape) signature of a launch argument
    tree — the thing jit retraces on, so (kind, build key, shape sig)
    names exactly ONE compiled executable."""
    out = []
    for a in tree:
        if isinstance(a, (tuple, list)):
            out.append(_shape_sig(a))
        else:
            shape = getattr(a, "shape", None)
            if shape is None:
                out.append(type(a).__name__)
            else:
                out.append((str(getattr(a, "dtype", "?")), tuple(shape)))
    return tuple(out)


def _tree_nbytes(tree) -> int:
    """Total array bytes in a (possibly nested) argument/output tree —
    the bytes-shipped/returned figure for EXPLAIN launch records and
    the per-profile counters feeding /debug/workload (ISSUE 18). Walked
    only when a profile is active; the unprofiled hot path (remote-leg
    internals, background rebuilds) never calls this."""
    if isinstance(tree, (tuple, list)):
        return sum(_tree_nbytes(a) for a in tree)
    return int(getattr(tree, "nbytes", 0) or 0)


def _sig_occupancy(shape_sig) -> Optional[int]:
    """Largest leading dim among rank-1 leaves of a shape signature —
    the [Q] slot-bucket of batched programs (None when the program has
    no per-slot operands)."""
    best = None
    for leaf in shape_sig:
        if isinstance(leaf, tuple) and leaf and isinstance(leaf[0], tuple):
            inner = _sig_occupancy(leaf)
            if inner is not None:
                best = inner if best is None else max(best, inner)
        elif (
            isinstance(leaf, tuple) and len(leaf) == 2
            and isinstance(leaf[1], tuple) and len(leaf[1]) == 1
        ):
            n = int(leaf[1][0])
            best = n if best is None else max(best, n)
    return best


class _ProgramEntry:
    """Ledger row for one compiled executable (see _ProgramLedger)."""

    __slots__ = (
        "kind", "program", "bucket", "shapes", "compiles",
        "compile_seconds", "launches", "device_seconds",
        "last_launch", "last_wall",
    )

    def __init__(self, kind: str, program: str, bucket, shapes: str):
        self.kind = kind
        self.program = program
        self.bucket = bucket
        self.shapes = shapes
        self.compiles = 0
        self.compile_seconds = 0.0
        self.launches = 0
        self.device_seconds = 0.0
        self.last_launch = 0.0   # perf_counter origin, for idle age
        self.last_wall = 0.0     # epoch stamp, for operator display


def _dropped(refs) -> bool:
    """Every output of a launch collected: nothing can wait for it."""
    for r in refs:
        if r() is not None:
            return False
    return bool(refs)


class _ProgramLedger:
    """Device-program ledger (ISSUE 16 tentpole 2): every compiled
    executable the backend ever launched, keyed by its (kind, build
    key, argument shape signature). Registration happens at the
    _counted_launch chokepoint, so the ledger sees the same stream the
    device_launches_total counter does.

    A compile observed for a signature ALREADY in the ledger is a
    recompile — the jit cache forgot an executable it had (bucket
    padding regressed, a cache was cleared, a shape leaked past its
    bucket) — and increments `device_recompiles_total{kind}`. Compile
    walls feed `device_compile_seconds{kind}`; the entry count is the
    `device_programs_live` gauge. Served coldest-first at
    GET /debug/programs, mirroring /debug/hbm.

    Device time: each launch is parked as outstanding (its thread,
    signature, dispatch t0 and weak references to its outputs) until
    the block_ready() wrapper around jax.block_until_ready that waits
    for it returns, on whichever thread: a wait closes the launches
    whose outputs it was handed, and every launch its own thread parked
    before them, whose outputs fed them or ran ahead of them; a wait on
    arrays no launch returned (a gather of an output) closes all of its
    thread's launches. Closing adds the launch's dispatch-to-wait
    seconds to its entry, and takes it off the process's feed clock
    (utils/qprofile.py `device_feed`). A launch whose outputs were all
    dropped unwaited is closed at the next wait or scrape."""

    #: Outstanding launches kept; past it a launch is not parked (and
    #: not counted on the feed clock), so a leak cannot grow the list.
    _PENDING_CAP = 256

    def __init__(self, stats):
        self._lock = threading.Lock()
        self._entries: dict[tuple, _ProgramEntry] = {}
        self._stats = stats
        #: Outstanding launches, oldest first: (thread ident, signature,
        #: dispatch t0, the outputs' ids, weak references to them).
        self._pending: list[tuple] = []
        #: (kind, fill) -> slots launched and not yet exported.
        self._slots: dict[tuple[str, str], int] = {}

    # -- registration ------------------------------------------------------

    def record_launch(self, kind: str, key, args, wall: float,
                      compiled: bool, t_dispatch: float, out) -> tuple:
        shape_sig = _shape_sig(args)
        sig = (kind, key, shape_sig)
        live = None
        with self._lock:
            e = self._entries.get(sig)
            if e is None:
                e = self._entries[sig] = _ProgramEntry(
                    kind,
                    repr(key)[:120] if key is not None else kind,
                    _sig_occupancy(shape_sig),
                    repr(shape_sig)[:200],
                )
            e.launches += 1
            e.last_launch = time.perf_counter()
            # Epoch stamp by contract: /debug/programs serves lastLaunch
            # as a wall time operators correlate with logs.
            e.last_wall = time.time()  # lint: allow-monotonic-time(operator-facing epoch display stamp)
            recompile = False
            if compiled:
                e.compiles += 1
                e.compile_seconds += wall
                recompile = e.compiles > 1
                live = len(self._entries)
        if compiled:
            st = self._stats.with_tags(f"kind:{kind}")
            st.timing("device_compile_seconds", wall)
            if recompile:
                st.count("device_recompiles_total")
            self._stats.gauge("device_programs_live", live)
        leaves = jax.tree_util.tree_leaves(out)
        refs = []
        for leaf in leaves:
            try:
                refs.append(weakref.ref(leaf))
            except TypeError:  # a Python scalar: nothing to wait for
                pass
        entry = (threading.get_ident(), sig, t_dispatch,
                 frozenset(map(id, leaves)), refs)
        with self._lock:
            parked = len(self._pending) < self._PENDING_CAP
            if parked:
                self._pending.append(entry)
        if parked:
            device_feed.launched()
        return sig

    def record_compile(self, kind: str, key, shapes, seconds: float) -> None:
        """AOT-compiled programs (.lower().compile() — groupn_pershard)
        measure their compile at build time; no launch-time cache-size
        delta exists for them."""
        shape_sig = _shape_sig(shapes) if isinstance(
            shapes, (tuple, list)
        ) else (shapes,)
        sig = (kind, key, shape_sig)
        with self._lock:
            e = self._entries.get(sig)
            if e is None:
                e = self._entries[sig] = _ProgramEntry(
                    kind,
                    repr(key)[:120] if key is not None else kind,
                    _sig_occupancy(shape_sig),
                    repr(shape_sig)[:200],
                )
            e.compiles += 1
            e.compile_seconds += seconds
            recompile = e.compiles > 1
            live = len(self._entries)
        st = self._stats.with_tags(f"kind:{kind}")
        st.timing("device_compile_seconds", seconds)
        if recompile:
            st.count("device_recompiles_total")
        self._stats.gauge("device_programs_live", live)

    # -- device-time accrual ----------------------------------------------

    def block_ready(self, x):
        """jax.block_until_ready + close the launches the wait covers
        (see the class docstring) into their entries' device seconds."""
        t0 = time.perf_counter()
        jax.block_until_ready(x)
        now = time.perf_counter()
        # The one place a request waits for the device: what
        # /debug/workload ranks shapes by (leaders only; a helper's plane
        # profile drops it, by the one-payer rule).
        current_profile().incr("device_wait_us", math.ceil((now - t0) * 1e6))
        self._close(now, threading.get_ident(),
                    set(map(id, jax.tree_util.tree_leaves(x))))
        return x

    def count_slots(self, kind: str, live: int, bucket: int) -> None:
        """One slot-bucketed launch: its distinct slots and its padding,
        exported at a scrape as `device_slots_total{kind,fill=live|pad}`."""
        with self._lock:
            for fill, n in (("live", live), ("pad", bucket - live)):
                if n:
                    key = (kind, fill)
                    self._slots[key] = self._slots.get(key, 0) + n

    def publish(self) -> None:
        """At a scrape: close the launches whose outputs were all dropped
        unwaited, and export the slots counted since the last one."""
        self._close(time.perf_counter(), None, ())
        with self._lock:
            slots, self._slots = self._slots, {}
        for (kind, fill), n in slots.items():
            self._stats.with_tags(f"kind:{kind}", f"fill:{fill}").count(
                "device_slots_total", n
            )

    def outstanding(self) -> int:
        """Launches parked and not yet closed by a wait."""
        with self._lock:
            return len(self._pending)

    def _close(self, now: float, me, ids) -> None:
        with self._lock:
            pend = self._pending
            if not pend:
                return
            last = -1  # the last launch the wait was handed
            for i, entry in enumerate(pend):
                if not entry[3].isdisjoint(ids):
                    last = i
            keep, closed = [], 0
            for i, entry in enumerate(pend):
                tid, sig, t0, out_ids, refs = entry
                if not (
                    (tid == me and (last < 0 or i <= last))
                    or (last >= 0 and not out_ids.isdisjoint(ids))
                    or _dropped(refs)
                ):
                    keep.append(entry)
                    continue
                closed += 1
                e = self._entries.get(sig)
                if e is not None:
                    e.device_seconds += now - t0
            self._pending = keep
        if closed:
            device_feed.closed(closed)

    # -- export ------------------------------------------------------------

    def ledger(self) -> list[dict]:
        """Ledger rows, coldest-first (longest since last launch),
        mirroring /debug/hbm's eviction-order listing."""
        now = time.perf_counter()
        with self._lock:
            entries = list(self._entries.values())
        entries.sort(key=lambda e: e.last_launch)
        return [
            {
                "kind": e.kind,
                "program": e.program,
                "bucket": e.bucket,
                "shapes": e.shapes,
                "compiles": e.compiles,
                "compileSeconds": round(e.compile_seconds, 6),
                "launches": e.launches,
                "deviceSeconds": round(e.device_seconds, 6),
                "lastLaunch": e.last_wall or None,
                "idleSeconds": (
                    round(now - e.last_launch, 3) if e.last_launch else None
                ),
            }
            for e in entries
        ]

    def counts(self) -> dict:
        with self._lock:
            entries = list(self._entries.values())
        return {
            "programs": len(entries),
            "compiles": sum(e.compiles for e in entries),
            "recompiles": sum(max(0, e.compiles - 1) for e in entries),
            "launches": sum(e.launches for e in entries),
        }


class TPUBackend(VersionWalks):
    """Drop-in replacement for CPUBackend with device execution.

    Anything not device-lowered falls back to the CPU oracle — results are
    identical (differentially tested in tests/test_tpu.py). Pass a
    ShardMesh to shard the stacked blocks over multiple devices; count
    programs then run under shard_map with psum over ICI.
    """

    def __init__(self, holder, device=None, mesh=None, max_bytes: Optional[int] = None,
                 heat_half_life: Optional[float] = None):
        # Every way in (server, bench, tests, the smoke's child) builds
        # this object, so the platform rule is enforced here, once
        # (ops/runtime.py): a TPU, or the platform JAX_PLATFORMS named.
        first = mesh.devices[0] if mesh is not None else device or jax.devices()[0]
        require_serving_platform(first.platform)
        self.holder = holder
        self.cpu = CPUBackend(holder)
        self.mesh = mesh if (mesh is not None and mesh.n > 1) else None
        # Fallback-counter state before the block store: _StackedBlocks
        # routes its mesh-tier degradations (reason=mesh_*) through
        # _count_device_fallback, which reads these.
        VersionWalks.__init__(self, global_stats)
        self._fallback_logged: set = set()
        self.logger = None
        self.blocks = _StackedBlocks(
            device, self.mesh, max_bytes, fallback=self._count_device_fallback,
            heat_half_life=heat_half_life,
            packed_extra=self._packed_row_counts,
            holder=holder,
        )
        self._fns: dict = {}
        self._fns_lock = threading.RLock()
        # Device-program ledger behind GET /debug/programs (ISSUE 16):
        # fed by _counted_launch, so it covers exactly the launch stream
        # device_launches_total counts.
        self.programs = _ProgramLedger(self.stats)
        # The serving tiers (exec/tiers.py): host tables that answer
        # with no device work and absorb write epochs on the host.
        # Pair statistics, (index, fa, fb) -> the flat
        # [rf*rg | cf | cg] totals: one entry per field pair, so
        # replacing it also drops the in-flight device array of the
        # sweep before.
        self._pair_cache = TierTable(
            MAX_PAIR_CACHE_ENTRIES,
            on_hit=lambda: self.stats.count("pair_stats_cache_hits_total"),
        )
        # TopN rank vector, (index, field) -> counts[R] — the
        # reference's rank cache idea with exact device recompute per
        # write epoch (cache.go:136).
        self._topn_cache = TierTable(
            MAX_PAIR_CACHE_ENTRIES,
            on_hit=lambda: self.stats.count("topn_cache_hits_total"),
        )
        # Unfiltered BSI aggregates (Sum/Min/Max), tiny scalars per
        # (kind, index, field) against the BSI view's write epoch, and
        # the group tensors of GroupBys no maintained table answers.
        self._agg_cache = TierTable(
            MAX_PAIR_CACHE_ENTRIES,
            on_hit=lambda: self.stats.count("agg_cache_hits_total"),
            on_change=self._agg_cache_charge,
        )
        # Maintained N>=3 group tensors (VERDICT r4 #1b): per-shard
        # [S, K*Rf*Rg] tables + per-field versions, so a write epoch
        # splices the affected shard rows on the host instead of
        # re-dispatching the tiled sweep.
        self._groupn_cache = TierTable(
            MAX_PAIR_CACHE_ENTRIES,
            on_hit=lambda: self.stats.count("groupn_cache_hits_total"),
        )
        # Pair-plan memo: parse-cache hits serve SHARED call trees, so a
        # batch's plan is keyed by the calls' identities. Cached entries
        # pin the call objects, so a key match implies the same objects
        # (a live object's id cannot be reused). Re-planning every
        # request cost ~12% of serving CPU.
        self._plan_cache = TierTable(512)
        # Whether no column of a resident stack lies in two of its rows,
        # (index, field) -> (weak reference to the stack it was counted
        # on, verdict). A write gives the field a new stack array (a
        # splice or a rebuild, never an update in place), so the verdict
        # holds for exactly the array it names (_rows_disjoint).
        self._disjoint: dict = {}
        # Background-compile the fixed-shape sparse-upload programs so
        # a cold stack build never pays their XLA compile on its
        # critical path (ops/sparse.py; idempotent per device). Under a
        # mesh every device runs its own sub-stack builder (ISSUE r13
        # tentpole 2), so each warms its own program set.
        if self.mesh is None:
            warm_chunk_programs(self.blocks.device)
        else:
            for dev in self.mesh.devices:
                warm_chunk_programs(dev)
        # Windowed refresher (ISSUE r19 tentpole 2): started by the
        # server when refresh-window-ms > 0.
        self._refresher: Optional[threading.Thread] = None
        self._refresher_stop: Optional[threading.Event] = None

    def start_refresher(self, window_ms: float) -> None:
        """Start the windowed device-refresh flusher: every window it
        splices the shards dirtied since the last round into each
        resident stack (blocks.refresh_stale), coalescing a window's
        churn into one incremental round per stack. Idempotent; a
        window of 0 keeps windowing off (inline-only refresh)."""
        if window_ms <= 0 or self._refresher is not None:
            return
        self.blocks.refresh_window_ms = window_ms
        stop = threading.Event()
        self._refresher_stop = stop

        def _loop():
            while not stop.wait(window_ms / 1000.0):
                try:
                    self.blocks.refresh_stale()
                except Exception:  # lint: allow-except-exception(refresher thread crash barrier: one bad round must not end windowing for the process; reads stay correct inline)
                    pass

        from pilosa_tpu.utils.threads import spawn

        self._refresher = spawn("device-refresh", _loop, name="stack-refresh")

    def stop_refresher(self) -> None:
        if self._refresher is not None:
            self._refresher_stop.set()
            self._refresher.join(timeout=5)
            self._refresher = None
            self._refresher_stop = None
            self.blocks.refresh_window_ms = 0

    def _count_device_fallback(self, reason: str, shape, err) -> None:
        """Count (and log once per shape) a device-fast-path fallback so
        hardware-only regressions surface on /metrics instead of shipping
        as silently-slow correct answers. `reason` is a bounded code-path
        label (pair_stats/groupn_pershard/...), never request content
        (lint: metric-tags). Exported as device_fallback_total{reason=...}."""
        self.stats.with_tags(f"reason:{reason}").count("device_fallback_total")
        key = (reason, shape)
        if key not in self._fallback_logged:
            self._fallback_logged.add(key)
            if self.logger is not None:
                self.logger.printf(
                    "device fast path %s fell back for shape %r: %s",
                    reason, shape, err,
                )

    def _host_path(self, call: str, why) -> None:
        """A call this backend does not lower (a stack over the HBM
        budget, a bound a program cannot hold) leaves for the host
        oracle. Correct by design, but not an answer from the device:
        counted as device_fallback_total{reason=unsupported_<call>} so a
        run that must have been served by the chip can check it was."""
        self._count_device_fallback(f"unsupported_{call}", None, why)

    # -- spec + leaf assembly ---------------------------------------------

    def _get_block(self, index, field_obj, shards, view_name=VIEW_STANDARD, min_rows=1):
        """Stack fetch that falls back (raises) when the stack can't be
        resident under the HBM budget."""
        block, rows_p = self.blocks.get(index, field_obj, shards, view_name, min_rows)
        if block is None:
            raise _Unsupported("stack exceeds HBM budget")
        return block, rows_p

    def _get_block_with_versions(self, index, field_obj, shards,
                                 view_name=VIEW_STANDARD, min_rows=1):
        """_get_block plus the packed-from versions (one raising wrapper
        so the over-budget contract lives in one place)."""
        block, rows_p, vers = self.blocks.get_with_versions(
            index, field_obj, shards, view_name, min_rows
        )
        if block is None:
            raise _Unsupported("stack exceeds HBM budget")
        return block, rows_p, vers

    def _field(self, index: str, name: str):
        idx = self.holder.index(index)
        f = idx.field(name) if idx else None
        if f is None:
            raise NotFoundError(f"field not found: {name}")
        return f

    def _build(self, index: str, c: Call, shards: tuple[int, ...],
               blocks: list, scalars: list):
        """One pass building (spec, device leaves). Raises _Unsupported for
        anything without a device lowering; callers fall back to the CPU
        oracle, which also produces the reference's error strings."""
        if c.name not in _DEVICE_LOWERED:
            raise _Unsupported(c.name)
        if c.name in ("Row", "Range"):
            return self._build_row(index, c, shards, blocks, scalars)
        if c.name == "All":
            if c.args:
                raise _Unsupported("All with args")
            self._push_existence(index, shards, blocks)
            return ("A",)
        if c.name == "Not":
            if len(c.children) != 1:
                raise _Unsupported("Not arity")
            self._push_existence(index, shards, blocks)
            child = self._build(index, c.children[0], shards, blocks, scalars)
            return ("N", child)
        if c.name == "Shift":
            n, _ = c.int_arg("n")
            if n < 0 or len(c.children) != 1:
                raise _Unsupported("Shift")
            child = self._build(index, c.children[0], shards, blocks, scalars)
            return ("S", n, child)
        # n-ary bitwise verbs
        if not c.children:
            raise _Unsupported("empty verb")  # CPU path yields reference error/empty
        if c.name == "Union":
            leaf = self._build_rowid_union(index, c, shards, blocks, scalars)
            if leaf is not None:
                return leaf
        kids = tuple(
            self._build(index, ch, shards, blocks, scalars) for ch in c.children
        )
        return ({"Union": "U", "Intersect": "I", "Difference": "D", "Xor": "X"}[c.name], kids)

    def _push_existence(self, index: str, shards, blocks) -> None:
        idx = self.holder.index(index)
        ef = idx.existence_field() if idx else None
        if ef is None:
            raise _Unsupported("no existence field")
        block, _ = self._get_block(index, ef, shards)
        blocks.append(block)

    def _build_row(self, index, c, shards, blocks, scalars):
        cond_args = [(k, v) for k, v in c.args.items() if isinstance(v, Condition)]
        if cond_args:
            return self._build_bsi(index, c, shards, blocks, scalars, cond_args)

        field_name = c.field_arg()
        f = self._field(index, field_name)
        row_id, ok = c.uint64_arg(field_name)
        if not ok:
            raise QueryError("Row() must specify row")

        if "from" in c.args or "to" in c.args:
            return self._build_time_row(index, c, f, row_id, shards, blocks, scalars)

        leaf = self._rowid_leaf(index, f, shards, [row_id], blocks, scalars)
        if leaf is not None:
            return leaf
        try:
            block, rows_p = self._get_block(index, f, shards)
        except _Unsupported:
            # Row paging: the full stack is over the HBM budget, but one
            # row always fits — fetch it on demand ([S, 1, W], cached).
            block = self.blocks.get_row(index, f, shards, VIEW_STANDARD, row_id)
            blocks.append(block)
            scalars.append(np.uint32(0))
            scalars.append(np.uint32(1))
            return ("R", field_name)
        blocks.append(block)
        scalars.append(np.uint32(min(row_id, rows_p - 1)))
        scalars.append(np.uint32(1 if row_id < rows_p else 0))
        return ("R", field_name)

    def _get_rowid(self, index, f, shards):
        """(row-id stack, rows) of a field held as row ids over `shards`,
        or None where it is held dense (or its ids could not be built:
        the choice then moves it back to dense)."""
        if not self.blocks.rowid_field(index, f, shards):
            return None
        ids, rows = self.blocks.get_rowid(index, f, tuple(shards))
        return None if ids is None else (ids, rows)

    def _rowid_leaf(self, index, f, shards, row_ids, blocks, scalars):
        """The ("RI", field, k) leaf over a field held as row ids, or
        None where the field is held dense."""
        got = self._get_rowid(index, f, shards)
        if got is None:
            return None
        ids, n_rows = got
        blocks.append(ids)
        scalars.append(np.array(
            [r if r < n_rows else ROWID_NO_ROW for r in row_ids],
            dtype=np.uint32,
        ))
        return ("RI", f.name, len(row_ids))

    def _build_rowid_union(self, index, c, shards, blocks, scalars):
        """A Union of plain Rows of one field held as row ids, as ONE
        row-id leaf (one pass over the ids), or None."""
        names = {next(iter(ch.args), None) for ch in c.children}
        if len(names) != 1:
            return None
        name = names.pop()
        if not isinstance(name, str) or is_reserved_arg(name):
            return None
        ids = _named_rows(c, name)
        if ids is None:
            return None
        f = self._field(index, name)
        if f.options.type != FIELD_TYPE_MUTEX:
            return None
        return self._rowid_leaf(index, f, shards, sorted(ids), blocks, scalars)

    def _build_time_row(self, index, c, f, row_id, shards, blocks, scalars):
        """Row(f=r, from=, to=) — union over quantum views (executor.go:1441)."""
        import datetime as dt

        if not f.options.time_quantum:
            # Reference returns empty for non-time fields with a range.
            self._push_bsi_or_field_block(index, f, shards, blocks)
            return ("E",)
        from_t = parse_time(c.args["from"]) if "from" in c.args else dt.datetime(1, 1, 1)
        to_t = (
            parse_time(c.args["to"])
            if "to" in c.args
            else dt.datetime.utcnow() + dt.timedelta(days=1)
        )
        views = [
            vn
            for vn in views_by_time_range(VIEW_STANDARD, from_t, to_t, f.options.time_quantum)
            if f.view(vn) is not None
        ]
        if not views:
            self._push_bsi_or_field_block(index, f, shards, blocks)
            return ("E",)
        for vn in views:
            block, rows_p = self._get_block(index, f, shards, view_name=vn)
            blocks.append(block)
            scalars.append(np.uint32(min(row_id, rows_p - 1)))
            scalars.append(np.uint32(1 if row_id < rows_p else 0))
        return ("T", f.name, len(views))

    def _push_bsi_or_field_block(self, index, f, shards, blocks) -> None:
        """Push any block purely as a shape carrier for an ("E",) node."""
        block, _ = self._get_block(index, f, shards)
        blocks.append(block)

    def _build_bsi(self, index, c, shards, blocks, scalars, cond_args):
        """BSI condition → resolved spec. Mirrors executeRowBSIGroupShard
        (executor.go:1533) + bsiGroup.baseValue (field.go:1584); the
        resolution (out-of-range/encompassing) happens here at assembly so
        the compiled program shape encodes only (op, sign, depth)."""
        if len(c.args) > 1:
            raise _Unsupported("Row(): too many arguments")
        field_name, cond = cond_args[0]
        f = self._field(index, field_name)
        if f.options.type != FIELD_TYPE_INT:
            raise _Unsupported("condition on non-int field")
        opts = f.bsi_group()
        depth = opts.bit_depth
        if depth > MAX_BSI_DEPTH:
            raise _Unsupported("bit depth")
        vname = bsi_view_name(field_name)

        def push_block():
            block, _ = self._get_block(
                index, f, shards, view_name=vname, min_rows=BSI_OFFSET_BIT + depth
            )
            blocks.append(block)

        if cond.op == NEQ and cond.value is None:
            push_block()
            return ("NN", field_name)

        if cond.op == BETWEEN:
            predicates = cond.int_slice_value()
            if len(predicates) != 2:
                raise QueryError(
                    "Row(): BETWEEN condition requires exactly two integer values"
                )
            lo, hi = predicates
            base_lo, base_hi, out_of_range = CPUBackend._base_value_between(f, lo, hi)
            push_block()
            if out_of_range:
                return ("E",)
            if lo <= opts.min and hi >= opts.max:
                return ("NN", field_name)
            if base_lo >= 0:
                cls = "pos"
                b1, b2 = abs(base_lo), abs(base_hi)
            elif base_hi < 0:
                cls = "neg"
                # magnitudes swap for the all-negative range; _eval_spec
                # swaps the operand order, so emit (|lo|, |hi|) as-is.
                b1, b2 = abs(base_lo), abs(base_hi)
            else:
                cls = "mixed"
                b1, b2 = abs(base_lo), abs(base_hi)
            scalars.append(_pred_bits(b1, depth))
            scalars.append(_pred_bits(b2, depth))
            return ("CB", field_name, cls, depth)

        if not isinstance(cond.value, int) or isinstance(cond.value, bool):
            raise QueryError("Row(): conditions only support integer values")
        value = cond.value
        base_value, out_of_range = CPUBackend._base_value(f, cond.op, value)
        push_block()
        if out_of_range and cond.op != NEQ:
            return ("E",)
        if (
            (cond.op == LT and value > opts.max)
            or (cond.op == LTE and value >= opts.max)
            or (cond.op == GT and value < opts.min)
            or (cond.op == GTE and value <= opts.min)
        ):
            return ("NN", field_name)
        if out_of_range and cond.op == NEQ:
            return ("NN", field_name)
        op = {EQ: "==", NEQ: "!=", LT: "<", LTE: "<", GT: ">", GTE: ">"}[cond.op]
        allow_eq = cond.op in (LTE, GTE)
        neg = base_value < 0
        scalars.append(_pred_bits(abs(base_value), depth))
        return ("C", field_name, op, neg, allow_eq, depth)

    def _assemble(self, index: str, c: Call, shards: tuple[int, ...]):
        blocks: list = []
        scalars: list = []
        spec = self._build(index, c, shards, blocks, scalars)
        # A stack that several leaves read (a Union of rows of one field)
        # is one argument of the program, not one a leaf: the compiler
        # counts every argument's bytes, and eight 4.7 GB leaves of one
        # stack are no 38 GB program. The spec keeps which leaf reads
        # which ("@", _eval_spec).
        unique: list = []
        at: dict = {}
        order = []
        for b in blocks:
            if id(b) not in at:
                at[id(b)] = len(unique)
                unique.append(b)
            order.append(at[id(b)])
        if len(unique) < len(blocks):
            spec = ("@", tuple(order), spec)
            blocks = unique
        return spec, tuple(blocks), tuple(scalars)

    # -- compiled programs -------------------------------------------------

    def _wrap(self, kind: str, body, extra_block: bool, out_specs):
        """jit the body under its kind's name; under a mesh, run it
        per-device via shard_map with psum collectives (out_specs
        describes the reduced outputs)."""
        body = _named(kind, body)
        if self.mesh is None:
            return jax.jit(body)
        ax = self.mesh.axis
        blk = P(ax)  # prefix spec: leading dim sharded, rest replicated
        in_specs = (blk, P()) if not extra_block else (blk, blk, P())
        return jax.jit(
            shard_map(body, mesh=self.mesh.mesh, in_specs=in_specs, out_specs=out_specs)
        )

    def _psum(self, x):
        return jax.lax.psum(x, self.mesh.axis) if self.mesh is not None else x

    def _counted_launch(self, kind: str, fn, key=None):
        """Wrap a compiled program so every execution counts as
        `device_launches_total{kind=…}` — the chokepoint every query
        program passes through, so batching wins are SLO-visible as a
        falling launch rate against a steady batch_legs_total (ISSUE r11:
        `query_phase_seconds{phase=device_dispatch}` collapses to a
        per-BATCH cost; this counter is the denominator that proves it).

        ISSUE 16: the same chokepoint feeds the device-program ledger.
        A jit executable exposes its trace-cache size; a cache growth
        across one call means THIS call paid a trace+compile, and the
        call's wall time is the measured compile cost (the first run's
        device execution rides along — the operator-relevant figure is
        'how long did this launch stall on XLA', which is exactly that).
        EXPLAIN launch records are written here too, only when the
        active profile carries a plan (zero allocation otherwise)."""
        stats = self.stats.with_tags(f"kind:{kind}")
        ledger = self.programs
        cache_size = getattr(fn, "_cache_size", None)
        mesh_n = self.mesh.n if self.mesh is not None else 1

        def counted(*args):
            stats.count("device_launches_total")
            before = cache_size() if cache_size is not None else None
            t0 = time.perf_counter()
            out = fn(*args)
            wall = time.perf_counter() - t0
            compiled = (
                before is not None and cache_size() > before
            )
            sig = ledger.record_launch(kind, key, args, wall, compiled, t0,
                                       out)
            prof = current_profile()
            if prof.charges:
                # ISSUE 18 satellite fix: stamp the cheap scalar totals
                # into EVERY profiled request's counters — before this,
                # per-launch totals only existed inside explain plans,
                # so /debug/queries ring entries dropped them for
                # normal traffic and the workload table would have
                # needed ?explain=1 traffic to accumulate. `wall` is the
                # asynchronous call's: dispatch, not device time (the
                # wait is block_ready's, which stamps device_wait_us).
                shipped = _tree_nbytes(args)
                returned = _tree_nbytes(out)
                prof.incr("device_launches")
                prof.incr("dispatch_us", int(wall * 1e6))
                prof.incr("bytes_shipped", shipped)
                prof.incr("bytes_returned", returned)
                ex = prof.explain
                if ex is not None:
                    ex.add_launch({
                        "kind": kind,
                        "program": sig[0] if key is None else repr(key)[:120],
                        "shapes": repr(sig[2])[:200],
                        "occupancy": _sig_occupancy(sig[2]),
                        "compiled": compiled,
                        "dispatchMs": round(wall * 1e3, 3),
                        "bytesShipped": shipped,
                        "bytesReturned": returned,
                        "devices": mesh_n,
                    })
            return out

        # The jitted program itself, for AOT `.lower()` against a
        # compile-only topology (tests/test_chip_compile.py).
        counted.__wrapped__ = fn
        return counted

    def _program(self, kind: str, spec, reduce_dev: bool, extra=None):
        """One compiled program per (kind, tree-shape, reduction mode);
        the spec tree fixes the leaf count, so it alone keys the shape.
        Batched kinds (count_batch / vec_batch) additionally key on the
        slot-count bucket through their [Q]-leading scalar shapes — see
        _slot_bucket."""
        key = (kind, spec, reduce_dev, extra)
        with self._fns_lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn

        mesh = self.mesh
        ax = P(mesh.axis) if mesh is not None else None

        if kind == "count":

            def body(blocks, scalars):
                slab = _eval_spec(spec, iter(blocks), iter(scalars))
                with jax.named_scope("popcount"):
                    per_shard = slab_counts(slab)
                if reduce_dev:
                    with jax.named_scope("shard_sum"):
                        return self._psum(
                            jnp.sum(per_shard, dtype=jnp.uint32)
                        )
                return per_shard

            out = (P() if reduce_dev else ax) if mesh is not None else None
            fn = self._wrap(kind, body, False, out)

        elif kind == "vec":

            def body(blocks, scalars):
                return _eval_spec(spec, iter(blocks), iter(scalars))

            fn = self._wrap(kind, body, False, ax)

        elif kind == "count_batch":

            def body(blocks, scalars):
                # scan over the query-slot axis: each step is the fused
                # unbatched count, its row leaves read in place from the
                # resident stacks (_eval_spec) — no [S, Q, W] gather
                # (32 GB at the 1B-column/256-batch shape) and no
                # row-sized temporary a slot — and works for any spec
                # (BSI leaves included). The LAST scanned array is the
                # [Q] ragged-occupancy lane mask: padded slots
                # (slot-count bucketing, _slot_bucket) replay slot 0's
                # scalars and are zeroed in-kernel so no reduction can
                # ever see them.
                def step(_, qs):
                    act = qs[-1]
                    slab = _eval_spec(spec, iter(blocks), iter(qs[:-1]))
                    with jax.named_scope("popcount"):
                        per_shard = masked_lane_counts(slab, act)
                    if reduce_dev:
                        with jax.named_scope("shard_sum"):
                            return None, self._psum(
                                jnp.sum(per_shard, dtype=jnp.uint32)
                            )
                    return None, per_shard

                _, out = jax.lax.scan(step, None, scalars)
                return out  # [Q] or [Q, S]

            out = (P() if reduce_dev else P(None, mesh.axis if mesh else None)) if mesh is not None else None
            fn = self._wrap(kind, body, False, out)

        elif kind == "vec_batch":

            def body(blocks, scalars):
                # Batched bitmap materialization: scan the query-slot
                # axis, stacking each slot's [S, W/128, 128] slab into
                # [Q, S, W/128, 128] (capped by MAX_ROW_BATCH_BYTES at
                # the call site). Same last-array lane-mask contract as
                # count_batch.
                def step(_, qs):
                    act = qs[-1]
                    slab = _eval_spec(spec, iter(blocks), iter(qs[:-1]))
                    return None, mask_lane_slab(slab, act)

                _, out = jax.lax.scan(step, None, scalars)
                return out  # [Q, S, W/128, 128]

            out = P(None, mesh.axis) if mesh is not None else None
            fn = self._wrap(kind, body, False, out)

        elif kind == "topn_plain":

            def body(field_block):
                per = slab_counts(field_block)  # [S, R]
                if reduce_dev:
                    return self._psum(jnp.sum(per, axis=0, dtype=jnp.uint32))
                return per

            body = _named(kind, body)
            if mesh is not None:
                fn = jax.jit(
                    shard_map(
                        body,
                        mesh=mesh.mesh,
                        in_specs=(P(mesh.axis),),
                        out_specs=P() if reduce_dev else P(mesh.axis),
                    )
                )
            else:
                fn = jax.jit(body)

        elif kind == "topn_src":

            def body(field_block, blocks, scalars):
                src = _eval_spec(spec, iter(blocks), iter(scalars))
                per = slab_counts(field_block & src[:, None])  # [S, R]
                if reduce_dev:
                    return self._psum(jnp.sum(per, axis=0, dtype=jnp.uint32))
                return per

            out = (P() if reduce_dev else ax) if mesh is not None else None
            fn = self._wrap(kind, body, True, out)

        elif kind == "bsi_sum":
            depth = extra

            def body(bsi_block, blocks, scalars):
                exists, sign, planes = _bsi_slabs(bsi_block, depth)
                consider = exists
                if spec is not None:
                    consider = consider & _eval_spec(spec, iter(blocks), iter(scalars))
                neg = sign & consider
                pos = consider & ~neg
                plane_stack = jnp.stack(planes, axis=1) if depth else jnp.zeros(
                    (exists.shape[0], 0) + exists.shape[1:], dtype=exists.dtype
                )  # [S, depth, W/128, 128]
                pos_c = jnp.sum(
                    slab_counts(plane_stack & pos[:, None]),
                    axis=0, dtype=jnp.uint32,
                )
                neg_c = jnp.sum(
                    slab_counts(plane_stack & neg[:, None]),
                    axis=0, dtype=jnp.uint32,
                )
                cnt = jnp.sum(jax.lax.population_count(consider), dtype=jnp.uint32)
                return self._psum(pos_c), self._psum(neg_c), self._psum(cnt)

            out = (P(), P(), P()) if mesh is not None else None
            fn = self._wrap(kind, body, True, out)

        elif kind in ("bsi_min", "bsi_max"):
            depth = extra

            def body(bsi_block, blocks, scalars):
                exists, sign, planes = _bsi_slabs(bsi_block, depth)
                consider = exists
                if spec is not None:
                    consider = consider & _eval_spec(spec, iter(blocks), iter(scalars))

                branch_mask = (
                    (sign & consider) if kind == "bsi_min" else (consider & ~sign)
                )
                # Branch A: maxUnsigned over branch_mask (fragment.go:1216).
                filt = branch_mask
                bits_a = []
                for i in range(depth - 1, -1, -1):
                    row = planes[i] & filt
                    took = slab_counts(row) > 0  # [S]
                    filt = _where(took[:, None, None], row, filt)
                    bits_a.append(took)
                bits_a = (
                    jnp.stack(bits_a[::-1], axis=1)
                    if depth
                    else jnp.zeros((exists.shape[0], 0), dtype=jnp.bool_)
                )
                cnt_a = slab_counts(filt)
                # Branch B: minUnsigned over consider (fragment.go:1198).
                filt = consider
                bits_b = []
                for i in range(depth - 1, -1, -1):
                    row = filt & ~planes[i]
                    # bit set when no zero-plane columns
                    empty = slab_counts(row) == 0
                    filt = _where(empty[:, None, None], filt, row)
                    bits_b.append(empty)
                bits_b = (
                    jnp.stack(bits_b[::-1], axis=1)
                    if depth
                    else jnp.zeros((exists.shape[0], 0), dtype=jnp.bool_)
                )
                cnt_b = slab_counts(filt)
                branch_any = slab_counts(branch_mask) > 0
                consider_any = slab_counts(consider) > 0
                return bits_a, cnt_a, bits_b, cnt_b, branch_any, consider_any

            out = (ax, ax, ax, ax, ax, ax) if mesh is not None else None
            fn = self._wrap(kind, body, True, out)

        elif kind in ("topn_tanimoto", "topn_tanimoto_counts",
                      "packed_row_counts"):
            # Over a packed stack (blocks.get_packed): one device only.
            fn = jax.jit(_named(kind, {
                "topn_tanimoto": tanimoto_topn,
                "topn_tanimoto_counts": tanimoto_counts,
                "packed_row_counts": packed_row_counts,
            }[kind]))

        else:
            raise ValueError(kind)

        fn = self._counted_launch(kind, fn, key=key)
        with self._fns_lock:
            fn = self._fns.setdefault(key, fn)
        return fn

    # -- backend interface -------------------------------------------------

    def _resident_shards(self, index: str, shard: int) -> tuple[tuple[int, ...], int]:
        """Shard tuple to assemble against for a single-shard call: the
        index's full available set, so shard-by-shard bitmap calls reuse
        ONE resident stack instead of thrashing the cache with per-shard
        repacks (each would replace the (index, field, view) entry)."""
        idx = self.holder.index(index)
        # lint: allow-hot-serialize(shard inventory is schema-sized and feeds list ops, not serialization)
        shards = idx.available_shards().to_array().tolist() if idx else []
        if shard in shards:
            return tuple(shards), shards.index(shard)
        return (shard,), 0

    @staticmethod
    def _slab_row(host: np.ndarray, shards) -> Row:
        """uint32[R, W] host slab whose rows align with `shards` ->
        lazy columns-backed Row via ONE vectorized whole-slab pass
        (ops/blocks.py unpack_slab_columns). Rows re-order (and DEDUPE)
        by shard first: Row.from_columns requires a sorted-unique
        column array, and a user-supplied shard list may repeat a shard
        (?shards=3,3) — the old per-shard merge() unioned duplicates
        idempotently, so this path must too (code review r14)."""
        bases = np.asarray(shards, dtype=np.uint64) * np.uint64(SHARD_WIDTH)
        if bases.size > 1:
            uniq, first = np.unique(bases, return_index=True)
            if uniq.size != bases.size or not np.array_equal(uniq, bases):
                host = host[first]
                bases = uniq
        return Row.from_columns(unpack_slab_columns(host, bases))

    def bitmap_call_shard(self, index: str, c: Call, shard: int) -> Row:
        shards_t, pos = self._resident_shards(index, shard)
        try:
            spec, blocks, scalars = self._assemble(index, c, shards_t)
        except _Unsupported as e:
            self._host_path("bitmap", e)
            return self.cpu.bitmap_call_shard(index, c, shard)
        slab = self.programs.block_ready(
            self._program("vec", spec, False)(blocks, scalars)
        )
        # Lazy columns-backed Row: unpack_row output is sorted and the
        # shard base is a scalar add — no roaring construction unless a
        # set-algebra caller materializes.
        cols = unpack_row(flat_words(np.asarray(slab[pos]))) + np.uint64(
            shard
        ) * np.uint64(SHARD_WIDTH)
        return Row.from_columns(cols)

    @_pinning
    def bitmap_call(self, index: str, c: Call, shards: list[int]) -> Row:
        """Whole-query bitmap materialization: evaluate the stack ONCE and
        read back [S, W/128, 128], slicing per-shard segments on the host: one
        program execution for any shard count, replacing the executor's
        shard-by-shard recursion (reference executeBitmapCallShard
        executor.go:651 became a single device program; VERDICT r2 #3
        killed the S-dispatches-of-S-shard-evaluations path)."""
        # Assemble against the index's full resident stack when it covers
        # the request, so subset queries don't replace the cached stack.
        idx = self.holder.index(index)
        # lint: allow-hot-serialize(shard inventory is schema-sized and feeds list ops, not serialization)
        avail = idx.available_shards().to_array().tolist() if idx else []
        pos_of = {s: i for i, s in enumerate(avail)}
        if avail and all(s in pos_of for s in shards):
            shards_t = tuple(avail)
            positions = [pos_of[s] for s in shards]
        else:
            shards_t = tuple(shards)
            positions = list(range(len(shards)))
        prof = current_profile()
        try:
            with prof.phase("plan"):
                spec, blocks, scalars = self._assemble(index, c, shards_t)
        except _Unsupported as e:
            self._host_path("bitmap", e)
            out = Row()
            for s in shards:
                out.merge(self.cpu.bitmap_call_shard(index, c, s))
            return out
        with prof.phase("dispatch", span="pilosa.bitmap_call"):
            slab = self._program("vec", spec, False)(blocks, scalars)
            # Subset requests gather on device first: reading the whole
            # [S_pad, W/128, 128] slab back for one shard would move ~120 MB to
            # the host when 128 KiB is needed.
            sub = len(positions) * 4 <= slab.shape[0]
            if sub:
                slab = slab[jnp.asarray(positions, dtype=jnp.int32)]
        with prof.phase("device_wait"):
            # Block HERE so device_dispatch carries the device round
            # trip and host_reduce is pure host-side work (ISSUE r14:
            # the phase table's post-collapse contract,
            # docs/observability.md).
            self.programs.block_ready(slab)
        with prof.phase("readback"):
            # Whole-slab vectorized materialization: one readback, one
            # unpackbits+flatnonzero pass, shard bases added vectorized
            # -> ONE sorted column array backing a lazy Row. Replaces
            # the per-shard unpack/Bitmap/merge loop (ISSUE r14).
            host = flat_words(np.asarray(slab))
            if not sub:
                if positions == list(range(len(positions))):
                    host = host[: len(positions)]  # contiguous: a view
                else:
                    host = host[np.asarray(positions, dtype=np.intp)]
            return self._slab_row(host, shards)

    def count_shard(self, index: str, c: Call, shard: int) -> int:
        return self.count_shards(index, c, [shard])

    @_pinning
    def count_shards(self, index: str, c: Call, shards: list[int]) -> int:
        """Whole-query count: ONE jitted dispatch over all shards + one
        scalar readback — the reference's scatter-gather mapReduce
        collapsed into device arithmetic (BASELINE.json north star)."""
        prof = current_profile()
        try:
            with prof.phase("plan"):
                spec, blocks, scalars = self._assemble(
                    index, c, tuple(shards)
                )
        except _Unsupported as e:
            self._host_path("count", e)
            return sum(self.cpu.count_shard(index, c, s) for s in shards)
        s_pad = blocks[0].shape[0]
        reduce_dev = s_pad <= MAX_DEVICE_SUM_SHARDS
        with prof.phase("dispatch", span="pilosa.count"):
            partials = self._program("count", spec, reduce_dev)(blocks, scalars)
        with prof.phase("device_wait"):
            # Block HERE: device_dispatch carries the device round trip
            # (dispatch floor included), host_reduce only the host-side
            # arithmetic — the phase table's post-collapse contract
            # (ISSUE r14, docs/observability.md).
            self.programs.block_ready(partials)
        # Host sum in Python ints: exact for any shard count.
        with prof.phase("readback"):
            return int(np.asarray(partials, dtype=np.uint64).sum())

    def count_batch(self, index: str, calls: list[Call], shards: list[int]) -> list[int]:
        """Q count queries in one (or few) dispatches; see count_batch_async."""
        return self.count_batch_async(index, calls, shards)()

    def count_batch_async(
        self, index: str, calls: list[Call], shards: list[int]
    ) -> Callable[[], list[int]]:
        """Dispatch a batch of count queries and return a resolver.

        The device work is enqueued immediately (XLA dispatch is async);
        calling the returned thunk reads results back. Keeping several
        batches in flight amortizes the per-dispatch round trip, which
        the device sits idle for unless another batch is already queued.

        Fast path: when every call is a 1- or 2-row combination over one
        field pair, ONE pair_stats sweep (ops/kernels.py) serves the whole
        batch — each stack byte is touched once instead of once per query.
        Everything else groups same-shape calls into fused scan dispatches
        (row ids as [Q] traced vectors), and the remainder falls back to
        count_shards/CPU per call.
        """
        if not calls:
            return lambda: []
        shards_t = tuple(shards)
        batch = None
        # One `plan` for the batch: the pair plan and, where there is
        # none, the scan path's assembly.
        with current_profile().phase("plan"):
            plan = self._cached_pair_plan(index, calls)
            if plan is None:
                batch = self._assemble_batch(index, calls, shards_t)
        if plan is not None:
            try:
                return self._pair_batch_dispatch(index, plan, shards_t)
            except QueryError:
                raise
            except _Unsupported:
                pass  # expected shape limits; the scan path serves it
            except Exception as e:  # noqa: BLE001 — Mosaic compile/VMEM
                # failures only real hardware can surface: the generic
                # scan path serves the same batch correctly, so never
                # let the fast path 500 — but count + log it (VERDICT r3
                # weak #7: silent fallbacks hid hardware regressions).
                self._count_device_fallback(
                    "pair_stats", (len(calls), len(shards_t)), e
                )
        return self._generic_batch_dispatch(index, calls, shards_t, batch)

    # -- pair-stats batch fast path (VERDICT r2 #1: row-reuse kernel) ------

    _PAIR_VERBS = {"Intersect": "I", "Union": "U", "Difference": "D", "Xor": "X"}

    def _plain_row_leaf(self, index: str, c: Call) -> Optional[tuple[str, int]]:
        """(field, row_id) when c is Row(field=intRow) on the standard
        view with nothing else going on; None otherwise."""
        if c.name != "Row" or c.children or len(c.args) != 1:
            return None
        try:
            fname = c.field_arg()
        except ValueError:
            return None
        v = c.args.get(fname)
        if isinstance(v, (Condition, bool)) or not isinstance(v, int) or v < 0:
            return None
        try:
            self._field(index, fname)
        except QueryError:
            return None  # let the fallback path raise the reference error
        return fname, v

    def _cached_pair_plan(self, index: str, calls: list[Call]):
        """Memoized _pair_batch_plan. Plans derive from call-tree
        structure (field names, rows, verbs) plus FIELD EXISTENCE — the
        field set is part of the key, so creating a field re-plans
        batches whose None plan predated it (shared parse-cache trees
        live as long as the process)."""
        if not all(c.cached for c in calls):
            # Fresh trees (key-translated rewrites, programmatic calls):
            # ids are per-request, so memoizing would never hit — it
            # would only pin throwaway trees and evict useful entries.
            return self._pair_batch_plan(index, calls)
        idx = self.holder.index(index)
        fields_key = tuple(idx.fields) if idx is not None else ()
        key = (index, fields_key, tuple(map(id, calls)))
        hit = self._plan_cache.hit(key, None)
        if hit is not None:
            return hit.value
        plan = self._pair_batch_plan(index, calls)
        self._plan_cache.store(key, TierEntry(None, plan, extra=tuple(calls)))
        return plan

    def _pair_batch_plan(self, index: str, calls: list[Call]):
        """Plan (entries, fa, fb) when the whole batch derives from the
        pair-count matrix + row-count vectors of one field pair. Entries
        are (op, row_a, row_b) with op 'A'/'B' for single-row counts on
        fa/fb and I/U/D/X for two-row verbs."""
        entries: list[tuple[str, int, int]] = []
        pair_fields: Optional[tuple[str, str]] = None
        singles: list[tuple[int, str, int]] = []  # (entry idx, field, row)
        for c in calls:
            leaf = self._plain_row_leaf(index, c)
            if leaf is not None:
                singles.append((len(entries), leaf[0], leaf[1]))
                entries.append(("A", leaf[1], 0))  # field side fixed below
                continue
            op = self._PAIR_VERBS.get(c.name)
            if op is None or len(c.children) != 2 or c.args:
                return None
            la = self._plain_row_leaf(index, c.children[0])
            lb = self._plain_row_leaf(index, c.children[1])
            if la is None or lb is None:
                return None
            if pair_fields is None:
                pair_fields = (la[0], lb[0])
            elif pair_fields != (la[0], lb[0]):
                return None
            entries.append((op, la[1], lb[1]))
        if pair_fields is None:
            if not singles:
                return None
            fa = singles[0][1]
            if any(f != fa for _, f, _ in singles):
                return None
            pair_fields = (fa, fa)
        fa, fb = pair_fields
        for i, f, row in singles:
            if f == fa:
                entries[i] = ("A", row, 0)
            elif f == fb:
                entries[i] = ("B", 0, row)
            else:
                return None
        return entries, fa, fb

    def _pair_program(self, pershard: bool = True):
        """Compiled pair_stats sweep (+ shard_map under a mesh).

        pershard=True (the default): per-shard stats
        [S, rf*rg + rf + rg] in ONE output (row i =
        [pair_i.ravel() | cf_i | cg_i]) — one readback (~300 KiB at the
        954-shard bench shape, still a single round trip) buys the
        host table that absorbs write epochs without re-sweeping
        (exec/tiers.py refresh_entry). Under a mesh the kernel runs on each
        device's local shard chunk and the output stays sharded
        (out_specs P(axis)); the readback gathers it so multi-chip
        serving gets the same host-maintained tables. pershard=False:
        device-summed (psum'd under mesh) totals [D] — used when the
        per-shard table would be too large to read back and retain
        (see MAX_PAIR_PERSHARD_BYTES)."""
        key = ("pair2", pershard)
        with self._fns_lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn
        interpret = pallas_interpret()

        def flat(fb, gb):
            pair, cf, cg = pair_stats_pershard(fb, gb, interpret=interpret)
            s = pair.shape[0]
            return jnp.concatenate(
                [pair.reshape(s, -1), cf.reshape(s, -1), cg.reshape(s, -1)],
                axis=1,
            )

        if self.mesh is None:
            if not pershard:

                def flat(fb, gb):  # noqa: F811 — summed variant
                    pair, cf, cg = pair_stats(fb, gb, interpret=interpret)
                    return jnp.concatenate([pair.ravel(), cf, cg])

            fn = jax.jit(_named("pair_stats", flat))
        elif pershard:
            mesh = self.mesh
            fn = jax.jit(
                shard_map(
                    _named("pair_stats", flat),
                    mesh=mesh.mesh,
                    in_specs=(P(mesh.axis), P(mesh.axis)),
                    out_specs=P(mesh.axis),
                    check_vma=False,
                )
            )
        else:
            mesh = self.mesh

            def body(fb, gb):
                pair, cf, cg = pair_stats(fb, gb, interpret=interpret)
                ax = mesh.axis
                return jax.lax.psum(
                    jnp.concatenate([pair.ravel(), cf, cg]), ax
                )

            fn = jax.jit(
                shard_map(
                    _named("pair_stats", body),
                    mesh=mesh.mesh,
                    in_specs=(P(mesh.axis), P(mesh.axis)),
                    out_specs=P(),
                    # pallas_call's out_shape carries no vma annotation;
                    # skip the varying-across-mesh check for this body.
                    check_vma=False,
                )
            )
        fn = self._counted_launch("pair_stats", fn, key=key)
        with self._fns_lock:
            fn = self._fns.setdefault(key, fn)
        return fn

    #: Host-update cutoff: re-deriving one shard's stats row costs host
    #: numpy (pack + popcounts) per dirty shard; a full device sweep
    #: costs a stack refresh plus one dispatch round trip whatever the
    #: dirty count — so up to this many dirty shards the host update is
    #: taken, beyond it the sweep. Where the crossover sits on a locally
    #: attached chip has not been measured (ROADMAP S2).
    MAX_PAIR_HOST_UPDATE_SHARDS = 64

    #: Per-shard table retention gate: beyond this, the readback +
    #: resident host copy (+ the kernel's HBM output) outweigh the
    #: incremental-update benefit — fall back to device-summed totals
    #: (write epochs then re-sweep, the pre-table behavior). 32 MiB
    #: covers the bench shape (954 shards x 80 stats = 305 KiB) with
    #: orders-of-magnitude headroom while capping the pathological
    #: rf*rg=2^16 case (which would be ~250 MB per entry).
    MAX_PAIR_PERSHARD_BYTES = 32 << 20

    def _pair_batch_dispatch(self, index, plan, shards_t):
        entries, fa, fb = plan
        f_obj = self._field(index, fa)
        g_obj = self._field(index, fb)
        if any(self.blocks.rowid_field(index, fo, shards_t)
               for fo in (f_obj, g_obj)):
            # The pair tables count dense stacks; the scan path reads
            # row ids (_eval_spec's row-id leaf).
            raise _Unsupported("row-id layout")
        # Host stats cache (the reference's rank-cache idea, cache.go:136:
        # materialize counts once, serve queries from them until writes
        # invalidate). Freshness is the LIVE per-shard fragment versions:
        # a generation-equal hit — or a small-epoch host table update —
        # resolves with ZERO device work, including no stack refresh; the
        # device stack is only (re)built when a sweep is actually needed,
        # so write churn costs O(dirty shards) numpy instead of a device
        # round trip per epoch. The LRU cap bounds the pair-combination
        # count for many-field indexes.
        views = (f_obj.view(VIEW_STANDARD), g_obj.view(VIEW_STANDARD))
        ckey = (index, fa, fb)
        ent = self._pair_cache.serve(
            ckey, shards_t, views,
            lambda stale, fp: self._pair_refresh(
                index, ckey, f_obj, g_obj, views, stale, fp
            ),
            gate_phase="freshness",
        )
        return functools.partial(self._pair_fetch, entries, ent, *ent.extra)

    def _pair_refresh(self, index, ckey, f_obj, g_obj, views, stale,
                      fp) -> TierEntry:
        """The single-flight body: host table update when possible, full
        stack fetch + device sweep otherwise."""
        shards_t = fp[0]
        prof = current_profile()
        with prof.phase("freshness"):
            live = self._tier_versions(stale, (f_obj, g_obj), shards_t, "pair")
            ent = refresh_entry(
                stale, fp, views, live, PairRows, self.stats,
                self.MAX_PAIR_HOST_UPDATE_SHARDS,
            )
        if ent is not None:
            # Already resolved: its resolver never touches the device.
            self._pair_cache.store(ckey, ent)
            return ent
        vers_f, vers_g = live

        # Sweep path: fetch (build/splice) the stacks, then one dispatch.
        with prof.phase("stack_fetch"):
            fblock, _, bvers_f = self._get_block_with_versions(
                index, f_obj, shards_t
            )
            if g_obj is f_obj:
                gblock, bvers_g = fblock, bvers_f
            else:
                gblock, _, bvers_g = self._get_block_with_versions(
                    index, g_obj, shards_t
                )
        rf, rg = fblock.shape[1], gblock.shape[1]
        reason, pershard_ok = self._pair_gates(fblock.shape[0], rf, rg)
        if reason is not None:
            raise _Unsupported(reason)
        # Stack-build versions describe exactly what the sweep reads; the
        # pre-read live versions are the conservative fallback if the
        # stack entry was concurrently replaced (older vers only means a
        # redundant re-update next epoch, never staleness).
        vers_f = bvers_f if bvers_f is not None else vers_f
        vers_g = bvers_g if bvers_g is not None else vers_g
        self.stats.count("pair_stats_sweeps_total")
        with prof.phase("dispatch", span="pilosa.pair_stats"):
            flat = self._pair_program(pershard=pershard_ok)(fblock, gblock)
        # Shards whose fragments moved during the stack build/dispatch
        # record _VERS_STALE (see _confirm_vers): the swept content for
        # them is ambiguous relative to any version we could record.
        with prof.phase("freshness"):
            vers_f = self._confirm_vers(f_obj, shards_t, vers_f, tier="pair")
            vers_g = (
                vers_f if g_obj is f_obj
                else self._confirm_vers(g_obj, shards_t, vers_g, tier="pair")
            )
        # The in-flight device array is cached right away — pipelined
        # batches and the single-flight waiters share this one sweep
        # instead of each missing until the first resolver lands.
        ent = TierEntry(fp, flat, None, (vers_f, vers_g), (rf, rg))
        self._pair_cache.store(ckey, ent)
        return ent

    def _pair_gates(self, s_pad, rf, rg):
        """Serving-path size gates for a pair sweep, shared with
        preheat's program warming so the copies can't drift. Returns
        (reject_reason_or_None, pershard_ok): pershard_ok is the
        per-shard table RETENTION gate — a huge table (large rf*rg at
        many shards) costs more in readback + resident copies than the
        incremental path saves, so device-summed totals serve instead
        (those epochs then re-sweep); summed totals accumulate on
        device in int32 (psum'd under a mesh), so tall summed sweeps
        are rejected outright."""
        if rf * rg > (1 << 16):
            return "pair matrix too large", False
        d_stats = rf * rg + rf + rg
        pershard_ok = s_pad * d_stats * 4 <= self.MAX_PAIR_PERSHARD_BYTES
        if not pershard_ok and s_pad > MAX_PAIR_SHARDS:
            return "pair sweep exceeds int32 shard bound", False
        return None, pershard_ok

    def _pair_fetch(self, entries, ent, rf, rg) -> list[int]:
        """Resolve stats (device array on first touch, host np after) and
        derive the batch's counts."""
        prof = current_profile()
        if not isinstance(ent.value, np.ndarray):
            with prof.phase("device_wait"):
                self.programs.block_ready(ent.value)
        with prof.phase("readback"):
            return self._pair_fetch_inner(entries, ent, rf, rg)

    def _pair_fetch_inner(self, entries, ent, rf, rg) -> list[int]:
        stats = ent.value
        if not isinstance(stats, np.ndarray):
            raw = np.asarray(stats)  # ONE readback for all stats
            if raw.ndim == 2:  # per-shard [S, D] (gathered when meshed)
                pershard = raw
                totals = pershard.sum(axis=0, dtype=np.int64)
            else:  # summed totals [D] (retention gate; psum'd on mesh)
                pershard = None
                totals = raw.astype(np.int64)
            self._pair_cache.settle(ent, stats, totals, pershard)
        else:
            totals = stats
        return self._pair_resolve(entries, totals, rf, rg)

    @staticmethod
    def _pair_resolve(entries, stats_np, rf, rg) -> list[int]:
        p = stats_np[: rf * rg].reshape(rf, rg)
        f_ = stats_np[rf * rg : rf * rg + rf]
        g_ = stats_np[rf * rg + rf :]
        out = []
        for op, a, b in entries:
            ca = int(f_[a]) if a < rf else 0
            cb = int(g_[b]) if b < rg else 0
            pi = int(p[a, b]) if (a < rf and b < rg) else 0
            if op == "A":
                v = ca
            elif op == "B":
                v = cb
            elif op == "I":
                v = pi
            elif op == "U":
                v = ca + cb - pi
            elif op == "D":
                v = ca - pi
            else:  # X
                v = ca + cb - 2 * pi
            out.append(v)
        return out

    # -- GroupBy device path (VERDICT r2 #4) --------------------------------

    def _group_program(self, n: int, filtered: bool):
        """Stats program for GroupBy over 1 or 2 Rows children (+ optional
        filter slab): n=1 -> per-row counts [R] (fused XLA reduce), n=2 ->
        pair matrix [Rf, Rg] (the Pallas pair_stats sweep — GroupBy over
        two Rows IS the pair-count matrix, VERDICT r2 weak #6). The
        3-child case composes already-compiled programs instead (see
        _group3_stats): compiling a Pallas-in-scan mega-program cost ~30 s
        on real hardware for a one-line win."""
        key = ("groupby", n, filtered)
        with self._fns_lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn
        interpret = pallas_interpret()

        def stats(*args):
            stacks, filt = args[:n], (args[n] if filtered else None)
            f = stacks[0]
            if filt is not None:
                f = f & filt[:, None]
            if n == 1:
                return jnp.sum(
                    jax.lax.population_count(f).astype(jnp.int32),
                    axis=(0, 2, 3),
                )
            return pair_stats(f, stacks[1], interpret=interpret)[0]

        if self.mesh is None:
            fn = jax.jit(_named("groupby", stats))
        else:
            mesh = self.mesh

            def body(*args):
                return jax.lax.psum(stats(*args), mesh.axis)

            n_in = n + (1 if filtered else 0)
            fn = jax.jit(
                shard_map(
                    _named("groupby", body),
                    mesh=mesh.mesh,
                    in_specs=(P(mesh.axis),) * n_in,
                    out_specs=P(),
                    check_vma=False,
                )
            )
        fn = self._counted_launch("groupby", fn, key=key)
        with self._fns_lock:
            fn = self._fns.setdefault(key, fn)
        return fn

    def _group_tile_program(self, shapes, t_slots: int, filtered: bool,
                            pershard: bool):
        """AOT-compiled tiled N-field GroupBy sweep (ISSUE 17 tentpole,
        replacing a one-shot whole-tensor program whose grid compiled
        the combination count in). Each of
        the t_slots slots sweeps ONE live extra-row combination — picked
        in-kernel from rows_idx, with padded slots replaying slot 0
        under a zero `active` lane mask — against the full [Rf, Rg]
        face. Slot counts are power-of-two buckets and shapes are the
        exact stack shapes, so the compiled-program set is
        O(log K · shapes) and device_recompiles_total stays flat across
        cardinality changes. AOT (.lower().compile()) so the cold-path
        prewarm thread in _groupn_refresh truly compiles concurrently
        with the stack fetch instead of racing jit's first-call lock."""
        assert not (pershard and filtered)
        key = ("group_tile", shapes, t_slots, filtered, pershard)
        with self._fns_lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn
        n_extra = len(shapes) - 2
        slab_shape = shapes[0][:1] + shapes[0][2:]  # [S, W/128, 128]
        # Pinned to the backend's device when it is not the default one:
        # an AOT executable binds to the device its avals name.
        dev = self.blocks.device
        avals = [_sds(s, jnp.uint32, dev) for s in shapes]
        avals.append(_sds((t_slots, n_extra), jnp.int32, dev))
        avals.append(_sds((t_slots,), jnp.uint32, dev))
        if filtered:
            avals.append(_sds(slab_shape, jnp.uint32, dev))

        def flat(fb, gb, *rest):
            extras = rest[:n_extra]
            rows_idx, active = rest[n_extra], rest[n_extra + 1]
            filt = rest[n_extra + 2] if filtered else None
            if pershard:
                return group_tile_stats_pershard(
                    fb, gb, extras, rows_idx, active
                )
            return group_tile_stats(fb, gb, extras, rows_idx, active, filt)

        kind = "group_tile_pershard" if pershard else "group_tile"
        t0 = time.perf_counter()
        if self.mesh is None:
            fn = jax.jit(_named(kind, flat)).lower(*avals).compile()
        else:
            mesh = self.mesh
            n_sharded = 2 + n_extra + (1 if filtered else 0)
            if pershard:
                body = flat
                out_specs = P(None, mesh.axis)
            else:

                def body(*args):
                    return jax.lax.psum(flat(*args), mesh.axis)

                out_specs = P()
            in_specs = (
                (P(mesh.axis),) * (2 + n_extra)
                + (P(), P())
                + ((P(mesh.axis),) if filtered else ())
            )
            mapped = shard_map(
                _named(kind, body), mesh=mesh.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False,
            )
            shard3 = NamedSharding(mesh.mesh, P(mesh.axis))
            repl = NamedSharding(mesh.mesh, P())
            shardings = (
                [shard3] * (2 + n_extra) + [repl, repl]
                + ([shard3] if filtered else [])
            )
            fn = jax.jit(mapped).lower(*[
                jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
                for a, sh in zip(avals, shardings)
            ]).compile()
        self.programs.record_compile(
            kind, key, shapes, time.perf_counter() - t0
        )
        fn = self._counted_launch(kind, fn, key=key)
        with self._fns_lock:
            fn = self._fns.setdefault(key, fn)
        return fn

    def _group_live_rows(self, stacks):
        """Per-extra-field live row ids from the SWEPT stacks' per-row
        popcounts (the already-compiled n=1 GroupBy reduction). Sound by
        construction: the counts come from the same device arrays every
        tile sweeps, so a row pruned here is all-zero in every cell it
        would have produced — unlike the maintained TopN tables, whose
        capture version can trail the fetched stacks under churn."""
        prog = self._group_program(1, False)
        counts = self.programs.block_ready([prog(st) for st in stacks[2:]])
        return [
            np.nonzero(np.asarray(c) > 0)[0].astype(np.int32) for c in counts
        ]

    def _group_tiles(self, stacks, filt, combos, t_slots: int,
                     pershard: bool = False) -> np.ndarray:
        """Sweep every live combination, t_slots per launch: returns
        [K_live, Rf, Rg] totals (or [K_live, S_pad, Rf, Rg] pershard).
        Dispatch-then-read: all tiles are enqueued before the first
        blocking np.asarray, so device work overlaps readback. Each tile
        routes through _counted_launch, so the program ledger and
        EXPLAIN attribute per-tile occupancy/bytes/device-wait."""
        rf, rg = int(stacks[0].shape[1]), int(stacks[1].shape[1])
        k_live = len(combos)
        if k_live == 0:
            shape = (
                (0, int(stacks[0].shape[0]), rf, rg) if pershard
                else (0, rf, rg)
            )
            return np.zeros(shape, np.int32)
        prog = self._group_tile_program(
            tuple(s.shape for s in stacks), t_slots,
            filt is not None and not pershard, pershard,
        )
        repl = (
            NamedSharding(self.mesh.mesh, P()) if self.mesh is not None
            else None
        )
        occ_st = self.stats
        pending = []
        for c0 in range(0, k_live, t_slots):
            chunk = combos[c0:c0 + t_slots]
            occ = len(chunk)
            if occ < t_slots:
                chunk = np.concatenate(
                    [chunk, np.repeat(chunk[:1], t_slots - occ, axis=0)]
                )
            active = np.zeros(t_slots, np.uint32)
            active[:occ] = 1
            rows_idx = np.ascontiguousarray(chunk, dtype=np.int32)
            if repl is not None:
                rows_idx = jax.device_put(rows_idx, repl)
                active = jax.device_put(active, repl)
            args = tuple(stacks) + (rows_idx, active)
            if filt is not None and not pershard:
                args = args + (filt,)
            occ_st.count("groupby_tiles_total")
            occ_st.histogram("groupby_tile_occupancy", occ)
            pending.append((occ, prog(*args)))
        self.programs.block_ready([o for _, o in pending])
        return np.concatenate([np.asarray(o)[:occ] for occ, o in pending])

    def preheat(self, logger=None) -> int:
        """Pack + upload every field's stack for its available shards so
        first queries skip the cold host-pack + upload (~1 GB per field
        at the 1B-column shape). Returns the
        number of stacks made resident; honors the HBM budget (over-
        budget fields are skipped — they serve via row paging)."""
        n = 0
        for iname in list(self.holder.indexes):
            idx = self.holder.index(iname)
            if idx is None:
                continue
            # Queries assemble against the INDEX-WIDE shard union
            # (bitmap_call/_resident_shards use idx.available_shards), so
            # preheat must key stacks the same way — a field-local shard
            # set would fingerprint-miss on first query and the repack
            # would REPLACE the preheated entry.
            shards = tuple(
                # lint: allow-hot-serialize(preheat inventory is schema-sized, off the serving path)
                int(s) for s in idx.available_shards().to_array().tolist()
            )
            if not shards:
                continue
            for fname in list(idx.fields):
                try:
                    f = idx.field(fname)
                    if f is None:
                        continue
                    for view_name in list(f.views):
                        # BSI views preheat at full plane height or the
                        # first BSI query's min_rows mismatch repacks.
                        min_rows = 1
                        if view_name == bsi_view_name(fname) and (
                            f.options.type == FIELD_TYPE_INT
                        ):
                            min_rows = BSI_OFFSET_BIT + f.options.bit_depth
                        ev_before = self.blocks.evictions
                        block, _ = self.blocks.get(
                            iname, f, shards, view_name, min_rows
                        )
                        if self.blocks.evictions > ev_before:
                            # Budget full: later uploads would only evict
                            # earlier preheated stacks — stop here, but
                            # still compile the serving programs for
                            # whatever IS resident.
                            if logger is not None:
                                logger.printf(
                                    "preheat: HBM budget reached at %s/%s",
                                    iname, fname,
                                )
                            self._preheat_programs(iname, idx, shards, logger)
                            return n
                        if block is not None:
                            n += 1
                except Exception as e:  # noqa: BLE001 — best-effort: a
                    # concurrent schema change must not kill the thread.
                    if logger is not None:
                        logger.printf("preheat %s/%s failed: %s", iname, fname, e)
            self._preheat_programs(iname, idx, shards, logger)
        return n

    def _preheat_programs(self, iname, idx, shards, logger) -> None:
        """Compile the serving programs against the preheated stacks so
        the FIRST queries skip the XLA compile too (~20 s of q=0 at the
        start of a serving window in the soak harness). Programs are
        shape-keyed under jit, so one pair sweep + one TopN popcount
        per distinct stack shape (in EITHER pair order — plans keep
        query field order) warms every same-shaped field pair, with
        variants chosen by the same gates serving uses. Called DIRECTLY
        (not via the dispatch paths) so no stats-cache entries are
        created with preheat-time versions. Best-effort per item, like
        the stack loop."""

        def _log(what, e):
            if logger is not None:
                logger.printf("preheat %s %s failed: %s", what, iname, e)

        std_blocks = []
        for fname in list(idx.fields):
            try:
                # peek, never build: warming must not trigger uploads or
                # evictions (the budget path stops packing deliberately).
                b = self.blocks.peek(iname, fname, VIEW_STANDARD)
                if b is not None:
                    std_blocks.append(b)
            except Exception as e:  # noqa: BLE001
                _log(f"block {fname}", e)
        shapes_done = set()
        for b in std_blocks:
            if b.shape in shapes_done:
                continue
            shapes_done.add(b.shape)
            try:
                reduce_dev = self._topn_gates(b.shape[0], b.shape[1], False)[1]
                self.programs.block_ready(
                    self._program("topn_plain", None, reduce_dev)(b)
                )
            except Exception as e:  # noqa: BLE001
                _log("topn program", e)
        compiled = set()
        for fb in std_blocks:
            for gb in std_blocks:  # both orders: jit caches per shape tuple
                key = (fb.shape, gb.shape)
                if key in compiled:
                    continue
                reason, pershard_ok = self._pair_gates(
                    fb.shape[0], fb.shape[1], gb.shape[1]
                )
                if reason is not None:
                    continue  # serving rejects this shape: nothing to warm
                if len(compiled) >= 4:
                    # Each distinct combo is its own XLA compile (tens
                    # of seconds); fields nearly always share shapes, so
                    # cap the long tail. Only combos that actually
                    # dispatch consume cap slots.
                    return
                compiled.add(key)
                try:
                    # Waited for and dropped (no readback): the compile
                    # is the cost being fronted.
                    self.programs.block_ready(
                        self._pair_program(pershard=pershard_ok)(fb, gb)
                    )
                except Exception as e:  # noqa: BLE001
                    _log("pair program", e)

    @_pinning
    def group_by(self, index, c: Call, filter_call, child_rows, shards,
                 cap=None, aggregate=None) -> Optional[list]:
        """_group_by, with a None (the executor then runs the host
        iterator) counted as a host-path answer. `aggregate` names the
        int field of `aggregate=Sum(field=…)`, validated by the executor.
        The stacks it fetches stay pinned until its answer is read back."""
        out = self._group_by(index, c, filter_call, child_rows, shards, cap,
                             aggregate)
        if out is None:
            self._host_path("groupby", "not lowerable")
        return out

    def _served(self, source: str) -> None:
        """One GroupBy answered: `device` (a program ran for it), `cache`
        (the group tensor cache held it) or `tables` (the maintained host
        tables did). The executor counts `host`."""
        self.stats.with_tags(f"path:{source}").count("groupby_served_total")

    def _group_by(self, index, c: Call, filter_call, child_rows, shards,
                  cap, aggregate=None) -> Optional[list]:
        """Whole-query GroupBy: device programs compute the group-count
        tensor over every shard — one fused sweep for n<=2, the tiled
        slot engine over the popcount-pruned live combination space for
        n>=3 (ISSUE 17) — and the host enumerates nonzero groups in
        odometer order (reference groupByIterator semantics,
        executor.go:3063 — but exact counts instead of a per-shard
        bitmap recursion), stopping at `cap` entries when the executor
        passes its limit+offset bound. Returns None when not lowerable
        so the executor falls back to the host path."""
        children = c.children
        n = len(children)
        if n == 0:
            return None
        shards_t = tuple(shards)
        fields = []
        starts = []
        for child in children:
            if "from" in child.args or "to" in child.args:
                return None  # time-ranged Rows: host path unions quantum views
            fname = child.args.get("field") or child.args.get("_field")
            f_obj = self._field(index, fname)  # raises the reference error
            fields.append((fname, f_obj))
            prev, has_prev = child.uint64_arg("previous")
            starts.append(prev + 1 if has_prev else 0)
        if aggregate is not None:
            return self._group_sum(index, fields, starts, child_rows,
                                   shards_t, filter_call, aggregate, cap)
        if any(self.blocks.rowid_field(index, fo, shards_t)
               for _, fo in fields):
            return None  # a count per group of row ids: the host's
        # Unfiltered 1-/2-field groups ARE the maintained host tables:
        # the TopN rank vector and the pair-count matrix — both
        # refreshed incrementally under write churn, so these GroupBys
        # stay sub-ms warm instead of re-dispatching per epoch. No
        # stack fetch, no tensor cache.
        if filter_call is None and n <= 2:
            served = self._group_from_tables(index, fields, shards_t, n)
            if served is not None:
                stats_np, rs = served
                self._served("tables")
                return self._group_enumerate(
                    fields, starts, child_rows, rs, stats_np, n, cap
                )
        # Unfiltered N>=3: the maintained per-shard group tensor
        # (VERDICT r4 #1b) — write epochs splice dirty shard rows on the
        # host instead of re-dispatching the sweep. On a cold miss it
        # AOT-compiles the tile program concurrently with the stack
        # fetch.
        if filter_call is None and n >= 3:
            served = self._groupn_tensor(index, fields, shards_t)
            if served is not None:
                stats_np, rs = served
                self._served("tables")
                return self._group_enumerate(
                    fields, starts, child_rows, rs, stats_np, n, cap
                )
        # Group-tensor cache: the stats do not depend on candidate
        # restrictions (limit/column/previous filter only the host
        # enumeration), so the write epoch of the child views keys a
        # reusable tensor — same discipline as the pair/TopN caches.
        # Filtered tensors (ISSUE 17 — previously never cached) key
        # additionally on the filter tree's canonical PQL spelling and
        # fingerprint the epoch vector of every field the filter
        # references, so a write to a filter input invalidates exactly
        # like a write to a grouped field. Fingerprint captured BEFORE
        # the stack fetch: a write racing this query must yield a
        # never-matching entry, not a stale one.
        fkey = ffp = None
        if filter_call is not None:
            ffp = self._filter_epochs(index, filter_call)
            if ffp is not None:
                fkey = canonical_key(filter_call)
        ckey = cfp = hit = payload = None
        if filter_call is None or fkey is not None:
            ckey = ("groupby", index, tuple(f for f, _ in fields), fkey)
            cfp = fingerprint(
                shards_t, [fo.view(VIEW_STANDARD) for _, fo in fields]
            ) + (ffp,)
        try:
            stacks = [self._get_block(index, fo, shards_t)[0] for _, fo in fields]
            filt = None
            if filter_call is not None:
                spec, blocks, scalars = self._assemble(index, filter_call, shards_t)
                filt = self._program("vec", spec, False)(blocks, scalars)
        except _Unsupported:
            return None  # prewarm daemon finishes in the background;
            # fallback paths must not stall behind a compile they never
            # dispatch (code review r5).
        if stacks[0].shape[0] > MAX_PAIR_SHARDS:
            return None  # int32 accumulator bound (ops/kernels.py)
        rs = [s.shape[1] for s in stacks]
        # Per-tile accumulator face: the first two fields' row product
        # is a dense [Rf, Rg] plane in every slot, so it keeps the
        # pair-sweep bound. The EXTRA fields' product is no longer
        # bounded here — pruning + tiling cover it (the old 2^16
        # whole-product bail); MAX_GROUP_RESULT_CELLS gates the live
        # product after pruning instead.
        if n >= 2 and rs[0] * rs[1] > (1 << 16):
            return None
        if n <= 2 and int(np.prod(rs)) > (1 << 16):
            return None
        if ckey is not None:
            hit = self._agg_cache.hit(ckey, cfp)
            if hit is not None:
                payload = hit.value
        if hit is None:
            with current_profile().phase("dispatch", span="pilosa.group_by"):
                if n >= 3:
                    try:
                        payload = self._group_tiled_sweep(stacks, filt, rs)
                    except Exception as e:  # noqa: BLE001 — Mosaic VMEM/
                        # compile limits only real hardware can hit: host
                        # fallback answers the query correctly instead of
                        # a 500. Counted + logged once per shape so a
                        # hardware-only regression is visible (VERDICT r3
                        # weak #7).
                        self._count_device_fallback("group_tile", (n, filt is not None), e)
                        return None
                    if payload is None:
                        return None  # live product past the cell budget
                else:
                    args = tuple(stacks) + ((filt,) if filt is not None else ())
                    payload = ("dense", np.asarray(self.programs.block_ready(
                        self._group_program(n, filt is not None)(*args)
                    )))
            if ckey is not None:
                self._agg_cache.store(ckey, TierEntry(cfp, payload))
        self._served("device" if hit is None else "cache")
        if payload[0] == "dense":
            return self._group_enumerate(
                fields, starts, child_rows, rs, payload[1], n, cap
            )
        _, live_rows, stats_live = payload
        return self._group_enumerate_live(
            fields, starts, child_rows, rs, live_rows, stats_live, n, cap
        )

    # -- GroupBy with a Sum aggregate ----------------------------------------

    def _group_sum_program(self, n: int, filtered: bool, depth: int,
                           signed: bool):
        """`pilosa_group_sum`: the count masks of the int field's planes
        under the filter (ops/kernels.py group_sum_masks), then every
        listed row combination of the n grouped fields counted against
        each of them (group_sum_counts). One program per (n, filter,
        depth, sign); jit keys the stack shapes."""
        key = ("group_sum", n, filtered, depth, signed)
        with self._fns_lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn

        interpret = pallas_interpret()

        def body(*args):
            stacks, bsi_block = args[:n], args[n]
            combos, active, n_chunks = args[n + 1:n + 4]
            filt = args[n + 4] if filtered else None
            masks = group_sum_masks(filt, bsi_block, depth, signed)
            return self._psum(group_sum_counts(
                stacks, masks, combos, active, n_chunks, interpret=interpret
            ))

        body = _named("group_sum", body)
        if self.mesh is None:
            fn = jax.jit(body)
        else:
            ax = P(self.mesh.axis)
            fn = jax.jit(shard_map(
                body, mesh=self.mesh.mesh,
                in_specs=(ax,) * (n + 1) + (P(), P(), P())
                + ((ax,) if filtered else ()),
                out_specs=P(), check_vma=False,
            ))
        fn = self._counted_launch("group_sum", fn, key=key)
        with self._fns_lock:
            fn = self._fns.setdefault(key, fn)
        return fn

    def _stack_program(self, kind: str, stats, sharded: tuple):
        """One jitted reduction over a stack, `jit_pilosa_<kind>`: `stats`
        under the mesh's shard_map with its outputs psum-ed, an argument
        split on the shard axis where `sharded` says so and replicated
        elsewhere."""
        key = (kind, sharded)
        with self._fns_lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn

        def body(*args):
            return jax.tree.map(self._psum, stats(*args))

        body = _named(kind, body)
        if self.mesh is None:
            fn = jax.jit(body)
        else:
            ax = self.mesh.axis
            fn = jax.jit(shard_map(
                body, mesh=self.mesh.mesh,
                in_specs=tuple(P(ax) if s else P() for s in sharded),
                out_specs=P(), check_vma=False,
            ))
        fn = self._counted_launch(kind, fn, key=key)
        with self._fns_lock:
            fn = self._fns.setdefault(key, fn)
        return fn

    def _group_rows_program(self, filtered: bool):
        """`pilosa_groupby_rows`: the count of each listed row of a stack
        under the filter, int32[k], reading only those rows. An id past
        the stack's rows flags a padded slot and counts 0."""

        def stats(stack, ids, *filt):
            # One row leaf a slot, as a count program reads its rows
            # (_eval_spec): a slice of the resident stack fused into the
            # reduction that counts it, no gathered copy.
            valid = ids < stack.shape[1]
            safe = jnp.where(valid, ids, 0)
            counts = []
            for j in range(ids.shape[0]):
                row = jnp.take(stack, safe[j], axis=1)
                if filt:
                    row = row & filt[0]
                counts.append(jnp.sum(
                    jax.lax.population_count(row).astype(jnp.int32)
                ))
            return jnp.stack(counts) * valid

        return self._stack_program(
            "groupby_rows", stats, (True, False) + ((True,) if filtered else ())
        )

    def _overlap_program(self):
        """`pilosa_groupby_overlap`: a stack's per-row counts, int32[R],
        and the count of the union of its rows."""

        def stats(stack):
            per_row = jnp.sum(
                jax.lax.population_count(stack).astype(jnp.int32),
                axis=(0, 2, 3),
            )
            union = jax.lax.reduce(stack, np.uint32(0), jax.lax.bitwise_or,
                                   (1,))
            return per_row, jnp.sum(
                jax.lax.population_count(union).astype(jnp.int32)
            )

        return self._stack_program("groupby_overlap", stats, (True,))

    #: High parts of a row id one `pilosa_rowid_hist` launch counts: its
    #: two small stacks hold at most this many and 32 dense rows.
    ROWID_HIST_HIGHS = 32

    def _rowid_program(self, kind: str, make, *static):
        """`jit_pilosa_<kind>` over row ids (one device), counted as the
        launches of its kind: `make(*static)` is the program's body, and
        `static` keys it."""
        key = (kind,) + static
        with self._fns_lock:
            got = self._fns.get(key)
        if got is not None:
            return got
        prog = self._counted_launch(
            kind, jax.jit(_named(kind, make(*static))), key=key
        )
        with self._fns_lock:
            return self._fns.setdefault(key, prog)

    def _rowid_hist_launches(self, ids, n_rows: int, extra: tuple) -> list:
        """Launches of `pilosa_rowid_hist` that count a row-id stack's
        ids under the filter (`extra`: the filter slab, or nothing),
        their outputs read back in order the counts of ids 0, 1, … An id
        is split into its low five bits and the rest: one launch counts
        ROWID_HIST_HIGHS high parts against every low part (ops/kernels.py
        rowid_hist); a field of up to 1,024 rows is one launch."""
        bits = max(1, (n_rows - 1).bit_length())
        lo_bits = min(bits, 5)
        highs = 1 << (bits - lo_bits)
        hi_rows = min(highs, self.ROWID_HIST_HIGHS)
        interpret = pallas_interpret()

        def make(lo_bits, hi_rows, _filtered):
            def hist(ids, hi0, *filt):
                return rowid_hist(ids, filt[0] if filt else None, hi0,
                                  lo_bits, hi_rows, interpret=interpret)
            return hist

        prog = self._rowid_program("rowid_hist", make, lo_bits, hi_rows,
                                   bool(extra))
        s = ids.shape[0]
        self.blocks.note_transient(
            (hi_rows + (1 << lo_bits)) * s * WORDS_PER_SHARD * 4
        )
        outs = []
        for h0 in range(0, highs, hi_rows):
            outs.append(prog(ids, np.int32(h0), *extra))
            # The bit-rows a launch reads: the ids' bits and the filter.
            self.stats.count("rowid_hist_rows_total",
                             8 * ids.dtype.itemsize + 1)
        return outs

    def _rowid_build_launch(self, ids, rows):
        """The rows `rows` of a row-id stack as a dense stack
        [S, bucket, L, 128] (`pilosa_rowid_build`), padded to a slot
        bucket by empty rows so that few programs serve every count."""
        k = _slot_bucket(rows.size)
        padded = np.full(k, ROWID_NO_ROW, np.uint32)
        padded[:rows.size] = rows
        self.blocks.note_transient(k * ids.shape[0] * WORDS_PER_SHARD * 4)
        prog = self._rowid_program(
            "rowid_build", lambda: lambda ids, rows: rowid_rows(ids, rows)
        )
        self.stats.count("rowid_rows_built_total", int(rows.size))
        return prog(ids, padded)

    def _rows_disjoint(self, index: str, name: str, stack) -> bool:
        """Whether no column of `stack` lies in two of its rows: the sum
        of the rows' counts equals the count of their union (never
        smaller, so equal means no overlap), summed exactly on the host.
        Counted once a stack array, `pilosa_groupby_overlap`."""
        key = (index, name)
        ent = self._disjoint.get(key)
        if ent is not None and ent[0]() is stack:
            return ent[1]
        prof = current_profile()
        with prof.phase("dispatch", span="pilosa.group_sum"):
            out = self._overlap_program()(stack)
        with prof.phase("device_wait"):
            self.programs.block_ready(out)
        with prof.phase("readback"):
            per_row, union = (np.asarray(o) for o in out)
        verdict = int(per_row.sum(dtype=np.int64)) == int(union)
        self._disjoint[key] = (weakref.ref(stack), verdict)
        return verdict

    def _filter_candidates(self, index, names, stacks, filter_call,
                           rowids=None) -> list:
        """Per grouped field, the sorted row ids its live rows can only be
        among, or None (count the whole stack). A field has them where
        the filter, or a conjunct of an Intersect filter, names its rows
        (`_named_rows`) and no column of its stack lies in two rows: a
        column under the filter then lies in a named row and in no
        other. Whether a stack's rows are disjoint is read from the
        stack (`_rows_disjoint`), whatever the field's type: a mutex
        field loaded by `import_roaring` can hold a column in two rows,
        and both count it. A field held as row ids (`rowids[i]` its row
        count) holds a column in one row by construction."""
        out = [None] * len(names)
        if filter_call is None:
            return out
        rowids = rowids or [None] * len(names)
        conjuncts = (filter_call.children if filter_call.name == "Intersect"
                     else [filter_call])
        for i, (name, stack) in enumerate(zip(names, stacks)):
            ids = None
            for call in conjuncts:
                named = _named_rows(call, name)
                if named is not None:
                    ids = named if ids is None else ids & named
            if ids is None:
                continue
            n_rows = rowids[i]
            if n_rows is None:
                if not self._rows_disjoint(index, name, stack):
                    continue
                n_rows = stack.shape[1]
            out[i] = np.array(
                sorted(r for r in ids if r < n_rows), dtype=np.int64
            )
        return out

    def _group_sum(self, index, fields, starts, child_rows, shards_t,
                   filter_call, agg_name, cap) -> Optional[list]:
        """GroupBy(…, aggregate=Sum(field=agg_name)): each group's count,
        and the sum of the int field over the group's columns that hold a
        value. The answer is cached like a filtered group tensor, keyed by
        the int field too and fingerprinted by its BSI view, so another
        aggregate field or a write to this one is never served an entry.
        None where the device cannot answer (the executor's host iterator
        then does)."""
        f_agg = self._field(index, agg_name)
        opts = f_agg.bsi_group()
        if opts.bit_depth > MAX_BSI_DEPTH:
            return None
        vn = bsi_view_name(agg_name)
        fkey = ffp = None
        if filter_call is not None:
            ffp = self._filter_epochs(index, filter_call)
            if ffp is not None:
                fkey = canonical_key(filter_call)
        ckey = cfp = payload = None
        if filter_call is None or fkey is not None:
            ckey = ("group_sum", index, tuple(f for f, _ in fields), fkey,
                    agg_name)
            # Captured BEFORE the stack fetch, as _group_by's.
            cfp = fingerprint(
                shards_t,
                [fo.view(VIEW_STANDARD) for _, fo in fields] + [f_agg.view(vn)],
            ) + (ffp,)
            hit = self._agg_cache.hit(ckey, cfp)
            if hit is not None:
                payload = hit.value
        if payload is not None:
            self._served("cache")
        else:
            prof = current_profile()
            try:
                # A field held as row ids brings its ids and its row
                # count; a dense one its stack and None.
                stacks, rowids = [], []
                for _, fo in fields:
                    got = self._get_rowid(index, fo, shards_t)
                    if got is None:
                        stacks.append(self._get_block(index, fo, shards_t)[0])
                        rowids.append(None)
                    else:
                        stacks.append(got[0])
                        rowids.append(got[1])
                bsi_block, _ = self._get_block(
                    index, f_agg, shards_t, view_name=vn,
                    min_rows=BSI_OFFSET_BIT + opts.bit_depth,
                )
                filt = None
                if filter_call is not None:
                    with prof.phase("plan"):
                        spec, blocks, scalars = self._assemble(
                            index, filter_call, shards_t
                        )
                    with prof.phase("dispatch", span="pilosa.group_sum"):
                        filt = self._program("vec", spec, False)(
                            blocks, scalars
                        )
            except _Unsupported:
                return None
            if stacks[0].shape[0] > MAX_PAIR_SHARDS:
                return None  # int32 accumulator bound (ops/kernels.py)
            cands = self._filter_candidates(
                index, [f for f, _ in fields], stacks, filter_call, rowids
            )
            payload = self._group_sum_sweep(stacks, bsi_block, filt, opts,
                                            cands, rowids)
            if payload is None:
                return None
            if ckey is not None:
                self._agg_cache.store(ckey, TierEntry(cfp, payload))
            self._served("device")
        _, live, counts, sums = payload
        return self._group_sum_enumerate(
            [f for f, _ in fields], starts, child_rows, live, counts, sums, cap
        )

    def _group_sum_sweep(self, stacks, bsi_block, filt, opts, cands,
                         rowids=None):
        """("sum", live rows a field, counts int64[K], sums int64[K]) over
        the K live combinations in odometer order, or None past
        MAX_GROUP_SUM_COMBOS. A row with no column under the filter is
        in no group, so only rows that hold one are combined (what keeps
        a 250 x 250 x 7 grouping of two cities of one nation at 2 x 2 x 6).
        A field with candidates (`_filter_candidates`) has only those
        counted, by `pilosa_groupby_rows` where they are few; the count
        of the BSI block's sign row under the filter says whether any
        column there holds a negative value. A field held as row ids
        (`rowids[i]` its row count) has its candidates taken as its live
        rows, or else its ids counted under the filter by
        `pilosa_rowid_hist`; its live rows are then built into a small
        dense stack (`pilosa_rowid_build`) that the count kernel reads
        by position. Each round of launches is timed as the other device
        reads are: `dispatch`, then `device_wait` until the device is
        done, then `readback`."""
        depth = opts.bit_depth
        filtered = filt is not None
        extra = (filt,) if filtered else ()
        prof = current_profile()
        repl = (NamedSharding(self.mesh.mesh, P()) if self.mesh is not None
                else None)
        prune = self._group_program(1, filtered)
        count_rows = self._group_rows_program(filtered)
        rowids = list(rowids or [None] * len(stacks))

        for ids in cands:
            self.stats.with_tags(
                "path:stack" if ids is None else "path:filter"
            ).count("group_prune_fields_total")
        # The int field's sign row rides along as one more candidate list.
        lists = list(zip(stacks, cands, rowids))
        if depth > 0:
            lists.append((bsi_block, np.array([BSI_SIGN_BIT]), None))
        # Per list: its launches, its ids, whether the launches counted
        # the ids alone (padded to a slot bucket by an id past the stack)
        # or every row (of a stack, or of row ids by id). Row ids with
        # candidates launch nothing: the candidates are their live rows.
        pending = []
        with prof.phase("dispatch", span="pilosa.group_sum"):
            for stack, ids, n_rows in lists:
                if n_rows is not None:
                    pending.append(
                        ([], ids) if ids is not None else
                        (self._rowid_hist_launches(stack, n_rows, extra), None)
                    )
                elif ids is not None and ids.size <= MAX_GROUP_ROW_SLOTS and (
                    _slot_bucket(ids.size) * GROUP_ROWS_SHARE <= stack.shape[1]
                ):
                    slots = np.full(_slot_bucket(ids.size), stack.shape[1],
                                    np.int32)
                    slots[:ids.size] = ids
                    if repl is not None:
                        slots = jax.device_put(slots, repl)
                    pending.append(([count_rows(stack, slots, *extra)], ids))
                else:
                    pending.append(([prune(stack, *extra)], None))
        with prof.phase("device_wait"):
            self.programs.block_ready([o for outs, _ in pending for o in outs])
        with prof.phase("readback"):
            counts = [
                np.concatenate([np.asarray(o).reshape(-1) for o in outs])
                if outs else None
                for outs, _ in pending
            ]
        live = []
        for c, (_, ids), (_, _, n_rows) in zip(counts, pending, lists):
            if c is None:
                live.append(ids)
            elif ids is None:
                live.append(np.flatnonzero(c[:n_rows] > 0) if n_rows is not None
                            else np.flatnonzero(c > 0))
            else:
                live.append(ids[c[:ids.size] > 0])
        signed = depth > 0 and live.pop().size > 0
        k_live = 1
        for lr in live:
            k_live *= lr.size
        if k_live > MAX_GROUP_SUM_COMBOS:
            return None
        live_t = tuple(tuple(int(r) for r in lr) for lr in live)
        if k_live == 0:
            empty = np.zeros(0, np.int64)
            return ("sum", live_t, empty, empty)
        # Row ids to dense: a field's live rows as a stack the count
        # kernel reads by position (0 .. live - 1).
        stacks, at = list(stacks), list(live)
        with prof.phase("dispatch", span="pilosa.group_sum"):
            for i, n_rows in enumerate(rowids):
                if n_rows is not None:
                    stacks[i] = self._rowid_build_launch(stacks[i], live[i])
                    at[i] = np.arange(live[i].size)
        live = at
        grids = np.meshgrid(*live, indexing="ij")
        combos = np.stack([g.ravel() for g in grids], axis=1).astype(np.int32)
        prog = self._group_sum_program(len(stacks), filtered, depth, signed)
        # What a launch has to read once: its combinations' rows of each
        # field, the BSI block (exists, sign and the planes) and the filter.
        fixed = depth + 2 + (1 if filtered else 0)
        pending = []
        with prof.phase("dispatch", span="pilosa.group_sum"):
            for c0 in range(0, k_live, GROUP_SUM_SLOTS):
                chunk = combos[c0:c0 + GROUP_SUM_SLOTS]
                occ = chunk.shape[0]
                slots = np.zeros((GROUP_SUM_SLOTS, chunk.shape[1]), np.int32)
                slots[:occ] = chunk
                active = np.zeros(GROUP_SUM_SLOTS, np.uint32)
                active[:occ] = 1
                steps = -(-occ // GROUP_SUM_CHUNK)
                n_chunks = np.int32(steps)
                if repl is not None:
                    slots, active, n_chunks = (
                        jax.device_put(a, repl)
                        for a in (slots, active, n_chunks)
                    )
                args = tuple(stacks) + (bsi_block, slots, active, n_chunks)
                if filtered:
                    args += (filt,)
                self.stats.count(
                    "group_sum_rows_total",
                    fixed + sum(np.unique(chunk[:, j]).size
                                for j in range(chunk.shape[1])),
                )
                # A step reads every mask whatever its occupancy: the
                # sweep's cost a combination is steps over combinations.
                self.stats.count("group_sum_steps_total", steps)
                self.stats.count("group_sum_combos_total", occ)
                pending.append((occ, prog(*args)))
        with prof.phase("device_wait"):
            self.programs.block_ready([o for _, o in pending])
        with prof.phase("readback"):
            out = np.concatenate(
                [np.asarray(o)[:occ] for occ, o in pending]
            ).astype(np.int64)
        counts = out[:, 0]
        planes = out[:, 2:2 + depth]
        if signed:
            planes = planes - 2 * out[:, 2 + depth:2 + 2 * depth]
        if depth <= 31:
            weights = np.left_shift(np.int64(1), np.arange(depth, dtype=np.int64))
            raw = planes @ weights
        else:  # exact in Python ints, then int64 like every Sum
            raw = np.array(
                planes.astype(object) @ np.array([1 << p for p in range(depth)],
                                                 dtype=object),
                dtype=np.int64,
            )
        sums = raw + opts.base * out[:, 1]
        return ("sum", live_t, counts, sums)

    @staticmethod
    def _group_sum_enumerate(names, starts, child_rows, live, counts, sums,
                             cap):
        """The non-empty groups of a grouped Sum among each field's
        candidates (its rows from `previous` + 1, or its Rows(limit=…)
        answer), in odometer order, the first `cap` of them."""
        from pilosa_tpu.exec.result import GroupCounts

        keep = []
        for lr, lo, rows in zip(live, starts, child_rows):
            lr = np.asarray(lr, dtype=np.int64)
            ok = lr >= lo
            if rows is not None:
                ok &= np.isin(lr, np.fromiter(rows, dtype=np.int64,
                                              count=len(rows)))
            keep.append(np.flatnonzero(ok))
        shape = tuple(len(lr) for lr in live)
        sub = np.ix_(*keep)
        cnt = counts.reshape(shape)[sub]
        hit = np.nonzero(cnt > 0)
        if cap is not None:
            hit = tuple(h[:cap] for h in hit)
        rows = np.stack(
            [np.asarray(lr, dtype=np.int64)[k][h]
             for lr, k, h in zip(live, keep, hit)],
            axis=1,
        )
        return GroupCounts(names, rows, cnt[hit], sums=sums.reshape(shape)[sub][hit])

    def _filter_epochs(self, index, filter_call):
        """Epoch fingerprint of every field a GroupBy filter tree
        references: sorted (field, ((view, generation), ...)) tuples.
        None = uncacheable (missing field — the assemble path raises
        the reference error — or a time-ranged call, whose view set
        depends on the clock, not an epoch)."""
        idx = self.holder.index(index)
        if idx is None:
            return None
        names = set()
        stack = [filter_call]
        while stack:
            call = stack.pop()
            if "from" in call.args or "to" in call.args:
                return None
            fn = call.args.get("field") or call.args.get("_field")
            if isinstance(fn, str):
                names.add(fn)
            for k, v in call.args.items():
                if isinstance(v, Call):
                    stack.append(v)
                elif not is_reserved_arg(k) and k != "field":
                    # Bitmap leaves spell the field as the arg KEY —
                    # Row(a=1), Row(v > 3) (Call.field_arg semantics) —
                    # so every non-reserved key is a field reference.
                    names.add(k)
            stack.extend(call.children)
        out = []
        for fn in sorted(names):
            f = idx.field(fn)
            if f is None:
                return None
            vs = tuple(sorted(
                (vn, f.view(vn).generation)
                for vn in list(f.views)
                if f.view(vn) is not None
            ))
            out.append((fn, vs))
        return tuple(out)

    def _agg_cache_charge(self, entries) -> None:
        """Ledger charge for the aggregate/group-tensor cache: total
        host bytes pinned by cached payload arrays. The table calls it
        under its lock after every store/evict so the gauge tracks the
        LRU exactly."""
        total = 0
        for ent in entries:
            payload = ent.value
            if isinstance(payload, tuple):
                total += sum(
                    p.nbytes for p in payload if isinstance(p, np.ndarray)
                )
            elif isinstance(payload, np.ndarray):
                total += payload.nbytes
        self.stats.gauge("agg_cache_bytes", total)

    def _group_live_plan(self, stacks, rs, max_cells=None):
        """Popcount pruning (ISSUE 17): a combination containing a
        globally-empty row is all-zero in EVERY cell, so only live
        combinations are swept. Returns (live_rows — per extra field the
        globally-live row ids —, combos int32[K_live, E] in odometer
        order, last field fastest, the slot bucket, the EXPLAIN note),
        or None when the live product exceeds `max_cells`."""
        live_rows = self._group_live_rows(stacks)
        k_nominal = 1
        for r in rs[2:]:
            k_nominal *= int(r)
        k_live = 1
        for lr in live_rows:
            k_live *= len(lr)
        pruned = k_nominal - k_live
        if pruned:
            self.stats.count("groupby_pruned_groups_total", pruned)
        if max_cells is not None and k_live * rs[0] * rs[1] > max_cells:
            return None
        t_slots = (
            _slot_bucket(min(k_live, MAX_GROUP_TILE_SLOTS)) if k_live else 0
        )
        if k_live:
            grids = np.meshgrid(*live_rows, indexing="ij")
            combos = np.stack(
                [g.ravel() for g in grids], axis=1
            ).astype(np.int32)
        else:
            combos = np.zeros((0, len(rs) - 2), np.int32)
        note = {
            "liveGroups": k_live,
            "prunedGroups": pruned,
            "slots": t_slots,
            "tiles": (k_live + t_slots - 1) // t_slots if k_live else 0,
        }
        return live_rows, combos, t_slots, note

    @staticmethod
    def _explain_tiles(note) -> None:
        ex = getattr(current_profile(), "explain", None)
        if ex is not None:
            ex._node().setdefault("groupbyTiles", []).append(note)

    def _group_tiled_sweep(self, stacks, filt, rs):
        """Prune + tile + sweep the n>=3 group tensor: returns the
        ("live", live_rows, stats_live) payload, or None when the live
        combination product exceeds the host cell budget. stats_live is
        [K_live, Rf, Rg] in odometer order over the live rows."""
        plan = self._group_live_plan(stacks, rs, MAX_GROUP_RESULT_CELLS)
        if plan is None:
            return None
        live_rows, combos, t_slots, note = plan
        stats_live = self._group_tiles(stacks, filt, combos, t_slots)
        self._explain_tiles(note)
        return (
            "live",
            tuple(tuple(int(r) for r in lr) for lr in live_rows),
            stats_live,
        )

    def _group_from_tables(self, index, fields, shards_t, n):
        """(stats, rs) for an unfiltered 1-/2-field GroupBy from the
        incrementally-maintained host tables, or None when a table
        can't serve (budget/bounds) and the tensor/host path should
        run. Row counts stay under the tensor path's 2^16 bound so
        tall fields keep falling through to the container-walking host
        iterator instead of a huge Python enumeration."""
        if n == 1:
            f_obj = fields[0][1]
            v = f_obj.view(VIEW_STANDARD)
            if v is not None:
                # Bound-check BEFORE computing the rank vector: a tall
                # field would otherwise pay a full paged device sweep
                # just to discover the result gets discarded here.
                max_row = max(
                    (fr.max_row_id for fr in (v.fragment(s) for s in shards_t)
                     if fr is not None),
                    default=0,
                )
                if max_row + 1 > (1 << 16):
                    return None
            counts = self._topn_counts(index, f_obj, fields[0][0], shards_t)
            if counts.size > (1 << 16):
                return None
            return counts.astype(np.int64), [counts.size]
        pm = self._pair_matrix(index, fields[0][0], fields[1][0], shards_t)
        if pm is None:
            return None
        matrix, rf, rg = pm
        return matrix, [rf, rg]

    def _pair_matrix(self, index, fa, fb, shards_t):
        """The pair-count matrix [rf, rg] through the same single-flight
        + incremental machinery as count batches. None when the pair
        path can't serve (HBM budget, size bounds, eviction race)."""
        try:
            resolver = self._pair_batch_dispatch(index, ([], fa, fb), shards_t)
        except _Unsupported:
            return None
        resolver()  # force readback so the entry's stats are host np
        ent = self._pair_cache.get((index, fa, fb))
        if (
            ent is None
            or ent.fp[0] != shards_t
            or not isinstance(ent.value, np.ndarray)
        ):
            return None
        rf, rg = ent.extra
        return ent.value[: rf * rg].reshape(rf, rg), rf, rg

    #: Slab-tier budget for host groupN re-derives: words ANDed per
    #: epoch (K*rf*rg*W per shard). Past this a device re-dispatch is
    #: cheaper than the numpy sweep on this one-core host.
    MAX_GROUPN_HOST_SLAB_WORDS = 1 << 29

    def _groupn_predicted_shapes(self, fobjs, views, shards_t):
        """The stack shapes a dispatch for these fields WILL use —
        computable from fragment heights without packing anything, so
        the sweep program can AOT-compile while the stacks build."""
        s = self.blocks._pad_shards(len(shards_t))
        shapes = []
        for v in views:
            n_rows = 1
            if v is not None:
                n_rows = max(
                    [
                        fr.max_row_id + 1
                        for fr in (v.fragment(sh) for sh in shards_t)
                        if fr is not None
                    ]
                    + [1]
                )
            shapes.append(stack_shape(s, _padded_rows(n_rows)))
        return tuple(shapes)

    def _groupn_tensor(self, index, fields, shards_t):
        """(stats int64[K,rf,rg], rs) for an unfiltered N>=3 GroupBy from
        the maintained per-shard table (VERDICT r4 #1b), or None when
        this path can't serve (repeated field, bounds) and the
        generic tensor path should run. Write epochs resolve on the
        host: point writes delta-apply against probes of the other
        fields, anything else re-derives just the dirty shards' rows —
        no stack fetch, no device round trip, same two-tier design and
        exactness discipline as the pair table. Mesh-capable since
        ISSUE r13: the cold sweep runs the tiled pershard kernel under
        shard_map (per-device shard chunks, output gathered once at
        readback) and the host table then absorbs churn exactly as on
        one chip. Cold sweeps prune + tile since ISSUE 17: only live
        extra-row combinations are dispatched, in slot-bucketed tiles
        that scatter back into the dense retained table."""
        fobjs = [fo for _, fo in fields]
        if len({id(f) for f in fobjs}) != len(fobjs):
            return None  # repeated field: delta ordering is ambiguous
        ckey = ("groupn", index, tuple(fn for fn, _ in fields))
        views = [f.view(VIEW_STANDARD) for f in fobjs]
        ent = self._groupn_cache.serve(
            ckey, shards_t, views,
            lambda stale, fp: self._groupn_refresh(
                index, ckey, fobjs, views, stale, fp
            ),
        )
        return None if ent is None else (ent.value, ent.extra)

    def _groupn_refresh(self, index, ckey, fobjs, views, stale, fp):
        """The single-flight body: the stored entry, or None when no
        table can be kept at this geometry."""
        shards_t = fp[0]
        # Fingerprint missed: a dispatch MAY be coming — start the
        # sweep's AOT compile now (predicted shapes, background
        # thread) so it overlaps the stack fetch on a cold path.
        # Costs one cheap fragment-height walk; if the incremental
        # tier absorbs the epoch the thread just warms the cache.
        shapes = self._groupn_predicted_shapes(fobjs, views, shards_t)
        d_pred = 1
        for sh in shapes:
            d_pred *= sh[1]
        if shapes[0][0] * d_pred * 4 > self.MAX_PAIR_PERSHARD_BYTES:
            # A table at this geometry could never be retained
            # (dispatch would bail on the same bound after packing
            # everything): bail BEFORE the prewarm compile and the
            # stack fetch — the generic tiled path (pruned, and
            # cacheable since ISSUE 17) serves instead.
            return None
        k_pred = 1
        for sh in shapes[2:]:
            k_pred *= sh[1]
        t_pred = _slot_bucket(min(k_pred, MAX_GROUP_TILE_SLOTS))
        with self._fns_lock:
            compiled = (
                "group_tile", shapes, t_pred, False, True
            ) in self._fns
        if not compiled:
            from pilosa_tpu.utils.threads import spawn

            spawn(
                "groupby-prewarm",
                lambda: self._group_tile_program(
                    shapes, t_pred, False, True
                ),
                name="groupn-prewarm",
            )
        live = self._tier_versions(stale, fobjs, shards_t, "groupn")
        ent = refresh_entry(
            stale, fp, views, live, GroupNRows, self.stats,
            self.MAX_PAIR_HOST_UPDATE_SHARDS,
            self.MAX_GROUPN_HOST_SLAB_WORDS,
        )
        if ent is None:
            ent = self._groupn_dispatch(index, fobjs, fp, live)
        if ent is not None:
            self._groupn_cache.store(ckey, ent)
        return ent

    def _groupn_dispatch(self, index, fobjs, cfp, live):
        """The cold sweep: the entry to store, or None when the generic
        path must decide (HBM budget, bounds, a device failure)."""
        shards_t = cfp[0]
        stacks = []
        verss = []
        try:
            for i, f in enumerate(fobjs):
                block, rp, vers = self.blocks.get_with_versions(
                    index, f, shards_t
                )
                if block is None:
                    return None  # over HBM budget: generic path decides
                stacks.append(block)
                verss.append(vers if vers is not None else live[i])
        except _Unsupported:
            return None
        rs = [int(s.shape[1]) for s in stacks]
        k_total = 1
        for rh in rs[2:]:
            k_total *= rh
        d_stats = k_total * rs[0] * rs[1]
        s_pad = stacks[0].shape[0]
        # The int32 accumulator bound applies to what the KERNEL sees:
        # the whole shard axis on one chip, the per-device chunk under a
        # mesh (shard_map splits the axis before the kernel runs). The
        # bound is per-tile now — only the [Rf, Rg] face must fit; the
        # extras product is covered by tiling (the old 2^16 whole-
        # product bail, lifted by ISSUE 17).
        s_kernel = s_pad // (self.mesh.n if self.mesh is not None else 1)
        if s_kernel > MAX_PAIR_SHARDS or rs[0] * rs[1] > (1 << 16):
            return None
        if s_pad * d_stats * 4 > self.MAX_PAIR_PERSHARD_BYTES:
            return None  # table too big to retain: generic path sweeps
        # Only live combinations are swept and scattered; pruned slots
        # of the dense retained table stay exactly zero.
        _, combos, t_slots, note = self._group_live_plan(stacks, rs)
        k_live = combos.shape[0]
        try:
            with current_profile().phase("dispatch", span="pilosa.groupn"):
                tiles = self._group_tiles(
                    stacks, None, combos, t_slots, pershard=True
                )
        except Exception as e:  # noqa: BLE001 — Mosaic/VMEM limits only
            # real hardware hits; the generic path answers instead.
            self._count_device_fallback("group_tile_pershard", tuple(rs), e)
            return None
        self._explain_tiles(note)
        # Scatter the live tiles [K_live, S_pad, rf, rg] into the dense
        # retained table rows [S_real, K*rf*rg] at their odometer slots
        # (combos carry row IDS; flat k = odometer over rs[2:]).
        pershard = np.zeros((len(shards_t), d_stats), np.int32)
        if k_live:
            flat = None
            for t in range(combos.shape[1]):
                col = combos[:, t].astype(np.int64)
                flat = col if flat is None else flat * rs[2 + t] + col
            view = pershard.reshape(len(shards_t), k_total, rs[0] * rs[1])
            view[:, flat, :] = (
                tiles[:, : len(shards_t)]
                .transpose(1, 0, 2, 3)
                .reshape(len(shards_t), k_live, rs[0] * rs[1])
            )
        totals = (
            pershard.sum(axis=0, dtype=np.int64).reshape(k_total, rs[0], rs[1])
        )
        # The sweep read stack content packed at-or-after the recorded
        # versions: stale out any shard that moved. Journal-backed since
        # ISSUE 17 — O(dirty) locked reads per field instead of the full
        # O(S) walk that cost the r13 groupby leg 12 full walks.
        vers_rec = tuple(
            self._confirm_vers_journal(
                f, shards_t, verss[i], cfp[1][i], tier="groupn"
            )
            for i, f in enumerate(fobjs)
        )
        return TierEntry(cfp, totals, pershard, vers_rec, rs)

    @staticmethod
    def _group_candidates(starts, child_rows, rs):
        """Per field, the candidate row ids in the order the reference
        iterator visits them (a child's pre-computed Rows, else every row
        from `previous` + 1), as int64 arrays. A row at or past the
        stack's height holds no bit, so it is left out here."""
        cand = []
        for lo, rows, height in zip(starts, child_rows, rs):
            if rows is None:
                cand.append(np.arange(min(lo, height), height, dtype=np.int64))
                continue
            c = np.fromiter(rows, dtype=np.uint64, count=len(rows))
            cand.append(
                c[(c >= min(lo, height)) & (c < height)].astype(np.int64)
            )
        return cand

    @staticmethod
    def _group_cells(fields, tensor, index, rows, cap):
        """The non-empty groups of `tensor` (one axis a field, in child
        order) restricted to `index` (per axis, positions in candidate
        order; `rows` the row ids they stand for), as GroupCounts.
        np.nonzero walks the restricted tensor in C order, first axis
        slowest, which is the reference groupByIterator's odometer
        (executor.go:3063); the first `cap` cells are exactly where its
        loop would have stopped."""
        from pilosa_tpu.exec.result import GroupCounts

        sub = tensor[np.ix_(*index)]
        hit = np.nonzero(sub > 0)
        if cap is not None:
            hit = tuple(h[:cap] for h in hit)
        return GroupCounts(
            [name for name, _ in fields],
            np.stack([r[h] for r, h in zip(rows, hit)], axis=1),
            sub[hit].astype(np.int64),
        )

    def _group_enumerate(self, fields, starts, child_rows, rs, stats_np, n,
                         cap=None):
        """Candidate enumeration over the group stats (tensor or table),
        matching the reference groupByIterator's ordering. Keeps the
        first `cap` nonzero groups when set: the executor's limit+offset
        bound is a prefix of the odometer order, so the cut is exact."""
        cand = self._group_candidates(starts, child_rows, rs)
        if n >= 3:
            # The tensor's k axis runs over fields 3..n (last fastest —
            # the tile odometer's decomposition order), while enumeration
            # order is child order (first field outermost).
            stats_np = np.moveaxis(
                stats_np.reshape(*rs[2:], rs[0], rs[1]), (-2, -1), (0, 1)
            )
        return self._group_cells(fields, stats_np, cand, cand, cap)

    def _group_enumerate_live(self, fields, starts, child_rows, rs,
                              live_rows, stats_live, n, cap=None):
        """Enumeration over the PRUNED group tensor [K_live, Rf, Rg]
        (ISSUE 17), whose k axis is an odometer over POSITIONS in each
        extra field's live-row list: an extra field's candidates are
        those of its rows that are live, in candidate order, each with
        its position. Combinations pruned before dispatch are genuinely
        absent here: they contained a globally-empty row, so their count
        is zero and the reference iterator would skip them too."""
        cand = self._group_candidates(starts, child_rows, rs)
        index = cand[:2]
        rows = cand[:2]
        for c, lr, height in zip(cand[2:], live_rows, rs[2:]):
            pos = np.full(height, -1, dtype=np.int64)
            pos[np.asarray(lr, dtype=np.int64)] = np.arange(len(lr))
            at = pos[c]
            index.append(at[at >= 0])
            rows.append(c[at >= 0])
        tensor = np.moveaxis(
            stats_live.reshape(*(len(lr) for lr in live_rows), rs[0], rs[1]),
            (-2, -1), (0, 1),
        )
        return self._group_cells(fields, tensor, index, rows, cap)

    # -- generic batched scan path -----------------------------------------

    @staticmethod
    def _padded_slot_scalars(per_call: list[tuple], qb: int) -> tuple:
        """Stack per-call scalar tuples into [Qb, ...] slot arrays padded
        to the slot bucket (padding replays slot 0), and append the [Qb]
        uint32 lane mask the batched program's scan consumes last —
        the fixed-shape-slot / ragged-occupancy layout."""
        q = len(per_call)
        n_scalars = len(per_call[0])
        out = []
        for j in range(n_scalars):
            rows = [np.asarray(pc[j], dtype=np.uint32) for pc in per_call]
            rows.extend(rows[:1] * (qb - q))
            out.append(np.stack(rows))
        active = np.zeros(qb, dtype=np.uint32)
        active[:q] = 1
        out.append(active)
        return tuple(out)

    @staticmethod
    def _dedupe_slots(assembled: dict, idxs: list[int]):
        """(unique, slot_of): the calls of `idxs` that differ in their
        scalar bytes, in order, and each call's slot among them."""
        slot_index: dict[tuple, int] = {}
        unique: list[int] = []
        slot_of: dict[int, int] = {}
        for i in idxs:
            k = tuple(
                np.asarray(s, dtype=np.uint32).tobytes()
                for s in assembled[i][1]
            )
            if k not in slot_index:
                slot_index[k] = len(unique)
                unique.append(i)
            slot_of[i] = slot_index[k]
        return unique, slot_of

    def _assemble_batch(self, index, calls, shards_t):
        """(groups, assembled, fallbacks): the calls that assemble,
        grouped by (spec, leaf blocks) and each with its (blocks,
        scalars), and the indices of those with no device lowering."""
        groups: dict = {}
        assembled: dict[int, tuple] = {}
        fallbacks: list[int] = []
        for i, c in enumerate(calls):
            try:
                spec, blocks, scalars = self._assemble(index, c, shards_t)
            except _Unsupported:
                fallbacks.append(i)
                continue
            # Blocks are cache-owned arrays, so identity keys the
            # group: same spec shape with different views/fields means
            # different block objects and must not share one dispatch.
            key = (spec, tuple(id(b) for b in blocks))
            groups.setdefault(key, []).append(i)
            assembled[i] = (blocks, scalars)
        return groups, assembled, fallbacks

    @_pinning
    def _generic_batch_dispatch(self, index, calls, shards_t, batch=None):
        """Group same-(spec, leaf-blocks) calls into fused scan dispatches:
        row ids become [Q] traced slot vectors, one program per group.
        Slot counts pad to a power-of-two bucket (_slot_bucket) so batch
        occupancy — which varies per drain window under backpressure
        batching — maps to O(log Q) compiled signatures instead of one
        XLA compile per occupancy; padded slots are lane-masked in-kernel
        and the `idxs` per-slot query-id vector scatters live results
        back at resolve time. `batch` is _assemble_batch's answer where
        the caller's `plan` already has it (not after a pair sweep that
        failed)."""
        prof = current_profile()
        results: list[Optional[int]] = [None] * len(calls)
        if batch is None:
            with prof.phase("plan"):
                batch = self._assemble_batch(index, calls, shards_t)
        groups, assembled, fallbacks = batch
        pending = []
        for (spec, _bk), idxs in groups.items():
            blocks = assembled[idxs[0]][0]
            n_scalars = len(assembled[idxs[0]][1])
            s_pad = blocks[0].shape[0]
            reduce_dev = s_pad <= MAX_DEVICE_SUM_SHARDS
            if n_scalars == 0:
                # No per-query scalars: every call in the group is the
                # SAME program over the same blocks (e.g. Count(All())
                # repeated) — one fused count serves them all; a scan
                # over a zero-leaf pytree has no query axis to scan.
                with prof.phase("dispatch", span="pilosa.count_batch",
                                legs=len(idxs), slots=1):
                    out = self._program("count", spec, reduce_dev)(blocks, ())
                self.programs.count_slots("count_batch", 1, 1)
                pending.append((idxs, out, None))
                continue
            # Slot dedupe by scalar bytes (ISSUE r14; the row_batch_async
            # idiom): a coalesced Zipfian window re-submits the same hot
            # call trees dozens of times per drain, and the scan's device
            # cost is O(slots) — Q must be the number of DISTINCT
            # queries, never the number of submitted legs (347 legs of a
            # 32-query pool used to scan 512 padded slots per launch).
            with prof.phase("slots"):
                unique, slot_of = self._dedupe_slots(assembled, idxs)
                qb = _slot_bucket(len(unique))
                scalars = self._padded_slot_scalars(
                    [assembled[i][1] for i in unique], qb
                )
            with prof.phase("dispatch", span="pilosa.count_batch",
                            legs=len(idxs), slots=qb):
                out = self._program("count_batch", spec, reduce_dev)(blocks, scalars)
            self.programs.count_slots("count_batch", len(unique), qb)
            pending.append((idxs, out, slot_of))

        def resolve() -> list[int]:
            prof_r = current_profile()
            with prof_r.phase("device_wait"):
                # The device wait belongs to the dispatch phase;
                # host_reduce below is pure host arithmetic (ISSUE r14).
                # Dispatches are already enqueued, so blocking here does
                # not undo the callers' batch pipelining.
                self.programs.block_ready([out for _, out, _ in pending])
            with prof_r.phase("readback"):
                for idxs, out, slot_of in pending:
                    arr = np.asarray(out, dtype=np.uint64)
                    if slot_of is None:  # shared zero-scalar program
                        val = int(arr.sum())  # scalar, or [S] partials
                        for i in idxs:
                            results[i] = val
                        continue
                    if arr.ndim == 2:  # [Q, S] partials past device-sum bound
                        arr = arr.sum(axis=1)
                    for i in idxs:
                        results[i] = int(arr[slot_of[i]])
            for i in fallbacks:
                results[i] = self.count_shards(index, calls[i], list(shards_t))
            return results  # type: ignore[return-value]

        return resolve

    def row_batch_async(
        self, index: str, calls: list[Call], shards: list[int]
    ) -> Callable[[], list[Row]]:
        """Batched bitmap materialization — the batching plane's row legs
        (Row/Intersect/Union/… resolves). Calls assemble against the
        resident stack and group by (spec shape, leaf blocks); within a
        group, byte-identical scalar slots dedupe (parse-cached trees
        make concurrent hot queries literally identical), the survivors
        pad to a slot bucket, and ONE vec_batch launch produces the
        group's [Q, S, W] slab stack (chunked under MAX_ROW_BATCH_BYTES).
        The resolver reads each chunk back once and builds every leg its
        own Row from its slot's slab — legs never share mutable results.

        Single-slot groups ride the existing "vec" program (no scan axis,
        no extra compile). Calls without a device lowering fall back to
        bitmap_call per call inside the resolver (CPU oracle included);
        a malformed call (QueryError) fails the whole group at assembly —
        the batcher then re-dispatches legs individually so only the
        offending submitter sees the error."""
        idx = self.holder.index(index)
        # lint: allow-hot-serialize(shard inventory is schema-sized and feeds list ops, not serialization)
        avail = idx.available_shards().to_array().tolist() if idx else []
        pos_of = {s: i for i, s in enumerate(avail)}
        if avail and all(s in pos_of for s in shards):
            shards_t = tuple(avail)
            positions = [pos_of[s] for s in shards]
        else:
            shards_t = tuple(shards)
            positions = list(range(len(shards)))
        prof = current_profile()
        results: list[Optional[Row]] = [None] * len(calls)
        with prof.phase("plan"):
            groups, assembled, fallbacks = self._assemble_batch(
                index, calls, shards_t
            )
        # (query ids, per-query slot, chunked device outputs, slots/chunk)
        pending: list[tuple] = []
        for (spec, _bk), idxs in groups.items():
            blocks = assembled[idxs[0]][0]
            s_pad = blocks[0].shape[0]
            # Slot dedupe by scalar bytes: the per-slot query-id mapping
            # (slot_of) scatters one computed slab to every leg that
            # asked for it.
            with prof.phase("slots"):
                unique, slot_of = self._dedupe_slots(assembled, idxs)
            # Per-DEVICE slab bytes: the cap guards device memory, and
            # under a mesh the [Q, S, W/128, 128] output is sharded over the
            # shard axis so each device holds only its 1/n chunk — a
            # whole-axis figure would shrink mesh launches n-fold below
            # what the HBM actually permits.
            slab_bytes = (
                s_pad // (self.mesh.n if self.mesh is not None else 1)
            ) * WORDS_PER_SHARD * 4
            # Rounded DOWN to a power of two: a full chunk's slot bucket
            # then equals per_chunk exactly, so bucket padding can never
            # inflate a launch past the byte cap it exists to enforce.
            per_chunk = max(1, MAX_ROW_BATCH_BYTES // slab_bytes)
            per_chunk = 1 << (per_chunk.bit_length() - 1)
            outs = []
            with prof.phase("dispatch", span="pilosa.row_batch",
                            legs=len(idxs), slots=len(unique)):
                for base in range(0, len(unique), per_chunk):
                    chunk = unique[base : base + per_chunk]
                    if len(chunk) == 1:
                        outs.append(
                            self._program("vec", spec, False)(
                                blocks, assembled[chunk[0]][1]
                            )
                        )
                        self.programs.count_slots("row_batch", 1, 1)
                        continue
                    qb = _slot_bucket(len(chunk))
                    scal = self._padded_slot_scalars(
                        [assembled[i][1] for i in chunk], qb
                    )
                    outs.append(
                        self._program("vec_batch", spec, False)(blocks, scal)
                    )
                    self.programs.count_slots("row_batch", len(chunk), qb)
            pending.append((idxs, slot_of, outs, per_chunk))

        # Subset requests gather on device before readback (same
        # heuristic as bitmap_call: reading a whole padded slab back
        # for a few shards wastes the transfer).
        sub = len(positions) * 4 <= (
            pending[0][2][0].shape[-3] if pending else 0  # the shard axis
        )
        pos_dev = jnp.asarray(positions, dtype=jnp.int32) if sub else None

        def resolve() -> list[Row]:
            prof_r = current_profile()
            with prof_r.phase("device_wait"):
                # The device wait belongs to the dispatch phase (the
                # leader pays it once per launch); host_reduce below is
                # pure host-side materialization (ISSUE r14).
                gathered = []
                for idxs, slot_of, outs, per_chunk in pending:
                    g = []
                    for out in outs:
                        if sub:
                            out = (
                                out[pos_dev] if out.ndim == 3
                                else out[:, pos_dev]
                            )
                        g.append(out)
                    self.programs.block_ready(g)
                    gathered.append(g)
            with prof_r.phase("readback"):
                row_pos = list(range(len(positions))) if sub else positions
                contiguous = row_pos == list(range(len(row_pos)))
                sel = None if contiguous else np.asarray(
                    row_pos, dtype=np.intp
                )
                for (idxs, slot_of, outs, per_chunk), g in zip(
                    pending, gathered
                ):
                    hosts = [flat_words(np.asarray(out)) for out in g]
                    for i in idxs:
                        slot = slot_of[i]
                        h = hosts[slot // per_chunk]
                        slab = h if h.ndim == 2 else h[slot % per_chunk]
                        slab = (
                            slab[: len(row_pos)] if contiguous
                            else slab[sel]
                        )
                        # One whole-slab vectorized pass per query ->
                        # lazy columns-backed Row (replaces the
                        # per-shard unpack/Bitmap/merge loop).
                        results[i] = self._slab_row(slab, shards)
            for i in fallbacks:
                results[i] = self.bitmap_call(index, calls[i], list(shards))
            return results  # type: ignore[return-value]

        return resolve

    # -- exact TopN (device fast path) -------------------------------------

    @_pinning
    def topn_field(
        self,
        index: str,
        field_name: str,
        shards: list[int],
        n: int,
        src_call: Optional[Call] = None,
    ) -> Optional[list[Pair]]:
        """Exact TopN in one dispatch: per-row popcounts of the stacked
        field block (optionally masked by a src tree), reduced over the
        shard axis on device; the counts vector reads back once."""
        f = self._field(index, field_name)
        if f.view(VIEW_STANDARD) is None:
            return []
        shards_t = tuple(shards)
        if self.blocks.rowid_field(index, f, shards_t):
            # Per-row counts of a field held as row ids: the host's.
            self._host_path("topn", "row-id layout")
            return None
        if src_call is not None:
            try:
                spec, blocks, scalars = self._assemble(index, src_call, shards_t)
            except _Unsupported as e:
                self._host_path("topn", e)
                return None
        if src_call is None:
            counts = self._topn_counts(index, f, field_name, shards_t)
            return self._topn_pairs(counts, n)
        return self._topn_pairs(
            self._topn_dispatch(
                index, f, shards_t, (spec, blocks, scalars), None
            )[0],
            n,
        )

    def _topn_counts(self, index, f, field_name, shards_t) -> np.ndarray:
        """The unfiltered per-row counts vector — the host rank-vector
        table (the reference's rank cache, cache.go:136): the view
        generation is the write epoch, so repeats serve without a
        dispatch; a SMALL epoch refreshes the resident per-shard table
        on the host (same incremental maintenance as the pair cache).
        Serves TopN, unfiltered Rows, and 1-field GroupBy (which wants
        the raw vector — no sort, no Pair objects)."""
        ckey = (index, field_name)
        views = (f.view(VIEW_STANDARD),)
        return self._topn_cache.serve(
            ckey, shards_t, views,
            lambda stale, fp: self._topn_refresh(
                index, ckey, f, views, stale, fp
            ),
        ).value

    def _topn_refresh(self, index, ckey, f, views, stale, fp) -> TierEntry:
        """The single-flight body. Generation moved: try the host table
        update against LIVE fragment versions — no stack fetch, no
        device round trip — and dispatch when it cannot absorb the
        epoch (cold field, row growth, shard-set change, too many slab
        shards)."""
        shards_t = fp[0]
        with current_profile().phase("freshness"):
            live = self._tier_versions(stale, (f,), shards_t, "topn")
            ent = refresh_entry(
                stale, fp, views, live, RowCountRows, self.stats,
                self.MAX_PAIR_HOST_UPDATE_SHARDS,
            )
        if ent is None:
            counts, pershard, vers, rp = self._topn_dispatch(
                index, f, shards_t, None, live[0]
            )
            # Dispatch read the stack content after the versions: stale
            # out any shard that moved meanwhile (see _confirm_vers).
            vers = self._confirm_vers(f, shards_t, vers, tier="topn")
            ent = TierEntry(fp, counts, pershard, (vers,), (rp,))
        self._topn_cache.store(ckey, ent)
        return ent

    def _topn_dispatch(self, index, f, shards_t, src, live_vers):
        """One sweep of the field's per-row counts (masked by the src
        tree when given): (counts[R], the int64[S, R] per-shard table
        or None, the versions the swept stack was packed from, the
        stack's row count)."""
        src_call = src is not None
        block, rp, vers = self.blocks.get_with_versions(index, f, shards_t)
        if vers is None:
            # Stack entry replaced concurrently: fall back to the
            # PRE-dispatch live read (conservative — recorded versions
            # may only be older than the swept data, so the worst case
            # is a redundant re-update, never staleness). Without this,
            # a None-vers entry refuses every future incremental update.
            vers = live_vers
        pershard = None
        packed_counts = None
        if block is None and src is None:
            # A field held packed has its row counts on the device.
            packed_counts = self.blocks.get_packed(index, f, shards_t)[2]
        if packed_counts is not None:
            counts = np.asarray(packed_counts, dtype=np.uint64).sum(axis=0)[:rp]
        elif block is None:
            # Over the HBM budget: page the row axis through the device
            # (VERDICT r2 #8) instead of falling back to the CPU path.
            counts = self._topn_paged_counts(index, f, shards_t, src)
        else:
            s_pad = block.shape[0]
            _, reduce_dev = self._topn_gates(s_pad, rp, src_call)
            with current_profile().phase("dispatch", span="pilosa.topn"):
                if not src_call:
                    counts = self._program("topn_plain", None, reduce_dev)(block)
                else:
                    spec, blocks, scalars = src
                    counts = self._program("topn_src", spec, reduce_dev)(
                        block, blocks, scalars
                    )
            with current_profile().phase("device_wait"):
                self.programs.block_ready(counts)
            counts = np.asarray(counts, dtype=np.uint64)
            if counts.ndim == 2:  # [S, R] per-shard partials
                pershard = counts.astype(np.int64)
                counts = counts.sum(axis=0)
        return counts, pershard, vers, rp

    def _topn_gates(self, s_pad, rp, src_call):
        """(pershard_ok, reduce_dev) for a TopN dispatch — shared with
        preheat's program warming so the copies can't drift (same
        discipline as _pair_gates). Unfiltered dispatches take [S, R]
        partials — the per-shard table is what absorbs later write
        epochs — but only under the same retention byte gate as the
        pair table (a many-row field's [S, R] readback + resident copy
        can reach hundreds of MB; over the gate, device-sum to [R] and
        let write epochs re-dispatch)."""
        pershard_ok = (
            not src_call
            and s_pad * rp * 8 <= self.MAX_PAIR_PERSHARD_BYTES
        )
        reduce_dev = (
            False if pershard_ok else s_pad <= MAX_DEVICE_SUM_SHARDS
        )
        return pershard_ok, reduce_dev

    def rows_field(self, index: str, field_name: str, shards: list[int],
                   start: int = 0) -> Optional[list[int]]:
        """Unfiltered Rows(field) from the rank-vector path (VERDICT r3
        #5): the per-row popcount vector — usually a host cache hit
        keyed on the view's write epoch — already answers 'which rows
        have any bit' in at most one dispatch, replacing the per-shard
        host fragment walk (reference fragment.rows, fragment.go:2618;
        at 954 shards the walk was a full host scan per query). Row ids
        ascending, >= start. Counts>0 is exact row presence: empty
        containers are dropped on write (roaring/bitmap.py _put), so a
        row with no bits has no containers."""
        pairs = self.topn_field(index, field_name, shards, 0, None)
        if pairs is None:
            return None
        return sorted(p.id for p in pairs if p.id >= start)

    @staticmethod
    def _topn_pairs(counts: np.ndarray, n: int) -> list[Pair]:
        order = np.lexsort((np.arange(counts.size), -counts.astype(np.int64)))
        pairs = [Pair(id=int(r), count=int(counts[r])) for r in order if counts[r] > 0]
        return pairs[:n] if n else pairs

    def _topn_paged_counts(
        self, index: str, f, shards_t: tuple[int, ...], src
    ) -> np.ndarray:
        """Streaming per-row popcounts for a field too tall to be
        HBM-resident: pack fixed-height row pages on the host, upload,
        popcount (optionally masked by the src tree), accumulate on the
        host. Two compiled shapes max (page + identical last page via
        zero-padding); page height sized to a QUARTER of the byte budget
        (one page in flight + src-pinned cache stays ~within budget)."""
        v = f.view(VIEW_STANDARD)
        frags = {s: (v.fragment(s) if v is not None else None) for s in shards_t}
        n_rows = max(
            [fr.max_row_id + 1 for fr in frags.values() if fr is not None] + [1]
        )
        s_pad = self.blocks._pad_shards(len(shards_t))
        bytes_per_row = s_pad * WORDS_PER_SHARD * 4
        budget = self.blocks.max_bytes or (1 << 30)
        # Quarter-budget pages: the loop holds ONE page in flight, so
        # cache + page stays within ~1.25x budget even when the cache is
        # pinned by this query's own src blocks (which make_room cannot
        # free — they're live references; being MRU they evict last).
        page = max(ROW_PAD, (budget // 4) // bytes_per_row // ROW_PAD * ROW_PAD)
        n_pages = (n_rows + page - 1) // page
        counts = np.zeros(n_pages * page, dtype=np.uint64)
        reduce_dev = s_pad <= MAX_DEVICE_SUM_SHARDS
        page_bytes = s_pad * page * WORDS_PER_SHARD * 4
        self.blocks.make_room(page_bytes)
        dev = None
        for start in range(0, n_rows, page):
            stop = min(start + page, n_rows)
            host = np.zeros((s_pad, page, WORDS_PER_SHARD), dtype=np.uint32)
            for i, s in enumerate(shards_t):
                fr = frags[s]
                if fr is not None and start <= fr.max_row_id:
                    host[i, : stop - start] = pack_rows(fr, start, stop)
            dev = self.blocks._put(host)
            global_stats.count("hbm_page_uploads_total")
            global_stats.count("hbm_page_bytes_total", host.nbytes)
            self.blocks._count_upload("dense", "page", host.nbytes)
            with current_profile().phase("dispatch", span="pilosa.topn_page"):
                if src is None:
                    out = self._program("topn_plain", None, reduce_dev)(dev)
                else:
                    spec, blocks, scalars = src
                    out = self._program("topn_src", spec, reduce_dev)(
                        dev, blocks, scalars
                    )
            with current_profile().phase("device_wait"):
                self.programs.block_ready(out)  # the page is complete
            arr = np.asarray(out, dtype=np.uint64)
            dev = None  # release before the next upload: 1 page in flight
            if arr.ndim == 2:
                arr = arr.sum(axis=0)
            counts[start : start + page] += arr
        return counts[:n_rows]

    # -- Tanimoto TopN over a packed field (ISSUE 36) ----------------------

    #: Legs one launch of the Tanimoto program holds (its slot buckets
    #: are 1, 2, 4, 8, 16); a drain of more is several launches.
    MAX_TANIMOTO_SLOTS = 16

    def packed_field(self, index: str, field_name: str, shards,
                     alone: bool = False) -> bool:
        """Whether the backend holds (or can hold) this field packed:
        the executor's gate for a TopN under a Row of the field itself.
        `alone`: and its dense stack is not admitted. A search by
        `tanimotoThreshold` is exact only from the packed stack and
        takes it wherever there is one; a plain TopN under a Row is
        exact from either and keeps the dense stack's sweep
        (topn_field) where there is a dense stack."""
        idx = self.holder.index(index)
        f = idx.field(field_name) if idx else None
        if (
            f is None or f.options.type == FIELD_TYPE_INT
            or f.view(VIEW_STANDARD) is None
        ):
            return False
        shards_t = tuple(shards)
        if self.blocks.get_packed(index, f, shards_t)[0] is None:
            return False
        return not alone or self.blocks.get(index, f, shards_t)[0] is None

    def _packed_row_counts(self, packed, stale):
        """A packed stack's row counts, int32[S, R] on the device: kept
        in the stack's entry of the block store (its `packed_extra`), so
        they are evicted with it."""
        counts = self._program("packed_row_counts", None, False)(packed)
        if stale is None or stale.shape != packed.shape:
            # A stack of a new shape: compile the exact finish of an
            # overflowing leg now, beside a build that costs seconds,
            # not under the first search that needs it (one inactive
            # slot: run, nothing found, result dropped).
            one = np.zeros(1, np.int32)
            self.programs.block_ready(
                self._program("topn_tanimoto_counts", None, False)(
                    packed, counts, one, one, one
                )
            )
        return self.programs.block_ready(counts)

    def topn_tanimoto_async(self, index: str, field_name: str,
                            shards: list[int], legs: list[tuple[int, int]]):
        """Dispatch `TopN(field, Row(field=m), tanimotoThreshold=T)` for
        every (m, T) of `legs` over the field's packed stack and return a
        resolver, or None where the field is not held packed.

        Each distinct leg takes a slot; slots go out MAX_TANIMOTO_SLOTS a
        launch, padded to a bucket (padded slots are inactive), and every
        launch is enqueued before the resolver reads any back. The
        resolver gives, leg by leg, (row ids, counts): every row r with
        c = |m ∩ r| > 0 and c * 100 // |m ∪ r| >= T, shard by shard as
        core/fragment.py `top` tests it, over ALL rows (the host path
        asks only its rank cache's candidates). By row id; the caller
        orders by count and trims. A leg with more hits than the
        program's lists hold is finished from its whole count vector
        (`topn_tanimoto_overflow_total`), never cut. T = 0 is the plain
        TopN under a Row.

        Every leg's answer comes from its short list where its hits fit
        there, else from a long slot, else from that finish
        (`topn_tanimoto_slots_total{list}`: ops/kernels.py
        tanimoto_topn)."""
        f = self._field(index, field_name)
        shards_t = tuple(shards)
        with current_profile().phase("stack_fetch"):
            packed, _, row_counts = self.blocks.get_packed(index, f, shards_t)
        if packed is None:
            return None
        rows_p = packed.shape[1]
        prof = current_profile()
        with prof.phase("slots"):
            slot_of: dict[tuple[int, int], int] = {}
            for leg in legs:
                slot_of.setdefault(leg, len(slot_of))
            unique = list(slot_of)
        pending = []
        fn = self._program("topn_tanimoto", None, False)
        for at in range(0, len(unique), self.MAX_TANIMOTO_SLOTS):
            part = unique[at : at + self.MAX_TANIMOTO_SLOTS]
            qb = _slot_bucket(len(part))
            ids = np.zeros(qb, np.int32)
            thr = np.zeros(qb, np.int32)
            act = np.zeros(qb, np.int32)
            for j, (m, t) in enumerate(part):
                # A row past the stack holds nothing: an inactive slot.
                ids[j], thr[j], act[j] = min(m, rows_p - 1), t, m < rows_p
            with prof.phase("dispatch", span="pilosa.topn_tanimoto",
                            legs=len(part), slots=qb):
                out = fn(packed, row_counts, ids, thr, act)
            self.programs.count_slots("topn_tanimoto", len(part), qb)
            pending.append((len(part), out, (ids, thr, act)))

        def resolve(deliver=None) -> list[tuple[np.ndarray, np.ndarray]]:
            """Launch by launch, in the order enqueued: wait, read back,
            and hand the legs of that launch to `deliver(leg indices,
            answers)` at once where one is given, while the device is
            still at the launches behind it. The batcher resolves a
            launch's legs there, so that their requests are answered,
            and the clients' next searches queued, before the drain has
            ended: the next drain then starts on a device that never
            stood still (one resolve at the drain's end left it idle for
            46 % of a window of 64 clients on the chip, PR 36)."""
            prof_r = current_profile()
            answers: list = [None] * len(unique)
            legs_of: dict[int, list[int]] = {}
            for i, leg in enumerate(legs):
                legs_of.setdefault(slot_of[leg], []).append(i)
            at = 0
            for n, out, args in pending:
                with prof_r.phase("device_wait"):
                    self.programs.block_ready(out)
                with prof_r.phase("readback"):
                    short, long_ = (np.asarray(a) for a in out)
                    slot_of_leg = {
                        int(j): s for s, j in enumerate(long_[:, 0]) if j >= 0
                    }
                    answered = {"short": 0, "long": 0, "finish": 0}
                    for j in range(n):
                        total = int(short[j, 0])
                        if total <= (short.shape[1] - 1) // 2:
                            listed, table = "short", short[j]
                        elif j in slot_of_leg:
                            listed, table = "long", long_[slot_of_leg[j]]
                        else:
                            listed, table = "finish", None
                        answered[listed] += 1
                        answers[at + j] = (
                            self._tanimoto_overflow(packed, row_counts, args, j)
                            if table is None else _from_list(table, total)
                        )
                    for listed, slots in answered.items():
                        self.stats.with_tags(f"list:{listed}").count(
                            "topn_tanimoto_slots_total", slots
                        )
                    self.stats.count(
                        "topn_tanimoto_hits_total",
                        sum(answers[at + j][0].size for j in range(n)),
                    )
                if deliver is not None:
                    mine = [i for j in range(n) for i in legs_of[at + j]]
                    deliver(mine, [answers[slot_of[legs[i]]] for i in mine])
                at += n
            return [answers[slot_of[leg]] for leg in legs]

        return resolve

    def _tanimoto_overflow(self, packed, row_counts, args, j):
        """A leg that neither of the program's lists holds (more hits
        than a long list, or a long leg past the launch's long slots),
        finished exactly: its whole masked count vector read back, the
        hits taken on the host."""
        self.stats.count("topn_tanimoto_overflow_total")
        ids, thr, act = (a[j : j + 1] for a in args)
        out = self._program("topn_tanimoto_counts", None, False)(
            packed, row_counts, ids, thr, act
        )
        counts = np.asarray(self.programs.block_ready(out))[0]
        rows = np.flatnonzero(counts)
        return rows.astype(np.int32), counts[rows]

    def topn_tanimoto(self, index, field_name, shards, row_id, threshold):
        """One leg, waited for: the batcher's path without a batcher."""
        resolver = self.topn_tanimoto_async(
            index, field_name, shards, [(row_id, threshold)]
        )
        return resolver()[0] if resolver is not None else None

    # -- BSI aggregates (device fast path; fragment.go:1111-1268) ----------

    def _bsi_setup(self, index, field_name, shards, filter_call):
        f = self._field(index, field_name)
        if f.options.type != FIELD_TYPE_INT:
            raise _Unsupported("not an int field")
        opts = f.bsi_group()
        if opts.bit_depth > MAX_BSI_DEPTH:
            raise _Unsupported("bit depth")
        shards_t = tuple(shards)
        if filter_call is not None:
            spec, blocks, scalars = self._assemble(index, filter_call, shards_t)
        else:
            spec, blocks, scalars = None, (), ()
        bsi_block, _ = self._get_block(
            index, f, shards_t, view_name=bsi_view_name(field_name),
            min_rows=BSI_OFFSET_BIT + opts.bit_depth,
        )
        return f, opts, spec, blocks, scalars, bsi_block

    def _bsi_aggregate(self, kind, tier, index, field_name, shards,
                       filter_call, incremental, sweep):
        """What Sum, Min and Max share. Unfiltered (a filtered aggregate
        depends on other fields' epochs): the cached result while the
        BSI view's generation holds; else the kind's host tier
        (`incremental(f, view name, previous entry, fingerprint)` -> the
        entry to store, or None when the epoch is not one it can
        absorb). Else the device (`sweep(opts, spec, bsi_block, blocks,
        scalars)` -> (result, per-shard table or None, extra)), stored
        with the versions it was swept at. None when not lowerable."""
        cacheable = filter_call is None
        if cacheable:
            key = (kind, index, field_name)
            shards_t = tuple(shards)
            f = self._field(index, field_name)
            vn = bsi_view_name(field_name)
            # Fingerprint BEFORE the data snapshot: a write racing this
            # query must produce a never-matching cache entry, never a
            # stale serve.
            cfp = fingerprint(shards_t, (f.view(vn),))
            ent = self._agg_cache.hit(key, cfp)
            if ent is not None:
                return ent.value
            stale = self._agg_cache.get(key)
            if (
                stale is not None
                and stale.vers is not None
                and stale.fp[0] == shards_t
            ):
                with current_profile().phase("freshness"):
                    ent = incremental(f, vn, stale, cfp)
                if ent is not None:
                    self._agg_cache.store(key, ent)
                    return ent.value
            pre_vers = self._live_versions(f, shards_t, vn, tier=tier)
        try:
            with current_profile().phase("stack_fetch"):
                f, opts, spec, blocks, scalars, bsi_block = self._bsi_setup(
                    index, field_name, shards, filter_call
                )
        except _Unsupported as e:
            self._host_path("bsi", e)
            return None
        if bsi_block.shape[0] > MAX_DEVICE_SUM_SHARDS:
            self._host_path("bsi", "shard axis past the device-sum bound")
            return None
        result, pershard, extra = sweep(opts, spec, bsi_block, blocks, scalars)
        if cacheable:
            # Pre-read versions confirmed post-sweep (moved shards get
            # _VERS_STALE): recorded versions never describe older
            # content than swept — the delta tiers require it.
            vers = self._confirm_vers(f, shards_t, pre_vers, vn, tier=tier)
            self._agg_cache.store(
                key, TierEntry(cfp, result, pershard, (vers,), extra)
            )
        return result

    @_pinning
    def bsi_sum(self, index, field_name, shards, filter_call=None):
        """Distributed Sum(field): per-plane popcounts fused on device
        (+psum over ICI with a mesh), exact host weighting. Returns
        (sum, count) or None when not lowerable.

        Unfiltered sums absorb point-value churn on the host: set/clear
        value ops are recorded per BSI fragment (fragment.value_ops),
        and an epoch fully explained by them updates the cached raw
        total/count as exact deltas — no plane re-sweep."""
        return self._bsi_aggregate(
            "sum", "sum", index, field_name, shards, filter_call,
            self._sum_try_incremental, self._sum_sweep,
        )

    def _sum_sweep(self, opts, spec, bsi_block, blocks, scalars):
        prof = current_profile()
        depth = opts.bit_depth
        with prof.phase("dispatch", span="pilosa.bsi_sum"):
            pos_c, neg_c, cnt = self._program(
                "bsi_sum", spec, True, extra=depth
            )(bsi_block, blocks, scalars)
        with prof.phase("device_wait"):
            self.programs.block_ready((pos_c, neg_c, cnt))
        with prof.phase("readback"):
            pos_c = np.asarray(pos_c, dtype=np.uint64)
            neg_c = np.asarray(neg_c, dtype=np.uint64)
            total = sum(
                (int(pos_c[i]) - int(neg_c[i])) << i for i in range(depth)
            )
            count = int(cnt)
        return (total + opts.base * count, count), None, (total, count)

    def _sum_try_incremental(self, f, vn, ent, cfp_now):
        """Apply a value-write epoch to the cached unfiltered Sum `ent`
        (same shard set, versions recorded) as exact deltas from the
        BSI fragments' value-op rings. Returns the entry of the fresh
        (sum, count), or None when the epoch isn't delta-coverable
        (bulk import_value, ring eviction)."""
        shards_t = cfp_now[0]
        (raw_total, count), vers_old = ent.extra, ent.vers[0]
        v = f.view(vn)
        vers_new = self._epoch_versions(
            f, shards_t, vn, vers_old, ent.fp[1][0], tier="sum"
        )
        d_sum = 0
        d_cnt = 0
        for i, s in enumerate(shards_t):
            ov, nv = vers_old[i], vers_new[i]
            if ov == nv:
                continue
            fr = v.fragment(s) if v is not None else None
            if fr is None or ov is None or nv is None or ov[0] != nv[0]:
                return None
            ops = fr.value_ops_between(ov[1], nv[1])
            if ops is None:
                return None
            for _, ook, ovv, nok, nvv in ops:
                d_sum += (nvv if nok else 0) - (ovv if ook else 0)
                d_cnt += (1 if nok else 0) - (1 if ook else 0)
        raw_total += d_sum
        count += d_cnt
        self.stats.count("sum_incremental_updates_total")
        return TierEntry(
            cfp_now, (raw_total + f.bsi_group().base * count, count),
            None, (vers_new,), (raw_total, count),
        )

    def bsi_min(self, index, field_name, shards, filter_call=None):
        return self._bsi_minmax("bsi_min", index, field_name, shards, filter_call)

    def bsi_max(self, index, field_name, shards, filter_call=None):
        return self._bsi_minmax("bsi_max", index, field_name, shards, filter_call)

    def _bsi_minmax(self, kind, index, field_name, shards, filter_call):
        """Per-shard Min/Max via plane narrowing with on-device selects (no
        host sync inside the scan), host reduce across shards with the
        executor's tie semantics. Returns (val, count) or None.

        Unfiltered Min/Max absorb churn on the host (VERDICT r4 #7):
        the per-shard (val, cnt) extremum table updates in O(1) for
        monotone value writes (a write that doesn't beat or clear the
        incumbent changes nothing; a better value replaces it), and
        only a shard whose incumbent was cleared re-derives — via the
        fragment's own host plane-narrowing (Fragment.min/max), no
        device dispatch at all. The reference recomputes per query
        (fragment.go:1147-1191)."""
        return self._bsi_aggregate(
            kind, "minmax", index, field_name, shards, filter_call,
            functools.partial(self._minmax_try_incremental, kind),
            functools.partial(self._minmax_sweep, kind, len(shards)),
        )

    def _minmax_sweep(self, kind, n_shards, opts, spec, bsi_block, blocks,
                      scalars):
        prof = current_profile()
        depth = opts.bit_depth
        with prof.phase("dispatch", span="pilosa." + kind):
            outs = self._program(kind, spec, True, extra=depth)(
                bsi_block, blocks, scalars
            )
        with prof.phase("device_wait"):
            self.programs.block_ready(outs)
        with prof.phase("readback"):
            bits_a, cnt_a, bits_b, cnt_b, branch_any, consider_any = (
                np.asarray(x) for x in outs
            )

        def assemble_max(bits) -> int:  # maxUnsigned decision bits
            return sum(1 << i for i in range(depth) if bits[i])

        def assemble_min(bits) -> int:  # minUnsigned: bit set when plane forced 1
            return sum(1 << i for i in range(depth) if bits[i])

        pershard: list[tuple[int, int]] = []
        for s in range(n_shards):
            if not consider_any[s]:
                pershard.append((0, 0))
                continue
            if kind == "bsi_min":
                if branch_any[s]:  # negatives exist: min = -maxUnsigned(neg)
                    val, cnt = -assemble_max(bits_a[s]), int(cnt_a[s])
                else:
                    val, cnt = assemble_min(bits_b[s]), int(cnt_b[s])
            else:
                if branch_any[s]:  # positives exist: max = maxUnsigned(pos)
                    val, cnt = assemble_max(bits_a[s]), int(cnt_a[s])
                else:  # all negative: max = -minUnsigned(consider)
                    val, cnt = -assemble_min(bits_b[s]), int(cnt_b[s])
            pershard.append((val + opts.base, cnt) if cnt else (0, 0))
        return self._minmax_reduce(kind, pershard), tuple(pershard), None

    @staticmethod
    def _minmax_reduce(kind, pershard) -> tuple[int, int]:
        """Cross-shard reduce with the executor's tie semantics (equal
        extrema accumulate counts) — shared by the dispatch and the
        incremental tier so they cannot drift."""
        best_val, best_cnt = 0, 0
        for val, cnt in pershard:
            if cnt == 0:
                continue
            if best_cnt == 0:
                best_val, best_cnt = val, cnt
            elif (kind == "bsi_min" and val < best_val) or (
                kind == "bsi_max" and val > best_val
            ):
                best_val, best_cnt = val, cnt
            elif val == best_val:
                best_cnt += cnt
        return best_val, best_cnt

    def _minmax_try_incremental(self, kind, f, vn, ent, cfp_now):
        """Apply a value-write epoch to the cached per-shard extremum
        table of `ent` (same shard set, versions recorded): O(1)
        monotone updates; a shard whose incumbent was cleared (or whose
        op window isn't ring-covered) re-derives via the fragment's
        HOST plane narrowing under its lock — exact, no device work.
        Returns the entry of the fresh (val, count), or None when the
        whole entry must re-dispatch."""
        shards_t = cfp_now[0]
        pershard_old, vers_old = ent.pershard, ent.vers[0]
        if f.options.type != FIELD_TYPE_INT:
            return None
        bg = f.bsi_group()
        base, depth = bg.base, bg.bit_depth
        v = f.view(vn)
        vers_new = self._epoch_versions(
            f, shards_t, vn, vers_old, ent.fp[1][0], tier="minmax"
        )
        better = (
            (lambda a, b: a < b) if kind == "bsi_min" else (lambda a, b: a > b)
        )
        pershard = list(pershard_old)
        vers_rec = list(vers_new)
        n_rederived = 0
        for i, s in enumerate(shards_t):
            ov, nv = vers_old[i], vers_new[i]
            if ov == nv:
                vers_rec[i] = ov
                continue
            fr = v.fragment(s) if v is not None else None
            if fr is None:
                pershard[i] = (0, 0)
                vers_rec[i] = None
                continue
            ops = None
            if ov is not None and nv is not None and ov[0] == nv[0]:
                ops = fr.value_ops_between(ov[1], nv[1])
            rederive = ops is None
            if not rederive:
                val, cnt = pershard[i]
                for _, ook, ovv, nok, nvv in ops:
                    if ook:
                        o = ovv + base
                        if cnt <= 0 or better(o, val):
                            rederive = True  # table inconsistent: rescan
                            break
                        if o == val:
                            cnt -= 1
                            if cnt == 0:
                                # Incumbent cleared: the next extremum
                                # is unknowable from deltas.
                                rederive = True
                                break
                    if nok:
                        nn = nvv + base
                        if cnt <= 0:
                            val, cnt = nn, 1
                        elif nn == val:
                            cnt += 1
                        elif better(nn, val):
                            val, cnt = nn, 1
                if not rederive:
                    pershard[i] = (val, cnt)
            if rederive:
                # Version captured under the SAME lock as the scan so it
                # describes exactly the scanned content (fr.min/max take
                # fr.lock; RLock makes this atomic).
                with fr.lock:
                    vv = (fr.uid, fr.version)
                    raw = (
                        fr.min(None, depth)
                        if kind == "bsi_min"
                        else fr.max(None, depth)
                    )
                pershard[i] = (raw[0] + base, raw[1]) if raw[1] else (0, 0)
                vers_rec[i] = vv
                n_rederived += 1
        self.stats.count("minmax_incremental_updates_total")
        if n_rederived:
            self.stats.count("minmax_shard_rederives_total", n_rederived)
        return TierEntry(
            cfp_now, self._minmax_reduce(kind, pershard), tuple(pershard),
            (tuple(vers_rec),),
        )
