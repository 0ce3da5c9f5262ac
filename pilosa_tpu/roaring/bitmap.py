"""Roaring bitmap core: containers and the 64-bit Bitmap.

Mirrors the semantics of reference roaring/roaring.go (Bitmap, Container,
set-algebra ops Intersect/Union/Difference/Xor/Shift/Flip at
roaring/roaring.go:595,620,891,918,946,1683; IntersectionCount :570;
Count/CountRange :407,438; OffsetRange :537) with numpy-vectorized container
kernels instead of per-container-type Go loops.
"""

from __future__ import annotations

import bisect
import os
from typing import Iterable, Iterator, Optional

import numpy as np

# Invariant-checking mode (reference roaringparanoia build tag): every
# container entering a Bitmap is validated. Off by default — it's a
# correctness harness for tests/debugging, not a production cost.
PARANOIA = os.environ.get("PILOSA_TPU_PARANOIA", "").lower() in ("1", "true")

# A container covers 2^16 bit positions (reference roaring/roaring.go:64-69).
CONTAINER_WIDTH = 1 << 16
# Max cardinality stored as a sorted uint16 array (reference ArrayMaxSize).
ARRAY_MAX_SIZE = 4096
# uint64 words in a bitmap container (reference bitmapN).
BITMAP_N = CONTAINER_WIDTH // 64
# Largest container key: 2^64 bit space / 2^16 container width.
MAX_CONTAINER_KEY = (1 << 48) - 1

TYPE_ARRAY = "array"
TYPE_BITMAP = "bitmap"
# First-class in-memory RLE containers (VERDICT r3 missing #5; reference
# roaring.go:64-69,1940-1943): data is uint16[R, 2] of [start, last]
# INCLUSIVE runs, sorted, non-overlapping, non-adjacent. Reads (contains,
# counts, pack, serialize) AND set algebra against run/array peers are
# run-native (VERDICT r4 #4; reference run-aware op matrix around
# roaring.go:2599-2790) — a runny container survives queries without
# ever materializing its 8 KiB bitmap twin. Ops against bitmap peers
# materialize (the reference does run×bitmap through the bitmap form
# too); point mutators convert, and optimize() re-packs.
TYPE_RUN = "run"

#: RUN -> array/bitmap twin materializations (run_materializations in
#: tests): time-quantum view queries over runny containers must keep
#: this flat on run/array op pairs.
UNRUN_MATERIALIZATIONS = [0]

_EMPTY_U16 = np.empty(0, dtype=np.uint16)

# Keep a container as runs when its RLE form is smaller than both other
# encodings (the serializer's pick-smallest rule, reference Optimize).
def _runs_win(run_count: int, n: int) -> bool:
    return 4 * run_count < min(2 * n, 8 * BITMAP_N)


def array_run_count(data: np.ndarray) -> int:
    """Runs of consecutive values in sorted-unique uint16 positions,
    counted without building them: whether an array container's RLE form
    could win is two passes over its values (a tall field is a million
    small array containers, none of which it does: ISSUE 36)."""
    if data.size < 2:
        return int(data.size)
    return int(np.count_nonzero(data[1:] - data[:-1] != 1)) + 1


def _sorted_member_mask(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Boolean mask over a: a[i] ∈ b (both sorted unique — the array-
    container invariant). A 64 KiB bool lookup over the uint16 domain:
    measured 18 µs vs 64 µs for vectorized binary search and 98 µs for
    np.intersect1d (which re-SORTS the concatenation — that sort alone
    profiled as 75% of the CPU oracle's whole query time)."""
    if a.size == 0 or b.size == 0:
        return np.zeros(a.size, dtype=bool)
    table = np.zeros(CONTAINER_WIDTH, dtype=bool)
    table[b] = True
    return table[a]


def _sorted_union(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Union of two uint16 arrays (sorted-unique NOT required — the
    stable sort + adjacent dedup handle anything; sorted inputs just
    make the radix pass cheap). kind='stable' is radix sort for small
    ints — O(n), no comparison re-sort of sorted runs."""
    out = np.sort(np.concatenate([a, b]), kind="stable")
    if out.size:
        out = out[np.concatenate(([True], out[1:] != out[:-1]))]
    return out


def _positions_to_runs(pos: np.ndarray) -> np.ndarray:
    """Sorted-unique positions -> [[start, last], ...] int64."""
    p = pos.astype(np.int64)
    if p.size == 0:
        return np.empty((0, 2), dtype=np.int64)
    breaks = np.nonzero(np.diff(p) != 1)[0]
    starts = np.concatenate(([0], breaks + 1))
    ends = np.concatenate((breaks, [p.size - 1]))
    return np.stack([p[starts], p[ends]], axis=1)


def _runs_member_mask(runs: np.ndarray, pos: np.ndarray) -> np.ndarray:
    """Boolean mask over pos: pos[i] inside some run. Vectorized: the
    predecessor run by start, then an upper-bound check on its last."""
    if runs.shape[0] == 0 or pos.size == 0:
        return np.zeros(pos.size, dtype=bool)
    starts = runs[:, 0].astype(np.int64)
    lasts = runs[:, 1].astype(np.int64)
    p = pos.astype(np.int64)
    idx = np.searchsorted(starts, p, side="right") - 1
    ok = idx >= 0
    return ok & (p <= lasts[np.clip(idx, 0, starts.size - 1)])


def _intersect_runs(ra: np.ndarray, rb: np.ndarray) -> np.ndarray:
    """Overlap sweep of two sorted run lists -> runs int64 (reference
    intersectRunRun, roaring.go's run-aware op matrix)."""
    out = []
    i = j = 0
    na, nb = ra.shape[0], rb.shape[0]
    while i < na and j < nb:
        s = max(ra[i, 0], rb[j, 0])
        l = min(ra[i, 1], rb[j, 1])
        if s <= l:
            out.append((s, l))
        if ra[i, 1] < rb[j, 1]:
            i += 1
        else:
            j += 1
    return np.array(out, dtype=np.int64).reshape(-1, 2)


def _union_runs(ra: np.ndarray, rb: np.ndarray) -> np.ndarray:
    """Merge + coalesce (adjacent runs fuse) -> runs int64. Vectorized
    interval merge: sort by start, running max of ends, break where the
    next start clears the running end by more than adjacency."""
    allr = np.concatenate([ra, rb]).astype(np.int64)
    if allr.shape[0] == 0:
        return allr.reshape(-1, 2)
    allr = allr[np.argsort(allr[:, 0], kind="stable")]
    starts = allr[:, 0]
    ends = np.maximum.accumulate(allr[:, 1])
    brk = np.nonzero(starts[1:] > ends[:-1] + 1)[0]
    s_idx = np.concatenate(([0], brk + 1))
    e_idx = np.concatenate((brk, [allr.shape[0] - 1]))
    return np.stack([starts[s_idx], ends[e_idx]], axis=1)


def _runs_could_win(n_runs_upper: int, n_upper: int) -> bool:
    """Cheap pre-gate for run-native batch ops: when even the BEST-case
    result (no coalescing losses counted) cannot encode smaller as runs,
    the materialized numpy kernels are faster than the run sweeps — a
    scattered 14k-value with_many through the run path measured ~90x
    slower than the bitmap kernel it replaced (code review r5), and the
    result demoted to a bitmap anyway."""
    return _runs_win(n_runs_upper, max(n_upper, 1))


def _difference_runs(ra: np.ndarray, rb: np.ndarray) -> np.ndarray:
    """ra \\ rb sweep -> runs int64."""
    out = []
    j = 0
    nb = rb.shape[0]
    for s, l in ra.astype(np.int64):
        cur = int(s)
        while j < nb and int(rb[j, 1]) < cur:
            j += 1
        k = j
        while k < nb and int(rb[k, 0]) <= l:
            bs, bl = int(rb[k, 0]), int(rb[k, 1])
            if bs > cur:
                out.append((cur, bs - 1))
            cur = max(cur, bl + 1)
            if cur > l:
                break
            k += 1
        if cur <= l:
            out.append((cur, int(l)))
    return np.array(out, dtype=np.int64).reshape(-1, 2)


def _runs_to_bitmap_words(runs: np.ndarray) -> np.ndarray:
    """Runs [[start, last], ...] -> uint64[1024] coverage words, via a
    boundary-delta cumsum (O(width), no per-position scatter). Deltas
    ACCUMULATE (add.at, coverage = running sum > 0) rather than assign:
    canonical containers are coalesced-disjoint, but a foreign writer
    can serialize adjacent runs like [[0,4],[5,9]] (codec.py builds
    TYPE_RUN straight from wire bytes, validate() is PARANOIA-gated) —
    assignment would let run2's +1 be overwritten by run1's -1 at the
    shared boundary and corrupt the whole mask (code review r7)."""
    d = np.zeros(CONTAINER_WIDTH + 1, dtype=np.int32)
    if runs.shape[0]:
        r = runs.astype(np.int64)
        np.add.at(d, r[:, 0], 1)
        np.add.at(d, r[:, 1] + 1, -1)
    bits = np.cumsum(d[:-1], dtype=np.int32) > 0
    return np.packbits(bits, bitorder="little").view(np.uint64)


def _as_bitmap_words(arr: np.ndarray) -> np.ndarray:
    """Sorted uint16 positions -> uint64[1024] bitmap words."""
    words = np.zeros(BITMAP_N, dtype=np.uint64)
    if arr.size:
        np.bitwise_or.at(words, arr >> 6, np.uint64(1) << (arr.astype(np.uint64) & np.uint64(63)))
    return words


def _bitmap_to_positions(words: np.ndarray) -> np.ndarray:
    """uint64[1024] bitmap words -> sorted uint16 positions."""
    bits = np.unpackbits(words.view(np.uint8), bitorder="little")
    return np.nonzero(bits)[0].astype(np.uint16)


class Container:
    """One 2^16-bit container: sorted uint16 array or uint64[1024] bitmap.

    Value semantics: operations return new containers; data arrays are treated
    as immutable once attached (the Bitmap mutators replace containers rather
    than editing them in place, which keeps snapshots/row views safe to share
    the way the reference's copy-on-write container freezing does,
    reference roaring/roaring.go Freeze).
    """

    __slots__ = ("typ", "data", "_n")

    def __init__(self, typ: str, data: np.ndarray, n: Optional[int] = None):
        self.typ = typ
        if PARANOIA and isinstance(data, np.ndarray):
            # Sentinel mode (reference roaringsentinel build tag,
            # roaring_sentinel.go): containers are immutable-by-convention
            # and structurally shared by clones/snapshots; freezing the
            # array makes any accidental in-place mutation raise instead
            # of silently corrupting every sharer.
            data = data.view()
            data.flags.writeable = False
        self.data = data
        if n is None:
            if typ == TYPE_ARRAY:
                n = int(data.size)
            elif typ == TYPE_RUN:
                n = int(
                    (data[:, 1].astype(np.int64) - data[:, 0].astype(np.int64) + 1).sum()
                )
            else:
                n = int(np.bitwise_count(data).sum())
        self._n = n

    # -- constructors ----------------------------------------------------

    def validate(self, key: int = -1) -> None:
        """Invariant checks for paranoia mode (reference roaringparanoia
        build tag, roaring/roaring_paranoia.go:20): array containers must
        be sorted unique within bounds; cached cardinality must match."""
        if self.typ == TYPE_ARRAY:
            a = self.data
            if a.dtype != np.uint16:
                raise AssertionError(f"container {key}: array dtype {a.dtype}")
            if a.size > 1 and not (a[1:] > a[:-1]).all():
                raise AssertionError(f"container {key}: array not sorted/unique")
            if self._n != int(a.size):
                raise AssertionError(
                    f"container {key}: n={self._n} != array size {a.size}"
                )
        elif self.typ == TYPE_RUN:
            r = self.data
            if r.ndim != 2 or r.shape[1] != 2 or r.dtype != np.uint16:
                raise AssertionError(f"container {key}: run shape {r.shape} {r.dtype}")
            if (r[:, 1] < r[:, 0]).any():
                raise AssertionError(f"container {key}: inverted run")
            if r.shape[0] > 1 and not (
                r[1:, 0].astype(np.int64) > r[:-1, 1].astype(np.int64) + 1
            ).all():
                raise AssertionError(
                    f"container {key}: runs overlap or are adjacent"
                )
            real = int(
                (r[:, 1].astype(np.int64) - r[:, 0].astype(np.int64) + 1).sum()
            )
            if self._n != real:
                raise AssertionError(f"container {key}: n={self._n} != runs {real}")
        else:
            if self.data.size != BITMAP_N:
                raise AssertionError(
                    f"container {key}: bitmap has {self.data.size} words"
                )
            real = int(np.bitwise_count(self.data).sum())
            if self._n != real:
                raise AssertionError(f"container {key}: n={self._n} != popcount {real}")

    @staticmethod
    def empty() -> "Container":
        return Container(TYPE_ARRAY, _EMPTY_U16, 0)

    @staticmethod
    def from_positions(arr: np.ndarray) -> "Container":
        """arr: sorted unique uint16 positions."""
        arr = np.asarray(arr, dtype=np.uint16)
        if arr.size > ARRAY_MAX_SIZE:
            return Container(TYPE_BITMAP, _as_bitmap_words(arr), int(arr.size))
        return Container(TYPE_ARRAY, arr, int(arr.size))

    @staticmethod
    def from_bitmap_words(words: np.ndarray, n: Optional[int] = None) -> "Container":
        if n is None:
            n = int(np.bitwise_count(words).sum())
        if n <= ARRAY_MAX_SIZE:
            return Container(TYPE_ARRAY, _bitmap_to_positions(words), n)
        return Container(TYPE_BITMAP, words, n)

    @staticmethod
    def from_runs(runs: np.ndarray) -> "Container":
        """runs: int array [[start, last], ...] inclusive (codec form).
        Stays RLE in memory when runs are the smallest encoding
        (VERDICT r3 #5 — this used to always inflate to array/bitmap,
        costing 8 KiB of host RAM for a 4-byte full-container run)."""
        n = int((runs[:, 1].astype(np.int64) - runs[:, 0].astype(np.int64) + 1).sum())
        if _runs_win(runs.shape[0], n):
            return Container(TYPE_RUN, np.asarray(runs, dtype=np.uint16), n)
        if n <= ARRAY_MAX_SIZE:
            parts = [np.arange(s, l + 1, dtype=np.uint16) for s, l in runs]
            return Container(TYPE_ARRAY, np.concatenate(parts) if parts else _EMPTY_U16, n)
        bits = np.zeros(CONTAINER_WIDTH, dtype=bool)
        for s, l in runs:
            bits[s : l + 1] = True
        words = np.packbits(bits, bitorder="little").view(np.uint64).copy()
        return Container(TYPE_BITMAP, words, n)

    # -- accessors -------------------------------------------------------

    @property
    def n(self) -> int:
        return self._n

    def positions(self) -> np.ndarray:
        """Sorted uint16 positions regardless of representation."""
        if self.typ == TYPE_ARRAY:
            return self.data
        if self.typ == TYPE_RUN:
            if self.data.shape[0] == 0:
                return _EMPTY_U16
            parts = [
                np.arange(int(s), int(l) + 1, dtype=np.uint16)
                for s, l in self.data
            ]
            return np.concatenate(parts)
        return _bitmap_to_positions(self.data)

    def bitmap_words(self) -> np.ndarray:
        """uint64[1024] words regardless of representation."""
        if self.typ == TYPE_BITMAP:
            return self.data
        if self.typ == TYPE_RUN:
            bits = np.zeros(CONTAINER_WIDTH, dtype=bool)
            for s, l in self.data:
                bits[int(s) : int(l) + 1] = True
            return np.packbits(bits, bitorder="little").view(np.uint64).copy()
        return _as_bitmap_words(self.data)

    def runs(self) -> np.ndarray:
        """Runs [[start, last], ...] inclusive, as int32 (native for RUN
        containers, detected for the others)."""
        if self.typ == TYPE_RUN:
            return self.data.astype(np.int32)
        return _positions_to_runs(self.positions()).astype(np.int32)

    def _unrun(self) -> "Container":
        """RUN -> array/bitmap twin (same bits) for ops with no RLE
        form; identity for the other types. Counted: run/array op pairs
        must never come through here (the run-native paths exist so
        time-quantum views don't allocate twins, VERDICT r4 #4)."""
        if self.typ != TYPE_RUN:
            return self
        UNRUN_MATERIALIZATIONS[0] += 1
        if self._n <= ARRAY_MAX_SIZE:
            return Container(TYPE_ARRAY, self.positions(), self._n)
        return Container(TYPE_BITMAP, self.bitmap_words(), self._n)

    def _i64_runs(self) -> np.ndarray:
        return self.data.astype(np.int64)

    def contains(self, v: int) -> bool:
        if self.typ == TYPE_ARRAY:
            i = np.searchsorted(self.data, np.uint16(v))
            return i < self.data.size and self.data[i] == v
        if self.typ == TYPE_RUN:
            # Find the last run with start <= v; v is inside iff v <= last.
            i = int(np.searchsorted(self.data[:, 0], np.uint16(v), side="right")) - 1
            return i >= 0 and v <= int(self.data[i, 1])
        return bool((int(self.data[v >> 6]) >> (v & 63)) & 1)

    def count_range(self, start: int, end: int) -> int:
        """Count positions in [start, end) within this container."""
        if self.typ == TYPE_ARRAY:
            lo = np.searchsorted(self.data, np.uint16(start), side="left")
            hi = self.data.size if end >= CONTAINER_WIDTH else np.searchsorted(
                self.data, np.uint16(end), side="left"
            )
            return int(hi - lo)
        if self.typ == TYPE_RUN:
            # Clip every run to [start, end): sum of positive overlaps.
            s = self.data[:, 0].astype(np.int64)
            l = self.data[:, 1].astype(np.int64)
            overlap = np.minimum(l, end - 1) - np.maximum(s, start) + 1
            return int(np.maximum(overlap, 0).sum())
        # Popcount whole words, masking the partial edge words.
        end = min(end, CONTAINER_WIDTH)
        if end <= start:
            return 0
        w0, w1 = start >> 6, (end - 1) >> 6
        words = self.data[w0 : w1 + 1].copy()
        lo_bits = start & 63
        hi_bits = (end - 1) & 63
        if lo_bits:
            words[0] &= ~np.uint64(0) << np.uint64(lo_bits)
        if hi_bits != 63:
            words[-1] &= ~np.uint64(0) >> np.uint64(63 - hi_bits)
        return int(np.bitwise_count(words).sum())

    # -- mutators (return new container) ---------------------------------

    def with_bit(self, v: int) -> "Container":
        if self.contains(v):
            return self
        if self.typ == TYPE_RUN:
            return self._unrun().with_bit(v)
        if self.typ == TYPE_ARRAY:
            i = int(np.searchsorted(self.data, np.uint16(v)))
            arr = np.insert(self.data, i, np.uint16(v))
            if arr.size > ARRAY_MAX_SIZE:
                return Container(TYPE_BITMAP, _as_bitmap_words(arr), int(arr.size))
            return Container(TYPE_ARRAY, arr, int(arr.size))
        words = self.data.copy()
        words[v >> 6] |= np.uint64(1) << np.uint64(v & 63)
        return Container(TYPE_BITMAP, words, self._n + 1)

    def without_bit(self, v: int) -> "Container":
        if not self.contains(v):
            return self
        if self.typ == TYPE_RUN:
            return self._unrun().without_bit(v)
        if self.typ == TYPE_ARRAY:
            i = int(np.searchsorted(self.data, np.uint16(v)))
            return Container(TYPE_ARRAY, np.delete(self.data, i), self._n - 1)
        words = self.data.copy()
        words[v >> 6] &= ~(np.uint64(1) << np.uint64(v & 63))
        return Container.from_bitmap_words(words, self._n - 1)

    def with_many(self, vs: np.ndarray) -> "Container":
        """Union with a sorted-or-not uint16 position array."""
        if vs.size == 0:
            return self
        if self.typ == TYPE_RUN:
            # Run-native when the result can stay RLE; a scattered batch
            # (run count ~ size) goes through the materialized kernels
            # instead (see _runs_could_win).
            vs_u = np.unique(vs.astype(np.uint16))
            vs_runs = _positions_to_runs(vs_u)
            if _runs_could_win(
                self.data.shape[0] + vs_runs.shape[0], self._n + vs_u.size
            ):
                return Container.from_runs(
                    _union_runs(self._i64_runs(), vs_runs)
                )
            return self._unrun().with_many(vs_u)
        if self.typ == TYPE_ARRAY:
            # _sorted_union's stable radix sort + adjacent-dedup handles
            # unsorted/duplicated vs directly — no np.unique pre-sort.
            arr = _sorted_union(self.data, vs.astype(np.uint16))
            return Container.from_positions(arr)
        words = self.data.copy()
        np.bitwise_or.at(words, vs >> 6, np.uint64(1) << (vs.astype(np.uint64) & np.uint64(63)))
        return Container.from_bitmap_words(words)

    def without_many(self, vs: np.ndarray) -> "Container":
        if vs.size == 0:
            return self
        if self.typ == TYPE_RUN:
            vs_u = np.unique(vs.astype(np.uint16))
            vs_runs = _positions_to_runs(vs_u)
            # Removal can only add as many runs as removed spans; same
            # could-win gate as with_many keeps scattered batches on the
            # vectorized kernels.
            if _runs_could_win(
                self.data.shape[0] + vs_runs.shape[0], self._n
            ):
                return Container.from_runs(
                    _difference_runs(self._i64_runs(), vs_runs)
                )
            return self._unrun().without_many(vs_u)
        if self.typ == TYPE_ARRAY:
            # The membership table is duplicate- and order-insensitive.
            keep = ~_sorted_member_mask(self.data, vs.astype(np.uint16))
            arr = self.data[keep]
            return Container(TYPE_ARRAY, arr, int(arr.size))
        mask = np.zeros(BITMAP_N, dtype=np.uint64)
        np.bitwise_or.at(mask, vs >> 6, np.uint64(1) << (vs.astype(np.uint64) & np.uint64(63)))
        return Container.from_bitmap_words(self.data & ~mask)

    # -- set algebra -----------------------------------------------------
    # run×run and run×array compute ON the runs (reference's run-aware
    # op matrix, roaring.go:2599-2790); run×bitmap intersect verbs AND
    # the bitmap words against a cumsum-built run coverage mask (no
    # _unrun() materialization — ISSUE r7 satellite); the remaining
    # run×bitmap verbs materialize (union/xor outputs have no run
    # structure to preserve when one side is a dense bitmap).

    def intersect(self, other: "Container") -> "Container":
        if self.typ == TYPE_RUN and other.typ == TYPE_RUN:
            return Container.from_runs(
                _intersect_runs(self._i64_runs(), other._i64_runs())
            )
        if self.typ == TYPE_RUN and other.typ == TYPE_ARRAY:
            keep = _runs_member_mask(self.data, other.data)
            return Container(TYPE_ARRAY, other.data[keep], None)
        if self.typ == TYPE_ARRAY and other.typ == TYPE_RUN:
            keep = _runs_member_mask(other.data, self.data)
            return Container(TYPE_ARRAY, self.data[keep], None)
        if self.typ == TYPE_RUN or other.typ == TYPE_RUN:
            # run x bitmap (VERDICT r5 missing #2): AND the bitmap words
            # against a cumsum-built run coverage mask instead of
            # _unrun()-materializing the run side — the time-quantum x
            # standard-view pair's hot combination.
            run_c, bm_c = (self, other) if self.typ == TYPE_RUN else (other, self)
            return Container.from_bitmap_words(
                _runs_to_bitmap_words(run_c.data) & bm_c.data
            )
        a, b = self, other
        if a.typ == TYPE_ARRAY and b.typ == TYPE_ARRAY:
            if a.data.size > b.data.size:
                a, b = b, a  # search the smaller array in the larger
            return Container.from_positions(
                a.data[_sorted_member_mask(a.data, b.data)]
            )
        if a.typ == TYPE_ARRAY:
            a, b = b, a
        if b.typ == TYPE_ARRAY:  # bitmap ∩ array
            keep = (a.data[b.data >> 6] >> (b.data.astype(np.uint64) & np.uint64(63))) & np.uint64(1)
            return Container(TYPE_ARRAY, b.data[keep == 1], None)
        return Container.from_bitmap_words(a.data & b.data)

    def intersection_count(self, other: "Container") -> int:
        if self.typ == TYPE_RUN and other.typ == TYPE_RUN:
            r = _intersect_runs(self._i64_runs(), other._i64_runs())
            return int((r[:, 1] - r[:, 0] + 1).sum()) if r.size else 0
        if self.typ == TYPE_RUN and other.typ == TYPE_ARRAY:
            return int(_runs_member_mask(self.data, other.data).sum())
        if self.typ == TYPE_ARRAY and other.typ == TYPE_RUN:
            return int(_runs_member_mask(other.data, self.data).sum())
        if self.typ == TYPE_RUN or other.typ == TYPE_RUN:
            # run x bitmap: popcount over the masked words directly — no
            # materialized intermediate container at all.
            run_c, bm_c = (self, other) if self.typ == TYPE_RUN else (other, self)
            return int(
                np.bitwise_count(
                    _runs_to_bitmap_words(run_c.data) & bm_c.data
                ).sum()
            )
        a, b = self, other
        if a.typ == TYPE_ARRAY and b.typ == TYPE_ARRAY:
            if a.data.size > b.data.size:
                a, b = b, a
            return int(_sorted_member_mask(a.data, b.data).sum())
        if a.typ == TYPE_ARRAY:
            a, b = b, a
        if b.typ == TYPE_ARRAY:
            keep = (a.data[b.data >> 6] >> (b.data.astype(np.uint64) & np.uint64(63))) & np.uint64(1)
            return int(keep.sum())
        return int(np.bitwise_count(a.data & b.data).sum())

    def union(self, other: "Container") -> "Container":
        if self.typ == TYPE_RUN and other.typ == TYPE_RUN:
            return Container.from_runs(
                _union_runs(self._i64_runs(), other._i64_runs())
            )
        if (self.typ == TYPE_RUN and other.typ == TYPE_ARRAY) or (
            self.typ == TYPE_ARRAY and other.typ == TYPE_RUN
        ):
            run_c, arr_c = (
                (self, other) if self.typ == TYPE_RUN else (other, self)
            )
            arr_runs = _positions_to_runs(arr_c.data)
            # Scattered arrays (run count ~ size) can't yield a runny
            # union: the vectorized kernels win (code review r5).
            if _runs_could_win(
                run_c.data.shape[0] + arr_runs.shape[0],
                run_c._n + arr_c._n,
            ):
                return Container.from_runs(
                    _union_runs(run_c._i64_runs(), arr_runs)
                )
        a, b = self._unrun(), other._unrun()
        if a.typ == TYPE_ARRAY and b.typ == TYPE_ARRAY:
            return Container.from_positions(_sorted_union(a.data, b.data))
        return Container.from_bitmap_words(a.bitmap_words() | b.bitmap_words())

    def difference(self, other: "Container") -> "Container":
        if self.typ == TYPE_RUN and other.typ == TYPE_RUN:
            return Container.from_runs(
                _difference_runs(self._i64_runs(), other._i64_runs())
            )
        if self.typ == TYPE_RUN and other.typ == TYPE_ARRAY:
            arr_runs = _positions_to_runs(other.data)
            # Same scattered-operand gate as with_many/union/xor: a
            # removal can split at most one run per removed span.
            if _runs_could_win(
                self.data.shape[0] + arr_runs.shape[0], self._n
            ):
                return Container.from_runs(
                    _difference_runs(self._i64_runs(), arr_runs)
                )
            return self._unrun().difference(other)
        if self.typ == TYPE_ARRAY and other.typ == TYPE_RUN:
            keep = ~_runs_member_mask(other.data, self.data)
            out = self.data[keep]
            return Container(TYPE_ARRAY, out, int(out.size))
        a, b = self._unrun(), other._unrun()
        if a.typ == TYPE_ARRAY:
            if b.typ == TYPE_ARRAY:
                out = a.data[~_sorted_member_mask(a.data, b.data)]
            else:
                keep = (b.data[a.data >> 6] >> (a.data.astype(np.uint64) & np.uint64(63))) & np.uint64(1)
                out = a.data[keep == 0]
            return Container(TYPE_ARRAY, out.astype(np.uint16), int(out.size))
        return Container.from_bitmap_words(a.data & ~b.bitmap_words())

    def xor(self, other: "Container") -> "Container":
        run_pair = (
            self.typ == TYPE_RUN and other.typ in (TYPE_RUN, TYPE_ARRAY)
        ) or (self.typ == TYPE_ARRAY and other.typ == TYPE_RUN)
        if run_pair:
            ra = (
                self._i64_runs()
                if self.typ == TYPE_RUN
                else _positions_to_runs(self.data)
            )
            rb = (
                other._i64_runs()
                if other.typ == TYPE_RUN
                else _positions_to_runs(other.data)
            )
            # Same scattered-operand gate as union (code review r5),
            # sized per ADVICE r5. The provable bound is ra+rb output
            # runs (an xor membership toggle needs an operand toggle;
            # ≤2(ra+rb) toggles → ≤ra+rb runs, achieved when one
            # operand's runs split the other's), so 2*(ra+rb) carries a
            # deliberate 2x margin: marginal operand pairs route to the
            # vectorized kernels, the direction the r5 perf fix chose
            # after the scattered-operand run sweep measured ~90x slow.
            if _runs_could_win(
                2 * (ra.shape[0] + rb.shape[0]), self._n + other._n
            ):
                # (a\b) and (b\a) are disjoint; their union coalesces
                # any adjacency the symmetric difference re-creates.
                return Container.from_runs(
                    _union_runs(
                        _difference_runs(ra, rb), _difference_runs(rb, ra)
                    )
                )
        a, b = self._unrun(), other._unrun()
        if a.typ == TYPE_ARRAY and b.typ == TYPE_ARRAY:
            return Container.from_positions(np.setxor1d(a.data, b.data, assume_unique=True))
        return Container.from_bitmap_words(a.bitmap_words() ^ b.bitmap_words())

    def flip(self) -> "Container":
        """Complement within the container (reference flipBitmap)."""
        return Container.from_bitmap_words(~self.bitmap_words())

    def shift_left_one(self) -> tuple["Container", bool]:
        """Shift all positions up by one; returns (container, carry-out).

        Mirrors reference roaring/roaring.go Shift (:946): a bit at 0xffff
        carries into the next container's bit 0.
        """
        pos = self.positions().astype(np.int32) + 1
        carry = bool(pos.size and pos[-1] == CONTAINER_WIDTH)
        pos = pos[pos < CONTAINER_WIDTH]
        return Container.from_positions(pos.astype(np.uint16)), carry


class Bitmap:
    """64-bit roaring bitmap: sorted map of container key -> Container.

    reference roaring/roaring.go:145. Containers are kept in a dict with a
    lazily maintained sorted key list (the reference offers slice- and
    btree-backed Containers implementations, roaring/containers_slice.go,
    containers_btree.go; a dict+sorted-keys is the idiomatic Python
    equivalent with the same O(log n) seek / O(1) hit behavior).
    """

    __slots__ = ("_cs", "_keys", "_keys_gen", "_keys_built", "op_writer",
                 "op_n", "flags")

    def __init__(self, values: Optional[Iterable[int]] = None):
        self._cs: dict[int, Container] = {}
        self._keys: list[int] = []
        # Key-list freshness is a GENERATION pair, not a dirty bool: a
        # locked writer racing an UNLOCKED reader's lazy rebuild (stack
        # pack under churn) could otherwise lose its dirty mark — reader
        # sorts, writer inserts + sets dirty, reader stores its stale
        # sort AND clears the flag — and the missing container would
        # survive every _pack_confirmed retry (exec/tiers.py), silently
        # breaking the host tables' exactness invariant.
        self._keys_gen = 0     # bumped by every container insert/delete
        self._keys_built = 0   # generation the cached sort was built at
        # Durability hook: fragment storage attaches a WAL writer here
        # (reference fragment.go:455 attaches the op writer; ops appended at
        # roaring/roaring.go:1612). None means no-op.
        self.op_writer = None
        self.op_n = 0
        self.flags = 0
        if values is not None:
            vals = np.asarray(list(values) if not isinstance(values, np.ndarray) else values, dtype=np.uint64)
            if vals.size:
                self.add_many(vals, log=False)

    # -- key bookkeeping -------------------------------------------------

    def keys(self) -> list[int]:
        if self._keys_gen != self._keys_built:
            # Read the generation BEFORE snapshotting: a writer landing
            # mid-sort bumps _keys_gen past `g`, so the cache stays
            # marked stale and the next call re-sorts. sorted(dict) is
            # a single GIL-atomic C snapshot for int keys (no Python
            # callbacks), so the sort itself cannot tear.
            g = self._keys_gen
            # lint: allow-shared-state(documented lock-free rebuild: the generation check above keeps a torn snapshot marked stale so the next reader re-sorts)
            self._keys = sorted(self._cs)
            # lint: allow-shared-state(publish ordered after the rebuild under program order; a racing writer bumps _keys_gen past g and the cache stays stale)
            self._keys_built = g
        return self._keys

    def container(self, key: int) -> Optional[Container]:
        return self._cs.get(key)

    def _put(self, key: int, c: Container) -> None:
        if PARANOIA:
            c.validate(key)
        if c.n == 0:
            if key in self._cs:
                # lint: allow-shared-state(a Bitmap is confined to its owning Fragment: every mutating path holds Fragment.lock; lock-free query readers follow the snapshot contract)
                del self._cs[key]
                # lint: allow-shared-state(generation RMW runs under the owning Fragment.lock with the mutation it stamps; unlocked keys readers only ever observe staleness)
                self._keys_gen += 1
            return
        is_new = key not in self._cs
        self._cs[key] = c
        if is_new:
            # Mutate-then-bump, matching the delete path above: bumping
            # BEFORE the insert would let an unlocked keys() rebuild
            # capture the post-bump generation with a pre-insert
            # snapshot and mark it fresh — the lost-staleness race the
            # generation counter exists to prevent.
            self._keys_gen += 1

    def put_container(self, key: int, c: Container) -> None:
        self._put(key, c)

    # -- basic ops -------------------------------------------------------

    def add(self, v: int, log: bool = True) -> bool:
        """DirectAdd + op-log append (reference roaring/roaring.go DirectAdd/Add)."""
        key, low = v >> 16, v & 0xFFFF
        c = self._cs.get(key)
        if c is None:
            self._put(key, Container(TYPE_ARRAY, np.array([low], dtype=np.uint16), 1))
            changed = True
        else:
            nc = c.with_bit(low)
            if nc is c:
                changed = False
            else:
                self._put(key, nc)
                changed = True
        if changed and log and self.op_writer is not None:
            self.op_writer.append_add(v)
            self.op_n += 1
        return changed

    def remove(self, v: int, log: bool = True) -> bool:
        key, low = v >> 16, v & 0xFFFF
        c = self._cs.get(key)
        if c is None:
            return False
        nc = c.without_bit(low)
        if nc is c:
            return False
        self._put(key, nc)
        if log and self.op_writer is not None:
            self.op_writer.append_remove(v)
            self.op_n += 1
        return True

    @staticmethod
    def from_sorted_array(vs: np.ndarray) -> "Bitmap":
        """Bulk-build from SORTED-UNIQUE uint64 values, skipping the
        np.unique re-sort add_many pays (ISSUE r14: the vectorized slab
        decode emits sorted output already — the Roaring reference's
        word-level bulk path). One container constructed per key group,
        no per-value work; copies each lows slice so the source buffer
        is never pinned."""
        bm = Bitmap()
        v = np.ascontiguousarray(vs, dtype=np.uint64)
        if v.size == 0:
            return bm
        keys = v >> np.uint64(16)
        lows = (v & np.uint64(0xFFFF)).astype(np.uint16)
        boundaries = np.nonzero(np.diff(keys))[0] + 1
        starts = np.concatenate(([0], boundaries))
        ends = np.concatenate((boundaries, [keys.size]))
        for s, e in zip(starts, ends):
            cnt = int(e - s)
            chunk = lows[s:e]
            if cnt <= ARRAY_MAX_SIZE:
                c = Container(TYPE_ARRAY, chunk.copy(), cnt)
            else:
                c = Container(TYPE_BITMAP, _as_bitmap_words(chunk), cnt)
            bm._put(int(keys[s]), c)
        return bm

    def add_many(self, vs: np.ndarray, log: bool = True) -> int:
        """Batch add; one AddBatch op-log record (reference DirectAddN)."""
        vs = np.asarray(vs, dtype=np.uint64)
        if vs.size == 0:
            return 0
        # ONE global value sort + dedup: keys come out grouped AND each
        # group's lows sorted+unique, so the per-container O(n log n)
        # np.unique disappears (import was sort-bound; the reference's
        # DirectAddN gets pre-sorted input from importPositions too,
        # fragment.go:2053).
        sv = np.unique(vs)
        keys = sv >> np.uint64(16)
        lows = (sv & np.uint64(0xFFFF)).astype(np.uint16)
        boundaries = np.nonzero(np.diff(keys))[0] + 1
        starts = np.concatenate(([0], boundaries))
        ends = np.concatenate((boundaries, [keys.size]))
        changed = 0
        for s, e in zip(starts, ends):
            changed += self._merge_lows(int(keys[s]), lows[s:e])
        if changed and log and self.op_writer is not None:
            # opN counts mutated values like the reference's op.count()
            # (roaring.go:1620), so it matches what a WAL replay computes.
            self.op_writer.append_add_batch(vs)
            # lint: allow-shared-state(op_n RMW is fragment-confined: every WAL-logged write path holds the owning Fragment.lock)
            self.op_n += int(vs.size)
        return changed

    def _merge_lows(self, key: int, chunk: np.ndarray) -> int:
        """Union one container's sorted-unique lows; returns bits added."""
        c = self._cs.get(key)
        if c is None:
            # Copy: from_positions would store the slice VIEW, pinning
            # the whole batch's lows buffer for the container's life.
            nc = Container.from_positions(chunk.copy())
        else:
            nc = c.with_many(chunk)
        self._put(key, nc)
        return nc.n - (c.n if c is not None else 0)

    def import_container_groups(
        self, keys: np.ndarray, counts: np.ndarray, lows: np.ndarray
    ) -> int:
        """Container-granular union (reference ImportRoaringBits,
        roaring/roaring.go:1511): pre-grouped sorted-unique lows per key
        (native.import_containers output) merge one container at a time —
        no per-value work, no comparison sort. Returns bits added.
        Op-logging is the caller's job (it holds the positions).

        OWNERSHIP: fresh containers keep zero-copy views of `lows`, so
        the caller must hand over an owned buffer it will not reuse
        (native.import_containers allocates one per call)."""
        changed = 0
        off = 0
        for j in range(keys.size):
            cnt = int(counts[j])
            key = int(keys[j])
            chunk = lows[off : off + cnt]
            c = self._cs.get(key)
            if c is None:
                if cnt <= ARRAY_MAX_SIZE:
                    nc = Container(TYPE_ARRAY, chunk, cnt)
                else:
                    nc = Container(TYPE_BITMAP, _as_bitmap_words(chunk), cnt)
                self._put(key, nc)
                changed += cnt
            else:
                nc = c.with_many(chunk)
                self._put(key, nc)
                changed += nc.n - c.n
            off += cnt
        return changed

    def remove_many(self, vs: np.ndarray, log: bool = True) -> int:
        vs = np.asarray(vs, dtype=np.uint64)
        if vs.size == 0:
            return 0
        sv = np.unique(vs)  # see add_many: grouped keys + sorted lows
        keys = sv >> np.uint64(16)
        lows = (sv & np.uint64(0xFFFF)).astype(np.uint16)
        boundaries = np.nonzero(np.diff(keys))[0] + 1
        starts = np.concatenate(([0], boundaries))
        ends = np.concatenate((boundaries, [keys.size]))
        changed = 0
        for s, e in zip(starts, ends):
            key = int(keys[s])
            c = self._cs.get(key)
            if c is not None:
                nc = c.without_many(lows[s:e])
                changed += c.n - nc.n
                self._put(key, nc)
        if changed and log and self.op_writer is not None:
            self.op_writer.append_remove_batch(vs)
            self.op_n += int(vs.size)
        return changed

    def optimize(self) -> int:
        """Re-pack containers as RLE runs where that is the smallest
        encoding (reference roaring.go Optimize). Batch mutators and
        run/array set algebra are run-preserving since r5; point
        mutators (with_bit/without_bit) and bitmap-side ops still leave
        array/bitmap results, so long-lived runny fragments call this
        after point-write churn to reclaim host RAM. Returns the number
        of containers converted."""
        converted = 0
        for key in self.keys():
            c = self._cs[key]
            if c.typ == TYPE_RUN:
                continue
            if c.typ == TYPE_ARRAY and not _runs_win(
                array_run_count(c.data), c.n
            ):
                continue
            runs = c.runs()
            if _runs_win(runs.shape[0], c.n):
                self._cs[key] = Container(
                    TYPE_RUN, runs.astype(np.uint16), c.n
                )
                converted += 1
        return converted

    def contains(self, v: int) -> bool:
        c = self._cs.get(v >> 16)
        return c is not None and c.contains(v & 0xFFFF)

    def count(self) -> int:
        return sum(c.n for c in self._cs.values())

    def any(self) -> bool:
        return any(c.n for c in self._cs.values())

    def count_range(self, start: int, end: int) -> int:
        """Count of bits in [start, end) (reference roaring.go:438)."""
        if end <= start:
            return 0
        skey, ekey = start >> 16, (end - 1) >> 16
        total = 0
        ks = self.keys()
        i = bisect.bisect_left(ks, skey)
        while i < len(ks) and ks[i] <= ekey:
            key = ks[i]
            c = self._cs[key]
            lo = start - (key << 16) if key == skey else 0
            hi = end - (key << 16) if key == ekey else CONTAINER_WIDTH
            if lo <= 0 and hi >= CONTAINER_WIDTH:
                total += c.n
            else:
                total += c.count_range(max(lo, 0), hi)
            i += 1
        return total

    def min(self) -> tuple[int, bool]:
        for key in self.keys():
            c = self._cs[key]
            if c.n:
                return (key << 16) | int(c.positions()[0]), True
        return 0, False

    def max(self) -> int:
        for key in reversed(self.keys()):
            c = self._cs[key]
            if c.n:
                return (key << 16) | int(c.positions()[-1])
        return 0

    def to_array(self) -> np.ndarray:
        """All set bits as a sorted uint64 array."""
        parts = []
        for key in self.keys():
            c = self._cs[key]
            if c.n:
                parts.append((np.uint64(key) << np.uint64(16)) | c.positions().astype(np.uint64))
        if not parts:
            return np.empty(0, dtype=np.uint64)
        return np.concatenate(parts)

    def __iter__(self) -> Iterator[int]:
        return iter(self.to_array().tolist())

    def iterate_from(self, start: int) -> Iterator[int]:
        arr = self.to_array()
        i = np.searchsorted(arr, np.uint64(start), side="left")
        return iter(arr[i:].tolist())

    # -- set algebra -----------------------------------------------------

    def _binary(self, other: "Bitmap", fn, keys: Iterable[int]) -> "Bitmap":
        out = Bitmap()
        empty = Container.empty()
        for key in keys:
            a = self._cs.get(key, empty)
            b = other._cs.get(key, empty)
            out._put(key, fn(a, b))
        return out

    def intersect(self, other: "Bitmap") -> "Bitmap":
        keys = self._cs.keys() & other._cs.keys()
        out = Bitmap()
        for key in keys:
            out._put(key, self._cs[key].intersect(other._cs[key]))
        return out

    def intersection_count(self, other: "Bitmap") -> int:
        keys = self._cs.keys() & other._cs.keys()
        # Array-array pairs batch into ONE native sorted-merge call per
        # row pair (reference intersectionCountArrayArray,
        # roaring/roaring.go:570) — the per-container Python dispatch was
        # the CPU executor's dominant cost at bench density; other type
        # pairs take the per-container path.
        aa_a: list[np.ndarray] = []
        aa_b: list[np.ndarray] = []
        total = 0
        for k in keys:
            ca, cb = self._cs[k], other._cs[k]
            if ca.typ == TYPE_ARRAY and cb.typ == TYPE_ARRAY:
                aa_a.append(ca.data)
                aa_b.append(cb.data)
            else:
                total += ca.intersection_count(cb)
        if aa_a:
            from pilosa_tpu import native

            n = native.intersection_count_many(aa_a, aa_b)
            if n is None:
                n = sum(
                    int(_sorted_member_mask(a, b).sum())
                    for a, b in zip(aa_a, aa_b)
                )
            total += n
        return total

    def union(self, other: "Bitmap") -> "Bitmap":
        return self._binary(other, Container.union, self._cs.keys() | other._cs.keys())

    def union_in_place(self, other: "Bitmap") -> None:
        for key, b in other._cs.items():
            a = self._cs.get(key)
            self._put(key, b if a is None else a.union(b))

    def difference(self, other: "Bitmap") -> "Bitmap":
        out = Bitmap()
        for key, a in self._cs.items():
            b = other._cs.get(key)
            out._put(key, a if b is None else a.difference(b))
        return out

    def xor(self, other: "Bitmap") -> "Bitmap":
        return self._binary(other, Container.xor, self._cs.keys() | other._cs.keys())

    def shift(self) -> "Bitmap":
        """Shift all bits up by one (reference roaring.go:946 Shift(1))."""
        out = Bitmap()
        carries: dict[int, bool] = {}
        for key in self.keys():
            c, carry = self._cs[key].shift_left_one()
            out._put(key, c)
            if carry:
                carries[key + 1] = True
        for key in carries:
            c = out._cs.get(key)
            one = Container(TYPE_ARRAY, np.array([0], dtype=np.uint16), 1)
            out._put(key, one if c is None else c.with_bit(0))
        return out

    def flip(self, start: int, end: int) -> "Bitmap":
        """Complement of bits in [start, end] inclusive (reference :1683)."""
        out = self.clone()
        for key in range(start >> 16, (end >> 16) + 1):
            lo = max(start - (key << 16), 0)
            hi = min(end - (key << 16), CONTAINER_WIDTH - 1)
            mask = np.zeros(CONTAINER_WIDTH, dtype=bool)
            mask[lo : hi + 1] = True
            mask_words = np.packbits(mask, bitorder="little").view(np.uint64)
            c = out._cs.get(key)
            words = c.bitmap_words() ^ mask_words if c is not None else mask_words
            out._put(key, Container.from_bitmap_words(words))
        return out

    def offset_range(self, offset: int, start: int, end: int) -> "Bitmap":
        """Bits in [start, end) re-based to offset (reference roaring.go:537).

        All three arguments must be container-aligned (multiples of 2^16) —
        same contract as the reference. Containers are shared, not copied.
        """
        assert offset & 0xFFFF == 0 and start & 0xFFFF == 0 and end & 0xFFFF == 0
        off_key, s_key, e_key = offset >> 16, start >> 16, end >> 16
        out = Bitmap()
        ks = self.keys()
        i = bisect.bisect_left(ks, s_key)
        while i < len(ks) and ks[i] < e_key:
            out._put(off_key + (ks[i] - s_key), self._cs[ks[i]])
            i += 1
        return out

    def clone(self) -> "Bitmap":
        out = Bitmap()
        out._cs = dict(self._cs)
        out._keys_gen = 1  # fresh instance: built==0 != gen -> re-sort
        return out

    # -- import (bulk union/clear from serialized roaring) ----------------

    def import_roaring_bits(self, data: bytes, clear: bool = False, log: bool = True, parsed: Optional["Bitmap"] = None) -> int:
        """Union (or clear) a serialized roaring bitmap into self in one op.

        reference roaring/roaring.go:1511 ImportRoaringBits; logged as a
        single AddRoaring/RemoveRoaring op (reference fragment.go:2255).
        Returns the number of bits changed. `parsed` lets a caller that
        already deserialized `data` (fragment.import_roaring reads the
        container keys for epoch stamping) skip the second parse; it
        must be the deserialization of `data` — the WAL still logs the
        raw bytes.
        """
        from pilosa_tpu.roaring.codec import deserialize

        other = parsed if parsed is not None else deserialize(data)
        changed = 0
        for key, b in other._cs.items():
            a = self._cs.get(key)
            if clear:
                if a is None:
                    continue
                nc = a.difference(b)
                changed += a.n - nc.n
                self._put(key, nc)
            else:
                if a is None:
                    changed += b.n
                    self._put(key, b)
                else:
                    nc = a.union(b)
                    changed += nc.n - a.n
                    self._put(key, nc)
        if changed and log and self.op_writer is not None:
            self.op_writer.append_roaring(data, changed, clear)
            self.op_n += changed
        return changed

    # -- serialization glue (implemented in codec.py) ---------------------

    def to_bytes(self) -> bytes:
        from pilosa_tpu.roaring.codec import serialize

        return serialize(self)

    @staticmethod
    def from_bytes(data: bytes) -> "Bitmap":
        from pilosa_tpu.roaring.codec import deserialize

        return deserialize(data)

    def __eq__(self, other) -> bool:
        if not isinstance(other, Bitmap):
            return NotImplemented
        return np.array_equal(self.to_array(), other.to_array())

    def __repr__(self) -> str:
        return f"Bitmap(count={self.count()}, containers={len(self._cs)})"
