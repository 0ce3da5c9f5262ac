"""Packed wire formats for cold stack uploads (VERDICT r4 #1, ISSUE r7).

Dense uint32[S, R, W/128, 128] is the right DEVICE layout for the sweep programs
but a wasteful WIRE format: at the bench shape the h-field stack ships
1 GB of which >80% of words are zero, and the host->HBM upload sits on
the 3-field GroupBy cold path. Which tiers earn their place on a locally
attached chip is ROADMAP S5's measurement. The reference never ships a
whole file when a delta will do
(/root/reference/roaring/roaring.go:1612 appends ops; :4649 unions
serialized containers); the same principle applied to the host->HBM hop.

Two sparse tiers, chosen PER CHUNK by measured occupancy:

  word-mask: (occupancy mask u32[C/32], nonzero words u32[B]) — wins
             when most 32-bit WORDS are zero (short fields, time-
             quantum views). Device: mask unpack -> prefix sum ->
             gather.
  container: the roaring containers themselves (ISSUE r7) — array
             containers ship their 16-bit positions (paged through one
             fixed-shape scatter program), run containers ship bit-span
             bounds, bitmap containers stay dense in a word-mask
             remainder. Wins exactly where the word mask loses: the
             bench f/g stacks at bit density 0.05 have ~80% word
             occupancy (no zeros to elide) but 16-bit positions still
             undercut the 32-bit words — the Chambi/Lemire container
             economics (PAPERS.md) applied to the host->HBM hop. The
             host never materializes the dense slab for these chunks,
             so the pack cost drops with the wire bytes.

Everything is FIXED-SHAPE so the XLA programs compile once per process
(warmable in the background at backend init) and never in a cold query
path: chunks are always CHUNK_WORDS words, value buffers are drawn from
a small bucket menu, container streams page through fixed-size buffers,
and a chunk no tier can beat simply ships dense (same placement
program).
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from pilosa_tpu import native
from pilosa_tpu.ops.blocks import (
    WORDS_PER_SHARD,
    _CONTAINERS_PER_ROW,
    _WORDS_PER_CONTAINER,
    pack_fragment,
)
from pilosa_tpu.roaring.bitmap import _runs_to_bitmap_words
from pilosa_tpu.utils.stats import global_stats

#: Fixed chunk size in uint32 words (32 MiB dense). Large enough that
#: per-chunk dispatch overhead vanishes, small enough that the staging
#: buffer and the per-chunk decompress transient stay cheap.
CHUNK_WORDS = 1 << 23

#: Value-buffer menu (words). A chunk ships with the smallest bucket
#: holding its nonzero count; denser chunks ship dense. Each bucket is
#: one compiled program, so the menu is deliberately short.
BUCKETS = (CHUNK_WORDS // 32, CHUNK_WORDS // 16, CHUNK_WORDS // 8,
           CHUNK_WORDS // 4)

#: Whole stacks below this skip chunking (one dense device_put is
#: simpler and the chunk-padding waste would dominate).
MIN_CHUNKED_WORDS = 2 * CHUNK_WORDS

#: Kill switch for the roaring-container wire tier — bench.py measures
#: the dense-baseline cold build by flipping this in the same process,
#: so the two cold_build_seconds figures compare wire formats under
#: identical conditions.
CONTAINER_TIER_ENABLED = True

#: In-flight upload bound (ADVICE r5 #2): compressed chunk buffers wait
#: in ChunkedStackBuilder._pending so uploads overlap the host pack, but
#: an unbounded queue holds EVERY chunk's device buffers until finish()
#: — on a borderline stack that transiently doubles the HBM footprint
#: the byte-budget admission check approved. Past this bound the builder
#: drains the placement chain early: pending chunks fold into the
#: accumulator (their buffers free as each placement dispatches) and the
#: queue resets, so peak transient HBM is stack + this bound.
MAX_PENDING_BYTES = 256 << 20


def _n_slots() -> int:
    """Roaring-container slots per chunk (container = 2048 words)."""
    return CHUNK_WORDS // _WORDS_PER_CONTAINER


def _pos_page() -> int:
    """Array-container positions per fixed expansion page. One page =
    one dispatch of ONE compiled program, so any position count streams
    through it; the size trades bucket-padding waste (≤ one page of
    u16s) against per-page dispatch overhead."""
    return max(1024, CHUNK_WORDS // 8)


def _run_page() -> int:
    """Run-container spans per fixed expansion page."""
    return max(256, CHUNK_WORDS // 128)


def compress_chunk(chunk: np.ndarray) -> tuple[np.ndarray, np.ndarray, int]:
    """(mask u32[C/32], vals u32[nnz], nnz) for one CHUNK_WORDS chunk.
    Bit b of mask[j] marks chunk[j*32+b] nonzero; vals are the nonzero
    words in order. Native C++ at ~1 GB/s with a numpy fallback."""
    mask = np.empty(CHUNK_WORDS // 32, dtype=np.uint32)
    vals_cap = np.empty(CHUNK_WORDS, dtype=np.uint32)
    nnz = native.compress_words(chunk, mask, vals_cap)
    if nnz is None:
        nz = chunk != 0
        np.bitwise_or.reduce(
            nz.reshape(-1, 32).astype(np.uint32)
            << np.arange(32, dtype=np.uint32)[None, :],
            axis=1, out=mask,
        )
        vals = chunk[nz]
        return mask, vals, int(vals.size)
    return mask, vals_cap[:nnz], nnz


def pick_bucket(nnz: int) -> Optional[int]:
    for b in BUCKETS:
        if nnz <= b:
            return b
    return None


# ---------------------------------------------------------------------------
# compiled programs (process-wide, keyed per device backend)
# ---------------------------------------------------------------------------

_progs: dict = {}
_progs_lock = threading.Lock()


def _dev_key(device) -> str:
    # None and the default device object both mean "the default device"
    # — canonicalized to one key so a warm with either spelling unlocks
    # builders constructed with the other (a mismatch silently forces
    # the dense path forever; code review r5).
    if device is not None and device != jax.devices()[0]:
        return str(device)
    return f"default-{jax.default_backend()}"


def _pin(device):
    """SingleDeviceSharding for a NON-default device, else None. The AOT
    `.lower().compile()` path binds an executable to the default device
    unless the avals carry a sharding; per-device mesh sub-stack
    builders (exec/tpu.py sharded cold build) need their programs
    compiled FOR their device or every call would raise a committed-
    operand/executable device mismatch."""
    if device is None or device == jax.devices()[0]:
        return None
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(device)


def _sds(shape, dtype, device):
    """ShapeDtypeStruct pinned to `device` when it is non-default."""
    pin = _pin(device)
    if pin is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=pin)


def _jit_out(fn, device, **kw):
    """jax.jit with outputs pinned to `device` when non-default — the
    zero-argument accumulator builders have no operand to carry the
    placement, so the out_shardings pin is what lands them on the right
    mesh device."""
    pin = _pin(device)
    if pin is not None:
        kw["out_shardings"] = pin
    return jax.jit(fn, **kw)


def _get_prog(name, key, build):
    full = (name,) + key
    with _progs_lock:
        fn = _progs.get(full)
    if fn is None:
        fn = build()
        with _progs_lock:
            fn = _progs.setdefault(full, fn)
    return fn


def _peek_prog(name, key):
    with _progs_lock:
        return _progs.get((name,) + key)


def chunk_prog_ready(device, bucket: int) -> bool:
    """True when the decompress program for this bucket is ALREADY
    compiled. The streaming builder ships a chunk sparse only then —
    compiling an XLA program inline would stall the very cold path this
    module exists to shorten (observed: a cold build racing its own
    background warm paid 4 serialized compiles). Before the warm lands,
    chunks ship dense — r4 behavior, never worse."""
    return _peek_prog("chunk", (_dev_key(device), CHUNK_WORDS, bucket)) is not None


def _chunk_prog(device, bucket: int):
    """u32[C] from (mask u32[C/32], vals u32[bucket]): unpack the
    occupancy bits, exclusive-prefix-sum them into gather indices, and
    select. The trailing zero positions may gather out of bounds when
    nnz == bucket; XLA clamps and the where() discards the value."""

    def build():
        def decompress(mask_words, vals):
            bits = (
                (mask_words[:, None]
                 >> jnp.arange(32, dtype=jnp.uint32)[None, :]) & 1
            ).reshape(-1).astype(jnp.int32)
            prefix = jnp.cumsum(bits) - bits
            return jnp.where(bits != 0, vals[prefix], 0).astype(jnp.uint32)

        return (
            jax.jit(decompress)
            .lower(
                _sds((CHUNK_WORDS // 32,), jnp.uint32, device),
                _sds((bucket,), jnp.uint32, device),
            )
            .compile()
        )

    # CHUNK_WORDS is in the key so tests can shrink the chunk size
    # without colliding with full-size cached programs.
    return _get_prog("chunk", (_dev_key(device), CHUNK_WORDS, bucket), build)


def _place_prog(device, n_pad: int):
    """acc u32[n_pad] <- dynamic_update_slice(acc, chunk u32[C], offset).
    acc is DONATED: the placement chain runs in-place, so a 1 GB stack
    holds one accumulator buffer instead of a queue of copies."""

    def build():
        def place(acc, chunk, offset):
            return jax.lax.dynamic_update_slice(acc, chunk, (offset,))

        return (
            jax.jit(place, donate_argnums=0)
            .lower(
                _sds((n_pad,), jnp.uint32, device),
                _sds((CHUNK_WORDS,), jnp.uint32, device),
                _sds((), jnp.int32, device),
            )
            .compile()
        )

    return _get_prog("place", (_dev_key(device), CHUNK_WORDS, n_pad), build)


def _zeros_prog(device, n_pad: int):
    def build():
        return _jit_out(
            lambda: jnp.zeros(n_pad, jnp.uint32), device
        ).lower().compile()

    return _get_prog("zeros", (_dev_key(device), n_pad), build)


def _final_prog(device, n_pad: int, shape: tuple):
    n = int(np.prod(shape))

    def build():
        def final(acc):
            return acc[:n].reshape(shape)

        # acc donated when the slice is the whole pad (XLA aliases the
        # reshape; a shorter slice can't alias — donating it would only
        # warn). Unaligned stacks pay one transient extra copy at the
        # final step, freed as soon as acc's ref drops.
        donate = (0,) if n == n_pad else ()
        return (
            jax.jit(final, donate_argnums=donate)
            .lower(_sds((n_pad,), jnp.uint32, device))
            .compile()
        )

    return _get_prog("final", (_dev_key(device), n_pad, shape), build)


def _chunk_zeros_prog(device):
    """Fresh all-zero chunk accumulator for container-tier expansion."""
    n = CHUNK_WORDS

    def build():
        return _jit_out(
            lambda: jnp.zeros(n, jnp.uint32), device
        ).lower().compile()

    return _get_prog("chunk_zeros", (_dev_key(device), n), build)


def _or_prog(device):
    """chunk | chunk (first operand donated) — merges the word-mask
    remainder of a container-tier chunk into its expansion accumulator."""
    n = CHUNK_WORDS

    def build():
        return (
            jax.jit(lambda a, b: a | b, donate_argnums=0)
            .lower(
                _sds((n,), jnp.uint32, device),
                _sds((n,), jnp.uint32, device),
            )
            .compile()
        )

    return _get_prog("chunk_or", (_dev_key(device), n), build)


def _pos_prog(device):
    """One page of array-container positions ORed into a donated chunk
    accumulator (ops/kernels.py expand_array_positions)."""
    n, p, s = CHUNK_WORDS, _pos_page(), _n_slots()

    def build():
        from pilosa_tpu.ops.kernels import expand_array_positions

        return (
            jax.jit(expand_array_positions, donate_argnums=0)
            .lower(
                _sds((n,), jnp.uint32, device),
                _sds((p,), jnp.uint16, device),
                _sds((s,), jnp.int32, device),
                _sds((), jnp.int32, device),
            )
            .compile()
        )

    return _get_prog("chunk_pos", (_dev_key(device), n, p, s), build)


def _run_prog(device):
    """One page of run-container spans ORed into a donated chunk
    accumulator (ops/kernels.py expand_run_spans)."""
    n, r = CHUNK_WORDS, _run_page()

    def build():
        from pilosa_tpu.ops.kernels import expand_run_spans

        return (
            jax.jit(expand_run_spans, donate_argnums=0)
            .lower(
                _sds((n,), jnp.uint32, device),
                _sds((r,), jnp.int32, device),
                _sds((r,), jnp.int32, device),
                _sds((), jnp.int32, device),
            )
            .compile()
        )

    return _get_prog("chunk_runs", (_dev_key(device), n, r), build)


def container_progs_ready(device) -> bool:
    """True when every container-tier expansion program is ALREADY
    compiled — same warm-gate contract as chunk_prog_ready: before the
    background warm lands, container chunks materialize dense instead of
    stalling the cold path on a multi-second XLA compile."""
    k = _dev_key(device)
    return (
        _peek_prog("chunk_zeros", (k, CHUNK_WORDS)) is not None
        and _peek_prog("chunk_or", (k, CHUNK_WORDS)) is not None
        and _peek_prog("chunk_pos", (k, CHUNK_WORDS, _pos_page(), _n_slots()))
        is not None
        and _peek_prog("chunk_runs", (k, CHUNK_WORDS, _run_page())) is not None
    )


_warmed: set = set()
_warm_inflight: set = set()


def warm_chunk_programs(device) -> threading.Thread:
    """Background-compile the fixed-shape chunk programs so a cold stack
    build never pays their XLA compile on its critical path (the
    placement/zeros/final programs are per-stack-shape and compile in
    ~1 s; the chunk programs are the expensive ones). Idempotent while
    a warm is in flight or succeeded; a FAILED warm retries on the next
    call — latching the failure would silently pin the dense path for
    the process lifetime (code review r5)."""
    key = _dev_key(device)

    def run():
        try:
            for b in BUCKETS:
                _chunk_prog(device, b)
            # Container-tier expansion programs (ISSUE r7): warmed in the
            # same pass so the f/g-shaped stacks ship container-native on
            # the first post-warm build.
            _chunk_zeros_prog(device)
            _or_prog(device)
            _pos_prog(device)
            _run_prog(device)
            with _progs_lock:
                _warmed.add(key)
        except Exception:  # noqa: BLE001 — best-effort: the builder's
            # warm-gate keeps shipping dense chunks; counted so the
            # silent-dense regression is visible on /metrics.
            global_stats.count("stack_sparse_warm_failures_total")
        finally:
            with _progs_lock:
                _warm_inflight.discard(key)

    from pilosa_tpu.utils.threads import spawn

    with _progs_lock:
        if key in _warmed or key in _warm_inflight:
            # joinable no-op: callers may t.join() the result
            return spawn("sparse-warm", lambda: None)
        _warm_inflight.add(key)
    return spawn("sparse-warm", run, name="sparse-warm")


class ChunkedStackBuilder:
    """Streaming builder for one device stack: the caller feeds host
    words in order (shard slab granularity) — dense via feed(), known-
    zero regions via skip(), whole fragments container-native via
    feed_fragment() — and chunks compress and upload as they fill,
    overlapping the remaining host pack with the wire; finish() chains
    the donated placements and returns the dense [shape] device array.

    Upload strategy per chunk, by measured occupancy: all-zero chunks
    ship NOTHING (the accumulator is already zero), word-sparse chunks
    ship mask+bucket, container-fed chunks ship 16-bit positions /
    run spans (+ a word-mask remainder for bitmap containers), and a
    chunk no tier can beat ships raw words — so worst-case degenerates
    to the dense path plus a placement copy, never worse wire-wise.

    In-flight device buffers are bounded by MAX_PENDING_BYTES (ADVICE
    r5 #2): past the bound, pending chunks drain into the placement
    accumulator early instead of stacking on top of it."""

    @staticmethod
    def transient_bytes(shape: tuple) -> int:
        """Device bytes a build of `shape` holds beside the stack it
        makes: the pending chunks' buffers (at most MAX_PENDING_BYTES,
        and at most the stack), a chunk being placed and one being
        expanded, the accumulator's padding past the stack, and where
        that padding is not zero the final slice's copy of the stack
        (finish())."""
        n = int(np.prod(shape))
        n_pad = ((n + CHUNK_WORDS - 1) // CHUNK_WORDS) * CHUNK_WORDS
        return (
            min(MAX_PENDING_BYTES, n * 4) + 2 * CHUNK_WORDS * 4
            + (n_pad - n) * 4 + (n * 4 if n_pad != n else 0)
        )

    def __init__(self, device, shape: tuple):
        self.device = device
        self.shape = tuple(int(s) for s in shape)
        n = int(np.prod(self.shape))
        self.n_pad = ((n + CHUNK_WORDS - 1) // CHUNK_WORDS) * CHUNK_WORDS
        self._stage = np.zeros(CHUNK_WORDS, dtype=np.uint32)
        # True when the CURRENT chunk's stage holds any fed words (the
        # container path skips the stage entirely, so a clean stage
        # never pays the compress scan or a post-flush re-zero).
        self._stage_dirty = False
        self._fill = 0
        self._offset = 0
        # (offset, kind, device buffers) per non-empty chunk; uploads
        # start here (async) while later slabs are still packing.
        self._pending: list = []
        self._pending_bytes = 0
        self._acc = None  # placement accumulator once draining starts
        self._wire_bytes = 0
        self._dense_bytes = 0
        # Roaring-container entries for the CURRENT chunk: (slot, data)
        # where data is the container's own array (u16 positions) or
        # run table (u16 [R, 2]) — zero-copy references, never
        # host-materialized unless the tier decision falls back.
        self._chunk_arrays: list = []
        self._chunk_runs: list = []

    def feed(self, words: np.ndarray) -> None:
        """Append a flat uint32 slab (any length)."""
        pos = 0
        n = words.size
        while pos < n:
            take = min(CHUNK_WORDS - self._fill, n - pos)
            self._stage[self._fill : self._fill + take] = words[pos : pos + take]
            self._stage_dirty = True
            self._fill += take
            pos += take
            if self._fill == CHUNK_WORDS:
                self._flush()

    def skip(self, n_words: int) -> None:
        """Advance over a known-all-zero region (missing fragments,
        shard padding) without staging a byte — the stage starts each
        chunk zeroed, so skipped spans are already correct."""
        self._advance(self._offset + self._fill + int(n_words))

    def _advance(self, target: int) -> None:
        """Move the global write position forward to `target`, flushing
        full chunks crossed on the way."""
        while target >= self._offset + CHUNK_WORDS:
            self._fill = CHUNK_WORDS
            self._flush()
        self._fill = target - self._offset

    def feed_fragment(self, frag, n_rows: int) -> None:
        """Stream one fragment's slab container-natively (ISSUE r7):
        array/run containers are RECORDED for the container wire tier —
        the host never scatters their bits into a dense slab — bitmap
        containers memcpy into the stage, and inter-container gaps just
        advance. Advances exactly n_rows * WORDS_PER_SHARD words, like
        feeding pack_fragment(frag, n_rows) densely (n_rows must be
        ROW_PAD-aligned, which every stack build guarantees). Falls back
        to the dense feed when the tier is disabled or the geometry
        can't carry containers (shrunken test chunks, unaligned base)."""
        base = self._offset + self._fill
        if (
            not CONTAINER_TIER_ENABLED
            or CHUNK_WORDS % _WORDS_PER_CONTAINER
            or base % _WORDS_PER_CONTAINER
        ):
            self.feed(pack_fragment(frag, n_rows=n_rows).reshape(-1))
            return
        storage = frag.storage
        for key in storage.keys():
            c = storage.container(key)
            if c is None or c.n == 0:
                continue
            row = key // _CONTAINERS_PER_ROW
            if row >= n_rows:
                continue  # caller asked for fewer rows than stored
            gw = base + row * WORDS_PER_SHARD + (
                key % _CONTAINERS_PER_ROW
            ) * _WORDS_PER_CONTAINER
            self._advance(gw)
            slot = self._fill // _WORDS_PER_CONTAINER
            if c.typ == "array":
                self._chunk_arrays.append((slot, c.data))
            elif c.typ == "run":
                self._chunk_runs.append((slot, c.data))
            else:  # bitmap container: already dense — memcpy to stage
                self._stage[
                    self._fill : self._fill + _WORDS_PER_CONTAINER
                ] = c.data.view("<u4")
                self._stage_dirty = True
            self._advance(gw + _WORDS_PER_CONTAINER)
        self._advance(base + n_rows * WORDS_PER_SHARD)

    def _flush(self) -> None:
        if self._fill == 0 and not self._chunk_arrays and not self._chunk_runs:
            return
        self._dense_bytes += CHUNK_WORDS * 4
        if self._chunk_arrays or self._chunk_runs:
            self._flush_container_chunk()
        elif self._stage_dirty:
            self._flush_dense_chunk()
        self._offset += CHUNK_WORDS
        self._fill = 0
        if self._stage_dirty:
            self._stage[:] = 0
            self._stage_dirty = False
        self._chunk_arrays = []
        self._chunk_runs = []
        if self._pending_bytes > MAX_PENDING_BYTES:
            # In-flight bound (ADVICE r5 #2): fold what's queued into
            # the accumulator now; each placement dispatch releases its
            # chunk's upload buffers.
            global_stats.count("stack_pending_drains_total")
            self._drain_pending()

    def _flush_dense_chunk(self) -> None:
        """The word-granular tiers over the staged chunk: nothing /
        mask+bucket / raw words (the r4 wire)."""
        mask, vals, nnz = compress_chunk(self._stage)
        if nnz == 0:
            return  # accumulator is already zero here: ship nothing
        bucket = pick_bucket(nnz)
        if bucket is not None and not chunk_prog_ready(self.device, bucket):
            global_stats.count("stack_sparse_not_warm_total")
            bucket = None
        if bucket is None:
            chunk_d = jax.device_put(self._stage.copy(), self.device)
            self._pending.append((self._offset, "dense", (chunk_d,)))
            self._note_wire(CHUNK_WORDS * 4)
        else:
            if vals.size < bucket:
                vals = np.concatenate(
                    [vals, np.zeros(bucket - vals.size, dtype=np.uint32)]
                )
            mask_d = jax.device_put(mask, self.device)
            vals_d = jax.device_put(vals, self.device)
            self._pending.append((self._offset, "sparse", (mask_d, vals_d)))
            self._note_wire(mask.nbytes + bucket * 4)

    def _note_wire(self, nbytes: int) -> None:
        self._wire_bytes += nbytes
        self._pending_bytes += nbytes

    def _flush_container_chunk(self) -> None:
        """The roaring-container wire (ISSUE r7), taken when its
        measured size undercuts dense; bitmap containers and generic
        dense feeds in the same chunk ride a word-mask remainder. The
        bench f/g regime (~80% word occupancy, ~5% bit occupancy) is
        exactly where this wins: the zero-word mask finds no zeros to
        elide, but 16-bit array positions still undercut 32-bit words —
        and the host never materialized the dense slab at all."""
        dev = self.device
        npos = int(sum(d.size for _, d in self._chunk_arrays))
        nruns = int(sum(d.shape[0] for _, d in self._chunk_runs))
        pp, rp, ns = _pos_page(), _run_page(), _n_slots()
        rem = None
        rem_wire = 0
        if self._stage_dirty:
            mask, vals, nnz_rem = compress_chunk(self._stage)
            if nnz_rem:
                bucket = pick_bucket(nnz_rem)
                if bucket is None or not chunk_prog_ready(dev, bucket):
                    # Dense remainder: the combined wire can't beat raw
                    # words — materialize and let the dense tiers decide.
                    self._materialize_dense()
                    return
                rem = (mask, vals, bucket)
                rem_wire = mask.nbytes + bucket * 4
        wire = (
            ((npos + pp - 1) // pp) * (pp * 2 + ns * 4)
            + ((nruns + rp - 1) // rp) * (rp * 8)
            + rem_wire
        )
        if wire >= CHUNK_WORDS * 4 or not container_progs_ready(dev):
            if not container_progs_ready(dev):
                global_stats.count("stack_container_not_warm_total")
            self._materialize_dense()
            return
        parts: list = []
        if npos:
            slots = np.fromiter(
                (s for s, _ in self._chunk_arrays), dtype=np.int32,
                count=len(self._chunk_arrays),
            )
            sizes = np.fromiter(
                (d.size for _, d in self._chunk_arrays), dtype=np.int64,
                count=len(self._chunk_arrays),
            )
            pos_cat = np.concatenate(
                [np.asarray(d, dtype=np.uint16) for _, d in self._chunk_arrays]
            )
            slot_of = np.repeat(slots, sizes)
            for p0 in range(0, npos, pp):
                sl = slice(p0, min(p0 + pp, npos))
                page = pos_cat[sl]
                nnz = page.size
                if nnz < pp:
                    page = np.concatenate(
                        [page, np.zeros(pp - nnz, dtype=np.uint16)]
                    )
                counts = np.bincount(slot_of[sl], minlength=ns).astype(np.int32)
                parts.append((
                    "pos",
                    (
                        jax.device_put(page, dev),
                        jax.device_put(counts, dev),
                        jax.device_put(np.int32(nnz), dev),
                    ),
                ))
        if nruns:
            lo_parts, hi_parts = [], []
            for slot, runs in self._chunk_runs:
                base_bit = np.int32(slot * _WORDS_PER_CONTAINER * 32)
                r = runs.astype(np.int32)
                lo_parts.append(base_bit + r[:, 0])
                hi_parts.append(base_bit + r[:, 1])
            lo_cat = np.concatenate(lo_parts)
            hi_cat = np.concatenate(hi_parts)
            for r0 in range(0, nruns, rp):
                sl = slice(r0, min(r0 + rp, nruns))
                lo, hi = lo_cat[sl], hi_cat[sl]
                nnz = lo.size
                if nnz < rp:
                    pad = np.zeros(rp - nnz, dtype=np.int32)
                    lo = np.concatenate([lo, pad])
                    hi = np.concatenate([hi, pad])
                parts.append((
                    "run",
                    (
                        jax.device_put(lo, dev),
                        jax.device_put(hi, dev),
                        jax.device_put(np.int32(nnz), dev),
                    ),
                ))
        if rem is not None:
            mask, vals, bucket = rem
            if vals.size < bucket:
                vals = np.concatenate(
                    [vals, np.zeros(bucket - vals.size, dtype=np.uint32)]
                )
            parts.append((
                "rem",
                (jax.device_put(mask, dev), jax.device_put(vals[:bucket], dev)),
            ))
        self._pending.append((self._offset, "cont", tuple(parts)))
        self._note_wire(wire)
        global_stats.count("stack_container_chunks_total")
        global_stats.count("stack_container_pos_total", npos)
        global_stats.count("stack_container_runs_total", nruns)
        global_stats.count("stack_container_wire_bytes_total", wire)

    def _materialize_dense(self) -> None:
        """Container-tier fallback: scatter the recorded containers into
        the stage (what pack_fragment would have done up front) and let
        the word-granular tiers ship the chunk."""
        for slot, data in self._chunk_arrays:
            base = slot * _WORDS_PER_CONTAINER
            d = np.ascontiguousarray(data, dtype=np.uint16)
            if not native.scatter_positions(self._stage, base, d):
                pos = d.astype(np.uint32)
                np.bitwise_or.at(
                    self._stage,
                    base + (pos >> 5),
                    np.uint32(1) << (pos & np.uint32(31)),
                )
        for slot, runs in self._chunk_runs:
            base = slot * _WORDS_PER_CONTAINER
            self._stage[base : base + _WORDS_PER_CONTAINER] |= (
                _runs_to_bitmap_words(runs).view("<u4")
            )
        self._stage_dirty = True
        self._flush_dense_chunk()

    def _drain_pending(self) -> None:
        """Fold every queued chunk into the placement accumulator.
        Each chunk's upload buffers drop as soon as its placement is
        dispatched — holding all of them through the chain makes peak
        HBM ~3x the stack on a dense stack (code review r5), invisible
        to the caller's max_bytes admission check."""
        dev = self.device
        if self._acc is None:
            self._acc = _zeros_prog(dev, self.n_pad)()
        for i in range(len(self._pending)):
            offset, kind, bufs = self._pending[i]
            self._pending[i] = None
            if kind == "sparse":
                mask_d, vals_d = bufs
                chunk = _chunk_prog(dev, vals_d.shape[0])(mask_d, vals_d)
            elif kind == "dense":
                (chunk,) = bufs
            else:  # "cont": expand pages into a fresh chunk accumulator
                chunk = _chunk_zeros_prog(dev)()
                for ckind, cbufs in bufs:
                    if ckind == "pos":
                        chunk = _pos_prog(dev)(chunk, *cbufs)
                    elif ckind == "run":
                        chunk = _run_prog(dev)(chunk, *cbufs)
                    else:  # "rem"
                        mask_d, vals_d = cbufs
                        dec = _chunk_prog(dev, vals_d.shape[0])(mask_d, vals_d)
                        chunk = _or_prog(dev)(chunk, dec)
            del bufs
            self._acc = _place_prog(dev, self.n_pad)(
                self._acc, chunk, jax.device_put(np.int32(offset), dev)
            )
            del chunk
        self._pending.clear()
        self._pending_bytes = 0

    def finish(self):
        self._flush()
        self._drain_pending()
        out = _final_prog(self.device, self.n_pad, self.shape)(self._acc)
        self._acc = None
        global_stats.count("stack_sparse_uploads_total")
        global_stats.count("stack_sparse_wire_bytes_total", self._wire_bytes)
        global_stats.count("stack_sparse_dense_bytes_total", self._dense_bytes)
        return out
