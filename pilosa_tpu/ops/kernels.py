"""Pallas TPU kernel for batched bitmap-count statistics.

The serving hot path: a batch of Count(verb(Row(f=a), Row(g=b))) queries
draws from few distinct rows (a bitmap field's row space is small next to
a batch), so instead of re-gathering ~2 rows x shards per query
(the reference's per-query loop, executor.go:2460), ONE blocked sweep of
both field stacks computes the sufficient statistics for every possible
2-row query:

    pair[a, b] = popcount(F_a & G_b)   -- the pair-count matrix
    cf[a]      = popcount(F_a)
    cg[b]      = popcount(G_b)

and the host derives any verb in O(1) per query:

    Intersect  = pair[a,b]
    Union      = cf[a] + cg[b] - pair[a,b]
    Difference = cf[a] - pair[a,b]
    Xor        = cf[a] + cg[b] - 2*pair[a,b]

Each stack byte is read exactly once per batch — the row-reuse roofline —
vs bytes x queries for the naive loop. Measured on v5e at the 1B-column
bench shape (954 shards, 8 rows/field): 2.8 ms per sweep (chip, PR 27; an
earlier 1.65 ms was read against 2.73 ms for the equivalent fused-XLA
broadcast) and ~64 GB of re-gathered traffic for
the per-query loop. The kernel tiles [1, R, LT, 128] blocks of both stacks
through VMEM over a (shards, word-line-tiles) grid, accumulating all three
stats in VMEM across grid steps (dimension_semantics=arbitrary keeps the
accumulator resident). Stacks are uint32[S, R, L, 128], the words of a
shard row as L lines of 128 (ops/blocks.py): a row of a block is whole
(8, 128) vregs, so the [Rf, Rg] broadcast runs over leading axes and
never across sublanes.

Counts accumulate in int32: a (row-pair, shard) popcount is <= 2^20, so
the sweep is exact while S*2^20 < 2^31, i.e. up to MAX_PAIR_SHARDS
shards; taller sweeps fall back to the caller's per-query path.

On non-TPU backends (the CPU test mesh) the same kernel runs in Pallas
interpret mode so differential tests exercise the identical code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# int32 accumulator bound: MAX_PAIR_SHARDS * 2^20 < 2^31.
MAX_PAIR_SHARDS = 2047

# VMEM budget for the broadcast intermediate [Rf, Rg, LT, 128] (int32) —
# half of the 16 MiB VMEM, leaving headroom for double-buffered input
# tiles and the accumulator blocks.
_VMEM_TILE_BYTES = 8 * 1024 * 1024

#: Sublanes of a vreg: the fewest word lines a block may hold (Mosaic
#: wants a block's second-minor dim a multiple of it, or the whole axis).
_MIN_LINES = 8


def _word_counts(x):
    """Sum an int32[..., LT, 128] popcount block over both word axes, lines
    first (whole-vreg adds), then lanes. One joint (-2, -1) reduce aborts
    Mosaic's layout pass (jax 0.9.0) and a flattening reshape relays the
    block out in VMEM: 6.64 ms a sweep against 2.82 (chip, PR 27)."""
    return jnp.sum(jnp.sum(x, axis=-2), axis=-1)


def _sequential_grid(n_axes: int, vmem_limit_bytes=None):
    """Mosaic parameters for the accumulating kernels: every grid axis
    is ARBITRARY — visited in order on one core — because each kernel
    carries an output block in VMEM across consecutive grid steps. An
    axis left to the default could be split or reordered and the
    accumulator silently lost. `vmem_limit_bytes` None is Mosaic's
    default scoped limit."""
    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.GridDimensionSemantics.ARBITRARY,) * n_axes,
        vmem_limit_bytes=vmem_limit_bytes,
    )


def _pair_stats_kernel(f_ref, g_ref, pair_ref, cf_ref, cg_ref):
    s = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(jnp.logical_and(s == 0, w == 0))
    def _():
        pair_ref[...] = jnp.zeros_like(pair_ref)
        cf_ref[...] = jnp.zeros_like(cf_ref)
        cg_ref[...] = jnp.zeros_like(cg_ref)

    f = f_ref[0]  # [Rf, LT, 128]
    g = g_ref[0]  # [Rg, LT, 128]
    pc = jax.lax.population_count(f[:, None] & g[None]).astype(jnp.int32)
    pair_ref[...] += _word_counts(pc)
    cf_ref[...] += _word_counts(jax.lax.population_count(f).astype(jnp.int32))
    cg_ref[...] += _word_counts(jax.lax.population_count(g).astype(jnp.int32))


def _line_tile(resident_rows: int, lines: int, lanes: int) -> int:
    """Word lines a block: the largest halving of `lines` whose
    resident_rows x LT x lanes int32 working set fits the VMEM budget."""
    lt = lines
    while (
        resident_rows * lt * lanes * 4 > _VMEM_TILE_BYTES
        and lt % (2 * _MIN_LINES) == 0
    ):
        lt //= 2
    return lt


@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_stats(f_stack, g_stack, interpret: bool = False):
    """(uint32[S, Rf, L, 128], uint32[S, Rg, L, 128]) ->
    (pair int32[Rf, Rg], cf int32[Rf], cg int32[Rg]).

    Single-device form; the mesh path shard_maps this over the shard axis
    and psums the partials (see TPUBackend._pair_program).
    """
    s, rf, lines, lanes = f_stack.shape
    rg = g_stack.shape[1]
    lt = _line_tile(rf * rg, lines, lanes)
    return pl.pallas_call(
        _pair_stats_kernel,
        grid=(s, lines // lt),
        in_specs=[
            pl.BlockSpec((1, rf, lt, lanes), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, rg, lt, lanes), lambda i, j: (i, 0, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rf, rg), lambda i, j: (0, 0)),
            pl.BlockSpec((rf,), lambda i, j: (0,)),
            pl.BlockSpec((rg,), lambda i, j: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rf, rg), jnp.int32),
            jax.ShapeDtypeStruct((rf,), jnp.int32),
            jax.ShapeDtypeStruct((rg,), jnp.int32),
        ],
        compiler_params=_sequential_grid(2),
        interpret=interpret,
    )(f_stack, g_stack)


def _pair_stats_pershard_kernel(f_ref, g_ref, pair_ref, cf_ref, cg_ref):
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _():
        pair_ref[...] = jnp.zeros_like(pair_ref)
        cf_ref[...] = jnp.zeros_like(cf_ref)
        cg_ref[...] = jnp.zeros_like(cg_ref)

    f = f_ref[0]  # [Rf, LT, 128]
    g = g_ref[0]  # [Rg, LT, 128]
    pc = jax.lax.population_count(f[:, None] & g[None]).astype(jnp.int32)
    pair_ref[0] += _word_counts(pc)
    cf_ref[0, 0] += _word_counts(jax.lax.population_count(f).astype(jnp.int32))
    cg_ref[0, 0] += _word_counts(jax.lax.population_count(g).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_stats_pershard(f_stack, g_stack, interpret: bool = False):
    """pair_stats WITHOUT the shard reduction:
    (uint32[S, Rf, L, 128], uint32[S, Rg, L, 128]) ->
    (pair int32[S, Rf, Rg], cf int32[S, 1, Rf], cg int32[S, 1, Rg]).

    The per-shard table is what makes write churn cheap: the host keeps
    it resident, totals are its int64 sum, and a write epoch that dirtied
    D shards replaces D rows of the table from host-packed slabs
    (exec/tiers.py _host_slab_pair_flat) instead of re-sweeping the stacks on
    device — the reference's incremental rank-cache maintenance
    (cache.go:136-301) applied to the pair matrix. Per-shard counts are
    <= 2^20 so int32 is exact for ANY shard count (the summed kernel's
    MAX_PAIR_SHARDS bound applies only to device-side totals)."""
    s, rf, lines, lanes = f_stack.shape
    rg = g_stack.shape[1]
    lt = _line_tile(rf * rg, lines, lanes)
    return pl.pallas_call(
        _pair_stats_pershard_kernel,
        # Shards outermost: each shard's output blocks see their word-tile
        # visits consecutively, so the VMEM accumulator carries across w
        # and flushes once per shard.
        grid=(s, lines // lt),
        in_specs=[
            pl.BlockSpec((1, rf, lt, lanes), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, rg, lt, lanes), lambda i, j: (i, 0, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, rf, rg), lambda i, j: (i, 0, 0)),
            # cf/cg carry a singleton middle axis: Mosaic requires the
            # block's last two dims to divide (8, 128) or equal the array
            # dims, and a [S, R] layout's (1, R) block satisfies neither.
            pl.BlockSpec((1, 1, rf), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, rg), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, rf, rg), jnp.int32),
            jax.ShapeDtypeStruct((s, 1, rf), jnp.int32),
            jax.ShapeDtypeStruct((s, 1, rg), jnp.int32),
        ],
        compiler_params=_sequential_grid(2),
        interpret=interpret,
    )(f_stack, g_stack)


# ---------------------------------------------------------------------------
# Container-native upload expansion (ISSUE r7): device-side rebuild of a
# dense uint32 chunk from roaring-container wire buffers (ops/sparse.py
# CONTAINER tier). Fixed shapes only — ops/sparse.py AOT-compiles these
# once per process and pages variable-size container streams through
# them, so no chunk ever pays an XLA compile on a cold build path.
# ---------------------------------------------------------------------------

#: Bits per roaring container slot (the 16-bit low-position domain).
CONTAINER_SLOT_BITS = 1 << 16


def expand_array_positions(acc, pos16, slot_counts, nnz):
    """OR one page of array-container bits into the chunk accumulator.

    acc: uint32[C] dense chunk words (donated by the caller's compile).
    pos16: uint16[P] low 16 bits of each set position, grouped by slot
        in ascending slot order; entries past nnz are padding.
    slot_counts: int32[NSLOTS] positions-per-slot for THIS page (sums
        to nnz), mapping each pos16 entry back to its container slot.
    nnz: int32 scalar, live entries in pos16.

    The scatter uses add, which equals OR here: positions within a
    container are unique (sorted-unique array invariant) and container
    slots partition the chunk's word space, so no (word, bit) pair is
    ever contributed twice — by this page, another page, or another
    wire tier (runs/remainder cover disjoint slots). Padding entries
    are routed out of bounds and dropped.
    """
    n_slots = slot_counts.shape[0]
    p = pos16.shape[0]
    slot = jnp.repeat(
        jnp.arange(n_slots, dtype=jnp.int32), slot_counts,
        total_repeat_length=p,
    )
    bit = slot * CONTAINER_SLOT_BITS + pos16.astype(jnp.int32)
    valid = jnp.arange(p, dtype=jnp.int32) < nnz
    word = jnp.where(valid, bit >> 5, acc.shape[0])
    val = jnp.left_shift(
        jnp.uint32(1), (bit & 31).astype(jnp.uint32)
    )
    # The wire stream is globally ascending (slots ascend, positions
    # ascend within a container) and padding lands past the end, so the
    # scatter indices are non-decreasing — declared so XLA can lower a
    # sequential-window scatter instead of the generic one.
    return acc.at[word].add(val, mode="drop", indices_are_sorted=True)


def expand_run_spans(acc, lo, hi, nnz):
    """OR one page of run-container spans into the chunk accumulator.

    lo/hi: int32[R] inclusive chunk-relative bit bounds per run (slot
    base already folded in by the host); entries past nnz are padding.
    Each run decomposes into at most two partial edge words (scatter-
    add; masks from distinct runs in one word are disjoint because runs
    are disjoint, so add equals OR) and an interior of all-ones words
    recovered by a +1/-1 boundary scatter and a cumsum coverage test —
    no per-run loop, so one fixed-shape program serves any run count.
    """
    c = acc.shape[0]
    full = jnp.uint32(0xFFFFFFFF)
    r = lo.shape[0]
    valid = jnp.arange(r, dtype=jnp.int32) < nnz
    w_lo = lo >> 5
    w_hi = hi >> 5
    m_lo = jnp.left_shift(full, (lo & 31).astype(jnp.uint32))
    m_hi = jnp.right_shift(full, (31 - (hi & 31)).astype(jnp.uint32))
    same = w_lo == w_hi
    # Runs arrive sorted-disjoint with padding past the live prefix, so
    # the first-edge indices are non-decreasing; the second-edge and
    # interior-delta scatters interleave dropped entries and stay
    # generic.
    acc = acc.at[jnp.where(valid, w_lo, c)].add(
        jnp.where(same, m_lo & m_hi, m_lo), mode="drop",
        indices_are_sorted=True,
    )
    acc = acc.at[jnp.where(valid & ~same, w_hi, c)].add(m_hi, mode="drop")
    # Interior words [w_lo+1, w_hi) are fully covered; delta has one +1
    # per span start and one -1 per span end, so the running sum is
    # positive exactly inside some span (spans from disjoint runs never
    # overlap, so counts cannot cancel across runs).
    start = w_lo + 1
    has_interior = valid & (start < w_hi)
    delta = jnp.zeros((c + 1,), jnp.int32)
    delta = delta.at[jnp.where(has_interior, start, c + 1)].add(1, mode="drop")
    delta = delta.at[jnp.where(has_interior, w_hi, c + 1)].add(-1, mode="drop")
    cover = jnp.cumsum(delta[:-1]) > 0
    return acc | jnp.where(cover, full, jnp.uint32(0))


# ---------------------------------------------------------------------------
# Sharded dirty-shard splice (ISSUE r13 tentpole 1): the per-device body
# of the mesh incremental stack update. Runs INSIDE shard_map — every
# operand is the device's local block of a NamedSharding(P('shards'))
# placement, so splicing never gathers the stack over ICI; each device
# applies only the slabs addressed to it.
# ---------------------------------------------------------------------------


def splice_shard_slabs(block, slabs, idx, valid):
    """Splice dirty shard slabs into one device's local stack block.

    block: uint32[S_local, R, L, 128] — this device's shard slabs.
    slabs: uint32[C, R, L, 128] — replacement slabs for this device (padding
        entries are ignored via `valid`).
    idx: int32[C] — LOCAL shard positions (0..S_local-1) each slab
        lands at; padding entries may hold any in-range value.
    valid: uint32[C] — 1 for live slabs, 0 for padding.

    Applied as a short sequential chain of predicated
    dynamic_update_slice steps (C is a small fixed chunk), NOT one
    scatter: a scatter with duplicate indices — a clamped padding entry
    colliding with a live slab's slot — has undefined write order,
    while the chain is deterministic (later entries win, and padding
    entries rewrite the current content, a no-op). Returns a NEW array;
    callers rely on the identity change as the write-epoch token."""
    s_local = block.shape[0]
    for j in range(slabs.shape[0]):
        li = jnp.clip(idx[j], 0, s_local - 1)
        cur = jax.lax.dynamic_slice_in_dim(block, li, 1, axis=0)
        upd = jnp.where(valid[j] != 0, slabs[j][None], cur)
        block = jax.lax.dynamic_update_slice_in_dim(block, upd, li, axis=0)
    return block


# ---------------------------------------------------------------------------
# Ragged-occupancy slot masking (ISSUE r11 batching plane): the batched
# serving programs in exec/tpu.py pad a group's query slots up to a fixed
# slot-count bucket so a handful of compiled signatures serve any
# occupancy. Padded slots replay slot 0's operands; these helpers zero
# them INSIDE the kernel so an inactive lane can never leak a value into
# any cross-lane reduction, whatever the program does downstream — the
# per-slot query-id scatter on the host is then a pure routing step.
# ---------------------------------------------------------------------------


def mask_lane_slab(slab, active):
    """Zero a padded slot's bitmap slab: uint32[...] & (0 - active) where
    active is a 0/1 uint32 — the two's-complement trick turns the flag
    into an all-ones/all-zeros mask without a select."""
    return slab & (jnp.uint32(0) - active)


def slab_counts(slab):
    """Popcounts of slabs, summed over both word axes:
    uint32[S, ..., L, 128] -> uint32[S, ...]."""
    return jnp.sum(
        jax.lax.population_count(slab), axis=(-2, -1), dtype=jnp.uint32
    )


def masked_lane_counts(slab, active):
    """Per-shard popcounts of one slot's slab with inactive lanes zeroed:
    uint32[S, L, 128], uint32 0/1 -> uint32[S]. The count-batch scan body
    uses this so a padded slot contributes exactly 0 to any reduction."""
    return slab_counts(slab) * active


# ---------------------------------------------------------------------------
# Tiled GroupBy slot programs (ISSUE 17): the N-field group tensor cut
# into fixed-shape slot arrays. A kernel that bakes the row
# combination into its grid makes K a COMPILED dimension (every
# cardinality change a recompile, the whole product tensor shipped in
# one piece; the one-shot sweep PR 17 retired did); these take the
# combination as a traced int32[T, E]
# operand: one compiled signature per (stack shapes, slot bucket)
# serves ANY row combination, so the scheduler in exec/tpu.py can prune
# empty rows, cut the live product into tiles, and launch each tile
# through the same program with zero recompiles. Fused-XLA formulation
# (precedent: pair_stats_xla; on v5e the fused pair sweep measured
# 2.73 ms vs 1.65 ms Pallas then — an acceptable trade for a traced-operand
# program, and on CPU hosts it avoids interpret-mode Pallas entirely,
# which walks the (K, S, W) grid in Python).
# ---------------------------------------------------------------------------

#: Shard-axis chunk for the tile programs' inner reduction scan. The
#: [SB, Rf, Rg, L, 128] popcount broadcast must stay small enough for the
#: backend's vector units to fuse well: measured on the 1-core CPU host
#: at the bench shape, SB=6 sweeps in 2.8 s where SB=12 falls off a
#: vectorization cliff to 37 s. Shard counts that don't divide evenly
#: finish with one static remainder chunk.
GROUP_TILE_SHARD_CHUNK = 6


def _tile_chunk_counts(fm, g_stack, pershard: bool):
    """Shard-chunked AND+popcount reduction of one slot's masked f
    against g: [Rf, Rg] totals, or [S, Rf, Rg] per-shard. The reduction
    keeps vector-shaped outputs at every step (sum the word axis first,
    then shards) — a joint multi-axis reduce lowers catastrophically on
    XLA CPU."""
    s, rf = fm.shape[:2]
    rg = g_stack.shape[1]
    sb = min(s, GROUP_TILE_SHARD_CHUNK)

    def pc_block(fc, gc):
        pc = jax.lax.population_count(
            fc[:, :, None] & gc[:, None]
        ).astype(jnp.int32)
        return jnp.sum(pc, axis=(3, 4))  # [sb, Rf, Rg]

    n_chunks = s // sb
    if pershard:
        def chunk(carry, i):
            fc = jax.lax.dynamic_slice_in_dim(fm, i * sb, sb, 0)
            gc = jax.lax.dynamic_slice_in_dim(g_stack, i * sb, sb, 0)
            return carry, pc_block(fc, gc)

        _, per = jax.lax.scan(chunk, None, jnp.arange(n_chunks))
        per = per.reshape(n_chunks * sb, rf, rg)
        if s % sb:
            per = jnp.concatenate(
                [per, pc_block(fm[n_chunks * sb :], g_stack[n_chunks * sb :])]
            )
        return per

    def chunk(acc, i):
        fc = jax.lax.dynamic_slice_in_dim(fm, i * sb, sb, 0)
        gc = jax.lax.dynamic_slice_in_dim(g_stack, i * sb, sb, 0)
        return acc + jnp.sum(pc_block(fc, gc), axis=0), None

    acc, _ = jax.lax.scan(chunk, jnp.zeros((rf, rg), jnp.int32), jnp.arange(n_chunks))
    if s % sb:
        acc = acc + jnp.sum(
            pc_block(fm[n_chunks * sb :], g_stack[n_chunks * sb :]), axis=0
        )
    return acc


def _group_tile(f_stack, g_stack, extras, rows_idx, active, filt, pershard):
    """Shared body of the tile programs: lax.scan over the slot axis, so
    T appears only as a scan length (one compiled signature per slot
    bucket) and every slot re-reads the stacks exactly once, whatever
    the number of live combinations."""

    def slot(carry, xs):
        idx, act = xs
        m = None
        for t, h in enumerate(extras):
            row = jax.lax.dynamic_index_in_dim(h, idx[t], axis=1, keepdims=False)
            m = row if m is None else (m & row)  # [S, L, 128]
        if filt is not None:
            m = m & filt
        # Padded slots replay slot 0's rows; the lane mask zeroes their
        # slab so they contribute exactly 0 to every cell.
        m = mask_lane_slab(m, act)
        fm = f_stack & m[:, None]
        return carry, _tile_chunk_counts(fm, g_stack, pershard)

    _, out = jax.lax.scan(slot, None, (rows_idx, active))
    return out


def group_tile_stats(f_stack, g_stack, extras, rows_idx, active, filt=None):
    """One tile of the N-field group tensor, slot-indexed:

    (uint32[S, Rf, L, 128], uint32[S, Rg, L, 128],
    (uint32[S, Rh1, L, 128], ...), int32[T, E], uint32[T]
    [, uint32[S, L, 128]]) -> int32[T, Rf, Rg] with
    out[q, a, b] = popcount(F_a & G_b & H1_{rows_idx[q,0]} & ... [& filt])
    for active[q] == 1, exactly 0 for padded slots.

    Slot q must agree bit-for-bit with pair_stats of the stack
    pre-masked by the slot's rows (differentially tested in
    tests/test_tpu.py TestTriStatsKernel). Accumulator bound: same
    MAX_PAIR_SHARDS int32 argument as pair_stats."""
    return _group_tile(f_stack, g_stack, extras, rows_idx, active, filt, False)


def group_tile_stats_pershard(f_stack, g_stack, extras, rows_idx, active):
    """group_tile_stats WITHOUT the shard reduction:
    -> int32[T, S, Rf, Rg]. Unfiltered by design — the per-shard table
    exists to absorb write churn for the UNFILTERED group tensor (a
    filter changes per query, so its sweeps are not maintainable). It
    is what lets N>=3 GroupBy absorb writes on the host (exec/tiers.py
    refresh_entry): totals are its int64 sum over shards, and a write
    epoch that dirtied D shards replaces D rows, from host slabs that
    must agree with it (_host_slab_groupn), instead of re-sweeping the
    stacks — the same design as pair_stats_pershard for two fields."""
    return _group_tile(f_stack, g_stack, extras, rows_idx, active, None, True)


# -- GroupBy with a Sum aggregate -------------------------------------------

#: Combinations a group_sum_counts step ANDs and counts at once.
GROUP_SUM_CHUNK = 16


def group_sum_masks(filt, bsi_block, depth: int, signed: bool):
    """The count masks of a grouped Sum, uint32[S, P, L, 128]: the filter
    (a group's count), the filter's columns that hold a value, each
    magnitude plane under those, and, where the field can hold negative
    values, each plane under those whose sign bit is set. `filt` None is
    every column."""
    exists, sign = bsi_block[:, 0], bsi_block[:, 1]
    count = jnp.full_like(exists, 0xFFFFFFFF) if filt is None else filt
    held = exists if filt is None else exists & filt
    planes = bsi_block[:, 2:2 + depth]
    s, _, lines, lanes = bsi_block.shape
    # Each group written in place into the masks' one buffer: the
    # compiler kept the signed planes' two groups as temporaries beside
    # a concatenation of them.
    masks = jnp.zeros((s, 2 + depth * (2 if signed else 1), lines, lanes),
                      jnp.uint32)
    masks = masks.at[:, 0].set(count).at[:, 1].set(held)
    masks = masks.at[:, 2:2 + depth].set(planes & held[:, None])
    if signed:
        masks = masks.at[:, 2 + depth:].set(planes & (held & sign)[:, None])
    return masks


#: Word lines of a sub-tile of the combination sweep: a combination's
#: AND is two vregs, its popcounts against a mask tile are summed into
#: one (8, 128) accumulator. On a v5e at SSB Q4.3's shape (19-50 masks)
#: 8 lines ran up to 29 % longer a step, 32 up to 11 %.
_COMBO_SUB_LINES = 16
#: VMEM for the combination sweep's double-buffered row and mask blocks
#: (blocks of 128 word lines at Q4.3's shape). Half of it ran 5-19 %
#: longer a step on a v5e, twice it within 1 %.
_COMBO_BLOCK_BYTES = 16 * 1024 * 1024


def _combo_tiles(n: int, masks: int, lines: int, lanes: int):
    """(word lines a block, lines a sub-tile, VMEM limit) of the
    combination sweep over n fields and `masks` masks."""
    chunk = GROUP_SUM_CHUNK
    sub = min(lines, _COMBO_SUB_LINES)
    lt = lines
    while 2 * (chunk * n + masks) * lt * lanes * 4 > _COMBO_BLOCK_BYTES \
            and lt > sub:
        lt //= 2
    blocks = 2 * (chunk * n + masks) * lt * lanes * 4
    acc = masks * chunk * _MIN_LINES * lanes * 4
    return lt, sub, blocks + acc + (8 << 20)


def _combo_sweep_kernel(n, sub, ids_ref, act_ref, *refs):
    """A block of the combination sweep: grid (step, shard, word-line
    block). Each step's combinations' rows of the block are copied from
    the stacks, which stay in HBM, into a double buffer a block ahead;
    a combination's n row tiles are ANDed in VMEM and counted against
    each mask tile in turn, a sub-tile of word lines at a time. The
    counts stay vreg-shaped in `acc_ref` until the step's last block, so
    their sublanes and lanes are summed once a step."""
    stacks, (masks_ref, out_ref) = refs[:n], refs[n:n + 2]
    bufs, (acc_ref, sem) = refs[n + 2:2 * n + 2], refs[2 * n + 2:]
    p, chunk, _, lanes = acc_ref.shape
    lt = masks_ref.shape[2]
    c, s, w = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_s, n_w = pl.num_programs(1), pl.num_programs(2)
    at_block = (c * n_s + s) * n_w + w

    def copies(block, slot, start: bool):
        """Start, or wait for, the copies of a block's rows."""
        step = block // (n_s * n_w)
        sh, wb = block // n_w % n_s, block % n_w
        for j in range(n):
            @pl.loop(0, chunk)
            def _(k):
                cp = pltpu.make_async_copy(
                    stacks[j].at[sh, ids_ref[(step * chunk + k) * n + j],
                                 pl.ds(wb * lt, lt)],
                    bufs[j].at[slot, k], sem.at[slot],
                )
                cp.start() if start else cp.wait()

    slot = at_block % 2

    @pl.when(at_block == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        copies(at_block, slot, True)

    @pl.when(at_block + 1 < pl.num_programs(0) * n_s * n_w)
    def _():
        copies(at_block + 1, 1 - slot, True)

    @pl.when(jnp.logical_and(s == 0, w == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    copies(at_block, slot, False)

    @pl.loop(0, lt // sub)
    def _(q):
        lines = pl.ds(pl.multiple_of(q * sub, sub), sub)
        combos = []
        for k in range(chunk):
            m = bufs[0][slot, k, lines, :]  # [sub, 128]
            for j in range(1, n):
                m = m & bufs[j][slot, k, lines, :]
            # A padded slot replays slot 0's rows: its flag zeroes them.
            combos.append(mask_lane_slab(m, act_ref[c * chunk + k]))
        combos = jnp.stack(combos)  # [C, sub, 128]
        # A mask tile at a time against every combination, never the
        # whole face in VMEM (its [C, P, sub, 128] form ran 1.2-2.2 times
        # as long).
        for i in range(p):
            pc = jax.lax.population_count(combos & masks_ref[0, i, lines, :])
            acc_ref[i] += pc.astype(jnp.int32).reshape(
                chunk, sub // _MIN_LINES, _MIN_LINES, lanes
            ).sum(axis=1)

    @pl.when(jnp.logical_and(s == n_s - 1, w == n_w - 1))
    def _():
        out_ref[pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :] = (
            _word_counts(jnp.swapaxes(acc_ref[...], 0, 1))
        )


def group_sum_counts(stacks, masks, combos, active, n_chunks,
                     interpret: bool = False):
    """Popcounts of row combinations against the count masks:

    ((uint32[S, R_i, L, 128], ...), uint32[S, P, L, 128], int32[T, n] row
    ids, uint32[T], int32 scalar) -> int32[T, P] with
    out[t, p] = popcount(AND_i stacks[i][:, combos[t, i]] & masks[:, p])
    for active[t] == 1, and 0 for every other slot.

    One Pallas sweep whose grid is (step, shard, word-line block): only
    the first `n_chunks` (at least 1) steps of GROUP_SUM_CHUNK
    combinations run, so one compiled program serves any number of live
    combinations up to T. The row ids and flags are prefetched as
    scalars; a step's combinations' row tiles are copied from the
    resident stacks by id, ANDed in VMEM and counted against every mask
    tile, the [combinations, masks] face accumulated as pair_stats does
    its [Rf, Rg]. No combination's row is written to HBM (ANDed into a
    uint32[S, C, L, 128] temporary and read back, they took a fifth of
    ssb-flights-1chip's device time: PERF.md). Each stack is one operand
    the kernel copies from, not a block a row: interpret mode copies
    every operand whole, and C x n of them held 34 GB on the test mesh.
    Accumulator bound: MAX_PAIR_SHARDS, as pair_stats."""
    t_slots, n = combos.shape
    s, p, lines, lanes = masks.shape
    chunk = GROUP_SUM_CHUNK
    lt, sub, vmem = _combo_tiles(n, p, lines, lanes)
    return pl.pallas_call(
        functools.partial(_combo_sweep_kernel, n, sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_chunks, s, lines // lt),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n + [
                pl.BlockSpec((1, p, lt, lanes), lambda c, i, w, *_: (i, 0, w, 0))
            ],
            out_specs=pl.BlockSpec((t_slots, p), lambda *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((2, chunk, lt, lanes), jnp.uint32)] * n
            + [pltpu.VMEM((p, chunk, _MIN_LINES, lanes), jnp.int32),
               pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((t_slots, p), jnp.int32),
        compiler_params=_sequential_grid(3, vmem_limit_bytes=vmem),
        interpret=interpret,
    )(combos.reshape(t_slots * n), active, *stacks, masks)


def pair_stats_xla(f_stack, g_stack):
    """Fused-XLA reference formulation of pair_stats (same results; used
    as the differential oracle for the Pallas kernel and as the fallback
    where Pallas/Mosaic is unavailable)."""
    pc = jax.lax.population_count(
        f_stack[:, :, None] & g_stack[:, None]
    ).astype(jnp.int32)
    pair = jnp.sum(pc, axis=(0, 3, 4))
    cf = jnp.sum(
        jax.lax.population_count(f_stack).astype(jnp.int32), axis=(0, 2, 3)
    )
    cg = jnp.sum(
        jax.lax.population_count(g_stack).astype(jnp.int32), axis=(0, 2, 3)
    )
    return pair, cf, cg


# -- Tanimoto TopN over a packed stack (ISSUE 36) ---------------------------

#: Rows a long list holds. More hits than this, or a long leg beyond the
#: launch's long slots, and the leg is finished from its whole count
#: vector (tanimoto_counts), never cut.
TANIMOTO_LIST = 4096
#: Rows every leg's short list holds (ISSUE 37): 92.8 % of the chem
#: cell's legs have no more hits than this (PERF.md §4).
TANIMOTO_SHORT = 512
#: Long lists a launch holds, for its legs with the most hits past the
#: short list (at most the launch's slots).
TANIMOTO_LONG_SLOTS = 4


def tanimoto_counts(packed, row_counts, ids, thresholds, active):
    """int32[B, R]: the whole masked count vector of each leg (what
    tanimoto_topn lists, and what finishes a leg whose hits outran its
    list): per leg b and row r, |A_b ∩ r| summed over the shards
    in which the pair passes the reference's integer test
    (core/fragment.py top: c > 0 and c * 100 // (|A| + |r| - c) >= T,
    which for whole numbers is c * 100 >= T * union), else 0.

    packed uint32[S, R, W]; row_counts int32[S, R]; ids, thresholds,
    active int32[B]. The AND, the popcount and the sum over a row's words
    are one fusion: [B, R, W] is never written."""
    src = packed[:, ids, :]  # [S, B, W]
    inter = jnp.sum(
        jax.lax.population_count(packed[:, None, :, :] & src[:, :, None, :]),
        axis=-1, dtype=jnp.int32,
    )  # [S, B, R]
    src_n = row_counts[:, ids]  # [S, B]
    union = row_counts[:, None, :] + src_n[:, :, None] - inter
    ok = (inter > 0) & (
        inter * 100 >= thresholds[None, :, None] * union
    ) & (active[None, :, None] > 0)
    return jnp.sum(jnp.where(ok, inter, 0), axis=0, dtype=jnp.int32)


def _pack_flags(flags):
    """bool[B, N] -> uint32[B, ceil(N / 32)]: flag i is bit i % 32 of word
    i // 32 (N padded with unset flags to whole words)."""
    b, n = flags.shape
    flags = jnp.pad(flags, ((0, 0), (0, -n % 32)))
    lane = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(
        flags.reshape(b, -1, 32).astype(jnp.uint32) << lane,
        axis=-1, dtype=jnp.uint32,
    )


#: Words a block of `_first_set_bits` holds.
_SELECT_BLOCK = 64


def _first_set_bits(words, k: int):
    """uint32[B, N] -> (int32[B, k], int32[B]): the positions (word * 32 +
    bit) of a row's first k set bits in order, and how many it has in
    all; entries past that number are filler.

    Bit j is found by comparing j with running counts, never by a loop
    of gathers, which is what a binary search over a prefix sum is on
    this device (16 rounds: PR 35 read 6 ms of a launch's 14 there), and
    in two steps of 64, never against all N words at once (k * N compares
    a row: twelve such fusions of [16, 4096, 4096] were 12 ms of a
    launch's 19.5 on the chip, PR 36): first the block of 64 words whose
    running count passes j, then the word within that block's own 64
    running counts, then the bit within the word's 32."""
    b, n = words.shape
    nb = -(-n // _SELECT_BLOCK)
    words = jnp.pad(words, ((0, 0), (0, nb * _SELECT_BLOCK - n)))
    lane = jnp.arange(32, dtype=jnp.uint32)
    ones = jax.lax.population_count(words).astype(jnp.int32)
    in_block = jnp.cumsum(ones.reshape(b, nb, _SELECT_BLOCK), axis=2)  # inclusive
    block_upto = jnp.cumsum(in_block[:, :, -1], axis=1)  # [B, nb], inclusive
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    block = jnp.minimum(
        jnp.sum(block_upto[:, None, :] <= j[:, :, None], axis=2, dtype=jnp.int32),
        nb - 1,
    )  # [B, k]
    before_block = jnp.where(
        block > 0,
        jnp.take_along_axis(block_upto, jnp.maximum(block - 1, 0), axis=1), 0,
    )
    j_in = j - before_block
    counts = jnp.take_along_axis(in_block, block[:, :, None], axis=1)  # [B, k, 64]
    wholly = counts <= j_in[:, :, None]  # words of the block wholly before bit j
    word_in = jnp.minimum(
        jnp.sum(wholly, axis=2, dtype=jnp.int32), _SELECT_BLOCK - 1
    )
    # The running count is non-decreasing: the largest one not past j is
    # the count before bit j's word.
    rank = j_in - jnp.max(jnp.where(wholly, counts, 0), axis=2)
    word_at = block * _SELECT_BLOCK + word_in
    word = jnp.take_along_axis(words, word_at, axis=1)
    bits = ((word[:, :, None] >> lane) & 1).astype(jnp.int32)  # [B, k, 32]
    bit_at = jnp.minimum(
        jnp.sum(jnp.cumsum(bits, axis=2) <= rank[:, :, None], axis=2,
                dtype=jnp.int32),
        31,
    )
    return jnp.minimum(word_at, n - 1) * 32 + bit_at, block_upto[:, -1]


def _levels(hit):
    """bool[B, R] -> the flags packed 32 to a word three times over
    (1.7 M rows: 53,152 words, 1,661, 52): a word of a level is non-empty
    where the flag of the level above is set."""
    level0 = _pack_flags(hit)
    level1 = _pack_flags(level0 != 0)
    return level0, level1, _pack_flags(level1 != 0)


def _compact(levels, k: int):
    """`_levels` of some legs -> int32[B, k]: each leg's first k set rows
    by id (filler past its number of hits).

    The hits are few among millions, so the set bits are taken from the
    top down: the non-empty words of a level are the set bits of the
    level above. A leg with n <= k hits has at most n non-empty words at
    every level and loses none; the work after the sweep is a function
    of k, not of the field's height."""
    level0, level1, level2 = levels

    def below(level, at, count, k_out):
        """The words of `level` at `at` (none past `count`), and the
        first k_out set bits among them as positions in `level`."""
        live = jnp.arange(at.shape[1], dtype=jnp.int32)[None, :] < count[:, None]
        words = jnp.where(
            live,
            jnp.take_along_axis(level, jnp.minimum(at, level.shape[1] - 1), axis=1),
            jnp.uint32(0),
        )
        local, n = _first_set_bits(words, k_out)
        return jnp.take_along_axis(at, local // 32, axis=1) * 32 + local % 32, n

    # Level 1 has at most this many words, whatever k.
    k1 = min(k, level1.shape[1])
    at1, n1 = _first_set_bits(level2, k1)   # non-empty words of level 1
    at0, n0 = below(level1, at1, n1, k)     # non-empty words of level 0
    rows, _ = below(level0, at0, n0, k)     # set rows
    return rows


def tanimoto_topn(packed, row_counts, ids, thresholds, active,
                  short: int = TANIMOTO_SHORT, k: int = TANIMOTO_LIST,
                  long_slots: int = TANIMOTO_LONG_SLOTS):
    """(int32[B, 1 + 2 short], int32[L, 1 + 2k]), L = min(long_slots, B).

    Per leg: the number of rows that pass, then the first `short` of
    them by row id, then their counts (0 beyond the hits). Per long slot:
    the leg it holds (-1 for none), then that leg's first k rows and
    their counts; the slots go to the legs with the most hits past
    `short` that k holds. A leg whose hits outran its short list and got
    no slot holds only a part of its answer here.

    The list's size follows the leg's hits (ISSUE 37): the selection's
    gathers are [B, short] and [L, k], where a list of k for every leg
    made them [B, k] (fourteen of 65,536 elements a 16-slot launch, 8.8
    ms of its 17.4 on the chip, PR 36)."""
    cnt = tanimoto_counts(packed, row_counts, ids, thresholds, active)
    levels = _levels(cnt > 0)
    total = jnp.sum(jax.lax.population_count(levels[0]), axis=1, dtype=jnp.int32)

    def listed(legs, of_legs, n, size):
        """[len(legs), 2 size]: the legs' first `size` rows, from their
        levels `of_legs`, and the rows' counts."""
        rows = _compact(of_legs, size)
        live = jnp.arange(size, dtype=jnp.int32)[None, :] < n[:, None]
        rows = jnp.where(live, jnp.minimum(rows, cnt.shape[1] - 1), 0)
        counts = jnp.where(live, cnt[legs[:, None], rows], 0)
        return jnp.concatenate([rows, counts], axis=1)

    every = jnp.arange(total.shape[0], dtype=jnp.int32)
    fits = (total > short) & (total <= k)
    n_long, legs = jax.lax.top_k(
        jnp.where(fits, total, 0), min(long_slots, total.shape[0])
    )
    legs = legs.astype(jnp.int32)
    # Most launches of a few legs, and about 3 in 10 of sixteen, have no
    # long leg: the long descent runs only where one has (on the chip a
    # 16-leg launch 13.6 -> 13.0 ms, a 4-leg one 4.9 -> 3.2: PR 37).
    long_lists = jax.lax.cond(
        n_long[0] > 0,
        lambda: listed(legs, tuple(lv[legs] for lv in levels), n_long, k),
        lambda: jnp.zeros((legs.shape[0], 2 * k), jnp.int32),
    )
    return (
        jnp.concatenate(
            [total[:, None], listed(every, levels, total, short)], axis=1
        ),
        jnp.concatenate(
            [jnp.where(n_long > 0, legs, -1)[:, None], long_lists], axis=1
        ),
    )


def packed_row_counts(packed):
    """int32[S, R]: bits a row holds in each shard of a packed stack."""
    return jnp.sum(jax.lax.population_count(packed), axis=-1, dtype=jnp.int32)


# -- the row-id layout (ops/blocks.py rowid_shape) --------------------------


def rowid_words(match):
    """bool[..., 32, 128] -> uint32[..., 128]: bit b of a lane's word is
    the match of sublane b, the dense layout's word of the same 32
    columns (distinct powers of two: their sum is their OR)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)[:, None]
    return jnp.sum(match.astype(jnp.uint32) << shifts, axis=-2,
                   dtype=jnp.uint32)


#: A row a row-id leaf asks for that matches no column (a row past the
#: field's): no id type holds it.
ROWID_NO_ROW = 0xFFFFFFFF


def _rowid_match(ids, rows):
    """ids == rows in the ids' own type (a widened copy of the ids would
    be a program's largest temporary), with a row the type cannot hold,
    ROWID_NO_ROW among them, matching nothing (the type's largest value
    marks a column no row holds)."""
    none = jnp.iinfo(ids.dtype).max
    return (ids == rows.astype(ids.dtype)) & (rows < none)


def rowid_row(ids, rows):
    """The columns of a row-id stack whose id is one of `rows`, as a
    dense slab uint32[S, L, 128]: Row(f=r), or a Union of Rows of one
    field, in one pass over the ids. `rows` uint32[k]; ROWID_NO_ROW
    matches no column."""
    match = _rowid_match(ids, rows[0])
    for j in range(1, rows.shape[0]):
        match = match | _rowid_match(ids, rows[j])
    return rowid_words(match)


def rowid_rows(ids, rows):
    """Rows of a row-id stack as a dense stack uint32[S, k, L, 128], row
    j the columns whose id is rows[j] (ROWID_NO_ROW: an empty row): the
    operand a dense program (group_sum_counts) reads by row id."""
    return rowid_words(
        _rowid_match(ids[:, None], rows[None, :, None, None, None])
    )


def rowid_hist(ids, filt, hi0, lo_bits: int, hi_rows: int,
               interpret: bool = False):
    """int32[hi_rows, 2^lo_bits]: out[h, l] counts the columns under
    `filt` (None: every column) whose id is ((hi0 + h) << lo_bits) | l.

    An id is split in two: the columns of each of the hi_rows high parts
    under the filter, and those of each low part, are built as two small
    dense stacks and counted against each other by the pair sweep
    (pair_stats): a column falls in exactly one (high, low) pair, its
    id's. A column no row holds (the id type's largest value) is under
    no filter. Temporaries: hi_rows + 2^lo_bits dense rows."""
    # In the ids' own type throughout: a widened copy of the ids would
    # be the program's largest temporary.
    none = jnp.iinfo(ids.dtype).max
    under = rowid_words(ids != none)
    if filt is not None:
        under = under & filt
    lo_n = 1 << lo_bits
    highs = (hi0 + jnp.arange(hi_rows, dtype=jnp.int32)).astype(ids.dtype)
    hi_stack = rowid_words(
        (ids >> lo_bits)[:, None] == highs[None, :, None, None, None]
    ) & under[:, None]
    lows = jnp.arange(lo_n, dtype=ids.dtype)
    lo_stack = rowid_words(
        (ids & (lo_n - 1))[:, None] == lows[None, :, None, None, None]
    )
    return pair_stats(hi_stack, lo_stack, interpret=interpret)[0]
