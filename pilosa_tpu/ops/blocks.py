"""Dense HBM block layout + device block cache.

Layout: one fragment (view ∩ shard) becomes uint32[rows_padded, WORDS]
where WORDS = SHARD_WIDTH/32 (32768 for the default 2^20 shard width, i.e.
128 KiB per row). uint32 is the TPU-native word (int64 is emulated on
TPU); rows are padded to a multiple of 8.

The host packs and unpacks rows flat, [..., WORDS]. On the device the word
axis is split in two, [..., WORD_LINES, WORD_LANES] (256 x 128 at the
default width): the TPU tiles an array's two minor axes in (8, 128), and
with the words alone on those axes a row of a shard is WORD_LINES/8 whole
tiles, 128 KiB contiguous, which a program reads in place. With the flat
[shards, rows, WORDS] shape the ROW axis was the tile's sublane axis: every
4 KiB tile held 512 B of each of 8 rows, and a program that wanted one row
first copied it out of the whole stack (ISSUE 27). The two shapes are the
same bytes in the same order, so tile_words / flat_words are views.

A second layout, "packed", is for a set field that is narrow, every bit
in a shard's first 4,096 columns (ISSUE 36: 1.7 M such rows, tall as
Pilosa's fields are). Dense it is rows x 128 KiB; packed it is
uint32[shards, rows_padded, PACKED_WORDS]: the 128 words in use on the
lane axis, rows on the sublane axis, so a program sweeps every row of the
field in one pass (pack_fragment_packed, pack_rows_packed).

A third layout, "row ids", is for a mutex field where the index's dense
stacks would not fit the block store's budget: one row id a column,
uint8 up to 255 rows and uint16 up to 65,535, [shards, WORD_LINES, 32,
WORD_LANES] so that the ids of a dense word's 32 columns lie on the
sublane axis under its lane (pack_fragment_rowid; ops/kernels.py
rowid_row, rowid_rows, rowid_hist). A row of it reads the whole field's
ids, 2 MiB a shard at uint16 where a dense row reads 128 KiB.

Packing walks roaring containers directly: a container key maps to
(row, word-range) and its 1024 uint64 words view as 2048 little-endian
uint32 words, so dense containers are a straight memcpy and array
containers scatter only their set bits.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from pilosa_tpu.shardwidth import SHARD_WIDTH

WORDS_PER_SHARD = SHARD_WIDTH // 32
_CONTAINERS_PER_ROW = SHARD_WIDTH >> 16
_WORDS_PER_CONTAINER = (1 << 16) // 32  # 2048

ROW_PAD = 8

#: The word axis as the device holds it (see the module docstring). A shard
#: row is at least one 2^16-bit container, 2048 words, so the lanes divide it.
WORD_LANES = 128
WORD_LINES = WORDS_PER_SHARD // WORD_LANES


def stack_shape(n_shards: int, n_rows: int) -> tuple[int, int, int, int]:
    """Device shape of a stack of n_shards x n_rows shard rows."""
    return (n_shards, n_rows, WORD_LINES, WORD_LANES)


def tile_words(words):
    """[..., W] -> [..., W/128, 128]: flat rows as the device holds them
    (a view of a contiguous host array)."""
    return words.reshape(words.shape[:-1] + (-1, WORD_LANES))


def flat_words(words):
    """[..., W/128, 128] -> [..., W]: device rows as the host's unpackers
    take them."""
    return words.reshape(words.shape[:-2] + (-1,))


def _padded_rows(n_rows: int) -> int:
    return max(((n_rows + ROW_PAD - 1) // ROW_PAD) * ROW_PAD, ROW_PAD)


def _scatter_container(row_words: np.ndarray, cidx: int, c) -> None:
    """OR one roaring container into a row's word vector at container
    slot cidx (dense containers memcpy; array containers scatter bits —
    via the native C++ loop when available, np.bitwise_or.at otherwise)."""
    base = cidx * _WORDS_PER_CONTAINER
    if c.typ == "bitmap":
        row_words[base : base + _WORDS_PER_CONTAINER] = c.data.view("<u4")
        return
    if c.typ == "run":
        # RLE containers pack via their materialized bitmap words (run
        # fills would need per-run partial-word masking for no gain —
        # packing is once per write epoch).
        row_words[base : base + _WORDS_PER_CONTAINER] = c.bitmap_words().view("<u4")
        return
    from pilosa_tpu.native import scatter_positions

    data = np.ascontiguousarray(c.data, dtype=np.uint16)
    if row_words.flags.c_contiguous and scatter_positions(row_words, base, data):
        return
    pos = data.astype(np.uint32)
    np.bitwise_or.at(
        row_words,
        base + (pos >> 5),
        np.uint32(1) << (pos & np.uint32(31)),
    )


def pack_fragment(frag, n_rows: Optional[int] = None) -> np.ndarray:
    """Flatten a fragment's roaring storage into uint32[rows_p, WORDS].

    n_rows: minimum logical row count (pad target); defaults to
    frag.max_row_id + 1.
    """
    storage = frag.storage
    if n_rows is None:
        n_rows = frag.max_row_id + 1
    rows_p = _padded_rows(n_rows)
    arr = np.zeros((rows_p, WORDS_PER_SHARD), dtype=np.uint32)
    for key in storage.keys():
        c = storage.container(key)
        if c is None or c.n == 0:
            continue
        row = key // _CONTAINERS_PER_ROW
        if row >= rows_p:
            continue  # caller asked for fewer rows than stored
        _scatter_container(arr[row], key % _CONTAINERS_PER_ROW, c)
    return arr


def fragment_tier_words(frag, n_rows: int) -> tuple[int, int]:
    """(array_words, run_words): how many of this fragment's resident
    device words trace back to array / run roaring containers — the
    representation-tier attribution behind the HBM ledger (ISSUE r8,
    after the Chambi/Lemire observation that the container mix is the
    dominant cost driver). Each container owns a fixed
    _WORDS_PER_CONTAINER span of the dense device slab; everything else
    (bitmap containers, empty space) counts as the dense tier. O(keys)
    — negligible next to the pack it attributes."""
    array_w = run_w = 0
    storage = frag.storage
    for key in storage.keys():
        c = storage.container(key)
        if c is None or c.n == 0:
            continue
        if key // _CONTAINERS_PER_ROW >= n_rows:
            continue
        if c.typ == "array":
            array_w += _WORDS_PER_CONTAINER
        elif c.typ == "run":
            run_w += _WORDS_PER_CONTAINER
    return array_w, run_w


def unpack_row(words: np.ndarray) -> np.ndarray:
    """uint32[WORDS] -> sorted shard-relative column positions."""
    bits = np.unpackbits(words.view(np.uint8), bitorder="little")
    return np.nonzero(bits)[0].astype(np.uint64)


#: Transient bit-buffer bound for unpack_slab_columns: unpackbits
#: materializes one byte per bit (8x the packed slab), so the slab is
#: processed in row blocks whose bit buffer stays under this — the
#: per-block pass is still fully vectorized, but a dense query over a
#: large resident stack can no longer allocate a GB-scale temporary
#: (code review r14; the old per-shard loop peaked at one row).
MAX_UNPACK_BITS_BYTES = 32 << 20


def unpack_slab_columns(host: np.ndarray, bases: np.ndarray) -> np.ndarray:
    """uint32[R, W] result slab + uint64[R] per-row column bases ->
    ONE sorted absolute-column uint64 array (ISSUE r14 tentpole 1).

    The whole-slab pass replaces R per-shard unpack_row calls + R
    Bitmap constructions + R Row merges with one (blocked) unpackbits,
    one flatnonzero, and one vectorized base add — the word-level bulk
    decode move from the Roaring reference library applied to device
    readback. Requires bases strictly ascending with row order and
    spaced at least one shard apart (callers sort + dedupe rows by
    shard); output is then globally sorted, ready for
    Row.from_columns."""
    host = np.ascontiguousarray(host, dtype=np.uint32)
    r_n, w = host.shape
    span = w * 32
    bases = np.asarray(bases, dtype=np.uint64)
    rows_per_block = max(1, MAX_UNPACK_BITS_BYTES // max(span, 1))
    parts = []
    for start in range(0, r_n, rows_per_block):
        block = host[start : start + rows_per_block]
        bits = np.unpackbits(
            block.view(np.uint8).reshape(-1), bitorder="little"
        )
        idx = np.flatnonzero(bits)
        if idx.size == 0:
            continue
        rows = idx // span
        pos = (idx - rows * span).astype(np.uint64)
        parts.append(bases[start + rows] + pos)
    if not parts:
        return np.empty(0, dtype=np.uint64)
    return parts[0] if len(parts) == 1 else np.concatenate(parts)


def pack_row(frag, row_id: int) -> np.ndarray:
    """One row of a fragment as uint32[WORDS] (the row-paging unit: a
    stack too tall for the HBM budget is served row-by-row instead of
    falling back to the CPU oracle — SURVEY.md §7 hard part (c))."""
    return pack_rows(frag, row_id, row_id + 1)[0]


def pack_rows(frag, row_start: int, row_end: int) -> np.ndarray:
    """Rows [row_start, row_end) as uint32[row_end-row_start, WORDS] —
    one page of a fragment too tall to be fully HBM-resident. Walks only
    the container-key range of the requested rows (keys are sorted)."""
    import bisect

    storage = frag.storage
    arr = np.zeros((row_end - row_start, WORDS_PER_SHARD), dtype=np.uint32)
    ks = storage.keys()
    lo = bisect.bisect_left(ks, row_start * _CONTAINERS_PER_ROW)
    hi = bisect.bisect_left(ks, row_end * _CONTAINERS_PER_ROW)
    for key in ks[lo:hi]:
        c = storage.container(key)
        if c is None or c.n == 0:
            continue
        _scatter_container(
            arr[key // _CONTAINERS_PER_ROW - row_start],
            key % _CONTAINERS_PER_ROW,
            c,
        )
    return arr


# -- the packed layout: a tall field's first PACKED_BITS columns ----------

#: Columns a packed row holds: one lane line of words.
PACKED_WORDS = 128
PACKED_BITS = PACKED_WORDS * 32

#: Rows of a packed stack are padded to this: a program packs a row mask
#: 32 rows to a word and wants whole lane lines of those words.
PACKED_ROW_PAD = 1024

#: Containers whose positions are gathered and packed in one numpy pass.
_PACK_CHUNK_KEYS = 1 << 16

_NO_POSITIONS = np.empty(0, dtype=np.uint16)


def packed_rows(n_rows: int) -> int:
    return max(-(-n_rows // PACKED_ROW_PAD), 1) * PACKED_ROW_PAD


def _packed_positions(c) -> Optional[np.ndarray]:
    """A container's positions where all lie under PACKED_BITS."""
    if c.typ == "array":
        data = c.data
    elif c.n > PACKED_BITS:
        return None
    else:
        data = c.positions()
    if data.size and int(data[-1]) >= PACKED_BITS:
        return None
    return data


def _or_positions(out: np.ndarray, row_of: np.ndarray, datas: list) -> None:
    """OR the positions of `datas` (each sorted, all under PACKED_BITS)
    into the rows `row_of` of out[rows, PACKED_WORDS]: one pass over the
    concatenated positions, no loop a container."""
    lens = np.fromiter(map(len, datas), dtype=np.int64, count=len(datas))
    if not int(lens.sum()):
        return
    pos = np.concatenate(datas).astype(np.int64)
    flat = np.repeat(row_of.astype(np.int64) * PACKED_BITS, lens) + pos
    word = flat >> 5
    bit = np.uint32(1) << (flat & 31).astype(np.uint32)
    # Rows ascend and a row's positions ascend, so equal words are
    # neighbours: one OR a run.
    starts = np.flatnonzero(np.concatenate(([True], word[1:] != word[:-1])))
    out.reshape(-1)[word[starts]] |= np.bitwise_or.reduceat(bit, starts)


def pack_fragment_packed(frag, rows_p: int) -> Optional[np.ndarray]:
    """A fragment as uint32[rows_p, PACKED_WORDS], or None where a bit
    lies at column PACKED_BITS or beyond (the field is not narrow: the
    caller keeps the dense layout or leaves for the host)."""
    storage = frag.storage
    out = np.zeros((rows_p, PACKED_WORDS), dtype=np.uint32)
    keys = storage.keys()
    for k0 in range(0, len(keys), _PACK_CHUNK_KEYS):
        chunk = np.asarray(keys[k0 : k0 + _PACK_CHUNK_KEYS], dtype=np.int64)
        if (chunk % _CONTAINERS_PER_ROW).any():
            return None  # a container past a row's first 2^16 columns
        rows = chunk // _CONTAINERS_PER_ROW
        datas = []
        for key in keys[k0 : k0 + _PACK_CHUNK_KEYS]:
            c = storage.container(key)
            data = _packed_positions(c) if c is not None else _NO_POSITIONS
            if data is None:
                return None
            datas.append(data)
        keep = rows < rows_p
        if not keep.all():
            datas = [d for d, k in zip(datas, keep.tolist()) if k]
            rows = rows[keep]
        _or_positions(out, rows, datas)
    return out



def pack_rows_packed(frag, row_ids) -> Optional[np.ndarray]:
    """The rows `row_ids` of a fragment as uint32[len, PACKED_WORDS], or
    None where one of them holds a bit at PACKED_BITS or beyond: what a
    point write's splice into a packed stack ships."""
    storage = frag.storage
    out = np.zeros((len(row_ids), PACKED_WORDS), dtype=np.uint32)
    datas = []
    for r in row_ids:
        base = int(r) * _CONTAINERS_PER_ROW
        for k in range(base + 1, base + _CONTAINERS_PER_ROW):
            if storage.container(k) is not None:
                return None
        c = storage.container(base)
        data = _packed_positions(c) if c is not None else _NO_POSITIONS
        if data is None:
            return None
        datas.append(data)
    _or_positions(out, np.arange(len(row_ids)), datas)
    return out


# -- the row-id layout: a mutex field as one row id a column ---------------

#: Columns a word holds: the sublane axis of a row-id stack.
ROWID_WORD_BITS = 32


def rowid_dtype(n_rows: int):
    """The id type of a row-id stack of n_rows rows, or None where ids
    would not fit: uint8 up to 255 rows, uint16 up to 65,535. The type's
    largest value marks a column that no row holds."""
    if n_rows <= np.iinfo(np.uint8).max:
        return np.uint8
    if n_rows <= np.iinfo(np.uint16).max:
        return np.uint16
    return None


def rowid_shape(n_shards: int) -> tuple[int, int, int, int]:
    """Device shape of a row-id stack: [S, WORD_LINES, 32, WORD_LANES],
    element [s, i, b, j] the id of column 32 (128 i + j) + b, so the ids
    of one dense word's 32 columns lie on the sublane axis under that
    word's lane and a compare packs to the dense layout's word in one
    reduce (ops/kernels.py rowid_words)."""
    return (n_shards, WORD_LINES, ROWID_WORD_BITS, WORD_LANES)


def pack_fragment_rowid(frag, dtype) -> Optional[np.ndarray]:
    """A fragment of a mutex field as its row-id slab, `dtype`[WORD_LINES,
    32, WORD_LANES] in device order, or None where a column lies in two
    rows (a mutex field loaded by import_roaring can hold one in two, and
    the layout holds one id a column) or a row id does not fit `dtype`.
    One numpy pass over the concatenated container positions."""
    none = np.iinfo(dtype).max
    ids = np.full(SHARD_WIDTH, none, dtype=dtype)
    storage = frag.storage
    keys = []
    datas = []
    for key in storage.keys():
        c = storage.container(key)
        if c is None or c.n == 0:
            continue
        keys.append(key)
        datas.append(c.positions())
    if keys:
        keys_a = np.asarray(keys, dtype=np.int64)
        rows = keys_a // _CONTAINERS_PER_ROW
        if int(rows[-1]) >= none:
            return None
        lens = np.fromiter(map(len, datas), dtype=np.int64, count=len(datas))
        cols = np.repeat((keys_a % _CONTAINERS_PER_ROW) << 16, lens)
        cols += np.concatenate(datas).astype(np.int64)
        rid = np.repeat(rows, lens).astype(dtype)
        ids[cols] = rid
        # A column set in two rows keeps one of them: the other reads
        # back different.
        if not np.array_equal(ids[cols], rid):
            return None
    return np.ascontiguousarray(
        ids.reshape(WORD_LINES, WORD_LANES, ROWID_WORD_BITS).transpose(0, 2, 1)
    )
