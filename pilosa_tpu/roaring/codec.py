"""Roaring bitmap (de)serialization — byte-compatible with the reference.

Implements the Pilosa roaring file format (reference roaring/roaring.go
writeToUnoptimized at :1054, docs/architecture.md):

  bytes 0-3   cookie = magic 12348 | version<<16 | flags<<24 (LE)
  bytes 4-7   container count (LE u32)
  then per container (12 bytes): key u64, type u16 (1=array,2=bitmap,3=run),
              cardinality-1 u16
  then per container: file offset u32
  then container data: array = N*u16; bitmap = 1024*u64;
              run = count u16 + count*(start u16, last u16) [inclusive]
  then an op log until EOF (reference roaring/roaring.go:4649-4700):
              type u8, value/len u64, fnv32a checksum u32 at [9:13],
              then batch values (8B each) or opN u32 + roaring payload.

Also reads the official RoaringFormatSpec formats (cookies 12346/12347,
reference roaring/unmarshal_binary.go readOfficialHeader at roaring.go:5315).
"""

from __future__ import annotations

import struct
from dataclasses import dataclass
from typing import BinaryIO, Optional

import numpy as np

from pilosa_tpu.native import fnv32a
from pilosa_tpu.roaring.bitmap import (
    ARRAY_MAX_SIZE,
    BITMAP_N,
    TYPE_ARRAY,
    Bitmap,
    Container,
    array_run_count,
)

MAGIC_NUMBER = 12348
STORAGE_VERSION = 0

# Official RoaringFormatSpec cookies (reference roaring/roaring.go).
SERIAL_COOKIE_NO_RUN = 12346
SERIAL_COOKIE = 12347

TYPE_CODE_ARRAY = 1
TYPE_CODE_BITMAP = 2
TYPE_CODE_RUN = 3

OP_ADD = 0
OP_REMOVE = 1
OP_ADD_BATCH = 2
OP_REMOVE_BATCH = 3
OP_ADD_ROARING = 4
OP_REMOVE_ROARING = 5

_MIN_OP_SIZE = 13


class CorruptWalError(ValueError):
    """Op-log corruption BEFORE the tail: a record that fails its
    checksum (or is structurally impossible) while more valid bytes
    follow it. Unlike a torn tail — which a crash mid-append produces
    legitimately and recovery truncates away — mid-log corruption means
    records AFTER the damage would be lost by truncation, so the caller
    must refuse to open the fragment rather than silently drop data
    (ISSUE r8 tentpole 1).

    `offset` is the file offset of the bad record; `reason` is a short
    machine-stable token (checksum | op-type | bounds)."""

    def __init__(self, msg: str, offset: int, reason: str):
        super().__init__(msg)
        self.offset = offset
        self.reason = reason


@dataclass
class ReplayInfo:
    """What a WAL replay actually did — the recovery contract's receipt.

    ops_applied:   op records applied (each batch record is ONE op here;
                   Bitmap.op_n still advances by changed-value counts).
    torn_offset:   file offset of a detected torn FINAL record (the
                   SIGKILL-mid-append shape: truncated, or checksum-
                   failing with nothing after it), or None when the log
                   replayed clean to EOF. The caller truncates the file
                   back to this offset to restore the consistent prefix.
    torn_reason:   short token for the torn detection (truncated |
                   checksum | short-record), "" when torn_offset is None.
    """

    ops_applied: int = 0
    torn_offset: Optional[int] = None
    torn_reason: str = ""


def _encoded_container(c: Container) -> tuple[int, bytes]:
    """Pick the smallest of array/bitmap/run encodings (reference Optimize)."""
    n = c.n
    if c.typ == TYPE_ARRAY and 2 + 4 * array_run_count(c.data) >= 2 * n:
        # An array container keeps its form unless its runs are few:
        # counted before they are built. A fragment of a million small
        # array containers paid the build for each (ISSUE 36: most of a
        # tall field's load and of its snapshots).
        return TYPE_CODE_ARRAY, c.data.astype("<u2", copy=False).tobytes()
    runs = c.runs()
    run_size = 2 + 4 * runs.shape[0]
    array_size = 2 * n
    bitmap_size = 8 * BITMAP_N
    best = min(run_size, array_size, bitmap_size)
    if best == run_size and run_size < array_size and run_size < bitmap_size:
        # runs serialized as [start, last] inclusive (docs/architecture.md)
        body = struct.pack("<H", runs.shape[0]) + runs.astype("<u2").tobytes()
        return TYPE_CODE_RUN, body
    if n <= ARRAY_MAX_SIZE and array_size <= bitmap_size:
        return TYPE_CODE_ARRAY, c.positions().astype("<u2").tobytes()
    return TYPE_CODE_BITMAP, c.bitmap_words().astype("<u8").tobytes()


def serialize(b: Bitmap) -> bytes:
    """Serialize without the op log (callers append ops separately)."""
    entries = []
    for key in b.keys():
        c = b.container(key)
        if c is None or c.n == 0:
            continue
        typ, body = _encoded_container(c)
        entries.append((key, typ, c.n, body))

    header_size = 8
    out = bytearray()
    cookie = MAGIC_NUMBER | (STORAGE_VERSION << 16) | ((b.flags & 0xFF) << 24)
    out += struct.pack("<II", cookie, len(entries))
    for key, typ, n, _ in entries:
        out += struct.pack("<QHH", key, typ, n - 1)
    offset = header_size + len(entries) * 12 + len(entries) * 4
    for _, _, _, body in entries:
        out += struct.pack("<I", offset & 0xFFFFFFFF)
        offset += len(body)
    for _, _, _, body in entries:
        out += body
    return bytes(out)


def serialized_size(b: Bitmap) -> int:
    return len(serialize(b))


def deserialize(data: bytes, b: Optional[Bitmap] = None,
                info: Optional[ReplayInfo] = None) -> Bitmap:
    """Parse either Pilosa or official roaring format, applying any op log.

    `info` (fragment recovery only) opts the op-log replay into the
    torn-tail contract documented on apply_ops and receives the replay
    receipt; without it any damage raises, as wire payloads require."""
    if b is None:
        b = Bitmap()
    if len(data) == 0:
        return b
    if len(data) < 8:
        raise ValueError(f"data too small: {len(data)} bytes")
    file_magic = struct.unpack_from("<H", data, 0)[0]
    try:
        if file_magic == MAGIC_NUMBER:
            return _deserialize_pilosa(data, b, info)
        return _deserialize_official(data, b)
    except struct.error as e:
        # Truncated inputs surface as the module's documented error type.
        raise ValueError(f"malformed roaring data: {e}") from e


def _deserialize_pilosa(data: bytes, b: Bitmap,
                        info: Optional[ReplayInfo] = None) -> Bitmap:
    if len(data) < 8:
        raise ValueError("data too small")
    version = data[2]
    if version != STORAGE_VERSION:
        raise ValueError(f"wrong roaring version: file is v{version}")
    b.flags = data[3]
    key_n = struct.unpack_from("<I", data, 4)[0]
    # Header must hold key_n * (12B descriptive + 4B offset) entries
    # (reference unmarshal_binary.go:150 checks 12B; offsets checked below).
    if len(data) < 8 + key_n * 16:
        raise ValueError(
            f"insufficient data for header + offsets: {key_n} containers, {len(data)} bytes"
        )

    if key_n:
        hdr12 = np.frombuffer(data, dtype=np.uint8, count=key_n * 12, offset=8).reshape(key_n, 12)
        keys = hdr12[:, 0:8].copy().view("<u8").reshape(key_n)
        typs = hdr12[:, 8:10].copy().view("<u2").reshape(key_n)
        cards = hdr12[:, 10:12].copy().view("<u2").reshape(key_n).astype(np.int64) + 1
    else:
        keys = np.empty(0, dtype=np.uint64)
        typs = np.empty(0, dtype=np.uint16)
        cards = np.empty(0, dtype=np.int64)

    ops_offset = 8 + key_n * 12
    # 32-bit offsets with wraparound for >4GB files (reference
    # unmarshal_binary.go:168-176 cycleOffset logic).
    cycle = ops_offset & ~((1 << 32) - 1)
    prev32 = ops_offset & 0xFFFFFFFF
    off_base = 8 + key_n * 12
    for i in range(key_n):
        off32 = struct.unpack_from("<I", data, off_base + i * 4)[0]
        if off32 < prev32:
            cycle += 1 << 32
        prev32 = off32
        offset = off32 + cycle
        if offset >= len(data) and cards[i] > 0:
            raise ValueError(f"offset out of bounds: off={offset}, len={len(data)}")
        typ = int(typs[i])
        n = int(cards[i])
        if typ == TYPE_CODE_ARRAY:
            arr = np.frombuffer(data, dtype="<u2", count=n, offset=offset).copy()
            b.put_container(int(keys[i]), Container.from_positions(arr))
            ops_offset = offset + n * 2
        elif typ == TYPE_CODE_BITMAP:
            words = np.frombuffer(data, dtype="<u8", count=BITMAP_N, offset=offset).copy()
            b.put_container(int(keys[i]), Container.from_bitmap_words(words, n))
            ops_offset = offset + BITMAP_N * 8
        elif typ == TYPE_CODE_RUN:
            run_n = struct.unpack_from("<H", data, offset)[0]
            runs = (
                np.frombuffer(data, dtype="<u2", count=run_n * 2, offset=offset + 2)
                .copy()
                .reshape(run_n, 2)
                .astype(np.int64)
            )
            b.put_container(int(keys[i]), Container.from_runs(runs))
            ops_offset = offset + 2 + run_n * 4
        else:
            raise ValueError(f"unsupported container type {typ}")

    apply_ops(b, data, ops_offset, info)
    return b


def _deserialize_official(data: bytes, b: Bitmap) -> Bitmap:
    """Official RoaringFormatSpec (16-bit keys, low 2^32 bit space only)."""
    if len(data) < 8:
        raise ValueError("buffer too small")
    cookie = struct.unpack_from("<I", data, 0)[0]
    pos = 4
    is_run = None
    if cookie == SERIAL_COOKIE_NO_RUN:
        key_n = struct.unpack_from("<I", data, pos)[0]
        pos += 4
        have_runs = False
    elif cookie & 0xFFFF == SERIAL_COOKIE:
        have_runs = True
        key_n = (cookie >> 16) + 1
        run_bitmap_size = (key_n + 7) // 8
        is_run = data[pos : pos + run_bitmap_size]
        pos += run_bitmap_size
    else:
        raise ValueError("did not find expected serialCookie in header")
    if key_n > (1 << 16):
        raise ValueError("more than 2^16 containers is impossible")

    hdr_pos = pos
    pos += 4 * key_n  # past descriptive header

    entries = []
    for i in range(key_n):
        key = struct.unpack_from("<H", data, hdr_pos + i * 4)[0]
        card = struct.unpack_from("<H", data, hdr_pos + i * 4 + 2)[0] + 1
        if have_runs and is_run is not None and (is_run[i // 8] >> (i % 8)) & 1:
            typ = TYPE_CODE_RUN
        elif card <= ARRAY_MAX_SIZE:
            typ = TYPE_CODE_ARRAY
        else:
            typ = TYPE_CODE_BITMAP
        entries.append((key, typ, card))

    # The official format has an offset section when there are no runs
    # (always written by the reference when !haveRuns); with runs the
    # containers follow immediately and run lengths are [start, length].
    if not have_runs:
        offsets = [struct.unpack_from("<I", data, pos + i * 4)[0] for i in range(key_n)]
        for (key, typ, card), offset in zip(entries, offsets):
            if typ == TYPE_CODE_ARRAY:
                arr = np.frombuffer(data, dtype="<u2", count=card, offset=offset).copy()
                b.put_container(key, Container.from_positions(arr))
            else:
                words = np.frombuffer(data, dtype="<u8", count=BITMAP_N, offset=offset).copy()
                b.put_container(key, Container.from_bitmap_words(words, card))
    else:
        for key, typ, card in entries:
            if typ == TYPE_CODE_RUN:
                run_n = struct.unpack_from("<H", data, pos)[0]
                pos += 2
                runs = (
                    np.frombuffer(data, dtype="<u2", count=run_n * 2, offset=pos)
                    .copy()
                    .reshape(run_n, 2)
                    .astype(np.int64)
                )
                runs[:, 1] = runs[:, 0] + runs[:, 1]  # start,length -> start,last
                b.put_container(key, Container.from_runs(runs))
                pos += run_n * 4
            elif typ == TYPE_CODE_ARRAY:
                arr = np.frombuffer(data, dtype="<u2", count=card, offset=pos).copy()
                b.put_container(key, Container.from_positions(arr))
                pos += card * 2
            else:
                words = np.frombuffer(data, dtype="<u8", count=BITMAP_N, offset=pos).copy()
                b.put_container(key, Container.from_bitmap_words(words, card))
                pos += BITMAP_N * 8
    return b


# ---------------------------------------------------------------------------
# Op log
# ---------------------------------------------------------------------------


def encode_op(typ: int, value: int = 0, values: Optional[np.ndarray] = None,
              roaring: bytes = b"", op_n: int = 0) -> bytes:
    """Encode one op record (reference roaring/roaring.go op.WriteTo)."""
    if typ in (OP_ADD, OP_REMOVE):
        buf = bytearray(13)
        buf[0] = typ
        struct.pack_into("<Q", buf, 1, value)
        payload = b""
    elif typ in (OP_ADD_BATCH, OP_REMOVE_BATCH):
        vals = np.asarray(values, dtype="<u8")
        buf = bytearray(13 + vals.size * 8)
        buf[0] = typ
        struct.pack_into("<Q", buf, 1, vals.size)
        buf[13:] = vals.tobytes()
        payload = b""
    elif typ in (OP_ADD_ROARING, OP_REMOVE_ROARING):
        buf = bytearray(17)
        buf[0] = typ
        struct.pack_into("<Q", buf, 1, len(roaring))
        struct.pack_into("<I", buf, 13, op_n)
        payload = roaring
    else:
        raise ValueError(f"unknown op type {typ}")
    h = fnv32a(bytes(buf[0:9]))
    h = fnv32a(bytes(buf[13:]), h)
    if payload:
        h = fnv32a(payload, h)
    struct.pack_into("<I", buf, 9, h)
    return bytes(buf) + payload


def _op_size(typ: int, value: int) -> int:
    if typ in (OP_ADD, OP_REMOVE):
        return 13
    if typ in (OP_ADD_BATCH, OP_REMOVE_BATCH):
        return 13 + 8 * value
    return 17 + value  # roaring ops: value is payload length


def apply_ops(b: Bitmap, data: bytes, offset: int,
              info: Optional[ReplayInfo] = None) -> int:
    """Replay the op log from offset to EOF. Returns number of ops applied.

    reference roaring/unmarshal_binary.go:207-228 (checksum-verified replay,
    op.apply at roaring/roaring.go:4669).

    Torn-tail contract (ISSUE r8): with `info` supplied (the fragment
    recovery path), a damaged FINAL record — truncated mid-append, or
    checksum-failing with nothing after it, the shapes a SIGKILL during
    the WAL append produces — stops the replay at the last good record
    and reports the torn offset in `info` instead of raising; the caller
    truncates the file there. Damage with MORE bytes after it (a
    checksum-failing or structurally impossible record before the tail)
    is mid-log corruption: truncating there would drop the records
    behind it, so it always raises CorruptWalError and the fragment
    refuses to open. Without `info` (wire payloads, block merges) every
    damage class raises, exactly as before — a peer's serialized bitmap
    has no legitimate torn tail.
    """
    n_ops = 0
    pos = offset
    while pos < len(data):
        if len(data) - pos < _MIN_OP_SIZE:
            if info is not None:
                info.torn_offset, info.torn_reason = pos, "short-record"
                break
            raise ValueError(f"op data out of bounds: len={len(data) - pos}")
        typ = data[pos]
        if typ > OP_REMOVE_ROARING:
            # Never a torn shape: a partial append is a PREFIX of a valid
            # record, whose first byte is a valid type — an impossible
            # type is a flipped bit, and record boundaries past it are
            # unknowable, so even at the tail this refuses.
            raise CorruptWalError(
                f"unknown op type {typ} at offset {pos}", pos, "op-type"
            )
        value = struct.unpack_from("<Q", data, pos + 1)[0]
        size = _op_size(typ, value)
        if pos + size > len(data):
            if info is not None:
                info.torn_offset, info.torn_reason = pos, "truncated"
                break
            raise ValueError("op data truncated")
        want = struct.unpack_from("<I", data, pos + 9)[0]
        h = fnv32a(data[pos : pos + 9])
        h = fnv32a(data[pos + 13 : pos + size], h)
        if h != want:
            if info is not None and pos + size == len(data):
                # Checksum-failing FINAL record: the mid-append crash
                # shape (payload bytes landed, some garbage/stale).
                info.torn_offset, info.torn_reason = pos, "checksum"
                break
            raise CorruptWalError(
                f"op checksum mismatch at offset {pos}", pos, "checksum"
            )
        if typ == OP_ADD:
            b.add(value, log=False)
            b.op_n += 1
        elif typ == OP_REMOVE:
            b.remove(value, log=False)
            b.op_n += 1
        elif typ in (OP_ADD_BATCH, OP_REMOVE_BATCH):
            vals = np.frombuffer(data, dtype="<u8", count=value, offset=pos + 13).copy()
            if typ == OP_ADD_BATCH:
                b.add_many(vals, log=False)
            else:
                b.remove_many(vals, log=False)
            b.op_n += int(value)
        else:
            payload = data[pos + 17 : pos + 17 + value]
            # opN stored in the record is the write-time changed count
            # (reference op.count() for roaring ops).
            op_n = struct.unpack_from("<I", data, pos + 13)[0]
            b.import_roaring_bits(bytes(payload), clear=(typ == OP_REMOVE_ROARING), log=False)
            b.op_n += op_n
        pos += size
        n_ops += 1
    if info is not None:
        info.ops_applied += n_ops
    return n_ops


class OpWriter:
    """Appends checksummed op records to a file (the fragment WAL).

    Attached to a Bitmap as bitmap.op_writer (reference fragment.go:455).
    Callers should hand in an unbuffered file (fragment.open uses
    buffering=0) so each record hits the OS immediately and a process crash
    loses nothing — matching the reference's unbuffered Go file writes;
    fsync is left to the OS like the reference does. flush() covers
    buffered writers.
    """

    def __init__(self, f: BinaryIO):
        self.f = f

    def _write(self, record: bytes) -> None:
        self.f.write(record)

    def append_add(self, v: int) -> None:
        self._write(encode_op(OP_ADD, value=v))

    def append_remove(self, v: int) -> None:
        self._write(encode_op(OP_REMOVE, value=v))

    def append_add_batch(self, vs: np.ndarray) -> None:
        self._write(encode_op(OP_ADD_BATCH, values=vs))

    def append_remove_batch(self, vs: np.ndarray) -> None:
        self._write(encode_op(OP_REMOVE_BATCH, values=vs))

    def append_roaring(self, data: bytes, op_n: int, clear: bool) -> None:
        typ = OP_REMOVE_ROARING if clear else OP_ADD_ROARING
        self._write(encode_op(typ, roaring=data, op_n=op_n))

    def flush(self) -> None:
        self.f.flush()
