"""Native (C++) helpers with pure-Python fallbacks.

The Go reference is a single static binary; here the Python control plane
offloads its few byte-at-a-time hot loops (FNV/xxhash hashing for op-log
checksums, partition hashing, and block checksums) to a small C++ library
built on first use with g++. If no toolchain is available every function
falls back to a pure-Python implementation with identical outputs.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "hash.cpp")
_CXX = ("g++", "-O3", "-shared", "-fPIC")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False
_scratch = threading.local()

FNV32_OFFSET = 2166136261
FNV64_OFFSET = 14695981039346656037


def _cpu_features() -> str:
    """The build host's instruction-set flags (Linux /proc/cpuinfo)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line
    except OSError:
        pass
    return os.uname().machine


def _lib_path() -> str:
    """Where the library for THIS source on THIS CPU lives. The name
    carries a digest of everything the binary depends on — the source
    text, the compiler line and, because -march=native bakes the build
    host's instruction set in, the CPU's feature flags — so reuse never
    rests on an mtime. A build directory copied from another machine
    with its mtimes intact holds a library under another name, and this
    host builds its own from src/hash.cpp instead of loading one that
    may die on an illegal instruction, which no retry can catch."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXX).encode())
    h.update(_cpu_features().encode())
    return os.path.join(
        _HERE, "build", f"libpilosa_native-{h.hexdigest()[:16]}.so"
    )


def _load() -> ctypes.CDLL | None:
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        for attempt in ("load", "rebuild"):
            try:
                lib_path = _lib_path()
                if attempt == "rebuild" or not os.path.exists(lib_path):
                    os.makedirs(os.path.dirname(lib_path), exist_ok=True)
                    # Compile to a private name and rename into place: a
                    # concurrent process (a server child, a pool worker)
                    # finds either no library, and builds its own, or a
                    # whole one.
                    tmp = f"{lib_path}.{os.getpid()}.tmp"
                    tail = ("-o", tmp, _SRC)
                    try:
                        # -march=native: the .so is built per host on
                        # first use, so host-specific vectorization is
                        # safe; retried without for exotic toolchains.
                        # lint: allow-lock-discipline(one-time lazy toolchain build under the init latch; first callers accept the compile latency)
                        subprocess.run(
                            _CXX[:2] + ("-march=native",) + _CXX[2:] + tail,
                            check=True,
                            capture_output=True,
                        )
                    except subprocess.CalledProcessError:
                        # lint: allow-lock-discipline(same one-time lazy build, -march fallback)
                        subprocess.run(
                            _CXX + tail, check=True, capture_output=True
                        )
                    os.replace(tmp, lib_path)
                lib = ctypes.CDLL(lib_path)
                lib.pilosa_fnv32a.restype = ctypes.c_uint32
                lib.pilosa_fnv32a.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32]
                lib.pilosa_fnv64a.restype = ctypes.c_uint64
                lib.pilosa_fnv64a.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64]
                lib.pilosa_xxhash64.restype = ctypes.c_uint64
                lib.pilosa_xxhash64.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64]
                lib.pilosa_scatter_positions.restype = None
                lib.pilosa_scatter_positions.argtypes = [
                    ctypes.c_void_p,
                    ctypes.c_size_t,
                    ctypes.c_void_p,
                    ctypes.c_size_t,
                ]
                lib.pilosa_intersection_count_many.restype = ctypes.c_longlong
                lib.pilosa_intersection_count_many.argtypes = [
                    ctypes.c_void_p,
                    ctypes.c_void_p,
                    ctypes.c_void_p,
                    ctypes.c_void_p,
                    ctypes.c_size_t,
                ]
                lib.pilosa_import_containers.restype = ctypes.c_longlong
                lib.pilosa_import_containers.argtypes = [
                    ctypes.c_void_p,
                    ctypes.c_void_p,
                    ctypes.c_size_t,
                    ctypes.c_uint32,
                    ctypes.c_size_t,
                    ctypes.c_void_p,
                    ctypes.c_void_p,
                    ctypes.c_void_p,
                ]
                lib.pilosa_import_containers32.restype = ctypes.c_longlong
                lib.pilosa_import_containers32.argtypes = (
                    lib.pilosa_import_containers.argtypes
                )
                lib.pilosa_import_containers_r8c32.restype = ctypes.c_longlong
                lib.pilosa_import_containers_r8c32.argtypes = (
                    lib.pilosa_import_containers.argtypes
                )
                lib.pilosa_compress_words.restype = ctypes.c_longlong
                lib.pilosa_compress_words.argtypes = [
                    ctypes.c_void_p,
                    ctypes.c_size_t,
                    ctypes.c_void_p,
                    ctypes.c_void_p,
                ]
                # lint: allow-shared-state(double-checked lazy init: the build is serialized by _build_lock and unlocked readers observe either None or the fully-initialized lib)
                _lib = lib
                return _lib
            # lint: allow-except-exception(toolchain probe: loop retries a forced rebuild, then the fallback warns and pure-Python continues)
            except Exception:
                # A truncated or foreign .so can fail to load: retry once
                # with a forced rebuild before giving up on the native path.
                continue
        _build_failed = True
        import warnings

        warnings.warn(
            "pilosa_tpu native helper library unavailable; using pure-Python "
            "fallbacks (slower; xxhash64 block checksums use a different "
            "algorithm — do not mix native and fallback nodes in one cluster)"
        )
    return _lib


def fnv32a(data: bytes, h: int = FNV32_OFFSET) -> int:
    lib = _load()
    if lib is not None:
        return lib.pilosa_fnv32a(data, len(data), h)
    for b in data:
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h


def fnv64a(data: bytes, h: int = FNV64_OFFSET) -> int:
    lib = _load()
    if lib is not None:
        return lib.pilosa_fnv64a(data, len(data), h)
    for b in data:
        h = ((h ^ b) * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    return h


def xxhash64(data: bytes, seed: int = 0) -> int:
    lib = _load()
    if lib is not None:
        return lib.pilosa_xxhash64(data, len(data), seed)
    import hashlib

    # Fallback: not the xxhash algorithm, but block checksums only need to be
    # consistent among our own nodes (all nodes agree on which path they use;
    # a native/fallback mixed cluster is not supported).
    return int.from_bytes(hashlib.blake2b(data, digest_size=8).digest(), "little")


def scatter_positions(words, base_word: int, pos) -> bool:
    """OR bit positions (uint16 ndarray) of one array container into a
    contiguous uint32 word vector at word offset base_word. Returns True
    when the native path ran; False means the caller must use its
    numpy fallback (np.bitwise_or.at). The HBM pack hot loop."""
    lib = _load()
    if lib is None:
        return False
    lib.pilosa_scatter_positions(
        words.ctypes.data,
        base_word,
        pos.ctypes.data,
        len(pos),
    )
    return True


def import_containers(rows, cols, shard_width_exp: int, key_cap: int = 1 << 16):
    """Container-granular import groups (reference ImportRoaringBits,
    roaring/roaring.go:1511): one shard's (row, col) uint64 arrays ->
    (keys u32 ascending, counts u32, lows u16 concatenated sorted
    unique). None means 'use the numpy comparison-sort fallback' (no
    toolchain, or rows too tall for the counting table)."""
    lib = _load()
    if lib is None:
        return None
    import numpy as np

    # Narrow streams stay narrow (the C import is input-load bound):
    # uint32 columns hold global ids up to 4096 shards; uint8 rows hold
    # the common short-field case — together 5 B/pair vs 16.
    if getattr(cols, "dtype", None) == np.uint32:
        cols = np.ascontiguousarray(cols)
        if getattr(rows, "dtype", None) == np.uint8:
            rows = np.ascontiguousarray(rows)
            entry = lib.pilosa_import_containers_r8c32
        else:
            rows = np.ascontiguousarray(rows, dtype=np.uint64)
            entry = lib.pilosa_import_containers32
    else:
        rows = np.ascontiguousarray(rows, dtype=np.uint64)
        cols = np.ascontiguousarray(cols, dtype=np.uint64)
        entry = lib.pilosa_import_containers
    n = rows.size
    cap = min(n, key_cap)
    # keys/counts are thread-local scratch (callers consume them within
    # the call); lows is a FRESH array each call — the C side writes it
    # once and Bitmap.import_container_groups hands zero-copy views of
    # it to the new containers (an extra owned copy per shard measured
    # ~0.5 ms at bench density on this host).
    scr = getattr(_scratch, "bufs", None)
    if scr is None or scr[0].size < cap:
        scr = (
            np.empty(max(cap, 1 << 12), dtype=np.uint32),
            np.empty(max(cap, 1 << 12), dtype=np.uint32),
        )
        _scratch.bufs = scr
    out_keys, out_counts = scr
    out_lows = np.empty(max(n, 1), dtype=np.uint16)
    rc = entry(
        rows.ctypes.data,
        cols.ctypes.data,
        n,
        shard_width_exp,
        key_cap,
        out_keys.ctypes.data,
        out_counts.ctypes.data,
        out_lows.ctypes.data,
    )
    if rc < 0:
        return None
    return out_keys[:rc], out_counts[:rc], out_lows


def intersection_count_many(a_list, b_list):
    """Sum of per-pair sorted-merge intersection counts over K
    array-container pairs (each list holds K sorted-unique uint16
    ndarrays). None means 'no native lib' — caller uses its numpy
    membership-mask fallback."""
    lib = _load()
    if lib is None:
        return None
    import numpy as np

    a = np.concatenate(a_list) if len(a_list) > 1 else a_list[0]
    b = np.concatenate(b_list) if len(b_list) > 1 else b_list[0]
    aoff = np.zeros(len(a_list) + 1, dtype=np.int64)
    np.cumsum([x.size for x in a_list], out=aoff[1:])
    boff = np.zeros(len(b_list) + 1, dtype=np.int64)
    np.cumsum([x.size for x in b_list], out=boff[1:])
    a = np.ascontiguousarray(a)
    b = np.ascontiguousarray(b)
    return int(
        lib.pilosa_intersection_count_many(
            a.ctypes.data, aoff.ctypes.data, b.ctypes.data, boff.ctypes.data,
            len(a_list),
        )
    )


def compress_words(chunk, mask_out, vals_out):
    """Zero-word compression of one uint32 word chunk (ops/sparse.py wire
    format): writes the occupancy mask (bit b of mask_out[j] covers
    chunk[j*32+b]) and packs nonzero words into vals_out. Returns nnz,
    or None when the native lib is unavailable (caller uses its numpy
    fallback). chunk size must be a multiple of 32."""
    lib = _load()
    if lib is None:
        return None
    return int(
        lib.pilosa_compress_words(
            chunk.ctypes.data, chunk.size, mask_out.ctypes.data,
            vals_out.ctypes.data,
        )
    )


def has_native() -> bool:
    return _load() is not None
