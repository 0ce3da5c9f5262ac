"""The deployment's data, a function of (configuration, seed, shard).

Copied from chip_smoke.py (`field_bits`, `v_values`, `pack64`,
`roaring_body`), which copied bench.py's draw: per row, n uniform
columns with replacement; a field marked `density_split_over_rows`
splits one field's n over its rows. The RNG key is [seed, shard, position
of the field in the configuration], so the same seed gives the same bits
in every run, here and in the reference.

Only `roaring_body` touches the program: it encodes one shard of one
field in the wire format of `POST .../import-roaring/{shard}` with the
program's own client-side codec, as any client library would.
"""

from __future__ import annotations

import numpy as np


def set_fields(config: dict) -> list[str]:
    return [n for n, f in config["fields"].items() if f["type"] == "set"]


def int_fields(config: dict) -> list[str]:
    return [n for n, f in config["fields"].items() if f["type"] == "int"]


def field_position(config: dict, field: str) -> int:
    return list(config["fields"]).index(field)


def field_bits(config: dict, seed: int, shard: int, field: str) -> np.ndarray:
    """bool[rows, shard_width] of one shard of one set field."""
    spec = config["fields"][field]
    width = config["shard_width"]
    rows = spec["rows"]
    n_bits = int(width * spec["density"])
    if spec.get("density_split_over_rows"):
        n_bits //= rows
    rng = np.random.default_rng([seed, shard, field_position(config, field)])
    cols = rng.integers(0, width, size=(rows, n_bits), dtype=np.uint32)
    bits = np.zeros((rows, width), dtype=bool)
    bits[np.arange(rows)[:, None], cols] = True
    return bits


def int_values(config: dict, seed: int, shard: int, field: str):
    """(in-shard columns, values) of one shard of one int field."""
    spec = config["fields"][field]
    width = config["shard_width"]
    rng = np.random.default_rng([seed, shard, field_position(config, field)])
    cols = np.unique(
        rng.integers(0, width, spec["values_per_shard"], dtype=np.int64)
    )
    lo, hi = spec["value_range"]
    return cols, rng.integers(lo, hi + 1, cols.size)


def pack64(bits: np.ndarray) -> np.ndarray:
    """bool[rows, width] -> uint64[rows, width // 64]."""
    return np.packbits(bits, axis=1, bitorder="little").view(np.uint64)


def roaring_body(bits: np.ndarray) -> bytes:
    """The import-roaring body of one shard of one field: positions are
    row * shard_width + column, the flat index of `bits`."""
    from pilosa_tpu.roaring import Bitmap
    from pilosa_tpu.roaring.codec import serialize
    from pilosa_tpu.server.wire import (
        ImportRoaringRequest,
        ImportRoaringRequestView,
    )

    pos = np.flatnonzero(bits.ravel()).astype(np.uint64)
    data = serialize(Bitmap.from_sorted_array(pos))
    return ImportRoaringRequest(
        views=[ImportRoaringRequestView(name="", data=data)]
    ).to_bytes()
