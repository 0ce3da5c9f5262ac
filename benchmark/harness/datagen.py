"""One shard of the deployment's data in hand, and the two ways the
loader ships it.

What a field holds is its draw's business (benchmark/draws/<name>.py,
named by the field's `draw` key or its type's default): one shard of one
field as a function of (configuration, seed, shard, field). A draw says
how it is shipped: `SHIP = "roaring"` (bool[rows, shard_width], posted to
`import-roaring/{shard}`) or `SHIP = "values"` ((columns, values), posted
to `import`).

Only `roaring_body` touches the program: it encodes one shard of one
field in the wire format of `POST .../import-roaring/{shard}` with the
program's own client-side codec, as any client library would.
"""

from __future__ import annotations

import numpy as np

from . import plugins


def set_fields(config: dict) -> list[str]:
    return [n for n, f in config["fields"].items() if f["type"] == "set"]


def field_position(config: dict, field: str) -> int:
    return list(config["fields"]).index(field)


def draw(config: dict, seed: int, shard: int, field: str):
    """One shard of one field, as its draw gives it."""
    return plugins.draw_of(config, field).draw(config, seed, shard, field)


class ShardData:
    """One shard's fields, each drawn when first asked for and kept: the
    loader ships what it holds, and a shape's `shard_tables` reads the
    fields it needs from the same arrays. `given` overrides the seed's
    draw, field by field (the tests' hand-worked shards)."""

    def __init__(self, config: dict, seed: int, shard: int,
                 given: dict | None = None):
        self.config, self.seed, self.shard = config, seed, shard
        self._have = dict(given or {})

    def field(self, name: str):
        if name not in self._have:
            self._have[name] = draw(self.config, self.seed, self.shard, name)
        return self._have[name]

    def bits(self, name: str) -> np.ndarray:
        """bool[rows, shard_width] of a field shipped as bits."""
        return self.field(name)

    def values(self, name: str):
        """(in-shard columns, values) of a field shipped as values."""
        return self.field(name)


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
