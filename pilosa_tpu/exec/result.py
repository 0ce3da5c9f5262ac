"""Executor result types (reference executor.go / row.go result shapes)."""

from __future__ import annotations

from collections.abc import Sequence
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from pilosa_tpu.core.cache import Pair
from pilosa_tpu.utils.stats import global_stats


@dataclass
class ValCount:
    """Sum/Min/Max result (reference ValCount executor.go)."""

    val: int = 0
    count: int = 0

    def to_json(self) -> dict:
        return {"value": self.val, "count": self.count}


@dataclass
class PairsField:
    """TopN result: pairs + the field they came from."""

    pairs: list[Pair] = field(default_factory=list)
    field_name: str = ""

    def to_json(self) -> list:
        out = []
        for p in self.pairs:
            if p.key:
                out.append({"key": p.key, "count": p.count})
            else:
                out.append({"id": p.id, "count": p.count})
        return out


@dataclass
class PairField:
    """MinRow/MaxRow result: a single pair (reference PairField)."""

    pair: Pair = field(default_factory=lambda: Pair(0, 0))
    field_name: str = ""

    def to_json(self) -> dict:
        if self.pair.key:
            return {"key": self.pair.key, "count": self.pair.count}
        return {"id": self.pair.id, "count": self.pair.count}


class RowIDs(list):
    """Rows() result: sorted row IDs with limit-aware merge
    (reference executor.go RowIDs.merge). When the field is keyed the
    executor fills `keys` and the JSON form emits them instead
    (reference RowIdentifiers marshaling)."""

    keys: Optional[list[str]] = None

    def merge(self, other: "RowIDs", limit: int) -> "RowIDs":
        seen = set(self)
        out = sorted(seen | set(other))
        return RowIDs(out[:limit])

    def to_json(self) -> dict:
        if self.keys is not None:
            return {"keys": self.keys}
        return {"rows": list(self)}


@dataclass
class FieldRow:
    """One (field, row) of a GroupBy group (reference executor.go:1154)."""

    field: str
    row_id: int
    row_key: str = ""

    def to_json(self) -> dict:
        if self.row_key:
            return {"field": self.field, "rowKey": self.row_key}
        return {"field": self.field, "rowID": self.row_id}


@dataclass
class GroupCount:
    """One GroupBy result group (reference executor.go:1187)."""

    group: list[FieldRow]
    count: int

    def compare_key(self) -> tuple:
        return tuple(fr.row_id for fr in self.group)

    def to_json(self) -> dict:
        return {"group": [fr.to_json() for fr in self.group], "count": self.count}


class GroupCounts(Sequence):
    """A GroupBy answer as columns: what the device path hands up in
    place of a list of GroupCount. `fields` holds the group's field names
    once, `rows` the row ids as int64[G, n] in enumeration (odometer)
    order, `counts` int64[G], and `keys[j]` the row keys of field j when
    the executor translated it (None: an id field). utils/fastjson
    encodes the columns by template; a consumer that wants objects
    (protobuf, a coordinator's merge, the dict encoder, tests) gets
    GroupCount objects built on demand through the sequence protocol,
    counted as
    group_rows_encoded_total{path="objects"}. A slice is a view of the
    same type over the same arrays."""

    __slots__ = ("fields", "rows", "counts", "keys")

    def __init__(self, fields, rows, counts, keys=None):
        self.fields = tuple(fields)
        self.rows = rows
        self.counts = counts
        self.keys = keys if keys is not None else [None] * len(self.fields)

    def __len__(self) -> int:
        return len(self.counts)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return GroupCounts(
                self.fields, self.rows[i], self.counts[i],
                [k if k is None else k[i] for k in self.keys],
            )
        n = len(self)
        if not -n <= i < n:
            raise IndexError("GroupCounts index out of range")
        i %= n
        return self[i:i + 1]._objects()[0]

    def __iter__(self):
        return iter(self._objects())

    def _objects(self) -> list[GroupCount]:
        global_stats.with_tags("path:objects").count(
            "group_rows_encoded_total", len(self)
        )
        cols = [
            (name, self.keys[j] or [""] * len(self))
            for j, name in enumerate(self.fields)
        ]
        # lint: allow-hot-serialize(the on-demand object form of a columnar answer: protobuf, merges and the dict oracle; the serving path's JSON never comes here)
        cells = zip(self.rows.tolist(), self.counts.tolist())
        return [
            GroupCount(
                [FieldRow(name, ids[j], ks[g]) for j, (name, ks) in enumerate(cols)],
                count,
            )
            for g, (ids, count) in enumerate(cells)
        ]

    def __eq__(self, other) -> bool:
        if isinstance(other, (GroupCounts, list, tuple)):
            return list(self) == list(other)
        return NotImplemented

    __hash__ = None

    def __repr__(self) -> str:
        return f"GroupCounts({list(self)!r})"

    def translate(self, j: int, translate_ids) -> None:
        """Fill field j's row keys: one bulk id -> key lookup over the
        column's distinct ids ("" where an id has no key, as the object
        form holds it)."""
        uniq, inverse = np.unique(self.rows[:, j], return_inverse=True)
        # lint: allow-hot-serialize(key translation builds one Python string per distinct row id; the id list is that lookup's input)
        found = translate_ids(uniq.tolist())
        by_id = np.array([k or "" for k in found], dtype=object)
        self.keys[j] = list(by_id[inverse])

    @property
    def nbytes(self) -> int:
        """What the result cache charges: the arrays, the names, and the
        key strings of translated fields."""
        n = 64 + self.rows.nbytes + self.counts.nbytes
        n += sum(56 + len(name) for name in self.fields)
        for ks in self.keys:
            if ks is not None:
                n += 56 + 8 * len(ks) + sum(56 + len(k) for k in ks)
        return n


def merge_group_counts(a: list[GroupCount], b: list[GroupCount], limit: int) -> list[GroupCount]:
    """Sorted merge summing counts of equal groups, capped at limit
    (reference executor.go mergeGroupCounts :1195)."""
    limit = min(limit, len(a) + len(b))
    out: list[GroupCount] = []
    i = j = 0
    while i < len(a) and j < len(b) and len(out) < limit:
        ka, kb = a[i].compare_key(), b[j].compare_key()
        if ka < kb:
            out.append(a[i])
            i += 1
        elif ka > kb:
            out.append(b[j])
            j += 1
        else:
            out.append(GroupCount(a[i].group, a[i].count + b[j].count))
            i += 1
            j += 1
    while i < len(a) and len(out) < limit:
        out.append(a[i])
        i += 1
    while j < len(b) and len(out) < limit:
        out.append(b[j])
        j += 1
    return out


@dataclass
class SignedRow:
    """Placeholder for signed BSI row results (used by later versions of the
    reference; kept for API-shape completeness)."""

    pos: Any = None
    neg: Any = None


def result_to_json(result: Any) -> Any:
    """Encode an executor result the way the HTTP layer does
    (reference http/handler.go query response encoding)."""
    from pilosa_tpu.core.row import Row

    if result is None:
        return None
    if isinstance(result, Row):
        # lint: allow-hot-serialize(legacy dict encoder kept as the byte-compat oracle; the serving path rides utils/fastjson)
        out: dict[str, Any] = {"columns": result.columns().tolist()}
        if result.keys:
            out = {"keys": result.keys, "columns": []}
        if result.attrs:
            out["attrs"] = result.attrs
        return out
    if isinstance(result, bool):
        return result
    if isinstance(result, int):
        return result
    if isinstance(result, (ValCount, PairsField, PairField, RowIDs)):
        return result.to_json()
    if isinstance(result, (list, GroupCounts)):
        return [result_to_json(r) for r in result]
    if isinstance(result, GroupCount):
        return result.to_json()
    return result
