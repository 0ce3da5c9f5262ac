"""An int field bulk-loaded as its bit-sliced planes through
`import-roaring` holds what the same values loaded through `import` hold.

View "" of an import-roaring request is the field's own view: `standard`
for a set field, `bsig_<field>` for an int field (it went to a `standard`
view no query reads before). A union into an int field's plane view raises
the field's `bit_depth` to the highest plane the bitmap holds, and a plane
no value between the field's min and max can set is refused (400) before
anything is written. Row 0 says which columns hold a value, row 1 holds
the sign, rows 2.. the bits of |value - base|, lowest first.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.view import bsi_view_name
from pilosa_tpu.exec import Executor
from pilosa_tpu.roaring import Bitmap, serialize
from pilosa_tpu.server.api import API
from pilosa_tpu.server.http import Server
from pilosa_tpu.server.wire import ImportRoaringRequest, ImportRoaringRequestView
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.stats import global_stats

SHARDS = 2


def planes_blob(columns: np.ndarray, values: np.ndarray, base: int = 0) -> bytes:
    """The roaring bitmap of one shard's planes: position = row *
    SHARD_WIDTH + in-shard column."""
    rel = values - base
    mag = np.abs(rel)
    rows = [np.zeros_like(columns)]  # exists
    cols = [columns]
    rows.append(np.ones_like(columns[rel < 0]))  # sign
    cols.append(columns[rel < 0])
    for bit in range(int(mag.max()).bit_length()):
        held = columns[(mag >> bit) & 1 == 1]
        rows.append(np.full_like(held, 2 + bit))
        cols.append(held)
    pos = np.concatenate(rows) * SHARD_WIDTH + np.concatenate(cols)
    return serialize(Bitmap(np.sort(pos).astype(np.uint64)))


def body(data: bytes, view: str = "") -> bytes:
    return ImportRoaringRequest(
        views=[ImportRoaringRequestView(name=view, data=data)]
    ).to_bytes()


def post(srv, path, payload, ctype="application/x-protobuf"):
    if not isinstance(payload, bytes):
        payload, ctype = json.dumps(payload).encode(), "application/json"
    r = urllib.request.Request(srv.uri + path, data=payload, method="POST",
                               headers={"Content-Type": ctype})
    with urllib.request.urlopen(r) as resp:
        return json.loads(resp.read())


@pytest.fixture
def server(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    srv = Server(API(holder, Executor(holder)), host="localhost", port=0).open()
    post(srv, "/index/i", {})
    yield srv
    srv.close()
    holder.close()


def drawn(rng, lo: int, hi: int, n: int = 3000):
    """Per shard (in-shard columns, values), distinct columns."""
    out = []
    for _ in range(SHARDS):
        cols = np.unique(rng.integers(0, SHARD_WIDTH, n))
        vals = rng.integers(lo, hi + 1, cols.size)
        vals[:2] = (lo, hi)  # the whole range, so the depths agree
        out.append((cols.astype(np.int64), vals.astype(np.int64)))
    return out


def load_both(srv, lo: int, hi: int, data, view: str = ""):
    """Field `planes` by import-roaring, field `values` by import."""
    opts = {"options": {"type": "int", "min": lo, "max": hi}}
    for name in ("planes", "values"):
        post(srv, f"/index/i/field/{name}", opts)
    f = srv.api.holder.index("i").field("planes")
    for shard, (cols, vals) in enumerate(data):
        post(srv, f"/index/i/field/planes/import-roaring/{shard}",
             body(planes_blob(cols, vals, f.options.base),
                  view and bsi_view_name("planes")))
        post(srv, "/index/i/field/values/import",
             {"columnIDs": (cols + shard * SHARD_WIDTH).tolist(),
              "values": vals.tolist()})


@pytest.mark.parametrize("lo,hi", [(0, 16383), (-1000, 1000), (10, 100)],
                         ids=["from-zero", "signed", "base-above-zero"])
@pytest.mark.parametrize("view", ["", "named"], ids=["view-empty", "view-bsig"])
def test_planes_and_values_hold_the_same(server, rng, lo, hi, view):
    data = drawn(rng, lo, hi)
    load_both(server, lo, hi, data, view)
    idx = server.api.holder.index("i")
    a, b = idx.field("planes"), idx.field("values")
    assert a.options.bit_depth == b.options.bit_depth > 0
    assert list(a.views) == [bsi_view_name("planes")]
    for shard in range(SHARDS):
        fa = a.view(bsi_view_name("planes")).fragment(shard)
        fb = b.view(bsi_view_name("values")).fragment(shard)
        assert np.array_equal(fa.storage.to_array(), fb.storage.to_array())
    mid = (lo + hi) // 2
    for q in ("Sum(field={f})", "Min(field={f})", "Max(field={f})",
              "Count(Row({f} > %d))" % mid, "Row({f} > %d)" % (hi - (hi - lo) // 8),
              "Count(Row({f} >< [%d, %d]))" % (lo, mid)):
        got = [post(server, "/index/i/query", q.format(f=name).encode(),
                    "text/plain")["results"][0] for name in ("planes", "values")]
        assert got[0] == got[1], q
        assert got[0] not in (0, {"value": 0, "count": 0}), q
    # What numpy says of the values themselves.
    total = int(sum(v.sum() for _, v in data))
    count = int(sum(v.size for _, v in data))
    assert post(server, "/index/i/query", b"Sum(field=planes)",
                "text/plain")["results"][0] == {"value": total, "count": count}


def test_bit_depth_is_saved_with_the_field(tmp_path, rng):
    """A reopened holder reads the planes at the depth the load raised."""
    path = str(tmp_path / "data")
    holder = Holder(path).open()
    api = API(holder, Executor(holder))
    api.create_index("i", {})
    api.create_field("i", "v", {"type": "int", "min": 0, "max": 16383})
    cols = np.arange(0, 5000, 5, dtype=np.int64)
    vals = rng.integers(0, 16384, cols.size).astype(np.int64)
    vals[0] = 16383
    api.import_roaring("i", "v", 0, {"": planes_blob(cols, vals)})
    assert holder.index("i").field("v").options.bit_depth == 14
    holder.close()
    holder = Holder(path).open()
    try:
        assert holder.index("i").field("v").options.bit_depth == 14
        got = Executor(holder).execute("i", "Sum(field=v)")[0]
        assert (got.val, got.count) == (int(vals.sum()), cols.size)
    finally:
        holder.close()


def test_a_plane_above_the_fields_max_is_refused(server):
    post(server, "/index/i/field/v", {"options": {"type": "int", "min": 0, "max": 100}})
    cols = np.array([1, 2, 3], dtype=np.int64)
    ok = planes_blob(cols, np.array([100, 64, 1], dtype=np.int64))
    too_high = planes_blob(cols, np.array([128, 5, 1], dtype=np.int64))
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server, "/index/i/field/v/import-roaring/0", body(too_high))
    assert e.value.code == 400
    f = server.api.holder.index("i").field("v")
    assert f.options.bit_depth == 0 and not list(f.views)  # nothing written
    post(server, "/index/i/field/v/import-roaring/0", body(ok))
    assert f.options.bit_depth == 7
    assert post(server, "/index/i/query", b"Sum(field=v)",
                "text/plain")["results"][0] == {"value": 165, "count": 3}


def test_a_set_fields_view_empty_is_still_standard(server):
    post(server, "/index/i/field/f", {})
    before = global_stats.timing_totals("import_roaring_seconds")
    bits = global_stats.counter_totals("import_roaring_bits_total")
    post(server, "/index/i/field/f/import-roaring/0",
         body(serialize(Bitmap(np.array([1, 2, SHARD_WIDTH + 3], dtype=np.uint64)))))
    f = server.api.holder.index("i").field("f")
    assert list(f.views) == ["standard"] and f.options.bit_depth == 0
    assert post(server, "/index/i/query", b"Row(f=0)",
                "text/plain")["results"][0]["columns"] == [1, 2]
    # Timed by the kind of view it went to, and its bits counted.
    after = global_stats.timing_totals("import_roaring_seconds")
    key = 'import_roaring_seconds{view_kind="set"}'
    assert after[key][1] == before.get(key, (0, 0))[1] + 1
    grown = global_stats.counter_totals("import_roaring_bits_total")
    assert sum(grown.values()) - sum(bits.values()) == 3


def test_clearing_planes_leaves_the_depth(server):
    post(server, "/index/i/field/v", {"options": {"type": "int", "min": 0, "max": 1000}})
    cols = np.array([7, 8], dtype=np.int64)
    blob = planes_blob(cols, np.array([1000, 3], dtype=np.int64))
    post(server, "/index/i/field/v/import-roaring/0", body(blob))
    f = server.api.holder.index("i").field("v")
    assert f.options.bit_depth == 10
    server.api.import_roaring("i", "v", 0, {"": blob}, clear=True)
    assert f.options.bit_depth == 10
    assert post(server, "/index/i/query", b"Sum(field=v)",
                "text/plain")["results"][0] == {"value": 0, "count": 0}
