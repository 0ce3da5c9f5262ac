#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call:
starts `python -m pilosa_tpu.cli server` (default executor: the device
backend) as a child on a fresh data directory, loads the repo's one
documented deployment over HTTP — index `bench`, 954 shards (1.0 B
columns), set fields f and g of 8 rows at 5 % density, h of 4 rows
sharing 5 %, int field v with 50 values a shard — asks a few requests of
every kind the device serves, and compares every answer with one computed
here with numpy from the same seed (never with exec/cpu.py). Then it
takes the proof that the chip did it from outside the server process:
/debug/diagnostics, /debug/hbm, /debug/programs, /metrics.

One process for the chip: this parent never imports jax (checked below);
only the server child does. Any failed phase — child died, non-200, wrong
answer, platform not tpu, a fallback counted, server not stopping cleanly —
raises, and the exit code is non-zero. The last stdout line of a passing
run is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

    python chip_smoke.py                      # one chip, full size
    python chip_smoke.py --mesh-devices 4     # four chips, one server

`--rehearse cpu` walks the same phases at a small size against a child
started with JAX_PLATFORMS=cpu, to debug the script without a chip. A
rehearsal never exits 0 and never prints the result line.
"""

from __future__ import annotations

import argparse
import http.client
import json
import multiprocessing
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
from importlib import metadata

import numpy as np

from pilosa_tpu import native
from pilosa_tpu.roaring import Bitmap
from pilosa_tpu.roaring.codec import serialize
from pilosa_tpu.server.wire import ImportRoaringRequest, ImportRoaringRequestView
from pilosa_tpu.shardwidth import SHARD_WIDTH

REPO = os.path.dirname(os.path.abspath(__file__))
INDEX = "bench"
#: The deployment (bench.py build_index / build_bsi_field).
SHARDS = 954
FIELD_ROWS = {"f": 8, "g": 8, "h": 4}
DENSITY = 0.05
V_MIN, V_MAX, V_PER_SHARD = -10000, 10000, 50
#: Stack rows pad to 8 on the device (ops/blocks.py ROW_PAD); 4 bytes a word.
STACK_BYTES_PER_SHARD = 8 * (SHARD_WIDTH // 32) * 4

PAIR_VERBS = ("Intersect", "Union", "Difference", "Xor")
#: 3-ary Counts: no pair table can answer them, each pays a generic scan.
NARY = (
    ("Intersect", ("f", 1), ("g", 2), ("h", 3)),  # also read back as a bitmap
    ("Union", ("f", 0), ("g", 1), ("h", 2)),
    ("Difference", ("f", 3), ("g", 4), ("h", 1)),
    ("Xor", ("f", 5), ("g", 6), ("h", 0)),
)
ROW_FIELD, ROW_ID = "f", 1          # Row(f=1), read back for two shards
TOPN_SRC_ROW = 3                    # TopN(f, Row(g=3))
FILTER_G_ROW = 5                    # GroupBy(..., filter=Row(g=5))
FILTER_H_ROW = 1                    # GroupBy(Rows(f), Rows(g), filter=Row(h=1))
V_GT, V_BETWEEN = 2500, (-3000, 4000)
SUM_FILTER_ROW = 1                  # Sum(Row(f=1), field=v)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# data and the numpy reference, both from the seed
# ---------------------------------------------------------------------------


def field_bits(seed: int, shard: int, field: str, density: float) -> np.ndarray:
    """bool[rows, SHARD_WIDTH] of one shard of one set field. bench.py's
    draw: n_bits uniform columns per row, with replacement (h splits one
    field's n_bits over its 4 rows)."""
    rows = FIELD_ROWS[field]
    n_bits = int(SHARD_WIDTH * density)
    if field == "h":
        n_bits //= rows
    rng = np.random.default_rng([seed, shard, "fgh".index(field)])
    cols = rng.integers(0, SHARD_WIDTH, size=(rows, n_bits), dtype=np.uint32)
    bits = np.zeros((rows, SHARD_WIDTH), dtype=bool)
    bits[np.arange(rows)[:, None], cols] = True
    return bits


def v_values(seed: int, shard: int) -> tuple[np.ndarray, np.ndarray]:
    """(in-shard columns, values) of the int field v for one shard."""
    rng = np.random.default_rng([seed, shard, 3])
    cols = np.unique(rng.integers(0, SHARD_WIDTH, V_PER_SHARD, dtype=np.int64))
    return cols, rng.integers(-9000, 9001, cols.size)


def pack64(bits: np.ndarray) -> np.ndarray:
    """bool[rows, SHARD_WIDTH] -> uint64[rows, SHARD_WIDTH // 64]."""
    return np.packbits(bits, axis=1, bitorder="little").view(np.uint64)


def popcount(words: np.ndarray) -> np.ndarray:
    return np.bitwise_count(words).sum(axis=-1, dtype=np.int64)


def pair_counts(f: np.ndarray, g: np.ndarray) -> np.ndarray:
    return popcount(f[:, None, :] & g[None, :, :])


def tri_counts(f, g, h) -> np.ndarray:
    """[Rf, Rg, Rh] three-way intersection counts."""
    return np.stack([pair_counts(f, g & hc[None, :]) for hc in h], axis=-1)


def roaring_body(bits: np.ndarray) -> bytes:
    """The import-roaring body of one shard of one field: positions are
    row * SHARD_WIDTH + column, which is the flat index of `bits`."""
    pos = np.flatnonzero(bits.ravel()).astype(np.uint64)
    data = serialize(Bitmap.from_sorted_array(pos))
    return ImportRoaringRequest(
        views=[ImportRoaringRequestView(name="", data=data)]
    ).to_bytes()


def nary_words(verb: str, leaves, words: dict) -> np.ndarray:
    out = words[leaves[0][0]][leaves[0][1]]
    for fld, row in leaves[1:]:
        w = words[fld][row]
        if verb == "Intersect":
            out = out & w
        elif verb == "Union":
            out = out | w
        elif verb == "Difference":
            out = out & ~w
        else:
            out = out ^ w
    return out


def words_to_cols(words: np.ndarray, shard: int) -> np.ndarray:
    bits = np.unpackbits(words.view(np.uint8), bitorder="little")
    return np.flatnonzero(bits).astype(np.int64) + shard * SHARD_WIDTH


def load_shards(args: tuple) -> dict:
    """Pool worker: generate a run of shards from the seed, POST each
    field's bits to import-roaring/{shard}, and return the shards'
    summed reference statistics. `edits` (shard -> [(set?, field, row,
    col)]) are the PQL Set()/Clear() calls the parent issues after the
    load: the reference applies them here, the import does not."""
    seed, density, port, shards, edits, row_shards = args
    conn = http.client.HTTPConnection("localhost", port, timeout=300)
    nf, ng, nh = FIELD_ROWS["f"], FIELD_ROWS["g"], FIELD_ROWS["h"]
    out = {
        "pair": np.zeros((nf, ng), np.int64),
        "cf": np.zeros(nf, np.int64),
        "cg": np.zeros(ng, np.int64),
        "tri": np.zeros((nf, ng, nh), np.int64),
        "tri_filt": np.zeros((nf, ng, nh), np.int64),
        "nary": np.zeros(len(NARY), np.int64),
        "topn_src": np.zeros(nf, np.int64),
        "v_sum_filt": 0,
        "v_cnt_filt": 0,
        "row_cols": {},
        "isect_cols": [],
        "post_seconds": 0.0,
    }
    for shard in shards:
        bits = {}
        for fld in FIELD_ROWS:
            bits[fld] = field_bits(seed, shard, fld, density)
            body = roaring_body(bits[fld])
            t0 = time.perf_counter()
            conn.request(
                "POST",
                f"/index/{INDEX}/field/{fld}/import-roaring/{shard}",
                body,
                {"Content-Type": "application/x-protobuf"},
            )
            resp = conn.getresponse()
            text = resp.read()
            out["post_seconds"] += time.perf_counter() - t0
            if resp.status != 200:
                raise SmokeFailure(
                    f"import-roaring {fld}/{shard}: HTTP {resp.status}: "
                    f"{text[:200]!r}"
                )
        for is_set, fld, row, col in edits.get(shard, ()):
            bits[fld][row, col] = is_set
        w = {fld: pack64(b) for fld, b in bits.items()}
        f, g, h = w["f"], w["g"], w["h"]
        out["pair"] += pair_counts(f, g)
        out["cf"] += popcount(f)
        out["cg"] += popcount(g)
        out["tri"] += tri_counts(f, g, h)
        out["tri_filt"] += tri_counts(f & g[FILTER_G_ROW][None, :], g, h)
        out["topn_src"] += popcount(f & g[TOPN_SRC_ROW][None, :])
        for k, (verb, *leaves) in enumerate(NARY):
            out["nary"][k] += int(popcount(nary_words(verb, leaves, w)))
        vcols, vvals = v_values(seed, shard)
        member = bits["f"][SUM_FILTER_ROW, vcols]
        out["v_sum_filt"] += int(vvals[member].sum())
        out["v_cnt_filt"] += int(member.sum())
        if shard in row_shards:
            out["row_cols"][shard] = words_to_cols(w[ROW_FIELD][ROW_ID], shard)
        out["isect_cols"].append(
            words_to_cols(nary_words(NARY[0][0], NARY[0][1:], w), shard)
        )
    conn.close()
    out["isect_cols"] = np.concatenate(out["isect_cols"])
    return out


# ---------------------------------------------------------------------------
# the server child and its HTTP surface
# ---------------------------------------------------------------------------


class Client:
    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("localhost", port, timeout=900)

    def request(self, method: str, path: str, body=None, headers=None):
        self.conn.request(method, path, body, headers or {})
        resp = self.conn.getresponse()
        data = resp.read()
        check(
            resp.status == 200,
            f"{method} {path}: HTTP {resp.status}: {data[:300]!r}",
        )
        return data

    def get_json(self, path: str):
        return json.loads(self.request("GET", path))

    def post_json(self, path: str, obj) -> None:
        self.request(
            "POST", path, json.dumps(obj).encode(),
            {"Content-Type": "application/json"},
        )

    def query(self, pql: str, params: str = "") -> list:
        data = self.request("POST", f"/index/{INDEX}/query{params}", pql.encode())
        return json.loads(data)["results"]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def start_server(data_dir: str, port: int, log_path: str, mesh_devices: int,
                 rehearse: str | None) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    if mesh_devices:
        env["PILOSA_TPU_MESH_DEVICES"] = str(mesh_devices)
    if rehearse:
        env["JAX_PLATFORMS"] = rehearse
        if mesh_devices:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={mesh_devices}"
            ).strip()
    # The normal command line, default executor: the device backend.
    with open(log_path, "wb") as log:
        return subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "-d", data_dir, "--bind", f"localhost:{port}"],
            env=env, stdout=log, stderr=subprocess.STDOUT,
        )


def wait_up(srv: subprocess.Popen, port: int, log_path: str,
            timeout: float = 300.0) -> float:
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout:
        if srv.poll() is not None:
            raise SmokeFailure(
                f"server exited with code {srv.returncode} before serving:\n"
                + log_tail(log_path)
            )
        try:
            conn = http.client.HTTPConnection("localhost", port, timeout=5)
            conn.request("GET", "/status")
            if conn.getresponse().status == 200:
                return time.perf_counter() - t0
        except OSError:
            time.sleep(0.25)
    raise SmokeFailure(f"server not up after {timeout:.0f}s:\n" + log_tail(log_path))


def log_tail(log_path: str, n: int = 40) -> str:
    try:
        with open(log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"(no server log: {e})"


def metric_series(text: str, name: str) -> dict[str, float]:
    """{label-string: value} of one Prometheus metric family."""
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        series, _, value = line.rpartition(" ")
        base, _, labels = series.partition("{")
        if base in (name, "pilosa_" + name):
            out[labels.rstrip("}")] = float(value)
    return out


# ---------------------------------------------------------------------------
# expected answers in the server's JSON shapes
# ---------------------------------------------------------------------------


def pair_body() -> tuple[str, list]:
    """The 16-Count body (the Pallas pair sweep): 4 of each verb."""
    specs = [(PAIR_VERBS[i % 4], i % 8, (3 * i + 1) % 8) for i in range(16)]
    body = "".join(
        f"Count({v}(Row(f={a}), Row(g={b})))" for v, a, b in specs
    )
    return body, specs


def pair_expect(specs, ref) -> list[int]:
    out = []
    for verb, a, b in specs:
        p, ca, cb = int(ref["pair"][a, b]), int(ref["cf"][a]), int(ref["cg"][b])
        out.append({
            "Intersect": p, "Union": ca + cb - p,
            "Difference": ca - p, "Xor": ca + cb - 2 * p,
        }[verb])
    return out


def topn_expect(counts: np.ndarray, n: int) -> list[dict]:
    order = sorted(range(counts.size), key=lambda r: (-int(counts[r]), r))
    return [
        {"id": r, "count": int(counts[r])} for r in order if counts[r] > 0
    ][:n]


def group_expect(fields: tuple, tensor: np.ndarray) -> list[dict]:
    """Nonzero groups in odometer order, last field fastest."""
    out = []
    for idx in np.ndindex(*tensor.shape):
        if tensor[idx]:
            out.append({
                "group": [
                    {"field": f, "rowID": int(r)} for f, r in zip(fields, idx)
                ],
                "count": int(tensor[idx]),
            })
    return out


def nary_pql(verb: str, leaves) -> str:
    return f"{verb}(" + ", ".join(f"Row({f}={r})" for f, r in leaves) + ")"


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run(args) -> dict:
    check("jax" not in sys.modules, "the parent imported jax: it would hold the chip")
    check(native.has_native(), "native helper library did not build on this host")
    shards, density, seed = args.shards, args.density, args.seed
    expect_platform = args.rehearse or "tpu"
    say(
        f"chip_smoke: shards={shards} rows=f{FIELD_ROWS['f']}/g{FIELD_ROWS['g']}"
        f"/h{FIELD_ROWS['h']} density={density} seed={seed} "
        f"mesh_devices={args.mesh_devices or 1} "
        f"jax={metadata.version('jax')} jaxlib={metadata.version('jaxlib')} "
        f"libtpu={metadata.version('libtpu')} numpy={np.__version__}"
    )
    if shards != SHARDS or density != DENSITY:
        say(f"CUT: not the documented deployment ({SHARDS} shards, density {DENSITY})")

    work = tempfile.mkdtemp(prefix="pilosa-tpu-smoke-")
    log_path = os.path.join(work, "server.log")
    port = free_port()
    srv = start_server(
        os.path.join(work, "data"), port, log_path, args.mesh_devices,
        args.rehearse,
    )
    pool = None
    try:
        say(f"server up in {wait_up(srv, port, log_path):.1f}s (pid {srv.pid})")
        cli = Client(port)

        # -- where is the child running? -----------------------------------
        diag = cli.get_json("/debug/diagnostics")
        jx = diag["jax"]
        check("error" not in jx, f"device inventory failed: {jx}")
        device = {
            "platform": jx["devices"][0]["platform"],
            "kind": jx["devices"][0]["kind"],
            "count": jx["device_count"],
        }
        say(
            f"device: {json.dumps(device)} default_backend={jx['platform']} "
            f"jax={jx['version']} libtpu={jx['libtpu_version']} "
            f"compile_cache={jx['compilation_cache_dir']} "
            f"has_native={diag['native']}"
        )
        check(
            jx["platform"] == expect_platform
            and device["platform"] == expect_platform,
            f"server is on platform {jx['platform']!r}, not {expect_platform!r}",
        )
        if expect_platform == "tpu":
            check(
                "v5" in device["kind"].lower(),
                f"device kind {device['kind']!r} is not a v5e",
            )
        check(diag["native"] is True, "server runs without the native library")
        check(
            device["count"] >= max(1, args.mesh_devices),
            f"{device['count']} devices visible, mesh wants {args.mesh_devices}",
        )
        # Where JAX_COMPILATION_CACHE_DIR places the cache, there and
        # nowhere else; otherwise the fixed directory in the checkout
        # (a CPU rehearsal keeps none: pilosa_tpu/ops/runtime.py).
        cache_dir = jx["compilation_cache_dir"]
        want_cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or (
            None if args.rehearse == "cpu" else os.path.join(REPO, ".jax_cache")
        )
        check(
            cache_dir == want_cache,
            f"compile cache at {cache_dir!r}, expected {want_cache!r}",
        )

        # -- schema --------------------------------------------------------
        cli.post_json(f"/index/{INDEX}", {})
        for fld in FIELD_ROWS:
            cli.post_json(f"/index/{INDEX}/field/{fld}", {})
        cli.post_json(
            f"/index/{INDEX}/field/v",
            {"options": {"type": "int", "min": V_MIN, "max": V_MAX}},
        )

        # -- the PQL writes the load ends with, known up front so the
        #    workers' reference includes them ------------------------------
        probe = min(7, shards - 1)
        existing = int(np.flatnonzero(field_bits(seed, probe, "f", density)[0])[0])
        edit_list = [
            (True, "f", 1, 77, 0),
            (True, "g", 2, 123456, shards // 2),
            (True, "h", 3, 999, shards - 1),
            (False, "f", 0, existing, probe),       # clears a set bit
            (False, "g", 6, 4242, shards // 3),     # most likely a no-op
        ]
        edits: dict[int, list] = {}
        for is_set, fld, row, col, shard in edit_list:
            edits.setdefault(shard, []).append((is_set, fld, row, col))
        row_shards = sorted({0, shards - 1})

        # -- load ----------------------------------------------------------
        t_load = time.perf_counter()
        n_workers = max(1, min(args.workers, shards))
        chunk = max(1, min(8, shards // (n_workers * 4) or 1))
        tasks = [
            (seed, density, port, list(range(s, min(s + chunk, shards))),
             edits, row_shards)
            for s in range(0, shards, chunk)
        ]
        pool = multiprocessing.get_context("spawn").Pool(n_workers)
        ref: dict = {}
        isect_cols = []
        row_cols: dict[int, np.ndarray] = {}
        post_seconds = 0.0
        for part in pool.imap_unordered(load_shards, tasks):
            check(srv.poll() is None, "server died during the load:\n" + log_tail(log_path))
            post_seconds += part.pop("post_seconds")
            isect_cols.append(part.pop("isect_cols"))
            row_cols.update(part.pop("row_cols"))
            for k, v in part.items():
                ref[k] = ref[k] + v if k in ref else v
        pool.close()
        pool.join()
        pool = None
        isect_cols = np.sort(np.concatenate(isect_cols))
        t_sets = time.perf_counter() - t_load

        v_cols, v_vals = [], []
        for shard in range(shards):
            c, v = v_values(seed, shard)
            v_cols.append(c + shard * SHARD_WIDTH)
            v_vals.append(v)
        v_cols, v_vals = np.concatenate(v_cols), np.concatenate(v_vals)
        cli.post_json(
            f"/index/{INDEX}/field/v/import",
            {"columnIDs": v_cols.tolist(), "values": v_vals.tolist()},
        )
        for is_set, fld, row, col, shard in edit_list:
            verb = "Set" if is_set else "Clear"
            cli.query(f"{verb}({shard * SHARD_WIDTH + col}, {fld}={row})")
        t_load = time.perf_counter() - t_load
        n_bits = int(ref["cf"].sum() + ref["cg"].sum())
        say(
            f"load: {t_load:.1f}s ({shards * 3} import-roaring requests in "
            f"{t_sets:.1f}s over {n_workers} workers, {post_seconds:.1f}s "
            f"summed server time; {v_cols.size} int values; "
            f"{len(edit_list)} PQL writes); f+g hold {n_bits} bits"
        )

        # -- queries: every kind the device serves -------------------------
        timings: dict[str, float] = {}

        def ask(name: str, pql: str, want: list, params: str = "") -> None:
            t0 = time.perf_counter()
            got = cli.query(pql, params)
            dt = time.perf_counter() - t0
            timings[name] = dt
            if got != want:
                g, w = json.dumps(got), json.dumps(want)
                raise SmokeFailure(
                    f"{name}: wrong answer for {pql[:120]}\n"
                    f"   got  {g[:400]}\n   want {w[:400]}"
                )
            say(f"  ok {name:<28} {dt * 1e3:10.1f} ms")

        body, specs = pair_body()
        ask("count16_cold", body, pair_expect(specs, ref))
        ask("count16_warm", body, pair_expect(specs, ref))
        nary_body = "".join(f"Count({nary_pql(v, l)})" for v, *l in NARY)
        nary_want = [int(x) for x in ref["nary"]]
        ask("count_3ary_cold", nary_body, nary_want)
        ask("count_3ary_warm", nary_body, nary_want)
        ask(
            "row_2shards", f"Row({ROW_FIELD}={ROW_ID})",
            [{"attrs": {}, "columns": np.concatenate(
                [row_cols[s] for s in row_shards]).tolist()}],
            "?shards=" + ",".join(map(str, row_shards)),
        )
        isect3 = nary_pql(NARY[0][0], NARY[0][1:])
        ask(
            "intersect3_materialized", isect3,
            [{"attrs": {}, "columns": isect_cols.tolist()}],
        )
        ask("topn", "TopN(f, n=5)", [topn_expect(ref["cf"], 5)])
        ask(
            "topn_filtered", f"TopN(f, Row(g={TOPN_SRC_ROW}), n=4)",
            [topn_expect(ref["topn_src"], 4)],
        )
        ask("sum", "Sum(field=v)",
            [{"value": int(v_vals.sum()), "count": int(v_vals.size)}])
        vmin, vmax = int(v_vals.min()), int(v_vals.max())
        ask("min", "Min(field=v)",
            [{"value": vmin, "count": int((v_vals == vmin).sum())}])
        ask("max", "Max(field=v)",
            [{"value": vmax, "count": int((v_vals == vmax).sum())}])
        ask(
            "sum_filtered", f"Sum(Row(f={SUM_FILTER_ROW}), field=v)",
            [{"value": int(ref["v_sum_filt"]), "count": int(ref["v_cnt_filt"])}],
        )
        lo, hi = V_BETWEEN
        ask("range_gt", f"Count(Row(v > {V_GT}))", [int((v_vals > V_GT).sum())])
        ask(
            "range_between", f"Count(Row(v >< [{lo}, {hi}]))",
            [int(((v_vals >= lo) & (v_vals <= hi)).sum())],
        )
        ask("groupby2", "GroupBy(Rows(f), Rows(g))",
            [group_expect(("f", "g"), ref["pair"])])
        ask(
            "groupby2_filtered",
            f"GroupBy(Rows(f), Rows(g), filter=Row(h={FILTER_H_ROW}))",
            [group_expect(("f", "g"), ref["tri"][:, :, FILTER_H_ROW])],
        )
        ask("groupby3_cold", "GroupBy(Rows(f), Rows(g), Rows(h))",
            [group_expect(("f", "g", "h"), ref["tri"])])
        ask("groupby3_warm", "GroupBy(Rows(f), Rows(g), Rows(h))",
            [group_expect(("f", "g", "h"), ref["tri"])])
        ask(
            "groupby3_filtered",
            f"GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(g={FILTER_G_ROW}))",
            [group_expect(("f", "g", "h"), ref["tri_filt"])],
        )

        # -- an acknowledged write is read back -----------------------------
        # A column in g=2 and h=3 but not f=1: Set(col, f=1) adds exactly
        # one to Intersect(f=1, g=2) — which the pair table absorbs on the
        # host — and to Intersect(f=1, g=2, h=3), which no table answers:
        # that Count has to splice the dirty shard into the resident f
        # stack on the device (the mesh splice, on four chips) and sweep.
        wshard = next(s for s in range(shards) if s not in edits)
        fb, gb, hb = (field_bits(seed, wshard, x, density) for x in "fgh")
        wcol = int(np.flatnonzero(gb[2] & hb[3] & ~fb[1])[0])
        in_g = gb[:, wcol]
        for is_set in (True, False):
            verb, sign = ("Set", 1) if is_set else ("Clear", -1)
            got = cli.query(f"{verb}({wshard * SHARD_WIDTH + wcol}, f=1)")
            check(got == [True], f"{verb}() of a {'new' if is_set else 'set'} bit answered {got}")
            ref["cf"][1] += sign
            ref["pair"][1, in_g] += sign
            ref["nary"][0] += sign
            ask(f"count16_after_{verb.lower()}", body, pair_expect(specs, ref))
            ask(
                f"count_after_{verb.lower()}", "Count(Intersect(Row(f=1), Row(g=2)))",
                [int(ref["pair"][1, 2])],
            )
            ask(
                f"count_3ary_after_{verb.lower()}",
                f"Count({isect3})", [int(ref["nary"][0])],
            )

        # -- proof, from outside, that the chip did it ----------------------
        hbm = cli.get_json("/debug/hbm")
        n_dev = max(1, args.mesh_devices)
        s_pad = -(-shards // n_dev) * n_dev
        fg_bytes = 2 * s_pad * STACK_BYTES_PER_SHARD
        resident = {
            (e["field"], e["view"]): e["bytes"] for e in hbm["entries"]
        }
        say(
            f"hbm: residentBytes={hbm['residentBytes']} (f+g stacks are "
            f"{fg_bytes}) entries={ {f'{k[0]}/{k[1]}': v for k, v in resident.items()} }"
        )
        for fld in ("f", "g"):
            check(
                resident.get((fld, "standard")) == fg_bytes // 2,
                f"stack {fld} not resident at {fg_bytes // 2} bytes: {resident}",
            )
        check(hbm["residentBytes"] >= fg_bytes, "f+g stacks are not resident")

        diag = cli.get_json("/debug/diagnostics")
        per_dev = []
        for d in diag["jax"]["devices"][:n_dev]:
            ms = d.get("memory_stats")
            if expect_platform == "tpu":
                check(bool(ms), f"device {d['id']} reports no memory_stats")
            per_dev.append(ms["bytes_in_use"] if ms else None)
        say(f"device memory bytes_in_use: {per_dev} "
            f"(ledger {hbm['residentBytes']} over {n_dev} device(s))")
        if expect_platform == "tpu":
            share = hbm["residentBytes"] / n_dev
            for i, b in enumerate(per_dev):
                check(
                    0.9 * share <= b <= 1.6 * share + (512 << 20),
                    f"device {i} holds {b} bytes, expected about {share:.0f} "
                    f"(1/{n_dev} of the resident stacks)",
                )

        progs = cli.get_json("/debug/programs")
        by_kind: dict[str, list] = {}  # kind -> [compiles, launches, seconds]
        for e in progs["entries"]:
            k = by_kind.setdefault(e["kind"], [0, 0, 0.0])
            k[0] += e["compiles"]
            k[1] += e["launches"]
            k[2] += e["compileSeconds"]
        say("programs: " + ", ".join(
            f"{k}(c{v[0]}/l{v[1]}/{v[2]:.1f}s)" for k, v in sorted(by_kind.items())
        ))
        compile_seconds = sum(v[2] for v in by_kind.values())
        say(f"programs: {progs['programs']} programs, {progs['compiles']} compiles "
            f"({compile_seconds:.1f}s summed compileSeconds), "
            f"{progs['launches']} launches, {progs['recompiles']} recompiles")
        # Behind the batcher a lone Count rides count_batch; without one
        # it would be `count`. Either is the generic scan program.
        for kinds in (
            ("pair_stats",), ("count_batch", "count"), ("vec",),
            ("topn_plain",), ("topn_src",), ("bsi_sum",), ("bsi_min",),
            ("bsi_max",), ("groupby",), ("group_tile_pershard",),
            ("group_tile",),
        ):
            c = sum(by_kind.get(k, (0, 0, 0.0))[0] for k in kinds)
            n = sum(by_kind.get(k, (0, 0, 0.0))[1] for k in kinds)
            check(
                c >= 1 and n >= 1,
                f"program kind {'/'.join(kinds)}: {c} compiles, {n} launches",
            )

        metrics = cli.request("GET", "/metrics").decode()
        fallbacks = metric_series(metrics, "device_fallback_total")
        warm_failures = metric_series(metrics, "stack_sparse_warm_failures_total")
        launches = metric_series(metrics, "device_launches_total")
        say(f"metrics: device_fallback_total={fallbacks or 0} "
            f"stack_sparse_warm_failures_total={warm_failures or 0} "
            f"device_launches_total={int(sum(launches.values()))}")
        # Which wire shipped the stacks (ops/sparse.py): information, not
        # a verdict — a build that outran the background warm ships dense.
        stack = {
            name: int(sum(metric_series(metrics, name).values()))
            for name in (
                "stack_sparse_uploads_total", "stack_sparse_wire_bytes_total",
                "stack_sparse_dense_bytes_total", "stack_container_chunks_total",
                "stack_sparse_not_warm_total", "stack_container_not_warm_total",
                "stack_incremental_updates_total", "stack_full_rebuilds_total",
            )
        }
        say("upload: " + " ".join(f"{k}={v}" for k, v in stack.items()))
        # The Set and the Clear each reached the device as a dirty-shard
        # splice of the resident stack, never as a rebuild of it.
        check(
            stack["stack_incremental_updates_total"] >= 2
            and stack["stack_full_rebuilds_total"] == 0,
            f"write epochs did not splice: {stack}",
        )
        check(not any(fallbacks.values()), f"device fallbacks counted: {fallbacks}")
        check(not any(warm_failures.values()),
              f"upload programs failed to compile: {warm_failures}")
        check(sum(launches.values()) > 0, "no device launch was counted")
        if cache_dir:
            n_cached = len(os.listdir(cache_dir))
            say(f"compile cache: {n_cached} entries in {cache_dir}")
            check(n_cached > 0, f"compile cache {cache_dir} is empty after the run")

        # -- stop: SIGINT is the server's clean shutdown --------------------
        t0 = time.perf_counter()
        srv.send_signal(signal.SIGINT)
        try:
            rc = srv.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("server did not stop within 120s of SIGINT:\n"
                               + log_tail(log_path)) from None
        check(rc == 0, f"server exited with code {rc} on SIGINT:\n" + log_tail(log_path))
        say(f"server stopped cleanly in {time.perf_counter() - t0:.1f}s")
        say(
            f"seconds: load={t_load:.1f} first_answer={timings['count16_cold']:.1f} "
            f"warm_answer={timings['count16_warm']:.4f} "
            f"3ary_cold={timings['count_3ary_cold']:.1f} "
            f"3ary_warm={timings['count_3ary_warm']:.4f} "
            f"groupby3_cold={timings['groupby3_cold']:.1f} "
            f"groupby3_warm={timings['groupby3_warm']:.4f} "
            f"compile_sum={compile_seconds:.1f}"
        )
        return device
    finally:
        if pool is not None:
            pool.terminate()
            pool.join()
        if srv.poll() is None:
            srv.kill()
            srv.wait()
        if args.server_log:
            os.makedirs(os.path.dirname(args.server_log) or ".", exist_ok=True)
            shutil.copyfile(log_path, args.server_log)
        if sys.exc_info()[0] is not None:
            print("server log, last lines:\n" + log_tail(log_path),
                  file=sys.stderr, flush=True)
        shutil.rmtree(work, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--shards", type=int, default=SHARDS)
    ap.add_argument("--density", type=float, default=DENSITY,
                    help="the only cut a time limit may force; printed")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="start the server with PILOSA_TPU_MESH_DEVICES=N")
    ap.add_argument("--workers", type=int,
                    default=max(2, min(8, (os.cpu_count() or 4) - 4)),
                    help="load-generator processes")
    ap.add_argument("--rehearse", default=None, metavar="PLATFORM",
                    help="walk the phases on this JAX platform; never exits 0")
    ap.add_argument("--server-log", default=None, metavar="PATH",
                    help="copy the server child's log here when the run ends")
    args = ap.parse_args()
    t0 = time.perf_counter()
    device = run(args)
    say(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.1f}s")
    if args.rehearse:
        say(f"rehearsal on {args.rehearse}: not a chip result, exiting 3")
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
