"""The seed-keyed data directory.

Data is a function of (configuration, seed): each field's shards as its
draw gives them (benchmark/draws/). The first run of a pair in a checkout
loads it over HTTP into a server child started on the host path
(`--executor cpu`: no device is needed to write fragments), stops that
child gracefully (`holder.close()`), and publishes the directory by an
atomic rename. Every later run of that pair starts the measured server on
the published directory: what a restarted node does.

The reference's per-shard tables lie beside the data, one file a call
shape (`reference_<shape>.npz`). The loader's pool makes those the cell's
mix asks for while a shard's bits and values are in hand; a cell that
finds the data published and a table of its own missing makes that table
from the draws alone and adds it, without loading the data again.

Where it lives: `bench_data/` inside the directory the program resolves
for its compile cache (pilosa_tpu/ops/runtime.py): the one
JAX_COMPILATION_CACHE_DIR names, else `.jax_cache/` at the root of the
checkout. The rule is applied here and not imported, because importing
runtime.py imports jax.
"""

from __future__ import annotations

import hashlib
import json
import multiprocessing
import os
import shutil
import time

import numpy as np

from . import datagen, plugins, reference
from .server import REPO, BenchFailure, Server

#: Published directories kept in a checkout, newest first by last use. A
#: check's two sets go through the same seeds in the same order, so fewer
#: than a set's seeds would evict each just before it is used again.
KEEP = 8
MARKER = "PUBLISHED.json"


def data_root(environ=os.environ) -> str:
    base = environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(REPO, ".jax_cache")
    return os.path.join(base, "bench_data")


def data_key(config: dict, seed: int) -> str:
    """Names the data and nothing else: index, shards, width, fields, the
    seed, and the source of the draws its fields use and of the wire
    encoding. Configurations that differ only in server options share a
    directory."""
    draws = sorted({plugins.draw_name(config, f) for f in config["fields"]})
    with open(datagen.__file__, "rb") as f:
        source = f.read()
    source += b"".join(b"\0" + plugins.source("draws", d) for d in draws)
    what = json.dumps(
        [config["index"], config["shards"], config["shard_width"],
         config["fields"]], sort_keys=True,
    ).encode()
    digest = hashlib.sha256(source + b"\0" + what).hexdigest()[:10]
    return f"{config['index']}-{config['shards']}-s{seed}-{digest}"


def _post(conn, path: str, body: bytes, content_type: str) -> float:
    """One POST over the worker's connection; the seconds it took."""
    t0 = time.perf_counter()
    conn.request("POST", path, body, {"Content-Type": content_type})
    resp = conn.getresponse()
    text = resp.read()
    if resp.status != 200:
        raise BenchFailure(f"POST {path}: HTTP {resp.status}: {text[:200]!r}")
    return time.perf_counter() - t0


def _load_shards(args: tuple) -> dict:
    """Pool worker: draw a run of shards from the seed and ship every
    field as its draw says: bits to import-roaring/{shard}, one request a
    shard; values to import, one request for the run. Returns each shard's
    reference tables, made while its fields are in hand. With no port the
    data is published already, and only the tables are made."""
    import http.client

    config, seed, port, shards, wanted = args
    conn = (http.client.HTTPConnection("localhost", port, timeout=300)
            if port else None)
    base = f"/index/{config['index']}/field/"
    width = config["shard_width"]
    # Nothing is shipped where there is no loader child to ship to.
    ships = ({f: plugins.draw_of(config, f).SHIP for f in config["fields"]}
             if conn is not None else {})
    tables: dict = {}
    values: dict = {}
    posts = {"roaring": 0, "values": 0}
    post_seconds = 0.0
    for shard in shards:
        data = datagen.ShardData(config, seed, shard)
        for fld, ship in ships.items():
            if ship == "roaring":
                post_seconds += _post(
                    conn, f"{base}{fld}/import-roaring/{shard}",
                    datagen.roaring_body(data.bits(fld)),
                    "application/x-protobuf",
                )
                posts["roaring"] += 1
            elif ship == "values":
                cols, vals = data.values(fld)
                into = values.setdefault(fld, ([], []))
                into[0].append(np.asarray(cols, dtype=np.int64) + shard * width)
                into[1].append(np.asarray(vals, dtype=np.int64))
            else:
                raise BenchFailure(f"field {fld!r}: no way to ship {ship!r}")
        tables[shard] = reference.shard_tables(config, wanted, data)
    n_values = 0
    for fld, (cols, vals) in values.items():
        cols, vals = np.concatenate(cols), np.concatenate(vals)
        post_seconds += _post(
            conn, f"{base}{fld}/import",
            json.dumps({"columnIDs": cols.tolist(),
                        "values": vals.tolist()}).encode(),
            "application/json",
        )
        posts["values"] += 1
        n_values += int(cols.size)
    if conn is not None:
        conn.close()
    return {"tables": tables, "post_seconds": post_seconds, "posts": posts,
            "n_values": n_values}


def _over_shards(config: dict, seed: int, port: int, wanted: dict,
                 workers: int, alive=None) -> tuple[dict, dict]:
    """Run `_load_shards` over all of the index's shards in a pool.
    ({shape: {name: array[shards, ...]}}, the workers' sums)."""
    shards = config["shards"]
    n_workers = max(1, min(workers, shards))
    chunk = max(1, min(8, shards // (n_workers * 4) or 1))
    tasks = [
        (config, seed, port, list(range(s, min(s + chunk, shards))), wanted)
        for s in range(0, shards, chunk)
    ]
    tables: dict = {}
    sums = {"post_seconds": 0.0, "roaring": 0, "values": 0, "n_values": 0,
            "tasks": len(tasks), "workers": n_workers}
    pool = multiprocessing.get_context("spawn").Pool(n_workers)
    try:
        for part in pool.imap_unordered(_load_shards, tasks):
            if alive is not None and not alive():
                raise BenchFailure("server died during the load")
            sums["post_seconds"] += part["post_seconds"]
            sums["n_values"] += part["n_values"]
            for k, n in part["posts"].items():
                sums[k] += n
            tables.update(part["tables"])
        pool.close()
        pool.join()
    finally:
        pool.terminate()
        pool.join()
    stacked = {
        shape: reference.stack_tables([tables[s][shape] for s in range(shards)])
        for shape, names in wanted.items() if names
    }
    return stacked, sums


def _load(config: dict, seed: int, srv: Server, workers: int, wanted: dict,
          say) -> dict:
    index = config["index"]
    srv.post_json(f"/index/{index}", {})
    for name, spec in config["fields"].items():
        srv.post_json(f"/index/{index}/field/{name}",
                      plugins.draw_of(config, name).options(spec))
    t0 = time.perf_counter()
    try:
        tables, sums = _over_shards(config, seed, srv.port, wanted, workers,
                                    alive=srv.alive)
    except BenchFailure as e:
        raise BenchFailure(f"{e}\n" + srv.log_tail()) from None
    say(
        f"load: {time.perf_counter() - t0:.1f}s ({sums['tasks']} tasks over "
        f"{sums['workers']} workers: {sums['roaring']} import-roaring requests, "
        f"{sums['values']} import requests of {sums['n_values']} int values, "
        f"{sums['post_seconds']:.1f}s summed server time)"
    )
    return tables


def _dir_bytes(path: str) -> int:
    total = 0
    for root, _, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return total


def _evict(root: str, keep: int, say) -> None:
    entries = []
    for name in os.listdir(root):
        path = os.path.join(root, name)
        if name.startswith(".tmp-"):
            # A run that died while loading: nothing can use it.
            if time.time() - os.path.getmtime(path) > 3600:
                shutil.rmtree(path, ignore_errors=True)
            continue
        marker = os.path.join(path, MARKER)
        if os.path.exists(marker):
            entries.append((os.path.getmtime(marker), path))
    entries.sort(reverse=True)
    for _, path in entries[keep:]:
        say(f"data: evicting {os.path.basename(path)}")
        shutil.rmtree(path, ignore_errors=True)


def _tables(directory: str, config: dict, seed: int, wanted: dict,
            workers: int, say) -> dict:
    """{shape: {name: array[shards, ...]}} of what `wanted` names, read
    from the files beside the published data; a table that no cell on this
    data has asked for yet, or that another source of the shape made
    (reference.py `load_tables`), is made from the draws and kept."""
    out = {}
    for shape, names in wanted.items():
        have = reference.load_tables(directory, shape)
        missing = [n for n in names if n not in have]
        if missing:
            t0 = time.perf_counter()
            made, _ = _over_shards(config, seed, 0, {shape: missing}, workers)
            have.update(made[shape])
            reference.save_tables(directory, shape, have)
            say(f"data: reference tables {missing} of shape {shape!r} made from "
                f"the draws in {time.perf_counter() - t0:.1f}s and kept")
        out[shape] = {n: have[n] for n in names}
    return out


def ensure(config: dict, seed: int, work_dir: str, say, wanted: dict,
           root: str | None = None, workers: int | None = None,
           extra_env: dict | None = None):
    """(data directory, {shape: reference tables}) of (config, seed):
    published already, or loaded and published now. `wanted` is
    {shape: table names} (harness/reference.py `needs`)."""
    root = root or data_root()
    os.makedirs(root, exist_ok=True)
    final = os.path.join(root, data_key(config, seed))
    marker = os.path.join(final, MARKER)
    if workers is None:
        workers = max(2, min(8, (os.cpu_count() or 4) - 4))
    if os.path.exists(marker):
        os.utime(marker)
        say(f"data: published directory found: {final}")
        return (os.path.join(final, "data"),
                _tables(final, config, seed, wanted, workers, say))
    t0 = time.perf_counter()
    tmp = os.path.join(root, f".tmp-{data_key(config, seed)}-{os.getpid()}")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    srv = Server(os.path.join(tmp, "data"), work_dir, {}, executor="cpu",
                 extra_env=extra_env, tag="loader")
    try:
        up = srv.wait_up()
        say(f"data: loader child (host path) up in {up:.1f}s")
        tables = _load(config, seed, srv, workers, wanted, say)
        stop = srv.stop_gracefully()
        say(f"data: loader child stopped gracefully in {stop:.1f}s")
    except BaseException:
        srv.kill()
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    for shape, named in tables.items():
        reference.save_tables(tmp, shape, named)
    size = _dir_bytes(tmp)
    with open(os.path.join(tmp, MARKER), "w") as f:
        json.dump({"config": config["name"], "seed": seed, "bytes": size}, f)
    try:
        os.rename(tmp, final)
    except OSError:
        # Another run published the same pair meanwhile: use theirs.
        shutil.rmtree(tmp, ignore_errors=True)
    _evict(root, KEEP, say)
    secs = time.perf_counter() - t0
    say(f"data: loaded and published {final} in {secs:.1f}s, {size} bytes on disk")
    return (os.path.join(final, "data"),
            _tables(final, config, seed, wanted, workers, say))
