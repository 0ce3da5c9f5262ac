"""The seed-keyed data directory.

Data is a function of (configuration, seed). The first run of a pair in a
checkout loads it over HTTP into a server child started on the host path
(`--executor cpu`: no device is needed to write fragments), stops that
child gracefully (`holder.close()`), and publishes the directory by an
atomic rename, with the reference's intersection tables beside it. Every
later run of that pair starts the measured server on the published
directory: what a restarted node does.

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

from . import datagen, reference
from .server import REPO, BenchFailure, Server

#: Published directories kept in a checkout, newest first by last use. A
#: check's two sets go through the same seeds in the same order, so fewer
#: than a set's seeds would evict each just before it is used again.
KEEP = 8
MARKER = "PUBLISHED.json"
TABLES = "reference_tables.npz"


def data_root(environ=os.environ) -> str:
    base = environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(REPO, ".jax_cache")
    return os.path.join(base, "bench_data")


def data_key(config: dict, seed: int) -> str:
    """Names the data and nothing else: index, shards, width, fields, the
    seed and the generator's source. Configurations that differ only in
    server options share a directory."""
    with open(datagen.__file__, "rb") as f:
        source = f.read()
    what = json.dumps(
        [config["index"], config["shards"], config["shard_width"],
         config["fields"]], sort_keys=True,
    ).encode()
    digest = hashlib.sha256(source + b"\0" + what).hexdigest()[:10]
    return f"{config['index']}-{config['shards']}-s{seed}-{digest}"


def _load_shards(args: tuple) -> dict:
    """Pool worker: make a run of shards from the seed, POST each set
    field's bits to import-roaring/{shard}, and return each shard's
    intersection tables."""
    import http.client

    config, seed, port, shards = args
    conn = http.client.HTTPConnection("localhost", port, timeout=300)
    fields = datagen.set_fields(config)
    tables: dict = {}
    post_seconds = 0.0
    for shard in shards:
        words = {}
        for fld in fields:
            bits = datagen.field_bits(config, seed, shard, fld)
            body = datagen.roaring_body(bits)
            t0 = time.perf_counter()
            conn.request(
                "POST",
                f"/index/{config['index']}/field/{fld}/import-roaring/{shard}",
                body, {"Content-Type": "application/x-protobuf"},
            )
            resp = conn.getresponse()
            text = resp.read()
            post_seconds += time.perf_counter() - t0
            if resp.status != 200:
                raise BenchFailure(
                    f"import-roaring {fld}/{shard}: HTTP {resp.status}: {text[:200]!r}"
                )
            words[fld] = datagen.pack64(bits)
        tables[shard] = reference.shard_tables(words, fields)
    conn.close()
    return {"tables": tables, "post_seconds": post_seconds}


def _load(config: dict, seed: int, srv: Server, workers: int, say) -> dict:
    index, shards = config["index"], config["shards"]
    srv.post_json(f"/index/{index}", {})
    for name, spec in config["fields"].items():
        if spec["type"] == "int":
            srv.post_json(
                f"/index/{index}/field/{name}",
                {"options": {"type": "int", "min": spec["min"], "max": spec["max"]}},
            )
        else:
            srv.post_json(f"/index/{index}/field/{name}", {})
    t0 = time.perf_counter()
    n_workers = max(1, min(workers, shards))
    chunk = max(1, min(8, shards // (n_workers * 4) or 1))
    tasks = [
        (config, seed, srv.port, list(range(s, min(s + chunk, shards))))
        for s in range(0, shards, chunk)
    ]
    tables: dict = {}
    post_seconds = 0.0
    pool = multiprocessing.get_context("spawn").Pool(n_workers)
    try:
        for part in pool.imap_unordered(_load_shards, tasks):
            if not srv.alive():
                raise BenchFailure("server died during the load:\n" + srv.log_tail())
            post_seconds += part["post_seconds"]
            tables.update(part["tables"])
        pool.close()
        pool.join()
    finally:
        pool.terminate()
        pool.join()
    t_sets = time.perf_counter() - t0
    n_values = 0
    for name in datagen.int_fields(config):
        cols, vals = [], []
        for shard in range(shards):
            c, v = datagen.int_values(config, seed, shard, name)
            cols.append(c + shard * config["shard_width"])
            vals.append(v)
        cols, vals = np.concatenate(cols), np.concatenate(vals)
        srv.post_json(
            f"/index/{index}/field/{name}/import",
            {"columnIDs": cols.tolist(), "values": vals.tolist()},
        )
        n_values += int(cols.size)
    say(
        f"load: {time.perf_counter() - t0:.1f}s ({len(tasks)} tasks, "
        f"{shards * len(datagen.set_fields(config))} import-roaring requests in "
        f"{t_sets:.1f}s over {n_workers} workers, {post_seconds:.1f}s summed "
        f"server time; {n_values} int values)"
    )
    return reference.stack_tables([tables[s] for s in range(shards)])


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


def ensure(config: dict, seed: int, work_dir: str, say, root: str | None = None,
           workers: int | None = None, extra_env: dict | None = None):
    """(data directory, intersection tables) of (config, seed):
    published already, or loaded and published now."""
    root = root or data_root()
    os.makedirs(root, exist_ok=True)
    final = os.path.join(root, data_key(config, seed))
    marker = os.path.join(final, MARKER)
    if os.path.exists(marker):
        os.utime(marker)
        say(f"data: published directory found: {final}")
        return (os.path.join(final, "data"),
                reference.load_tables(os.path.join(final, TABLES)))
    t0 = time.perf_counter()
    tmp = os.path.join(root, f".tmp-{data_key(config, seed)}-{os.getpid()}")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    if workers is None:
        workers = max(2, min(8, (os.cpu_count() or 4) - 4))
    srv = Server(os.path.join(tmp, "data"), work_dir, {}, executor="cpu",
                 extra_env=extra_env, tag="loader")
    try:
        up = srv.wait_up()
        say(f"data: loader child (host path) up in {up:.1f}s")
        tables = _load(config, seed, srv, workers, say)
        stop = srv.stop_gracefully()
        say(f"data: loader child stopped gracefully in {stop:.1f}s")
    except BaseException:
        srv.kill()
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    reference.save_tables(os.path.join(tmp, TABLES), tables)
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
            reference.load_tables(os.path.join(final, TABLES)))
