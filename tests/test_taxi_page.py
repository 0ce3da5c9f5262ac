"""The "Transportation" page (benchmark/shapes/taxi_page.py) through the
system's normal path, against the benchmark's plain reference.

Three shards of the configuration's five fields, drawn by the
configuration's own draws, are loaded over HTTP as the benchmark's loader
loads them (`import-roaring/{shard}`, view "", the int field as its
planes), and every one of the page's 13 calls goes through the HTTP
handler, the executor, the batcher and the device executor (on JAX's CPU
devices here). What comes back must equal the reference's answer, which
imports nothing of the program, and nothing may have left the device path.
"""

import json
import os
import sys
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "benchmark")
for p in (REPO, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import datagen, plugins, reference  # noqa: E402

from pilosa_tpu.core import Holder  # noqa: E402
from pilosa_tpu.exec import Executor  # noqa: E402
from pilosa_tpu.exec.batcher import ShardLegBatcher  # noqa: E402
from pilosa_tpu.exec.tpu import TPUBackend  # noqa: E402
from pilosa_tpu.server.api import API  # noqa: E402
from pilosa_tpu.server.http import Server  # noqa: E402
from pilosa_tpu.utils.stats import global_stats  # noqa: E402

SHARDS = 3
SEED = 20261002
SHAPE = plugins.load("shapes", "taxi_page")


def taxi_config(shards: int = SHARDS) -> dict:
    with open(os.path.join(BENCH_DIR, "configs", "taxi-1chip.json")) as f:
        return dict(json.load(f), shards=shards)


def post(srv, path: str, body: bytes, ctype="application/json"):
    r = urllib.request.Request(
        srv.uri + path, data=body, method="POST",
        headers={"Content-Type": ctype},
    )
    with urllib.request.urlopen(r) as resp:
        return json.loads(resp.read())


def load(srv, config: dict, seed: int) -> None:
    """The benchmark loader's requests, one shard after another."""
    index = config["index"]
    post(srv, f"/index/{index}", b"{}")
    for name, spec in config["fields"].items():
        post(srv, f"/index/{index}/field/{name}",
             json.dumps(plugins.draw_of(config, name).options(spec)).encode())
    for shard in range(config["shards"]):
        data = datagen.ShardData(config, seed, shard)
        for name in config["fields"]:
            assert plugins.draw_of(config, name).SHIP == "roaring"
            post(srv, f"/index/{index}/field/{name}/import-roaring/{shard}",
                 datagen.roaring_body(data.bits(name)),
                 "application/x-protobuf")


def fallbacks() -> float:
    return sum(global_stats.counter_totals("device_fallback_total").values())


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(server on the device executor with the taxi index loaded, the
    configuration, the reference's totals)."""
    config = taxi_config()
    holder = Holder(str(tmp_path_factory.mktemp("taxi") / "data")).open()
    backend = TPUBackend(holder)
    executor = Executor(holder, backend=backend)
    executor.batcher = ShardLegBatcher(backend)
    srv = Server(API(holder, executor), host="localhost", port=0).open()
    load(srv, config, SEED)
    wanted = {"taxi_page": SHAPE.tables_needed([], config)}
    tables = reference.tables_for_shards(config, SEED, range(SHARDS), wanted)
    ref = reference.Reference(config, tables)
    yield srv, config, ref
    srv.close()
    holder.close()


PAGE = SHAPE.page(taxi_config())


@pytest.mark.parametrize("at", range(len(PAGE)), ids=[SHAPE.pql(c) for c in PAGE])
def test_each_call_of_the_page_equals_the_reference(served, at):
    srv, config, ref = served
    call = PAGE[at]
    before = fallbacks()
    for _ in range(2):  # the cold answer, then the warm one (tables)
        got = post(srv, f"/index/{config['index']}/query",
                   SHAPE.render([call]))["results"]
        want = ref.answer("taxi_page", call)
        assert SHAPE.compare(got[0], want) == (True, 0), (got[0], want)
    assert fallbacks() == before


def test_the_whole_page_in_one_request(served):
    srv, config, ref = served
    before = fallbacks()
    got = post(srv, f"/index/{config['index']}/query",
               SHAPE.render(PAGE))["results"]
    assert len(got) == len(PAGE) == 13
    for call, result in zip(PAGE, got):
        assert SHAPE.compare(result, ref.answer("taxi_page", call)) == (True, 0)
    assert fallbacks() == before
    # Every call of the body was timed by itself.
    timed = global_stats.timing_totals("query_call_seconds")
    for name, at_least in (("TopN", 1), ("Sum", 10), ("GroupBy", 2)):
        assert timed[f'query_call_seconds{{call="{name}"}}'][1] >= at_least


def test_the_answers_are_not_trivial(served):
    """The reference itself: a Sum per passenger count over every column,
    and groups in more than one year."""
    _, config, ref = served
    sums = [ref.answer("taxi_page", ("sum", k)) for k in range(10)]
    assert sum(s["count"] for s in sums) == SHARDS * config["shard_width"]
    assert all(s["value"] > 3 * s["count"] for s in sums if s["count"])
    years = {g["group"][1]["rowID"]
             for g in ref.answer("taxi_page", ("groupby2", 0))}
    assert len(years) > 1
