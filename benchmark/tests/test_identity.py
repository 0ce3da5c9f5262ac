"""What a cell reads must not move because the code that reads it was
rearranged. For `count3-c16` on both configurations and three seeds: the
bytes of every client's first 2,000 requests of streams 0, 1 and 2, every
field of shards 0-4, and the reference's tables at 5 shards equal those of
the tree before the shape and draw seam (PR 27's), by digests taken from
that tree (data/golden_count3-c16.json says how)."""

import hashlib
import json
import os

import numpy as np
import pytest

from conftest import BENCH_DIR, REPO
from harness import datagen, plugins, reference, traffic

with open(os.path.join(BENCH_DIR, "tests", "data", "golden_count3-c16.json")) as f:
    GOLDEN = json.load(f)["digests"]
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    CONFIGS = {c["name"]: c["file"] for c in json.load(f)["configs"]}
CASES = [(c, seed) for c in sorted(GOLDEN) for seed in sorted(GOLDEN[c])]
MIX = traffic.load_mix(os.path.join(BENCH_DIR, "traffic", "count3-c16.json"))


def config_of(name):
    with open(os.path.join(REPO, CONFIGS[name])) as f:
        return json.load(f)


def test_the_golden_file_covers_both_configurations_and_three_seeds():
    assert set(GOLDEN) == set(CONFIGS) and len(CASES) == 6
    assert any(int(seed) > 2**31 for _, seed in CASES)


@pytest.mark.parametrize("name, seed", CASES)
@pytest.mark.parametrize("stream", [0, 1, 2])
def test_request_bytes_are_the_parents(name, seed, stream):
    config = config_of(name)
    h = hashlib.sha256()
    for client, group in enumerate(traffic.client_groups(MIX)):
        s = traffic.RequestStream(group, config, int(seed), client, stream)
        for _ in range(2000):
            h.update(s.next()[0] + b"\n")
    assert h.hexdigest() == GOLDEN[name][seed]["requests"][str(stream)]


@pytest.mark.parametrize("name, seed", CASES)
def test_field_data_is_the_parents(name, seed):
    config = config_of(name)
    want = GOLDEN[name][seed]
    assert set(want["bits"]) | set(want["values"]) == set(config["fields"])
    for field in config["fields"]:
        h = hashlib.sha256()
        ship = plugins.draw_of(config, field).SHIP
        for shard in range(5):
            drawn = datagen.draw(config, int(seed), shard, field)
            if ship == "roaring":
                h.update(np.packbits(drawn).tobytes())
            else:
                cols, vals = drawn
                h.update(np.asarray(cols, np.int64).tobytes()
                         + np.asarray(vals, np.int64).tobytes())
        kind = "bits" if ship == "roaring" else "values"
        assert h.hexdigest() == want[kind][field], field


@pytest.mark.parametrize("name, seed", CASES)
def test_reference_tables_are_the_parents(name, seed):
    config = dict(config_of(name), shards=5)
    wanted = reference.needs(MIX, config)
    tables = reference.tables_for_shards(config, int(seed), range(5), wanted)
    want = GOLDEN[name][seed]["tables"]
    assert set(tables) == {"count"} and set(tables["count"]) == set(want)
    for key, table in tables["count"].items():
        a = np.asarray(table, np.int64)
        got = hashlib.sha256(str(a.shape).encode() + a.tobytes()).hexdigest()
        assert got == want[key], key
