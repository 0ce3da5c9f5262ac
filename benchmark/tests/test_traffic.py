"""The traffic generator: a client's requests are a function of (seed,
client, stream) and of nothing else."""

import json
import os

import pytest

from conftest import BENCH, data_file
from harness import plugins, traffic

count = plugins.load("shapes", "count")

CONFIG = {"fields": {
    "f": {"type": "set", "rows": 8}, "g": {"type": "set", "rows": 8},
    "h": {"type": "set", "rows": 4}, "v": {"type": "int"},
}}
#: The benchmark's mixes and the tests' added one (two shapes side by side).
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})


def load(name):
    return traffic.load_mix(data_file("traffic", name + ".json"))


def first_group(name):
    return load(name)["groups"][0]


#: (mix, index of the group, first client of the group) for every group of
#: every mix: a mix of two shapes is checked shape by shape.
GROUPS = [
    (name, i, sum(int(g["clients"]) for g in load(name)["groups"][:i]))
    for name in MIXES for i in range(len(load(name)["groups"]))
]


def take(stream, n):
    return [stream.next() for _ in range(n)]


@pytest.mark.parametrize("name, index, client", GROUPS)
def test_deterministic_per_seed_and_client(name, index, client):
    mix = load(name)["groups"][index]
    seed = 2**31 + 4242
    a = take(traffic.RequestStream(mix, CONFIG, seed, client), 700)
    b = take(traffic.RequestStream(mix, CONFIG, seed, client), 700)
    assert a == b
    other_client = take(traffic.RequestStream(mix, CONFIG, seed, client + 1), 700)
    other_seed = take(traffic.RequestStream(mix, CONFIG, seed + 1, client), 700)
    other_stream = take(traffic.RequestStream(mix, CONFIG, seed, client, stream=1), 700)
    assert a != other_client and a != other_seed and a != other_stream


def test_a_mix_of_two_shapes_is_deterministic_client_by_client():
    """Every client of a mix whose groups differ in shape: the same
    requests again, each body one shape's rendering, and no two clients
    with the same requests."""
    mix = load("count-sum")
    groups = traffic.client_groups(mix)
    assert {plugins.shape_name(g) for g in groups} == {"count", "sum"}
    seed = 2**31 + 77
    first = [
        [body for body, _ in take(traffic.RequestStream(g, CONFIG, seed, c), 40)]
        for c, g in enumerate(groups)
    ]
    again = [
        [body for body, _ in take(traffic.RequestStream(g, CONFIG, seed, c), 40)]
        for c, g in enumerate(groups)
    ]
    assert first == again
    assert len({tuple(bodies) for bodies in first}) == len(groups)
    for g, bodies in zip(groups, first):
        opener = {"count": b"Count(", "sum": b"Sum("}[plugins.shape_name(g)]
        for body in bodies:
            assert body.count(opener) == g["calls_per_request"]
            assert body.count(b"Count(") + body.count(b"Sum(") == g["calls_per_request"]


@pytest.mark.parametrize("name", MIXES)
def test_requests_follow_the_mix(name):
    mix = load(name)
    groups = traffic.client_groups(mix)
    assert len(groups) == sum(g["clients"] for g in mix["groups"])
    for client in {0, len(groups) - 1}:
        group = groups[client]
        shape = plugins.shape_of(group)
        reqs = take(traffic.RequestStream(group, CONFIG, 1, client), 600)
        for body, calls in reqs:
            assert len(calls) == group["calls_per_request"]
            assert body == shape.render(calls)
        if plugins.shape_name(group) == "count":
            sets = [tuple(s) for s in group["operand_sets"]]
            seen_verbs = set()
            for body, calls in reqs:
                assert body.count(b"Count(") == len(calls)
                for verb, leaves in calls:
                    seen_verbs.add(verb)
                    assert tuple(f for f, _ in leaves) in sets
                    for f, r in leaves:
                        assert 0 <= r < CONFIG["fields"][f]["rows"]
            assert seen_verbs == set(group["verbs"])
        else:
            seen_rows = set()
            for body, calls in reqs:
                assert body.count(b"Sum(Row(") == len(calls)
                for f, row, v in calls:
                    assert f in group["filters"] and v in group["fields"]
                    seen_rows.add((f, row))
            assert seen_rows == {(f, r) for f in group["filters"]
                                 for r in range(CONFIG["fields"][f]["rows"])}


def test_every_seed_draws_the_same_work():
    """Seeds reorder the pool; they do not change its make-up: over many
    requests every verb and every row comes about equally often."""
    mix = first_group(MIXES[0])
    for seed in (5, 2**31 + 5):
        counts: dict = {}
        s = traffic.RequestStream(mix, CONFIG, seed, client=0)
        n = 4000
        for _ in range(n):
            for verb, leaves in s.next()[1]:
                counts[verb] = counts.get(verb, 0) + 1
        total = sum(counts.values())
        for verb in mix["verbs"]:
            assert abs(counts[verb] / total - 1 / len(mix["verbs"])) < 0.03


def test_restricted_verbs():
    mix = first_group(MIXES[0])
    s = traffic.RequestStream(count.narrowed(mix, ["Xor"]), CONFIG, 9,
                              client=1, stream=2)
    assert {v for _, calls in take(s, 50) for v, _ in calls} == {"Xor"}
    # A group that has none of the verbs asked for is left with none.
    assert count.narrowed(mix, ["TopN"])["verbs"] == []


@pytest.mark.parametrize("n", [1, 2, 4, 8, 16])
def test_the_warm_up_sends_n_different_calls_of_one_verb(n):
    mix = first_group("count3-c16")
    sent = []
    count.warm(dict(mix, warm_batch_sizes=[n]), CONFIG, 5, 0, sent.append,
               lambda what: None)
    assert len(sent) == len(mix["verbs"])
    for verb, body in zip(mix["verbs"], sent):
        calls = body.decode().split("Count(")[1:]
        assert len(calls) == len(set(calls)) == n
        assert all(c.startswith(verb + "(") for c in calls)


def test_mix_files_are_plain_data():
    for name in MIXES:
        with open(data_file("traffic", name + ".json")) as f:
            mix = json.load(f)
        for group in mix["groups"]:
            assert group["loop"] == "closed"
            assert os.path.exists(
                data_file("shapes", plugins.shape_name(group) + ".py"))


def test_the_latest_first_request_of_a_window():
    """run.py's measure of a late start: the latest first request of any
    client, from the window's start."""
    import run

    window = {"t_start": 100.0, "replies": [
        {"clients": {0: {"sent": [100.001, 100.2]}, 2: {"sent": []}}},
        {"clients": {1: {"sent": [102.81]}}},
    ]}
    assert run.generator_late_by(window) == pytest.approx(2.81)
    window["replies"][1]["clients"][1]["sent"] = [100.002]
    assert run.generator_late_by(window) == pytest.approx(0.002)
