"""The traffic generator: a client's requests are a function of (seed,
client, stream) and of nothing else."""

import json
import os

import pytest

from harness import traffic

CONFIG = {"fields": {
    "f": {"type": "set", "rows": 8}, "g": {"type": "set", "rows": 8},
    "h": {"type": "set", "rows": 4}, "v": {"type": "int"},
}}
TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC_DIR) if f.endswith(".json"))


def first_group(name):
    return traffic.load_mix(os.path.join(TRAFFIC_DIR, name + ".json"))["groups"][0]


def take(stream, n):
    return [stream.next() for _ in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_per_seed_and_client(name):
    mix = first_group(name)
    seed = 2**31 + 4242
    a = take(traffic.RequestStream(mix, CONFIG, seed, client=3), 700)
    b = take(traffic.RequestStream(mix, CONFIG, seed, client=3), 700)
    assert a == b
    other_client = take(traffic.RequestStream(mix, CONFIG, seed, client=4), 700)
    other_seed = take(traffic.RequestStream(mix, CONFIG, seed + 1, client=3), 700)
    other_stream = take(traffic.RequestStream(mix, CONFIG, seed, 3, stream=1), 700)
    assert a != other_client and a != other_seed and a != other_stream


@pytest.mark.parametrize("name", MIXES)
def test_requests_follow_the_mix(name):
    mix = traffic.load_mix(os.path.join(TRAFFIC_DIR, name + ".json"))
    groups = traffic.client_groups(mix)
    assert len(groups) == sum(g["clients"] for g in mix["groups"])
    for client in {0, len(groups) - 1}:
        group = groups[client]
        reqs = take(traffic.RequestStream(group, CONFIG, 1, client), 600)
        sets = [tuple(s) for s in group["operand_sets"]]
        seen_verbs = set()
        for body, calls in reqs:
            assert len(calls) == group["calls_per_request"]
            assert body == traffic.render(calls)
            assert body.count(b"Count(") == len(calls)
            for verb, leaves in calls:
                seen_verbs.add(verb)
                assert tuple(f for f, _ in leaves) in sets
                for f, r in leaves:
                    assert 0 <= r < CONFIG["fields"][f]["rows"]
        assert seen_verbs == set(group["verbs"])


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
    s = traffic.RequestStream(mix, CONFIG, 9, client=1, stream=2, verbs=["Xor"])
    assert {v for _, calls in take(s, 50) for v, _ in calls} == {"Xor"}
    # A client whose group has none of the verbs asked for sits the round out.
    assert traffic.RequestStream(mix, CONFIG, 9, 1, 2, verbs=["TopN"]).verbs == []


def test_mix_files_are_plain_data():
    for name in MIXES:
        with open(os.path.join(TRAFFIC_DIR, name + ".json")) as f:
            mix = json.load(f)
        for group in mix["groups"]:
            assert group["loop"] == "closed"
            assert isinstance(group["operand_sets"], list) and group["verbs"]


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
