"""The reduction from a trace to busy time, program times, idle gaps and a
breakdown: on hand-made events whose answer is known, and on a small cut
of a trace recorded on a v5e (tests/data/: `trace_reduce.extract`'s lists,
the first 120 events of each device line)."""

import glob
import json
import os

import pytest

from harness import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
US = 1000


def hand_trace():
    """One chip, 1,000 us. Two programs of 100 us, each a `while` of 100 us
    holding two fusions of 40 us: busy is 200 us, not 360. Gaps: 100 us
    before the first program under `pilosa.count_batch`, 400 us between the
    two with no annotation, 300 us after the second."""
    ops, modules = [], []
    for start in (100 * US, 600 * US):
        modules.append(["jit_body(1)", start, 100 * US])
        ops.append(["while.3", start, 100 * US])
        ops.append(["fusion.8", start + 5 * US, 40 * US])
        ops.append(["fusion.9", start + 50 * US, 40 * US])
    return {
        "devices": {"0": {"ops": ops, "modules": modules}},
        "host": [["pilosa.count_batch", 0, 150 * US]],
        "extent": [0, 1000 * US],
    }


def test_hand_made_trace():
    r = trace_reduce.reduce(hand_trace())
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx(200e-6)
    assert r["busy_s_max"] == pytest.approx(200e-6)
    assert r["devices"]["0"]["modules"]["jit_body(1)"] == [2, pytest.approx(200e-6)]
    ops = dict(map(tuple, r["breakdown"]["device_ops"]))
    # Each operation without what is nested in it: the whiles keep 20 us each.
    assert ops["while.3"] == pytest.approx(40e-6)
    assert ops["fusion.8"] == pytest.approx(80e-6)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    gaps = dict(map(tuple, r["breakdown"]["idle_gaps"]))
    assert gaps["pilosa.count_batch"] == pytest.approx(100e-6)
    assert gaps["_no_annotation_"] == pytest.approx(700e-6)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_several_chips_average_and_busiest():
    ex = hand_trace()
    ex["devices"]["1"] = {"ops": [["fusion.1", 0, 500 * US]], "modules": []}
    r = trace_reduce.reduce(ex)
    assert r["n_devices"] == 2
    assert r["busy_s"] == pytest.approx((200e-6 + 500e-6) / 2)
    assert r["busy_s_max"] == pytest.approx(500e-6)


def test_no_device_plane_reads_nothing():
    r = trace_reduce.reduce({"devices": {}, "host": [], "extent": [0, 10]})
    assert r["n_devices"] == 0 and r["busy_s"] == 0.0
    assert r["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_names_fit_a_breakdown():
    name = trace_reduce.short("%constant_dynamic-slice_fusion.8 = u32[954,1,32768]{2,1,0:T(1,128)}")
    assert len(name) <= 64 and " " not in name and "," not in name and "/" not in name


RECORDED = sorted(glob.glob(os.path.join(DATA, "trace_sample_*.json")))


@pytest.mark.parametrize("path", RECORDED)
def test_recorded_trace(path):
    """What holds of any trace of a TPU: device planes found, busy time
    above nought and within the window, programs named, gaps and busy time
    adding up to the window on the busiest chip."""
    with open(path) as f:
        ex = json.load(f)
    r = trace_reduce.reduce(ex)
    assert r["n_devices"] >= 1
    assert 0 < r["busy_s"] <= r["busy_s_max"] <= r["window_s"]
    dev = next(iter(r["devices"].values()))
    assert dev["n_ops"] > 0 and dev["modules"]
    assert 1 <= len(r["breakdown"]["device_ops"]) <= 10
    gaps = sum(s for _, s in r["breakdown"]["idle_gaps"])
    assert gaps <= r["window_s"] - r["busy_s_max"] + 1e-9
    expected = os.path.splitext(path)[0] + ".expected"
    if os.path.exists(expected):
        with open(expected) as f:
            want = json.load(f)
        assert r["busy_s"] == pytest.approx(want["busy_s"])
        assert r["window_s"] == pytest.approx(want["window_s"])


def test_there_is_a_recorded_trace():
    assert RECORDED, "benchmark/tests/data holds no recorded trace"
