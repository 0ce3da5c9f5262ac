"""run.py end to end on the CPU at a tiny size, once for every cell of
BENCHMARK.json and for the cells of tests/added/ (two call shapes in one
mix; a field that names its draw), which run from a copy of the benchmark
that holds the added files: a rehearsal can never print a result line; the control
comes out not correct; and with a fault planted under the timed path the
rest of a run sees `correct` come out false.

These skip the harness's look for a chip (`--rehearse cpu`) and nothing
else: server child, published data directory, generator processes, warm-up,
window, judging and metric readers are the real ones."""

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import BENCH, BENCH_DIR, CELLS, OWN_CELLS, REPO, data_file
from harness import plugins, traffic

MESH_CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] > 1]
FAULTS = os.path.join(BENCH_DIR, "tests", "faults")


def shapes_of(cell):
    traffic_name = next(w["traffic"] for w in BENCH["workloads"] if w["name"] == cell)
    mix = traffic.load_mix(data_file("traffic", traffic_name + ".json"))
    return list(plugins.groups_by_shape(mix))


SUM_CELLS = [c for c in CELLS if "sum" in shapes_of(c)]


def int_values_expected(cell, shards=5):
    """(least, most) int values the loader ships for the cell's
    configuration at `shards` shards, by each int field's draw parameters."""
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    path = next(c["file"] for c in BENCH["configs"] if c["name"] == entry["config"])
    with open(data_file("configs", os.path.basename(path))) as f:
        config = json.load(f)
    least = most = 0.0
    for spec in config["fields"].values():
        if spec["type"] != "int":
            continue
        if "values_per_shard" in spec:      # sparse_int: less those drawn twice
            n = shards * spec["values_per_shard"]
            least, most = least + 0.98 * n, most + n
        else:                               # dense_int: a share of the columns
            n = shards * config["shard_width"] * spec["density"]
            least, most = least + 0.95 * n, most + 1.05 * n
    return least, most


def judged_by_shape(done):
    """{shape: {"requests", "wrong", "failed"}} as the run printed it."""
    return {
        m.group(1): json.loads(m.group(2))
        for m in re.finditer(r"^judged, shape '(\w+)': (\{.*\})$", done.stderr, re.M)
    }


def rehearse(root, cell, tmp_path, *extra, seconds="2", trace="0"):
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"),
           "--workload", cell, "--seed", str(2**31 + 1234), "--seconds", seconds,
           "--trace", trace, "--rehearse", "cpu", "--shards", "5",
           "--data-root", str(tmp_path / "bench_data"), *extra]
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    done = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=600)
    verdict = None
    for line in done.stderr.splitlines():
        if line.startswith("rehearsal result"):
            verdict = json.loads(line.split(": ", 1)[1])
    return done, verdict


def assert_no_result_line(done):
    """Nothing on standard output, so no line a driver could read as a
    result, let alone one under a device metric's name."""
    assert done.returncode == 3, done.stderr[-3000:]
    assert done.stdout.strip() == ""
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert f'"{m["name"]}"' not in done.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_prints_no_result(cell, tmp_path, checkout_of):
    done, verdict = rehearse(checkout_of(cell), cell, tmp_path)
    assert_no_result_line(done)
    assert verdict is not None and verdict["correct"] is True, done.stderr[-3000:]
    assert verdict["attempted"] > 0 and verdict["failed"] == 0
    assert "compared: wrong_answers=0(limit 0)" in done.stderr
    # Every shape of the cell's mix was judged, and the run says how much.
    by_shape = judged_by_shape(done)
    assert sorted(by_shape) == sorted(shapes_of(cell))
    assert all(j["requests"] > 0 and j["wrong"] == 0 for j in by_shape.values())
    assert sum(j["requests"] for j in by_shape.values()) == verdict["attempted"]
    # The loader shipped what each int field's draw gives: 50 values a shard
    # by the default draw, a 64th of the columns where the field names
    # `dense_int`.
    shipped = int(re.search(r"import requests of (\d+) int values", done.stderr).group(1))
    least, most = int_values_expected(cell)
    assert least <= shipped <= most, (shipped, least, most)
    # The second run of the seed starts from the published directory, and a
    # traced run reads its per-layer metrics without a result line either.
    done, verdict = rehearse(checkout_of(cell), cell, tmp_path, trace="1")
    assert_no_result_line(done)
    assert "published directory found" in done.stderr
    assert verdict["correct"] is True, done.stderr[-3000:]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, tmp_path, checkout_of):
    done, verdict = rehearse(checkout_of(cell), cell, tmp_path, "--control", "drop_shard")
    assert_no_result_line(done)
    assert verdict is not None and verdict["correct"] is False
    assert verdict["failed"] > 0
    # Not correct for each shape of the mix, not for one of them alone.
    by_shape = judged_by_shape(done)
    assert sorted(by_shape) == sorted(shapes_of(cell))
    assert all(j["wrong"] > 0 for j in by_shape.values()), by_shape


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(cell, tmp_path, checkout_of):
    done, verdict = rehearse(
        checkout_of(cell), cell, tmp_path, "--launcher", os.path.join(FAULTS, "alter_answer.py")
    )
    assert_no_result_line(done)
    assert verdict is not None and verdict["correct"] is False
    assert 0 < verdict["failed"] < verdict["attempted"]


@pytest.mark.parametrize("cell", SUM_CELLS)
def test_altered_sum_is_not_correct(cell, tmp_path, checkout_of):
    """A value altered where `bsi_sum` returns it: the Sums come out wrong,
    a third of them, and nothing else does."""
    done, verdict = rehearse(
        checkout_of(cell), cell, tmp_path, "--launcher", os.path.join(FAULTS, "alter_sum.py")
    )
    assert_no_result_line(done)
    assert verdict is not None and verdict["correct"] is False
    by_shape = judged_by_shape(done)
    assert 0 < by_shape["sum"]["wrong"] < by_shape["sum"]["requests"]
    assert verdict["failed"] == by_shape["sum"]["wrong"]
    assert all(j["wrong"] == 0 for shape, j in by_shape.items() if shape != "sum")
    assert "worst_abs_count_error=1(limit 0)" in done.stderr


@pytest.mark.parametrize("cell", MESH_CELLS)
def test_exchange_left_out_is_not_correct(cell, tmp_path, checkout_of):
    done, verdict = rehearse(
        checkout_of(cell), cell, tmp_path, "--launcher", os.path.join(FAULTS, "skip_exchange.py")
    )
    # A child that cannot serve at all has failed too: either the run gives
    # no result, or it gives one that is not correct.
    assert done.stdout.strip() == ""
    assert done.returncode in (1, 3)
    if verdict is not None:
        assert verdict["correct"] is False


def test_what_is_added_edits_nothing(checkout_of, tmp_path):
    """The copy the added cells run from holds every file of benchmark/ as
    it is here, and its BENCHMARK.json every entry of ours; an added file
    or entry that is there already is refused."""
    import filecmp

    import overlay

    root = checkout_of(SUM_CELLS[0])
    for at, _, files in os.walk(BENCH_DIR):
        if "__pycache__" in at:
            continue
        for name in files:
            here = os.path.join(at, name)
            there = os.path.join(root, "benchmark", os.path.relpath(here, BENCH_DIR))
            assert filecmp.cmp(here, there, shallow=False), here
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        assert json.load(f) == BENCH
    with pytest.raises(ValueError, match="there already"):
        overlay.merged(BENCH, overlay.added_entries())
    # An "added" file in the place of one of ours: the build refuses it.
    edit = tmp_path / "added"
    (edit / "traffic").mkdir(parents=True)
    (edit / "traffic" / "count3-c16.json").write_text("{}")
    (edit / overlay.ENTRIES).write_text("{}")
    with pytest.raises(ValueError, match="there already"):
        overlay.build(str(tmp_path / "copy"), added=str(edit))


def test_outside_a_checkout_there_is_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` the program is missing: exit non-zero, no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", OWN_CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""
