"""run.py end to end on the CPU at a tiny size, once for every cell of
BENCHMARK.json: a rehearsal can never print a result line; the control
comes out not correct; and with a fault planted under the timed path the
rest of a run sees `correct` come out false.

These skip the harness's look for a chip (`--rehearse cpu`) and nothing
else: server child, published data directory, generator processes, warm-up,
window, judging and metric readers are the real ones."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, REPO

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
MESH_CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] > 1]
FAULTS = os.path.join(BENCH_DIR, "tests", "faults")


def rehearse(cell, tmp_path, *extra, seconds="2", trace="0"):
    cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"),
           "--workload", cell, "--seed", str(2**31 + 1234), "--seconds", seconds,
           "--trace", trace, "--rehearse", "cpu", "--shards", "5",
           "--data-root", str(tmp_path / "bench_data"), *extra]
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    done = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
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
def test_rehearsal_is_correct_and_prints_no_result(cell, tmp_path):
    done, verdict = rehearse(cell, tmp_path)
    assert_no_result_line(done)
    assert verdict is not None and verdict["correct"] is True, done.stderr[-3000:]
    assert verdict["attempted"] > 0 and verdict["failed"] == 0
    assert "compared: wrong_answers=0(limit 0)" in done.stderr
    # The second run of the seed starts from the published directory, and a
    # traced run reads its per-layer metrics without a result line either.
    done, verdict = rehearse(cell, tmp_path, trace="1")
    assert_no_result_line(done)
    assert "published directory found" in done.stderr
    assert verdict["correct"] is True, done.stderr[-3000:]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, tmp_path):
    done, verdict = rehearse(cell, tmp_path, "--control", "drop_shard")
    assert_no_result_line(done)
    assert verdict is not None and verdict["correct"] is False
    assert verdict["failed"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(cell, tmp_path):
    done, verdict = rehearse(
        cell, tmp_path, "--launcher", os.path.join(FAULTS, "alter_answer.py")
    )
    assert_no_result_line(done)
    assert verdict is not None and verdict["correct"] is False
    assert 0 < verdict["failed"] < verdict["attempted"]


@pytest.mark.parametrize("cell", MESH_CELLS)
def test_exchange_left_out_is_not_correct(cell, tmp_path):
    done, verdict = rehearse(
        cell, tmp_path, "--launcher", os.path.join(FAULTS, "skip_exchange.py")
    )
    # A child that cannot serve at all has failed too: either the run gives
    # no result, or it gives one that is not correct.
    assert done.stdout.strip() == ""
    assert done.returncode in (1, 3)
    if verdict is not None:
        assert verdict["correct"] is False


def test_outside_a_checkout_there_is_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` the program is missing: exit non-zero, no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""
