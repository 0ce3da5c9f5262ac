"""The per-layer metrics of PR 26: the `counter_at_start` reader on a
recorded scrape, the count programs' roofline on a reduced trace that
holds other programs too, and a CPU rehearsal of each cell that reads
every one of the new metrics from the real server's /metrics."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, REPO
from harness.server import parse_metrics

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NEW = [
    "launch_gap_ms", "plane_idle_ms", "leg_queue_wait_ms", "dispatch_ms",
    "device_wait_ms", "drain_cpu_share", "count_program_roofline",
    "holder_open_s", "stack_build_s", "program_compile_s",
]

SCRAPE = """\
# TYPE pilosa_holder_open_seconds gauge
pilosa_holder_open_seconds 21.5
pilosa_stack_build_seconds_sum{field="f"} 6.25
pilosa_stack_build_seconds_sum{field="g"} 6.5
pilosa_stack_build_seconds_count{field="f"} 1
pilosa_device_compile_seconds_sum{kind="count_batch"} 2.5
pilosa_device_compile_seconds_sum{kind="count"} 0.25
"""


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + name, os.path.join(BENCH_DIR, "readers", name + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metric_args(name):
    with open(os.path.join(BENCH_DIR, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_every_new_metric_is_declared_with_a_file():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert name in declared, name
        spec = metric_args(name)
        assert os.path.exists(
            os.path.join(BENCH_DIR, "readers", spec["reader"] + ".py")
        )
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] == NEW


def test_counter_at_start_reads_the_first_scrape():
    read = reader("counter_at_start")
    first = parse_metrics(SCRAPE)
    later = parse_metrics(SCRAPE.replace("21.5", "99"))
    ctx = {"scrapes": {"window": (first, later)}}
    assert read(ctx, "holder_open_seconds") == 21.5
    assert read(ctx, "stack_build_seconds_sum") == 12.75
    assert read(ctx, "stack_build_seconds_sum", where={"field": "g"}) == 6.5
    assert read(ctx, "device_compile_seconds_sum", scale=1000.0) == 2750.0
    # A program without the family (the parent commit): nothing to read.
    assert read(ctx, "holder_close_seconds_sum") is None
    for name in ("holder_open_s", "stack_build_s", "program_compile_s"):
        spec = metric_args(name)
        assert spec["reader"] == "counter_at_start"
        assert read(ctx, **spec["args"]) is not None


def test_count_program_roofline_counts_count_programs_only():
    spec = metric_args("count_program_roofline")
    old = metric_args("count_scan_roofline")
    assert spec["reader"] == old["reader"] == "trace_kernel_roofline"
    assert {k: spec["args"][k] for k in ("units", "operands_per_unit")} == {
        k: old["args"][k] for k in ("units", "operands_per_unit")
    }
    read = reader("trace_kernel_roofline")
    legs = 'pilosa_batch_legs_total{kind="count"} '
    ctx = {
        "trace": {"n_devices": 1, "devices": {"0": {"modules": {
            "jit_pilosa_count_batch(123)": [10, 0.5],
            "jit_pilosa_count_batch(456)": [10, 0.5],
            "jit_pilosa_topn_src(789)": [1, 3.0],
        }}}},
        "scrapes": {"trace": (parse_metrics(legs + "0"), parse_metrics(legs + "100"))},
        "config": {"shards": 954, "shard_width": 1 << 20},
        "peaks": {"hbm_bytes_per_s": 819e9},
    }
    least = 100 * 3 * 954 * (1 << 20) // 8 / 819e9
    assert read(ctx, **spec["args"]) == pytest.approx(100.0 * least / 1.0)
    # The older share takes every program for a count program.
    assert read(ctx, **old["args"]) == pytest.approx(100.0 * least / 4.0)
    # A program that names nothing (the parent commit): nothing to read.
    ctx["trace"]["devices"]["0"]["modules"] = {"jit_body(1)": [20, 1.0]}
    assert read(ctx, **spec["args"]) is None


REHEARSE = """
import json, sys
sys.argv = ["run.py"]
sys.path.insert(0, {bench!r})
import run
args = run.parse_args(["--workload", {cell!r}, "--seed", str(2**31 + 4321),
                       "--seconds", "3", "--trace", "1", "--rehearse", "cpu",
                       "--shards", "5", "--data-root", {root!r}])
result = run.run_cell(args)
print("METRICS " + json.dumps({{k: v["value"] for k, v in result["metrics"].items()}}))
"""


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_reads_every_new_metric(cell, tmp_path):
    """The real server on the CPU's devices, the real readers: every new
    metric that does not need a device trace has a value, and the six
    that were there still do. (The trace of a CPU run has no device
    plane, so the two rooflines and the idle share find nothing there;
    the test above gives the new roofline a reduced trace.) The numbers
    are a CPU's: printed by this test's child only, never a result."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    done = subprocess.run(
        [sys.executable, "-c", REHEARSE.format(
            bench=BENCH_DIR, cell=cell, root=str(tmp_path / "bench_data"))],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    lines = [l for l in done.stdout.splitlines() if l.startswith("METRICS ")]
    assert done.returncode == 0 and lines, done.stderr[-3000:]
    got = json.loads(lines[-1][len("METRICS "):])
    on_trace = {"count_program_roofline", "count_scan_roofline", "device_idle_share"}
    want = {
        m["name"] for m in BENCH["per_layer"]
        if "workloads" not in m or cell in m["workloads"]
    } - on_trace
    assert want <= set(got), sorted(want - set(got))
    assert set(NEW) - on_trace <= set(got)
    assert got["leg_queue_wait_ms"] <= got["batch_wait_ms"]
    assert 0 < got["drain_cpu_share"] <= 100.5
    assert got["holder_open_s"] > 0 and got["stack_build_s"] > 0
    assert got["program_compile_s"] > 0
