"""The per-layer metrics: the `counter_at_start` and `counter_ratio`
readers on recorded scrapes, a named program's roofline on a reduced
trace that holds other programs too, and a CPU rehearsal of each cell
(BENCHMARK.json's, and those of tests/added/ from the copy that holds
them) that reads every metric that applies to it from the real server's
/metrics."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, BENCH_DIR, CELLS, OWN, data_file
from harness.server import parse_metrics

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
    with open(data_file("layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_every_new_metric_is_declared_with_a_file():
    declared = {m["name"]: m for m in OWN["per_layer"]}
    for name in NEW:
        assert name in declared, name
    # The tests' added metrics are declared in the copy alone.
    added = {m["name"] for m in BENCH["per_layer"]} - set(declared)
    assert added == {"table_hit_share", "sum_program_roofline"}
    for name in set(declared) | added:
        spec = metric_args(name)
        assert os.path.exists(
            os.path.join(BENCH_DIR, "readers", spec["reader"] + ".py")
        )
    # No definition file without an entry (a metric taken out goes whole).
    files = {f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "layer_metrics"))}
    assert files == set(declared)


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


@pytest.mark.parametrize("name, program, counter, rows", [
    ("count_program_roofline", "count_batch",
     'pilosa_batch_legs_total{kind="count"} ', 3),
    ("sum_program_roofline", "bsi_sum",
     'pilosa_device_launches_total{kind="bsi_sum"} ', 17),
])
def test_program_roofline_counts_its_own_programs_only(name, program, counter, rows):
    spec = metric_args(name)
    assert spec["reader"] == "trace_kernel_roofline"
    assert spec["args"]["operands_per_unit"] == rows
    read = reader("trace_kernel_roofline")
    ctx = {
        "trace": {"n_devices": 1, "devices": {"0": {"modules": {
            f"jit_pilosa_{program}(123)": [10, 0.5],
            f"jit_pilosa_{program}(456)": [10, 0.5],
            "jit_pilosa_topn_src(789)": [1, 3.0],
        }}}},
        "scrapes": {"trace": (parse_metrics(counter + "0"),
                              parse_metrics(counter + "100"))},
        "config": {"shards": 954, "shard_width": 1 << 20},
        "peaks": {"hbm_bytes_per_s": 819e9},
    }
    least = 100 * rows * 954 * (1 << 20) // 8 / 819e9
    assert read(ctx, **spec["args"]) == pytest.approx(100.0 * least / 1.0)
    # A program that names nothing (the parent commit): nothing to read.
    ctx["trace"]["devices"]["0"]["modules"] = {"jit_body(1)": [20, 1.0]}
    assert read(ctx, **spec["args"]) is None
    # Work of another kind was counted, none of this kind: nothing to read.
    ctx["trace"]["devices"]["0"]["modules"] = {f"jit_pilosa_{program}(1)": [2, 1.0]}
    other = 'pilosa_device_launches_total{kind="topn"} '
    ctx["scrapes"]["trace"] = (parse_metrics(other + "0"), parse_metrics(other + "9"))
    assert read(ctx, **spec["args"]) is None


def test_counter_ratio_needs_the_numerators_family():
    """A commit without the series has nothing to read; one that has it and
    saw no growth reads 0."""
    read = reader("counter_ratio")
    launches = 'pilosa_device_launches_total{kind="count_batch"} '
    steps = 'pilosa_batch_step_seconds_sum{step="dispatch"} '
    without = (parse_metrics(launches + "10"), parse_metrics(launches + "110"))
    for name in ("launch_gap_ms", "dispatch_ms", "device_wait_ms"):
        spec = metric_args(name)
        assert spec["reader"] == "counter_ratio"
        assert read({"scrapes": {"window": without}}, **spec["args"]) is None
    having = (parse_metrics(launches + "10\n" + steps + "1.0"),
              parse_metrics(launches + "110\n" + steps + "1.5"))
    args = metric_args("dispatch_ms")["args"]
    assert read({"scrapes": {"window": having}}, **args) == pytest.approx(5.0)
    # The family is there under another label only: it exists, and the
    # chosen series did not grow.
    other = 'pilosa_batch_step_seconds_sum{step="take"} '
    idle = (parse_metrics(launches + "10\n" + other + "1.0"),
            parse_metrics(launches + "110\n" + other + "1.5"))
    assert read({"scrapes": {"window": idle}}, **args) == 0.0
    # Nothing to divide by: nothing to read.
    still = (parse_metrics(launches + "10\n" + steps + "1.0"),
             parse_metrics(launches + "10\n" + steps + "1.5"))
    assert read({"scrapes": {"window": still}}, **args) is None


def test_table_hit_share_is_hits_over_count_groups():
    read = reader("counter_ratio")
    page = ('pilosa_pair_stats_cache_hits_total {hits}\n'
            'pilosa_batch_occupancy_count{{kind="count"}} {groups}\n'
            'pilosa_batch_occupancy_count{{kind="bsi_sum"}} {sums}\n')
    edges = (parse_metrics(page.format(hits=5, groups=10, sums=0)),
             parse_metrics(page.format(hits=104, groups=110, sums=700)))
    spec = metric_args("table_hit_share")
    assert read({"scrapes": {"window": edges}}, **spec["args"]) == pytest.approx(99.0)


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
def test_rehearsal_reads_every_new_metric(cell, tmp_path, checkout_of):
    """The real server on the CPU's devices, the real readers: every new
    metric of the cell that does not need a device trace has a value, and
    no other has. (The trace of a CPU run has no device plane, so the
    rooflines and the idle share find nothing there; the test above gives
    each roofline a reduced trace.) The numbers
    are a CPU's: printed by this test's child only, never a result."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    root = checkout_of(cell)
    done = subprocess.run(
        [sys.executable, "-c", REHEARSE.format(
            bench=os.path.join(root, "benchmark"), cell=cell,
            root=str(tmp_path / "bench_data"))],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    lines = [l for l in done.stdout.splitlines() if l.startswith("METRICS ")]
    assert done.returncode == 0 and lines, done.stderr[-3000:]
    got = json.loads(lines[-1][len("METRICS "):])
    on_trace = {"count_program_roofline", "sum_program_roofline",
                "device_idle_share"}
    applies = {
        m["name"] for m in BENCH["per_layer"]
        if "workloads" not in m or cell in m["workloads"]
    }
    assert set(got) == applies - on_trace, sorted(applies ^ set(got))
    if "batch_wait_ms" in applies:
        assert set(NEW) - on_trace <= set(got)
        assert got["leg_queue_wait_ms"] <= got["batch_wait_ms"]
        assert 0 < got["drain_cpu_share"] <= 100.5
    if "table_hit_share" in applies:
        assert 99.0 < got["table_hit_share"] <= 100.0
    assert got["holder_open_s"] > 0 and got["stack_build_s"] > 0
    assert got["program_compile_s"] > 0
