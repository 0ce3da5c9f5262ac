"""A served process is one process: after a bulk load of an int field
through import-roaring and a page of the "Transportation" queries, a server
started through `pilosa_tpu.cli server` has no child process, and SIGINT
ends it with exit code 0 (a non-daemon thread left behind would hold it).

A benchmark run kills its server child with SIGKILL; a worker process the
server had started would outlive that kill and every later run could be
answered by it (what PR 29 was refused for). The device executor runs here
on JAX's CPU devices, through the normal command line.
"""

import glob
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "benchmark")
for p in (REPO, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import datagen, plugins  # noqa: E402


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(port, method, path, body=None, ctype="application/json", timeout=120):
    r = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body, method=method,
        headers={"Content-Type": ctype},
    )
    with urllib.request.urlopen(r, timeout=timeout) as resp:
        return resp.read()


def children_of(pid: int) -> list[str]:
    """Pids of the child processes of every thread of `pid`."""
    out = []
    for path in glob.glob(f"/proc/{pid}/task/*/children"):
        with open(path) as f:
            out += f.read().split()
    return out


@pytest.fixture
def server(tmp_path):
    port = free_port()
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, as on a plain host
    log = open(tmp_path / "server.log", "wb")
    proc = subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.cli", "server",
         "-d", str(tmp_path / "data"), "-b", f"127.0.0.1:{port}"],
        env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
        # pytest's own parent may have handed SIGINT down ignored.
        preexec_fn=lambda: signal.signal(signal.SIGINT, signal.SIG_DFL),
    )
    deadline = time.monotonic() + 120
    while True:
        if proc.poll() is not None or time.monotonic() > deadline:
            proc.kill()
            pytest.fail("server did not come up:\n"
                        + (tmp_path / "server.log").read_text()[-2000:])
        try:
            http(port, "GET", "/status", timeout=2)
            break
        except (urllib.error.URLError, OSError):
            time.sleep(0.2)
    yield proc, port, tmp_path / "server.log"
    if proc.poll() is None:
        proc.kill()
    proc.wait()
    log.close()


def test_load_page_and_stop_leave_one_process(server):
    proc, port, log = server
    if not os.path.exists(f"/proc/{proc.pid}/task"):
        pytest.skip("no /proc/<pid>/task here")
    with open(os.path.join(BENCH_DIR, "configs", "taxi-1chip.json")) as f:
        config = dict(json.load(f), shards=1)
    shape = plugins.load("shapes", "taxi_page")
    index = config["index"]
    http(port, "POST", f"/index/{index}", b"{}")
    data = datagen.ShardData(config, 7, 0)
    for name, spec in config["fields"].items():
        http(port, "POST", f"/index/{index}/field/{name}",
             json.dumps(plugins.draw_of(config, name).options(spec)).encode())
        http(port, "POST", f"/index/{index}/field/{name}/import-roaring/0",
             datagen.roaring_body(data.bits(name)), "application/x-protobuf")
    assert children_of(proc.pid) == []
    page = shape.render(shape.page(config))
    for _ in range(2):
        results = json.loads(http(port, "POST", f"/index/{index}/query", page))
        assert len(results["results"]) == 13
        sums = results["results"][1:11]
        assert sum(s["count"] for s in sums) == config["shard_width"]
    metrics = http(port, "GET", "/metrics").decode()
    assert 'pilosa_import_roaring_seconds_count{view_kind="bsi"} 1' in metrics
    assert 'pilosa_query_call_seconds_count{call="Sum"} 20' in metrics
    fallen = [l for l in metrics.splitlines()
              if l.startswith("pilosa_device_fallback_total") and not l.endswith(" 0")]
    assert fallen == []
    assert children_of(proc.pid) == []
    proc.send_signal(signal.SIGINT)
    assert proc.wait(timeout=120) == 0
    said = log.read_text()
    assert "objects frozen after the holder opened" in said
    assert "holder closed in" in said
    assert "; import_roaring_seconds bsi=" in said
