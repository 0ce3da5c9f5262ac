"""The server child and its HTTP surface, seen from outside the process.

The child is `python -m pilosa_tpu.cli server` with its normal command
line, reached through benchmark/launcher.py, which adds nothing to the
served path but a dormant tracer thread. This side never imports jax.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
LAUNCHER = os.path.join(BENCH_DIR, "launcher.py")


class BenchFailure(Exception):
    """The run cannot give a result: exit non-zero, print no result line."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def toml_text(options: dict) -> str:
    """The configuration's server options as the TOML file the child
    reads (flat keys of pilosa_tpu/server/config.py)."""
    lines = []
    for k, v in options.items():
        if isinstance(v, bool):
            lines.append(f"{k} = {'true' if v else 'false'}")
        elif isinstance(v, (int, float)):
            lines.append(f"{k} = {v}")
        else:
            lines.append(f"{k} = {json.dumps(str(v))}")
    return "\n".join(lines) + "\n"


class Server:
    """One server child. `options` are the configuration file's server
    options beyond the defaults; `extra_env` is the rehearsal's."""

    def __init__(self, data_dir: str, work_dir: str, options: dict,
                 launcher: str = LAUNCHER, executor: str | None = None,
                 extra_env: dict | None = None, tag: str = "server"):
        self.port = free_port()
        self.log_path = os.path.join(work_dir, f"{tag}.log")
        self.ctl_dir = os.path.join(work_dir, f"{tag}.ctl")
        os.makedirs(self.ctl_dir, exist_ok=True)
        conf = os.path.join(work_dir, f"{tag}.toml")
        with open(conf, "w") as f:
            f.write(toml_text(options))
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        env["BENCH_LAUNCHER_CTL"] = self.ctl_dir
        env.update(extra_env or {})
        cmd = [sys.executable, launcher, "server", "-c", conf,
               "-d", data_dir, "--bind", f"localhost:{self.port}"]
        if executor:
            cmd += ["--executor", executor]
        self.t_start = time.perf_counter()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                cmd, env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO
            )
        self.conn: http.client.HTTPConnection | None = None

    # -- life cycle --------------------------------------------------------

    def log_tail(self, n: int = 40) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError as e:
            return f"(no server log: {e})"

    def wait_up(self, timeout: float = 300.0) -> float:
        while time.perf_counter() - self.t_start < timeout:
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"server exited with code {self.proc.returncode} before "
                    "serving:\n" + self.log_tail()
                )
            try:
                conn = http.client.HTTPConnection("localhost", self.port, timeout=5)
                conn.request("GET", "/status")
                if conn.getresponse().status == 200:
                    conn.close()
                    return time.perf_counter() - self.t_start
            except OSError:
                time.sleep(0.1)
        raise BenchFailure(f"server not up after {timeout:.0f}s:\n" + self.log_tail())

    def stop_gracefully(self, timeout: float = 240.0) -> float:
        """SIGINT: the server's clean shutdown (holder.close())."""
        t0 = time.perf_counter()
        self.close_conn()
        self.proc.send_signal(signal.SIGINT)
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            raise BenchFailure(
                f"server did not stop within {timeout:.0f}s of SIGINT:\n"
                + self.log_tail()
            ) from None
        if rc != 0:
            raise BenchFailure(
                f"server exited with code {rc} on SIGINT:\n" + self.log_tail()
            )
        return time.perf_counter() - t0

    def kill(self) -> None:
        self.close_conn()
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()

    def alive(self) -> bool:
        return self.proc.poll() is None

    # -- HTTP ----------------------------------------------------------------

    def close_conn(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None

    def request(self, method: str, path: str, body=None, headers=None) -> bytes:
        if self.conn is None:
            self.conn = http.client.HTTPConnection(
                "localhost", self.port, timeout=900
            )
        try:
            self.conn.request(method, path, body, headers or {})
            resp = self.conn.getresponse()
            data = resp.read()
        except (OSError, http.client.HTTPException) as e:
            self.close_conn()
            raise BenchFailure(
                f"{method} {path}: {type(e).__name__}: {e}\n" + self.log_tail()
            ) from None
        if resp.status != 200:
            raise BenchFailure(
                f"{method} {path}: HTTP {resp.status}: {data[:300]!r}"
            )
        return data

    def get_json(self, path: str):
        return json.loads(self.request("GET", path))

    def post_json(self, path: str, obj) -> None:
        self.request("POST", path, json.dumps(obj).encode(),
                     {"Content-Type": "application/json"})

    def metrics(self) -> dict:
        return parse_metrics(self.request("GET", "/metrics").decode())

    # -- the launcher's tracer ----------------------------------------------

    def tracer(self, command: str, timeout: float = 120.0) -> None:
        """Ask the launcher's tracer thread to `start` or `stop`, and wait
        until it says it has."""
        want = os.path.join(self.ctl_dir, command + ".done")
        with open(os.path.join(self.ctl_dir, command), "w"):
            pass
        t0 = time.perf_counter()
        while not os.path.exists(want):
            if not self.alive():
                raise BenchFailure("server died while tracing:\n" + self.log_tail())
            if time.perf_counter() - t0 > timeout:
                raise BenchFailure(f"tracer did not {command} in {timeout:.0f}s")
            time.sleep(0.01)


def scrape(port: int) -> dict:
    """One /metrics page over a connection of its own (for a thread beside
    the one that owns a Server's connection)."""
    conn = http.client.HTTPConnection("localhost", port, timeout=60)
    try:
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        text = resp.read().decode()
    finally:
        conn.close()
    if resp.status != 200:
        raise BenchFailure(f"GET /metrics: HTTP {resp.status}")
    return parse_metrics(text)


def parse_metrics(text: str) -> dict:
    """{(family, frozenset of (label, value))): value} of a Prometheus text
    page, the family without the `pilosa_` prefix."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        series, _, value = line.rpartition(" ")
        name, _, labels = series.partition("{")
        if name.startswith("pilosa_"):
            name = name[len("pilosa_"):]
        pairs = []
        for part in labels.rstrip("}").split(","):
            if "=" in part:
                k, _, v = part.partition("=")
                pairs.append((k.strip(), v.strip().strip('"')))
        try:
            out[(name, frozenset(pairs))] = float(value)
        except ValueError:
            continue
    return out


def series_sum(sample: dict, name: str, where: dict | None = None) -> float:
    """Sum of the series of one family whose labels match `where`: a label
    maps to one value or to a list of allowed values."""
    total = 0.0
    for (fam, labels), value in sample.items():
        if fam != name:
            continue
        have = dict(labels)
        ok = True
        for k, want in (where or {}).items():
            allowed = want if isinstance(want, list) else [want]
            if have.get(k) not in allowed:
                ok = False
                break
        if ok:
            total += value
    return total


def delta(before: dict, after: dict, name: str, where: dict | None = None) -> float:
    return series_sum(after, name, where) - series_sum(before, name, where)
