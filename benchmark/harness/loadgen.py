"""Load generator processes. Each holds a few clients as threads, one
keep-alive connection a client, a raw HTTP/1.1 exchange (the bytes are
built before the clock starts; the response is kept as bytes and judged
after the window). The parent talks to each process over a pipe: one
command a phase, one reply.

Clocks: time.monotonic() is one clock for every process of the machine,
so a phase's start and end are handed over as monotonic instants.
"""

from __future__ import annotations

import json
import multiprocessing
import socket
import threading
import time

from . import plugins, traffic
from . import reference as ref_mod


class _Conn:
    def __init__(self, port: int, path: str):
        self.port = port
        self.set_path(path)
        self.sock: socket.socket | None = None
        self.buf = b""

    def set_path(self, path: str) -> None:
        self.head = (
            f"POST {path} HTTP/1.1\r\nHost: localhost\r\nContent-Length: "
        ).encode()

    def open(self) -> None:
        self.sock = socket.create_connection(("localhost", self.port), timeout=120)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""

    def close(self) -> None:
        if self.sock is not None:
            self.sock.close()
            self.sock = None

    def exchange(self, body: bytes) -> tuple[int, bytes]:
        """Send one request, return (status, response body)."""
        if self.sock is None:
            self.open()
        self.sock.sendall(self.head + str(len(body)).encode() + b"\r\n\r\n" + body)
        buf = self.buf
        while True:
            end = buf.find(b"\r\n\r\n")
            if end >= 0:
                break
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("server closed the connection")
            buf += chunk
        header = buf[:end].lower()
        status = int(header[9:12])
        at = header.find(b"content-length:")
        if at < 0:
            raise ConnectionError("response without Content-Length")
        stop = header.find(b"\r\n", at)
        length = int(header[at + 15: stop if stop >= 0 else len(header)])
        need = end + 4 + length
        while len(buf) < need:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("server closed the connection")
            buf += chunk
        self.buf = buf[need:]
        return status, buf[end + 4: need]


def _client_phase(conn: _Conn, stream, phase: dict, out: dict) -> None:
    """One client through one phase of a closed loop. Records (sent, done,
    status, body, calls) per request."""
    t_start, t_end = phase["t_start"], phase["t_end"]
    records = []
    out["records"] = records
    time.sleep(max(0.0, t_start - time.monotonic()))
    while time.monotonic() < t_end:
        body, calls = stream.next()
        t0 = time.monotonic()
        try:
            status, data = conn.exchange(body)
        except (OSError, ValueError) as e:
            conn.close()
            status, data = -1, repr(e).encode()
        t1 = time.monotonic()
        records.append((t0, t1, status, data, calls))


def _judge(records, reference, shape: str) -> dict:
    """Compare every answer with the reference's, each result as the
    group's shape compares it. An answer is wrong when the request failed,
    the body does not parse, or any result differs."""
    wrong = failed = 0
    worst = 0
    examples = []
    ok = []
    mod = plugins.load("shapes", shape)
    config, totals = reference.config, reference.totals.get(shape, {})
    for t0, t1, status, data, calls in records:
        if status != 200:
            failed += 1
            ok.append(False)
            if len(examples) < 3:
                examples.append(f"HTTP {status}: {data[:120]!r}")
            continue
        try:
            got = json.loads(data)["results"]
        except (ValueError, KeyError, TypeError):
            got = None
        want = [mod.answer(config, totals, call) for call in calls]
        right = isinstance(got, list) and len(got) == len(want)
        if right:
            for g, w in zip(got, want):
                equal, error = mod.compare(g, w)
                right = right and equal
                if error is not None:
                    worst = max(worst, error)
        ok.append(right)
        if not right:
            wrong += 1
            if len(examples) < 3:
                examples.append(
                    f"{mod.render(calls)[:100]!r}: got {str(got)[:120]} "
                    f"want {str(want)[:120]}"
                )
    return {"wrong": wrong, "failed": failed, "ok": ok,
            "worst_abs_error": worst, "examples": examples}


def query_path(config: dict, drop_last_shard: bool = False) -> str:
    """The path the requests go to. `drop_last_shard` is the control: the
    request is sent to all of the index's shards but the last (and is
    still judged by the whole index)."""
    path = f"/index/{config['index']}/query"
    if not drop_last_shard:
        return path
    return path + "?shards=" + ",".join(
        str(s) for s in range(int(config["shards"]) - 1)
    )


def worker_main(pipe, port: int, mix: dict, config: dict, seed: int,
                clients: list[int], tables: dict | None) -> None:
    """Process entry. Commands: {"op": "phase", ...} or {"op": "exit"}."""
    reference = ref_mod.Reference(config, tables) if tables is not None else None
    groups = traffic.client_groups(mix)
    conns = {c: _Conn(port, "") for c in clients}
    try:
        while True:
            cmd = pipe.recv()
            if cmd["op"] == "exit":
                break
            outs, threads = {}, []
            cpu0, wall0 = time.process_time(), time.monotonic()
            for c in clients:
                stream = traffic.RequestStream(
                    groups[c], config, seed, c, cmd["stream"]
                )
                conns[c].set_path(
                    query_path(config, bool(cmd.get("drop_last_shard")))
                )
                outs[c] = {}
                t = threading.Thread(
                    target=_client_phase,
                    args=(conns[c], stream, cmd, outs[c]),
                )
                t.start()
                threads.append(t)
            for t in threads:
                t.join()
            cpu = time.process_time() - cpu0
            wall = time.monotonic() - wall0
            reply = {"cpu_s": cpu, "wall_s": wall, "clients": {}}
            for c, out in outs.items():
                recs = out.get("records", [])
                entry = {
                    "shape": plugins.shape_name(groups[c]),
                    "sent": [r[0] for r in recs],
                    "done": [r[1] for r in recs],
                    "calls": [len(r[4]) for r in recs],
                }
                if cmd.get("judge") and reference is not None:
                    entry["judged"] = _judge(recs, reference, entry["shape"])
                reply["clients"][c] = entry
            pipe.send(reply)
    finally:
        for conn in conns.values():
            conn.close()


class Generator:
    """The parent's handle on the generator processes."""

    def __init__(self, port: int, mix: dict, config: dict, seed: int,
                 tables: dict | None):
        n_clients = len(traffic.client_groups(mix))
        n_proc = max(1, min(int(mix.get("generator_processes", 1)), n_clients))
        ctx = multiprocessing.get_context("spawn")
        self.n_clients = n_clients
        self.procs = []
        for p in range(n_proc):
            mine = list(range(p, n_clients, n_proc))
            parent_end, child_end = ctx.Pipe()
            proc = ctx.Process(
                target=worker_main,
                args=(child_end, port, mix, config, seed, mine, tables),
                daemon=True,
            )
            proc.start()
            child_end.close()
            self.procs.append((proc, parent_end))

    def phase(self, seconds: float, stream: int, judge: bool = False,
              drop_last_shard: bool = False, lead: float = 0.25,
              drain_timeout: float = 120.0) -> dict:
        """Run one phase on every process and gather the replies."""
        t_start = time.monotonic() + lead
        cmd = {
            "op": "phase", "t_start": t_start, "t_end": t_start + seconds,
            "stream": stream, "judge": judge,
            "drop_last_shard": drop_last_shard,
        }
        for _, pipe in self.procs:
            pipe.send(cmd)
        replies = []
        for proc, pipe in self.procs:
            if not pipe.poll(lead + seconds + drain_timeout):
                raise RuntimeError("a generator process did not answer")
            replies.append(pipe.recv())
        return {"t_start": t_start, "t_end": t_start + seconds,
                "seconds": seconds, "replies": replies}

    def close(self) -> None:
        for proc, pipe in self.procs:
            try:
                pipe.send({"op": "exit"})
            except (OSError, BrokenPipeError):
                pass
        for proc, pipe in self.procs:
            proc.join(timeout=10)
            if proc.is_alive():
                proc.kill()
                proc.join()
            pipe.close()
