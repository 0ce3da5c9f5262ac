#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts the real server as a child (benchmark/launcher.py, which is
`python -m pilosa_tpu.cli server` with a dormant tracer beside it) on the
seed's published data directory, warms the shapes the cell's traffic uses,
drives `POST /index/<index>/query` for --seconds from generator processes
of its own, compares every answer of the window with the numpy reference,
and prints one JSON object as the last line of standard output. This
process never imports jax: a parent that had would hold the chip.

Everything that belongs to one cell is found by name: the cell, its
configuration and its metrics in BENCHMARK.json; the configuration's file
as named there, each field's draw in benchmark/draws/; the mix in
benchmark/traffic/<traffic>.json, each group's call shape (its calls,
their text, their reference and their warm-up) in benchmark/shapes/; each
metric in benchmark/end_to_end/<name>.json or
benchmark/layer_metrics/<name>.json, naming a reader in
benchmark/readers/. See benchmark/README.md.

`--rehearse cpu` walks the same phases against a child on JAX's CPU
devices, to debug the harness without a chip: it never exits 0 and never
prints a result line. `--control <name>` breaks a guarantee on purpose
(see PERF.md): such a run must come out with `correct` false.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, REPO)

from harness import dataset, hostwatch, loadgen, plugins, reference, traffic  # noqa: E402
from harness.server import LAUNCHER, BenchFailure, Server, delta, scrape  # noqa: E402

REHEARSAL_EXIT = 3
#: How a control breaks a guarantee the configuration states, through a
#: path of the program's own, reached by its public API. "exact answers":
#: every query is sent to all of the index's shards but the last (`?shards=`,
#: harness/loadgen.py `query_path`), so each count lacks one shard's part:
#: the approximate answer that a sampled or partial scan would give.
CONTROLS = ("drop_shard",)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchFailure(f"BENCHMARK.json has no {what} named {name!r}")


def metrics_of(bench: dict, section: str, cell: dict) -> list[dict]:
    """The section's metrics that this cell reports: those without a
    `workloads` key, and those whose key lists the cell."""
    return [
        m for m in bench[section]
        if "workloads" not in m or cell["name"] in m["workloads"]
    ]


def read_metric(section_dir: str, metric: dict, ctx: dict):
    """Value of one metric by its definition file, or None where the
    reader finds nothing to read."""
    spec = load_json(os.path.join(BENCH_DIR, section_dir, metric["name"] + ".json"))
    return plugins.load("readers", spec["reader"]).read(ctx, **spec.get("args", {}))


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------


def compile_count(srv: Server) -> tuple[int, float]:
    """(programs compiled so far, device_recompiles_total)."""
    progs = srv.get_json("/debug/programs")
    return int(progs["compiles"]), float(progs.get("recompiles", 0))


def wait_for_roles(srv: Server, roles: list[str], timeout: float = 900.0) -> float:
    """Wait until the child has no live thread of any of `roles`; the
    seconds it took (0.0 where none was alive)."""
    t0 = time.monotonic()
    while roles:
        alive = srv.get_json("/debug/threads")["roles"]
        busy = [r for r in roles if alive.get(r)]
        if not busy:
            break
        if time.monotonic() - t0 > timeout:
            raise BenchFailure(f"threads of {busy} still alive after {timeout:.0f}s")
        time.sleep(0.5)
    waited = time.monotonic() - t0
    if waited >= 0.5:
        say(f"warm-up: waited {waited:.1f}s for background threads {roles}")
        return waited
    return 0.0


def warm_up(srv: Server, gen: loadgen.Generator, config: dict, mix: dict,
            seed: int) -> None:
    """Build the cell's stacks, compile its programs, and go on until
    nothing compiles any more.

    1. One request alone from each group of clients, in the mix's order:
       the first answers, which build the stacks the mix's fields need
       (and no others).
    2. What the group's shape knows it needs beyond that (its `warm`):
       requests that reach programs the window's timing may or may not
       bring about, so that each is compiled here whatever the window does.
    3. Rounds of the mix itself until a round compiles nothing
       (/debug/programs) twice running, and no thread of a role the mix
       lists under `quiet_roles` is alive (/debug/threads): in a checkout
       with an empty compile cache the server compiles its upload programs
       in the background for minutes, and a window that begins meanwhile
       would hold compilation.
    """
    warm = mix.get("warm", {})
    path = loadgen.query_path(config)
    client = 0
    for group in mix["groups"]:
        name = group.get("name", client)
        stream = traffic.RequestStream(group, config, seed, client, stream=1)
        t0 = time.monotonic()
        srv.request("POST", path, stream.next()[0])
        say(f"warm-up: first answer of group {name!r} "
            f"in {time.monotonic() - t0:.1f}s")
        t0 = time.monotonic()

        def send(body: bytes) -> None:
            srv.request("POST", path, body)

        def said(what: str) -> None:
            say(f"warm-up: group {name!r}: {what} in "
                f"{time.monotonic() - t0:.1f}s, "
                f"{compile_count(srv)[0]} programs compiled so far")

        plugins.shape_of(group).warm(group, config, seed, client, send, said)
        client += int(group["clients"])
    stream_id = 2
    quiet, rounds = 0, 0
    before = compile_count(srv)
    t0 = time.monotonic()
    while quiet < 2 and rounds < int(warm.get("max_settle_rounds", 10)):
        waited = wait_for_roles(srv, warm.get("quiet_roles", []))
        gen.phase(warm.get("settle_seconds", 1.5), stream_id)
        stream_id += 1
        rounds += 1
        after = compile_count(srv)
        quiet = quiet + 1 if after == before and not waited else 0
        before = after
    say(f"warm-up: {rounds} settle rounds in {time.monotonic() - t0:.1f}s, "
        f"{before[0]} programs compiled, quiet for {quiet}")


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def generator_late_by(window: dict) -> float:
    """How long after the window's start the last client sent its first
    request (a closed-loop client sends at once)."""
    return max(
        (entry["sent"][0] - window["t_start"]
         for reply in window["replies"]
         for entry in reply["clients"].values() if entry["sent"]),
        default=0.0,
    )


def gather(window: dict) -> dict:
    """Flatten the generator processes' replies of the window phase."""
    out = {k: [] for k in ("sent", "done", "calls", "ok", "shape")}
    judged = {"wrong": 0, "failed": 0, "worst_abs_error": 0, "examples": [],
              "by_shape": {}}
    cpu = []
    for reply in window["replies"]:
        cpu.append(reply["cpu_s"] / reply["wall_s"] if reply["wall_s"] else 0.0)
        for entry in reply["clients"].values():
            for k in ("sent", "done", "calls"):
                out[k].extend(entry[k])
            j = entry["judged"]
            out["ok"].extend(j["ok"])
            out["shape"].extend([entry["shape"]] * len(j["ok"]))
            judged["wrong"] += j["wrong"]
            judged["failed"] += j["failed"]
            mine = judged["by_shape"].setdefault(
                entry["shape"], {"requests": 0, "wrong": 0, "failed": 0}
            )
            mine["requests"] += len(j["ok"])
            mine["wrong"] += j["wrong"]
            mine["failed"] += j["failed"]
            judged["worst_abs_error"] = max(
                judged["worst_abs_error"], j["worst_abs_error"]
            )
            judged["examples"].extend(j["examples"])
    out.update(t_start=window["t_start"], t_end=window["t_end"],
               seconds=window["seconds"])
    return {"window": out, "judged": judged, "generator_cpu_share": cpu}


def say_latency(w: dict) -> None:
    """The window's latency percentiles on standard error: of all requests,
    and of each shape's where the mix has more than one."""
    shapes = sorted(set(w["shape"]))
    for shape in [None] + (shapes if len(shapes) > 1 else []):
        lat = sorted(
            (t1 - t0) * 1e3
            for t0, t1, s in zip(w["sent"], w["done"], w["shape"])
            if shape in (None, s)
        )
        if not lat:
            continue
        say("latency, send to last byte, ms"
            + (f", shape {shape!r} ({len(lat)} requests)" if shape else "")
            + ": " + " ".join(
                f"p{p}={lat[min(len(lat) - 1, len(lat) * p // 100)]:.1f}"
                for p in (50, 75, 90, 95, 97, 99)
            ) + "; share over 1.5 times the median: "
            f"{sum(x > 1.5 * lat[len(lat) // 2] for x in lat) / len(lat):.4f}")


def trace_stretch(srv: Server, at: float, seconds: float, out: dict) -> None:
    """Inside the window: scrape, start the child's tracer, wait, stop it,
    scrape. Runs on a thread of its own while the generators drive."""
    try:
        time.sleep(max(0.0, at - time.monotonic()))
        srv.tracer("start")
        before = scrape(srv.port)
        t0 = time.monotonic()
        time.sleep(seconds)
        after = scrape(srv.port)
        t1 = time.monotonic()
        srv.tracer("stop")
        out.update(scrapes=(before, after), between_scrapes_s=t1 - t0)
    except BaseException as e:  # noqa: BLE001 - reported by the caller
        out["error"] = e


def run_cell(args) -> dict:
    """The result object. Raises BenchFailure where the run can give no
    result."""
    if "jax" in sys.modules:
        raise BenchFailure("the parent imported jax: it would hold the chip")
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = find(bench["workloads"], args.workload, "workload")
    conf_entry = find(bench["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(REPO, conf_entry["file"]))
    mix = traffic.load_mix(
        os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    )
    chips = int(cell["chips"])
    rehearse = args.rehearse
    extra_env = {}
    if rehearse:
        config = dict(config, shards=args.shards or 8)
        extra_env["JAX_PLATFORMS"] = rehearse
        if chips > 1:
            extra_env["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}"
            ).strip()
    peaks_table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    say(f"run: workload={cell['name']} config={config['name']} "
        f"traffic={cell['traffic']} chips={chips} seed={args.seed} "
        f"seconds={args.seconds} trace={args.trace}"
        + (f" REHEARSAL on {rehearse}, {config['shards']} shards" if rehearse else "")
        + (f" CONTROL {args.control}" if args.control else ""))

    work = tempfile.mkdtemp(prefix="pilosa-tpu-bench-")
    srv = gen = None
    try:
        data_dir, tables = dataset.ensure(
            config, args.seed, work, say, reference.needs(mix, config),
            root=args.data_root,
            extra_env={"JAX_PLATFORMS": rehearse} if rehearse else None,
        )
        t_data = time.monotonic() - T_PROCESS_START
        srv = Server(data_dir, work, config.get("server", {}),
                     launcher=args.launcher, extra_env=extra_env)
        up = srv.wait_up()
        say(f"server child up in {up:.1f}s (pid {srv.proc.pid})")

        # -- where is the child running? -----------------------------------
        jx = srv.get_json("/debug/diagnostics")["jax"]
        if "error" in jx:
            raise BenchFailure(f"device inventory failed: {jx}")
        device = {
            "platform": jx["devices"][0]["platform"],
            "kind": jx["devices"][0]["kind"],
            "count": jx["device_count"],
        }
        say(f"device: {json.dumps(device)} compile_cache={jx['compilation_cache_dir']}")
        want_platform = rehearse or "tpu"
        if jx["platform"] != want_platform or device["platform"] != want_platform:
            raise BenchFailure(
                f"server is on platform {jx['platform']!r}, not {want_platform!r}"
            )
        if device["count"] < chips:
            raise BenchFailure(
                f"{device['count']} devices visible, the cell asks for {chips}"
            )
        peaks = peaks_table.get(device["kind"])
        if peaks is None and not rehearse:
            raise BenchFailure(
                f"device kind {device['kind']!r} is not in benchmark/peaks.json"
            )

        # -- warm-up ---------------------------------------------------------
        gen = loadgen.Generator(srv.port, mix, config, args.seed, tables)
        warm_up(srv, gen, config, mix, args.seed)

        # -- the window -------------------------------------------------------
        tr_out: dict = {}
        tracer = None
        lead = 0.5
        compiles0 = compile_count(srv)
        m0 = srv.metrics()
        if args.trace:
            tr_seconds = min(float(mix.get("trace_seconds", 5.0)),
                             0.6 * args.seconds)
            at = time.monotonic() + lead + 0.25 * args.seconds
            tracer = threading.Thread(
                target=trace_stretch, args=(srv, at, tr_seconds, tr_out)
            )
            tracer.start()
        setup_s = time.monotonic() + lead - T_PROCESS_START
        watch = hostwatch.HostWatch()
        window = gen.phase(args.seconds, stream=0, judge=True,
                           drop_last_shard=args.control == "drop_shard",
                           lead=lead)
        # A machine that stands still over the window's start wakes the
        # clients late; the window is kept as it is and counts the silence.
        say("the machine in the window: " + watch.report())
        say(f"the generator's last client began "
            f"{generator_late_by(window):.3f}s after the window's start")
        if tracer is not None:
            tracer.join()
            if "error" in tr_out:
                raise BenchFailure(f"tracing failed: {tr_out['error']!r}")
        m1 = srv.metrics()
        compiles1 = compile_count(srv)
        g = gather(window)
        w = g["window"]
        late = [t for t in w["done"] if t > w["t_end"]]
        say(f"window: {len(w['done'])} requests, {len(late)} answered after the "
            f"close (last {max(late) - w['t_end']:.3f}s past it)" if late else
            f"window: {len(w['done'])} requests, none answered after the close")
        answered = sorted(w["done"])
        say("longest silence between two answers: "
            f"{max((b - a for a, b in zip(answered, answered[1:])), default=0.0):.3f}s")
        say_latency(w)
        say("generator CPU share per process (1.0 = one core): "
            + " ".join(f"{x:.2f}" for x in g["generator_cpu_share"]))
        say(f"compiles inside the window: programs {compiles1[0] - compiles0[0]}, "
            f"device_recompiles_total {compiles1[1] - compiles0[1]:.0f}")

        # -- the device, after the window ------------------------------------
        jx = srv.get_json("/debug/diagnostics")["jax"]
        peak_bytes = held_bytes = 0
        for d in jx["devices"]:
            ms = d.get("memory_stats") or {}
            peak_bytes = max(peak_bytes, int(ms.get("peak_bytes_in_use", 0)))
            held_bytes = max(held_bytes, int(ms.get("bytes_in_use", 0)))
        if not peak_bytes and not rehearse:
            raise BenchFailure("the device reports no peak_bytes_in_use")
        device["memory_peak_bytes"] = peak_bytes
        # What the fullest chip still holds once the window has closed: the
        # stacks a deployment keeps, without the scans' temporaries.
        device["memory_held_bytes"] = held_bytes
        fallbacks = delta({}, m1, "device_fallback_total")
        for (family, labels), value in sorted(m1.items(), key=str):
            if family == "device_fallback_total" and value:
                say(f"device_fallback_total{dict(labels)} = {value:.0f}")
        launches = delta(m0, m1, "device_launches_total")
        srv.kill()
        gen.close()
        gen = None

        # -- the trace --------------------------------------------------------
        trace = None
        if args.trace:
            summary = os.path.join(work, "trace_summary.json")
            cmd = [sys.executable,
                   os.path.join(BENCH_DIR, "harness", "trace_reduce.py"),
                   os.path.join(srv.ctl_dir, "trace"), summary]
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            t0 = time.monotonic()
            done = subprocess.run(cmd, env=env, capture_output=True, text=True,
                                  timeout=300)
            if done.returncode != 0:
                raise BenchFailure("trace reduction failed:\n" + done.stderr[-2000:])
            trace = load_json(summary)
            say(f"trace: reduced in {time.monotonic() - t0:.1f}s; window "
                f"{trace['window_s']:.3f}s (scrapes {tr_out['between_scrapes_s']:.3f}s "
                f"apart), {trace['n_devices']} device planes, busy "
                f"{trace['busy_s']:.3f}s")
            if not rehearse and trace["busy_s"] <= 0:
                raise BenchFailure("the traced run saw no operation on the device")
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]

        # -- metrics ----------------------------------------------------------
        ctx = {
            "window": w, "setup_s": setup_s, "config": config, "mix": mix,
            "chips": chips, "peaks": peaks, "trace": trace,
            "scrapes": {"window": (m0, m1), "trace": tr_out.get("scrapes")},
        }
        section, section_dir = (
            ("per_layer", "layer_metrics") if args.trace else
            ("end_to_end", "end_to_end")
        )
        metrics = {}
        for m in metrics_of(bench, section, cell):
            value = read_metric(section_dir, m, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        # -- correct ----------------------------------------------------------
        judged = g["judged"]
        attempted = len(w["done"])
        failed = judged["wrong"] + judged["failed"]
        compared = {
            "wrong_answers": {"value": judged["wrong"], "limit": 0},
            "unanswered_or_failed": {"value": judged["failed"], "limit": 0},
            "worst_abs_count_error": {"value": judged["worst_abs_error"], "limit": 0},
            "device_fallbacks": {"value": fallbacks, "limit": 0},
            "requests_judged": {"value": attempted, "limit_at_least": 1},
        }
        # Every shape of the mix has to have been judged, not one of them
        # for the others.
        for shape in plugins.groups_by_shape(mix):
            mine = judged["by_shape"].get(shape, {"requests": 0})
            compared["judged_" + shape] = {
                "value": mine["requests"], "limit_at_least": 1,
            }
            say(f"judged, shape {shape!r}: " + json.dumps(mine))
        correct = all(
            v["value"] <= v["limit"] if "limit" in v
            else v["value"] >= v["limit_at_least"]
            for v in compared.values()
        )
        for ex in judged["examples"][:5]:
            say("wrong: " + ex)
        result = {
            "correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device,
        }
        if trace is not None:
            result["breakdown"] = trace["breakdown"]
        result["compared"] = compared
        say(f"set-up: data {t_data:.1f}s, whole {setup_s:.1f}s; "
            f"device launches in the window: {launches:.0f}")
        return result
    finally:
        if gen is not None:
            gen.close()
        if srv is not None:
            if sys.exc_info()[0] is not None:
                say("server log, last lines:\n" + srv.log_tail())
            srv.kill()
        shutil.rmtree(work, ignore_errors=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", default=None, metavar="PLATFORM",
                    help="walk the phases on this JAX platform; never exits 0")
    ap.add_argument("--shards", type=int, default=None,
                    help="rehearsal only: shards of the index")
    ap.add_argument("--control", choices=CONTROLS, default=None,
                    help="break a stated guarantee; the run must not be correct")
    ap.add_argument("--launcher", default=LAUNCHER,
                    help="the server child's entry (tests plant faults here)")
    ap.add_argument("--data-root", default=None,
                    help="where published data directories live")
    args = ap.parse_args(argv)
    if args.shards and not args.rehearse:
        ap.error("--shards is for --rehearse only: a cell runs at its size")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = run_cell(args)
    except BenchFailure as e:
        say(f"benchmark run failed: {e}")
        return 1
    say("compared: " + " ".join(
        f"{k}={v['value']}(limit {v.get('limit', v.get('limit_at_least'))})"
        for k, v in result["compared"].items()
    ))
    if args.rehearse:
        say("rehearsal result (not a chip result): " + json.dumps(
            {k: result[k] for k in ("correct", "attempted", "failed")}
        ))
        say(f"rehearsal on {args.rehearse}: exiting {REHEARSAL_EXIT}")
        return REHEARSAL_EXIT
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
