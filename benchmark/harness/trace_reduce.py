"""From a profiler trace to device busy time, program and operation
times, and idle gaps named by what the host was doing.

Two stages, so that the arithmetic can be checked without a chip:
`extract` reads the profiler's `.xplane.pb` with jax.profiler.ProfileData
(the only place this benchmark imports jax, in a process of its own that
is started after the server child has gone, with JAX_PLATFORMS=cpu), and
gives plain lists; `reduce` is pure Python over those lists and is what
benchmark/tests/test_trace_reduce.py drives on a small recorded trace.

What the trace of a TPU looks like (jax 0.9.0, libtpu 0.0.34): one plane
per chip named `/device:TPU:<n>`, whose line `XLA Ops` holds one event per
executed HLO operation and whose line `XLA Modules` holds one event per
executed program; host threads are lines of the plane `/host:CPU`, where
`jax.profiler.TraceAnnotation` spans appear under their own names.

    python trace_reduce.py <trace dir or .xplane.pb> <out.json>
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Gaps shorter than this are the device's own turn-around between two
#: operations of one program, not the host's doing.
MIN_GAP_NS = 20_000


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def extract(path: str, annotation_prefixes=("pilosa.",)) -> dict:
    """{"devices": {n: {"ops": [...], "modules": [...]}}, "host":
    [[name, start_ns, dur_ns], ...], "extent": [first_ns, last_ns]} with
    ops and modules as [name, start_ns, dur_ns]. Of the host plane only
    the annotated spans are kept, and the extent of everything."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    devices: dict = {}
    host = []
    first, last = None, None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            keep = None
            if m and line.name == OPS_LINE:
                keep = devices.setdefault(int(m.group(1)), {}).setdefault("ops", [])
            elif m and line.name == MODULES_LINE:
                keep = devices.setdefault(int(m.group(1)), {}).setdefault("modules", [])
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                if first is None or s < first:
                    first = s
                if last is None or s + d > last:
                    last = s + d
                if keep is not None:
                    keep.append([ev.name, s, d])
                elif not m and ev.name.startswith(tuple(annotation_prefixes)):
                    host.append([ev.name, s, d])
    return {
        "devices": {str(k): v for k, v in sorted(devices.items())},
        "host": host,
        "extent": [first or 0, last or 0],
    }


def union_ns(intervals) -> tuple[int, list]:
    """(total length, merged intervals) of [start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def self_times(ops) -> dict[str, int]:
    """{name: ns} of one line's events, each counted without the events
    nested in it: a `while` holds the fusions of its body, and counting
    both would show the body's time twice."""
    out: dict[str, int] = {}
    open_: list[list] = []  # [name, end, self_ns], innermost last
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        while open_ and open_[-1][1] <= s:
            done = open_.pop()
            out[done[0]] = out.get(done[0], 0) + max(0, done[2])
        if open_:
            open_[-1][2] -= d
        open_.append([name, s + d, d])
    for done in open_:
        out[done[0]] = out.get(done[0], 0) + max(0, done[2])
    return out


def short(name: str, n: int = 64) -> str:
    """A trace name as a breakdown can carry it: no spaces, commas or
    slashes, at most n characters."""
    return re.sub(r"[^A-Za-z0-9_.:\-]", "_", name)[:n]


def reduce(ex: dict) -> dict:
    """The summary the readers and the result line use."""
    first, last = ex["extent"]
    window_ns = max(0, last - first)
    per_device = {}
    ops_total: dict[str, int] = {}
    for dev, lines in ex["devices"].items():
        ops = lines.get("ops", [])
        busy_ns, merged = union_ns([(s, s + d) for _, s, d in ops])
        modules: dict[str, list] = {}
        for name, _, d in lines.get("modules", []):
            ent = modules.setdefault(name, [0, 0])
            ent[0] += 1
            ent[1] += d
        for name, ns in self_times(ops).items():
            # By the name as a breakdown carries it: operations of several
            # programs that differ only past its end are counted together.
            ops_total[short(name)] = ops_total.get(short(name), 0) + ns
        per_device[dev] = {
            "busy_s": busy_ns / 1e9,
            "n_ops": len(ops),
            "modules": {k: [v[0], v[1] / 1e9] for k, v in modules.items()},
            "merged": merged,
        }
    n_dev = len(per_device)
    busy = [d["busy_s"] for d in per_device.values()]
    # Idle gaps of the busiest chip, each named by the host annotation that
    # covers more than half of it; a gap that no annotation covers so far
    # is the host's doing outside every annotated span.
    gaps: dict[str, int] = {}
    if per_device:
        busiest = max(per_device, key=lambda k: per_device[k]["busy_s"])
        merged = per_device[busiest]["merged"]
        edges = [first] + [x for iv in merged for x in iv] + [last]
        spans = sorted((s, s + d, name) for name, s, d in ex["host"])
        starts = [s for s, _, _ in spans]
        # Longest annotation seen so far bounds how far back to look.
        longest = max((e - s for s, e, _ in spans), default=0)
        for i in range(0, len(edges), 2):
            g0, g1 = edges[i], edges[i + 1]
            if g1 - g0 < MIN_GAP_NS:
                continue
            cover: dict[str, int] = {}
            lo = bisect.bisect_left(starts, g0 - longest)
            hi = bisect.bisect_right(starts, g1)
            for s, e, name in spans[lo:hi]:
                ov = min(e, g1) - max(s, g0)
                if ov > 0:
                    cover[name] = cover.get(name, 0) + ov
            name = max(cover, key=cover.get) if cover else None
            if name is None or 2 * cover[name] <= g1 - g0:
                name = "_no_annotation_"
            gaps[name] = gaps.get(name, 0) + (g1 - g0)
    for d in per_device.values():
        del d["merged"]
    top = lambda table: [  # noqa: E731
        [short(k), v / 1e9]
        for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:10]
    ]
    return {
        "window_s": window_ns / 1e9,
        "n_devices": n_dev,
        "busy_s": (sum(busy) / n_dev) if n_dev else 0.0,
        "busy_s_max": max(busy) if busy else 0.0,
        "devices": per_device,
        "breakdown": {"device_ops": top(ops_total), "idle_gaps": top(gaps)},
    }


def main(argv) -> int:
    ex = extract(argv[1])
    with open(argv[2], "w") as f:
        json.dump(reduce(ex), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
