#!/usr/bin/env python3
"""A copy of the benchmark with the files of benchmark/tests/added/ put in,
as a later PR that may only add files and entries would put them in.

    python3 benchmark/tests/overlay.py <directory>

The copy holds BENCHMARK.json, benchmark/ and a link to the program. Every
added file must be new there (an edit of a file that exists fails the
build, which is the point), and every added entry a new name; the one
thing merged into entries that exist is a cell's name into the `workloads`
list of a metric it also reports (`also_listed_by`). The added cells run
from the copy like any cell: `<directory>/benchmark/run.py --workload ...`.

What is added is the tests' and not the benchmark's: two call shapes in one
mix (traffic/count-sum.json) on bench954-1chip, and the same mix on a
configuration whose int field names a draw of its own (draws/dense_int.py).
No cell of BENCHMARK.json reads any of it.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(TESTS_DIR)
REPO = os.path.dirname(BENCH_DIR)
ADDED = os.path.join(TESTS_DIR, "added")
ENTRIES = "BENCHMARK.add.json"
SECTIONS = ("configs", "workloads", "end_to_end", "per_layer")


def added_entries(added: str = ADDED) -> dict:
    with open(os.path.join(added, ENTRIES)) as f:
        return json.load(f)


def merged(bench: dict, add: dict) -> dict:
    """`bench` with the entries of `add`; a name that exists is refused."""
    out = {k: list(v) if k in SECTIONS else v for k, v in bench.items()}
    for section in SECTIONS:
        have = {e["name"] for e in out[section]}
        for entry in add.get(section, []):
            if entry["name"] in have:
                raise ValueError(f"{section}: {entry['name']!r} is there already")
            out[section].append(entry)
    for name, cells in add.get("also_listed_by", {}).items():
        (at,) = [i for i, m in enumerate(out["per_layer"]) if m["name"] == name]
        metric = dict(out["per_layer"][at])
        metric["workloads"] = metric["workloads"] + cells
        out["per_layer"][at] = metric
    return out


def build(dest: str, added: str = ADDED) -> str:
    """Make the copy in `dest` (which must not exist) with the files and
    entries of `added`, and return it."""
    os.makedirs(dest)
    shutil.copytree(BENCH_DIR, os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "pilosa_tpu"), os.path.join(dest, "pilosa_tpu"))
    for root, _, files in os.walk(added):
        for name in files:
            if name == ENTRIES or name.endswith(".pyc"):
                continue
            rel = os.path.relpath(os.path.join(root, name), added)
            target = os.path.join(dest, "benchmark", rel)
            if os.path.exists(target):
                raise ValueError(f"benchmark/{rel} is there already")
            os.makedirs(os.path.dirname(target), exist_ok=True)
            shutil.copy(os.path.join(root, name), target)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(merged(bench, added_entries(added)), f, indent=1)
    return dest


if __name__ == "__main__":
    print(build(sys.argv[1]))
