"""Tests of the benchmark's own yardstick. They run on the CPU and never
need a chip: `python -m pytest benchmark/tests -q` from the repo's root.

Beside the cells of BENCHMARK.json the rehearsals run the cells of
tests/added/ (overlay.py): files a later PR could add, put into a copy of
the benchmark without an edit of anything that is there."""

import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
for p in (REPO, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

import overlay  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    OWN = json.load(f)
#: BENCHMARK.json with the tests' added entries, as the copy holds it.
BENCH = overlay.merged(OWN, overlay.added_entries())
OWN_CELLS = [w["name"] for w in OWN["workloads"]]
CELLS = [w["name"] for w in BENCH["workloads"]]


def data_file(kind: str, name: str) -> str:
    """benchmark/<kind>/<name>, or the added file of that place."""
    for base in (BENCH_DIR, overlay.ADDED):
        path = os.path.join(base, kind, name)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"{kind}/{name}")


@pytest.fixture(scope="session")
def checkout_of(tmp_path_factory):
    """cell -> the root to run it from: the repo for a cell of its own,
    the copy with the added files (built once a session) for an added one."""
    built = []

    def root(cell: str) -> str:
        if cell in OWN_CELLS:
            return REPO
        if not built:
            built.append(overlay.build(
                str(tmp_path_factory.mktemp("overlay") / "checkout")
            ))
        return built[0]

    return root
