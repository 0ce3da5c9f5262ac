"""Things found by name: a call shape in benchmark/shapes/<name>.py, a
data draw in benchmark/draws/<name>.py. The harness knows neither by
name; a mix's group names its `shape`, a configuration's field its
`draw`, and a later PR adds either as a file (benchmark/README.md).

One default each keeps the files that were written before the seam as
they are: a group that names no shape is `count`, a field that names no
draw takes its type's entry of DEFAULT_DRAW.
"""

from __future__ import annotations

import importlib.util
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_SHAPE = "count"
DEFAULT_DRAW = {"set": "uniform_set", "int": "sparse_int"}


def load(kind: str, name: str):
    """The module benchmark/<kind>/<name>.py, loaded once a process."""
    key = f"bench_{kind}_{name}"
    if key in sys.modules:
        return sys.modules[key]
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise ValueError(f"benchmark/{kind}/ has no {name}.py")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


def source(kind: str, name: str) -> bytes:
    with open(os.path.join(BENCH_DIR, kind, name + ".py"), "rb") as f:
        return f.read()


def shape_name(group: dict) -> str:
    return group.get("shape", DEFAULT_SHAPE)


def shape_of(group: dict):
    return load("shapes", shape_name(group))


def draw_name(config: dict, field: str) -> str:
    spec = config["fields"][field]
    return spec.get("draw") or DEFAULT_DRAW[spec["type"]]


def draw_of(config: dict, field: str):
    return load("draws", draw_name(config, field))


def groups_by_shape(mix: dict) -> dict[str, list[dict]]:
    """The mix's groups under the name of their shape, in the mix's order."""
    out: dict[str, list[dict]] = {}
    for g in mix["groups"]:
        out.setdefault(shape_name(g), []).append(g)
    return out
