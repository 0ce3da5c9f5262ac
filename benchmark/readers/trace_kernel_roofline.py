"""A kernel's share of its memory roofline, in %: the least time the
chips could take to read the bytes the work needs, over the time the
kernel's programs ran.

The bytes are counted from what was asked, not from what the kernel does:
`units` (a /metrics counter, grown over the traced stretch) times
`operands_per_unit` rows, each `shards * shard_width / 8` bytes of the
configuration. The time is the summed duration, in the trace, of the
programs whose name matches `programs` (a regular expression), averaged
over the chips; the peak is the chips' together (peaks.json, by device
kind). A kernel that shares operand rows across a batch reads less than
this count and is the one thing that could carry the share past 100."""

import re

from harness.server import delta


def read(ctx, programs, units, operands_per_unit):
    tr = ctx.get("trace")
    edges = ctx["scrapes"].get("trace")
    if not tr or not tr["n_devices"] or not edges:
        return None
    pat = re.compile(programs)
    seconds = sum(
        total
        for dev in tr["devices"].values()
        for name, (_, total) in dev["modules"].items()
        if pat.search(name)
    ) / tr["n_devices"]
    n_units = delta(edges[0], edges[1], units["metric"], units.get("where"))
    if seconds <= 0 or n_units <= 0:
        return None
    cfg = ctx["config"]
    row_bytes = cfg["shards"] * cfg["shard_width"] // 8
    need = n_units * operands_per_unit * row_bytes
    least = need / (ctx["peaks"]["hbm_bytes_per_s"] * tr["n_devices"])
    return 100.0 * least / seconds
