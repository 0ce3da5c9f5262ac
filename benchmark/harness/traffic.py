"""The one general traffic generator. A mix is a data file
(benchmark/traffic/<name>.json) of parameters; nothing here knows a mix,
a cell or a configuration by name.

A mix's keys:
  generator_processes  the clients are spread over this many processes
  trace_seconds      how long a `--trace 1` run keeps the profiler on
  warm               how the warm-up settles before the window (run.py
                     `warm_up`): settle_seconds, max_settle_rounds,
                     quiet_roles
  groups             one or more groups of clients, each with:
    name               for the log
    loop               "closed": each client sends its next request when
                       the last one is answered. The one kind so far; a
                       cell that needs another brings the code with it
    clients            concurrent clients, one keep-alive connection each
    calls_per_request  PQL calls in one request body
    verbs              the verbs a call draws from, uniformly
    operand_sets       lists of set fields; a call draws one uniformly,
                       then a row of each field uniformly:
                       Count(<verb>(Row(<field>=<row>), ...))
    warm_batch_sizes   optional: the numbers of same-verb calls that one
                       launch can hold, each of which the warm-up sends
                       once as one request, verb by verb

Clients are numbered through the groups in order. A client's requests are
a function of (seed, client index, stream) alone: the measured window is
stream 0 of every client, warm-up rounds take other streams. Every seed
draws from the same pools with the same probabilities, so seeds reorder
the work and do not change it.
"""

from __future__ import annotations

import json

import numpy as np

CHUNK = 512


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if not mix.get("groups"):
        raise ValueError(f"{path}: a mix needs at least one group of clients")
    for g in mix["groups"]:
        if g["loop"] != "closed":
            raise ValueError(f"{path}: loop is {g['loop']!r}")
    return mix


def client_groups(mix: dict) -> list[dict]:
    """The group of each client, by client index."""
    return [g for g in mix["groups"] for _ in range(int(g["clients"]))]


class RequestStream:
    """The endless request sequence of one client in one stream. Each
    request is (body bytes, calls) with calls = [(verb, [(field, row)..])].
    `verbs` narrows the group's verbs (warm-up)."""

    def __init__(self, group: dict, config: dict, seed: int, client: int,
                 stream: int = 0, verbs: list[str] | None = None):
        self.rows = {n: f["rows"] for n, f in config["fields"].items()
                     if f["type"] == "set"}
        self.verbs = [v for v in group["verbs"] if verbs is None or v in verbs]
        self.sets = [list(s) for s in group["operand_sets"]]
        self.per_request = int(group["calls_per_request"])
        self.rng = np.random.default_rng([seed, client, stream])
        self._set_i: list = []
        self._at = 0

    def _fill(self) -> None:
        """Draw CHUNK requests' worth of random numbers at once; the calls
        and the body are built a request at a time in `next`, so that the
        cost is spread evenly over a client's requests."""
        n = CHUNK * self.per_request
        width = max(len(s) for s in self.sets)
        self._set_i = self.rng.integers(0, len(self.sets), n).tolist()
        self._verb_i = self.rng.integers(0, len(self.verbs), n).tolist()
        # One uniform draw per operand slot, scaled to the field's rows.
        self._u = self.rng.random((n, width)).tolist()
        self._at = 0

    def next(self):
        if self._at >= len(self._set_i):
            self._fill()
        calls = []
        for k in range(self._at, self._at + self.per_request):
            u = self._u[k]
            calls.append((
                self.verbs[self._verb_i[k]],
                [(f, int(u[j] * self.rows[f]))
                 for j, f in enumerate(self.sets[self._set_i[k]])],
            ))
        self._at += self.per_request
        return render(calls), calls


def render(calls) -> bytes:
    return "".join(
        f"Count({verb}(" + ", ".join(f"Row({f}={r})" for f, r in leaves) + "))"
        for verb, leaves in calls
    ).encode()

