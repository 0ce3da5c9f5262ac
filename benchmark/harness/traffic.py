"""The one general traffic generator. A mix is a data file
(benchmark/traffic/<name>.json) of parameters; nothing here knows a mix,
a cell, a configuration or a call shape by name.

A mix's keys:
  generator_processes  the clients are spread over this many processes
  trace_seconds      how long a `--trace 1` run keeps the profiler on
  warm               how the warm-up settles before the window (run.py
                     `warm_up`): settle_seconds, max_settle_rounds,
                     quiet_roles
  groups             one or more groups of clients, each with:
    name               for the log
    shape              the group's call shape, a file of benchmark/shapes/
                       (harness/plugins.py; `count` where none is named).
                       A group has one shape, so a request body is one
                       shape's rendering
    loop               "closed": each client sends its next request when
                       the last one is answered. The one kind so far; a
                       cell that needs another brings the code with it
    clients            concurrent clients, one keep-alive connection each
    calls_per_request  PQL calls in one request body
    ...                the shape's own keys: what a call draws from

Clients are numbered through the groups in order. A client's requests are
a function of (seed, client index, stream) alone: the measured window is
stream 0 of every client, warm-up rounds take other streams. Every seed
draws from the same pools with the same probabilities, so seeds reorder
the work and do not change it.
"""

from __future__ import annotations

import itertools
import json

import numpy as np

from . import plugins

CHUNK = 512


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if not mix.get("groups"):
        raise ValueError(f"{path}: a mix needs at least one group of clients")
    for g in mix["groups"]:
        if g["loop"] != "closed":
            raise ValueError(f"{path}: loop is {g['loop']!r}")
        plugins.shape_of(g)
    return mix


def client_groups(mix: dict) -> list[dict]:
    """The group of each client, by client index."""
    return [g for g in mix["groups"] for _ in range(int(g["clients"]))]


class RequestStream:
    """The endless request sequence of one client in one stream. Each
    request is (body bytes, calls), the calls as the group's shape draws
    them from the client's own RNG, CHUNK requests' worth at a time."""

    def __init__(self, group: dict, config: dict, seed: int, client: int,
                 stream: int = 0):
        self.group, self.config = group, config
        self.shape = plugins.shape_of(group)
        self.per_request = int(group["calls_per_request"])
        self.rng = np.random.default_rng([seed, client, stream])
        self._calls = iter(())

    def next(self):
        calls = list(itertools.islice(self._calls, self.per_request))
        if not calls:
            self._calls = self.shape.draw(
                self.group, self.config, self.rng, CHUNK * self.per_request
            )
            calls = list(itertools.islice(self._calls, self.per_request))
        return self.shape.render(calls), calls
