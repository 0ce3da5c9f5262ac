"""Query-lifecycle telemetry: per-phase attribution from HTTP to HBM.

A QueryProfile carries named phase timers + counters for ONE query as it
moves through the serving path (server/http.py -> server/api.py ->
exec/executor.py -> exec/tpu.py). The profile is activated thread-locally
(profile_scope) so deep layers attribute work without threading an object
through every signature; the serving path is thread-per-request, so the
thread-local IS the request scope.

Batching-plane attribution contract (exec/batcher.py, ISSUE r11): a
coalesced follower's ENTIRE cost is its `batch_wait` phase — the wait on
the leader's shared launch covers plan + dispatch + readback done on its
behalf. The leader (or detached helper drain) self-attributes the shared
work (`plan`/`device_dispatch`/`host_reduce`) exactly once per launch,
so summing `query_phase_seconds{phase=device_dispatch}` over a window
yields the PER-BATCH launch cost while `phase=batch_wait` carries the
per-query experience — shared device work has exactly one payer per
dispatch, never one per coalesced query. Whichever thread drains, leader
or detached helper, runs under the plane's own profile (PlaneProfile):
it times every step of the drain into `batch_step_seconds{step=…}` and
forwards to the leader's request profile where there is one, so a
helper's launches have a series too.

`phase()` is the one span primitive (ISSUE 26): it times, and for a
working phase it also opens a profiler span (`jax.profiler.
TraceAnnotation`, installed by pilosa_tpu.ops where the process has
imported jax), so that every phase lands on the host plane of a profiler
trace, on the device planes' clock. The span is inert unless a profiler
session is on. A phase in which the thread only waits (WAITING_PHASES)
carries none: sixteen waiting threads would cover every idle gap of the
device and name none.

Three export surfaces (all fed from profile_scope.__exit__):
- tagged histograms on /metrics: query_phase_seconds{call=...,phase=...}
- the in-memory ring behind /debug/queries (recent + in-flight)
- the executor's slow-query log line (threshold: Executor.long_query_time,
  config long-query-time), which prints the breakdown

Motivated by VERDICT r5 "What's weak" #1/#5: the 9 ms of unattributed
per-query host work at 954 shards could not even be diagnosed — a perf
claim is only as good as the attribution behind it (arXiv:1709.07821).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Callable, Optional

#: Canonical phase order for display; profiles may carry others (they
#: sort after these in summaries). "other" is derived, never recorded:
#: duration minus the sum of recorded phases.
PHASES = (
    "parse",
    "plan",
    "key_translate",
    "freshness",
    "stack_fetch",
    "device_dispatch",
    "host_reduce",
    "batch_wait",
    "serialize",
    "resp_write",
)

#: Phases in which the thread does nothing but wait for another's work:
#: timed like the rest, never put on the profiler's host plane.
WAITING_PHASES = frozenset({"batch_wait"})

#: The steps of a drain (exec/batcher.py; docs/observability.md lists
#: what each covers), in the order a drain passes through them. The
#: backend's batched entry points open the middle five under these names;
#: a request profile files each under the coarser phase /metrics has
#: always carried (None: under none, it stays in `other`).
DRAIN_STEPS = (
    "take", "group", "plan", "slots", "dispatch", "device_wait",
    "readback", "scatter", "handoff",
)
_STEP_PHASE = {
    "take": None, "group": None, "slots": None, "scatter": None,
    "handoff": None,
    "dispatch": "device_dispatch", "device_wait": "device_dispatch",
    "readback": "host_reduce",
}

#: Span names coarser than their phases: a trace reduction names a gap of
#: the device by the ONE name that covers more than half of it, summed
#: over threads, so the short phases a request passes through before and
#: after the batcher share a name each.
_REQUEST_PRE = "pilosa.request.pre"
_REQUEST_POST = "pilosa.request.post"
_SPAN_NAMES = {
    "parse": _REQUEST_PRE, "plan": _REQUEST_PRE,
    "key_translate": _REQUEST_PRE,
    "host_reduce": _REQUEST_POST, "readback": _REQUEST_POST,
    "serialize": _REQUEST_POST, "resp_write": _REQUEST_POST,
}

_qid_counter = itertools.count(1)
_local = threading.local()

#: jax.profiler.TraceAnnotation where the process has imported jax
#: (pilosa_tpu/ops/__init__.py installs it); a host-path server
#: (`--executor cpu`) never does, and its phases only time. A provider
#: hook for the reason utils/stats.py has one: this module must import
#: without jax.
_span_factory: Optional[Callable] = None


def set_span_factory(factory: Optional[Callable]) -> None:
    global _span_factory
    _span_factory = factory


def _span(phase: str, name: Optional[str], meta: dict):
    """The profiler span of one phase, or None for a waiting phase and
    for a process without jax."""
    if _span_factory is None or phase in WAITING_PHASES:
        return None
    return _span_factory(
        name or _SPAN_NAMES.get(phase) or "pilosa." + phase, **meta
    )


def cache_state(counters: Optional[dict]) -> Optional[str]:
    """Result-cache verdict for one profile's counters: `hit` when
    EVERY answer came from the result cache, `partial` when some did,
    `miss` when lookups happened but none hit, `bypass` when the
    request asked past the cache, None when nothing was even looked
    up. Shared by the X-Pilosa-Cache response header, the
    /debug/queries ring entry, and the EXPLAIN plan."""
    c = counters or {}
    if c.get("cache_bypass"):
        return "bypass"
    lookups = c.get("cache_lookups", 0)
    if not lookups:
        return None
    hits = c.get("cache_hits", 0)
    uncached = c.get("cache_uncached", 0)
    if hits and hits == lookups and not uncached:
        return "hit"
    if hits:
        return "partial"
    return "miss"


class ExplainPlan:
    """Executed-plan record for ONE query (ISSUE 16 tentpole 1):
    per-call route + cache verdict, per-leg batcher records, per-launch
    program records. Allocated ONLY when the request asked for it
    (?explain=1 / X-Pilosa-Explain) — with the flag off, the profile's
    `explain` slot stays None and every deep-layer hook is a single
    `getattr(prof, "explain", None) is not None` check; no plan node is
    ever constructed (tests/test_explain.py pins this).

    Threading: the plan belongs to the request thread, but a batcher
    LEADER thread appends leg/launch records into a follower's plan via
    the sink captured at submit time — list.append is GIL-atomic, and
    the follower only reads after its leg event is set (the same
    happens-before edge the result itself rides)."""

    __slots__ = ("calls", "_cur")

    def __init__(self):
        self.calls: list = []
        self._cur: Optional[dict] = None

    def begin_call(self, name: str) -> dict:
        node: dict = {"call": name}
        self.calls.append(node)
        self._cur = node
        return node

    def _node(self) -> dict:
        return self._cur if self._cur is not None else self.begin_call("")

    def note(self, key: str, value) -> None:
        self._node()[key] = value

    def leg_sink(self) -> list:
        """The list batcher leg records append to — captured at submit
        time so the leader can attribute into the follower's plan."""
        return self._node().setdefault("legs", [])

    def add_launch(self, rec: dict) -> None:
        self._node().setdefault("launches", []).append(rec)

    def to_dict(self) -> dict:
        return {"calls": self.calls}


class _PhaseTimer:
    __slots__ = ("profile", "name", "span", "t0")

    def __init__(self, profile: "QueryProfile", name: Optional[str], span):
        self.profile = profile
        self.name = name
        self.span = span

    def __enter__(self):
        if self.profile.in_call is not None:
            self.profile.in_call.suspend()
        if self.span is not None:
            self.span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.name is not None:
            self.profile.add_phase(self.name, time.perf_counter() - self.t0)
        if self.span is not None:
            self.span.__exit__(*exc)
        if self.profile.in_call is not None:
            self.profile.in_call.resume()


class _CallTimer:
    """One PQL call of a request, the first or the tenth of its body:
    observed as `query_call_seconds{call=<name>}` from its start to its
    result, waits included (what the client of that call would feel),
    and on the profiler's host plane as `pilosa.call.<name>` over the
    stretches in which its thread is in no phase and serves no drain: a
    phase and a drain step carry spans of their own, and a wait carries
    none (WAITING_PHASES), so the call's span names what is left, the
    thread's own work between them. A member of a run of reads
    (exec/executor.py `_device_read`) opens its timer when its turn
    comes after the run's one wait, with `waited` = the run's submission
    to its own leg's resolution: one observation a call all the same,
    and the calls of a run overlap."""

    __slots__ = ("profile", "name", "span", "covered", "t0", "waited")

    def __init__(self, profile: "QueryProfile", name: str,
                 waited: float = 0.0):
        self.profile = profile
        self.name = name
        #: Seconds the call had already taken when the timer opens.
        self.waited = waited
        self.span = None
        #: Phases and drains open inside the call (and, outside `with`,
        #: the call's own being shut): the span is open while this is 0.
        self.covered = 1

    def __enter__(self):
        self.profile.in_call = self
        self.resume()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        from pilosa_tpu.utils.stats import global_stats

        global_stats.with_tags(f"call:{self.name}").timing(
            "query_call_seconds",
            self.waited + time.perf_counter() - self.t0,
        )
        self.suspend()
        self.profile.in_call = None

    def suspend(self) -> None:
        self.covered += 1
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None

    def resume(self) -> None:
        self.covered -= 1
        if self.covered == 0 and _span_factory is not None:
            self.span = _span_factory("pilosa.call." + self.name)
            self.span.__enter__()


class QueryProfile:
    """Phase timers + counters for one query. Not thread-safe by design:
    one profile belongs to one serving thread (see module docstring)."""

    __slots__ = (
        "qid", "index", "query", "call", "started_at", "_t0",
        "phases", "counters", "error", "duration", "remote",
        "explain", "shards", "shape", "in_call",
    )
    #: A request pays for what this thread does: per-request counters
    #: (bytes shipped, launches) are worth working out.
    charges = True

    def __init__(self, index: str = "", query: str = "", call: str = ""):
        self.qid = next(_qid_counter)
        self.index = index
        # Truncated: profiles live in a ring; an unbounded PQL body (bulk
        # Set batches) would pin MBs per slot.
        self.query = query[:200]
        self.call = call
        # True when this execution is a coordinator-dispatched peer leg
        # (?remote=true): its phases still attribute, but it must NOT
        # feed the whole-query latency series (see _export).
        self.remote = False
        # Epoch stamp by contract: /debug/queries serves startedAt as a
        # wall-clock time operators correlate with logs; durations come
        # from the separate perf_counter t0 below.
        self.started_at = time.time()  # lint: allow-monotonic-time(startedAt is an operator-facing epoch display stamp)
        self._t0 = time.perf_counter()
        self.phases: dict[str, float] = {}
        self.counters: dict[str, int] = {}
        self.error: Optional[str] = None
        self.duration: Optional[float] = None
        # ISSUE 16: executed-plan record, allocated only under the
        # explain flag; resolved shard count, recorded by the executor
        # for every request so the ring/slow-query log can name the
        # route without explain.
        self.explain: Optional[ExplainPlan] = None
        self.shards: Optional[int] = None
        # ISSUE 18: canonical-PQL shape fingerprint (pql/ast.shape_key —
        # structure + field names, literals stripped), stamped by the
        # executor after parse; the workload table's aggregation key.
        self.shape: Optional[str] = None
        #: The call of the request's body being executed (`call`).
        self.in_call: Optional[_CallTimer] = None

    def call_timer(self, name: str, waited: float = 0.0) -> _CallTimer:
        """Time one call of the request's body (see _CallTimer)."""
        return _CallTimer(self, name, waited)

    def phase(self, name: str, span: Optional[str] = None,
              **meta) -> _PhaseTimer:
        """Time one phase and put it on the profiler's host plane, as
        `span` where the caller names it (a dispatch keeps its kind's
        name), with `meta` as the span's metadata. A drain step's name
        files under the phase it has always belonged to."""
        return _PhaseTimer(
            self, _STEP_PHASE.get(name, name), _span(name, span, meta)
        )

    def add_phase(self, name: str, seconds: float) -> None:
        self.phases[name] = self.phases.get(name, 0.0) + seconds

    def incr(self, name: str, value: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + value

    def finish(self) -> "QueryProfile":
        self.duration = time.perf_counter() - self._t0
        return self

    def elapsed(self) -> float:
        return self.duration if self.duration is not None else (
            time.perf_counter() - self._t0
        )

    def unattributed(self) -> float:
        return max(0.0, self.elapsed() - sum(self.phases.values()))

    def phases_ms(self, snapshot: Optional[dict] = None) -> dict[str, float]:
        src = dict(self.phases) if snapshot is None else snapshot
        ordered = sorted(
            src,
            key=lambda n: (PHASES.index(n) if n in PHASES else len(PHASES), n),
        )
        return {n: round(src[n] * 1e3, 3) for n in ordered}

    def phase_summary(self) -> str:
        """Compact 'phase=1.2ms ...' string for the slow-query log."""
        parts = [f"{n}={v}ms" for n, v in self.phases_ms().items()]
        parts.append(f"other={round(self.unattributed() * 1e3, 3)}ms")
        return " ".join(parts)

    def to_dict(self) -> dict:
        # Snapshot the mutable dicts ONCE: /debug/queries serializes
        # IN-FLIGHT profiles while the owning serving thread appends
        # phases/counters. dict(...) copies are atomic C-level operations
        # under the GIL, and deriving elapsed/phases/other from the same
        # snapshot keeps the reported fields mutually consistent instead
        # of torn across concurrent phase transitions.
        phases = dict(self.phases)
        counters = dict(self.counters)
        duration = self.duration
        elapsed = (
            duration if duration is not None
            else time.perf_counter() - self._t0
        )
        out = {
            "qid": self.qid,
            "index": self.index,
            "query": self.query,
            "call": self.call,
            "startedAt": self.started_at,
            "elapsedMs": round(elapsed * 1e3, 3),
            "inFlight": duration is None,
            "phasesMs": self.phases_ms(phases),
            "otherMs": round(
                max(0.0, elapsed - sum(phases.values())) * 1e3, 3
            ),
            "counters": counters,
        }
        # Route context (ISSUE 16 satellite): resolved shard count +
        # cache verdict survive into the ring for EVERY request, so a
        # slow-query entry names its route without needing explain.
        if self.shards is not None:
            out["shards"] = self.shards
        cache = cache_state(counters)
        if cache is not None:
            out["cache"] = cache
        if self.explain is not None:
            out["explain"] = self.explain.to_dict()
        if self.error is not None:
            out["error"] = self.error
        return out


class NopProfile:
    """Zero-cost sink for instrumentation when no profile is active
    (internal maintenance work, direct backend calls outside a scope)."""

    class _NopPhase:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    _PHASE = _NopPhase()
    phases: dict = {}
    counters: dict = {}
    call = ""
    explain = None
    shards = None
    shape = None
    charges = False
    in_call = None

    def phase(self, name: str, span: Optional[str] = None, **meta):
        # Unprofiled work (prewarm threads, direct backend calls) still
        # shows in a trace: the span alone.
        return _span(name, span, meta) or self._PHASE

    def call_timer(self, name: str, waited: float = 0.0):
        return self._PHASE

    def add_phase(self, name: str, seconds: float) -> None:
        pass

    def incr(self, name: str, value: int = 1) -> None:
        pass


NOP_PROFILE = NopProfile()


class _StepTimer:
    __slots__ = ("plane", "step", "span")

    def __init__(self, plane: "PlaneProfile", step: str, span):
        self.plane = plane
        self.step = step
        self.span = span

    def __enter__(self):
        self.plane._open = True
        if self.span is not None:
            self.span.__enter__()
        return self

    def __exit__(self, *exc):
        if self.span is not None:
            self.span.__exit__(*exc)
        self.plane._lap(self.step)


class PlaneProfile(NopProfile):
    """The batching plane's own profile (exec/batcher.py runs each
    `_drain` inside `with PlaneProfile(stats)`, on whichever thread
    drains: a leader's request thread or a detached helper).

    Its phases are the STEPS of a drain, kept as laps: a step runs from
    the end of the one before it (from the activation, for the first) to
    its own end, so the steps of a drain add up to its wall with nothing
    between them, and the few bytecodes between two `with` blocks belong
    to the step they prepare. Each step is observed as
    `batch_step_seconds{step=…}` with the thread's CPU time over the same
    stretch in `batch_step_cpu_seconds_total{step=…}` (less CPU than wall
    is the thread waiting: for the device in `device_wait`, for the
    interpreter lock or a lock anywhere else), carries a profiler span
    `pilosa.drain.<step>` with the drain's number as metadata (a
    dispatch keeps its kind's span name, `pilosa.count_batch`, …), and is
    forwarded to the leader's request profile under the phase it has
    always had there, so `query_phase_seconds` keeps one payer per
    dispatch. A helper has no leader; what it would forward is dropped."""

    def __init__(self, stats):
        self.stats = stats
        self.leader: Optional[QueryProfile] = None
        #: The number of the drain being served: what its spans share.
        self.drain = 0
        self._open = False
        self._wall = time.perf_counter()
        self._cpu = time.thread_time()

    def __enter__(self) -> "PlaneProfile":
        """Active on this thread, over the request profile it carries (a
        leader's; none on a helper), which is restored on exit."""
        self.leader = getattr(_local, "profile", None)
        _local.profile = self
        if self.leader is not None and self.leader.in_call is not None:
            self.leader.in_call.suspend()
        return self

    def __exit__(self, *exc):
        _local.profile = self.leader
        if self.leader is not None and self.leader.in_call is not None:
            self.leader.in_call.resume()
        return False

    @property
    def explain(self):
        return self.leader.explain if self.leader is not None else None

    @property
    def charges(self) -> bool:
        return self.leader is not None

    def phase(self, name: str, span: Optional[str] = None, **meta):
        if self._open:
            # Inside an open step: the step covers the stretch and its
            # span names it; only the leader's phase table hears more.
            if self.leader is None:
                return self._PHASE
            return _PhaseTimer(self.leader, _STEP_PHASE.get(name, name), None)
        return _StepTimer(self, name, _span(
            name, span or "pilosa.drain." + name, dict(meta, drain=self.drain)
        ))

    def _lap(self, step: str) -> None:
        wall, cpu = time.perf_counter(), time.thread_time()
        d_wall, d_cpu = wall - self._wall, cpu - self._cpu
        self._wall, self._cpu = wall, cpu
        self._open = False
        st = self.stats.with_tags(f"step:{step}")
        st.timing("batch_step_seconds", d_wall)
        st.count("batch_step_cpu_seconds_total", d_cpu)
        name = _STEP_PHASE.get(step, step)
        if self.leader is not None and name is not None:
            self.leader.add_phase(name, d_wall)

    def incr(self, name: str, value: int = 1) -> None:
        if self.leader is not None:
            self.leader.incr(name, value)


def current_profile():
    """The active thread's QueryProfile, or the nop sink."""
    return getattr(_local, "profile", None) or NOP_PROFILE


class QueryRing:
    """Recent completed profiles (bounded ring) + in-flight registry —
    the store behind /debug/queries."""

    def __init__(self, capacity: int = 128):
        self.capacity = capacity
        self._lock = threading.Lock()
        self._recent: deque = deque(maxlen=capacity)
        self._inflight: dict[int, QueryProfile] = {}

    def start(self, p: QueryProfile) -> None:
        with self._lock:
            self._inflight[p.qid] = p

    def finish(self, p: QueryProfile) -> None:
        with self._lock:
            self._inflight.pop(p.qid, None)
            self._recent.append(p)

    def recent(self, n: int = 50) -> list[dict]:
        if n <= 0:  # [-0:] would return the WHOLE ring, not nothing
            return []
        with self._lock:
            items = list(self._recent)[-n:]
        return [p.to_dict() for p in reversed(items)]  # newest first

    def inflight(self) -> list[dict]:
        with self._lock:
            items = list(self._inflight.values())
        return [p.to_dict() for p in items]


global_query_ring = QueryRing()


class WorkloadTable:
    """Per-query-shape cost accounting (ISSUE 18 tentpole 3): a bounded
    top-K table keyed by canonical-PQL shape fingerprint, fed from every
    completed profile's counters — device-wait, launches, bytes shipped/
    returned, lock-wait — so GET /debug/workload answers 'which query
    SHAPES consume the device' with cumulative device-seconds per shape.
    This is the accounting substrate the ROADMAP item-5 per-tenant
    quotas will charge against.

    Shapes are structure-only (literals stripped, pql/ast.shape_key), so
    the key population is bounded by call vocabulary x schema fields —
    pilint-cardinality-safe by construction. The table itself is ALSO
    bounded: past `capacity` distinct shapes, the entry with the
    smallest cumulative device-seconds is evicted (the table exists to
    rank device consumers; the cheapest consumer is the safest loss)."""

    def __init__(self, capacity: int = 256):
        self.capacity = capacity
        self._lock = threading.Lock()
        self._shapes: dict[str, dict] = {}
        self.evicted = 0

    def observe(self, p: QueryProfile, stats=None) -> None:
        shape = getattr(p, "shape", None)
        if not shape or p.duration is None:
            return
        c = p.counters
        with self._lock:
            ent = self._shapes.get(shape)
            if ent is None:
                if len(self._shapes) >= self.capacity:
                    victim = min(
                        self._shapes,
                        key=lambda k: self._shapes[k]["deviceSeconds"],
                    )
                    del self._shapes[victim]
                    self.evicted += 1
                ent = self._shapes[shape] = {
                    "queries": 0, "errors": 0, "seconds": 0.0,
                    "deviceSeconds": 0.0, "launches": 0,
                    "bytesShipped": 0, "bytesReturned": 0,
                    "lockWaitSeconds": 0.0, "cacheHits": 0,
                    "cacheLookups": 0, "maxMs": 0.0,
                    # One example spelling (already ring-truncated) so
                    # an operator can read the shape back as PQL.
                    "example": p.query,
                }
                if stats is not None:
                    # Distinct-shape counter (bench LEG_COUNTER_FAMILIES
                    # rides counter families, and the table is a gauge-
                    # shaped thing otherwise).
                    stats.count("workload_shapes_total")
            ent["queries"] += 1
            if p.error is not None:
                ent["errors"] += 1
            ent["seconds"] += p.duration
            ent["deviceSeconds"] += c.get("device_wait_us", 0) / 1e6
            ent["launches"] += c.get("device_launches", 0)
            ent["bytesShipped"] += c.get("bytes_shipped", 0)
            ent["bytesReturned"] += c.get("bytes_returned", 0)
            ent["lockWaitSeconds"] += c.get("lock_wait_us", 0) / 1e6
            ent["cacheHits"] += c.get("cache_hits", 0)
            ent["cacheLookups"] += c.get("cache_lookups", 0)
            ms = p.duration * 1e3
            if ms > ent["maxMs"]:
                ent["maxMs"] = ms
            # Epoch stamp by contract: operators correlate lastSeen with
            # logs, same display contract as startedAt above.
            ent["lastSeen"] = time.time()  # lint: allow-monotonic-time(lastSeen is an operator-facing epoch display stamp)

    def top(self, n: int = 50) -> list[dict]:
        """Entries by cumulative device-seconds, heaviest first (whole-
        query seconds break ties: host-only shapes still rank)."""
        with self._lock:
            items = [
                dict(ent, shape=shape) for shape, ent in self._shapes.items()
            ]
        items.sort(
            key=lambda e: (e["deviceSeconds"], e["seconds"]), reverse=True
        )
        out = []
        for ent in items[: n if n > 0 else len(items)]:
            ent["seconds"] = round(ent["seconds"], 6)
            ent["deviceSeconds"] = round(ent["deviceSeconds"], 6)
            ent["lockWaitSeconds"] = round(ent["lockWaitSeconds"], 6)
            ent["maxMs"] = round(ent["maxMs"], 3)
            out.append(ent)
        return out

    def snapshot(self, n: int = 50) -> dict:
        with self._lock:
            shapes, evicted = len(self._shapes), self.evicted
        return {"shapes": shapes, "evicted": evicted, "entries": self.top(n)}

    def clear(self) -> None:
        with self._lock:
            self._shapes.clear()
            self.evicted = 0


global_workload_table = WorkloadTable()


class profile_scope:
    """Activate a QueryProfile for the current thread.

    The OUTERMOST scope owns the profile: it registers it in-flight,
    finalizes it, and exports the phase histograms. Nested scopes (the
    executor inside the HTTP handler) reuse the outer profile so phases
    accumulate into one record per query."""

    __slots__ = ("index", "query", "call", "profile", "owned")

    def __init__(self, index: str = "", query: str = "", call: str = ""):
        self.index = index
        self.query = query
        self.call = call

    def __enter__(self) -> QueryProfile:
        cur = getattr(_local, "profile", None)
        if cur is not None:
            self.profile, self.owned = cur, False
            return cur
        p = QueryProfile(self.index, self.query, self.call)
        _local.profile = p
        global_query_ring.start(p)
        self.profile, self.owned = p, True
        return p

    def __exit__(self, etype, evalue, tb):
        if not self.owned:
            return False
        _local.profile = None
        p = self.profile
        if evalue is not None and p.error is None:
            p.error = str(evalue)[:200]
        p.finish()
        global_query_ring.finish(p)
        self._export(p)
        return False

    @staticmethod
    def _export(p: QueryProfile) -> None:
        from pilosa_tpu.utils.stats import global_stats

        call = p.call or "?"
        # Whole-query latency distribution per call type: the series SLO
        # objectives and /debug/queries quantiles read. Phases attribute
        # WHERE time went; this one answers "what is the p99" — a
        # question the per-phase series cannot (phases of one query land
        # in different buckets). Remote peer legs are excluded: one
        # distributed query must be ONE observation in the cluster-merged
        # distribution (the coordinator's, which is what the user felt),
        # not one per participating node diluted by fast leg samples.
        if p.duration is not None and not p.remote:
            global_stats.with_tags(f"call:{call}").timing(
                "query_seconds", p.duration
            )
        for name, secs in p.phases.items():
            global_stats.with_tags(f"call:{call}", f"phase:{name}").timing(
                "query_phase_seconds", secs
            )
        un = p.unattributed()
        if un > 0:
            global_stats.with_tags(f"call:{call}", "phase:other").timing(
                "query_phase_seconds", un
            )
        # Per-shape cost accounting (ISSUE 18). Remote peer legs DO
        # feed the table — unlike query_seconds, /debug/workload is a
        # strictly per-node attribution surface (never cluster-merged),
        # and a data node serving only coordinator-dispatched legs
        # would otherwise report an empty table while its device burns.
        global_workload_table.observe(p, global_stats)
