"""Unified shard-leg batching plane: cross-request device-launch coalescing.

An uncached query pays one dispatch and one readback PER LAUNCH, a
fixed host-side cost next to which a sweep of resident stacks is short
(ledger, PR 31, a launch of count3's traffic: `dispatch_ms` 1.39 and
`device_wait_ms` 1.52 on one chip, 5.31 and 0.21 on four). The design
is the standard
TPU-serving answer to many small heterogeneous requests (the
fixed-shape-slot / ragged-occupancy trick of "Ragged Paged Attention",
PAPERS.md): concurrent queries' device dispatches — Count, bitmap
Row/Intersect/Union resolves, BSI Sum/Min/Max, TopN per-shard counts —
are enqueued as typed LEG descriptors, and a drain groups compatible legs
by (kind, index, shard set) so ONE device launch (exec/tpu.py batched
programs: fixed-shape slot arrays, padded to a slot-count bucket, inactive
lanes masked in-kernel, a per-slot query-id vector scattering results
back) answers the whole group.

Scheduling is the proven leader/follower backpressure loop (VERDICT r2
#2, ADVICE r3): the first submitter becomes leader and dispatches its
batch IMMEDIATELY (no coalescing sleep — an uncontended single leg pays
zero added latency); legs arriving while the leader's dispatch is in
flight queue behind the leadership flag and drain as the NEXT batch (by a
detached helper thread, so the leader's own HTTP response returns as soon
as its leg resolves). Batching therefore emerges from backpressure: the
busier the device round trip, the larger the coalesced batches, with no
idle window on a quiet server. `window > 0` restores a fixed coalescing
sleep for tests that need deterministic batch composition.

Coalescing strategy per kind:
- count: every group's calls concatenate into one backend
  count_batch_async (pair-stats fast path or slot-bucketed fused scans).
- row: calls share one slot-bucketed scanned launch per (spec, blocks)
  group via row_batch_async; identical specs dedupe to one slot.
- topn_tanimoto (ISSUE 36): the legs of one field, each a (source row,
  threshold), share the sweeps of the field's packed stack via
  topn_tanimoto_async: sixteen slots a launch, identical legs one slot,
  every launch of the drain enqueued before any is read back.
- bsi_sum/bsi_min/bsi_max and topn: identical legs (same field + filter
  tree) dedupe to ONE backend call — the concurrent-hot-query case that
  dominates serving traffic — and the backend's epoch caches make the
  deduped call itself usually a host hit.

Telemetry: each dispatched group observes its occupancy — legs per
coalesced launch GROUP — into the `batch_occupancy{kind=…}` histogram
and counts `batch_legs_total{kind=…}` / `batch_coalesced_total{kind=…}`;
the backend counts every real program execution as
`device_launches_total{kind=…}` at the compiled-program chokepoint.
A group usually maps to one launch, but heterogeneous specs or a
byte-capped row group can fan one group into several, so compare
batch_legs_total against device_launches_total for the exact
coalescing ratio; occupancy is the per-drain grouping view. Followers
attribute their whole cost to the `batch_wait` profile phase; the
leader's dispatch work self-attributes (`device_dispatch` et al.) inside
the backend calls it makes on behalf of the batch.

The plane's own profile (ISSUE 26): every `_drain`, on a leader's thread
or a helper's, runs under a utils/qprofile.py PlaneProfile, which times
the steps of each drain (take, group, plan, slots, dispatch,
device_wait, readback, scatter, handoff; docs/observability.md says what
each covers) into `batch_step_seconds{step=…}`, puts each on the
profiler's host plane as `pilosa.drain.<step>`, and forwards to the
leader's request profile. Beside them `batch_drains_total`,
`batch_queue_wait_seconds` (a leg's wait from its append to the drain
that takes it: `batch_wait` less this is the leg's own drain) and
`batch_idle_seconds_total` (no thread was draining: the plane had
nothing to launch).

Mesh composition (ISSUE r13): when the backend carries a ShardMesh,
every launch this plane coalesces — count_batch/vec_batch scans, the
pair-stats sweep, BSI aggregates, TopN popcounts — runs under
shard_map on the sharded stacks with psum/all_gather merges over ICI;
the leg descriptors, group keys, and power-of-two slot buckets are
identical in both regimes (slot padding is a query-axis concern,
orthogonal to the shard axis the mesh splits), so nothing here
branches on topology. Coalescing matters MORE under a mesh: each
launch is a collective across every chip, so the per-launch overhead
the leader/follower drain amortizes is multiplied by the device
count. The backend's [Q, S, W] row-batch byte cap is per-device there
(exec/tpu.py row_batch_async), so mesh row groups chunk n-fold less.

Error isolation: a failed group dispatch retries each member leg
individually so one client's bad query (unknown field, unsupported
shape) errors only that client, never the whole window. Only Exception
is absorbed into the retry path; KeyboardInterrupt/SystemExit in the
drain thread propagates after waiters are released (ADVICE r3).

The reference has no analog: the Go engine executes each request's calls
serially per connection (executor.go:231) because its per-shard loop is
already CPU-parallel. On a TPU the economics invert — dispatches are
expensive, device sweeps are cheap — so coalescing across requests is
what makes the serving path reach the batched-kernel throughput.

Within a request too (ISSUE 33): a trip (`submit`) takes any number of
legs. The executor hands over a request's run of device reads that stand
side by side (a page's TopN and ten Sums: exec/executor.py `_device_read`)
as the legs of ONE trip: queued under one visit to the lock, so one drain
takes them together, and awaited once, where one leg a trip made the
page eleven drain cycles long. `count()`, `row()`, `bsi()` and `topn()`
are trips of one leg through the same code. `batch_trips_total` counts
trips, whatever their legs; `batch_legs_total{kind}` the legs.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Optional

from pilosa_tpu.utils.locks import InstrumentedLock
from pilosa_tpu.utils.qprofile import PlaneProfile, current_profile
from pilosa_tpu.utils.stats import global_stats
from pilosa_tpu.utils.threads import spawn

#: Leg kinds the plane coalesces. count/row/topn legs are built only by
#: their own methods; bsi_leg() takes the kind as an argument and
#: validates it against the bsi_ subset below.
LEG_KINDS = ("count", "row", "bsi_sum", "bsi_min", "bsi_max", "topn",
             "topn_tanimoto")


def topn_trim(pairs, n: int):
    """A topn leg's ranked vector cut to one submitter's n (0: all)."""
    if pairs is None:
        return None
    return pairs[:n] if n else list(pairs)


class _Leg:
    """One enqueued shard-leg: a typed descriptor plus its rendezvous."""

    __slots__ = ("kind", "index", "shards", "payload", "event", "result",
                 "error", "explain", "explain_rec", "queued_at",
                 "resolved_at")

    def __init__(self, kind: str, index: str, shards, payload):
        self.kind = kind
        self.index = index
        self.shards = shards  # tuple — part of the group key
        self.payload = payload
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.queued_at = 0.0  # perf_counter at submit's append
        self.resolved_at = 0.0  # perf_counter as the event was set
        # EXPLAIN (ISSUE 16): the submitter's plan leg-sink, captured at
        # construction ON THE SUBMITTING THREAD so the leader can
        # attribute this leg's group record into the right plan. None
        # when the submitter carries no plan (the common case) — the
        # batching plane then allocates nothing.
        ex = getattr(current_profile(), "explain", None)
        self.explain = ex.leg_sink() if ex is not None else None
        self.explain_rec: Optional[dict] = None

    def resolve(self, result) -> None:
        self.result = result
        self.resolved_at = time.perf_counter()
        self.event.set()

    def fail(self, error: BaseException) -> None:
        self.error = error
        self.resolved_at = time.perf_counter()
        self.event.set()

    def value(self):
        """The leg's answer once `submit` has returned: the backend's
        result, or its error raised on the submitter's thread."""
        if self.error is not None:
            raise self.error
        return self.result


class ShardLegBatcher:
    """Leader/follower backpressure batcher over the device backend's
    batched entry points (count_batch_async / row_batch_async /
    bsi_* / topn_field).

    window > 0 restores the fixed coalescing sleep before each drain
    (useful for tests that need deterministic batch composition); the
    production default is 0 — see module docstring.
    """

    def __init__(self, backend, window: float = 0.0):
        self.backend = backend
        self.window = window
        self._lock = InstrumentedLock("batcher_drain")
        self._pending: list[_Leg] = []
        self._leader_active = False
        # perf_counter at which leadership was last released (None until
        # the first drain has ended): the next leader adds the stretch
        # since to batch_idle_seconds_total. Guarded by _lock.
        self._idle_since: Optional[float] = None
        self.stats = global_stats
        # Drain numbers and EXPLAIN group ids, from one counter:
        # process-unique per batcher, so two legs of one query showing
        # the same group id PROVES they shared a drain group, and the
        # spans of one drain share its number (itertools.count:
        # GIL-atomic, no lock).
        self._group_ids = itertools.count(1)

    # -- public submit API --------------------------------------------------
    # One method a leg kind, each a trip of one leg; `bsi_leg` / `topn_leg`
    # build the same legs unsubmitted, for a caller that has several to
    # hand over in one trip (`submit`).

    def count(self, index: str, calls: list, shards: list[int]) -> list[int]:
        """Block until the batch containing these Count calls resolves;
        returns one count per call. Thread-safe; any thread may become
        leader."""
        return self._one(_Leg("count", index, tuple(shards), list(calls)))

    def row(self, index: str, call, shards: list[int]):
        """Bitmap materialization (Row/Intersect/Union/... resolve):
        returns the merged Row for the shard set."""
        return self._one(_Leg("row", index, tuple(shards), call))

    def bsi_leg(self, kind: str, index: str, field_name: str,
                shards: list[int], filter_call=None) -> _Leg:
        """BSI aggregate (kind: bsi_sum | bsi_min | bsi_max). The leg's
        value is the backend's (value, count) tuple, or None when not
        lowerable (the executor then runs its map-reduce path)."""
        if kind not in LEG_KINDS or not kind.startswith("bsi_"):
            raise ValueError(f"unknown bsi leg kind: {kind!r}")
        return _Leg(kind, index, tuple(shards), (field_name, filter_call))

    def bsi(self, kind: str, index: str, field_name: str, shards: list[int],
            filter_call=None):
        return self._one(
            self.bsi_leg(kind, index, field_name, shards, filter_call)
        )

    def topn_leg(self, index: str, field_name: str, shards: list[int],
                 src_call=None) -> _Leg:
        """Exact TopN. The leg's value is the FULL ranked vector (or None
        when not device-servable), computed once per unique (field, src)
        leg; each submitter trims to its own n (`topn_trim`), so TopN(n=5)
        and TopN(n=50) on the same field share one launch."""
        return _Leg("topn", index, tuple(shards), (field_name, src_call))

    def topn(self, index: str, field_name: str, shards: list[int], n: int,
             src_call=None):
        return topn_trim(
            self._one(self.topn_leg(index, field_name, shards, src_call)), n
        )

    def topn_tanimoto_leg(self, index: str, field_name: str,
                          shards: list[int], row_id: int,
                          threshold: int) -> _Leg:
        """TopN(field, Row(field=row_id), tanimotoThreshold=threshold)
        over a field the backend holds packed. The leg's value is
        (row ids, counts) of every row that passes, by row id (the
        submitter orders and trims), or None where the field is not
        held packed after all (the executor then takes the host path)."""
        return _Leg("topn_tanimoto", index, tuple(shards),
                    (field_name, int(row_id), int(threshold)))

    def topn_tanimoto(self, index, field_name, shards, row_id, threshold):
        return self._one(self.topn_tanimoto_leg(
            index, field_name, shards, row_id, threshold
        ))

    def _one(self, leg: _Leg):
        self.submit((leg,))
        return leg.value()

    # -- leader/follower drain ---------------------------------------------

    def submit(self, legs) -> None:
        """One trip of a request's thread: queue `legs` (one, or a run of
        a request's reads that stand side by side) in one visit to the
        lock, lead a drain if no thread is draining, and return when
        every one of them has resolved; `leg.value()` then gives each
        answer or raises its error. Legs queued together are taken by
        one drain together."""
        idle = None
        with self._lock:
            now = time.perf_counter()
            for leg in legs:
                leg.queued_at = now
            self._pending.extend(legs)
            am_leader = not self._leader_active
            if am_leader:
                self._leader_active = True
                if self._idle_since is not None:
                    idle = now - self._idle_since
        self.stats.count("batch_trips_total")
        if am_leader:
            if idle is not None:
                self.stats.count("batch_idle_seconds_total", idle)
            self._drain(leader_call=True)
        # Telemetry: a follower's whole cost is this wait (the leader's
        # dispatch work self-attributes inside the backend calls); for
        # the leader the events are already set and the phase is ~0.
        with current_profile().phase("batch_wait"):
            for leg in legs:
                leg.event.wait()

    def _drain(self, leader_call: bool) -> None:
        """Serve queued batches. A leader (client thread) serves exactly
        ONE batch — its own leg resolves in it — then hands any queue
        that built up during the round trip to a detached helper thread,
        so under sustained load the first client's HTTP response is not
        held open serving everyone else's batches (code review r4). The
        helper loops until the queue is empty; leadership is released
        under the lock, so a concurrent submitter either sees pending
        work claimed or becomes the next leader itself — never neither.

        Every turn of the loop is one drain, under the plane's profile,
        and passes through the same steps on either kind of thread:
        `take`, what `_serve` and the backend open (`group` … `scatter`),
        and `handoff` (is anything queued meanwhile, and who serves
        it)."""
        if leader_call and self.window > 0:
            # Optional fixed coalescing window before the leader's first
            # (only) drain; helper threads never sleep — the device round
            # trip itself is their window.
            time.sleep(self.window)
        with PlaneProfile(self.stats) as plane:
            batch = None
            while True:
                plane.drain = next(self._group_ids)
                with plane.phase("take"):
                    if batch is None:
                        # A thread's first turn. Never empty: a leader's
                        # own leg is queued before it takes, and a helper
                        # is started only where `handoff` saw legs.
                        with self._lock:
                            batch = self._pending
                            self._pending = []
                    self.stats.count("batch_drains_total")
                    taken_at = time.perf_counter()
                    for leg in batch:
                        self.stats.timing(
                            "batch_queue_wait_seconds",
                            taken_at - leg.queued_at,
                        )
                try:
                    self._serve(batch, plane)
                except BaseException:
                    # KeyboardInterrupt/SystemExit (or a bug in _serve):
                    # free the waiters — INCLUDING followers already
                    # queued behind this leadership, who would otherwise
                    # wait forever with no leader — and release
                    # leadership before propagating.
                    err = RuntimeError("shard-leg batch leader interrupted")
                    with self._lock:
                        stranded = self._pending
                        self._pending = []
                        self._release_leadership()
                    for leg in batch + stranded:
                        if not leg.event.is_set():
                            leg.fail(err)
                    raise
                with plane.phase("handoff"):
                    # One visit to the lock a turn, as before the plane
                    # had a profile: sixteen submitters contend for it,
                    # and a contended acquire costs the drain thread a
                    # round of the interpreter lock. A helper that finds
                    # legs takes them here and goes round again.
                    with self._lock:
                        batch = self._pending
                        if not batch:
                            self._release_leadership()
                            return
                        if not leader_call:
                            self._pending = []
                    if leader_call:
                        spawn("batcher-leader", self._drain, args=(False,))
                        return

    def _release_leadership(self) -> None:
        """Under _lock: no thread drains from here on, until a submitter
        finds the flag down and takes it."""
        self._leader_active = False
        self._idle_since = time.perf_counter()

    # -- batch service ------------------------------------------------------

    def _serve(self, batch: list[_Leg], plane: PlaneProfile) -> None:
        """Group the drained window by (kind, index, shard set), dispatch
        every async-capable group BEFORE resolving any (XLA pipelines the
        device work past the readback round trips), then run the
        synchronous groups and scatter results back by leg."""
        with plane.phase("group"):
            groups: dict[tuple, list[_Leg]] = {}
            for leg in batch:
                groups.setdefault(
                    (leg.kind, leg.index, leg.shards), []
                ).append(leg)
            for (kind, _index, _shards), legs in groups.items():
                self._observe_group(kind, legs, plane.drain)
        pending = []  # (legs, resolver) for async kinds
        sync_groups = []
        for (kind, index, shards), legs in groups.items():
            if kind == "count":
                pending.append((legs, self._dispatch_count(index, shards, legs)))
            elif kind == "row":
                pending.append((legs, self._dispatch_row(index, shards, legs)))
            elif kind == "topn_tanimoto":
                pending.extend(self._dispatch_tanimoto(index, shards, legs))
            else:
                sync_groups.append((kind, index, shards, legs))
        # Synchronous kinds (bsi_*/topn) run AFTER every async dispatch is
        # in flight, so their host/cache work overlaps the device round
        # trips instead of serializing ahead of them.
        for kind, index, shards, legs in sync_groups:
            self._serve_sync(kind, index, shards, legs)
        for legs, resolver in pending:
            if resolver is None:
                continue  # already resolved individually by the dispatcher
            try:
                resolver()
            except Exception:
                # Shared-launch resolution failed: visible on /metrics,
                # then isolate so one bad query can't fail the window.
                # Legs a launch already answered (a Tanimoto group
                # delivers launch by launch) keep their answers.
                self.stats.with_tags(f"kind:{legs[0].kind}").count(
                    "batch_dispatch_errors_total"
                )
                self._resolve_individually(
                    [leg for leg in legs if not leg.event.is_set()]
                )

    def _observe_group(self, kind: str, legs: list[_Leg], drain: int) -> None:
        st = self.stats.with_tags(f"kind:{kind}")
        st.count("batch_legs_total", len(legs))
        if len(legs) > 1:
            st.count("batch_coalesced_total", len(legs) - 1)
        # Occupancy histogram: legs per coalesced launch group (unit:
        # legs, not seconds — the shared bucket set covers 1..100 with
        # 5 buckets/decade; the mean from _sum/_count is exact).
        st.timing("batch_occupancy", float(len(legs)))
        if any(leg.explain is not None for leg in legs):
            occ = len(legs)
            gid = next(self._group_ids)
            bucket = 1 if occ <= 1 else 1 << (occ - 1).bit_length()
            for leg in legs:
                if leg.explain is None:
                    continue
                rec = {
                    "drain": drain,
                    "group": gid,
                    "kind": kind,
                    "occupancy": occ,
                    "occupancyBucket": bucket,
                    "shards": len(leg.shards),
                }
                leg.explain.append(rec)
                leg.explain_rec = rec

    # -- count legs ---------------------------------------------------------

    def _dispatch_count(self, index, shards, legs):
        all_calls = [c for leg in legs for c in leg.payload]
        try:
            resolver = self.backend.count_batch_async(
                index, all_calls, list(shards)
            )
        except Exception:
            self.stats.with_tags("kind:count").count(
                "batch_dispatch_errors_total"
            )
            self._resolve_individually(legs)
            return None

        def resolve():
            values = resolver()
            with current_profile().phase("scatter"):
                off = 0
                for leg in legs:
                    n = len(leg.payload)
                    leg.resolve([int(v) for v in values[off : off + n]])
                    off += n

        return resolve

    # -- row legs -----------------------------------------------------------

    def _dispatch_row(self, index, shards, legs):
        try:
            resolver = self.backend.row_batch_async(
                index, [leg.payload for leg in legs], list(shards)
            )
        except Exception:
            self.stats.with_tags("kind:row").count(
                "batch_dispatch_errors_total"
            )
            self._resolve_individually(legs)
            return None

        def resolve():
            rows = resolver()
            with current_profile().phase("scatter"):
                for leg, row in zip(legs, rows):
                    leg.resolve(row)

        return resolve

    # -- Tanimoto TopN legs ---------------------------------------------------

    def _dispatch_tanimoto(self, index, shards, legs):
        """[(legs, resolver)] of the group, a field at a time: every
        launch is enqueued here and read back by the resolver, after the
        drain's other launches are in flight too."""
        by_field: dict[str, list[_Leg]] = {}
        for leg in legs:
            by_field.setdefault(leg.payload[0], []).append(leg)
        out = []
        for field_name, members in by_field.items():
            try:
                resolver = self.backend.topn_tanimoto_async(
                    index, field_name, list(shards),
                    [leg.payload[1:] for leg in members],
                )
            except Exception:
                self.stats.with_tags("kind:topn_tanimoto").count(
                    "batch_dispatch_errors_total"
                )
                self._resolve_individually(members)
                continue
            if resolver is None:
                # Not held packed (any more): each submitter's host path.
                for leg in members:
                    leg.resolve(None)
                continue
            out.append((members, self._tanimoto_scatter(members, resolver)))
        return out

    @staticmethod
    def _tanimoto_scatter(members, resolver):
        """The group's resolver: a launch's legs are resolved as soon as
        that launch has been read back, the launches behind it still on
        the device."""
        def deliver(which, answers):
            with current_profile().phase("scatter"):
                for i, answer in zip(which, answers):
                    members[i].resolve(answer)

        return lambda: resolver(deliver)

    # -- synchronous kinds (bsi aggregates, topn) ---------------------------

    def _serve_sync(self, kind, index, shards, legs) -> None:
        """Dedupe identical legs (same field + same filter tree object —
        parse-cached trees make repeated hot queries literally identical)
        to one backend call each; every member leg of a dedupe set gets
        the shared immutable result."""
        by_payload: dict[tuple, list[_Leg]] = {}
        for leg in legs:
            field_name, filt = leg.payload
            by_payload.setdefault((field_name, id(filt) if filt is not None else None), []).append(leg)
        for (field_name, _fid), members in by_payload.items():
            filt = members[0].payload[1]
            for leg in members:
                if leg.explain_rec is not None:
                    # Slot-dedupe outcome: `shared` means this leg rode
                    # another identical leg's backend call.
                    leg.explain_rec["dedupe"] = (
                        "shared" if len(members) > 1 else "unique"
                    )
            try:
                if kind == "topn":
                    # n=0: the full ranked vector — submitters trim in
                    # topn() so different n's share the launch.
                    result = self.backend.topn_field(
                        index, field_name, list(shards), 0, filt
                    )
                else:
                    result = getattr(self.backend, kind)(
                        index, field_name, list(shards), filt
                    )
            except Exception as e:  # noqa: BLE001 — delivered to waiters
                for leg in members:
                    leg.fail(e)
                continue
            with current_profile().phase("scatter"):
                for leg in members:
                    leg.resolve(result)

    # -- error isolation ----------------------------------------------------

    def _resolve_individually(self, legs: list[_Leg]) -> None:
        """Group dispatch failed — isolate: one dispatch per leg so only
        the offending client sees the error."""
        for leg in legs:
            try:
                if leg.kind == "count":
                    resolver = self.backend.count_batch_async(
                        leg.index, leg.payload, list(leg.shards)
                    )
                    result = [int(v) for v in resolver()]
                elif leg.kind == "row":
                    result = self.backend.bitmap_call(
                        leg.index, leg.payload, list(leg.shards)
                    )
                elif leg.kind == "topn_tanimoto":
                    result = self.backend.topn_tanimoto(
                        leg.index, leg.payload[0], list(leg.shards),
                        leg.payload[1], leg.payload[2],
                    )
                else:  # bsi_*/topn legs retry through _serve_sync directly
                    self._serve_sync(
                        leg.kind, leg.index, leg.shards, [leg]
                    )
                    continue
            except Exception as e:  # noqa: BLE001 — delivered to waiter
                leg.fail(e)
                continue
            leg.resolve(result)


#: Backward-compatible name: the plane grew out of the Count-only
#: coalescer and every wiring site (cli, bench, tests) used this name.
CountBatcher = ShardLegBatcher
