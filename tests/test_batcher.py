"""Unified shard-leg batching plane (exec/batcher.py, ISSUE r11).

Two layers of coverage:
- StubBackend tests exercise the batcher's composition contract with no
  device (or jax) dependency: deterministic windows via window > 0,
  mixed-kind grouping (Count + Row + Sum + TopN legs drained together
  land in per-kind groups, one backend dispatch each), identical-leg
  dedupe for the synchronous kinds, per-slot query-id result scatter,
  error isolation (one bad leg fails only its submitter), and the
  occupancy/coalesce telemetry.
- Differential tests (skipped where the device backend can't import)
  prove batched results identical to the unbatched path for
  Count/Row/Sum/Min/Max/TopN under concurrent submission — the ISSUE
  r11 acceptance bar.
"""

import threading
import time

import numpy as np
import pytest

from pilosa_tpu.exec.batcher import CountBatcher, ShardLegBatcher
from pilosa_tpu.utils import qprofile
from pilosa_tpu.utils.qprofile import DRAIN_STEPS, current_profile, profile_scope
from pilosa_tpu.utils.stats import StatsClient, global_stats


class StubBackend:
    """Deterministic fake of the device backend's batched entry points.

    Count calls are ints; a count resolves to call*10 so scatter order is
    checkable. Row calls resolve to ("row", call). BSI aggregates return
    (value, count) derived from the field name; TopN returns a ranked
    list the batcher must trim per leg. Every dispatch is recorded."""

    BAD = object()  # a call whose presence fails any dispatch it rides in

    def __init__(self):
        self.count_groups = []
        self.row_groups = []
        self.bsi_calls = []
        self.topn_calls = []
        self.individual_counts = []
        self.fail_count_groups = False

    # -- count legs --------------------------------------------------------

    def count_batch_async(self, index, calls, shards):
        if self.fail_count_groups and len(calls) > 1:
            raise RuntimeError("injected group failure")
        if any(c is self.BAD for c in calls):
            if len(calls) == 1:
                self.individual_counts.append(list(calls))
            raise ValueError("bad call")
        if len(calls) == 1 and self.fail_count_groups:
            self.individual_counts.append(list(calls))
        self.count_groups.append((list(calls), tuple(shards)))
        values = [c * 10 for c in calls]
        return lambda: values

    # -- row legs ----------------------------------------------------------

    def row_batch_async(self, index, calls, shards):
        if any(c is self.BAD for c in calls):
            raise ValueError("bad row call")
        self.row_groups.append((list(calls), tuple(shards)))
        rows = [("row", c) for c in calls]
        return lambda: rows

    def bitmap_call(self, index, call, shards):
        if call is self.BAD:
            raise ValueError("bad row call")
        return ("row", call)

    # -- synchronous kinds -------------------------------------------------

    def bsi_sum(self, index, field, shards, filter_call=None):
        if field == "boom":
            raise ValueError("bad field")
        self.bsi_calls.append(("bsi_sum", field, filter_call))
        return (len(field) * 100, 7)

    def bsi_min(self, index, field, shards, filter_call=None):
        self.bsi_calls.append(("bsi_min", field, filter_call))
        return (1, 2)

    def topn_field(self, index, field, shards, n, src_call=None):
        assert n == 0, "batcher must request the full ranked vector"
        self.topn_calls.append((field, src_call))
        return [(r, 50 - r) for r in range(5)]


def _run_threads(fns):
    """Run callables concurrently; return per-fn (result | exception)."""
    out = [None] * len(fns)

    def wrap(k):
        try:
            out[k] = fns[k]()
        except Exception as e:  # noqa: BLE001 — asserted by callers
            out[k] = e

    threads = [threading.Thread(target=wrap, args=(k,)) for k in range(len(fns))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


class TestLegComposition:
    def test_mixed_kinds_group_per_kind(self):
        """Count + Row + Sum + TopN legs drained in one window land in
        per-kind groups: one count dispatch carrying every count call,
        one row dispatch, deduped sync calls."""
        be = StubBackend()
        b = ShardLegBatcher(be, window=0.3)
        shards = [0, 1]
        filt = object()  # shared filter tree (parse-cache identity)
        fns = [
            lambda: b.count("i", [1, 2], shards),
            lambda: b.count("i", [3], shards),
            lambda: b.row("i", "rowA", shards),
            lambda: b.row("i", "rowB", shards),
            lambda: b.bsi("bsi_sum", "i", "v", shards, None),
            lambda: b.bsi("bsi_sum", "i", "v", shards, None),  # dedupes
            lambda: b.bsi("bsi_min", "i", "v", shards, None),
            lambda: b.topn("i", "f", shards, 2, filt),
            lambda: b.topn("i", "f", shards, 0, filt),  # shares the launch
        ]
        got = _run_threads(fns)
        assert not any(isinstance(g, Exception) for g in got), got
        # One count dispatch carried all three calls (leader order may
        # interleave legs, but the group is singular and complete).
        assert len(be.count_groups) == 1
        assert sorted(be.count_groups[0][0]) == [1, 2, 3]
        assert sorted(got[0]) + got[1] == [10, 20, 30]
        # One row launch with both legs' calls; per-leg results.
        assert len(be.row_groups) == 1
        assert sorted(be.row_groups[0][0]) == ["rowA", "rowB"]
        assert got[2] == ("row", "rowA") and got[3] == ("row", "rowB")
        # Identical Sum legs deduped to ONE backend call; Min separate.
        assert be.bsi_calls.count(("bsi_sum", "v", None)) == 1
        assert be.bsi_calls.count(("bsi_min", "v", None)) == 1
        assert got[4] == got[5] == (100, 7)
        assert got[6] == (1, 2)
        # TopN shared one ranked-vector computation; n trimmed per leg.
        assert len(be.topn_calls) == 1
        assert got[7] == [(0, 50), (1, 49)]
        assert len(got[8]) == 5

    def test_count_scatter_respects_leg_boundaries(self):
        be = StubBackend()
        b = ShardLegBatcher(be, window=0.2)
        got = _run_threads([
            lambda: b.count("i", [1, 2], [0]),
            lambda: b.count("i", [7], [0]),
        ])
        assert got[0] == [10, 20]
        assert got[1] == [70]

    def test_distinct_shard_sets_do_not_share_a_group(self):
        be = StubBackend()
        b = ShardLegBatcher(be, window=0.2)
        got = _run_threads([
            lambda: b.count("i", [1], [0]),
            lambda: b.count("i", [2], [0, 1]),
        ])
        assert got[0] == [10] and got[1] == [20]
        assert len(be.count_groups) == 2
        assert {g[1] for g in be.count_groups} == {(0,), (0, 1)}

    def test_uncontended_leg_dispatches_immediately(self):
        """window=0: a lone leg pays no coalescing sleep and still works
        through every public submit method."""
        be = StubBackend()
        b = ShardLegBatcher(be, window=0.0)
        assert b.count("i", [4], [0]) == [40]
        assert b.row("i", "r", [0]) == ("row", "r")
        assert b.bsi("bsi_sum", "i", "v", [0]) == (100, 7)
        assert b.topn("i", "f", [0], 1) == [(0, 50)]

    def test_countbatcher_alias(self):
        assert CountBatcher is ShardLegBatcher

    @pytest.mark.parametrize("n_sums", [0, 1, 10])
    def test_a_run_of_legs_is_one_trip_and_one_drain(self, n_sums):
        """`submit` queues a request's run of legs (a TopN and n Sums) in
        one visit to the lock: one trip, one drain that takes them all,
        every leg resolved when it returns, each with its own answer."""
        be = StubBackend()
        b = ShardLegBatcher(be)
        b.stats = StatsClient()
        filters = [object() for _ in range(n_sums)]
        legs = [b.topn_leg("i", "f", [0, 1])] + [
            b.bsi_leg("bsi_sum", "i", "v" * (k + 1), [0, 1], filters[k])
            for k in range(n_sums)
        ]
        b.submit(legs)
        assert b._pending == [] and not b._leader_active
        assert all(leg.event.is_set() for leg in legs)
        assert [leg.value() for leg in legs[1:]] == [
            (100 * (k + 1), 7) for k in range(n_sums)
        ]
        assert legs[0].value() == [(r, 50 - r) for r in range(5)]
        assert all(leg.resolved_at >= leg.queued_at > 0 for leg in legs)
        assert len({leg.queued_at for leg in legs}) == 1  # stamped together
        assert be.bsi_calls == [
            ("bsi_sum", "v" * (k + 1), filters[k]) for k in range(n_sums)
        ]
        assert b.stats.counter_totals("batch_trips_total", "batch_drains_total") == {
            "batch_trips_total": 1.0, "batch_drains_total": 1.0,
        }
        legs_by_kind = b.stats.counter_totals("batch_legs_total")
        assert legs_by_kind.get('batch_legs_total{kind="bsi_sum"}', 0) == n_sums
        assert legs_by_kind['batch_legs_total{kind="topn"}'] == 1

    def test_every_one_leg_method_is_one_trip(self):
        be = StubBackend()
        b = ShardLegBatcher(be)
        b.stats = StatsClient()
        b.count("i", [4, 5], [0])
        b.row("i", "r", [0])
        b.bsi("bsi_min", "i", "v", [0])
        b.topn("i", "f", [0], 2)
        assert b.stats.counter_totals("batch_trips_total") == {
            "batch_trips_total": 4.0
        }


class TestErrorIsolation:
    def test_bad_count_leg_fails_only_its_submitter(self):
        be = StubBackend()
        b = ShardLegBatcher(be, window=0.25)
        got = _run_threads([
            lambda: b.count("i", [1], [0]),
            lambda: b.count("i", [StubBackend.BAD], [0]),
            lambda: b.count("i", [5], [0]),
        ])
        bads = [g for g in got if isinstance(g, ValueError)]
        goods = sorted(g[0] for g in got if isinstance(g, list))
        assert len(bads) == 1
        assert goods == [10, 50]

    def test_group_failure_retries_individually(self):
        """A whole-group dispatch failure re-dispatches each leg alone:
        every good leg still resolves, through the isolation path."""
        be = StubBackend()
        be.fail_count_groups = True
        b = ShardLegBatcher(be, window=0.25)
        got = _run_threads([
            lambda: b.count("i", [1], [0]),
            lambda: b.count("i", [2], [0]),
        ])
        assert sorted(g[0] for g in got) == [10, 20]

    def test_bad_row_leg_fails_only_its_submitter(self):
        be = StubBackend()
        b = ShardLegBatcher(be, window=0.25)
        got = _run_threads([
            lambda: b.row("i", "good", [0]),
            lambda: b.row("i", StubBackend.BAD, [0]),
        ])
        bads = [g for g in got if isinstance(g, ValueError)]
        assert len(bads) == 1
        assert ("row", "good") in got

    def test_bad_sync_leg_fails_only_its_dedupe_set(self):
        be = StubBackend()
        b = ShardLegBatcher(be, window=0.25)
        got = _run_threads([
            lambda: b.bsi("bsi_sum", "i", "v", [0]),
            lambda: b.bsi("bsi_sum", "i", "boom", [0]),
        ])
        bads = [g for g in got if isinstance(g, ValueError)]
        assert len(bads) == 1
        assert (100, 7) in got


    @pytest.mark.parametrize("bad_at", [0, 1, 2])
    def test_bad_leg_of_a_run_reaches_only_its_request(self, bad_at):
        """Two requests' runs share a drain (identical legs share one
        backend call); the leg that fails raises in its own request, at
        its own place, and nowhere else."""
        be = StubBackend()
        b = ShardLegBatcher(be, window=0.25)
        b.stats = StatsClient()
        fields = ["v", "vv", "vvv"]

        def run(names):
            def go():
                legs = [b.bsi_leg("bsi_sum", "i", n, [0]) for n in names]
                b.submit(legs)
                out = []
                for leg in legs:
                    try:
                        out.append(leg.value())
                    except ValueError as e:
                        out.append(e)
                return out
            return go

        with_bad = list(fields)
        with_bad.insert(bad_at, "boom")
        good, mixed = _run_threads([run(fields), run(with_bad)])
        assert good == [(100, 7), (200, 7), (300, 7)]
        assert isinstance(mixed.pop(bad_at), ValueError)
        assert mixed == good
        # One drain served both runs, and each distinct Sum once.
        assert b.stats.counter_totals("batch_drains_total", "batch_trips_total") == {
            "batch_drains_total": 1.0, "batch_trips_total": 2.0,
        }
        assert sorted(c[1] for c in be.bsi_calls) == fields


class TestTelemetry:
    def _counters(self):
        return dict(global_stats.snapshot()["counters"])

    def test_occupancy_and_coalesce_counters(self):
        before = self._counters()
        be = StubBackend()
        b = ShardLegBatcher(be, window=0.25)
        got = _run_threads([
            lambda: b.count("i", [1], [0]),
            lambda: b.count("i", [2], [0]),
            lambda: b.count("i", [3], [0]),
        ])
        assert sorted(g[0] for g in got) == [10, 20, 30]
        after = self._counters()

        def delta(name):
            return after.get(name, 0.0) - before.get(name, 0.0)

        assert delta('batch_legs_total{kind="count"}') == 3
        # 3 legs in one launch group = 2 coalesced beyond the first.
        assert delta('batch_coalesced_total{kind="count"}') == 2
        snap = global_stats.histogram_snapshot()
        occ = snap.get('batch_occupancy{kind="count"}')
        assert occ is not None and occ["count"] >= 1

    def test_histogram_mean_helper(self):
        from pilosa_tpu.utils.stats import histogram_mean

        assert histogram_mean({"sum": 12.0, "count": 3}) == 4.0
        assert histogram_mean(
            {"sum": 12.0, "count": 4}, {"sum": 2.0, "count": 2}
        ) == 5.0
        assert histogram_mean({"sum": 0.0, "count": 0}) is None


# ---------------------------------------------------------------------------
# Differential acceptance: batched == unbatched for every routed kind,
# under concurrent submission through the real executor + device backend.
# ---------------------------------------------------------------------------


@pytest.fixture
def device_backend_available():
    """Skip (never error) where the device backend can't import — the
    stub-backend half of this module must still run on a jax without
    shard_map (the same gate tests/test_bench_smoke.py uses)."""
    pytest.importorskip(
        "pilosa_tpu.exec.tpu",
        reason="device backend unavailable (jax.shard_map)",
        exc_type=ImportError,
    )


@pytest.fixture
def holder(tmp_path, device_backend_available):
    from pilosa_tpu.core import Holder

    h = Holder(str(tmp_path / "data")).open()
    yield h
    h.close()


def _build_index(holder, rng):
    from pilosa_tpu.core.field import options_for_int
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    idx = holder.create_index("i")
    for fname, rows in (("f", (1, 2)), ("g", (9,))):
        field = idx.create_field(fname)
        for row in rows:
            cols = np.unique(
                rng.integers(0, 2 * SHARD_WIDTH, 2500, dtype=np.uint64)
            )
            field.import_bits(np.full(cols.size, row, dtype=np.uint64), cols)
    v = idx.create_field("v", options_for_int(-1000, 1000))
    cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 400, dtype=np.uint64))
    v.import_value(cols, rng.integers(-900, 901, cols.size))


DIFF_QUERIES = [
    "Count(Intersect(Row(f=1), Row(g=9)))",
    "Count(Row(f=2))",
    "Row(f=1)",
    "Union(Row(f=1), Row(g=9))",
    "Intersect(Row(f=2), Row(g=9))",
    "Sum(field=v)",
    "Min(field=v)",
    "Max(field=v)",
    "Sum(Row(f=1), field=v)",
    "TopN(f, n=1)",
    "TopN(f)",
]


class TestBatchedDifferential:
    def test_batched_equals_unbatched_under_concurrency(self, holder, rng):
        """The ISSUE r11 differential gate: every routed leg kind returns
        byte-identical JSON through the batching plane (window > 0 so
        the legs REALLY coalesce) and through the plain oracle path."""
        from pilosa_tpu.exec import Executor
        from pilosa_tpu.exec.result import result_to_json
        from pilosa_tpu.exec.tpu import TPUBackend

        _build_index(holder, rng)
        oracle = Executor(holder)
        want = {q: result_to_json(oracle.execute("i", q)[0]) for q in DIFF_QUERIES}

        be = TPUBackend(holder)
        ex = Executor(holder, backend=be)
        ex.batcher = ShardLegBatcher(be, window=0.2)
        counters0 = dict(global_stats.snapshot()["counters"])

        def run(q):
            return lambda: result_to_json(ex.execute("i", q)[0])

        got = _run_threads([run(q) for q in DIFF_QUERIES])
        for q, g in zip(DIFF_QUERIES, got):
            assert not isinstance(g, Exception), (q, g)
            assert g == want[q], q
        # The window really coalesced: at least one multi-leg group.
        after = dict(global_stats.snapshot()["counters"])
        coalesced = sum(
            after.get(k, 0.0) - counters0.get(k, 0.0)
            for k in after
            if k.startswith("batch_coalesced_total")
        )
        assert coalesced >= 1

    def test_row_batch_async_direct(self, holder, rng):
        """row_batch_async alone: slot dedupe + scatter parity with
        bitmap_call, including an unsupported call's fallback slot."""
        from pilosa_tpu.exec.tpu import TPUBackend
        from pilosa_tpu.pql import parse_string

        _build_index(holder, rng)
        be = TPUBackend(holder)
        shards = [0, 1]
        calls = [
            parse_string("Row(f=1)").calls[0],
            parse_string("Union(Row(f=1), Row(g=9))").calls[0],
            parse_string("Row(f=1)").calls[0],  # dedupes with slot 0
        ]
        rows = be.row_batch_async("i", calls, shards)()
        for c, row in zip(calls, rows):
            want = be.bitmap_call("i", c, shards)
            np.testing.assert_array_equal(
                row.columns(), want.columns()
            )
        # Distinct legs never share a Row object (downstream mutates
        # attrs/keys per query).
        assert rows[0] is not rows[2]

    def test_executor_single_query_via_batcher_matches(self, holder, rng):
        """window=0 single legs through the executor: no coalescing, no
        added latency path — results still oracle-identical."""
        from pilosa_tpu.exec import Executor
        from pilosa_tpu.exec.result import result_to_json
        from pilosa_tpu.exec.tpu import TPUBackend

        _build_index(holder, rng)
        be = TPUBackend(holder)
        ex = Executor(holder, backend=be)
        ex.batcher = ShardLegBatcher(be, window=0.0)
        oracle = Executor(holder)
        for q in DIFF_QUERIES:
            assert result_to_json(ex.execute("i", q)[0]) == result_to_json(
                oracle.execute("i", q)[0]
            ), q


    @pytest.mark.parametrize("window, clients", [(0.0, 1), (0.15, 4)])
    def test_a_body_of_reads_through_the_device_backend_matches(
            self, window, clients, holder, rng):
        """A request's run of reads (ISSUE 33), alone and from several
        clients whose runs share drains: every result of every body is
        the oracle's, in call order; a write between two reads is read
        by the second."""
        from pilosa_tpu.exec import Executor
        from pilosa_tpu.exec.result import result_to_json
        from pilosa_tpu.exec.tpu import TPUBackend

        _build_index(holder, rng)
        be = TPUBackend(holder)
        ex = Executor(holder, backend=be)
        ex.batcher = ShardLegBatcher(be, window=window)
        ex.batcher.stats = StatsClient()
        oracle = Executor(holder)
        body = ("TopN(f) Sum(Row(f=1), field=v) Sum(Row(f=2), field=v) "
                "Min(field=v) Max(Row(g=9), field=v) TopN(f, Row(g=9), n=1) "
                "Count(Row(f=1)) Sum(field=v) GroupBy(Rows(f))")
        want = [result_to_json(r) for r in oracle.execute("i", body)]
        got = _run_threads([
            lambda: [result_to_json(r) for r in ex.execute("i", body)]
        ] * clients)
        assert got == [want] * clients
        # Each client: the six reads before the Count in one trip, the
        # Count's, the Sum's after it.
        assert ex.batcher.stats.counter_totals("batch_trips_total") == {
            "batch_trips_total": 3.0 * clients
        }
        if clients == 1:
            col = 7
            a, _, b = ex.execute("i", f"Sum(field=v) Set({col}, v=333) Sum(field=v)")
            assert oracle.execute("i", "Sum(field=v)")[0] == b
            assert (b.val, b.count) != (a.val, a.count)


# -- the plane's own profile (ISSUE 26) ----------------------------------

class SteppedBackend:
    """count_batch_async that opens the backend's steps as exec/tpu.py
    does (plan, slots, dispatch; device_wait, readback in the resolver),
    each `step_s` long, and can hold a dispatch at a gate."""

    def __init__(self, step_s=0.0):
        self.step_s = step_s
        self.gates = []  # one threading.Event per dispatch to hold, in order
        self.entered = threading.Semaphore(0)

    def count_batch_async(self, index, calls, shards):
        prof = current_profile()
        for step in ("plan", "slots"):
            with prof.phase(step):
                time.sleep(self.step_s)
        with prof.phase("dispatch", span="pilosa.count_batch",
                        legs=len(calls), slots=len(calls)):
            self.entered.release()
            if self.gates:
                assert self.gates.pop(0).wait(10)
            time.sleep(self.step_s)

        def resolve():
            prof_r = current_profile()
            for step in ("device_wait", "readback"):
                with prof_r.phase(step):
                    time.sleep(self.step_s)
            return [c * 10 for c in calls]

        return resolve


class _RecordingSpan:
    """Stands in for jax.profiler.TraceAnnotation: what was opened, on
    which thread, with what metadata."""

    log: list = []

    def __init__(self, name, **meta):
        self.name, self.meta = name, meta

    def __enter__(self):
        self.log.append((threading.current_thread().name, self.name, self.meta))
        return self

    def __exit__(self, *exc):
        pass


@pytest.fixture
def spans():
    before = qprofile._span_factory
    _RecordingSpan.log = []
    qprofile.set_span_factory(_RecordingSpan)
    yield _RecordingSpan.log
    qprofile.set_span_factory(before)


def _plane_batcher(step_s=0.0):
    be = SteppedBackend(step_s)
    b = ShardLegBatcher(be)
    b.stats = StatsClient()  # a registry of this test's own
    return be, b


def _step_table(stats, family="batch_step_seconds"):
    return {
        name.split('"')[1]: v
        for name, v in stats.timing_totals(family).items()
    }


def _leader_then_helper(be, b, when_leading=lambda: None):
    """One leg served by its own submitter and a second, queued while the
    first's dispatch is held at a gate, served by a helper thread."""
    gate = threading.Event()
    be.gates.append(gate)
    out = {}
    t1 = threading.Thread(
        target=lambda: out.setdefault(1, b.count("i", [1], [0])), name="client-1"
    )
    t1.start()
    assert be.entered.acquire(timeout=10)  # the leader is in its dispatch
    when_leading()
    t2 = threading.Thread(
        target=lambda: out.setdefault(2, b.count("i", [2], [0])), name="client-2"
    )
    t2.start()
    while True:  # the second leg is queued behind the leadership flag
        with b._lock:
            if b._pending:
                break
        time.sleep(0.001)
    gate.set()
    for t in (t1, t2):
        t.join(10)
        assert not t.is_alive()
    assert out == {1: [10], 2: [20]}


class TestPlaneProfile:
    def test_helper_drain_observes_every_step_once_in_order(self, spans):
        be, b = _plane_batcher()
        _leader_then_helper(be, b)
        helper = [
            (name, meta) for thread, name, meta in spans
            if thread.startswith("batcher-leader")
        ]
        want = [
            "pilosa.count_batch" if s == "dispatch" else "pilosa.drain." + s
            for s in DRAIN_STEPS
        ]
        assert [name for name, _ in helper] == want
        # One drain, one number on every span of it, and not the leader's.
        drains = {meta["drain"] for _, meta in helper}
        assert len(drains) == 1
        leader = [meta["drain"] for t, n, meta in spans if t == "client-1"]
        assert len(set(leader)) == 1 and set(leader) != drains
        assert [n for t, n, _ in spans if t == "client-1"] == want
        dispatch = dict(helper)["pilosa.count_batch"]
        assert dispatch["legs"] == 1 and dispatch["slots"] == 1
        # Both drains, every step: one observation each.
        steps = _step_table(b.stats)
        assert set(steps) == set(DRAIN_STEPS)
        assert all(n == 2 for _, n in steps.values())
        assert b.stats.counter_totals("batch_drains_total") == {
            "batch_drains_total": 2.0
        }

    def test_waiting_phase_carries_no_span(self, spans):
        be, b = _plane_batcher()
        with profile_scope(index="i", query="q", call="Count"):
            assert b.count("i", [1], [0]) == [10]
        assert not [n for _, n, _ in spans if "batch_wait" in n]

    def test_steps_of_a_drain_sum_to_its_wall(self):
        be, b = _plane_batcher(step_s=0.004)
        walls = []
        drain = b._drain

        def timed(leader_call):
            t0 = time.perf_counter()
            try:
                drain(leader_call)
            finally:
                walls.append(time.perf_counter() - t0)

        b._drain = timed
        for k in range(5):
            assert b.count("i", [k], [0]) == [k * 10]
        steps = _step_table(b.stats)
        stepped = sum(total for total, _ in steps.values())
        assert len(walls) == 5 and sum(walls) > 5 * 5 * 0.004
        # Laps: nothing lies between two steps, so what is missing is
        # only the activation around the first and after the last.
        assert stepped <= sum(walls)
        assert sum(walls) - stepped < 0.002 * len(walls)
        # The thread's CPU time beside each: a step that sleeps uses none.
        cpu = {
            n.split('"')[1]: v for n, v in
            b.stats.counter_totals("batch_step_cpu_seconds_total").items()
        }
        assert set(cpu) == set(steps)
        assert sum(cpu.values()) < 0.5 * stepped

    def test_queue_wait_one_observation_a_leg(self):
        be, b = _plane_batcher()
        for k in range(4):
            b.count("i", [k], [0])
        (total, n), = b.stats.timing_totals("batch_queue_wait_seconds").values()
        assert n == 4
        assert total < 4 * 0.001  # an uncontended leader takes its own leg at once
        be2, b2 = _plane_batcher()
        _leader_then_helper(be2, b2)
        (total2, n2), = b2.stats.timing_totals("batch_queue_wait_seconds").values()
        assert n2 == 2
        assert total2 > total / 4  # the queued leg waited out the gate

    def test_idle_does_not_grow_while_a_helper_loops(self):
        be, b = _plane_batcher()

        def idle():
            return b.stats.counter_totals("batch_idle_seconds_total").get(
                "batch_idle_seconds_total", 0.0
            )

        b.count("i", [0], [0])  # the first leader: nothing to add yet
        assert idle() == 0.0
        time.sleep(0.05)
        # The plane stood idle for those 50 ms, and the next leader says
        # so as it takes the flag ...
        be.step_s = 0.02
        seen = []
        _leader_then_helper(be, b, when_leading=lambda: seen.append(idle()))
        assert seen[0] > 0.04
        # ... while the stretch in which the helper served (5 steps of 20
        # ms at least) added nothing: leadership was never released.
        assert idle() == seen[0]
        assert _step_table(b.stats)["device_wait"][0] > 0.035
        time.sleep(0.03)
        b.count("i", [3], [0])
        assert idle() - seen[0] > 0.025

    def test_leader_phases_keep_their_meaning(self):
        """The leader's request profile hears of the shared work under
        the names /metrics has always had: plan, device_dispatch (the
        dispatch and the device wait), host_reduce (the readback), and
        nothing under a step's name. A follower's is its batch_wait."""
        be, b = _plane_batcher(step_s=0.01)
        with profile_scope(index="i", query="q", call="Count") as prof:
            b.count("i", [1], [0])
            assert current_profile() is prof
        assert set(prof.phases) == {
            "plan", "device_dispatch", "host_reduce", "batch_wait"
        }
        assert prof.phases["device_dispatch"] == pytest.approx(0.02, abs=0.01)
        assert prof.phases["plan"] == pytest.approx(0.01, abs=0.008)
        assert prof.phases["host_reduce"] == pytest.approx(0.01, abs=0.008)
        steps = _step_table(b.stats)
        assert steps["dispatch"][0] + steps["device_wait"][0] == pytest.approx(
            prof.phases["device_dispatch"]
        )
