"""Query-lifecycle telemetry tests (ISSUE r6): per-phase attribution,
/debug/queries + /debug/vars, the freshness-walk counters' O(dirty)
invariant, the slow-query log, and bench.py's capture-proof retry."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.exec import Executor
from pilosa_tpu.server.api import API
from pilosa_tpu.server.http import Server, _HTTPServer
from pilosa_tpu.utils.qprofile import (
    QueryProfile,
    current_profile,
    global_query_ring,
    profile_scope,
)
from pilosa_tpu.utils.stats import global_stats


def counter_sum(prefix: str) -> float:
    """Sum of every counter series whose name starts with prefix (series
    names carry tags, e.g. version_walk_total{kind="full",tier="sum"})."""
    snap = global_stats.snapshot()
    return sum(v for k, v in snap["counters"].items() if k.startswith(prefix))


@pytest.fixture
def server(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    srv = Server(API(holder, Executor(holder)), host="localhost", port=0).open()
    yield srv
    srv.close()
    holder.close()


def req(srv, method, path, body=None, ctype="text/plain"):
    data = None
    if body is not None:
        data = body if isinstance(body, bytes) else body.encode()
    r = urllib.request.Request(
        srv.uri + path, data=data, method=method,
        headers={"Content-Type": ctype},
    )
    return json.loads(urllib.request.urlopen(r).read())


class TestQueryProfile:
    def test_phases_accumulate_and_nest(self):
        with profile_scope(index="i", query="Count(Row(f=1))") as outer:
            outer.add_phase("parse", 0.001)
            # A nested scope must reuse the outer profile.
            with profile_scope(index="other") as inner:
                assert inner is outer
                inner.add_phase("parse", 0.002)
                inner.incr("version_walk_full", 3)
            assert current_profile() is outer
        assert current_profile().__class__.__name__ == "NopProfile"
        assert outer.phases["parse"] == pytest.approx(0.003)
        assert outer.counters == {"version_walk_full": 3}
        assert outer.duration is not None

    def test_ring_records_and_histograms_export(self):
        with profile_scope(index="i", query="q", call="Count") as prof:
            prof.add_phase("host_reduce", 0.004)
        recent = global_query_ring.recent(5)
        assert recent and recent[0]["qid"] == prof.qid
        assert recent[0]["phasesMs"]["host_reduce"] == pytest.approx(4.0)
        assert recent[0]["inFlight"] is False
        snap = global_stats.snapshot()
        key = 'query_phase_seconds{call="Count",phase="host_reduce"}'
        assert key in snap["timings"]
        assert snap["timings"][key]["count"] >= 1

    def test_error_recorded(self):
        with pytest.raises(ValueError):
            with profile_scope(index="i", query="boom") as prof:
                raise ValueError("the failure")
        assert "the failure" in prof.error
        assert any(
            r["qid"] == prof.qid and "error" in r
            for r in global_query_ring.recent(10)
        )

    def test_unattributed_never_negative(self):
        p = QueryProfile()
        p.add_phase("parse", 99.0)  # more than the real elapsed time
        p.finish()
        assert p.unattributed() == 0.0


class TestDebugEndpoints:
    def test_debug_queries_live_data(self, server):
        req(server, "POST", "/index/i", b"{}", ctype="application/json")
        req(server, "POST", "/index/i/field/f", b"{}", ctype="application/json")
        req(server, "POST", "/index/i/query", "Set(10, f=1)")
        out = req(server, "POST", "/index/i/query", "Count(Row(f=1))")
        assert out == {"results": [1]}
        # The Count's profile enters `recent` when its scope exits,
        # which happens AFTER the reply bytes reached this in-process
        # client — one GIL slice later. quiesce() is the server's
        # finalization barrier for exactly that window (ISSUE r13;
        # this used to be an ad-hoc poll loop).
        assert server.quiesce(timeout=5.0)
        dbg = req(server, "GET", "/debug/queries?n=10")
        assert "inflight" in dbg and "recent" in dbg
        counts = [
            r for r in dbg["recent"]
            if r["call"] == "Count" and r["query"].startswith("Count(")
        ]
        assert counts, dbg["recent"]
        entry = counts[0]
        assert entry["index"] == "i"
        assert entry["query"].startswith("Count(")
        # The serving path must attribute real phases end to end.
        assert "parse" in entry["phasesMs"]
        assert "serialize" in entry["phasesMs"]
        assert entry["elapsedMs"] > 0

    def test_phase_histograms_on_metrics(self, server):
        req(server, "POST", "/index/i", b"{}", ctype="application/json")
        req(server, "POST", "/index/i/field/f", b"{}", ctype="application/json")
        req(server, "POST", "/index/i/query", "Count(Row(f=1))")
        text = urllib.request.urlopen(server.uri + "/metrics").read().decode()
        assert 'pilosa_query_phase_seconds_count{call="Count",phase="parse"}' in text
        assert 'phase="serialize"' in text

    def test_debug_vars_live_data(self, server):
        req(server, "GET", "/version")
        out = req(server, "GET", "/debug/vars")
        assert out["version"]
        assert out["uptimeSeconds"] >= 0
        assert any(
            k.startswith("http_requests_total") for k in out["counters"]
        ), list(out["counters"])[:5]
        # Timing series carry the monotonic count/sum pair.
        t = [k for k in out["timings"] if k.startswith("http_request_duration_seconds")]
        assert t and out["timings"][t[0]]["count"] >= 1

    def test_connection_abort_counted(self, server):
        """A handler hitting a client reset mid-response must count the
        abort instead of 500ing (VERDICT r5 #1c). Injected by making one
        route raise ConnectionResetError — the deterministic equivalent
        of the client vanishing between headers and body write."""
        handler_cls = server._httpd.RequestHandlerClass

        def aborting(self):
            raise ConnectionResetError("client went away")

        import http.client

        before = counter_sum("http_connection_aborts_total")
        handler_cls.handle_home = aborting
        try:
            # The server sends nothing back, so the client sees the
            # connection die (RemoteDisconnected / reset, depending on
            # how urllib surfaces it).
            with pytest.raises(
                (urllib.error.URLError, OSError, http.client.HTTPException)
            ):
                urllib.request.urlopen(server.uri + "/", timeout=5)
        finally:
            del handler_cls.handle_home
        assert counter_sum("http_connection_aborts_total") == before + 1

    def test_request_queue_size_raised(self, server):
        # The bench's 16 clients + writer overflowed the default 5-deep
        # listen backlog (the BENCH_r05 reset); 128 is the floor now.
        assert _HTTPServer.request_queue_size >= 128
        assert isinstance(server._httpd, _HTTPServer)


class TestSlowQueryLog:
    def test_fires_with_phase_breakdown(self, tmp_path):
        holder = Holder(str(tmp_path / "data")).open()
        try:
            ex = Executor(holder)
            lines = []

            class CaptureLogger:
                def printf(self, fmt, *args):
                    lines.append(fmt % args if args else fmt)

            ex.logger = CaptureLogger()
            ex.long_query_time = 0.0  # every query exceeds the threshold
            holder.create_index("i").create_field("f")
            ex.execute("i", "Set(3, f=2)")
            ex.execute("i", "Count(Row(f=2))")
            assert lines, "slow-query log never fired"
            assert "longQueryTime exceeded" in lines[-1]
            assert "qid=" in lines[-1]
            assert "parse=" in lines[-1]  # the phase breakdown rides along
        finally:
            holder.close()

    def test_quiet_above_threshold(self, tmp_path):
        holder = Holder(str(tmp_path / "data")).open()
        try:
            ex = Executor(holder)
            lines = []
            ex.logger = type(
                "L", (), {"printf": lambda self, fmt, *a: lines.append(fmt)}
            )()
            ex.long_query_time = 60.0
            holder.create_index("i").create_field("f")
            ex.execute("i", "Count(Row(f=1))")
            assert not lines
        finally:
            holder.close()


class TestVersionWalkCounters:
    """The freshness-walk assertion VERDICT r5 next-round #2 asked for:
    under point-write churn the journal-backed tiers must pay O(dirty)
    per-shard version reads, never a full O(shards) walk."""

    N_SHARDS = 6

    def _build(self, holder):
        from pilosa_tpu.core.field import options_for_int
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        idx = holder.create_index("i")
        f = idx.create_field("v", options_for_int(-10000, 10000))
        rng = np.random.default_rng(17)
        for shard in range(self.N_SHARDS):
            cols = (
                np.unique(rng.integers(0, SHARD_WIDTH, 40, dtype=np.uint64))
                + shard * SHARD_WIDTH
            )
            f.import_value(cols, rng.integers(-9000, 9001, cols.size))
        return f

    def test_sum_epoch_walks_are_journal_backed_o_dirty(self):
        tpu = pytest.importorskip(
            "pilosa_tpu.exec.tpu",
            reason="device backend needs jax.shard_map",
            exc_type=ImportError,
        )
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        holder = Holder(None).open()
        try:
            self._build(holder)
            be = tpu.TPUBackend(holder)
            ex = Executor(holder, backend=be)
            oracle = Executor(holder)

            first = ex.execute("i", "Sum(field=v)")[0]
            assert first.count > 0
            ex.execute("i", "Sum(field=v)")  # generation-keyed cache hit

            j_walks0 = counter_sum('version_walk_total{kind="journal",tier="sum"}')
            j_shards0 = counter_sum(
                'version_walk_shards_total{kind="journal",tier="sum"}'
            )
            f_shards0 = counter_sum(
                'version_walk_shards_total{kind="full",tier="sum"}'
            )
            incr0 = counter_sum("sum_incremental_updates_total")

            # Churn: EPOCHS point writes, each dirtying exactly one shard,
            # each followed by a Sum that must absorb it incrementally.
            epochs = 4
            rng = np.random.default_rng(3)
            for e in range(epochs):
                shard = e % self.N_SHARDS
                col = shard * SHARD_WIDTH + int(rng.integers(0, SHARD_WIDTH))
                ex.execute("i", f"Set({col}, v={int(rng.integers(-9000, 9001))})")
                got = ex.execute("i", "Sum(field=v)")[0]
                want = oracle.execute("i", "Sum(field=v)")[0]
                assert (got.val, got.count) == (want.val, want.count)

            j_walks = (
                counter_sum('version_walk_total{kind="journal",tier="sum"}')
                - j_walks0
            )
            j_shards = (
                counter_sum('version_walk_shards_total{kind="journal",tier="sum"}')
                - j_shards0
            )
            f_shards = (
                counter_sum('version_walk_shards_total{kind="full",tier="sum"}')
                - f_shards0
            )
            incr = counter_sum("sum_incremental_updates_total") - incr0
            assert incr == epochs, "epochs were not absorbed incrementally"
            assert j_walks == epochs
            # THE O(dirty) claim: one locked version read per dirty shard
            # per epoch — not N_SHARDS per epoch.
            assert j_shards == epochs
            # And the epoch path never fell back to a full walk.
            assert f_shards == 0
        finally:
            holder.close()

    def test_full_walk_counted_per_tier(self):
        tpu = pytest.importorskip(
            "pilosa_tpu.exec.tpu",
            reason="device backend needs jax.shard_map",
            exc_type=ImportError,
        )
        holder = Holder(None).open()
        try:
            self._build(holder)
            be = tpu.TPUBackend(holder)
            ex = Executor(holder, backend=be)
            before = counter_sum('version_walk_shards_total{kind="full",tier="sum"}')
            ex.execute("i", "Sum(field=v)")  # cold: pre-vers + confirm walks
            delta = (
                counter_sum('version_walk_shards_total{kind="full",tier="sum"}')
                - before
            )
            assert delta > 0
            assert delta % self.N_SHARDS == 0  # full walks read every shard
        finally:
            holder.close()


class TestJournalCompleteFreshness:
    """ISSUE r7 tentpole: the pair, TopN, and GroupN serving tiers must
    route epoch freshness through the journal-backed _epoch_versions —
    under point-write churn their version_walk_total{kind=full} stays
    FLAT while kind=journal pays exactly the dirty set."""

    N_SHARDS = 6
    ROWS = 4

    def _tpu(self):
        return pytest.importorskip(
            "pilosa_tpu.exec.tpu",
            reason="device backend needs jax.shard_map",
            exc_type=ImportError,
        )

    def _build(self, holder, fields=("f", "g")):
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        idx = holder.create_index("i")
        rng = np.random.default_rng(23)
        for fname in fields:
            f = idx.create_field(fname)
            for shard in range(self.N_SHARDS):
                cols = (
                    np.unique(
                        rng.integers(0, SHARD_WIDTH, 300, dtype=np.uint64)
                    )
                    + shard * SHARD_WIDTH
                )
                f.import_bits(
                    rng.integers(0, self.ROWS, cols.size, dtype=np.uint64),
                    cols,
                )

    def _set_stmt(self, rng, field="f"):
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        shard = int(rng.integers(0, self.N_SHARDS))
        col = shard * SHARD_WIDTH + int(rng.integers(0, SHARD_WIDTH))
        return f"Set({col}, {field}={int(rng.integers(0, self.ROWS))})"

    def _walks(self, tier):
        return {
            kind: (
                counter_sum(f'version_walk_total{{kind="{kind}",tier="{tier}"}}'),
                counter_sum(
                    f'version_walk_shards_total{{kind="{kind}",tier="{tier}"}}'
                ),
            )
            for kind in ("full", "journal")
        }

    def test_pair_churn_walks_journal_backed(self):
        tpu = self._tpu()
        from pilosa_tpu.pql import parse_string

        holder = Holder(None).open()
        try:
            self._build(holder)
            be = tpu.TPUBackend(holder)
            ex = Executor(holder, backend=be)
            oracle = Executor(holder)
            shards = list(range(self.N_SHARDS))
            queries = [
                "Count(Intersect(Row(f=1), Row(g=2)))",
                "Count(Union(Row(f=0), Row(g=3)))",
            ]
            calls = [parse_string(q).calls[0].children[0] for q in queries]
            be.count_batch("i", calls, shards)  # warm: sweep + full walks
            w0 = self._walks("pair")
            rng = np.random.default_rng(11)
            epochs = 5
            for _ in range(epochs):
                ex.execute("i", self._set_stmt(rng))
                got = be.count_batch("i", calls, shards)
                want = [oracle.execute("i", f"{q}")[0] for q in queries]
                assert got == want
            w1 = self._walks("pair")
            # Zero full walks under churn — the acceptance bar.
            assert w1["full"] == w0["full"]
            # Each epoch walks both pair sides through the journal; only
            # f's one dirtied shard pays a locked read.
            assert w1["journal"][0] - w0["journal"][0] == 2 * epochs
            assert w1["journal"][1] - w0["journal"][1] == epochs
        finally:
            holder.close()

    def test_topn_churn_walks_journal_backed(self):
        tpu = self._tpu()
        holder = Holder(None).open()
        try:
            self._build(holder, fields=("f",))
            be = tpu.TPUBackend(holder)
            ex = Executor(holder, backend=be)
            oracle = Executor(holder)
            shards = list(range(self.N_SHARDS))
            be.topn_field("i", "f", shards, 0)  # warm
            w0 = self._walks("topn")
            rng = np.random.default_rng(13)
            epochs = 5
            for _ in range(epochs):
                ex.execute("i", self._set_stmt(rng))
                got = ex.execute("i", "TopN(f, n=8)")
                want = oracle.execute("i", "TopN(f, n=8)")
                assert got == want
            w1 = self._walks("topn")
            assert w1["full"] == w0["full"]
            assert w1["journal"][0] - w0["journal"][0] == epochs
            assert w1["journal"][1] - w0["journal"][1] == epochs
        finally:
            holder.close()

    def test_groupn_churn_walks_journal_backed(self):
        tpu = self._tpu()
        holder = Holder(None).open()
        try:
            self._build(holder, fields=("f", "g", "h"))
            be = tpu.TPUBackend(holder)
            ex = Executor(holder, backend=be)
            oracle = Executor(holder)
            q = "GroupBy(Rows(f), Rows(g), Rows(h))"
            assert ex.execute("i", q) == oracle.execute("i", q)  # warm
            w0 = self._walks("groupn")
            rng = np.random.default_rng(29)
            epochs = 4
            for _ in range(epochs):
                ex.execute("i", self._set_stmt(rng))
                assert ex.execute("i", q) == oracle.execute("i", q)
            w1 = self._walks("groupn")
            assert w1["full"] == w0["full"]
            # Three fields walked per epoch; one dirtied shard total.
            assert w1["journal"][0] - w0["journal"][0] == 3 * epochs
            assert w1["journal"][1] - w0["journal"][1] == epochs
        finally:
            holder.close()

    def test_groupn_redispatch_confirm_journal_backed(self):
        """A forced groupn re-dispatch (tensor caches dropped) pays only
        the 3 unavoidable cold pre-vers full walks — the post-fetch
        confirm rides the journal (ISSUE 17 satellite: the r13 groupby
        leg showed 12 full walks = 2 executes x (3 pre + 3 confirm))."""
        tpu = self._tpu()
        holder = Holder(None).open()
        try:
            self._build(holder, fields=("f", "g", "h"))
            be = tpu.TPUBackend(holder)
            ex = Executor(holder, backend=be)
            q = "GroupBy(Rows(f), Rows(g), Rows(h))"
            ex.execute("i", q)  # warm: compile + first dispatch
            be._groupn_cache.clear()
            be._agg_cache.clear()
            w0 = self._walks("groupn")
            ex.execute("i", q)
            w1 = self._walks("groupn")
            assert w1["full"][0] - w0["full"][0] == 3
            assert w1["full"][1] - w0["full"][1] == 3 * self.N_SHARDS
            # The confirm side: journal walks with ZERO locked shard
            # reads (nothing dirtied between snapshot and fetch).
            assert w1["journal"][0] - w0["journal"][0] == 3
            assert w1["journal"][1] - w0["journal"][1] == 0
        finally:
            holder.close()

    def test_epoch_versions_differential_vs_live(self):
        """Journal-derived versions must equal the full locked walk in
        every regime: journal-covered epochs, evicted windows, and
        structural (new-fragment) events."""
        tpu = self._tpu()
        from pilosa_tpu.core.view import VIEW_STANDARD
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        holder = Holder(None).open()
        try:
            self._build(holder)
            be = tpu.TPUBackend(holder)
            ex = Executor(holder, backend=be)
            f = be._field("i", "f")
            shards_t = tuple(range(self.N_SHARDS))
            rng = np.random.default_rng(31)

            def snap():
                v = f.view(VIEW_STANDARD)
                return be._live_versions(f, shards_t), v.generation

            # journal-covered: a few point writes
            vers_old, gen_old = snap()
            for _ in range(3):
                ex.execute("i", self._set_stmt(rng))
            assert be._epoch_versions(
                f, shards_t, VIEW_STANDARD, vers_old, gen_old
            ) == be._live_versions(f, shards_t)

            # evicted window: more writes than the journal retains
            from pilosa_tpu.core.view import View

            vers_old, gen_old = snap()
            for _ in range(View.JOURNAL_MAX + 8):
                ex.execute("i", self._set_stmt(rng))
            assert be._epoch_versions(
                f, shards_t, VIEW_STANDARD, vers_old, gen_old
            ) == be._live_versions(f, shards_t)

            # structural event: a write creating a NEW shard's fragment
            vers_old, gen_old = snap()
            ex.execute(
                "i", f"Set({self.N_SHARDS * SHARD_WIDTH + 7}, f=1)"
            )
            shards_t2 = tuple(range(self.N_SHARDS + 1))
            live = be._live_versions(f, shards_t2)
            assert be._epoch_versions(
                f, shards_t2, VIEW_STANDARD,
                vers_old + (None,), gen_old
            ) == live
        finally:
            holder.close()


class TestBenchCaptureProof:
    def test_post_retries_once_on_reset(self, server):
        """The r5 failure shape: ONE mid-run connection reset must cost a
        counted retry, not the whole artifact (fault injected through the
        FaultProxy fixture's one-shot RST mode)."""
        from bench import RETRIES, BenchConn
        from tests.cluster_harness import FaultProxy

        req(server, "POST", "/index/i", b"{}", ctype="application/json")
        req(server, "POST", "/index/i/field/f", b"{}", ctype="application/json")
        req(server, "POST", "/index/i/query", "Set(7, f=1)")

        proxy = FaultProxy(server.host, server.port)
        try:
            bc = BenchConn("127.0.0.1", proxy.port, "/index/i/query")
            assert bc.post("Count(Row(f=1))") == [1]
            before = RETRIES["post"]
            proxy.mode = "reset_once"
            bc.conn.close()  # force the next post onto a fresh (reset) conn
            assert bc.post("Count(Row(f=1))") == [1]
            assert RETRIES["post"] == before + 1
            # The proxy reverted: further posts are clean, no extra retry.
            assert bc.post("Count(Row(f=1))") == [1]
            assert RETRIES["post"] == before + 1
            bc.close()
        finally:
            proxy.close()

    def test_second_consecutive_failure_propagates(self, server):
        from bench import BenchConn
        from tests.cluster_harness import FaultProxy

        proxy = FaultProxy(server.host, server.port)
        try:
            proxy.mode = "refuse"  # every connection dies: systemic
            bc = BenchConn("127.0.0.1", proxy.port, "/index/i/query")
            with pytest.raises(Exception):
                bc.post("Count(Row(f=1))")
            bc.close()
        finally:
            proxy.close()

    def test_phase_means_parser(self):
        from bench import phase_means_ms

        text = (
            'pilosa_query_phase_seconds_count{call="Count",phase="parse"} 4\n'
            'pilosa_query_phase_seconds_sum{call="Count",phase="parse"} 0.002\n'
            'pilosa_query_phase_seconds_count{call="Row",phase="parse"} 6\n'
            'pilosa_query_phase_seconds_sum{call="Row",phase="parse"} 0.004\n'
            'pilosa_query_phase_seconds_count{call="Count",phase="serialize"} 4\n'
            'pilosa_query_phase_seconds_sum{call="Count",phase="serialize"} 0.008\n'
            "pilosa_other_metric 3\n"
        )
        means = phase_means_ms(text)
        assert means["parse"] == pytest.approx(0.6)  # merged across calls
        assert means["serialize"] == pytest.approx(2.0)

    def test_phase_means_baseline_diff(self):
        """The registry is cumulative: the HTTP leg's means must diff out
        earlier in-process legs' histograms (code review r6)."""
        from bench import phase_means_ms, phase_totals

        before = (
            'pilosa_query_phase_seconds_count{call="Count",phase="parse"} 10\n'
            'pilosa_query_phase_seconds_sum{call="Count",phase="parse"} 1.0\n'
        )
        after = (
            'pilosa_query_phase_seconds_count{call="Count",phase="parse"} 14\n'
            'pilosa_query_phase_seconds_sum{call="Count",phase="parse"} 1.002\n'
        )
        means = phase_means_ms(after, baseline=phase_totals(before))
        # 4 new queries costing 2 ms total -> 0.5 ms mean, not the
        # cumulative 1.002/14.
        assert means["parse"] == pytest.approx(0.5)


class TestDrainAndSetupTelemetry:
    """ISSUE 26: the plane's steps through the real backend, the program
    names a trace carries, a device wait that is one, and the set-up and
    stop-the-world counters."""

    @pytest.fixture
    def served(self, tmp_path):
        tpu = pytest.importorskip(
            "pilosa_tpu.exec.tpu",
            reason="device backend needs jax.shard_map",
            exc_type=ImportError,
        )
        from pilosa_tpu.exec.batcher import ShardLegBatcher

        holder = Holder(str(tmp_path / "data")).open()
        idx = holder.create_index("i")
        for name in ("f", "g", "h"):
            idx.create_field(name)
        be = tpu.TPUBackend(holder)
        ex = Executor(holder, backend=be)
        ex.batcher = ShardLegBatcher(be)
        ex.execute("i", "Set(10, f=1) Set(10, g=2) Set(10, h=1) Set(1048577, f=1)")
        yield holder, be, ex
        holder.close()

    @staticmethod
    def _steps():
        return {
            name.split('"')[1]: v
            for name, v in global_stats.timing_totals("batch_step_seconds").items()
        }

    def test_count_drain_steps_and_request_phases(self, served):
        holder, be, ex = served
        q = "Count(Intersect(Row(f=1), Row(g=2), Row(h=1)))"
        before = self._steps()
        with profile_scope(index="i", query=q, call="Count") as prof:
            assert ex.execute("i", q) == [1]
        grew = {
            s for s, (_, n) in self._steps().items()
            if n > before.get(s, (0.0, 0))[1]
        }
        from pilosa_tpu.utils.qprofile import DRAIN_STEPS

        assert grew == set(DRAIN_STEPS)
        # The leader's own table: the names /metrics has always had.
        assert {"plan", "device_dispatch", "host_reduce", "batch_wait"} <= set(
            prof.phases
        )
        assert not set(prof.phases) & {
            "take", "group", "slots", "dispatch", "device_wait", "readback",
            "scatter", "handoff",
        }
        # The asynchronous call's wall is dispatch; the wait is block_ready's.
        assert prof.counters["device_launches"] == 1
        assert prof.counters["dispatch_us"] > 0
        assert prof.counters["device_wait_us"] > 0

    def test_lowered_count_batch_module_is_named_for_its_kind(self, served):
        from pilosa_tpu.pql import parse_string

        holder, be, ex = served
        call = parse_string("Intersect(Row(f=1), Row(g=2), Row(h=1))").calls[0]
        spec, blocks, scalars = be._assemble("i", call, (0, 1))
        slots = be._padded_slot_scalars([scalars, scalars], 2)
        program = be._program("count_batch", spec, True)
        text = program.__wrapped__.lower(blocks, slots).as_text()
        assert "jit_pilosa_count_batch" in text[:400]
        one = be._program("count", spec, True).__wrapped__.lower(blocks, scalars)
        assert "jit_pilosa_count" in one.as_text()[:400]

    def test_stack_build_and_holder_open_close_series(self, served, tmp_path):
        holder, be, ex = served
        builds0 = global_stats.timing_totals("stack_build_seconds")
        ex.execute("i", "Count(Intersect(Row(f=1), Row(g=2), Row(h=1)))")
        ex.execute("i", "Count(Intersect(Row(f=1), Row(g=2), Row(h=1)))")
        builds = global_stats.timing_totals("stack_build_seconds")
        for name in ("f", "g", "h"):  # one full build each; the second query hits
            key = f'stack_build_seconds{{field="{name}"}}'
            assert builds[key][1] - builds0.get(key, (0, 0))[1] == 1
        closes0 = global_stats.timing_totals("holder_close_seconds")
        holder.close()
        closes = {
            n.split('"')[1]: v[1] - closes0.get(n, (0, 0))[1]
            for n, v in global_stats.timing_totals("holder_close_seconds").items()
        }
        frags = sum(
            len(v.fragments) for f in holder.index("i").fields.values()
            for v in f.views.values()
        )
        assert frags >= 4
        for step in ("snapshot_wait", "cache_flush", "wal_drain",
                     "block_epochs", "file_close"):
            assert closes[step] == frags, (step, closes)
        assert closes["attr_stores"] >= 4  # every field's, and the index's
        again = Holder(str(tmp_path / "data")).open()
        try:
            assert global_stats.gauge_value("holder_fragments_opened") == frags
            assert global_stats.gauge_value("holder_open_seconds") > 0
        finally:
            again.close()

    def test_graceful_stop_logs_where_the_seconds_went(self, served):
        from pilosa_tpu import cli

        holder, be, ex = served
        lines = []

        class Log:
            def printf(self, fmt, *args):
                lines.append(fmt % args)

        cli._close_holder(holder, Log())
        (line,) = lines
        assert line.startswith("holder closed in ")
        for step in ("snapshot_wait=", "cache_flush=", "wal_drain=",
                     "block_epochs=", "file_close=", "attr_stores="):
            assert step in line, line

    def test_gc_pauses_are_observed_by_generation(self):
        import gc

        from pilosa_tpu.utils.monitor import RuntimeMonitor

        key = 'runtime_gc_pause_seconds{generation="2"}'
        n0 = global_stats.timing_totals("runtime_gc_pause_seconds").get(
            key, (0.0, 0)
        )[1]
        mon = RuntimeMonitor()
        gc.callbacks.append(mon._on_gc)
        try:
            gc.collect()  # a full collection: generation 2
        finally:
            gc.callbacks.remove(mon._on_gc)
        # Stamped inside the collector, observed outside it.
        assert [g for g, _ in mon._gc_pauses].count(2) == 1
        mon._flush_gc_pauses()
        assert global_stats.timing_totals("runtime_gc_pause_seconds")[key][1] == n0 + 1
        assert mon._gc_pauses == []


class TestCallTimer:
    """`query_call_seconds{call}` and the span `pilosa.call.<name>`
    (PR 30): every call of a request's body is timed by itself, and its
    span is open only while its thread is in no phase and serves no
    drain, so that a waiting call never names a gap of the device."""

    class Span:
        events: list = []

        def __init__(self, name, **meta):
            self.name = name

        def __enter__(self):
            self.events.append(("open", self.name))
            return self

        def __exit__(self, *exc):
            self.events.append(("shut", self.name))

    @pytest.fixture
    def events(self):
        from pilosa_tpu.utils import qprofile

        before = qprofile._span_factory
        self.Span.events = []
        qprofile.set_span_factory(self.Span)
        yield self.Span.events
        qprofile.set_span_factory(before)

    @staticmethod
    def observed(call: str) -> float:
        key = f'query_call_seconds{{call="{call}"}}'
        return global_stats.timing_totals("query_call_seconds").get(key, (0, 0))[1]

    def test_the_span_is_shut_over_phases_waits_and_drains(self, events):
        from pilosa_tpu.utils.qprofile import PlaneProfile

        before = self.observed("Sum")
        with profile_scope(index="i") as prof:
            with prof.call_timer("Sum"):
                with prof.phase("stack_fetch"):
                    with prof.phase("freshness"):  # nested: still shut
                        pass
                with prof.phase("batch_wait"):  # a wait has no span at all
                    pass
                with PlaneProfile(global_stats) as plane:  # leading a drain
                    with plane.phase("take"):
                        pass
            assert prof.in_call is None
        call = "pilosa.call.Sum"
        assert events == [
            ("open", call), ("shut", call),
            ("open", "pilosa.stack_fetch"), ("open", "pilosa.freshness"),
            ("shut", "pilosa.freshness"), ("shut", "pilosa.stack_fetch"),
            ("open", call), ("shut", call),     # before the wait
            ("open", call), ("shut", call),     # between wait and drain
            ("open", "pilosa.drain.take"), ("shut", "pilosa.drain.take"),
            ("open", call), ("shut", call),
        ]
        assert self.observed("Sum") == before + 1

    def test_every_call_of_a_body_is_observed(self, server):
        req(server, "POST", "/index/i", "{}", ctype="application/json")
        req(server, "POST", "/index/i/field/f", "{}", ctype="application/json")
        before = {c: self.observed(c) for c in ("TopN", "Rows", "Set")}
        req(server, "POST", "/index/i/query",
            "Set(1, f=1)TopN(f)Rows(f)TopN(f, n=1)")
        grown = {c: self.observed(c) - before[c] for c in before}
        assert grown == {"TopN": 2, "Rows": 1, "Set": 1}
