"""Execution introspection plane tests (ISSUE 16): EXPLAIN is free when
off and faithful when on (its plan agrees with the embedded counter
families), the device-program ledger detects a forced recompile, and a
two-thread WAL convoy lands in the lock-stall plane with an exemplar
that resolves to the waiter's trace."""

import json
import threading
import time
import urllib.request

import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.fragment import _WalFile
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.tpu import TPUBackend
from pilosa_tpu.server.api import API
from pilosa_tpu.server.http import Server
from pilosa_tpu.utils.locks import global_stall_ledger
from pilosa_tpu.utils.qprofile import ExplainPlan, profile_scope
from pilosa_tpu.utils.stats import global_stats
from pilosa_tpu.utils.tracing import global_tracer


@pytest.fixture
def server(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    srv = Server(API(holder, Executor(holder)), host="localhost", port=0).open()
    yield srv
    srv.close()
    holder.close()


def _post(srv, path, body=b"{}", ctype="application/json", headers=None):
    hdrs = {"Content-Type": ctype}
    hdrs.update(headers or {})
    r = urllib.request.Request(srv.uri + path, data=body, method="POST", headers=hdrs)
    return json.loads(urllib.request.urlopen(r).read())


def post_query(srv, pql, suffix="", headers=None):
    return _post(
        srv, "/index/i/query" + suffix, pql.encode(), "text/plain", headers
    )


def get_json(srv, path):
    return json.loads(urllib.request.urlopen(srv.uri + path).read())


def setup_index(srv):
    _post(srv, "/index/i")
    _post(srv, "/index/i/field/f")
    post_query(srv, "Set(10, f=1) Set(100, f=1)")


class TestExplainOptIn:
    def test_off_allocates_no_plan(self, server, monkeypatch):
        """The alloc pin: with the flag off, no ExplainPlan is ever
        constructed anywhere on the serving path — the deep hooks are
        getattr checks against a None slot, not plan-node builders."""
        setup_index(server)
        made = []
        orig = ExplainPlan.__init__

        def counting(plan):
            made.append(1)
            orig(plan)

        monkeypatch.setattr(ExplainPlan, "__init__", counting)
        out = post_query(server, "Count(Row(f=1))")
        assert out == {"results": [2]}
        assert made == []
        out = post_query(server, "Count(Row(f=1))", suffix="?explain=1")
        assert out["results"] == [2]
        assert made == [1]

    def test_flag_attaches_plan(self, server):
        setup_index(server)
        out = post_query(server, "Count(Row(f=1))", suffix="?explain=1")
        assert out["results"] == [2]
        calls = out["explain"]["calls"]
        assert calls and calls[0]["call"] == "Count"
        assert "route" in calls[0]
        # Header spelling of the same opt-in.
        out = post_query(server, "Row(f=1)", headers={"X-Pilosa-Explain": "1"})
        assert "explain" in out
        assert out["explain"]["calls"][0]["call"] == "Row"

    def test_ring_carries_shards_and_plan(self, server):
        """Satellite: every ring entry (explain or not) carries the
        resolved shard count; explain entries carry the plan too."""
        setup_index(server)
        post_query(server, "Row(f=1)")
        post_query(server, "Count(Row(f=1))", suffix="?explain=1")
        # A profile enters the ring one GIL slice after its reply has
        # reached this client: wait for that, or an earlier test's entry
        # of the same query text is the newest one seen.
        assert server.quiesce(timeout=5.0)
        recent = get_json(server, "/debug/queries")["recent"]
        # The ring is process-global and newest-first: keep the newest
        # entry per query so earlier tests' entries don't shadow ours.
        by_query = {}
        for e in recent:
            if e.get("query") and e["query"] not in by_query:
                by_query[e["query"]] = e
        assert by_query["Row(f=1)"]["shards"] >= 1
        assert "explain" not in by_query["Row(f=1)"]
        assert "calls" in by_query["Count(Row(f=1))"]["explain"]

    def test_debug_stalls_and_programs_routes(self, server):
        stalls = get_json(server, "/debug/stalls?n=5")
        assert "worst" in stalls and "sites" in stalls
        programs = get_json(server, "/debug/programs")
        assert {"programs", "compiles", "recompiles", "launches", "entries"} <= set(
            programs
        )


@pytest.fixture
def tpu_ex(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    Executor(holder).execute("i", "Set(10, f=1) Set(100, f=1) Set(7, f=2)")
    be = TPUBackend(holder)
    yield Executor(holder, backend=be), be
    holder.close()


def _device_counters():
    snap = global_stats.snapshot()["counters"]
    return {
        k: v
        for k, v in snap.items()
        if k.startswith(("device_launches_total", "device_recompiles_total"))
    }


class TestExplainDifferential:
    def test_plan_matches_leg_counter_deltas(self, tpu_ex):
        """The plan must agree with the embedded counter families
        (bench.py LEG_COUNTER_FAMILIES): one launch record per
        device_launches_total increment, and the recompile family stays
        flat on a first-compile run."""
        from bench import LEG_COUNTER_FAMILIES

        assert "device_recompiles_total" in LEG_COUNTER_FAMILIES
        assert "snapshot_stall_seconds_total" in LEG_COUNTER_FAMILIES
        ex, _ = tpu_ex
        before = _device_counters()
        with profile_scope(index="i", query="Count(Row(f=1))") as prof:
            prof.explain = ExplainPlan()
            assert ex.execute("i", "Count(Row(f=1))") == [2]
        after = _device_counters()
        launched = sum(
            len(c.get("launches", [])) for c in prof.explain.calls
        )
        delta = sum(
            v - before.get(k, 0.0)
            for k, v in after.items()
            if k.startswith("device_launches_total")
        )
        assert launched == delta
        # Each launch record names its program and carries the byte
        # accounting the ledger aggregates.
        for call in prof.explain.calls:
            for rec in call.get("launches", []):
                assert rec["kind"] and rec["program"]
                assert rec["bytesShipped"] > 0
        recompiled = sum(
            v - before.get(k, 0.0)
            for k, v in after.items()
            if k.startswith("device_recompiles_total")
        )
        assert recompiled == 0

    def test_forced_recompile_detected(self, tpu_ex):
        """Dropping the jit-fn cache and re-running the same shape is a
        same-signature second compile: the ledger must count it as a
        recompile (the /debug/programs regression signal)."""
        ex, be = tpu_ex
        ex.execute("i", "Count(Row(f=1))")
        base = be.programs.counts()
        ex.execute("i", "Count(Row(f=1))")
        steady = be.programs.counts()
        assert steady["recompiles"] == base["recompiles"]
        be._fns.clear()
        ex.execute("i", "Count(Row(f=1))")
        forced = be.programs.counts()
        assert forced["recompiles"] > base["recompiles"]
        assert any(
            k.startswith("device_recompiles_total")
            for k in global_stats.snapshot()["counters"]
        )
        # The ledger row for the recompiled program shows both compiles.
        assert any(e["compiles"] >= 2 for e in be.programs.ledger())


class TestLockStallAttribution:
    def test_wal_convoy_attributed_with_exemplar(self, tmp_path):
        """Two-thread WAL convoy: the writer that waits must land in
        lock_wait_seconds{site=wal_append} and the stall ledger, with a
        trace id that resolves to the waiter's span."""
        wal = _WalFile(str(tmp_path / "f.wal"))
        holding = threading.Event()
        release = threading.Event()

        def holder_thread():
            with wal._lock:
                holding.set()
                release.wait(5.0)

        trace_id = []

        def writer_thread():
            with global_tracer.start_span("wal-convoy-writer") as span:
                trace_id.append(span.trace_id)
                wal.write(b"x" * 64)

        t_hold = threading.Thread(target=holder_thread)
        t_hold.start()
        assert holding.wait(5.0)
        t_write = threading.Thread(target=writer_thread)
        t_write.start()
        time.sleep(0.05)  # let the writer block on the held lock
        release.set()
        t_write.join(5.0)
        t_hold.join(5.0)
        wal.release()

        entries = [
            e for e in global_stall_ledger.worst(256)
            if e["site"] == "wal_append" and e["traceId"] == trace_id[0]
        ]
        assert entries, "convoyed WAL write missing from the stall ledger"
        assert entries[0]["waitMs"] > 0
        # The exemplar resolves: the tracer can serve the waiter's span.
        assert global_tracer.spans_for(trace_id[0])
        # Site aggregates and the histogram family both saw the wait.
        assert global_stall_ledger.sites()["wal_append"]["waits"] >= 1
        timings = global_stats.snapshot()["timings"]
        assert any(
            name.startswith("lock_wait_seconds") and 'site="wal_append"' in name
            for name in timings
        )
        hist = global_stats.histogram_snapshot()
        waits = [
            ent for name, ent in hist.items()
            if name.startswith("lock_wait_seconds") and 'site="wal_append"' in name
        ]
        assert waits and waits[0]["count"] >= 1
        assert any(
            ex_rec["trace_id"] == trace_id[0]
            for ent in waits
            for ex_rec in ent.get("exemplars", [])
        )
