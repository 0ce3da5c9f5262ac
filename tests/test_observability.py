"""Round-8 cluster observability plane: distributed trace assembly,
span-stack hygiene, peer-RPC metrics, metrics federation, the HBM
ledger, and the diagnostics device inventory (ISSUE r8)."""

import json
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.stats import global_stats
from pilosa_tpu.utils.tracing import Tracer, global_tracer
from tests.cluster_harness import TestCluster


def _counter(name_prefix: str) -> float:
    snap = global_stats.snapshot()["counters"]
    return sum(v for k, v in snap.items() if k.startswith(name_prefix))


def _get_json(uri: str, path: str) -> dict:
    with urllib.request.urlopen(uri + path, timeout=10) as resp:
        return json.loads(resp.read())


def _get_text(uri: str, path: str) -> str:
    with urllib.request.urlopen(uri + path, timeout=10) as resp:
        return resp.read().decode()


class TestTracerHygiene:
    def test_finish_pops_abandoned_children(self):
        t = Tracer()
        before = _counter("trace_spans_dropped_total")
        outer = t.start_span("outer")
        t.start_span("abandoned-child")  # exception path: never finished
        outer.finish()
        # The abandoned child must NOT keep re-parenting later spans.
        assert t.active_span() is None
        fresh = t.start_span("fresh")
        assert fresh.trace_id != outer.trace_id
        fresh.finish()
        assert _counter("trace_spans_dropped_total") == before + 1

    def test_depth_cap_forced_pop(self):
        t = Tracer()
        before = _counter("trace_spans_dropped_total")
        root = t.start_span("root")
        for i in range(1, t.MAX_STACK_DEPTH + 5):
            t.start_span(f"s{i}")
        stack = t._stack()
        assert len(stack) <= t.MAX_STACK_DEPTH
        # The live ROOT survives the cap; the oldest abandoned entries
        # ABOVE it were the forced-pop victims.
        assert stack[0] is root
        assert _counter("trace_spans_dropped_total") == before + 5
        # When the root finally finishes, its whole abandoned subtree
        # is truncated and counted.
        root.finish()
        assert t.active_span() is None
        # 5 force-pops + the 63 abandoned children truncated at finish:
        # every span but the root was dropped exactly once.
        assert (
            _counter("trace_spans_dropped_total")
            == before + t.MAX_STACK_DEPTH + 4
        )

    def test_spans_for_indexes_by_trace(self):
        t = Tracer()
        with t.start_span("a") as a:
            with t.start_span("a-child"):
                pass
        with t.start_span("b") as b:
            pass
        assert a.trace_id != b.trace_id
        got = t.spans_for(a.trace_id)
        assert {s["name"] for s in got} == {"a", "a-child"}
        assert all(s["traceID"] == a.trace_id for s in got)
        # Wall-clock start is recorded for cross-node ordering.
        assert all(s["start"] > 0 for s in got)
        assert t.spans_for("nonexistent") == []

    def test_ring_trim_prunes_trace_index(self):
        t = Tracer(capacity=8)
        for i in range(40):
            t.start_span(f"s{i}").finish()
        live = {s.trace_id for s in t._spans}
        assert set(t._by_trace) == live


class TestClusterTraces:
    def _seed(self, c, n_shards=6):
        c.create_index("i")
        c.create_field("i", "f")
        for shard in range(n_shards):
            c.query(0, "i", f"Set({shard * SHARD_WIDTH + 1}, f=0)")
        c.await_shard_convergence("i")

    def test_trace_propagates_across_nodes(self):
        """A fanned-out query leaves spans carrying ONE trace id on both
        the coordinator and the remote node (ISSUE r8 satellite)."""
        with TestCluster(2) as c:
            self._seed(c)
            uri = str(c[0].node.uri)
            req = urllib.request.Request(
                uri + "/index/i/query", data=b"Count(Row(f=0))", method="POST"
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                out = json.loads(resp.read())
            assert out["results"][0] == 6
            # Spans enter the ring at finish(), and the remote node's
            # http span finishes AFTER its reply bytes reached the
            # coordinator — an in-process client can read the ring a
            # GIL slice before that finalization lands. quiesce() on
            # BOTH nodes is the deterministic barrier (ISSUE r13; this
            # used to be an ad-hoc poll loop on the span ring). It must
            # come BEFORE picking "the newest query span": until the
            # coordinator's handler finalizes, the newest
            # http.handle_post_query in the ring is a _seed write's —
            # whose trace never fanned out.
            assert c[1].server.quiesce(timeout=5.0)
            assert c[0].server.quiesce(timeout=5.0)
            # The serving span of THIS query: newest http query span.
            qspans = [
                s
                for s in global_tracer.recent(400)
                if s["name"] == "http.handle_post_query"
            ]
            assert qspans
            trace_id = qspans[-1]["traceID"]
            spans = global_tracer.spans_for(trace_id)
            nodes = {
                s["tags"].get("node") for s in spans
                if "node" in s["tags"]
            }
            assert {"node0", "node1"} <= nodes, spans
            # The remote leg is linked, not a parallel orphan: node1's
            # http span chains to a coordinator-side mapper span.
            by_id = {s["spanID"]: s for s in spans}
            remote = next(
                s for s in spans if s["tags"].get("node") == "node1"
            )
            parent = by_id.get(remote["parentID"])
            assert parent is not None and parent["name"] == "cluster.mapShards"

    def test_debug_traces_assembles_one_tree(self):
        """/debug/traces/<id> returns one parent-linked tree containing
        spans attributed to >= 2 distinct nodes (acceptance)."""
        with TestCluster(2) as c:
            self._seed(c)
            uri = str(c[0].node.uri)
            req = urllib.request.Request(
                uri + "/index/i/query", data=b"Count(Row(f=0))", method="POST"
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                json.loads(resp.read())
            qspans = [
                s
                for s in global_tracer.recent(400)
                if s["name"] == "http.handle_post_query"
            ]
            trace_id = qspans[-1]["traceID"]
            tree = _get_json(uri, f"/debug/traces/{trace_id}")
            assert tree["traceID"] == trace_id
            assert tree["spanCount"] >= 3
            assert len(tree["nodes"]) >= 2
            assert tree["scrapeFailures"] == []

            # Every span appears exactly once (the in-process harness
            # shares rings; assembly must dedup by span id).
            seen = []

            def walk(node):
                seen.append(node["spanID"])
                for ch in node["children"]:
                    assert ch["parentID"] == node["spanID"]
                    walk(ch)

            for root in tree["tree"]:
                walk(root)
            assert len(seen) == len(set(seen)) == tree["spanCount"]
            # The remote node's serving span is a DESCENDANT in the tree.
            flat_nodes = set()

            def collect(node):
                flat_nodes.add(node.get("node"))
                for ch in node["children"]:
                    collect(ch)

            for root in tree["tree"]:
                collect(root)
            assert {"node0", "node1"} <= flat_nodes

    def test_internal_traces_serves_local_ring(self):
        with TestCluster(1) as c:
            with global_tracer.start_span("local-op") as sp:
                pass
            out = _get_json(
                str(c[0].node.uri), f"/internal/traces/{sp.trace_id}"
            )
            assert out["node"] == "node0"
            assert any(s["name"] == "local-op" for s in out["spans"])


class TestPeerRpcMetrics:
    def test_latency_series_tagged_per_peer_and_method(self):
        with TestCluster(2) as c:
            c.create_index("i")
            c.create_field("i", "f")
            for shard in range(4):
                c.query(0, "i", f"Set({shard * SHARD_WIDTH + 1}, f=0)")
            c.query(0, "i", "Count(Row(f=0))")
            timings = global_stats.snapshot()["timings"]
            series = [
                k
                for k in timings
                if k.startswith("peer_rpc_seconds")
                and 'method="query_node"' in k
            ]
            assert series, sorted(timings)[:20]
            assert all('peer="' in k for k in series)

    def test_error_classes_counted(self):
        from pilosa_tpu.cluster.client import ClientError, InternalClient

        # retries=0: this asserts PER-ATTEMPT error counting; the
        # idempotent-GET retry (ISSUE r9) would legitimately dial — and
        # count — a second transport error.
        client = InternalClient(timeout=0.5, retries=0)
        before = _counter("peer_rpc_errors_total")
        with pytest.raises(ClientError):
            client.status("http://127.0.0.1:1")  # nothing listens on :1
        snap = global_stats.snapshot()["counters"]
        transport = [
            k
            for k, v in snap.items()
            if k.startswith("peer_rpc_errors_total")
            and 'class="transport"' in k
            and 'peer="127.0.0.1:1"' in k
            and 'method="status"' in k
        ]
        assert transport
        assert _counter("peer_rpc_errors_total") == before + 1

    def test_failed_node_counts_a_retry(self):
        """Scatter-gather re-split onto a replica increments
        peer_rpc_retries_total for the failed peer."""
        with TestCluster(2, replica_n=2) as c:
            c.create_index("i")
            c.create_field("i", "f")
            for shard in range(4):
                c.query(0, "i", f"Set({shard * SHARD_WIDTH + 1}, f=0)")
            c.sync_all()
            before = _counter("peer_rpc_retries_total")
            # Kill node1's listener: remote legs fail, shards re-split
            # onto node0's replicas.
            c[1].server.close()
            # Fresh client: keep-alive state would mask the refusal.
            out = c.query(0, "i", "Count(Row(f=0))")
            assert out["results"][0] == 4
            assert _counter("peer_rpc_retries_total") >= before + 1


class TestFederation:
    def test_metrics_cluster_tags_every_node(self):
        with TestCluster(2) as c:
            text = _get_text(str(c[0].node.uri), "/metrics/cluster")
            assert 'node="node0"' in text
            assert 'node="node1"' in text
            assert 'pilosa_cluster_scrape_up{node="node0"} 1' in text
            assert 'pilosa_cluster_scrape_up{node="node1"} 1' in text
            assert "pilosa_cluster_scrape_seconds" in text
            # Pre-existing labels survive the retag (node label FIRST).
            assert 'pilosa_http_requests_total{node="node0",' in text

    def test_downed_node_is_scrape_failure_not_hang(self):
        with TestCluster(2) as c:
            before = _counter("cluster_scrape_failures_total")
            c[1].server.close()
            text = _get_text(
                str(c[0].node.uri), "/metrics/cluster?timeout=2"
            )
            assert 'pilosa_cluster_scrape_up{node="node1"} 0' in text
            assert 'pilosa_cluster_scrape_up{node="node0"} 1' in text
            assert _counter("cluster_scrape_failures_total") >= before + 1

    def test_debug_cluster_federates_vars(self):
        with TestCluster(2) as c:
            out = _get_json(str(c[0].node.uri), "/debug/cluster")
            assert set(out["nodes"]) == {"node0", "node1"}
            for ent in out["nodes"].values():
                assert ent["up"] is True
                assert "counters" in ent["vars"]
                # The LOCAL leg serves the same shape as remote
                # /debug/vars — version/uptime must not be missing for
                # exactly one node.
                assert "version" in ent["vars"]
                assert "uptimeSeconds" in ent["vars"]
                assert ent["scrapeMs"] >= 0

    def test_retag_renames_preexisting_node_label(self):
        """A member's own node=-tagged series (scrape-failure counters)
        must federate as exported_node=, never as an illegal duplicate
        node label."""
        with TestCluster(2) as c:
            # Seed a node=-tagged series on node0's registry.
            global_stats.with_tags("node:deadbeef").count(
                "cluster_scrape_failures_total"
            )
            text = _get_text(str(c[0].node.uri), "/metrics/cluster")
            assert 'exported_node="deadbeef"' in text
            for line in text.splitlines():
                assert line.count('node="') - line.count(
                    'exported_node="'
                ) <= 1, line

    def test_single_node_is_one_member_cluster(self):
        with TestCluster(1) as c:
            text = _get_text(str(c[0].node.uri), "/metrics/cluster")
            assert 'node="node0"' in text


class TestHbmLedger:
    @staticmethod
    def _blocks_cls():
        tpu = pytest.importorskip(
            "pilosa_tpu.exec.tpu",
            reason="device backend needs jax.shard_map",
            exc_type=ImportError,
        )
        return tpu._StackedBlocks

    def _field(self, h, name, cols):
        idx = h.index("b") or h.create_index("b")
        f = idx.create_field(name)
        f.import_bits(np.zeros(len(cols), dtype=np.uint64),
                      np.asarray(cols, dtype=np.uint64))
        return f

    def test_tier_bytes_sum_to_resident(self):
        from pilosa_tpu.core.holder import Holder

        _StackedBlocks = self._blocks_cls()
        h = Holder(None).open()
        rng = np.random.default_rng(5)
        # Sparse bits -> array containers.
        f = self._field(h, "f", rng.integers(0, SHARD_WIDTH, 500))
        # Contiguous range, optimized -> run container(s).
        g = self._field(h, "g", np.arange(7000))
        for frag_field in (g,):
            frag = frag_field.view("standard").fragment(0)
            frag.storage.optimize()
        blocks = _StackedBlocks()
        blocks.get("b", f, (0,))
        blocks.get("b", g, (0,))
        tiers = blocks.tier_bytes()
        assert sum(tiers.values()) == blocks.resident_bytes() > 0
        assert tiers["array"] > 0
        assert tiers["run"] > 0
        h.close()

    def test_layout_bytes_sum_to_resident(self):
        """hbm_layout_bytes{layout}: dense, packed and rowid sum to the
        resident total, as the tiers do."""
        from pilosa_tpu.core.holder import Holder

        _StackedBlocks = self._blocks_cls()
        h = Holder(None).open()
        rng = np.random.default_rng(8)
        f = self._field(h, "f", rng.integers(0, SHARD_WIDTH, 500))
        blocks = _StackedBlocks()
        blocks.get("b", f, (0,))
        layouts = blocks.layout_bytes()
        assert layouts == {"dense": blocks.resident_bytes(), "packed": 0,
                           "rowid": 0}
        assert blocks.budget() is None  # a CPU device reports no limit
        h.close()

    def test_ledger_coldness_order_and_access_churn(self):
        from pilosa_tpu.core.holder import Holder

        _StackedBlocks = self._blocks_cls()
        h = Holder(None).open()
        rng = np.random.default_rng(6)
        f = self._field(h, "f", rng.integers(0, SHARD_WIDTH, 300))
        g = self._field(h, "g", rng.integers(0, SHARD_WIDTH, 300))
        blocks = _StackedBlocks()
        blocks.get("b", f, (0,))
        blocks.get("b", g, (0,))
        led = blocks.ledger()
        assert [e["field"] for e in led] == ["f", "g"]  # f is coldest
        # Access churn: touching f reorders the eviction-candidate list.
        blocks.get("b", f, (0,))
        led = blocks.ledger()
        assert [e["field"] for e in led] == ["g", "f"]
        ent = next(e for e in led if e["field"] == "f")
        assert ent["accessCount"] == 2
        assert ent["uploads"] == 1
        assert ent["uploadEpoch"] >= 1
        h.close()

    def test_rebuild_bumps_epoch_eviction_drops_entry(self):
        from pilosa_tpu.core.holder import Holder

        _StackedBlocks = self._blocks_cls()
        h = Holder(None).open()
        rng = np.random.default_rng(7)
        f = self._field(h, "f", rng.integers(0, SHARD_WIDTH, 300))
        blocks = _StackedBlocks()
        blocks.get("b", f, (0,))
        epoch0 = blocks.ledger()[0]["uploadEpoch"]
        # A write starts a new epoch; the refreshed entry keeps its
        # access history but records the new upload.
        f.import_bits(np.array([1], dtype=np.uint64),
                      np.array([99], dtype=np.uint64))
        blocks.get("b", f, (0,))
        ent = blocks.ledger()[0]
        assert ent["uploadEpoch"] > epoch0
        assert ent["uploads"] == 2
        blocks.clear()
        assert blocks.ledger() == []
        assert blocks.tier_bytes() == {"dense": 0, "array": 0, "run": 0}
        h.close()

    def test_debug_hbm_endpoint(self):
        """/debug/hbm serves the ledger; tier totals sum to the resident
        gauge (acceptance). Stub block store: the HTTP wiring under test
        is backend-agnostic."""
        from types import SimpleNamespace

        with TestCluster(1) as c:

            class FakeBlocks:
                evictions = 2

                def resident_bytes(self):
                    return 96

                def tier_bytes(self):
                    return {"dense": 32, "array": 48, "run": 16}

                def ledger(self):
                    return [
                        {"index": "b", "field": "f", "view": "standard",
                         "bytes": 96,
                         "tierBytes": {"dense": 32, "array": 48, "run": 16},
                         "rows": 8, "uploadEpoch": 1, "uploads": 1,
                         "accessCount": 3, "lastAccess": 0.0,
                         "idleSeconds": 1.0}
                    ]

            c[0].executor.backend = SimpleNamespace(blocks=FakeBlocks())
            out = _get_json(str(c[0].node.uri), "/debug/hbm")
            assert out["residentBytes"] == 96
            assert sum(out["tierBytes"].values()) == out["residentBytes"]
            assert out["entries"][0]["field"] == "f"
            # And the tier gauges land on /metrics at scrape time.
            text = _get_text(str(c[0].node.uri), "/metrics")
            assert 'pilosa_hbm_resident_bytes{tier="array"} 48' in text
            assert "pilosa_hbm_resident_bytes 96" in text

    def test_debug_hbm_without_backend(self):
        with TestCluster(1) as c:
            out = _get_json(str(c[0].node.uri), "/debug/hbm")
            assert out == {"residentBytes": 0, "tierBytes": {},
                           "evictions": 0, "entries": [],
                           "totalEntries": 0}


class TestDiagnosticsDevices:
    def test_snapshot_includes_jax_inventory(self):
        from pilosa_tpu.utils.monitor import diagnostics_snapshot

        snap = diagnostics_snapshot()
        jx = snap["jax"]
        assert "error" not in jx, jx
        assert jx["device_count"] >= 1
        assert jx["platform"]
        d0 = jx["devices"][0]
        assert {"id", "platform", "kind"} <= set(d0)

    def test_served_over_http(self):
        with TestCluster(1) as c:
            out = _get_json(str(c[0].node.uri), "/debug/diagnostics")
            assert out["jax"]["device_count"] >= 1


class TestResizeGossipCounters:
    def test_resize_job_counters_and_progress(self):
        before_started = _counter("resize_jobs_started_total")
        before_done = _counter("resize_jobs_completed_total")
        with TestCluster(2) as c:
            c.create_index("i")
            c.create_field("i", "f")
            for shard in range(4):
                c.query(0, "i", f"Set({shard * SHARD_WIDTH + 1}, f=0)")
            c.add_node_via_resize()
            assert _counter("resize_jobs_started_total") == before_started + 1
            assert _counter("resize_jobs_completed_total") == before_done + 1
            gauges = global_stats.snapshot()["gauges"]
            assert gauges.get("resize_pending_nodes") == 0
            assert "resize_migration_sources_total" in gauges

    def test_state_transition_counter(self):
        with TestCluster(1) as c:
            before = _counter("cluster_state_transitions_total")
            c[0].cluster.set_state("RESIZING")
            c[0].cluster.set_state("NORMAL")
            c[0].cluster.set_state("NORMAL")  # no-op: not a transition
            assert _counter("cluster_state_transitions_total") == before + 2
