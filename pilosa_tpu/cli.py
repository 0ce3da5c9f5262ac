"""Command-line interface (reference cmd/ + ctl/: server, import, export,
check, inspect, generate-config, config).

Usage: python -m pilosa_tpu.cli <command> [flags]
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _device_backend(cfg, holder):
    """The TPUBackend for executor=tpu, over a mesh when mesh-devices
    asks for one (ISSUE r13: block stacks sharded over the devices,
    programs under shard_map with ICI collectives). A count the
    platform cannot satisfy raises MeshConfigError rather than
    under-sharding a node sized for more chips."""
    import jax

    from pilosa_tpu.exec.tpu import TPUBackend
    from pilosa_tpu.parallel import MeshConfigError, ShardMesh

    mesh = None
    if cfg.mesh_devices:
        devices = jax.devices()
        want = len(devices) if cfg.mesh_devices < 0 else cfg.mesh_devices
        if want > len(devices):
            raise MeshConfigError(
                f"mesh-devices={want} but only {len(devices)} "
                "devices are visible"
            )
        if want > 1:
            mesh = ShardMesh(devices[:want])
    return TPUBackend(
        holder, mesh=mesh, max_bytes=cfg.max_hbm_bytes or None,
        heat_half_life=cfg.heat_half_life or None,
    )


def _close_holder(holder, log) -> None:
    """holder.close(), and the one line that says where a graceful stop's
    seconds went: the process is about to go, and /metrics with it. Per
    step of Fragment.close() (and the attribute stores), summed seconds
    over the number of closes."""
    import time

    from pilosa_tpu.utils.stats import global_stats

    def per_tag(family: str) -> str:
        return " ".join(
            f"{name.split('"')[1]}={total:.2f}s/{n}"
            for name, (total, n) in sorted(
                global_stats.timing_totals(family).items()
            )
        )

    t0 = time.perf_counter()
    holder.close()
    said = "holder closed in %.2fs: holder_close_seconds %s" % (
        time.perf_counter() - t0, per_tag("holder_close_seconds"),
    )
    loads = per_tag("import_roaring_seconds")
    if loads:
        # What a bulk load through import-roaring cost this process, by
        # the kind of view it went to (a loader's last word).
        said += "; import_roaring_seconds %s import_roaring_bits_total=%d" % (
            loads,
            sum(global_stats.counter_totals("import_roaring_bits_total").values()),
        )
    log.printf("%s", said)


#: Container objects allocated (less freed) before a young collection, and
#: young collections per older one: CPython's defaults are 700, 10, 10.
GC_THRESHOLDS = (50_000, 20, 20)


def _tune_collector(log) -> None:
    """The cyclic collector, set for a process that holds a large heap
    for good and answers requests of thousands of objects each.

    What the holder has just built (every fragment, its containers and
    caches: hundreds of thousands of objects that live as long as the
    process) is frozen: out of every collection's sight. Frozen objects
    are still freed by their reference counts; only a cycle among them
    that becomes garbage is kept, and a served holder makes none.

    And collections are made rarer. A collection stops every thread, an
    answer of a few thousand groups is ~15,000 containers that reference
    counting frees by itself, and sixteen such answers in flight are the
    heap a collection walks: at the default thresholds a 51 s window held
    16,500 young collections and 136 full ones of 54 ms (118 ms before
    the freeze), 15 s in all (PERF.md, PR 30)."""
    import gc

    gc.collect()
    gc.freeze()
    gc.set_threshold(*GC_THRESHOLDS)
    log.printf(
        "gc: %d objects frozen after the holder opened, thresholds %s",
        gc.get_freeze_count(), GC_THRESHOLDS,
    )


def cmd_server(args) -> int:
    from pilosa_tpu.core import Holder
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.server.api import API
    from pilosa_tpu.server.config import Config
    from pilosa_tpu.server.http import Server
    from pilosa_tpu.utils.logger import StandardLogger

    cfg = Config.from_sources(
        toml_path=args.config,
        args={
            "data_dir": args.data_dir,
            "bind": args.bind,
            "executor": args.executor,
            "verbose": args.verbose or None,
        },
    )
    # log-path: append server logs to a file instead of stderr
    # (reference config.go LogPath; the config-drift rule caught the
    # knob parsed but never consumed). Line-buffered so a crash loses
    # at most one line.
    log_stream = (
        open(os.path.expanduser(cfg.log_path), "a", buffering=1)
        if cfg.log_path
        else None
    )
    log = StandardLogger(stream=log_stream, verbose=cfg.verbose)
    data_dir = os.path.expanduser(cfg.data_dir)
    holder = Holder(data_dir).open()
    _tune_collector(log)

    backend = None
    if cfg.executor == "tpu":
        # On the chip or not up. Whatever stops the device backend from
        # being built — no TPU initialized (the platform rule,
        # ops/runtime.py), mesh-devices past the inventory, a runtime
        # that fails to start — ends the process with the reason and a
        # non-zero code. The host path is `--executor cpu`, never a
        # fallback taken here: a server that quietly served from the CPU
        # under the name executor=tpu is the failure this refuses.
        try:
            backend = _device_backend(cfg, holder)
        except Exception as e:  # noqa: BLE001 — start-up boundary:
            # every failure is reported and becomes exit code 1.
            log.printf(
                "executor=tpu: cannot build the device backend (%s: %s); "
                "not serving",
                type(e).__name__, e,
            )
            holder.close()
            return 1
        n_dev = backend.mesh.n if backend.mesh is not None else 1
        log.printf(
            "executor=tpu: device backend enabled (%d device%s)",
            n_dev, "s" if n_dev > 1 else "",
        )
    executor = Executor(holder, backend=backend)
    if backend is not None:
        from pilosa_tpu.exec.batcher import ShardLegBatcher

        executor.batcher = ShardLegBatcher(backend, window=cfg.batch_window)
        if cfg.preheat:
            from pilosa_tpu.utils.threads import spawn

            def _preheat():
                n = backend.preheat(logger=log)
                log.printf("preheat: %d stacks resident", n)

            spawn("preheat", _preheat)
    # Epoch-tagged result cache (exec/rescache.py, ISSUE r12): serve hot
    # terminal answers from memory while their journal-derived epoch
    # vector matches. 0 bytes = disabled (the max-inflight convention);
    # cache-enabled=false keeps it out even with a budget set.
    if cfg.cache_enabled and cfg.max_result_cache_bytes > 0:
        from pilosa_tpu.exec.rescache import ResultCache

        executor.rescache = ResultCache(
            holder,
            max_bytes=cfg.max_result_cache_bytes,
            max_staleness=cfg.max_staleness,
        )
        log.printf(
            "result cache: %.1f MiB budget, max-staleness=%d",
            cfg.max_result_cache_bytes / (1 << 20), cfg.max_staleness,
        )
    executor.logger = log
    if backend is not None:
        # Device-fallback one-line logs (exec/tpu.py _count_device_fallback)
        # need the server logger or they count on /metrics but never log.
        backend.logger = log
    if cfg.profile_port:
        try:
            import jax

            jax.profiler.start_server(cfg.profile_port)
            log.printf("jax profiler server on :%d", cfg.profile_port)
        except Exception as e:  # noqa: BLE001 — profiling is optional
            log.printf("jax profiler server failed: %s", e)
    if cfg.long_query_time > 0:
        executor.long_query_time = cfg.long_query_time
    api = API(holder, executor)
    # Default per-query budget for clients that send no ?timeout=
    # (server/http.py opens the deadline scope at ingress).
    api.query_timeout = cfg.query_timeout
    # In-flight /query admission cap (deliberate 429 shedding past it).
    api.max_inflight_queries = cfg.max_inflight
    # Write-side admission: in-flight import bytes + pending-WAL depth
    # caps (deliberate 429/503 import shedding — never OOM).
    api.max_import_bytes = cfg.max_import_bytes
    api.max_pending_wal = cfg.max_pending_wal
    # Per-request write-call cap + metric exposition switch (both knobs
    # existed since the seed but nothing consumed them — config-drift).
    api.max_writes_per_request = cfg.max_writes_per_request
    api.metric_service = cfg.metric_service
    # Read/write plane isolation (ISSUE r19): paced + globally bounded
    # background snapshots, windowed device-refresh coalescing, and
    # SLO-adaptive import derating.
    from pilosa_tpu.core.fragment import SNAPSHOT_SCHEDULER

    SNAPSHOT_SCHEDULER.configure(
        concurrency=cfg.snapshot_concurrency,
        bandwidth=cfg.snapshot_bandwidth,
    )
    api.ingest_derate = cfg.ingest_derate
    if backend is not None and cfg.refresh_window_ms > 0:
        backend.start_refresher(cfg.refresh_window_ms)
        log.printf(
            "windowed device refresh: %d ms coalescing window",
            cfg.refresh_window_ms,
        )

    # TLS (reference server/tlsconfig.go): certificate+key serve HTTPS;
    # peers are dialed with a CA-verified (or skip-verify) context. A
    # bare host in cluster.hosts inherits the local scheme so an
    # all-TLS cluster doesn't need https:// spelled 9 times.
    local_scheme = "https" if cfg.tls.enabled else "http"
    client_ssl = (
        cfg.tls.client_context()
        if (cfg.tls.enabled or cfg.tls.skip_verify or cfg.tls.ca_certificate)
        else None
    )

    # Persisted topology (ISSUE r9 tentpole 3): membership survives
    # restarts in <data-dir>/.topology, written atomically on every
    # durable change, so a restarting node rejoins with its same
    # identity and a full-cluster restart reconverges without operator
    # re-seeding.
    from pilosa_tpu.cluster.topology import TOPOLOGY_FILE, load_topology

    topo_path = os.path.join(data_dir, TOPOLOGY_FILE)
    saved = load_topology(topo_path)  # None on absent/corrupt: reseed
    saved_nodes = []
    saved_local = None
    if saved:
        from pilosa_tpu.cluster import Node
        from pilosa_tpu.cluster.topology import NODE_STATE_READY

        saved_nodes = [Node.from_json(d) for d in saved["nodes"]]
        for n in saved_nodes:
            # Persisted liveness is stale by definition: every member
            # boots READY and the failure detector re-learns the truth.
            n.state = NODE_STATE_READY
        saved_local = next(
            (
                n
                for n in saved_nodes
                if n.uri.host == cfg.host and n.uri.port == cfg.port
            ),
            None,
        )

    def restore_saved_cluster():
        """Boot from the persisted topology: the one restore sequence
        both the --join-restart and no-cluster-config paths share."""
        if saved.get("replicaN"):
            cfg.cluster.replicas = int(saved["replicaN"])
        cluster = wire_cluster(
            saved_nodes, saved_local.id, partition_n=saved.get("partitionN")
        )
        log.printf(
            "restored topology from %s: %d nodes, replicas=%d, local id %s",
            topo_path, len(saved_nodes), cfg.cluster.replicas, saved_local.id,
        )
        return cluster

    def wire_cluster(topo_nodes, local_id, partition_n=None):
        """Shared cluster bootstrap for the static-hosts, --join, and
        persisted-topology paths: build the topology, attach seams,
        start daemons."""
        from pilosa_tpu.cluster import Cluster, InternalClient, Topology
        from pilosa_tpu.cluster.breaker import BreakerRegistry
        from pilosa_tpu.cluster.sync import FailureDetector, SyncDaemon
        from pilosa_tpu.cluster.topology import DEFAULT_PARTITION_N

        topo = Topology(
            topo_nodes,
            replica_n=cfg.cluster.replicas,
            partition_n=partition_n or DEFAULT_PARTITION_N,
        )
        local = topo.node_by_id(local_id)
        if local is None:
            return None
        cluster = Cluster(
            local, topo, holder,
            client=InternalClient(
                timeout=cfg.client_timeout,
                ssl_context=client_ssl,
                retries=cfg.client_retries,
                breakers=BreakerRegistry(
                    threshold=cfg.breaker_threshold,
                    cooldown=cfg.breaker_cooldown,
                ),
            ),
        )
        cluster.hedge_delay = cfg.hedge_delay
        cluster.logger = log
        cluster.attach(executor, api)
        api.cluster = cluster
        resizer = cluster.attach_resizer(log)
        # Cluster-lifecycle knobs (ISSUE r9): follower rollback lease +
        # migration throttles.
        resizer.lease_timeout = cfg.resize_lease
        resizer.fetch_concurrency = cfg.migration_concurrency
        resizer.bandwidth_limit = cfg.migration_bandwidth
        resizer.fetch_timeout = cfg.client_timeout
        if saved:
            # The resize epoch survives restarts: a rebooted
            # coordinator's fresh jobs must outrank any dead job whose
            # completion reports are still in retry flight.
            resizer._epoch = int(saved.get("resizeEpoch") or 0)
        cluster.topology_file = topo_path
        cluster.persist_topology()
        daemons.append(
            SyncDaemon(cluster, interval=cfg.anti_entropy_interval, logger=log).start()
        )
        daemons.append(FailureDetector(cluster, logger=log).start())
        if cfg.read_repair_queue > 0:
            # Read-path divergence monitor (ISSUE r15 tentpole 2):
            # hedge races' replica-pair answers feed a bounded queue of
            # background checksum diffs + targeted epoch-directed
            # repairs, surfaced at /debug/consistency.
            from pilosa_tpu.cluster.consistency import DivergenceMonitor

            daemons.append(
                DivergenceMonitor(
                    cluster, max_queue=cfg.read_repair_queue, logger=log
                ).start()
            )
        return cluster

    daemons = []
    from pilosa_tpu.utils.monitor import RuntimeMonitor

    monitor = RuntimeMonitor(holder, backend)
    # SLO objectives (config `slo`): the monitor's poll loop keeps the
    # windowed histogram snapshots /debug/slo evaluates them against.
    monitor.slo = cfg.slo
    api.slo = cfg.slo
    api.monitor = monitor
    daemons.append(monitor.start())
    join_cluster_ref = None
    if getattr(args, "join", None):
        # Dynamic join (reference gossip join → listenForJoins
        # cluster.go:1063): boot as a single-node topology; the announce
        # fires AFTER the HTTP server is bound (below) so the
        # coordinator's resize instructions can reach us, and the resize
        # machinery delivers schema + fragments + the real topology.
        from pilosa_tpu.cluster import Node, URI

        if saved_local is not None and len(saved_nodes) > 1:
            # Restart of a previously joined node: come back with the
            # SAME identity and the last known membership — the cluster
            # still routes shards to us, so booting as a blank
            # single-node would orphan them until a fresh resize. The
            # announce below re-syncs schema/shards (handle_join's
            # restarted-member path) without moving any data.
            join_cluster_ref = restore_saved_cluster()
        else:
            local_id = f"node-{cfg.host}-{cfg.port}"
            local = Node(
                id=local_id,
                uri=URI(scheme=local_scheme, host=cfg.host, port=cfg.port),
            )
            join_cluster_ref = wire_cluster([local], local_id)
    elif cfg.cluster.hosts:
        from pilosa_tpu.cluster import Node, URI

        # Node IDs derive from the URI so every host computes the same
        # ID-sorted ring without an out-of-band registry (the reference
        # persists a UUID and gossips it; static topology needs neither).
        import dataclasses as _dc

        nodes = []
        for h in cfg.cluster.hosts:
            u = URI.parse(h)
            if "://" not in h and local_scheme != "http":
                u = _dc.replace(u, scheme=local_scheme)
            nodes.append(Node(id=f"node-{u.host}-{u.port}", uri=u))
        local_id = f"node-{cfg.host}-{cfg.port}"
        if cfg.cluster.coordinator:
            # cluster.coordinator = true marks THIS node the coordinator
            # (reference server/config.go Cluster.Coordinator); set it in
            # every node's config consistently.
            for n in nodes:
                n.is_coordinator = n.id == local_id
        elif nodes:
            min(nodes, key=lambda n: n.id).is_coordinator = True
        cluster = wire_cluster(nodes, local_id)
        if cluster is None:
            log.printf(
                "bind %s:%d is not in cluster.hosts %s", cfg.host, cfg.port, cfg.cluster.hosts
            )
            return 1
        log.printf(
            "clustered: %d nodes, replicas=%d, coordinator=%s",
            len(nodes), cfg.cluster.replicas, cluster.coordinator().id,
        )
    elif saved_local is not None and len(saved_nodes) > 1:
        # No cluster config at all, but a persisted topology: a
        # full-cluster restart reconverges straight from the file —
        # every member boots with the membership it last agreed on, no
        # operator re-seeding (ISSUE r9 tentpole 3).
        restore_saved_cluster()

    server = Server(api, host=cfg.host, port=cfg.port, tls=cfg.tls)  # binds
    log.printf(
        "listening on %s://%s:%d (data: %s)",
        local_scheme, cfg.host, cfg.port, data_dir,
    )
    if join_cluster_ref is not None:
        from pilosa_tpu.utils.threads import spawn

        def announce():
            if join_cluster_ref.join_cluster(args.join):
                log.printf("joined cluster via %s", args.join)
            else:
                log.printf("join via %s timed out; still standalone", args.join)

        spawn("cluster-announce", announce)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        log.printf("shutting down")
        for d in daemons:
            d.stop()
        _close_holder(holder, log)
    return 0


def _client_tls_context(args):
    """ssl context for the ctl-style client commands' --ca-certificate /
    --skip-verify trust flags (reference ctl's --tls.* flags). None for
    plain-http hosts or default system-store verification."""
    if getattr(args, "skip_verify", False):
        from pilosa_tpu.server.config import TLSConfig

        return TLSConfig(skip_verify=True).client_context()
    if getattr(args, "ca_certificate", None):
        import ssl

        return ssl.create_default_context(cafile=args.ca_certificate)
    return None


def cmd_import(args) -> int:
    """CSV import: rows of row_id,column_id (or col,value with -v)
    (reference ctl/import.go)."""
    import urllib.error
    import urllib.request

    host = args.host.rstrip("/")
    ctx = _client_tls_context(args)
    index, field = args.index, args.field

    # create index/field if requested
    if args.create:
        for url, body in [
            (f"{host}/index/{index}", {}),
            (
                f"{host}/index/{index}/field/{field}",
                {"options": {"type": "int", "min": args.min, "max": args.max}}
                if args.value
                else {},
            ),
        ]:
            req = urllib.request.Request(
                url, data=json.dumps(body).encode(), method="POST",
                headers={"Content-Type": "application/json"},
            )
            try:
                urllib.request.urlopen(req, context=ctx)
            except urllib.error.HTTPError as e:
                if e.code != 409:  # only "already exists" is benign
                    raise

    rows, cols, values = [], [], []
    for path in args.files:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                parts = line.split(",")
                if args.value:
                    cols.append(int(parts[0]))
                    values.append(int(parts[1]))
                else:
                    rows.append(int(parts[0]))
                    cols.append(int(parts[1]))

    payload = (
        {"columnIDs": cols, "values": values}
        if args.value
        else {"rowIDs": rows, "columnIDs": cols}
    )
    req = urllib.request.Request(
        f"{host}/index/{index}/field/{field}/import",
        data=json.dumps(payload).encode(),
        method="POST",
        headers={"Content-Type": "application/json"},
    )
    resp = urllib.request.urlopen(req, context=ctx)
    print(resp.read().decode().strip())
    return 0


def cmd_export(args) -> int:
    """reference ctl/export.go: exports the whole field across every
    shard and node by default; --shard restricts to one shard."""
    import urllib.request

    url = f"{args.host.rstrip('/')}/export?index={args.index}&field={args.field}"
    if args.shard is not None:
        url += f"&shard={args.shard}"
    resp = urllib.request.urlopen(
        urllib.request.Request(url), context=_client_tls_context(args)
    )
    sys.stdout.write(resp.read().decode())
    return 0


def cmd_check(args) -> int:
    """Offline consistency check of fragment + cache files
    (reference ctl/check.go:28-50)."""
    from pilosa_tpu.roaring.codec import deserialize

    ok = True
    for path in args.files:
        try:
            with open(path, "rb") as f:
                data = f.read()
            b = deserialize(data)
            print(f"{path}: ok ({b.count()} bits, {len(b._cs)} containers, opN={b.op_n})")
        except Exception as e:
            ok = False
            print(f"{path}: CORRUPT: {e}")
    return 0 if ok else 1


def cmd_inspect(args) -> int:
    """Dump roaring container stats (reference ctl/inspect.go:30-60)."""
    from pilosa_tpu.roaring.codec import deserialize

    for path in args.files:
        with open(path, "rb") as f:
            b = deserialize(f.read())
        type_counts: dict[str, int] = {}
        for key in b.keys():
            c = b.container(key)
            type_counts[c.typ] = type_counts.get(c.typ, 0) + 1
        print(f"{path}:")
        print(f"  bits: {b.count()}")
        print(f"  containers: {len(b._cs)} {type_counts}")
        print(f"  ops applied: {b.op_n}")
        if args.containers:
            for key in b.keys():
                c = b.container(key)
                print(f"  {key:>12} {c.typ:>6} n={c.n}")
    return 0


def cmd_generate_config(args) -> int:
    from pilosa_tpu.server.config import Config

    sys.stdout.write(Config().toml_text())
    return 0


def cmd_config(args) -> int:
    """Validate a config file (reference `pilosa config`)."""
    from pilosa_tpu.server.config import Config

    try:
        cfg = Config.from_sources(toml_path=args.config)
    except Exception as e:
        print(f"invalid config: {e}", file=sys.stderr)
        return 1
    print(json.dumps(cfg.to_dict(), indent=2))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="pilosa-tpu", description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("server", help="run the server")
    sp.add_argument("-d", "--data-dir", default=None)
    sp.add_argument("-b", "--bind", default=None)
    sp.add_argument("-c", "--config", default=None)
    sp.add_argument("--executor", choices=["tpu", "cpu"], default=None)
    sp.add_argument(
        "--join",
        default=None,
        metavar="URI",
        help="announce to a live cluster's coordinator and join it "
        "(dynamic membership; no operator resize call needed)",
    )
    sp.add_argument("--verbose", action="store_true")
    sp.set_defaults(fn=cmd_server)

    def _tls_client_flags(sp):
        sp.add_argument(
            "--ca-certificate", default="",
            help="PEM CA bundle to verify an https:// host against",
        )
        sp.add_argument(
            "--skip-verify", action="store_true",
            help="accept any https:// server certificate (dev clusters)",
        )

    sp = sub.add_parser("import", help="import CSV data")
    sp.add_argument("--host", default="http://localhost:10101")
    _tls_client_flags(sp)
    sp.add_argument("-i", "--index", required=True)
    sp.add_argument("-f", "--field", required=True)
    sp.add_argument("--create", action="store_true", help="create index/field first")
    sp.add_argument("-v", "--value", action="store_true", help="int-field value import")
    sp.add_argument("--min", type=int, default=0)
    sp.add_argument("--max", type=int, default=1 << 40)
    sp.add_argument("files", nargs="+")
    sp.set_defaults(fn=cmd_import)

    sp = sub.add_parser(
        "export", help="export a whole field (all shards/nodes) as CSV"
    )
    sp.add_argument("--host", default="http://localhost:10101")
    _tls_client_flags(sp)
    sp.add_argument("-i", "--index", required=True)
    sp.add_argument("-f", "--field", required=True)
    sp.add_argument("-s", "--shard", type=int, default=None,
                    help="restrict to one shard (default: all)")
    sp.set_defaults(fn=cmd_export)

    sp = sub.add_parser("check", help="check fragment files for corruption")
    sp.add_argument("files", nargs="+")
    sp.set_defaults(fn=cmd_check)

    sp = sub.add_parser("inspect", help="inspect roaring fragment files")
    sp.add_argument("--containers", action="store_true")
    sp.add_argument("files", nargs="+")
    sp.set_defaults(fn=cmd_inspect)

    sp = sub.add_parser("generate-config", help="print default config TOML")
    sp.set_defaults(fn=cmd_generate_config)

    sp = sub.add_parser("config", help="validate a config file")
    sp.add_argument("-c", "--config", required=True)
    sp.set_defaults(fn=cmd_config)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
