"""Server config: three sources with later-wins precedence, TOML
round-trip through generate-config, and option wiring (reference
server/config.go + docs/configuration.md)."""

import tomllib

from pilosa_tpu.server.config import Config


class TestSources:
    def test_defaults(self):
        cfg = Config.from_sources(env={})
        assert cfg.bind == "localhost:10101"
        assert cfg.executor == "tpu"
        assert cfg.max_hbm_bytes == 0
        assert cfg.client_timeout == 30.0

    def test_toml_then_env_then_flags(self, tmp_path):
        p = tmp_path / "c.toml"
        p.write_text(
            'bind = "host1:1"\nexecutor = "cpu"\nmax-hbm-bytes = 123\n'
            "[cluster]\nreplicas = 2\n"
        )
        cfg = Config.from_sources(
            toml_path=str(p),
            env={"PILOSA_TPU_BIND": "host2:2", "PILOSA_TPU_MAX_HBM_BYTES": "456"},
            args={"bind": "host3:3"},
        )
        assert cfg.bind == "host3:3"  # flag beats env beats toml
        assert cfg.max_hbm_bytes == 456  # env beats toml
        assert cfg.executor == "cpu"  # toml beats default
        assert cfg.cluster.replicas == 2

    def test_env_cluster_hosts(self):
        cfg = Config.from_sources(env={"PILOSA_TPU_CLUSTER_HOSTS": "a:1,b:2"})
        assert cfg.cluster.hosts == ["a:1", "b:2"]

    def test_bind_forms(self):
        for bind, want in [
            ("h:9", ("h", 9)),
            (":9", ("localhost", 9)),
            ("h", ("h", 10101)),
            ("[::1]:9", ("::1", 9)),
            ("::1", ("::1", 10101)),
        ]:
            cfg = Config.from_sources(env={}, args={"bind": bind})
            assert (cfg.host, cfg.port) == want, bind


class TestRoundTrip:
    def test_generate_config_reparses_to_same_values(self, tmp_path):
        cfg = Config.from_sources(env={})
        cfg.max_hbm_bytes = 789
        cfg.long_query_time = 1.5
        text = cfg.toml_text()
        data = tomllib.loads(text)
        assert data["max-hbm-bytes"] == 789
        p = tmp_path / "gen.toml"
        p.write_text(text)
        cfg2 = Config.from_sources(toml_path=str(p), env={})
        assert cfg2.max_hbm_bytes == 789
        assert cfg2.long_query_time == 1.5
        assert cfg2.to_dict() == cfg.to_dict()


class TestPlaneIsolationKnobs:
    """ISSUE r19 knobs (snapshot-bandwidth / snapshot-concurrency /
    refresh-window-ms / ingest-derate): every source and sink agrees —
    the config-drift contract, pinned per-knob here."""

    def test_defaults(self):
        cfg = Config.from_sources(env={})
        assert cfg.snapshot_bandwidth == 0       # uncapped
        assert cfg.snapshot_concurrency == 2
        assert cfg.refresh_window_ms == 0        # windowing off
        assert cfg.ingest_derate is True

    def test_env(self):
        cfg = Config.from_sources(env={
            "PILOSA_TPU_SNAPSHOT_BANDWIDTH": "1048576",
            "PILOSA_TPU_SNAPSHOT_CONCURRENCY": "4",
            "PILOSA_TPU_REFRESH_WINDOW_MS": "50",
            "PILOSA_TPU_INGEST_DERATE": "false",
        })
        assert cfg.snapshot_bandwidth == 1 << 20
        assert cfg.snapshot_concurrency == 4
        assert cfg.refresh_window_ms == 50
        assert cfg.ingest_derate is False
        d = cfg.to_dict()
        assert d["snapshot-bandwidth"] == 1 << 20
        assert d["snapshot-concurrency"] == 4
        assert d["refresh-window-ms"] == 50
        assert d["ingest-derate"] is False

    def test_toml_text_round_trip(self, tmp_path):
        cfg = Config.from_sources(env={})
        cfg.snapshot_bandwidth = 8 << 20
        cfg.snapshot_concurrency = 3
        cfg.refresh_window_ms = 25
        cfg.ingest_derate = False
        p = tmp_path / "gen.toml"
        p.write_text(cfg.toml_text())
        cfg2 = Config.from_sources(toml_path=str(p), env={})
        assert cfg2.snapshot_bandwidth == 8 << 20
        assert cfg2.snapshot_concurrency == 3
        assert cfg2.refresh_window_ms == 25
        assert cfg2.ingest_derate is False
        assert cfg2.to_dict() == cfg.to_dict()
