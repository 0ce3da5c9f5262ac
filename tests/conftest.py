"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform so multi-chip sharding
(mesh/shard_map/psum paths) is exercised without TPU hardware, mirroring how
the reference tests multi-node with in-process clusters instead of real ones
(reference test/pilosa.go MustRunCluster). Must run before jax is imported.

Opt-in REAL-chip leg (VERDICT r4 #6): `PILOSA_TPU_TEST_TPU=1 pytest -m tpu`
keeps the ambient TPU platform and runs only the @pytest.mark.tpu tests
(tests/test_tpu_live.py) against the live chip. Run it SOLO — never
concurrently with bench.py or another chip user.
"""

import os

import numpy as np
import pytest

LIVE_TPU = os.environ.get("PILOSA_TPU_TEST_TPU", "") in ("1", "true")

if not LIVE_TPU:
    # Force, not setdefault: the ambient environment may preselect the real
    # TPU platform, but tests must run on the virtual 8-device CPU mesh.
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: opt-in tests that require the real TPU chip "
        "(PILOSA_TPU_TEST_TPU=1 pytest -m tpu; run solo)",
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests (FaultProxy blackhole/latency/drop "
        "in the in-process cluster harness); fast, bounded-timeout chaos "
        "stays in tier-1 — anything slow carries `slow` too",
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (`-m 'not slow'`)",
    )


def pytest_collection_modifyitems(config, items):
    if LIVE_TPU:
        # Live-chip mode runs ONLY the tpu-marked leg: the rest of the
        # suite depends on the virtual 8-device CPU mesh (not forced
        # above) and must never hammer the shared chip.
        keep = [i for i in items if "tpu" in i.keywords]
        drop = [i for i in items if "tpu" not in i.keywords]
        if drop:
            config.hook.pytest_deselected(items=drop)
            items[:] = keep
        return
    skip_tpu = pytest.mark.skip(
        reason="real-chip leg: set PILOSA_TPU_TEST_TPU=1 and run -m tpu solo"
    )
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip_tpu)


@pytest.fixture
def rng():
    return np.random.default_rng(42)
