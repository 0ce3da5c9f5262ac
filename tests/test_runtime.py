"""The package's binding to the JAX runtime (pilosa_tpu/ops/runtime.py):
the platform a device backend may serve from, and where compiled
programs persist.

Every case here gives the same result on a host that does hold a chip:
the rule is tested as a function of (resolved platform, environment),
and the refusals run inside this process, whose JAX the suite already
pinned to CPU devices — no child is started that an unset JAX_PLATFORMS
would let take the chip.
"""

import os
import subprocess
import sys

import pytest

import jax

from pilosa_tpu import cli
from pilosa_tpu.core import Holder
from pilosa_tpu.exec.tpu import TPUBackend
from pilosa_tpu.ops.runtime import (
    COMPILE_CACHE_DIR,
    DevicePlatformError,
    configure_compile_cache,
    require_serving_platform,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize(
    "platform, env, ok",
    [
        ("tpu", {}, True),
        ("tpu", {"JAX_PLATFORMS": "cpu"}, True),
        ("cpu", {"JAX_PLATFORMS": "cpu"}, True),        # asked for
        ("cpu", {"JAX_PLATFORMS": " CPU "}, True),
        ("cpu", {}, False),                              # got, not asked
        ("cpu", {"JAX_PLATFORMS": ""}, False),
        ("cpu", {"JAX_PLATFORMS": "tpu"}, False),
        ("gpu", {"JAX_PLATFORMS": "cpu"}, False),
    ],
)
def test_platform_rule(platform, env, ok):
    if ok:
        require_serving_platform(platform, env)
        return
    with pytest.raises(DevicePlatformError) as e:
        require_serving_platform(platform, env)
    assert repr(platform) in str(e.value)


def test_backend_refuses_cpu_devices_nobody_asked_for(tmp_path, monkeypatch):
    holder = Holder(str(tmp_path)).open()
    try:
        TPUBackend(holder)  # the suite exported JAX_PLATFORMS=cpu: asked for
        monkeypatch.delenv("JAX_PLATFORMS")
        with pytest.raises(DevicePlatformError, match="'cpu'"):
            TPUBackend(holder)
    finally:
        holder.close()


def _server_exit(tmp_path, monkeypatch) -> tuple[int, str]:
    log = tmp_path / "server.log"
    monkeypatch.setenv("PILOSA_TPU_LOG_PATH", str(log))
    rc = cli.main(["server", "-d", str(tmp_path / "data"), "--bind", "localhost:0"])
    return rc, log.read_text()


def test_server_not_on_the_asked_platform_exits_nonzero(tmp_path, monkeypatch):
    """executor=tpu (the default) on CPU devices JAX fell back to:
    exit code 1 and the platform named, before anything is served."""
    monkeypatch.delenv("JAX_PLATFORMS")
    rc, log = _server_exit(tmp_path, monkeypatch)
    assert rc == 1
    assert "DevicePlatformError" in log and "'cpu'" in log
    assert "listening on" not in log


def test_mesh_config_error_reaches_the_exit_code(tmp_path, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_MESH_DEVICES", str(len(jax.devices()) + 1))
    rc, log = _server_exit(tmp_path, monkeypatch)
    assert rc == 1
    assert "MeshConfigError" in log and "mesh-devices" in log
    assert "listening on" not in log


def test_cache_directory_is_fixed_in_the_checkout_when_unplaced():
    before = jax.config.jax_compilation_cache_dir
    try:
        first = configure_compile_cache({})
        second = configure_compile_cache({"JAX_PLATFORMS": "tpu"})
        assert first == second == COMPILE_CACHE_DIR
        assert COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == COMPILE_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_no_in_checkout_cache_on_asked_for_cpu_devices():
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache({"JAX_PLATFORMS": "cpu"}) is None
    assert jax.config.jax_compilation_cache_dir == before


def test_placed_cache_is_used_and_nothing_lands_in_the_checkout(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX's config holds that directory,
    jit and AOT compiles both land there, and the checkout's own cache
    directory is not touched."""
    placed = tmp_path / "cache"

    def listing():
        return sorted(os.listdir(COMPILE_CACHE_DIR)) if os.path.isdir(
            COMPILE_CACHE_DIR) else None

    before = listing()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(placed), PYTHONPATH=REPO)
    code = (
        "import jax, jax.numpy as jnp\n"
        "import pilosa_tpu.ops\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()\n"
        "jax.jit(lambda x: x ^ 5).lower("
        "jax.ShapeDtypeStruct((16,), jnp.uint32)).compile()\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == str(placed)
    assert len(os.listdir(placed)) >= 2
    assert listing() == before
