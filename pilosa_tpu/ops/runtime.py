"""How the package binds to the JAX runtime: which platform a device
backend may serve from, when Pallas kernels interpret, and where
compiled programs persist. One definition of each, so the server, the
bench, the smoke's child and the tests cannot disagree about them.

The platform rule. `TPUBackend` serves from a TPU. JAX itself is less
strict: on a host where no TPU initializes, `jax.devices()` quietly
yields CPU devices and every program still runs — the Pallas kernels in
interpret mode. A server that came up that way would answer correctly,
slowly, and claim "executor=tpu". So a platform other than `tpu` is
accepted only when the operator named it: `JAX_PLATFORMS=cpu` exported
before start-up asked for CPU devices (the test suite's virtual
8-device mesh, `make bench-smoke`); the same CPU devices with
`JAX_PLATFORMS` unset were not asked for, and the backend refuses to
build on them.
"""

from __future__ import annotations

import os
from collections.abc import Mapping
from typing import Optional

import jax

#: Persistent compile cache when JAX_COMPILATION_CACHE_DIR does not
#: place it: a fixed directory in the checkout, derived from the
#: package's location — never from tempfile, a pid or a clock — so a
#: restart finds what the last process compiled.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def _asked_platforms(environ: Mapping[str, str]) -> list[str]:
    """The platforms the operator named in JAX_PLATFORMS, in order."""
    return [
        p.strip().lower()
        for p in environ.get("JAX_PLATFORMS", "").split(",")
        if p.strip()
    ]


def configure_compile_cache(
    environ: Mapping[str, str] = os.environ,
) -> Optional[str]:
    """Point JAX's persistent compilation cache at its directory and
    return it (None: no cache). With JAX_COMPILATION_CACHE_DIR set, JAX
    has already read it into its config and no directory is set in
    code; otherwise the cache goes to COMPILE_CACHE_DIR — except where
    the operator asked for CPU devices: an XLA:CPU executable is tied to
    the feature set of the CPU that compiled it (the loader warns of
    SIGILL on every hit, even on the same host), and the test suite has
    no restart to speed up. Must run before the process's first
    compile: JAX decides once, at that compile, whether a cache is in
    use. `pilosa_tpu.ops` calls it on import, which every module that
    compiles passes through (jit and AOT `.lower().compile()` alike)."""
    # Every program is kept, not only those JAX's default deems slow to
    # compile (>= 1 s): the serving path holds dozens of small programs
    # whose compiles add up on a restart's first queries, and "a warm
    # cache compiles nothing" is a property a run can check.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if _asked_platforms(environ)[:1] == ["cpu"]:
        return None
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


class DevicePlatformError(RuntimeError):
    """The JAX platform is not one a device backend may serve from."""


def require_serving_platform(
    platform: str, environ: Mapping[str, str] = os.environ
) -> None:
    """The platform rule (module docstring): `tpu`, or a platform the
    operator named in JAX_PLATFORMS. Raises DevicePlatformError naming
    the platform otherwise."""
    if platform == "tpu" or platform in _asked_platforms(environ):
        return
    raise DevicePlatformError(
        f"JAX resolved platform {platform!r}, not 'tpu', and "
        f"JAX_PLATFORMS ({environ.get('JAX_PLATFORMS', '')!r}) did not "
        "ask for it: no TPU initialized on this host. Export "
        f"JAX_PLATFORMS={platform} to serve from {platform} devices on "
        "purpose, or start the server with --executor cpu"
    )


def pallas_interpret() -> bool:
    """Pallas kernels compile through Mosaic on a TPU and run in
    interpret mode anywhere else — which, by the platform rule, is only
    where the operator asked for another platform."""
    return jax.default_backend() != "tpu"
