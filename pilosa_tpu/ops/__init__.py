"""TPU device ops: dense bitmap blocks in HBM + the Pallas batch kernel.

This is the execution layer BASELINE.json's north star describes: each
fragment's roaring containers are flattened into a dense
uint32[rows, SHARD_WIDTH/32] block resident in HBM; PQL bitmap verbs
lower to bitwise ops and Count/TopN/Sum to popcount reductions, fused by
XLA, with the pair_stats Pallas kernel sweeping batched 2-row counts at
HBM roofline. Blocks are cached on device and re-uploaded only when the
owning fragment's version changes.
"""

from pilosa_tpu.ops.runtime import configure_compile_cache

# Before anything under this package can compile: JAX latches whether a
# persistent cache is in use at the process's first compile.
configure_compile_cache()

# A process that runs device programs puts its phases on the profiler's
# host plane, on the device planes' clock (utils/qprofile.py `phase`).
import jax.profiler  # noqa: E402

from pilosa_tpu.utils import qprofile  # noqa: E402

qprofile.set_span_factory(jax.profiler.TraceAnnotation)

from pilosa_tpu.ops.blocks import WORDS_PER_SHARD, pack_fragment  # noqa: E402
from pilosa_tpu.ops.kernels import (  # noqa: E402
    MAX_PAIR_SHARDS,
    pair_stats,
    pair_stats_xla,
)
