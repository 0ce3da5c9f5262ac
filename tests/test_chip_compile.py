"""Every device program of the served path, AOT-compiled for a v5e with
no chip attached.

`jax.experimental.topologies` describes a `v5e:2x2` host to the installed
libtpu without touching hardware, and `.lower(avals).compile()` against
its devices runs the whole TPU pipeline — Mosaic for the Pallas kernels,
XLA:TPU for the rest — at the shape the deployment really has: 954 shards
(956 when four devices split them), 8 rows, a full shard width of 32768
words. So a change that breaks Mosaic lowering, or that makes a program
materialize gigabytes of temporaries next to the resident stacks, fails
here on the CPU and not on a chip-minute budget. Compiling is not
running: VMEM behaviour at execution still belongs to chip_smoke.py.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from pilosa_tpu.core import Holder
from pilosa_tpu.exec import tpu as tpu_mod
from pilosa_tpu.exec.tpu import TPUBackend
from pilosa_tpu.ops import kernels, sparse
from pilosa_tpu.ops.blocks import WORDS_PER_SHARD
from pilosa_tpu.parallel import ShardMesh

SHARDS, ROWS = 954, 8
#: A v5e chip has 16 GiB; f, g, h and the BSI planes hold about 5 of
#: them. A program whose temporaries pass this has stopped streaming.
MAX_TEMP_BYTES = 2 << 30


@pytest.fixture(scope="module")
def v5e():
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu: nothing to compile with
        pytest.skip(f"no compile-only TPU topology: {e}")
    assert "v5" in topo.devices[0].device_kind.lower()
    return topo.devices


@pytest.fixture
def on_chip(monkeypatch, tmp_path):
    """Backends as a v5e process would build them: Pallas through
    Mosaic, not the interpreter, and no background warm threads racing
    the compiles the test makes itself."""
    monkeypatch.setattr(tpu_mod, "pallas_interpret", lambda: False)
    monkeypatch.setattr(tpu_mod, "warm_chunk_programs", lambda device: None)
    holder = Holder(str(tmp_path)).open()
    yield holder
    holder.close()


def _compiled(jitted, *avals):
    exe = jitted.lower(*avals).compile()
    mem = exe.memory_analysis()
    assert mem.temp_size_in_bytes <= MAX_TEMP_BYTES, (
        f"{mem.temp_size_in_bytes} bytes of temporaries"
    )
    return mem


def _stack(sharding, shards=SHARDS, rows=ROWS):
    return jax.ShapeDtypeStruct(
        (shards, rows, WORDS_PER_SHARD), jnp.uint32, sharding=sharding
    )


@pytest.mark.parametrize("rows", [8, 16])
def test_pallas_pair_kernels_lower_through_mosaic(v5e, rows):
    one = SingleDeviceSharding(v5e[0])
    stack = _stack(one, rows=rows)
    for kernel in (kernels.pair_stats, kernels.pair_stats_pershard):
        mem = _compiled(
            jax.jit(lambda f, g, k=kernel: k(f, g, interpret=False)),
            stack, stack,
        )
        # The sweep streams: nothing but the stacks in, the stats out.
        assert mem.temp_size_in_bytes < (64 << 20)


def test_one_device_serving_programs(v5e, on_chip):
    be = TPUBackend(on_chip, device=v5e[0])
    one = SingleDeviceSharding(v5e[0])
    stack = _stack(one)
    for pershard in (True, False):
        _compiled(be._pair_program(pershard).__wrapped__, stack, stack)
    # GroupBy(Rows(f), Rows(g) [, filter]) rides the Pallas sweep too.
    slab = jax.ShapeDtypeStruct((SHARDS, WORDS_PER_SHARD), jnp.uint32, sharding=one)
    _compiled(be._group_program(2, True).__wrapped__, stack, stack, slab)
    # The tile engine compiles itself ahead of time, for its own device.
    shapes = (stack.shape,) * 3
    for filtered, pershard in ((False, True), (True, False)):
        be._group_tile_program(shapes, 4, filtered, pershard)
    kinds = {e["kind"]: e["compiles"] for e in be.programs.ledger()}
    assert kinds == {"group_tile_pershard": 1, "group_tile": 1}
    # The dirty-shard scatter every write epoch chains.
    _compiled(
        be.blocks._warm_update_fn(stack.shape),
        stack,
        _stack(one, shards=be.blocks.UPDATE_CHUNK),
        jax.ShapeDtypeStruct((be.blocks.UPDATE_CHUNK,), jnp.int32, sharding=one),
    )


def test_four_device_mesh_programs(v5e, on_chip):
    mesh = ShardMesh(v5e)
    be = TPUBackend(on_chip, mesh=mesh)
    s_pad = be.blocks._pad_shards(SHARDS)
    assert s_pad == 956
    sharded = NamedSharding(mesh.mesh, P(mesh.axis))
    stack = _stack(sharded, shards=s_pad)
    for pershard in (True, False):  # shard_map + gather / psum over ICI
        _compiled(be._pair_program(pershard).__wrapped__, stack, stack)
    shapes = (stack.shape,) * 3
    for filtered, pershard in ((False, True), (True, False)):
        be._group_tile_program(shapes, 4, filtered, pershard)
    # The splice body: one slab, index and validity lane per device.
    n = mesh.n * be.blocks.MESH_UPDATE_CHUNK
    lane = lambda dt: jax.ShapeDtypeStruct((n,), dt, sharding=sharded)  # noqa: E731
    _compiled(
        be.blocks._mesh_update_fn(), stack, _stack(sharded, shards=n),
        lane(jnp.int32), lane(jnp.uint32),
    )


def test_upload_programs_compile_pinned_to_a_mesh_device(v5e):
    """ops/sparse.py AOT-builds its fixed-shape upload programs per
    device, pinned when the device is not the process's first — what a
    mesh's per-device sub-stack builders depend on. Only the placement
    programs are compiled here: the decompress and container-expansion
    programs take 8-27 s each to compile for a v5e, which is a
    background warm's job (and the persistent cache's), not tier-1's."""
    dev = v5e[1]
    assert sparse._pin(dev) is not None
    n_pad = -(-(SHARDS // 4 + 1) * ROWS * WORDS_PER_SHARD
              // sparse.CHUNK_WORDS) * sparse.CHUNK_WORDS
    sparse._chunk_zeros_prog(dev)
    sparse._or_prog(dev)
    sparse._zeros_prog(dev, n_pad)
    sparse._place_prog(dev, n_pad)
    sparse._final_prog(dev, n_pad, (SHARDS // 4 + 1, ROWS, WORDS_PER_SHARD))


@pytest.mark.parametrize("meshed", [False, True])
def test_count_batch_scan_carries_its_kind(v5e, on_chip, meshed):
    """The benchmark's count program (a 3-row verb, four query slots) at
    the deployment's shape: it compiles for the chip with its scopes, and
    the module is named for its kind, which is how a trace reduction
    tells a count program from any other (ISSUE 26)."""
    if meshed:
        mesh = ShardMesh(v5e)
        be = TPUBackend(on_chip, mesh=mesh)
        place = NamedSharding(mesh.mesh, P(mesh.axis))
        scalar = NamedSharding(mesh.mesh, P())
        shards = be.blocks._pad_shards(SHARDS)
    else:
        be = TPUBackend(on_chip, device=v5e[0])
        place = scalar = SingleDeviceSharding(v5e[0])
        shards = SHARDS
    spec = ("I", (("R", "f"), ("R", "g"), ("R", "h")))
    blocks = (_stack(place, shards=shards),) * 3
    slots = jax.ShapeDtypeStruct((4,), jnp.uint32, sharding=scalar)
    scalars = (slots,) * 7  # a row id and a mask for each leaf, the lane mask
    program = be._program("count_batch", spec, True).__wrapped__
    lowered = program.lower(blocks, scalars)
    assert "jit_pilosa_count_batch" in lowered.as_text()[:400]
    text = lowered.compile().as_text()
    assert "HloModule jit_pilosa_count_batch" in text
    for scope in ("row_gather", "verb", "popcount", "shard_sum"):
        assert scope in text, scope
