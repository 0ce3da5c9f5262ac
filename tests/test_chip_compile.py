"""Every device program of the served path, AOT-compiled for a v5e with
no chip attached.

`jax.experimental.topologies` describes a `v5e:2x2` host to the installed
libtpu without touching hardware, and `.lower(avals).compile()` against
its devices runs the whole TPU pipeline — Mosaic for the Pallas kernels,
XLA:TPU for the rest — at the shape the deployment really has: 954 shards
(956 when four devices split them), 8 rows, a full shard width of 32768
words as 256 lines of 128 (ops/blocks.py stack_shape). So a change that breaks Mosaic lowering, or that makes a program
materialize gigabytes of temporaries next to the resident stacks, fails
here on the CPU and not on a chip-minute budget. Compiling is not
running: VMEM behaviour at execution still belongs to chip_smoke.py.
"""

import re

import pytest

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from pilosa_tpu.core import Holder
from pilosa_tpu.exec import tpu as tpu_mod
from pilosa_tpu.exec.tpu import TPUBackend
from pilosa_tpu.ops import kernels, sparse
from pilosa_tpu.ops.blocks import WORD_LANES, WORD_LINES, stack_shape
from pilosa_tpu.parallel import ShardMesh

SHARDS, ROWS = 954, 8
#: A v5e chip has 16 GiB; f, g, h and the BSI planes hold about 5 of
#: them. A program whose temporaries pass this has stopped streaming.
MAX_TEMP_BYTES = 2 << 30
#: A count program reads its operand rows where they lie: next to 125 MB
#: a row (31 MB on a quarter) its temporaries are nothing (0.3 MB read).
MAX_COUNT_TEMP_BYTES = 16 << 20


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
        stack_shape(shards, rows), jnp.uint32, sharding=sharding
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
    slab = jax.ShapeDtypeStruct(
        (SHARDS, WORD_LINES, WORD_LANES), jnp.uint32, sharding=one
    )
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
    shape = stack_shape(SHARDS // 4 + 1, ROWS)
    n_pad = -(-shape[0] * ROWS * WORD_LINES * WORD_LANES
              // sparse.CHUNK_WORDS) * sparse.CHUNK_WORDS
    sparse._chunk_zeros_prog(dev)
    sparse._or_prog(dev)
    sparse._zeros_prog(dev, n_pad)
    sparse._place_prog(dev, n_pad)
    sparse._final_prog(dev, n_pad, shape)


def _deployment(v5e, holder, meshed):
    """(backend, a stack's sharding, a scalar's, the padded shard count)
    on one device, or on the four as a mesh."""
    if meshed:
        mesh = ShardMesh(v5e)
        be = TPUBackend(holder, mesh=mesh)
        return (
            be, NamedSharding(mesh.mesh, P(mesh.axis)),
            NamedSharding(mesh.mesh, P()), be.blocks._pad_shards(SHARDS),
        )
    one = SingleDeviceSharding(v5e[0])
    return TPUBackend(holder, device=v5e[0]), one, one, SHARDS


def _count_program(v5e, holder, meshed, kind, verb, n_slots):
    """(jitted count program, its arguments' avals, shards a device) for
    the benchmark's query shape, <verb>(Row(f), Row(g), Row(h)), at the
    deployment's size."""
    be, place, scalar, shards = _deployment(v5e, holder, meshed)
    spec = (verb, (("R", "f"), ("R", "g"), ("R", "h")))
    blocks = (_stack(place, shards=shards),) * 3
    if kind == "count_batch":
        # a row id and a mask for each leaf, the lane mask
        lane = jax.ShapeDtypeStruct((n_slots,), jnp.uint32, sharding=scalar)
        scalars = (lane,) * 7
    else:
        scalars = (jax.ShapeDtypeStruct((), jnp.uint32, sharding=scalar),) * 6
    program = be._program(kind, spec, True).__wrapped__
    return program, (blocks, scalars), shards // (len(v5e) if meshed else 1)


@pytest.mark.parametrize("meshed", [False, True])
def test_count_batch_scan_carries_its_kind(v5e, on_chip, meshed):
    """The benchmark's count program (a 3-row verb, four query slots) at
    the deployment's shape: it compiles for the chip with its scopes, and
    the module is named for its kind, which is how a trace reduction
    tells a count program from any other (ISSUE 26)."""
    program, avals, _ = _count_program(
        v5e, on_chip, meshed, "count_batch", "I", 4
    )
    lowered = program.lower(*avals)
    assert "jit_pilosa_count_batch" in lowered.as_text()[:400]
    text = lowered.compile().as_text()
    assert "HloModule jit_pilosa_count_batch" in text
    for scope in ("row_gather", "verb", "popcount", "shard_sum"):
        assert scope in text, scope


def _row_sized_results(text: str, local_shards: int) -> list[str]:
    """Instructions of a compiled program that WRITE a whole operand row
    of a device's stack (`u32[954,256,128]`, `u32[954,1,32768]` and their
    like): 125 MB written and read again for every row of every query
    slot. Lines inside a fusion's own computation are steps of one loop
    over the operands, nothing in memory, and do not count; parameters
    and their tuple plumbing carry the whole STACK, eight rows, and do
    not match."""
    row = re.compile(
        r"= u32\[%d,(?:1,)?(?:%d,%d|%d)\]"
        % (local_shards, WORD_LINES, WORD_LANES, WORD_LINES * WORD_LANES)
    )
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    found, computation = [], None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{$", ln)
        if head:
            computation = head.group(1)
        elif computation not in fused and row.search(ln):
            found.append(ln.strip()[:160])
    return found


@pytest.mark.parametrize("meshed", [False, True], ids=["one", "mesh4"])
@pytest.mark.parametrize(
    "kind,n_slots", [("count_batch", 4), ("count_batch", 16), ("count", 1)]
)
@pytest.mark.parametrize("verb", ["I", "U", "D", "X"])
def test_count_programs_read_rows_in_place(v5e, on_chip, verb, kind, n_slots,
                                           meshed):
    """ISSUE 27: the count programs of the benchmark's traffic stream.
    Each operand row is read from the resident stack inside the fusion
    that counts it; no instruction's result is a row (with the rows on
    the stack's tiled axes every verb copied three, 375 MB a slot, nine
    tenths of the chip's busy time). The compiled text is the criterion:
    under shard_map the memory analysis does not show such copies."""
    program, avals, local = _count_program(
        v5e, on_chip, meshed, kind, verb, n_slots
    )
    exe = program.lower(*avals).compile()
    assert _row_sized_results(exe.as_text(), local) == []
    if not meshed:
        temp = exe.memory_analysis().temp_size_in_bytes
        assert temp <= MAX_COUNT_TEMP_BYTES, f"{temp} bytes of temporaries"


@pytest.mark.parametrize("meshed", [False, True], ids=["one", "mesh4"])
def test_other_stack_readers_compile(v5e, on_chip, meshed):
    """Every other program kind that reads a stack, at the deployment's
    shape: a materialised row tree (with a Shift, which flattens its slab
    across the 128-word lines), its batched form, plain and filtered
    TopN, and the BSI sum and min over the 16 planes of `v` under a Row
    filter. None runs in a benchmark cell, so a layout change that one
    of them cannot follow shows here and in chip_smoke.py only."""
    be, place, scalar, shards = _deployment(v5e, on_chip, meshed)
    stack = _stack(place, shards=shards)
    u32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.uint32, sharding=scalar
    )
    row = ("R", "f")
    tree = ("U", (row, ("S", 3, ("R", "g"))))
    filt = ((stack,), (u32(), u32()))
    depth = 14
    bsi = _stack(place, shards=shards, rows=16)
    for kind, spec, args, extra in (
        ("vec", tree, ((stack,) * 2, (u32(),) * 4), None),
        ("vec_batch", tree, ((stack,) * 2, (u32(4),) * 5), None),
        ("topn_plain", None, (stack,), None),
        ("topn_src", row, (stack, *filt), None),
        ("bsi_sum", row, (bsi, *filt), depth),
        ("bsi_min", row, (bsi, *filt), depth),
    ):
        program = be._program(kind, spec, True, extra=extra).__wrapped__
        _compiled(program, *args)


@pytest.mark.parametrize("slots", [1, 16])
def test_tanimoto_programs_compile_at_the_molecule_library_s_height(v5e, on_chip, slots):
    """The programs over a packed stack (ISSUE 36) at chem-1chip's shape,
    uint32[1, 1,700,864, 128] (0.87 GB): the sweep with its bounded lists
    at the smallest and the largest slot bucket, the exact finish of an
    overflowing leg, the row counts and the row splice of a point write.
    At 16 slots the [16, R] counts and the compaction's compares are the
    temporaries: well under a chip, next to the stack."""
    from pilosa_tpu.ops.blocks import PACKED_WORDS, packed_rows

    be = TPUBackend(on_chip, device=v5e[0])
    one = SingleDeviceSharding(v5e[0])
    rows = packed_rows(1_700_000)
    assert rows == 1_700_864
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one
    )
    packed = jax.ShapeDtypeStruct((1, rows, PACKED_WORDS), jnp.uint32, sharding=one)
    legs = (i32(slots),) * 3
    topn = be._program("topn_tanimoto", None, False).__wrapped__
    mem = _compiled(topn, packed, i32(1, rows), *legs)
    # What a launch hands back is its bounded lists, not a row vector:
    # a short one a slot, and the long slots (ISSUE 37).
    long_slots = min(kernels.TANIMOTO_LONG_SLOTS, slots)
    listed = (slots * (1 + 2 * kernels.TANIMOTO_SHORT)
              + long_slots * (1 + 2 * kernels.TANIMOTO_LIST)) * 4
    assert listed <= mem.output_size_in_bytes <= listed + (64 << 10)
    if slots == 1:
        _compiled(be._program("topn_tanimoto_counts", None, False).__wrapped__,
                  packed, i32(1, rows), *legs)
        _compiled(be._program("packed_row_counts", None, False).__wrapped__, packed)
        n = be.blocks.PACKED_UPDATE_ROWS
        _compiled(
            be.blocks._packed_update_fn(), packed, i32(n), i32(n),
            jax.ShapeDtypeStruct((n, PACKED_WORDS), jnp.uint32, sharding=one),
        )


@pytest.mark.parametrize("depth, signed", [(24, False), (17, False), (24, True)],
                         ids=["revenue", "supplycost", "signed"])
@pytest.mark.parametrize("meshed", [False, True], ids=["one", "mesh4"])
def test_group_sum_program_compiles_at_the_star_schema_s_shape(v5e, on_chip,
                                                                meshed, depth,
                                                                signed):
    """`pilosa_group_sum` (GroupBy with a Sum aggregate) at the shape of
    SSB's Q4.3 on one chip's share of SF 100: 36 shards; d_year, s_city
    and p_brand1 (8, 256 and 1,000 rows) grouped under a filter; the
    revenue's 24 planes or the supply cost's 17, and a signed field's
    masks (twice the planes). Mosaic lowers one kernel, whose grid walks
    the steps: it copies each step's combinations' rows by id and ANDs
    them as it counts them against the masks. The masks are the
    program's one large temporary, and no step writes its combinations'
    rows (`u32[S, 16, 256, 128]`, 75.5 MB a field at 36 shards) to
    HBM."""
    shards = 36
    if meshed:
        mesh = ShardMesh(v5e)
        be = TPUBackend(on_chip, mesh=mesh)
        place = NamedSharding(mesh.mesh, P(mesh.axis))
        scalar = NamedSharding(mesh.mesh, P())
    else:
        be = TPUBackend(on_chip, device=v5e[0])
        place = scalar = SingleDeviceSharding(v5e[0])
    stacks = [_stack(place, shards=shards, rows=r) for r in (8, 256, 1000)]
    bsi = _stack(place, shards=shards, rows=32)
    slab = jax.ShapeDtypeStruct((shards, WORD_LINES, WORD_LANES), jnp.uint32,
                                sharding=place)
    slots = tpu_mod.GROUP_SUM_SLOTS
    program = be._group_sum_program(3, True, depth, signed).__wrapped__
    lowered = program.lower(
        *stacks, bsi,
        jax.ShapeDtypeStruct((slots, 3), jnp.int32, sharding=scalar),
        jax.ShapeDtypeStruct((slots,), jnp.uint32, sharding=scalar),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar),
        slab,
    )
    assert "jit_pilosa_group_sum" in lowered.as_text()[:400]
    exe = lowered.compile()
    text = exe.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    local = shards // (len(v5e) if meshed else 1)
    step_rows = "u32[%d,%d,%d,%d]" % (
        local, kernels.GROUP_SUM_CHUNK, WORD_LINES, WORD_LANES
    )
    assert step_rows not in text
    mem = exe.memory_analysis()
    assert mem.temp_size_in_bytes <= MAX_TEMP_BYTES, mem.temp_size_in_bytes
    if not meshed:
        # The 19-50 masks of 36 shards, 89-236 MB, and nothing of size
        # beside them.
        n_masks = 2 + depth * (2 if signed else 1)
        masks = shards * n_masks * WORD_LINES * WORD_LANES * 4
        assert mem.temp_size_in_bytes <= masks + (16 << 20), (
            mem.temp_size_in_bytes, masks
        )


def test_a_union_of_rows_of_one_stack_reads_it_as_one_argument(v5e, on_chip):
    """SSB's Q2.2 filter, a Union of eight rows of p_brand1 (1,000 rows of
    36 shards, 4.7 GB): the assembled program takes the stack once ("@" in
    the spec, TPUBackend._assemble), where a leaf each asked the compiler
    for eight copies, 38 GB, and it refused the program."""
    be = TPUBackend(on_chip, device=v5e[0])
    one = SingleDeviceSharding(v5e[0])
    stack = _stack(one, shards=36, rows=1000)
    u32 = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one)
    spec = ("@", (0,) * 8, ("U", (("R", "p_brand1"),) * 8))
    mem = _compiled(be._program("vec", spec, False).__wrapped__, (stack,), (u32,) * 16)
    assert mem.argument_size_in_bytes < 5 << 30


@pytest.mark.parametrize("meshed", [False, True], ids=["one", "mesh4"])
def test_group_rows_program_reads_the_listed_rows_in_place(v5e, on_chip, meshed):
    """`pilosa_groupby_rows`, the count under a filter of the rows a
    grouped Sum's filter names, at SSB's p_brand1 shape (1,000 rows of
    36 shards, 4.7 GB) and a bucket of 8 ids (Q2.2's brands): it reads
    those rows where they lie, so its temporaries hold no copy of the
    stack, nor of the eight rows; and the check that a stack's rows are
    disjoint, which reads the whole stack once, holds no copy of it
    either."""
    shards = 36
    be, place, scalar, _ = _deployment(v5e, on_chip, meshed)
    stack = _stack(place, shards=shards, rows=1000)
    slab = jax.ShapeDtypeStruct((shards, WORD_LINES, WORD_LANES), jnp.uint32,
                                sharding=place)
    ids = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=scalar)
    local = shards // (len(v5e) if meshed else 1)
    row_bytes = local * WORD_LINES * WORD_LANES * 4
    lowered = be._group_rows_program(True).__wrapped__.lower(stack, ids, slab)
    assert "jit_pilosa_groupby_rows" in lowered.as_text()[:400]
    mem = _compiled(be._group_rows_program(True).__wrapped__, stack, ids, slab)
    assert mem.temp_size_in_bytes < row_bytes, mem.temp_size_in_bytes
    mem = _compiled(
        be._overlap_program().__wrapped__, stack
    )
    assert mem.temp_size_in_bytes < row_bytes, mem.temp_size_in_bytes


@pytest.mark.parametrize("filtered", [True, False])
def test_row_id_programs_compile_at_72_shards(v5e, on_chip, filtered):
    """A mutex field of 1,000 rows held as uint16 row ids over 72 shards
    (an SF 100 node's share of eight, p_brand1): the histogram that
    prunes it, the build of 64 live rows into a dense stack, and a row
    leaf of a Union of eight of its rows compile for one v5e. The
    histogram's two small stacks (32 + 32 rows, 604 MB) and a part of
    the ids (151 MB) are its temporaries, the built rows (64 rows, 604
    MB) the build's output; nothing of the size of the 9.4 GB dense
    stack appears."""
    shards = 72
    be = TPUBackend(on_chip, device=v5e[0])
    one = SingleDeviceSharding(v5e[0])
    ids = jax.ShapeDtypeStruct((shards, WORD_LINES, 32, WORD_LANES), jnp.uint16,
                               sharding=one)
    slab = jax.ShapeDtypeStruct((shards, WORD_LINES, WORD_LANES), jnp.uint32,
                                sharding=one)
    hi0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    row_bytes = shards * WORD_LINES * WORD_LANES * 4

    def make(lo_bits, hi_rows, _filtered):
        def hist(ids, hi0, *filt):
            return kernels.rowid_hist(ids, filt[0] if filt else None, hi0,
                                      lo_bits, hi_rows)
        return hist

    prog = be._rowid_program("rowid_hist", make, 5, 32, filtered).__wrapped__
    lowered = prog.lower(ids, hi0, *((slab,) if filtered else ()))
    assert "jit_pilosa_rowid_hist" in lowered.as_text()[:400]
    exe = lowered.compile()
    assert exe.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert exe.memory_analysis().temp_size_in_bytes <= (64 + 16) * row_bytes + (64 << 20)

    build = be._rowid_program(
        "rowid_build", lambda: lambda ids, rows: kernels.rowid_rows(ids, rows)
    ).__wrapped__
    exe = build.lower(
        ids, jax.ShapeDtypeStruct((64,), jnp.uint32, sharding=one)
    ).compile()
    assert exe.memory_analysis().temp_size_in_bytes <= 16 << 20

    from pilosa_tpu.exec.tpu import _eval_spec

    spec = ("RI", "p_brand1", 8)
    leaf = jax.jit(lambda blocks, scalars: _eval_spec(
        spec, iter(blocks), iter(scalars)))
    exe = leaf.lower(
        (ids,), (jax.ShapeDtypeStruct((8,), jnp.uint32, sharding=one),)
    ).compile()
    assert exe.memory_analysis().temp_size_in_bytes <= MAX_COUNT_TEMP_BYTES
