"""TPU backend tests: block packing, kernels, TPUBackend differential vs
the CPU oracle, and mesh execution on the 8-device virtual CPU platform
(the multi-node-without-a-cluster strategy, SURVEY.md §4.3)."""

import time

import numpy as np
import pytest

import jax

from pilosa_tpu.core import Fragment, Holder
from pilosa_tpu.core.field import options_for_int
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.result import result_to_json
from pilosa_tpu.exec.tpu import TPUBackend
from pilosa_tpu.ops.blocks import (
    WORDS_PER_SHARD,
    pack_fragment,
    pack_row,
    tile_words as _tiled,
    unpack_row,
)
from pilosa_tpu.ops.kernels import pair_stats, pair_stats_xla
from pilosa_tpu.parallel import ShardMesh
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.stats import global_stats


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data")).open()
    yield h
    h.close()


class TestBlockPacking:
    def test_pack_roundtrip(self, rng):
        f = Fragment(None, "i", "f", "standard", 0)
        cols = np.unique(rng.integers(0, SHARD_WIDTH, 5000, dtype=np.uint64))
        f.bulk_import(np.full(cols.size, 3, dtype=np.uint64), cols)
        block = pack_fragment(f)
        assert block.shape[1] == WORDS_PER_SHARD
        assert block.shape[0] % 8 == 0
        np.testing.assert_array_equal(unpack_row(block[3]), cols)
        assert block[0].sum() == 0

    def test_pack_dense_container(self):
        f = Fragment(None, "i", "f", "standard", 0)
        cols = np.arange(0, 100_000, dtype=np.uint64)  # bitmap containers
        f.bulk_import(np.zeros(cols.size, dtype=np.uint64), cols)
        block = pack_fragment(f)
        np.testing.assert_array_equal(unpack_row(block[0]), cols)

    def test_pack_row_matches_pack_fragment(self, rng):
        f = Fragment(None, "i", "f", "standard", 0)
        cols = np.unique(rng.integers(0, SHARD_WIDTH, 5000, dtype=np.uint64))
        f.bulk_import(np.full(cols.size, 2, dtype=np.uint64), cols)
        block = pack_fragment(f)
        np.testing.assert_array_equal(pack_row(f, 2), block[2])
        np.testing.assert_array_equal(pack_row(f, 0), np.zeros(WORDS_PER_SHARD, np.uint32))


class TestPairStatsKernel:
    """The batched-count Pallas kernel (interpret mode on CPU) must match
    both the fused-XLA formulation and a numpy oracle."""

    def test_pair_stats_matches_numpy(self, rng):
        S, RF, RG, W = 3, 8, 16, 512
        f = rng.integers(0, 2**32, (S, RF, W), dtype=np.uint32)
        g = rng.integers(0, 2**32, (S, RG, W), dtype=np.uint32)
        pair, cf, cg = (
            np.asarray(x)
            for x in pair_stats(_tiled(f), _tiled(g), interpret=True)
        )
        want_pair = np.zeros((RF, RG), dtype=np.int64)
        for a in range(RF):
            for b in range(RG):
                want_pair[a, b] = np.bitwise_count(f[:, a] & g[:, b]).sum()
        np.testing.assert_array_equal(pair, want_pair)
        np.testing.assert_array_equal(cf, np.bitwise_count(f).sum(axis=(0, 2)))
        np.testing.assert_array_equal(cg, np.bitwise_count(g).sum(axis=(0, 2)))

    def test_pair_stats_matches_xla(self, rng):
        S, R, W = 5, 8, 256
        f = rng.integers(0, 2**32, (S, R, W), dtype=np.uint32)
        g = rng.integers(0, 2**32, (S, R, W), dtype=np.uint32)
        got = pair_stats(_tiled(f), _tiled(g), interpret=True)
        want = pair_stats_xla(_tiled(f), _tiled(g))
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestTPUBackendDifferential:
    """The TPU backend must agree with the CPU oracle on every query."""

    def _setup(self, holder, rng):
        idx = holder.create_index("i")
        idx.create_field("f")
        idx.create_field("g")
        idx.create_field("v", options_for_int(-500, 500))
        ex_cpu = Executor(holder)
        # random data across 3 shards
        for row in [1, 2, 3]:
            cols = np.unique(rng.integers(0, 3 * SHARD_WIDTH, 2000, dtype=np.uint64))
            idx.field("f").import_bits(np.full(cols.size, row, dtype=np.uint64), cols)
            ef = idx.existence_field()
            ef.import_bits(np.zeros(cols.size, dtype=np.uint64), cols)
        cols = np.unique(rng.integers(0, 3 * SHARD_WIDTH, 1500, dtype=np.uint64))
        idx.field("g").import_bits(np.full(cols.size, 7, dtype=np.uint64), cols)
        ex_tpu = Executor(holder, backend=TPUBackend(holder))
        return ex_cpu, ex_tpu

    QUERIES = [
        "Row(f=1)",
        "Count(Row(f=2))",
        "Count(Intersect(Row(f=1), Row(g=7)))",
        "Count(Union(Row(f=1), Row(f=2), Row(f=3)))",
        "Count(Difference(Row(f=1), Row(g=7)))",
        "Count(Xor(Row(f=2), Row(g=7)))",
        "Union(Row(f=1), Row(g=7))",
        "Intersect(Row(f=1), Row(f=2))",
        "Not(Row(f=1))",
        "All()",
        "Count(Not(Union(Row(f=1), Row(f=2))))",
        "TopN(f, n=2)",
        "TopN(f)",
        "TopN(f, Row(g=7), n=3)",
    ]

    @pytest.mark.parametrize("q", QUERIES)
    def test_differential(self, holder, rng, q):
        ex_cpu, ex_tpu = self._setup(holder, rng)
        want = [result_to_json(r) for r in ex_cpu.execute("i", q)]
        got = [result_to_json(r) for r in ex_tpu.execute("i", q)]
        assert got == want, q

    def test_write_invalidates_device_blocks(self, holder, rng):
        ex_cpu, ex_tpu = self._setup(holder, rng)
        before = ex_tpu.execute("i", "Count(Row(f=1))")[0]
        ex_tpu.execute("i", f"Set({SHARD_WIDTH + 123456}, f=1)")
        after = ex_tpu.execute("i", "Count(Row(f=1))")[0]
        assert after == before + 1
        # still agrees with oracle
        assert ex_cpu.execute("i", "Count(Row(f=1))")[0] == after

    BSI_QUERIES = [
        "Sum(field=v)",
        "Sum(Row(f=1), field=v)",
        "Min(field=v)",
        "Max(field=v)",
        "Min(Row(f=1), field=v)",
        "Max(Row(f=1), field=v)",
        "Row(v > 0)",
        "Row(v >= 0)",
        "Row(v < 0)",
        "Row(v <= 0)",
        "Row(v == 42)",
        "Row(v != 42)",
        "Row(v != null)",
        "Row(v > -50)",
        "Row(v < -50)",
        "Row(v >= -10)",
        "Row(v <= -10)",
        "Row(v > 1000)",  # out of range
        "Row(v < 1000)",  # encompassing -> notNull
        "Row(v >< [-20, 30])",  # mixed between
        "Row(v >< [5, 60])",  # positive between
        "Row(v >< [-60, -5])",  # negative between
        "Row(v >< [-500, 500])",  # full range -> notNull
        "Count(Intersect(Row(f=1), Row(v > 0)))",
    ]

    def _setup_bsi(self, holder, rng):
        ex_cpu, ex_tpu = self._setup(holder, rng)
        cols = np.unique(rng.integers(0, 3 * SHARD_WIDTH, 800, dtype=np.uint64))
        vals = rng.integers(-500, 501, cols.size)
        holder.index("i").field("v").import_value(cols, vals)
        ex_cpu.execute("i", "Set(5, v=42) Set(6, v=-10)")
        return ex_cpu, ex_tpu

    @pytest.mark.parametrize("q", BSI_QUERIES)
    def test_bsi_runs_on_device(self, holder, rng, q):
        ex_cpu, ex_tpu = self._setup_bsi(holder, rng)
        want = [result_to_json(r) for r in ex_cpu.execute("i", q)]
        got = [result_to_json(r) for r in ex_tpu.execute("i", q)]
        assert got == want, q

    def test_shift_on_device(self, holder, rng):
        ex_cpu, ex_tpu = self._setup(holder, rng)
        for q in ["Shift(Row(f=1), n=1)", "Shift(Row(f=2), n=40)", "Count(Shift(Row(f=1), n=3))"]:
            want = [result_to_json(r) for r in ex_cpu.execute("i", q)]
            got = [result_to_json(r) for r in ex_tpu.execute("i", q)]
            assert got == want, q

    def test_time_range_on_device(self, holder, rng):
        from pilosa_tpu.core.field import options_for_time

        ex_cpu, ex_tpu = self._setup(holder, rng)
        idx = holder.index("i")
        idx.create_field("t", options_for_time("YMDH"))
        ex_cpu.execute("i", 'Set(3, t=9, 2019-08-03T10:00)')
        ex_cpu.execute("i", 'Set(1048579, t=9, 2019-08-05T12:00)')
        q = "Row(t=9, from='2019-08-01T00:00', to='2019-08-31T00:00')"
        want = [result_to_json(r) for r in ex_cpu.execute("i", q)]
        got = [result_to_json(r) for r in ex_tpu.execute("i", q)]
        assert got == want

    def test_hbm_budget_evicts(self, holder, rng):
        ex_cpu, _ = self._setup(holder, rng)
        # Budget fits roughly one stack: queries still correct, stacks evict.
        be = TPUBackend(holder, max_bytes=3 * 8 * WORDS_PER_SHARD * 4)
        ex_tpu = Executor(holder, backend=be)
        for q in ["Count(Row(f=1))", "Count(Row(g=7))", "Count(Row(f=2))"]:
            want = [result_to_json(r) for r in ex_cpu.execute("i", q)]
            got = [result_to_json(r) for r in ex_tpu.execute("i", q)]
            assert got == want, q
        assert be.blocks.evictions > 0
        assert be.blocks.resident_bytes() <= 3 * 8 * WORDS_PER_SHARD * 4


class TestMeshExecutor:
    """Real PQL through the 8-device mesh: holder-resident fragments are
    stacked, sharded over the mesh with NamedSharding(P('shards')), and
    queried through shard_map+psum — differentially checked vs the CPU
    oracle (the VERDICT r1 top-next item)."""

    def _setup(self, holder, rng):
        idx = holder.create_index("i")
        idx.create_field("f")
        idx.create_field("g")
        idx.create_field("v", options_for_int(-500, 500))
        n_shards = 11  # not a multiple of 8: exercises shard padding
        for row in [1, 2, 3]:
            cols = np.unique(rng.integers(0, n_shards * SHARD_WIDTH, 6000, dtype=np.uint64))
            idx.field("f").import_bits(np.full(cols.size, row, dtype=np.uint64), cols)
            idx.existence_field().import_bits(np.zeros(cols.size, dtype=np.uint64), cols)
        cols = np.unique(rng.integers(0, n_shards * SHARD_WIDTH, 4000, dtype=np.uint64))
        idx.field("g").import_bits(np.full(cols.size, 7, dtype=np.uint64), cols)
        cols = np.unique(rng.integers(0, n_shards * SHARD_WIDTH, 900, dtype=np.uint64))
        vals = rng.integers(-500, 501, cols.size)
        idx.field("v").import_value(cols, vals)
        ex_cpu = Executor(holder)
        ex_mesh = Executor(holder, backend=TPUBackend(holder, mesh=ShardMesh()))
        return ex_cpu, ex_mesh

    QUERIES = [
        "Count(Intersect(Row(f=1), Row(g=7)))",
        "Count(Union(Row(f=1), Row(f=2), Row(f=3)))",
        "Count(Not(Row(f=1)))",
        "Row(f=2)",
        "TopN(f, n=2)",
        "TopN(f, Row(g=7), n=3)",
        "Sum(field=v)",
        "Min(field=v)",
        "Max(field=v)",
        "Count(Row(v > 100))",
        "Count(Row(v >< [-100, 100]))",
    ]

    @pytest.mark.parametrize("q", QUERIES)
    def test_mesh_differential(self, holder, rng, q):
        ex_cpu, ex_mesh = self._setup(holder, rng)
        want = [result_to_json(r) for r in ex_cpu.execute("i", q)]
        got = [result_to_json(r) for r in ex_mesh.execute("i", q)]
        assert got == want, q

    def test_mesh_count_batch(self, holder, rng):
        _, ex_mesh = self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string

        be = ex_mesh.backend
        calls = [
            parse_string(f"Intersect(Row(f={r}), Row(g=7))").calls[0] for r in [1, 2, 3]
        ]
        shards = list(range(11))
        batch = be.count_batch("i", calls, shards)
        singles = [be.count_shards("i", c, shards) for c in calls]
        assert batch == singles


class TestShardMesh:
    def test_mesh_has_8_devices(self):
        assert len(jax.devices()) == 8


class TestRowLeafEdges:
    """The row leaf at its edges (ISSUE 27): a row id beyond the packed
    rows reads as an all-zero slab under every verb and under Not (the
    leaf's mask; Union, Difference, Xor and Not sit above it and would
    count a clamped row's bits), rows 0 and rows_p - 1 read their own
    bits, and a padded slot of a batched launch contributes 0. Every
    count against numpy on the bits that were set."""

    N_SHARDS = 3
    ROWS = {"f": range(8), "g": (0, 3, 7), "h": range(4)}
    #: (f, g, h) row ids: first rows, last packed rows (rows_p - 1 = 7;
    #: h packs 4 rows into 8), then ids past the packed rows, alone and
    #: mixed with live ones.
    TRIPLES = [(0, 0, 0), (7, 7, 3), (8, 100, 9), (0, 100, 0), (7, 0, 9),
               (1000, 3, 2)]
    VERBS = {
        "Intersect": lambda a, b, c: a & b & c,
        "Union": lambda a, b, c: a | b | c,
        "Difference": lambda a, b, c: a & ~b & ~c,
        "Xor": lambda a, b, c: a ^ b ^ c,
    }

    def _setup(self, holder, rng):
        idx = holder.create_index("i")
        width = self.N_SHARDS * SHARD_WIDTH
        bits = {}
        exists = np.zeros(width, dtype=bool)
        for name, rows in self.ROWS.items():
            fld = idx.create_field(name)
            for row in rows:
                cols = np.unique(rng.integers(0, width, 4000, dtype=np.uint64))
                fld.import_bits(np.full(cols.size, row, dtype=np.uint64), cols)
                idx.existence_field().import_bits(
                    np.zeros(cols.size, dtype=np.uint64), cols
                )
                mask = np.zeros(width, dtype=bool)
                mask[cols] = True
                bits[name, row] = mask
                exists |= mask
        none = np.zeros(width, dtype=bool)
        return TPUBackend(holder), lambda name, row: bits.get((name, row), none), exists

    def _cases(self, verb, row_bits, exists):
        """[(pql, numpy count)] for a verb over the edge rows."""
        if verb == "Not":
            cases = [
                (f"Not(Row(f={r}))", exists & ~row_bits("f", r))
                for r in (0, 7, 8, 1000)
            ]
            cases.append((
                "Not(Union(Row(f=8), Row(g=0)))", exists & ~row_bits("g", 0)
            ))
        else:
            cases = [
                (
                    f"{verb}(Row(f={a}), Row(g={b}), Row(h={c}))",
                    self.VERBS[verb](
                        row_bits("f", a), row_bits("g", b), row_bits("h", c)
                    ),
                )
                for a, b, c in self.TRIPLES
            ]
        return [(q, int(m.sum())) for q, m in cases]

    @pytest.mark.parametrize("batched", [False, True], ids=["count", "count_batch"])
    @pytest.mark.parametrize(
        "verb", ["Intersect", "Union", "Difference", "Xor", "Not"]
    )
    def test_counts_equal_numpy(self, holder, rng, verb, batched):
        from pilosa_tpu.pql import parse_string

        be, row_bits, exists = self._setup(holder, rng)
        cases = self._cases(verb, row_bits, exists)
        calls = [parse_string(q).calls[0] for q, _ in cases]
        shards = list(range(self.N_SHARDS))
        assert be._pair_batch_plan("i", calls) is None  # the scan path
        if batched:
            launches = global_stats._counters.get(
                ("device_launches_total", ("kind:count_batch",)), 0
            )
            got = be.count_batch("i", calls, shards)
            assert global_stats._counters[
                ("device_launches_total", ("kind:count_batch",))
            ] > launches
        else:
            got = [be.count_shards("i", c, shards) for c in calls]
        assert got == [n for _, n in cases], [q for q, _ in cases]

    @pytest.mark.parametrize("verb", ["I", "U", "D", "X"])
    def test_masked_leaf_and_padded_slot(self, holder, rng, verb):
        """The program itself, below the batcher's routing: slot 1's h
        leaf is masked (row id past the packed rows, clamped to the last
        packed row as _build_row clamps it) and slots 2 and 3 are padding
        that replays slot 0 with the lane mask off."""
        be, row_bits, _ = self._setup(holder, rng)
        shards_t = tuple(range(self.N_SHARDS))
        blocks = tuple(
            be._get_block("i", be._field("i", n), shards_t)[0] for n in "fgh"
        )
        assert blocks[2].shape[1] == 8  # h: 4 rows packed into 8
        u32 = lambda *xs: np.array(xs, dtype=np.uint32)  # noqa: E731
        scalars = (
            u32(7, 0, 7, 7), u32(1, 1, 1, 1),  # f rows, masks
            u32(7, 3, 7, 7), u32(1, 1, 1, 1),  # g
            u32(3, 7, 3, 3), u32(1, 0, 1, 1),  # h: slot 1 masked
            u32(1, 1, 0, 0),                   # lane mask
        )
        spec = (verb, (("R", "f"), ("R", "g"), ("R", "h")))
        out = np.asarray(be._program("count_batch", spec, True)(blocks, scalars))
        fn = self.VERBS[
            {"I": "Intersect", "U": "Union", "D": "Difference", "X": "Xor"}[verb]
        ]
        none = np.zeros_like(row_bits("f", 0))
        want = [
            int(fn(row_bits("f", 7), row_bits("g", 7), row_bits("h", 3)).sum()),
            int(fn(row_bits("f", 0), row_bits("g", 3), none).sum()),
            0, 0,
        ]
        assert out.tolist() == want


class TestCountBatch:
    def test_count_batch_matches_singles(self, holder, rng):
        idx = holder.create_index("i")
        idx.create_field("f")
        idx.create_field("g")
        for row in [1, 2, 3]:
            cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 3000, dtype=np.uint64))
            idx.field("f").import_bits(np.full(cols.size, row, dtype=np.uint64), cols)
        cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 3000, dtype=np.uint64))
        idx.field("g").import_bits(np.full(cols.size, 9, dtype=np.uint64), cols)
        be = TPUBackend(holder)
        from pilosa_tpu.pql import parse_string

        calls = [
            parse_string(f"Intersect(Row(f={r}), Row(g=9))").calls[0] for r in [1, 2, 3, 7]
        ]
        shards = [0, 1]
        batch = be.count_batch("i", calls, shards)
        singles = [be.count_shards("i", c, shards) for c in calls]
        assert batch == singles
        assert batch[3] == 0  # nonexistent row counts zero

    def _setup(self, holder, rng):
        idx = holder.create_index("i")
        idx.create_field("f")
        idx.create_field("g")
        idx.create_field("v", options_for_int(-100, 100))
        for row in [1, 2, 3]:
            cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 3000, dtype=np.uint64))
            idx.field("f").import_bits(np.full(cols.size, row, dtype=np.uint64), cols)
        cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 3000, dtype=np.uint64))
        idx.field("g").import_bits(np.full(cols.size, 9, dtype=np.uint64), cols)
        cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 500, dtype=np.uint64))
        idx.field("v").import_value(cols, rng.integers(-100, 101, cols.size))
        return idx

    def test_mixed_verbs_pair_path(self, holder, rng):
        """All four verbs + single rows over one field pair derive from
        one pair_stats sweep; results must match per-query execution."""
        self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string

        be = TPUBackend(holder)
        qs = [
            "Intersect(Row(f=1), Row(g=9))",
            "Union(Row(f=2), Row(g=9))",
            "Difference(Row(f=3), Row(g=9))",
            "Xor(Row(f=1), Row(g=9))",
            "Row(f=2)",
            "Row(g=9)",
            "Union(Row(f=99), Row(g=9))",  # missing row -> just |g|
        ]
        calls = [parse_string(q).calls[0] for q in qs]
        shards = [0, 1]
        assert be._pair_batch_plan("i", calls) is not None
        batch = be.count_batch("i", calls, shards)
        singles = [be.count_shards("i", c, shards) for c in calls]
        assert batch == singles

    def test_generic_path_groups_specs(self, holder, rng):
        """Non-pair-able batches (BSI, Not) group by spec shape and still
        match per-query execution."""
        self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string

        be = TPUBackend(holder)
        qs = [
            "Row(v > 10)",
            "Row(v > -5)",
            "Not(Row(f=1))",
            "Intersect(Row(f=1), Row(v > 0))",
        ]
        calls = [parse_string(q).calls[0] for q in qs]
        assert be._pair_batch_plan("i", calls) is None
        shards = [0, 1]
        batch = be.count_batch("i", calls, shards)
        singles = [be.count_shards("i", c, shards) for c in calls]
        assert batch == singles

    def test_multi_count_query_through_executor(self, holder, rng):
        """A multi-Count PQL request is served by one batched dispatch and
        matches the CPU oracle call-for-call (the serving-batch surface)."""
        self._setup(holder, rng)
        q = (
            "Count(Intersect(Row(f=1), Row(g=9)))"
            "Count(Union(Row(f=2), Row(g=9)))"
            "Count(Row(f=3))"
            "Count(Xor(Row(f=1), Row(g=9)))"
        )
        want = Executor(holder).execute("i", q)
        got = Executor(holder, backend=TPUBackend(holder)).execute("i", q)
        assert got == want

    def test_bitmap_call_shard_subset(self, holder, rng):
        """Whole-query bitmap materialization honors shard subsets."""
        self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string

        be = TPUBackend(holder)
        cpu = Executor(holder).backend
        c = parse_string("Union(Row(f=1), Row(g=9))").calls[0]
        for shards in ([0], [1], [0, 1]):
            got = be.bitmap_call("i", c, shards)
            want_cols = []
            for s in shards:
                want_cols.extend(cpu.bitmap_call_shard("i", c, s).columns().tolist())
            np.testing.assert_array_equal(got.columns(), np.array(sorted(want_cols), dtype=np.uint64))

    def test_count_batch_async_pipelines(self, holder, rng):
        """Multiple batches in flight resolve to correct results."""
        self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string

        be = TPUBackend(holder)
        shards = [0, 1]
        pending = []
        for r in [1, 2, 3]:
            calls = [parse_string(f"Intersect(Row(f={r}), Row(g=9))").calls[0]]
            pending.append((r, be.count_batch_async("i", calls, shards)))
        for r, resolve in pending:
            c = parse_string(f"Intersect(Row(f={r}), Row(g=9))").calls[0]
            assert resolve() == [be.count_shards("i", c, shards)]

    def test_pair_cache_hit_and_write_invalidation(self, holder, rng):
        """Repeat batches serve from the host stats cache; a write to
        either field invalidates it (block identity = write epoch)."""
        idx = self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string

        be = TPUBackend(holder)
        calls = [parse_string("Intersect(Row(f=1), Row(g=9))").calls[0]]
        shards = [0, 1]
        first = be.count_batch("i", calls, shards)
        assert len(be._pair_cache) == 1
        assert be.count_batch("i", calls, shards) == first
        # Set a column that's in g=9 but not f=1: intersect count +1.
        g_cols = set(Executor(holder).backend.bitmap_call_shard("i", parse_string("Row(g=9)").calls[0], 0).columns().tolist())
        f_cols = set(Executor(holder).backend.bitmap_call_shard("i", parse_string("Row(f=1)").calls[0], 0).columns().tolist())
        col = next(iter(g_cols - f_cols))
        idx.field("f").set_bit(1, col)
        assert be.count_batch("i", calls, shards) == [first[0] + 1]

    def test_count_batch_zero_scalar_group(self, holder, rng):
        """Calls with no traced scalars (All()) cannot scan over a query
        axis — they group into one shared program and fan out (found by
        the randomized churn differential)."""
        idx = self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string

        ef = idx.existence_field()
        cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 500, dtype=np.uint64))
        ef.import_bits(np.zeros(cols.size, dtype=np.uint64), cols)
        be = TPUBackend(holder)
        calls = [parse_string(q).calls[0]
                 for q in ("All()", "All()", "Not(Row(f=1))")]
        shards = [0, 1]
        got = be.count_batch("i", calls, shards)
        want = [be.count_shards("i", c, shards) for c in calls]
        assert got == want
        assert got[0] == got[1] == cols.size

    def test_host_slab_stats_match_pershard_kernel(self, rng):
        """The host-update helper must agree bit-for-bit with the device
        per-shard kernel — a host-refreshed table row sits next to
        device-swept rows."""
        from pilosa_tpu.exec.tpu import _host_slab_pair_flat
        from pilosa_tpu.ops.kernels import pair_stats_pershard

        S, RF, RG, W = 3, 8, 4, 512
        f = rng.integers(0, 2**32, (S, RF, W), dtype=np.uint32)
        g = rng.integers(0, 2**32, (S, RG, W), dtype=np.uint32)
        pair, cf, cg = (
            np.asarray(x)
            for x in pair_stats_pershard(_tiled(f), _tiled(g), interpret=True)
        )
        for i in range(S):
            np.testing.assert_array_equal(
                np.concatenate([pair[i].ravel(), cf[i, 0], cg[i, 0]]),
                _host_slab_pair_flat(f[i], g[i]),
            )

    def _pair_counters(self):
        from pilosa_tpu.utils.stats import global_stats

        c = global_stats._counters
        return (
            c[("pair_stats_sweeps_total", ())],
            c[("pair_stats_incremental_updates_total", ())],
        )

    def test_pair_incremental_host_update(self, holder, rng):
        """Write epochs are absorbed by the host per-shard table: after
        the one cold sweep, mutations cost zero device sweeps and every
        epoch's batch stays oracle-exact (the write-churn serving path,
        VERDICT r3 #1)."""
        idx = self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string

        be = TPUBackend(holder)
        queries = [
            "Intersect(Row(f=1), Row(g=9))",
            "Union(Row(f=2), Row(g=9))",
            "Difference(Row(f=3), Row(g=9))",
            "Xor(Row(f=1), Row(g=9))",
            "Row(f=2)",
        ]
        calls = [parse_string(q).calls[0] for q in queries]
        shards = [0, 1]
        be.count_batch("i", calls, shards)
        s0, u0 = self._pair_counters()
        cpu = Executor(holder)
        wcol = 11  # fresh columns: every Set is a real mutation
        set_cols = []
        for epoch in range(4):
            for _ in range(3):
                fname = ("f", "g")[int(rng.integers(0, 2))]
                row = int(rng.integers(1, 4)) if fname == "f" else 9
                if set_cols and rng.integers(0, 3) == 0:
                    f2, r2, c2 = set_cols.pop()
                    idx.field(f2).clear_bit(r2, c2)
                else:
                    wcol += 97
                    idx.field(fname).set_bit(row, wcol % (2 * SHARD_WIDTH))
                    set_cols.append((fname, row, wcol % (2 * SHARD_WIDTH)))
            got = be.count_batch("i", calls, shards)
            want = [cpu.execute("i", f"Count({q})")[0] for q in queries]
            assert got == want, (epoch, got, want)
            s1, u1 = self._pair_counters()
            assert s1 == s0, "write epoch must not re-sweep on device"
            assert u1 == u0 + epoch + 1
        # Repeat without writes: plain identity hit, no update, no sweep.
        assert be.count_batch("i", calls, shards) == want
        assert self._pair_counters() == (s0, u0 + 4)

    def test_pair_incremental_same_field_pair(self, holder, rng):
        """Singles-only batches plan as the (f, f) self-pair; the host
        update must handle fb == fa (one slab, both sides)."""
        idx = self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string

        be = TPUBackend(holder)
        calls = [parse_string(f"Row(f={r})").calls[0] for r in (1, 2, 3)]
        shards = [0, 1]
        be.count_batch("i", calls, shards)
        s0, u0 = self._pair_counters()
        idx.field("f").set_bit(2, 123457)
        cpu = Executor(holder)
        got = be.count_batch("i", calls, shards)
        want = [cpu.execute("i", f"Count(Row(f={r}))")[0] for r in (1, 2, 3)]
        assert got == want
        assert self._pair_counters() == (s0, u0 + 1)

    def test_pair_incremental_threshold_falls_back_to_sweep(self, holder, rng):
        """Epochs whose slab-tier shard count exceeds the cutoff re-sweep
        instead of paying per-shard host work. A bulk import is not
        delta-coverable (no bit-op ring entries), so with the gate shut
        it must go back to the device."""
        idx = self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string

        be = TPUBackend(holder)
        be.MAX_PAIR_HOST_UPDATE_SHARDS = 0  # force the slab gate shut
        calls = [parse_string("Intersect(Row(f=1), Row(g=9))").calls[0]]
        shards = [0, 1]
        first = be.count_batch("i", calls, shards)
        s0, u0 = self._pair_counters()
        g_cols = set(Executor(holder).backend.bitmap_call_shard(
            "i", parse_string("Row(g=9)").calls[0], 0).columns().tolist())
        f_cols = set(Executor(holder).backend.bitmap_call_shard(
            "i", parse_string("Row(f=1)").calls[0], 0).columns().tolist())
        col = next(iter(g_cols - f_cols))
        idx.field("f").import_bits(
            np.array([1], dtype=np.uint64), np.array([col], dtype=np.uint64)
        )
        assert be.count_batch("i", calls, shards) == [first[0] + 1]
        s1, u1 = self._pair_counters()
        assert (s1, u1) == (s0 + 1, u0)

    def test_pair_delta_tier_applies_point_writes(self, holder, rng):
        """Point writes are absorbed by the delta tier (bit-op ring ->
        cf/pair adjustments), not slab recompute: the delta-op counter
        moves and results stay oracle-exact, including clears and writes
        to the 'other' field of the pair."""
        idx = self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string
        from pilosa_tpu.utils.stats import global_stats

        be = TPUBackend(holder)
        queries = [
            "Intersect(Row(f=1), Row(g=9))",
            "Union(Row(f=2), Row(g=9))",
            "Xor(Row(f=3), Row(g=9))",
        ]
        calls = [parse_string(q).calls[0] for q in queries]
        shards = [0, 1]
        be.count_batch("i", calls, shards)
        cpu = Executor(holder)

        def dops():
            return global_stats._counters[("pair_stats_delta_ops_total", ())]

        d0 = dops()
        n_ops = 0
        for k in range(6):
            fname = ("f", "g")[k % 2]
            row = (1 + k % 3) if fname == "f" else 9
            col = 777_000 + k
            idx.field(fname).set_bit(row, col)
            n_ops += 1
            if k == 3:  # a clear in the middle of the stream
                idx.field(fname).clear_bit(row, col)
                n_ops += 1
            got = be.count_batch("i", calls, shards)
            want = [cpu.execute("i", f"Count({q})")[0] for q in queries]
            assert got == want, (k, got, want)
        assert dops() == d0 + n_ops

    def test_topn_incremental_host_update(self, holder, rng):
        """TopN's rank vector absorbs write epochs via the per-shard
        row-count table — no re-dispatch for a small epoch, results stay
        oracle-exact (including Rows(), which serves from it)."""
        idx = self._setup(holder, rng)
        from pilosa_tpu.utils.stats import global_stats

        be = TPUBackend(holder)
        ex_cpu = Executor(holder)
        ex_tpu = Executor(holder, backend=be)
        q = "TopN(f, n=0)"
        assert ex_tpu.execute("i", q) == ex_cpu.execute("i", q)

        def upds():
            return global_stats._counters[("topn_incremental_updates_total", ())]

        u0 = upds()
        wcol = 5
        for epoch in range(3):
            wcol += 131071
            idx.field("f").set_bit(int(rng.integers(1, 4)), wcol % (2 * SHARD_WIDTH))
            assert ex_tpu.execute("i", q) == ex_cpu.execute("i", q)
            assert ex_tpu.execute("i", "Rows(f)") == ex_cpu.execute("i", "Rows(f)")
            assert upds() == u0 + epoch + 1

    def test_pair_pershard_size_gate(self, holder, rng):
        """Over the per-shard-table byte gate the sweep returns summed
        totals (no resident table) and write epochs re-sweep — correct,
        just without the incremental path."""
        idx = self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string

        be = TPUBackend(holder)
        be.MAX_PAIR_PERSHARD_BYTES = 0
        calls = [parse_string("Intersect(Row(f=1), Row(g=9))").calls[0]]
        shards = [0, 1]
        first = be.count_batch("i", calls, shards)
        assert be._pair_cache[("i", "f", "g")].pershard is None
        s0, u0 = self._pair_counters()
        idx.field("f").set_bit(1, 3)
        want = Executor(holder).execute("i", "Count(Intersect(Row(f=1), Row(g=9)))")
        assert be.count_batch("i", calls, shards) == want
        assert self._pair_counters() == (s0 + 1, u0)
        assert first is not None

    def test_pair_cache_concurrent_readers_and_writers(self, holder, rng):
        """The freshness protocol under real thread interleaving: batch
        readers race bit writers; every observed count must correspond
        to SOME prefix of the writes (never above the final state, never
        below the initial — staleness is allowed, corruption is not; the
        store rule is last-writer-wins, so per-reader monotonicity is
        NOT promised), and after writers finish the caches converge to
        oracle-exact."""
        import threading

        idx = self._setup(holder, rng)
        from pilosa_tpu.pql import parse_string

        be = TPUBackend(holder)
        calls = [parse_string("Intersect(Row(f=1), Row(g=9))").calls[0]]
        shards = [0, 1]
        initial = be.count_batch("i", calls, shards)[0]
        cpu = Executor(holder)
        g_cols = set(cpu.backend.bitmap_call_shard(
            "i", parse_string("Row(g=9)").calls[0], 0).columns().tolist())
        f_cols = set(cpu.backend.bitmap_call_shard(
            "i", parse_string("Row(f=1)").calls[0], 0).columns().tolist())
        # 24 columns in g=9 but not f=1: each Set(f=1) adds exactly +1.
        to_set = sorted(g_cols - f_cols)[:24]
        errors: list = []
        stop = threading.Event()

        def writer():
            for col in to_set:
                idx.field("f").set_bit(1, col)
            stop.set()

        def reader():
            while not stop.is_set():
                got = be.count_batch("i", calls, shards)[0]
                if not (initial <= got <= initial + len(to_set)):
                    errors.append(("count out of range", initial, got))
                    return

        threads = [threading.Thread(target=reader) for _ in range(3)]
        wt = threading.Thread(target=writer)
        for t in threads:
            t.start()
        wt.start()
        wt.join()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors[:3]
        want = cpu.execute("i", "Count(Intersect(Row(f=1), Row(g=9)))")
        assert be.count_batch("i", calls, shards) == want
        assert want[0] == initial + len(to_set)

    def test_topn_refresh_on_out_of_scope_write(self, holder, rng):
        """Writes to shards OUTSIDE the queried set bump the view
        generation but must not degrade TopN to a dispatch per query —
        the entry re-keys with unchanged counts."""
        idx = self._setup(holder, rng)
        from pilosa_tpu.utils.stats import global_stats

        be = TPUBackend(holder)
        want = be.topn_field("i", "f", [0], 0)
        disp0 = global_stats._counters[("topn_cache_hits_total", ())]
        for k in range(3):
            # Shard 5 is far outside the queried set [0].
            idx.field("f").set_bit(1, 5 * SHARD_WIDTH + k)
            assert be.topn_field("i", "f", [0], 0) == want
        # Second query after each write serves as a plain generation hit.
        assert be.topn_field("i", "f", [0], 0) == want
        assert global_stats._counters[("topn_cache_hits_total", ())] == disp0 + 1


class TestGroupByFromTables:
    """Unfiltered 1-/2-field GroupBy serves from the incrementally-
    maintained TopN/pair tables: exact under point-write churn with no
    device sweeps after the first."""

    def test_groupby_2field_under_churn(self, holder, rng):
        idx = holder.create_index("i")
        for fname, nrows in (("a", 3), ("b", 2)):
            idx.create_field(fname)
            for row in range(1, nrows + 1):
                cols = np.unique(
                    rng.integers(0, 2 * SHARD_WIDTH, 1500, dtype=np.uint64)
                )
                idx.field(fname).import_bits(
                    np.full(cols.size, row, dtype=np.uint64), cols
                )
        from pilosa_tpu.utils.stats import global_stats

        ex_cpu = Executor(holder)
        be = TPUBackend(holder)
        ex_tpu = Executor(holder, backend=be)
        for q in ("GroupBy(Rows(a))", "GroupBy(Rows(a), Rows(b))"):
            assert ex_tpu.execute("i", q) == ex_cpu.execute("i", q)
        s0 = global_stats._counters[("pair_stats_sweeps_total", ())]
        for k in range(4):
            idx.field("a").set_bit(1 + k % 3, 333_000 + k)
            for q in ("GroupBy(Rows(a))", "GroupBy(Rows(a), Rows(b))",
                      "GroupBy(Rows(a), Rows(b), limit=2)"):
                assert ex_tpu.execute("i", q) == ex_cpu.execute("i", q), (k, q)
        assert global_stats._counters[("pair_stats_sweeps_total", ())] == s0


class TestGroupByDevice:
    """Device GroupBy = whole-query group-count tensor (VERDICT r2 #4);
    every shape must match the host iterator call-for-call."""

    def _setup(self, holder, rng):
        idx = holder.create_index("i")
        for fname, nrows in (("a", 3), ("b", 2), ("c", 2), ("d", 2)):
            idx.create_field(fname)
            for row in range(1, nrows + 1):
                cols = np.unique(
                    rng.integers(0, 2 * SHARD_WIDTH, 1500, dtype=np.uint64)
                )
                idx.field(fname).import_bits(
                    np.full(cols.size, row, dtype=np.uint64), cols
                )
        return idx

    QUERIES = [
        "GroupBy(Rows(a))",
        "GroupBy(Rows(a), Rows(b))",
        "GroupBy(Rows(a), Rows(b), Rows(c))",
        "GroupBy(Rows(a), Rows(b), Rows(c), filter=Row(a=1))",
        "GroupBy(Rows(a), Rows(b), filter=Row(c=1))",
        "GroupBy(Rows(a), filter=Row(b=2))",
        "GroupBy(Rows(a), Rows(b), limit=3)",
        "GroupBy(Rows(a), Rows(b), limit=2, offset=1)",
        "GroupBy(Rows(a, limit=2), Rows(b))",
        "GroupBy(Rows(a, previous=1), Rows(b))",
        # 4-field shapes: the N-field odometer kernel (VERDICT r3 #4
        # removed the 3-field cliff).
        "GroupBy(Rows(a), Rows(b), Rows(c), Rows(d))",
        "GroupBy(Rows(a), Rows(b), Rows(c), Rows(d), filter=Row(a=2))",
        "GroupBy(Rows(a), Rows(b), Rows(c), Rows(d), limit=5, offset=2)",
        "GroupBy(Rows(a), Rows(b), Rows(c, limit=1), Rows(d))",
    ]

    def test_differential_vs_host(self, holder, rng):
        self._setup(holder, rng)
        host = Executor(holder)
        dev = Executor(holder, backend=TPUBackend(holder))
        for q in self.QUERIES:
            want = host.execute("i", q)
            got = dev.execute("i", q)
            assert got == want, q

    def test_device_path_taken(self, holder, rng):
        """The fast path actually runs (returns non-None) for the plain
        2-child case."""
        self._setup(holder, rng)
        be = TPUBackend(holder)
        from pilosa_tpu.pql import parse_string

        c = parse_string("GroupBy(Rows(a), Rows(b))").calls[0]
        out = be.group_by("i", c, None, [None, None], [0, 1])
        assert out is not None and len(out) > 0

    def test_write_invalidation(self, holder, rng):
        """GroupBy counts must reflect writes (stack cache freshness)."""
        idx = self._setup(holder, rng)
        dev = Executor(holder, backend=TPUBackend(holder))
        before = dev.execute("i", "GroupBy(Rows(a), Rows(b))")[0]
        # New column in both a=1 and b=1: that group's count +1.
        col = 3 * SHARD_WIDTH - 5
        idx.field("a").set_bit(1, col)
        idx.field("b").set_bit(1, col)
        after = dev.execute("i", "GroupBy(Rows(a), Rows(b))")[0]
        want = Executor(holder).execute("i", "GroupBy(Rows(a), Rows(b))")[0]
        assert after == want
        assert after != before


class TestAggCache:
    """Unfiltered Sum/Min/Max results cache against the BSI view's write
    epoch and must invalidate on writes."""

    def test_hit_and_invalidation(self, holder, rng):
        idx = holder.create_index("i")
        idx.create_field("v", options_for_int(-100, 100))
        cols = np.unique(rng.integers(0, SHARD_WIDTH, 400, dtype=np.uint64))
        vals = rng.integers(-100, 101, cols.size)
        idx.field("v").import_value(cols, vals)
        be = TPUBackend(holder)
        first = be.bsi_sum("i", "v", [0])
        assert first is not None
        assert be.bsi_sum("i", "v", [0]) == first  # cache hit
        assert len(be._agg_cache) == 1
        mn, mx = be.bsi_min("i", "v", [0]), be.bsi_max("i", "v", [0])
        # Oracle agreement.
        want_sum = Executor(holder).execute("i", "Sum(field=v)")[0]
        assert first == (want_sum.val, want_sum.count)
        # A new value invalidates: sum/min/max all change deterministically.
        free_col = int(cols.max()) + 1
        idx.field("v").set_value(free_col, -100)
        after = be.bsi_sum("i", "v", [0])
        assert after == (first[0] - 100, first[1] + 1)
        assert be.bsi_min("i", "v", [0])[0] == -100
        want_max = Executor(holder).execute("i", "Max(field=v)")[0]
        assert be.bsi_max("i", "v", [0]) == (want_max.val, want_max.count)
        assert (mn, mx) != (None, None)

    def test_sum_value_delta_tier(self, holder, rng):
        """Point value writes (set/clear/overwrite, any sign) update the
        cached Sum as exact host deltas — no plane re-sweep; bulk
        import_value is not delta-coverable and re-dispatches."""
        from pilosa_tpu.utils.stats import global_stats

        idx = holder.create_index("i")
        idx.create_field("v", options_for_int(-100, 100))
        cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 500, dtype=np.uint64))
        idx.field("v").import_value(cols, rng.integers(-100, 101, cols.size))
        be = TPUBackend(holder)
        ex_cpu = Executor(holder)
        shards = [0, 1]
        assert be.bsi_sum("i", "v", shards) is not None

        def upds():
            return global_stats._counters[("sum_incremental_updates_total", ())]

        u0 = upds()
        taken = set(cols.tolist())
        free = next(c for c in range(SHARD_WIDTH) if c not in taken)
        free1 = next(
            c for c in range(SHARD_WIDTH, 2 * SHARD_WIDTH)
            if c not in taken and c != free
        )
        ops = [
            ("set", free, 37),        # new column
            ("set", free, -14),       # overwrite, sign flip
            ("set", int(cols[0]), 9),  # overwrite existing
            ("clear", free, None),    # removal
            ("set", free1, 50),       # the other queried shard
        ]
        for k, (verb, col, val) in enumerate(ops):
            f = idx.field("v")
            if verb == "set":
                f.set_value(col, val)
            else:
                frag = f.view(f"bsig_v").fragment(col // SHARD_WIDTH)
                frag.clear_value(col, f.bsi_group().bit_depth)
            got = be.bsi_sum("i", "v", shards)
            want = ex_cpu.execute("i", "Sum(field=v)")[0]
            assert got == (want.val, want.count), (k, got, want)
            assert upds() == u0 + k + 1
        # Bulk path: not coverable, must re-dispatch yet stay exact.
        more = np.array([free + 5, free + 6], dtype=np.uint64)
        idx.field("v").import_value(more, np.array([1, 2]))
        got = be.bsi_sum("i", "v", shards)
        want = ex_cpu.execute("i", "Sum(field=v)")[0]
        assert got == (want.val, want.count)
        assert upds() == u0 + len(ops)


class TestRowPaging:
    """HBM row paging (VERDICT r2 #8): a field too tall for the byte
    budget still answers Row/Count/TopN on device via on-demand row
    fetches and streaming page sweeps — not the CPU oracle."""

    def _tall_field(self, holder, rng, n_rows=2000):
        idx = holder.create_index("i")
        idx.create_field("tall")
        rows = np.arange(n_rows, dtype=np.uint64).repeat(3)
        cols = rng.integers(0, SHARD_WIDTH, rows.size, dtype=np.uint64)
        idx.field("tall").import_bits(rows, cols)
        return idx

    def test_row_query_pages_single_row(self, holder, rng):
        idx = self._tall_field(holder, rng)
        be = TPUBackend(holder, max_bytes=16 << 20)
        # The full stack (2000 rows x 128 KiB) exceeds the 16 MiB budget.
        assert be.blocks.get("i", idx.field("tall"), (0,))[0] is None
        from pilosa_tpu.pql import parse_string

        for rid in (0, 1500, 1999, 5000):
            c = parse_string(f"Count(Row(tall={rid}))").calls[0].children[0]
            want = Executor(holder).backend.count_shard("i", c, 0)
            assert be.count_shards("i", c, [0]) == want, rid
        # Combinations of paged rows lower too.
        c = parse_string("Union(Row(tall=3), Row(tall=1500))").calls[0]
        want = Executor(holder).backend.count_shard("i", c, 0)
        assert be.count_shards("i", c, [0]) == want

    def test_topn_paged_matches_oracle(self, holder, rng):
        self._tall_field(holder, rng)
        from pilosa_tpu.utils.stats import global_stats

        be = TPUBackend(holder, max_bytes=16 << 20)
        host = Executor(holder)
        dev = Executor(holder, backend=be)
        def uploads() -> float:
            for line in global_stats.prometheus_text().splitlines():
                if line.startswith("pilosa_hbm_page_uploads_total"):
                    return float(line.split()[1])
            return 0.0

        before = uploads()
        want = [result_to_json(r) for r in host.execute("i", "TopN(tall, n=10)")]
        got = [result_to_json(r) for r in dev.execute("i", "TopN(tall, n=10)")]
        assert got == want
        # Page traffic from THIS query is observable on /metrics.
        assert uploads() > before
        assert "hbm_page_bytes_total" in global_stats.prometheus_text()


class TestPreheat:
    def test_preheat_makes_stacks_resident_and_queries_hit(self, holder, rng):
        idx = holder.create_index("i")
        idx.create_field("f")
        idx.create_field("v", options_for_int(-100, 100))
        cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 2000, dtype=np.uint64))
        idx.field("f").import_bits(np.full(cols.size, 1, dtype=np.uint64), cols)
        vcols = np.unique(rng.integers(0, SHARD_WIDTH, 300, dtype=np.uint64))
        idx.field("v").import_value(vcols, rng.integers(-100, 101, vcols.size))
        be = TPUBackend(holder)
        n = be.preheat()
        assert n >= 2  # f standard + v bsig (at full plane height)
        resident_before = be.blocks.resident_bytes()
        # Queries must reuse the preheated stacks (no repack/replace).
        from pilosa_tpu.pql import parse_string

        # Index-union shard lists — what the executor passes; v only has
        # data in shard 0 but must still be keyed by the union, or the
        # first query would repack and REPLACE the preheated stack.
        c = parse_string("Row(f=1)").calls[0]
        assert be.count_shards("i", c, [0, 1]) == cols.size
        assert be.bsi_sum("i", "v", [0, 1]) is not None
        assert be.blocks.resident_bytes() == resident_before


class TestCountBatcher:
    """exec/batcher.py: cross-request coalescing (VERDICT r2 #2)."""

    def _setup(self, holder, rng):
        idx = holder.create_index("i")
        idx.create_field("f")
        idx.create_field("g")
        for row in [1, 2]:
            cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 2000, dtype=np.uint64))
            idx.field("f").import_bits(np.full(cols.size, row, dtype=np.uint64), cols)
        cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 2000, dtype=np.uint64))
        idx.field("g").import_bits(np.full(cols.size, 9, dtype=np.uint64), cols)

    def test_concurrent_submissions_coalesce(self, holder, rng):
        import threading

        from pilosa_tpu.exec.batcher import CountBatcher
        from pilosa_tpu.pql import parse_string

        self._setup(holder, rng)
        be = TPUBackend(holder)
        batcher = CountBatcher(be, window=0.15)
        shards = [0, 1]
        queries = [f"Intersect(Row(f={r}), Row(g=9))" for r in (1, 2)] + ["Row(f=1)"]
        want = [
            be.count_shards("i", parse_string(q).calls[0], shards) for q in queries
        ]
        got = [None] * len(queries)
        errs = []

        def worker(k):
            try:
                got[k] = batcher.count(
                    "i", [parse_string(queries[k]).calls[0]], shards
                )[0]
            except BaseException as e:  # pragma: no cover
                errs.append(e)

        threads = [
            __import__("threading").Thread(target=worker, args=(k,))
            for k in range(len(queries))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert got == want

    def test_error_isolation(self, holder, rng):
        """A bad query in the window errors only its own submitter."""
        import threading

        from pilosa_tpu.exec.batcher import CountBatcher
        from pilosa_tpu.exec.cpu import QueryError
        from pilosa_tpu.pql import parse_string

        self._setup(holder, rng)
        be = TPUBackend(holder)
        batcher = CountBatcher(be, window=0.15)
        shards = [0, 1]
        good_call = parse_string("Row(f=1)").calls[0]
        bad_call = parse_string("Row(nope=1)").calls[0]
        want = be.count_shards("i", good_call, shards)
        results = {}

        def run(name, call):
            try:
                results[name] = batcher.count("i", [call], shards)[0]
            except QueryError as e:
                results[name] = e

        t1 = threading.Thread(target=run, args=("good", good_call))
        t2 = threading.Thread(target=run, args=("bad", bad_call))
        t1.start(), t2.start()
        t1.join(), t2.join()
        assert results["good"] == want
        assert isinstance(results["bad"], QueryError)

    def test_executor_rides_batcher(self, holder, rng):
        """Executor with a batcher returns oracle-identical results, even
        for a single-Count query."""
        from pilosa_tpu.exec.batcher import CountBatcher

        self._setup(holder, rng)
        be = TPUBackend(holder)
        ex = Executor(holder, backend=be)
        ex.batcher = CountBatcher(be, window=0.0)
        for q in (
            "Count(Intersect(Row(f=1), Row(g=9)))",
            "Count(Row(f=2))Count(Union(Row(f=1), Row(g=9)))",
        ):
            assert ex.execute("i", q) == Executor(holder).execute("i", q)


class TestTriStatsKernel:
    def test_tri_matches_premasked_pairs(self, rng):
        """group_tile_stats' slot k must equal
        pair_stats(F & H_k [& filt], G)."""
        from pilosa_tpu.ops.kernels import group_tile_stats, pair_stats

        S, RF, RG, RH, W = 3, 8, 8, 4, 512
        f = rng.integers(0, 1 << 32, (S, RF, W), dtype=np.uint32)
        g = rng.integers(0, 1 << 32, (S, RG, W), dtype=np.uint32)
        h = rng.integers(0, 1 << 32, (S, RH, W), dtype=np.uint32)
        filt = rng.integers(0, 1 << 32, (S, W), dtype=np.uint32)
        tf, tg, th = _tiled(f), _tiled(g), _tiled(h)
        rows_idx = np.arange(RH, dtype=np.int32)[:, None]
        active = np.ones(RH, dtype=np.uint32)
        tri = np.asarray(group_tile_stats(tf, tg, (th,), rows_idx, active))
        tri_f = np.asarray(
            group_tile_stats(tf, tg, (th,), rows_idx, active, _tiled(filt))
        )
        for k in range(RH):
            m = h[:, k, :]
            want = np.asarray(
                pair_stats(_tiled(f & m[:, None, :]), tg, interpret=True)[0]
            )
            np.testing.assert_array_equal(tri[k], want)
            want_f = np.asarray(
                pair_stats(
                    _tiled(f & (m & filt)[:, None, :]), tg, interpret=True
                )[0]
            )
            np.testing.assert_array_equal(tri_f[k], want_f)


class TestIncrementalStackUpdate:
    """VERDICT r3 #1: a write touching one shard must refresh the
    resident stack by splicing that shard's slab, not repacking the
    whole stack."""

    def _build(self, holder, rng, n_shards=4):
        idx = holder.create_index("i")
        f = idx.create_field("f")
        for shard in range(n_shards):
            base = shard * SHARD_WIDTH
            cols = np.unique(
                rng.integers(0, SHARD_WIDTH, 3000, dtype=np.uint64)
            ) + base
            f.import_bits(np.full(cols.size, 1, dtype=np.uint64), cols)
        return idx

    def test_single_shard_write_is_incremental_and_correct(self, holder, rng):
        from pilosa_tpu.pql import parse_string
        from pilosa_tpu.utils.stats import global_stats

        idx = self._build(holder, rng, n_shards=16)
        be = TPUBackend(holder)
        shards = list(range(16))
        call = parse_string("Count(Row(f=1))").calls[0].children[0]
        before_total = be.count_shards("i", call, shards)
        old_arr = be.blocks._entries[("i", "f", "standard")][1]

        def updates():
            return global_stats._counters.get(
                ("stack_incremental_updates_total", ()), 0
            )

        n0 = updates()
        # One write in shard 3 (a fresh column: count must grow by 1).
        idx.field("f").set_bit(1, 3 * SHARD_WIDTH + 777_777)
        after_total = be.count_shards("i", call, shards)
        assert after_total == before_total + 1
        assert updates() == n0 + 1
        new_arr = be.blocks._entries[("i", "f", "standard")][1]
        # New array object: identity-keyed caches see a fresh epoch.
        assert new_arr is not old_arr
        # And a repeat query is a pure fingerprint hit (no new update).
        assert be.count_shards("i", call, shards) == after_total
        assert updates() == n0 + 1

    def test_many_dirty_shards_full_rebuild(self, holder, rng):
        from pilosa_tpu.pql import parse_string
        from pilosa_tpu.utils.stats import global_stats

        idx = self._build(holder, rng, n_shards=4)
        be = TPUBackend(holder)
        shards = list(range(4))
        call = parse_string("Count(Row(f=1))").calls[0].children[0]
        base = be.count_shards("i", call, shards)

        def updates():
            return global_stats._counters.get(
                ("stack_incremental_updates_total", ()), 0
            )

        n0 = updates()
        # Dirty 3 of 4 shards: over the 1/8 cutoff -> full rebuild.
        for s in range(3):
            idx.field("f").set_bit(1, s * SHARD_WIDTH + 999_999)
        assert be.count_shards("i", call, shards) == base + 3
        assert updates() == n0

    def test_row_growth_forces_rebuild(self, holder, rng):
        """A write that adds a new max row changes the stack height —
        never incrementally spliceable."""
        from pilosa_tpu.pql import parse_string

        idx = self._build(holder, rng, n_shards=16)
        be = TPUBackend(holder)
        shards = list(range(16))
        call = parse_string("Count(Row(f=63))").calls[0].children[0]
        assert be.count_shards("i", call, shards) == 0
        idx.field("f").set_bit(63, 5 * SHARD_WIDTH + 42)
        assert be.count_shards("i", call, shards) == 1


class TestRowsDevice:
    """Rows() served from the counts vector (VERDICT r3 #5) must match
    the host fragment walk in every shape."""

    def _setup(self, holder, rng):
        idx = holder.create_index("i")
        f = idx.create_field("f")
        for row in (0, 2, 5):
            cols = np.unique(rng.integers(0, 3 * SHARD_WIDTH, 2500, dtype=np.uint64))
            f.import_bits(np.full(cols.size, row, dtype=np.uint64), cols)
        return idx

    QUERIES = [
        "Rows(f)",
        "Rows(f, previous=1)",
        "Rows(f, previous=2)",
        "Rows(f, limit=2)",
        "Rows(f, previous=0, limit=1)",
        f"Rows(f, column={SHARD_WIDTH + 17})",
    ]

    def test_differential_vs_host(self, holder, rng):
        self._setup(holder, rng)
        host = Executor(holder)
        dev = Executor(holder, backend=TPUBackend(holder))
        for q in self.QUERIES:
            assert dev.execute("i", q) == host.execute("i", q), q

    def test_device_path_taken_and_row_clear(self, holder, rng):
        idx = self._setup(holder, rng)
        be = TPUBackend(holder)
        shards = [0, 1, 2]
        assert be.rows_field("i", "f", shards) == [0, 2, 5]
        assert be.rows_field("i", "f", shards, start=1) == [2, 5]
        # Clearing every bit of a row removes it (empty containers drop).
        Executor(holder).execute("i", "ClearRow(f=2)")
        assert be.rows_field("i", "f", shards) == [0, 5]
        assert Executor(holder, backend=be).execute("i", "Rows(f)") == Executor(
            holder
        ).execute("i", "Rows(f)")


class TestVersionCaptureRace:
    """ADVICE r4 (high): writers mutate storage BEFORE bumping version,
    both inside fr.lock (fragment.py set_bit). A version capture that
    does not serialize with that critical section can record a
    pre-write version for post-write content, and the non-idempotent
    delta replay then double-applies the op. These tests pin the fix:
    every capture/confirm read of (uid, version) holds fr.lock."""

    def _mid_write(self, fr, row, col):
        """Start a writer parked inside its critical section: storage
        mutated, version NOT yet bumped. Returns (thread, release)."""
        import threading

        from pilosa_tpu.core.fragment import pos

        entered = threading.Event()
        release = threading.Event()

        def writer():
            with fr.lock:
                fr.storage.add(pos(row, col))  # content lands first...
                entered.set()
                release.wait(5)
                fr.version += 1  # ...version bumps before unlock

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        assert entered.wait(5)
        return t, release

    def test_pack_confirmed_blocks_on_mid_write(self):
        import threading

        from pilosa_tpu.exec.tpu import _pack_confirmed

        fr = Fragment(None, "i", "f", "standard", 0)
        fr.set_bit(0, 1)
        t, release = self._mid_write(fr, 1, 5)
        done = {}

        def packer():
            done["res"] = _pack_confirmed(fr, 2)

        p = threading.Thread(target=packer, daemon=True)
        p.start()
        p.join(0.3)
        # Must be parked on fr.lock — capturing now would pair the
        # pre-write version with who-knows-which content.
        assert "res" not in done
        release.set()
        t.join(5)
        p.join(5)
        slab, v = done["res"]
        # The recorded version describes exactly the returned content:
        # the mid-flight write is in BOTH the slab and the version.
        assert v == (fr.uid, fr.version)
        assert slab[1][0] & (1 << 5)

    def test_live_versions_serialize_with_writer(self, holder):
        import threading

        idx = holder.create_index("i")
        f = idx.create_field("f")
        f.set_bit(0, 1)
        be = TPUBackend(holder)
        fr = f.view("standard").fragment(0)
        v_before = fr.version
        t, release = self._mid_write(fr, 1, 5)
        got = {}

        def reader():
            got["v"] = be._live_versions(f, (0,))

        r = threading.Thread(target=reader, daemon=True)
        r.start()
        r.join(0.3)
        assert "v" not in got  # parked on fr.lock, not reading mid-write
        release.set()
        t.join(5)
        r.join(5)
        assert got["v"][0] == (fr.uid, v_before + 1)


class TestGroupNMaintainedTensor:
    """VERDICT r4 #1b: unfiltered N>=3 GroupBy must absorb write churn
    through the maintained per-shard tensor (host delta/slab tiers), not
    re-dispatch the tiled sweep every epoch — and stay exact vs the
    oracle through every tier."""

    def _build(self, holder, rng, n_shards=4):
        idx = holder.create_index("i")
        for fn, nrows in (("f", 4), ("g", 4), ("h", 3)):
            f = idx.create_field(fn)
            for s in range(n_shards):
                cols = np.unique(
                    rng.integers(0, SHARD_WIDTH, 2500, dtype=np.uint64)
                ) + s * SHARD_WIDTH
                f.import_bits(
                    rng.integers(0, nrows, cols.size, dtype=np.uint64), cols
                )
        return idx

    def _updates(self):
        from pilosa_tpu.utils.stats import global_stats

        return global_stats._counters.get(
            ("groupn_incremental_updates_total", ()), 0
        )

    Q = "GroupBy(Rows(f), Rows(g), Rows(h))"

    def test_host_slab_matches_pershard_kernel(self, rng):
        from pilosa_tpu.exec.tpu import _host_slab_groupn
        from pilosa_tpu.ops.kernels import group_tile_stats_pershard

        rf, rg, rh, w = 8, 8, 4, 512
        fs = rng.integers(0, 2**32, (2, rf, w), dtype=np.uint32)
        gs = rng.integers(0, 2**32, (2, rg, w), dtype=np.uint32)
        hs = rng.integers(0, 2**32, (2, rh, w), dtype=np.uint32)
        per = np.asarray(
            group_tile_stats_pershard(
                _tiled(fs), _tiled(gs), (_tiled(hs),),
                np.arange(rh, dtype=np.int32)[:, None],
                np.ones(rh, dtype=np.uint32),
            )
        )  # [K, S, rf, rg]
        for s in range(2):
            host = _host_slab_groupn([fs[s], gs[s], hs[s]], [rf, rg, rh])
            np.testing.assert_array_equal(
                host, per[:, s].reshape(-1).astype(np.int32)
            )

    def test_point_write_delta_tier(self, holder, rng):
        idx = self._build(holder, rng)
        be = TPUBackend(holder)
        dev = Executor(holder, backend=be)
        host = Executor(holder)
        assert dev.execute("i", self.Q) == host.execute("i", self.Q)
        n0 = self._updates()
        # Point writes on each field in turn: every epoch must resolve
        # through the incremental tier, exactly.
        for j, fn in enumerate(("f", "g", "h", "f")):
            idx.field(fn).set_bit(j % 3, (j % 4) * SHARD_WIDTH + 12345 + j)
            assert dev.execute("i", self.Q) == host.execute("i", self.Q), fn
        assert self._updates() == n0 + 4
        # Clears too (negative deltas).
        idx.field("f").clear_bit(0, 12345)
        assert dev.execute("i", self.Q) == host.execute("i", self.Q)
        assert self._updates() == n0 + 5

    def test_bulk_write_slab_tier(self, holder, rng):
        idx = self._build(holder, rng)
        be = TPUBackend(holder)
        dev = Executor(holder, backend=be)
        host = Executor(holder)
        dev.execute("i", self.Q)
        n0 = self._updates()
        # Bulk import into one shard: the op ring can't explain it ->
        # that shard's row re-derives from _pack_confirmed slabs.
        cols = np.unique(
            rng.integers(0, SHARD_WIDTH, 3000, dtype=np.uint64)
        ) + 2 * SHARD_WIDTH
        idx.field("g").import_bits(
            rng.integers(0, 4, cols.size, dtype=np.uint64), cols
        )
        assert dev.execute("i", self.Q) == host.execute("i", self.Q)
        assert self._updates() == n0 + 1

    def test_row_growth_redispatches(self, holder, rng):
        idx = self._build(holder, rng)
        be = TPUBackend(holder)
        dev = Executor(holder, backend=be)
        host = Executor(holder)
        dev.execute("i", self.Q)
        # New max row on h changes the tensor K axis: must re-dispatch
        # (stack heights are padded to 8, so grow past the pad).
        idx.field("h").set_bit(9, SHARD_WIDTH + 7)
        assert dev.execute("i", self.Q) == host.execute("i", self.Q)

    def test_mixed_churn_stays_exact(self, holder, rng):
        idx = self._build(holder, rng)
        be = TPUBackend(holder)
        dev = Executor(holder, backend=be)
        host = Executor(holder)
        dev.execute("i", self.Q)
        w = np.random.default_rng(5)
        for step in range(12):
            fn = ("f", "g", "h")[step % 3]
            if step % 5 == 4:
                cols = np.unique(
                    w.integers(0, SHARD_WIDTH, 500, dtype=np.uint64)
                ) + int(w.integers(0, 4)) * SHARD_WIDTH
                idx.field(fn).import_bits(
                    w.integers(0, 3, cols.size, dtype=np.uint64), cols
                )
            else:
                idx.field(fn).set_bit(
                    int(w.integers(0, 3)),
                    int(w.integers(0, 4 * SHARD_WIDTH)),
                )
            assert dev.execute("i", self.Q) == host.execute("i", self.Q), step

    def test_four_fields(self, holder, rng):
        idx = self._build(holder, rng)
        f = idx.create_field("e")
        for s in range(4):
            cols = np.unique(
                rng.integers(0, SHARD_WIDTH, 1500, dtype=np.uint64)
            ) + s * SHARD_WIDTH
            f.import_bits(np.zeros(cols.size, dtype=np.uint64) + rng.integers(0, 2), cols)
        be = TPUBackend(holder)
        dev = Executor(holder, backend=be)
        host = Executor(holder)
        q = "GroupBy(Rows(f), Rows(g), Rows(h), Rows(e))"
        assert dev.execute("i", q) == host.execute("i", q)
        idx.field("e").set_bit(1, 3 * SHARD_WIDTH + 99)
        assert dev.execute("i", q) == host.execute("i", q)


class TestMinMaxChurnAbsorption:
    """VERDICT r4 #7: Min/Max must absorb point-value churn through the
    per-shard extremum table — O(1) for monotone writes, host re-derive
    (no device dispatch) only for shards whose incumbent was cleared —
    and stay exact vs the oracle through every tier."""

    def _build(self, holder, rng, shards=3):
        idx = holder.create_index("i")
        idx.create_field("v", options_for_int(-1000, 1000))
        cols = np.unique(
            rng.integers(0, shards * SHARD_WIDTH, 600, dtype=np.uint64)
        )
        idx.field("v").import_value(cols, rng.integers(-900, 901, cols.size))
        return idx, cols

    def _upd(self, name):
        from pilosa_tpu.utils.stats import global_stats

        return global_stats._counters.get((name, ()), 0)

    def _check(self, holder, be, shards):
        ex = Executor(holder)
        for kind, q in (("min", "Min(field=v)"), ("max", "Max(field=v)")):
            want = ex.execute("i", q)[0]
            got = getattr(be, f"bsi_{kind}")("i", "v", shards)
            assert got == (want.val, want.count), (kind, got, want)

    def test_monotone_writes_are_o1(self, holder, rng):
        idx, cols = self._build(holder, rng)
        shards = [0, 1, 2]
        be = TPUBackend(holder)
        self._check(holder, be, shards)
        n0 = self._upd("minmax_incremental_updates_total")
        r0 = self._upd("minmax_shard_rederives_total")
        # A middling value: beats neither extremum -> pure O(1) update.
        free = int(cols.max()) + 10
        idx.field("v").set_value(free, 5)
        self._check(holder, be, shards)
        assert self._upd("minmax_incremental_updates_total") == n0 + 2
        assert self._upd("minmax_shard_rederives_total") == r0
        # New global min and max: still O(1) (better value replaces).
        idx.field("v").set_value(free + 1, -999)
        idx.field("v").set_value(free + 2, 999)
        self._check(holder, be, shards)
        assert self._upd("minmax_shard_rederives_total") == r0

    def test_cleared_incumbent_rederives_one_shard(self, holder, rng):
        idx, cols = self._build(holder, rng)
        shards = [0, 1, 2]
        be = TPUBackend(holder)
        # Plant a unique global minimum, warm the table.
        free = int(cols.max()) + 10
        idx.field("v").set_value(free, -999)
        self._check(holder, be, shards)
        r0 = self._upd("minmax_shard_rederives_total")
        # Overwrite the incumbent minimum with a middling value: its
        # shard's extremum is cleared -> exactly that shard re-derives
        # on the host.
        idx.field("v").set_value(free, 17)
        self._check(holder, be, shards)
        assert self._upd("minmax_shard_rederives_total") == r0 + 1
        # Max table for the same epoch should NOT have re-derived
        # (the old -999 and new 17 both lose to the max incumbent)...
        # already covered by the +1 (min) instead of +2.

    @staticmethod
    def _clear(f, col):
        f._bsi_fragment(col // SHARD_WIDTH).clear_value(
            col, f.bsi_group().bit_depth
        )

    def test_clear_value_and_ties(self, holder, rng):
        idx = holder.create_index("i")
        idx.create_field("v", options_for_int(-100, 100))
        f = idx.field("v")
        # Tie: two columns in different shards share the minimum.
        f.set_value(5, -50)
        f.set_value(SHARD_WIDTH + 7, -50)
        f.set_value(20, 30)
        be = TPUBackend(holder)
        shards = [0, 1]
        self._check(holder, be, shards)
        assert be.bsi_min("i", "v", shards) == (-50, 2)
        # Clearing one of the tied pair: count drops, value holds.
        self._clear(f, 5)
        self._check(holder, be, shards)
        assert be.bsi_min("i", "v", shards) == (-50, 1)
        # Clearing the last: shard 1's incumbent clears -> re-derive.
        self._clear(f, SHARD_WIDTH + 7)
        self._check(holder, be, shards)
        assert be.bsi_min("i", "v", shards) == (30, 1)

    def test_bulk_import_rederives_not_redispatches(self, holder, rng):
        idx, cols = self._build(holder, rng)
        shards = [0, 1, 2]
        be = TPUBackend(holder)
        self._check(holder, be, shards)
        n0 = self._upd("minmax_incremental_updates_total")
        # Bulk import into shard 1: ring can't explain -> host re-derive
        # of that shard (still the incremental tier, no dispatch).
        newc = np.unique(
            rng.integers(SHARD_WIDTH, 2 * SHARD_WIDTH, 300, dtype=np.uint64)
        )
        idx.field("v").import_value(newc, rng.integers(-900, 901, newc.size))
        self._check(holder, be, shards)
        assert self._upd("minmax_incremental_updates_total") == n0 + 2

    def test_churn_stays_exact(self, holder, rng):
        idx, cols = self._build(holder, rng)
        shards = [0, 1, 2]
        be = TPUBackend(holder)
        self._check(holder, be, shards)
        w = np.random.default_rng(9)
        for step in range(25):
            col = int(w.integers(0, 3 * SHARD_WIDTH))
            if step % 7 == 6:
                self._clear(idx.field("v"), col)
            else:
                idx.field("v").set_value(col, int(w.integers(-1000, 1001)))
            self._check(holder, be, shards)


class TestWindowedRefresh:
    """Windowed device-refresh coalescing (ISSUE r19 tentpole 2):
    answers under churn stay byte-identical to unwindowed execution, a
    read landing mid-window forces the flush barrier, a window flush
    goes through the incremental splice (full rebuilds flat), and the
    background flusher actually refreshes stale stacks."""

    def _setup(self, holder, rng):
        idx = holder.create_index("i")
        idx.create_field("f")
        idx.create_field("g")
        for row in [1, 2, 3]:
            cols = np.unique(
                rng.integers(0, 2 * SHARD_WIDTH, 3000, dtype=np.uint64)
            )
            idx.field("f").import_bits(
                np.full(cols.size, row, dtype=np.uint64), cols
            )
        cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 2000, dtype=np.uint64))
        idx.field("g").import_bits(np.full(cols.size, 9, dtype=np.uint64), cols)
        return idx

    @staticmethod
    def _counter(name):
        from pilosa_tpu.utils.stats import global_stats

        return global_stats.snapshot()["counters"].get(name, 0.0)

    def test_differential_with_mid_window_barrier(self, holder, rng):
        """Interleave background window flushes (refresh_stale) with
        mid-window stack reads across import churn: the windowed
        backend's device tensor must stay byte-identical to an
        UNWINDOWED backend's, query answers must match the CPU oracle,
        the mid-window reads must show up as forced barriers, the
        flushes as windowed refreshes — and stack_full_rebuilds_total
        must not move (the splice stays on the incremental path)."""
        from pilosa_tpu.pql import parse_string

        idx = self._setup(holder, rng)
        be_w = TPUBackend(holder)   # windowed
        be_u = TPUBackend(holder)   # unwindowed reference
        cpu = Executor(holder)
        queries = [
            "Intersect(Row(f=1), Row(g=9))",
            "Union(Row(f=2), Row(g=9))",
            "Row(f=3)",
        ]
        calls = [parse_string(q).calls[0] for q in queries]
        fobj = idx.field("f")
        shards = (0, 1)
        be_w.blocks.get("i", fobj, shards)  # resident
        rebuilds0 = self._counter("stack_full_rebuilds_total")
        forced0 = self._counter("stack_refresh_forced_total")
        windowed0 = self._counter("stack_windowed_refresh_total")
        # Windowing on, no flusher thread: the window boundary is
        # driven manually (refresh_stale) so the test is deterministic.
        be_w.blocks.refresh_window_ms = 60_000
        forced = windowed = 0
        for k in range(8):
            fobj.set_bit(1 + k % 3, 555_000 + 97 * k)
            if k % 2 == 0:
                # Mid-window read: the flush-on-demand barrier splices
                # inline rather than serving stale device bits.
                forced += 1
            else:
                # The window boundary: dirty shards flush as one
                # incremental round per stale stack.
                n = be_w.blocks.refresh_stale()
                assert n >= 1, "write must have staled the stack"
                windowed += n
            block_w, _ = be_w.blocks.get("i", fobj, shards)
            block_u, _ = be_u.blocks.get("i", fobj, shards)
            np.testing.assert_array_equal(
                np.asarray(block_w), np.asarray(block_u)
            )
            got = be_w.count_batch("i", calls, list(shards))
            want = [cpu.execute("i", f"Count({q})")[0] for q in queries]
            assert got == want, (k, got, want)
        assert self._counter("stack_refresh_forced_total") - forced0 == forced
        assert (
            self._counter("stack_windowed_refresh_total") - windowed0
            == windowed
        )
        assert self._counter("stack_full_rebuilds_total") == rebuilds0
        # A read right after a window flush is a plain hit: no barrier.
        assert be_w.blocks.refresh_stale() == 0
        f1 = self._counter("stack_refresh_forced_total")
        be_w.blocks.get("i", fobj, shards)
        assert self._counter("stack_refresh_forced_total") == f1

    def test_background_flusher_thread_refreshes(self, holder, rng):
        """start_refresher: the stack-refresh daemon picks up a write
        within a few windows with no read in between."""
        from pilosa_tpu.pql import parse_string

        idx = self._setup(holder, rng)
        be = TPUBackend(holder)
        calls = [parse_string("Row(f=1)").calls[0]]
        shards = [0, 1]
        first = be.count_batch("i", calls, shards)
        be.start_refresher(10)
        try:
            w0 = self._counter("stack_windowed_refresh_total")
            idx.field("f").set_bit(1, 777_777)
            deadline = time.monotonic() + 10
            while self._counter("stack_windowed_refresh_total") == w0:
                assert time.monotonic() < deadline, "flusher never refreshed"
                time.sleep(0.01)
            # The flushed stack serves the new bit as a plain hit.
            f0 = self._counter("stack_refresh_forced_total")
            assert be.count_batch("i", calls, shards) == [first[0] + 1]
            assert self._counter("stack_refresh_forced_total") == f0
        finally:
            be.stop_refresher()
        assert be.blocks.refresh_window_ms == 0
