"""Opt-in REAL-chip leg (VERDICT r4 #6): the dryrun query list + write
churn against the live TPU with small stacks. Pallas interpret mode (the
CPU suite) can't catch Mosaic-on-hardware behavior — VMEM limits, layout
choices — which is exactly what the device_fallback_total counter
exists for; this leg asserts the counter does NOT grow, i.e. every
device fast path really ran on the chip.

    PILOSA_TPU_TEST_TPU=1 python -m pytest -m tpu -q

Run SOLO: a chip belongs to one process at a time, so nothing else that
touches JAX (bench.py, a server, chip_smoke.py) may run beside it."""

import numpy as np
import pytest

from pilosa_tpu.shardwidth import SHARD_WIDTH

pytestmark = pytest.mark.tpu

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
    "GroupBy(Rows(f))",
    "GroupBy(Rows(f), Rows(g))",
    "GroupBy(Rows(f), Rows(g), filter=Row(f=2))",
    "GroupBy(Rows(f), Rows(g), Rows(h))",
]


@pytest.fixture(scope="module")
def live_setup(tmp_path_factory):
    import jax

    assert jax.default_backend() == "tpu", (
        f"live leg needs the real chip, got {jax.default_backend()}"
    )
    import __graft_entry__ as ge
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.exec.tpu import TPUBackend

    rng = np.random.default_rng(0)
    holder = ge._build_holder(
        str(tmp_path_factory.mktemp("live")), 4, rng
    )
    be = TPUBackend(holder)
    yield holder, Executor(holder), Executor(holder, backend=be)
    holder.close()


def _fallbacks() -> int:
    from pilosa_tpu.utils.stats import global_stats

    with global_stats._lock:
        return int(
            sum(
                v
                for (name, _tags), v in global_stats._counters.items()
                if name == "device_fallback_total"
            )
        )


class TestLiveChip:
    def test_dryrun_query_list_exact_with_zero_fallbacks(self, live_setup):
        from pilosa_tpu.exec.result import result_to_json

        holder, ex_cpu, ex_dev = live_setup
        before = _fallbacks()
        for q in QUERIES:
            want = [result_to_json(r) for r in ex_cpu.execute("i", q)]
            got = [result_to_json(r) for r in ex_dev.execute("i", q)]
            assert got == want, q
        assert _fallbacks() == before, "device fast path fell back on chip"

    def test_churn_epoch_stays_exact_with_zero_fallbacks(self, live_setup):
        from pilosa_tpu.exec.result import result_to_json

        holder, ex_cpu, ex_dev = live_setup
        idx = holder.index("i")
        before = _fallbacks()
        for k in range(2):
            idx.field("f").set_bit(1, 7 + k * 131)
            idx.field("v").set_value(23 + k * 97, 400 - k)
            for q in (
                "Count(Intersect(Row(f=1), Row(g=7)))",
                "TopN(f, n=0)",
                "Sum(field=v)",
                "Min(field=v)",
                "Max(field=v)",
                "GroupBy(Rows(f), Rows(g), Rows(h))",
            ):
                want = [result_to_json(r) for r in ex_cpu.execute("i", q)]
                got = [result_to_json(r) for r in ex_dev.execute("i", q)]
                assert got == want, (k, q)
        assert _fallbacks() == before, "churn epoch fell back on chip"
