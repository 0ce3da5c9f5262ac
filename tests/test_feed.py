"""The feed clock, the lock ledger and the launched slots:
`device_feed_seconds_total{state,cause}` sums to wall time and charges
unfed time only to steps with nothing outstanding, a launch is fed once
whatever the threads, every launch kind a request makes is closed by a
wait, `thread_cpu_seconds_total` reads every thread's CPU by role, and
`device_slots_total` adds up to the bucket."""

import os
import re
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.exec import Executor
from pilosa_tpu.server.api import API
from pilosa_tpu.server.http import Server
from pilosa_tpu.utils import qprofile
from pilosa_tpu.utils.qprofile import DeviceFeed, PlaneProfile
from pilosa_tpu.utils.stats import StatsClient, global_stats

tpu = pytest.importorskip(
    "pilosa_tpu.exec.tpu",
    reason="device backend needs jax.shard_map",
    exc_type=ImportError,
)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def feed_seconds(stats) -> dict:
    """{(state, cause): seconds} of `device_feed_seconds_total`."""
    out = {}
    for key, v in stats.counter_totals("device_feed_seconds_total").items():
        tags = dict(re.findall(r'(\w+)="([^"]*)"', key))
        out[(tags["state"], tags["cause"])] = v
    return out


@pytest.fixture
def feed(monkeypatch):
    """A feed clock of the test's own: the process's is shared by every
    backend the worker has built."""
    clock = DeviceFeed()
    monkeypatch.setattr(qprofile, "device_feed", clock)
    monkeypatch.setattr(tpu, "device_feed", clock)
    return clock


_inc = jax.jit(lambda a: a + 1)


def launch(ledger, x):
    """One launch parked in `ledger` as `_counted_launch` parks it."""
    out = _inc(x)
    ledger.record_launch("count", None, (x,), 0.0, False,
                         time.perf_counter(), out)
    return out


# -- the feed clock ------------------------------------------------------


def test_a_drain_with_one_launch_sums_to_wall_and_charges_idle_steps(feed):
    stats = StatsClient()
    ledger = tpu._ProgramLedger(stats)
    x = jnp.arange(8)
    jax.block_until_ready(_inc(x))  # compiled
    t0 = feed._mark
    with PlaneProfile(stats) as plane:
        with plane.phase("take"):
            time.sleep(0.03)
        with plane.phase("dispatch"):
            out = launch(ledger, x)
            time.sleep(0.03)
        with plane.phase("device_wait"):
            time.sleep(0.03)
            ledger.block_ready(out)
        with plane.phase("readback"):
            time.sleep(0.03)
    feed.flush(stats)
    wall = feed._mark - t0
    got = feed_seconds(stats)
    assert sum(got.values()) == pytest.approx(wall, rel=0.01)
    assert got[("fed", "launch")] >= 0.06
    assert got[("unfed", "take")] >= 0.03
    assert got[("unfed", "readback")] >= 0.03
    # Something was outstanding through the whole of these two but for
    # the instants before the launch and after the wait.
    assert got.get(("unfed", "dispatch"), 0.0) < 0.02
    assert got.get(("unfed", "device_wait"), 0.0) < 0.02
    assert ledger.outstanding() == 0 and feed.outstanding == 0


def test_overlapping_launches_from_two_threads_count_fed_once(feed):
    stats = StatsClient()
    ledger = tpu._ProgramLedger(stats)
    x = jnp.arange(8)
    jax.block_until_ready(_inc(x))
    b_launched, a_closed = threading.Event(), threading.Event()
    stamps = {}

    def a():
        stamps["first"] = time.perf_counter()
        out = launch(ledger, x)
        b_launched.wait()
        time.sleep(0.03)
        ledger.block_ready(out)  # closes A's launch, not B's
        stamps["a_closed"] = feed.outstanding
        a_closed.set()

    def b():
        time.sleep(0.01)
        out = launch(ledger, x)
        b_launched.set()
        a_closed.wait()
        time.sleep(0.03)
        ledger.block_ready(out)
        stamps["last"] = time.perf_counter()

    threads = [threading.Thread(target=a), threading.Thread(target=b)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    feed.flush(stats)
    fed = feed_seconds(stats)[("fed", "launch")]
    assert stamps["a_closed"] == 1
    # One stretch from A's launch to B's close: A's and B's added would
    # count their ~30 ms of overlap twice and pass the whole.
    assert 0.06 <= fed <= stamps["last"] - stamps["first"]
    assert feed.outstanding == 0


def test_a_wait_closes_what_fed_it_and_leaves_later_launches(feed):
    """A wait on one launch closes its thread's launches before it (the
    intermediates that fed it), not those after it; a wait on a derived
    array closes the thread's all; a dropped output is swept."""
    ledger = tpu._ProgramLedger(StatsClient())
    x = jnp.arange(8)
    first, second, third = (launch(ledger, x) for _ in range(3))
    ledger.block_ready(second)
    assert ledger.outstanding() == 1  # the third is still outstanding
    ledger.block_ready(third[:4])     # a gather no launch returned
    assert ledger.outstanding() == 0
    launch(ledger, x)                 # its output dropped at once
    ledger.publish()
    assert ledger.outstanding() == 0 and feed.outstanding == 0


def test_many_threads_launching_and_waiting_lose_no_launch(feed):
    """More threads than cores, switching as often as the interpreter
    allows: every launch parked is closed once, the count ends at 0 and
    the family still sums to the wall."""
    stats = StatsClient()
    ledger = tpu._ProgramLedger(stats)
    x = jnp.arange(8)
    jax.block_until_ready(_inc(x))
    rounds, workers = 40, (os.cpu_count() or 4) + 4
    t0 = feed._mark
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(rounds):
                outs = [launch(ledger, x) for _ in range(3)]
                ledger.block_ready(outs[-1])

        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    assert ledger.outstanding() == 0 and feed.outstanding == 0
    feed.flush(stats)
    assert sum(feed_seconds(stats).values()) == pytest.approx(
        feed._mark - t0, rel=0.01
    )
    launched = ledger.counts()["launches"]
    assert launched == rounds * workers * 3


# -- every launch kind a request makes is closed ----------------------------


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from pilosa_tpu.exec.batcher import ShardLegBatcher

    holder = Holder(str(tmp_path_factory.mktemp("feed"))).open()
    idx = holder.create_index("i")
    for name in ("f", "g", "h"):
        idx.create_field(name)
    idx.create_field("v", FieldOptions(type="int", min=-100, max=1000))
    be = tpu.TPUBackend(holder)
    ex = Executor(holder, backend=be)
    ex.batcher = ShardLegBatcher(be)
    ex.execute("i", "Set(10, f=1) Set(10, g=2) Set(10, h=1) Set(1048577, f=1)"
               " Set(11, f=2) Set(11, g=1) Set(12, h=2)"
               " Set(10, v=5) Set(11, v=7) Set(1048577, v=-3)")
    yield be, ex
    holder.close()


def launches(kind: str) -> float:
    return global_stats.counter_totals("device_launches_total").get(
        f'device_launches_total{{kind="{kind}"}}', 0.0
    )


@pytest.mark.parametrize("kind,query", [
    ("count_batch", "Count(Intersect(Row(f=1), Row(g=2), Row(h=1)))"),
    ("pair_stats", "Count(Intersect(Row(f=1), Row(g=1)))"),
    ("vec", "Intersect(Row(f=1), Row(g=2))"),
    ("topn_plain", "TopN(g)"),
    ("topn_src", "TopN(f, Row(h=1))"),
    ("groupby", "GroupBy(Rows(f), Rows(g), filter=Row(h=1))"),
    ("group_tile_pershard", "GroupBy(Rows(f), Rows(g), Rows(h))"),
    ("group_tile", "GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(f=1))"),
    ("bsi_sum", "Sum(Row(f=1), field=v)"),
    ("bsi_min", "Min(field=v)"),
    ("bsi_max", "Max(field=v)"),
    ("group_sum", "GroupBy(Rows(f), Rows(g), aggregate=Sum(field=v))"),
])
def test_a_request_leaves_no_launch_outstanding(served, kind, query):
    be, ex = served
    before = launches(kind)
    ex.execute("i", query)
    assert launches(kind) > before, f"{query} launched no {kind}"
    assert be.programs.outstanding() == 0


def test_a_count_and_a_row_batch_leave_no_launch_outstanding(served):
    """The shapes the batcher makes of concurrent requests: a Count of
    no scalars (one slot) and a row batch of several slots."""
    from pilosa_tpu.pql import parse_string

    be, _ = served
    for kind, run in (
        ("count", lambda: be.count_batch("i", [parse_string(
            "Count(All())").calls[0].children[0]], [0, 1])),
        ("vec_batch", lambda: be.row_batch_async("i", [
            parse_string(q).calls[0] for q in ("Row(f=1)", "Row(f=2)")
        ], [0, 1])()),
    ):
        before = launches(kind)
        run()
        assert launches(kind) > before
        assert be.programs.outstanding() == 0


# -- launched slots --------------------------------------------------------


def slots(be, kind: str) -> tuple[float, float]:
    be.programs.publish()  # what a scrape does
    got = global_stats.counter_totals("device_slots_total")
    return tuple(
        got.get(f'device_slots_total{{fill="{fill}",kind="{kind}"}}', 0.0)
        for fill in ("live", "pad")
    )


@pytest.mark.parametrize("kind,calls,live,bucket", [
    # Three distinct calls and a duplicate: three slots of a bucket of 4.
    ("count_batch", ["Intersect(Row(f=1), Row(g=2), Row(h=1))",
                     "Intersect(Row(f=2), Row(g=1), Row(h=1))",
                     "Intersect(Row(f=1), Row(g=1), Row(h=2))",
                     "Intersect(Row(f=1), Row(g=2), Row(h=1))"], 3, 4),
    ("count_batch", ["Union(Row(f=1), Row(g=2), Row(h=1))"], 1, 1),
    # One group a field: f's three rows share a launch of a bucket of 4.
    ("row_batch", ["Row(f=1)", "Row(f=2)", "Row(f=3)"], 3, 4),
    ("row_batch", ["Row(h=2)"], 1, 1),
])
def test_launched_slots_add_up_to_the_bucket(served, kind, calls, live, bucket):
    from pilosa_tpu.pql import parse_string

    be, _ = served
    parsed = [parse_string(c).calls[0] for c in calls]
    live0, pad0 = slots(be, kind)
    if kind == "count_batch":
        be.count_batch("i", parsed, [0, 1])
    else:
        be.row_batch_async("i", parsed, [0, 1])()
    live1, pad1 = slots(be, kind)
    assert (live1 - live0, pad1 - pad0) == (live, bucket - live)


# -- the lock ledger -------------------------------------------------------


def scrape(srv) -> tuple[float, dict]:
    """(seconds the scrape took to come back, {series: value})."""
    t0 = time.perf_counter()
    text = urllib.request.urlopen(srv.uri + "/metrics").read().decode()
    took = time.perf_counter() - t0
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            series, _, value = line.rpartition(" ")
            out[series] = float(value)
    return took, out


def family(page: dict, name: str) -> float:
    return sum(v for k, v in page.items() if k.split("{")[0] == name)


def test_a_live_server_reports_thread_cpu_and_a_feed_that_sums_to_wall(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    srv = Server(API(holder, Executor(holder)), host="localhost",
                 port=0).open()
    try:
        req = urllib.request.Request(srv.uri + "/index/i", data=b"{}",
                                     method="POST")
        urllib.request.urlopen(req).read()
        req = urllib.request.Request(srv.uri + "/index/i/query",
                                     data=b"Count(All())", method="POST")
        urllib.request.urlopen(req).read()
        t_a = time.perf_counter()
        took_1, first = scrape(srv)
        t_b = t_a + took_1
        time.sleep(0.2)
        t_c = time.perf_counter()
        took_2, second = scrape(srv)
        t_d = t_c + took_2
    finally:
        srv.close()
        holder.close()
    assert 'pilosa_thread_cpu_seconds_total{role="http-worker",' \
        'runtime="python"}' in second
    assert any('runtime="native"' in k for k in second
               if k.startswith("pilosa_thread_cpu_seconds_total"))
    grew = (family(second, "pilosa_device_feed_seconds_total")
            - family(first, "pilosa_device_feed_seconds_total"))
    # Each scrape flushes at an instant inside its own request.
    assert t_c - t_b <= grew <= t_d - t_a
    assert np.isfinite(family(second, "pilosa_thread_cpu_seconds_total"))
