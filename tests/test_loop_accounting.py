"""Where the loop thread's and the device worker's wall time goes: the
event loop's timed wait (``core/tracing.py TimedSelector`` over the
native codec's ``epoll_wait``), the send calls the pump hands to the
codec's writer thread (``send_packets``), each named thread's CPU and run-queue
time read at scrape (``core/metrics.py ThreadClocks``), and the ingest
stages on the device trace's clock while a profiler session is live.

Timing bounds here are loose (ten times or more): these run on a loaded
CPU host.
"""

import asyncio
import selectors
import signal
import socket
import threading
import time

import pytest

from channeld_tpu.core import connection as connection_mod
from channeld_tpu.core import metrics, tracing
from channeld_tpu.core.device_guard import DeviceGuard
from channeld_tpu.native import codec

from helpers import stage_count

native = pytest.mark.skipif(
    getattr(codec, "epoll_wait", None) is None,
    reason="the native codec (with epoll_wait) is not built")


def counters() -> dict:
    return codec.counters()


def scrape() -> dict:
    """``{(family, labels): value}`` of everything ``ThreadClocks``
    exports now."""
    return {(s.name, tuple(sorted(s.labels.items()))): s.value
            for family in metrics.thread_clocks.collect()
            for s in family.samples}


class Annotation:
    """A profiler session faked live (as tests/test_tracing.py does)."""

    made: list = []

    def __init__(self, name):
        self.name = name

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        Annotation.made.append(self.name)
        return self

    def __exit__(self, *exc):
        pass


@pytest.fixture(params=[False, True], ids=["plain", "profiling"])
def profiling(request, monkeypatch):
    Annotation.made = []
    if request.param:
        monkeypatch.setattr(tracing, "_annotation", Annotation)
    monkeypatch.setattr(tracing.recorder, "profiling", request.param)
    return request.param


@pytest.fixture
def pair():
    a, b = socket.socketpair()
    a.setblocking(False)
    b.setblocking(False)
    yield a, b
    a.close()
    b.close()


def both():
    """The stock selector and the timed one."""
    stock = selectors.EpollSelector()
    timed = tracing.TimedSelector(codec.epoll_wait)
    return stock, timed


def keys(ready) -> list:
    return sorted((key.fd, key.events, key.data, events)
                  for key, events in ready)


# ---------------------------------------------------------------------------
# the timed selector
# ---------------------------------------------------------------------------


@native
def test_register_modify_unregister_return_what_epoll_returns(pair):
    a, b = pair
    stock, timed = both()
    try:
        for sel in (stock, timed):
            assert sel.register(a, selectors.EVENT_READ, "r") == \
                selectors.SelectorKey(a, a.fileno(), selectors.EVENT_READ,
                                      "r")
        assert stock.get_key(a) == timed.get_key(a)
        assert stock.modify(a, selectors.EVENT_WRITE, "w") == \
            timed.modify(a, selectors.EVENT_WRITE, "w")
        assert stock.unregister(a) == timed.unregister(a)
        assert stock.get_map() == {} and dict(timed.get_map()) == {}
    finally:
        stock.close()
        timed.close()


@native
@pytest.mark.parametrize("timeout", [None, 0, 0.2])
def test_select_gives_what_epoll_gives(pair, timeout):
    a, b = pair
    stock, timed = both()
    try:
        for sel in (stock, timed):
            sel.register(a, selectors.EVENT_READ | selectors.EVENT_WRITE, 1)
            sel.register(b, selectors.EVENT_READ, 2)
        b.send(b"x")  # a is readable and writable, b neither
        assert keys(timed.select(timeout)) == keys(stock.select(timeout))
        assert keys(timed.select(timeout)) == [
            (a.fileno(), selectors.EVENT_READ | selectors.EVENT_WRITE, 1,
             selectors.EVENT_READ | selectors.EVENT_WRITE)]
        a.recv(1)
        for sel in (stock, timed):
            sel.modify(a, selectors.EVENT_READ, 1)
        if timeout is not None:  # nothing ready: no events, either way
            assert timed.select(timeout) == stock.select(timeout) == []
    finally:
        stock.close()
        timed.close()


@native
def test_a_selector_with_nothing_registered_waits_its_timeout():
    timed = tracing.TimedSelector(codec.epoll_wait)
    try:
        assert timed.select(0) == []
        assert timed.select(0.001) == []
    finally:
        timed.close()


@native
def test_a_wait_a_signal_cuts_short_gives_no_events(pair):
    a, _ = pair
    timed = tracing.TimedSelector(codec.epoll_wait)
    timed.register(a, selectors.EVENT_READ)
    caught = []
    old = signal.signal(signal.SIGALRM, lambda *_: caught.append(1))
    try:
        signal.setitimer(signal.ITIMER_REAL, 0.05)
        began = time.monotonic()
        assert timed.select(20.0) == []
        assert time.monotonic() - began < 10.0
        assert caught == [1]  # the handler ran before select returned
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)
        timed.close()


def test_interrupted_error_gives_an_empty_list():
    def interrupted(epfd, timeout_ms, max_ev):
        raise InterruptedError

    timed = tracing.TimedSelector(interrupted)
    try:
        assert timed.select(1.0) == []
    finally:
        timed.close()


def test_the_timeout_is_rounded_as_epoll_rounds_it():
    asked = []

    def wait(epfd, timeout_ms, max_ev):
        asked.append((timeout_ms, max_ev))
        return []

    timed = tracing.TimedSelector(wait)
    try:
        for timeout in (None, -1, 0, 0.0001, 0.001, 0.0015, 2.5):
            timed.select(timeout)
    finally:
        timed.close()
    assert asked == [(-1, 1), (0, 1), (0, 1), (1, 1), (1, 1), (2, 1),
                     (2500, 1)]


@native
def test_an_idle_wait_is_counted_asleep():
    timed = tracing.TimedSelector(codec.epoll_wait)
    before = counters()
    try:
        began = time.monotonic()
        timed.select(0.05)
        took = time.monotonic() - began
    finally:
        timed.close()
    after = counters()
    asleep = (after["poll_asleep_ns"] - before["poll_asleep_ns"]) / 1e9
    assert after["polls"] - before["polls"] == 1
    assert 0.04 <= asleep <= took
    assert after["poll_relock_ns"] >= before["poll_relock_ns"]


def test_the_wait_is_the_annotation_alone_while_profiling(profiling):
    def wait(epfd, timeout_ms, max_ev):
        return []

    timed = tracing.TimedSelector(wait)
    try:
        for _ in range(3):
            timed.select(0)
    finally:
        timed.close()
    assert Annotation.made == (["channeld/loop.poll"] * 3 if profiling
                               else [])
    # No ring span and no stage: thousands a second would evict the ticks.
    assert not [s for s in tracing.recorder.snapshot()
                if s["name"] == "loop.poll"]
    assert ("loop.poll",) not in metrics.tick_stage_ms._metrics


@native
def test_the_entry_points_loop_runs_on_the_timed_selector(pair):
    a, b = pair
    factory = tracing.timed_loop_factory()
    assert factory is not None
    before = counters()["polls"]

    async def main():
        loop = asyncio.get_running_loop()
        assert isinstance(loop._selector, tracing.TimedSelector)
        b.send(b"ping")
        assert await loop.sock_recv(a, 4) == b"ping"
        await asyncio.sleep(0.01)
        return "done"

    assert asyncio.run(main(), loop_factory=factory) == "done"
    assert counters()["polls"] - before >= 2


def test_without_the_codec_the_stock_selector_and_no_counter(monkeypatch):
    from channeld_tpu import native as native_pkg

    monkeypatch.setattr(native_pkg, "codec", None)
    assert tracing.timed_loop_factory() is None
    names = {name for name, _ in scrape()}
    assert not names & {"loop_poll_seconds_total", "loop_polls_total",
                        "send_pump_write_seconds_total",
                        "send_pump_partial_writes_total",
                        "send_writer_wait_seconds_total",
                        "send_writer_batches_total",
                        "send_writer_backlog_bytes"}


# ---------------------------------------------------------------------------
# the send pump's hand-off and the writer's send calls
# ---------------------------------------------------------------------------


def batch(n: int) -> list:
    """``n`` messages too large to share a packet: ``n`` packets."""
    return [(0, 0, 0, 100 + i, bytes([i]) * 40_000) for i in range(n)]


@pytest.mark.skipif(getattr(codec, "writer_register", None) is None,
                    reason="the native codec (with its send writer) is not "
                           "built")
@pytest.mark.parametrize("packets", [4, 6, 1])
def test_a_pass_adds_its_write_time_and_one_release_from_four_packets(
        packets):
    """However many packets a pass holds, it hands them off and gives
    the lock up nowhere (no release is counted at all); the writer's
    send calls add their time, and the hand-off one batch and its
    wait."""
    a, b = socket.socketpair()
    a.setblocking(False)
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)
    key = codec.writer_register(a.fileno())
    try:
        before = counters()
        (result,) = codec.send_packets([(key, batch(packets), 0)])
        assert result[0] == packets and not result[4]
        deadline = time.monotonic() + 10
        while codec.writer_pending(key):
            assert time.monotonic() < deadline, "the writer did not drain"
            time.sleep(0.001)
        after = counters()
        assert after["pump_write_ns"] > before["pump_write_ns"]
        assert after["writer_batches"] - before["writer_batches"] == 1
        assert after["writer_wait_ns"] > before["writer_wait_ns"]
        got = scrape()
        assert ("send_pump_releases_total", ()) not in got
        assert ("send_pump_relock_seconds_total", ()) not in got
    finally:
        codec.writer_release(key, False)
        a.close()
        b.close()


@native
def test_the_codecs_counters_reach_metrics():
    got = scrape()
    c = counters()
    assert got[("loop_polls_total", ())] == c["polls"]
    assert got[("loop_poll_seconds_total", (("part", "asleep"),))] == \
        pytest.approx(c["poll_asleep_ns"] / 1e9)
    assert got[("loop_poll_seconds_total", (("part", "relock"),))] == \
        pytest.approx(c["poll_relock_ns"] / 1e9)
    assert got[("send_pump_write_seconds_total", ())] == \
        pytest.approx(c["pump_write_ns"] / 1e9)
    assert got[("send_pump_partial_writes_total", ())] == c["partial_writes"]
    assert got[("send_writer_wait_seconds_total", ())] == \
        pytest.approx(c["writer_wait_ns"] / 1e9)
    assert got[("send_writer_batches_total", ())] == c["writer_batches"]
    assert got[("send_writer_backlog_bytes", ())] == c["writer_backlog"]
    # The pump gives the lock up nowhere: nothing counts a release.
    assert not {name for name, _ in got} & {
        "send_pump_releases_total", "send_pump_relock_seconds_total"}


# ---------------------------------------------------------------------------
# each thread's CPU and run-queue time, read at scrape
# ---------------------------------------------------------------------------


def cpu_of(name: str) -> float:
    return scrape().get(("thread_cpu_seconds_total", (("thread", name),)),
                        0.0)


def run_noted(name: str, seconds: float, spin: bool) -> float:
    """A thread noted as ``name`` that spins (or sleeps) ``seconds``;
    its CPU seconds as read while it still ran."""
    read = []
    go = threading.Event()

    def body():
        metrics.thread_clocks.note(name)
        end = time.monotonic() + seconds
        while time.monotonic() < end:
            if not spin:
                time.sleep(0.01)
        read.append(cpu_of(name))
        go.wait(10)

    t = threading.Thread(target=body)
    t.start()
    try:
        while not read and t.is_alive():
            time.sleep(0.01)
    finally:
        go.set()
        t.join(10)
    assert not t.is_alive()
    return read[0]


def test_a_spinning_threads_cpu_counter_rises():
    spun = run_noted("test_spin", 0.3, spin=True)
    assert spun >= 0.03  # a tenth of what it spun, on a loaded host
    slept = run_noted("test_sleep", 0.3, spin=False)
    assert slept < 0.15


def test_a_thread_that_takes_a_name_starts_from_its_predecessor():
    first = run_noted("test_worker", 0.2, spin=True)
    assert cpu_of("test_worker") >= first  # gone: its last read stands
    second = run_noted("test_worker", 0.2, spin=True)
    assert second >= first + 0.02
    assert cpu_of("test_worker") >= second


def test_run_queue_time_where_the_kernel_gives_it():
    metrics.thread_clocks.note("test_main")
    got = scrape()
    assert got[("thread_cpu_seconds_total", (("thread", "test_main"),))] > 0
    try:
        with open(f"/proc/self/task/{threading.get_native_id()}"
                  "/schedstat") as f:
            run_ns = int(f.read().split()[0])
    except OSError:
        run_ns = 0
    key = ("thread_runqueue_seconds_total", (("thread", "test_main"),))
    assert (key in got) == bool(run_ns)
    if run_ns:
        assert got[key] >= 0


def test_the_device_worker_notes_itself_before_its_first_job():
    guard = DeviceGuard()
    try:
        ident = guard._executor().submit(threading.get_ident).result(10)
        assert metrics.thread_clocks._threads["device_worker"][1] == ident
        assert ("thread_cpu_seconds_total",
                (("thread", "device_worker"),)) in scrape()
    finally:
        guard.shutdown()


# ---------------------------------------------------------------------------
# ingest and stash retry on the device trace's clock
# ---------------------------------------------------------------------------


class Reader:
    """A connection with one deferred ingest run, or a stash to retry."""

    def __init__(self):
        self.flushed = 0

    def is_closing(self):
        return False

    def flush_ingest(self):
        self.flushed += 1

    def has_pending(self):
        return False

    def pending_head_channel(self):
        return None

    def flush_pending(self):
        self.flushed += 1
        return True


def test_ingest_and_stash_retry_are_regions_while_profiling(profiling,
                                                            monkeypatch):
    ingest, stashed = Reader(), Reader()
    monkeypatch.setattr(connection_mod, "_pending_ingest", {ingest})
    monkeypatch.setattr(connection_mod, "_stash_retry", {stashed: None})
    before = [stage_count("ingest"), stage_count("stash_retry")]
    connection_mod.flush_pending_ingest()
    assert (ingest.flushed, stashed.flushed) == (1, 1)
    assert not connection_mod._stash_retry
    # One observation each either way: ingest_busy_pct reads the same.
    assert [stage_count("ingest"), stage_count("stash_retry")] == [
        before[0] + 1, before[1] + 1]
    assert Annotation.made == (
        ["channeld/stash_retry", "channeld/ingest"] if profiling else [])
    names = [s["name"] for s in tracing.recorder.snapshot()]
    assert "ingest" in names and "stash_retry" in names
