"""The readers PR 38 added, on a hand-made ``ctx``: where the loop
thread's wall time goes (the codec's counters in a ``/metrics`` delta), and the device's idle time
under the send pump's pass and the loop's wait (``split_idle`` over
hand-made spans). A value where the counter or span is there; ``None``,
never 0, where it is not (the parent)."""

import os

import pytest

from benchmark.harness import driver, host_spans

HERE = os.path.dirname(os.path.abspath(__file__))
BASE = os.path.dirname(HERE)
MS = 1_000_000  # ns


def reader(name):
    return driver.load_file(os.path.join(BASE, "layer_metrics", name + ".py"),
                            "layer_metric_" + name)


def sample(name, value, **labels):
    return {(name, tuple(sorted(labels.items()))): value}


def stage(name, total_ms, count):
    return {**sample("tick_stage_ms_sum", total_ms, stage=name),
            **sample("tick_stage_ms_count", count, stage=name)}


STAGES = {**stage("device_step", 3280.0, 200), **stage("step.flush", 100.0, 200),
          **stage("step.dispatch", 500.0, 200),
          **stage("step.fetch", 2400.0, 200),
          **stage("step.census_fetch", 120.0, 4)}
# A window of 20 s: the loop thread 9 s on the CPU, 1 s runnable, 6 s
# asleep in 10,000 waits that took 0.25 s to win the lock back; the send
# writer 4 s in its sends; the worker 1.2 s on the CPU and 0.2 s runnable
# (operators' counters, which no reader takes).
COUNTERS = {
    **sample("thread_cpu_seconds_total", 9.0, thread="loop"),
    **sample("thread_runqueue_seconds_total", 1.0, thread="loop"),
    **sample("thread_cpu_seconds_total", 1.2, thread="device_worker"),
    **sample("thread_runqueue_seconds_total", 0.2, thread="device_worker"),
    **sample("loop_poll_seconds_total", 6.0, part="asleep"),
    **sample("loop_poll_seconds_total", 0.25, part="relock"),
    **sample("loop_polls_total", 10_000),
    **sample("send_pump_write_seconds_total", 4.0),
}
EXPECTED = {
    "loop_asleep_pct": 30.0,            # 6 s of 20
    "loop_lock_wait_pct": 1.25,         # 0.25 s of 20
    "loop_wake_relock_us": 25.0,        # 0.25 s over 10,000
    "send_pump_write_pct": 20.0,        # 4 s of 20
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_made_ctx(name):
    read = reader(name).read
    ctx = {"metrics": {**STAGES, **COUNTERS}, "wall_s": 20.0, "trace": None,
           "base": BASE}
    assert read(ctx) == pytest.approx(EXPECTED[name])
    # The parent's /metrics has none of these counters: left out of the
    # line, never 0.
    assert read(dict(ctx, metrics=STAGES)) is None
    assert read(dict(ctx, metrics={})) is None


def test_the_lock_wait_is_the_timed_relock():
    """The loop's lock wait is what its timed release site reads: the
    wait's relock a wait times the waits. (The send pump's pass, whose
    relock was the other site, gives the lock up nowhere since its sends
    moved to the codec's writer thread.)"""
    ctx = {"metrics": {**STAGES, **COUNTERS}, "wall_s": 20.0}
    relock_s = reader("loop_wake_relock_us").read(ctx) * 1e-6 * 10_000
    assert reader("loop_lock_wait_pct").read(ctx) == \
        pytest.approx(100.0 * relock_s / 20.0)


def test_without_thread_clocks_the_lock_wait_is_still_read():
    """A kernel that gives no schedstat, and a CPU clock that is not to
    be trusted (the chip's gVisor sandbox): the lock wait needs neither."""
    counters = {k: v for k, v in COUNTERS.items()
                if not k[0].startswith("thread_")}
    ctx = {"metrics": {**STAGES, **counters}, "wall_s": 20.0}
    assert reader("loop_lock_wait_pct").read(ctx) == pytest.approx(1.25)


# ---- the device's idle time under the pump and the wait -------------------

LOOP, WORKER = 3, 5  # lines of the host plane
# A GLOBAL tick [0, 20), a pump pass [20, 30), the loop asleep [30, 60)
# and [70, 90), an ingest run [60, 70) and nothing after 90; the
# worker's fetch on another line.
HOST = [
    (LOOP, "channeld/tick.GLOBAL", 0, 20 * MS),
    (LOOP, "channeld/send_pump", 20 * MS, 30 * MS),
    (LOOP, "channeld/loop.poll", 30 * MS, 60 * MS),
    (LOOP, "channeld/ingest", 60 * MS, 70 * MS),
    (LOOP, "channeld/loop.poll", 70 * MS, 90 * MS),
    (WORKER, "channeld/step.fetch", 20 * MS, 40 * MS),
]
OPS = [(5 * MS, 10 * MS), (25 * MS, 27 * MS), (40 * MS, 50 * MS)]
WINDOW = (0, 100 * MS)


def test_split_idle_names_the_pump_and_the_wait():
    split = host_spans.split_idle(OPS, HOST, WINDOW)
    by = split["by_span"]
    assert by["channeld/send_pump"] == pytest.approx(0.008)   # 10 less 2
    assert by["channeld/loop.poll"] == pytest.approx(0.040)   # 50 less 10
    assert by["channeld/ingest"] == pytest.approx(0.010)
    # Neither lies inside a tick: both are in the unspanned share.
    assert split["unspanned_s"] == pytest.approx(0.068)
    assert split["unspanned_s"] >= (by["channeld/send_pump"]
                                    + by["channeld/loop.poll"])


IDLE = {"idle_send_pump_pct": 8.0 / 3.0, "idle_loop_asleep_pct": 40.0 / 3.0}


@pytest.mark.parametrize("name", sorted(IDLE))
def test_idle_share_readers(name, tmp_path, monkeypatch):
    read = reader(name).read
    assert read({"trace": None, "base": str(tmp_path)}) is None
    traced = {"trace": {"window_s": 0.3, "busy_s": 0.017},
              "base": str(tmp_path)}
    assert read(traced) is None  # traced, but no trace file
    where = tmp_path / "out" / "cell" / "trace" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    path = str(where / "vm.xplane.pb")
    (where / "vm.xplane.pb").write_bytes(b"")
    split = host_spans.split_idle(OPS, HOST, WINDOW)
    split["window_s"] = 0.1
    monkeypatch.setitem(host_spans._read, path, split)
    # The same window device_idle_pct and idle_unspanned_pct take.
    assert read(traced) == pytest.approx(IDLE[name])
    unspanned = reader("idle_unspanned_pct").read(traced)
    assert sum(reader(n).read(traced) for n in IDLE) <= unspanned
    # A program older than the span (the parent has no loop.poll): None.
    older = host_spans.split_idle(
        OPS, [s for s in HOST if s[1] != "channeld/" + (
            "send_pump" if name == "idle_send_pump_pct" else "loop.poll")],
        WINDOW)
    monkeypatch.setitem(host_spans._read, path, older)
    assert read(traced) is None
    monkeypatch.setitem(host_spans._read, path, {"host_spans": 0})
    assert read(traced) is None
