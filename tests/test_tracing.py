"""Flight-recorder tests (core/tracing.py; doc/observability.md):
ring-overflow semantics with exact drop accounting, span nesting under
concurrent per-channel tick tasks, trace-id round-trip over a REAL
trunk pair, the pinned Perfetto trace_event schema, the anomaly
auto-dump path, regions as annotations in a ``jax.profiler`` trace, and
the two wait counters (tick lateness, fan-out window lag)."""

import asyncio
import json
import os
import time

import pytest

from channeld_tpu.core import tracing
from channeld_tpu.core.tracing import recorder


@pytest.fixture(autouse=True)
def _fresh_recorder(tmp_path):
    recorder.configure(dump_path=str(tmp_path))
    yield
    recorder.reset()


# ---- ring semantics --------------------------------------------------------


def test_ring_overflow_keeps_newest_with_exact_drop_accounting():
    recorder.configure(ring_spans=64, dump_path=recorder.dump_path)
    for i in range(200):
        recorder.set_tick(i)
        recorder.span(f"s{i}", recorder.now())
    st = recorder.stats()
    assert st["spans"] == 64
    assert st["dropped"] == 200 - 64
    spans = recorder.snapshot()
    # The newest 64 survive, in order; everything older was overwritten.
    assert [s["name"] for s in spans] == [f"s{i}" for i in range(136, 200)]
    assert spans[0]["tick"] == 136 and spans[-1]["tick"] == 199


def test_ring_floor_and_last_ticks_filter():
    recorder.configure(ring_spans=16, dump_path=recorder.dump_path)
    for i in range(10):
        recorder.set_tick(i)
        recorder.span("s", recorder.now())
    assert len(recorder.snapshot(last_ticks=3)) == 3  # ticks 7, 8, 9
    assert {s["tick"] for s in recorder.snapshot(last_ticks=3)} == {7, 8, 9}


def test_disabled_recorder_records_nothing_but_histograms_move():
    from channeld_tpu.core import metrics

    recorder.configure(enabled=False, dump_path=recorder.dump_path)
    before = (
        metrics.tick_stage_ms.labels(stage="messages")._sum.get()
    )
    recorder.span("x", recorder.now())
    recorder.instant("y")
    recorder.stage("messages", recorder.now())
    assert recorder.stats()["spans"] == 0
    assert metrics.tick_stage_ms.labels(
        stage="messages")._sum.get() >= before


# ---- nesting under concurrent tick tasks -----------------------------------


def test_span_nesting_reconstructs_under_concurrent_tick_tasks():
    """N concurrent per-channel tick tasks interleave on one thread;
    lanes (channel ids) keep their spans apart, and within each lane
    every inner span lies inside its outer span — Perfetto's X-event
    containment is exactly how nesting is reconstructed."""

    async def scenario():
        async def channel_tick(lane: int):
            for _ in range(3):
                t_outer = recorder.now()
                t_inner = recorder.now()
                await asyncio.sleep(0)  # interleave with the other tasks
                recorder.span("messages", t_inner, lane=lane)
                t_inner2 = recorder.now()
                await asyncio.sleep(0)
                recorder.span("fanout", t_inner2, lane=lane)
                recorder.span("tick", t_outer, lane=lane)

        await asyncio.gather(*(channel_tick(lane) for lane in (7, 8, 9)))

    asyncio.run(scenario())
    spans = recorder.snapshot()
    for lane in (7, 8, 9):
        mine = [s for s in spans if s["lane"] == lane]
        ticks = [s for s in mine if s["name"] == "tick"]
        inner = [s for s in mine if s["name"] != "tick"]
        assert len(ticks) == 3 and len(inner) == 6
        for s in inner:
            assert any(
                t["start_ns"] <= s["start_ns"]
                and s["start_ns"] + s["dur_ns"]
                <= t["start_ns"] + t["dur_ns"]
                for t in ticks
            ), f"span {s} not contained in any tick span of lane {lane}"
    # Distinct lanes land on distinct trace_event rows.
    doc = recorder.to_trace_events(spans)
    tids = {e["tid"] for e in doc["traceEvents"]}
    assert len(tids) == 3


# ---- the pinned Perfetto schema --------------------------------------------


def _check_trace_doc(doc: dict) -> None:
    """The committed trace_event contract: what ui.perfetto.dev and
    chrome://tracing actually require. A drift here silently breaks
    every dump, so the schema is pinned."""
    assert set(doc) >= {"traceEvents", "displayTimeUnit", "otherData"}
    assert doc["displayTimeUnit"] in ("ms", "ns")
    assert isinstance(doc["traceEvents"], list)
    for ev in doc["traceEvents"]:
        assert set(ev) >= {"name", "ph", "ts", "pid", "tid", "args"}
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert "tick" in ev["args"]
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        else:
            assert ev["s"] in ("t", "p", "g")


def test_dump_trace_validates_against_pinned_schema(tmp_path):
    t0 = recorder.now()
    recorder.set_tick(5)
    recorder.stage("messages", t0, lane=3)
    recorder.instant("fed.redirect", trace="a-1-1")
    path = recorder.dump_trace(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    _check_trace_doc(doc)
    assert len(doc["traceEvents"]) == 2
    traced = [e for e in doc["traceEvents"]
              if e["args"].get("trace") == "a-1-1"]
    assert len(traced) == 1


def test_anomaly_freezes_last_ticks_and_counts(tmp_path):
    from channeld_tpu.core import metrics

    recorder.configure(dump_ticks=4, dump_path=str(tmp_path),
                       anomaly_cooldown_s=0.0)
    for i in range(10):
        recorder.set_tick(i)
        recorder.span("tick", recorder.now())
    before = metrics.trace_dumps.labels(
        trigger="tick_budget")._value.get()
    path = recorder.note_anomaly("tick_budget", "test blow")
    assert path is not None
    assert metrics.trace_dumps.labels(
        trigger="tick_budget")._value.get() == before + 1
    # The JSON write is off-thread; wait until it parses (a file that
    # merely EXISTS may still be mid-write), bounded.
    import time

    doc = None
    deadline = time.monotonic() + 5.0
    while doc is None:
        try:
            doc = json.load(open(path))
        except (OSError, ValueError):
            assert time.monotonic() < deadline, f"dump never completed: {path}"
            time.sleep(0.02)
    _check_trace_doc(doc)
    assert doc["otherData"]["trigger"] == "tick_budget"
    # Only the last 4 ticks were frozen.
    assert {e["args"]["tick"] for e in doc["traceEvents"]} == {6, 7, 8, 9}
    # Cooldown: a second anomaly right away is counted but not dumped.
    recorder.anomaly_cooldown_s = 60.0
    assert recorder.note_anomaly("tick_budget", "again") is None
    assert metrics.trace_dumps.labels(
        trigger="tick_budget")._value.get() == before + 2


def test_anomaly_formats_off_thread_and_the_dump_is_unchanged(
        tmp_path, monkeypatch):
    """The thread that trips an anomaly pays for the raw ring copy and
    nothing else (the ``trace_freeze`` stage): the tick filter, the sort
    and the JSON are the dumper thread's, and no span dict is built on
    either. The file is byte for byte what ``json.dump`` of the
    snapshot's ``trace_event`` object gave before."""
    import threading
    import time

    from channeld_tpu.core import metrics

    recorder.configure(dump_ticks=4, dump_path=str(tmp_path),
                       anomaly_cooldown_s=0.0, origin="gw-a")
    for i in range(10):
        recorder.set_tick(i)
        recorder.span("tick", recorder.now(), lane=i % 3)
        recorder.instant("mark", trace="a-1-1")
    want = recorder.to_trace_events(recorder.snapshot(4))
    want["otherData"]["trigger"] = "tick_budget"
    want["otherData"]["detail"] = "test blow"
    rendered_on: list = []
    render, snapshot = recorder._render, recorder.snapshot

    def watched(*args):
        rendered_on.append(threading.current_thread().name)
        return render(*args)

    def no_dicts(*args):
        raise AssertionError("an anomaly builds no span dicts")

    monkeypatch.setattr(recorder, "_render", watched)
    monkeypatch.setattr(recorder, "snapshot", no_dicts)
    freezes = metrics.tick_stage_ms.labels(stage="trace_freeze")
    before = freezes._sum.get()
    path = recorder.note_anomaly("tick_budget", "test blow")
    assert freezes._sum.get() > before
    deadline = time.monotonic() + 5.0
    while not rendered_on or not os.path.exists(path) \
            or not os.path.getsize(path):
        assert time.monotonic() < deadline, f"dump never completed: {path}"
        time.sleep(0.02)
    assert rendered_on == ["trace-dump-tick_budget"]
    assert open(path).read() == json.dumps(want)
    # The manual dump writes the same format through the same code.
    monkeypatch.setattr(recorder, "snapshot", snapshot)
    want = recorder.to_trace_events(recorder.snapshot())
    want["otherData"]["trigger"] = "manual"
    manual = recorder.dump_trace(str(tmp_path / "manual.json"))
    assert open(manual).read() == json.dumps(want)


# ---- regions: one API, two sinks ---------------------------------------------


def test_region_records_span_and_stage_and_nests_by_containment():
    from channeld_tpu.core import metrics

    child = metrics.tick_stage_ms.labels(stage="publish_due")
    count, total = child._buckets[-1].get(), child._sum.get()
    with recorder.region("tick.GLOBAL", lane=7):
        with recorder.region("publish_due", stage=True):
            pass
        with recorder.region("plain"):
            pass
    # A stage observes its histogram; a plain region is a span only.
    assert sum(b.get() for b in child._buckets) >= count + 1
    assert child._sum.get() >= total
    spans = {s["name"]: s for s in recorder.snapshot()}
    assert set(spans) == {"tick.GLOBAL", "publish_due", "plain"}
    outer = spans["tick.GLOBAL"]
    assert outer["lane"] == 7
    for inner in (spans["publish_due"], spans["plain"]):
        assert outer["start_ns"] <= inner["start_ns"]
        assert (inner["start_ns"] + inner["dur_ns"]
                <= outer["start_ns"] + outer["dur_ns"])
    # A discarded region leaves neither span nor observation.
    held = metrics.tick_stage_ms.labels(stage="device_step")
    before = sum(b.get() for b in held._buckets)
    with recorder.region("device_step", stage=True) as step:
        step.discard()
    assert sum(b.get() for b in held._buckets) == before
    assert "device_step" not in {s["name"] for s in recorder.snapshot()}


def _tpu_world_with_entity():
    """2x1 TPU world, two spatial servers, one entity (the device
    guard's own test world)."""
    import test_device_guard as tdg

    tdg.register_sim_types()
    ctl, (sa, _sb) = tdg.make_tpu_world()
    tdg.add_entity(ctl, sa, tdg.ENTITY_START + 1, 50, 50)
    return ctl


def _host_lines(trace_dir) -> list:
    """``[{annotation name: [(start_ns, end_ns)]}]``, one for each line
    of the trace's host plane that holds a ``channeld/`` event."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            found: dict = {}
            for e in line.events:
                if e.name.startswith("channeld/"):
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
            if found:
                lines.append(found)
    return lines


def test_profile_tpu_lays_the_spans_beside_the_device_on_one_clock(tmp_path):
    """``-profile tpu`` opens its trace through core/tracing.py, and
    while it is live the recorder's regions are ``channeld/<span>``
    annotations stamped by the profiler itself: the GLOBAL tick and the
    step's begin half on the loop thread's line, the step's flush,
    dispatch and fetch on the device worker's, inside the tick's
    interval. ``device_step`` itself spans the wait between the halves
    and is recorded after the fact: no annotation carries it."""
    import signal

    from channeld_tpu.core import profiling
    from helpers import fresh_runtime

    gch = fresh_runtime()
    _tpu_world_with_entity()
    handlers = {s: signal.getsignal(s) for s in (signal.SIGINT,
                                                 signal.SIGTERM)}
    try:
        profiling.start_profiling("tpu", str(tmp_path))
        for _ in range(4):
            gch.tick_once(gch.get_time())
        assert recorder.profiling
        trace_dir = profiling.stop_profiling()
    finally:
        for sig, handler in handlers.items():
            signal.signal(sig, handler)
    assert trace_dir == os.path.join(str(tmp_path), "tpu_trace")
    gch.tick_once(gch.get_time())
    assert not recorder.profiling  # follows the session, whoever holds it

    lines = _host_lines(trace_dir)
    (loop,) = [ln for ln in lines if "channeld/tick.GLOBAL" in ln]
    (worker,) = [ln for ln in lines if "channeld/step.flush" in ln]
    assert loop is not worker
    # The GLOBAL tick learns of the session before its own region
    # opens: the first traced tick is in the trace whole.
    assert len(loop["channeld/tick.GLOBAL"]) == 4
    assert "channeld/device_step" not in loop
    assert len(loop["channeld/step.begin"]) == 4
    for s0, s1 in loop["channeld/step.begin"]:
        assert any(t0 <= s0 and s1 <= t1
                   for t0, t1 in loop["channeld/tick.GLOBAL"])
    # A direct tick_once() blocks between the halves: one tick.GLOBAL
    # holds the whole step.
    steps = loop["channeld/tick.GLOBAL"]
    assert "channeld/publish_due" in loop
    for name in ("channeld/step.flush", "channeld/step.dispatch",
                 "channeld/step.fetch"):
        assert name not in loop
        assert len(worker[name]) == 4
        for w0, w1 in worker[name]:
            assert any(s0 <= w0 and w1 <= s1 for s0, s1 in steps), name
    for (f0, f1), (d0, d1), (r0, r1) in zip(
            worker["channeld/step.flush"], worker["channeld/step.dispatch"],
            worker["channeld/step.fetch"]):
        assert f1 <= d0 and d1 <= r0  # flush, then dispatch, then fetch


def test_no_annotation_is_made_without_a_profiler_session(monkeypatch):
    from helpers import fresh_runtime

    made: list = []

    class Watched:
        def __init__(self, name):
            made.append(name)

        @staticmethod
        def is_enabled():
            return False

    monkeypatch.setattr(tracing, "_annotation", Watched)
    gch = fresh_runtime()
    _tpu_world_with_entity()
    for _ in range(3):
        gch.tick_once(gch.get_time())
    with recorder.region("publish_due", stage=True):
        pass
    assert not recorder.profiling
    assert made == []
    names = {s["name"] for s in recorder.snapshot()}
    assert {"tick.GLOBAL", "device_step", "step.flush", "step.dispatch",
            "step.fetch", "publish_due"} <= names


def test_no_annotation_is_open_while_the_global_task_awaits(monkeypatch):
    """Annotations are per thread and nest by containment: one held
    across the GLOBAL tick's await would take every other channel's
    ``tick.*`` span for its own and spoil the device's idle shares
    (benchmark/harness/host_spans.py). With a profiler session faked
    live: nothing is open on the loop thread while the step is in
    flight, the tick shows as two ``tick.GLOBAL`` spans around the
    await, and every thread exits what it entered last."""
    import threading

    import test_step_await as tsa

    stacks: dict = {}
    closed: list = []
    torn: list = []
    on_closed = [None]  # the scenario's hook, called as a span closes

    class Recorded:
        def __init__(self, name):
            self.name = name

        @staticmethod
        def is_enabled():
            return True

        def __enter__(self):
            stacks.setdefault(threading.get_ident(), []).append(self.name)
            return self

        def __exit__(self, *exc):
            stack = stacks[threading.get_ident()]
            if stack[-1] != self.name:
                torn.append((self.name, list(stack)))
            stack.remove(self.name)
            closed.append((threading.get_ident(), self.name))
            if on_closed[0] is not None:
                on_closed[0](self.name)

    monkeypatch.setattr(tracing, "_annotation", Recorded)
    loop_thread = threading.get_ident()

    def global_spans() -> int:
        return sum(1 for t, n in closed
                   if t == loop_thread and n == "channeld/tick.GLOBAL")

    async def scenario():
        gch = tsa.new_runtime()
        ctl, _servers = tsa.world_with_entity()
        held = tsa.Held(ctl.engine)
        await tsa.until(held.entered.is_set)
        assert recorder.profiling
        ticks = global_spans()
        awaits = tsa.stage_count("step.await")
        in_flight = []
        for _ in range(5):  # other tasks run; GLOBAL's is parked
            in_flight.append(list(stacks.get(loop_thread, ())))
            await asyncio.sleep(0)
        assert ctl._in_flight is not None
        assert in_flight == [[]] * 5
        assert "channeld/step.begin" in {n for _, n in closed}
        # The end is an event, not a count that moves when a tick
        # BEGINS: the close of the second span of a whole tick after the
        # one in flight. GLOBAL's task is stopped there, between two
        # ticks, with no step in flight and no span open on any thread.
        # (This test waited for ``tick_frames >= 3``, true from the
        # start of the third tick on, and under load returned with that
        # tick's step still inside the worker's ``step.*`` spans.)
        finished = asyncio.Event()

        def tick_closed(name):
            if (name == "channeld/tick.GLOBAL"
                    and threading.get_ident() == loop_thread
                    and ctl._in_flight is None
                    and global_spans() >= ticks + 3):
                on_closed[0] = None
                gch._tick_task.cancel()
                finished.set()

        on_closed[0] = tick_closed
        held.release.set()
        await asyncio.wait_for(finished.wait(), 30.0)
        assert tsa.stage_count("step.await") >= awaits + 2
        return ticks

    before = asyncio.run(scenario())
    assert torn == []
    assert all(not stack for stack in stacks.values())
    names = [n for t, n in closed if t == loop_thread]
    # The tick in flight had closed its first tick.GLOBAL before the
    # await; each awaited tick closes two.
    assert names.count("channeld/tick.GLOBAL") >= before + 2
    assert "channeld/device_step" not in names
    assert "channeld/step.await" not in names
    worker = {n for t, n in closed if t != loop_thread}
    assert {"channeld/step.flush", "channeld/step.dispatch",
            "channeld/step.fetch"} <= worker


def test_one_place_opens_a_device_trace():
    """``grep -rn start_trace channeld_tpu`` finds one call."""
    import glob

    hits = []
    for path in glob.glob(os.path.join(REPO, "channeld_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            hits += [path for line in f if "start_trace" in line]
    assert [os.path.relpath(p, REPO) for p in hits] == [
        os.path.join("channeld_tpu", "core", "tracing.py")]


# ---- the wait counters -------------------------------------------------------


def _sum_count(metric, channel_type: str) -> tuple:
    child = metric.labels(channel_type=channel_type)
    return child._sum.get(), child._count.get()


def test_tick_lateness_from_an_injected_clock():
    """A tick is late by its start minus the tick before it plus the
    interval, never by less than 0; the first tick and one that follows
    a park are late against nothing."""
    from channeld_tpu.core import channel as channel_mod
    from channeld_tpu.core import metrics
    from helpers import fresh_runtime

    gch = fresh_runtime()
    interval = gch.tick_interval
    assert interval > 0
    sum0, count0 = _sum_count(metrics.tick_late_ms, "GLOBAL")
    clock = 100.0
    gch._note_tick_start(clock, None)  # the first tick: late against nothing
    for late in (0.0, 0.030, -0.010):  # on time, 30 ms late, early
        due = clock + interval
        clock += interval + late
        gch._note_tick_start(clock, due)
    gch._note_tick_start(clock + 10.0, None)  # the loop had parked
    assert channel_mod._tick_late[gch.channel_type][1] == 3
    gch.tick_once(gch.get_time())  # the GLOBAL tick carries it to /metrics
    total, count = _sum_count(metrics.tick_late_ms, "GLOBAL")
    assert count - count0 == 3
    assert total - sum0 == pytest.approx(30.0, abs=1e-6)
    assert channel_mod._tick_late[gch.channel_type] == [0, 0]


def test_the_scheduler_counts_a_sample_a_tick_and_none_for_an_idle_channel():
    """The real task: a channel with no work is not ticked and adds no
    lateness sample; a channel that is sent messages adds one a tick,
    each against the instant its work was ready."""
    from channeld_tpu.core import channel as channel_mod
    from channeld_tpu.core import metrics
    from channeld_tpu.core.channel import create_channel
    from channeld_tpu.core.types import ChannelType
    from helpers import fresh_runtime

    async def scenario():
        fresh_runtime()
        idle = create_channel(ChannelType.SUBWORLD, None)
        busy = create_channel(ChannelType.PRIVATE, None)
        handled = []
        end = time.monotonic() + busy.tick_interval * 4 + 0.05
        while time.monotonic() < end:
            busy.execute(lambda ch: handled.append(ch.tick_frames))
            await asyncio.sleep(0.005)
        late = channel_mod._tick_late
        assert idle.tick_frames == 0 and busy.tick_frames >= 3
        assert len(set(handled)) == busy.tick_frames
        # What the GLOBAL tick has not carried to /metrics yet.
        return (late[ChannelType.SUBWORLD][1], late[ChannelType.PRIVATE][1],
                busy.tick_frames)

    counts0 = [_sum_count(metrics.tick_late_ms, t)[1]
               for t in ("SUBWORLD", "PRIVATE")]
    parked, running, frames = asyncio.run(scenario())
    flushed = [_sum_count(metrics.tick_late_ms, t)[1] - c0
               for t, c0 in zip(("SUBWORLD", "PRIVATE"), counts0)]
    assert parked + flushed[0] == 0
    assert running + flushed[1] == frames


def test_fanout_window_lag_of_a_subscription_served_late():
    """A subscription served two intervals after its window closed adds
    two intervals of lag; its first fan-out adds none."""
    from channeld_tpu.core import metrics
    from channeld_tpu.core.channel import create_channel
    from channeld_tpu.core.data import tick_data, window_lag_ns
    from channeld_tpu.core.subscription import subscribe_to_channel
    from channeld_tpu.core.types import ChannelType
    from channeld_tpu.models import sim_pb2
    from channeld_tpu.protocol import control_pb2
    from helpers import StubConnection, fresh_runtime

    gch = fresh_runtime()
    ch = create_channel(ChannelType.SUBWORLD, None)
    ch.init_data(sim_pb2.SimEntityChannelData(), None)
    opts = control_pb2.ChannelSubscriptionOptions(
        fanOutIntervalMs=100, fanOutDelayMs=0)
    conn = StubConnection(1)
    subscribe_to_channel(conn, ch, opts)
    (foc,) = ch.fan_out_queue
    ms = 1_000_000
    t0 = foc.last_fanout_time + 100 * ms
    tick_data(ch, t0)  # the first fan-out: the full state, no lag sample
    assert foc.had_first_fanout
    assert window_lag_ns[ChannelType.SUBWORLD] == [0, 0]
    tick_data(ch, foc.last_fanout_time + 50 * ms)  # not due: not served
    assert window_lag_ns[ChannelType.SUBWORLD] == [0, 0]
    due = foc.last_fanout_time + 100 * ms
    ch.data.on_update(sim_pb2.SimEntityChannelData(),
                      foc.last_fanout_time + 10 * ms, 7)
    tick_data(ch, due + 200 * ms)  # served two intervals late
    assert window_lag_ns[ChannelType.SUBWORLD] == [200 * ms, 1]
    assert len(conn.sent) == 2
    # The window that held the update moved on by one interval; the two
    # empty ones behind it will cost a subtraction, and no lag sample.
    assert foc.last_fanout_time == due
    tick_data(ch, due + 250 * ms)
    assert window_lag_ns[ChannelType.SUBWORLD] == [200 * ms, 1]
    assert foc.last_fanout_time == due + 200 * ms
    sum0, count0 = _sum_count(metrics.fanout_window_lag_ms, "SUBWORLD")
    gch.tick_once(gch.get_time())
    total, count = _sum_count(metrics.fanout_window_lag_ms, "SUBWORLD")
    assert (total - sum0, count - count0) == (pytest.approx(200.0), 1)


def test_hot_objects_keep_their_shared_keys():
    """CPython shares the attribute keys of a class's instances, and
    keeps their values inline, only up to 30 attributes. Past that every
    ``self.x`` of every tick is a full dict lookup on a dict six times
    the size: PR 25's first cut took Channel from 26 to 31 and the GLOBAL
    tick rate fell 4% on the chip (PERF.md). Bundle, do not add."""
    from channeld_tpu.core.connection import add_connection
    from channeld_tpu.core.types import ConnectionType
    from helpers import FakeTransport, fresh_runtime

    gch = fresh_runtime()
    assert len(vars(gch)) <= 30, sorted(vars(gch))
    conn = add_connection(FakeTransport(), ConnectionType.CLIENT)
    assert len(vars(conn)) <= 30, sorted(vars(conn))


def test_census_ticks_fit_the_histograms_buckets():
    """The census ticks (68-158 ms on the chip) sat past the last finite
    bucket of both families."""
    from channeld_tpu.core import metrics

    assert metrics.tick_stage_ms._kwargs["buckets"][-3:] == (
        200.0, 500.0, 1000.0)
    assert metrics.tpu_step_latency._upper_bounds[-3:-1] == [0.2, 0.5]


# ---- tick stamping from the channel plane ----------------------------------


def test_global_tick_stamps_spans():
    from helpers import fresh_runtime

    gch = fresh_runtime()
    recorder.configure(dump_path=recorder.dump_path)
    gch.tick_once(gch.get_time())
    gch.tick_once(gch.get_time())
    assert recorder.tick == gch.tick_frames
    spans = recorder.snapshot()
    assert any(s["name"] == "tick.GLOBAL" for s in spans)


# ---- trace-id round-trip over a real trunk pair ----------------------------


def test_trace_id_round_trips_over_real_trunk_pair():
    """Two TrunkManagers on real sockets: gateway a sends a handover
    prepare carrying a trace id, b receives it intact and echoes it in
    the ack — the wire contract that lets one trace id stitch spans
    from both gateways' recorders."""
    import socket

    from channeld_tpu.core.types import MessageType
    from channeld_tpu.federation.directory import ShardDirectory
    from channeld_tpu.federation.trunk import TrunkManager
    from channeld_tpu.protocol import control_pb2

    socks, ports = [], []
    for _ in range(2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    cfg = {
        "secret": "trace-test",
        "gateways": {
            "a": {"trunk": f"127.0.0.1:{ports[0]}", "servers": [0]},
            "b": {"trunk": f"127.0.0.1:{ports[1]}", "servers": [1]},
        },
    }

    async def scenario():
        dir_a, dir_b = ShardDirectory(), ShardDirectory()
        dir_a.load_dict(cfg, "a")
        dir_b.load_dict(cfg, "b")
        got_b: list = []
        got_a: list = []

        def on_msg_b(peer, msg_type, msg):
            got_b.append((peer, msg_type, msg))
            if msg_type == MessageType.TRUNK_HANDOVER_PREPARE:
                mgr_b.links[peer].send(
                    MessageType.TRUNK_HANDOVER_ACK,
                    control_pb2.TrunkHandoverAckMessage(
                        batchId=msg.batchId, committed=True,
                        traceId=msg.traceId,
                    ),
                )

        mgr_a = TrunkManager(dir_a, lambda p, t, m: got_a.append((p, t, m)),
                             lambda p, l: None, lambda p, l: None)
        mgr_b = TrunkManager(dir_b, on_msg_b,
                             lambda p, l: None, lambda p, l: None)
        try:
            await mgr_b.start()
            await mgr_a.start()
            for _ in range(200):
                link = mgr_a.links.get("b")
                if link is not None and link.alive:
                    break
                await asyncio.sleep(0.02)
            else:
                raise TimeoutError("trunk a<->b never came up")
            trace_id = tracing.new_trace_id("a")
            link.send(
                MessageType.TRUNK_HANDOVER_PREPARE,
                control_pb2.TrunkHandoverPrepareMessage(
                    batchId=11, srcChannelId=1, dstChannelId=2,
                    traceId=trace_id,
                ),
            )
            for _ in range(200):
                if any(t == MessageType.TRUNK_HANDOVER_ACK
                       for _, t, _m in got_a):
                    break
                await asyncio.sleep(0.02)
            else:
                raise TimeoutError("ack never arrived")
            return trace_id, got_b, got_a
        finally:
            mgr_a.stop()
            mgr_b.stop()

    trace_id, got_b, got_a = asyncio.run(scenario())
    prepares = [m for _, t, m in got_b
                if t == MessageType.TRUNK_HANDOVER_PREPARE]
    assert len(prepares) == 1
    assert prepares[0].traceId == trace_id  # survived the wire a -> b
    acks = [m for _, t, m in got_a
            if t == MessageType.TRUNK_HANDOVER_ACK]
    assert len(acks) == 1
    assert acks[0].traceId == trace_id  # echoed back b -> a
    assert acks[0].committed


# ---- the trace soak (smoke in tier-1; full run is slow) --------------------


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _trace_soak_module():
    import sys

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import trace_soak

    return trace_soak


def test_trace_soak_smoke():
    """Live-gateway phase + overhead phase with smoke-sized numbers:
    every per-stage budget measured, at least one anomaly dump frozen
    and Perfetto-valid (the federation phase has its own 2-process
    smoke in the slow soak; trace-id propagation is covered above)."""
    ts = _trace_soak_module()
    p = ts.TraceSoakParams(
        live_s=6.0, clients=6, msg_rate=25, entities=60, followers=2,
        storm_size=20, quiesce_s=2.0, overhead_ticks=40,
        overhead_rounds=2, skip_federation=True,
    )

    async def run(tmp):
        return await ts.run_live_phase(p, tmp)

    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        live = asyncio.run(run(tmp))
    for stage in ("ingest", "messages", "device_step", "readback",
                  "follow_interests", "overload"):
        assert stage in live["stages"], (stage, sorted(live["stages"]))
        assert live["stages"][stage]["count"] > 0
    assert live["follower_readbacks_total"] > 0
    dumped = [d for d in live["anomaly_dumps"] if d["trigger"] ==
              "tick_budget"]
    assert dumped and all(d["perfetto_valid"] for d in dumped)
    overhead = ts.run_overhead_phase(p)
    assert overhead["tick_ns_disabled"] > 0
    assert overhead["span_cost_ns"] > 0


@pytest.mark.slow
def test_trace_soak_full():
    """The acceptance soak (TRACE_r11.json form), federation included."""
    ts = _trace_soak_module()
    p = ts.TraceSoakParams(live_s=15.0)
    report = asyncio.run(ts.run_trace_soak(p))
    assert report["invariants"]["ok"], report["invariants"]


def test_trace_artifact_schema():
    """TRACE_r11.json stays parseable with the keys its acceptance
    claims cite (scripts/check_artifacts.py pins the same shape)."""
    path = os.path.join(REPO, "TRACE_r11.json")
    doc = json.load(open(path))
    assert doc["kind"] == "trace_soak"
    assert doc["invariants"]["ok"] is True
    for stage in ("ingest", "messages", "fanout", "device_step",
                  "readback", "follow_interests", "handover", "overload",
                  "trunk"):
        assert doc["stages"][stage]["count"] > 0
    assert doc["overhead"]["overhead_pct"] < 3.0
    assert doc["cross_gateway"]["stitched_traces"] > 0
    ex = doc["cross_gateway"]["example"]
    assert "fed.prepare" in ex["a_spans"] and "fed.apply" in ex["b_spans"]
    assert any(d["trigger"] == "tick_budget" and d["perfetto_valid"]
               for d in doc["anomaly_dumps"])
    assert any(d["trigger"] == "handover_abort" and d["perfetto_valid"]
               for d in doc["anomaly_dumps"])


def test_stage_redirect_carries_trace_id_on_the_wire():
    from channeld_tpu.protocol import control_pb2

    msg = control_pb2.TrunkStageRedirectMessage(
        pit="p1", entityId=9, channelIds=[1, 2], token="t",
        traceId="a-77-1",
    )
    rt = control_pb2.TrunkStageRedirectMessage()
    rt.ParseFromString(msg.SerializeToString())
    assert rt.traceId == "a-77-1"
    # Old-wire compat: a prepare without the field parses to "".
    old = control_pb2.TrunkHandoverPrepareMessage(batchId=1)
    rt2 = control_pb2.TrunkHandoverPrepareMessage()
    rt2.ParseFromString(old.SerializeToString())
    assert rt2.traceId == ""
