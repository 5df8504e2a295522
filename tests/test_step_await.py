"""The GLOBAL tick awaits the device step (core/channel.py
``_tick_global``, spatial/tpu_controller.py ``begin_tick`` /
``finish_tick``, core/device_guard.py ``begin_step`` / ``finish_step``,
ops/engine.py ``stage_step`` / ``run_staged``): while the guard's worker
and the device run a step the loop thread serves the other channels,
what the mutators write meanwhile reaches the device in the NEXT step
and never half-way into this one, the watchdog still fences and
abandons a hung step, and a direct ``tick_once()`` makes the same
decisions tick for tick.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

import channeld_tpu.core.channel as channel_mod
import channeld_tpu.core.connection as connection_mod
from channeld_tpu.chaos import arm, disarm
from channeld_tpu.core import metrics
from channeld_tpu.core.affinity import affinity
from channeld_tpu.core.channel import (
    all_channels,
    create_channel,
    get_channel,
)
from channeld_tpu.core.device_guard import DeviceState, guard
from channeld_tpu.core.message import MessageContext
from channeld_tpu.core.overload import governor
from channeld_tpu.core.settings import global_settings
from channeld_tpu.core.subscription import subscribe_to_channel
from channeld_tpu.core.tracing import recorder
from channeld_tpu.core.types import ChannelType, ConnectionType, MessageType
from channeld_tpu.models.sim import register_sim_types
from channeld_tpu.ops.spatial_ops import AOI_SPHERE
from channeld_tpu.protocol import control_pb2, wire_pb2
from channeld_tpu.spatial.controller import (
    SpatialInfo,
    set_spatial_controller,
)
from channeld_tpu.spatial.tpu_controller import TPUSpatialController

import test_device_guard as tdg
from helpers import StubConnection, fresh_runtime, stage_count

E = tdg.ENTITY_START


def new_runtime():
    """Called inside a running loop it starts the GLOBAL channel's own
    tick task."""
    gch = fresh_runtime()
    register_sim_types()
    return gch


@pytest.fixture(autouse=True)
def runtime():
    new_runtime()
    global_settings.development = True
    yield
    disarm()


class Held:
    """Holds the engine's device half (``run_staged``, on the guard's
    worker) until ``release`` is set: a step in flight for as long as the
    test wants one."""

    def __init__(self, engine):
        self.entered = threading.Event()
        self.release = threading.Event()
        self._run = engine.run_staged
        engine.run_staged = self._held

    def _held(self, batch):
        self.entered.set()
        assert self.release.wait(10.0)
        return self._run(batch)


def world_with_entity():
    ctl, servers = tdg.make_tpu_world()
    tdg.add_entity(ctl, servers[0], E + 1, 50, 50)
    return ctl, servers


async def until(pred, timeout=5.0):
    end = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < end, "timed out"
        await asyncio.sleep(0.002)


# ---- (a) the loop serves the other channels during the flight --------------


def test_another_channels_message_is_handled_while_the_step_is_in_flight():
    async def scenario():
        gch = new_runtime()
        ctl, _servers = world_with_entity()
        held = Held(ctl.engine)
        steps0 = stage_count("device_step")
        await until(held.entered.is_set)
        step = ctl._in_flight
        assert step is not None
        frames = gch.tick_frames
        other = create_channel(ChannelType.SUBWORLD, None)
        handled = []
        other.put_message(
            control_pb2.CreateChannelMessage(),
            lambda ctx: handled.append(ctl._in_flight is not None),
            None, wire_pb2.MessagePack(channelId=other.id, msgType=100))
        await until(lambda: handled)
        # Handled with the step still in flight, by a tick GLOBAL did
        # not make: its own tick is parked in the await.
        assert handled == [True]
        assert other.tick_frames >= 1
        assert gch.tick_frames == frames
        assert stage_count("device_step") == steps0
        held.release.set()
        await until(lambda: stage_count("device_step") >= steps0 + 1)
        assert ctl._in_flight is not step  # finished in the tick it began
        await until(lambda: gch.tick_frames > frames)

    asyncio.run(scenario())


# ---- (b) writes during the flight reach the NEXT step, whole ---------------


def test_writes_made_in_flight_reach_the_device_in_the_next_step():
    ctl, _servers = world_with_entity()
    eng = ctl.engine
    ctl.tick()
    client = StubConnection(9, ConnectionType.CLIENT)
    slot1 = eng.slot_of_entity(E + 1)
    eng.update_entity(E + 1, 60.0, 0.0, 60.0)  # dirty BEFORE the step
    held = Held(eng)
    step = ctl.begin_tick()
    assert held.entered.wait(5.0)
    batch = step.guarded.batch
    assert set(batch.entity[0]) == {slot1}

    # The loop thread, free during the flight, runs the mutators.
    ctl.track_entity(E + 2, SpatialInfo(150, 0, 50))
    ctl.observe_entity(E + 3, SpatialInfo(20, 0, 20))
    eng.update_entity(E + 1, 70.0, 0.0, 70.0)  # the row in flight, again
    sub = ctl.device_sub_add(100, 0, tdg.START)
    ctl.device_sub_first_fanout(sub)
    eng.set_query(client.id, AOI_SPHERE, (50.0, 50.0), (30.0, 0.0))
    slot2, slot3 = eng.slot_of_entity(E + 2), eng.slot_of_entity(E + 3)
    q = eng.query_row_of_conn(client.id)

    # None of it is in the batch the worker holds: its arrays are its
    # own, gathered at staging.
    assert set(batch.entity[0]) == {slot1}
    assert batch.entity[2][0].tolist() == [60.0, 0.0, 60.0]
    assert batch.queries is None and batch.sub_rows is None
    assert eng._dirty_slots == {slot1, slot2, slot3}
    assert eng._sub_dirty_slots == {sub} and eng._sub_last_dirty == {sub}

    held.release.set()
    guard.wait_step(step.guarded)
    ctl.finish_tick(step)
    valid = np.asarray(eng._d_valid)
    assert not valid[slot2] and not valid[slot3]
    # The row that was in flight went up as staged: not the later write,
    # not half of it.
    assert np.asarray(eng._d_positions)[slot1].tolist() == [60.0, 0.0, 60.0]
    assert not np.asarray(eng._d_sub_state[2])[sub]
    assert np.asarray(eng._d_queries.kind)[q] == 0
    assert eng._dirty_slots == {slot1, slot2, slot3}  # none lost

    ctl.tick()
    positions = np.asarray(eng._d_positions)
    valid = np.asarray(eng._d_valid)
    assert valid[slot2] and valid[slot3]
    assert positions[slot1].tolist() == [70.0, 0.0, 70.0]
    assert positions[slot2].tolist() == [150.0, 0.0, 50.0]
    assert positions[slot3].tolist() == [20.0, 0.0, 20.0]
    last, interval, active = (np.asarray(a) for a in eng._d_sub_state)
    assert active[sub] and interval[sub] == 100
    assert np.asarray(eng._d_queries.kind)[q] == AOI_SPHERE
    assert np.asarray(eng._d_queries.center)[q].tolist() == [50.0, 50.0]
    assert not eng._dirty_slots and not eng._sub_dirty_slots


def test_a_failed_step_hands_its_batch_back():
    """What a step that raised never committed goes back to the dirty
    sets; the retry carries it, newer writes winning."""
    ctl, _servers = world_with_entity()
    eng = ctl.engine
    ctl.tick()
    global_settings.device_retry_backoff_ms = 1
    slot1 = eng.slot_of_entity(E + 1)
    eng.update_entity(E + 1, 60.0, 0.0, 60.0)
    arm({"seed": 1, "faults": [
        {"point": "device.step_error", "every_n": 1, "max_fires": 1}]})
    ctl.tick()
    assert guard.state == DeviceState.DEGRADED
    assert eng._dirty_slots == {slot1}
    time.sleep(0.005)
    ctl.tick()
    assert guard.state == DeviceState.ACTIVE
    assert np.asarray(eng._d_positions)[slot1].tolist() == [60.0, 0.0, 60.0]
    assert not eng._dirty_slots


# ---- (b') owners that change in flight: the result says nothing of them ----


def _held_step(ctl):
    """Begin a tick and return (held, step) once the worker is in the
    device half: everything until ``_finish`` happens in flight."""
    held = Held(ctl.engine)
    step = ctl.begin_tick()
    assert step is not None and held.entered.wait(5.0)
    return held, step


def _finish(ctl, held, step) -> None:
    held.release.set()
    ctl.engine.run_staged = held._run
    guard.wait_step(step.guarded)
    ctl.finish_tick(step)


def _client(cid):
    client = StubConnection(cid, ConnectionType.CLIENT)
    connection_mod._all_connections[client.id] = client
    return client


def _tick_cells() -> None:
    for ch in list(all_channels().values()):
        if ch.channel_type == ChannelType.SPATIAL:
            ch.tick_once(0)


@pytest.mark.parametrize("queryplane", [True, False],
                         ids=["queryplane", "follower-readback"])
def test_a_follower_registered_in_flight_inherits_nothing_of_the_row(
        queryplane):
    """A's query row is freed during a step whose result carries A's
    delta (cell 0 left, cell 1 entered); B takes the row (LIFO) in the
    same flight, over cell 0 alone. B must end up with cell 0 and
    nothing of cell 1, on the plane's changed-rows path and on the
    legacy whole-mask readback alike."""
    global_settings.queryplane_enabled = queryplane
    ctl, servers = world_with_entity()
    tdg.add_entity(ctl, servers[0], E + 2, 40, 50)
    eng = ctl.engine
    a, b = _client(9), _client(10)
    ctl.register_follow_interest(a, E + 1, AOI_SPHERE, extent=(30.0, 0.0))
    row = eng.query_row_of_conn(a.id)
    for _ in range(2):
        ctl.tick()
        _tick_cells()
    assert set(a.spatial_subscriptions) == {tdg.START}
    # A's query moves to cell 1 for the step about to fly.
    eng.set_query(a.id, AOI_SPHERE, (150.0, 50.0), (30.0, 0.0))
    held, step = _held_step(ctl)
    ctl.unregister_follow_interest(a.id)
    ctl.register_follow_interest(b, E + 2, AOI_SPHERE, extent=(30.0, 0.0))
    assert eng.query_row_of_conn(b.id) == row
    assert step.guarded.batch.churn.queries == {row}
    _finish(ctl, held, step)
    _tick_cells()
    assert eng._flight is None
    if queryplane:
        assert not ctl.queryplane._mirror.get(row)
    assert b.spatial_subscriptions == {}  # no mask of its own yet
    for _ in range(2):
        ctl.tick()
        _tick_cells()
    assert set(b.spatial_subscriptions) == {tdg.START}
    if queryplane:  # (the legacy unregister leaves A's subs to A)
        assert a.spatial_subscriptions == {}
        assert set(ctl.queryplane._mirror[row]) == {0}


@pytest.mark.parametrize("reuse", [False, True], ids=["freed", "reused"])
def test_a_crossing_of_an_entity_untracked_in_flight_is_not_orchestrated(
        monkeypatch, reuse):
    from channeld_tpu.spatial.grid import StaticGrid2DSpatialController

    batches = []
    monkeypatch.setattr(
        StaticGrid2DSpatialController, "notify_crossings",
        lambda self, batch: batches.append(
            [provider(-1, -1) for _old, _new, provider in batch]))
    ctl, _servers = world_with_entity()
    eng = ctl.engine
    ctl.tick()
    slot = eng.slot_of_entity(E + 1)
    eng.update_entity(E + 1, 150.0, 0.0, 50.0)  # crosses in the flight
    held, step = _held_step(ctl)
    ctl.untrack_entity(E + 1)
    if reuse:
        ctl.track_entity(E + 2, SpatialInfo(20, 0, 20))
        assert eng.slot_of_entity(E + 2) == slot
    assert step.guarded.batch.churn.entities == {slot}
    _finish(ctl, held, step)
    # The step did see the crossing; nobody is told of it.
    result = eng.last_result
    assert int(result["handover_count"]) == 1
    assert int(result["handovers"][0][0]) == slot
    assert eng.handover_list(result) == []
    assert batches == [] and not ctl._deferred_crossings
    ctl.tick()
    assert batches == []  # the new owner starts from no baseline
    if reuse:
        # ...and is served from then on like any other.
        ctl.notify(SpatialInfo(20, 0, 20), SpatialInfo(150, 0, 20),
                   lambda s, d: E + 2)
        ctl.tick()
        assert batches == [[E + 2]]


def test_a_due_bit_of_a_sub_slot_freed_in_flight_reaches_no_channel():
    ctl, _servers = world_with_entity()
    sub = ctl.device_sub_add(20, 0, tdg.START)
    ctl.tick()
    time.sleep(0.03)
    ctl.tick()
    assert sub in ctl._due_pending[tdg.START]  # the slot does come due
    ctl._due_pending[tdg.START].pop(sub)
    time.sleep(0.03)
    held, step = _held_step(ctl)
    ctl.device_sub_remove(sub)
    sub2 = ctl.device_sub_add(60_000, 60_000, tdg.START + 1)
    assert sub2 == sub
    assert step.guarded.batch.churn.subs == {sub}
    _finish(ctl, held, step)
    due = np.unpackbits(np.asarray(ctl.engine.last_result["due_packed"]))
    assert due[sub]  # the old owner's window closed in the flight
    assert sub not in ctl._due_pending.get(tdg.START, {})
    assert sub not in ctl._due_pending.get(tdg.START + 1, {})
    ctl.tick()
    assert sub not in ctl._due_pending.get(tdg.START + 1, {})  # a minute off


def test_a_census_does_not_overwrite_an_agent_seeded_in_flight():
    import test_sim as tsim

    ctl, _server, _channels = tsim.make_world(agents=6, census=1)
    eng = ctl.engine
    for _ in range(3):
        ctl.tick()
    gone = int(eng.agent_ids()[0])
    slot = eng.slot_of_entity(gone)
    held, step = _held_step(ctl)
    ctl.untrack_entity(gone)
    new_id = gone + 1000
    eng.seed_agents([(new_id, 7.0, 0.0, 9.0)], eng.sim_seed, eng.sim_params,
                    vels=[(1.0, 0.0, 2.0)], states=[0],
                    targets=[(70.0, 0.0, 90.0)])
    assert eng.slot_of_entity(new_id) == slot
    _finish(ctl, held, step)
    assert "sim_census" in eng.last_result  # a census tick
    assert eng._positions[slot].tolist() == [7.0, 0.0, 9.0]
    assert eng._vel[slot].tolist() == [1.0, 0.0, 2.0]
    assert eng._sim_target[slot].tolist() == [70.0, 0.0, 90.0]
    assert slot in eng._sim_dirty and slot in eng._dirty_slots


def test_churn_is_recorded_only_while_a_step_is_in_flight():
    ctl, _servers = world_with_entity()
    eng = ctl.engine
    ctl.tick()
    assert eng._flight is None
    ctl.untrack_entity(E + 1)
    ctl.track_entity(E + 2, SpatialInfo(20, 0, 20))
    ctl.tick()
    assert "churn" not in eng.last_result
    held, step = _held_step(ctl)
    assert eng._flight is step.guarded.batch.churn
    _finish(ctl, held, step)
    assert eng._flight is None and "churn" not in eng.last_result


# ---- (c) the awaited path's deadline ---------------------------------------


def test_hang_on_the_task_path_fences_abandons_and_rebuilds():
    async def scenario():
        gch = new_runtime()
        ctl, _servers = world_with_entity()
        eng = ctl.engine
        await until(lambda: stage_count("device_step") >= 1)
        other = create_channel(ChannelType.SUBWORLD, None)
        subscribe_to_channel(StubConnection(7), other, None)
        global_settings.device_step_deadline_s = 0.08
        arm({"seed": 3, "faults": [
            {"point": "device.step_hang", "every_n": 1, "max_fires": 1,
             "stall_ms": 400}]})
        await until(lambda: ctl._in_flight is not None)
        step = ctl._in_flight.guarded
        gen0, pool0 = eng.generation, guard._pool
        frames0, other0 = gch.tick_frames, other.tick_frames
        other.execute(lambda ch: None)  # work for the scheduler meanwhile
        t0 = time.monotonic()
        await until(lambda: guard.failure_counts.get("hang"))
        waited = time.monotonic() - t0
        disarm()
        # The deadline, not the stall; and the loop ran meanwhile.
        assert waited < 0.3
        assert other.tick_frames > other0
        assert gch.tick_frames == frames0 + 1 or gch.tick_frames == frames0
        # Fence first, then abandon; the rebuild ran in the same finish.
        assert eng.generation > gen0
        assert guard._pool is not pool0
        assert guard.recovery_counts == {"hang": 1}
        assert guard.state == DeviceState.ACTIVE
        assert [e["to"] for e in guard.events if "to" in e][:2] == [
            "REBUILDING", "ACTIVE"]
        assert ctl._in_flight is None or ctl._in_flight.guarded is not step
        # The zombie wakes, sees the stale generation, commits nothing.
        await until(step.fut.done)
        assert "stale device tick" in str(step.fut.exception())
        steps = stage_count("device_step")
        await until(lambda: stage_count("device_step") > steps)  # serves
        assert guard.state == DeviceState.ACTIVE

    asyncio.run(scenario())


# ---- (d) what swaps device handles waits for the finish --------------------


def test_a_geometry_epoch_in_flight_rebuilds_after_the_finish():
    ctl, _servers = world_with_entity()
    eng = ctl.engine
    ctl.tick()
    rebuilds = metrics.partition_device_rebuilds.labels(result="verified")
    n0, gen0, epoch0 = rebuilds._value.get(), eng.generation, eng.query_epoch
    due0 = ctl._due_seq
    held = Held(eng)
    step = ctl.begin_tick()
    assert held.entered.wait(5.0)
    handles = (eng._d_positions, eng._d_cell)

    ctl.on_geometry_changed()  # a trunk's geometry sync, mid-flight
    assert ctl._geometry_deferred
    assert (eng._d_positions, eng._d_cell) == handles
    assert eng.generation == gen0 and rebuilds._value.get() == n0
    # The swap itself, asked for directly, is an assertion under -dev.
    assert affinity.violations == []
    eng.apply_grid(eng.grid, ctl.rebuild_seed_cells())
    assert [v["domain"] for v in affinity.violations] == [
        "apply_grid during a device step",
        "rebuild_device_state during a device step"]
    affinity.violations.clear()  # planted: not this test's failure
    gen1 = eng.generation

    held.release.set()
    guard.wait_step(step.guarded)
    ctl.finish_tick(step)
    # The rebuild ran in the finish; the step it superseded was not
    # consumed (its rows were in the old grid's indices).
    assert not ctl._geometry_deferred
    assert rebuilds._value.get() == n0 + 1
    assert eng.generation > gen1 and eng.query_epoch > epoch0
    assert ctl._due_seq == due0
    assert affinity.violations == []
    ctl.tick()
    assert ctl._due_seq == due0 + 1
    assert guard.state == DeviceState.ACTIVE


# ---- (e) the task path and the direct path decide alike --------------------


def _walker_world(clock, n=200):
    global_settings.tpu_entity_capacity = 256
    global_settings.tpu_query_capacity = 16
    global_settings.queryplane_enabled = True
    # The ladder moves with the host's speed, and with it the fan-out
    # intervals: not a decision either path makes.
    global_settings.overload_enabled = False
    ctl = TPUSpatialController()
    ctl.load_config(
        dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100, GridHeight=100,
             GridCols=4, GridRows=4, ServerCols=2, ServerRows=2,
             ServerInterestBorderSize=1))
    set_spatial_controller(ctl)
    # The engine's clock is the tick count: every window of every
    # subscription opens and closes alike on both paths.
    ctl.engine.now_ms = lambda: clock[0] * 50
    servers = []
    for i in range(1, 5):
        server = StubConnection(i, ConnectionType.SERVER)
        ctx = MessageContext(
            msg_type=MessageType.CREATE_CHANNEL,
            msg=control_pb2.CreateChannelMessage(), connection=server)
        for ch in ctl.create_channels(ctx):
            subscribe_to_channel(server, ch, None)
        servers.append(server)
    rng = np.random.default_rng(26)
    pos = rng.uniform(5.0, 395.0, (n, 2))
    for i in range(n):
        x, z = float(pos[i, 0]), float(pos[i, 1])
        cell = get_channel(ctl.get_channel_id(SpatialInfo(x, 0, z)))
        tdg.add_entity(ctl, cell.get_owner(), E + 1 + i, x, z)
    clients = []
    for c in range(4):
        client = StubConnection(100 + c, ConnectionType.CLIENT)
        connection_mod._all_connections[client.id] = client
        ctl.register_follow_interest(client, E + 1 + c, AOI_SPHERE,
                                     extent=(120.0, 0.0))
        clients.append(client)
    return ctl, rng, pos


def _walk(ctl, rng, pos) -> None:
    step = rng.uniform(-30.0, 30.0, pos.shape)
    new = np.clip(pos + step, 5.0, 395.0)
    for i in range(len(pos)):
        eid = E + 1 + i
        ctl.notify(SpatialInfo(float(pos[i, 0]), 0, float(pos[i, 1])),
                   SpatialInfo(float(new[i, 0]), 0, float(new[i, 1])),
                   lambda s, d, eid=eid: eid)
    pos[:] = new


def _record_decisions(ctl):
    """Keep, for each step, what the device decided."""
    seen = []
    publish = ctl._publish_due

    def recording(result):
        seen.append((
            sorted(ctl.engine.handover_list(result)),
            np.asarray(result["due_packed"]).tobytes(),
            np.asarray(result["query_blob"]).tobytes(),
        ))
        publish(result)

    ctl._publish_due = recording
    return seen


def _tick_the_rest(gch, tick: int) -> None:
    """The other channels on the engine's clock (a mark that comes
    before the host's own window has closed is kept until it has)."""
    for ch in list(all_channels().values()):
        if ch is not gch:
            ch.tick_once(10_000_000_000 + tick * 50_000_000)


def test_the_task_path_and_the_direct_path_decide_alike():
    ticks = 50

    def direct():
        gch = new_runtime()
        clock = [0]
        ctl, rng, pos = _walker_world(clock)
        seen = _record_decisions(ctl)
        for t in range(ticks):
            clock[0] = t
            _walk(ctl, rng, pos)
            gch.tick_once(gch.get_time())
            _tick_the_rest(gch, t)
        return seen, stage_count("step.await")

    async def by_task():
        gch = new_runtime()
        clock = [0]
        ctl, rng, pos = _walker_world(clock)
        # This coroutine is GLOBAL's tick task, and paces the others:
        # what reaches the engine, and in which tick, is then the same
        # on both paths.
        gch._tick_task.cancel()
        gch._tick_task = None
        channel_mod.scheduler.stop()
        seen = _record_decisions(ctl)
        for t in range(ticks):
            clock[0] = t
            _walk(ctl, rng, pos)
            await gch._tick_global(gch.get_time(), time.monotonic())
            _tick_the_rest(gch, t)
        return seen

    awaits0 = stage_count("step.await")
    steps0 = stage_count("device_step")
    want, awaits = direct()
    # (g) the direct path: a device_step a tick, and no step.await.
    assert awaits == awaits0
    assert stage_count("device_step") == steps0 + ticks
    got = asyncio.run(by_task())
    # (g) the task path: one of each for every step.
    assert stage_count("step.await") == awaits0 + ticks
    assert stage_count("device_step") == steps0 + 2 * ticks
    assert len(want) == len(got) == ticks
    for t, (a, b) in enumerate(zip(want, got)):
        assert a == b, f"tick {t}"
    assert sum(len(h) for h, _, _ in want) > 100  # the walkers do cross
    assert len({d for _, d, _ in want}) > 1
    assert len({q for _, _, q in want}) > 10


# ---- (f) a tick's cost is its loop-thread time -----------------------------


def test_the_awaited_interval_is_not_the_global_ticks_cost():
    """Held by what is charged against what was awaited, on one clock:
    the tick's wall time is its loop-thread time plus the await, and
    only the first is the tick's cost. No absolute time is held: under
    a loaded machine the loop-thread part is as long as it is."""
    async def scenario():
        gch = new_runtime()
        gch._tick_task.cancel()
        gch._tick_task = None
        ctl, _servers = world_with_entity()
        assert 0 < gch.tick_interval < 0.2
        await gch._tick_global(gch.get_time(), time.monotonic())  # warm
        utils = []
        note_tick = governor.note_tick
        governor.note_tick = lambda elapsed, interval: (
            utils.append(elapsed), note_tick(elapsed, interval))[1]
        duration = metrics.channel_tick_duration.labels(channel_type="GLOBAL")
        step_ms = metrics.tick_stage_ms.labels(stage="device_step")
        await_ms = metrics.tick_stage_ms.labels(stage="step.await")
        sum0, count0 = duration._sum.get(), stage_count("device_step")
        step0, await0 = step_ms._sum.get(), await_ms._sum.get()
        anomalies0 = len(recorder.anomalies)
        held = Held(ctl.engine)
        asyncio.get_running_loop().call_later(0.2, held.release.set)
        t0 = time.monotonic()
        await gch._tick_global(gch.get_time(), t0)
        wall = time.monotonic() - t0
        governor.note_tick = note_tick
        assert wall >= 0.2
        assert stage_count("device_step") == count0 + 1
        # device_step is the step from outside, the wait included; the
        # await is on the record beside it.
        awaited = (await_ms._sum.get() - await0) / 1e3
        assert awaited >= 0.19
        assert step_ms._sum.get() - step0 >= awaited * 1e3
        # What the tick is charged is what is left of its wall time once
        # the await is taken out, to the histogram and to the governor
        # alike: the awaited seconds were other channels' ticks.
        on_loop = wall - awaited
        charged = duration._sum.get() - sum0
        assert 0 < charged <= on_loop + 1e-6
        # (The notes before the last are the other channels' ticks, made
        # during the await.)
        assert utils and utils[-1] == pytest.approx(charged)
        assert charged + awaited <= wall + 1e-6
        if on_loop < gch.tick_interval:
            assert [a for a in recorder.anomalies[anomalies0:]
                    if a["trigger"] == "tick_budget"] == []

    asyncio.run(scenario())


def test_one_lateness_sample_a_global_tick_on_the_task_path():
    """The real task: lateness is noted once a tick, against its begin,
    however long the await inside it."""
    from channeld_tpu.core import channel as channel_mod

    async def scenario():
        gch = new_runtime()
        world_with_entity()
        frames0 = gch.tick_frames
        await until(lambda: gch.tick_frames >= frames0 + 4)
        noted = channel_mod._tick_late[ChannelType.GLOBAL][1]
        # One sample for every tick but the first, and but what the
        # GLOBAL tick has flushed to /metrics already.
        flushed = metrics.tick_late_ms.labels(
            channel_type="GLOBAL")._count.get()
        return gch.tick_frames - frames0, noted + flushed

    before = metrics.tick_late_ms.labels(channel_type="GLOBAL")._count.get()
    ticks, samples = asyncio.run(scenario())
    assert ticks - 2 <= samples - before <= ticks
