"""Device-driven fan-out: spatial channels take the per-subscriber "due"
decision from the SpatialEngine's batched tick instead of the host scan
(ref: data.go:175-291 — hot loop #2, moved onto the device plane)."""

import time

import pytest

from channeld_tpu.core.channel import get_channel
from channeld_tpu.core.message import MessageContext
from channeld_tpu.core.subscription import (
    subscribe_to_channel,
    unsubscribe_from_channel,
)
from channeld_tpu.core.types import ConnectionType, MessageType
from channeld_tpu.models.sim import register_sim_types
from channeld_tpu.models import sim_pb2
from channeld_tpu.protocol import control_pb2
from channeld_tpu.spatial.controller import set_spatial_controller
from channeld_tpu.spatial.tpu_controller import TPUSpatialController

from helpers import StubConnection, fresh_runtime

START = 0x10000


@pytest.fixture(autouse=True)
def runtime():
    gch = fresh_runtime()
    register_sim_types()
    yield gch


def make_tpu_world(**extra_cfg):
    from channeld_tpu.core.settings import global_settings

    global_settings.tpu_entity_capacity = 64
    global_settings.tpu_query_capacity = 8
    ctl = TPUSpatialController()
    ctl.load_config(
        dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100, GridHeight=100,
             GridCols=2, GridRows=1, ServerCols=2, ServerRows=1,
             ServerInterestBorderSize=1, **extra_cfg)
    )
    set_spatial_controller(ctl)
    server = StubConnection(1, ConnectionType.SERVER)
    ctx = MessageContext(
        msg_type=MessageType.CREATE_CHANNEL,
        msg=control_pb2.CreateChannelMessage(),
        connection=server,
    )
    for ch in ctl.create_channels(ctx):
        subscribe_to_channel(server, ch, None)
    return ctl, server


def data_updates(conn):
    return [c for c in conn.sent
            if c.msg_type == MessageType.CHANNEL_DATA_UPDATE]


def test_spatial_fanout_consumes_device_due_mask():
    _run_fanout_consumes_device_due_mask()


def test_spatial_fanout_device_due_cells_sharded():
    """The same device-due contract served from the cell-sharded plane
    over the 8-virtual-device mesh (Config {"Sharding": "cells"})."""
    ctl, _ = _run_fanout_consumes_device_due_mask(
        MeshDevices=8, Sharding="cells")
    assert ctl.engine._sharding == "cells"
    assert ctl.engine._mesh is not None


def _run_fanout_consumes_device_due_mask(**extra_cfg):
    ctl, server = make_tpu_world(**extra_cfg)
    ch = get_channel(START)
    ch.init_data(sim_pb2.SimSpatialChannelData(), None)

    client = StubConnection(9, ConnectionType.CLIENT)
    opts = control_pb2.ChannelSubscriptionOptions(
        fanOutIntervalMs=1, fanOutDelayMs=0
    )
    cs, _ = subscribe_to_channel(client, ch, opts)
    foc = cs.fanout_conn

    # The subscription landed in the engine's device sub table.
    assert foc.device_sub_slot is not None
    assert ch.device_sub_slots[foc.device_sub_slot] is foc
    assert ctl._device_sub_count >= 1

    # Engine tick publishes a due decision (no entities needed).
    time.sleep(0.005)
    ctl.tick()
    assert ctl.device_due(ch.id) is not None
    seq1, pending1 = ctl.device_due(ch.id)
    assert foc.device_sub_slot in pending1

    # Channel tick: first fan-out sends the full state.
    ch.tick_once(ch.get_time())
    assert len(data_updates(client)) == 1
    assert foc.had_first_fanout

    # Buffer an update; the device decision for seq1 is consumed, so a
    # second channel tick on the SAME engine tick must not fan out — even
    # though the 1ms host interval has long passed (this is what pins the
    # decision to the device, not the host clock).
    upd = sim_pb2.SimSpatialChannelData()
    upd.entities[7].SetInParent()
    ch.data.on_update(upd, ch.get_time(), 1, None)
    time.sleep(0.005)
    ch.tick_once(ch.get_time())
    assert len(data_updates(client)) == 1, "fan-out must wait for the device"

    # Next engine ticks re-arm the due bit; the channel tick delivers.
    # Bounded catch-up loop: the fan-out window advances one interval per
    # due tick (reference-exact (last, last+interval] semantics, pinned by
    # test_channel_data's design-doc timeline), so under scheduler delay
    # the buffered update can sit a few windows ahead — late delivery is
    # correct; lost delivery is the bug this asserts against.
    for _ in range(50):
        time.sleep(0.005)
        ctl.tick()
        ch.tick_once(ch.get_time())
        if len(data_updates(client)) == 2:
            break
    updates = data_updates(client)
    assert len(updates) == 2
    from channeld_tpu.utils.anyutil import unpack_any

    assert 7 in unpack_any(updates[-1].msg.data).entities

    # Unsubscribe releases the device slot.
    slot = foc.device_sub_slot
    unsubscribe_from_channel(client, ch)
    assert foc.device_sub_slot is None
    assert slot not in ch.device_sub_slots
    return ctl, server


def test_spatial_fanout_host_fallback_without_engine_tick():
    """Before the first engine tick there is no device decision; the host
    time check must serve (no starvation at boot)."""
    ctl, server = make_tpu_world()
    ch = get_channel(START)
    ch.init_data(sim_pb2.SimSpatialChannelData(), None)
    client = StubConnection(9, ConnectionType.CLIENT)
    subscribe_to_channel(client, ch, control_pb2.ChannelSubscriptionOptions(
        fanOutIntervalMs=1, fanOutDelayMs=0))
    assert ctl.device_due(ch.id) is None
    time.sleep(0.003)
    ch.tick_once(ch.get_time())
    assert len(data_updates(client)) == 1  # host path delivered full state


def test_device_slot_freed_on_connection_drop():
    """The crash/drop path (no explicit unsubscribe) must free the engine
    sub slot — one leak per disconnect would exhaust the table."""
    ctl, server = make_tpu_world()
    ch = get_channel(START)
    ch.init_data(sim_pb2.SimSpatialChannelData(), None)
    client = StubConnection(9, ConnectionType.CLIENT)
    cs, _ = subscribe_to_channel(client, ch, control_pb2.ChannelSubscriptionOptions(
        fanOutIntervalMs=1))
    slot = cs.fanout_conn.device_sub_slot
    assert slot is not None
    before = ctl._device_sub_count

    client.close(unexpected=True)  # dropped without unsubscribing
    ch.tick_once(ch.get_time())
    assert ctl._device_sub_count == before - 1
    assert not ctl.engine._sub_active[slot]
    assert slot in ctl.engine._sub_free
    assert slot not in ch.device_sub_slots
    # The fan-out queue entry goes too: device mode never sweeps the
    # queue, so a leftover foc would leak once per disconnect.
    assert cs.fanout_conn not in ch.fan_out_queue


def test_interval_change_preserves_device_window_start():
    """Re-subscribing with a new fanOutIntervalMs must not snap the sub's
    device-side window start back to the stale host mirror."""
    from channeld_tpu.ops.engine import SpatialEngine
    from channeld_tpu.ops.spatial_ops import GridSpec
    import numpy as np

    grid = GridSpec(0.0, 0.0, 100.0, 100.0, 2, 1)
    eng = SpatialEngine(grid, entity_capacity=16, query_capacity=4,
                        sub_capacity=8)
    s = eng.add_subscription(interval_ms=50, first_due_ms=0)
    for now in (60, 110, 160):  # device last advances to 150
        out = eng.tick(now_ms=now)
        assert np.asarray(out["due"])[s]
    eng.set_sub_interval(s, 100)  # interval-only host write
    out = eng.tick(now_ms=170)
    assert not np.asarray(out["due"])[s], (
        "interval change dragged the stale host last-fan-out along"
    )
    out = eng.tick(now_ms=260)  # 150 + 100 = 250 -> due
    assert np.asarray(out["due"])[s]


def test_pending_due_survives_missed_channel_ticks():
    """A due decision the channel hasn't consumed yet must survive further
    engine ticks (the device advances the window unconditionally, so a
    dropped bit would slip the sub's fan-out by a full interval)."""
    ctl, server = make_tpu_world()
    ch = get_channel(START)
    ch.init_data(sim_pb2.SimSpatialChannelData(), None)
    client = StubConnection(9, ConnectionType.CLIENT)
    cs, _ = subscribe_to_channel(client, ch, control_pb2.ChannelSubscriptionOptions(
        fanOutIntervalMs=1, fanOutDelayMs=0))
    slot = cs.fanout_conn.device_sub_slot

    # Two engine ticks with no channel tick in between.
    time.sleep(0.005)
    ctl.tick()
    time.sleep(0.005)
    ctl.tick()
    _, pending = ctl.device_due(ch.id)
    assert slot in pending
    ch.tick_once(ch.get_time())
    assert len(data_updates(client)) == 1  # served exactly once
    assert slot not in pending  # consumed


def test_sub_window_survives_table_churn():
    """Adding/removing other subscriptions must not reset existing subs'
    device-side window starts (the host mirror never sees the device's
    advances; a wholesale rebuild would snap windows back and collapse
    interval throttling)."""
    from channeld_tpu.ops.engine import SpatialEngine
    from channeld_tpu.ops.spatial_ops import GridSpec
    import numpy as np

    grid = GridSpec(0.0, 0.0, 100.0, 100.0, 2, 1)
    eng = SpatialEngine(grid, entity_capacity=16, query_capacity=4,
                        sub_capacity=8)
    s = eng.add_subscription(interval_ms=50, first_due_ms=0)
    out = eng.tick(now_ms=60)
    assert np.asarray(out["due"])[s]  # device advances last to 50
    eng.add_subscription(interval_ms=1000, first_due_ms=60)  # table churn
    out = eng.tick(now_ms=70)
    assert not np.asarray(out["due"])[s], (
        "window start was stomped by the table flush"
    )
    out = eng.tick(now_ms=110)
    assert np.asarray(out["due"])[s]  # due again at 100 as scheduled


def test_follow_interest_reaped_when_entity_destroyed():
    """A follower whose entity was untracked must
    not keep a stale interest center forever — the follow is dropped and
    the spatial subscriptions cleared."""

    from channeld_tpu.ops.spatial_ops import AOI_SPHERE
    from channeld_tpu.spatial.controller import SpatialInfo

    from channeld_tpu.core import connection as connection_mod
    from channeld_tpu.core.channel import all_channels

    ctl, server = make_tpu_world()
    eid = 0x80000 + 70
    ctl.track_entity(eid, SpatialInfo(50.0, 0.0, 50.0))
    client = StubConnection(9, ConnectionType.CLIENT)
    connection_mod._all_connections[client.id] = client
    ctl.register_follow_interest(client, eid, AOI_SPHERE, extent=(80.0, 0.0))

    def run_ticks():
        ctl.tick()
        for ch in list(all_channels().values()):
            ch.tick_once(0)

    run_ticks(); run_ticks()
    assert client.spatial_subscriptions  # following produced interest

    ctl.untrack_entity(eid)  # entity destroyed
    run_ticks(); run_ticks()
    assert client.id not in ctl._followers
    assert not client.spatial_subscriptions  # interest cleared


def test_follow_interest_survives_before_first_entity_update():
    """A follow registered before the entity's first position update must
    NOT be reaped (the entity simply hasn't been seen yet)."""
    from channeld_tpu.ops.spatial_ops import AOI_SPHERE
    from channeld_tpu.spatial.controller import SpatialInfo

    ctl, server = make_tpu_world()
    client = StubConnection(9, ConnectionType.CLIENT)
    eid = 0x80000 + 71
    ctl.register_follow_interest(client, eid, AOI_SPHERE, extent=(80.0, 0.0))
    ctl.tick()
    assert client.id in ctl._followers  # grace: entity not yet seen
    ctl.track_entity(eid, SpatialInfo(50.0, 0.0, 50.0))
    ctl.tick()
    assert client.id in ctl._followers  # now seen and still followed
