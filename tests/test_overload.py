"""Overload-control plane: the degradation ladder (core/overload.py), its
threading through fan-out / handover / admission, and the chaos-forced
<60s smoke soak proving L0 -> L2+ -> L0 under live saturation.

The full acceptance soak (SOAK_OVERLOAD_r07.json) runs the same
machinery via ``python scripts/overload_soak.py`` and as the
``slow``-marked test at the bottom; its artifact schema is pinned in
tests/test_chaos.py.
"""

import asyncio
import importlib.util
import os
import sys

import pytest

from channeld_tpu.core import connection as connection_mod
from channeld_tpu.core import metrics
from channeld_tpu.core.channel import (
    create_channel,
    create_entity_channel,
    get_channel,
    get_global_channel,
)
from channeld_tpu.core.connection import add_connection
from channeld_tpu.core.data import NS_PER_MS
from channeld_tpu.core.message import MessageContext
from channeld_tpu.core.overload import (
    AdmissionDecision,
    OverloadLevel,
    governor,
    sub_priority,
)
from channeld_tpu.core.settings import global_settings
from channeld_tpu.core.subscription import subscribe_to_channel
from channeld_tpu.core.types import (
    ChannelDataAccess,
    ChannelType,
    ConnectionType,
    MessageType,
)
from channeld_tpu.models import sim_pb2
from channeld_tpu.models.sim import register_sim_types
from channeld_tpu.protocol import (
    FrameDecoder,
    MESSAGE_TEMPLATES,
    control_pb2,
    encode_packet,
    wire_pb2,
)
from channeld_tpu.spatial.controller import set_spatial_controller

from helpers import FakeTransport, StubConnection, fresh_runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
START = 0x10000
ENTITY_START = 0x80000


@pytest.fixture(autouse=True)
def runtime():
    gch = fresh_runtime()
    global_settings.development = True
    connection_mod.set_fsm_templates(None, None)
    yield gch


def saturate(updates: int = 20, util: float = 5.0) -> None:
    """Drive the governor to L3 deterministically."""
    global_settings.overload_up_hold_ticks = 1
    for _ in range(updates):
        governor.note_tick(util * 0.01, 0.01)
        governor.update(0.01)
        if governor.level == OverloadLevel.L3:
            break


def wire(msg_type: int, msg, channel_id: int = 0) -> bytes:
    return encode_packet(wire_pb2.Packet(messages=[wire_pb2.MessagePack(
        channelId=channel_id, msgType=msg_type,
        msgBody=msg.SerializeToString(),
    )]))


def sent_messages(transport: FakeTransport) -> list:
    dec = FrameDecoder()
    out = []
    for chunk in transport.written:
        for packet in dec.decode_packets(chunk):
            out.extend(packet.messages)
    return out


# ---- the ladder ------------------------------------------------------------


def test_ladder_climbs_one_step_per_update_with_hold():
    global_settings.overload_up_hold_ticks = 2
    global_settings.overload_down_hold_s = 0.0
    for _ in range(30):
        governor.note_tick(0.05, 0.01)  # utilization 5x budget
        governor.update(0.01)
    assert governor.level == OverloadLevel.L3
    steps = [(t["from"], t["to"]) for t in governor.transitions]
    assert steps == [(0, 1), (1, 2), (2, 3)]  # no level skipped
    # Metric gauge mirrors the level.
    assert metrics.overload_level._value.get() == 3


def test_ladder_descends_with_hysteresis_dwell():
    saturate()
    assert governor.level == OverloadLevel.L3
    global_settings.overload_down_hold_s = 3600.0  # never dwell long enough
    for _ in range(20):
        governor.update(0.01)  # pressure decays below every exit threshold
    assert governor.level == OverloadLevel.L3  # dwell not met: holds
    global_settings.overload_down_hold_s = 0.0
    for _ in range(20):
        governor.update(0.01)
    assert governor.level == OverloadLevel.L0
    down = [(t["from"], t["to"]) for t in governor.transitions[-3:]]
    assert down == [(3, 2), (2, 1), (1, 0)]


def test_single_spike_does_not_escalate():
    global_settings.overload_up_hold_ticks = 3
    governor.note_tick(0.02, 0.01)  # one tick at 2x budget
    governor.update(0.01)
    assert governor.level == OverloadLevel.L0  # smoothed under threshold
    for _ in range(10):
        governor.update(0.01)
    assert governor.level == OverloadLevel.L0


def test_disabled_governor_pins_l0():
    saturate()
    assert governor.level == OverloadLevel.L3
    global_settings.overload_enabled = False
    governor.note_tick(0.5, 0.01)
    governor.update(0.01)
    assert governor.level == OverloadLevel.L0
    assert governor.admit_connection().admitted


def test_global_tick_drives_governor():
    """The GLOBAL channel tick is the governor's update cadence."""
    gch = get_global_channel()
    gch.tick_once(0)
    # note_tick + update ran (components sampled this tick).
    assert "tick_util" in governor.components


# ---- brownout: fan-out stretch + coalescing --------------------------------


def _subscribed_channel(conn, fanout_ms=20, access=ChannelDataAccess.READ_ACCESS):
    register_sim_types()
    ch = create_channel(ChannelType.SUBWORLD, None)
    ch.init_data(sim_pb2.SimSpatialChannelData(), None)
    cs, _ = subscribe_to_channel(
        conn, ch,
        control_pb2.ChannelSubscriptionOptions(
            dataAccess=access, fanOutIntervalMs=fanout_ms,
            skipSelfUpdateFanOut=False,
        ),
    )
    return ch, cs


def _update(ch, at_ns, eid=ENTITY_START + 1, x=1.0):
    upd = sim_pb2.SimSpatialChannelData()
    upd.entities[eid].entityId = eid
    upd.entities[eid].transform.position.x = x
    ch.data.on_update(upd, at_ns, 999)


def test_l1_stretches_fanout_interval():
    from channeld_tpu.utils.anyutil import unpack_any

    conn = StubConnection(7, ConnectionType.CLIENT)
    ch, cs = _subscribed_channel(conn, fanout_ms=20)
    from channeld_tpu.core.data import tick_data

    tick_data(ch, 30 * NS_PER_MS)  # first fan-out (full state)
    assert len(conn.sent) == 1

    governor.level = int(OverloadLevel.L1)  # stretch = 2.0 -> 40ms
    _update(ch, 35 * NS_PER_MS)
    tick_data(ch, 55 * NS_PER_MS)
    assert len(conn.sent) == 1  # 25ms after fan-out < stretched 40ms: held
    tick_data(ch, 75 * NS_PER_MS)
    assert len(conn.sent) == 2  # delivered once the stretched window passed
    # Nothing lost: the held update arrived coalesced into this fan-out.
    delivered = unpack_any(conn.sent[-1].msg.data)
    assert ENTITY_START + 1 in delivered.entities


def test_l2_sheds_low_priority_updates_and_counts():
    lowpri = StubConnection(8, ConnectionType.CLIENT)
    server = StubConnection(9, ConnectionType.SERVER)
    register_sim_types()
    ch = create_channel(ChannelType.SUBWORLD, None)
    ch.init_data(sim_pb2.SimSpatialChannelData(), None)
    # Low priority: READ access, slower than the channel default.
    cs_low, _ = subscribe_to_channel(
        lowpri, ch, control_pb2.ChannelSubscriptionOptions(
            dataAccess=ChannelDataAccess.READ_ACCESS, fanOutIntervalMs=200,
            skipSelfUpdateFanOut=False))
    cs_srv, _ = subscribe_to_channel(
        server, ch, control_pb2.ChannelSubscriptionOptions(
            dataAccess=ChannelDataAccess.READ_ACCESS, fanOutIntervalMs=200,
            skipSelfUpdateFanOut=False))
    assert cs_low.priority == 2
    assert cs_srv.priority == 0  # SERVER connections are never shed
    from channeld_tpu.core.data import tick_data

    tick_data(ch, 300 * NS_PER_MS)  # first fan-out handshake for both
    assert len(lowpri.sent) == len(server.sent) == 1

    governor.level = int(OverloadLevel.L2)
    before = dict(governor.shed_counts)
    _update(ch, 500 * NS_PER_MS)
    # L2 stretch is 4x: 200ms intervals become 800ms — due at 1100ms.
    tick_data(ch, 1200 * NS_PER_MS)
    assert len(server.sent) == 2  # the authority plane still gets data
    assert len(lowpri.sent) == 1  # the observer's due delivery was shed...
    shed = governor.shed_counts.get("update_priority", 0)
    assert shed == before.get("update_priority", 0) + 1  # ...and counted
    from channeld_tpu.chaos.invariants import sample_total

    assert sample_total(
        None, "overload_sheds_total", reason="update_priority") >= shed

    governor.level = int(OverloadLevel.L0)  # release: delivery resumes
    tick_data(ch, 1400 * NS_PER_MS)
    assert len(lowpri.sent) == 2  # the withheld window arrives (coalesced)


def test_shed_past_ring_eviction_gets_full_state_resync():
    """A subscriber held (shed) so long that the update ring evicted
    entries from its catch-up window must get a FULL-STATE resync on
    release — deltas can no longer reconstruct its view."""
    from channeld_tpu.core.data import MAX_UPDATE_MSG_BUFFER_SIZE, tick_data
    from channeld_tpu.utils.anyutil import unpack_any

    lowpri = StubConnection(11, ConnectionType.CLIENT)
    register_sim_types()
    ch = create_channel(ChannelType.SUBWORLD, None)
    ch.init_data(sim_pb2.SimSpatialChannelData(), None)
    subscribe_to_channel(
        lowpri, ch, control_pb2.ChannelSubscriptionOptions(
            dataAccess=ChannelDataAccess.READ_ACCESS, fanOutIntervalMs=200,
            skipSelfUpdateFanOut=False))
    tick_data(ch, 300 * NS_PER_MS)  # first fan-out
    assert len(lowpri.sent) == 1

    governor.level = int(OverloadLevel.L2)  # shed begins
    # Push far past the ring cap with arrival stamps spread well beyond
    # the (stretched) retention horizon: early entries evict.
    first_eid = ENTITY_START + 100
    for i in range(MAX_UPDATE_MSG_BUFFER_SIZE + 64):
        _update(ch, (400 + i * 20) * NS_PER_MS, eid=first_eid + (i % 8),
                x=float(i))
    assert ch.data.evicted_through > 0  # the ring really overflowed

    governor.level = int(OverloadLevel.L0)  # release
    tick_data(ch, (400 + 13000) * NS_PER_MS)
    assert len(lowpri.sent) == 2
    delivered = unpack_any(lowpri.sent[-1].msg.data)
    # Full state, not a (gapped) delta window: every entity present with
    # its LATEST position.
    for k in range(8):
        assert first_eid + k in delivered.entities
    assert delivered.entities[first_eid].transform.position.x == float(
        MAX_UPDATE_MSG_BUFFER_SIZE + 64 - 8)


def test_sub_priority_from_options():
    mk = control_pb2.ChannelSubscriptionOptions
    assert sub_priority(mk(dataAccess=2, fanOutIntervalMs=500), 20) == 0
    assert sub_priority(mk(dataAccess=1, fanOutIntervalMs=20), 20) == 1
    assert sub_priority(mk(dataAccess=1, fanOutIntervalMs=100), 20) == 2


# ---- L3 admission control --------------------------------------------------


def test_l3_rejects_new_client_auth_with_retry_after():
    global_settings.overload_retry_after_ms = 1234
    t = FakeTransport()
    conn = add_connection(t, ConnectionType.CLIENT)
    saturate()
    assert governor.level == OverloadLevel.L3
    before = governor.shed_counts.get("admission_connection", 0)

    conn.on_bytes(wire(MessageType.AUTH, control_pb2.AuthMessage(
        playerIdentifierToken="late-joiner")))
    get_global_channel().tick_once(0)

    assert conn.is_closing()
    busy = [m for m in sent_messages(t) if m.msgType == MessageType.SERVER_BUSY]
    assert len(busy) == 1  # the structured refusal hit the wire pre-close
    msg = control_pb2.ServerBusyMessage()
    msg.ParseFromString(busy[0].msgBody)
    assert msg.retryAfterMs == 1234
    assert msg.reason == "connection"
    assert msg.overloadLevel == 3
    assert governor.shed_counts["admission_connection"] == before + 1


def test_l3_still_admits_servers():
    t = FakeTransport()
    conn = add_connection(t, ConnectionType.SERVER)
    saturate()
    conn.on_bytes(wire(MessageType.AUTH, control_pb2.AuthMessage(
        playerIdentifierToken="spatial-7")))
    get_global_channel().tick_once(0)
    assert not conn.is_closing()
    assert [m for m in sent_messages(t)
            if m.msgType == MessageType.SERVER_BUSY] == []


def test_l3_rejects_new_client_subscription_keeps_existing():
    t = FakeTransport()
    conn = add_connection(t, ConnectionType.CLIENT)
    conn.on_bytes(wire(MessageType.AUTH, control_pb2.AuthMessage(
        playerIdentifierToken="sub-client")))
    gch = get_global_channel()
    gch.tick_once(0)
    sub = create_channel(ChannelType.SUBWORLD, None)
    # Existing subscription on another channel, made while healthy.
    conn.on_bytes(wire(MessageType.SUB_TO_CHANNEL,
                       control_pb2.SubscribedToChannelMessage(),
                       channel_id=sub.id))
    sub.tick_once(0)
    assert conn in sub.subscribed_connections

    saturate()
    sub2 = create_channel(ChannelType.SUBWORLD, None)
    t.written.clear()
    conn.on_bytes(wire(MessageType.SUB_TO_CHANNEL,
                       control_pb2.SubscribedToChannelMessage(),
                       channel_id=sub2.id))
    sub2.tick_once(0)
    conn.flush()
    assert conn not in sub2.subscribed_connections  # refused...
    busy = [m for m in sent_messages(t) if m.msgType == MessageType.SERVER_BUSY]
    assert len(busy) == 1  # ...with the structured result, conn kept open
    assert not conn.is_closing()
    assert governor.shed_counts.get("admission_subscription", 0) == 1

    # A RE-subscription (option merge) on the existing channel is served.
    t.written.clear()
    conn.on_bytes(wire(
        MessageType.SUB_TO_CHANNEL,
        control_pb2.SubscribedToChannelMessage(
            subOptions=control_pb2.ChannelSubscriptionOptions(
                fanOutIntervalMs=500)),
        channel_id=sub.id))
    sub.tick_once(0)
    assert conn in sub.subscribed_connections
    assert sub.subscribed_connections[conn].options.fanOutIntervalMs == 500
    assert [m for m in sent_messages(t)
            if m.msgType == MessageType.SERVER_BUSY] == []


def test_server_busy_message_round_trip_and_registry():
    assert MESSAGE_TEMPLATES[int(MessageType.SERVER_BUSY)] is (
        control_pb2.ServerBusyMessage
    )
    m = control_pb2.ServerBusyMessage(
        reason="subscription", retryAfterMs=2000, overloadLevel=2)
    m2 = control_pb2.ServerBusyMessage.FromString(m.SerializeToString())
    assert (m2.reason, m2.retryAfterMs, m2.overloadLevel) == (
        "subscription", 2000, 2)


# ---- handover fan-out deferral + batching ----------------------------------


def _spatial_world():
    from channeld_tpu.spatial.grid import StaticGrid2DSpatialController

    ctl = StaticGrid2DSpatialController()
    ctl.load_config(
        dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100, GridHeight=100,
             GridCols=2, GridRows=1, ServerCols=2, ServerRows=1,
             ServerInterestBorderSize=1))
    set_spatial_controller(ctl)
    register_sim_types()
    server_a = StubConnection(1, ConnectionType.SERVER)
    server_b = StubConnection(2, ConnectionType.SERVER)
    for server in (server_a, server_b):
        ctx = MessageContext(
            msg_type=MessageType.CREATE_CHANNEL,
            msg=control_pb2.CreateChannelMessage(),
            connection=server,
        )
        for ch in ctl.create_channels(ctx):
            subscribe_to_channel(server, ch, None)
    return ctl, server_a, server_b


def _crossing_entity(ctl, server_a, eid, x=50.0):
    entity_ch = create_entity_channel(eid, server_a)
    d = sim_pb2.SimEntityChannelData()
    d.state.entityId = eid
    d.state.transform.position.x = x
    d.state.transform.position.z = 50
    entity_ch.init_data(d, None)
    entity_ch.spatial_notifier = ctl
    subscribe_to_channel(server_a, entity_ch, None)
    get_channel(START).get_data_message().add_entity(
        eid, entity_ch.get_data_message())
    return entity_ch


def _move(entity_ch, eid, ctl, x):
    upd = sim_pb2.SimEntityChannelData()
    upd.state.entityId = eid
    upd.state.transform.position.x = x
    upd.state.transform.position.z = 50
    entity_ch.data.on_update(upd, 0, 1, ctl)


def test_handover_shares_one_encode_across_recipients():
    """The per-recipient handover sends are
    batched — src-only observers share one pre-encoded context, and dst
    conns with unchanged subscriptions share one payload."""
    ctl, server_a, server_b = _spatial_world()
    observers = [StubConnection(10 + i, ConnectionType.CLIENT)
                 for i in range(3)]
    for obs in observers:  # subscribed to src cell only
        subscribe_to_channel(obs, get_channel(START), None)
    eid = ENTITY_START + 30
    entity_ch = _crossing_entity(ctl, server_a, eid)
    _move(entity_ch, eid, ctl, 150)  # cross into cell 1
    get_channel(START).tick_once(0)
    get_channel(START + 1).tick_once(0)
    assert entity_ch.get_owner() is server_b

    handover_ctxs = [
        ctx for obs in observers for ctx in obs.sent
        if ctx.msg_type == MessageType.CHANNEL_DATA_HANDOVER
    ]
    assert len(handover_ctxs) == 3
    # One shared context object == one encode for the whole fleet.
    assert len({id(c) for c in handover_ctxs}) == 1
    assert handover_ctxs[0].raw_body is not None


def test_l2_sheds_only_redundant_handover_fanout():
    """At L2+ the ONLY withheld handover payload is the redundant one:
    a dst client already subscribed to every moved entity. Load-bearing
    messages — the src-side departure signal and any payload carrying a
    new subscriber's full state — still go out."""
    ctl, server_a, server_b = _spatial_world()
    # Observer subscribed to BOTH cells: it rides dst-side fan-out.
    obs = StubConnection(20, ConnectionType.CLIENT)
    subscribe_to_channel(obs, get_channel(START), None)
    subscribe_to_channel(obs, get_channel(START + 1), None)
    # Src-only observer: its departure signal is load-bearing.
    src_obs = StubConnection(21, ConnectionType.CLIENT)
    subscribe_to_channel(src_obs, get_channel(START), None)
    eid = ENTITY_START + 31
    entity_ch = _crossing_entity(ctl, server_a, eid)

    governor.level = int(OverloadLevel.L2)
    before = governor.shed_counts.get("handover_fanout", 0)
    _move(entity_ch, eid, ctl, 150)  # cell 0 -> 1
    get_channel(START).tick_once(0)
    get_channel(START + 1).tick_once(0)

    # The orchestration itself ran in full: owner swap + data move.
    assert entity_ch.get_owner() is server_b
    assert eid in get_channel(START + 1).get_data_message().entities
    # First crossing: the dst observer's entity subscription is NEW, so
    # its handover message (carrying full state) is NOT shed.
    assert [c for c in obs.sent
            if c.msg_type == MessageType.CHANNEL_DATA_HANDOVER]
    assert governor.shed_counts.get("handover_fanout", 0) == before

    # Second crossing back (1 -> 0): the observer is subscribed to both
    # cells AND to the entity channel by now — the payload is redundant
    # for it, and only now is it shed (and counted).
    obs.sent.clear()
    src_obs.sent.clear()
    _move(entity_ch, eid, ctl, 50)
    get_channel(START).tick_once(0)
    get_channel(START + 1).tick_once(0)
    assert entity_ch.get_owner() is server_a
    assert [c for c in obs.sent
            if c.msg_type == MessageType.CHANNEL_DATA_HANDOVER] == []
    assert governor.shed_counts["handover_fanout"] == before + 1
    # The src-only observer's departure signal was NOT shed on either
    # crossing — without it the entity would ghost in its view forever.
    assert [c for c in src_obs.sent
            if c.msg_type == MessageType.CHANNEL_DATA_HANDOVER]
    # The server plane saw everything (authority must stay coherent).
    assert [c for c in server_a.sent
            if c.msg_type == MessageType.CHANNEL_DATA_HANDOVER]


def test_handover_batch_cap_query():
    global_settings.overload_handover_batch_cap = 7
    assert governor.handover_batch_cap() is None
    governor.level = int(OverloadLevel.L2)
    assert governor.handover_batch_cap() == 7
    governor.level = int(OverloadLevel.L3)
    assert governor.handover_batch_cap() == 7


def test_deferred_crossing_chain_settles_correctly():
    """L2+ caps handover orchestration; a deferred entity that keeps
    moving collapses into ONE crossing from the cell its data lives in
    to its current cell — zero loss, zero duplication."""
    from channeld_tpu.core.settings import global_settings as st
    from channeld_tpu.spatial.controller import SpatialInfo
    from channeld_tpu.spatial.tpu_controller import TPUSpatialController

    st.tpu_entity_capacity = 64
    st.tpu_query_capacity = 8
    st.overload_handover_batch_cap = 0  # defer EVERY crossing at L2+
    ctl = TPUSpatialController()
    ctl.load_config(
        dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100, GridHeight=100,
             GridCols=3, GridRows=1, ServerCols=3, ServerRows=1,
             ServerInterestBorderSize=1))
    set_spatial_controller(ctl)
    register_sim_types()
    servers = []
    for i in range(3):
        server = StubConnection(1 + i, ConnectionType.SERVER)
        ctx = MessageContext(
            msg_type=MessageType.CREATE_CHANNEL,
            msg=control_pb2.CreateChannelMessage(),
            connection=server,
        )
        for ch in ctl.create_channels(ctx):
            subscribe_to_channel(server, ch, None)
        servers.append(server)

    eid = ENTITY_START + 40
    entity_ch = create_entity_channel(eid, servers[0])
    d = sim_pb2.SimEntityChannelData()
    d.state.entityId = eid
    d.state.transform.position.x = 50
    d.state.transform.position.z = 50
    entity_ch.init_data(d, None)
    entity_ch.spatial_notifier = ctl
    subscribe_to_channel(servers[0], entity_ch, None)
    get_channel(START).get_data_message().add_entity(
        eid, entity_ch.get_data_message())
    ctl.track_entity(eid, SpatialInfo(50, 0, 50))
    ctl.tick()

    governor.level = int(OverloadLevel.L2)
    _move(entity_ch, eid, ctl, 150)  # cell 0 -> 1
    ctl.tick()  # detected, deferred (cap 0)
    assert eid in ctl._deferred_crossings
    assert eid in get_channel(START).get_data_message().entities  # data waits
    _move(entity_ch, eid, ctl, 250)  # cell 1 -> 2 while deferred
    ctl.tick()  # chain-merged: now 0 -> 2
    assert governor.shed_counts.get("handover_defer", 0) > 0

    governor.level = int(OverloadLevel.L0)  # release: the backlog drains
    ctl.tick()
    for cid in (START, START + 1, START + 2):
        get_channel(cid).tick_once(0)
    assert entity_ch.get_owner() is servers[2]
    placements = [
        cid for cid in (START, START + 1, START + 2)
        if eid in get_channel(cid).get_data_message().entities
    ]
    assert placements == [START + 2]  # exactly one cell, the current one
    assert ctl._deferred_crossings == {}


# ---- follower-interest instrumentation -----------------------------------


def test_follower_interest_cost_histogram():
    from channeld_tpu.ops.spatial_ops import AOI_SPHERE
    from channeld_tpu.spatial.controller import SpatialInfo
    from channeld_tpu.spatial.tpu_controller import TPUSpatialController

    global_settings.tpu_entity_capacity = 64
    global_settings.tpu_query_capacity = 8
    ctl = TPUSpatialController()
    ctl.load_config(dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100,
                         GridHeight=100, GridCols=3, GridRows=1,
                         ServerCols=1, ServerRows=1,
                         ServerInterestBorderSize=1))
    set_spatial_controller(ctl)
    server = StubConnection(1, ConnectionType.SERVER)
    ctx = MessageContext(
        msg_type=MessageType.CREATE_CHANNEL,
        msg=control_pb2.CreateChannelMessage(),
        connection=server,
    )
    ctl.create_channels(ctx)
    eid = ENTITY_START + 60
    ctl.track_entity(eid, SpatialInfo(50, 0, 50))
    player = StubConnection(2, ConnectionType.CLIENT)
    connection_mod._all_connections[player.id] = player
    ctl.register_follow_interest(player, eid, AOI_SPHERE, extent=(40.0, 0.0))

    def hist_count(h):
        for fam in h.collect():
            for s in fam.samples:
                if s.name.endswith("_count"):
                    return s.value
        return 0.0

    before = hist_count(metrics.follower_interest_ms)
    ctl.tick()
    assert hist_count(metrics.follower_interest_ms) == before + 1


def test_l2_defers_follower_interest_every_other_tick():
    from channeld_tpu.ops.spatial_ops import AOI_SPHERE
    from channeld_tpu.spatial.controller import SpatialInfo
    from channeld_tpu.spatial.tpu_controller import TPUSpatialController

    global_settings.tpu_entity_capacity = 64
    global_settings.tpu_query_capacity = 8
    ctl = TPUSpatialController()
    ctl.load_config(dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100,
                         GridHeight=100, GridCols=3, GridRows=1,
                         ServerCols=1, ServerRows=1,
                         ServerInterestBorderSize=1))
    set_spatial_controller(ctl)
    server = StubConnection(1, ConnectionType.SERVER)
    ctl.create_channels(MessageContext(
        msg_type=MessageType.CREATE_CHANNEL,
        msg=control_pb2.CreateChannelMessage(),
        connection=server,
    ))
    eid = ENTITY_START + 61
    ctl.track_entity(eid, SpatialInfo(50, 0, 50))
    player = StubConnection(2, ConnectionType.CLIENT)
    connection_mod._all_connections[player.id] = player
    ctl.register_follow_interest(player, eid, AOI_SPHERE, extent=(40.0, 0.0))

    governor.level = int(OverloadLevel.L2)
    # Follower interest rides the standing-query plane now
    # (doc/query_engine.md): the deferred apply pass sheds under
    # `query_apply_defer`, one count per deferred standing row.
    before = governor.shed_counts.get("query_apply_defer", 0)
    ctl.tick()  # skipped
    ctl.tick()  # applied
    ctl.tick()  # skipped
    assert governor.shed_counts["query_apply_defer"] == before + 2


# ---- admission decision surface -------------------------------------------


def test_admission_decision_structure():
    global_settings.overload_retry_after_ms = 777
    governor.level = int(OverloadLevel.L3)
    d = governor.admit_connection()
    assert d == AdmissionDecision(False, 777, "connection")
    d = governor.admit_subscription()
    assert d == AdmissionDecision(False, 777, "subscription")
    governor.level = int(OverloadLevel.L2)
    assert governor.admit_connection().admitted
    assert governor.admit_subscription().admitted


# ---- the seeded smoke soak (tier-1) ---------------------------------------


def _load_overload_soak():
    spec = importlib.util.spec_from_file_location(
        "overload_soak", os.path.join(REPO, "scripts", "overload_soak.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["overload_soak"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_overload_smoke_soak():
    """Seeded <60s live soak: a chaos saturation window forces the
    ladder L0 -> L2+ and back to L0, with every invariant (monotonic
    engagement, bounded tick p99 at every level, zero lost entities,
    exact shed accounting, recovery deadline) holding."""
    mod = _load_overload_soak()
    # Doubled tick budget + lighter baseline than the acceptance soak:
    # the smoke must have honest L0 headroom even on a throttled CI box
    # (the injected 90ms stalls saturate a 100ms budget regardless).
    p = mod.OverloadSoakParams(
        warmup_s=4.0, saturation_s=12.0, recover_deadline_s=20.0,
        quiesce_s=4.0, clients=6, observers=3, entities=32,
        msg_rate=10.0, storm_every_s=4.0, storm_size=24,
        global_tick_ms=100, require_handover_defer=False,
        require_update_priority=False,
    )
    report = asyncio.run(mod.run_overload_soak(p))
    failed = [c for c in report["invariants"]["checks"] if not c["ok"]]
    assert report["invariants"]["ok"], failed
    assert report["max_level"] >= 2
    assert sum(report["stats"]["sheds"].values()) > 0


@pytest.mark.slow
def test_overload_full_soak():
    """The acceptance soak (SOAK_OVERLOAD_r07.json form): full warmup /
    saturation / recovery timeline with the default scenario."""
    mod = _load_overload_soak()
    p = mod.OverloadSoakParams()
    report = asyncio.run(mod.run_overload_soak(p))
    failed = [c for c in report["invariants"]["checks"] if not c["ok"]]
    assert report["invariants"]["ok"], failed
