"""Engine adapter: spawn routing, destroy, recovery extension
(ref: pkg/unreal/message.go, recovery.go)."""

import pytest

from channeld_tpu.core.channel import create_entity_channel, get_channel
from channeld_tpu.core.message import MESSAGE_MAP, MessageContext
from channeld_tpu.core.subscription import subscribe_to_channel
from channeld_tpu.core.types import ChannelType, ConnectionType, MessageType
from channeld_tpu.models import sim_pb2
from channeld_tpu.models.engine_adapter import (
    MSG_DESTROY,
    MSG_SPAWN,
    RecoverableChannelDataExtension,
    check_entity_handover,
    init_message_handlers,
)
from channeld_tpu.models.sim import register_sim_types
from channeld_tpu.protocol import control_pb2, wire_pb2
from channeld_tpu.spatial.controller import set_spatial_controller
from channeld_tpu.spatial.grid import StaticGrid2DSpatialController

from helpers import StubConnection, fresh_runtime

START = 0x10000
E = 0x80000


@pytest.fixture(autouse=True)
def runtime():
    gch = fresh_runtime()
    register_sim_types()
    init_message_handlers()
    yield gch


def spawn_forward(net_id, x=None, z=None, channel_id=0, conn_id=0):
    spawn = sim_pb2.SpawnObjectMessage(channelId=channel_id)
    spawn.obj.netId = net_id
    spawn.obj.owningConnId = conn_id
    if x is not None:
        spawn.location.x = x
        spawn.location.z = z
    return wire_pb2.ServerForwardMessage(payload=spawn.SerializeToString())


def make_spatial_world():
    ctl = StaticGrid2DSpatialController()
    ctl.load_config(dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100,
                         GridHeight=100, GridCols=2, GridRows=1, ServerCols=2,
                         ServerRows=1, ServerInterestBorderSize=1))
    set_spatial_controller(ctl)
    servers = []
    for i in range(2):
        server = StubConnection(10 + i, ConnectionType.SERVER)
        ctx = MessageContext(
            msg_type=MessageType.CREATE_CHANNEL,
            msg=control_pb2.CreateChannelMessage(),
            connection=server,
        )
        for ch in ctl.create_channels(ctx):
            subscribe_to_channel(server, ch, None)
        servers.append(server)
    return ctl, servers


def test_spawn_rewrites_spatial_channel_and_inserts_entity():
    ctl, (server_a, server_b) = make_spatial_world()
    net_id = E + 31
    # Spawn at x=150 (cell 1) but addressed to cell 0: must be re-routed.
    ctx = MessageContext(
        msg_type=MSG_SPAWN,
        msg=spawn_forward(net_id, x=150.0, z=50.0, channel_id=START),
        connection=server_a,
        channel=get_channel(START),
        channel_id=START,
    )
    MESSAGE_MAP[MSG_SPAWN].handler(ctx)
    dst = get_channel(START + 1)
    dst.tick_once(0)  # run the queued execute + forward
    assert net_id in dst.get_data_message().entities
    assert net_id not in get_channel(START).get_data_message().entities
    # The forward went to the dst channel's owner.
    forwards = [c for c in server_b.sent if c.msg_type == MSG_SPAWN]
    assert len(forwards) == 1


def test_spawn_without_location_records_for_recovery():
    from channeld_tpu.core.channel import create_channel

    owner = StubConnection(1, ConnectionType.SERVER)
    ch = create_channel(ChannelType.SUBWORLD, owner)
    ch.init_data(None, None)
    assert isinstance(ch.data.extension, RecoverableChannelDataExtension)
    net_id = E + 32
    ctx = MessageContext(
        msg_type=MSG_SPAWN,
        msg=spawn_forward(net_id, conn_id=7),
        connection=owner,
        channel=ch,
        channel_id=ch.id,
    )
    MESSAGE_MAP[MSG_SPAWN].handler(ctx)
    assert net_id in ch.data.extension.spawned_objs
    recovery_data = ch.data.extension.get_recovery_data_message()
    assert recovery_data.spawnedObjects[net_id].owningConnId == 7


def test_destroy_removes_entity_and_channel():
    ctl, (server_a, server_b) = make_spatial_world()
    net_id = E + 33
    entity_ch = create_entity_channel(net_id, server_a)
    src = get_channel(START)
    src.get_data_message().entities[net_id].entityId = net_id

    ctx = MessageContext(
        msg_type=MSG_DESTROY,
        msg=wire_pb2.ServerForwardMessage(
            payload=sim_pb2.DestroyObjectMessage(netId=net_id).SerializeToString()
        ),
        connection=server_a,
        channel=src,
        channel_id=START,
    )
    MESSAGE_MAP[MSG_DESTROY].handler(ctx)
    assert net_id not in src.get_data_message().entities
    assert get_channel(net_id) is None


def test_check_entity_handover():
    a = sim_pb2.Vec3(x=1, y=2, z=3)
    b = sim_pb2.Vec3(x=1, y=2, z=3)
    moved, old, new = check_entity_handover(1, a, b)
    assert not moved
    b2 = sim_pb2.Vec3(x=5, y=2, z=3)
    moved, old, new = check_entity_handover(1, b2, a)
    assert moved and new.x == 5 and old.x == 1
    # UE axis swap: Z-up -> Y-up.
    moved, old, new = check_entity_handover(1, b2, a, swap_yz=True)
    assert new.y == 3 and new.z == 2


def test_well_known_entity_visible_to_all_clients(runtime):
    """isWellKnown entity channels subscribe every current client at
    creation and every later-authenticating client via the auth hook with
    a 1s fan-out delay (ref: message_spatial.go:191-333 well-known
    entities + Event_AuthComplete)."""
    from channeld_tpu.core import events
    from channeld_tpu.core.channel import get_global_channel
    from channeld_tpu.core.connection import add_connection
    from channeld_tpu.spatial.messages import handle_create_entity_channel
    from channeld_tpu.protocol import spatial_pb2

    from helpers import FakeTransport

    server = StubConnection(1, ConnectionType.SERVER)
    early_client = add_connection(FakeTransport(), ConnectionType.CLIENT)

    ctx = MessageContext(
        msg_type=MessageType.CREATE_ENTITY_CHANNEL,
        msg=spatial_pb2.CreateEntityChannelMessage(entityId=E + 777, isWellKnown=True),
        connection=server,
        channel=get_global_channel(),
        channel_id=0,
    )
    handle_create_entity_channel(ctx)
    ch = get_channel(E + 777)
    assert ch is not None
    assert early_client in ch.subscribed_connections  # existing client

    # A client authenticating later is auto-subscribed with the spawn
    # grace delay.
    late_client = add_connection(FakeTransport(), ConnectionType.CLIENT)
    events.auth_complete.broadcast(
        events.AuthEventData(connection=late_client, player_identifier_token="late")
    )
    assert late_client in ch.subscribed_connections
    assert ch.subscribed_connections[late_client].options.fanOutDelayMs == 1000

    # Another server is NOT swept in.
    other_server = StubConnection(9, ConnectionType.SERVER)
    events.auth_complete.broadcast(
        events.AuthEventData(connection=other_server, player_identifier_token="srv")
    )
    assert other_server not in ch.subscribed_connections


def test_partial_position_update_merges_without_zeroing():
    """Vec3 axes carry presence (ref: unrealpb FVector optional fields):
    an update replicating only the changed axis merges over the old
    coordinates instead of zeroing them, and the handover notification
    uses the resolved position (ref: handover.go:8-30 fallback ladder)."""
    notifications = []

    class Notifier:
        def notify(self, old_info, new_info, provider):
            notifications.append((old_info, new_info, provider(-1, -1)))

    data = sim_pb2.SimEntityChannelData()
    data.state.entityId = E + 1
    data.state.transform.position.x = 150.0
    data.state.transform.position.y = 5.0
    data.state.transform.position.z = 50.0

    # Partial update: only x replicated.
    upd = sim_pb2.SimEntityChannelData()
    upd.state.entityId = E + 1
    upd.state.transform.position.x = 30.0
    data.merge(upd, None, Notifier())

    assert (data.state.transform.position.x,
            data.state.transform.position.y,
            data.state.transform.position.z) == (30.0, 5.0, 50.0)
    assert len(notifications) == 1
    old_info, new_info, eid = notifications[0]
    assert (old_info.x, old_info.y, old_info.z) == (150.0, 5.0, 50.0)
    assert (new_info.x, new_info.y, new_info.z) == (30.0, 5.0, 50.0)
    assert eid == E + 1


def test_unmoved_update_fires_no_handover_check():
    """(ref: handover.go:31 — identical position returns false)."""
    notifications = []

    class Notifier:
        def notify(self, *a):
            notifications.append(a)

    data = sim_pb2.SimEntityChannelData()
    data.state.entityId = E + 2
    data.state.transform.position.x = 10.0
    upd = sim_pb2.SimEntityChannelData()
    upd.state.entityId = E + 2
    upd.state.transform.position.x = 10.0  # same spot
    upd.state.payload = b"anim-state"  # non-positional change
    data.merge(upd, None, Notifier())
    assert notifications == []
    assert data.state.payload == b"anim-state"


def test_check_entity_handover_axis_presence_fallback():
    old = sim_pb2.Vec3(x=1.0, y=2.0, z=3.0)
    new = sim_pb2.Vec3()
    new.x = 9.0  # only x replicated
    moved, old_info, new_info = check_entity_handover(E + 3, new, old)
    assert moved
    assert (new_info.x, new_info.y, new_info.z) == (9.0, 2.0, 3.0)
    # All axes absent -> full fallback -> no movement.
    moved, _, _ = check_entity_handover(E + 3, sim_pb2.Vec3(), old)
    assert not moved
    # UE Z-up swap still applies.
    moved, old_i, new_i = check_entity_handover(
        E + 3, sim_pb2.Vec3(x=1.0, y=7.0, z=3.0), old, swap_yz=True)
    assert moved and (new_i.x, new_i.y, new_i.z) == (1.0, 3.0, 7.0)


def test_spatially_owned_entity_enters_spatial_data():
    """(ref: pkg/unreal/message.go:205-215): when an entity channel gets
    spatially owned, its entity lands in the spatial channel's table so
    handover can see it."""
    from channeld_tpu.core import events

    ctl, servers = make_spatial_world()
    entity_ch = create_entity_channel(E + 4, servers[0])
    data = sim_pb2.SimEntityChannelData()
    data.state.entityId = E + 4
    data.state.transform.position.x = 150.0
    entity_ch.init_data(data, None)

    spatial_ch = get_channel(START + 1)
    spatial_ch.init_data(sim_pb2.SimSpatialChannelData(), None)
    events.entity_channel_spatially_owned.broadcast(
        events.SpatialOwnershipData(
            entity_channel=entity_ch, spatial_channel=spatial_ch
        )
    )
    spatial_ch.tick_once(0)
    assert E + 4 in spatial_ch.get_data_message().entities


def test_handover_data_payload_trimming():
    """The HandoverDataWithPayload seam (ref: spatial.go:594-597 +
    unrealpb/extension.go ClearPayload): identity context survives, the
    bulk channel data is stripped for no-interest connections."""
    ho = sim_pb2.SimHandoverData()
    ho.channelData.entities[E + 5].entityId = E + 5
    hctx = ho.context.add()
    hctx.obj.netId = E + 5
    hctx.clientConnId = 42
    hctx.clientState = b"inventory"
    ho.clear_payload()
    assert not ho.HasField("channelData")
    assert ho.context[0].clientConnId == 42
    assert ho.context[0].clientState == b"inventory"


def test_tpu_handover_uses_true_old_position():
    """The device-detected crossing hands the REAL
    previous position to the orchestration, not a synthetic cell center."""
    from channeld_tpu.core.settings import global_settings
    from channeld_tpu.spatial.controller import SpatialInfo
    from channeld_tpu.spatial.tpu_controller import TPUSpatialController

    global_settings.tpu_entity_capacity = 64
    global_settings.tpu_query_capacity = 8
    ctl = TPUSpatialController()
    ctl.load_config(dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100,
                         GridHeight=100, GridCols=2, GridRows=1, ServerCols=2,
                         ServerRows=1, ServerInterestBorderSize=1))
    set_spatial_controller(ctl)

    seen = []
    orig_notify = StaticGrid2DSpatialController.notify_crossings

    def spy(self, crossings):
        seen.extend((old, new) for old, new, _p in crossings)

    StaticGrid2DSpatialController.notify_crossings = spy
    try:
        eid = E + 6
        ctl.track_entity(eid, SpatialInfo(40.0, 0.0, 60.0))
        ctl.tick()
        # Movement with a distinctive real old position inside cell 0.
        ctl.notify(SpatialInfo(40.0, 0.0, 60.0), SpatialInfo(170.0, 0.0, 30.0),
                   lambda s, d: eid)
        ctl.tick()
        assert len(seen) == 1
        old_info, new_info = seen[0]
        assert (old_info.x, old_info.z) == (40.0, 60.0)  # true, not (50, 50)
        assert (new_info.x, new_info.z) == (170.0, 30.0)
    finally:
        StaticGrid2DSpatialController.notify_crossings = orig_notify


def test_stationary_entity_still_observed_by_device_controller():
    """An unmoved update fires no handover check, but the TPU controller
    must still learn the entity (tracking + follow-interest centering
    come from updates)."""
    from channeld_tpu.core.settings import global_settings
    from channeld_tpu.spatial.tpu_controller import TPUSpatialController

    global_settings.tpu_entity_capacity = 64
    global_settings.tpu_query_capacity = 8
    ctl = TPUSpatialController()
    ctl.load_config(dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100,
                         GridHeight=100, GridCols=2, GridRows=1, ServerCols=2,
                         ServerRows=1, ServerInterestBorderSize=1))

    data = sim_pb2.SimEntityChannelData()
    data.state.entityId = E + 7
    data.state.transform.position.x = 150.0
    data.state.transform.position.z = 50.0
    upd = sim_pb2.SimEntityChannelData()
    upd.state.entityId = E + 7
    upd.state.transform.position.x = 150.0  # unchanged position
    upd.state.transform.position.z = 50.0
    data.merge(upd, None, ctl)

    assert ctl.engine.entity_count() == 1
    info = ctl._last_positions[E + 7]
    assert (info.x, info.z) == (150.0, 50.0)
    assert E + 7 in ctl._providers


def test_first_stationary_observation_seeds_handover_baseline():
    """An entity first seen via an unmoved merge must still have its
    device baseline cell seeded — a crossing in the same tick window
    would otherwise start from prev_cell=-1 and never be detected."""
    from channeld_tpu.core.settings import global_settings
    from channeld_tpu.spatial.controller import SpatialInfo
    from channeld_tpu.spatial.tpu_controller import TPUSpatialController

    global_settings.tpu_entity_capacity = 64
    global_settings.tpu_query_capacity = 8
    ctl = TPUSpatialController()
    ctl.load_config(dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100,
                         GridHeight=100, GridCols=2, GridRows=1, ServerCols=2,
                         ServerRows=1, ServerInterestBorderSize=1))
    eid = E + 8
    ctl.observe_entity(eid, SpatialInfo(40.0, 0.0, 60.0))  # cell 0, no tick yet
    ctl.notify(SpatialInfo(40.0, 0.0, 60.0), SpatialInfo(170.0, 0.0, 30.0),
               lambda s, d: eid)  # crossing before the first engine tick
    result = ctl.engine.tick()
    crossings = ctl.engine.handover_list(result)
    assert crossings == [(eid, 0, 1)], crossings
