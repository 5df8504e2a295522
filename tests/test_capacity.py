"""Capacity-overflow policy: a full device table degrades to the host
path with a metric + security-log line — never an exception inside the
channel tick. The reference has no device tables;
its analog is that a full world simply keeps running the per-entity host
loops (spatial.go:612-858), which is exactly the degraded mode here."""

import pytest

from channeld_tpu.core.message import MessageContext
from channeld_tpu.core.types import ConnectionType, MessageType
from channeld_tpu.models import sim_pb2
from channeld_tpu.models.sim import register_sim_types
from channeld_tpu.protocol import control_pb2
from channeld_tpu.spatial.controller import SpatialInfo, set_spatial_controller
from channeld_tpu.spatial.tpu_controller import TPUSpatialController

from helpers import StubConnection, fresh_runtime

START = 0x10000
ENTITY_START = 0x80000


@pytest.fixture(autouse=True)
def runtime():
    gch = fresh_runtime()
    register_sim_types()
    yield gch


def entity_data(entity_id: int, x: float, z: float) -> sim_pb2.SimEntityChannelData:
    d = sim_pb2.SimEntityChannelData()
    d.state.entityId = entity_id
    d.state.transform.position.x = x
    d.state.transform.position.z = z
    return d


def make_tiny_world(entity_capacity=2, query_capacity=1):
    from channeld_tpu.core.settings import global_settings

    global_settings.tpu_entity_capacity = entity_capacity
    global_settings.tpu_query_capacity = query_capacity
    ctl = TPUSpatialController()
    ctl.load_config(
        dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100, GridHeight=100,
             GridCols=2, GridRows=1, ServerCols=2, ServerRows=1,
             ServerInterestBorderSize=1)
    )
    set_spatial_controller(ctl)
    servers = []
    for cid in (1, 2):
        server = StubConnection(cid, ConnectionType.SERVER)
        ctx = MessageContext(
            msg_type=MessageType.CREATE_CHANNEL,
            msg=control_pb2.CreateChannelMessage(),
            connection=server,
        )
        from channeld_tpu.core.subscription import subscribe_to_channel

        for ch in ctl.create_channels(ctx):
            subscribe_to_channel(server, ch, None)
        servers.append(server)
    return ctl, servers


def _shed_count(table: str) -> float:
    from channeld_tpu.core import metrics

    return metrics.tpu_capacity_shed.labels(table=table)._value.get()


def test_track_entity_at_capacity_sheds_not_raises():
    ctl, _ = make_tiny_world(entity_capacity=2)
    before = _shed_count("entity")
    for i in range(6):  # 4 beyond capacity
        ctl.track_entity(ENTITY_START + i, SpatialInfo(50, 0, 50))
    assert _shed_count("entity") == before + 4
    # The world still ticks (the device plane serves the resident two).
    ctl.tick()
    assert ctl.engine.entity_count() == 2
    # Shed entities remain host-tracked for follow centering etc.
    assert ENTITY_START + 5 in ctl._last_positions


def test_notify_at_capacity_runs_host_handover():
    """A shed entity's boundary crossing still hands over — through the
    host orchestration, synchronously at notify time."""
    from channeld_tpu.core.channel import create_entity_channel, get_channel
    from channeld_tpu.core.subscription import subscribe_to_channel

    ctl, (server_a, server_b) = make_tiny_world(entity_capacity=1)
    # Fill the table with an unrelated resident.
    ctl.track_entity(ENTITY_START + 1, SpatialInfo(50, 0, 50))

    eid = ENTITY_START + 2
    entity_ch = create_entity_channel(eid, server_a)
    entity_ch.init_data(entity_data(eid, 50, 50), None)
    entity_ch.spatial_notifier = ctl
    subscribe_to_channel(server_a, entity_ch, None)
    src_ch = get_channel(START)
    dst_ch = get_channel(START + 1)
    src_ch.get_data_message().add_entity(eid, entity_ch.get_data_message())

    before = _shed_count("entity")
    # Movement across the cell border: notify degrades to the host path
    # (the per-notify orchestration) instead of raising in the tick.
    entity_ch.data.on_update(entity_data(eid, 150, 50), 0, server_a.id, ctl)
    src_ch.tick_once(0)
    dst_ch.tick_once(0)
    assert _shed_count("entity") > before
    assert entity_ch.get_owner() is server_b
    assert eid in dst_ch.get_data_message().entities
    # And the device tick still runs clean afterwards.
    ctl.tick()


def test_readopted_shed_entity_keeps_handover():
    """Regression: an entity shed at track_entity and re-adopted after a
    slot frees must have its baseline seeded — its very first crossing
    after re-adoption hands over (a fresh prev-cell of -1 would hide it
    from detect_handovers and the host fallback alike)."""
    from channeld_tpu.core.channel import create_entity_channel, get_channel
    from channeld_tpu.core.subscription import subscribe_to_channel

    ctl, (server_a, server_b) = make_tiny_world(entity_capacity=1)
    blocker = ENTITY_START + 1
    ctl.track_entity(blocker, SpatialInfo(50, 0, 50))  # fills the table

    eid = ENTITY_START + 2
    entity_ch = create_entity_channel(eid, server_a)
    entity_ch.init_data(entity_data(eid, 40, 50), None)
    entity_ch.spatial_notifier = ctl
    subscribe_to_channel(server_a, entity_ch, None)
    src_ch = get_channel(START)
    dst_ch = get_channel(START + 1)
    src_ch.get_data_message().add_entity(eid, entity_ch.get_data_message())

    ctl.track_entity(eid, SpatialInfo(40, 0, 50))  # shed: table full
    assert ctl.engine.slot_of_entity(eid) is None
    ctl.untrack_entity(blocker)  # a slot frees

    # Next movement re-adopts AND crosses: the handover must fire (the
    # re-adoption seeds prev from the old position; detection next tick).
    entity_ch.data.on_update(entity_data(eid, 150, 50), 0, server_a.id, ctl)
    assert ctl.engine.slot_of_entity(eid) is not None
    ctl.tick()
    src_ch.tick_once(0)
    dst_ch.tick_once(0)
    assert entity_ch.get_owner() is server_b
    assert eid in dst_ch.get_data_message().entities


def test_follow_interest_at_query_capacity_sheds():
    from channeld_tpu.ops.spatial_ops import AOI_SPHERE

    ctl, _ = make_tiny_world(query_capacity=1)
    eid = ENTITY_START + 3
    ctl.track_entity(eid, SpatialInfo(50, 0, 50))
    c1 = StubConnection(11, ConnectionType.CLIENT)
    c2 = StubConnection(12, ConnectionType.CLIENT)
    ctl.register_follow_interest(c1, eid, AOI_SPHERE, extent=(40.0, 0.0))
    before = _shed_count("query")
    ctl.register_follow_interest(c2, eid, AOI_SPHERE, extent=(40.0, 0.0))
    assert _shed_count("query") == before + 1
    assert c2.id not in ctl._followers  # shed, not half-registered
    ctl.tick()  # world keeps ticking
