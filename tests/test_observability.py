"""Pinning tests for the observability surface: C17 metrics families,
C18 profiling modes (incl. the asyncio task-dump analog of
`-profile=goroutine`), and the C22 debug-regions handler
(ref: metrics.go:7-131, profiling.go:12-31, message_debug.go:8-39)."""

import asyncio

import pytest

from channeld_tpu.core.types import ConnectionType, MessageType
from channeld_tpu.protocol import control_pb2

from helpers import StubConnection, fresh_runtime


@pytest.fixture(autouse=True)
def runtime():
    yield fresh_runtime()


# ---- C17: metric families (reference names must not drift) ---------------


def test_reference_metric_families_exported():
    """The reference's Prometheus families (metrics.go:7-131) all exist
    under the same names, plus the TPU decision-plane additions."""
    from channeld_tpu.core.metrics import registry

    names = {m.name for m in registry.collect()}
    # Counters lose their _total suffix in collect(); Gauges keep names.
    for family in (
        "messages_in", "messages_out", "packets_in", "packets_out",
        "bytes_in", "bytes_out", "packets_drop", "packets_frag",
        "packets_comb", "connection_num", "channel_num",
        "channel_tick_duration", "connection_closed", "logs",
        # channeld-tpu decision-plane families.
        "fanout_decision_latency_seconds", "tpu_spatial_step_seconds",
        "tpu_entities", "tpu_cell_overflow", "tpu_capacity_shed",
    ):
        assert family in names, f"metric family {family} missing"


def test_message_traffic_updates_counters():
    """The receive path increments the same families the reference does
    (receiveMessage -> msgReceived, connection.go:547-615)."""
    from channeld_tpu.core import metrics
    from channeld_tpu.core.connection import add_connection

    from helpers import FakeTransport
    from channeld_tpu.protocol import encode_packet, wire_pb2

    def sample(counter, conn_type):
        return counter.labels(conn_type=conn_type)._value.get()

    before = sample(metrics.packet_received, "CLIENT")
    conn = add_connection(FakeTransport(), ConnectionType.CLIENT)
    pkt = wire_pb2.Packet()
    mp = pkt.messages.add()
    mp.msgType = MessageType.AUTH
    mp.msgBody = control_pb2.AuthMessage(
        playerIdentifierToken="pit", loginToken="lt"
    ).SerializeToString()
    conn.on_bytes(encode_packet(pkt))
    assert sample(metrics.packet_received, "CLIENT") == before + 1


# ---- C18: profiling modes -------------------------------------------------


def test_cpu_and_mem_profiles_write_files(tmp_path):
    from channeld_tpu.core import profiling

    profiling.start_profiling("cpu", str(tmp_path))
    sum(i * i for i in range(1000))
    path = profiling.stop_profiling()
    assert path and path.endswith(".pstats")

    profiling.start_profiling("mem", str(tmp_path))
    _ = [bytearray(100) for _ in range(100)]
    path = profiling.stop_profiling()
    assert path and path.endswith(".txt")


def test_task_dump_names_live_tasks(tmp_path):
    """`-profile tasks`: the goroutine-dump analog captures every live
    asyncio task with its stack."""
    from channeld_tpu.core import profiling

    async def scenario():
        async def worker():
            await asyncio.sleep(10)

        task = asyncio.get_running_loop().create_task(
            worker(), name="channel-tick-47"
        )
        await asyncio.sleep(0)  # let it park in the sleep
        text = profiling.dump_tasks()
        task.cancel()
        return text

    text = asyncio.run(scenario())
    assert "channel-tick-47" in text
    assert "worker" in text
    assert "=== threads:" in text

    # The armed mode writes the dump to the profile path on stop.
    from channeld_tpu.core import profiling as p

    p.start_profiling("tasks", str(tmp_path))
    path = p.stop_profiling()
    assert path and path.endswith(".txt")
    assert "asyncio tasks" in open(path).read()


def test_unknown_profile_kind_rejected():
    from channeld_tpu.core import profiling

    with pytest.raises(ValueError):
        profiling.start_profiling("goroutine")


# ---- C22: debug regions handler ------------------------------------------


def _regions_world():
    from channeld_tpu.core.message import MessageContext
    from channeld_tpu.core.subscription import subscribe_to_channel
    from channeld_tpu.spatial.controller import set_spatial_controller
    from channeld_tpu.spatial.grid import StaticGrid2DSpatialController

    ctl = StaticGrid2DSpatialController()
    ctl.load_config(dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100,
                         GridHeight=100, GridCols=2, GridRows=1,
                         ServerCols=1, ServerRows=1,
                         ServerInterestBorderSize=1))
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


def test_debug_get_spatial_regions_dev_mode_only():
    """(ref: message_debug.go:8-39): dev mode returns the region table as
    SPATIAL_REGIONS_UPDATE; production mode refuses."""
    from channeld_tpu.core.message import MessageContext
    from channeld_tpu.core.settings import global_settings
    from channeld_tpu.protocol import spatial_pb2
    from channeld_tpu.spatial.messages import (
        handle_debug_get_spatial_regions,
    )

    ctl, server = _regions_world()
    client = StubConnection(5, ConnectionType.CLIENT)
    ctx = MessageContext(
        msg_type=MessageType.DEBUG_GET_SPATIAL_REGIONS,
        msg=spatial_pb2.DebugGetSpatialRegionsMessage(),
        connection=client,
        channel_id=0,
    )

    global_settings.development = False
    handle_debug_get_spatial_regions(ctx)
    assert not [c for c in client.sent
                if c.msg_type == MessageType.SPATIAL_REGIONS_UPDATE]

    global_settings.development = True
    handle_debug_get_spatial_regions(ctx)
    updates = [c for c in client.sent
               if c.msg_type == MessageType.SPATIAL_REGIONS_UPDATE]
    assert len(updates) == 1
    regions = updates[0].msg.regions
    # 2x1 world, one server: the region table covers both columns
    # (ref: spatial.go:319-356 GetRegions).
    assert len(regions) >= 1
    assert {r.serverIndex for r in regions} == {0}


def test_introspect_names_the_device_the_engine_holds(runtime):
    """A gateway on the chip must be tellable from one that fell back to
    the CPU: /introspect carries what the engine logged at construction."""
    from channeld_tpu.core.opshttp import introspect
    from channeld_tpu.spatial.controller import set_spatial_controller
    from channeld_tpu.spatial.tpu_controller import TPUSpatialController

    assert introspect()["engine"] is None  # no device controller yet
    ctl = TPUSpatialController()
    ctl.load_config(dict(
        WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100, GridHeight=100,
        GridCols=2, GridRows=2, ServerCols=1, ServerRows=1,
    ))
    set_spatial_controller(ctl)
    engine = introspect()["engine"]
    assert engine == ctl.engine.device_info
    assert engine["platform"] == "cpu" and engine["device_count"] >= 1
    assert engine["mesh"] is None and engine["use_pallas"] is False
    assert set(engine) == {"platform", "device_kind", "device_count", "mesh",
                           "use_pallas", "native_codec"}
