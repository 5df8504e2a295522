"""``tanks-2x2``'s shapes at a small size on the CPU, held to the plain
reference (``benchmark/harness/reference.py``: float64 on the float32 the
wire carries; it imports nothing of the program).

The deployment's own world (4x4 cells of 50 units at -100, 2x2 servers,
a border of 1) and its hifi windows (20 ms for every channel type), 32
tanks on seeded ``walk`` schedules, 16 standing spheres of 10 units, 384
updates. The controller and its channels are driven in process through
the program's own message handlers, as ``tests/test_handover.py`` and
``tests/test_device_fanout.py`` drive them; the clients keep their
subscriptions with the benchmark's own receiver
(``benchmark/harness/workers.py:Receiver``), fed from the stub
connections instead of sockets. Held equal to the reference: the block
each server owns, the cell of every update, the handovers each server
reads, each client's interest and the rows each client is sent. One
planted fault, positions rounded to bfloat16 on their way to the engine,
does not come out equal.
"""

import collections
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.generators import walk  # noqa: E402
from benchmark.harness import stats, wire  # noqa: E402
from benchmark.harness.reference import Grid  # noqa: E402
from benchmark.harness.workers import (  # noqa: E402
    Receiver, _Client, client_centres,
)

import channeld_tpu.core.connection as connection_mod  # noqa: E402
from channeld_tpu.core.channel import all_channels, get_channel  # noqa: E402
from channeld_tpu.core.message import MESSAGE_MAP  # noqa: E402
from channeld_tpu.core.settings import global_settings  # noqa: E402
from channeld_tpu.core.types import (  # noqa: E402
    ChannelType, ConnectionType, MessageType,
)
from channeld_tpu.models.sim import register_sim_types  # noqa: E402
from channeld_tpu.ops.engine import SpatialEngine  # noqa: E402
from channeld_tpu.protocol import control_pb2, spatial_pb2, wire_pb2  # noqa: E402
from channeld_tpu.spatial.controller import set_spatial_controller  # noqa: E402
from channeld_tpu.spatial.tpu_controller import TPUSpatialController  # noqa: E402

from helpers import StubConnection, fresh_runtime  # noqa: E402

SCC = os.path.join(REPO, "config", "spatial_tpu_4x4.json")
HIFI = os.path.join(REPO, "config", "channel_settings_hifi.json")
TANKS, CLIENTS, RADIUS, ROUNDS = 32, 16, 10.0, 12
MIX = {"speed": 12.0, "rate": 1, "frame_ms": 20}
WINDOW_S = 0.021  # one hifi window and a little


def bf16(a):
    """float32 values rounded to bfloat16 (nearest even)."""
    bits = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def layout(grid: Grid) -> dict:
    """The first seed whose schedule has at least two updates that
    bfloat16 puts in another cell (so the planted fault has to show),
    with the reference's reading of it."""
    for seed in range(1, 200):
        sched = walk.schedule(grid, TANKS, MIX, [seed, 0], ROUNDS - 1.0)
        pos = sched["pos"][:ROUNDS]
        cells = grid.cells_of(pos[..., 0], pos[..., 1])
        rounded = grid.cells_of(bf16(pos[..., 0]), bf16(pos[..., 1]))
        if ((rounded != cells) & (rounded >= 0)).sum() >= 2:
            break
    else:
        raise AssertionError("no seed moves an update under bfloat16")
    start = sched["start"]
    start_cells = grid.cells_of(start[:, 0], start[:, 1])
    return {"pos": pos, "start": start, "cells": cells,
            "start_cells": start_cells,
            "prev": np.concatenate([start_cells[None, :], cells[:-1]]),
            "centres": client_centres(grid, CLIENTS, RADIUS, [seed, 1])}


class Peer:
    """What the receiver knows of a client's socket, over a stub
    connection: ``queue`` hands the message to the program's handler."""

    def __init__(self, world: "World", conn: StubConnection):
        self.world, self.conn, self.conn_id = world, conn, conn.id
        self.cursor = 0  # how much of ``conn.sent`` has been read

    def queue(self, channel_id: int, msg_type: int, body: bytes) -> None:
        self.world.send(self.conn, channel_id, msg_type,
                        MESSAGE_MAP[msg_type].template.FromString(body))


class Clients(Receiver):
    """The benchmark's receiver with the sockets taken out: the same
    bookkeeping of subscriptions, reads and wrong rows."""

    def __init__(self, world: "World", pos: np.ndarray):
        self.grid, self.spec = world.grid, {"entities": TANKS}
        self.plan = {"pos": pos}
        self.first_eid = world.first_eid
        self.clients, self.lags = {}, []
        self.crossing_seqs = collections.defaultdict(list)
        for k, n, src, dst in zip(*stats.crossings(
                world.layout["start_cells"], world.layout["cells"])):
            self.crossing_seqs[int(n), int(src), int(dst)].append(int(k) + 1)
        self.wrong_rows = self.rows = 0

    def read(self) -> None:
        """Everything the stubs were sent since the last read."""
        for peer in self.clients:
            sent = peer.conn.sent
            while peer.cursor < len(sent):
                ctx = sent[peer.cursor]
                peer.cursor += 1
                if ctx.msg is not None:
                    self.on_message(peer, time.monotonic(), wire_pb2.MessagePack(
                        channelId=ctx.channel_id, msgType=ctx.msg_type,
                        msgBody=ctx.msg.SerializeToString()))


class World:
    """The deployment in process: the controller on the 4x4 world, four
    spatial servers, the tanks and the clients, every message through
    the handler the wire would reach."""

    def __init__(self):
        fresh_runtime()
        register_sim_types()
        global_settings.load_channel_settings(HIFI)
        global_settings.tpu_entity_capacity = 64
        global_settings.tpu_query_capacity = 32
        self.grid = Grid.load(SCC, global_settings.spatial_channel_id_start,
                              global_settings.entity_channel_id_start)
        self.first_eid = self.grid.entity_start + 1
        self.layout = layout(self.grid)
        with open(SCC) as f:
            config = json.load(f)["Config"]
        self.ctl = TPUSpatialController()
        self.ctl.load_config(config)
        set_spatial_controller(self.ctl)
        self.master = StubConnection(1, ConnectionType.SERVER)
        self.send(self.master, 0, MessageType.CREATE_CHANNEL,
                  control_pb2.CreateChannelMessage(
                      channelType=ChannelType.GLOBAL))
        self.tick()
        self.servers = [StubConnection(2 + i, ConnectionType.SERVER)
                        for i in range(self.grid.num_servers)]
        write = control_pb2.ChannelSubscriptionOptions(
            dataAccess=wire.WRITE_ACCESS)
        for server in self.servers:
            self.send(server, 0, MessageType.CREATE_CHANNEL,
                      control_pb2.CreateChannelMessage(
                          channelType=ChannelType.SPATIAL,
                          data=wire.pack_any(
                              wire.sim_pb2.SimSpatialChannelData()),
                          subOptions=write))
            self.tick()
        self.owner_of = {  # cell -> index of the server that owns it
            ch.id - self.grid.cell_start: self.servers.index(ch.get_owner())
            for ch in all_channels().values()
            if ch.channel_type == ChannelType.SPATIAL}
        # The tanks, each created by its cell's owner with a transform,
        # and every cell's table as a running world has it.
        tables: dict = {}
        for n, cell in enumerate(self.layout["start_cells"]):
            x, z = map(float, self.layout["start"][n])
            eid = self.first_eid + n
            state = wire.entity_state(eid, x, z, 0, 0.0)
            self.send(self.servers[self.owner_of[int(cell)]], 0,
                      MessageType.CREATE_ENTITY_CHANNEL,
                      spatial_pb2.CreateEntityChannelMessage(
                          entityId=eid, subOptions=write,
                          data=wire.pack_any(
                              wire.sim_pb2.SimEntityChannelData(state=state))))
            tables.setdefault(int(cell), wire.sim_pb2.SimSpatialChannelData()
                              ).entities[eid].CopyFrom(state)
        self.tick()
        for cell, table in tables.items():
            self.update(self.servers[self.owner_of[cell]],
                        self.grid.cell_start + cell, table)
        self.tick()
        # The clients, one standing sphere each.
        self.clients = Clients(self, self.layout["pos"])
        for i, (cx, cz, cells) in enumerate(self.layout["centres"]):
            conn = StubConnection(100 + i, ConnectionType.CLIENT)
            connection_mod._all_connections[conn.id] = conn
            peer = Peer(self, conn)
            self.clients.clients[peer] = _Client(peer, cells, TANKS)
            self.send(self.master, self.grid.cell_start,
                      MessageType.UPDATE_SPATIAL_INTEREST,
                      spatial_pb2.UpdateSpatialInterestMessage.FromString(
                          wire.sphere_interest(conn.id, cx, cz, RADIUS)))
        self.settle(lambda: False, 6)

    def send(self, conn, channel_id: int, msg_type: int, msg) -> None:
        ch = get_channel(channel_id)
        assert ch is not None, channel_id
        assert ch.put_message(
            msg, MESSAGE_MAP[msg_type].handler, conn,
            wire_pb2.MessagePack(channelId=channel_id, msgType=msg_type))

    def update(self, conn, channel_id: int, data) -> None:
        self.send(conn, channel_id, MessageType.CHANNEL_DATA_UPDATE,
                  control_pb2.ChannelDataUpdateMessage(
                      data=wire.pack_any(data)))

    def tick(self) -> None:
        """Every channel once, GLOBAL (and with it the device step) first."""
        for ch in list(all_channels().values()):
            if not ch.is_removing():
                ch.tick_once(ch.get_time())

    def settle(self, done, most: int) -> None:
        """Tick a window at a time until ``done`` or ``most`` windows."""
        for _ in range(most):
            self.tick()
            self.clients.read()
            if done():
                return
            time.sleep(WINDOW_S)

    def handovers_read(self) -> dict:
        """{(server index, n, src, dst): times read}, over every server."""
        seen: dict = collections.Counter()
        for index, server in enumerate(self.servers):
            for ctx in server.sent:
                if ctx.msg_type != MessageType.CHANNEL_DATA_HANDOVER:
                    continue
                src = ctx.msg.srcChannelId - self.grid.cell_start
                dst = ctx.msg.dstChannelId - self.grid.cell_start
                for state in wire.states_in(ctx.msg.data):
                    seen[index, state.entityId - self.first_eid, src, dst] += 1
        return seen

    def cells_holding(self, n: int) -> list:
        """The cells whose table has a row of tank ``n``."""
        eid = self.first_eid + n
        return [c for c in range(self.grid.num_cells)
                if eid in get_channel(self.grid.cell_start + c)
                .get_data_message().entities]


def drive(world: World, most: int) -> dict:
    """Send the schedule a round at a time, each update by the owner of
    the cell it leaves, and hold every answer against the reference;
    the counts of what differs."""
    lay, grid, clients = world.layout, world.grid, world.clients
    cells, prev, pos = lay["cells"], lay["prev"], lay["pos"]
    wrong_cells = 0
    for k in range(ROUNDS):
        tables: dict = {}
        for n in range(TANKS):
            server = world.servers[world.owner_of[int(prev[k, n])]]
            eid = world.first_eid + n
            state = wire.entity_state(eid, float(pos[k, n, 0]),
                                      float(pos[k, n, 1]), k + 1, 0.0)
            world.update(server, eid,
                         wire.sim_pb2.SimEntityChannelData(state=state))
            if cells[k, n] == prev[k, n]:
                # The row of the cell's table; a crossing tank's row is
                # moved by the gateway's handover.
                tables.setdefault((server, int(cells[k, n])),
                                  wire.sim_pb2.SimSpatialChannelData()
                                  ).entities[eid].CopyFrom(state)
        for (server, cell), table in tables.items():
            world.update(server, grid.cell_start + cell, table)

        def reflected() -> bool:
            return all(
                c.last[n] >= k + 1
                for c in clients.clients.values() for n in range(TANKS)
                if int(cells[k, n]) in c.covers) and all(
                world.cells_holding(n) == [int(cells[k, n])]
                for n in range(TANKS))

        world.settle(reflected, most)
        wrong_cells += sum(world.cells_holding(n) != [int(cells[k, n])]
                           for n in range(TANKS))
    world.settle(lambda: False, 3)

    # Handovers: each crossing read exactly once by both owners.
    times: dict = collections.Counter()
    owners: dict = {}
    for k, n, src, dst in zip(*stats.crossings(lay["start_cells"], cells)):
        pair = (int(n), int(src), int(dst))
        times[pair] += 1
        owners[pair] = {world.owner_of[int(src)], world.owner_of[int(dst)]}
    checks = stats.handover_account(times, owners, world.handovers_read())
    checks["crossings"] = sum(times.values())
    checks["cells_wrong"] = wrong_cells
    # Interest: each client's spatial subscriptions, and the rows it read.
    checks["interest_mismatched"] = sum(
        sorted(ch - grid.cell_start for ch in c.subscribed
               if grid.cell_start <= ch < grid.entity_start) != sorted(c.covers)
        for c in clients.clients.values())
    unreflected = deliveries = 0
    for c in clients.clients.values():
        covers = np.zeros(grid.num_cells, bool)
        covers[list(c.covers)] = True
        rec = np.array(c.reads, np.float64).reshape(-1, 3).T
        _, _, _, read = stats.delivery_times(
            cells, np.zeros(cells.shape), covers, rec)
        deliveries += len(read)
        unreflected += int(np.isnan(read).sum())
    checks["deliveries"] = deliveries
    checks["deliveries_unreflected"] = unreflected
    checks["rows_wrong"] = clients.wrong_rows
    checks["rows_read"] = clients.rows
    return checks


FAULTS = ("handovers_lost", "handovers_duplicated", "handovers_unpredicted",
          "cells_wrong", "interest_mismatched", "deliveries_unreflected",
          "rows_wrong")


def test_the_servers_own_the_references_blocks():
    world = World()
    for cell, server in world.owner_of.items():
        assert server == world.grid.server_of_cell(cell), cell
    assert len(world.owner_of) == 16 and len(world.servers) == 4
    for kind in (ChannelType.SPATIAL, ChannelType.ENTITY):
        settings = global_settings.get_channel_settings(kind)
        assert (settings.tick_interval_ms,
                settings.default_fanout_interval_ms) == (20, 20)


def test_the_system_equals_the_plain_reference():
    world = World()
    checks = drive(world, most=40)
    assert {name: checks[name] for name in FAULTS} == dict.fromkeys(FAULTS, 0)
    # ... and the run was not empty: tanks crossed, clients read rows.
    assert checks["crossings"] >= 10 and checks["deliveries"] >= 100
    assert checks["rows_read"] >= checks["deliveries"]


def test_positions_rounded_to_bfloat16_do_not(monkeypatch):
    """The control the chip runs (``--control bf16``) at this size: the
    engine is handed every position in the nearest precision below the
    configuration's float32, and the comparison has to say so."""
    update = SpatialEngine.update_entity

    def update_entity(self, entity_id, x, y, z):
        return update(self, entity_id, float(bf16(x)), y, float(bf16(z)))

    monkeypatch.setattr(SpatialEngine, "update_entity", update_entity)
    world = World()
    checks = drive(world, most=6)
    assert any(checks[name] for name in FAULTS), checks
    assert checks["cells_wrong"] or checks["handovers_lost"], checks
