"""Live chaos soak: a full gateway under deterministic fault injection.

Boots the real gateway stack in-process — TCP listeners, the 1ms flush
pump, per-channel tick tasks, the TPU spatial controller on the
cells-sharded serving plane (``config/spatial_tpu_cells_2x2.json``) with
a deliberately undersized ``CellBucket`` — then presses it with:

- a master server possessing GLOBAL and 4 spatial servers building the
  4x4 world through the real CREATE_CHANNEL message path,
- a fleet of real TCP clients streaming sequence-stamped user-space
  forwards (the reference's headline routing path) that reconnect and
  re-auth whenever a fault kills their socket,
- a seeded entity sim driving the real entity-data merge -> spatial
  notify -> batched device handover orchestration, with periodic
  "storm" phases that march a crowd across a cell boundary to force
  handover bursts and cells-plane bucket overflow (the live shed +
  re-offer path, spatial/tpu_controller.py),
- an armed chaos scenario (channeld_tpu.chaos) firing transport resets,
  truncated/corrupt frames, EOF races, fake queue-full backpressure,
  tick-budget stalls, and device dispatch stalls.

After the soak, traffic stops, the injector disarms, a quiesce window
lets everything drain, and the invariant checker asserts the gateway
degraded — never broke:

- no lost entities (every entity still device/host-tracked AND present
  in exactly one spatial channel's data),
- exact message accounting (owner-drained == gateway-counted received;
  per-client sequences strictly increasing, no duplicates),
- every client that lost its socket recovered within the deadline,
- GLOBAL tick p99 bounded,
- the overflow shed demonstrably fired (cumulative counter > 0) and
  handovers were orchestrated.

Emits a ``SOAK_*.json`` artifact with the scenario, the fault journal,
the invariant results, and a metrics summary.

Run the acceptance soak (120s):
  python scripts/chaos_soak.py --duration 120 --out SOAK_r06.json

The <60s CI smoke runs the same machinery with smaller numbers
(tests/test_chaos.py::test_chaos_smoke_soak).
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# 8 virtual CPU devices for the cells-sharded plane (before jax loads);
# CHTPU_SOAK_TPU=1 leaves the platform to JAX to soak against a real chip.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
if os.environ.get("CHTPU_SOAK_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"  # gateway children inherit it

import argparse
import asyncio
import json
import struct
import time
from dataclasses import dataclass, field
from random import Random

DEFAULT_SCENARIO = {
    "name": "cells-soak",
    "seed": 20260803,
    # Undersized redistribution bucket: storm crowds overflow it, the
    # shed fires, and the undelivered entities re-offer next tick.
    "config_overrides": {"CellBucket": 6},
    "faults": [
        {"point": "transport.reset", "every_n": 700, "max_fires": 30},
        {"point": "transport.truncate", "every_n": 1150, "max_fires": 15},
        {"point": "transport.corrupt", "every_n": 1400, "max_fires": 15},
        {"point": "connection.eof_race", "every_n": 1800, "max_fires": 10},
        {"point": "connection.queue_full", "every_n": 900, "burst": 3},
        {"point": "channel.tick_budget", "every_n": 500,
         "stall_ms": 15, "max_fires": 60},
        {"point": "device.dispatch_stall", "every_n": 90,
         "stall_ms": 40, "max_fires": 40},
    ],
}


@dataclass
class SoakParams:
    duration_s: float = 120.0
    clients: int = 24
    entities: int = 160
    msg_rate: float = 25.0  # per client
    storm_every_s: float = 10.0
    storm_size: int = 48
    recovery_deadline_s: float = 8.0
    tick_p99_bound_s: float = 1.5
    quiesce_s: float = 10.0
    config_path: str = os.path.join(REPO, "config", "spatial_tpu_cells_2x2.json")
    scenario: dict = field(default_factory=lambda: dict(DEFAULT_SCENARIO))
    out_path: str = ""
    entity_capacity: int = 256
    query_capacity: int = 32


@dataclass
class SoakStats:
    client_sent: dict = field(default_factory=dict)  # idx -> frames written
    drained: dict = field(default_factory=dict)  # idx -> list of seqs
    disconnects: int = 0
    reconnects: int = 0
    recovery_latencies: list = field(default_factory=list)
    auth_retries: int = 0


def _frame(msg_type: int, body: bytes, channel_id: int = 0) -> bytes:
    from channeld_tpu.protocol import encode_packet, wire_pb2

    return encode_packet(wire_pb2.Packet(messages=[wire_pb2.MessagePack(
        channelId=channel_id, msgType=msg_type, msgBody=body,
    )]))


def _auth_frame(pit: str) -> bytes:
    from channeld_tpu.core.types import MessageType
    from channeld_tpu.protocol import control_pb2

    return _frame(MessageType.AUTH, control_pb2.AuthMessage(
        playerIdentifierToken=pit, loginToken="soak",
    ).SerializeToString())


async def _read_frames(reader, on_pack, stop) -> None:
    """Drain a socket into per-MessagePack callbacks until EOF/stop."""
    from channeld_tpu.protocol import FrameDecoder

    dec = FrameDecoder()
    while not stop.is_set():
        try:
            data = await reader.read(65536)
        except (ConnectionError, OSError):
            return
        if not data:
            return
        for packet in dec.decode_packets(data):
            for mp in packet.messages:
                on_pack(mp)


# ---- control plane: master + spatial servers ------------------------------


async def _connect(host: str, port: int):
    return await asyncio.open_connection(host, port)


async def _auth_and_wait(reader, writer, pit: str, timeout: float = 5.0):
    """AUTH and wait for the result frame (any first frame back)."""
    writer.write(_auth_frame(pit))
    await writer.drain()
    from channeld_tpu.protocol import FrameDecoder

    dec = FrameDecoder()
    deadline = time.monotonic() + timeout
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise TimeoutError(f"auth timeout for {pit}")
        data = await asyncio.wait_for(reader.read(65536), timeout=remaining)
        if not data:
            raise ConnectionError(f"closed during auth of {pit}")
        packets = dec.decode_packets(data)
        if any(p.messages for p in packets):
            return


async def _boot_world(host: str, server_port: int, stats: SoakStats,
                      stop: asyncio.Event):
    """Master (GLOBAL owner + forward drain) and 4 spatial servers."""
    from channeld_tpu.core.channel import all_channels
    from channeld_tpu.core.settings import global_settings
    from channeld_tpu.core.types import (
        ChannelDataAccess,
        ChannelType,
        MessageType,
    )
    from channeld_tpu.protocol import control_pb2, wire_pb2

    # Master possesses GLOBAL; its reader is the owner drain that counts
    # every routed client forward (the accounting invariant's far end).
    m_reader, m_writer = await _connect(host, server_port)
    await _auth_and_wait(m_reader, m_writer, "soak-master")
    m_writer.write(_frame(
        MessageType.CREATE_CHANNEL,
        control_pb2.CreateChannelMessage(
            channelType=ChannelType.GLOBAL).SerializeToString(),
    ))
    await m_writer.drain()

    def _on_master_pack(mp) -> None:
        if mp.msgType < 100:
            return
        sfm = wire_pb2.ServerForwardMessage()
        try:
            sfm.ParseFromString(mp.msgBody)
            cid, seq = struct.unpack("<II", sfm.payload[:8])
        except Exception:
            return
        stats.drained.setdefault(cid, []).append(seq)

    drain_task = asyncio.ensure_future(
        _read_frames(m_reader, _on_master_pack, stop)
    )

    # 4 spatial servers claim their authority blocks through the real
    # CREATE_CHANNEL(SPATIAL) path.
    spatial_socks = []
    for i in range(4):
        r, w = await _connect(host, server_port)
        await _auth_and_wait(r, w, f"soak-spatial-{i}")
        w.write(_frame(
            MessageType.CREATE_CHANNEL,
            control_pb2.CreateChannelMessage(
                channelType=ChannelType.SPATIAL,
                subOptions=control_pb2.ChannelSubscriptionOptions(
                    dataAccess=ChannelDataAccess.WRITE_ACCESS,
                ),
            ).SerializeToString(),
        ))
        await w.drain()
        # Their fan-out traffic must drain or the gateway sheds them.
        task = asyncio.ensure_future(_read_frames(r, lambda mp: None, stop))
        spatial_socks.append((r, w, task))

    # World ready: all 16 spatial channels exist and are owned.
    start = global_settings.spatial_channel_id_start
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        spatial = [ch for cid, ch in all_channels().items()
                   if start <= cid < global_settings.entity_channel_id_start]
        if len(spatial) == 16 and all(ch.has_owner() for ch in spatial):
            break
        await asyncio.sleep(0.1)
    else:
        raise RuntimeError("spatial world failed to come up")
    return (m_reader, m_writer, drain_task), spatial_socks


# ---- client fleet ----------------------------------------------------------


async def _client_loop(idx: int, host: str, port: int, rate: float,
                       stats: SoakStats, stop: asyncio.Event,
                       send_stop: asyncio.Event) -> None:
    """One dumb client: connect, auth, stream seq-stamped forwards;
    reconnect (and measure the outage) whenever the gateway side dies."""
    seq = 0
    interval = 1.0 / rate
    disconnected_at = None
    while not stop.is_set():
        writer = None
        try:
            reader, writer = await _connect(host, port)
            await _auth_and_wait(reader, writer, f"soak-client-{idx}",
                                 timeout=1.5)
        except (ConnectionError, OSError, TimeoutError):
            stats.auth_retries += 1
            if writer is not None:
                # Close the half-authed socket NOW: a lingering
                # unauthenticated conn would trip the anti-DDoS reaper
                # and blacklist the loopback IP for the whole fleet.
                try:
                    writer.close()
                except Exception:
                    pass
            await asyncio.sleep(0.1)
            continue
        if disconnected_at is not None:
            stats.recovery_latencies.append(time.monotonic() - disconnected_at)
            stats.reconnects += 1
            disconnected_at = None
        eof = asyncio.Event()

        def _on_pack(mp, _eof=eof):
            pass  # nothing expected beyond auth; just drain

        reader_task = asyncio.ensure_future(
            _read_frames(reader, _on_pack, stop)
        )
        try:
            while not stop.is_set():
                if send_stop.is_set():
                    # Traffic phase over: hold the socket open quietly.
                    await asyncio.sleep(0.2)
                    if reader_task.done():
                        raise ConnectionError("gateway closed the socket")
                    continue
                if reader_task.done():  # EOF: the gateway dropped us
                    raise ConnectionError("gateway closed the socket")
                body = struct.pack("<II", idx, seq)
                writer.write(_frame(100, body))
                await writer.drain()
                seq += 1
                stats.client_sent[idx] = stats.client_sent.get(idx, 0) + 1
                await asyncio.sleep(interval)
        except (ConnectionError, OSError):
            stats.disconnects += 1
            disconnected_at = time.monotonic()
        finally:
            reader_task.cancel()
            try:
                writer.close()
            except Exception:
                pass
        if not stop.is_set() and disconnected_at is None:
            # send loop exited without an error (stop flags): keep socket
            break
    # leave the connection to the gateway's teardown


# ---- entity sim ------------------------------------------------------------


class EntitySim:
    """Seeded random-walk world over the 4x4 grid with storm phases that
    march a crowd across one boundary (handover burst + bucket overflow)."""

    def __init__(self, ctl, params: SoakParams, rng: Random):
        self.ctl = ctl
        self.p = params
        self.rng = rng
        self.positions: dict[int, tuple[float, float]] = {}
        self.entity_ids: list[int] = []
        self.storming = False

    def world_xz(self) -> tuple[float, float, float, float]:
        c = self.ctl
        x0 = c.world_offset_x + 1.0
        z0 = c.world_offset_z + 1.0
        x1 = c.world_offset_x + c.grid_width * c.grid_cols - 1.0
        z1 = c.world_offset_z + c.grid_height * c.grid_rows - 1.0
        return x0, z0, x1, z1

    def create_entities(self) -> None:
        from channeld_tpu.core.channel import (
            create_entity_channel,
            get_channel,
        )
        from channeld_tpu.core.settings import global_settings
        from channeld_tpu.core.subscription import subscribe_to_channel
        from channeld_tpu.models import sim_pb2
        from channeld_tpu.spatial.controller import SpatialInfo

        x0, z0, x1, z1 = self.world_xz()
        estart = global_settings.entity_channel_id_start
        for i in range(self.p.entities):
            eid = estart + 1 + i
            x = self.rng.uniform(x0, x1)
            z = self.rng.uniform(z0, z1)
            info = SpatialInfo(x, 0, z)
            cell_ch = get_channel(self.ctl.get_channel_id(info))
            owner = cell_ch.get_owner()
            ch = create_entity_channel(eid, owner)
            d = sim_pb2.SimEntityChannelData()
            d.state.entityId = eid
            d.state.transform.position.x = x
            d.state.transform.position.z = z
            ch.init_data(d, None)
            ch.spatial_notifier = self.ctl
            if owner is not None:
                subscribe_to_channel(owner, ch, None)
            cell_ch.execute(
                lambda c, e=eid, dd=d: c.get_data_message().add_entity(e, dd)
            )
            self.ctl.track_entity(eid, info)
            self.positions[eid] = (x, z)
            self.entity_ids.append(eid)

    def _move(self, eid: int, x: float, z: float) -> None:
        from channeld_tpu.core.channel import get_channel
        from channeld_tpu.models import sim_pb2

        ch = get_channel(eid)
        if ch is None or ch.is_removing():
            return
        upd = sim_pb2.SimEntityChannelData()
        upd.state.entityId = eid
        upd.state.transform.position.x = x
        upd.state.transform.position.z = z

        def _apply(c, u=upd):
            owner = c.get_owner()
            c.data.on_update(
                u, c.get_time(), owner.id if owner is not None else 0,
                self.ctl,
            )

        ch.execute(_apply)
        self.positions[eid] = (x, z)

    def jitter_step(self) -> None:
        """Random walk for a sample of entities (bounded to the world)."""
        x0, z0, x1, z1 = self.world_xz()
        for eid in self.rng.sample(
            self.entity_ids, max(1, len(self.entity_ids) // 8)
        ):
            x, z = self.positions[eid]
            x = min(max(x + self.rng.uniform(-8, 8), x0), x1)
            z = min(max(z + self.rng.uniform(-8, 8), z0), z1)
            self._move(eid, x, z)

    def storm_gather(self) -> list[int]:
        """March a crowd into one target cell: a handover burst, and a
        density spike past the undersized CellBucket."""
        c = self.ctl
        col = self.rng.randrange(c.grid_cols)
        row = self.rng.randrange(c.grid_rows)
        cx = c.world_offset_x + (col + 0.5) * c.grid_width
        cz = c.world_offset_z + (row + 0.5) * c.grid_height
        crowd = self.rng.sample(
            self.entity_ids, min(self.p.storm_size, len(self.entity_ids))
        )
        for eid in crowd:
            self._move(
                eid,
                cx + self.rng.uniform(-c.grid_width * 0.4, c.grid_width * 0.4),
                cz + self.rng.uniform(-c.grid_height * 0.4, c.grid_height * 0.4),
            )
        return crowd

    def disperse(self, crowd: list[int]) -> None:
        x0, z0, x1, z1 = self.world_xz()
        for eid in crowd:
            self._move(eid, self.rng.uniform(x0, x1), self.rng.uniform(z0, z1))


# ---- the soak --------------------------------------------------------------


async def run_soak(p: SoakParams) -> dict:
    from channeld_tpu import chaos as chaos_mod
    from channeld_tpu.chaos import arm, chaos, disarm
    from channeld_tpu.chaos.invariants import (
        InvariantChecker,
        delta,
        histogram_quantile,
        sample_total,
        scrape,
    )
    from channeld_tpu.core import channel as channel_mod
    from channeld_tpu.core import connection as connection_mod
    from channeld_tpu.core import data as data_mod
    from channeld_tpu.core import ddos as ddos_mod
    from channeld_tpu.core import connection_recovery as recovery_mod
    from channeld_tpu.core.channel import get_channel, init_channels
    from channeld_tpu.core.connection import init_connections
    from channeld_tpu.core.ddos import init_anti_ddos, unauth_reaper_loop
    from channeld_tpu.core.server import flush_loop, start_listening
    from channeld_tpu.core.settings import (
        ChannelSettings,
        global_settings,
        reset_global_settings,
    )
    from channeld_tpu.core.types import ChannelType, ConnectionType
    from channeld_tpu.models.sim import register_sim_types
    from channeld_tpu.spatial.controller import (
        get_spatial_controller,
        init_spatial_controller,
        reset_spatial_controller,
    )

    t_start = time.monotonic()

    from channeld_tpu.core.overload import reset_overload
    from channeld_tpu.federation import reset_federation

    # -- fresh runtime (idempotent; the pytest smoke shares a process) --
    channel_mod.reset_channels()
    connection_mod.reset_connections()
    data_mod.reset_registries()
    ddos_mod.reset_ddos()
    recovery_mod.reset_recovery()
    reset_spatial_controller()
    reset_global_settings()
    reset_overload()

    global_settings.development = True
    # This soak proves the CHAOS plane: the balancer's planned migrations
    # would add nondeterministic authority moves to a seeded scenario.
    global_settings.balancer_enabled = False
    # Adaptive partitioning stays pinned OFF: this soak's envelope
    # assumes the static boot grid (doc/partitioning.md);
    # scripts/density_soak.py is the partitioning plane's own soak.
    global_settings.partition_enabled = False
    # Flight recorder pinned OFF (doc/observability.md): these soaks
    # prove deterministic accounting and timing envelopes; span
    # recording and anomaly auto-dumps must not perturb either
    # (scripts/trace_soak.py is the recorder's own soak).
    global_settings.trace_enabled = False
    # Device guard pinned OFF (doc/device_recovery.md): this soak's
    # envelope is deterministic; the watchdog worker-thread hop and
    # any chaos-adjacent retry would perturb it. The device plane's
    # own soak is scripts/device_soak.py.
    global_settings.device_guard_enabled = False
    # SLO plane pinned OFF (doc/observability.md): this soak's
    # envelope predates the delivery-latency sampling; the health
    # plane has its own soak (scripts/obs_soak.py).
    global_settings.slo_enabled = False
    from channeld_tpu.core.tracing import recorder as _flight_recorder

    _flight_recorder.configure(enabled=False)
    # Federation stays pinned OFF: a remote shard would route some
    # crossings over a trunk and break this soak's deterministic
    # single-gateway accounting (doc/federation.md).
    reset_federation()
    global_settings.federation_config = ""
    # Standing-query plane pinned OFF (doc/query_engine.md): this
    # soak's envelope predates the device diff pass; the plane has its
    # own soak (scripts/sensor_soak.py).
    global_settings.queryplane_enabled = False
    # Simulation plane pinned OFF (doc/simulation.md): an agent
    # population would add its own crossings/census traffic to this
    # soak's deterministic accounting; scripts/sim_soak.py is the sim
    # plane's own soak.
    global_settings.sim_enabled = False
    global_settings.tpu_entity_capacity = p.entity_capacity
    global_settings.tpu_query_capacity = p.query_capacity
    # Tick cadences tuned for a live soak on a shared CPU box: GLOBAL
    # (device plane) at 33ms, the 16 spatial + entity channels coarser.
    global_settings.channel_settings = {
        ChannelType.GLOBAL: ChannelSettings(
            tick_interval_ms=33, default_fanout_interval_ms=50),
        ChannelType.SPATIAL: ChannelSettings(
            tick_interval_ms=50, default_fanout_interval_ms=100),
        ChannelType.ENTITY: ChannelSettings(
            tick_interval_ms=50, default_fanout_interval_ms=100),
    }

    register_sim_types()
    init_connections(
        os.path.join(REPO, "config", "server_authoritative_fsm.json"),
        os.path.join(REPO, "config", "client_authoritative_fsm.json"),
    )
    init_channels()
    init_anti_ddos()

    # -- spatial controller from the shipped config + chaos overrides --
    with open(p.config_path) as f:
        spec = json.load(f)
    overrides = dict(p.scenario.get("config_overrides", {}))
    spec.setdefault("Config", {}).update(overrides)
    merged_path = os.path.join(
        "/tmp", f"chaos_soak_spatial_{os.getpid()}.json"
    )
    with open(merged_path, "w") as f:
        json.dump(spec, f)
    init_spatial_controller(merged_path)
    ctl = get_spatial_controller()

    baseline = scrape()
    arm(p.scenario)

    host = "127.0.0.1"
    server_srv = await start_listening(ConnectionType.SERVER, "tcp", f"{host}:0")
    server_port = server_srv.sockets[0].getsockname()[1]
    client_srv = await start_listening(ConnectionType.CLIENT, "tcp", f"{host}:0")
    client_port = client_srv.sockets[0].getsockname()[1]

    stop = asyncio.Event()
    send_stop = asyncio.Event()
    tasks = [
        asyncio.ensure_future(flush_loop()),
        asyncio.ensure_future(unauth_reaper_loop()),
    ]
    stats = SoakStats()
    control_writers: list = []

    fault_log: list[str] = []
    try:
        (m_reader, m_writer, drain_task), spatial_socks = await _boot_world(
            host, server_port, stats, stop
        )
        tasks.append(drain_task)
        tasks.extend(t for _, _, t in spatial_socks)
        control_writers.append(m_writer)
        control_writers.extend(w for _, w, _ in spatial_socks)

        rng = Random(p.scenario.get("seed", 0) ^ 0x50AC)
        sim = EntitySim(ctl, p, rng)
        sim.create_entities()

        for idx in range(p.clients):
            tasks.append(asyncio.ensure_future(_client_loop(
                idx, host, client_port, p.msg_rate, stats, stop, send_stop,
            )))

        # -- main soak timeline --
        traffic_s = max(p.duration_s - p.quiesce_s, 1.0)
        storm_at = p.storm_every_s
        last_crowd: list[int] = []
        t0 = time.monotonic()
        while time.monotonic() - t0 < traffic_s:
            sim.jitter_step()
            now = time.monotonic() - t0
            if now >= storm_at:
                if last_crowd:
                    sim.disperse(last_crowd)
                    last_crowd = []
                # No storm inside the final stretch: crossings must have
                # time to settle before the invariant pass.
                if now < traffic_s - max(p.storm_every_s * 0.8, 6.0):
                    last_crowd = sim.storm_gather()
                storm_at += p.storm_every_s
            await asyncio.sleep(0.1)
        if last_crowd:
            sim.disperse(last_crowd)

        # -- quiesce: stop traffic, disarm, let everything drain --
        send_stop.set()
        chaos_report = chaos.report()  # before disarm clears the state
        fire_counts = dict(chaos.fire_counts())
        disarm()
        await asyncio.sleep(p.quiesce_s)

        # -- invariants --
        inv = InvariantChecker()
        now_samples = scrape()
        d = delta(now_samples, baseline)

        # 1. No lost entities: still tracked, and in exactly one cell.
        lost_tracking = [
            eid for eid in sim.entity_ids
            if ctl.engine.slot_of_entity(eid) is None
            and eid not in ctl._last_positions
        ]
        inv.expect_equal("no_lost_entity_tracking", lost_tracking, [],
                         "device slot or host tracking")
        from channeld_tpu.core.channel import all_channels

        start_id = global_settings.spatial_channel_id_start
        placement: dict[int, int] = {}
        for cid, ch in all_channels().items():
            if not (start_id <= cid < global_settings.entity_channel_id_start):
                continue
            data_msg = ch.get_data_message()
            ents = getattr(data_msg, "entities", None)
            if ents is None:
                continue
            for eid in ents:
                placement[eid] = placement.get(eid, 0) + 1
        missing = [e for e in sim.entity_ids if placement.get(e, 0) == 0]
        duped = [e for e in sim.entity_ids if placement.get(e, 0) > 1]
        inv.expect_equal("every_entity_in_exactly_one_cell",
                         (missing, duped), ([], []),
                         "missing / duplicated in spatial channel data")

        # 2. Exact accounting: what the gateway counted as received is
        # exactly what the owner drained (no silent loss inside).
        received = sample_total(
            d, "messages_in_total", conn_type="CLIENT", msg_type="100"
        )
        drained = sum(len(v) for v in stats.drained.values())
        sent = sum(stats.client_sent.values())
        inv.expect_equal("received_equals_owner_drained",
                         int(received), drained)
        inv.expect_le("received_le_sent", int(received), sent,
                      "transport faults may discard in-flight frames")

        # 3. Per-client ordering: strictly increasing, no duplicates.
        disordered = [
            cid for cid, seqs in stats.drained.items()
            if any(b <= a for a, b in zip(seqs, seqs[1:]))
        ]
        inv.expect_equal("per_client_order_no_dup", disordered, [])

        # 4. Recovery: every socket kill recovered inside the deadline.
        worst = max(stats.recovery_latencies, default=0.0)
        inv.expect_le("reconnect_within_deadline", worst,
                      p.recovery_deadline_s,
                      f"{len(stats.recovery_latencies)} recoveries")
        inv.expect_equal("all_disconnects_recovered",
                         stats.disconnects - stats.reconnects, 0,
                         f"disconnects={stats.disconnects}")

        # 5. Tick p99 bounded (GLOBAL carries the device plane + stalls).
        p99 = histogram_quantile(
            d, "channel_tick_duration", 0.99, channel_type="GLOBAL"
        )
        inv.expect_le("global_tick_p99_bounded", p99, p.tick_p99_bound_s)

        # 6. The degradation paths actually fired.
        overflow_total = sample_total(d, "tpu_cell_overflow_entities_total")
        inv.expect_gt("cells_overflow_shed_fired", overflow_total, 0)
        handovers = sample_total(d, "handovers_total")
        inv.expect_gt("handovers_orchestrated", handovers, 0)
        silent = [r["point"] for r in p.scenario["faults"]
                  if fire_counts.get(r["point"], 0) == 0]
        inv.expect_equal("every_fault_point_fired", silent, [])

        report = {
            "kind": "chaos_soak",
            "config": os.path.basename(p.config_path),
            "config_overrides": overrides,
            "duration_s": round(time.monotonic() - t_start, 2),
            "traffic_s": traffic_s,
            "clients": p.clients,
            "entities": p.entities,
            "msg_rate_per_client": p.msg_rate,
            "scenario": p.scenario,
            "chaos": chaos_report,
            "invariants": inv.summary(),
            "stats": {
                "client_frames_sent": sent,
                "gateway_received": int(received),
                "owner_drained": drained,
                "disconnects": stats.disconnects,
                "reconnects": stats.reconnects,
                "auth_retries": stats.auth_retries,
                "recovery_latency_max_s": round(worst, 3),
                "recovery_latency_avg_s": round(
                    sum(stats.recovery_latencies)
                    / max(len(stats.recovery_latencies), 1), 3),
                "handovers": int(handovers),
                "cell_overflow_entities": int(overflow_total),
                "global_tick_p99_s": p99,
                "device_step_p99_s": histogram_quantile(
                    d, "tpu_spatial_step_seconds", 0.99),
                "packets_dropped": sample_total(
                    d, "packets_drop_total", conn_type="CLIENT"),
                "connections_closed": sample_total(
                    d, "connection_closed_total", conn_type="CLIENT"),
            },
        }
        if fault_log:
            report["notes"] = fault_log
        if p.out_path:
            with open(p.out_path, "w") as f:
                json.dump(report, f, indent=2)
        return report
    finally:
        disarm()
        stop.set()
        for t in tasks:
            t.cancel()
        await asyncio.sleep(0)
        for w in control_writers:
            try:
                w.close()
            except Exception:
                pass
        server_srv.close()
        client_srv.close()
        channel_mod.reset_channels()
        connection_mod.reset_connections()
        data_mod.reset_registries()
        ddos_mod.reset_ddos()
        recovery_mod.reset_recovery()
        reset_spatial_controller()
        reset_global_settings()
        reset_overload()
        try:
            os.remove(merged_path)
        except OSError:
            pass


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--duration", type=float, default=120.0)
    ap.add_argument("--clients", type=int, default=24)
    ap.add_argument("--entities", type=int, default=160)
    ap.add_argument("--rate", type=float, default=25.0)
    ap.add_argument("--scenario", type=str, default="",
                    help="scenario JSON path (default: built-in)")
    ap.add_argument("--out", type=str, default="")
    args = ap.parse_args()
    scenario = dict(DEFAULT_SCENARIO)
    if args.scenario:
        with open(args.scenario) as f:
            scenario = json.load(f)
    p = SoakParams(
        duration_s=args.duration, clients=args.clients,
        entities=args.entities, msg_rate=args.rate,
        scenario=scenario, out_path=args.out,
    )
    report = asyncio.run(run_soak(p))
    print(json.dumps(report, indent=2))
    if not report["invariants"]["ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
