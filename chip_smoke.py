#!/usr/bin/env python3
"""Chip smoke: one gateway on the accelerator, driven over real TCP.

Boots ``python -m channeld_tpu`` through its normal entry point at the
default device width (131,072 entity slots, 4,096 query rows) on
upstream's benchmark world (config/spatial_tpu_benchmark.json: 15x15
cells of 2,000 units, 3x3 servers) with 100,000 on-device sim agents
and upstream's MMO channel settings (50 ms ticks, 100 ms fan-out),
claims the world with a master and nine spatial servers, creates 2,000
wire entities and 64 clients holding standing sphere interests, moves
the entities for ~20 s (200 of them across a cell border), and checks
the gateway's answers against the host grid controller
(channeld_tpu/spatial/grid.py, the plain reference of the same
semantics). Then it reads /metrics and /introspect, drains the gateway
with SIGTERM, and boots it a second time to the two ``listening`` lines
to prove the compile cache hits.

This process never imports jax: one process per chip, and the gateway
child is that process. Without an accelerator it measures nothing and
exits non-zero. It prints two lines of JSON: the report, and last the
verdict, ``{"ok": ..., "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py            # one chip
    python chip_smoke.py --mesh 4   # four chips, entity-sharded, no sim
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import importlib.metadata
import json
import os
import random
import signal
import socket
import struct
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1100.0  # the whole run, compilation included


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke drives. The defaults are the run the chip check
    makes; tier-1 passes a tiny world to walk the same flow on the CPU."""

    scc: str = "config/spatial_tpu_benchmark.json"
    chs: str = "config/channel_settings_lofi.json"
    agents: int = 100_000
    wire_entities: int = 2_000
    clients: int = 64
    crossings: int = 200
    radius: float = 3_000.0
    move_s: float = 20.0
    round_s: float = 0.5  # a quarter of the wire entities move per round
    mesh: int = 0  # >0: -mesh-devices N, entity-sharded, and no sim plane
    seed: int = 21


FULL = Sizes()

# What FULL gives up against the run ISSUE 21 set out, and what forced it
# (the numbers are PR 21's chip runs; CHANGES.md has them in full).
FULL_REDUCED = [
    "channel settings: config/channel_settings_hifi.json (20 ms ticks, the "
    "gateway's default) -> config/channel_settings_lofi.json (50 ms ticks, "
    "100 ms fan-out, upstream's MMO interval). On a v5e the tick's three "
    "device passes at the default width take 13-16 ms at the median, "
    "blocking (the gateway's own histogram), and the sim agents' own "
    "handovers 10-18 ms of host time in the ticks that have them: with the "
    "world idle the 20 ms budget is spent, the overload ladder swings "
    "between L1 and L3, and L3 refuses the clients' admission and their "
    "interest subscriptions. Cutting the wire population does not change "
    "that; the device width and the 100,000 agents are not cut.",
    "wire movement: every entity once per 0.5 s round -> a quarter of them "
    "per round (each entity every 2 s). The smoke's single-threaded Python "
    "driver, not the gateway, could not hold 4,450 messages/s: its rounds "
    "ran 1.1 s long.",
]


class SmokeFailure(Exception):
    """A phase could not run to its end; the message says what it found."""


def _say(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _f32(x: float) -> float:
    """The value a proto ``float`` field carries for ``x``."""
    return struct.unpack("f", struct.pack("f", x))[0]


def _free_ports(n: int) -> list[int]:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


# ---------------------------------------------------------------------------
# the gateway child
# ---------------------------------------------------------------------------


class Gateway:
    """One ``python -m channeld_tpu`` child, logging to a file."""

    def __init__(self, sizes: Sizes, out_dir: str, tag: str):
        self.log_path = os.path.join(out_dir, f"gateway_{tag}.log")
        self.mport, self.sport, self.cport = _free_ports(3)
        cmd = [
            sys.executable, "-m", "channeld_tpu", "-dev",
            "-scc", sizes.scc,
            "-chs", sizes.chs,
            "-imports", "channeld_tpu.models.sim",
            "-cwm", "false",
            "-cfsm", "config/client_authoritative_fsm.json",
            "-loglevel", "0",
            "-mport", str(self.mport),
            "-sa", f"127.0.0.1:{self.sport}",
            "-ca", f"127.0.0.1:{self.cport}",
            "-profilepath", os.path.join(out_dir, "profiles"),
        ]
        if sizes.mesh:
            cmd += ["-mesh-devices", str(sizes.mesh)]
        else:
            cmd += ["-sim", "true", "-sim-agents", str(sizes.agents)]
        self._log = open(self.log_path, "w")
        self.started = time.monotonic()
        self.proc = subprocess.Popen(
            cmd, cwd=REPO, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_listening(self, timeout: float) -> float:
        """Seconds from process start to both listeners open."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if self.log_text().count("listening for") >= 2:
                return time.monotonic() - self.started
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"gateway exited {self.proc.returncode} during boot; "
                    f"log tail:\n{self.log_text()[-3000:]}"
                )
            time.sleep(0.2)
        raise SmokeFailure(
            f"gateway not listening after {timeout:.0f}s; log tail:\n"
            f"{self.log_text()[-3000:]}"
        )

    def _get(self, path: str) -> bytes:
        url = f"http://127.0.0.1:{self.mport}{path}"
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.read()

    def metrics(self) -> dict:
        """/metrics as {(sample_name, sorted label items): value} — the
        shape channeld_tpu.chaos.invariants reads."""
        from prometheus_client.parser import text_string_to_metric_families

        out: dict = {}
        for family in text_string_to_metric_families(
                self._get("/metrics").decode()):
            for s in family.samples:
                out[(s.name, tuple(sorted(s.labels.items())))] = s.value
        return out

    def introspect(self) -> dict:
        return json.loads(self._get("/introspect"))

    def drain(self, timeout: float = 60.0) -> int:
        """SIGTERM, then wait for the gateway's own exit."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"gateway still alive {timeout:.0f}s after "
                               "SIGTERM")

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


# ---------------------------------------------------------------------------
# the world, over the wire
# ---------------------------------------------------------------------------


def _total(samples: dict, name: str, **labels) -> float:
    from channeld_tpu.chaos.invariants import sample_total

    return sample_total(samples, name, **labels)


class World:
    """Master + spatial servers + wire entities + clients on real TCP
    sockets, and the host grid controller that predicts their answers."""

    def __init__(self, sizes: Sizes, gw: Gateway, deadline: float):
        from channeld_tpu.core.settings import global_settings
        from channeld_tpu.spatial.grid import StaticGrid2DSpatialController

        self.sizes, self.gw, self.deadline = sizes, gw, deadline
        self.rng = random.Random(sizes.seed)
        with open(os.path.join(REPO, sizes.scc)) as f:
            config = json.load(f)["Config"]
        self.grid = StaticGrid2DSpatialController()
        self.grid.load_config(config)
        self.cell_start = global_settings.spatial_channel_id_start
        self.entity_start = global_settings.entity_channel_id_start
        self.n_cells = self.grid.grid_cols * self.grid.grid_rows
        self.n_servers = self.grid.server_cols * self.grid.server_rows
        self.master = None
        self.servers: list = []
        self.server_cells: list[set[int]] = []
        self.owner_of: dict[int, int] = {}  # cell -> owning server index
        self.clients: list = []
        self.conns: list = []  # every socket, pumped together
        # wire entity id -> [x, z, cell, driving server index]
        self.entities: dict[int, list] = {}
        # (server index, src, dst, entity id) -> handover messages seen
        self.handovers_seen: dict[tuple, int] = {}
        self.fanouts = [0] * sizes.clients
        self.wire_bytes = {"entity_update": 0, "spatial_update": 0}

    # ---- plumbing --------------------------------------------------------

    def _left(self, want: float) -> float:
        left = self.deadline - time.monotonic()
        if left <= 0:
            raise SmokeFailure("out of time: the run's deadline passed")
        return min(want, left)

    def pump(self, seconds: float = 0.0) -> None:
        """Tick every connection (non-blocking reads, flush writes)."""
        end = time.monotonic() + seconds
        while True:
            for c in self.conns:
                c.tick()
            if time.monotonic() >= end:
                return
            time.sleep(0.005)

    def _connect(self, port: int, pit: str, attempts: int = 1):
        """Connect and auth. At overload L3 the gateway answers a
        client's AUTH with ServerBusy and closes: retry, paced."""
        from channeld_tpu.client import Client
        from channeld_tpu.core.types import MessageType
        from channeld_tpu.protocol import control_pb2

        for _ in range(attempts):
            c = Client(f"127.0.0.1:{port}")
            c.set_message_entry(MessageType.SERVER_BUSY,
                                control_pb2.ServerBusyMessage)
            c.auth(pit=pit)
            end = time.monotonic() + self._left(5.0)
            while c.id == 0 and c.connected and time.monotonic() < end:
                c.tick(timeout=0.02)
                self.pump()
            if c.id:
                self.conns.append(c)
                return c
            c.disconnect()
            self.pump(self._left(2.0))
        raise SmokeFailure(f"{pit}: auth refused {attempts} times (overload "
                           f"level {self.overload_level()})")

    def overload_level(self) -> int:
        return int(_total(self.gw.metrics(), "overload_level"))

    def wait_metric(self, what: str, pred, timeout: float) -> dict:
        """Poll /metrics (paced: a tight scrape loop is load) until
        ``pred(samples)``; SmokeFailure on timeout."""
        end = time.monotonic() + self._left(timeout)
        while True:
            samples = self.gw.metrics()
            if pred(samples):
                return samples
            if time.monotonic() >= end:
                raise SmokeFailure(f"timed out after {timeout:.0f}s "
                                   f"waiting for {what}")
            self.pump(1.0)

    def settle(self, timeout: float = 90.0) -> dict:
        """Wait for the overload ladder to come to rest at L0/L1 (three
        paced scrapes in a row), at most ``timeout``. Returns what it saw,
        with the host's tick stages over the wait: a ladder that will not
        come down is a finding to record, not a reason to stop."""
        from channeld_tpu.chaos.invariants import delta

        start, t0 = self.gw.metrics(), time.monotonic()
        end = t0 + self._left(timeout)
        now, levels, calm = start, [], 0
        while calm < 3 and time.monotonic() < end:
            self.pump(1.0)
            now = self.gw.metrics()
            levels.append(int(_total(now, "overload_level")))
            # The level lags the pressure by the ladder's hold times.
            at_rest = levels[-1] <= 1 and _total(now, "overload_pressure") < 0.95
            calm = calm + 1 if at_rest else 0
        return {
            "settled": calm >= 3,
            "level": levels[-1],
            "max_level": max(levels),
            "waited_s": round(time.monotonic() - t0, 1),
            "pressure": round(_total(now, "overload_pressure"), 3),
            **self.tick_profile(delta(now, start), time.monotonic() - t0),
        }

    @staticmethod
    def tick_profile(samples: dict, seconds: float) -> dict:
        """Where the host's tick time goes, from the gateway's own
        histograms over ``samples`` (a delta between two scrapes taken
        ``seconds`` apart)."""
        from channeld_tpu.chaos.invariants import histogram_quantile

        stages = {}
        for (name, labels), value in samples.items():
            if name == "tick_stage_ms_sum":
                stage = dict(labels).get("stage", "")
                n = _total(samples, "tick_stage_ms_count", stage=stage)
                if n:
                    stages[stage] = round(value / n, 3)
        steps = _total(samples, "tpu_spatial_step_seconds_count")
        out = {"device_steps_per_s": round(steps / seconds, 2),
               "tick_stage_ms_mean": stages}
        for q, key in ((0.5, "p50"), (0.99, "p99")):
            v = histogram_quantile(samples, "tpu_spatial_step_seconds", q)
            out[f"tpu_spatial_step_seconds_{key}"] = (
                None if v is None or v != v else round(v, 6))
        return out

    # ---- set-up ----------------------------------------------------------

    def claim(self) -> None:
        """Master possesses GLOBAL; each spatial server claims its block
        (channel type 4, WRITE) until SPATIAL_CHANNELS_READY."""
        from channeld_tpu.core.types import BroadcastType, MessageType
        from channeld_tpu.models import sim_pb2
        from channeld_tpu.protocol import control_pb2
        from channeld_tpu.utils.anyutil import pack_any

        self.master = self._connect(self.gw.sport, "smoke-master")
        self.master.send(
            0, BroadcastType.NO_BROADCAST, MessageType.CREATE_CHANNEL,
            control_pb2.CreateChannelMessage(channelType=1),
        )
        self.pump(0.2)
        ready = []
        for i in range(self.n_servers):
            s = self._connect(self.gw.sport, f"smoke-spatial-{i}")
            cells: set[int] = set()
            s.add_message_handler(
                MessageType.CREATE_SPATIAL_CHANNEL,
                lambda c, ch, m, cells=cells: cells.update(m.spatialChannelId),
            )
            s.add_message_handler(
                MessageType.SPATIAL_CHANNELS_READY,
                lambda c, ch, m: ready.append(m.serverIndex),
            )
            s.add_message_handler(
                MessageType.CHANNEL_DATA_HANDOVER,
                lambda c, ch, m, i=i: self._on_handover(i, m),
            )
            s.send(
                0, BroadcastType.NO_BROADCAST, MessageType.CREATE_CHANNEL,
                control_pb2.CreateChannelMessage(
                    channelType=4,
                    data=pack_any(sim_pb2.SimSpatialChannelData()),
                    subOptions=control_pb2.ChannelSubscriptionOptions(
                        dataAccess=2),
                ),
            )
            self.servers.append(s)
            self.server_cells.append(cells)
            end = time.monotonic() + self._left(30.0)
            while not cells and time.monotonic() < end:
                self.pump(0.05)
            if not cells:
                raise SmokeFailure(f"spatial server {i} got no channels")
        end = time.monotonic() + self._left(60.0)
        while len(ready) < self.n_servers and time.monotonic() < end:
            self.pump(0.05)
        if len(ready) < self.n_servers:
            raise SmokeFailure(
                f"SPATIAL_CHANNELS_READY reached {len(ready)} of "
                f"{self.n_servers} servers")
        self.owner_of = {c: i for i, cells in enumerate(self.server_cells)
                         for c in cells}

    def _on_handover(self, server: int, msg) -> None:
        from channeld_tpu.models import sim_pb2

        data = sim_pb2.SimSpatialChannelData()
        if not msg.data.Unpack(data):
            return
        for eid in data.entities:
            if eid in self.entities:  # sim agents hand over too
                key = (server, msg.srcChannelId, msg.dstChannelId, eid)
                self.handovers_seen[key] = self.handovers_seen.get(key, 0) + 1

    def _cell_rect(self, cell: int) -> tuple[float, float, float, float]:
        i = cell - self.cell_start
        g = self.grid
        x0 = g.world_offset_x + (i % g.grid_cols) * g.grid_width
        z0 = g.world_offset_z + (i // g.grid_cols) * g.grid_height
        return x0, z0, x0 + g.grid_width, z0 + g.grid_height

    def _point_in(self, cell: int) -> tuple[float, float]:
        """A seeded point well inside ``cell`` (20% margin: no float
        can put it on the other side of a border)."""
        x0, z0, x1, z1 = self._cell_rect(cell)
        mx, mz = 0.2 * (x1 - x0), 0.2 * (z1 - z0)
        return (_f32(self.rng.uniform(x0 + mx, x1 - mx)),
                _f32(self.rng.uniform(z0 + mz, z1 - mz)))

    def _entity_update(self, eid: int, x: float, z: float):
        from channeld_tpu.models import sim_pb2

        data = sim_pb2.SimEntityChannelData()
        data.state.entityId = eid
        data.state.transform.position.x = x
        data.state.transform.position.z = z
        return data

    def spawn_entities(self) -> None:
        """Wire entities, one cell after another so every cell holds
        some, each created by its cell's owner with a transform (that is
        what tracks it on the device from birth)."""
        from channeld_tpu.core.types import BroadcastType, MessageType
        from channeld_tpu.protocol import control_pb2, spatial_pb2
        from channeld_tpu.utils.anyutil import pack_any

        owner_of = self.owner_of
        created = [0]
        for s in self.servers:
            s.add_message_handler(
                MessageType.CREATE_ENTITY_CHANNEL,
                lambda c, ch, m: created.__setitem__(0, created[0] + 1),
            )
        for n in range(self.sizes.wire_entities):
            eid = self.entity_start + 1 + n
            cell = self.cell_start + n % self.n_cells
            x, z = self._point_in(cell)
            server = owner_of[cell]
            self.entities[eid] = [x, z, cell, server]
            self.servers[server].send(
                0, BroadcastType.NO_BROADCAST,
                MessageType.CREATE_ENTITY_CHANNEL,
                spatial_pb2.CreateEntityChannelMessage(
                    entityId=eid,
                    data=pack_any(self._entity_update(eid, x, z)),
                    subOptions=control_pb2.ChannelSubscriptionOptions(
                        dataAccess=2),
                ),
            )
            if n % 100 == 99:
                self.pump(0.05)
        end = time.monotonic() + self._left(60.0)
        while created[0] < self.sizes.wire_entities and time.monotonic() < end:
            self.pump(0.05)
        if created[0] < self.sizes.wire_entities:
            raise SmokeFailure(f"only {created[0]} of "
                               f"{self.sizes.wire_entities} entity channels "
                               "were created")

    def _sphere_query(self, cx: float, cz: float):
        from channeld_tpu.protocol import spatial_pb2

        q = spatial_pb2.SpatialInterestQuery()
        q.sphereAOI.center.x = cx
        q.sphereAOI.center.z = cz
        q.sphereAOI.radius = self.sizes.radius
        return q

    def _exact_sphere_cells(self, cx: float, cz: float) -> tuple[set, float]:
        """Cells whose rectangle the sphere overlaps, and how close the
        nearest miss-or-hit comes to the sphere's edge."""
        import math

        r = self.sizes.radius
        hit, slack = set(), float("inf")
        for cell in range(self.cell_start, self.cell_start + self.n_cells):
            x0, z0, x1, z1 = self._cell_rect(cell)
            d = math.hypot(max(x0 - cx, 0.0, cx - x1),
                           max(z0 - cz, 0.0, cz - z1))
            slack = min(slack, abs(d - r))
            if d <= r:
                hit.add(cell)
        return hit, slack

    def connect_clients(self) -> list[set[int]]:
        """Clients with one standing sphere interest each. The host grid
        samples a sphere at half-cell steps and can miss a cell the
        sphere only grazes (tests/test_queryplane.py pins host ⊆ device);
        centers are drawn from the seed until the sampled answer IS the
        exact overlap with room to spare, so the reference and the
        device plane must agree cell for cell. Returns each client's
        predicted cell set."""
        from channeld_tpu.core.types import BroadcastType, MessageType
        from channeld_tpu.protocol import spatial_pb2

        g = self.grid
        want: list[set[int]] = []
        for i in range(self.sizes.clients):
            c = self._connect(self.gw.cport, f"smoke-client-{i}", attempts=30)
            c.add_message_handler(
                MessageType.CHANNEL_DATA_UPDATE,
                lambda cl, ch, m, i=i: self.fanouts.__setitem__(
                    i, self.fanouts[i] + 1),
            )
            self.clients.append(c)
            while True:
                cx = _f32(self.rng.uniform(
                    g.world_offset_x, g.world_offset_x + g.world_width()))
                cz = _f32(self.rng.uniform(
                    g.world_offset_z, g.world_offset_z + g.world_height()))
                query = self._sphere_query(cx, cz)
                exact, slack = self._exact_sphere_cells(cx, cz)
                if (slack > 0.02 * self.sizes.radius
                        and set(g.query_channel_ids(query)) == exact):
                    break
            want.append(exact)
            self.master.send(
                self.cell_start, BroadcastType.NO_BROADCAST,
                MessageType.UPDATE_SPATIAL_INTEREST,
                spatial_pb2.UpdateSpatialInterestMessage(
                    connId=c.id, query=query),
            )
            self.pump(0.02)
        return want

    # ---- the move window -------------------------------------------------

    def plan_crossings(self) -> dict[int, tuple[int, int, int]]:
        """{entity id: (round, src cell, dst cell)} for the seeded subset
        carried across a border: src and dst come from the host grid."""
        from channeld_tpu.spatial.controller import SpatialInfo

        rounds = max(2, int(self.sizes.move_s / self.sizes.round_s))
        plan = {}
        for eid in self.rng.sample(sorted(self.entities), self.sizes.crossings):
            x, z, cell, _ = self.entities[eid]
            src = self.grid.get_channel_id(SpatialInfo(x, 0, z))
            assert src == cell
            i = cell - self.cell_start
            col, row = i % self.grid.grid_cols, i // self.grid.grid_cols
            steps = [(dc, dr) for dc, dr in ((1, 0), (-1, 0), (0, 1), (0, -1))
                     if 0 <= col + dc < self.grid.grid_cols
                     and 0 <= row + dr < self.grid.grid_rows]
            dc, dr = self.rng.choice(steps)
            dst = self.cell_start + (col + dc) + (row + dr) * self.grid.grid_cols
            # Not in the last two rounds: the crossing must be detected,
            # orchestrated and delivered inside the window's tail.
            plan[eid] = (self.rng.randrange(1, max(2, rounds - 2)), src, dst)
        return plan

    def move(self, plan: dict) -> dict:
        """Every round a quarter of the wire entities take one msgType-8
        step inside their cell, sent by the server that drives each; on
        its planned round a crossing entity steps into the neighbour cell
        instead, and is then left to its new owner, which moves it on
        only after its own socket has seen the handover. Every round each
        server also replicates the moved rows of its cells' entity tables
        (spatial channel data), which is what fans out to the clients
        watching those cells."""
        from channeld_tpu.core.types import BroadcastType, MessageType
        from channeld_tpu.models import sim_pb2
        from channeld_tpu.protocol import control_pb2
        from channeld_tpu.spatial.controller import SpatialInfo
        from channeld_tpu.utils.anyutil import pack_any

        owner_of = self.owner_of
        rounds = max(2, int(self.sizes.move_s / self.sizes.round_s))
        awaiting: dict[int, tuple[int, int, int]] = {}  # eid -> (owner, src, dst)
        sent = {"entity_updates": 0, "spatial_updates": 0, "late_rounds": 0}
        t0 = time.monotonic()
        for rnd in range(rounds):
            for eid in [e for e, (o, s, d) in awaiting.items()
                        if self.handovers_seen.get((o, s, d, e))]:
                del awaiting[eid]
            tables: dict[int, sim_pb2.SimSpatialChannelData] = {}
            for n, (eid, ent) in enumerate(self.entities.items()):
                if eid in awaiting:
                    continue
                step = plan.get(eid)
                crossing = step is not None and step[0] == rnd
                if not crossing and n % 4 != rnd % 4:
                    continue
                if crossing:
                    _, src, dst = step
                    x, z = self._point_in(dst)
                    got = self.grid.get_channel_id(SpatialInfo(x, 0, z))
                    assert got == dst and ent[2] == src
                    sender = ent[3]
                    ent[:] = [x, z, dst, owner_of[dst]]
                    awaiting[eid] = (owner_of[dst], src, dst)
                    update = self._entity_update(eid, x, z)
                else:
                    x, z = self._point_in(ent[2])
                    sender = ent[3]
                    ent[0], ent[1] = x, z
                    update = self._entity_update(eid, x, z)
                    tables.setdefault(
                        ent[2], sim_pb2.SimSpatialChannelData()
                    ).entities[eid].CopyFrom(update.state)
                msg = control_pb2.ChannelDataUpdateMessage(
                    data=pack_any(update))
                self.wire_bytes["entity_update"] = msg.ByteSize()
                self.servers[sender].send(
                    eid, BroadcastType.NO_BROADCAST,
                    MessageType.CHANNEL_DATA_UPDATE, msg)
                sent["entity_updates"] += 1
                if n % 200 == 199:
                    self.pump()
            for cell, table in tables.items():
                msg = control_pb2.ChannelDataUpdateMessage(data=pack_any(table))
                self.wire_bytes["spatial_update"] = max(
                    self.wire_bytes["spatial_update"], msg.ByteSize())
                self.servers[owner_of[cell]].send(
                    cell, BroadcastType.NO_BROADCAST,
                    MessageType.CHANNEL_DATA_UPDATE, msg)
                sent["spatial_updates"] += 1
            next_round = t0 + (rnd + 1) * self.sizes.round_s
            if time.monotonic() > next_round:
                sent["late_rounds"] += 1  # the driver, not the gateway
            self.pump(max(0.0, next_round - time.monotonic()))
        sent["rounds"] = rounds
        sent["window_s"] = round(time.monotonic() - t0, 2)
        return sent

    def close(self) -> None:
        for c in self.conns:
            c.disconnect()


# ---------------------------------------------------------------------------
# the flow
# ---------------------------------------------------------------------------


def _cache_dir() -> str:
    from channeld_tpu.utils.devices import compile_cache_dir

    return compile_cache_dir()


def _cache_entries() -> set[str]:
    """One ``<program name>-<key>-cache`` file per compiled program."""
    return {os.path.basename(p)
            for p in glob.glob(os.path.join(_cache_dir(), "*-cache"))}


def _by_program(entries: set[str]) -> dict[str, int]:
    names = [e.rsplit("-", 2)[0] for e in entries]
    return {n: names.count(n) for n in sorted(set(names))}


def _prune(directory: str, keep: int = 4) -> None:
    """The flight recorder dumps a trace per anomaly; keep the newest few
    so the run's output stays small."""
    dumps = sorted(glob.glob(os.path.join(directory, "*")), key=os.path.getmtime)
    for path in dumps[:-keep]:
        os.remove(path)


def _build_native() -> None:
    """Rebuild the native codec from source, always: which ingest/encode
    path a gateway runs must not depend on a .so that lay in the tree."""
    for stale in glob.glob(os.path.join(REPO, "channeld_tpu", "native",
                                        "_codec*.so")):
        os.remove(stale)
    done = subprocess.run(
        ["sh", os.path.join(REPO, "scripts", "build_native.sh")],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    if done.returncode != 0:
        raise SmokeFailure(f"native codec build failed:\n{done.stderr[-2000:]}")


def _counters(samples: dict) -> dict:
    return {
        "sim_ticks_total": _total(samples, "sim_ticks_total"),
        "query_plane_transfers_total":
            _total(samples, "query_plane_transfers_total"),
        "handovers_total": _total(samples, "handovers_total"),
        "device_recoveries_total": _total(samples, "device_recoveries_total"),
        "device_step_failures_total":
            _total(samples, "device_step_failures_total"),
    }


def run(sizes: Sizes, out_dir: str, need_platform: str = "") -> dict:
    """Drive the whole flow once; returns the report ``verify`` judges.
    ``need_platform`` stops the run right after boot when the gateway
    holds another platform (the chip check has no use for a CPU run)."""
    from channeld_tpu.chaos.invariants import delta

    deadline = time.monotonic() + DEADLINE_S
    os.makedirs(out_dir, exist_ok=True)
    report: dict = {
        "jax_version": importlib.metadata.version("jax"),
        "sizes": dataclasses.asdict(sizes),
        "reduced": FULL_REDUCED + [
            f"{f.name}: {getattr(FULL, f.name)} -> {getattr(sizes, f.name)}"
            for f in dataclasses.fields(Sizes)
            if f.name not in ("mesh", "seed")
            and getattr(sizes, f.name) != getattr(FULL, f.name)
        ],
        "checks": {},
    }
    checks = report["checks"]

    _say("building the native codec")
    _build_native()
    report["cache_dir"] = _cache_dir()
    report["cache_entries_start"] = len(_cache_entries())

    _say("cold boot")
    gw = Gateway(sizes, out_dir, "cold")
    world = None
    try:
        report["boot_s_cold"] = round(gw.wait_listening(600.0), 2)
        at_listening = _cache_entries()
        report["cache_entries_cold_boot"] = len(at_listening)
        # platform, device_kind, device_count, mesh, use_pallas,
        # native_codec: as the gateway's engine reports them.
        report.update(gw.introspect().get("engine") or {})
        _say(f"listening after {report['boot_s_cold']}s on "
             f"{report.get('platform')} {report.get('device_kind')!r}")
        if need_platform and report.get("platform") != need_platform:
            raise SmokeFailure(
                f"the gateway holds platform {report.get('platform')!r}, "
                f"not {need_platform!r}")
        base = _counters(gw.metrics())

        world = World(sizes, gw, deadline)
        _say(f"claiming the world: master + {world.n_servers} spatial servers")
        world.claim()
        checks["server_blocks_match_host_grid"] = all(
            cells == {c for c in range(world.cell_start,
                                       world.cell_start + world.n_cells)
                      if world.grid.server_index_of_cell(c) == i}
            for i, cells in enumerate(world.server_cells)
        )
        if not sizes.mesh:
            _say(f"waiting for {sizes.agents} agents")
            world.wait_metric(
                "sim_agents_num",
                lambda s: _total(s, "sim_agents_num") == sizes.agents, 300.0)
        report["ladder_after_spawn"] = world.settle()

        _say(f"creating {sizes.wire_entities} wire entities")
        world.spawn_entities()
        _say(f"connecting {sizes.clients} clients")
        want_cells = world.connect_clients()
        report["ladder_before_move"] = world.settle()
        resident = sizes.wire_entities + (0 if sizes.mesh else sizes.agents)
        world.wait_metric(
            "tpu_entities",
            lambda s: _total(s, "tpu_entities") >= resident, 60.0)

        _say(f"moving for {sizes.move_s}s, {sizes.crossings} across a border")
        plan = world.plan_crossings()
        before, t_before = gw.metrics(), time.monotonic()
        report["traffic"] = world.move(plan)
        # Outside the window: let the last crossings and subscriptions land.
        expected = {}
        owner_of = world.owner_of
        for eid, (_, src, dst) in plan.items():
            for owner in {owner_of[src], owner_of[dst]}:
                expected[(owner, src, dst, eid)] = 1
        end = time.monotonic() + world._left(30.0)
        while time.monotonic() < end and any(
                k not in world.handovers_seen for k in expected):
            world.pump(0.2)
        world.pump(2.0)
        after, t_after = gw.metrics(), time.monotonic()

        # ---- answers against the host grid ----
        seen = world.handovers_seen
        owners_seen = {k: v for k, v in seen.items() if k in expected}
        stray = [k for k in seen
                 if (k[3] not in plan or (k[1], k[2]) != plan[k[3]][1:])]
        checks["handovers_none_lost"] = len(owners_seen) == len(expected)
        checks["handovers_none_duplicated"] = all(
            v == 1 for v in seen.values())
        checks["handovers_none_unpredicted"] = not stray
        report["handover_messages"] = {
            "expected_at_owners": len(expected),
            "seen_at_owners": len(owners_seen),
            "seen_anywhere": len(seen),
            "unpredicted": len(stray),
            "lost_sample": sorted(set(expected) - set(seen))[:5],
        }
        got_cells = [
            {c for c in cl.subscribed_channels
             if world.cell_start <= c < world.entity_start}
            for cl in world.clients
        ]
        wrong = [i for i, (g, w) in enumerate(zip(got_cells, want_cells))
                 if g != w]
        checks["client_interest_matches_host_grid"] = not wrong
        report["client_interest"] = {
            "clients": sizes.clients,
            "cells_per_client_min": min(map(len, want_cells)),
            "cells_per_client_max": max(map(len, want_cells)),
            "mismatched": wrong[:8],
        }
        checks["every_client_got_fanout"] = all(world.fanouts)
        report["client_fanouts_min"] = min(world.fanouts)

        # ---- the gateway's own account ----
        report["device_state"] = gw.introspect().get("device")
        now = _counters(after)
        report["counters"] = {k: now[k] - base[k] for k in now}
        at_window_start = _counters(before)
        report["counters_in_window"] = {
            k: v - at_window_start[k] for k, v in now.items()}
        report["tpu_entities"] = _total(after, "tpu_entities")
        report["sim_agents_num"] = _total(after, "sim_agents_num")
        report["device_state_metric"] = _total(after, "device_state")
        report["device_step_failures_by_cause"] = {
            dict(labels)["cause"]: value
            for (name, labels), value in after.items()
            if name == "device_step_failures_total" and value
        }
        report["overload_level_end"] = int(_total(after, "overload_level"))
        report["overload_sheds_total"] = _total(after, "overload_sheds_total")
        report["move_window"] = world.tick_profile(
            delta(after, before), t_after - t_before)
        report.update(world.tick_profile(  # the whole run, from listening
            after, t_after - gw.started - report["boot_s_cold"]))
        report["wire_bytes"] = world.wire_bytes
        at_end = _cache_entries()
        report["cache_entries_end"] = len(at_end)
        report["compiles_after_listening"] = len(at_end - at_listening)
        report["compiles_after_listening_by_program"] = _by_program(
            at_end - at_listening)
        world.close()
        world = None

        _say("SIGTERM")
        report["drain_exit_code"] = gw.drain()
        log = gw.log_text()
        checks["clean_drain"] = (
            report["drain_exit_code"] == 0 and "drain complete" in log)
        checks["no_traceback_in_log"] = "Traceback" not in log
    finally:
        if world is not None:
            world.close()
        gw.close()
        _prune(os.path.join(out_dir, "profiles"))

    _say("warm boot")
    before_warm = _cache_entries()
    gw = Gateway(sizes, out_dir, "warm")
    try:
        report["boot_s_warm"] = round(gw.wait_listening(600.0), 2)
        report["cache_new_entries_warm_boot"] = len(
            _cache_entries() - before_warm)
        checks["warm_boot_clean_drain"] = gw.drain() == 0
    finally:
        gw.close()
        _prune(os.path.join(out_dir, "profiles"))
    return report


def verify(report: dict) -> list[str]:
    """Everything that must hold for the smoke to pass; returns what does
    not. Times are in the report for the record and gate nothing."""
    bad = [f"check failed: {name}"
           for name, ok in report["checks"].items() if not ok]
    sizes = report["sizes"]
    if report.get("platform") != "tpu":
        bad.append(f"platform is {report.get('platform')!r}, not 'tpu'")
    if not report.get("native_codec"):
        bad.append("the native codec is not loaded")
    if report["device_state"] != "ACTIVE" or report["device_state_metric"] != 0:
        bad.append(f"device state {report['device_state']}")
    moved = ["query_plane_transfers_total", "handovers_total"]
    if sizes["mesh"]:
        if report.get("mesh") is None:
            bad.append("no mesh on the engine")
    else:
        moved.append("sim_ticks_total")
        if not report.get("use_pallas"):
            bad.append("use_pallas is false")
    counters = report["counters"]
    for name in ("device_recoveries_total", "device_step_failures_total"):
        if counters[name]:
            bad.append(f"{name} moved by {counters[name]:g}")
    for name in moved:
        if counters[name] <= 0:
            bad.append(f"{name} did not advance")
    resident = sizes["wire_entities"] + (0 if sizes["mesh"] else sizes["agents"])
    if report["tpu_entities"] < resident:
        bad.append(f"tpu_entities {report['tpu_entities']:g} < {resident}")
    if report["cache_new_entries_warm_boot"]:
        bad.append(f"{report['cache_new_entries_warm_boot']} new compile-"
                   "cache entries on the second boot")
    return bad


def result_line(report: dict, ok: bool) -> str:
    """The last line of stdout: the verdict and the device as JAX reported
    it to the gateway, and no other key. The report is the line before."""
    return json.dumps({
        "ok": ok,
        "device": {"platform": report["platform"],
                   "kind": report["device_kind"],
                   "count": report["device_count"]},
    })


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", type=int, default=0, choices=(0, 4),
                    help="shard the engine over this many chips (no sim)")
    args = ap.parse_args()
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and platforms.split(",")[0] != "tpu":
        print(f"chip_smoke: JAX_PLATFORMS={platforms} keeps JAX off the "
              "accelerator; nothing was run", file=sys.stderr)
        return 2
    try:
        import channeld_tpu.client  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the program is not here ({e}); nothing was run",
              file=sys.stderr)
        return 2
    sizes = dataclasses.replace(FULL, mesh=args.mesh)
    out_dir = os.path.join(REPO, "chiprun_out", "chip_smoke")
    try:
        report = run(sizes, out_dir, need_platform="tpu")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    failures = verify(report)
    if "jax" in sys.modules:
        failures.append("the smoke's own process imported jax")
    for f in failures:
        print(f"chip_smoke: {f}", file=sys.stderr)
    report["failures"] = failures
    with open(os.path.join(out_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    print(result_line(report, ok=not failures))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
