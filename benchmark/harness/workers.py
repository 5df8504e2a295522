"""The peers' processes. A worker holds some of a flow's peers on one
selector (``wire.Peers``) and never touches jax. The parent tells it
what to do over a pipe, one phase at a time, and gets each phase's
answer back; between phases a worker keeps reading its sockets, because
the gateway fans out whether or not anyone is timing. A flow's peer
kinds subclass ``Worker``, and the flow names them in its ``WORKERS``.

The spatial world's peers are here: spatial servers that send
(``Sender``) and clients that receive (``Receiver``). All of them make
the same schedule from the seed (``plan``) and share
``CLOCK_MONOTONIC``, so a due time means the same instant to each.
"""

from __future__ import annotations

import collections
import gc
import importlib
import time

import numpy as np

from . import stats, wire
from .reference import Grid, f32


def plan(spec: dict) -> dict:
    """The run's layout and schedule, and the reference's reading of
    them. Everything is drawn from the seed: where the walks start and
    head, and where the clients stand. What is held equal from seed to
    seed is the offered load, by count: layouts are drawn, one after
    another from the same seed, until the deliveries that fall due in
    the window come to the mix's ``offered`` count for each update,
    ``within`` its share. Every worker and the parent draw alike."""
    grid = Grid.load(spec["scc"], spec["cell_start"], spec["entity_start"])
    generator = importlib.import_module(
        f"benchmark.generators.{spec['mix']['generator']}")
    offered = spec["mix"]["offered"]
    w0, w1 = spec["window"]
    for attempt in range(1000):
        sched = generator.schedule(grid, spec["entities"], spec["mix"],
                                   [spec["seed"], attempt, 0],
                                   spec["seconds_total"])
        pos, start, due = sched["pos"], sched["start"], sched["due"]
        cells = grid.cells_of(pos[..., 0], pos[..., 1])
        start_cells = grid.cells_of(start[:, 0], start[:, 1])
        if (cells < 0).any() or (start_cells < 0).any():
            raise ValueError("the generator put an entity outside the world")
        centres = client_centres(grid, spec["clients_total"], spec["radius"],
                                 [spec["seed"], attempt, 1])
        watchers = np.zeros(grid.num_cells, np.int64)  # clients on each cell
        for _, _, covered in centres:
            watchers[list(covered)] += 1
        in_window = (due >= w0) & (due < w1)
        each = watchers[cells[in_window]].sum() / max(1, in_window.sum())
        if abs(each / offered["deliveries_per_update"] - 1.0) <= offered["within"]:
            break
    else:
        raise ValueError(
            f"no layout of seed {spec['seed']} offers "
            f"{offered['deliveries_per_update']} deliveries an update within "
            f"{offered['within']:.2%}: the last offered {each:.3f}")
    return {"grid": grid, "pos": pos, "start": start, "due": due,
            "cells": cells, "start_cells": start_cells, "centres": centres,
            "layouts_drawn": attempt + 1, "deliveries_per_update": float(each),
            "prev": np.concatenate([start_cells[None, :], cells[:-1]])}


def client_centres(grid: Grid, n: int, radius: float, seed) -> list:
    """Each client's standing sphere: ``(cx, cz, cells covered)``, drawn
    from the seed. A centre is drawn again where some cell's rectangle,
    hit or missed, lies within ``border_guard`` (64 float32 steps) of the
    sphere's edge: there float32 on the chip may fairly decide either
    way. Anything coarser than float32 moves a sphere by far more than
    that. The clients stand still for the run, so every delivery is
    known beforehand."""
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        cx = f32(rng.uniform(grid.offset_x, grid.offset_x + grid.width))
        cz = f32(rng.uniform(grid.offset_z, grid.offset_z + grid.height))
        cells, slack = grid.sphere_cells(cx, cz, radius)
        if slack > grid.border_guard:
            out.append((cx, cz, cells))
    return out


CELL_PATH, ENTITY_PATH, HANDOVER_PATH = 0, 1, 2  # how a row reached a client


class Worker:
    """One worker process: its peers, and a ``do_<phase>`` method for each
    phase the parent asks for."""

    def __init__(self, pipe, spec: dict):
        self.pipe, self.spec = pipe, spec
        self.peers = wire.Peers()

    def on_message(self, peer, t: float, mp) -> None:
        raise NotImplementedError

    def pump(self, seconds: float, until=None) -> bool:
        return self.peers.pump(seconds, until, self.on_message)

    def serve(self) -> None:
        """Answer the parent's phases until ``quit``."""
        try:
            while True:
                if not self.pipe.poll():
                    self.pump(0.05)
                    continue
                name, *args = self.pipe.recv()
                if name == "quit":
                    return
                # No collector pause inside a phase: a pass over the lists
                # of stamps a window gathers takes tens of milliseconds,
                # and would be read as the gateway's lateness.
                gc.disable()
                try:
                    answer = getattr(self, "do_" + name)(*args)
                finally:
                    gc.enable()
                self.pipe.send(("ok", answer))
        except Exception as e:  # the parent reports it and ends the run
            import traceback

            self.pipe.send(("error", f"{type(e).__name__}: {e}\n"
                            f"{traceback.format_exc()}"))
        finally:
            self.peers.close()


class _SpatialWorker(Worker):
    def __init__(self, pipe, spec: dict):
        super().__init__(pipe, spec)
        self.plan = plan(spec)
        self.grid = self.plan["grid"]
        self.first_eid = self.grid.entity_start + 1


class Sender(_SpatialWorker):
    """Some of the spatial servers: claims their blocks, creates the
    entities that start in them, and in the window sends each update its
    servers own, stamped, on the open-loop schedule."""

    def __init__(self, pipe, spec):
        super().__init__(pipe, spec)
        self.cells_of_peer: dict = {}  # peer -> set of 0-based cells
        self.ready = 0
        self.created = 0
        self.seen_at: dict = {}  # (server, n, src, dst) -> [read times]
        self.handover_log: list = []  # (t, server, n, src, dst)

    def on_message(self, peer, t, mp) -> None:
        kind = mp.msgType
        if kind == wire.HANDOVER:
            msg = wire.spatial_pb2.ChannelDataHandoverMessage.FromString(
                mp.msgBody)
            src = msg.srcChannelId - self.grid.cell_start
            dst = msg.dstChannelId - self.grid.cell_start
            for state in wire.states_in(msg.data):
                n = state.entityId - self.first_eid
                if 0 <= n < self.spec["entities"]:  # sim agents cross too
                    key = (peer.index, n, src, dst)
                    self.seen_at.setdefault(key, []).append(t)
                    self.handover_log.append((t, *key))
        elif kind == wire.CREATE_SPATIAL:
            msg = wire.spatial_pb2.CreateSpatialChannelsResultMessage.FromString(
                mp.msgBody)
            self.cells_of_peer[peer].update(
                c - self.grid.cell_start for c in msg.spatialChannelId)
        elif kind == wire.READY:
            self.ready += 1
        elif kind == wire.CREATE_ENTITY:
            self.created += 1

    def do_claim(self) -> dict:
        """Connect this worker's servers and claim a block each."""
        for name in self.spec["servers"]:
            p = wire.connect(self.spec["sport"], name, self.peers)
            self.cells_of_peer[p] = set()
            p.queue(0, wire.CREATE_CHANNEL, wire.control_pb2.CreateChannelMessage(
                channelType=wire.SPATIAL,
                data=wire.pack_any(wire.sim_pb2.SimSpatialChannelData()),
                subOptions=wire.control_pb2.ChannelSubscriptionOptions(
                    dataAccess=wire.WRITE_ACCESS)).SerializeToString())
            if not self.pump(30.0, until=lambda: self.cells_of_peer[p]):
                raise RuntimeError(f"{name} got no spatial channels")
        return {p.name: sorted(self.cells_of_peer[p]) for p in self.peers}

    def do_ready(self, owner_of: dict) -> int:
        """Wait for SPATIAL_CHANNELS_READY; learn who owns which cell.
        ``owner_of`` maps a cell to (server index, worker index)."""
        if not self.pump(90.0, until=lambda: self.ready >= len(self.peers)):
            raise RuntimeError("SPATIAL_CHANNELS_READY did not reach every "
                               "server")
        self.owner_of = owner_of
        by_name = {p.name: p for p in self.peers}
        self.peer_of_server = {}
        for i, name in enumerate(self.spec["all_servers"]):
            if name in by_name:
                by_name[name].index = i
                self.peer_of_server[i] = by_name[name]
        return self.ready

    def do_spawn(self) -> int:
        """Create the wire entities that start in this worker's cells,
        each by its cell's owner and with a transform (that is what
        tracks it on the device from birth), a burst at a time."""
        mine = 0
        for n, cell in enumerate(self.plan["start_cells"]):
            peer = self.peer_of_server.get(self.owner_of[int(cell)])
            if peer is None:
                continue
            mine += 1
            x, z = map(float, self.plan["start"][n])
            eid = self.first_eid + n
            data = wire.sim_pb2.SimEntityChannelData(
                state=wire.entity_state(eid, x, z, 0, 0.0))
            peer.queue(0, wire.CREATE_ENTITY,
                       wire.spatial_pb2.CreateEntityChannelMessage(
                           entityId=eid, data=wire.pack_any(data),
                           subOptions=wire.control_pb2.ChannelSubscriptionOptions(
                               dataAccess=wire.WRITE_ACCESS)).SerializeToString())
            if mine % 200 == 0:
                self.pump(0.0)
        if not self.pump(120.0, until=lambda: self.created >= mine):
            raise RuntimeError(f"only {self.created} of {mine} entity "
                               "channels were created")
        # The cells' tables as a running world has them: every entity's
        # row in the cell it stands in, before any client looks.
        tables: dict = {}
        for n, cell in enumerate(self.plan["start_cells"]):
            peer = self.peer_of_server.get(self.owner_of[int(cell)])
            if peer is not None:
                x, z = map(float, self.plan["start"][n])
                tables.setdefault(
                    (peer, int(cell)), wire.sim_pb2.SimSpatialChannelData()
                ).entities[self.first_eid + n].CopyFrom(
                    wire.entity_state(self.first_eid + n, x, z, 0, 0.0))
        for (peer, cell), table in tables.items():
            peer.queue(self.grid.cell_start + cell, wire.DATA_UPDATE,
                       wire.data_update(table))
        self.pump(0.5)
        return mine

    def do_go(self, t0: float, close: float, end: float) -> dict:
        """Send on the schedule from ``t0`` until ``end``; ``close`` is the
        window's last instant, after which an update may stay unsent."""
        pl, spec = self.plan, self.spec
        frame = spec["mix"]["frame_ms"] / 1000.0
        cells, prev, due, pos = pl["cells"], pl["prev"], pl["due"], pl["pos"]
        n_updates = cells.shape[0]
        cross = cells != prev
        # The ordinal of each crossing among the entity's crossings of
        # the same border in the same direction: the new owner waits for
        # that many handovers, not for any earlier one.
        ordinal = np.zeros(cells.shape, np.int32)
        counts: dict = collections.Counter()
        for k, n in zip(*np.nonzero(cross)):
            key = (int(n), int(prev[k, n]), int(cells[k, n]))
            counts[key] += 1
            ordinal[k, n] = counts[key]
        server_of = np.vectorize(self.owner_of.get, otypes=[np.int64])
        sender = server_of(prev)  # update k is sent by the owner of the
        mine = np.isin(sender, list(self.peer_of_server))  # cell it leaves
        ks, ns = np.nonzero(mine)
        order = np.argsort(due[ks, ns], kind="stable")
        events = collections.deque(
            zip(due[ks, ns][order].tolist(), ks[order].tolist(),
                ns[order].tolist()))
        queues: dict = collections.defaultdict(collections.deque)
        sent: list = []  # (k, n, send time, time it became eligible)
        cell_start = self.grid.cell_start
        f = 0
        while True:
            frame_t = t0 + f * frame
            if frame_t >= end:
                break
            # Read (handovers, fan-out to the servers) until the frame.
            while True:
                left = frame_t - time.monotonic()
                if left <= 0:
                    break
                self.peers.flush()
                self.peers.wait(min(left, 0.05), self.on_message)
            horizon = f * frame + 1e-9
            while events and events[0][0] <= horizon:
                _, k, n = events.popleft()
                queues[n].append(k)
            tables: dict = {}
            for n in [n for n, q in queues.items() if q]:
                q = queues[n]
                while q:
                    k = q[0]
                    eligible = t0 + due[k, n]
                    if k and cross[k - 1, n]:
                        key = (int(sender[k, n]), n, int(prev[k - 1, n]),
                               int(cells[k - 1, n]))
                        reads = self.seen_at.get(key, ())
                        if len(reads) < ordinal[k - 1, n]:
                            break  # its handover has not reached us yet
                        eligible = max(eligible,
                                       reads[ordinal[k - 1, n] - 1])
                    q.popleft()
                    x, z = float(pos[k, n, 0]), float(pos[k, n, 1])
                    eid = self.first_eid + n
                    state = wire.entity_state(eid, x, z, k + 1,
                                              t0 + due[k, n])
                    peer = self.peer_of_server[int(sender[k, n])]
                    peer.queue(eid, wire.DATA_UPDATE, wire.data_update(
                        wire.sim_pb2.SimEntityChannelData(state=state)))
                    if not cross[k, n]:
                        # The row of the cell's table that fans out to the
                        # watching clients; a crossing entity's row is
                        # moved by the gateway's handover.
                        tables.setdefault(
                            (peer, int(cells[k, n])),
                            wire.sim_pb2.SimSpatialChannelData(),
                        ).entities[eid].CopyFrom(state)
                    sent.append((k, n, time.monotonic(), eligible))
            for (peer, cell), table in tables.items():
                peer.queue(cell_start + cell, wire.DATA_UPDATE,
                           wire.data_update(table))
            self.peers.flush()
            f += 1
        # Held to the end for a handover that never came. What falls due
        # in the drain may still be waiting for one, fairly.
        unsent = sum(bool(t0 + due[k, n] < close)
                     for n, q in queues.items() for k in q)
        return {"sent": np.array(sent, np.float64).reshape(-1, 4),
                "handovers": np.array(self.handover_log,
                                      np.float64).reshape(-1, 5),
                "unsent": unsent, "updates": n_updates}


class _Client:
    """One client's view: what it covers, what it is subscribed to, and
    every read of a stamped row newer than the last of its entity."""

    def __init__(self, peer, cells, entities: int):
        self.peer = peer
        self.covers = set(cells)  # 0-based cells its sphere overlaps
        self.subscribed: set = set()  # channel ids
        self.pending: set = set()  # entity channels asked for or dropped
        self.where: dict = {}  # entity channel -> last known cell
        self.last = np.zeros(entities, np.int64)  # newest sequence read
        self.handovers: dict = collections.Counter()  # (n, src, dst) read
        self.reads: list = []  # (n, seq, t)
        self.sub_body = wire.control_pb2.SubscribedToChannelMessage(
            connId=peer.conn_id,
            subOptions=wire.control_pb2.ChannelSubscriptionOptions(
                dataAccess=wire.READ_ACCESS)).SerializeToString()
        self.unsub_body = wire.control_pb2.UnsubscribedFromChannelMessage(
            connId=peer.conn_id).SerializeToString()


class Receiver(_SpatialWorker):
    """Some of the clients: connects them, keeps their subscriptions as
    an engine's client would, and notes every read of a stamped row."""

    def __init__(self, pipe, spec):
        super().__init__(pipe, spec)
        self.centres = [self.plan["centres"][i] for i in spec["clients"]]
        self.clients: dict = {}  # peer -> _Client
        # (n, src, dst) -> the sequence numbers of the updates that carry
        # entity n over that border, in order.
        self.crossing_seqs: dict = collections.defaultdict(list)
        for k, n, src, dst in zip(*stats.crossings(self.plan["start_cells"],
                                                   self.plan["cells"])):
            self.crossing_seqs[int(n), int(src), int(dst)].append(int(k) + 1)
        self.lags: list = []  # (path, read time, read - due)
        self.wrong_rows = 0
        self.rows = 0

    def on_message(self, peer, t, mp) -> None:
        kind, c = mp.msgType, self.clients[peer]
        if kind == wire.DATA_UPDATE:
            msg = wire.control_pb2.ChannelDataUpdateMessage.FromString(
                mp.msgBody)
            cell = mp.channelId - self.grid.cell_start
            states = wire.states_in(msg.data)
            if 0 <= cell < self.grid.num_cells:
                self._rows(c, t, states, CELL_PATH)
                # As an engine's client does: what it sees in a cell it
                # watches, it subscribes to, and an entity's state then
                # reaches it through the entity's own channel.
                for state in states:
                    c.where.setdefault(state.entityId, cell)
                    self._want(c, state.entityId)
            else:
                self._rows(c, t, states, ENTITY_PATH)
        elif kind == wire.HANDOVER:
            msg = wire.spatial_pb2.ChannelDataHandoverMessage.FromString(
                mp.msgBody)
            states = wire.states_in(msg.data)
            self._rows(c, t, states, HANDOVER_PATH)
            src = msg.srcChannelId - self.grid.cell_start
            dst = msg.dstChannelId - self.grid.cell_start
            for state in states:
                c.where[state.entityId] = dst
                self._want(c, state.entityId)
                # The notice itself is the entity's later state, even
                # where it names the entity alone (a client that watches
                # only the cell that was left): it stands for the update
                # that crossed, and so for every earlier one.
                n = state.entityId - self.first_eid
                key = (n, src, dst)
                c.handovers[key] += 1
                seqs = self.crossing_seqs.get(key, ())
                if c.handovers[key] <= len(seqs):
                    seq = seqs[c.handovers[key] - 1]
                    if seq > c.last[n]:
                        c.last[n] = seq
                        c.reads.append((n, seq, t))
        elif kind in (wire.SUB, wire.UNSUB):
            result = (wire.control_pb2.SubscribedToChannelResultMessage
                      if kind == wire.SUB else
                      wire.control_pb2.UnsubscribedFromChannelResultMessage)
            if result.FromString(mp.msgBody).connId == peer.conn_id:
                (c.subscribed.add if kind == wire.SUB
                 else c.subscribed.discard)(mp.channelId)
                c.pending.discard(mp.channelId)
                self._want(c, mp.channelId)

    def _want(self, c: _Client, channel: int) -> None:
        """Hold a client's subscription to an entity's channel to where
        the entity is: subscribed while it is in a cell the client
        watches, dropped once a handover takes it out of them."""
        if channel < self.grid.entity_start or channel in c.pending:
            return
        cell = c.where.get(channel)
        have = channel in c.subscribed
        if cell in c.covers and not have:
            c.peer.queue(channel, wire.SUB, c.sub_body)
            c.pending.add(channel)
        elif cell is not None and cell not in c.covers and have:
            c.peer.queue(channel, wire.UNSUB, c.unsub_body)
            c.pending.add(channel)

    def _rows(self, c: _Client, t, states, path: int) -> None:
        last, pos = c.last, self.plan["pos"]
        for state in states:
            seq, due = wire.read_stamp(state.payload)
            n = state.entityId - self.first_eid
            if not seq or not 0 <= n < len(last):
                continue
            self.rows += 1
            if path == CELL_PATH:  # every read: how stale the tables run
                self.lags.append((path, t, t - due))
            if seq > last[n]:
                last[n] = seq
                c.reads.append((n, seq, t))
                if path != CELL_PATH:
                    self.lags.append((path, t, t - due))
                p = state.transform.position
                if (seq > len(pos) or p.x != pos[seq - 1, n, 0]
                        or p.z != pos[seq - 1, n, 1]):
                    self.wrong_rows += 1  # not the state that was sent

    def do_connect(self) -> list:
        for i, (cx, cz, cells) in zip(self.spec["clients"], self.centres):
            p = wire.connect(self.spec["cport"], f"bench-client-{i}",
                             self.peers, attempts=30)
            self.clients[p] = _Client(p, cells, self.spec["entities"])
        return [p.conn_id for p in self.peers]

    def do_subs(self) -> list:
        """Each client's spatial subscriptions, as 0-based cells."""
        g = self.grid
        return [sorted(ch - g.cell_start for ch in self.clients[p].subscribed
                       if g.cell_start <= ch < g.entity_start)
                for p in self.peers]

    def do_go(self, t0: float, close: float, end: float) -> dict:
        while time.monotonic() < end:
            self.pump(min(0.25, max(0.0, end - time.monotonic())))
        pl = self.plan
        due = t0 + pl["due"]
        out = []
        for i, p in zip(self.spec["clients"], self.peers):
            c = self.clients[p]
            covers = np.zeros(self.grid.num_cells, bool)
            covers[list(c.covers)] = True
            rec = np.array(c.reads, np.float64).reshape(-1, 3).T
            k, n, d, r = stats.delivery_times(pl["cells"], due, covers, rec)
            out.append((np.full(len(k), i), k, n, d, r))
        client, k, n, d, r = map(np.concatenate, zip(*out))
        return {"client": client, "k": k, "n": n, "due": d, "read": r,
                "lags": np.array(self.lags, np.float64).reshape(-1, 3),
                "wrong_rows": self.wrong_rows, "rows": self.rows,
                "entity_subs": sum(
                    sum(1 for ch in c.subscribed if ch >= self.grid.entity_start)
                    for c in self.clients.values())}


def run_worker(kind: str, pipe, spec: dict) -> None:
    """Entry of a spawned worker process: the peers of kind ``kind`` of
    the flow in the file ``spec["flow"]``."""
    import sys

    sys.path.insert(0, spec["repo"])
    from .driver import load_file

    flow = load_file(spec["flow"], "flow_" + kind)
    flow.WORKERS[kind](pipe, spec).serve()
