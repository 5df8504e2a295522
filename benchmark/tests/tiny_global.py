"""A deployment that is no spatial world, as a flow of its own: GLOBAL
chat. Clients subscribe to GLOBAL (the gateway runs with ``-imports
channeld_tpu.models.chat``, so GLOBAL holds ``ChatChannelData``), and
sayers, clients with write access, each say a stamped ``ChatMessage``
every ``every_ms`` on a schedule drawn from the seed. The reference:
every say is reflected, with the content it was said with, at every
listening subscriber.

``rehearse.make_root`` writes this file into a copy's ``flows/`` as a new
file, beside a configuration that names it: the harness runs it with no
file of its own edited.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import wire
from benchmark.harness.workers import Worker
from channeld_tpu.models import chat_pb2


def says(spec: dict) -> tuple:
    """``(due offsets f64[S, K], tokens i64[S, K])`` of every sayer's
    says: a phase from the seed, then one every ``every_ms``."""
    rng = np.random.default_rng([spec["seed"], 2])
    every = spec["mix"]["every_ms"] / 1000.0
    count = int(spec["seconds_total"] / every)
    phase = rng.uniform(0.0, every, len(spec["all_sayers"]))
    due = phase[:, None] + every * np.arange(count)[None, :]
    return due, rng.integers(0, 1 << 30, due.shape)


def content(s: int, k: int, token: int) -> str:
    return f"{s}:{k}:{token}"


class _Chat(Worker):
    def subscribe(self, names: list, port: int, access: int) -> list:
        subscribed: set = set()

        def on_message(peer, t, mp) -> None:
            if mp.msgType == wire.SUB and mp.channelId == 0:
                subscribed.add(peer)

        for name in names:
            wire.connect(port, name, self.peers)
        for p in self.peers:  # once all are in: connect drops what it reads
            p.queue(0, wire.SUB, wire.control_pb2.SubscribedToChannelMessage(
                connId=p.conn_id,
                subOptions=wire.control_pb2.ChannelSubscriptionOptions(
                    dataAccess=access)).SerializeToString())
        if not self.peers.pump(30.0, lambda: len(subscribed) == len(names),
                               on_message):
            raise RuntimeError(f"{len(subscribed)} of {len(names)} "
                               "subscribed to GLOBAL")
        return [p.conn_id for p in self.peers]

    def on_message(self, peer, t, mp) -> None:
        pass


class Sayer(_Chat):
    def do_connect(self) -> list:
        return self.subscribe(self.spec["sayers"], self.spec["cport"],
                              wire.WRITE_ACCESS)

    def do_go(self, t0: float, close: float, end: float) -> dict:
        due, tokens = says(self.spec)
        mine = [self.spec["all_sayers"].index(n) for n in self.spec["sayers"]]
        order = sorted(((due[s, k], s, k, peer) for s, peer in
                        zip(mine, self.peers) for k in range(due.shape[1])),
                       key=lambda say: say[:3])
        sent = 0
        for d, s, k, peer in order:
            if t0 + d >= end:
                break
            self.peers.pump(max(0.0, t0 + d - time.monotonic()))
            msg = chat_pb2.ChatChannelData()
            msg.chatMessages.add(sender=peer.name, sendTime=int(d * 1000),
                                 content=content(s, k, int(tokens[s, k])))
            peer.queue(0, wire.DATA_UPDATE, wire.data_update(msg))
            self.peers.flush()
            sent += 1
        unsent = sum(t0 + d < close for d, *_ in order[sent:])
        return {"sent": sent, "unsent": unsent}


class Listener(_Chat):
    def __init__(self, pipe, spec):
        super().__init__(pipe, spec)
        self.tokens = says(spec)[1]
        self.reads: dict = {}  # (client, sayer, k) -> first read time
        self.wrong = 0

    def do_connect(self) -> list:
        ids = self.subscribe([f"bench-listener-{i}" for i in
                              self.spec["clients"]],
                             self.spec["cport"], wire.READ_ACCESS)
        self.index = {p: i for i, p in zip(self.spec["clients"], self.peers)}
        return ids

    def on_message(self, peer, t, mp) -> None:
        if mp.msgType != wire.DATA_UPDATE or mp.channelId != 0:
            return
        update = wire.control_pb2.ChannelDataUpdateMessage.FromString(
            mp.msgBody)
        data = chat_pb2.ChatChannelData.FromString(update.data.value)
        for m in data.chatMessages:
            s, k, token = map(int, m.content.split(":"))
            if self.tokens[s, k] != token:
                self.wrong += 1
            self.reads.setdefault((self.index[peer], s, k), t)

    def do_go(self, t0: float, close: float, end: float) -> dict:
        self.pump(max(0.0, end - time.monotonic()))
        return {"reads": np.array([(*key, t) for key, t in self.reads.items()],
                                  np.float64).reshape(-1, 4),
                "wrong": self.wrong}


WORKERS = {"sayer": Sayer, "listener": Listener}


def prepare(cell: dict, seed: int, seconds: float) -> dict:
    config, mix = cell["config"], cell["mix"]
    pop = config["populations"]
    sayers = [f"bench-sayer-{i}" for i in range(pop["sayers"])]
    spec = {"mix": mix, "seed": seed, "all_sayers": sayers,
            "seconds_total": mix["warmup_s"] + seconds + mix["drain_s"]}
    n = config["workers"]["listeners"]
    clients = list(range(pop["clients"]))
    return {"says_due": says(spec)[0],
            "specs": [("sayer", dict(spec, sayers=sayers))]
            + [("listener", dict(spec, clients=clients[i::n]))
               for i in range(n)]}


def build(cell, prepared, gw, master, peers, workers) -> dict:
    workers.call("listener", "connect")
    workers.call("sayer", "connect")
    peers.pump(1.0)
    return {"says_due": prepared["says_due"], "checks": {}}


def after_window(world, workers) -> None:
    pass


def account(cell: dict, run: dict) -> dict:
    clients = cell["config"]["populations"]["clients"]
    (said,) = run["answers"]["sayer"]
    listened = run["answers"]["listener"]
    due = run["t0"] + run["says_due"]
    reads = np.concatenate([a["reads"] for a in listened])
    read = np.full((clients,) + due.shape, np.nan)
    cols = reads[:, :3].astype(np.int64)
    ok = cols[:, 2] < due.shape[1]
    read[cols[ok, 0], cols[ok, 1], cols[ok, 2]] = reads[ok, 3]
    due = np.broadcast_to(due, read.shape)
    in_window = (due >= run["w0"]) & (due < run["w1"])
    unreflected = int(np.isnan(read[in_window]).sum())
    return {"due": due[in_window], "read": read[in_window],
            "reflected_in_window": int(((read >= run["w0"])
                                        & (read < run["w1"])).sum()),
            "attempted": int(in_window.sum()), "failed": unreflected,
            "checks": {"says_unsent": said["unsent"],
                       "says_unreflected": unreflected,
                       "says_wrong": sum(a["wrong"] for a in listened)},
            "info": {"says_sent": said["sent"]}, "ctx": {}}
