"""The peers' side of the wire: one non-blocking TCP socket to the
gateway, upstream's 5-byte framing, and the few messages the cells send.

The message types and their fields are the program's protocol and come
from its generated ``*_pb2`` modules; the framing, the queues and the
clock are the benchmark's own, so that a later change to the program's
client SDK cannot move what the benchmark times. A frame's read time is
``time.monotonic()`` straight after the ``recv`` that completed it.
"""

from __future__ import annotations

import socket
import struct
import time

from channeld_tpu.models import sim_pb2
from channeld_tpu.protocol import control_pb2, spatial_pb2, wire_pb2

HEADER = 5
MAX_BODY = 0xFFFF
# Message types of wire.proto's MessageType, as the cells use them.
AUTH, CREATE_CHANNEL, SUB, UNSUB, DATA_UPDATE = 1, 3, 6, 7, 8
CREATE_SPATIAL, HANDOVER, INTEREST, CREATE_ENTITY, READY, BUSY = (
    10, 12, 14, 15, 18, 24)
GLOBAL, SPATIAL = 1, 4  # channel types
READ_ACCESS, WRITE_ACCESS = 1, 2

# EntityState.payload of a stamped update: magic, sequence number (the
# update's index + 1; 0 marks the state an entity is created with) and
# the due time on the peers' shared CLOCK_MONOTONIC.
STAMP = struct.Struct("<4sId")
MAGIC = b"CHBM"
_ANY_PREFIX = "type.googleapis.com/"


def stamp(seq: int, due: float) -> bytes:
    return STAMP.pack(MAGIC, seq, due)


def read_stamp(payload: bytes) -> tuple:
    """``(sequence number, due time)`` of a stamped state; ``(0, 0.0)``
    for any other payload."""
    if len(payload) == STAMP.size and payload[:4] == MAGIC:
        return STAMP.unpack(payload)[1:]
    return 0, 0.0


def pack_any(msg):
    from google.protobuf import any_pb2

    return any_pb2.Any(type_url=_ANY_PREFIX + msg.DESCRIPTOR.full_name,
                       value=msg.SerializeToString())


def entity_state(eid: int, x: float, z: float, seq: int, due: float):
    state = sim_pb2.EntityState(entityId=eid, payload=stamp(seq, due))
    state.transform.position.x = x
    state.transform.position.z = z
    return state


def data_update(data) -> bytes:
    return control_pb2.ChannelDataUpdateMessage(
        data=pack_any(data)).SerializeToString()


def states_in(any_msg) -> list:
    """The EntityState rows an update or a handover carries."""
    name = any_msg.type_url.rpartition("/")[2]
    if name == "chtpu.sim.SimSpatialChannelData":
        table = sim_pb2.SimSpatialChannelData.FromString(any_msg.value)
        rows = []
        for eid, state in table.entities.items():
            if not state.entityId:
                state.entityId = eid
            rows.append(state)
        return rows
    if name == "chtpu.sim.SimEntityChannelData":
        return [sim_pb2.SimEntityChannelData.FromString(any_msg.value).state]
    return []


class PeerClosed(Exception):
    """The gateway closed this peer's socket."""


class Peer:
    """One connection: queue messages, ``flush`` them as packets, ``read``
    what has arrived as ``(read time, MessagePack)`` pairs."""

    def __init__(self, port: int, name: str):
        self.name, self.conn_id = name, 0
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=10)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.setblocking(False)
        self._in = bytearray()
        self._out = bytearray()
        self._queued: list = []
        self.busy = False  # the gateway answered AUTH with ServerBusy

    def fileno(self) -> int:
        return self.sock.fileno()

    def queue(self, channel_id: int, msg_type: int, body: bytes) -> None:
        self._queued.append(wire_pb2.MessagePack(
            channelId=channel_id, msgType=msg_type, msgBody=body))

    def flush(self) -> bool:
        """Frame what is queued and write what the socket takes; True
        while bytes are left over for a later call."""
        packet, size = wire_pb2.Packet(), 0
        for mp in self._queued:
            n = mp.ByteSize() + 6
            if packet.messages and size + n > MAX_BODY:
                self._frame(packet)
                packet, size = wire_pb2.Packet(), 0
            packet.messages.append(mp)
            size += n
        self._queued.clear()
        if packet.messages:
            self._frame(packet)
        if self._out:
            try:
                sent = self.sock.send(self._out)
            except BlockingIOError:
                sent = 0
            except OSError as e:
                raise PeerClosed(f"{self.name}: {e}") from None
            del self._out[:sent]
        return bool(self._out)

    def _frame(self, packet) -> None:
        body = packet.SerializeToString()
        if len(body) > MAX_BODY:
            raise ValueError(f"{self.name}: a {len(body)}-byte packet")
        self._out += bytes((0x43, 0x48, len(body) >> 8, len(body) & 0xFF, 0))
        self._out += body

    def read(self) -> list:
        try:
            data = self.sock.recv(1 << 18)
        except BlockingIOError:
            return []
        except OSError as e:
            raise PeerClosed(f"{self.name}: {e}") from None
        now = time.monotonic()
        if not data:
            raise PeerClosed(f"{self.name}: closed by the gateway")
        buf = self._in
        buf += data
        out, pos = [], 0
        while len(buf) - pos >= HEADER:
            if buf[pos] != 0x43:
                raise PeerClosed(f"{self.name}: bad frame tag")
            # Upstream's 3-byte size escape: byte 1 is 'H' or the size's
            # topmost byte (server->client packets over 64 KB).
            top = 0 if buf[pos + 1] == 0x48 else buf[pos + 1]
            size = (top << 16) | (buf[pos + 2] << 8) | buf[pos + 3]
            if len(buf) - pos < HEADER + size:
                break
            body = bytes(buf[pos + HEADER:pos + HEADER + size])
            if buf[pos + 4] == 1:
                from channeld_tpu.protocol import snappy

                body = snappy.uncompress(body, max_len=1 << 24)
            pos += HEADER + size
            for mp in wire_pb2.Packet.FromString(body).messages:
                if mp.msgType == AUTH and not self.conn_id:
                    self.conn_id = control_pb2.AuthResultMessage.FromString(
                        mp.msgBody).connId
                elif mp.msgType == BUSY:
                    self.busy = True
                out.append((now, mp))
        del buf[:pos]
        return out

    def auth(self) -> None:
        self.queue(0, AUTH, control_pb2.AuthMessage(
            playerIdentifierToken=self.name).SerializeToString())
        self.flush()

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


def pump(peers, seconds: float, until=None, on_message=None) -> bool:
    """Read and flush ``peers`` for ``seconds`` or until ``until()``;
    ``on_message(peer, read time, MessagePack)`` sees what arrives."""
    import select

    end = time.monotonic() + seconds
    while True:
        if until is not None and until():
            return True
        left = end - time.monotonic()
        if left <= 0:
            return until is None
        writers = [p for p in peers if p.flush()]
        readable, _, _ = select.select(peers, writers, [], min(left, 0.05))
        for p in readable:
            for t, mp in p.read():
                if on_message is not None:
                    on_message(p, t, mp)


def connect(port: int, name: str, others=(), attempts: int = 1) -> Peer:
    """Connect and AUTH. At overload L3 the gateway answers a client's
    AUTH with ServerBusy and closes: try again, paced."""
    for _ in range(attempts):
        p = Peer(port, name)
        p.auth()
        try:
            pump([p, *others], 5.0, until=lambda: p.conn_id or p.busy)
        except PeerClosed:
            pass
        if p.conn_id:
            return p
        p.close()
        time.sleep(1.0)
    raise PeerClosed(f"{name}: AUTH refused {attempts} times")


def sphere_interest(conn_id: int, cx: float, cz: float, radius: float) -> bytes:
    msg = spatial_pb2.UpdateSpatialInterestMessage(connId=conn_id)
    msg.query.sphereAOI.center.x = cx
    msg.query.sphereAOI.center.z = cz
    msg.query.sphereAOI.radius = radius
    return msg.SerializeToString()
