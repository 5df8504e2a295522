"""The send pump's native pass (``core/server.py _pump_sends``,
``native/codec.cc send_packets``) and the codec's send writer thread
over real loopback sockets.

A pass of the pump that found output queued makes ONE call into the
native codec, which encodes the packets of every TCP peer and hands
them to the writer thread, which makes the send calls; everything else
goes through ``Connection.flush``. Held here: the peers read byte for
byte what ``encode_packets`` + ``transport.write`` give for the same
queues; a socket that takes less keeps the rest at the head of its
connection's queue, in order with every later byte; the backlog is
what the transport gate and ``MAX_SEND_BUFFER`` read; ``close`` drains
and ``abort`` drops; the writer's own descriptor outlives a close; a
fault on one socket touches no other connection and reaches ``fail``
on the next pass; a pass returns without waiting for a peer; the other
transports take the old path; the counters and the ``send_pump`` stage
read the same either way.
"""

import asyncio
import errno
import random
import select
import socket
import struct
import sys
import threading
import time

import pytest
from prometheus_client import generate_latest

from channeld_tpu import native as native_pkg
from channeld_tpu.core import connection as connection_mod
from channeld_tpu.core import edge
from channeld_tpu.core import metrics
from channeld_tpu.core import server as server_mod
from channeld_tpu.core.connection import Connection, add_connection
from channeld_tpu.core.message import MessageContext
from channeld_tpu.core.server import TcpTransport, flush_loop, start_listening
from channeld_tpu.core.settings import global_settings
from channeld_tpu.core.types import CompressionType, ConnectionType
from channeld_tpu.native import codec
from channeld_tpu.protocol import snappy as snappy_codec

from helpers import FakeTransport, fresh_runtime, stage_count

pytestmark = pytest.mark.skipif(
    getattr(codec, "send_packets", None) is None,
    reason="the native codec (with send_packets) is not built")


@pytest.fixture(autouse=True)
def runtime():
    gch = fresh_runtime()
    global_settings.development = True
    connection_mod.set_fsm_templates(None, None)
    yield gch


# ---------------------------------------------------------------------------
# a gateway's TCP listener, and peers that are plain sockets
# ---------------------------------------------------------------------------


class Peer:
    """One TCP peer: its socket here, its connection in the gateway."""

    def __init__(self, sock: socket.socket, conn: Connection):
        self.sock, self.conn = sock, conn

    async def read(self, n: int, timeout: float = 5.0) -> bytes:
        """Exactly ``n`` bytes off the socket (the loop runs meanwhile,
        so a transport with bytes buffered drains)."""
        loop = asyncio.get_running_loop()
        got = bytearray()
        while len(got) < n:
            chunk = await asyncio.wait_for(
                loop.sock_recv(self.sock, min(1 << 20, n - len(got))), timeout)
            assert chunk, f"peer closed after {len(got)} of {n} bytes"
            got += chunk
        return bytes(got)

    async def nothing_more(self, wait: float = 0.05) -> bool:
        """Nothing (more) arrives within ``wait``; what did is dropped."""
        loop = asyncio.get_running_loop()
        try:
            return not await asyncio.wait_for(
                loop.sock_recv(self.sock, 1 << 20), wait)
        except asyncio.TimeoutError:
            return True


class Gateway:
    """A TCP listener on a free loopback port, inside the running loop."""

    async def __aenter__(self):
        self.server = await start_listening(
            ConnectionType.CLIENT, "tcp", "127.0.0.1:0")
        self.port = self.server.sockets[0].getsockname()[1]
        self.peers: list[Peer] = []
        return self

    async def __aexit__(self, *exc):
        for peer in self.peers:
            peer.sock.close()
        self.server.close()
        await self.server.wait_closed()
        await asyncio.sleep(0.01)  # let every connection_lost run

    async def connect(self, n: int, rcvbuf: int = 0) -> list[Peer]:
        loop = asyncio.get_running_loop()
        socks = []
        for _ in range(n):
            sock = socket.socket()
            if rcvbuf:
                sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
            sock.setblocking(False)
            await loop.sock_connect(sock, ("127.0.0.1", self.port))
            socks.append(sock)
        # Each new socket's own connection, found by its address (the
        # registry may hold others); the bound is loose, for a loaded
        # host.
        wanted = {s.getsockname()[:2] for s in socks}
        by_addr: dict = {}
        deadline = time.monotonic() + 30
        while not wanted <= by_addr.keys():
            assert time.monotonic() < deadline, (
                f"the listener accepted {len(wanted & by_addr.keys())} of "
                f"{n}; registered: {sorted(by_addr)}")
            await asyncio.sleep(0.005)
            by_addr = {tuple(c.remote_addr()[:2]): c
                       for c in connection_mod.all_connections().values()
                       if type(c.transport) is TcpTransport}
        new = [Peer(s, by_addr[s.getsockname()[:2]]) for s in socks]
        self.peers += new
        return new


def run(main) -> None:
    asyncio.run(asyncio.wait_for(main(), 60))


def queue(conn: Connection, batch: list) -> None:
    """Queue ``batch`` through the connection's real sender."""
    for channel_id, broadcast, stub_id, msg_type, body in batch:
        conn.send(MessageContext(msg_type=msg_type, channel_id=channel_id,
                                 broadcast=broadcast, stub_id=stub_id,
                                 raw_body=body))


def pump(conns=None, wait: bool = True) -> int:
    """One pass of the send pump over what is pending, or over what of
    it is ``conns``' where given; with ``wait``, until the writer has
    written every byte the pass's TCP peers hold."""
    if conns is None:
        pending = connection_mod.drain_pending_flush()
    else:
        pending = {c for c in conns if c in connection_mod._pending_flush}
        connection_mod._pending_flush.difference_update(pending)
    first = server_mod._pump_sends(pending)
    if wait:
        for conn in pending:
            if type(conn.transport) is TcpTransport:
                until(lambda: not backlog(conn), "the writer did not drain")
    return first


def backlog(conn: Connection) -> int:
    """Bytes the send writer holds for ``conn``."""
    return conn.transport.get_write_buffer_size()


def until(ready, what: str, timeout: float = 10.0) -> None:
    """Wait (blocking the loop: the writer is its own thread) for
    ``ready()``; the bound is loose, for a loaded host."""
    deadline = time.monotonic() + timeout
    while not ready():
        assert time.monotonic() < deadline, what
        time.sleep(0.001)


def wire(batch: list, compression: int = 0) -> bytes:
    """What ``Connection.flush`` puts on the wire for ``batch``."""
    frames, _counts = codec.encode_packets(batch, compression)
    return b"".join(frames)


def random_batch(rng: random.Random, messages: int, biggest: int) -> list:
    batch = []
    for _ in range(messages):
        size = rng.choice((0, 1, rng.randrange(2, 200),
                           rng.randrange(200, biggest)))
        # Half of the bodies compress, half do not.
        body = (bytes([rng.randrange(256)]) * size if rng.random() < 0.5
                else rng.randbytes(size))
        batch.append((rng.randrange(0, 1 << 20), rng.randrange(0, 3),
                      rng.choice((0, rng.randrange(1, 1 << 16))),
                      rng.randrange(1, 2000), body))
    return batch


def counter(name: str, **labels) -> float:
    return metrics.registry.get_sample_value(name, labels) or 0.0


def sent_counters() -> dict:
    out = {name: counter(name + "_total", conn_type="CLIENT")
           for name in ("packets_out", "bytes_out", "packets_comb")}
    out["messages_out"] = counter(
        "messages_out_total", conn_type="CLIENT", channel_type="",
        msg_type="")
    for path in ("native", "python"):
        out[path] = counter("send_pump_messages_total", path=path)
    out["partial"] = counter("send_pump_partial_writes_total")
    return out


def delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in sent_counters().items()}


def without_send_packets(monkeypatch) -> None:
    """A tree whose library is older than its source: no writer, so no
    TCP peer is registered with one and every send takes the old
    path."""
    monkeypatch.delattr(codec, "send_packets")
    monkeypatch.delattr(codec, "writer_register")


def stall(peer: Peer, sndbuf: int = 4096) -> None:
    """Shrink the gateway side's send buffer for a peer that will not
    read (its receive buffer was cut when it connected). The writer's
    descriptor shares the socket, and so the buffer."""
    peer.conn.transport._sock.setsockopt(
        socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf)


async def eof(peer: Peer, timeout: float = 5.0) -> bytes:
    """Everything the peer reads until EOF."""
    loop = asyncio.get_running_loop()
    got = bytearray()
    while True:
        chunk = await asyncio.wait_for(
            loop.sock_recv(peer.sock, 1 << 20), timeout)
        if not chunk:
            return bytes(got)
        got += chunk


# ---------------------------------------------------------------------------
# (a) byte for byte, on one connection and on many
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("snappy", [False, True], ids=["plain", "snappy"])
@pytest.mark.parametrize("peers,messages,biggest", [
    (1, 1, 300), (1, 40, 30_000), (7, 12, 70_000), (40, 5, 2_000),
], ids=["one-message", "one-peer-many-packets", "bodies-over-a-packet",
        "many-peers"])
def test_peers_read_what_flush_would_have_written(peers, messages, biggest,
                                                  snappy):
    if snappy and not snappy_codec.available():
        pytest.skip("no snappy")
    rng = random.Random(peers * 1000 + messages + snappy)
    ct = int(CompressionType.SNAPPY if snappy else
             CompressionType.NO_COMPRESSION)

    async def main():
        async with Gateway() as gw:
            conns = await gw.connect(peers)
            batches = []
            for peer in conns:
                peer.conn.compression_type = CompressionType(ct)
                batch = random_batch(rng, messages, biggest)
                queue(peer.conn, batch)
                # A body over the packet cap that reached the queue all
                # the same is skipped, as encode_packets skips it.
                if biggest > 65_535:
                    over = (1, 0, 0, 100, b"o" * 66_000)
                    peer.conn.send_queue.insert(len(batch) // 2, over)
                    batch.insert(len(batch) // 2, over)
                batches.append([e for e in peer.conn.send_queue])
            before = sent_counters()
            assert pump()
            for peer, batch in zip(conns, batches):
                expected = wire(batch, ct)
                assert await peer.read(len(expected)) == expected
                assert await peer.nothing_more(0.01)
                assert not peer.conn.send_queue
                assert peer.conn.envelope.queue_bytes == 0
            moved = delta(before)
            written = sum(sum(codec.encode_packets(b, ct)[1])
                          for b in batches)
            assert moved["native"] + moved["python"] == written
            assert moved["messages_out"] == written
            # Every peer is the writer's, so the native call took all.
            assert moved["native"] == written

    run(main)


def test_a_busy_second_thread_changes_nothing_of_the_bytes():
    """The pass gives the interpreter lock up nowhere and the writer
    takes it never; a thread that takes every turn it can get (the
    device worker's part) must find nothing of the pass to disturb."""
    rng = random.Random(3)
    stop = threading.Event()
    turns = [0]

    def spin():
        while not stop.is_set():
            turns[0] += 1

    async def main():
        async with Gateway() as gw:
            conns = await gw.connect(24)
            expected = [b""] * len(conns)
            for _ in range(20):
                for i, peer in enumerate(conns):
                    batch = random_batch(rng, rng.randrange(1, 6), 3_000)
                    queue(peer.conn, batch)
                    expected[i] += wire(batch)
                assert pump()
            for peer, want in zip(conns, expected):
                assert await peer.read(len(want)) == want

    interval = sys.getswitchinterval()
    worker = threading.Thread(target=spin, daemon=True)
    sys.setswitchinterval(1e-5)
    worker.start()
    try:
        run(main)
    finally:
        stop.set()
        worker.join(5)
        sys.setswitchinterval(interval)
    assert not worker.is_alive() and turns[0] > 0


# ---------------------------------------------------------------------------
# (b) a peer that does not read
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("passes", [2, 5])
def test_a_remainder_goes_to_the_transport_in_order(passes):
    """What a full socket does not take stays at the head of the
    connection's queue on the writer; every later pass goes the native
    way behind it, and the peer reads it all in order."""
    rng = random.Random(passes)

    async def main():
        async with Gateway() as gw:
            slow, = await gw.connect(1, rcvbuf=2048)
            other, = await gw.connect(1)
            stall(slow)
            before = sent_counters()
            expected = b""
            for i in range(passes):
                # ~0.5 MB a pass: far over the socket's few KB, under
                # the transport gate's 1 MB in all.
                batch = [(i, 0, 0, 100 + j, rng.randbytes(4_000))
                         for j in range(120 // passes)]
                queue(slow.conn, batch)
                queue(other.conn, [(i, 0, 0, 7, b"fine")])
                expected += wire(batch)
                assert pump(wait=False)
                assert backlog(slow.conn) > 0
                assert slow.conn.transport.writer_key()
                assert not slow.conn.send_queue  # all handed over
            until(lambda: counter("send_pump_partial_writes_total")
                  > before["partial"], "no send met the full socket")
            moved = delta(before)
            assert moved["native"] == 120 // passes * passes + passes
            assert moved["python"] == 0
            assert await slow.read(len(expected)) == expected
            assert await slow.nothing_more()
            fine = wire([(0, 0, 0, 7, b"fine")])
            assert len(await other.read(len(fine) * passes)) == \
                len(fine) * passes
            # Drained: the writer holds nothing for it.
            until(lambda: not backlog(slow.conn), "the backlog stayed")
            last = [(9, 0, 0, 9, b"after")]
            queue(slow.conn, last)
            mark = sent_counters()
            pump()
            assert delta(mark)["native"] == 1
            assert await slow.read(len(wire(last))) == wire(last)

    run(main)


def test_bytes_stay_in_order_across_passes_and_transport_writes():
    """Every byte of a connection goes through its one queue on the
    writer, whoever handed it off: the pump's passes and the
    transport's own writes (a direct flush, a disconnect's last
    message) interleave on a socket that keeps filling up."""
    rng = random.Random(7)

    async def main():
        async with Gateway() as gw:
            peer, = await gw.connect(1, rcvbuf=4096)
            stall(peer, 8192)
            expected = b""
            for i in range(30):
                batch = random_batch(rng, rng.randrange(1, 8), 9_000)
                queue(peer.conn, batch)
                expected += wire(batch)
                pump(wait=False)
                direct = [(i, 0, 0, 55, rng.randbytes(rng.randrange(1, 5_000)))]
                frame = wire(direct)
                peer.conn.transport.write(frame)
                expected += frame
            assert backlog(peer.conn) > 0  # the reader has not read yet
            await asyncio.sleep(0.05)  # the reader pauses, then drains
            assert await peer.read(len(expected)) == expected
            assert await peer.nothing_more()

    run(main)


def test_a_pass_returns_while_its_peer_reads_nothing():
    """A pass hands off and returns: a peer that reads nothing holds up
    neither the loop nor the writer's sends to every other peer."""

    async def main():
        async with Gateway() as gw:
            slow, = await gw.connect(1, rcvbuf=2048)
            others = await gw.connect(3)
            stall(slow)
            batch = [(1, 0, 0, 100, bytes([i]) * 8_000) for i in range(100)]
            queue(slow.conn, batch)
            began = time.monotonic()
            assert pump(wait=False)
            assert time.monotonic() - began < 2.0  # loose: a loaded host
            assert backlog(slow.conn) > len(wire(batch)) // 2
            small = [(2, 0, 0, 7, b"while it waits")]
            for peer in others:
                queue(peer.conn, small)
            pump([peer.conn for peer in others])  # their backlogs reach 0
            for peer in others:
                assert await peer.read(len(wire(small))) == wire(small)
            assert backlog(slow.conn) > 0
            assert await slow.read(len(wire(batch))) == wire(batch)

    run(main)


def test_many_peers_under_a_busy_thread_keep_their_bytes_and_order():
    """The loop thread and the writer share each peer's queue: with more
    peers than cores, a reader thread that drains them in bursts while
    passes and transport writes keep handing off (so the writer's sends
    meet full sockets and hand-offs at once), a thread that takes every
    turn it can and a short switch interval, every peer reads exactly
    its own bytes in order; one closed mid-stream reads them all, then
    EOF, and one aborted a prefix of them, then EOF."""
    rng = random.Random(41)
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass

    async def main():
        async with Gateway() as gw:
            peers = await gw.connect(24, rcvbuf=4096)
            for peer in peers:
                stall(peer, 8192)
            got = [bytearray() for _ in peers]
            ended = [False] * len(peers)
            done = threading.Event()

            def reader():
                index = {p.sock: i for i, p in enumerate(peers)}
                while not done.is_set():
                    live = [p.sock for i, p in enumerate(peers)
                            if not ended[i]]
                    ready, _, _ = select.select(live, [], [], 0.01)
                    for sock in ready:
                        try:
                            chunk = sock.recv(1 << 16)
                        except (BlockingIOError, ConnectionError):
                            continue
                        if chunk:
                            got[index[sock]] += chunk
                        else:
                            ended[index[sock]] = True
                    time.sleep(0.002)  # a pause: the sockets fill again

            drainer = threading.Thread(target=reader, daemon=True)
            drainer.start()
            expected = [b""] * len(peers)
            try:
                for _ in range(60):
                    for i, peer in enumerate(peers):
                        if rng.random() < 0.5:
                            batch = random_batch(rng, rng.randrange(1, 4),
                                                 3_000)
                            queue(peer.conn, batch)
                            expected[i] += wire(batch)
                    pump(wait=False)
                    # A direct write goes behind what the pass handed off.
                    for i, peer in enumerate(peers):
                        if rng.random() < 0.3:
                            frame = wire([(i, 0, 0, 9, rng.randbytes(500))])
                            peer.conn.transport.write(frame)
                            expected[i] += frame
                    await asyncio.sleep(0.001)
                closed, aborted = peers[0], peers[1]
                closed.conn.transport.close()
                aborted.conn.transport.abort()
                deadline = time.monotonic() + 30
                while not (ended[0] and ended[1] and all(
                        len(g) >= len(w)
                        for g, w in zip(got[2:], expected[2:]))):
                    assert time.monotonic() < deadline, "peers starved"
                    await asyncio.sleep(0.01)
            finally:
                done.set()
                drainer.join(5)
            assert not drainer.is_alive()
            assert bytes(got[0]) == expected[0]
            assert expected[1].startswith(bytes(got[1]))
            assert [bytes(g) for g in got[2:]] == expected[2:]

    interval = sys.getswitchinterval()
    worker = threading.Thread(target=spin, daemon=True)
    sys.setswitchinterval(1e-5)
    worker.start()
    try:
        run(main)
    finally:
        stop.set()
        worker.join(5)
        sys.setswitchinterval(interval)
    assert not worker.is_alive()


# ---------------------------------------------------------------------------
# (b2) close, abort, and the writer's own descriptor
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("how", ["close", "abort"])
def test_close_delivers_the_backlog_and_abort_drops_it(how):
    """``close()``: the peer reads every byte handed off, then EOF, as
    asyncio's close gives; ``abort()``: the backlog is dropped and the
    peer reads EOF at once."""

    async def main():
        async with Gateway() as gw:
            peer, = await gw.connect(1, rcvbuf=2048)
            stall(peer)
            batch = [(1, 0, 0, 100, bytes([i]) * 4_000) for i in range(60)]
            queue(peer.conn, batch)
            pump(wait=False)
            assert backlog(peer.conn) > 0
            transport = peer.conn.transport
            getattr(transport, how)()
            assert not transport.writer_key()
            got = await eof(peer)
            if how == "close":
                assert got == wire(batch)
            else:
                assert len(got) < len(wire(batch))
                assert wire(batch).startswith(got)

    run(main)


def test_a_socket_that_takes_a_closed_ones_fd_number_gets_none_of_its_bytes():
    """The writer writes to its own dup of the socket: once a connection
    closes with bytes still queued, the fd number the kernel hands out
    again belongs to another socket, which must read none of them."""

    async def main():
        async with Gateway() as gw:
            peer, = await gw.connect(1, rcvbuf=2048)
            stall(peer)
            batch = [(1, 0, 0, 100, b"old" * 2_000) for _ in range(40)]
            queue(peer.conn, batch)
            pump(wait=False)
            assert backlog(peer.conn) > 0
            old_fd = peer.conn.transport._sock.fileno()
            peer.conn.close()
            await asyncio.sleep(0.02)  # asyncio closes its descriptor
            reused = []
            for _ in range(64):
                pair = socket.socketpair()
                reused.append(pair)
                if old_fd in (pair[0].fileno(), pair[1].fileno()):
                    break
            else:
                pytest.fail(f"fd {old_fd} was not handed out again")
            fresh = pair[0] if pair[0].fileno() == old_fd else pair[1]
            other_end = pair[1] if fresh is pair[0] else pair[0]
            # The old peer reads its bytes, then EOF; the new socket
            # nothing.
            assert await eof(peer) == wire(batch)
            other_end.setblocking(False)
            with pytest.raises(BlockingIOError):
                other_end.recv(1)
            for a, b in reused:
                a.close()
                b.close()

    run(main)


# ---------------------------------------------------------------------------
# (b3) the backlog is what the gate and the backstop read
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("how", ["write", "pump-edge-off", "pump-gate-0"])
def test_the_backlog_trips_the_send_buffer_backstop(how, monkeypatch):
    """A peer that stops reading meets ``MAX_SEND_BUFFER`` through a
    transport write, and through the pump's hand-offs where the edge
    plane's transport gate is off (the plane off, or its bound 0): the
    codec refuses the hand-off that would pass the bound, and the
    connection is reaped as ``TcpTransport.write`` reaps it."""
    monkeypatch.setattr(server_mod, "MAX_SEND_BUFFER", 64 * 1024)
    if how == "pump-edge-off":
        global_settings.edge_enabled = False
    elif how == "pump-gate-0":
        global_settings.edge_transport_high_bytes = 0

    async def main():
        async with Gateway() as gw:
            peer, = await gw.connect(1, rcvbuf=2048)
            stall(peer)
            reaped = edge.ledgers.reap_counts.get("send_buffer", 0)
            transport = peer.conn.transport
            batch = [(1, 0, 0, 100, b"x" * 8_000)]  # one packet
            frame = wire(batch)
            sent = 0
            while not transport.transport.is_closing():
                if how == "write":
                    transport.write(frame)
                else:
                    queue(peer.conn, batch)
                    assert pump(wait=False)
                sent += 1
                assert sent < 100, "the backstop did not fire"
            # The socket's few KB plus the writer's backlog: the last
            # one, past 64 KB of backlog, was refused.
            assert sent * len(frame) > 64 * 1024
            assert edge.ledgers.reap_counts["send_buffer"] == reaped + 1
            # A close, not an abort: what was queued still goes out.
            assert await eof(peer) == frame * (sent - 1)

    run(main)


# ---------------------------------------------------------------------------
# (c) the fairness cap and the transport gate
# ---------------------------------------------------------------------------


def test_the_fairness_cap_carries_over_on_the_native_path():
    global_settings.edge_flush_fair_msgs = 16

    async def main():
        async with Gateway() as gw:
            hot, quiet = await gw.connect(2)
            batch = [(1, 0, 0, 100, b"%03d" % i) for i in range(40)]
            queue(hot.conn, batch)
            queue(quiet.conn, batch[:3])
            before = sent_counters()
            left = []
            while hot.conn.send_queue:
                assert pump()
                left.append(len(hot.conn.send_queue))
                # The carry-over: requeued without a new send.
                assert (hot.conn in connection_mod._pending_flush) == \
                    bool(hot.conn.send_queue)
            assert left == [24, 8, 0]
            assert hot.conn.envelope.queue_bytes == 0
            assert delta(before)["native"] == 43
            # Three packets, one a pass, and nothing lost or reordered.
            expected = (wire(batch[:16]) + wire(batch[16:32])
                        + wire(batch[32:]))
            assert await hot.read(len(expected)) == expected
            assert await quiet.read(len(wire(batch[:3]))) == wire(batch[:3])

    run(main)


def test_the_transport_gate_holds_a_peer_that_stopped_draining():
    global_settings.edge_transport_high_bytes = 1024

    async def main():
        async with Gateway() as gw:
            slow, = await gw.connect(1, rcvbuf=2048)
            stall(slow)
            first = [(1, 0, 0, 100, bytes([i]) * 4_000) for i in range(100)]
            queue(slow.conn, first)
            assert pump(wait=False)  # the socket takes a few KB
            assert backlog(slow.conn) > 1024  # the writer holds the rest
            held = [(2, 0, 0, 101, b"held-%d" % i) for i in range(5)]
            queue(slow.conn, held)
            owed = slow.conn.envelope.queue_bytes
            assert owed > 0
            mark = sent_counters()
            for _ in range(3):
                # Found output queued, handed none of it off.
                assert pump(wait=False)
                assert len(slow.conn.send_queue) == 5
                assert slow.conn.envelope.queue_bytes == owed
                assert slow.conn in connection_mod._pending_flush
            moved = delta(mark)
            moved.pop("partial")  # the writer's sends into the full socket
            assert not any(moved.values())
            # The peer reads again: the backlog drains and the gate opens.
            assert await slow.read(len(wire(first))) == wire(first)
            while slow.conn.send_queue:
                pump(wait=False)
                await asyncio.sleep(0.001)
            assert await slow.read(len(wire(held))) == wire(held)

    run(main)


# ---------------------------------------------------------------------------
# (d) a fault on one descriptor
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fault", ["reset", "encode"])
def test_a_fault_stays_with_its_connection(fault, monkeypatch):
    """A send that fails on the writer drops that connection's queue
    and reaches ``transport.fail`` on the pump's next pass; an encode
    that fails drops that connection's batch. No other connection sees
    either."""
    failed = []
    real_fail = TcpTransport.fail

    def fail(self, error):
        failed.append((self, error.errno))
        real_fail(self, error)

    monkeypatch.setattr(TcpTransport, "fail", fail)

    async def main():
        async with Gateway() as gw:
            conns = await gw.connect(5)
            bad = conns[2]
            batch = [(1, 0, 0, 100, b"payload-%d" % i) for i in range(6)]
            for peer in conns:
                queue(peer.conn, batch)
            if fault == "reset":
                bad.sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                    struct.pack("ii", 1, 0))
                bad.sock.close()  # an RST, before the loop can read it
                time.sleep(0.02)
            else:
                bad.conn.send_queue[3] = (1, 0, 0, "not a number", b"")
            assert pump()
            if fault == "reset":
                # The send failed on the writer; the next pass hears of
                # it (before the loop reads the reset itself).
                assert not failed
                pump()
                assert failed == [(bad.conn.transport, failed[0][1])]
                assert failed[0][1] in (errno.ECONNRESET, errno.EPIPE)
            for peer in conns:
                if peer is not bad:
                    assert await peer.read(len(wire(batch))) == wire(batch)
                    assert not peer.conn.is_closing()
            await asyncio.sleep(0.02)
            if fault == "reset":
                assert bad.conn.is_closing()  # as a failed write closes it
            else:
                # The batch is dropped whole, as flush drops it; the
                # connection lives and its next batch goes out.
                assert not failed and not bad.conn.is_closing()
                assert await bad.nothing_more()
                queue(bad.conn, batch)
                pump()
                assert await bad.read(len(wire(batch))) == wire(batch)

    run(main)


# ---------------------------------------------------------------------------
# (e) what does not go through the native call
# ---------------------------------------------------------------------------


def flushed_by(monkeypatch) -> list:
    """Spy: the connections ``Connection.flush`` was called for."""
    calls = []
    real_flush = Connection.flush

    def flush(self, fair=False):
        calls.append(self)
        real_flush(self, fair)

    monkeypatch.setattr(Connection, "flush", flush)
    return calls


def test_other_transports_in_the_same_pass_take_flush(monkeypatch):
    websockets = pytest.importorskip("websockets")
    from channeld_tpu.core.kcp import KcpClient

    async def main():
        loop = asyncio.get_running_loop()
        async with Gateway() as gw:
            tcp, = await gw.connect(1)
            fake = add_connection(FakeTransport(), ConnectionType.CLIENT)
            ws_server = await start_listening(
                ConnectionType.CLIENT, "ws", "127.0.0.1:0")
            ws_port = ws_server.sockets[0].getsockname()[1]
            kcp_server = await start_listening(
                ConnectionType.CLIENT, "kcp", "127.0.0.1:0")
            kcp_port = kcp_server.transport.get_extra_info("sockname")[1]
            known = set(connection_mod.all_connections().values())
            ws = await websockets.connect(f"ws://127.0.0.1:{ws_port}")
            kcp = KcpClient("127.0.0.1", kcp_port)
            kcp.send(b"C")  # opens the conversation; no whole frame yet
            while len(connection_mod.all_connections()) < len(known) + 2:
                await asyncio.sleep(0.005)
            others = {type(c.transport).__name__: c
                      for c in connection_mod.all_connections().values()
                      if c not in known}
            assert sorted(others) == ["KcpTransport", "WebSocketTransport"]

            batch = [(3, 0, 0, 100, b"same bytes by every road")] * 4
            everyone = [tcp.conn, fake, *others.values()]
            for conn in everyone:
                queue(conn, batch)
            flushed = flushed_by(monkeypatch)
            before = sent_counters()
            assert pump()
            assert sorted(c.id for c in flushed) == sorted(
                c.id for c in everyone if c is not tcp.conn)
            moved = delta(before)
            assert (moved["native"], moved["python"]) == (4, 12)
            expected = wire(batch)
            assert await tcp.read(len(expected)) == expected
            assert b"".join(fake.transport.written) == expected
            assert await asyncio.wait_for(ws.recv(), 5) == expected
            got = b""
            while len(got) < len(expected):
                got += await loop.run_in_executor(None, kcp.recv, 0.2)
            assert got == expected
            await ws.close()
            kcp.close()
            ws_server.close()
            kcp_server.close()

    run(main)


def test_without_the_codec_tcp_takes_flush_and_no_writer_counter_shows(
        monkeypatch):
    """A build without the native codec: the TCP peers' transports
    write through asyncio, the pump flushes each connection, and
    ``/metrics`` has none of the writer's counters."""
    monkeypatch.setattr(connection_mod, "_native_codec", None)
    monkeypatch.setattr(native_pkg, "codec", None)
    flushed = flushed_by(monkeypatch)

    async def main():
        async with Gateway() as gw:
            conns = await gw.connect(3)
            assert not any(p.conn.transport.writer_key() for p in conns)
            batch = random_batch(random.Random(6), 20, 30_000)
            for peer in conns:
                queue(peer.conn, batch)
            before = sent_counters()
            assert pump()
            assert sorted(c.id for c in flushed) == sorted(
                p.conn.id for p in conns)
            moved = delta(before)
            assert (moved["native"], moved["python"]) == (0, 60)
            for peer in conns:
                frames = peer.conn._encode_packets_py(batch, 0)[0]
                want = b"".join(frames)
                assert await peer.read(len(want)) == want

    run(main)
    scraped = generate_latest(metrics.registry).decode()
    assert "send_writer_" not in scraped
    assert "send_pump_write_seconds" not in scraped


def test_a_codec_without_send_packets_sends_the_old_way(monkeypatch):
    without_send_packets(monkeypatch)
    flushed = flushed_by(monkeypatch)

    async def main():
        async with Gateway() as gw:
            conns = await gw.connect(3)
            batch = random_batch(random.Random(5), 20, 30_000)
            for peer in conns:
                queue(peer.conn, batch)
            before = sent_counters()
            assert pump()
            assert sorted(c.id for c in flushed) == sorted(
                p.conn.id for p in conns)
            moved = delta(before)
            assert (moved["native"], moved["python"]) == (0, 60)
            for peer in conns:
                assert await peer.read(len(wire(batch))) == wire(batch)

    run(main)


# ---------------------------------------------------------------------------
# (f) the counters, either way
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("snappy", [False, True], ids=["plain", "snappy"])
def test_the_counters_read_the_same_totals_either_way(snappy, monkeypatch):
    if snappy and not snappy_codec.available():
        pytest.skip("no snappy")
    ct = CompressionType.SNAPPY if snappy else CompressionType.NO_COMPRESSION

    def totals() -> dict:
        rng = random.Random(11)
        moved = {}

        async def main():
            async with Gateway() as gw:
                conns = await gw.connect(6)
                before = sent_counters()
                for _ in range(3):
                    for peer in conns:
                        peer.conn.compression_type = ct
                        queue(peer.conn,
                              random_batch(rng, rng.randrange(1, 30), 40_000))
                    pump()
                moved.update(delta(before))
                for peer in conns:  # drain what the run wrote
                    while not await peer.nothing_more(0.02):
                        pass

        run(main)
        return moved

    native = totals()
    without_send_packets(monkeypatch)
    fresh_runtime()
    python = totals()
    assert native.pop("python") == 0 and python.pop("native") == 0
    assert native.pop("native") == python.pop("python") > 0
    assert native == python
    assert native["messages_out"] > native["packets_out"] > 18
    assert native["packets_comb"] > 0 and native["bytes_out"] > 0


def test_a_native_pass_adds_the_counters_once_for_each_connection_type(
        monkeypatch):
    """Counted over this test's own connections and pass alone: a pass
    over whatever else the process left pending would add its own."""
    accounted = []
    real = Connection.account_sent
    own: set = set()

    def account_sent(self, packets, nbytes, combined, msgs, native=False):
        if self in own:
            accounted.append((self.connection_type, packets, msgs, native))
        real(self, packets, nbytes, combined, msgs, native)

    monkeypatch.setattr(Connection, "account_sent", account_sent)

    async def main():
        async with Gateway() as gw:
            clients = await gw.connect(20)
            servers = await start_listening(
                ConnectionType.SERVER, "tcp", "127.0.0.1:0")
            gw.port = servers.sockets[0].getsockname()[1]
            backends = await gw.connect(3)
            own.update(peer.conn for peer in clients + backends)
            batch = [(1, 0, 0, 100, b"m")] * 2
            for peer in clients + backends:
                queue(peer.conn, batch)
            # The sums are what the pass adds: one account_sent for each
            # type, through whichever of its connections came first.
            accounted.clear()
            assert pump(own)
            assert sorted(accounted) == [
                (ConnectionType.SERVER, 3, 6, True),
                (ConnectionType.CLIENT, 20, 40, True)]
            for peer in clients + backends:
                assert await peer.read(len(wire(batch))) == wire(batch)
            servers.close()

    run(main)


# ---------------------------------------------------------------------------
# (g) the ``send_pump`` stage
# ---------------------------------------------------------------------------


def test_the_stage_is_observed_once_a_pass_that_sent():
    before = stage_count("send_pump")

    async def main():
        async with Gateway() as gw:
            conns = await gw.connect(3)
            task = asyncio.ensure_future(flush_loop())
            try:
                await asyncio.sleep(0.02)  # empty passes
                assert stage_count("send_pump") == before
                batch = [(1, 0, 0, 100, b"tick")]
                for peer in conns:  # no await between: one pass sees all
                    queue(peer.conn, batch)
                for peer in conns:
                    assert await peer.read(len(wire(batch))) == wire(batch)
                await asyncio.sleep(0.02)  # more empty passes
                assert stage_count("send_pump") == before + 1
            finally:
                task.cancel()
                await asyncio.gather(task, return_exceptions=True)

    run(main)
