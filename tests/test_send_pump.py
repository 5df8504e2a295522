"""The send pump's native pass (``core/server.py _pump_sends``,
``native/codec.cc send_packets``) over real loopback sockets.

A pass of the pump that found output queued makes ONE call into the
native codec, which encodes and writes the packets of every TCP peer
with nothing buffered; everything else goes through
``Connection.flush``. Held here: the peers read byte for byte what
``encode_packets`` + ``transport.write`` give for the same queues; a
socket that takes less leaves the remainder to the transport, in
order; the fairness cap and the transport gate hold; a fault on one
descriptor touches no other connection; the other transports take the
old path; the counters and the ``send_pump`` stage read the same either
way.
"""

import asyncio
import errno
import random
import socket
import struct
import sys
import threading
import time

import pytest

from channeld_tpu.core import connection as connection_mod
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
        by_addr: dict = {}
        deadline = time.monotonic() + 5
        while len(by_addr) < len(self.peers) + n:
            assert time.monotonic() < deadline, "the listener did not accept"
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


def pump() -> int:
    """One pass of the send pump over what is pending."""
    return server_mod._pump_sends(connection_mod.drain_pending_flush())


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
    """A tree whose library is older than its source."""
    monkeypatch.delattr(codec, "send_packets")


def stall(peer: Peer, sndbuf: int = 4096) -> None:
    """Shrink the gateway side's send buffer for a peer that will not
    read (its receive buffer was cut when it connected)."""
    peer.conn.transport._sock.setsockopt(
        socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf)


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
            # Every peer's buffer was empty, so the native call took
            # all of them (one that left a remainder is counted too).
            assert moved["native"] == written

    run(main)


def test_a_busy_second_thread_changes_nothing_of_the_bytes():
    """A pass of four packets or more gives the interpreter lock up once
    round its write loop; a thread that takes every turn it can get (the
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
    rng = random.Random(passes)

    async def main():
        async with Gateway() as gw:
            slow, = await gw.connect(1, rcvbuf=2048)
            other, = await gw.connect(1)
            stall(slow)
            before = sent_counters()
            expected = b""
            paths = []
            for i in range(passes):
                # ~0.5 MB a pass: far over the socket's few KB, under
                # the transport gate's 1 MB in all.
                batch = [(i, 0, 0, 100 + j, rng.randbytes(4_000))
                         for j in range(120 // passes)]
                queue(slow.conn, batch)
                queue(other.conn, [(i, 0, 0, 7, b"fine")])
                expected += wire(batch)
                mark = sent_counters()
                assert pump()
                step = delta(mark)
                paths.append("native" if step["native"] > 1 else "python")
                assert slow.conn.transport.get_write_buffer_size() > 0
                assert slow.conn.transport.direct_fd() == -1
                assert not slow.conn.send_queue  # all handed over
            # The first pass met an empty buffer and left a remainder;
            # every later one found bytes buffered and went the old way.
            assert paths == ["native"] + ["python"] * (passes - 1)
            moved = delta(before)
            assert moved["partial"] == 1
            assert moved["native"] == 120 // passes + passes
            assert moved["python"] == (120 // passes) * (passes - 1)
            assert await slow.read(len(expected)) == expected
            assert await slow.nothing_more()
            fine = wire([(0, 0, 0, 7, b"fine")])
            assert len(await other.read(len(fine) * passes)) == \
                len(fine) * passes
            # Drained: the next pass writes to the socket again.
            assert slow.conn.transport.get_write_buffer_size() == 0
            last = [(9, 0, 0, 9, b"after")]
            queue(slow.conn, last)
            mark = sent_counters()
            pump()
            assert delta(mark)["native"] == 1
            assert await slow.read(len(wire(last))) == wire(last)

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
            assert pump()  # native: the socket takes a few KB
            assert slow.conn.transport.get_write_buffer_size() > 1024
            held = [(2, 0, 0, 101, b"held-%d" % i) for i in range(5)]
            queue(slow.conn, held)
            owed = slow.conn.envelope.queue_bytes
            assert owed > 0
            mark = sent_counters()
            for _ in range(3):
                assert pump()  # found output queued, sent none of it
                assert len(slow.conn.send_queue) == 5
                assert slow.conn.envelope.queue_bytes == owed
                assert slow.conn in connection_mod._pending_flush
            assert not any(delta(mark).values())
            # The peer reads again: the buffer drains and the gate opens.
            assert await slow.read(len(wire(first))) == wire(first)
            while slow.conn.send_queue:
                pump()
                await asyncio.sleep(0.001)
            assert await slow.read(len(wire(held))) == wire(held)

    run(main)


# ---------------------------------------------------------------------------
# (d) a fault on one descriptor
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fault", ["reset", "encode"])
def test_a_fault_stays_with_its_connection(fault, monkeypatch):
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
            for peer in conns:
                if peer is not bad:
                    assert await peer.read(len(wire(batch))) == wire(batch)
                    assert not peer.conn.is_closing()
            await asyncio.sleep(0.02)
            if fault == "reset":
                assert failed == [(bad.conn.transport, failed[0][1])]
                assert failed[0][1] in (errno.ECONNRESET, errno.EPIPE)
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
    accounted = []
    real = Connection.account_sent

    def account_sent(self, packets, nbytes, combined, msgs, native=False):
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
            batch = [(1, 0, 0, 100, b"m")] * 2
            for peer in clients + backends:
                queue(peer.conn, batch)
            assert pump()
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
