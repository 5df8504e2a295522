"""``wire.Peers``: one process's peers on one selector, with fd numbers
past the 1,024 that ``select`` takes, against a local echo listener and
no gateway."""

import resource
import selectors
import socket
import threading

import pytest

from benchmark.harness import wire


def _echo(listener: socket.socket, stop: threading.Event) -> None:
    sel = selectors.DefaultSelector()
    sel.register(listener, selectors.EVENT_READ)
    try:
        while not stop.is_set():
            for key, _ in sel.select(0.05):
                sock = key.fileobj
                if sock is listener:
                    conn, _ = listener.accept()
                    sel.register(conn, selectors.EVENT_READ)
                    continue
                data = sock.recv(1 << 16)
                if data:
                    sock.sendall(data)
                else:
                    sel.unregister(sock)
                    sock.close()
    finally:
        for key in list(sel.get_map().values()):
            if key.fileobj is not listener:
                key.fileobj.close()
        sel.close()


@pytest.mark.parametrize("peers_n,messages,size", [
    (2500, 1, 16),     # fd numbers past 1,024
    (1, 80, 60_000),   # more bytes than the socket takes at once: the
])                     # peer is watched for writing until they are out
def test_one_process_pumps_every_peers_bytes(peers_n, messages, size):
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if hard != resource.RLIM_INFINITY and hard < 2 * peers_n + 256:
        pytest.skip(f"the hard limit of open files is {hard}")
    resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    listener = socket.create_server(("127.0.0.1", 0), backlog=4096)
    stop = threading.Event()
    echo = threading.Thread(target=_echo, args=(listener, stop), daemon=True)
    echo.start()
    peers = wire.Peers()
    want = {f"peer-{i}": [(k, wire.DATA_UPDATE, f"{i}:{k}:".encode().ljust(
        size, b"x")) for k in range(messages)] for i in range(peers_n)}
    got: dict = {name: [] for name in want}
    try:
        port = listener.getsockname()[1]
        for name, sent in want.items():
            p = wire.Peer(port, name)
            peers.add(p)
            for mp in sent:
                p.queue(*mp)
        assert len(peers) == peers_n
        if peers_n > 1024:
            assert max(p.fileno() for p in peers) > 1024

        def on_message(peer, t, mp) -> None:
            got[peer.name].append((mp.channelId, mp.msgType, mp.msgBody))

        assert peers.pump(60.0, until=lambda: sum(map(len, got.values()))
                          == peers_n * messages, on_message=on_message)
    finally:
        peers.close()
        stop.set()
        echo.join(10)
        listener.close()
        resource.setrlimit(resource.RLIMIT_NOFILE, (soft, hard))
    assert not echo.is_alive()
    assert got == want
