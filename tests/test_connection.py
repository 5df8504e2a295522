"""Connection layer: dispatch, auth flow, FSM gating, flush batching.

(ref: pkg/channeld/connection_test.go, message_test.go, ddos_test.go —
in-process transports instead of real sockets.)
"""

import pytest

from channeld_tpu.core import connection as connection_mod
from channeld_tpu.core.channel import get_channel, get_global_channel
from channeld_tpu.core.connection import add_connection
from channeld_tpu.core.fsm import MessageFsm
from channeld_tpu.core.settings import global_settings
from channeld_tpu.core.types import (
    ChannelType,
    ConnectionState,
    ConnectionType,
    MessageType,
)
from channeld_tpu.protocol import FrameDecoder, control_pb2, encode_packet, wire_pb2
from channeld_tpu.utils.anyutil import pack_any

from helpers import FakeTransport, fresh_runtime

AUTH_FSM = {
    "States": [
        {"Name": "INIT", "MsgTypeWhitelist": "1", "MsgTypeBlacklist": ""},
        {"Name": "OPEN", "MsgTypeWhitelist": "2-65535", "MsgTypeBlacklist": ""},
    ],
    "Transitions": [],
}


@pytest.fixture(autouse=True)
def runtime():
    gch = fresh_runtime()
    global_settings.development = True
    connection_mod.set_fsm_templates(
        MessageFsm.from_dict(AUTH_FSM), MessageFsm.from_dict(AUTH_FSM)
    )
    yield gch


def wire(msg_type: int, msg, channel_id: int = 0, stub_id: int = 0) -> bytes:
    p = wire_pb2.Packet(
        messages=[
            wire_pb2.MessagePack(
                channelId=channel_id,
                stubId=stub_id,
                msgType=msg_type,
                msgBody=msg.SerializeToString(),
            )
        ]
    )
    return encode_packet(p)


def sent_messages(transport: FakeTransport) -> list:
    """Decode everything the server flushed to this transport."""
    dec = FrameDecoder()
    out = []
    for chunk in transport.written:
        for packet in dec.decode_packets(chunk):
            out.extend(packet.messages)
    return out


def auth_client(name="alice"):
    t = FakeTransport()
    conn = add_connection(t, ConnectionType.CLIENT)
    conn.on_bytes(
        wire(MessageType.AUTH, control_pb2.AuthMessage(playerIdentifierToken=name))
    )
    get_global_channel().tick_once(0)
    conn.flush()
    return conn, t


def test_auth_flow_end_to_end():
    conn, t = auth_client()
    msgs = sent_messages(t)
    assert len(msgs) == 1
    assert msgs[0].msgType == MessageType.AUTH
    result = control_pb2.AuthResultMessage()
    result.ParseFromString(msgs[0].msgBody)
    assert result.result == control_pb2.AuthResultMessage.SUCCESSFUL
    assert result.connId == conn.id
    assert conn.state == ConnectionState.AUTHENTICATED
    assert conn.fsm.current.name == "OPEN"


def test_fsm_blocks_preauth_messages():
    t = FakeTransport()
    conn = add_connection(t, ConnectionType.CLIENT)
    # Data update before auth: FSM must reject it.
    conn.on_bytes(
        wire(
            MessageType.CHANNEL_DATA_UPDATE,
            control_pb2.ChannelDataUpdateMessage(),
        )
    )
    get_global_channel().tick_once(0)
    conn.flush()
    assert sent_messages(t) == []


def test_create_channel_and_update_roundtrip():
    from channeld_tpu.models import testdata_pb2

    conn, t = auth_client()
    t.written.clear()
    conn.on_bytes(
        wire(
            MessageType.CREATE_CHANNEL,
            control_pb2.CreateChannelMessage(
                channelType=ChannelType.SUBWORLD,
                metadata="room1",
                data=pack_any(testdata_pb2.TestChannelDataMessage(text="hello")),
            ),
            stub_id=7,
        )
    )
    get_global_channel().tick_once(0)
    conn.flush()
    msgs = sent_messages(t)
    types = [m.msgType for m in msgs]
    assert MessageType.CREATE_CHANNEL in types
    assert MessageType.SUB_TO_CHANNEL in types
    created = control_pb2.CreateChannelResultMessage()
    created.ParseFromString(
        [m for m in msgs if m.msgType == MessageType.CREATE_CHANNEL][0].msgBody
    )
    assert created.channelId == 1
    ch = get_channel(created.channelId)
    assert ch is not None and ch.metadata == "room1"
    assert ch.get_owner() is conn
    assert ch.get_data_message().text == "hello"

    # Owner sends an update; next owner-due tick fans it back out only after
    # data changes — first fan-out (full state) happens on the channel tick.
    t.written.clear()
    conn.on_bytes(
        wire(
            MessageType.CHANNEL_DATA_UPDATE,
            control_pb2.ChannelDataUpdateMessage(
                data=pack_any(testdata_pb2.TestChannelDataMessage(text="world"))
            ),
            channel_id=ch.id,
        )
    )
    ch.tick_once(ch.get_time())
    assert ch.get_data_message().text == "world"


def test_list_channel_with_filters():
    conn, t = auth_client()
    for meta in ("alpha", "beta"):
        conn.on_bytes(
            wire(
                MessageType.CREATE_CHANNEL,
                control_pb2.CreateChannelMessage(
                    channelType=ChannelType.SUBWORLD, metadata=meta
                ),
            )
        )
    get_global_channel().tick_once(0)
    t.written.clear()
    conn.on_bytes(
        wire(
            MessageType.LIST_CHANNEL,
            control_pb2.ListChannelMessage(metadataFilters=["alp"]),
        )
    )
    get_global_channel().tick_once(0)
    conn.flush()
    msgs = [
        m for m in sent_messages(t) if m.msgType == MessageType.LIST_CHANNEL
    ]
    assert len(msgs) == 1
    result = control_pb2.ListChannelResultMessage()
    result.ParseFromString(msgs[0].msgBody)
    assert [c.metadata for c in result.channels] == ["alpha"]


def test_flush_batches_multiple_messages_into_one_packet():
    conn, t = auth_client()
    t.written.clear()
    from channeld_tpu.core.message import MessageContext

    for i in range(5):
        conn.send(
            MessageContext(
                msg_type=MessageType.LIST_CHANNEL,
                msg=control_pb2.ListChannelResultMessage(),
                channel_id=0,
            )
        )
    conn.flush()
    assert len(t.written) == 1  # one frame
    assert len(sent_messages(t)) == 5


def test_oversize_carryover():
    conn, t = auth_client()
    t.written.clear()
    from channeld_tpu.core.message import MessageContext
    from channeld_tpu.models import testdata_pb2

    big = testdata_pb2.TestChannelDataMessage(text="x" * 30000)
    for _ in range(4):
        conn.send(
            MessageContext(
                msg_type=MessageType.CHANNEL_DATA_UPDATE,
                msg=control_pb2.ChannelDataUpdateMessage(data=pack_any(big)),
            )
        )
    conn.flush()
    conn.flush()
    assert len(t.written) == 2  # two frames, each under the 64KB cap
    assert len(sent_messages(t)) == 4


def test_garbage_bytes_close_connection():
    t = FakeTransport()
    conn = add_connection(t, ConnectionType.CLIENT)
    conn.on_bytes(b"GET / HTTP/1.1\r\nHost: x\r\n\r\n")
    assert conn.is_closing()
    assert t.closed


def test_unauth_timeout_blacklists_ip():
    """(ref: ddos_test.go TestUnauthTimeout)."""
    from channeld_tpu.core import ddos

    global_settings.connection_auth_timeout_ms = 0  # disabled: no reap
    t = FakeTransport()
    conn = add_connection(t, ConnectionType.CLIENT)
    ddos.check_unauth_conns_once()
    assert not conn.is_closing()

    global_settings.connection_auth_timeout_ms = 1
    ddos.track_unauthenticated(conn)
    conn.conn_time -= 10  # pretend it connected 10s ago
    ddos.check_unauth_conns_once()
    assert conn.is_closing()
    assert ddos.is_ip_banned("127.0.0.1")


def test_failed_auth_blacklists_pit():
    """(ref: ddos_test.go TestWrongPassword)."""
    from channeld_tpu.core import ddos
    from channeld_tpu.core.auth import FixedPasswordAuthProvider, set_auth_provider

    set_auth_provider(FixedPasswordAuthProvider("secret"))
    global_settings.max_failed_auth_attempts = 2
    try:
        for i in range(2):
            t = FakeTransport()
            conn = add_connection(t, ConnectionType.CLIENT)
            conn.on_bytes(
                wire(
                    MessageType.AUTH,
                    control_pb2.AuthMessage(
                        playerIdentifierToken="mallory", loginToken="wrong"
                    ),
                )
            )
            get_global_channel().tick_once(0)
        assert ddos.is_pit_banned("mallory")
        # A banned PIT is refused at the auth handler.
        t = FakeTransport()
        conn = add_connection(t, ConnectionType.CLIENT)
        conn.on_bytes(
            wire(
                MessageType.AUTH,
                control_pb2.AuthMessage(
                    playerIdentifierToken="mallory", loginToken="secret"
                ),
            )
        )
        get_global_channel().tick_once(0)
        assert conn.is_closing()
    finally:
        set_auth_provider(None)


def test_handler_exception_does_not_kill_channel():
    """One bad message must not stop the channel (code-review regression)."""
    conn, t = auth_client()
    gch = get_global_channel()
    # SPATIAL creation currently routes to the spatial module; even if a
    # handler raises, the channel must keep processing subsequent messages.
    conn.on_bytes(
        wire(
            MessageType.CREATE_CHANNEL,
            control_pb2.CreateChannelMessage(channelType=ChannelType.SPATIAL),
        )
    )
    conn.on_bytes(
        wire(
            MessageType.LIST_CHANNEL,
            control_pb2.ListChannelMessage(),
        )
    )
    gch.tick_once(0)
    conn.flush()
    types = [m.msgType for m in sent_messages(t) if m.msgType == MessageType.LIST_CHANNEL]
    assert types == [MessageType.LIST_CHANNEL]


def test_banned_ip_refused_at_accept():
    from channeld_tpu.core import ddos

    ddos._ip_blacklist["127.0.0.1"] = 0.0
    t = FakeTransport()
    with pytest.raises(ConnectionRefusedError):
        add_connection(t, ConnectionType.CLIENT)
    assert t.closed


def test_full_queue_stashes_instead_of_dropping():
    """A full channel in-queue must apply lossless backpressure: the
    overflowing message is stashed on the connection (receive_message ->
    None), reads pause via the congestion set, and flush_pending
    re-dispatches everything in order once the tick drains the queue —
    the asyncio analog of the reference's blocking inMsgQueue send
    (channel.go:295-310). Before this contract, a 40K mps overload
    dropped >1M messages (round 3's own run).

    Pinned to the per-message (protobuf) path: the batched native ingest
    coalesces user-space reads into one queue item, so filling the queue
    one message at a time requires the native codec off (the batch-path
    stash contract has its own test below)."""
    from channeld_tpu.core import channel as channel_mod
    from channeld_tpu.core.channel import get_global_channel

    transport = FakeTransport()
    conn = connection_mod.add_connection(transport, ConnectionType.CLIENT)
    conn.on_bytes(wire(MessageType.AUTH, control_pb2.AuthMessage(
        playerIdentifierToken="pit-bp", loginToken="lt")))
    gch = get_global_channel()
    gch.tick_once()

    native = connection_mod._native_codec
    connection_mod._native_codec = None
    try:
        _fill_queue_then_assert_stash(conn, gch, channel_mod)
    finally:
        connection_mod._native_codec = native


def _fill_queue_then_assert_stash(conn, gch, channel_mod):
    # Fill the queue to the external cap with user-space forwards.
    frame = wire(100, control_pb2.AuthMessage())  # opaque body
    baseline = gch.in_msg_queue.qsize()
    for _ in range(channel_mod.QUEUE_CAPACITY - baseline):
        conn.on_bytes(frame)
    assert not conn.has_pending()
    assert gch.in_msg_queue.qsize() == channel_mod.QUEUE_CAPACITY

    # The next messages stash, never drop, and the conn reads congested.
    for _ in range(3):
        conn.on_bytes(frame)
    assert conn.has_pending()
    assert len(conn._pending_msgs) == 3
    assert channel_mod.connection_congested(conn)
    assert gch.in_msg_queue.qsize() == channel_mod.QUEUE_CAPACITY

    # Internal control puts still fit (the reserve above the cap).
    gch.execute(lambda ch: None)
    assert gch.in_msg_queue.qsize() == channel_mod.QUEUE_CAPACITY + 1

    # Drain the ticks (a slow box may hit the tick budget and defer a
    # tail to the next tick); flush_pending re-dispatches the stash in
    # order once the queue is empty.
    for _ in range(100):
        if gch.in_msg_queue.qsize() == 0:
            break
        gch.tick_once()
    assert gch.in_msg_queue.qsize() == 0
    assert conn.flush_pending()
    assert not conn.has_pending()
    assert gch.in_msg_queue.qsize() == 3


def test_fsm_transition_deferred_until_enqueue_succeeds():
    """A msg-type-triggered FSM transition must not fire on a queue-full
    attempt: the stash/retry contract re-enters receive_message with the
    same pack, and a transition applied on the failed attempt would make
    the retry disallowed by the state its own first attempt advanced
    (advisor r3, medium)."""
    from channeld_tpu.core import channel as channel_mod
    from channeld_tpu.core.channel import get_global_channel

    transport = FakeTransport()
    conn = connection_mod.add_connection(transport, ConnectionType.CLIENT)
    conn.on_bytes(wire(MessageType.AUTH, control_pb2.AuthMessage(
        playerIdentifierToken="pit-fsm", loginToken="lt")))
    gch = get_global_channel()
    gch.tick_once()

    # Type 100 transitions OPEN -> LOCKED, and LOCKED disallows 100: a
    # premature transition makes the retried message drop itself.
    conn.fsm = MessageFsm.from_dict({
        "States": [
            {"Name": "OPEN", "MsgTypeWhitelist": "2-65535",
             "MsgTypeBlacklist": ""},
            {"Name": "LOCKED", "MsgTypeWhitelist": "2-99",
             "MsgTypeBlacklist": ""},
        ],
        "Transitions": [
            {"FromState": "OPEN", "ToState": "LOCKED", "MsgType": 100},
        ],
    })

    filler = wire(101, control_pb2.AuthMessage())
    baseline = gch.in_msg_queue.qsize()
    for _ in range(channel_mod.QUEUE_CAPACITY - baseline):
        conn.on_bytes(filler)
    assert gch.in_msg_queue.qsize() == channel_mod.QUEUE_CAPACITY

    conn.on_bytes(wire(100, control_pb2.AuthMessage()))
    assert conn.has_pending()
    assert conn.fsm.current.name == "OPEN"  # NOT advanced on the failure

    # Drain the ticks (a slow box may hit the tick budget and defer a
    # tail to the next tick) before the stash retries.
    for _ in range(100):
        if gch.in_msg_queue.qsize() == 0:
            break
        gch.tick_once()
    assert conn.flush_pending()
    assert gch.in_msg_queue.qsize() == 1  # the retried message enqueued
    assert conn.fsm.current.name == "LOCKED"  # transition fired exactly once


def test_packet_dropped_counted_once_per_packet_across_stash_flush():
    """packet_dropped is a packet-level counter (reference parity): a
    packet that drops a message in on_bytes and drops another when its
    stashed tail flushes must increment the counter exactly once
    (advisor r3, low)."""
    from channeld_tpu.core import channel as channel_mod
    from channeld_tpu.core.channel import get_global_channel

    transport = FakeTransport()
    conn = connection_mod.add_connection(transport, ConnectionType.CLIENT)
    conn.on_bytes(wire(MessageType.AUTH, control_pb2.AuthMessage(
        playerIdentifierToken="pit-drop", loginToken="lt")))
    gch = get_global_channel()
    gch.tick_once()

    native = connection_mod._native_codec
    connection_mod._native_codec = None  # per-message fill (see stash test)
    try:
        filler = wire(101, control_pb2.AuthMessage())
        baseline = gch.in_msg_queue.qsize()
        for _ in range(channel_mod.QUEUE_CAPACITY - baseline):
            conn.on_bytes(filler)

        # One packet, three messages: [drop (unknown channel), enqueue-full
        # (stash), drop (unknown channel)]. The first drop counts; the tail
        # stashes; the flush-time drop must NOT count again.
        body = control_pb2.AuthMessage().SerializeToString()
        p = wire_pb2.Packet(messages=[
            wire_pb2.MessagePack(channelId=999, msgType=101, msgBody=body),
            wire_pb2.MessagePack(channelId=0, msgType=101, msgBody=body),
            wire_pb2.MessagePack(channelId=999, msgType=101, msgBody=body),
        ])
        before = conn._m_packet_dropped._value.get()
        conn.on_bytes(encode_packet(p))
        assert conn.has_pending()
        assert conn._m_packet_dropped._value.get() == before + 1

        gch.tick_once()
        assert conn.flush_pending()
        assert not conn.has_pending()
        assert conn._m_packet_dropped._value.get() == before + 1
    finally:
        connection_mod._native_codec = native


def _owner_with_global():
    """Server connection that owns GLOBAL (forward target)."""
    t = FakeTransport()
    owner = add_connection(t, ConnectionType.SERVER)
    owner.on_bytes(
        wire(MessageType.AUTH, control_pb2.AuthMessage(playerIdentifierToken="own"))
    )
    gch = get_global_channel()
    gch.tick_once(0)
    gch.set_owner(owner)
    return owner, t


def _forward_wire(payloads, msg_type=100):
    p = wire_pb2.Packet(
        messages=[
            wire_pb2.MessagePack(channelId=0, msgType=msg_type, msgBody=b)
            for b in payloads
        ]
    )
    return encode_packet(p)


def test_fast_forward_path_matches_protobuf_path():
    """The batched native ingest must produce byte-identical owner
    traffic to the per-message protobuf path (same ServerForwardMessage
    wrapping, same order), including interleaved system messages."""
    owner, ot = _owner_with_global()
    conn, _ = auth_client()
    ot.written.clear()

    payloads = [b"alpha", b"", b"g" * 500]
    conn.on_bytes(_forward_wire(payloads))
    # Interleave: forward, system (sub), forward — order must hold.
    conn.on_bytes(_forward_wire([b"tail1", b"tail2"], msg_type=101))
    gch = get_global_channel()
    gch.tick_once(0)
    owner.flush()

    fast_msgs = sent_messages(ot)
    fwd = [m for m in fast_msgs if m.msgType >= 100]
    assert [m.msgType for m in fwd] == [100, 100, 100, 101, 101]
    for m, body in zip(fwd, payloads + [b"tail1", b"tail2"]):
        sfm = wire_pb2.ServerForwardMessage()
        sfm.ParseFromString(m.msgBody)
        assert sfm.clientConnId == conn.id
        assert sfm.payload == body

    # Same traffic with the native codec disabled -> identical bytes.
    ot.written.clear()
    native = connection_mod._native_codec
    connection_mod._native_codec = None
    try:
        conn.on_bytes(_forward_wire(payloads))
        conn.on_bytes(_forward_wire([b"tail1", b"tail2"], msg_type=101))
        gch.tick_once(0)
        owner.flush()
    finally:
        connection_mod._native_codec = native
    slow_fwd = [m for m in sent_messages(ot) if m.msgType >= 100]
    assert [(m.msgType, m.msgBody) for m in slow_fwd] == [
        (m.msgType, m.msgBody) for m in fwd
    ]


def test_fast_forward_respects_fsm_gate():
    """Pre-auth user-space messages must still be FSM-rejected on the
    fast path (INIT state whitelists only AUTH)."""
    owner, ot = _owner_with_global()
    t = FakeTransport()
    conn = add_connection(t, ConnectionType.CLIENT)
    ot.written.clear()
    conn.on_bytes(_forward_wire([b"sneak"]))
    gch = get_global_channel()
    gch.tick_once(0)
    owner.flush()
    assert [m for m in sent_messages(ot) if m.msgType >= 100] == []


def test_fast_batch_stashes_on_full_queue():
    """The batched ingest honors the same lossless backpressure: a full
    channel queue stashes the whole run (has_pending -> reads pause) and
    flush_pending re-dispatches it after the tick drains."""
    if connection_mod._native_codec is None:
        pytest.skip("native codec not built")
    from channeld_tpu.core import channel as channel_mod

    owner, ot = _owner_with_global()
    conn, _ = auth_client()
    gch = get_global_channel()
    gch.tick_once(0)

    cap = channel_mod.QUEUE_CAPACITY
    channel_mod.QUEUE_CAPACITY = 2
    try:
        gch.execute(lambda ch: None)  # occupy the tiny queue (internal)
        gch.execute(lambda ch: None)
        conn.on_bytes(_forward_wire([b"bp1", b"bp2"]))
        conn.flush_ingest()  # pump-time dispatch hits the full queue
        assert conn.has_pending()
        assert channel_mod.connection_congested(conn)

        gch.tick_once(0)  # drains the queue, lifts congestion
        assert conn.flush_pending()
        assert not conn.has_pending()
    finally:
        channel_mod.QUEUE_CAPACITY = cap

    gch.tick_once(0)
    owner.flush()
    ot_msgs = [m for m in sent_messages(ot) if m.msgType >= 100]
    got = []
    for m in ot_msgs:
        sfm = wire_pb2.ServerForwardMessage()
        sfm.ParseFromString(m.msgBody)
        got.append(sfm.payload)
    assert got == [b"bp1", b"bp2"]  # nothing lost, order kept


def test_pump_retries_stashed_batch_without_transport_drain():
    """A batch stashed from a pump/tick-time flush_ingest (no transport
    _drain task exists there) must be retried by the next pump cycle —
    a request-then-wait client must not stall forever (advisor r5)."""
    if connection_mod._native_codec is None:
        pytest.skip("native codec not built")
    from channeld_tpu.core import channel as channel_mod

    owner, ot = _owner_with_global()
    conn, _ = auth_client()
    gch = get_global_channel()
    gch.tick_once(0)

    cap = channel_mod.QUEUE_CAPACITY
    channel_mod.QUEUE_CAPACITY = 1
    try:
        gch.execute(lambda ch: None)  # fill the tiny queue
        conn.on_bytes(_forward_wire([b"wait-for-me"]))
        # Pump-time dispatch: queue full -> stash; pump must remember it.
        connection_mod.flush_pending_ingest()
        assert conn.has_pending()
        assert conn in connection_mod._stash_retry

        gch.tick_once(0)  # drains the queue (and runs a retry itself)
        connection_mod.flush_pending_ingest()  # next pump cycle
        assert not conn.has_pending()
        assert conn not in connection_mod._stash_retry
    finally:
        channel_mod.QUEUE_CAPACITY = cap

    gch.tick_once(0)
    owner.flush()
    fwd = [m for m in sent_messages(ot) if m.msgType >= 100]
    assert len(fwd) == 1  # delivered without the client sending again


# ---- round-5 advisor regressions ------------------------------------------


def test_fast_path_defers_to_registered_user_handlers():
    """Advisor r5 high: a client msgType with a registered user-space
    handler (MSG_SPAWN=103 style) must take the MESSAGE_MAP dispatch, not
    the raw-forward fast path — mis-routing it skips spawn registration."""
    if connection_mod._native_codec is None:
        pytest.skip("native codec not built")
    from channeld_tpu.core.message import register_message_handler

    owner, ot = _owner_with_global()
    conn, _ = auth_client()
    ot.written.clear()

    handled = []
    register_message_handler(
        103, wire_pb2.ServerForwardMessage,
        lambda ctx: handled.append(ctx.msg_type),
    )

    # One packet: a plain forward (100) and the registered type (103).
    conn.on_bytes(_forward_wire([b"plain"], msg_type=100))
    conn.on_bytes(_forward_wire([wire_pb2.ServerForwardMessage(
        clientConnId=conn.id).SerializeToString()], msg_type=103))
    gch = get_global_channel()
    gch.tick_once(0)
    owner.flush()

    assert handled == [103]  # dispatched to the handler...
    fwd = [m for m in sent_messages(ot) if m.msgType >= 100]
    assert [m.msgType for m in fwd] == [100]  # ...not forwarded raw


def test_close_delivers_deferred_ingest_run():
    """Advisor r5 medium: a final user-space burst racing EOF into the
    same event-loop batch (deferred _fast_run, then close before the 1ms
    pump) must still reach the owner."""
    if connection_mod._native_codec is None:
        pytest.skip("native codec not built")
    owner, ot = _owner_with_global()
    conn, _ = auth_client()
    ot.written.clear()

    conn.on_bytes(_forward_wire([b"last-words"]))
    assert conn._fast_run is not None  # deferred, pump hasn't run
    conn.close(unexpected=True)  # EOF wins the race

    gch = get_global_channel()
    gch.tick_once(0)
    owner.flush()
    fwd = [m for m in sent_messages(ot) if m.msgType >= 100]
    assert len(fwd) == 1
    sfm = wire_pb2.ServerForwardMessage()
    sfm.ParseFromString(fwd[0].msgBody)
    assert sfm.payload == b"last-words"


def test_stashed_batch_revalidates_fsm_at_dispatch():
    """Advisor r5 low: a fast batch stashed behind a message that
    transitions the FSM must be re-validated when the stash flushes —
    the parse-time verdict is stale by then."""
    if connection_mod._native_codec is None:
        pytest.skip("native codec not built")
    from channeld_tpu.core import channel as channel_mod

    owner, ot = _owner_with_global()
    conn, _ = auth_client()
    ot.written.clear()

    # OPEN allows everything but transitions to LOCKED on SUB (6);
    # LOCKED rejects user space. No user-space transition exists, so the
    # parse-time user_space_fast check passes in OPEN.
    conn.fsm = MessageFsm.from_dict({
        "States": [
            {"Name": "OPEN", "MsgTypeWhitelist": "1-65535",
             "MsgTypeBlacklist": ""},
            {"Name": "LOCKED", "MsgTypeWhitelist": "1-99",
             "MsgTypeBlacklist": ""},
        ],
        "InitState": "OPEN",
        "Transitions": [
            {"FromState": "OPEN", "ToState": "LOCKED", "MsgType": 6},
        ],
    })

    cap = channel_mod.QUEUE_CAPACITY
    channel_mod.QUEUE_CAPACITY = 0  # every external put stashes
    try:
        conn.on_bytes(wire(
            MessageType.SUB_TO_CHANNEL,
            control_pb2.SubscribedToChannelMessage(),
        ))
        assert conn.has_pending()
        conn.on_bytes(_forward_wire([b"sneaky"]))  # batch stashes behind
        assert len(conn._pending_msgs) == 2
    finally:
        channel_mod.QUEUE_CAPACITY = cap

    before = conn._m_packet_dropped._value.get()
    assert conn.flush_pending()  # SUB transitions OPEN -> LOCKED first
    assert conn.fsm.current.name == "LOCKED"
    assert conn._m_packet_dropped._value.get() == before + 1  # batch dropped

    gch = get_global_channel()
    gch.tick_once(0)
    owner.flush()
    assert [m for m in sent_messages(ot) if m.msgType >= 100] == []


def test_flush_pending_ingest_skips_only_full_channels():
    """Advisor r5 low: one conn blocked on a full channel must not delay
    every other stashed conn to the next pump cycle — only conns whose
    stash head targets a known-full channel are skipped."""
    from channeld_tpu.core import channel as channel_mod
    from channeld_tpu.core.channel import create_channel

    _owner_with_global()
    conn_a, _ = auth_client("stuck")
    conn_b, _ = auth_client("fine")
    sub = create_channel(ChannelType.SUBWORLD, None)

    native = connection_mod._native_codec
    connection_mod._native_codec = None  # per-message stash for conn_a
    cap = channel_mod.QUEUE_CAPACITY
    try:
        channel_mod.QUEUE_CAPACITY = 0  # stash everything
        p = wire_pb2.Packet(messages=[wire_pb2.MessagePack(
            channelId=sub.id, msgType=101, msgBody=b"x")])
        conn_a.on_bytes(encode_packet(p))
        conn_b.on_bytes(wire(101, control_pb2.AuthMessage()))  # GLOBAL
        assert conn_a.has_pending() and conn_b.has_pending()
        assert conn_a.pending_head_channel() == sub.id
        assert conn_b.pending_head_channel() == 0

        # Keep ONLY the SUBWORLD channel full; GLOBAL drains.
        channel_mod.QUEUE_CAPACITY = 2
        sub.execute(lambda ch: None)
        sub.execute(lambda ch: None)

        # conn_a stashed first: the old break would starve conn_b here.
        connection_mod._stash_retry.clear()
        connection_mod._stash_retry[conn_a] = None
        connection_mod._stash_retry[conn_b] = None
        connection_mod.flush_pending_ingest()
        assert conn_a.has_pending()  # still blocked on the full channel
        assert not conn_b.has_pending()  # flushed in the SAME cycle
        assert conn_b not in connection_mod._stash_retry
    finally:
        channel_mod.QUEUE_CAPACITY = cap
        connection_mod._native_codec = native


def test_flush_pending_ingest_multiple_distinct_full_channels():
    """Extends the PR-1 stash-retry fix: TWO distinct channels full in
    the SAME flush_pending_ingest cycle. Conns blocked on either full
    channel are skipped (each full channel discovered at most once per
    cycle), while a conn targeting a drained third channel flushes in
    that same cycle — and each blocked conn drains as soon as ITS
    channel frees, independent of the other full channel."""
    from channeld_tpu.core import channel as channel_mod
    from channeld_tpu.core.channel import create_channel

    _owner_with_global()
    conn_a, _ = auth_client("stuck-on-a")
    conn_b, _ = auth_client("stuck-on-b")
    conn_c, _ = auth_client("fine")
    sub_a = create_channel(ChannelType.SUBWORLD, None)
    sub_b = create_channel(ChannelType.SUBWORLD, None)

    native = connection_mod._native_codec
    connection_mod._native_codec = None  # per-message stash path
    cap = channel_mod.QUEUE_CAPACITY
    try:
        channel_mod.QUEUE_CAPACITY = 0  # stash everything
        for conn, target in ((conn_a, sub_a.id), (conn_b, sub_b.id)):
            conn.on_bytes(encode_packet(wire_pb2.Packet(
                messages=[wire_pb2.MessagePack(
                    channelId=target, msgType=101, msgBody=b"x")])))
        conn_c.on_bytes(wire(101, control_pb2.AuthMessage()))  # GLOBAL
        assert conn_a.pending_head_channel() == sub_a.id
        assert conn_b.pending_head_channel() == sub_b.id
        assert conn_c.pending_head_channel() == 0

        # BOTH subworld channels stay full; only GLOBAL drains.
        channel_mod.QUEUE_CAPACITY = 2
        for sub in (sub_a, sub_b):
            sub.execute(lambda ch: None)
            sub.execute(lambda ch: None)

        connection_mod._stash_retry.clear()
        connection_mod._stash_retry[conn_a] = None
        connection_mod._stash_retry[conn_b] = None
        connection_mod._stash_retry[conn_c] = None
        connection_mod.flush_pending_ingest()
        assert conn_a.has_pending() and conn_b.has_pending()
        assert not conn_c.has_pending()  # drained-channel conn: same cycle
        assert conn_c not in connection_mod._stash_retry

        # Channel B frees; A stays full. Only conn_b must drain — the
        # full channel A must not hold it (nor vice versa).
        sub_b.tick_once(0)
        connection_mod.flush_pending_ingest()
        assert conn_a.has_pending()  # its channel is still full
        assert not conn_b.has_pending()
        assert conn_b not in connection_mod._stash_retry

        # Finally A frees too: nothing left behind.
        sub_a.tick_once(0)
        connection_mod.flush_pending_ingest()
        assert not conn_a.has_pending()
        assert connection_mod._stash_retry == {}
    finally:
        channel_mod.QUEUE_CAPACITY = cap
        connection_mod._native_codec = native


def test_close_counts_undeliverable_stash_as_dropped():
    """A stash the full channel still refuses at close time dies with
    the connection — but counted in packet_dropped, never silently."""
    from channeld_tpu.core import channel as channel_mod

    _owner_with_global()
    conn, _ = auth_client("doomed")

    native = connection_mod._native_codec
    connection_mod._native_codec = None
    cap = channel_mod.QUEUE_CAPACITY
    try:
        channel_mod.QUEUE_CAPACITY = 0  # everything stashes, nothing drains
        conn.on_bytes(_forward_wire([b"a"]))
        conn.on_bytes(_forward_wire([b"b"]))
        assert len(conn._pending_msgs) == 2
        before = conn._m_packet_dropped._value.get()
        conn.close(unexpected=True)
        assert conn._m_packet_dropped._value.get() == before + 2
        assert not conn._pending_msgs
    finally:
        channel_mod.QUEUE_CAPACITY = cap
        connection_mod._native_codec = native
