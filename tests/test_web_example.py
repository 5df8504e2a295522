"""Pin the browser chat example's hand-rolled wire code to the protocol
(examples/web was in the parity table with nothing
automated). The JS cannot execute under pytest, so the pin is structural:
the constants and field numbers the page hand-encodes must match the
real schema — that is exactly what drifts when the protocol evolves."""

import re
from pathlib import Path

import pytest

from channeld_tpu.core.types import MessageType
from channeld_tpu.protocol import wire_pb2
from channeld_tpu.protocol.framing import _MAGIC0, _MAGIC1

WEB = Path(__file__).resolve().parent.parent / "examples" / "web" / "index.html"

pytestmark = pytest.mark.skipif(not WEB.exists(), reason="web example absent")


def test_js_frame_magic_matches_framing():
    src = WEB.read_text()
    assert f"0x{_MAGIC0:02x},0x{_MAGIC1:02x}" in src.lower().replace(" ", ""), (
        "frame tag bytes drifted from protocol/framing.py"
    )
    # Decoder checks the same magic.
    assert re.search(r"buf\[0\]!==0x43\s*\|\|\s*buf\[1\]!==0x48", src)


def test_js_messagepack_field_numbers_match_schema():
    """The page hand-encodes MessagePack{1:channelId, 4:msgType, 5:msgBody};
    those field numbers must be the generated schema's."""
    fields = wire_pb2.MessagePack.DESCRIPTOR.fields_by_name
    assert fields["channelId"].number == 1
    assert fields["msgType"].number == 4
    assert fields["msgBody"].number == 5
    src = WEB.read_text()
    assert "varintField(1,channelId)" in src.replace(" ", "")
    assert "varintField(4,msgType)" in src.replace(" ", "")
    assert "bytesField(5,body)" in src.replace(" ", "")


def test_js_message_type_ids_match_enum():
    src = WEB.read_text()
    # The page dispatches on AUTH(1) and CHANNEL_DATA_UPDATE(8).
    assert int(MessageType.AUTH) == 1
    assert int(MessageType.CHANNEL_DATA_UPDATE) == 8
    assert "msgType===1" in src.replace(" ", "")
    assert "msgType===8" in src.replace(" ", "")


def test_js_frames_decode_with_the_real_decoder():
    """Reproduce the page's byte-level encoder in Python (same literal
    algorithm: varint fields 1/4/5, 5-byte CH tag) and assert the real
    FrameDecoder + protobuf parse what the browser would send."""
    from channeld_tpu.protocol.framing import FrameDecoder

    def varint(v):
        out = bytearray()
        while True:
            b = v & 0x7F
            v >>= 7
            out.append(b | (0x80 if v else 0))
            if not v:
                return bytes(out)

    def varint_field(f, v):
        return bytes([f << 3]) + varint(v)

    def bytes_field(f, data):
        return bytes([(f << 3) | 2]) + varint(len(data)) + data

    # What the page's sendMsg(0, AUTH, authBody) builds.
    auth_body = bytes_field(1, b"web-pit") + bytes_field(2, b"lt")
    mp = varint_field(1, 0) + varint_field(4, 1) + bytes_field(5, auth_body)
    packet = bytes_field(1, mp)
    frame = bytes([0x43, 0x48, (len(packet) >> 8) & 0xFF,
                   len(packet) & 0xFF, 0]) + packet

    bodies = FrameDecoder().feed(frame)
    assert len(bodies) == 1
    parsed = wire_pb2.Packet()
    parsed.ParseFromString(bodies[0])
    assert parsed.messages[0].msgType == MessageType.AUTH
    from channeld_tpu.protocol import control_pb2

    auth = control_pb2.AuthMessage()
    auth.ParseFromString(parsed.messages[0].msgBody)
    assert auth.playerIdentifierToken == "web-pit"
