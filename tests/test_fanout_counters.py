"""What the fan-out and the send pump count (PR 36).

``fanout_encodes_total`` / ``fanout_sends_total``: a window's update is
serialized once and sent to every subscriber that shares it, and
``tick_data`` adds its tick's count to the counters once a tick. The
``send_pump`` stage: one observation for a pass of ``flush_loop`` that
flushed at least one connection, none for a pass that found nothing
queued.
"""

import asyncio

import pytest

from channeld_tpu.core import connection as connection_mod
from channeld_tpu.core import data as data_mod
from channeld_tpu.core import metrics, tracing
from channeld_tpu.core.channel import create_channel, create_entity_channel
from channeld_tpu.core.data import tick_data
from channeld_tpu.core.server import flush_loop
from channeld_tpu.core.settings import global_settings
from channeld_tpu.core.subscription import subscribe_to_channel
from channeld_tpu.core.types import ChannelType, ConnectionType
from channeld_tpu.models import testdata_pb2
from channeld_tpu.protocol import control_pb2

from helpers import StubConnection, fresh_runtime, stage_count

MS = 1_000_000  # channel time is integer nanoseconds
HIFI_MS = 20  # channel_settings_hifi.json's fan-out interval
T0 = 100 * MS
BOTH = pytest.mark.parametrize("channel_type",
                               [ChannelType.SPATIAL, ChannelType.ENTITY])


@pytest.fixture(autouse=True)
def runtime():
    yield fresh_runtime()


def counted(channel_type) -> tuple[float, float]:
    """(encodes, sends) of one channel type so far."""
    name = channel_type.name
    return (metrics.fanout_encodes.labels(channel_type=name)._value.get(),
            metrics.fanout_sends.labels(channel_type=name)._value.get())


def channel_with(channel_type, subscribers: int, **options):
    """A channel of ``channel_type`` with data, its owner, and that many
    subscribers past their first fan-out, all served at ``T0``: their
    windows close together from then on."""
    owner = StubConnection(1, ConnectionType.SERVER)
    if channel_type == ChannelType.ENTITY:
        ch = create_entity_channel(
            global_settings.entity_channel_id_start + 7, owner)
    else:
        ch = create_channel(channel_type, owner)
    ch.init_data(testdata_pb2.TestChannelDataMessage(text="a", num=1), None)
    conns = [StubConnection(10 + i) for i in range(subscribers)]
    for conn in conns:
        cs, _ = subscribe_to_channel(
            conn, ch, control_pb2.ChannelSubscriptionOptions(
                fanOutIntervalMs=HIFI_MS, **options))
        assert cs is not None
    tick_data(ch, T0)
    assert all(len(c.data_updates()) == 1 for c in conns)
    return ch, owner, conns


def update(ch, owner, text: str, at: int) -> None:
    ch.data.on_update(testdata_pb2.TestChannelDataMessage(text=text), at,
                      owner.id, None)


@BOTH
def test_one_update_to_32_subscribers_is_one_encode_and_32_sends(channel_type):
    before = counted(channel_type)
    ch, owner, conns = channel_with(channel_type, 32)
    first = counted(channel_type)
    # The first fan-out carries the whole state: one body, 32 sends.
    assert (first[0] - before[0], first[1] - before[1]) == (1, 32)
    update(ch, owner, "b", T0 + 5 * MS)
    tick_data(ch, T0 + HIFI_MS * MS)
    after = counted(channel_type)
    assert (after[0] - first[0], after[1] - first[1]) == (1, 32)
    assert all(c.latest_data_update().text == "b" for c in conns)
    bodies = {id(c.sent[-1]) for c in conns}
    assert len(bodies) == 1  # the one context went to every subscriber
    # No other channel type moved.
    other = (ChannelType.ENTITY if channel_type == ChannelType.SPATIAL
             else ChannelType.SPATIAL)
    quiet = counted(other)
    tick_data(ch, T0 + 2 * HIFI_MS * MS)  # nothing owed: nothing counted
    assert counted(channel_type) == after and counted(other) == quiet


@BOTH
def test_per_subscriber_content_is_an_encode_a_send(channel_type):
    """Field masks make every subscriber's body its own: nothing is
    shared, so encodes equal sends."""
    before = counted(channel_type)
    ch, owner, conns = channel_with(channel_type, 8, dataFieldMasks=["text"])
    update(ch, owner, "b", T0 + 5 * MS)
    tick_data(ch, T0 + HIFI_MS * MS)
    after = counted(channel_type)
    assert (after[0] - before[0], after[1] - before[1]) == (16, 16)
    assert all(c.latest_data_update().text == "b" for c in conns)


def test_subscribers_with_and_without_masks_share_what_they_can():
    before = counted(ChannelType.SPATIAL)
    ch, owner, plain = channel_with(ChannelType.SPATIAL, 5)
    masked = StubConnection(99)
    subscribe_to_channel(masked, ch, control_pb2.ChannelSubscriptionOptions(
        fanOutIntervalMs=HIFI_MS, dataFieldMasks=["text"]))
    tick_data(ch, T0)  # the newcomer's first fan-out, its own body
    first = counted(ChannelType.SPATIAL)
    assert (first[0] - before[0], first[1] - before[1]) == (2, 6)
    update(ch, owner, "b", T0 + 5 * MS)
    tick_data(ch, T0 + HIFI_MS * MS)
    after = counted(ChannelType.SPATIAL)
    assert (after[0] - first[0], after[1] - first[1]) == (2, 6)


@BOTH
def test_the_counters_rise_once_a_tick(channel_type, monkeypatch):
    """``tick_data`` counts into locals: whatever it served, a tick is
    one ``inc`` of each counter, and a tick that sent nothing is none."""

    class Tally:
        def __init__(self):
            self.calls: list = []

        def inc(self, amount):
            self.calls.append(amount)

    ch, owner, _conns = channel_with(channel_type, 32)
    encodes, sends = Tally(), Tally()
    monkeypatch.setitem(data_mod._fanout_counters, channel_type,
                        (encodes, sends))
    tick_data(ch, T0 + HIFI_MS * MS)  # nothing owed
    assert encodes.calls == sends.calls == []
    for i, text in enumerate("bcd", start=1):
        update(ch, owner, text, T0 + i * HIFI_MS * MS + 5 * MS)
        tick_data(ch, T0 + (i + 1) * HIFI_MS * MS)
    assert encodes.calls == [1, 1, 1]
    assert sends.calls == [32, 32, 32]


# ---------------------------------------------------------------------------
# the send pump's stage
# ---------------------------------------------------------------------------


class Queued:
    """What the pump touches of a connection."""

    transport = None  # no TCP socket: the pump calls ``flush``

    def __init__(self, messages: int):
        self.send_queue = ["m"] * messages
        self.flushes = 0

    def is_closing(self) -> bool:
        return False

    def flush(self, fair: bool = False) -> None:
        assert fair
        self.flushes += 1
        self.send_queue = []


def pump(conns: list, seconds: float = 0.03) -> int:
    """Hand ``conns`` to the pump as one pass's pending set and let it
    run a few more passes; the ``send_pump`` observations that made."""

    async def main():
        for conn in conns:
            connection_mod.requeue_flush(conn)
        task = asyncio.ensure_future(flush_loop())
        await asyncio.sleep(seconds)
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)

    before = stage_count("send_pump")
    asyncio.run(main())
    return int(stage_count("send_pump") - before)


class Annotation:
    """A profiler session faked live (as tests/test_tracing.py does)."""

    made: list = []

    def __init__(self, name):
        self.name = name

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        Annotation.made.append(self.name)
        return self

    def __exit__(self, *exc):
        pass


@pytest.fixture(params=[False, True], ids=["plain", "profiling"])
def profiling(request, monkeypatch):
    Annotation.made = []
    if request.param:
        monkeypatch.setattr(tracing, "_annotation", Annotation)
    monkeypatch.setattr(tracing.recorder, "profiling", request.param)
    return request.param


def test_a_pass_that_flushed_two_connections_is_one_observation(profiling):
    a, b = Queued(3), Queued(1)
    assert pump([a, b]) == 1  # the later passes found nothing pending
    assert (a.flushes, b.flushes) == (1, 1)
    spans = [s for s in tracing.recorder.snapshot()
             if s["name"] == "send_pump"]
    assert len(spans) == 1 and spans[0]["dur_ns"] >= 0
    assert Annotation.made == (["channeld/send_pump"] if profiling else [])


def test_a_pass_with_nothing_queued_records_nothing(profiling):
    idle = Queued(0)  # pending, but its queue emptied before its turn
    assert pump([]) == 0
    assert pump([idle]) == 0 and idle.flushes == 0
    assert not [s for s in tracing.recorder.snapshot()
                if s["name"] == "send_pump"]
