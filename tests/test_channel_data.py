"""ChannelData fan-out and merge semantics.

Replicates the reference's canonical timeline test
(ref: pkg/channeld/data_test.go TestFanOutChannelData:98, which itself
replays the U1/U2/F1..F9 diagram from doc/design.md) plus merge options
and field masks (TestDataMergeOptions:290, TestDataFieldMasks:349).
"""

import pytest

from channeld_tpu.core.channel import create_channel
from channeld_tpu.core.data import tick_data
from channeld_tpu.core.subscription import subscribe_to_channel
from channeld_tpu.core.types import ChannelType, ConnectionType
from channeld_tpu.models import testdata_pb2
from channeld_tpu.protocol import control_pb2
from channeld_tpu.utils.fieldmask import filter_fields

from helpers import StubConnection, fresh_runtime

MS = 1_000_000  # channel time is integer nanoseconds


@pytest.fixture(autouse=True)
def runtime():
    yield fresh_runtime()


def test_fanout_timeline():
    """The exact F0..F9 fan-out timeline from the reference design doc."""
    c0 = StubConnection(1, ConnectionType.SERVER)  # server owner
    c1 = StubConnection(2)
    c2 = StubConnection(3)

    ch = create_channel(ChannelType.TEST, c0)
    ch.init_data(testdata_pb2.TestChannelDataMessage(text="a", num=1), None)

    assert subscribe_to_channel(c0, ch, None)[0] is not None
    cs1, _ = subscribe_to_channel(
        c1, ch, control_pb2.ChannelSubscriptionOptions(fanOutIntervalMs=50)
    )
    assert cs1 is not None

    t0 = 100 * MS  # channel time of the first tick

    # F0: first fan-out sends the whole data to c1.
    tick_data(ch, t0)
    assert len(c1.data_updates()) == 1
    assert len(c2.data_updates()) == 0
    assert c1.latest_data_update().num == 1

    cs2, _ = subscribe_to_channel(
        c2, ch, control_pb2.ChannelSubscriptionOptions(fanOutIntervalMs=100)
    )
    assert cs2 is not None

    # F1 (c1): no new data -> nothing; F7 (c2): first fan-out, whole data.
    tick_data(ch, t0 + 50 * MS)
    assert len(c1.data_updates()) == 1
    assert len(c2.data_updates()) == 1
    assert c2.latest_data_update().num == 1

    # U1 arrives at 160ms.
    ch.data.on_update(
        testdata_pb2.TestChannelDataMessage(text="b"), t0 + 60 * MS, c0.id, None
    )

    # F2 (c1 at 200ms) = U1. c2 not due.
    tick_data(ch, t0 + 100 * MS)
    assert len(c1.data_updates()) == 2
    assert len(c2.data_updates()) == 1
    assert c1.latest_data_update().num == 0  # update carries no num
    assert c1.latest_data_update().text == "b"
    assert c2.latest_data_update().text == "a"

    # U2 arrives at 220ms.
    ch.data.on_update(
        testdata_pb2.TestChannelDataMessage(text="c"), t0 + 120 * MS, c0.id, None
    )

    # F8 (c2) = U1+U2; F3 (c1) = U2.
    tick_data(ch, t0 + 150 * MS)
    assert len(c1.data_updates()) == 3
    assert len(c2.data_updates()) == 2
    assert c1.latest_data_update().text == "c"
    assert c2.latest_data_update().text == "c"

    # U3 arrives from c2 itself at 305ms; tick at 310ms: c1's window
    # [250,300] closes before U3's arrival -> nothing fans out.
    ch.data.on_update(
        testdata_pb2.TestChannelDataMessage(text="d"), t0 + 205 * MS, c2.id, None
    )
    tick_data(ch, t0 + 210 * MS)
    assert len(c1.data_updates()) == 3
    assert len(c2.data_updates()) == 2

    # 350ms: c1 due, window [300,350] contains U3 (sender c2 != c1) -> "d".
    # c2 due too, but U3 is its own update and skipSelfUpdateFanOut defaults
    # true -> skipped. (Deviation from the reference *test file*, which
    # expects self-delivery; the reference *code* skips self updates —
    # data.go:242 runs before the window check — so we assert code-faithful
    # behavior here and cover the opt-out in test_skip_self_update_fanout.)
    tick_data(ch, t0 + 250 * MS)
    assert len(c1.data_updates()) == 4
    assert c1.latest_data_update().text == "d"
    assert len(c2.data_updates()) == 2

    # U5 from the server at 460ms; the next tick comes at 500ms, two of
    # c1's intervals after its last one. The windows nothing arrived in
    # ((350,400], (400,450]) close by arithmetic and each subscriber is
    # served the window that holds U5, no sooner than that window's close:
    # what a tick every interval, on time, would have sent.
    ch.data.on_update(
        testdata_pb2.TestChannelDataMessage(text="e"), t0 + 360 * MS, c0.id, None
    )
    tick_data(ch, t0 + 400 * MS)  # c1 (450,500] holds 460; c2 (450,550] open
    assert len(c1.data_updates()) == 5
    assert c1.latest_data_update().text == "e"
    assert len(c2.data_updates()) == 2
    assert cs1.fanout_conn.last_fanout_time == t0 + 400 * MS
    assert cs2.fanout_conn.last_fanout_time == t0 + 350 * MS
    tick_data(ch, t0 + 450 * MS)  # c2 (450,550] closes -> "e"; c1 owed nothing
    assert len(c1.data_updates()) == 5
    assert len(c2.data_updates()) == 3
    assert c2.latest_data_update().text == "e"
    tick_data(ch, t0 + 500 * MS)  # nothing owed: windows move on, nothing sent
    assert len(c1.data_updates()) == 5
    assert len(c2.data_updates()) == 3
    assert cs1.fanout_conn.last_fanout_time == t0 + 500 * MS


def test_skip_self_update_fanout():
    c1 = StubConnection(1)
    ch = create_channel(ChannelType.TEST, None)
    ch.init_data(testdata_pb2.TestChannelDataMessage(text="x"), None)
    subscribe_to_channel(
        c1, ch, control_pb2.ChannelSubscriptionOptions(fanOutIntervalMs=100)
    )
    tick_data(ch, 100 * MS)  # first: full state
    ch.data.on_update(
        testdata_pb2.TestChannelDataMessage(text="self"), 110 * MS, c1.id, None
    )
    tick_data(ch, 200 * MS)
    # Own update skipped (default skipSelfUpdateFanOut=True).
    assert len(c1.data_updates()) == 1
    # With skipSelf disabled the update comes through.
    ch.subscribed_connections[c1].options.skipSelfUpdateFanOut = False
    ch.data.on_update(
        testdata_pb2.TestChannelDataMessage(text="self2"), 210 * MS, c1.id, None
    )
    tick_data(ch, 300 * MS)
    assert c1.latest_data_update().text == "self2"


def test_merge_options_list_limit():
    """(ref: data_test.go TestDataMergeOptions)."""
    from channeld_tpu.core.data import reflect_merge

    dst = testdata_pb2.TestChannelDataMessage(list=["a", "b", "c"])
    src = testdata_pb2.TestChannelDataMessage(list=["d", "e"])

    opts = control_pb2.ChannelDataMergeOptions(listSizeLimit=4)
    reflect_merge(dst, src, opts)
    assert list(dst.list) == ["a", "b", "c", "d"]  # tail-truncated

    dst = testdata_pb2.TestChannelDataMessage(list=["a", "b", "c"])
    opts = control_pb2.ChannelDataMergeOptions(listSizeLimit=4, truncateTop=True)
    reflect_merge(dst, src, opts)
    assert list(dst.list) == ["b", "c", "d", "e"]  # head-truncated

    dst = testdata_pb2.TestChannelDataMessage(list=["a", "b", "c"])
    opts = control_pb2.ChannelDataMergeOptions(shouldReplaceList=True)
    reflect_merge(dst, src, opts)
    assert list(dst.list) == ["d", "e"]


def test_merge_removable_map_field():
    from channeld_tpu.core.data import reflect_merge

    dst = testdata_pb2.TestChannelDataMessage()
    dst.kv[1].name = "alice"
    dst.kv[2].name = "bob"
    src = testdata_pb2.TestChannelDataMessage()
    src.kv[2].removed = True
    opts = control_pb2.ChannelDataMergeOptions(shouldCheckRemovableMapField=True)
    reflect_merge(dst, src, opts)
    assert 1 in dst.kv and 2 not in dst.kv


def test_protobuf_map_merge_overwrites_entries():
    """(ref: data_test.go TestProtobufMapMerge)."""
    from channeld_tpu.core.data import reflect_merge

    dst = testdata_pb2.TestChannelDataMessage()
    dst.attrs["k"] = "old"
    src = testdata_pb2.TestChannelDataMessage()
    src.attrs["k"] = "new"
    src.attrs["k2"] = "v2"
    reflect_merge(dst, src, None)
    assert dst.attrs["k"] == "new" and dst.attrs["k2"] == "v2"


def test_data_field_masks():
    """(ref: data_test.go TestDataFieldMasks)."""
    msg = testdata_pb2.TestChannelDataMessage(text="t", num=7, list=["x"])
    msg.kv[1].name = "alice"
    msg.kv[2].name = "bob"
    filter_fields(msg, ["text", "kv.1"])
    assert msg.text == "t"
    assert msg.num == 0
    assert list(msg.list) == []
    assert 1 in msg.kv and 2 not in msg.kv


def test_fanout_applies_field_masks_per_subscriber():
    c1 = StubConnection(1)
    c2 = StubConnection(2)
    ch = create_channel(ChannelType.TEST, None)
    ch.init_data(testdata_pb2.TestChannelDataMessage(text="a", num=5), None)
    subscribe_to_channel(
        c1,
        ch,
        control_pb2.ChannelSubscriptionOptions(
            fanOutIntervalMs=10, dataFieldMasks=["text"]
        ),
    )
    subscribe_to_channel(
        c2, ch, control_pb2.ChannelSubscriptionOptions(fanOutIntervalMs=10)
    )
    tick_data(ch, 100 * MS)
    masked = c1.latest_data_update()
    assert masked.text == "a" and masked.num == 0
    full = c2.latest_data_update()
    assert full.text == "a" and full.num == 5
    # The shared state was not corrupted by the masked copy.
    assert ch.data.msg.num == 5


def test_update_buffer_overflow_drops_consumed_only():
    ch = create_channel(ChannelType.TEST, None)
    ch.init_data(testdata_pb2.TestChannelDataMessage(), None)
    ch.data.max_fanout_interval_ms = 100
    from channeld_tpu.core.data import MAX_UPDATE_MSG_BUFFER_SIZE

    for i in range(MAX_UPDATE_MSG_BUFFER_SIZE + 10):
        ch.data.on_update(
            testdata_pb2.TestChannelDataMessage(num=i), i * MS, 42, None
        )
    # Old entries past every subscriber's window were dropped.
    assert len(ch.data.update_msg_buffer) <= MAX_UPDATE_MSG_BUFFER_SIZE + 1


def test_skip_first_fanout():
    """skipFirstFanOut suppresses the full-state send: the subscriber only
    sees updates buffered after it joined (ref: subscription.go:72 seeds
    hadFirstFanOut from the option)."""
    owner = StubConnection(1, ConnectionType.SERVER)
    sub = StubConnection(2)
    ch = create_channel(ChannelType.TEST, owner)
    ch.init_data(testdata_pb2.TestChannelDataMessage(text="pre", num=7), None)

    cs, _ = subscribe_to_channel(
        sub, ch, control_pb2.ChannelSubscriptionOptions(
            fanOutIntervalMs=50, skipFirstFanOut=True),
    )
    assert cs is not None

    # Would be the full-state first fan-out; the option suppresses it.
    tick_data(ch, 100 * MS)
    assert len(sub.data_updates()) == 0

    # A later update fans out normally — without replaying the "pre" state.
    # (Windows are [last, last+interval] in channel time, so the 120ms
    # arrival lands in the window that closes at 150ms, delivered on the
    # following due tick — same lag as the reference's F2 step.)
    ch.data.on_update(
        testdata_pb2.TestChannelDataMessage(text="post"), 120 * MS, owner.id, None
    )
    tick_data(ch, 150 * MS)
    tick_data(ch, 200 * MS)
    assert len(sub.data_updates()) == 1
    assert sub.latest_data_update().text == "post"
    assert sub.latest_data_update().num == 0  # never saw the initial state


def test_merge_sub_options_on_resubscribe():
    """Re-subscribing merges partial options over the existing ones:
    explicitly-sent fields override, unsent fields keep their values, and
    the result-send flag fires only when data access changed
    (ref: data_test.go TestMergeSubOptions + subscription.go:34-102)."""
    conn = StubConnection(1)
    ch = create_channel(ChannelType.TEST, None)
    cs, _ = subscribe_to_channel(
        conn, ch, control_pb2.ChannelSubscriptionOptions(
            dataAccess=2,  # WRITE
            fanOutIntervalMs=100, fanOutDelayMs=200),
    )
    assert (cs.options.dataAccess, cs.options.fanOutIntervalMs,
            cs.options.fanOutDelayMs) == (2, 100, 200)

    # Partial update: access drops to READ, interval halves, delay unsent.
    cs2, access_changed = subscribe_to_channel(
        conn, ch, control_pb2.ChannelSubscriptionOptions(
            dataAccess=1, fanOutIntervalMs=50),
    )
    assert cs2 is cs and access_changed
    assert (cs.options.dataAccess, cs.options.fanOutIntervalMs,
            cs.options.fanOutDelayMs) == (1, 50, 200)

    # Non-access field changed: merged, but no result resend needed.
    _, access_changed = subscribe_to_channel(
        conn, ch, control_pb2.ChannelSubscriptionOptions(fanOutIntervalMs=20),
    )
    assert not access_changed
    assert cs.options.fanOutIntervalMs == 20

    # Identical options resent: no change, no result resend.
    _, access_changed = subscribe_to_channel(
        conn, ch, control_pb2.ChannelSubscriptionOptions(
            dataAccess=1, fanOutIntervalMs=20),
    )
    assert not access_changed
    assert (cs.options.dataAccess, cs.options.fanOutIntervalMs,
            cs.options.fanOutDelayMs) == (1, 20, 200)


def test_cross_type_update_dropped_cleanly():
    """A client shipping a data type the channel doesn't speak must not
    traceback-spam the log or corrupt state — clean warning drop (the
    reference's reflection merge would panic the channel goroutine)."""
    from channeld_tpu.core.data import ChannelData
    from channeld_tpu.models import sim_pb2
    from channeld_tpu.models.sim import register_sim_types  # noqa: F401

    data = ChannelData(sim_pb2.SimGlobalChannelData())
    data.msg.kv["k"] = "v"
    hostile = sim_pb2.SimSpatialChannelData()
    hostile.entities[1].SetInParent()
    data.on_update(hostile, 0, 1, None)  # must not raise
    assert data.msg.kv["k"] == "v"  # state intact
    assert type(data.msg) is sim_pb2.SimGlobalChannelData
    # Custom-merge path (spatial data) rejects cross-type cleanly too.
    spatial = ChannelData(sim_pb2.SimSpatialChannelData())
    spatial.msg.entities[5].SetInParent()
    data2 = sim_pb2.SimGlobalChannelData()
    spatial.on_update(data2, 0, 1, None)  # must not raise
    assert 5 in spatial.msg.entities


def test_dropped_cross_type_update_never_enters_the_ring():
    """A dropped incompatible update must not be buffered either — it
    would fan out verbatim or crash window accumulation later."""
    from channeld_tpu.core.data import ChannelData
    from channeld_tpu.models import sim_pb2
    import channeld_tpu.models.sim  # noqa: F401  (attaches merges)

    data = ChannelData(sim_pb2.SimGlobalChannelData())
    before = len(data.update_msg_buffer)
    data.on_update(sim_pb2.SimSpatialChannelData(), 0, 1, None)
    assert len(data.update_msg_buffer) == before
    assert data.msg_index == 0


def test_hostile_first_update_cannot_wedge_a_registered_channel():
    """Late-binding adoption: once a data type is registered for the
    channel type, a mistyped first update is refused (it would otherwise
    fix the wrong type forever, warn-dropping all legit updates)."""
    from channeld_tpu.core.channel import ChannelType
    from channeld_tpu.core.data import (
        ChannelData,
        register_channel_data_type,
    )
    from channeld_tpu.models import sim_pb2
    import channeld_tpu.models.sim  # noqa: F401

    register_channel_data_type(ChannelType.GLOBAL, sim_pb2.SimGlobalChannelData())
    data = ChannelData(None, channel_type=ChannelType.GLOBAL)
    hostile = sim_pb2.SimSpatialChannelData()
    data.on_update(hostile, 0, 666, None)
    assert data.msg is None  # refused
    good = sim_pb2.SimGlobalChannelData()
    good.kv["k"] = "v"
    data.on_update(good, 0, 1, None)
    assert data.msg is good  # legit adoption proceeds
