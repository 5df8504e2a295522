"""Channels tick for work, not for time (core/channel.py ``TickScheduler``,
core/data.py ``tick_data``): one task ticks every channel but GLOBAL when
it has a message, a closed fan-out window that holds an owed update, or
one of the duties a tick carries; a window nothing arrived in closes by
arithmetic. A message is paced to one tick an interval; a window is
served AT its close, whatever the channel's last tick was. Held here on
a synthetic clock: the scheduler sends every subscriber what a tick
every interval, on time, sends it, no sooner than its own window's
close and no later than that tick; and none of the duties is lost with
the ticks.
"""

import asyncio
import gc
import math
import time

import pytest

import channeld_tpu.core.channel as channel_mod
import channeld_tpu.core.connection as connection_mod
import channeld_tpu.core.data as data_mod
from channeld_tpu.core.channel import create_channel, remove_channel
from channeld_tpu.core.overload import governor
from channeld_tpu.core.settings import global_settings
from channeld_tpu.core.subscription import (
    subscribe_to_channel,
    unsubscribe_from_channel,
)
from channeld_tpu.core.types import ChannelType, ConnectionType
from channeld_tpu.models import testdata_pb2
from channeld_tpu.protocol import control_pb2
from channeld_tpu.spatial.controller import set_spatial_controller
from channeld_tpu.utils.anyutil import unpack_any

from helpers import StubConnection, fresh_runtime

MS = 1_000_000  # channel time is integer nanoseconds
TICK_MS = 50  # the cells' settings: 50 ms ticks, 100 ms fan-out
scheduler = channel_mod.scheduler


class Clock:
    """Stands in for the ``time`` module in core/channel.py: the loop's
    clock and every channel's time come from ``ns``."""

    def __init__(self):
        self.ns = 1_000 * 1_000 * MS

    def monotonic(self) -> float:
        return self.ns * 1e-9

    def monotonic_ns(self) -> int:
        return self.ns

    def set_ms(self, ms: float) -> None:
        self.ns = max(self.ns, self.t0 + round(ms * MS))

    def ms(self) -> float:
        return (self.ns - self.t0) / MS

    sleep = staticmethod(time.sleep)
    time = staticmethod(time.time)


@pytest.fixture
def clock(monkeypatch):
    fresh_runtime()
    clock = Clock()
    clock.t0 = clock.ns
    monkeypatch.setattr(channel_mod, "time", clock)
    # The passes are driven by hand, on the synthetic clock: a channel
    # made inside a loop must not start the real task beside them.
    monkeypatch.setattr(scheduler, "start", lambda: None)
    yield clock
    scheduler.reset()


class Peer(StubConnection):
    """Records what it is sent, and when on the synthetic clock."""

    def __init__(self, conn_id, clock, conn_type=ConnectionType.CLIENT):
        super().__init__(conn_id, conn_type)
        self.clock = clock
        self.got = []  # (ms, text)

    def send(self, ctx) -> None:
        super().send(ctx)
        if ctx.msg_type == 8:
            self.got.append(
                (self.clock.ms(), unpack_any(ctx.msg.data).text))


class StubDevice:
    """The device's half of the fan-out plane, by its rule
    (ops/spatial_ops.py ``fanout_due``): a subscription is marked due
    at the first step at or after ``last + interval`` and its ``last``
    moves on by ONE interval; marks wait in a table keyed by slot, so
    two that land between two ticks of the channel are one. ``step``
    is spatial/tpu_controller.py ``_publish_due``'s contract."""

    def __init__(self, clock):
        self.clock = clock
        self.subs = {}  # slot -> [last_ms, interval_ms, channel id]
        self.pending = {}
        self.seq = 0

    def device_sub_add(self, interval_ms, delay_ms, channel_id):
        slot = len(self.subs)
        self.subs[slot] = [self.clock.ms() + delay_ms, interval_ms,
                           channel_id]
        return slot

    def device_sub_remove(self, slot):
        sub = self.subs.pop(slot)
        self.pending.get(sub[2], {}).pop(slot, None)

    def device_sub_set_interval(self, slot, interval_ms):
        self.subs[slot][1] = interval_ms

    def device_sub_first_fanout(self, slot):
        self.subs[slot][0] = self.clock.ms()

    def device_due(self, channel_id):
        if self.seq == 0:
            return None
        return self.seq, self.pending.setdefault(channel_id, {})

    def step(self):
        self.seq += 1
        now = self.clock.ms()
        for slot, sub in self.subs.items():
            if now >= sub[0] + sub[1]:
                sub[0] += sub[1]
                self.pending.setdefault(sub[2], {})[slot] = self.seq
                ch = channel_mod.get_channel(sub[2])
                if data_mod.mark_has_work(ch, slot):
                    scheduler.note_device_due(ch)


def sub_options(interval_ms=100, delay_ms=0, skip_self=True, **kw):
    return control_pb2.ChannelSubscriptionOptions(
        fanOutIntervalMs=interval_ms, fanOutDelayMs=delay_ms,
        skipSelfUpdateFanOut=skip_self, **kw)


# ---- the scripts ------------------------------------------------------------
# (ms, what, ...): "sub" conn options | "unsub" conn | "update" sender text
# | "step" (a device step) | "level" n (the ladder) | "stall" until_ms (no
# pass reaches the channel: the loop is in other channels' ticks, while
# messages are enqueued and the device steps; the reference is not stalled).
# Bursts lie 0.3-5 s apart. Subscriptions are made so that their first
# window closes on a tick of the reference; updates keep clear of the
# window boundaries, where a microsecond decides the window.


def _bursts(starts, sender=9, per=4, gap=37.0):
    """``per`` updates ``gap`` ms apart from each start."""
    out = []
    for b, start in enumerate(starts):
        for i in range(per):
            out.append((start + 3.3 + i * gap, "update", sender, f"u{b}.{i}"))
    return out


def _device_steps(until_ms, every=50):
    return [(float(t), "step") for t in range(every, until_ms, every)]


STARTS = (130, 460, 1_810, 6_840, 7_190)  # gaps of 0.3, 1.3, 5.0, 0.3 s

SCRIPTS = {
    "host_checked": dict(
        script=[(0, "sub", 1, sub_options(100)),
                (0, "sub", 2, sub_options(150)),
                *_bursts(STARTS)],
        end=7_700),
    "device_marked": dict(
        channel_type=ChannelType.SPATIAL, device=True,
        script=[(0, "sub", 1, sub_options(100)),
                (0, "sub", 2, sub_options(150)),
                *_device_steps(7_700), *_bursts(STARTS)],
        end=7_700),
    "skip_self": dict(
        script=[(0, "sub", 1, sub_options(100)),
                (0, "sub", 2, sub_options(100, skip_self=False)),
                *_bursts(STARTS, sender=1), *_bursts((700, 3_300), sender=2)],
        end=7_700),
    "first_fanout": dict(
        # A subscriber that comes in the middle of a burst, one that
        # comes into an idle channel, with and without a delay; one
        # leaves and comes back.
        script=[(0, "sub", 1, sub_options(100)),
                *_bursts(STARTS),
                (200, "sub", 2, sub_options(100)),
                (1_000, "sub", 3, sub_options(100, delay_ms=50)),
                (1_900, "unsub", 2),
                (3_000, "sub", 2, sub_options(150)),
                (6_700, "sub", 4, sub_options(50))],
        end=7_700),
    "eviction_resync": dict(
        # The ladder at L2 withholds a slow observer's updates while
        # more than a ring of them arrives: it is owed the whole state.
        script=[(0, "level", 2),
                (0, "sub", 1, sub_options(50)),
                (0, "sub", 2, sub_options(100)),
                *[(401.3 + i * 3.1, "update", 9, f"e{i}") for i in range(700)],
                (3_000, "level", 0),
                *_bursts((3_400, 6_840))],
        default_interval=50, end=7_400, resyncs={(2, "e699")}),
    "ladder_stretch_l1": dict(
        script=[(0, "level", 1),
                (0, "sub", 1, sub_options(100)),
                (0, "sub", 2, sub_options(50)),
                *_bursts(STARTS, gap=61.0)],
        stretch=2.0, end=7_800),
    "coalesced_due_marks": dict(
        # An update in every window, and this channel not reached while
        # the device marks two of them: one entry in the table, two
        # windows owed, and more closing behind them.
        channel_type=ChannelType.SPATIAL, device=True,
        script=[(0, "sub", 1, sub_options(100)),
                *_device_steps(4_000),
                (630, "update", 9, "a"), (730, "update", 9, "b"),
                (760, "stall", 960),
                (830, "update", 9, "c"), (930, "update", 9, "d"),
                (1_030, "update", 9, "e"), (1_130, "update", 9, "f"),
                (1_230, "update", 9, "g"), (2_730, "update", 9, "h")],
        end=4_000, stalled=True, deliveries=9, skipped=15),
}


def _settings(ctype, **kw):
    from channeld_tpu.core.settings import ChannelSettings

    global_settings.channel_settings[ctype] = ChannelSettings(
        tick_interval_ms=TICK_MS, **kw)


def _world(clock, case):
    ctype = case.get("channel_type", ChannelType.TEST)
    _settings(ctype, default_fanout_interval_ms=case.get(
        "default_interval", 100))
    if "stretch" in case:
        global_settings.overload_l1_stretch = case["stretch"]
    device = None
    if case.get("device"):
        device = StubDevice(clock)
        set_spatial_controller(device)
    owner = Peer(9, clock, ConnectionType.SERVER)
    ch = create_channel(ctype, owner)
    ch.init_data(testdata_pb2.TestChannelDataMessage(text="state"), None)
    assert ch.tick_interval == TICK_MS / 1e3
    return ch, device, {9: owner}


def _update(ch, text, sender=9, handled=None, clock=None):
    """An update enqueued now; ``handled`` takes (merged at, enqueued
    at) on the clock's scale."""
    msg = testdata_pb2.TestChannelDataMessage(text=text)
    arrival = ch.get_time()  # stamped at the enqueue
    enqueued = clock.ms() if clock is not None else None

    def merge(c):
        if handled is not None:
            handled.append((clock.ms(), enqueued))
        c.data.on_update(msg, arrival, sender, None, now_ns=c.get_time())

    ch.execute(merge)


def _apply(ch, device, peers, clock, event):
    what = event[1]
    if what == "sub":
        peer = peers.setdefault(event[2], Peer(event[2], clock))
        subscribe_to_channel(peer, ch, event[3])
    elif what == "unsub":
        unsubscribe_from_channel(peers[event[2]], ch)
    elif what == "update":
        _update(ch, event[3], event[2])
    elif what == "step":
        device.step()
    elif what == "level":
        governor.level = event[2]
    else:
        raise AssertionError(what)


def _reference(clock, case):
    """``tick_once()`` every interval, on time, as before the scheduler."""
    ch, device, peers = _world(clock, case)
    events = sorted(case["script"], key=lambda e: e[0])
    i = 0
    for t in range(0, case["end"] + 1, TICK_MS):
        while i < len(events) and events[i][0] <= t:
            if events[i][1] != "stall":
                clock.set_ms(events[i][0])
                _apply(ch, device, peers, clock, events[i])
            i += 1
        clock.set_ms(t)
        ch.tick_once(ch.get_time())
    return {cid: p.got for cid, p in peers.items()}, ch.tick_frames


def _next_ms(clock) -> float:
    """The instant of the scheduler's earliest work, on the script's
    scale, in the clock's nanoseconds (a timer aims a microsecond past
    its window's close, so the nearest will do)."""
    at = scheduler.next_at()
    if at == math.inf:
        return at
    ns = round(at * 1e9)
    return (ns + (ns * 1e-9 < at) - clock.t0) / MS


async def _scheduled(clock, case):
    """The same script through the scheduler: it runs whenever it has
    work that is ready, and at no other time."""
    ch, device, peers = _world(clock, case)
    events = sorted(case["script"], key=lambda e: e[0])
    events.append((case["end"], "end"))
    stalled_until = 0.0
    for event in events:
        while True:
            # What falls on one instant goes in the reference's order:
            # the event, then the tick.
            at_ms = max(_next_ms(clock), stalled_until)
            if at_ms >= event[0]:
                break
            clock.set_ms(at_ms)
            await scheduler.run_due()
        clock.set_ms(event[0])
        if event[1] == "stall":
            stalled_until = event[2]
        elif event[1] != "end":
            _apply(ch, device, peers, clock, event)
    return {cid: p.got for cid, p in peers.items()}, ch.tick_frames


def _own_closes(case, cid, got):
    """The close of each delivery's OWN window, from the script and not
    from any tick's instant: a subscription's first window closes its
    delay and one interval after it was made; the lattice starts where
    the first fan-out (or a resync) was served and steps by the
    interval, times the ladder's stretch in force; a delivery's window
    is the one on that lattice that holds the newest update it carries.
    None for a resync, which closes no window."""
    events = sorted(case["script"], key=lambda e: e[0])
    arrivals = [(e[0], e[3]) for e in events if e[1] == "update"]
    subs = [(e[0], e[3]) for e in events if e[1] == "sub" and e[2] == cid]
    levels = [(e[0], e[2]) for e in events if e[1] == "level"]
    stretches = (1.0, global_settings.overload_l1_stretch,
                 global_settings.overload_l2_stretch)
    closes, last, anchored_by = [], None, None
    for t, text in got:
        sub_ms, options = [s for s in subs if s[0] <= t][-1]
        level = ([lv for ms, lv in levels if ms <= t] or [0])[-1]
        interval = options.fanOutIntervalMs * stretches[min(level, 2)]
        if anchored_by != sub_ms:  # its first fan-out: the whole state
            anchored_by, last = sub_ms, t
            closes.append(sub_ms + options.fanOutDelayMs + interval)
        elif (cid, text) in case.get("resyncs", ()):
            last = t
            closes.append(None)
        else:
            arrived = [ms for ms, sent in arrivals if sent == text and ms <= t]
            last += math.ceil((arrived[-1] - last) / interval) * interval
            closes.append(last)
    return closes


def _tick_bound(case, deliveries):
    """The ticks the script has work for: one for each message the
    pacing lets through (the first at once, the next no sooner than an
    interval after it, and so on) and one for each fan-out."""
    ticks, due = 0, -math.inf
    for ms in sorted(e[0] for e in case["script"] if e[1] == "update"):
        if ms > due:
            due = max(ms, due + TICK_MS)
            ticks += 1
    return ticks + deliveries


@pytest.mark.parametrize("name", list(SCRIPTS))
def test_the_scheduler_sends_what_a_tick_every_interval_sends(clock, name):
    case = SCRIPTS[name]
    ctype = case.get("channel_type", ChannelType.TEST)
    want, ticks_before = _reference(clock, case)
    assert data_mod.window_lag_ns[ctype][1] > 0
    fresh_runtime()  # the wait counters start from 0 again
    clock.t0 = clock.ns = clock.ns + 10_000 * MS
    got, ticks_after = asyncio.run(_scheduled(clock, case))
    deliveries = sum(len(v) for v in want.values())
    assert deliveries >= case.get("deliveries", 12)
    for cid in want:
        # The same messages, in the same order ...
        assert [text for _, text in got[cid]] == \
            [text for _, text in want[cid]], f"conn {cid}"
        closes = _own_closes(case, cid, got[cid])
        for (t_got, text), (t_want, _), close in zip(
                got[cid], want[cid], closes):
            # ... none before its own window's close, and none later
            # than the reference, whose tick falls ON the close, sends
            # it (a timer aims a microsecond past its instant): the
            # pacing holds no window back. Or later by the stall.
            assert close is None or t_got >= close - 1e-6, (cid, text)
            late = 250.0 if case.get("stalled") else 0.01
            assert t_got <= t_want + late, (cid, text)
    # For work, not for time: no tick but for a message the pacing let
    # through or for a fan-out, and far fewer than one an interval.
    assert ticks_after <= _tick_bound(case, deliveries)
    assert ticks_after < ticks_before / 2
    lag = data_mod.window_lag_ns[ctype]
    if case.get("stalled"):
        # The host's window did not stay behind the device's, though
        # no window since was empty: two ticks after the stall every
        # service is at its window's close again.
        tail = {t: g for g, t in got[1]}
        ref = {t: w for w, t in want[1]}
        for text in "efgh":
            assert tail[text] - ref[text] <= 0.01, text
    elif name != "eviction_resync":
        # Served at the close: the mean lag is a timer's slack, where
        # the pacing of every tick left it a third of an interval.
        assert lag[1] and lag[0] / lag[1] <= 0.01 * MS
    # The idle gaps cost subtractions.
    assert data_mod.windows_skipped[ctype] > case.get("skipped", 30)


# ---- the duties a tick carries besides messages -----------------------------


async def _drain(clock, until_ms, limit=10_000):
    """Run the scheduler until it has no work ready by ``until_ms``."""
    ticks = 0
    while ticks < limit:
        at_ms = _next_ms(clock)
        if at_ms > until_ms:
            break
        clock.set_ms(at_ms)
        ticks += await scheduler.run_due()
    clock.set_ms(until_ms)
    return ticks


def _idle(ch) -> bool:
    """No task, no timer, no entry: nothing of the scheduler's names it
    (a heap entry without its ``_work`` entry is dead, and dropped)."""
    return (ch not in scheduler._work and ch._tick_task is None
            and ch._wake is None)


def _channel(ctype=ChannelType.SUBWORLD, owner=None, **kw):
    _settings(ctype, **kw)
    ch = create_channel(ctype, owner)
    ch.init_data(testdata_pb2.TestChannelDataMessage(text="state"), None)
    return ch


async def _duty_backpressure(clock):
    """Backpressure set by a full queue is lifted, and the paused
    connection reads again, with no further message."""
    ch = _channel()
    conn = Peer(1, clock)
    accepted = 0
    while ch.put_message(
            control_pb2.CreateChannelMessage(), lambda ctx: None, conn,
            _pack(ch), external=True):
        accepted += 1
    assert accepted == channel_mod.QUEUE_CAPACITY
    assert channel_mod.connection_congested(conn)
    waiter = asyncio.ensure_future(channel_mod.congestion_wait(conn))
    await asyncio.sleep(0)
    assert not waiter.done()
    await _drain(clock, 200)
    assert not channel_mod.connection_congested(conn)
    await asyncio.wait_for(waiter, 1.0)
    assert not ch.in_msg_queue and _idle(ch)
    # The chaos layer's kind: reported full with nothing queued.
    channel_mod._congested_channels.add(ch.id)
    ch._mark_congested(channel_mod._QueuedMessage(None, None))
    assert await _drain(clock, 400) == 1
    assert not channel_mod.is_congested() and _idle(ch)


async def _duty_prune(clock):
    """A closed subscriber is pruned, and an owner's loss announced,
    once ``close_epoch`` has moved."""
    owner = Peer(1, clock, ConnectionType.SERVER)
    ch = _channel(owner=owner, send_owner_lost_and_recovered=True)
    watcher, leaver = Peer(2, clock), Peer(3, clock)
    for peer in (owner, watcher, leaver):
        subscribe_to_channel(peer, ch, sub_options(100))
    await _drain(clock, 300)
    assert _idle(ch)
    leaver.close()

    class Handle:  # a recoverable server's: its loss is announced
        new_conn = None

        def is_timed_out(self):
            return False

    owner.recover_handle = Handle()
    owner.close()
    connection_mod.close_epoch += 1  # what Connection.close() does,
    scheduler.wake()  # and the pass it starts finds who to visit
    assert await scheduler.run_due() + await _drain(clock, 400) >= 1
    assert set(ch.subscribed_connections) == {watcher}
    assert ch.get_owner() is None
    assert [c.msg_type for c in watcher.sent].count(
        channel_mod.MessageType.CHANNEL_OWNER_LOST) == 1
    assert owner.pit in ch.recoverable_subs


async def _duty_recoverable(clock):
    """A recoverable subscription is looked at every interval until it
    expires, and the channel is idle after."""
    ch = _channel()

    class Handle:
        new_conn = None
        expired = False

        def is_timed_out(self):
            return self.expired

    handle = Handle()
    from channeld_tpu.core import connection_recovery as recovery_mod

    looked = []
    real = recovery_mod.tick_recoverable_subscriptions

    def looking(channel):
        looked.append(clock.ms())
        if handle.expired:
            channel.recoverable_subs.clear()  # what the expiry path ends in
        else:
            real(channel)

    recovery_mod.tick_recoverable_subscriptions = looking
    try:
        ch.recoverable_subs["pit"] = recovery_mod.RecoverableSubscription(
            conn_handle=handle, is_owner=False, old_sub_time=0.0,
            old_sub_options=sub_options(100))
        ch.execute(lambda c: None)  # one message; the rest is the duty
        await _drain(clock, 500)
        assert len(looked) >= 9  # every interval, with no message
        handle.expired = True
        await _drain(clock, 700)
        assert not ch.recoverable_subs and _idle(ch)
        n = len(looked)
        await _drain(clock, 2_000)
        assert len(looked) == n
    finally:
        recovery_mod.tick_recoverable_subscriptions = real


async def _duty_removed(clock):
    """A removed channel is never visited again and leaves no timer."""
    ch = _channel()
    peer = Peer(1, clock)
    subscribe_to_channel(peer, ch, sub_options(100))
    ch.execute(lambda c: None)
    assert not _idle(ch)
    frames = ch.tick_frames
    remove_channel(ch)
    assert _idle(ch) and ch not in scheduler._last
    ch.execute(lambda c: None)  # to a dying channel: vanishes
    assert await _drain(clock, 1_000) == 0
    assert ch.tick_frames == frames
    # Removed between two of its visits, with a timer armed.
    ch2 = _channel()
    subscribe_to_channel(peer, ch2, sub_options(100))
    ch2.removing = True  # _remove_channel_after_owner_removed's half
    assert await _drain(clock, 2_000) == 0
    assert ch2.tick_frames == 0 and _idle(ch2)


async def _duty_pacing(clock):
    """A stream of messages ticks a channel at most once an interval; an
    idle channel is ticked on the arrival."""
    ch = _channel()
    handled = []
    clock.set_ms(1_000)
    for i in range(400):  # a message every 2.5 ms for a second
        clock.set_ms(1_000 + i * 2.5)
        ch.execute(lambda c, i=i: handled.append((clock.ms(), i)))
        await _drain(clock, clock.ms())
    await _drain(clock, 2_100)
    assert [i for _, i in handled] == list(range(400))
    starts = sorted({t for t, _ in handled})
    assert len(starts) == ch.tick_frames <= 1_000 // TICK_MS + 1
    assert min(b - a for a, b in zip(starts, starts[1:])) >= TICK_MS
    # No message handled later than a tick every interval would have.
    assert all(t - (1_000 + i * 2.5) <= TICK_MS + 1e-3 for t, i in handled)
    # Idle for longer than its interval: within the pass the arrival
    # starts, at the arrival's own instant.
    clock.set_ms(5_000)
    ch.execute(lambda c: handled.append((clock.ms(), "idle")))
    assert await scheduler.run_due() == 1
    assert handled[-1] == (5_000, "idle")
    assert _idle(ch)


async def _three_lattices(clock, offsets):
    """A channel and three subscribers of 100 ms made ``offsets`` ms
    into it: each one's lattice starts at its first fan-out, one
    interval after it was made."""
    ch = _channel()
    peers = [Peer(i, clock) for i in (1, 2, 3)]
    for peer, at in zip(peers, offsets):
        clock.set_ms(at)
        subscribe_to_channel(peer, ch, sub_options(100))
    assert await _drain(clock, 900) == 3 and _idle(ch)
    for peer, at in zip(peers, offsets):
        assert [text for _, text in peer.got] == ["state"]
        assert 0 < peer.got[0][0] - (at + 100) < 0.01
    return ch, peers


async def _duty_each_close(clock):
    """Three subscribers on lattices of their own and one update, merged
    by a tick 10 ms before the first of their closes: each is served at
    its own close, by a tick sooner than an interval after the one
    before it, and the channel is idle after the last."""
    ch, peers = await _three_lattices(clock, (0, 30, 60))
    clock.set_ms(1_020)
    _update(ch, "x")
    frames = ch.tick_frames
    assert await scheduler.run_due() == 1  # merged on the arrival
    assert all(len(peer.got) == 1 for peer in peers)
    assert await _drain(clock, 1_029) == 0
    served = []
    while not _idle(ch):
        at = _next_ms(clock)
        clock.set_ms(at)
        assert await scheduler.run_due() == 1
        served.append((at, [len(peer.got) for peer in peers]))
    # Sorted by close: the second subscriber's, the third's, the first's.
    assert [n for _, n in served] == [[1, 2, 1], [1, 2, 2], [2, 2, 2]]
    for (at, _), close in zip(served, (1_030, 1_060, 1_100)):
        assert 0 < at - close < 0.01
    assert all(peer.got[-1][1] == "x" for peer in peers)
    assert ch.tick_frames == frames + 4
    assert await _drain(clock, 3_000) == 0
    # The six window ticks (three first fan-outs), five of them sooner
    # than an interval after the channel's last tick, served the three
    # subscriptions past their first fan-out one a tick.
    assert scheduler.window_ticks[ChannelType.SUBWORLD] == [6, 5, 3]
    assert scheduler.ticks == {(ChannelType.SUBWORLD, "message"): 1,
                               (ChannelType.SUBWORLD, "window"): 6}


async def _duty_stream_and_closes(clock):
    """A stream of updates and the closes it makes, together: every
    window is served at its close, and the messages add at most one
    tick an interval to those: a tick that merges is a close's, or comes
    an interval or more after the merge before it."""
    ch, peers = await _three_lattices(clock, (0, 10, 20))
    handled = []
    frames = ch.tick_frames
    for i in range(400):  # an update every 2.5 ms for a second
        await _drain(clock, 1_001.3 + i * 2.5)  # each pass at its instant
        _update(ch, f"m{i}", handled=handled, clock=clock)
    await _drain(clock, 2_300)
    assert _idle(ch) and len(handled) == 400
    closes = set()
    for peer, offset in zip(peers, (0, 10, 20)):
        times = [t for t, _ in peer.got[1:]]
        # Every window from the stream's first update to its last, each
        # at its close on the subscriber's own lattice.
        assert len(times) == 10 + (offset > 0)
        for k, t in enumerate(times):
            assert 0 < t - (1_000 + offset + 100 * (k + (offset == 0))) \
                < 0.01
        closes.update(times)
        assert peer.got[-1][1] == "m399"
    merges = sorted({t for t, _ in handled})
    own = [t for t in merges if t not in closes]  # the messages' own ticks
    assert own and closes & set(merges)
    for t in own:
        before = [m for m in merges if m < t]
        assert not before or t - before[-1] >= TICK_MS
    assert ch.tick_frames - frames <= len(closes) + 1_000 // TICK_MS + 1
    # No update merged later than a tick every interval would have.
    assert all(t - arrival <= TICK_MS + 1e-3 for t, arrival in handled)


async def _duty_fairness(clock):
    """A pass over 2,000 ready channels never holds the loop over 5 ms
    between yields, and a task awaiting a finished future (GLOBAL's,
    awaiting the device step) resumes inside that bound. Real clock;
    the holds are read in the loop thread's own CPU time, which another
    process on the machine cannot lengthen."""
    scheduler.reset()
    channel_mod.time = time  # this duty is about real milliseconds
    channels = [_channel() for _ in range(2_000)]
    for ch in channels:
        ch.execute(lambda c: sum(range(2_000)))  # a tick of some cost
    loop = asyncio.get_running_loop()
    gaps, last = [], [None]

    def beat():
        now = time.thread_time()
        if last[0] is not None:
            gaps.append(now - last[0])
        last[0] = now
        if not done.is_set():
            loop.call_soon(beat)

    done = asyncio.Event()
    step = loop.create_future()
    resumed = []

    async def global_task():
        await step
        resumed.append(time.thread_time())

    waiter = asyncio.ensure_future(global_task())
    finished = []
    # The worker finishes the step a little into the pass.
    loop.call_later(0.004, lambda: (
        finished.append(time.thread_time()), step.set_result(None)))
    loop.call_soon(beat)
    frozen = gc.isenabled()
    gc.disable()  # a collection is not the scheduler's hold
    try:
        ticks = 0
        end = time.monotonic() + 10.0
        while ticks < 2_000 and time.monotonic() < end:
            ticks += await scheduler.run_due()
            await asyncio.sleep(0)
        await waiter
    finally:
        if frozen:
            gc.enable()
        done.set()
    assert ticks == 2_000 and all(ch.tick_frames == 1 for ch in channels)
    assert len(gaps) > 10  # the pass took many slices
    assert max(gaps) < 0.005, max(gaps)
    assert resumed[0] - finished[0] < 0.005


async def _duty_no_work_no_cost(clock):
    """A channel with no work has no task and no timer: count them."""
    start = type(scheduler).start.__get__(scheduler)  # the real one
    start()
    tasks0 = len(asyncio.all_tasks())
    idle = [_channel() for _ in range(300)]
    peer = Peer(1, clock)
    for ch in idle[:100]:
        subscribe_to_channel(peer, ch, sub_options(100))
    scheduler.stop()  # driven by hand below, on the synthetic clock
    await _drain(clock, 1_000)  # the first fan-outs
    assert len(peer.got) == 100
    assert all(_idle(ch) for ch in idle)
    assert scheduler.next_at() == float("inf") and not scheduler._work
    assert len(asyncio.all_tasks()) <= tasks0
    # The real task, asleep: one task, and no timer while nothing is due.
    start()
    await asyncio.sleep(0)
    await asyncio.sleep(0)
    assert len(asyncio.all_tasks()) == tasks0
    assert scheduler._timer is None
    loop = asyncio.get_running_loop()
    assert not [h for h in loop._scheduled if not h.cancelled()]
    # An update makes one entry and one timer for the channel it is
    # for, and none for the other 299.
    idle[0].execute(lambda c: c.data.on_update(
        testdata_pb2.TestChannelDataMessage(text="x"), c.get_time(), 9))
    assert set(scheduler._work) == {idle[0]}
    scheduler.stop()
    await _drain(clock, 2_000)
    assert [text for _, text in peer.got][-1] == "x"
    assert not scheduler._work and all(_idle(ch) for ch in idle)
    assert scheduler.ticks[(ChannelType.SUBWORLD, "message")] == 1
    assert scheduler.ticks[(ChannelType.SUBWORLD, "window")] == 101


def _pack(ch):
    from channeld_tpu.protocol import wire_pb2

    return wire_pb2.MessagePack(channelId=ch.id, msgType=100)


DUTIES = {
    "backpressure_is_lifted": _duty_backpressure,
    "a_closed_subscriber_is_pruned": _duty_prune,
    "a_recoverable_subscription_expires": _duty_recoverable,
    "a_removed_channel_is_never_visited": _duty_removed,
    "at_most_one_tick_an_interval_and_at_once_when_idle": _duty_pacing,
    "each_window_is_served_at_its_own_close": _duty_each_close,
    "a_message_stream_is_paced_and_its_closes_are_on_time":
        _duty_stream_and_closes,
    "a_pass_yields_every_2ms": _duty_fairness,
    "no_work_no_task_no_timer": _duty_no_work_no_cost,
}


@pytest.mark.parametrize("duty", list(DUTIES))
def test_a_duty_of_the_tick_is_kept(clock, duty):
    async def scenario():
        scheduler.stop()  # each duty drives the passes itself
        await DUTIES[duty](clock)

    asyncio.run(scenario())


def test_the_counters_reach_metrics_with_the_global_tick(clock):
    """``channel_ticks{cause}``, ``fanout_windows_skipped``,
    ``window_ticks_early`` and ``window_tick_subscriptions`` ride the
    GLOBAL tick to /metrics like the wait counters."""
    from channeld_tpu.core import metrics

    def read():
        served = metrics.window_tick_subscriptions.labels(channel_type="TEST")
        return [
            metrics.channel_ticks.labels(
                channel_type="TEST", cause="message")._value.get(),
            metrics.channel_ticks.labels(
                channel_type="TEST", cause="window")._value.get(),
            metrics.fanout_windows_skipped.labels(
                channel_type="TEST")._value.get(),
            metrics.window_ticks_early.labels(
                channel_type="TEST")._value.get(),
            served._sum.get(), served._count.get()]

    before = read()
    asyncio.run(_scheduled(clock, SCRIPTS["host_checked"]))
    window_ticks, early, served = scheduler.window_ticks[ChannelType.TEST]
    # Two subscribers, one tick a close: each window tick served one or
    # both, and most came within an interval of the update's own tick.
    assert window_ticks <= served <= 2 * window_ticks
    assert window_ticks / 2 < early < window_ticks
    channel_mod.get_global_channel().tick_once()
    messages, windows, skipped, early_, served_, window_ticks_ = [
        b - a for a, b in zip(before, read())]
    assert messages >= len(STARTS)
    # A window tick that found a message counts as the message's.
    assert len(STARTS) <= windows <= window_ticks
    assert skipped > 30
    assert (early_, served_, window_ticks_) == (early, served, window_ticks)
    assert not scheduler.ticks
    assert scheduler.window_ticks[ChannelType.TEST] == [0, 0, 0]
