"""Micro-benchmarks mirroring the reference's committed benchmark results
(ref: BASELINE.md): MessagePack marshal ns/op, frame encode/decode, merge
throughput, and handover churn. Prints one JSON line per benchmark.

Reference numbers for comparison (Go, dev boxes):
  - MessagePack marshal: 127.8 ns/op (message_test.go:137)
  - 1000-client handover sub/unsub churn: 12.67 ms/op = ~79K handovers/s
    (subscription_test.go:89)
"""

import json
import time

import numpy as np


def bench(name, fn, reps, unit="ns/op", reference=None):
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    per_op = (time.perf_counter() - t0) / reps * 1e9
    row = {"metric": name, "value": round(per_op, 1), "unit": unit}
    if reference is not None:
        row["reference_go"] = reference
    print(json.dumps(row), flush=True)


def main():
    from channeld_tpu.protocol import encode_frame, wire_pb2, FrameDecoder
    from channeld_tpu.models import sim_pb2
    import channeld_tpu.models.sim  # attaches custom merges

    body = sim_pb2.SimEntityChannelData()
    body.state.entityId = 1234
    body.state.transform.position.x = 1.5
    payload = body.SerializeToString()

    mp = wire_pb2.MessagePack(channelId=1, msgType=8, msgBody=payload)

    # MessagePack marshal (ref: 127.8 ns/op in Go).
    bench("messagepack_marshal", mp.SerializeToString, 200_000,
          reference=127.8)

    # Frame encode/decode through the native codec.
    packet = wire_pb2.Packet(messages=[mp])
    pbody = packet.SerializeToString()
    bench("frame_encode_native", lambda: encode_frame(pbody, 0), 200_000)
    frame = encode_frame(pbody, 0)
    dec = FrameDecoder()
    bench("frame_decode_native", lambda: dec.feed(frame), 200_000)

    # Reflection merge vs custom merge (ref: tpspb BenchmarkMerge1/2).
    from channeld_tpu.core.data import reflect_merge

    dst = sim_pb2.SimSpatialChannelData()
    for i in range(100):
        dst.entities[i].entityId = i
    src = sim_pb2.SimSpatialChannelData()
    src.entities[5].transform.position.x = 9.0
    bench("reflect_merge_100_entities", lambda: reflect_merge(dst, src, None),
          20_000)
    bench("custom_merge_100_entities", lambda: dst.merge(src, None, None),
          20_000)

    # Handover churn: device detection + compaction of 1000 simultaneous
    # crossings (the decision part of the reference's 12.67 ms/op
    # 1000-client churn; sub/unsub bookkeeping happens on due entities only).
    import jax
    import jax.numpy as jnp

    from channeld_tpu.ops.spatial_ops import GridSpec, spatial_step, QuerySet

    grid = GridSpec(-15000.0, -15000.0, 2000.0, 2000.0, 15, 15)
    n = 1000
    rng = np.random.default_rng(0)
    prev = jnp.zeros(n, jnp.int32)
    pos = jnp.asarray(
        np.stack([rng.uniform(-12000, 14000, n), np.zeros(n),
                  rng.uniform(-12000, 14000, n)], axis=1).astype(np.float32)
    )
    queries = QuerySet(jnp.zeros(4, jnp.int32), jnp.zeros((4, 2), jnp.float32),
                       jnp.zeros((4, 2), jnp.float32),
                       jnp.ones((4, 2), jnp.float32), jnp.zeros(4, jnp.float32))
    subs = (jnp.zeros(n, jnp.int32), jnp.full(n, 50, jnp.int32),
            jnp.ones(n, bool))

    from collections import deque

    def dispatch():
        out = spatial_step(grid, pos, jnp.zeros(n, jnp.int32),
                           jnp.ones(n, bool), queries, subs, 1024,
                           jnp.int32(100))
        out["consume"].copy_to_host_async()
        return out

    jax.block_until_ready(dispatch()["consume"])
    reps = 60
    inflight = deque()
    t0 = time.perf_counter()
    for _ in range(reps):
        inflight.append(dispatch())
        if len(inflight) > 16:
            np.asarray(inflight.popleft()["consume"])
    while inflight:
        np.asarray(inflight.popleft()["consume"])
    ms_op = (time.perf_counter() - t0) / reps * 1000
    print(json.dumps({
        "metric": "handover_churn_1000_entities",
        "value": round(ms_op, 2), "unit": "ms/op (pipelined decision pass)",
        "reference_go": 12.67,
    }), flush=True)


def bench_fanout_decision():
    """Per-tick fan-out decision cost: host scan (every subscriber gets a
    time check, ref data.go:175-291) vs device due-mask consumption (only
    due subscribers are visited). The device cost is flat in subscriber
    count."""
    from channeld_tpu.core.channel import Channel
    from channeld_tpu.core.data import FanOutConnection, ChannelData, tick_data
    from channeld_tpu.core.subscription import ChannelSubscription
    from channeld_tpu.core.types import ChannelType
    from channeld_tpu.models import sim_pb2
    from channeld_tpu.protocol import control_pb2
    from channeld_tpu.spatial import controller as ctl_mod

    class _Conn:
        __slots__ = ("id",)

        def __init__(self, cid):
            self.id = cid

        def is_closing(self):
            return False

        def send(self, ctx):
            pass

    class _FakeDeviceCtl:
        """Publishes a pending due queue, like TPUSpatialController."""

        def __init__(self):
            self.seq = 0
            self.due = frozenset()
            self.pending = {}

        def publish(self):
            self.seq += 1
            for slot in self.due:
                self.pending[slot] = self.seq

        def device_due(self, channel_id):
            return (self.seq, self.pending) if self.seq else None

        def device_sub_first_fanout(self, slot):
            pass

    DUE = 128  # due subscribers per tick, independent of S
    for n_subs in (1_000, 10_000, 50_000):
        ch = Channel(0x10000 + 1, ChannelType.SPATIAL)
        ch.data = ChannelData(sim_pb2.SimSpatialChannelData())
        far_future = 1 << 60
        for i in range(n_subs):
            conn = _Conn(i + 10)
            foc = FanOutConnection(conn=conn, had_first_fanout=True,
                                   last_fanout_time=far_future,
                                   device_sub_slot=i)
            ch.fan_out_queue.append(foc)
            ch.device_sub_slots[i] = foc
            ch.subscribed_connections[conn] = ChannelSubscription(
                options=control_pb2.ChannelSubscriptionOptions(
                    dataAccess=2, fanOutIntervalMs=50),
                sub_time=0, fanout_conn=foc,
            )

        # Host scan: no controller -> every subscriber time-checked.
        prev_ctl = ctl_mod.get_spatial_controller()
        ctl_mod.set_spatial_controller(None)
        reps = max(3, 300_000 // n_subs)
        t0 = time.perf_counter()
        for _ in range(reps):
            tick_data(ch, now=0)
        host_us = (time.perf_counter() - t0) / reps * 1e6

        # Device mask: only the DUE slots are visited.
        fake = _FakeDeviceCtl()
        fake.due = frozenset(range(0, n_subs, max(1, n_subs // DUE)))
        ctl_mod.set_spatial_controller(fake)
        t0 = time.perf_counter()
        for rep in range(reps):
            fake.publish()  # fresh decisions each engine tick
            tick_data(ch, now=0)
        device_us = (time.perf_counter() - t0) / reps * 1e6
        ctl_mod.set_spatial_controller(prev_ctl)
        print(json.dumps({
            "metric": f"fanout_decision_{n_subs}_subs",
            "host_scan_us_per_tick": round(host_us, 1),
            "device_mask_us_per_tick": round(device_us, 1),
            "due_per_tick": len(fake.due),
            "speedup": round(host_us / device_us, 1),
        }), flush=True)


if __name__ == "__main__":
    main()
    bench_fanout_decision()
