"""Host handover-orchestration bench.

bench.py's step detects ~1,469 crossings per 33ms tick at its 100K load
(handovers_per_step in its output). This measures whether the HOST side —
owner swap, channel-data remove/add, handover fan-out
(ref: spatial.go:612-858) — keeps up with that detection rate, and by
how much, for both the per-crossing path (reference shape) and the
batched per-(src,dst)-pair path the TPU controller uses.

CPU-only (no chip needed): the orchestration under test is pure host
work. One JSON line out.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

CROSSINGS_PER_TICK = 1469
TICK_MS = 33.0
TICKS = 8


def build_world():
    from helpers import StubConnection, fresh_runtime
    from channeld_tpu.core.message import MessageContext
    from channeld_tpu.core.subscription import subscribe_to_channel
    from channeld_tpu.core.types import ConnectionType, MessageType
    from channeld_tpu.models.sim import register_sim_types
    from channeld_tpu.protocol import control_pb2
    from channeld_tpu.spatial.controller import set_spatial_controller
    from channeld_tpu.spatial.grid import StaticGrid2DSpatialController

    fresh_runtime()
    register_sim_types()
    ctl = StaticGrid2DSpatialController()
    # The benchmark world: 15x15 cells, 2000-unit cells, one server per
    # half (cross-server handovers are the expensive case).
    ctl.load_config(dict(
        WorldOffsetX=-15000, WorldOffsetZ=-15000, GridWidth=2000,
        GridHeight=2000, GridCols=15, GridRows=15, ServerCols=3,
        ServerRows=1, ServerInterestBorderSize=1,
    ))
    set_spatial_controller(ctl)
    servers = [StubConnection(i + 1, ConnectionType.SERVER)
               for i in range(3)]
    for server in servers:
        ctx = MessageContext(
            msg_type=MessageType.CREATE_CHANNEL,
            msg=control_pb2.CreateChannelMessage(),
            connection=server,
        )
        for ch in ctl.create_channels(ctx):
            subscribe_to_channel(server, ch, None)
    return ctl, servers


def seed_entities(ctl, n):
    """n entities on cell borders, alternating crossing direction."""
    from channeld_tpu.core.channel import create_entity_channel
    from channeld_tpu.models import sim_pb2
    from channeld_tpu.spatial.grid import SpatialInfo

    E = 0x80000
    moves = []
    for i in range(n):
        eid = E + 1 + i
        # Walk along x through the middle row; crossing col k -> k+1.
        col = i % 14
        x0 = -15000 + col * 2000 + 1990.0
        z = -15000 + 7 * 2000 + 1000.0
        d = sim_pb2.SimEntityChannelData()
        d.state.entityId = eid
        d.state.transform.position.x = x0
        d.state.transform.position.z = z
        ch = create_entity_channel(eid, None)
        ch.init_data(d)
        src = SpatialInfo(x0, 0, z)
        dst = SpatialInfo(x0 + 20.0, 0, z)
        # Register in the src spatial channel's data.
        src_ch_id = ctl.get_channel_id(src)
        from channeld_tpu.core.channel import get_channel

        sch = get_channel(src_ch_id)
        sch.get_data_message().add_entity(eid, d)
        moves.append((eid, src, dst))
    return moves


def main() -> None:
    out = {"metric": "handover_orchestration",
           "crossings_per_tick": CROSSINGS_PER_TICK,
           "detection_rate_per_sec": round(CROSSINGS_PER_TICK / (TICK_MS / 1e3))}

    # --- Sequential per-crossing orchestration (reference shape) ---------
    ctl, _ = build_world()
    moves = seed_entities(ctl, CROSSINGS_PER_TICK)
    t0 = time.perf_counter()
    for eid, src, dst in moves:
        ctl.notify(src, dst, lambda s, d, e=eid: e)
    seq_s = time.perf_counter() - t0
    out["sequential_ms_per_tick_batch"] = round(seq_s * 1000, 2)
    out["sequential_orchestrations_per_sec"] = round(CROSSINGS_PER_TICK / seq_s)
    out["sequential_us_per_handover"] = round(seq_s / CROSSINGS_PER_TICK * 1e6, 1)

    # --- Batched per-(src,dst) orchestration (TPU controller path) -------
    if hasattr(ctl, "notify_crossings"):
        from statistics import median

        samples = []
        for _ in range(TICKS):
            ctl, _ = build_world()  # fresh world per measured tick
            moves = seed_entities(ctl, CROSSINGS_PER_TICK)
            crossings = []
            for eid, src, dst in moves:
                crossings.append((src, dst, lambda s, d, e=eid: e))
            t0 = time.perf_counter()
            ctl.notify_crossings(crossings)
            samples.append(time.perf_counter() - t0)
        med = float(median(samples))
        out["batched_ms_per_tick_batch"] = round(med * 1000, 2)
        out["batched_orchestrations_per_sec"] = round(CROSSINGS_PER_TICK / med)
        out["batched_us_per_handover"] = round(med / CROSSINGS_PER_TICK * 1e6, 1)
        out["keeps_up_with_detection"] = med * 1000 <= TICK_MS

    print(json.dumps(out))


if __name__ == "__main__":
    main()
