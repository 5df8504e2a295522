"""Overload soak: chaos-driven saturation proving the degradation ladder.

Boots the same live gateway as ``scripts/chaos_soak.py`` (real TCP
listeners, the 1ms pump, the TPU spatial controller on the cells plane,
a master + 4 spatial servers, a client fleet, a seeded entity sim), then
drives a three-phase timeline:

1. **warmup** — normal load; the governor must sit at L0.
2. **saturation** — a chaos window opens (``start_at_s``/``stop_at_s``
   gates on heavy ``device.dispatch_stall`` + ``channel.tick_budget``
   stalls) while storms march crowds across cell boundaries: the GLOBAL
   tick budget collapses, pressure climbs, and the ladder must engage
   step by step (L0 -> L1 -> L2 [-> L3]). Low-priority observer clients
   see their updates shed; handover orchestration defers past its cap;
   at L3 reconnecting clients are refused with ServerBusyMessage.
3. **recovery** — the chaos window closes, storms stop, light load
   continues: the ladder must walk back to L0 within the configured
   deadline.

The invariant checker then asserts the PR's acceptance bar:

- the ladder reached at least L2 and every transition was exactly one
  step (monotonic engagement and release — no level skipping);
- once the post-window descent began, the ladder never rose again;
- GLOBAL tick p99 stayed bounded at EVERY level (per-level bounds,
  accumulated from histogram deltas attributed to the level that was
  active in each sampling window);
- zero entities lost (every sim entity still tracked and present in
  exactly one spatial channel's data);
- exact shed accounting: every ``overload_sheds_total{reason}`` sample
  equals the governor's python-side ledger, and the ServerBusyMessage
  frames clients observed never exceed the admission sheds counted;
- return to L0 within ``recover_deadline_s`` of the window closing.

Emits a ``SOAK_OVERLOAD_*.json`` artifact with the scenario, the level
timeline, per-level tick p99s, the governor report and the invariant
results.

Run the acceptance soak (~75s of timeline):
  python scripts/overload_soak.py --out SOAK_OVERLOAD_r07.json

The <60s CI smoke runs the same machinery with smaller numbers
(tests/test_overload.py::test_overload_smoke_soak).
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
if os.environ.get("CHTPU_SOAK_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"  # gateway children inherit it

import argparse
import asyncio
import importlib.util
import json
import time
from dataclasses import dataclass, field
from random import Random


def _load_chaos_soak():
    """The chaos soak module provides the world-boot / client / sim
    machinery this soak re-drives on a different timeline."""
    spec = importlib.util.spec_from_file_location(
        "chaos_soak", os.path.join(REPO, "scripts", "chaos_soak.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("chaos_soak", mod)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class OverloadSoakParams:
    warmup_s: float = 10.0
    saturation_s: float = 35.0
    recover_deadline_s: float = 15.0
    quiesce_s: float = 6.0
    clients: int = 16
    observers: int = 4  # low-priority (slow READ) spatial subscribers
    entities: int = 128
    msg_rate: float = 20.0
    storm_every_s: float = 6.0
    storm_size: int = 64
    handover_batch_cap: int = 4
    down_hold_s: float = 1.0
    # GLOBAL tick budget (ms); SPATIAL/ENTITY run at 2x. The CI smoke
    # doubles it so the L0 phases keep genuine headroom on a throttled
    # shared box (the ladder measures budget overrun, so the budget
    # must be honestly meetable at baseline load).
    global_tick_ms: int = 50
    # Per-level GLOBAL tick p99 bounds (seconds). The saturation stalls
    # are injected 60ms device + 12ms/message sleeps, so elevated levels
    # legitimately run slow ticks — bounded, not pretty. L0's bound
    # absorbs shared-CI-box noise and stray jit recompiles.
    tick_p99_bounds: tuple = (1.0, 1.5, 2.0, 2.0)
    config_path: str = os.path.join(REPO, "config", "spatial_tpu_cells_2x2.json")
    scenario: dict = field(default_factory=dict)
    out_path: str = ""
    entity_capacity: int = 256
    query_capacity: int = 32
    require_handover_defer: bool = True
    # The update_priority shed needs an observer to come DUE while the
    # ladder holds; with stretched intervals and a short window that is
    # timing-sensitive, so the CI smoke only requires sheds in general.
    require_update_priority: bool = True


def default_scenario(p: OverloadSoakParams) -> dict:
    """Saturation window gated by wall clock relative to arming (the
    timeline arms right as the traffic phase starts)."""
    t0 = p.warmup_s
    t1 = p.warmup_s + p.saturation_s
    return {
        "name": "overload-saturation",
        "seed": 20260803,
        "config_overrides": {"CellBucket": 6},
        "faults": [
            # The saturation driver: every device dispatch stalls ~1.8x
            # the GLOBAL tick budget -> utilization ~2, sustained for
            # the whole window, independent of traffic rate.
            {"point": "device.dispatch_stall", "every_n": 1,
             "stall_ms": round(p.global_tick_ms * 1.8),
             "start_at_s": t0, "stop_at_s": t1},
            # Message-path pressure: periodic handler stalls.
            {"point": "channel.tick_budget", "every_n": 6,
             "stall_ms": 12, "start_at_s": t0, "stop_at_s": t1},
            # Socket weather inside the window so clients reconnect INTO
            # the L3 admission gate and exercise ServerBusyMessage.
            {"point": "transport.reset", "every_n": 150,
             "start_at_s": t0 + 2.0, "stop_at_s": t1},
        ],
    }


async def run_overload_soak(p: OverloadSoakParams) -> dict:
    cs = _load_chaos_soak()

    from channeld_tpu.chaos import arm, chaos, disarm
    from channeld_tpu.chaos.invariants import (
        InvariantChecker,
        delta,
        histogram_quantile,
        sample_total,
        scrape,
    )
    from channeld_tpu.core import channel as channel_mod
    from channeld_tpu.core import connection as connection_mod
    from channeld_tpu.core import data as data_mod
    from channeld_tpu.core import ddos as ddos_mod
    from channeld_tpu.core import connection_recovery as recovery_mod
    from channeld_tpu.core.channel import all_channels, init_channels
    from channeld_tpu.core.connection import init_connections
    from channeld_tpu.core.ddos import init_anti_ddos, unauth_reaper_loop
    from channeld_tpu.core.overload import governor, reset_overload
    from channeld_tpu.federation import reset_federation
    from channeld_tpu.core.server import flush_loop, start_listening
    from channeld_tpu.core.settings import (
        ChannelSettings,
        global_settings,
        reset_global_settings,
    )
    from channeld_tpu.core.types import ChannelType, ConnectionType, MessageType
    from channeld_tpu.models.sim import register_sim_types
    from channeld_tpu.protocol import control_pb2
    from channeld_tpu.spatial.controller import (
        get_spatial_controller,
        init_spatial_controller,
        reset_spatial_controller,
    )

    t_start = time.monotonic()
    if not p.scenario:
        p.scenario = default_scenario(p)

    # -- fresh runtime (idempotent; the pytest smoke shares a process) --
    channel_mod.reset_channels()
    connection_mod.reset_connections()
    data_mod.reset_registries()
    ddos_mod.reset_ddos()
    recovery_mod.reset_recovery()
    reset_spatial_controller()
    reset_global_settings()
    reset_overload()

    global_settings.development = True
    # This soak proves the OVERLOAD ladder; the balancer never migrates
    # at L2+ anyway, but pinning it off keeps the saturation timeline
    # free of planned authority moves (scripts/balance_soak.py owns that).
    global_settings.balancer_enabled = False
    # Adaptive partitioning stays pinned OFF: this soak's envelope
    # assumes the static boot grid (doc/partitioning.md);
    # scripts/density_soak.py is the partitioning plane's own soak.
    global_settings.partition_enabled = False
    # Device guard pinned OFF (doc/device_recovery.md): this soak's
    # envelope is deterministic; the watchdog worker-thread hop and
    # any chaos-adjacent retry would perturb it. The device plane's
    # own soak is scripts/device_soak.py.
    global_settings.device_guard_enabled = False
    # SLO plane pinned OFF (doc/observability.md): this soak's
    # envelope predates the delivery-latency sampling; the health
    # plane has its own soak (scripts/obs_soak.py).
    global_settings.slo_enabled = False
    # Flight recorder pinned OFF (doc/observability.md): these soaks
    # prove deterministic accounting and timing envelopes; span
    # recording and anomaly auto-dumps must not perturb either
    # (scripts/trace_soak.py is the recorder's own soak).
    global_settings.trace_enabled = False
    from channeld_tpu.core.tracing import recorder as _flight_recorder

    _flight_recorder.configure(enabled=False)
    # Federation stays pinned OFF: a remote shard would route some
    # crossings over a trunk and break this soak's deterministic
    # single-gateway accounting (doc/federation.md).
    reset_federation()
    global_settings.federation_config = ""
    global_settings.tpu_entity_capacity = p.entity_capacity
    global_settings.tpu_query_capacity = p.query_capacity
    global_settings.overload_down_hold_s = p.down_hold_s
    global_settings.overload_handover_batch_cap = p.handover_batch_cap
    # Coarser cadences than the chaos soak: the overload soak measures
    # *budget overrun*, so the L0 phases must have genuine headroom on a
    # shared CPU box (the device step alone is ~10-20ms there).
    global_settings.channel_settings = {
        ChannelType.GLOBAL: ChannelSettings(
            tick_interval_ms=p.global_tick_ms,
            default_fanout_interval_ms=50),
        ChannelType.SPATIAL: ChannelSettings(
            tick_interval_ms=p.global_tick_ms * 2,
            default_fanout_interval_ms=100),
        ChannelType.ENTITY: ChannelSettings(
            tick_interval_ms=p.global_tick_ms * 2,
            default_fanout_interval_ms=100),
    }

    register_sim_types()
    init_connections(
        os.path.join(REPO, "config", "server_authoritative_fsm.json"),
        os.path.join(REPO, "config", "client_authoritative_fsm.json"),
    )
    init_channels()
    init_anti_ddos()

    with open(p.config_path) as f:
        spec = json.load(f)
    overrides = dict(p.scenario.get("config_overrides", {}))
    spec.setdefault("Config", {}).update(overrides)
    merged_path = os.path.join(
        "/tmp", f"overload_soak_spatial_{os.getpid()}.json"
    )
    with open(merged_path, "w") as f:
        json.dump(spec, f)
    init_spatial_controller(merged_path)
    ctl = get_spatial_controller()

    host = "127.0.0.1"
    server_srv = await start_listening(ConnectionType.SERVER, "tcp", f"{host}:0")
    server_port = server_srv.sockets[0].getsockname()[1]
    client_srv = await start_listening(ConnectionType.CLIENT, "tcp", f"{host}:0")
    client_port = client_srv.sockets[0].getsockname()[1]

    stop = asyncio.Event()
    send_stop = asyncio.Event()
    tasks = [
        asyncio.ensure_future(flush_loop()),
        asyncio.ensure_future(unauth_reaper_loop()),
    ]
    stats = cs.SoakStats()
    busy_seen = {"connection": 0}
    accounting = {"open": False}
    control_writers: list = []

    # -- per-level tick accounting (histogram deltas attributed to the
    # level active at each sampling window's start) --
    level_buckets: dict[int, dict[float, float]] = {}
    timeline: list[dict] = []

    def _tick_buckets(samples) -> dict[float, float]:
        out = {}
        for (name, labels), value in samples.items():
            if name != "channel_tick_duration_bucket":
                continue
            ld = dict(labels)
            if ld.get("channel_type") != "GLOBAL":
                continue
            le = ld.get("le")
            out[float("inf") if le == "+Inf" else float(le)] = value
        return out

    def _bucket_p99(buckets: dict[float, float]):
        if not buckets:
            return None
        items = sorted(buckets.items())
        total = items[-1][1]
        if total <= 0:
            return None
        target = 0.99 * total
        prev_le, prev_n = 0.0, 0.0
        for le, n in items:
            if n >= target:
                if le == float("inf"):
                    return prev_le
                span = n - prev_n
                frac = (target - prev_n) / span if span > 0 else 1.0
                return prev_le + (le - prev_le) * frac
            prev_le, prev_n = le, n
        return items[-1][0]

    async def _poller():
        prev = _tick_buckets(scrape())
        while not stop.is_set():
            level_at_start = int(governor.level)
            await asyncio.sleep(0.25)
            cur = _tick_buckets(scrape())
            acc = level_buckets.setdefault(level_at_start, {})
            for le, v in cur.items():
                acc[le] = acc.get(le, 0.0) + (v - prev.get(le, 0.0))
            prev = cur
            timeline.append({
                "t": round(time.monotonic() - t_start, 2),
                "level": int(governor.level),
                "pressure": round(governor.pressure, 3),
                "comps": {
                    k: round(v, 3)
                    for k, v in governor.components.items()
                },
            })

    async def _busy_aware_client(idx: int) -> None:
        """Like the chaos soak client, but it understands the L3 refusal:
        a ServerBusyMessage during auth backs the client off for the
        advertised retryAfterMs (the well-behaved-peer contract)."""
        from channeld_tpu.protocol import FrameDecoder

        seq = 0
        interval = 1.0 / p.msg_rate
        # Staggered start: a whole fleet connecting in one instant is a
        # thundering herd that can engage the ladder during warmup.
        await asyncio.sleep(idx * 0.15)
        while not stop.is_set():
            writer = None
            try:
                reader, writer = await cs._connect(host, client_port)
                writer.write(cs._auth_frame(f"ov-client-{idx}"))
                await writer.drain()
                dec = FrameDecoder()
                deadline = time.monotonic() + 2.0
                busy_ms = None
                authed = False
                while not authed and busy_ms is None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError("auth timeout")
                    data = await asyncio.wait_for(
                        reader.read(65536), timeout=remaining)
                    if not data:
                        raise ConnectionError("closed during auth")
                    for packet in dec.decode_packets(data):
                        for mp in packet.messages:
                            if mp.msgType == MessageType.SERVER_BUSY:
                                busy = control_pb2.ServerBusyMessage()
                                busy.ParseFromString(mp.msgBody)
                                busy_ms = busy.retryAfterMs or 500
                            elif mp.msgType == MessageType.AUTH:
                                authed = True
                if busy_ms is not None:
                    # Accounting opens at timeline zero: refusals during
                    # the settle phase (pre-ledger-reset) still back the
                    # client off but are not part of the exactness bar.
                    if accounting["open"]:
                        busy_seen["connection"] += 1
                    try:
                        writer.close()
                    except Exception:
                        pass
                    await asyncio.sleep(min(busy_ms / 1000.0, 3.0))
                    continue
            except (ConnectionError, OSError, TimeoutError):
                stats.auth_retries += 1
                if writer is not None:
                    try:
                        writer.close()
                    except Exception:
                        pass
                await asyncio.sleep(0.25)
                continue
            reader_task = asyncio.ensure_future(
                cs._read_frames(reader, lambda mp: None, stop))
            try:
                while not stop.is_set():
                    if send_stop.is_set():
                        await asyncio.sleep(0.2)
                        if reader_task.done():
                            raise ConnectionError("gateway closed the socket")
                        continue
                    if reader_task.done():
                        raise ConnectionError("gateway closed the socket")
                    import struct as _struct

                    writer.write(cs._frame(100, _struct.pack("<II", idx, seq)))
                    await writer.drain()
                    seq += 1
                    stats.client_sent[idx] = stats.client_sent.get(idx, 0) + 1
                    await asyncio.sleep(interval)
            except (ConnectionError, OSError):
                stats.disconnects += 1
            finally:
                reader_task.cancel()
                try:
                    writer.close()
                except Exception:
                    pass

    async def _observer_client(idx: int) -> None:
        """A deliberately low-priority subscriber: READ access to one
        spatial channel at a slow cadence (priority 2) — the first
        thing the L2 shed withholds. Retries through refusals and
        socket kills: the soak needs these subs alive to prove the
        update_priority shed."""
        start_id = global_settings.spatial_channel_id_start
        target = start_id + (idx % 16)
        await asyncio.sleep(0.5 + idx * 0.2)  # behind the client stagger
        while not stop.is_set():
            try:
                reader, writer = await cs._connect(host, client_port)
                await cs._auth_and_wait(reader, writer, f"ov-observer-{idx}")
                writer.write(cs._frame(
                    MessageType.SUB_TO_CHANNEL,
                    control_pb2.SubscribedToChannelMessage(
                        subOptions=control_pb2.ChannelSubscriptionOptions(
                            dataAccess=1,  # READ
                            fanOutIntervalMs=200,  # slower than default
                        ),
                    ).SerializeToString(),
                    channel_id=target,
                ))
                await writer.drain()
                # Drains fan-out until EOF (an L3 refusal closes the
                # socket here too — the loop just tries again later).
                await cs._read_frames(reader, lambda mp: None, stop)
            except (ConnectionError, OSError, TimeoutError):
                pass
            await asyncio.sleep(1.0)

    fault_log: list[str] = []
    try:
        (m_reader, m_writer, drain_task), spatial_socks = await cs._boot_world(
            host, server_port, stats, stop
        )
        tasks.append(drain_task)
        tasks.extend(t for _, _, t in spatial_socks)
        control_writers.append(m_writer)
        control_writers.extend(w for _, w, _ in spatial_socks)

        rng = Random(p.scenario.get("seed", 0) ^ 0x0F0F)
        sim_params = cs.SoakParams(
            entities=p.entities, storm_size=p.storm_size)
        sim = cs.EntitySim(ctl, sim_params, rng)
        sim.create_entities()

        # Bring the whole fleet up DURING the settle phase: the connect
        # burst, the observers' engine sub-table registration, and every
        # jit variant those paths trigger must compile before the
        # measured timeline, or boot stalls masquerade as L0 overload.
        for idx in range(p.clients):
            tasks.append(asyncio.ensure_future(_busy_aware_client(idx)))
        for idx in range(p.observers):
            tasks.append(asyncio.ensure_future(_observer_client(idx)))

        # Settle until the governor itself reads healthy (bounded): the
        # timeline must start from a genuine L0.
        settle_deadline = time.monotonic() + 30.0
        while time.monotonic() < settle_deadline:
            sim.jitter_step()
            await asyncio.sleep(0.5)
            if (time.monotonic() > settle_deadline - 27.0
                    and governor.level == 0 and governor.pressure < 0.5):
                break

        # Timeline zero: re-zero the governor (its transition clock and
        # shed ledger must not carry settle-phase stalls), snapshot the
        # metric baseline for exact shed accounting, open the clients'
        # busy-frame accounting, and arm — the wall-clock fault gates
        # are relative to ARMING, so start/stop line up with the phases.
        reset_overload()
        baseline = scrape()
        accounting["open"] = True
        arm(p.scenario)
        tasks.append(asyncio.ensure_future(_poller()))
        t0 = time.monotonic()
        sat_open = p.warmup_s
        sat_close = p.warmup_s + p.saturation_s
        storm_at = sat_open + 1.0
        # No storm in the final stretch of the window: in-flight
        # crossing chains must drain before the recovery phase.
        storm_stop = sat_close - max(p.storm_every_s, 6.0)
        last_crowd: list[int] = []
        max_level_seen = 0
        observer_subs_seen = 0
        while time.monotonic() - t0 < sat_close:
            now = time.monotonic() - t0
            sim.jitter_step()
            if sat_open <= now < storm_stop and now >= storm_at:
                if last_crowd:
                    sim.disperse(last_crowd)
                last_crowd = sim.storm_gather()
                storm_at += p.storm_every_s
            max_level_seen = max(max_level_seen, int(governor.level))
            if not observer_subs_seen:
                start_sp = global_settings.spatial_channel_id_start
                observer_subs_seen = sum(
                    1
                    for cid, ch in all_channels().items()
                    if start_sp <= cid < global_settings.entity_channel_id_start
                    for c in ch.subscribed_connections
                    if c.connection_type == ConnectionType.CLIENT
                )
            await asyncio.sleep(0.1)
        if last_crowd:
            sim.disperse(last_crowd)
        window_closed_at = time.monotonic()
        peak_at_close = max_level_seen

        # -- recovery: light load continues; the ladder must walk home --
        recovered_at = None
        while time.monotonic() - window_closed_at < p.recover_deadline_s:
            sim.jitter_step()
            max_level_seen = max(max_level_seen, int(governor.level))
            if governor.level == 0:
                recovered_at = time.monotonic()
                break
            await asyncio.sleep(0.2)

        send_stop.set()
        chaos_report = chaos.report()
        disarm()
        await asyncio.sleep(p.quiesce_s)

        # -- invariants --
        inv = InvariantChecker()
        now_samples = scrape()
        d = delta(now_samples, baseline)
        gov = governor.report()

        # 1. Ladder engaged, monotonically, and released.
        inv.expect_gt("ladder_reached_at_least_L2", max_level_seen, 1,
                      f"max level seen {max_level_seen}")
        steps = [t["to"] - t["from"] for t in gov["transitions"]]
        inv.expect_equal("ladder_moves_one_step_at_a_time",
                         [s for s in steps if abs(s) != 1], [],
                         f"steps={steps}")
        # Once the saturation window closed (plus a grace tick for the
        # EWMA to see it), the ladder may re-brake while draining the
        # withheld work — but it must never climb ABOVE the level the
        # overload itself reached: the release must not be worse than
        # the disease. Transition times are relative to the governor
        # re-zero at timeline zero.
        ups_after_close = [
            t for t in gov["transitions"]
            if t["to"] > peak_at_close and t["t"] > sat_close + 2.0
        ]
        inv.expect_equal("release_never_exceeds_overload_peak",
                         ups_after_close, [])
        inv.check(
            "returned_to_L0_within_deadline",
            recovered_at is not None and governor.level <= 1,
            f"deadline={p.recover_deadline_s}s, recovered_in="
            f"{round(recovered_at - window_closed_at, 2) if recovered_at else None}s"
            f", final_level={int(governor.level)}",
        )

        # 2. Tick p99 bounded at EVERY level the gateway passed through.
        per_level_p99 = {}
        for lvl, buckets in sorted(level_buckets.items()):
            p99 = _bucket_p99(buckets)
            per_level_p99[lvl] = p99
            if p99 is None:
                continue  # no GLOBAL ticks observed in that level's windows
            inv.expect_le(f"tick_p99_bounded_at_L{lvl}", p99,
                          p.tick_p99_bounds[lvl])

        # 3. Zero entities lost.
        lost_tracking = [
            eid for eid in sim.entity_ids
            if ctl.engine.slot_of_entity(eid) is None
            and eid not in ctl._last_positions
        ]
        inv.expect_equal("no_lost_entity_tracking", lost_tracking, [])
        start_id = global_settings.spatial_channel_id_start
        placement: dict[int, int] = {}
        for cid, ch in all_channels().items():
            if not (start_id <= cid < global_settings.entity_channel_id_start):
                continue
            ents = getattr(ch.get_data_message(), "entities", None)
            if ents is None:
                continue
            for eid in ents:
                placement[eid] = placement.get(eid, 0) + 1
        missing = [e for e in sim.entity_ids if placement.get(e, 0) == 0]
        duped = [e for e in sim.entity_ids if placement.get(e, 0) > 1]
        inv.expect_equal("every_entity_in_exactly_one_cell",
                         (missing, duped), ([], []))

        # 4. Exact shed accounting: the prometheus counter must equal the
        # governor's python-side ledger for every reason — and reasons
        # absent from the ledger must be absent from the counter.
        metric_sheds = {}
        for (name, labels), value in d.items():
            # Zero-delta samples are labels registered by an earlier run
            # in the same process (the pytest smoke); a zero delta and an
            # absent ledger key mean the same thing: nothing shed.
            if name == "overload_sheds_total" and value:
                metric_sheds[dict(labels)["reason"]] = int(value)
        inv.expect_equal("shed_accounting_exact",
                         metric_sheds, gov["shed_counts"])
        total_sheds = sum(gov["shed_counts"].values())
        inv.expect_gt("sheds_fired", total_sheds, 0)
        if p.require_update_priority:
            inv.expect_gt("low_priority_updates_shed",
                          gov["shed_counts"].get("update_priority", 0), 0)
        if p.require_handover_defer:
            inv.expect_gt("handover_deferred",
                          gov["shed_counts"].get("handover_defer", 0), 0)
        # Busy refusals clients actually observed can never exceed the
        # refusals the governor counted (frames may die with a socket,
        # but the ledger must never undercount).
        admission = gov["shed_counts"].get("admission_connection", 0)
        inv.expect_le("busy_frames_le_admission_sheds",
                      busy_seen["connection"], admission,
                      f"seen={busy_seen['connection']} counted={admission}")

        handovers = sample_total(d, "handovers_total")
        inv.expect_gt("handovers_orchestrated", handovers, 0)

        report = {
            "kind": "overload_soak",
            "config": os.path.basename(p.config_path),
            "config_overrides": overrides,
            "duration_s": round(time.monotonic() - t_start, 2),
            "phases": {
                "warmup_s": p.warmup_s,
                "saturation_s": p.saturation_s,
                "recover_deadline_s": p.recover_deadline_s,
                "quiesce_s": p.quiesce_s,
            },
            "clients": p.clients,
            "observers": p.observers,
            "entities": p.entities,
            "scenario": p.scenario,
            "governor": gov,
            "max_level": max_level_seen,
            "recovered_in_s": (
                round(recovered_at - window_closed_at, 2)
                if recovered_at else None
            ),
            "tick_p99_per_level": {
                f"L{k}": v for k, v in per_level_p99.items()
            },
            "timeline": timeline,
            "chaos": chaos_report,
            "invariants": inv.summary(),
            "stats": {
                "client_frames_sent": sum(stats.client_sent.values()),
                "observer_subscriptions": observer_subs_seen,
                "busy_refusals_observed": busy_seen["connection"],
                "disconnects": stats.disconnects,
                "auth_retries": stats.auth_retries,
                "handovers": int(handovers),
                "sheds": gov["shed_counts"],
                "global_tick_p99_s": histogram_quantile(
                    d, "channel_tick_duration", 0.99, channel_type="GLOBAL"),
            },
        }
        if fault_log:
            report["notes"] = fault_log
        if p.out_path:
            with open(p.out_path, "w") as f:
                json.dump(report, f, indent=2)
        return report
    finally:
        disarm()
        stop.set()
        for t in tasks:
            t.cancel()
        await asyncio.sleep(0)
        for w in control_writers:
            try:
                w.close()
            except Exception:
                pass
        server_srv.close()
        client_srv.close()
        channel_mod.reset_channels()
        connection_mod.reset_connections()
        data_mod.reset_registries()
        ddos_mod.reset_ddos()
        recovery_mod.reset_recovery()
        reset_spatial_controller()
        reset_global_settings()
        reset_overload()
        try:
            os.remove(merged_path)
        except OSError:
            pass


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--warmup", type=float, default=10.0)
    ap.add_argument("--saturation", type=float, default=35.0)
    ap.add_argument("--recover-deadline", type=float, default=15.0)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--observers", type=int, default=4)
    ap.add_argument("--entities", type=int, default=128)
    ap.add_argument("--rate", type=float, default=20.0)
    ap.add_argument("--scenario", type=str, default="",
                    help="scenario JSON path (default: built-in window)")
    ap.add_argument("--out", type=str, default="")
    args = ap.parse_args()
    p = OverloadSoakParams(
        warmup_s=args.warmup, saturation_s=args.saturation,
        recover_deadline_s=args.recover_deadline,
        clients=args.clients, observers=args.observers,
        entities=args.entities, msg_rate=args.rate,
        out_path=args.out,
    )
    if args.scenario:
        with open(args.scenario) as f:
            p.scenario = json.load(f)
    report = asyncio.run(run_overload_soak(p))
    slim = dict(report)
    slim["timeline"] = f"<{len(report['timeline'])} samples>"
    print(json.dumps(slim, indent=2))
    if not report["invariants"]["ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
