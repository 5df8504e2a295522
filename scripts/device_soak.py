"""Device-recovery soak: kill/corrupt the engine under live load and
prove zero entity loss (doc/device_recovery.md).

Boots the real gateway stack in-process — the same scaffolding as
``scripts/chaos_soak.py`` (TCP listeners, the 1ms flush pump, a master +
4 spatial servers building a 4x4 world through the real CREATE_CHANNEL
path, a fleet of reconnecting TCP clients streaming sequence-stamped
forwards, and a seeded entity sim with storm phases that march crowds
across cell boundaries) — then repeatedly breaks the DEVICE ENGINE
mid-handover-burst with the seeded chaos points the device guard
supervises:

- ``device.step_error``: a short window of transient XLA-style step
  errors — the guard retries with backoff and recovers WITHOUT a
  rebuild (cause=transient);
- ``device.step_hang``: one step stalls past the watchdog deadline —
  abandoned off-thread, engine rebuilt from the host shadow
  (cause=hang); the first rebuild attempt is additionally failed by
  ``device.rebuild_fail`` to exercise the FAILED -> retry path;
- ``device.nan``: device state silently rotted (NaN positions +
  garbage cell baselines) — the readback sentinel catches the
  impossible src cell from the ordinary fetched handover rows and the
  engine rebuilds (cause=corruption).

While the engine is down the gateway degrades instead of dying: held
device work, overload ladder pinned L2+, anomaly trace freeze, and an
immediate snapshot on the fatal and on the recovery. After the soak the
invariant checker asserts:

- zero entities lost or duplicated (device/host tracking AND exactly
  one spatial channel's data rows per entity),
- every recovery within ``device_recovery_deadline_s``, ending ACTIVE,
- exact double-entry accounting: ``device_recoveries_total{cause}``
  equals the guard's python ledger per cause,
- the overload ladder was pinned to L2+ during the outages and the
  floor released after recovery,
- the gateway was never declared dead and no server was declared lost
  (``gateway_deaths_total`` and ``server_lost_total`` both unmoved),
- client accounting stayed exact (received == owner-drained) and
  handovers kept flowing after the rebuilds.

Run the acceptance soak (60s):
  python scripts/device_soak.py --duration 60 --out SOAK_DEVICE_r13.json

The <60s CI smoke runs the same machinery with smaller numbers
(tests/test_device_guard.py::test_device_smoke_soak).
"""

from __future__ import annotations

import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# Same platform choice as chaos_soak (must precede any jax import).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
if os.environ.get("CHTPU_SOAK_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"  # gateway children inherit it

import argparse
import asyncio
import json
import tempfile
import time
from dataclasses import dataclass, field
from random import Random


def _load_chaos_soak():
    """The shared soak scaffolding (world boot, client fleet, entity
    sim) lives in chaos_soak.py; scripts/ is not a package, so load it
    by path."""
    if "chaos_soak" in sys.modules:
        return sys.modules["chaos_soak"]
    spec = importlib.util.spec_from_file_location(
        "chaos_soak", os.path.join(REPO, "scripts", "chaos_soak.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chaos_soak"] = mod
    spec.loader.exec_module(mod)
    return mod


def build_scenario(seed: int = 20260804, error_at: float = 8.0,
                   hang_at: float = 18.0, nan_at: float = 30.0) -> dict:
    """The seeded device-failure schedule. Windows are relative to
    chaos arming (which happens right before the listeners open);
    storms run continuously, so every window lands under live load
    with crossings in flight."""
    return {
        "name": "device-recovery",
        "seed": seed,
        "faults": [
            # Two transient errors then success: retry-with-backoff
            # recovery, no rebuild (device_retry_max=2 means the budget
            # is never exhausted).
            {"point": "device.step_error", "every_n": 1,
             "start_at_s": error_at, "max_fires": 2},
            # One hang well past the watchdog deadline -> abandoned
            # worker + rebuild...
            {"point": "device.step_hang", "every_n": 1,
             "start_at_s": hang_at, "max_fires": 1, "stall_ms": 3500},
            # ...whose FIRST rebuild attempt fails (FAILED -> backoff
            # -> successful retry).
            {"point": "device.rebuild_fail", "every_n": 1, "max_fires": 1},
            # Silent device-state rot caught by the readback sentinel.
            {"point": "device.nan", "every_n": 1,
             "start_at_s": nan_at, "max_fires": 1},
        ],
    }


@dataclass
class SoakParams:
    duration_s: float = 60.0
    clients: int = 12
    entities: int = 96
    msg_rate: float = 20.0
    storm_every_s: float = 6.0
    storm_size: int = 40
    tick_p99_bound_s: float = 2.0
    quiesce_s: float = 8.0
    config_path: str = os.path.join(REPO, "config", "spatial_tpu_4x4.json")
    scenario: dict = field(default_factory=build_scenario)
    out_path: str = ""
    entity_capacity: int = 256
    query_capacity: int = 32


async def run_soak(p: SoakParams) -> dict:
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
    from channeld_tpu.core.device_guard import guard, reset_device_guard
    from channeld_tpu.core.overload import governor, reset_overload
    from channeld_tpu.core.server import flush_loop, start_listening
    from channeld_tpu.core.settings import (
        ChannelSettings,
        global_settings,
        reset_global_settings,
    )
    from channeld_tpu.core.types import ChannelType, ConnectionType
    from channeld_tpu.federation import reset_federation
    from channeld_tpu.models.sim import register_sim_types
    from channeld_tpu.spatial.controller import (
        get_spatial_controller,
        init_spatial_controller,
        reset_spatial_controller,
    )

    t_start = time.monotonic()

    # -- fresh runtime (idempotent; the pytest smoke shares a process) --
    channel_mod.reset_channels()
    connection_mod.reset_connections()
    data_mod.reset_registries()
    ddos_mod.reset_ddos()
    recovery_mod.reset_recovery()
    reset_spatial_controller()
    reset_global_settings()
    reset_overload()
    reset_device_guard()
    reset_federation()

    global_settings.development = True
    # This soak proves the DEVICE plane: the guard is ON (the point of
    # the exercise); the balancer/federation/tracing planes are pinned
    # off to keep the envelope deterministic, like every other soak.
    global_settings.balancer_enabled = False
    # Adaptive partitioning stays pinned OFF: this soak's envelope
    # assumes the static boot grid (doc/partitioning.md);
    # scripts/density_soak.py is the partitioning plane's own soak.
    global_settings.partition_enabled = False
    # Simulation plane pinned OFF (doc/simulation.md): an agent
    # population would add its own crossings/census traffic to this
    # soak's deterministic accounting; scripts/sim_soak.py is the sim
    # plane's own soak.
    global_settings.sim_enabled = False
    global_settings.trace_enabled = False
    # SLO plane pinned OFF (doc/observability.md): this soak's
    # envelope predates the delivery-latency sampling; the health
    # plane has its own soak (scripts/obs_soak.py).
    global_settings.slo_enabled = False
    from channeld_tpu.core.tracing import recorder as _flight_recorder

    _flight_recorder.configure(enabled=False)
    global_settings.federation_config = ""
    global_settings.device_guard_enabled = True
    # Deadline with headroom over a loaded CI box's worst REAL step
    # (standalone GLOBAL tick p99 measured ~0.3s here): a genuinely
    # slow step misclassified as a hang still recovers cleanly, but it
    # would steal the transient window's retry sequence and break the
    # phase accounting this soak pins. The chaos stall (3.5s) stays
    # far above it either way.
    global_settings.device_step_deadline_s = 1.5
    global_settings.device_retry_backoff_ms = 50
    global_settings.tpu_entity_capacity = p.entity_capacity
    global_settings.tpu_query_capacity = p.query_capacity
    # Fatal-failure + recovery snapshots land here (the crash-during-
    # recovery durability satellite); checked as an invariant below.
    snap_dir = tempfile.mkdtemp(prefix="device_soak_")
    global_settings.snapshot_path = os.path.join(snap_dir, "gateway.snap")
    global_settings.channel_settings = {
        ChannelType.GLOBAL: ChannelSettings(
            tick_interval_ms=33, default_fanout_interval_ms=50),
        ChannelType.SPATIAL: ChannelSettings(
            tick_interval_ms=50, default_fanout_interval_ms=100),
        ChannelType.ENTITY: ChannelSettings(
            tick_interval_ms=50, default_fanout_interval_ms=100),
    }

    register_sim_types()
    init_connections(
        os.path.join(REPO, "config", "server_authoritative_fsm.json"),
        os.path.join(REPO, "config", "client_authoritative_fsm.json"),
    )
    init_channels()
    init_anti_ddos()
    init_spatial_controller(p.config_path)
    ctl = get_spatial_controller()

    baseline = scrape()
    arm(p.scenario)

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
    control_writers: list = []
    try:
        (m_reader, m_writer, drain_task), spatial_socks = await cs._boot_world(
            host, server_port, stats, stop
        )
        tasks.append(drain_task)
        tasks.extend(t for _, _, t in spatial_socks)
        control_writers.append(m_writer)
        control_writers.extend(w for _, w, _ in spatial_socks)

        rng = Random(p.scenario.get("seed", 0) ^ 0xD51CE)
        sim = cs.EntitySim(ctl, p, rng)
        sim.create_entities()

        for idx in range(p.clients):
            tasks.append(asyncio.ensure_future(cs._client_loop(
                idx, host, client_port, p.msg_rate, stats, stop, send_stop,
            )))

        # -- main soak timeline: continuous storms so every chaos
        # window lands mid-handover-burst --
        traffic_s = max(p.duration_s - p.quiesce_s, 1.0)
        storm_at = p.storm_every_s * 0.5
        last_crowd: list[int] = []
        t0 = time.monotonic()
        while time.monotonic() - t0 < traffic_s:
            sim.jitter_step()
            now = time.monotonic() - t0
            if now >= storm_at:
                if last_crowd:
                    sim.disperse(last_crowd)
                    last_crowd = []
                if now < traffic_s - max(p.storm_every_s * 0.8, 5.0):
                    last_crowd = sim.storm_gather()
                storm_at += p.storm_every_s
            await asyncio.sleep(0.1)
        if last_crowd:
            sim.disperse(last_crowd)

        # -- quiesce: stop traffic, disarm, let recovery finish --
        send_stop.set()
        chaos_report = chaos.report()
        fire_counts = dict(chaos.fire_counts())
        disarm()
        quiesce_deadline = time.monotonic() + p.quiesce_s
        while time.monotonic() < quiesce_deadline:
            await asyncio.sleep(0.25)
            if guard.state == 0 and time.monotonic() > quiesce_deadline - 2.0:
                break

        guard_report = guard.report()
        governor_report = governor.report()
        floor_released = governor._level_floor == 0

        # -- invariants --
        inv = InvariantChecker()
        d = delta(scrape(), baseline)

        # 1. Zero entities lost or duplicated across every failure +
        # rebuild: still device/host-tracked AND in exactly one cell.
        lost_tracking = [
            eid for eid in sim.entity_ids
            if ctl.engine.slot_of_entity(eid) is None
            and eid not in ctl._last_positions
        ]
        inv.expect_equal("no_lost_entity_tracking", lost_tracking, [],
                         "device slot or host tracking")
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
                         (missing, duped), ([], []),
                         "missing / duplicated in spatial channel data")

        # 2. The engine actually failed AND recovered, every way the
        # scenario broke it — ending ACTIVE.
        rec = guard_report["recovery_counts"]
        inv.expect_gt("transient_retry_recovered",
                      rec.get("transient", 0), 0)
        inv.expect_gt("engine_rebuilt_after_hang", rec.get("hang", 0), 0)
        inv.expect_gt("engine_rebuilt_after_corruption",
                      rec.get("corruption", 0), 0)
        inv.expect_gt("rebuild_retry_exercised",
                      guard_report["failure_counts"].get("rebuild_fail", 0),
                      0)
        inv.expect_equal("device_state_active_at_end",
                         guard_report["state"], "ACTIVE")
        silent = [r["point"] for r in p.scenario["faults"]
                  if fire_counts.get(r["point"], 0) == 0]
        inv.expect_equal("every_fault_point_fired", silent, [])

        # 3. Bounded recovery.
        worst_recovery = max(guard_report["recovery_times_s"], default=0.0)
        inv.expect_le("recovery_within_deadline", worst_recovery,
                      global_settings.device_recovery_deadline_s,
                      f"{len(guard_report['recovery_times_s'])} recoveries")

        # 4. Exact double-entry accounting per cause.
        mismatched = {
            cause: (count, sample_total(
                d, "device_recoveries_total", cause=cause))
            for cause, count in rec.items()
            if count != sample_total(d, "device_recoveries_total",
                                     cause=cause)
        }
        inv.expect_equal("device_recoveries_ledger_matches_metric",
                         mismatched, {})

        # 5. The gateway degraded, never died: ladder pinned L2+ while
        # the engine was down, floor released after; no death/loss
        # declarations anywhere.
        inv.check("overload_pinned_during_outage",
                  any(t["to"] >= 2 for t in governor_report["transitions"]),
                  f"transitions={governor_report['transitions']}")
        inv.check("overload_floor_released", floor_released)
        deaths = sample_total(d, "gateway_deaths_total")
        lost = sample_total(d, "server_lost_total")
        inv.expect_equal("gateway_never_declared_dead",
                         (int(deaths), int(lost)), (0, 0),
                         "gateway_deaths_total / server_lost_total deltas")

        # 6. Fatal + recovery snapshots landed (crash-during-recovery
        # durability) and still parse.
        snap_ok = False
        try:
            from channeld_tpu.protocol import snapshot_pb2

            with open(global_settings.snapshot_path, "rb") as f:
                parsed = snapshot_pb2.GatewaySnapshot()
                parsed.ParseFromString(f.read())
            snap_ok = len(parsed.channels) > 0
        except Exception:
            pass
        inv.check("recovery_snapshot_written", snap_ok,
                  global_settings.snapshot_path)

        # 7. Client accounting stayed exact through every outage.
        received = sample_total(
            d, "messages_in_total", conn_type="CLIENT", msg_type="100"
        )
        drained = sum(len(v) for v in stats.drained.values())
        sent = sum(stats.client_sent.values())
        inv.expect_equal("received_equals_owner_drained",
                         int(received), drained)

        # 8. The world kept moving: handovers orchestrated (incl. the
        # re-detections after each rebuild), tick p99 bounded.
        handovers = sample_total(d, "handovers_total")
        inv.expect_gt("handovers_orchestrated", handovers, 0)
        p99 = histogram_quantile(
            d, "channel_tick_duration", 0.99, channel_type="GLOBAL"
        )
        inv.expect_le("global_tick_p99_bounded", p99, p.tick_p99_bound_s)

        report = {
            "kind": "device_soak",
            "config": os.path.basename(p.config_path),
            "duration_s": round(time.monotonic() - t_start, 2),
            "traffic_s": traffic_s,
            "clients": p.clients,
            "entities": p.entities,
            "msg_rate_per_client": p.msg_rate,
            "scenario": p.scenario,
            "chaos": chaos_report,
            "device": guard_report,
            "governor": governor_report,
            "recoveries": {
                "counts": rec,
                "worst_s": round(worst_recovery, 3),
                "deadline_s": global_settings.device_recovery_deadline_s,
                "rebuild_ms_observed": sample_total(
                    d, "device_rebuild_ms_count"),
            },
            "census": {"missing": missing, "duplicated": duped,
                       "total": len(sim.entity_ids)},
            "invariants": inv.summary(),
            "stats": {
                "client_frames_sent": sent,
                "gateway_received": int(received),
                "owner_drained": drained,
                "disconnects": stats.disconnects,
                "reconnects": stats.reconnects,
                "handovers": int(handovers),
                "held_ticks": guard_report["held_ticks"],
                "global_tick_p99_s": p99,
                "device_step_p99_s": histogram_quantile(
                    d, "tpu_spatial_step_seconds", 0.99),
            },
        }
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
        reset_device_guard()
        import shutil

        shutil.rmtree(snap_dir, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--clients", type=int, default=12)
    ap.add_argument("--entities", type=int, default=96)
    ap.add_argument("--rate", type=float, default=20.0)
    ap.add_argument("--scenario", type=str, default="",
                    help="scenario JSON path (default: built-in)")
    ap.add_argument("--out", type=str, default="")
    args = ap.parse_args()
    scenario = build_scenario()
    if args.scenario:
        with open(args.scenario) as f:
            scenario = json.load(f)
    p = SoakParams(
        duration_s=args.duration, clients=args.clients,
        entities=args.entities, msg_rate=args.rate,
        scenario=scenario, out_path=args.out,
    )
    report = asyncio.run(run_soak(p))
    print(json.dumps(report, indent=2))
    if not report["invariants"]["ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
