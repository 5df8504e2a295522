"""Balance soak: herd a skewed hotspot, prove planned zero-loss migration.

Boots the same live gateway as ``scripts/chaos_soak.py`` (real TCP
listeners, the 1ms pump, the TPU spatial controller on the cells plane,
a master + 4 spatial servers, a client fleet, a seeded entity sim) and
drives the workload the static grid cannot absorb — a sustained
single-quadrant hotspot:

1. **warmup** — entities spread uniformly; handover paths hot; the
   balancer sees a balanced world and does nothing.
2. **hotspot** — every entity herds into ONE server's quadrant and
   keeps jittering inside it. One server now hosts the whole world's
   load while three idle; the balancer (doc/balancer.md) must plan and
   commit live cell migrations — freeze -> journal drain -> owner flip
   with a ``CellMigratedMessage`` bootstrap — until the per-server
   entity load flattens below the imbalance threshold.
3. **kill mid-migration** (acceptance soak only) — the crowd re-herds
   into a fresh quadrant and, the moment a migration enters its
   freeze/drain window, the DESTINATION server's socket is aborted.
   The migration must abort deterministically back to the old owner
   (nothing moved, crossings unfrozen and replayed); the failover plane
   then cleans up the dead server's own cells.
4. **aftermath + quiesce** — the world keeps serving; frozen backlogs
   drain; every ledger must balance.

The invariant checker asserts the PR's acceptance bar: at least one
committed migration; steady-state max/mean per-server entity load under
the enter threshold; zero entities lost or duplicated (exact placement
accounting, handover journal prepared == committed + aborted); the
injected crash aborts cleanly back to the old owner; per-epoch commits
within the budget; no cell migrates twice within its cooldown; GLOBAL
tick p99 bounded throughout.

Emits a ``SOAK_BALANCE_*.json`` artifact with the migration timeline,
the balancer/journal ledgers, and the invariant results.

Run the acceptance soak (~60s of timeline):
  python scripts/balance_soak.py --out SOAK_BALANCE_r09.json

The <60s CI smoke runs the same machinery with smaller numbers
(tests/test_balancer.py::test_balance_smoke_soak).
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
    machinery this soak re-drives around a skewed hotspot."""
    spec = importlib.util.spec_from_file_location(
        "chaos_soak", os.path.join(REPO, "scripts", "chaos_soak.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("chaos_soak", mod)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class BalanceSoakParams:
    warmup_s: float = 6.0
    hotspot_s: float = 22.0
    aftermath_s: float = 8.0
    quiesce_s: float = 8.0
    clients: int = 10
    entities: int = 128
    msg_rate: float = 20.0
    # Second hotspot with a destination-server kill mid-migration.
    kill_mid_migration: bool = True
    kill_phase_s: float = 14.0
    recover_window_s: float = 1.5
    # Balancer tuning for soak cadence (33ms GLOBAL ticks).
    imbalance_enter: float = 1.5
    imbalance_exit: float = 1.2
    hold_ticks: int = 3
    epoch_ticks: int = 90
    budget_per_epoch: int = 2
    cooldown_ticks: int = 240
    min_entity_delta: int = 8
    freeze_min_ticks: int = 6
    # Freeze window for the kill phase (wide enough to land the abort).
    kill_freeze_min_ticks: int = 45
    tick_p99_bound_s: float = 1.5
    global_tick_ms: int = 33
    config_path: str = os.path.join(REPO, "config", "spatial_tpu_cells_2x2.json")
    scenario: dict = field(default_factory=dict)
    out_path: str = ""
    entity_capacity: int = 256
    query_capacity: int = 32


def default_scenario(p: BalanceSoakParams) -> dict:
    """Ambient chaos weather only — mild stalls; the deliberate fault is
    the workload skew (and, in the acceptance soak, the destination
    kill)."""
    return {
        "name": "balance-weather",
        "seed": 20260803,
        "config_overrides": {"CellBucket": 8},
        "faults": [
            {"point": "device.dispatch_stall", "every_n": 40,
             "stall_ms": 20, "max_fires": 50},
        ],
    }


async def run_balance_soak(p: BalanceSoakParams) -> dict:
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
    from channeld_tpu.core.failover import journal, plane, reset_failover
    from channeld_tpu.core.overload import reset_overload
    from channeld_tpu.federation import reset_federation
    from channeld_tpu.core.server import flush_loop, start_listening
    from channeld_tpu.core.settings import (
        ChannelSettings,
        global_settings,
        reset_global_settings,
    )
    from channeld_tpu.core.types import ChannelType, ConnectionType
    from channeld_tpu.models.sim import register_sim_types
    from channeld_tpu.spatial.balancer import balancer, reset_balancer
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
    reset_failover()
    reset_balancer()

    global_settings.development = True
    # Flight recorder pinned OFF (doc/observability.md): these soaks
    # prove deterministic accounting and timing envelopes; span
    # recording and anomaly auto-dumps must not perturb either
    # (scripts/trace_soak.py is the recorder's own soak).
    global_settings.trace_enabled = False
    # Device guard pinned OFF (doc/device_recovery.md): this soak's
    # envelope is deterministic; the watchdog worker-thread hop and
    # any chaos-adjacent retry would perturb it. The device plane's
    # own soak is scripts/device_soak.py.
    global_settings.device_guard_enabled = False
    # SLO plane pinned OFF (doc/observability.md): this soak's
    # envelope predates the delivery-latency sampling; the health
    # plane has its own soak (scripts/obs_soak.py).
    global_settings.slo_enabled = False
    from channeld_tpu.core.tracing import recorder as _flight_recorder

    _flight_recorder.configure(enabled=False)
    global_settings.tpu_entity_capacity = p.entity_capacity
    global_settings.tpu_query_capacity = p.query_capacity
    # This soak proves the BALANCER plane; the overload ladder stays
    # pinned at L0 so boot-time jit stalls can't push the gateway into
    # L3 admission control (the overload soak owns that interplay), and
    # its veto can't mask the migrations under test.
    global_settings.overload_enabled = False
    global_settings.server_conn_recoverable = True
    global_settings.server_conn_recover_timeout_ms = int(
        p.recover_window_s * 1000
    )
    global_settings.failover_enabled = True
    global_settings.balancer_enabled = True
    # Adaptive partitioning stays pinned OFF: this soak PROVES the
    # fixed-grid 1.31 floor the density soak then beats
    # (doc/partitioning.md) — a live split here would invalidate
    # the envelope.
    global_settings.partition_enabled = False
    # Federation stays pinned OFF: a remote shard would route some
    # crossings over a trunk and break this soak's deterministic
    # single-gateway accounting (doc/federation.md).
    reset_federation()
    global_settings.federation_config = ""
    global_settings.balancer_imbalance_enter = p.imbalance_enter
    global_settings.balancer_imbalance_exit = p.imbalance_exit
    global_settings.balancer_hold_ticks = p.hold_ticks
    global_settings.balancer_epoch_ticks = p.epoch_ticks
    global_settings.balancer_budget_per_epoch = p.budget_per_epoch
    global_settings.balancer_cooldown_ticks = p.cooldown_ticks
    global_settings.balancer_min_entity_delta = p.min_entity_delta
    global_settings.balancer_freeze_min_ticks = p.freeze_min_ticks
    global_settings.channel_settings = {
        ChannelType.GLOBAL: ChannelSettings(
            tick_interval_ms=p.global_tick_ms, default_fanout_interval_ms=50),
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

    with open(p.config_path) as f:
        spec = json.load(f)
    overrides = dict(p.scenario.get("config_overrides", {}))
    spec.setdefault("Config", {}).update(overrides)
    merged_path = os.path.join(
        "/tmp", f"balance_soak_spatial_{os.getpid()}.json"
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
    control_writers: list = []

    start_id = global_settings.spatial_channel_id_start
    end_id = global_settings.entity_channel_id_start

    def spatial_channels():
        return {cid: ch for cid, ch in all_channels().items()
                if start_id <= cid < end_id}

    def server_entity_loads() -> dict[int, int]:
        """conn id -> entities resident in its owned cells."""
        out: dict[int, int] = {}
        for ch in spatial_channels().values():
            if not ch.has_owner():
                continue
            ents = getattr(ch.get_data_message(), "entities", None)
            out[ch.get_owner().id] = (
                out.get(ch.get_owner().id, 0)
                + (len(ents) if ents is not None else 0)
            )
        return out

    def entity_imbalance(loads: dict[int, int]) -> float:
        if not loads:
            return 0.0
        mean = sum(loads.values()) / len(loads)
        return (max(loads.values()) / mean) if mean > 0 else 0.0

    timeline: list[dict] = []
    fault_log: list[str] = []

    async def _poller():
        while not stop.is_set():
            loads = server_entity_loads()
            mig = balancer.migration_in_flight()
            timeline.append({
                "t": round(time.monotonic() - t_start, 2),
                "server_entities": dict(sorted(loads.items())),
                "entity_imbalance": round(entity_imbalance(loads), 3),
                "committed": balancer.ledger.get("committed", 0),
                "aborted": balancer.ledger.get("aborted", 0),
                "in_flight": mig.cell_id if mig is not None else None,
            })
            await asyncio.sleep(0.25)

    try:
        (m_reader, m_writer, drain_task), spatial_socks = await cs._boot_world(
            host, server_port, stats, stop
        )
        tasks.append(drain_task)
        control_writers.append(m_writer)
        for _r, w, task in spatial_socks:
            tasks.append(task)
            control_writers.append(w)

        rng = Random(p.scenario.get("seed", 0) ^ 0xBA1A)
        sim_params = cs.SoakParams(entities=p.entities, storm_size=48)
        sim = cs.EntitySim(ctl, sim_params, rng)
        sim.create_entities()

        for idx in range(p.clients):
            tasks.append(asyncio.ensure_future(cs._client_loop(
                idx, host, client_port, p.msg_rate, stats, stop, send_stop,
            )))

        baseline = scrape()
        arm(p.scenario)
        tasks.append(asyncio.ensure_future(_poller()))

        # ---- quadrant herding helpers --------------------------------
        def quadrant_bounds(sx: int, sy: int):
            sgc = -(-ctl.grid_cols // ctl.server_cols)
            sgr = -(-ctl.grid_rows // ctl.server_rows)
            x0 = ctl.world_offset_x + sx * sgc * ctl.grid_width + 1.0
            z0 = ctl.world_offset_z + sy * sgr * ctl.grid_height + 1.0
            x1 = x0 + sgc * ctl.grid_width - 2.0
            z1 = z0 + sgr * ctl.grid_height - 2.0
            return x0, z0, x1, z1

        def herd(sx: int, sy: int) -> None:
            x0, z0, x1, z1 = quadrant_bounds(sx, sy)
            for eid in sim.entity_ids:
                sim._move(eid, rng.uniform(x0, x1), rng.uniform(z0, z1))

        def quadrant_jitter(sx: int, sy: int) -> None:
            x0, z0, x1, z1 = quadrant_bounds(sx, sy)
            for eid in rng.sample(sim.entity_ids,
                                  max(1, len(sim.entity_ids) // 8)):
                x, z = sim.positions[eid]
                x = min(max(x + rng.uniform(-8, 8), x0), x1)
                z = min(max(z + rng.uniform(-8, 8), z0), z1)
                sim._move(eid, x, z)

        # -- warmup: uniform world, hot paths, no migrations expected --
        warm_until = time.monotonic() + p.warmup_s
        while time.monotonic() < warm_until:
            sim.jitter_step()
            await asyncio.sleep(0.1)
        committed_at_warmup = balancer.ledger.get("committed", 0)

        # -- the hotspot: everyone into quadrant (0, 0). Adaptive phase
        # length: at least hotspot_s, then up to 2x while the per-server
        # entity load is still above the threshold (a slow CI box pays
        # more wall clock instead of flaking the steady-state check).
        herd(0, 0)
        hot_min = time.monotonic() + p.hotspot_s
        hot_cap = time.monotonic() + p.hotspot_s * 2
        while time.monotonic() < hot_min or (
            time.monotonic() < hot_cap
            and (entity_imbalance(server_entity_loads()) >= p.imbalance_enter
                 or balancer.migration_in_flight() is not None)
        ):
            quadrant_jitter(0, 0)
            await asyncio.sleep(0.1)
        hotspot_committed = balancer.ledger.get("committed", 0)

        # Steady-state balance after the migrations settled (let any
        # in-flight migration finish first).
        settle_until = time.monotonic() + 3.0
        while (time.monotonic() < settle_until
               and balancer.migration_in_flight() is not None):
            await asyncio.sleep(0.1)
        steady_loads = server_entity_loads()
        steady_imbalance = entity_imbalance(steady_loads)

        # -- kill-mid-migration phase (acceptance soak) --
        kill_rec = None
        if p.kill_mid_migration:
            global_settings.balancer_freeze_min_ticks = p.kill_freeze_min_ticks
            sim.disperse(list(sim.entity_ids))
            await asyncio.sleep(1.5)
            herd(1, 1)
            kill_until = time.monotonic() + p.kill_phase_s
            while time.monotonic() < kill_until:
                quadrant_jitter(1, 1)
                mig = balancer.migration_in_flight()
                if mig is not None and kill_rec is None:
                    # The migration is inside its freeze/drain window:
                    # abort the DESTINATION server's socket now.
                    dst_pit = getattr(mig.dst_conn, "pit", "")
                    idx = None
                    if dst_pit.startswith("soak-spatial-"):
                        idx = int(dst_pit.rsplit("-", 1)[1])
                    if idx is not None and idx < len(spatial_socks):
                        cell_id = mig.cell_id
                        aborted_before = balancer.ledger.get("aborted", 0)
                        spatial_socks[idx][1].transport.abort()
                        t_kill = time.monotonic()
                        # Wait for THIS migration to resolve (the cell
                        # may legitimately re-plan right after — read
                        # the rollback property off the abort event, not
                        # a racy owner poll).
                        while (balancer.migration_in_flight() is mig
                               and time.monotonic() < t_kill + 5.0):
                            await asyncio.sleep(0.05)
                        abort_ev = next(
                            (e for e in reversed(balancer.events)
                             if e["cell"] == cell_id
                             and e["result"] not in ("committed",)),
                            None,
                        )
                        kill_rec = {
                            "dst_pit": dst_pit,
                            "cell": cell_id,
                            "t": round(t_kill - t_start, 2),
                            "resolved_in_s": round(
                                time.monotonic() - t_kill, 2),
                            "aborted": (
                                balancer.ledger.get("aborted", 0)
                                > aborted_before
                            ),
                            "owner_is_src_after_abort": bool(
                                abort_ev is not None
                                and abort_ev.get("owner_rolled_back")
                            ),
                        }
                    else:
                        fault_log.append(
                            f"kill skipped: dst {dst_pit!r} unmapped")
                await asyncio.sleep(0.1)
            if kill_rec is None:
                fault_log.append("no migration observed in kill phase")

        # -- aftermath: world keeps serving on whatever fleet remains --
        aft_until = time.monotonic() + p.aftermath_s
        while time.monotonic() < aft_until:
            sim.jitter_step()
            await asyncio.sleep(0.1)

        send_stop.set()
        chaos_report = chaos.report()
        disarm()
        await asyncio.sleep(p.quiesce_s)

        # -- invariants --
        inv = InvariantChecker()
        now_samples = scrape()
        d = delta(now_samples, baseline)
        breport = balancer.report()
        events = breport["events"]
        commits = [e for e in events if e["result"] == "committed"]

        # 1. The hotspot produced planned, committed migrations; the
        #    balanced warmup produced none.
        inv.expect_equal("no_migration_while_balanced",
                         committed_at_warmup, 0)
        inv.expect_gt("hotspot_migrations_committed",
                      hotspot_committed, 0)

        # 2. Steady-state per-server entity load flattened under the
        #    configured threshold.
        inv.expect_le("steady_state_entity_imbalance_under_threshold",
                      steady_imbalance, p.imbalance_enter,
                      f"loads={steady_loads}")

        # 3. Exact migration accounting: metric == python ledger per
        #    result; planned == committed + aborted; nothing in flight.
        metric_results = {}
        for (name, labels), value in d.items():
            if name == "balancer_migrations_total" and value:
                metric_results[dict(labels)["result"]] = int(value)
        inv.expect_equal("migration_metric_matches_ledger",
                         metric_results, dict(balancer.ledger))
        inv.expect_equal(
            "migrations_planned_equals_committed_plus_aborted",
            balancer.ledger.get("planned", 0),
            balancer.ledger.get("committed", 0)
            + balancer.ledger.get("aborted", 0),
            f"ledger={balancer.ledger}",
        )
        inv.expect_equal("no_migration_left_in_flight",
                         balancer.migration_in_flight(), None)
        inv.expect_equal("no_frozen_crossing_left_behind",
                         (sorted(balancer.frozen_cells),
                          len(balancer._frozen_crossings)),
                         ([], 0))

        # 4. Budget respected per epoch; no cell re-migrated within its
        #    cooldown (no oscillation).
        per_epoch: dict[int, int] = {}
        for e in commits:
            per_epoch[e["epoch"]] = per_epoch.get(e["epoch"], 0) + 1
        over_budget = {ep: n for ep, n in per_epoch.items()
                       if n > p.budget_per_epoch}
        inv.expect_equal("per_epoch_commits_within_budget", over_budget, {},
                         f"per_epoch={per_epoch}")
        flaps = []
        by_cell: dict[int, list] = {}
        for e in commits:
            by_cell.setdefault(e["cell"], []).append(e["resolved_tick"])
        for cell, ticks in by_cell.items():
            ticks.sort()
            for a, b in zip(ticks, ticks[1:]):
                if b - a < p.cooldown_ticks:
                    flaps.append((cell, a, b))
        inv.expect_equal("no_cell_migrates_twice_within_cooldown",
                         flaps, [])

        # 5. The injected crash aborted cleanly back to the old owner.
        if p.kill_mid_migration:
            inv.check("kill_mid_migration_landed", kill_rec is not None,
                      str(fault_log))
            if kill_rec is not None:
                inv.check("crash_mid_migration_aborts_to_old_owner",
                          kill_rec["aborted"]
                          and kill_rec["owner_is_src_after_abort"],
                          str(kill_rec))

        # 6. Zero entity loss; exactly-once placement; journal balances.
        lost_tracking = [
            eid for eid in sim.entity_ids
            if ctl.engine.slot_of_entity(eid) is None
            and eid not in ctl._last_positions
        ]
        inv.expect_equal("no_lost_entity_tracking", lost_tracking, [])
        placement: dict[int, int] = {}
        for cid, ch in spatial_channels().items():
            ents = getattr(ch.get_data_message(), "entities", None)
            if ents is None:
                continue
            for eid in ents:
                placement[eid] = placement.get(eid, 0) + 1
        missing = [e for e in sim.entity_ids if placement.get(e, 0) == 0]
        duped = [e for e in sim.entity_ids if placement.get(e, 0) > 1]
        dup_where = {
            str(e): sorted(
                cid for cid, ch in spatial_channels().items()
                if e in (getattr(ch.get_data_message(), "entities", None)
                         or ())
            )
            for e in duped
        }
        inv.expect_equal("every_entity_in_exactly_one_cell",
                         (missing, duped), ([], []),
                         f"dup_cells={dup_where}" if dup_where else "")
        jc = dict(journal.counts)
        inv.expect_equal(
            "journal_prepared_equals_committed_plus_aborted",
            jc.get("prepared", 0),
            jc.get("committed", 0) + jc.get("aborted", 0),
            f"counts={jc}",
        )
        inv.expect_equal("journal_nothing_in_flight",
                         journal.in_flight_count(), 0)

        # 7. Tick p99 bounded throughout.
        p99 = histogram_quantile(
            d, "channel_tick_duration", 0.99, channel_type="GLOBAL")
        inv.expect_le("global_tick_p99_bounded", p99, p.tick_p99_bound_s)

        report = {
            "kind": "balance_soak",
            "config": os.path.basename(p.config_path),
            "config_overrides": overrides,
            "duration_s": round(time.monotonic() - t_start, 2),
            "phases": {
                "warmup_s": p.warmup_s,
                "hotspot_s": p.hotspot_s,
                "kill_phase_s": p.kill_phase_s if p.kill_mid_migration else 0,
                "aftermath_s": p.aftermath_s,
                "quiesce_s": p.quiesce_s,
            },
            "clients": p.clients,
            "entities": p.entities,
            "balancer_knobs": {
                "imbalance_enter": p.imbalance_enter,
                "imbalance_exit": p.imbalance_exit,
                "hold_ticks": p.hold_ticks,
                "epoch_ticks": p.epoch_ticks,
                "budget_per_epoch": p.budget_per_epoch,
                "cooldown_ticks": p.cooldown_ticks,
                "freeze_min_ticks": p.freeze_min_ticks,
            },
            "scenario": p.scenario,
            "balancer": breport,
            "kill": kill_rec,
            "steady_state": {
                "server_entities": {
                    str(k): v for k, v in sorted(steady_loads.items())
                },
                "entity_imbalance": round(steady_imbalance, 3),
            },
            "failover": plane.report(),
            "journal": journal.report(),
            "timeline": timeline,
            "chaos": chaos_report,
            "invariants": inv.summary(),
            "stats": {
                "client_frames_sent": sum(stats.client_sent.values()),
                "migrations_committed": balancer.ledger.get("committed", 0),
                "migrations_aborted": balancer.ledger.get("aborted", 0),
                "migrations_vetoed": balancer.ledger.get("vetoed", 0),
                "handovers_total": int(sample_total(d, "handovers_total")),
                "steady_entity_imbalance": round(steady_imbalance, 3),
                "global_tick_p99_s": p99,
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
        reset_failover()
        reset_balancer()
        try:
            os.remove(merged_path)
        except OSError:
            pass


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--warmup", type=float, default=6.0)
    ap.add_argument("--hotspot", type=float, default=22.0)
    ap.add_argument("--aftermath", type=float, default=8.0)
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--entities", type=int, default=128)
    ap.add_argument("--rate", type=float, default=20.0)
    ap.add_argument("--no-kill", action="store_true",
                    help="skip the kill-mid-migration phase")
    ap.add_argument("--scenario", type=str, default="",
                    help="scenario JSON path (default: built-in weather)")
    ap.add_argument("--out", type=str, default="")
    args = ap.parse_args()
    p = BalanceSoakParams(
        warmup_s=args.warmup, hotspot_s=args.hotspot,
        aftermath_s=args.aftermath, clients=args.clients,
        entities=args.entities, msg_rate=args.rate,
        kill_mid_migration=not args.no_kill, out_path=args.out,
    )
    if args.scenario:
        with open(args.scenario) as f:
            p.scenario = json.load(f)
    report = asyncio.run(run_balance_soak(p))
    slim = dict(report)
    slim["timeline"] = f"<{len(report['timeline'])} samples>"
    print(json.dumps(slim, indent=2))
    if not report["invariants"]["ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
