"""Density soak: pile a crowd into ONE cell, prove live split/merge.

Boots the same live gateway as ``scripts/chaos_soak.py`` (real TCP
listeners, the 1ms pump, the TPU spatial controller on the cells plane,
a master + 4 spatial servers, a client fleet, a seeded entity sim) and
drives the workload a fixed grid has **no remedy** for — the whole
population denser than one cell:

1. **warmup** — entities spread uniformly; handover paths hot; the
   density governor sees a balanced world and does nothing.
2. **pileup** — every entity herds into ONE CELL and keeps jittering
   inside it. The balancer alone is helpless here (its improvement
   guard proves moving the one giant cell just relocates the hotspot —
   the 1.31 max/mean floor of SOAK_BALANCE_r09 is the best a fixed
   grid can do). The density governor (doc/partitioning.md) must
   commit a live quadtree split — freeze -> journal drain -> WAL
   geometry record -> repartition -> ``CellGeometryUpdateMessage``
   bootstrap — and the balancer then migrates the finer granules
   across servers until per-server load flattens BELOW the fixed-grid
   floor.
3. **kill mid-split** (acceptance soak only) — the crowd re-herds into
   a fresh cell and, the moment the governor's split enters its
   freeze/drain window, the OWNING server's socket is aborted. The
   split must abort deterministically (nothing mutated before the WAL
   commit point, geometry epoch unchanged); failover then re-hosts the
   dead server's cells and the re-planned split commits on the new
   owner.
4. **disperse + quiesce** — the crowd leaves; cold sibling groups
   consolidate authority (directed balancer migrations) and merge
   back until the boot geometry is restored; every ledger must
   balance.

The invariant checker asserts the PR's acceptance bar: at least one
committed live split; steady-state per-server max/mean entity load
under the 1.31 fixed-grid floor; zero entities lost or duplicated
(exact placement accounting, handover journal prepared == committed +
aborted); ``partition_ops_total`` == the python ledger; device
micro-grid rebuilds verified bit-identical (zero mismatches); the
injected kill aborts deterministically; cold merges restore the
original geometry.

Emits a ``SOAK_SPLIT_*.json`` artifact with the geometry timeline,
the partition/balancer/journal ledgers, and the invariant results.

Run the acceptance soak (~75s of timeline):
  python scripts/density_soak.py --out SOAK_SPLIT_r18.json

The <60s CI smoke runs the same machinery with smaller numbers
(tests/test_partitioning.py::test_density_smoke_soak).
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
    machinery this soak re-drives around a one-cell pileup."""
    spec = importlib.util.spec_from_file_location(
        "chaos_soak", os.path.join(REPO, "scripts", "chaos_soak.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("chaos_soak", mod)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class DensitySoakParams:
    warmup_s: float = 6.0
    pileup_s: float = 20.0
    disperse_s: float = 12.0
    quiesce_s: float = 6.0
    clients: int = 10
    entities: int = 128
    msg_rate: float = 20.0
    # Second pileup with the owning server killed mid-split.
    kill_mid_split: bool = True
    kill_phase_s: float = 18.0
    recover_window_s: float = 1.5
    # Density governor tuning for soak cadence (33ms GLOBAL ticks).
    split_entities: int = 48
    merge_entities: int = 16
    max_depth: int = 2
    eval_ticks: int = 6
    hold_ticks: int = 2
    epoch_ticks: int = 150
    budget_per_epoch: int = 2
    cooldown_ticks: int = 90
    freeze_min_ticks: int = 4
    drain_deadline_ticks: int = 120
    # Freeze window for the kill phase (wide enough to land the abort).
    kill_freeze_min_ticks: int = 45
    # The balancer migrates the split granules (and runs the directed
    # consolidation migrations the merge path requests).
    imbalance_enter: float = 1.25
    imbalance_exit: float = 1.1
    balancer_min_entity_delta: int = 8
    balancer_freeze_min_ticks: int = 4
    balancer_epoch_ticks: int = 90
    balancer_budget_per_epoch: int = 2
    balancer_cooldown_ticks: int = 120
    # The acceptance bar: SOAK_BALANCE_r09's fixed-grid floor.
    density_ratio_bound: float = 1.31
    tick_p99_bound_s: float = 1.5
    global_tick_ms: int = 33
    config_path: str = os.path.join(REPO, "config", "spatial_tpu_cells_2x2.json")
    scenario: dict = field(default_factory=dict)
    out_path: str = ""
    entity_capacity: int = 256
    query_capacity: int = 32


def default_scenario(p: DensitySoakParams) -> dict:
    """Ambient chaos weather only — mild stalls; the deliberate fault is
    the density pileup (and, in the acceptance soak, the owner kill)."""
    return {
        "name": "density-weather",
        "seed": 20260807,
        "config_overrides": {"CellBucket": 8},
        "faults": [
            {"point": "device.dispatch_stall", "every_n": 40,
             "stall_ms": 20, "max_fires": 50},
        ],
    }


async def run_density_soak(p: DensitySoakParams) -> dict:
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
    from channeld_tpu.core.channel import all_channels, get_channel, init_channels
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
    from channeld_tpu.spatial.partition import partition, reset_partition
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
    reset_partition()

    global_settings.development = True
    # Flight recorder / device guard / SLO plane pinned OFF for the same
    # reasons as scripts/balance_soak.py: this soak proves deterministic
    # geometry accounting and a timing envelope; each of those planes
    # has its own soak.
    global_settings.trace_enabled = False
    global_settings.device_guard_enabled = False
    global_settings.slo_enabled = False
    # Simulation plane pinned OFF (doc/simulation.md): an agent
    # population would add its own crossings/census traffic to this
    # soak's deterministic accounting; scripts/sim_soak.py is the sim
    # plane's own soak.
    global_settings.sim_enabled = False
    from channeld_tpu.core.tracing import recorder as _flight_recorder

    _flight_recorder.configure(enabled=False)
    global_settings.tpu_entity_capacity = p.entity_capacity
    global_settings.tpu_query_capacity = p.query_capacity
    # Overload ladder pinned at L0: its L2+ veto of geometry ops is
    # unit-tested (tests/test_partitioning.py); here a boot-time jit
    # stall must not mask the splits under test.
    global_settings.overload_enabled = False
    global_settings.server_conn_recoverable = True
    global_settings.server_conn_recover_timeout_ms = int(
        p.recover_window_s * 1000
    )
    global_settings.failover_enabled = True
    # Federation stays pinned OFF: single-gateway deterministic
    # accounting (geometry anti-entropy has its own unit tests).
    reset_federation()
    global_settings.federation_config = ""

    # The plane under test: the density governor...
    global_settings.partition_enabled = True
    global_settings.partition_split_entities = p.split_entities
    global_settings.partition_merge_entities = p.merge_entities
    global_settings.partition_max_depth = p.max_depth
    global_settings.partition_eval_ticks = p.eval_ticks
    global_settings.partition_hold_ticks = p.hold_ticks
    global_settings.partition_epoch_ticks = p.epoch_ticks
    global_settings.partition_budget_per_epoch = p.budget_per_epoch
    global_settings.partition_cooldown_ticks = p.cooldown_ticks
    global_settings.partition_freeze_min_ticks = p.freeze_min_ticks
    global_settings.partition_drain_deadline_ticks = p.drain_deadline_ticks
    # ...and the balancer that places the granules splits create (the
    # two planes share the crossing freeze; their mutual exclusion is
    # part of what this soak exercises).
    global_settings.balancer_enabled = True
    global_settings.balancer_imbalance_enter = p.imbalance_enter
    global_settings.balancer_imbalance_exit = p.imbalance_exit
    global_settings.balancer_hold_ticks = p.hold_ticks
    global_settings.balancer_epoch_ticks = p.balancer_epoch_ticks
    global_settings.balancer_budget_per_epoch = p.balancer_budget_per_epoch
    global_settings.balancer_cooldown_ticks = p.balancer_cooldown_ticks
    global_settings.balancer_min_entity_delta = p.balancer_min_entity_delta
    global_settings.balancer_freeze_min_ticks = p.balancer_freeze_min_ticks
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
        "/tmp", f"density_soak_spatial_{os.getpid()}.json"
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

    def density_ratio(loads: dict[int, int]) -> float:
        """Per-server max/mean entity load — the same fold the balance
        soak bounds at 1.31 on the fixed grid."""
        if not loads:
            return 0.0
        mean = sum(loads.values()) / len(loads)
        return (max(loads.values()) / mean) if mean > 0 else 0.0

    def max_leaf_depth() -> int:
        tree = ctl.tree
        return max((tree.depth_of(c) for c in tree.leaves()), default=0)

    def split_commits() -> int:
        return partition.ledger.get("split_committed", 0)

    def geometry_busy() -> bool:
        return (partition.op_in_flight() is not None
                or balancer.migration_in_flight() is not None)

    timeline: list[dict] = []
    fault_log: list[str] = []

    async def _poller():
        while not stop.is_set():
            loads = server_entity_loads()
            op = partition.op_in_flight()
            timeline.append({
                "t": round(time.monotonic() - t_start, 2),
                "server_entities": dict(sorted(loads.items())),
                "density_ratio": round(density_ratio(loads), 3),
                "geometry_epoch": ctl.tree.epoch,
                "splits": len(ctl.tree.splits),
                "max_depth": max_leaf_depth(),
                "split_committed": split_commits(),
                "merge_committed": partition.ledger.get("merge_committed", 0),
                "migrations_committed": balancer.ledger.get("committed", 0),
                "in_flight": (
                    f"{op.op}:{op.target}" if op is not None else None
                ),
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

        rng = Random(p.scenario.get("seed", 0) ^ 0xDE45)
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

        # ---- one-cell herding helpers --------------------------------
        def cell_bounds(col: int, row: int):
            x0 = ctl.world_offset_x + col * ctl.grid_width + 1.0
            z0 = ctl.world_offset_z + row * ctl.grid_height + 1.0
            return (x0, z0,
                    x0 + ctl.grid_width - 2.0, z0 + ctl.grid_height - 2.0)

        def herd_cell(col: int, row: int) -> None:
            x0, z0, x1, z1 = cell_bounds(col, row)
            for eid in sim.entity_ids:
                sim._move(eid, rng.uniform(x0, x1), rng.uniform(z0, z1))

        def cell_jitter(col: int, row: int) -> None:
            x0, z0, x1, z1 = cell_bounds(col, row)
            for eid in rng.sample(sim.entity_ids,
                                  max(1, len(sim.entity_ids) // 8)):
                x, z = sim.positions[eid]
                x = min(max(x + rng.uniform(-6, 6), x0), x1)
                z = min(max(z + rng.uniform(-6, 6), z0), z1)
                sim._move(eid, x, z)

        # -- warmup: uniform world, hot paths, no geometry ops expected --
        warm_until = time.monotonic() + p.warmup_s
        while time.monotonic() < warm_until:
            sim.jitter_step()
            await asyncio.sleep(0.1)
        committed_at_warmup = split_commits()
        epoch_at_warmup = ctl.tree.epoch

        # -- the pileup: everyone into cell (1, 1) — interior to one
        # server's quadrant, denser than the split threshold. Adaptive
        # phase length: at least pileup_s, then up to 2.5x while the
        # governor/balancer pipeline is still flattening (a slow CI box
        # pays wall clock instead of flaking the steady-state check).
        herd_cell(1, 1)
        pile_min = time.monotonic() + p.pileup_s
        pile_cap = time.monotonic() + p.pileup_s * 2.5
        while time.monotonic() < pile_min or (
            time.monotonic() < pile_cap
            and (split_commits() == 0
                 or density_ratio(server_entity_loads()) > p.density_ratio_bound
                 or geometry_busy())
        ):
            cell_jitter(1, 1)
            await asyncio.sleep(0.1)
        pileup_splits = split_commits()

        # Steady state after the split + granule migrations settled.
        settle_until = time.monotonic() + 3.0
        while time.monotonic() < settle_until and geometry_busy():
            await asyncio.sleep(0.1)
        steady_loads = server_entity_loads()
        steady_ratio = density_ratio(steady_loads)
        steady_depth = max_leaf_depth()
        steady_epoch = ctl.tree.epoch

        # -- kill-mid-split phase (acceptance soak) --
        kill_rec = None
        if p.kill_mid_split:
            global_settings.partition_freeze_min_ticks = (
                p.kill_freeze_min_ticks
            )
            sim.disperse(list(sim.entity_ids))
            await asyncio.sleep(1.0)
            herd_cell(2, 2)
            commits_before_kill = split_commits()
            kill_until = time.monotonic() + p.kill_phase_s
            while time.monotonic() < kill_until:
                cell_jitter(2, 2)
                op = partition.op_in_flight()
                if (kill_rec is None and op is not None
                        and op.op == "split" and op.state == "draining"):
                    target_ch = get_channel(op.target)
                    owner = (target_ch.get_owner()
                             if target_ch is not None else None)
                    pit = getattr(owner, "pit", "") if owner else ""
                    idx = None
                    if pit.startswith("soak-spatial-"):
                        idx = int(pit.rsplit("-", 1)[1])
                    if idx is not None and idx < len(spatial_socks):
                        epoch_before = ctl.tree.epoch
                        # The split is inside its freeze/drain window:
                        # abort the OWNING server's socket now.
                        spatial_socks[idx][1].transport.abort()
                        t_kill = time.monotonic()
                        while (partition.op_in_flight() is op
                               and time.monotonic() < t_kill + 8.0):
                            await asyncio.sleep(0.05)
                        abort_ev = next(
                            (e for e in reversed(partition.events)
                             if e["op_id"] == op.op_id),
                            None,
                        )
                        kill_rec = {
                            "owner_pit": pit,
                            "cell": op.target,
                            "t": round(t_kill - t_start, 2),
                            "resolved_in_s": round(
                                time.monotonic() - t_kill, 2),
                            "aborted": bool(
                                abort_ev is not None
                                and abort_ev["result"] == "aborted"
                            ),
                            "reason": (
                                abort_ev["reason"] if abort_ev else None
                            ),
                            # Deterministic rollback: nothing mutates
                            # before the WAL commit point, so the abort
                            # leaves the geometry epoch untouched.
                            "epoch_unchanged_by_abort": bool(
                                abort_ev is not None
                                and abort_ev["epoch"] == epoch_before
                            ),
                        }
                    else:
                        fault_log.append(
                            f"kill skipped: owner {pit!r} unmapped")
                await asyncio.sleep(0.05)
            if kill_rec is None:
                fault_log.append("no split observed in kill phase")
            else:
                # Failover re-hosts the dead server's cells; the
                # re-planned split must commit on the new owner.
                kill_rec["recommitted_after_failover"] = (
                    split_commits() > commits_before_kill
                )
            global_settings.partition_freeze_min_ticks = p.freeze_min_ticks

        # -- disperse: the crowd leaves; cold sibling groups consolidate
        # authority and merge until the boot geometry is restored.
        sim.disperse(list(sim.entity_ids))
        disp_min = time.monotonic() + p.disperse_s
        disp_cap = time.monotonic() + p.disperse_s * 3.0
        while time.monotonic() < disp_min or (
            time.monotonic() < disp_cap
            and (ctl.tree.splits or geometry_busy())
        ):
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
        preport = partition.report()
        events = preport["events"]
        commits = [e for e in events if e["result"] == "committed"]
        ledger = dict(partition.ledger)

        # 1. The balanced warmup produced no geometry op; the pileup
        #    produced at least one committed live split.
        inv.expect_equal("no_geometry_op_while_uniform",
                         (committed_at_warmup, epoch_at_warmup), (0, 0))
        inv.expect_gt("pileup_split_committed", pileup_splits, 0)
        inv.expect_gt("steady_geometry_epoch_advanced", steady_epoch, 0)

        # 2. Steady-state per-server load flattened BELOW the fixed-grid
        #    floor the balance soak could only meet (the whole point:
        #    splits give the balancer granules a fixed grid denies it).
        inv.expect_le("steady_density_ratio_below_fixed_grid_floor",
                      steady_ratio, p.density_ratio_bound,
                      f"loads={steady_loads} depth={steady_depth}")
        inv.expect_gt("steady_split_depth_live", steady_depth, 0)

        # 3. Exact geometry accounting: metric == python ledger per
        #    (op, result); planned == committed + aborted per op;
        #    nothing in flight; no freeze left behind.
        metric_results = {}
        for (name, labels), value in d.items():
            if name == "partition_ops_total" and value:
                lab = dict(labels)
                metric_results[f"{lab['op']}_{lab['result']}"] = int(value)
        inv.expect_equal("partition_metric_matches_ledger",
                         metric_results, ledger)
        for op_name in ("split", "merge"):
            inv.expect_equal(
                f"{op_name}s_planned_equals_committed_plus_aborted",
                ledger.get(f"{op_name}_planned", 0),
                ledger.get(f"{op_name}_committed", 0)
                + ledger.get(f"{op_name}_aborted", 0),
                f"ledger={ledger}",
            )
        inv.expect_equal("no_geometry_op_left_in_flight",
                         partition.op_in_flight(), None)
        inv.expect_equal("no_migration_left_in_flight",
                         balancer.migration_in_flight(), None)
        inv.expect_equal("no_frozen_crossing_left_behind",
                         (sorted(balancer.frozen_cells),
                          len(balancer._frozen_crossings)),
                         ([], 0))

        # 4. Governor discipline: per-epoch commits within budget; no
        #    cell re-operated within its post-commit cooldown.
        per_epoch: dict[int, int] = {}
        for e in commits:
            per_epoch[e["governor_epoch"]] = (
                per_epoch.get(e["governor_epoch"], 0) + 1
            )
        over_budget = {ep: n for ep, n in per_epoch.items()
                       if n > p.budget_per_epoch}
        inv.expect_equal("per_epoch_commits_within_budget", over_budget, {},
                         f"per_epoch={per_epoch}")
        flaps = []
        by_cell: dict[int, list] = {}
        for e in commits:
            by_cell.setdefault(e["target"], []).append(e["resolved_tick"])
        for cell, ticks in by_cell.items():
            ticks.sort()
            for a, b in zip(ticks, ticks[1:]):
                if b - a < p.cooldown_ticks:
                    flaps.append((cell, a, b))
        inv.expect_equal("no_cell_reops_within_cooldown", flaps, [])

        # 5. The injected kill aborted deterministically; the re-planned
        #    split committed once failover re-hosted the dead server.
        if p.kill_mid_split:
            inv.check("kill_mid_split_landed", kill_rec is not None,
                      str(fault_log))
            if kill_rec is not None:
                inv.check("kill_mid_split_aborts_deterministically",
                          kill_rec["aborted"]
                          and kill_rec["epoch_unchanged_by_abort"],
                          str(kill_rec))
                inv.check("split_recommits_after_failover",
                          kill_rec["recommitted_after_failover"],
                          str(kill_rec))

        # 6. Cold merge restored the boot geometry.
        inv.expect_gt("merges_committed",
                      ledger.get("merge_committed", 0), 0)
        inv.expect_equal("geometry_restored_after_disperse",
                         sorted(ctl.tree.splits), [],
                         f"epoch={ctl.tree.epoch}")

        # 7. Device micro-grid rebuilds: every depth-changing epoch
        #    rebuilt the device arrays and verified them bit-identical
        #    against the host shadow; zero mismatches ever.
        rebuilds_ok = int(sample_total(
            d, "partition_device_rebuilds_total", result="verified"))
        rebuilds_bad = int(sample_total(
            d, "partition_device_rebuilds_total", result="mismatch"))
        inv.expect_gt("device_rebuilds_verified", rebuilds_ok, 1)
        inv.expect_equal("device_rebuilds_zero_mismatch", rebuilds_bad, 0)

        # 8. Zero entity loss; exactly-once placement; journal balances.
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

        # 9. Tick p99 bounded throughout.
        p99 = histogram_quantile(
            d, "channel_tick_duration", 0.99, channel_type="GLOBAL")
        inv.expect_le("global_tick_p99_bounded", p99, p.tick_p99_bound_s)

        report = {
            "kind": "density_soak",
            "config": os.path.basename(p.config_path),
            "config_overrides": overrides,
            "duration_s": round(time.monotonic() - t_start, 2),
            "phases": {
                "warmup_s": p.warmup_s,
                "pileup_s": p.pileup_s,
                "kill_phase_s": p.kill_phase_s if p.kill_mid_split else 0,
                "disperse_s": p.disperse_s,
                "quiesce_s": p.quiesce_s,
            },
            "clients": p.clients,
            "entities": p.entities,
            "partition_knobs": {
                "split_entities": p.split_entities,
                "merge_entities": p.merge_entities,
                "max_depth": p.max_depth,
                "eval_ticks": p.eval_ticks,
                "hold_ticks": p.hold_ticks,
                "epoch_ticks": p.epoch_ticks,
                "budget_per_epoch": p.budget_per_epoch,
                "cooldown_ticks": p.cooldown_ticks,
                "freeze_min_ticks": p.freeze_min_ticks,
            },
            "scenario": p.scenario,
            "partition": preport,
            "balancer": balancer.report(),
            "kill": kill_rec,
            "steady_state": {
                "server_entities": {
                    str(k): v for k, v in sorted(steady_loads.items())
                },
                "density_ratio": round(steady_ratio, 3),
                "max_depth": steady_depth,
                "geometry_epoch": steady_epoch,
            },
            "final_geometry": {
                "epoch": ctl.tree.epoch,
                "splits": sorted(ctl.tree.splits),
            },
            "device_rebuilds": {
                "verified": rebuilds_ok,
                "mismatch": rebuilds_bad,
            },
            "failover": plane.report(),
            "journal": journal.report(),
            "timeline": timeline,
            "chaos": chaos_report,
            "invariants": inv.summary(),
            "stats": {
                "client_frames_sent": sum(stats.client_sent.values()),
                "splits_committed": ledger.get("split_committed", 0),
                "splits_aborted": ledger.get("split_aborted", 0),
                "splits_vetoed": ledger.get("split_vetoed", 0),
                "merges_committed": ledger.get("merge_committed", 0),
                "migrations_committed": balancer.ledger.get("committed", 0),
                "entities_repartitioned": sum(
                    e["moved"] for e in commits
                ),
                "handovers_total": int(sample_total(d, "handovers_total")),
                "steady_density_ratio": round(steady_ratio, 3),
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
        reset_partition()
        try:
            os.remove(merged_path)
        except OSError:
            pass


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--warmup", type=float, default=6.0)
    ap.add_argument("--pileup", type=float, default=20.0)
    ap.add_argument("--disperse", type=float, default=12.0)
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--entities", type=int, default=128)
    ap.add_argument("--rate", type=float, default=20.0)
    ap.add_argument("--no-kill", action="store_true",
                    help="skip the kill-mid-split phase")
    ap.add_argument("--scenario", type=str, default="",
                    help="scenario JSON path (default: built-in weather)")
    ap.add_argument("--out", type=str, default="")
    args = ap.parse_args()
    p = DensitySoakParams(
        warmup_s=args.warmup, pileup_s=args.pileup,
        disperse_s=args.disperse, clients=args.clients,
        entities=args.entities, msg_rate=args.rate,
        kill_mid_split=not args.no_kill, out_path=args.out,
    )
    if args.scenario:
        with open(args.scenario) as f:
            p.scenario = json.load(f)
    report = asyncio.run(run_density_soak(p))
    slim = dict(report)
    slim["timeline"] = f"<{len(report['timeline'])} samples>"
    print(json.dumps(slim, indent=2))
    if not report["invariants"]["ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
