"""Failover soak: kill a spatial server for good, prove cell re-hosting.

Boots the same live gateway as ``scripts/chaos_soak.py`` (real TCP
listeners, the 1ms pump, the TPU spatial controller on the cells plane,
a master + 4 spatial servers, a client fleet, a seeded entity sim) with
recoverable server connections and a short recovery window, then drives
the failure the recovery subsystem alone cannot absorb — a dedicated
server that never comes back:

1. **warmup** — traffic + a storm so every handover path is hot.
2. **kill #1, mid-handover burst** — a storm marches a crowd across
   cell boundaries and, while that burst is orchestrating, one spatial
   server's socket is aborted. Its connection becomes a recovery handle;
   the window expires with no return; ``ServerLostEvent`` fires and the
   failover plane re-hosts its cells onto the surviving servers
   (doc/failover.md). While the cells are ownerless, a prober client
   streams forwards at one of them — every one must be counted in
   ``ownerless_drops_total``, never silently swallowed.
3. **kill #2, during the failover epoch** (acceptance soak only) — as
   soon as the first ``ServerLostEvent`` is observed, a second storm
   fires and a second server (possibly already carrying re-hosted
   cells) is killed the same way. Failover must resolve the compound
   loss: every cell, including the just-re-hosted ones, lands on one of
   the two remaining servers.
4. **aftermath** — storms and jitter continue on the shrunken fleet:
   handovers must keep orchestrating against the new owners.

The invariant checker then asserts the PR's acceptance bar:

- one ``ServerLostEvent`` (and one ``server_lost_total`` increment) per
  kill — never zero, never duplicated;
- 100% of orphaned cells re-hosted, each loss resolved within
  ``recover_window + rehost_deadline`` of the kill, and the failover
  pass itself under the deadline;
- exact re-host accounting: ``failover_rehost_total`` == the plane's
  python ledger == the orphan-cell count across events;
- the handover journal balances exactly: prepared == committed +
  aborted with nothing left in flight (every entity resolved to exactly
  one owning cell), metric and python ledger agreeing;
- zero entity loss: every sim entity still tracked and present in
  exactly one spatial channel's data; every entity channel has a live
  owner after failover;
- exact ownerless-drop accounting: probe frames sent minus probe frames
  drained by any server == ``ownerless_drops_total`` delta;
- GLOBAL tick p99 bounded throughout AND across the post-failover
  phase alone;
- handovers orchestrated after the last re-host (the world keeps
  moving).

Emits a ``SOAK_FAILOVER_*.json`` artifact with the kill/re-host
timeline, the failover and journal ledgers, and the invariant results.

Run the acceptance soak (~75s of timeline):
  python scripts/failover_soak.py --out SOAK_FAILOVER_r08.json

The <60s CI smoke runs the same machinery with smaller numbers
(tests/test_failover.py::test_failover_smoke_soak).
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
import struct
import time
from dataclasses import dataclass, field
from random import Random


def _load_chaos_soak():
    """The chaos soak module provides the world-boot / client / sim
    machinery this soak re-drives around permanent server loss."""
    spec = importlib.util.spec_from_file_location(
        "chaos_soak", os.path.join(REPO, "scripts", "chaos_soak.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("chaos_soak", mod)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class FailoverSoakParams:
    warmup_s: float = 8.0
    aftermath_s: float = 12.0
    quiesce_s: float = 8.0
    clients: int = 12
    entities: int = 128
    msg_rate: float = 20.0
    storm_size: int = 48
    kills: int = 2  # 1-of-N spatial servers, then one more mid-failover
    # Recovery window the dead server is given to come back (it won't).
    recover_window_s: float = 1.5
    # Bound on one failover pass AND on kill -> all-cells-owned (the
    # latter additionally allows the recovery window + a settle margin).
    rehost_deadline_s: float = 3.0
    settle_margin_s: float = 3.0
    # Probe frames aimed at an orphaned cell while it is ownerless.
    probe_frames: int = 20
    tick_p99_bound_s: float = 1.5
    global_tick_ms: int = 33
    config_path: str = os.path.join(REPO, "config", "spatial_tpu_cells_2x2.json")
    scenario: dict = field(default_factory=dict)
    out_path: str = ""
    entity_capacity: int = 256
    query_capacity: int = 32


def default_scenario(p: FailoverSoakParams) -> dict:
    """Ambient chaos weather only — stalls, no transport faults: the
    transport-level fault IS the deliberate server kill, and the
    exact-drop accounting needs client frames to actually reach the
    gateway."""
    return {
        "name": "failover-weather",
        "seed": 20260803,
        "config_overrides": {"CellBucket": 6},
        "faults": [
            {"point": "device.dispatch_stall", "every_n": 25,
             "stall_ms": 30, "max_fires": 60},
            {"point": "channel.tick_budget", "every_n": 400,
             "stall_ms": 10, "max_fires": 40},
        ],
    }


async def run_failover_soak(p: FailoverSoakParams) -> dict:
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

    global_settings.development = True
    global_settings.tpu_entity_capacity = p.entity_capacity
    global_settings.tpu_query_capacity = p.query_capacity
    # This soak proves the FAILOVER plane; the overload ladder stays
    # pinned at L0 so boot-time jit stalls can't push the gateway into
    # L3 admission control and refuse the soak's own client fleet (the
    # overload soak owns that interplay).
    global_settings.overload_enabled = False
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
    # ... and the balancer stays off for the same reason: this soak's
    # re-host accounting must see only CRASH-path authority moves
    # (scripts/balance_soak.py proves the planned-migration path).
    global_settings.balancer_enabled = False
    # Adaptive partitioning stays pinned OFF: this soak's envelope
    # assumes the static boot grid (doc/partitioning.md);
    # scripts/density_soak.py is the partitioning plane's own soak.
    global_settings.partition_enabled = False
    # Federation stays pinned OFF: a remote shard would route some
    # crossings over a trunk and break this soak's deterministic
    # single-gateway accounting (doc/federation.md).
    reset_federation()
    global_settings.federation_config = ""
    global_settings.server_conn_recoverable = True
    global_settings.server_conn_recover_timeout_ms = int(
        p.recover_window_s * 1000
    )
    global_settings.failover_enabled = True
    global_settings.failover_rehost_deadline_s = p.rehost_deadline_s
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
        "/tmp", f"failover_soak_spatial_{os.getpid()}.json"
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

    def all_cells_owned() -> bool:
        cells = spatial_channels()
        return len(cells) == 16 and all(ch.has_owner() for ch in cells.values())

    # Probe-forward accounting: every spatial-server drain counts probe
    # frames (payload prefix b"orfn") it receives; what was sent minus
    # what any server drained must equal the ownerless-drop counter.
    probe = {"sent": 0, "received": 0}

    def _probe_drain(mp) -> None:
        if mp.msgType < 100:
            return
        from channeld_tpu.protocol import wire_pb2

        sfm = wire_pb2.ServerForwardMessage()
        try:
            sfm.ParseFromString(mp.msgBody)
        except Exception:
            return
        if sfm.payload.startswith(b"orfn"):
            probe["received"] += 1

    async def _probe_orphan_cell(cell_id: int, until: float) -> None:
        """Stream forwards at an ownerless cell until ``until``; counts
        every frame sent (the gateway must count every drop). Retries
        through connect/auth hiccups — the accounting only covers frames
        that actually went out."""
        n = 0
        while time.monotonic() < until and n < p.probe_frames:
            writer = None
            try:
                reader, writer = await cs._connect(host, client_port)
                await cs._auth_and_wait(
                    reader, writer, f"orphan-prober-{cell_id}")
                reader_task = asyncio.ensure_future(
                    cs._read_frames(reader, lambda mp: None, stop))
                while time.monotonic() < until and n < p.probe_frames:
                    writer.write(cs._frame(
                        100, b"orfn" + struct.pack("<I", n),
                        channel_id=cell_id))
                    await writer.drain()
                    probe["sent"] += 1
                    n += 1
                    await asyncio.sleep(0.02)
                reader_task.cancel()
            except (ConnectionError, OSError, TimeoutError) as e:
                fault_log.append(f"orphan prober retry: {e!r}")
                await asyncio.sleep(0.05)
            finally:
                if writer is not None:
                    try:
                        writer.close()
                    except Exception:
                        pass

    timeline: list[dict] = []
    kills: list[dict] = []

    async def _poller():
        while not stop.is_set():
            timeline.append({
                "t": round(time.monotonic() - t_start, 2),
                "cells_owned": sum(
                    1 for ch in spatial_channels().values() if ch.has_owner()
                ),
                "servers_lost": plane.ledger["servers_lost"],
                "cells_rehosted": plane.ledger["cells_rehosted"],
            })
            await asyncio.sleep(0.25)

    fault_log: list[str] = []
    try:
        (m_reader, m_writer, drain_task), spatial_socks = await cs._boot_world(
            host, server_port, stats, stop
        )
        tasks.append(drain_task)
        control_writers.append(m_writer)
        # Re-wrap each spatial server's drain with the probe counter.
        live_socks = []
        for r, w, task in spatial_socks:
            task.cancel()
            new_task = asyncio.ensure_future(
                cs._read_frames(r, _probe_drain, stop))
            tasks.append(new_task)
            control_writers.append(w)
            live_socks.append((r, w, new_task))

        rng = Random(p.scenario.get("seed", 0) ^ 0xFA11)
        sim_params = cs.SoakParams(
            entities=p.entities, storm_size=p.storm_size)
        sim = cs.EntitySim(ctl, sim_params, rng)
        sim.create_entities()

        for idx in range(p.clients):
            tasks.append(asyncio.ensure_future(cs._client_loop(
                idx, host, client_port, p.msg_rate, stats, stop, send_stop,
            )))

        baseline = scrape()
        arm(p.scenario)
        tasks.append(asyncio.ensure_future(_poller()))

        # -- warmup: hot handover paths before anything dies --
        warm_until = time.monotonic() + p.warmup_s
        crowd = sim.storm_gather()
        while time.monotonic() < warm_until:
            sim.jitter_step()
            await asyncio.sleep(0.1)
        sim.disperse(crowd)

        # -- the kills --
        def _find_server_conn(pit: str):
            for conn in connection_mod.all_connections().values():
                if conn.pit == pit and not conn.is_closing():
                    return conn
            return None

        async def _kill(index: int, label: str) -> dict:
            victim_pit = f"soak-spatial-{index}"
            conn = _find_server_conn(victim_pit)
            if conn is None:
                raise RuntimeError(f"victim {victim_pit} not found/alive")
            owned = sorted(
                cid for cid, ch in spatial_channels().items()
                if ch.get_owner() is conn
            )
            # Mid-handover burst: march a crowd NOW, then abort the
            # socket while those crossings orchestrate.
            sim.storm_gather()
            await asyncio.sleep(0.15)
            t_kill = time.monotonic()
            r, w, _task = live_socks[index]
            w.transport.abort()
            rec = {
                "label": label,
                "pit": victim_pit,
                "conn_id": conn.id,
                "t": round(t_kill - t_start, 2),
                "owned_cells": owned,
            }
            # The abort lands on the next loop turn: wait until the
            # cells are genuinely orphaned before timing the re-host.
            orphan_deadline = t_kill + 2.0
            while time.monotonic() < orphan_deadline and all_cells_owned():
                await asyncio.sleep(0.02)
            rec["orphaned"] = not all_cells_owned()
            # Probe an orphaned cell through the whole ownerless window
            # (stops itself at probe_frames or the window's end).
            if owned:
                until = t_kill + p.recover_window_s - 0.2
                tasks.append(asyncio.ensure_future(
                    _probe_orphan_cell(owned[0], until)))
            # Wait out the window + failover: every cell owned again.
            deadline = (t_kill + p.recover_window_s + p.rehost_deadline_s
                        + p.settle_margin_s)
            while time.monotonic() < deadline:
                sim.jitter_step()
                if all_cells_owned():
                    break
                await asyncio.sleep(0.1)
            rec["rehosted_in_s"] = (
                round(time.monotonic() - t_kill, 2)
                if all_cells_owned() else None
            )
            return rec

        kills.append(await _kill(1, "kill-1-mid-handover-burst"))
        if p.kills > 1:
            # The second kill lands inside the first failover EPOCH: the
            # fleet is still resyncing, re-offered handovers are still
            # draining, and the victim may carry just-re-hosted cells.
            kills.append(await _kill(2, "kill-2-during-failover"))

        rehost_done_at = time.monotonic()
        after_rehost = scrape()

        # -- aftermath: the shrunken fleet keeps serving handovers --
        aft_until = time.monotonic() + p.aftermath_s
        crowd = []
        storm_at = time.monotonic() + 1.0
        while time.monotonic() < aft_until:
            sim.jitter_step()
            if time.monotonic() >= storm_at:
                if crowd:
                    sim.disperse(crowd)
                    crowd = []
                if time.monotonic() < aft_until - 5.0:
                    crowd = sim.storm_gather()
                storm_at += 4.0
            await asyncio.sleep(0.1)
        if crowd:
            sim.disperse(crowd)

        send_stop.set()
        chaos_report = chaos.report()
        disarm()
        await asyncio.sleep(p.quiesce_s)

        # -- invariants --
        inv = InvariantChecker()
        now_samples = scrape()
        d = delta(now_samples, baseline)
        d_post = delta(now_samples, after_rehost)
        freport = plane.report()

        # 1. One ServerLostEvent per kill, metric == ledger.
        inv.expect_equal("one_server_lost_event_per_kill",
                         plane.ledger["servers_lost"], len(kills))
        inv.expect_equal("server_lost_metric_matches_ledger",
                         int(sample_total(d, "server_lost_total")),
                         plane.ledger["servers_lost"])

        # 2. Every orphaned cell re-hosted, inside the deadline.
        inv.check("all_cells_owned_after_failover", all_cells_owned(),
                  f"{sum(1 for ch in spatial_channels().values() if ch.has_owner())}/16")
        orphans_seen = sum(len(e["orphan_cells"]) for e in freport["events"])
        rehosts_seen = sum(len(e["rehosted"]) for e in freport["events"])
        inv.expect_equal("every_orphan_cell_rehosted",
                         rehosts_seen, orphans_seen)
        worst_pass_ms = max(
            (e["duration_ms"] for e in freport["events"]), default=0.0)
        inv.expect_le("failover_pass_under_deadline",
                      worst_pass_ms / 1000.0, p.rehost_deadline_s)
        inv.expect_equal("every_kill_orphaned_cells",
                         [k["label"] for k in kills if not k["orphaned"]],
                         [])
        slow = [k for k in kills if k["rehosted_in_s"] is None
                or k["rehosted_in_s"] > p.recover_window_s
                + p.rehost_deadline_s + p.settle_margin_s]
        inv.expect_equal("rehost_within_window_plus_deadline", slow, [],
                         f"kills={[(k['label'], k['rehosted_in_s']) for k in kills]}")

        # 3. Exact re-host accounting (metric == ledger == events).
        inv.expect_equal(
            "rehost_accounting_exact",
            (int(sample_total(d, "failover_rehost_total")),
             plane.ledger["cells_rehosted"]),
            (rehosts_seen, rehosts_seen),
        )

        # 4. Journal balances exactly; nothing left in flight.
        jc = dict(journal.counts)
        metric_jc = {}
        for (name, labels), value in d.items():
            if name == "handover_journal_total" and value:
                metric_jc[dict(labels)["state"]] = int(value)
        inv.expect_equal("journal_metric_matches_ledger", metric_jc, jc)
        inv.expect_equal(
            "journal_prepared_equals_committed_plus_aborted",
            jc.get("prepared", 0),
            jc.get("committed", 0) + jc.get("aborted", 0),
            f"counts={jc}",
        )
        inv.expect_equal("journal_nothing_in_flight",
                         journal.in_flight_count(), 0)

        # 5. Zero entity loss; exactly-once placement; live authority.
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
        inv.expect_equal("every_entity_in_exactly_one_cell",
                         (missing, duped), ([], []))
        from channeld_tpu.core.channel import get_channel

        ownerless_entities = [
            eid for eid in sim.entity_ids
            if (ech := get_channel(eid)) is not None
            and not ech.is_removing() and not ech.has_owner()
        ]
        inv.expect_equal("every_entity_channel_has_live_owner",
                         ownerless_entities, [])

        # 6. Exact ownerless-drop accounting: sent - forwarded == counted.
        drops = int(sample_total(d, "ownerless_drops_total"))
        expected_drops = probe["sent"] - probe["received"]
        inv.expect_equal("ownerless_drops_exact", drops, expected_drops,
                         f"sent={probe['sent']} received={probe['received']}")
        inv.expect_gt("ownerless_window_probed", probe["sent"], 0)

        # 7. Tick p99 bounded throughout AND post-failover alone.
        p99 = histogram_quantile(
            d, "channel_tick_duration", 0.99, channel_type="GLOBAL")
        inv.expect_le("global_tick_p99_bounded", p99, p.tick_p99_bound_s)
        p99_post = histogram_quantile(
            d_post, "channel_tick_duration", 0.99, channel_type="GLOBAL")
        inv.expect_le("post_failover_tick_p99_bounded",
                      p99_post, p.tick_p99_bound_s)

        # 8. The world keeps moving on the shrunken fleet.
        handovers_post = sample_total(d_post, "handovers_total")
        inv.expect_gt("handovers_after_failover", handovers_post, 0)

        report = {
            "kind": "failover_soak",
            "config": os.path.basename(p.config_path),
            "config_overrides": overrides,
            "duration_s": round(time.monotonic() - t_start, 2),
            "phases": {
                "warmup_s": p.warmup_s,
                "recover_window_s": p.recover_window_s,
                "rehost_deadline_s": p.rehost_deadline_s,
                "aftermath_s": p.aftermath_s,
                "quiesce_s": p.quiesce_s,
            },
            "clients": p.clients,
            "entities": p.entities,
            "scenario": p.scenario,
            "kills": kills,
            "failover": freport,
            "journal": journal.report(),
            "timeline": timeline,
            "chaos": chaos_report,
            "invariants": inv.summary(),
            "stats": {
                "client_frames_sent": sum(stats.client_sent.values()),
                "probe_frames_sent": probe["sent"],
                "probe_frames_forwarded": probe["received"],
                "ownerless_drops": drops,
                "cells_rehosted": plane.ledger["cells_rehosted"],
                "entities_repointed": plane.ledger["entities_repointed"],
                "handovers_total": int(sample_total(d, "handovers_total")),
                "handovers_after_failover": int(handovers_post),
                "global_tick_p99_s": p99,
                "post_failover_tick_p99_s": p99_post,
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
        try:
            os.remove(merged_path)
        except OSError:
            pass


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--warmup", type=float, default=8.0)
    ap.add_argument("--aftermath", type=float, default=12.0)
    ap.add_argument("--clients", type=int, default=12)
    ap.add_argument("--entities", type=int, default=128)
    ap.add_argument("--rate", type=float, default=20.0)
    ap.add_argument("--kills", type=int, default=2, choices=(1, 2))
    ap.add_argument("--window", type=float, default=1.5,
                    help="recovery window (s) the dead server never uses")
    ap.add_argument("--scenario", type=str, default="",
                    help="scenario JSON path (default: built-in weather)")
    ap.add_argument("--out", type=str, default="")
    args = ap.parse_args()
    p = FailoverSoakParams(
        warmup_s=args.warmup, aftermath_s=args.aftermath,
        clients=args.clients, entities=args.entities, msg_rate=args.rate,
        kills=args.kills, recover_window_s=args.window, out_path=args.out,
    )
    if args.scenario:
        with open(args.scenario) as f:
            p.scenario = json.load(f)
    report = asyncio.run(run_failover_soak(p))
    slim = dict(report)
    slim["timeline"] = f"<{len(report['timeline'])} samples>"
    print(json.dumps(slim, indent=2))
    if not report["invariants"]["ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
